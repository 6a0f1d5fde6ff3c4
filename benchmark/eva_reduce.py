"""What the EVA cell's metrics are read from, one function a source, as
``moe_reduce.py`` and ``mla_reduce.py`` are for theirs (whose scope shares,
kernel seconds, counters and step count these reuse): device time under a
scope of ``fedml_tpu/obs/trace.py`` ``EVA_SCOPES``, the flash kernels' time
against ``eva_costs.attention_cost``, the program's ``eva/call`` and
``attn/call`` notes, and the engine's ``eva/remote_mass`` counters. A program
without the scopes, the notes or the counters (the parent of the PR that added
this file) gives nothing, and the reader returns None.
"""

from __future__ import annotations

from benchmark import eva_costs, kernel_costs, mla_reduce, moe_reduce

BYTES = {"bfloat16": 2, "float32": 4}


def eva_notes() -> list:
    """The program's distinct ``eva/call`` notes."""
    try:
        from fedml_tpu.obs import trace
        return trace.program_notes("eva/call")
    except (ImportError, AttributeError):
        return []


def eva_note(ctx):
    """The ``eva/call`` note at the cell's training shape, or None."""
    traffic = ctx["cell"]["traffic"]
    notes = [n for n in eva_notes() if n["shape"][0] == traffic["batch_size"]
             and n["shape"][2] == traffic["seq_len"]]
    return notes[-1] if notes else None


def layer_calls(ctx) -> int:
    """Calls of one kind in the traced rounds: a training step of every layer."""
    return moe_reduce.layer_steps(ctx) * ctx["cell"]["config"]["num_hidden_layers"]


def summary_roofline_pct(ctx):
    """Least time of every layer's summary call, forward and backward, at
    stated traffic, over the device time under ``attn/eva/summary``."""
    note, share = eva_note(ctx), mla_reduce.scope_pct(ctx, "attn/eva/summary")
    if note is None or not note["summaries"] or not share:
        return None
    b, h, t, d = note["shape"]
    least = sum(kernel_costs.least_seconds(*eva_costs.summary_cost(
        b, h, t, d, note["chunk"], backward, BYTES[note["dtype"]]), ctx["peaks"])[0]
        for backward in (False, True))
    measured = share / 100.0 * ctx["trace"]["chip0"]["busy_s"]
    return 100.0 * layer_calls(ctx) * least / measured


def flash_roofline_pct(ctx):
    """Least time of every layer's local and remote flash call, forward and
    backward, by their visible pairs, over the flash kernels' device time."""
    note, measured = eva_note(ctx), moe_reduce.flash_kernel_seconds(ctx)
    if note is None or not measured:
        return None
    b, _, t, _ = note["shape"]
    least = sum(kernel_costs.least_seconds(*cost, ctx["peaks"])[0]
                for backward in (False, True)
                for cost in eva_costs.attention_cost(ctx["cell"]["config"], b, t, backward,
                                                     BYTES[note["dtype"]]) if cost[0])
    return 100.0 * layer_calls(ctx) * least / measured


def remote_tiles_visited_pct(ctx):
    """Score elements in the tiles the remote calls' kernels visit over those
    of their whole ``[T, T / chunk]`` rectangles (``attn/call`` notes of kind
    ``stair`` at the cell's sequence length)."""
    traffic = ctx["cell"]["traffic"]
    notes = [n for n in moe_reduce.attention_notes()
             if n.get("kind") == "stair" and n["shape"][2] == traffic["seq_len"]]
    total = sum(n["tiles_total"] for n in notes)
    return 100.0 * sum(n["tiles_visited"] for n in notes) / total if total else None


def remote_mass_pct():
    """Mean of the layers' ``eva/remote_mass`` counters, in percent."""
    mass = moe_reduce.per_layer("eva/remote_mass")
    return 100.0 * sum(mass) / len(mass) if mass else None
