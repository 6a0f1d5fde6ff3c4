"""What the sparse-attention cell's metrics are read from, one function a
source, as ``moe_reduce.py``, ``mla_reduce.py`` and ``eva_reduce.py`` are for
theirs (whose scope shares, kernel seconds, counters and step count these
reuse): device time under a scope of ``fedml_tpu/obs/trace.py``
``DSA_SCOPES``, the attention custom calls' time against
``dsa_costs.attention_cost``, the program's ``dsa/call`` notes, and the
engine's ``dsa/*`` counters. A program without the scopes, the notes or the
counters (the parent of the PR that added this file) gives nothing, and the
reader returns None.
"""

from __future__ import annotations

from benchmark import dsa_costs, kernel_costs, mla_reduce, moe_costs, moe_reduce

BYTES = {"bfloat16": 2, "float32": 4}
SCORES_AND_SELECT = ("attn/dsa/index/scores", "attn/dsa/select")


def dsa_notes() -> list:
    """The program's distinct ``dsa/call`` notes."""
    try:
        from fedml_tpu.obs import trace
        return trace.program_notes("dsa/call")
    except (ImportError, AttributeError):
        return []


def dsa_note(ctx):
    """The ``dsa/call`` note at the cell's training shape, or None."""
    traffic = ctx["cell"]["traffic"]
    notes = [n for n in dsa_notes() if n["shape"][0] == traffic["batch_size"]
             and n["shape"][2] == traffic["seq_len"]]
    return notes[-1] if notes else None


def layer_calls(ctx) -> int:
    """Calls of one kind in the traced rounds: a training step of every layer."""
    return moe_reduce.layer_steps(ctx) * ctx["cell"]["config"]["num_hidden_layers"]


def select_roofline_pct(ctx):
    """Least time of every layer's index scores and selection (made once a
    training step: the chosen set is kept, not recomputed) at stated work,
    over the device time under ``attn/dsa/index/scores`` and
    ``attn/dsa/select``."""
    note = dsa_note(ctx)
    share = sum(mla_reduce.scope_pct(ctx, scope) or 0.0 for scope in SCORES_AND_SELECT)
    if note is None or not share:
        return None
    measured = share / 100.0 * ctx["trace"]["chip0"]["busy_s"]
    b, _, t, _ = note["shape"]
    least = kernel_costs.least_seconds(*dsa_costs.select_cost(
        b, t, note["index_heads"], note["index_dim"], note["selection_bytes"],
        BYTES[note["index_dtype"]]), ctx["peaks"])[0]
    return 100.0 * layer_calls(ctx) * least / measured


def flash_roofline_pct(ctx):
    """Least time of every layer's attention call, forward and backward, over
    the selected pairs, over the attention custom calls' device time."""
    note, measured = dsa_note(ctx), moe_reduce.flash_kernel_seconds(ctx)
    if note is None or not measured:
        return None
    b, h, t, d = note["shape"]
    least = sum(kernel_costs.least_seconds(*dsa_costs.attention_cost(
        b, h, note["kv_heads"], t, d, note["topk"], backward, note["selection_bytes"],
        BYTES[note["dtype"]]), ctx["peaks"])[0] for backward in (False, True))
    return 100.0 * layer_calls(ctx) * least / measured


def experts_roofline_pct(ctx):
    """``moe_reduce.experts_roofline_pct`` under this configuration's keys:
    least time of the held experts' grouped products over the assignments
    counted, forward and backward, over the device time under ``moe/experts``."""
    held, measured = moe_reduce.per_layer("moe/assignments_held"), moe_reduce.scope_seconds(
        ctx, "experts")
    model = ctx["cell"]["config"]
    if not held or not measured or "moe_intermediate_size" not in model:
        return None
    least = sum(kernel_costs.least_seconds(*moe_costs.experts_cost(
        assignments, model["hidden_size"], model["moe_intermediate_size"],
        model["num_local_experts"], backward), ctx["peaks"])[0]
        for assignments in held for backward in (False, True))
    return 100.0 * moe_reduce.layer_steps(ctx) * least / measured


def counter_mean(name: str):
    """Mean over the layers of the engine's ``dsa/<name>/layer_<i>`` counters
    (last round of the traced window), or None."""
    values = moe_reduce.per_layer("dsa/" + name)
    return sum(values) / len(values) if values else None
