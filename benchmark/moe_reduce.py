"""What the routed-expert and window-attention metrics are read from, one
function a source, so that each reader under ``layer_metrics/`` is a line or
two and the arithmetic can be checked on a fixture.

- device time by scope: ``scope_reduce.scope_rows`` of the traced run (an
  op's ``op_name`` holds ``moe/route``, ``moe/dispatch``, ``moe/experts`` or
  ``moe/combine``, forward or under jax's ``transpose(``);
- device time by kernel: the rows named ``flash_fwd*``, ``flash_bwd_dkv*``,
  ``flash_bwd_dq*`` (the Mosaic calls' ``name=``);
- the program's counters and trace-time notes: ``fedml_tpu/obs/trace.py``
  keeps the last sample of each counter and each distinct ``attn/call`` note
  past the tracer's life. A program without them (the parent of the PR that
  added this file) gives nothing, and the reader returns None.
"""

from __future__ import annotations

import re

from benchmark import kernel_costs, moe_costs, scope_reduce

MOE_SCOPE = r"(?:^|[/(])moe/(%s)(?=[/)]|$)"
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def _rows(ctx):
    return scope_reduce.scope_rows(scope_reduce.xplane_path(ctx["cell"]["name"]))


def scope_seconds(ctx, scopes: str):
    """Seconds of chip 0's ops under ``moe/<one of scopes>`` (an alternation,
    e.g. "dispatch|combine"); None where no op bears such a scope."""
    pattern = re.compile(MOE_SCOPE % scopes)
    hits = [self_us for per_program in _rows(ctx).values()
            for _, op_name, _, self_us in per_program if pattern.search(op_name)]
    return sum(hits) / 1e6 if hits else None


def scope_pct(ctx, scopes: str):
    seconds, busy = scope_seconds(ctx, scopes), ctx["trace"]["chip0"]["busy_s"]
    return None if seconds is None or not busy else 100.0 * seconds / busy


def flash_kernel_seconds(ctx):
    """Summed self time of the three flash kernels' custom calls; None where
    the trace holds none."""
    hits = [self_us for name, per_program in _rows(ctx).items()
            for _, op_name, category, self_us in per_program
            if name.startswith(FLASH_KERNELS)
            or ("custom-call" in category.lower() and any(k in op_name for k in FLASH_KERNELS))]
    return sum(hits) / 1e6 if hits else None


def counters(prefix: str) -> dict:
    """{name: last value} of the program's counters under ``prefix``."""
    try:
        from fedml_tpu.obs import trace
        return trace.last_counters(prefix)
    except (ImportError, AttributeError):
        return {}


def attention_notes() -> list:
    try:
        from fedml_tpu.obs import trace
        return trace.program_notes("attn/call")
    except (ImportError, AttributeError):
        return []


def layer_steps(ctx) -> int:
    """Training steps of one layer in the traced rounds."""
    traffic = ctx["cell"]["traffic"]
    return ctx["traced_rounds"] * traffic["clients_per_round"] * traffic["local_steps"]


def per_layer(prefix: str) -> list:
    """The per-layer counter ``prefix/layer_<i>``, in layer order."""
    found = counters(prefix + "/layer_")
    return [found[k] for k in sorted(found, key=lambda k: int(k.rsplit("_", 1)[1]))]


def held_share_pct(ctx):
    """Assignments held / (tokens x k) of a step, mean of the layers."""
    held = per_layer("moe/assignments_held")
    if not held:
        return None
    traffic, model = ctx["cell"]["traffic"], ctx["cell"]["config"]
    offered = traffic["batch_size"] * traffic["seq_len"] * model["moe_num_active_primary_experts"]
    return 100.0 * sum(held) / len(held) / offered


def experts_roofline_pct(ctx):
    """Least time of the grouped products over the assignments counted,
    forward and backward, over the device time under ``moe/experts``."""
    held, measured = per_layer("moe/assignments_held"), scope_seconds(ctx, "experts")
    if not held or not measured:
        return None
    model = ctx["cell"]["config"]
    least = 0.0
    for assignments in held:
        for backward in (False, True):
            flops, moved = moe_costs.experts_cost(
                assignments, model["hidden_size"], model["moe_ffn_hidden_size"],
                model["moe_num_primary_experts"], backward)
            least += kernel_costs.least_seconds(flops, moved, ctx["peaks"])[0]
    return 100.0 * layer_steps(ctx) * least / measured


def flash_window_roofline_pct(ctx):
    """Least time of the traced rounds' attention calls, forward and
    backward, each layer by its kind, over the three kernels' device time."""
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    measured = flash_kernel_seconds(ctx)
    if "sliding_window_layout" not in model or not measured:
        return None
    least = 0.0
    for window in moe_costs.layer_windows(model):
        for backward in (False, True):
            flops, moved = moe_costs.attention_cost(
                traffic["batch_size"], model["num_attention_heads"],
                model["num_key_value_heads"], traffic["seq_len"], model["head_dim"],
                window, backward)
            least += kernel_costs.least_seconds(flops, moved, ctx["peaks"])[0]
    return 100.0 * layer_steps(ctx) * least / measured


def tiles_visited_pct(ctx):
    """Score elements in the tiles the three kernels visit over those of the
    whole squares, all layers of the configuration: each layer's kind picks
    its ``attn/call`` notes at the cell's sequence length."""
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    notes = [n for n in attention_notes() if n["shape"][2] == traffic["seq_len"]]
    if "sliding_window_layout" not in model or not notes:
        return None
    visited = total = 0
    for window in moe_costs.layer_windows(model):
        for note in (n for n in notes if n["window"] == window):
            area = note["tile"][0] * note["tile"][1]
            visited += area * note["tiles_visited"]
            total += area * note["tiles_total"]
    return 100.0 * visited / total if total else None
