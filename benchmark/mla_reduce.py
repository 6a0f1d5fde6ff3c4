"""What the latent-attention cell's metrics are read from, one function a
source, as ``moe_reduce.py`` is for the routed-expert cell's (whose scope
rows, kernel seconds, counters and step count these reuse): device time
under a scope of ``fedml_tpu/obs/trace.py`` ``MLA_SCOPES``, the three flash
kernels' time against ``mla_costs.attention_cost``, and the engine's
``moe/assignments_held`` counters. A program without the scope or the
counters gives nothing, and the reader returns None.
"""

from __future__ import annotations

import re

from benchmark import kernel_costs, mla_costs, moe_reduce, scope_reduce

SCOPE = r"(?:^|[/(])%s(?=[/)]|$)"


def scope_pct(ctx, scope: str):
    """Percent of chip 0's busy time in ops whose ``op_name`` holds
    ``scope`` as a whole path element (``attn/mla``, ``mtp``), forward,
    recomputed forward and backward; None where no op bears it."""
    pattern = re.compile(SCOPE % re.escape(scope))
    rows = scope_reduce.scope_rows(scope_reduce.xplane_path(ctx["cell"]["name"]))
    hits = [self_us for per_program in rows.values()
            for _, op_name, _, self_us in per_program if pattern.search(op_name)]
    busy = ctx["trace"]["chip0"]["busy_s"]
    return 100.0 * sum(hits) / 1e6 / busy if hits and busy else None


def flash_mla_roofline_pct(ctx):
    """Least time of every block's forward and backward attention call in
    the traced rounds (the multi-token-prediction modules' blocks over one
    position fewer) over the three kernels' device time."""
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    measured = moe_reduce.flash_kernel_seconds(ctx)
    if "kv_lora_rank" not in model or not measured:
        return None
    d_qk, d_v = mla_costs.widths(model)
    t = traffic["seq_len"]
    least = 0.0
    for positions in [t] * model["num_hidden_layers"] + [t - 1] * model["num_nextn_predict_layers"]:
        for backward in (False, True):
            flops, moved = mla_costs.attention_cost(
                traffic["batch_size"], model["num_attention_heads"], positions, d_qk, d_v,
                model["qk_rope_head_dim"], backward)
            least += kernel_costs.least_seconds(flops, moved, ctx["peaks"])[0]
    return 100.0 * moe_reduce.layer_steps(ctx) * least / measured


def routed_held_pct(ctx):
    """Assignments held / (tokens x experts a token) of a step, mean of the
    routed blocks."""
    held = moe_reduce.per_layer("moe/assignments_held")
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    if not held or "num_experts_per_tok" not in model:
        return None
    offered = traffic["batch_size"] * traffic["seq_len"] * model["num_experts_per_tok"]
    return 100.0 * sum(held) / len(held) / offered
