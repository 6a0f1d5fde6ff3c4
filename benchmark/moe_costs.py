"""Operations and bytes the SmallThinker-style layer's algorithms need, from
their shapes: the family's FLOPs a round and the numerators of
``moe_experts_roofline`` and ``flash_window_roofline``. Every count is a
lower bound on the work (only the pairs the mask shows, only the assignments
held, every operand moved once, nothing recomputed), so a share made from it
cannot pass 100%. Kept with the benchmark, beside ``kernel_costs.py``.
"""

from __future__ import annotations


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a causal mask shows in one sequence: key j is
    visible to query i iff j <= i and, under a window, j > i - window."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_windows(config: dict) -> list:
    """The window of each layer the configuration runs, None for a global
    layer: ``sliding_window_layout`` 1 is a window layer, 0 a global one."""
    layout = config["sliding_window_layout"][:config["num_hidden_layers"]]
    return [config["sliding_window_size"] if kind else None for kind in layout]


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    """2 x multiply-accumulates of one token's forward pass on this chip's
    share: q, k, v, o and the router in every layer, the experts held here
    for the expected ``k * held / outputs`` assignments of a token, attention
    over the visible pairs, and the head over the held vocabulary."""
    d, dh = config["hidden_size"], config["head_dim"]
    q_width = config["num_attention_heads"] * dh
    kv_width = config["num_key_value_heads"] * dh
    held_per_token = (config["moe_num_active_primary_experts"] * config["moe_num_primary_experts"]
                      / config["moe_router_outputs"])
    per_layer = (2 * d * (2 * q_width + 2 * kv_width) + 2 * d * config["moe_router_outputs"]
                 + held_per_token * 6 * d * config["moe_ffn_hidden_size"])
    attention = sum(4 * q_width * visible_pairs(seq_len, w) / seq_len
                    for w in layer_windows(config))
    return config["num_hidden_layers"] * per_layer + attention + 2 * d * config["vocab_size"]


def attention_cost(batch: int, heads: int, kv_heads: int, seq_len: int, head_dim: int,
                   window: int | None, backward: bool,
                   bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one attention call over [B, H, T, D] queries and
    [B, H_kv, T, D] keys and values. Forward: QK^T and PV over the visible
    pairs; q read, the output written, K and V read once a KV head. Backward:
    the five products an attention backward needs (scores, dV, dP, dK, dQ;
    the kernels recompute two of them, which is not counted); q, dO, K and V
    read, dQ, dK and dV written."""
    pairs = visible_pairs(seq_len, window) * batch * heads
    q_elems = batch * heads * seq_len * head_dim
    kv_elems = batch * kv_heads * seq_len * head_dim
    if backward:
        return 10.0 * pairs * head_dim, float(bytes_per_element) * (3 * q_elems + 4 * kv_elems)
    return 4.0 * pairs * head_dim, float(bytes_per_element) * (2 * q_elems + 2 * kv_elems)


def experts_cost(assignments: float, hidden: int, width: int, held: int, backward: bool,
                 bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the held experts' ReGLU over
    ``assignments`` rows: three grouped products of 2 x hidden x width
    multiply-accumulates a row, twice that backward; the held weights read
    once (and their gradients written once backward), each row read and
    written once."""
    weights = 3.0 * held * hidden * width
    flops = 6.0 * assignments * hidden * width
    rows = 2.0 * assignments * hidden
    if backward:
        return 2 * flops, bytes_per_element * (2 * weights + 2 * rows)
    return flops, bytes_per_element * (weights + rows)
