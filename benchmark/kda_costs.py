"""Operations and bytes the Kimi-Linear decoder's algorithms need, from their
shapes: the family ``kda_moe_lm``'s FLOPs a round and the numerator of
``kda_scan_roofline``. The recurrence is counted as the chunked algorithm at a
*stated* chunk of 64 tokens, whatever chunk or kernel the program runs, so
that a share made from it reads the same work under any implementation; every
operand is moved once and nothing recomputed. Kept with the benchmark, beside
``mla_costs.py``.
"""

from __future__ import annotations

from benchmark import mla_costs

CHUNK = 64  # the stated chunk of the recurrence's count


def mixers(config: dict) -> tuple:
    """"kda" / "mla" of each layer the configuration runs: the published
    1-based ``kda_layers`` and ``full_attn_layers``, cut to the depth."""
    linear = config["linear_attn_config"]
    kinds = []
    for layer in range(1, config["num_hidden_layers"] + 1):
        in_kda, in_full = layer in linear["kda_layers"], layer in linear["full_attn_layers"]
        if in_kda == in_full:
            raise ValueError(
                f"layer {layer} must be in exactly one of kda_layers, full_attn_layers")
        kinds.append("kda" if in_kda else "mla")
    return tuple(kinds)


def kda_widths(config: dict) -> tuple[int, int]:
    """(heads, columns a head) of the delta-attention mixer; keys and values
    have the same width."""
    linear = config["linear_attn_config"]
    return linear["num_heads"], linear["head_dim"]


def kda_projection_flops(config: dict) -> float:
    """2 x multiply-accumulates a token of the mixer outside its recurrence:
    q, k, v and the output's projections, the two low-rank pairs (rank = the
    head's width), beta's, and the three convolutions' taps."""
    d = config["hidden_size"]
    heads, width = kda_widths(config)
    inner = heads * width
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    return 2.0 * (4 * d * inner + 2 * (d * width + width * inner) + d * heads + 3 * taps * inner)


def recurrence_flops_per_token(heads: int, d_k: int, d_v: int, chunk: int = CHUNK) -> float:
    """Forward FLOPs a token of the chunked delta rule, all heads: A, B and
    T K+ over ``d_k`` and T V and B U over ``d_v`` (``chunk`` multiply-
    accumulates a column each), the three products with the state (read for
    the writes, read for the outputs, updated), and the unit triangular
    solve's ``chunk^3 / 3`` multiply-accumulates a chunk."""
    return heads * (2.0 * chunk * (3 * d_k + 2 * d_v) + 6.0 * d_k * d_v + 2.0 / 3.0 * chunk ** 2)


def scan_cost(batch: int, heads: int, positions: int, d_k: int, d_v: int, backward: bool,
              chunk: int = CHUNK, bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call of the recurrence over ``[B, H, T, d_k]``
    queries and keys, ``[B, H, T, d_v]`` values, float32 log-decays ``[B, H,
    T, d_k]`` and float32 ``beta`` ``[B, H, T]``. Forward: q, k, v, g and beta
    read, the output written. Backward: twice the forward's FLOPs; the five
    operands and the output's gradient read, the five gradients written."""
    tokens = float(batch * heads * positions)
    flops = batch * positions * recurrence_flops_per_token(heads, d_k, d_v, chunk)
    operands = tokens * (bytes_per_element * (2 * d_k + d_v) + 4 * d_k + 4)
    out = tokens * bytes_per_element * d_v
    if backward:
        return 2.0 * flops, 2.0 * operands + out
    return flops, operands + out


def mla_projection_flops(config: dict) -> float:
    """2 x multiply-accumulates a token of the latent-attention layer's four
    projections: h -> q (no query latent), h -> c_kv | k_b, c_kv -> k_a | v,
    and the output's."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    d_qk, d_v = mla_costs.widths(config)
    return 2.0 * (d * heads * d_qk + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
                  + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"] + d_v)
                  + heads * d_v * d)


def mixer_flops_per_token(config: dict, kind: str, positions: int) -> float:
    if kind == "kda":
        heads, width = kda_widths(config)
        return kda_projection_flops(config) + recurrence_flops_per_token(heads, width, width)
    return mla_projection_flops(config) + mla_costs.attention_flops_per_token(config, positions)


def feed_forward_flops_per_token(config: dict, routed: bool) -> float:
    """The dense feed-forward, or the router, the shared experts and the
    experts held here for the expected ``k * held / outputs`` assignments."""
    d = config["hidden_size"]
    if not routed:
        return 6.0 * d * config["intermediate_size"]
    held_per_token = (config["num_experts_per_token"] * config["num_experts"]
                      / config["moe_router_outputs"])
    return 2.0 * d * config["moe_router_outputs"] + 6.0 * d * config["moe_intermediate_size"] * (
        config["num_shared_experts"] + held_per_token)


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    """2 x multiply-accumulates of one token's training forward on this
    chip's share: each layer's mixer and feed-forward, and the head over the
    held vocabulary."""
    dense = config["first_k_dense_replace"]
    layers = sum(mixer_flops_per_token(config, kind, seq_len)
                 + feed_forward_flops_per_token(config, i >= dense)
                 for i, kind in enumerate(mixers(config)))
    return layers + 2.0 * config["hidden_size"] * config["vocab_size"]
