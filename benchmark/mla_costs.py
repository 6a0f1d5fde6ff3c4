"""Operations and bytes the latent-attention decoder's algorithms need, from
their shapes: the family ``mla_moe_lm``'s FLOPs a round and the numerator of
``flash_mla_roofline``. Every count is a lower bound on the work (only the
pairs the mask shows, only the assignments held, every operand moved once,
the shared rotary key once and not once a head, nothing recomputed, no
padded position), so a share made from it cannot pass 100%. Kept with the
benchmark, beside ``moe_costs.py``.
"""

from __future__ import annotations

from benchmark.moe_costs import visible_pairs


def widths(config: dict) -> tuple[int, int]:
    """(score width, value width) of a head."""
    return config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"]


def mla_projection_flops(config: dict) -> float:
    """2 x multiply-accumulates a token of the five projections: h -> c_q ->
    q, h -> c_kv | k_rope, c_kv -> k_nope | v, and the output's."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    d_qk, d_v = widths(config)
    return 2.0 * (d * config["q_lora_rank"] + config["q_lora_rank"] * heads * d_qk
                  + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
                  + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"] + d_v)
                  + heads * d_v * d)


def attention_flops_per_token(config: dict, positions: int) -> float:
    """QK^T over the score width and PV over the value width, the visible
    pairs of one causal sequence of ``positions`` spread over its tokens."""
    d_qk, d_v = widths(config)
    return (2.0 * (d_qk + d_v) * config["num_attention_heads"]
            * visible_pairs(positions, None) / positions)


def block_flops_per_token(config: dict, positions: int, routed: bool) -> float:
    """One block's forward: latent attention, then the dense feed-forward,
    or the router, the shared expert and the experts held here for the
    expected ``k * held / outputs`` assignments of a token."""
    d = config["hidden_size"]
    attention = mla_projection_flops(config) + attention_flops_per_token(config, positions)
    if not routed:
        return attention + 6.0 * d * config["intermediate_size"]
    held_per_token = (config["num_experts_per_tok"] * config["n_routed_experts"]
                      / config["moe_router_outputs"])
    return attention + 2.0 * d * config["moe_router_outputs"] + 6.0 * d * config[
        "moe_intermediate_size"] * (config["n_shared_experts"] + held_per_token)


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    """2 x multiply-accumulates of one token's training forward on this
    chip's share: the dense and routed blocks, the head over the held
    vocabulary, and each multi-token-prediction module (the product M, a
    routed block and a second head pass) over the ``seq_len - 1`` positions
    of a sequence that have a second-next token."""
    d, dense = config["hidden_size"], config["first_k_dense_replace"]
    head = 2.0 * d * config["vocab_size"]
    main = (dense * block_flops_per_token(config, seq_len, False)
            + (config["num_hidden_layers"] - dense) * block_flops_per_token(config, seq_len, True)
            + head)
    mtp = 4.0 * d * d + block_flops_per_token(config, seq_len - 1, True) + head
    return main + config["num_nextn_predict_layers"] * mtp * (seq_len - 1) / seq_len


def attention_cost(batch: int, heads: int, positions: int, d_qk: int, d_v: int, d_rope: int,
                   backward: bool, bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one causal attention call over [B, H, T, d_qk]
    queries whose keys are [B, H, T, d_qk - d_rope] a head plus one
    [B, T, d_rope] rotary part for all heads, with [B, H, T, d_v] values.
    Forward: QK^T and PV over the visible pairs, 2 x (d_qk + d_v) FLOP a pair
    a head; q, the keys and v read, the output written. Backward: the five
    products (scores, dP, dV over d_v; dQ, dK over d_qk): 6 x d_qk + 4 x d_v;
    q, dO, the keys and v read, dQ, both parts of dK and dV written."""
    pairs = float(visible_pairs(positions, None)) * batch * heads
    q, out = (batch * heads * positions * w for w in (d_qk, d_v))
    keys = batch * positions * (heads * (d_qk - d_rope) + d_rope)
    if backward:
        return pairs * (6 * d_qk + 4 * d_v), float(bytes_per_element) * (
            2 * q + out + 2 * keys + 2 * out)
    return pairs * 2 * (d_qk + d_v), float(bytes_per_element) * (q + out + keys + out)
