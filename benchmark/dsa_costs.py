"""Operations and bytes the Keye-VL-2.0 language model's algorithms need, from
their shapes: the family ``dsa_moe_lm``'s FLOPs a round and the numerators of
``dsa_select_roofline`` and ``flash_dsa_roofline``. Every count is a lower
bound on the work (only the pairs a query may choose from or has chosen, only
the assignments held, every operand moved once, nothing recomputed), so a
share made from it cannot pass 100%. Kept with the benchmark, beside
``moe_costs.py``.

Sparse attention (``fedml_tpu/ops/dsa.py``): an indexer of ``indexer_num_heads``
heads of ``indexer_head_dim`` columns scores every earlier key for a query
(the causal pairs), and the query attends to its ``topk`` best (the selected
pairs: every earlier key while there are no more than ``topk``).
"""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs with the key at or before the query."""
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs the attention runs over: ``min(t + 1, topk)`` keys
    for query ``t`` (1,792.125 a query at T 8,192 and 2,048 keys)."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def index_widths(config: dict) -> tuple:
    sa = config["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def layer_forward_flops_per_token(config: dict, seq_len: int) -> dict:
    """2 x multiply-accumulates of one token's forward pass through one layer
    on this chip's share, by part: q, k, v, o; the attention over the selected
    pairs (4 d a pair a head); the indexer's three projections; its scores
    over the causal pairs (2 d_I a pair a head); the index loss's pass over
    q k^T on the selected pairs (2 d a pair a head); the router; the held
    experts for the expected ``k * held / outputs`` assignments of a token."""
    d, dh = config["hidden_size"], config["head_dim"]
    q_width, kv_width = config["num_attention_heads"] * dh, config["num_key_value_heads"] * dh
    heads, width, topk = index_widths(config)
    chosen, seen = selected_pairs(seq_len, topk) / seq_len, causal_pairs(seq_len) / seq_len
    held = config["num_experts_per_tok"] * config["num_experts"] / config["moe_router_outputs"]
    return {
        "projections": 2.0 * d * (2 * q_width + 2 * kv_width),
        "attention": 4.0 * q_width * chosen,
        "indexer": 2.0 * d * (heads * width + width + heads),
        "index_scores": 2.0 * heads * width * seen,
        "index_loss": 2.0 * q_width * chosen,
        "router": 2.0 * d * config["moe_router_outputs"],
        "experts": held * 6.0 * d * config["moe_intermediate_size"],
    }


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    """Every layer's parts and the head over the held vocabulary."""
    layer = sum(layer_forward_flops_per_token(config, seq_len).values())
    return config["num_hidden_layers"] * layer + 2.0 * config["hidden_size"] * config["vocab_size"]


def parameters(config: dict) -> int:
    """The client model's leaves: a layer's q, k, v, o, the heads' two norms,
    the block's two norms, the router, the indexer (three matrices and a
    LayerNorm's scale and bias) and the held experts; the embedding, the final
    norm and the head."""
    d, dh = config["hidden_size"], config["head_dim"]
    q_width, kv_width = config["num_attention_heads"] * dh, config["num_key_value_heads"] * dh
    heads, width, _ = index_widths(config)
    layer = (2 * d * q_width + 2 * d * kv_width + 2 * dh + 2 * d + d * config["moe_router_outputs"]
             + d * (heads * width + width + heads) + 2 * width
             + config["num_experts"] * 3 * d * config["moe_intermediate_size"])
    return config["num_hidden_layers"] * layer + 2 * config["vocab_size"] * d + d


def selection_bytes(batch: int, seq_len: int, tile: int) -> int:
    """What carries the chosen set to the kernels: a bit a (query, key) pair
    both ways and an int32 count a tile."""
    return batch * (2 * seq_len * seq_len // 8 + 4 * (seq_len // tile) ** 2)


def select_cost(batch: int, seq_len: int, heads: int, width: int, carried_bytes: int,
                bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's index scores and selection: the heads'
    products over the causal pairs (2 x ``width`` a pair a head; the ReLU, the
    weighted sum and the selection's compares are not counted), ``qI``, ``kI``
    and ``wI`` read once and what carries the selection written once."""
    flops = 2.0 * heads * width * causal_pairs(seq_len) * batch
    moved = bytes_per_element * batch * seq_len * (heads * width + width + heads)
    return flops, float(moved + carried_bytes)


def attention_cost(batch: int, heads: int, kv_heads: int, seq_len: int, head_dim: int,
                   topk: int, backward: bool, carried_bytes: int,
                   bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one attention call over the selected pairs by
    ``moe_costs.attention_cost``'s rule: 4 x pairs x d forward, 10 x backward
    (the five products); q read and the output written forward, q, dO read and
    dQ written backward, K and V moved once a KV head (read forward; read and
    their gradients written backward), and the selection's bytes once a call
    (each direction reads its half)."""
    pairs = selected_pairs(seq_len, topk) * batch * heads
    q_elems = batch * heads * seq_len * head_dim
    kv_elems = batch * kv_heads * seq_len * head_dim
    elems = 3 * q_elems + 4 * kv_elems if backward else 2 * q_elems + 2 * kv_elems
    return ((10.0 if backward else 4.0) * pairs * head_dim,
            float(bytes_per_element * elems + carried_bytes / 2))
