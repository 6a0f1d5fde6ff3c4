"""Operations and bytes the EvaByte layer's algorithms need, from their
shapes: the family's FLOPs a round and the numerators of
``eva_summary_roofline`` and ``flash_eva_roofline``. Every count is a lower
bound on the work (only the pairs the masks show, every operand moved once,
nothing recomputed), so a share made from it cannot pass 100%. Kept with the
benchmark, beside ``kernel_costs.py``.

EVA attention (``fedml_tpu/ops/eva.py``): a query sees the keys up to itself
inside its own window of ``window_size`` positions, and one summary for every
chunk of ``chunk_size`` positions of the windows before it.
"""

from __future__ import annotations


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def windows(config: dict, seq_len: int) -> int:
    return max(seq_len // config["window_size"], 1)


def local_pairs(config: dict, seq_len: int) -> int:
    """(query, key) pairs of one head's sequence inside the windows: each
    window a causal square."""
    w = min(config["window_size"], seq_len)
    return windows(config, seq_len) * w * (w + 1) // 2


def remote_pairs(config: dict, seq_len: int) -> int:
    """(query, summary) pairs of one head's sequence: every query of window
    ``w`` sees the ``w * window / chunk`` summaries of the windows before."""
    n_w, per_window = windows(config, seq_len), config["window_size"] // config["chunk_size"]
    return sum(config["window_size"] * w * per_window for w in range(n_w))


def summary_flops_per_token(config: dict) -> int:
    """A key's score under phi (2 d) and its share of the pooled key and
    value (4 d), every head."""
    return config["num_attention_heads"] * 6 * head_dim(config)


def layer_forward_flops_per_token(config: dict, seq_len: int) -> float:
    """2 x multiply-accumulates of one token's forward pass through one layer:
    q, k, v, o; the gated feed-forward's three products; attention over the
    visible keys and summaries (4 d a pair a head); the summaries."""
    d, width = config["hidden_size"], config["num_attention_heads"] * head_dim(config)
    pairs = (local_pairs(config, seq_len) + remote_pairs(config, seq_len)) / seq_len
    return (2 * 4 * d * width + 2 * 3 * d * config["intermediate_size"]
            + 4 * width * pairs + summary_flops_per_token(config))


def head_forward_flops_per_token(config: dict) -> int:
    return 2 * config["hidden_size"] * config["num_pred_heads"] * config["vocab_size"]


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    return (config["num_hidden_layers"] * layer_forward_flops_per_token(config, seq_len)
            + head_forward_flops_per_token(config))


def parameters(config: dict) -> int:
    """The client model's leaves: a layer's q, k, v, o, gate, up, down, two
    norms, phi and mu; the embedding, the final norm and the heads."""
    d, width = config["hidden_size"], config["num_attention_heads"] * head_dim(config)
    layer = 4 * d * width + 3 * d * config["intermediate_size"] + 2 * d + 2 * width
    return (config["num_hidden_layers"] * layer + config["vocab_size"] * d + d
            + d * config["num_pred_heads"] * config["vocab_size"])


def summary_cost(batch: int, heads: int, seq_len: int, d: int, chunk: int, backward: bool,
                 bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one call that makes the chunk summaries of ``k`` and
    ``v`` ``[batch, heads, seq_len, d]`` at stated traffic. Forward: k and v
    read once, the two ``[batch, heads, seq_len / chunk, d]`` summaries
    written. Backward: k and v read again with the summaries' gradients, and
    the gradients of k and v written. phi and mu are a head's two vectors:
    not counted. A byte moved buys under two operations: memory-bound."""
    elems, sums = batch * heads * seq_len * d, batch * heads * (seq_len // chunk) * d
    if backward:
        return 12.0 * elems, float(bytes_per_element) * (4 * elems + 2 * sums)
    return 6.0 * elems, float(bytes_per_element) * (2 * elems + 2 * sums)


def attention_cost(config: dict, batch: int, seq_len: int, backward: bool,
                   bytes_per_element: int = 2) -> tuple[tuple, tuple]:
    """``((FLOPs, bytes) local, (FLOPs, bytes) remote)`` of one layer's two
    flash calls by ``moe_costs.attention_cost``'s rule: 4 x pairs x d forward
    (scores and the weighted values), 10 x backward (the five products);
    forward q, k, v read and the output written, backward q, dO, k, v read
    and the three gradients written, the summaries in the keys' place in the
    remote call. The rows' log-sum-exps (4 bytes a row) are not counted."""
    heads, d = config["num_attention_heads"], head_dim(config)
    q_elems = batch * heads * seq_len * d
    sum_elems = batch * heads * (seq_len // config["chunk_size"]) * d
    per_pair = (10.0 if backward else 4.0) * d * batch * heads
    moved = lambda q, kv: float(bytes_per_element) * (  # noqa: E731
        3 * q + 4 * kv if backward else 2 * q + 2 * kv)
    local = (per_pair * local_pairs(config, seq_len), moved(q_elems, q_elems))
    if windows(config, seq_len) == 1:
        return local, (0.0, 0.0)
    return local, (per_pair * remote_pairs(config, seq_len), moved(q_elems, sum_elems))
