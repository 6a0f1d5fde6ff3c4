"""The index loss's pass against its roofline over the traced rounds: the
least time of every layer's pass at *stated* work, once a training step (its
three gradients are kept under ``remat``, so no second forward makes them
again), over the device time under the scope ``attn/dsa/index_loss``. By scope
and not by an op's name, from the shapes in the program's ``dsa/call`` notes, so
that it reads the same work whatever implements the pass (plain XLA at the
PR that added this file's parent, Mosaic kernels since). A program without the
scope or the notes gives None.

Stated work (:func:`loss_cost`; a lower bound, so the share cannot pass 100):
over the *selected* pairs alone (``dsa_costs.selected_pairs``: a query's
``min(t + 1, topk)`` keys) the heads' ``q k^T`` (2 x head_dim a pair a head) and
the indexer's scores and its two gradient products (3 x 2 x index_dim a pair an
index head; the ``exp``s, ReLUs, the KL and ``d_wI``'s sums are not counted);
``q``, ``k``, ``qI``, ``kI``, ``wI``, the heads' log-sum-exp and the set's
packed words one way read once, the three gradients written once. At ``[1, 32,
8192, 128]`` on 4 KV heads with 16 x 64 index heads and 2,048 keys a query:
210.5 GFLOP and 121 MB, 1.07 ms at a v5e's peak, bound by compute."""

from benchmark import dsa_costs, dsa_reduce, kernel_costs, mla_reduce

SCOPE = "attn/dsa/index_loss"


def loss_cost(batch: int, heads: int, kv_heads: int, seq_len: int, head_dim: int,
              index_heads: int, index_dim: int, topk: int, bytes_per_element: int = 2,
              index_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's index loss and its three gradients."""
    pairs = dsa_costs.selected_pairs(seq_len, topk) * batch
    flops = pairs * (2.0 * head_dim * heads + 3 * 2.0 * index_dim * index_heads)
    index_elems = batch * seq_len * (index_heads * index_dim + index_dim + index_heads)
    moved = (bytes_per_element * batch * seq_len * head_dim * (heads + kv_heads)
             + 2 * index_bytes * index_elems  # read, and their gradients written
             + 4 * batch * heads * seq_len  # the heads' float32 log-sum-exp
             + batch * seq_len * seq_len // 8)  # a bit a (query, key) pair
    return flops, float(moved)


def read(ctx):
    note, share = dsa_reduce.dsa_note(ctx), mla_reduce.scope_pct(ctx, SCOPE)
    if note is None or not share:
        return None
    measured = share / 100.0 * ctx["trace"]["chip0"]["busy_s"]
    b, h, t, d = note["shape"]
    least = kernel_costs.least_seconds(*loss_cost(
        b, h, note["kv_heads"], t, d, note["index_heads"], note["index_dim"], note["topk"],
        dsa_reduce.BYTES[note["dtype"]], dsa_reduce.BYTES[note["index_dtype"]]), ctx["peaks"])[0]
    return 100.0 * dsa_reduce.layer_calls(ctx) * least / measured
