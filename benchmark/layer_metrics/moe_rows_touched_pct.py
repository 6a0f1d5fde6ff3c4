"""Rows of the sorted-order buffers that a routed layer's passes cover (its
buffers' capacity plus whole overflow tiles; the program's
``moe/rows_touched`` counters) over tokens x experts a token, mean of the
routed blocks: 100 where the layer walks every assignment whatever is held,
150% of the held share where its buffers follow the share."""

from benchmark import moe_reduce

TOP_K = ("num_experts_per_tok", "moe_num_active_primary_experts")  # a family's own key


def read(ctx):
    touched = moe_reduce.per_layer("moe/rows_touched")
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    top_k = next((model[key] for key in TOP_K if key in model), None)
    if not touched or not top_k:
        return None
    return 100.0 * sum(touched) / len(touched) / (traffic["batch_size"] * traffic["seq_len"] * top_k)
