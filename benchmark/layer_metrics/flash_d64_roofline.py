"""The three flash kernels' share of their roofline at 64-wide heads over the
traced rounds: the least time of every attention layer's forward and backward
call (``benchmark/moe_costs.py`` ``attention_cost`` at the shapes of the
program's ``attn/call`` notes: only the pairs the mask shows, K and V moved
once a KV head) over the summed self time of ``flash_fwd``,
``flash_bwd_dkv`` and ``flash_bwd_dq``. A program without the kernels or the
notes gives None."""

from benchmark import kernel_costs, lfm2_costs, moe_reduce


def read(ctx):
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    measured = moe_reduce.flash_kernel_seconds(ctx)
    notes = [n for n in moe_reduce.attention_notes()
             if n["kernel"] == "fwd" and n["shape"][0] == traffic["batch_size"]
             and n["shape"][2] == traffic["seq_len"]]
    if "conv_L_cache" not in model or not notes or not measured:
        return None
    least = sum(kernel_costs.least_seconds(*lfm2_costs.attention_cost(notes[-1], backward),
                                           ctx["peaks"])[0] for backward in (False, True))
    calls_traced = moe_reduce.layer_steps(ctx) * lfm2_costs.mixers(model).count("gqa")
    return 100.0 * calls_traced * least / measured
