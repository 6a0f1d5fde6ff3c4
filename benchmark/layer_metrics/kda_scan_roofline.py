"""The delta rule's recurrence against its roofline over the traced rounds:
the least time of every delta-attention layer's call, forward and backward
(``benchmark/kda_costs.py`` ``scan_cost``: the chunked algorithm at a stated
chunk of 64, every operand moved once; a recomputed forward counts once, it
is no work the model asks for), over the device time under the scope
``attn/kda/scan``. By scope and not by a kernel's name, from the shapes in the
program's ``kda/call`` notes, so that it reads the same work whatever
implements the scan. A program without the scope or the notes gives None."""

from benchmark import kda_costs, kernel_costs, mla_reduce, moe_reduce


def calls() -> list:
    """The program's distinct ``kda/call`` notes."""
    try:
        from fedml_tpu.obs import trace
        return trace.program_notes("kda/call")
    except (ImportError, AttributeError):
        return []


def read(ctx):
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    notes = [n for n in calls() if n["t"] == traffic["seq_len"]]
    share = mla_reduce.scope_pct(ctx, "attn/kda/scan")
    if "linear_attn_config" not in model or not notes or not share:
        return None
    note = notes[-1]
    least = sum(kernel_costs.least_seconds(*kda_costs.scan_cost(
        traffic["batch_size"], note["heads"], note["t"], note["d_k"], note["d_v"], backward),
        ctx["peaks"])[0] for backward in (False, True))
    calls_traced = moe_reduce.layer_steps(ctx) * kda_costs.mixers(model).count("kda")
    measured = share / 100.0 * ctx["trace"]["chip0"]["busy_s"]
    return 100.0 * calls_traced * least / measured
