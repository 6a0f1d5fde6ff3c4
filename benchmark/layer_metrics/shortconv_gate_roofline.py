"""The short-convolution operator's elementwise chain against its roofline
over the traced rounds: the least time of every convolution layer's call,
forward and backward, at *stated* traffic (``benchmark/lfm2_costs.py``
``gate_cost``: the input projection's ``[tokens, 3 D]`` read once and
``[tokens, D]`` written forward; those, the output's gradient and the taps'
backward; a recomputed forward counts once, it is no work the model asks
for), over the device time under the scope ``mix/shortconv/gate``. By scope
and not by an op's name, from the shapes in the program's ``shortconv/call``
notes, so that it reads the same work whatever implements the chain. A
program without the scope or the notes gives None."""

from benchmark import kernel_costs, lfm2_costs, mla_reduce, moe_reduce


def calls() -> list:
    """The program's distinct ``shortconv/call`` notes."""
    try:
        from fedml_tpu.obs import trace
        return trace.program_notes("shortconv/call")
    except (ImportError, AttributeError):
        return []


def read(ctx):
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    tokens = traffic["batch_size"] * traffic["seq_len"]
    notes = [n for n in calls() if n["tokens"] == tokens]
    share = mla_reduce.scope_pct(ctx, "mix/shortconv/gate")
    if "conv_L_cache" not in model or not notes or not share:
        return None
    note = notes[-1]
    bytes_per_element = {"bfloat16": 2, "float32": 4}[note["dtype"]]
    least = sum(kernel_costs.least_seconds(*lfm2_costs.gate_cost(
        note["tokens"], note["channels"], note["taps"], backward, bytes_per_element),
        ctx["peaks"])[0] for backward in (False, True))
    calls_traced = moe_reduce.layer_steps(ctx) * lfm2_costs.mixers(model).count("conv")
    measured = share / 100.0 * ctx["trace"]["chip0"]["busy_s"]
    return 100.0 * calls_traced * least / measured
