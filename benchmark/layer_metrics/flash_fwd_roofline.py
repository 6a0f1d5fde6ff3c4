"""The flash forward kernel's share of its roofline in the traced rounds: the
least time the chip could take for the forward calls the rounds make
(``benchmark/kernel_costs.py``; compute-bound at T 2048, D 128) over the
summed self time of the Mosaic custom calls on chip 0. The forward kernel is
the program's only Pallas call (its backward is plain XLA), so every
``custom_call`` event of the trace is one; a trace without any gives nothing.
"""

from benchmark import kernel_costs


def read(ctx):
    model, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    if "n_head" not in model:
        return None
    measured = ctx["trace"]["chip0"]["categories"].get("custom_call")
    if not measured:
        return None
    calls = (ctx["traced_rounds"] * traffic["clients_per_round"] * traffic["local_steps"]
             * model["n_layer"])
    flops, bytes_moved = kernel_costs.flash_forward_cost(
        traffic["batch_size"], model["n_head"],
        traffic["seq_len"], model["n_embd"] // model["n_head"])
    least, _ = kernel_costs.least_seconds(flops, bytes_moved, ctx["peaks"])
    return 100.0 * calls * least / measured
