"""``moe_experts_roofline`` in ``keyevl2_silo2``: the least time of the held
experts' grouped products for the assignments the program counted
(``moe_costs.experts_cost``, the accepted arithmetic under this
configuration's keys) over the device time under ``moe/experts``. The product
a rematerialised block makes again is time and no stated work."""

from benchmark import dsa_reduce


def read(ctx):
    return dsa_reduce.experts_roofline_pct(ctx)
