"""``moe_load_max_over_mean`` read in ``keyevl2_silo2``: the worst layer's most
loaded held expert over the mean held expert's load. The accepted reader under
the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.moe_load_max_over_mean import read  # noqa: F401
