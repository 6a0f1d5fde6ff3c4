"""``moe_dispatch_time_pct`` read in ``keyevl2_silo2``: ops under ``moe/dispatch``
and ``moe/combine``, forward and backward. The accepted reader under the cell's
name (PERF.md section 7)."""

from benchmark.layer_metrics.moe_dispatch_time_pct import read  # noqa: F401
