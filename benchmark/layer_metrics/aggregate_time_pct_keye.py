"""``aggregate_time_pct`` read in ``keyevl2_silo2``: ops under ``fed/aggregate``.
The accepted reader under the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.aggregate_time_pct import read  # noqa: F401
