"""``optimizer_time_pct`` read in ``keyevl2_silo2``: ops under ``fed/opt``.
The accepted reader under the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.optimizer_time_pct import read  # noqa: F401
