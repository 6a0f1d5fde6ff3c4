"""``unscoped_time_pct`` read in ``keyevl2_silo2``: ops with neither a ``fed/*``
phase nor a ``loop/*`` name. The accepted reader under the cell's name (PERF.md
section 7)."""

from benchmark.layer_metrics.unscoped_time_pct import read  # noqa: F401
