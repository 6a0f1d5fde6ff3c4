"""``unattributed_time_pct`` read in ``keyevl2_silo2``: busy time that no
``fed/*`` phase claims. The accepted reader under the cell's name (PERF.md
section 7)."""

from benchmark.layer_metrics.unattributed_time_pct import read  # noqa: F401
