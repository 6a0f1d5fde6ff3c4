"""``loop_steps_time_pct`` read in ``keyevl2_silo2``: ops no ``fed/*`` phase
claims whose innermost loop is the local-step scan. The accepted reader under
the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.loop_steps_time_pct import read  # noqa: F401
