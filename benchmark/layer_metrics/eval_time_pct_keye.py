"""``eval_time_pct`` read in ``keyevl2_silo2``: ops under ``fed/eval`` (the
traced call's last round evaluates, through the indexer and the selection as a
training step's forward does). The accepted reader under the cell's name
(PERF.md section 7)."""

from benchmark.layer_metrics.eval_time_pct import read  # noqa: F401
