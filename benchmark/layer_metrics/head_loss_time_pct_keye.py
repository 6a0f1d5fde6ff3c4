"""``head_loss_time_pct`` read in ``keyevl2_silo2``: ops under the flax module
``head`` or ``fed/loss``, forward and backward. The accepted reader under the
cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.head_loss_time_pct import read  # noqa: F401
