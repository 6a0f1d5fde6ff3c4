"""``head_loss_time_pct`` read in ``lfm2moe_silo2``, whose head is tied: ops
under the scope ``head`` around the embedding's transposed product, or
``fed/loss``, forward and backward. The accepted reader under the cell's name
(PERF.md section 7)."""

from benchmark.layer_metrics.head_loss_time_pct import read  # noqa: F401
