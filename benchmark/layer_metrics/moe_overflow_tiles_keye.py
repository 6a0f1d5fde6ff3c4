"""``moe_overflow_tiles`` read in ``keyevl2_silo2``: row tiles computed past the
buffers' capacity, all layers together. The accepted reader under the cell's
name (PERF.md section 7)."""

from benchmark.layer_metrics.moe_overflow_tiles import read  # noqa: F401
