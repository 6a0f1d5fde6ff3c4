"""``unattributed_time_pct`` read in ``joyai_flash_silo2``: the accepted reader under a second
name, because a test holds the accepted metric's ``workloads`` list and a file
the benchmark has is not an appending PR's to edit (PERF.md section 7)."""

from benchmark.layer_metrics.unattributed_time_pct import read  # noqa: F401
