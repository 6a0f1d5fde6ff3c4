"""``moe_rows_touched_pct`` read in ``keyevl2_silo2``: rows of the sorted-order
buffers the routed layers' passes cover over tokens x experts a token. The
accepted reader under the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.moe_rows_touched_pct import read  # noqa: F401
