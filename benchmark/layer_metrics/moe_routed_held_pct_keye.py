"""``moe_routed_held_pct`` read in ``keyevl2_silo2``: assignments that land on
held experts over tokens x experts a token, mean of the layers; 12.5 when
routing is even over 16 of 128 experts. The accepted reader under the cell's
name (PERF.md section 7)."""

from benchmark.layer_metrics.moe_routed_held_pct import read  # noqa: F401
