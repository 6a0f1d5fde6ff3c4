"""``moe_routed_held_pct`` read in ``lfm2moe_silo2``: assignments that land on
held experts over tokens x experts a token, mean of the routed blocks; 12.5
when routing is even over 8 of 64 experts. The accepted reader under the
cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.moe_routed_held_pct import read  # noqa: F401
