"""``attn_bwd_time_pct`` read in ``keyevl2_silo2``: ops under
``attn/blockwise_bwd``, here the masked ``flash_bwd_dkv`` calls (a part of the
backward share). The accepted reader under the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.attn_bwd_time_pct import read  # noqa: F401
