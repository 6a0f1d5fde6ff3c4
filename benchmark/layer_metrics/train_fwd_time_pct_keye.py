"""``train_fwd_time_pct`` read in ``keyevl2_silo2``: ops under ``fed/fwd_bwd``
outside jax's ``transpose(``: the forward, the rematerialised one with it. The
accepted reader under the cell's name (PERF.md section 7)."""

from benchmark.layer_metrics.train_fwd_time_pct import read  # noqa: F401
