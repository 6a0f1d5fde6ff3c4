"""Share of chip 0's idle time in the traced window that lies inside a
``prefetch/consumer_stall`` annotation of the driver thread: the device
waited because the driver waited for staging (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.idle_pct(ctx, scope_reduce.STALL)
