"""Share of chip 0's idle time in the traced window that lies inside no
program annotation of the driver thread: idle time the spans do not explain
(``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.idle_pct(ctx, None)
