"""Host milliseconds a round sampling cohorts and building their index maps:
the ``engine/stage/cohort`` spans of the window over its rounds."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.span_ms_per_round(ctx, "engine/stage/cohort")
