"""Host milliseconds a round the driver was blocked on staging: the
``prefetch/consumer_stall`` spans of the window over its rounds; 0.0 when the
prefetcher ran and the driver never waited, nothing when it did not run."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.stall_ms_per_round(ctx)
