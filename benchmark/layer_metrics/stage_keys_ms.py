"""Host milliseconds a round deriving the rounds' rng keys: the
``engine/stage/keys`` spans of the window over its rounds."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.span_ms_per_round(ctx, "engine/stage/keys")
