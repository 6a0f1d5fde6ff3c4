"""Programs built (compiled, or loaded from the persistent cache) while the
timed window ran, counted from jax.monitoring events. Anything but 0 is a
finding: the warm-up missed a shape."""


def read(ctx):
    return ctx["compiles_in_window"]
