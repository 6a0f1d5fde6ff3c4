"""Precisions of the reference's matrix products.

"f32" is the reference proper. "bf16" and "fp8" are the controls of "How
correct is decided": the same arithmetic with every convolution and matrix
product computed as a chip would in the lower type: both operands rounded to
it on the way in, and in the backward pass the incoming gradient rounded too,
the products themselves accumulated in float32. fp8 (e4m3) takes a per-tensor
scale, as a served fp8 path would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8")
_FP8_MAX = 448.0  # float8_e4m3fn


def round_to(x, precision: str):
    """``x`` rounded to ``precision`` and back to float32."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r} (expected one of {PRECISIONS})")


def product(f, precision: str):
    """``f(x, w)``, a convolution or matrix product, at ``precision``."""
    if precision == "f32":
        return f

    @jax.custom_vjp
    def op(x, w):
        return f(round_to(x, precision), round_to(w, precision))

    def forward(x, w):
        return jax.vjp(f, round_to(x, precision), round_to(w, precision))

    def backward(vjp, dy):
        return vjp(round_to(dy, precision))

    op.defvjp(forward, backward)
    return op
