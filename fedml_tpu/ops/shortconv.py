"""The elementwise chain of a gated short-convolution operator (the mixer of
three in four LFM2 layers): with ``[B | C | z]`` the three ``channels``-wide
chunks of the operator's input projection, in that order,

    u   = B * z
    c_t = sum_j w_j * u_{t - (K - 1) + j}     causal, depthwise, K taps a channel
                                              (tap K - 1 meets the token itself),
                                              zeros before the first token, no bias
    y   = C * c

The projections either side of it are the module's (``models/
mla_moe_transformer.py`` ``ShortConv``). Plain XLA: the three steps and their
backward are elementwise passes over ``[tokens, channels]`` arrays and one
reduction for the taps' gradient, which XLA fuses; the products round to the
input's dtype as the published module's do and the taps accumulate in
float32. The taps are ``ops/kda.py`` ``short_conv``'s arithmetic with the
zeros put before the sequence in the input's dtype and each tap's slice
widened to float32 as it is read: compiled for the v5e at ``[2, 8192, 6144]``
bfloat16, that form's float32 ``[2, 8194, 2048]`` copy of the padded input
(134 MB written and read again, in the forward and once more in the backward)
is gone, and the forward writes 134 MB where it wrote 268, the backward 470
where it wrote 805; no differentiation rule of its own is needed for that.
All of it sits in the scope ``mix/shortconv/gate`` (``obs/trace.py``), so a
trace gives the chain's device time whatever implements it, and every call
leaves a ``shortconv/call`` program note with its shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fedml_tpu.obs import trace

NOTE = "shortconv/call"


def _taps(u, w):
    """``c_t = sum_j w_j * u_{t - (K - 1) + j}`` along axis 1 of ``u`` ``[batch,
    T, channels]`` under ``w`` ``[K, channels]``, accumulated in float32,
    returned in ``u``'s dtype."""
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(padded[:, j:j + t].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(taps))
    return c.astype(u.dtype)


def gated_short_conv(bcz, w):
    """``bcz`` ``[batch, T, 3 * channels]`` (the chunks ``B``, ``C``, ``z``),
    ``w`` ``[K, channels]`` -> ``C * conv(B * z)`` ``[batch, T, channels]`` in
    ``bcz``'s dtype."""
    batch, t, width = bcz.shape
    taps, channels = w.shape
    if width != 3 * channels:
        raise ValueError(f"gated_short_conv: {width} columns are not three chunks of {channels}")
    trace.program_note(NOTE, impl="xla", tokens=batch * t, channels=channels, taps=taps,
                       dtype=jnp.dtype(bcz.dtype).name)
    with jax.named_scope(trace.SCOPE_SHORTCONV_GATE):
        b_gate, c_gate, z = jnp.split(bcz, 3, axis=-1)
        return c_gate * _taps(b_gate * z, w)


def gated_short_conv_reference(bcz, w):
    """The same chain token by token in float32 (a Python loop over T): the
    definition the operator is held to."""
    bcz, w = bcz.astype(jnp.float32), w.astype(jnp.float32)
    b_gate, c_gate, z = jnp.split(bcz, 3, axis=-1)
    u = b_gate * z
    taps, t = w.shape[0], bcz.shape[1]
    rows = []
    for i in range(t):
        c = sum(w[j] * u[:, i - (taps - 1) + j] for j in range(taps) if i - (taps - 1) + j >= 0)
        rows.append(c_gate[:, i] * c)
    return jnp.stack(rows, axis=1)
