"""The gated short-convolution operator's elementwise chain
(``fedml_tpu/ops/shortconv.py``) on the CPU against the same chain written
token by token: forward and every gradient, at 3 and 4 taps, in float32 and
bfloat16, at lengths that are multiples of nothing; its scope and its program
note; and the module around it (``ShortConv``) against its equations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.mla_moe_transformer import ShortConv
from fedml_tpu.obs import trace
from fedml_tpu.ops import kda, remat, shortconv


def _operands(t, channels, taps, dtype, batch=2, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    bcz = jax.random.normal(k1, (batch, t, 3 * channels), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (taps, channels), jnp.float32) * taps ** -0.5
    dy = jax.random.normal(k3, (batch, t, channels), jnp.float32)
    return bcz, w, dy


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("t", [1, 2, 13, 37])
def test_forward_equals_the_token_by_token_loop(t, taps, dtype):
    bcz, w, _ = _operands(t, 24, taps, dtype)
    got = shortconv.gated_short_conv(bcz, w)
    want = shortconv.gated_short_conv_reference(bcz, w)
    assert got.dtype == dtype and got.shape == (2, t, 24)
    # bfloat16: B * z, the taps' sum and C * c each round once to 8 bits
    atol = 1e-6 if dtype == jnp.float32 else 0.03 * float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("taps", [3, 4])
def test_gradients_equal_the_loops(taps, dtype):
    """d(bcz) in all three chunks and d(taps), pulled back from one random
    cotangent."""
    bcz, w, dy = _operands(13, 24, taps, dtype)

    def pulled(f):
        return jax.grad(lambda x, taps: jnp.sum(f(x, taps).astype(jnp.float32) * dy),
                        argnums=(0, 1))(bcz, w)

    (d_bcz, d_w), (r_bcz, r_w) = pulled(shortconv.gated_short_conv), pulled(
        shortconv.gated_short_conv_reference)
    assert d_bcz.dtype == dtype and d_w.dtype == jnp.float32
    loose = 1.0 if dtype == jnp.float32 else 4e3
    for got, want in ((d_bcz, r_bcz), (d_w, r_w)):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got.astype(jnp.float32), want, atol=1e-5 * loose * scale)
    chunks = jnp.split(d_bcz.astype(jnp.float32), 3, axis=-1)
    assert all(float(jnp.abs(c).max()) > 1e-2 for c in chunks)  # B, C and z each bear one


def test_the_taps_are_short_convs_arithmetic():
    """``_taps`` pads in the input's dtype and widens a tap at a time:
    ``ops/kda.py`` ``short_conv``'s values, bit for bit."""
    u = jax.random.normal(jax.random.key(3), (2, 21, 16)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.key(4), (4, 16))
    np.testing.assert_array_equal(shortconv._taps(u, w), kda.short_conv(u, w))


def test_a_future_token_never_reaches_an_earlier_output():
    bcz, w, _ = _operands(9, 8, 3, jnp.float32, batch=1)
    base = shortconv.gated_short_conv(bcz, w)
    later = shortconv.gated_short_conv(bcz.at[:, 5:].add(1.0), w)
    np.testing.assert_array_equal(later[:, :5], base[:, :5])
    assert float(jnp.abs(later[:, 5:] - base[:, 5:]).max()) > 1e-3
    # tap K - 1 meets the token itself: with that tap alone the chain is C * w * B * z
    alone = jnp.zeros_like(w).at[-1].set(w[-1])
    b_gate, c_gate, z = jnp.split(bcz, 3, axis=-1)
    np.testing.assert_allclose(shortconv.gated_short_conv(bcz, alone),
                               c_gate * alone[-1] * b_gate * z, atol=1e-6)


def test_three_chunks_are_asked_for():
    with pytest.raises(ValueError, match="not three chunks"):
        shortconv.gated_short_conv(jnp.zeros((1, 4, 10)), jnp.zeros((3, 4)))


def test_scope_and_note():
    """The chain's ops, forward and backward, bear ``mix/shortconv/gate``; a
    call leaves one ``shortconv/call`` note with its shapes."""
    bcz, w, _ = _operands(16, 8, 3, jnp.bfloat16)
    text = jax.jit(jax.grad(lambda x, taps: jnp.sum(
        shortconv.gated_short_conv(x, taps).astype(jnp.float32)), argnums=(0, 1))).lower(
            bcz, w).as_text(debug_info=True)
    assert trace.SCOPE_SHORTCONV_GATE in text
    assert f"transpose(jvp({trace.SCOPE_SHORTCONV_GATE}))" in text
    note = {"impl": "xla", "tokens": 32, "channels": 8, "taps": 3, "dtype": "bfloat16"}
    assert note in trace.program_notes(shortconv.NOTE)
    assert trace.SCOPE_SHORTCONV_GATE.startswith(trace.SCOPE_SHORTCONV + "/")
    assert {trace.SCOPE_SHORTCONV, trace.SCOPE_SHORTCONV_GATE, trace.SCOPE_GQA,
            trace.SCOPE_HEAD} == set(trace.SHORTCONV_SCOPES)


@pytest.mark.parametrize("taps", [3, 4])
def test_the_module_is_its_equations(taps):
    """``out((C * conv(B * z)))`` with ``[B | C | z] = h W_in``: the module
    against the equations written out, and what it keeps under remat."""
    d, t = 16, 11
    h = jax.random.normal(jax.random.key(0), (2, t, d))
    module = ShortConv(taps)
    params = module.init(jax.random.key(1), h)["params"]
    assert {k: v["kernel"].shape for k, v in params.items()} == {
        "in": (d, 3 * d), "taps": (taps, d), "out": (d, d)}
    got = module.apply({"params": params}, h)
    bcz = h @ params["in"]["kernel"]
    b_gate, c_gate, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    u = jnp.concatenate([jnp.zeros((2, taps - 1, d)), b_gate * z], axis=1)
    c = sum(params["taps"]["kernel"][j] * u[:, j:j + t] for j in range(taps))
    np.testing.assert_allclose(got, (c_gate * c) @ params["out"]["kernel"], atol=1e-5)
    assert remat.SHORTCONV_IN in remat.KEPT
    text = jax.jit(lambda p, x: module.apply({"params": p}, x)).lower(params, h).as_text(
        debug_info=True)
    assert trace.SCOPE_SHORTCONV in text and trace.SCOPE_SHORTCONV_GATE in text
