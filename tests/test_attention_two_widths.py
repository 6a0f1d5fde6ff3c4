"""The two flash kernels with a score width that differs from the value
width, and with rotary key columns shared by every head (latent attention),
against ``attention_reference``: forward and the three gradients, on the CPU
interpreter. At equal widths they are the kernels they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.obs import trace
from fedml_tpu.ops import attention as att
from fedml_tpu.ops.attention import attention_reference, flash_attention


def _qkv(shape, d_v, kv_heads=None, seed=0, dtype=jnp.float32, t_k=None):
    b, h, t, d = shape
    key = jax.random.key(seed)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32).astype(dtype)
               for i, s in enumerate([shape, (b, kv_heads or h, t_k or t, d),
                                      (b, kv_heads or h, t_k or t, d_v)]))
    return q, k, v


def _grads(fn, q, k, v, **kw):
    g = jax.random.normal(jax.random.key(9), q.shape[:-1] + (v.shape[-1],), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, **kw).astype(jnp.float32) * g)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape,d_v,kv_heads,causal,window,blocks", [
    pytest.param((2, 4, 64, 24), 16, None, True, None, (16, 16), id="24-on-16-causal"),
    pytest.param((1, 4, 64, 24), 16, None, False, None, (32, 16), id="24-on-16-full"),
    pytest.param((1, 4, 64, 16), 24, None, True, None, (16, 32), id="16-on-24-wider-values"),
    pytest.param((1, 4, 64, 24), 16, 2, True, None, (16, 16), id="24-on-16-grouped-kv"),
    pytest.param((1, 4, 64, 24), 16, 2, True, 24, (16, 16), id="24-on-16-grouped-window"),
    pytest.param((1, 2, 128, 192), 128, None, True, None, (None, None), id="192-on-128-own-tiles"),
])
def test_two_widths_equal_the_reference_forward_and_gradients(shape, d_v, kv_heads, causal,
                                                              window, blocks):
    q, k, v = _qkv(shape, d_v, kv_heads)
    kw = dict(causal=causal, window=window)
    out = flash_attention(q, k, v, causal, None, *blocks, window)
    want = attention_reference(q, k, v, **kw)
    assert out.shape == shape[:-1] + (d_v,) == want.shape
    np.testing.assert_allclose(out, want, atol=2e-5)
    got = _grads(lambda q, k, v, **_: flash_attention(q, k, v, causal, None, *blocks, window),
                 q, k, v)
    for a, b, name in zip(got, _grads(attention_reference, q, k, v, **kw), "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=f"d{name}")


def test_the_default_scale_is_the_score_widths():
    q, k, v = _qkv((1, 2, 32, 24), 16)
    np.testing.assert_allclose(flash_attention(q, k, v, True, None, 16, 16),
                               flash_attention(q, k, v, True, 24 ** -0.5, 16, 16), atol=0)
    np.testing.assert_allclose(attention_reference(q, k, v, causal=True),
                               attention_reference(q, k, v, causal=True, sm_scale=24 ** -0.5),
                               atol=0)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 5e-5), (jnp.bfloat16, 6e-2)])
def test_a_rotary_key_shared_by_the_heads(dtype, atol):
    """Latent attention's score: per-head columns plus rotary columns that
    are one vector a position for all heads. The kernels' key carries the
    shared columns a head; the gradient of the shared part is the heads'
    sum, and all of it equals the two-product form of the equations."""
    b, h, t, nope, rope, d_v = 1, 4, 64, 16, 8, 16
    key = jax.random.key(3)
    q, k_nope, k_rope, v = (
        jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32).astype(dtype)
        for i, s in enumerate([(b, h, t, nope + rope), (b, h, t, nope), (b, 1, t, rope),
                               (b, h, t, d_v)]))
    g = jax.random.normal(jax.random.key(4), (b, h, t, d_v), jnp.float32)

    def kernels(q, k_nope, k_rope, v):
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, h, t, rope))], axis=-1)
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16).astype(jnp.float32) * g)

    def equations(q, k_nope, k_rope, v):
        q, k_nope, k_rope, v = (a.astype(jnp.float32) for a in (q, k_nope, k_rope, v))
        s = (jnp.einsum("bhqd,bhkd->bhqk", q[..., :nope], k_nope)
             + jnp.einsum("bhqd,bkd->bhqk", q[..., nope:], k_rope[:, 0])) * (nope + rope) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v) * g)

    got, got_grads = jax.value_and_grad(kernels, argnums=(0, 1, 2, 3))(q, k_nope, k_rope, v)
    want, want_grads = jax.value_and_grad(equations, argnums=(0, 1, 2, 3))(q, k_nope, k_rope, v)
    assert abs(float(got) - float(want)) <= atol * 40
    for a, w, name in zip(got_grads, want_grads, ("q", "k_nope", "k_rope", "v")):
        assert a.shape == w.shape and a.dtype == dtype
        np.testing.assert_allclose(a.astype(jnp.float32), w.astype(jnp.float32), atol=atol * 4,
                                   err_msg=name)


@pytest.mark.parametrize("shape,kv_heads,window", [
    ((2, 4, 64, 16), None, None), ((1, 4, 64, 16), 2, 24)])
def test_equal_widths_are_what_they_were(shape, kv_heads, window):
    """With one width the call takes no Mosaic parameter (its program is the
    one it was: the gradient's jaxpr at the three cells' shapes equals the
    parent commit's, compared by hand in PR 32), and output and gradients
    equal the reference as they did."""
    q, k, v = _qkv(shape, shape[-1], kv_heads)
    assert att._mosaic_params(jnp.bfloat16, (8192, 128), (8192, 128)) is None
    assert att._mosaic_params(jnp.float32, (2048, 128), (2048, 128)) is None
    assert att._mosaic_params(jnp.bfloat16, (8192, 192), (8192, 128)).vmem_limit_bytes == (
        2 * 8192 * (256 + 128) * 2 + 8 * 2 ** 20)
    out = flash_attention(q, k, v, True, None, 16, 16, window)
    np.testing.assert_allclose(out, attention_reference(q, k, v, causal=True, window=window),
                               atol=2e-5)
    jaxpr = str(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16, window))(
        q, k, v))
    assert "compiler_params=None" in jaxpr or "vmem_limit" not in jaxpr
    got = _grads(lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16, window), q, k, v)
    for a, b in zip(got, _grads(attention_reference, q, k, v, causal=True, window=window)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_attn_call_notes_carry_both_widths():
    q, k, v = _qkv((1, 2, 96, 40), 8, seed=5)
    jax.grad(lambda q: flash_attention(q, k, v, True, None, 32, 32).sum())(q)
    notes = [n for n in trace.program_notes("attn/call") if n["shape"] == (1, 2, 96, 40)]
    assert {n["kernel"] for n in notes} == {"fwd", "dkv"}  # dkv writes dq too
    assert all((n["d_qk"], n["d_v"]) == (40, 8) for n in notes)
    fwd = next(n for n in notes if n["kernel"] == "fwd")  # the backward picks its own tiles
    assert (fwd["tile"], fwd["tiles_visited"], fwd["tiles_total"]) == ((32, 32), 6, 9)
    q, k, v = _qkv((1, 2, 96, 8), 8, seed=5)
    flash_attention(q, k, v, True, None, 32, 32)
    same = [n for n in trace.program_notes("attn/call") if n["shape"] == (1, 2, 96, 8)]
    assert same and all((n["d_qk"], n["d_v"]) == (8, 8) for n in same)


def test_mismatched_widths_are_refused():
    q, k, v = _qkv((1, 2, 32, 24), 16)
    with pytest.raises(Exception):
        flash_attention(q, k[..., :16], v, True, None, 16, 16)
