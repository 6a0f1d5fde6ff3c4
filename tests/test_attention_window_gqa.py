"""Grouped KV heads and a sliding window in the three flash kernels
(interpreter mode), against ``attention_reference``: forward, gradients,
windows that cut tiles, and the program without them left as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.obs import trace
from fedml_tpu.ops import attention as att
from fedml_tpu.ops.attention import attention_reference, flash_attention


def _qkvg(rng, h, h_kv, t_q, t_k, d=8, b=2, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.randn(b, n, t, d), dtype)
                 for n, t in ((h, t_q), (h_kv, t_k), (h_kv, t_k), (h, t_q)))


def test_reference_window_counts_the_query_and_groups_heads(rng):
    """Key j is visible to query i iff i - window < j <= i; query head n
    reads KV head n // group."""
    q, k, v, _ = _qkvg(rng, 4, 2, 6, 6, b=1)
    out = attention_reference(q, k, v, causal=True, window=2)
    for n in range(4):
        s = (q[0, n] @ k[0, n // 2].T) * 8 ** -0.5
        for i in range(6):
            keys = [j for j in range(6) if i - 2 < j <= i]
            assert keys == [j for j in (i - 1, i) if j >= 0]
            p = jax.nn.softmax(s[i, jnp.asarray(keys)])
            np.testing.assert_allclose(out[0, n, i], p @ v[0, n // 2][jnp.asarray(keys)],
                                       atol=1e-5)
    with pytest.raises(ValueError, match="window needs causal"):
        attention_reference(q, k, v, causal=False, window=2)
    with pytest.raises(ValueError, match="do not divide"):
        attention_reference(q[:, :3], k, v, causal=True)


@pytest.mark.parametrize("h,h_kv", [(2, 2), (4, 2), (7, 1)])  # 1, 2, 7 query heads a KV head
@pytest.mark.parametrize("window", [None, 1, 5, 24, 40])
@pytest.mark.parametrize("t_q,t_k,blocks", [
    (64, 64, (16, 16)),   # the window cuts tiles inside and at their edges
    (64, 64, (8, 32)),
    (64, 64, (32, 8)),
    (32, 64, (16, 16)),   # right-aligned offset
])
def test_kernels_match_reference(rng, h, h_kv, window, t_q, t_k, blocks):
    """Forward, lse-fed backward kernels (dq; dk and dv summed over a KV
    head's query heads) against jax's gradient of the plain reference."""
    q, k, v, g = _qkvg(rng, h, h_kv, t_q, t_k)
    sm_scale = 8 ** -0.5
    out, lse = att._flash_fwd(q, k, v, True, sm_scale, *blocks, True, window)
    got = att._flash_bwd(q, k, v, out, lse, g, True, sm_scale, *blocks, True, window)
    want_out, vjp = jax.vjp(
        lambda q, k, v: attention_reference(q, k, v, causal=True, window=window), q, k, v)
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got, vjp(g)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)])
def test_public_path_gradients(rng, dtype, tol):
    """``flash_attention`` itself (the custom VJP, its own backward tiles)
    with 7 query heads a KV head and a window shorter than the sequence."""
    q, k, v, g = _qkvg(rng, 7, 1, 128, 128, d=16, b=1, dtype=dtype)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

    got = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, True, None, 32, 32, 48)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: attention_reference(q, k, v, causal=True, window=48)),
                    argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) <= tol, name


def test_window_at_least_the_sequence_is_bit_equal_to_causal(rng):
    q, k, v, g = _qkvg(rng, 4, 2, 64, 64)
    for window in (64, 1000):
        a = jax.vjp(lambda *x: flash_attention(*x, True, None, 16, 16, window), q, k, v)
        b = jax.vjp(lambda *x: flash_attention(*x, True, None, 16, 16), q, k, v)
        np.testing.assert_array_equal(a[0], b[0])
        for x, y in zip(a[1](g), b[1](g)):
            np.testing.assert_array_equal(x, y)


def test_equal_heads_and_no_window_is_the_program_it_was(rng):
    """``window=None`` with equal head counts is decided on static values:
    the traced program names no window, divides no head index, and writes
    dK and dV straight from the kernel in the input's dtype."""
    q, k, v, _ = _qkvg(rng, 2, 2, 64, 64, dtype=jnp.bfloat16)
    loss = lambda *x: jnp.sum(flash_attention(*x, True, None, 16, 16).astype(jnp.float32))  # noqa: E731
    plain = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    grouped = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: loss(q, k[:, :1], v[:, :1]), argnums=(0, 1, 2)))(q, k, v))
    assert "f32[4,64,8]" in grouped and "f32[4,64,8]" not in plain  # the f32 parts of dK, dV


def test_tile_ranges_by_hand():
    """T 8192, window 4096, the cell's tiles: forward 256 x 1024, backward
    512 x 512."""
    fwd = [att._fwd_kb_range(i, 256, 1024, 0, 8, True, 4096) for i in range(32)]
    assert fwd[0] == (0, 1) and fwd[15] == (0, 4) and fwd[16] == (0, 5)
    assert fwd[20] == (1, 6) and fwd[31] == (3, 8)
    assert sum(hi - lo for lo, hi in fwd) == 120
    assert sum(hi - lo for lo, hi in (att._fwd_kb_range(i, 256, 1024, 0, 8, True, None)
                                       for i in range(32))) == 144
    # dq, query block 10 (rows 5120 .. 5631): keys 1025 .. 5631 are visible,
    # blocks 2 .. 10; block 2 is cut by the window, 3 .. 9 whole, 10 by the diagonal
    assert att._dq_kb_ranges(10, 512, 512, 0, 16, 4096) == (2, 3, 10, 11)
    assert att._dq_kb_ranges(10, 512, 512, 0, 16, None) == (0, 0, 10, 11)
    # dkv, key block 2 (columns 1024 .. 1535): queries 1024 .. 5630 see it,
    # blocks 2 .. 10; block 2 is cut by the diagonal, 3 .. 9 whole, 10 by the window
    assert att._dkv_qb_ranges(2, 512, 512, 0, 16, 4096) == (2, 3, 10, 11)
    assert att._dkv_qb_ranges(2, 512, 512, 0, 16, None) == (2, 3, 16, 16)
    for ranges, n in ((att._dq_kb_ranges, 3), (att._dkv_qb_ranges, 3)):
        both = [ranges(i, 512, 512, 0, 16, 4096) for i in range(16)]
        assert sum(r[n] - r[0] for r in both) == 136 - 28  # 16 x 17 / 2 less the hidden 7 x 8 / 2


def test_attention_calls_are_noted_with_their_tiles(rng):
    """Tracing records one ``attn/call`` note a kernel and kind, tracer or
    none: kind, grouping, tiles visited of the tiles in the square."""
    q, k, v, _ = _qkvg(rng, 4, 2, 64, 64, d=16, b=1)
    tracer = trace.install()
    try:
        jax.jit(jax.grad(lambda *x: jnp.sum(flash_attention(*x, True, None, 16, 16, 24)))).lower(
            q, k, v)
    finally:
        trace.uninstall()
    mine = [n for n in trace.program_notes("attn/call")
            if n["shape"] == (1, 4, 64, 16) and n["window"] == 24]
    assert {n["kernel"] for n in mine} == {"fwd", "dkv", "dq"}
    fwd = next(n for n in mine if n["kernel"] == "fwd")
    assert fwd["kind"] == "window" and fwd["q_heads_per_kv_head"] == 2
    # 16 x 16 tiles of a 64 square: rows of blocks see 1, 2, 3 (24 keys back
    # reach two blocks behind only in part), 3 blocks
    assert (fwd["tiles_visited"], fwd["tiles_total"], fwd["tile"]) == (9, 16, (16, 16))
    events = [e for e in tracer.events() if e["name"] == "attn/call"]
    assert len(events) == 3 and events[0]["args"]["kind"] == "window"
