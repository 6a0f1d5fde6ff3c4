"""Grouped KV heads and a sliding window in the two flash kernels
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
    """T 8192, window 4096: wide 256 x 1024 tiles, then the cell's 512 x 512,
    which the forward walks by query block and the backward by key block."""
    fwd = [att._fwd_kb_ranges(i, 256, 1024, 0, 8, 4096) for i in range(32)]
    # (start, whole_start, whole_end, last): rows 0 .. 255 see block 0 cut by
    # the diagonal; rows 5120 .. 5375 see keys 1025 .. 5375, block 1 cut by
    # the window, 2 .. 4 whole, 5 by the diagonal
    assert fwd[0] == (0, 0, 0, 1) and fwd[15] == (0, 0, 3, 4) and fwd[16] == (0, 1, 4, 5)
    assert fwd[20] == (1, 2, 5, 6) and fwd[31] == (3, 4, 7, 8)
    assert sum(r[3] - r[0] for r in fwd) == 120
    assert sum(r[3] - r[0] for r in (att._fwd_kb_ranges(i, 256, 1024, 0, 8, None)
                                     for i in range(32))) == 144
    # the forward and dq, query block 10 (rows 5120 .. 5631): keys 1025 .. 5631 are
    # visible, blocks 2 .. 10; block 2 is cut by the window, 3 .. 9 whole, 10 by the diagonal
    assert att._fwd_kb_ranges(10, 512, 512, 0, 16, 4096) == (2, 3, 10, 11)
    assert att._fwd_kb_ranges(10, 512, 512, 0, 16, None) == (0, 0, 10, 11)
    # dkv, key block 2 (columns 1024 .. 1535): queries 1024 .. 5630 see it,
    # blocks 2 .. 10; block 2 is cut by the diagonal, 3 .. 9 whole, 10 by the window
    assert att._dkv_qb_ranges(2, 512, 512, 0, 16, 4096) == (2, 3, 10, 11)
    assert att._dkv_qb_ranges(2, 512, 512, 0, 16, None) == (2, 3, 16, 16)
    for ranges, n in ((att._fwd_kb_ranges, 3), (att._dkv_qb_ranges, 3)):
        both = [ranges(i, 512, 512, 0, 16, 4096) for i in range(16)]
        assert sum(r[n] - r[0] for r in both) == 136 - 28  # 16 x 17 / 2 less the hidden 7 x 8 / 2


def _tile_mask(t_q, t_k, window):
    """The mask read straight off its definition: key j is visible to query
    i iff j <= i + (t_k - t_q) and, under a window, j > i + (t_k - t_q) - window."""
    q_pos = np.arange(t_q)[:, None] + (t_k - t_q)
    k_pos = np.arange(t_k)[None, :]
    seen = k_pos <= q_pos
    if window is not None:
        seen &= k_pos > q_pos - window
    return seen


@pytest.mark.parametrize("t_q,t_k,block_q,block_k,window,has_whole", [
    (64, 64, 16, 16, None, True),    # whole tiles, then the diagonal's
    (64, 64, 16, 16, 1, False),      # a window of one key: every visited tile is cut
    (64, 64, 16, 16, 5, False),      # under a tile
    (64, 64, 16, 16, 16, False),     # a tile (a whole one takes 16 + 16 - 1 keys)
    (64, 64, 16, 16, 40, True),      # all three ranges in the later query blocks
    (64, 64, 16, 16, 1000, True),    # over the sequence: the window's range is empty
    (64, 64, 8, 32, 24, False),      # wide tiles
    (64, 64, 32, 8, 24, False),      # tall tiles: several tiles on the diagonal
    (32, 64, 16, 16, None, True),    # t_q != t_k: right-aligned offset
    (32, 64, 16, 16, 20, False),
    (64, 32, 16, 16, None, True),    # more queries than keys: the first blocks see nothing
    (64, 32, 16, 8, 12, False),
    (48, 96, 16, 32, 50, True),      # an offset and a window that are multiples of neither side
])
def test_forward_key_block_ranges_against_the_mask(t_q, t_k, block_q, block_k, window,
                                                  has_whole):
    """``_fwd_kb_ranges`` against a brute-force reading of the mask: every
    visible pair lies in a visited tile, every visited tile holds one, a tile
    of the whole range hides no pair and a tile of a cut range hides one."""
    seen = _tile_mask(t_q, t_k, window)
    nk = t_k // block_k
    some_whole = some_cut = False
    for i in range(t_q // block_q):
        start, whole_start, whole_end, last = att._fwd_kb_ranges(
            i, block_q, block_k, t_k - t_q, nk, window)
        assert 0 <= start <= whole_start <= whole_end <= last <= nk
        for j in range(nk):
            tile = seen[i * block_q:(i + 1) * block_q, j * block_k:(j + 1) * block_k]
            assert tile.any() == (start <= j < last), (i, j)
            if whole_start <= j < whole_end:
                assert tile.all(), (i, j)
                some_whole = True
            elif start <= j < last:
                assert not tile.all(), (i, j)
                some_cut = True
    assert some_cut and some_whole == has_whole


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case,t_q,t_k,d,blocks,causal,window", [
    ("masked_only", 16, 16, 16, (16, 16), True, None),     # one tile, on the diagonal
    ("whole_only", 32, 64, 16, (16, 16), False, None),     # no mask: the whole loop alone
    ("whole_and_diagonal", 64, 64, 16, (16, 16), True, None),
    ("all_three", 64, 64, 16, (16, 16), True, 40),
    ("window_of_one", 64, 64, 16, (16, 32), True, 1),      # rows with no key in a visited tile
    ("offset_window", 32, 64, 16, (16, 16), True, 24),
    # key blocks of whole lanes: the running max and sum are kept a lane
    ("lanes_head_under", 256, 512, 16, (64, 256), True, 300),
    ("lanes_head_equal", 128, 256, 128, (64, 128), True, None),
    ("lanes_head_over", 64, 128, 256, (32, 128), False, None),
])
def test_forward_output_and_lse_match_reference(rng, dtype, tol, case, t_q, t_k, d, blocks,
                                                causal, window):
    """The forward's two outputs, with operands in the input's dtype: ``out``
    against ``attention_reference`` and ``lse`` against the log-sum-exp of the
    visible scores, 7 query heads a KV head."""
    q, k, v, _ = _qkvg(rng, 7, 1, t_q, t_k, d=d, b=1, dtype=dtype)
    sm_scale = d ** -0.5
    out, lse = att._flash_fwd(q, k, v, causal, sm_scale, *blocks, True, window)
    assert out.dtype == dtype and lse.dtype == jnp.float32 and lse.shape == (1, 7, t_q)
    want = attention_reference(q, k, v, causal=causal, window=window).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want))) <= tol
    s = jnp.einsum("bhqd,bkd->bhqk", q.astype(jnp.float32), k[:, 0].astype(jnp.float32)) * sm_scale
    if causal:
        s = jnp.where(_tile_mask(t_q, t_k, window), s, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), atol=tol, rtol=tol)


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
    assert {n["kernel"] for n in mine} == {"fwd", "dkv"}  # dkv writes dq too
    fwd = next(n for n in mine if n["kernel"] == "fwd")
    assert fwd["kind"] == "window" and fwd["q_heads_per_kv_head"] == 2
    # 16 x 16 tiles of a 64 square: rows of blocks see 1, 2, 3 (24 keys back
    # reach two blocks behind only in part), 3 blocks
    assert (fwd["tiles_visited"], fwd["tiles_total"], fwd["tile"]) == (9, 16, (16, 16))
    # a window of 24 under tiles of 16 leaves no tile whole (that takes 31 keys)
    assert fwd["tiles_masked"] == 9
    for n in mine:  # the backward's one 64 x 64 tile is cut by the diagonal and the window
        if n["kernel"] != "fwd":
            assert (n["tiles_visited"], n["tiles_masked"], n["tile"]) == (1, 1, (64, 64))
    events = [e for e in tracer.events() if e["name"] == "attn/call"]
    assert len(events) == 2 and events[0]["args"]["kind"] == "window"
    # without the window: 1 + 2 + 3 + 4 tiles, the diagonal's four run the mask;
    # without the mask: every tile, none masked
    jax.jit(lambda *x: flash_attention(*x, True, None, 16, 16)).lower(q, k, v)
    jax.jit(lambda *x: flash_attention(*x, False, None, 16, 32)).lower(q, k, v)
    # by tile too: the notes outlive a test, and other files note this shape
    glob, full = (next(n for n in trace.program_notes("attn/call")
                       if n["shape"] == (1, 4, 64, 16) and (n["kind"], n["tile"]) == want)
                  for want in (("global", (16, 16)), ("full", (16, 32))))
    assert (glob["tiles_visited"], glob["tiles_masked"], glob["tiles_total"]) == (10, 4, 16)
    assert (full["tiles_visited"], full["tiles_masked"], full["tiles_total"]) == (8, 0, 8)
