"""``ops/eva.py`` and what it asks of ``ops/attention.py``, on the CPU in
float32: the flash call that hands out its log-sum-exp (output, log-sum-exp
and the gradients through both) against ``attention_reference``; the staircase
mask with ``t_q != t_k`` at tiles that do and do not divide its steps, and the
tile ranges the kernels walk against the mask itself; the chunk summaries and
the merge against the benchmark's plain reference; EVA attention whole."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import eva_lm as reference
from fedml_tpu.ops import attention as att
from fedml_tpu.ops import eva


def _rnd(seed, *shape):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def _weighted(fn, w_out, w_lse):
    """A scalar of both outputs; rows that see no key (lse about -5e29 in the
    kernel, -1e30 in the oracle) take no part."""
    def loss(q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(out * w_out) + jnp.sum(jnp.where(lse < -1e20, 0.0, lse) * w_lse)
    return loss


@pytest.mark.parametrize("t_q, t_k, kwargs", [
    (64, 64, dict(causal=True, block_q=16, block_k=16)),  # the accepted mask, lse handed out
    (128, 32, dict(stair=(32, 8))),  # tiles of a step: none cut
    (128, 32, dict(stair=(32, 8), block_q=64, block_k=32)),  # tiles wider than a step: all cut
    (128, 32, dict(stair=(32, 8), block_q=16, block_k=16)),  # a query tile inside a step
    (96, 24, dict(stair=(24, 6), block_q=48, block_k=24)),  # steps that are no power of two
])
def test_the_lse_call_equals_the_oracle_forward_and_backward(t_q, t_k, kwargs):
    q, k, v = _rnd(1, 1, 2, t_q, 16), _rnd(2, 1, 2, t_k, 16), _rnd(3, 1, 2, t_k, 16)
    ref_kwargs = {a: b for a, b in kwargs.items() if a in ("causal", "stair")}
    flash = lambda q, k, v: att.flash_attention_lse(q, k, v, **kwargs)  # noqa: E731
    plain = lambda q, k, v: att.attention_reference(q, k, v, with_lse=True,  # noqa: E731
                                                    **ref_kwargs)
    with jax.default_matmul_precision("highest"):
        (out, lse), (want, want_lse) = flash(q, k, v), plain(q, k, v)
        seen = want_lse > -1e20
        np.testing.assert_allclose(out, want, atol=2e-6)
        np.testing.assert_allclose(jnp.where(seen, lse, 0.0), jnp.where(seen, want_lse, 0.0),
                                   atol=2e-6)
        if "stair" in kwargs:  # the first step's rows see nothing: zeros and a floor
            assert not bool(seen[:, :, :kwargs["stair"][0]].any())
            assert float(jnp.abs(out[:, :, :kwargs["stair"][0]]).max()) == 0.0
            assert float(lse[:, :, :kwargs["stair"][0]].max()) < -1e29
        w_out, w_lse = _rnd(4, 1, 2, t_q, 16), _rnd(5, 1, 2, t_q)
        got = jax.grad(_weighted(flash, w_out, w_lse), (0, 1, 2))(q, k, v)
        want = jax.grad(_weighted(plain, w_out, w_lse), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_a_call_without_the_new_arguments_is_the_accepted_call():
    """``flash_attention`` traces to the same jaxpr whether or not the new
    code is there to be asked: no ``stair`` reaches either kernel."""
    q = _rnd(1, 1, 2, 64, 16)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        att.flash_attention(q, q, q, True, None, 16, 16))))(q))
    assert "stair" not in text and text.count("pallas_call") == 2
    with pytest.raises(ValueError, match="excludes causal"):
        att.flash_attention_lse(q, q, q, True, None, None, None, None, (32, 8))


@pytest.mark.parametrize("block_q, block_k", [(512, 128), (512, 512), (256, 64), (384, 96),
                                               (1024, 128), (2048, 512)])
def test_the_staircases_tile_ranges_are_the_masks(block_q, block_k):
    """At the published steps (2,048 x 128 over 8,192 x 512) the forward's key
    blocks and the backward's query blocks: every tile outside the ranges is
    hidden whole, every tile in a whole range is seen whole, and the cut
    ranges are empty when the blocks divide the steps."""
    stair, t_q, t_k = (2048, 128), 8192, 512
    if t_q % block_q or t_k % block_k:
        block_q, block_k = 512, 128
    nq, nk = t_q // block_q, t_k // block_k
    mask = (np.arange(t_k)[None] < stair[1] * (np.arange(t_q)[:, None] // stair[0]))
    tiles = mask.reshape(nq, block_q, nk, block_k).transpose(0, 2, 1, 3)
    some, every = tiles.any((2, 3)), tiles.all((2, 3))
    for i in range(nq):
        start, whole_start, whole_end, last = att._stair_kb_ranges(i, block_q, block_k, nk, stair)
        assert (start, whole_start) == (0, 0)
        assert every[i, :whole_end].all() and not some[i, last:].any()
        assert some[i, whole_end:last].all() and not every[i, whole_end:last].any()
    for j in range(nk):
        first, first_whole, end_whole, end = att._stair_qb_ranges(j, block_q, block_k, nq, stair)
        assert (end_whole, end) == (nq, nq)
        assert every[first_whole:, j].all() and not some[:first, j].any()
        assert some[first:first_whole, j].all() and not every[first:first_whole, j].any()
    cuts = att._stair_cuts(block_q, block_k, stair)
    assert cuts or not (some & ~every).any()  # a cut tile implies the masked loops
    if not cuts:
        assert (some == every).all() and some.sum() * 16 == 6 * nq * nk


def test_the_blocks_of_a_staircase_call_cut_no_tile_at_the_published_steps():
    assert att._fwd_blocks(8192, 512, jnp.bfloat16, stair=(2048, 128)) == (512, 128)
    assert att._bwd_blocks(8192, 512, jnp.bfloat16, (512, 128), (2048, 128)) == (512, 128)
    # a step that is no whole number of sublane tiles keeps the accepted choice
    assert att._fwd_blocks(128, 32, jnp.bfloat16, stair=(32, 8)) == (32, 32)
    assert att._fwd_blocks(2048, 2048, jnp.bfloat16) == (512, 512)


def test_summaries_and_merge_equal_the_plain_reference():
    h, t, d, chunk = 3, 64, 16, 4
    k, v, phi, mu = _rnd(1, 1, h, t, d), _rnd(2, 1, h, t, d), 2.0 * _rnd(3, h, d), _rnd(4, h, d)
    arch = reference.Arch(num_heads=h, window=16, chunk=chunk, pred_heads=1, rope_theta=1e5,
                          rms_eps=1e-5)
    k_sum, v_sum = eva.chunk_summaries(k, v, phi, mu, chunk, d ** -0.5)
    want_k, want_v = reference.summaries(k[0], v[0], phi, mu, arch)
    np.testing.assert_allclose(k_sum[0], want_k, atol=1e-5)
    np.testing.assert_allclose(v_sum[0], want_v, atol=1e-5)
    no_mu, _ = reference.summaries(k[0], v[0], phi, mu, arch._replace(mu=False))
    np.testing.assert_allclose(want_k - no_mu, jnp.broadcast_to(mu[:, None], no_mu.shape), atol=1e-5)
    # two softmaxes over two key sets merged by their log-sum-exps are one over both
    q = _rnd(5, 1, h, 8, d)
    with jax.default_matmul_precision("highest"):
        out_l, lse_l = att.attention_reference(q, k, v, with_lse=True)
        out_r, lse_r = att.attention_reference(q, k_sum, v_sum, with_lse=True)
        both = att.attention_reference(q, jnp.concatenate([k, k_sum], 2),
                                       jnp.concatenate([v, v_sum], 2))
    out, w_r = eva.merge(out_l, lse_l, out_r, lse_r)
    np.testing.assert_allclose(out, both, atol=1e-5)
    assert bool(jnp.all((w_r > 0) & (w_r < 1)))
    # a row with nothing remote takes its local output whole
    out, w_r = eva.merge(out_l, lse_l, jnp.zeros_like(out_l), jnp.full_like(lse_l, -5e29))
    np.testing.assert_allclose(out, out_l)
    assert float(w_r.max()) == 0.0


@pytest.mark.parametrize("t, window, chunk", [(128, 32, 4), (64, 32, 8), (32, 32, 4)])
def test_eva_attention_equals_the_plain_reference_and_its_oracle(t, window, chunk):
    b, h, d = 1, 2, 16
    q, k, v = _rnd(1, b, h, t, d), _rnd(2, b, h, t, d), _rnd(3, b, h, t, d)
    phi, mu, w = 3.0 * _rnd(6, h, d), 0.5 * _rnd(7, h, d), _rnd(8, b, h, t, d)
    arch = reference.Arch(num_heads=h, window=window, chunk=chunk, pred_heads=1, rope_theta=1e5,
                          rms_eps=1e-5)
    run = lambda impl: (lambda *a: eva.eva_attention(  # noqa: E731
        *a, window=window, chunk=chunk, impl=impl))
    with jax.default_matmul_precision("highest"):
        (out, mass), (want, want_mass) = run("flash")(q, k, v, phi, mu), run("xla")(q, k, v, phi, mu)
        np.testing.assert_allclose(out, want, atol=3e-6)
        np.testing.assert_allclose(mass, want_mass, atol=1e-6)
        assert (float(mass) > 0) == (t > window)
        for i in range(b):
            np.testing.assert_allclose(
                out[i], reference.eva_attention(q[i], k[i], v[i], phi, mu, arch, "f32"), atol=3e-6)
        loss = lambda impl: (lambda *a: jnp.sum(run(impl)(*a)[0] * w))  # noqa: E731
        got = jax.grad(loss("flash"), (0, 1, 2, 3, 4))(q, k, v, phi, mu)
        want = jax.grad(loss("xla"), (0, 1, 2, 3, 4))(q, k, v, phi, mu)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=1e-5)
    if t > window:
        assert all(float(jnp.abs(g).max()) > 0 for g in got)
