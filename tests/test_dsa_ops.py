"""``ops/dsa.py`` and the masked flash kernels (``ops/attention.py``
``flash_attention_selected``) on the CPU: the threshold selection against
``lax.top_k``'s set, the packed set both ways, the kernels under a set with an
empty tile against ``attention_reference``, the index loss against its
definition, and that a call without the new argument is the accepted call
(which leaves each loss moves is ``tests/test_keye.py``'s, through the model).
Toy sizes; the kernels run interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu.ops.attention as att
from fedml_tpu.ops import dsa

T, K = 128, 16


def _rnd(i, *shape):
    return jax.random.normal(jax.random.key(i), shape, jnp.float32)


def _top_k_set(scores, topk):
    """``lax.top_k``'s set of each causal row, by its indices."""
    t = scores.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, ids = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    rows = jnp.arange(t)[:, None]
    return jnp.zeros((t, t), bool).at[rows, ids].set(True) & causal


@pytest.mark.parametrize("case", ["tie_free", "exact_ties", "all_equal", "topk_over_t"])
def test_the_selection_is_top_ks_set(case):
    """Tie-free rows, rows with exact ties (quantised scores, zeros of both
    signs), rows of one value (the lowest positions win) and ``t < topk``."""
    scores = _rnd(1, T, T)
    topk = K
    if case == "exact_ties":
        scores = jnp.round(2 * scores) / 2 * jnp.where(_rnd(2, T, T) > 1, -0.0, 1.0)
    elif case == "all_equal":
        scores = jnp.full((T, T), -1.5)
    elif case == "topk_over_t":
        topk = 2 * T
    chosen, mass = jax.jit(lambda s: dsa._choose(s[None], 0, topk))(scores)
    want = _top_k_set(jnp.where(scores == 0.0, 0.0, scores), topk)
    np.testing.assert_array_equal(chosen[0], want)
    assert set(np.asarray(chosen[0].sum(-1))) == set(np.minimum(np.arange(T) + 1, topk))
    if case == "all_equal":
        np.testing.assert_array_equal(chosen[0, -1, :K], True)
    if case == "topk_over_t":
        np.testing.assert_allclose(mass, 1.0, rtol=1e-6)
    else:
        assert 0.0 < float(mass[0, -1]) < 1.0 and float(mass[0, 0]) == 1.0


def test_threshold_is_the_kth_largest_key_and_keys_keep_the_floats_order():
    x = jnp.concatenate([_rnd(3, 3, 61) * 1e3, jnp.array([[-0.0, 0.0, jnp.inf]] * 3)], axis=-1)
    u = dsa._ordered(x)
    assert int(u[0, 61]) + 1 == int(u[0, 62])  # -0.0 right under 0.0
    order = np.argsort(np.asarray(x), axis=-1, kind="stable")
    assert (np.diff(np.take_along_axis(np.asarray(u), order, -1).astype(np.int64)) >= 0).all()
    assert int(u.min()) > 0 and int(dsa._ordered(jnp.float32(-jnp.inf))) > 0
    for want in (1, 7, 64):
        tau = dsa._threshold(u, jnp.full((3,), want))
        np.testing.assert_array_equal(tau, np.sort(np.asarray(u), -1)[:, -want])


def test_packed_bits_and_select_against_the_reference():
    """``select`` over blocks and causal groups gives ``dsa_reference``'s set;
    the packed rows and columns unpack to it and its transpose; the tiles
    count it; the layout at the cell's length packs 32 planes of 128 lanes."""
    q, k, v = _rnd(1, 1, 2, 384, 8), _rnd(2, 1, 2, 384, 8), _rnd(3, 1, 2, 384, 8)
    qi, ki, wi = _rnd(4, 1, 3, 384, 8), _rnd(5, 1, 384, 8), _rnd(6, 1, 384, 3)
    assert dsa._causal_groups(384, 128) == [(0, 1, 128), (1, 1, 256), (2, 1, 384)]
    assert dsa._causal_groups(8192, 512) == [(4 * g, 4, 2048 * (g + 1)) for g in range(4)]
    selection, mass = jax.jit(lambda *a: dsa.select(*a, K, 128))(qi, ki, wi)
    _, _, want = jax.jit(lambda *a: dsa.dsa_reference(*a, topk=K))(q, k, v, qi, ki, wi)
    np.testing.assert_array_equal(dsa.unpack_bits(selection.rows, 384), want)
    np.testing.assert_array_equal(dsa.unpack_bits(selection.cols, 384), want.swapaxes(1, 2))
    np.testing.assert_array_equal(
        selection.tiles, want.reshape(1, 3, 128, 3, 128).sum((2, 4)))
    assert selection.rows.shape == (1, 384, 128) and 0.0 < float(mass) < 1.0
    assert att.selection_layout(8192) == (128, 32) and att.selection_layout(256) == (128, 2)
    assert att.selection_layout(64) == (64, 1) and att.selection_layout(384) == (128, 3)
    bits = _rnd(7, 2, 5, 8192) > 0
    np.testing.assert_array_equal(dsa.unpack_bits(dsa.pack_bits(bits), 8192), bits)
    with pytest.raises(ValueError, match="whole runs"):
        dsa.selection_from_mask(jnp.zeros((1, 256, 256), bool), 32)


@pytest.fixture(scope="module")
def masked_call():
    """Grouped heads (4 on 2), T 256 in tiles of 128, a set with an empty
    tile below the diagonal and rows that miss their own position."""
    q, k, v = _rnd(1, 1, 4, 256, 16), _rnd(2, 1, 2, 256, 16), _rnd(3, 1, 2, 256, 16)
    pos = jnp.arange(256)
    chosen = (pos[None] <= pos[:, None]) & (((pos[:, None] - pos[None]) % 3 == 1)
                                           | (pos[None] == 0))
    chosen = chosen & ~((pos[:, None] >= 128) & (pos[None] < 128) & (pos[None] > 0))
    chosen = chosen.at[128:, 0].set(False).at[128, 128].set(True)[None]
    return q, k, v, chosen, dsa.selection_from_mask(chosen, 128)


def test_masked_kernels_against_the_reference(masked_call):
    """Forward, log-sum-exp and the three gradients through both outputs."""
    q, k, v, chosen, selection = masked_call
    assert int(selection.tiles[0, 1, 0]) == 0 and int(selection.tiles[0, 0, 1]) == 0
    w_out, w_lse = _rnd(4, 1, 4, 256, 16), _rnd(5, 1, 4, 256)

    def weighted(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out * w_out) + jnp.sum(lse * w_lse), (out, lse)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, got), got_g = weighted(lambda q, k, v: att.flash_attention_selected(
            q, k, v, selection))(q, k, v)
        (_, want), want_g = weighted(lambda q, k, v: att.attention_reference(
            q, k, v, selected=chosen, with_lse=True))(q, k, v)
    for a, b in zip((*got, *got_g), (*want, *want_g)):
        np.testing.assert_allclose(a, b, atol=5e-6)
    notes = [n for n in att.trace.program_notes("attn/call") if n.get("kind") == "selected"]
    assert {n["kernel"] for n in notes} == {"fwd", "dkv"}
    assert all(n["tiles_visited"] == n["tiles_masked"] == 3 and n["tile"] == (128, 128)
               for n in notes if n["shape"] == (1, 4, 256, 16))
    with pytest.raises(ValueError, match="whole mask"):
        att.attention_reference(q, k, v, causal=True, selected=chosen)
    with pytest.raises(ValueError, match="as many queries as keys"):
        att.flash_attention_selected(q[:, :, :128], k, v, selection)


def test_a_call_without_the_new_argument_is_the_accepted_call():
    """``flash_attention`` and ``flash_attention_lse`` trace to jaxprs that
    name no selection: two ``pallas_call``s of the parent's operands."""
    q = _rnd(1, 1, 2, 64, 16)
    for call in (lambda q: att.flash_attention(q, q, q, True, None, 16, 16),
                 lambda q: att.flash_attention_lse(q, q, q, True, None, 16, 16)[0]):
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(call(q))))(q)
        calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
        text = str(jaxpr)
        assert [len(e.invars) for e in calls] == [3, 6] and "selected" not in text
        assert "shift_right_logical" not in text and "memory_space=smem" not in text.lower()


def test_sparse_attention_and_the_index_loss_against_the_reference(monkeypatch):
    """Output, ``L_I`` and the six gradients of ``sparse_attention`` (the set
    kept packed, the masked kernels, the index loss's own pass and its
    hand-written gradients) against ``dsa_reference`` differentiated by jax."""
    t = 2 * T  # two tiles a side, two causal groups
    monkeypatch.setattr(dsa, "TILE", T)
    q, k, v = _rnd(1, 2, 4, t, 8), _rnd(2, 2, 2, t, 8), _rnd(3, 2, 2, t, 8)
    qi, ki, wi = _rnd(4, 2, 3, t, 8), _rnd(5, 2, t, 8), 0.3 * _rnd(6, 2, t, 3)
    w_out = _rnd(7, 2, 4, t, 8)

    def program(*args):
        out, stats = dsa.sparse_attention(*args, topk=K)
        return jnp.sum(out * w_out) + stats["dsa/index_kl"], stats

    def reference(*args):
        out, kl, _ = dsa.dsa_reference(*args, topk=K)
        return jnp.sum(out * w_out) + kl, kl

    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.jit(jax.value_and_grad(program, range(6), has_aux=True))(
            q, k, v, qi, ki, wi)
        (want, kl), want_g = jax.jit(jax.value_and_grad(reference, range(6), has_aux=True))(
            q, k, v, qi, ki, wi)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(stats["dsa/index_kl"], kl, rtol=1e-5)
    assert float(kl) > 0.0 and 0.0 < float(stats["dsa/index_mass"]) < 1.0
    assert float(stats["dsa/tiles_nonempty"]) == 1.0
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()) + 1e-7)
    # "xla" runs the same set through attention_reference
    out_x, _ = jax.jit(lambda *a: dsa.sparse_attention(
        *a, topk=K, impl="xla", with_loss=False))(q, k, v, qi, ki, wi)
    np.testing.assert_allclose(
        out_x, jax.jit(lambda *a: dsa.dsa_reference(*a, topk=K)[0])(q, k, v, qi, ki, wi), atol=2e-6)
    note = [n for n in att.trace.program_notes("dsa/call") if n["shape"] == (2, 4, t, 8)
            and n["tile"] == (T, T)][-1]
    assert (note["topk"], note["index_heads"], note["index_dim"], note["kv_heads"]) == (K, 3, 8, 2)
    assert note["select"] == dsa.SELECT_IMPL
    assert note["selection_bytes"] == 2 * (2 * t * (t // 32) + 4) * 4
