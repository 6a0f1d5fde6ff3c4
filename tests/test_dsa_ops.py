"""``ops/dsa.py`` and the masked flash kernels (``ops/attention.py``
``flash_attention_selected``) on the CPU: the threshold selection against
``lax.top_k``'s set, the selection kernel (``ops/dsa_select.py``) against both
bit for bit, the packed set both ways, the kernels under a set with an
empty tile against ``attention_reference``, the index loss against its
definition, its pass as Mosaic kernels (``ops/dsa_index_loss.py``) against the
plain pass and the definition, and that a call without the new argument is the
accepted call
(which leaves each loss moves is ``tests/test_keye.py``'s, through the model).
Toy sizes; the kernels run interpreted."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu.ops.attention as att
from fedml_tpu.ops import dsa, dsa_index_loss, dsa_select

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


def _case_scores(case, t):
    scores, topk = _rnd(1, t, t), K
    if case == "exact_ties":
        scores = jnp.round(2 * scores) / 2 * jnp.where(_rnd(2, t, t) > 1, -0.0, 1.0)
    elif case == "all_equal":
        scores = jnp.full((t, t), -1.5)
    elif case == "topk_over_t":
        topk = 2 * t
    return scores, topk


@pytest.mark.parametrize("case", ["tie_free", "exact_ties", "all_equal", "topk_over_t"])
def test_the_selection_is_top_ks_set(case):
    """Tie-free rows, rows with exact ties (quantised scores, zeros of both
    signs), rows of one value (the lowest positions win) and ``t < topk``."""
    scores, topk = _case_scores(case, T)
    chosen, mass, _ = jax.jit(lambda s: dsa._choose(s[None], 0, topk))(scores)
    want = _top_k_set(jnp.where(scores == 0.0, 0.0, scores), topk)
    np.testing.assert_array_equal(chosen[0], want)
    assert set(np.asarray(chosen[0].sum(-1))) == set(np.minimum(np.arange(T) + 1, topk))
    if case == "all_equal":
        np.testing.assert_array_equal(chosen[0, -1, :K], True)
    if case == "topk_over_t":
        np.testing.assert_allclose(mass, 1.0, rtol=1e-6)
    else:
        assert 0.0 < float(mass[0, -1]) < 1.0 and float(mass[0, 0]) == 1.0



def _kernel_selection(scores, topk, rows, groups, tile):
    """The kernel over ``groups`` causal groups of equal rows, each against
    the keys its last query sees, ``rows`` query rows a grid step, as
    ``dsa.select`` calls it: ``(Selection, mass [1, T], flags [1, steps])``."""
    t = scores.shape[-1]
    per = t // groups
    words, mass, flags = zip(*(dsa_select.select_rows(
        scores[None, g * per:(g + 1) * per, :(g + 1) * per], g * per, topk, t, block=rows)
        for g in range(groups)))
    return (dsa.selection_from_rows(jnp.concatenate(words, 1), tile),
            jnp.concatenate(mass, 1), jnp.concatenate(flags, 1))


@pytest.mark.parametrize("groups", [1, 2, 4])
@pytest.mark.parametrize("rows", [32, 128])
@pytest.mark.parametrize("case", ["tie_free", "exact_ties", "all_equal", "topk_over_t"])
def test_the_kernels_selection_is_chooses_and_top_ks_bit_for_bit(case, rows, groups):
    """The Mosaic kernel (interpreted here) in blocks of ``rows`` x the keys of
    1, 2 and 4 causal groups of T 512 (``[32 | 128, 128 ... 512]``): packed
    rows, columns and tile counts equal to ``selection_from_mask`` of
    ``_choose``'s set and of ``lax.top_k``'s, ties to the lower position; the
    mass within float32 rounding; the tie path taken where a row ties and
    nowhere else; every block skipped where ``t < topk`` on all its rows."""
    t, tile = 512, 128
    scores, topk = _case_scores(case, t)
    got, mass, flags = jax.jit(
        lambda s: _kernel_selection(s, topk, rows, groups, tile))(scores)
    chosen, want_mass, tied = jax.jit(lambda s: dsa._choose(s[None], 0, topk))(scores)
    for want in (chosen, _top_k_set(jnp.where(scores == 0.0, 0.0, scores), topk)[None]):
        for a, b in zip(got, dsa.selection_from_mask(want, tile)):
            np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(mass, want_mass, rtol=2e-6)
    assert flags.shape == (1, t // rows)
    took = {"tie_free": {dsa_select.SEARCHED}, "exact_ties": {dsa_select.SEARCHED, dsa_select.TIED},
            "all_equal": {dsa_select.TIED}, "topk_over_t": {dsa_select.SKIPPED}}[case]
    assert set(np.asarray(flags).ravel()) <= took and (dsa_select.TIED in took) == bool(tied)
    if case == "exact_ties":
        assert (np.asarray(flags) == dsa_select.TIED).any()


def test_blocks_below_topk_skip_and_select_counts_the_tie_blocks():
    """``topk`` 64 over blocks of 32 rows: the first two blocks hold no row past
    ``topk`` and skip the search, the others search; ``select`` reports no
    tied block where no ReLU is ever zero (three heads' zeros would tie), every
    searched block on integer scores and a skipped block left out of the
    count, through the kernel as through ``_choose``."""
    scores = _rnd(1, 256, 256)
    words, _, flags = jax.jit(lambda s: dsa_select.select_rows(s[None], 0, 64, 256, block=32))(
        scores)
    np.testing.assert_array_equal(flags[0], [dsa_select.SKIPPED] * 2 + [dsa_select.SEARCHED] * 6)
    np.testing.assert_array_equal(dsa.unpack_bits(words, 256)[0], _top_k_set(scores, 64))
    qi, ki, wi = (jnp.abs(x) for x in (_rnd(4, 1, 3, 256, 8), _rnd(5, 1, 256, 8),
                                       _rnd(6, 1, 256, 3)))
    whole = lambda x: jnp.round(2 * x)  # noqa: E731  (sums of products of small integers tie)
    for impl in ("flash", "xla"):
        run = jax.jit(lambda *a, impl=impl: dsa.select(*a, K, 128, impl)[1:])
        assert float(run(qi, ki, wi)[1]) == 0.0
        mass, tie_blocks = run(whole(qi), whole(ki), whole(wi))
        assert float(tie_blocks) == 1.0 and 0.0 < float(mass) < 1.0
        # the first of two blocks holds no row past ``topk``: not searched, so not counted
        assert float(jax.jit(lambda *a, impl=impl: dsa.select(*a, 128, 128, impl)[2])(
            whole(qi), whole(ki), whole(wi))) == 1.0
    same = [jax.jit(lambda *a, impl=impl: dsa.select(*a, K, 128, impl)[0])(
        whole(qi), whole(ki), whole(wi)) for impl in ("flash", "xla")]
    for a, b in zip(*same):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows, keys, t, block, message", [
    (128, 192, 384, None, "cannot tile"),  # keys that are no whole runs of the packed lanes
    (12, 128, 128, None, "cannot tile"),  # a step of rows that is no whole sublane tile
    (128, 256, 128, None, "cannot tile"),  # more keys than the sequence has
    (8, 2 ** 18, 2 ** 18, None, "MiB"),  # what the default scoped VMEM cannot hold 8 rows of
], ids=["keys", "rows", "keys_over_t", "vmem"])
def test_a_shape_the_kernel_cannot_tile_is_refused(rows, keys, t, block, message):
    with pytest.raises(ValueError, match=message):
        dsa_select.tiling(rows, keys, t, block)
    with pytest.raises(ValueError, match=message):
        dsa_select.select_rows(jnp.zeros((1, rows, keys)), 0, K, t, block=block)


@pytest.mark.parametrize("shape, want", [
    ((512, 8192, 8192), (128, 512)),  # the cell's last causal group: 12 MiB, at the limit
    ((512, 8192, 8192, 256), (128, 512)),  # a step of 256 rows asked for is halved to fit
    ((512, 12288, 16384), (64, 512)), ((512, 16384, 16384), (64, 512)),  # past 8,192 keys fewer rows
    ((512, 2 ** 17, 2 ** 17), (8, 512)),  # down to one sublane tile
    ((128, 128, 128), (128, 128)), ((64, 256, 256), (64, 256)), ((96, 384, 3072), (96, 384)),
])
def test_a_step_takes_as_many_rows_as_the_default_vmem_holds(shape, want):
    assert dsa_select.tiling(*shape) == want
    step, keys = want[0], shape[1]
    assert 3 * step * keys * 4 <= dsa_select.VMEM_BYTES


def test_groups_of_different_steps_select_top_ks_set():
    """Four causal groups whose keys give them steps of two sizes (the limit
    lowered so that toy sizes meet it, as T 16,384 meets the real one): the
    plain path's set, and a flag a step of either size."""
    t = 512
    qi, ki, wi = (jnp.abs(x) for x in (_rnd(4, 1, 3, t, 8), _rnd(5, 1, t, 8), _rnd(6, 1, t, 3)))
    want = jax.jit(lambda *a: dsa.select(*a, K, 128, "xla"))(qi, ki, wi)
    limit, dsa_select.VMEM_BYTES = dsa_select.VMEM_BYTES, 3 * 128 * 256 * 4
    try:
        assert [dsa_select.tiling(128, keys, t)[0] for keys in (128, 256, 384, 512)] == [
            128, 128, 64, 64]
        got = jax.jit(lambda *a: dsa.select(*a, K, 128, "flash"))(qi, ki, wi)
    finally:
        dsa_select.VMEM_BYTES = limit
    for a, b in zip(got[0], want[0]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-6)
    assert float(got[2]) == float(want[2]) == 0.0


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
    selection, mass, _ = jax.jit(lambda *a: dsa.select(*a, K, 128))(qi, ki, wi)
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
    # three heads' ReLUs are all zero on an eighth of the keys: early rows tie at 0.0
    assert 0.0 < float(stats["dsa/select_tie_blocks"]) <= 1.0
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()) + 1e-7)
    # "xla" runs the same set through attention_reference
    out_x, _ = jax.jit(lambda *a: dsa.sparse_attention(
        *a, topk=K, impl="xla", with_loss=False))(q, k, v, qi, ki, wi)
    np.testing.assert_allclose(
        out_x, jax.jit(lambda *a: dsa.dsa_reference(*a, topk=K)[0])(q, k, v, qi, ki, wi), atol=2e-6)
    notes = {n.get("impl"): n for n in att.trace.program_notes("dsa/call")
             if n["shape"] == (2, 4, t, 8) and n["tile"] == (T, T)}
    note = notes["flash"]
    assert (note["topk"], note["index_heads"], note["index_dim"], note["kv_heads"]) == (K, 3, 8, 2)
    assert note["select"] == dsa.SELECT_IMPL["flash"] == "mosaic"
    assert notes["xla"]["select"] == dsa.SELECT_IMPL["xla"] == "radix"
    assert (note["index_loss"], notes["xla"]["index_loss"]) == ("mosaic", "xla")
    assert note["selection_bytes"] == 2 * (2 * t * (t // 32) + 4) * 4


# -- the index loss's pass as Mosaic kernels ----------------------------------

# (batch, T, topk, queries x keys of a tile or None for the module's, dtype, quantised indexer)
LOSS_CASES = {
    "two_groups_batch_of_two": (2, 256, K, (128, 128), jnp.float32, False),
    "four_groups": (1, 512, K, (128, 256), jnp.float32, False),
    "rows_below_topk": (1, 256, 512, (128, 128), jnp.float32, False),
    "rows_that_tie": (1, 256, K, (128, 64), jnp.float32, True),
    "bfloat16": (2, 256, K, (128, 128), jnp.bfloat16, False),
    "the_modules_tiles": (1, 256, K, None, jnp.float32, False),
}


@pytest.mark.parametrize("case", LOSS_CASES)
def test_the_index_loss_kernels_against_the_plain_pass_and_the_definition(monkeypatch, case):
    """``L_I``, ``d_qI``, ``d_kI`` and ``d_wI`` from the two kernels
    (interpreted here) against ``impl`` "xla" on the same operands (the set,
    ``q``, ``k`` and the flash kernel's log-sum-exp) and against
    ``dsa_reference`` differentiated by jax: two and four causal groups of the
    plain pass, a batch of two, every row with ``t < topk`` (each takes all it
    sees), an indexer of small integers (rows tie at their threshold, and at
    0.0), bfloat16 operands, and the module's own tiles."""
    b, t, topk, block, dtype, quantised = LOSS_CASES[case]
    monkeypatch.setattr(dsa, "LOSS_ROWS", 128)
    if block:
        monkeypatch.setattr(dsa_index_loss, "ROWS", block[0])
        monkeypatch.setattr(dsa_index_loss, "KEYS", block[1])
    assert len(dsa._causal_groups(t, 128)) == (4 if t == 512 else 2)
    q, k, v = _rnd(1, b, 4, t, 8), _rnd(2, b, 2, t, 8), _rnd(3, b, 2, t, 8)
    qi, ki, wi = _rnd(4, b, 3, t, 8), _rnd(5, b, t, 8), 0.3 * _rnd(6, b, t, 3)
    if quantised:
        qi, ki, wi = jnp.round(2 * qi), jnp.round(2 * ki), jnp.round(4 * jnp.abs(wi)) / 4
    q, k, v, qi, ki, wi = (x.astype(dtype) for x in (q, k, v, qi, ki, wi))

    def program(impl):
        def run(q, k, v, qi, ki, wi):
            selection, _, tie_blocks = dsa.select(qi, ki, wi, topk, 128, "flash")
            _, lse = att.flash_attention_selected(q, k, v, selection, 8 ** -0.5)
            loss = lambda qi, ki, wi: dsa.index_loss(  # noqa: E731
                qi, ki, wi, q, k, lse, selection, 8 ** -0.5, impl)
            return jax.value_and_grad(loss, (0, 1, 2))(qi, ki, wi), tie_blocks
        return jax.jit(run)(q, k, v, qi, ki, wi)

    reference = jax.jit(jax.value_and_grad(
        lambda qi, ki, wi: dsa.dsa_reference(q, k, v, qi, ki, wi, topk=topk)[1], (0, 1, 2)))
    with jax.default_matmul_precision("highest"):
        (got, got_g), tie_blocks = program("flash")
        (plain, plain_g), _ = program("xla")
        want, want_g = reference(qi, ki, wi)
    assert float(tie_blocks) == 1.0 or not quantised  # every searched block holds a tied row
    rough = dtype == jnp.bfloat16  # the gradients leave in the operands' dtype
    np.testing.assert_allclose(got, plain, rtol=2e-6)
    np.testing.assert_allclose(got, want, rtol=2e-2 if rough else 2e-5)
    assert float(want) > 0.0
    for a, same, ref in zip(got_g, plain_g, want_g):
        assert a.dtype == same.dtype == dtype and a.shape == ref.shape
        a, same, ref = (np.asarray(x, np.float32) for x in (a, same, ref))
        np.testing.assert_allclose(a, same, atol=(1e-2 if rough else 2e-6) * np.abs(same).max())
        if not quantised:  # the definition's ``where(scores == 0.0, 0.0, scores)`` passes no
            # gradient at a score of exactly 0.0; the pass, plain or kernel, does
            np.testing.assert_allclose(
                a, ref, atol=(2e-2 if rough else 2e-5) * np.abs(ref).max() + 1e-9)


@pytest.mark.parametrize("t, dtype, block, want", [
    (8192, jnp.bfloat16, None, (128, 512)),  # the cell's
    (8192, jnp.bfloat16, (256, 512), ValueError),  # sixteen [512, 256] float32 tiles are 8 MiB
    (8192, jnp.bfloat16, (256, 256), (256, 256)),
    (16384, jnp.bfloat16, None, ValueError),  # d_kI whole and a tile's words pass what it may hold
    (2048, jnp.bfloat16, None, (128, 512)),
    (256, jnp.float32, None, (128, 256)), (384, jnp.float32, None, (128, 384)),
    (96, jnp.float32, None, (96, 96)),  # one run of packed lanes, one tile
    (1000, jnp.bfloat16, None, ValueError),  # no divisor of whole 16-row tiles
], ids=["cell", "tiles_too_large", "square_tiles", "twice_the_cell", "a_quarter", "t256", "t384",
        "t96", "t1000"])
def test_the_index_loss_kernels_tiles_and_the_shapes_they_refuse(monkeypatch, t, dtype, block,
                                                                 want):
    if block:
        monkeypatch.setattr(dsa_index_loss, "ROWS", block[0])
        monkeypatch.setattr(dsa_index_loss, "KEYS", block[1])
    args = (t, 32, 4, 128, 16, 64, dtype, dtype)
    if want is ValueError:
        with pytest.raises(ValueError, match="dsa|flash attention"):
            dsa_index_loss.tiling(*args)
    else:
        assert dsa_index_loss.tiling(*args) == want
        assert t % want[0] == 0 and want[0] % att.selection_layout(t)[0] == 0


def _abstract_loss(t, impl, dtype=jnp.bfloat16):
    """The jaxpr of the index loss and its three gradients at the cell's
    widths and a length of ``t``, from shapes alone."""
    on = jax.ShapeDtypeStruct
    words = on((1, t, t // 32), jnp.int32)
    selection = att.Selection(words, words, on((1, t // 512, t // 512), jnp.int32))
    loss = lambda qi, ki, wi, q, k, lse, selection: dsa.index_loss(  # noqa: E731
        qi, ki, wi, q, k, lse, selection, 128 ** -0.5, impl)
    return jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(
        on((1, 16, t, 64), dtype), on((1, t, 64), dtype), on((1, t, 16), dtype),
        on((1, 32, t, 128), dtype), on((1, 4, t, 128), dtype), on((1, 32, t), jnp.float32),
        selection)


def _outside_kernels(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, a kernel's body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _outside_kernels(sub)


def _scores_like(jaxpr):
    """Shapes of the float32 ``[.., heads, rows, keys]`` arrays outside a kernel."""
    return [v.aval.shape for e in _outside_kernels(jaxpr.jaxpr) for v in e.outvars
            if v.aval.dtype == jnp.float32 and len(v.aval.shape) >= 4
            and v.aval.shape[-1] >= 2048 and v.aval.shape[-2] >= 128]


def _largest_float32(jaxpr):
    return max(int(np.prod(v.aval.shape)) for e in _outside_kernels(jaxpr.jaxpr)
               for v in e.outvars if v.aval.dtype == jnp.float32)


def test_the_flash_path_writes_no_heads_rows_keys_array_and_xla_is_the_parents_pass():
    """At the cell's shape the ``flash`` path is two kernels and no float32
    array outside them is larger than ``d_kI`` (``[8192, 64]``), where the
    plain pass forms ``[1, 4, 8, 256, keys]`` and ``[1, 16, 256, keys]``
    float32 arrays a step; ``impl`` "xla" holds no kernel and traces to the
    parent's jaxpr (PR 49's: the same primitives with the same result shapes
    in the same order, at a toy shape; the digest is of the parent's tree)."""
    flash, plain = _abstract_loss(8192, "flash"), _abstract_loss(8192, "xla")
    kernels = [e.params["name"] for e in _outside_kernels(flash.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert kernels == [dsa_index_loss.LSE_NAME, dsa_index_loss.NAME]
    # the largest either way: d_qI in float32 while the backward rule scales it
    assert _scores_like(flash) == [] and _largest_float32(flash) == 16 * 8192 * 64
    assert {(1, 4, 8, 256, 8192), (1, 16, 256, 8192)} <= set(_scores_like(plain))
    assert _largest_float32(plain) == 32 * 256 * 8192
    assert not [e for e in _outside_kernels(plain.jaxpr) if e.primitive.name == "pallas_call"]
    on = jax.ShapeDtypeStruct
    words = on((2, 512, 128), jnp.int32)
    toy = jax.make_jaxpr(jax.value_and_grad(
        lambda qi, ki, wi, q, k, lse, s: dsa.index_loss(qi, ki, wi, q, k, lse, s, 0.35, "xla"),
        (0, 1, 2)))(
        on((2, 3, 512, 8), jnp.float32), on((2, 512, 8), jnp.float32), on((2, 512, 3), jnp.float32),
        on((2, 4, 512, 8), jnp.float32), on((2, 2, 512, 8), jnp.float32),
        on((2, 4, 512), jnp.float32), att.Selection(words, words, on((2, 4, 4), jnp.int32)))
    lines = [e.primitive.name + ":" + ",".join(str(v.aval.shape) for v in e.outvars)
             for e in _outside_kernels(toy.jaxpr)]
    assert len(lines) == 255
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] == "a291d77b198609e7"


@pytest.mark.parametrize("t, dtype", [(16384, jnp.bfloat16), (1000, jnp.bfloat16)],
                         ids=["vmem", "sublanes"])
def test_a_shape_the_index_loss_kernels_cannot_tile_takes_the_plain_path(t, dtype):
    """By the shape alone, no knob: the note says which path a call took."""
    q, k, qi = (jax.ShapeDtypeStruct(s, dtype) for s in (
        (1, 32, t, 128), (1, 4, t, 128), (1, 16, t, 64)))
    assert dsa.index_loss_impl("flash", q, k, qi) == "xla"
    assert dsa.index_loss_impl("xla", q, k, qi) == "xla"
    small = [jax.ShapeDtypeStruct((*x.shape[:2], 8192, *x.shape[3:]), dtype) for x in (q, k, qi)]
    assert dsa.index_loss_impl("flash", *small) == "mosaic"
    if t == 16384:
        jaxpr = _abstract_loss(t, "flash")
        assert not [e for e in _outside_kernels(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
        assert _largest_float32(jaxpr) == 32 * 256 * t
