"""The Keye-VL-2.0 language model's block (``models/mla_moe_transformer.py``
mixer "dsa", router "softmax") on the CPU at a toy size: the model against the
plain reference on seeded weights, the index loss and the selection included;
which leaves each loss moves; the eight shares' routed parts add up to the
uncut layer; under ``remat`` the selection is kept by name and made once, and
a block holds the ``pallas_call``s of the parent's block of its kind."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dsa_moe_lm as reference
from fedml_tpu.core.trainer import MTP_COLLECTION, STATS_COLLECTION
from fedml_tpu.models.mla_moe_transformer import DSA, SOFTMAX, MLABlock, MLAMoETransformerLM
from fedml_tpu.obs import trace
from fedml_tpu.ops import dsa_index_loss, remat

V, D, H, HKV, DH, E, F, T, TOPK, LAYERS = 48, 32, 4, 2, 8, 8, 16, 128, 16, 2
ARCH = reference.Arch(num_heads=H, num_kv_heads=HKV, index_heads=3, topk=TOPK, top_k=2,
                      experts_first=0, rope_theta=1e7, rms_eps=1e-6)


def _model(**over):
    kw = dict(vocab_size=V, embed_dim=D, dense_layers=0, routed_layers=LAYERS, num_heads=H,
              kv_heads=HKV, head_dim=DH, num_experts=E, experts_per_token=2, expert_dim=F,
              shared_dim=0, mtp_depth=0, rope_theta=1e7, mixers=(DSA,) * LAYERS, router=SOFTMAX,
              index_heads=3, index_dim=8, index_topk=TOPK, attn_impl="flash")
    return MLAMoETransformerLM(**{**kw, **over})


@pytest.fixture(scope="module")
def seeded():
    x = jax.random.randint(jax.random.key(0), (1, T), 0, V)
    params = _model().init(jax.random.key(1), x)["params"]
    # draws that make the indexer's parts all matter: no zero bias, no unit scale
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = [leaf + 0.1 * jax.random.normal(jax.random.key(i), leaf.shape)
              if "norm" in jax.tree_util.keystr(path) else leaf
              for i, (path, leaf) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(tree, leaves), x


def _losses(model, params, x):
    """``(logits, next-token stand-in, the sown index loss, stats)``."""
    logits, state = model.apply({"params": params}, x, train=True,
                                mutable=[MTP_COLLECTION, STATS_COLLECTION])
    (owed,) = state[MTP_COLLECTION].values()
    return logits, jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0]), owed, \
        state[STATS_COLLECTION]["dsa"]


@pytest.fixture(scope="module")
def step(seeded):
    """One compiled program of the rematerialised model: the logits, the
    index loss, the counters and each loss's gradient."""
    params, x = seeded
    model = _model(remat=True)

    def both(p):
        logits, _, owed, stats = _losses(model, p, x)
        g_next = jax.grad(lambda p: _losses(model, p, x)[1])(p)
        g_index = jax.grad(lambda p: _losses(model, p, x)[2]["loss"])(p)
        return logits, owed, stats, g_next, g_index

    with jax.default_matmul_precision("highest"):
        return jax.jit(both)(params)


def test_model_against_the_plain_reference(seeded, step):
    """Logits and the summed index loss of the model (the packed set, the
    masked kernels, ``remat`` on) against ``benchmark/reference/dsa_moe_lm.py``
    (whole score rows, ``lax.top_k``), and the index loss's gradient against
    jax's of the reference."""
    params, x = seeded
    logits, owed, stats, _, g_index = step
    with jax.default_matmul_precision("highest"):
        want, want_kl = jax.jit(lambda p: reference.forward(p, x[0], ARCH))(params)
        want_g = jax.jit(jax.grad(lambda p: reference.forward(p, x[0], ARCH)[1]))(params)
    np.testing.assert_allclose(logits[0], want, atol=2e-5)
    np.testing.assert_allclose(owed["loss"], want_kl, rtol=2e-5)
    assert float(owed["weight"]) == 1.0 and float(want_kl) > 0.01
    np.testing.assert_allclose(jnp.sum(stats["index_kl"]), want_kl, rtol=2e-5)
    assert stats["index_kl"].shape == stats["index_mass"].shape == (LAYERS,)
    assert bool(jnp.all((stats["index_mass"] > 0) & (stats["index_mass"] < 1)))
    np.testing.assert_array_equal(stats["tiles_nonempty"], 1.0)
    for a, b in zip(jax.tree.leaves(g_index), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()) + 1e-8)
    # the faults the benchmark's limits are held to change the reference's answer
    for fault in ({"select": False}, {"relu": False}):
        broken = reference.forward(params, x[0], ARCH._replace(**fault))
        assert float(jnp.abs(broken[0] - want).max()) > 1e-3, fault
    assert float(reference.forward(params, x[0], ARCH._replace(index_loss=False))[1]) == 0.0


def test_each_loss_moves_its_own_leaves(step):
    """The index loss's gradient reaches the indexer's leaves (three matrices,
    a LayerNorm's scale and bias, a layer) and no other; the next-token loss's
    gradient none of them and every other leaf."""
    _, _, _, g_next, g_index = step
    size = lambda g: {jax.tree_util.keystr(path): float(jnp.abs(leaf).max())  # noqa: E731
                      for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]}
    for name, reach in size(g_next).items():
        assert (reach == 0.0) == ("indexer" in name), name
    for name, reach in size(g_index).items():
        assert (reach > 0.0) == ("indexer" in name), name
    assert len([n for n in size(g_index) if "indexer" in n]) == 5 * LAYERS


def test_the_eight_shares_add_up_to_the_uncut_layer(seeded):
    """One layer, attention whole on every chip: the routed parts of eight
    shares of one expert each (``experts_first`` i, the router 8 wide on all)
    sum to what the uncut reference's layer adds."""
    params, x = seeded
    p = params["block_0"]
    stream = params["tok_embed"]["embedding"][x[0]]
    x1, _ = reference.attention_half(stream, p, ARCH, "f32")
    want, _ = reference.moe_half(x1, p, ARCH, "f32")
    total = jnp.zeros_like(x1)
    for i in range(E):
        share = {**p, "experts": jax.tree.map(lambda w: w[i:i + 1], p["experts"])}
        block = MLABlock(True, H, None, 0, 0, 0, 0, 0, E, 2, F, 0, 1.0, i, 1, 1e7, mixer=DSA,
                         kv_heads=HKV, head_dim=DH, router=SOFTMAX, index_heads=3, index_dim=8,
                         index_topk=TOPK)
        y, _ = block.apply({"params": share}, stream[None])
        total = total + (y[0] - x1)
    np.testing.assert_allclose(total, want - x1, atol=2e-5)
    assert float(jnp.abs(want - x1).max()) > 0.05


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _counts(policy, x, params):
    """Primitive counts of a training step's jaxpr under ``policy``: "names"
    (the blocks' own), "bare" (a checkpoint that keeps the input alone)."""
    model = _model(remat=policy != "none")
    block_cls = remat.block
    if policy == "bare":
        remat.block = nn.remat
    try:
        loss = lambda p: sum(jnp.sum(v) for v in jax.tree.leaves(  # noqa: E731
            _losses(model, p, x)[:3]))
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    finally:
        remat.block = block_cls
    eqns = list(_equations(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    selecting = sum(trace.SCOPE_DSA_SELECT in str(e.source_info.name_stack)
                    for e in eqns  # the jitted kernel call (differentiation leaves an empty twin)
                    if e.params.get("name") == "_select_rows" and e.outvars)
    passes = sum(e.primitive.name == "pallas_call" and e.params["name"] == dsa_index_loss.NAME
                 for e in eqns)
    return {"select": selecting, "index_loss": passes,
            **{n: names.count(n) for n in ("pallas_call", "top_k")}}


def test_kept_names_the_selection_made_once_and_the_kernel_count(seeded):
    """A rematerialised block keeps the chosen set's three arrays and the index
    loss's three gradients beside the flash kernels' five, so its backward
    makes no second selection (one selection kernel under ``attn/dsa/select``
    a block, the plain model's count; two under a bare checkpoint), runs the
    index loss's pass once (its main kernel once a block: the second forward
    finds the three gradients kept; twice under a bare checkpoint) and runs
    no attention kernel twice: **fifteen ``pallas_call``s a block's training
    step** (the selection; the index loss's two, PR 50; the masked forward and
    backward; the routed layer's three grouped products forward, six backward
    and the down product remade: three more than the parent's "gqa" block over
    the same routed layer holds (``tests/test_lfm2_moe.py``); fourteen without
    ``remat``, twenty-one under a bare checkpoint)."""
    params, x = seeded
    plain, names, bare = (_counts(policy, x, params) for policy in ("none", "names", "bare"))
    assert (plain["select"], names["select"], bare["select"]) == (LAYERS, LAYERS, 2 * LAYERS)
    assert (plain["index_loss"], names["index_loss"], bare["index_loss"]) == (
        LAYERS, LAYERS, 2 * LAYERS)
    assert (plain["pallas_call"], names["pallas_call"], bare["pallas_call"]) == (
        14 * LAYERS, 15 * LAYERS, 21 * LAYERS)
    assert plain["top_k"] == LAYERS  # the routers' alone: the selection sorts nothing
    kept = {n["kept"] for n in trace.program_notes(remat.NOTE)}
    assert {*remat.DSA_SELECTION, *remat.DSA_INDEX_GRADS, *remat.ATTN_RESIDUALS,
            remat.MOE_IDS} <= kept
    note = [n for n in trace.program_notes("dsa/call")
            if n["shape"] == (1, H, T, DH) and n["impl"] == "flash"][-1]
    assert note["topk"] == TOPK and note["tile"] == (T, T) and note["kv_heads"] == HKV


@pytest.fixture(scope="module")
def lowered(seeded):
    params, x = seeded
    model = _model(remat=True)
    loss = lambda p: sum(jnp.sum(v) for v in jax.tree.leaves(  # noqa: E731
        _losses(model, p, x)[:3]))
    return jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)


@pytest.mark.parametrize("name", [*trace.DSA_SCOPES, *trace.MOE_SCOPES, trace.SCOPE_FLASH_FWD,
                                  trace.SCOPE_BLOCKWISE_BWD])
def test_scope_names_are_in_the_lowered_step(lowered, name):
    assert name in lowered
