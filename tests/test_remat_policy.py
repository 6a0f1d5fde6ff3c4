"""The blocks' checkpoint policy (``fedml_tpu/ops/remat.py``): a decoder
with ``remat=True`` keeps the named values and its backward pass runs no
flash forward kernel a second time; the tags are identities elsewhere.
Counted in jaxprs on the CPU: no timing, no memory."""

import collections
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.models.moe_transformer import MoETransformerLM
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.obs import trace
from fedml_tpu.ops import remat

T, D, F, E, K, V = 32, 64, 32, 8, 2, 96
KINDS = ("global", "window", "window")


def _moe_lm(**over):
    return MoETransformerLM(**{**dict(
        vocab_size=V, embed_dim=D, layer_kinds=KINDS, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=E, experts_per_token=K, expert_dim=F, window=8, attn_impl="flash"), **over})


def _dense_lm(**over):
    return TransformerLM(**{**dict(
        vocab_size=V, embed_dim=D, num_layers=2, num_heads=4, max_len=T, attn_impl="flash"),
        **over})


MODELS = {"moe": (_moe_lm, len(KINDS)), "dense": (_dense_lm, 2)}


def _loss_of(model, seed=0):
    tokens = jnp.asarray(np.random.RandomState(seed).randint(0, V, (2, T + 1)), jnp.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    params = dict(model.init(jax.random.key(seed), x)["params"])
    # a wide embedding, so that routing follows the token (tests/test_moe_transformer.py)
    params["tok_embed"] = {"embedding": 50.0 * params["tok_embed"]["embedding"]}

    def loss(params):
        logits = model.apply({"params": params}, x)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, y))

    return loss, params


def _equations(jaxpr, out=None):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold, in
    order, as ``(primitive, kernel name or None, output avals)``."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        kernel = None
        if eqn.primitive.name == "pallas_call":
            kernel = eqn.params["name"]  # None for megablox' grouped products
        out.append((eqn.primitive.name, kernel, tuple(str(v.aval) for v in eqn.outvars)))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _equations(inner, out)
    return out


def _kernel_calls(loss, params):
    eqns = _equations(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    return collections.Counter(kernel or prim for prim, kernel, _ in eqns)


@pytest.mark.parametrize("policy", ["by_name", "bare"])
@pytest.mark.parametrize("family", sorted(MODELS))
def test_backward_runs_no_second_flash_forward(family, policy, monkeypatch):
    """``num_layers`` forward kernel calls in the gradient's jaxpr, as with
    remat off, and ``num_layers`` of the one backward kernel; under a bare
    ``nn.remat`` (the control: what the models did before) twice the forward
    calls. The routed layer's two sorts are not run twice either, and of the
    three grouped products only the last one is."""
    if policy == "bare":
        monkeypatch.setattr(remat, "block", lambda cls, **kw: nn.remat(cls, **kw))
    build, layers = MODELS[family]
    plain = _kernel_calls(*_loss_of(build()))
    got = _kernel_calls(*_loss_of(build(remat=True)))
    assert plain["flash_fwd"] == layers
    assert got["flash_fwd"] == (2 * layers if policy == "bare" else layers)
    assert got["flash_bwd_dkv"] == layers and "flash_bwd_dq" not in got
    if family == "moe":
        again = 1 if policy == "bare" else 0
        assert got["sort"] == plain["sort"] * (1 + again) == 2 * layers * (1 + again)
        # the grouped products (megablox' unnamed pallas calls): forward 3 gmm, backward
        # 3 gmm + 3 tgmm a layer; the recomputed forward adds 3, or 1 (the down product)
        assert plain["pallas_call"] == 9 * layers
        assert got["pallas_call"] == (12 if policy == "bare" else 10) * layers


def test_policy_reaches_the_kernel_under_a_sharded_plan():
    """Under an active multi-device mesh the kernel sits in a ``shard_map``
    (``flash_attention_head_parallel``); the policy is applied inside it."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("clients", "model"))
    with mesh:
        got = _kernel_calls(*_loss_of(_dense_lm(remat=True)))
    assert got["shard_map"] > 0
    assert got["flash_fwd"] == got["flash_bwd_dkv"] == 2 and "flash_bwd_dq" not in got


@pytest.mark.parametrize("kinds", [("global",), ("window",), KINDS])
def test_gradients_equal_those_without_remat(kinds):
    """Exactly: a kept value is the value the recompute would have produced,
    and on the CPU nothing is fused differently around it. Grouped KV heads
    (4 query heads on 2), a global and a window layer, alone and mixed."""
    loss, params = _loss_of(_moe_lm(layer_kinds=kinds))
    loss_remat, _ = _loss_of(_moe_lm(layer_kinds=kinds, remat=True))
    want_loss, want = jax.jit(jax.value_and_grad(loss))(params)
    got_loss, got = jax.jit(jax.value_and_grad(loss_remat))(params)
    assert float(got_loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(want)[0], jax.tree.leaves(got)):
        assert float(jnp.max(jnp.abs(a))) > 0, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def test_dense_gradients_equal_those_without_remat():
    loss, params = _loss_of(_dense_lm())
    loss_remat, _ = _loss_of(_dense_lm(remat=True))
    want, got = jax.jit(jax.grad(loss))(params), jax.jit(jax.grad(loss_remat))(params)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(a, b)


def test_kept_note_names_and_bytes(monkeypatch):
    """One ``remat/kept`` note a name, with the bytes a layer holds for it;
    none from a model whose remat is off."""
    monkeypatch.setattr(trace, "_program_notes", {})
    loss, params = _loss_of(_moe_lm())
    jax.make_jaxpr(jax.grad(loss))(params)
    assert trace.program_notes(remat.NOTE) == []
    loss, params = _loss_of(_moe_lm(remat=True))
    jax.make_jaxpr(jax.grad(loss))(params)
    notes = {n["kept"]: n for n in trace.program_notes(remat.NOTE)}
    rows = 2 * T * K  # batch 2, every assignment a row; float32 throughout
    q, kv = 2 * 4 * T * 16 * 4, 2 * 2 * T * 16 * 4  # 4 query heads on 2 KV heads of 16
    assert {k: n["bytes"] for k, n in notes.items()} == {
        "attn/q": q, "attn/k": kv, "attn/v": kv, "attn/out": q, "attn/lse": 2 * 4 * T * 4,
        remat.MOE_ORDER: rows * 4, remat.MOE_POS: rows * 4, remat.MOE_SIZES: E * 4,
        remat.MOE_GATE_OUT: rows * F * 4, remat.MOE_UP_OUT: rows * F * 4,
        remat.MOE_IDS: rows * 4}
    # every name but a delta-attention, short-convolution, EVA or sparse-attention block's:
    # this model has none
    assert set(notes) == (set(remat.KEPT) - set(remat.KDA_KEPT) - {remat.SHORTCONV_IN}
                          - set(remat.EVA_LOCAL + remat.EVA_REMOTE)
                          - set(remat.DSA_SELECTION + remat.DSA_INDEX_GRADS))
    assert notes["attn/out"]["shape"] == (2, 4, T, 16)
    assert notes[remat.MOE_GATE_OUT]["dtype"] == "float32"


@pytest.mark.parametrize("family", sorted(MODELS))
def test_tags_are_identities_without_remat(family, monkeypatch):
    """With remat off the gradient's jaxpr is the one the untagged code
    gives, apart from the ``name`` equations, which lower to nothing."""
    build, layers = MODELS[family]

    def equations():
        loss, params = _loss_of(build())
        return _equations(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)

    tagged = equations()
    monkeypatch.setattr(remat, "checkpoint_name", lambda x, name: x)
    bare = equations()
    names = [e for e in tagged if e[0] == "name"]
    assert len(names) >= 2 * layers and not any(e[0] == "name" for e in bare)
    assert [e for e in tagged if e[0] != "name"] == bare

    # and so is the lowered program, the counter in private functions' names aside
    def lowered():
        loss, params = _loss_of(build())
        return re.sub(r"(@\w+?)_\d+", r"\1", jax.jit(jax.grad(loss)).lower(params).as_text())

    untagged = lowered()
    monkeypatch.undo()
    assert lowered() == untagged
