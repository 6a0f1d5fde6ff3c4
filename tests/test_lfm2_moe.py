"""The LFM2 mixture-of-experts decoder (``fedml_tpu/models/mla_moe_transformer.py``
with ``mixers`` "conv" and "gqa", no shared expert, a tied head) at a toy size
on the CPU, in float32, against the benchmark's plain reference
(``benchmark/reference/conv_moe_lm.py``) on seeded weights: logits, loss,
every gradient and one round's update, remat on and off; the flash kernels at
64-wide heads under 128-wide key tiles; the shares of an expert-parallel
layer; the tied leaf's two gradients; scopes, notes and kernel counts."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import conv_moe_lm as reference
from fedml_tpu.core.trainer import STATS_COLLECTION, ClientTrainer
from fedml_tpu.models.mla_moe_transformer import CONV, GQA, MLABlock, MLAMoETransformerLM
from fedml_tpu.models.moe_transformer import GroupedAttention, rope
from fedml_tpu.obs import trace
from fedml_tpu.ops import attention as att
from fedml_tpu.ops.attention import attention_reference, flash_attention

# hidden 64; 4 query heads on 2 KV heads of 16; 3 taps; a dense layer of 128
# (convolution), then attention and two convolution layers routed: 8 experts
# top-2 of width 32, no shared expert, scale 1; T 24; a tied head over 96 ids
T, D, F, E, K, V = 24, 64, 32, 8, 2, 96
MIXERS = (CONV, GQA, CONV, CONV)


def _model(**over):
    return MLAMoETransformerLM(**{**dict(
        vocab_size=V, embed_dim=D, dense_layers=1, routed_layers=3, num_heads=4, kv_heads=2,
        head_dim=16, dense_dim=128, num_experts=E, experts_per_token=K, expert_dim=F,
        shared_dim=0, route_scale=1.0, mtp_depth=0, rope_theta=1e6, mixers=MIXERS, conv_size=3,
        rms_eps=1e-5, tie_head=True, attn_impl="flash"), **over})


def _arch(first=0):
    return reference.Arch(mixers=MIXERS, num_heads=4, num_kv_heads=2, top_k=K, route_scale=1.0,
                          experts_first=first, rope_theta=1e6, rms_eps=1e-5)


def _seeded(model, seed=0):
    tokens = jnp.asarray(np.random.RandomState(seed).randint(0, V, (2, T + 1)), jnp.int32)
    params = dict(model.init(jax.random.key(seed), tokens[:, :-1])["params"])
    params["tok_embed"] = {"embedding": 50.0 * params["tok_embed"]["embedding"]}
    params["norm_f"] = {"scale": 0.125 * (1.0 + 0.1 * jnp.cos(jnp.arange(float(D))))}
    for i, mixer in enumerate(model.mixers):
        block = dict(params[f"block_{i}"])
        if "select_bias" in block:
            block["select_bias"] = {"kernel": 0.1 * block["select_bias"]["kernel"]}
        if mixer == GQA:  # the heads' two scales away from one
            attn = dict(block["attn"])
            attn["q_norm"] = {"scale": 1.0 + 0.3 * jnp.sin(jnp.arange(16.0))}
            attn["k_norm"] = {"scale": 1.0 + 0.3 * jnp.cos(jnp.arange(16.0))}
            block["attn"] = attn
        params[f"block_{i}"] = block
    return params, tokens[:, :-1], tokens[:, 1:]


def _batch(x, y):
    return {"x": x, "y": y, "mask": jnp.ones(x.shape, jnp.float32)}


def _trainer(model):
    return ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))


def _losses(model, arch, x, y):
    trainer = _trainer(model)

    def loss(params):
        return trainer.loss_fn(params, {}, params, _batch(x, y), jax.random.key(0))[0]

    def ref_loss(params):
        return jnp.mean(jnp.stack([reference._seq_loss(params, row, tgt, arch, "f32")
                                   for row, tgt in zip(x, y)]))

    return loss, ref_loss


@pytest.mark.parametrize("attn_impl,first,held,remat", [
    ("flash", 2, 4, False), ("xla", 0, 8, False), ("flash", 0, 8, True), ("flash", 2, 4, True)])
def test_model_equals_the_plain_reference(attn_impl, first, held, remat):
    """Logits, the loss, every gradient and one SGD step's update, whole and
    on a share, remat on and off, through the trainer's ``loss_fn``."""
    model = _model(attn_impl=attn_impl, experts_first=first, experts_held=held, remat=remat)
    params, x, y = _seeded(model)
    arch = _arch(first)
    loss, ref_loss = _losses(model, arch, x, y)
    logits, state = jax.jit(lambda p: model.apply(
        {"params": p}, x, train=True, mutable=[STATS_COLLECTION]))(params)
    for row in range(2):
        np.testing.assert_allclose(logits[row], reference.forward(params, x[row], arch), atol=3e-5)
    stats = state[STATS_COLLECTION]
    assert set(stats) == {"moe"} and stats["moe"]["assignments_held"].shape == (3,)
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    want, ref_grads = jax.jit(jax.value_and_grad(ref_loss))(params)
    assert abs(float(got) - float(want)) <= 1e-5
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    # an operator has 3 leaves and the attention 6; dense block 2 + 3, routed 2 + 2 + 3; the
    # embedding and the final norm, and no head
    assert flat.keys() == ref_flat.keys() and len(flat) == 3 * 3 + 6 + 5 + 3 * 7 + 2
    assert "head" not in params
    for path in flat:
        scale = max(float(jnp.max(jnp.abs(ref_flat[path]))), 1.0)
        np.testing.assert_allclose(flat[path], ref_flat[path], atol=5e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # b gets no gradient, and the q / k scales do
    assert float(jnp.abs(grads["block_1"]["select_bias"]["kernel"]).max()) == 0.0
    assert float(jnp.abs(grads["block_1"]["attn"]["q_norm"]["scale"]).max()) > 1e-6
    ref_batch_loss, batch_grads, state = reference.loss_and_grad(
        {"params": params}, {"x": np.asarray(x), "y": np.asarray(y), "arch": arch})
    assert abs(float(ref_batch_loss) - float(want)) <= 1e-6 and state == {}
    step = lambda p, g: jax.tree.map(lambda a, b: a - 0.01 * b, p, g)  # noqa: E731
    new, ref_new = step(params, grads), step(params, batch_grads)
    for a, b, old in zip(jax.tree.leaves(new), jax.tree.leaves(ref_new), jax.tree.leaves(params)):
        np.testing.assert_allclose(a - old, b - old,
                                   atol=1e-6 * max(1.0, 100 * float(jnp.abs(b - old).max())))


def test_the_tied_leafs_gradient_is_the_gathers_plus_the_heads():
    """The embedding leaf is read twice, by the gather and by the float32
    head product; its gradient is the sum of the two uses' gradients."""
    model = _model(attn_impl="xla")
    params, x, y = _seeded(model)
    loss, _ = _losses(model, _arch(), x, y)
    whole = jax.grad(loss)(params)["tok_embed"]["embedding"]
    emb = params["tok_embed"]["embedding"]

    arch = _arch()

    def ref_loss(gather_leaf, head_leaf):
        total = 0.0
        for row, tgt in zip(x, y):
            h = reference.last_hidden({**params, "tok_embed": {"embedding": gather_leaf}},
                                      row, arch)
            h = reference._rmsnorm(h, params["norm_f"]["scale"], arch.rms_eps)
            total = total + reference._mean_ce(h, head_leaf.T, tgt, "f32")
        return total / x.shape[0]

    d_gather, d_head = jax.grad(ref_loss, argnums=(0, 1))(emb, emb)
    assert float(jnp.abs(d_gather).max()) > 1e-6 and float(jnp.abs(d_head).max()) > 1e-6
    np.testing.assert_allclose(whole, d_gather + d_head, atol=2e-6)
    # rows no token of the batch names get the head's gradient alone
    unseen = np.setdiff1d(np.arange(V), np.asarray(x).ravel())
    assert unseen.size and float(jnp.abs(d_gather[unseen]).max()) == 0.0
    np.testing.assert_allclose(whole[unseen], d_head[unseen], atol=2e-6)


def _block(model, routed, mixer):
    held = model.num_experts if model.experts_held is None else model.experts_held
    return MLABlock(
        routed, model.num_heads, model.q_rank, model.kv_rank, model.nope_dim, model.rope_dim,
        model.v_dim, model.dense_dim, model.num_experts, model.experts_per_token,
        model.expert_dim, model.shared_dim, model.route_scale, model.experts_first, held,
        model.rope_theta, model.rms_eps, model.attn_impl, model.dtype, mixer, model.kda_heads,
        model.kda_head_dim, model.conv_size, model.kv_heads, model.head_dim)


@pytest.mark.parametrize("layer", [1, 2], ids=["attention", "short_convolution"])
def test_the_eight_shares_add_up_to_the_uncut_layer(layer):
    """A routed layer over 8 shares of one expert each (the deployment's 8
    chips a layer): the routed parts summed, with what every chip computes
    alike (the residual and the mixer; there is no shared expert) counted
    once, equal the uncut reference layer; and the dense layer, whole on every
    chip, equals the reference's."""
    whole = _model(attn_impl="xla")
    params, x, _ = _seeded(whole)
    arch, kind = _arch(), MIXERS[layer]
    h = params["tok_embed"]["embedding"][x[0]]
    p = params[f"block_{layer}"]
    want = reference.block(h, p, kind, arch, "f32")
    alike = reference.block(
        h, {**p, "experts": jax.tree.map(jnp.zeros_like, p["experts"])}, kind, arch, "f32")
    parts = []
    for first in range(E):
        share = _model(attn_impl="xla", experts_first=first, experts_held=1)
        held = {**p, "experts": jax.tree.map(lambda a: a[first:first + 1], p["experts"])}
        out = _block(share, True, kind).apply({"params": held}, h[None])[0][0]
        np.testing.assert_allclose(
            out, reference.block(h, held, kind, arch._replace(experts_first=first), "f32"),
            atol=3e-5)
        parts.append(out - alike)
    np.testing.assert_allclose(alike + sum(parts), want, atol=6e-5)
    assert sum(float(jnp.abs(part).max()) > 1e-3 for part in parts) >= 6
    assert float(jnp.abs(want - alike).max()) > 1e-2
    dense = _block(whole, False, CONV).apply({"params": params["block_0"]}, h[None])[0][0]
    np.testing.assert_allclose(dense, reference.block(h, params["block_0"], CONV, arch, "f32"),
                               atol=3e-5)


# -- 64-wide heads in the two flash kernels ---------------------------------------------


def _heads64(seed=0, t=256, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(k1, (1, 4, t, 64), dtype)
    k = jax.random.normal(k2, (1, 1, t, 64), dtype)
    v = jax.random.normal(k3, (1, 1, t, 64), dtype)
    return q, k, v, jax.random.normal(k4, (1, 4, t, 64), jnp.float32)


def _normed_and_turned(q, k):
    """RMSNorm a head under a scale of 64 (one for q, one for k), then the
    rotate-half rotation: what ``GroupedAttention`` does with ``qk_norm_eps``."""
    def norm(x, scale):
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale

    q = norm(q, 1.0 + 0.2 * jnp.sin(jnp.arange(64.0))).astype(q.dtype)
    k = norm(k, 1.0 + 0.2 * jnp.cos(jnp.arange(64.0))).astype(k.dtype)
    return rope(q, 1e6), rope(k, 1e6)


def test_the_kernels_take_their_128_lane_path_at_64_wide_heads():
    """T 256 under the default tiles: key tiles of 256 (a multiple of 128
    lanes: the running max and sum are kept a lane) over a 64-wide output
    accumulator, so ``_spread(alpha, 64)`` meets ``64 % 128 != 0`` and gives
    the first lane back."""
    assert att._fwd_blocks(256, 256, jnp.float32) == (256, 256)
    x = jnp.arange(8.0 * 128).reshape(8, 128)
    assert att._spread(x, 64).shape == (8, 1)
    np.testing.assert_array_equal(att._spread(x, 64), x[:, :1])
    assert att._spread(x, 256).shape == (8, 256) and att._spread(x, 128) is x
    assert att._spread(x[:, :1], 64).shape == (8, 1)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_flash_at_width_64_grouped_four_to_one_equals_the_oracle(dtype, atol):
    """Forward and the three gradients of the two kernels (interpreted) at
    head width 64, four query heads on one KV head, on normalised and rotated
    q and k, against ``attention_reference``."""
    q, k, v, do = _heads64(dtype=dtype)

    def run(attend):
        def f(q, k, v):
            qn, kn = _normed_and_turned(q, k)
            out = attend(qn, kn, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) * do), out
        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    out, grads = run(flash_attention)
    want, want_grads = run(attention_reference)
    assert out.shape == (1, 4, 256, 64) and out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32), atol=atol)
    for got, ref in zip(grads, want_grads):
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        np.testing.assert_allclose(got.astype(jnp.float32), ref.astype(jnp.float32),
                                   atol=atol * max(scale, 1.0))
    notes = [n for n in trace.program_notes("attn/call")
             if n["shape"] == (1, 4, 256, 64) and n["dtype"] == jnp.dtype(dtype).name]
    assert {n["kernel"] for n in notes} == {"fwd", "dkv"}  # dkv writes dq too
    assert all(n["d_qk"] == 64 and n["d_v"] == 64 and n["q_heads_per_kv_head"] == 4
               for n in notes)


def test_grouped_attention_with_normalised_heads():
    """``qk_norm_eps``: two leaves of ``head_dim``, the norm before the
    rotation, the flash path equal to the XLA path; without it the module has
    the leaves and the lowered text it had."""
    h = jax.random.normal(jax.random.key(0), (2, T, D))
    normed = GroupedAttention(4, 2, 16, rope_theta=1e6, qk_norm_eps=1e-5)
    params = normed.init(jax.random.key(1), h)["params"]
    assert set(params) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert params["q_norm"]["scale"].shape == params["k_norm"]["scale"].shape == (16,)
    params = {**params, "q_norm": {"scale": 1.0 + 0.3 * jnp.sin(jnp.arange(16.0))},
              "k_norm": {"scale": 0.5 + 0.3 * jnp.cos(jnp.arange(16.0))}}
    got = normed.apply({"params": params}, h)

    def heads(name, n):
        return (h @ params[name]["kernel"]).reshape(2, T, n, 16).transpose(0, 2, 1, 3)

    def norm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale

    q = rope(norm(heads("q", 4), params["q_norm"]["scale"]), 1e6)
    k = rope(norm(heads("k", 2), params["k_norm"]["scale"]), 1e6)
    a = attention_reference(q, k, heads("v", 2), causal=True)
    want = a.transpose(0, 2, 1, 3).reshape(2, T, 64) @ params["o"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    flash = GroupedAttention(4, 2, 16, rope_theta=1e6, qk_norm_eps=1e-5, attn_impl="flash")
    np.testing.assert_allclose(flash.apply({"params": params}, h), got, atol=2e-5)
    plain = GroupedAttention(4, 2, 16, rope_theta=1e6)
    plain_params = plain.init(jax.random.key(1), h)["params"]
    assert set(plain_params) == {"q", "k", "v", "o"}
    assert float(jnp.abs(plain.apply({"params": plain_params}, h) - got).max()) > 1e-3


# -- names, notes and kernel counts ------------------------------------------------------


def _count_pallas(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_pallas(sub)
    return n


@pytest.fixture(scope="module")
def lowered_step():
    model = _model(remat=True)
    params, x, y = _seeded(model)
    loss, _ = _losses(model, _arch(), x, y)
    fn = jax.jit(jax.value_and_grad(loss))
    return fn.lower(params).as_text(debug_info=True), jax.make_jaxpr(jax.value_and_grad(loss))(
        params)


@pytest.mark.parametrize("name", [
    trace.SCOPE_SHORTCONV, trace.SCOPE_SHORTCONV_GATE, trace.SCOPE_GQA, "moe/route",
    "moe/experts", trace.SCOPE_FLASH_FWD, trace.SCOPE_BLOCKWISE_BWD])
def test_scope_names_are_in_the_lowered_step(lowered_step, name):
    assert name in lowered_step[0]


def test_the_tied_heads_product_bears_the_heads_name(lowered_step):
    """``head`` as a whole path element, forward and backward, so the
    accepted ``head_loss`` reader finds the tied product."""
    from benchmark import scope_reduce

    text = lowered_step[0]
    names = set(re.findall(r'"(jit\([^"]*)"', text))
    head = [n for n in names if scope_reduce.HEAD_OR_LOSS.search(n) and "/head/" in n + "/"]
    assert any("dot_general" in n for n in head), sorted(names)[:5]
    assert any("transpose(" in n for n in head)
    # the attention layer's scope holds the kernels' scopes, the operator's holds its chain's
    assert any(f"{trace.SCOPE_GQA}/" in n and trace.SCOPE_FLASH_FWD in n for n in names)
    assert not any(trace.SCOPE_GQA in n and trace.SCOPE_SHORTCONV in n for n in names)


def test_notes_and_kernel_counts(lowered_step):
    """One ``shortconv/call`` and the two ``attn/call`` notes at this
    model's shapes; and PR 34's rule: a block holds no more ``pallas_call``s
    than its parent's kind. An attention block under remat: flash forward
    once (its residuals are kept) and the one backward kernel; a routed
    feed-forward's nine and its recompute's one; the operator none."""
    conv = {"impl": "xla", "tokens": 2 * T, "channels": D, "taps": 3, "dtype": "float32"}
    assert conv in trace.program_notes("shortconv/call")
    calls = [n for n in trace.program_notes("attn/call") if n["shape"] == (2, 4, T, 16)]
    assert {n["kernel"] for n in calls} == {"fwd", "dkv"}  # dkv writes dq too
    assert all(n["q_heads_per_kv_head"] == 2 and n["kind"] == "global" for n in calls)
    assert _count_pallas(lowered_step[1].jaxpr) == 2 + 3 * 10
    dense_only = _model(remat=True, dense_layers=4, routed_layers=0,
                        mixers=(CONV, CONV, CONV, CONV))
    params, x, y = _seeded(dense_only)
    loss, _ = _losses(dense_only, _arch(), x, y)
    assert _count_pallas(jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr) == 0


def test_mixers_shared_expert_and_tied_head_follow_what_they_are_given():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="mla, kda, conv or gqa"):
        _model(mixers=(CONV, "ssm", CONV, CONV)).init(jax.random.key(0), tokens)
    tied = _model().init(jax.random.key(0), tokens)["params"]
    assert "head" not in tied and "shared" not in tied["block_1"]
    assert set(tied["block_0"]) == {"norm_attn", "conv", "norm_ffn", "mlp"}
    assert set(tied["block_1"]) == {"norm_attn", "attn", "norm_ffn", "router", "select_bias",
                                    "experts"}
    untied = _model(tie_head=False, shared_dim=32).init(jax.random.key(0), tokens)["params"]
    assert untied["head"]["kernel"].shape == (D, V) and "shared" in untied["block_1"]


def test_the_registry_reaches_the_mixers_by_the_modules_name():
    from fedml_tpu.models.registry import create_model

    model = create_model("mla_moe_transformer", V).clone(
        mixers=(CONV, GQA, GQA), shared_dim=0, tie_head=True, mtp_depth=0)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert "conv" in params["block_0"] and "q_norm" in params["block_1"]["attn"]
