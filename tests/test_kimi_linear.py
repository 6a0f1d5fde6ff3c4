"""The Kimi-Linear decoder (``fedml_tpu/models/mla_moe_transformer.py`` with
``mixers``: delta attention three times to one latent-attention layer without
a query latent or positions) at a toy size on the CPU, in float32, against
the benchmark's plain reference (``benchmark/reference/kda_moe_lm.py``: the
recurrence token by token, no kernel, no chunk) on seeded weights; the shares
of an expert-parallel layer; and that latent attention with a query latent
and rotation is what it was."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import kda_moe_lm as reference
from fedml_tpu.core.trainer import STATS_COLLECTION, ClientTrainer
from fedml_tpu.models.mla_moe_transformer import (
    KDA, MLA, DeltaAttention, LatentAttention, MLABlock, MLAMoETransformerLM, rope_interleaved)
from fedml_tpu.models.moe_transformer import RMSNorm
from fedml_tpu.ops import kda as kda_ops
from fedml_tpu.ops.attention import attention_reference

# hidden 64; delta attention: 4 heads of 16 x 16 state, 4 taps; latent attention:
# 4 heads of 16 + 8 score and 16 value columns, latent 32, no query latent, no
# rotation; a dense layer of 128 (delta attention), then KDA and MLA routed: 8
# experts top-2 of width 32 beside a shared one of 32, scale 2.446; T 24 (one
# chunk of 64 padded; tests/test_kda.py has the chunks)
T, D, F, E, K, V = 24, 64, 32, 8, 2, 96
MIXERS = (KDA, KDA, MLA)


def _model(**over):
    return MLAMoETransformerLM(**{**dict(
        vocab_size=V, embed_dim=D, dense_layers=1, routed_layers=2, q_rank=None, rope_theta=None,
        route_scale=2.446, mtp_depth=0, mixers=MIXERS, kda_heads=4, kda_head_dim=16,
        attn_impl="flash"), **over})


def _arch(first=0):
    return reference.Arch(mixers=MIXERS, num_heads=4, nope_dim=16, kv_rank=32, kda_heads=4,
                          top_k=K, route_scale=2.446, experts_first=first, rms_eps=1e-6)


def _seeded(model, seed=0):
    tokens = jnp.asarray(np.random.RandomState(seed).randint(0, V, (2, T + 1)), jnp.int32)
    params = dict(model.init(jax.random.key(seed), tokens[:, :-1])["params"])
    params["tok_embed"] = {"embedding": 50.0 * params["tok_embed"]["embedding"]}
    for i, mixer in enumerate(MIXERS):
        block = dict(params[f"block_{i}"])
        if "select_bias" in block:
            block["select_bias"] = {"kernel": 0.1 * block["select_bias"]["kernel"]}
        if mixer == KDA:  # a decay's bias of deviation 2 and a gate's bias that is not zero
            attn = dict(block["attn"])
            attn["dt_bias"] = {"kernel": 2.0 * attn["dt_bias"]["kernel"]}
            attn["g_b"] = {**attn["g_b"], "bias": 0.5 * jnp.cos(jnp.arange(64.0))}
            block["attn"] = attn
        params[f"block_{i}"] = block
    return params, tokens[:, :-1], tokens[:, 1:]


def _batch(x, y):
    return {"x": x, "y": y, "mask": jnp.ones(x.shape, jnp.float32)}


@pytest.mark.parametrize("attn_impl,first,held,remat", [
    ("flash", 2, 4, False), ("xla", 0, 8, False), ("flash", 0, 8, True)])
def test_model_equals_the_plain_reference(attn_impl, first, held, remat):
    """Logits, the loss and every gradient, whole and on a share, through the
    trainer's ``loss_fn``: the chunked scan and the flash kernels (interpreted)
    and the token-by-token path alike."""
    model = _model(attn_impl=attn_impl, experts_first=first, experts_held=held, remat=remat)
    params, x, y = _seeded(model)
    arch = _arch(first)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))

    def loss(params):
        return trainer.loss_fn(params, {}, params, _batch(x, y), jax.random.key(0))[0]

    def ref_loss(params):
        return jnp.mean(jnp.stack([reference._seq_loss(params, row, tgt, arch, "f32")
                                   for row, tgt in zip(x, y)]))

    logits, state = jax.jit(lambda p: model.apply(
        {"params": p}, x, train=True, mutable=[STATS_COLLECTION]))(params)
    for row in range(2):
        np.testing.assert_allclose(logits[row], reference.forward(params, x[row], arch), atol=3e-5)
    # one floor a delta-attention block, one count a routed block
    stats = state[STATS_COLLECTION]
    floors = stats["kda"]["decay_floor"]
    assert floors.shape == (2,) and float(floors.max()) < 0
    assert stats["moe"]["assignments_held"].shape == (2,)
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    want, ref_grads = jax.jit(jax.value_and_grad(ref_loss))(params)
    assert abs(float(got) - float(want)) <= 1e-5
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    # a delta mixer has 16 leaves and the latent one 5; dense block 2 + 3, routed 2 + 2 + 3 + 3
    assert flat.keys() == ref_flat.keys() and len(flat) == 2 * 16 + 5 + 5 + 2 * 10 + 3
    for path in flat:
        scale = max(float(jnp.max(jnp.abs(ref_flat[path]))), 1.0)
        np.testing.assert_allclose(flat[path], ref_flat[path], atol=5e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    ref_batch_loss, batch_grads, state = reference.loss_and_grad(
        {"params": params}, {"x": np.asarray(x), "y": np.asarray(y), "arch": arch})
    assert abs(float(ref_batch_loss) - float(want)) <= 1e-6 and state == {}
    np.testing.assert_allclose(batch_grads["head"]["kernel"], ref_grads["head"]["kernel"],
                               atol=1e-6)


def _block(model, routed, mixer):
    held = model.num_experts if model.experts_held is None else model.experts_held
    return MLABlock(
        routed, model.num_heads, model.q_rank, model.kv_rank, model.nope_dim, model.rope_dim,
        model.v_dim, model.dense_dim, model.num_experts, model.experts_per_token,
        model.expert_dim, model.shared_dim, model.route_scale, model.experts_first, held,
        model.rope_theta, model.rms_eps, model.attn_impl, model.dtype, mixer, model.kda_heads,
        model.kda_head_dim, model.conv_size)


@pytest.mark.parametrize("layer", [1, 2], ids=["delta_attention", "latent_attention"])
def test_the_shares_add_up_to_the_uncut_layer(layer):
    """A routed layer over 4 shares of 2 experts each: the routed parts
    summed, with what every chip computes alike (the residual, the mixer and
    the shared expert) counted once, equal the uncut reference layer; and the
    dense layer, whole on every chip, equals the reference's."""
    whole = _model(attn_impl="xla")
    params, x, _ = _seeded(whole)
    arch, kind = _arch(), MIXERS[layer]
    h = params["tok_embed"]["embedding"][x[0]]
    p = params[f"block_{layer}"]
    want = reference.block(h, p, kind, arch, "f32")
    alike = reference.block(
        h, {**p, "experts": jax.tree.map(jnp.zeros_like, p["experts"])}, kind, arch, "f32")
    parts = []
    for first in range(0, E, 2):
        share = _model(attn_impl="xla", experts_first=first, experts_held=2)
        held = {**p, "experts": jax.tree.map(lambda a: a[first:first + 2], p["experts"])}
        out = _block(share, True, kind).apply({"params": held}, h[None])[0][0]
        np.testing.assert_allclose(
            out, reference.block(h, held, kind, arch._replace(experts_first=first), "f32"),
            atol=3e-5)
        parts.append(out - alike)
    np.testing.assert_allclose(alike + sum(parts), want, atol=6e-5)
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in parts)
    assert float(jnp.abs(want - alike).max()) > 1e-2
    dense = _block(whole, False, KDA).apply({"params": params["block_0"]}, h[None])[0][0]
    np.testing.assert_allclose(dense, reference.block(h, params["block_0"], KDA, arch, "f32"),
                               atol=3e-5)


def test_delta_attention_is_its_equations():
    """The module against the equations written out: convolution, SiLU,
    l2norm, the decay from ``A_log`` and ``dt_bias``, beta, the recurrence, the
    gated norm. And the chunked path equals the token-by-token one."""
    model = _model()
    params, x, _ = _seeded(model)
    p = params["block_1"]["attn"]
    h = jax.random.normal(jax.random.key(4), (1, T, D))
    got, floor = DeltaAttention(4, 16, attn_impl="xla").apply({"params": p}, h)
    chunked, floor_c = DeltaAttention(4, 16, attn_impl="flash").apply({"params": p}, h)
    np.testing.assert_allclose(chunked, got, atol=1e-5)
    heads = lambda y: y.reshape(T, 4, 16).transpose(1, 0, 2)  # noqa: E731
    l2 = lambda y: y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)  # noqa: E731

    def conv(name):
        y = h[0] @ p[name]["kernel"]
        w = p[name + "_conv"]["kernel"]
        padded = jnp.concatenate([jnp.zeros((3, 64)), y])
        return heads(jax.nn.silu(sum(w[j] * padded[j:j + T] for j in range(4))))

    q, k, v = l2(conv("q")) * 0.25, l2(conv("k")), conv("v")
    g = -jnp.exp(p["A_log"]["kernel"][0])[:, None, None] * heads(jax.nn.softplus(
        h[0] @ p["f_a"]["kernel"] @ p["f_b"]["kernel"] + p["dt_bias"]["kernel"][0]))
    beta = jax.nn.sigmoid(h[0] @ p["b"]["kernel"]).T
    state, outs = jnp.zeros((4, 16, 16)), []
    for t in range(T):
        state = state * jnp.exp(g[:, t])[..., None]
        u = beta[:, t, None] * (v[:, t] - jnp.einsum("hkv,hk->hv", state, k[:, t]))
        state = state + k[:, t, :, None] * u[:, None, :]
        outs.append(jnp.einsum("hkv,hk->hv", state, q[:, t]))
    o = jnp.stack(outs, 1)  # [H, T, 16]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * p["o_norm"]["scale"]
    gate = jax.nn.sigmoid(h[0] @ p["g_a"]["kernel"] @ p["g_b"]["kernel"] + p["g_b"]["bias"])
    want = (o.transpose(1, 0, 2).reshape(T, 64) * gate) @ p["o"]["kernel"]
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert float(floor) == pytest.approx(float(kda_ops.decay_floor(g)), rel=1e-5)
    assert float(floor_c) == float(floor) < 0


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("name,stand_in", [
    ("short_conv", lambda x, w: x), ("l2norm", lambda x, eps=1e-6: x.astype(jnp.float32))],
    ids=["short_conv", "l2norm"])
def test_a_patched_definition_is_the_one_the_mixer_runs(monkeypatch, name, stand_in, attn_impl):
    """``ops/kda.py``'s ``short_conv`` and ``l2norm`` are looked up in the module
    when the operators are traced: the benchmark's controls
    (``tests/benchmark_tests/test_benchmark_kda.py``) break the model by
    standing another function in their place, so each stand-in must change
    the module's output, on both paths."""
    params, _, _ = _seeded(_model())
    p = params["block_1"]["attn"]
    h = jax.random.normal(jax.random.key(4), (1, T, D))
    module = DeltaAttention(4, 16, attn_impl=attn_impl)
    sound, _ = module.apply({"params": p}, h)
    monkeypatch.setattr(kda_ops, name, stand_in)
    broken, _ = module.apply({"params": p}, h)
    assert bool(jnp.all(jnp.isfinite(broken)))
    assert float(jnp.max(jnp.abs(broken - sound))) > 0.05 * float(jnp.max(jnp.abs(sound)))


class _LatentAttentionAsItWas(LatentAttention):
    """``LatentAttention.__call__`` as the parent commit had it (PR 34): a
    query latent and rotation always."""

    @nn.compact
    def __call__(self, h):
        b, t, _ = h.shape
        n, nope, rope_d = self.num_heads, self.nope_dim, self.rope_dim
        dense = lambda name, width, y: nn.Dense(  # noqa: E731
            width, use_bias=False, name=name, dtype=self.dtype)(y)
        heads = lambda y, width: y.reshape(b, t, n, width).transpose(0, 2, 1, 3)  # noqa: E731
        c_q = RMSNorm(self.rms_eps, self.dtype, name="q_a_norm")(dense("q_a", self.q_rank, h))
        q = heads(dense("q_b", n * (nope + rope_d), c_q), nope + rope_d)
        kv_a = dense("kv_a", self.kv_rank + rope_d, h)
        c_kv = RMSNorm(self.rms_eps, self.dtype, name="kv_a_norm")(kv_a[..., :self.kv_rank])
        k_rope = rope_interleaved(kv_a[:, None, :, self.kv_rank:], self.rope_theta)
        kv = heads(dense("kv_b", n * (nope + self.v_dim), c_kv), nope + self.v_dim)
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], self.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, n, t, rope_d))], axis=-1)
        a = attention_reference(q, k, kv[..., nope:], causal=True)
        return dense("o", h.shape[-1], a.transpose(0, 2, 1, 3).reshape(b, t, n * self.v_dim))


LATENT = (4, 48, 32, 16, 8, 16, 32e6)  # heads, q latent, kv latent, nope, rope, v, theta


def test_latent_attention_with_a_query_latent_and_rotation_is_bit_for_bit_what_it_was():
    h = jax.random.normal(jax.random.key(0), (2, T, D))
    now, was = LatentAttention(*LATENT), _LatentAttentionAsItWas(*LATENT)
    params = now.init(jax.random.key(1), h)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, was.init(jax.random.key(1), h))
    np.testing.assert_array_equal(now.apply(params, h), was.apply(params, h))
    text = lambda m: jax.jit(m.apply).lower(params, h).as_text()  # noqa: E731
    assert text(now) == text(was)


def test_latent_attention_without_a_query_latent_or_positions():
    """``q_rank`` None: one product to the heads and no ``q_a`` leaf;
    ``rope_theta`` None: moving a token moves its output with it (no
    position enters), where rotation would change it."""
    h = jax.random.normal(jax.random.key(0), (1, T, D))
    plain = LatentAttention(4, None, 32, 16, 8, 16, None)
    params = plain.init(jax.random.key(1), h)
    assert set(params["params"]) == {"q", "kv_a", "kv_a_norm", "kv_b", "o"}
    assert params["params"]["q"]["kernel"].shape == (D, 4 * 24)
    out = plain.apply(params, h)
    # the first two tokens swapped: position 1 then sees {h1, h0} as position 1
    # did before seeing {h0, h1}: softmax over a set, no position
    swapped = plain.apply(params, h.at[:, :2].set(h[:, 1::-1]))
    np.testing.assert_allclose(swapped[:, 2:], out[:, 2:], atol=1e-6)
    turned = LatentAttention(4, None, 32, 16, 8, 16, 1e4)
    moved = turned.apply(params, h.at[:, :2].set(h[:, 1::-1]))
    assert float(jnp.max(jnp.abs(moved[:, 2:] - turned.apply(params, h)[:, 2:]))) > 1e-4


def test_the_mixers_list_is_held_to_the_depth():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="mixers must name 3"):
        _model(mixers=(KDA, MLA)).init(jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="mla, kda, conv or gqa"):
        _model(mixers=(KDA, "ssm", MLA)).init(jax.random.key(0), tokens)
    # no mixers given: latent attention in every layer, and no "kda" statistics
    params = _model(mixers=None).init(jax.random.key(0), tokens)
    assert "kv_a" in params["params"]["block_0"]["attn"]


def test_the_registry_builds_the_family_by_name():
    from fedml_tpu.models.registry import create_model

    assert isinstance(create_model("mla_moe_transformer", V), MLAMoETransformerLM)
