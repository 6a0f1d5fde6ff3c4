"""The routed-expert layer and the decoder built on it
(``fedml_tpu/ops/moe.py``, ``fedml_tpu/models/moe_transformer.py``) at a toy
size on the CPU, in float32, against the benchmark's plain reference
(``benchmark/reference/moe_lm.py``: no kernel, no sort) on seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import moe_lm as reference
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.models.moe_transformer import MoETransformerLM
from fedml_tpu.obs import trace
from fedml_tpu.ops import moe

# hidden 64, 4 / 2 heads of 16, 8 experts top-2 of width 32, window 8, T 32, one period
T, D, F, E, K, V = 32, 64, 32, 8, 2, 96
KINDS = ("global", "window", "window", "window")


def _model(**over):
    return MoETransformerLM(**{**dict(
        vocab_size=V, embed_dim=D, layer_kinds=KINDS, num_heads=4, num_kv_heads=2, head_dim=16,
        num_experts=E, experts_per_token=K, expert_dim=F, window=8, attn_impl="flash"), **over})


def _arch(first=0):
    return reference.Arch(num_heads=4, num_kv_heads=2, head_dim=16, top_k=K, experts_first=first,
                          windows=(None, 8, 8, 8), rope_theta=1.5e6, rms_eps=1e-6)


def _seeded(model, seed=0):
    tokens = jnp.asarray(np.random.RandomState(seed).randint(0, V, (2, T + 1)), jnp.int32)
    variables = model.init(jax.random.key(seed), tokens[:, :-1])
    # a wide embedding, so that routing follows the token and not the stream's mean
    params = dict(variables["params"])
    params["tok_embed"] = {"embedding": 50.0 * params["tok_embed"]["embedding"]}
    return params, tokens[:, :-1], tokens[:, 1:]


@pytest.mark.parametrize("attn_impl,first,held", [("flash", 2, 4), ("xla", 0, 8)])
def test_model_equals_the_plain_reference(attn_impl, first, held):
    """Logits, loss and every gradient to 1e-5, whole and on a share."""
    model = _model(attn_impl=attn_impl, experts_first=first, experts_held=held)
    params, x, y = _seeded(model)
    arch = _arch(first)

    def loss(params):
        logits = model.apply({"params": params}, x)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, y)), logits

    def ref_loss(params):
        logits = jnp.stack([reference.forward(params, row, arch) for row in x])
        per_row = jnp.stack([reference._seq_loss(params, row, tgt, arch, "f32")
                             for row, tgt in zip(x, y)])
        return jnp.mean(per_row), logits

    (got, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
    (want, ref_logits), ref_grads = jax.value_and_grad(ref_loss, has_aux=True)(params)
    np.testing.assert_allclose(logits, ref_logits, atol=1e-5)
    assert abs(float(got) - float(want)) <= 1e-5
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    assert flat.keys() == ref_flat.keys() and len(flat) == 4 * 10 + 3
    for path in flat:
        np.testing.assert_allclose(flat[path], ref_flat[path], atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    batch = {"x": np.asarray(x), "y": np.asarray(y), "arch": arch}
    ref_batch_loss, batch_grads, _ = reference.loss_and_grad({"params": params}, batch)
    assert abs(float(ref_batch_loss) - float(want)) <= 1e-6
    np.testing.assert_allclose(batch_grads["head"]["kernel"], ref_grads["head"]["kernel"],
                               atol=1e-6)


def _layer_inputs(seed=1):
    ks = jax.random.split(jax.random.key(seed), 5)
    u = jax.random.normal(ks[0], (T, D))
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    gate, up = (jax.random.normal(k, (E, D, F)) * D ** -0.5 for k in ks[2:4])
    down = jax.random.normal(ks[4], (E, F, D)) * F ** -0.5
    return u, router, gate, up, down


def _share(u, ids, weights, gate, up, down, first, count):
    held = slice(first, first + count)
    return moe.expert_layer(u, ids, weights, gate[held], up[held], down[held],
                            first=first, count=count, dtype=jnp.float32)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of four chips holds two of the eight experts, routes over all
    eight and gives its own experts' part; the parts add up to the whole
    layer, by the sorted path and by the benchmark's dense loop alike."""
    u, router, gate, up, down = _layer_inputs()
    ids, weights = moe.route(u, router, K)
    whole, whole_stats = _share(u, ids, weights, gate, up, down, 0, E)
    parts = [_share(u, ids, weights, gate, up, down, first, 2) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=1e-5)
    assert sum(float(p[1]["moe/assignments_held"]) for p in parts) == T * K
    assert float(whole_stats["moe/assignments_held"]) == T * K
    ref_whole = reference._experts(u, ids, weights, gate, up, down, 0, "f32")
    np.testing.assert_allclose(whole, ref_whole, atol=1e-5)
    ref_parts = [reference._experts(u, ids, weights, gate[f:f + 2], up[f:f + 2], down[f:f + 2],
                                    f, "f32") for f in (0, 2, 4, 6)]
    for (part, _), ref_part in zip(parts, ref_parts):
        np.testing.assert_allclose(part, ref_part, atol=1e-5)
    np.testing.assert_allclose(
        reference._experts(u, ids, weights, gate[2:6], up[2:6], down[2:6], 2, "f32"),
        sum(ref_parts[1:3]), atol=1e-5)


@pytest.mark.parametrize("chosen,held_share", [((3, 9), 0.5), ((8, 9), 0.0), ((2, 3), 1.0)])
def test_skewed_routing_is_dropless(chosen, held_share):
    """Every token to one held expert (and one absent), every token to
    absent experts, every assignment to held experts: nothing is dropped,
    and nothing held gives zeros; the gradients agree too."""
    u, _, gate, up, down = _layer_inputs(2)
    gate, up, down = gate[:4], up[:4], down[:4]
    ids = jnp.tile(jnp.asarray(chosen, jnp.int32), (T, 1))  # experts 2 .. 5 are held
    weights = jax.nn.softmax(jax.random.normal(jax.random.key(3), (T, K)), axis=-1)

    def layer(u, weights, gate, up, down):
        return moe.expert_layer(u, ids, weights, gate, up, down, first=2, count=4,
                                dtype=jnp.float32)[0]

    def oracle(u, weights, gate, up, down):
        return reference._experts(u, ids, weights, gate, up, down, 2, "f32")

    out, stats = moe.expert_layer(u, ids, weights, gate, up, down, first=2, count=4,
                                  dtype=jnp.float32)
    np.testing.assert_allclose(out, oracle(u, weights, gate, up, down), atol=1e-5)
    assert float(stats["moe/assignments_held"]) == held_share * T * K
    if held_share == 0.0:
        np.testing.assert_array_equal(out, 0.0)
    else:
        assert float(jnp.min(jnp.linalg.norm(out, axis=-1))) > 0
        assert float(stats["moe/load_max_over_mean"]) == pytest.approx(4 / (held_share * K))
    c = jax.random.normal(jax.random.key(4), (T, D))
    got = jax.grad(lambda *a: jnp.sum(layer(*a) * c), argnums=(0, 1, 2, 3, 4))(
        u, weights, gate, up, down)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * c), argnums=(0, 1, 2, 3, 4))(
        u, weights, gate, up, down)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_routing_is_float32_whatever_the_stream():
    u, router, *_ = _layer_inputs()
    ids, weights = moe.route(u.astype(jnp.bfloat16), router, K)
    assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.0, atol=1e-6)
    logits = u.astype(jnp.bfloat16).astype(jnp.float32) @ router
    np.testing.assert_array_equal(ids, jax.lax.top_k(logits, K)[1])


def test_scopes_and_kernels_in_the_lowered_training_step():
    """The four ``moe/*`` scopes inside ``fed/fwd_bwd``, forward and
    backward, and the three flash kernels by name."""
    model = _model(experts_first=2, experts_held=4)
    params, x, y = _seeded(model)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
    batch = {"x": x, "y": y, "mask": jnp.ones(x.shape, jnp.float32)}
    variables = {"params": params}
    text = jax.jit(trainer.train_step_stats).lower(
        variables, trainer.optimizer.init(params), params, batch, jax.random.key(0)
    ).as_text(debug_info=True)
    assert trace.MOE_SCOPES == ("moe/route", "moe/dispatch", "moe/experts", "moe/combine")
    for scope in trace.MOE_SCOPES:
        assert f"fed/fwd_bwd/jvp(MoETransformerLM)/block_1/" in text
        assert any(scope in line and "transpose(" not in line for line in text.splitlines()), scope
    for scope in ("moe/dispatch", "moe/experts", "moe/combine"):
        assert any(scope in line and "transpose(" in line for line in text.splitlines()), scope
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert kernel in text, kernel
    assert "flash_bwd_dq" not in text
    assert not set(trace.MOE_SCOPES) & set(trace.SCOPES)


def test_training_step_returns_the_routing_statistics():
    model = _model(experts_first=2, experts_held=4)
    params, x, y = _seeded(model)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
    batch = {"x": x, "y": y, "mask": jnp.ones(x.shape, jnp.float32)}
    sample = {k: v[:1] for k, v in batch.items()}
    assert "stats" not in trainer.init(jax.random.key(0), sample)
    new, _, loss, stats = jax.jit(trainer.train_step_stats)(
        {"params": params}, trainer.optimizer.init(params), params, batch, jax.random.key(0))
    assert set(stats) == {"moe/assignments_held", "moe/load_max_over_mean",
                          "moe/rows_touched", "moe/overflow_tiles"}
    held = np.asarray(stats["moe/assignments_held"])
    assert held.shape == (4,) and np.all(held > 0) and np.all(held <= 2 * T * K)
    assert np.all(np.asarray(stats["moe/load_max_over_mean"]) >= 1.0)
    assert np.isfinite(float(loss)) and set(new) == {"params"}
    assert len(jax.eval_shape(trainer.train_step, {"params": params},
                              trainer.optimizer.init(params), params, batch,
                              jax.random.key(0))) == 3


def test_fedsim_round_carries_the_counts_to_counters():
    """Through ``FedSim.run``: the per-layer counts ride on the round's
    metrics and become counters at the engine's sync."""
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    rows = np.random.RandomState(0).randint(0, V, (8, T + 1)).astype(np.int32)
    train = FederatedArrays({"x": rows[:, :-1], "y": rows[:, 1:],
                             "mask": np.ones((8, T), np.float32)},
                            {i: np.arange(2 * i, 2 * i + 2) for i in range(4)})
    trainer = ClientTrainer(module=_model(experts_held=4), task="nwp",
                            optimizer=optax.sgd(0.01), epochs=1)
    cfg = SimConfig(client_num_in_total=4, client_num_per_round=2, batch_size=1, comm_round=2,
                    epochs=1, frequency_of_the_test=100, cohort_execution="scan",
                    block_dispatch=False)
    tracer = trace.install()
    try:
        _, history = FedSim(trainer, train, None, cfg).run()
    finally:
        trace.uninstall()
    keys = [f"stats/moe/{name}/layer_{i}" for name in ("assignments_held", "load_max_over_mean")
            for i in range(4)]
    assert all(k in history[-1] for k in keys)
    assert 0 < history[-1]["stats/moe/assignments_held/layer_0"] <= T * K
    counters = [e for e in tracer.events() if e["ph"] == "C" and e["name"].startswith("moe/")]
    assert len(counters) == 4 * 8  # four statistics, four layers, two rounds
    last = trace.last_counters("moe/assignments_held/")
    assert last["moe/assignments_held/layer_3"] == history[-1][keys[3]]


def test_registry_builds_the_model():
    from fedml_tpu.models import registry

    model = registry.create_model("moe_transformer", V)
    assert isinstance(model, MoETransformerLM) and model.vocab_size == V
    assert registry.create_model("moe_transformer", V, dtype="bfloat16").dtype == jnp.bfloat16
