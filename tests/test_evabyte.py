"""The EvaByte decoder (``fedml_tpu/models/mla_moe_transformer.py`` with the
mixer "eva", unit-offset norms and eight prediction heads) at a toy size on
the CPU, in float32, against the benchmark's plain reference
(``benchmark/reference/eva_lm.py``) on seeded weights: logits, loss and every
gradient leaf, remat on and off, both attention paths; ``lm_loss`` over
``[B, T, 8]``; what the reference's two faults do; scopes, notes, the counter
and kernel counts."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import eva_lm as reference
from fedml_tpu.core import trainer as trainerlib
from fedml_tpu.core.trainer import STATS_COLLECTION, ClientTrainer
from fedml_tpu.models.mla_moe_transformer import EVA, GQA, MLAMoETransformerLM
from fedml_tpu.obs import trace
from fedml_tpu.ops import remat

# hidden 64; 4 heads of 16; windows of 32 positions in chunks of 4; T 128 (four
# windows, 32 summaries); a feed-forward of 96; 2 layers; 8 heads over 40 ids
T, D, H, W, C, P, V, LAYERS = 128, 64, 4, 32, 4, 8, 40, 2


def _model(**over):
    return MLAMoETransformerLM(**{**dict(
        vocab_size=V, embed_dim=D, dense_layers=LAYERS, routed_layers=0, num_heads=H,
        head_dim=D // H, dense_dim=96, mtp_depth=0, rope_theta=1e5, mixers=(EVA,) * LAYERS,
        eva_window=W, eva_chunk=C, norm_unit_offset=True, num_pred_heads=P, rms_eps=1e-5,
        attn_impl="flash"), **over})


def _arch(**over):
    return reference.Arch(**{**dict(num_heads=H, window=W, chunk=C, pred_heads=P, rope_theta=1e5,
                                    rms_eps=1e-5), **over})


def _seeded(model, seed=0, batch=1):
    rows = jnp.asarray(np.random.RandomState(seed).randint(0, V, (batch, T + P)), jnp.int32)
    x = rows[:, :T]
    y = jnp.stack([rows[:, 1 + p:1 + p + T] for p in range(P)], axis=-1)
    params = dict(model.init(jax.random.key(seed), x)["params"])
    params["tok_embed"] = {"embedding": 50.0 * params["tok_embed"]["embedding"]}
    # the offsets away from zero, phi and mu large enough to be felt
    params["norm_f"] = {"scale": 0.2 * jnp.cos(jnp.arange(float(D)))}
    for i in range(LAYERS):
        block = dict(params[f"block_{i}"])
        block["norm_attn"] = {"scale": 0.3 * jnp.sin(jnp.arange(float(D)) + i)}
        block["norm_ffn"] = {"scale": 0.3 * jnp.cos(jnp.arange(float(D)) - i)}
        attn = dict(block["attn"])
        attn["adaptive_phi"] = {"kernel": 6.0 * attn["adaptive_phi"]["kernel"]}
        attn["adaptive_mu_k"] = {"kernel": 3.0 * attn["adaptive_mu_k"]["kernel"]}
        block["attn"] = attn
        params[f"block_{i}"] = block
    return params, x, y


def _losses(model, arch, x, y):
    batch = {"x": x, "y": y, "mask": jnp.ones(y.shape, jnp.float32)}

    def program(params):
        logits = model.apply({"params": params}, x, train=True)
        return trainerlib.lm_loss(logits, batch)

    def plain(params):
        return reference.loss_and_grad({"params": params}, {"x": np.asarray(x), "y": np.asarray(y),
                                                            "arch": arch})
    return program, plain


@pytest.mark.parametrize("attn_impl, use_remat", [("flash", True), ("xla", False)])
def test_model_equals_the_plain_reference(attn_impl, use_remat):
    model = _model(attn_impl=attn_impl, remat=use_remat)
    params, x, y = _seeded(model)
    assert model.apply({"params": params}, x).shape == (1, T, P, V)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, x)
        np.testing.assert_allclose(logits[0], reference.forward(params, x[0], _arch()), atol=2e-4)
        program, plain = _losses(model, _arch(), x, y)
        loss, grads = jax.value_and_grad(program)(params)
        want_loss, want_grads, _ = plain(params)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat, want_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, want_grads))
    assert set(flat) == set(want_flat) and len(flat) == 3 + 11 * LAYERS
    for path, got in flat.items():
        scale = float(jnp.abs(want_flat[path]).max())
        assert scale > 0, path
        np.testing.assert_allclose(got / scale, want_flat[path] / scale, atol=3e-5,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("fault", ["remote", "mu"])
def test_the_references_faults_change_the_loss_and_a_gradient(fault):
    """``remote`` False is window-local attention alone; ``mu`` False pools
    the keys and adds nothing: each moves the logits, so each is a fault the
    chip run's limits can be held to (``PERF.md``)."""
    model = _model(attn_impl="xla")
    params, x, y = _seeded(model, batch=1)
    with jax.default_matmul_precision("highest"):
        sound = reference.forward(params, x[0], _arch())
        broken = reference.forward(params, x[0], _arch(**{fault: False}))
    np.testing.assert_allclose(broken[:W], sound[:W], atol=1e-5)  # window 0 reads no summary
    assert float(jnp.abs(broken[W:] - sound[W:]).max()) > 1e-2


def test_lm_loss_and_metrics_take_eight_targets_a_position():
    """``lm_loss`` and ``lm_metrics`` are shape-generic: ``[B, T, 8, V]``
    logits against ``y`` and ``mask`` ``[B, T, 8]`` give the mean over
    positions and heads, and a masked head leaves the mean."""
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(2, 6, P, V), jnp.float32)
    y = jnp.asarray(rng.randint(0, V, (2, 6, P)), jnp.int32)
    mask = jnp.ones((2, 6, P), jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    np.testing.assert_allclose(trainerlib.lm_loss(logits, {"y": y, "mask": mask}), ce.mean(),
                               rtol=1e-6)
    mask = mask.at[:, :, 3].set(0.0)
    np.testing.assert_allclose(trainerlib.lm_loss(logits, {"y": y, "mask": mask}),
                               jnp.delete(ce, 3, axis=2).mean(), rtol=1e-6)
    metrics = trainerlib.lm_metrics(logits, {"y": y, "mask": mask})
    assert float(metrics["test_total"]) == 2 * 6 * (P - 1)


def test_one_federated_round_runs_the_mixer_through_the_engine():
    """The ``nwp`` trainer, ``FedSim.run`` and the scan cohort with targets
    ``[n, T, 8]``: the loss is finite and the engine hands out one
    ``eva/remote_mass`` counter a layer."""
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    model = _model(remat=True)
    params, x, y = _seeded(model, batch=2)
    train = FederatedArrays({"x": np.asarray(x), "y": np.asarray(y),
                             "mask": np.ones(y.shape, np.float32)},
                            {0: np.arange(0, 1), 1: np.arange(1, 2)})
    tr = ClientTrainer(module=model, task="nwp", epochs=1, optimizer=optax.sgd(0.01))
    cfg = SimConfig(client_num_in_total=2, client_num_per_round=2, batch_size=1, epochs=1,
                    comm_round=1, frequency_of_the_test=10000, eval_batch_size=1,
                    shuffle_each_round=False, cohort_execution="scan", block_dispatch=False)
    seen = {}
    tracer = trace.install(trace.Tracer())
    try:
        _, history = FedSim(tr, train, None, cfg).run(variables={"params": params})
        seen = trace.last_counters("eva/remote_mass/")
    finally:
        trace.uninstall()
        del tracer
    assert np.isfinite(history[-1]["Train/Loss"])
    assert sorted(seen) == [f"eva/remote_mass/layer_{i}" for i in range(LAYERS)]
    assert all(0.0 < v < 1.0 for v in seen.values())


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
    program, _ = _losses(model, _arch(), x, y)
    fn = jax.jit(jax.value_and_grad(program))
    return fn.lower(params).as_text(debug_info=True), jax.make_jaxpr(jax.value_and_grad(program))(
        params)


@pytest.mark.parametrize("name", [*trace.EVA_SCOPES, trace.SCOPE_FLASH_FWD,
                                  trace.SCOPE_BLOCKWISE_BWD])
def test_scope_names_are_in_the_lowered_step(lowered_step, name):
    assert name in lowered_step[0]


def test_notes_stats_and_kernel_counts(lowered_step):
    """One ``eva/call`` note at the model's shapes, the local call's notes as
    a causal call over the windows beside the heads and the remote call's as
    a staircase with ``t_k`` the summaries; a rematerialised EVA block keeps
    both calls' residuals, so it holds the two forward calls once and the two
    backward calls: four ``pallas_call``s a layer, and a block that asks for
    none of the new arguments holds its parent's two."""
    note = {"impl": "flash", "shape": (1, H, T, D // H), "window": W, "chunk": C, "windows": 4,
            "summaries": T // C, "dtype": "float32"}
    assert note in trace.program_notes("eva/call")
    calls = trace.program_notes("attn/call")
    local = [n for n in calls if n["shape"] == (1, H * 4, W, D // H)]
    remote = [n for n in calls if n["shape"] == (1, H, T, D // H) and n["t_k"] == T // C]
    assert {n["kernel"] for n in local} == {n["kernel"] for n in remote} == {"fwd", "dkv"}
    assert all(n["kind"] == "global" and n["stair"] is None for n in local)
    assert all(n["kind"] == "stair" and n["stair"] == (W, W // C) for n in remote)
    # 6 of the 16 window-by-window blocks are visible, and no tile is cut
    assert all(16 * n["tiles_visited"] == 6 * n["tiles_total"] and n["tiles_masked"] == 0
               for n in remote)
    assert _count_pallas(lowered_step[1].jaxpr) == 4 * LAYERS
    kept = {n["kept"] for n in trace.program_notes(remat.NOTE)}
    assert set(remat.EVA_LOCAL + remat.EVA_REMOTE) <= kept
    gqa = MLAMoETransformerLM(
        vocab_size=V, embed_dim=D, dense_layers=LAYERS, routed_layers=0, num_heads=H, kv_heads=2,
        head_dim=D // H, dense_dim=96, mtp_depth=0, rope_theta=1e5, mixers=(GQA,) * LAYERS,
        attn_impl="flash", remat=True)
    x = jnp.zeros((1, T), jnp.int32)
    gqa_params = gqa.init(jax.random.key(0), x)["params"]
    loss = lambda p: jnp.sum(gqa.apply({"params": p}, x))  # noqa: E731
    assert _count_pallas(jax.make_jaxpr(jax.grad(loss))(gqa_params).jaxpr) == 2 * LAYERS


def test_the_model_sows_one_remote_mass_a_layer_and_a_short_row_reads_no_summary():
    model = _model(attn_impl="xla")
    params, x, _ = _seeded(model)
    _, state = model.apply({"params": params}, x, train=True, mutable=[STATS_COLLECTION])
    mass = state[STATS_COLLECTION]["eva"]["remote_mass"]
    assert mass.shape == (LAYERS,) and bool(jnp.all((mass > 0) & (mass < 1)))
    # one window or less: plain causal attention, no summary and no staircase call
    short = x[:, :W]
    flash = _model().apply({"params": params}, short)
    np.testing.assert_allclose(flash, model.apply({"params": params}, short), atol=2e-4)
    with pytest.raises(ValueError, match="whole windows"):
        _model().apply({"params": params}, x[:, :W + C])
    with pytest.raises(ValueError, match="or eva"):
        _model(mixers=(EVA, "ssm")).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="tied head"):
        _model(tie_head=True).init(jax.random.key(0), x)
