"""The latent-attention decoder (``fedml_tpu/models/mla_moe_transformer.py``),
its sigmoid router and shared expert (``fedml_tpu/ops/moe.py``) and the
trainer's multi-token-prediction loss at a toy size on the CPU, in float32,
against the benchmark's plain reference (``benchmark/reference/mla_moe_lm.py``:
no kernel, no sort, no padded position) on seeded weights."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import mla_moe_lm as reference
from fedml_tpu.core.trainer import MTP_COLLECTION, ClientTrainer, lm_loss, one_token_further
from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM, rope_interleaved
from fedml_tpu.obs import trace
from fedml_tpu.ops import moe

# hidden 64, 4 heads of 16 + 8 score columns and 16 value columns, latents 48 / 32,
# one dense layer of 128, two routed layers: 8 experts top-2 of width 32 beside a
# shared one of 32, scale 2.5, T 32, one MTP module
T, D, F, E, K, V = 32, 64, 32, 8, 2, 96
LAMBDA = 0.3


def _model(**over):
    return MLAMoETransformerLM(**{**dict(vocab_size=V, attn_impl="flash"), **over})


def _arch(first=0, mtp_weight=LAMBDA):
    return reference.Arch(num_heads=4, nope_dim=16, rope_dim=8, v_dim=16, kv_rank=32, layers=3,
                          top_k=K, route_scale=2.5, experts_first=first, mtp_weight=mtp_weight,
                          rope_theta=32e6, rms_eps=1e-6)


def _seeded(model, seed=0):
    tokens = jnp.asarray(np.random.RandomState(seed).randint(0, V, (2, T + 1)), jnp.int32)
    params = dict(model.init(jax.random.key(seed), tokens[:, :-1])["params"])
    # a wide embedding, so that routing follows the token; a selection bias that
    # moves the choice for some tokens and not for all
    params["tok_embed"] = {"embedding": 50.0 * params["tok_embed"]["embedding"]}
    for name in ("block_1", "block_2", "mtp_block"):
        params[name] = {**params[name], "select_bias": {
            "kernel": 0.1 * params[name]["select_bias"]["kernel"]}}
    return params, tokens[:, :-1], tokens[:, 1:]


def _batch(x, y):
    return {"x": x, "y": y, "mask": jnp.ones(x.shape, jnp.float32)}


@pytest.mark.parametrize("attn_impl,first,held,remat", [
    ("flash", 2, 4, False), ("xla", 0, 8, False), ("flash", 0, 8, True)])
def test_model_equals_the_plain_reference(attn_impl, first, held, remat):
    """Both logits, the loss with its MTP term and every gradient to 1e-5,
    whole and on a share, through the trainer's ``loss_fn``."""
    model = _model(attn_impl=attn_impl, experts_first=first, experts_held=held, remat=remat)
    params, x, y = _seeded(model)
    arch = _arch(first)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))

    def loss(params):
        return trainer.loss_fn(params, {}, params, _batch(x, y), jax.random.key(0))[0]

    def ref_loss(params):
        return jnp.mean(jnp.stack([reference._seq_loss(params, row, tgt, arch, "f32")
                                   for row, tgt in zip(x, y)]))

    logits, state = model.apply({"params": params}, x, train=True, mutable=[MTP_COLLECTION])
    ahead = state[MTP_COLLECTION]["next2"]
    assert float(ahead["weight"]) == pytest.approx(LAMBDA)
    for row in range(2):
        want, want_ahead = reference.forward(params, x[row], arch)
        np.testing.assert_allclose(logits[row], want, atol=2e-5)
        # the program's last position is padding: it has no second-next token
        np.testing.assert_allclose(ahead["logits"][row, :-1], want_ahead, atol=2e-5)
    got, grads = jax.value_and_grad(loss)(params)
    want, ref_grads = jax.value_and_grad(ref_loss)(params)
    assert abs(float(got) - float(want)) <= 1e-5
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref_grads))
    # dense block 7 + 2 + 3, routed 7 + 2 + 2 + 3 + 3, the MTP module's 4, embedding, norm, head
    assert flat.keys() == ref_flat.keys() and len(flat) == 12 + 3 * 17 + 4 + 3
    for path in flat:
        np.testing.assert_allclose(flat[path], ref_flat[path], atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))
    ref_batch_loss, batch_grads, state = reference.loss_and_grad(
        {"params": params}, {"x": np.asarray(x), "y": np.asarray(y), "arch": arch})
    assert abs(float(ref_batch_loss) - float(want)) <= 1e-6 and state == {}
    np.testing.assert_allclose(batch_grads["head"]["kernel"], ref_grads["head"]["kernel"],
                               atol=1e-6)


def test_the_mtp_term_is_the_loss_weight_times_a_second_cross_entropy():
    model = _model(experts_held=4)
    params, x, y = _seeded(model)
    batch = _batch(x, y)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
    logits, state = model.apply({"params": params}, x, train=True, mutable=[MTP_COLLECTION])
    ahead = state[MTP_COLLECTION]["next2"]["logits"]
    further = one_token_further(batch)
    np.testing.assert_array_equal(further["y"][:, :-1], y[:, 1:])
    assert float(jnp.sum(further["mask"])) == 2 * (T - 1) and not further["mask"][:, -1].any()
    total = trainer.loss_fn(params, {}, params, batch, jax.random.key(0))[0]
    assert float(total) == pytest.approx(
        float(lm_loss(logits, batch) + LAMBDA * lm_loss(ahead, further)), rel=1e-6)
    # lambda 0 in the reference is the next-token loss alone
    main = jnp.mean(jnp.stack([reference._seq_loss(params, r, t, _arch(mtp_weight=0.0), "f32")
                               for r, t in zip(x, y)]))
    assert float(lm_loss(logits, batch)) == pytest.approx(float(main), abs=1e-5)
    # eval and train=False build no MTP logits, and the padded position's garbage reaches no loss
    plain, state = model.apply({"params": params}, x, train=False, mutable=[MTP_COLLECTION])
    assert MTP_COLLECTION not in state
    np.testing.assert_allclose(plain, logits, atol=1e-6)
    metrics = trainer.eval_batch({"params": params}, batch)
    assert float(metrics["test_loss"]) == pytest.approx(
        float(lm_loss(logits, batch)) * 2 * T, rel=1e-5)
    other = x.at[:, 0].set((x[:, 0] + 1) % V)  # the token rolled round to the last position
    moved = model.apply({"params": params}, other, train=True, mutable=[MTP_COLLECTION])[1]
    assert not np.allclose(moved[MTP_COLLECTION]["next2"]["logits"][:, -1], ahead[:, -1])


def test_a_model_without_the_collection_runs_the_program_it_ran():
    """``loss_fn`` of a model that sows no MTP logits: the same jaxpr with
    and without the collection asked for."""
    from fedml_tpu.core import trainer as trainerlib
    from fedml_tpu.models.moe_transformer import MoETransformerLM

    model = MoETransformerLM(vocab_size=V, experts_held=4)
    x = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.key(0), x)["params"]
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
    args = (params, {}, params, _batch(x, x), jax.random.key(0))
    now = str(jax.make_jaxpr(trainer.loss_fn)(*args))
    real = trainerlib.MTP_COLLECTION
    try:
        trainerlib.MTP_COLLECTION = trainerlib.STATS_COLLECTION  # asked for nothing new
        before = str(jax.make_jaxpr(trainer.loss_fn)(*args))
    finally:
        trainerlib.MTP_COLLECTION = real
    assert now == before


def test_rope_turns_adjacent_pairs():
    x = jax.random.normal(jax.random.key(0), (1, 2, 5, 8))
    got = rope_interleaved(x, 100.0)
    np.testing.assert_allclose(got[:, :, 0], x[:, :, 0], atol=1e-7)  # position 0: no turn
    for pos in (1, 4):
        for i in range(4):
            angle = pos * 100.0 ** (-2 * i / 8)
            a, b = x[0, 1, pos, 2 * i], x[0, 1, pos, 2 * i + 1]
            np.testing.assert_allclose(got[0, 1, pos, 2 * i], a * np.cos(angle) - b * np.sin(angle),
                                       atol=1e-5)
            np.testing.assert_allclose(got[0, 1, pos, 2 * i + 1],
                                       b * np.cos(angle) + a * np.sin(angle), atol=1e-5)
    np.testing.assert_allclose(reference._rope(x[0], 100.0), got[0], atol=1e-6)


# -- the router ------------------------------------------------------------------


def _router_inputs(seed=1, tokens=64):
    u, router, bias = (jax.random.normal(jax.random.fold_in(jax.random.key(seed), i), shape)
                       for i, shape in enumerate([(tokens, D), (D, E), (E,)]))
    return u, router / np.sqrt(D), bias


def test_the_bias_moves_the_choice_never_the_weights_and_gets_no_gradient():
    u, router, bias = _router_inputs()
    scores = jax.nn.sigmoid(u @ router)
    ids0, w0 = moe.route(u, router, K, select_bias=jnp.zeros(E), scale=2.5)
    ids, w = moe.route(u, router, K, select_bias=bias, scale=2.5)
    np.testing.assert_array_equal(ids0, jax.lax.top_k(scores, K)[1])
    np.testing.assert_array_equal(ids, jax.lax.top_k(scores + bias, K)[1])
    moved = np.any(np.sort(ids, -1) != np.sort(ids0, -1), axis=-1)
    assert 0.2 < moved.mean() < 1.0  # a unit bias re-chooses many tokens, not all
    # the weights are the chosen scores over their sum, times 2.5: no bias in them
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    # a token whose choice the bias leaves alone keeps its weights to the bit
    same = ~moved & np.all(np.asarray(ids) == np.asarray(ids0), axis=-1)
    assert same.any()
    np.testing.assert_array_equal(np.asarray(w)[same], np.asarray(w0)[same])

    def loss(bias, router):
        ids, w = moe.route(u, router, K, select_bias=bias, scale=2.5)
        return jnp.sum(w * (1 + ids))

    g_bias, g_router = jax.grad(loss, argnums=(0, 1))(bias, router)
    assert not np.asarray(g_bias).any() and np.asarray(g_router).any()
    ref_ids, ref_w = reference.route(u, router, bias, _arch(), jnp.dot)
    np.testing.assert_array_equal(ref_ids, ids)
    np.testing.assert_allclose(ref_w, w, rtol=1e-6)


def test_softmax_routing_is_what_it_was():
    u, router, _ = _router_inputs()
    ids, w = moe.route(u, router, K)
    top, want = jax.lax.top_k(jnp.dot(u, router, precision=moe.HI), K)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_array_equal(w, jax.nn.softmax(top, axis=-1))


def test_the_model_gives_the_bias_a_zero_gradient_and_sgd_leaves_it():
    model = _model(experts_held=4)
    params, x, y = _seeded(model)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.5))
    new, _, loss, _ = jax.jit(trainer.train_step_stats)(
        {"params": params}, trainer.optimizer.init(params), params, _batch(x, y),
        jax.random.key(0))
    for name in ("block_1", "block_2", "mtp_block"):
        np.testing.assert_array_equal(new["params"][name]["select_bias"]["kernel"],
                                      params[name]["select_bias"]["kernel"])
        assert not np.array_equal(new["params"][name]["router"]["kernel"],
                                  params[name]["router"]["kernel"])
    assert np.isfinite(float(loss))


# -- the shares ------------------------------------------------------------------


def _block(model, routed):
    from fedml_tpu.models.mla_moe_transformer import MLABlock

    held = model.num_experts if model.experts_held is None else model.experts_held
    return MLABlock(
        routed, model.num_heads, model.q_rank, model.kv_rank, model.nope_dim, model.rope_dim,
        model.v_dim, model.dense_dim, model.num_experts, model.experts_per_token,
        model.expert_dim, model.shared_dim, model.route_scale, model.experts_first, held,
        model.rope_theta, model.rms_eps, model.attn_impl, model.dtype)


def test_the_shares_add_up_to_the_uncut_layer():
    """A routed layer over 4 shares of 2 experts each: the routed parts
    summed, with what every chip computes alike (the residual, attention and
    the shared expert) counted once, equal the uncut reference layer; and
    the dense layer, whole on every chip, equals the reference's."""
    whole = _model(attn_impl="xla")
    params, x, _ = _seeded(whole)
    arch = _arch()
    h = params["tok_embed"]["embedding"][x[0]]
    p = params["block_1"]
    want = reference.block(h, p, arch, "f32")
    # what every chip computes alike: the layer with no expert's part
    alike = reference.block(
        h, {**p, "experts": jax.tree.map(jnp.zeros_like, p["experts"])}, arch, "f32")
    parts = []
    for first in range(0, E, 2):
        share = _model(attn_impl="xla", experts_first=first, experts_held=2)
        held = {**p, "experts": jax.tree.map(lambda a: a[first:first + 2], p["experts"])}
        out = _block(share, True).apply({"params": held}, h[None])[0][0]
        np.testing.assert_allclose(
            out, reference.block(h, held, arch._replace(experts_first=first), "f32"), atol=2e-5)
        parts.append(out - alike)  # this share's routed part
    np.testing.assert_allclose(alike + sum(parts), want, atol=5e-5)
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in parts)
    assert float(jnp.abs(want - alike).max()) > 1e-2
    dense = _block(whole, False).apply({"params": params["block_0"]}, h[None])[0][0]
    np.testing.assert_allclose(dense, reference.block(h, params["block_0"], arch, "f32"),
                               atol=2e-5)


@pytest.mark.parametrize("chosen,held_share", [((3, 9), 0.5), ((8, 9), 0.0), ((2, 3), 1.0)])
def test_skewed_routing_stays_dropless_under_silu(chosen, held_share):
    """Every token to the same two experts (of 12; 2 .. 5 held): the held
    part equals a dense SwiGLU over those experts, none dropped."""
    rng = jax.random.key(3)
    u, gate, up, down = (jax.random.normal(jax.random.fold_in(rng, i), s) * sc for i, (s, sc) in
                         enumerate([((T, D), 1.0), ((4, D, F), D ** -0.5), ((4, D, F), D ** -0.5),
                                    ((4, F, D), F ** -0.5)]))
    ids = jnp.tile(jnp.asarray(chosen, jnp.int32), (T, 1))
    weights = jnp.full((T, 2), 1.25, jnp.float32)
    out, stats = moe.expert_layer(u, ids, weights, gate, up, down, first=2, count=4,
                                  dtype=jnp.float32, activation=jax.nn.silu)
    want = jnp.zeros((T, D))
    for e in chosen:
        if 2 <= e < 6:
            want += 1.25 * (jax.nn.silu(u @ gate[e - 2]) * (u @ up[e - 2])) @ down[e - 2]
    np.testing.assert_allclose(out, want, atol=1e-4)
    assert float(stats["moe/assignments_held"]) == held_share * 2 * T


# -- scopes, notes and counters ----------------------------------------------------


def test_scopes_kernels_and_notes_in_the_lowered_training_step():
    """``attn/mla``, ``moe/shared`` and ``mtp`` inside ``fed/fwd_bwd``,
    forward and backward; the MTP loss under ``mtp`` and ``fed/loss``; the
    three kernels by name; ``attn/call`` notes with both widths; ``remat/kept``
    notes of a rematerialised block."""
    model = _model(experts_first=2, experts_held=4, remat=True)
    params, x, y = _seeded(model)
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
    text = jax.jit(trainer.train_step_stats).lower(
        {"params": params}, trainer.optimizer.init(params), params, _batch(x, y),
        jax.random.key(0)).as_text(debug_info=True)
    lines = text.splitlines()
    assert trace.MLA_SCOPES == ("attn/mla", "moe/shared", "mtp")
    assert not set(trace.MLA_SCOPES) & (set(trace.SCOPES) | set(trace.MOE_SCOPES))
    for scope in trace.MLA_SCOPES:
        assert any(f"/{scope}/" in ln and "fed/fwd_bwd" in ln and "transpose(" not in ln
                   for ln in lines), scope
        assert any(f"/{scope}/" in ln and "fed/fwd_bwd" in ln and "transpose(" in ln
                   for ln in lines), scope
    # the trainer's scope is the loss's outermost: jax writes it jvp(mtp)
    assert any("jvp(mtp)/fed/loss/" in ln and "transpose(" not in ln for ln in lines)
    assert any("transpose(jvp(mtp))/fed/loss/" in ln for ln in lines)
    assert any("/mtp/mtp_block/" in ln and "attn/mla" in ln for ln in lines)
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert any(kernel in ln and "attn/mla" in ln for ln in lines), kernel
    assert "flash_bwd_dq" not in text
    notes = [n for n in trace.program_notes("attn/call") if n["shape"] == (2, 4, T, 24)]
    assert {n["kernel"]: n["writes"] for n in notes} == {
        "fwd": ("out", "lse"), "dkv": ("dq", "dk", "dv")}
    assert all((n["d_qk"], n["d_v"], n["kind"]) == (24, 16, "global") for n in notes)
    kept = {n["kept"]: n for n in trace.program_notes("remat/kept")
            if n["shape"][-2:] in ((T, 24), (T, 16)) and n["shape"][0] == 2}
    assert {"attn/q", "attn/k", "attn/v", "attn/out"} <= set(kept)
    assert kept["attn/k"]["bytes"] == 2 * 4 * T * 24 * 4
    assert kept["attn/v"]["bytes"] == 2 * 4 * T * 16 * 4


def test_a_rematerialised_block_routes_once():
    """The router reads a stream the block recomputes, and a second top-k
    over a stream that differs in a last bit can choose otherwise for a token
    in a near tie, against the kept layout (on the chip: one token's row of
    garbage, NaN in every gradient below it). The ids are kept by name, so
    the backward pass holds no second top-k."""
    from fedml_tpu.ops import remat

    def top_ks(remat_on):
        model = _model(experts_held=4, remat=remat_on)
        params, x, y = _seeded(model)
        trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: trainer.loss_fn(p, {}, p, _batch(x, y), jax.random.key(0))[0]))(params)
        return str(jaxpr).count("top_k[")

    assert top_ks(False) == 3  # two routed blocks and the MTP module's
    assert top_ks(True) == 3
    assert remat.MOE_IDS in remat.KEPT
    kept = {n["kept"] for n in trace.program_notes("remat/kept")}
    assert remat.MOE_IDS in kept


@pytest.mark.parametrize("overflow", [False, True], ids=["within_capacity", "overflow"])
@pytest.mark.parametrize("sigmoid", [False, True], ids=["softmax", "sigmoid"])
def test_a_recomputed_stream_that_differs_in_its_last_bits_moves_no_row(sigmoid, overflow,
                                                                        monkeypatch):
    """A router that reads what a rematerialised block recomputes, under
    either scoring. The stream comes through a host callback that nudges one
    element of token 0 from its second evaluation on (x (1 + 2^-18): the
    recompute), which turns that token's tie between a held and an absent
    expert. With the ids kept by name the gradients are those of the layer
    without remat; with ``moe/ids`` off the list the second choice meets the
    first's layout and they are not. With ``overflow`` the layer's buffers
    hold 6 rows (a router said to be 8 wide, row tiles of 2) and the held
    assignments pass them, so the overflow loop's backward reads the kept
    layout beside the recomputed stream too."""
    from fedml_tpu.ops import remat

    if overflow:
        monkeypatch.setattr(moe, "GMM_TILES", (2, 1280, 1280))
    width = 8 if overflow else 2

    held = 2
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    x[0] = 0.0
    x[0, :2] = 0.5
    router = np.zeros((8, 4), np.float32)  # token 0: x[0, 0] scores expert 1, x[0, 1] expert 2
    router[0, 1] = router[1, 2] = 1.0
    router[2:, (0, 3)] = 0.01 * rng.randn(6, 2)
    stacks = [rng.randn(*shape).astype(np.float32)
              for shape in [(held, 8, 8), (held, 8, 8), (held, 8, 8)]]

    def layer(calls):
        def host(x):
            calls.append(1)
            x = np.array(x)
            if len(calls) > 1:
                x[0, 1] *= np.float32(1 + 2.0 ** -18)
            return x

        @jax.custom_jvp
        def stream(x):
            return jax.pure_callback(host, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        stream.defjvp(lambda primals, tangents: (stream(primals[0]), tangents[0]))

        def f(x, router, gate, up, down):
            u = stream(x)
            bias = dict(select_bias=jnp.zeros(4), scale=2.5) if sigmoid else {}
            ids, weights = moe.route(u, router, 1, **bias)
            with moe.router_width(width):
                out, stats = moe.expert_layer(u, ids, weights, gate, up, down, first=0,
                                              count=held, dtype=jnp.float32)
            return jnp.sum(out * jnp.arange(1.0, 9.0)), stats["moe/overflow_tiles"]

        return f

    def grads(names):
        calls = []
        f = layer(calls)
        if names is not None:
            f = jax.checkpoint(f, policy=jax.checkpoint_policies.save_only_these_names(*names))
        out, tiles = jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(x, router, *stacks)
        assert (float(tiles) > 0) == overflow
        return [np.asarray(g) for g in out], len(calls)

    want, calls = grads(None)
    assert calls == 1
    got, calls = grads(remat.KEPT)
    assert calls == 2  # the backward pass read the stream again, nudged
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)
    loose, _ = grads(tuple(n for n in remat.KEPT if n != remat.MOE_IDS))
    assert np.abs(loose[0] - want[0]).max() > 1.0


def test_fedsim_round_trains_the_model_and_carries_the_counts():
    """Through ``FedSim.run`` with the scan cohort: the loss falls, the
    reported eval is of the main logits, and each routed block's count (the
    MTP module's last) becomes a counter."""
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    rows = np.random.RandomState(0).randint(0, V, (8, T + 1)).astype(np.int32)
    train = FederatedArrays({"x": rows[:, :-1], "y": rows[:, 1:],
                             "mask": np.ones((8, T), np.float32)},
                            {i: np.arange(2 * i, 2 * i + 2) for i in range(4)})
    trainer = ClientTrainer(module=_model(experts_held=4), task="nwp",
                            optimizer=optax.sgd(0.05), epochs=1)
    cfg = SimConfig(client_num_in_total=4, client_num_per_round=4, batch_size=1, comm_round=3,
                    epochs=1, frequency_of_the_test=100, cohort_execution="scan",
                    block_dispatch=False)
    tracer = trace.install()
    try:
        _, history = FedSim(trainer, train, None, cfg).run()
    finally:
        trace.uninstall()
    assert history[1]["Train/Loss"] < history[0]["Train/Loss"]
    keys = [f"stats/moe/assignments_held/layer_{i}" for i in range(3)]
    assert all(k in history[-1] for k in keys)
    assert 0 < history[-1][keys[2]] <= T * K
    assert trace.last_counters("moe/assignments_held/")["moe/assignments_held/layer_2"] == (
        history[-1][keys[2]])
    assert [e for e in tracer.events() if e["ph"] == "C" and e["name"].startswith("moe/")]


def test_registry_builds_the_model():
    from fedml_tpu.models import registry

    model = registry.create_model("mla_moe_transformer", V)
    assert isinstance(model, MLAMoETransformerLM) and model.vocab_size == V
    assert registry.create_model("mla_moe_transformer", V, dtype="bfloat16").dtype == jnp.bfloat16
