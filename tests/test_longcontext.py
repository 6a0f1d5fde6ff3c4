"""Long-context stack: pallas flash attention, ring attention over the sp
mesh axis, and the sequence-parallel transformer train step (all on the
8-virtual-device CPU mesh; the pallas kernel runs in interpreter mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from functools import partial
from jax.sharding import PartitionSpec as P


from fedml_tpu.ops.attention import attention_reference, flash_attention
from fedml_tpu.parallel.ring_attention import ring_attention
from fedml_tpu.parallel import sequence as seqlib
from fedml_tpu.models.transformer import TransformerLM


def _qkv(rng, b=2, h=2, t=64, d=8):
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(rng, causal):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, causal, None, 16, 16)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_flash_gradients(rng):
    q, k, v = _qkv(rng, t=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 8, 8) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact(rng, causal):
    mesh = seqlib.sequence_mesh(8)
    q, k, v = _qkv(rng, t=64)

    ring = partial(ring_attention, axis_name="sp", causal=causal)
    sharded = jax.shard_map(
        ring,
        mesh=mesh,
        in_specs=(P(None, None, "sp"), P(None, None, "sp"), P(None, None, "sp")),
        out_specs=P(None, None, "sp"),
        check_vma=False,
    )
    out = jax.jit(sharded)(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.slow  # compile-heavy on XLA:CPU; kept out of the fast gate
def test_sp_train_step_matches_single_device(rng):
    vocab, b, t = 31, 2, 64
    mesh = seqlib.sequence_mesh(8)
    x = rng.randint(0, vocab, (b, t))
    y = np.roll(x, -1, axis=1)
    batch = {
        "x": x.astype(np.int32),
        "y": y.astype(np.int32),
        "mask": np.ones((b, t), np.float32),
    }

    def build(attn):
        return TransformerLM(
            vocab_size=vocab, embed_dim=32, num_layers=2, num_heads=2,
            max_len=t, attn_impl=attn,
        )

    ref_model = build("xla")
    sp_model = build("ring")
    params = ref_model.init(jax.random.key(0), jnp.asarray(batch["x"]))["params"]
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)

    # single-device reference step
    def ref_loss(p):
        logits = ref_model.apply({"params": p}, jnp.asarray(batch["x"]), train=True)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, jnp.asarray(batch["y"]))
        return jnp.mean(ce)

    ref_loss_val, ref_grads = jax.value_and_grad(ref_loss)(params)
    updates, _ = opt.update(ref_grads, opt_state, params)
    ref_params = optax.apply_updates(params, updates)

    step = seqlib.make_sp_lm_train_step(sp_model, opt, mesh)
    sp_batch = seqlib.shard_lm_batch(batch, mesh)
    sp_params, _, sp_loss = step(params, opt_state, sp_batch, jax.random.key(1))

    np.testing.assert_allclose(float(sp_loss), float(ref_loss_val), atol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(ref_params)
    flat_sp = jax.tree_util.tree_leaves(sp_params)
    for a, b_ in zip(flat_ref, flat_sp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


def test_transformer_in_fed_sim(rng):
    """TransformerLM slots into the vectorized FL engine as an nwp client."""
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    vocab, t, n_clients, per_client = 17, 16, 8, 24
    arrays, cidx = {}, []
    xs = rng.randint(0, vocab, (n_clients * per_client, t)).astype(np.int32)
    ys = np.roll(xs, -1, axis=1)
    mask = np.ones((n_clients * per_client, t), np.float32)
    partition = {
        c: np.arange(c * per_client, (c + 1) * per_client)
        for c in range(n_clients)
    }
    fed = FederatedArrays({"x": xs, "y": ys, "mask": mask}, partition)
    model = TransformerLM(vocab_size=vocab, embed_dim=16, num_layers=1,
                          num_heads=2, max_len=t)
    trainer = ClientTrainer(module=model, task="nwp",
                            optimizer=optax.sgd(0.1), epochs=1)
    sim = FedSim(
        trainer, fed, {"x": xs[:16], "y": ys[:16], "mask": mask[:16]},
        SimConfig(client_num_in_total=n_clients, client_num_per_round=8,
                  batch_size=8, comm_round=2, frequency_of_the_test=2),
    )
    _, history = sim.run()
    assert len(history) == 2
    assert np.isfinite(history[-1]["Train/Loss"])


def test_flash_bwd_fully_masked_rows(rng):
    """Causal cross-attention with t_q > t_k right-aligns the key window, so
    the first t_q - t_k query rows attend to nothing. The forward kernel
    zeroes those rows; the blockwise backward must produce zero (not O(1)
    garbage from exp(NEG_INF - NEG_INF)) gradients through them, even when
    the upstream cotangent is nonzero there."""
    b, h, t_q, t_k, d = 1, 2, 16, 8, 8
    q = jnp.asarray(rng.randn(b, h, t_q, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t_k, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t_k, d), jnp.float32)
    cot = jnp.asarray(rng.randn(b, h, t_q, d), jnp.float32)  # nonzero everywhere

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 8, 8) * cot)

    def loss_ref(q, k, v):
        # reference with fully-masked rows forced to the kernel's zero output
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        p = jnp.where(mask.any(-1)[:, None], p, 0.0)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) * cot)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    n_masked = t_q - t_k
    np.testing.assert_array_equal(np.asarray(g1[0][:, :, :n_masked]), 0.0)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=1e-4)


def _masked_reference(q, k, v, causal):
    """attention_reference in f32 with fully masked query rows (causal,
    t_q > t_k) forced to the kernels' zero output, and the log-sum-exp of
    each row's scores."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    t_q, t_k = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q) if causal \
        else jnp.ones((t_q, t_k), bool)
    s = jnp.where(mask, s, -jnp.inf)
    seen = mask.any(-1)[:, None]
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, 0.0), axis=-1), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), jax.nn.logsumexp(s, axis=-1)


# (t_q, t_k, d, dtype, blocks): several blocks a side, so that the causal
# skipping and both loops of a kernel (masked tiles, whole tiles) engage
_BWD_CASES = [
    (64, 64, 8, jnp.float32, (16, 16)),
    (64, 64, 8, jnp.float32, (32, 16)),    # block_q != block_k, both ways
    (64, 64, 8, jnp.float32, (16, 32)),
    (32, 64, 8, jnp.float32, (16, 16)),    # t_q < t_k: right-aligned offset
    (32, 16, 8, jnp.float32, (8, 8)),      # t_q > t_k: fully masked rows
    (64, 64, 16, jnp.bfloat16, (16, 16)),
    (32, 64, 16, jnp.bfloat16, (16, 32)),
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t_q,t_k,d,dtype,blocks", _BWD_CASES)
def test_flash_bwd_kernels_match_reference(rng, causal, t_q, t_k, d, dtype, blocks):
    """dq, dk, dv of the one backward kernel, and the forward kernel's lse
    that feeds it, against jax's own gradient of the plain reference."""
    from fedml_tpu.ops.attention import _flash_bwd, _flash_fwd

    q, k, v, g = (jnp.asarray(rng.randn(2, 2, t, d), dtype)
                  for t in (t_q, t_k, t_k, t_q))
    sm_scale = d ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, *blocks, True)
    got = _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, *blocks, True)
    assert lse.shape == (2, 2, t_q) and lse.dtype == jnp.float32
    (want_out, want_lse), vjp = jax.vjp(
        lambda q, k, v: _masked_reference(q, k, v, causal), q, k, v)
    want = vjp((g.astype(jnp.float32), jnp.zeros_like(want_lse)))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    seen = np.isfinite(np.asarray(want_lse))  # a fully masked row keeps ~NEG_INF
    np.testing.assert_allclose(np.asarray(lse)[seen], np.asarray(want_lse)[seen],
                               atol=tol)
    assert np.all(np.asarray(lse)[~seen] < -1e29)
    np.testing.assert_allclose(out.astype(jnp.float32), want_out, atol=tol)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == dtype, name
        np.testing.assert_allclose(a.astype(jnp.float32), b, atol=tol * 10,
                                   rtol=tol, err_msg=name)
    if causal and t_q > t_k:
        np.testing.assert_array_equal(np.asarray(got[0][:, :, :t_q - t_k]), 0.0)


# dQ is summed in VMEM across a head's key blocks (zeroed at the first, written
# at the last): tiles of 8-32 so that every dQ row meets three or more of them.
# (shape of q, KV heads, t_k, d_v, dtype, causal, window, (block_q, block_k))
_DQ_CASES = {
    "causal": ((2, 2, 64, 8), 2, 64, 8, jnp.float32, True, None, (16, 16)),
    "full": ((2, 2, 64, 8), 2, 64, 8, jnp.float32, False, None, (32, 8)),
    # key blocks 0 .. 3 lie wholly before the last query block's window
    "window": ((1, 2, 96, 8), 2, 96, 8, jnp.float32, True, 24, (16, 16)),
    "window-bf16": ((1, 2, 96, 16), 2, 96, 16, jnp.bfloat16, True, 40, (32, 16)),
    "grouped": ((2, 4, 64, 8), 2, 64, 8, jnp.float32, True, None, (16, 16)),
    "grouped-window": ((1, 6, 64, 8), 2, 64, 8, jnp.float32, True, 20, (8, 16)),
    "192-on-128": ((1, 2, 64, 192), 2, 64, 128, jnp.float32, True, None, (16, 16)),
    "192-on-128-bf16": ((1, 2, 64, 192), 2, 64, 128, jnp.bfloat16, True, None, (16, 16)),
    "64-wide-bf16": ((2, 4, 64, 64), 2, 64, 64, jnp.bfloat16, True, None, (16, 16)),
    "t_q<t_k": ((2, 2, 32, 8), 2, 64, 8, jnp.float32, True, None, (16, 16)),
    "t_q<t_k-bf16": ((1, 2, 32, 16), 1, 96, 16, jnp.bfloat16, True, None, (16, 32)),
    # right-aligned: the first 24 query rows see no key, their dQ is zero
    "masked-rows": ((2, 2, 48, 8), 2, 24, 8, jnp.float32, True, None, (8, 8)),
}


@pytest.mark.parametrize("case", sorted(_DQ_CASES))
def test_flash_bwd_sums_dq_across_key_blocks(rng, case):
    """The one backward kernel's dQ (and dK, dV beside it) against jax's
    gradient of ``attention_reference``, where a query row's terms come from
    three or more grid steps and, in the second head on, after another head
    has used the accumulator."""
    from fedml_tpu.ops.attention import _flash_bwd, _flash_fwd

    q_shape, kv_heads, t_k, d_v, dtype, causal, window, blocks = _DQ_CASES[case]
    b, h, t_q, d = q_shape
    assert t_k // blocks[1] >= 3 and b * h >= 2
    q, g = (jnp.asarray(rng.randn(b, h, t_q, w), dtype) for w in (d, d_v))
    k, v = (jnp.asarray(rng.randn(b, kv_heads, t_k, w), dtype) for w in (d, d_v))
    sm_scale = d ** -0.5
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, *blocks, True, window)
    got = _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, *blocks, True, window)
    if t_q > t_k:  # the kernels' zero output for rows that see nothing
        def reference(q, k, v):
            return _masked_reference(q, k, v, causal)[0]
    else:
        def reference(q, k, v):
            return attention_reference(q, k, v, causal=causal, window=window)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    want = jax.vjp(reference, *f32)[1](g.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for name, a, w, x in zip(("dq", "dk", "dv"), got, want, (q, k, v)):
        assert a.dtype == dtype and a.shape == x.shape, name
        np.testing.assert_allclose(a.astype(jnp.float32), w, atol=tol * 10, rtol=tol,
                                   err_msg=name)
    if t_q > t_k:
        np.testing.assert_array_equal(np.asarray(got[0][:, :, :t_q - t_k]), 0.0)


def test_flash_bwd_sums_dq_under_a_cohort_vmap(rng, monkeypatch):
    """The vmapped cohort adds a grid axis in front: the key-block axis the
    accumulator's first and last steps are told by moves with it."""
    import fedml_tpu.ops.attention as att

    monkeypatch.setattr(att, "_bwd_blocks", lambda *a: (16, 16))
    q = jnp.asarray(rng.randn(3, 1, 4, 64, 8), jnp.float32)
    k, v = (jnp.asarray(rng.randn(3, 1, 2, 64, 8), jnp.float32) for _ in range(2))

    def grads(fn):
        return jax.vmap(jax.grad(lambda *x: jnp.sum(fn(*x) ** 2), argnums=(0, 1, 2)))(q, k, v)

    got = grads(lambda q, k, v: flash_attention(q, k, v, True, None, 16, 16, 24))
    want = grads(lambda q, k, v: attention_reference(q, k, v, causal=True, window=24))
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_with_the_picked_blocks(rng, causal):
    """The public path at the kernels' own width: T 256, D 128, bf16, the
    backward's tiles chosen by ``_bwd_blocks`` (not the forward's 64 x 64)."""
    from fedml_tpu.ops.attention import _bwd_blocks

    q, k, v, g = (jnp.asarray(rng.randn(1, 2, 256, 128), jnp.bfloat16)
                  for _ in range(4))
    assert _bwd_blocks(256, 256, jnp.bfloat16, (64, 64)) == (256, 256)
    assert _bwd_blocks(2048, 1024, jnp.bfloat16, (256, 1024)) == (512, 512)
    # 1072 = 16 x 67: no divisor up to 512 is a sublane multiple but 16
    assert _bwd_blocks(1072, 1072, jnp.float32, (16, 16)) == (16, 16)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                       * g.astype(jnp.float32))

    got = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, causal, None, 64, 64)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _masked_reference(q, k, v, causal)[0]),
                    argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err <= 2e-2, (name, err)  # chip_smoke.py's bound


def test_flash_bwd_path_event(rng):
    """Tracing a gradient records one ``attn/bwd_path`` event a custom-VJP
    backward, so a fallback, were one left, is seen and counted."""
    from fedml_tpu.obs import trace

    q, k, v = _qkv(rng, t=32)

    def loss(q, k, v):  # two attention calls: two backwards
        a = flash_attention(q, k, v, True, None, 8, 8)
        return jnp.sum(flash_attention(a, k, v, False, None, 8, 8))

    tracer = trace.install()
    try:
        jax.jit(jax.grad(loss)).lower(q, k, v)
        jax.jit(loss).lower(q, k, v)  # a forward alone records nothing
    finally:
        trace.uninstall()
    events = [e for e in tracer.events() if e["name"] == "attn/bwd_path"]
    assert len(events) == 2
    for e in events:
        assert e["args"]["impl"] == "fused"
        assert tuple(e["args"]["shape"]) == (2, 2, 32, 8)
        assert tuple(e["args"]["blocks"]) == (32, 32)


@pytest.mark.slow  # compile-heavy on XLA:CPU; kept out of the fast gate
def test_transformer_remat_matches_plain():
    """jax.checkpoint on blocks must not change values or gradients."""
    import numpy as np
    import optax

    from fedml_tpu.models.transformer import TransformerLM

    x = jnp.asarray(np.random.RandomState(0).randint(0, 50, (2, 16)), jnp.int32)
    y = jnp.asarray(np.random.RandomState(1).randint(0, 50, (2, 16)), jnp.int32)
    plain = TransformerLM(vocab_size=50, embed_dim=32, num_layers=2, num_heads=4,
                          max_len=16)
    remat = TransformerLM(vocab_size=50, embed_dim=32, num_layers=2, num_heads=4,
                          max_len=16, remat=True)
    v = plain.init({"params": jax.random.key(0)}, x, train=False)

    def loss(model, variables):
        logits = model.apply(variables, x, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    l1, g1 = jax.value_and_grad(lambda v_: loss(plain, v_))(v)
    l2, g2 = jax.value_and_grad(lambda v_: loss(remat, v_))(v)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    # the remat wrapper must also train with dropout (train is static)
    dr = TransformerLM(vocab_size=50, embed_dim=32, num_layers=2, num_heads=4,
                       max_len=16, remat=True, dropout_rate=0.1)
    vd = dr.init({"params": jax.random.key(0), "dropout": jax.random.key(1)},
                 x, train=True)
    out = dr.apply(vd, x, train=True, rngs={"dropout": jax.random.key(2)})
    assert np.isfinite(np.asarray(out)).all()
