"""The head-and-loss operator (``fedml_tpu/ops/head_loss.py``): in chunks of
rows it gives the plain expression's loss and gradients to float32 rounding,
whatever the head (bias, tied, P heads), the mask, the batching and the mesh;
one chunk is the parent's jaxpr; a chunked step holds no ``[rows, V]`` array;
the chunk rule is a function of the shape alone."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.core import trainer as trainerlib
from fedml_tpu.core.trainer import ClientTrainer, lm_loss, one_token_further
from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM
from fedml_tpu.models.moe_transformer import MoETransformerLM
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.obs import trace
from fedml_tpu.ops import head_loss as hl
from fedml_tpu.ops.head_loss import HeadOperands, chunking, head_loss
from fedml_tpu.parallel.mesh import MODEL_AXIS

D, V = 16, 50


def _chunks_of(monkeypatch, rows):
    """Chunks of ``rows`` rows in tiles of 8, whatever the logits' size."""
    monkeypatch.setattr(hl, "WHOLE_BYTES", 0)
    monkeypatch.setattr(hl, "CHUNK_ROWS", rows)
    monkeypatch.setattr(hl, "TILE_ROWS", 8)


@pytest.fixture
def small_chunks(monkeypatch):
    _chunks_of(monkeypatch, 32)


def _plain(h, kernel, bias, targets, mask, tied=False, heads=1):
    z = h @ (kernel.T if tied else kernel)
    if bias is not None:
        z = z + bias
    if heads > 1:
        z = z.reshape(*z.shape[:-1], heads, -1)
    return jnp.sum(optax.softmax_cross_entropy_with_integer_labels(z, targets) * mask)


def _operands(lead, tied=False, heads=1, bias=False, mask="some", seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    h = jax.random.normal(ks[0], (*lead, D))
    kernel = 0.3 * jax.random.normal(ks[1], (V, D) if tied else (D, V * heads))
    b = jax.random.normal(ks[2], (V * heads,)) if bias else None
    shape = lead + ((heads,) if heads > 1 else ())
    targets = jax.random.randint(ks[3], shape, 0, V)
    m = {"some": (jax.random.uniform(ks[4], shape) > 0.3).astype(jnp.float32),
         "ones": jnp.ones(shape, jnp.float32), "zeros": jnp.zeros(shape, jnp.float32)}[mask]
    return h, kernel, b, targets, m


def _both(lead, tied=False, heads=1, bias=False, mask="some"):
    h, kernel, b, targets, m = _operands(lead, tied, heads, bias, mask)
    wrt = (0, 1, 2) if bias else (0, 1)
    # a cotangent other than 1: the backward scales what the forward made
    got = jax.value_and_grad(lambda h, k, b: 0.37 * head_loss(
        HeadOperands(h, k, b, tied=tied, heads=heads), targets, m), argnums=wrt)(h, kernel, b)
    want = jax.value_and_grad(lambda h, k, b: 0.37 * _plain(
        h, k, b, targets, m, tied, heads), argnums=wrt)(h, kernel, b)
    return got, want


CASES = {
    "bias": dict(lead=(4, 64), bias=True),
    "no_bias": dict(lead=(4, 64)),
    "tied": dict(lead=(2, 64), tied=True),
    "three_heads": dict(lead=(2, 64), heads=3),
    "three_heads_bias": dict(lead=(2, 64), heads=3, bias=True),
    "no_row_masked": dict(lead=(2, 64), mask="ones"),
    "rows_the_chunk_does_not_divide": dict(lead=(4, 50), bias=True),  # 200 rows: 7 x 32, padded
    "rows_under_a_tile_of_the_chunks": dict(lead=(1, 33)),  # 2 chunks of 24, 15 rows padded
    "flat_rows": dict(lead=(128,), tied=True),
}


@pytest.mark.parametrize("case", CASES)
def test_chunked_equals_the_plain_expression(small_chunks, case):
    """Loss and every gradient (h, kernel or tied embedding, bias) within
    float32 rounding of ``Dense`` + ``softmax_cross_entropy``."""
    kw = CASES[case]
    rows = int(np.prod(kw["lead"]))
    assert chunking(rows, V * kw.get("heads", 1))[0] > 1
    (got, grads), (want, ref) = _both(**kw)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert len(grads) == len(ref)
    for g, r in zip(grads, ref):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_allclose(g, r, atol=2e-6)


@pytest.mark.parametrize("tied", [False, True])
def test_an_all_zero_mask_gives_zero_gradients_and_no_nan(small_chunks, tied):
    (got, grads), _ = _both((2, 64), tied=tied, bias=not tied, mask="zeros")
    assert float(got) == 0.0
    for g in grads:
        assert not np.any(np.asarray(g))


def test_a_bf16_stream_gets_a_bf16_gradient_and_a_float32_head(small_chunks):
    """``TransformerLM``'s case: the norm's output is bf16, the head float32."""
    h, kernel, b, targets, m = _operands((2, 64), bias=True)
    h = h.astype(jnp.bfloat16)
    got = jax.grad(lambda h, k, b: head_loss(
        HeadOperands(h, k, b, dtype=jnp.float32), targets, m), argnums=(0, 1, 2))(h, kernel, b)
    want = jax.grad(lambda h, k, b: _plain(
        h.astype(jnp.float32), k, b, targets, m), argnums=(0, 1, 2))(h, kernel, b)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    np.testing.assert_allclose(got[0].astype(jnp.float32), want[0].astype(jnp.float32),
                               atol=2e-2, rtol=1e-2)
    np.testing.assert_allclose(got[1], want[1], atol=2e-6)
    np.testing.assert_allclose(got[2], want[2], atol=2e-6)


def test_under_vmap(small_chunks):
    """A vmapped cohort: ``custom_vjp`` of ``jnp`` ops batches as it is."""
    clients = [_operands((2, 32), bias=True, seed=s) for s in range(3)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *clients)

    def loss(h, k, b, y, m):
        return head_loss(HeadOperands(h, k, b), y, m)

    got = jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2)))(*stacked)
    for i, (h, k, b, y, m) in enumerate(clients):
        want = jax.value_and_grad(_plain, argnums=(0, 1, 2))(h, k, b, y, m)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g[i], r, atol=2e-6, rtol=1e-6)


@pytest.mark.parametrize("tied", [False, True])
def test_under_a_v_sharded_mesh(small_chunks, tied):
    """``parallel/rules.py`` shards ``head/kernel`` over the model axis: the
    log-sum-exp then sums across shards, and XLA's SPMD partitions the
    operator's plain ops as it partitions the plain head."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), (MODEL_AXIS,))
    h, kernel, _, targets, m = _operands((2, 64), tied=tied)
    spec = P(MODEL_AXIS, None) if tied else P(None, MODEL_AXIS)
    shard = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))  # noqa: E731

    def step(h, kernel):
        return jax.value_and_grad(lambda h, k: head_loss(
            HeadOperands(h, k, None, tied=tied), targets, m), argnums=(0, 1))(h, kernel)

    got = jax.jit(step, out_shardings=(None, (None, NamedSharding(mesh, spec))))(
        shard(h, P()), shard(kernel, spec))
    assert got[1][1].sharding.spec == spec
    want = jax.value_and_grad(_plain, argnums=(0, 1))(h, kernel, None, targets, m, tied)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, r, atol=2e-6, rtol=1e-6)


# -- through the trainer and the three decoder files ---------------------------

T = 48  # 96 rows: no weight of the toy decoders has as many
DECODERS = {
    "transformer_bias": lambda: TransformerLM(
        vocab_size=V, embed_dim=16, num_layers=1, num_heads=2, max_len=T),
    "moe": lambda: MoETransformerLM(vocab_size=V, embed_dim=32, layer_kinds=("global",)),
    "mla_mtp": lambda: MLAMoETransformerLM(vocab_size=V, routed_layers=1),
    "mla_tied": lambda: MLAMoETransformerLM(
        vocab_size=V, routed_layers=1, mtp_depth=0, tie_head=True),
    "mla_three_heads": lambda: MLAMoETransformerLM(
        vocab_size=V, routed_layers=1, mtp_depth=0, num_pred_heads=3),
}


def _trainer_and_batch(name):
    module = DECODERS[name]()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, V, (2, T)), jnp.int32)
    shape = (2, T, 3) if name == "mla_three_heads" else (2, T)
    batch = {"x": x, "y": jnp.asarray(rng.randint(0, V, shape), jnp.int32),
             "mask": jnp.asarray(rng.uniform(size=shape) > 0.2, jnp.float32)}
    trainer = ClientTrainer(module=module, task="nwp", optimizer=optax.sgd(0.01))
    params = trainer.init(jax.random.key(1), batch)["params"]
    return trainer, params, batch


def _loss_and_grads(trainer, params, batch):
    return jax.value_and_grad(lambda p: trainer.loss_fn(
        p, {}, p, batch, jax.random.key(0))[0])(params)


def _logits_path(monkeypatch):
    """The parent's program: the trainer asks for no operands, so the
    decoders hand it logits."""
    monkeypatch.setattr(trainerlib, "HEAD_COLLECTION", "nobody_asks")


@pytest.mark.parametrize("name", DECODERS)
def test_a_chunked_training_step_equals_the_logits_path(monkeypatch, name):
    """Loss and every parameter's gradient, through ``ClientTrainer.loss_fn``:
    the MTP term through ``one_token_further`` (``mla_mtp``), the tied
    embedding's two gradients summed, the P-head form."""
    trainer, params, batch = _trainer_and_batch(name)
    with monkeypatch.context() as m:
        _chunks_of(m, 16)
        trace._program_notes.pop(hl.NOTE, None)
        got, grads = _loss_and_grads(trainer, params, batch)
        notes = trace.program_notes(hl.NOTE)
    assert {n["form"] for n in notes} == {"chunked"}
    assert len(notes) == 1 and notes[0]["chunks"] == 6  # the MTP pass has the main one's shape
    _logits_path(monkeypatch)
    want, ref = _loss_and_grads(trainer, params, batch)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    flat, ref_flat = (dict(jax.tree_util.tree_flatten_with_path(g)[0]) for g in (grads, ref))
    assert flat.keys() == ref_flat.keys()
    for path, g in flat.items():
        np.testing.assert_allclose(g, ref_flat[path], atol=3e-6, err_msg=str(path))


def test_the_mtp_term_is_the_operator_one_token_further(small_chunks):
    """What the trainer adds for the MTP module: ``weight * lm_loss`` of its
    operands against the targets one position on, the row's last out."""
    h, kernel, _, targets, m = _operands((2, 64))
    batch = one_token_further({"y": targets, "mask": jnp.ones_like(m)})
    got = lm_loss(HeadOperands(h, kernel), batch)
    assert float(batch["mask"][:, -1].sum()) == 0.0
    want = _plain(h[:, :-1], kernel, None, targets[:, 1:], jnp.ones((2, 63))) / (2 * 63)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("name", DECODERS)
def test_one_chunk_is_the_parents_program(monkeypatch, name):
    """Where one chunk covers the rows a decoder hands the trainer its logits
    as ever: the training step's jaxpr is the one a trainer that asks for no
    operands traces to, and the ``head_loss/call`` note says ``whole``."""
    trainer, params, batch = _trainer_and_batch(name)

    def jaxpr():  # of a function traced for the first time: jax keeps a trace by the function
        return str(jax.make_jaxpr(lambda p: _loss_and_grads(trainer, p, batch))(params))

    trace._program_notes.pop(hl.NOTE, None)
    asked = jaxpr()
    heads = 3 if name == "mla_three_heads" else 1
    assert trace.program_notes(hl.NOTE) == [dict(
        rows=2 * T, columns=heads * V, chunks=1, chunk_rows=2 * T,
        logits_bytes=2 * T * heads * V * 4, form="whole")]
    _logits_path(monkeypatch)
    trace._program_notes.pop(hl.NOTE, None)
    assert jaxpr() == asked
    assert not trace.program_notes(hl.NOTE)  # nobody asked


@pytest.mark.parametrize("head", ["bias", "no_bias", "tied", "three_heads"])
def test_a_one_chunk_call_traces_to_the_plain_heads_jaxpr(head):
    """Called with logits one chunk covers, the operator is the plain
    expression: the jaxpr of ``flax.linen.Dense`` (``Embed.attend`` where
    tied), the decoders' upcast and optax's cross-entropy, no ``custom_vjp``."""
    import flax.linen as nn

    tied, heads = head == "tied", 3 if head == "three_heads" else 1
    h, kernel, b, targets, m = _operands((2, 64), tied, heads, head == "bias")

    def plain(h, kernel, b):
        if tied:
            z = nn.Embed(V, D).apply({"params": {"embedding": kernel}}, h, method="attend")
        else:
            z = nn.Dense(V * heads, use_bias=b is not None).apply(
                {"params": {"kernel": kernel, **({} if b is None else {"bias": b})}}, h)
        z = z.astype(jnp.float32)
        if heads > 1:
            z = z.reshape(*z.shape[:-1], heads, V)
        return jnp.sum(optax.softmax_cross_entropy_with_integer_labels(z, targets) * m)

    def operator(h, kernel, b):
        return head_loss(HeadOperands(h, kernel, b, tied=tied, heads=heads), targets, m)

    wrt = (0, 1, 2) if b is not None else (0, 1)
    got, want = (str(jax.make_jaxpr(jax.value_and_grad(f, argnums=wrt))(h, kernel, b))
                 for f in (operator, plain))
    assert got == want and "custom_vjp" not in got


def _shapes(jaxpr):
    """Every array shape a jaxpr holds, its loops' and calls' bodies included."""
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v.aval, "shape"):
                yield tuple(v.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("name", ["transformer_bias", "mla_mtp", "mla_tied"])
def test_a_chunked_training_step_holds_no_rows_by_v_array(monkeypatch, name):
    trainer, params, batch = _trainer_and_batch(name)
    rows = 2 * T

    def jaxpr():  # of a function traced for the first time: jax keeps a trace by the function
        return jax.make_jaxpr(lambda p: _loss_and_grads(trainer, p, batch))(params).jaxpr

    def widest(jaxpr):  # the most elements of an array with V columns
        return max(int(np.prod(s)) for s in _shapes(jaxpr) if s and s[-1] == V)

    assert widest(jaxpr()) == rows * V  # one chunk: the logits
    _chunks_of(monkeypatch, 16)
    chunked = jaxpr()
    # a chunk's logits, or the kernel and its gradient: nothing of rows x V
    assert 16 * V <= widest(chunked) < rows * V
    assert not [s for s in _shapes(chunked) if int(np.prod(s)) >= rows * V and V in s]


def test_eval_keeps_the_logits_path():
    """``eval_batch`` needs the argmax; and a caller that lists no
    ``HEAD_COLLECTION`` gets logits while training too."""
    trainer, params, batch = _trainer_and_batch("moe")
    trace._program_notes.pop(hl.NOTE, None)
    metrics = trainer.eval_batch({"params": params}, batch)
    assert float(metrics["test_total"]) == float(batch["mask"].sum())
    out, _ = trainer.module.apply({"params": params}, batch["x"], train=True, mutable=["stats"])
    assert out.shape == (2, T, V) and not trace.program_notes(hl.NOTE)


CELLS = {  # rows, columns -> chunks, rows a chunk: the benchmark's seven heads
    "smallthinker21b_silo2": ((8192, 37984), (2, 4096)),
    "joyai_flash_silo2": ((8192, 16160), (2, 4096)),
    "lfm2moe_silo2": ((16384, 16384), (4, 4096)),
    "kimilinear_silo2": ((8192, 20480), (2, 4096)),
    "cgpt13b_silo2": ((8192, 50257), (2, 4096)),
    "evabyte_silo2": ((8192, 8 * 320), (1, 8192)),
    "a_short_batch_of_a_wide_head": ((4096, 131072), (1, 4096)),
    "rows_no_tile_divides": ((5000, 50000), (2, 2560)),
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_chunk_rule_is_of_the_shape_alone_and_the_note_carries_it(cell):
    (rows, columns), (chunks, chunk_rows) = CELLS[cell]
    assert chunking(rows, columns) == (chunks, chunk_rows)
    assert chunks * chunk_rows >= rows and chunk_rows % hl.TILE_ROWS in (0, rows % hl.TILE_ROWS)
    trace._program_notes.pop(hl.NOTE, None)
    ops = HeadOperands(jax.ShapeDtypeStruct((rows, 8), jnp.float32),
                       jax.ShapeDtypeStruct((8, columns), jnp.float32))
    targets = jax.ShapeDtypeStruct((rows,), jnp.int32)
    jax.eval_shape(head_loss, ops, targets, jax.ShapeDtypeStruct((rows,), jnp.float32))
    assert trace.program_notes(hl.NOTE) == [dict(
        rows=rows, columns=columns, chunks=chunks, chunk_rows=chunk_rows,
        logits_bytes=rows * columns * 4, form="whole" if chunks == 1 else "chunked")]
