"""The flash kernels compiled for a described TPU v5e (no chip): Mosaic and
the TPU compiler refuse here what the interpreter lets through (VMEM a kernel
may not have, a slice off the tiling). The topology is described inside a
fixture, so only the worker that runs this file loads the TPU's library; keep
every such compile in this one file (the ``on-chip-measurement`` guide, 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import fedml_tpu.ops.attention as att


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache and cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (q shape, KV heads, t_k, d_v, dtype, causal, window): the five LM cells'
# calls, chip_smoke.py's check, and the kinds of shape the CPU tests run
# through the kernels' own tiles: a block that is the whole axis and no
# multiple of 128 (T 64, 96), one that is a part of it and no multiple of 128
# (T 640 in blocks of 320), t_q != t_k both ways, f32, no mask; with head counts
# no other test uses, since the ``attn/call`` notes outlive a test
SHAPES = [
    pytest.param((1, 32, 8192, 192), 32, 8192, 128, jnp.bfloat16, True, None, id="joyai-kimi"),
    pytest.param((1, 28, 8192, 128), 4, 8192, 128, jnp.bfloat16, True, None, id="st-global"),
    pytest.param((1, 28, 8192, 128), 4, 8192, 128, jnp.bfloat16, True, 4096, id="st-window"),
    pytest.param((2, 32, 8192, 64), 8, 8192, 64, jnp.bfloat16, True, None, id="lfm2"),
    pytest.param((4, 16, 2048, 128), 16, 2048, 128, jnp.bfloat16, True, None, id="cgpt"),
    pytest.param((4, 16, 1024, 128), 16, 1024, 128, jnp.bfloat16, True, None, id="chip_smoke"),
    pytest.param((1, 2, 640, 128), 2, 640, 128, jnp.bfloat16, True, None, id="blocks-of-320"),
    pytest.param((1, 3, 96, 40), 3, 96, 8, jnp.float32, True, None, id="T96-40-on-8"),
    pytest.param((1, 6, 64, 24), 3, 64, 16, jnp.float32, True, 24, id="T64-grouped-window"),
    pytest.param((1, 3, 64, 24), 3, 64, 16, jnp.bfloat16, True, None, id="T64-bf16"),
    pytest.param((1, 3, 128, 192), 3, 128, 128, jnp.float32, True, None, id="T128-192-on-128"),
    pytest.param((1, 2, 256, 128), 2, 512, 128, jnp.bfloat16, True, None, id="t_q-under-t_k"),
    pytest.param((1, 2, 512, 128), 2, 256, 128, jnp.bfloat16, True, None, id="t_q-over-t_k"),
    pytest.param((1, 2, 1024, 128), 2, 1024, 128, jnp.float32, False, None, id="full-f32"),
]


@pytest.mark.parametrize("q_shape,kv_heads,t_k,d_v,dtype,causal,window", SHAPES)
def test_flash_forward_and_backward_compile_for_the_v5e(
        monkeypatch, one_chip, q_shape, kv_heads, t_k, d_v, dtype, causal, window):
    """The gradient of ``flash_attention`` (the forward kernel and the one
    backward kernel, with the VMEM ``_mosaic_params`` asks for them) compiles
    for the chip at the tiles the kernels pick."""
    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    b, _, _, d = q_shape

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, causal, window=window).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip(*q_shape), on_chip(b, kv_heads, t_k, d), on_chip(b, kv_heads, t_k, d_v)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd_dkv" in text and "flash_bwd_dq" not in text


@pytest.mark.parametrize("q_shape,kv_heads,tile", [
    pytest.param((1, 32, 8192, 128), 4, 512, id="keye"),
    pytest.param((2, 4, 256, 128), 2, 128, id="two-planes-two-batches")])
def test_the_masked_kernels_compile_for_the_v5e(monkeypatch, one_chip, q_shape, kv_heads, tile):
    """``flash_attention_selected`` at the sparse-attention cell's shape (the
    set's packed words read by a dynamic lane slice and a shift, the tiles'
    counts from SMEM, no VMEM asked for beside the causal kernels' own) and at
    a length whose bits fill two planes."""
    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    b, _, t, d = q_shape
    lanes, planes = att.selection_layout(t)

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, rows, cols, tiles):
        out, lse = att.flash_attention_selected(q, k, v, att.Selection(rows, cols, tiles))
        return out.astype(jnp.float32).sum() + lse.sum()

    words = on_chip((b, t, t // planes), jnp.int32)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        on_chip(q_shape), on_chip((b, kv_heads, t, d)), on_chip((b, kv_heads, t, d)), words,
        words, on_chip((b, t // tile, t // tile), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd" in text and "flash_bwd_dkv" in text and "vmem_limit" not in text



@pytest.mark.parametrize("rows,keys,t", [
    pytest.param(512, 8192, 8192, id="keye-last-group"),
    pytest.param(512, 2048, 8192, id="keye-first-group"),
    pytest.param(512, 16384, 16384, id="64-row-steps"),
    pytest.param(128, 256, 256, id="two-planes")])
def test_the_selection_kernel_compiles_for_the_v5e(monkeypatch, one_chip, rows, keys, t):
    """``dsa_select.select_rows`` on a block of the sparse-attention cell's
    index scores (128 rows a grid step against the keys of the last and the
    first causal group: the float32 block twice and its int32 keys, 12 MiB at
    8,192 keys), at twice the keys, where a step takes 64 rows, and at a toy
    length: Mosaic takes the dynamic lane slices, the
    int32 lane sums and the nested loops, inside its default scoped VMEM."""
    from fedml_tpu.ops import dsa_select

    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    text = jax.jit(lambda s, lo: dsa_select.select_rows(s, lo[0], min(2048, t // 4), t)).lower(
        jax.ShapeDtypeStruct((1, rows, keys), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "dsa_select" in text and "vmem_limit" not in text


@pytest.mark.parametrize("b,t,heads,kv_heads,d,index_heads,index_dim,dtype", [
    pytest.param(1, 8192, 32, 4, 128, 16, 64, jnp.bfloat16, id="keye"),
    pytest.param(2, 2048, 32, 4, 128, 16, 64, jnp.bfloat16, id="batch-of-two-T2048"),
    pytest.param(1, 256, 4, 2, 128, 3, 64, jnp.float32, id="T256-f32")])
def test_the_index_loss_kernels_compile_for_the_v5e(monkeypatch, one_chip, b, t, heads, kv_heads,
                                                    d, index_heads, index_dim, dtype):
    """``dsa_index_loss.index_loss_grads`` at the sparse-attention cell's shape
    (tiles of 512 keys x 128 queries; ``d_kI^T`` whole, a query block's ``q``
    and sixteen float32 tiles of ``relu(z)`` resident: 11.4 MiB), at a batch of
    two and at a toy length in float32: Mosaic takes the walk from SMEM, the
    64-wide contractions and the resident output inside its default scoped
    VMEM, and the custom calls bear the names the trace is read by."""
    from fedml_tpu.ops import dsa_index_loss

    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    on = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    text = jax.jit(lambda *a: dsa_index_loss.index_loss_grads(*a, d ** -0.5)).lower(
        on((b, index_heads, t, index_dim)), on((b, t, index_dim)), on((b, t, index_heads)),
        on((b, heads, t, d)), on((b, kv_heads, t, d)), on((b, heads, t), jnp.float32),
        on((b, t, t // 32), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "dsa_index_loss" in text and "dsa_index_loss_lse" in text
    assert "vmem_limit" not in text and "flash_fwd" not in text and "flash_bwd" not in text


# the delta-attention mixer's three chains (ops/kda.py): the cell's shape
# ([1, 8192, 32 x 128] bfloat16: whole blocks of 2,048 tokens), a length that is
# a part of one block, one that is no multiple of a block, float32, one head
CHAINS = [
    pytest.param(op, *shape, id=f"{op}-{name}")
    for op in ("conv_act_norm", "conv_act_plain", "decay", "gated_norm")
    for name, shape in (("kimi", (1, 8192, 32, 128, jnp.bfloat16)),
                        ("part-of-a-block", (2, 200, 2, 128, jnp.bfloat16)),
                        ("blocks-and-a-part", (1, 4500, 1, 256, jnp.float32)))]


@pytest.mark.parametrize("op,b,t,n,d,dtype", CHAINS)
def test_the_mixers_chains_compile_for_the_v5e(monkeypatch, one_chip, op, b, t, n, d, dtype):
    """Value and gradients of each operator: its forward and its backward
    kernel compile for the chip (the in-kernel ``short_conv`` pads and slices
    off the sublane tiling, which only Mosaic can refuse), and at whole blocks
    nothing of XLA's moves an array beside them."""
    import fedml_tpu.ops.kda as kda

    monkeypatch.setattr(kda, "_interpret_on", lambda platform: False)

    def on_chip(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    flat, heads = on_chip((b, t, n * d), dtype), on_chip((b, n, t, d), dtype)
    if op == "decay":
        fn, args = kda.decay, (flat, on_chip((n * d,)), on_chip((n,)))
    elif op == "gated_norm":
        fn, args = (lambda *a: kda.gated_norm(*a, eps=1e-5)), (heads, flat, on_chip((d,)))
    else:
        norm = op == "conv_act_norm"
        fn = lambda y, w: kda.conv_act(y, w, heads=n, norm=norm, scale=d ** -0.5)  # noqa: E731
        args = (flat, on_chip((4, n * d)))
    text = jax.jit(jax.value_and_grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                                      argnums=range(len(args)))).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert sum(kernel in text for kernel in kda.CHAIN_KERNELS) == 2
    if t % kda.BLOCK == 0:
        assert " copy(" not in text and " transpose(" not in text and " pad(" not in text


def test_lower_hash_is_steady_and_sees_a_renamed_scope(monkeypatch, one_chip):
    """``tools/lower_hash.py``, the check that a host-side change moved no
    device program: a ``FedSim`` built on the described chip twice gives one
    hash of its round program, kernel payloads and file lines left out, and a
    scope under another name gives another."""
    import numpy as np
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.obs import trace
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig
    from tools import lower_hash

    x = np.random.RandomState(0).randint(0, 31, (8, 128)).astype(np.int32)
    train = FederatedArrays(
        {"x": x, "y": np.roll(x, -1, 1), "mask": np.ones(x.shape, np.float32)},
        {c: np.arange(4 * c, 4 * c + 4) for c in range(2)})
    trainer = ClientTrainer(
        module=TransformerLM(vocab_size=31, embed_dim=256, num_layers=1, num_heads=2,
                             max_len=128, attn_impl="flash"),
        task="nwp", epochs=1, optimizer=optax.sgd(0.01, momentum=0.9))
    cfg = SimConfig(client_num_in_total=2, client_num_per_round=2, batch_size=2, comm_round=1,
                    frequency_of_the_test=1000, seed=0, cohort_execution="scan")
    monkeypatch.setattr(FedSim, "_put", lower_hash.put_shapes)
    lower_hash.as_on_the_chip(monkeypatch.setattr)
    mesh = lower_hash.described_chip_mesh(next(iter(one_chip.device_set)))

    def texts():
        sim = FedSim(trainer, train, None, cfg, mesh=mesh)
        assert sim._block_dispatch  # as on the chip: the mesh's platform says so
        lowered = lower_hash.lower_program(sim, "round")
        return lower_hash.strip(lowered.as_text(debug_info=True)), lowered.as_text()

    stripped, whole = texts()
    assert whole.count("tpu_custom_call") == 2 and "engine.py" not in stripped
    assert f'"{trace.SCOPE_OPT}/' in stripped or f"/{trace.SCOPE_OPT}/" in stripped
    assert lower_hash.sha256(texts()[0]) == lower_hash.sha256(stripped)
    monkeypatch.setattr(trace, "SCOPE_OPT", "fed/renamed")
    renamed = texts()[0]
    assert "fed/renamed" in renamed
    assert lower_hash.sha256(renamed) != lower_hash.sha256(stripped)
