"""Start-up decisions that pick which program the chip runs (PR 21). Nothing
here compiles a model: tier-1 has no room (ROADMAP Design 6)."""

import os
import tempfile

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.core import compile_cache
from fedml_tpu.ops.attention import _interpret_on, _pick_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_left_alone_when_env_var_set(monkeypatch):
    updates = []
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append(name))
    compile_cache.configure_compile_cache()
    assert "jax_compilation_cache_dir" not in updates
    assert compile_cache.cache_dir_to_set(
        {compile_cache.ENV_VAR: "/somewhere/else"}, None) is None


def test_cache_dir_default_is_one_fixed_path_under_the_checkout():
    path = compile_cache.cache_dir_to_set({}, None)
    # equal to a path computed from this file's location alone: no pid, no
    # time, no temp dir can be in it, and no respelling (jax keys on the string)
    assert path == os.path.join(REPO, ".jax_cache")
    assert os.path.isabs(path) and path == os.path.normpath(path)
    assert not path.startswith(tempfile.gettempdir() + os.sep)
    assert compile_cache.cache_dir_to_set({}, None) == path
    # a directory some caller already configured (this suite's conftest) wins
    assert compile_cache.cache_dir_to_set({}, "/already/configured") is None


def test_cache_floor_lowered_only_from_jax_default():
    assert compile_cache.floor_to_set(1.0) == 0.0
    assert compile_cache.floor_to_set(0.5) is None  # the suite's own choice


def test_flash_interpret_decision_is_a_function_of_the_platform():
    assert _interpret_on("cpu") is True
    assert _interpret_on("tpu") is False
    for other in ("gpu", "cuda", "METAL"):
        with pytest.raises(RuntimeError, match=other):
            _interpret_on(other)


def test_pick_block_refuses_what_mosaic_refuses():
    with pytest.raises(ValueError, match=r"T=1000.*block 250.*8-row"):
        _pick_block(1000, 256, jnp.float32)
    assert _pick_block(1024, 256, jnp.bfloat16) == 256
    # a block equal to the axis is always a legal tile (the ring-attention
    # dry run's T=64 under the default 128 tiles)
    assert _pick_block(64, 128, jnp.float32) == 64
    assert _pick_block(64, 128, jnp.bfloat16) == 64
    # bf16 packs 16 rows to a sublane tile
    assert _pick_block(32, 8, jnp.float32) == 8
    with pytest.raises(ValueError, match="16-row"):
        _pick_block(32, 8, jnp.bfloat16)


def test_flash_wrap_lowers_for_tpu_under_a_sharded_plan(monkeypatch):
    """Mosaic kernels cannot be partitioned automatically, nor under a
    shard_map manual over only some mesh axes: jax raises at lowering, which
    the interpreted kernel never reaches. So lower FOR the TPU from here
    (no compile) and meet the refusal the chip gave in PR 21."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import fedml_tpu.ops.attention as att

    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("clients", "model"))
    rep = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((2, 2, 128, 128), jnp.bfloat16)

    def lowered_for_tpu(fn):
        with mesh:
            return jax.jit(fn, in_shardings=(rep,) * 3, out_shardings=rep) \
                .trace(q, q, q).lower(lowering_platforms=("tpu",)) \
                .as_text(debug_info=True)

    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lowered_for_tpu(lambda q, k, v: att.flash_attention(q, k, v, True))
    for axis in (None, "model"):  # gather-for-compute plan, tensor-parallel plan
        def attend(q, k, v, axis=axis):
            return att.flash_attention_head_parallel(q, k, v, axis=axis, causal=True)

        text = lowered_for_tpu(attend)
        assert "tpu_custom_call" in text
        # the custom VJP sits inside the all-axes shard_map, so the one
        # backward kernel lowers per device too, on local heads
        text = lowered_for_tpu(jax.grad(
            lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        assert text.count("tpu_custom_call") == 2
        for kernel in ("flash_fwd", "flash_bwd_dkv"):
            assert kernel in text, (axis, kernel)
        assert "flash_bwd_dq" not in text


def _lowered_for_tpu(fn, *shapes):
    """The TPU lowering of ``fn`` (no compile, no chip): Mosaic refuses at
    lowering what the interpreter lets through."""
    return jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


# the LM cells' attention calls, bf16 causal: (q shape, KV heads, d_v, window) and the
# MiB of VMEM the forward's K and V (double-buffered) and the backward's q, dO, dQ (one
# buffer each) and f32 dQ accumulator hold, lanes padded to 128; a call that holds more
# than 10 MiB asks Mosaic for that plus 8 (``_mosaic_params``), and only the 192-wide do
CELL_ATTENTION = [
    pytest.param((4, 16, 2048, 128), 16, 128, None, 2, 2.5, id="cgpt13b_silo2"),
    pytest.param((1, 28, 8192, 128), 4, 128, None, 8, 10, id="smallthinker21b_silo2-global"),
    pytest.param((1, 28, 8192, 128), 4, 128, 4096, 8, 10, id="smallthinker21b_silo2-window"),
    pytest.param((1, 32, 8192, 192), 32, 128, None, 12, 18, id="joyai_flash_silo2-192-on-128"),
    pytest.param((1, 32, 8192, 192), 32, 128, None, 12, 18, id="kimilinear_silo2-192-on-128"),
    pytest.param((2, 32, 8192, 64), 8, 64, None, 8, 10, id="lfm2moe_silo2-64-wide"),
]


@pytest.mark.parametrize("q_shape,kv_heads,d_v,window,fwd_mib,bwd_mib", CELL_ATTENTION)
def test_mosaic_params_count_what_the_kernels_hold(q_shape, kv_heads, d_v, window, fwd_mib,
                                                   bwd_mib):
    import fedml_tpu.ops.attention as att

    _, _, t, d = q_shape
    mib = 2 ** 20
    pad = lambda w: -(-w // 128) * 128  # noqa: E731
    assert 2 * 2 * t * (pad(d) + pad(d_v)) == fwd_mib * mib
    assert 2 * t * (2 * pad(d) + pad(d_v)) + 4 * t * pad(d) == bwd_mib * mib
    for held, params in (
            (fwd_mib, att._mosaic_params(jnp.bfloat16, (t, d), (t, d_v))),
            (bwd_mib, att._mosaic_params(jnp.bfloat16, (t, d), (t, d_v), (t, d),
                                         scratch=[(t, d)], buffers=1))):
        if held <= 10:
            assert params is None  # Mosaic's default 16 MB: no request, which is not free
        else:
            assert params.vmem_limit_bytes == (held + 8) * mib < 128 * mib  # the v5e's VMEM


def test_mosaic_params_count_f32_sequences_at_twice_the_bytes():
    """The backward at T 4096 of 128 columns holds 8 MiB in f32, still no
    request; at T 8192 it holds 16 and asks for 24."""
    import fedml_tpu.ops.attention as att

    def backward(t):
        return att._mosaic_params(jnp.float32, (t, 128), (t, 128), (t, 128), scratch=[(t, 128)],
                                  buffers=1)

    assert backward(4096) is None
    assert backward(8192).vmem_limit_bytes == (16 + 8) * 2 ** 20


@pytest.mark.parametrize("q_shape,kv_heads,d_v,window,fwd_mib,bwd_mib", CELL_ATTENTION)
def test_flash_kernels_lower_for_tpu_at_the_cells_shapes(monkeypatch, q_shape, kv_heads, d_v,
                                                         window, fwd_mib, bwd_mib):
    """The two kernels at the LM cells' shapes in bf16, with the tiles the
    kernels choose for them (``_fwd_blocks``, ``_bwd_blocks``): equal heads at
    T 2048; 28 query heads on 4 KV heads at T 8192, global and window; 32
    heads of 192 score columns on 128 value columns at T 8192; 32 heads of 64
    on 8 at batch 2. Two custom calls an attention, forward and backward; the
    one that holds more than 10 MiB writes its VMEM limit."""
    import fedml_tpu.ops.attention as att

    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    b, _, t, d = q_shape
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, kv_heads, t, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, kv_heads, t, d_v), jnp.bfloat16)
    assert att._fwd_blocks(t, t, jnp.bfloat16) == (512, 512)
    assert att._bwd_blocks(t, t, jnp.bfloat16, (512, 512)) == (512, 512)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, True, window=window).astype(jnp.float32).sum()

    text = _lowered_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert text.count("tpu_custom_call") == 2
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert kernel in text, kernel
    assert "flash_bwd_dq" not in text
    # where a VMEM limit is written: once a kernel that asks
    assert text.count("scoped_memory_configs") == (fwd_mib > 10) + (bwd_mib > 10)


def test_expert_layer_lowers_for_tpu_at_the_cells_shapes(monkeypatch):
    """8192 tokens x 6 of 64 experts, 16 held, 2560 -> 768 -> 2560 in bf16:
    megablox's grouped products, forward (gmm) and backward (gmm, tgmm)."""
    import fedml_tpu.ops.moe as moe

    monkeypatch.setattr(moe, "_interpret_on", lambda platform: False)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731

    def loss(u, router, gate, up, down):
        ids, weights = moe.route(u, router, 6)
        out, _ = moe.expert_layer(u.astype(jnp.bfloat16), ids, weights, gate, up, down,
                                  first=0, count=16, dtype=jnp.bfloat16)
        return out.sum()

    text = _lowered_for_tpu(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), f32(8192, 2560), f32(2560, 64),
        f32(16, 2560, 768), f32(16, 2560, 768), f32(16, 768, 2560))
    # gmm at the two forward shapes and the two transposed ones, tgmm at its
    # two: products of one shape share one lowered function
    assert text.count("tpu_custom_call") >= 6
    assert moe.gmm_tiling(49152, 2560, 768) == (512, 1280, 768)
    assert moe.gmm_tiling(49152, 768, 2560) == (512, 768, 1280)
    assert moe.gmm_tiling(64, 64, 32) == (64, 64, 32)
