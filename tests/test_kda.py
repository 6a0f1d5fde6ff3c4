"""The delta rule's chunked scan (``fedml_tpu/ops/kda.py``: two Mosaic kernels,
interpreted here) against the recurrence run token by token: output and the
five operands' gradients at three chunk sizes, at a length that is no
multiple of the chunk, under decays whose cumulated logs pass -200 (where
``exp(-gamma)`` alone overflows), on sixty-four equal keys (where powers of the
triangular system overflow), and in bfloat16; the short convolution against a
direct sum; the kernels and loops a block adds, and the scope they sit under;
and the scan's lowering for the TPU at the cell's shape. On the CPU; nothing
here describes a TPU topology, so the file is safe under xdist.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.mla_moe_transformer import KDA, MLABlock
from fedml_tpu.obs import trace
from fedml_tpu.ops import kda, remat
from tests.test_remat_policy import _equations

KERNELS = (trace.KDA_FWD_KERNEL_NAME, trace.KDA_BWD_KERNEL_NAME)

B, H, D_K, D_V = 2, 3, 32, 24
OPERANDS = ("q", "k", "v", "g", "beta")
# name: (tokens, scale of the log-decays, floor the chunk-cumulated log-decay must pass)
CASES = {"mild": (72, 0.02, -1.0), "strong_decay": (200, 0.5, -200.0)}


def operands(t, decay, seed=1, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, H, t, D_K))) * D_K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, H, t, D_K)))
    v = jax.random.normal(ks[2], (B, H, t, D_V))
    # log-normal rates: some channels keep nearly everything, some nearly nothing
    g = -decay * jnp.exp(2.0 * jax.random.normal(ks[3], (B, H, t, D_K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, t)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def close(got, want, tol):
    """Within ``tol`` of the largest entry of ``want``, and finite."""
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(jnp.max(jnp.abs(want))), (
        float(jnp.max(jnp.abs(got - want))), float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_chunked_form_equals_the_recurrence(chunk, case):
    """Output and all five gradients; T is no multiple of any chunk. The
    tolerance is float32's over cumulated logs: a log-decay summed to -1,700
    holds 1e-4 of absolute error, which its ``exp`` turns into relative."""
    t, decay, floor = CASES[case]
    assert t % chunk
    args = operands(t, decay)
    assert float(kda.decay_floor(args[3], chunk)) < floor
    tol = 2e-5 if case == "mild" else 3e-4
    close(kda.kda(*args, chunk=chunk), kda.kda_reference(*args), tol)
    weight = jax.random.normal(jax.random.key(9), (B, H, t, D_V))
    grads = jax.grad(lambda *a: jnp.sum(kda.kda(*a, chunk=chunk) * weight), argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(kda.kda_reference(*a) * weight), argnums=range(5))(*args)
    for name, got, want in zip(OPERANDS, grads, wants):
        close(got, want, tol), name


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_equal_keys_and_beta_near_one(chunk):
    """Every key the same direction, beta 0.999, hardly any decay: the
    triangular system is all ones below its diagonal, whose powers grow like
    binomials (its inverse does not). The block substitution stays exact."""
    t = 256
    ks = jax.random.split(jax.random.key(3), 3)
    k = jax.random.normal(ks[0], (1, 2, 1, D_K)) + 0.01 * jax.random.normal(ks[1], (1, 2, t, D_K))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, 2, t, D_V))
    g, beta = jnp.full((1, 2, t, D_K), -1e-4), jnp.full((1, 2, t), 0.999)
    args = (k * D_K ** -0.5, k, v, g, beta)
    close(kda.kda(*args, chunk=chunk), kda.kda_reference(*args), 1e-5)
    # the inverse's cotangent, -T^T dT T^T, on the same system
    weight = jax.random.normal(jax.random.key(4), v.shape)
    grads = jax.grad(lambda *a: jnp.sum(kda.kda(*a, chunk=chunk) * weight), argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(kda.kda_reference(*a) * weight), argnums=range(5))(*args)
    for name, got, want in zip(OPERANDS, grads, wants):
        if name == "g":
            # sums of order one that cancel to 4e-4 on equal keys: float32 leaves 1e-5 of them
            assert float(jnp.max(jnp.abs(got - want))) < 2e-5
        else:
            close(got, want, 1e-5)


@pytest.mark.parametrize("chunk", [8, 24, 96])
def test_a_chunk_the_kernels_cannot_halve_is_refused(chunk):
    """On every backend, as Mosaic would: a chunk is a power of two of at
    least 16 rows (bfloat16's sublane tile), halved down to the sub-blocks."""
    with pytest.raises(ValueError, match="power of two"):
        kda.kda(*operands(40, 0.1), chunk=chunk)


def test_bfloat16_operands_stay_finite_and_near():
    """The cell's dtype: products in bfloat16 with float32 accumulation, the
    state and the solve float32. Against the float32 recurrence on the same
    rounded operands: bfloat16's 2^-9 a rounding, a few roundings deep."""
    t, decay, _ = CASES["strong_decay"]
    args = operands(t, decay, dtype=jnp.bfloat16)
    out = kda.kda(*args)
    assert out.dtype == jnp.bfloat16
    close(out.astype(jnp.float32), kda.kda_reference(*args), 0.03)
    weight = jax.random.normal(jax.random.key(9), out.shape)
    grads = jax.grad(lambda *a: jnp.sum(kda.kda(*a).astype(jnp.float32) * weight),
                     argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(kda.kda_reference(*a) * weight), argnums=range(5))(*args)
    for name, got, want in zip(OPERANDS, grads, wants):
        assert got.dtype == want.dtype, name
        close(got.astype(jnp.float32), want.astype(jnp.float32), 0.05)


def test_no_initial_state_and_causal():
    """A later token changes no earlier output, and the first token's output
    is beta (q . k) v: the state starts at zero."""
    q, k, v, g, beta = operands(70, 0.1)
    out = kda.kda(q, k, v, g, beta, chunk=16)
    first = (beta[:, :, 0] * jnp.sum(q[:, :, 0] * k[:, :, 0], -1))[..., None] * v[:, :, 0]
    np.testing.assert_allclose(out[:, :, 0], first, rtol=1e-5, atol=1e-7)
    changed = kda.kda(q, k.at[:, :, 40:].multiply(-1.0), v.at[:, :, 40:].add(3.0), g, beta,
                      chunk=16)
    np.testing.assert_array_equal(out[:, :, :32], changed[:, :, :32])
    assert float(jnp.max(jnp.abs(out[:, :, 40:] - changed[:, :, 40:]))) > 0.1


@pytest.mark.parametrize("taps", [1, 4])
def test_short_conv_against_a_direct_sum(taps):
    x = jax.random.normal(jax.random.key(0), (2, 9, 5))
    w = jax.random.normal(jax.random.key(1), (taps, 5))
    want = np.zeros((2, 9, 5), np.float32)
    for t in range(9):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, t - (taps - 1) + j])
    got = kda.short_conv(x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the first positions see zeros before the sequence: the first only its own tap
    np.testing.assert_allclose(got[:, 0], w[-1] * x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(got[:, :3], want[:, :3], rtol=1e-5, atol=1e-6)
    assert kda.short_conv(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


def test_decay_floor_is_the_least_chunk_sum():
    g = -jnp.arange(1.0, 11.0)[None, :, None] * jnp.ones((2, 10, 3))  # tokens 1..10
    assert float(kda.decay_floor(g, 4)) == -(5 + 6 + 7 + 8)  # chunks 1-4, 5-8, 9-10 (padded)
    assert float(kda.decay_floor(g, 16)) == -55.0


def test_every_call_leaves_a_note():
    args = operands(40, 0.1, seed=5)
    kda.kda(*args, chunk=16)
    assert {"impl": "kda_fwd", "chunk": 16, "chunks": 3, "heads": H, "d_k": D_K, "d_v": D_V,
            "t": 40} in trace.program_notes("kda/call")


# -- what a block adds to the program -----------------------------------------------
# a delta-attention block's value holds one Mosaic kernel, kda_fwd, and its
# gradient a second, kda_bwd; neither holds a loop (a group's chunks lie side by
# side in a grid step, and the grid walks the groups). A rematerialised block
# keeps the scan's output and states by name and runs no forward kernel twice

def _block(remat_on):
    cls = remat.block(MLABlock) if remat_on else MLABlock
    return cls(False, 4, None, 32, 16, 8, 16, 128, 8, 2, 32, 32, 2.446, 0, 8, None,
               attn_impl="flash", mixer=KDA, kda_heads=4, kda_head_dim=16)


def _loops(jaxpr):
    return collections.Counter(
        kernel or p for p, kernel, _ in _equations(jaxpr) if p in ("scan", "while", "pallas_call"))


@pytest.mark.parametrize("remat_on", [False, True], ids=["plain", "remat"])
def test_loops_and_kernels_a_delta_attention_block_adds(remat_on):
    block = _block(remat_on)
    x = jax.random.normal(jax.random.key(0), (1, 48, 64))
    params = block.init(jax.random.key(1), x)
    value = lambda params: jnp.sum(block.apply(params, x)[0])  # noqa: E731
    assert dict(_loops(jax.make_jaxpr(value)(params).jaxpr)) == {"kda_fwd": 1}
    assert dict(_loops(jax.make_jaxpr(jax.value_and_grad(value))(params).jaxpr)) == {
        "kda_fwd": 1, "kda_bwd": 1}
    kept = {n["kept"] for n in trace.program_notes(remat.NOTE)}
    if remat_on:
        assert set(remat.KDA_KEPT) <= kept


@pytest.mark.parametrize("remat_on", [False, True], ids=["plain", "remat"])
def test_both_kernels_sit_under_the_scans_scope(remat_on):
    """Two ledger metrics read the scan by ``attn/kda/scan`` in the ops' names
    (``benchmark/layer_metrics/kda_scan_*``): the forward kernel and the
    backward kernel both bear it in the locations of the text lowered from a
    block's gradient, which is where the chip's trace takes ``op_name`` from."""
    block = _block(remat_on)
    x = jax.random.normal(jax.random.key(0), (1, 48, 64))
    params = block.init(jax.random.key(1), x)
    grad = jax.grad(lambda params: jnp.sum(block.apply(params, x)[0]))
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for kernel in KERNELS:
        named = [line for line in text.splitlines()
                 if line.lstrip().startswith("#loc") and kernel in line]
        assert named and all(trace.SCOPE_KDA_SCAN in line for line in named), kernel


def test_the_scan_lowers_for_the_tpu_at_the_cells_shape(monkeypatch):
    """[1, 32, 8192, 128] in bfloat16, value and gradients, lowered for the TPU
    from here (no compile, no chip; Mosaic as on the chip, not interpreted):
    two custom calls, kda_fwd and kda_bwd, and no loop of XLA's (the walk
    over chunks is inside them)."""
    monkeypatch.setattr(kda, "_interpret_on", lambda platform: False)
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 32, 8192), jnp.float32)

    def loss(q, k, v, g, beta):
        return kda.kda(q, k, v, g, beta).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=range(5))).trace(q, q, q, g, beta).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.while") == 0
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    for kernel in KERNELS:
        assert text.count(f'kernel_name = "{kernel}"') == 1
    assert {"impl": "kda_fwd", "chunk": kda.CHUNK, "chunks": 8192 // kda.CHUNK, "heads": 32,
            "d_k": 128, "d_v": 128, "t": 8192} in trace.program_notes("kda/call")
