"""The delta rule's chunked scan (``fedml_tpu/ops/kda.py``: two Mosaic kernels,
interpreted here) against the recurrence run token by token: output and the
five operands' gradients at three chunk sizes, at a length that is no
multiple of the chunk, under decays whose cumulated logs pass -200 (where
``exp(-gamma)`` alone overflows), on sixty-four equal keys (where powers of the
triangular system overflow), and in bfloat16; the short convolution against a
direct sum; the mixer's three pointwise chains (``conv_act``, ``decay``,
``gated_norm``: a hand-written backward each) against the plain compositions
they replace, values and every operand's gradient; the kernels and loops a
block adds, and the scope they sit under; and the scan's lowering for the TPU
at the cell's shape. On the CPU; nothing here describes a TPU topology, so
the file is safe under xdist.
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


# -- the three chains: each operator against the composition it replaces ---------------
# (batch, tokens, heads, head width): T 1, T no multiple of 8, one head and several;
# a block of several pieces, and three blocks (the last part padding) whose taps
# and whose convolution's backward read across pieces and across blocks
CHAIN_SHAPES = {"one_token": (2, 1, 3, 8), "odd_length": (2, 13, 3, 8), "one_head": (1, 21, 1, 16),
                "two_tiles": (1, 40, 4, 16), "pieces": (2, 300, 2, 8), "blocks": (1, 4500, 2, 8)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _draw(seed, shape, dtype=jnp.float32, scale=1.0):
    return (scale * jax.random.normal(jax.random.key(seed), shape)).astype(dtype)


def _chain(op, shape, dtype, taps=4):
    """``(operator, reference, operands)`` of one chain at a shape."""
    b, t, n, d = shape
    if op == "gated_norm":
        args = (_draw(1, (b, n, t, d), dtype), _draw(2, (b, t, n * d), dtype),
                1.0 + 0.3 * _draw(3, (d,)))
        return (lambda *a: kda.gated_norm(*a, eps=1e-5),
                lambda *a: kda.gated_norm_reference(*a, eps=1e-5), args)
    if op == "decay":
        return kda.decay, kda.decay_reference, (
            _draw(1, (b, t, n * d), dtype), _draw(2, (n * d,), scale=2.0), _draw(3, (n,)))
    norm = op == "conv_act_norm"
    kw = dict(heads=n, norm=norm, scale=d ** -0.5 if norm else 1.0)
    args = (_draw(1, (b, t, n * d), dtype), _draw(2, (taps, n * d), scale=taps ** -0.5))
    return (lambda *a: kda.conv_act(*a, **kw), lambda *a: kda.conv_act_reference(*a, **kw), args)


CHAINS = ("conv_act_norm", "conv_act_plain", "decay", "gated_norm")


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(CHAIN_SHAPES))
@pytest.mark.parametrize("op", CHAINS)
def test_a_chain_equals_the_composition_it_replaces(op, shape, dtype):
    """Values, and every operand's gradient pulled back from one random
    cotangent: the kernels (interpreted here) against the plain composition
    and jax's own backward of it. Float32 to rounding; bfloat16 to a few of
    its roundings (the operators stop in bfloat16 where the composition does,
    or later: they hold float32 from the convolution's output on)."""
    fn, ref, args = _chain(op, CHAIN_SHAPES[shape], DTYPES[dtype])
    got, want = fn(*args), ref(*args)
    assert got.dtype == want.dtype and got.shape == want.shape
    close(got.astype(jnp.float32), want.astype(jnp.float32), 2e-6 if dtype == "f32" else 0.02)
    weight = _draw(9, got.shape)
    pulled = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weight), argnums=range(len(args)))(*args)
    for i, (g, r) in enumerate(zip(pulled(fn), pulled(ref))):
        assert g.dtype == r.dtype == args[i].dtype and g.shape == args[i].shape, i
        scale = max(float(jnp.max(jnp.abs(r.astype(jnp.float32)))), 1e-3)
        tol = 2e-5 if dtype == "f32" and args[i].dtype == jnp.float32 else 0.04
        np.testing.assert_allclose(g.astype(jnp.float32), r.astype(jnp.float32), atol=tol * scale,
                                   err_msg=f"{op} operand {i}")


@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("op", ["conv_act_norm", "conv_act_plain"])
def test_conv_act_at_two_and_four_taps_and_causal(op, taps):
    """The taps' and the input's gradients at both tap counts, against the
    composition's; and a later token changes no earlier output, nor does a
    later cotangent reach an earlier token's input gradient the wrong way (the
    input's gradient at ``t`` reads cotangents ``t ... t + K - 1`` only)."""
    fn, ref, (y, w) = _chain(op, (2, 11, 2, 8), jnp.float32, taps)
    weight = _draw(9, fn(y, w).shape)
    pulled = lambda f, w8=weight: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w8), argnums=(0, 1))(y, w)
    for g, r in zip(pulled(fn), pulled(ref)):
        np.testing.assert_allclose(g, r, atol=2e-5 * float(jnp.max(jnp.abs(r))))
    later = fn(y.at[:, 6:].add(1.0), w)
    np.testing.assert_array_equal(later[:, :, :6], fn(y, w)[:, :, :6])
    assert float(jnp.abs(later[:, :, 6:] - fn(y, w)[:, :, 6:]).max()) > 1e-3
    d_y, _ = pulled(fn)
    d_y_cut, _ = pulled(fn, weight.at[:, :, :4].set(0.0))  # cotangents of tokens 0 .. 3 dropped
    np.testing.assert_allclose(d_y_cut[:, 4:], d_y[:, 4:], atol=1e-6)
    assert float(jnp.abs(d_y_cut[:, :4] - d_y[:, :4]).max()) > 1e-3


@pytest.mark.parametrize("op", CHAINS)
def test_a_chains_gradient_program_is_two_kernels_and_no_padded_float32_array(op, monkeypatch):
    """Lowered for the TPU at whole blocks (``[1, 2048, 4 x 128]`` bfloat16; no
    compile, no chip; Mosaic as on the chip, not interpreted): the forward
    kernel and the backward kernel under the chain's scope and nothing of XLA's
    around them that moves an array: no pad (no ``[B, T + K - 1, C]`` array in
    any dtype: the rows before a block are read by a second BlockSpec), no
    transpose (the move between the layouts is the BlockSpecs' index maps),
    no slice back."""
    monkeypatch.setattr(kda, "_interpret_on", lambda platform: False)
    fn, _, args = _chain(op, (1, 2048, 4, 128), jnp.bfloat16)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    text = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                                      argnums=range(len(args)))).trace(*shapes).lower(
                                          lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    kernels = [k for k in kda.CHAIN_KERNELS if f'kernel_name = "{k}"' in text]
    assert len(kernels) == 2 and kernels[0][:-3] == kernels[1][:-3]  # one chain's fwd and bwd
    scope = kda.CHAIN_SCOPES[kernels[0].split("_")[1]]
    for kernel in kernels:
        named = [line for line in text.splitlines()
                 if line.lstrip().startswith("#loc") and f"{kernel}/" in line]
        assert named and all(scope in line for line in named), kernel
    body = text[:text.index("\n#loc", 200)]
    assert "stablehlo.pad" not in body and "stablehlo.transpose" not in body
    assert "x2051x" not in body and "stablehlo.slice" not in body


def test_every_chain_leaves_a_note_with_its_stated_bytes():
    for op in CHAINS:
        fn, _, args = _chain(op, (2, 17, 3, 8), jnp.bfloat16)  # a shape no other test notes
        fn(*args)
    notes = {n["op"]: n for n in trace.program_notes(kda.CHAIN_NOTE)
             if (n["rows"], n["columns"]) == (34, 24)}
    # bfloat16 [34, 24]: one in and one out; one in and float32 out; two in and one out
    assert {op: n["bytes"] for op, n in notes.items()} == {
        "conv_act": 2 * 34 * 24 * 2, "decay": 34 * 24 * (2 + 4), "gated_norm": 3 * 34 * 24 * 2}


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
# a delta-attention block's value holds the scan's kernel, kda_fwd, and the five
# chain kernels round it (three streams, the decay, the gated norm); its gradient
# the scan's second, kda_bwd, and the five backward kernels. The scan's kernels
# hold no loop (a group's chunks lie side by side in a grid step, and the grid
# walks the groups); a chain's kernel holds one, over its block's pieces, and XLA
# sees none. A rematerialised block keeps the scan's output and states by name and
# runs no scan twice; the chains, which keep nothing, run again

FORWARD = {"kda_fwd": 1, "kda_conv_fwd": 3, "kda_decay_fwd": 1, "kda_gate_fwd": 1}
BACKWARD = {"kda_bwd": 1, "kda_conv_bwd": 3, "kda_decay_bwd": 1, "kda_gate_bwd": 1}


def _with_their_loops(kernels):
    """... and the one loop inside each chain kernel (jax writes it ``scan``)."""
    return {**kernels, "scan": sum(v for k, v in kernels.items() if k in kda.CHAIN_KERNELS)}


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
    assert dict(_loops(jax.make_jaxpr(value)(params).jaxpr)) == _with_their_loops(FORWARD)
    again = {k: 2 * v for k, v in FORWARD.items() if k != "kda_fwd"} if remat_on else {}
    assert dict(_loops(jax.make_jaxpr(jax.value_and_grad(value))(params).jaxpr)) == (
        _with_their_loops({**FORWARD, **again, **BACKWARD}))
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
