"""Fused blockwise (flash) attention — the pallas hot-op for transformer
clients.

The reference has no attention anywhere (its NLP models are LSTMs,
fedml_api/model/nlp/rnn.py) and no long-context support (SURVEY §5.7). This
framework treats long sequences as first-class: the single-chip hot path is
this pallas kernel (online-softmax blockwise attention, O(T) memory instead of
the O(T²) score matrix), and the multi-chip path is ring attention over a
sequence-parallel mesh axis (fedml_tpu/parallel/ring_attention.py) which
reuses the same math.

Layout convention: ``[B, H, T, D]`` (batch, heads, sequence, head_dim).
Forward runs the pallas kernel ``flash_fwd``, which also writes each query
row's log-sum-exp (``B*H*T`` f32, the backward's one extra residual);
backward is a custom VJP of two more pallas kernels, the standard flash
backward: ``flash_bwd_dkv`` (a key block a grid step, looping over the query
blocks that see it) and ``flash_bwd_dq`` (a query block a step, looping over
key blocks up to the causal limit). Both recompute the scores a tile at a
time in VMEM from q, k and the log-sum-exp, with matmul operands in the
input's dtype and f32 accumulation, so memory is O(T) in both directions and
no ``[T, T]`` tile ever reaches HBM. The backward picks its own tiles
(:func:`_bwd_blocks`); ``block_q`` / ``block_k`` are the forward's.
On the CPU backend the kernels run in interpreter mode so the full test
suite exercises them on the 8-device CPU mesh; on TPU they are
Mosaic-compiled; any other backend is refused (see :func:`_interpret_on`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.obs import trace

NEG_INF = -1e30


def _interpret_on(platform: str) -> bool:
    """Whether the pallas kernel runs interpreted on ``platform``: yes on
    the CPU (the test suite), no on TPU (Mosaic). Anything else raises —
    guessing either way would hide which program actually ran."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"flash attention: no pallas lowering chosen for backend {platform!r} "
        "(interpreted on 'cpu', Mosaic-compiled on 'tpu'); use "
        "attn_impl='xla' there"
    )


def _pick_block(t: int, preferred: int, dtype) -> int:
    """Largest divisor of ``t`` not above ``preferred``. The block is the
    second-to-last (sublane) dimension of a VMEM tile, so it must be the
    whole axis or a multiple of the dtype's sublane tile (8 rows of 32 bits:
    8 for f32, 16 for bf16, 32 for 8-bit) — Mosaic refuses anything else,
    and the CPU interpreter must refuse it too or the suite passes shapes
    the chip cannot compile."""
    b = min(preferred, t)
    while t % b:
        b -= 1
    sublane = 8 * max(4 // jnp.dtype(dtype).itemsize, 1)
    if b != t and b % sublane:
        raise ValueError(
            f"flash attention: T={t} with preferred block {preferred} gives "
            f"block {b}, which is neither the whole axis nor a multiple of "
            f"the {sublane}-row sublane tile for {jnp.dtype(dtype).name}; "
            f"pad T to a multiple of {sublane} or pick a block that divides it"
        )
    return b


def attention_reference(q, k, v, causal: bool = False, sm_scale: float | None = None):
    """Plain XLA attention, the numerical oracle for the kernels.

    Causal convention (shared with the pallas kernel): query i attends to
    keys j with j <= i + (t_k - t_q) — i.e. sequences are right-aligned, the
    standard decode convention."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _eye(n):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (n, n), 1
    )


def _row(col):
    """``[n, 1]`` (one value a sublane) -> ``[1, n]`` (one value a lane) by a
    masked sublane reduction: exact, ``n * n`` elements once a query block,
    and faster on the v5e than the reshape Mosaic offers (PERF.md §6, PR 26)."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0, keepdims=True)


def _col(row):
    """``[1, n]`` -> ``[n, 1]``, the inverse of :func:`_row`."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1, keepdims=True)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal, sm_scale, block_q
):
    # q_ref: [block_q, D]; k_ref/v_ref: [T, D] (whole sequence for this head);
    # lse_ref: [1, block_q]; grid = (B*H, T // block_q).
    iq = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * sm_scale
    t_k, d = k_ref.shape
    num_kb = t_k // block_k
    t_q = pl.num_programs(1) * block_q

    # right-aligned causal offset, matching attention_reference
    q_pos = (t_k - t_q) + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )

    def body(j, carry):
        o, l, m = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o = o * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o, l, m_new

    o = jnp.zeros((block_q, d), jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    if causal:
        # only key blocks at or before this query block's last position
        last_q_pos = (t_k - t_q) + (iq + 1) * block_q - 1
        num_kb_eff = jnp.clip(last_q_pos // block_k + 1, 0, num_kb)
    else:
        num_kb_eff = num_kb
    o, l, m = jax.lax.fori_loop(0, num_kb_eff, body, (o, l, m))
    o_ref[:] = (o / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    # the backward's residual: log-sum-exp of each query row's scores, one
    # f32 a row in lanes (a fully masked row keeps about NEG_INF)
    lse_ref[:] = _row(m + jnp.log(jnp.maximum(l, 1e-20)))


def _head_seq(t, d):
    """A head's whole ``[t, d]`` sequence, resident across the grid's axis 1."""
    return pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0))


def _head_block(block, d):
    return pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0))


def _rows_spec(block):
    """BlockSpec of a query block's ``[1, block]`` tile of a per-row f32
    statistic (lse, delta) kept as ``[B*H, T // block, 1, block]``: T * 4
    bytes a head in HBM, and the tile's last two dimensions are the array's
    own, so Mosaic takes every ``block`` that :func:`_pick_block` passes."""
    return pl.BlockSpec((None, None, 1, block), lambda i, j: (i, j, 0, 0))


@jax.named_scope(trace.SCOPE_FLASH_FWD)
def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """``(out [B, H, T, D], lse [B, H, T] f32)``."""
    b, h, t, d = q.shape
    t_k = k.shape[2]
    block_q = _pick_block(t, block_q, q.dtype)
    block_k = _pick_block(t_k, block_k, k.dtype)
    nq = t // block_q
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t_k, d)
    vf = v.reshape(b * h, t_k, d)
    kernel = functools.partial(
        _flash_fwd_kernel,
        block_k=block_k,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq),
        in_specs=[_head_block(block_q, d), _head_seq(t_k, d), _head_seq(t_k, d)],
        out_specs=[_head_block(block_q, d), _rows_spec(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, nq, 1, block_q), jnp.float32),
        ],
        interpret=interpret,
        name=trace.FLASH_KERNEL_NAME,
    )(qf, kf, vf)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t)


# ---------------------------------------------------------------------------
# Pallas backward kernels: the standard two-kernel flash backward. Scores are
# recomputed a tile at a time in VMEM from q, k and the forward's lse; the
# [T, T] matrix never reaches HBM, and key blocks that the causal mask hides
# whole are skipped as the forward skips them.
# ---------------------------------------------------------------------------


def _visible(q_lo, k_lo, shape, transposed):
    """Causal mask of one score tile: key position <= query position (which
    carries the right-aligned offset). ``transposed`` tiles are [keys, queries]."""
    qd, kd = (1, 0) if transposed else (0, 1)
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, qd)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, kd)
    return k_pos <= q_pos


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, block_q, causal, sm_scale,
):
    # k_ref/v_ref/dk_ref/dv_ref: [block_k, D]; q_ref/do_ref: [T_q, D] (the
    # head's whole sequence); lse_ref/delta_ref: [T_q // block_q, 1, block_q];
    # grid = (B*H, T_k // block_k). Tiles are transposed, [keys, queries], so
    # the per-query statistics broadcast along sublanes and all four matmuls
    # are plain NN / NT.
    jk = pl.program_id(1)
    block_k = k_ref.shape[0]
    t_q = q_ref.shape[0]
    num_qb = t_q // block_q
    off = pl.num_programs(1) * block_k - t_q
    k = k_ref[:]
    v = v_ref[:]
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def step(masked, i):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[rows, :]
        do = do_ref[rows, :]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * sm_scale - lse_ref[i])
        if masked:
            # select, not multiply: a fully masked row's lse is about
            # NEG_INF and exp() of its scores is inf
            p = jnp.where(
                _visible(off + i * block_q, jk * block_k, s.shape, True), p, 0.0
            )
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[i]) * sm_scale
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32
        )
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32
        )

    def loop(lo, hi, masked):
        jax.lax.fori_loop(lo, hi, lambda i, c: step(masked, i), None)

    if causal:
        # query blocks whose last row reaches this key block's first column,
        # and of those the ones whose first row sees its last column (no mask)
        first = jnp.clip((jk * block_k - off) // block_q, 0, num_qb)
        first_whole = jnp.clip(
            ((jk + 1) * block_k - 1 - off + block_q - 1) // block_q, first, num_qb
        )
        loop(first, first_whole, True)
        loop(first_whole, num_qb, False)
    else:
        loop(0, num_qb, False)
    dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, block_k, causal, sm_scale,
):
    # q_ref/do_ref/dq_ref: [block_q, D]; k_ref/v_ref: [T_k, D] (the head's
    # whole sequence); lse_ref/delta_ref: [1, block_q];
    # grid = (B*H, T_q // block_q).
    iq = pl.program_id(1)
    block_q = q_ref.shape[0]
    t_k = k_ref.shape[0]
    num_kb = t_k // block_k
    off = t_k - pl.num_programs(1) * block_q
    q = q_ref[:]
    do = do_ref[:]
    lse = _col(lse_ref[:])
    delta = _col(delta_ref[:])
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def step(masked, j):
        cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[cols, :]
        v = v_ref[cols, :]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * sm_scale - lse)
        if masked:
            p = jnp.where(
                _visible(off + iq * block_q, j * block_k, s.shape, False), p, 0.0
            )
        dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32
        )

    def loop(lo, hi, masked):
        jax.lax.fori_loop(lo, hi, lambda j, c: step(masked, j), None)

    if causal:
        # key blocks up to this query block's last position (as the forward's
        # num_kb_eff), and of those the ones its first row sees whole (no mask)
        last = jnp.clip((off + (iq + 1) * block_q - 1) // block_k + 1, 0, num_kb)
        whole = jnp.clip((off + iq * block_q + 1) // block_k, 0, last)
        loop(0, whole, False)
        loop(whole, last, True)
    else:
        loop(0, num_kb, False)
    dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_blocks(t_q, t_k, dtype, fwd_blocks):
    """The backward kernels' ``(block_q, block_k)``. Measured on the v5e at
    D 128 bf16, causal: 512 x 512 is the fastest for both kernels at T 2048
    (2.17 ms a call; 256 x 256 2.73, 1024 x 1024 2.35) and at T 1024 (0.77;
    0.91, 0.85): tiles large enough to amortise the loop, small enough that
    the causal diagonal wastes an eighth of the square and not a quarter, and
    every temporary of a step fits the 16 MB of scoped VMEM wherever the
    forward's whole-sequence K and V do (PERF.md §6, PR 26). A length whose
    divisor under 512 Mosaic refuses keeps the forward's block, which passed."""

    def pick(t, fwd_block):
        try:
            return _pick_block(t, 512, dtype)
        except ValueError:
            return _pick_block(t, fwd_block, dtype)

    return pick(t_q, fwd_blocks[0]), pick(t_k, fwd_blocks[1])


@jax.named_scope(trace.SCOPE_BLOCKWISE_BWD)
def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret):
    """``(dq, dk, dv)``; ``block_q`` / ``block_k`` divide ``t_q`` / ``t_k``
    (:func:`_bwd_blocks` picks them through :func:`_pick_block`)."""
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    nq, nk = t_q // block_q, t_k // block_k
    # D_i = rowsum(dO * O)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    args = (
        q.reshape(b * h, t_q, d), k.reshape(b * h, t_k, d), v.reshape(b * h, t_k, d),
        g.reshape(b * h, t_q, d),
        lse.reshape(b * h, nq, 1, block_q), delta.reshape(b * h, nq, 1, block_q),
    )
    q_seq, k_seq = _head_seq(t_q, d), _head_seq(t_k, d)
    q_blk, k_blk = _head_block(block_q, d), _head_block(block_k, d)
    # a head's every [1, block_q] tile of lse / delta, resident like q_seq
    rows_seq = pl.BlockSpec((None, nq, 1, block_q), lambda i, j: (i, 0, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, causal=causal, sm_scale=sm_scale
        ),
        grid=(b * h, nk),
        in_specs=[q_seq, k_blk, k_blk, q_seq, rows_seq, rows_seq],
        out_specs=[k_blk, k_blk],
        out_shape=[jax.ShapeDtypeStruct((b * h, t_k, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, t_k, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32)] * 2,
        interpret=interpret,
        name=trace.FLASH_BWD_DKV_KERNEL_NAME,
    )(*args)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, causal=causal, sm_scale=sm_scale
        ),
        grid=(b * h, nq),
        in_specs=[q_blk, k_seq, k_seq, q_blk, _rows_spec(block_q), _rows_spec(block_q)],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=trace.FLASH_BWD_DQ_KERNEL_NAME,
    )(*args)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# Public API: pallas forward + pallas backward
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
):
    """Blockwise fused attention for ``[B, H, T, D]`` inputs.

    Forward = pallas kernel (interpreter mode on the CPU); backward = two
    pallas kernels that recompute the scores blockwise from the forward's
    log-sum-exp — O(T·block) memory in both directions, the [T, T] score
    matrix is never materialized. ``block_q`` / ``block_k`` tile the forward;
    the backward chooses its own tiles from the shape and dtype.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)[0]


def flash_attention_head_parallel(
    q,
    k,
    v,
    *,
    axis: str | None,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
):
    """:func:`flash_attention` inside a global-view (pjit) program over a
    multi-device mesh: the pallas kernel runs per device under a
    ``shard_map``, on its LOCAL heads when the plan is tensor-parallel.

    Mosaic refuses to be partitioned: lowering a pallas call raises "Mosaic
    kernels cannot be automatically partitioned" in a multi-device pjit
    program, and just the same under a shard_map that is manual over only
    SOME mesh axes (seen on the v5e, 4 chips, PR 21; the CPU interpreter
    accepts both). So whenever a multi-device mesh is active the kernel is
    wrapped in a shard_map manual over EVERY mesh axis:

    - heads split over ``axis`` when the plan is tensor-parallel — attention
      is head-local math (softmax normalizes per head), so the per-rank
      kernel computes bits identical to the full-head kernel's and the
      ``[B, H_local, T, D]`` blocks stay resident;
    - everything else replicated: each device runs the kernel on what it
      holds, which is what the partitioner does with an op it cannot split
      (gather-for-compute plans, ``axis=None``). A cohort vmap adds its
      ``spmd_axis_name`` to the specs by itself.

    Resolution order at trace time:

    - no active mesh (eager use, or a client-mapped shard_map program, which
      is already manual) or a 1-device mesh → the plain kernel;
    - heads do NOT divide a >1-way ``axis`` → :func:`attention_reference`
      (plain XLA — the partitioner can split *its* einsums head-wise) with
      a loud warning, because silently gathering the kernel would defeat
      the plan;
    - otherwise → the kernel under the all-axes ``jax.shard_map``.
    """
    from fedml_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal, sm_scale, block_q, block_k)
    n_ranks = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    n_heads = q.shape[1]
    if n_heads % n_ranks:
        import logging

        logging.getLogger(__name__).warning(
            "flash attention under a %d-way %r model axis: %d heads do not "
            "divide the axis, so the pallas kernel cannot run per-rank — "
            "falling back to gathered xla attention for this program; pick "
            "num_heads divisible by the model axis to keep the kernel on "
            "the sharded path",
            n_ranks, axis, n_heads,
        )
        return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    from jax.sharding import PartitionSpec

    hspec = PartitionSpec(None, axis if n_ranks > 1 else None, None, None)
    return jax.shard_map(
        functools.partial(
            flash_attention, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k,
        ),
        mesh=mesh, in_specs=(hspec,) * 3, out_specs=hspec, check_vma=False,
    )(q, k, v)


def _fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    blocks = _bwd_blocks(q.shape[2], k.shape[2], q.dtype, (block_q, block_k))
    trace.event(
        "attn/bwd_path", impl="kernel", shape=tuple(q.shape), t_k=k.shape[2],
        dtype=jnp.dtype(q.dtype).name, blocks=blocks,
    )
    interpret = _interpret_on(jax.default_backend())
    return _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, *blocks, interpret)


flash_attention.defvjp(_fwd_rule, _bwd_rule)
