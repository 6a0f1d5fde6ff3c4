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
Forward runs the pallas kernel; backward is a custom VJP that recomputes
attention blockwise with plain XLA ops — O(T) memory in both directions.
On the CPU backend the kernel runs in interpreter mode so the full test
suite exercises it on the 8-device CPU mesh; on TPU it is Mosaic-compiled;
any other backend is refused (see :func:`_interpret_on`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal, sm_scale, block_q):
    # q_ref: [block_q, D]; k_ref/v_ref: [T, D] (whole sequence for this head);
    # grid = (B*H, T // block_q).
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


@jax.named_scope(trace.SCOPE_FLASH_FWD)
def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    b, h, t, d = q.shape
    t_k = k.shape[2]
    block_q = _pick_block(t, block_q, q.dtype)
    block_k = _pick_block(t_k, block_k, k.dtype)
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
    out = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t_k, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t_k, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
        name=trace.FLASH_KERNEL_NAME,
    )(qf, kf, vf)
    return out.reshape(b, h, t, d)


# ---------------------------------------------------------------------------
# Blockwise backward (plain XLA, O(T·block) memory — never materializes the
# [T, T] score matrix; standard flash-attention backward recomputation)
# ---------------------------------------------------------------------------


@jax.named_scope(trace.SCOPE_BLOCKWISE_BWD)
def _blockwise_bwd(q, k, v, out, g, causal, sm_scale, block_k):
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    block_k = _pick_block(t_k, block_k, k.dtype)
    nkb = t_k // block_k
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    off = t_k - t_q

    # log-sum-exp per query row, recomputed blockwise
    q_pos = off + jnp.arange(t_q)

    def lse_step(carry, j):
        m, l = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32)) * sm_scale
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)
            s = jnp.where((k_pos[None] <= q_pos[:, None])[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # masked entries must contribute 0, not exp(NEG_INF - NEG_INF) = 1
        # (NEG_INF is finite; a fully masked row keeps m_new at NEG_INF)
        e = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l = l * jnp.exp(m - m_new) + jnp.sum(e, axis=-1, keepdims=True)
        return (m_new, l), None

    m0 = jnp.full((b, h, t_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_q, 1), jnp.float32)
    (m, l), _ = jax.lax.scan(lse_step, (m0, l0), jnp.arange(nkb))
    lse = m + jnp.log(jnp.maximum(l, 1e-20))

    # D_i = rowsum(dO * O)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1, keepdims=True)

    def grad_step(dq, j):
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, 2).astype(jnp.float32)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k, 2).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk) * sm_scale
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)
            s = jnp.where((k_pos[None] <= q_pos[:, None])[None, None], s, NEG_INF)
        # zero masked entries like the forward kernel does — for a fully
        # masked row lse is ~NEG_INF too and exp(s - lse) would be O(1)
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))  # [b,h,t_q,block_k]
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_blk)
        ds = p * (dp - delta) * sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(grad_step, dq0, jnp.arange(nkb))
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, t_k, d)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, t_k, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API: pallas forward + blockwise backward
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

    Forward = pallas kernel (interpreter mode on the CPU); backward = blockwise
    recomputation in plain XLA — O(T·block) memory in both directions, the
    [T, T] score matrix is never materialized.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)


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
    out = flash_attention(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out)


def _bwd_rule(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, out = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _blockwise_bwd(q, k, v, out, g, causal, sm_scale, block_k)


flash_attention.defvjp(_fwd_rule, _bwd_rule)
