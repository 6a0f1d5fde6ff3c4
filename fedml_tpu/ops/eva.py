"""EVA attention in its causal, deterministic form (Zheng, Yuan, Wang, Kong,
"Efficient Attention via Control Variates", arXiv:2302.04542, as the EvaByte
release runs it): exact softmax attention inside a query's own window of
``window`` positions, and one softmax shared with *summaries* of every chunk
of ``chunk`` positions in the windows before it. For a head with ``q_i, k_j,
v_j`` (positions already applied), ``s`` the score scale and learned ``phi``,
``mu`` of the head's width:

    chunk c (positions chunk * c ... chunk * c + chunk - 1):
      alpha = softmax_j(s * <phi, k_j>)             over the chunk's positions
      k~_c  = sum_j alpha_j k_j + mu;   v~_c = sum_j alpha_j v_j
    query i, in window w = i // window, sees
      keys j of its own window with j <= i          scores s * <q_i, k_j>
      summaries c < (window / chunk) * w            scores s * <q_i, k~_c>
    o_i = one softmax over the union, times [v_j ; v~_c]

Window 0 has no summaries and is plain causal attention. How the one softmax
is made: two flash calls (``ops/attention.py`` :func:`flash_attention_lse`),
each over one key set and each handing out its rows' log-sum-exp, merged here:

    local:   q, k, v as ``[B, H * n_w, window, D]`` (a head's windows beside
             the heads: a reshape, nothing moves), causal
    remote:  q ``[B, H, T, D]`` on the summaries ``[B, H, T / chunk, D]`` under
             the staircase mask ``stair=(window, window / chunk)``
    lse = logaddexp(lse_l, lse_r);  o = o_l e^(lse_l - lse) + o_r e^(lse_r - lse)

A row of window 0 has a remote log-sum-exp of about -5e29 and takes its local
output whole. The summaries and the merge are plain fused XLA (elementwise
passes and a 16-wide reduction: memory-bound) under the scopes
``attn/eva/summary`` and ``attn/eva/merge`` (``obs/trace.py``); every call
leaves an ``eva/call`` program note with its shapes. ``eva_reference`` is the
same mathematics with the whole score matrix, the oracle of the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat
from fedml_tpu.ops.attention import NEG_INF, flash_attention_lse

NOTE = "eva/call"


def chunk_summaries(k, v, phi, mu, chunk: int, sm_scale: float):
    """``(k~, v~)`` ``[B, H, T / chunk, D]`` in ``k``'s and ``v``'s dtypes from
    ``k``, ``v`` ``[B, H, T, D]`` and ``phi``, ``mu`` ``[H, D]``; the weights
    and the pools in float32."""
    b, h, t, d = k.shape
    with jax.named_scope(trace.SCOPE_EVA_SUMMARY):
        kc = k.reshape(b, h, t // chunk, chunk, d).astype(jnp.float32)
        vc = v.reshape(b, h, t // chunk, chunk, v.shape[-1]).astype(jnp.float32)
        a = sm_scale * jnp.sum(kc * phi.astype(jnp.float32)[None, :, None, None], axis=-1)
        alpha = jax.nn.softmax(a, axis=-1)[..., None]
        k_sum = jnp.sum(alpha * kc, axis=3) + mu.astype(jnp.float32)[None, :, None]
        return k_sum.astype(k.dtype), jnp.sum(alpha * vc, axis=3).astype(v.dtype)


def merge(out_l, lse_l, out_r, lse_r):
    """The one softmax's output from the two calls', and ``exp(lse_r - lse)``
    ``[B, H, T]``: the share of a row's softmax that lies on the summaries."""
    with jax.named_scope(trace.SCOPE_EVA_MERGE):
        lse = jnp.logaddexp(lse_l, lse_r)
        w_l, w_r = jnp.exp(lse_l - lse), jnp.exp(lse_r - lse)
        out = out_l.astype(jnp.float32) * w_l[..., None] + out_r.astype(jnp.float32) * w_r[..., None]
        return out.astype(out_l.dtype), w_r


def _flash(q, k, v, **kwargs):
    """:func:`flash_attention_lse`, under a multi-device mesh inside a
    ``shard_map`` over every axis with everything replicated (Mosaic refuses
    to be partitioned: ``flash_attention_head_parallel``)."""
    from fedml_tpu.parallel.mesh import current_mesh

    call = functools.partial(flash_attention_lse, **kwargs)
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return call(q, k, v)
    spec = jax.sharding.PartitionSpec()
    return jax.shard_map(call, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec, spec),
                         check_vma=False)(q, k, v)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int, sm_scale: float | None = None,
                  impl: str = "flash"):
    """``(out [B, H, T, D], remote_mass)``: the module docstring's attention
    over ``q``, ``k``, ``v`` ``[B, H, T, D]``, and the mean over the queries
    past window 0 of the softmax's share on the summaries (a scalar, 0 where
    ``T <= window``). ``T`` is at most one window or whole windows of whole
    chunks. ``impl`` "xla" is :func:`eva_reference`."""
    b, h, t, d = q.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    if window % chunk or (t > window and t % window):
        raise ValueError(f"eva: T {t} is not whole windows of {window} in chunks of {chunk}")
    n_w = max(t // window, 1)
    trace.program_note(NOTE, impl=impl, shape=(b, h, t, d), window=window, chunk=chunk,
                       windows=n_w, summaries=t // chunk if n_w > 1 else 0,
                       dtype=jnp.dtype(q.dtype).name)
    if impl != "flash":
        return eva_reference(q, k, v, phi, mu, window=window, chunk=chunk, sm_scale=sm_scale)
    fold = lambda x: x.reshape(b, h * n_w, t // n_w, x.shape[-1])  # noqa: E731
    out_l, lse_l = _flash(fold(q), fold(k), fold(v), causal=True, sm_scale=sm_scale,
                          keep=remat.EVA_LOCAL)
    out_l, lse_l = out_l.reshape(b, h, t, -1), lse_l.reshape(b, h, t)
    if n_w == 1:
        return out_l, jnp.float32(0.0)
    k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk, sm_scale)
    out_r, lse_r = _flash(q, k_sum, v_sum, sm_scale=sm_scale, stair=(window, window // chunk),
                          keep=remat.EVA_REMOTE)
    out, w_r = merge(out_l, lse_l, out_r, lse_r)
    return out, jax.lax.stop_gradient(jnp.mean(w_r[:, :, window:]))


def eva_reference(q, k, v, phi, mu, *, window: int, chunk: int, sm_scale: float | None = None):
    """:func:`eva_attention` with the whole ``[T, T + T / chunk]`` score
    matrix in float32 and one ``softmax`` over it."""
    b, h, t, d = q.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    dtype = q.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    pos = jnp.arange(t)
    local = (pos[None] <= pos[:, None]) & (pos[None] // window == pos[:, None] // window)
    s = jnp.where(local, jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale, NEG_INF)
    values = v
    if t > window:
        k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk, sm_scale)
        remote = jnp.arange(t // chunk)[None] < (window // chunk) * (pos[:, None] // window)
        s_r = jnp.where(remote, jnp.einsum("bhqd,bhkd->bhqk", q, k_sum) * sm_scale, NEG_INF)
        s, values = jnp.concatenate([s, s_r], axis=-1), jnp.concatenate([v, v_sum], axis=2)
    p = jax.nn.softmax(s, axis=-1)
    mass = jnp.mean(jnp.sum(p[:, :, window:, t:], axis=-1)) if t > window else jnp.float32(0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, values).astype(dtype), jax.lax.stop_gradient(mass)
