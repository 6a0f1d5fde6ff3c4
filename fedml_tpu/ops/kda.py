"""Kimi Delta Attention's recurrence (a gated delta rule with one decay a key
channel) in chunked form, forward and backward, and the short causal
convolution that feeds it.

A head keeps a state ``S`` ``[d_k, d_v]`` that every token first decays, a
key channel at its own rate, then rewrites by a delta rule, then reads
(:func:`kda_reference` runs exactly this, token by token, in float32):

    S~  = Diag(exp g_t) S_{t-1}                     g_t <= 0: log-decays [d_k]
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T        beta_t in (0, 1)
    o_t = S_t^T q_t                                 S_0 = 0

:func:`kda` computes the same in chunks of ``C`` tokens. With ``gamma_r`` the
log-decays cumulated from the chunk's start and ``S_0`` the state that
enters, the delta rule's writes ``u`` solve a unit lower-triangular system

    (I + Diag(beta) strict_tril(A)) U = Diag(beta) (V - K+ S_0)
    A_ij = sum_c k_ic k_jc exp(gamma_ic - gamma_jc)  (i > j),   K+_i = k_i * exp(gamma_i)
    o_r  = (q_r * exp gamma_r)^T S_0 + sum_{i<=r} B_ri u_i
    B_ri = sum_c q_rc k_ic exp(gamma_rc - gamma_ic)           (r >= i)
    S_C  = Diag(exp gamma_C) S_0 + sum_i (k_i * exp(gamma_C - gamma_i)) u_i^T

**Every exponent formed is a difference with the later index first, so it is
<= 0**: ``exp(-gamma)`` alone, which overflows float32 once a chunk's
cumulated log-decay passes -88, is never computed. ``A`` and ``B`` are built
over sub-blocks of ``SUB`` rows: a sub-block's rows against an *earlier*
sub-block's columns go through the MXU with both factors taken against the
later sub-block's first row (``exp(gamma_i - rho) * exp(rho - gamma_j)``, each
<= 1); inside a sub-block the ``[SUB, SUB, d_k]`` contraction is formed
directly, the mask applied to the exponent and not to its ``exp`` (an ``inf``
selected away would still put a NaN in the gradient). The triangular system
is solved by inverting it in blocks that double (:func:`_unit_lower_inverse`:
six levels at ``C`` 64, no power of ``L`` formed) in float32 at ``highest``; the
state, the cumulated log-decays and the solve are float32 whatever the
products' dtype.

The program is plain XLA: one ``lax.scan`` over groups of ``GROUP`` tokens
that carries ``S``; a step forms its chunks' local quantities batched over
heads and chunks, then walks its chunks in a straight line. The backward
(``jax.custom_vjp``) keeps the state that entered each group (67 MB a layer
at T 8192) and scans the groups in reverse, differentiating a recomputed
group at a time, so its temporaries are a group's and not the sequence's.
Under a rematerialised block the output and those states are kept by name
(``ops/remat.py``: ``kda/out``, ``kda/states``) and the block's second forward
runs no scan.

Scope ``attn/kda/scan`` (``obs/trace.py``) is round the forward and the
backward scan; every call leaves a ``kda/call`` program note.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat

HI = jax.lax.Precision.HIGHEST
CHUNK = 64  # tokens a chunk: the one rule (PERF.md section 6, PR 38)
# measured at [1, 32, 8192, 128] in bfloat16 on the v5e, forward + backward of one
# call (PERF.md section 6, PR 38): chunks of 128 take 1.3-1.6 x the time of 64;
# groups of 256 tokens 54 ms where 512 take 66-70 and 1024 76 (128: 52); sub-blocks
# of 8 rows 6% under 16
SUB = 8  # rows of a sub-block of A and B
GROUP = 256  # tokens a step of the scan over the sequence


def short_conv(x, w):
    """Causal depthwise convolution along the sequence: ``x`` ``[B, T, C]``,
    ``w`` ``[K, C]`` (tap ``K - 1`` meets the token itself), zeros before the
    first token, no bias: ``y_t = sum_j w_j * x_{t - (K - 1) + j}``.
    Accumulated in float32, returned in ``x``'s dtype."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
    y = sum(padded[:, j:j + t] * w[j].astype(jnp.float32) for j in range(taps))
    return y.astype(x.dtype)


def l2norm(x, eps: float = 1e-6):
    """``x * rsqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def kda_reference(q, k, v, g, beta):
    """The recurrence token by token in float32: ``q``, ``k``, ``g`` ``[B, H,
    T, d_k]``, ``v`` ``[B, H, T, d_v]``, ``beta`` ``[B, H, T]`` -> ``[B, H, T,
    d_v]`` float32. The definition the chunked form is held to, and the
    ``xla`` path of the module."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HI))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    s0 = jnp.zeros((*q.shape[:2], q.shape[-1], v.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2)


def decay_floor(g, chunk: int = CHUNK):
    """The most negative log-decay a chunk of ``g`` ``[..., T, d_k]``
    cumulates (a scalar): how far below 0 the chunked form's exponents
    reach."""
    t = g.shape[-2]
    g = jnp.pad(g, [(0, 0)] * (g.ndim - 2) + [(0, -t % chunk), (0, 0)])
    return jnp.min(jnp.sum(g.reshape(*g.shape[:-2], -1, chunk, g.shape[-1]), axis=-2))


def _mm(spec, a, b):
    """A product in the operands' dtype with float32 accumulation; float32
    operands at ``highest``."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                      precision=HI if a.dtype == jnp.float32 else None)


def _unit_lower_inverse(low):
    """``(I + low)^-1`` of strictly lower-triangular ``low`` ``[..., C, C]``
    (float32; C a power of two) by block doubling: with the inverses of two
    neighbouring diagonal blocks in hand, ``[[A, 0], [X, B]]^-1 = [[A^-1, 0],
    [-B^-1 X A^-1, B^-1]]``, from blocks of one row (whose inverse is 1) up to
    the whole. A substitution in blocks: no power of ``low`` is formed, so
    nothing grows that the inverse itself does not hold (the product
    ``prod_k (I + (-low)^(2^k))`` overflows on 64 equal keys)."""
    c = low.shape[-1]
    lead = low.shape[:-2]
    inverse = jnp.ones((*lead, c, 1, 1), low.dtype)
    size = 1
    while size < c:
        pairs = c // (2 * size)
        # the diagonal blocks of 2 x size rows, then each one's lower left quarter
        own = jnp.eye(pairs, dtype=low.dtype)[:, None, :, None]
        below = jnp.sum(low.reshape(*lead, pairs, 2 * size, pairs, 2 * size) * own,
                        axis=-2)[..., size:, :size]
        first, second = (inverse.reshape(*lead, pairs, 2, size, size)[..., i, :, :]
                         for i in (0, 1))
        corner = -jnp.matmul(jnp.matmul(second, below, precision=HI), first, precision=HI)
        inverse = jnp.concatenate(
            [jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
             jnp.concatenate([corner, second], axis=-1)], axis=-2)
        size *= 2
    return inverse.reshape(*lead, c, c)


def _group(s, q, k, v, g, beta, sub):
    """One step of the scan: the chunks ``[B, H, R, C, ...]`` of a group from
    the state ``s`` ``[B, H, d_k, d_v]`` (float32) that enters it. Returns the
    group's outputs ``[B, H, R, C, d_v]`` and the state that leaves."""
    dt, f32 = q.dtype, jnp.float32
    r, c, d_k = q.shape[2], q.shape[3], q.shape[4]
    n = c // sub
    lead = q.shape[:3]
    qf, kf, g, beta = q.astype(f32), k.astype(f32), g.astype(f32), beta.astype(f32)
    gamma = jnp.cumsum(g, axis=-2)  # [B, H, R, C, d_k], inclusive, <= 0
    blocks = lambda x: x.reshape(*lead, n, sub, x.shape[-1])  # noqa: E731
    gamma_s, q_s, k_s = blocks(gamma), blocks(qf), blocks(kf)
    rho = gamma_s[..., 0, :]  # a sub-block's first row: [..., n, d_k]
    # rows against earlier sub-blocks' columns, both factors against rho
    rows = jnp.exp(gamma_s - rho[..., None, :])
    rows = jnp.concatenate([k_s * rows, q_s * rows], axis=-2).astype(dt)  # [..., n, 2 sub, d_k]
    earlier = jnp.arange(c)[None, :] < (jnp.arange(n) * sub)[:, None]  # [n, C]
    cols = jnp.where(earlier[..., None], rho[..., None, :] - gamma[..., None, :, :], -jnp.inf)
    cols = (kf[..., None, :, :] * jnp.exp(cols)).astype(dt)  # [..., n, C, d_k]
    off = _mm("...id,...jd->...ij", rows, cols)  # [..., n, 2 sub, C]
    # inside a sub-block, directly
    seen = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    pair = jnp.exp(jnp.where(seen[..., None],
                             gamma_s[..., :, None, :] - gamma_s[..., None, :, :], -jnp.inf))
    own = jnp.eye(n, dtype=f32)[:, None, :, None]  # places [n, sub, sub] on the block diagonal

    def square(off_part, left):
        inside = jnp.sum(left[..., :, None, :] * k_s[..., None, :, :] * pair, axis=-1)
        return (off_part + (inside[..., :, :, None, :] * own).reshape(*lead, n, sub, c)
                ).reshape(*lead, c, c)

    a = square(off[..., :sub, :], k_s)
    b = square(off[..., sub:, :], q_s)  # lower triangle, the diagonal with it
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    solve = _unit_lower_inverse(jnp.where(strict, beta[..., None] * a, 0.0))
    decayed = jnp.exp(gamma)
    uw = jnp.matmul(solve, beta[..., None] * jnp.concatenate(
        [v.astype(f32), kf * decayed], axis=-1), precision=HI)
    u, w = uw[..., :v.shape[-1]], uw[..., v.shape[-1]:].astype(dt)
    q_in = (qf * decayed).astype(dt)
    last = gamma[..., -1:, :]
    k_out = (kf * jnp.exp(last - gamma)).astype(dt)
    carried = jnp.exp(last[..., 0, :])  # [B, H, R, d_k]: what is left of the entering state
    # the one sequential part: a chunk's writes need the state the chunks before left
    def chunk(s, xs):
        u_i, w_i, k_i, carried_i = xs
        entered = s.astype(dt)
        new = (u_i - _mm("bhck,bhkv->bhcv", w_i, entered)).astype(dt)
        return carried_i[..., None] * s + _mm("bhck,bhcv->bhkv", k_i, new), (new, entered)

    s, (new, entered) = jax.lax.scan(
        chunk, s, tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, k_out, carried)))
    new, entered = jnp.moveaxis(new, 0, 2), jnp.moveaxis(entered, 0, 2)
    out = (_mm("bhrck,bhrkv->bhrcv", q_in, entered)
           + _mm("bhrcj,bhrjv->bhrcv", b.astype(dt), new))
    return out.astype(v.dtype), s


def _forward(q, k, v, g, beta, sub):
    """Grouped operands ``[G, B, H, R, C, ...]`` -> outputs in the same
    layout and the state that entered each group ``[G, B, H, d_k, d_v]``."""
    def step(s, xs):
        out, s_next = _group(s, *xs, sub)
        return s_next, (out, s)

    s0 = jnp.zeros((*q.shape[1:3], q.shape[-1], v.shape[-1]), jnp.float32)
    with jax.named_scope(trace.SCOPE_KDA_SCAN):
        _, (out, states) = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return out, states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, sub):
    return _forward(q, k, v, g, beta, sub)[0]


def _fwd_rule(q, k, v, g, beta, sub):
    out, states = _forward(q, k, v, g, beta, sub)
    # kept by a rematerialised block (ops/remat.py), whose second forward
    # then runs no scan; the operands are remade from the kept projections
    out, states = remat.keep(remat.KDA_OUT, out), remat.keep(remat.KDA_STATES, states)
    return out, (q, k, v, g, beta, states)


def _bwd_rule(sub, res, d_out):
    *operands, states = res

    def step(d_s, xs):
        s_in, d_o, *group = xs
        _, pull = jax.vjp(lambda s, *ops: _group(s, *ops, sub), s_in, *group)
        d_s, *d_group = pull((d_o, d_s))
        return d_s, tuple(d_group)

    with jax.named_scope(trace.SCOPE_KDA_SCAN):
        _, grads = jax.lax.scan(step, jnp.zeros_like(states[0]), (states, d_out, *operands),
                                reverse=True)
    return grads


_scan.defvjp(_fwd_rule, _bwd_rule)


def kda(q, k, v, g, beta, *, chunk: int = CHUNK):
    """The recurrence in chunks of ``chunk`` tokens: ``q``, ``k`` ``[B, H, T,
    d_k]`` (already normalised and scaled), ``v`` ``[B, H, T, d_v]``, ``g``
    ``[B, H, T, d_k]`` log-decays (<= 0), ``beta`` ``[B, H, T]`` -> ``[B, H, T,
    d_v]`` in ``v``'s dtype. No initial state: a sequence starts at zero. T
    need not be a multiple of the chunk (padded tokens write and decay
    nothing)."""
    b, h, t, d_k = q.shape
    sub = math.gcd(chunk, SUB)
    chunks = -(-t // chunk)
    per_group = min(max(GROUP // chunk, 1), chunks)
    groups = -(-chunks // per_group)
    trace.program_note("kda/call", impl="xla", chunk=chunk, chunks=chunks, heads=h, d_k=d_k,
                       d_v=v.shape[-1], t=t)
    pad = groups * per_group * chunk - t

    def grouped(x):  # [B, H, T, ...] -> [G, B, H, R, C, ...]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        return jnp.moveaxis(x.reshape(b, h, groups, per_group, chunk, *x.shape[3:]), 2, 0)

    out = _scan(grouped(q), grouped(k), grouped(v), grouped(g.astype(jnp.float32)),
                grouped(beta.astype(jnp.float32)), sub)
    return jnp.moveaxis(out, 0, 2).reshape(b, h, -1, v.shape[-1])[:, :, :t]
