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
over sub-blocks of ``SUB`` rows. Rows in *different* sub-blocks meet on the
MXU, a level a product from the chunk's halves down to pairs of sub-blocks:
a level's second blocks' rows against its first blocks' columns, both
factors taken against the second block's first row (``exp(gamma_i - rho) *
exp(rho - gamma_j)``, each <= 1; ``rho`` cancels, so it bears no gradient).
Rows of one sub-block meet on the VPU in float32, a diagonal at a time (a
row against the row ``d`` before it, one lane reduction), the mask applied to
the exponent and not to its ``exp`` (an ``inf`` selected away would still put a
NaN in a gradient). The unit triangular system is inverted in float32:
blocks of ``ELIMINATED`` rows on the diagonal by elimination on the VPU, a
column a step, then blocks that double (``[[P, 0], [X, Q]]^-1 = [[P^-1, 0],
[-Q^-1 X P^-1, Q^-1]]``: no power of the system is formed, so nothing grows
that the inverse does not hold). The log-decays are cumulated by float32
adds (shifts that double). The state, the cumulated log-decays and the solve
are float32 whatever the products' dtype, and every float32 product asks
Mosaic for ``contract_precision<fp32>``.

The program is two Mosaic kernels, ``kda_fwd`` and ``kda_bwd``
(``trace.KDA_FWD_KERNEL_NAME`` / ``KDA_BWD_KERNEL_NAME``; interpreted on the
CPU). A grid step holds a group of ``GROUP`` tokens of one head in VMEM; the
group axis is sequential and carries the state, transposed (``[d_v, d_k]``: a
key channel's decay is a lane's), in scratch, so the walk over chunks is
inside and only the five operands, the output and one state a group (67 MB
a layer at T 8192) cross HBM. A group's chunks' local quantities are formed
side by side (arrays ``[R, C, ...]``), so that one chunk's waits on the MXU
and on lane reductions are another's work; only the walk takes the chunks in
turn. ``kda_bwd`` (under ``jax.custom_vjp``) takes the groups last to first: it
forms a group's local quantities and walks it again from its entering
state, walks back over its chunks carrying the state's cotangent, then
forms the chunks' five gradients by hand, side by side (the inverse's
cotangent is ``-T^T dT T^T`` below the diagonal, the cumulated sum's a
cumulated sum reversed). Under a
rematerialised block the output and the groups' states are kept by name
(``ops/remat.py``: ``kda/out``, ``kda/states``) and the block's second forward
runs no kernel. Mosaic tiles every shape the repo calls with (``d_k`` 16 to
128); a chunk that is no power of two of 16 rows or more is refused on
every backend.

Scope ``attn/kda/scan`` (``obs/trace.py``) is round both kernels' calls, so
their custom calls' ``op_name`` holds it; every call leaves a ``kda/call``
program note.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat
from fedml_tpu.ops.attention import _interpret_on

HI = jax.lax.Precision.HIGHEST
CHUNK = 64  # tokens a chunk: the one rule (PERF.md section 6, PR 38)
SUB = 8  # rows of a sub-block of A and B: a float32 sublane tile
ELIMINATED = 16  # rows of a diagonal block of the triangular system inverted on the VPU
# tokens a grid step, and between kept states: a state a chunk would be 268 MB
# a layer at T 8192 where a state a group of 256 is 67 (PERF.md section 6, PR 39)
GROUP = 256


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


# ---------------------------------------------------------------------------
# Mosaic kernels. A grid step holds a group of chunks of one head in VMEM and
# walks them in order; the state is carried transposed (``[d_v, d_k]``, so a
# key channel's decay is a lane's) in scratch over the sequential group axis.
# ---------------------------------------------------------------------------

NEG = -1e30  # a masked exponent: its exp is 0, and no inf enters the arithmetic


def _dot(a, b, form):
    """``a @ b`` (``nn``), ``a @ b.T`` (``nt``) or ``a.T @ b`` (``tn``) over the
    last two axes, the leading one a batch: in the operands' dtype with
    float32 accumulation; float32 operands at ``highest``, which Mosaic gives
    only when asked (``contract_precision<fp32>``)."""
    lead = a.ndim - 2
    lhs, rhs = {"nn": (1, 0), "nt": (1, 1), "tn": (0, 0)}[form]
    batch = tuple(range(lead))
    return jax.lax.dot_general(a, b, (((lead + lhs,), (lead + rhs,)), (batch, batch)),
                               preferred_element_type=jnp.float32,
                               precision=HI if a.dtype == jnp.float32 else None)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _square(c, sub):
    """Where an entry of a ``[C, C]`` square lies, as the masks are cut from
    it by one comparison each: ``(rows - columns, rows ^ columns, rows, rows'
    place in their sub-block)``. Blocks are powers of two, so two indices
    share a block of ``n`` rows iff their exclusive-or is under ``n``."""
    ri, ci, row = _iota((c, c), 0), _iota((c, c), 1), _iota((c, 1), 0)
    return ri - ci, ri ^ ci, row, row & (sub - 1)


def _shifted(x, d):
    """Row ``t`` of every chunk holds ``x[t - d]`` (the first ``d`` rows wrap:
    callers mask); a negative ``d`` shifts back."""
    return pltpu.roll(x, d % x.shape[-2], x.ndim - 2) if d else x


def _levels(c, sub):
    """Half-widths of the blocks whose lower-left quarters go through the
    MXU: from the chunk's halves down to pairs of sub-blocks."""
    out, h = [], c // 2
    while h >= sub:
        out.append(h)
        h //= 2
    return out


def _far_factors(gamma, h):
    """``(rows, cols)`` ``[R, C, d_k]`` of a level of ``h``-row blocks, taken
    in pairs against the cumulated log-decay ``rho`` of a pair's second
    block's first row (later than every row of the first block, no later
    than any of the second): ``exp(gamma_i - rho)`` on the second block and 0
    on the first, ``exp(rho - gamma_j)`` on the first and 0 on the second:
    every exponent <= 0."""
    r, c, d_k = gamma.shape
    second = _iota((c, 1), 0) & h != 0
    rho = jnp.concatenate([jnp.broadcast_to(gamma[:, p + h:p + h + 1], (r, 2 * h, d_k))
                           for p in range(0, c, 2 * h)], axis=1)
    f = jnp.exp(jnp.where(second, gamma - rho, rho - gamma))
    rows = jnp.where(second, f, 0.0)
    return rows, f - rows


def _cumulated(x, row, reverse=False):
    """The sum over a chunk's rows up to and including each row (``reverse``:
    from each row on), by shifts that double: float32 adds on the VPU."""
    c, s = x.shape[-2], 1
    while s < c:
        if reverse:
            x = x + jnp.where(row < c - s, _shifted(x, -s), 0.0)
        else:
            x = x + jnp.where(row >= s, _shifted(x, s), 0.0)
        s *= 2
    return x


def _near(kf, gamma, d, pos):
    """Diagonal ``d`` of a sub-block: ``(k[t - d] * decay, decay)``, ``decay`` =
    ``exp(gamma[t] - gamma[t - d])`` on the rows that have a row ``d`` before
    them in their sub-block and 0 on the others (the mask is on the
    exponent)."""
    decay = jnp.exp(jnp.where(pos >= d, gamma - _shifted(gamma, d), NEG))
    return _shifted(kf, d) * decay, decay


def _local(q, k, v, g, beta_row, square, sub):
    """The local quantities of a group's ``R`` chunks from their operands
    (``q``, ``k`` ``[R, C, d_k]``, ``v`` ``[R, C, d_v]`` in the products' dtype,
    ``g`` ``[R, C, d_k]`` float32, ``beta_row`` ``[R, 1, C]`` float32), as values
    in VMEM, the chunks side by side so that one's waits are another's work:
    ``gamma``, ``beta`` as a column, ``A`` (strictly lower), ``B`` (lower), ``(I
    + Diag(beta) A)^-1`` and its product with ``Diag(beta) [V | K+]``, all
    float32. Pairs of rows in different sub-blocks meet on the MXU, level by
    level (:func:`_far_factors`); pairs inside a sub-block are formed a
    diagonal at a time on the VPU in float32, a row against the row ``d``
    before it."""
    dt, f32 = q.dtype, jnp.float32
    r, c = q.shape[:2]
    qf, kf = q.astype(f32), k.astype(f32)
    below, apart, row, pos = square
    gamma = _cumulated(g, row)  # inclusive, <= 0
    beta = jnp.sum(jnp.where(below == 0, beta_row, 0.0), axis=2, keepdims=True)  # [R, C, 1]
    a = b = jnp.zeros((r, c, c), f32)
    for h in _levels(c, sub):
        rows, cols = _far_factors(gamma, h)
        prod = _dot(jnp.concatenate([kf * rows, qf * rows], axis=1).astype(dt),
                    (kf * cols).astype(dt), "nt")  # [R, 2 C, C]
        same = apart < 2 * h
        a, b = a + jnp.where(same, prod[:, :c], 0.0), b + jnp.where(same, prod[:, c:], 0.0)
    for d in range(sub):
        earlier, _ = _near(kf, gamma, d, pos)
        on = below == d
        b = b + jnp.where(on, jnp.sum(qf * earlier, axis=2, keepdims=True), 0.0)
        if d:
            a = a + jnp.where(on, jnp.sum(kf * earlier, axis=2, keepdims=True), 0.0)
    low = beta * a
    # (I + low)^-1: blocks of ELIMINATED rows on the diagonal by elimination, a
    # column a step, then blocks that double (no power of low is formed)
    solve = jnp.broadcast_to(jnp.where(below == 0, 1.0, 0.0), (r, c, c))
    size = min(ELIMINATED, c)
    place = row & (size - 1)
    inside = jnp.where(apart < size, low, 0.0)
    for s in range(size - 1):
        col = jnp.sum(jnp.where(below == place - s, inside, 0.0), axis=2, keepdims=True)
        src = jnp.broadcast_to(solve.reshape(r, c // size, size, c)[:, :, s:s + 1],
                               (r, c // size, size, c)).reshape(r, c, c)
        solve = solve - jnp.where(place > s, col * src, 0.0)
    while size < c:
        quarter = jnp.where((apart >= size) & (apart < 2 * size), low, 0.0)
        solve = solve - _dot(_dot(solve, quarter, "nn"), solve, "nn")
        size *= 2
    y = jnp.concatenate([v.astype(f32), kf * jnp.exp(gamma)], axis=2)
    return gamma, beta, a, b, solve, y, _dot(solve, beta * y, "nn")


def _factors(q, k, gamma, uw, d_v):
    """What the walk multiplies, in the products' dtype: the solved keys
    ``w``, ``q e^gamma``, ``k e^(gamma_C - gamma)``; and what is left of a state
    across a chunk, ``e^gamma_C`` ``[R, 1, d_k]`` float32."""
    dt, f32 = q.dtype, jnp.float32
    last = gamma[:, gamma.shape[1] - 1:]
    return (uw[:, :, d_v:].astype(dt), (q.astype(f32) * jnp.exp(gamma)).astype(dt),
            (k.astype(f32) * jnp.exp(last - gamma)).astype(dt), jnp.exp(last))


def _walk(u, w, q_in, k_out, carried, b, st):
    """One chunk of the walk from the transposed state ``st`` ``[d_v, d_k]``
    float32 that enters: the chunk's writes and outputs, the state that
    leaves, and the entering state as the products saw it."""
    c = u.shape[0]
    entered = st.astype(w.dtype)
    both = _dot(jnp.concatenate([w, q_in], axis=0), entered, "nt")
    new = (u - both[:c]).astype(w.dtype)
    out = both[c:] + _dot(b, new, "nn")
    return new, out, carried * st + _dot(new, k_out, "tn"), entered


def _chunks(ref, chunk):
    """A group's rows ``[R C, d]`` as its chunks ``[R, C, d]``."""
    return ref[...].reshape(-1, chunk, ref.shape[-1])


def _prepared(q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, sub):
    """A group's chunks from its blocks: ``(q, k, square, what _local made,
    what _walk takes of each chunk: u, w, q_in, k_out, carried, b)``."""
    q, k, v = (_chunks(ref, chunk) for ref in (q_ref, k_ref, v_ref))
    d_v = v.shape[2]
    square = _square(chunk, sub)
    local = _local(q, k, v, _chunks(g_ref, chunk), beta_ref[...], square, sub)
    gamma, _, _, b, _, _, uw = local
    walked = (uw[:, :, :d_v], *_factors(q, k, gamma, uw, d_v), b.astype(q.dtype))
    return q, k, square, local, walked


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, states_ref, st_ref, *, chunk,
                sub):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st = st_ref[...]
    states_ref[...] = st
    q, *_, walked = _prepared(q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, sub)
    for r in range(q.shape[0]):
        _, out, st, _ = _walk(*(x[r] for x in walked), st)
        out_ref[r * chunk:(r + 1) * chunk, :] = out.astype(out_ref.dtype)
    st_ref[...] = st


def _walk_bwd(d_out, new, entered, st, w, q_in, k_out, carried, b, d_st):
    """:func:`_walk` backward for one chunk, from ``d_out`` ``[C, d_v]`` and
    the cotangent ``d_st`` of the state that left: the cotangents of ``[u |
    w]``, ``B``, ``q e^gamma``, ``k e^(gamma_C - gamma)`` and ``gamma_C`` (through
    ``e^gamma_C``), and of the state that entered. Every product meets the MXU
    in the dtype it met it in forward."""
    dt = w.dtype
    d_st_dt = d_st.astype(dt)
    d_new = _dot(b, d_out, "tn") + _dot(k_out, d_st_dt, "nt")  # [C, d_v]
    d_new_dt = d_new.astype(dt)
    d_uw = jnp.concatenate([d_new, -_dot(d_new_dt, entered, "nn")], axis=1)
    d_last = jnp.sum(d_st * st, axis=0, keepdims=True) * carried  # [1, d_k]
    return (d_uw, _dot(d_out, new, "nt"), _dot(d_out, entered, "nn"), _dot(new, d_st_dt, "nn"),
            d_last, carried * d_st + _dot(d_out, q_in, "tn") - _dot(d_new_dt, w, "tn"))


def _local_bwd(q, k, gamma, beta, a, solve, y, d_uw, d_b, d_q_in, d_k_out, d_last,
               square, sub):
    """The five gradients of a group's chunks from the cotangents
    :func:`_walk_bwd` left and what :func:`_local` made, the chunks side by
    side. The inverse's cotangent is ``-T^T dT T^T`` below the diagonal; the
    cumulated sum's is a cumulated sum reversed; the anchors of
    :func:`_far_factors` cancel in every entry, so they bear no gradient."""
    dt, f32 = q.dtype, jnp.float32
    r, c = q.shape[:2]
    d_v = y.shape[2] - q.shape[2]
    qf, kf = q.astype(f32), k.astype(f32)
    below, apart, row, pos = square
    d_b = jnp.where(below >= 0, d_b, 0.0)
    # uw = T Diag(beta) y, T = (I + Diag(beta) A)^-1, y = [V | K+]
    d_y = _dot(solve, d_uw, "tn")
    d_solve = _dot(d_uw, beta * y, "nt")
    d_low = jnp.where(below > 0, -_dot(_dot(solve, d_solve, "tn"), solve, "nt"), 0.0)
    d_a = beta * d_low
    d_beta = (jnp.sum(d_low * a, axis=2, keepdims=True)
              + jnp.sum(d_y * y, axis=2, keepdims=True))
    d_y = beta * d_y
    d_k_in = d_y[:, :, d_v:]
    # the decayed factors: q e^gamma, K+ = k e^gamma, k e^(gamma_C - gamma)
    decayed = jnp.exp(gamma)
    left = jnp.exp(gamma[:, c - 1:] - gamma)
    d_q = d_q_in * decayed
    d_k = d_k_in * decayed + d_k_out * left
    through_left = d_k_out * kf * left
    d_gamma = (d_q_in * qf + d_k_in * kf) * decayed - through_left
    d_last = d_last + jnp.sum(through_left, axis=1, keepdims=True)
    # A and B, rows in different sub-blocks
    for h in _levels(c, sub):
        rows, cols = _far_factors(gamma, h)
        same = apart < 2 * h
        d_ab = jnp.concatenate([jnp.where(same, d_a, 0.0), jnp.where(same, d_b, 0.0)],
                               axis=1).astype(dt)  # [R, 2 C, C]
        kq_rows = jnp.concatenate([kf * rows, qf * rows], axis=1)
        k_cols = kf * cols
        d_rows = _dot(d_ab, k_cols.astype(dt), "nn")  # [R, 2 C, d_k]
        d_cols = _dot(d_ab, kq_rows.astype(dt), "tn")  # [R, C, d_k]
        d_k = d_k + d_rows[:, :c] * rows + d_cols * cols
        d_q = d_q + d_rows[:, c:] * rows
        through = d_rows * kq_rows
        d_gamma = d_gamma + through[:, :c] + through[:, c:] - d_cols * k_cols
    # ... and inside one, a diagonal at a time
    for d in range(sub):
        earlier, decay = _near(kf, gamma, d, pos)
        on = below == d
        from_b = jnp.sum(jnp.where(on, d_b, 0.0), axis=2, keepdims=True)
        later = from_b * qf  # what the row d before receives, before its decay
        d_q = d_q + from_b * earlier
        if d:
            from_a = jnp.sum(jnp.where(on, d_a, 0.0), axis=2, keepdims=True)
            later = later + from_a * kf
            d_k = d_k + from_a * earlier
            through = later * earlier
            d_gamma = d_gamma + through - _shifted(through, -d)
        d_k = d_k + _shifted(later * decay, -d)
    d_gamma = d_gamma + jnp.where(row == c - 1, d_last, 0.0)
    d_g = _cumulated(d_gamma, row, reverse=True)
    d_beta_row = jnp.sum(jnp.where(below == 0, d_beta, 0.0), axis=1, keepdims=True)  # [R, 1, C]
    return d_q, d_k, d_y[:, :, :d_v], d_g, d_beta_row


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, d_out_ref, d_q_ref, d_k_ref,
                d_v_ref, d_g_ref, d_beta_ref, d_st_ref, *, chunk, sub):
    """A group of chunks, groups last to first: the forward again from the
    group's entering state, the walk back over its chunks, last to first,
    carrying the state's cotangent, then the chunks' gradients side by side."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        d_st_ref[...] = jnp.zeros_like(d_st_ref)

    q, k, square, (gamma, beta, a, _, solve, y, _), walked = _prepared(
        q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, sub)
    d_out = _chunks(d_out_ref, chunk).astype(q.dtype)
    st, seen = states_ref[...], []
    for r in range(q.shape[0]):
        new, _, st_next, entered = _walk(*(x[r] for x in walked), st)
        seen.append((new, entered, st))
        st = st_next
    d_st, back = d_st_ref[...], []
    for r in reversed(range(q.shape[0])):
        *cotangents, d_st = _walk_bwd(d_out[r], *seen[r], *(x[r] for x in walked[1:]), d_st)
        back.append(cotangents)
    d_st_ref[...] = d_st
    grads = _local_bwd(q, k, gamma, beta, a, solve, y,
                       *(jnp.stack(x) for x in zip(*reversed(back))), square, sub)
    for ref, x in zip((d_q_ref, d_k_ref, d_v_ref, d_g_ref), grads):
        ref[...] = x.reshape(ref.shape).astype(ref.dtype)
    d_beta_ref[...] = grads[4]


def _specs(per_group, chunk, d_k, d_v, reverse=0):
    """BlockSpecs of a group's rows of a head: operands ``[B H, T, d]``, beta
    ``[B H, chunks, 1, C]`` (a chunk's row), states ``[B H, G, d_v, d_k]``;
    the ``reverse`` groups last to first."""
    at = (lambda j: reverse - 1 - j) if reverse else (lambda j: j)
    rows = lambda d: pl.BlockSpec((None, per_group * chunk, d), lambda i, j: (i, at(j), 0))  # noqa: E731
    beta = pl.BlockSpec((None, per_group, 1, chunk), lambda i, j: (i, at(j), 0, 0))
    state = pl.BlockSpec((None, None, d_v, d_k), lambda i, j: (i, at(j), 0, 0))
    return rows, beta, state


def _kernel(body, name, chunk, d_k, d_v):
    """What both ``pallas_call``s share: the kernel with its statics, its
    name in the trace, the carried ``[d_v, d_k]`` float32 scratch, heads in
    parallel over groups in sequence."""
    return dict(
        kernel=functools.partial(body, chunk=chunk, sub=math.gcd(chunk, SUB)), name=name,
        scratch_shapes=[pltpu.VMEM((d_v, d_k), jnp.float32)],
        interpret=_interpret_on(jax.default_backend()),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")))


def _forward(q, k, v, g, beta, chunk, per_group):
    """Operands ``[N, T, d]`` (``N`` = B x H, ``T`` whole groups), ``beta``
    ``[N, chunks, 1, C]`` -> outputs ``[N, T, d_v]`` and the transposed state
    that entered each group ``[N, G, d_v, d_k]`` float32."""
    n, t, d_k = q.shape
    d_v = v.shape[-1]
    groups = t // (per_group * chunk)
    rows, beta_spec, state = _specs(per_group, chunk, d_k, d_v)
    with jax.named_scope(trace.SCOPE_KDA_SCAN):
        return pl.pallas_call(
            grid=(n, groups),
            in_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), beta_spec],
            out_specs=[rows(d_v), state],
            out_shape=[jax.ShapeDtypeStruct((n, t, d_v), v.dtype),
                       jax.ShapeDtypeStruct((n, groups, d_v, d_k), jnp.float32)],
            **_kernel(_fwd_kernel, trace.KDA_FWD_KERNEL_NAME, chunk, d_k, d_v),
        )(q, k, v, g, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, v, g, beta, chunk, per_group):
    return _forward(q, k, v, g, beta, chunk, per_group)[0]


def _fwd_rule(q, k, v, g, beta, chunk, per_group):
    out, states = _forward(q, k, v, g, beta, chunk, per_group)
    # kept by a rematerialised block (ops/remat.py), whose second forward
    # then runs no scan; the operands are remade from the kept projections
    out, states = remat.keep(remat.KDA_OUT, out), remat.keep(remat.KDA_STATES, states)
    return out, (q, k, v, g, beta, states)


def _bwd_rule(chunk, per_group, res, d_out):
    q, k, v, g, beta, states = res
    n, d_k, d_v = q.shape[0], q.shape[-1], v.shape[-1]
    groups = states.shape[1]
    rows, beta_spec, state = _specs(per_group, chunk, d_k, d_v, reverse=groups)
    with jax.named_scope(trace.SCOPE_KDA_SCAN):
        return tuple(pl.pallas_call(
            grid=(n, groups),
            in_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), beta_spec, state, rows(d_v)],
            out_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), beta_spec],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)],
            **_kernel(_bwd_kernel, trace.KDA_BWD_KERNEL_NAME, chunk, d_k, d_v),
        )(q, k, v, g, beta, states, d_out))


_scan.defvjp(_fwd_rule, _bwd_rule)


def kda(q, k, v, g, beta, *, chunk: int = CHUNK):
    """The recurrence in chunks of ``chunk`` tokens: ``q``, ``k`` ``[B, H, T,
    d_k]`` (already normalised and scaled), ``v`` ``[B, H, T, d_v]``, ``g``
    ``[B, H, T, d_k]`` log-decays (<= 0), ``beta`` ``[B, H, T]`` -> ``[B, H, T,
    d_v]`` in ``v``'s dtype. No initial state: a sequence starts at zero. T
    need not be a multiple of the chunk (padded tokens write and decay
    nothing)."""
    b, h, t, d_k = q.shape
    if chunk < 16 or chunk & (chunk - 1):
        raise ValueError(
            f"kda: a chunk of {chunk} tokens is not a power of two of at least 16: the kernels "
            "halve it down to sub-blocks, and 16 rows are bfloat16's sublane tile")
    chunks = -(-t // chunk)
    per_group = min(max(GROUP // chunk, 1), chunks)
    pad = -(-chunks // per_group) * per_group * chunk - t
    trace.program_note("kda/call", impl=trace.KDA_FWD_KERNEL_NAME, chunk=chunk, chunks=chunks,
                       heads=h, d_k=d_k, d_v=v.shape[-1], t=t)

    def rows(x):  # [B, H, T, ...] -> [B H, whole groups, ...]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        return x.reshape(b * h, *x.shape[2:])

    out = _scan(rows(q), rows(k), rows(v), rows(g.astype(jnp.float32)),
                rows(beta.astype(jnp.float32)).reshape(b * h, -1, 1, chunk), chunk, per_group)
    return out.reshape(b, h, -1, v.shape[-1])[:, :, :t]
