"""Routed experts for one chip's share of an expert-parallel layer.

The layer is told which experts it holds (``first``, ``count``). It routes
every token over *all* the router's outputs, computes its own experts' part
of the result for the tokens routed to them, dropping none, and returns that
partial sum; what the absent experts would add is another chip's to compute
and an exchange's to add, and on one chip nothing stands in for either.

Every shape is static. The ``T x k`` assignments are sorted by held expert
(the absent ones last), so a held expert's rows are contiguous and the
grouped matrix products (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm``
forward, ``gmm`` + ``tgmm`` backward) visit only the row tiles that hold an
assignment; rows past the last held assignment are never written, and never
read except through a select. The moves between token order and sorted order
are gathers in both directions (each one's backward is the other), and the
moves into sorted order run over whole chunks of used rows only, so the work
follows the number of assignments held through whole tiles and chunks and
through nothing else.

The router scores as the configuration states (:func:`route`): a softmax
over the chosen logits, or sigmoid scores chosen under a selection bias,
normalised over the chosen and scaled. The experts' gate activation is the
configuration's too (ReLU unless told).

Scopes (``obs/trace.py`` ``MOE_SCOPES``): ``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat
from fedml_tpu.ops.attention import _interpret_on

HI = jax.lax.Precision.HIGHEST
GATHER_CHUNK = 2048  # rows a step of the moves into sorted order
GMM_TILES = (512, 1280, 1280)  # caps of the grouped products' tiles: see gmm_tiling


@jax.named_scope(trace.SCOPE_MOE_ROUTE)
def route(x, router_kernel, top_k: int, *, select_bias=None, scale: float = 1.0):
    """``(expert ids [T, k] int32, weights [T, k] f32)`` from the router's
    logits over all its outputs, all in float32 whatever ``x`` is.

    Without ``select_bias``: the ``top_k`` largest logits and a softmax over
    those alone. With one (``[outputs]``; the "noaux_tc" router of one
    group): the scores are ``sigmoid(logits)``, the ``top_k`` largest of
    ``scores + select_bias`` are chosen, and the weights are the chosen
    *scores* over their sum, times ``scale``. The bias chooses and never
    weighs, and no gradient reaches it: upstream moves it by a load-balancing
    rule outside the gradient."""
    logits = jnp.dot(x.astype(jnp.float32), router_kernel.astype(jnp.float32), precision=HI)
    if select_bias is None:
        top, ids = jax.lax.top_k(logits, top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(
            jax.lax.stop_gradient(scores + select_bias.astype(jnp.float32)), top_k)
    # a rematerialised block chooses once (ops/remat.py): what follows reads
    # the kept ids, as the kept sorted layout was made from them
    ids = remat.keep(remat.MOE_IDS, ids.astype(jnp.int32))
    if select_bias is None:
        return ids, jax.nn.softmax(top, axis=-1)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def sorted_layout(ids, first: int, count: int):
    """Where each assignment goes once sorted by held expert.

    ``ids`` [T, k] are the router's choices over all experts. Returns
    ``held`` [T, k] (the assignment is to one of ``first ... first + count -
    1``), ``pos`` [T, k] (its row in sorted order; rows of absent experts'
    assignments lie past every held one), ``order`` [T * k] (the assignment
    ``t * k + j`` of each sorted row) and ``sizes`` [count] (rows of each
    held expert, in order). The sort is stable, so a held expert's rows keep
    token order."""
    t, k = ids.shape
    local = ids - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32).reshape(t, k)  # the inverse permutation
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=key.dtype), axis=0, dtype=jnp.int32)
    return (held, remat.keep(remat.MOE_POS, pos), remat.keep(remat.MOE_ORDER, order),
            remat.keep(remat.MOE_SIZES, sizes))


def _over_used_chunks(chunk_fn, n_used, outs):
    """Fill ``outs`` (zero buffers of ``m`` rows each) chunk by chunk with
    ``chunk_fn(lo, chunk)`` over the whole chunks that hold a used row
    (``row < n_used``); later chunks stay zero. One loop whose trip count is
    the number of used chunks."""
    chunk = _divisor(outs[0].shape[0], GATHER_CHUNK)

    def body(i, bufs):
        lo = i * chunk
        return tuple(
            jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), (lo,) + (0,) * (buf.ndim - 1))
            for buf, new in zip(bufs, chunk_fn(lo, chunk)))

    return jax.lax.fori_loop(0, (n_used + chunk - 1) // chunk, body, tuple(outs))


def _rows_of(x, order, k, lo, chunk):
    """``x[token of sorted row r]`` for ``r`` in ``[lo, lo + chunk)``."""
    tokens = jax.lax.dynamic_slice(order, (lo,), (chunk,)) // k
    return jnp.take(x, tokens, axis=0, mode="clip")


def _from_rows(rows, pos, held, weights=None):
    """``out[t] = sum over j held of (weights[t, j] *) rows[pos[t, j]]``, in
    float32: a gather per choice. Rows of assignments not held are selected
    away, never multiplied, because nothing ever wrote them."""
    out = jnp.zeros((pos.shape[0], rows.shape[1]), jnp.float32)
    for j in range(pos.shape[1]):
        picked = jnp.take(rows, pos[:, j], axis=0, mode="clip").astype(jnp.float32)
        if weights is not None:
            picked = picked * weights[:, j, None]
        out = out + jnp.where(held[:, j, None], picked, 0.0)
    return out


@jax.custom_vjp
def dispatch(x, layout):
    """Tokens ``x`` [T, D] into sorted order [T * k, D]; rows past the used
    chunks are zero. ``layout`` = (held, pos, order, n_used)."""
    _, pos, order, n_used = layout
    k = pos.shape[1]
    (rows,) = _over_used_chunks(
        lambda lo, chunk: (_rows_of(x, order, k, lo, chunk),), n_used,
        [jnp.zeros((order.shape[0], x.shape[1]), x.dtype)])
    return rows


def _dispatch_fwd(x, layout):
    return dispatch(x, layout), layout


@jax.named_scope(trace.SCOPE_MOE_DISPATCH)
def _dispatch_bwd(layout, g):
    held, pos, _, _ = layout
    return _from_rows(g, pos, held).astype(g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, layout):
    """Sorted rows [T * k, D] back to tokens: ``out[t]`` is the weighted sum
    of token ``t``'s held assignments' rows, float32."""
    held, pos, _, _ = layout
    return _from_rows(rows, pos, held, weights)


def _combine_fwd(rows, weights, layout):
    return combine(rows, weights, layout), (rows, weights, layout)


@jax.named_scope(trace.SCOPE_MOE_COMBINE)
def _combine_bwd(res, g):
    rows, weights, (held, pos, order, n_used) = res
    k = weights.shape[1]
    g = g.astype(rows.dtype)
    row_weight = jnp.take(jnp.where(held, weights, 0.0).reshape(-1), order, mode="clip")

    def chunk_fn(lo, chunk):
        g_rows = _rows_of(g, order, k, lo, chunk).astype(jnp.float32)  # dL/d(out[token of row])
        mine = jax.lax.dynamic_slice(rows, (lo, 0), (chunk, rows.shape[1])).astype(jnp.float32)
        w = jax.lax.dynamic_slice(row_weight, (lo,), (chunk,))
        # dL/d(row), and dL/d(the row's weight) = <dL/d(out[t]), the row>
        return g_rows * w[:, None], jnp.sum(g_rows * mine, axis=-1)

    d_rows, dots = _over_used_chunks(
        chunk_fn, n_used, [jnp.zeros_like(rows), jnp.zeros((rows.shape[0],), jnp.float32)])
    d_weights = jnp.where(held, jnp.take(dots, pos.reshape(-1), mode="clip").reshape(-1, k), 0.0)
    return d_rows, d_weights.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


def _divisor(x: int, cap: int, multiple: int = 1) -> int:
    """Largest divisor of ``x`` not above ``cap`` that is a multiple of
    ``multiple``; ``min(x, cap)`` where there is none."""
    for d in range(min(x, cap), 0, -1):
        if x % d == 0 and d % multiple == 0:
            return d
    return min(x, cap)


def gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles (rows, contraction, columns) of a grouped product, looked up by
    megablox from each product's own sizes, forward and backward: the rows
    tile must divide ``m``; the other two are the whole axis up to
    ``GMM_TILES`` and else its largest lane-aligned divisor."""
    return (_divisor(m, GMM_TILES[0]), _divisor(k, GMM_TILES[1], 128 if k > GMM_TILES[1] else 1),
            _divisor(n, GMM_TILES[2], 128 if n > GMM_TILES[2] else 1))


def _gmm(lhs, rhs, sizes, interpret):
    return gmm(lhs, rhs, sizes, lhs.dtype, gmm_tiling, None, None, False, interpret)


@jax.named_scope(trace.SCOPE_MOE_EXPERTS)
def glu_experts(rows, gate, up, down, sizes, interpret: bool, activation=jax.nn.relu):
    """``(activation(rows @ gate_e) * (rows @ up_e)) @ down_e`` for each held
    expert ``e`` over its own rows (``sizes``), in ``rows``' dtype with
    float32 accumulation. Rows past ``sum(sizes)`` are not computed."""
    g = remat.keep(remat.MOE_GATE_OUT, _gmm(rows, gate.astype(rows.dtype), sizes, interpret))
    u = remat.keep(remat.MOE_UP_OUT, _gmm(rows, up.astype(rows.dtype), sizes, interpret))
    return _gmm(activation(g) * u, down.astype(rows.dtype), sizes, interpret)


def expert_layer(u, ids, weights, gate, up, down, *, first: int, count: int, dtype,
                 activation=jax.nn.relu):
    """This chip's part of the routed-expert layer.

    ``u`` [T, D] is the layer's normalised input, ``ids`` / ``weights``
    [T, k] the router's choices over all experts (:func:`route`), ``gate`` /
    ``up`` [count, D, F] and ``down`` [count, F, D] the held experts
    ``first ... first + count - 1``, ``activation`` the gate's (ReLU: ReGLU;
    ``jax.nn.silu``: SwiGLU). Returns the partial sum [T, D] float32
    over the held experts and the layer's routing statistics: assignments
    held and the most loaded held expert's rows over the mean."""
    interpret = _interpret_on(jax.default_backend())
    with jax.named_scope(trace.SCOPE_MOE_DISPATCH):
        held, pos, order, sizes = sorted_layout(ids, first, count)
        n_held = jnp.sum(sizes)
        layout = (held, pos, order, n_held)
        rows = dispatch(u.astype(dtype), layout)
    out_rows = glu_experts(rows, gate, up, down, sizes, interpret, activation)
    with jax.named_scope(trace.SCOPE_MOE_COMBINE):
        out = combine(out_rows, weights, layout)
    mean = jnp.maximum(n_held.astype(jnp.float32) / count, 1e-9)
    stats = {"moe/assignments_held": n_held.astype(jnp.float32),
             "moe/load_max_over_mean": jnp.max(sizes).astype(jnp.float32) / mean}
    return out, jax.lax.stop_gradient(stats)
