"""Routed experts for one chip's share of an expert-parallel layer.

The layer is told which experts it holds (``first``, ``count``). It routes
every token over *all* the router's outputs, computes its own experts' part
of the result for the tokens routed to them, dropping none, and returns that
partial sum; what the absent experts would add is another chip's to compute
and an exchange's to add, and on one chip nothing stands in for either.

Every shape is static. The ``T x k`` assignments are sorted by held expert
(the absent ones last), so a held expert's rows are contiguous, and every
buffer in sorted order has ``C`` rows (:func:`capacity`): one and a half
times the even share ``T x k x count / outputs`` in whole row tiles of the
grouped products, never more than ``T x k``. The layer is told the router's
width as it is told ``first`` and ``count`` (:func:`router_width`); no option
sets ``C``. Over those ``C`` rows run the moves between token order and
sorted order (gathers in both directions, each one's backward the other, the
moves into sorted order over whole chunks of used rows only), the grouped
matrix products (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm``
forward, ``gmm`` + ``tgmm`` backward, visiting only the row tiles that hold
an assignment) and what a rematerialised block keeps of them
(``moe/gate_out``, ``moe/up_out``: ``[C, F]``). Rows past the last used one
are never written, and never read except through a select. Only the int32
layout (``pos``, ``order``), the two sorts that make it and the router walk
all ``T x k`` assignments.

A step whose held rows pass ``C`` is finished exactly: the assignments in
sorted rows ``[C, n_held)`` are computed by a loop in plain XLA, a tile of
one expert's rows a trip (three dots in the grouped products' arithmetic,
their rows added into the same float32 result), and the mirror loop adds
their gradients into the straight-line pass's own. The layer has one
differentiation rule for both (:func:`_held_rows`), so a step with no
overflow runs two loops of no trips and allocates nothing for them, and the
program holds the Mosaic kernels once: nothing is dropped, nothing is
approximated, and no kernel is there twice.

The router scores as the configuration states (:func:`route`): a softmax
over the chosen logits, or sigmoid scores chosen under a selection bias,
normalised over the chosen and scaled. The experts' gate activation is the
configuration's too (ReLU unless told).

Scopes (``obs/trace.py`` ``MOE_SCOPES``): ``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat
from fedml_tpu.ops.attention import _interpret_on

HI = jax.lax.Precision.HIGHEST
GATHER_CHUNK = 2048  # rows a step of the moves into sorted order
GMM_TILES = (512, 1280, 1280)  # caps of the grouped products' tiles: see gmm_tiling
# buffer rows over the even share of the assignments held (capacity). What C
# bounds is the sum over the held experts: a block's read 0.70-1.33 of the even
# share at 8 of 256 held and 0.97-1.02 at 16 of 64 over every seed run on the
# chip, where one expert's load reads up to 2.2 of its mean (PERF.md section 6)
CAPACITY_FACTOR = 1.5


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


def capacity(assignments: int, count: int, outputs: int) -> int:
    """Rows ``C`` of every buffer in sorted order, for ``assignments``
    (``T x k``) choices of which ``count`` of the router's ``outputs`` are
    held: ``CAPACITY_FACTOR`` times the even share in whole row tiles of the
    grouped products, and never more than all the assignments (what a layer
    that holds every expert gets)."""
    tile = GMM_TILES[0]
    share = math.ceil(CAPACITY_FACTOR * assignments * count / outputs)
    return min(assignments, -(-share // tile) * tile)


class _Router(threading.local):
    outputs = None  # width of the router whose choices the layer being traced reads


_router = _Router()


@contextlib.contextmanager
def router_width(outputs: int | None):
    """Tells the :func:`expert_layer` calls made inside how many outputs the
    router has, of which they hold ``count`` (None: no more than they hold, as
    outside any such call). The width sizes the buffers and comes this way,
    as ``ops/remat.py``'s depth does, because the call's keywords are held
    by a stand-in of the benchmark's tests (``models/moe_transformer.py``
    ``RoutedExperts``). A layer that holds all it knows of takes its share
    for whole: its buffers hold every assignment."""
    before, _router.outputs = _router.outputs, outputs
    try:
        yield
    finally:
        _router.outputs = before


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


def _from_rows(rows, pos, in_rows, weights=None):
    """``out[t] = sum over j in_rows of (weights[t, j] *) rows[pos[t, j]]``,
    in float32: a gather per choice. Rows of assignments that have no row in
    the buffer (not held, or past its capacity) are selected away, never
    multiplied: the clipped read is of another assignment's row, or of one
    nothing ever wrote."""
    out = jnp.zeros((pos.shape[0], rows.shape[1]), jnp.float32)
    for j in range(pos.shape[1]):
        picked = jnp.take(rows, pos[:, j], axis=0, mode="clip").astype(jnp.float32)
        if weights is not None:
            picked = picked * weights[:, j, None]
        out = out + jnp.where(in_rows[:, j, None], picked, 0.0)
    return out


@jax.custom_vjp
def dispatch(x, layout):
    """Tokens ``x`` [T, D] into sorted order [C, D]; rows past the used
    chunks are zero. ``layout`` = (in_rows [T, k], pos [T, k], order [C],
    n_used): the assignments that have a row among the ``C``, every
    assignment's sorted row, the assignment of each of the ``C`` rows, and
    how many of them are used."""
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
    in_rows, pos, _, _ = layout
    return _from_rows(g, pos, in_rows).astype(g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, layout):
    """Sorted rows [C, D] back to tokens: ``out[t]`` is the weighted sum of
    the rows of token ``t``'s assignments that have one, float32."""
    in_rows, pos, _, _ = layout
    return _from_rows(rows, pos, in_rows, weights)


def _combine_fwd(rows, weights, layout):
    return combine(rows, weights, layout), (rows, weights, layout)


@jax.named_scope(trace.SCOPE_MOE_COMBINE)
def _combine_bwd(res, g):
    rows, weights, (in_rows, pos, order, n_used) = res
    k = weights.shape[1]
    g = g.astype(rows.dtype)
    row_weight = jnp.take(jnp.where(in_rows, weights, 0.0).reshape(-1), order, mode="clip")

    def chunk_fn(lo, chunk):
        g_rows = _rows_of(g, order, k, lo, chunk).astype(jnp.float32)  # dL/d(out[token of row])
        mine = jax.lax.dynamic_slice(rows, (lo, 0), (chunk, rows.shape[1])).astype(jnp.float32)
        w = jax.lax.dynamic_slice(row_weight, (lo,), (chunk,))
        # dL/d(row), and dL/d(the row's weight) = <dL/d(out[t]), the row>
        return g_rows * w[:, None], jnp.sum(g_rows * mine, axis=-1)

    d_rows, dots = _over_used_chunks(
        chunk_fn, n_used, [jnp.zeros_like(rows), jnp.zeros((rows.shape[0],), jnp.float32)])
    d_weights = jnp.where(in_rows, jnp.take(dots, pos.reshape(-1), mode="clip").reshape(-1, k), 0.0)
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


class _Static(NamedTuple):
    """What the layer's rules are compiled for, not differentiated."""

    activation: Callable  # the gate's
    interpret: bool  # the grouped products' kernels (the CPU suite)
    tile: int  # rows of an overflow tile


def _capacity_pass(static, u, weights, gate, up, down, layout, sizes):
    """The straight-line pass over the ``C`` rows of the buffers: tokens into
    sorted order, the grouped products, the weighted rows back, [T, D] f32."""
    with jax.named_scope(trace.SCOPE_MOE_DISPATCH):
        rows = dispatch(u, layout)
    out_rows = glu_experts(rows, gate, up, down, sizes, static.interpret, static.activation)
    with jax.named_scope(trace.SCOPE_MOE_COMBINE):
        return combine(out_rows, weights, layout)


def _overflow_tile(i, over, k, tile):
    """The ``i``-th (held expert, row tile) pair past the capacity: the
    expert, the assignment ``t * k + j`` of each of the tile's sorted rows
    and which of them are that expert's."""
    order, lo, ends, tiles, tiles_to = over
    e = jnp.sum(tiles_to <= i)
    rows = lo[e] + (i - (tiles_to[e] - tiles[e])) * tile + jnp.arange(tile)
    return e, jnp.take(order, rows, mode="clip"), rows < ends[e]


def _tile_rows(static, x, w, gate_e, up_e, down_e):
    """What one expert adds to its tokens' results, ``w * glu(x)`` [tile, D]
    float32, in the arithmetic of the grouped products: operands in ``x``'s
    dtype, float32 accumulation, each product rounded to ``x``'s dtype."""
    def product(a, b):
        return jnp.dot(a, b.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)

    out = product(static.activation(product(x, gate_e)) * product(x, up_e), down_e)
    return w[:, None] * out.astype(jnp.float32)


def _expert(stacks, e):
    return [jax.lax.dynamic_index_in_dim(s, e, keepdims=False) for s in stacks]


@jax.named_scope(trace.SCOPE_MOE_EXPERTS)
def _overflow(static, out, u, weights, gate, up, down, over):
    """``out`` plus the held assignments whose sorted row lies past the
    capacity, a tile of one expert's rows a step, in plain XLA. The loop
    runs as many times as there are such tiles: not once on a step whose
    held rows fit."""
    k, n_tiles = weights.shape[1], over[-1][-1]

    def body(i, out):
        e, assignment, mine = _overflow_tile(i, over, k, static.tile)
        tokens = assignment // k
        w = jnp.where(mine, jnp.take(weights.reshape(-1), assignment), 0.0)
        return out.at[tokens].add(
            _tile_rows(static, jnp.take(u, tokens, axis=0), w, *_expert((gate, up, down), e)))

    return jax.lax.fori_loop(0, n_tiles, body, out)


@jax.named_scope(trace.SCOPE_MOE_EXPERTS)
def _overflow_bwd(static, g, grads, u, weights, gate, up, down, over):
    """The mirror loop: the same tiles, each recomputed from the layout and
    pulled back, added into the straight-line pass's gradients ``grads`` of
    ``u``, ``weights``, ``gate``, ``up``, ``down`` (the loop's carry, so a
    step with no overflow pays for no second buffer)."""
    k, n_tiles = weights.shape[1], over[-1][-1]
    d_u, d_weights, *d_stacks = grads

    def body(i, carry):
        d_u, d_w, *d_stacks = carry
        e, assignment, mine = _overflow_tile(i, over, k, static.tile)
        tokens = assignment // k
        _, pull = jax.vjp(
            functools.partial(_tile_rows, static), jnp.take(u, tokens, axis=0),
            jnp.take(weights.reshape(-1), assignment), *_expert((gate, up, down), e))
        d_x, d_row_w, *d_expert = pull(jnp.where(mine[:, None], jnp.take(g, tokens, axis=0), 0.0))
        return (d_u.at[tokens].add(d_x.astype(jnp.float32)), d_w.at[assignment].add(d_row_w),
                *(d.at[e].add(new) for d, new in zip(d_stacks, d_expert)))

    d_u32, d_w, *d_stacks = jax.lax.fori_loop(
        0, n_tiles, body, (d_u.astype(jnp.float32), d_weights.reshape(-1), *d_stacks))
    return d_u32.astype(d_u.dtype), d_w.reshape(d_weights.shape), *d_stacks


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows(static, u, weights, gate, up, down, layout, sizes, over):
    """Every held assignment's part of the result: the straight-line pass
    over the buffers' ``C`` rows, then the overflow past them. One
    differentiation rule for both, so that the overflow's gradients are
    added into the pass's own."""
    out = _capacity_pass(static, u, weights, gate, up, down, layout, sizes)
    return _overflow(static, out, u, weights, gate, up, down, over)


def _held_rows_fwd(static, u, weights, gate, up, down, layout, sizes, over):
    out, pull = jax.vjp(
        lambda *a: _capacity_pass(static, *a, layout, sizes), u, weights, gate, up, down)
    return (_overflow(static, out, u, weights, gate, up, down, over),
            (pull, u, weights, gate, up, down, over))


def _held_rows_bwd(static, res, g):
    pull, *operands, over = res
    return (*_overflow_bwd(static, g, pull(g), *operands, over), None, None, None)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def expert_layer(u, ids, weights, gate, up, down, *, first: int, count: int, dtype,
                 activation=jax.nn.relu):
    """This chip's part of the routed-expert layer.

    ``u`` [T, D] is the layer's normalised input, ``ids`` / ``weights``
    [T, k] the router's choices over all experts (:func:`route`), ``gate`` /
    ``up`` [count, D, F] and ``down`` [count, F, D] the held experts
    ``first ... first + count - 1``, ``activation`` the gate's (ReLU: ReGLU;
    ``jax.nn.silu``: SwiGLU). The router's width, which sizes the buffers
    (:func:`capacity`), is what :func:`router_width` says around the call.
    Returns the partial sum [T, D] float32 over the held experts and the
    layer's routing statistics: assignments held, the most loaded held
    expert's rows over the mean, the sorted rows the layer's passes covered
    and the overflow tiles among them."""
    t, k = ids.shape
    cap = capacity(t * k, count, _router.outputs or count)
    tile = GMM_TILES[0]
    with jax.named_scope(trace.SCOPE_MOE_DISPATCH):
        held, pos, order, sizes = sorted_layout(ids, first, count)
        ends = jnp.cumsum(sizes)
        starts, n_held = ends - sizes, ends[-1]
        layout = (held & (pos < cap), pos, order[:cap], jnp.minimum(n_held, cap))
        sizes_in = jnp.minimum(ends, cap) - jnp.minimum(starts, cap)
        lo = jnp.maximum(starts, cap)  # an expert's first row past the capacity
        tiles = (jnp.maximum(ends - lo, 0) + tile - 1) // tile
        tiles_to = jnp.cumsum(tiles)  # the last: every (expert, tile) pair past the capacity
        over = (order, lo, ends, tiles, tiles_to)
    out = _held_rows(_Static(activation, _interpret_on(jax.default_backend()), tile),
                     u.astype(dtype), weights, gate, up, down, layout, sizes_in, over)
    mean = jnp.maximum(n_held.astype(jnp.float32) / count, 1e-9)
    stats = {"moe/assignments_held": n_held.astype(jnp.float32),
             "moe/load_max_over_mean": jnp.max(sizes).astype(jnp.float32) / mean,
             "moe/rows_touched": (cap + tile * tiles_to[-1]).astype(jnp.float32),
             "moe/overflow_tiles": tiles_to[-1].astype(jnp.float32)}
    return out, jax.lax.stop_gradient(stats)
