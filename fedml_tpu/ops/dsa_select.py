"""The sparse-attention selection (``ops/dsa.py``) as one Mosaic kernel.

A grid step holds ``ROWS`` query rows of the index scores against the ``K``
keys of their causal group in VMEM and makes, from that one read:

1. the ordered key of every score (``-0.0`` folded into ``0.0``, the float's
   bits as a signed integer with the floats' order, ``INT_MIN`` on a key after
   its query), kept in a ``[ROWS, K]`` int32 scratch beside the block;
2. each row's threshold, the ``min(topk, t + 1)``-th largest key, bits from
   the top, one a pass (in VMEM the compares an element are what is paid: one
   bit is 32 of them, two are 48, four are 120; step 0 of PR 49, ``PERF.md``
   section 6), a pass counting the keys at or above its candidate. The step's
   counters stay in registers while the resident keys stream by, ``WIDE``
   lanes a trip, and only the lane tiles up to the diagonal are walked. A
   block whose rows all have ``t < topk`` takes every visible key and skips
   the search;
3. where a row of the block holds more keys equal to its threshold than it
   needs, and only there, the cut among them: the same compare-and-count
   search over the bits of the position, so that the lowest positions win, as
   ``lax.top_k`` and ``dsa._choose`` have it;
4. the chosen set packed in ``selection_layout(T)`` (a bit plane is a shift
   and an OR of whole registers), the share of the row's softmax over its
   visible keys that lies on the set, and a flag a block: 0 skipped, 1
   searched, 2 searched and the tie path taken.

VMEM held at ``[128, 8192]``: the block twice (the pipeline's buffers, 8 MiB)
and the keys once (4 MiB): inside Mosaic's default 16 MiB, nothing asked for
(a request slows XLA's own fusions: ``ops/attention.py`` ``_mosaic_params``).
Past 8,192 keys a step takes fewer rows (:func:`tiling`).
On the CPU backend the kernel runs interpreted, as the flash kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops import attention

ROWS = 128  # query rows a grid step, their counters in registers together: [128, 8192] float32 is 4 MiB
WIDE = 512  # lanes of the resident keys a trip of a counting loop
VMEM_BYTES = 12 * 2 ** 20  # what a step may hold of Mosaic's default 16 MiB
INT_MIN = -2 ** 31
SKIPPED, SEARCHED, TIED = 0, 1, 2


def _count(u_ref, trips, wide, lanes, n, hit):
    """``n`` arrays ``[rows, lanes]`` int32: how often each of the ``n``
    predicates ``hit(keys, first lane)`` holds down each lane column over the
    first ``trips * wide`` keys."""

    def trip(w, accs):
        for c in range(wide // lanes):
            lo = w * wide + c * lanes
            u = u_ref[:, pl.ds(pl.multiple_of(lo, lanes), lanes)]
            accs = tuple(a + jnp.where(h, 1, 0) for a, h in zip(accs, hit(u, lo)))
        return accs

    return jax.lax.fori_loop(0, trips, trip, tuple(
        jnp.zeros((u_ref.shape[0], lanes), jnp.int32) for _ in range(n)))


def _total(acc):
    return jnp.sum(acc, axis=1, keepdims=True)


def _select_kernel(row0_ref, x_ref, words_ref, mass_ref, flag_ref, u_ref, tau_ref, cut_ref,
                   tied_ref, *, topk, layout, wide):
    rows, keys = x_ref.shape
    lanes, planes = layout
    row0 = row0_ref[0] + pl.program_id(1) * rows
    searched = row0 + rows > topk
    # the lane tiles a row of the block can see, in whole trips
    trips = jnp.minimum(row0 + rows + wide - 1, keys) // wide
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    pos = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(topk, pos + 1)
    tied_ref[0] = 0

    def order(c, top):
        cs = pl.ds(pl.multiple_of(c * lanes, lanes), lanes)
        x = x_ref[:, cs]
        valid = c * lanes + lane <= pos
        b = jnp.where(x == 0.0, 0, jax.lax.bitcast_convert_type(x, jnp.int32))
        u_ref[:, cs] = jnp.where(valid, jnp.where(b < 0, b ^ 0x7FFFFFFF, b), INT_MIN)
        return jnp.maximum(top, jnp.where(valid, x, -jnp.inf))

    top = jax.lax.fori_loop(0, trips * (wide // lanes), order,
                            jnp.full((rows, lanes), -jnp.inf, jnp.float32))
    top = jnp.max(top, axis=1, keepdims=True)

    @pl.when(searched)
    def _():
        def one_pass(k, prefix):  # the bits found so far, as the unsigned key's
            cand = prefix | jnp.left_shift(jnp.int32(1), 31 - k)
            floor = jnp.broadcast_to(cand ^ INT_MIN, (rows, lanes))
            count, = _count(u_ref, trips, wide, lanes, 1, lambda u, lo: [u >= floor])
            return jnp.where(_total(count) >= want, cand, prefix)

        tau = jax.lax.fori_loop(0, 32, one_pass, jnp.zeros((rows, 1), jnp.int32)) ^ INT_MIN
        at = jnp.broadcast_to(tau, (rows, lanes))
        above, level = _count(u_ref, trips, wide, lanes, 2, lambda u, lo: [u > at, u == at])
        need = want - _total(above)
        tied = jnp.max(jnp.where(_total(level) > need, 1, 0))
        tau_ref[...] = tau
        cut_ref[...] = jnp.full((rows, 1), keys, jnp.int32)  # every equal key

        @pl.when(tied > 0)
        def _():
            # the largest cut with at most ``need`` equal keys before it
            tied_ref[0] = 1

            def one_bit(k, cut):
                cand = cut | jnp.left_shift(jnp.int32(1), keys.bit_length() - 1 - k)
                edge = jnp.broadcast_to(cand, (rows, lanes))
                before, = _count(u_ref, trips, wide, lanes, 1,
                                 lambda u, lo: [(u == at) & (lo + lane < edge)])
                return jnp.where(_total(before) <= need, cand, cut)

            cut_ref[...] = jax.lax.fori_loop(0, keys.bit_length(), one_bit,
                                             jnp.zeros((rows, 1), jnp.int32))

    @pl.when(jnp.logical_not(searched))
    def _():
        tau_ref[...] = jnp.full((rows, 1), INT_MIN, jnp.int32)  # every visible key is above
        cut_ref[...] = jnp.zeros((rows, 1), jnp.int32)  # and no hidden one is level

    at = jnp.broadcast_to(tau_ref[...], (rows, lanes))
    edge = jnp.broadcast_to(cut_ref[...], (rows, lanes))
    on_all = jnp.zeros((rows, lanes), jnp.float32)
    on_set = jnp.zeros((rows, lanes), jnp.float32)
    for g in range(words_ref.shape[1] // lanes):

        def plane(p, carry, g=g):
            word, on_all, on_set = carry
            lo = (g * planes + p) * lanes
            cs = pl.ds(pl.multiple_of(lo, lanes), lanes)
            u = u_ref[:, cs]
            chosen = (u > at) | ((u == at) & (lo + lane < edge))
            e = jnp.where(u > INT_MIN, jnp.exp(x_ref[:, cs] - top), 0.0)
            return (word | jnp.left_shift(jnp.where(chosen, 1, 0), p),
                    on_all + e, on_set + jnp.where(chosen, e, 0.0))

        seen = jnp.clip(trips * (wide // lanes) - g * planes, 0, planes)
        word, on_all, on_set = jax.lax.fori_loop(
            0, seen, plane, (jnp.zeros((rows, lanes), jnp.int32), on_all, on_set))
        words_ref[:, g * lanes:(g + 1) * lanes] = word
    mass_ref[...] = (jnp.sum(on_set, axis=1, keepdims=True)
                     / jnp.sum(on_all, axis=1, keepdims=True))
    flag_ref[...] = jnp.full(flag_ref.shape, jnp.where(searched, SEARCHED + tied_ref[0], SKIPPED),
                             jnp.int32)


def tiling(rows: int, keys: int, t: int, block: int | None = None) -> tuple:
    """``(rows a grid step, lanes a trip)`` for ``rows`` query rows against
    ``keys`` keys of a sequence of ``t``: at most ``block`` (``ROWS``) rows a
    step, halved while the step's scores and keys pass ``VMEM_BYTES``; a
    ValueError where the kernel cannot tile the shape (on every backend:
    Mosaic would refuse)."""
    lanes = attention.selection_layout(t)[0]
    step = min(block or ROWS, rows)
    while rows % step:
        step -= 1
    while 3 * step * keys * 4 > VMEM_BYTES and step % 16 == 0:
        step //= 2
    if keys % lanes or keys > t or step % 8:
        raise ValueError(
            f"dsa: the selection kernel cannot tile {rows} rows x {keys} keys of a sequence of "
            f"{t}: the keys are whole runs of {lanes} packed positions and a step's rows "
            f"({step}) whole sublane tiles of 8")
    if 3 * step * keys * 4 > VMEM_BYTES:
        raise ValueError(
            f"dsa: {step} rows x {keys} keys of float32 scores, twice, and their int32 keys "
            f"pass the {VMEM_BYTES >> 20} MiB a step of the selection kernel may hold")
    wide = min(WIDE, keys) // lanes * lanes
    while keys % wide:
        wide -= lanes
    return step, wide


def select_rows(scores, row0, topk: int, t: int, *, block: int | None = None):
    """``(words [B, R, W] int32, mass [B, R] float32, flags [B, R / step]
    int32)`` of the query rows ``row0 ...`` whose index scores against the keys
    ``0 ... K - 1`` are ``scores`` ``[B, R, K]`` float32: each row's ``S_t``
    packed in ``selection_layout(t)``, the share of its softmax over the keys
    it sees that lies on ``S_t``, and each step's ``SKIPPED`` / ``SEARCHED`` /
    ``TIED``. No gradient passes through the choice. ``row0`` may be traced;
    ``block`` caps a step's rows. The call is jitted, so that a model's layers
    (and its round, eval and ``init`` programs) trace the kernel once a shape
    and not once a call."""
    step, wide = tiling(*scores.shape[1:], t, block)
    return _select_rows(jax.lax.stop_gradient(scores), row0, topk, t, step, wide,
                        attention._interpret_on(jax.default_backend()))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _select_rows(scores, row0, topk, t, step, wide, interpret):
    b, rows, keys = scores.shape
    layout = attention.selection_layout(t)
    width = t // layout[1]
    words, mass, flags = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, layout=layout, wide=wide),
        grid=(b, rows // step),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, step, keys), lambda i, j: (i, j, 0))],
        out_specs=[pl.BlockSpec((None, step, width), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((None, step, 1), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((None, None, 8, 128), lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, rows, width), jnp.int32),
                   jax.ShapeDtypeStruct((b, rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, rows // step, 8, 128), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((step, keys), jnp.int32), pltpu.VMEM((step, 1), jnp.int32),
                        pltpu.VMEM((step, 1), jnp.int32), pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
        name="dsa_select",
    )(jnp.asarray(row0, jnp.int32).reshape(1), scores.astype(jnp.float32))
    return words, mass[..., 0], flags[:, :, 0, 0]
