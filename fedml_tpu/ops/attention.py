"""Fused blockwise (flash) attention — the pallas hot-op for transformer
clients.

The reference has no attention anywhere (its NLP models are LSTMs,
fedml_api/model/nlp/rnn.py) and no long-context support (SURVEY §5.7). This
framework treats long sequences as first-class: the single-chip hot path is
this pallas kernel (online-softmax blockwise attention, O(T) memory instead of
the O(T²) score matrix), and the multi-chip path is ring attention over a
sequence-parallel mesh axis (fedml_tpu/parallel/ring_attention.py) which
reuses the same math.

Layout convention: ``[B, H, T, D]`` (batch, heads, sequence, head_dim). The
scores' width (q and k) and the values' (v and the output) may differ, as in
latent attention, whose keys carry rotary columns the values lack. K and V
may hold fewer heads than Q (grouped KV heads: query head ``n`` reads KV head
``n // (H // H_kv)``), and ``window`` limits a query to the last ``window``
keys up to and including its own position; both are static, and with equal
head counts and ``window=None`` the kernels are the programs they were.
A third mask, ``stair=(s_q, s_k)``, shows key ``j`` to query ``i`` iff ``j <
s_k * (i // s_q)``: whole ``s_q x s_k`` steps of a staircase, for keys that
summarise the windows before the query's own (``ops/eva.py``), where ``t_q``
and ``t_k`` differ; a row that sees no key gives zeros and a log-sum-exp of
about ``NEG_INF / 2``. :func:`flash_attention_lse` hands the log-sum-exp out
as a second, differentiable output, so that two calls over two key sets can
share one softmax. A fourth mask comes from the data and not from integers:
:func:`flash_attention_selected` shows query ``i`` the keys of a set chosen for
it (``ops/dsa.py``), one set a query shared by every head, handed over as a
:class:`Selection`: the set's bits packed 32 to a word (:func:`selection_layout`),
once with a query's keys along a row and once with a key's queries, and the
count of chosen pairs in every tile, by which a tile that holds none is skipped.
Forward runs the pallas kernel ``flash_fwd``, which also writes each query
row's log-sum-exp (``B*H*T`` f32, the backward's one extra residual);
backward is a custom VJP of one more pallas kernel, ``flash_bwd_dkv`` (a key
block a grid step, looping over the query blocks that see it): it recomputes
each score tile once in VMEM from q, k and the log-sum-exp and feeds all
three gradients from it, dK and dV of its key block and the head's dQ, which
it sums in VMEM across the key blocks. Both kernels feed the MXU operands in
the input's dtype with f32 accumulation, skip the tiles the mask hides
whole, and run mask arithmetic only on the tiles the diagonal or the window's
edge cuts, so memory is O(T) in both directions and no ``[T, T]`` tile ever
reaches HBM. Each direction picks its tiles from the shape
(:func:`_fwd_blocks`, :func:`_bwd_blocks`); a caller's ``block_q`` /
``block_k`` override the forward's.
On the CPU backend the kernels run in interpreter mode so the full test
suite exercises them on the 8-device CPU mesh; on TPU they are
Mosaic-compiled; any other backend is refused (see :func:`_interpret_on`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat

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


def attention_reference(q, k, v, causal: bool = False, sm_scale: float | None = None,
                        window: int | None = None, stair: tuple | None = None,
                        with_lse: bool = False, selected=None):
    """Plain XLA attention, the numerical oracle for the kernels.

    Causal convention (shared with the pallas kernel): query i attends to
    keys j with j <= i + (t_k - t_q) — i.e. sequences are right-aligned, the
    standard decode convention. ``window`` (with ``causal``) counts the query
    itself: key j is visible to query i iff i - window < j <= i, so a query
    sees at most ``window`` keys and ``window >= t_k`` is plain causal. K and V
    with fewer heads than Q are grouped: query head n reads KV head
    n // (H // H_kv). q and k share the scores' width ``D_qk``; v and the
    output have the values' width ``D_v``, which may be another.
    ``stair=(s_q, s_k)`` (without ``causal``): key j is visible to query i iff
    j < s_k * (i // s_q), both counted from 0; a row that sees no key gives
    zeros. ``with_lse``: ``(out, lse [B, H, T_q] float32)``, the log-sum-exp
    of each row's visible scores (about ``NEG_INF`` where it sees none).
    ``selected`` (a bool ``[B, T_q, T_k]``, with neither ``causal`` nor
    ``stair``): query i sees key j iff ``selected[b, i, j]``, in every head."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    group = _kv_group(q.shape[1], k.shape[1])
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    _check_window(window, causal)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        s = jnp.where(mask, s, NEG_INF)
    _check_stair(stair, causal)
    if stair:
        steps = jnp.arange(s.shape[-2])[:, None] // stair[0]
        mask = jnp.arange(s.shape[-1])[None] < stair[1] * steps
        s = jnp.where(mask, s, NEG_INF)
    if selected is not None:
        if causal or stair:
            raise ValueError("attention: a selected set is the whole mask")
        s = jnp.where(selected[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if stair:
        p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return (out, jax.nn.logsumexp(s, axis=-1)) if with_lse else out


def _kv_group(h: int, h_kv: int) -> int:
    """Query heads a KV head (1 = plain multi-head attention)."""
    if h % h_kv:
        raise ValueError(f"attention: {h} query heads do not divide into {h_kv} KV heads")
    return h // h_kv


def _check_window(window, causal) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError("attention: a window needs causal=True and window >= 1")


def _check_stair(stair, causal) -> None:
    if stair is not None and (causal or len(stair) != 2 or min(stair) < 1):
        raise ValueError("attention: stair=(s_q, s_k) of whole steps >= 1 excludes causal")


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _eye(n):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(
        jnp.int32, (n, n), 1
    )


def _row(col):
    """``[n, 1]`` (one value a sublane) -> ``[1, n]`` (one value a lane) by a
    masked sublane reduction: exact, ``n * n`` elements once a query block,
    and faster on the v5e than the reshape Mosaic offers (PERF.md §6, PR 26)."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0, keepdims=True)


def _spread(x, n):
    """``[rows, lanes]``, a row's value in every lane -> what broadcasts on ``[rows, n]``: whole
    registers where ``lanes`` divides ``n``, else (``n`` 64 of 128 lanes) lane 0, ``[rows, 1]``."""
    lanes = x.shape[1]
    if lanes in (1, n):
        return x
    if n % lanes:
        return x[:, :1]
    return jnp.tile(x, (1, n // lanes))


def _fold(p, lanes):
    """``[rows, n]`` -> ``[rows, lanes]``: the sum of the lane-wide column
    groups, adds of whole registers; the lanes themselves are summed once,
    after the last tile."""
    if lanes == 1:
        return jnp.sum(p, axis=-1, keepdims=True)
    return functools.reduce(
        jnp.add, (p[:, c:c + lanes] for c in range(0, p.shape[1], lanes)))


class Selection(NamedTuple):
    """The keys chosen for each query, one set a query whatever the head, as
    the kernels of :func:`flash_attention_selected` read it. ``rows`` ``[B,
    T_q, W_k]`` int32: query ``i``'s row holds the bit of every key, packed by
    :func:`selection_layout` of ``T_k``; ``cols`` ``[B, T_k, W_q]``: the same
    set with a key's queries along its row (the backward's tiles are
    transposed); ``tiles`` ``[B, T_q / block_q, T_k / block_k]`` int32: chosen
    pairs in each tile, whose shape fixes the kernels' blocks. A key after its
    query is never chosen. ``ops/dsa.py`` ``selection_from_mask`` makes one."""

    rows: jax.Array
    cols: jax.Array
    tiles: jax.Array


def selection_layout(t: int) -> tuple[int, int]:
    """``(lanes, planes)`` of the bits of ``t`` positions packed into int32
    words: position ``s`` is bit ``(s // lanes) % planes`` of the word in
    column ``(s // lanes // planes) * lanes + s % lanes``. A run of ``lanes``
    positions is then one bit plane of ``lanes`` adjacent words, and a kernel
    unpacks a tile by a shift and a mask of whole registers, with no move
    across lanes (``lanes`` 128 wherever 128 divides ``t``)."""
    lanes = 128 if t % 128 == 0 else t
    planes = min(32, t // lanes)
    while (t // lanes) % planes:
        planes -= 1
    return lanes, planes


def _selected_tile(ref, blk, block, layout):
    """Bool ``[rows, block]``: block ``blk`` (a traced index) of the packed
    positions along the rows of ``ref`` ``[rows, W]``."""
    lanes, planes = layout
    pieces = []
    for i in range(block // lanes):
        piece = blk * (block // lanes) + i
        group = _step_of(piece, planes)
        word = ref[:, pl.ds(pl.multiple_of(group * lanes, lanes), lanes)]
        plane = jnp.broadcast_to(piece - group * planes, word.shape)
        pieces.append(jax.lax.shift_right_logical(word, plane) & 1)
    return (pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)) != 0


def _tile_counts(ref, heads, num_qb):
    """``(i, j) -> tiles[b, i, j]`` of a :class:`Selection` inside a kernel
    whose grid axis 0 is batch x ``heads``: the counts lie in SMEM as ``[B *
    nq, nk]``."""
    base = (pl.program_id(0) // heads) * num_qb
    return lambda i, j: ref[base + i, j]


def _flash_fwd_selected_kernel(q_ref, k_ref, v_ref, rows_ref, tiles_ref, *rest, **static):
    _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, sel=(rows_ref, tiles_ref), **static)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc,
    *, block_k, causal, sm_scale, window=None, stair=None, selected=None, sel=None,
):
    # q_ref: [block_q, D_qk]; k_ref: [T_k, D_qk] and v_ref: [T_k, D_v] (the
    # head's whole sequence); o_ref: [block_q, D_v]; lse_ref: [1, block_q];
    # grid = (B*H, T_q // block_q).
    # Scratch, all f32: o_acc [block_q, D_v]; m_acc, l_acc [block_q, lanes], the
    # running max with a row's value in every lane and the running sum a lane.
    # Cross-lane work is what bounds the kernel on the v5e (PERF.md §6, PR
    # 29), so a step keeps one lane reduction, the max: the sum's waits for
    # the end, and m and alpha meet the scores and o_acc lane for lane.
    # Operands reach the MXU in the input's dtype; scores, exp, the running
    # max and sum and the output's accumulator are f32, as in the backward.
    iq = pl.program_id(1)
    block_q, d = o_ref.shape
    lanes = m_acc.shape[1]
    t_k = k_ref.shape[0]
    num_kb = t_k // block_k
    off = t_k - pl.num_programs(1) * block_q  # right-aligned, as attention_reference
    q = q_ref[:]
    o_acc[:] = jnp.zeros_like(o_acc)
    l_acc[:] = jnp.zeros_like(l_acc)
    # the running max starts above the mask's value: a row that has seen no
    # key yet (its window opens in a later tile) gets exp(-5e29) = 0 for every
    # hidden pair, where a start at NEG_INF would give exp(0)
    m_acc[:] = jnp.full_like(m_acc, NEG_INF / 2)
    if causal or stair:
        # a tile's mask from one value a row and one a column, so no
        # [block_q, block_k] index array is ever made: a cut tile costs a
        # compare and a select an element (two compares under a window)
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    if stair:
        # the keys each row of this query block sees: s_k a whole step below it
        seen_to = stair[1] * _step_of(q_idx + iq * block_q, stair[0])

    def step(masked, j):
        cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[cols, :]
        v = v_ref[cols, :]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * sm_scale
        if masked == "selected":
            s = jnp.where(_selected_tile(sel[0], j, block_k, selected[0]), s, NEG_INF)
        elif masked and stair:
            s = jnp.where(k_idx < seen_to - j * block_k, s, NEG_INF)
        elif masked:
            # the tile's column of query i's own position
            diag = q_idx + (off + iq * block_q - j * block_k)
            seen = k_idx <= diag
            if window is not None:
                seen &= k_idx > diag - window
            s = jnp.where(seen, s, NEG_INF)
        m = m_acc[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _spread(m_new, block_k))
        alpha = jnp.exp(m - m_new)
        m_acc[:] = m_new
        l_acc[:] = l_acc[:] * alpha + _fold(p, lanes)
        o_acc[:] = o_acc[:] * _spread(alpha, d) + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32
        )

    def loop(lo, hi, masked):
        jax.lax.fori_loop(lo, hi, lambda j, c: step(masked, j), None)

    if selected:
        # every causal tile that holds a chosen pair, under the set's own bits
        # (which hide what the diagonal would): the count says which hold none
        tiles = _tile_counts(sel[1], selected[1], pl.num_programs(1))
        last = _fwd_kb_ranges(iq, block_q, block_k, off, num_kb, None)[3]
        jax.lax.fori_loop(0, last, lambda j, c: pl.when(tiles(iq, j) > 0)(
            lambda: step("selected", j)), None)
    elif causal:
        # under a window the key blocks its edge cuts, then the ones every
        # row sees whole (no mask arithmetic), then the ones the diagonal cuts
        start, whole_start, whole_end, last = _fwd_kb_ranges(
            iq, block_q, block_k, off, num_kb, window
        )
        if window is not None:
            loop(start, whole_start, True)
        loop(whole_start, whole_end, False)
        loop(whole_end, last, True)
    elif stair:
        # the key blocks every row sees whole, then the ones a step's edge cuts
        _, _, whole_end, last = _stair_kb_ranges(iq, block_q, block_k, num_kb, stair)
        loop(0, whole_end, False)
        if _stair_cuts(block_q, block_k, stair):
            loop(whole_end, last, True)
    else:
        loop(0, num_kb, False)
    l = jnp.maximum(jnp.sum(l_acc[:], axis=-1, keepdims=True), 1e-20)
    o_ref[:] = (o_acc[:] / l).astype(o_ref.dtype)
    # the backward's residual: log-sum-exp of each query row's scores, one
    # f32 a row in lanes (a fully masked row keeps about NEG_INF / 2)
    lse_ref[:] = _row(m_acc[:, :1] + jnp.log(l))


def _head_seq(t, d, group=1, buffers=None):
    """A head's whole ``[t, d]`` sequence, resident across the grid's axis 1.
    With ``group`` query heads a KV head, grid step ``i`` (a flat batch x
    query head) reads the KV head ``i // group``: consecutive steps of one
    group name the same block, so it is fetched once. ``buffers=1`` keeps one
    copy in VMEM and not the pipeline's two (the block changes once a head)."""
    mode = {} if buffers is None else {"pipeline_mode": pl.Buffered(buffers)}
    if group == 1:
        return pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0), **mode)
    return pl.BlockSpec((None, t, d), lambda i, j: (i // group, 0, 0), **mode)


def _head_block(block, d, group=1):
    if group == 1:
        return pl.BlockSpec((None, block, d), lambda i, j: (i, j, 0))
    return pl.BlockSpec((None, block, d), lambda i, j: (i // group, j, 0))


def _rows_spec(block):
    """BlockSpec of a query block's ``[1, block]`` tile of a per-row f32
    statistic (lse, delta) kept as ``[B*H, T // block, 1, block]``: T * 4
    bytes a head in HBM, and the tile's last two dimensions are the array's
    own, so Mosaic takes every ``block`` that :func:`_pick_block` passes."""
    return pl.BlockSpec((None, None, 1, block), lambda i, j: (i, j, 0, 0))


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # a Selection's tile counts, whole


def _selected_rows(block, width, heads):
    """A block of a :class:`Selection`'s packed rows: grid step ``(i, j)`` (a
    flat batch x head, a block) reads rows ``j`` of batch ``i // heads``, so a
    batch's heads read the same words."""
    return pl.BlockSpec((None, block, width), lambda i, j: (i // heads, j, 0))


def _mosaic_params(dtype, *whole_sequences, scratch=(), buffers=2):
    """``compiler_params`` of a kernel that keeps ``whole_sequences`` (the
    ``(T, D)`` of a head's resident operands and outputs of ``dtype``, each in
    ``buffers`` copies: the pipeline's two, or the one of ``_head_seq(...,
    buffers=1)``) and ``scratch`` (the ``(T, D)`` of its resident f32
    accumulators) in VMEM, last dimensions padded to whole 128-lane registers.
    Mosaic's default is 16 MB of scoped VMEM. What holds 10 MiB or less fits
    that beside the tiles (the forward's K and V at ``[8192, 128]`` bf16: 8;
    the backward's q, dO, dQ and accumulator there: 10) and gets no parameter.
    **A request is not free**: XLA takes the largest limit any custom call of
    a program asks out of what its own fusions may keep in VMEM (in
    ``lfm2moe_silo2`` a 24 MiB request made seven matmul fusions of other
    layers 40-60% slower, 3.4% of the round, on the parent's kernels as on
    these; PERF.md §6, PR 43), so a kernel holds as little as it can and asks
    only when it must: the 192-wide key pads to 256 lanes (forward 12 MiB,
    refused at the default by 0.8 MB, PR 32; backward 18), and asks for what
    it holds plus the 8 MiB the tiles and a step's temporaries had before."""
    held = sum(buffers * t * -(-d // 128) * 128 * jnp.dtype(dtype).itemsize
               for t, d in whole_sequences)
    held += sum(t * -(-d // 128) * 128 * 4 for t, d in scratch)
    if held <= 10 * 2 ** 20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=held + 8 * 2 ** 20)


def _fwd_blocks(t_q, t_k, dtype, block_q=None, block_k=None, stair=None):
    """The forward kernel's ``(block_q, block_k)``: the caller's where it
    names them (the tests' toy tiles), else 512 x 512, the backward's tile
    too. Measured on the v5e at D 128 bf16 causal, ms a call (PERF.md §6, PR
    29; T 2048 is (4, 16, 2048, 128), T 8192 is 28 query heads on 4 KV heads
    of one sequence, global and under a 4096 window; the last column, PR 32,
    is 32 heads of T 8192 at a 192-column score on 128-column values):

        tile         T 1024   T 2048   T 8192   T 8192 w   192 | 128
        256 x 256    0.873    1.368    7.621    6.018      10.943
        256 x 512    0.642    0.894    4.243    3.513       7.087
        256 x 1024   0.711    0.928    3.954    3.457       6.904
        512 x 256    0.685    1.020    5.318    4.312       8.359
        512 x 512    0.501    0.718    3.547    2.898       6.342
        512 x 1024   0.612    0.832    3.779    3.212       6.731
        1024 x 512   0.536    0.755    3.446    2.912       6.337
        1024 x 1024  0.567    0.805    3.719    3.155      refused

    A step's cost is the tile's area plus a part a query row that key blocks
    under 512 do not amortise; a wider or taller tile than 512 spends more on
    pairs the diagonal hides than it saves. 1024 x 512 is 2.9% faster on the
    T 8192 global layer alone and 0.5% over that model's one global and three
    window layers, and level with 512 x 512 at 192 | 128: not worth a rule.
    Both fit Mosaic's default 16 MB of VMEM beside a head's whole K and V at
    T 8192 (what ``_mosaic_params`` asks at 192 | 128); 1024 x 1024 does not.
    Under a staircase a block of its own choosing is no longer than a step
    where the step is a whole number of sublane tiles, so that no tile is cut
    (steps of 2048 x 128: 512 x 128, 6 of the 16 step-by-step blocks visited)."""
    want_q, want_k = block_q or 512, block_k or 512
    if stair:
        sublane = 8 * max(4 // jnp.dtype(dtype).itemsize, 1)
        want_q = want_q if block_q or stair[0] % sublane else min(want_q, stair[0])
        want_k = want_k if block_k or stair[1] % sublane else min(want_k, stair[1])
    return _pick_block(t_q, want_q, dtype), _pick_block(t_k, want_k, dtype)


@jax.named_scope(trace.SCOPE_FLASH_FWD)
def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window=None, stair=None,
               selected=None):
    """``(out [B, H, T, D_v], lse [B, H, T] f32)``; ``block_q`` / ``block_k``
    of ``None`` are chosen by :func:`_fwd_blocks`. ``selected``: a
    :class:`Selection` (with ``causal``), whose tiles are the blocks."""
    b, h, t, d = q.shape
    h_kv, t_k, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = _kv_group(h, h_kv)
    _check_window(window, causal)
    _check_stair(stair, causal)
    block_q, block_k = _fwd_blocks(t, t_k, q.dtype, block_q, block_k, stair)
    nq = t // block_q
    lanes = 1 if block_k % 128 else 128  # of the running max and sum, see the kernel
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h_kv, t_k, d)
    vf = v.reshape(b * h_kv, t_k, d_v)
    kernel = functools.partial(
        _flash_fwd_selected_kernel if selected else _flash_fwd_kernel,
        block_k=block_k,
        causal=causal,
        sm_scale=sm_scale,
        window=window,
        **({"stair": stair} if stair else {}),
        **({"selected": (selection_layout(t_k), h)} if selected else {}),
    )
    _note_call("fwd", q, t_k, d_v, group, causal, window, block_q, block_k, stair,
               selected is not None)
    more_specs, more = [], ()
    if selected:
        more_specs = [_selected_rows(block_q, selected.rows.shape[2], h), _SMEM]
        more = (selected.rows, selected.tiles.reshape(b * nq, t_k // block_k))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, nq),
        in_specs=[_head_block(block_q, d), _head_seq(t_k, d, group),
                  _head_seq(t_k, d_v, group), *more_specs],
        out_specs=[_head_block(block_q, d_v), _rows_spec(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, nq, 1, block_q), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d_v), jnp.float32),
                        pltpu.VMEM((block_q, lanes), jnp.float32),
                        pltpu.VMEM((block_q, lanes), jnp.float32)],
        interpret=interpret,
        name=trace.FLASH_KERNEL_NAME,
        compiler_params=_mosaic_params(k.dtype, (t_k, d), (t_k, d_v)),
    )(qf, kf, vf, *more)
    return out.reshape(b, h, t, d_v), lse.reshape(b, h, t)


# ---------------------------------------------------------------------------
# Pallas backward kernel: one pass over the visible tiles. A score tile is
# recomputed once in VMEM from q, k and the forward's lse and feeds dQ, dK and
# dV (five products and one exp a tile); the [T, T] matrix never reaches HBM,
# and tiles that the causal mask hides whole are skipped as the forward skips them.
# ---------------------------------------------------------------------------


def _visible(q_lo, k_lo, shape, window=None, stair=None):
    """Mask of one transposed score tile, [keys, queries]: key position <=
    query position (which carries the right-aligned offset) and, under a
    window, > query position - window; under a staircase, key position <
    s_k * (query position // s_q), from one value a column and one a row."""
    if stair:
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
        return k_pos < stair[1] * _step_of(q_pos, stair[0])
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


def _clip(x, lo, hi):
    """``clip`` for a kernel's traced block indices and for the plain ints
    the tile counts of :func:`_note_call` are made of."""
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _step_of(pos, s_q):
    """``pos // s_q`` for positions >= 0, ints or a kernel's int32 values: a
    shift where ``s_q`` is a power of two (every published window), else
    ``lax.div``, which truncates as it floors there."""
    if isinstance(pos, int):
        return pos // s_q
    if s_q & (s_q - 1) == 0:
        return jax.lax.shift_right_logical(pos, jnp.int32(s_q.bit_length() - 1))
    return jax.lax.div(pos, jnp.int32(s_q))


# Which tiles a grid step visits. Rows of query block i are off + i * block_q
# ... off + (i + 1) * block_q - 1 (off: the right-aligned offset t_k - t_q),
# columns of key block j are j * block_k ... (j + 1) * block_k - 1; a pair is
# visible iff k <= q and, under a window, k > q - window. Each function gives
# half-open ranges of block indices; tiles outside them are hidden whole and
# never visited, tiles in a "whole" range need no mask arithmetic.


def _fwd_kb_ranges(iq, block_q, block_k, off, num_kb, window):
    """``(start, whole_start, whole_end, last)`` of the key blocks a causal
    query block visits in the forward: ``[start, whole_start)`` are cut by
    the window's edge, ``[whole_start, whole_end)`` are seen whole,
    ``[whole_end, last)`` are cut by the diagonal. Without a window ``start =
    whole_start = 0``."""
    q_lo, q_hi = off + iq * block_q, off + (iq + 1) * block_q - 1
    last = _clip(q_hi // block_k + 1, 0, num_kb)
    whole_end = _clip((q_lo + 1) // block_k, 0, last)
    if window is None:
        return 0, 0, whole_end, last
    start = _clip((q_lo - window + 1) // block_k, 0, last)
    whole_start = _clip((q_hi - window + block_k) // block_k, start, last)
    return start, whole_start, _clip(whole_end, whole_start, last), last


def _dkv_qb_ranges(jk, block_q, block_k, off, num_qb, window):
    """``(first, first_whole, end_whole, end)`` of the causal dkv kernel's
    query blocks: ``[first, first_whole)`` are cut by the diagonal,
    ``[first_whole, end_whole)`` see the key block whole, ``[end_whole, end)``
    are cut by the window's edge. Without a window ``end_whole = end = num_qb``."""
    k_lo, k_hi = jk * block_k, (jk + 1) * block_k - 1
    first = _clip((k_lo - off) // block_q, 0, num_qb)
    first_whole = (k_hi - off + block_q - 1) // block_q
    if window is None:
        return first, _clip(first_whole, first, num_qb), num_qb, num_qb
    end = _clip((k_hi + window - 1 - off) // block_q + 1, first, num_qb)
    first_whole = _clip(first_whole, first, end)
    end_whole = _clip((k_lo + window - off) // block_q, first_whole, end)
    return first, first_whole, end_whole, end


# Under ``stair=(s_q, s_k)`` key j is visible to query i iff j < s_k * (i //
# s_q): no offset, both counted from 0. A tile is cut only where a step's
# edge crosses it, which none does when the blocks divide the steps.


def _stair_cuts(block_q, block_k, stair) -> bool:
    """Whether any visited tile can need mask arithmetic."""
    return bool(stair[0] % block_q or stair[1] % block_k)


def _stair_kb_ranges(iq, block_q, block_k, num_kb, stair):
    """:func:`_fwd_kb_ranges` under a staircase: ``[0, whole_end)`` are seen
    whole by every row of query block ``iq``, ``[whole_end, last)`` by some."""
    s_q, s_k = stair
    to_all = s_k * _step_of(iq * block_q, s_q)
    to_some = s_k * _step_of((iq + 1) * block_q - 1, s_q)
    whole_end = _clip(to_all // block_k, 0, num_kb)
    return 0, 0, whole_end, _clip((to_some + block_k - 1) // block_k, whole_end, num_kb)


def _stair_qb_ranges(jk, block_q, block_k, num_qb, stair):
    """:func:`_dkv_qb_ranges` under a staircase: query blocks ``[first,
    first_whole)`` see key block ``jk`` in part, ``[first_whole, num_qb)`` whole."""
    s_q, s_k = stair
    q_some = s_q * (_step_of(jk * block_k, s_k) + 1)  # the first query that sees its first key
    q_all = s_q * (_step_of((jk + 1) * block_k - 1, s_k) + 1)  # ... and its last
    first = _clip(q_some // block_q, 0, num_qb)
    first_whole = _clip((q_all + block_q - 1) // block_q, first, num_qb)
    return first, first_whole, num_qb, num_qb


def _note_call(kernel, q, t_k, d_v, group, causal, window, block_q, block_k, stair=None,
               selected=False):
    """Record, while the program is traced, what one attention call will do:
    its kind, its two widths, its grouping, how many of the square's tiles
    the kernel visits and what it ``writes`` (``obs/trace.py``
    :func:`program_note`; docs/OBSERVABILITY.md). Under a selected set the
    tiles counted are the causal ones, every one under the set's bits: which
    of them hold no chosen pair and are skipped only the data says (the
    ``dsa/tiles_nonempty`` counters)."""
    t_q = q.shape[2]
    nq, nk, off = t_q // block_q, t_k // block_k, t_k - t_q
    if stair and kernel == "dkv":
        ranges = [_stair_qb_ranges(j, block_q, block_k, nq, stair) for j in range(nk)]
    elif stair:
        ranges = [_stair_kb_ranges(i, block_q, block_k, nk, stair) for i in range(nq)]
    elif not causal:
        ranges = [(0, 0, nk, nk)] * nq
    elif kernel == "dkv":
        ranges = [_dkv_qb_ranges(j, block_q, block_k, off, nq, window) for j in range(nk)]
    else:
        ranges = [_fwd_kb_ranges(i, block_q, block_k, off, nk, window) for i in range(nq)]
    visited = sum(r[3] - r[0] for r in ranges)
    # tiles that run mask arithmetic: the two cut ranges either side of the whole one
    masked = visited if selected else visited - sum(r[2] - r[1] for r in ranges)
    trace.program_note(
        "attn/call", kernel=kernel,
        writes=("dq", "dk", "dv") if kernel == "dkv" else ("out", "lse"),
        kind=("selected" if selected else "stair" if stair else "window" if window is not None
              else "global" if causal else "full"),
        window=window, stair=stair, shape=tuple(q.shape), t_k=t_k, d_qk=q.shape[3], d_v=d_v,
        q_heads_per_kv_head=group,
        dtype=jnp.dtype(q.dtype).name, tile=(block_q, block_k),
        tiles_visited=visited, tiles_masked=masked, tiles_total=nq * nk,
    )


def _flash_bwd_dkv_selected_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, cols_ref,
                                   tiles_ref, *rest, **static):
    _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                          sel=(cols_ref, tiles_ref), **static)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_acc, dk_acc, dv_acc, *, block_q, causal, sm_scale, window=None, stair=None,
    selected=None, sel=None,
):
    # k_ref/dk_ref: [block_k, D_qk]; v_ref/dv_ref: [block_k, D_v]; q_ref/dq_ref:
    # [T_q, D_qk] and do_ref: [T_q, D_v] (the head's whole sequence);
    # lse_ref/delta_ref: [T_q // block_q, 1, block_q];
    # grid = (B*H, T_k // block_k). Tiles are transposed, [keys, queries], so
    # the per-query statistics broadcast along sublanes and four of the five
    # matmuls are plain NN / NT; dQ's contracts the tile's keys, dimension 0 of
    # both operands. dq_acc [T_q, D_qk] f32 lives across the head's key blocks,
    # which come in ascending order: zeroed at the first, written at the last.
    # q_ref, do_ref and dq_ref change once a head and keep one buffer each, so
    # that at [8192, 128] the kernel asks Mosaic for no VMEM (_mosaic_params).
    jk = pl.program_id(1)
    block_k = k_ref.shape[0]
    t_q = q_ref.shape[0]
    num_qb = t_q // block_q
    off = pl.num_programs(1) * block_k - t_q
    k = k_ref[:]
    v = v_ref[:]
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(jk == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def step(masked, i):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[rows, :]
        do = do_ref[rows, :]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp(s * sm_scale - lse_ref[i])
        if masked == "selected":
            p = jnp.where(_selected_tile(sel[0], i, block_q, selected[0]), p, 0.0)
        elif masked:
            # select, not multiply: a fully masked row's lse is about
            # NEG_INF and exp() of its scores is inf
            q_lo = i * block_q if stair else off + i * block_q
            p = jnp.where(_visible(q_lo, jk * block_k, s.shape, window, stair), p, 0.0)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[i]) * sm_scale).astype(q.dtype)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32
        )
        dk_acc[:] += jax.lax.dot_general(ds, q, _NN, preferred_element_type=jnp.float32)
        dq_acc[rows, :] += jax.lax.dot_general(ds, k, _TN, preferred_element_type=jnp.float32)

    def loop(lo, hi, masked):
        jax.lax.fori_loop(lo, hi, lambda i, c: step(masked, i), None)

    if selected:
        # as the forward: the causal tiles that hold a chosen pair
        tiles = _tile_counts(sel[1], selected[1], num_qb)
        first = _dkv_qb_ranges(jk, block_q, block_k, off, num_qb, None)[0]
        jax.lax.fori_loop(first, num_qb, lambda i, c: pl.when(tiles(i, jk) > 0)(
            lambda: step("selected", i)), None)
    elif causal:
        # query blocks whose last row reaches this key block's first column,
        # of those the ones whose first row sees its last column (no mask),
        # and under a window the ones its edge cuts, then none
        first, first_whole, end_whole, end = _dkv_qb_ranges(
            jk, block_q, block_k, off, num_qb, window
        )
        loop(first, first_whole, True)
        loop(first_whole, end_whole, False)
        if window is not None:
            loop(end_whole, end, True)
    elif stair:
        # query blocks a step's edge cuts, then the ones that see the key block whole
        first, first_whole, _, _ = _stair_qb_ranges(jk, block_q, block_k, num_qb, stair)
        if _stair_cuts(block_q, block_k, stair):
            loop(first, first_whole, True)
        loop(first_whole, num_qb, False)
    else:
        loop(0, num_qb, False)
    dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jk == pl.num_programs(1) - 1)
    def _():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_blocks(t_q, t_k, dtype, fwd_blocks, stair=None):
    """The backward kernel's ``(block_q, block_k)``. Measured on the v5e, bf16
    causal, ms a backward (the kernel, the ``rowsum(dO * O)`` fusion and a KV
    group's sum; PERF.md §6, PR 43; the columns are :func:`_fwd_blocks`' and
    32 query heads on 8 of width 64 at batch 2; PR 26's pair of kernels took
    20.10, 11.26, 9.49, 26.64 and 2.214 at 512 x 512; measured with q, dO and
    dQ double-buffered: with the one buffer each they have now 512 x 512
    reads 15.06, 8.04, 6.77, 19.33 and 1.643):

        tile         192 | 128   T 8192   T 8192 w   D 64     T 2048
        256 x 256    16.35       11.30    9.03       26.53    2.009
        256 x 512    15.44        8.57    7.08       20.40    1.659
        512 x 256    15.56        8.67    7.18       20.56    1.684
        512 x 512    14.84        8.09    6.79       19.26    1.571
        512 x 1024   15.04        8.13    7.25       refused  1.754
        1024 x 512   15.11        8.15    7.28       19.41    1.766
        1024 x 1024  refused      refused refused    refused  1.728

    Tiles large enough to amortise the loop, small enough that the causal
    diagonal wastes an eighth of the square and not a quarter; "refused" is
    the VMEM :func:`_mosaic_params` asks for, which counts 512 x 512's
    temporaries. A length whose divisor under 512 Mosaic refuses keeps the
    forward's block, which passed."""

    def pick(t, fwd_block):
        try:
            return _pick_block(t, 512, dtype)
        except ValueError:
            return _pick_block(t, fwd_block, dtype)

    if stair:  # the forward's, which cut no tile where the steps allow it
        return fwd_blocks
    return pick(t_q, fwd_blocks[0]), pick(t_k, fwd_blocks[1])


@jax.named_scope(trace.SCOPE_BLOCKWISE_BWD)
def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
               window=None, stair=None, g_lse=None, selected=None):
    """``(dq, dk, dv)`` from one kernel; ``block_q`` / ``block_k`` divide
    ``t_q`` / ``t_k`` (:func:`_bwd_blocks` picks them through
    :func:`_pick_block`). Under grouped KV heads ``flash_bwd_dkv`` writes each
    query head's part of dK and dV in f32 and one XLA reduction sums a KV
    head's group."""
    b, h, t_q, d = q.shape
    h_kv, t_k, d_v = k.shape[1], k.shape[2], v.shape[3]
    group = _kv_group(h, h_kv)
    nq, nk = t_q // block_q, t_k // block_k
    # D_i = rowsum(dO * O); the log-sum-exp's own cotangent, where it was
    # handed out, is a term of it: d lse_i / d s_ij = p_ij
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    args = (
        q.reshape(b * h, t_q, d), k.reshape(b * h_kv, t_k, d), v.reshape(b * h_kv, t_k, d_v),
        g.reshape(b * h, t_q, d_v),
        lse.reshape(b * h, nq, 1, block_q), delta.reshape(b * h, nq, 1, block_q),
    )
    # a head's every [1, block_q] tile of lse / delta, resident like q
    rows_seq = pl.BlockSpec((None, nq, 1, block_q), lambda i, j: (i, 0, 0, 0))
    part = jnp.float32 if group > 1 else None  # a query head's part of dK, dV
    _note_call("dkv", q, t_k, d_v, group, causal, window, block_q, block_k, stair,
               selected is not None)
    more_specs = []
    if selected:
        more_specs = [_selected_rows(block_k, selected.cols.shape[2], h), _SMEM]
        args += (selected.cols, selected.tiles.reshape(b * nq, nk))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_selected_kernel if selected else _flash_bwd_dkv_kernel,
            block_q=block_q, causal=causal, sm_scale=sm_scale,
            window=window, **({"stair": stair} if stair else {}),
            **({"selected": (selection_layout(t_q), h)} if selected else {}),
        ),
        grid=(b * h, nk),
        in_specs=[_head_seq(t_q, d, buffers=1), _head_block(block_k, d, group),
                  _head_block(block_k, d_v, group), _head_seq(t_q, d_v, buffers=1),
                  rows_seq, rows_seq, *more_specs],
        out_specs=[_head_seq(t_q, d, buffers=1), _head_block(block_k, d),
                   _head_block(block_k, d_v)],
        out_shape=[jax.ShapeDtypeStruct((b * h, t_q, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, t_k, d), part or k.dtype),
                   jax.ShapeDtypeStruct((b * h, t_k, d_v), part or v.dtype)],
        scratch_shapes=[pltpu.VMEM((t_q, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        interpret=interpret,
        name=trace.FLASH_BWD_DKV_KERNEL_NAME,
        compiler_params=_mosaic_params(
            q.dtype, (t_q, d), (t_q, d_v), (t_q, d), scratch=[(t_q, d)], buffers=1),
    )(*args)
    if group > 1:
        dk = dk.reshape(b, h_kv, group, t_k, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, h_kv, group, t_k, d_v).sum(axis=2).astype(v.dtype)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# Public API: pallas forward + pallas backward
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
):
    """Blockwise fused attention for ``[B, H, T, D_qk]`` queries, ``[B, H_kv,
    T_k, D_qk]`` keys and ``[B, H_kv, T_k, D_v]`` values (``H_kv`` divides
    ``H``; see :func:`attention_reference` for the grouping, for ``window``
    and for the two widths); the output is ``[B, H, T, D_v]``.

    Forward = pallas kernel (interpreter mode on the CPU); backward = one
    pallas kernel that recomputes the scores blockwise from the forward's
    log-sum-exp — O(T·block) memory in both directions, the [T, T] score
    matrix is never materialized. Both directions choose their tiles from the
    shape and dtype; ``block_q`` / ``block_k`` override the forward's.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_lse(q, k, v, causal=False, sm_scale=None, block_q=None, block_k=None,
                        window=None, stair=None, keep=None):
    """:func:`flash_attention` with each row's log-sum-exp as a second,
    differentiable output: ``(out [B, H, T, D_v], lse [B, H, T] float32)``,
    from the same two kernels (the forward writes the log-sum-exp anyway; its
    cotangent enters the backward as a term of the row's ``delta``). Two
    calls over two key sets then share one softmax: ``ops/eva.py`` merges
    them. ``stair=(s_q, s_k)`` is the staircase mask of
    :func:`attention_reference`. ``keep``: the five names a rematerialised
    block keeps this call's q, k, v, out and lse under (``None``:
    ``remat.ATTN_RESIDUALS``)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, stair)


def _lse_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, window, stair, keep):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, stair)
    # kept by a rematerialised block (ops/remat.py), whose backward then has
    # no use for a second forward call, nor for the projections and rotary
    # positions that made q, k and v
    q, k, v, out, lse = (remat.keep(name, x) for name, x in zip(
        keep or remat.ATTN_RESIDUALS, (q, k, v, out, lse)))
    return (out, lse), (q, k, v, out, lse)


def _lse_bwd_rule(causal, sm_scale, block_q, block_k, window, stair, keep, res, g):
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    blocks = _bwd_blocks(q.shape[2], k.shape[2], q.dtype, _fwd_blocks(
        q.shape[2], k.shape[2], q.dtype, block_q, block_k, stair), stair)
    trace.event(
        "attn/bwd_path", impl="fused", shape=tuple(q.shape), t_k=k.shape[2],
        dtype=jnp.dtype(q.dtype).name, blocks=blocks,
    )
    interpret = _interpret_on(jax.default_backend())
    return _flash_bwd(q, k, v, out, lse, g[0], causal, sm_scale, *blocks, interpret, window,
                      stair, g_lse=g[1])


flash_attention_lse.defvjp(_lse_fwd_rule, _lse_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def flash_attention_selected(q, k, v, selection: Selection, sm_scale=None):
    """``(out, lse)`` as :func:`flash_attention_lse` gives them, query ``i``
    seeing the keys ``selection`` chose for it (one set a query, every head
    under it; none after the query; ``T_q == T_k``). The two kernels run the
    causal tiles under the set's packed bits, a tile of no chosen pair skipped
    by its count, on the blocks ``selection.tiles`` was counted in. No
    gradient reaches the selection."""
    return _selected_fwd_rule(q, k, v, selection, sm_scale)[0]


def _selection_blocks(q, k, selection):
    if q.shape[2] != k.shape[2]:
        raise ValueError("attention: a selected set needs as many queries as keys")
    nq, nk = selection.tiles.shape[1:]
    return q.shape[2] // nq, k.shape[2] // nk


def _selected_fwd_rule(q, k, v, selection, sm_scale):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    out, lse = _flash_fwd(q, k, v, True, sm_scale, *_selection_blocks(q, k, selection),
                          interpret, selected=selection)
    q, k, v, out, lse = (remat.keep(name, x) for name, x in zip(
        remat.ATTN_RESIDUALS, (q, k, v, out, lse)))
    return (out, lse), (q, k, v, out, lse, selection)


def _selected_bwd_rule(sm_scale, res, g):
    q, k, v, out, lse, selection = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = _interpret_on(jax.default_backend())
    grads = _flash_bwd(q, k, v, out, lse, g[0], True, sm_scale,
                       *_selection_blocks(q, k, selection), interpret, g_lse=g[1],
                       selected=selection)
    no_grad = jax.tree.map(lambda x: np.zeros(x.shape, jax.dtypes.float0), selection)
    return (*grads, no_grad)


flash_attention_selected.defvjp(_selected_fwd_rule, _selected_bwd_rule)


def flash_attention_head_parallel(
    q,
    k,
    v,
    *,
    axis: str | None,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
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
        return flash_attention(q, k, v, causal, sm_scale, block_q, block_k, window)
    n_ranks = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    n_heads = q.shape[1]
    # grouped KV heads split with their query heads or not at all
    if n_heads % n_ranks or k.shape[1] % n_ranks:
        import logging

        logging.getLogger(__name__).warning(
            "flash attention under a %d-way %r model axis: %d heads do not "
            "divide the axis, so the pallas kernel cannot run per-rank — "
            "falling back to gathered xla attention for this program; pick "
            "num_heads divisible by the model axis to keep the kernel on "
            "the sharded path",
            n_ranks, axis, n_heads,
        )
        return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale, window=window)
    from jax.sharding import PartitionSpec

    hspec = PartitionSpec(None, axis if n_ranks > 1 else None, None, None)
    return jax.shard_map(
        functools.partial(
            flash_attention, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, window=window,
        ),
        mesh=mesh, in_specs=(hspec,) * 3, out_specs=hspec, check_vma=False,
    )(q, k, v)


def _fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, window):
    (out, _), res = _lse_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, window, None, None)
    return out, res


def _bwd_rule(causal, sm_scale, block_q, block_k, window, res, g):
    return _lse_bwd_rule(causal, sm_scale, block_q, block_k, window, None, None, res, (g, None))


flash_attention.defvjp(_fwd_rule, _bwd_rule)
