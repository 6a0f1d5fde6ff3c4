"""Learned sparse attention (DSA: the lightning indexer and top-k selection of
the DeepSeek-V3.2 report) over grouped-query attention, with its index loss.

An indexer of ``J`` small heads scores every earlier key for every query, each
query attends to its ``topk`` best, and the indexer learns from one loss of
its own. With ``qI`` ``[T, J, d]``, ``kI`` ``[T, d]`` (one key head) and ``wI``
``[T, J]`` the indexer's outputs (positions and scales already applied), and
``q``, ``k``, ``v`` the attention's own:

    I[t, s] = sum_j wI[t, j] * relu(<qI[t, j], kI[s]>)          s <= t, float32
    S_t     = the s <= t with the topk largest I[t, s]           (all of them while t < topk;
                                                                 ties to the lower s, as lax.top_k)
    o[t, n] = softmax over s in S_t of (scale <q[t, n], k[s, n // g]>) times v[s, n // g]
    phat[t, s] = mean over the heads n of that softmax           (no gradient)
    L_I     = mean over t of KL(phat[t, .] || softmax over S_t of I[t, .])

One set a query, shared by every head. No gradient passes through the choice
of ``S_t``; the attention's loss moves nothing of the indexer and ``L_I``
nothing else (the model hands the indexer a stream under ``stop_gradient``).

How it runs. No ``[T, T]`` float32 array is ever whole: the index scores are
made a block of queries at a time against the keys up to the block's causal
group (``CAUSAL_GROUPS`` groups of blocks, each with the keys its last query
can see: 62.5% of the square at four groups where the triangle is 53%), in
plain XLA. **The selection finds each row's ``topk``-th largest score and not
the order of the rest**, then takes everything above that threshold and, of
the scores equal to it, the lowest positions (``lax.top_k`` at k 2,048 is a
full sort on the TPU). Under ``impl`` "flash" that is one Mosaic kernel
(``ops/dsa_select.py``, PR 49): 128 rows of a block's scores are read once and
held in VMEM with their ordered integer keys while the threshold is searched a
bit a pass over the lane tiles up to the diagonal (a pass costs no HBM traffic
there, so what is paid is the compares an element: 32 at a bit a pass where
four bits a pass, the form that reads HBM least, pays 120), the cut among
equal scores is searched the same way over the position's bits only in a
block that has such a row, and the set leaves packed, with the row's share of
the indexer's softmax and a flag a block. The set's columns and tile counts
are made from the packed words by XLA (:func:`selection_from_rows`); no ``[T,
T]`` array of bits is written. Under "xla" it is :func:`_choose` in ``jnp``
(:func:`_threshold`: four bits a pass, fifteen compare-and-counts a pass; in
the compiled cell seventeen fusions a block, each a read of the block from
HBM at two thirds of its bandwidth, PERF.md section 6, PR 49; a prefix sum
for the ties): the plain path and the kernel's second oracle. What leaves either way is an
``ops/attention.py`` :class:`Selection`: the set's bits packed 32 to a word
both ways and the count of chosen pairs a tile, 16.8 MB a layer at T 8,192,
kept by name under ``remat`` so that the set is made once
(``ops/remat.py``). The masked flash kernels read it
(:func:`flash_attention_selected`). The index loss's forward rule makes, in
one more blocked pass (its own scores, the heads' ``exp(scale q k^T - lse)``
from the kernel's log-sum-exp, their mean, the KL and its gradient by the
scores), the loss and its gradients by ``qI``, ``kI`` and ``wI``; the
backward rule scales them by the loss's cotangent. Under ``impl`` "flash"
that pass is two Mosaic kernels (``ops/dsa_index_loss.py``, PR 50: every
``[keys, queries]`` tile of the 32 heads' scores and of the indexer's made
and spent in VMEM, the causal tiles alone walked) wherever they can tile the
shape; under "xla", and at any other shape, ``LOSS_ROWS`` queries a step
against their causal group's keys in ``jnp`` (:func:`_index_loss_plain`:
``[heads, 256, keys]`` float32 arrays through HBM, 12.6 ms a layer at the
cell's shape where the kernels take about half, PERF.md section 6, PR 50):
the plain path and the kernels' second oracle.

Scopes (``obs/trace.py`` ``DSA_SCOPES``): ``attn/dsa/index/scores``,
``attn/dsa/select``, ``attn/dsa/index_loss``; every call leaves a ``dsa/call``
program note. :func:`dsa_reference` is the same mathematics over the whole
score matrix with ``lax.top_k``, the oracle of the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.obs import trace
from fedml_tpu.ops import dsa_index_loss, dsa_select, remat
from fedml_tpu.ops.attention import (
    Selection, _fwd_blocks, attention_reference, flash_attention_selected, selection_layout)

NOTE = "dsa/call"
# what runs under attn/dsa/select, for the note, by ``impl``: the Mosaic kernel
# of ops/dsa_select.py (PR 49: a block of scores held in VMEM while its
# threshold is searched a bit a pass, the set written packed) or _choose in
# jnp (PR 48: four bits a pass, each a read of the block from HBM; lax.top_k's
# last value in its place took 24.2 ms a layer against 11.6, PERF.md section 6)
SELECT_IMPL = {"flash": "mosaic", "xla": "radix"}
TILE = 512  # the selection's tiles are the masked kernels' blocks (attention._fwd_blocks' own side)
LOSS_ROWS = 256  # queries a step of the index loss's plain pass: [B, H, 256, T] float32 scores
CAUSAL_GROUPS = 4


# -- the chosen set, packed ---------------------------------------------------


def pack_bits(mask):
    """Bool ``[..., R, C]`` -> int32 ``[..., R, W]`` in ``selection_layout(C)``."""
    c = mask.shape[-1]
    lanes, planes = selection_layout(c)
    bits = mask.reshape(*mask.shape[:-1], c // lanes // planes, planes, lanes).astype(jnp.int32)
    words = jnp.sum(bits << jnp.arange(planes, dtype=jnp.int32)[:, None], axis=-2)
    return words.reshape(*mask.shape[:-1], c // planes)


def unpack_bits(words, c: int):
    """:func:`pack_bits`' inverse: int32 ``[..., R, W]`` -> bool ``[..., R, c]``."""
    lanes, planes = selection_layout(c)
    words = words.reshape(*words.shape[:-1], c // lanes // planes, 1, lanes)
    plane = jnp.arange(planes, dtype=jnp.int32)[:, None]
    bits = jax.lax.shift_right_logical(*jnp.broadcast_arrays(words, plane)) & 1
    return bits.reshape(*words.shape[:-3], c) != 0


def _whole_runs(t: int, block: int) -> None:
    if t % block or block % selection_layout(t)[0]:
        raise ValueError(f"dsa: a block of {block} is not whole runs of "
                         f"{selection_layout(t)[0]} packed positions of {t}")


def selection_from_mask(chosen, block_q: int, block_k: int | None = None) -> Selection:
    """The :class:`Selection` of a bool ``[B, T_q, T_k]`` set (no key after its
    query), its pairs counted in tiles of ``block_q x block_k``. A tile's keys
    and queries must be whole runs of the packing's lanes."""
    b, t_q, t_k = chosen.shape
    block_k = block_k or block_q
    _whole_runs(t_q, block_q)
    _whole_runs(t_k, block_k)
    tiles = jnp.sum(chosen.reshape(b, t_q // block_q, block_q, t_k // block_k, block_k),
                    axis=(2, 4), dtype=jnp.int32)
    return Selection(pack_bits(chosen), pack_bits(chosen.swapaxes(1, 2)), tiles)


def selection_from_rows(rows, block: int) -> Selection:
    """The :class:`Selection` whose packed ``rows`` ``[B, T, W]`` are given (a
    square set), its pairs counted in tiles of ``block x block``: the columns
    and the counts from the words, no ``[T, T]`` array of bits between."""
    b, t, _ = rows.shape
    lanes, planes = selection_layout(t)
    groups = t // lanes // planes
    _whole_runs(t, block)
    plane = jnp.arange(planes, dtype=jnp.int32)
    # words with a key's lane down the rows: [b, g_k, l, g_q, p_q, r]; then key
    # plane p_k of each becomes bit p_q of the key's word
    by_key = rows.reshape(b, groups, planes, lanes, groups, lanes).transpose(0, 4, 5, 1, 2, 3)
    bit = (by_key[:, :, None] >> plane[:, None, None, None, None]) & 1
    cols = jnp.sum(bit << plane[:, None], axis=5, dtype=jnp.int32).reshape(rows.shape)
    by_tile = rows.reshape(b, t // block, block, groups, 1, lanes)
    per_plane = jnp.sum((by_tile >> plane[:, None]) & 1, axis=(2, 5), dtype=jnp.int32)
    tiles = per_plane.reshape(b, t // block, t // block, block // lanes).sum(-1)
    return Selection(rows, cols, tiles)


# -- the index scores and the selection ---------------------------------------


def index_scores(qi, ki, wi):
    """``I`` ``[B, T_q, T_k]`` float32 (no mask) and the heads' products ``z``
    ``[B, J, T_q, T_k]`` it was summed from; ``qi`` ``[B, J, T_q, d]``, ``ki``
    ``[B, T_k, d]``, ``wi`` ``[B, T_q, J]``."""
    z = jnp.einsum("bjqd,bkd->bjqk", qi, ki, preferred_element_type=jnp.float32)
    w = wi.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    return jnp.sum(w * jax.nn.relu(z), axis=1), z


def _ordered(x):
    """float32 -> uint32 with the same order (``-0.0`` below ``0.0``); every
    finite value and both infinities give a key above 0."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def _threshold(u, want):
    """The largest ``tau`` with ``count(u >= tau) >= want`` along the last axis
    (the ``want``-th largest key; ``1 <= want <= u.shape[-1]``): the key's
    bits four at a time from the top, a pass counting the keys at or above
    each of the fifteen candidates that extend the bits found so far."""
    prefix = jnp.zeros(u.shape[:-1], jnp.uint32)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)
    for shift in range(28, -1, -4):
        cands = prefix[..., None] | (digits << shift)
        counts = jnp.sum(u[..., None, :] >= cands[..., None], axis=-1, dtype=jnp.int32)
        # the counts fall as the candidate rises: the largest that still
        # holds ``want`` keys is as many candidates up as hold them
        digit = jnp.sum(counts >= want[..., None], axis=-1).astype(jnp.uint32)
        prefix = prefix | (digit << shift)
    return prefix


def _choose(scores, first_row, topk: int):
    """``(chosen bool [B, R, K], mass [B, R], tied bool)`` of a block of rows
    ``first_row ...`` of the index scores against keys ``0 ... K - 1``:
    ``S_t``, the share of the row's softmax over every visible key that lies
    on it, and whether a row held more keys equal to its threshold than it
    needed (the block took the prefix sum). The plain form: what ``impl``
    "xla" runs, and the kernel's oracle beside ``lax.top_k``."""
    rows, keys = scores.shape[-2:]
    pos = first_row + jnp.arange(rows)
    valid = jnp.arange(keys)[None] <= pos[:, None]
    want = jnp.minimum(topk, pos + 1)
    # one zero, as top_k compares them (a select: XLA folds ``x + 0.0`` away)
    scores = jnp.where(scores == 0.0, 0.0, scores)
    u = jnp.where(valid, _ordered(scores), jnp.uint32(0))
    tau = _threshold(u, jnp.broadcast_to(want, u.shape[:-1]))
    above, ties = u > tau[..., None], u == tau[..., None]
    need = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    tied = jnp.any(jnp.sum(ties, axis=-1, dtype=jnp.int32) != need)
    chosen = jax.lax.cond(
        tied,
        lambda: above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= need[..., None])),
        lambda: above | ties)
    lse = lambda m: jax.nn.logsumexp(jnp.where(m, scores, -jnp.inf), axis=-1)  # noqa: E731
    return chosen, jnp.exp(lse(chosen) - lse(valid)), tied


def _causal_groups(t: int, rows: int) -> list:
    """``[(first block, blocks, keys)]``: the blocks of ``rows`` queries in up
    to ``CAUSAL_GROUPS`` equal groups, each with the keys its last query sees."""
    n = t // rows
    groups = min(CAUSAL_GROUPS, n)
    while n % groups:
        groups -= 1
    per = n // groups
    return [(g * per, per, (g + 1) * per * rows) for g in range(groups)]


def _rows(x, lo, n, axis):
    return jax.lax.dynamic_slice_in_dim(x, lo, n, axis=axis)


def select(qi, ki, wi, topk: int, block: int, impl: str = "flash"):
    """``(Selection, mass, tie_blocks)``: every query's ``S_t`` as the kernels
    read it, its tiles ``block x block``; the mean over the queries ``t >=
    topk`` of the share of ``softmax(I[t, :t + 1])`` that lies on ``S_t`` (1
    where ``T <= topk``); and the blocks that took the tie path over the
    blocks searched (one whose rows all have ``t < topk`` is not searched).
    ``impl`` "flash": a block of scores goes through
    ``dsa_select.select_rows`` and leaves as packed words, and a counted block
    is a grid step of the kernel (128 rows at the cell's shape); "xla":
    :func:`_choose` and :func:`selection_from_mask`, a counted block ``block``
    rows, so the share reads higher there on the same scores."""
    b, _, t, _ = qi.shape
    kernel = impl == "flash"
    chosen, mass, flags = [], [], []
    for first, n, keys in _causal_groups(t, block):

        def one(i, first=first, keys=keys):
            lo = (first + i) * block
            with jax.named_scope(trace.SCOPE_DSA_SCORES):
                scores, _ = index_scores(_rows(qi, lo, block, 2), ki[:, :keys],
                                         _rows(wi, lo, block, 1))
            with jax.named_scope(trace.SCOPE_DSA_SELECT):
                if kernel:
                    return dsa_select.select_rows(scores, lo, topk, t)
                got, share, tied = _choose(scores, lo, topk)
                flag = jnp.where(lo + block <= topk, dsa_select.SKIPPED,
                                 jnp.where(tied, dsa_select.TIED, dsa_select.SEARCHED))
                return (jnp.pad(got, ((0, 0), (0, 0), (0, t - keys))), share,
                        jnp.full((b, 1), flag, jnp.int32))

        got, share, flag = jax.lax.map(one, jnp.arange(n))  # [n, B, block, ...], [n, B, steps]
        chosen.append(got)
        mass.append(share)
        flags.append(flag.reshape(-1))  # a group's steps follow its keys (dsa_select.tiling)
    with jax.named_scope(trace.SCOPE_DSA_SELECT):
        chosen = jnp.concatenate(chosen).transpose(1, 0, 2, 3).reshape(b, t, -1)
        mass = jnp.concatenate(mass).transpose(1, 0, 2).reshape(b, t)
        mass = jnp.mean(mass[:, topk:]) if t > topk else jnp.float32(1.0)
        flags = jnp.concatenate(flags)
        tie_blocks = (jnp.sum(flags == dsa_select.TIED)
                      / jnp.maximum(jnp.sum(flags != dsa_select.SKIPPED), 1))
        selection = (selection_from_rows if kernel else selection_from_mask)(chosen, block)
        return selection, mass, tie_blocks.astype(jnp.float32)


# -- the index loss -----------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def index_loss(qi, ki, wi, q, k, lse, selection: Selection, sm_scale: float, impl: str = "xla"):
    """``L_I`` (a float32 scalar: the mean over the batch's queries) from the
    indexer's outputs, the attention's ``q`` ``[B, H, T, D]``, ``k`` ``[B, H_kv,
    T, D]`` and log-sum-exp ``[B, H, T]`` over the chosen keys, and the chosen
    set. Differentiable by ``qi``, ``ki`` and ``wi`` alone. ``impl`` "flash":
    the pass is ``dsa_index_loss.index_loss_grads``' two Mosaic kernels over
    the set's packed ``cols`` wherever they can tile the shape; "xla", and any
    other shape: the blocked pass in ``jnp`` over its packed ``rows``, the
    plain path and the kernels' second oracle."""
    return _index_loss_fwd(qi, ki, wi, q, k, lse, selection, sm_scale, impl)[0]


def index_loss_impl(impl: str, q, k, qi) -> str:
    """What runs the index loss's pass under ``impl`` at these operands'
    shapes, for the ``dsa/call`` note: "mosaic" or "xla" (the shape alone
    decides where the kernels cannot tile it)."""
    if impl != "flash":
        return "xla"
    try:
        dsa_index_loss.tiling(q.shape[2], q.shape[1], k.shape[1], q.shape[3], qi.shape[1],
                              qi.shape[3], q.dtype, qi.dtype)
    except ValueError:
        return "xla"
    return "mosaic"


def _index_loss_fwd(qi, ki, wi, q, k, lse, selection, sm_scale, impl):
    if index_loss_impl(impl, q, k, qi) == "mosaic":
        with jax.named_scope(trace.SCOPE_DSA_INDEX_LOSS):
            kl, *grads = dsa_index_loss.index_loss_grads(
                qi, ki, wi, q, k, lse, selection.cols, sm_scale)
            grads = tuple(remat.keep(name, x) for name, x in zip(remat.DSA_INDEX_GRADS, grads))
    else:
        kl, grads = _index_loss_plain(qi, ki, wi, q, k, lse, selection.rows, sm_scale)
    return kl, (grads, q, k, lse, selection)


def _index_loss_plain(qi, ki, wi, q, k, lse, rows, sm_scale):
    b, _, t, d = qi.shape
    h, h_kv = q.shape[1], k.shape[1]
    block = min(LOSS_ROWS, t)
    while t % block:
        block -= 1
    d_qi, d_wi, kl = [], [], jnp.float32(0.0)
    d_ki = jnp.zeros((b, t, d), jnp.float32)
    with jax.named_scope(trace.SCOPE_DSA_INDEX_LOSS):
        for first, n, keys in _causal_groups(t, block):
            ki_g, k_g = ki[:, :keys], k[:, :, :keys]

            def step(carry, i, first=first, keys=keys, ki_g=ki_g, k_g=k_g):
                d_ki, kl = carry
                lo = (first + i) * block
                qi_b, wi_b = _rows(qi, lo, block, 2), _rows(wi, lo, block, 1)
                chosen = unpack_bits(_rows(rows, lo, block, 1), t)[..., :keys]
                scores, z = index_scores(qi_b, ki_g, wi_b)
                logit = jnp.where(chosen, scores, -jnp.inf)
                log_sigma = logit - jax.nn.logsumexp(logit, axis=-1, keepdims=True)
                # the heads' softmax over the chosen keys, from the kernel's log-sum-exp
                s = jnp.einsum("bngqd,bnkd->bngqk",
                               _rows(q, lo, block, 2).reshape(b, h_kv, h // h_kv, block, -1), k_g,
                               preferred_element_type=jnp.float32) * sm_scale
                lse_b = _rows(lse, lo, block, 2).reshape(b, h_kv, h // h_kv, block, 1)
                p_hat = jnp.where(chosen, jnp.mean(jnp.exp(jnp.minimum(s - lse_b, 0.0)),
                                                   axis=(1, 2)), 0.0)
                kl_rows = jnp.sum(jax.scipy.special.xlogy(p_hat, p_hat)
                                  - p_hat * jnp.where(chosen, log_sigma, 0.0), axis=-1)
                # d (mean KL) / d scores, then back through the scores' sum of ReLUs
                g = (jnp.where(chosen, jnp.exp(log_sigma), 0.0) - p_hat) / (b * t)
                d_w = jnp.sum(g[:, None] * jax.nn.relu(z), axis=-1).transpose(0, 2, 1)
                w = wi_b.astype(jnp.float32).transpose(0, 2, 1)[..., None]
                d_z = jnp.where(z > 0, g[:, None] * w, 0.0).astype(qi.dtype)
                d_q = jnp.einsum("bjqk,bkd->bjqd", d_z, ki_g, preferred_element_type=jnp.float32)
                d_ki = d_ki.at[:, :keys].add(jnp.einsum(
                    "bjqk,bjqd->bkd", d_z, qi_b, preferred_element_type=jnp.float32))
                return (d_ki, kl + jnp.sum(kl_rows)), (d_q.astype(qi.dtype), d_w.astype(wi.dtype))

            (d_ki, kl), (d_q, d_w) = jax.lax.scan(step, (d_ki, kl), jnp.arange(n))
            d_qi.append(d_q)  # [n, B, J, block, d]
            d_wi.append(d_w)  # [n, B, block, J]
        d_qi = jnp.concatenate(d_qi).transpose(1, 2, 0, 3, 4).reshape(qi.shape)
        d_wi = jnp.concatenate(d_wi).transpose(1, 0, 2, 3).reshape(wi.shape)
        grads = tuple(remat.keep(name, x) for name, x in zip(
            remat.DSA_INDEX_GRADS, (d_qi, d_ki.astype(ki.dtype), d_wi)))
    return kl / (b * t), grads


def _index_loss_bwd(sm_scale, impl, res, g):
    grads, q, k, lse, selection = res
    with jax.named_scope(trace.SCOPE_DSA_INDEX_LOSS):
        scaled = tuple((g * x.astype(jnp.float32)).astype(x.dtype) for x in grads)
    return (*scaled, jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
            jax.tree.map(lambda x: np.zeros(x.shape, jax.dtypes.float0), selection))


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


# -- the mixer's attention ----------------------------------------------------


def sparse_attention(q, k, v, qi, ki, wi, *, topk: int, impl: str = "flash",
                     with_loss: bool = True):
    """``(out [B, H, T, D], stats)``: attention of ``q`` ``[B, H, T, D]`` over
    the keys the indexer chose for each query (the module docstring), and
    ``dsa/tiles_nonempty`` (tiles holding a chosen pair over the causal
    tiles), ``dsa/index_mass``, ``dsa/select_tie_blocks`` (the selection's
    blocks that took the tie path over the blocks searched) and,
    ``with_loss``, ``dsa/index_kl`` (``L_I``, the one entry a gradient passes
    through). ``impl`` "flash": the selection kernel and the masked kernels;
    "xla": :func:`_choose` and :func:`attention_reference` under the unpacked
    set."""
    b, h, t, d = q.shape
    sm_scale = d ** -0.5
    block = _fwd_blocks(t, t, q.dtype, TILE, TILE)[0]
    trace.program_note(
        NOTE, impl=impl, select=SELECT_IMPL[impl], index_loss=index_loss_impl(impl, q, k, qi),
        shape=(b, h, t, d), kv_heads=k.shape[1],
        index_heads=qi.shape[1], index_dim=qi.shape[3], topk=topk, tile=(block, block),
        dtype=jnp.dtype(q.dtype).name, index_dtype=jnp.dtype(qi.dtype).name,
        selection_bytes=b * (2 * t * (t // 32) + (t // block) ** 2) * 4)
    selection, mass, tie_blocks = select(qi, ki, wi, topk, block, impl)
    # kept by a rematerialised block (ops/remat.py): the set is made once
    selection = Selection(*(remat.keep(name, x) for name, x in zip(
        remat.DSA_SELECTION, selection)))
    if impl == "flash":
        from fedml_tpu.parallel.mesh import current_mesh

        mesh = current_mesh()
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError("dsa: the masked kernels run on one device's plan")
        out, lse = flash_attention_selected(q, k, v, selection, sm_scale)
    else:
        out, lse = attention_reference(q, k, v, sm_scale=sm_scale, with_lse=True,
                                       selected=unpack_bits(selection.rows, t))
    nq = t // block
    stats = {
        "dsa/tiles_nonempty": jnp.sum(selection.tiles > 0) / (b * nq * (nq + 1) / 2.0),
        "dsa/index_mass": jax.lax.stop_gradient(mass),
        "dsa/select_tie_blocks": tie_blocks,
    }
    if with_loss:
        no_grad = jax.lax.stop_gradient
        stats["dsa/index_kl"] = index_loss(qi, ki, wi, no_grad(q), no_grad(k), no_grad(lse),
                                           selection, sm_scale, impl)
    return out, stats


def dsa_reference(q, k, v, qi, ki, wi, *, topk: int):
    """``(out, L_I, chosen bool [B, T, T])`` with the whole ``[T, T]`` score
    matrices in float32, the set by ``lax.top_k``'s indices and the loss by
    its definition; differentiate it with ``jax.grad``."""
    b, h, t, d = q.shape
    sm_scale = d ** -0.5
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    causal = jnp.tril(jnp.ones((t, t), bool))
    z = jnp.einsum("bjqd,bkd->bjqk", f32(qi), f32(ki))
    scores = jnp.einsum("bqj,bjqk->bqk", f32(wi), jax.nn.relu(z))
    scores = jnp.where(scores == 0.0, 0.0, scores)
    _, ids = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    chosen = jnp.zeros((b, t, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None], ids].set(True) & causal
    group = h // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", f32(q), jnp.repeat(f32(k), group, axis=1)) * sm_scale
    p = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(f32(v), group, axis=1)).astype(q.dtype)
    p_hat = jax.lax.stop_gradient(jnp.mean(p, axis=1))
    log_sigma = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    kl = jnp.sum(jax.scipy.special.xlogy(p_hat, p_hat)
                 - p_hat * jnp.where(chosen, log_sigma, 0.0), axis=-1)
    return out, jnp.mean(kl), chosen
