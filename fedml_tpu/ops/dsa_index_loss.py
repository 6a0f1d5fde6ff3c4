"""The sparse-attention index loss's pass (``ops/dsa.py`` ``index_loss``) as
Mosaic kernels: ``L_I`` and its gradients by ``qI``, ``kI`` and ``wI`` with
every ``[keys, queries]`` tile of the heads' scores, the indexer's, ``p_hat``,
``sigma``, ``g`` and ``d_z`` made and spent in VMEM.

Tiles are transposed, ``[KEYS keys, ROWS queries]``, as ``flash_bwd_dkv``'s
are: what a query owns (a head's log-sum-exp, an index head's weight, the
indexer's own log-sum-exp, the KL's and ``d_wI``'s sums) is one value a lane
and meets a tile by a sublane broadcast, a sum over keys is adds of whole
registers, and the chosen bits come from the set's packed ``cols`` by a lane
slice and a shift (``attention._selected_tile``). The grid is (batch, step of
the walk): a query block's key tiles up to the diagonal, block after block,
their indices prefetched to SMEM, so a hidden tile is neither fetched nor a
grid step (544 of the square's 1,024 tiles at T 8,192). Two kernels:

1. ``dsa_index_loss_lse``: the sixteen index heads' products alone, ``I =
   sum_j wI_j relu(kI qI_j^T)``, and each query's log-sum-exp of ``I`` over its
   chosen keys (a running max and sum a lane), which ``sigma`` needs whole
   before any gradient;
2. ``dsa_index_loss``: a tile's ``I`` again with the heads' ``relu(z_j)`` kept
   (sixteen float32 tiles, 4 MiB), the 32 heads' ``exp(min(scale k q_h^T -
   lse_h, 0))`` summed in a float32 accumulator, ``p_hat``, ``sigma = exp(I -
   lse_I)``, the KL's sums, ``g = (sigma - p_hat) / (B T)``, and an index head
   at a time ``d_wI_j += sum_k g relu(z_j)``, ``d_z_j = where(z_j > 0, g
   wI_j)`` in ``qI``'s dtype and the two gradient products, both with ``d_z_j``
   as the MXU's resident operand and 64 rows streamed past it: ``d_kI^T[:,
   keys] += qI_j^T d_z_j^T`` (into the float32 output block ``[d_I, T]``,
   resident over a batch row's walk) and ``d_qI_j^T += kI^T d_z_j`` (a float32
   scratch over the query block's tiles). Neither product turns a tile: XLA
   hands the kernel ``qI`` and ``kI`` both ways and turns the two gradients
   back (16.8 MB each way a layer).

The loops are paced by the MXU's rows (a ``[512, .] x [., 128]`` product is 128
cycles on the four units whatever its depth, so the indexer's 64-wide
contractions cost what the heads' 128-wide ones do) and by one vector store a
bundle where ``z`` is kept; ``UNROLL`` bodies share a trip. Float32 wherever the
plain pass (``dsa._index_loss_plain``, ``impl`` "xla") is float32, and no
product in a lower precision. VMEM at ``[1, 32, 8192, 128]`` on 4 KV heads with
16 x 64 index heads: 11.4 MiB by :func:`tiling`'s count, inside Mosaic's default
16; nothing is asked for (a request slows XLA's own fusions: ``ops/attention.py``
``_mosaic_params``). On the CPU backend the kernels run interpreted, as the
flash kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops import attention
from fedml_tpu.ops.attention import _NN, _NT

ROWS = 128  # queries a tile, along the lanes
KEYS = 512  # keys a tile, down the sublanes
VMEM_BYTES = 14 * 2 ** 20  # what the main kernel may hold of Mosaic's default 16 MiB
# bodies a trip of the kernels' three loops (the index heads' scores, a KV head's query heads, the
# index heads' gradients): a loop's trips run one after another, so one body's MXU waits are hidden
# by another's VPU work only inside a trip (both kernels at the cell's shape, ms on the chip: 10.78
# at 1, 1, 1; 8.89 at 1, 2, 2; 8.01 at 1, 4, 4; 7.62 at 1, 8, 4: PERF.md section 6, PR 50)
UNROLL = (2, 8, 4)
NAME, LSE_NAME = "dsa_index_loss", "dsa_index_loss_lse"


def _padded(*shape):
    """Elements of a VMEM array whose last dimension fills whole 128-lane
    registers and whose second to last whole 8-row ones."""
    *lead, rows, lanes = shape
    n = -(-rows // 8) * 8 * -(-lanes // 128) * 128
    for x in lead:
        n *= x
    return n


def tiling(t: int, heads: int, kv_heads: int, d: int, index_heads: int, index_dim: int,
           dtype, index_dtype=None) -> tuple:
    """``(queries, keys)`` of a tile for a sequence of ``t`` (at most ``ROWS``
    x ``KEYS``): the queries whole runs of the packed set's
    lanes, the keys whole sublane tiles of the narrower dtype; a ValueError
    where no such tile divides ``t`` or what the main kernel keeps in VMEM
    passes ``VMEM_BYTES`` (on every backend, since Mosaic would refuse):
    ``d_kI^T`` whole in float32, a query block's ``q`` and ``qI`` both ways
    once, a tile's ``k``, ``kI`` both ways and packed words twice,
    ``index_heads + 2`` float32 tiles (the heads' ``relu(z)``, ``I``, ``p_hat``
    then ``g``) and ``d_qI^T``'s accumulator and block."""
    index_dtype = index_dtype or dtype
    size, index_size = jnp.dtype(dtype).itemsize, jnp.dtype(index_dtype).itemsize
    lanes = attention.selection_layout(t)[0]
    rows = max(min(ROWS, t) // lanes * lanes, lanes)
    while t % rows:
        rows -= lanes
    keys = attention._pick_block(t, KEYS, dtype if size <= index_size else index_dtype)
    held = (4 * _padded(index_dim, t)
            + size * (heads * _padded(rows, d) + 2 * kv_heads * _padded(keys, d))
            + index_size * (index_heads * (_padded(rows, index_dim) + _padded(index_dim, rows))
                            + 2 * (_padded(keys, index_dim) + _padded(index_dim, keys)))
            + 2 * 4 * _padded(keys, t // 32)
            + 4 * (index_heads + 2) * _padded(keys, rows)
            + (4 + index_size) * index_heads * _padded(index_dim, rows))
    if held > VMEM_BYTES:
        raise ValueError(
            f"dsa: the index loss's kernel would hold {held / 2 ** 20:.1f} MiB at T {t} in tiles "
            f"of {keys} keys x {rows} queries, over the {VMEM_BYTES >> 20} MiB it may hold")
    return rows, keys


def _last(i, rows, keys):
    """The last key tile query block ``i`` can have chosen from."""
    return ((i + 1) * rows - 1) // keys


def _loop(n: int, body, unroll: int) -> None:
    """``body(i)`` for ``i`` in ``range(n)``, ``unroll`` of them a trip of a
    ``fori_loop``: the scheduler overlaps one body's MXU waits with another's
    VPU work inside a trip, never across trips."""
    unroll = unroll if n % unroll == 0 else 1

    def trip(i, carry):
        for u in range(unroll):
            body(i * unroll + u)
        return carry

    jax.lax.fori_loop(0, n // unroll, trip, None)


def _over_keys(x, op=jnp.sum):
    """``op`` down a tile's keys, ``[keys, queries] -> [1, queries]``, in
    eight strands: one chain of ``keys / 8`` dependent register adds is what
    the whole index head's step then waits for."""
    keys, rows = x.shape
    strands = 8 if keys % 64 == 0 else 1
    return op(op(x.reshape(strands, keys // strands, rows), axis=0), axis=0, keepdims=True)


def _index_scores(ki, qi_ref, w_ref, acc_ref, z_ref=None):
    """``acc_ref[...] = I^T`` ``[keys, queries]`` float32 of a tile; the heads'
    ``relu(z_j^T)`` into ``z_ref`` where one is given. ``UNROLL[0]`` heads are
    summed before the accumulator is read and written again: with ``z`` kept
    the loop is paced by its one vector store a bundle."""
    acc_ref[...] = jnp.zeros_like(acc_ref)
    heads = qi_ref.shape[0]
    group = UNROLL[0] if heads % UNROLL[0] == 0 else 1

    def trip(n, carry):
        part = None
        for j in (n * group + u for u in range(group)):
            z = jax.lax.dot_general(ki, qi_ref[j], _NT, preferred_element_type=jnp.float32)
            z = jnp.maximum(z, 0.0)
            if z_ref is not None:
                z_ref[j] = z
            part = w_ref[j] * z if part is None else part + w_ref[j] * z
        acc_ref[...] += part
        return carry

    jax.lax.fori_loop(0, heads // group, trip, None)


def _lse_kernel(i_ref, j_ref, qi_ref, ki_ref, w_ref, cols_ref, lse_ref, i_acc, m_acc, l_acc,
                *, layout):
    # qi_ref [J, R, dI]; ki_ref [K, dI]; w_ref [J, 1, R] float32; cols_ref [K, W]; lse_ref [1, R]
    i, j = i_ref[pl.program_id(1)], j_ref[pl.program_id(1)]
    keys, rows = i_acc.shape
    last = _last(i, rows, keys)

    @pl.when(j == 0)
    def _():
        # above the mask's value, as the flash forward's running max starts
        m_acc[...] = jnp.full_like(m_acc, attention.NEG_INF / 2)
        l_acc[...] = jnp.zeros_like(l_acc)

    _index_scores(ki_ref[...], qi_ref, w_ref, i_acc)
    chosen = attention._selected_tile(cols_ref, i, rows, layout)
    x = jnp.where(chosen, i_acc[...], attention.NEG_INF)
    m = m_acc[...]
    m_new = jnp.maximum(m, _over_keys(x, jnp.max))
    l_acc[...] = l_acc[...] * jnp.exp(m - m_new) + _over_keys(jnp.exp(x - m_new))
    m_acc[...] = m_new

    @pl.when(j == last)
    def _():
        lse_ref[...] = m_acc[...] + jnp.log(jnp.maximum(l_acc[...], 1e-37))


def _loss_kernel(i_ref, j_ref, q_ref, k_ref, qi_ref, qit_ref, ki_ref, kit_ref, w_ref, lse_ref,
                 lse_i_ref, cols_ref, kl_ref, dqi_ref, dki_ref, dw_ref, i_acc, p_acc, z_acc,
                 dqi_acc, dw_acc, kl_acc, *, layout, sm_scale, scale):
    # q_ref [H, R, D]; k_ref [H_kv, K, D]; qi_ref [J, R, dI] and qit_ref [J, dI, R], ki_ref [K, dI]
    # and kit_ref [dI, K]: each with its transpose; w_ref [J, 1, R] and lse_ref [H, 1, R] float32;
    # lse_i_ref [1, R]; cols_ref [K, W]; kl_ref [1, R]; dqi_ref [J, dI, R] and dki_ref [dI, T]
    # float32 (a batch row's, resident), both transposed; dw_ref [J, 1, R]
    i, j = i_ref[pl.program_id(1)], j_ref[pl.program_id(1)]
    keys, rows = i_acc.shape
    heads, kv_heads, index_heads = q_ref.shape[0], k_ref.shape[0], qi_ref.shape[0]
    group = heads // kv_heads
    last = _last(i, rows, keys)

    @pl.when((i == 0) & (j == 0))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(j == 0)
    def _():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)
        kl_acc[...] = jnp.zeros_like(kl_acc)

    ki, kit = ki_ref[...], kit_ref[...]
    _index_scores(ki, qi_ref, w_ref, i_acc, z_acc)
    # the heads' softmax over the chosen keys, from the flash kernel's log-sum-exp
    p_acc[...] = jnp.zeros_like(p_acc)

    def kv_head(n, carry):
        k = k_ref[n]

        def head(h):
            h = n * group + h
            s = jax.lax.dot_general(k, q_ref[h], _NT, preferred_element_type=jnp.float32)
            p_acc[...] += jnp.exp(jnp.minimum(s * sm_scale - lse_ref[h], 0.0))

        _loop(group, head, UNROLL[1])
        return carry

    jax.lax.fori_loop(0, kv_heads, kv_head, None)
    chosen = attention._selected_tile(cols_ref, i, rows, layout)
    p_hat = jnp.where(chosen, p_acc[...] / heads, 0.0)
    log_sigma = i_acc[...] - lse_i_ref[...]
    # p_hat is 0 off the set, where log_sigma is finite: xlogy(p, p) - p log_sigma
    kl_acc[...] += _over_keys(p_hat * (jnp.log(jnp.maximum(p_hat, 1e-37)) - log_sigma))
    # d (mean KL) / d scores, then back through the scores' sum of ReLUs
    p_acc[...] = (jnp.where(chosen, jnp.exp(log_sigma), 0.0) - p_hat) / scale
    at = pl.ds(pl.multiple_of(j * keys, keys), keys)

    def index_head(n):
        qit, z, g = qit_ref[n], z_acc[n], p_acc[...]  # z: relu(z_n^T), kept from the scores
        dw_acc[n] += _over_keys(g * z)
        d_z = jnp.where(z > 0, g * w_ref[n], 0.0).astype(qit.dtype)
        dki_ref[:, at] += jax.lax.dot_general(qit, d_z, _NT, preferred_element_type=jnp.float32)
        dqi_acc[n] += jax.lax.dot_general(kit, d_z, _NN, preferred_element_type=jnp.float32)

    _loop(index_heads, index_head, UNROLL[2])

    @pl.when(j == last)
    def _():
        kl_ref[...] = kl_acc[...]
        dqi_ref[...] = dqi_acc[...].astype(dqi_ref.dtype)
        dw_ref[...] = dw_acc[...]


def index_loss_grads(qi, ki, wi, q, k, lse, cols, sm_scale: float):
    """``(L_I, d_qI, d_kI, d_wI)`` of ``ops/dsa.py``'s index loss (its
    docstring has the operands; ``cols`` ``[B, T, W]`` is the chosen set packed
    with a key's queries along its row, ``Selection.cols``): the loss a float32
    scalar, the gradients in their operands' dtypes. A ValueError where
    :func:`tiling` finds no tile. The call is jitted, so that a model's layers
    and its programs trace the kernels once a shape."""
    b, heads, t, d = q.shape
    rows, keys = tiling(t, heads, k.shape[1], d, qi.shape[1], qi.shape[3], q.dtype, qi.dtype)
    return _call(qi, ki, wi, q, k, lse, cols, sm_scale, rows, keys,
                 attention._interpret_on(jax.default_backend()))


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _call(qi, ki, wi, q, k, lse, cols, sm_scale, rows, keys, interpret):
    b, heads, t, d = q.shape
    kv_heads, (index_heads, index_dim) = k.shape[1], qi.shape[1::2]
    n_rows = t // rows
    layout = attention.selection_layout(t)
    f32 = jnp.float32
    # the walk: a query block's key tiles up to the diagonal, block after block, from SMEM
    walk = np.array([(i, j) for i in range(n_rows) for j in range(_last(i, rows, keys) + 1)],
                    np.int32).T
    once = {"pipeline_mode": pl.Buffered(1)}  # changes once a query block
    # what a query owns, a value a lane: [.., query block, 1, rows]
    w = wi.astype(f32).transpose(0, 2, 1).reshape(b, index_heads, n_rows, 1, rows)
    lse = lse.astype(f32).reshape(b, heads, n_rows, 1, rows)
    qi_spec = pl.BlockSpec((None, index_heads, rows, index_dim),
                           lambda n, s, i, j: (n, 0, i[s], 0), **once)
    qit_spec = pl.BlockSpec((None, index_heads, index_dim, rows),  # qI^T in, d_qI^T out
                            lambda n, s, i, j: (n, 0, 0, i[s]), **once)
    ki_spec = pl.BlockSpec((None, keys, index_dim), lambda n, s, i, j: (n, j[s], 0))
    w_spec = pl.BlockSpec((None, index_heads, None, 1, rows), lambda n, s, i, j: (n, 0, i[s], 0, 0))
    cols_spec = pl.BlockSpec((None, keys, cols.shape[2]), lambda n, s, i, j: (n, j[s], 0))
    row_spec = pl.BlockSpec((None, None, 1, rows), lambda n, s, i, j: (n, i[s], 0, 0))
    row_shape = jax.ShapeDtypeStruct((b, n_rows, 1, rows), f32)
    tile_f32 = pltpu.VMEM((keys, rows), f32)
    grid = {"num_scalar_prefetch": 2, "grid": (b, walk.shape[1])}
    lse_i = pl.pallas_call(
        functools.partial(_lse_kernel, layout=layout),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            **grid, in_specs=[qi_spec, ki_spec, w_spec, cols_spec], out_specs=row_spec,
            scratch_shapes=[tile_f32, pltpu.VMEM((1, rows), f32), pltpu.VMEM((1, rows), f32)]),
        out_shape=row_shape, interpret=interpret, name=LSE_NAME,
    )(*walk, qi, ki, w, cols)
    kl, d_qi, d_ki, d_w = pl.pallas_call(
        functools.partial(_loss_kernel, layout=layout, sm_scale=sm_scale, scale=float(b * t)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            **grid,
            in_specs=[pl.BlockSpec((None, heads, rows, d), lambda n, s, i, j: (n, 0, i[s], 0),
                                   **once),
                      pl.BlockSpec((None, kv_heads, keys, d), lambda n, s, i, j: (n, 0, j[s], 0)),
                      qi_spec, qit_spec, ki_spec,
                      pl.BlockSpec((None, index_dim, keys), lambda n, s, i, j: (n, 0, j[s])),
                      w_spec,
                      pl.BlockSpec((None, heads, None, 1, rows),
                                   lambda n, s, i, j: (n, 0, i[s], 0, 0)),
                      row_spec, cols_spec],
            out_specs=[row_spec, qit_spec,
                       pl.BlockSpec((None, index_dim, t), lambda n, s, i, j: (n, 0, 0), **once),
                       w_spec],
            scratch_shapes=[tile_f32, tile_f32, pltpu.VMEM((index_heads, keys, rows), f32),
                            pltpu.VMEM((index_heads, index_dim, rows), f32),
                            pltpu.VMEM((index_heads, 1, rows), f32), pltpu.VMEM((1, rows), f32)]),
        out_shape=[row_shape, jax.ShapeDtypeStruct((b, index_heads, index_dim, t), qi.dtype),
                   jax.ShapeDtypeStruct((b, index_dim, t), f32),
                   jax.ShapeDtypeStruct(w.shape, f32)],
        interpret=interpret, name=NAME,
    )(*walk, q, k, qi, qi.transpose(0, 1, 3, 2), ki, ki.transpose(0, 2, 1), w, lse, lse_i, cols)
    d_w = d_w.reshape(b, index_heads, t).transpose(0, 2, 1)
    return (jnp.sum(kl) / (b * t), d_qi.transpose(0, 1, 3, 2),
            d_ki.transpose(0, 2, 1).astype(ki.dtype), d_w.astype(wi.dtype))
