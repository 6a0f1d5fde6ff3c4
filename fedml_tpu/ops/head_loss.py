"""An LM head and its cross-entropy in chunks of rows, the gradient formed in
the forward pass.

A decoder's last step is ``logits = h @ W (+ b)`` over ``[rows, V]`` and the
masked cross-entropy of those logits. Written plainly, a training step holds
the float32 logits, their gradient of the same size, and whatever copies XLA
makes of the gradient to feed the weight-gradient product: 1.2 GB each at
``[8192, 37984]``, passed over several times by elementwise ops. Here

    head_loss(operands, targets, mask) -> sum(ce * mask)

works ``chunk_rows`` rows at a time under one ``jax.custom_vjp``. A chunk's
trip forms its logits in float32 (the head's own dtype and jax's default
precision first, as the plain head does), their log-sum-exp and the chunk's
loss, and at once ``dlogits = (softmax - onehot) * mask``,
``dh_chunk = dlogits W^T``, ``dW += h_chunk^T dlogits`` and ``db +=
sum(dlogits)``: the same three products, none computed twice. What the
backward keeps is ``dh`` ``[rows, D]``, ``dW`` and ``db``; it multiplies them
by the scalar cotangent. No ``[rows, V]`` array exists, float32 or bf16.

The chunking is a function of the shape alone (:func:`chunking`): logits of
``WHOLE_BYTES`` or less are one chunk, and then the operator *is* the plain
expression (``flax.linen.Dense`` / ``Embed.attend``, then
``optax.softmax_cross_entropy_with_integer_labels``): no ``custom_vjp``, the
plain head's jaxpr (a CPU test holds it), and a decoder does not even hand
its operands over (:func:`decoder_head`): the trainer gets logits, the
parent's program. Plain ``jnp`` / ``lax`` throughout, so
XLA's SPMD partitions a ``V``-sharded kernel as it partitions the plain head,
and ``vmap`` batches it as it is.

Operands (:class:`HeadOperands`): ``h`` ``[..., D]`` the final norm's output;
``kernel`` ``[D, P * V]``, or the embedding ``[V, D]`` where ``tied``; ``bias``
``[P * V]`` or None; ``heads`` P prediction heads a position (targets and
mask then ``[..., P]``). A decoder hands them to the trainer in place of
logits while training (:func:`decoder_head`; ``core/trainer.py``
``HEAD_COLLECTION``). The products sit in the scope ``head`` (the name an
untied head's flax module bears), and the trainer calls the operator under
``fed/loss``, so the accepted readers of head and loss find the same work.
Every call leaves a ``head_loss/call`` program note.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.linen.dtypes import promote_dtype
from jax import lax

from fedml_tpu.obs import trace

NOTE = "head_loss/call"
# A trainer that can take :class:`HeadOperands` in place of logits lists this
# collection as mutable while it trains; nothing is written to it. A decoder
# asks ``is_mutable_collection`` (:func:`decoder_head`), as the MTP module
# asks of ``core/trainer.py`` ``MTP_COLLECTION``.
HEAD_COLLECTION = "head_operands"
# Logits of this many float32 bytes or fewer are one chunk: the plain
# expression. Larger ones go in chunks of CHUNK_ROWS rows, or a whole tile of
# TILE_ROWS fewer where that pads less (PERF.md section 6, PR 45: the sweep)
WHOLE_BYTES = 2 ** 28
CHUNK_ROWS = 4096
TILE_ROWS = 512


@struct.dataclass
class HeadOperands:
    """What an LM head's logits are made from (module docstring)."""

    h: jax.Array
    kernel: jax.Array
    bias: jax.Array | None = None
    tied: bool = struct.field(pytree_node=False, default=False)
    heads: int = struct.field(pytree_node=False, default=1)
    dtype: Any = struct.field(pytree_node=False, default=None)

    @property
    def columns(self) -> int:
        return self.kernel.shape[0] if self.tied else self.kernel.shape[1]


def chunking(rows: int, columns: int) -> tuple[int, int]:
    """``(chunks, chunk_rows)`` for logits of ``[rows, columns]`` float32, from
    the shape alone: one chunk of all the rows where the logits are
    ``WHOLE_BYTES`` or less (or ``CHUNK_ROWS`` rows cover them), else the
    fewest chunks of ``CHUNK_ROWS`` rows or fewer, each the same whole number
    of ``TILE_ROWS``-row tiles (the last rows padded where they do not fill
    it)."""
    chunks = -(-rows // CHUNK_ROWS)
    if rows * columns * 4 <= WHOLE_BYTES or chunks == 1:
        return 1, rows
    return chunks, -(-rows // (chunks * TILE_ROWS)) * TILE_ROWS


def _noted_chunking(rows: int, columns: int) -> tuple[int, int]:
    """:func:`chunking`, and the call's program note."""
    chunks, chunk_rows = chunking(rows, columns)
    trace.program_note(
        NOTE, rows=rows, columns=columns, chunks=chunks, chunk_rows=chunk_rows,
        logits_bytes=rows * columns * 4, form="whole" if chunks == 1 else "chunked")
    return chunks, chunk_rows


def logits(ops: HeadOperands) -> jax.Array:
    """The plain head: float32 logits ``[..., V]`` (``[..., P, V]`` for P > 1),
    op for op what ``flax.linen.Dense`` (``Embed.attend`` where tied) and the
    decoders' upcast make of the operands."""
    if ops.tied:
        h, embedding = promote_dtype(ops.h, ops.kernel, dtype=ops.dtype)
        with jax.named_scope(trace.SCOPE_HEAD):
            out = jnp.dot(h, embedding.T).astype(jnp.float32)
    else:
        h, kernel, bias = promote_dtype(ops.h, ops.kernel, ops.bias, dtype=ops.dtype)
        with jax.named_scope(trace.SCOPE_HEAD):
            out = lax.dot_general(h, kernel, (((h.ndim - 1,), (0,)), ((), ())))
            if bias is not None:
                out += jnp.reshape(bias, (1,) * (out.ndim - 1) + (-1,))
            out = out.astype(jnp.float32)
    if ops.heads == 1:
        return out
    return out.reshape(*out.shape[:-1], ops.heads, ops.columns // ops.heads)


def head_loss(ops: HeadOperands, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """``sum(ce * mask)`` of the head's logits against integer ``targets``
    (``mask`` in the targets' shape): chunk by chunk where :func:`chunking`
    says so, the plain expression otherwise."""
    rows = math.prod(ops.h.shape[:-1])
    chunks, chunk_rows = _noted_chunking(rows, ops.columns)
    if chunks == 1:
        ce = optax.softmax_cross_entropy_with_integer_labels(logits(ops), targets)
        return jnp.sum(ce * mask)
    d = ops.h.shape[-1]
    pad = chunks * chunk_rows - rows

    def by_chunk(a, width):
        a = a.reshape(rows, width)
        if pad:  # padded rows leave the mask: no loss, no gradient
            a = jnp.pad(a, ((0, pad), (0, 0)))
        return a.reshape(chunks, chunk_rows, width)

    return _chunked(
        ops.tied, ops.dtype, by_chunk(ops.h, d), ops.kernel, ops.bias,
        by_chunk(targets, ops.heads), by_chunk(mask.astype(jnp.float32), ops.heads))


def _chunk_trip(tied, dtype, kernel, bias, h, targets, mask):
    """One chunk: ``(loss, dh [c, D], dW, db)`` from ``h`` ``[c, D]``,
    ``targets`` and ``mask`` ``[c, P]``."""
    c, heads = targets.shape
    h, kernel, bias = promote_dtype(h, kernel, bias, dtype=dtype)
    contract_kernel = 1 if tied else 0
    with jax.named_scope(trace.SCOPE_HEAD):
        z = lax.dot_general(h, kernel, (((1,), (contract_kernel,)), ((), ())))
        if bias is not None:
            z += bias[None]
        z = z.astype(jnp.float32).reshape(c, heads, -1)
    top = jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z - top)
    norm = jnp.sum(e, axis=-1, keepdims=True)
    hit = lax.broadcasted_iota(jnp.int32, z.shape, 2) == targets[..., None]
    picked = jnp.sum(jnp.where(hit, z, 0.0), axis=-1)
    ce = jnp.log(norm[..., 0]) + top[..., 0] - picked
    loss = jnp.sum(ce * mask)
    p = e / norm
    dz = (jnp.where(hit, p - 1.0, p) * mask[..., None]).reshape(c, -1).astype(kernel.dtype)
    with jax.named_scope(trace.SCOPE_HEAD):
        dh = lax.dot_general(dz, kernel, (((1,), (1 - contract_kernel,)), ((), ())))
        if tied:
            dw = lax.dot_general(dz, h, (((0,), (0,)), ((), ())))
        else:
            dw = lax.dot_general(h, dz, (((0,), (0,)), ((), ())))
    db = None if bias is None else jnp.sum(dz, axis=0)
    return loss, dh, dw, db


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunked(tied, dtype, h, kernel, bias, targets, mask):
    return _chunked_fwd(tied, dtype, h, kernel, bias, targets, mask)[0]


def _chunked_fwd(tied, dtype, h, kernel, bias, targets, mask):
    """``h`` ``[n, c, D]``, ``targets`` and ``mask`` ``[n, c, P]``."""
    trip = partial(_chunk_trip, tied, dtype, kernel, bias)

    def body(carry, xs):
        loss, dw, db = carry
        loss_c, dh, dw_c, db_c = trip(*xs)
        dw = dw + dw_c.astype(dw.dtype)
        if db is not None:
            db = db + db_c.astype(db.dtype)
        return (loss + loss_c, dw, db), dh.astype(h.dtype)

    zeros = (jnp.float32(0.0), jnp.zeros_like(kernel),
             None if bias is None else jnp.zeros_like(bias))
    (loss, dw, db), dh = lax.scan(body, zeros, (h, targets, mask))
    return loss, (dh, dw, db)


def _chunked_bwd(tied, dtype, residuals, g):
    dh, dw, db = residuals
    scale = lambda a: None if a is None else (g * a).astype(a.dtype)  # noqa: E731
    return scale(dh), scale(dw), scale(db), None, None


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def decoder_head(module: nn.Module, h, train: bool, *, dense: nn.Dense | None = None,
                 embed: nn.Embed | None = None, heads: int = 1):
    """A decoder's last step, for the three decoder files: float32 logits
    from ``dense`` (or, tied, ``embed.attend``) as ever; but while a trainer
    that can take them trains (it lists ``HEAD_COLLECTION`` as mutable) and
    the operator would work those logits in chunks, the :class:`HeadOperands`
    they would be made from, in their place. Where one chunk covers the rows
    the trainer gets logits: the parent's program itself."""
    if (train and not module.is_initializing()
            and module.is_mutable_collection(HEAD_COLLECTION)):
        if dense is None:
            ops = HeadOperands(h, embed.embedding, None, tied=True, dtype=embed.dtype)
        else:
            params = dense.variables["params"]
            ops = HeadOperands(h, params["kernel"], params.get("bias"), heads=heads,
                               dtype=dense.dtype)
        # the note is kept once a shape, so head_loss's own is this one
        if _noted_chunking(math.prod(h.shape[:-1]), ops.columns)[0] > 1:
            return ops
    if dense is None:
        with jax.named_scope(trace.SCOPE_HEAD):
            return embed.attend(h).astype(jnp.float32)
    out = dense(h).astype(jnp.float32)
    if heads == 1:
        return out
    return out.reshape(*out.shape[:-1], heads, out.shape[-1] // heads)
