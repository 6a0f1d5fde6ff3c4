"""Sequence/context parallelism: the sharded long-context train step.

No reference equivalent (SURVEY §5.7 — absent there; first-class here). The
recipe follows the standard JAX scaling pattern: pick a mesh with an ``sp``
axis, shard the token axis of the batch over it, keep params replicated, and
let the model's only cross-token op (attention) run as a ring over the axis
(fedml_tpu/parallel/ring_attention.py). Loss and gradients are token-local
sums, so they close over two ``psum``s — XLA lays both on ICI.

Composes with federated axes: a ``(clients, sp)`` mesh trains a cohort of
long-context clients, cohort-parallel over ``clients`` and sequence-parallel
over ``sp``.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import Mesh, PartitionSpec as P

SP_AXIS = "sp"

Pytree = Any


def sequence_mesh(num_sp: int | None = None, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if num_sp is None:
        num_sp = len(devices)
    return Mesh(np.asarray(devices[:num_sp]), (SP_AXIS,))


def make_sp_lm_train_step(model, optimizer: optax.GradientTransformation, mesh: Mesh,
                          sp_axis: str = SP_AXIS):
    """Returns ``step(params, opt_state, batch, rng) -> (params, opt_state, loss)``.

    ``batch = {"x": [B, T], "y": [B, T], "mask": [B, T]}`` with the T axis
    sharded over ``sp_axis``; params/opt_state/rng replicated (the dropout rng
    is folded with the shard index so shards draw independent masks). The
    model must be built with ``attn_impl="ring"`` and the same ``sp_axis``.
    """

    def local_loss(params, batch, rng, global_count):
        # NOTE: no psum inside the differentiated function. Under full-manual
        # shard_map (check_vma=False) the transpose of psum is psum, so a psum
        # in the loss would scale gradients by the axis size. The pattern:
        # token-local masked sum over a *global* normalizer (computed outside
        # the grad), then psum the gradients once.
        x = batch["x"]
        t_loc = x.shape[1]
        idx = jax.lax.axis_index(sp_axis)
        logits = model.apply(
            {"params": params},
            x,
            train=True,
            pos_offset=idx * t_loc,
            rngs={"dropout": jax.random.fold_in(rng, idx)},
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        return jnp.sum(ce * batch["mask"]) / global_count

    batch_spec = {"x": P(None, sp_axis), "y": P(None, sp_axis), "mask": P(None, sp_axis)}

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def step(params, opt_state, batch, rng):
        global_count = jnp.maximum(
            jax.lax.psum(jnp.sum(batch["mask"]), sp_axis), 1.0
        )
        loss, grads = jax.value_and_grad(local_loss)(params, batch, rng, global_count)
        loss = jax.lax.psum(loss, sp_axis)
        # each shard's grad covers only its tokens' contribution
        grads = jax.lax.psum(grads, sp_axis)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step)


def shard_lm_batch(batch: dict, mesh: Mesh, sp_axis: str = SP_AXIS) -> dict:
    """Device-put a [B, T] token batch with T sharded over the sp axis."""
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, P(None, sp_axis))
    return {k: jax.device_put(jnp.asarray(v), sh) for k, v in batch.items()}
