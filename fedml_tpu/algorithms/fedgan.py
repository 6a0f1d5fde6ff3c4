"""Federated GAN.

Reference: fedml_api/distributed/fedgan/ — clients run an adversarial train
loop on a (generator, discriminator) pair; the aggregator weighted-averages a
*dict of two networks* with a nested two-level loop
(FedGANAggregator.aggregate:58-88). Here the pair is one pytree
``{"generator": vars, "discriminator": vars}`` so the standard weighted mean
IS the nested average, and the local adversarial loop is a jitted scan vmapped
over the cohort like any other trainer.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.algorithms.base import Aggregator, fedavg_aggregator
from fedml_tpu.core import scan as scanlib

Pytree = Any


@dataclasses.dataclass(frozen=True)
class GANTrainer:
    generator: Any
    discriminator: Any
    g_opt: optax.GradientTransformation
    d_opt: optax.GradientTransformation
    latent_dim: int = 100
    epochs: int = 1

    def init(self, rng: jax.Array, sample_batch: dict) -> Pytree:
        kg, kd = jax.random.split(rng)
        z = jnp.zeros((sample_batch["x"].shape[0], self.latent_dim))
        gvars = self.generator.init({"params": kg}, z, train=False)
        dvars = self.discriminator.init({"params": kd}, sample_batch["x"], train=False)
        return {"generator": dict(gvars), "discriminator": dict(dvars)}

    def _apply(self, module, variables, x, train, rng):
        state = {k: v for k, v in variables.items() if k != "params"}
        if train and state:
            out, new_state = module.apply(variables, x, train=True, mutable=list(state.keys()),
                                          rngs={"dropout": rng})
            return out, new_state
        return module.apply(variables, x, train=train, rngs={"dropout": rng}), state

    def train_step(self, variables: Pytree, opt_states, batch: dict, rng: jax.Array):
        """Non-saturating GAN step: D on real+fake, then G (reference
        MyModelTrainer adversarial loop)."""
        kz, kd, kg = jax.random.split(rng, 3)
        real, mask = batch["x"], batch["mask"]
        B = real.shape[0]
        z = jax.random.normal(kz, (B, self.latent_dim))
        gvars, dvars = variables["generator"], variables["discriminator"]
        g_opt_state, d_opt_state = opt_states

        def bce_logits(logits, target):
            return optax.sigmoid_binary_cross_entropy(logits[:, 0], target)

        # --- discriminator step ---
        def d_loss_fn(dp):
            dv = {**dvars, "params": dp}
            fake, _ = self._apply(self.generator, gvars, z, True, kg)
            real_logit, dstate = self._apply(self.discriminator, dv, real, True, kd)
            fake_logit, _ = self._apply(self.discriminator, dv, jax.lax.stop_gradient(fake), True, kd)
            loss = bce_logits(real_logit, jnp.ones(B)) + bce_logits(fake_logit, jnp.zeros(B))
            return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0), dstate

        (d_loss, dstate), d_grads = jax.value_and_grad(d_loss_fn, has_aux=True)(dvars["params"])
        d_updates, d_opt_state = self.d_opt.update(d_grads, d_opt_state, dvars["params"])
        dvars = {**dvars, **dstate, "params": optax.apply_updates(dvars["params"], d_updates)}

        # --- generator step ---
        def g_loss_fn(gp):
            gv = {**gvars, "params": gp}
            fake, gstate = self._apply(self.generator, gv, z, True, kg)
            fake_logit, _ = self._apply(self.discriminator, dvars, fake, True, kd)
            loss = bce_logits(fake_logit, jnp.ones(B))
            return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0), gstate

        (g_loss, gstate), g_grads = jax.value_and_grad(g_loss_fn, has_aux=True)(gvars["params"])
        g_updates, g_opt_state = self.g_opt.update(g_grads, g_opt_state, gvars["params"])
        gvars = {**gvars, **gstate, "params": optax.apply_updates(gvars["params"], g_updates)}

        return ({"generator": gvars, "discriminator": dvars},
                (g_opt_state, d_opt_state), {"d_loss": d_loss, "g_loss": g_loss})


def make_gan_local_train(trainer: GANTrainer):
    """local_train(global_pair, data, rng, num_steps=None) -> (pair, metrics)
    — same contract as core.trainer.make_local_train (incl. the per-client
    step budget), so FedSim can federate GANs unchanged."""

    def local_train(global_variables: Pytree, data: dict, rng: jax.Array,
                    num_steps=None):
        opt_states = (
            trainer.g_opt.init(global_variables["generator"]["params"]),
            trainer.d_opt.init(global_variables["discriminator"]["params"]),
        )
        S = jax.tree.leaves(data)[0].shape[0]

        def epoch(carry, e):
            variables, opt_states, rng = carry

            def step(carry, xs):
                variables, opt_states, rng = carry
                s, batch = xs
                rng, sub = jax.random.split(rng)
                new_vars, new_opts, losses = trainer.train_step(
                    variables, opt_states, batch, sub
                )
                # freeze past the step budget or on fully-padded batches
                active = jnp.sum(batch["mask"]) > 0
                if num_steps is not None:
                    active = active & ((e * S + s) < num_steps)
                keep = lambda n, o: jax.tree.map(
                    lambda a, b: jnp.where(active, a, b), n, o
                )
                variables = keep(new_vars, variables)
                opt_states = keep(new_opts, opt_states)
                return (variables, opt_states, rng), losses["g_loss"] + losses["d_loss"]

            (variables, opt_states, rng), losses = scanlib.scan(
                step, (variables, opt_states, rng), (jnp.arange(S), data)
            )
            return (variables, opt_states, rng), losses.mean()

        (variables, opt_states, rng), epoch_losses = scanlib.scan(
            epoch, (global_variables, opt_states, rng), jnp.arange(trainer.epochs)
        )
        return variables, {"train_loss": epoch_losses[-1]}

    return local_train


def fedgan_aggregator() -> Aggregator:
    """The nested two-network weighted average (FedGANAggregator.aggregate:
    58-88) — identical math to fedavg over the pair pytree."""
    return dataclasses.replace(fedavg_aggregator(), name="fedgan")
