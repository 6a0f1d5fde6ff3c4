"""FedOpt: server-side adaptive optimization (FedAdam/FedYogi/FedAdagrad/
FedAvgM family).

Reference: fedml_api/distributed/fedopt/FedOptAggregator.py:94-120 — weighted-
average the client models, set the *pseudo-gradient* ``old − avg`` on the
global params, and step a torch server optimizer looked up by name from
``OptRepo`` (optrepo.py:7-25) with ``server_lr`` / ``server_momentum``.

Here the server optimizer is any optax GradientTransformation — optax covers
the whole OptRepo surface natively. Only the ``params`` collection gets the
optimizer treatment; auxiliary state (BN stats) is plainly averaged, matching
the reference which applies the optimizer to named parameters only.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.algorithms.base import Aggregator, mean_aggregator
from fedml_tpu.core import tree as treelib


def server_optimizer(name: str, server_lr: float = 1.0, server_momentum: float = 0.9) -> optax.GradientTransformation:
    """Name dispatch mirroring OptRepo.name2cls (fedopt/optrepo.py:25)."""
    name = name.lower()
    if name in ("sgd", "fedavgm"):
        return optax.sgd(server_lr, momentum=server_momentum)
    if name in ("adam", "fedadam"):
        return optax.adam(server_lr, b1=server_momentum, eps=1e-3)
    if name in ("yogi", "fedyogi"):
        return optax.yogi(server_lr, b1=server_momentum)
    if name in ("adagrad", "fedadagrad"):
        return optax.adagrad(server_lr)
    if name == "rmsprop":
        return optax.rmsprop(server_lr, momentum=server_momentum)
    if name == "adamw":
        return optax.adamw(server_lr, b1=server_momentum)
    raise ValueError(f"unknown server optimizer {name!r}")


def fedopt_aggregator(opt: optax.GradientTransformation) -> Aggregator:
    def init_state(global_variables):
        return opt.init(global_variables["params"])

    def aggregate_mean(global_variables, avg, weights, opt_state, rng, extras=None):
        # pseudo-gradient: old - avg (FedOptAggregator.set_model_global_grads:109-120)
        pseudo_grad = treelib.tree_sub(global_variables["params"], avg["params"])
        updates, opt_state = opt.update(pseudo_grad, opt_state, global_variables["params"])
        new_params = optax.apply_updates(global_variables["params"], updates)
        new_global = {**avg, "params": new_params}
        return new_global, opt_state, {}

    return mean_aggregator(init_state, aggregate_mean, name="fedopt")
