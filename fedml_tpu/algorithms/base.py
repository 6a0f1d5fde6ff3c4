"""Server-side aggregator protocol.

Reference shape: each algorithm package has an ``<Algo>Aggregator`` class
holding mutable server state and an ``aggregate()`` method looping over
client state_dicts key by key (e.g. fedml_api/distributed/fedavg/
FedAVGAggregator.py:59-88). Here an aggregator is a pair of pure functions
over *stacked* client pytrees (leading client axis) — aggregation is one
weighted reduction XLA lowers to a psum over the mesh's client axis. A rule
that needs nothing of the clients' models but their sample-weighted mean
says so (``Aggregator.aggregate_mean``, built by :func:`mean_aggregator`),
and an engine that runs its clients in turn then never builds the stack.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from fedml_tpu.core import tree as treelib

Pytree = Any


class EmptyRoundError(RuntimeError):
    """A round closed (or staged) with NOTHING to aggregate.

    Wire path (fedavg_distributed): ``aggregate()`` was asked to close a
    round with ZERO uploads — every worker (stragglers included) was
    dropped by the elastic round timeout. The server keeps the previous
    global model in that case (``_round_timed_out`` re-arms instead of
    closing); calling aggregate directly on an empty tally is a protocol
    bug, reported loudly instead of the legacy ``IndexError``/NaN.

    Sim engine: a population's availability churn left the round's cohort
    empty (or every sampled member dropped mid-round) — raised at staging
    with the round named, mirroring the wire path's semantics instead of
    surfacing as a downstream shape/NaN error. Defined here (the light
    shared layer) so both paths raise ONE class."""


@dataclasses.dataclass(frozen=True)
class Aggregator:
    """``init_state(global_variables) -> state`` and
    ``aggregate(global, stacked_locals, weights, state, rng, extras=None)
    -> (new_global, new_state, metrics)``.

    ``stacked_locals`` leaves have shape [C, ...]; ``weights`` is [C]
    (per-client sample counts — the reference's weighting scheme).
    ``extras`` is an optional dict of additional per-client arrays the engine
    supplies — currently ``tau`` [C], the true local SGD step counts
    (heterogeneous under the straggler protocol), consumed by FedNova, and
    the static ``max_tau`` loop bound.

    ``per_client=True`` switches the engine to per-client persistent models
    (decentralized/gossip FL): the first aggregate argument and return value
    are then *stacked* [C, ...] pytrees — each client trains from its own
    round-(r-1) model, and aggregation maps the trained stack to next round's
    per-client stack (e.g. a mixing-matrix multiply). The reference analogue
    is each DecentralizedWorker holding its own model across rounds
    (decentralized_framework/decentralized_worker.py:4).

    ``aggregate_mean(global, mean, weights, state, rng, extras=None)`` is
    ``aggregate`` with the clients' sample-weighted mean
    (``tree_weighted_mean(stacked_locals, weights)``, one model) in the
    stack's place. A rule gives it iff its result depends on the clients'
    models through that mean alone (FedAvg, FedOpt; build such a rule with
    :func:`mean_aggregator`, so it has one definition). It is the rule's
    whole declaration: the sim engine, where the cohort runs in sequence
    (``SimConfig.cohort_execution="scan"``), then folds each client's
    result into a running float32 sum as it finishes and calls this, and
    the [C, ...] stack is never built. Every rule that looks at clients
    one by one (robust statistics, FedNova's per-client normalisers,
    compression residuals, per-client models) leaves it None and is handed
    the stack as ever.
    """

    init_state: Callable[[Pytree], Any]
    aggregate: Callable[..., tuple[Pytree, Any, dict]]
    name: str = "aggregator"
    per_client: bool = False
    # per_client only: number of real clients the rule is configured for
    # (e.g. the mixing matrix's order) — the engine validates it against
    # client_num_in_total so a misconfigured topology fails loudly instead of
    # silently isolating the overflow clients behind identity rows
    num_clients: int | None = None
    # per_client only: gather the previous round's full model stack as the
    # first aggregate argument (costs an all_gather; rules like gossip that
    # only consume the trained stack leave this off and receive the local
    # shard's slice instead)
    needs_prev_stack: bool = False
    # the rule over the clients' weighted mean, where that is all it needs
    # of their models (class docstring); None: the rule needs the stack
    aggregate_mean: Callable[..., tuple[Pytree, Any, dict]] | None = None


def mean_aggregator(init_state, aggregate_mean, name: str) -> Aggregator:
    """The rule ``aggregate_mean`` (``Aggregator`` docstring) as an
    aggregator: its ``aggregate`` takes the stack's weighted mean and hands
    it on, so the rule is written once."""

    def aggregate(global_variables, stacked, weights, state, rng, extras=None):
        mean = treelib.tree_weighted_mean(stacked, weights)
        return aggregate_mean(global_variables, mean, weights, state, rng, extras)

    return Aggregator(init_state, aggregate, name=name, aggregate_mean=aggregate_mean)


def fedavg_aggregator() -> Aggregator:
    """Sample-count-weighted averaging (FedAVGAggregator.py:59-88)."""

    def init_state(global_variables):
        return ()

    def aggregate_mean(global_variables, mean, weights, state, rng, extras=None):
        return mean, state, {}

    return mean_aggregator(init_state, aggregate_mean, name="fedavg")
