"""Hierarchical (two-level) FedAvg: clients → groups → global.

Reference: fedml_api/standalone/hierarchical_fl/ — random group assignment
(trainer.py:10-30), nested loops global_comm_round × group_comm_round ×
epochs with epoch-aligned aggregation (trainer.py:43-69, group.py:93-115).
(The reference file has a stale import and cannot actually run — SURVEY §2.3;
the capability is reproduced here, working.)

Invariant carried to tests: with full-batch E=1 and all clients, hierarchical
FL equals centralized GD for ANY grouping whose global×group round product is
fixed (CI-script-fedavg.sh:50-58).

Production analogue: cross-silo (intra-silo DP under a silo master under the
FL server) — on TPU the group level maps onto mesh axes (SURVEY §3.3).
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from fedml_tpu.core import tree as treelib
from fedml_tpu.sim.cohort import FederatedArrays
from fedml_tpu.sim.engine import FedSim, SimConfig


def random_group_assignment(n_clients: int, n_groups: int, seed: int = 0) -> dict[int, np.ndarray]:
    """group id -> client ids (trainer.py:10-30 random partition)."""
    rng = np.random.RandomState(seed)
    perm = rng.permutation(n_clients)
    return {g: np.sort(part) for g, part in enumerate(np.array_split(perm, n_groups))}


@dataclasses.dataclass
class HierConfig:
    group_num: int = 2
    global_comm_round: int = 2
    group_comm_round: int = 2
    group_seed: int = 0


class HierarchicalFedAvg:
    """Two-level loop reusing the vectorized round program per group."""

    def __init__(self, sim: FedSim, hier: HierConfig):
        if sim._per_client:
            raise ValueError(
                "HierarchicalFedAvg drives the broadcast-global round program; "
                "per-client aggregators (decentralized/gossip) are not composable here"
            )
        self.sim = sim
        self.hier = hier
        self.groups = random_group_assignment(
            sim.config.client_num_in_total, hier.group_num, hier.group_seed
        )

    def run(self):
        sim, hier = self.sim, self.hier
        variables = jax.device_put(sim.init_variables(), sim._rep)
        server_state = sim.aggregator.init_state(variables)
        from fedml_tpu.core import rng as rnglib

        root = rnglib.root_key(sim.config.seed)
        history = []
        round_counter = 0
        for g_round in range(hier.global_comm_round):
            group_models, group_weights = [], []
            for gid, client_ids in self.groups.items():
                # the round program consumes its model argument (FedSim.
                # _call_round); give each group a private copy so the global
                # model survives all groups.
                gvars = jax.tree.map(jnp.copy, variables)
                for _ in range(hier.group_comm_round):
                    # shared staging + dispatch: straggler budgets, padding,
                    # sharding, and the on-device index-map path all behave
                    # identically to the flat engine
                    rkey = rnglib.round_key(root, round_counter)
                    gvars, server_state, _ = sim.run_cohort_round(
                        client_ids, round_counter, gvars, server_state, rkey
                    )
                    round_counter += 1
                group_models.append(gvars)
                group_weights.append(
                    float(sum(len(sim.train_data.partition[int(c)]) for c in client_ids))
                )
            stacked = treelib.tree_stack(group_models)
            variables = treelib.tree_weighted_mean(stacked, jnp.asarray(group_weights))
            rec = {"round": g_round}
            rec.update(sim.evaluate(variables))
            history.append(rec)
        return variables, history

