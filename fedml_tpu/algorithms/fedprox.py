"""FedProx: proximal local objective for heterogeneous clients.

Reference capability note: the reference's *distributed* fedprox package is a
verbatim FedAvg copy whose MyModelTrainer has NO μ term (fedml_api/distributed/
fedprox/MyModelTrainer.py:19-49 — SURVEY §2.2); the real proximal math lives
in its standalone fednova optimizer (fednova.py:48 mu support). Here FedProx
is actually implemented: the client loss gains μ/2·||w − w_global||²
(core/trainer.py ClientTrainer.prox_mu), and this module provides the named
algorithm wrapper plus straggler simulation — heterogeneous local epoch
counts, the scenario FedProx was designed for (absent from the reference,
SURVEY §5.3).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from fedml_tpu.algorithms.base import Aggregator, fedavg_aggregator
from fedml_tpu.core.trainer import ClientTrainer


def fedprox_trainer(trainer: ClientTrainer, mu: float) -> ClientTrainer:
    """Attach the proximal term to any ClientTrainer."""
    return dataclasses.replace(trainer, prox_mu=mu)


def fedprox_aggregator() -> Aggregator:
    """Server side is plain weighted averaging (FedProx paper)."""
    return dataclasses.replace(fedavg_aggregator(), name="fedprox")


def straggler_epochs(
    round_idx: int, cohort_size: int, epochs: int, straggler_frac: float, seed: int = 0
) -> np.ndarray:
    """Per-client local-epoch counts with a straggler fraction doing strictly
    fewer epochs (uniform 1..E-1), the FedProx heterogeneity protocol."""
    rng = np.random.RandomState(seed * 77_003 + round_idx)
    out = np.full(cohort_size, epochs, dtype=np.int32)
    stragglers = rng.rand(cohort_size) < straggler_frac
    out[stragglers] = rng.randint(1, max(epochs, 2), size=int(stragglers.sum()))
    return out
