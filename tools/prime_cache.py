"""Pre-populate the persistent XLA compile cache for the test gate.

Most of the suite's cold wall-clock is XLA:CPU compilation of federated
round programs; many tests rebuild the same program shapes. This script
compiles the highest-cost SHARED programs once so a following
``pytest -m "not slow"`` run starts from a partly warm cache instead of a
fully cold one (cold is 20+ min).

Usage (fresh clone):
    python tools/prime_cache.py
    python -m pytest tests/ -q -m "not slow"

jax hashes the cache directory STRING into every key, so priming only
helps a reader that spells the directory the same way. This script imports
``tests/conftest.py`` for exactly that: the platform, the eight virtual
devices and the suite's own directory string ($JAX_COMPILATION_CACHE_DIR
when set). Priming is idempotent and safe to re-run.
"""

from __future__ import annotations

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_REPO, os.path.join(_REPO, "tests")]

import conftest  # noqa: E402,F401 — cpu platform, 8 devices, the suite's cache dir
import jax  # noqa: E402


def _t(label, fn):
    t0 = time.time()
    fn()
    print(f"  {label}: {time.time() - t0:.1f}s", flush=True)


def main():
    import numpy as np

    import jax.numpy as jnp
    import optax

    print("priming XLA compile cache "
          f"({jax.config.jax_compilation_cache_dir}) ...", flush=True)

    # 1. the graft-entry dryrun: 2-D mesh round + ring-attention SP step —
    #    the driver gate's exact programs
    import __graft_entry__ as graft

    _t("dryrun_multichip(8)", lambda: graft.dryrun_multichip(8))

    # 2. the flagship single-chip forward (entry contract)
    def entry_fwd():
        fn, args = graft.entry()
        jax.jit(fn)(*args)

    _t("entry() forward", entry_fwd)

    # 3. the equivalence-oracle round shape shared by many engine tests:
    #    vmapped cohort + scan epochs on the 2-conv CNN
    def engine_round():
        from fedml_tpu.core.trainer import ClientTrainer
        from fedml_tpu.data.synthetic import gaussian_blobs
        from fedml_tpu.models.cnn import CNNOriginalFedAvg
        from fedml_tpu.sim.engine import FedSim, SimConfig

        train, test = gaussian_blobs(
            n_clients=4, samples_per_client=16, num_classes=4,
            dim=4 * 4 * 3, seed=0,
        )
        for arrays in (train.arrays, test):
            arrays["x"] = arrays["x"].reshape(-1, 4, 4, 3)
        trainer = ClientTrainer(
            module=CNNOriginalFedAvg(num_classes=4),
            optimizer=optax.sgd(0.1, momentum=0.9), epochs=1,
        )
        cfg = SimConfig(client_num_in_total=4, client_num_per_round=4,
                        batch_size=8, comm_round=1, epochs=1,
                        frequency_of_the_test=1, seed=0)
        FedSim(trainer, train, test, cfg).run()

    _t("engine round (CNN)", engine_round)

    # 4. the distributed-manager local_train jit (fedavg_distributed tests)
    def dist_local():
        from fedml_tpu.core.trainer import ClientTrainer, make_local_train
        from fedml_tpu.models.linear import LogisticRegression

        trainer = ClientTrainer(
            module=LogisticRegression(num_classes=2),
            optimizer=optax.sgd(0.1), epochs=1,
        )
        batches = {
            "x": jnp.zeros((2, 8, 8), jnp.float32),
            "y": jnp.zeros((2, 8), jnp.int32),
            "mask": jnp.ones((2, 8), jnp.float32),
        }
        variables = trainer.init(jax.random.key(0),
                                 jax.tree.map(lambda v: v[0], batches))
        jax.jit(make_local_train(trainer))(variables, batches,
                                           jax.random.key(1))

    _t("distributed local_train (LR)", dist_local)

    print("cache primed.", flush=True)


if __name__ == "__main__":
    main()
