"""TurboAggregate (secure aggregation) experiment entry.

Reference: fedml_experiments/distributed/turboaggregate/ — FedAvg where the
server reconstructs only the SUM of quantized client updates from BGW secret
shares, never an individual client's plaintext (TA_Aggregator.py:13,
mpc_function.py:62-110).

Runs the real multi-party protocol (algorithms/turboaggregate_dist.py) over
a comm fabric: clients BGW-share weighted quantized deltas peer-to-peer,
upload only share-sums, the server reconstructs only the aggregate.
"""

from __future__ import annotations

import argparse
import logging


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--dataset", type=str, default="synthetic")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--partition_method", type=str, default="homo")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--client_num_in_total", type=int, default=4)
    parser.add_argument("--privacy_threshold", type=int, default=1)
    parser.add_argument("--backend", type=str, default="loopback",
                        choices=["loopback", "shm"])
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0)
    return parser


def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fedml_tpu.algorithms.turboaggregate_dist import run_turboaggregate
    from fedml_tpu.comm.managers import create_backend  # noqa: F401 (shm path)
    from fedml_tpu.core.trainer import ClientTrainer, make_local_eval
    from fedml_tpu.data import load_partition_data
    from fedml_tpu.models import create_model
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.cohort import batch_array

    logging_config(0)
    ds = load_partition_data(
        args.dataset, args.data_dir, args.partition_method, args.partition_alpha,
        args.client_num_in_total, args.seed,
    )
    model = create_model("lr", ds.class_num, args.dataset)
    trainer = ClientTrainer(
        module=model, optimizer=optax.sgd(args.lr), epochs=args.epochs
    )
    workers = ds.train.num_clients

    made = []
    if args.backend == "loopback":
        from fedml_tpu.comm.loopback import LoopbackCommManager, LoopbackFabric

        fabric = LoopbackFabric(workers + 1)
        make_comm = lambda r: LoopbackCommManager(fabric, r)  # noqa: E731
    else:
        import uuid

        job = f"ta_{uuid.uuid4().hex[:8]}"

        def make_comm(r):
            m = create_backend("shm", r, workers + 1, job=job)
            made.append(m)
            return m

    try:
        final = run_turboaggregate(
            trainer, ds.train, workers, args.comm_round, args.batch_size,
            make_comm, threshold=args.privacy_threshold, seed=args.seed,
        )
    finally:
        for m in made:
            m.cleanup()

    batches = jax.tree.map(jnp.asarray, batch_array(ds.test_arrays, 256))
    m = make_local_eval(trainer)(jax.tree.map(jnp.asarray, final), batches)
    acc = float(np.asarray(m["test_correct"]) / np.maximum(np.asarray(m["test_total"]), 1))
    out = {"rounds": args.comm_round, "test_acc": acc}
    logging.info("turboaggregate final: %s", out)
    return out


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(
        argparse.ArgumentParser("fedml_tpu turboaggregate entry")
    ).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
