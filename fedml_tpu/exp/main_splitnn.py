"""SplitNN experiment entry.

Reference: fedml_experiments/distributed/split_nn/main_split_nn.py — clients
hold the bottom network, the server holds the top; activations/grads cross
the cut layer and clients take turns in a relay ring (split_nn/server.py:62-72).
Flag names follow the reference argparse.
"""

from __future__ import annotations

import argparse
import logging

import numpy as np


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--dataset", type=str, default="synthetic")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--partition_method", type=str, default="homo")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--client_number", type=int, default=4)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--epochs", type=int, default=4)
    parser.add_argument("--hidden", type=int, default=32)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--backend", type=str, default="inprocess",
                        choices=["inprocess", "loopback", "shm"],
                        help="inprocess: single jitted program; loopback/shm: "
                             "server + clients as separate threads with "
                             "activations/grads as wire payloads "
                             "(bit-identical)")
    return parser


def run(args) -> dict:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.splitnn import SplitNN, run_splitnn_relay, splitnn_eval
    from fedml_tpu.data import load_partition_data
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.cohort import batch_array, stack_cohort

    logging_config(0)
    ds = load_partition_data(
        args.dataset, args.data_dir, args.partition_method, args.partition_alpha,
        args.client_number, args.seed,
    )

    class Bottom(nn.Module):
        hidden: int

        @nn.compact
        def __call__(self, x, train: bool = False):
            h = x.reshape((x.shape[0], -1)).astype(jnp.float32)
            return nn.relu(nn.Dense(self.hidden)(h))

    class Top(nn.Module):
        classes: int

        @nn.compact
        def __call__(self, acts, train: bool = False):
            return nn.Dense(self.classes)(acts)

    split = SplitNN(
        Bottom(args.hidden), Top(ds.class_num),
        optax.sgd(args.lr), optax.sgd(args.lr),
    )
    client_batches = []
    for c in range(ds.train.num_clients):
        stack, _ = stack_cohort(ds.train, np.asarray([c]), args.batch_size)
        client_batches.append(jax.tree.map(lambda v: jnp.asarray(v[0]), stack))

    if args.backend == "loopback":
        from fedml_tpu.algorithms.splitnn_dist import run_distributed_splitnn_loopback

        cvars, svars, losses = run_distributed_splitnn_loopback(
            split, client_batches, epochs=args.epochs, rng=jax.random.key(args.seed)
        )
    elif args.backend == "shm":
        import uuid

        from fedml_tpu.algorithms.splitnn_dist import run_distributed_splitnn
        from fedml_tpu.comm.shm import ShmCommManager

        job = f"splitnn_{uuid.uuid4().hex[:8]}"
        mgrs = {
            r: ShmCommManager(job, r, len(client_batches) + 1)
            for r in range(len(client_batches) + 1)
        }
        try:
            cvars, svars, losses = run_distributed_splitnn(
                split, client_batches, epochs=args.epochs,
                rng=jax.random.key(args.seed), make_comm=lambda r: mgrs[r],
            )
        finally:
            for m in mgrs.values():
                m.cleanup()
    else:
        cvars, svars, losses = run_splitnn_relay(
            split, client_batches, epochs=args.epochs, rng=jax.random.key(args.seed)
        )
    out = {"Train/Loss": float(losses[-1])}
    if ds.test_arrays is not None:
        test_b = jax.tree.map(jnp.asarray, batch_array(ds.test_arrays, 64))
        out["Test/Acc"] = float(splitnn_eval(split, cvars[0], svars, test_b))
    logging.info("splitnn final: %s", out)
    return out


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu splitnn entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
