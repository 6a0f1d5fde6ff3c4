"""FedNAS experiment entry.

Reference: fedml_experiments/distributed/fednas/main_fednas.py — clients run
DARTS bilevel search (architecture-α step + weight step, FedNASTrainer.py:
34-127), the server averages both weights and α (FedNASAggregator.py:71-113)
and decodes the genotype each round (record_model_global_architecture:173).
"""

from __future__ import annotations

import argparse
import logging

import numpy as np


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--dataset", type=str, default="synthetic_cv")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--client_number", type=int, default=2)
    parser.add_argument("--comm_round", type=int, default=2)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--arch_lr", type=float, default=3e-3)
    parser.add_argument("--channels", type=int, default=4)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--steps", type=int, default=2)
    parser.add_argument("--search_mode", type=str, default="darts",
                        choices=["darts", "gdas"],
                        help="darts = softmax mixture over ops; gdas = "
                             "Gumbel-softmax hard sample per forward")
    parser.add_argument("--tau", type=float, default=5.0,
                        help="gdas Gumbel temperature")
    parser.add_argument("--unrolled", type=int, default=0,
                        help="1 = second-order architect (reference "
                             "architect.py:47 unrolled=True): one unrolled "
                             "weight step + exact Hessian-vector term")
    parser.add_argument("--seed", type=int, default=0)
    return parser


def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.fednas import (
        FedNASTrainer,
        fednas_aggregator,
        global_genotype,
    )
    from fedml_tpu.core.tree import tree_stack
    from fedml_tpu.models.darts import DARTSNetwork
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.cohort import stack_cohort

    logging_config(0)
    if args.dataset == "synthetic_cv":
        rng = np.random.RandomState(args.seed)
        n, hw, classes = args.client_number * 4 * args.batch_size, 8, 4
        x = rng.rand(n, hw, hw, 3).astype(np.float32)
        y = rng.randint(0, classes, n).astype(np.int32)
        from fedml_tpu.sim.cohort import FederatedArrays

        per = n // args.client_number
        train = FederatedArrays(
            {"x": x, "y": y},
            {c: np.arange(c * per, (c + 1) * per) for c in range(args.client_number)},
        )
    else:
        from fedml_tpu.data import load_partition_data

        ds = load_partition_data(
            args.dataset, args.data_dir, "hetero", 0.5, args.client_number, args.seed
        )
        train, classes = ds.train, ds.class_num

    net = DARTSNetwork(
        num_classes=classes, channels=args.channels, layers=args.layers,
        steps=args.steps, search_mode=args.search_mode, tau=args.tau,
    )
    tr = FedNASTrainer(net, optax.sgd(args.lr), optax.adam(args.arch_lr),
                       epochs=args.epochs,
                       unrolled=bool(args.unrolled), unrolled_eta=args.lr)
    agg = fednas_aggregator()

    # per-client train/val batch stacks (bilevel search needs both)
    stacks, weights = [], []
    for c in range(train.num_clients):
        stack, w = stack_cohort(train, np.asarray([c]), args.batch_size)
        stacks.append(jax.tree.map(lambda v: jnp.asarray(v[0]), stack))
        weights.append(float(w[0]))

    variables = tr.init(jax.random.key(args.seed), stacks[0]["x"][0])
    state = agg.init_state(variables)
    search = jax.jit(tr.local_search)
    history = []
    for r in range(args.comm_round):
        outs, losses = [], []
        for c in range(train.num_clients):
            out, m = search(variables, stacks[c], stacks[c], jax.random.key(r * 7919 + c))
            outs.append(out)
            losses.append(float(m["train_loss"]))
        stacked = tree_stack(outs)
        variables, state, _ = agg.aggregate(
            variables, stacked, jnp.asarray(weights), state, jax.random.key(r)
        )
        genotype = global_genotype(variables)
        rec = {"round": r, "Train/Loss": float(np.mean(losses)),
               "genotype_normal": str(genotype.normal)}
        history.append(rec)
        logging.info("fednas round %d: loss=%.4f genotype=%s", r, rec["Train/Loss"],
                     genotype.normal[:2])
    return history[-1]


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu fednas entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
