"""FedGKT experiment entry.

Reference: fedml_experiments/distributed/fedgkt/main_fedgkt.py — clients
train a small feature extractor (ResNet-8 class), upload per-batch features
+ logits + labels; the server trains the big network on those features with
bidirectional temperature-scaled KL distillation (GKTServerTrainer.py:13,
utils.py:75-90).
"""

from __future__ import annotations

import argparse
import logging

import numpy as np


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--dataset", type=str, default="synthetic_cv")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--partition_method", type=str, default="hetero")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--client_number", type=int, default=2)
    parser.add_argument("--comm_round", type=int, default=2)
    parser.add_argument("--epochs_client", type=int, default=1)
    parser.add_argument("--epochs_server", type=int, default=1)
    parser.add_argument("--batch_size", type=int, default=8)
    parser.add_argument("--lr", type=float, default=0.03)
    parser.add_argument("--temperature", type=float, default=3.0)
    parser.add_argument("--alpha", type=float, default=1.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--backend", type=str, default="inprocess",
                        choices=["inprocess", "loopback"],
                        help="inprocess: orchestrated in this process; "
                             "loopback: server + clients as separate threads "
                             "with features/logits as wire payloads")
    return parser


def _load_images(args):
    """CV dataset via the registry, or a synthetic image fixture."""
    if args.dataset == "synthetic_cv":
        rng = np.random.RandomState(args.seed)
        n, hw, classes = args.client_number * 4 * args.batch_size, 8, 4
        x = rng.rand(n, hw, hw, 3).astype(np.float32)
        y = rng.randint(0, classes, n).astype(np.int32)
        from fedml_tpu.sim.cohort import FederatedArrays

        part = {
            c: np.arange(c * (n // args.client_number), (c + 1) * (n // args.client_number))
            for c in range(args.client_number)
        }
        return FederatedArrays({"x": x, "y": y}, part), classes
    from fedml_tpu.data import load_partition_data

    ds = load_partition_data(
        args.dataset, args.data_dir, args.partition_method, args.partition_alpha,
        args.client_number, args.seed,
    )
    return ds.train, ds.class_num


def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.fedgkt import FedGKT
    from fedml_tpu.models.resnet_gkt import ResNetGKTClient, ResNetGKTServer
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.cohort import stack_cohort

    logging_config(0)
    train, class_num = _load_images(args)

    gkt = FedGKT(
        ResNetGKTClient(num_classes=class_num, blocks=1),
        ResNetGKTServer(num_classes=class_num, blocks_per_stage=1),
        optax.sgd(args.lr), optax.sgd(args.lr),
        temperature=args.temperature, alpha=args.alpha,
    )
    # per-client fixed batch stacks (the per-batch feature exchange keys on
    # stable batch identity, GKTClientTrainer.train extracted_feature_dict)
    client_batches = []
    for c in range(train.num_clients):
        stack, _ = stack_cohort(train, np.asarray([c]), args.batch_size)
        client_batches.append(jax.tree.map(lambda v: jnp.asarray(v[0]), stack))

    # both backends run the SAME orchestration semantics (run_fedgkt is the
    # numerics oracle of the distributed path): identical args + seed give
    # identical models whichever backend is chosen
    if args.backend == "loopback":
        from fedml_tpu.algorithms.fedgkt_dist import run_distributed_fedgkt_loopback

        cvars_list, svars = run_distributed_fedgkt_loopback(
            gkt, client_batches, rounds=args.comm_round,
            client_epochs=args.epochs_client, server_epochs=args.epochs_server,
            rng=jax.random.key(args.seed),
        )
    else:
        from fedml_tpu.algorithms.fedgkt import run_fedgkt

        cvars_list, svars, _ = run_fedgkt(
            gkt, client_batches, rounds=args.comm_round,
            client_epochs=args.epochs_client, server_epochs=args.epochs_server,
            rng=jax.random.key(args.seed),
        )
    return _final_metrics(gkt, cvars_list, svars, client_batches)


def _final_metrics(gkt, cvars_list, svars, client_batches) -> dict:
    """Final train accuracy through the full client->server pipeline."""
    import jax
    import jax.numpy as jnp

    correct = total = 0.0
    for c in range(len(client_batches)):
        feats, _ = jax.vmap(
            lambda b_x: gkt.client_module.apply(cvars_list[c], b_x, train=False)
        )(client_batches[c]["x"])
        logits = jax.vmap(
            lambda f: gkt.server_module.apply(svars, f, train=False)
        )(feats)
        pred = np.asarray(jnp.argmax(logits, -1))
        y = np.asarray(client_batches[c]["y"])
        m = np.asarray(client_batches[c]["mask"])
        correct += ((pred == y) * m).sum()
        total += m.sum()
    out = {"Train/Acc": float(correct / max(total, 1.0))}
    logging.info("fedgkt final: %s", out)
    return out


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu fedgkt entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
