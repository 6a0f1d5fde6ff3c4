"""FedSeg (federated semantic segmentation) experiment entry.

Reference: fedml_experiments/distributed/fedseg/main_fedseg.py — FedAvg over
segmentation models with the confusion-matrix Evaluator protocol: per-client
mIoU / FWIoU / pixel-acc dicts tracked by the aggregator
(FedSegAggregator.py:105-235, utils.py Evaluator).
"""

from __future__ import annotations

import argparse
import logging

import numpy as np


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--dataset", type=str, default="synthetic_seg")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--model", type=str, default="unet",
                        choices=["unet", "deeplab"])
    parser.add_argument("--client_num_in_total", type=int, default=4)
    parser.add_argument("--client_num_per_round", type=int, default=4)
    parser.add_argument("--num_classes", type=int, default=3)
    parser.add_argument("--batch_size", type=int, default=4)
    parser.add_argument("--lr", type=float, default=3e-3)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=2)
    parser.add_argument("--frequency_of_the_test", type=int, default=2)
    parser.add_argument("--seed", type=int, default=0)
    return parser


def _synthetic_seg(args):
    """Blob-segmentation fixture: class = quadrant-dependent intensity."""
    rng = np.random.RandomState(args.seed)
    n, hw = args.client_num_in_total * 4 * args.batch_size, 16
    base = rng.randint(0, args.num_classes, (n, 1, 1))
    ys = np.broadcast_to(base, (n, hw, hw)).astype(np.int32).copy()
    ys[:, : hw // 2] = (ys[:, : hw // 2] + 1) % args.num_classes
    xs = (ys[..., None] / args.num_classes + 0.15 * rng.randn(n, hw, hw, 1)).astype(
        np.float32
    )
    from fedml_tpu.sim.cohort import FederatedArrays

    per = n // args.client_num_in_total
    train = FederatedArrays(
        {"x": xs, "y": ys},
        {c: np.arange(c * per, (c + 1) * per) for c in range(args.client_num_in_total)},
    )
    test = {"x": xs[: 2 * args.batch_size], "y": ys[: 2 * args.batch_size]}
    return train, test


def run(args) -> dict:
    import optax

    from fedml_tpu.algorithms.fedseg import FedSegSim
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.segmentation import DeepLabLite, UNet
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.engine import SimConfig

    logging_config(0)
    if args.dataset == "synthetic_seg":
        train, test = _synthetic_seg(args)
        class_num = args.num_classes
    else:
        from fedml_tpu.data import load_partition_data

        ds = load_partition_data(
            args.dataset, args.data_dir, "seg", 0.5, args.client_num_in_total,
            args.seed,
        )
        train, test, class_num = ds.train, ds.test_arrays, ds.class_num

    model = (
        UNet(num_classes=class_num, features=(8, 8, 16))
        if args.model == "unet"
        else DeepLabLite(num_classes=class_num)
    )
    trainer = ClientTrainer(
        module=model, task="segmentation", optimizer=optax.adam(args.lr),
        epochs=args.epochs,
    )
    cfg = SimConfig(
        client_num_in_total=train.num_clients,
        client_num_per_round=min(args.client_num_per_round, train.num_clients),
        batch_size=args.batch_size, comm_round=args.comm_round,
        epochs=args.epochs, frequency_of_the_test=args.frequency_of_the_test,
        seed=args.seed,
    )
    sim = FedSegSim(trainer, train, test, cfg)
    variables, history = sim.run()
    per_client, global_m = sim.evaluate_clients(variables)
    out = {**history[-1], **global_m}
    logging.info("fedseg final: %s  (clients evaluated: %d)", global_m, len(per_client))
    return out


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu fedseg entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
