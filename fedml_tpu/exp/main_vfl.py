"""Classical vertical FL experiment entry.

Reference: fedml_experiments/standalone/classical_vertical_fl/ (run_vfl_*
party scripts) — guest holds labels + a feature block, hosts hold the other
feature columns; per-batch logits flow guest-ward, per-host gradients flow
back (classical_vertical_fl/guest_trainer.py:73-120).
"""

from __future__ import annotations

import argparse
import logging

import numpy as np


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--dataset", type=str, default="synthetic_vfl",
                        choices=["synthetic_vfl", "lending_club", "nus_wide"])
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--party_num", type=int, default=2)
    parser.add_argument("--batch_size", type=int, default=40)
    parser.add_argument("--lr", type=float, default=0.3)
    parser.add_argument("--epochs", type=int, default=8)
    parser.add_argument("--hidden", type=int, default=16)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--backend", type=str, default="inprocess",
                        choices=["inprocess", "loopback"],
                        help="inprocess: single jitted program; loopback: "
                             "guest + hosts as separate threads over the "
                             "comm layer (bit-identical)")
    return parser


def run(args) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.vertical import PartyModel, VerticalFL, run_vfl
    from fedml_tpu.data.vertical_tabular import load_vertical, synthetic_vertical
    from fedml_tpu.obs.metrics import logging_config

    logging_config(0)
    if args.dataset == "synthetic_vfl":
        dims = tuple([16] * args.party_num)
        tr_splits, y_tr, te_splits, y_te = synthetic_vertical(
            dims=dims, seed=args.seed
        )
    else:
        tr_splits, y_tr, te_splits, y_te = load_vertical(
            args.dataset, args.data_dir, n_parties=args.party_num, seed=args.seed
        )

    if args.backend == "loopback":
        from fedml_tpu.algorithms.vertical_dist import run_distributed_vfl_loopback

        vfl = VerticalFL(
            [PartyModel(hidden=args.hidden) for _ in tr_splits],
            optax.sgd(args.lr),
        )
        pvars, losses = run_distributed_vfl_loopback(
            vfl, [jnp.asarray(s) for s in tr_splits], jnp.asarray(y_tr),
            args.epochs, args.batch_size, jax.random.key(args.seed),
        )
    else:
        vfl, pvars, losses = run_vfl(
            [jnp.asarray(s) for s in tr_splits], jnp.asarray(y_tr),
            epochs=args.epochs, batch_size=args.batch_size, lr=args.lr,
            hidden=args.hidden, seed=args.seed,
        )
    pred = np.asarray(vfl.predict(pvars, [jnp.asarray(s) for s in te_splits])) > 0.5
    out = {
        "Train/Loss": float(losses[-1]),
        "Test/Acc": float((pred == np.asarray(y_te)).mean()),
    }
    logging.info("vfl final: %s", out)
    return out


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu vertical-FL entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
