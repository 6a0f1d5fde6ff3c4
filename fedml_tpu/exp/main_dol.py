"""Decentralized online learning (DOL) experiment entry.

Reference: fedml_experiments/standalone/decentralized/main_dol.py — gossip
online learning on streaming UCI data (SUSY / room occupancy): DSGD over an
undirected topology or Push-Sum over (optionally time-varying) directed
graphs, with cumulative regret as the metric (decentralized_fl_api.py:11).
Reference flag names kept where the concept survives; the mode flag maps
DOL→gossip modes (dsgd | pushsum) instead of the reference's LOCAL/DOL/COL
process split.
"""

from __future__ import annotations

import argparse
import logging


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--mode", type=str, default="dsgd",
                        choices=["dsgd", "pushsum"])
    parser.add_argument("--data_name", type=str, default="SUSY",
                        help="SUSY | room_occupancy (RO)")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--iteration_number", type=int, default=200,
                        help="streaming rounds T (>= 2: the report splits "
                             "the stream into halves)")
    parser.add_argument("--client_number", type=int, default=15,
                        help="network size N")
    parser.add_argument("--learning_rate", type=float, default=0.01)
    parser.add_argument("--topology_neighbors_num_undirected", type=int, default=4)
    parser.add_argument("--time_varying", type=int, default=0,
                        help="pushsum: redraw the directed graph every round")
    parser.add_argument("--seed", type=int, default=0)
    return parser


def run(args) -> dict:
    from fedml_tpu.algorithms.decentralized import run_online_gossip
    from fedml_tpu.data.uci import load_streaming
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.topology.topology import SymmetricTopologyManager

    logging_config(0)
    if args.iteration_number < 2:
        # fail before the gossip run, not after it: the report splits the
        # stream into halves and needs at least two rounds
        raise ValueError("--iteration_number must be >= 2")
    name = {"ro": "room_occupancy"}.get(args.data_name.lower(), args.data_name)
    xs, ys = load_streaming(
        name, args.data_dir, n_nodes=args.client_number,
        T=args.iteration_number, seed=args.seed,
    )
    topology = SymmetricTopologyManager(
        args.client_number, args.topology_neighbors_num_undirected,
        seed=args.seed,
    ).generate_topology()
    if args.mode == "pushsum":
        # push-sum conserves mass only under a COLUMN-stochastic mixing
        # matrix (client_pushsum.py:36-45); the symmetric manager emits a
        # row-stochastic one, so hand its transpose to the static path
        # (time-varying graphs are generated column-stochastic already)
        topology = topology.T
    params, regret = run_online_gossip(
        xs, ys, n_nodes=args.client_number, lr=args.learning_rate,
        mode=args.mode, topology=topology,
        time_varying=bool(args.time_varying), seed=args.seed,
    )
    half = len(regret) // 2
    final = {
        "mode": args.mode,
        "iterations": int(args.iteration_number),
        "final_regret": float(regret[-1]),
        "avg_regret": float(regret[-1] / len(regret)),
        # per-round loss averages for the two stream halves: a learner
        # makes the late half cheaper than the early half
        "early_avg_loss": float(regret[half - 1] / half),
        "late_avg_loss": float((regret[-1] - regret[half - 1]) / (len(regret) - half)),
    }
    logging.info("dol final: %s", final)
    return final


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu dol entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
