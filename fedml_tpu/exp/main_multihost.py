"""Multi-host federated training entry (the jax_dcn cluster runtime).

Reference role: fedml_experiments/distributed/* launched via mpirun — one
process per worker, MPI for transport (mpi/com_manager.py:13). Here one
controller process runs per HOST, jax.distributed fuses every host's chips
into one global mesh, and the engine's round program spans it (SURVEY §5.8;
parallel/multihost.py).

Launch the same command on every host (or N local processes for testing):

  # host 0 (coordinator) .. host K-1
  python -m fedml_tpu.exp.main_multihost \\
      --coordinator host0:9911 --num_processes K --process_id <k> \\
      --dataset synthetic --client_num_in_total 64 ...

On TPU pods, omit coordinator/num_processes/process_id — they auto-detect.
For a local smoke test: --num_processes 2 --local_device_count 2
--platform cpu with two processes on one machine.
"""

from __future__ import annotations

import argparse
import logging


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    # cluster topology
    parser.add_argument("--coordinator", type=str, default=None,
                        help="host:port of process 0 (auto-detected on TPU pods)")
    parser.add_argument("--num_processes", type=int, default=None)
    parser.add_argument("--process_id", type=int, default=None)
    parser.add_argument("--local_device_count", type=int, default=None,
                        help="force N virtual CPU devices per process (testing)")
    parser.add_argument("--platform", type=str, default=None,
                        help="pin the jax platform (e.g. cpu for local testing)")
    parser.add_argument("--silo", type=int, default=1,
                        help="devices per silo group (clients x silo global mesh)")
    # the reference experiment flags (main_fedavg.py:46-130 subset)
    parser.add_argument("--dataset", type=str, default="synthetic")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--partition_method", type=str, default="hetero")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--model", type=str, default="lr")
    parser.add_argument("--client_num_in_total", type=int, default=16)
    parser.add_argument("--client_num_per_round", type=int, default=8)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=10)
    parser.add_argument("--frequency_of_the_test", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", type=str, default=None,
                        help="npz path for the final model (per process)")
    return parser


def run(args) -> dict:
    from fedml_tpu.parallel.multihost import (
        flatten_variables,
        global_client_mesh,
        init_multihost,
    )

    init_multihost(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        local_device_count=args.local_device_count,
        platform=args.platform,
    )

    import numpy as np
    import optax

    import jax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data import load_partition_data
    from fedml_tpu.models import create_model
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.engine import FedSim, SimConfig

    logging_config(jax.process_index())
    logging.info(
        "multihost: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
    ds = load_partition_data(
        args.dataset, args.data_dir, args.partition_method, args.partition_alpha,
        args.client_num_in_total, args.seed,
    )
    trainer = ClientTrainer(
        module=create_model(args.model, ds.class_num, args.dataset),
        optimizer=optax.sgd(args.lr), epochs=args.epochs,
    )
    cfg = SimConfig(
        client_num_in_total=ds.train.num_clients,
        client_num_per_round=args.client_num_per_round,
        batch_size=args.batch_size, comm_round=args.comm_round,
        epochs=args.epochs, frequency_of_the_test=args.frequency_of_the_test,
        seed=args.seed,
    )
    mesh = global_client_mesh(silo=args.silo)
    sim = FedSim(trainer, ds.train, ds.test_arrays, cfg, mesh=mesh)
    variables, history = sim.run()
    final = history[-1]
    if args.out:
        np.savez(args.out, flat=flatten_variables(variables), **{
            k.replace("/", "_"): v for k, v in final.items()
        })
    logging.info("multihost final: %s", final)
    return final


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("fedml_tpu multihost entry")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
