"""Unified experiment entry point.

Flag names follow the reference CLI exactly (fedml_experiments/distributed/
fedavg/main_fedavg.py:46-130 ``add_args``; the unified --algorithm switch is
the fedall entry, fedml_experiments/distributed/fedall/main_fedavg.py) so
reference run scripts translate 1:1:

    python -m fedml_tpu.exp.main_fedavg --model resnet56 --dataset cifar10 \
        --partition_method hetero --partition_alpha 0.5 \
        --client_num_in_total 10 --client_num_per_round 10 \
        --batch_size 64 --lr 0.001 --epochs 20 --comm_round 100

Instead of mpirun W+1 processes (run_fedavg_distributed_pytorch.sh:21), the
whole federation runs as one jitted program over the local device mesh.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import numpy as np


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    # canonical reference flag set (main_fedavg.py:46-130)
    parser.add_argument("--cf", "--config_file", dest="cf", type=str, default=None,
                        help="YAML config file; keys are the flag names below "
                             "(CLI flags override file values)")
    parser.add_argument("--model", type=str, default="lr")
    parser.add_argument("--dataset", type=str, default="mnist")
    parser.add_argument("--data_dir", type=str, default=None)
    parser.add_argument("--partition_method", type=str, default="hetero")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--dataidx_map_path", type=str, default=None,
                        help="saved net_dataidx_map file for "
                             "--partition_method hetero-fix (reference "
                             "cifar10/data_loader.py:150-158; txt or JSON)")
    parser.add_argument("--client_num_in_total", type=int, default=10)
    parser.add_argument("--client_num_per_round", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--client_optimizer", type=str, default="sgd")
    parser.add_argument("--lr", type=float, default=0.03)
    parser.add_argument("--wd", type=float, default=0.0)
    parser.add_argument("--momentum", type=float, default=0.0)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=10)
    parser.add_argument("--frequency_of_the_test", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--ci", type=int, default=0)
    parser.add_argument("--is_mobile", type=int, default=0,
                        help="1 = clients speak the reference's nested-list "
                             "JSON wire format (transform_tensor_to_list, "
                             "fedavg/utils.py:7-16) over any --backend; "
                             "requires a message-passing backend")
    parser.add_argument("--backend", type=str, default="sim",
                        choices=["sim", "loopback", "shm", "grpc", "mqtt_s3"],
                        help="sim = vectorized single-program engine; "
                             "loopback/shm/grpc/mqtt_s3 = real message-passing "
                             "FedAvg protocol over the chosen transport "
                             "(mqtt_s3: control plane on MQTT topics, model "
                             "blobs through the object store; offline it runs "
                             "on the in-process broker + filesystem store)")
    parser.add_argument("--mqtt_host", type=str, default=None,
                        help="real MQTT broker host for --backend mqtt_s3 "
                             "(default: in-process broker)")
    parser.add_argument("--mqtt_port", type=int, default=1883)
    parser.add_argument("--object_store_dir", type=str, default=None,
                        help="filesystem object-store root for mqtt_s3 "
                             "(default: a temp dir)")
    parser.add_argument("--offload_threshold_bytes", type=int, default=1 << 14,
                        help="arrays >= this many bytes ride the object "
                             "store instead of the MQTT control plane")
    parser.add_argument("--grpc_send_timeout", type=float, default=600.0,
                        help="per-send unary deadline (seconds) on the gRPC "
                             "transport (was hardcoded 600)")
    parser.add_argument("--grpc_send_workers", type=int, default=4,
                        help="broadcast send-pool width on the gRPC "
                             "transport; 0 = serial fan-out on the manager "
                             "thread (docs/PERFORMANCE.md server wire path)")
    # multi-tenant job plane (fedml_tpu/tenancy, docs/MULTITENANCY.md)
    parser.add_argument("--jobs", type=str, default=None,
                        help="path to a JSON job list: N federations "
                             "co-scheduled over ONE shared wire, send pool "
                             "and process (fedml_tpu/tenancy, "
                             "docs/MULTITENANCY.md). Each entry is an "
                             "object {\"job_id\": <name>, <flag>: <value>, "
                             "...} overriding the training/codec/defense "
                             "flags below per job; the CLI flags are the "
                             "defaults every job inherits. Requires "
                             "--backend loopback")
    # barrier-free server plane (fedml_tpu/async_agg, docs/PERFORMANCE.md
    # "Barrier-free aggregation"); message-passing backends only
    parser.add_argument("--server_mode", type=str, default="sync",
                        choices=["sync", "async", "tree"],
                        help="sync = the round-barrier protocol; async = "
                             "FedBuff-style buffered-async server (uploads "
                             "fold on arrival staleness-weighted, a model "
                             "version is emitted every --buffer_goal "
                             "arrivals, --comm_round counts emitted "
                             "versions); tree = hierarchical aggregation "
                             "(clients -> edge tiers -> root, each tier a "
                             "streaming accumulator forwarding one folded "
                             "super-update)")
    parser.add_argument("--buffer_goal", type=int, default=0,
                        help="async/tree mode: arrivals per emitted model "
                             "version (0 = the worker count, which with "
                             "the const staleness weight reproduces the "
                             "sync path bit-for-bit). Under --server_mode "
                             "tree this is the per-EDGE fold window: each "
                             "tier forwards a partial upstream every this "
                             "many child arrivals instead of per barrier")
    parser.add_argument("--staleness_weight", type=str, default="const",
                        help="async/tree mode: staleness decay family for "
                             "folds of old-version uploads — const | "
                             "poly:a | hinge:a,b (FedAsync family; "
                             "s(0) == 1 always). Under --server_mode tree "
                             "it weights stale child uploads at each edge "
                             "tier")
    parser.add_argument("--tree_fan_ins", type=str, default=None,
                        help="tree mode: comma-separated fan-in per tier, "
                             "root downward, last entry = clients per leaf "
                             "edge (e.g. '4,16' = 4 edges x 16 clients); "
                             "the leaf count must equal "
                             "--client_num_per_round. Default: one edge "
                             "over the whole cohort")
    parser.add_argument("--tree_transport", type=str, default="loopback",
                        choices=["loopback", "shm", "grpc"],
                        help="tree mode: transport each tier cell's comm "
                             "fabric runs on — loopback (in-process), shm "
                             "(one shared-memory ring namespace per cell), "
                             "grpc (localhost port block per cell, needs "
                             "grpcio)")
    parser.add_argument("--tier_timeout", type=float, default=0.0,
                        help="tree mode: elastic per-tier window timeout "
                             "in seconds — an edge whose children stall "
                             "past this emits the partial it has (complete "
                             "if the window never opened this round is "
                             "covered by the root's round timeout). 0 = "
                             "wait for the buffer goal. Arms the async "
                             "tier discipline")
    parser.add_argument("--tier_compressor", type=str, default=None,
                        help="tree mode: tier-to-tier uplink codec for "
                             "edge partials (encoded through "
                             "compress/aggregate.py encode_partial): none "
                             "| bf16 | topk | q8 | q4, composable with "
                             "'+'. 'none' ships the raw f64 accumulator "
                             "bit-exactly; delta codecs frame the partial "
                             "against the round global. Arms the async "
                             "tier discipline")
    # algorithm switch (fedall) + algorithm-specific knobs
    parser.add_argument("--algorithm", type=str, default="fedavg",
                        choices=["fedavg", "fedopt", "fedprox", "fednova", "fedgan",
                                 "hierarchical", "decentralized", "fedavg_robust"])
    parser.add_argument("--server_optimizer", type=str, default="adam")
    parser.add_argument("--server_lr", type=float, default=1e-1)
    parser.add_argument("--server_momentum", type=float, default=0.9)
    parser.add_argument("--fedprox_mu", type=float, default=0.1)
    parser.add_argument("--straggler_frac", type=float, default=0.0,
                        help="fraction of each cohort running a reduced "
                             "uniform 1..E-1 local-epoch budget (FedProx "
                             "straggler protocol)")
    parser.add_argument("--group_num", type=int, default=2)
    parser.add_argument("--group_comm_round", type=int, default=2)
    # robustness knobs (fedavg_robust main_fedavg_robust.py args;
    # docs/ROBUSTNESS.md). On --backend sim the defense runs inside the
    # round program; on the message-passing backends it runs in the
    # streaming server tally (robust_distributed.RobustDistAggregator).
    parser.add_argument("--norm_bound", type=float, default=0.0,
                        help="clip each client delta's L2 norm to this "
                             "bound (0 = no clipping)")
    parser.add_argument("--stddev", "--dp_stddev", dest="stddev",
                        type=float, default=0.0,
                        help="seeded weak-DP gaussian noise stddev on the "
                             "aggregate (0 = no noise; --dp_stddev is the "
                             "docs/ROBUSTNESS.md spelling, --stddev the "
                             "reference's)")
    parser.add_argument("--robust_rule", type=str, default="mean",
                        choices=["mean", "median", "trimmed_mean", "krum"])
    parser.add_argument("--reservoir_k", type=int, default=0,
                        help="message-passing backends only: bound the "
                             "median/trimmed_mean/krum rules to a seeded "
                             "reservoir of K uploads (0 = keep all = the "
                             "exact rule; K>0 caps host memory at O(K x "
                             "model) for huge cohorts)")
    parser.add_argument("--fault_spec", type=str, default=None,
                        help="seeded wire-fault injection on the "
                             "message-passing backends (comm/faults.py): "
                             "';'-separated '<rank|*>:<fault>=<val>,...' "
                             "with faults drop|delay[@p]|dup|corrupt|fail|"
                             "recv_drop|recv_delay[@p]|crash, e.g. "
                             "'2:drop=1.0;*:corrupt=0.05' or '0:crash=3'")
    # fault-tolerant runtime (docs/ROBUSTNESS.md "Failure recovery");
    # message-passing backends only
    parser.add_argument("--send_retries", type=int, default=0,
                        help="re-attempts per failed send on the "
                             "message-passing backends (comm/retry.py "
                             "exponential backoff + jitter); 0 = a "
                             "transient send failure fails that leg. "
                             "Fault-free runs are bit-identical either way")
    parser.add_argument("--retry_base_delay", type=float, default=0.05,
                        help="first-retry backoff in seconds (doubles per "
                             "attempt, jittered)")
    parser.add_argument("--heartbeat_interval", type=float, default=0.0,
                        help="seconds between client heartbeat status "
                             "messages (comm/status.py HeartbeatSender); "
                             "lets the server tell SLOW from dead before "
                             "the round timeout and enables readmission of "
                             "excluded workers that reappear. 0 = off")
    # heterogeneous population model (fedml_tpu/population,
    # docs/PERFORMANCE.md "Heterogeneous populations"): sim backend drives
    # cohorts/budgets/dropout in-engine; message-passing backends map the
    # spec onto per-rank upload delays/drops via the fault machinery
    from fedml_tpu.population import add_cli_flags as add_population_cli_flags

    add_population_cli_flags(parser)
    # update compression (fedml_tpu/compress, docs/COMPRESSION.md)
    parser.add_argument("--compressor", type=str, default="none",
                        help="client->server update codec: none | bf16 | "
                             "topk | q8 | q4, composable with '+' "
                             "(e.g. topk+q4). 'none' keeps the dense "
                             "bit-identical path. Works on --backend sim "
                             "and the message-passing backends; round "
                             "metrics gain Comm/* bytes-on-wire keys")
    parser.add_argument("--topk-frac", "--topk_frac", dest="topk_frac",
                        type=float, default=0.01,
                        help="fraction of entries the topk codec keeps "
                             "per leaf")
    parser.add_argument("--quantize_bits", type=int, default=8,
                        choices=[4, 8],
                        help="bit width for the quantize/q* codecs")
    parser.add_argument("--error_feedback", type=int, default=1,
                        help="carry the codec's dropped mass into the next "
                             "round's update (EF-SGD residual)")
    # downlink delta coding (fedml_tpu/compress/downlink.py,
    # docs/COMPRESSION.md "Downlink delta coding")
    parser.add_argument("--downlink_compressor", type=str, default="none",
                        help="server->client model distribution codec "
                             "(none | bf16 | topk | q8 | q4, '+'-chains): "
                             "each round close is encoded ONCE as a delta "
                             "against the previous emitted version and "
                             "served by the version each client echoed; "
                             "reconstruction is bit-exact. 'none' keeps "
                             "the dense broadcast bit-identically. "
                             "Message-passing backends only")
    parser.add_argument("--downlink_keyframe_every", type=int, default=8,
                        help="every Nth model version is a dense keyframe "
                             "(chain reset + lossless resync point)")
    parser.add_argument("--downlink_retention", type=int, default=4,
                        help="one-step deltas retained for cumulative "
                             "chains; the async server raises it from its "
                             "staleness p99 so slow clients keep a base")
    parser.add_argument("--broadcast_generations", type=int, default=2,
                        help="mqtt_s3 object-store fan-out blob retention: "
                             "a shared broadcast blob is retired once this "
                             "many newer fan-outs exist")
    # engine knobs
    parser.add_argument("--model_dtype", type=str, default="float32",
                        choices=["float32", "bfloat16"],
                        help="compute dtype for models that support one "
                             "(CV zoo, transformer); params stay float32")
    parser.add_argument("--augment", type=int, default=0,
                        help="on-device crop/flip/cutout train augmentation "
                             "(the reference's CIFAR-family torchvision "
                             "pipeline)")
    parser.add_argument("--eval_on_clients", type=int, default=0,
                        help="also run the vectorized per-client server eval "
                             "at test rounds (FedAVGAggregator "
                             "test_on_server_for_all_clients)")
    parser.add_argument("--stage_on_device", type=int, default=-1,
                        help="-1 auto, 0 host staging, 1 device-resident "
                             "dataset + in-program gather")
    parser.add_argument("--pack_lanes", type=int, default=0,
                        help="packed-lane cohort execution (docs/"
                             "PERFORMANCE.md): bin-pack each round's "
                             "per-client step streams into N fixed-length "
                             "lanes per mesh shard instead of padding every "
                             "client to the cohort max — the FLOP win on "
                             "power-law client populations. 0 = off (padded "
                             "path); bit-identical results either way")
    parser.add_argument("--pack_capacity_factor", type=float, default=1.25,
                        help="lane-length head room over the expected "
                             "per-shard cohort load; overflow draws spill "
                             "to an extra sequential pass")
    parser.add_argument("--mesh_shape", type=str, default=None,
                        help="2-D device mesh 'CLIENTSxMODEL' (e.g. 2x4): "
                             "cohort parallelism across the client axis, "
                             "tensor/FSDP model parallelism within a "
                             "client across the model axis (docs/"
                             "PERFORMANCE.md 'Sharded client models'); "
                             "validated against the device count")
    parser.add_argument("--shard_rules", type=str, default=None,
                        help="partition-rule set sharding the client model "
                             "over the mesh's model axis: transformer_tp | "
                             "transformer_fsdp | cnn_tp | cnn_fsdp "
                             "(fedml_tpu.parallel.rules); unset = every "
                             "client model lives whole on one chip. "
                             "Requires --backend sim")
    parser.add_argument("--pipeline_depth", type=int, default=-1,
                        help="pipelined round driver: -1 auto (double-"
                             "buffered staging prefetch + deferred metrics "
                             "drain), 0 serial driver, N>0 stage up to N "
                             "dispatches ahead (docs/PERFORMANCE.md); "
                             "bit-identical results either way")
    parser.add_argument("--profile_dir", type=str, default=None,
                        help="capture a jax.profiler trace of the round loop")
    # observability
    from fedml_tpu.obs.registry import add_cli_flag as add_fleet_cli_flag
    from fedml_tpu.obs.trace import add_cli_flag as add_trace_cli_flag

    add_trace_cli_flag(parser)
    add_fleet_cli_flag(parser)
    parser.add_argument("--run_dir", type=str, default=None)
    parser.add_argument("--enable_wandb", type=int, default=0)
    parser.add_argument("--checkpoint_dir", type=str, default=None)
    parser.add_argument("--checkpoint_every", type=int, default=0)
    parser.add_argument("--resume", type=int, default=0)
    parser.add_argument("--init_from", type=str, default=None,
                        help="warm-start params from a save_params .npz "
                             "(reference pretrained checkpoints, "
                             "resnet.py:202-224)")
    parser.add_argument("--save_params_to", type=str, default=None,
                        help="write the final global model variables as a "
                             "save_params .npz (reusable via --init_from)")
    return parser


def build_trainer(args, model, dataset_name: str):
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.registry import task_for_dataset

    if args.client_optimizer == "sgd":
        opt = optax.sgd(args.lr, momentum=args.momentum or None)
    else:
        opt = optax.adam(args.lr)
    if args.wd:
        opt = optax.chain(optax.add_decayed_weights(args.wd), opt)
    prox = args.fedprox_mu if args.algorithm == "fedprox" else 0.0
    trainer = ClientTrainer(
        module=model,
        task=task_for_dataset(dataset_name),
        optimizer=opt,
        epochs=args.epochs,
        prox_mu=prox,
    )
    if getattr(args, "augment", 0):
        from fedml_tpu.ops.augment import ImageAugment, with_augmentation

        if task_for_dataset(dataset_name) != "classification":
            raise ValueError("--augment is for image classification datasets")
        if dataset_name not in ("cifar10", "cifar100", "cinic10"):
            raise ValueError(
                "--augment currently implements the CIFAR-family pipeline "
                "(pad-4 crop / flip / cutout-16, reference "
                "cifar10/data_loader.py:58-76); compose "
                "fedml_tpu.ops.augment primitives directly for other shapes"
            )
        trainer = with_augmentation(trainer, ImageAugment())
    return trainer


def build_aggregator(args, train_data):
    from fedml_tpu.algorithms import (
        RobustConfig,
        fedavg_aggregator,
        fednova_aggregator,
        fedopt_aggregator,
        robust_aggregator,
        server_optimizer,
    )

    if args.algorithm == "fedopt":
        return fedopt_aggregator(
            server_optimizer(args.server_optimizer, args.server_lr, args.server_momentum)
        )
    if args.algorithm == "fednova":
        return fednova_aggregator(
            client_lr=args.lr, momentum=args.momentum, mu=0.0,
            batch_size=args.batch_size, epochs=args.epochs,
            max_client_samples=train_data.max_client_size(),
        )
    if args.algorithm == "fedavg_robust":
        return robust_aggregator(RobustConfig(
            norm_bound=args.norm_bound, stddev=args.stddev, rule=args.robust_rule,
        ))
    if args.algorithm == "decentralized":
        from fedml_tpu.algorithms.decentralized import gossip_aggregator
        from fedml_tpu.topology.topology import ring_topology

        return gossip_aggregator(ring_topology(train_data.num_clients))
    if args.algorithm == "fedgan":
        from fedml_tpu.algorithms.fedgan import fedgan_aggregator

        return fedgan_aggregator()
    if args.algorithm in ("fedavg", "fedprox", "hierarchical"):
        return fedavg_aggregator()
    # an accepted-but-unwired choice must fail loudly, never silently run
    # a different algorithm (round-1 defect: fedgan fell through to fedavg)
    raise NotImplementedError(
        f"--algorithm {args.algorithm} has no engine wiring yet"
    )


def _make_eval_fn(trainer, ds, eval_batch_size: int = 256):
    """Jitted full-test-set eval over the dataset's test arrays (the
    message-passing harness's per-round ``ev``); None when the dataset
    ships no test split."""
    if ds.test_arrays is None:
        return None
    import jax
    import jax.numpy as jnp

    from fedml_tpu.core import scan as scanlib
    from fedml_tpu.sim import cohort as cohortlib

    test_batches = jax.tree.map(
        jnp.asarray, cohortlib.batch_array(ds.test_arrays, eval_batch_size)
    )

    @jax.jit
    def ev(variables):
        def step(c, b):
            return c, trainer.eval_batch(variables, b)

        _, m = scanlib.scan(step, 0, test_batches)
        s = jax.tree.map(lambda x: jnp.sum(x, 0), m)
        tot = jnp.maximum(s["test_total"], 1.0)
        return s["test_correct"] / tot, s["test_loss"] / tot

    return ev


def _run_message_passing(args, trainer, ds, cfg, metrics) -> list[dict]:
    """Drive the real distributed FedAvg protocol (typed array messages,
    server + worker managers) over the selected transport. Reference run
    shape: mpirun W+1 processes (run_fedavg_distributed_pytorch.sh:21); here
    rank threads on loopback queues / native shm rings / localhost gRPC."""
    import functools

    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_grpc,
        run_distributed_fedavg_loopback,
        run_distributed_fedavg_mqtt_s3,
        run_distributed_fedavg_shm,
    )

    ev = _make_eval_fn(trainer, ds, cfg.eval_batch_size)

    history: list[dict] = []

    def on_round(r, variables):
        rec = {"round": r}
        # the server's accountant flushes the round's Comm/* record into
        # comm_stats just before this callback fires (fedavg_distributed
        # _done), so bytes-on-wire land in the same metrics stream as
        # Test/Acc; ditto the robust tally's Robust/* record and the async
        # server's per-emission Async/* record
        for crec in comm_stats.get("rounds", []):
            if crec.get("round") == r:
                rec.update({k: v for k, v in crec.items() if k != "round"})
        for rrec in robust_stats.get("rounds", []):
            if rrec.get("round") == r:
                rec.update({k: v for k, v in rrec.items() if k != "round"})
        for arec in async_stats.get("rounds", []):
            if arec.get("round") == r:
                rec.update({k: v for k, v in arec.items() if k != "round"})
        if ev is not None and (
            (r + 1) % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1
        ):
            acc, loss = ev(variables)
            rec.update({"Test/Acc": float(acc), "Test/Loss": float(loss)})
        history.append(rec)
        metrics.log(rec, round_idx=r)

    runners = {
        "loopback": run_distributed_fedavg_loopback,
        "shm": run_distributed_fedavg_shm,
        "grpc": functools.partial(
            run_distributed_fedavg_grpc,
            send_timeout=getattr(args, "grpc_send_timeout", 600.0),
            send_workers=getattr(args, "grpc_send_workers", 4),
        ),
        "mqtt_s3": functools.partial(
            run_distributed_fedavg_mqtt_s3,
            store_dir=args.object_store_dir,
            mqtt_host=args.mqtt_host,
            mqtt_port=args.mqtt_port,
            threshold_bytes=args.offload_threshold_bytes,
            broadcast_generations=getattr(args, "broadcast_generations", 2),
        ),
    }
    codec_kwargs = {}
    comm_stats: dict = {}
    robust_stats: dict = {}
    async_stats: dict = {}
    tier_stats: dict = {}
    # fleet telemetry plane (obs/registry.py, docs/OBSERVABILITY.md "Fleet
    # telemetry"): the runner fills the dict with per-round fleet
    # snapshots + totals; this entry persists them as fleet.jsonl/.json in
    # the --fleet_stats dir for tools/fleet_report.py. Read-only: results
    # are bit-identical with the flag off (tools/fleet_smoke.py).
    fleet_stats: dict | None = (
        {} if getattr(args, "fleet_stats", None) else None
    )
    fleet_kwargs = {"fleet_stats": fleet_stats} if fleet_stats is not None else {}
    robust_kwargs: dict = {}
    if args.algorithm == "fedavg_robust":
        from fedml_tpu.algorithms.robust_distributed import RobustDistConfig

        robust_kwargs = {
            "robust_config": RobustDistConfig(
                rule=args.robust_rule, norm_bound=args.norm_bound,
                dp_stddev=args.stddev, dp_seed=cfg.seed,
                reservoir_k=getattr(args, "reservoir_k", 0),
            ),
            "robust_stats": robust_stats,
        }
    if getattr(args, "fault_spec", None):
        robust_kwargs["fault_specs"] = args.fault_spec
        robust_kwargs["fault_seed"] = cfg.seed
    pop_kwargs: dict = {}
    if getattr(args, "population", None):
        # population wire adapter (population/wire.py): the spec's
        # distributions become per-rank upload delays/drops; profile
        # gauges ride fleet telemetry when --fleet_stats is on
        from fedml_tpu.population import population_fault_specs

        pop_seed = getattr(args, "population_seed", None)
        pop_kwargs["population"] = population_fault_specs(
            args.population, cfg.client_num_per_round,
            seed=cfg.seed if pop_seed is None else pop_seed,
        )
    ft_kwargs: dict = {}
    if getattr(args, "send_retries", 0):
        from fedml_tpu.comm.retry import RetryPolicy

        ft_kwargs["retry_policy"] = RetryPolicy(
            max_attempts=1 + args.send_retries,
            base_delay=getattr(args, "retry_base_delay", 0.05),
        )
        if getattr(args, "compressor", "none") == "none":
            # Comm/RetryCount rides comm_stats totals; with a codec the
            # compressed path passes the same dict itself
            ft_kwargs["comm_stats"] = comm_stats
    if getattr(args, "heartbeat_interval", 0.0):
        ft_kwargs["heartbeat_interval"] = args.heartbeat_interval
    if getattr(args, "checkpoint_dir", None):
        # crash-recoverable server round state: snapshot every
        # --checkpoint_every round closes; --resume restores the latest
        # snapshot and re-broadcasts its round (docs/ROBUSTNESS.md)
        ft_kwargs["checkpoint_dir"] = args.checkpoint_dir
        ft_kwargs["checkpoint_every"] = max(
            1, getattr(args, "checkpoint_every", 0) or 1
        )
        ft_kwargs["resume"] = bool(getattr(args, "resume", 0))
    if getattr(args, "compressor", "none") != "none":
        if getattr(args, "is_mobile", 0):
            raise NotImplementedError(
                "--compressor and --is_mobile both redefine the wire "
                "format; pick one"
            )
        from fedml_tpu.compress import make_codec

        codec_kwargs = {
            "codec": make_codec(args.compressor, topk_frac=args.topk_frac,
                                quantize_bits=args.quantize_bits),
            "error_feedback": bool(args.error_feedback),
            "comm_stats": comm_stats,
        }
    downlink_kwargs: dict = {}
    downlink_codec = None
    if getattr(args, "downlink_compressor", "none") != "none":
        # downlink delta coding (compress/downlink.py, docs/COMPRESSION.md
        # "Downlink delta coding"): one encode per round close, serve by
        # echoed version; 'none' resolves to the unchanged dense broadcast
        from fedml_tpu.compress.downlink import resolve_downlink_codec

        downlink_codec = resolve_downlink_codec(
            args.downlink_compressor, topk_frac=args.topk_frac,
            quantize_bits=args.quantize_bits,
        )
    if downlink_codec is not None:
        kf_every = getattr(args, "downlink_keyframe_every", 8)
        downlink_kwargs = {
            "downlink_codec": downlink_codec,
            "downlink_keyframe_every": kf_every,
            "downlink_retention": getattr(args, "downlink_retention", 4),
        }
        if "comm_stats" not in codec_kwargs and "comm_stats" not in ft_kwargs:
            downlink_kwargs["comm_stats"] = comm_stats
    overrides = None
    if getattr(args, "init_from", None):
        from fedml_tpu.obs.checkpoint import load_params

        overrides = load_params(args.init_from)
        logging.info("warm-starting from %s", args.init_from)
    mobile_kwargs = {}
    if getattr(args, "is_mobile", 0):
        # reference semantics: is_mobile=1 means EVERY client is a phone —
        # all model payloads cross the wire as nested-list JSON
        from fedml_tpu.algorithms.fedavg_mobile import mobile_runner_kwargs

        ranks = set(range(1, cfg.client_num_per_round + 1))
        mobile_kwargs = mobile_runner_kwargs(ranks)
        logging.info("is_mobile=1: JSON nested-list wire format for ranks %s",
                     sorted(ranks))
    server_mode = getattr(args, "server_mode", "sync")
    if server_mode == "tree":
        # hierarchical aggregation: its process topology is a tree of comm
        # cells, not the flat runners' single fan-out
        from fedml_tpu.async_agg.tree import TreeTopology, run_tree_fedavg_loopback

        fan_spec = getattr(args, "tree_fan_ins", None)
        fan_ins = (tuple(int(f) for f in fan_spec.split(","))
                   if fan_spec else (1, cfg.client_num_per_round))
        topo = TreeTopology(fan_ins)
        if topo.leaf_count != cfg.client_num_per_round:
            raise ValueError(
                f"--tree_fan_ins {fan_ins} has {topo.leaf_count} leaves but "
                f"--client_num_per_round is {cfg.client_num_per_round}; the "
                "leaves ARE the per-round cohort"
            )
        logging.info("tree mode: fan-ins %s (%d leaves, %d edge tiers)",
                     fan_ins, topo.leaf_count, topo.tier_count)
        tree_kwargs: dict = {"tier_stats": tier_stats}
        if "comm_stats" not in downlink_kwargs:
            tree_kwargs["comm_stats"] = comm_stats
        if getattr(args, "buffer_goal", 0):
            tree_kwargs["buffer_goal"] = args.buffer_goal
        if getattr(args, "staleness_weight", "const") != "const":
            tree_kwargs["tier_staleness"] = args.staleness_weight
        if getattr(args, "tier_timeout", 0.0):
            tree_kwargs["tier_timeout"] = args.tier_timeout
        if getattr(args, "tier_compressor", None) is not None:
            tree_kwargs["tier_uplink_codec"] = args.tier_compressor
        if codec_kwargs:
            # the same client->server codec the flat runners take, applied
            # at the leaf edges (each decodes its children's encoded deltas
            # into the model domain before folding)
            tree_kwargs["client_codec"] = codec_kwargs["codec"]
            tree_kwargs["client_error_feedback"] = codec_kwargs[
                "error_feedback"]
        if pop_kwargs:
            # one churn trace over the whole hierarchy: the adapter indexes
            # by GLOBAL leaf number, so the tree sees the same per-client
            # draws the flat wire path would
            tree_kwargs["population"] = pop_kwargs["population"]
            tree_kwargs["fault_seed"] = pop_kwargs["population"].seed
        for k in ("retry_policy", "heartbeat_interval"):
            if k in ft_kwargs:
                tree_kwargs[k] = ft_kwargs[k]
        transport = getattr(args, "tree_transport", "loopback")
        if transport == "shm":
            from fedml_tpu.async_agg.tree import run_tree_fedavg_shm

            tree_runner = run_tree_fedavg_shm
        elif transport == "grpc":
            from fedml_tpu.async_agg.tree import GrpcGroupComm

            tree_runner = run_tree_fedavg_loopback
            tree_kwargs["make_group_comm"] = GrpcGroupComm(
                base_port=getattr(args, "grpc_base_port", 8890))
        else:
            tree_runner = run_tree_fedavg_loopback
        final_variables = tree_runner(
            trainer, ds.train, topo, cfg.comm_round, cfg.batch_size,
            seed=cfg.seed, on_round_done=on_round, init_overrides=overrides,
            **downlink_kwargs,
            **fleet_kwargs,
            **tree_kwargs,
        )
    else:
        mode_kwargs = {}
        if server_mode == "async":
            mode_kwargs = {
                "server_mode": "async",
                "buffer_goal": getattr(args, "buffer_goal", 0) or None,
                "staleness_weight": getattr(args, "staleness_weight", "const"),
                "async_stats": async_stats,
            }
        final_variables = runners[args.backend](
            trainer, ds.train,
            worker_num=cfg.client_num_per_round,
            round_num=cfg.comm_round,
            batch_size=cfg.batch_size,
            seed=cfg.seed,
            on_round_done=on_round,
            init_overrides=overrides,
            **mobile_kwargs,
            **codec_kwargs,
            **downlink_kwargs,
            **robust_kwargs,
            **ft_kwargs,
            **mode_kwargs,
            **fleet_kwargs,
            **pop_kwargs,
        )
    if comm_stats.get("totals"):
        logging.info("bytes on wire: %s", comm_stats["totals"])
    if async_stats.get("totals"):
        logging.info("async server: %s", async_stats["totals"])
    if tier_stats.get("totals"):
        logging.info("edge tiers: %s", tier_stats["totals"])
    if fleet_stats is not None:
        import json
        import os

        from fedml_tpu.obs.registry import FLEET_JSONL_NAME

        out_dir = args.fleet_stats
        os.makedirs(out_dir, exist_ok=True)
        jsonl = os.path.join(out_dir, FLEET_JSONL_NAME)
        with open(jsonl, "w") as f:
            for rec in fleet_stats.get("rounds", []):
                f.write(json.dumps(rec) + "\n")
        with open(os.path.join(out_dir, "fleet.json"), "w") as f:
            # the per-round snapshots live in fleet.jsonl only — each one is
            # a full cumulative fleet view, so duplicating the list here
            # would double the disk footprint for nothing
            json.dump({"totals": fleet_stats.get("totals"),
                       "registry": fleet_stats.get("registry"),
                       "rounds_recorded": len(fleet_stats.get("rounds", []))},
                      f)
        logging.info("fleet telemetry written to %s (render: python "
                     "tools/fleet_report.py %s)", out_dir, jsonl)
    if getattr(args, "save_params_to", None):
        from fedml_tpu.obs.checkpoint import save_params

        saved = save_params(args.save_params_to, final_variables)
        logging.info("saved final model variables to %s", saved)
    return history


# per-job override keys the --jobs entries may carry: the core training /
# codec / defense flags. Everything else (fault injection, retry/liveness,
# checkpointing, topology modes) stays single-job and is rejected loudly in
# _reject_multijob_conflicts — never silently dropped.
_JOBS_OVERRIDE_KEYS = frozenset({
    "model", "dataset", "data_dir", "partition_method", "partition_alpha",
    "dataidx_map_path", "client_num_in_total", "client_num_per_round",
    "batch_size", "client_optimizer", "lr", "wd", "momentum", "epochs",
    "comm_round", "frequency_of_the_test", "seed", "algorithm",
    "fedprox_mu", "robust_rule", "norm_bound", "stddev", "reservoir_k",
    "compressor", "topk_frac", "quantize_bits", "error_feedback",
    "downlink_compressor", "downlink_keyframe_every", "downlink_retention",
    "model_dtype",
})


def _reject_multijob_conflicts(args) -> None:
    """Flag-combination gate for --jobs: fail before any data/model work
    (the same loud-rejection convention as the sim/tree guards in _run)."""
    if args.backend != "loopback":
        raise NotImplementedError(
            "--jobs co-schedules every job's federation over ONE shared "
            "endpoint with job-id demux (fedml_tpu/tenancy); only the "
            "loopback transport has the shared-fabric wiring — pick "
            "--backend loopback"
        )
    if getattr(args, "server_mode", "sync") != "sync":
        raise NotImplementedError(
            f"--server_mode {args.server_mode} reshapes the single server "
            "plane the jobs share; --jobs runs each job's sync round "
            "protocol — pick --server_mode sync"
        )
    if getattr(args, "is_mobile", 0):
        raise NotImplementedError(
            "--is_mobile selects the JSON nested-list wire format, which "
            "is not wired through the shared job plane; pick one"
        )
    unwired = [
        flag for flag, val in [
            ("--fault_spec", getattr(args, "fault_spec", None)),
            ("--population", getattr(args, "population", None)),
            ("--send_retries", getattr(args, "send_retries", 0)),
            ("--heartbeat_interval", getattr(args, "heartbeat_interval", 0.0)),
            ("--checkpoint_dir", getattr(args, "checkpoint_dir", None)),
            ("--resume", getattr(args, "resume", 0)),
            ("--init_from", getattr(args, "init_from", None)),
            ("--save_params_to", getattr(args, "save_params_to", None)),
        ] if val
    ]
    if unwired:
        # consumed by the single-job harness this branch bypasses; ignoring
        # them silently would fake a robustness or recovery experiment
        raise NotImplementedError(
            f"{', '.join(unwired)} not wired into --jobs yet: the "
            "multi-tenant entry wires the training/codec/defense planes "
            "per job — drive tenancy.run_multi_job(run_kwargs=...) "
            "directly for the fault/retry/liveness/checkpoint planes"
        )


def _multijob_run_kwargs(overlay):
    """One job's composition kwargs for run_distributed_fedavg (the --jobs
    subset of the single-job harness planes: uplink codec, downlink delta
    coding, robust defense). Returns (run_kwargs, stats_dicts) where each
    stats dict fills with per-round records to merge into the job's
    metric stream."""
    run_kwargs: dict = {}
    comm_stats: dict = {}
    robust_stats: dict = {}
    if getattr(overlay, "compressor", "none") != "none":
        from fedml_tpu.compress import make_codec

        run_kwargs.update(
            codec=make_codec(overlay.compressor, topk_frac=overlay.topk_frac,
                             quantize_bits=overlay.quantize_bits),
            error_feedback=bool(overlay.error_feedback),
            comm_stats=comm_stats,
        )
    if getattr(overlay, "downlink_compressor", "none") != "none":
        from fedml_tpu.compress.downlink import resolve_downlink_codec

        downlink_codec = resolve_downlink_codec(
            overlay.downlink_compressor, topk_frac=overlay.topk_frac,
            quantize_bits=overlay.quantize_bits,
        )
        if downlink_codec is not None:
            run_kwargs.update(
                downlink_codec=downlink_codec,
                downlink_keyframe_every=getattr(
                    overlay, "downlink_keyframe_every", 8),
                downlink_retention=getattr(overlay, "downlink_retention", 4),
            )
            if "comm_stats" not in run_kwargs:
                run_kwargs["comm_stats"] = comm_stats
    if overlay.algorithm == "fedavg_robust":
        from fedml_tpu.algorithms.robust_distributed import RobustDistConfig

        run_kwargs.update(
            robust_config=RobustDistConfig(
                rule=overlay.robust_rule, norm_bound=overlay.norm_bound,
                dp_stddev=overlay.stddev, dp_seed=overlay.seed,
                reservoir_k=getattr(overlay, "reservoir_k", 0),
            ),
            robust_stats=robust_stats,
        )
    return run_kwargs, [comm_stats, robust_stats]


def _run_multi_job(args, metrics) -> list[dict]:
    """--jobs harness: load the JSON job list, build each job's data/model/
    trainer from the overlaid flags, and hand the whole set to
    tenancy.run_multi_job — one shared wire, send pool, and scheduler
    (docs/MULTITENANCY.md). Each job's per-round records (Comm/*, Robust/*,
    Test/* at the job's test frequency) are logged tagged with its name;
    with --fleet_stats DIR the runner writes DIR/<job>/fleet.jsonl +
    DIR/jobs.json."""
    import copy
    import json

    from fedml_tpu.comm.message import Message
    from fedml_tpu.data import load_partition_data
    from fedml_tpu.models import create_model
    from fedml_tpu.tenancy import JobSpec, job_key, run_multi_job

    with open(args.jobs) as f:
        entries = json.load(f)
    if not isinstance(entries, list) or not entries:
        raise ValueError(
            f"--jobs {args.jobs}: expected a non-empty JSON list of job "
            "objects (docs/MULTITENANCY.md 'Job specs')"
        )
    specs: list[JobSpec] = []
    hist_by_job: dict[str, list[dict]] = {}
    for i, entry in enumerate(entries):
        if not isinstance(entry, dict):
            raise ValueError(
                f"--jobs entry {i} is not a JSON object: {entry!r}")
        entry = dict(entry)
        # the spec field is deliberately spelled like the wire header the
        # name becomes (docs/MULTITENANCY.md "The wire header")
        job_id = entry.pop(Message.MSG_ARG_KEY_JOB_ID, None)
        if job_id is None and len(entries) > 1:
            raise ValueError(
                f"--jobs entry {i} has no job_id — with more than one job "
                "every entry needs a unique name on the shared wire"
            )
        unknown = sorted(set(entry) - _JOBS_OVERRIDE_KEYS)
        if unknown:
            raise ValueError(
                f"--jobs entry {i} ({job_key(job_id)}): unknown override "
                f"keys {unknown}; supported: {sorted(_JOBS_OVERRIDE_KEYS)}"
            )
        overlay = copy.copy(args)
        for k, v in entry.items():
            setattr(overlay, k, v)
        if overlay.algorithm not in ("fedavg", "fedprox", "fedavg_robust"):
            raise NotImplementedError(
                f"--jobs entry {job_key(job_id)}: --algorithm "
                f"{overlay.algorithm} is sim-engine only; the job plane "
                "runs the message-passing protocol (fedavg | fedprox | "
                "fedavg_robust)"
            )
        ds = load_partition_data(
            overlay.dataset, overlay.data_dir, overlay.partition_method,
            overlay.partition_alpha, overlay.client_num_in_total,
            overlay.seed,
            dataidx_map_path=getattr(overlay, "dataidx_map_path", None),
        )
        model = create_model(overlay.model, ds.class_num, overlay.dataset,
                             dtype=getattr(overlay, "model_dtype", None))
        trainer = build_trainer(overlay, model, overlay.dataset)
        run_kwargs, stats_dicts = _multijob_run_kwargs(overlay)
        name = job_key(job_id)
        history = hist_by_job.setdefault(name, [])
        ev = _make_eval_fn(trainer, ds)
        freq = max(overlay.frequency_of_the_test
                   if not overlay.ci else overlay.comm_round, 1)
        last = overlay.comm_round - 1

        def on_round(r, variables, name=name, history=history, ev=ev,
                     stats_dicts=stats_dicts, freq=freq, last=last):
            rec = {"job": name, "round": r}
            for stats in stats_dicts:
                for srec in stats.get("rounds", []):
                    if srec.get("round") == r:
                        rec.update({k: v for k, v in srec.items()
                                    if k != "round"})
            if ev is not None and ((r + 1) % freq == 0 or r == last):
                acc, loss = ev(variables)
                rec.update({"Test/Acc": float(acc),
                            "Test/Loss": float(loss)})
            history.append(rec)

        specs.append(JobSpec(
            trainer=trainer, train_data=ds.train,
            worker_num=min(overlay.client_num_per_round,
                           ds.train.num_clients),
            round_num=overlay.comm_round, batch_size=overlay.batch_size,
            job_id=job_id, seed=overlay.seed, on_round=on_round,
            fleet=bool(getattr(args, "fleet_stats", None)),
            run_kwargs=run_kwargs,
        ))
    out_dir = getattr(args, "fleet_stats", None)
    logging.info("--jobs: co-scheduling %d jobs (%d workers total) over "
                 "one shared wire", len(specs),
                 sum(s.worker_num for s in specs))
    results = run_multi_job(specs, out_dir=out_dir)
    history: list[dict] = []
    failed: dict[str, BaseException] = {}
    for spec in specs:
        res = results[spec.name]
        for rec in hist_by_job.get(spec.name, []):
            metrics.log(rec)
            history.append(rec)
        logging.info("job %s: totals %s", spec.name, res.totals)
        if res.error is not None:
            failed[spec.name] = res.error
    if out_dir:
        logging.info("per-job telemetry written to %s (jobs.json + "
                     "<job>/fleet.jsonl)", out_dir)
    if failed:
        # neighbors' results are already logged/written above — the CLI
        # still has to exit nonzero when any tenant failed
        raise RuntimeError(
            f"{len(failed)}/{len(specs)} jobs failed: "
            + "; ".join(f"{n}: {e!r}" for n, e in sorted(failed.items()))
        )
    return history


def run(args) -> list[dict]:
    from fedml_tpu.obs.trace import run_traced

    return run_traced(_run, args)


def _run(args) -> list[dict]:
    import jax

    from fedml_tpu.data import load_partition_data
    from fedml_tpu.models import create_model
    from fedml_tpu.obs.metrics import MetricsLogger, logging_config
    from fedml_tpu.parallel.mesh import parse_mesh_shape
    from fedml_tpu.sim.engine import FedSim, SimConfig

    logging_config(0)
    if getattr(args, "jobs", None):
        # multi-tenant job plane (fedml_tpu/tenancy, docs/MULTITENANCY.md):
        # N federations over one shared wire. Gate the flag combos loudly,
        # then hand off — each job builds its own data/model/trainer from
        # its overlaid flags inside the harness
        _reject_multijob_conflicts(args)
        with MetricsLogger(run_dir=args.run_dir,
                           use_wandb=bool(args.enable_wandb)) as metrics:
            return _run_multi_job(args, metrics)
    if getattr(args, "is_mobile", 0) and args.backend == "sim":
        # pure flag-combination error: fail before any data/model work
        raise NotImplementedError(
            "--is_mobile 1 selects the JSON wire format, which only exists "
            "on the message-passing backends — pick --backend "
            "loopback|shm|grpc|mqtt_s3"
        )
    if getattr(args, "fault_spec", None) and args.backend == "sim":
        raise NotImplementedError(
            "--fault_spec injects wire faults — there is no wire on "
            "--backend sim; pick --backend loopback|shm|grpc|mqtt_s3"
        )
    if getattr(args, "population_trace", None) and args.backend != "sim":
        raise NotImplementedError(
            "--population_trace replays recorded sim cohorts/step budgets/"
            "dropouts; the message-passing backends take the generative "
            "--population spec (per-rank delay/drop adapter) — use "
            "--backend sim"
        )
    if getattr(args, "population", None) and getattr(args, "fault_spec", None):
        raise NotImplementedError(
            "--population and --fault_spec both drive the seeded wire "
            "fault injector — one schedule would silently shift the "
            "other; pick one"
        )
    if getattr(args, "fleet_stats", None) and args.backend == "sim":
        raise NotImplementedError(
            "--fleet_stats records per-CLIENT wire/health telemetry — on "
            "--backend sim there are no client processes or uploads to "
            "observe; pick --backend loopback|shm|grpc|mqtt_s3 (the sim "
            "engine's observability is --trace_dir, docs/OBSERVABILITY.md)"
        )
    server_mode = getattr(args, "server_mode", "sync")
    if server_mode != "sync":
        if args.backend == "sim":
            raise NotImplementedError(
                f"--server_mode {server_mode} selects a message-passing "
                "server execution mode — there is no server process on "
                "--backend sim; pick --backend loopback|shm|grpc|mqtt_s3"
            )
        if getattr(args, "is_mobile", 0):
            raise NotImplementedError(
                f"--server_mode {server_mode} and --is_mobile both redefine "
                "the server protocol; pick one"
            )
    if server_mode not in ("async", "tree"):
        misapplied = [
            flag for flag, val in [
                ("--buffer_goal", getattr(args, "buffer_goal", 0)),
                ("--staleness_weight",
                 getattr(args, "staleness_weight", "const") != "const"),
            ] if val
        ]
        if misapplied:
            # same loud-rejection convention as the unwired tree flags
            # below: silently dropping these would fake a staleness
            # experiment as a plain sync run
            raise NotImplementedError(
                f"not valid with --server_mode {server_mode}: "
                f"{', '.join(misapplied)} (buffered-async fold knobs) — "
                "pick --server_mode async|tree"
            )
    if server_mode != "tree":
        tree_only = [
            flag for flag, val in [
                ("--tree_fan_ins", getattr(args, "tree_fan_ins", None)),
                ("--tree_transport",
                 getattr(args, "tree_transport", "loopback") != "loopback"),
                ("--tier_timeout", getattr(args, "tier_timeout", 0.0)),
                ("--tier_compressor",
                 getattr(args, "tier_compressor", None) is not None),
            ] if val
        ]
        if tree_only:
            raise NotImplementedError(
                f"{', '.join(tree_only)} shape the hierarchical tier plane "
                f"and are ignored under --server_mode {server_mode} — pick "
                "--server_mode tree"
            )
    if server_mode == "tree":
        if args.backend != "loopback":
            raise NotImplementedError(
                "--server_mode tree builds its own comm fabric per tier "
                "cell; the cell transport is --tree_transport "
                "loopback|shm|grpc, not --backend — keep --backend "
                "loopback"
            )
        if args.algorithm == "fedavg_robust":
            raise NotImplementedError(
                "--algorithm fedavg_robust's flat-cohort rules "
                "(median/krum/...) need every upload resident and do not "
                "compose with streaming tiers; the tree's per-tier "
                "clip+DP defense is the harness API "
                "(async_agg.tree.run_tree_fedavg(tier_defense=...)) — "
                "use --server_mode sync|async for fedavg_robust"
            )
        unwired = [
            flag for flag, val in [
                ("--fault_spec", getattr(args, "fault_spec", None)),
                ("--checkpoint_dir", getattr(args, "checkpoint_dir", None)),
                ("--resume", getattr(args, "resume", 0)),
            ] if val
        ]
        if unwired:
            # these flags are consumed by the flat runner the tree branch
            # bypasses — ignoring them silently would fake a robustness or
            # recovery experiment (same loud-rejection convention as the
            # sim-backend guards above)
            raise NotImplementedError(
                f"{', '.join(unwired)} not wired into --server_mode tree "
                "yet: the tree branch drives its own per-cell harness "
                "(async_agg.tree.run_tree_fedavg), which does not take the "
                "fault-injection/checkpoint planes — use --server_mode "
                "sync|async, or drive the harness API directly "
                "(churn rides --population instead)"
            )
    if (getattr(args, "send_retries", 0)
            or getattr(args, "heartbeat_interval", 0.0)) and args.backend == "sim":
        raise NotImplementedError(
            "--send_retries/--heartbeat_interval configure the "
            "message-passing send/liveness planes — there is no wire on "
            "--backend sim; pick --backend loopback|shm|grpc|mqtt_s3"
        )
    if getattr(args, "downlink_compressor", "none") != "none" \
            and getattr(args, "is_mobile", 0):
        raise NotImplementedError(
            "--downlink_compressor and --is_mobile both redefine the "
            "downlink wire format; pick one"
        )
    if getattr(args, "broadcast_generations", 2) != 2 \
            and args.backend != "mqtt_s3":
        raise NotImplementedError(
            "--broadcast_generations shapes the mqtt_s3 object-store "
            "blob retention; the other backends keep no broadcast blobs "
            "— pick --backend mqtt_s3"
        )
    if (getattr(args, "shard_rules", None)
            or getattr(args, "mesh_shape", None)) and args.backend != "sim":
        raise NotImplementedError(
            "--shard_rules/--mesh_shape configure the sim engine's device "
            "mesh and jitted round programs; the message-passing backends "
            "train whole models per worker — use --backend sim"
        )
    logging.info("devices: %s", jax.devices())

    ds = load_partition_data(
        args.dataset, args.data_dir, args.partition_method, args.partition_alpha,
        args.client_num_in_total, args.seed,
        dataidx_map_path=getattr(args, "dataidx_map_path", None),
    )
    model = create_model(args.model, ds.class_num, args.dataset,
                         dtype=getattr(args, "model_dtype", None))
    trainer = build_trainer(args, model, args.dataset)
    aggregator = build_aggregator(args, ds.train)

    # decentralized/gossip: every node participates every round
    per_round = (
        ds.train.num_clients
        if args.algorithm == "decentralized"
        else min(args.client_num_per_round, ds.train.num_clients)
    )
    cfg = SimConfig(
        client_num_in_total=ds.train.num_clients,
        client_num_per_round=per_round,
        batch_size=args.batch_size,
        comm_round=args.comm_round,
        epochs=args.epochs,
        frequency_of_the_test=args.frequency_of_the_test if not args.ci else args.comm_round,
        seed=args.seed,
        straggler_frac=args.straggler_frac,
        eval_on_clients=bool(args.eval_on_clients),
        stage_on_device=(None if args.stage_on_device < 0
                         else bool(args.stage_on_device)),
        pipeline_depth=(None if getattr(args, "pipeline_depth", -1) < 0
                        else args.pipeline_depth),
        pack_lanes=getattr(args, "pack_lanes", 0),
        pack_capacity_factor=getattr(args, "pack_capacity_factor", 1.25),
        population=(getattr(args, "population", None)
                    if args.backend == "sim" else None),
        population_trace=getattr(args, "population_trace", None),
        population_seed=getattr(args, "population_seed", None),
        mesh_shape=parse_mesh_shape(getattr(args, "mesh_shape", None)),
        shard_rules=getattr(args, "shard_rules", None),
        compressor=getattr(args, "compressor", "none"),
        topk_frac=getattr(args, "topk_frac", 0.01),
        quantize_bits=getattr(args, "quantize_bits", 8),
        downlink_compressor=getattr(args, "downlink_compressor", "none"),
        error_feedback=bool(getattr(args, "error_feedback", 1)),
        profile_dir=args.profile_dir,
    )

    metrics = MetricsLogger(run_dir=args.run_dir, use_wandb=bool(args.enable_wandb))

    # ---- real message-passing backends (loopback / shm / grpc) ----
    if args.backend != "sim":
        if args.algorithm not in ("fedavg", "fedprox", "fedavg_robust"):
            raise NotImplementedError(
                f"--backend {args.backend} runs the message-passing FedAvg "
                f"protocol; --algorithm {args.algorithm} is sim-engine only"
            )
        history = _run_message_passing(args, trainer, ds, cfg, metrics)
        metrics.close()
        return history

    if args.algorithm == "fedgan":
        from fedml_tpu.algorithms.fedgan import GANTrainer, make_gan_local_train
        from fedml_tpu.models.gan import Discriminator, Generator

        import optax

        img_shape = tuple(ds.train.arrays["x"].shape[1:])
        gan = GANTrainer(
            Generator(img_shape=img_shape),
            Discriminator(img_shape=img_shape),
            optax.adam(args.lr, b1=0.5),
            optax.adam(args.lr, b1=0.5),
            epochs=args.epochs,
        )
        sim = FedSim(
            gan, ds.train, None, cfg, aggregator=aggregator,
            local_train_fn=make_gan_local_train(gan),
        )
        _, history = sim.run(callback=lambda rec: metrics.log(rec))
        metrics.close()
        return history

    if args.algorithm == "hierarchical":
        from fedml_tpu.algorithms.hierarchical import HierarchicalFedAvg, HierConfig

        sim = FedSim(trainer, ds.train, ds.test_arrays, cfg, aggregator=aggregator)
        hier = HierarchicalFedAvg(sim, HierConfig(
            group_num=args.group_num,
            global_comm_round=args.comm_round,
            group_comm_round=args.group_comm_round,
        ))
        _, history = hier.run()
        for rec in history:
            metrics.log(rec)
        metrics.close()
        return history

    sim = FedSim(trainer, ds.train, ds.test_arrays, cfg, aggregator=aggregator)

    ckptr = None
    if args.checkpoint_dir:
        from fedml_tpu.obs.checkpoint import RoundCheckpointer

        ckptr = RoundCheckpointer(args.checkpoint_dir)

    overrides = None
    if args.init_from:
        from fedml_tpu.obs.checkpoint import load_params

        overrides = load_params(args.init_from)
        logging.info("warm-starting from %s (collections: %s)",
                     args.init_from, sorted(overrides))

    # checkpoint/resume-aware run. Without checkpointing, the engine's
    # run() drives everything (block dispatch, profiling, per-client eval).
    # With checkpointing, rounds run one dispatch at a time so every saved
    # round has its exact model state.
    variables = sim.init_round_variables(overrides)
    server_state = sim.aggregator.init_state(variables)
    start_round = 0
    history: list[dict] = []
    if args.resume and ckptr is not None and ckptr.latest_round() is not None:
        variables, server_state, start_round, history = ckptr.restore(
            variables, like_server_state=server_state
        )
        start_round += 1
        logging.info("resumed from round %d", start_round - 1)

    def _maybe_save_params(final_variables):
        if args.save_params_to:
            from fedml_tpu.obs.checkpoint import save_params

            saved = save_params(args.save_params_to, sim.consensus(final_variables))
            logging.info("saved final model variables to %s", saved)

    if ckptr is None or not args.checkpoint_every:
        final_variables, run_history = sim.run(
            callback=lambda rec: metrics.log(rec, round_idx=rec["round"]),
            variables=variables, server_state=server_state,
            start_round=start_round,
        )
        _maybe_save_params(final_variables)
        metrics.close()
        return history + run_history

    from fedml_tpu.core import rng as rnglib

    if cfg.profile_dir:
        logging.warning(
            "--profile_dir is not captured on the checkpointed per-round "
            "path; run without --checkpoint_every to profile"
        )
    freq = max(cfg.frequency_of_the_test, 1)
    root = rnglib.root_key(cfg.seed)
    for r in range(start_round, cfg.comm_round):
        variables, server_state, m = sim.run_round(r, variables, server_state, root)
        jax.block_until_ready(jax.tree_util.tree_leaves(variables)[0])
        rec = {"round": r, **{k: float(v) for k, v in m.items()}}
        if (r + 1) % freq == 0 or r == cfg.comm_round - 1:
            rec.update(sim.eval_record(variables))
        history.append(rec)
        metrics.log(rec, round_idx=r)
        if (r + 1) % args.checkpoint_every == 0:
            ckptr.save(r, variables, server_state, history)
    _maybe_save_params(variables)
    metrics.close()
    return history


def parse_with_config(parser: argparse.ArgumentParser, argv=None):
    """Parse argv, honoring ``--cf config.yaml`` (the north-star "unchanged
    YAML configs" entry shape; reference passes YAML for GPU mapping and
    credentials, fed_launch/main.py:357). File keys are flag names; explicit
    CLI flags override file values; unknown keys fail loudly."""
    args = parser.parse_args(argv)
    if not args.cf:
        return args
    import yaml

    with open(args.cf) as f:
        conf = yaml.safe_load(f) or {}
    if not isinstance(conf, dict):
        raise ValueError(f"--cf {args.cf}: top level must be a mapping")
    actions = {a.dest: a for a in parser._actions}
    known = set(vars(args)) - {"cf"}  # no config chaining: cf-in-cf is an error
    unknown = sorted(set(conf) - known)
    if unknown:
        raise ValueError(f"--cf {args.cf}: unknown keys {unknown}")
    coerced = {}
    for key, val in conf.items():
        a = actions[key]
        # apply the type coercion + choices validation the CLI path gets
        # (YAML reads "1e-3" as a string, set_defaults alone would smuggle
        # it past type=float)
        if val is None:
            if a.default is not None:
                raise ValueError(
                    f"--cf {args.cf}: key {key} has no value "
                    f"(flag default is {a.default!r})"
                )
        elif a.type is not None:
            if a.type is int and isinstance(val, float) and int(val) != val:
                raise ValueError(
                    f"--cf {args.cf}: key {key}: {val!r} is not an integer"
                )
            try:
                val = a.type(val)
            except (TypeError, ValueError) as e:
                raise ValueError(f"--cf {args.cf}: key {key}: {e}") from None
        if a.choices is not None and val not in a.choices:
            raise ValueError(
                f"--cf {args.cf}: key {key}: {val!r} not in {sorted(a.choices)}"
            )
        coerced[key] = val
    parser.set_defaults(**coerced)
    return parser.parse_args(argv)  # CLI flags still win over file values


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    parser = add_args(argparse.ArgumentParser("fedml_tpu unified entry"))
    args = parse_with_config(parser, argv)
    history = run(args)
    final = history[-1] if history else {}
    logging.info("final: %s", final)
    return final


if __name__ == "__main__":
    main()
