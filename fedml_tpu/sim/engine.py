"""The vectorized federated-simulation engine.

Replaces the reference's entire distributed actor system for the simulation
paradigm (SURVEY §3.1/§3.2): instead of W+1 MPI processes exchanging pickled
state_dicts, one jitted XLA program runs the whole round — ``vmap`` over the
cohort's client axis (sharded over the device mesh), ``lax.scan`` over local
epochs/steps, and a weighted all-reduce for aggregation. The 0.3 s polling
loops, per-message pickling, and serial client loop of the reference
(mpi/com_manager.py:71-78, fedavg_api.py:56-66) have no equivalent here — they
are compiled away.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.base import (
    Aggregator,
    EmptyRoundError,
    fedavg_aggregator,
)
from fedml_tpu.core import rng as rnglib
from fedml_tpu.core import scan as scanlib
from fedml_tpu.core.trainer import (
    STATS_PREFIX, ClientTrainer, make_lane_step, make_local_eval,
    make_local_train,
)
from fedml_tpu.obs import trace
from fedml_tpu.parallel import mesh as meshlib
from fedml_tpu.sim import cohort as cohortlib

Pytree = Any


@dataclasses.dataclass
class SimConfig:
    """Flag names follow the reference CLI (main_fedavg.py:46-130)."""

    client_num_in_total: int = 10
    client_num_per_round: int = 10
    batch_size: int = 32
    comm_round: int = 10
    epochs: int = 1  # local epochs per round
    frequency_of_the_test: int = 1
    eval_batch_size: int = 256
    seed: int = 0
    shuffle_each_round: bool = True
    # FedProx straggler protocol: this fraction of each cohort runs a reduced
    # uniform 1..E-1 local-epoch budget (masked early exit inside the jitted
    # scan — the heterogeneity FedProx/FedNova were designed for, absent from
    # the reference despite the naming, SURVEY §5.3)
    straggler_frac: float = 0.0
    # Heterogeneous population model (fedml_tpu/population, docs/
    # PERFORMANCE.md "Heterogeneous populations"): a spec string
    # ("speed=lognormal:0,0.5;avail=0.8;dropout=0.05", see
    # population.parse_population_spec) drives cohort ELIGIBILITY
    # (availability on/off blocks feed the sampler), per-client STEP
    # BUDGETS from the speed multipliers (replacing the uniform
    # straggler_frac draw — setting both fails loudly), and MID-ROUND
    # DROPOUT injection (a dropped member trains part of its budget and
    # its update is excluded, weight 0). The packed-lane planner bins by
    # the population's PREDICTED steps and re-packs dropped lanes into
    # overflow passes. None (default) keeps every path bit-identical to
    # the population-free engine (tools/population_smoke.py).
    population: str | None = None
    # Replay a saved population trace (population.save_trace JSONL)
    # instead of drawing from the spec: cohorts, budgets, and dropouts
    # reproduce bit-exactly. Exactly one of population/population_trace.
    population_trace: str | None = None
    # Seed for the population's draws (None = the run seed): separate so
    # the same federated run can be replayed under another realization.
    population_seed: int | None = None
    # Server-side per-client evaluation at test frequency (reference
    # FedAVGAggregator.test_on_server_for_all_clients, FedAVGAggregator.py:110-164)
    eval_on_clients: bool = False
    # Cap the POOLED-TRAIN eval to the first N samples (None = all). For
    # population-scale rows (StackOverflow: 2.4M host-resident sequences)
    # evaluating the full train pool per test round is the reference's own
    # hidden bottleneck (SURVEY §7 "Eval cost ... vectorize it or sample");
    # Train/Acc becomes a fixed-subset estimate, Test metrics are untouched.
    train_eval_samples: int | None = None
    # Keep the training arrays resident on device and gather each round's
    # cohort inside the jitted program — per-round host->device traffic drops
    # from the full batch stack to a [C, S, B] int32 index array. None = auto
    # (on when the dataset fits comfortably in HBM). The host-staging path
    # remains for datasets larger than device memory.
    stage_on_device: bool | None = None
    # Dispatch rounds in eval-aligned blocks (one lax.scan program per block,
    # one host->device round-trip). None = auto: on for accelerator meshes
    # (a round trip a block, not a round), OFF on XLA:CPU — convolutions
    # inside a while loop take XLA:CPU's single-threaded slow path
    # (core/scan.py has the CPU figures).
    block_dispatch: bool | None = None
    # How the cohort's clients execute inside the round program:
    # "vmap" (default) trains every local client simultaneously — small
    # models fill the MXU only across clients, but peak HBM scales with
    # C_local (each live client holds params + optimizer state +
    # activations); "scan" trains them sequentially (a loop over the
    # device's clients), holding ONE client's transient state at a time —
    # the big-model mode: a client's transformer state is GBs and its
    # matmuls fill the MXU without cross-client batching (the LM cells run
    # it, PERF.md section 4). Where the aggregation rule needs the clients'
    # weighted mean alone (Aggregator.aggregate_mean: FedAvg, FedOpt) that
    # loop folds each client's result into a running float32 sum and no
    # [C, ...] stack of models is built; any other rule gets the stack, a
    # client at a time (lax.map).
    cohort_execution: str = "vmap"
    # Packed-lane execution (docs/PERFORMANCE.md "Packed-lane cohort
    # execution"): 0 (default) = the padded [C, S_max] layout above; N > 0 =
    # host staging bin-packs each round's per-client step streams into N
    # fixed-length lanes PER MESH SHARD and the round program scans lanes,
    # resetting its carry at client boundaries — device FLOPs scale with the
    # cohort's executed steps instead of C x the straggler max, the big win
    # on power-law populations where one client holds 10-100x the median.
    # The padded path's arithmetic in another program: bit-identical to it on
    # one device and on uniform cohorts, within float32 rounding (1 ULP seen)
    # on a multi-device client mesh with unequal lanes, where XLA fuses the
    # update differently (tests/test_packed_lanes.py; tools/pack_smoke.py);
    # requires broadcast-mode aggregation and the default cohort_execution.
    pack_lanes: int = 0
    # Lane length head-room over the expected per-shard cohort load. Lanes
    # are sized ONCE (compile-once shapes): s_lane = max(population max
    # client steps, ceil(factor * mean load / lanes)); a round whose draw
    # overflows every lane spills the leftovers to an extra sequential pass
    # of the same compiled program.
    pack_capacity_factor: float = 1.25
    # Update compression (fedml_tpu/compress, docs/COMPRESSION.md): codec
    # spec for client->server updates — "none" keeps the dense bit-identical
    # path with no compression machinery in the program; "topk"/"q8"/"q4"/
    # "bf16" and "+"-chains route every client delta through
    # encode->decode with optional error feedback, and the round metrics
    # gain the Comm/* bytes-on-wire keys (obs/metrics.py).
    compressor: str = "none"
    topk_frac: float = 0.01
    quantize_bits: int = 8
    # Downlink delta coding (fedml_tpu/compress/downlink.py,
    # docs/COMPRESSION.md "Downlink delta coding") is a WIRE-PATH plane:
    # the sim engine broadcasts in-memory views, so there are no downlink
    # bytes to compress and nothing to delta-code — only "none" (the
    # bit-identical no-op) is accepted here; any real codec spec fails
    # loudly at construction instead of silently faking a bytes experiment.
    downlink_compressor: str = "none"
    # Robust aggregation defense (algorithms/robust.py, docs/ROBUSTNESS.md):
    # clip -> combine (mean/median/trimmed_mean/krum) -> seeded weak-DP
    # noise, run inside the round program. Defaults are the no-defense
    # identity (plain FedAvg). Round metrics gain the Robust/* keys when
    # any stage is active. A caller-supplied ``aggregator`` takes
    # precedence; setting both fails loudly at construction.
    robust_rule: str = "mean"
    norm_bound: float = 0.0
    dp_stddev: float = 0.0
    # Sim-mode error feedback keys residuals by cohort slot, which equals
    # client identity only at full participation (rng.sample_clients returns
    # arange there) — enforced at engine construction.
    error_feedback: bool = True
    # Sharded client models (docs/PERFORMANCE.md "Sharded client models"):
    # mesh_shape = (n_client_shards, n_model_shards) builds a 2-D
    # (clients, model) device mesh — cohort parallelism across the client
    # axis, tensor/FSDP parallelism WITHIN one client's model across the
    # model axis. Validated against the available device count
    # (parallel/mesh.shard_mesh). None keeps the 1-D all-clients mesh.
    mesh_shape: tuple | None = None
    # Partition-rule plan for the client model (parallel/rules.py): the
    # name of a built-in rule set ("transformer_tp", "transformer_fsdp",
    # "cnn_tp", "cnn_fsdp", ...) mapping every param (and its optimizer
    # state) to a PartitionSpec over the model axis. When the plan shards
    # anything, the round is lowered via pjit with explicit in/out
    # shardings (parallel/dispatch.py) instead of the client-mapped
    # shard_map program; FSDP-style sets (gather_compute) keep the round
    # bit-identical to the unsharded program on the transformer path
    # (tools/shard_smoke.py guards it; BN batch statistics carry a ~1 ULP
    # cross-program fusion caveat, parallel/rules.py module note).
    # None = unsharded (every client model lives whole on one chip).
    shard_rules: str | None = None
    # Pipelined round driver (sim/prefetch.py, docs/PERFORMANCE.md): a
    # background thread builds and device_puts the NEXT dispatch's staging
    # (index maps / batch stacks) while the current one executes, and round
    # metrics stay on device in a drain queue fetched a round behind —
    # the driver only synchronizes at eval boundaries and at the end.
    # Depth N keeps up to N dispatches staged ahead; 0 = serial (stage,
    # dispatch, fetch every round); None = auto (depth 1, double buffering,
    # on for host-staged and on-device paths alike). Staging is a pure
    # function of (seed, round), so the pipelined driver is bit-identical
    # to the serial one (tools/pipeline_smoke.py guards this).
    pipeline_depth: int | None = None
    # capture an XLA trace of the round loop (SURVEY §5.1: jax.profiler is the
    # TPU equivalent of the reference's wandb/host tracing)
    profile_dir: str | None = None


@dataclasses.dataclass(frozen=True)
class PackedStaged:
    """A packed round's staged payload (SimConfig.pack_lanes > 0): one
    device-resident plan per pass — (data, slot, gidx, boundary), where data
    is the [L, S_lane, B] index map (on-device dataset) or the gathered
    [L, S_lane, B, ...] batch stacks (host staging) — plus the cohort's
    weights/budgets and the round rng key. ``stats`` carries host-side plan
    accounting (n_passes / total_steps / capacity) for observability; it
    never enters the jitted programs."""

    passes: tuple
    weights: Any
    num_steps: Any
    rkey: Any
    stats: dict


class FedSim:
    """Single-program federated simulator.

    Parameters
    ----------
    trainer: ClientTrainer (module + task + local optimizer + epochs)
    train_data: FederatedArrays (client-partitioned train set)
    test_arrays: dict of [N, ...] arrays — pooled global test set
    aggregator: server aggregation rule; defaults to FedAvg weighted mean
    mesh: jax Mesh with a "clients" axis; defaults to all local devices
    local_train_fn: override for the client-side round program — any
        ``(variables, data, rng, num_steps) -> (variables, metrics)``
        (e.g. make_gan_local_train's adversarial loop); defaults to
        make_local_train(trainer). Trainers without ``eval_batch`` (GAN)
        simply skip server-side evaluation.
    """

    def __init__(
        self,
        trainer: ClientTrainer,
        train_data: cohortlib.FederatedArrays,
        test_arrays: dict[str, np.ndarray] | None,
        config: SimConfig,
        aggregator: Aggregator | None = None,
        mesh=None,
        local_train_fn=None,
    ):
        self.trainer = trainer
        self.train_data = train_data
        self.config = config
        if config.cohort_execution not in ("vmap", "scan"):
            raise ValueError(
                f"unknown cohort_execution {config.cohort_execution!r} "
                "(expected 'vmap' or 'scan') — a silent fallback here would "
                "benchmark or OOM the wrong execution mode"
            )
        # Each step settles one thing, sets the attributes it owns and reads
        # what the steps before it have set.
        self._resolve_population()
        self._resolve_mesh(mesh)
        self._resolve_aggregator(aggregator)
        self._resolve_shard_plan()
        trainer = self.trainer  # the plan may have rebound its module
        self._local_train = local_train_fn or make_local_train(trainer)
        self._can_eval = hasattr(trainer, "eval_batch")
        self._local_eval = make_local_eval(trainer) if self._can_eval else None
        # Pin steps-per-epoch to the global max so every round compiles once.
        self._steps = cohortlib.steps_per_epoch(
            train_data.max_client_size(), config.batch_size
        )
        self._resolve_lanes(local_train_fn)
        self._place_dataset()
        self._build_programs()
        self._place_eval_data(test_arrays)

    # -- the constructor's steps ---------------------------------------------

    def _resolve_population(self):
        """Heterogeneous population (fedml_tpu/population, docs/
        PERFORMANCE.md "Heterogeneous populations"): resolve the spec or
        trace into the round-view provider driving cohorts/budgets/dropout
        (``_population``; None without one)."""
        config = self.config
        self._population = None
        self._pop_view_cache: tuple | None = None
        if not (config.population or config.population_trace):
            return
        from fedml_tpu import population as poplib

        if config.population and config.population_trace:
            raise ValueError(
                "SimConfig.population and SimConfig.population_trace "
                "are both set — one of them would silently win; pick "
                "the generative spec OR the trace replay"
            )
        if config.straggler_frac > 0:
            raise ValueError(
                "SimConfig.population replaces the uniform "
                "straggler_frac draw with speed-model step budgets — "
                "configure per-client heterogeneity in exactly one "
                "place (drop straggler_frac)"
            )
        pop_seed = (config.population_seed
                    if config.population_seed is not None
                    else config.seed)
        if config.population_trace:
            self._population = poplib.load_trace(config.population_trace)
            if self._population.num_clients != config.client_num_in_total:
                raise ValueError(
                    f"population trace {config.population_trace} was "
                    f"captured over {self._population.num_clients} "
                    f"clients but client_num_in_total="
                    f"{config.client_num_in_total} — a trace replays "
                    "one population only"
                )
            if self._population.jitter_active:
                # same contract as the generative spec path below: a
                # wire-captured schedule replayed on sim must not
                # silently lose its jitter dimension
                raise NotImplementedError(
                    f"population trace {config.population_trace} "
                    "records upload-arrival jitter — a wire-only "
                    "knob; there is no wire on the sim engine "
                    "(re-capture without jitter, or run the "
                    "message-passing backends)"
                )
        else:
            spec = poplib.parse_population_spec(config.population)
            if spec.jitter_active:
                raise NotImplementedError(
                    "population jitter schedules upload-arrival delays "
                    "— a wire-only knob; there is no wire on the sim "
                    "engine (run the message-passing backends, or drop "
                    "jitter from the spec)"
                )
            self._population = poplib.Population(
                spec, config.client_num_in_total, pop_seed
            )

    def _resolve_mesh(self, mesh):
        """The device mesh, the two shardings staging ships to, and the
        cohort's size on it (``_c_pad``: the cohort axis pads to a multiple
        of the mesh's client axis with zero-weight dummies,
        ``_host_cohort_batches``)."""
        config = self.config
        if config.mesh_shape is not None and mesh is not None:
            raise ValueError(
                "SimConfig.mesh_shape and an explicit mesh= were both "
                "given — one of them would silently win; configure the "
                "mesh in exactly one place"
            )
        if mesh is not None:
            self.mesh = mesh
        elif config.mesh_shape is not None:
            self.mesh = meshlib.shard_mesh(config.mesh_shape)
        elif config.shard_rules:
            # the flagship geometry when no shape is given: one client at
            # a time, the whole mesh given to its model (the model that
            # doesn't fit one chip is WHY the rules are on)
            self.mesh = meshlib.shard_mesh((1, len(jax.devices())))
        else:
            self.mesh = meshlib.client_mesh()
        self._rep = meshlib.replicated(self.mesh)
        self._shard = meshlib.cohort_batch_sharding(self.mesh)
        self._client_shard = meshlib.client_sharded(self.mesh)
        n_dev = self._n_client_shards = self.mesh.shape[meshlib.CLIENT_AXIS]
        self._c_pad = -(-config.client_num_per_round // n_dev) * n_dev
        # multi-controller (jax.distributed) jobs: every process stages the
        # same host arrays but materializes only its addressable shards
        self._multihost = jax.process_count() > 1

    def _resolve_aggregator(self, aggregator):
        """The aggregation rule (``aggregator``) with the robust defence and
        the update codec wrapped around it, and whether it keeps a model per
        client (``_per_client``)."""
        config = self.config
        robust_on = (config.robust_rule != "mean" or config.norm_bound > 0
                     or config.dp_stddev > 0)
        if robust_on and aggregator is not None:
            raise ValueError(
                "SimConfig robust defense flags (robust_rule/norm_bound/"
                "dp_stddev) conflict with an explicit aggregator= — one of "
                "them would silently win; configure the defense in exactly "
                "one place"
            )
        if robust_on:
            from fedml_tpu.algorithms.robust import RobustConfig, robust_aggregator

            aggregator = robust_aggregator(RobustConfig(
                norm_bound=config.norm_bound, stddev=config.dp_stddev,
                rule=config.robust_rule,
            ))
        self.aggregator = aggregator or fedavg_aggregator()
        if (robust_on and config.robust_rule != "mean"
                and self._c_pad != config.client_num_per_round):
            # order-statistic rules run over the padded cohort stack; any
            # padding slots are zero-delta phantoms that bias the statistic
            # toward the current global — name it loudly
            logging.warning(
                "robust rule %r runs over a padded cohort stack: %d real "
                "clients + %d zero-delta padding slots (cohort not "
                "divisible by the %d-way client mesh) — the order "
                "statistic is biased toward the current global; prefer "
                "client_num_per_round divisible by the mesh",
                config.robust_rule, config.client_num_per_round,
                self._c_pad - config.client_num_per_round,
                self._n_client_shards,
            )
        if (config.downlink_compressor
                and config.downlink_compressor != "none"):
            raise ValueError(
                f"downlink_compressor={config.downlink_compressor!r}: "
                "downlink delta coding is a wire-path plane "
                "(compress/downlink.py) — the sim engine broadcasts "
                "in-memory views, so there are no downlink bytes to "
                "compress; run a message-passing backend "
                "(loopback/shm/grpc/mqtt_s3), or 'none' for the "
                "bit-identical sim path"
            )
        if config.compressor and config.compressor != "none":
            from fedml_tpu.compress import make_codec
            from fedml_tpu.compress.aggregate import compressed_aggregator

            if self._population is not None and config.error_feedback:
                raise ValueError(
                    "sim-mode error feedback keys residuals by cohort "
                    "slot; a population's availability churn maps slots "
                    "to different clients every round — use "
                    "error_feedback=False or a message-passing backend"
                )
            if (config.error_feedback
                    and config.client_num_per_round != config.client_num_in_total):
                raise ValueError(
                    "sim-mode error feedback keys residuals by cohort slot, "
                    "which matches client identity only at full participation "
                    f"(got {config.client_num_per_round}/"
                    f"{config.client_num_in_total} per round); use full "
                    "participation, error_feedback=False, or a "
                    "message-passing backend (residuals keyed by assigned "
                    "client index)"
                )
            self.aggregator = compressed_aggregator(
                make_codec(config.compressor, topk_frac=config.topk_frac,
                           quantize_bits=config.quantize_bits),
                inner=self.aggregator,
                error_feedback=config.error_feedback,
                num_slots=self._c_pad,
            )
        # per-client persistent models (decentralized/gossip FL): each client
        # trains from its own round-(r-1) model instead of a broadcast global
        self._per_client = bool(getattr(self.aggregator, "per_client", False))
        if self._per_client and self._population is not None:
            raise ValueError(
                "per-client aggregators (decentralized/gossip) keep slot i "
                "== client i with full participation every round; a "
                "population's availability churn breaks that identity — "
                "run populations with broadcast-mode aggregation"
            )
        if self._per_client and config.client_num_per_round != config.client_num_in_total:
            raise ValueError(
                "per-client aggregators (decentralized/gossip) require full "
                "participation: client_num_per_round == client_num_in_total "
                f"(got {config.client_num_per_round} != {config.client_num_in_total})"
            )
        agg_n = getattr(self.aggregator, "num_clients", None)
        if self._per_client and agg_n is not None and agg_n != config.client_num_in_total:
            raise ValueError(
                f"aggregator '{self.aggregator.name}' is configured for "
                f"{agg_n} clients (e.g. its mixing-matrix order) but "
                f"client_num_in_total={config.client_num_in_total} — a "
                "mismatched topology would silently isolate clients"
            )

    def _resolve_shard_plan(self):
        """Partition-rule model parallelism (docs/PERFORMANCE.md "Sharded
        client models"): resolve the rule set into a PartitionSpec plan over
        the model variables (``_var_specs``, ``_var_shardings``; ``_spmd``
        when it shards anything; ``_shard_gather`` for an FSDP-style plan),
        rebinding the trainer's module with the model axis when the plan
        carries block-boundary activation constraints (TP)."""
        from fedml_tpu.parallel import dispatch as displib

        config = self.config
        self._var_specs = None
        self._shard_gather = False
        self._spmd = False
        if config.shard_rules:
            from fedml_tpu.parallel import rules as ruleslib

            if meshlib.MODEL_AXIS not in self.mesh.axis_names:
                raise ValueError(
                    f"shard_rules={config.shard_rules!r} needs a mesh with "
                    f"a '{meshlib.MODEL_AXIS}' axis — set SimConfig."
                    "mesh_shape=(clients, model) or leave mesh= unset for "
                    "the default 1 x all-devices model mesh"
                )
            if self._per_client:
                raise ValueError(
                    "shard_rules shards ONE broadcast global model over "
                    "the mesh; per-client aggregators (decentralized/"
                    "gossip) keep a model per client and need the "
                    "unsharded path"
                )
            if config.block_dispatch:
                raise ValueError(
                    "block_dispatch scans whole rounds inside one program "
                    "and cannot split the sharded round's train/aggregate "
                    "dispatch boundary; leave block_dispatch off with "
                    "shard_rules"
                )
            # multi-controller (jax.distributed) meshes are supported: the
            # (hosts x clients x model) device grid comes from shard_mesh's
            # global jax.devices() order, pjit programs run global-view, and
            # the jax.process_count()>1 capability check routes model
            # staging through stage_global (each process materializes only
            # its addressable shards of the rule-placed layout)
            ruleset = ruleslib.rule_set(config.shard_rules)
            self._shard_gather = ruleset.gather_compute
            if ruleset.act_spec is not None and hasattr(
                self.trainer.module, "mp_axis"
            ):
                self.trainer = dataclasses.replace(
                    self.trainer,
                    module=self.trainer.module.clone(
                        mp_axis=meshlib.MODEL_AXIS),
                )
            with self.mesh:
                self._var_specs = ruleslib.match_partition_rules(
                    ruleset.rules, self._variables_shape_tree()
                )
            self._spmd = displib.plan_is_sharded(self._var_specs)
            if not self._spmd:
                logging.warning(
                    "shard_rules=%r matched no shardable leaf on this "
                    "model (every rule resolved to the replicate "
                    "default) — the round runs on the client-mapped "
                    "shard_map path and the mesh's %d-way '%s' axis is "
                    "pure replication",
                    config.shard_rules,
                    self.mesh.shape[meshlib.MODEL_AXIS], meshlib.MODEL_AXIS,
                )
            # the spec->NamedSharding tree is static: build it once here
            # instead of on every dispatch (named_sharding validates each
            # leaf's axis names, a per-leaf Python cost)
            self._var_shardings = displib.to_shardings(
                self.mesh, self._var_specs
            )
        elif meshlib.MODEL_AXIS in self.mesh.axis_names:
            # a model axis with no shard plan is pure replication: every
            # model-column device computes the same round redundantly —
            # name it loudly instead of silently delivering 1/(model-axis)
            # of the mesh's throughput
            logging.warning(
                "mesh has a %d-way '%s' axis but no shard_rules — the "
                "model axis devices replicate the same work; set "
                "SimConfig.shard_rules to shard the client model (or drop "
                "mesh_shape)",
                self.mesh.shape[meshlib.MODEL_AXIS], meshlib.MODEL_AXIS,
            )
        # sharded rounds (pjit) take the tiny [C] cohort vectors (weights,
        # budgets) replicated — explicit in_shardings reject a mismatched
        # committed layout; the client-mapped rounds take them over the
        # client axis like the cohort's arrays
        self._vector_sharding = (
            self._rep if self._spmd else self._client_shard)

    def _resolve_lanes(self, local_train_fn):
        """Packed-lane geometry (``SimConfig.pack_lanes``; ``_pack``): what
        packing cannot be combined with, the lane length ``_s_lane`` and the
        lane step the pass program scans."""
        config = self.config
        if config.pack_lanes < 0:
            # -1 is NOT "auto" here (unlike pipeline_depth): a negative lane
            # count silently running the padded path would mislabel benchmarks
            raise ValueError(
                f"pack_lanes must be >= 0 (got {config.pack_lanes}); "
                "0 disables packing"
            )
        self._pack = config.pack_lanes > 0
        if not self._pack:
            return
        # One error per conflict, each leading with the SimConfig field
        # (or constructor argument) that has to change — a config with
        # several conflicts reports the first, fixes it, and gets the
        # next precise message instead of one undifferentiated blob.
        if self._per_client:
            raise ValueError(
                f"aggregator={self.aggregator.name!r} (per-client) "
                f"conflicts with pack_lanes={config.pack_lanes}: packed "
                "lanes reset carries to the BROADCAST global params at "
                "client boundaries, but per-client aggregators (decentralized/"
                "gossip) keep a model per client — use the padded path "
                "(pack_lanes=0)"
            )
        if config.cohort_execution == "scan":
            raise ValueError(
                "SimConfig.cohort_execution='scan' conflicts with "
                f"pack_lanes={config.pack_lanes}: packed lanes replace "
                "the cohort execution loop entirely — leave "
                "cohort_execution='vmap' (lanes are vmapped)"
            )
        if local_train_fn is not None:
            raise ValueError(
                "local_train_fn conflicts with pack_lanes="
                f"{config.pack_lanes}: packed lanes drive "
                "ClientTrainer.train_step directly (boundary-aware lane "
                "steps) and cannot honor a custom round program (e.g. "
                "the GAN adversarial loop) — use the padded path "
                "(pack_lanes=0)"
            )
        if config.block_dispatch:
            raise ValueError(
                "SimConfig.block_dispatch=True conflicts with "
                f"pack_lanes={config.pack_lanes}: packed rounds already "
                "dispatch one program per pass — leave block_dispatch "
                "off (or unset) with pack_lanes"
            )
        self._lane_step = make_lane_step(self.trainer)
        # Fixed lane length (compile-once): fit the population's largest
        # per-client step budget, with capacity-factor head room over the
        # expected per-shard cohort load; overflow draws spill to extra
        # sequential passes of the same compiled program.
        sizes = self.train_data.client_sizes()
        slots = self._steps * config.batch_size
        d = np.ceil(
            np.minimum(sizes, slots) / max(config.batch_size, 1)
        ).astype(np.int64)
        t = self.trainer.epochs * d
        t_max = int(t.max()) if len(t) else 1
        mean_t = float(t.mean()) if len(t) else 1.0
        c_local = self._c_pad // self._n_client_shards
        need = (
            config.pack_capacity_factor * mean_t * c_local
            / config.pack_lanes
        )
        self._s_lane = max(t_max, int(np.ceil(need)), 1)

    def _place_dataset(self):
        """Device-resident dataset + in-program cohort gather: the TPU-first
        answer to the reference's per-batch .to(device) traffic — ship the
        arrays once, then each round uploads only a [C, S, B] index map.
        Host or device data is settled here, once (``_on_device``): the
        dispatch sites hand every program ``_data_args`` before what is
        staged, the resident dataset or nothing."""
        config = self.config
        nbytes = sum(a.nbytes for a in self.train_data.arrays.values())
        self._on_device = (
            config.stage_on_device
            if config.stage_on_device is not None
            else nbytes <= 2 << 30
        )
        self._block_dispatch = (
            config.block_dispatch
            if config.block_dispatch is not None
            else (self._on_device
                  and next(iter(self.mesh.devices.flat)).platform != "cpu")
        ) and self._on_device and not self._pack and not self._spmd
        self._dataset = None
        if self._on_device:
            self._dataset = self._put(
                {k: np.asarray(v) for k, v in self.train_data.arrays.items()},
                self._rep,
            )
        self._data_args = (self._dataset,) if self._on_device else ()

    def _build_programs(self):
        """The device programs this plan dispatches, as rows of one table:
        the attribute a program is reached by, its traced function, its in
        and out specs in the words defined first, and what it donates.
        A program the plan does not dispatch is None."""
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel import dispatch as displib

        sharded, gathered = self._spmd, self._on_device
        # clients in sequence under a rule that is a function of their
        # weighted mean: the round program (not the sharded plan's two, nor
        # the packed lanes') sums the mean in the cohort loop's carry and
        # never builds the stack of their models (_cohort_mean)
        self._mean_in_carry = (
            self.config.cohort_execution == "scan" and not self._per_client
            and not sharded
            and getattr(self.aggregator, "aggregate_mean", None) is not None
        )
        self._spare = None
        # Every compiled round program is lowered through the compile
        # dispatcher (parallel/dispatch.py): pjit with explicit in/out
        # shardings when the plan shards the model, the manual shard_map
        # lowering otherwise — each device then runs an ordinary vmap over
        # its local cohort slice and the client stacks are all-gathered for
        # the aggregator. (Leaving the client axis to GSPMD on conv models
        # hits an XLA limitation: vmap expresses per-client conv kernel
        # gradients as feature-grouped convolutions, which the SPMD
        # partitioner cannot split along the group axis.) Other mesh axes
        # (e.g. ``silo`` intra-client DP) stay automatic.
        #
        # ``rep``: one value everywhere (server state, keys, the resident
        # dataset). ``cohort``: a cohort's [C, ...] array over the client
        # axis (index maps, batch stacks, a lane plan: lanes ride the clients
        # axis, binned per client shard, so gather maps never touch the
        # model axes). ``vector``: a cohort's [C] weights, budgets or losses,
        # as staging ships them (_vector_sharding).
        rep, cohort = P(), P(meshlib.CLIENT_AXIS)
        vector = rep if sharded else cohort
        # ``model``: the model at rest, each leaf at its rule's spec under a
        # shard plan. per-client mode: the model state is itself a stacked
        # [C, ...] pytree sharded over the clients axis, in and out of the
        # round program
        model = (self._var_specs if sharded
                 else cohort if self._per_client else rep)
        # ``stack``: the clients' [C, ...] models where they cross from one
        # program to the next; ``lane_buf``: the round buffers beside it
        # (written mask + loss/weight scatter buffers). Client-mapped
        # programs keep both over the client axis. Under a shard plan the
        # boundary layout follows the plan's contract: gather_compute
        # (FSDP-style) plans use a REPLICATED boundary — all cross-shard
        # movement is concat/slice, never a reassociated reduction, which is
        # what keeps them bit-identical to the shard_map path
        # (tools/shard_smoke.py) at the cost of a full [C, model] stack per
        # device there (gather plans replicate params for compute anyway, so
        # the boundary is not their binding memory constraint). TP plans
        # instead keep the stack SHARDED (clients x each leaf's own
        # model-axis spec) through the boundary — O(local shard) per chip
        # end to end, the too-big-for-one-chip contract — accepting the ~1
        # ULP cross-shard reduce association TP already carries. The round
        # buffers follow the STACK's boundary layout, not the lane layout:
        # under gather plans they must arrive replicated at the aggregate
        # program, or GSPMD shards the rebuilt per-client stack over clients
        # and PARTITIONS the aggregator's reduce — a cross-shard partial-sum
        # reassociation that breaks the gather plan's bit-identity contract
        # (measured: 1 ULP). TP plans keep them lane-sharded (their reduce
        # is partitioned anyway — the documented ~1 ULP TP caveat).
        stack = lane_buf = rep if sharded and self._shard_gather else cohort
        if sharded and not self._shard_gather:
            stack = jax.tree_util.tree_map(
                lambda s: P(meshlib.CLIENT_AXIS, *s), self._var_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        bufs = (stack, lane_buf, lane_buf, lane_buf)
        # ``dataset``: the resident dataset in front of what is staged. The
        # host-staged and the device-gathered form of a program are one row,
        # without it or with it, under the form's names and dispatch label:
        dataset = (rep,) if gathered else ()
        (round_fn, round_impl, self._round_label), train, lane_pass = (
            (("_gather_round_fn", self._gather_round_impl, "gather"),
             ("_spmd_gather_train_fn", self._spmd_gather_train_impl),
             self._packed_gather_pass_impl) if gathered else
            (("_round_fn", self._round_impl, "padded"),
             ("_spmd_train_fn", self._spmd_train_impl),
             self._packed_host_pass_impl))
        at = len(dataset)  # what follows the dataset sits one place later

        def donated(*argnums):
            # shard_map programs donate on every backend; the pjit programs
            # only where the backend implements it (XLA:CPU does not)
            on_cpu = sharded and jax.default_backend() == "cpu"
            return () if on_cpu else argnums

        if self._pack:
            # Packed-lane programs (docs/PERFORMANCE.md): a zero-buffer init,
            # a lane-scan pass (one per plan pass; the common draw needs one),
            # and the aggregation program consuming the SAME [C_pad, ...]
            # update stack the padded round builds; on a sharded plan
            # ("Packed lanes on sharded plans") the same three in GLOBAL
            # view, GSPMD partitioning the model per the rule plan inside
            # every lane step. The chained round buffers are exclusively
            # owned (built by the buf program, consumed once per pass, then
            # by the aggregation) — donate them so passes update the stack
            # in place instead of holding two [C_pad, model] copies live.
            rows = [
                ("_packed_buf_fn", self._packed_buf_impl, (model,), bufs, ()),
                ("_packed_pass_fn", lane_pass,
                 (model,) + dataset + (cohort,) * 4 + bufs + (rep,), bufs,
                 donated(*range(5 + at, 9 + at))),
                ("_packed_agg_fn", self._packed_agg_impl,
                 (model, rep) + bufs + (vector, vector, rep),
                 (model, rep, rep), donated(2, 3, 4, 5)),
            ]
        elif sharded:
            # Two-program sharded round: a pjit TRAIN program emits the
            # cohort's update stack at a program boundary, then a pjit
            # AGGREGATE program reduces it, donating the old global (in/out
            # specs match, and the train dispatch is ordered before the
            # aggregate on the device stream, so aliasing is safe) plus the
            # exclusively-owned stack/loss buffers — without it the
            # big-model path holds two full model copies live across the
            # aggregate
            rows = [
                (*train, (model,) + dataset + (cohort, vector, rep),
                 (stack, vector), ()),
                ("_spmd_agg_fn", self._spmd_agg_impl,
                 (model, rep, stack, vector, vector, vector, rep),
                 (model, rep, rep), donated(0, 2, 3)),
            ]
        else:
            # The round donates its model argument, but a round that sums
            # the mean in its cohort loop's carry cannot write the sum over
            # its model: clients start from the model until the last one
            # has. Donated, XLA would copy the finished sum back into it (a
            # model read and written a round, under no scope); not donated,
            # the runtime would hold a third model, the output of the round
            # it enqueues while this one runs. So such a round takes one
            # argument more, ``spare``, last: a model's worth of dead
            # buffers (the model of the round before, kept by _call_round)
            # that it donates and sums into, and two models pass each other
            # from round to round.
            spare = (model,) if self._mean_in_carry else ()
            rows = [(round_fn, round_impl,
                     (model, rep) + dataset + (cohort, vector, vector, rep)
                     + spare,
                     (model, rep, rep), (6 + at,) if spare else (0,))]
            if gathered:  # R rounds in one program, on [R, C, ...] arrays
                rounds = P(None, meshlib.CLIENT_AXIS)
                rows.append(("_block_fn", self._block_impl,
                             (model, rep, rep, rounds, rounds, rounds, rep),
                             (model, rep, rep), (0,)))
        self._round_fn = self._gather_round_fn = self._block_fn = None
        self._spmd_train_fn = self._spmd_gather_train_fn = None
        self._spmd_agg_fn = self._packed_buf_fn = None
        self._packed_pass_fn = self._packed_agg_fn = None
        for name, impl, in_specs, out_specs, donate in rows:
            setattr(self, name, displib.lower(
                impl, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                donate_argnums=donate,
            ))
        # one program a role for the dispatch sites, whichever form it has
        self._round_program = getattr(self, round_fn)
        self._train_program = getattr(self, train[0])

        # eval programs: plain jit normally; under a shard plan they trace
        # under the mesh context (module-side constraints) and consume the
        # model in whatever layout the round program left it
        jit_ = (
            (lambda f: displib.jit_sharded(f, self.mesh))
            if sharded else jax.jit
        )
        self._eval_fn = self._client_eval_fn = None
        self._eval_gather_fn = self._client_eval_gather_fn = None
        if self._can_eval:
            self._eval_fn = jit_(self._eval_impl)
            self._client_eval_fn = jit_(
                lambda v, d: jax.vmap(self._local_eval, in_axes=(None, 0))(
                    self._compute_view(v), d))
        if self._can_eval and gathered:
            self._eval_gather_fn = jit_(self._eval_gather_impl)
            # per-client analogue: gather each chunk's batches from the
            # resident dataset, then the same vmapped local eval
            self._client_eval_gather_fn = jit_(
                lambda variables, dataset, idx: jax.vmap(
                    self._local_eval, in_axes=(None, 0)
                )(self._compute_view(variables),
                  self._gather_batches(dataset, idx))
            )

    def _place_eval_data(self, test_arrays):
        """The pooled test batches and what the pooled train eval runs on
        (``_train_eval``: its program, then that program's arguments after
        the model)."""
        config = self.config
        self._test_batches = None
        self._train_eval_idx = None
        self._train_eval = None
        if not self._can_eval:
            return
        if test_arrays is not None:
            b = cohortlib.batch_array(test_arrays, config.eval_batch_size)
            self._test_batches = (
                self._put(b, self._rep) if self._on_device else b
            )
        # Pooled train eval: on-device mode gathers eval batches from the
        # already-resident dataset (an index map, not a second copy of the
        # training arrays in HBM); host mode keeps materialized batches.
        n_eval = self.train_data.num_samples
        if config.train_eval_samples is not None:
            n_eval = min(n_eval, config.train_eval_samples)
        if self._on_device:
            bs = config.eval_batch_size
            steps = cohortlib.steps_per_epoch(n_eval, bs)
            eidx = np.full(steps * bs, -1, np.int32)
            eidx[:n_eval] = np.arange(n_eval, dtype=np.int32)
            self._train_eval_idx = self._put(
                eidx.reshape(steps, bs), self._rep
            )
            self._train_eval = (
                self._eval_gather_fn, self._dataset, self._train_eval_idx)
        else:
            self._train_eval = (self._eval_fn, cohortlib.batch_array(
                {k: v[:n_eval] for k, v in self.train_data.arrays.items()},
                config.eval_batch_size,
            ))

    @property
    def pipeline_depth(self) -> int:
        """Effective prefetch/drain depth (0 = serial driver); see
        SimConfig.pipeline_depth."""
        d = self.config.pipeline_depth
        return 1 if d is None else max(0, int(d))

    def _put(self, value, sharding):
        """device_put (to one sharding, or to a tree of them, a leaf each)
        that also works when ``self.mesh`` spans processes
        (multi-controller): each process supplies only the shards it owns
        (parallel/multihost.py staging discipline)."""
        if not self._multihost:
            return jax.device_put(value, sharding)
        from fedml_tpu.parallel.multihost import stage_global

        if isinstance(sharding, jax.sharding.Sharding):
            sharding = jax.tree.map(lambda _: sharding, value)
        return jax.tree.map(
            lambda leaf, sh: stage_global(np.asarray(leaf), sh),
            value, sharding,
        )

    # -- jitted programs -----------------------------------------------------

    def _round_impl(self, global_variables, server_state, batches, weights,
                    num_steps, rng, spare=None):
        # Runs per client-shard: ``batches``/``weights``/``num_steps`` carry
        # this device's local cohort slice [C_local, ...]. Per-client rng keys
        # are derived from the *global* client slot so results are
        # mesh-shape-invariant.
        from fedml_tpu.parallel.mesh import CLIENT_AXIS

        c_local = weights.shape[0]
        shard_idx = jax.lax.axis_index(CLIENT_AXIS)
        slot_ids = shard_idx * c_local + jnp.arange(c_local)
        keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(slot_ids)
        # per-client mode: each client starts from its own model (stacked
        # leading axis); broadcast mode: everyone starts from the global
        var_axis = 0 if self._per_client else None
        local_vars = mean_sum = None
        if self._mean_in_carry:
            mean_sum, train_metrics = self._cohort_mean(
                global_variables, batches, weights, num_steps, keys, spare)
        else:
            with self._cohort_loop(global_variables, c_local):
                if self.config.cohort_execution == "scan":
                    # sequential clients: one client's optimizer state +
                    # activations live at a time; the rule needs every
                    # client's model, so the results stack to [C, ...]
                    if self._per_client:
                        local_vars, train_metrics = jax.lax.map(
                            lambda args: self._local_train(*args),
                            (global_variables, batches, keys, num_steps),
                        )
                    else:
                        local_vars, train_metrics = jax.lax.map(
                            lambda args: self._local_train(global_variables, *args),
                            (batches, keys, num_steps),
                        )
                else:
                    local_vars, train_metrics = jax.vmap(
                        self._local_train, in_axes=(var_axis, 0, 0, 0)
                    )(global_variables, batches, keys, num_steps)
        return self._aggregate_tail(
            global_variables, server_state, local_vars, weights, num_steps,
            train_metrics["train_loss"], rng,
            model_stats={k: v for k, v in train_metrics.items()
                         if k.startswith(STATS_PREFIX)},
            mean_sum=mean_sum,
        )

    def _cohort_mean(self, global_variables, batches, weights, num_steps, keys,
                     spare=None):
        """The device's clients in sequence, each one's trained model folded
        into a running sum as it finishes: ``tree_weighted_mean``'s
        arithmetic (weights normalised in float32 over the whole cohort, a
        float32 sum, one cast at the end, in ``_aggregate_tail``) with the
        sum in the loop's carry, so there is no ``[C, ...]`` stack to move a
        result into and none to pass over afterwards. The sum starts in
        ``spare``'s buffers (a dead model's, donated: ``__init__``), whatever
        they hold: the first trip adds to zero and not to what it finds. A
        block of rounds has no spare and starts from zeros. Returns this
        shard's partial sum (float32 leaves) and the clients' ``[C_local]``
        metrics."""
        from fedml_tpu.parallel.mesh import CLIENT_AXIS

        c_local = weights.shape[0]
        w = weights.astype(jnp.float32)
        w = w / jnp.maximum(jax.lax.psum(jnp.sum(w), CLIENT_AXIS), 1e-12)

        def one_client(acc, client):
            first, w_i, *args = client
            vars_i, metrics_i = self._local_train(global_variables, *args)
            with jax.named_scope(trace.SCOPE_AGGREGATE):
                acc = jax.tree.map(
                    lambda a, v: jnp.where(first, 0.0, a) + v.astype(jnp.float32) * w_i,
                    acc, vars_i)
            return acc, metrics_i

        if spare is None:
            spare = jax.tree.map(jnp.zeros_like, global_variables)
        acc = jax.tree.map(lambda x: x.astype(jnp.float32), spare)
        with self._cohort_loop(global_variables, c_local, acc=acc):
            return jax.lax.scan(
                one_client, acc,
                (jnp.arange(c_local) == 0, w, batches, keys, num_steps))

    def _cohort_loop(self, global_variables, clients_a_device: int, acc=None):
        """The name of the cohort's execution, around the ``lax.scan``, the
        ``lax.map`` or the ``vmap`` that makes it (under ``vmap`` there is no
        loop, but the broadcast of the global variables to a client axis and
        the stacking of the clients' results are the same work under another
        lowering), and its two notes. ``loop/carry``: what a trip carries,
        the running sum ``acc`` where the mean is folded in the loop
        (``_cohort_mean``), else what a client starts from, and how many
        clients a device trains side by side. ``cohort/aggregate``: in which
        ``form`` the clients' models reach the rule, summed in the ``carry``
        or as a ``stack``, the device's ``clients``, and the ``bytes`` of
        their stack, built or not."""
        one_client = global_variables
        if self._per_client:  # a stacked leading axis: one client's slice
            one_client = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), global_variables)
        trace.program_note(
            trace.COHORT_AGGREGATE_NOTE, form="stack" if acc is None else "carry",
            clients=clients_a_device,
            bytes=clients_a_device * sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves(one_client)))
        vmapped = self.config.cohort_execution != "scan"
        return trace.loop(trace.SCOPE_LOOP_COHORT, one_client if acc is None else acc,
                          side_by_side=clients_a_device if vmapped else 1)

    @jax.named_scope(trace.SCOPE_AGGREGATE)
    def _aggregate_tail(self, global_variables, server_state, local_vars,
                        weights, num_steps, train_loss, rng, model_stats=None,
                        mean_sum=None):
        # The round's server side, shared verbatim by the padded, packed,
        # and sharded execution modes: all_gather the cohort stack, derive
        # tau, run the aggregation rule, and assemble round metrics. Where
        # the cohort loop summed the clients' weighted mean in its carry
        # (_cohort_mean) there is no stack: ``local_vars`` is None,
        # ``mean_sum`` is this shard's float32 partial sum, one model of it
        # crosses the client axis, and the rule is handed the mean. Runs
        # per client-shard inside shard_map — except under a shard plan
        # (self._spmd), where it is its own global-view pjit program whose
        # inputs already arrive as full replicated stacks, so the gather is
        # the identity and the reduce association matches the manual path's
        # gathered full-stack reduce exactly.
        from fedml_tpu.parallel.mesh import CLIENT_AXIS

        c_local = weights.shape[0]
        if self._spmd:
            shard_idx = 0
            gather = lambda x: x  # noqa: E731 — inputs are the full stacks
        else:
            shard_idx = jax.lax.axis_index(CLIENT_AXIS)
            # Full cohort stack for the aggregator (robust rules need every
            # client's model: median/krum/clipping are cross-client).
            gather = partial(
                jax.lax.all_gather, axis_name=CLIENT_AXIS, axis=0, tiled=True
            )
        stacked = jax.tree.map(gather, local_vars)  # None where there is none
        all_weights = gather(weights)
        all_losses = gather(train_loss)
        # true per-client SGD steps τ_i = e_i · ceil(n_i / B) — heterogeneous
        # local work for normalized-averaging rules (FedNova τ_eff). The
        # static max_tau keeps the normalizer recursion's loop bound
        # consistent with these τ values regardless of aggregator config.
        epochs_i = gather(num_steps).astype(jnp.float32) / float(self._steps)
        tau = epochs_i * jnp.ceil(
            jnp.maximum(all_weights, 1.0) / self.config.batch_size
        )
        extras = {"tau": tau, "max_tau": self.trainer.epochs * self._steps}
        if mean_sum is not None:
            if self._n_client_shards > 1:
                mean_sum = jax.lax.psum(mean_sum, CLIENT_AXIS)
            mean = jax.tree.map(
                lambda a, x: a.astype(x.dtype), mean_sum, global_variables)
            new_global, server_state, agg_metrics = self.aggregator.aggregate_mean(
                global_variables, mean, all_weights, server_state, rng, extras
            )
        elif self._per_client:
            # shard info lets the rule compute only its block of output rows
            extras["shard_start"] = shard_idx * c_local
            extras["shard_size"] = c_local
            prev = (
                jax.tree.map(gather, global_variables)
                if getattr(self.aggregator, "needs_prev_stack", False)
                else global_variables  # this shard's slice, un-gathered
            )
            new_stacked, server_state, agg_metrics = self.aggregator.aggregate(
                prev, stacked, all_weights, server_state, rng, extras
            )
            # rules may return the local block directly or the full stack
            out_c = jax.tree.leaves(new_stacked)[0].shape[0]
            if out_c == c_local:
                new_global = new_stacked
            else:
                new_global = jax.tree.map(
                    lambda l: jax.lax.dynamic_slice_in_dim(
                        l, shard_idx * c_local, c_local, 0
                    ),
                    new_stacked,
                )
        else:
            new_global, server_state, agg_metrics = self.aggregator.aggregate(
                global_variables, stacked, all_weights, server_state, rng, extras
            )
        metrics = {
            "Train/Loss": jnp.sum(
                all_losses * all_weights / jnp.sum(all_weights)
            ),
            **agg_metrics,
        }
        # the client model's own statistics (core/trainer.py STATS_PREFIX;
        # a routed-expert model's per-layer counts): the cohort's
        # sample-weighted mean, one scalar a layer, on the metrics the round
        # returns anyway, so they cost no host sync of their own
        for name, per_client in (model_stats or {}).items():
            per_layer = jnp.tensordot(all_weights / jnp.sum(all_weights), gather(per_client), 1)
            for i in range(per_layer.shape[0]):
                metrics[f"{name}/layer_{i}"] = per_layer[i]
        return new_global, server_state, metrics

    @staticmethod
    @jax.named_scope(trace.SCOPE_GATHER)
    def _gather_batches(dataset, idx):
        """Gather [*, S, B] index maps (-1 = empty slot) into batch stacks
        with stack_cohort's exact zero-fill/mask semantics — the one
        definition used by the round, pooled-eval, and per-client-eval
        gather programs."""
        valid = (idx >= 0).astype(jnp.float32)
        safe = jnp.maximum(idx, 0).reshape(-1)
        batches = {
            k: jnp.take(v, safe, axis=0).reshape(idx.shape + v.shape[1:])
            for k, v in dataset.items()
        }
        batches = {
            k: v * valid.reshape(
                valid.shape + (1,) * (v.ndim - idx.ndim)
            ).astype(v.dtype)
            for k, v in batches.items()
        }
        if "mask" in dataset:
            batches["mask"] = batches["mask"].astype(jnp.float32)
        else:
            batches["mask"] = valid
        return batches

    def _gather_round_impl(self, global_variables, server_state, dataset, idx,
                           weights, num_steps, rng, spare=None):
        # Build this shard's batch stack on device: ``idx`` [C_local, S, B]
        # indexes dataset rows, -1 marks an empty padding slot.
        batches = self._gather_batches(dataset, idx)
        return self._round_impl(
            global_variables, server_state, batches, weights, num_steps, rng,
            spare,
        )

    # -- sharded client models (SimConfig.shard_rules) -----------------------

    def _compute_view(self, variables):
        """The model layout the training/eval math runs in: under an
        FSDP-style gather_compute plan the sharded-at-rest model is pinned
        replicated (one all-gather per leaf — concat, bit-exact), so every
        arithmetic op sees the tensors the unsharded program sees; TP plans
        and unsharded runs pass through untouched."""
        if self._spmd and self._shard_gather:
            from fedml_tpu.parallel import dispatch as displib

            return displib.replicate(variables, self.mesh)
        return variables

    def _spmd_train_impl(self, global_variables, batches, num_steps, rng):
        # Global-view client training (the pjit half of the sharded round):
        # one vmap over the WHOLE cohort — slot ids are literal (no
        # axis_index), rng chains identical to the manual program's
        # global-slot fold_ins — with GSPMD partitioning the client axis
        # per the in_shardings and the model axes per the rule plan. The
        # update stack exits at the plan's boundary layout (replicated for
        # gather_compute exactness, sharded for TP memory) — see the
        # program-construction comment in __init__.
        global_variables = self._compute_view(global_variables)
        C = num_steps.shape[0]
        keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(C))
        with self._cohort_loop(global_variables, C // self._n_client_shards):
            if self.config.cohort_execution == "scan":
                local_vars, train_metrics = jax.lax.map(
                    lambda args: self._local_train(global_variables, *args),
                    (batches, keys, num_steps),
                )
            else:
                local_vars, train_metrics = jax.vmap(
                    self._local_train, in_axes=(None, 0, 0, 0),
                    spmd_axis_name=meshlib.CLIENT_AXIS,
                )(global_variables, batches, keys, num_steps)
        return local_vars, train_metrics["train_loss"]

    def _spmd_gather_train_impl(self, global_variables, dataset, idx,
                                num_steps, rng):
        # on-device-dataset variant: gather the cohort's batches in HBM
        # through the one canonical batch-gather definition, then train
        return self._spmd_train_impl(
            global_variables, self._gather_batches(dataset, idx), num_steps,
            rng,
        )

    def _spmd_agg_impl(self, global_variables, server_state, local_vars,
                       train_loss, weights, num_steps, rng):
        # The aggregation half of the sharded round. Under gather_compute
        # plans the stack arrives fully replicated (in_shardings P()), so
        # the shared aggregate tail reduces it with the manual path's
        # exact association and the new global re-shards at the
        # out_shardings (a slice per shard — exact). Under TP plans the
        # stack stays sharded through the boundary (O(local shard) per
        # chip) and GSPMD partitions the reduce — the ~1 ULP association
        # caveat TP already carries.
        global_variables = self._compute_view(global_variables)
        return self._aggregate_tail(
            global_variables, server_state, local_vars, weights, num_steps,
            train_loss, rng,
        )

    # -- packed-lane execution (SimConfig.pack_lanes) ------------------------

    def _packed_buf_impl(self, variables):
        # Per-shard zero output buffers for one packed round: the update
        # stack [c_local, ...], its written mask, and the per-(client, chain
        # step) loss/weight scatter buffers the metrics are rebuilt from.
        # Under a shard plan the program is global-view pjit, so the buffers
        # span the whole cohort and GSPMD lays them out per the out specs.
        c_local = (
            self._c_pad if self._spmd
            else self._c_pad // self._n_client_shards
        )
        T = self.trainer.epochs * self._steps
        stack = jax.tree.map(
            lambda l: jnp.zeros((c_local,) + l.shape, l.dtype), variables
        )
        written = jnp.zeros((c_local,), jnp.float32)
        lbuf = jnp.zeros((c_local, T), jnp.float32)
        wbuf = jnp.zeros((c_local, T), jnp.float32)
        return stack, written, lbuf, wbuf

    @jax.named_scope(trace.SCOPE_PACK_PASS)
    def _packed_pass_body(self, variables, get_batch, data, slot, gidx,
                          boundary, stack, written, lbuf, wbuf, rng):
        # One lane-scan pass over this shard's [L_local, S_lane] plan. Each
        # lane carries ONE client's training state at a time; `gidx` indexes
        # the client's padded-scan step chain so rng keys and loss positions
        # land exactly where the padded program would put them, and
        # `boundary` steps emit the finished client into the update stack.
        from fedml_tpu.parallel.mesh import CLIENT_AXIS

        T = self.trainer.epochs * self._steps
        c_local = written.shape[0]
        l_local = slot.shape[0]
        if self._spmd:
            # global-view pjit: every slot is visible, so the slot ids ARE
            # the global ids — identical rng chains to the manual program's
            # axis_index-derived fold_ins. The model arrives in the plan's
            # at-rest layout; pin it to the compute view (replicated under
            # gather plans — bit-exact concat — identity under TP).
            variables = self._compute_view(variables)
            base = 0
        else:
            shard_idx = jax.lax.axis_index(CLIENT_AXIS)
            base = shard_idx * c_local
        slot_ids = base + jnp.arange(c_local)
        # The EXACT per-client rng chains the padded scan walks: fold_in by
        # global slot, then one split per epochs-x-steps scan step. Skipped
        # padding steps still advance the chain (a threefry hash each, not a
        # train step), so executed steps read identical step keys.
        keys0 = jax.vmap(lambda i: jax.random.fold_in(rng, i))(slot_ids)

        def chain(k):
            def body(kk, _):
                kk, s = jax.random.split(kk)
                return kk, s

            return jax.lax.scan(body, k, None, length=T)[1]

        keys_full = jax.vmap(chain)(keys0)  # [c_local, T] step keys
        opt0 = self.trainer.optimizer.init(variables["params"])
        # under a shard plan the lane axis IS the mesh's client axis (lanes
        # are binned per client shard), so name it for GSPMD like the padded
        # sharded round's cohort vmap
        vstep = jax.vmap(
            self._lane_step, in_axes=(0, 0, None, None, 0, 0, 0),
            **({"spmd_axis_name": CLIENT_AXIS} if self._spmd else {}),
        )
        broadcast = lambda tree: jax.tree.map(  # noqa: E731
            lambda l: jnp.broadcast_to(
                jnp.asarray(l)[None], (l_local,) + jnp.shape(l)
            ),
            tree,
        )

        def step(carry, xs):
            lane_vars, lane_opt, stack, written, lbuf, wbuf = carry
            slot_t, gidx_t, bound_t, data_t = xs
            batch_t = get_batch(data_t)
            # per-shard packing guarantees this shard's lanes only carry its
            # own slot block; the range check is defensive (bad plans drop
            # instead of corrupting a neighbor's slot)
            ok = (slot_t >= base) & (slot_t < base + c_local)
            lslot = jnp.clip(slot_t - base, 0, c_local - 1)
            is_first = ok & (gidx_t == 0)
            g = jnp.clip(gidx_t, 0, T - 1)
            keys_t = keys_full[lslot, g]
            lane_vars, lane_opt, loss, w = vstep(
                lane_vars, lane_opt, variables, opt0, batch_t, keys_t,
                is_first,
            )
            wr = jnp.where(ok, lslot, c_local)  # c_local is OOB -> dropped
            lbuf = lbuf.at[wr, g].set(loss, mode="drop")
            wbuf = wbuf.at[wr, g].set(w, mode="drop")
            em = jnp.where(ok & (bound_t > 0), lslot, c_local)
            stack = jax.tree.map(
                lambda st, lv: st.at[em].set(lv, mode="drop"), stack,
                lane_vars,
            )
            written = written.at[em].set(1.0, mode="drop")
            return (lane_vars, lane_opt, stack, written, lbuf, wbuf), None

        xs = (
            jnp.swapaxes(slot, 0, 1),
            jnp.swapaxes(gidx, 0, 1),
            jnp.swapaxes(boundary, 0, 1),
            jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), data),
        )
        carry = (broadcast(variables), broadcast(opt0), stack, written,
                 lbuf, wbuf)
        (_, _, stack, written, lbuf, wbuf), _ = scanlib.scan(step, carry, xs)
        return stack, written, lbuf, wbuf

    def _packed_host_pass_impl(self, variables, batches, slot, gidx, boundary,
                               stack, written, lbuf, wbuf, rng):
        # host-staged variant: `batches` leaves are [L_local, S_lane, B, ...]
        return self._packed_pass_body(
            variables, lambda b: b, batches, slot, gidx, boundary, stack,
            written, lbuf, wbuf, rng,
        )

    def _packed_gather_pass_impl(self, variables, dataset, idx, slot, gidx,
                                 boundary, stack, written, lbuf, wbuf, rng):
        # on-device-dataset variant: `idx` is [L_local, S_lane, B], gathered
        # per step with the one canonical batch-gather definition
        return self._packed_pass_body(
            variables, lambda i: self._gather_batches(dataset, i), idx, slot,
            gidx, boundary, stack, written, lbuf, wbuf, rng,
        )

    def _packed_agg_impl(self, variables, server_state, stack, written, lbuf,
                         wbuf, weights, num_steps, rng):
        # Rebuild exactly the padded round's per-client quantities from the
        # pass buffers, then run the shared aggregation tail. Unwritten slots
        # (zero-weight cohort padding) select the global variables — the same
        # bits the padded path's fully-masked scan leaves there.
        variables = self._compute_view(variables)
        E, S = self.trainer.epochs, self._steps
        c_local = weights.shape[0]
        local_vars = jax.tree.map(
            lambda st, g: jnp.where(
                written.reshape((c_local,) + (1,) * g.ndim) > 0, st, g[None]
            ),
            stack, variables,
        )
        # The padded program's per-epoch loss sum is `jnp.sum(losses * ws)`
        # (under a shard plan, `variables` was pinned to the compute view
        # above, so the unwritten-slot fallback bits match the padded
        # sharded program's masked-scan leftovers exactly)
        # over the step scan's ys — and its SUMMATION ORDER depends on how
        # that scan lowered: straight-lined (scanlib's CPU mode) the stack
        # of per-step scalars fuses into a left-to-right add chain; rolled,
        # it is an XLA Reduce. The two differ by ULPs (measured), so
        # reproduce whichever form the padded local_train compiled to,
        # using scanlib's own unroll predicate.
        prods = (lbuf * wbuf).reshape(c_local, E, S)
        wres = wbuf.reshape(c_local, E, S)
        chained = (
            jax.default_backend() == "cpu"
            and 0 < E <= scanlib.UNROLL_CAP
            and S <= scanlib.UNROLL_CAP // E
        )

        def epoch_sums(mat):  # [c_local, E, S] -> [c_local, E]
            if chained:
                acc = mat[:, :, 0]
                for s in range(1, S):
                    acc = acc + mat[:, :, s]
                return acc
            return jnp.stack(
                [jnp.sum(mat[:, e, :], axis=-1) for e in range(E)], axis=1
            )

        loss_sums = epoch_sums(prods)
        w_sums = epoch_sums(wres)
        last = jnp.maximum(
            jnp.minimum((num_steps.astype(jnp.int32) - 1) // S, E - 1), 0
        )
        rows = jnp.arange(c_local)
        train_loss = loss_sums[rows, last] / jnp.maximum(
            w_sums[rows, last], 1.0
        )
        return self._aggregate_tail(
            variables, server_state, local_vars, weights, num_steps,
            train_loss, rng,
        )

    def _block_impl(self, global_variables, server_state, dataset, idxs,
                    weights, num_steps, rngs):
        # R stacked rounds in one program: lax.scan over the round axis of
        # [R, C_local, ...] index/weight stacks. One dispatch per block
        # amortizes host->device latency over R rounds (the per-round
        # dispatch cost dominates small models on remote-attached chips).
        def step(carry, xs):
            v, s = carry
            idx, w, ns, key = xs
            v, s, m = self._gather_round_impl(v, s, dataset, idx, w, ns, key)
            return (v, s), m

        carry = (global_variables, server_state)
        with trace.loop(trace.SCOPE_LOOP_ROUNDS, carry):
            (v, s), ms = jax.lax.scan(
                step, carry, (idxs, weights, num_steps, rngs)
            )
        return v, s, ms

    def _get_block_fn(self, n_rounds: int):
        """The R-round block program: one row of ``_build_programs``' table
        for every R (jit keeps a compiled program per R)."""
        return self._block_fn

    def _stage_block(self, start_round: int, n_rounds: int, root_rng):
        """Host staging for one R-round block: stacked [R, C_pad, ...]
        index/weight/step arrays (each round's slice built by the vectorized
        cohort builder) shipped with block sharding, plus per-round rng
        keys. Pure in (config, rounds, root_rng), so the prefetch thread
        can build the next block while the current one executes."""
        with trace.span("engine/stage", round=start_round,
                        n_rounds=n_rounds, block=True):
            return self._stage_block_impl(start_round, n_rounds, root_rng)

    def _stage_block_impl(self, start_round: int, n_rounds: int, root_rng):
        from jax.sharding import NamedSharding, PartitionSpec as P

        with trace.span("engine/stage/cohort", round=start_round):
            per_round = [
                self._host_cohort_indices(self._sample_round_cohort(r), r)
                for r in range(start_round, start_round + n_rounds)
            ]
        block_sharding = NamedSharding(self.mesh, P(None, meshlib.CLIENT_AXIS))
        with trace.span("engine/stage/put", round=start_round):
            idxs = self._put(
                np.stack([p[0] for p in per_round]), block_sharding)
            weights = self._put(
                np.stack([p[1] for p in per_round]), block_sharding)
            num_steps = self._put(
                np.stack([p[2] for p in per_round]), block_sharding)
        with trace.span("engine/stage/keys", round=start_round):
            rngs = jnp.stack([
                rnglib.round_key(root_rng, r)
                for r in range(start_round, start_round + n_rounds)
            ])
        return idxs, weights, num_steps, rngs

    def run_block(self, start_round: int, n_rounds: int, global_variables,
                  server_state, root_rng, staged=None):
        """Run ``n_rounds`` consecutive rounds in ONE device dispatch
        (on-device-dataset path only). Returns (variables, server_state,
        stacked metrics dict with a leading [n_rounds] axis). ``staged``
        passes a pre-built _stage_block payload (the pipelined driver's
        prefetch thread); default stages inline."""
        if not self._on_device:
            raise ValueError("run_block requires the on-device dataset path")
        if self._pack:
            raise ValueError(
                "run_block is the padded block-dispatch path; packed rounds "
                "(pack_lanes > 0) dispatch one program per pass instead"
            )
        if self._spmd:
            raise ValueError(
                "run_block scans whole rounds inside one program; sharded "
                "rounds (shard_rules) dispatch a train and an aggregate "
                "program per round instead"
            )
        idxs, weights, num_steps, rngs = (
            staged if staged is not None
            else self._stage_block(start_round, n_rounds, root_rng)
        )
        with trace.span("engine/dispatch", program=f"block{n_rounds}",
                        round=start_round, n_rounds=n_rounds):
            return self._get_block_fn(n_rounds)(
                global_variables, server_state, self._dataset, idxs, weights,
                num_steps, rngs,
            )

    @jax.named_scope(trace.SCOPE_EVAL)
    def _eval_impl(self, variables, batches):
        variables = self._compute_view(variables)

        def step(carry, batch):
            return carry, self.trainer.eval_batch(variables, batch)

        _, m = scanlib.scan(step, 0, batches)
        summed = jax.tree.map(lambda x: jnp.sum(x, axis=0), m)
        total = jnp.maximum(summed["test_total"], 1.0)
        return {
            "Acc": summed["test_correct"] / total,
            "Loss": summed["test_loss"] / total,
        }

    def _eval_gather_impl(self, variables, dataset, idx):
        # pooled-eval analogue of _gather_round_impl: idx [S, B], -1 = pad
        return self._eval_impl(variables, self._gather_batches(dataset, idx))

    # -- host driver ---------------------------------------------------------

    def init_variables(self) -> Pytree:
        sample = {
            name: jnp.asarray(arr[: self.config.batch_size])
            for name, arr in self.train_data.arrays.items()
        }
        sample.setdefault("mask", jnp.ones((self.config.batch_size,), jnp.float32))
        return self.trainer.init(jax.random.key(self.config.seed), sample)

    def _variables_shape_tree(self) -> Pytree:
        """Abstract model variables (shapes/dtypes only) for partition-rule
        planning: ``jax.eval_shape`` over ``trainer.init``, so planning a
        too-big-for-one-chip model never materializes it."""
        return jax.eval_shape(self.init_variables)

    def init_round_variables(self, overrides: Pytree | None = None) -> Pytree:
        """Model state in the engine's layout: a replicated global model, or —
        per-client mode — an identical-init stacked [C_pad, ...] model set
        sharded over the clients axis (every node starts from the same point,
        the standard decentralized-optimization setup).

        ``overrides`` warm-starts collections from a pretrained file
        (reference resnet.py:202-224): a partial variables dict — e.g.
        ``{"params": ...}`` from :func:`fedml_tpu.obs.checkpoint.load_params`
        — grafted over the fresh init before layout."""
        v = self.init_variables()
        if overrides:
            from fedml_tpu.obs.checkpoint import graft_params

            v = graft_params(jax.tree.map(np.asarray, dict(v)), dict(overrides))
        if self._per_client:
            # every client is in every round's cohort (_resolve_aggregator),
            # so the clients' padded count is the cohort's
            stacked = jax.tree.map(
                lambda l: np.broadcast_to(
                    np.asarray(l)[None], (self._c_pad,) + l.shape), v
            )
            return self._put(stacked, self._client_shard)
        # under a shard plan the sharded-at-rest layout: each leaf placed per
        # its rule (multi-controller: every process holds the same host init
        # and materializes only the addressable shards of that placement)
        return self._put(v, self._var_shardings if self._spmd else self._rep)

    def consensus(self, variables: Pytree) -> Pytree:
        """A single evaluable model: identity in broadcast mode; the node
        average over real clients (padding excluded) in per-client mode."""
        if not self._per_client:
            return variables
        n = self.config.client_num_in_total
        return jax.tree.map(lambda l: jnp.mean(l[:n], axis=0), variables)

    def stage_cohort(self, cohort, round_idx: int):
        """Stage an explicit cohort's data on device: stack, apply straggler
        budgets, pad to the mesh's client axis, ship."""
        return self._stage_host_cohort(
            self._host_cohort_batches, self._shard, cohort, round_idx)

    def _stage_host_cohort(self, host_side, sharding, cohort, round_idx: int):
        """Build a cohort's data, weights and budgets on the host
        (``host_side``) and ship them, the data to ``sharding``."""
        with trace.span("engine/stage/cohort", round=round_idx):
            data, weights, num_steps = host_side(cohort, round_idx)
        with trace.span("engine/stage/put", round=round_idx):
            return (
                self._put(data, sharding),
                self._put(weights, self._vector_sharding),
                self._put(num_steps, self._vector_sharding),
            )

    def _host_cohort(self, build, fill, cohort, round_idx: int):
        """What the two host-side builders share around ``build``
        (``cohortlib.stack_cohort`` or ``cohort_index_map``): the round's
        shuffle, the budgets and weights, and the padding, whose data slots
        hold ``fill``."""
        cfg = self.config
        shuffle = (
            np.random.RandomState(cfg.seed * 1_000_003 + round_idx)
            if cfg.shuffle_each_round
            else None
        )
        data, weights = build(
            self.train_data, cohort, cfg.batch_size, steps=self._steps,
            rng=shuffle,
        )
        # budgets first: their cohort-identity check fails loudly before
        # the dropout weight mask could hit a shape mismatch
        num_steps = self._round_budgets(cohort, round_idx)
        weights = self._population_weights(weights, round_idx)
        # Pad the cohort axis to a multiple of the mesh's client axis with
        # zero-weight dummy clients (fully masked, excluded from the weighted
        # aggregation) so the stack shards evenly over devices.
        pad = (-len(cohort)) % self._n_client_shards
        if pad:
            data = jax.tree.map(
                lambda v: np.concatenate(
                    [v, np.full((pad,) + v.shape[1:], fill, v.dtype)]), data)
            weights = np.concatenate([weights, np.zeros(pad, np.float32)])
            num_steps = np.concatenate([num_steps, np.zeros(pad, np.int32)])
        return data, weights, num_steps

    def _host_cohort_batches(self, cohort, round_idx: int):
        """Host side of :meth:`stage_cohort`: the cohort's batch stack,
        weights and step budgets, padded to the mesh."""
        return self._host_cohort(cohortlib.stack_cohort, 0, cohort, round_idx)

    def _population_view(self, round_idx: int):
        """The round's realized population state (cached per round — the
        sampler, budget, weight, and pack hooks all read it). Raises the
        wire path's :class:`EmptyRoundError` when availability churn leaves
        the round with nothing to aggregate, instead of a downstream
        shape/NaN error."""
        cached = self._pop_view_cache
        if cached is not None and cached[0] == round_idx:
            return cached[1]
        view = self._population.round_view(
            round_idx, self.config.client_num_per_round
        )
        if view.eligible_count == 0 or not view.real().any():
            raise EmptyRoundError(
                f"round {round_idx}: availability churn left no eligible "
                f"clients (population of {self._population.num_clients}, "
                "0 available) — nothing to aggregate; widen avail/"
                "avail_block or skip the round"
            )
        if bool((view.dropped | ~view.real()).all()):
            raise EmptyRoundError(
                f"round {round_idx}: every sampled cohort member "
                f"({int(view.real().sum())} of "
                f"{view.cohort_size}) dropped mid-round — no update "
                "survives to aggregate (the wire path's all-dropped-round "
                "semantics)"
            )
        self._pop_view_cache = (round_idx, view)
        return view

    def _population_budgets(self, view) -> tuple[np.ndarray, np.ndarray]:
        """(actual, predicted) per-slot step budgets for a population
        round, in scan-step units against the engine's epochs x steps
        chain (population.step_budgets does the mapping)."""
        from fedml_tpu.population import step_budgets

        return step_budgets(view, self.trainer.epochs * self._steps)

    def _round_budgets(self, cohort, round_idx: int) -> np.ndarray:
        """Per-client local-step budgets (scan-step units): stragglers run a
        reduced epoch count e_i, i.e. the first e_i * steps-per-epoch steps.
        With a population configured, budgets come from its per-client
        speed model instead (dropout truncation included)."""
        cfg = self.config
        if self._population is not None:
            view = self._population_view(round_idx)
            if not np.array_equal(np.asarray(cohort), view.cohort):
                raise ValueError(
                    "SimConfig.population drives cohort selection; "
                    "compositions that pick their own cohorts (e.g. "
                    "hierarchical groups) need the population off"
                )
            actual, _ = self._population_budgets(view)
            return actual
        if cfg.straggler_frac > 0.0:
            from fedml_tpu.algorithms.fedprox import straggler_epochs

            epochs_arr = straggler_epochs(
                round_idx, len(cohort), cfg.epochs, cfg.straggler_frac, cfg.seed
            )
        else:
            epochs_arr = np.full(len(cohort), cfg.epochs, np.int32)
        return (epochs_arr * self._steps).astype(np.int32)

    def _population_weights(self, weights: np.ndarray,
                            round_idx: int) -> np.ndarray:
        """Zero the aggregation weight of mid-round-dropped cohort members:
        they trained part of their budget (the FLOPs are real) but their
        update never reaches the server — excluded from the weighted mean
        and the loss average exactly like a padding slot. No-op without a
        population."""
        if self._population is None:
            return weights
        view = self._population_view(round_idx)
        return np.where(view.dropped, 0.0, weights).astype(np.float32)

    def _host_cohort_indices(self, cohort, round_idx: int):
        """Host-side index staging: [C_pad, S, B] int32 index map (-1 = empty
        slot) + weights + per-client step budgets, padded to the mesh.
        Vectorized (cohortlib.cohort_index_map): a fixed number of numpy ops
        per round regardless of cohort size — the builder run_round,
        run_block, and evaluate_per_client all share."""
        return self._host_cohort(
            cohortlib.cohort_index_map, -1, cohort, round_idx)

    def stage_cohort_indices(self, cohort, round_idx: int):
        """Device staging for the on-device-dataset path: instead of the full
        [C, S, B, ...] batch stack, upload only a [C, S, B] int32 index map
        (-1 = empty slot); the round program gathers rows in HBM."""
        return self._stage_host_cohort(
            self._host_cohort_indices, self._client_shard, cohort, round_idx)

    def _sample_round_cohort(self, round_idx: int) -> np.ndarray:
        cfg = self.config
        if self._per_client:
            # stable identity order: slot i is client i every round, so the
            # persistent stack and the mixing matrix's adjacency line up
            return np.arange(cfg.client_num_in_total)
        if self._population is not None:
            # availability-aware sampling (population/model.py): the view's
            # cohort is always exactly client_num_per_round slots — churn
            # that leaves fewer eligible clients pads with -1 empty slots,
            # so compiled shapes never change
            return self._population_view(round_idx).cohort
        return rnglib.sample_clients(
            round_idx, cfg.client_num_in_total, cfg.client_num_per_round
        )

    def run_cohort_round(self, cohort, round_idx, global_variables,
                         server_state, rkey):
        """One round over an explicit cohort: stage (on-device index map or
        host batches) and dispatch. Shared by run_round and compositions
        that pick their own cohorts (HierarchicalFedAvg's groups)."""
        return self.run_staged_round(
            self.stage_cohort_round(cohort, round_idx, rkey),
            global_variables, server_state,
        )

    def stage_round(self, round_idx: int, root_rng):
        """All host work for one round — cohort sampling, vectorized index/
        batch staging, device_put, rng-key derivation. Pure in (config,
        round_idx, root_rng): prefetching it ahead of the dispatch loop
        (sim/prefetch.py) cannot change cohorts, keys, or metrics."""
        # the same three children as a block's staging; the key and the
        # sampling come before ``engine/stage`` opens, so here they are its
        # siblings under ``prefetch/stage``
        with trace.span("engine/stage/keys", round=round_idx):
            rkey = rnglib.round_key(root_rng, round_idx)
        with trace.span("engine/stage/cohort", round=round_idx):
            cohort = self._sample_round_cohort(round_idx)
        return self.stage_cohort_round(cohort, round_idx, rkey)

    def stage_cohort_round(self, cohort, round_idx: int, rkey):
        """Staged payload for one round over an explicit cohort (the
        on-device index map or the host batch stack, + weights, budgets,
        and the round's rng key; a :class:`PackedStaged` lane plan when
        packed execution is on)."""
        with trace.span("engine/stage", round=round_idx, packed=self._pack):
            if self._pack:
                return self._stage_packed_round(cohort, round_idx, rkey)
            stage = (self.stage_cohort_indices if self._on_device
                     else self.stage_cohort)
            return stage(cohort, round_idx) + (rkey,)

    def _pack_round_plan(self, cohort, round_idx: int):
        """Host-only planning for one packed round: the round's [C_pad, S, B]
        cohort index map (built exactly as the padded path builds it) plus
        the lane packing of each client's executed-step stream. No device
        work."""
        idx, weights, num_steps = self._host_cohort_indices(cohort, round_idx)
        if len(weights) != self._c_pad:
            raise ValueError(
                f"packed execution compiled for {self._c_pad} cohort slots "
                f"but this cohort stages {len(weights)} — compositions that "
                "pick their own cohort sizes (e.g. hierarchical groups) "
                "need the padded path"
            )
        B = self.config.batch_size
        valid_counts = (idx >= 0).reshape(len(weights), -1).sum(axis=1)
        data_steps = -(-valid_counts // B)
        predicted = None
        if self._population is not None:
            # the planner bins by the population's PREDICTED budgets (the
            # scheduler cannot know who drops mid-round); dropped lanes are
            # re-packed by their actual truncated streams into overflow
            # passes inside pack_cohort
            _, predicted = self._population_budgets(
                self._population_view(round_idx)
            )
            pad = len(weights) - len(predicted)
            if pad:
                predicted = np.concatenate(
                    [predicted, np.zeros(pad, np.int32)]
                )
        plan = cohortlib.pack_cohort(
            num_steps, data_steps, self._steps, self.trainer.epochs,
            self.config.pack_lanes, self._s_lane, self._n_client_shards,
            predicted_steps=predicted,
        )
        return idx, weights, num_steps, plan

    def _stage_packed_round(self, cohort, round_idx: int, rkey) -> PackedStaged:
        """Host staging for one packed round: plan it (:meth:`_pack_round_plan`),
        gather each pass's data, and ship plan + data to device. Pure in
        (config, round_idx, rkey) like every staging path, so the prefetch
        thread can run it ahead."""
        idx, weights, num_steps, plan = self._pack_round_plan(cohort, round_idx)
        # lane occupancy (executed steps / scanned lane slots, overflow
        # passes included) and overflow-pass count per round: the two
        # numbers that say whether the lane geometry fits the population
        trace.gauge("engine/lane_occupancy",
                    plan.total_steps / max(plan.capacity, 1),
                    round=round_idx)
        trace.counter("engine/overflow_passes", len(plan.passes) - 1,
                      round=round_idx)
        passes = []
        for pp in plan.passes:
            data = cohortlib.pack_index_map(idx, pp)
            if not self._on_device:  # ship the batches, not their index map
                data = cohortlib.gather_index_stack(
                    self.train_data.arrays, data)
            passes.append((
                self._put(data, self._client_shard),
                self._put(pp.slot, self._client_shard),
                self._put(pp.gidx, self._client_shard),
                self._put(pp.boundary, self._client_shard),
            ))
        return PackedStaged(
            passes=tuple(passes),
            weights=self._put(weights, self._vector_sharding),
            num_steps=self._put(num_steps, self._vector_sharding),
            rkey=rkey,
            stats={
                "n_passes": len(plan.passes),
                "total_steps": plan.total_steps,
                "capacity": plan.capacity,
                "padded_steps": len(weights) * self.trainer.epochs * self._steps,
            },
        )

    def run_staged_round(self, staged, global_variables, server_state):
        """Dispatch one round from a stage_round payload."""
        if isinstance(staged, PackedStaged):
            with trace.span("engine/dispatch", program="packed", n_rounds=1,
                            n_passes=staged.stats["n_passes"]):
                return self._run_packed(staged, global_variables, server_state)
        data, weights, num_steps, rkey = staged
        if self._spmd:
            # sharded round: train dispatch, then aggregate dispatch — both
            # enqueue asynchronously, so the split costs no host sync.
            global_variables, server_state = self._in_plan_layout(
                global_variables, server_state)
            with trace.span("engine/dispatch", program="spmd_train",
                            n_rounds=1):
                stack, losses = self._train_program(
                    global_variables, *self._data_args, data, num_steps, rkey,
                )
            # the round's second dispatch: n_rounds=0, so that summing
            # n_rounds over dispatches counts each round once
            with trace.span("engine/dispatch", program="spmd_agg",
                            n_rounds=0):
                return self._spmd_agg_fn(
                    global_variables, server_state, stack, losses, weights,
                    num_steps, rkey,
                )
        with trace.span("engine/dispatch", program=self._round_label,
                        n_rounds=1):
            return self._call_round(
                self._round_program, global_variables, server_state,
                *self._data_args, data, weights, num_steps, rkey,
            )

    def _in_plan_layout(self, global_variables, server_state):
        """The caller's model and server state in a shard plan's at-rest
        layout: a checkpoint restore or a fresh aggregator state may arrive
        in another sharding (device_put short-circuits when it already
        matches). Multihost runs skip this: cross-process resharding is not
        a device_put, and init_round_variables already places the model
        globally."""
        if self._multihost:
            return global_variables, server_state
        return (jax.device_put(global_variables, self._var_shardings),
                jax.device_put(server_state, self._rep))

    def _call_round(self, program, global_variables, *args):
        """``program(global_variables, *args)`` for a round program, which
        consumes its model argument. The stack's round donates it. The
        running mean's round (``_build_programs``: ``spare``) is handed the model
        of the round before to sum into, and its own model is kept for the
        round after: the caller's arrays are gone one call later. The first
        round, or one given the same arrays again, sums into new zeros."""
        if not self._mean_in_carry:
            return program(global_variables, *args)
        spare, self._spare = self._spare, global_variables
        given = {id(x) for x in jax.tree.leaves(global_variables)}
        if spare is None or any(
                id(x) in given or not isinstance(x, jax.Array) or x.is_deleted()
                for x in jax.tree.leaves(spare)):
            spare = jax.tree.map(jnp.zeros_like, global_variables)
        return program(global_variables, *args, spare)

    def _run_packed(self, staged: PackedStaged, global_variables, server_state):
        """One packed round: zero buffers, P lane-scan passes chaining the
        update stack, then the aggregation program. All dispatches enqueue
        asynchronously, so the extra program boundaries cost no host sync."""
        if self._spmd:
            global_variables, server_state = self._in_plan_layout(
                global_variables, server_state)
        bufs = self._packed_buf_fn(global_variables)
        for data, slot, gidx, boundary in staged.passes:
            bufs = self._packed_pass_fn(
                global_variables, *self._data_args, data, slot, gidx,
                boundary, *bufs, staged.rkey,
            )
        return self._packed_agg_fn(
            global_variables, server_state, *bufs, staged.weights,
            staged.num_steps, staged.rkey,
        )

    def pack_summary(self) -> dict:
        """Static packed-execution accounting (empty when pack_lanes is off):
        lane geometry and the padded-path step count one round would have
        scanned — the observability hook exp loops log at run start."""
        if not self._pack:
            return {}
        return {
            "pack_lanes": self.config.pack_lanes,
            "s_lane": self._s_lane,
            "lane_capacity_per_pass":
                self.config.pack_lanes * self._n_client_shards * self._s_lane,
            "padded_scan_steps":
                self._c_pad * self.trainer.epochs * self._steps,
        }

    def shard_summary(self) -> dict:
        """Static sharded-model accounting (empty when no shard plan is
        configured): the rule set, mesh geometry, lowering mode, and how
        many variable leaves actually shard — the observability hook exp
        loops log at run start (mirrors :meth:`pack_summary`)."""
        if not self.config.shard_rules:
            return {}
        from jax.sharding import PartitionSpec

        from fedml_tpu.parallel import dispatch as displib

        leaves = jax.tree_util.tree_leaves(
            self._var_specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
        )
        return {
            "shard_rules": self.config.shard_rules,
            "mesh": {
                ax: int(n) for ax, n in
                zip(self.mesh.axis_names, self.mesh.devices.shape)
            },
            "mode": "pjit" if self._spmd else "shard_map",
            "gather_compute": self._shard_gather,
            "sharded_leaves": sum(
                1 for s in leaves if displib.spec_is_sharded(s)
            ),
            "total_leaves": len(leaves),
        }

    def population_summary(self) -> dict:
        """Static population accounting (empty when no population is
        configured): the spec/trace identity and geometry — the
        observability hook exp loops log at run start (mirrors
        :meth:`pack_summary`)."""
        if self._population is None:
            return {}
        return self._population.describe()

    def defense_summary(self) -> dict:
        """Static robust-defense accounting (empty when no defense stage is
        configured): the clip/rule/noise knobs in effect — the observability
        hook exp loops log at run start (mirrors :meth:`pack_summary`)."""
        c = self.config
        if c.robust_rule == "mean" and c.norm_bound <= 0 and c.dp_stddev <= 0:
            return {}
        return {
            "rule": c.robust_rule,
            "norm_bound": c.norm_bound,
            "dp_stddev": c.dp_stddev,
            "aggregator": self.aggregator.name,
        }

    def run_round(self, round_idx, global_variables, server_state, root_rng):
        return self.run_staged_round(
            self.stage_round(round_idx, root_rng), global_variables,
            server_state,
        )

    def evaluate_per_client(
        self,
        variables,
        client_ids=None,
        data: cohortlib.FederatedArrays | None = None,
        batch_size: int | None = None,
        chunk: int = 64,
    ) -> dict[str, np.ndarray]:
        """Vectorized server-side eval of one model on every client's shard.

        The reference walks clients serially through one torch loop
        (FedAVGAggregator.test_on_server_for_all_clients,
        FedAVGAggregator.py:110-164); here a single jitted
        ``vmap(local_eval)`` evaluates a whole chunk of clients at once.
        Returns raw summed metric arrays keyed like ``trainer.eval_batch``'s
        output (e.g. test_correct/test_total/test_loss, plus task extras such
        as fedseg's per-client confusion matrices), each with a leading
        [num_clients] axis. Clients are processed in uniform-shape chunks of
        ``min(chunk, len(ids))``, so repeated calls over the same client set
        reuse one compiled program.
        """
        if not self._can_eval:
            return {}
        use_resident = data is None and self._on_device
        data = data if data is not None else self.train_data
        ids = np.asarray(
            client_ids if client_ids is not None else np.arange(data.num_clients)
        )
        if len(ids) == 0:
            return {}
        bs = batch_size or self.config.eval_batch_size
        steps = cohortlib.steps_per_epoch(data.max_client_size(), bs)
        csz = min(chunk, len(ids))
        outs = []
        for lo in range(0, len(ids), csz):
            sel = ids[lo : lo + csz]
            pad = csz - len(sel)
            padded = np.concatenate([sel, np.repeat(sel[-1:], pad)]) if pad else sel
            if use_resident:
                # same vectorized index builder as the round path; pad rows
                # stay all -1 (fully masked)
                idx, _ = cohortlib.cohort_index_map(data, sel, bs, steps=steps)
                if pad:
                    idx = np.concatenate(
                        [idx, np.full((pad,) + idx.shape[1:], -1, np.int32)]
                    )
                m = self._client_eval_gather_fn(
                    variables, self._dataset, self._put(idx, self._rep),
                )
            else:
                stack = cohortlib.stack_client_eval(data, padded, bs, steps=steps)
                if pad:  # fully mask the duplicate tail clients
                    stack["mask"][len(sel):] = 0.0
                m = self._client_eval_fn(variables, jax.tree.map(jnp.asarray, stack))
            outs.append(jax.tree.map(lambda x: np.asarray(x)[: len(sel)], m))
        return {
            k: np.concatenate([o[k] for o in outs]) for k in outs[0]
        }

    def per_client_summary(self, variables) -> dict[str, float]:
        """Pooled train metrics from the per-client eval — the numbers the
        reference logs from test_on_server_for_all_clients (sum of per-client
        corrects / totals, FedAVGAggregator.py:139-147)."""
        m = self.evaluate_per_client(variables)
        if not m or "test_total" not in m:
            return {}
        total = max(float(m["test_total"].sum()), 1.0)
        return {
            "Train/AccOnClients": float(m["test_correct"].sum()) / total,
            "Train/LossOnClients": float(m["test_loss"].sum()) / total,
        }

    def eval_record(self, variables) -> dict[str, float]:
        """The test-round metric block: pooled eval (+ per-client summary
        when configured). One definition for every run loop."""
        with trace.span("engine/eval",
                        on_clients=self.config.eval_on_clients):
            eval_vars = self.consensus(variables)
            out = self.evaluate(eval_vars)
            if self.config.eval_on_clients:
                out.update(self.per_client_summary(eval_vars))
            return out

    def evaluate(self, variables) -> dict[str, float]:
        if not self._can_eval:
            return {}
        # enqueue BOTH eval programs before fetching anything: JAX dispatch
        # is async, so the train and test programs overlap on device and the
        # host pays ONE round-trip (device_get) instead of four synchronous
        # float() fetches
        train_eval, *args = self._train_eval
        train_m = train_eval(variables, *args)
        test_m = (
            self._eval_fn(variables, self._test_batches)
            if self._test_batches is not None
            else None
        )
        train_m, test_m = jax.device_get((train_m, test_m))
        out = {
            "Train/Acc": float(train_m["Acc"]),
            "Train/Loss": float(train_m["Loss"]),
        }
        if test_m is not None:
            out["Test/Acc"] = float(test_m["Acc"])
            out["Test/Loss"] = float(test_m["Loss"])
        return out

    def _dispatch_plan(self, start_round: int) -> list[tuple[int, int]]:
        """The run's dispatch segments ``[(first_round, n_rounds), ...]``:
        eval-aligned blocks when block dispatch is on (one device dispatch
        per block amortizes host->device latency; alignment keeps every eval
        at a block end so accuracy is attributed to the right round),
        single rounds otherwise. Under profiling the first segment runs
        alone so the trace skips compilation. Deterministic up front, so
        staging can be prefetched ahead of the dispatch loop."""
        cfg = self.config
        freq = max(cfg.frequency_of_the_test, 1)
        plan = []
        r = start_round
        while r < cfg.comm_round:
            next_eval = ((r // freq) + 1) * freq
            n = (min(cfg.comm_round, next_eval) - r
                 if self._block_dispatch else 1)
            if cfg.profile_dir and r == start_round:
                n = 1
            plan.append((r, n))
            r += n
        return plan

    def _stage_segment(self, segment: tuple[int, int], root_rng):
        r, n = segment
        if n == 1:
            return self.stage_round(r, root_rng)
        return self._stage_block(r, n, root_rng)

    def run(self, callback=None, variables=None, server_state=None,
            start_round: int = 0) -> tuple[Pytree, list[dict]]:
        """Run the configured rounds. ``variables``/``server_state``/
        ``start_round`` resume from a checkpoint (obs/checkpoint.py);
        defaults start fresh.

        With ``pipeline_depth`` > 0 (the default) the driver is pipelined
        (sim/prefetch.py): a background thread stages upcoming dispatches
        while the device executes the current one, and round metrics drain
        a dispatch behind — the host synchronizes with the device only at
        eval boundaries and at the end. Bit-identical to the serial driver
        (``pipeline_depth=0``); records reach ``callback`` and the history
        in round order, delivered at each synchronization point.
        ``round_time`` (on each segment's last round) is the synchronization
        window's per-round wall-time average, so summing it over
        single-round dispatches recovers the run's wall time just as in the
        serial driver."""
        from fedml_tpu.sim.prefetch import MetricsDrain, Prefetcher

        cfg = self.config
        if variables is None:
            variables = self.init_round_variables()
        if server_state is None:
            server_state = self.aggregator.init_state(variables)
        root = rnglib.root_key(cfg.seed)
        history: list[dict] = []
        profiling = False
        freq = max(cfg.frequency_of_the_test, 1)
        plan = self._dispatch_plan(start_round)
        depth = self.pipeline_depth
        prefetch = (
            Prefetcher(plan, lambda seg: self._stage_segment(seg, root), depth)
            if depth and plan else None
        )
        drain = MetricsDrain(depth)

        def is_eval_round(rr: int) -> bool:
            return (rr + 1) % freq == 0 or rr == cfg.comm_round - 1

        def emit(segment, stacked_np, per_round_time=None, eval_rec=None):
            r0, n = segment
            for j in range(n):
                rr = r0 + j
                rec = {"round": rr}
                if j == n - 1 and per_round_time is not None:
                    rec["round_time"] = per_round_time
                rec.update({k: float(v[j]) for k, v in stacked_np.items()})
                for k, v in rec.items():
                    if k.startswith(STATS_PREFIX):  # the model's own counters
                        trace.counter(k[len(STATS_PREFIX):], v, round=rr)
                if j == n - 1 and eval_rec:
                    rec.update(eval_rec)
                history.append(rec)
                if callback:
                    callback(rec)
                logging.info(
                    "round %d: %s", rr,
                    {k: v for k, v in rec.items() if k != "round"},
                )

        t_mark = time.perf_counter()
        rounds_in_window = 0
        # metrics fetched mid-window (they fell off the drain's back) are
        # held here and emitted at the window's sync point, where the
        # per-round wall time they should carry is known
        pending: list[tuple] = []
        try:
            for segment in plan:
                r0, n = segment
                # start the trace after the first round so compilation
                # doesn't drown the steady-state rounds in the profile (a
                # 1-round run traces its only round, compilation included)
                if cfg.profile_dir and not profiling and (
                    r0 > start_round or cfg.comm_round - start_round == 1
                ):
                    jax.profiler.start_trace(cfg.profile_dir)
                    profiling = True
                staged = prefetch.get(segment) if prefetch else None
                if n == 1:
                    if staged is None:
                        staged = self.stage_round(r0, root)
                    variables, server_state, metrics = self.run_staged_round(
                        staged, variables, server_state
                    )
                    stacked = {
                        k: jnp.asarray(v)[None] for k, v in metrics.items()
                    }
                else:
                    variables, server_state, stacked = self.run_block(
                        r0, n, variables, server_state, root, staged=staged
                    )
                rounds_in_window += n
                last = r0 + n - 1
                if is_eval_round(last) or depth == 0:
                    # synchronization point: fetch everything queued
                    # (including this segment's metrics), then eval
                    with trace.span("engine/sync", round=last):
                        ready = (pending + drain.push(segment, stacked)
                                 + drain.flush())
                        pending = []
                        if depth == 0:
                            jax.block_until_ready(variables)
                    per_round = (
                        (time.perf_counter() - t_mark)
                        / max(rounds_in_window, 1)
                    )
                    eval_rec = (
                        self.eval_record(variables)
                        if is_eval_round(last) else None
                    )
                    for pseg, pstacked in ready:
                        emit(pseg, pstacked, per_round_time=per_round,
                             eval_rec=eval_rec if pseg == segment else None)
                    t_mark = time.perf_counter()
                    rounds_in_window = 0
                else:
                    # non-blocking: only metrics that fell off the drain's
                    # back (already-finished dispatches) are fetched; they
                    # are emitted at the window's sync point with its timing
                    pending.extend(drain.push(segment, stacked))
        finally:
            if prefetch:
                prefetch.close()
            if profiling:
                jax.profiler.stop_trace()
        return variables, history


# ---------------------------------------------------------------------------
# Centralized baseline (reference fedml_api/centralized/centralized_trainer.py:9)
# — used by the FedAvg ≡ centralized equivalence oracle (CI-script-fedavg.sh:41-47).
# ---------------------------------------------------------------------------


def centralized_train(
    trainer: ClientTrainer,
    arrays: dict[str, np.ndarray],
    batch_size: int,
    num_epochs: int,
    seed: int = 0,
):
    """Train on the pooled dataset with the same jitted machinery."""
    batches = cohortlib.batch_array(arrays, batch_size)
    sample = jax.tree.map(lambda x: jnp.asarray(x[0]), batches)
    variables = trainer.init(jax.random.key(seed), sample)
    local_train = make_local_train(
        dataclasses.replace(trainer, epochs=num_epochs)
    )
    fn = jax.jit(local_train)
    variables, metrics = fn(variables, jax.tree.map(jnp.asarray, batches), jax.random.key(seed + 1))
    return variables, metrics
