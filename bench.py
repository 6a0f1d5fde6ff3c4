"""Benchmark: federated round throughput + delivered FLOPs on the local chip.

Needs a TPU: on any other backend it prints one error line and exits
non-zero (there is no CPU shape and no CPU metric). Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": ...,
   "platform": "tpu", ...}

Primary metric (comparable across rounds): FedAvg rounds/sec for the
reference's cross-silo headline model (ResNet-56, CIFAR-10 shapes;
BASELINE.md cross-silo table) — 10 clients x 1 local epoch x 8 steps x
batch 32, in **bfloat16 compute / f32 params** — the TPU-first numerics
(tests/test_models.py asserts f32-vs-bf16 accuracy parity on this model
family). ``vs_baseline`` divides it by the same federated round executed
the reference's way (sequential per-client torch training, this host's CPU —
the only executable reference here; the reference repo publishes no
wall-clock, SURVEY §6). The torch number is measured once and cached. The
f32 rounds/sec stays in ``extra`` for continuity with BENCH_r02.

MFU story (the number that actually says "fast on TPU"): a big-shape
federated LM round — TransformerLM (D=2048, L=8, H=16, T=1024, V=32k) in
bfloat16 with the pallas flash-attention kernel (ops/attention.py, which
picks its tiles), 2 clients x 32 local steps x batch 4 — with analytic model FLOPs
(matmul 2P per token + causal attention at half of 4TD, train = 3x fwd)
against the chip's peak. Also reports pooled eval throughput on the ResNet.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

CACHE = Path(__file__).parent / ".bench_cache.json"

CLIENTS = 10
STEPS = 8
BATCH = 32
EPOCHS = 1

# peak dense bf16 TFLOP/s per chip, by jax device_kind
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5": 459.0,       # v5p
    "TPU v6 lite": 918.0,  # v6e / Trillium
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}

# LM bench shape (tuned on the v5e within its 16G HBM: D=2048 tiles the MXU
# better than D=1024 — 34% vs 31% MFU measured). 32 local steps amortize the
# per-round aggregation. The round-3 plateau at 0.467 was an HBM wall — the
# vmapped cohort held BOTH clients' model+optimizer state and activations
# simultaneously; cohort_execution="scan" (engine.py) trains the cohort
# sequentially, freeing one client's worth of HBM, which buys batch 8.
# Measured MFU ladder on the v5e — xla attention S=8: 0.351, flash S=8:
# 0.438, flash S=32: 0.459, + 256x1024 tiles: 0.467, + scan cohort B=8:
# 0.564. Beyond that the ladder bends down: scan B=16 thrashes (0.224),
# T=2048 grows the attention share without MXU benefit (0.445), remat
# only adds recompute once scan has already freed the memory (0.378).
LM_D, LM_L, LM_H, LM_T, LM_V = 2048, 8, 16, 1024, 32000
LM_CLIENTS, LM_STEPS, LM_BATCH = 2, 32, 8
LM_ATTN = "flash"  # the pallas kernel IS the benchmarked path
LM_COHORT = "scan"  # sequential cohort: the big-model HBM mode

# conv-probe shape: same engine path as the ResNet bench but with channel
# widths that actually fill the 128-lane MXU contraction/output dims —
# demonstrates the ~5% ResNet-56 delivered fraction is an
# arithmetic-intensity ceiling of the 16/32/64-channel CIFAR shapes, not
# engine overhead (see resnet_bound in the output)
CP_C, CP_HW, CP_LAYERS, CP_BATCH, CP_STEPS, CP_CLIENTS = 256, 32, 10, 128, 4, 2


def resnet56_train_flops_per_image() -> float:
    """Analytic FLOPs (2 x MAC) for one ResNet-56 CIFAR training example:
    stem + 3 stages x 9 blocks x 2 convs (+1x1 shortcut at stage entry) + fc,
    with train = 3 x forward (backward ~ 2 x forward)."""
    fl = 2 * 32 * 32 * 9 * 3 * 16  # stem 3x3, 3->16, 32x32
    spec = [(16, 16, 32), (16, 32, 16), (32, 64, 8)]
    for si, (cin, cout, hw) in enumerate(spec):
        for b in range(9):
            c_in = cin if b == 0 else cout
            fl += 2 * hw * hw * 9 * c_in * cout  # conv1 (output spatial size)
            fl += 2 * hw * hw * 9 * cout * cout  # conv2
            if b == 0 and si > 0:
                fl += 2 * hw * hw * 1 * c_in * cout  # 1x1 projection shortcut
    fl += 2 * 64 * 10  # fc
    return 3.0 * fl


def conv_probe_flops_per_image() -> float:
    """Analytic FLOPs (2 x MAC) for one wide-conv-probe training example:
    stem 3->C then (layers-1) CxC 3x3 convs at hw^2, + head; train = 3x fwd."""
    fl = 2 * CP_HW * CP_HW * 9 * 3 * CP_C
    fl += (CP_LAYERS - 1) * 2 * CP_HW * CP_HW * 9 * CP_C * CP_C
    fl += 2 * CP_C * 10
    return 3.0 * fl


def lm_train_flops_per_round() -> float:
    """Analytic matmul FLOPs for one federated LM round. Per token forward:
    2 x (12 L D^2 + D V) for the dense stack + head, plus causal attention
    counted at half the full 4 T D (only the lower triangle is useful work).
    Train = 3 x forward; round = clients x steps x batch x T tokens."""
    p_mm = LM_L * 12 * LM_D * LM_D + LM_D * LM_V
    fwd_per_tok = 2 * p_mm + LM_L * 2 * LM_T * LM_D
    tokens = LM_CLIENTS * LM_STEPS * LM_BATCH * LM_T
    return 3.0 * fwd_per_tok * tokens


def _measure_rounds(sim, n_meas: int = 5, block: int = 1) -> float:
    """Seconds per round, steady state; each timed call ends in a host
    fetch of the round's aggregated train loss.
    ``block`` > 1 measures the block-dispatch path (R rounds per device
    round-trip — the deployment configuration for small models)."""
    from fedml_tpu.core import rng as rnglib

    variables = sim.init_round_variables()
    server_state = sim.aggregator.init_state(variables)
    root = rnglib.root_key(0)
    if block == 1:
        variables, server_state, m = sim.run_round(0, variables, server_state, root)
        float(m["Train/Loss"])  # compile + first-round sync
        t0 = time.perf_counter()
        for r in range(1, 1 + n_meas):
            variables, server_state, m = sim.run_round(r, variables, server_state, root)
            float(m["Train/Loss"])
        return (time.perf_counter() - t0) / n_meas
    variables, server_state, m = sim.run_block(0, block, variables, server_state, root)
    float(m["Train/Loss"][-1])  # compile + first-block sync
    t0 = time.perf_counter()
    for i in range(n_meas):
        variables, server_state, m = sim.run_block(
            (i + 1) * block, block, variables, server_state, root
        )
        float(m["Train/Loss"][-1])
    return (time.perf_counter() - t0) / (n_meas * block)


STAGE_CLIENTS = 256  # the staging probe's synthetic cohort size


def bench_stage_probe():
    """Host staging cost per round at population scale: the vectorized
    cohort builder (sim/cohort.cohort_index_map) vs the pre-PR per-client
    Python loop, on a 256-client cohort with per-round shuffling. Pure host
    numpy — meaningful on any backend, and exactly what the pipelined
    driver's prefetch thread runs per round. Returns
    (host_stage_ms, host_stage_ms_loop)."""
    import numpy as np

    from fedml_tpu.sim.cohort import (
        FederatedArrays,
        _cohort_index_map_loop,
        cohort_index_map,
    )

    n_per = 64
    C = STAGE_CLIENTS
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(C)}
    data = FederatedArrays(
        {"x": np.zeros((C * n_per, 8), np.float32),
         "y": np.zeros(C * n_per, np.int32)},
        part,
    )
    cohort = np.arange(C)
    data.index_csr()  # one-time cache build stays out of the per-round cost
    reps = 20

    def per_round_ms(fn):
        # best of 3 windows: host microbenchmark, so take the least
        # load-disturbed window rather than averaging scheduler noise in
        fn(data, cohort, 32, rng=np.random.RandomState(0))  # warm
        best = float("inf")
        for _trial in range(3):
            t0 = time.perf_counter()
            for rep in range(reps):
                fn(data, cohort, 32, rng=np.random.RandomState(rep))
            best = min(best, (time.perf_counter() - t0) / reps * 1e3)
        return best

    return per_round_ms(cohort_index_map), per_round_ms(_cohort_index_map_loop)


def bench_pipeline_ab(trainer, train, test, cfg, n_rounds: int):
    """A-B probe for the pipelined round driver: rounds/sec through
    FedSim.run() with the pipeline on (default double-buffered prefetch +
    metrics drain) vs off (serial stage->dispatch->fetch). Single-round
    dispatch (block_dispatch=False) — the path where per-round host staging
    actually sits between device programs. Both arms share one compiled
    program; each arm runs once to warm, once measured."""
    import dataclasses

    from fedml_tpu.sim.engine import FedSim

    cfg = dataclasses.replace(
        cfg, comm_round=n_rounds, frequency_of_the_test=10_000,
        block_dispatch=False,
    )

    def rps(depth):
        sim = FedSim(trainer, train, test, dataclasses.replace(cfg, pipeline_depth=depth))
        sim.run()  # compile + warm
        t0 = time.perf_counter()
        _, hist = sim.run()
        return len(hist) / (time.perf_counter() - t0)

    return rps(None), rps(0)


TRACE_PROBE_ROUNDS = 40  # tracer-overhead probe length (pipelined LR rounds)


def bench_trace_overhead(n_rounds: int = TRACE_PROBE_ROUNDS):
    """Tracer-overhead probe (fedml_tpu/obs/trace.py): rounds/sec through
    the pipelined FedSim.run() loop with the process tracer installed vs
    the default no-op path, on a small LR config where host-side per-round
    overhead is the largest relative share (a heavy model would hide it).
    The disabled figure is the configuration every other bench number runs
    in — instrumentation with no tracer installed must cost ~nothing; the
    enabled overhead is the price of recording.

    The third arm probes the propagated wire context (docs/OBSERVABILITY.md
    "Cross-rank causal tracing") on the path the sim loop never touches — a
    loopback FedAvg run where an armed ``trace_wire`` stamps the context on
    every send leg: tracing-off vs context-off (tracer only) vs context-on
    (tracer + stamps). The stamp is one small header dict per message;
    context-on over context-off targets <= 3%. Returns probe metrics."""
    import numpy as np

    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs import trace
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    C, B, F, K, n_per = 16, 16, 32, 4, 64
    rng = np.random.RandomState(0)
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(C)}
    train = FederatedArrays(
        {"x": rng.rand(C * n_per, F).astype(np.float32),
         "y": rng.randint(0, K, C * n_per).astype(np.int32)},
        part,
    )
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=K),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=C, client_num_per_round=C, batch_size=B,
        comm_round=n_rounds, epochs=1, frequency_of_the_test=10_000,
        shuffle_each_round=False, seed=0, block_dispatch=False,
        pipeline_depth=1,
    )
    sim = FedSim(trainer, train, None, cfg)
    sim.run()  # compile + warm (shared by both arms: same programs)

    def rps(traced: bool):
        # best of 3 windows: host-dominated microbenchmark, so take the
        # least load-disturbed window (same policy as bench_stage_probe)
        best, tracer = 0.0, None
        for _trial in range(3):
            tracer = trace.install() if traced else None
            try:
                t0 = time.perf_counter()
                _, hist = sim.run()
                dt = time.perf_counter() - t0
            finally:
                if traced:
                    trace.uninstall()
            best = max(best, len(hist) / dt)
        return best, tracer

    disabled, _ = rps(False)
    enabled, tracer = rps(True)

    # propagated-context arm: loopback FedAvg, where every uplink/downlink
    # leg stamps MSG_ARG_KEY_TRACE_CTX once trace_wire is armed
    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )

    W, wire_rounds = 2, 6
    wpart = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(W)}
    wtrain = FederatedArrays(
        {"x": rng.rand(W * n_per, F).astype(np.float32),
         "y": rng.randint(0, K, W * n_per).astype(np.int32)},
        wpart,
    )

    def wire_rps(tracer_on: bool, ctx_on: bool) -> float:
        best = 0.0
        for _trial in range(3):
            if tracer_on:
                trace.install()
            try:
                t0 = time.perf_counter()
                run_distributed_fedavg_loopback(
                    trainer, wtrain, worker_num=W, round_num=wire_rounds,
                    batch_size=B, seed=0, trace_wire=ctx_on,
                )
                dt = time.perf_counter() - t0
            finally:
                if tracer_on:
                    trace.uninstall()
            best = max(best, wire_rounds / dt)
        return best

    wire_rps(False, False)  # compile + warm the wire-path programs
    wire_off = wire_rps(False, False)
    ctx_off = wire_rps(True, False)
    ctx_on = wire_rps(True, True)
    return {
        "trace_probe_rounds": n_rounds,
        "trace_disabled_rounds_per_sec": round(disabled, 3),
        "trace_enabled_rounds_per_sec": round(enabled, 3),
        "trace_enabled_overhead_pct": round(
            100.0 * (disabled - enabled) / disabled, 2
        ),
        "trace_events_per_round": round(len(tracer.events()) / n_rounds, 1),
        "trace_wire_probe_rounds": wire_rounds,
        "trace_wire_untraced_rounds_per_sec": round(wire_off, 3),
        "trace_ctx_off_rounds_per_sec": round(ctx_off, 3),
        "trace_ctx_on_rounds_per_sec": round(ctx_on, 3),
        "trace_ctx_overhead_pct": round(
            100.0 * (ctx_off - ctx_on) / ctx_off, 2
        ),
    }


PACK_CLIENTS = 256  # the packed-lane probe's Zipf cohort size
PACK_LANES = 16


def bench_pack_ab(n_rounds: int = 3):
    """Packed-vs-padded A/B (docs/PERFORMANCE.md "Packed-lane cohort
    execution") on a Zipf-partitioned 256-client full-participation cohort:
    the head client holds 64 steps of data, the median client one — the
    paper's non-IID shape, where the padded layout scans 256 x 64 steps and
    masks most of them. Reports rounds/sec through FedSim.run() for both
    modes plus each mode's padding-step fraction (fraction of scanned steps
    that are masked no-ops). Both arms run once to warm, once measured.
    Returns a dict of probe metrics."""
    import dataclasses

    import numpy as np

    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    C, B, F, K = PACK_CLIENTS, 16, 64, 16
    sizes = np.maximum((1024 / np.arange(1, C + 1) ** 1.1), 1).astype(int)
    rng = np.random.RandomState(0)
    n = int(sizes.sum())
    x = rng.rand(n, F).astype(np.float32)
    y = rng.randint(0, K, n).astype(np.int32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    part = {i: np.arange(bounds[i], bounds[i + 1]) for i in range(C)}
    train = FederatedArrays({"x": x, "y": y}, part)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=K),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=C, client_num_per_round=C, batch_size=B,
        comm_round=n_rounds, epochs=1, frequency_of_the_test=10_000,
        shuffle_each_round=False, seed=0, block_dispatch=False,
    )

    def rps(pack_lanes):
        sim = FedSim(trainer, train, None,
                     dataclasses.replace(cfg, pack_lanes=pack_lanes))
        sim.run()  # compile + warm
        t0 = time.perf_counter()
        _, hist = sim.run()
        return len(hist) / (time.perf_counter() - t0), sim

    packed_rps, packed_sim = rps(PACK_LANES)
    padded_rps, _ = rps(0)
    # padding-step fractions from the round-0 plan (full participation, no
    # shuffle: every round packs identically) — host-side planning only
    stats = packed_sim.pack_round_stats(0)
    return {
        "pack_zipf_clients": C,
        "pack_lanes": PACK_LANES,
        "pack_rounds_per_sec": round(packed_rps, 3),
        "padded_rounds_per_sec": round(padded_rps, 3),
        "pack_speedup": round(packed_rps / padded_rps, 2),
        "pack_n_passes": stats["n_passes"],
        "padding_step_frac_padded": round(
            1.0 - stats["total_steps"] / stats["padded_steps"], 4
        ),
        "padding_step_frac_packed": round(
            1.0 - stats["total_steps"] / stats["capacity"], 4
        ),
    }


BROADCAST_WORKERS = 8  # the broadcast A/B probe's fan-out width
BROADCAST_PAYLOAD_MB = 4.0


def bench_broadcast_ab(n_fanouts: int = 25):
    """Encode-once broadcast vs per-rank fan-out (docs/PERFORMANCE.md "The
    server wire path") at N=8 loopback receivers with a model-sized payload:
    arm A frames the message ONCE per fan-out (`broadcast_message`; shared
    payload buffer, per-receiver header patch), arm B replays the legacy
    per-rank `send_message` loop (one full serialization per receiver).
    Payload serializations are counted through the wire ledger
    (fedml_tpu.comm.message.wire_stats); queues are drained between fan-outs
    so memory, not backpressure, stays constant. Returns probe metrics."""
    import numpy as np

    from fedml_tpu.comm.loopback import LoopbackCommManager, LoopbackFabric
    from fedml_tpu.comm.message import Message, reset_wire_stats, wire_stats

    N = BROADCAST_WORKERS
    payload = np.random.RandomState(0).rand(
        int(BROADCAST_PAYLOAD_MB * (1 << 20) // 4)
    ).astype(np.float32)
    fabric = LoopbackFabric(N + 1)
    mgr = LoopbackCommManager(fabric, 0)
    receivers = list(range(1, N + 1))
    per_recv = {r: {"client_idx": r} for r in receivers}

    def drain():
        for r in receivers:
            q = fabric.queues[r]
            while not q.empty():
                q.get_nowait()

    def fanout_broadcast():
        msg = Message(2, 0, 1)
        msg.add_params("model_params", payload)
        mgr.broadcast_message(msg, receivers, per_receiver=per_recv)

    def fanout_per_rank():
        for r in receivers:
            msg = Message(2, 0, r)
            msg.add_params("model_params", payload)
            msg.add_params("client_idx", r)
            mgr.send_message(msg)

    out = {}
    for label, fanout in (("broadcast", fanout_broadcast),
                          ("per_rank", fanout_per_rank)):
        fanout(); drain()  # warm
        reset_wire_stats()
        t0 = time.perf_counter()
        for _ in range(n_fanouts):
            fanout()
            drain()
        dt = time.perf_counter() - t0
        out[f"{label}_fanouts_per_sec"] = round(n_fanouts / dt, 2)
        out[f"{label}_serializations_per_fanout"] = (
            wire_stats()["payload_serializations"] / n_fanouts
        )
    out.update({
        "broadcast_receivers": N,
        "broadcast_payload_mb": BROADCAST_PAYLOAD_MB,
        "broadcast_speedup": round(
            out["broadcast_fanouts_per_sec"] / out["per_rank_fanouts_per_sec"], 2
        ),
    })
    return out


def bench_downlink_ab(n_rounds: int = 4):
    """Dense vs delta+q8 downlink at an N=8 loopback fan-out
    (docs/COMPRESSION.md "Downlink delta coding"): arm A is today's dense
    model broadcast, arm B arms the downlink delta plane with the q8
    codec — each round close encodes the new global once against the
    previous emitted version and the fan-out serves encoded chains. The
    probe reports downlink bytes/round off the wire accountant (real
    encoded payload + descriptor bytes, not theory) and fan-out rounds/sec
    for both arms. Bytes reduction is a property of the codec and the
    model size, not of the platform."""
    import numpy as np
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )
    from fedml_tpu.compress import make_codec
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs import metrics as metricslib

    workers = BROADCAST_WORKERS
    # a model big enough that the chain descriptor amortizes (the bytes
    # claim is about model payloads; tiny fixtures are all descriptor)
    train, _ = gaussian_blobs(n_clients=workers, samples_per_client=24,
                              num_classes=4, dim=4096, seed=0)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.1), epochs=1,
    )

    def run(downlink):
        comm: dict = {}
        kwargs = {}
        if downlink:
            # ONE codec object for warm-up and timed run: the jitted
            # encode/decode programs are cached per codec instance
            kwargs = dict(downlink_codec=make_codec("q8"),
                          downlink_keyframe_every=64)
        # warm with the SAME arm config (compile + thread spinup — the
        # delta arm's one-time jit compile must not bill the timed window)
        run_distributed_fedavg_loopback(
            trainer, train, worker_num=workers, round_num=1, batch_size=8,
            **kwargs,
        )
        t0 = time.perf_counter()
        run_distributed_fedavg_loopback(
            trainer, train, worker_num=workers, round_num=n_rounds,
            batch_size=8, comm_stats=comm if downlink else None, **kwargs,
        )
        return n_rounds / (time.perf_counter() - t0), comm

    dense_rps, _ = run(False)
    delta_rps, comm = run(True)
    rounds = comm["rounds"]
    down = [r[metricslib.COMM_DOWNLINK_BYTES] for r in rounds]
    dense_equiv = [r[metricslib.COMM_DOWNLINK_DENSE_BYTES] for r in rounds]
    # steady state excludes the init keyframe (round 0's record carries it;
    # it amortizes over a real deployment's horizon)
    steady = [r[metricslib.COMM_DOWNLINK_RATIO] for r in rounds[1:]
              if metricslib.COMM_DOWNLINK_KEYFRAMES not in r]
    return {
        "downlink_dense_rounds_per_sec": round(dense_rps, 2),
        "downlink_delta_rounds_per_sec": round(delta_rps, 2),
        "downlink_bytes_per_round": int(np.mean(down)),
        "downlink_dense_bytes_per_round": int(np.mean(dense_equiv)),
        "downlink_ratio_total": round(sum(dense_equiv) / sum(down), 2),
        "downlink_ratio_steady_state": (
            round(float(np.mean(steady)), 2) if steady else None
        ),
        "downlink_workers": workers,
    }


def bench_robust_ab(n_rounds: int = 4):
    """Robust streaming vs plain streaming rounds/sec on the loopback
    message-passing path (docs/ROBUSTNESS.md): arm A folds each upload
    through the per-upload clip + seeded-DP defense
    (robust_distributed.RobustDistAggregator), arm B is the plain streaming
    tally — same workers, rounds, data, and arrival schedule. The defense
    adds one O(model) delta/norm pass per upload, so the acceptance target
    is robust within ~10% of plain. Returns probe metrics."""
    import numpy as np
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import run_distributed_fedavg_loopback
    from fedml_tpu.algorithms.robust_distributed import RobustDistConfig
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression

    workers = 4
    train, _ = gaussian_blobs(n_clients=workers, samples_per_client=64,
                              num_classes=4, seed=0)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    defense = RobustDistConfig(rule="mean", norm_bound=0.5, dp_stddev=0.01)

    def run(robust_config):
        run_distributed_fedavg_loopback(  # warm (compile + thread spinup)
            trainer, train, worker_num=workers, round_num=1, batch_size=16,
            robust_config=robust_config,
        )
        t0 = time.perf_counter()
        run_distributed_fedavg_loopback(
            trainer, train, worker_num=workers, round_num=n_rounds,
            batch_size=16, robust_config=robust_config,
        )
        return n_rounds / (time.perf_counter() - t0)

    plain_rps, robust_rps = run(None), run(defense)
    return {
        "robust_rounds_per_sec": round(robust_rps, 2),
        "robust_plain_rounds_per_sec": round(plain_rps, 2),
        "robust_overhead_frac": round(1.0 - robust_rps / plain_rps, 4),
        "robust_workers": workers,
    }


def bench_ft_overhead(n_rounds: int = 4):
    """Fault-tolerance overhead A/B (docs/ROBUSTNESS.md "Failure
    recovery"): loopback message-passing rounds/sec with the full recovery
    stack ON — per-client heartbeat threads, a retry policy armed on every
    rank's send plane, and per-round server state checkpointing — vs plain
    streaming. Fault-free, so retries never fire; the stack's cost is the
    heartbeat traffic plus one O(model) state snapshot per round close.
    Acceptance target: within ~10% of plain. Returns probe metrics."""
    import shutil
    import tempfile

    import optax

    from fedml_tpu.algorithms.fedavg_distributed import run_distributed_fedavg_loopback
    from fedml_tpu.comm.retry import RetryPolicy
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression

    workers = 4
    train, _ = gaussian_blobs(n_clients=workers, samples_per_client=64,
                              num_classes=4, seed=0)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.1), epochs=1,
    )

    def run(**kw):
        run_distributed_fedavg_loopback(  # warm (compile + thread spinup)
            trainer, train, worker_num=workers, round_num=1, batch_size=16,
            **kw,
        )
        t0 = time.perf_counter()
        run_distributed_fedavg_loopback(
            trainer, train, worker_num=workers, round_num=n_rounds,
            batch_size=16, **kw,
        )
        return n_rounds / (time.perf_counter() - t0)

    plain_rps = run()
    ckpt = tempfile.mkdtemp(prefix="bench_ft_ckpt_")
    try:
        ft_rps = run(
            retry_policy=RetryPolicy(max_attempts=3, base_delay=0.01),
            heartbeat_interval=0.05,
            checkpoint_dir=ckpt, checkpoint_every=1,
        )
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    return {
        "ft_rounds_per_sec": round(ft_rps, 2),
        "ft_plain_rounds_per_sec": round(plain_rps, 2),
        "ft_overhead_frac": round(1.0 - ft_rps / plain_rps, 4),
        "ft_workers": workers,
    }


def bench_fleet_overhead(n_rounds: int = 6):
    """Fleet telemetry A/B (docs/OBSERVABILITY.md "Fleet telemetry"):
    loopback message-passing rounds/sec with --fleet_stats ON — process
    registry installed, clients timing + piggybacking per-upload telemetry
    reports, the server folding them into the per-rank health view and
    flushing a fleet snapshot per round — vs plain. Telemetry is read-only
    (models bit-identical, tools/fleet_smoke.py), so this probe is its
    whole cost story. Acceptance target: <= 3% rounds/sec overhead on the
    loopback LR probe. Returns probe metrics."""
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import run_distributed_fedavg_loopback
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression

    workers = 4
    train, _ = gaussian_blobs(n_clients=workers, samples_per_client=64,
                              num_classes=4, seed=0)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.1), epochs=1,
    )

    def run(**kw):
        t0 = time.perf_counter()
        run_distributed_fedavg_loopback(
            trainer, train, worker_num=workers, round_num=n_rounds,
            batch_size=16, **kw,
        )
        return n_rounds / (time.perf_counter() - t0)

    run()  # warm (compile + thread spinup), shared by both arms
    # interleaved ABAB with best-of-passes per arm: a lone A-then-B
    # measurement on a loaded CPU host systematically favors whichever arm
    # runs later
    plain_a, fleet_a = run(), run(fleet_stats={})
    plain_rps = max(plain_a, run())
    fleet_rps = max(fleet_a, run(fleet_stats={}))
    return {
        "fleet_rounds_per_sec": round(fleet_rps, 2),
        "fleet_plain_rounds_per_sec": round(plain_rps, 2),
        "fleet_overhead_frac": round(1.0 - fleet_rps / plain_rps, 4),
        "fleet_workers": workers,
    }


def bench_multijob(n_rounds: int = 3):
    """Multi-tenant co-scheduling A/B (docs/MULTITENANCY.md): the 8
    heterogeneous federation jobs of tests/test_tenancy.py (mixed worker
    counts, uplink codecs, robust defenses, downlink delta coding)
    co-scheduled over ONE shared wire/send pool (tenancy.run_multi_job) vs
    the same jobs run solo back-to-back.

    Reports aggregate uploads/sec co-scheduled vs the isolated runs'
    aggregate uploads/sec (total uploads / summed solo wall time — what
    the 8 runs achieve back-to-back on the same machine; acceptance
    target: ratio >= 0.8, i.e. sharing one plane costs at most ~20% vs
    running the tenants serially — in practice concurrency puts it above
    1). The sum of the isolated RATES also lands in the metrics for
    context, but it is not the bar: each solo run already saturates the
    device via XLA intra-op parallelism, so N co-scheduled jobs cannot
    reach N saturated machines' worth of rate. Also reports the per-job
    fairness spread: max/min over jobs of the job's co-scheduled-vs-solo
    slowdown (1.0 = perfectly even sharing; a large spread means the
    scheduler favored somebody). Returns probe metrics."""
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )
    from fedml_tpu.algorithms.robust_distributed import RobustDistConfig
    from fedml_tpu.compress import make_codec
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.tenancy import JobSpec, run_multi_job

    # (job_id, worker_num, num_classes, seed, run_kwargs factory) — the
    # tier-1 bit-identity matrix, reused here for the throughput story
    matrix = [
        ("plain-a", 2, 4, 1, dict),
        ("plain-b", 3, 3, 2, dict),
        ("bf16", 2, 4, 3, lambda: {"codec": make_codec("bf16")}),
        ("topk", 2, 4, 4, lambda: {"codec": make_codec("topk",
                                                       topk_frac=0.5)}),
        ("robust", 2, 4, 5, lambda: {
            "robust_config": RobustDistConfig(rule="median")}),
        ("robust-dp", 2, 3, 6, lambda: {
            "robust_config": RobustDistConfig(rule="mean", norm_bound=0.5,
                                              dp_stddev=0.01, dp_seed=2)}),
        ("downlink", 2, 4, 7, lambda: {"downlink_codec": "q8"}),
        ("lr-tiny", 2, 2, 8, dict),
    ]

    def build(jid, w, nc, seed):
        train, _ = gaussian_blobs(n_clients=w, samples_per_client=32,
                                  num_classes=nc, seed=seed)
        trainer = ClientTrainer(
            module=LogisticRegression(num_classes=nc),
            optimizer=optax.sgd(0.1), epochs=1,
        )
        return trainer, train

    data = {jid: build(jid, w, nc, seed) for jid, w, nc, seed, _ in matrix}
    uploads = {jid: w * n_rounds for jid, w, nc, seed, _ in matrix}

    # -- solo arm: each job isolated on its own fabric -------------------
    solo_t: dict[str, float] = {}
    for jid, w, nc, seed, kw in matrix:
        trainer, train = data[jid]
        run_distributed_fedavg_loopback(  # warm (compile + thread spinup)
            trainer, train, worker_num=w, round_num=1, batch_size=8,
            seed=seed, **kw(),
        )
        t0 = time.perf_counter()
        run_distributed_fedavg_loopback(
            trainer, train, worker_num=w, round_num=n_rounds, batch_size=8,
            seed=seed, **kw(),
        )
        solo_t[jid] = time.perf_counter() - t0

    # -- multi arm: all 8 co-scheduled on one wire/pool ------------------
    def specs(rounds, done_at=None):
        out = []
        for jid, w, nc, seed, kw in matrix:
            trainer, train = data[jid]
            on_round = None
            if done_at is not None:
                # the job's completion time is its LAST round's callback
                on_round = (lambda r, v, j=jid:
                            done_at.__setitem__(j, time.perf_counter()))
            out.append(JobSpec(
                trainer=trainer, train_data=train, worker_num=w,
                round_num=rounds, batch_size=8, job_id=jid, seed=seed,
                on_round=on_round, run_kwargs=kw()))
        return out

    run_multi_job(specs(1), join_timeout=300)  # warm the shared plane
    done_at: dict[str, float] = {}
    t0 = time.perf_counter()
    results = run_multi_job(specs(n_rounds, done_at), join_timeout=300)
    t_multi = time.perf_counter() - t0
    failed = [n for n, r in results.items() if not r.ok]
    if failed:
        raise RuntimeError(f"multijob probe jobs failed: {failed}")

    total_uploads = sum(uploads.values())
    agg_ups = total_uploads / t_multi
    solo_agg_ups = total_uploads / sum(solo_t.values())
    solo_sum_rates = sum(uploads[j] / t for j, t in solo_t.items())
    slowdowns = {j: (done_at[j] - t0) / solo_t[j] for j in solo_t}
    return {
        "multijob_jobs": len(matrix),
        "multijob_agg_uploads_per_sec": round(agg_ups, 2),
        "multijob_solo_agg_uploads_per_sec": round(solo_agg_ups, 2),
        "multijob_solo_sum_rates_uploads_per_sec": round(solo_sum_rates, 2),
        "multijob_uploads_ratio": round(agg_ups / solo_agg_ups, 4),
        "multijob_fairness_spread": round(
            max(slowdowns.values()) / min(slowdowns.values()), 4),
    }


POP_CLIENTS = 128  # the population probe's Zipf cohort size
POP_SPEC = "speed=lognormal:0,0.6;dropout=0.1"
POP_WIRE_SPEC = "speed=lognormal:0,0.6;jitter=uniform:0.01,0.35"


def bench_population_ab(n_rounds: int = 3):
    """Heterogeneous-population A/B (docs/PERFORMANCE.md "Heterogeneous
    populations"), two arms sharing one population realization:

    1. **Packed-lane win preserved under heterogeneity**: the Zipf-data
       cohort of bench_pack_ab, but with a lognormal speed model truncating
       budgets and 10% mid-round dropout — the packer bins by PREDICTED
       steps and re-packs dropped lanes into overflow passes. Reports
       packed vs padded rounds/sec through FedSim.run() (bit-identical
       results, tools/population_smoke.py).
    2. **Sync vs async time-to-accuracy under the same trace**: a loopback
       run whose per-rank upload delays come from the population's
       jitter/speed draws (population/wire.py) — the sync barrier waits for
       the population's stragglers every round, the buffered-async server
       emits on its buffer goal. Reports wall seconds and final pooled
       accuracy per arm.
    Returns probe metrics for ``extra``."""
    import dataclasses

    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )
    from fedml_tpu.core import scan as scanlib
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.population import population_fault_specs
    from fedml_tpu.sim.cohort import FederatedArrays, batch_array
    from fedml_tpu.sim.engine import FedSim, SimConfig

    # -- arm 1: packed vs padded under churn (sim) -------------------------
    C, B, F, K = POP_CLIENTS, 16, 64, 16
    sizes = np.maximum((1024 / np.arange(1, C + 1) ** 1.1), 1).astype(int)
    rng = np.random.RandomState(0)
    n = int(sizes.sum())
    x = rng.rand(n, F).astype(np.float32)
    y = rng.randint(0, K, n).astype(np.int32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    part = {i: np.arange(bounds[i], bounds[i + 1]) for i in range(C)}
    train = FederatedArrays({"x": x, "y": y}, part)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=K),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=C, client_num_per_round=C, batch_size=B,
        comm_round=n_rounds, epochs=1, frequency_of_the_test=10_000,
        shuffle_each_round=False, seed=0, block_dispatch=False,
        population=POP_SPEC,
    )

    def rps(pack_lanes):
        sim = FedSim(trainer, train, None,
                     dataclasses.replace(cfg, pack_lanes=pack_lanes))
        sim.run()  # compile + warm
        t0 = time.perf_counter()
        _, hist = sim.run()
        return len(hist) / (time.perf_counter() - t0), sim

    packed_rps, packed_sim = rps(PACK_LANES)
    padded_rps, _ = rps(0)
    stats = packed_sim.pack_round_stats(0)
    out = {
        "pop_pack_clients": C,
        "pop_spec": POP_SPEC,
        "pop_pack_rounds_per_sec": round(packed_rps, 3),
        "pop_padded_rounds_per_sec": round(padded_rps, 3),
        "pop_pack_speedup": round(packed_rps / padded_rps, 2),
        "pop_pack_n_passes": stats["n_passes"],
        "pop_padding_step_frac_packed": round(
            1.0 - stats["total_steps"] / stats["capacity"], 4
        ),
    }

    # -- arm 2: sync vs async time-to-accuracy under the same trace --------
    workers = 8
    wtrain, _ = gaussian_blobs(n_clients=workers, samples_per_client=48,
                               num_classes=4, seed=0)
    wtrainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    adapter = population_fault_specs(POP_WIRE_SPEC, workers, seed=0)
    pooled = batch_array(
        {k: np.concatenate([v[wtrain.partition[i]] for i in range(workers)])
         for k, v in wtrain.arrays.items()},
        64,
    )
    pooled = jax.tree.map(jnp.asarray, pooled)

    @jax.jit
    def acc_of(variables):
        def step(c, b):
            return c, wtrainer.eval_batch(variables, b)

        _, m = scanlib.scan(step, 0, pooled)
        s = jax.tree.map(lambda v: jnp.sum(v, 0), m)
        return s["test_correct"] / jnp.maximum(s["test_total"], 1.0)

    def timed_arm(**kw):
        run_distributed_fedavg_loopback(  # warm: compile + thread spinup
            wtrainer, wtrain, worker_num=workers, round_num=1, batch_size=8,
            **{k: v for k, v in kw.items() if k != "population"},
        )
        t0 = time.perf_counter()
        final = run_distributed_fedavg_loopback(
            wtrainer, wtrain, worker_num=workers, round_num=n_rounds,
            batch_size=8, population=adapter, **kw,
        )
        return time.perf_counter() - t0, float(acc_of(final))

    sync_s, sync_acc = timed_arm()
    async_s, async_acc = timed_arm(
        server_mode="async", buffer_goal=workers // 2,
    )
    out.update({
        "pop_wire_spec": POP_WIRE_SPEC,
        "pop_wire_workers": workers,
        "pop_sync_wall_s": round(sync_s, 3),
        "pop_sync_acc": round(sync_acc, 4),
        "pop_async_wall_s": round(async_s, 3),
        "pop_async_acc": round(async_acc, 4),
        "pop_async_speedup": round(sync_s / async_s, 2),
    })
    return out


def bench_async_ab(n_rounds: int = 3):
    """Barrier-free server A/B (docs/PERFORMANCE.md "Barrier-free
    aggregation"): loopback uploads/sec and models-emitted/sec for the
    three server execution modes at fan-in 4 and 16 — sync round barrier,
    buffered-async (buffer_goal = fan-in/2, so two model versions emit per
    sync-round's worth of uploads), and a 2-tier aggregation tree
    (sqrt(fan-in) edges x sqrt(fan-in) clients). The headline is
    uploads/sec SCALING WITH TREE FAN-IN: the root folds O(tiers)
    partials, not O(clients) models. Returns probe metrics for ``extra``."""
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )
    from fedml_tpu.async_agg.tree import run_tree_fedavg_loopback
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs import metrics as metricslib

    out = {}
    tree_shapes = {4: (2, 2), 16: (4, 4)}
    for fan_in in (4, 16):
        workers = fan_in
        train, _ = gaussian_blobs(n_clients=workers, samples_per_client=24,
                                  num_classes=4, seed=0)
        trainer = ClientTrainer(
            module=LogisticRegression(num_classes=4),
            optimizer=optax.sgd(0.1), epochs=1,
        )

        def timed(fn):
            fn()  # warm: compile + thread spinup
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0

        dt = timed(lambda: run_distributed_fedavg_loopback(
            trainer, train, worker_num=workers, round_num=n_rounds,
            batch_size=8,
        ))
        out[f"async_f{fan_in}_sync_uploads_per_sec"] = round(
            n_rounds * workers / dt, 1)
        out[f"async_f{fan_in}_sync_models_per_sec"] = round(n_rounds / dt, 2)

        stats: dict = {}

        def run_async():
            stats.clear()
            return run_distributed_fedavg_loopback(
                trainer, train, worker_num=workers, round_num=n_rounds,
                batch_size=8, server_mode="async",
                buffer_goal=max(1, workers // 2), async_stats=stats,
            )

        dt = timed(run_async)
        uploads = sum(r[metricslib.ASYNC_ARRIVALS]
                      for r in stats.get("rounds", []))
        out[f"async_f{fan_in}_async_uploads_per_sec"] = round(uploads / dt, 1)
        out[f"async_f{fan_in}_async_models_per_sec"] = round(
            stats["totals"][metricslib.ASYNC_MODELS_EMITTED] / dt, 2)

        dt = timed(lambda: run_tree_fedavg_loopback(
            trainer, train, tree_shapes[fan_in], n_rounds, 8,
        ))
        out[f"async_f{fan_in}_tree_uploads_per_sec"] = round(
            n_rounds * workers / dt, 1)
        out[f"async_f{fan_in}_tree_models_per_sec"] = round(n_rounds / dt, 2)

    # 3-tier async cascade arms (async_agg/cascade.py): synthesized leaf
    # uploads through REAL barrier-free edge tiers at fan-in 4/16/32. The
    # headline columns: uploads/sec scaling with fan-in (fan^3 leaves per
    # round through the same per-tier code path), interior tier-to-tier
    # bytes raw-f64 vs q8-encoded (the >=4x bar), and the per-tier
    # peak-resident-state-vs-model-size probe (O(model) per tier, not
    # O(children)) plus the process RSS delta after warmup.
    from fedml_tpu.async_agg.cascade import run_cascade

    model_size = 1000
    out["cascade_model_bytes"] = model_size * 4
    for fan in (4, 16, 32):
        rep = run_cascade((fan, fan, fan), rounds=2, model_size=model_size,
                          buffer_goal=fan, tier_staleness="const")
        out[f"cascade_f{fan}_uploads_per_sec"] = round(rep.uploads_per_s, 1)
        out[f"cascade_f{fan}_interior_raw_bytes"] = rep.interior_dense_bytes
        out[f"cascade_f{fan}_tier_state_bytes"] = rep.max_tier_state_bytes
        out[f"cascade_f{fan}_state_per_model"] = round(
            rep.max_tier_state_bytes / (model_size * 4), 2)
        out[f"cascade_f{fan}_rss_delta_kb"] = rep.rss_delta_kb
        enc = run_cascade((fan, fan, fan), rounds=2, model_size=model_size,
                          buffer_goal=fan, tier_uplink_codec="q8")
        out[f"cascade_f{fan}_interior_enc_bytes"] = enc.interior_uplink_bytes
        out[f"cascade_f{fan}_interior_ratio"] = round(
            enc.interior_dense_bytes / max(enc.interior_uplink_bytes, 1), 2)
    return out


def bench_fold_ab(n_rounds: int = 2):
    """Sharded fold plane A/B (docs/PERFORMANCE.md "The server fold
    plane"): 16-client loopback fan-in with an ~8 MB dense payload and a
    no-op local train, so the round is the SERVER's fold throughput, not
    client compute. Reports uploads/sec and the upload-handler p99 with
    the plane off vs on (4 chunk workers). The speedup assertions
    (>= 2.5x uploads/sec, >= 5x handler-p99 drop) only arm on hosts with
    >= 4 cores — thread parallelism cannot pay for itself without them,
    so a single-core container just reports the numbers."""
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import (
        FedAvgClientManager,
        MyMessage,
        run_distributed_fedavg_loopback,
    )
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.obs import trace

    workers = 16
    dim, classes = 32768, 64  # (dim+1) x classes f32 params ~= 8.0 MB
    train, _ = gaussian_blobs(n_clients=workers, samples_per_client=2,
                              num_classes=classes, dim=dim, seed=0)
    trainer = ClientTrainer(module=LogisticRegression(num_classes=classes),
                            optimizer=optax.sgd(0.1), epochs=1)

    def no_train(variables, batches, key):
        return variables, None

    def client_cls(rank):
        def make(comm, r, size, tr, data, bs, tmpl):
            return FedAvgClientManager(comm, r, size, tr, data, bs, tmpl,
                                       local_train_fn=no_train)

        return make

    def run(**kw):
        tracer = trace.install(trace.Tracer())
        try:
            t0 = time.perf_counter()
            run_distributed_fedavg_loopback(
                trainer, train, worker_num=workers, round_num=n_rounds,
                batch_size=2, client_cls_for_rank=client_cls, **kw,
            )
            dt = time.perf_counter() - t0
        finally:
            trace.uninstall()
        # upload-handler wall time only: the sync fan-out and init legs
        # share the span name but not the bottleneck under test
        handler_ms = sorted(
            e["dur"] / 1e3 for e in tracer.events()
            if e["name"] == "comm/handler" and e.get("ph") == "X"
            and e.get("args", {}).get("msg_type")
            == MyMessage.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER
        )
        p99 = (handler_ms[min(len(handler_ms) - 1,
                              int(0.99 * len(handler_ms)))]
               if handler_ms else 0.0)
        return n_rounds * workers / dt, p99

    run()  # warm: thread spinup, allocator, loopback queues
    serial_ups, serial_p99 = run()
    run(fold_workers=4)
    plane_ups, plane_p99 = run(fold_workers=4)
    out = {
        "fold_payload_bytes": (dim + 1) * classes * 4,
        "fold_serial_uploads_per_sec": round(serial_ups, 1),
        "fold_plane_uploads_per_sec": round(plane_ups, 1),
        "fold_uploads_speedup": round(plane_ups / max(serial_ups, 1e-9), 2),
        "fold_serial_handler_p99_ms": round(serial_p99, 2),
        "fold_plane_handler_p99_ms": round(plane_p99, 2),
        "fold_handler_p99_drop": round(serial_p99 / max(plane_p99, 1e-9), 1),
    }
    cores = os.cpu_count() or 1
    if cores >= 4:
        assert out["fold_uploads_speedup"] >= 2.5, out
        assert out["fold_handler_p99_drop"] >= 5.0, out
    else:
        out["fold_gate"] = (
            f"cpu_count={cores} < 4: speedup assertions skipped (chunk "
            "workers need cores to beat the serial fold)"
        )
    return out


def bench_shard_ab(peak_tflops):
    """Sharded-client-model A/B (docs/PERFORMANCE.md "Sharded client
    models"): the benched LM round with the client model tensor-parallel
    over a (1, n_devices) mesh (``shard_rules="transformer_tp"``) vs the
    unsharded program, reporting ``shard_mfu`` against the chip peak — the
    probe targeting MFU >= 0.55 on the benched LM path. On a single chip
    there is no model axis to win on: the probe reports ``shard_skipped``."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"shard_skipped":
                f"needs >= 2 devices for a model axis, have {n_dev}"}

    import dataclasses

    from fedml_tpu.sim.engine import FedSim

    # Both arms use the xla attention path: the pallas flash kernel is an
    # opaque custom call to the SPMD partitioner, so under TP it would run
    # on gathered heads — measuring it would judge the 0.55 target on the
    # pairing docs/PERFORMANCE.md explicitly warns against. Keeping the
    # arms symmetric keeps the A/B honest; the flash unsharded figure is
    # bench_lm's headline number.
    trainer, train, cfg = _build_lm_sim(attn_impl="xla")
    sec_unsharded = _measure_rounds(FedSim(trainer, train, None, cfg),
                                    n_meas=3)
    sec_sharded = _measure_rounds(
        FedSim(trainer, train, None, dataclasses.replace(
            cfg, mesh_shape=(1, n_dev), shard_rules="transformer_tp")),
        n_meas=3,
    )
    flops = lm_train_flops_per_round()
    out = {
        "shard_mesh": [1, n_dev],
        "shard_rules": "transformer_tp",
        "shard_attn_impl": "xla",
        "shard_lm_sec_per_round": round(sec_sharded, 4),
        "unsharded_lm_sec_per_round": round(sec_unsharded, 4),
        "shard_lm_delivered_tflops": round(flops / sec_sharded / 1e12, 2),
    }
    # sharded MFU counts the n_dev-chip aggregate peak — the number
    # that says the sharded program uses the WHOLE mesh well
    out["shard_mfu"] = round(
        flops / sec_sharded / 1e12 / (peak_tflops * n_dev), 4)
    out["shard_mfu_target"] = 0.55
    return out


PACK_SHARD_LANES = 8  # lanes for the pack x shard A/B


def bench_pack_shard_ab(n_rounds: int = 2):
    """Three-arm rounds/sec for packed lanes composed with sharded plans
    (docs/PERFORMANCE.md "Packed lanes on sharded plans") on a Zipf-256
    TransformerLM cohort — the paper's non-IID shape, where the padded
    layout scans 256 x head-client steps and masks most of them:

    - packed x sharded: ``pack_lanes`` on a (2, model) fsdp mesh
    - packed x unsharded: the same lanes on a 2-device client mesh
      (isolates what the model axis costs the packed program)
    - padded x sharded: the same fsdp mesh without lanes (isolates what
      packing buys once the plan is sharded)

    Both attention arms stay on the xla path for symmetry (the flash
    kernel's per-rank shard_map wrap is exercised by the smoke and the TP
    tests; mixing it into one arm only would skew the A/B). Returns a dict
    of probe metrics."""
    import dataclasses

    import numpy as np

    import jax
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.parallel.mesh import client_mesh
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    devices = jax.devices()
    n_dev = len(devices)
    if n_dev < 4:
        return {"pack_shard_skipped":
                f"needs >= 4 devices for a (2, n) mesh, have {n_dev}"}
    mesh_shape = (2, n_dev // 2)

    C, B, V, T, D, H, L = PACK_CLIENTS, 16, 64, 16, 32, 2, 2
    sizes = np.maximum((256 / np.arange(1, C + 1) ** 1.1), 1).astype(int)
    rng = np.random.RandomState(0)
    n = int(sizes.sum())
    x = rng.randint(0, V, (n, T)).astype(np.int32)
    y = rng.randint(0, V, (n, T)).astype(np.int32)
    mask = np.ones((n, T), np.float32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    part = {i: np.arange(bounds[i], bounds[i + 1]) for i in range(C)}
    train = FederatedArrays({"x": x, "y": y, "mask": mask}, part)
    trainer = ClientTrainer(
        module=TransformerLM(vocab_size=V, embed_dim=D, num_layers=L,
                             num_heads=H, max_len=T, attn_impl="xla"),
        task="nwp",
        optimizer=optax.sgd(0.1), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=C, client_num_per_round=C, batch_size=B,
        comm_round=n_rounds, epochs=1, frequency_of_the_test=10_000,
        shuffle_each_round=False, seed=0, block_dispatch=False,
    )

    def rps(c, mesh=None):
        sim = FedSim(trainer, train, None, c, mesh=mesh)
        sim.run()  # compile + warm
        t0 = time.perf_counter()
        _, hist = sim.run()
        return len(hist) / (time.perf_counter() - t0), sim

    shard_cfg = dataclasses.replace(
        cfg, mesh_shape=mesh_shape, shard_rules="transformer_fsdp")
    ps_rps, ps_sim = rps(dataclasses.replace(
        shard_cfg, pack_lanes=PACK_SHARD_LANES))
    pu_rps, _ = rps(dataclasses.replace(cfg, pack_lanes=PACK_SHARD_LANES),
                    mesh=client_mesh(devices[:2]))
    pad_rps, _ = rps(shard_cfg)
    stats = ps_sim.pack_round_stats(0)
    return {
        "pack_shard_mesh": list(mesh_shape),
        "pack_shard_rules": "transformer_fsdp",
        "pack_shard_zipf_clients": C,
        "pack_shard_lanes": PACK_SHARD_LANES,
        "pack_shard_rounds_per_sec": round(ps_rps, 3),
        "pack_unsharded_rounds_per_sec": round(pu_rps, 3),
        "padded_shard_rounds_per_sec": round(pad_rps, 3),
        "pack_shard_speedup_vs_padded": round(ps_rps / pad_rps, 2),
        "pack_shard_n_passes": stats["n_passes"],
    }


def bench_resnet():
    """(rounds/sec, eval examples/sec, pipeline extras) for the primary
    ResNet-56 config."""
    import numpy as np

    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.resnet import resnet56
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    rng = np.random.RandomState(0)
    n_per = STEPS * BATCH
    n = CLIENTS * n_per
    x = rng.rand(n, 32, 32, 3).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(CLIENTS)}
    train = FederatedArrays({"x": x, "y": y}, part)

    trainer = ClientTrainer(
        module=resnet56(class_num=10),
        optimizer=optax.sgd(0.1, momentum=0.9),
        epochs=EPOCHS,
    )
    cfg = SimConfig(
        client_num_in_total=CLIENTS, client_num_per_round=CLIENTS,
        batch_size=BATCH, comm_round=1, epochs=EPOCHS,
        frequency_of_the_test=10_000, shuffle_each_round=False, seed=0,
    )
    n_eval = 4096
    test = {
        "x": rng.rand(n_eval, 32, 32, 3).astype(np.float32),
        "y": rng.randint(0, 10, n_eval).astype(np.int32),
    }
    # PRIMARY: bf16 compute (f32 params) with block dispatch (10 rounds per
    # device round-trip) — the TPU-first numerics and deployment dispatch
    import jax.numpy as jnp

    trainer_bf16 = ClientTrainer(
        module=resnet56(class_num=10, dtype=jnp.bfloat16),
        optimizer=optax.sgd(0.1, momentum=0.9),
        epochs=EPOCHS,
    )
    sec_per_round = _measure_rounds(
        FedSim(trainer_bf16, train, test, cfg), n_meas=3, block=10
    )
    # secondaries: f32 block-dispatch (BENCH_r02 continuity) + bf16
    # single-dispatch (per-round host sync)
    sec_per_round_f32 = _measure_rounds(
        FedSim(trainer, train, test, cfg), n_meas=3, block=10
    )
    sec_per_round_single = _measure_rounds(
        FedSim(trainer_bf16, train, test, cfg), n_meas=5, block=1
    )
    sim = FedSim(trainer, train, test, cfg)

    # pooled eval throughput (examples/sec): evaluate() runs the pooled train
    # set (n) plus the test set (n_eval) and returns host floats, so it is
    # synchronous by construction. Measured over 3 trials after a warm-up:
    # on the earlier machine eval throughput ramped with recent dispatch
    # activity (14k ex/s cold vs 19.7k after sustained work, 2026-07-31);
    # not re-measured on this one. The PRIMARY figure is the median trial
    # (steady state); the best trial stays in extra.
    variables = sim.init_round_variables()
    sim.evaluate(variables)  # compile
    for _ in range(2):
        sim.evaluate(variables)  # ramp
    trials = []
    for _trial in range(3):
        t0 = time.perf_counter()
        for _ in range(3):
            sim.evaluate(variables)
        trials.append((n + n_eval) * 3 / (time.perf_counter() - t0))
    eval_eps = sorted(trials)[len(trials) // 2]
    # pipelined-driver A-B (bf16, single-round dispatch — the path where
    # host staging sits between device programs)
    pipe_on, pipe_off = bench_pipeline_ab(trainer_bf16, train, test, cfg, 10)
    pipeline_extra = {
        "pipeline_on_rounds_per_sec": round(pipe_on, 3),
        "pipeline_off_rounds_per_sec": round(pipe_off, 3),
    }
    return (1.0 / sec_per_round, 1.0 / sec_per_round_single,
            1.0 / sec_per_round_f32, eval_eps, max(trials), pipeline_extra)


def bench_compress_probe():
    """Uplink-compression probe (fedml_tpu/compress, docs/COMPRESSION.md):
    topk-1% encode of the bench ResNet-56 variables pytree. The byte counts
    are static shape/dtype arithmetic; the timing is the jitted encode
    wall-clock, ending in a host fetch of a value plane. Returns
    (dense_bytes, encoded_bytes, encode_ms)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.compress import make_codec
    from fedml_tpu.compress.codec import tree_bytes
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.resnet import resnet56

    trainer = ClientTrainer(
        module=resnet56(class_num=10), optimizer=optax.sgd(0.1), epochs=1
    )
    sample = {
        "x": jnp.zeros((1, 32, 32, 3), jnp.float32),
        "y": jnp.zeros((1,), jnp.int32),
        "mask": jnp.ones((1,), jnp.float32),
    }
    variables = trainer.init(jax.random.key(0), sample)
    codec = make_codec("topk", topk_frac=0.01)
    enc_fn = jax.jit(codec.encode)

    def run():
        enc = enc_fn(variables, jax.random.key(1))
        np.asarray(jax.tree_util.tree_leaves(enc.planes["values"])[0])
        return enc

    run()  # compile
    t0 = time.perf_counter()
    enc = run()
    ms = (time.perf_counter() - t0) * 1e3
    return tree_bytes(variables), enc.nbytes, ms


def bench_conv_probe():
    """Delivered TFLOP/s for MXU-filling conv shapes on the SAME federated
    engine path as the ResNet bench (256-channel 3x3 convs, bf16)."""
    import numpy as np

    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    class WideConvNet(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            for _ in range(CP_LAYERS):
                x = nn.relu(nn.Conv(CP_C, (3, 3), padding="SAME",
                                    dtype=jnp.bfloat16)(x))
            return nn.Dense(10)(x.mean(axis=(1, 2)).astype(jnp.float32))

    rng = np.random.RandomState(0)
    n_per = CP_STEPS * CP_BATCH
    n = CP_CLIENTS * n_per
    x = rng.rand(n, CP_HW, CP_HW, 3).astype(np.float32)
    y = rng.randint(0, 10, n).astype(np.int32)
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(CP_CLIENTS)}
    train = FederatedArrays({"x": x, "y": y}, part)
    trainer = ClientTrainer(
        module=WideConvNet(), optimizer=optax.sgd(0.1, momentum=0.9), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=CP_CLIENTS, client_num_per_round=CP_CLIENTS,
        batch_size=CP_BATCH, comm_round=1, epochs=1,
        frequency_of_the_test=10_000, shuffle_each_round=False, seed=0,
    )
    sec = _measure_rounds(FedSim(trainer, train, None, cfg), n_meas=3)
    flops = conv_probe_flops_per_image() * CP_CLIENTS * CP_STEPS * CP_BATCH
    return flops / sec / 1e12


def _build_lm_sim(attn_impl: str = LM_ATTN):
    """The ONE construction of the benched federated LM problem —
    (trainer, train_data, SimConfig) at the bench shape — shared by
    bench_lm and the shard A/B so the arms can never desynchronize."""
    import numpy as np

    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    rng = np.random.RandomState(0)
    n_per = LM_STEPS * LM_BATCH
    n = LM_CLIENTS * n_per
    x = rng.randint(0, LM_V, (n, LM_T)).astype(np.int32)
    y = rng.randint(0, LM_V, (n, LM_T)).astype(np.int32)
    mask = np.ones((n, LM_T), np.float32)
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(LM_CLIENTS)}
    train = FederatedArrays({"x": x, "y": y, "mask": mask}, part)

    model = TransformerLM(
        vocab_size=LM_V, embed_dim=LM_D, num_layers=LM_L, num_heads=LM_H,
        max_len=LM_T, attn_impl=attn_impl, dtype=jnp.bfloat16,
    )
    trainer = ClientTrainer(
        module=model, task="nwp", optimizer=optax.sgd(0.01, momentum=0.9), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=LM_CLIENTS, client_num_per_round=LM_CLIENTS,
        batch_size=LM_BATCH, comm_round=1, epochs=1,
        frequency_of_the_test=10_000, shuffle_each_round=False, seed=0,
        cohort_execution=LM_COHORT,
    )
    return trainer, train, cfg


def bench_lm():
    """Seconds/round for the big-shape bf16 federated LM config."""
    from fedml_tpu.sim.engine import FedSim

    trainer, train, cfg = _build_lm_sim()
    sim = FedSim(trainer, train, None, cfg)
    return _measure_rounds(sim, n_meas=4)


def bench_torch_reference() -> float:
    """Rounds/sec for the primary config on the reference stack:
    sequential per-client torch training (the reference's standalone path,
    fedavg_api.py:56-66) with an equivalent ResNet-56, on CPU."""
    import numpy as np
    import torch
    import torch.nn as nn

    torch.manual_seed(0)
    torch.set_num_threads(os.cpu_count() or 8)

    class Block(nn.Module):
        def __init__(self, cin, cout, stride):
            super().__init__()
            self.c1 = nn.Conv2d(cin, cout, 3, stride, 1, bias=False)
            self.b1 = nn.BatchNorm2d(cout)
            self.c2 = nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
            self.b2 = nn.BatchNorm2d(cout)
            self.short = (
                nn.Sequential(nn.Conv2d(cin, cout, 1, stride, bias=False), nn.BatchNorm2d(cout))
                if (stride != 1 or cin != cout)
                else nn.Identity()
            )

        def forward(self, x):
            h = torch.relu(self.b1(self.c1(x)))
            h = self.b2(self.c2(h))
            return torch.relu(h + self.short(x))

    def resnet56_torch():
        layers = [nn.Conv2d(3, 16, 3, 1, 1, bias=False), nn.BatchNorm2d(16), nn.ReLU()]
        cin = 16
        for stage, cout in enumerate([16, 32, 64]):
            for b in range(9):
                layers.append(Block(cin, cout, 2 if (stage > 0 and b == 0) else 1))
                cin = cout
        return nn.Sequential(*layers), nn.Linear(64, 10)

    body, head = resnet56_torch()
    opt = torch.optim.SGD(list(body.parameters()) + list(head.parameters()), lr=0.1, momentum=0.9)
    lossf = nn.CrossEntropyLoss()
    x = torch.rand(BATCH, 3, 32, 32)
    y = torch.randint(0, 10, (BATCH,))

    def step():
        opt.zero_grad()
        h = body(x).mean(dim=(2, 3))
        loss = lossf(head(h), y)
        loss.backward()
        opt.step()

    step()  # warmup
    t0 = time.perf_counter()
    n_meas = 3
    for _ in range(n_meas):
        step()
    per_step = (time.perf_counter() - t0) / n_meas
    # one federated round = CLIENTS sequential clients x EPOCHS x STEPS steps
    round_time = per_step * STEPS * EPOCHS * CLIENTS
    return 1.0 / round_time


def main():
    stage_box = ["torch_baseline"]
    try:
        _main(stage_box)
    except BaseException as e:  # noqa: BLE001 — the artifact must be JSON
        print(json.dumps({
            "metric": "bench_error",
            "value": None,
            "unit": "rounds/sec",
            "vs_baseline": None,
            "error": f"{type(e).__name__}: {e}",
            "stage": stage_box[0],
        }))
        sys.exit(1)


def _main(stage: list):
    stage[0] = "backend_init"
    import jax

    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"bench.py needs a TPU: jax.default_backend() is "
            f"{jax.default_backend()!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAK_TFLOPS:
        raise KeyError(
            f"no peak bf16 TFLOP/s on record for device kind "
            f"{device_kind!r}: add it to PEAK_TFLOPS with its source"
        )
    peak = PEAK_TFLOPS[device_kind]

    stage[0] = "torch_baseline"
    cache = {}
    if CACHE.exists():
        try:
            cache = json.loads(CACHE.read_text())
        except Exception:
            cache = {}
    key = f"torch_cpu_resnet56_c{CLIENTS}_s{STEPS}_b{BATCH}_e{EPOCHS}"
    if key not in cache:
        cache[key] = bench_torch_reference()
        try:
            CACHE.write_text(json.dumps(cache))
        except OSError:
            pass
    baseline = cache[key]

    stage[0] = "bench_resnet"
    (rounds_per_sec, rounds_per_sec_single, rounds_per_sec_f32, eval_eps,
     eval_eps_best, pipeline_extra) = bench_resnet()

    stage[0] = "bench_pack_probe"
    pipeline_extra.update(bench_pack_ab())

    stage[0] = "bench_trace_probe"
    pipeline_extra.update(bench_trace_overhead())

    stage[0] = "bench_broadcast_probe"
    pipeline_extra.update(bench_broadcast_ab())

    stage[0] = "bench_downlink_probe"
    pipeline_extra.update(bench_downlink_ab())

    stage[0] = "bench_robust_probe"
    pipeline_extra.update(bench_robust_ab())

    stage[0] = "bench_ft_probe"
    pipeline_extra.update(bench_ft_overhead())

    stage[0] = "bench_async_probe"
    pipeline_extra.update(bench_async_ab())

    stage[0] = "bench_fold_probe"
    pipeline_extra.update(bench_fold_ab())

    stage[0] = "bench_population_probe"
    pipeline_extra.update(bench_population_ab())

    stage[0] = "bench_fleet_probe"
    pipeline_extra.update(bench_fleet_overhead())

    stage[0] = "bench_multijob_probe"
    pipeline_extra.update(bench_multijob())

    stage[0] = "bench_shard_probe"
    pipeline_extra.update(bench_shard_ab(peak))

    stage[0] = "bench_pack_shard_probe"
    pipeline_extra.update(bench_pack_shard_ab())

    stage[0] = "bench_stage_probe"
    stage_ms, stage_ms_loop = bench_stage_probe()
    pipeline_extra.update({
        "host_stage_ms": round(stage_ms, 3),
        "host_stage_ms_loop": round(stage_ms_loop, 3),
        "host_stage_clients": STAGE_CLIENTS,
    })
    resnet_tflops = (
        resnet56_train_flops_per_image() * CLIENTS * STEPS * BATCH * EPOCHS
        * rounds_per_sec / 1e12
    )
    stage[0] = "bench_conv_probe"
    conv_tflops = bench_conv_probe()

    stage[0] = "bench_lm"
    lm_sec = bench_lm()
    lm_tflops = lm_train_flops_per_round() / lm_sec / 1e12
    mfu = lm_tflops / peak

    stage[0] = "bench_compress"
    dense_b, enc_b, enc_ms = bench_compress_probe()
    compress_extra = {
        "compress_topk1pct_uplink_bytes": enc_b,
        "compress_dense_bytes": dense_b,
        "compress_topk1pct_ratio": round(dense_b / enc_b, 1),
        "compress_encode_ms": round(enc_ms, 1),
    }

    print(json.dumps({
        "metric": "fedavg_rounds_per_sec_resnet56_cifar10_10clients_bf16",
        "value": round(rounds_per_sec, 4),
        "unit": "rounds/sec",
        "vs_baseline": round(rounds_per_sec / baseline, 2),
        "mfu": round(mfu, 4),
        "platform": jax.devices()[0].platform,
        "extra": {
            "device": device_kind,
            "bench_shape": f"{CLIENTS} clients x {STEPS} steps x batch {BATCH}",
            "peak_bf16_tflops": peak,
            "lm_config": (
                f"TransformerLM bf16 D{LM_D} L{LM_L} H{LM_H} T{LM_T} V{LM_V}, "
                f"attn={LM_ATTN} (pallas, tiles from the shape), "
                f"{LM_CLIENTS} clients x {LM_STEPS} steps x batch {LM_BATCH}, "
                f"cohort={LM_COHORT} (sequential clients free the HBM that "
                "capped round 3 at batch 4 / MFU 0.467)"
            ),
            "lm_sec_per_round": round(lm_sec, 4),
            "lm_delivered_tflops": round(lm_tflops, 2),
            "resnet_delivered_tflops": round(resnet_tflops, 2),
            "resnet_bound": (
                "arithmetic-intensity, not engine overhead: ResNet-56 CIFAR "
                "channel widths are 16/32/64 against the 128x128 MXU, so "
                "conv contraction/output dims fill 12.5-50% of the array "
                "(stage-weighted ~25% structural ceiling), and BN/ReLU on "
                "[B,32,32,16] activations are HBM-bound (~0.4 FLOP/byte); "
                "~5% of peak delivered at B=32 is the expected shape "
                "ceiling — see conv_probe_* for the same engine path with "
                "MXU-filling channels"
            ),
            "conv_probe_config": (
                f"{CP_LAYERS}x conv3x3 {CP_C}ch bf16 @ {CP_HW}x{CP_HW}, "
                f"{CP_CLIENTS} clients x {CP_STEPS} steps x batch {CP_BATCH}"
            ),
            "conv_probe_delivered_tflops": round(conv_tflops, 2),
            "conv_probe_pct_peak": round(100 * conv_tflops / peak, 1),
            "resnet_rounds_per_sec_single_dispatch": round(rounds_per_sec_single, 3),
            "resnet_f32_rounds_per_sec": round(rounds_per_sec_f32, 3),
            "eval_examples_per_sec": round(eval_eps, 1),
            "eval_examples_per_sec_best": round(eval_eps_best, 1),
            **pipeline_extra,
            **compress_extra,
        },
    }))


if __name__ == "__main__":
    main()
