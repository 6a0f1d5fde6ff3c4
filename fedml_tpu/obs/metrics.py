"""Metrics & logging.

Reference channels (SURVEY §5.5): python logging with per-process format
(fedml_api/utils/logger.py:7), wandb learning curves keyed Train/Acc,
Train/Loss, Test/Acc, Test/Loss by round (FedAVGAggregator.py:137-163), MLOps
MQTT telemetry (fedml_core/mlops_logger.py). Here: one MetricsLogger with the
same wandb key names, writing JSONL locally and forwarding to wandb when
available; MLOps-style system metrics come from obs.sysstats.
"""

from __future__ import annotations

import json
import logging
import os
import time
from pathlib import Path
from typing import Any


# Canonical bytes-on-wire metric keys (compress subsystem): actual bytes
# that crossed (or would cross) the transport vs the dense-f32 equivalent,
# per round. Emitted by the sim engine's compressed aggregator and the
# message-passing FedAvg server so compression ratio shows up in the same
# metrics stream as Train/Acc (docs/COMPRESSION.md).
COMM_UPLINK_BYTES = "Comm/UplinkBytes"
COMM_UPLINK_DENSE_BYTES = "Comm/UplinkDenseBytes"
COMM_DOWNLINK_BYTES = "Comm/DownlinkBytes"
COMM_DOWNLINK_DENSE_BYTES = "Comm/DownlinkDenseBytes"
COMM_RATIO = "Comm/CompressionRatio"
COMM_DOWNLINK_RATIO = "Comm/DownlinkCompressionRatio"
# Downlink delta coding (compress/downlink.py, docs/COMPRESSION.md
# "Downlink delta coding"): how many receivers were served a dense
# keyframe this round (vs an encoded delta chain). With the plane armed,
# DownlinkBytes measures the ENCODED payloads actually on the wire
# (chain blob + descriptor), so DownlinkCompressionRatio is real, not
# the dense/dense identity it was before the plane existed.
COMM_DOWNLINK_KEYFRAMES = "Comm/DownlinkKeyframes"

# ratio keys are derived, not additive — totals() must never sum them
_RATIO_KEYS = (COMM_RATIO, COMM_DOWNLINK_RATIO)

# Interior (tier-to-tier) uplink bytes in tree mode (async_agg/tree.py,
# docs/PERFORMANCE.md "Barrier-free aggregation"): actual bytes each edge
# tier's partial put on the wire toward its parent vs the raw-f64
# accumulator equivalent. With the tier uplink codec armed the partial
# ships as an EncodedUpdate (delta framing against the round global), so
# the ratio measures real interior-bandwidth savings; without a codec the
# two are equal. Summed over every edge into tier_stats/comm_stats totals
# by run_tree_fedavg and the cascade harness.
COMM_TIER_UPLINK_BYTES = "Comm/TierUplinkBytes"
COMM_TIER_UPLINK_DENSE_BYTES = "Comm/TierUplinkDenseBytes"

# retry/backoff send plane (comm/retry.py, docs/ROBUSTNESS.md "Failure
# recovery"): how many send attempts were re-tried after a transient
# failure over the whole run. Emitted into comm_stats totals by
# run_distributed_fedavg when a RetryPolicy is armed.
COMM_RETRY_COUNT = "Comm/RetryCount"

# Stale uploads at the synchronous server (docs/PERFORMANCE.md
# "Barrier-free aggregation"): a straggler's model from an already-closed
# round that the sync round protocol must discard (the async server folds
# these with a staleness weight instead). Emitted into comm_stats totals by
# run_distributed_fedavg — the observability baseline async staleness
# weighting builds on.
COMM_STALE_UPLOADS = "Comm/StaleUploads"

# Async / barrier-free server keys (docs/PERFORMANCE.md "Barrier-free
# aggregation"): per-emission-window fold counts from the buffered-async
# tally (async_agg.AsyncFedAggregator). Arrivals is the number of uploads
# folded into the emitted model (== buffer_goal), StaleFolds how many of
# them trained an older model version (folded with the staleness weight,
# never dropped), DuplicateUploads how many replayed (sender, version)
# pairs the idempotence guard absorbed, MeanStaleness the mean version lag
# over the window's folds. ModelsEmitted rides the run totals.
ASYNC_ARRIVALS = "Async/Arrivals"
ASYNC_STALE_FOLDS = "Async/StaleFolds"
ASYNC_DUP_UPLOADS = "Async/DuplicateUploads"
ASYNC_MEAN_STALENESS = "Async/MeanStaleness"
ASYNC_MODELS_EMITTED = "Async/ModelsEmitted"

# Robust-aggregation defense keys (docs/ROBUSTNESS.md): per-round mean
# pre-clip update norm, fraction of the cohort whose delta got clipped, and
# how many client updates the combine rule discarded (krum keeps one,
# trimmed mean drops 2k, non-finite wire uploads are rejected). Emitted by
# the sim engine's robust_aggregator and the message-passing
# RobustDistAggregator so both defense paths land in one metrics stream.
ROBUST_UPDATE_NORM = "Robust/UpdateNorm"
ROBUST_CLIP_FRACTION = "Robust/ClipFraction"
ROBUST_FILTERED = "Robust/FilteredClients"

# Multi-tenant job plane keys (fedml_tpu/tenancy/, docs/MULTITENANCY.md):
# per-job accounting when N federations share one wire, one send pool, and
# one scheduler. SendBytes/SendLegs/SchedulerTurns are emitted by the fair
# fan-out scheduler's per-job stats (tenancy/scheduler.py — bytes actually
# dispatched for the job, individual send legs, and deficit-round-robin
# visits that dispatched work); Rounds/Errors ride each job's totals from
# the tenancy runner (rounds that closed, 1 if the job died with a captured
# exception). All land in per-job ``totals`` (jobs.json) and, when a
# job-scoped registry is installed, in that job's metric stream.
JOB_SEND_BYTES = "Job/SendBytes"
JOB_SEND_LEGS = "Job/SendLegs"
JOB_SCHED_TURNS = "Job/SchedulerTurns"
JOB_ROUNDS = "Job/Rounds"
JOB_ERRORS = "Job/Errors"

# Sharded fold plane keys (algorithms/fold_plane.py, docs/PERFORMANCE.md
# "The server fold plane"): QueueDepth is the gauge of uploads submitted to
# the chunk workers and not yet fully folded (sampled at each enqueue, after
# the plane condition is released); StallMs is the histogram of wall time a
# quiesce point (aggregate / emit / snapshot / export) spent draining the
# queues — how much fold debt the barrier actually paid. Rendered by
# tools/fleet_report.py from the run's registry snapshot.
FOLD_QUEUE_DEPTH = "Fold/QueueDepth"
FOLD_STALL_MS = "Fold/StallMs"


class CommBytesAccountant:
    """Per-round uplink/downlink byte ledger for the message-passing path.

    The sim engine computes these inside the round program (shapes are
    static); the wire path counts real payload sizes here instead — one
    ``record_*`` call per message, ``round_record`` to flush a round's
    totals into the metrics stream under the canonical keys."""

    def __init__(self):
        import threading

        # record_* runs on the server's receive thread; round_record can run
        # on the straggler-timeout timer thread (fedavg_distributed
        # _round_timed_out -> _complete_round) — counters need the lock or
        # an interleaved read-add-store loses straggler bytes
        self._lock = threading.Lock()
        self.rounds: list[dict] = []  # guarded-by: _lock
        self._up = 0  # guarded-by: _lock
        self._up_dense = 0  # guarded-by: _lock
        self._down = 0  # guarded-by: _lock
        self._down_dense = 0  # guarded-by: _lock
        self._keyframes = 0  # guarded-by: _lock

    def record_uplink(self, actual: int, dense: int) -> None:
        with self._lock:
            self._up += int(actual)
            self._up_dense += int(dense)

    def record_downlink(self, actual: int, dense: int) -> None:
        with self._lock:
            self._down += int(actual)
            self._down_dense += int(dense)

    def record_keyframes(self, count: int = 1) -> None:
        """Receivers served a dense keyframe instead of a delta chain
        (downlink delta plane only — the key is emitted only when the
        counter moved, so pre-downlink records are unchanged)."""
        with self._lock:
            self._keyframes += int(count)

    def round_record(self, round_idx: int) -> dict:
        with self._lock:
            rec = {
                "round": round_idx,
                COMM_UPLINK_BYTES: self._up,
                COMM_UPLINK_DENSE_BYTES: self._up_dense,
                COMM_DOWNLINK_BYTES: self._down,
                COMM_DOWNLINK_DENSE_BYTES: self._down_dense,
            }
            if self._up:
                rec[COMM_RATIO] = self._up_dense / self._up
            if self._down:
                rec[COMM_DOWNLINK_RATIO] = self._down_dense / self._down
            if self._keyframes:
                rec[COMM_DOWNLINK_KEYFRAMES] = self._keyframes
            self.rounds.append(rec)
            self._up = self._up_dense = self._down = self._down_dense = 0
            self._keyframes = 0
            return rec

    def totals(self) -> dict:
        out: dict = {}
        # include traffic recorded since the last round flush (e.g. the
        # final stop broadcast, which lands after the last round_record)
        with self._lock:
            pending = {
                COMM_UPLINK_BYTES: self._up,
                COMM_UPLINK_DENSE_BYTES: self._up_dense,
                COMM_DOWNLINK_BYTES: self._down,
                COMM_DOWNLINK_DENSE_BYTES: self._down_dense,
            }
            if self._keyframes:
                pending[COMM_DOWNLINK_KEYFRAMES] = self._keyframes
            rounds = list(self.rounds)
        for rec in rounds + [pending]:
            for k, v in rec.items():
                if k.startswith("Comm/") and k not in _RATIO_KEYS:
                    out[k] = out.get(k, 0) + v
        if out.get(COMM_UPLINK_BYTES):
            out[COMM_RATIO] = (
                out[COMM_UPLINK_DENSE_BYTES] / out[COMM_UPLINK_BYTES]
            )
        if out.get(COMM_DOWNLINK_BYTES):
            out[COMM_DOWNLINK_RATIO] = (
                out[COMM_DOWNLINK_DENSE_BYTES] / out[COMM_DOWNLINK_BYTES]
            )
        return out


def logging_config(process_id: int = 0, level=logging.INFO) -> None:
    """Per-process log format (fedml_api/utils/logger.py:7-32)."""
    logging.basicConfig(
        level=level,
        format=f"%(asctime)s [{process_id}] %(filename)s[%(lineno)d] %(levelname)s: %(message)s",
        force=True,
    )

class MetricsLogger:
    """wandb-key-compatible metric sink (Train/Acc, Test/Acc, ... by round).

    Usable as a context manager — the JSONL handle is closed even when the
    run body raises. ``close()`` is idempotent; ``log()`` after close raises
    instead of writing to a closed handle."""

    def __init__(self, run_dir: str | Path | None = None, use_wandb: bool = False,
                 wandb_kwargs: dict | None = None):
        self.run_dir = Path(run_dir) if run_dir else None
        self._fh = None
        self._closed = False
        if self.run_dir:
            self.run_dir.mkdir(parents=True, exist_ok=True)
            self._fh = open(self.run_dir / "metrics.jsonl", "a")
        self._wandb = None
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb
                wandb.init(**(wandb_kwargs or {}))
            except Exception as e:  # wandb optional, never fatal
                logging.warning("wandb unavailable: %s", e)
        self.history: list[dict[str, Any]] = []

    def log(self, metrics: dict[str, Any], round_idx: int | None = None) -> None:
        if self._closed:
            raise RuntimeError(
                "MetricsLogger.log() after close(): the JSONL sink is gone; "
                "records logged here would be silently lost"
            )
        rec = dict(metrics)
        if round_idx is not None:
            rec["round"] = round_idx
        rec["_ts"] = time.time()
        self.history.append(rec)
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        if self._wandb:
            self._wandb.log({k: v for k, v in rec.items() if not k.startswith("_")})

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._fh:
            self._fh.close()
            self._fh = None
        if self._wandb:
            self._wandb.finish()

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
