"""Shared round loop for the BASELINE repro scripts.

Drives ``FedSim`` one round-dispatch at a time (instead of the engine's
eval-block scan), writing each round's record to ``metrics_out`` as it
completes: a crash mid-run leaves every completed round on disk, and the
failure then propagates so the entry point exits non-zero.

When the sim exposes a nonzero ``pipeline_depth`` (FedSim's default), the
loop is pipelined (fedml_tpu.sim.prefetch): staging for upcoming rounds
runs on a background thread and round metrics are fetched a round behind,
flushed at eval boundaries — per-round dispatch is kept, but the host no
longer serializes stage -> dispatch -> fetch. Bit-identical records, up to
``pipeline_depth`` rounds later in the file — which bounds the durability
tradeoff: a Python exception still records every completed round, but a
hard kill (SIGKILL/OOM/segfault) can lose the at-most-``pipeline_depth``
trailing records still in the drain. Recipes that prioritize write-through
durability over overlap set ``pipeline_depth=0`` in their SimConfig. Sims
without the staged-round API (no ``pipeline_depth`` attribute) run the
serial path unchanged.
"""

from __future__ import annotations

import json
import logging
import os
import time

from fedml_tpu.obs import trace


def run_rounds(sim, cfg, metrics_out: str, stop_when=None) -> tuple[list, float]:
    """Returns (records, wall_seconds). A round that raises stops the loop:
    the rounds that completed are already in ``metrics_out`` (those still
    queued in the metrics drain are flushed to it first) and the exception
    is re-raised — a failed run must not read as a short successful one.
    ``stop_when(records) -> bool`` is consulted after every eval round: a
    True return stops the run early (saturation guard — a curve pinned at
    its fixture ceiling carries no further convergence signal; callers
    report the stop round)."""
    from fedml_tpu.core import rng as rnglib

    records: list[dict] = []
    # clear any stale stop sentinel BEFORE the loop: a leftover file from a
    # run that ended another way (exception, stop_when) must not silently
    # truncate THIS run to one round
    try:
        os.unlink(metrics_out + ".stop")
    except FileNotFoundError:
        pass
    variables = sim.init_round_variables()
    server_state = sim.aggregator.init_state(variables)
    root = rnglib.root_key(cfg.seed)
    pack = getattr(sim, "pack_summary", lambda: {})()
    if pack:
        # packed-lane execution (SimConfig.pack_lanes): record the lane
        # geometry next to the run so a report reader can tell which
        # execution mode produced the (bit-identical) curve
        logging.info("packed-lane execution: %s", pack)
    shard = getattr(sim, "shard_summary", lambda: {})()
    if shard:
        # sharded client models (SimConfig.shard_rules): record the rule
        # set, mesh geometry, and lowering mode next to the run so a
        # report reader can tell which parallelism produced the curve
        logging.info("shard_summary: %s", shard)
    pop = getattr(sim, "population_summary", lambda: {})()
    if pop:
        # heterogeneous population (SimConfig.population): name the spec/
        # trace realization up front — a curve trained under churned
        # cohorts and truncated budgets must never be mistaken for an
        # idealized-population run
        logging.info("population: %s", pop)
    defense = getattr(sim, "defense_summary", lambda: {})()
    if defense:
        # robust aggregation (docs/ROBUSTNESS.md): name the active defense
        # stages up front — a curve trained under clip/DP-noise must never
        # be mistaken for a plain FedAvg run
        logging.info("robust defense: %s", defense)
    freq = max(cfg.frequency_of_the_test, 1)
    depth = getattr(sim, "pipeline_depth", 0)
    prefetch = drain = None
    if depth and cfg.comm_round > 0:
        from fedml_tpu.sim.prefetch import MetricsDrain, Prefetcher

        prefetch = Prefetcher(
            range(cfg.comm_round), lambda r: sim.stage_round(r, root), depth
        )
        drain = MetricsDrain(depth)
    t0 = time.time()
    try:
        with open(metrics_out, "w") as f:

            def write(rr, metrics, eval_rec=None):
                rec = {"round": rr,
                       **{k: float(v) for k, v in metrics.items()}}
                if eval_rec:
                    rec.update(eval_rec)
                records.append(rec)
                f.write(json.dumps(rec) + "\n")
                f.flush()

            try:
                for r in range(cfg.comm_round):
                    with trace.span("loop/round", round=r):
                        if prefetch is not None:
                            variables, server_state, m = sim.run_staged_round(
                                prefetch.get(r), variables, server_state
                            )
                        else:
                            variables, server_state, m = sim.run_round(
                                r, variables, server_state, root
                            )
                        evaled = (r + 1) % freq == 0 or r == cfg.comm_round - 1
                        if drain is not None:
                            # non-blocking: queue this round's metrics on
                            # device, fetch whatever fell off the back; evals
                            # force a full flush (the host syncs there anyway)
                            ready = drain.push(r, m)
                            if evaled:
                                ready = ready + drain.flush()
                        else:
                            ready = [(r, m)]
                        # completed rounds go on the record BEFORE eval runs:
                        # an eval failure must not lose rounds that trained
                        # fine (only the current round's record rides on its
                        # eval, exactly as in the serial driver)
                        current = None
                        for rr, mm in ready:
                            if evaled and rr == r:
                                current = mm
                            else:
                                write(rr, mm)
                        if evaled:
                            write(r, current, sim.eval_record(variables))
                    if evaled and stop_when is not None and stop_when(records):
                        logging.info(
                            "stop_when fired at round %d — stopping early", r
                        )
                        break
                    if os.path.exists(metrics_out + ".stop"):
                        # graceful external stop: `touch <metrics_out>.stop`
                        # ends the run after the current round WITH the final
                        # report written — a SIGTERM would lose it (partial
                        # curves stay reportable). Consumed on use: a leftover
                        # sentinel must not kill the next run at round 0.
                        os.unlink(metrics_out + ".stop")
                        logging.info(
                            "stop file %s.stop found at round %d — stopping",
                            metrics_out, r,
                        )
                        break
            finally:
                # rounds that completed but were still queued in the drain
                # when a stop — or a failed round, on its way out — broke the
                # loop ran fine: record them
                if drain is not None:
                    with trace.span("loop/salvage_flush"):
                        for rr, mm in drain.flush():
                            write(rr, mm)
    finally:
        if prefetch is not None:
            prefetch.close()
    return records, (time.time() - t0) or 1.0
