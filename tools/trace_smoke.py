"""Trace smoke: run a 3-round traced sim (pipelined driver) plus a
compressed loopback FedAvg round on XLA:CPU under ONE process tracer, then
validate the exported Chrome trace end-to-end — the file parses with
tools/trace_report.py, carries spans from all five instrumented layers
(engine, prefetch, loop, comm, compress) in one stream with schema-valid
events, and the traced sim's records are identical to an untraced run
(tracing is read-only).

The multi-rank arm then runs a small 2-tier loopback tree with per-node
lanes (``trace_lanes=``), merges the per-lane exports into ONE Chrome
trace with tools/trace_merge.py, schema-checks the merged stream (open
``B`` spans and ``s``/``f`` wire flows included), asserts every round
close is causally linked across lanes back to a ``client/train`` span by
the wire-propagated contexts, and re-asserts bit-identity against an
untraced run of the same tree.

    JAX_PLATFORMS=cpu python tools/trace_smoke.py
"""

from __future__ import annotations

import os
import sys
import tempfile
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROUNDS = 3
LAYERS = ("engine/", "prefetch/", "loop/", "comm/", "compress/")


def _run_sim(tmp: Path, tag: str):
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.exp._loop import run_rounds
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.sim.engine import FedSim, SimConfig

    import optax

    train, test = gaussian_blobs(
        n_clients=8, samples_per_client=24, num_classes=4, seed=7
    )
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.2), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=8, client_num_per_round=4, batch_size=8,
        comm_round=ROUNDS, frequency_of_the_test=2, seed=0, pipeline_depth=1,
    )
    sim = FedSim(trainer, train, test, cfg)
    records, _ = run_rounds(sim, cfg, str(tmp / f"metrics_{tag}.jsonl"))
    return records


def _run_compressed_loopback():
    import numpy as np
    import optax

    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )
    from fedml_tpu.compress import make_codec
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.sim.cohort import FederatedArrays

    rng = np.random.RandomState(3)
    n_per, C = 16, 2
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(C)}
    train = FederatedArrays(
        {"x": rng.rand(C * n_per, 8).astype(np.float32),
         "y": rng.randint(0, 4, C * n_per).astype(np.int32)},
        part,
    )
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    comm_stats: dict = {}
    run_distributed_fedavg_loopback(
        trainer, train, worker_num=C, round_num=1, batch_size=8, seed=0,
        codec=make_codec("q8"), error_feedback=True, comm_stats=comm_stats,
    )
    return comm_stats


def _run_tree(trace_dir: str | None):
    """One small 2-tier loopback tree run (root -> 2 edges -> 4 leaves);
    ``trace_dir`` installs per-node lanes + wire contexts, None runs the
    identical computation untraced."""
    import optax

    from fedml_tpu.async_agg.tree import run_tree_fedavg_loopback
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression

    train, _ = gaussian_blobs(n_clients=4, samples_per_client=16,
                              num_classes=4, seed=5)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.2), epochs=1,
    )
    return run_tree_fedavg_loopback(trainer, train, (2, 2), 2, 8,
                                    trace_lanes=trace_dir)


def _check_multi_rank(tmp: Path, trace_report, trace_merge) -> dict:
    """The multi-rank arm: traced tree vs untraced tree bit-identical,
    lanes merge into one Perfetto stream, round closes causally linked
    back to client/train across lanes."""
    import jax
    import numpy as np

    tree_dir = tmp / "tree_lanes"
    tree_dir.mkdir()
    ref = _run_tree(None)
    traced = _run_tree(str(tree_dir))
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(traced)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
            "traced tree run differs from untraced — tracing must be "
            "read-only"
        )

    merged = trace_merge.merge_dir(tree_dir)
    out = trace_merge.write_chrome(merged, tree_dir / trace_merge.MERGED_TRACE_NAME)
    assert out.exists()
    assert len(merged["lanes"]) == 7, merged["lanes"]  # root+2 edges+4 leaves
    assert merged["links"], "no wire context matched a send span"
    assert not merged["truncated"]

    # merged-stream schema: open spans stay as B begins, wire flows come
    # in s/f pairs sharing an id, every X span still carries dur
    flow_ids: dict[str, list] = {"s": [], "f": []}
    for e in merged["traceEvents"]:
        ph = e.get("ph")
        assert ph in ("X", "C", "i", "B", "M", "s", "f"), e
        if ph == "X":
            assert "dur" in e and e["dur"] >= 0, e
        if ph in ("s", "f"):
            flow_ids[ph].append(e["id"])
    assert flow_ids["s"] and sorted(flow_ids["s"]) == sorted(flow_ids["f"])

    rows = trace_report.critical_paths(merged)
    closes = [r for r in rows if r["name"] == "round/close"]
    assert closes, "no round/close terminals in the merged trace"
    for row in closes:
        names = [n["name"] for n in row["chain"]]
        assert row["crossed_lanes"], row
        assert any(n.startswith("client/train") for n in names), (
            f"round {row['round']} close not causally linked to a "
            f"client/train span; chain = {names}"
        )
    return {"lanes": len(merged["lanes"]), "links": len(merged["links"]),
            "closes": len(closes)}


def main(argv=None) -> int:
    from fedml_tpu.obs import trace

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_merge
    import trace_report

    with tempfile.TemporaryDirectory() as td:
        tmp = Path(td)
        # untraced reference run first: tracing must not change results
        untraced = _run_sim(tmp, "untraced")

        with trace.trace_to(tmp) as tracer:
            traced = _run_sim(tmp, "traced")
            comm_stats = _run_compressed_loopback()
        chrome = tmp / trace.CHROME_TRACE_NAME

        assert traced == untraced, (
            "traced sim records differ from untraced — tracing must be "
            "read-only"
        )
        assert comm_stats.get("totals"), "loopback run produced no Comm totals"

        # schema check on the raw Chrome file: every event carries valid
        # ph/ts/tid, X events carry dur, tid maps to a named thread track
        import json

        raw = json.loads(chrome.read_text())
        events = raw["traceEvents"]
        named_tids = {e["tid"] for e in events if e.get("ph") == "M"
                      and e["name"] == "thread_name"}
        n_spans = 0
        for e in events:
            if e.get("ph") == "M":
                continue
            assert e["ph"] in ("X", "C", "i"), e
            assert isinstance(e["ts"], (int, float)), e
            assert isinstance(e["tid"], int), e
            assert e["tid"] in named_tids, f"tid {e['tid']} has no track name"
            if e["ph"] == "X":
                assert "dur" in e and e["dur"] >= 0, e
                n_spans += 1
        assert n_spans, "no spans recorded"

        # the report must parse the export and see every instrumented layer
        report = trace_report.summarize(trace_report.load_events(chrome))
        span_names = {r["name"] for r in report["spans"]}
        missing = [p for p in LAYERS
                   if not any(n.startswith(p) for n in span_names)]
        assert not missing, (
            f"layers missing from the trace: {missing}; got {sorted(span_names)}"
        )
        assert report["stall_fraction"] is not None
        assert tracer.events(), "tracer recorded nothing"

        # a build is recorded where it happened: the traced sim is a new
        # FedSim, so its first round dispatch builds (compiles, or loads from
        # the persistent cache) and the steady rounds build nothing
        spans = [e for e in tracer.events() if e["ph"] == "X"]
        dispatches = [e for e in spans if e["name"] == "engine/dispatch"]
        assert len(dispatches) == ROUNDS, dispatches
        built = [
            [b["name"] for b in spans
             if b["name"] in trace.COMPILE_SPANS.values()
             and b["args"].get("parent_id") == d["args"]["span_id"]]
            for d in dispatches
        ]
        assert built[0] and not any(built[1:]), (
            f"builds under the {ROUNDS} round dispatches: {built}; expected "
            f"one under the first (cold) dispatch only"
        )

        multi = _check_multi_rank(tmp, trace_report, trace_merge)

        print(
            f"trace smoke OK: {report['events']} events, "
            f"{len(span_names)} span kinds across all 5 layers "
            f"({', '.join(sorted(p.rstrip('/') for p in LAYERS))}); "
            f"stall fraction {report['stall_fraction']}, "
            f"traced == untraced records; multi-rank: {multi['lanes']} lanes "
            f"merged, {multi['links']} wire links, {multi['closes']} round "
            f"closes causally linked to client/train"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
