"""Cross-rank causal tracing: wire-propagated trace contexts, multi-rank
trace merge (tools/trace_merge.py), and round critical-path attribution
(tools/trace_report.py) — docs/OBSERVABILITY.md "Cross-rank causal
tracing"."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from fedml_tpu.comm.loopback import LoopbackCommManager, LoopbackFabric
from fedml_tpu.comm.message import Message
from fedml_tpu.obs import trace
from fedml_tpu.obs.trace import Tracer

_TOOLS = Path(__file__).parent.parent / "tools"


def _load_tool(name):
    if str(_TOOLS) not in sys.path:  # tools import each other by bare name
        sys.path.insert(0, str(_TOOLS))
    spec = importlib.util.spec_from_file_location(name, _TOOLS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    trace.uninstall()
    yield
    trace.uninstall()


def _lr_fixture(workers=2, samples=16, seed=11):
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression

    train, _ = gaussian_blobs(n_clients=workers, samples_per_client=samples,
                              num_classes=4, seed=seed)
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.2), epochs=1,
    )
    return trainer, train


# -- the per-manager opt-in --------------------------------------------------


def test_stamp_is_explicit_opt_in():
    """No ``trace_wire`` -> no stamp even with a tracer installed; armed
    but untraced -> still no stamp (wire_ctx is None); armed AND traced ->
    the header names the calling thread's open span."""
    cm = LoopbackCommManager(LoopbackFabric(2), 0)
    msg = Message(1, 0, 1)
    cm.trace_wire = True
    cm.stamp_trace_ctx(msg)  # no tracer resolves: nothing to propagate
    assert msg.get(Message.MSG_ARG_KEY_TRACE_CTX) is None

    t = trace.install()
    cm.trace_wire = False
    with t.span("loop/round"), t.span("comm/send"):
        cm.stamp_trace_ctx(msg)
        assert msg.get(Message.MSG_ARG_KEY_TRACE_CTX) is None
        cm.trace_wire = True
        cm.stamp_trace_ctx(msg)
        ctx = msg.get(Message.MSG_ARG_KEY_TRACE_CTX)
    assert ctx is not None
    assert ctx["rank"] == 0 and ctx["span"] >= 1
    assert isinstance(ctx["sent_at"], float)
    assert ctx["chain"] == [ctx["span"] - 1]  # the enclosing loop/round


class _SpyFabric(LoopbackFabric):
    """Captures every framed wire post (materialized to bytes) in order."""

    def __init__(self, world_size):
        super().__init__(world_size)
        self.posted = []

    def post_raw(self, receiver, data):
        if isinstance(data, tuple):
            self.posted.append((receiver, bytes(data[0]), bytes(data[1])))
        else:
            self.posted.append((receiver, bytes(data)))
        super().post_raw(receiver, data)


def _run_spied(worker_num=1, round_num=2, trace_wire=False):
    from fedml_tpu.algorithms.fedavg_distributed import run_distributed_fedavg

    trainer, train = _lr_fixture(workers=worker_num)
    fabric = _SpyFabric(worker_num + 1)
    final = run_distributed_fedavg(
        trainer, train, worker_num, round_num, 8,
        lambda r: LoopbackCommManager(fabric, r), seed=0,
        trace_wire=trace_wire,
    )
    return final, fabric.posted


def _decode(post):
    if len(post) == 3:
        return Message.from_buffers(post[1], post[2])
    return Message.from_bytes(post[1])


def test_ctx_off_wire_bytes_identical():
    """The read-only contract at the byte level: with a tracer installed
    but ``trace_wire`` off, every framed wire post is byte-identical to a
    tracer-free run and no message carries the context key. Armed, the
    context rides the header and the model trajectory is unchanged."""
    import jax

    final_plain, posted_plain = _run_spied()

    trace.install()
    final_traced, posted_traced = _run_spied()
    trace.uninstall()
    assert posted_traced == posted_plain
    assert all(
        _decode(p).get(Message.MSG_ARG_KEY_TRACE_CTX) is None
        for p in posted_plain
    )

    trace.install()
    final_armed, posted_armed = _run_spied(trace_wire=True)
    trace.uninstall()
    stamped = [p for p in posted_armed
               if _decode(p).get(Message.MSG_ARG_KEY_TRACE_CTX) is not None]
    assert stamped, "trace_wire armed but no post carried a context"
    assert posted_armed != posted_plain
    for a, b in zip(jax.tree.leaves(final_plain), jax.tree.leaves(final_armed)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# -- flat loopback propagation + merge ---------------------------------------


def test_flat_lanes_propagate_and_merge(tmp_path):
    from fedml_tpu.algorithms.fedavg_distributed import (
        run_distributed_fedavg_loopback,
    )

    trace_merge = _load_tool("trace_merge")
    trainer, train = _lr_fixture(workers=2)
    run_distributed_fedavg_loopback(trainer, train, worker_num=2,
                                    round_num=2, batch_size=8,
                                    trace_lanes=str(tmp_path))

    paths = trace_merge.lane_files(tmp_path)
    lanes = {trace_merge.load_lane(p)["lane"] for p in paths}
    assert lanes == {"rank0", "rank1", "rank2"}

    merged = trace_merge.merge_dir(tmp_path)
    assert not merged["truncated"]
    pairs = {(lk["src_lane"], lk["dst_lane"]) for lk in merged["links"]}
    # uplink contexts land at the server, downlink contexts at the clients
    assert ("rank1", "rank0") in pairs and ("rank2", "rank0") in pairs
    assert ("rank0", "rank1") in pairs
    recv = next(lk["dst"] for lk in merged["links"]
                if (lk["src_lane"], lk["dst_lane"]) == ("rank1", "rank0"))
    assert recv["args"]["ctx_lane"] == "rank1"
    assert recv["args"]["ctx_span"] >= 1
    assert recv["args"]["ctx_rank"] == 1

    # the fleet view joins the same lanes into its per-round gating column
    fleet_report = _load_tool("fleet_report")
    report = fleet_report.attach_critical_paths({}, tmp_path)
    rows = report["critical_rounds"]
    assert {r["round"] for r in rows} == {0, 1}
    assert all(r["gating_rank"] is not None for r in rows)


# -- crash-truncated lanes (open spans + torn final line) --------------------


def test_truncated_lane_renders_open_spans(tmp_path):
    """A lane whose process died mid-round: spans still open export as
    ``B`` records and the final JSONL line is torn. The report renders the
    open spans open-ended (duration = trace end, flagged) and both loaders
    drop the torn line instead of failing."""
    trace_merge = _load_tool("trace_merge")
    trace_report = _load_tool("trace_report")

    t = Tracer(lane="crash")
    outer = t.span("round/run")
    outer.__enter__()  # never exited: the crash left it open
    with t.span("comm/send"):
        pass
    path = t.export_jsonl(tmp_path / "trace_crash.jsonl")
    with open(path, "a") as f:
        f.write('{"name": "torn-mid-wri')  # death mid-write

    lane = trace_merge.load_lane(path)
    assert lane["truncated"] and lane["lane"] == "crash"
    assert all(e.get("name") != "torn-mid-wri" for e in lane["events"])

    events = trace_report.load_events(path)
    report = trace_report.summarize(events)
    assert report["open_spans"] == 1
    rows = {r["name"]: r for r in report["spans"]}
    send = rows["comm/send"]
    # open-ended render: the open root span spans the whole trace, so it
    # covers (at least) everything the closed child did
    assert rows["round/run"]["total_ms"] >= send["total_ms"]

    merged = trace_merge.merge(
        [path])  # torn lanes still merge, flagged
    assert merged["truncated"] == ["crash"]
    opens = [e for e in merged["traceEvents"] if e.get("ph") == "B"]
    assert [e["name"] for e in opens] == ["round/run"]


# -- clock alignment ---------------------------------------------------------


def _lane_file(tmp_path, lane, wall0, events):
    recs = [{"name": trace.META_EVENT_NAME, "ph": "M", "pid": 1, "tid": 0,
             "args": {"wall0": wall0, "lane": lane}}]
    recs += events
    p = tmp_path / f"trace_{lane}.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return p


def test_merge_wall_anchor_is_primary(tmp_path):
    """A one-way positive send->recv gap is indistinguishable from wire
    latency (e.g. an injected delay), so the causal-bound estimator applies
    NO correction — the wall anchors stand and the gap stays visible."""
    trace_merge = _load_tool("trace_merge")
    a = _lane_file(tmp_path, "a", 100.0, [
        {"name": "comm/send", "ph": "X", "ts": 1000.0, "dur": 50.0,
         "tid": 1, "args": {"span_id": 7}},
    ])
    b = _lane_file(tmp_path, "b", 100.0, [
        {"name": "comm/recv", "ph": "X", "ts": 401000.0, "dur": 30.0,
         "tid": 1, "args": {"ctx_lane": "a", "ctx_span": 7}},
    ])
    merged = trace_merge.merge([a, b])
    assert merged["offsets_us"] == {"a": 0.0, "b": 0.0}
    assert len(merged["links"]) == 1
    send = next(e for e in merged["traceEvents"]
                if e.get("name") == "comm/send")
    recv = next(e for e in merged["traceEvents"]
                if e.get("name") == "comm/recv")
    assert recv["ts"] - send["ts"] == pytest.approx(400000.0)


def test_merge_corrects_causality_violation(tmp_path):
    """A receive landing BEFORE its send on the wall-anchored axis is
    provable skew; the minimal correction restores causality exactly."""
    trace_merge = _load_tool("trace_merge")
    a = _lane_file(tmp_path, "a", 100.0, [
        {"name": "comm/send", "ph": "X", "ts": 1000.0, "dur": 50.0,
         "tid": 1, "args": {"span_id": 3}},
    ])
    # lane b's wall clock runs 0.5 s behind: its recv appears ~499.9 ms
    # before the send that caused it
    b = _lane_file(tmp_path, "b", 99.5, [
        {"name": "comm/recv", "ph": "X", "ts": 1100.0, "dur": 30.0,
         "tid": 1, "args": {"ctx_lane": "a", "ctx_span": 3}},
    ])
    merged = trace_merge.merge([a, b])
    assert merged["offsets_us"]["a"] == 0.0
    assert merged["offsets_us"]["b"] == pytest.approx(-499900.0)
    send = next(e for e in merged["traceEvents"]
                if e.get("name") == "comm/send")
    recv = next(e for e in merged["traceEvents"]
                if e.get("name") == "comm/recv")
    assert recv["ts"] >= send["ts"]
    assert recv["ts"] - send["ts"] == pytest.approx(0.0, abs=1e-6)
    flows = [e for e in merged["traceEvents"] if e.get("ph") in ("s", "f")]
    assert sorted(e["ph"] for e in flows) == ["f", "s"]
    assert len({e["id"] for e in flows}) == 1


# -- acceptance A: delay-injected async tree straggler attribution -----------


# The straggler's upload delay, as a multiple of an undelayed round's
# measured period on this machine, now; 0.4 s at the least. Nothing else in a
# round may take as long as the delay or another lane gates the round: under
# six busy xdist workers a round that takes 40 ms alone can take ten times
# that, so a fixed 0.4 s was a coin there (the driver's run of PR 29).
STRAGGLER_DELAY_ROUNDS = 8
STRAGGLER_DELAY_MIN_S = 0.4


def _tree_round_rows(trainer, train, rounds, fault_specs, lane_dir):
    """Run the 2x2 async tree over loopback with ``fault_specs`` on its
    global leaves, merge its lanes and return (merged, the root's
    ``round/close`` critical-path rows in round order)."""
    from fedml_tpu.async_agg.tree import run_tree_fedavg_loopback
    from fedml_tpu.population.model import PopulationSpec
    from fedml_tpu.population.wire import PopulationWireAdapter

    adapter = PopulationWireAdapter(
        spec=PopulationSpec(), seed=0, worker_num=4,
        fault_specs=fault_specs, profiles={},
    )
    run_tree_fedavg_loopback(
        trainer, train, (2, 2), rounds, 8,
        buffer_goal=2, population=adapter, trace_lanes=str(lane_dir),
    )
    merged = _load_tool("trace_merge").merge_dir(lane_dir)
    rows = [r for r in _load_tool("trace_report").critical_paths(merged)
            if r["name"] == "round/close"]
    return merged, sorted(rows, key=lambda r: r["round"])


def test_tree_straggler_attribution(tmp_path):
    """2-tier async tree with an upload delay injected on global leaf
    rank 3: every lane merges into ONE trace, every round close links
    causally across lanes, and the critical path names the straggler's
    lane in every round after the first. Round 0 is the warm-up: every
    leaf's first ``client/train`` traces and loads its programs anew in each
    run (0.5 s here, 0.84 s seen), a race the delay need not win. The delay
    is sized from this machine's own undelayed round
    (:data:`STRAGGLER_DELAY_ROUNDS`), measured by a first run."""
    from fedml_tpu.comm.faults import FaultSpec

    rounds = 6
    straggler = 3
    trainer, train = _lr_fixture(workers=4)

    # undelayed: rounds 1 and 2 give the period (root close to root close)
    # a warm round takes here
    _, undelayed = _tree_round_rows(trainer, train, 3, {},
                                    tmp_path / "undelayed")
    closes = [r["chain"][0]["ts_ms"] for r in undelayed]
    assert len(closes) == 3
    period_s = max(b - a for a, b in zip(closes, closes[1:])) / 1e3
    delay = max(STRAGGLER_DELAY_MIN_S, STRAGGLER_DELAY_ROUNDS * period_s)

    merged, rows = _tree_round_rows(
        trainer, train, rounds,
        {straggler: FaultSpec(delay=delay, delay_prob=1.0)},
        tmp_path / "delayed")
    assert set(merged["lanes"]) == {
        "root", "edge0", "edge1", "leaf1", "leaf2", "leaf3", "leaf4"}
    assert len(rows) == rounds
    assert all(r["crossed_lanes"] for r in rows)
    seen = [(r["round"], r["gating_lane"], r["gating_span"], r["gating_ms"])
            for r in rows]
    judged = rows[1:]
    assert all(r["gating_lane"] == f"leaf{straggler}" for r in judged), (
        delay, period_s, seen)
    # gated on the delayed wire leg itself: the held send->recv gap is
    # charged to the straggler's send span
    delayed_sends = [r for r in judged if r["gating_span"] == "comm/send"
                     and r["gating_ms"] >= 0.75 * delay * 1e3]
    assert delayed_sends, (delay, period_s, seen)


# -- acceptance B: 8-job multi-tenant merge ----------------------------------


def test_multi_tenant_eight_jobs_merge(tmp_path):
    """8 federations co-scheduled over one wire, one trace lane per job:
    the run merges into ONE Perfetto trace and every job's round closes
    link causally (via the wire contexts) back to a client/train span."""
    from fedml_tpu.tenancy.job import JobSpec
    from fedml_tpu.tenancy.runner import run_multi_job

    trace_merge = _load_tool("trace_merge")
    trace_report = _load_tool("trace_report")

    jobs = []
    for i in range(8):
        trainer, train = _lr_fixture(workers=2, samples=16, seed=20 + i)
        jobs.append(JobSpec(trainer=trainer, train_data=train, worker_num=2,
                            round_num=2, batch_size=8, job_id=f"job{i}",
                            seed=i))
    results = run_multi_job(jobs, join_timeout=300,
                            trace_dir=str(tmp_path))
    assert all(r.error is None for r in results.values()), {
        name: repr(r.error) for name, r in results.items() if r.error}

    merged = trace_merge.merge_dir(tmp_path)
    assert set(merged["lanes"]) == {f"job{i}" for i in range(8)}
    out = trace_merge.write_chrome(
        merged, tmp_path / trace_merge.MERGED_TRACE_NAME)
    assert json.loads(out.read_text())["traceEvents"]

    rows = [r for r in trace_report.critical_paths(merged)
            if r["name"] == "round/close"]
    by_lane = {}
    for r in rows:
        by_lane.setdefault(r["lane"], []).append(r)
    assert set(by_lane) == {f"job{i}" for i in range(8)}
    for lane, lane_rows in by_lane.items():
        assert {r["round"] for r in lane_rows} == {0, 1}, (lane, lane_rows)
        for r in lane_rows:
            names = [n["name"] for n in r["chain"]]
            assert any(n.startswith("client/train") for n in names), (
                lane, r["round"], names)
