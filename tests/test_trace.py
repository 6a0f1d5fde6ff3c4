"""Span tracer, instrumented-layer emission, and the obs satellite fixes
(MetricsLogger / CommBytesAccountant / SysStats)."""

import json
import threading
import time

import numpy as np
import pytest

from fedml_tpu.obs import trace
from fedml_tpu.obs.metrics import (
    COMM_DOWNLINK_RATIO,
    COMM_RATIO,
    CommBytesAccountant,
    MetricsLogger,
)
from fedml_tpu.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends with no process tracer installed."""
    trace.uninstall()
    yield
    trace.uninstall()


# -- Tracer core -------------------------------------------------------------


def test_span_nesting_across_threads():
    t = Tracer()
    # the workers meet inside their outer spans: all three are alive at once,
    # so the OS cannot hand a finished worker's ident to the next one started
    # (which merged two tracks into one on a loaded machine)
    meet = threading.Barrier(3)

    def work(tag, meet=None):
        with t.span("outer", tag=tag):
            if meet is not None:
                meet.wait(timeout=30)
            with t.span("inner", tag=tag):
                time.sleep(0.002)

    threads = [threading.Thread(target=work, args=(i, meet), name=f"w{i}")
               for i in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    work("main")

    spans = [e for e in t.events() if e["ph"] == "X"]
    assert len(spans) == 8  # 4 threads x (outer + inner)
    # one track id per thread, and thread names recorded for the export
    tids = {e["tid"] for e in spans}
    assert len(tids) == 4
    names = t.thread_names()
    assert {"w0", "w1", "w2"} <= set(names.values())
    # per thread: inner nests inside outer (child exits first, so it is
    # appended first; timestamps contain it)
    by_tid = {}
    for e in spans:
        by_tid.setdefault(e["tid"], []).append(e)
    for group in by_tid.values():
        inner = next(e for e in group if e["name"] == "inner")
        outer = next(e for e in group if e["name"] == "outer")
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
        assert inner["args"]["tag"] == outer["args"]["tag"]


def test_disabled_tracer_is_shared_noop():
    assert trace.get() is None and not trace.enabled()
    s1 = trace.span("anything", round=3)
    s2 = trace.span("else")
    assert s1 is s2  # the shared no-op instance: nothing allocated per call
    with s1:
        pass
    trace.event("x")
    trace.counter("c", 1.0)
    trace.gauge("g", 2.0)  # none of these may raise or record anywhere

    tracer = trace.install()
    with trace.span("real"):
        pass
    assert [e["name"] for e in tracer.events()] == ["real"]
    trace.uninstall()
    assert trace.span("again") is s1


def test_event_cap_is_a_ring_keeping_the_recent_window(tmp_path):
    t = Tracer(max_events=3)
    for i in range(5):
        t.event(f"e{i}")
    # ring semantics: bounded memory, OLDEST evicted — a multi-hour traced
    # run keeps the most recent window, the part an operator debugging a
    # live slowdown actually wants
    assert len(t.events()) == 3
    assert t.dropped == 2
    assert [e["name"] for e in t.events()] == ["e2", "e3", "e4"]
    # both exports surface the drop count in-band
    jl = t.export_jsonl(tmp_path / "t.jsonl")
    lines = [json.loads(line) for line in jl.read_text().splitlines()]
    assert lines[-1]["name"] == Tracer.DROPPED_EVENT_NAME
    assert lines[-1]["args"]["value"] == 2.0
    raw = json.loads(t.export_chrome(tmp_path / "t.json").read_text())
    assert raw["droppedEvents"] == 2
    assert any(e["name"] == Tracer.DROPPED_EVENT_NAME
               for e in raw["traceEvents"])
    # an un-wrapped tracer exports no drop record
    t2 = Tracer(max_events=10)
    t2.event("only")
    jl2 = t2.export_jsonl(tmp_path / "t2.jsonl")
    assert all(json.loads(line)["name"] != Tracer.DROPPED_EVENT_NAME
               for line in jl2.read_text().splitlines())


def test_install_returns_and_replaces():
    a = trace.install()
    assert trace.get() is a
    b = trace.install()
    assert trace.get() is b and a is not b
    assert trace.uninstall() is b
    assert trace.get() is None


def test_chrome_export_schema(tmp_path):
    t = Tracer()
    with t.span("s", k=1):
        t.event("marker", note="hi")
        t.counter("depth", 2)
    path = t.export_chrome(tmp_path / "t.json")
    raw = json.loads(path.read_text())
    events = raw["traceEvents"]
    named_tids = {e["tid"] for e in events
                  if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert named_tids, "thread_name metadata missing"
    body = [e for e in events if e.get("ph") != "M"]
    assert {e["ph"] for e in body} == {"X", "i", "C"}
    for e in body:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["tid"], int) and e["tid"] in named_tids
        assert e["pid"] == Tracer.PID
        if e["ph"] == "X":
            assert e["dur"] >= 0
    counter = next(e for e in body if e["ph"] == "C")
    assert counter["args"]["value"] == 2.0


def test_jsonl_export_and_report_loader(tmp_path):
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "trace_report",
        Path(__file__).parent.parent / "tools" / "trace_report.py",
    )
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)

    t = Tracer()
    with t.span("a"):
        with t.span("b"):
            pass
    jl = t.export_jsonl(tmp_path / "t.jsonl")
    ch = t.export_chrome(tmp_path / "t.chrome.json")
    for path in (jl, ch):
        events = trace_report.load_events(path)
        assert {e["name"] for e in events} == {"a", "b"}

    report = trace_report.summarize(trace_report.load_events(ch))
    rows = {r["name"]: r for r in report["spans"]}
    # self time: a's self excludes b (same-thread nesting by timestamps)
    assert rows["a"]["self_ms"] <= rows["a"]["total_ms"]
    assert rows["b"]["total_ms"] <= rows["a"]["total_ms"]


def test_report_self_time_and_stall_fraction():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "trace_report2",
        Path(__file__).parent.parent / "tools" / "trace_report.py",
    )
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)

    events = [
        {"name": "loop/round", "ph": "X", "ts": 0.0, "dur": 100.0, "tid": 1},
        {"name": "prefetch/consumer_stall", "ph": "X", "ts": 10.0,
         "dur": 40.0, "tid": 1},
        {"name": "engine/dispatch", "ph": "X", "ts": 60.0, "dur": 30.0,
         "tid": 1},
        {"name": "engine/lane_occupancy", "ph": "C", "ts": 5.0, "tid": 1,
         "args": {"value": 0.75}},
    ]
    rep = trace_report.summarize(events)
    rows = {r["name"]: r for r in rep["spans"]}
    assert rows["loop/round"]["total_ms"] == 0.1
    # 100 - (40 + 30) = 30 us self
    assert rows["loop/round"]["self_ms"] == pytest.approx(0.03)
    assert rep["stall_fraction"] == pytest.approx(0.4)
    assert rep["lane_occupancy_mean"] == 0.75


def test_trace_to_exports_and_restores(tmp_path):
    outer = trace.install()
    with trace.trace_to(tmp_path):
        assert trace.get() is not outer
        with trace.span("inside"):
            pass
    assert trace.get() is outer  # previous tracer restored
    assert (tmp_path / trace.JSONL_TRACE_NAME).exists()
    chrome = json.loads((tmp_path / trace.CHROME_TRACE_NAME).read_text())
    assert any(e.get("name") == "inside" for e in chrome["traceEvents"])


# -- instrumented layers -----------------------------------------------------


def test_prefetcher_stall_gauge_and_span_emission():
    from fedml_tpu.sim.prefetch import Prefetcher

    tracer = trace.install()
    try:
        # slow staging, eager consumer -> consumer stalls
        with Prefetcher(range(3), lambda r: (time.sleep(0.03), r)[1],
                        depth=1) as pf:
            for r in range(3):
                assert pf.get(r) == r
        names = [e["name"] for e in tracer.events()]
        assert "prefetch/consumer_stall" in names
        assert "prefetch/stage" in names
        depths = [e for e in tracer.events()
                  if e["ph"] == "C" and e["name"] == "prefetch/queue_depth"]
        assert depths and all("value" in e["args"] for e in depths)

        # instant staging, slow consumer, depth 1 -> producer blocks
        with Prefetcher(range(4), lambda r: r, depth=1) as pf:
            time.sleep(0.25)  # let the producer fill the queue and block
            for r in range(4):
                assert pf.get(r) == r
        names = [e["name"] for e in tracer.events()]
        assert "prefetch/producer_blocked" in names
    finally:
        trace.uninstall()


def test_metrics_drain_fetch_behind_span():
    from fedml_tpu.sim.prefetch import MetricsDrain

    tracer = trace.install()
    try:
        d = MetricsDrain(depth=1)
        assert d.push(0, {"m": np.float32(1)}) == []
        out = d.push(1, {"m": np.float32(2)})
        assert [tag for tag, _ in out] == [0]
        out = d.flush()
        assert [tag for tag, _ in out] == [1]
        fetches = [e for e in tracer.events()
                   if e["name"] == "prefetch/drain_fetch"]
        assert len(fetches) == 2
        assert all(e["args"]["behind_s"] >= 0 for e in fetches)
    finally:
        trace.uninstall()


def test_wire_path_span_attrs_on_loopback():
    """comm/send + comm/recv + comm/handler spans carry message type and
    payload bytes on the loopback backend."""
    from fedml_tpu.comm.loopback import LoopbackCommManager, LoopbackFabric
    from fedml_tpu.comm.managers import DistributedManager
    from fedml_tpu.comm.message import Message

    MSG = 7
    payload = np.arange(12, dtype=np.float32)  # 48 bytes

    class Echo(DistributedManager):
        def register_message_receive_handlers(self):
            self.register_message_receive_handler(MSG, self._on)

        def _on(self, msg):
            np.testing.assert_array_equal(
                np.asarray(msg.get("blob")), payload
            )
            self.finish()

    fabric = LoopbackFabric(2)
    receiver = Echo(LoopbackCommManager(fabric, 1), rank=1, size=2)
    sender = DistributedManager(LoopbackCommManager(fabric, 0), rank=0, size=2)

    tracer = trace.install()
    try:
        th = threading.Thread(target=receiver.run, daemon=True)
        th.start()
        msg = Message(MSG, 0, 1)
        msg.add_params("blob", payload)
        sender.send_message(msg)
        th.join(timeout=10.0)
        assert not th.is_alive()
    finally:
        trace.uninstall()

    spans = {e["name"]: e for e in tracer.events() if e["ph"] == "X"}
    assert {"comm/send", "comm/recv", "comm/handler"} <= set(spans)
    for name in ("comm/send", "comm/recv"):
        assert spans[name]["args"]["msg_type"] == MSG
        assert spans[name]["args"]["bytes"] == payload.nbytes
    assert spans["comm/handler"]["args"]["msg_type"] == MSG
    # send lands on the caller thread, recv/handler on the receive loop's
    assert spans["comm/send"]["tid"] != spans["comm/handler"]["tid"]


def test_message_payload_nbytes():
    from fedml_tpu.comm.message import Message

    msg = Message(1, 0, 1)
    msg.add_params("a", np.zeros(10, np.float32))
    msg.add_params("b", np.zeros((2, 3), np.int64))
    msg.add_params("note", "not an array")
    assert msg.payload_nbytes() == 40 + 48


def test_compress_accumulate_span():
    from fedml_tpu.compress import make_codec
    from fedml_tpu.compress.aggregate import accumulate_encoded

    import jax

    codec = make_codec("q8")
    tree = {"w": np.linspace(-1, 1, 16).astype(np.float32)}
    enc = jax.tree.map(np.asarray, codec.encode(tree, jax.random.key(0)))
    tracer = trace.install()
    try:
        acc = np.zeros(16, np.float64)
        accumulate_encoded(acc, enc, 1.0, codec)
    finally:
        trace.uninstall()
    names = [e["name"] for e in tracer.events()]
    assert "compress/accumulate" in names
    assert "compress/decode" in names  # q8 takes the dense-decode path


def _blobs_sim(seed, comm_round, frequency_of_the_test, per_round, **over):
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.sim.engine import FedSim, SimConfig

    train, test = gaussian_blobs(
        n_clients=4, samples_per_client=16, num_classes=3, seed=seed
    )
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=3),
        optimizer=optax.sgd(0.1), epochs=1,
    )
    cfg = SimConfig(client_num_in_total=4, client_num_per_round=per_round,
                    batch_size=8, comm_round=comm_round,
                    frequency_of_the_test=frequency_of_the_test, seed=0,
                    **over)
    return FedSim(trainer, train, test, cfg)


def test_engine_round_spans_and_compile_spans():
    """The round driver's spans, and a build recorded where it happened: a
    cold program's dispatch holds a ``jax/compile`` (or ``jax/cache_load``)
    child, a warm one none, and a new block length compiles again under the
    dispatch that needed it, whatever the program kind."""
    sim = _blobs_sim(1, comm_round=4, frequency_of_the_test=2, per_round=4,
                     block_dispatch=True)
    tracer = trace.install()
    try:
        variables, _ = sim.run()  # blocks (0, 2), (2, 2)
        sim.config.comm_round = 10
        sim.config.frequency_of_the_test = 3
        sim.run(variables=variables, start_round=4)  # (4, 2), (6, 3), (9, 1)
    finally:
        trace.uninstall()
    spans = [e for e in tracer.events() if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"engine/stage", "engine/stage/cohort", "engine/stage/put",
            "engine/stage/keys", "engine/dispatch", "engine/sync",
            "engine/eval"} <= names, names
    by_id = {e["args"]["span_id"]: e for e in spans}
    builds = [e for e in spans if e["name"] in trace.COMPILE_SPANS.values()]
    built_under = {}
    for e in builds:
        parent = by_id.get(e["args"].get("parent_id"))
        if parent is not None and parent["name"] == "engine/dispatch":
            built_under.setdefault(parent["args"]["span_id"], []).append(e)
    dispatches = [e for e in spans if e["name"] == "engine/dispatch"]
    assert [(d["args"]["program"], d["args"]["n_rounds"]) for d in dispatches] == [
        ("block2", 2), ("block2", 2), ("block2", 2), ("block3", 3), ("gather", 1)]
    cold = [d["args"]["span_id"] in built_under for d in dispatches]
    assert cold == [True, False, False, True, True]
    for d in dispatches:  # a build lies inside the dispatch that caused it
        for e in built_under.get(d["args"]["span_id"], []):
            assert e["tid"] == d["tid"] and e["dur"] <= d["dur"]
            assert e["ts"] + e["dur"] <= d["ts"] + d["dur"] + 1.0
    # the children lie inside their parent, on the staging thread
    stage = next(e for e in spans if e["name"] == "engine/stage")
    kids = [e for e in spans if e["args"].get("parent_id") == stage["args"]["span_id"]]
    assert {e["name"] for e in kids} >= {
        "engine/stage/cohort", "engine/stage/put", "engine/stage/keys"}
    assert all(e["tid"] == stage["tid"] for e in kids)
    assert sum(e["dur"] for e in kids
               if e["name"].startswith("engine/stage/")) <= stage["dur"]


@pytest.mark.parametrize("block", [False, True])
def test_traced_run_bit_identical_to_untraced(block, tmp_path):
    """Tracing is read-only: same records, same final variables, with the
    spans mirrored into a recording profiler and the staging's child spans
    on, on the one-round and on the block path."""
    import jax

    def sim():
        return _blobs_sim(2, comm_round=4, frequency_of_the_test=2,
                          per_round=2, block_dispatch=block)

    v_plain, h_plain = sim().run()
    trace.install()
    jax.profiler.start_trace(str(tmp_path))
    try:
        v_traced, h_traced = sim().run()
    finally:
        jax.profiler.stop_trace()
        trace.uninstall()
    assert len(h_plain) == len(h_traced) == 4
    for a, b in zip(jax.tree.leaves(v_plain), jax.tree.leaves(v_traced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for rp, rt in zip(h_plain, h_traced):
        for k, v in rp.items():
            if k != "round_time":
                assert rt[k] == v, k


def test_cli_trace_dir_writes_trace(tmp_path):
    """--trace_dir on the unified entry records and exports the run."""
    import argparse

    from fedml_tpu.exp.main_fedavg import add_args, run

    parser = add_args(argparse.ArgumentParser())
    args = parser.parse_args([
        "--model", "lr", "--dataset", "synthetic_0.5_0.5",
        "--client_num_in_total", "8", "--client_num_per_round", "4",
        "--batch_size", "8", "--comm_round", "2",
        "--frequency_of_the_test", "2", "--lr", "0.05",
        "--trace_dir", str(tmp_path),
    ])
    history = run(args)
    assert len(history) == 2
    assert trace.get() is None  # tracer uninstalled after the run
    jsonl = tmp_path / trace.JSONL_TRACE_NAME
    chrome = tmp_path / trace.CHROME_TRACE_NAME
    assert jsonl.exists() and chrome.exists()
    names = {json.loads(line)["name"] for line in jsonl.read_text().splitlines()}
    assert any(n.startswith("engine/") for n in names)
    assert any(n.startswith("prefetch/") for n in names)


def test_trace_smoke_tool_runs():
    """tools/trace_smoke.py is the end-to-end guard the docs point at — run
    it in-process so tier-1 exercises the five-layer trace stream."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).parent.parent / "tools" / "trace_smoke.py"
    spec = importlib.util.spec_from_file_location("trace_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0


# -- satellite fixes ---------------------------------------------------------


def test_metrics_logger_context_manager_and_close_semantics(tmp_path):
    with pytest.raises(RuntimeError, match="boom"):
        with MetricsLogger(run_dir=tmp_path) as m:
            m.log({"Train/Acc": 0.5}, round_idx=0)
            raise RuntimeError("boom")
    # the handle was closed by __exit__ despite the exception
    assert m._fh is None
    lines = (tmp_path / "metrics.jsonl").read_text().strip().splitlines()
    assert len(lines) == 1

    m.close()  # idempotent: second close is a no-op
    m.close()
    with pytest.raises(RuntimeError, match="after close"):
        m.log({"Train/Acc": 0.6}, round_idx=1)


def test_accountant_downlink_compression_ratio():
    acc = CommBytesAccountant()
    acc.record_uplink(100, 400)
    acc.record_downlink(200, 600)
    rec = acc.round_record(0)
    assert rec[COMM_RATIO] == pytest.approx(4.0)
    assert rec[COMM_DOWNLINK_RATIO] == pytest.approx(3.0)
    acc.record_downlink(100, 100)  # post-flush traffic (stop broadcast)
    totals = acc.totals()
    assert totals[COMM_DOWNLINK_RATIO] == pytest.approx(700 / 300)
    assert totals[COMM_RATIO] == pytest.approx(4.0)
    # ratio keys are derived — byte totals must not absorb them
    assert totals["Comm/DownlinkBytes"] == 300

    # guard: no downlink traffic -> no downlink ratio key
    empty = CommBytesAccountant()
    empty.record_uplink(10, 20)
    assert COMM_DOWNLINK_RATIO not in empty.round_record(0)
    assert COMM_DOWNLINK_RATIO not in empty.totals()


def test_sysstats_cpu_counter_primed():
    from fedml_tpu.obs import sysstats

    s = sysstats.SysStats()
    sample = s.sample()
    assert "uptime_s" in sample
    if sysstats.HAS_PSUTIL:
        # the constructor primed cpu_percent, so the first sample reports a
        # real utilization measurement (a float; 0.0 only if the host was
        # truly idle over the window, not the unprimed constant)
        assert isinstance(sample["cpu_utilization"], float)


# -- the delta-attention mixer's scopes, note, counter and kept names --------


def _delta_lm(**over):
    from fedml_tpu.models.mla_moe_transformer import KDA, MLAMoETransformerLM

    return MLAMoETransformerLM(**{**dict(
        vocab_size=61, embed_dim=32, dense_layers=2, routed_layers=0, q_rank=None,
        rope_theta=None, dense_dim=64, mtp_depth=0, mixers=(KDA, KDA), kda_heads=2,
        kda_head_dim=16, attn_impl="flash"), **over})


def test_delta_attention_scopes_note_and_kept_names_in_the_lowered_training_step():
    """``attn/kda`` round the mixer, ``attn/kda/scan`` round the recurrence
    alone and ``attn/kda/conv``, ``attn/kda/decay``, ``attn/kda/gate`` round the
    three pointwise chains, inside ``fed/fwd_bwd``, forward and backward; a
    ``kda/call`` note a call and a ``kda/chain`` note an operator; a
    rematerialised block's ``remat/kept`` notes for the three projections, the
    scan's output and its states: the same five names as before the
    operators, which keep nothing."""
    import jax
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.ops import kda, remat

    assert trace.KDA_SCOPES == (
        trace.SCOPE_KDA, trace.SCOPE_KDA_SCAN, trace.SCOPE_KDA_CONV, trace.SCOPE_KDA_DECAY,
        trace.SCOPE_KDA_GATE) == (
            "attn/kda", "attn/kda/scan", "attn/kda/conv", "attn/kda/decay", "attn/kda/gate")
    assert not set(trace.KDA_SCOPES) & (
        set(trace.SCOPES) | set(trace.MOE_SCOPES) | set(trace.MLA_SCOPES))
    model = _delta_lm(remat=True)
    x = jnp.zeros((2, 24), jnp.int32)
    params = model.init(jax.random.key(0), x)["params"]
    trainer = ClientTrainer(module=model, task="nwp", optimizer=optax.sgd(0.01))
    batch = {"x": x, "y": x, "mask": jnp.ones(x.shape, jnp.float32)}
    lines = jax.jit(trainer.train_step_stats).lower(
        {"params": params}, trainer.optimizer.init(params), params, batch,
        jax.random.key(0)).as_text(debug_info=True).splitlines()
    for scope in trace.KDA_SCOPES:
        assert any(f"/{scope}/" in ln and "fed/fwd_bwd" in ln and "transpose(" not in ln
                   for ln in lines), scope
        assert any(f"/{scope}/" in ln and "fed/fwd_bwd" in ln and "transpose(" in ln
                   for ln in lines), scope
    # the projections lie under the mixer's scope and not under the scan's
    assert any("attn/kda/q/dot_general" in ln and "attn/kda/scan" not in ln for ln in lines)
    assert {"impl": trace.KDA_FWD_KERNEL_NAME, "chunk": kda.CHUNK, "chunks": 1, "heads": 2,
            "d_k": 16, "d_v": 16, "t": 24} in trace.program_notes("kda/call")
    # [2, 24, 2 x 16] float32: one in and one out; one in and float32 out; two in and one out
    chains = {n["op"]: n for n in trace.program_notes(kda.CHAIN_NOTE)
              if (n["rows"], n["columns"]) == (48, 32)}
    assert {op: n["bytes"] for op, n in chains.items()} == {
        "conv_act": 2 * 48 * 32 * 4, "decay": 48 * 32 * 8, "gated_norm": 3 * 48 * 32 * 4}
    kept = {n["kept"]: n for n in trace.program_notes(remat.NOTE) if n["kept"].startswith("kda/")}
    assert set(kept) == set(remat.KDA_KEPT) and len(kept) == 5
    assert kept[remat.KDA_Q]["shape"] == (2, 24, 32) and kept[remat.KDA_Q]["bytes"] == 2 * 24 * 32 * 4
    # one group of one (padded) chunk: the state that entered it, float32 a head
    # (transposed: [d_v, d_k]), and the padded rows of each of the 2 x 2 heads
    assert kept[remat.KDA_STATES]["shape"] == (4, 1, 16, 16)
    assert kept[remat.KDA_OUT]["shape"] == (4, kda.CHUNK, 16)


def test_decay_floor_counters_reach_the_tracer_through_the_round():
    """``kda/decay_floor/layer_<i>``: one a delta-attention block, on the
    engine's stats path, kept past the tracer's life for the benchmark's
    reader."""
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import FedSim, SimConfig

    rows = np.random.RandomState(0).randint(0, 61, (4, 25)).astype(np.int32)
    train = FederatedArrays(
        {"x": rows[:, :-1], "y": rows[:, 1:], "mask": np.ones((4, 24), np.float32)},
        {0: np.arange(2), 1: np.arange(2, 4)})
    sim = FedSim(
        ClientTrainer(module=_delta_lm(), task="nwp", epochs=1, optimizer=optax.sgd(0.01)),
        train, None, SimConfig(client_num_in_total=2, client_num_per_round=2, batch_size=1,
                               comm_round=1, epochs=1, frequency_of_the_test=100,
                               shuffle_each_round=False, cohort_execution="scan",
                               block_dispatch=False))
    tracer = trace.install()
    try:
        _, history = sim.run()
    finally:
        trace.uninstall()
    floors = trace.last_counters("kda/decay_floor/")
    assert set(floors) == {"kda/decay_floor/layer_0", "kda/decay_floor/layer_1"}
    assert all(v < 0 for v in floors.values())
    assert history[-1]["stats/kda/decay_floor/layer_1"] == pytest.approx(
        floors["kda/decay_floor/layer_1"])
    assert any(e["name"] == "kda/decay_floor/layer_0" for e in tracer.events())
    assert jnp.isfinite(history[-1]["Train/Loss"])
