"""Sharded fold plane tests (docs/PERFORMANCE.md "The server fold plane"):
plane-on must be BITWISE identical to the serial fold on every aggregator
family under adversarial arrival schedules (reversed, interleaved), the
chunk grid must cover ragged accumulators, mid-window snapshot/restore
must compose with non-empty fold queues, and a crashed fold worker must
fail the round loudly instead of wedging the barrier. The end-to-end arms
(flat/robust/q8/async/tree over the wire) live in tools/fold_smoke.py."""

import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg_distributed import (
    CompressedDistAggregator,
    FedAvgDistAggregator,
)
from fedml_tpu.algorithms.fold_plane import (
    DenseFoldTask,
    FoldPlane,
    FoldTask,
)
from fedml_tpu.algorithms.robust_distributed import (
    RobustDistAggregator,
    RobustDistConfig,
)
from fedml_tpu.async_agg.server import AsyncFedAggregator
from fedml_tpu.async_agg.tree import TierAggregator

# reversed and interleaved arrival orders over 5 uploads — both arms see
# the SAME order; the plane must reproduce the serial bits under each
ORDERS = ([4, 3, 2, 1, 0], [0, 4, 1, 3, 2])


def _payloads(n, size=53, seed=0):
    rng = np.random.RandomState(seed)
    flats = [rng.randn(size).astype(np.float32).view(np.uint8)
             for _ in range(n)]
    weights = [float(w) for w in rng.randint(1, 20, n)]
    return flats, weights


def _plane(autostart=True):
    # 2 workers x 7-element chunks over a 53-element accumulator: ragged
    # final chunk, several chunks per worker — the real grid, not a
    # degenerate one-chunk pass
    return FoldPlane(2, chunk_elems=7, autostart=autostart)


# ---------------------------------------------------------------------------
# bitwise identity per family, adversarial orders
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("order", ORDERS)
def test_dense_plane_matches_serial_bitwise(order):
    flats, weights = _payloads(5)
    serial, plane = FedAvgDistAggregator(5), FedAvgDistAggregator(5)
    plane.attach_fold_plane(_plane())
    for _ in range(2):  # two rounds: the tally resets and refills
        for i in order:
            serial.add_local_trained_result(i, flats[i], weights[i])
            plane.add_local_trained_result(i, flats[i], weights[i])
        np.testing.assert_array_equal(serial.aggregate(), plane.aggregate())
    plane.close_fold_plane()


@pytest.mark.parametrize("spec", ["q8", "topk"])
@pytest.mark.parametrize("order", ORDERS)
def test_compressed_plane_matches_serial_bitwise(spec, order):
    import jax

    from fedml_tpu.compress.codec import make_codec

    codec = make_codec(spec, topk_frac=0.25)
    rng = np.random.RandomState(7)
    base = rng.randn(60).astype(np.float32)
    encs, weights = [], [3.0, 1.0, 5.0, 2.0, 8.0]
    for i in range(5):
        delta = {"w": np.asarray(rng.randn(12, 5), np.float32)}
        encs.append(jax.tree.map(
            np.asarray, codec.encode(delta, jax.random.key(i))
        ))
    serial = CompressedDistAggregator(5, codec)
    plane = CompressedDistAggregator(5, codec)
    serial.get_global = plane.get_global = lambda: base.view(np.uint8)
    plane.attach_fold_plane(_plane())
    for i in order:
        serial.add_local_trained_result(i, encs[i], weights[i])
        plane.add_local_trained_result(i, encs[i], weights[i])
    np.testing.assert_array_equal(serial.aggregate(), plane.aggregate())
    plane.close_fold_plane()


@pytest.mark.parametrize("order", ORDERS)
def test_robust_plane_matches_serial_bitwise(order):
    flats, weights = _payloads(5, seed=3)
    # one hostile upload: the plane's prepare must reject it exactly like
    # the serial decision phase (n/rejected stats land in arrival order)
    hostile = flats[1].view(np.float32).copy()
    hostile[4] = np.inf
    flats[1] = hostile.view(np.uint8)
    base = np.random.RandomState(9).randn(53).astype(np.float32)
    cfg = RobustDistConfig(rule="mean", norm_bound=0.8, dp_stddev=0.02,
                           dp_seed=11)
    serial, plane = RobustDistAggregator(5, cfg), RobustDistAggregator(5, cfg)
    serial.get_global = plane.get_global = lambda: base.view(np.uint8)
    plane.attach_fold_plane(_plane())
    for _ in range(2):  # the DP noise schedule advances across rounds
        for i in order:
            serial.add_local_trained_result(i, flats[i], weights[i])
            plane.add_local_trained_result(i, flats[i], weights[i])
        np.testing.assert_array_equal(serial.aggregate(), plane.aggregate())
        assert serial.pop_round_stats() == plane.pop_round_stats()
    plane.close_fold_plane()


def test_non_mean_robust_rule_keeps_serial_path():
    # order-statistic rules stack per-client vectors — not chunkable; the
    # attach gate must leave the plane off and the tally untouched
    flats, weights = _payloads(3)
    base = np.zeros(53, np.float32)
    cfg = RobustDistConfig(rule="median")
    serial, gated = (RobustDistAggregator(3, cfg),
                     RobustDistAggregator(3, cfg))
    serial.get_global = gated.get_global = lambda: base.view(np.uint8)
    gated.attach_fold_plane(_plane())
    assert gated._plane is None
    for i in range(3):
        serial.add_local_trained_result(i, flats[i], weights[i])
        gated.add_local_trained_result(i, flats[i], weights[i])
    np.testing.assert_array_equal(serial.aggregate(), gated.aggregate())


@pytest.mark.parametrize("order", ORDERS)
def test_async_plane_matches_serial_bitwise(order):
    flats, weights = _payloads(5, seed=5)
    serial, plane = AsyncFedAggregator(5), AsyncFedAggregator(5)
    plane.attach_fold_plane(_plane())
    for version in range(2):
        for i in order:
            assert serial.fold_async(i, flats[i], weights[i], version)
            assert plane.fold_async(i, flats[i], weights[i], version)
        assert serial.arrivals == plane.arrivals == 5
        np.testing.assert_array_equal(serial.emit(), plane.emit())
    plane.close_fold_plane()


@pytest.mark.parametrize("order", ORDERS)
def test_tier_plane_matches_serial_bitwise(order):
    # mixed schedule: barrier-free weighted partials (plane-queued, with a
    # stale down-weight) interleaved with an inline first-wins child
    # partial — the inline fold must drain the queue first so everything
    # applies in arrival order
    rng = np.random.RandomState(13)
    parts = [rng.randn(53).astype(np.float64) for _ in range(5)]
    wsums = [float(w) for w in rng.randint(1, 9, 5)]
    scales = [1.0, 0.5, 1.0, 0.25, 1.0]
    serial, plane = TierAggregator(2), TierAggregator(2)
    plane.attach_fold_plane(_plane())
    for agg in (serial, plane):
        for i in order[:4]:
            agg.fold_partial_weighted(parts[i], wsums[i], scales[i])
        agg.add_partial_result(0, parts[order[4]].view(np.uint8),
                               wsums[order[4]])
    a, wa = serial.export_partial()
    b, wb = plane.export_partial()
    np.testing.assert_array_equal(a, b)
    assert wa == wb
    plane.close_fold_plane()


def test_tier_first_partial_copy_through_plane():
    # the first partial is COPIED, not added onto zeros: -0.0 coordinates
    # must survive bit-for-bit through the plane's assign-on-first path
    part = np.array([-0.0, 1.5, -0.0, 2.5, -0.0], np.float64)
    serial, plane = TierAggregator(1), TierAggregator(1)
    plane.attach_fold_plane(FoldPlane(2, chunk_elems=2))
    for agg in (serial, plane):
        agg.fold_partial_weighted(part, 3.0)
    a, _ = serial.export_partial()
    b, _ = plane.export_partial()
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
    plane.close_fold_plane()


# ---------------------------------------------------------------------------
# snapshot / restore with non-empty fold queues
# ---------------------------------------------------------------------------


def test_snapshot_mid_window_with_queued_tasks():
    # autostart=False: no worker threads, so the submitted tasks provably
    # sit queued until the snapshot's drain folds them inline
    flats, weights = _payloads(4)
    serial, plane = FedAvgDistAggregator(4), FedAvgDistAggregator(4)
    fp = _plane(autostart=False)
    plane.attach_fold_plane(fp)
    for i in (2, 0):
        serial.add_local_trained_result(i, flats[i], weights[i])
        plane.add_local_trained_result(i, flats[i], weights[i])
    assert fp.queued() == 2
    snap_s, snap_p = serial.snapshot_state(), plane.snapshot_state()
    assert fp.queued() == 0  # the snapshot drained the window
    np.testing.assert_array_equal(snap_s["acc"], snap_p["acc"])
    assert snap_s["wsum"] == snap_p["wsum"]
    assert snap_s["uploaded"] == snap_p["uploaded"]
    # restore the mid-window state into a FRESH plane aggregator and finish
    # the round: bitwise identical to the serial continuation
    resumed = FedAvgDistAggregator(4)
    resumed.attach_fold_plane(_plane())
    resumed.restore_state(snap_p)
    for i in (3, 1):
        serial.add_local_trained_result(i, flats[i], weights[i])
        resumed.add_local_trained_result(i, flats[i], weights[i])
    np.testing.assert_array_equal(serial.aggregate(), resumed.aggregate())
    resumed.close_fold_plane()


def test_restore_discards_queued_tasks_against_old_tally():
    flats, weights = _payloads(3, seed=8)
    serial, plane = FedAvgDistAggregator(3), FedAvgDistAggregator(3)
    plane.attach_fold_plane(_plane(autostart=False))
    baseline = serial.snapshot_state()  # empty tally
    for i in range(3):
        plane.add_local_trained_result(i, flats[i], weights[i])
    # restore wholesale: in-flight folds retire against the PRE-restore
    # tally and are then overwritten, exactly like a serial restore
    plane.restore_state(baseline)
    serial.restore_state(baseline)
    for i in (1, 0):
        serial.add_local_trained_result(i, flats[i], weights[i])
        plane.add_local_trained_result(i, flats[i], weights[i])
    np.testing.assert_array_equal(serial.aggregate(), plane.aggregate())
    plane.close_fold_plane()


# ---------------------------------------------------------------------------
# worker-crash propagation
# ---------------------------------------------------------------------------


class _PoisonTask(FoldTask):
    def __init__(self):
        super().__init__(53)

    def _prepare(self):
        raise ValueError("poisoned upload")


def test_worker_crash_fails_the_round_loudly():
    flats, weights = _payloads(2)
    agg = FedAvgDistAggregator(2)
    agg.attach_fold_plane(_plane(autostart=False))
    agg.add_local_trained_result(0, flats[0], weights[0])
    agg._fold_task = lambda payload, weight: _PoisonTask()
    agg.add_local_trained_result(1, flats[1], weights[1])
    with pytest.raises(RuntimeError, match="fold plane worker failed"):
        agg.aggregate()


def test_crash_surfaces_from_live_workers_too():
    # same failure through the real worker threads: the error is recorded
    # by whichever thread hit it and re-raised at the next drain
    plane = FoldPlane(2, chunk_elems=7)
    acc = np.zeros(53, np.float64)
    plane.submit(_PoisonTask(), acc)
    with pytest.raises(RuntimeError, match="fold plane worker failed"):
        # the workers may or may not have popped the task yet — drain
        # either helps fold it (hitting the memoized error) or re-raises
        # the recorded one; both paths must surface
        plane.drain()
    plane.close()


def test_prepare_error_is_memoized_not_double_raised_side_effects():
    task = _PoisonTask()
    with pytest.raises(ValueError, match="poisoned upload"):
        task.ensure_prepared()
    with pytest.raises(ValueError, match="poisoned upload"):
        task.ensure_prepared()  # memoized: same error object, no re-run


# ---------------------------------------------------------------------------
# plane mechanics
# ---------------------------------------------------------------------------


def test_chunk_grid_covers_every_element_once():
    plane = FoldPlane(3, chunk_elems=7, autostart=False)
    n = 53
    seen = np.zeros(n, np.int64)
    for w in range(plane.workers):
        for lo, hi in plane._owned(w, n):
            assert 0 <= lo < hi <= n
            seen[lo:hi] += 1
    assert (seen == 1).all()


def test_submit_after_close_raises():
    plane = FoldPlane(1, autostart=False)
    plane.close()
    with pytest.raises(RuntimeError, match="closed"):
        plane.submit(DenseFoldTask(np.zeros(4, np.float32), 1.0),
                     np.zeros(4, np.float64))


def test_plane_validates_knobs():
    with pytest.raises(ValueError):
        FoldPlane(0)
    with pytest.raises(ValueError):
        FoldPlane(1, chunk_elems=0)


# ---------------------------------------------------------------------------
# satellite tooling: fleet-report fold section, tier-1 budget report
# ---------------------------------------------------------------------------


def test_fleet_report_renders_fold_section(tmp_path):
    import json

    from fedml_tpu.obs import metrics as metricslib
    from fedml_tpu.obs.registry import FleetHealth, MetricRegistry
    from tools.fleet_report import (
        attach_fold_plane,
        format_text,
        load_fleet,
        load_process_registry,
        summarize,
    )

    reg = MetricRegistry()
    reg.gauge(metricslib.FOLD_QUEUE_DEPTH, 3)
    reg.observe(metricslib.FOLD_STALL_MS, 1.5)
    fh = FleetHealth()
    fh.counter(1, "uploads")
    path = tmp_path / "fleet.json"
    path.write_text(json.dumps({
        "totals": fh.snapshot(), "rounds_recorded": 2,
        "registry": reg.snapshot(),
    }))
    view, rounds = load_fleet(path)
    report = attach_fold_plane(summarize(view, rounds=rounds),
                               load_process_registry(path))
    assert report["fold"]["queue_depth"] == 3
    assert report["fold"]["stall_ms"]["count"] == 1
    text = format_text(report)
    assert "server fold plane" in text and "fold stall ms" in text
    # a fleet file with no registry section (pre-plane runs) renders clean
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"totals": fh.snapshot(), "rounds": [0]}))
    report2 = attach_fold_plane(summarize(*load_fleet(bare)),
                                load_process_registry(bare))
    assert "fold" not in report2
    assert "server fold plane" not in format_text(report2)


def test_t1_budget_parses_durations_and_headroom():
    from tools.t1_budget import build_report, parse_log

    log = "\n".join([
        "  12.34s call     tests/test_a.py::test_x",
        "  0.50s setup    tests/test_a.py::test_x",
        "  90.00s call     tests/test_b.py::test_y[q8]",
        "= 639 passed, 4 skipped, 37 deselected in 696.39s =",
    ])
    report = build_report(parse_log(log))
    assert report["total_s"] == 696.39
    assert report["over_budget"] is False
    # against the driver's command: a working budget of 1,100 s under its
    # 1,470 s kill
    assert report["budget_headroom_s"] == pytest.approx(403.61)
    assert report["timeout_headroom_s"] == pytest.approx(773.61)
    # call + setup phases aggregate per test id; files roll tests up
    assert report["slowest_tests"][0]["test"] == "tests/test_b.py::test_y[q8]"
    assert report["slowest_tests"][1]["seconds"] == pytest.approx(12.84)
    assert report["slowest_files"][1]["file"] == "tests/test_a.py"
    assert report["outcomes"]["passed"] == 639


# ---------------------------------------------------------------------------
# tier-1 smoke
# ---------------------------------------------------------------------------


def test_fold_smoke_tool_runs():
    """tools/fold_smoke.py is the tier-1 bit-identity guard the docs point
    at — run it in-process (mirrors the async/wire smokes' wiring)."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).parent.parent / "tools" / "fold_smoke.py"
    spec = importlib.util.spec_from_file_location("fold_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0
