"""The pipelined round driver (sim/prefetch.py) must be bit-identical to the
serial driver — same cohorts, same rng keys, same metrics — on both staging
paths and on more than one mesh shape, and its background staging thread
must never outlive a run (even one that dies mid-round). Also covers the
vectorized cohort builder against its per-client-loop oracle."""

import dataclasses
import json
import threading

import jax
import numpy as np
import optax
import pytest

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.synthetic import gaussian_blobs
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.parallel import mesh as meshlib
from fedml_tpu.sim.engine import FedSim, SimConfig
from fedml_tpu.sim.prefetch import THREAD_NAME, MetricsDrain, Prefetcher


def _fixture(n_clients=6, samples_per_client=33, partition_method="homo"):
    train, test = gaussian_blobs(
        n_clients=n_clients, samples_per_client=samples_per_client,
        num_classes=4, partition_method=partition_method, seed=5,
    )
    trainer = ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.2),
        epochs=2,
    )
    return train, test, trainer


def _no_prefetch_threads():
    return not any(
        t.name == THREAD_NAME and t.is_alive() for t in threading.enumerate()
    )


def _assert_histories_match(h_pipe, h_serial):
    assert len(h_pipe) == len(h_serial)
    for rec_p, rec_s in zip(h_pipe, h_serial):
        # identical key sets — a spurious extra key (e.g. eval metrics
        # leaking onto non-eval rounds) must fail, not pass silently
        assert set(rec_p) == set(rec_s), (rec_p, rec_s)
        for key, val in rec_s.items():
            if key == "round_time":  # wall-clock, legitimately differs
                continue
            assert rec_p[key] == val, (key, rec_p, rec_s)


@pytest.mark.parametrize("n_mesh_devices", [1, 8])
@pytest.mark.parametrize("stage_on_device", [True, False])
def test_pipelined_run_bit_identical_to_serial(n_mesh_devices, stage_on_device):
    train, test, trainer = _fixture()
    mesh = meshlib.client_mesh(jax.devices()[:n_mesh_devices])
    cfg = SimConfig(
        client_num_in_total=6, client_num_per_round=4, batch_size=8,
        comm_round=5, epochs=2, frequency_of_the_test=2,
        straggler_frac=0.5, seed=0, stage_on_device=stage_on_device,
    )
    v_pipe, h_pipe = FedSim(
        trainer, train, test, dataclasses.replace(cfg, pipeline_depth=2),
        mesh=mesh,
    ).run()
    v_ser, h_ser = FedSim(
        trainer, train, test, dataclasses.replace(cfg, pipeline_depth=0),
        mesh=mesh,
    ).run()
    for a, b in zip(jax.tree.leaves(v_pipe), jax.tree.leaves(v_ser)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r["round"] for r in h_pipe] == list(range(5))
    _assert_histories_match(h_pipe, h_ser)
    assert _no_prefetch_threads()


def test_pipelined_block_dispatch_bit_identical():
    """Pipelining must also hold under block dispatch (the prefetch thread
    stages the NEXT eval block while the current block executes)."""
    train, test, trainer = _fixture()
    cfg = SimConfig(
        client_num_in_total=6, client_num_per_round=4, batch_size=8,
        comm_round=6, epochs=1, frequency_of_the_test=3, seed=0,
        stage_on_device=True, block_dispatch=True,
    )
    v_pipe, h_pipe = FedSim(
        trainer, train, test, dataclasses.replace(cfg, pipeline_depth=1)
    ).run()
    v_ser, h_ser = FedSim(
        trainer, train, test, dataclasses.replace(cfg, pipeline_depth=0)
    ).run()
    for a, b in zip(jax.tree.leaves(v_pipe), jax.tree.leaves(v_ser)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r["round"] for r in h_pipe] == list(range(6))
    _assert_histories_match(h_pipe, h_ser)


def test_run_rounds_pipelined_matches_serial(tmp_path):
    """The repro loop's pipelined path writes the same records (in the same
    round order) as its serial path."""
    import json

    from fedml_tpu.exp._loop import run_rounds

    train, test, trainer = _fixture()
    cfg = SimConfig(
        client_num_in_total=6, client_num_per_round=4, batch_size=8,
        comm_round=6, frequency_of_the_test=2, seed=0,
    )
    out_p = str(tmp_path / "pipe.jsonl")
    out_s = str(tmp_path / "serial.jsonl")
    recs_p, _ = run_rounds(FedSim(trainer, train, test, cfg), cfg, out_p)
    recs_s, _ = run_rounds(
        FedSim(trainer, train, test,
               dataclasses.replace(cfg, pipeline_depth=0)),
        dataclasses.replace(cfg, pipeline_depth=0), out_s,
    )
    assert recs_p == recs_s
    assert [r["round"] for r in recs_p] == list(range(6))
    assert [json.loads(line) for line in open(out_p)] == recs_p
    assert _no_prefetch_threads()


def _rounds_on_file(path):
    return [json.loads(line)["round"] for line in path.read_text().splitlines()]


def test_prefetch_shutdown_on_midrun_exception(tmp_path):
    """An exception mid-run must not leak the staging thread or wedge a
    subsequent run_rounds; completed-but-undrained rounds are salvaged
    into the metrics file, and then the failure propagates."""
    from fedml_tpu.exp._loop import run_rounds

    train, test, trainer = _fixture()
    cfg = SimConfig(
        client_num_in_total=6, client_num_per_round=4, batch_size=8,
        comm_round=6, frequency_of_the_test=2, seed=0,
    )
    sim = FedSim(trainer, train, test, cfg)
    orig = sim.stage_round

    def boom(r, root):
        if r >= 3:
            raise RuntimeError("staging blew up")
        return orig(r, root)

    sim.stage_round = boom
    with pytest.raises(RuntimeError, match="staging blew up"):
        run_rounds(sim, cfg, str(tmp_path / "m.jsonl"))
    assert _rounds_on_file(tmp_path / "m.jsonl") == [0, 1, 2]
    assert _no_prefetch_threads()
    # the engine (and a fresh prefetch thread) still works afterwards
    sim.stage_round = orig
    records2, _ = run_rounds(sim, cfg, str(tmp_path / "m2.jsonl"))
    assert len(records2) == 6
    assert _no_prefetch_threads()


def test_eval_failure_keeps_drained_rounds(tmp_path):
    """An eval_record failure must not lose rounds that trained fine: the
    pipelined partial record ends exactly where the serial one does."""
    from fedml_tpu.exp._loop import run_rounds

    train, test, trainer = _fixture()
    cfg = SimConfig(
        client_num_in_total=6, client_num_per_round=4, batch_size=8,
        comm_round=6, frequency_of_the_test=4, seed=0,
    )

    def partial_records(depth):
        sim = FedSim(trainer, train, test,
                     dataclasses.replace(cfg, pipeline_depth=depth))
        orig = sim.eval_record
        sim.eval_record = lambda v: (_ for _ in ()).throw(
            RuntimeError("eval blew up")
        )
        with pytest.raises(RuntimeError, match="eval blew up"):
            run_rounds(sim, cfg, str(tmp_path / f"d{depth}.jsonl"))
        sim.eval_record = orig
        return _rounds_on_file(tmp_path / f"d{depth}.jsonl")

    # eval fires at round 3; rounds 0-2 completed and must be reported
    assert partial_records(1) == partial_records(0) == [0, 1, 2]
    assert _no_prefetch_threads()


def test_prefetcher_orders_and_propagates_errors():
    staged = []

    def stage(t):
        if t == 3:
            raise RuntimeError("boom")
        staged.append(t)
        return t * 10

    p = Prefetcher(range(5), stage, depth=2)
    try:
        assert [p.get(i) for i in range(3)] == [0, 10, 20]
        with pytest.raises(RuntimeError, match="boom"):
            p.get(3)
    finally:
        p.close()
    assert staged == [0, 1, 2]  # nothing staged past the failure
    assert _no_prefetch_threads()


def test_prefetcher_delivers_final_payload_after_worker_exit():
    """A payload enqueued just before the worker exits must be delivered,
    not mistaken for a died-short worker (the end-of-plan race)."""
    p = Prefetcher([0], lambda t: t * 10, depth=2)
    p._thread.join(timeout=10)  # worker stages its only task and exits
    assert not p._thread.is_alive()
    assert p.get(0) == 0
    p.close()
    assert _no_prefetch_threads()


def test_prefetcher_close_with_producer_blocked():
    """close() must unblock a producer stuck on a full queue (a consumer
    that stops early must not wedge)."""
    p = Prefetcher(range(100), lambda t: t, depth=1)
    assert p.get(0) == 0
    p.close()
    assert _no_prefetch_threads()


def test_metrics_drain_depth_and_flush_order():
    d = MetricsDrain(2)
    assert d.push("a", {"x": 1}) == []
    assert d.push("b", {"x": 2}) == []
    assert d.push("c", {"x": 3}) == [("a", {"x": 1})]
    assert d.flush() == [("b", {"x": 2}), ("c", {"x": 3})]
    assert d.flush() == []
    # depth 0 degrades to fetch-every-push (the serial driver)
    d0 = MetricsDrain(0)
    assert d0.push("a", {"x": 1}) == [("a", {"x": 1})]


def test_cohort_index_map_matches_loop_reference():
    """The vectorized builder is bit-identical to the per-client loop it
    replaced (unshuffled; shuffle draws differ by construction)."""
    from fedml_tpu.sim.cohort import _cohort_index_map_loop, cohort_index_map

    train, _, _ = _fixture(n_clients=7, samples_per_client=29,
                           partition_method="hetero")
    cohort = np.asarray([5, 1, 6, 2])
    for steps in (None, 2):
        idx_v, w_v = cohort_index_map(train, cohort, 8, steps=steps)
        idx_l, w_l = _cohort_index_map_loop(train, cohort, 8, steps=steps)
        np.testing.assert_array_equal(idx_v, idx_l)
        np.testing.assert_array_equal(w_v, w_l)


def test_cohort_index_map_shuffle_is_per_client_permutation():
    from fedml_tpu.sim.cohort import cohort_index_map

    train, _, _ = _fixture(n_clients=7, samples_per_client=29,
                           partition_method="hetero")
    cohort = np.asarray([0, 3, 6])
    idx, _ = cohort_index_map(train, cohort, 8,
                              rng=np.random.RandomState(3))
    plain, _ = cohort_index_map(train, cohort, 8)
    shuffled_any = False
    for row, base, cid in zip(
        idx.reshape(len(cohort), -1), plain.reshape(len(cohort), -1), cohort
    ):
        got = row[row >= 0]
        # a permutation of exactly the client's rows, padding at the tail
        np.testing.assert_array_equal(
            np.sort(got), np.sort(train.partition[int(cid)])
        )
        assert (row >= 0).sum() == (base >= 0).sum()
        shuffled_any |= bool((got != base[base >= 0]).any())
    assert shuffled_any  # astronomically unlikely to be the identity


def test_pipeline_smoke_tool_runs():
    """tools/pipeline_smoke.py is the tier-1 guard the docs point at — run
    it in-process."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).parent.parent / "tools" / "pipeline_smoke.py"
    spec = importlib.util.spec_from_file_location("pipeline_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0
