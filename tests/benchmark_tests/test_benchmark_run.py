"""``benchmark/run.py`` end to end: it refuses a machine without a TPU, and,
with only its look for a chip skipped, a whole run at toy size on the CPU
produces every metric the manifest lists for the cell, stamps the device it
really ran on, and comes out not correct when the timed path is broken
underneath. The figures of such a run are never device metrics: the device
stamp says ``cpu`` and nothing is written anywhere.
"""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from benchmark import peaks as peakslib
from benchmark import run as benchrun
from benchmark import trace_reduce

from ._tiny import ROOT, TOY_BN, tiny_cell

TIGHT = {"loss_gap": 1e-5, "norm_gap": 1e-4, "update_rel_l2": 1e-3, "eval_test_loss_gap": 1e-5}


def test_refuses_a_machine_without_a_tpu():
    t0 = time.time()
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet18gn_dev10", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert time.time() - t0 < 60
    assert "'cpu'" in done.stderr and "no result" in done.stderr
    for line in done.stdout.splitlines():
        assert not line.startswith("{"), "a result line was printed"


def test_unknown_workload_and_unknown_device_are_errors():
    with pytest.raises(SystemExit, match="no workload"):
        benchrun.load_cell("no_such_cell")
    with pytest.raises(KeyError, match="no peaks on record"):
        peakslib.peaks_for("TPU v99")
    assert peakslib.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the look for a chip; a CPU trace has no device plane, so the
    fixture trace stands in for the reduced one."""
    fixture = os.path.join(ROOT, "benchmark", "fixtures", "trace_fixture.json")
    monkeypatch.setattr(benchrun, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peakslib, "peaks_for", lambda kind: peakslib.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(trace_reduce, "reduce_xplane",
                        lambda path, n: trace_reduce.reduce_fixture(fixture))


def tight_cell(name):
    cell = tiny_cell(name)
    limits = cell["config"]["check"]["limits"]
    cell["config"]["check"]["limits"] = {k: TIGHT[k.split(".")[0]] for k in limits}
    return cell


@pytest.mark.parametrize("name", [TOY_BN, "cgpt13b_silo2"])
def test_untraced_run_reports_the_cells_end_to_end_metrics(on_cpu, name):
    cell = tight_cell(name)
    result = benchrun.run(cell, 2 ** 31 + 5, 0.5, traced=False)
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert {"rounds_per_s", "setup_s"} <= set(result["metrics"])
    assert ("eval_samples_per_s" in result["metrics"]) == (name == TOY_BN)
    for m in cell["end_to_end"]:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert result["correct"] is True and result["failed"] == 0
    unit = benchrun.dispatch_unit(cell)
    assert result["attempted"] >= unit and result["attempted"] % unit == 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    json.dumps(result)


def test_traced_run_reports_the_cells_per_layer_metrics(on_cpu):
    cell = tight_cell(TOY_BN)
    result = benchrun.run(cell, 7, 0.5, traced=True)
    # the CPU reports no memory statistics, so that reader finds nothing
    listed = {m["name"]: m for m in cell["per_layer"] if m["name"] != "peak_hbm_gb"}
    assert set(result["metrics"]) == set(listed)
    for name, m in listed.items():
        assert result["metrics"][name]["unit"] == m["unit"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["host_stage_ms"]["value"] > 0
    assert result["metrics"]["device_idle_pct"]["value"] == pytest.approx(40.0)
    # MXU time: the convolution fusion (1.0 s) and the dot fusion (1.5 s) of 6.0 s busy
    assert result["metrics"]["conv_time_pct"]["value"] == pytest.approx(250 / 6)
    assert result["device"]["busy_s"] == pytest.approx(4.0)  # the fixture's two chips, averaged
    assert result["device"]["window_s"] == pytest.approx(10.0)
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert result["correct"] is True
    json.dumps(result)


def test_a_round_that_returns_its_state_unchanged_is_not_correct(on_cpu, monkeypatch):
    from fedml_tpu.sim.engine import FedSim

    sound = FedSim._aggregate_tail

    def stuck(self, global_variables, *args, **kwargs):
        _, server_state, metrics = sound(self, global_variables, *args, **kwargs)
        return global_variables, server_state, metrics

    monkeypatch.setattr(FedSim, "_aggregate_tail", stuck)
    result = benchrun.run(tight_cell("resnet18gn_dev10"), 11, 0.5, traced=False)
    assert result["correct"] is False and result["failed"] == 0


def test_a_client_left_out_of_the_round_is_not_correct(on_cpu, monkeypatch):
    """Part of the batch left out: the cohort's last client is given no
    weight, so the aggregate and the round's loss miss it."""
    from fedml_tpu.sim.engine import FedSim

    sound = FedSim._host_cohort_indices

    def drop_last(self, cohort, round_idx):
        idx, weights, num_steps = sound(self, cohort, round_idx)
        weights = weights.copy()
        weights[len(cohort) - 1] = 0.0
        return idx, weights, num_steps

    monkeypatch.setattr(FedSim, "_host_cohort_indices", drop_last)
    result = benchrun.run(tight_cell(TOY_BN), 13, 0.5, traced=False)
    assert result["correct"] is False


def test_window_and_trace_round_counts():
    blocks = [{"round": 4, "round_time": 0.1}]
    assert benchrun.window_rounds(30.0, 5, 0.75, blocks) == 200  # 40 units of 5
    assert benchrun.window_rounds(1.0, 50, 2.6, blocks) == 50  # never less than one unit
    # no periodic eval: 0.2 s of the 1.16 s call is the last eval, paid once
    assert benchrun.window_rounds(30.0, 1, 1.16, [{"round": 3, "round_time": 0.96}]) == 31
    assert benchrun.trace_rounds(5, 0.75) == 10  # about 2 s, whole units
    assert benchrun.trace_rounds(1, 0.9) == 3  # at least three rounds
    assert benchrun.trace_rounds(50, 2.6) == 50  # one unit covers three rounds
    assert benchrun.dispatch_unit({"traffic": {"frequency_of_the_test": 10000}}) == 1
    assert benchrun.dispatch_unit({"traffic": {"frequency_of_the_test": 50}}) == 50
    history = [{"round": r, "Train/Loss": float(r)} for r in range(5, 10)]
    assert benchrun.local_losses(history, {"frequency_of_the_test": 5}) == [
        (5, 5.0), (6, 6.0), (7, 7.0), (8, 8.0)]
