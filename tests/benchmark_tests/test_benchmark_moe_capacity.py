"""The two readers of the routed layer's capacity counters
(``benchmark/layer_metrics/moe_rows_touched_pct.py``,
``moe_overflow_tiles.py``) on recorded counters, in both routed families'
configurations, and on a program that has no such counter."""

import importlib
import json
import os

import pytest

from benchmark import moe_reduce
from benchmark import run as benchrun

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAMES = ("moe_rows_touched_pct", "moe_overflow_tiles")
CELLS = ["smallthinker21b_silo2", "joyai_flash_silo2"]
# two routed blocks of 1 x 64 tokens: the first within its 48 rows, the second
# three tiles of 8 past them; and counters of others' that must not be read
COUNTERS = {"moe/rows_touched/layer_0": 48.0, "moe/rows_touched/layer_1": 72.0,
            "moe/overflow_tiles/layer_0": 0.0, "moe/overflow_tiles/layer_1": 3.0,
            "moe/assignments_held/layer_0": 40.0, "engine/other": 3.0}


def ctx_for(top_k_key):
    return {"cell": {"name": "no_such_trace", "config": {top_k_key: 2},
                     "traffic": {"batch_size": 1, "seq_len": 64}}}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.fixture
def counters(monkeypatch):
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {
        k: v for k, v in COUNTERS.items() if k.startswith(prefix)})


@pytest.mark.parametrize("top_k_key", ["moe_num_active_primary_experts", "num_experts_per_tok"])
def test_readers_give_the_hand_figures(counters, top_k_key):
    ctx = ctx_for(top_k_key)
    # (48 + 72) / 2 blocks over 64 tokens x 2 choices
    assert read("moe_rows_touched_pct", ctx) == pytest.approx(100.0 * 60 / 128)
    assert read("moe_overflow_tiles", ctx) == 3.0
    cell = {"per_layer": [{"name": n, "unit": "x"} for n in NAMES]}
    assert set(benchrun.layer_metrics(cell, ctx)) == set(NAMES)


def test_readers_find_nothing_in_a_program_without_the_counters(monkeypatch):
    """The parent of the PR that added them, and a family that routes
    nothing: None, and the result line leaves the metric out."""
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {})
    ctx = ctx_for("num_experts_per_tok")
    assert [read(n, ctx) for n in NAMES] == [None, None]
    assert benchrun.layer_metrics({"per_layer": [{"name": n, "unit": "x"} for n in NAMES]},
                                  ctx) == {}


def test_a_family_without_a_top_k_reports_no_share(counters):
    assert read("moe_rows_touched_pct", {"cell": {
        "config": {}, "traffic": {"batch_size": 1, "seq_len": 64}}}) is None


def test_manifest_lists_both_routed_cells_for_both():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, unit in zip(NAMES, ("%", "count")):
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower", "source": "program_counter",
            "layer": "local training and models", "moves": "rounds_per_s", "workloads": CELLS}
    for cell in CELLS:
        assert {m["name"] for m in benchrun.load_cell(cell)["per_layer"]} >= set(NAMES)
