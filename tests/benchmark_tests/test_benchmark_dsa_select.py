"""``dsa_select_tie_blocks_pct`` (PR 49): the manifest's entry, and its reader on
a fixture: nothing from a program without the counter (the parent of the PR
that added it), the layers' mean in percent with it."""

import importlib
import json
import os

import pytest

from benchmark import moe_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, CELL = "dsa_select_tie_blocks_pct", "keyevl2_silo2"


def test_the_manifest_declares_it_for_the_sparse_attention_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "rounds_per_s", "workloads": [CELL]}
    accepted = next(m for m in manifest["per_layer"] if m["name"] == "dsa_tiles_nonempty_pct")
    assert all(entry[k] == accepted[k] for k in ("source", "layer", "moves", "workloads"))


@pytest.mark.parametrize("counters, want", [
    ({}, None),
    ({"dsa/tiles_nonempty/layer_0": 1.0}, None),  # the parent's counters: none of this name
    ({"dsa/select_tie_blocks/layer_0": 0.0, "dsa/select_tie_blocks/layer_1": 0.0}, 0.0),
    ({"dsa/select_tie_blocks/layer_0": 0.25, "dsa/select_tie_blocks/layer_1": 0.75,
      "dsa/tiles_nonempty/layer_0": 1.0}, 50.0),
], ids=["no_counters", "the_parents_counters", "no_block_tied", "half_the_blocks"])
def test_the_reader_on_a_fixture(monkeypatch, counters, want):
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in counters.items() if k.startswith(prefix)})
    got = importlib.import_module(f"benchmark.layer_metrics.{NAME}").read({})
    assert got is None if want is None else got == pytest.approx(want)
