"""``dsa_index_loss_roofline`` (PR 50): the manifest's entry, its stated work at
the sparse-attention cell's shape by hand, and its reader on a fixture: nothing
from a program without the scope or the note, the hand figure with them,
whatever implements the pass (the parent's plain XLA bears the same scope and
leaves the same note, less its ``index_loss`` field)."""

import importlib
import json
import os

import pytest

from benchmark import dsa_costs, dsa_reduce, kernel_costs, scope_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, CELL = "dsa_index_loss_roofline", "keyevl2_silo2"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DSA = "block_0/attn/attn/dsa"
ROWS = {
    "dsa_index_loss.1": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/index_loss/jit(_call)/"
                             "dsa_index_loss/pallas_call", "custom-call", 500.0)],
    "dsa_index_loss_lse.2": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/index_loss/jit(_call)/"
                                 "dsa_index_loss_lse/pallas_call", "custom-call", 100.0)],
    "fusion.3": [(1, f"jit(f)/fed/fwd_bwd/transpose(jvp(M))/{DSA}/index_loss/mul", "loop", 40.0)],
    "fusion.4": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/select/x", "loop", 300.0)],
    "fusion.5": [(2, "jit(g)/fed/eval/block_0/attn/dsa/index_lossx/y", "loop", 900.0)],
}
NOTE = {"impl": "flash", "select": "mosaic", "index_loss": "mosaic", "shape": (1, 32, 8192, 128),
        "kv_heads": 4, "index_heads": 16, "index_dim": 64, "topk": 2048, "tile": (512, 512),
        "dtype": "bfloat16", "index_dtype": "bfloat16", "selection_bytes": 16_778_240}


def reader():
    return importlib.import_module(f"benchmark.layer_metrics.{NAME}")


def test_the_manifest_declares_it_for_the_sparse_attention_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "rounds_per_s", "workloads": [CELL]}
    accepted = next(m for m in manifest["per_layer"] if m["name"] == "dsa_select_roofline")
    assert {k: v for k, v in entry.items() if k != "name"} == {
        k: v for k, v in accepted.items() if k != "name"}
    timed = next(m for m in manifest["per_layer"] if m["name"] == "dsa_index_loss_time_pct")
    assert timed["workloads"] == entry["workloads"] and timed["moves"] == entry["moves"]


def test_the_stated_work_at_the_cells_shape_by_hand():
    """14,681,088 selected pairs x (2 x 128 x 32 + 3 x 2 x 64 x 16) FLOP; q 67.1
    MB, k 8.4, the indexer's three operands 18.1 and their gradients again, the
    log-sum-exp 1.05, a bit a pair 8.4: 210.5 GFLOP, 121 MB, 1.07 ms, compute."""
    flops, moved = reader().loss_cost(1, 32, 4, 8192, 128, 16, 64, 2048)
    pairs = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert pairs == dsa_costs.selected_pairs(8192, 2048) == 14_681_088
    assert flops == pairs * (8192 + 6144) == pytest.approx(210.47e9, rel=1e-4)
    by_hand = (2 * 8192 * 128 * 36 + 2 * 2 * 8192 * (1024 + 64 + 16) + 4 * 32 * 8192
               + 8192 * 8192 // 8)
    assert moved == by_hand == pytest.approx(121.2e6, rel=1e-3)
    seconds, bound = kernel_costs.least_seconds(flops, moved, PEAKS)
    assert bound == "compute" and seconds * 1e3 == pytest.approx(1.0684, rel=1e-3)
    # float32 operands move twice the bytes; a batch of two does twice the work
    assert reader().loss_cost(1, 32, 4, 8192, 128, 16, 64, 2048, 4, 4)[1] == pytest.approx(
        2 * moved - 4 * 32 * 8192 - 8192 * 8192 // 8)
    assert reader().loss_cost(2, 32, 4, 8192, 128, 16, 64, 2048) == (2 * flops, 2 * moved)
    # while no query has more keys to choose from than it may choose, every causal pair
    assert reader().loss_cost(1, 32, 4, 1024, 128, 16, 64, 2048)[0] == 1024 * 1025 // 2 * 14336


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    monkeypatch.setattr(dsa_reduce, "dsa_notes",
                        lambda: [{**NOTE, "shape": (1, 32, 64, 128)}, NOTE])
    config = {"num_hidden_layers": 6}
    traffic = {"clients_per_round": 2, "local_steps": 2, "batch_size": 1, "seq_len": 8192}
    return {"cell": {"name": "no_such_trace", "config": config, "traffic": traffic},
            "peaks": PEAKS, "traced_rounds": 3, "trace": {"chip0": {"busy_s": 0.010}}}


@pytest.mark.parametrize("rows, notes, want", [
    ("none", "cell", None), ("scope", "none", None), ("scope", "other_shape", None),
    ("scope", "cell", "hand"), ("scope", "parents", "hand"),
], ids=["no_scope", "no_note", "a_note_of_another_shape", "scope_and_note", "the_parents_note"])
def test_the_reader_on_a_fixture(ctx, monkeypatch, rows, notes, want):
    """72 layer-steps (6 layers x 3 rounds x 2 clients x 2 steps) of 1.0684 ms
    over the 500 + 100 + 40 us under the scope, forward and backward (the
    selection's and ``index_lossx``'s are another scope's)."""
    if rows == "none":
        monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {
            "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/Dense_0/dot_general", "convolution", 9.0)]})
    parents = {k: v for k, v in NOTE.items() if k != "index_loss"}
    monkeypatch.setattr(dsa_reduce, "dsa_notes", lambda: {
        "none": [], "other_shape": [{**NOTE, "shape": (1, 32, 64, 128)}], "cell": [NOTE],
        "parents": [parents]}[notes])
    got = reader().read(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(100.0 * 72 * 210.47e9 / 197e12 / 640e-6, rel=1e-4)
        assert got > 100.0  # a fixture's microseconds, not a device's: no clamp hides it
