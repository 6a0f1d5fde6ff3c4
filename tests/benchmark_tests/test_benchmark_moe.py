"""The configuration ``smallthinker_21b_a3b_cut`` and the cell
``smallthinker21b_silo2`` at a toy size on the CPU, in float32: the normal
path (``FedSim.run``) equals the plain reference, a lower precision or a
broken path in its place does not; the manifest's entries; the FLOPs
arithmetic; and each new per-layer reader on hand figures. The figures such
a toy cell produces are never device metrics.

Nothing here describes a TPU topology; the file is safe under xdist.
"""

import copy
import importlib
import json
import os

import jax
import pytest

from benchmark import moe_costs, moe_reduce, scope_reduce
from benchmark import run as benchrun
from benchmark.families import moe_lm as family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "smallthinker21b_silo2", "smallthinker_21b_a3b_cut"
# this file's own toy overrides: hidden 64, 4 / 2 heads of 16, 8 router outputs
# with experts 2 .. 5 held, top-2 of width 32, window 8, T 32, one period
TOY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "moe_router_outputs": 8, "moe_num_primary_experts": 4,
              "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 32,
              "sliding_window_size": 8, "vocab_size": 97, "compute_dtype": "float32",
              "remat": False}
TOY_TRAFFIC = {"seq_len": 32, "ramp_alphabet": 97}
TIGHT = {"loss_gap": 1e-5, "norm_gap": 1e-4, "update_rel_l2": 1e-3}
SEED = 2 ** 31 + 77


def toy_cell():
    cell = benchrun.load_cell(CELL, ROOT)
    cell["config"] = {**copy.deepcopy(cell["config"]), **TOY_CONFIG}
    cell["config"]["share"]["experts_first"] = 2
    cell["traffic"] = {**cell["traffic"], **TOY_TRAFFIC}
    return cell


def program_check(cell):
    sim, variables = benchrun.build_sim(cell, SEED, jax.devices()[:1])
    return benchrun.program_check(sim, variables, cell)[0]


def within(numbers, limits):
    return all(v <= limits[k.split(".")[0]] for k, v in numbers.items()
               if k.split(".")[0] in limits)


@pytest.fixture(scope="module")
def reference():
    cell = toy_cell()
    shapes = program_check_shapes(cell)
    return cell, benchrun.reference_check(cell, SEED, cell["traffic"]["check_rounds"], shapes)


def program_check_shapes(cell):
    job = cell["family"].build(cell["config"], cell["traffic"], SEED)
    sample = {k: jax.ShapeDtypeStruct((1,) + v.shape[1:], v.dtype)
              for k, v in job["train"].arrays.items()}
    return jax.eval_shape(job["trainer"].init, jax.random.key(0), sample)


def test_toy_cell_is_correct(reference):
    cell, ref = reference
    check = program_check(cell)
    numbers = benchrun.compare(check, ref, family.HEAD)
    assert check["losses"], "no local training loss was compared"
    assert within(numbers, TIGHT), numbers
    assert benchrun.judge(numbers, TIGHT)


@pytest.mark.parametrize("broken", ["window_ignored", "an_experts_part_left_out"])
def test_a_broken_path_is_not_correct(reference, monkeypatch, broken):
    """The window layers attending to every earlier key, or the last held
    expert's part missing from the mixture: the update's distance fails."""
    from fedml_tpu.models import moe_transformer
    from fedml_tpu.ops import moe

    if broken == "window_ignored":
        real = moe_transformer.flash_attention_head_parallel
        monkeypatch.setattr(moe_transformer, "flash_attention_head_parallel",
                            lambda *a, window=None, **k: real(*a, **k))
    else:
        real = moe.expert_layer
        monkeypatch.setattr(
            moe, "expert_layer",
            lambda u, ids, w, gate, up, down, *, first, count, dtype: real(
                u, ids, w, gate[:-1], up[:-1], down[:-1], first=first, count=count - 1,
                dtype=dtype))
    cell, ref = reference
    numbers = benchrun.compare(program_check(cell), ref, family.HEAD)
    assert not within(numbers, TIGHT), numbers
    assert numbers["update_rel_l2.params"] > 10 * TIGHT["update_rel_l2"], numbers


def test_fp8_control_fails(reference):
    cell, ref = reference
    stand_in = benchrun.reference_check(cell, SEED, ref["rounds"], program_check_shapes(cell),
                                        precision="fp8")
    numbers = benchrun.compare(stand_in, ref, family.HEAD)
    assert numbers["update_rel_l2.params"] > 3 * TIGHT["update_rel_l2"], numbers
    assert not benchrun.judge(numbers, {"update_rel_l2": TIGHT["update_rel_l2"]})


def test_rows_span_the_held_vocabulary_and_never_repeat_a_token():
    x, y = family.ramp_rows(SEED, 4, 8192, 37984)
    assert x.shape == (4, 8192) and (y[:, :-1] == x[:, 1:]).all()
    assert x.min() >= 0 and x.max() < 37984
    assert all(len(set(row)) == 8192 for row in x)
    assert len({tuple(row[:2]) for row in x}) == 4
    again, _ = family.ramp_rows(SEED, 4, 8192, 37984)
    other, _ = family.ramp_rows(SEED + 1, 4, 8192, 37984)
    assert (again == x).all() and (other != x).any()
    with pytest.raises(ValueError, match="repeats"):
        family.ramp_rows(SEED, 2, 64, 64)


# -- the manifest's new entries and the configuration file ---------------------


def test_manifest_entries_and_the_configuration_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["configs"][-1]
    assert entry["name"] == CONFIG and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "silo2", 1)
    new = {"moe_time_pct", "moe_dispatch_time_pct", "moe_experts_roofline",
           "flash_window_roofline", "attn_tiles_visited_pct", "moe_held_share_pct",
           "moe_load_max_over_mean"}
    mine = [m for m in manifest["per_layer"] if m["name"] in new]
    assert {m["name"] for m in mine} == new
    assert [m["name"] for m in manifest["per_layer"][-7:]] == [m["name"] for m in mine]
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "rounds_per_s"
        assert callable(importlib.import_module(f"benchmark.layer_metrics.{m['name']}").read)
    loaded = benchrun.load_cell(CELL, ROOT)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert new <= reported and {"mfu_pct", "peak_hbm_gb", "device_idle_pct", "longest_gap_ms",
                                "compiles_in_window", "host_stage_ms",
                                "host_sync_ms"} <= reported
    assert loaded["traffic"] == {
        "clients_total": 2, "clients_per_round": 2, "local_steps": 2, "batch_size": 1,
        "seq_len": 8192, "ramp_alphabet": 37984, "frequency_of_the_test": 10000,
        "eval_batch_size": 1, "cohort_execution": "scan", "check_rounds": 2}
    config = loaded["config"]
    published = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
                 "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
                 "num_attention_heads": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
                 "rope_theta": 1500000, "sliding_window_size": 4096}
    assert {k: config[k] for k in published} == published
    assert config["rope_layout"] == config["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["moe_router_outputs"], config["vocab_size"]) == (4, 16, 64, 37984)
    assert set(config["reduced"]) == set(entry["reduced"])
    assert {"router input", "rotary pairing", "window", "optimizer"} <= set(config["assumed"])
    assert config["share"]["chips_per_layer"] == 4 and config["optimizer"]["momentum"] == 0.0
    assert family.layer_kinds(config) == ("global", "window", "window", "window")


# -- the FLOPs arithmetic, pinned ---------------------------------------------------


def test_flops_closed_forms():
    """ISSUE 28: forward a token 4 x (41.9 + 0.3 + 17.7) + 58.7 + 3 x 44.0 +
    194.5 = 625 MFLOP; 61.4 TFLOP a round of 32,768 tokens."""
    cell = benchrun.load_cell(CELL, ROOT)
    config, traffic = cell["config"], cell["traffic"]
    assert moe_costs.visible_pairs(8192, None) == 8192 * 8193 // 2
    assert moe_costs.visible_pairs(8192, 4096) == 25_167_872
    assert moe_costs.visible_pairs(6, 2) == 1 + 2 * 5 and moe_costs.visible_pairs(6, 9) == 21
    assert moe_costs.layer_windows(config) == [None, 4096, 4096, 4096]
    fwd = moe_costs.forward_flops_per_token(config, 8192)
    by_hand = (4 * (2 * 2560 * 8192 + 2 * 2560 * 64 + 1.5 * 6 * 2560 * 768)
               + 4 * 3584 * (8193 / 2 + 3 * 25_167_872 / 8192) + 2 * 2560 * 37984)
    assert fwd == pytest.approx(by_hand, rel=1e-12)
    assert fwd / 1e6 == pytest.approx(625.1, abs=0.1)
    assert family.samples_per_round(config, traffic) == 32768
    assert family.flops_per_round(config, traffic) / 1e12 == pytest.approx(61.45, abs=0.05)
    assert family.eval_samples(config, traffic) == 0
    # one global forward call: 4 x pairs x 128 x 28 heads; K and V once a KV head
    flops, moved = moe_costs.attention_cost(1, 28, 4, 8192, 128, None, False)
    assert flops == 4.0 * (8192 * 8193 // 2) * 28 * 128
    assert moved == 2 * (2 * 28 + 2 * 4) * 8192 * 128
    assert moe_costs.attention_cost(1, 28, 4, 8192, 128, 4096, True)[0] == (
        10.0 * 25_167_872 * 28 * 128)
    flops, moved = moe_costs.experts_cost(12288, 2560, 768, 16, False)
    assert flops == 12288 * 6 * 2560 * 768
    assert moved == 2 * (3 * 16 * 2560 * 768 + 2 * 12288 * 2560)
    assert moe_costs.experts_cost(12288, 2560, 768, 16, True)[0] == 2 * flops


# -- the readers, on hand figures -------------------------------------------------

PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
ROWS = {  # instruction -> [(program, op_name, category, self us)]
    "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/moe/route/dot_general", "convolution", 100.0)],
    "fusion.2": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/experts/moe/dispatch/gather", "data", 300.0)],
    "gmm.3": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/experts/moe/experts/jit(gmm)/pallas_call",
               "custom-call", 2000.0)],
    "tgmm.4": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_0/experts/moe/experts/jit(tgmm)/x",
                "custom-call", 2000.0)],
    "fusion.5": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_0/experts/moe/combine/mul", "loop", 600.0)],
    "flash_fwd.6": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/attn/attn/flash_fwd/flash_fwd",
                     "custom-call", 1000.0)],
    "flash_bwd_dkv.7": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/attn/blockwise_bwd/flash_bwd_dkv",
                         "custom-call", 1500.0)],
    "flash_bwd_dq.8": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/attn/blockwise_bwd/flash_bwd_dq",
                        "custom-call", 1500.0)],
    "fusion.9": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/attn/attn/flash_fwd/reshape", "data", 50.0),
                 (2, "jit(g)/fed/eval/remoe/experts_x", "loop", 950.0)],
}
NOTES = [
    {"kernel": k, "kind": kind, "window": w, "shape": (1, 4, 64, 16), "t_k": 64,
     "q_heads_per_kv_head": 2, "dtype": "float32", "tile": tile, "tiles_visited": seen,
     "tiles_total": total}
    for k, kind, w, tile, seen, total in [
        ("fwd", "global", None, (16, 32), 6, 8), ("dkv", "global", None, (32, 32), 3, 4),
        ("dq", "global", None, (32, 32), 3, 4), ("fwd", "window", 24, (16, 32), 5, 8),
        ("dkv", "window", 24, (32, 32), 3, 4), ("dq", "window", 24, (32, 32), 3, 4),
        ("fwd", "window", 24, (8, 8), 1, 1)]]  # the last: another length, not this cell's
NOTES[-1]["shape"] = (1, 4, 8, 16)


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: NOTES)
    counters = {"moe/assignments_held/layer_0": 20.0, "moe/assignments_held/layer_1": 12.0,
                "moe/load_max_over_mean/layer_0": 1.25, "moe/load_max_over_mean/layer_1": 1.75,
                "engine/other": 3.0}
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in counters.items() if k.startswith(prefix)})
    config = {"hidden_size": 1000, "moe_ffn_hidden_size": 500, "moe_num_primary_experts": 4,
              "moe_num_active_primary_experts": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
              "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 24}
    traffic = {"clients_per_round": 2, "local_steps": 2, "batch_size": 1, "seq_len": 64}
    return {"cell": {"name": "no_such_trace", "config": config, "traffic": traffic},
            "peaks": PEAKS, "traced_rounds": 3,
            "trace": {"chip0": {"busy_s": 0.010, "ops": {"x": 1.0}}}}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_readers_give_the_hand_figures(ctx):
    # 100 + 300 + 2000 + 2000 + 600 us under moe/* of 10 ms busy; "remoe" is no scope
    assert read("moe_time_pct", ctx) == pytest.approx(50.0)
    assert read("moe_dispatch_time_pct", ctx) == pytest.approx(9.0)
    # counters: (20 + 12) / 2 layers of 1 x 64 tokens x 2 choices
    assert read("moe_held_share_pct", ctx) == pytest.approx(100.0 * 16 / 128)
    assert read("moe_load_max_over_mean", ctx) == 1.75
    # tiles by area, layer 0 global and layer 1 window, at T 64 only:
    # visited 512 x (6 + 5) + 1024 x (3 + 3 + 3 + 3), total 512 x 16 + 1024 x 16
    assert read("attn_tiles_visited_pct", ctx) == pytest.approx(
        100.0 * (512 * 11 + 1024 * 12) / (512 * 16 + 1024 * 16))
    # experts: 12 layer-steps; a call of A rows is 6 A x 1000 x 500 FLOP (compute-bound
    # at these peaks? bytes 2 x (3 x 4 x 5e5 + 2 A x 1000) = 12.08e6 / 12.048e6 -> 12.08 us /
    # 12.05 us against 0.6 us / 0.36 us: memory-bound), backward 2 x weights and rows
    least = 0.0
    for a in (20.0, 12.0):
        fwd_bytes = 2 * (6e6 + 2 * a * 1000)
        least += max(6 * a * 5e5 / 100e12, fwd_bytes / 1e12)
        least += max(12 * a * 5e5 / 100e12, 2 * fwd_bytes / 1e12)
    assert read("moe_experts_roofline", ctx) == pytest.approx(100.0 * 12 * least / 0.004)
    # attention: the kernels' 4000 us; a layer's forward 4 x pairs x 16 x 4 heads FLOP
    pairs = {None: 64 * 65 // 2, 24: 24 * 25 // 2 + 40 * 24}
    least = 0.0
    for window in (None, 24):
        q, kv = 4 * 64 * 16, 2 * 64 * 16
        least += max(4 * pairs[window] * 64 / 100e12, 2 * (2 * q + 2 * kv) / 1e12)
        least += max(10 * pairs[window] * 64 / 100e12, 2 * (3 * q + 4 * kv) / 1e12)
    assert read("flash_window_roofline", ctx) == pytest.approx(100.0 * 12 * least / 0.004)


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters(ctx, monkeypatch):
    """The parent of the PR that added them: every reader returns None."""
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {
        "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/Dense_0/dot_general", "convolution", 9.0)]})
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: [])
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {})
    for name in ("moe_time_pct", "moe_dispatch_time_pct", "moe_experts_roofline",
                 "flash_window_roofline", "attn_tiles_visited_pct", "moe_held_share_pct",
                 "moe_load_max_over_mean"):
        assert read(name, ctx) is None, name
    out = benchrun.layer_metrics(
        {"per_layer": [{"name": "moe_time_pct", "unit": "%"}]}, ctx)
    assert out == {}


def test_counters_and_notes_come_from_the_program(monkeypatch):
    from fedml_tpu.obs import trace

    tracer = trace.install()
    try:
        trace.counter("moe/assignments_held/layer_0", 7.0)
        trace.counter("moe/assignments_held/layer_0", 9.0)
    finally:
        trace.uninstall()
    trace.counter("moe/assignments_held/layer_0", 11.0)  # no tracer: not kept
    assert moe_reduce.counters("moe/assignments_held/")["moe/assignments_held/layer_0"] == 9.0
    assert len([e for e in tracer.events() if e["ph"] == "C"]) == 2
    trace.program_note("attn/call", kernel="fwd", shape=(9, 9, 9, 9), window=None)
    trace.program_note("attn/call", kernel="fwd", shape=(9, 9, 9, 9), window=None)
    assert len([n for n in moe_reduce.attention_notes() if n["shape"] == (9, 9, 9, 9)]) == 1
