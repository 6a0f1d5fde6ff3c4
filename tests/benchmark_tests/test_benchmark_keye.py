"""The configuration ``keye_vl2_30b_a3b_cut`` and the cell ``keyevl2_silo2`` at
a toy size on the CPU, in float32: the normal path (``FedSim.run``) equals the
plain reference, a lower precision or a broken layer in its place does not;
the manifest's entries, looked up by name; the parameter count from the
module's shapes; the FLOPs and bytes arithmetic; and each new per-layer reader
on hand figures. The figures such a toy cell produces are never device metrics.

Nothing here describes a TPU topology; the file is safe under xdist.
"""

import copy
import importlib
import json
import os

import jax
import pytest

from benchmark import dsa_costs, dsa_reduce, kernel_costs, moe_reduce, scope_reduce
from benchmark import run as benchrun
from benchmark.families import dsa_moe_lm as family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "keyevl2_silo2", "keye_vl2_30b_a3b_cut"
# this file's own toy overrides: hidden 32; 4 query heads on 2 KV heads of 8; an indexer of
# 3 heads x 8 choosing 16 keys; 8 experts of 16, 2 a token, 4 held; 2 layers, no remat
# (tests/test_keye.py has it on); T 64 over 160 ids, one step a client
TOY_CONFIG = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
              "moe_intermediate_size": 16, "num_hidden_layers": 2, "vocab_size": 160,
              "num_experts": 4, "num_local_experts": 4, "moe_router_outputs": 8,
              "num_experts_per_tok": 2,
              "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                            "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
                            "topk": 16},
              "compute_dtype": "float32", "remat": False}
TOY_TRAFFIC = {"seq_len": 64, "ramp_alphabet": 160, "local_steps": 1}
TIGHT = {"loss_gap": 1e-5, "norm_gap": 5e-4, "update_rel_l2": 1e-3}
SEED = 2 ** 31 + 48


def toy_cell():
    cell = benchrun.load_cell(CELL, ROOT)
    cell["config"] = {**copy.deepcopy(cell["config"]), **TOY_CONFIG}
    cell["traffic"] = {**cell["traffic"], **TOY_TRAFFIC}
    return cell


def within(numbers, limits):
    return all(v <= limits[k.split(".")[0]] for k, v in numbers.items()
               if k.split(".")[0] in limits)


def reference_check(cell, shapes, precision="f32", **arch):
    """``benchrun.reference_check``, the reference's layer broken by ``arch``."""
    if arch:
        job = family.reference_job

        def broken(*args):
            out = job(*args)
            for cohort in out["rounds"]:
                for i, (weight, batches) in enumerate(cohort):
                    cohort[i] = (weight, lambda batches=batches: (
                        {**b, "arch": b["arch"]._replace(**arch)} for b in batches()))
            return out
        cell = {**cell, "family": type("Broken", (), {
            "REFERENCE": family.REFERENCE, "reference_job": staticmethod(broken)})}
    return benchrun.reference_check(cell, SEED, cell["traffic"]["check_rounds"], shapes, precision)


@pytest.fixture(scope="module")
def checked():
    cell = toy_cell()
    sim, variables = benchrun.build_sim(cell, SEED, jax.devices()[:1])
    check = benchrun.program_check(sim, variables, cell)[0]
    return cell, check, reference_check(cell, check["shapes"])


def test_toy_cell_is_correct(checked):
    cell, check, ref = checked
    numbers = benchrun.compare(check, ref, family.HEAD)
    assert check["losses"], "no local training loss was compared"
    assert within(numbers, TIGHT), numbers
    assert benchrun.judge(numbers, TIGHT)
    params = check["variables"]["params"]
    assert set(params["block_0"]) == {"norm_attn", "attn", "indexer", "norm_ffn", "router",
                                      "experts"}
    assert params["block_0"]["indexer"]["q"]["kernel"].shape == (32, 3 * 8)
    assert params["block_0"]["experts"]["gate"]["kernel"].shape == (4, 32, 16)
    assert params["block_0"]["router"]["kernel"].shape == (32, 8) and "head" in params


@pytest.mark.parametrize("fault", [{"select": False}, {"index_loss": False}, {"relu": False},
                                   {"precision": "fp8"}],
                         ids=["selection_ignored", "index_loss_left_out", "relu_left_out",
                              "control_fp8"])
def test_a_broken_layer_or_a_lower_precision_is_not_correct(checked, fault):
    """Every earlier key attended, the indexer left without its loss (its
    leaves stand still: ``norm_gap`` reads the worst leaf), the index scores
    without their ReLU, and the reference rounded to fp8 each fail a limit."""
    cell, check, ref = checked
    numbers = benchrun.compare(check, reference_check(cell, check["shapes"], **fault), family.HEAD)
    assert not within(numbers, TIGHT), numbers
    assert max(numbers["update_rel_l2.params"] / TIGHT["update_rel_l2"],
               numbers["norm_gap"] / TIGHT["norm_gap"]) > 10, numbers
    assert not benchrun.judge(numbers, TIGHT)
    if fault == {"index_loss": False}:
        assert numbers["norm_gap"] > 0.5


# -- the manifest's new entries and the configuration file ---------------------

NEW = ["dsa_time_pct", "dsa_index_time_pct", "dsa_select_time_pct", "dsa_index_loss_time_pct",
       "dsa_select_roofline", "flash_dsa_roofline", "dsa_tiles_nonempty_pct", "dsa_index_kl",
       "dsa_index_mass_pct", "moe_routed_time_pct_keye", "head_loss_time_pct_keye",
       "loop_steps_time_pct_keye"]
# accepted readers under the cell's name (one import each), then the experts' roofline
# under this configuration's keys
TWINS = ["train_fwd_time_pct", "train_bwd_time_pct", "optimizer_time_pct", "eval_time_pct",
         "aggregate_time_pct", "unattributed_time_pct", "attn_bwd_time_pct",
         "moe_dispatch_time_pct", "loop_cohort_time_pct", "unscoped_time_pct",
         "moe_load_max_over_mean", "moe_routed_held_pct", "moe_rows_touched_pct",
         "moe_overflow_tiles"]
MINE = NEW + [name + "_keye" for name in TWINS] + ["moe_experts_roofline_keye"]


def _catalog_config():
    """The catalog's ``config`` of Keye-VL-2.0-30B-A3B, every key."""
    return {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}


def test_manifest_entries_and_the_configuration_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
                               "config.json")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "num_local_experts",
                                "vocab_size"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "silo2", 1)
    assert len([w for w in manifest["workloads"] if w["config"] == CONFIG]) == 1
    mine = {m["name"]: m for m in manifest["per_layer"] if m.get("workloads") == [CELL]}
    assert list(mine) == MINE
    for m in mine.values():
        assert m["moves"] == "rounds_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert callable(importlib.import_module(f"benchmark.layer_metrics.{m['name']}").read)
    for name in ("dsa_select_roofline", "flash_dsa_roofline"):
        assert (mine[name]["unit"], mine[name]["layer"], mine[name]["better"]) == (
            "%", "kernels", "higher")
    for name in ("dsa_tiles_nonempty_pct", "dsa_index_kl", "dsa_index_mass_pct"):
        assert mine[name]["source"] == "program_counter"
    loaded = benchrun.load_cell(CELL, ROOT)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(MINE) <= reported and {"mfu_pct", "peak_hbm_gb", "device_idle_pct",
                                     "longest_gap_ms", "compiles_in_window", "host_stage_ms",
                                     "host_sync_ms"} <= reported
    assert not {"eva_time_pct", "mla_time_pct", "moe_routed_time_pct", "flash_window_roofline",
                "head_loss_time_pct", "loop_steps_time_pct", "shortconv_time_pct"} & reported
    assert {m["name"] for m in loaded["end_to_end"]} == {"rounds_per_s", "setup_s"}
    assert loaded["traffic"] == {
        "clients_total": 2, "clients_per_round": 2, "local_steps": 2, "batch_size": 1,
        "seq_len": 8192, "ramp_alphabet": 18992, "frequency_of_the_test": 10000,
        "eval_batch_size": 1, "cohort_execution": "scan", "check_rounds": 2}
    config = loaded["config"]
    published = _catalog_config()
    cut = {"num_hidden_layers": 6, "num_experts": 16, "num_local_experts": 16, "vocab_size": 18992}
    assert {k: config[k] for k in published} == {**published, **cut}
    assert config["published"] == {k: published[k] for k in cut}
    assert set(config["reduced"]) == set(entry["reduced"]) == set(cut)
    assert config["moe_router_outputs"] == 128
    assert config["share"] == {"chips_per_layer": 8, "experts_published": 128, "experts_first": 0,
                               "vocab_published": 151936, "vocab_first": 0}
    assert config["parameters"] == 659_190_016 and config["family"] == "dsa_moe_lm"
    assert {"indexer", "indexer positions", "selection", "q_chunk_size and kv_chunk_size",
            "index loss", "indexer precision", "q and k norms", "rotation", "router", "optimizer",
            "residual stream"} <= set(config["assumed"])
    assert config["optimizer"]["momentum"] == 0.0 and config["remat"] is True
    assert set(config["check"]["limits"]) and "seeds" in config["check"]["readings"]
    assert config["check"]["control_precision"] == "fp8"
    for key in ("deployment", "remat_why", "init_why"):
        assert len(config[key]) > 200, key
    from benchmark import traffic as trafficlib
    rule = lambda path, shape: trafficlib.init_leaf_rule(path, shape, config["init"])  # noqa: E731
    assert rule("['params']['block_0']['indexer']['k_norm']['bias']", (64,)) == ("zeros", 0.0)
    assert rule("['params']['block_0']['indexer']['q']['kernel']", (2048, 1024))[1] == \
        pytest.approx(2048 ** -0.5)
    assert rule("['params']['block_0']['experts']['up']['kernel']", (16, 2048, 768))[1] == \
        pytest.approx(2048 ** -0.5)
    with pytest.raises(ValueError, match="not this family's block"):
        family.build({**config, "norm_topk_prob": False}, loaded["traffic"], 1)
    with pytest.raises(ValueError, match="held slice"):
        family.build(config, {**loaded["traffic"], "ramp_alphabet": 20000}, 1)


def test_the_program_counts_the_parameters_the_file_states():
    """659,190,016: the issue's count, from the module's own shapes."""
    cell = benchrun.load_cell(CELL, ROOT)
    job = family.build(cell["config"], {**cell["traffic"], "seq_len": 64}, 1)
    sample = {k: jax.ShapeDtypeStruct((1,) + v.shape[1:], v.dtype)
              for k, v in job["train"].arrays.items()}
    shapes = jax.eval_shape(job["trainer"].init, jax.random.key(0), sample)["params"]
    count = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == cell["config"]["parameters"] == 659_190_016
    assert count(shapes) == dsa_costs.parameters(cell["config"])
    assert count(shapes["block_0"]["indexer"]) == 2_261_120
    assert count(shapes["block_0"]["experts"]) == 75_497_472
    assert count(shapes["block_0"]) - 2_261_120 - 75_497_472 == 19_140_864
    assert [count(shapes[f"block_{i}"]) for i in range(6)] == [96_899_456] * 6
    assert count(shapes["tok_embed"]) == count(shapes["head"]) == 18_992 * 2048
    assert "select_bias" not in shapes["block_0"] and "shared" not in shapes["block_0"]


# -- the arithmetic, pinned -----------------------------------------------------------


def test_costs_closed_forms():
    """ISSUE 48's count: 1,792.1 keys a query at T 8,192 where a causal layer
    reads 4,096.5; a layer's parts in MFLOP a token; 69.4 TFLOP a round; the
    selection's and the attention's least times."""
    cell = benchrun.load_cell(CELL, ROOT)
    config, traffic = cell["config"], cell["traffic"]
    assert dsa_costs.selected_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert dsa_costs.selected_pairs(8192, 2048) / 8192 == 1792.125
    assert dsa_costs.causal_pairs(8192) / 8192 == 4096.5
    assert dsa_costs.selected_pairs(1024, 2048) == dsa_costs.causal_pairs(1024)
    parts = dsa_costs.layer_forward_flops_per_token(config, 8192)
    assert {k: round(v / 1e6, 1) for k, v in parts.items()} == {
        "projections": 37.7, "attention": 29.4, "indexer": 4.5, "index_scores": 8.4,
        "index_loss": 14.7, "router": 0.5, "experts": 9.4}
    mixer = sum(v for k, v in parts.items() if k not in ("router", "experts"))
    assert 85.0 < 100 * mixer / sum(parts.values()) < 91.0
    fwd = dsa_costs.forward_flops_per_token(config, 8192)
    assert fwd == 6 * sum(parts.values()) + 2 * 2048 * 18992
    assert family.samples_per_round(config, traffic) == 32768
    assert family.flops_per_round(config, traffic) == 3.0 * fwd * 32768
    assert family.flops_per_round(config, traffic) / 1e12 == pytest.approx(69.38, abs=0.005)
    assert family.eval_samples(config, traffic) == 0
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    carried = dsa_costs.selection_bytes(1, 8192, 512)
    assert carried == 2 * 8192 * 1024 + 4 * 256 == 16_778_240
    flops, moved = dsa_costs.select_cost(1, 8192, 16, 64, carried)
    assert flops == 2.0 * 16 * 64 * 8192 * 8193 / 2
    assert moved == 2 * 8192 * (1024 + 64 + 16) + carried
    seconds, bound = kernel_costs.least_seconds(flops, moved, peaks)
    assert bound == "compute" and seconds * 1e3 == pytest.approx(0.349, abs=0.001)
    q_elems, kv_elems = 32 * 8192 * 128, 4 * 8192 * 128
    f_fwd, b_fwd = dsa_costs.attention_cost(1, 32, 4, 8192, 128, 2048, False, carried)
    f_bwd, b_bwd = dsa_costs.attention_cost(1, 32, 4, 8192, 128, 2048, True, carried)
    assert f_fwd == 4.0 * 14_681_088 * 32 * 128 and f_bwd == 2.5 * f_fwd
    assert b_fwd == 2.0 * (2 * q_elems + 2 * kv_elems) + carried / 2
    assert b_bwd == 2.0 * (3 * q_elems + 4 * kv_elems) + carried / 2
    assert kernel_costs.least_seconds(f_fwd, b_fwd, peaks)[0] * 1e3 == pytest.approx(
        1.221, abs=0.001)
    assert kernel_costs.least_seconds(f_bwd, b_bwd, peaks) == (f_bwd / 197e12, "compute")


# -- the readers, on hand figures -------------------------------------------------

PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
DSA = "block_0/attn/dsa"
ROWS = {  # instruction -> [(program, op_name, category, self us)]
    "fusion.1": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/attn/q/dot_general", "convolution", 400.0)],
    "fusion.2": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/attn/dsa/index/indexer/q/dot_general",
                  "convolution", 100.0)],
    "fusion.3": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/attn/attn/dsa/index/scores/while/body/dot",
                  "convolution", 200.0)],
    "fusion.4": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/attn/attn/dsa/select/while/body/reduce",
                  "loop", 300.0)],
    "fusion.5": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/attn/attn/dsa/index_loss/while/body/exp",
                  "loop", 600.0)],
    "fusion.6": [(1, f"jit(f)/fed/fwd_bwd/transpose(jvp(M))/{DSA}/attn/attn/dsa/index_loss/mul",
                  "loop", 50.0)],
    "flash_fwd.7": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{DSA}/attn/attn/flash_fwd/flash_fwd",
                     "custom-call", 200.0)],
    "flash_bwd_dkv.8": [(1, f"jit(f)/fed/fwd_bwd/transpose(jvp(M))/{DSA}/attn/attn/blockwise_bwd/"
                            "flash_bwd_dkv", "custom-call", 300.0)],
    "fusion.9": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/moe/experts/gmm/pallas_call",
                  "custom-call", 700.0)],
    "fusion.10": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/head/dot_general", "convolution", 150.0)],
    "fusion.11": [(1, "jit(f)/loop/cohort/loop/epochs/while/body/loop/steps/while/body/copy",
                   "data formatting", 350.0)],
    "fusion.12": [(2, "jit(g)/fed/eval/block_0/attn/dsax/select/x", "loop", 950.0)],
}
NOTE = {"impl": "flash", "select": "radix", "shape": (1, 4, 256, 16), "kv_heads": 2,
        "index_heads": 3, "index_dim": 8, "topk": 64, "tile": (128, 128), "dtype": "bfloat16",
        "index_dtype": "bfloat16", "selection_bytes": 2 * 256 * 32 + 16}


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    counters = {"dsa/tiles_nonempty/layer_0": 1.0, "dsa/tiles_nonempty/layer_1": 0.5,
                "dsa/index_kl/layer_0": 0.2, "dsa/index_kl/layer_1": 0.4,
                "dsa/index_mass/layer_0": 0.6, "dsa/index_mass/layer_1": 0.7}
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in counters.items() if k.startswith(prefix)})
    monkeypatch.setattr(dsa_reduce, "dsa_notes", lambda: [
        {**NOTE, "shape": (1, 4, 64, 16)}, NOTE])  # a shorter call of another program
    config = {"num_hidden_layers": 2}
    traffic = {"clients_per_round": 2, "local_steps": 2, "batch_size": 1, "seq_len": 256}
    return {"cell": {"name": "no_such_trace", "config": config, "traffic": traffic},
            "peaks": PEAKS, "traced_rounds": 3,
            "trace": {"chip0": {"busy_s": 0.010, "ops": {"x": 1.0}}}}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_readers_give_the_hand_figures(ctx):
    # 400 + 100 + 200 + 300 + 600 + 50 + 200 + 300 us under attn/dsa of 10 ms busy; "dsax" is none
    assert read("dsa_time_pct", ctx) == pytest.approx(21.5)
    assert read("dsa_index_time_pct", ctx) == pytest.approx(3.0)
    assert read("dsa_select_time_pct", ctx) == pytest.approx(3.0)
    assert read("dsa_index_loss_time_pct", ctx) == pytest.approx(6.5)
    assert read("moe_routed_time_pct_keye", ctx) == pytest.approx(7.0)
    assert read("head_loss_time_pct_keye", ctx) == pytest.approx(1.5)
    assert read("loop_steps_time_pct_keye", ctx) == pytest.approx(3.5)
    assert read("dsa_tiles_nonempty_pct", ctx) == pytest.approx(75.0)
    assert read("dsa_index_kl", ctx) == pytest.approx(0.3)
    assert read("dsa_index_mass_pct", ctx) == pytest.approx(65.0)
    # two layers x 3 rounds x 2 clients x 2 steps calls; scores and selection at T 256: 2 x 3 x 8
    # FLOP a causal pair against the bytes of qI, kI, wI and the set, over 200 + 300 us
    calls = 24
    least = max(2.0 * 3 * 8 * 256 * 257 / 2 / 100e12,
                (2 * 256 * (24 + 8 + 3) + NOTE["selection_bytes"]) / 1e12)
    assert read("dsa_select_roofline", ctx) == pytest.approx(100.0 * calls * least / 0.0005)
    # the attention over the selected pairs (64 x 65 / 2 + 192 x 64), 4 heads of 16 on 2 KV heads
    pairs, q_elems, kv_elems = 64 * 65 // 2 + 192 * 64, 4 * 256 * 16, 2 * 256 * 16
    half = NOTE["selection_bytes"] / 2
    fwd = max(4.0 * 16 * 4 * pairs / 100e12, (2.0 * (2 * q_elems + 2 * kv_elems) + half) / 1e12)
    bwd = max(10.0 * 16 * 4 * pairs / 100e12, (2.0 * (3 * q_elems + 4 * kv_elems) + half) / 1e12)
    assert read("flash_dsa_roofline", ctx) == pytest.approx(100.0 * calls * (fwd + bwd) / 0.0005)


@pytest.mark.parametrize("name", TWINS)
def test_a_twin_is_the_accepted_reader_and_declared_as_it_is(name):
    twin = importlib.import_module(f"benchmark.layer_metrics.{name}_keye")
    assert twin.read is importlib.import_module(f"benchmark.layer_metrics.{name}").read
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    same = ("unit", "better", "source", "layer", "moves")
    assert [declared[name + "_keye"][k] for k in same] == [declared[name][k] for k in same]
    assert CELL not in declared[name]["workloads"]


def test_the_experts_roofline_under_this_configurations_keys(ctx, monkeypatch):
    """8,000 and 9,000 assignments held in two layers of 16 experts 2048 x 768,
    24 / 2 steps a layer, over the 700 us under ``moe/experts``; the bytes
    bound both passes at these peaks."""
    held = {"moe/assignments_held/layer_0": 8000.0, "moe/assignments_held/layer_1": 9000.0}
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in held.items() if k.startswith(prefix)})
    ctx["cell"]["config"].update(hidden_size=2048, moe_intermediate_size=768, num_local_experts=16)
    least = 0.0
    for rows in held.values():
        flops, weights, moved = 6.0 * rows * 2048 * 768, 3.0 * 16 * 2048 * 768, 2.0 * rows * 2048
        least += max(flops / 100e12, 2 * (weights + moved) / 1e12)
        least += max(2 * flops / 100e12, 2 * (2 * weights + 2 * moved) / 1e12)
    assert read("moe_experts_roofline_keye", ctx) == pytest.approx(100.0 * 12 * least / 0.0007)
    assert read("moe_routed_held_pct_keye", ctx) is None  # the toy config names no experts a token
    del ctx["cell"]["config"]["moe_intermediate_size"]
    assert read("moe_experts_roofline_keye", ctx) is None


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters(ctx, monkeypatch):
    """The parent of the PR that added them: every reader returns None and
    the result line leaves the metrics out."""
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {
        "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/Dense_0/dot_general", "convolution", 9.0)]})
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {})
    monkeypatch.setattr(dsa_reduce, "dsa_notes", lambda: [])
    found = {name: read(name, ctx) for name in NEW + ["moe_experts_roofline_keye"]}
    assert found.pop("head_loss_time_pct_keye") == 0.0  # the accepted reader: rows, no scope
    assert set(found.values()) == {None}, found
    assert set(benchrun.layer_metrics({"per_layer": [{"name": n, "unit": "%"} for n in NEW]},
                                      ctx)) == {"head_loss_time_pct_keye"}
    # the scopes without the notes, the notes without the scopes
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    assert read("dsa_select_roofline", ctx) is None and read("flash_dsa_roofline", ctx) is None
    monkeypatch.setattr(dsa_reduce, "dsa_notes", lambda: [NOTE])
    assert read("dsa_select_roofline", ctx) is not None
    assert read("flash_dsa_roofline", ctx) is not None
    # the program's own notes are what the reader reads when nothing stands in
    monkeypatch.undo()
    assert isinstance(dsa_reduce.dsa_notes(), list)


def test_the_parent_cannot_build_the_cell_and_says_so_at_once():
    """The driver tries the new cell on the parent commit with these benchmark
    files laid over it: its model takes no ``router`` / ``index_heads``, so the
    family's ``build`` raises a TypeError from the constructor, before any
    device work."""
    import fedml_tpu.models.mla_moe_transformer as models

    class Parent:  # the parent's constructor: the fields it had at PR 47
        def __init__(self, *, vocab_size, embed_dim, dense_layers, routed_layers, num_heads,
                     head_dim, num_experts, experts_per_token, expert_dim, shared_dim,
                     experts_first, experts_held, mtp_depth, rope_theta, mixers, rms_eps,
                     attn_impl, dtype, remat, kv_heads=2):
            raise AssertionError("the parent was built with fields it does not have")

    cell = benchrun.load_cell(CELL, ROOT)
    real = models.MLAMoETransformerLM
    models.MLAMoETransformerLM = Parent
    try:
        with pytest.raises(TypeError, match="router|index_heads|index_dim|index_topk"):
            family.build(cell["config"], cell["traffic"], 1)
    finally:
        models.MLAMoETransformerLM = real


FAULTS = ["control_fp8", "selection_ignored", "index_loss_left_out", "relu_left_out"]


def _readings():
    check = benchrun.load_cell(CELL, ROOT)["config"]["check"]
    at_size = check["readings_at_size"]
    faults = {name: {k: float(v) for k, v in reading.items()}
              for name, reading in {**at_size["broken"],
                                    "control_fp8": at_size["control_fp8"]}.items()}
    return check["limits"], at_size, faults


@pytest.mark.parametrize("fault", FAULTS)
def test_the_limits_fail_each_fault_as_it_read_at_the_cells_size(fault, capsys):
    """The chip's readings at the timed sizes (the configuration file records
    them) through the harness's own ``judge``."""
    limits, _, faults = _readings()
    assert set(faults) == set(FAULTS)
    assert not benchrun.judge(faults[fault], limits)
    assert "FAIL" in capsys.readouterr().out


def test_sound_readings_pass_with_room():
    """Every limit lies between its two readings with room on both sides: the
    largest sound reading 1.5 times under it or more, the fp8 control 1.5 times
    over it or more (so the control fails all four), and every fault over a
    limit by 1.5 times or more."""
    limits, at_size, faults = _readings()
    assert benchrun.judge(at_size["sound_largest"], limits)
    for name, limit in limits.items():
        key = name if name in at_size["sound_largest"] else name + ".round0"
        assert 1.5 * at_size["sound_largest"][key] <= limit, (name, limit)
        assert faults["control_fp8"][key] >= 1.5 * limit, (name, limit)
    for fault, reading in faults.items():
        over = [reading[k] / limits[k.replace(".round0", "")] for k in reading
                if k.replace(".round0", "") in limits]
        assert max(over) >= 1.5, (fault, reading)
    assert at_size["selection_pairs_differing"]["pairs"] >= 0
