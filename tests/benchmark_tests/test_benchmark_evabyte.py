"""The configuration ``evabyte_6p5b_cut`` and the cell ``evabyte_silo2`` at a
toy size on the CPU, in float32: the normal path (``FedSim.run``) equals the
plain reference, a lower precision or a broken layer in its place does not;
the manifest's entries, looked up by name; the parameter count from the
module's shapes; the FLOPs and bytes arithmetic; the family's rows; and each
new per-layer reader on hand figures. The figures such a toy cell produces
are never device metrics.

Nothing here describes a TPU topology; the file is safe under xdist.
"""

import copy
import importlib
import json
import os

import jax
import numpy as np
import pytest

from benchmark import eva_costs, eva_reduce, kernel_costs, moe_reduce, scope_reduce
from benchmark import run as benchrun
from benchmark.families import eva_lm as family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "evabyte_silo2", "evabyte_6p5b_cut"
# this file's own toy overrides: hidden 64; 4 heads of 16; windows of 32 in chunks of 4;
# a feed-forward of 96; 2 layers; 4 heads over 40 ids; T 96 (three windows)
TOY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 96, "num_hidden_layers": 2, "vocab_size": 40,
              "num_pred_heads": 4, "window_size": 32, "chunk_size": 4,
              "compute_dtype": "float32", "remat": False}
TOY_TRAFFIC = {"seq_len": 96, "alphabet": 40, "targets_per_position": 4}
TIGHT = {"loss_gap": 1e-5, "norm_gap": 2e-4, "update_rel_l2": 1e-3}
SEED = 2 ** 31 + 44


def toy_cell():
    cell = benchrun.load_cell(CELL, ROOT)
    cell["config"] = {**copy.deepcopy(cell["config"]), **TOY_CONFIG}
    cell["traffic"] = {**cell["traffic"], **TOY_TRAFFIC}
    return cell


def program_check(cell):
    sim, variables = benchrun.build_sim(cell, SEED, jax.devices()[:1])
    return benchrun.program_check(sim, variables, cell)[0]


def within(numbers, limits):
    return all(v <= limits[k.split(".")[0]] for k, v in numbers.items()
               if k.split(".")[0] in limits)


def program_check_shapes(cell):
    job = cell["family"].build(cell["config"], cell["traffic"], SEED)
    sample = {k: jax.ShapeDtypeStruct((1,) + v.shape[1:], v.dtype)
              for k, v in job["train"].arrays.items()}
    return jax.eval_shape(job["trainer"].init, jax.random.key(0), sample)


def reference_check(cell, precision="f32", **arch):
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
    return benchrun.reference_check(cell, SEED, cell["traffic"]["check_rounds"],
                                    program_check_shapes(toy_cell()), precision)


@pytest.fixture(scope="module")
def checked():
    cell = toy_cell()
    return cell, program_check(cell), reference_check(cell)


def test_toy_cell_is_correct(checked):
    cell, check, ref = checked
    numbers = benchrun.compare(check, ref, family.HEAD)
    assert check["losses"], "no local training loss was compared"
    assert within(numbers, TIGHT), numbers
    assert benchrun.judge(numbers, TIGHT)
    assert "stats" not in check["variables"]
    params = check["variables"]["params"]
    assert params["head"]["kernel"].shape == (64, 4 * 40) and "update_rel_l2.head" in numbers
    assert set(params["block_0"]) == {"norm_attn", "attn", "norm_ffn", "mlp"}
    assert params["block_0"]["attn"]["adaptive_phi"]["kernel"].shape == (4, 16)
    # the offsets start at zero and a round moves them
    assert float(np.abs(ref["initial"]["params"]["norm_f"]["scale"]).max()) == 0.0
    assert float(np.abs(params["norm_f"]["scale"]).max()) > 0.0


@pytest.mark.parametrize("fault", [{"remote": False}, {"mu": False}, {"precision": "fp8"}],
                         ids=["remote_left_out", "mu_left_out", "control_fp8"])
def test_a_broken_layer_or_a_lower_precision_is_not_correct(checked, fault):
    """Window-local attention alone, the summaries' keys without ``mu``, and
    the reference rounded to fp8 each fail a limit against the program."""
    cell, check, ref = checked
    stand_in = reference_check(cell, **fault)
    numbers = benchrun.compare(check, stand_in, family.HEAD)
    assert not within(numbers, TIGHT), numbers
    assert max(numbers["update_rel_l2.params"] / TIGHT["update_rel_l2"],
               numbers["norm_gap"] / TIGHT["norm_gap"]) > 10, numbers
    assert not benchrun.judge(numbers, TIGHT)


# -- the manifest's new entries and the configuration file ---------------------

NEW = ["eva_time_pct", "eva_summary_time_pct", "eva_summary_roofline", "flash_eva_roofline",
       "eva_remote_tiles_visited_pct", "eva_remote_mass_pct", "dense_mlp_time_pct",
       "head_loss_time_pct_evabyte", "loop_steps_time_pct_evabyte"]


def _catalog_config():
    """The catalog's ``config`` of EvaByte, every key."""
    return {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False,
        "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32, "num_chunks": None,
        "num_hidden_layers": 32, "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}


def test_manifest_entries_and_the_configuration_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "silo2", 1)
    assert len([w for w in manifest["workloads"] if w["config"] == CONFIG]) == 1
    mine = {m["name"]: m for m in manifest["per_layer"] if m.get("workloads") == [CELL]}
    assert list(mine) == NEW
    for m in mine.values():
        assert m["moves"] == "rounds_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert callable(importlib.import_module(f"benchmark.layer_metrics.{m['name']}").read)
    for name in ("eva_summary_roofline", "flash_eva_roofline"):
        assert (mine[name]["unit"], mine[name]["layer"], mine[name]["better"]) == (
            "%", "kernels", "higher")
    assert mine["eva_summary_time_pct"]["layer"] == "kernels"
    assert mine["eva_remote_tiles_visited_pct"]["source"] == "program_counter"
    assert mine["eva_remote_mass_pct"]["source"] == "program_counter"
    loaded = benchrun.load_cell(CELL, ROOT)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW) <= reported and {"mfu_pct", "peak_hbm_gb", "device_idle_pct",
                                     "longest_gap_ms", "compiles_in_window", "host_stage_ms",
                                     "host_sync_ms"} <= reported
    assert not {"kda_time_pct", "mla_time_pct", "moe_routed_time_pct", "flash_window_roofline",
                "head_loss_time_pct", "loop_steps_time_pct", "shortconv_time_pct"} & reported
    assert {m["name"] for m in loaded["end_to_end"]} == {"rounds_per_s", "setup_s"}
    assert loaded["traffic"] == {
        "clients_total": 2, "clients_per_round": 2, "local_steps": 2, "batch_size": 1,
        "seq_len": 8192, "alphabet": 320, "targets_per_position": 8,
        "frequency_of_the_test": 10000, "eval_batch_size": 1, "cohort_execution": "scan",
        "check_rounds": 2}
    config = loaded["config"]
    published = _catalog_config()
    assert {k: config[k] for k in published} == {**published, "num_hidden_layers": 4}
    assert config["published"] == {"num_hidden_layers": 32}
    assert set(config["reduced"]) == set(entry["reduced"])
    assert config["parameters"] == 821_366_784 and config["family"] == "eva_lm"
    assert {"head_dim", "summaries", "one softmax", "rotation", "norms", "heads",
            "optimizer", "residual stream"} <= set(config["assumed"])
    assert config["optimizer"]["momentum"] == 0.0 and config["remat"] is True
    assert set(config["check"]["limits"]) and "seeds" in config["check"]["readings"]
    assert config["check"]["control_precision"] == "fp8"
    for key in ("deployment", "remat_why", "init_why"):
        assert len(config[key]) > 200, key
    from benchmark import traffic as trafficlib
    assert trafficlib.init_leaf_rule("['params']['block_0']['norm_attn']['scale']", (4096,),
                                     config["init"]) == ("ones", 0.0)
    assert trafficlib.init_leaf_rule("['params']['block_0']['attn']['adaptive_phi']['kernel']",
                                     (32, 128), config["init"])[1] == pytest.approx(1.0)
    with pytest.raises(ValueError, match="not this family's block"):
        family.build({**config, "norm_add_unit_offset": False}, loaded["traffic"], 1)
    with pytest.raises(ValueError, match="alphabet and targets"):
        family.build(config, {**loaded["traffic"], "alphabet": 256}, 1)


def test_the_program_counts_the_parameters_the_file_states():
    """821,366,784: the issue's count, from the module's own shapes."""
    cell = benchrun.load_cell(CELL, ROOT)
    job = family.build(cell["config"], {**cell["traffic"], "seq_len": 64}, 1)
    sample = {k: jax.ShapeDtypeStruct((1,) + v.shape[1:], v.dtype)
              for k, v in job["train"].arrays.items()}
    shapes = jax.eval_shape(job["trainer"].init, jax.random.key(0), sample)["params"]
    count = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == cell["config"]["parameters"] == 821_366_784
    assert count(shapes) == eva_costs.parameters(cell["config"])
    assert count(shapes["block_0"]["attn"]) == 67_108_864 + 8_192
    assert count(shapes["block_0"]["mlp"]) == 135_266_304
    assert [count(shapes[f"block_{i}"]) for i in range(4)] == [202_391_552] * 4
    assert count(shapes["tok_embed"]) == 1_310_720 and count(shapes["head"]) == 10_485_760
    assert shapes["head"]["kernel"].shape == (4096, 8 * 320)


def test_the_familys_rows():
    """``T + 8`` bytes a row over all 320 ids: every row differs, every id
    occurs, position t's targets are the eight bytes after it, and every seed
    gives the same shapes."""
    x, y = family.byte_rows(7, 4, 1000, 320, 8)
    assert x.shape == (4, 1000) and y.shape == (4, 1000, 8) and x.dtype == y.dtype == np.int32
    assert len({row.tobytes() for row in x}) == 4
    assert set(np.unique(x)) == set(range(320))
    for h in range(8):
        np.testing.assert_array_equal(y[:, :-1 - h, h], x[:, 1 + h:])
    np.testing.assert_array_equal(x[:, 320:640], x[:, :320])  # learnable: a row repeats itself
    other, _ = family.byte_rows(8, 4, 1000, 320, 8)
    assert not np.array_equal(other, x)
    again, _ = family.byte_rows(7, 4, 1000, 320, 8)
    np.testing.assert_array_equal(again, x)
    cell = toy_cell()
    job = family.build(cell["config"], cell["traffic"], SEED)
    arrays = job["train"].arrays
    assert arrays["x"].shape == (4, 96) and arrays["y"].shape == arrays["mask"].shape == (4, 96, 4)


# -- the arithmetic, pinned -----------------------------------------------------------


def test_costs_closed_forms():
    """ISSUE 44's count: a layer 424,706,048 FLOP a token forward at T 8,192,
    the heads 20,971,520, 1.7198 G in all, 169.06 TFLOP a round; the
    summaries' and the two flash calls' least times."""
    cell = benchrun.load_cell(CELL, ROOT)
    config, traffic = cell["config"], cell["traffic"]
    assert eva_costs.local_pairs(config, 8192) == 4 * 2048 * 2049 // 2
    assert eva_costs.remote_pairs(config, 8192) == 2048 * 128 * (1 + 2 + 3)
    assert eva_costs.local_pairs(config, 8192) / 8192 == 1024.5
    assert eva_costs.remote_pairs(config, 8192) / 8192 == 192.0
    assert eva_costs.remote_pairs(config, 32768) / 32768 == 128 * 15 / 2
    projections, feed_forward = 2 * 4 * 4096 * 4096, 2 * 3 * 4096 * 11008
    local, remote, pools = 4 * 4096 * 1024.5, 4 * 4096 * 192, 32 * 6 * 128
    assert (projections, feed_forward, local, remote, pools) == (
        134_217_728, 270_532_608, 16_785_408.0, 3_145_728, 24_576)
    layer = eva_costs.layer_forward_flops_per_token(config, 8192)
    assert layer == projections + feed_forward + local + remote + pools == 424_706_048
    assert eva_costs.head_forward_flops_per_token(config) == 20_971_520
    fwd = eva_costs.forward_flops_per_token(config, 8192)
    assert fwd == 4 * 424_706_048 + 20_971_520 and fwd / 1e9 == pytest.approx(1.7198, abs=5e-5)
    assert 100 * (projections + local + remote + pools) * 4 / fwd == pytest.approx(35.9, abs=0.05)
    assert family.samples_per_round(config, traffic) == 32768
    assert family.flops_per_round(config, traffic) / 1e12 == pytest.approx(169.06, abs=0.005)
    assert family.eval_samples(config, traffic) == 0
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # one call of the summaries over [1, 32, 8192, 128] bfloat16: memory-bound both ways
    elems, sums = 32 * 8192 * 128, 32 * 512 * 128
    assert eva_costs.summary_cost(1, 32, 8192, 128, 16, False) == (6.0 * elems,
                                                                   2.0 * (2 * elems + 2 * sums))
    assert eva_costs.summary_cost(1, 32, 8192, 128, 16, True) == (12.0 * elems,
                                                                  2.0 * (4 * elems + 2 * sums))
    seconds, bound = kernel_costs.least_seconds(
        *eva_costs.summary_cost(1, 32, 8192, 128, 16, False), peaks)
    assert bound == "memory" and seconds * 1e3 == pytest.approx(0.174, abs=0.001)
    # the two flash calls of a layer, forward: the local one compute-bound, the remote one
    # (192 summaries a query on average) bound by reading q and writing the output
    (fl, bl), (fr, br) = eva_costs.attention_cost(config, 1, 8192, False)
    assert fl == 4.0 * 128 * 32 * 4 * 2048 * 2049 // 2 and bl == 2.0 * 4 * elems
    assert fr == 4.0 * 128 * 32 * 2048 * 128 * 6 and br == 2.0 * (2 * elems + 2 * sums)
    assert kernel_costs.least_seconds(fl, bl, peaks)[1] == "compute"
    assert kernel_costs.least_seconds(fl, bl, peaks)[0] * 1e3 == pytest.approx(0.698, abs=0.001)
    assert fr / 197e12 * 1e3 == pytest.approx(0.131, abs=0.001)
    seconds, bound = kernel_costs.least_seconds(fr, br, peaks)
    assert bound == "memory" and seconds * 1e3 == pytest.approx(0.174, abs=0.001)
    (fl_b, _), (fr_b, br_b) = eva_costs.attention_cost(config, 1, 8192, True)
    assert (fl_b, fr_b) == (2.5 * fl, 2.5 * fr) and br_b == 2.0 * (3 * elems + 4 * sums)
    seconds, bound = kernel_costs.least_seconds(fr_b, br_b, peaks)
    assert bound == "compute" and seconds * 1e3 == pytest.approx(0.327, abs=0.001)
    # one window or less: no remote call
    assert eva_costs.attention_cost(config, 1, 2048, False)[1] == (0.0, 0.0)
    assert eva_costs.remote_pairs(config, 2048) == 0


# -- the readers, on hand figures -------------------------------------------------

PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
EVA = "block_0/attn/attn/eva"
ROWS = {  # instruction -> [(program, op_name, category, self us)]
    "fusion.1": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{EVA}/q/dot_general", "convolution", 400.0)],
    "fusion.2": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{EVA}/attn/eva/summary/mul", "loop", 100.0)],
    "fusion.3": [(1, f"jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_0/checkpoint/{EVA}/attn/eva/"
                     "summary/mul", "loop", 300.0)],
    "fusion.4": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{EVA}/attn/eva/merge/exp", "loop", 100.0)],
    "flash_fwd.5": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{EVA}/attn/flash_fwd/flash_fwd",
                     "custom-call", 200.0)],
    "flash_bwd_dkv.6": [(1, f"jit(f)/fed/fwd_bwd/transpose(jvp(M))/{EVA}/attn/blockwise_bwd/"
                            "flash_bwd_dkv", "custom-call", 300.0)],
    "fusion.7": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/mlp/dense/mlp/gate/dot_general",
                  "convolution", 2500.0)],
    "fusion.8": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_0/mlp/dense/mlp/up/transpose",
                  "convolution", 2000.0)],
    "fusion.9": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/head/dot_general", "convolution", 150.0)],
    "fusion.10": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/head/transpose", "loop", 50.0)],
    "fusion.11": [(1, "jit(f)/loop/cohort/loop/epochs/while/body/loop/steps/while/body/copy",
                   "data formatting", 350.0)],
    "fusion.12": [(2, "jit(g)/fed/eval/block_0/attn/attn/evax/summary/x", "loop", 950.0)],
}
EVA_NOTE = {"impl": "flash", "shape": (1, 4, 128, 16), "window": 32, "chunk": 4, "windows": 4,
            "summaries": 32, "dtype": "bfloat16"}
ATTN_NOTES = [
    {"kernel": "fwd", "kind": "global", "shape": (1, 16, 32, 16), "t_k": 32, "tiles_visited": 16,
     "tiles_total": 16},
    {"kernel": "fwd", "kind": "stair", "shape": (1, 4, 128, 16), "t_k": 32, "tiles_visited": 6,
     "tiles_total": 16},
    {"kernel": "dkv", "kind": "stair", "shape": (1, 4, 128, 16), "t_k": 32, "tiles_visited": 6,
     "tiles_total": 16},
    {"kernel": "fwd", "kind": "stair", "shape": (1, 4, 64, 16), "t_k": 16, "tiles_visited": 1,
     "tiles_total": 4}]


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    counters = {"eva/remote_mass/layer_0": 0.10, "eva/remote_mass/layer_1": 0.20}
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in counters.items() if k.startswith(prefix)})
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: ATTN_NOTES)
    monkeypatch.setattr(eva_reduce, "eva_notes", lambda: [
        {**EVA_NOTE, "shape": (1, 4, 64, 16)}, EVA_NOTE])  # a shorter call of another program
    config = {"num_hidden_layers": 2, "num_attention_heads": 4, "hidden_size": 64,
              "window_size": 32, "chunk_size": 4}
    traffic = {"clients_per_round": 2, "local_steps": 2, "batch_size": 1, "seq_len": 128}
    return {"cell": {"name": "no_such_trace", "config": config, "traffic": traffic},
            "peaks": PEAKS, "traced_rounds": 3,
            "trace": {"chip0": {"busy_s": 0.010, "ops": {"x": 1.0}}}}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_readers_give_the_hand_figures(ctx):
    # 400 + 100 + 300 + 100 + 200 + 300 us under attn/eva of 10 ms busy; "evax" is no scope
    assert read("eva_time_pct", ctx) == pytest.approx(14.0)
    assert read("eva_summary_time_pct", ctx) == pytest.approx(4.0)
    assert read("dense_mlp_time_pct", ctx) == pytest.approx(45.0)
    assert read("head_loss_time_pct_evabyte", ctx) == pytest.approx(2.0)
    assert read("loop_steps_time_pct_evabyte", ctx) == pytest.approx(3.5)
    assert read("eva_remote_mass_pct", ctx) == pytest.approx(15.0)
    # the stair notes at the cell's T: 6 + 6 of 16 + 16 tiles
    assert read("eva_remote_tiles_visited_pct", ctx) == pytest.approx(37.5)
    # two layers x 3 rounds x 2 clients x 2 steps calls of the summaries over [1, 4, 128, 16]
    # bfloat16, forward and backward, by their bytes, over the 400 us under the scope
    elems, sums = 4 * 128 * 16, 4 * 32 * 16
    least = 2 * (2 * elems + 2 * sums) / 1e12 + 2 * (4 * elems + 2 * sums) / 1e12
    assert read("eva_summary_roofline", ctx) == pytest.approx(100.0 * 24 * least / 0.0004)
    # the local call (4 windows' causal squares) and the remote (6 blocks of 32 x 8), 4 heads of 16,
    # forward and backward, over the 500 us of the two kernels
    pairs_l, pairs_r = 4 * 32 * 33 // 2, 32 * 8 * 6
    fwd = (max(4.0 * 16 * 4 * pairs_l / 100e12, 2.0 * 4 * elems / 1e12)
           + max(4.0 * 16 * 4 * pairs_r / 100e12, 2.0 * (2 * elems + 2 * sums) / 1e12))
    bwd = (max(10.0 * 16 * 4 * pairs_l / 100e12, 2.0 * 7 * elems / 1e12)
           + max(10.0 * 16 * 4 * pairs_r / 100e12, 2.0 * (3 * elems + 4 * sums) / 1e12))
    assert read("flash_eva_roofline", ctx) == pytest.approx(100.0 * 24 * (fwd + bwd) / 0.0005)


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters(ctx, monkeypatch):
    """The parent of the PR that added them: every reader returns None and
    the result line leaves the metrics out."""
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {
        "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/Dense_0/dot_general", "convolution", 9.0)]})
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {})
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: [])
    monkeypatch.setattr(eva_reduce, "eva_notes", lambda: [])
    # the accepted head-and-loss reader gives 0.0 where the table has rows and none bears
    # its scopes; every other reader finds nothing and the result line leaves it out
    found = {name: read(name, ctx) for name in NEW}
    assert found.pop("head_loss_time_pct_evabyte") == 0.0
    assert set(found.values()) == {None}, found
    assert set(benchrun.layer_metrics({"per_layer": [{"name": n, "unit": "%"} for n in NEW]},
                                      ctx)) == {"head_loss_time_pct_evabyte"}
    # the scope without the notes, the notes without the scope
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    assert read("eva_summary_roofline", ctx) is None and read("flash_eva_roofline", ctx) is None
    monkeypatch.setattr(eva_reduce, "eva_notes", lambda: [EVA_NOTE])
    assert read("eva_summary_roofline", ctx) is not None
    assert read("flash_eva_roofline", ctx) is not None
    # a sequence of one window: no summaries, no staircase call
    monkeypatch.setattr(eva_reduce, "eva_notes", lambda: [{**EVA_NOTE, "summaries": 0}])
    assert read("eva_summary_roofline", ctx) is None
    # the program's own notes are what the reader reads when nothing stands in
    monkeypatch.undo()
    assert isinstance(eva_reduce.eva_notes(), list)


def test_the_parent_cannot_build_the_cell_and_says_so_at_once():
    """The driver tries the new cell on the parent commit with these benchmark
    files laid over it: its model takes no ``eva_window`` / ``num_pred_heads``,
    so the family's ``build`` raises a TypeError from the constructor, before
    any device work."""
    import fedml_tpu.models.mla_moe_transformer as models

    class Parent:  # the parent's constructor: the fields it had at PR 43
        def __init__(self, *, vocab_size, embed_dim, dense_layers, routed_layers, num_heads,
                     head_dim, dense_dim, mtp_depth, rope_theta, mixers, rms_eps, attn_impl,
                     dtype, remat, kv_heads=2, tie_head=False):
            raise AssertionError("the parent was built with fields it does not have")

    cell = benchrun.load_cell(CELL, ROOT)
    real = models.MLAMoETransformerLM
    models.MLAMoETransformerLM = Parent
    try:
        with pytest.raises(TypeError, match="eva_window|eva_chunk|norm_unit_offset|num_pred_heads"):
            family.build(cell["config"], cell["traffic"], 1)
    finally:
        models.MLAMoETransformerLM = real


def _readings():
    check = benchrun.load_cell(CELL, ROOT)["config"]["check"]
    at_size = check["readings_at_size"]
    faults = {name: {k: float(v) for k, v in reading.items()}
              for name, reading in {**at_size["broken"],
                                    "control_fp8": at_size["control_fp8"]}.items()}
    return check["limits"], at_size, faults


@pytest.mark.parametrize("fault", ["control_fp8", "remote_left_out", "mu_left_out"])
def test_the_limits_fail_each_fault_as_it_read_at_the_cells_size(fault, capsys):
    """The chip's readings at the timed sizes (the configuration file records
    them) through the harness's own ``judge``."""
    limits, _, faults = _readings()
    assert set(faults) == {"control_fp8", "remote_left_out", "mu_left_out"}
    assert not benchrun.judge(faults[fault], limits)
    assert "FAIL" in capsys.readouterr().out


def test_sound_readings_pass_with_room():
    """Every limit leaves the largest sound reading 1.5 times its size or
    more, and every fault and the control lie over a limit by 1.5 times or more."""
    limits, at_size, faults = _readings()
    assert benchrun.judge(at_size["sound_largest"], limits)
    for name, limit in limits.items():
        key = name if name in at_size["sound_largest"] else name + ".round0"
        assert 1.5 * at_size["sound_largest"][key] <= limit, (name, limit)
    for fault, reading in faults.items():
        over = [reading[k] / limits[k.replace(".round0", "")] for k in reading
                if k.replace(".round0", "") in limits]
        assert max(over) >= 1.5, (fault, reading)
