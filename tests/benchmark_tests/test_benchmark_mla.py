"""The configuration ``joyai_llm_flash_cut`` and the cell ``joyai_flash_silo2``
at a toy size on the CPU, in float32: the normal path (``FedSim.run``) equals
the plain reference, a lower precision or a broken path in its place does
not; the manifest's entries; the FLOPs arithmetic; and each new per-layer
reader on hand figures. The figures such a toy cell produces are never
device metrics.

Nothing here describes a TPU topology; the file is safe under xdist.
"""

import copy
import importlib
import json
import os

import jax
import pytest

from benchmark import mla_costs, mla_reduce, moe_reduce, scope_reduce
from benchmark import run as benchrun
from benchmark.families import mla_moe_lm as family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "joyai_flash_silo2", "joyai_llm_flash_cut"
# this file's own toy overrides: hidden 64, 4 heads of 16 + 8 score and 16 value
# columns, latents 48 / 32, a dense layer of 128 and two routed ones, 8 router
# outputs with experts 2 .. 5 held, top-2 of width 32, T 32
TOY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "intermediate_size": 128, "moe_intermediate_size": 32, "moe_router_outputs": 8,
              "n_routed_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 3,
              "vocab_size": 97, "compute_dtype": "float32", "remat": False}
TOY_TRAFFIC = {"seq_len": 32, "ramp_alphabet": 97}
# the toy's selection bias is wider than the cell's 0.02, so that it re-chooses
# tokens among 8 outputs as the cell's does among 256
TOY_INIT = {"select_bias": ["normal", 0.3]}
TIGHT = {"loss_gap": 1e-5, "norm_gap": 1e-4, "update_rel_l2": 1e-3}
SEED = 2 ** 31 + 77


def toy_cell():
    cell = benchrun.load_cell(CELL, ROOT)
    cell["config"] = {**copy.deepcopy(cell["config"]), **TOY_CONFIG}
    cell["config"]["share"]["experts_first"] = 2
    cell["config"]["init"].update(TOY_INIT)
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


@pytest.fixture(scope="module")
def reference():
    cell = toy_cell()
    shapes = program_check_shapes(cell)
    return cell, benchrun.reference_check(cell, SEED, cell["traffic"]["check_rounds"], shapes)


def test_toy_cell_is_correct(reference):
    cell, ref = reference
    check = program_check(cell)
    numbers = benchrun.compare(check, ref, family.HEAD)
    assert check["losses"], "no local training loss was compared"
    assert within(numbers, TIGHT), numbers
    assert benchrun.judge(numbers, TIGHT)
    assert "mtp" not in check["variables"] and "stats" not in check["variables"]


def _rotary_left_out(monkeypatch, rope=TOY_CONFIG["qk_rope_head_dim"]):
    """The score without its rotary columns' part (``rope`` of them)."""
    from fedml_tpu.models import mla_moe_transformer as model

    real = model.flash_attention_head_parallel

    def no_rope(q, k, v, **kw):
        scale = q.shape[-1] ** -0.5
        return real(q[..., :-rope], k[..., :-rope], v, sm_scale=scale, **kw)

    monkeypatch.setattr(model, "flash_attention_head_parallel", no_rope)


def _route_with(monkeypatch, change):
    """``ops/moe.py`` ``route`` called with changed arguments."""
    from fedml_tpu.ops import moe

    real = moe.route

    def route(x, kernel, top_k, *, select_bias, scale):
        return change(real, x, kernel, top_k, select_bias, scale)

    monkeypatch.setattr(moe, "route", route)


def _bias_left_out(monkeypatch):
    _route_with(monkeypatch, lambda real, x, kernel, k, b, scale: real(
        x, kernel, k, select_bias=0.0 * b, scale=scale))


def _bias_in_the_weights(monkeypatch):
    import jax.numpy as jnp

    def change(real, x, kernel, k, b, scale):
        ids, w = real(x, kernel, k, select_bias=b, scale=scale)
        chosen = jnp.take_along_axis(jax.nn.sigmoid(x @ kernel) + b, ids, axis=-1)
        return ids, scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)

    _route_with(monkeypatch, change)


def _scale_read_as_one(monkeypatch):
    _route_with(monkeypatch, lambda real, x, kernel, k, b, scale: real(
        x, kernel, k, select_bias=b, scale=1.0))


def _shared_left_out(monkeypatch):
    from fedml_tpu.models import mla_moe_transformer as model

    real = model.GatedMLP.__call__

    def call(self, u):
        out = real(self, u)
        return 0.0 * out if self.name == "shared" else out

    monkeypatch.setattr(model.GatedMLP, "__call__", call)


def _lambda_read_as_zero(monkeypatch):
    from fedml_tpu.core import trainer

    real = trainer.lm_loss
    monkeypatch.setattr(trainer, "lm_loss", lambda logits, batch: 0.0 * real(logits, batch))


BROKEN = {"rotary_part_of_the_score_left_out": _rotary_left_out,
          "bias_left_out_of_the_choice": _bias_left_out,
          "bias_added_into_the_weights": _bias_in_the_weights,
          "shared_expert_left_out": _shared_left_out,
          "scale_2p5_read_as_1": _scale_read_as_one,
          "lambda_read_as_0": _lambda_read_as_zero}


@pytest.mark.parametrize("broken", sorted(BROKEN))
def test_a_broken_path_is_not_correct(reference, monkeypatch, broken):
    """Each of the six ways to get this block wrong fails a limit. (The
    nwp task's own loss is ``TASKS``' entry, bound before the patch: only the
    MTP term reads ``trainer.lm_loss`` by name.)"""
    BROKEN[broken](monkeypatch)
    cell, ref = reference
    numbers = benchrun.compare(program_check(cell), ref, family.HEAD)
    assert not within(numbers, TIGHT), numbers
    assert max(numbers["update_rel_l2.params"] / TIGHT["update_rel_l2"],
               numbers["norm_gap"] / TIGHT["norm_gap"],
               numbers["loss_gap.round0"] / TIGHT["loss_gap"]) > 10, numbers


def test_fp8_control_fails(reference):
    cell, ref = reference
    stand_in = benchrun.reference_check(cell, SEED, ref["rounds"], program_check_shapes(cell),
                                        precision="fp8")
    numbers = benchrun.compare(stand_in, ref, family.HEAD)
    assert numbers["update_rel_l2.params"] > 3 * TIGHT["update_rel_l2"], numbers
    assert not benchrun.judge(numbers, {"update_rel_l2": TIGHT["update_rel_l2"]})


# -- the manifest's new entries and the configuration file ---------------------

NEW = ["mla_time_pct", "flash_mla_roofline", "mtp_time_pct", "moe_shared_time_pct",
       "moe_routed_time_pct", "moe_routed_held_pct"]


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_entries_and_the_configuration_file():
    manifest = _manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "silo2", 1)
    mine = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == NEW
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "rounds_per_s"
        assert callable(importlib.import_module(f"benchmark.layer_metrics.{m['name']}").read)
    loaded = benchrun.load_cell(CELL, ROOT)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW) <= reported and {"mfu_pct", "peak_hbm_gb", "device_idle_pct",
                                     "longest_gap_ms", "compiles_in_window", "host_stage_ms",
                                     "host_sync_ms"} <= reported
    assert not {"moe_time_pct", "flash_window_roofline", "matmul_time_pct"} & reported
    assert {m["name"] for m in loaded["end_to_end"]} == {"rounds_per_s", "setup_s"}
    assert loaded["traffic"] == {
        "clients_total": 2, "clients_per_round": 2, "local_steps": 2, "batch_size": 1,
        "seq_len": 8192, "ramp_alphabet": 16160, "frequency_of_the_test": 10000,
        "eval_batch_size": 1, "cohort_execution": "scan", "check_rounds": 2}
    config = loaded["config"]
    published = {  # the catalog's config, every key but the three reduced
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 7168,
        "kv_lora_rank": 512, "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-6, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
        "v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["moe_router_outputs"], config["vocab_size"]) == (5, 8, 256, 16160)
    assert config["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256,
                                   "vocab_size": 129280, "first_k_dense_replace": 1,
                                   "num_nextn_predict_layers": 1}
    assert set(config["reduced"]) == set(entry["reduced"])
    assert {"mtp_loss_weight", "mtp halves", "select_bias", "rotary pairing"} <= set(
        config["assumed"])
    share = config["share"]
    assert (share["chips_per_layer"], share["vocab_chips"], share["experts_first"]) == (32, 8, 0)
    assert share["experts_published"] // share["chips_per_layer"] == config["n_routed_experts"]
    assert share["vocab_published"] // share["vocab_chips"] == config["vocab_size"]
    assert config["optimizer"]["momentum"] == 0.0 and config["mtp_loss_weight"] == 0.3
    assert set(config["check"]["limits"]) and "seeds" in config["check"]["readings"]
    assert config["check"]["limits"]["loss_gap"] == 6e-5  # between its two readings
    with pytest.raises(ValueError, match="not this family's block"):
        family.build({**config, "scoring_func": "softmax"}, loaded["traffic"], 1)


PHASE_TWINS = ["train_fwd_time_pct", "train_bwd_time_pct", "optimizer_time_pct", "eval_time_pct",
               "head_loss_time_pct", "attn_bwd_time_pct", "unattributed_time_pct"]


@pytest.mark.parametrize("accepted", PHASE_TWINS)
def test_a_phase_share_is_the_accepted_reader_under_the_cells_name(accepted):
    """The accepted phase shares hold their ``workloads`` lists by a test, so
    the cell lists each under ``<name>_joyai``: the same ``read``, the same
    entry but for the name and the list."""
    per_layer = {m["name"]: m for m in _manifest()["per_layer"]}
    twin = per_layer[accepted + "_joyai"]
    assert {**twin, "name": accepted, "workloads": per_layer[accepted]["workloads"]} == (
        per_layer[accepted])
    assert twin["workloads"] == [CELL] and CELL not in per_layer[accepted]["workloads"]
    assert (importlib.import_module(f"benchmark.layer_metrics.{accepted}_joyai").read
            is importlib.import_module(f"benchmark.layer_metrics.{accepted}").read)
    assert accepted + "_joyai" in {m["name"] for m in benchrun.load_cell(CELL, ROOT)["per_layer"]}


def _readings():
    check = benchrun.load_cell(CELL, ROOT)["config"]["check"]
    at_size = check["readings_at_size"]
    faults = {"control_fp8": at_size["control_fp8"], **at_size["broken"]}
    return check["limits"], at_size, faults


@pytest.mark.parametrize("fault", ["control_fp8", *sorted(BROKEN)])
def test_the_limits_fail_each_fault_as_it_read_at_the_cells_size(fault, capsys):
    """The chip's readings at the timed sizes (the configuration file records
    them) through the harness's own ``judge``: the fp8 control and every
    broken path the toy cell fails is failed there too."""
    limits, _, faults = _readings()
    assert set(faults) == {"control_fp8", *BROKEN}
    assert not benchrun.judge(faults[fault], limits)
    assert "FAIL" in capsys.readouterr().out


def test_each_limit_lies_between_its_two_readings():
    """Sound runs pass with room, and each limit has a fault above it that
    reads at least three times the largest sound reading (a reading nearer
    the sound level than that is no upper reading: fp8's ``loss_gap``)."""
    limits, at_size, faults = _readings()
    assert benchrun.judge(at_size["sound"], limits)
    largest = at_size["sound_largest_of_15_seeds"]
    assert benchrun.judge(largest, limits)
    for name, limit in limits.items():
        key = name if name in largest else name + ".round0"
        upper = min(f[key] for f in faults.values() if f[key] >= 3 * largest[key])
        assert 2 * largest[key] <= limit <= upper / 2, (name, largest[key], limit, upper)


# -- the FLOPs arithmetic, pinned ---------------------------------------------------


def test_flops_closed_forms():
    """ISSUE 32: MLA's projections 26.35M parameters a layer; attention 640
    FLOP a visible pair a head forward, 6 x 192 + 4 x 128 backward; the
    round's FLOPs from the blocks by hand."""
    cell = benchrun.load_cell(CELL, ROOT)
    config, traffic = cell["config"], cell["traffic"]
    assert mla_costs.widths(config) == (192, 128)
    projections = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048)
    assert projections == 26_345_472
    assert mla_costs.mla_projection_flops(config) == 2.0 * projections
    pairs = 8192 * 8193 // 2
    assert mla_costs.attention_flops_per_token(config, 8192) == 640.0 * 32 * pairs / 8192
    attention = 2.0 * projections + 640.0 * 32 * pairs / 8192
    dense = attention + 6.0 * 2048 * 7168
    routed = attention + 2.0 * 2048 * 256 + 6.0 * 2048 * 768 * (1 + 8 * 8 / 256)
    assert mla_costs.block_flops_per_token(config, 8192, False) == pytest.approx(dense, rel=1e-12)
    assert mla_costs.block_flops_per_token(config, 8192, True) == pytest.approx(routed, rel=1e-12)
    head = 2.0 * 2048 * 16160
    mtp_block = routed - 640.0 * 32 * (pairs / 8192 - (8191 * 8192 // 2) / 8191)
    by_hand = dense + 4 * routed + head + (4.0 * 2048 * 2048 + mtp_block + head) * 8191 / 8192
    fwd = mla_costs.forward_flops_per_token(config, 8192)
    assert fwd == pytest.approx(by_hand, rel=1e-12)
    assert fwd / 1e6 == pytest.approx(1120.95, abs=0.01)
    assert family.samples_per_round(config, traffic) == 32768
    assert family.flops_per_round(config, traffic) / 1e12 == pytest.approx(110.19, abs=0.01)
    assert family.eval_samples(config, traffic) == 0
    # one forward call: 2 x (192 + 128) a pair a head; the rotary key moved once
    flops, moved = mla_costs.attention_cost(1, 32, 8192, 192, 128, 64, False)
    assert flops == 640.0 * pairs * 32
    assert moved == 2 * 8192 * (32 * 192 + 32 * 128 + (32 * 128 + 64) + 32 * 128)
    flops, moved = mla_costs.attention_cost(1, 32, 8192, 192, 128, 64, True)
    assert flops == (6 * 192 + 4 * 128) * pairs * 32.0
    assert moved == 2 * 8192 * (2 * 32 * 192 + 3 * 32 * 128 + 2 * (32 * 128 + 64))
    # a config without MTP: the main model alone
    assert mla_costs.forward_flops_per_token({**config, "num_nextn_predict_layers": 0}, 8192) == (
        pytest.approx(dense + 4 * routed + head, rel=1e-12))


# -- the readers, on hand figures -------------------------------------------------

PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
ROWS = {  # instruction -> [(program, op_name, category, self us)]
    "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_1/attn/attn/mla/q_a/dot_general",
                  "convolution", 400.0)],
    "flash_fwd.2": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_1/attn/attn/mla/attn/flash_fwd/flash_fwd",
                     "custom-call", 1000.0)],
    "flash_bwd_dkv.3": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_1/checkpoint/block_1/attn/"
                            "attn/mla/attn/blockwise_bwd/flash_bwd_dkv", "custom-call", 1600.0)],
    "flash_bwd_dq.4": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/mtp/mtp_block/checkpoint/"
                           "mtp_block/attn/attn/mla/attn/blockwise_bwd/flash_bwd_dq",
                        "custom-call", 1400.0)],
    "fusion.5": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_1/moe/shared/shared/gate/dot_general",
                  "convolution", 300.0)],
    "fusion.6": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_1/moe/route/dot_general", "convolution", 100.0)],
    "gmm.7": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_1/experts/moe/experts/jit(gmm)/x",
               "custom-call", 500.0)],
    "fusion.8": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/mtp/mtp_proj/dot_general", "convolution", 200.0)],
    "fusion.9": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(mtp))/fed/loss/mul", "loop", 250.0),
                 (2, "jit(g)/fed/eval/mtp_norm_f/attn/mlab/x", "loop", 950.0)],
}


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    counters = {"moe/assignments_held/layer_0": 6.0, "moe/assignments_held/layer_1": 2.0,
                "moe/assignments_held/layer_2": 4.0, "engine/other": 3.0}
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in counters.items() if k.startswith(prefix)})
    config = {"hidden_size": 1000, "num_attention_heads": 4, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "num_hidden_layers": 2, "num_nextn_predict_layers": 1, "num_experts_per_tok": 2}
    traffic = {"clients_per_round": 2, "local_steps": 2, "batch_size": 1, "seq_len": 64}
    return {"cell": {"name": "no_such_trace", "config": config, "traffic": traffic},
            "peaks": PEAKS, "traced_rounds": 3,
            "trace": {"chip0": {"busy_s": 0.010, "ops": {"x": 1.0}}}}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_readers_give_the_hand_figures(ctx):
    # 400 + 1000 + 1600 + 1400 us under attn/mla of 10 ms busy; "mlab" is no scope
    assert read("mla_time_pct", ctx) == pytest.approx(44.0)
    # under mtp: the dq kernel of its block, the product M and its loss (jvp(mtp))
    assert read("mtp_time_pct", ctx) == pytest.approx(100.0 * (1400 + 200 + 250) / 10000)
    assert read("moe_shared_time_pct", ctx) == pytest.approx(3.0)
    assert read("moe_routed_time_pct", ctx) == pytest.approx(6.0)  # route + experts, not shared
    # counters: (6 + 2 + 4) / 3 blocks of 1 x 64 tokens x 2 choices
    assert read("moe_routed_held_pct", ctx) == pytest.approx(100.0 * 4 / 128)
    # the kernels' 4000 us; two blocks over 64 positions and the MTP module's over 63
    least = 0.0
    for t in (64, 64, 63):
        pairs, q, out, keys = t * (t + 1) // 2 * 4, 4 * t * 24, 4 * t * 16, t * (4 * 16 + 8)
        least += max(2 * 40 * pairs / 100e12, 2 * (q + 2 * out + keys) / 1e12)
        least += max((6 * 24 + 4 * 16) * pairs / 100e12, 2 * (2 * q + 3 * out + 2 * keys) / 1e12)
    assert read("flash_mla_roofline", ctx) == pytest.approx(100.0 * 12 * least / 0.004)


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters(ctx, monkeypatch):
    """The parent of the PR that added them: every reader returns None."""
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {
        "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/Dense_0/dot_general", "convolution", 9.0)]})
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {})
    for name in NEW:
        assert read(name, ctx) is None, name
    assert benchrun.layer_metrics({"per_layer": [{"name": n, "unit": "%"} for n in NEW]}, ctx) == {}
    # another family's configuration under a trace that has the kernels: no roofline of this kind
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    other = {**ctx, "cell": {**ctx["cell"], "config": {"head_dim": 128}}}
    assert mla_reduce.flash_mla_roofline_pct(other) is None
    assert mla_reduce.routed_held_pct(other) is None
