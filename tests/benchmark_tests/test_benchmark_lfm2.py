"""The configuration ``lfm2_24b_a2b_cut`` and the cell ``lfm2moe_silo2`` at a
toy size on the CPU, in float32: the normal path (``FedSim.run``) equals the
plain reference, a lower precision or a broken path in its place does not; the
manifest's entries, looked up by name; the FLOPs and bytes arithmetic; and
each new per-layer reader on hand figures. The figures such a toy cell
produces are never device metrics.

Nothing here describes a TPU topology; the file is safe under xdist.
"""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import kernel_costs, lfm2_costs, moe_reduce, scope_reduce
from benchmark import run as benchrun
from benchmark.families import conv_moe_lm as family
from benchmark.layer_metrics import shortconv_gate_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "lfm2moe_silo2", "lfm2_24b_a2b_cut"
# this file's own toy overrides: hidden 64; 4 query heads on 2 KV heads of 16; three
# layers (published layers 1-3: the second dense layer, a convolution layer, then an
# attention and a convolution layer, both routed); 8 router outputs with experts 2 .. 5
# held, top-2 of width 32; 97 ids; T 16
TOY_CONFIG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 128, "moe_intermediate_size": 32, "moe_router_outputs": 8,
              "num_experts": 4, "num_experts_per_tok": 2, "num_hidden_layers": 3,
              "layers_run": [1, 2, 3], "vocab_size": 97, "compute_dtype": "float32",
              "remat": False}
TOY_TRAFFIC = {"seq_len": 16, "ramp_alphabet": 97, "batch_size": 1, "eval_batch_size": 1}
TOY_INIT = {"select_bias": ["normal", 0.3]}
TIGHT = {"loss_gap": 1e-5, "norm_gap": 2e-4, "update_rel_l2": 1e-3}
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
    assert "stats" not in check["variables"]
    params = check["variables"]["params"]
    assert "head" not in params and family.HEAD == "tok_embed"
    assert "update_rel_l2.head" in numbers  # the tied leaf, read as the output layer
    assert params["block_0"]["conv"]["taps"]["kernel"].shape == (3, 64)
    assert "mlp" in params["block_0"]
    assert params["block_1"]["attn"]["q_norm"]["scale"].shape == (16,)
    assert "router" in params["block_1"] and "shared" not in params["block_1"]


# -- the eight ways to get this block wrong -------------------------------------------


def _chain(monkeypatch, broken):
    """``ops/shortconv.py`` ``gated_short_conv`` with another chain in its place."""
    from fedml_tpu.ops import shortconv

    def call(bcz, w):
        b_gate, c_gate, z = jnp.split(bcz, 3, axis=-1)
        return broken(b_gate, c_gate, z, w, shortconv._taps)

    monkeypatch.setattr(shortconv, "gated_short_conv", call)


def _b_gate_left_out(monkeypatch):
    _chain(monkeypatch, lambda b, c, z, w, taps: c * taps(z, w))


def _c_gate_left_out(monkeypatch):
    _chain(monkeypatch, lambda b, c, z, w, taps: taps(b * z, w))


def _last_tap_alone(monkeypatch):
    _chain(monkeypatch, lambda b, c, z, w, taps: c * taps(
        b * z, jnp.zeros_like(w).at[-1].set(w[-1])))


def _shifted_by_one_token(monkeypatch):
    """The convolution sees t + 1: output t is what t + 1 should get."""
    def ahead(b, c, z, w, taps):
        conv = taps(b * z, w)
        return c * jnp.concatenate([conv[:, 1:], jnp.zeros_like(conv[:, :1])], axis=1)

    _chain(monkeypatch, ahead)


def _qk_norms_left_out(monkeypatch):
    from fedml_tpu.models import moe_transformer

    real = moe_transformer.RMSNorm.__call__

    def call(self, x):
        out = real(self, x)  # the leaf is made all the same
        return x.astype(out.dtype) if self.name in ("q_norm", "k_norm") else out

    monkeypatch.setattr(moe_transformer.RMSNorm, "__call__", call)


def _rotation_left_out(monkeypatch):
    from fedml_tpu.models import moe_transformer

    monkeypatch.setattr(moe_transformer, "rope", lambda x, theta: x)


def _bias_left_out(monkeypatch):
    from fedml_tpu.ops import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda x, kernel, top_k, *, select_bias, scale: real(
        x, kernel, top_k, select_bias=0.0 * select_bias, scale=scale))


def _heads_gradient_left_out(monkeypatch):
    """The tied leaf gets the gather's gradient alone."""
    import flax.linen as nn

    monkeypatch.setattr(nn.Embed, "attend", lambda self, query: jnp.dot(
        query, jax.lax.stop_gradient(self.embedding).T))


BROKEN = {"b_gate_left_out": _b_gate_left_out,
          "c_gate_left_out": _c_gate_left_out,
          "taps_read_as_the_last_tap_alone": _last_tap_alone,
          "convolution_shifted_by_one_token": _shifted_by_one_token,
          "qk_norms_left_out": _qk_norms_left_out,
          "rotation_left_out": _rotation_left_out,
          "bias_left_out_of_the_choice": _bias_left_out,
          "heads_gradient_left_out_of_the_embedding": _heads_gradient_left_out}


@pytest.mark.parametrize("broken", sorted(BROKEN))
def test_a_broken_path_is_not_correct(reference, monkeypatch, broken):
    """Each of the eight ways to get this block wrong fails a limit."""
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

NEW = ["shortconv_time_pct", "shortconv_gate_time_pct", "shortconv_gate_roofline",
       "flash_d64_roofline", "moe_routed_time_pct_lfm2", "moe_routed_held_pct_lfm2",
       "head_loss_time_pct_lfm2", "loop_steps_time_pct_lfm2"]


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _catalog_config():
    """The catalog's ``config`` of LFM2-24B-A2B, every key."""
    period = ["full_attention", "conv", "conv", "conv"]
    return {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
        "layer_types": ["conv", "conv"] + period * 9 + ["full_attention", "conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def test_manifest_entries_and_the_configuration_file():
    manifest = _manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry == manifest["configs"][-1] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, CONFIG, "silo2", 1)
    assert [m["name"] for m in manifest["per_layer"][-len(NEW):]] == NEW
    for m in manifest["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "rounds_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert callable(importlib.import_module(f"benchmark.layer_metrics.{m['name']}").read)
    mine = {m["name"]: m for m in manifest["per_layer"][-len(NEW):]}
    assert mine["shortconv_gate_roofline"]["unit"] == mine["flash_d64_roofline"]["unit"] == "%"
    assert mine["shortconv_gate_roofline"]["layer"] == mine["flash_d64_roofline"]["layer"] == (
        mine["shortconv_gate_time_pct"]["layer"]) == "kernels"
    assert mine["moe_routed_held_pct_lfm2"]["source"] == "program_counter"
    loaded = benchrun.load_cell(CELL, ROOT)
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW) <= reported and {"mfu_pct", "peak_hbm_gb", "device_idle_pct",
                                     "longest_gap_ms", "compiles_in_window", "host_stage_ms",
                                     "host_sync_ms"} <= reported
    assert not {"kda_time_pct", "mla_time_pct", "moe_routed_time_pct", "flash_window_roofline",
                "head_loss_time_pct", "loop_steps_time_pct"} & reported
    assert {m["name"] for m in loaded["end_to_end"]} == {"rounds_per_s", "setup_s"}
    assert loaded["traffic"] == {
        "clients_total": 2, "clients_per_round": 2, "local_steps": 2, "batch_size": 2,
        "seq_len": 8192, "ramp_alphabet": 16384, "frequency_of_the_test": 10000,
        "eval_batch_size": 2, "cohort_execution": "scan", "check_rounds": 2}
    config = loaded["config"]
    published = _catalog_config()
    reduced = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 16384}
    assert {k: config[k] for k in published} == {**published, **reduced}
    assert config["published"] == {**{k: published[k] for k in reduced}, "num_dense_layers": 2}
    assert set(config["reduced"]) == set(entry["reduced"]) == set(reduced)
    assert (config["moe_router_outputs"], config["layers_run"], config["parameters"]) == (
        64, [1, 2, 3, 4, 5], 486_062_464)
    assert {"head_dim", "tie_word_embeddings", "operator", "q and k norms", "rotation",
            "expert_bias", "router", "optimizer"} <= set(config["assumed"])
    assert config["tie_word_embeddings"] is True and "1e-6" in config["assumed"]["router"]
    share = config["share"]
    assert (share["chips_per_layer"], share["vocab_chips"], share["experts_first"],
            share["vocab_first"]) == (8, 4, 0, 0)
    assert share["experts_published"] // share["chips_per_layer"] == config["num_experts"]
    assert share["vocab_published"] // share["vocab_chips"] == config["vocab_size"]
    assert config["optimizer"]["momentum"] == 0.0 and config["remat"] is True
    assert lfm2_costs.layers(config) == (("conv", False), ("gqa", True), ("conv", True),
                                         ("conv", True), ("conv", True))
    assert lfm2_costs.dense_layers(config) == 1 and lfm2_costs.head_dim(config) == 64
    assert set(config["check"]["limits"]) and "seeds" in config["check"]["readings"]
    for key in ("deployment", "remat_why", "init_why"):
        assert len(config[key]) > 200, key
    assert "shortconv/in" in config["remat_why"] and "norm_f" in config["init_why"]
    # the tied leaf and the final norm keep the rule's own draw (init_why says why)
    from benchmark import traffic as trafficlib
    assert not [k for k in config["init"] if "tok_embed" in k or "norm_f" in k]
    assert trafficlib.init_leaf_rule("['params']['tok_embed']['embedding']", (16384, 2048),
                                     config["init"]) == ("normal", 0.02)
    assert trafficlib.init_leaf_rule("['params']['norm_f']['scale']", (2048,),
                                     config["init"]) == ("ones", 1.0)
    assert trafficlib.init_leaf_rule(
        "['params']['block_1']['experts']['gate']['kernel']", (8, 2048, 1536),
        config["init"])[1] == pytest.approx(2048 ** -0.5)
    with pytest.raises(ValueError, match="not this family's block"):
        family.build({**config, "conv_bias": True}, loaded["traffic"], 1)
    with pytest.raises(ValueError, match="layers_run"):
        lfm2_costs.layers({**config, "layers_run": [1, 2, 3]})


def test_the_program_counts_the_parameters_the_file_states():
    """486,062,464: the issue's count, from the module's own shapes."""
    cell = benchrun.load_cell(CELL, ROOT)
    shapes = program_check_shapes(cell)["params"]
    count = lambda tree: sum(leaf.size for leaf in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == cell["config"]["parameters"] == 486_062_464
    assert count(shapes["block_0"]["conv"]) == 16_783_360  # an operator
    assert count(shapes["block_1"]["attn"]) == 10_485_888  # attention with its two scales
    assert count(shapes["block_0"]["mlp"]) == 72_351_744
    routed = sum(count(shapes["block_2"][k]) for k in ("router", "select_bias", "experts"))
    assert routed == 75_628_608
    assert [count(shapes[f"block_{i}"]) for i in range(5)] == [
        89_139_200, 86_118_592, 92_416_064, 92_416_064, 92_416_064]
    assert count(shapes["tok_embed"]) == 33_554_432 and "head" not in shapes


def _readings():
    check = benchrun.load_cell(CELL, ROOT)["config"]["check"]
    at_size = check["readings_at_size"]
    faults = dict(at_size.get("broken", {}))
    if at_size.get("control_fp8"):
        faults["control_fp8"] = at_size["control_fp8"]
    # a reading that was not finite is written "nan": JSON has no such number
    faults = {name: {k: float(v) for k, v in reading.items()} for name, reading in faults.items()}
    return check["limits"], at_size, faults


@pytest.mark.parametrize("fault", ["control_fp8", *sorted(BROKEN)])
def test_the_limits_fail_each_fault_as_it_read_at_the_cells_size(fault, capsys):
    """The chip's readings at the timed sizes (the configuration file records
    them) through the harness's own ``judge``: every broken path the toy cell
    fails is failed there too."""
    limits, _, faults = _readings()
    assert set(faults) <= {"control_fp8", *BROKEN}
    if fault not in faults:
        pytest.skip("not read at the cell's size (the configuration's check.readings says why)")
    assert not benchrun.judge(faults[fault], limits)
    assert "FAIL" in capsys.readouterr().out


def test_sound_readings_pass_with_room_and_faults_read_over_the_limits():
    """Every limit leaves the largest sound reading 1.7 times its size or
    more; every fault read at the cell's size, and the fp8 control, lies over
    a limit by 1.6 times or more (the bias left out of the choice reads 1.64
    times ``norm_gap``'s limit since ``select_bias`` is drawn at 0.005, which
    gives every seed the same work: ``init_why``); and ``norm_gap`` lies
    between the largest sound reading and the one fault only it fails, three
    times from each."""
    limits, at_size, faults = _readings()
    assert benchrun.judge(at_size["sound"], limits)
    largest = at_size["sound_largest"]
    assert benchrun.judge(largest, limits)
    for name, limit in limits.items():
        key = name if name in largest else name + ".round0"
        assert 1.7 * largest[key] <= limit, (name, largest[key], limit)
    assert set(faults) == {"control_fp8", *BROKEN}
    for fault, reading in faults.items():
        over = [reading[k] / limits[k.replace(".round0", "")] for k in reading
                if k.replace(".round0", "") in limits]
        assert max(over) >= 1.6, (fault, reading)
    only_norm_gap = faults["qk_norms_left_out"]
    assert only_norm_gap["update_rel_l2.params"] < limits["update_rel_l2.params"]
    assert only_norm_gap["update_rel_l2.head"] < limits["update_rel_l2.head"]
    assert 3 * largest["norm_gap"] <= limits["norm_gap"] <= only_norm_gap["norm_gap"] / 3
    # the control is failed by the update's distance, not by the loss
    assert faults["control_fp8"]["update_rel_l2.params"] > 3 * limits["update_rel_l2.params"]
    assert faults["control_fp8"]["loss_gap.round0"] < limits["loss_gap"]


# -- the arithmetic, pinned -----------------------------------------------------------


def test_costs_closed_forms():
    """ISSUE 42's count: 439.4 MFLOP a token forward (the four operators
    30.6%, the dense feed-forward 32.9, the head 15.3, attention 12.4, routed
    experts and routers 8.8) and 86.4 TFLOP a round; the chain's and the
    attention's least times a call."""
    cell = benchrun.load_cell(CELL, ROOT)
    config, traffic = cell["config"], cell["traffic"]
    operator = 2.0 * (2048 * 6144 + 2048 * 2048) + 8.0 * 2048
    assert lfm2_costs.conv_flops_per_token(config) == operator
    pairs = 8192 * 8193 // 2
    attention = 2.0 * 2048 * (2 * 2048 + 2 * 512) + 4.0 * 2048 * pairs / 8192
    assert lfm2_costs.attention_flops_per_token(config, 8192) == pytest.approx(attention)
    dense = 6.0 * 2048 * 11776
    routed = 2.0 * 2048 * 64 + 6.0 * 2048 * 1536 * (4 * 8 / 64)
    assert lfm2_costs.feed_forward_flops_per_token(config, False) == dense
    assert lfm2_costs.feed_forward_flops_per_token(config, True) == pytest.approx(routed)
    head = 2.0 * 2048 * 16384
    by_hand = 4 * operator + attention + dense + 4 * routed + head
    fwd = lfm2_costs.forward_flops_per_token(config, 8192)
    assert fwd == pytest.approx(by_hand, rel=1e-12)
    assert fwd / 1e6 == pytest.approx(439.4, abs=0.05)
    for part, share in ((4 * operator, 30.6), (dense, 32.9), (head, 15.3), (attention, 12.4),
                        (4 * routed, 8.8)):
        assert 100.0 * part / fwd == pytest.approx(share, abs=0.05)
    assert family.samples_per_round(config, traffic) == 65536
    assert family.flops_per_round(config, traffic) / 1e12 == pytest.approx(86.4, abs=0.05)
    assert family.eval_samples(config, traffic) == 0
    # one call of the chain over 16,384 tokens of 2048 channels in bfloat16
    flops, moved = lfm2_costs.gate_cost(16384, 2048, 3, False)
    assert (flops, moved) == (8.0 * 16384 * 2048, 2 * 4 * 16384 * 2048)
    flops_b, moved_b = lfm2_costs.gate_cost(16384, 2048, 3, True)
    assert (flops_b, moved_b) == (23.0 * 16384 * 2048, 2 * 7 * 16384 * 2048 + 4 * 3 * 2048)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = kernel_costs.least_seconds(flops, moved, peaks)
    assert bound == "memory" and seconds * 1e3 == pytest.approx(0.328, abs=0.001)
    seconds_b, bound_b = kernel_costs.least_seconds(flops_b, moved_b, peaks)
    assert bound_b == "memory" and seconds_b * 1e3 == pytest.approx(0.574, abs=0.001)
    # one attention call: [2, 32, 8192, 64] on 8 KV heads, compute-bound both ways
    note = {"shape": (2, 32, 8192, 64), "q_heads_per_kv_head": 4, "window": None}
    flops, moved = lfm2_costs.attention_cost(note, False)
    assert flops == 4.0 * pairs * 2 * 32 * 64
    assert moved == 2.0 * (2 * 2 * 32 * 8192 * 64 + 2 * 2 * 8 * 8192 * 64)
    seconds, bound = kernel_costs.least_seconds(flops, moved, peaks)
    assert bound == "compute" and seconds * 1e3 == pytest.approx(2.791, abs=0.001)
    assert lfm2_costs.attention_cost(note, True)[0] == 2.5 * flops


# -- the readers, on hand figures -------------------------------------------------

PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
GATE = "block_0/conv/mix/shortconv/mix/shortconv/gate"
ROWS = {  # instruction -> [(program, op_name, category, self us)]
    "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_0/conv/mix/shortconv/in/dot_general",
                  "convolution", 400.0)],
    "fusion.2": [(1, f"jit(f)/fed/fwd_bwd/jvp(M)/{GATE}/mul", "loop", 100.0)],
    "fusion.3": [(1, f"jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_0/checkpoint/{GATE}/mul",
                  "loop", 300.0)],
    "fusion.4": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_0/checkpoint/rematted_computation/"
                     f"{GATE}/add", "loop", 100.0)],
    "flash_fwd.5": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_1/attn/gqa/attn/attn/flash_fwd/flash_fwd",
                     "custom-call", 200.0)],
    "flash_bwd_dq.6": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_1/attn/gqa/attn/attn/"
                           "blockwise_bwd/flash_bwd_dq", "custom-call", 300.0)],
    "fusion.7": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/block_1/moe/route/dot_general", "convolution",
                  100.0)],
    "gmm.8": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/block_1/experts/moe/experts/jit(gmm)/x",
               "custom-call", 500.0)],
    "fusion.9": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/head/dot_general", "convolution", 250.0)],
    "fusion.10": [(1, "jit(f)/fed/fwd_bwd/transpose(jvp(M))/head/transpose", "loop", 150.0)],
    "fusion.11": [(1, "jit(f)/loop/cohort/loop/epochs/while/body/loop/steps/while/body/copy",
                   "data formatting", 350.0)],
    "fusion.12": [(2, "jit(g)/fed/eval/block_0/conv/mix/shortconvx/gate/x", "loop", 950.0)],
}
CONV_NOTE = {"impl": "xla", "tokens": 256, "channels": 64, "taps": 3, "dtype": "bfloat16"}
ATTN_NOTES = [
    {"kernel": "fwd", "shape": (2, 4, 64, 16), "q_heads_per_kv_head": 2, "window": None},
    {"kernel": "fwd", "shape": (2, 4, 128, 16), "q_heads_per_kv_head": 2, "window": None},
    {"kernel": "dkv", "shape": (2, 4, 128, 16), "q_heads_per_kv_head": 2, "window": None}]


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    counters = {"moe/assignments_held/layer_0": 120.0, "moe/assignments_held/layer_1": 136.0}
    monkeypatch.setattr(moe_reduce, "counters",
                        lambda prefix: {k: v for k, v in counters.items() if k.startswith(prefix)})
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: ATTN_NOTES)
    monkeypatch.setattr(shortconv_gate_roofline, "calls", lambda: [
        {**CONV_NOTE, "tokens": 64}, CONV_NOTE])  # a shorter call of some other program, then ours
    config = {"num_hidden_layers": 4, "num_dense_layers": 2, "layers_run": [1, 2, 3, 4],
              "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
              "conv_L_cache": 3, "num_experts_per_tok": 4}
    traffic = {"clients_per_round": 2, "local_steps": 2, "batch_size": 2, "seq_len": 128}
    return {"cell": {"name": "no_such_trace", "config": config, "traffic": traffic},
            "peaks": PEAKS, "traced_rounds": 3,
            "trace": {"chip0": {"busy_s": 0.010, "ops": {"x": 1.0}}}}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_readers_give_the_hand_figures(ctx):
    # 400 + 100 + 300 + 100 us under mix/shortconv of 10 ms busy; "shortconvx" is no scope
    assert read("shortconv_time_pct", ctx) == pytest.approx(9.0)
    assert read("shortconv_gate_time_pct", ctx) == pytest.approx(5.0)
    assert read("moe_routed_time_pct_lfm2", ctx) == pytest.approx(6.0)
    assert read("head_loss_time_pct_lfm2", ctx) == pytest.approx(4.0)
    assert read("loop_steps_time_pct_lfm2", ctx) == pytest.approx(3.5)
    # (120 + 136) / 2 held of 2 x 128 x 4 offered
    assert read("moe_routed_held_pct_lfm2", ctx) == pytest.approx(12.5)
    # three convolution layers x 3 rounds x 2 clients x 2 steps calls over [256, 64] bfloat16,
    # forward and backward, by their bytes, over the 500 us under the chain's scope
    elems = 256 * 64
    least = 2 * 4 * elems / 1e12 + (2 * 7 * elems + 4 * 3 * 64) / 1e12
    assert read("shortconv_gate_roofline", ctx) == pytest.approx(100.0 * 3 * 12 * least / 0.0005)
    # one attention layer x 12 calls of [2, 4, 128, 16] on 2 KV heads over the 500 us of the kernels
    pairs = 128 * 129 // 2 * 2 * 4
    q_elems, kv_elems = 2 * 4 * 128 * 16, 2 * 2 * 128 * 16
    fwd = max(4.0 * pairs * 16 / 100e12, 2.0 * (2 * q_elems + 2 * kv_elems) / 1e12)
    bwd = max(10.0 * pairs * 16 / 100e12, 2.0 * (3 * q_elems + 4 * kv_elems) / 1e12)
    assert read("flash_d64_roofline", ctx) == pytest.approx(100.0 * 12 * (fwd + bwd) / 0.0005)


def test_readers_find_nothing_in_a_program_without_the_scopes_and_counters(ctx, monkeypatch):
    """The parent of the PR that added them: every reader returns None and
    the result line leaves the metrics out."""
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {
        "fusion.1": [(1, "jit(f)/fed/fwd_bwd/jvp(M)/Dense_0/dot_general", "convolution", 9.0)]})
    monkeypatch.setattr(moe_reduce, "counters", lambda prefix: {})
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: [])
    monkeypatch.setattr(shortconv_gate_roofline, "calls", lambda: [])
    # the accepted head-and-loss reader gives 0.0 where the table has rows and none bears
    # its scopes; every other reader finds nothing and the result line leaves it out
    found = {name: read(name, ctx) for name in NEW}
    assert found.pop("head_loss_time_pct_lfm2") == 0.0
    assert set(found.values()) == {None}, found
    assert set(benchrun.layer_metrics({"per_layer": [{"name": n, "unit": "%"} for n in NEW]},
                                      ctx)) == {"head_loss_time_pct_lfm2"}
    # the scope without the notes, the notes without the scope, another family's configuration
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: ROWS)
    assert read("shortconv_gate_roofline", ctx) is None and read("flash_d64_roofline", ctx) is None
    monkeypatch.setattr(shortconv_gate_roofline, "calls", lambda: [CONV_NOTE])
    monkeypatch.setattr(moe_reduce, "attention_notes", lambda: ATTN_NOTES)
    assert read("shortconv_gate_roofline", ctx) is not None
    assert read("flash_d64_roofline", ctx) is not None
    other = {**ctx, "cell": {**ctx["cell"], "config": {"head_dim": 128}}}
    assert read("shortconv_gate_roofline", other) is None
    assert read("flash_d64_roofline", other) is None
    # the program's own notes are what the reader reads when nothing stands in
    monkeypatch.undo()
    assert isinstance(shortconv_gate_roofline.calls(), list)


def test_the_parent_cannot_build_the_cell_and_says_so_at_once():
    """The driver tries the new cell on the parent commit with these benchmark
    files laid over it: its model takes no ``kv_heads`` / ``tie_head``, so the
    family's ``build`` raises a TypeError from the constructor, before any
    device work."""
    import fedml_tpu.models.mla_moe_transformer as model

    class Parent:
        def __init__(self, *, vocab_size, embed_dim, dense_layers, routed_layers, num_heads,
                     dense_dim, num_experts, experts_per_token, expert_dim, shared_dim,
                     route_scale, experts_first, experts_held, mtp_depth, rope_theta, mixers,
                     conv_size, rms_eps, attn_impl, dtype, remat):
            raise AssertionError("the parent's constructor took this PR's fields")

    cell = benchrun.load_cell(CELL, ROOT)
    real = model.MLAMoETransformerLM
    model.MLAMoETransformerLM = Parent
    try:
        with pytest.raises(TypeError, match="kv_heads|head_dim|tie_head"):
            family.build(cell["config"], cell["traffic"], 1)
    finally:
        model.MLAMoETransformerLM = real
