"""``benchmark/scope_reduce.py`` and the sixteen readers over it, against
figures worked out by hand on ``benchmark/fixtures/scope_fixture.json``: the
phase shares of the device's busy time, the staging and dispatch spans a
round, and the idle time the driver thread's annotations explain."""

import importlib
import json
import os

import pytest

from benchmark import scope_reduce, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "scope_fixture.json")

# reader -> the fixture's hand figure: (key of expect_seconds, its base) for a
# share, milliseconds a round for a span metric
EXPECTED = {
    "train_fwd_time_pct": ("train_fwd", "busy"),
    "train_bwd_time_pct": ("train_bwd", "busy"),
    "optimizer_time_pct": ("optimizer", "busy"),
    "gather_time_pct": ("gather", "busy"),
    "aggregate_time_pct": ("aggregate", "busy"),
    "eval_time_pct": ("eval", "busy"),
    "head_loss_time_pct": ("head_loss", "busy"),
    "attn_bwd_time_pct": ("attn_bwd", "busy"),
    "unattributed_time_pct": ("unattributed", "busy"),
    "idle_in_stage_stall_pct": ("idle_in_stall", "idle"),
    "idle_unlabelled_pct": ("idle_unlabelled", "idle"),
    "stage_cohort_ms": 2.0,
    "stage_put_ms": 1.0,
    "stage_keys_ms": 6.0,
    "stage_stall_ms": 1.5,
    "host_dispatch_ms": 1.0,
}


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.fixture
def raw():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture
def ctx(raw, monkeypatch):
    """A traced run's ``ctx`` as ``benchmark/run.py`` builds it, with the
    fixture's scope table and annotations in the place of an xplane."""
    rows = {name: [tuple(r) for r in rs] for name, rs in raw["scope_rows"].items()}
    lines = tuple(tuple(tuple(e) for e in line) for line in raw["host_lines"])
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: rows)
    monkeypatch.setattr(scope_reduce, "host_lines", lambda path: lines)
    return {"cell": {"name": "scope_fixture"}, "host_spans": raw["host_spans"],
            "window": {"rounds": raw["window_rounds"], "rounds_per_s": 1.0},
            "trace": trace_reduce.reduce_fixture(FIXTURE)}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_figure(ctx, raw, name):
    want = EXPECTED[name]
    if isinstance(want, tuple):
        part, base = want
        want = 100.0 * raw["expect_seconds"][part] / raw["expect_seconds"][base]
    assert read(name, ctx) == pytest.approx(want)


def test_exclusive_classes_add_up_to_the_busy_time(ctx, raw):
    chip = ctx["trace"]["chip0"]
    assert chip["busy_s"] == pytest.approx(raw["expect_seconds"]["busy"])
    assert sum(d for _, d in chip["gaps"]) == pytest.approx(raw["expect_seconds"]["idle"])
    exclusive = [n for n, want in EXPECTED.items() if isinstance(want, tuple)
                 and want[1] == "busy" and want[0] not in ("head_loss", "attn_bwd")]
    assert len(exclusive) == 7
    assert sum(read(n, ctx) for n in exclusive) == pytest.approx(100.0)
    seconds = scope_reduce.phase_seconds(scope_reduce.scope_rows(None), chip["busy_s"])
    assert seconds["pack_pass"] == 0.0
    assert sum(seconds[k] for k in scope_reduce.EXCLUSIVE) == pytest.approx(chip["busy_s"])
    # the table's own self times, not the reduction's: the fixture's while
    # reads 1.0 s either way, but a row's time is what counts
    assert seconds["attn_bwd"] == pytest.approx(1.0)
    # sub-shares are parts of forward + backward, not further classes
    assert seconds["head_loss"] + seconds["attn_bwd"] <= seconds["train_fwd"] + seconds["train_bwd"]


def test_the_readers_copy_of_the_names_is_the_programs():
    from fedml_tpu.obs import trace

    assert scope_reduce.SCOPES == trace.SCOPES
    assert scope_reduce.FLASH_KERNEL_NAME == trace.FLASH_KERNEL_NAME


@pytest.mark.parametrize("op_name,want,subs", [
    ("jit(f)/jit(main)/fed/gather/jit(_take)/gather", "gather", []),
    ("fed/aggregate/reduce_sum", "aggregate", []),  # inside shard_map the stack starts anew
    ("vmap(fed/fwd_bwd)/jvp(ResNet)/conv_init/conv_general_dilated", "train_fwd", []),
    ("vmap(fed/fwd_bwd)/transpose(jvp(ResNet))/conv_init/conv_general_dilated", "train_bwd", []),
    ("jit(f)/fed/fwd_bwd/jvp(fed/loss)/reduce_max", "train_fwd", ["head_loss"]),
    ("jit(f)/fed/fwd_bwd/transpose(jvp(TransformerLM))/head/dot_general", "train_bwd", ["head_loss"]),
    ("jit(f)/fed/fwd_bwd/transpose(fed/fwd_bwd)/jvp(TransformerLM)/block_1/attn/blockwise_bwd/while",
     "train_bwd", ["attn_bwd"]),
    ("jit(f)/transpose(jvp(Model))/dot_general", "unattributed", []),  # no phase scope at all
    ("jit(f)/fed/pack_pass/while/body/vmap(fed/fwd_bwd)/jvp(M)/dot", "pack_pass", []),  # outermost wins
    ("jit(f)/fed/eval/while/body/closed_call/ResNet/conv", "eval", []),
    ("jit(f)/fed/opt/jit(_where)/select_n", "optimizer", []),
    ("jit(f)/notfed/optics/add", "unattributed", []),
    ("jit(f)/fed/lossy/add", "unattributed", []),  # a longer name is another scope
    ("", "unattributed", []),
    (None, "unattributed", []),
])
def test_classify(op_name, want, subs):
    assert scope_reduce.classify(op_name) == want
    assert scope_reduce.sub_shares(op_name) == subs


def test_without_scopes_everything_is_unattributed_and_without_annotations_nothing(ctx, monkeypatch):
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {})
    monkeypatch.setattr(scope_reduce, "host_lines", lambda path: ())
    assert read("unattributed_time_pct", ctx) == pytest.approx(100.0)
    for name in ("train_fwd_time_pct", "eval_time_pct", "head_loss_time_pct"):
        assert read(name, ctx) == 0.0
    assert read("idle_in_stage_stall_pct", ctx) is None
    assert read("idle_unlabelled_pct", ctx) is None
    # the parent's program: spans, but no children; no prefetcher, no stall figure
    ctx["host_spans"] = [s for s in ctx["host_spans"]
                         if s["name"] in ("engine/stage", "engine/dispatch")]
    assert read("stage_put_ms", ctx) is None and read("stage_stall_ms", ctx) is None
    assert read("host_dispatch_ms", ctx) == pytest.approx(1.0)
    ctx["trace"]["chip0"]["ops"] = {}
    assert read("unattributed_time_pct", ctx) is None


def test_a_prefetcher_that_never_stalled_reads_zero(ctx):
    ctx["host_spans"] = [s for s in ctx["host_spans"] if s["name"] != scope_reduce.STALL]
    assert read("stage_stall_ms", ctx) == 0.0


def test_no_trace_on_disk_is_an_empty_table_not_an_error(tmp_path):
    assert scope_reduce.xplane_path("no_such_cell", str(tmp_path)) is None
    assert scope_reduce.scope_rows(None) == {} and scope_reduce.host_lines(None) == ()


def test_manifest_lists_the_sixteen_for_the_cells_they_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert len(EXPECTED) == 16 and set(EXPECTED) <= set(per_layer)
    for name in EXPECTED:
        m = per_layer[name]
        lm_only = name in ("head_loss_time_pct", "attn_bwd_time_pct")
        assert m["workloads"] == (["cgpt13b_silo2"] if lm_only
                                  else ["cgpt13b_silo2", "resnet18gn_dev10"])
        assert m["moves"] == "rounds_per_s"
        assert m["unit"] == ("ms/round" if name.endswith("_ms") else "%")
        assert m["source"] == ("program_span" if name.endswith("_ms") else "device_trace")
        assert m["better"] == ("higher" if name in ("train_fwd_time_pct", "train_bwd_time_pct")
                               else "lower")
