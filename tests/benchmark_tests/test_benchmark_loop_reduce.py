"""``benchmark/loop_reduce.py`` and the five readers over it, against figures
worked out by hand on ``benchmark/fixtures/loop_fixture.json``: the loop an
unattributed op counts for, what the loops leave unscoped, the carry's passes,
and nothing at all from a program without the loops' names."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import loop_reduce, scope_reduce
from benchmark import run as benchrun

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "loop_fixture.json")
CELLS = ["cgpt13b_silo2", "resnet18gn_dev10", "smallthinker21b_silo2", "joyai_flash_silo2",
         "kimilinear_silo2"]
SHARES = {"loop_steps_time_pct": "steps", "loop_cohort_time_pct": "cohort",
          "loop_rounds_time_pct": "rounds", "unscoped_time_pct": "unscoped"}
NEW = sorted(SHARES) + ["loop_steps_carry_passes"]


def read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.fixture
def raw():
    with open(FIXTURE) as f:
        return json.load(f)


def _rows(raw):
    return {name: [tuple(r) for r in rs] for name, rs in raw["scope_rows"].items()}


@pytest.fixture
def ctx(raw, monkeypatch, tmp_path):
    """A traced run's ``ctx`` as ``benchmark/run.py`` builds it, as far as the
    readers look: the fixture's table in the place of an xplane's, its notes
    in the place of the program's, its trace under ``tmp_path``."""
    rows = _rows(raw)
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: rows)
    monkeypatch.setattr(scope_reduce, "xplane_path",
                        lambda cell, root=ROOT: str(tmp_path / "vm.xplane.pb"))
    monkeypatch.setattr(loop_reduce, "carry_notes",
                        lambda: {n["loop"]: n for n in raw["notes"]})
    return {"cell": {"name": "loop_fixture", "traffic": raw["traffic"], "config": raw["config"]},
            "peaks": {"hbm_bytes_per_s": raw["hbm_bytes_per_s"]},
            "traced_rounds": raw["traced_rounds"],
            "trace": {"chip0": {"busy_s": raw["busy_s"], "ops": {"copy.3": 0.8}}}}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_reader_gives_the_hand_figure(ctx, raw, name):
    want = 100.0 * raw["expect_seconds"][SHARES[name]] / raw["expect_seconds"]["busy"]
    assert read(name, ctx) == pytest.approx(want)


def test_shares_and_the_remainder_add_up_to_the_unattributed_share(ctx, raw):
    seconds = loop_reduce.loop_seconds(scope_reduce.scope_rows(None), raw["busy_s"])
    assert seconds == pytest.approx({k: raw["expect_seconds"][k] for k in seconds})
    unattributed = scope_reduce.phase_pct(ctx, "unattributed")
    assert unattributed == pytest.approx(24.0)
    assert sum(read(n, ctx) for n in SHARES) == pytest.approx(unattributed, abs=1e-9)
    # 0.15 s in a row with neither a phase nor a loop, 0.6 s in ops with no row
    assert read("unscoped_time_pct", ctx) == pytest.approx(1.5 + 6.0)


R = "jit(_block_impl)/loop/rounds/while/body/closed_call/"


@pytest.mark.parametrize("op_name,want", [
    (R + "loop/cohort/vmap(loop/epochs)/while/body/closed_call/vmap(loop/steps)/while", "steps"),
    (R + "loop/cohort/vmap(loop/epochs)/while", "steps"),  # epochs count with steps
    (R + "loop/cohort/vmap()/broadcast_in_dim", "cohort"),  # the innermost, not the first
    (R[:-len("closed_call/")] + "dynamic_update_slice", "rounds"),
    ("jit(_gather_round_impl)/loop/cohort/while/body/closed_call/loop/epochs/while/body/"
     "loop/steps/while/body/dynamic_slice", "steps"),
    ("loop/steps/while", "steps"),  # inside shard_map the stack starts anew
    (R + "loop/cohort/vmap(loop/epochs)/while/body/vmap(loop/steps)/while/body/"
     "vmap(fed/fwd_bwd)/jvp(ResNet)/conv", None),  # a phase claims it
    (R + "fed/aggregate/reduce_sum", None),
    ("jit(_block_impl)/convert_element_type", None),  # neither a phase nor a loop
    ("jit(f)/loop/stepsize/while", None),  # a longer name is another scope
    ("jit(f)/myloop/steps/while", None),
    ("", None),
    (None, None),
])
def test_an_op_counts_for_its_innermost_loop_unless_a_phase_claims_it(op_name, want):
    assert loop_reduce.loop_of(op_name) == want


def test_a_program_without_the_loops_names_gives_nothing(ctx, raw, monkeypatch):
    """The parent's program, or one served from an older cache: its table has
    rows and no loop's name, and a missing number is honest where 0.0 is not."""
    bare = {name: [(p, loop_reduce.LOOP.sub("/x", op), c, t) for p, op, c, t in rs]
            for name, rs in _rows(raw).items()}
    assert not any("loop/" in r[1] for rs in bare.values() for r in rs)
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: bare)
    assert [read(n, ctx) for n in NEW] == [None] * 5
    assert benchrun.layer_metrics({"per_layer": [{"name": n, "unit": "%"} for n in NEW]}, ctx) == {}
    # the accepted reader still reads: everything the loops carried is unattributed
    assert scope_reduce.phase_pct(ctx, "unattributed") == pytest.approx(24.0)
    # one op that bears a name, whatever its class, and the zeros are measured
    bare["fusion.1"] = _rows(raw)["fusion.1"]
    assert read("loop_steps_time_pct", ctx) == 0.0
    assert read("unscoped_time_pct", ctx) == pytest.approx(24.0)


def test_no_table_at_all_reads_as_the_accepted_reader_does(ctx, monkeypatch):
    """A CPU run's trace has no device plane and xprof gives no table: nothing
    is attributed, as ``unattributed_time_pct`` says there (100)."""
    monkeypatch.setattr(scope_reduce, "scope_rows", lambda path: {})
    assert scope_reduce.phase_pct(ctx, "unattributed") == pytest.approx(100.0)
    assert [read(n, ctx) for n in sorted(SHARES)] == [0.0, 0.0, 0.0, pytest.approx(100.0)]
    ctx["trace"]["chip0"]["ops"] = {}
    assert [read(n, ctx) for n in NEW] == [None] * 5


def test_passes_are_the_steps_seconds_over_the_carrys_least(ctx, raw):
    assert read("loop_steps_carry_passes", ctx) == pytest.approx(raw["expect_passes"])


@pytest.mark.parametrize("traffic,side_by_side,want", [
    # stated steps, clients in turn: 50 x 2 x 2 = 200 trips of 6.5 ms over 0.1 ms
    ({"clients_per_round": 2, "local_steps": 2, "batch_size": 4}, 1, 65.0),
    # two epochs over the largest client's 3 batches, 4 side by side: 1.3 s / (50 x 8 x 6 / 4)
    ({"clients_total": 3, "clients_per_round": 8, "batch_size": 20, "client_sizes": [20, 40, 60]},
     4, 1.3 / 600 / 4e-4),
])
def test_passes_count_the_trips_from_the_traffic_file(ctx, raw, monkeypatch, traffic,
                                                      side_by_side, want):
    notes = {n["loop"]: dict(n) for n in raw["notes"]}
    notes["loop/cohort"]["side_by_side"] = side_by_side
    monkeypatch.setattr(loop_reduce, "carry_notes", lambda: notes)
    ctx["cell"] = {"name": "loop_fixture", "traffic": traffic, "config": {"local_epochs": 2}}
    assert read("loop_steps_carry_passes", ctx) == pytest.approx(want)


@pytest.mark.parametrize("missing", ["loop/steps", "loop/cohort", "all"])
def test_no_passes_without_the_programs_notes(ctx, raw, monkeypatch, missing):
    notes = {n["loop"]: n for n in raw["notes"] if missing not in (n["loop"], "all")}
    monkeypatch.setattr(loop_reduce, "carry_notes", lambda: notes)
    assert read("loop_steps_carry_passes", ctx) is None
    assert read("loop_steps_time_pct", ctx) == pytest.approx(13.0)  # the shares need no note


def test_the_notes_are_kept_beside_the_trace_for_the_command_line(ctx, raw, tmp_path):
    assert not (tmp_path / loop_reduce.NOTES_FILE).exists()
    read("loop_steps_carry_passes", ctx)
    with open(tmp_path / loop_reduce.NOTES_FILE) as f:
        assert json.load(f) == raw["notes"]


def test_the_readers_copy_of_the_names_is_the_programs(monkeypatch):
    from fedml_tpu.obs import trace

    assert loop_reduce.LOOP_SCOPES == trace.LOOP_SCOPES
    assert loop_reduce.CARRY_NOTE == trace.LOOP_CARRY_NOTE
    for name in trace.LOOP_SCOPES:  # the pattern knows each name
        assert loop_reduce.LOOP.fullmatch(name).group(1) == name.split("/")[1]
    assert not set(trace.LOOP_SCOPES) & set(trace.SCOPES)
    assert not any(s.startswith("fed/") for s in trace.LOOP_SCOPES)
    # the program's notes, read where the readers read them
    monkeypatch.setattr(trace, "_program_notes", {})
    trace.loop(trace.SCOPE_LOOP_STEPS, {"w": np.zeros((3, 5), np.float32)})
    assert loop_reduce.carry_notes() == {"loop/steps": {
        "loop": "loop/steps", "leaves": 1, "bytes": 60, "side_by_side": 1}}


def test_manifest_lists_the_five_for_the_cells_they_read():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]] == CELLS
    mine = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == [
        "loop_steps_time_pct", "loop_cohort_time_pct", "loop_rounds_time_pct",
        "unscoped_time_pct", "loop_steps_carry_passes"]
    layers = {"loop_steps_time_pct": "local training and models",
              "loop_cohort_time_pct": "round programs", "loop_rounds_time_pct": "round programs",
              "unscoped_time_pct": "device", "loop_steps_carry_passes": "local training and models"}
    for m in mine:
        # every cell; the rounds' loop only where rounds are dispatched as a block
        assert m["workloads"] == (["resnet18gn_dev10"] if m["name"] == "loop_rounds_time_pct"
                                  else CELLS)
        assert (m["moves"], m["source"], m["better"]) == ("rounds_per_s", "device_trace", "lower")
        assert m["unit"] == ("ratio" if m["name"] == "loop_steps_carry_passes" else "%")
        assert m["layer"] == layers[m["name"]]
        assert callable(importlib.import_module(f"benchmark.layer_metrics.{m['name']}").read)
    for cell in CELLS:
        reported = {m["name"] for m in benchrun.load_cell(cell, ROOT)["per_layer"]}
        assert set(NEW) - reported == (set() if cell == "resnet18gn_dev10"
                                       else {"loop_rounds_time_pct"})
        # the accepted unattributed share stays where it was
        assert {"unattributed_time_pct", "unattributed_time_pct_joyai"} & reported or cell in (
            "smallthinker21b_silo2", "kimilinear_silo2")
