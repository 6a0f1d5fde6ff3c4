"""BENCHMARK.json against the contract's limits, against the files it names,
and the promise that a later PR adds cells, configurations, families and
per-layer metrics as new files plus manifest entries, editing nothing."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32 and all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) and not p.startswith("/")
        assert ".." not in p.split("/") and os.path.isdir(os.path.join(ROOT, p))
    for word in manifest["command"]:
        if os.path.exists(os.path.join(ROOT, word)) and "/" in word:
            assert any(word.startswith(p + "/") for p in manifest["paths"])


def test_names_units_and_entry_keys(manifest):
    for cfg in manifest["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(cfg["name"]) and _line(cfg["source"]) and _line(cfg["why"])
        assert len(cfg["reduced"]) <= 16 and all(NAME.match(k) for k in cfg["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert 1 <= len(manifest["configs"]) <= 24 and 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_named_file_exists(manifest):
    bench = os.path.join(ROOT, manifest["paths"][0])
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    for cfg in manifest["configs"]:
        assert cfg["name"] in used, "a configuration no cell uses"
        assert any(cfg["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        assert body["name"] == cfg["name"]
        assert sorted(body["reduced"]) == sorted(cfg["reduced"])
        assert os.path.isfile(os.path.join(bench, "families", body["family"] + ".py"))
        assert set(body["check"]["limits"]), "a configuration without limits decides nothing"
    for w in manifest["workloads"]:
        assert w["config"] in {c["name"] for c in manifest["configs"]}
        with open(os.path.join(bench, "workloads", w["name"] + ".json")) as f:
            body = json.load(f)
        assert body["config"] == w["config"] and body["chips"] == w["chips"]
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "layer_metrics", m["name"] + ".py"))


def _cells_of(metric, manifest):
    return set(metric.get("workloads", [w["name"] for w in manifest["workloads"]]))


def test_moves_and_cells(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert _cells_of(e2e["setup_s"], manifest) == cells
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert _cells_of(m, manifest) <= _cells_of(e2e[m["moves"]], manifest)
    for cell in cells:
        others = [m for m in e2e.values() if m["name"] != "setup_s" and cell in _cells_of(m, manifest)]
        assert others, f"{cell} reports no end-to-end metric but setup_s"
        assert any(cell in _cells_of(m, manifest) for m in manifest["per_layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert _cells_of(m, manifest) <= cells


def test_at_most_a_quarter_of_the_cells_take_four_chips(manifest):
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


EXTENSION = r"""
import json, sys
sys.path.insert(0, ".")
from benchmark import run
cell = run.load_cell("newcell", ".")
assert cell["config"]["name"] == "newconfig" and cell["family"].__name__.endswith("newfamily")
assert cell["traffic"]["clients_total"] == 3 and cell["chips"] == 1
names = [m["name"] for m in cell["per_layer"]]
assert "new_metric" in names and "conv_time_pct" not in names
from benchmark import trace_reduce
out = run.layer_metrics(cell, {
    "answer": 42.0, "compiles_in_window": 0, "host_spans": [], "chips": 1, "cell": cell,
    "window": {"rounds": 1, "rounds_per_s": 1.0}, "flops_per_round": 1e12,
    "peaks": {"bf16_flops_per_s": 197e12}, "memory_peak_bytes": 5e9,
    "trace": trace_reduce.reduce_fixture("benchmark/fixtures/trace_fixture.json"),
    "traced_rounds": 2})
assert out["new_metric"] == {"value": 42.0, "unit": "count"}, out
assert "host_stage_ms" not in out  # a reader that finds nothing returns nothing
print("found")
"""


def test_new_files_are_found_with_no_edit(manifest, tmp_path):
    """Copy the benchmark, drop in a configuration, a workload, a family and
    a per-layer metric, add manifest entries, and load them."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    bench = tmp_path / "benchmark"
    (bench / "families" / "newfamily.py").write_text("REFERENCE = 'none'\n")
    (bench / "configs" / "newconfig.json").write_text(json.dumps(
        {"name": "newconfig", "family": "newfamily", "reduced": {}, "check": {"limits": {"x": 0}}}))
    (bench / "workloads" / "newcell.json").write_text(json.dumps(
        {"name": "newcell", "config": "newconfig", "chips": 1, "traffic": {"clients_total": 3}}))
    (bench / "layer_metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx['answer']\n")
    new = json.loads(json.dumps(manifest))
    new["configs"].append({"name": "newconfig", "source": "https://example.org/paper",
                           "file": "benchmark/configs/newconfig.json", "reduced": [], "why": "w"})
    new["workloads"].append({"name": "newcell", "config": "newconfig", "traffic": "new",
                             "chips": 1, "why": "w"})
    new["per_layer"].append({"name": "new_metric", "unit": "count", "better": "lower",
                             "source": "program_counter", "layer": "round driver",
                             "moves": "rounds_per_s", "workloads": ["newcell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    done = subprocess.run([sys.executable, "-c", EXTENSION], cwd=tmp_path, capture_output=True,
                          text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0 and done.stdout.strip().endswith("found"), done.stderr[-2000:]
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
