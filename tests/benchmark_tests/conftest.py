"""``BENCHMARK.json``'s lists grow only by appending, and
``test_benchmark_moe.py::test_manifest_entries_and_the_configuration_file``
reads PR 28's entries as the lists' last. A file the benchmark has is not a
later PR's to edit, so that one test is handed the manifest cut after PR 28's
entries, in a root of its own; every line of it runs, and whatever it says of
those entries, their files and the loaded cell still fails if it stops being
true. Later tests look their entries up by name and need nothing here."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# (test module, test) -> the last entry of each list when the test was written
READS_TAILS = {
    ("test_benchmark_moe", "test_manifest_entries_and_the_configuration_file"): {
        "configs": "smallthinker_21b_a3b_cut", "workloads": "smallthinker21b_silo2",
        "per_layer": "moe_load_max_over_mean"},
}


@pytest.fixture(autouse=True)
def manifest_as_the_test_found_it(request, monkeypatch, tmp_path):
    tails = READS_TAILS.get((request.module.__name__.rsplit(".", 1)[-1], request.node.name))
    if tails is None:
        return
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for group, last in tails.items():
        names = [entry["name"] for entry in manifest[group]]
        manifest[group] = manifest[group][:names.index(last) + 1]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    monkeypatch.setattr(request.module, "ROOT", str(tmp_path))
