"""The reduction from device events to busy time, gaps, self times and
categories, against figures worked out by hand on the fixture trace."""

import json
import os

import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(trace_reduce.__file__), "fixtures", "trace_fixture.json")


@pytest.fixture(scope="module")
def fixture():
    with open(FIXTURE) as f:
        return json.load(f), trace_reduce.reduce_fixture(FIXTURE)


def test_busy_union_counts_nested_and_overlapping_once(fixture):
    _, red = fixture
    assert red["chip0"]["busy_s"] == pytest.approx(6.0)  # not 4+1+1.5+.5+1+1.5 = 9.5
    assert red["chips"][1]["busy_s"] == pytest.approx(2.0)
    assert red["busy_s"] == pytest.approx(4.0)  # averaged over the two chips
    assert red["window_s"] == pytest.approx(10.0)
    assert red["n_events"] == 7


def test_idle_share_and_gaps(fixture):
    _, red = fixture
    chip = red["chip0"]
    assert 1.0 - chip["busy_s"] / red["window_s"] == pytest.approx(0.4)
    assert [(round(s, 6), round(d, 6)) for s, d in chip["gaps"]] == [
        (0.0, 1.0), (5.0, 1.0), (8.0, 2.0)]
    assert max(d for _, d in chip["gaps"]) == pytest.approx(2.0)


def test_self_times_and_categories(fixture):
    _, red = fixture
    chip = red["chip0"]
    assert chip["ops"]["while.1"] == pytest.approx(1.0)  # 4.0 less its three children
    assert chip["ops"]["copy.7"] == pytest.approx(0.5)  # the custom call overlaps its tail
    assert chip["categories"]["convolution"] == pytest.approx(1.0)
    assert chip["categories"]["matmul"] == pytest.approx(1.5)
    assert chip["categories"]["collective"] == pytest.approx(0.5)
    assert chip["categories"]["custom_call"] == pytest.approx(1.5)
    assert chip["categories"]["other"] == pytest.approx(1.5)
    # self times add up to the busy union: nothing is counted twice
    assert sum(chip["categories"].values()) == pytest.approx(chip["busy_s"])


def test_window_defaults_to_first_and_last_operation(fixture):
    raw, _ = fixture
    devices = {0: [dict(e, category="other") for e in raw["devices"]["0"]]}
    red = trace_reduce.reduce_events(devices)
    assert red["window"] == (1.0, 8.0)
    assert red["chip0"]["busy_s"] == pytest.approx(6.0)


def test_empty_trace_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_events({0: []})


def test_breakdown_labels_gaps_by_host_span(fixture):
    raw, red = fixture
    out = trace_reduce.breakdown(red, raw["host_spans"], raw["host_offset"])
    assert out["device_ops"][0] == ["fusion.11", pytest.approx(1.5)]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    # longest first: [8,10) under engine/sync, then [0,1) under engine/stage
    # and [5,6) under engine/eval
    assert out["idle_gaps"][0] == ["engine/sync", pytest.approx(2.0)]
    assert {tuple(g) for g in out["idle_gaps"][1:]} == {("engine/stage", 1.0), ("engine/eval", 1.0)}


@pytest.mark.parametrize("name,hlo,want", [
    ("fusion.3", "convolution fusion", "convolution"),
    ("convolution.12", None, "convolution"),
    ("fusion.9", "dot fusion", "matmul"),
    ("dot_general.4", None, "matmul"),
    ("all-gather.1", None, "collective"),
    ("reduce-scatter-start.2", "reduce-scatter", "collective"),
    ("custom-call.5", None, "custom_call"),
    ("copy.1", "data formatting", "other"),
    ("fusion.2832", "loop fusion", "other"),
    ("convert_reduce_fusion.230", "convolution fusion", "convolution"),
    ("convert_reduce_fusion.230", None, "other"),  # "convert" is not a convolution
    ("%fusion.7 = (bf16[8]{0}, bf16[8]{0}) fusion(bf16[8]{0} %p), kind=kLoop", None, "other"),
    ("%convolution.3 = bf16[4,8]{1,0} convolution(bf16[4,8] %a, bf16[8,8] %b)", None, "convolution"),
    ("%all-gather-done.2 = f32[8]{0} all-gather-done(%s)", "async-done", "collective"),
    ("%custom-call.9 = bf16[4]{0} custom-call(%q), custom_call_target=\"tpu_custom_call\"", "custom-call", "custom_call"),
])
def test_categorize(name, hlo, want):
    assert trace_reduce.categorize(name, hlo) == want


def test_short_name_keeps_the_instructions_own_name():
    text = "%fusion.2832 = (bf16[10,64,32,32,16]{1,4,0,3,2:T(8,128)(2,1)}) fusion(bf16[64] %x)"
    assert trace_reduce.short_name(text) == "fusion.2832"
    assert trace_reduce.short_name("dot.5") == "dot.5"
