"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, so that the arithmetic can be checked on a hand-made trace
(``benchmark/fixtures/``) without a chip:

- ``events_from_xplane`` reads the ``.xplane.pb`` jax's profiler wrote
  (``jax.profiler.ProfileData``): for each device plane the events of its
  "XLA Ops" line, and from the host planes the ``bench/traced_window``
  annotation that brackets the traced rounds on the same clock;
- ``reduce_events`` does the arithmetic: per chip the union of the
  intervals in which an operation ran (nested and overlapping events are not
  counted twice), the gaps between them, and per operation and per category
  its *self* time, i.e. its duration less the part its nested children cover.

How an operation is put in a category (read off one trace by hand on a TPU
v5e, jax 0.9.0; PERF.md section 3): an event of the TPU's "XLA Ops" line is
named by its whole HLO instruction and carries no category, and a fusion's
name does not say what it fuses. XLA's own category of each instruction
("convolution fusion", "loop fusion", "data formatting", ...) comes from
xprof's ``hlo_stats`` table of the same trace, where xprof is installed; else
the instruction's name and opcode decide. On the TPU XLA lowers a dot to a
convolution, so "convolution" here holds every MXU operation, dots included.
"""

from __future__ import annotations

import re

WINDOW_ANNOTATION = "bench/traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP_N = 10

COLLECTIVE_WORDS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
                    "all-to-all", "all_gather", "all_reduce", "reduce_scatter",
                    "collective_permute")


def short_name(name: str) -> str:
    """On the TPU an event's name is the whole HLO instruction
    (``%fusion.12 = (bf16[...]...) fusion(...)``): keep the instruction's
    own name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def categorize(name: str, hlo_category: str | None = None) -> str:
    """One of: convolution, matmul, collective, custom_call, other. By XLA's
    category where ``hlo_category`` gives it, else by the HLO instruction's
    own name and opcode."""
    if hlo_category:
        cat = hlo_category.lower()
        if any(w in cat for w in COLLECTIVE_WORDS):
            return "collective"
        if "convolution" in cat:
            return "convolution"
        if "dot" in cat or "matmul" in cat:
            return "matmul"
        if "custom-call" in cat or "custom call" in cat:
            return "custom_call"
        if cat not in ("async-start", "async-done"):  # those wrap what the name says
            return "other"
    head, _, body = name.partition(" = ")
    opcode = re.search(r"\)?\s([a-z][a-z\-]*)\(", " " + body) if body else None
    text = f"{head} {opcode.group(1) if opcode else ''}".lower()
    if any(w in text for w in COLLECTIVE_WORDS):
        return "collective"
    if "custom-call" in text or "custom_call" in text or "custom call" in text:
        return "custom_call"
    if "convolution" in text or re.search(r"conv(?!ert)", text):
        return "convolution"
    if re.search(r"(?<![a-z])(dot|matmul|einsum)", text):
        return "matmul"
    return "other"


def xprof_categories(path: str) -> dict:
    """{HLO instruction name: XLA's category} from xprof's ``hlo_stats`` of
    the trace; empty where xprof is not installed or gives no table."""
    import json

    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return {}
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    if not data:
        return {}
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    i_name, i_cat = cols.index("hlo_op_name"), cols.index("category")
    return {row["c"][i_name]["v"]: row["c"][i_cat]["v"] for row in table["rows"]}


def events_from_xplane(path: str) -> dict:
    """{"devices": {chip: [event]}, "window": (start_s, end_s) or None} with
    event = {"name", "category", "start", "dur"} in seconds."""
    from jax.profiler import ProfileData

    categories = xprof_categories(path)
    data = ProfileData.from_file(path)
    devices, window = {}, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    hlo_cat = categories.get(short_name(e.name))
                    events.append({
                        "name": short_name(e.name), "hlo_category": hlo_cat,
                        "category": categorize(e.name, hlo_cat),
                        "start": e.start_ns / 1e9, "dur": e.duration_ns / 1e9})
            devices[int(m.group(1))] = events
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_ANNOTATION:
                        window = (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                        break
                if window:
                    break
    return {"devices": devices, "window": window}


def _union_and_gaps(events: list, lo: float, hi: float):
    """Seconds covered by at least one event inside [lo, hi], and the gaps
    (start, dur) between covered stretches, the window's ends included."""
    busy, gaps, cursor = 0.0, [], lo
    for start, end in sorted((max(e["start"], lo), min(e["start"] + e["dur"], hi))
                             for e in events):
        if end <= start or end <= cursor:
            continue
        if start > cursor:
            gaps.append((cursor, start - cursor))
        busy += end - max(start, cursor)
        cursor = end
    if hi > cursor:
        gaps.append((cursor, hi - cursor))
    return busy, gaps


def _self_times(events: list) -> list:
    """Each event's duration less what its nested children cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["start"], -events[i]["dur"]))
    self_t = [e["dur"] for e in events]
    stack = []  # indices of open events
    for i in order:
        start, end = events[i]["start"], events[i]["start"] + events[i]["dur"]
        while stack and events[stack[-1]]["start"] + events[stack[-1]]["dur"] <= start:
            stack.pop()
        if stack:  # nested in (or overlapping) the open event: its parent loses it
            parent_end = events[stack[-1]]["start"] + events[stack[-1]]["dur"]
            self_t[stack[-1]] -= max(min(end, parent_end) - start, 0.0)
        stack.append(i)
    return [max(t, 0.0) for t in self_t]


def reduce_events(devices: dict, window=None) -> dict:
    """The reduction of per-chip event lists. ``window`` is (start, end) in
    the events' clock; without it the window runs from the first operation's
    start to the last one's end over all chips."""
    all_events = [e for evs in devices.values() for e in evs]
    if not all_events:
        raise ValueError("the trace holds no device operation: nothing ran on the chip")
    if window is None:
        window = (min(e["start"] for e in all_events),
                  max(e["start"] + e["dur"] for e in all_events))
    lo, hi = window
    chips = {}
    for chip, events in sorted(devices.items()):
        inside = [e for e in events if e["start"] + e["dur"] > lo and e["start"] < hi]
        busy, gaps = _union_and_gaps(inside, lo, hi)
        ops, cats = {}, {}
        for e, t in zip(inside, _self_times(inside)):
            ops[e["name"]] = ops.get(e["name"], 0.0) + t
            cats[e["category"]] = cats.get(e["category"], 0.0) + t
        chips[chip] = {"busy_s": busy, "gaps": gaps, "ops": ops, "categories": cats,
                       "n_events": len(inside)}
    first = chips[min(chips)]
    return {
        "window": window, "window_s": hi - lo,
        "busy_s": sum(c["busy_s"] for c in chips.values()) / len(chips),
        "n_events": sum(c["n_events"] for c in chips.values()),
        "chips": chips, "chip0": first,
    }


def reduce_fixture(path: str) -> dict:
    """The reduction of a hand-made trace (``benchmark/fixtures/``: events
    with XLA's category written beside each name), for the tests."""
    import json

    with open(path) as f:
        raw = json.load(f)
    devices = {int(chip): [dict(e, category=categorize(e["name"], e["hlo_category"]))
                           for e in events] for chip, events in raw["devices"].items()}
    return reduce_events(devices, tuple(raw["window"]))


def mxu_share_pct(chip: dict):
    """Percent of a chip's busy time in MXU operations: XLA's "convolution"
    categories, which on the TPU hold dots as well, and named dots."""
    t = chip["categories"].get("convolution", 0.0) + chip["categories"].get("matmul", 0.0)
    return 100.0 * t / chip["busy_s"] if t else None


def reduce_xplane(path: str, n_chips: int) -> dict:
    raw = events_from_xplane(path)
    if len(raw["devices"]) < n_chips:
        raise ValueError(f"the trace has device planes {sorted(raw['devices'])}, "
                         f"the cell ran on {n_chips} chip(s)")
    return reduce_events(raw["devices"], raw["window"])


def label_gaps(gaps: list, spans: list, offset: float) -> list:
    """Name each gap (start, dur on the trace's clock) by the host span that
    covers most of it; ``offset`` added to a trace time gives the spans'
    clock. Returns [[label, seconds], ...], longest first."""
    out = []
    for start, dur in sorted(gaps, key=lambda g: -g[1])[:TOP_N]:
        lo, hi = start + offset, start + offset + dur
        best, best_cover = "none", 0.0
        for s in spans:
            cover = min(hi, s["start"] + s["dur"]) - max(lo, s["start"])
            if cover > best_cover:
                best, best_cover = s["name"], cover
        out.append([best, dur])
    return out


def breakdown(reduced: dict, spans: list | None = None, offset: float = 0.0) -> dict:
    """The result line's ``breakdown``: chip 0's ten operations with most
    self time, and its ten longest idle gaps labelled by host span."""
    chip = reduced["chip0"]
    ops = sorted(chip["ops"].items(), key=lambda kv: -kv[1])[:TOP_N]
    return {"device_ops": [[name, t] for name, t in ops],
            "idle_gaps": label_gaps(chip["gaps"], spans or [], offset)}
