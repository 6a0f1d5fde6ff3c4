"""From the traced run to the phase shares and the idle attribution.

What the program writes (``fedml_tpu/obs/trace.py``) and what is read here:

- ``jax.named_scope`` phase scopes (``SCOPES``) are metadata on the compiled
  ops. Read off one TPU v5e trace by hand (jax 0.9.0, xprof 2.21.5; PERF.md
  section 3): the events of the device plane's "XLA Ops" line carry the HLO
  instruction and no scope; the scope is the ``op_name`` of the instruction's
  metadata, which xprof's ``hlo_stats`` table of the same trace gives as the
  ``tf_op_name`` column, one row per (program, instruction). A fusion has the
  ``op_name`` of its root instruction, so a fusion that mixes scopes counts
  whole under its root's. A ``while`` comes with an empty name and a self
  time of microseconds: what its body runs are rows of their own, and the
  copies and slices that carry its state are named by the ``scan`` / ``map``
  that made the loop.
- every host span is also a ``jax.profiler.TraceAnnotation``: an event on its
  thread's line of the xplane's host plane, on the device events' clock.

An op's time is the ``total_self_time`` of its row, xprof's own self time per
(program, instruction), and a share is that over chip 0's busy time in
``ctx["trace"]`` (the denominator of ``conv_time_pct`` / ``matmul_time_pct``).
Not the self times of ``ctx["trace"]["chip0"]["ops"]``: that reduction gives
a parent whose children overlap one another too much (on the TPU an op
starts while its predecessor drains), 5% of the busy time in both cells, all
of it on ``while`` ops, and it merges programs by instruction name; xprof's
self times add up to the busy time within 0.01% in both cells (my chip runs,
PR 25). The exclusive classes therefore partition the busy time:
``unattributed`` is what the scoped classes leave of it, so an op with no
row or no ``fed/*`` scope is counted there and never dropped.

The readers under ``benchmark/layer_metrics/`` are one line each over this
module. ``python benchmark/scope_reduce.py <cell>`` prints what the numbers
were made from, for the builder who looks at a trace by hand.
"""

from __future__ import annotations

import functools
import glob
import os
import re

# the program's own names (fedml_tpu/obs/trace.py SCOPES); a test holds the
# two equal, so a rename there fails a test and not a metric
SCOPES = ("fed/gather", "fed/fwd_bwd", "fed/loss", "fed/opt", "fed/aggregate",
          "fed/eval", "fed/pack_pass", "attn/flash_fwd", "attn/blockwise_bwd")
FLASH_KERNEL_NAME = "flash_fwd"

# an op belongs to the class of the outermost of these in its op_name
PHASE = re.compile(r"(?:^|[/(])fed/(gather|fwd_bwd|opt|aggregate|eval|pack_pass)(?=[/)]|$)")
BACKWARD = "transpose("  # jax's mark on the ops of a backward pass
HEAD_OR_LOSS = re.compile(r"(?:^|[/(])(?:head|fed/loss)(?=[/)]|$)")
ATTN_BWD = "attn/blockwise_bwd"
EXCLUSIVE = ("gather", "train_fwd", "train_bwd", "optimizer", "aggregate", "eval",
             "pack_pass", "unattributed")

DRIVER_MARK, STAGING_MARK = "engine/dispatch", "prefetch/stage"
STALL = "prefetch/consumer_stall"
PROGRAM_PREFIXES = ("engine/", "prefetch/")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def xplane_path(cell_name: str, root: str = ROOT):
    """The .xplane.pb the traced run of ``cell_name`` left, or None."""
    found = sorted(glob.glob(os.path.join(
        root, ".bench_out", "trace", cell_name, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def classify(op_name: str | None) -> str:
    """The exclusive class of an op from its ``op_name``."""
    m = PHASE.search(op_name or "")
    if not m:
        return "unattributed"
    scope = m.group(1)
    if scope == "fwd_bwd":
        return "train_bwd" if BACKWARD in op_name[m.end():] else "train_fwd"
    return {"opt": "optimizer"}.get(scope, scope)


def sub_shares(op_name: str | None) -> list:
    """The sub-shares (parts of forward + backward) an op also counts in."""
    if not op_name or not classify(op_name).startswith("train_"):
        return []
    return [name for name, hit in (("head_loss", HEAD_OR_LOSS.search(op_name)),
                                   ("attn_bwd", ATTN_BWD in op_name)) if hit]


@functools.lru_cache(maxsize=4)
def scope_rows(path: str | None) -> dict:
    """{instruction name: [(program id, op_name, category, self us)]} from
    xprof's ``hlo_stats`` of the trace; empty without a trace, without xprof,
    or where the trace has no device plane (a CPU run)."""
    import json

    if not path:
        return {}
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return {}
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    if not data:
        return {}
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    i = {k: cols.index(k) for k in ("hlo_op_name", "program_id", "tf_op_name", "category",
                                    "total_self_time")}
    rows = {}
    for row in table["rows"]:
        cell = lambda k: (row["c"][i[k]] or {}).get("v")  # noqa: E731
        rows.setdefault(cell("hlo_op_name"), []).append(
            (cell("program_id"), cell("tf_op_name") or "", cell("category") or "",
             float(cell("total_self_time") or 0.0)))
    return rows


def phase_seconds(rows: dict, busy_s: float) -> dict:
    """Seconds of the busy time by exclusive class and by sub-share: the
    table's self times under each scope, and what they leave as unattributed."""
    out = dict.fromkeys(EXCLUSIVE + ("head_loss", "attn_bwd"), 0.0)
    for per_program in rows.values():
        for _, op_name, _, self_us in per_program:
            for key in [classify(op_name)] + sub_shares(op_name):
                out[key] += self_us / 1e6
    out["unattributed"] = busy_s - sum(out[k] for k in EXCLUSIVE[:-1])
    return out


def phase_pct(ctx: dict, key: str):
    """Percent of chip 0's busy time in the class or sub-share ``key``;
    nothing where the trace holds no op."""
    chip = ctx["trace"]["chip0"]
    if not chip["ops"] or not chip["busy_s"]:
        return None
    rows = scope_rows(xplane_path(ctx["cell"]["name"]))
    return 100.0 * phase_seconds(rows, chip["busy_s"])[key] / chip["busy_s"]


# -- host spans of the timed window -------------------------------------------


def span_ms_per_round(ctx: dict, name: str):
    """Milliseconds a round in the window's host spans called ``name``;
    nothing where the program recorded none."""
    spans = [s for s in ctx["host_spans"] if s["name"] == name]
    if not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx["window"]["rounds"]


def stall_ms_per_round(ctx: dict):
    """The driver's wait for staging: 0.0 when the prefetcher ran and the
    driver never waited for it, nothing when it did not run."""
    if not any(s["name"] == STAGING_MARK for s in ctx["host_spans"]):
        return None
    return span_ms_per_round(ctx, STALL) or 0.0


# -- the mirrored annotations, on the device's clock --------------------------


@functools.lru_cache(maxsize=4)
def host_lines(path: str | None) -> tuple:
    """The host planes' lines that hold program annotations, each a tuple of
    (name, start s, end s) on the trace's clock; empty without a trace."""
    if not path:
        return ()
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = tuple((e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                          for e in line.events if e.name.startswith(PROGRAM_PREFIXES))
            if found:
                lines.append(found)
    return tuple(lines)


def thread_line(lines: tuple, mark: str) -> tuple:
    """The events of the thread whose line holds ``mark`` (both threads'
    lines bear the interpreter's name; the content tells them apart)."""
    return tuple(e for line in lines if any(n == mark for n, _, _ in line) for e in line)


def covered_seconds(gaps: list, intervals) -> float:
    """Seconds of ``gaps`` [(start, dur)] inside the union of ``intervals``
    [(start, end)]: the reduction's own union, a gap at a time."""
    from benchmark import trace_reduce

    events = [{"start": lo, "dur": hi - lo} for lo, hi in intervals]
    return sum(trace_reduce._union_and_gaps(events, start, start + dur)[0]
               for start, dur in gaps)


def idle_pct(ctx: dict, inside: str | None):
    """Percent of chip 0's idle time in the traced window that lies inside a
    driver-thread annotation called ``inside``, or, with None, inside no
    driver-thread program annotation at all. Nothing where the trace holds
    no mirrored annotation (the program does not mirror its spans)."""
    lines = host_lines(xplane_path(ctx["cell"]["name"]))
    if not lines:
        return None
    gaps = ctx["trace"]["chip0"]["gaps"]
    idle = sum(d for _, d in gaps)
    if not idle:
        return 0.0
    driver = thread_line(lines, DRIVER_MARK)
    if inside is None:
        return 100.0 * (1.0 - covered_seconds(gaps, [(lo, hi) for _, lo, hi in driver]) / idle)
    return 100.0 * covered_seconds(gaps, [(lo, hi) for n, lo, hi in driver if n == inside]) / idle


# -- for the builder: what the numbers were made from --------------------------


def report(cell_name: str, root: str = ROOT) -> dict:
    """The shares of the trace the cell's last traced run left under
    ``root``, from the trace alone (``trace_reduce.reduce_xplane``), with the
    ops that are unattributed, the custom calls and their scopes, the
    instruction names whose programs disagree on the class, and the threads."""
    import sys

    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import trace_reduce

    path = xplane_path(cell_name, root)
    if path is None:
        raise SystemExit(f"no trace of {cell_name} under {root}/.bench_out/trace")
    reduced = trace_reduce.reduce_xplane(path, 1)
    chip, rows = reduced["chip0"], scope_rows(path)
    busy = chip["busy_s"]
    seconds = phase_seconds(rows, busy)
    flat = sorted(((self_us / 1e6, name, classify(op_name), category, op_name)
                   for name, per_program in rows.items()
                   for _, op_name, category, self_us in per_program), reverse=True)
    mixed = [name for name, per_program in rows.items()
             if len({classify(r[1]) for r in per_program}) > 1]
    mixed_s = sum(r[3] for name in mixed for r in rows[name]) / 1e6
    custom = [(t, name, op_name) for t, name, _, category, op_name in flat
              if "custom-call" in category.lower()]
    unnamed = [c for c in custom if FLASH_KERNEL_NAME not in c[2]]
    lines = host_lines(path)
    gaps = chip["gaps"]

    def over(gap, mark):  # the thread's annotations that cover most of the gap
        start, dur = gap
        return sorted({n for n, lo, hi in thread_line(lines, mark)
                       if min(hi, start + dur) - max(lo, start) > 0.5 * dur})

    return {
        "xplane": path, "window_s": reduced["window_s"], "busy_s": busy,
        "pct_of_busy": {k: 100.0 * v / busy for k, v in seconds.items()},
        "table_self_s": sum(t for t, *_ in flat),
        "reduction_self_s": sum(chip["ops"].values()),
        "top_ops": [[n, t, cls, op[-110:]] for t, n, cls, _, op in flat[:25]],
        "unattributed_ops": [[n, t, op[-90:]] for t, n, cls, _, op in flat
                             if cls == "unattributed"][:15],
        "ops_without_a_row": sorted(((t, n) for n, t in chip["ops"].items() if n not in rows),
                                    reverse=True)[:10],
        "names_in_two_classes": {"count": len(mixed), "seconds": mixed_s,
                                 "pct_of_busy": 100.0 * mixed_s / busy},
        "custom_calls": {
            "count": len(custom), "seconds": sum(c[0] for c in custom),
            "without_kernel_name": {
                "count": len(unnamed), "seconds": sum(c[0] for c in unnamed),
                "op_names": sorted({c[2][-60:] for c in unnamed})[:12]}},
        "threads": [{"events": len(line), "names": sorted({n for n, _, _ in line})}
                    for line in lines],
        "idle_s": sum(d for _, d in gaps),
        "longest_gaps": [[g[1], over(g, DRIVER_MARK), over(g, STAGING_MARK)]
                         for g in sorted(gaps, key=lambda g: -g[1])[:8]],
    }


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(report(*sys.argv[1:3]), indent=1))
