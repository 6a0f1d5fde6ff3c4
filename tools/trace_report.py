"""Summarize a fedml_tpu trace (obs/trace.py output, JSONL or Chrome
trace-event JSON): top spans by total/self time, pipeline stall fraction,
packed-lane occupancy, and counter series — the terminal-side answer to
"where did the round time go" before (or instead of) opening Perfetto.

    python tools/trace_report.py RUN_DIR/trace.chrome.json
    python tools/trace_report.py RUN_DIR/trace.jsonl --format json --top 15

Pointed at a DIRECTORY of per-lane ``trace_<lane>.jsonl`` exports (what the
``trace_lanes=`` run harnesses write), it merges them in-memory with
tools/trace_merge.py and adds the round critical-path table: for every
``round/close`` (and async ``async/emit``) it walks the causal chain —
parent links, same-thread predecessors, and the cross-rank jumps the wire
contexts recorded — back toward the round's origin and names the gating
leg: which lane, which span, how many ms it held the round open
(docs/OBSERVABILITY.md "Reading a round's critical path").

    python tools/trace_report.py RUN_DIR            # per-round gating table
    python tools/trace_report.py RUN_DIR --format json

Spans a crash or hang left open (exported as ``B`` records) render
open-ended — duration extended to the trace end and flagged ``open`` —
instead of corrupting the timestamp-nesting reconstruction; a final JSONL
line torn by mid-write death is dropped, not fatal.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

# span names whose total duration is host-side *waiting* rather than work —
# their share of wall time is the pipeline stall fraction
STALL_SPANS = ("prefetch/producer_blocked", "prefetch/consumer_stall")
OCCUPANCY_GAUGE = "engine/lane_occupancy"

# causal-walk terminals: spans that close a round's output (the sync
# barrier's round close; the barrier-free server's model emission)
TERMINAL_SPANS = ("round/close", "async/emit")
_MAX_CHAIN = 512


def load_events(path: str | Path) -> list[dict]:
    """Load trace events from either exporter format. Chrome files are an
    object with a ``traceEvents`` list; JSONL files are one event per line
    (a torn FINAL line — the process died mid-write — is dropped).
    Metadata (``ph == "M"``) events are dropped; open-span ``B`` records
    are kept (summarize renders them open-ended)."""
    path = Path(path)
    text = path.read_text()
    try:  # Chrome form: ONE json document (multi-line JSONL fails this)
        obj = json.loads(text)
    except json.JSONDecodeError:
        lines = [ln for ln in text.splitlines() if ln.strip()]
        events = []
        for i, line in enumerate(lines):
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                if i == len(lines) - 1:
                    continue  # torn tail write; the rest of the file is whole
                raise
    else:
        if isinstance(obj, dict) and "traceEvents" in obj:
            events = obj["traceEvents"]
        elif isinstance(obj, list):
            events = obj
        else:  # a one-line JSONL file parses as a single event dict
            events = [obj]
    out = []
    for e in events:
        if e.get("ph") == "M":
            continue
        if "name" not in e or "ts" not in e or "ph" not in e:
            raise ValueError(
                f"{path}: event missing name/ts/ph fields: {e!r}"
            )
        out.append(e)
    return out


def _self_times(spans: list[dict]) -> dict[int, float]:
    """Per-span self time (dur minus same-thread children), computed from
    timestamp nesting: spans recorded by context managers on one thread are
    properly nested, so a stack sweep in ts order recovers the tree.
    Returns {id(span): self_us}."""
    out: dict[int, float] = {}
    by_tid: dict[int, list[dict]] = {}
    for s in spans:
        by_tid.setdefault(s.get("tid", 0), []).append(s)
    for group in by_tid.values():
        group.sort(key=lambda s: (s["ts"], -s.get("dur", 0.0)))
        stack: list[tuple[float, dict, list[float]]] = []  # (end, span, child durs)

        def pop(entry):
            end, span, children = entry
            out[id(span)] = max(span.get("dur", 0.0) - sum(children), 0.0)

        for s in group:
            dur = s.get("dur", 0.0)
            while stack and stack[-1][0] <= s["ts"] + 1e-9:
                pop(stack.pop())
            # count s toward the enclosing span's children only when fully
            # contained: manually-timed spans (Tracer.add_span, the build
            # listener's) can overlap without nesting, and subtracting
            # a merely-overlapping span would corrupt the parent's self time
            if stack and stack[-1][0] >= s["ts"] + dur - 1e-9:
                stack[-1][2].append(dur)
            stack.append((s["ts"] + dur, s, []))
        while stack:
            pop(stack.pop())
    return out


def _with_open_spans(events: list[dict]) -> tuple[list[dict], int]:
    """Complete (``X``) spans plus every ``B`` record rendered open-ended:
    duration extended to the trace end and flagged ``open=True`` — a span a
    crash left unterminated stays visible (and stays properly nested, so
    the self-time sweep is not corrupted). Returns (spans, open_count)."""
    spans = [e for e in events if e.get("ph") == "X"]
    opens = [e for e in events if e.get("ph") == "B"]
    if not opens:
        return spans, 0
    t_max = max((e["ts"] + e.get("dur", 0.0) for e in events
                 if "ts" in e), default=0.0)
    for e in opens:
        spans.append({**e, "ph": "X", "dur": max(t_max - e["ts"], 0.0),
                      "args": {**e.get("args", {}), "open": True}})
    return spans, len(opens)


def summarize(events: list[dict]) -> dict:
    """Aggregate a trace into the report dict: per-name span rollups
    (count/total/self/max, sorted by total desc), wall span, stall
    fraction, lane occupancy, and counter last-values."""
    spans, n_open = _with_open_spans(events)
    counters = [e for e in events if e.get("ph") == "C"]
    instants = [e for e in events if e.get("ph") == "i"]
    if not events:
        return {"wall_ms": 0.0, "spans": [], "counters": {},
                "stall_fraction": None, "lane_occupancy_mean": None,
                "events": 0, "open_spans": 0}
    t_min = min(e["ts"] for e in events)
    t_max = max(e["ts"] + e.get("dur", 0.0) for e in events)
    wall_us = max(t_max - t_min, 1e-9)

    selfs = _self_times(spans)
    rollup: dict[str, dict] = {}
    for s in spans:
        r = rollup.setdefault(
            s["name"],
            {"name": s["name"], "count": 0, "total_ms": 0.0,
             "self_ms": 0.0, "max_ms": 0.0},
        )
        dur_ms = s.get("dur", 0.0) / 1e3
        r["count"] += 1
        r["total_ms"] += dur_ms
        r["self_ms"] += selfs.get(id(s), 0.0) / 1e3
        r["max_ms"] = max(r["max_ms"], dur_ms)
    span_rows = sorted(rollup.values(), key=lambda r: -r["total_ms"])
    for r in span_rows:
        for k in ("total_ms", "self_ms", "max_ms"):
            r[k] = round(r[k], 3)

    stall_us = sum(
        s.get("dur", 0.0) for s in spans if s["name"] in STALL_SPANS
    )
    # counter/gauge series rollup: sample count + min/max/mean/last — the
    # series' shape without replaying it (a gauge's min/max bound its
    # excursion; a cumulative counter's last value is its total)
    counter_rollup: dict[str, dict] = {}
    for c in counters:
        v = c.get("args", {}).get("value")
        r = counter_rollup.setdefault(
            c["name"],
            {"count": 0, "last": None, "mean": 0.0, "min": None, "max": None})
        r["count"] += 1
        r["last"] = v
        if v is not None:
            r["mean"] += (v - r["mean"]) / r["count"]
            r["min"] = v if r["min"] is None else min(r["min"], v)
            r["max"] = v if r["max"] is None else max(r["max"], v)
    for r in counter_rollup.values():
        r["mean"] = round(r["mean"], 4)
    occ = counter_rollup.get(OCCUPANCY_GAUGE)
    return {
        "wall_ms": round(wall_us / 1e3, 3),
        "spans": span_rows,
        "counters": counter_rollup,
        "instants": sorted({e["name"] for e in instants}),
        "stall_fraction": round(stall_us / wall_us, 4),
        "lane_occupancy_mean": occ["mean"] if occ else None,
        "events": len(events),
        "open_spans": n_open,
    }


# -- round critical path (merged multi-rank traces) --------------------------


def _lanes_by_pid(merged: dict) -> dict[int, str]:
    by_pid = {pid: lane for lane, pid in merged.get("lanes", {}).items()}
    if not by_pid:  # a written trace.merged.json: recover from metadata
        for e in merged.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                by_pid[e.get("pid", 0)] = e.get("args", {}).get("name", "")
    return by_pid


def _walk_chain(span: dict, idx: dict, siblings: dict, pid_by_lane: dict,
                t_floor: float = float("-inf")) -> list[dict]:
    """The causal chain behind ``span``, newest first. Each step prefers
    (1) the cross-rank jump a wire context recorded (``ctx_lane``/
    ``ctx_span`` -> the sender lane's send span), then (2) the latest
    same-parent sibling that ended before this span began (the preceding
    step of the same handler — e.g. the local train before its upload),
    then (3) the enclosing parent span. The walk stops at ``t_floor`` (the
    previous round's close): everything before it belongs to the previous
    round's window and would mis-charge this round's gating leg to it."""
    chain = [span]
    seen = {id(span)}
    cur = span
    while len(chain) < _MAX_CHAIN:
        args = cur.get("args", {})
        nxt = None
        src_lane, src_span = args.get("ctx_lane"), args.get("ctx_span")
        if src_lane is not None and src_span is not None:
            nxt = idx.get((pid_by_lane.get(src_lane), src_span))
        if nxt is None or id(nxt) in seen or nxt["ts"] <= t_floor:
            group = siblings.get((cur.get("pid", 0), cur.get("tid", 0),
                                  args.get("parent_id")), ())
            best = None
            for s in group:
                if id(s) in seen or s["ts"] <= t_floor:
                    continue
                if s["ts"] + s.get("dur", 0.0) <= cur["ts"] + 0.5:
                    if best is None or s["ts"] > best["ts"]:
                        best = s
            nxt = best
        if (nxt is None or id(nxt) in seen) \
                and args.get("parent_id") is not None:
            nxt = idx.get((cur.get("pid", 0), args["parent_id"]))
        if nxt is None or id(nxt) in seen or nxt["ts"] <= t_floor:
            break
        chain.append(nxt)
        seen.add(id(nxt))
        cur = nxt
    return chain


def critical_paths(merged: dict,
                   terminals: tuple[str, ...] = TERMINAL_SPANS) -> list[dict]:
    """Per-round gating attribution over a merged multi-rank trace (the
    dict tools/trace_merge.py ``merge``/``merge_dir`` returns, or a loaded
    ``trace.merged.json`` payload).

    For each terminal span (one ``round/close`` per (lane, round) — the
    benign double-close guard span is deduped by keeping the longest; one
    ``async/emit`` per (lane, version)) the causal chain is walked back
    (:func:`_walk_chain`) and each chain node is charged the interval from
    its start to its successor's start — the stretch of the round it was
    the frontier of. The node with the largest charge is the GATING leg:
    its lane names the straggler (a client lane for a slow train, a sender
    lane's ``comm/send`` for a slow/delayed wire leg, a ``comm/retry`` for
    a retry sequence). Rounds a timer closed (``timed_out=1``) whose chain
    never crossed lanes are attributed ``timeout`` — nothing arrived to
    gate on."""
    # the gating node's rank attr is the wire sender field the comm spans
    # recorded — read it by its wire-key constant
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from fedml_tpu.comm.message import Message

    lane_by_pid = _lanes_by_pid(merged)
    pid_by_lane = {lane: pid for pid, lane in lane_by_pid.items()}
    spans, _ = _with_open_spans(merged.get("traceEvents", []))
    idx: dict[tuple, dict] = {}
    siblings: dict[tuple, list[dict]] = {}
    for s in spans:
        args = s.get("args", {})
        sid = args.get("span_id")
        if sid is not None:
            idx[(s.get("pid", 0), sid)] = s
        siblings.setdefault(
            (s.get("pid", 0), s.get("tid", 0), args.get("parent_id")),
            []).append(s)
    for group in siblings.values():
        group.sort(key=lambda s: s["ts"])

    closes: dict[tuple, dict] = {}
    for s in spans:
        if s["name"] not in terminals:
            continue
        args = s.get("args", {})
        key = (s.get("pid", 0), s["name"],
               args.get("round", args.get("version")))
        if key not in closes or s.get("dur", 0.0) > closes[key].get("dur", 0.0):
            closes[key] = s

    # causal floor per terminal: the previous terminal of the same kind on
    # the same lane — round N's window opens where round N-1 closed
    prior: dict[tuple, float] = {}
    floors: dict[int, float] = {}
    for s in sorted(closes.values(), key=lambda s: s["ts"]):
        key = (s.get("pid", 0), s["name"])
        floors[id(s)] = prior.get(key, float("-inf"))
        prior[key] = s["ts"]

    rows = []
    for s in sorted(closes.values(), key=lambda s: s["ts"]):
        args = s.get("args", {})
        chain = _walk_chain(s, idx, siblings, pid_by_lane,
                            t_floor=floors[id(s)])
        contrib = [s.get("dur", 0.0)]
        for i in range(1, len(chain)):
            contrib.append(max(chain[i - 1]["ts"] - chain[i]["ts"], 0.0))
        g = max(range(len(chain)), key=lambda i: contrib[i])
        gate = chain[g]
        g_args = gate.get("args", {})
        crossed = len({n.get("pid", 0) for n in chain}) > 1
        timed_out = bool(args.get("timed_out"))
        rows.append({
            "name": s["name"],
            "round": args.get("round", args.get("version")),
            "lane": lane_by_pid.get(s.get("pid", 0)),
            "close_ms": round(s.get("dur", 0.0) / 1e3, 3),
            "timed_out": timed_out,
            "gating_span": ("timeout" if timed_out and not crossed
                            else gate["name"]),
            "gating_lane": lane_by_pid.get(gate.get("pid", 0)),
            "gating_rank": g_args.get(
                "rank", g_args.get(Message.MSG_ARG_KEY_SENDER)),
            "gating_ms": round(contrib[g] / 1e3, 3),
            "crossed_lanes": crossed,
            "chain": [
                {"lane": lane_by_pid.get(n.get("pid", 0)), "name": n["name"],
                 "ts_ms": round(n["ts"] / 1e3, 3),
                 "contrib_ms": round(c / 1e3, 3),
                 "open": bool(n.get("args", {}).get("open"))}
                for n, c in zip(chain, contrib)
            ],
        })
    return rows


def format_critical_text(rows: list[dict]) -> str:
    lines = [
        f"{'terminal':<12} {'round':>5} {'lane':<8} {'close ms':>9} "
        f"{'gating lane':<12} {'gating span':<16} {'gating ms':>10} {'chain'}",
    ]
    for r in rows:
        chain = " <- ".join(f"{n['lane']}:{n['name']}" for n in r["chain"][:6])
        if len(r["chain"]) > 6:
            chain += " <- ..."
        lines.append(
            f"{r['name']:<12} {str(r['round']):>5} {str(r['lane']):<8} "
            f"{r['close_ms']:>9.2f} {str(r['gating_lane']):<12} "
            f"{r['gating_span']:<16} {r['gating_ms']:>10.2f} {chain}"
        )
    return "\n".join(lines)


def format_text(report: dict, top: int) -> str:
    lines = [
        f"wall {report['wall_ms']:.1f} ms, {report['events']} events, "
        f"stall fraction {report['stall_fraction']}"
        + (f", lane occupancy {report['lane_occupancy_mean']}"
           if report["lane_occupancy_mean"] is not None else ""),
        "",
        f"{'span':<34} {'count':>6} {'total ms':>10} {'self ms':>10} {'max ms':>9}",
    ]
    for r in report["spans"][:top]:
        lines.append(
            f"{r['name']:<34} {r['count']:>6} {r['total_ms']:>10.2f} "
            f"{r['self_ms']:>10.2f} {r['max_ms']:>9.2f}"
        )
    if report["counters"]:
        lines += ["", f"{'counter':<34} {'samples':>7} {'min':>10} "
                      f"{'max':>10} {'mean':>10} {'last':>10}"]
        for name in sorted(report["counters"]):
            c = report["counters"][name]
            lines.append(
                f"{name:<34} {c['count']:>7} {c.get('min'):>10} "
                f"{c.get('max'):>10} {c['mean']:>10} {c['last']:>10}"
            )
    if report["instants"]:
        lines += ["", "markers: " + ", ".join(report["instants"])]
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("fedml_tpu trace summarizer")
    p.add_argument("trace", help="trace.jsonl / trace.chrome.json "
                                 "(obs/trace.py exports), a merged "
                                 "trace.merged.json, or a DIRECTORY of "
                                 "per-lane trace_<lane>.jsonl files")
    p.add_argument("--format", choices=["text", "json"], default="text")
    p.add_argument("--top", type=int, default=20,
                   help="span rows to print (text format)")
    args = p.parse_args(argv)
    merged = None
    if Path(args.trace).is_dir():
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import trace_merge

        merged = trace_merge.merge_dir(args.trace)
        events = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    else:
        events = load_events(args.trace)
        # a written trace.merged.json still walks: recover lanes from its
        # metadata records
        raw = None
        if str(args.trace).endswith(".json"):
            try:
                raw = json.loads(Path(args.trace).read_text())
            except json.JSONDecodeError:
                raw = None
        if isinstance(raw, dict) and any(
                e.get("ph") == "M" and e.get("name") == "process_name"
                for e in raw.get("traceEvents", [])):
            merged = raw
    report = summarize(events)
    rows = critical_paths(merged) if merged is not None else None
    if args.format == "json":
        if rows is not None:
            report["critical_path"] = rows
        print(json.dumps(report))
    else:
        print(format_text(report, args.top))
        if rows:
            print("\nround critical path (gating leg per close):\n")
            print(format_critical_text(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
