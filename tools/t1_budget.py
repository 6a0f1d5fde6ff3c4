"""Tier-1 wall-time budget report: where the suite's 1,470s timeout margin
is going, test by test.

Tier-1 (``pytest -m 'not slow'``) runs as the driver runs it (``commands`` in
``/root/TESTS_LAST_RUN.json``): six xdist workers, a file to a worker
(``--dist loadfile``), under a 1,470s kill; a run cut there counts only as
far as it got. The working budget is 1,100s so a slow machine or a new suite
never lands within kill distance. Under ``loadfile`` the wall time is the most
loaded worker's, so the slowest FILES are what to split or trim. This tool
parses a pytest run's output — run tier-1 with ``--durations=0 -vv`` (or any
``--durations=N`` large enough) and point the tool at the captured log — and
reports:

- the 15 slowest tests (call + setup + teardown summed per test id),
- the slowest test FILES (where a whole suite, not one test, is the cost),
- total wall time vs the 1,100s budget and the 1,470s timeout.

    timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \\
        -m 'not slow' -p xdist -n 6 --dist loadfile --durations=0 -vv \\
        > /tmp/t1.log; \\
    python tools/t1_budget.py /tmp/t1.log
    python tools/t1_budget.py /tmp/t1.log --format json
    python tools/t1_budget.py /tmp/t1.log --strict   # exit 1 over budget

``--strict`` makes an over-budget run a hard failure for CI wiring; the
default is report-only so a developer can eyeball headroom after any run.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

BUDGET_S = 1100.0   # working budget: tier-1 should finish under this
TIMEOUT_S = 1470.0  # the driver's hard kill (timeout -k 10 1470 ...)
TOP_N = 15

# pytest --durations lines: "  12.34s call     tests/test_x.py::test_y[p]"
_DURATION = re.compile(
    r"^\s*(\d+(?:\.\d+)?)s\s+(call|setup|teardown)\s+(\S+)\s*$"
)
# the summary tail: "= 639 passed, 4 skipped, 37 deselected in 796.39s ="
_TOTAL = re.compile(r"\bin (\d+(?:\.\d+)?)s(?:\s|=|$)")
_OUTCOMES = re.compile(
    r"\b(\d+) (passed|failed|error|errors|skipped|deselected|xfailed|xpassed)\b"
)


def parse_log(text: str) -> dict:
    """Aggregate a pytest log into {tests, files, total_s, outcomes}."""
    per_test: dict[str, float] = defaultdict(float)
    for line in text.splitlines():
        m = _DURATION.match(line)
        if m:
            per_test[m.group(3)] += float(m.group(1))
    per_file: dict[str, float] = defaultdict(float)
    for test_id, secs in per_test.items():
        per_file[test_id.split("::", 1)[0]] += secs
    total = None
    outcomes: dict[str, int] = {}
    for m in _TOTAL.finditer(text):
        total = float(m.group(1))  # last match wins: the final summary line
    for m in _OUTCOMES.finditer(text):
        outcomes[m.group(2)] = int(m.group(1))
    return {
        "tests": sorted(per_test.items(), key=lambda kv: -kv[1]),
        "files": sorted(per_file.items(), key=lambda kv: -kv[1]),
        "total_s": total,
        "outcomes": outcomes,
    }


def build_report(parsed: dict, top: int = TOP_N) -> dict:
    total = parsed["total_s"]
    measured = sum(s for _, s in parsed["tests"])
    report = {
        "budget_s": BUDGET_S,
        "timeout_s": TIMEOUT_S,
        "total_s": total,
        "measured_s": round(measured, 2),
        "outcomes": parsed["outcomes"],
        "slowest_tests": [
            {"test": t, "seconds": round(s, 2)}
            for t, s in parsed["tests"][:top]
        ],
        "slowest_files": [
            {"file": f, "seconds": round(s, 2)}
            for f, s in parsed["files"][:top]
        ],
    }
    if total is not None:
        report["budget_headroom_s"] = round(BUDGET_S - total, 2)
        report["timeout_headroom_s"] = round(TIMEOUT_S - total, 2)
        report["over_budget"] = total > BUDGET_S
    return report


def format_text(report: dict) -> str:
    lines = ["tier-1 wall-time budget", "=" * 23, ""]
    total = report["total_s"]
    if total is None:
        lines.append(
            "total: (no pytest summary line found — durations only)"
        )
    else:
        verdict = "OVER BUDGET" if report["over_budget"] else "ok"
        lines.append(
            f"total: {total:.1f}s  budget: {report['budget_s']:.0f}s "
            f"(headroom {report['budget_headroom_s']:+.1f}s)  "
            f"timeout: {report['timeout_s']:.0f}s "
            f"(headroom {report['timeout_headroom_s']:+.1f}s)  [{verdict}]"
        )
    if report["outcomes"]:
        lines.append("outcomes: " + ", ".join(
            f"{n} {k}" for k, n in sorted(report["outcomes"].items())
        ))
    if report["total_s"] is not None and report["measured_s"]:
        # durations measure call/setup/teardown; the gap is collection +
        # interpreter + import time, which no single test owns
        overhead = report["total_s"] - report["measured_s"]
        lines.append(
            f"measured in tests: {report['measured_s']:.1f}s "
            f"(collection/import overhead {overhead:.1f}s)"
        )
    lines.append("")
    lines.append(f"slowest {len(report['slowest_tests'])} tests")
    lines.append("-" * 20)
    for row in report["slowest_tests"]:
        lines.append(f"  {row['seconds']:8.2f}s  {row['test']}")
    if not report["slowest_tests"]:
        lines.append("  (no --durations lines in the log; rerun tier-1 "
                     "with --durations=0 -vv)")
    lines.append("")
    lines.append(f"slowest {len(report['slowest_files'])} files")
    lines.append("-" * 20)
    for row in report["slowest_files"]:
        lines.append(f"  {row['seconds']:8.2f}s  {row['file']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Report tier-1 wall-time budget from a pytest log "
                    "captured with --durations=0 -vv"
    )
    ap.add_argument("log", help="pytest output file ('-' for stdin)")
    ap.add_argument("--top", type=int, default=TOP_N,
                    help=f"rows per table (default {TOP_N})")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when the run exceeds the 1,100s budget")
    args = ap.parse_args(argv)

    text = (sys.stdin.read() if args.log == "-"
            else Path(args.log).read_text())
    report = build_report(parse_log(text), top=args.top)
    if args.format == "json":
        print(json.dumps(report, indent=2))
    else:
        print(format_text(report))
    if args.strict and report.get("over_budget"):
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
