"""What the phase split leaves unnamed, split by the loop that carries it.

``scope_reduce.classify`` gives an op to the outermost ``fed/*`` scope of its
``op_name``; what has none is ``unattributed``, 5.7-14.7% of every cell's busy
time (PERF.md section 5). The program names the four loops of a round's path
(``fedml_tpu/obs/trace.py`` ``LOOP_SCOPES``, each a ``jax.named_scope`` around
the call that makes the loop and never under ``fed/``), so the copies and
slices that carry a loop's state bear its name and the ops inside keep their
phase. Read here, over ``scope_reduce.scope_rows`` as ``moe_reduce.py`` and
``mla_reduce.py`` are:

- an op counts for a loop when its class is ``unattributed`` and its
  ``op_name`` holds a loop's name; it belongs to the innermost one (the last
  in the ``op_name``); ``loop/epochs`` counts with ``loop/steps``;
- ``unscoped`` is what the loops leave of ``unattributed``: ops with neither a
  phase nor a loop, or with no row;
- the program's ``loop/carry`` notes (one a loop, left where it is made while
  jax traces) say how many bytes a trip carries for one client and how many
  clients a trip holds side by side, so the local-step scan's seconds become
  passes over its carry at the chip's bandwidth.

Every reader gives None where the table has rows and none bears a loop's
name: scopes are not in jax's compile-cache key, so a program served from an
older cache, like the parent of the PR that added this file, has none, and a
missing number is honest where a zero is not. Where there is no table at all
(a CPU run, whose trace has no device plane, or no xprof) the readers say
what ``unattributed_time_pct`` says there, that nothing is attributed: the
loops read 0.0 and ``unscoped`` the whole busy time.

``python benchmark/loop_reduce.py <cell>`` prints, a loop, its ten largest
ops with HLO category and ``op_name``, and the notes the traced run left
beside its trace, for the builder who reads a trace by hand.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # run as a script
    sys.path.insert(0, ROOT)

from benchmark import scope_reduce  # noqa: E402

# the program's own names (fedml_tpu/obs/trace.py LOOP_SCOPES); a test holds
# the two equal
LOOP_SCOPES = ("loop/rounds", "loop/cohort", "loop/epochs", "loop/steps")
CARRY_NOTE = "loop/carry"
LOOP = re.compile(r"(?:^|[/(])loop/(%s)(?=[/)]|$)" % "|".join(
    name.split("/")[1] for name in LOOP_SCOPES))
LOOPS = ("rounds", "cohort", "steps")  # what is reported; epochs count with steps
NOTES_FILE = "loop_notes.json"  # beside the cell's trace, for the command line


def loop_of(op_name: str | None):
    """The loop an op counts for: the innermost loop of its ``op_name`` where
    no phase scope claims it, else None."""
    if scope_reduce.classify(op_name) != "unattributed":
        return None
    found = LOOP.findall(op_name or "")
    if not found:
        return None
    return "steps" if found[-1] == "epochs" else found[-1]


def loop_seconds(rows: dict, busy_s: float):
    """Seconds of the busy time by loop, and ``unscoped``: what the loops
    leave of the unattributed time. None where the table's rows bear no
    loop's name at all (see the module's docstring)."""
    out, named = dict.fromkeys(LOOPS, 0.0), False
    for per_program in rows.values():
        for _, op_name, _, self_us in per_program:
            named = named or bool(LOOP.search(op_name))
            loop = loop_of(op_name)
            if loop:
                out[loop] += self_us / 1e6
    if rows and not named:
        return None
    unattributed = scope_reduce.phase_seconds(rows, busy_s)["unattributed"]
    out["unscoped"] = unattributed - sum(out[k] for k in LOOPS)
    return out


def _seconds(ctx):
    chip = ctx["trace"]["chip0"]
    if not chip["ops"] or not chip["busy_s"]:
        return None
    rows = scope_reduce.scope_rows(scope_reduce.xplane_path(ctx["cell"]["name"]))
    return loop_seconds(rows, chip["busy_s"])


def loop_pct(ctx, key: str):
    """Percent of chip 0's busy time in the unattributed ops of the loop
    ``key`` (``rounds``, ``cohort``, ``steps``) or in ``unscoped``."""
    seconds = _seconds(ctx)
    return None if seconds is None else 100.0 * seconds[key] / ctx["trace"]["chip0"]["busy_s"]


def carry_notes() -> dict:
    """{loop name: its last ``loop/carry`` note} of the program; empty for a
    program that leaves none."""
    try:
        from fedml_tpu.obs import trace
        notes = trace.program_notes(CARRY_NOTE)
    except (ImportError, AttributeError):
        return {}
    return {n["loop"]: n for n in notes}


def client_steps(cell: dict) -> int:
    """Local steps one client takes a round, from the cell's traffic file:
    stated, or its largest client's batches an epoch times the epochs."""
    from benchmark import traffic as trafficlib

    traffic = cell["traffic"]
    if "local_steps" in traffic:
        return traffic["local_steps"]
    batches = int(trafficlib.client_sizes(traffic, 0).max()) // traffic["batch_size"]
    return batches * cell["config"].get("local_epochs", 1)


def carry_passes(ctx):
    """How many times over the local step's carry is read and written at the
    chip's bandwidth: seconds a trip of the step loop in its unattributed ops
    x bytes/s of the chip's memory / (2 x the note's bytes x the clients a
    trip holds side by side). About 1 is one copy a step at bandwidth."""
    notes = carry_notes()
    steps, cohort = notes.get("loop/steps"), notes.get("loop/cohort")
    seconds = _seconds(ctx)
    if seconds is None or not steps or not cohort or not steps["bytes"]:
        return None
    _keep(ctx, notes)
    side_by_side = cohort["side_by_side"]
    trips = (ctx["traced_rounds"] * ctx["cell"]["traffic"]["clients_per_round"]
             * client_steps(ctx["cell"]) / side_by_side)
    moved = 2.0 * steps["bytes"] * side_by_side
    return seconds["steps"] / trips * ctx["peaks"]["hbm_bytes_per_s"] / moved


def _keep(ctx, notes: dict) -> None:
    """The notes beside the cell's trace: they live in the traced run's
    process, and the command line below runs in another."""
    path = scope_reduce.xplane_path(ctx["cell"]["name"])
    if path:
        with open(os.path.join(os.path.dirname(path), NOTES_FILE), "w") as f:
            json.dump(list(notes.values()), f)


# -- for the builder: what the numbers were made from --------------------------


def report(cell_name: str, root: str = ROOT) -> dict:
    """The split of the trace the cell's last traced run left under ``root``,
    each loop's ten largest ops and its time by HLO category, and the run's
    ``loop/carry`` notes."""
    from benchmark import trace_reduce

    path = scope_reduce.xplane_path(cell_name, root)
    if path is None:
        raise SystemExit(f"no trace of {cell_name} under {root}/.bench_out/trace")
    busy = trace_reduce.reduce_xplane(path, 1)["chip0"]["busy_s"]
    rows = scope_reduce.scope_rows(path)
    seconds = loop_seconds(rows, busy)
    ops = {k: [] for k in LOOPS + ("unscoped",)}
    for name, per_program in rows.items():
        for _, op_name, category, self_us in per_program:
            if scope_reduce.classify(op_name) == "unattributed":
                ops[loop_of(op_name) or "unscoped"].append(
                    [self_us / 1e6, name, category, op_name[-150:]])
    notes_path = os.path.join(os.path.dirname(path), NOTES_FILE)
    notes = None
    if os.path.exists(notes_path):
        with open(notes_path) as f:
            notes = json.load(f)
    return {
        "xplane": path, "busy_s": busy,
        "pct_of_busy": seconds and {k: 100.0 * v / busy for k, v in seconds.items()},
        "largest_ops": {k: sorted(v, reverse=True)[:10] for k, v in ops.items()},
        "by_category": {k: _by_category(v, busy) for k, v in ops.items()},
        "notes": notes,
    }


def _by_category(ops: list, busy: float) -> dict:
    out = {}
    for seconds, _, category, _ in ops:
        out[category] = out.get(category, 0.0) + 100.0 * seconds / busy
    return dict(sorted(out.items(), key=lambda kv: -kv[1])[:6])


if __name__ == "__main__":
    print(json.dumps(report(*sys.argv[1:3]), indent=1))
