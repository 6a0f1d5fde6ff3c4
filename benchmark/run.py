"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: set-up (imports, data and weights made from
the seed, the first federated rounds through ``FedSim.run``, which compile or
load every program the window uses and are the rounds ``correct`` is decided
on), the timed window (``FedSim.run`` over whole rounds, from the call to its
return), the eval passes, then the plain reference, after the program's state
is freed. The last line of standard output is the result object; everything
else goes on earlier lines. Without a TPU holding the cell's chips it exits
non-zero, names what it found, and prints no result.

The harness is driven by data. A cell is ``BENCHMARK.json``'s entry plus
``benchmark/workloads/<cell>.json``; its configuration is
``benchmark/configs/<config>.json``, which names its model family
(``benchmark/families/<family>.py``); each per-layer metric is
``benchmark/layer_metrics/<metric>.py`` with one ``read(ctx)``. All are
found by name: a new one is a new file and a manifest entry.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, ".bench_out")  # traces; listed in .gitignore
TRACE_TARGET_S, TRACE_MAX_S, TRACE_MIN_ROUNDS = 2.0, 5.0, 3
NO_PERIODIC_EVAL = 1000  # a test frequency beyond this means "only the run's last eval"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def say(msg: str) -> None:
    print(msg, flush=True)


# -- the manifest and the files it names --------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything data says about one cell: its manifest entry, its workload
    and configuration files, its family module and its metrics."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(entries)})")
    entry = entries[name]
    bench_dir = os.path.join(root, manifest["paths"][0])
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    workload = load_json(os.path.join(bench_dir, "workloads", name + ".json"))

    def in_cell(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name, "config": config, "traffic": workload["traffic"], "chips": entry["chips"],
        "family": importlib.import_module(f"benchmark.families.{config['family']}"),
        "end_to_end": [m for m in manifest["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in manifest["per_layer"] if in_cell(m)],
    }


def layer_reader(metric_name: str):
    return importlib.import_module(f"benchmark.layer_metrics.{metric_name}").read


# -- the device ---------------------------------------------------------------


def require_chips(chips: int) -> list:
    """The cell's devices, or exit non-zero naming what jax found."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU chip(s), but jax.default_backend() "
            f"is {backend!r} with {len(devices)} device(s) "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); no result")
    return devices[:chips]


def peak_bytes(stats: dict) -> int:
    """A chip's peak from jax's ``memory_stats``. On the TPU
    ``peak_bytes_in_use`` counts live arrays only; the scratch XLA reserves
    for the loaded programs' temporaries is ``peak_bytes_reserved``, a region
    apart (the two and ``largest_free_block_bytes`` add up to ``bytes_limit``;
    PERF.md, Findings PR 24)."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def device_stamp(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak_bytes(d.memory_stats() or {}) for d in devices)}


class CompileCounter:
    """Counts programs built while it is armed: backend compiles and loads
    from the persistent cache both mean a program the warm-up missed."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


# -- the system under test ----------------------------------------------------


def build_sim(cell: dict, seed: int, devices):
    """One FedSim for the cell and the seed's initial variables, made by the
    benchmark (``traffic.init_variables``), in the engine's layout."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import traffic as trafficlib
    from fedml_tpu.parallel import mesh as meshlib
    from fedml_tpu.sim.engine import FedSim

    job = cell["family"].build(cell["config"], cell["traffic"], seed)
    sharded = job["sim_config"].shard_rules is not None
    mesh = None if sharded else meshlib.client_mesh(list(devices))
    sim = FedSim(job["trainer"], job["train"], job["test"], job["sim_config"], mesh=mesh)
    batch = job["sim_config"].batch_size
    sample = {k: jax.ShapeDtypeStruct((batch,) + v.shape[1:], v.dtype)
              for k, v in job["train"].arrays.items()}
    shapes = jax.eval_shape(job["trainer"].init, jax.random.key(0), sample)
    shardings = (sim._var_shardings if sharded and sim._spmd
                 else NamedSharding(sim.mesh, P()))
    variables = trafficlib.init_variables(seed, shapes, shardings, cell["config"].get("init"))
    return sim, variables


def run_rounds(sim, variables, start: int, n: int):
    """Rounds [start, start + n) through ``FedSim.run``; returns the final
    variables, the history and the wall seconds from the call to its return
    (which follows the last round's eval and host fetch)."""
    sim.config.comm_round = start + n
    t0 = time.perf_counter()
    variables, history = sim.run(variables=variables, start_round=start)
    return variables, history, time.perf_counter() - t0


def dispatch_unit(cell: dict) -> int:
    """Rounds between evals: the window holds whole multiples, so that every
    block program has the warmed-up length and the eval share is fixed."""
    freq = cell["traffic"]["frequency_of_the_test"]
    return freq if freq <= NO_PERIODIC_EVAL else 1


def window_rounds(seconds: float, unit: int, call_seconds: float, history: list) -> int:
    """Rounds for a window of about ``seconds``, from a warm ``FedSim.run``
    call of one unit that took ``call_seconds``. Where every unit ends in an
    eval, a window of m units takes m such calls' time. Where the only eval
    is the run's last (unit 1), the engine's own ``round_time`` of the call
    is the round and the rest of the call is paid once."""
    if unit == 1 and history[-1].get("round_time"):
        per_round = history[-1]["round_time"]
        once = max(call_seconds - per_round, 0.0)
        return max(1, round((seconds - once) / per_round))
    return max(1, round(seconds / max(call_seconds, 1e-9))) * unit


def local_losses(history: list, traffic: dict) -> list:
    """Train/Loss of the rounds that report the cohort's local training loss
    (an eval round's record carries the pooled-train eval loss instead)."""
    freq = max(traffic["frequency_of_the_test"], 1)
    last = history[-1]["round"]
    return [(r["round"], r["Train/Loss"]) for r in history
            if (r["round"] + 1) % freq != 0 and r["round"] != last]


def time_eval(sim, variables, passes: int) -> float:
    """Seconds a warm ``FedSim.evaluate`` call takes, each ending in the host
    fetch: ``passes`` calls back to back, timed as one span (the host's clock
    is off by half a millisecond, so the span is kept over a quarter second)."""
    t0 = time.perf_counter()
    for _ in range(passes):
        sim.evaluate(variables)
    return (time.perf_counter() - t0) / passes


# -- correctness --------------------------------------------------------------


def reference_check(cell: dict, seed: int, rounds: int, shapes, precision: str = "f32") -> dict:
    """The check rounds by the plain reference, from the seed's own data and
    weights: what the program's check holds (each round's local training
    loss, the variables after the rounds, the test loss on them), plus the
    initial variables. At a precision below "f32" this is the control that
    stands in the program's place."""
    import jax
    import numpy as np

    from benchmark import traffic as trafficlib
    from benchmark.reference import fedavg

    model = importlib.import_module(cell["family"].REFERENCE)
    job = cell["family"].reference_job(cell["config"], cell["traffic"], seed, rounds)
    v0 = jax.tree.map(np.asarray, trafficlib.init_variables(
        seed, shapes, overrides=cell["config"].get("init")))
    out = {"rounds": rounds, "initial": v0}
    variables, losses = fedavg.run_rounds(model, v0, job["rounds"], job["optimizer"], precision)
    out["losses"] = list(enumerate(losses))
    out["eval"] = {}
    if job["test"] is not None:
        out["eval"]["Test/Loss"] = model.eval_loss(variables, *job["test"], precision=precision)
    out["variables"] = variables
    return out


def compare(check: dict, ref: dict, head: str | None = None) -> dict:
    """The numbers ``correct`` rests on: ``check`` (the program's check
    rounds, or the control's) against the plain reference's. ``head`` names
    the output layer, whose update is reported apart: it depends on the
    forward pass and one step back, so it is steady where a deep stack's
    gradients are not."""
    from benchmark.reference import fedavg

    ref_losses = dict(ref["losses"])
    numbers = {f"loss_gap.round{r}": abs(loss - ref_losses[r]) / abs(ref_losses[r])
               for r, loss in check["losses"]}
    numbers.update(fedavg.update_numbers(ref["initial"], check["variables"], ref["variables"]))
    if head:
        sub = lambda v: {"head": v["params"][head]}  # noqa: E731
        numbers["update_rel_l2.head"] = fedavg.update_numbers(
            sub(ref["initial"]), sub(check["variables"]), sub(ref["variables"]))["update_rel_l2.head"]
    if "Test/Loss" in ref["eval"] and "Test/Loss" in check["eval"]:
        numbers["eval_test_loss_gap"] = (
            abs(check["eval"]["Test/Loss"] - ref["eval"]["Test/Loss"]) / abs(ref["eval"]["Test/Loss"]))
    return numbers


def program_check(sim, variables, cell: dict):
    """Drive the one FedSim through its first ``check_rounds`` by the window's
    own call; returns what ``compare`` needs and the variables to go on from."""
    import jax

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), variables)
    k = cell["traffic"]["check_rounds"]
    variables, history, _ = run_rounds(sim, variables, 0, k)
    check = {"rounds": k, "shapes": shapes, "losses": local_losses(history, cell["traffic"]),
             "eval": {key: v for key, v in history[-1].items() if "/" in key},
             "variables": jax.device_get(variables)}
    return check, variables


def judge(numbers: dict, limits: dict) -> bool:
    """Print each number compared beside its limit; every limit must hold."""
    ok = True
    for name, limit in limits.items():
        matched = [k for k in numbers if k == name or k.startswith(name + ".")]
        if not matched:
            say(f"correct: {name}: NOT PRODUCED (limit {limit})")
            ok = False
        for k in matched:
            good = math.isfinite(numbers[k]) and numbers[k] <= limit
            say(f"correct: {k} = {numbers[k]:.6g} (limit {limit}) {'ok' if good else 'FAIL'}")
            ok = ok and good
    held = {k for k in numbers for name in limits if k == name or k.startswith(name + ".")}
    for k in sorted(set(numbers) - held):
        say(f"correct: {k} = {numbers[k]:.6g} (not held to a limit)")
    return ok


# -- the traced run -----------------------------------------------------------


def spans_in(tracer, t_install: float, t0: float, t1: float) -> list:
    """The tracer's complete spans that start inside [t0, t1] (perf_counter
    seconds), as {name, start, dur} in seconds."""
    out = []
    for e in tracer.events():
        if e.get("ph") != "X":
            continue
        start = t_install + e["ts"] / 1e6
        if t0 <= start <= t1:
            out.append({"name": e["name"], "start": start, "dur": e["dur"] / 1e6})
    return out


def trace_rounds(unit: int, unit_seconds: float) -> int:
    """Rounds under the profiler: whole units, at least TRACE_MIN_ROUNDS,
    about TRACE_TARGET_S of them and never planned past TRACE_MAX_S."""
    need = -(-TRACE_MIN_ROUNDS // unit)
    want = int(TRACE_TARGET_S / max(unit_seconds, 1e-9))
    cap = max(int(TRACE_MAX_S / max(unit_seconds, 1e-9)), need)
    return unit * max(need, min(want, cap))


def profile_rounds(sim, variables, start: int, n: int, out_dir: str):
    """Run n rounds under jax's profiler; returns the variables, the path of
    the .xplane.pb and the traced call's perf_counter interval."""
    import glob

    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/traced_window"):
            variables, _, _ = run_rounds(sim, variables, start, n)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {out_dir}")
    return variables, paths[0], (t0, t1)


def layer_metrics(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in cell["per_layer"]:
        value = layer_reader(metric["name"])(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


# -- one run ------------------------------------------------------------------


def run(cell: dict, seed: int, seconds: float, traced: bool) -> dict:
    import jax

    from benchmark import peaks as peakslib
    from benchmark import trace_reduce
    from fedml_tpu.core.compile_cache import configure_compile_cache
    from fedml_tpu.obs import trace as tracelib

    devices = require_chips(cell["chips"])
    say(f"benchmark: cell {cell['name']}, seed {seed}, {seconds} s, trace {int(traced)}, "
        f"compile cache {configure_compile_cache()}")
    peaks = peakslib.peaks_for(devices[0].device_kind)
    compiles = CompileCounter()
    traffic, family = cell["traffic"], cell["family"]
    unit = dispatch_unit(cell)

    # set-up: the job, then its first rounds through the window's own call.
    # The first check["rounds"] are what the reference follows.
    sim, variables = build_sim(cell, seed, devices)
    check, variables = program_check(sim, variables, cell)
    k = check["rounds"]
    say(f"set-up: check rounds {[(r, round(v, 5)) for r, v in check['losses']]}, "
        f"eval {check['eval']}")
    start = -(-k // unit) * unit
    for _ in range(2):  # the window's own programs: first call builds, second times
        variables, history, unit_seconds = run_rounds(sim, variables, start, unit)
        start += unit
    n = window_rounds(seconds, unit, unit_seconds, history)
    say(f"set-up: a unit of {unit} round(s) took {unit_seconds:.4f} s warm; "
        f"the window runs {n} rounds")

    tracer, t_install = None, None
    if traced:
        t_install = time.perf_counter()
        tracer = tracelib.install(tracelib.Tracer())
    compiles.armed = True
    t0 = time.perf_counter()
    setup_s = time.time() - T_PROCESS
    variables, history, window_s = run_rounds(sim, variables, start, n)
    t1 = time.perf_counter()
    compiles.armed = False
    start += n
    rounds_per_s = n / window_s
    failed = sum(1 for r in history if not math.isfinite(r.get("Train/Loss", math.nan)))
    say(f"window: {n} rounds in {window_s:.4f} s, {failed} failed, "
        f"{compiles.count} program(s) built inside it, "
        f"last record {{{', '.join(f'{a}: {b:.5g}' for a, b in history[-1].items() if '/' in a)}}}")

    metrics = {"rounds_per_s": {"value": rounds_per_s, "unit": "rounds/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    n_eval = family.eval_samples(cell["config"], traffic)
    if n_eval:
        eval_s = time_eval(sim, variables, traffic["eval_passes"])
        metrics["eval_samples_per_s"] = {"value": n_eval / eval_s, "unit": "samples/s"}
        say(f"eval: {traffic['eval_passes']} passes back to back, {eval_s:.4f} s each over "
            f"{n_eval} samples")
    metrics = {m["name"]: metrics[m["name"]] for m in cell["end_to_end"]}
    device = device_stamp(devices)
    say(f"device: memory statistics of chip 0 {devices[0].memory_stats()}")

    result = {"correct": False, "attempted": n, "failed": failed, "metrics": metrics,
              "device": device}
    if traced:
        unit_now = window_s / n * unit
        n_tr = trace_rounds(unit, unit_now)
        variables, xplane, (p0, p1) = profile_rounds(
            sim, variables, start, n_tr, os.path.join(OUT_DIR, "trace", cell["name"]))
        tracelib.uninstall()
        reduced = trace_reduce.reduce_xplane(xplane, len(devices))
        ctx = {
            "cell": cell, "peaks": peaks, "chips": len(devices),
            "window": {"rounds": n, "rounds_per_s": rounds_per_s},
            "host_spans": spans_in(tracer, t_install, t0, t1),
            "compiles_in_window": compiles.count,
            "flops_per_round": family.flops_per_round(cell["config"], traffic),
            "memory_peak_bytes": device["memory_peak_bytes"],
            "trace": reduced, "traced_rounds": n_tr,
        }
        result["metrics"] = layer_metrics(cell, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        # the annotation opens as the traced call starts: p0 on the host's clock
        result["breakdown"] = trace_reduce.breakdown(
            reduced, spans_in(tracer, t_install, p0, p1), p0 - reduced["window"][0])
        say(f"trace: {n_tr} rounds, window {reduced['window_s']:.4f} s, busy "
            f"{reduced['busy_s']:.4f} s, {reduced['n_events']} device events")

    # the plain reference, once the program's state is gone
    del sim, variables, history
    gc.collect()
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    numbers = compare(check, reference_check(cell, seed, k, check["shapes"]), family.HEAD)
    say(f"reference: {k} round(s) followed in {time.perf_counter() - t_ref:.1f} s")
    result["correct"] = judge(numbers, cell["config"]["check"]["limits"]) and failed == 0
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = load_cell(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
