"""Read the two numbers a limit is set from (steps 3 to 5 of "How correct is
decided"): over some seeds, what sound runs of the program give against the
plain reference, and what the control gives in the program's place.

    python benchmark/check_limits.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3] [--control bf16]

One process, on the chip, at the cell's own size; no timed window. For each
seed the cell's FedSim runs its check rounds as ``benchmark/run.py`` drives
them, is freed, and the reference follows them in float32; for each control
seed the reference also follows them at the configuration's
``control_precision`` and those results are compared as if they were the
program's. Prints every number per seed, then per number the sound runs'
largest and the control's smallest and their ratio. The benchmark's own runs
never call this; ``tests/benchmark_tests/test_benchmark_reference.py`` keeps
the same comparison at a size a test can hold.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import jax

    from benchmark import run as benchrun
    from fedml_tpu.core.compile_cache import configure_compile_cache

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--control", default=None,
                        help="precision of the control (default: the configuration's)")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    cell = benchrun.load_cell(args.workload)
    devices = benchrun.require_chips(cell["chips"])
    configure_compile_cache()
    control = args.control or cell["config"]["check"]["control_precision"]
    sound, broken = {}, {}
    for seed in sorted(set(seeds) | set(control_seeds)):
        sim, variables = benchrun.build_sim(cell, seed, devices)
        check, variables = benchrun.program_check(sim, variables, cell)
        del sim, variables
        gc.collect()
        jax.clear_caches()
        ref = benchrun.reference_check(cell, seed, check["rounds"], check["shapes"])
        if seed in seeds:
            numbers = benchrun.compare(check, ref, cell['family'].HEAD)
            print(f"seed {seed} sound   {json.dumps(numbers)}", flush=True)
            for k, v in numbers.items():
                sound.setdefault(k, []).append(v)
        if seed in control_seeds:
            stand_in = benchrun.reference_check(cell, seed, check["rounds"], check["shapes"],
                                                precision=control)
            stand_in["losses"] = [(r, v) for r, v in stand_in["losses"]
                                  if r in dict(check["losses"])]
            numbers = benchrun.compare(stand_in, ref, cell['family'].HEAD)
            print(f"seed {seed} control({control}) {json.dumps(numbers)}", flush=True)
            for k, v in numbers.items():
                broken.setdefault(k, []).append(v)
    for k in sorted(sound):
        hi = max(sound[k])
        line = f"{k}: sound max {hi:.6g} over {len(sound[k])} seeds"
        if k in broken:
            lo = min(broken[k])
            line += f"; control min {lo:.6g} over {len(broken[k])} seeds; ratio {lo / max(hi, 1e-30):.3g}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
