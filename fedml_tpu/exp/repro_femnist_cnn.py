"""BASELINE.md reproduction: FederatedEMNIST + CNN, shallow-NN table row.

Reference config (benchmark/README.md:51-58): FEMNIST, 3400 writer-clients,
CNN_DropOut (2 conv + 2 FC), 10 clients/round, B=20, SGD lr=0.1 — test
accuracy 84.9 beyond ~1500 rounds.

Runs on the real fed_emnist h5 archives when ``--data_dir`` has them;
otherwise generates the offline TFF-format fixture
(data/tff_fixture.py — real sklearn handwriting, per-writer styles; 10 digit
classes, NOT the 62-class EMNIST, and REPRO.md says so). Writes
repro_femnist_metrics.jsonl + a REPRO.md section.

Usage: python -m fedml_tpu.exp.repro_femnist_cnn [--comm_round 1500]
"""

from __future__ import annotations

import argparse
import json
import logging
import time
from pathlib import Path


def run(args) -> dict:
    from fedml_tpu.obs.trace import run_traced

    return run_traced(_run, args)


def _run(args) -> dict:
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data import load_partition_data
    from fedml_tpu.data.fixture_util import is_fixture
    from fedml_tpu.data.tff_fixture import write_femnist_h5_fixture
    from fedml_tpu.models.cnn import CNNDropOut
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.engine import FedSim, SimConfig
    from fedml_tpu.algorithms.robust import sim_config_fields as robust_fields
    from fedml_tpu.population import sim_config_fields as population_fields

    logging_config(0)
    data_dir = Path(args.data_dir)
    real = (
        (data_dir / "fed_emnist_train.h5").exists()
        and not is_fixture(data_dir, "femnist")
    )
    if not real:
        # idempotent: regenerates only when absent or when the marker records
        # a different (n_clients, seed) than this run requests
        logging.info("no real fed_emnist h5 at %s — using offline fixture", data_dir)
        write_femnist_h5_fixture(data_dir, n_clients=args.client_num_in_total,
                                 seed=args.seed)
    ds = load_partition_data("femnist", str(data_dir),
                             client_num_in_total=args.client_num_in_total)

    trainer = ClientTrainer(
        # exact reference model shape: 62-way head even on the 10-class
        # fixture (labels are a subset; the architecture is the row's)
        module=CNNDropOut(num_classes=ds.class_num),
        optimizer=optax.sgd(args.lr),
        epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=ds.train.num_clients,
        client_num_per_round=args.client_num_per_round,
        batch_size=args.batch_size,
        comm_round=args.comm_round,
        epochs=1,
        frequency_of_the_test=args.frequency_of_the_test,
        seed=args.seed,
        pack_lanes=args.pack_lanes,
        pack_capacity_factor=args.pack_capacity_factor,
        **robust_fields(args),
        **population_fields(args),
    )
    sim = FedSim(trainer, ds.train, ds.test_arrays, cfg)

    metrics_path = Path(args.metrics_out)
    records = []
    t0 = time.time()
    with open(metrics_path, "w") as f:
        def cb(rec):
            records.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()

        sim.run(callback=cb)
    wall = time.time() - t0

    evals = [r for r in records if "Test/Acc" in r]
    if not evals:
        raise ValueError(
            f"no eval rounds ran (comm_round={cfg.comm_round} < "
            f"frequency_of_the_test={cfg.frequency_of_the_test}?)"
        )
    best = max(e["Test/Acc"] for e in evals)
    first_over = next(
        (e["round"] for e in evals if e["Test/Acc"] > 0.849), None
    )
    result = {
        "dataset": "FederatedEMNIST h5" if real else "TFF-format offline fixture (10-class)",
        "clients": ds.train.num_clients,
        "samples": ds.train.num_samples,
        "rounds": cfg.comm_round,
        "best_test_acc": round(best, 4),
        "first_round_over_84.9": first_over,
        "rounds_per_sec": round(cfg.comm_round / wall, 2),
        "final": {k: round(v, 4) for k, v in evals[-1].items() if k != "round"},
    }
    if args.out:
        _write_report(Path(args.out), args, result, evals)
    logging.info("repro result: %s", result)
    return result


def _write_report(path: Path, args, result: dict, evals: list) -> None:
    from fedml_tpu.exp._report import acc_curve, ceiling_lookup, update_section

    ceil = ceiling_lookup("femnist_cnn", report_path=path)
    ceiling_line = (
        f"\n- fixture centralized ceiling {ceil['ceiling_acc'] * 100:.2f} "
        "(Fixture ceilings section): the row saturates its 10-class "
        "fixture — evidence of pipeline + recipe execution at 3400-client "
        "scale, not of a hard convergence margin"
        if ceil else ""
    )

    curve = acc_curve(evals, points=12)
    fixture_note = (
        "Real FederatedEMNIST h5 archives were used."
        if result["dataset"] == "FederatedEMNIST h5"
        else (
            "**Data note:** this environment has no network egress, so the real "
            "fed_emnist h5 archives are unavailable. The run uses the TFF-format "
            "offline fixture (`fedml_tpu/data/tff_fixture.py`): real sklearn "
            "handwritten digits with persistent per-writer styles, written in "
            "the exact `examples/<client>/pixels|label` h5 schema and ingested "
            "through the real `tff_h5.load_federated_emnist` path. It has 10 "
            "digit classes, NOT the 62-class EMNIST, so the absolute accuracy "
            "is an easier target than the reference's 84.9; treat the result "
            "as evidence the 3400-client cross-device pipeline converges with "
            "the row's exact model/optimizer/cohort recipe, not as a literal "
            "FEMNIST score."
        )
    )
    update_section(path, "femnist_cnn", f"""# BASELINE reproduction — FederatedEMNIST + CNN (shallow-NN table row)

Reference target (BASELINE.md / benchmark/README.md:51-58): test acc **84.9**
beyond **~1500 rounds** — 3400 clients, 10/round, B=20, SGD lr=0.1, E=1,
CNN_DropOut (2 conv + 2 FC).

{fixture_note}

## Config

| clients | per round | batch | lr | local epochs | rounds |
|---|---|---|---|---|---|
| {result['clients']} | {args.client_num_per_round} | {args.batch_size} | {args.lr} | 1 | {result['rounds']} |

## Result

- best test accuracy: **{result['best_test_acc'] * 100:.2f}**{ceiling_line}
- first round with test acc > 84.9: **{result['first_round_over_84.9']}**
- wall-clock: {result['rounds_per_sec']} rounds/sec on this chip
- raw per-round metrics: `repro_femnist_metrics.jsonl`

Accuracy curve (round:acc): {curve}

Reproduce with: `python -m fedml_tpu.exp.repro_femnist_cnn --out REPRO.md`
""")


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    from fedml_tpu.algorithms.robust import add_cli_flags as add_robust_cli_flags
    from fedml_tpu.obs.trace import add_cli_flag as add_trace_cli_flag

    parser.add_argument("--data_dir", type=str, default="./data/femnist")
    parser.add_argument("--client_num_in_total", type=int, default=3400)
    parser.add_argument("--client_num_per_round", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=20)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--comm_round", type=int, default=1500)
    parser.add_argument("--frequency_of_the_test", type=int, default=25)
    parser.add_argument("--pack_lanes", type=int, default=0,
                        help="packed-lane cohort execution (docs/"
                             "PERFORMANCE.md): N lanes per mesh shard "
                             "bin-packed from the cohort's step streams "
                             "instead of padding to the straggler max; "
                             "0 = padded path (bit-identical either way)")
    parser.add_argument("--pack_capacity_factor", type=float, default=1.25,
                        help="lane-length head room over the expected "
                             "per-shard cohort load (overflow spills to an "
                             "extra sequential pass)")
    add_trace_cli_flag(parser)
    from fedml_tpu.population import add_cli_flags as add_population_cli_flags

    add_robust_cli_flags(parser)
    add_population_cli_flags(parser)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--metrics_out", type=str, default="repro_femnist_metrics.jsonl")
    parser.add_argument("--out", type=str, default="REPRO.md")
    return parser


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("femnist+cnn baseline repro")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
