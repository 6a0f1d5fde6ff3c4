"""BASELINE reproduction: StackOverflow next-word prediction (shallow-NN row).

Reference config (benchmark/README.md:54-57; BASELINE.md): **342,477
clients** (the full TFF StackOverflow population), 50/round, B=16, SGD
lr=10^-0.5, E=1, RNN_StackOverFlow (1x670 LSTM + 2 FC, 10k vocab + 4
specials; fedml_api/model/nlp/rnn.py:39, data contract
stackoverflow_nwp/data_loader.py:96) — test accuracy 19.5 beyond ~1500
rounds.

This is the one BASELINE row whose point is POPULATION scale: the client
population is far larger than any HBM-resident cohort, so the run keeps the
full dataset host-side (``stage_on_device=False``) and stages only each
round's 50-client cohort onto the chip — the framework's host-population /
device-cohort split exercised at the row's real 342,477-client scale.

Runs on real stackoverflow h5 + vocab when ``--data_dir`` has them;
otherwise the schema-exact offline fixture
(data/tff_fixture.py::write_stackoverflow_nwp_fixture) whose generating
process is a known word-level Markov chain — its analytic Bayes ceiling
(``stackoverflow_bayes_ceiling``) is reported next to the result so the
curve can actually fail.

Usage: python -m fedml_tpu.exp.repro_stackoverflow_nwp [--comm_round 1500]
"""

from __future__ import annotations

import argparse
import logging
import time
from pathlib import Path


def run(args) -> dict:
    from fedml_tpu.obs.trace import run_traced

    return run_traced(_run, args)


def _run(args) -> dict:
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.fixture_util import is_fixture
    from fedml_tpu.data.tff_fixture import (
        stackoverflow_bayes_ceiling,
        write_stackoverflow_nwp_fixture,
    )
    from fedml_tpu.data.tff_h5 import load_stackoverflow_nwp
    from fedml_tpu.exp._loop import run_rounds
    from fedml_tpu.models.rnn import RNNStackOverflow
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.parallel.mesh import parse_mesh_shape
    from fedml_tpu.sim.engine import FedSim, SimConfig
    from fedml_tpu.algorithms.robust import sim_config_fields as robust_fields
    from fedml_tpu.population import sim_config_fields as population_fields

    logging_config(0)
    data_dir = Path(args.data_dir)
    real = (
        (data_dir / "stackoverflow_train.h5").exists()
        and not is_fixture(data_dir, "stackoverflow_nwp")
    )
    # fixture task constants, computed ONCE: the generator, the early-stop
    # target, and the report must all describe the same task. Active words
    # stay within the loader's vocab or they would collapse to OOV and the
    # reported ceiling would describe a task the model never saw.
    active = min(500, args.vocab_size)
    bayes = floor = None
    if not real:
        if args.seq_len <= args.fixture_sentence_len:
            # a shorter window truncates sentences: the per-token ceiling
            # and eos floor below would describe a DIFFERENT task than the
            # one trained (tff_fixture.stackoverflow_bayes_ceiling assumes
            # the full sentence + eos fit in the window)
            raise ValueError(
                f"--seq_len ({args.seq_len}) must exceed "
                f"--fixture_sentence_len ({args.fixture_sentence_len}); the "
                "reported Bayes ceiling / eos floor assume untruncated "
                "fixture sentences"
            )
        bayes = stackoverflow_bayes_ceiling(
            active_words=active, seed=args.seed,
            sentence_len=args.fixture_sentence_len,
        )
        # eos-only floor: the fixture's fixed sentence length makes the
        # final eos deterministic, so a model that learned NOTHING but
        # "predict eos" scores 1/(sentence_len+1)
        floor = 1.0 / (args.fixture_sentence_len + 1)
        logging.info(
            "no real stackoverflow h5 at %s — writing the %d-client "
            "schema-exact fixture (idempotent)", data_dir,
            args.client_num_in_total,
        )
        t0 = time.time()
        write_stackoverflow_nwp_fixture(
            data_dir, n_clients=args.client_num_in_total, seed=args.seed,
            test_clients=args.test_clients, vocab_size=args.vocab_size,
            active_words=active, sentence_len=args.fixture_sentence_len,
            max_sent=args.fixture_max_sent,
        )
        logging.info("fixture ready in %.0fs", time.time() - t0)

    t0 = time.time()
    train, test_arrays, _ = load_stackoverflow_nwp(
        data_dir, vocab_size=args.vocab_size, seq_len=args.seq_len,
        limit_clients=args.limit_clients,
    )
    logging.info(
        "loaded %d clients / %d sequences in %.0fs (host-resident)",
        train.num_clients, train.num_samples, time.time() - t0,
    )

    trainer = ClientTrainer(
        # defaults are the row's exact architecture (1x670 LSTM + 2 FC);
        # the size flags exist so the fast test gate can compile a small one
        module=RNNStackOverflow(vocab_size=args.vocab_size + 4,
                                embedding_dim=args.embedding_dim,
                                hidden_size=args.hidden_size),
        task="nwp",
        optimizer=optax.sgd(args.lr),
        epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=train.num_clients,
        client_num_per_round=args.client_num_per_round,
        batch_size=args.batch_size,
        comm_round=args.comm_round,
        epochs=1,
        frequency_of_the_test=args.frequency_of_the_test,
        seed=args.seed,
        pack_lanes=args.pack_lanes,
        pack_capacity_factor=args.pack_capacity_factor,
        mesh_shape=parse_mesh_shape(args.mesh_shape),
        shard_rules=args.shard_rules or None,
        **robust_fields(args),
        **population_fields(args),
        # THE row's systems point: population >> cohort. Keep the dataset
        # host-side; each round stages only its 50-client cohort.
        stage_on_device=False,
        # pooled-train eval over all 2.4M sequences per test round is the
        # reference's own hidden bottleneck — sample it
        train_eval_samples=args.train_eval_samples or None,
    )
    sim = FedSim(trainer, train, test_arrays, cfg)
    stop_when = None
    if not real and args.stop_at_learnable_frac:
        # saturation-style guard (the cross-silo precedent): once the curve
        # captures this fraction of the fixture's learnable signal
        # (ceiling - floor), further rounds carry wall-clock only
        _target = floor + args.stop_at_learnable_frac * (bayes - floor)

        def stop_when(records):
            accs = [r["Test/Acc"] for r in records if "Test/Acc" in r]
            return bool(accs) and accs[-1] >= _target

    records, wall = run_rounds(sim, cfg, args.metrics_out, stop_when=stop_when)

    evals = [r for r in records if "Test/Acc" in r]
    if not evals:
        raise RuntimeError("no completed eval rounds — nothing to report")
    best = max(e["Test/Acc"] for e in evals)
    first_over = next(
        (e["round"] for e in evals if e["Test/Acc"] > 0.195), None
    )
    result = {
        "dataset": ("stackoverflow h5" if real
                    else "schema-exact Markov-word fixture"),
        "clients": train.num_clients,
        "samples": train.num_samples,
        "rounds": len(records),
        "best_test_acc": round(best, 4),
        "first_round_over_19.5": first_over,
        "rounds_per_sec": round(len(records) / wall, 2),
        "final": {k: round(v, 4) for k, v in evals[-1].items()
                  if k != "round"},
    }
    if not real:
        result["fixture_bayes_ceiling"] = round(bayes, 4)
        result["eos_only_floor"] = round(floor, 4)
        result["pct_of_ceiling"] = round(100 * best / bayes, 1)
        result["pct_of_learnable"] = round(
            100 * max(best - floor, 0.0) / (bayes - floor), 1
        )
    if args.out:
        _write_report(Path(args.out), args, result, evals, real)
    logging.info("stackoverflow_nwp repro result: %s", result)
    return result


def _write_report(path: Path, args, result: dict, evals: list,
                  real: bool) -> None:
    import jax

    from fedml_tpu.exp._report import acc_curve, update_section

    platform = jax.devices()[0].platform  # honest: chip vs XLA:CPU fallback
    curve = acc_curve(evals, points=12)
    if real:
        note = "Real StackOverflow h5 archives were used."
        ceiling_line = ""
    else:
        bayes = result["fixture_bayes_ceiling"]
        note = (
            "**Data note:** this environment has no network egress, so the "
            "real 342k-client StackOverflow archive is unavailable. The run "
            "uses the schema-exact offline fixture "
            "(`data/tff_fixture.py::write_stackoverflow_nwp_fixture`): "
            "string sentences under `examples/<client>/tokens` plus the "
            "`stackoverflow.word_count` vocab file, ingested through the "
            "real `tff_h5.load_stackoverflow_nwp` tokenizer at the full "
            f"{result['clients']:,}-client population. The generating "
            "process is a known word-level Markov chain, so the fixture's "
            f"attainable accuracy is EXACTLY {bayes * 100:.2f}% "
            "(`stackoverflow_bayes_ceiling`); the published 19.5 does not "
            "transfer — read the result against the fixture's own ceiling. "
            "The dataset stays HOST-side (`stage_on_device=False`): each "
            "round stages only its 50-client cohort to the chip, which is "
            "the row's actual systems claim (population >> device memory)."
        )
        ceiling_line = (
            f"- fixture Bayes ceiling: **{bayes * 100:.2f}**, eos-only "
            f"floor: {result['eos_only_floor'] * 100:.2f} -> best federated "
            f"accuracy is **{result['pct_of_ceiling']}% of ceiling**, "
            f"capturing **{result['pct_of_learnable']}% of the learnable "
            "signal** (acc-floor)/(ceiling-floor)\n"
        )
    update_section(path, "stackoverflow_nwp", f"""# BASELINE reproduction — StackOverflow + RNN next-word (shallow-NN table row)

Reference target (BASELINE.md / benchmark/README.md:54-57): test acc
**19.5** beyond **~1500 rounds** — **342,477 clients**, 50/round, B=16,
SGD lr=10^-0.5, E=1, RNN_StackOverFlow (1x670 LSTM + 2 FC).

{note}

## Config

| clients | per round | batch | lr | local epochs | rounds | seq len |
|---|---|---|---|---|---|---|
| {result['clients']:,} | {args.client_num_per_round} | {args.batch_size} | {args.lr:.4f} | 1 | {result['rounds']} | {args.seq_len} |

## Result

- best test accuracy: **{result['best_test_acc'] * 100:.2f}**
{ceiling_line}- first round with test acc > 19.5: **{result['first_round_over_19.5']}**
- wall-clock: {result['rounds_per_sec']} rounds/sec on this host's `{platform}` backend (host-staged cohorts)
- raw per-round metrics: `{args.metrics_out}`

Accuracy curve (round:acc): {curve}

Reproduce with: `python -m fedml_tpu.exp.repro_stackoverflow_nwp --test_clients {args.test_clients} --fixture_max_sent {args.fixture_max_sent} --train_eval_samples {args.train_eval_samples} --frequency_of_the_test {args.frequency_of_the_test} --out REPRO.md`
""")


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    from fedml_tpu.algorithms.robust import add_cli_flags as add_robust_cli_flags
    from fedml_tpu.obs.trace import add_cli_flag as add_trace_cli_flag

    parser.add_argument("--data_dir", type=str,
                        default="./data/stackoverflow_nwp")
    parser.add_argument("--client_num_in_total", type=int, default=342_477)
    parser.add_argument("--client_num_per_round", type=int, default=50)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--lr", type=float, default=10 ** -0.5)
    parser.add_argument("--seq_len", type=int, default=20)
    parser.add_argument("--vocab_size", type=int, default=10_000)
    parser.add_argument("--fixture_sentence_len", type=int, default=10,
                        help="fixed words per fixture sentence (drives both "
                             "the writer and the floor/ceiling math)")
    parser.add_argument("--embedding_dim", type=int, default=96)
    parser.add_argument("--hidden_size", type=int, default=670)
    parser.add_argument("--test_clients", type=int, default=10_000)
    parser.add_argument("--limit_clients", type=int, default=None,
                        help="cap loaded clients (None = full population)")
    parser.add_argument("--comm_round", type=int, default=1500)
    parser.add_argument("--frequency_of_the_test", type=int, default=50)
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
    parser.add_argument("--mesh_shape", type=str, default=None,
                        help="2-D 'CLIENTSxMODEL' device mesh for sharded "
                             "client models (docs/PERFORMANCE.md 'Sharded "
                             "client models'); unset = 1-D client mesh")
    parser.add_argument("--shard_rules", type=str, default=None,
                        help="partition-rule set sharding the client model "
                             "over the mesh's model axis (e.g. "
                             "transformer_fsdp); unset = unsharded")
    add_trace_cli_flag(parser)
    from fedml_tpu.population import add_cli_flags as add_population_cli_flags

    add_robust_cli_flags(parser)
    add_population_cli_flags(parser)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--train_eval_samples", type=int, default=50_000,
                        help="cap the pooled-train eval subset (None/0 = "
                             "all 2.4M sequences)")
    parser.add_argument("--fixture_max_sent", type=int, default=64,
                        help="fixture: max sentences per client (the engine "
                             "pads every cohort slot to the population max, "
                             "so this bounds the padded-compute waste; 16 "
                             "keeps ~89%% of the lognormal population "
                             "unclipped at 4x less padding than 64)")
    parser.add_argument("--stop_at_learnable_frac", type=float, default=0.8,
                        help="fixture runs: stop once Test/Acc captures this "
                             "fraction of (bayes ceiling - eos floor); 0 "
                             "disables")
    parser.add_argument("--metrics_out", type=str,
                        default="repro_stackoverflow_nwp_metrics.jsonl")
    parser.add_argument("--out", type=str, default="REPRO.md")
    return parser


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(
        argparse.ArgumentParser("stackoverflow+rnn baseline repro")
    ).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
