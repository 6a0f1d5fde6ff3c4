"""BASELINE reproduction: Shakespeare + RNN (2 LSTM + 1 FC), shallow-NN row.

Reference config (benchmark/README.md:54-57; BASELINE.md): LEAF Shakespeare
next-char prediction — 715 speaking-role clients, RNN_OriginalFedAvg
(8-dim embed, 2x256 LSTM, dense head; fedml_api/model/nlp/rnn.py:4),
10 clients/round, B=4, SGD lr=1.0 — test accuracy 56.9 beyond ~1200 rounds.

Runs on real LEAF Shakespeare JSON when ``--data_dir`` has it; otherwise a
Markov-chain char-LM fixture with 715 clients (90-token vocab, 80-char
windows — the reference's exact sequence shape) through the same ingestion.
A 2-layer LSTM recovers a first-order Markov source's transition structure,
so the fixture row validates recipe mechanics and next-char convergence, not
the literal 56.9 (REPRO.md says so).

Usage: python -m fedml_tpu.exp.repro_shakespeare [--comm_round 1200]
"""

from __future__ import annotations

import argparse
import logging
from pathlib import Path


def run(args) -> dict:
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.registry import synthetic_char_lm
    from fedml_tpu.exp._loop import run_rounds
    from fedml_tpu.models.rnn import RNNOriginalFedAvg
    from fedml_tpu.obs.metrics import logging_config
    from fedml_tpu.sim.engine import FedSim, SimConfig

    logging_config(0)
    data_dir = Path(args.data_dir)
    real = (data_dir / "train").is_dir() and any((data_dir / "train").glob("*.json"))
    if real:
        # direct loader call (not the registry) so --seq_len actually shapes
        # the real-data windows too
        from fedml_tpu.data.leaf import load_leaf_shakespeare

        train, test_arrays, _ = load_leaf_shakespeare(
            data_dir / "train", data_dir / "test", seq_len=args.seq_len
        )
        vocab = 90
    else:
        logging.info("no LEAF shakespeare json at %s — Markov char fixture", data_dir)
        vocab = 90
        train, test_arrays, _ = synthetic_char_lm(
            n_clients=args.client_num_in_total, vocab=vocab,
            seq_len=args.seq_len, samples=args.samples_per_client,
            seed=args.seed,
        )

    trainer = ClientTrainer(
        module=RNNOriginalFedAvg(vocab_size=vocab),
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
    )
    sim = FedSim(trainer, train, test_arrays, cfg)
    records, wall = run_rounds(sim, cfg, args.metrics_out)

    evals = [r for r in records if "Test/Acc" in r]
    if not evals:
        raise RuntimeError("no completed eval rounds — nothing to report")
    best = max(e["Test/Acc"] for e in evals)
    first_over = next((e["round"] for e in evals if e["Test/Acc"] > 0.569), None)
    result = {
        "dataset": "LEAF shakespeare json" if real else "Markov char-LM fixture",
        "clients": train.num_clients,
        "samples": train.num_samples,
        "rounds": len(records),
        "best_test_acc": round(best, 4),
        "first_round_over_56.9": first_over,
        "rounds_per_sec": round(len(records) / wall, 2),
        "final": {k: round(v, 4) for k, v in evals[-1].items() if k != "round"},
    }
    if not real:
        # the fixture's exact attainable ceiling: Bayes-optimal next-char
        # accuracy of the generating Markov chain (repro_ceilings)
        from fedml_tpu.exp.repro_ceilings import markov_bayes_ceiling

        bayes = markov_bayes_ceiling(vocab=vocab, seed=args.seed)
        result["fixture_bayes_ceiling"] = round(bayes, 4)
        result["pct_of_ceiling"] = round(100 * best / bayes, 1)
    if args.out:
        _write_report(Path(args.out), args, result, evals, real)
    logging.info("shakespeare repro result: %s", result)
    return result


def _write_report(path: Path, args, result: dict, evals: list, real: bool) -> None:
    from fedml_tpu.exp._report import acc_curve, update_section

    curve = acc_curve(evals, points=12)
    if real:
        note = "Real LEAF Shakespeare JSON was used."
        ceiling_line = ""
    else:
        bayes = result["fixture_bayes_ceiling"]
        note = (
            "**Data note:** this environment has no network egress, so the "
            "real LEAF Shakespeare JSON is unavailable. The run uses a "
            "Markov-chain char-LM fixture at the row's exact scale and "
            "shapes (715 clients, 90-token vocab, 80-char windows) through "
            "the same FederatedArrays path. The fixture's attainable "
            f"accuracy is EXACTLY {bayes * 100:.2f}% — the Bayes optimum "
            "of a known first-order Markov source "
            "(`repro_ceilings.markov_bayes_ceiling`: sum_i pi_i max_j "
            "T[i,j]) — so the absolute number is not comparable to the "
            "published 56.9; read the result as a fraction of the "
            "fixture's own ceiling."
        )
        ceiling_line = (
            f"- fixture Bayes ceiling: **{bayes * 100:.2f}** -> the best "
            f"federated accuracy is **{result['pct_of_ceiling']}% of the "
            "attainable ceiling**\n"
        )
    update_section(path, "shakespeare_rnn", f"""# BASELINE reproduction — Shakespeare + RNN (shallow-NN table row)

Reference target (BASELINE.md / benchmark/README.md:54-57): test acc
**56.9** beyond **~1200 rounds** — 715 clients, 10/round, B=4, SGD lr=1.0,
E=1, RNN_OriginalFedAvg (2x256 LSTM + FC next-char).

{note}

## Config

| clients | per round | batch | lr | local epochs | rounds | seq len |
|---|---|---|---|---|---|---|
| {result['clients']} | {args.client_num_per_round} | {args.batch_size} | {args.lr} | 1 | {result['rounds']} | {args.seq_len} |

## Result

- best test accuracy: **{result['best_test_acc'] * 100:.2f}**
{ceiling_line}- first round with test acc > 56.9: **{result['first_round_over_56.9']}**
- wall-clock: {result['rounds_per_sec']} rounds/sec on this chip
- raw per-round metrics: `{args.metrics_out}`

Accuracy curve (round:acc): {curve}

Reproduce with: `python -m fedml_tpu.exp.repro_shakespeare --out REPRO.md`
""")


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    parser.add_argument("--data_dir", type=str, default="./data/shakespeare")
    parser.add_argument("--client_num_in_total", type=int, default=715)
    parser.add_argument("--client_num_per_round", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=4)
    parser.add_argument("--lr", type=float, default=1.0)
    parser.add_argument("--seq_len", type=int, default=80)
    parser.add_argument("--samples_per_client", type=int, default=16)
    parser.add_argument("--comm_round", type=int, default=1200)
    parser.add_argument("--frequency_of_the_test", type=int, default=25)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--metrics_out", type=str, default="repro_shakespeare_metrics.jsonl")
    parser.add_argument("--out", type=str, default="REPRO.md")
    return parser


def main(argv=None):
    from fedml_tpu.core.compile_cache import configure_compile_cache

    configure_compile_cache()
    args = add_args(argparse.ArgumentParser("shakespeare+rnn baseline repro")).parse_args(argv)
    return run(args)


if __name__ == "__main__":
    main()
