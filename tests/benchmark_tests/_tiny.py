"""Toy-size cells for the benchmark's CPU tests: the real cells' files with
the sizes cut down (fewer blocks, narrow widths, short sequences), so that
one federated round and its plain reference take seconds on the CPU; and one
toy cell of the tests' own, ``toy_bn_silo3.json``, which keeps the ``resnet``
family's BatchNorm arch, unequal shards and weight decay under test now that
no cell of the benchmark uses them (``PERF.md`` section 7). The figures such
a cell produces are never device metrics."""

import copy
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY_BN = "toy_bn_silo3"

TINY = {
    "resnet18gn_dev10": {
        "config": {"model": {"image_hw": 8, "num_classes": 10},
                   "train_images": 64, "test_images": 16},
        "traffic": {"clients_total": 8, "clients_per_round": 2, "batch_size": 4,
                    "samples_per_client": 8, "frequency_of_the_test": 3,
                    "eval_batch_size": 16, "check_rounds": 2, "eval_passes": 2},
    },
    "cgpt13b_silo2": {
        "config": {"n_embd": 32, "n_head": 2, "n_inner": 128, "n_layer": 2, "n_positions": 16,
                   "vocab_size": 67, "compute_dtype": "float32"},
        "traffic": {"local_steps": 2, "batch_size": 2, "seq_len": 16, "ramp_alphabet": 8,
                    "eval_batch_size": 2, "check_rounds": 2},
    },
}


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def tiny_cell(name: str, **config_over) -> dict:
    """The cell ``name`` as ``benchmark.run.load_cell`` gives it, at toy size."""
    from benchmark import run as benchrun

    if name == TOY_BN:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), TOY_BN + ".json")) as f:
            toy = json.load(f)
        cell = benchrun.load_cell(toy["metrics_like"], ROOT)  # the family's metrics
        cell.update(name=name, config=_merge(toy["config"], config_over), traffic=toy["traffic"])
        return cell
    cell = benchrun.load_cell(name, ROOT)
    cell["config"] = _merge(_merge(cell["config"], TINY[name]["config"]), config_over)
    cell["traffic"] = _merge(cell["traffic"], TINY[name]["traffic"])
    return cell
