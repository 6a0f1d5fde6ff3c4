"""Family ``eva_lm``: a dense byte-level decoder whose every layer mixes by
EVA attention (exact softmax inside a window, one softmax shared with chunk
summaries of the windows before it) and predicts the next ``num_pred_heads``
bytes from one stream (the EvaByte configuration), through
``fedml_tpu/models/mla_moe_transformer.py`` (``MLAMoETransformerLM`` with its
mixer "eva", ``ops/eva.py`` and the flash kernels), the ``nwp``
``ClientTrainer`` and ``FedSim``. Nothing of a layer is shared out: a chip
holds whole layers, the first of a pipeline's stages.

Builds the federated job, gives its FLOPs a round (``benchmark/eva_costs.py``)
and builds the same job for the plain reference
(``benchmark/reference/eva_lm.py``). The configuration file keeps the
published ``config.json`` keys at its top level.

The rows are bytes: ``seq_len + num_pred_heads`` of them a row over the whole
alphabet of ``vocab_size`` ids, so a row is far longer than its alphabet and
``moe_lm.ramp_rows``, which refuses that, is not used. A row is a ramp
``(start + stride * pos) % alphabet`` with its own start and a stride that
shares no factor with the alphabet: every row differs, every id occurs, a row
repeats itself every ``alphabet`` positions (learnable: the loss must fall),
and every seed gives the same work. Position ``t``'s targets are the bytes
``t + 1 ... t + num_pred_heads``: ``y`` and ``mask`` are ``[n, T, P]``.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import eva_costs
from benchmark import traffic as trafficlib
from benchmark.families.moe_lm import eval_samples, samples_per_round  # noqa: F401

REFERENCE = "benchmark.reference.eva_lm"
HEAD = "head"  # the output layer in the parameter tree
SAMPLE_UNIT = "tokens"


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's bytes; no recompute."""
    return 3.0 * eva_costs.forward_flops_per_token(config, traffic["seq_len"]) \
        * samples_per_round(config, traffic)


def _check_block(config: dict, traffic: dict) -> None:
    """What this family's block is, and a sequence it has positions for."""
    want = {"model_type": "evabyte", "attention_class": "eva", "norm_add_unit_offset": True,
            "fp32_skip_add": True, "fp32_logits": True, "tie_word_embeddings": False,
            "attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
            "num_key_value_heads": config["num_attention_heads"]}
    wrong = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    if wrong:
        raise ValueError(f"not this family's block: {wrong}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len is beyond the model's max_position_embeddings")
    if traffic["alphabet"] != config["vocab_size"] or (
            traffic["targets_per_position"] != config["num_pred_heads"]):
        raise ValueError("the traffic's alphabet and targets are the model's vocabulary and heads")


def byte_rows(seed: int, n: int, length: int, alphabet: int, targets: int):
    """``(x [n, length], y [n, length, targets])`` int32: ``n`` rows of
    ``length + targets`` bytes as the module docstring says, and for each
    position the ``targets`` bytes that follow it."""
    rng = trafficlib.host_rng(seed, 3)
    pairs = set()
    while len(pairs) < n:
        start, stride = int(rng.integers(alphabet)), int(rng.integers(1, alphabet))
        if math.gcd(stride, alphabet) == 1:
            pairs.add((start, stride))
    start, stride = (np.asarray(c, np.int64)[:, None] for c in zip(*sorted(pairs)))
    seq = ((start + stride * np.arange(length + targets)[None]) % alphabet)[rng.permutation(n)]
    y = np.stack([seq[:, 1 + h:1 + h + length] for h in range(targets)], axis=-1)
    return seq[:, :length].astype(np.int32), y.astype(np.int32)


def _rows(config: dict, traffic: dict, seed: int):
    rows_per_client = traffic["local_steps"] * traffic["batch_size"]
    n = traffic["clients_total"] * rows_per_client
    x, y = byte_rows(seed, n, traffic["seq_len"], traffic["alphabet"],
                     traffic["targets_per_position"])
    return x, y, np.full(traffic["clients_total"], rows_per_client, np.int64)


def build(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    _check_block(config, traffic)
    opt = config["optimizer"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    layers = config["num_hidden_layers"]
    module = MLAMoETransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"], dense_layers=layers,
        routed_layers=0, num_heads=config["num_attention_heads"],
        head_dim=eva_costs.head_dim(config), dense_dim=config["intermediate_size"], mtp_depth=0,
        rope_theta=float(config["rope_theta"]), mixers=("eva",) * layers,
        eva_window=config["window_size"], eva_chunk=config["chunk_size"],
        norm_unit_offset=True, num_pred_heads=config["num_pred_heads"],
        rms_eps=config["rms_norm_eps"], attn_impl="flash", dtype=dtype,
        remat=bool(config.get("remat", False)))
    x, y, sizes = _rows(config, traffic, seed)
    train = FederatedArrays(
        {"x": x, "y": y, "mask": np.ones(y.shape, np.float32)}, trafficlib.partition(sizes))
    return {
        "trainer": ClientTrainer(
            module=module, task="nwp", epochs=1,
            optimizer=optax.sgd(opt["lr"], momentum=opt.get("momentum") or None)),
        "train": train,
        "test": None,
        "sim_config": SimConfig(
            client_num_in_total=traffic["clients_total"],
            client_num_per_round=traffic["clients_per_round"],
            batch_size=traffic["batch_size"], epochs=1,
            frequency_of_the_test=traffic["frequency_of_the_test"],
            eval_batch_size=traffic["eval_batch_size"],
            shuffle_each_round=False, seed=int(seed) % 4096,
            cohort_execution=traffic.get("cohort_execution", "scan"),
            block_dispatch=False,
        ),
    }


def reference_job(config: dict, traffic: dict, seed: int, n_rounds: int) -> dict:
    from benchmark.reference.eva_lm import Arch

    _check_block(config, traffic)
    x, y, sizes = _rows(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]
    arch = Arch(num_heads=config["num_attention_heads"], window=config["window_size"],
                chunk=config["chunk_size"], pred_heads=config["num_pred_heads"],
                rope_theta=float(config["rope_theta"]), rms_eps=config["rms_norm_eps"])

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "arch": arch}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
