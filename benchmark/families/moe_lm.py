"""Family ``moe_lm``: a decoder with routed experts, grouped KV heads and
window and global attention layers mixed (the SmallThinker configurations)
through ``fedml_tpu/models/moe_transformer.py`` (``MoETransformerLM`` with the
flash kernels), the ``nwp`` ``ClientTrainer`` and ``FedSim``, on one chip's
share of a layer: the experts and the vocabulary rows the configuration's
``share`` says are held here.

Builds the federated job, gives its FLOPs and tokens a round
(``benchmark/moe_costs.py``), and builds the same job for the plain
reference (``benchmark/reference/moe_lm.py``). The configuration file keeps
the published ``config.json`` keys at its top level.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import moe_costs
from benchmark import traffic as trafficlib

REFERENCE = "benchmark.reference.moe_lm"
HEAD = "head"  # the output layer in the parameter tree
SAMPLE_UNIT = "tokens"


def samples_per_round(config: dict, traffic: dict) -> float:
    return float(traffic["clients_per_round"] * traffic["local_steps"]
                 * traffic["batch_size"] * traffic["seq_len"])


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's tokens; no recompute."""
    return 3.0 * moe_costs.forward_flops_per_token(config, traffic["seq_len"]) \
        * samples_per_round(config, traffic)


def eval_samples(config: dict, traffic: dict) -> int:
    return 0  # no test set: the cell reports no eval_samples_per_s


def ramp_rows(seed: int, n: int, length: int, alphabet: int):
    """``n`` token rows ``(start + stride * pos) % alphabet`` that all differ,
    each with a stride that shares no factor with the alphabet, so a row of
    up to ``alphabet`` tokens never repeats one and the ids span the whole
    alphabet; and their next-token targets. (``traffic.ramp_tokens`` permutes
    every (start, stride) pair, 1.4e9 numbers at an alphabet of 37,984.)"""
    if length + 1 > alphabet:
        raise ValueError("a row longer than the alphabet repeats tokens")
    rng = trafficlib.host_rng(seed, 3)
    pairs = set()
    while len(pairs) < n:
        start, stride = int(rng.integers(alphabet)), int(rng.integers(1, alphabet))
        if math.gcd(stride, alphabet) == 1:
            pairs.add((start, stride))
    start, stride = (np.asarray(c, np.int64)[:, None] for c in zip(*sorted(pairs)))
    order = rng.permutation(n)
    seq = ((start + stride * np.arange(length + 1)[None]) % alphabet)[order]
    return seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)


def _tokens(config: dict, traffic: dict, seed: int):
    if traffic["ramp_alphabet"] > config["vocab_size"]:
        raise ValueError("the traffic's ids must lie in the held slice of the vocabulary")
    rows_per_client = traffic["local_steps"] * traffic["batch_size"]
    n = traffic["clients_total"] * rows_per_client
    x, y = ramp_rows(seed, n, traffic["seq_len"], traffic["ramp_alphabet"])
    return x, y, np.full(traffic["clients_total"], rows_per_client, np.int64)


def layer_kinds(config: dict) -> tuple:
    """"window" / "global" of each layer run (``moe_costs.layer_windows``);
    the published ``rope_layout`` is the same list as
    ``sliding_window_layout`` (rotary positions on window layers alone) and
    is held to it."""
    n = config["num_hidden_layers"]
    if config["rope_layout"][:n] != config["sliding_window_layout"][:n]:
        raise ValueError("rope_layout and sliding_window_layout differ: not this family's block")
    return tuple("window" if w else "global" for w in moe_costs.layer_windows(config))


def build(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.moe_transformer import MoETransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    opt, share = config["optimizer"], config["share"]
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len is beyond the model's max_position_embeddings")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    module = MoETransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"],
        layer_kinds=layer_kinds(config), num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        num_experts=config["moe_router_outputs"],
        experts_per_token=config["moe_num_active_primary_experts"],
        expert_dim=config["moe_ffn_hidden_size"], experts_first=share["experts_first"],
        experts_held=config["moe_num_primary_experts"], window=config["sliding_window_size"],
        rope_theta=float(config["rope_theta"]), rms_eps=config["rms_norm_eps"],
        attn_impl="flash", dtype=dtype, remat=bool(config.get("remat", False)))
    x, y, sizes = _tokens(config, traffic, seed)
    train = FederatedArrays(
        {"x": x, "y": y, "mask": np.ones(x.shape, np.float32)}, trafficlib.partition(sizes))
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
    from benchmark.reference.moe_lm import Arch

    x, y, sizes = _tokens(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]
    arch = Arch(
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], top_k=config["moe_num_active_primary_experts"],
        experts_first=config["share"]["experts_first"],
        windows=tuple(moe_costs.layer_windows(config)), rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"])

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "arch": arch}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
