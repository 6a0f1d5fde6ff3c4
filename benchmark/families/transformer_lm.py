"""Family ``transformer_lm``: the GPT-2-style decoder of the Cerebras-GPT
configurations through ``fedml_tpu/models/transformer.py`` (``TransformerLM``
with the flash forward kernel), the ``nwp`` ``ClientTrainer`` and ``FedSim``,
on one chip or under a partition-rule plan across chips.

Builds the federated job, gives its FLOPs and tokens a round, and builds the
same job for the plain reference (``benchmark/reference/transformer_lm.py``).
"""

from __future__ import annotations

import numpy as np

from benchmark import traffic as trafficlib

REFERENCE = "benchmark.reference.transformer_lm"
HEAD = "head"  # the output layer in the parameter tree
SAMPLE_UNIT = "tokens"


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward (3 x forward) matmul FLOPs a token: 2 x (12 L D^2 +
    D V) for the blocks and the head, plus causal attention at half of the
    full 4 T D a layer (only the lower triangle is work). No recompute."""
    d, layers, vocab = config["n_embd"], config["n_layer"], config["vocab_size"]
    return 3.0 * (2 * (12 * layers * d * d + d * vocab) + layers * 2 * seq_len * d)


def samples_per_round(config: dict, traffic: dict) -> float:
    return float(traffic["clients_per_round"] * traffic["local_steps"]
                 * traffic["batch_size"] * traffic["seq_len"])


def flops_per_round(config: dict, traffic: dict) -> float:
    return train_flops_per_token(config, traffic["seq_len"]) * samples_per_round(
        config, traffic)


def eval_samples(config: dict, traffic: dict) -> int:
    return 0  # no test set: the cell reports no eval_samples_per_s


def _tokens(traffic: dict, seed: int):
    rows_per_client = traffic["local_steps"] * traffic["batch_size"]
    n = traffic["clients_total"] * rows_per_client
    x, y = trafficlib.ramp_tokens(seed, n, traffic["seq_len"], traffic["ramp_alphabet"])
    return x, y, np.full(traffic["clients_total"], rows_per_client, np.int64)


def build(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    model, opt = config, config["optimizer"]  # the published keys sit at the file's top level
    if traffic["seq_len"] > model["n_positions"]:
        raise ValueError("seq_len is beyond the model's n_positions")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    module = TransformerLM(
        vocab_size=model["vocab_size"], embed_dim=model["n_embd"],
        num_layers=model["n_layer"], num_heads=model["n_head"],
        max_len=model["n_positions"], attn_impl="flash", dtype=dtype,
        remat=bool(config.get("remat", False)))
    x, y, sizes = _tokens(traffic, seed)
    train = FederatedArrays(
        {"x": x, "y": y, "mask": np.ones(x.shape, np.float32)}, trafficlib.partition(sizes))
    layout = config.get("layout", {})
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
            shard_rules=layout.get("shard_rules"),
            mesh_shape=tuple(layout["mesh_shape"]) if layout.get("mesh_shape") else None,
        ),
    }


def reference_job(config: dict, traffic: dict, seed: int, n_rounds: int) -> dict:
    x, y, sizes = _tokens(traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs, heads = traffic["batch_size"], config["n_head"]

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "num_heads": heads}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
