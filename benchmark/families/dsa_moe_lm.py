"""Family ``dsa_moe_lm``: a decoder whose every layer is grouped-query
attention over a learned selection of keys (an indexer scores every earlier
key, each query attends to its ``topk`` best, and the indexer learns from an
index loss of its own) and routed experts behind a softmax router with no
shared expert (the Keye-VL-2.0-30B-A3B language model), through
``fedml_tpu/models/mla_moe_transformer.py`` (``MLAMoETransformerLM`` with its
mixer "dsa" and router "softmax", ``ops/dsa.py`` and the masked flash
kernels), the ``nwp`` ``ClientTrainer`` and ``FedSim``, on one chip's share of
a layer: the experts and the vocabulary rows the configuration's ``share``
says are held here.

Builds the federated job, gives its FLOPs a round (``benchmark/dsa_costs.py``,
from the *selected* pairs; tokens a round, the rows and the absent test set
are ``moe_lm``'s, as the traffic is), and builds the same job for the plain
reference (``benchmark/reference/dsa_moe_lm.py``). The configuration file
keeps the published ``config.json`` keys at its top level.
"""

from __future__ import annotations

import numpy as np

from benchmark import dsa_costs
from benchmark import traffic as trafficlib
from benchmark.families.moe_lm import _tokens, eval_samples, samples_per_round  # noqa: F401

REFERENCE = "benchmark.reference.dsa_moe_lm"
HEAD = "head"  # the output layer in the parameter tree
SAMPLE_UNIT = "tokens"


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's tokens at stated work:
    the attention over the selected pairs, no recompute."""
    return 3.0 * dsa_costs.forward_flops_per_token(config, traffic["seq_len"]) \
        * samples_per_round(config, traffic)


def _check_block(config: dict, traffic: dict) -> None:
    """What this family's block is: every layer routed, a softmax over the
    chosen logits, no bias, SiLU, an untied head, one rotation; and a sequence
    the model has positions for."""
    want = {"model_type": "KeyeVL2", "norm_topk_prob": True, "attention_bias": False,
            "hidden_act": "silu", "tie_word_embeddings": False, "mlp_only_layers": [],
            "decoder_sparse_step": 1, "use_sliding_window": False}
    wrong = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    if config["sa_config"]["indexer_num_kv_heads"] != 1:
        wrong["sa_config"] = config["sa_config"]
    if wrong:
        raise ValueError(f"not this family's block: {wrong}")
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len is beyond the model's max_position_embeddings")


def build(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    _check_block(config, traffic)
    opt, share = config["optimizer"], config["share"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    heads, width, topk = dsa_costs.index_widths(config)
    layers = config["num_hidden_layers"]
    module = MLAMoETransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"], dense_layers=0,
        routed_layers=layers, num_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        num_experts=config["moe_router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], shared_dim=0,
        experts_first=share["experts_first"], experts_held=config["num_experts"], mtp_depth=0,
        rope_theta=float(config["rope_theta"]), mixers=("dsa",) * layers, router="softmax",
        index_heads=heads, index_dim=width, index_topk=topk,
        rms_eps=config["rms_norm_eps"], attn_impl="flash", dtype=dtype,
        remat=bool(config.get("remat", False)))
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
    from benchmark.reference.dsa_moe_lm import Arch

    _check_block(config, traffic)
    x, y, sizes = _tokens(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]
    heads, _, topk = dsa_costs.index_widths(config)
    arch = Arch(
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        index_heads=heads, topk=topk, top_k=config["num_experts_per_tok"],
        experts_first=config["share"]["experts_first"], rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"])

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "arch": arch}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
