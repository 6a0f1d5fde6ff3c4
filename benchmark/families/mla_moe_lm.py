"""Family ``mla_moe_lm``: a decoder with latent attention (MLA), a sigmoid
router with a selection bias beside a shared expert, a leading dense layer
and a multi-token-prediction loss (the JoyAI-LLM-Flash configuration) through
``fedml_tpu/models/mla_moe_transformer.py`` (``MLAMoETransformerLM`` with the
flash kernels at two head widths), the ``nwp`` ``ClientTrainer`` and
``FedSim``, on one chip's share of a layer: the experts and the vocabulary
rows the configuration's ``share`` says are held here.

Builds the federated job, gives its FLOPs a round (``benchmark/mla_costs.py``;
tokens a round and the absent test set are ``moe_lm``'s, as the traffic is),
and builds the same job for the plain reference
(``benchmark/reference/mla_moe_lm.py``). The configuration file keeps the
published ``config.json`` keys at its top level.
"""

from __future__ import annotations

import numpy as np

from benchmark import mla_costs
from benchmark import traffic as trafficlib
from benchmark.families.moe_lm import eval_samples, ramp_rows, samples_per_round  # noqa: F401

REFERENCE = "benchmark.reference.mla_moe_lm"
HEAD = "head"  # the output layer in the parameter tree
SAMPLE_UNIT = "tokens"


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's tokens, the
    multi-token-prediction module's included; no recompute."""
    return 3.0 * mla_costs.forward_flops_per_token(config, traffic["seq_len"]) \
        * samples_per_round(config, traffic)


def _tokens(config: dict, traffic: dict, seed: int):
    if traffic["ramp_alphabet"] > config["vocab_size"]:
        raise ValueError("the traffic's ids must lie in the held slice of the vocabulary")
    rows_per_client = traffic["local_steps"] * traffic["batch_size"]
    n = traffic["clients_total"] * rows_per_client
    x, y = ramp_rows(seed, n, traffic["seq_len"], traffic["ramp_alphabet"])
    return x, y, np.full(traffic["clients_total"], rows_per_client, np.int64)


def _check_block(config: dict) -> None:
    """What this family's block is: one group, sigmoid scores, adjacent
    rotary pairs, no rope scaling, at most one MTP module."""
    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "rope_interleave": True, "rope_scaling": None,
            "hidden_act": "silu", "moe_layer_freq": 1, "attention_bias": False,
            "tie_word_embeddings": False}
    wrong = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    if wrong or config["num_nextn_predict_layers"] > 1:
        raise ValueError(f"not this family's block: {wrong}")


def build(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    _check_block(config)
    opt, share = config["optimizer"], config["share"]
    if traffic["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("seq_len is beyond the model's max_position_embeddings")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    dense = config["first_k_dense_replace"]
    module = MLAMoETransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"], dense_layers=dense,
        routed_layers=config["num_hidden_layers"] - dense,
        num_heads=config["num_attention_heads"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"], nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        dense_dim=config["intermediate_size"], num_experts=config["moe_router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["n_shared_experts"] * config["moe_intermediate_size"],
        route_scale=config["routed_scaling_factor"], experts_first=share["experts_first"],
        experts_held=config["n_routed_experts"], mtp_depth=config["num_nextn_predict_layers"],
        mtp_loss_weight=config["mtp_loss_weight"], rope_theta=float(config["rope_theta"]),
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
    from benchmark.reference.mla_moe_lm import Arch

    _check_block(config)
    x, y, sizes = _tokens(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]
    arch = Arch(
        num_heads=config["num_attention_heads"], nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"], layers=config["num_hidden_layers"],
        top_k=config["num_experts_per_tok"], route_scale=config["routed_scaling_factor"],
        experts_first=config["share"]["experts_first"],
        mtp_weight=config["mtp_loss_weight"] * config["num_nextn_predict_layers"],
        rope_theta=float(config["rope_theta"]), rms_eps=config["rms_norm_eps"])

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "arch": arch}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
