"""Family ``kda_moe_lm``: a decoder whose layers mix by Kimi Delta Attention
(a gated delta-rule linear attention with one decay a key channel, a short
convolution and a gated output norm) three times to one latent-attention
layer without positions, over the sigmoid router, shared expert and leading
dense layer of ``mla_moe_lm`` (the Kimi-Linear configuration), through
``fedml_tpu/models/mla_moe_transformer.py`` (``MLAMoETransformerLM`` with its
per-layer ``mixers``, the chunked scan of ``fedml_tpu/ops/kda.py`` and the
flash kernels), the ``nwp`` ``ClientTrainer`` and ``FedSim``, on one chip's
share of a layer: the experts and the vocabulary rows the configuration's
``share`` says are held here.

Builds the federated job, gives its FLOPs a round (``benchmark/kda_costs.py``;
tokens a round and the absent test set are ``moe_lm``'s, as the traffic is),
and builds the same job for the plain reference
(``benchmark/reference/kda_moe_lm.py``). The configuration file keeps the
published ``config.json`` keys at its top level.
"""

from __future__ import annotations

import numpy as np

from benchmark import kda_costs
from benchmark import traffic as trafficlib
from benchmark.families.moe_lm import eval_samples, ramp_rows, samples_per_round  # noqa: F401

REFERENCE = "benchmark.reference.kda_moe_lm"
HEAD = "head"  # the output layer in the parameter tree
SAMPLE_UNIT = "tokens"


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's tokens; no recompute."""
    return 3.0 * kda_costs.forward_flops_per_token(config, traffic["seq_len"]) \
        * samples_per_round(config, traffic)


def _tokens(config: dict, traffic: dict, seed: int):
    if traffic["ramp_alphabet"] > config["vocab_size"]:
        raise ValueError("the traffic's ids must lie in the held slice of the vocabulary")
    if traffic["seq_len"] > config["model_max_length"]:
        raise ValueError("seq_len is beyond the model's model_max_length")
    rows_per_client = traffic["local_steps"] * traffic["batch_size"]
    n = traffic["clients_total"] * rows_per_client
    x, y = ramp_rows(seed, n, traffic["seq_len"], traffic["ramp_alphabet"])
    return x, y, np.full(traffic["clients_total"], rows_per_client, np.int64)


def _check_block(config: dict) -> None:
    """What this family's block is: one group, sigmoid scores normalised
    over the chosen, no query latent, no positions, no MTP module."""
    want = {"moe_router_activation_func": "sigmoid", "moe_renormalize": True,
            "num_expert_group": 1, "topk_group": 1, "hidden_act": "silu", "moe_layer_freq": 1,
            "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "mla_use_nope": True,
            "q_lora_rank": None, "rope_scaling": None}
    wrong = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    if wrong:
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
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    dense = config["first_k_dense_replace"]
    kda_heads, kda_width = kda_costs.kda_widths(config)
    module = MLAMoETransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"], dense_layers=dense,
        routed_layers=config["num_hidden_layers"] - dense,
        num_heads=config["num_attention_heads"], q_rank=None,
        kv_rank=config["kv_lora_rank"], nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        dense_dim=config["intermediate_size"], num_experts=config["moe_router_outputs"],
        experts_per_token=config["num_experts_per_token"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["num_shared_experts"] * config["moe_intermediate_size"],
        route_scale=config["routed_scaling_factor"], experts_first=share["experts_first"],
        experts_held=config["num_experts"], mtp_depth=0, rope_theta=None,
        mixers=kda_costs.mixers(config), kda_heads=kda_heads, kda_head_dim=kda_width,
        conv_size=config["linear_attn_config"]["short_conv_kernel_size"],
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
    from benchmark.reference.kda_moe_lm import Arch

    _check_block(config)
    x, y, sizes = _tokens(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]
    arch = Arch(
        mixers=kda_costs.mixers(config), num_heads=config["num_attention_heads"],
        nope_dim=config["qk_nope_head_dim"], kv_rank=config["kv_lora_rank"],
        kda_heads=kda_costs.kda_widths(config)[0], top_k=config["num_experts_per_token"],
        route_scale=config["routed_scaling_factor"],
        experts_first=config["share"]["experts_first"], rms_eps=config["rms_norm_eps"])

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "arch": arch}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
