"""Family ``conv_moe_lm``: a decoder whose layers mix by a gated short
convolution three times to one grouped-query attention layer with normalised
64-wide heads, over a sigmoid router with a selection bias and no shared
expert, leading dense layers and a head tied to the embedding (the LFM2
mixture-of-experts configuration), through
``fedml_tpu/models/mla_moe_transformer.py`` (``MLAMoETransformerLM`` with its
per-layer ``mixers`` "conv" and "gqa", ``ops/shortconv.py`` and the flash
kernels), the ``nwp`` ``ClientTrainer`` and ``FedSim``, on one chip's share of
a layer: the experts and the vocabulary rows the configuration's ``share``
says are held here.

Builds the federated job, gives its FLOPs a round (``benchmark/lfm2_costs.py``;
tokens a round and the absent test set are ``moe_lm``'s, as the traffic is),
and builds the same job for the plain reference
(``benchmark/reference/conv_moe_lm.py``). The configuration file keeps the
published ``config.json`` keys at its top level.
"""

from __future__ import annotations

import numpy as np

from benchmark import lfm2_costs
from benchmark import traffic as trafficlib
from benchmark.families.moe_lm import _tokens, eval_samples, samples_per_round  # noqa: F401

REFERENCE = "benchmark.reference.conv_moe_lm"
HEAD = "tok_embed"  # the output layer in the parameter tree: the head is tied to it
SAMPLE_UNIT = "tokens"


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's tokens; no recompute."""
    return 3.0 * lfm2_costs.forward_flops_per_token(config, traffic["seq_len"]) \
        * samples_per_round(config, traffic)


def _check_block(config: dict, traffic: dict) -> None:
    """What this family's block is: sigmoid scores normalised over the chosen
    with a bias for the choice, no bias on the convolution, plain rotary
    positions, a tied head; and a sequence the model has positions for."""
    want = {"model_type": "lfm2_moe", "norm_topk_prob": True, "use_expert_bias": True,
            "conv_bias": False, "tie_word_embeddings": True}
    wrong = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    if config["rope_parameters"]["rope_type"] != "default":
        wrong["rope_parameters"] = config["rope_parameters"]
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
    dense = lfm2_costs.dense_layers(config)
    module = MLAMoETransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["hidden_size"], dense_layers=dense,
        routed_layers=config["num_hidden_layers"] - dense,
        num_heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"],
        head_dim=lfm2_costs.head_dim(config), dense_dim=config["intermediate_size"],
        num_experts=config["moe_router_outputs"],
        experts_per_token=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], shared_dim=0,
        route_scale=float(config["routed_scaling_factor"]),
        experts_first=share["experts_first"], experts_held=config["num_experts"], mtp_depth=0,
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        mixers=lfm2_costs.mixers(config), conv_size=config["conv_L_cache"],
        rms_eps=config["norm_eps"], attn_impl="flash", dtype=dtype, tie_head=True,
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
    from benchmark.reference.conv_moe_lm import Arch

    _check_block(config, traffic)
    x, y, sizes = _tokens(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]
    arch = Arch(
        mixers=lfm2_costs.mixers(config), num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], top_k=config["num_experts_per_tok"],
        route_scale=float(config["routed_scaling_factor"]),
        experts_first=config["share"]["experts_first"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]), rms_eps=config["norm_eps"])

    def client_batches(c):
        def gen():
            for lo in range(offsets[c], offsets[c + 1], bs):
                yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs], "arch": arch}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"], "test": None}
