"""Family ``resnet``: the CIFAR ResNet with BatchNorm and ResNet-18 with
GroupNorm, both through ``fedml_tpu/models/resnet.py``, the classification
``ClientTrainer`` and ``FedSim``.

From a configuration file, a workload's traffic and the seed this builds the
federated job (trainer, client-partitioned train set, test set, SimConfig);
gives the job's FLOPs and samples a round; and builds the same job for the
plain reference (``benchmark/reference/resnet.py``), which reads none of the
program's code.
"""

from __future__ import annotations

import numpy as np

from benchmark import traffic as trafficlib

REFERENCE = "benchmark.reference.resnet"
HEAD = "Dense_0"  # the output layer in the parameter tree
SAMPLE_UNIT = "images"


def forward_flops_per_image(model: dict) -> float:
    """2 x multiply-accumulates of one forward pass: the 3x3 stem, every
    block's two 3x3 convolutions at the block's output size, the 1x1
    projection at each later stage's entry, and the classifier."""
    hw = model["image_hw"]
    chans = model["stage_channels"]
    fl = 2 * hw * hw * 9 * model["image_channels"] * chans[0]
    c_prev = chans[0]
    for si, c in enumerate(chans):
        if si > 0:
            hw //= 2
        for b in range(model["blocks_per_stage"]):
            c_in = c_prev if b == 0 else c
            fl += 2 * hw * hw * 9 * c_in * c + 2 * hw * hw * 9 * c * c
            if b == 0 and si > 0:
                fl += 2 * hw * hw * c_in * c
        c_prev = c
    return float(fl + 2 * chans[-1] * model["num_classes"])


def samples_per_round(config: dict, traffic: dict) -> float:
    """Real (unpadded) training images a round, on average."""
    sizes = trafficlib.client_sizes(traffic, 0)
    return float(sizes.mean() * traffic["clients_per_round"] * config["local_epochs"])


def flops_per_round(config: dict, traffic: dict) -> float:
    """Forward + backward (3 x forward) of the round's real images; padded
    steps, eval and aggregation count nothing."""
    return 3.0 * forward_flops_per_image(config["model"]) * samples_per_round(config, traffic)


def eval_samples(config: dict, traffic: dict) -> int:
    """Examples one ``FedSim.evaluate`` call scores: the pooled train set
    and the test set."""
    return int(config["train_images"] + config["test_images"])


def _data(config: dict, traffic: dict, seed: int):
    model = config["model"]
    sizes = trafficlib.client_sizes(traffic, seed)
    if sizes.sum() != config["train_images"]:
        raise ValueError("the workload's client sizes do not sum to train_images")
    labels = trafficlib.skewed_labels(sizes, model["num_classes"],
                                      traffic["label_dirichlet_alpha"], seed)
    test_labels = trafficlib.host_rng(seed, 5).integers(
        0, model["num_classes"], config["test_images"]).astype(np.int32)
    x = trafficlib.class_images(seed, labels, model["image_hw"], model["num_classes"], 10)
    tx = trafficlib.class_images(seed, test_labels, model["image_hw"], model["num_classes"], 11)
    return sizes, (x, labels), (tx, test_labels)


def build(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models import resnet as zoo
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    model, opt = config["model"], config["optimizer"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["compute_dtype"]]
    if model["arch"] == "cifar_resnet":
        module = zoo.CifarResNet(depth=model["depth"], num_classes=model["num_classes"],
                                 norm="bn", dtype=dtype)
    elif model["arch"] == "resnet18_gn":
        module = zoo.resnet18_gn(model["num_classes"], dtype=dtype)
    else:
        raise ValueError(f"family resnet has no arch {model['arch']!r}")
    tx = optax.sgd(opt["lr"], momentum=opt.get("momentum") or None)
    if opt.get("weight_decay"):
        tx = optax.chain(optax.add_decayed_weights(opt["weight_decay"]), tx)
    sizes, (x, y), (test_x, test_y) = _data(config, traffic, seed)
    train = FederatedArrays({"x": np.asarray(x), "y": y}, trafficlib.partition(sizes))
    return {
        "trainer": ClientTrainer(module=module, task="classification", optimizer=tx,
                                 epochs=config["local_epochs"]),
        "train": train,
        "test": {"x": np.asarray(test_x), "y": test_y},
        "sim_config": SimConfig(
            client_num_in_total=traffic["clients_total"],
            client_num_per_round=traffic["clients_per_round"],
            batch_size=traffic["batch_size"], epochs=config["local_epochs"],
            frequency_of_the_test=traffic["frequency_of_the_test"],
            eval_batch_size=traffic["eval_batch_size"],
            shuffle_each_round=False, seed=int(seed) % 4096,
            cohort_execution=traffic.get("cohort_execution", "vmap"),
        ),
    }


def reference_job(config: dict, traffic: dict, seed: int, n_rounds: int) -> dict:
    """The first ``n_rounds`` of the same job for ``reference/fedavg.py``:
    the batches follow ``benchmark/traffic.py``'s rules, not the program's
    staging."""
    sizes, (x, y), (test_x, test_y) = _data(config, traffic, seed)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    bs = traffic["batch_size"]

    def client_batches(c):
        def gen():
            for _ in range(config["local_epochs"]):
                for lo in range(offsets[c], offsets[c + 1], bs):
                    yield {"x": x[lo:lo + bs], "y": y[lo:lo + bs]}
        return gen

    rounds = [[(float(sizes[c]), client_batches(c)) for c in trafficlib.cohort(traffic, r)]
              for r in range(n_rounds)]
    return {"rounds": rounds, "optimizer": config["optimizer"],
            "test": (test_x, test_y)}
