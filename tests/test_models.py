"""Model zoo shape/param tests (reference analogue: fedml_api/model/cv/
test_cnn.py FLOPs/param counting)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.tree import tree_size
from fedml_tpu.models import (
    CNNDropOut,
    CNNOriginalFedAvg,
    Discriminator,
    Generator,
    LogisticRegression,
    MobileNet,
    MobileNetV3,
    RNNOriginalFedAvg,
    RNNStackOverflow,
    VGG,
    create_model,
    resnet56,
    resnet18_gn,
    task_for_dataset,
)

KEY = jax.random.key(0)


def _init_and_apply(module, x, check_params=None):
    variables = module.init({"params": KEY, "dropout": KEY}, x, train=False)
    out = module.apply(variables, x, train=False)
    out2, _ = module.apply(
        variables, x, train=True,
        mutable=["batch_stats"], rngs={"dropout": KEY},
    )
    assert out.shape == out2.shape
    if check_params:
        n = tree_size(variables["params"])
        assert abs(n - check_params) / check_params < 0.35, n
    return variables, out


def test_lr():
    x = jnp.ones((4, 28, 28))
    _, out = _init_and_apply(LogisticRegression(num_classes=10), x, 7850)
    assert out.shape == (4, 10)


def test_cnn_original():
    x = jnp.ones((2, 28, 28, 1))
    _, out = _init_and_apply(CNNOriginalFedAvg(num_classes=62), x)
    assert out.shape == (2, 62)


def test_cnn_dropout():
    x = jnp.ones((2, 28, 28, 1))
    _, out = _init_and_apply(CNNDropOut(num_classes=62), x)
    assert out.shape == (2, 62)


def test_resnet56_params():
    x = jnp.ones((2, 32, 32, 3))
    # reference resnet56 ~0.86M params (resnet.py:202 CIFAR family)
    variables, out = _init_and_apply(resnet56(class_num=10), x, 860_000)
    assert out.shape == (2, 10)
    assert "batch_stats" in variables


def test_resnet18_gn():
    x = jnp.ones((2, 24, 24, 3))
    # ~11M params (resnet_gn.py:183)
    variables, out = _init_and_apply(resnet18_gn(class_num=100), x, 11_000_000)
    assert out.shape == (2, 100)
    assert "batch_stats" not in variables  # GN has no federated running stats


@pytest.mark.slow  # compile/compute-heavy on the single-core CI box; core logic covered by faster siblings
def test_mobilenet():
    x = jnp.ones((2, 32, 32, 3))
    variables, out = _init_and_apply(MobileNet(num_classes=10), x, 3_200_000)
    assert out.shape == (2, 10)


@pytest.mark.slow  # compile-heavy on XLA:CPU; kept out of the fast gate
def test_mobilenet_v3_small():
    x = jnp.ones((2, 32, 32, 3))
    _, out = _init_and_apply(MobileNetV3(num_classes=10, mode="small"), x)
    assert out.shape == (2, 10)


def test_vgg11():
    x = jnp.ones((2, 32, 32, 3))
    _, out = _init_and_apply(VGG(depth=11, num_classes=10), x)
    assert out.shape == (2, 10)


def test_rnn_shakespeare():
    x = jnp.ones((2, 20), jnp.int32)
    # reference RNN_OriginalFedAvg: ~820k params (2xLSTM(256), 90 vocab)
    _, out = _init_and_apply(RNNOriginalFedAvg(), x, 820_000)
    assert out.shape == (2, 20, 90)


def test_rnn_stackoverflow():
    x = jnp.ones((2, 20), jnp.int32)
    _, out = _init_and_apply(RNNStackOverflow(), x)
    assert out.shape == (2, 20, 10004)


def test_gan_shapes():
    z = jnp.ones((3, 100))
    gen = Generator()
    gv = gen.init({"params": KEY}, z, train=False)
    img = gen.apply(gv, z, train=False)
    assert img.shape == (3, 28, 28, 1)
    disc = Discriminator()
    dv = disc.init({"params": KEY}, img, train=False)
    logit = disc.apply(dv, img, train=False)
    assert logit.shape == (3, 1)


def test_registry_dispatch():
    assert isinstance(create_model("lr", 10, "mnist"), LogisticRegression)
    assert isinstance(create_model("rnn", 90, "shakespeare"), RNNOriginalFedAvg)
    assert isinstance(create_model("rnn", 0, "stackoverflow_nwp"), RNNStackOverflow)
    assert isinstance(create_model("cnn", 62, "femnist"), CNNDropOut)
    assert isinstance(create_model("vgg16", 10), VGG)
    with pytest.raises(ValueError):
        create_model("nope", 10)
    assert task_for_dataset("shakespeare") == "char_lm"
    assert task_for_dataset("cifar10") == "classification"


def test_cnn_trains_one_step():
    """A CNN with dropout + a BN model goes through the ClientTrainer step."""
    import optax

    from fedml_tpu.core.trainer import ClientTrainer

    x = np.random.RandomState(0).rand(2, 8, 8, 3).astype(np.float32)
    batch = {
        "x": jnp.asarray(x),
        "y": jnp.asarray([0, 1]),
        "mask": jnp.ones(2, jnp.float32),
    }
    from fedml_tpu.models.resnet import CifarResNet

    # depth-8 member of the same BN family: exercises the identical
    # batch_stats plumbing at a fraction of resnet56's unjitted trace cost
    tr = ClientTrainer(module=CifarResNet(depth=8, num_classes=4),
                       optimizer=optax.sgd(0.1))
    variables = tr.init(KEY, batch)
    opt_state = tr.optimizer.init(variables["params"])
    new_vars, _, loss = tr.train_step(variables, opt_state, variables["params"], batch, KEY)
    assert jnp.isfinite(loss)
    # batch_stats must have been updated by the training step
    diff = jax.tree_util.tree_leaves(
        jax.tree.map(lambda a, b: jnp.abs(a - b).sum(), variables["batch_stats"], new_vars["batch_stats"])
    )
    assert sum(float(d) for d in diff) > 0


@pytest.mark.slow  # compile-heavy on XLA:CPU; kept out of the fast gate
def test_efficientnet_b0():
    from fedml_tpu.models.efficientnet import efficientnet

    x = jnp.ones((2, 32, 32, 3))
    # reference b0 ~5.3M params (efficientnet.py:138 torch port); GN-instead-of-
    # BN shifts the count slightly
    variables, out = _init_and_apply(efficientnet("efficientnet-b0", 10), x, 5_300_000)
    assert out.shape == (2, 10)
    assert "batch_stats" not in variables


@pytest.mark.slow  # compile-heavy on XLA:CPU; kept out of the fast gate
def test_efficientnet_scaling():
    from fedml_tpu.models.efficientnet import efficientnet
    from fedml_tpu.core.tree import tree_size

    x = jnp.ones((1, 32, 32, 3))
    n0 = tree_size(
        efficientnet("efficientnet-b0", 10).init({"params": KEY, "dropout": KEY}, x, train=False)["params"]
    )
    n2 = tree_size(
        efficientnet("efficientnet-b2", 10).init({"params": KEY, "dropout": KEY}, x, train=False)["params"]
    )
    assert n2 > 1.2 * n0  # compound scaling grows the network


@pytest.mark.slow  # compile/compute-heavy on the single-core CI box; core logic covered by faster siblings
def test_efficientnet_registry():
    m = create_model("efficientnet-b1", 10)
    x = jnp.ones((1, 32, 32, 3))
    out = m.apply(m.init({"params": KEY, "dropout": KEY}, x, train=False), x, train=False)
    assert out.shape == (1, 10)


def test_lenet_shapes():
    from fedml_tpu.models.cnn import LeNet

    m = LeNet(num_classes=10)
    x = jnp.ones((2, 28, 28, 1))
    v = m.init({"params": jax.random.key(0)}, x)
    assert m.apply(v, x).shape == (2, 10)
    # 3-dim (H, W) input is auto-expanded (LEAF mnist arrays)
    assert m.apply(v, jnp.ones((2, 28, 28))).shape == (2, 10)


def test_darts_gdas_samples_single_op():
    import numpy as np

    from fedml_tpu.models.darts import DARTSNetwork, gumbel_hard_weights

    # straight-through weights: exact one-hot forward, soft gradient
    alphas = jnp.asarray(np.random.RandomState(0).randn(5, 6).astype(np.float32))
    w = gumbel_hard_weights(alphas, jax.random.key(1), tau=5.0)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), np.ones(5), rtol=1e-6)
    # one-hot up to float rounding ((1 + s) - s): one ~1.0 entry per edge
    wn = np.asarray(w)
    assert (np.isclose(wn, 1.0, atol=1e-5).sum(axis=-1) == 1).all()
    assert np.allclose(np.sort(wn, axis=-1)[:, :-1], 0.0, atol=1e-5)
    g = jax.grad(lambda a: gumbel_hard_weights(a, jax.random.key(1), 5.0).sum())(alphas)
    assert np.isfinite(np.asarray(g)).all()

    net = DARTSNetwork(num_classes=4, channels=4, layers=2, steps=2,
                       search_mode="gdas")
    x = jnp.ones((2, 16, 16, 3))
    v = net.init({"params": jax.random.key(0), "gumbel": jax.random.key(1)},
                 x, train=True)
    out, _ = net.apply(v, x, train=True, mutable=["batch_stats"],
                       rngs={"gumbel": jax.random.key(2)})
    assert out.shape == (2, 4)
    # eval path is deterministic (argmax ops, no rng needed)
    out_eval = net.apply(v, x, train=False)
    assert out_eval.shape == (2, 4)


@pytest.mark.slow  # compile-heavy on XLA:CPU; kept out of the fast gate
def test_cv_zoo_bf16_compute():
    """Every CV-zoo model takes a compute dtype: bf16 forward works, params
    stay f32, logits come back f32."""
    import numpy as np

    from fedml_tpu.models.cnn import CNNDropOut, CNNOriginalFedAvg, LeNet
    from fedml_tpu.models.efficientnet import EfficientNet
    from fedml_tpu.models.mobilenet import MobileNet, MobileNetV3
    from fedml_tpu.models.resnet import resnet18_gn, resnet56
    from fedml_tpu.models.vgg import VGG

    cases = [
        (CNNOriginalFedAvg(num_classes=4, dtype=jnp.bfloat16), (2, 28, 28, 1)),
        (CNNDropOut(num_classes=4, dtype=jnp.bfloat16), (2, 28, 28, 1)),
        (LeNet(num_classes=4, dtype=jnp.bfloat16), (2, 28, 28, 1)),
        (resnet56(4, dtype=jnp.bfloat16), (2, 32, 32, 3)),
        (resnet18_gn(4, dtype=jnp.bfloat16), (2, 32, 32, 3)),
        (MobileNet(num_classes=4, dtype=jnp.bfloat16), (2, 32, 32, 3)),
        (MobileNetV3(num_classes=4, dtype=jnp.bfloat16), (2, 32, 32, 3)),
        (VGG(depth=11, num_classes=4, dtype=jnp.bfloat16), (2, 32, 32, 3)),
        (EfficientNet(num_classes=4, dtype=jnp.bfloat16), (2, 32, 32, 3)),
    ]
    for model, shape in cases:
        x = jnp.ones(shape, jnp.float32)
        v = model.init({"params": jax.random.key(0), "dropout": jax.random.key(1)},
                       x, train=False)
        out = model.apply(v, x, train=False)
        assert out.shape == (2, 4), type(model).__name__
        assert out.dtype == jnp.float32, type(model).__name__
        assert all(
            l.dtype == jnp.float32
            for l in jax.tree.leaves(v["params"])
        ), type(model).__name__
        assert np.isfinite(np.asarray(out)).all(), type(model).__name__


def test_resnet_f32_vs_bf16_accuracy_parity():
    """bf16 compute (the precision the benchmark's LM configurations state:
    ``compute_dtype`` in benchmark/configs/cerebras_gpt_1p3b_cut.json) matches f32
    training accuracy on the ResNet family: same data, same recipe, both must
    learn the task and land within a few points of each other."""
    import numpy as np
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.models.resnet import CifarResNet
    from fedml_tpu.sim.engine import FedSim, SimConfig

    train, test = gaussian_blobs(n_clients=4, samples_per_client=32,
                                 num_classes=4, dim=8 * 8 * 3, seed=5)
    for arrays in (train.arrays, test):
        arrays["x"] = arrays["x"].reshape(-1, 8, 8, 3)

    accs = {}
    for name, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        tr = ClientTrainer(
            module=CifarResNet(depth=8, num_classes=4, dtype=dtype),
            optimizer=optax.sgd(0.1, momentum=0.9), epochs=1,
        )
        cfg = SimConfig(client_num_in_total=4, client_num_per_round=4,
                        batch_size=16, comm_round=8, epochs=1,
                        frequency_of_the_test=8, seed=0)
        _, hist = FedSim(tr, train, test, cfg).run()
        accs[name] = hist[-1]["Test/Acc"]
    assert accs["f32"] > 0.85, accs
    assert accs["bf16"] > 0.85, accs
    assert abs(accs["f32"] - accs["bf16"]) < 0.1, accs
