"""Entry-point, metrics, and checkpoint/resume tests."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.exp.main_fedavg import add_args, run
from fedml_tpu.obs.checkpoint import RoundCheckpointer
from fedml_tpu.obs.metrics import MetricsLogger
from fedml_tpu.obs.sysstats import SysStats

import argparse


def _args(extra=None):
    parser = add_args(argparse.ArgumentParser())
    base = [
        "--model", "lr", "--dataset", "synthetic_0.5_0.5",
        "--client_num_in_total", "8", "--client_num_per_round", "4",
        "--batch_size", "8", "--comm_round", "3", "--frequency_of_the_test", "3",
        "--lr", "0.05",
    ]
    return parser.parse_args(base + (extra or []))


def test_cli_fedavg_runs(tmp_path):
    history = run(_args(["--run_dir", str(tmp_path)]))
    assert len(history) == 3
    assert "Test/Acc" in history[-1]
    lines = (tmp_path / "metrics.jsonl").read_text().strip().splitlines()
    assert len(lines) == 3
    assert "Train/Loss" in json.loads(lines[0])


def test_cli_fedopt_and_fednova_and_robust():
    for algo_flags in (
        ["--algorithm", "fedopt", "--server_optimizer", "adam", "--server_lr", "0.05"],
        ["--algorithm", "fednova"],
        ["--algorithm", "fedprox", "--fedprox_mu", "0.5"],
        ["--algorithm", "fedavg_robust", "--norm_bound", "5.0", "--robust_rule", "median"],
    ):
        history = run(_args(algo_flags))
        assert np.isfinite(history[-1]["Train/Loss"]), algo_flags


def test_cli_hierarchical():
    history = run(_args(["--algorithm", "hierarchical", "--comm_round", "2",
                         "--group_num", "2", "--group_comm_round", "1"]))
    assert len(history) == 2


def test_checkpoint_roundtrip(tmp_path):
    variables = {"params": {"w": jnp.arange(6.0).reshape(2, 3)}}
    server_state = ()
    ck = RoundCheckpointer(tmp_path, keep=2)
    for r in (0, 1, 2, 3):
        ck.save(r, variables, server_state, history=[{"round": r}])
    assert ck.latest_round() == 3
    got, st, r, hist = ck.restore(variables)
    np.testing.assert_allclose(np.asarray(got["params"]["w"]), np.arange(6.0).reshape(2, 3))
    assert r == 3 and hist == [{"round": 3}]
    # gc kept only 2
    assert len(list(tmp_path.glob("round_*"))) == 2


def test_resume_continues_training(tmp_path):
    a1 = _args(["--checkpoint_dir", str(tmp_path), "--checkpoint_every", "1"])
    h1 = run(a1)
    a2 = _args(["--checkpoint_dir", str(tmp_path), "--resume", "1", "--comm_round", "5"])
    h2 = run(a2)
    assert h2[-1]["round"] == 4
    # resumed history contains the pre-resume rounds
    assert [r["round"] for r in h2][:3] == [0, 1, 2]


def test_sysstats_sample():
    s = SysStats().sample()
    assert "uptime_s" in s


def test_metrics_logger_no_dir():
    m = MetricsLogger()
    m.log({"Train/Acc": 1.0}, round_idx=0)
    assert m.history[0]["round"] == 0
    m.close()


def test_save_load_params_resnet56_and_gkt_pair(tmp_path):
    """save_params -> load_params is bit-equal on resnet56 and the GKT
    client/server split pair (reference pretrained warm-start,
    resnet.py:202-224, resnet56_gkt/resnet_pretrained.py)."""
    from fedml_tpu.models.resnet import resnet56
    from fedml_tpu.models.resnet_gkt import ResNetGKTClient, ResNetGKTServer
    from fedml_tpu.obs.checkpoint import load_params, save_params

    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    models = {
        "resnet56": (resnet56(class_num=10), x),
        "gkt_client": (ResNetGKTClient(num_classes=10), x),
    }
    client = ResNetGKTClient(num_classes=10)
    feats, _ = client.apply(client.init(jax.random.key(0), x), x, train=False)
    models["gkt_server"] = (ResNetGKTServer(num_classes=10), feats)

    for name, (model, inp) in models.items():
        variables = model.init(jax.random.key(1), inp, train=False)
        path = save_params(tmp_path / f"{name}.npz", variables)
        loaded = load_params(path, like=variables)
        for (kp_a, a), (kp_b, b) in zip(
            jax.tree_util.tree_flatten_with_path(jax.tree.map(np.asarray, dict(variables)))[0],
            jax.tree_util.tree_flatten_with_path(loaded)[0],
        ):
            assert kp_a == kp_b, name
            np.testing.assert_array_equal(a, b, err_msg=f"{name} {kp_a}")


def test_load_params_shape_mismatch_and_unknown_key(tmp_path):
    from fedml_tpu.obs.checkpoint import load_params, save_params

    variables = {"params": {"w": np.zeros((2, 3), np.float32)}}
    path = save_params(tmp_path / "p.npz", variables)
    with pytest.raises(ValueError, match="shape"):
        load_params(path, like={"params": {"w": np.zeros((4, 3), np.float32)}})
    with pytest.raises(ValueError, match="not present"):
        load_params(path, like={"params": {"v": np.zeros((2, 3), np.float32)}})
    # partial files warm-start only the saved subtree
    partial = load_params(path, like={"params": {"w": np.ones((2, 3), np.float32),
                                                 "b": np.ones((3,), np.float32)}})
    np.testing.assert_array_equal(partial["params"]["w"], 0.0)
    np.testing.assert_array_equal(partial["params"]["b"], 1.0)


def test_cli_init_from_warm_start(tmp_path):
    """--save_params_to then --init_from: the second run starts from the
    first run's final model (its round-0 train loss continues, not restarts)."""
    p = tmp_path / "warm.npz"
    run(_args(["--run_dir", str(tmp_path / "a"), "--save_params_to", str(p)]))
    assert p.exists()

    from fedml_tpu.obs.checkpoint import load_params

    h_cold = run(_args(["--run_dir", str(tmp_path / "b"), "--comm_round", "1",
                        "--frequency_of_the_test", "1"]))
    h_warm = run(_args(["--run_dir", str(tmp_path / "c"), "--comm_round", "1",
                        "--frequency_of_the_test", "1", "--init_from", str(p)]))
    assert h_warm[0]["Train/Loss"] < h_cold[0]["Train/Loss"]
    # the saved file holds the params collection
    assert "params" in load_params(p)
