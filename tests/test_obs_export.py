"""MLOps telemetry protocol, package builder, SyncBN, and model export."""

import json
import zipfile

import flax.linen as nn
import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.models.export import (
    export_stablehlo,
    flat_list_to_params,
    load_stablehlo,
    params_to_flat_list,
)
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs.mlops import (
    TOPIC_SERVER_METRICS,
    TOPIC_SYSTEM,
    FileMessenger,
    MLOpsLogger,
)
from fedml_tpu.obs.package import build_mlops_package, verify_package
from fedml_tpu.ops.syncbn import SyncBatchNorm

REPO_ROOT = __import__("pathlib").Path(__file__).resolve().parent.parent


# -- MLOps telemetry ---------------------------------------------------------


def test_mlops_logger_reference_topics(tmp_path):
    sink = tmp_path / "mlops.jsonl"
    logger = MLOpsLogger(FileMessenger(sink), run_id="r1", edge_id=3)
    logger.report_client_training_status(3, "TRAINING")
    logger.report_client_id_status("r1", 3, "ONLINE")
    logger.report_server_training_metric({"round": 1, "acc": 0.5})
    logger.report_system_metric()
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    topics = [r["topic"] for r in recs]
    assert topics == [
        "fl_client/mlops/status",
        "fl_client/mlops/3/status",
        TOPIC_SERVER_METRICS,
        TOPIC_SYSTEM,
    ]
    assert recs[0]["payload"] == {"edge_id": 3, "status": "TRAINING"}
    assert "cpu" in json.dumps(recs[3]["payload"]).lower() or recs[3]["payload"]


def test_mlops_round_callback_streams_engine_history(tmp_path):
    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.data.synthetic import gaussian_blobs
    from fedml_tpu.sim.engine import FedSim, SimConfig

    sink = tmp_path / "mlops.jsonl"
    logger = MLOpsLogger(FileMessenger(sink), run_id="run42")
    train, test = gaussian_blobs(n_clients=4, samples_per_client=20, num_classes=4, seed=0)
    tr = ClientTrainer(module=LogisticRegression(num_classes=4),
                       optimizer=optax.sgd(0.3), epochs=1)
    cfg = SimConfig(client_num_in_total=4, client_num_per_round=4,
                    batch_size=10, comm_round=2, frequency_of_the_test=2)
    FedSim(tr, train, test, cfg).run(callback=logger.round_callback())
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    metric_recs = [r for r in recs if r["topic"] == TOPIC_SERVER_METRICS]
    assert len(metric_recs) == 2
    assert metric_recs[0]["payload"]["run_id"] == "run42"
    assert "Train/Loss" in metric_recs[0]["payload"]


# -- packaging ---------------------------------------------------------------


def test_build_and_verify_mlops_package(tmp_path):
    zips = build_mlops_package(
        REPO_ROOT, tmp_path,
        run_config={"server_args": ["--comm_round", "1"]},
    )
    assert set(zips) == {"client", "server"}
    for role, zp in zips.items():
        assert zp.exists()
        with zipfile.ZipFile(zp) as z:
            names = z.namelist()
            assert "package/run.py" in names
            assert "package/fedml_config.json" in names
            assert any(n.startswith("package/fedml_tpu/sim/") for n in names)
            assert not any("__pycache__" in n for n in names)
        assert verify_package(zp, tmp_path / f"unpack_{role}")


# -- SyncBN ------------------------------------------------------------------


def test_syncbn_matches_pooled_stats():
    """Sharding the batch over the silo axis must produce the same batch
    statistics as the pooled batch on one device (the reference
    SynchronizedBatchNorm semantics)."""
    from jax.sharding import Mesh, PartitionSpec as P

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return SyncBatchNorm(use_running_average=False)(x)

    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    net = Net()
    variables = net.init(jax.random.key(0), jnp.asarray(x))

    # pooled single-device truth (axis unbound -> plain BatchNorm)
    pooled, _ = net.apply(variables, jnp.asarray(x), mutable=["batch_stats"])

    mesh = Mesh(np.array(jax.devices()[:4]), ("silo",))

    def sharded(v, xb):
        out, _ = net.apply(v, xb, mutable=["batch_stats"])
        return out

    out = jax.jit(
        jax.shard_map(
            sharded, mesh=mesh, in_specs=(P(), P("silo")), out_specs=P("silo"),
            check_vma=False,
        )
    )(variables, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(pooled), rtol=1e-5, atol=1e-5)


# -- export ------------------------------------------------------------------


def test_flat_list_roundtrip():
    model = LogisticRegression(num_classes=5)
    v = model.init(jax.random.key(0), jnp.ones((2, 12)))
    flat = params_to_flat_list(v["params"])
    assert all(isinstance(a, np.ndarray) for a in flat)
    rebuilt = flat_list_to_params(flat, v["params"])
    for a, b in zip(jax.tree.leaves(rebuilt), jax.tree.leaves(v["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="not aligned"):
        flat_list_to_params(flat[:-1], v["params"])


def test_mobile_wire_format_matches_reference_roundtrip():
    """Interop with the reference's ``is_mobile`` JSON
    (fedavg/utils.py:7-16): our wire dict must survive json.dumps, convert
    through the reference's OWN ``transform_list_to_tensor`` logic (torch)
    byte-exactly, and come back through ``transform_tensor_to_list``'s
    output into identical parameters — same nesting, same ordering."""
    import torch

    from fedml_tpu.models.cnn import LeNet
    from fedml_tpu.models.export import (
        nested_lists_to_params,
        params_to_nested_lists,
    )

    model = LeNet(num_classes=10)
    v = model.init(jax.random.key(0), jnp.ones((1, 28, 28, 1)))
    params = jax.tree.map(np.asarray, v["params"])

    wire = params_to_nested_lists(params)
    # nesting depth of each value equals the array's ndim (the reference's
    # .tolist() contract), and key order is deterministic
    flat = params_to_flat_list(params)
    for arr, (key, val) in zip(flat, wire.items()):
        depth, probe = 0, val
        while isinstance(probe, list):
            depth, probe = depth + 1, probe[0]
        assert depth == arr.ndim, key

    # through real JSON, then the reference's transform_list_to_tensor
    # verbatim (utils.py:7-10): torch.from_numpy(np.asarray(v)).float()
    decoded = json.loads(json.dumps(wire))
    as_tensors = {
        k: torch.from_numpy(np.asarray(p)).float() for k, p in decoded.items()
    }
    for arr, (key, t) in zip(flat, as_tensors.items()):
        np.testing.assert_array_equal(t.numpy(), arr, err_msg=key)

    # and the reference's transform_tensor_to_list output (utils.py:13-16)
    # rebuilds our params exactly
    back_wire = {k: t.detach().numpy().tolist() for k, t in as_tensors.items()}
    rebuilt = nested_lists_to_params(back_wire, params)
    for a, b in zip(jax.tree.leaves(rebuilt), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # missing / misshapen parameters fail loudly, like the reference's
    # aligned-layer assumption
    with pytest.raises(ValueError, match="missing"):
        nested_lists_to_params({}, params)


def test_stablehlo_export_roundtrip(tmp_path):
    model = LogisticRegression(num_classes=3)
    x = jnp.ones((2, 8))
    v = model.init(jax.random.key(0), x)

    def fwd(variables, xin):
        return model.apply(variables, xin)

    path = tmp_path / "model.stablehlo"
    export_stablehlo(fwd, (v, x), path)
    loaded = load_stablehlo(path)
    out = loaded.call(v, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fwd(v, x)), rtol=1e-6)


def test_fed_events_span(tmp_path):
    """FedEvents publishes the reference /mlops/events payloads
    (FedEventSDK.py:70-81): started_time on start, ended_time on end."""
    import json

    from fedml_tpu.obs.mlops import FedEvents, FileMessenger

    sink = tmp_path / "events.jsonl"
    ev = FedEvents(FileMessenger(sink), run_id="r1", edge_id=2)
    with ev.span("aggregate", event_value="round3"):
        pass
    ev.log_event_started("train", event_edge_id=7)

    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    assert [r["topic"] for r in recs] == ["/mlops/events"] * 3
    start, end, other = (r["payload"] for r in recs)
    assert start["event_name"] == "aggregate" and "started_time" in start
    assert end["event_name"] == "aggregate" and "ended_time" in end
    assert start["run_id"] == "r1" and start["edge_id"] == 2
    assert other["edge_id"] == 7  # explicit edge id override


def test_fed_logs_incremental_upload(tmp_path):
    """FedLogs ships only new lines on each call, batched at
    LOG_LINES_PER_UPLOAD with the reference upload keys (FedLogsSDK.py:102)."""
    import json

    from fedml_tpu.obs.mlops import FedLogs, FileMessenger

    log = tmp_path / "run.log"
    sink = tmp_path / "logs.jsonl"
    shipper = FedLogs(log, FileMessenger(sink), run_id=9, edge_id=1)

    assert shipper.upload_once() == 0  # file not there yet

    log.write_text("".join(f"line{i}\n" for i in range(250)))
    assert shipper.upload_once() == 250
    with log.open("a") as f:
        f.write("line250")  # partial line: held back until the newline lands
    assert shipper.upload_once() == 0
    with log.open("a") as f:
        f.write(" done\nline251\n")
    assert shipper.upload_once() == 2
    assert shipper.upload_once() == 0

    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    assert [len(r["payload"]["logs"]) for r in recs] == [100, 100, 50, 2]
    p = recs[0]["payload"]
    assert {"run_id", "edge_id", "logs", "create_time", "update_time",
            "created_by", "updated_by"} <= set(p)
    assert recs[-1]["payload"]["logs"] == ["line250 done\n", "line251\n"]

    # in-place truncation (copytruncate): restarts at byte 0, never goes quiet
    log.write_text("fresh\n")
    assert shipper.upload_once() == 1
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    assert recs[-1]["payload"]["logs"] == ["fresh\n"]

    # rotation to a NEW file that grows past the old offset before the next
    # call: the inode check catches it, nothing from the new file is dropped
    big = "".join(f"rotated{i}\n" for i in range(80))
    assert len(big) > shipper._offset
    log.rename(log.with_suffix(".1"))
    log.write_text(big)
    assert shipper.upload_once() == 80
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    assert recs[-1]["payload"]["logs"][0] == "rotated0\n"


def test_fed_logs_chunked_backlog(tmp_path):
    """A backlog larger than MAX_BYTES_PER_READ ships completely in bounded
    chunks, including lines straddling a chunk boundary."""
    import json

    from fedml_tpu.obs.mlops import FedLogs, FileMessenger

    log = tmp_path / "run.log"
    sink = tmp_path / "logs.jsonl"
    shipper = FedLogs(log, FileMessenger(sink), run_id=1, edge_id=0)
    shipper.MAX_BYTES_PER_READ = 64  # force many chunks
    lines = [f"entry-{i:04d}-padding-to-make-lines-long\n" for i in range(40)]
    log.write_text("".join(lines))
    assert shipper.upload_once() == 40
    recs = [json.loads(l) for l in sink.read_text().splitlines()]
    got = [ln for r in recs for ln in r["payload"]["logs"]]
    assert got == lines
