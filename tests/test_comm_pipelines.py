"""SplitNN / VFL / FedGKT over the comm layer: bit-equality oracles.

The reference runs these three pipelines as separate processes by
construction (split_nn/client.py:24-34 + server.py:40-60,
classical_vertical_fl/guest_manager.py:6 + host_manager.py:6,
fedgkt/GKTServerManager.py:8). Here each wire path shares its per-step /
per-phase jitted programs with an in-process oracle, so the loopback run
must be BIT-identical to it — and the oracle must match the single-program
simulation path (the same discipline as multihost and is_mobile).
"""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from fedml_tpu.algorithms.fedgkt import FedGKT, run_fedgkt
from fedml_tpu.algorithms.fedgkt_dist import run_distributed_fedgkt_loopback
from fedml_tpu.algorithms.splitnn import SplitNN, run_splitnn_relay
from fedml_tpu.algorithms.splitnn_dist import (
    run_distributed_splitnn,
    run_distributed_splitnn_loopback,
    run_splitnn_relay_stepwise,
)
from fedml_tpu.algorithms.vertical import PartyModel, VerticalFL, run_vfl
from fedml_tpu.algorithms.vertical_dist import (
    run_distributed_vfl_loopback,
    run_vfl_stepwise,
)
from fedml_tpu.data.synthetic import gaussian_blobs
from fedml_tpu.models.resnet_gkt import ResNetGKTClient, ResNetGKTServer
from fedml_tpu.sim.cohort import stack_cohort


def assert_trees_equal(a, b, what=""):
    mismatches = []

    def chk(path, x, y):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            mismatches.append(path)

    jax.tree_util.tree_map_with_path(
        lambda p, x, y: chk(jax.tree_util.keystr(p), x, y), a, b
    )
    assert not mismatches, f"{what}: leaves differ at {mismatches[:5]}"


class _Bottom(nn.Module):
    hidden: int = 12

    @nn.compact
    def __call__(self, x, train: bool = False):
        return nn.relu(nn.Dense(self.hidden)(x.astype(jnp.float32)))


class _Top(nn.Module):
    classes: int = 4

    @nn.compact
    def __call__(self, acts, train: bool = False):
        return nn.Dense(self.classes)(acts)


def _split_setup(n_clients=3, batch=10):
    train, _ = gaussian_blobs(
        n_clients=n_clients, samples_per_client=4 * batch, num_classes=4, seed=0
    )
    split = SplitNN(_Bottom(), _Top(), optax.sgd(0.2), optax.sgd(0.2))
    cb = []
    for c in range(n_clients):
        stack, _ = stack_cohort(train, np.asarray([c]), batch_size=batch)
        cb.append(jax.tree.map(lambda v: jnp.asarray(v[0]), stack))
    return split, cb


def test_splitnn_stepwise_matches_single_program():
    """The decomposed per-step programs reproduce the jitted scan exactly."""
    split, cb = _split_setup()
    cv1, sv1, l1 = run_splitnn_relay(split, cb, epochs=2, rng=jax.random.key(0))
    cv2, sv2, l2 = run_splitnn_relay_stepwise(split, cb, epochs=2, rng=jax.random.key(0))
    assert_trees_equal(sv1, sv2, "server vars")
    assert_trees_equal(cv1, cv2, "client vars")
    # variables ARE bit-equal (asserted above), but the reported per-step
    # losses cross a jitted-scan vs per-step-program boundary where XLA:CPU
    # fuses the loss reduction differently — ULP-level drift on some
    # containers. rtol 1e-6 ~ a few f32 ULPs at these magnitudes; anything
    # real (wrong step order, stale activations) is orders larger.
    np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-7)


def test_splitnn_loopback_matches_stepwise():
    """Activations/grads as wire payloads change nothing: bit-identical."""
    split, cb = _split_setup()
    cv1, sv1, l1 = run_splitnn_relay_stepwise(split, cb, epochs=2, rng=jax.random.key(0))
    cv2, sv2, l2 = run_distributed_splitnn_loopback(split, cb, epochs=2, rng=jax.random.key(0))
    assert_trees_equal(sv1, sv2, "server vars")
    assert_trees_equal(cv1, cv2, "client vars")
    assert l1 == l2


def test_splitnn_over_shm_ring():
    """The relay crosses the native C++ shared-memory transport (the real
    process-boundary-capable ring) bit-identically."""
    import uuid

    from fedml_tpu.comm.shm import ShmCommManager

    split, cb = _split_setup(n_clients=2)
    cv1, sv1, l1 = run_splitnn_relay_stepwise(split, cb, epochs=1, rng=jax.random.key(0))
    job = f"splitnn_{uuid.uuid4().hex[:8]}"
    mgrs = {r: ShmCommManager(job, r, len(cb) + 1) for r in range(len(cb) + 1)}
    try:
        cv2, sv2, l2 = run_distributed_splitnn(
            split, cb, epochs=1, rng=jax.random.key(0), make_comm=lambda r: mgrs[r]
        )
    finally:
        for m in mgrs.values():
            m.cleanup()
    assert_trees_equal(sv1, sv2, "server vars")
    assert_trees_equal(cv1, cv2, "client vars")
    assert l1 == l2


def test_splitnn_real_processes(tmp_path):
    """The reference's ACTUAL process model: each client is a separate OS
    process (split_nn/client.py), here joined to the parent's server over
    the native C++ shm ring — bit-identical to the in-process oracle."""
    import os
    import subprocess
    import sys
    import uuid

    from fedml_tpu.algorithms.splitnn_dist import SplitNNServerManager
    from fedml_tpu.comm.shm import ShmCommManager

    split, cb = _split_setup(n_clients=2)
    cv1, sv1, l1 = run_splitnn_relay_stepwise(split, cb, epochs=1, rng=jax.random.key(0))

    job = f"sp_{uuid.uuid4().hex[:8]}"
    workers = []
    worker_path = __import__("pathlib").Path(__file__).parent / "_splitnn_worker.py"
    worker_src = str(worker_path)
    # worker scripts get sys.path[0] = tests/, not the repo root (same
    # forwarding as tests/test_multihost.py _run_procs)
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(worker_path.parent.parent) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    # the workers share this suite's compile cache, under the same string
    env["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    for r, batches in enumerate(cb, start=1):
        npz = tmp_path / f"client{r}.npz"
        np.savez(npz, **{k: np.asarray(v) for k, v in batches.items()})
        workers.append(subprocess.Popen(
            [sys.executable, worker_src, job, str(r), str(len(cb) + 1), str(npz)],
            env=env,
        ))

    # server in THIS process (mirrors run_distributed_splitnn's setup)
    sample_x = jax.tree.map(lambda v: v[0], cb[0])["x"]
    cvars0, svars = split.init(jax.random.key(0), sample_x)
    comm = ShmCommManager(job, 0, len(cb) + 1)
    server = SplitNNServerManager(
        comm, split, len(cb), 1, jax.random.key(0), cvars0, svars
    )
    import threading

    protocol_done = threading.Event()

    def watchdog():
        # a child that dies before FINAL_VARS would leave the server's
        # receive loop waiting forever — break it so the test FAILS (on the
        # final_cvars count) instead of hanging the suite
        while not protocol_done.wait(1.0):
            if any(w.poll() is not None and w.returncode != 0 for w in workers):
                server.finish()
                return

    guard = threading.Thread(target=watchdog, daemon=True)
    guard.start()
    try:
        server.register_message_receive_handlers()
        server.send_init_msg()
        server.comm.handle_receive_message()  # until all FINAL_VARS arrive
        protocol_done.set()
        assert len(server.final_cvars) == len(cb), "a worker died mid-protocol"
        for w in workers:
            assert w.wait(timeout=120) == 0
    finally:
        protocol_done.set()
        for w in workers:
            if w.poll() is None:
                w.kill()
        comm.cleanup()

    cv2 = [jax.tree.map(jnp.asarray, server.final_cvars[r])
           for r in range(1, len(cb) + 1)]
    assert_trees_equal(sv1, server.svars, "server vars")
    assert_trees_equal(cv1, cv2, "client vars")
    assert l1 == server.losses


def test_splitnn_over_grpc():
    """The relay crosses real localhost gRPC sockets (the cross-host
    transport) bit-identically — per-step activations/grads survive actual
    network serialization."""
    import socket

    pytest.importorskip("grpc")
    from fedml_tpu.comm.grpc_backend import GRPCCommManager

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    split, cb = _split_setup(n_clients=2)
    cv1, sv1, l1 = run_splitnn_relay_stepwise(split, cb, epochs=1, rng=jax.random.key(0))
    # manager construction inside the try: a lost bind race (free_port's
    # close-then-rebind window) must still stop the managers already built
    mgrs = {}
    try:
        for attempt in range(3):  # retry the whole set on a bind race
            try:
                cfg = {r: ("127.0.0.1", free_port()) for r in range(len(cb) + 1)}
                for r in range(len(cb) + 1):
                    mgrs[r] = GRPCCommManager(r, cfg)
                break
            except OSError:
                for m in mgrs.values():
                    m.stop_receive_message()
                mgrs = {}
                if attempt == 2:
                    raise
        cv2, sv2, l2 = run_distributed_splitnn(
            split, cb, epochs=1, rng=jax.random.key(0), make_comm=lambda r: mgrs[r]
        )
    finally:
        for m in mgrs.values():
            m.stop_receive_message()
    assert_trees_equal(sv1, sv2, "server vars")
    assert_trees_equal(cv1, cv2, "client vars")
    assert l1 == l2


def _vfl_setup(n_parties=3):
    rng = np.random.RandomState(0)
    n, d = 200, 20
    x = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d)
    y = (x @ w > 0).astype(np.int32)
    cuts = np.linspace(0, d, n_parties + 1).astype(int)
    fs = [jnp.asarray(x[:, cuts[i]:cuts[i + 1]]) for i in range(n_parties)]
    vfl = VerticalFL([PartyModel(hidden=16) for _ in fs], optax.sgd(0.3))
    return vfl, fs, jnp.asarray(y)


def test_vfl_stepwise_matches_single_program():
    vfl, fs, y = _vfl_setup()
    _, pv1, l1 = run_vfl(fs, y, epochs=2, batch_size=40, lr=0.3)
    pv2, l2 = run_vfl_stepwise(vfl, fs, y, 2, 40, jax.random.key(0))
    assert_trees_equal(pv1, pv2, "party vars")
    assert l1 == l2


def test_vfl_loopback_matches_stepwise():
    vfl, fs, y = _vfl_setup()
    pv1, l1 = run_vfl_stepwise(vfl, fs, y, 2, 40, jax.random.key(0))
    pv2, l2 = run_distributed_vfl_loopback(vfl, fs, y, 2, 40, jax.random.key(0))
    assert_trees_equal(pv1, pv2, "party vars")
    assert l1 == l2


def test_vfl_stale_logits_resend_guard():
    """A stale H2G logits message re-announces the current step to that host
    (a non-FIFO transport can reorder the announcement past the reply, which
    would deadlock if silently dropped) — but only while that host's
    current-step answer is outstanding; a late duplicate after it answered
    must be dropped, or each resend's extra reply arrives one step late and
    echoes another resend until the schedule ends."""
    from fedml_tpu.algorithms.vertical_dist import VFLGuestManager, VFLMsg
    from fedml_tpu.comm.message import Message

    class _RecordingComm:
        def __init__(self):
            self.sent = []

        def add_observer(self, obs):
            pass

        def send_message(self, msg):
            self.sent.append(msg)

    vfl, fs, y = _vfl_setup()
    comm = _RecordingComm()
    guest = VFLGuestManager(comm, vfl, vfl.init(jax.random.key(0), fs),
                            fs[0], y, batch_size=40, epochs=1)

    def h2g(host, step):
        msg = Message(VFLMsg.MSG_TYPE_H2G_LOGITS, host, 0)
        msg.add_params(VFLMsg.KEY_STEP, step)
        msg.add_params(VFLMsg.KEY_LOGITS, np.zeros((40, 2), np.float32))
        return msg

    # host 2's answer for the current step is outstanding: re-announce once
    guest._on_logits(h2g(2, guest.step + 5))
    assert len(comm.sent) == 1
    assert comm.sent[0].get_receiver_id() == 2
    assert int(comm.sent[0].get(VFLMsg.KEY_STEP)) == guest.step

    # after host 2 answers the current step, a late duplicate is dropped
    guest._on_logits(h2g(2, guest.step))
    guest._on_logits(h2g(2, guest.step + 5))
    assert len(comm.sent) == 1

    # a duplicate landing AFTER the step advanced (the echo tail a resend's
    # extra reply produces) is also dropped: host 2 acked this step already
    answered = guest.step
    guest.step += 1
    guest._step_logits = {}
    guest._on_logits(h2g(2, answered))
    assert len(comm.sent) == 1
    # ...while a never-accepted stale answer (host 1 lost the announcement)
    # still triggers the deadlock-breaking re-announce
    guest._on_logits(h2g(1, answered))
    assert len(comm.sent) == 2
    assert comm.sent[1].get_receiver_id() == 1
    assert int(comm.sent[1].get(VFLMsg.KEY_STEP)) == guest.step


def _gkt_setup(n_clients=2, S=2, B=8):
    train, _ = gaussian_blobs(
        n_clients=n_clients, samples_per_client=S * B, num_classes=4, seed=1
    )
    imgs = train.arrays["x"].reshape(-1, 4, 4, 1)
    gkt = FedGKT(
        ResNetGKTClient(num_classes=4, blocks=1),
        ResNetGKTServer(num_classes=4, blocks_per_stage=1),
        optax.sgd(0.05), optax.sgd(0.05), temperature=2.0,
    )
    cb = []
    for c in range(n_clients):
        lo = c * S * B
        cb.append({
            "x": jnp.asarray(imgs[lo:lo + S * B].reshape(S, B, 4, 4, 1)),
            "y": jnp.asarray(train.arrays["y"][lo:lo + S * B].reshape(S, B)),
            "mask": jnp.ones((S, B), jnp.float32),
        })
    return gkt, cb


def test_fedgkt_loopback_matches_inprocess():
    """Features/logits/labels as wire payloads, two rounds (so the server's
    fed-back logits cross the wire too): bit-identical to run_fedgkt."""
    gkt, cb = _gkt_setup()
    cv1, sv1, _ = run_fedgkt(
        gkt, cb, rounds=2, client_epochs=1, server_epochs=1, rng=jax.random.key(0)
    )
    cv2, sv2 = run_distributed_fedgkt_loopback(
        gkt, cb, rounds=2, client_epochs=1, server_epochs=1, rng=jax.random.key(0)
    )
    assert_trees_equal(sv1, sv2, "server vars")
    for a, b in zip(cv1, cv2):
        assert_trees_equal(a, b, "client vars")


@pytest.mark.slow  # 44 s cold (GKT ResNet XLA:CPU compiles); the loopback
# equality test above already runs the same orchestration
def test_fedgkt_inprocess_learns():
    """The orchestrated loop trains: loss-bearing sanity on the oracle."""
    gkt, cb = _gkt_setup()
    cv, sv, slog = run_fedgkt(
        gkt, cb, rounds=1, client_epochs=2, server_epochs=2, rng=jax.random.key(0)
    )
    for s in slog:
        assert np.isfinite(np.asarray(s)).all()
