"""Sharded-model smoke: federated TransformerLM rounds with the client model
sharded across the mesh's ``model`` axis (``SimConfig.shard_rules``,
docs/PERFORMANCE.md "Sharded client models") vs the unsharded shard_map
program, asserting identical round metrics and bit-identical final
variables — the tier-1 guard that partition-rule model parallelism computes
the same round the single-chip program does.

Two arms run by default on XLA:CPU host devices:

- ``(2, 2)`` clients x model mesh with the ``transformer_fsdp`` rule set
  (gather-for-compute: sharded at rest, bit-exact math) vs the unsharded
  program on a 2-device client mesh (same client-axis extent, so cohort
  padding and rng slot chains line up).
- ``(1, 4)`` — the flagship big-model geometry (one client at a time,
  the whole mesh given to its model, ``cohort_execution="scan"``) vs the
  single-device program.

    JAX_PLATFORMS=cpu python tools/shard_smoke.py [--packed]

``--packed`` runs the packed-lane composition arms instead (docs/
PERFORMANCE.md "Packed lanes on sharded plans"): ``pack_lanes`` on the
(2, 2) fsdp mesh and on the (1, 4) single-client-shard geometry, each vs
the SAME ``pack_lanes`` on an unsharded client mesh of equal client-axis
extent — bit-identical variables and metrics, the tier-1 guard that
gather-plan sharding composes with lane packing without touching the
model math. (Packed vs padded on one mesh is pack_smoke's separate
contract and carries its own transformer fusion caveat, so the packed
arms pin against packed twins, not padded ones.) Tier-1 runs this arm
in-process (tests/test_shard_parallel.py).
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # standalone runs need >= 4 host devices; under pytest the conftest
    # already forced 8 before jax initialized
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROUNDS = 3


def _build(seed: int = 0):
    import numpy as np

    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays

    V, T, D, H, L = 32, 8, 16, 2, 2
    C, n_per = 4, 16
    rng = np.random.RandomState(seed)
    n = C * n_per
    x = rng.randint(0, V, (n, T)).astype(np.int32)
    y = rng.randint(0, V, (n, T)).astype(np.int32)
    mask = np.ones((n, T), np.float32)
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(C)}
    train = FederatedArrays({"x": x, "y": y, "mask": mask}, part)
    test = {"x": x[:8], "y": y[:8], "mask": mask[:8]}
    trainer = ClientTrainer(
        module=TransformerLM(vocab_size=V, embed_dim=D, num_layers=L,
                             num_heads=H, max_len=T),
        task="nwp",
        optimizer=optax.sgd(0.1, momentum=0.9),
        epochs=2,
    )
    return trainer, train, test


def _assert_same(label, sharded, unsharded):
    import numpy as np

    import jax

    (v_s, h_s), (v_u, h_u) = sharded, unsharded
    for a, b in zip(jax.tree.leaves(v_s), jax.tree.leaves(v_u)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{label}: sharded variables diverged from unsharded",
        )
    assert len(h_s) == len(h_u) == ROUNDS, (label, len(h_s), len(h_u))
    for rec_s, rec_u in zip(h_s, h_u):
        assert set(rec_s) == set(rec_u), (
            f"{label} round {rec_u['round']}: key sets differ "
            f"({sorted(rec_s)} vs {sorted(rec_u)})"
        )
        for key, val in rec_u.items():
            if key == "round_time":  # wall-clock, legitimately differs
                continue
            assert rec_s[key] == val, (
                f"{label} round {rec_u['round']}: {key} "
                f"sharded={rec_s.get(key)!r} unsharded={val!r}"
            )


def main(argv=None) -> int:
    import dataclasses
    import json

    import jax

    from fedml_tpu.core.compile_cache import configure_compile_cache
    from fedml_tpu.parallel.mesh import client_mesh
    from fedml_tpu.sim.engine import FedSim, SimConfig

    configure_compile_cache()

    packed = bool(argv) and "--packed" in argv
    devices = jax.devices()
    if len(devices) < 4:
        print(json.dumps({
            "shard_smoke": "skipped",
            "reason": f"needs >= 4 devices, have {len(devices)}",
        }))
        return 0

    trainer, train, test = _build()
    cfg = SimConfig(
        client_num_in_total=4, client_num_per_round=4, batch_size=4,
        comm_round=ROUNDS, epochs=2, frequency_of_the_test=2,
        straggler_frac=0.5, seed=0,
    )

    def run(c, mesh=None):
        sim = FedSim(trainer, train, test, c, mesh=mesh)
        return sim.run(), sim

    if packed:
        # Packed-lane composition arms: pack_lanes on a sharded plan vs the
        # SAME pack_lanes on an unsharded client mesh of equal client-axis
        # extent — the acceptance contract is packed-sharded == unsharded
        # packed, bit for bit (gather plans; the padded-vs-packed relation
        # is pack_smoke's separate contract and carries its own transformer
        # fusion caveat).
        pack_cfg = dataclasses.replace(cfg, pack_lanes=2)
        res_p, sim_p = run(dataclasses.replace(
            pack_cfg, mesh_shape=(2, 2), shard_rules="transformer_fsdp"
        ))
        assert sim_p._pack and sim_p._spmd, "packed arm must compose"
        assert sim_p.shard_summary()["mode"] == "pjit", sim_p.shard_summary()
        res_pu, _ = run(pack_cfg, mesh=client_mesh(devices[:2]))
        _assert_same("packed 2x2 fsdp", res_p, res_pu)

        # the flagship geometry with lanes: one client shard, the whole
        # model axis to each lane step, vs the 1-device packed program
        res_p2, _ = run(dataclasses.replace(
            pack_cfg, mesh_shape=(1, 4), shard_rules="transformer_fsdp"
        ))
        res_pu2, _ = run(pack_cfg, mesh=client_mesh(devices[:1]))
        _assert_same("packed 1x4 fsdp", res_p2, res_pu2)
        metric_keys = sorted(k for k in res_pu[1][-1] if k != "round_time")
        print(
            f"shard smoke --packed OK: {ROUNDS} rounds, packed-sharded == "
            f"packed-unsharded on {metric_keys} and final variables "
            "(2x2 fsdp + 1x4 arms)"
        )
        return 0

    # arm 1: 2x2 clients x model, FSDP-gather rules, vs 2-client-shard
    # unsharded (same client-axis extent -> same padding and rng chains)
    shard_cfg = dataclasses.replace(
        cfg, mesh_shape=(2, 2), shard_rules="transformer_fsdp"
    )
    res_s, sim_s = run(shard_cfg)
    res_u, _ = run(cfg, mesh=client_mesh(devices[:2]))
    assert sim_s.shard_summary()["mode"] == "pjit", sim_s.shard_summary()
    _assert_same("2x2 fsdp", res_s, res_u)

    # arm 2: the flagship geometry — one client at a time (scan cohort),
    # the whole 1x4 mesh given to its model — vs the 1-device program
    scan_cfg = dataclasses.replace(cfg, cohort_execution="scan")
    res_s2, _ = run(dataclasses.replace(
        scan_cfg, mesh_shape=(1, 4), shard_rules="transformer_fsdp"
    ))
    res_u2, _ = run(scan_cfg, mesh=client_mesh(devices[:1]))
    _assert_same("1x4 scan fsdp", res_s2, res_u2)

    metric_keys = sorted(k for k in res_u[1][-1] if k != "round_time")
    print(
        f"shard smoke OK: {ROUNDS} rounds, sharded == unsharded on "
        f"{metric_keys} and final variables (2x2 fsdp + 1x4 scan arms)"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
