"""Packed-lane cohort execution (SimConfig.pack_lanes) must follow the
padded path — same cohorts, same rng chains, same update stack, same
metrics — across mesh shapes, staging paths, uniform and power-law
partitions, straggler budgets, overflow passes, and update compression:
bit for bit, except on an 8-way client mesh with unequal lanes, where the
two programs agree within float32 rounding (``UNEQUAL_LANES_ULPS``).
Also covers the host-side bin-packing planner against its invariants."""

import dataclasses

import numpy as np
import pytest

import jax
import optax

from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.parallel import mesh as meshlib
from fedml_tpu.sim.cohort import (
    FederatedArrays,
    executed_steps,
    pack_cohort,
    pack_index_map,
)
from fedml_tpu.sim.engine import FedSim, PackedStaged, SimConfig


def _fixture(sizes, num_classes=4, dim=12, seed=3):
    """Federated blobs with EXPLICIT per-client sizes — power-law skew is the
    packed path's raison d'etre, so the fixture controls it directly."""
    rng = np.random.RandomState(seed)
    n = int(sum(sizes))
    centers = rng.normal(0.0, 2.0, (num_classes, dim))
    y = rng.randint(0, num_classes, n).astype(np.int32)
    x = (centers[y] + rng.normal(0.0, 0.6, (n, dim))).astype(np.float32)
    bounds = np.cumsum([0] + list(sizes))
    part = {i: np.arange(bounds[i], bounds[i + 1]) for i in range(len(sizes))}
    test = {"x": x[: 4 * num_classes], "y": y[: 4 * num_classes]}
    return FederatedArrays({"x": x, "y": y}, part), test


UNIFORM = [33] * 6
POWERLAW = [97, 41, 24, 12, 9, 6]  # head holds ~8x the median


def _trainer(epochs=2):
    return ClientTrainer(
        module=LogisticRegression(num_classes=4),
        optimizer=optax.sgd(0.2),
        epochs=epochs,
    )


# Packed and padded are two programs. On an 8-way client mesh with unequal
# lanes (the power-law partition) XLA fuses the update differently in each,
# so parameters and the metrics computed from them differ in the last bit:
# measured 1 ULP (max relative difference 1.5e-7, 2 of 4 elements of one
# leaf). One device, and uniform cohorts on eight, hold exact equality.
UNEQUAL_LANES_ULPS = 4
F32_ULP = float(np.finfo(np.float32).eps)  # 2**-23, one ULP at 1.0


def _run_pair(sizes, mesh_n, pack_kwargs, ulps=0, **cfg_kwargs):
    """Run padded and packed and compare. ``ulps=0`` (every caller but the
    two named above) demands bit equality of the variables and of every
    metric but ``Train/Loss``; ``ulps=n`` lets float values differ by
    ``n`` float32 ULP (relative, with one ULP of the leaf's largest
    magnitude as the absolute floor for elements near zero). Integer and
    count metrics stay exact either way."""
    train, test = _fixture(sizes)
    kwargs = dict(
        client_num_in_total=len(sizes), client_num_per_round=4, batch_size=8,
        comm_round=4, epochs=2, frequency_of_the_test=2, seed=0,
    )
    kwargs.update(cfg_kwargs)
    cfg = SimConfig(**kwargs)
    mesh = meshlib.client_mesh(jax.devices()[:mesh_n])
    trainer = _trainer()
    v_pad, h_pad = FedSim(trainer, train, test, cfg, mesh=mesh).run()
    sim_pack = FedSim(
        trainer, train, test, dataclasses.replace(cfg, **pack_kwargs),
        mesh=mesh,
    )
    v_pack, h_pack = sim_pack.run()
    for a, b in zip(jax.tree.leaves(v_pad), jax.tree.leaves(v_pack)):
        a, b = np.asarray(a), np.asarray(b)
        if ulps and np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(
                b, a, rtol=ulps * F32_ULP,
                atol=F32_ULP * float(np.abs(a).max()))
        else:
            np.testing.assert_array_equal(a, b)
    assert len(h_pad) == len(h_pack)
    for rec_d, rec_k in zip(h_pad, h_pack):
        # identical key sets AND identical values — a packed-only metric key
        # would silently fork the record schema
        assert set(rec_d) == set(rec_k), (rec_d, rec_k)
        for key, val in rec_d.items():
            if key == "round_time":  # wall-clock, legitimately differs
                continue
            if key == "Train/Loss":
                # The per-step loss PRIMAL is a pure observability scalar
                # (gradients never consume it), and its [B]-reduce sits in
                # two differently-fused XLA programs — reduce association is
                # fusion luck, so this one scalar can drift by ~1 ULP (the
                # splitnn stepwise oracle tolerates the same phenomenon).
                # Everything that feeds training — variables, weights,
                # Comm/* bytes, Test/* metrics — is asserted bit-exact.
                np.testing.assert_allclose(rec_k[key], val, rtol=1e-6,
                                           atol=1e-9)
                continue
            if ulps and isinstance(val, float):
                np.testing.assert_allclose(rec_k[key], val,
                                           rtol=ulps * F32_ULP, err_msg=key)
                continue
            assert rec_k[key] == val, (key, rec_d, rec_k)
    return sim_pack


@pytest.mark.parametrize("n_mesh_devices", [1, 8])
@pytest.mark.parametrize("sizes", [UNIFORM, POWERLAW],
                         ids=["uniform", "powerlaw"])
def test_packed_bit_identical_to_padded(n_mesh_devices, sizes):
    """The tentpole property: packed trajectories == padded trajectories,
    on ≥2 mesh shapes, on uniform AND power-law partitions, with straggler
    budgets in play (the heterogeneity the packing must respect). Bit for
    bit in three cases; within ``UNEQUAL_LANES_ULPS`` on power-law-8."""
    unequal_lanes = n_mesh_devices == 8 and sizes is POWERLAW
    _run_pair(sizes, n_mesh_devices, {"pack_lanes": 2},
              ulps=UNEQUAL_LANES_ULPS if unequal_lanes else 0,
              straggler_frac=0.5)


def test_packed_bit_identical_host_staged():
    """Host-staged datasets ship gathered [L, S_lane, B, ...] lane stacks
    instead of index maps — same trajectory either way (power-law on an
    8-way mesh: within ``UNEQUAL_LANES_ULPS``)."""
    _run_pair(POWERLAW, 8, {"pack_lanes": 2}, ulps=UNEQUAL_LANES_ULPS,
              stage_on_device=False)


def test_packed_overflow_pass_bit_identical():
    """A capacity factor far too small forces multi-pass rounds (lane
    overflow spills to an extra sequential dispatch of the same program);
    trajectories must not notice."""
    sim = _run_pair(
        POWERLAW, 1, {"pack_lanes": 1, "pack_capacity_factor": 0.01}
    )
    from fedml_tpu.core import rng as rnglib

    staged = sim._stage_packed_round(
        np.asarray([0, 1, 2, 3]), 0,
        rnglib.round_key(rnglib.root_key(0), 0),
    )
    assert isinstance(staged, PackedStaged)
    assert staged.stats["n_passes"] > 1  # the overflow actually happened


def test_packed_with_compression_bit_identical():
    """The packed path feeds the SAME [C_pad, ...] update stack to the
    compressed aggregator (codec + error feedback), so Comm/* metrics and
    the trajectory stay bit-identical."""
    _run_pair(
        POWERLAW, 2, {"pack_lanes": 2},
        client_num_per_round=6, compressor="q8",
    )


def test_packed_pipelined_prefetch_stages_lane_plans():
    """pack_lanes composes with the pipelined driver: the prefetch thread
    builds PackedStaged payloads ahead and the run stays bit-identical to
    the packed serial driver."""
    train, test = _fixture(POWERLAW)
    cfg = SimConfig(
        client_num_in_total=6, client_num_per_round=4, batch_size=8,
        comm_round=4, epochs=2, frequency_of_the_test=2, seed=0,
        pack_lanes=2,
    )
    trainer = _trainer()
    v_pipe, h_pipe = FedSim(
        trainer, train, test, dataclasses.replace(cfg, pipeline_depth=2)
    ).run()
    v_ser, h_ser = FedSim(
        trainer, train, test, dataclasses.replace(cfg, pipeline_depth=0)
    ).run()
    for a, b in zip(jax.tree.leaves(v_pipe), jax.tree.leaves(v_ser)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [
        {k: v for k, v in r.items() if k != "round_time"} for r in h_pipe
    ] == [
        {k: v for k, v in r.items() if k != "round_time"} for r in h_ser
    ]


# -- planner unit tests ------------------------------------------------------


def _plan_placements(plan):
    """{(slot, gidx): count} over every pass/lane step of a plan."""
    placed: dict = {}
    for pp in plan.passes:
        for lane in range(pp.slot.shape[0]):
            for t in range(pp.slot.shape[1]):
                s = int(pp.slot[lane, t])
                if s >= 0:
                    key = (s, int(pp.gidx[lane, t]))
                    placed[key] = placed.get(key, 0) + 1
    return placed


def test_pack_cohort_places_every_step_exactly_once():
    num_steps = np.asarray([8, 6, 0, 3, 8, 1], np.int64)  # budgets (e_i * S)
    data_steps = np.asarray([4, 2, 3, 4, 1, 1], np.int64)
    S, E = 4, 2
    plan = pack_cohort(num_steps, data_steps, S, E, lanes_per_shard=2,
                       s_lane=8, n_shards=1)
    per_epoch = executed_steps(num_steps, data_steps, S, E)
    expect = {
        (c, e * S + s)
        for c in range(6)
        for e in range(E)
        for s in range(int(per_epoch[c, e]))
    }
    placed = _plan_placements(plan)
    assert set(placed) == expect
    assert all(v == 1 for v in placed.values())  # exactly once
    assert plan.total_steps == len(expect)
    # lane capacity respected in every pass
    for pp in plan.passes:
        assert ((pp.slot >= 0).sum(axis=1) <= plan.s_lane).all()
    # exactly one boundary per placed client, on its last executed step
    for c in np.unique([c for c, _ in expect]):
        t_c = int(per_epoch[c].sum())
        last_g = max(g for cc, g in expect if cc == c)
        hits = [
            (int(pp.gidx[lane, t]))
            for pp in plan.passes
            for lane in range(pp.slot.shape[0])
            for t in range(pp.slot.shape[1])
            if pp.slot[lane, t] == c and pp.boundary[lane, t]
        ]
        assert hits == [last_g], (c, t_c, hits)


def test_pack_cohort_overflow_spills_to_extra_pass():
    # 3 clients x 4 steps into ONE 4-step lane -> must take 3 passes
    plan = pack_cohort(
        np.asarray([4, 4, 4]), np.asarray([4, 4, 4]), 4, 1,
        lanes_per_shard=1, s_lane=4, n_shards=1,
    )
    assert len(plan.passes) == 3
    placed = _plan_placements(plan)
    assert len(placed) == 12 and all(v == 1 for v in placed.values())
    # a client that can NEVER fit fails loudly at plan time
    with pytest.raises(ValueError, match="lane"):
        pack_cohort(np.asarray([8]), np.asarray([8]), 8, 1,
                    lanes_per_shard=1, s_lane=4, n_shards=1)


def test_pack_cohort_respects_shard_blocks():
    """Per-shard packing: a shard's lanes carry only its own slot block (the
    device-locality invariant the engine's all_gather combine relies on)."""
    plan = pack_cohort(
        np.full(8, 4), np.full(8, 2), 4, 1,
        lanes_per_shard=2, s_lane=8, n_shards=4,
    )
    for pp in plan.passes:
        for lane in range(pp.slot.shape[0]):
            shard = lane // 2
            slots = pp.slot[lane][pp.slot[lane] >= 0]
            assert ((slots // 2) == shard).all(), (lane, slots)


def test_pack_index_map_gathers_padded_rows():
    train, _ = _fixture(POWERLAW)
    from fedml_tpu.sim.cohort import cohort_index_map

    idx, _ = cohort_index_map(train, np.asarray([0, 3, 5]), 8)
    plan = pack_cohort(
        np.asarray([idx.shape[1]] * 3),
        np.asarray([(idx[c] >= 0).any(axis=-1).sum() for c in range(3)]),
        idx.shape[1], 1, lanes_per_shard=2, s_lane=idx.shape[1] * 2,
        n_shards=1,
    )
    packed = pack_index_map(idx, plan.passes[0])
    pp = plan.passes[0]
    for lane in range(packed.shape[0]):
        for t in range(packed.shape[1]):
            if pp.slot[lane, t] >= 0:
                np.testing.assert_array_equal(
                    packed[lane, t], idx[pp.slot[lane, t], pp.sidx[lane, t]]
                )
            else:
                assert (packed[lane, t] == -1).all()


def test_pack_lanes_config_validation():
    # one error per conflict, each leading with the SimConfig field (or
    # constructor argument) the user has to change
    train, test = _fixture(UNIFORM)
    base = dict(client_num_in_total=6, client_num_per_round=4, batch_size=8)
    with pytest.raises(
        ValueError,
        match=r"SimConfig\.cohort_execution='scan' conflicts with pack_lanes=2",
    ):
        FedSim(_trainer(), train, test,
               SimConfig(pack_lanes=2, cohort_execution="scan", **base))
    with pytest.raises(
        ValueError,
        match=r"SimConfig\.block_dispatch=True conflicts with pack_lanes=2",
    ):
        FedSim(_trainer(), train, test,
               SimConfig(pack_lanes=2, block_dispatch=True, **base))
    with pytest.raises(
        ValueError, match=r"local_train_fn conflicts with pack_lanes=2",
    ):
        FedSim(_trainer(), train, test, SimConfig(pack_lanes=2, **base),
               local_train_fn=lambda *a: None)

    from fedml_tpu.algorithms.decentralized import gossip_aggregator
    from fedml_tpu.topology.topology import ring_topology

    with pytest.raises(
        ValueError,
        match=r"aggregator='.*' \(per-client\) conflicts with pack_lanes=2",
    ):
        # full participation: the per-client aggregator's own precondition
        FedSim(_trainer(), train, test,
               SimConfig(pack_lanes=2, client_num_in_total=6,
                         client_num_per_round=6, batch_size=8),
               aggregator=gossip_aggregator(ring_topology(6)))


def test_pack_smoke_tool_runs():
    """tools/pack_smoke.py is the tier-1 guard the docs point at — run it
    in-process (mirrors the pipeline smoke's wiring)."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).parent.parent / "tools" / "pack_smoke.py"
    spec = importlib.util.spec_from_file_location("pack_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0
