"""The scan cohort's running mean (``sim/engine.py`` ``_cohort_mean``): where
the clients run in sequence and the rule declares that their weighted mean is
all it needs (``Aggregator.aggregate_mean``), the round program sums the mean
in the cohort loop's carry and holds no ``[C, ...]`` stack of models; every
other rule, and the ``vmap`` cohort, keep the stack. The arithmetic is
``tree_weighted_mean``'s."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.algorithms.base import Aggregator, fedavg_aggregator
from fedml_tpu.algorithms.fedopt import fedopt_aggregator
from fedml_tpu.algorithms.robust import RobustConfig, robust_aggregator
from fedml_tpu.core import rng as rnglib
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.core.tree import tree_stack, tree_weighted_mean
from fedml_tpu.data.synthetic import gaussian_blobs
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import trace
from fedml_tpu.parallel import mesh as meshlib
from fedml_tpu.sim.cohort import FederatedArrays
from fedml_tpu.sim.engine import FedSim, SimConfig

RULES = {
    "fedavg": fedavg_aggregator,
    "fedopt": lambda: fedopt_aggregator(optax.adam(0.05)),
    "robust": lambda: robust_aggregator(RobustConfig(rule="median")),
    # FedAvg without its declaration: handed the stack, as before there was one
    "fedavg_by_stack": lambda: dataclasses.replace(fedavg_aggregator(), aggregate_mean=None),
}


class MixedPrecision(nn.Module):
    """A bfloat16 kernel under a float32 head: the mean of a low-precision
    leaf is summed in float32 and cast once."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Dense(8, param_dtype=jnp.bfloat16, name="low")(x.astype(jnp.float32))
        return nn.Dense(4, name="head")(nn.relu(x).astype(jnp.float32))


def _sim(rule="fedavg", execution="scan", *, per_round=4, devices=1, module=None, total=6):
    """Clients of unequal sizes (34, 30, 18, 10, 18, 34 of 144 samples; 34 and
    14 where there are two)."""
    train, test = gaussian_blobs(n_clients=6, samples_per_client=24, num_classes=4,
                                 partition_method="hetero", partition_alpha=0.5, seed=3)
    if total == 2:
        train = FederatedArrays(train.arrays, {0: np.arange(34), 1: np.arange(34, 48)})
    trainer = ClientTrainer(module=module or LogisticRegression(num_classes=4),
                            optimizer=optax.sgd(0.2), epochs=2)
    cfg = SimConfig(client_num_in_total=total, client_num_per_round=per_round, batch_size=8,
                    comm_round=3, epochs=2, frequency_of_the_test=3, seed=0,
                    cohort_execution=execution, block_dispatch=False)
    return FedSim(trainer, train, test, cfg, aggregator=RULES[rule](),
                  mesh=meshlib.client_mesh(jax.devices()[:devices]))


def _round_args(sim):
    """The gather round's arguments; the running mean's round takes one more,
    a dead model's buffers to sum into (here: whatever a model's copy holds)."""
    variables = sim.init_round_variables()
    state = sim.aggregator.init_state(variables)
    staged = sim.stage_round(0, rnglib.root_key(sim.config.seed))
    spare = (jax.tree.map(lambda x: x + 1, variables),) if sim._mean_in_carry else ()
    return variables, state, sim._dataset, *staged, *spare


def _shapes(jaxpr):
    """Every array shape a jaxpr holds, its loops' and calls' bodies included."""
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            yield tuple(getattr(var.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes(sub)


@pytest.mark.parametrize("rule,execution,form", [
    ("fedavg", "scan", "carry"),
    ("fedopt", "scan", "carry"),
    ("robust", "scan", "stack"),
    ("fedavg", "vmap", "stack"),
])
def test_the_round_notes_its_form_and_the_carry_holds_no_stack(monkeypatch, rule, execution, form):
    monkeypatch.setattr(trace, "_program_notes", {})
    sim = _sim(rule, execution)
    args = _round_args(sim)
    jaxpr = sim._gather_round_fn.fn.trace(*args).jaxpr
    largest = max(jax.tree.leaves(args[0]), key=lambda x: x.size)
    model_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(args[0]))
    assert trace.program_notes(trace.COHORT_AGGREGATE_NOTE) == [
        {"form": form, "clients": 4, "bytes": 4 * model_bytes}]
    stacked = (4, *largest.shape) in set(_shapes(jaxpr.jaxpr))
    assert stacked == (form == "stack")
    (carry,) = [n for n in trace.program_notes(trace.LOOP_CARRY_NOTE)
                if n["loop"] == trace.SCOPE_LOOP_COHORT]
    # float32 leaves here, so the running sum's bytes are one model's
    assert (carry["bytes"], carry["side_by_side"]) == (
        model_bytes, 4 if execution == "vmap" else 1)


CASES = {
    "unequal_4_of_6": dict(),
    "padding_slot_of_weight_0": dict(per_round=3, devices=2),
    "bfloat16_leaf": dict(module=MixedPrecision()),
    "two_devices_two_clients_each": dict(devices=2),
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("rule", ["fedavg", "fedopt"])
def test_scan_carry_matches_vmap_after_three_rounds(rule, case):
    scan, vmap = (_sim(rule, execution, **CASES[case]) for execution in ("scan", "vmap"))
    assert scan._mean_in_carry and not vmap._mean_in_carry
    if case == "padding_slot_of_weight_0":
        weights = np.asarray(scan.stage_round(0, rnglib.root_key(0))[1])
        assert weights.shape == (4,) and weights[-1] == 0 and (weights[:3] > 0).all()
    scan_vars, scan_hist = scan.run()
    vmap_vars, vmap_hist = vmap.run()
    for a, b in zip(jax.tree.leaves(scan_vars), jax.tree.leaves(vmap_vars)):
        assert a.dtype == b.dtype
        low = a.dtype == jnp.bfloat16  # one rounding of the mean, to 8 bits
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2 ** -7 if low else 1e-6, atol=1e-7)
    assert scan_hist[-1].keys() == vmap_hist[-1].keys()
    np.testing.assert_allclose(scan_hist[-1]["Train/Loss"], vmap_hist[-1]["Train/Loss"],
                               rtol=1e-5)


def _two_client_round(sim, args=None):
    new_global, _, _ = sim._gather_round_fn.fn(*(args or _round_args(sim)))
    return new_global


def test_two_clients_mean_is_tree_weighted_mean_of_their_results_bit_for_bit():
    sim = _sim(per_round=2, total=2)
    args = variables, _, dataset, idx, weights, num_steps, rkey, _ = _round_args(sim)
    assert np.asarray(weights).tolist() == [34.0, 14.0]
    batches = sim._gather_batches(dataset, idx)
    results = [
        jax.jit(sim._local_train)(variables, jax.tree.map(lambda x: x[i], batches),
                                  jax.random.fold_in(rkey, i), num_steps[i])[0]
        for i in range(2)]
    # in one program, as the round is: XLA:CPU contracts a multiply-add
    # inside a program and not across eager calls
    want = jax.jit(tree_weighted_mean)(tree_stack(results), weights)
    new_global = _two_client_round(sim, args)
    for a, b in zip(jax.tree.leaves(new_global), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_two_clients_carry_equals_the_stack_form_bit_for_bit():
    """The same rule without its declaration is handed the stack (the parent's
    program): with two clients ``0 + w_0 a + w_1 b`` is the stacked reduce."""
    carry, stack = _sim(per_round=2, total=2), _sim("fedavg_by_stack", per_round=2, total=2)
    assert carry._mean_in_carry and not stack._mean_in_carry
    for a, b in zip(jax.tree.leaves(_two_client_round(carry)),
                    jax.tree.leaves(_two_client_round(stack))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("execution", ["scan", "vmap"])
def test_a_round_consumes_its_model_argument_either_way(execution):
    """The stack's round donates its model. The running mean's round cannot
    write the sum over a model its clients still start from: it donates the
    model of the round before and sums into that, so the caller's arrays are
    gone one call later and two models' buffers pass each other."""
    sim = _sim("fedavg", execution)
    carry = execution == "scan"
    assert sim._gather_round_fn.donate_argnums == ((7,) if carry else (0,))
    root = rnglib.root_key(sim.config.seed)
    first = sim.init_round_variables()
    state = sim.aggregator.init_state(first)
    where_first_was = {x.unsafe_buffer_pointer() for x in jax.tree.leaves(first)}
    second, state, _ = sim.run_staged_round(sim.stage_round(0, root), first, state)
    assert all(x.is_deleted() for x in jax.tree.leaves(first)) == (not carry)
    third, state, _ = sim.run_staged_round(sim.stage_round(1, root), second, state)
    for gone in (first, second):
        assert all(x.is_deleted() for x in jax.tree.leaves(gone)) == (gone is first or not carry)
    if carry:  # the third model lives where the first did
        assert {x.unsafe_buffer_pointer() for x in jax.tree.leaves(third)} == where_first_was
    assert np.isfinite(np.asarray(jax.tree.leaves(third)[0])).all()


def test_a_round_given_the_same_arrays_again_sums_into_new_zeros():
    sim = _sim("fedavg", "scan")
    root = rnglib.root_key(sim.config.seed)
    variables = sim.init_round_variables()
    state = sim.aggregator.init_state(variables)
    staged = sim.stage_round(0, root)
    once, _, _ = sim.run_staged_round(staged, variables, state)
    again, _, _ = sim.run_staged_round(staged, variables, state)  # its own spare
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rule", ["fedavg", "fedopt"])
def test_aggregate_of_a_stack_is_aggregate_mean_of_its_mean_bit_for_bit(rule):
    agg = RULES[rule]()
    keys = jax.random.split(jax.random.key(0), 3)
    variables = {"params": {"w": jax.random.normal(keys[0], (5, 3)),
                            "low": jax.random.normal(keys[1], (7,)).astype(jnp.bfloat16)}}
    stacked = jax.tree.map(
        lambda x: x[None] + jax.random.normal(keys[2], (4, *x.shape)).astype(x.dtype), variables)
    weights = jnp.asarray([24.0, 7.0, 0.0, 13.0])
    state = agg.init_state(variables)
    by_stack = agg.aggregate(variables, stacked, weights, state, keys[0], None)
    by_mean = agg.aggregate_mean(variables, tree_weighted_mean(stacked, weights), weights,
                                 state, keys[0], None)
    assert jax.tree.structure(by_stack) == jax.tree.structure(by_mean)
    for a, b in zip(jax.tree.leaves(by_stack), jax.tree.leaves(by_mean)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rule", ["robust", "fednova", "fednas", "gossip", "compressed"])
def test_rules_that_look_at_each_client_declare_nothing(rule):
    from fedml_tpu.algorithms.decentralized import gossip_aggregator
    from fedml_tpu.algorithms.fednas import fednas_aggregator
    from fedml_tpu.algorithms.fednova import fednova_aggregator
    from fedml_tpu.compress import make_codec
    from fedml_tpu.compress.aggregate import compressed_aggregator
    from fedml_tpu.topology.topology import ring_topology

    agg = {
        "robust": RULES["robust"],
        "fednova": lambda: fednova_aggregator(client_lr=0.1),
        "fednas": fednas_aggregator,
        "gossip": lambda: gossip_aggregator(ring_topology(4)),
        "compressed": lambda: compressed_aggregator(
            make_codec("q8"), inner=fedavg_aggregator(), error_feedback=False),
    }[rule]()
    assert isinstance(agg, Aggregator) and agg.aggregate_mean is None


# -- the table of device programs (``FedSim._build_programs``) ----------------
# One letter a spec. M: the model at rest (replicated; over the client axis
# where every client keeps its own; each leaf's own spec under shard rules).
# R: replicated. C: a cohort's array, over the client axis. V: a cohort's [C]
# vector (weights, budgets, losses), which a pjit program takes replicated.
# S: the clients' stack where it crosses from one program to the next (under
# shard rules replicated for a gather plan, clients x each leaf's own spec for
# a TP plan). L: a lane buffer beside the stack. B: a block's [R, C, ...] array.
ON_CPU = ()  # a pjit program donates only where the backend implements it
PROGRAM_PLANS = {
    "padded host": (dict(stage_on_device=False), {
        "_round_fn": ("MRCVVR", "MRR", (0,))}),
    "padded gathered": (dict(), {
        "_gather_round_fn": ("MRRCVVR", "MRR", (0,))}),
    "scan carry": (dict(cohort_execution="scan"), {
        "_gather_round_fn": ("MRRCVVRM", "MRR", (7,))}),
    "scan carry host": (dict(cohort_execution="scan", stage_on_device=False), {
        "_round_fn": ("MRCVVRM", "MRR", (6,))}),
    "per client": (dict(rule="gossip"), {
        "_gather_round_fn": ("MRRCVVR", "MRR", (0,))}),
    "resnet_fsdp": (dict(shard_rules="resnet_fsdp"), {
        "_spmd_gather_train_fn": ("MRCVR", "SV", ()),
        "_spmd_agg_fn": ("MRSVVVR", "MRR", ON_CPU)}),
    "resnet_fsdp host": (dict(shard_rules="resnet_fsdp", stage_on_device=False), {
        "_spmd_train_fn": ("MCVR", "SV", ()),
        "_spmd_agg_fn": ("MRSVVVR", "MRR", ON_CPU)}),
    "packed": (dict(pack_lanes=2), {
        "_packed_buf_fn": ("M", "SLLL", ()),
        "_packed_pass_fn": ("MRCCCCSLLLR", "SLLL", (6, 7, 8, 9)),
        "_packed_agg_fn": ("MRSLLLVVR", "MRR", (2, 3, 4, 5))}),
    "packed host": (dict(pack_lanes=2, stage_on_device=False), {
        "_packed_buf_fn": ("M", "SLLL", ()),
        "_packed_pass_fn": ("MCCCCSLLLR", "SLLL", (5, 6, 7, 8)),
        "_packed_agg_fn": ("MRSLLLVVR", "MRR", (2, 3, 4, 5))}),
    "packed on resnet_fsdp": (dict(pack_lanes=2, shard_rules="resnet_fsdp"), {
        "_packed_buf_fn": ("M", "SLLL", ()),
        "_packed_pass_fn": ("MRCCCCSLLLR", "SLLL", ON_CPU),
        "_packed_agg_fn": ("MRSLLLVVR", "MRR", ON_CPU)}),
    "packed on transformer_tp": (dict(pack_lanes=2, shard_rules="transformer_tp"), {
        "_packed_buf_fn": ("M", "SLLL", ()),
        "_packed_pass_fn": ("MRCCCCSLLLR", "SLLL", ON_CPU),
        "_packed_agg_fn": ("MRSLLLVVR", "MRR", ON_CPU)}),
    "block of 3": (dict(block_dispatch=True), {
        "_gather_round_fn": ("MRRCVVR", "MRR", (0,)),
        "block:3": ("MRRBBBR", "MRR", (0,))}),
    "block of 3 under the scan carry": (dict(block_dispatch=True, cohort_execution="scan"), {
        "_gather_round_fn": ("MRRCVVRM", "MRR", (7,)),
        "block:3": ("MRRBBBR", "MRR", (0,))}),
}
ROUND_PROGRAMS = ("_round_fn", "_gather_round_fn", "_spmd_train_fn", "_spmd_gather_train_fn",
                  "_spmd_agg_fn", "_packed_buf_fn", "_packed_pass_fn", "_packed_agg_fn")


def _plan_sim(rule="fedavg", shard_rules=None, **over):
    """A FedSim of the plan: blobs under a two-device client mesh, or, under
    shard rules, a model they match on a 2 x 2 (clients, model) mesh."""
    from jax.sharding import PartitionSpec as P

    from fedml_tpu.algorithms.decentralized import gossip_aggregator
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.topology.topology import ring_topology

    class TinyCNN(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.relu(nn.Conv(8, (3, 3))(x))
            return nn.Dense(4)(x.reshape((x.shape[0], -1)))

    rng = np.random.RandomState(0)
    part = {i: np.arange(8 * i, 8 * i + 8) for i in range(4)}
    cfg = SimConfig(client_num_in_total=4, client_num_per_round=4, batch_size=4,
                    comm_round=3, frequency_of_the_test=3, seed=0, **over)
    if shard_rules == "transformer_tp":
        x = rng.randint(0, 32, (32, 8)).astype(np.int32)
        train = FederatedArrays({"x": x, "y": x, "mask": np.ones(x.shape, np.float32)}, part)
        module, task = TransformerLM(vocab_size=32, embed_dim=16, num_layers=1, num_heads=2,
                                     max_len=8), "nwp"
    elif shard_rules:
        train = FederatedArrays({"x": rng.rand(32, 8, 8, 3).astype(np.float32),
                                 "y": rng.randint(0, 4, 32).astype(np.int32)}, part)
        module, task = TinyCNN(), "classification"
    else:
        train, _ = gaussian_blobs(n_clients=4, samples_per_client=8, num_classes=4, seed=3)
        module, task = LogisticRegression(num_classes=4), "classification"
    trainer = ClientTrainer(module=module, task=task, optimizer=optax.sgd(0.1), epochs=1)
    if shard_rules:
        cfg = dataclasses.replace(cfg, mesh_shape=(2, 2), shard_rules=shard_rules)
        sim = FedSim(trainer, train, None, cfg)
    else:
        aggregator = gossip_aggregator(ring_topology(4)) if rule == "gossip" else None
        sim = FedSim(trainer, train, None, cfg, aggregator=aggregator,
                     mesh=meshlib.client_mesh(jax.devices()[:2]))
    clients = P(meshlib.CLIENT_AXIS)
    gather = bool(shard_rules) and shard_rules.endswith("fsdp")
    letters = {"R": P(), "C": clients, "B": P(None, meshlib.CLIENT_AXIS),
               "M": clients if rule == "gossip" else P(), "V": clients, "S": clients, "L": clients}
    if shard_rules:
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        assert any(s != P() for s in jax.tree.leaves(sim._var_specs, is_leaf=is_spec))
        letters.update(
            M=sim._var_specs, V=P(), L=P() if gather else clients,
            S=P() if gather else jax.tree.map(
                lambda s: P(meshlib.CLIENT_AXIS, *s), sim._var_specs, is_leaf=is_spec))
    return sim, letters


@pytest.mark.parametrize("plan", PROGRAM_PLANS)
def test_each_plan_builds_its_programs_with_these_specs_and_donations(monkeypatch, plan):
    """Which device programs a plan builds, under the names their readers know
    (``chip_smoke.py``, ``tools/lower_hash.py``, ``tests/test_phase_scopes.py``),
    each one's in and out specs and what it donates; and that the pooled and
    the per-client eval come in the forms the plan's data asks for."""
    from fedml_tpu.parallel import dispatch as displib

    over, want = PROGRAM_PLANS[plan]
    lowered, real = [], displib.lower

    def spy(fn, **kw):
        lowered.append((real(fn, **kw), fn.__name__, kw))
        return lowered[-1][0]

    monkeypatch.setattr(displib, "lower", spy)
    sim, letters = _plan_sim(**over)
    for name, (ins, outs, donated) in want.items():
        program = sim._get_block_fn(3) if name == "block:3" else getattr(sim, name)
        (impl, kw), = [(i, kw) for p, i, kw in lowered if p is program]
        gathered = "gather_" if sim._on_device and name != "_spmd_agg_fn" else ""
        assert impl == {"_packed_pass_fn": f"_packed_{gathered or 'host_'}pass_impl",
                        "block:3": "_block_impl"}.get(name, name[:-2] + "impl"), (name, impl)
        assert kw["mesh"] is sim.mesh
        assert tuple(kw["in_specs"]) == tuple(letters[c] for c in ins), (name, kw["in_specs"])
        assert tuple(kw["out_specs"]) == tuple(letters[c] for c in outs), (name, kw["out_specs"])
        assert tuple(kw.get("donate_argnums", ())) == donated == program.donate_argnums, name
    # what the plan does not dispatch is not built (before PR 47 a gathered
    # program's host-staged twin was, and the padded round under lanes)
    for name in set(ROUND_PROGRAMS) - set(want):
        assert getattr(sim, name) is None, name
    assert (sim._get_block_fn(3) is None) == ("_gather_round_fn" not in want)
    # the eval programs: plain jit, or under a shard plan jit under the mesh
    for name in ("_eval_fn", "_client_eval_fn", "_eval_gather_fn", "_client_eval_gather_fn"):
        program = getattr(sim, name)
        if "gather" in name and not sim._on_device:
            assert program is None, name
        else:
            assert isinstance(program, displib.Lowered) == bool(over.get("shard_rules")), name
            assert callable(program)
