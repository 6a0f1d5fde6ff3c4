"""The device side of tracing: every phase scope of ``obs/trace.py``'s
``SCOPES`` and every loop's name of its ``LOOP_SCOPES`` is in the lowered text
of the program that should carry it, the loops' names change no reader's
answer for an op a scope already claims, each loop leaves the note of its
carry, and the host spans mirror into jax's profiler, each thread on its own
line."""

import glob
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.core import rng as rnglib
from fedml_tpu.core.trainer import ClientTrainer
from fedml_tpu.data.synthetic import gaussian_blobs
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.obs import trace
from fedml_tpu.sim.engine import FedSim, SimConfig


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    trace.uninstall()
    yield
    trace.uninstall()


def _blobs_sim(**over):
    train, test = gaussian_blobs(n_clients=8, samples_per_client=16,
                                 num_classes=3, seed=1)
    trainer = ClientTrainer(module=LogisticRegression(num_classes=3),
                            optimizer=optax.sgd(0.1), epochs=1)
    cfg = SimConfig(client_num_in_total=8, client_num_per_round=4,
                    batch_size=8, comm_round=6, frequency_of_the_test=3,
                    seed=0, **over)
    return FedSim(trainer, train, test, cfg)


def _lm_sim(module=None, **over):
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays

    rng = np.random.RandomState(0)
    x = rng.randint(0, 31, (8, 16)).astype(np.int32)
    train = FederatedArrays(
        {"x": x, "y": np.roll(x, -1, 1), "mask": np.ones(x.shape, np.float32)},
        {c: np.arange(4 * c, 4 * c + 4) for c in range(2)})
    module = module or TransformerLM(vocab_size=31, embed_dim=16, num_layers=1,
                                     num_heads=2, max_len=16, attn_impl="flash")
    trainer = ClientTrainer(module=module, task="nwp", epochs=1,
                            optimizer=optax.sgd(0.01, momentum=0.9))
    cfg = SimConfig(client_num_in_total=2, client_num_per_round=2,
                    batch_size=2, comm_round=1, frequency_of_the_test=1000,
                    seed=0, cohort_execution="scan", block_dispatch=False,
                    **over)
    return FedSim(trainer, train, None, cfg)


def _lowered(sim, program: str) -> str:
    """Lowered text, debug info in, of one of the sim's programs on the
    arguments its own staging gives it."""
    variables = sim.init_round_variables()
    state = sim.aggregator.init_state(variables)
    root = rnglib.root_key(sim.config.seed)
    if program == "gather_round":
        fn, args = sim._gather_round_fn.fn, (
            variables, state, sim._dataset, *sim.stage_round(0, root))
        if sim._mean_in_carry:  # and a dead model's buffers to sum into
            args += (variables,)
    elif program == "block":
        fn, args = sim._get_block_fn(3).fn, (
            variables, state, sim._dataset, *sim._stage_block(0, 3, root))
    elif program == "eval":
        fn, args = sim._eval_gather_fn, (
            variables, sim._dataset, sim._train_eval_idx)
    elif program == "packed_pass":
        staged = sim.stage_round(0, root)
        bufs = sim._packed_buf_fn(variables)
        fn, args = sim._packed_pass_fn.fn, (
            variables, sim._dataset, *staged.passes[0], *bufs, staged.rkey)
    return fn.lower(*args).as_text(debug_info=True)


ROUND_SCOPES = (trace.SCOPE_GATHER, trace.SCOPE_FWD_BWD, trace.SCOPE_LOSS,
                trace.SCOPE_OPT, trace.SCOPE_AGGREGATE)
PROGRAMS = [
    ("gather_round", _blobs_sim, ROUND_SCOPES),
    ("block", _blobs_sim, ROUND_SCOPES),
    ("eval", _blobs_sim, (trace.SCOPE_GATHER, trace.SCOPE_EVAL)),
    ("gather_round", _lm_sim, ROUND_SCOPES + (
        trace.SCOPE_FLASH_FWD, trace.SCOPE_BLOCKWISE_BWD)),
    ("packed_pass", lambda: _blobs_sim(pack_lanes=2), (
        trace.SCOPE_PACK_PASS, trace.SCOPE_GATHER, trace.SCOPE_FWD_BWD,
        trace.SCOPE_OPT)),
]


@pytest.mark.parametrize("program,make,scopes", PROGRAMS)
def test_scopes_are_in_the_lowered_programs(program, make, scopes):
    text = _lowered(make(), program)
    for scope in scopes:  # "fed/x/op", "a/fed/x/op", or "vmap(fed/x)/op"
        assert re.search(rf'["/(]{scope}[/)]', text), scope
    if trace.SCOPE_FWD_BWD in scopes:
        # jax's own mark on the backward ops, inside the scope: what the
        # benchmark's readers split forward from backward by
        assert re.search(rf"{trace.SCOPE_FWD_BWD}\)?/transpose\(jvp\(", text)
        assert re.search(rf"{trace.SCOPE_FWD_BWD}\)?/jvp\(", text)
    if trace.SCOPE_EVAL in scopes:
        assert trace.SCOPE_FWD_BWD not in text


def test_every_scope_is_held_to_some_program():
    assert len(set(trace.SCOPES)) == len(trace.SCOPES)
    assert {s for _, _, scopes in PROGRAMS for s in scopes} == set(trace.SCOPES)


# -- the loops of a round's path -----------------------------------------------

ALL_LOOPS = trace.LOOP_SCOPES
IN_A_ROUND = trace.LOOP_SCOPES[1:]  # a gather round is one trip of no rounds' loop
LOOP_PROGRAMS = {
    "gather_round/vmap": ("gather_round", _blobs_sim, IN_A_ROUND),
    "block/vmap": ("block", _blobs_sim, ALL_LOOPS),
    "gather_round/scan_lm": ("gather_round", _lm_sim, IN_A_ROUND),
    "block/scan": ("block", lambda: _blobs_sim(cohort_execution="scan"), ALL_LOOPS),
}


@pytest.fixture(scope="module")
def loop_texts():
    """{(program, rolled): lowered text}, made once: straight-lined as
    ``core/scan.py`` lowers the trainer's loops on the CPU, and rolled as on
    the chip (the module is handed a jax that names another backend)."""
    from fedml_tpu.core import scan as scanlib

    texts = {}
    for key, (program, make, _) in LOOP_PROGRAMS.items():
        texts[key, False] = _lowered(make(), program)
    chip = types.SimpleNamespace(default_backend=lambda: "tpu", lax=jax.lax, tree=jax.tree)
    real, scanlib.jax = scanlib.jax, chip
    try:
        for key in ("block/vmap", "gather_round/scan_lm"):
            program, make, _ = LOOP_PROGRAMS[key]
            texts[key, True] = _lowered(make(), program)
    finally:
        scanlib.jax = real
    return texts


@pytest.mark.parametrize("key,loop", [
    (key, loop) for key, (_, _, loops) in LOOP_PROGRAMS.items() for loop in loops])
def test_loop_names_are_in_the_lowered_programs(loop_texts, key, loop):
    text = loop_texts[key, False]
    assert re.search(rf'["/(]{loop}[/)]', text), loop  # "vmap(loop/epochs)/" under a vmap
    # never under a phase: a reader gives an op to the outermost fed/*. (A
    # backward op repeats its forward's names inside "transpose(...)", the
    # loops' with them: "fed/fwd_bwd/transpose(loop/epochs)/loop/steps/fed/...")
    for name in re.findall(r'loc\("([^"]*loop/[^"]*)"', text):
        assert not re.search(r"fed/[a-z_]+.*loop/", name.split("transpose(")[0]), name
    if loop == trace.SCOPE_LOOP_STEPS:  # straight-lined, the steps are ops of the loop's name
        assert re.search(rf"{loop}\)?/(vmap\()?{trace.SCOPE_FWD_BWD}", text)


@pytest.mark.parametrize("key", ["block/vmap", "gather_round/scan_lm"])
def test_rolled_loops_are_whiles_under_their_names(loop_texts, key):
    text = loop_texts[key, True]
    for loop in LOOP_PROGRAMS[key][2]:
        if loop == trace.SCOPE_LOOP_COHORT and "vmap" in key:
            assert re.search(rf"{loop}/vmap\(", text)  # no loop there: the vmap bears the name
        else:
            assert re.search(rf"{loop}\)?/while/body/", text), loop
    # the body is a function of its own, whose names start anew in this text
    assert re.search(rf'"(vmap\()?{trace.SCOPE_FWD_BWD}', text)


def _ops_in_the_cohorts_body(text):
    """(op, name) of the ops in the function that is the cohort loop's body
    (the callee of the call named ``.../loop/cohort/while/body/closed_call``):
    names start anew there, and the chip writes each after that call's."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    (callee,) = {m.group(1) for m in re.finditer(r"func\.call @(\w+)\(.*loc\((#loc\d+)\)", text)
                 if locs.get(m.group(2), "").endswith(
                     f"{trace.SCOPE_LOOP_COHORT}/while/body/closed_call")}
    body = text.split(f"func.func private @{callee}(")[1].split("func.func")[0]
    return [(op, locs[ref]) for op, ref in
            re.findall(r"= stablehlo\.(\w+) .*loc\((#loc\d+)\)", body) if ref in locs]


@pytest.mark.parametrize("key,rolled", [
    ("gather_round/scan_lm", False), ("gather_round/scan_lm", True), ("block/scan", False)])
def test_the_running_means_multiply_add_is_aggregation_inside_the_cohort_loop(
        loop_texts, key, rolled):
    """Where the scan cohort sums the clients' mean in its carry, a leaf's
    multiply and add are ``fed/aggregate`` ops of the loop's body, one of each
    a leaf: a phase claims an op before a loop does, so none of them counts
    for ``loop/cohort``."""
    sim = LOOP_PROGRAMS[key][1]()
    assert sim._mean_in_carry
    leaves = len(jax.tree.leaves(sim.init_round_variables()))
    ops = _ops_in_the_cohorts_body(loop_texts[key, rolled])
    for op, name in (("multiply", "mul"), ("add", "add")):
        assert ops.count((op, f"{trace.SCOPE_AGGREGATE}/{name}")) == leaves, op
    # a bare name there is the loop's alone, and a loop's name after the
    # phase's would hand the op to the phase's reader under the wrong loop
    assert ("multiply", "mul") not in ops
    assert not any(trace.SCOPE_AGGREGATE in name and "loop/" in name for _, name in ops)


def _fixture_op_names():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "fixtures", "scope_fixture.json")) as f:
        rows = json.load(f)["scope_rows"]
    return sorted({r[1] for per_program in rows.values() for r in per_program})


LOOPS_AROUND = ("loop/rounds/while/body/closed_call/loop/cohort/while/body/closed_call/"
                "loop/epochs/while/body/loop/steps/while/body/closed_call/")
LOOPS_AROUND_A_VMAP = ("loop/rounds/while/body/closed_call/loop/cohort/vmap(loop/epochs)/while/"
                       "body/closed_call/vmap(loop/steps)/while/body/closed_call/")


def _inside_the_loops(op_name: str) -> str:
    """``op_name`` as the chip writes it once the loops are named: the loops'
    path before the op's first scope, after the ``jit(...)`` elements."""
    head = re.match(r"(?:jit\([^)]*\)/)*", op_name).end()
    around = LOOPS_AROUND_A_VMAP if op_name[head:].startswith("vmap(") else LOOPS_AROUND
    return op_name[:head] + around + op_name[head:]


@pytest.mark.parametrize("op_name", _fixture_op_names() + [
    "jit(f)/fed/fwd_bwd/jvp(M)/blocks_1/moe/dispatch/gather",
    "jit(f)/fed/fwd_bwd/transpose(jvp(M))/blocks_1/moe/experts/gmm/pallas_call",
    "jit(f)/fed/fwd_bwd/jvp(M)/blocks_0/attn/mla/attn/flash_fwd/flash_fwd/pallas_call",
    "jit(f)/fed/fwd_bwd/jvp(mtp)/fed/loss/reduce_max",
    "jit(f)/fed/fwd_bwd/transpose(jvp(M))/blocks_2/attn/kda/attn/kda/scan/kda_bwd/pallas_call",
])
def test_the_loops_names_change_no_readers_answer(op_name):
    from benchmark import loop_reduce, mla_reduce, moe_reduce, scope_reduce

    named = _inside_the_loops(op_name)
    assert named != op_name and len(loop_reduce.LOOP.findall(named)) == 4
    assert scope_reduce.classify(named) == scope_reduce.classify(op_name)
    assert scope_reduce.sub_shares(named) == scope_reduce.sub_shares(op_name)
    patterns = [moe_reduce.MOE_SCOPE % "route|dispatch|experts|combine",
                moe_reduce.MOE_SCOPE % "dispatch|combine"]
    patterns += [mla_reduce.SCOPE % re.escape(scope) for scope in
                 trace.MLA_SCOPES + trace.KDA_SCOPES]
    for pattern in patterns:
        assert bool(re.search(pattern, named)) == bool(re.search(pattern, op_name)), pattern
    # and the loop's own reader leaves what a phase claims alone
    assert (loop_reduce.loop_of(named) is None) == (scope_reduce.classify(op_name) != "unattributed")


def _tree_bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("execution,width", [("vmap", 4), ("scan", 1)])
def test_each_loop_leaves_the_note_of_its_carry_once_a_shape(monkeypatch, execution, width):
    """One chip holds the cohort of four: side by side under ``vmap``, in turn
    under ``scan``, where the trip carries the running float32 sum of their
    models (FedAvg's mean is summed in the loop): one model's bytes either way."""
    from fedml_tpu.parallel import mesh as meshlib

    monkeypatch.setattr(trace, "_program_notes", {})
    train, test = gaussian_blobs(n_clients=8, samples_per_client=16, num_classes=3, seed=1)
    optimizer = optax.sgd(0.1, momentum=0.9)
    trainer = ClientTrainer(module=LogisticRegression(num_classes=3), optimizer=optimizer,
                            epochs=2)
    cfg = SimConfig(client_num_in_total=8, client_num_per_round=4, batch_size=8, comm_round=6,
                    frequency_of_the_test=3, seed=0, epochs=2, cohort_execution=execution)
    sim = FedSim(trainer, train, test, cfg, mesh=meshlib.client_mesh(jax.devices()[:1]))
    _lowered(sim, "block")
    variables = sim.init_round_variables()
    model = _tree_bytes(variables)
    state = _tree_bytes(sim.aggregator.init_state(variables))
    step = model + _tree_bytes(optimizer.init(variables["params"])) + 8  # and the key
    leaves = len(jax.tree.leaves(variables))
    notes = trace.program_notes(trace.LOOP_CARRY_NOTE)
    assert {n["loop"]: (n["bytes"], n["side_by_side"]) for n in notes} == {
        trace.SCOPE_LOOP_ROUNDS: (model + state, 1),
        trace.SCOPE_LOOP_COHORT: (model, width),
        trace.SCOPE_LOOP_EPOCHS: (step, 1),  # the trainer cannot see a vmap around it
        trace.SCOPE_LOOP_STEPS: (step, 1)}
    assert len(notes) == 4 and all(n["leaves"] >= leaves for n in notes)
    _lowered(sim, "gather_round")  # the same shapes again: no second note
    assert trace.program_notes(trace.LOOP_CARRY_NOTE) == notes


# -- head and loss in chunks of rows (ops/head_loss.py) --------------------------

def _mla_sim(**model):
    from fedml_tpu.models.mla_moe_transformer import MLAMoETransformerLM

    return _lm_sim(MLAMoETransformerLM(vocab_size=31, routed_layers=1, **model))


CHUNKED_HEADS = {  # sim, the scopes that a second pass bears besides
    "bias": (_lm_sim, ()),
    "mtp": (lambda: _mla_sim(mtp_depth=1), (trace.SCOPE_MTP,)),
    "tied": (lambda: _mla_sim(mtp_depth=0, tie_head=True), ()),
}


@pytest.mark.parametrize("case", CHUNKED_HEADS)
def test_the_chunked_heads_ops_bear_the_names_its_readers_look_for(monkeypatch, case):
    """Compiled, every op of the operator's loop (the three products a pass
    under ``head``, the elementwise loss, the loop itself) bears ``fed/loss``
    inside ``fed/fwd_bwd``, so ``head_loss_time_pct`` and its twins count it
    (``benchmark/scope_reduce.py`` ``sub_shares``); the MTP pass bears ``mtp``
    too, as ``mtp_time_pct`` wants; all of it is forward but the scaling by
    the cotangent."""
    from benchmark import scope_reduce
    from fedml_tpu.ops import head_loss

    for constant, value in (("WHOLE_BYTES", 0), ("CHUNK_ROWS", 16), ("TILE_ROWS", 8)):
        monkeypatch.setattr(head_loss, constant, value)
    make, scopes = CHUNKED_HEADS[case]
    sim = make()
    variables = sim.init_round_variables()
    args = (variables, sim.aggregator.init_state(variables), sim._dataset,
            *sim.stage_round(0, rnglib.root_key(sim.config.seed)), variables)
    text = sim._gather_round_fn.fn.lower(*args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # "jvp(fed/loss)/while/body/...", and the MTP pass "jvp(mtp)/fed/loss/while/body/..."
    in_the_loop = {n for n in names if re.search(rf"{trace.SCOPE_LOSS}\)?/while/body/", n)}
    heads = {n for n in in_the_loop if n.endswith(f"/{trace.SCOPE_HEAD}/dot_general")}
    assert heads and any(n.endswith("/exp") for n in in_the_loop)
    for n in in_the_loop:
        assert scope_reduce.classify(n) == "train_fwd", n
        assert scope_reduce.sub_shares(n) == ["head_loss"], n
    if scopes:  # the second pass: some of the loop's ops sit under every scope
        inner = {n for n in in_the_loop if all(re.search(
            rf"[/(]{s}[/)]", n) for s in scopes)}
        assert inner and {n for n in inner if n in heads} and inner != in_the_loop
    # no product of head and loss outside the loop, and no head's product outside the loss
    assert not [n for n in names if n.endswith("/dot_general") and n not in in_the_loop
                and "head_loss" in scope_reduce.sub_shares(n)]
    note = trace.program_notes(head_loss.NOTE)[-1]
    assert note["form"] == "chunked" and note["chunk_rows"] == 16


def test_flash_kernel_is_named_in_the_tpu_lowering(monkeypatch):
    """On the chip the Mosaic custom call carries ``name=``: lower for the TPU
    from here (no compile) and find it."""
    import fedml_tpu.ops.attention as att

    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    q = jax.ShapeDtypeStruct((1, 2, 128, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v: att.flash_attention(q, k, v, True)) \
        .trace(q, q, q).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    assert trace.FLASH_KERNEL_NAME in text
    assert f"{trace.SCOPE_FLASH_FWD}/" in text


@pytest.mark.parametrize("t_q,t_k", [(1024, 1024), (128, 256)])
def test_flash_backward_kernels_are_named_in_the_tpu_lowering(monkeypatch, t_q, t_k):
    """``jax.grad`` of flash attention lowers for the TPU to two Mosaic custom
    calls an attention, the forward and the one backward kernel, by name,
    under the scope ``attn_bwd_time_pct`` reads; ``flash_bwd_dq`` is gone, and
    no XLA loop is left under that scope (the plain-XLA backward was two
    ``while`` scans there)."""
    import fedml_tpu.ops.attention as att

    monkeypatch.setattr(att, "_interpret_on", lambda platform: False)
    q = jax.ShapeDtypeStruct((1, 2, t_q, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, t_k, 128), jnp.bfloat16)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .trace(q, k, k).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("tpu_custom_call") == 2
    assert "flash_bwd_dq" not in text and not hasattr(trace, "FLASH_BWD_DQ_KERNEL_NAME")
    kernel = trace.FLASH_BWD_DKV_KERNEL_NAME
    assert trace.FLASH_KERNEL_NAME not in kernel  # the forward's readers match on it
    # "attn/blockwise_bwd/<kernel>" in a model, "transpose(jvp(attn/
    # blockwise_bwd))/<kernel>" when the gradient is of the op itself
    assert re.search(rf"{trace.SCOPE_BLOCKWISE_BWD}\)*/{kernel}/", text), kernel
    assert not re.search(rf"{trace.SCOPE_BLOCKWISE_BWD}\)*/while", text)
    assert re.search(rf"{trace.SCOPE_FLASH_FWD}\)*/{trace.FLASH_KERNEL_NAME}/", text)


def _host_annotations(profile_dir):
    """{line index: [(name, stats)]} of the engine/ and prefetch/ events on
    the host planes of the one .xplane.pb under ``profile_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{profile_dir}/plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            found = [(e.name, dict(e.stats)) for e in line.events
                     if e.name.startswith(("engine/", "prefetch/"))]
            if found:
                lines[(plane.name, i)] = found
    return lines


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_spans_mirror_into_the_profiler_on_their_own_threads_lines(tmp_path):
    sim = _blobs_sim(block_dispatch=True)
    state = {"variables": sim.run()[0]}  # compile outside the profile

    def profiled(out):
        sim.config.comm_round = 12
        jax.profiler.start_trace(str(out))
        try:  # the round program donates its variables: go on from the last
            state["variables"], _ = sim.run(
                variables=state["variables"], start_round=6)
        finally:
            jax.profiler.stop_trace()
        return _host_annotations(out)

    assert profiled(tmp_path / "off") == {}  # no tracer, no annotation

    trace.install()
    lines = profiled(tmp_path / "on")
    trace.uninstall()
    where = {}
    for key, events in lines.items():
        for name, stats in events:
            where.setdefault(name, set()).add(key)
    for name in ("engine/dispatch", "engine/stage", "engine/stage/cohort",
                 "engine/stage/put", "engine/stage/keys", "prefetch/stage",
                 "engine/sync", "engine/eval"):
        assert len(where[name]) == 1, (name, where.get(name))
    driver, staging = where["engine/dispatch"], where["prefetch/stage"]
    assert driver != staging
    assert where["engine/stage/put"] == staging and where["engine/sync"] == driver
    (driver,), (staging,) = driver, staging
    dispatch = [s for n, s in lines[driver] if n == "engine/dispatch"]
    assert [(s["round"], s["n_rounds"], s["program"]) for s in dispatch] == [
        (6, 3, "block3"), (9, 3, "block3")]
    puts = [s for n, s in lines[staging] if n == "engine/stage/put"]
    assert [s["round"] for s in puts] == [6, 9]
