"""The device side of tracing: every phase scope of ``obs/trace.py``'s
``SCOPES`` is in the lowered text of the program that should carry it, and
the host spans mirror into jax's profiler, each thread on its own line."""

import glob
import re

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


def _lm_sim(**over):
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays

    rng = np.random.RandomState(0)
    x = rng.randint(0, 31, (8, 16)).astype(np.int32)
    train = FederatedArrays(
        {"x": x, "y": np.roll(x, -1, 1), "mask": np.ones(x.shape, np.float32)},
        {c: np.arange(4 * c, 4 * c + 4) for c in range(2)})
    module = TransformerLM(vocab_size=31, embed_dim=16, num_layers=1,
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
    """``jax.grad`` of flash attention lowers for the TPU to the two Mosaic
    backward kernels, by name, under the scope ``attn_bwd_time_pct`` reads;
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
    assert text.count("tpu_custom_call") == 3
    for kernel in (trace.FLASH_BWD_DKV_KERNEL_NAME, trace.FLASH_BWD_DQ_KERNEL_NAME):
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
