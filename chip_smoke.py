"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # from the checkout root, on a machine with a TPU

One process drives the system's main paths once, through the entry points a
user would call, at full model width; checks
what comes out by the repo's own means; and prints as the LAST line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU it exits non-zero within seconds, naming the platform it
found, and prints no result. It sets JAX_PLATFORMS nowhere, starts no
process, needs no network, and wraps no phase in a catch: whatever raises
ends the run with a traceback and a non-zero exit.

Phases (each prints its timings as builder figures with the device stamp;
they are claimed under no metric name):

a. The paper's headline model through the CLI: ``main_fedavg.main`` with
   ``configs/cifar10_resnet56.yaml`` (ResNet-56, 10 clients, non-IID
   alpha=0.5, batch 64, bf16 — full depth and width), only the run length
   overridden, so the default TPU path runs: device-resident data, the
   two-round block program, donation, rolled scans, on-chip eval.
b. The kernel path through the library surface: ``FedSim`` +
   ``ClientTrainer`` + ``TransformerLM`` at this file's own width
   (D2048 L8 H16 T1024 V32000 bf16, flash attention at the kernel's own tiles) on
   one device; the lowered round program must contain the Mosaic custom call;
   and ``flash_attention`` against ``attention_reference``, forward and all
   three gradients of the custom VJP; the staircase call and the masked call
   under a selected set (``[1, 32, 8192, 128]`` on 4 KV heads) likewise.
c. With four or more devices: phase a as it is (the default mesh takes every
   chip) and phase b's round under three sharded plans over all of them.
   With fewer it says so by name and does not run.

Rehearsing on the CPU: the phases are plain functions with their sizes as
keyword arguments, so a scratch script can import this module and call them
at toy size under JAX_PLATFORMS=cpu (the Mosaic assertion then fails, as it
must). ``main()`` itself has no such mode.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import time

# -- what a pass means, in numbers -------------------------------------------
# Each bound was set from the runs recorded in CHANGES.md (PR 21) on a TPU
# v5e ("TPU v5 lite"); the observed figure is beside it.

# phase a: the cohort's mean local training loss in round 3 must be below
# round 1's by this much. Rounds 2 and 4 report the pooled-train EVAL loss
# under Train/Loss instead — eval-mode BatchNorm on four rounds of running
# statistics, which need not fall yet (observed 2.507 -> 2.635) — so they
# are only checked finite. Observed local loss 3.753 -> 3.220, a drop of 0.53.
RESNET_LOCAL_LOSS_DROP = 0.25

# phase b: the LM's round-3 training loss must be below round 1's by this
# much on the learnable ramp stream (ln 32000 = 10.37 at init). Observed
# 8.09 -> 4.63 -> 3.67 at both batch shapes, a drop of 4.4.
LM_LOSS_DROP = 2.0

# phase b kernel check at [4, 16, 1024, 128] bf16 causal: max |flash - ref|
# over max |ref|, where ref is attention_reference on the same values in f32
# at highest matmul precision. bf16 carries 8 bits (eps 3.9e-3). Observed
# out 3.2e-3, dq 4.7e-3, dk 4.1e-3, dv 5.8e-3.
KERNEL_SHAPE = (4, 16, 1024, 128)
KERNEL_TOL = {"out": 2e-2, "dq": 2e-2, "dk": 2e-2, "dv": 2e-2}

# phase c: round-1 Train/Loss of each sharded arm against the one-device
# value. The fsdp plans gather for compute (same math; observed 1.2e-6 at
# 1x4 and 3.7e-6 at 2x2); the tp plan reassociates its cross-shard
# reductions (observed 5.0e-4).
SHARD_LOSS_RTOL = {"transformer_fsdp": 1e-4, "transformer_tp": 5e-3}

# the LM round: this smoke's own width (the benchmark's LM cells are
# benchmark/configs/), two clients of 4 local steps at batch 4
LM_WIDTH = dict(vocab_size=32000, embed_dim=2048, num_layers=8, num_heads=16,
                max_len=1024)
LM_CLIENTS, LM_STEPS, LM_BATCH = 2, 4, 4
LM_ROUNDS = 3
RAMP_ALPHABET = 64  # distinct tokens in the learnable stream

OUT_DIR = os.path.join("chiprun_out", "chip_smoke")


def say(msg: str) -> None:
    print(msg, flush=True)


def open_backend() -> dict:
    """Open the default backend once; refuse anything but a TPU. Returns
    the device stamp every figure is printed with."""
    from importlib import metadata

    import jax

    from fedml_tpu.core.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    backend = jax.default_backend()
    versions = {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}
    say(f"chip_smoke: {versions}, JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r}, compile cache {cache_dir}")
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax.default_backend() is "
            f"{backend!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    dev = jax.devices()
    stamp = {"platform": dev[0].platform, "kind": dev[0].device_kind,
             "count": len(dev)}
    say(f"chip_smoke: device {stamp}")
    return stamp


def cache_entries() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    if not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if f.endswith("-cache"))


def free_device_memory() -> None:
    """Drop every unreferenced array and executable: the LM round was tuned
    to the edge of 16 GB, so nothing of an earlier phase may stay."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
    stats = [d.memory_stats() for d in jax.devices()]  # None off the TPU
    say("  freed: bytes_in_use per device now "
        f"{[s and s['bytes_in_use'] for s in stats]}")


def assert_finite(name: str, values) -> None:
    assert all(map(math.isfinite, values)), (name, values)


# -- phase a -----------------------------------------------------------------


def phase_a(n_rounds: int = 4, freq: int = 2) -> dict:
    import jax

    from fedml_tpu.exp import main_fedavg

    run_dir = os.path.join(OUT_DIR, "phase_a")
    shutil.rmtree(run_dir, ignore_errors=True)  # metrics.jsonl appends
    if not os.path.isdir(os.path.join("data", "cifar10")):
        say("  no data/cifar10: the loader warns and uses its CIFAR-shaped "
            "synthetic fixture (2000 train / 400 test images)")
    t0 = time.perf_counter()
    final = main_fedavg.main([
        "--cf", "configs/cifar10_resnet56.yaml", "--epochs", "1",
        "--comm_round", str(n_rounds), "--frequency_of_the_test", str(freq),
        "--run_dir", run_dir,
    ])
    wall = time.perf_counter() - t0
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    for r in recs:
        say(f"  round {r['round']}: "
            + ", ".join(f"{k}={v:.4f}" for k, v in sorted(r.items())
                        if k not in ("round", "_ts")))

    assert [r["round"] for r in recs] == list(range(n_rounds)), recs
    assert final["round"] == n_rounds - 1, final
    assert_finite("phase a Train/Loss", [r["Train/Loss"] for r in recs])
    evals = [r for r in recs if (r["round"] + 1) % freq == 0]
    locals_ = [r for r in recs if (r["round"] + 1) % freq != 0]
    for r in evals:
        assert "Test/Acc" in r and 0.0 <= r["Test/Acc"] <= 1.0, r
    # an eval round's Train/Loss is the pooled-train eval loss, the others'
    # the cohort's mean local training loss: compare like with like
    local_drop = locals_[0]["Train/Loss"] - locals_[-1]["Train/Loss"]
    assert local_drop > RESNET_LOCAL_LOSS_DROP, (local_drop, recs)
    # the model's arrays lived on every TPU device of the default mesh
    peaks = {}
    for d in jax.devices():
        assert d.platform == "tpu", d
        peaks[d.id] = d.memory_stats()["peak_bytes_in_use"]
        assert peaks[d.id] > 0, (d, "held no bytes during phase a")
    # round_time is the per-round average of a sync window (sim/engine.py
    # run): the first block's includes its compile, the last block's is
    # steady state ending in the host fetch of the block's metrics
    return {
        "wall_s": round(wall, 2),
        "first_block_s": round(evals[0]["round_time"] * freq, 2),
        "steady_s_per_round": round(evals[-1]["round_time"], 4),
        "local_train_loss": [round(r["Train/Loss"], 4) for r in locals_],
        "eval_train_loss": [round(r["Train/Loss"], 4) for r in evals],
        "test_acc": [round(r["Test/Acc"], 4) for r in evals],
        "peak_bytes_in_use": peaks,
    }


# -- phase b -----------------------------------------------------------------


def build_lm_problem(width=None, clients=LM_CLIENTS, steps=LM_STEPS,
                     batch=LM_BATCH, cohort_execution="vmap"):
    """(trainer, train_data, SimConfig) of the LM round. Every sequence is
    a ramp over a small alphabet (y = x + 1 mod A), so the loss must fall
    (uniform-random targets would teach nothing)."""
    import numpy as np

    import jax.numpy as jnp
    import optax

    from fedml_tpu.core.trainer import ClientTrainer
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.sim.cohort import FederatedArrays
    from fedml_tpu.sim.engine import SimConfig

    width = dict(width or LM_WIDTH)
    t = width["max_len"]
    n_per = steps * batch
    n = clients * n_per
    start = np.random.RandomState(0).randint(0, RAMP_ALPHABET, (n, 1))
    x = ((start + np.arange(t)[None]) % RAMP_ALPHABET).astype(np.int32)
    y = ((x + 1) % RAMP_ALPHABET).astype(np.int32)
    part = {i: np.arange(i * n_per, (i + 1) * n_per) for i in range(clients)}
    train = FederatedArrays(
        {"x": x, "y": y, "mask": np.ones((n, t), np.float32)}, part)
    trainer = ClientTrainer(
        module=TransformerLM(**width, attn_impl="flash", dtype=jnp.bfloat16),
        task="nwp", optimizer=optax.sgd(0.01, momentum=0.9), epochs=1,
    )
    cfg = SimConfig(
        client_num_in_total=clients, client_num_per_round=clients,
        batch_size=batch, comm_round=1, epochs=1,
        frequency_of_the_test=10_000, shuffle_each_round=False, seed=0,
        cohort_execution=cohort_execution,
    )
    return trainer, train, cfg


def round_program_text(sim, staged, variables, server_state) -> str:
    """Lowered (StableHLO) text of the program one training round
    dispatches — white-box on purpose: the text, not a flag, says whether
    the pallas kernel went to Mosaic."""
    data, weights, num_steps, rkey = staged
    if sim._spmd:
        prog = sim._spmd_gather_train_fn
        args = (variables, sim._dataset, data, num_steps, rkey)
    else:
        prog = sim._gather_round_fn
        args = (variables, server_state, sim._dataset, data, weights,
                num_steps, rkey)
        if sim._mean_in_carry:  # and a model's worth of buffers to sum into
            args += (variables,)
    # as dispatch.Lowered.__call__ does: only global-view programs trace
    # under the mesh context
    with prog.mesh if prog.mode == "pjit" else contextlib.nullcontext():
        return prog.fn.lower(*args).as_text()


def run_lm_rounds(label: str, sim, n_rounds: int = LM_ROUNDS):
    """n_rounds of ``sim`` from a fresh init: asserts the Mosaic call is in
    the round program and the loss is finite and falling. Returns (figures,
    final variables); the figures are the losses, the compile and steady
    timings and the block-versus-fetch pair."""
    import jax

    from fedml_tpu.core import rng as rnglib

    variables = sim.init_round_variables()
    server_state = sim.aggregator.init_state(variables)
    root = rnglib.root_key(0)
    text = round_program_text(sim, sim.stage_round(0, root), variables,
                              server_state)
    n_calls = text.count("tpu_custom_call")
    assert n_calls > 0, (
        f"{label}: no tpu_custom_call in the lowered round program — the "
        "flash kernel did not lower to Mosaic")
    losses, block_s, fetch_s = [], [], []
    for r in range(n_rounds):
        t0 = time.perf_counter()
        variables, server_state, m = sim.run_round(
            r, variables, server_state, root)
        jax.block_until_ready((variables, server_state, m))
        t1 = time.perf_counter()
        losses.append(float(m["Train/Loss"]))
        fetch_s.append(time.perf_counter() - t1)
        block_s.append(t1 - t0)
    say(f"  {label}: Train/Loss {[round(v, 4) for v in losses]}, "
        f"{n_calls} tpu_custom_call sites")
    assert_finite(f"{label} Train/Loss", losses)
    assert losses[0] - losses[-1] > LM_LOSS_DROP, (label, losses)
    return {
        "loss": losses,
        "first_call_s": round(block_s[0], 2),
        "steady_s_per_round": round(min(block_s[1:]), 4),
        "block_until_ready_s": round(block_s[-1], 4),
        "fetch_after_block_s": round(fetch_s[-1], 6),
    }, variables


def check_flash_against_reference(shape=KERNEL_SHAPE) -> dict:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.attention import attention_reference, flash_attention

    f32 = jnp.float32
    q, k, v, g = (
        jax.random.normal(key, shape, jnp.bfloat16)
        for key in jax.random.split(jax.random.key(0), 4)
    )

    def flash(q, k, v):
        return flash_attention(q, k, v, True)

    def reference(q, k, v):
        return attention_reference(
            q.astype(f32), k.astype(f32), v.astype(f32), causal=True)

    def vjp_of(fn):
        # all three gradients of sum(out * g) — the custom VJP's outputs
        def loss(q, k, v, g):
            return jnp.sum(fn(q, k, v).astype(f32) * g.astype(f32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    got = (jax.jit(flash)(q, k, v), *vjp_of(flash)(q, k, v, g))
    with jax.default_matmul_precision("highest"):
        want = (jax.jit(reference)(q, k, v), *vjp_of(reference)(q, k, v, g))
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = a.astype(f32), b.astype(f32)
        assert bool(jnp.all(jnp.isfinite(a))), f"flash {name} not finite"
        errs[name] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    say(f"  flash vs reference at {shape} bf16 causal, max|diff|/max|ref|: "
        + ", ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    for name, e in errs.items():
        assert e <= KERNEL_TOL[name], (name, e, KERNEL_TOL[name])
    return {n: float(f"{e:.3g}") for n, e in errs.items()}


def check_staircase_against_reference(shape=(1, 8, 4096, 128), stair=(1024, 64)) -> dict:
    """``flash_attention_lse`` under the staircase mask with ``t_q != t_k``
    (``ops/eva.py``'s remote call): output, log-sum-exp and the three
    gradients through both against ``attention_reference``."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.attention import attention_reference, flash_attention_lse

    f32 = jnp.float32
    b, h, t, d = shape
    t_k = t // stair[0] * stair[1]
    kq, kk, kv, kg, kl = jax.random.split(jax.random.key(1), 5)
    q, g = (jax.random.normal(key, shape, jnp.bfloat16) for key in (kq, kg))
    k, v = (jax.random.normal(key, (b, h, t_k, d), jnp.bfloat16) for key in (kk, kv))
    g_lse = jax.random.normal(kl, (b, h, t), f32)

    def both(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            seen = lse > -1e20  # the first step's rows see no key
            return (jnp.sum(out.astype(f32) * g.astype(f32))
                    + jnp.sum(jnp.where(seen, lse * g_lse, 0.0))), (out, jnp.where(seen, lse, 0.0))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    flash = both(lambda q, k, v: flash_attention_lse(q, k, v, stair=stair))
    reference = both(lambda q, k, v: attention_reference(
        q.astype(f32), k.astype(f32), v.astype(f32), stair=stair, with_lse=True))
    (_, got_aux), got = flash(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, want_aux), want = reference(q, k, v)
    errs = {}
    for name, a, b_ in zip(("out", "lse", "dq", "dk", "dv"), (*got_aux, *got), (*want_aux, *want)):
        a, b_ = a.astype(f32), b_.astype(f32)
        assert bool(jnp.all(jnp.isfinite(a))), f"staircase {name} not finite"
        errs[name] = float(jnp.max(jnp.abs(a - b_)) / jnp.max(jnp.abs(b_)))
    say(f"  staircase {stair} with lse at {shape} on {t_k} keys bf16, max|diff|/max|ref|: "
        + ", ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    for name, e in errs.items():
        assert e <= 2e-2, (name, e)
    return {n: float(f"{e:.3g}") for n, e in errs.items()}


def check_selected_against_reference(shape=(1, 32, 8192, 128), kv_heads=4, topk=2048) -> dict:
    """``flash_attention_selected`` (``ops/dsa.py``'s masked call: 32 query
    heads on 4 KV heads, each query a chosen 2,048 of its earlier keys, the
    set made by the selection kernel of ``ops/dsa_select.py`` and handed over
    bit-packed with its tiles' counts): the set bit for bit against
    ``lax.top_k``'s through ``dsa._choose``, then output, log-sum-exp and the
    three gradients against ``attention_reference`` under the same mask, a KV
    head's group at a time so that the reference's float32 scores fit; then
    the index loss's pass over that set and log-sum-exp, the Mosaic kernels of
    ``ops/dsa_index_loss.py`` against the plain pass (``impl`` "xla"): the loss
    and its three gradients, 16 index heads of 64."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import dsa, dsa_select
    from fedml_tpu.ops.attention import attention_reference, flash_attention_selected

    f32 = jnp.float32
    b, h, t, d = shape
    group = h // kv_heads
    kq, kk, kv, kg, ks = jax.random.split(jax.random.key(2), 5)
    q, g = (jax.random.normal(key, shape, jnp.bfloat16) for key in (kq, kg))
    k, v = (jax.random.normal(key, (b, kv_heads, t, d), jnp.bfloat16) for key in (kk, kv))
    block = min(512, t)

    def scores(i):  # a random score a pair: each query's topk largest among its earlier keys
        return jax.random.normal(jax.random.fold_in(ks, i), (b, block, t), f32)

    def one(i):  # the kernel's words and flags, _choose's set, and whether that is lax.top_k's
        lo, s = i * block, scores(i)
        words, _, flags = dsa_select.select_rows(s, lo, topk, t)  # 128 rows a grid step
        plain = dsa._choose(s, lo, topk)[0]
        valid = jnp.arange(t)[None] <= (lo + jnp.arange(block))[:, None]
        _, ids = jax.lax.top_k(jnp.where(valid, s, -jnp.inf), min(topk, t))
        top = jnp.zeros((b, block, t), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(block)[None, :, None], ids].set(True) & valid
        return words, flags, plain, jnp.all(plain == top)

    words, flags, chosen, same = jax.lax.map(one, jnp.arange(t // block))
    selection = jax.jit(lambda w: dsa.selection_from_rows(w, block))(
        words.transpose(1, 0, 2, 3).reshape(b, t, -1))
    chosen = chosen.transpose(1, 0, 2, 3).reshape(b, t, t)
    assert bool(jnp.all(same)), "dsa._choose's set is not lax.top_k's"
    for name, a, b_ in zip(("rows", "cols", "tiles"), selection,
                           jax.jit(lambda m: dsa.selection_from_mask(m, block))(chosen)):
        assert bool(jnp.all(a == b_)), f"the selection kernel's {name} are not lax.top_k's set"
    say(f"  selection kernel at [{b}, {t}, {t}], topk {topk}: rows, cols and tiles equal "
        f"lax.top_k's set bit for bit; blocks skipped / searched / tied "
        f"{[int(jnp.sum(flags == f)) for f in (0, 1, 2)]}")

    def both(fn):
        def loss(q, k, v, g):
            out, lse = fn(q, k, v)
            return jnp.sum(out.astype(f32) * g.astype(f32)) + jnp.sum(lse), (out, lse)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))

    (_, got_aux), got = both(
        lambda q, k, v: flash_attention_selected(q, k, v, selection))(q, k, v, g)
    reference = both(lambda q, k, v: attention_reference(
        q.astype(f32), k.astype(f32), v.astype(f32), selected=chosen, with_lse=True))
    parts = []
    with jax.default_matmul_precision("highest"):
        for n in range(kv_heads):
            heads = slice(n * group, (n + 1) * group)
            (_, aux), grads = reference(q[:, heads], k[:, n:n + 1], v[:, n:n + 1], g[:, heads])
            parts.append((*aux, *grads))
    want = [jnp.concatenate(x, axis=1) for x in zip(*parts)]
    errs = {}
    for name, a, b_ in zip(("out", "lse", "dq", "dk", "dv"), (*got_aux, *got), want):
        a, b_ = a.astype(f32), b_.astype(f32)
        assert bool(jnp.all(jnp.isfinite(a))), f"selected {name} not finite"
        errs[name] = float(jnp.max(jnp.abs(a - b_)) / jnp.max(jnp.abs(b_)))
    say(f"  selected ({topk} of the earlier keys) with lse at {shape} on {kv_heads} KV heads bf16, "
        "max|diff|/max|ref|: " + ", ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    for name, e in errs.items():
        assert e <= 2e-2, (name, e)
    errs.update(_check_index_loss(q, k, got_aux[1], selection))
    return {n: float(f"{e:.3g}") for n, e in errs.items()}


def _check_index_loss(q, k, lse, selection, index_heads=16, index_dim=64) -> dict:
    """``dsa.index_loss`` under ``impl`` "flash" against "xla" on one set of
    operands: ``L_I`` to float32 rounding, the gradients to bfloat16's."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops import dsa

    b, _, t, d = q.shape
    kq, kk, kw = jax.random.split(jax.random.key(3), 3)
    qi = jax.random.normal(kq, (b, index_heads, t, index_dim), jnp.bfloat16)
    ki = jax.random.normal(kk, (b, t, index_dim), jnp.bfloat16)
    wi = (jax.random.normal(kw, (b, t, index_heads)) * (index_heads * index_dim) ** -0.5).astype(
        jnp.bfloat16)
    assert dsa.index_loss_impl("flash", q, k, qi) == "mosaic"

    def run(impl):
        loss = lambda qi, ki, wi: dsa.index_loss(  # noqa: E731
            qi, ki, wi, q, k, lse, selection, d ** -0.5, impl)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(qi, ki, wi)

    (got, got_g), (want, want_g) = run("flash"), run("xla")
    errs = {"L_I": abs(float(got) - float(want)) / abs(float(want))}
    for name, a, b_ in zip(("d_qI", "d_kI", "d_wI"), got_g, want_g):
        a, b_ = a.astype(jnp.float32), b_.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(a))), f"index loss {name} not finite"
        errs[name] = float(jnp.max(jnp.abs(a - b_)) / jnp.max(jnp.abs(b_)))
    say(f"  index loss at {tuple(q.shape)} with {index_heads} x {index_dim} index heads bf16, "
        f"kernels against the plain pass: L_I {float(got):.6f} against {float(want):.6f}, "
        "max|diff|/max|ref|: " + ", ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    assert errs["L_I"] <= 1e-4 and max(errs.values()) <= 2e-2, errs
    return errs


def phase_b() -> dict:
    import jax

    from fedml_tpu.parallel.mesh import client_mesh
    from fedml_tpu.sim.engine import FedSim

    one = client_mesh(jax.devices()[:1])
    out = {}
    # both cohort executions: vmapped at batch 4, then sequential at
    # batch 8 (the mode the benchmark's LM cells run)
    for label, kw in (("vmap_b4", {}),
                      ("scan_b8", dict(batch=8, cohort_execution="scan"))):
        trainer, train, cfg = build_lm_problem(**kw)
        out[label] = run_lm_rounds(
            f"lm {label}", FedSim(trainer, train, None, cfg, mesh=one))[0]
        free_device_memory()
    out["kernel_rel_err"] = check_flash_against_reference()
    out["staircase_rel_err"] = check_staircase_against_reference()
    free_device_memory()
    out["selected_rel_err"] = check_selected_against_reference()
    free_device_memory()
    return out


# -- phase c -----------------------------------------------------------------

SHARDED_ARMS = (
    ("fsdp_1x4", (1, 4), "transformer_fsdp"),
    ("fsdp_2x2", (2, 2), "transformer_fsdp"),
    ("tp_1x4", (1, 4), "transformer_tp"),  # 4 heads per rank, flash per rank
)


def run_sharded_arm(label: str, mesh_shape, shard_rules: str,
                    one_device_loss: float) -> dict:
    import dataclasses

    import jax

    from fedml_tpu.sim.engine import FedSim

    trainer, train, cfg = build_lm_problem()
    sim = FedSim(trainer, train, None, dataclasses.replace(
        cfg, mesh_shape=mesh_shape, shard_rules=shard_rules))
    say(f"  {label}: {sim.shard_summary()}")
    res, variables = run_lm_rounds(f"lm {label}", sim)
    # the failure to look for is everything sitting on device 0
    mesh_devices = set(sim.mesh.devices.flat)
    leaves = jax.tree_util.tree_leaves(variables)
    sharded = [x for x in leaves if not x.sharding.is_fully_replicated]
    assert sharded, f"{label}: no variable leaf is sharded"
    for x in leaves:
        assert x.sharding.device_set == mesh_devices, (label, x.sharding)
    rel = abs(res["loss"][0] - one_device_loss) / abs(one_device_loss)
    assert rel <= SHARD_LOSS_RTOL[shard_rules], (label, res["loss"][0],
                                                 one_device_loss)
    in_use = {d.id: d.memory_stats()["bytes_in_use"] for d in mesh_devices}
    assert all(b > 0 for b in in_use.values()), (label, in_use)
    res.update(bytes_in_use=in_use, sharded_leaves=len(sharded),
               round1_loss_rel_diff=float(f"{rel:.3g}"))
    return res


def phase_c(one_device_loss: float) -> dict:
    out = {}
    for label, shape, rules in SHARDED_ARMS:
        out[label] = run_sharded_arm(label, shape, rules, one_device_loss)
        free_device_memory()  # the arm's arrays died with its frame
    return out


# ----------------------------------------------------------------------------


def main() -> None:
    stamp = open_backend()
    n_cache = [cache_entries()]
    figures = {}

    say("phase a: ResNet-56 / CIFAR-10 shapes through main_fedavg.main")
    figures["a"] = phase_a()
    free_device_memory()
    n_cache.append(cache_entries())

    say("phase b: TransformerLM D2048 L8 H16 T1024 V32000 bf16 flash, one device")
    figures["b"] = phase_b()
    n_cache.append(cache_entries())

    if stamp["count"] >= 4:
        say(f"phase c: sharded LM plans over {stamp['count']} devices "
            "(phase a above already ran on the default mesh over all of them)")
        figures["c"] = phase_c(figures["b"]["vmap_b4"]["loss"][0])
        n_cache.append(cache_entries())
    else:
        say(f"phase c (sharded plans on >= 4 devices): DID NOT RUN — "
            f"{stamp['count']} device(s)")

    say("phase d: builder figures, claimed under no metric name: "
        + json.dumps({"device": stamp, "cache_entries_at_phase_ends": n_cache,
                      **figures}))
    print(json.dumps({"ok": True, "device": stamp}), flush=True)


if __name__ == "__main__":
    main()
