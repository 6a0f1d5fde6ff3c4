"""The plain federated round every cell's ``correct`` rests on.

Float32 under ``jax.default_matmul_precision("highest")``; a Python loop over
rounds, clients and local steps: gradient, SGD update (weight decay, then
momentum, as ``optax.chain(add_decayed_weights, sgd)`` orders them), then the
sample-weighted mean of the clients' variables. It imports nothing of the
program under test and is handed nothing the program made: the initial
variables, the data and the batches all come from the benchmark's own
generator (``benchmark/traffic.py``).

``model`` is one of the sibling reference modules: it gives
``loss_and_grad(variables, batch, precision)`` -> (loss, grads, new_state)
with its own forward pass. ``precision`` is "f32" for the reference proper;
"bf16" and "fp8" are the controls that must come out as not correct.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def sgd_step(params, momentum_buf, grads, lr, wd, mu):
    """One SGD step; returns (params, momentum_buf)."""
    momentum_buf = jax.tree.map(lambda p, m, g: mu * m + g + wd * p,
                                params, momentum_buf, grads)
    return jax.tree.map(lambda p, m: p - lr * m, params, momentum_buf), momentum_buf


_sgd_step = jax.jit(sgd_step, static_argnums=(3, 4, 5), donate_argnums=(0, 1))


def client_update(model, variables, batches, opt, precision):
    """Local training of one client from the broadcast ``variables`` over its
    ``batches`` (an iterable of batch dicts). Returns the client's variables
    and the mean of its per-step losses."""
    params = jax.tree.map(jnp.array, variables["params"])  # a copy: donated below
    state = {k: v for k, v in variables.items() if k != "params"}
    buf = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for batch in batches:
        loss, grads, state = model.loss_and_grad(
            {"params": params, **state}, batch, precision)
        params, buf = _sgd_step(params, buf, grads, opt["lr"],
                                opt.get("weight_decay", 0.0),
                                opt.get("momentum", 0.0))
        losses.append(loss)
    return {"params": params, **state}, jnp.mean(jnp.stack(losses))


@partial(jax.jit, donate_argnums=(0,))
def _add_scaled(acc, share, tree):
    return jax.tree.map(lambda a, t: a + share * t, acc, tree)


def run_rounds(model, variables, rounds, opt, precision="f32"):
    """Follow ``rounds``: a list, one entry a round, of lists of
    ``(weight, batches)`` per cohort client. Returns the final variables (on
    the host) and each round's sample-weighted mean local training loss.

    The global variables stay on the host between clients, so that the
    device holds the running weighted mean and one client's parameters,
    momentum and gradients, and nothing else: a 0.5B-parameter client model
    then fits beside its own temporaries."""
    losses = []
    variables = jax.tree.map(np.asarray, variables)
    with jax.default_matmul_precision("highest"):
        for cohort in rounds:
            total = float(sum(w for w, _ in cohort))
            acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), variables)
            round_loss = 0.0
            for weight, batches in cohort:
                local, loss = client_update(model, variables, batches(), opt, precision)
                acc = _add_scaled(acc, jnp.float32(weight / total), local)
                round_loss += float(loss) * weight / total
                del local
            variables = jax.tree.map(np.asarray, acc)
            del acc
            losses.append(round_loss)
    return variables, losses


# -- the numbers ``correct`` compares ----------------------------------------


def leaf_norms(tree) -> dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(jnp.ravel(x).astype(jnp.float32)))
            for p, x in flat}


def update_numbers(old, new_prog, new_ref) -> dict[str, float]:
    """The program's update against the reference's, from the same start.

    ``norm_gap``: by the worst leaf, the gap between the two updates' norms
    over the reference's norm of that leaf or of the median leaf, whichever
    is larger (some leaves hardly move). ``update_rel_l2.<subtree>``: the
    norm of the difference of the two updates over the reference's, per
    top-level subtree."""
    sub = lambda a, b: jax.tree.map(  # noqa: E731
        lambda x, y: jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32), a, b)
    d_prog, d_ref = sub(new_prog, old), sub(new_ref, old)
    n_prog, n_ref = leaf_norms(d_prog), leaf_norms(d_ref)
    med = float(np.median(list(n_ref.values())))
    out = {"norm_gap": max(
        abs(n_prog[k] - n_ref[k]) / max(n_ref[k], med, 1e-30) for k in n_ref)}
    for top in d_ref:
        num = sum(v ** 2 for v in leaf_norms(sub(d_prog[top], d_ref[top])).values()) ** 0.5
        den = sum(v ** 2 for v in leaf_norms(d_ref[top]).values()) ** 0.5
        out[f"update_rel_l2.{top}"] = num / max(den, 1e-30)
    return out
