"""Plain forward passes of the two ResNets the benchmark runs: the CIFAR
ResNet-56 with BatchNorm (He et al., 3 x 9 basic blocks, 16/32/64 channels)
and ResNet-18 with GroupNorm (Hsieh et al. / Reddi et al.: 3x3 stem, no
max-pool, 64/128/256/512 channels, two groups a norm).

``jax.numpy`` and ``jax.lax.conv_general_dilated`` only. The parameter tree is
read by the names the program's modules give (``Conv_0``, ``BatchNorm_0``,
``BasicBlock_<i>``, ``Dense_0``): a block with a third convolution is a stage
entry (stride 2, 1x1 projection with its own norm); every other block keeps
its input's shape. Departures from the published models are the program's
and are listed in the configuration files.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference.precision import product

BN_MOMENTUM, BN_EPS, GN_EPS, GN_GROUPS = 0.9, 1e-5, 1e-6, 2


def _conv(x, kernel, stride, precision):
    return product(lambda a, k: jax.lax.conv_general_dilated(
        a, k, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST), precision)(x, kernel)


def _norm(x, params, stats, train):
    """BatchNorm (``stats`` given) or GroupNorm (``stats`` None). Returns the
    normalised activations and the new running statistics (or None)."""
    if stats is None:
        n, h, w, c = x.shape
        g = x.reshape(n, h * w, GN_GROUPS, c // GN_GROUPS)
        mean = jnp.mean(g, axis=(1, 3), keepdims=True)
        var = jnp.mean(jnp.square(g - mean), axis=(1, 3), keepdims=True)
        y = ((g - mean) * jax.lax.rsqrt(var + GN_EPS)).reshape(x.shape)
        return y * params["scale"] + params["bias"], None
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        new = {"mean": BN_MOMENTUM * stats["mean"] + (1 - BN_MOMENTUM) * mean,
               "var": BN_MOMENTUM * stats["var"] + (1 - BN_MOMENTUM) * var}
    else:
        mean, var, new = stats["mean"], stats["var"], stats
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    return y * params["scale"] + params["bias"], new


def forward(variables, x, train: bool, precision: str = "f32"):
    """Logits [N, classes] and the new ``batch_stats`` (empty for GroupNorm)."""
    params = variables["params"]
    stats = variables.get("batch_stats")
    kind = "BatchNorm" if stats is not None else "GroupNorm"
    new_stats = {}

    def norm(scope_params, scope_stats, scope_new, i, y):
        name = f"{kind}_{i}"
        y, new = _norm(y, scope_params[name],
                       None if scope_stats is None else scope_stats[name], train)
        if new is not None:
            scope_new[name] = new
        return y

    x = x.astype(jnp.float32)
    y = _conv(x, params["Conv_0"]["kernel"], 1, precision)
    y = jax.nn.relu(norm(params, stats, new_stats, 0, y))
    n_blocks = sum(1 for k in params if k.startswith("BasicBlock_"))
    for b in range(n_blocks):
        name = f"BasicBlock_{b}"
        p = params[name]
        s = None if stats is None else stats[name]
        ns = new_stats.setdefault(name, {}) if stats is not None else {}
        stride = 2 if "Conv_2" in p else 1
        z = _conv(y, p["Conv_0"]["kernel"], stride, precision)
        z = jax.nn.relu(norm(p, s, ns, 0, z))
        z = _conv(z, p["Conv_1"]["kernel"], 1, precision)
        z = norm(p, s, ns, 1, z)
        if "Conv_2" in p:
            y = _conv(y, p["Conv_2"]["kernel"], stride, precision)
            y = norm(p, s, ns, 2, y)
        y = jax.nn.relu(y + z)
    y = jnp.mean(y, axis=(1, 2))
    d = params["Dense_0"]
    logits = product(lambda a, k: jnp.dot(a, k, precision=jax.lax.Precision.HIGHEST),
                     precision)(y, d["kernel"]) + d["bias"]
    return logits, new_stats


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def _loss(params, state, batch, precision):
    logits, new_stats = forward({"params": params, **state}, batch["x"], True, precision)
    new_state = {"batch_stats": new_stats} if "batch_stats" in state else {}
    return jnp.mean(_cross_entropy(logits, batch["y"])), new_state


@partial(jax.jit, static_argnums=(2,))
def _loss_and_grad(variables, batch, precision):
    state = {k: v for k, v in variables.items() if k != "params"}
    (loss, new_state), grads = jax.value_and_grad(_loss, has_aux=True)(
        variables["params"], state, batch, precision)
    return loss, grads, new_state


def loss_and_grad(variables, batch, precision="f32"):
    return _loss_and_grad(variables, batch, precision)


@partial(jax.jit, static_argnums=(3,))
def _eval_sums(variables, x, y, precision):
    logits, _ = forward(variables, x, False, precision)
    return jnp.sum(_cross_entropy(logits, y))


def eval_loss(variables, x, y, block: int = 500, precision="f32") -> float:
    """Mean cross-entropy over (x, y) in eval mode, in blocks of rows."""
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(y), block):
            total += float(_eval_sums(variables, x[i:i + block], y[i:i + block], precision))
    return total / len(y)
