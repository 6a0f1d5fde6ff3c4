"""Plain forward pass of the GPT-2-style decoder the Cerebras-GPT
configurations run (arXiv:2304.03208): learned token and position
embeddings, pre-LayerNorm blocks of causal multi-head attention and a GELU
MLP of four times the width, a final LayerNorm and an output head.

``jax.numpy`` only: no kernel, no scan, one sequence at a time with the
gradients of a batch accumulated, because plain attention at T 2048 in
float32 keeps a gigabyte of scores a layer at batch 4. The parameter tree is
read by the program's names (``tok_embed``, ``pos_embed``, ``block_<i>`` with
``LayerNorm_0``, ``MultiHeadSelfAttention_0`` (``qkv``, ``proj``),
``LayerNorm_1``, ``Dense_0``, ``Dense_1``; ``ln_f``; ``head``). The program's
departures from the published model (no bias on qkv and proj, an untied head
with a bias, tanh GELU, no dropout) are followed here and listed in the
configuration files.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference.precision import product

LN_EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p, precision):
    y = product(lambda a, k: jnp.dot(a, k, precision=HI), precision)(x, p["kernel"])
    return y + p["bias"] if "bias" in p else y


def forward(params, tokens, num_heads: int, precision: str = "f32"):
    """Logits [T, V] for one sequence of tokens [T]."""
    t = tokens.shape[0]
    h = params["tok_embed"]["embedding"][tokens] + params["pos_embed"][:t]
    d = h.shape[-1] // num_heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    n_layers = sum(1 for k in params if k.startswith("block_"))
    for i in range(n_layers):
        p = params[f"block_{i}"]
        a = p["MultiHeadSelfAttention_0"]
        qkv = _dense(_layer_norm(h, p["LayerNorm_0"]), a["qkv"], precision)
        q, k, v = (x.reshape(t, num_heads, d).transpose(1, 0, 2)
                   for x in jnp.split(qkv, 3, axis=-1))
        s = product(lambda a, b: jnp.einsum("hqd,hkd->hqk", a, b, precision=HI),
                    precision)(q, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = product(lambda a, b: jnp.einsum("hqk,hkd->hqd", a, b, precision=HI),
                    precision)(w, v)
        h = h + _dense(o.transpose(1, 0, 2).reshape(t, -1), a["proj"], precision)
        m = _dense(_layer_norm(h, p["LayerNorm_1"]), p["Dense_0"], precision)
        h = h + _dense(jax.nn.gelu(m, approximate=True), p["Dense_1"], precision)
    return _dense(_layer_norm(h, params["ln_f"]), params["head"], precision)


def _seq_loss(params, x, y, num_heads, precision):
    logp = jax.nn.log_softmax(forward(params, x, num_heads, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@partial(jax.jit, static_argnums=(5, 6), donate_argnums=(1,))
def _seq_step(params, grad_sum, x, y, share, num_heads, precision):
    """One sequence's loss, and its gradient added into ``grad_sum`` in place
    (donated), so that no second whole-model gradient is held."""
    loss, grads = jax.value_and_grad(_seq_loss)(params, x, y, num_heads, precision)
    return loss, jax.tree.map(lambda a, g: a + share * g, grad_sum, grads)


def loss_and_grad(variables, batch, precision="f32"):
    """Mean next-token loss of a batch {"x": [B, T], "y": [B, T],
    "num_heads": H} and its gradient, one sequence at a time."""
    params = variables["params"]
    b = batch["x"].shape[0]
    grad_sum = jax.tree.map(jnp.zeros_like, params)
    loss = 0.0
    for i in range(b):
        seq_loss, grad_sum = _seq_step(params, grad_sum, batch["x"][i], batch["y"][i],
                                       jnp.float32(1.0 / b), batch["num_heads"], precision)
        loss = loss + seq_loss / b
    return loss, grad_sum, {}
