"""Plain forward pass of the SmallThinker decoder on one chip's share
(PowerInfer, SmallThinker-21BA3B-Instruct, ``config.json``): token embedding,
blocks in a period of one global and three window layers, a final RMSNorm
and an untied head over the held slice of the vocabulary. One block, with x
its input ``[T, D]``:

    r  = x @ W_r                      float32; the router reads the block's input
    I  = top-k of r;  w = softmax(r[I])
    h  = rmsnorm(x, g1);  q, k, v = h @ W_q, h @ W_k, h @ W_v
    q, k = rope(q), rope(k)           window layers only (rotate-half, whole head)
    a  = causal attention, query head n reads KV head n // (H // H_kv); on a
         window layer key j is visible to query i iff i - window < j <= i
    x1 = x + a @ W_o
    u  = rmsnorm(x1, g2)
    m  = sum over e in I, e held here, of w_e * ((relu(u @ G_e) * (u @ U_e)) @ D_e)
    y  = x1 + m

``jax.numpy`` only, float32: no kernel, no sort, one sequence at a time. The
experts are a dense loop over the held ones with a mask; what the absent
experts would add is left out, as in the program. Attention runs by blocks
of queries against all keys and the head by blocks of tokens, each block
recomputed in the backward pass, so that neither the ``H x T x T`` scores nor
the ``T x V`` logits are ever whole. The parameter tree is read by the
program's names (``tok_embed``; ``block_<i>`` with ``router``, ``norm_attn``,
``attn`` (``q``, ``k``, ``v``, ``o``), ``norm_moe``, ``experts`` (``gate``,
``up``, ``down``); ``norm_f``; ``head``). Every product goes
through ``precision.product``, so that the control can round its operands.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.precision import product

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
HEAD_BLOCK = 1024


class Arch(NamedTuple):
    """The numbers the forward pass needs that the parameter tree does not
    show. ``windows`` holds each layer's window, None for a global layer."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    top_k: int
    experts_first: int
    windows: tuple
    rope_theta: float
    rms_eps: float


def _dot(precision):
    return product(lambda a, b: jnp.dot(a, b, precision=HI), precision)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """[H, T, D]: dimension i turns with dimension i + D/2 by position x
    theta^(-2i/D)."""
    t, d = x.shape[-2], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window, precision):
    """q [H, T, D], k and v [H_kv, T, D] -> [H, T, D], a block of queries at
    a time against all keys."""
    h, t, d = q.shape
    h_kv = k.shape[0]
    block = min(QUERY_BLOCK, t)
    scores = product(lambda a, b: jnp.einsum("ngqd,nkd->ngqk", a, b, precision=HI), precision)
    values = product(lambda a, b: jnp.einsum("ngqk,nkd->ngqd", a, b, precision=HI), precision)
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one_block(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1).reshape(h_kv, h // h_kv, block, d)
        query_pos = lo + jnp.arange(block)[:, None]
        seen = key_pos <= query_pos
        if window is not None:
            seen &= key_pos > query_pos - window
        s = scores(qb, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return values(w, v).reshape(h, block, d)

    out = jax.lax.map(one_block, jnp.arange(0, t, block))  # [T / block, H, block, D]
    return out.transpose(1, 0, 2, 3).reshape(h, t, d)


def _experts(u, ids, weights, gate, up, down, first, precision):
    """The held experts' part of the mixture: a dense loop with a mask."""
    dot = _dot(precision)

    @jax.checkpoint
    def part(e, g, u_k, d_k):
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w_e[:, None] * dot(jax.nn.relu(dot(u, g)) * dot(u, u_k), d_k)

    out, _ = jax.lax.scan(lambda out, xs: (out + part(*xs), None), jnp.zeros_like(u),
                          (jnp.arange(gate.shape[0]), gate, up, down))
    return out


def hidden_states(params, tokens, arch: Arch, precision: str = "f32"):
    """The normalised final hidden states [T, D] of one sequence [T]."""
    dot = _dot(precision)
    x = params["tok_embed"]["embedding"][tokens]
    t = tokens.shape[0]

    def block(x, p, window):
        top, ids = jax.lax.top_k(dot(x, p["router"]["kernel"]), arch.top_k)
        weights = jax.nn.softmax(top, axis=-1)
        h = _rmsnorm(x, p["norm_attn"]["scale"], arch.rms_eps)
        q, k, v = (dot(h, p["attn"][n]["kernel"]).reshape(t, -1, arch.head_dim).transpose(1, 0, 2)
                   for n in ("q", "k", "v"))
        if window is not None:
            q, k = _rope(q, arch.rope_theta), _rope(k, arch.rope_theta)
        a = _attention(q, k, v, window, precision)
        x = x + dot(a.transpose(1, 0, 2).reshape(t, -1), p["attn"]["o"]["kernel"])
        u = _rmsnorm(x, p["norm_moe"]["scale"], arch.rms_eps)
        e = p["experts"]
        return x + _experts(u, ids, weights, e["gate"]["kernel"], e["up"]["kernel"],
                            e["down"]["kernel"], arch.experts_first, precision)

    for i, window in enumerate(arch.windows):
        x = jax.checkpoint(partial(block, window=window))(x, params[f"block_{i}"])
    return _rmsnorm(x, params["norm_f"]["scale"], arch.rms_eps)


def forward(params, tokens, arch: Arch, precision: str = "f32"):
    """Logits [T, V] of one sequence (whole: for sizes a test can hold)."""
    return _dot(precision)(hidden_states(params, tokens, arch, precision),
                           params["head"]["kernel"])


def _seq_loss(params, x, y, arch, precision):
    """Mean next-token loss of one sequence, the head a block of tokens at a
    time."""
    h = hidden_states(params, x, arch, precision)
    t = x.shape[0]
    block = min(HEAD_BLOCK, t)
    dot = _dot(precision)

    @jax.checkpoint
    def one_block(lo):
        logits = dot(jax.lax.dynamic_slice_in_dim(h, lo, block), params["head"]["kernel"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        targets = jax.lax.dynamic_slice_in_dim(y, lo, block)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))

    return jnp.sum(jax.lax.map(one_block, jnp.arange(0, t, block))) / t


@partial(jax.jit, static_argnums=(3, 4))
def _seq_grad(params, x, y, arch, precision):
    return jax.value_and_grad(_seq_loss)(params, x, y, arch, precision)


@partial(jax.jit, donate_argnums=(0,))
def _add_scaled(grad_sum, share, grads):
    return jax.tree.map(lambda a, g: a + share * g, grad_sum, grads)


def loss_and_grad(variables, batch, precision="f32"):
    """Mean next-token loss of a batch {"x": [B, T], "y": [B, T], "arch":
    Arch} and its gradient, one sequence at a time; a batch of one sequence
    holds no second gradient."""
    params = variables["params"]
    b = batch["x"].shape[0]
    loss, grads = _seq_grad(params, batch["x"][0], batch["y"][0], batch["arch"], precision)
    if b > 1:
        loss, grads = loss / b, jax.tree.map(lambda g: g / b, grads)
        for i in range(1, b):
            seq_loss, seq_grads = _seq_grad(params, batch["x"][i], batch["y"][i],
                                            batch["arch"], precision)
            loss, grads = loss + seq_loss / b, _add_scaled(grads, jnp.float32(1.0 / b), seq_grads)
    return loss, grads, {}
