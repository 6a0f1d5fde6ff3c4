"""Plain forward pass and training loss of the Kimi-Linear decoder on one
chip's share (moonshotai, Kimi-Linear-48B-A3B-Instruct, ``config.json``:
``model_type`` ``kimi_linear``; the architecture's report is arXiv:2510.26692):
token embedding, one leading dense layer, routed layers, a final RMSNorm and
an untied head over the held slice of the vocabulary. A layer's mixer is Kimi
Delta Attention (KDA) or latent attention (MLA), three to one. With x
``[T, D]`` the block's input and h = rmsnorm(x):

    KDA mixer, H heads, d key and value columns a head:
      q, k, v = silu(conv4(h @ W_q)), silu(conv4(h @ W_k)), silu(conv4(h @ W_v))
                conv4: causal depthwise convolution along T, 4 taps a channel
                (tap 3 meets the token itself), zeros before the first token,
                no bias
      q_t, k_t a head:  q_t = l2norm(q_t) * d^-0.5,  k_t = l2norm(k_t)   (eps 1e-6)
      g_t    = -exp(A_log[head]) * softplus((h @ W_fa) @ W_fb + dt_bias)   [T, H, d] <= 0
      beta_t = sigmoid(h @ W_b)                                            [T, H]
      S_0 = 0;  S~ = Diag(exp g_t) S_{t-1}                                 S: [d, d] a head
                S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
                o_t = S_t^T q_t
      x1 = x + concat_heads(rmsnorm_d(o_t) * sigmoid((h @ W_ga) @ W_gb + b_g)) @ W_o
    MLA mixer:
      q = h @ W_q -> H heads of [q_a | q_b]                  (no query latent)
      c_kv | k_b = h @ W_kva                                 k_b: one a position, all heads
      [k_a | v] a head = rmsnorm(c_kv) @ W_kvb
      s_ij = (q_a,i . k_a,j + q_b,i . k_b,j) / sqrt(|q_a| + |q_b|), causal,
             NO rotation of q_b or k_b (``mla_use_nope``)
      x1 = x + concat_heads(softmax(s) @ v) @ W_o
    u = rmsnorm(x1)
    dense layer:   y = x1 + (silu(u @ G) * (u @ U)) @ D
    routed layer:  sc = sigmoid(u @ W_r)                     float32
                   I  = top-k of (sc + b)                    b chooses, never weighs
                   w  = scale * sc[I] / (sum sc[I] + 1e-20)
                   y  = x1 + shared(u) + sum over e in I, e held here, of
                        w_e * ((silu(u @ G_e) * (u @ U_e)) @ D_e)
    L = mean over i of CE(rmsnorm(h_L,i) @ W_head, t_{i+1})

Departures from the published description are the configuration file's
``assumed``: no bias on the convolution and SiLU after it; ``A_log`` one
scalar a head and ``dt_bias`` one a channel; the output gate a sigmoid with a
bias on its second matrix; the eps of l2norm and of the norms; the ``q_b`` /
``k_b`` columns kept and left unrotated; b held fixed by a client.

``jax.numpy`` only, float32: no kernel, no sort, no chunked algebra, one
sequence at a time. **The recurrence runs token by token**: a ``lax.scan``
over the tokens of a block inside a ``lax.scan`` over blocks of
``TOKEN_BLOCK`` tokens, each block recomputed in the backward pass, so that
the gradient holds the state at the blocks' boundaries and one block's
steps, not all T states. Latent attention by blocks of queries, the experts
as a dense loop with a mask and the head by blocks of tokens are
``reference/mla_moe_lm.py``'s. The parameter tree is read by the program's
names (``tok_embed``; ``block_<i>`` with ``norm_attn``, ``attn``: for KDA ``q``,
``k``, ``v``, ``q_conv``, ``k_conv``, ``v_conv``, ``f_a``, ``f_b``, ``dt_bias``,
``A_log``, ``b``, ``g_a``, ``g_b``, ``o_norm``, ``o``; for MLA ``q``, ``kv_a``,
``kv_a_norm``, ``kv_b``, ``o``; ``norm_ffn``, then ``mlp`` or ``router``,
``select_bias``, ``shared``, ``experts``; ``norm_f``; ``head``). Every product
and convolution goes through ``precision.product``, so that the control can
round its operands.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.mla_moe_lm import (
    HI, _add_scaled, _attention, _dot, _experts, _glu, _mean_ce, _rmsnorm, route)
from benchmark.reference.precision import product

TOKEN_BLOCK = 64


class Arch(NamedTuple):
    """The numbers the forward pass needs that the parameter tree does not
    show. ``top_k``, ``route_scale`` and ``experts_first`` are read by
    ``reference/mla_moe_lm.py``'s ``route`` under these names."""

    mixers: tuple  # "kda" | "mla" of each block
    num_heads: int  # of the latent-attention layers
    nope_dim: int
    kv_rank: int
    kda_heads: int
    top_k: int
    route_scale: float
    experts_first: int
    rms_eps: float


def _conv(x, w, precision):
    """[T, C] under taps [K, C]: y_t = sum_j w_j * x_{t - (K - 1) + j}."""
    def direct(x, w):
        taps, t = w.shape[0], x.shape[0]
        padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        return sum(padded[j:j + t] * w[j] for j in range(taps))

    return product(direct, precision)(x, w)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, precision="f32"):
    """The recurrence of one sequence, token by token: q, k, g [H, T, d_k], v
    [H, T, d_v], beta [H, T] -> [H, T, d_v]."""
    h, t, d_k = q.shape
    read = product(lambda s, x: jnp.einsum("hkv,hk->hv", s, x, precision=HI), precision)
    write = product(lambda x, u: jnp.einsum("hk,hv->hkv", x, u, precision=HI), precision)

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        s = s + write(k_t, b_t[:, None] * (v_t - read(s, k_t)))
        return s, read(s, q_t)

    pad = -t % TOKEN_BLOCK
    by_block = lambda x: jnp.moveaxis(  # noqa: E731  [H, T, ...] -> [blocks, TOKEN_BLOCK, H, ...]
        jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)), 1, 0
    ).reshape(-1, TOKEN_BLOCK, h, *x.shape[2:])
    s0 = jnp.zeros((h, d_k, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(jax.checkpoint(lambda s, xs: jax.lax.scan(token, s, xs)), s0,
                          tuple(by_block(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out.reshape(t + pad, h, -1)[:t], 0, 1)


def delta_attention(h, a, arch: Arch, precision: str):
    """The KDA mixer's output [T, D] from the normed stream ``h`` [T, D]."""
    dot = _dot(precision)
    t, n = h.shape[0], arch.kda_heads
    heads = lambda y: y.reshape(t, n, -1).transpose(1, 0, 2)  # noqa: E731
    q, k, v = (heads(jax.nn.silu(_conv(dot(h, a[name]["kernel"]), a[name + "_conv"]["kernel"],
                                       precision))) for name in ("q", "k", "v"))
    q, k = _l2norm(q) * q.shape[-1] ** -0.5, _l2norm(k)
    step = jax.nn.softplus(dot(dot(h, a["f_a"]["kernel"]), a["f_b"]["kernel"])
                           + a["dt_bias"]["kernel"][0])
    g = -jnp.exp(a["A_log"]["kernel"][0])[:, None, None] * heads(step)
    beta = jax.nn.sigmoid(dot(h, a["b"]["kernel"])).T
    o = _rmsnorm(delta_rule(q, k, v, g, beta, precision), a["o_norm"]["scale"], arch.rms_eps)
    gate = jax.nn.sigmoid(dot(dot(h, a["g_a"]["kernel"]), a["g_b"]["kernel"]) + a["g_b"]["bias"])
    return dot(o.transpose(1, 0, 2).reshape(t, -1) * gate, a["o"]["kernel"])


def latent_attention(h, a, arch: Arch, precision: str):
    """The MLA mixer's output [T, D]: no query latent, no rotation."""
    dot = _dot(precision)
    t = h.shape[0]
    heads = lambda y: y.reshape(t, arch.num_heads, -1).transpose(1, 0, 2)  # noqa: E731
    q = heads(dot(h, a["q"]["kernel"]))
    kv_a = dot(h, a["kv_a"]["kernel"])
    c_kv = _rmsnorm(kv_a[:, :arch.kv_rank], a["kv_a_norm"]["scale"], arch.rms_eps)
    kv = heads(dot(c_kv, a["kv_b"]["kernel"]))
    out = _attention(q[..., :arch.nope_dim], q[..., arch.nope_dim:], kv[..., :arch.nope_dim],
                     kv_a[:, arch.kv_rank:], kv[..., arch.nope_dim:], precision)
    return dot(out.transpose(1, 0, 2).reshape(t, -1), a["o"]["kernel"])


def block(x, p, kind: str, arch: Arch, precision: str):
    """One decoder block over ``x`` [T, D]; dense where ``p`` has no router."""
    dot = _dot(precision)
    h = _rmsnorm(x, p["norm_attn"]["scale"], arch.rms_eps)
    mixer = delta_attention if kind == "kda" else latent_attention
    x = x + mixer(h, p["attn"], arch, precision)
    u = _rmsnorm(x, p["norm_ffn"]["scale"], arch.rms_eps)
    if "router" not in p:
        return x + _glu(u, p["mlp"], dot)
    ids, weights = route(u, p["router"]["kernel"], p["select_bias"]["kernel"][0], arch, dot)
    return x + _glu(u, p["shared"], dot) + _experts(
        u, ids, weights, p["experts"], arch.experts_first, precision)


def last_hidden(params, tokens, arch: Arch, precision: str = "f32"):
    """The last block's output [T, D] of one sequence [T], before the final
    norm."""
    x = params["tok_embed"]["embedding"][tokens]
    for i, kind in enumerate(arch.mixers):
        x = jax.checkpoint(partial(block, kind=kind, arch=arch, precision=precision))(
            x, params[f"block_{i}"])
    return x


def forward(params, tokens, arch: Arch, precision: str = "f32"):
    """Logits [T, V] of one sequence (whole: for sizes a test can hold)."""
    h = last_hidden(params, tokens, arch, precision)
    return _dot(precision)(_rmsnorm(h, params["norm_f"]["scale"], arch.rms_eps),
                           params["head"]["kernel"])


def _seq_loss(params, x, y, arch, precision):
    h = last_hidden(params, x, arch, precision)
    return _mean_ce(_rmsnorm(h, params["norm_f"]["scale"], arch.rms_eps),
                    params["head"]["kernel"], y, precision)


@partial(jax.jit, static_argnums=(3, 4))
def _seq_grad(params, x, y, arch, precision):
    return jax.value_and_grad(_seq_loss)(params, x, y, arch, precision)


def loss_and_grad(variables, batch, precision="f32"):
    """Mean training loss of a batch {"x": [B, T], "y": [B, T], "arch": Arch}
    and its gradient, one sequence at a time."""
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
