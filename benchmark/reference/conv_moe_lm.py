"""Plain forward pass and training loss of the LFM2 mixture-of-experts decoder
on one chip's share (LiquidAI, LFM2-24B-A2B, ``config.json``: ``model_type``
``lfm2_moe``): token embedding, leading dense layers, routed layers, a final
RMSNorm (the family's ``embedding_norm``) and a head **tied to the
embedding**, over the held slice of the vocabulary. A layer's mixer is a
gated short convolution or grouped-query attention, three to one. With x
``[T, D]`` the block's input and a = rmsnorm(x) (``operator_norm``):

    conv mixer:
      [B | C | z] = a @ W_in                       W_in: D x 3D, no bias; chunks in that order
      u   = B * z
      c_t = sum_{j=0..K-1} w_j * u_{t-(K-1)+j}     depthwise, causal, K = conv_L_cache taps a
                                                   channel, zeros before the first token, no bias
      x1  = x + (C * c) @ W_out
    attention mixer, H query heads on H_kv key and value heads of d columns:
      q = a @ W_q -> [H, T, d];  k, v = a @ W_k, a @ W_v -> [H_kv, T, d]     (no bias)
      q, k = rmsnorm_d(q), rmsnorm_d(k)            one learned scale of d each, shared by the heads
      q, k = rope(q), rope(k)                      rotate-half pairs (i with i + d/2), theta
      s_ij = q_i . k_j / sqrt(d), causal;  query head n reads KV head n // (H / H_kv)
      x1  = x + concat_heads(softmax(s) @ v) @ W_o
    f = rmsnorm(x1)                                ``ffn_norm``
    dense layer:   y = x1 + (silu(f @ W_1) * (f @ W_3)) @ W_2
    routed layer:  s = sigmoid(f @ W_r)            float32
                   I = top-k of (s + b)            b (``expert_bias``) chooses, never weighs
                   w = scale * s[I] / (sum s[I] + 1e-6)
                   y = x1 + sum over e in I, e held here, of
                       w_e * ((silu(f @ W_1e) * (f @ W_3e)) @ W_2e)          no shared expert
    L = mean over i of CE(rmsnorm(x_L,i) @ Emb^T, t_{i+1})

so the embedding leaf takes two gradients, the gather's and the head
product's, and their sum is what a step applies. Departures from the
published description are the configuration file's ``assumed``: the widths of
a head, the tied head, b held fixed by a client.

``jax.numpy`` only, float32: no kernel, no sort, one sequence at a time.
Attention runs a block of queries at a time against all keys, the experts as
a dense loop with a mask and the head a block of tokens at a time
(``reference/mla_moe_lm.py``'s helpers; the grouped attention and the
rotate-half rotation are ``reference/moe_lm.py``'s, the convolution
``reference/kda_moe_lm.py``'s); every block is recomputed in the backward
pass. The parameter tree is read by the program's names
(``tok_embed``; ``block_<i>`` with ``norm_attn``, then ``conv``: ``in``,
``taps``, ``out`` or ``attn``: ``q``, ``k``, ``v``, ``q_norm``, ``k_norm``,
``o``; ``norm_ffn``, then ``mlp`` or ``router``, ``select_bias``, ``experts``;
``norm_f``). Every matrix product and the convolution go through
``precision.product``, so that the control can round their operands.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference.kda_moe_lm import _conv
from benchmark.reference.mla_moe_lm import _add_scaled, _dot, _experts, _glu, _mean_ce, _rmsnorm
from benchmark.reference.moe_lm import _attention, _rope

ROUTE_EPS = 1e-6  # of the chosen scores' sum, as published


class Arch(NamedTuple):
    """The numbers the forward pass needs that the parameter tree does not
    show."""

    mixers: tuple  # "conv" | "gqa" of each block
    num_heads: int
    num_kv_heads: int
    top_k: int
    route_scale: float
    experts_first: int
    rope_theta: float
    rms_eps: float


def short_conv_mixer(a, p, precision):
    """The operator's output [T, D] from the normed stream ``a`` [T, D]."""
    dot = _dot(precision)
    b_gate, c_gate, z = jnp.split(dot(a, p["in"]["kernel"]), 3, axis=-1)
    return dot(c_gate * _conv(b_gate * z, p["taps"]["kernel"], precision), p["out"]["kernel"])


def attention_mixer(a, p, arch: Arch, precision):
    """The attention layer's output [T, D] from the normed stream ``a``."""
    dot = _dot(precision)
    t = a.shape[0]
    heads = lambda y, n: y.reshape(t, n, -1).transpose(1, 0, 2)  # noqa: E731
    q = heads(dot(a, p["q"]["kernel"]), arch.num_heads)
    k = heads(dot(a, p["k"]["kernel"]), arch.num_kv_heads)
    v = heads(dot(a, p["v"]["kernel"]), arch.num_kv_heads)
    q = _rope(_rmsnorm(q, p["q_norm"]["scale"], arch.rms_eps), arch.rope_theta)
    k = _rope(_rmsnorm(k, p["k_norm"]["scale"], arch.rms_eps), arch.rope_theta)
    out = _attention(q, k, v, None, precision)  # grouped: query head n reads KV head n // 4
    return dot(out.transpose(1, 0, 2).reshape(t, -1), p["o"]["kernel"])


def route(f, router, bias, arch: Arch, dot):
    """``(ids [T, k], weights [T, k])`` of the sigmoid router."""
    scores = jax.nn.sigmoid(dot(f, router))
    _, ids = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), arch.top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, arch.route_scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + ROUTE_EPS)


def block(x, p, kind: str, arch: Arch, precision: str):
    """One decoder block over ``x`` [T, D]; dense where ``p`` has no router."""
    dot = _dot(precision)
    a = _rmsnorm(x, p["norm_attn"]["scale"], arch.rms_eps)
    if kind == "conv":
        x = x + short_conv_mixer(a, p["conv"], precision)
    else:
        x = x + attention_mixer(a, p["attn"], arch, precision)
    f = _rmsnorm(x, p["norm_ffn"]["scale"], arch.rms_eps)
    if "router" not in p:
        return x + _glu(f, p["mlp"], dot)
    ids, weights = route(f, p["router"]["kernel"], p["select_bias"]["kernel"][0], arch, dot)
    return x + _experts(f, ids, weights, p["experts"], arch.experts_first, precision)


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
                           params["tok_embed"]["embedding"].T)


def _seq_loss(params, x, y, arch, precision):
    h = last_hidden(params, x, arch, precision)
    return _mean_ce(_rmsnorm(h, params["norm_f"]["scale"], arch.rms_eps),
                    params["tok_embed"]["embedding"].T, y, precision)


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
