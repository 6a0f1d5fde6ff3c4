"""Plain forward pass and training loss of the JoyAI-LLM-Flash decoder on one
chip's share (jdopensource, JoyAI-LLM-Flash, ``config.json``: ``model_type``
``joyai_llm_flash``, the DeepSeek-V3 block): token embedding, one leading
dense layer, routed layers, a final RMSNorm and an untied head over the held
slice of the vocabulary, and one multi-token-prediction module. One block,
with x its input ``[T, D]``:

    h      = rmsnorm(x)
    c_q    = rmsnorm(h @ W_qa)
    q      = c_q @ W_qb -> H heads of [q_nope | q_rope]
    c_kv | k_rope = h @ W_kva               k_rope: one vector a position, all heads
    [k_nope | v] a head = rmsnorm(c_kv) @ W_kvb
    q_rope, k_rope = rope(.)                adjacent pairs (2i, 2i + 1) turned by
                                            position x theta^(-2i / rope_dim)
    s_ij   = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(nope + rope), causal
    a      = softmax(s) @ v;   x1 = x + concat_heads(a) @ W_o
    u      = rmsnorm(x1)
    dense layer:   y = x1 + (silu(u @ G) * (u @ U)) @ D
    routed layer:  sc = sigmoid(u @ W_r)                      float32
                   I  = top-k of (sc + b)                     b chooses, never weighs
                   w  = scale * sc[I] / (sum sc[I] + 1e-20)
                   y  = x1 + (silu(u @ G_s) * (u @ U_s)) @ D_s
                           + sum over e in I, e held here, of
                             w_e * ((silu(u @ G_e) * (u @ U_e)) @ D_e)

    L_main = mean over i of CE(rmsnorm(h_L,i) @ W_head, t_{i+1})
    MTP (DeepSeek-V3 eq. 21-25, depth 1), for i = 0 .. T-2, with h_L the last
    block's output before the final norm:
        g_i   = [rmsnorm(h_L,i) ; rmsnorm(Emb(t_{i+1}))] @ M
        g'    = one routed block over g (T-1 positions)
        L_mtp = mean over i of CE(rmsnorm(g'_i) @ W_head, t_{i+2})
    L = L_main + lambda * L_mtp

Departures from the published description are the configuration file's
``assumed``: lambda, the order of the two halves of g, a norm of the MTP
module's own before the shared head, b held fixed by a client.

``jax.numpy`` only, float32: no kernel, no sort, one sequence at a time, the
MTP module over the T-1 positions that have a target (the program pads a
position and masks it). The score is the two products the equation states;
no key is concatenated. The experts are a dense loop over the held ones with
a mask; what the absent experts would add is left out, as in the program.
Attention runs by blocks of queries against all keys and the head by blocks
of tokens, each block recomputed in the backward pass. The parameter tree is
read by the program's names (``tok_embed``; ``block_<i>`` with ``norm_attn``,
``attn`` (``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``, ``kv_b``,
``o``), ``norm_ffn``, then ``mlp`` or ``router``, ``select_bias``, ``shared``,
``experts`` (each ``gate``, ``up``, ``down``); ``norm_f``; ``head``;
``mtp_norm_h``, ``mtp_norm_e``, ``mtp_proj``, ``mtp_block``, ``mtp_norm_f``).
Every product goes through ``precision.product``, so that the control can
round its operands.
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
    show."""

    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    layers: int  # blocks of the main model; those with no router are dense
    top_k: int
    route_scale: float
    experts_first: int
    mtp_weight: float  # lambda; 0: no MTP module
    rope_theta: float
    rms_eps: float


def _dot(precision):
    return product(lambda a, b: jnp.dot(a, b, precision=HI), precision)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """[..., T, D]: dimension 2i turns with dimension 2i + 1 by position x
    theta^(-2i/D)."""
    t, d = x.shape[-2], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _attention(q_nope, q_rope, k_nope, k_rope, v, precision):
    """q_nope [H, T, N], q_rope [H, T, R], k_nope [H, T, N], k_rope [T, R]
    (every head's), v [H, T, V] -> [H, T, V], a block of queries at a time
    against all keys; T need not be a multiple of the block."""
    h, t, _ = q_nope.shape
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    q_nope, q_rope = (jnp.pad(q, ((0, 0), (0, pad), (0, 0))) for q in (q_nope, q_rope))
    scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    nope = product(lambda a, b: jnp.einsum("hqd,hkd->hqk", a, b, precision=HI), precision)
    rope = product(lambda a, b: jnp.einsum("hqd,kd->hqk", a, b, precision=HI), precision)
    values = product(lambda a, b: jnp.einsum("hqk,hkd->hqd", a, b, precision=HI), precision)
    key_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one_block(lo):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, lo, block, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, lo, block, axis=1)
        seen = key_pos <= lo + jnp.arange(block)[:, None]
        s = (nope(qn, k_nope) + rope(qr, k_rope)) * scale
        return values(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    out = jax.lax.map(one_block, jnp.arange(0, t + pad, block))  # [blocks, H, block, V]
    return out.transpose(1, 0, 2, 3).reshape(h, t + pad, -1)[:, :t]


def _glu(u, p, dot):
    return dot(jax.nn.silu(dot(u, p["gate"]["kernel"])) * dot(u, p["up"]["kernel"]),
               p["down"]["kernel"])


def _experts(u, ids, weights, p, first, precision):
    """The held experts' part of the mixture: a dense loop with a mask."""
    dot = _dot(precision)

    @jax.checkpoint
    def part(e, g, u_k, d_k):
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w_e[:, None] * dot(jax.nn.silu(dot(u, g)) * dot(u, u_k), d_k)

    gate, up, down = (p[n]["kernel"] for n in ("gate", "up", "down"))
    out, _ = jax.lax.scan(lambda out, xs: (out + part(*xs), None), jnp.zeros_like(u),
                          (jnp.arange(gate.shape[0]), gate, up, down))
    return out


def route(u, router, select_bias, arch: Arch, dot):
    """``(ids [T, k], weights [T, k])`` of the sigmoid router."""
    scores = jax.nn.sigmoid(dot(u, router))
    _, ids = jax.lax.top_k(jax.lax.stop_gradient(scores + select_bias), arch.top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, arch.route_scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def block(x, p, arch: Arch, precision: str):
    """One decoder block over ``x`` [T, D]; dense where ``p`` has no router."""
    dot = _dot(precision)
    t = x.shape[0]
    a = p["attn"]
    heads = lambda y: y.reshape(t, arch.num_heads, -1).transpose(1, 0, 2)  # noqa: E731
    h = _rmsnorm(x, p["norm_attn"]["scale"], arch.rms_eps)
    c_q = _rmsnorm(dot(h, a["q_a"]["kernel"]), a["q_a_norm"]["scale"], arch.rms_eps)
    q = heads(dot(c_q, a["q_b"]["kernel"]))
    kv_a = dot(h, a["kv_a"]["kernel"])
    c_kv = _rmsnorm(kv_a[:, :arch.kv_rank], a["kv_a_norm"]["scale"], arch.rms_eps)
    kv = heads(dot(c_kv, a["kv_b"]["kernel"]))
    out = _attention(
        q[..., :arch.nope_dim], _rope(q[..., arch.nope_dim:], arch.rope_theta),
        kv[..., :arch.nope_dim], _rope(kv_a[:, arch.kv_rank:], arch.rope_theta),
        kv[..., arch.nope_dim:], precision)
    x = x + dot(out.transpose(1, 0, 2).reshape(t, -1), a["o"]["kernel"])
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
    for i in range(arch.layers):
        x = jax.checkpoint(partial(block, arch=arch, precision=precision))(
            x, params[f"block_{i}"])
    return x


def mtp_hidden(params, h_last, tokens, arch: Arch, precision: str = "f32"):
    """The MTP module's output [T-1, D] before its norm: position i has seen
    the main model's state at i and the embedding of token i + 1."""
    g = jnp.concatenate(
        [_rmsnorm(h_last[:-1], params["mtp_norm_h"]["scale"], arch.rms_eps),
         _rmsnorm(params["tok_embed"]["embedding"][tokens[1:]], params["mtp_norm_e"]["scale"],
                  arch.rms_eps)], axis=-1)
    g = _dot(precision)(g, params["mtp_proj"]["kernel"])
    return jax.checkpoint(partial(block, arch=arch, precision=precision))(g, params["mtp_block"])


def forward(params, tokens, arch: Arch, precision: str = "f32"):
    """``(logits [T, V], MTP logits [T-1, V])`` of one sequence (whole: for
    sizes a test can hold)."""
    dot = _dot(precision)
    h = last_hidden(params, tokens, arch, precision)
    g = mtp_hidden(params, h, tokens, arch, precision)
    head = params["head"]["kernel"]
    return (dot(_rmsnorm(h, params["norm_f"]["scale"], arch.rms_eps), head),
            dot(_rmsnorm(g, params["mtp_norm_f"]["scale"], arch.rms_eps), head))


def _mean_ce(h, head, targets, precision):
    """Mean cross-entropy of ``h @ head`` [n, V] against ``targets`` [n], the
    head a block of tokens at a time; n need not be a multiple of the block."""
    n = h.shape[0]
    block_n = min(HEAD_BLOCK, n)
    pad = -n % block_n
    h, targets = jnp.pad(h, ((0, pad), (0, 0))), jnp.pad(targets, (0, pad))
    dot = _dot(precision)

    @jax.checkpoint
    def one_block(lo):
        logp = jax.nn.log_softmax(dot(jax.lax.dynamic_slice_in_dim(h, lo, block_n), head), axis=-1)
        picked = jnp.take_along_axis(
            logp, jax.lax.dynamic_slice_in_dim(targets, lo, block_n)[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(lo + jnp.arange(block_n) < n, picked, 0.0))

    return jnp.sum(jax.lax.map(one_block, jnp.arange(0, n + pad, block_n))) / n


def _seq_loss(params, x, y, arch, precision):
    """The training loss of one sequence: next-token, plus ``mtp_weight``
    times the MTP module's over the T-1 positions that have a second-next
    token."""
    head = params["head"]["kernel"]
    h = last_hidden(params, x, arch, precision)
    loss = _mean_ce(_rmsnorm(h, params["norm_f"]["scale"], arch.rms_eps), head, y, precision)
    if arch.mtp_weight:
        g = mtp_hidden(params, h, x, arch, precision)
        loss = loss + arch.mtp_weight * _mean_ce(
            _rmsnorm(g, params["mtp_norm_f"]["scale"], arch.rms_eps), head, y[1:], precision)
    return loss


@partial(jax.jit, static_argnums=(3, 4))
def _seq_grad(params, x, y, arch, precision):
    return jax.value_and_grad(_seq_loss)(params, x, y, arch, precision)


@partial(jax.jit, donate_argnums=(0,))
def _add_scaled(grad_sum, share, grads):
    return jax.tree.map(lambda a, g: a + share * g, grad_sum, grads)


def loss_and_grad(variables, batch, precision="f32"):
    """Mean training loss of a batch {"x": [B, T], "y": [B, T], "arch": Arch}
    and its gradient, one sequence at a time; a batch of one sequence holds
    no second gradient."""
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
