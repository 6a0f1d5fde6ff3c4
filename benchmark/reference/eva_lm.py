"""Plain forward pass, training loss and gradients of the EvaByte decoder
(EvaByte, EvaByte 6.5B, ``config.json``: ``model_type`` ``evabyte``,
``attention_class`` ``eva``): a byte embedding, layers of EVA attention and a
dense gated feed-forward, a final norm and ``num_pred_heads`` untied heads on
one stream. EVA is Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates" (arXiv:2302.04542) in the causal, deterministic form of the release.
With x ``[T, D]`` a block's input, ``norm(x) = x / rms(x) * (1 + g)`` (``g``
from zero: ``norm_add_unit_offset``), H heads of d = D / H columns, s = d^-1/2,
windows of W positions and chunks of C:

    a = norm(x);  q, k, v = a W_q, a W_k, a W_v -> [H, T, d]       (no bias)
    q, k = rope(q), rope(k)              rotate-half pairs (i with i + d/2), theta
    chunk c (positions C c ... C c + C - 1), a head at a time:
      alpha = softmax_j(s <phi, k_j>)    over the chunk; phi = ``adaptive_phi`` [H, d]
      k~_c  = sum_j alpha_j k_j + mu     mu = ``adaptive_mu_k`` [H, d]
      v~_c  = sum_j alpha_j v_j
    query i, in window w = i // W, sees the keys j <= i of its own window
      (scores s <q_i, k_j>) and the summaries c < (W / C) w of the windows before
      it (scores s <q_i, k~_c>); ONE softmax over the union:
      o_i = sum_j p_ij v_j + sum_c p_ic v~_c
    x1 = x + concat_heads(o) W_o
    y  = x1 + (silu(f W_1) * (f W_3)) W_2,   f = norm(x1)
    logits = norm(x_L) W_head -> [T, P, V];  head p at position t predicts byte t + 1 + p
    L = mean over t and p of CE(logits[t, p], byte[t + 1 + p])     (equal weights)

Departures from the release, each also under ``assumed`` in the configuration
file (there is no network here: what ``config.json`` does not state was not
looked up): the scores of ``phi`` carry ``s``; ``mu`` is added after the pool;
positions are applied to the keys before the pool; a window's own chunks are
never summarised for it (the window's keys are exact); the last window's
summaries are computed and read by nobody; the eight heads' losses weigh the
same; no dropout; the stream, the norms' statistics and the logits are
float32 as ``fp32_skip_add`` and ``fp32_logits`` say (here everything is).

Float32 at ``highest``. It fits beside ``fedavg.py``'s four copies of an
821M-parameter model (13.1 GB of the chip's 16.9) because it never holds more
than half a layer's intermediates: the forward keeps each half-layer's input
on the HOST, and the backward takes the half-layers last to first, each its
own ``jax.vjp`` (the half's forward runs again) whose parameter gradients go
to the host as they are made, the attention a block of 512
queries of one window at a time and the feed-forward and the heads a block of
2,048 rows at a time, each block under ``jax.checkpoint``. The parameter tree
is read by the program's names (``tok_embed``; ``block_<i>`` with
``norm_attn``, ``attn``: ``q``, ``k``, ``v``, ``o``, ``adaptive_phi``,
``adaptive_mu_k``; ``norm_ffn``, ``mlp``: ``gate``, ``up``, ``down``;
``norm_f``; ``head``). Every matrix product goes through
``precision.product``, so that the control can round their operands. ``Arch``
can leave the remote part or ``mu`` out: the faults the limits are held to.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.precision import product

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512  # queries a step of the attention
ROW_BLOCK = 2048  # rows a step of the feed-forward and of the heads


class Arch(NamedTuple):
    """The numbers the forward pass needs that the parameter tree does not
    show; ``remote`` and ``mu`` False break the layer on purpose."""

    num_heads: int
    window: int
    chunk: int
    pred_heads: int
    rope_theta: float
    rms_eps: float
    remote: bool = True
    mu: bool = True


def _dot(precision):
    return product(lambda a, b: jnp.dot(a, b, precision=HI), precision)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """[H, T, d]: dimension i turns with dimension i + d/2 by position x
    theta^(-2i/d)."""
    t, d = x.shape[-2], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _blocks(n: int, want: int) -> int:
    """The block of ``n`` rows: ``want`` where it divides ``n``, else all."""
    return want if n % want == 0 else n


def summaries(k, v, phi, mu, arch: Arch):
    """``(k~, v~)`` [H, T / C, d] of ``k``, ``v`` [H, T, d]."""
    h, t, d = k.shape
    kc, vc = (x.reshape(h, t // arch.chunk, arch.chunk, d) for x in (k, v))
    a = d ** -0.5 * jnp.sum(kc * phi[:, None, None, :], axis=-1)
    alpha = jax.nn.softmax(a, axis=-1)[..., None]
    k_sum = jnp.sum(alpha * kc, axis=2)
    if arch.mu:
        k_sum = k_sum + mu[:, None, :]
    return k_sum, jnp.sum(alpha * vc, axis=2)


def eva_attention(q, k, v, phi, mu, arch: Arch, precision: str):
    """[H, T, d] -> [H, T, d]: a window at a time (a Python loop), inside it a
    block of queries at a time against the window's keys and the summaries of
    the windows before, one softmax over both."""
    h, t, d = q.shape
    w = min(arch.window, t)
    if t % w or w % arch.chunk:
        raise ValueError(f"T {t} is not whole windows of {arch.window} in chunks of {arch.chunk}")
    scores = product(lambda a, b: jnp.einsum("hqd,hkd->hqk", a, b, precision=HI), precision)
    values = product(lambda a, b: jnp.einsum("hqk,hkd->hqd", a, b, precision=HI), precision)
    k_sum, v_sum = summaries(k, v, phi, mu, arch)
    block = _blocks(w, QUERY_BLOCK)
    key_pos = jnp.arange(w)[None, :]
    outs = []
    for i in range(t // w):
        q_w, k_w, v_w = (x[:, i * w:(i + 1) * w] for x in (q, k, v))
        n_r = (w // arch.chunk) * i if arch.remote else 0
        k_r, v_r = k_sum[:, :n_r], v_sum[:, :n_r]

        @jax.checkpoint
        def one_block(lo, q_w=q_w, k_w=k_w, v_w=v_w, k_r=k_r, v_r=v_r, n_r=n_r):
            qb = jax.lax.dynamic_slice_in_dim(q_w, lo, block, axis=1)
            seen = key_pos <= lo + jnp.arange(block)[:, None]
            s = jnp.where(seen, scores(qb, k_w) * d ** -0.5, -jnp.inf)
            if n_r:
                s = jnp.concatenate([s, scores(qb, k_r) * d ** -0.5], axis=-1)
            p = jax.nn.softmax(s, axis=-1)
            out = values(p[..., :w], v_w)
            return out + values(p[..., w:], v_r) if n_r else out

        out = jax.lax.map(one_block, jnp.arange(0, w, block))  # [w / block, H, block, d]
        outs.append(out.transpose(1, 0, 2, 3).reshape(h, w, d))
    return jnp.concatenate(outs, axis=1)


def attention_half(x, p, arch: Arch, precision: str):
    """``x + W_o(eva(norm(x)))`` over ``x`` [T, D]; ``p`` the block's tree."""
    dot = _dot(precision)
    t = x.shape[0]
    a = _norm(x, p["norm_attn"]["scale"], arch.rms_eps)
    heads = lambda y: y.reshape(t, arch.num_heads, -1).transpose(1, 0, 2)  # noqa: E731
    q, k, v = (heads(dot(a, p["attn"][n]["kernel"])) for n in ("q", "k", "v"))
    q, k = _rope(q, arch.rope_theta), _rope(k, arch.rope_theta)
    out = eva_attention(q, k, v, p["attn"]["adaptive_phi"]["kernel"],
                        p["attn"]["adaptive_mu_k"]["kernel"], arch, precision)
    return x + dot(out.transpose(1, 0, 2).reshape(t, -1), p["attn"]["o"]["kernel"])


def mlp_half(x, p, arch: Arch, precision: str):
    """``x + down(silu(gate(f)) * up(f))``, ``f = norm(x)``, a block of rows
    at a time."""
    dot = _dot(precision)
    block = _blocks(x.shape[0], ROW_BLOCK)

    @jax.checkpoint
    def one_block(xb):
        f = _norm(xb, p["norm_ffn"]["scale"], arch.rms_eps)
        m = p["mlp"]
        return xb + dot(jax.nn.silu(dot(f, m["gate"]["kernel"])) * dot(f, m["up"]["kernel"]),
                        m["down"]["kernel"])

    return jax.lax.map(one_block, x.reshape(-1, block, x.shape[1])).reshape(x.shape)


def head_loss(x, p, y, arch: Arch, precision: str):
    """Mean cross-entropy of the ``pred_heads`` heads over ``x`` [T, D]
    against ``y`` [T, P]; ``p`` holds ``norm_f`` and ``head``."""
    dot = _dot(precision)
    t = x.shape[0]
    block = _blocks(t, ROW_BLOCK)

    @jax.checkpoint
    def one_block(xy):
        xb, yb = xy
        logits = dot(_norm(xb, p["norm_f"]["scale"], arch.rms_eps), p["head"]["kernel"])
        logp = jax.nn.log_softmax(logits.reshape(block, arch.pred_heads, -1), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yb[..., None], axis=-1))

    blocks = (x.reshape(-1, block, x.shape[1]), y.reshape(-1, block, arch.pred_heads))
    return jnp.sum(jax.lax.map(one_block, blocks)) / (t * arch.pred_heads)


def forward(params, tokens, arch: Arch, precision: str = "f32"):
    """Logits [T, P, V] of one sequence (whole: for sizes a test can hold)."""
    x = params["tok_embed"]["embedding"][tokens]
    for i in range(_layers(params)):
        x = mlp_half(attention_half(x, params[f"block_{i}"], arch, precision),
                     params[f"block_{i}"], arch, precision)
    logits = _dot(precision)(_norm(x, params["norm_f"]["scale"], arch.rms_eps),
                             params["head"]["kernel"])
    return logits.reshape(x.shape[0], arch.pred_heads, -1)


def _layers(params) -> int:
    return sum(1 for k in params if k.startswith("block_"))


HALVES = {"attn": attention_half, "mlp": mlp_half}
LEAVES = {"attn": ("norm_attn", "attn"), "mlp": ("norm_ffn", "mlp")}


@partial(jax.jit, static_argnums=(2, 3, 4))
def _half_fwd(x, p, half, arch, precision):
    return HALVES[half](x, p, arch, precision)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _half_bwd(x, p, g, half, arch, precision):
    """(dL/dx, dL/dp) of one half-layer from its input and dL/d(output)."""
    _, vjp = jax.vjp(lambda x, p: HALVES[half](x, p, arch, precision), x, p)
    return vjp(g)


@partial(jax.jit, static_argnums=(3, 4))
def _head_grad(x, p, y, arch, precision):
    return jax.value_and_grad(head_loss, argnums=(0, 1))(x, p, y, arch, precision)


@partial(jax.jit, static_argnums=(2,))
def _embed_grad(tokens, g, rows):
    return jnp.zeros((rows, g.shape[1]), g.dtype).at[tokens].add(g)


def _seq_grad(params, tokens, y, arch, precision):
    """(loss, grads) of one sequence, half a layer on the device at a time:
    the halves' inputs wait on the host for the backward pass."""
    n = _layers(params)
    halves = [(i, half) for i in range(n) for half in ("attn", "mlp")]
    part = lambda i, half: {k: params[f"block_{i}"][k] for k in LEAVES[half]}  # noqa: E731
    x = params["tok_embed"]["embedding"][tokens]
    inputs = []
    for i, half in halves:
        inputs.append(np.asarray(x))
        x = _half_fwd(x, part(i, half), half, arch, precision)
    top = {"norm_f": params["norm_f"], "head": params["head"]}
    loss, (g, g_top) = _head_grad(x, top, jnp.asarray(y), arch, precision)
    to_host = lambda tree: jax.tree.map(np.asarray, tree)  # noqa: E731
    grads = {f"block_{i}": {} for i in range(n)}
    grads.update(to_host(g_top))
    for (i, half), x_in in zip(reversed(halves), reversed(inputs)):
        g, g_p = _half_bwd(jnp.asarray(x_in), part(i, half), g, half, arch, precision)
        grads[f"block_{i}"].update(to_host(g_p))
    rows = params["tok_embed"]["embedding"].shape[0]
    grads["tok_embed"] = {"embedding": np.asarray(_embed_grad(jnp.asarray(tokens), g, rows))}
    return loss, grads


def _add_scaled(grad_sum, share, grads):
    return jax.tree.map(lambda a, g: a + share * g, grad_sum, grads)


def loss_and_grad(variables, batch, precision="f32"):
    """Mean training loss of a batch {"x": [B, T], "y": [B, T, P], "arch":
    Arch} and its gradient, one sequence at a time. The gradient is handed
    back on the HOST (numpy leaves, which ``fedavg.py``'s jitted step takes as
    they are): the caller still holds the step before's gradient while this
    one is made, and a fifth copy of the model does not fit the device."""
    params = variables["params"]
    b = batch["x"].shape[0]
    loss, grads = _seq_grad(params, batch["x"][0], batch["y"][0], batch["arch"], precision)
    if b > 1:
        loss, grads = loss / b, jax.tree.map(lambda g: g / b, grads)
        for i in range(1, b):
            seq_loss, seq_grads = _seq_grad(params, batch["x"][i], batch["y"][i],
                                            batch["arch"], precision)
            loss, grads = loss + seq_loss / b, _add_scaled(grads, np.float32(1.0 / b), seq_grads)
    return loss, grads, {}
