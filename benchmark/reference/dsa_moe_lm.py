"""Plain forward pass, training loss and gradients of the Keye-VL-2.0-30B-A3B
language model on one chip's share (Kwai-Keye, ``config.json``: ``model_type``
``KeyeVL2``; the vision tower is not built, the clients train on token ids):
a token embedding, layers of grouped-query attention over a **learned
selection of keys** and routed experts, a final RMSNorm and an untied head over
the held slice of the vocabulary. The selection is DeepSeek's sparse attention
(DSA: the lightning indexer and top-k selection of the DeepSeek-V3.2 report) at
``sa_config``'s sizes. With x ``[T, D]`` a block's input, H query heads on
H_kv key and value heads of d columns, J index heads of d_I columns, k keys a
query:

    h = rmsnorm(x);  q = h W_q -> [H, T, d];  k, v = h W_k, h W_v -> [H_kv, T, d]   (no bias)
    q, k = rmsnorm_d(q), rmsnorm_d(k)       one learned scale of d each, shared by the heads
    q, k = rope(q), rope(k)                 rotate-half pairs (i with i + d/2), theta
    hbar = stop_gradient(h)
    qI = hbar W_qI -> [J, T, d_I];  kI = layernorm(hbar W_kI) -> [T, d_I]     one key head
    qI, kI = rope(qI), rope(kI)             over the d_I columns, the same theta
    wI = (hbar W_w) * J^-1/2 * d_I^-1/2 -> [T, J]
    I[t, s] = sum_j wI[t, j] * relu(<qI[t, j], kI[s]>)                        s <= t
    S_t = the s <= t with the k largest I[t, s] (all of them while t < k); ties to the lower s
    o[t, n] = sum over s in S_t of softmax_{s in S_t}(d^-1/2 <q[t, n], k[s, g]>) v[s, g]
              g = n // (H / H_kv), the query head's KV head
    x1 = x + concat_heads(o) W_o
    phat[t, s] = stop_gradient(mean over n of that softmax)
    L_I(layer) = mean over t of KL(phat[t, .] || softmax_{s in S_t}(I[t, .]))
    u = rmsnorm(x1);  ids, w = the top-8 of u W_r and a softmax over those 8  (float32)
    y = x1 + sum over e in ids, e held here, of w_e * ((silu(u G_e) * (u U_e)) D_e)
    L = mean over t of CE(rmsnorm(x_L,t) W_head, token t + 1) + sum over the layers of L_I

so the next-token loss moves everything but the indexer, ``L_I`` moves ``W_qI``,
``W_kI``, its norm and ``W_w`` alone, and no gradient passes through the
choice of ``S_t``. Departures from the releases, each also under ``assumed``
in the configuration file: DeepSeek's indexer runs in FP8 behind a Hadamard
rotation of ``qI`` and ``kI`` (orthogonal: it changes no score in exact
arithmetic), here float32 and no rotation; no vision tower, so the three
position streams of ``mrope`` are equal and the rotation is the plain one; the
dense warm-up stage (the indexer trained under full attention) is not run, a
client fine-tunes a trained model; ``q_chunk_size`` and ``kv_chunk_size`` are
read as the tiles the release forms its scores in and change no equation.

Float32 at ``highest``. It fits beside ``fedavg.py``'s four copies of a
659M-parameter model (10.55 GB of the chip's 16.9) as ``reference/eva_lm.py``
does: each half-layer's input waits on the HOST, the backward takes the
half-layers last to first, each its own ``jax.vjp`` whose parameter gradients
go to the host as they are made, and inside a half the attention, the index
scores and the selection go a block of ``QUERY_BLOCK`` queries at a time
against whole ``[T]`` rows of scores, the head a block of rows at a time, each
under ``jax.checkpoint``. It imports nothing of the program. The parameter
tree is read by the program's names (``tok_embed``; ``block_<i>`` with
``norm_attn``, ``attn``: ``q``, ``k``, ``v``, ``q_norm``, ``k_norm``, ``o``;
``indexer``: ``q``, ``k``, ``k_norm``, ``w``; ``norm_ffn``, ``router``,
``experts``; ``norm_f``; ``head``). Every matrix product goes through
``precision.product``, so that the control can round their operands. ``Arch``
can ignore the selection, leave the index loss or the ReLU out: the faults the
limits are held to.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mla_moe_lm import _dot, _experts, _mean_ce, _rmsnorm
from benchmark.reference.moe_lm import _rope
from benchmark.reference.precision import product

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256  # queries a step of the attention: [H, 256, T] float32 scores


class Arch(NamedTuple):
    """The numbers the forward pass needs that the parameter tree does not
    show; ``select``, ``index_loss`` and ``relu`` False break the layer on
    purpose."""

    num_heads: int
    num_kv_heads: int
    index_heads: int
    topk: int  # keys a query attends to
    top_k: int  # experts a token
    experts_first: int
    rope_theta: float
    rms_eps: float
    select: bool = True
    index_loss: bool = True
    relu: bool = True


def _layernorm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def chosen_keys(scores, first_row, topk: int):
    """Bool ``[rows, T]``: for query ``first_row + r`` the ``topk`` largest of
    ``scores[r, :t + 1]`` (every one while ``t < topk``), equal scores to the
    lower position."""
    rows, t = scores.shape
    pos = first_row + jnp.arange(rows)
    valid = jnp.arange(t)[None] <= pos[:, None]
    scores = jnp.where(valid, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf)  # one zero
    tau = jax.lax.top_k(scores, min(topk, t))[0][:, -1:]
    above, ties = scores > tau, (scores == tau) & valid
    need = jnp.minimum(topk, pos + 1)[:, None] - jnp.sum(above, axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= need))


def sparse_attention(q, k, v, qi, ki, wi, arch: Arch, precision: str):
    """``(out [H, T, d], sum over t of KL_t)`` from q ``[H, T, d]``, k and v
    ``[H_kv, T, d]``, qi ``[J, T, d_I]``, ki ``[T, d_I]``, wi ``[T, J]``, a
    block of queries at a time against all keys."""
    h, t, d = q.shape
    h_kv = k.shape[0]
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"T {t} is not whole blocks of {block} queries")
    scores = product(lambda a, b: jnp.einsum("ngqd,nkd->ngqk", a, b, precision=HI), precision)
    values = product(lambda a, b: jnp.einsum("ngqk,nkd->ngqd", a, b, precision=HI), precision)
    index = product(lambda a, b: jnp.einsum("jqd,kd->jqk", a, b, precision=HI), precision)

    @jax.checkpoint
    def one_block(lo):
        z = index(jax.lax.dynamic_slice_in_dim(qi, lo, block, axis=1), ki)
        w = jax.lax.dynamic_slice_in_dim(wi, lo, block, axis=0).T[..., None]
        i_scores = jnp.sum(w * (jax.nn.relu(z) if arch.relu else z), axis=0)  # [block, T]
        causal = jnp.arange(t)[None] <= lo + jnp.arange(block)[:, None]
        chosen = chosen_keys(jax.lax.stop_gradient(i_scores), lo, arch.topk) if arch.select \
            else causal
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1).reshape(h_kv, h // h_kv, block, d)
        p = jax.nn.softmax(jnp.where(chosen, scores(qb, k) * d ** -0.5, -jnp.inf), axis=-1)
        p_hat = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        log_sigma = jax.nn.log_softmax(jnp.where(chosen, i_scores, -jnp.inf), axis=-1)
        kl = jnp.sum(jax.scipy.special.xlogy(p_hat, p_hat)
                     - p_hat * jnp.where(chosen, log_sigma, 0.0))
        return values(p, v).reshape(h, block, d), kl

    out, kl = jax.lax.map(one_block, jnp.arange(0, t, block))  # [T / block, H, block, d]
    return out.transpose(1, 0, 2, 3).reshape(h, t, d), jnp.sum(kl)


def attention_half(x, p, arch: Arch, precision: str):
    """``(x + W_o(attention(rmsnorm(x))), L_I)`` over ``x`` [T, D]; ``p`` the
    block's tree."""
    dot = _dot(precision)
    t = x.shape[0]
    a, ix = p["attn"], p["indexer"]
    heads = lambda y, n: y.reshape(t, n, -1).transpose(1, 0, 2)  # noqa: E731
    hidden = _rmsnorm(x, p["norm_attn"]["scale"], arch.rms_eps)
    q = heads(dot(hidden, a["q"]["kernel"]), arch.num_heads)
    k = heads(dot(hidden, a["k"]["kernel"]), arch.num_kv_heads)
    v = heads(dot(hidden, a["v"]["kernel"]), arch.num_kv_heads)
    q = _rope(_rmsnorm(q, a["q_norm"]["scale"], arch.rms_eps), arch.rope_theta)
    k = _rope(_rmsnorm(k, a["k_norm"]["scale"], arch.rms_eps), arch.rope_theta)
    detached = jax.lax.stop_gradient(hidden)
    qi = _rope(heads(dot(detached, ix["q"]["kernel"]), arch.index_heads), arch.rope_theta)
    ki = _rope(_layernorm(dot(detached, ix["k"]["kernel"]), ix["k_norm"], arch.rms_eps),
               arch.rope_theta)
    wi = dot(detached, ix["w"]["kernel"]) * (arch.index_heads ** -0.5 * qi.shape[-1] ** -0.5)
    out, kl = sparse_attention(q, k, v, qi, ki, wi, arch, precision)
    index_loss = kl / t if arch.index_loss else jnp.float32(0.0)
    return x + dot(out.transpose(1, 0, 2).reshape(t, -1), a["o"]["kernel"]), index_loss


def moe_half(x, p, arch: Arch, precision: str):
    """``(x + the held experts' part of the mixture over rmsnorm(x), 0)``: a
    softmax router, the ``top_k`` largest logits and a softmax over those."""
    dot = _dot(precision)
    u = _rmsnorm(x, p["norm_ffn"]["scale"], arch.rms_eps)
    top, ids = jax.lax.top_k(dot(u, p["router"]["kernel"]), arch.top_k)
    weights = jax.nn.softmax(top, axis=-1)
    return x + _experts(u, ids, weights, p["experts"], arch.experts_first, precision), \
        jnp.float32(0.0)


def _layers(params) -> int:
    return sum(1 for k in params if k.startswith("block_"))


HALVES = {"attn": attention_half, "moe": moe_half}
LEAVES = {"attn": ("norm_attn", "attn", "indexer"), "moe": ("norm_ffn", "router", "experts")}


def forward(params, tokens, arch: Arch, precision: str = "f32"):
    """``(logits [T, V], sum of the layers' L_I)`` of one sequence (whole: for
    sizes a test can hold)."""
    x, index_loss = params["tok_embed"]["embedding"][tokens], jnp.float32(0.0)
    for i in range(_layers(params)):
        for half in ("attn", "moe"):
            x, more = HALVES[half](x, params[f"block_{i}"], arch, precision)
            index_loss = index_loss + more
    logits = _dot(precision)(_rmsnorm(x, params["norm_f"]["scale"], arch.rms_eps),
                             params["head"]["kernel"])
    return logits, index_loss


@partial(jax.jit, static_argnums=(2, 3, 4))
def _half_fwd(x, p, half, arch, precision):
    return HALVES[half](x, p, arch, precision)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _half_bwd(x, p, g, half, arch, precision):
    """(dL/dx, dL/dp) of one half-layer from its input and dL/d(output); its
    own loss enters L with weight 1."""
    _, vjp = jax.vjp(lambda x, p: HALVES[half](x, p, arch, precision), x, p)
    return vjp((g, jnp.float32(1.0)))


def _head_loss(x, p, y, arch, precision):
    return _mean_ce(_rmsnorm(x, p["norm_f"]["scale"], arch.rms_eps), p["head"]["kernel"], y,
                    precision)


@partial(jax.jit, static_argnums=(3, 4))
def _head_grad(x, p, y, arch, precision):
    return jax.value_and_grad(_head_loss, argnums=(0, 1))(x, p, y, arch, precision)


@partial(jax.jit, static_argnums=(2,))
def _embed_grad(tokens, g, rows):
    return jnp.zeros((rows, g.shape[1]), g.dtype).at[tokens].add(g)


def _seq_grad(params, tokens, y, arch, precision):
    """(loss, grads) of one sequence, half a layer on the device at a time:
    the halves' inputs wait on the host for the backward pass."""
    n = _layers(params)
    halves = [(i, half) for i in range(n) for half in ("attn", "moe")]
    part = lambda i, half: {k: params[f"block_{i}"][k] for k in LEAVES[half]}  # noqa: E731
    x = params["tok_embed"]["embedding"][tokens]
    inputs, index_loss = [], 0.0
    for i, half in halves:
        inputs.append(np.asarray(x))
        x, more = _half_fwd(x, part(i, half), half, arch, precision)
        index_loss += float(more)
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
    return loss + index_loss, grads


def loss_and_grad(variables, batch, precision="f32"):
    """Mean training loss (next-token + the layers' index losses) of a batch
    {"x": [B, T], "y": [B, T], "arch": Arch} and its gradient, one sequence at
    a time. The gradient is handed back on the HOST, as
    ``reference/eva_lm.py`` hands its own: a fifth copy of the model does not
    fit the device."""
    params = variables["params"]
    b = batch["x"].shape[0]
    loss, grads = _seq_grad(params, batch["x"][0], batch["y"][0], batch["arch"], precision)
    if b > 1:
        loss, grads = loss / b, jax.tree.map(lambda g: g / b, grads)
        for i in range(1, b):
            seq_loss, seq_grads = _seq_grad(params, batch["x"][i], batch["y"][i],
                                            batch["arch"], precision)
            loss = loss + seq_loss / b
            grads = jax.tree.map(lambda a, g: a + g / b, grads, seq_grads)
    return loss, grads, {}
