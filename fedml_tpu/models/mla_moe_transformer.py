"""Decoder-only LM with latent attention (MLA), a sigmoid router with a
selection bias beside a shared expert, a leading dense layer and a
multi-token-prediction module, for federated clients that hold one chip's
share of an expert-parallel model (the JoyAI-LLM-Flash / DeepSeek-V3 block).

A block, with x its input ``[T, D]`` (the residual stream, float32):

    h      = rmsnorm(x)
    c_q    = rmsnorm(h W_qa)                                  [T, q_rank]
    q      = c_q W_qb -> H heads of [q_nope | q_rope]         [T, H, nope + rope]
    c_kv | k_rope = h W_kva                                   [T, kv_rank] | [T, rope]
    [k_nope | v] a head = rmsnorm(c_kv) W_kvb                 [T, H, nope + v_dim]
    q_rope, k_rope = rope(.)   adjacent pairs (2i, 2i + 1) turned by position x
                               theta^(-2i / rope); k_rope is one vector a
                               position, shared by every head
    s_ij   = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(nope + rope), causal
    a      = softmax(s) v;  x1 = x + concat_heads(a) W_o      (W_o: H v_dim x D)
    u      = rmsnorm(x1)                                      (float32: the router reads it)
    dense layer:   y = x1 + (silu(u G) * (u U)) D
    routed layer:  sc = sigmoid(u W_r);  I = top-k of (sc + b);
                   w = scale * sc[I] / (sum sc[I] + 1e-20)    (``ops/moe.py`` route)
                   y = x1 + shared(u) + sum over e in I, e *held here*, of
                       w_e * ((silu(u G_e) * (u U_e)) D_e)
                   shared(u) = (silu(u G_s) * (u U_s)) D_s    (whole on every chip)

then a final RMSNorm and an untied head give the main logits. While training
the module also gives the logits of a depth-1 multi-token-prediction module
(DeepSeek-V3, eq. 21-25), which shares the embedding and the head: with h_L
the last block's output before the final norm,

    g_i = [rmsnorm(h_L,i) ; rmsnorm(Emb(t_{i+1}))] M          (M: 2D x D)
    g'  = one routed block over g;  logits_mtp = rmsnorm(g') W_head

position i predicting t_{i+2}. The module runs it over all T positions with
``t_{i+1}`` rolled round at the row's last position, which has no t_{i+1}:
attention is causal, so that position touches no other, and the trainer gives
it no loss (``core/trainer.py`` ``MTP_COLLECTION``). ``select_bias`` (b) is a
parameter leaf that gets a zero gradient. ``experts_first`` / ``experts_held``
say which of the router's outputs have their expert here, as in
``models/moe_transformer.py``, whose ``RMSNorm``, ``Kernel`` and
``RoutedExperts`` these blocks share.

A layer's mixer is latent attention as above or, where ``mixers`` says
"kda", Kimi Delta Attention (the Kimi-Linear block: three such layers to one
latent-attention layer, which there has no query latent, ``q_rank`` None, and
leaves its position columns unrotated, ``rope_theta`` None). With H heads of
d = 128 key and value columns:

    q, k, v = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h W_v))   [T, H d] each
              conv4: causal depthwise convolution along T, 4 taps a channel,
              zeros before the first token, no bias (``ops/kda.py`` short_conv)
    q_t, k_t a head:  q_t = l2norm(q_t) * d^-0.5,  k_t = l2norm(k_t)       (eps 1e-6)
    g_t    = -exp(A_log[head]) * softplus((h W_fa) W_fb + dt_bias)         [T, H, d] <= 0, float32
    beta_t = sigmoid(h W_b)                                                [T, H]
    S_0 = 0;  S~ = Diag(exp g_t) S_{t-1};  S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t
    x1 = x + concat_heads(rmsnorm_d(o_t) * sigmoid((h W_ga) W_gb + b_g)) W_o

``attn_impl`` "flash" runs the recurrence in chunks (``ops/kda.py`` kda),
"xla" token by token (kda_reference). ``A_log`` is one scalar a head and
``dt_bias`` one a channel, each a ``[1, n]`` leaf named ``kernel``.

Two more mixers make the LFM2 block (three short-convolution layers to one
attention layer, no shared expert: ``shared_dim`` 0 builds none; a head tied
to the embedding: ``tie_head``). "conv", the gated short-convolution operator
(:class:`ShortConv`; the chain is ``ops/shortconv.py``'s):

    [B | C | z] = h W_in                           W_in: D x 3D, no bias
    c_t = sum_j w_j * (B * z)_{t - (K - 1) + j}    K = ``conv_size`` taps a channel, causal
    x1  = x + (C * c) W_out                        W_out: D x D

and "gqa", ``models/moe_transformer.py``'s :class:`GroupedAttention` with
``num_heads`` query heads on ``kv_heads`` KV heads of ``head_dim`` columns,
an RMSNorm of q and of k a head (one scale of ``head_dim`` each) and then
rotate-half rotary positions at ``rope_theta``, every earlier key visible.

A fifth mixer, "eva", makes the EvaByte block (every layer dense:
``routed_layers`` 0; ``norm_unit_offset``: every RMSNorm is ``x / rms(x) * (1
+ g)`` with ``g`` from zero; ``num_pred_heads`` next-byte heads on one stream):
EVA attention (:class:`EvaAttention`; the mathematics is ``ops/eva.py``'s), H
heads of ``head_dim`` columns with rotate-half positions on q and k:

    q, k, v = h W_q, h W_k, h W_v;  q, k = rope(q), rope(k)
    k~, v~  = chunk summaries of k, v by the head's learned phi and mu
    o_i     = one softmax over the keys j <= i of query i's own ``eva_window``
              and the summaries of every ``eva_chunk`` of the windows before it
    x1      = x + concat_heads(o) W_o

A sixth mixer, "dsa", makes the Keye-VL-2.0 language model's block (every
layer routed through a **softmax** router, ``router`` "softmax": the
``experts_per_token`` largest of ``u W_r`` and a softmax over those, no
selection bias and no such leaf; no shared expert): the "gqa" mixer's
attention over a learned selection of keys (:class:`Indexer`; the mathematics
is ``ops/dsa.py``'s). With ``hbar = stop_gradient(h)``, ``index_heads`` J heads
of ``index_dim`` d columns:

    qI = hbar W_qI -> [J, T, d];  kI = layernorm(hbar W_kI) -> [T, d]    one key head
    qI, kI = rope(qI), rope(kI)          rotate-half pairs over the d columns, theta
    wI = (hbar W_w) * J^-1/2 * d^-1/2 -> [T, J]
    I[t, s] = sum_j wI[t, j] relu(<qI[t, j], kI[s]>)                     s <= t, float32
    S_t = the ``index_topk`` largest of I[t, :t + 1];  o = attention over S_t alone
    L_I = mean over t of KL(mean over heads of the attention's softmax || softmax of I over S_t)

While training the layers' ``L_I`` are summed and handed to the trainer with
weight 1 (``core/trainer.py`` ``MTP_COLLECTION``): the one loss the indexer's
leaves learn from, and it moves no other leaf.

With ``num_pred_heads`` P > 1 the head has P x V columns and the logits are
``[B, T, P, V]``: head p at position t predicts token t + 1 + p, and the
trainer's ``lm_loss`` takes ``y`` and ``mask`` of ``[B, T, P]`` as they are.

Same interface as the rest of the zoo: int tokens ``[B, T]`` in, logits
``[B, T, V]`` float32 out, ``train`` kwarg. ``train=False`` builds no MTP
logits.
"""

from __future__ import annotations

import contextlib
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.core.trainer import MTP_COLLECTION, STATS_COLLECTION
from fedml_tpu.models.moe_transformer import (
    GroupedAttention, Kernel, RMSNorm, RoutedExperts, rope as rope_half)
from fedml_tpu.obs import trace
from fedml_tpu.ops import dsa, eva, kda, moe, remat, shortconv
from fedml_tpu.ops.attention import attention_reference, flash_attention_head_parallel
from fedml_tpu.ops.head_loss import decoder_head

MLA, KDA, CONV, GQA, EVA, DSA = "mla", "kda", "conv", "gqa", "eva", "dsa"
SIGMOID, SOFTMAX = "sigmoid", "softmax"  # MLABlock.router


def rope_interleaved(x, theta: float):
    """Rotary positions over the whole last dimension of ``[B, H, T, D]``,
    adjacent pairing (dimension 2i with 2i + 1), positions 0 ... T-1."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    num_heads: int
    q_rank: int | None  # None: no query latent, one product from the stream to the heads
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float | None  # None: the ``rope_dim`` columns stay as they are, unrotated
    rms_eps: float = 1e-6
    attn_impl: str = "xla"  # xla | flash
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, _ = h.shape
        n, nope, rope_d = self.num_heads, self.nope_dim, self.rope_dim

        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, name=name, dtype=self.dtype)(y)

        def heads(y, width):  # [B, T, n * width] -> [B, n, T, width]
            return y.reshape(b, t, n, width).transpose(0, 2, 1, 3)

        def turned(y):
            return y if self.rope_theta is None else rope_interleaved(y, self.rope_theta)

        with jax.named_scope(trace.SCOPE_MLA):
            if self.q_rank is None:
                q = heads(dense("q", n * (nope + rope_d), h), nope + rope_d)
            else:
                c_q = RMSNorm(self.rms_eps, self.dtype, name="q_a_norm")(
                    dense("q_a", self.q_rank, h))
                q = heads(dense("q_b", n * (nope + rope_d), c_q), nope + rope_d)
            kv_a = dense("kv_a", self.kv_rank + rope_d, h)
            c_kv = RMSNorm(self.rms_eps, self.dtype, name="kv_a_norm")(kv_a[..., :self.kv_rank])
            k_rope = turned(kv_a[:, None, :, self.kv_rank:])
            kv = heads(dense("kv_b", n * (nope + self.v_dim), c_kv), nope + self.v_dim)
            if self.rope_theta is not None:
                q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
            # the kernels' key is one [H, T, nope + rope] operand: the shared
            # rotary columns are written once a head (PERF.md section 6, PR 32)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (b, n, t, rope_d))], axis=-1)
            v = kv[..., nope:]
            if self.attn_impl == "flash":
                a = flash_attention_head_parallel(q, k, v, axis=None, causal=True)
            else:
                a = attention_reference(q, k, v, causal=True)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, n * self.v_dim)
            return dense("o", h.shape[-1], a)


class Scale(nn.Module):
    """A norm's learned scale alone, a leaf named ``scale`` as ``RMSNorm``'s
    is, for a norm that an operator of ``ops/`` applies."""

    width: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.width,))


class DeltaAttention(nn.Module):
    """Kimi Delta Attention (the module docstring's equations): ``num_heads``
    heads of ``head_dim`` key and value columns."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    rms_eps: float = 1e-6
    attn_impl: str = "xla"  # xla: token by token | flash: in chunks
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        n, d = self.num_heads, self.head_dim  # d is also the two low-rank gates' rank

        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, name=name, dtype=self.dtype)(y)

        def stream(name, kept, norm, scale=1.0):
            # the projection is what a rematerialised block keeps: the chain's
            # backward reads it, and the chain costs little to run again
            y = remat.keep(kept, dense(name, n * d, h))
            taps = Kernel((self.conv_size, n * d), name=name + "_conv")()
            return kda.conv_act(y, taps, heads=n, norm=norm, scale=scale)

        with jax.named_scope(trace.SCOPE_KDA):
            q = stream("q", remat.KDA_Q, True, d ** -0.5)
            k, v = stream("k", remat.KDA_K, True), stream("v", remat.KDA_V, False)
            dt_bias, a_log = Kernel((1, n * d), name="dt_bias")(), Kernel((1, n), name="A_log")()
            g = kda.decay(dense("f_b", n * d, dense("f_a", d, h)), dt_bias[0], a_log[0])
            beta = jax.nn.sigmoid(dense("b", n, h).astype(jnp.float32)).transpose(0, 2, 1)
            if self.attn_impl == "flash":
                o = kda.kda(q, k, v, g, beta)
            else:
                o = kda.kda_reference(q, k, v, g, beta)
            gate = nn.Dense(n * d, name="g_b", dtype=self.dtype)(dense("g_a", d, h))
            o = kda.gated_norm(o, gate, Scale(d, name="o_norm")(), eps=self.rms_eps)
            return dense("o", h.shape[-1], o), jax.lax.stop_gradient(kda.decay_floor(g))


class ShortConv(nn.Module):
    """The gated short-convolution operator (the module docstring's
    equations): ``taps`` taps a channel, as many channels as the stream."""

    taps: int = 3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]

        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, name=name, dtype=self.dtype)(y)

        with jax.named_scope(trace.SCOPE_SHORTCONV):
            # what a rematerialised block keeps: every step of the chain and of
            # its backward reads a chunk of it, and the chain is cheap to run again
            bcz = remat.keep(remat.SHORTCONV_IN, dense("in", 3 * d, h))
            y = shortconv.gated_short_conv(bcz, Kernel((self.taps, d), name="taps")())
            return dense("out", d, y)


class EvaAttention(nn.Module):
    """EVA attention (the module docstring's equations): ``num_heads`` heads
    of ``head_dim`` columns, ``adaptive_phi`` and ``adaptive_mu_k`` one vector
    a head each, ``[num_heads, head_dim]`` leaves named ``kernel``."""

    num_heads: int
    head_dim: int
    window: int
    chunk: int
    rope_theta: float
    attn_impl: str = "xla"  # xla: the whole score matrix | flash: two kernel calls merged
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, _ = h.shape
        n, d = self.num_heads, self.head_dim

        def heads(name):  # [B, T, D] -> [B, n, T, d]
            y = nn.Dense(n * d, use_bias=False, name=name, dtype=self.dtype)(h)
            return y.reshape(b, t, n, d).transpose(0, 2, 1, 3)

        with jax.named_scope(trace.SCOPE_EVA):
            q, k = rope_half(heads("q"), self.rope_theta), rope_half(heads("k"), self.rope_theta)
            v = heads("v")
            phi, mu = Kernel((n, d), name="adaptive_phi")(), Kernel((n, d), name="adaptive_mu_k")()
            o, mass = eva.eva_attention(q, k, v, phi, mu, window=self.window, chunk=self.chunk,
                                        impl=self.attn_impl)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, n * d)
            return nn.Dense(h.shape[-1], use_bias=False, name="o", dtype=self.dtype)(o), mass


class Indexer(nn.Module):
    """The lightning indexer of the "dsa" mixer (the module docstring's
    equations): ``(qI [B, J, T, d], kI [B, T, d], wI [B, T, J])`` from the
    normed stream, which the caller hands over under ``stop_gradient``."""

    heads: int
    dim: int
    rope_theta: float
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, _ = h.shape

        def dense(name, width):
            return nn.Dense(width, use_bias=False, name=name, dtype=self.dtype)(h)

        q = dense("q", self.heads * self.dim).reshape(b, t, self.heads, self.dim)
        k = nn.LayerNorm(epsilon=self.eps, dtype=self.dtype, name="k_norm")(dense("k", self.dim))
        q, k = rope_half(q.transpose(0, 2, 1, 3), self.rope_theta), rope_half(k, self.rope_theta)
        w = dense("w", self.heads) * (self.heads ** -0.5 * self.dim ** -0.5)
        return q, k, w.astype(self.dtype)


class GatedMLP(nn.Module):
    """``(silu(u G) * (u U)) D``: the leading dense layer's feed-forward and
    the shared expert."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        def dense(name, width, y):
            return nn.Dense(width, use_bias=False, name=name, dtype=self.dtype)(y)

        return dense("down", u.shape[-1], jax.nn.silu(dense("gate", self.width, u))
                     * dense("up", self.width, u))


class MLABlock(nn.Module):
    routed: bool  # False: the dense feed-forward of ``dense_dim``
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_dim: int
    num_experts: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    route_scale: float
    experts_first: int
    experts_held: int
    rope_theta: float | None
    rms_eps: float = 1e-6
    attn_impl: str = "xla"
    dtype: jnp.dtype = jnp.float32
    mixer: str = MLA  # MLA | KDA | CONV | GQA
    kda_heads: int = 0
    kda_head_dim: int = 0
    conv_size: int = 4  # taps of the KDA and CONV mixers' convolutions
    kv_heads: int = 0  # of the GQA mixer, whose query heads are ``num_heads``
    head_dim: int = 0  # of the GQA and EVA mixers
    eva_window: int = 0
    eva_chunk: int = 0
    norm_unit_offset: bool = False
    router: str = SIGMOID  # scores under a selection bias | SOFTMAX: over the chosen logits
    index_heads: int = 0  # of the DSA mixer's indexer
    index_dim: int = 0
    index_topk: int = 0
    index_loss: bool = False  # the DSA mixer also gives its index loss (a training step)

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h = RMSNorm(self.rms_eps, self.dtype, self.norm_unit_offset, name="norm_attn")(x)
        mixer_stats = {}
        if self.mixer == EVA:
            mixed, mass = EvaAttention(
                self.num_heads, self.head_dim, self.eva_window, self.eva_chunk, self.rope_theta,
                self.attn_impl, self.dtype, name="attn")(h)
            mixer_stats = {"eva/remote_mass": mass}
        elif self.mixer == KDA:
            mixed, floor = DeltaAttention(
                self.kda_heads, self.kda_head_dim, self.conv_size, self.rms_eps,
                self.attn_impl, self.dtype, name="attn")(h)
            mixer_stats = {"kda/decay_floor": floor}
        elif self.mixer == CONV:
            mixed = ShortConv(self.conv_size, self.dtype, name="conv")(h)
        elif self.mixer == DSA:
            with jax.named_scope(trace.SCOPE_DSA):
                with jax.named_scope(trace.SCOPE_DSA_INDEX):
                    index = Indexer(self.index_heads, self.index_dim, self.rope_theta,
                                    self.rms_eps, self.dtype, name="indexer")(
                                        jax.lax.stop_gradient(h))

                def attend(q, k, v):
                    out, found = dsa.sparse_attention(
                        q, k, v, *index, topk=self.index_topk, impl=self.attn_impl,
                        with_loss=self.index_loss)
                    mixer_stats.update(found)
                    return out

                mixed = GroupedAttention(
                    self.num_heads, self.kv_heads, self.head_dim, rope_theta=self.rope_theta,
                    attn_impl=self.attn_impl, dtype=self.dtype, qk_norm_eps=self.rms_eps,
                    attend=attend, name="attn")(h)
        elif self.mixer == GQA:
            with jax.named_scope(trace.SCOPE_GQA):
                mixed = GroupedAttention(
                    self.num_heads, self.kv_heads, self.head_dim, rope_theta=self.rope_theta,
                    attn_impl=self.attn_impl, dtype=self.dtype, qk_norm_eps=self.rms_eps,
                    name="attn")(h)
        else:
            mixed = LatentAttention(
                self.num_heads, self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim,
                self.v_dim, self.rope_theta, self.rms_eps, self.attn_impl, self.dtype,
                name="attn")(h)
        x = x + mixed
        u = RMSNorm(self.rms_eps, jnp.float32, self.norm_unit_offset, name="norm_ffn")(x)
        if not self.routed:
            # the feed-forward bears a scope of its own on the EVA model's path only
            scope = (jax.named_scope(trace.SCOPE_MLP_DENSE) if self.mixer == EVA
                     else contextlib.nullcontext())
            with scope:
                return x + GatedMLP(self.dense_dim, self.dtype, name="mlp")(u).astype(x.dtype), (
                    mixer_stats)
        u = u.reshape(b * t, d)
        router = Kernel((d, self.num_experts), name="router")()
        scoring = {} if self.router == SOFTMAX else {  # SOFTMAX: over the chosen logits, no bias
            "select_bias": Kernel((1, self.num_experts), name="select_bias")()[0],
            "scale": self.route_scale}
        ids, weights = moe.route(u, router, self.experts_per_token, **scoring)
        if self.shared_dim:
            with jax.named_scope(trace.SCOPE_MOE_SHARED):
                shared = GatedMLP(self.shared_dim, self.dtype, name="shared")(u)
        m, stats = RoutedExperts(
            d, self.expert_dim, self.experts_first, self.experts_held, self.dtype,
            activation=jax.nn.silu, outputs=self.num_experts, name="experts")(u, ids, weights)
        if self.shared_dim:
            m = shared.astype(jnp.float32) + m
        return x + m.reshape(b, t, d).astype(x.dtype), {**stats, **mixer_stats}


class MLAMoETransformerLM(nn.Module):
    """Causal LM of ``dense_layers`` dense then ``routed_layers`` routed
    :class:`MLABlock` layers, with ``mtp_depth`` (0 or 1) multi-token-
    prediction modules of one routed block each. ``mixers`` gives each
    layer's mixer in order ("mla" | "kda" | "conv" | "gqa" | "eva" | "dsa"; None:
    latent attention in all). ``router`` "softmax": the routed layers' softmax
    router, which has no ``select_bias`` leaf. ``shared_dim`` 0: no shared expert. ``tie_head``: the
    logits are the final norm's output times the embedding's transpose (in
    float32, as the embedding is), and the tree has no ``head``."""

    vocab_size: int = 96
    embed_dim: int = 64
    dense_layers: int = 1
    routed_layers: int = 2
    num_heads: int = 4
    q_rank: int | None = 48
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    dense_dim: int = 128
    num_experts: int = 8
    experts_per_token: int = 2
    expert_dim: int = 32
    shared_dim: int = 32
    route_scale: float = 2.5
    experts_first: int = 0
    experts_held: int | None = None  # None: all of them
    mtp_depth: int = 1
    mtp_loss_weight: float = 0.3
    rope_theta: float | None = 32e6
    mixers: Sequence[str] | None = None
    kda_heads: int = 4
    kda_head_dim: int = 16
    conv_size: int = 4
    rms_eps: float = 1e-6
    attn_impl: str = "xla"
    dtype: jnp.dtype = jnp.float32  # compute dtype of the products; params stay f32
    head_dtype: jnp.dtype = jnp.float32
    # rematerialize each block in the backward pass under ops/remat.py's
    # policy, as MoETransformerLM.remat
    remat: bool = False
    kv_heads: int = 2  # of the "gqa" mixer, whose query heads are ``num_heads``
    head_dim: int = 16  # of the "gqa" and "eva" mixers
    tie_head: bool = False
    eva_window: int = 32  # of the "eva" mixer: positions a window, positions a chunk
    eva_chunk: int = 4
    norm_unit_offset: bool = False  # every RMSNorm as x / rms(x) * (1 + g)
    num_pred_heads: int = 1  # P > 1: the head has P x V columns, logits [B, T, P, V]
    router: str = SIGMOID
    index_heads: int = 0  # of the "dsa" mixer's indexer: heads, columns a head, keys a query
    index_dim: int = 0
    index_topk: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.mtp_depth not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")
        embed = nn.Embed(self.vocab_size, self.embed_dim, name="tok_embed")
        if self.tie_head and self.num_pred_heads != 1:
            raise ValueError("a tied head predicts one token a position")
        head = None if self.tie_head else nn.Dense(
            self.num_pred_heads * self.vocab_size, use_bias=False, name="head",
            dtype=self.head_dtype)
        held = self.num_experts if self.experts_held is None else self.experts_held
        block_cls = remat.block(MLABlock) if self.remat else MLABlock

        layers = self.dense_layers + self.routed_layers
        mixers = (MLA,) * layers if self.mixers is None else tuple(self.mixers)
        if len(mixers) != layers or set(mixers) - {MLA, KDA, CONV, GQA, EVA, DSA}:
            raise ValueError(
                f"mixers must name {layers} layers' mixers, each mla, kda, conv or gqa, or eva "
                "or dsa")
        if DSA in mixers and not (self.index_heads and self.index_dim and self.index_topk):
            raise ValueError("a dsa mixer needs index_heads, index_dim and index_topk")
        if self.router not in (SIGMOID, SOFTMAX):
            raise ValueError(f"router is sigmoid or softmax, not {self.router!r}")
        # the losses a module hands the trainer exist in a training step alone
        hands_losses = train and self.is_mutable_collection(MTP_COLLECTION)

        def block(routed, name, mixer=MLA):
            return block_cls(
                routed, self.num_heads, self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim,
                self.v_dim, self.dense_dim, self.num_experts, self.experts_per_token,
                self.expert_dim, self.shared_dim, self.route_scale, self.experts_first, held,
                self.rope_theta, self.rms_eps, self.attn_impl, self.dtype, mixer,
                self.kda_heads, self.kda_head_dim, self.conv_size, self.kv_heads, self.head_dim,
                self.eva_window, self.eva_chunk, self.norm_unit_offset, self.router,
                self.index_heads, self.index_dim, self.index_topk,
                hands_losses and mixer == DSA, name=name)

        def logits(h, norm):
            h = RMSNorm(self.rms_eps, self.head_dtype, self.norm_unit_offset, name=norm)(h)
            return decoder_head(self, h, train, dense=head, embed=embed,
                                heads=self.num_pred_heads)

        h = embed(x)  # the residual stream stays float32: the router reads it
        stats = []
        for i, mixer in enumerate(mixers):
            h, layer_stats = block(i >= self.dense_layers, f"block_{i}", mixer)(h)
            stats.append(layer_stats)
        # params of every module are made at init, whatever ``train`` says
        if self.mtp_depth and (self.is_initializing() or hands_losses):
            with jax.named_scope(trace.SCOPE_MTP):
                norm = lambda name, y: RMSNorm(self.rms_eps, self.dtype, name=name)(y)  # noqa: E731
                g = jnp.concatenate(
                    [norm("mtp_norm_h", h), norm("mtp_norm_e", embed(jnp.roll(x, -1, axis=1)))],
                    axis=-1)
                g = nn.Dense(self.embed_dim, use_bias=False, name="mtp_proj", dtype=self.dtype)(g)
                g, layer_stats = block(True, "mtp_block")(g.astype(h.dtype))
                stats.append(layer_stats)
                self.sow(MTP_COLLECTION, "next2",
                         {"logits": logits(g, "mtp_norm_f"),
                          "weight": jnp.float32(self.mtp_loss_weight)},
                         reduce_fn=lambda _, new: new, init_fn=lambda: None)
        index_kl = [s["dsa/index_kl"] for s in stats if "dsa/index_kl" in s]
        if index_kl:  # the sparse-attention layers' index losses, summed: the indexers' one loss
            self.sow(MTP_COLLECTION, "index",
                     {"loss": sum(index_kl), "weight": jnp.float32(1.0)},
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
        # for the engine's counters: one value a routed block (the MTP module's
        # last) under "moe", one a delta-attention block under "kda", one an
        # EVA block under "eva", four a sparse-attention block under "dsa"
        for group in ("moe", "kda", "eva", "dsa"):
            found = [{k: v for k, v in s.items() if k.startswith(group + "/")} for s in stats]
            found = [s for s in found if s]
            if found:
                self.sow(STATS_COLLECTION, group,
                         {k.split("/", 1)[1]: jnp.stack([s[k] for s in found]) for k in found[0]},
                         reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return logits(h, "norm_f")
