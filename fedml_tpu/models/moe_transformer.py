"""Decoder-only LM with routed experts, grouped KV heads and a per-layer
attention kind, for federated clients that hold one chip's share of an
expert-parallel model.

A block, with x its input ``[T, D]`` (the residual stream, float32):

    ids, w = top-k of x @ W_r, softmax over the k chosen     (float32; the
                                                    router reads the block's
                                                    input, before attention)
    h  = rmsnorm(x);  q, k, v = h W_q, h W_k, h W_v          (no bias)
    q, k = rope(q), rope(k)                                  ("window" layers)
    a  = attention(q, k, v), causal, grouped KV heads; a "window" layer sees
         the last ``window`` keys, the query's own among them; a "global"
         layer sees every earlier key and has no positional encoding at all
    x1 = x + a W_o
    m  = sum over the chosen experts e *held here* of
         w_e * ((relu(u G_e) * (u U_e)) D_e),  u = rmsnorm(x1)
    y  = x1 + m

then a final RMSNorm and an untied head. ``experts_first`` / ``experts_held``
say which of the router's ``num_experts`` outputs have their expert here
(``fedml_tpu/ops/moe.py``): the rest are another chip's, and nothing stands
in for them. The vocabulary is whatever ``vocab_size`` says; a sliced
vocabulary is a smaller vocabulary.

Same interface as the rest of the zoo: int tokens ``[B, T]`` in, logits
``[B, T, V]`` float32 out, ``train`` kwarg. Routing statistics of a training
step are sown into the ``stats`` collection (``core/trainer.py``).
"""

from __future__ import annotations

from typing import Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.core.trainer import STATS_COLLECTION
from fedml_tpu.ops import moe, remat
from fedml_tpu.ops.attention import attention_reference, flash_attention_head_parallel
from fedml_tpu.ops.head_loss import decoder_head

GLOBAL, WINDOW = "global", "window"


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32  # of the output; the statistics are float32
    # x / rms(x) * (1 + g): the learned ``scale`` starts at zero and is an
    # offset from one (EvaByte's ``norm_add_unit_offset``)
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],))
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (y * (1.0 + scale if self.unit_offset else scale)).astype(self.dtype)


def rope(x, theta: float):
    """Rotary positions over the whole head dimension of ``[B, H, T, D]``,
    rotate-half pairing (dimension i with i + D/2), positions 0 ... T-1."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class GroupedAttention(nn.Module):
    """Causal attention of ``num_heads`` query heads on ``num_kv_heads`` key
    and value heads of ``head_dim`` columns (query head n reads KV head
    n // (num_heads // num_kv_heads)), no bias: ``softmax(q k^T /
    sqrt(head_dim)) v`` a head, then the output projection. With
    ``qk_norm_eps`` the queries and keys are RMS-normalised a head before the
    rotation, each kind under one learned scale of ``head_dim`` that the
    heads share (``q_norm``, ``k_norm``); without it the module has no such
    leaves and computes what it did. ``attend`` (``(q, k, v) -> [B, H, T,
    D]``, all three rotated and normalised as above) stands in for the causal
    attention itself where a caller chooses the keys (``ops/dsa.py``)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None = None  # None: every earlier key
    rope_theta: float | None = None  # None: no positional encoding
    attn_impl: str = "xla"  # xla | flash
    dtype: jnp.dtype = jnp.float32
    qk_norm_eps: float | None = None  # None: q and k go on as projected
    attend: Callable | None = None  # None: every earlier key (inside ``window``)

    @nn.compact
    def __call__(self, h):
        b, t, _ = h.shape

        def heads(name, n):  # [B, T, C] -> [B, n, T, D]
            y = nn.Dense(n * self.head_dim, use_bias=False, name=name, dtype=self.dtype)(h)
            return y.reshape(b, t, n, self.head_dim).transpose(0, 2, 1, 3)

        q, k, v = heads("q", self.num_heads), heads("k", self.num_kv_heads), heads(
            "v", self.num_kv_heads)
        if self.qk_norm_eps is not None:
            q = RMSNorm(self.qk_norm_eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(self.qk_norm_eps, self.dtype, name="k_norm")(k)
        if self.rope_theta is not None:
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        if self.attend is not None:
            a = self.attend(q, k, v)
        elif self.attn_impl == "flash":
            a = flash_attention_head_parallel(
                q, k, v, axis=None, causal=True, window=self.window)
        else:
            a = attention_reference(q, k, v, causal=True, window=self.window)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, self.num_heads * self.head_dim)
        return nn.Dense(h.shape[-1], use_bias=False, name="o", dtype=self.dtype)(a)


class Kernel(nn.Module):
    """A bare matrix (or the held experts' stack of matrices, ``[held, in,
    out]``) as a leaf named ``kernel``, for products that are not a Dense."""

    shape: tuple[int, ...]

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.normal(self.shape[-2] ** -0.5), self.shape)


class RoutedExperts(nn.Module):
    """This chip's experts (``ops/moe.py`` :func:`expert_layer`): the held
    experts' three stacks of matrices and the partial sum they give."""

    hidden: int
    expert_dim: int
    first: int
    held: int
    dtype: jnp.dtype = jnp.float32
    # of the gate; None: expert_layer's own, ReLU. Not passed on then, because
    # tests/benchmark_tests/test_benchmark_moe.py stands a broken expert_layer
    # of the older signature in this one's place
    activation: Callable | None = None
    # the router's outputs, of which ``held`` have their expert here (None:
    # all of them). It sizes the layer's buffers and goes around the call, not
    # into it, for the same stand-in's sake
    outputs: int | None = None

    @nn.compact
    def __call__(self, u, ids, weights):
        d, f, held = self.hidden, self.expert_dim, self.held
        gate = {} if self.activation is None else {"activation": self.activation}
        with moe.router_width(self.outputs):
            return moe.expert_layer(
                u, ids, weights, Kernel((held, d, f), name="gate")(),
                Kernel((held, d, f), name="up")(), Kernel((held, f, d), name="down")(),
                first=self.first, count=held, dtype=self.dtype, **gate)


class MoEBlock(nn.Module):
    kind: str  # GLOBAL | WINDOW
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    experts_per_token: int
    expert_dim: int
    experts_first: int
    experts_held: int
    window: int
    rope_theta: float
    rms_eps: float = 1e-6
    attn_impl: str = "xla"
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        windowed = self.kind == WINDOW
        ids, weights = moe.route(
            x.reshape(b * t, d), Kernel((d, self.num_experts), name="router")(),
            self.experts_per_token)
        h = RMSNorm(self.rms_eps, self.dtype, name="norm_attn")(x)
        x = x + GroupedAttention(
            self.num_heads, self.num_kv_heads, self.head_dim,
            window=self.window if windowed else None,
            rope_theta=self.rope_theta if windowed else None,
            attn_impl=self.attn_impl, dtype=self.dtype, name="attn")(h)
        u = RMSNorm(self.rms_eps, self.dtype, name="norm_moe")(x)
        m, stats = RoutedExperts(
            d, self.expert_dim, self.experts_first, self.experts_held, self.dtype,
            outputs=self.num_experts, name="experts")(u.reshape(b * t, d), ids, weights)
        return x + m.reshape(b, t, d).astype(x.dtype), stats


class MoETransformerLM(nn.Module):
    """Causal LM of :class:`MoEBlock` layers; ``layer_kinds`` gives each
    layer's kind in order (a published ``sliding_window_layout`` of 0s and
    1s maps to "global" and "window")."""

    vocab_size: int = 96
    embed_dim: int = 64
    layer_kinds: Sequence[str] = (GLOBAL, WINDOW, WINDOW, WINDOW)
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_experts: int = 8
    experts_per_token: int = 2
    expert_dim: int = 32
    experts_first: int = 0
    experts_held: int | None = None  # None: all of them
    window: int = 8
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    attn_impl: str = "xla"
    dtype: jnp.dtype = jnp.float32  # compute dtype of the products; params stay f32
    head_dtype: jnp.dtype = jnp.float32
    # rematerialize each block in the backward pass under ops/remat.py's
    # policy (as TransformerLM.remat): a block keeps its input, the flash
    # kernels' five residuals, the router's ids, the sorted layout made from
    # them and the gate and up products over the routed layer's buffer rows
    # (192.3 MB a layer in smallthinker21b_silo2, which cannot fit without
    # remat), and computes again its norms, the router's logits and weights,
    # output projection, row gathers and down product: 5% of the busy time
    # where a bare checkpoint's second forward was 12% (PERF.md section 5)
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        # no dropout: ``train`` only lets the head hand the trainer its operands
        # the residual stream stays float32: the router reads it, and a
        # choice among near-equal logits should not hang on a bf16 rounding
        h = nn.Embed(self.vocab_size, self.embed_dim, name="tok_embed")(x)
        held = self.num_experts if self.experts_held is None else self.experts_held
        block_cls = remat.block(MoEBlock) if self.remat else MoEBlock
        stats = []
        for i, kind in enumerate(self.layer_kinds):
            h, layer_stats = block_cls(
                kind, self.num_heads, self.num_kv_heads, self.head_dim, self.num_experts,
                self.experts_per_token, self.expert_dim, self.experts_first, held,
                self.window, self.rope_theta, self.rms_eps, self.attn_impl, self.dtype,
                name=f"block_{i}")(h)
            stats.append(layer_stats)
        # one value a layer, for the engine's counters
        self.sow(STATS_COLLECTION, "moe",
                 {k.split("/", 1)[1]: jnp.stack([s[k] for s in stats]) for k in stats[0]},
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        h = RMSNorm(self.rms_eps, self.head_dtype, name="norm_f")(h)
        return decoder_head(self, h, train, dense=nn.Dense(
            self.vocab_size, use_bias=False, name="head", dtype=self.head_dtype))
