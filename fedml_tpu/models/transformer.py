"""Decoder-only transformer LM for long-context federated clients.

The reference's NLP zoo stops at LSTMs (fedml_api/model/nlp/rnn.py:4,39); this
model extends the zoo to transformer clients with three attention paths:

- ``attn_impl="xla"``  — plain dot-product attention (small sequences; XLA
  fuses it fine).
- ``attn_impl="flash"`` — the pallas blockwise kernel
  (fedml_tpu/ops/attention.py): O(T) memory on one chip.
- ``attn_impl="ring"``  — ring attention over the ``sp`` mesh axis
  (fedml_tpu/parallel/ring_attention.py); the module must then run inside
  ``shard_map`` with the sequence axis sharded (see
  fedml_tpu/parallel/sequence.py). Every other op in this module is
  token-local, so the module is sequence-parallel-safe by construction.

Same interface as the rest of the zoo: int tokens ``[B, T]`` in, logits
``[B, T, V]`` out, ``train`` kwarg, dropout rng when training.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.ops import remat
from fedml_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    flash_attention_head_parallel,
)
from fedml_tpu.ops.head_loss import decoder_head
from fedml_tpu.parallel.ring_attention import ring_attention


class MultiHeadSelfAttention(nn.Module):
    num_heads: int
    attn_impl: str = "xla"  # xla | flash | ring
    sp_axis: str = "sp"
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32  # compute dtype (bf16 on TPU); params stay f32
    # model-parallel mesh axis (docs/PERFORMANCE.md "Sharded client
    # models"): when set, head-axis sharding constraints pin q/k/v to the
    # tensor-parallel layout the partition rules put on the qkv kernel, so
    # each model shard attends over its own heads. Requires tracing under
    # the plan's mesh (parallel/dispatch.py provides the context). GSPMD
    # partitions the xla attention path by heads on its own; Mosaic refuses
    # to have the pallas flash kernel partitioned, so the flash path routes
    # through ops.attention.flash_attention_head_parallel (under any
    # sharded plan a shard_map over the whole mesh, heads split over this
    # axis, with a gathered-xla fallback when heads don't divide it).
    mp_axis: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        from fedml_tpu.parallel.rules import constrain

        b, t, c = x.shape
        head_dim = c // self.num_heads
        qkv = nn.Dense(3 * c, use_bias=False, name="qkv", dtype=self.dtype)(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(a):  # [B, T, C] -> [B, H, T, D]
            return a.reshape(b, t, self.num_heads, head_dim).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        if self.mp_axis:
            hspec = (None, self.mp_axis, None, None)
            q = constrain(q, hspec)
            k = constrain(k, hspec)
            v = constrain(v, hspec)
        if self.attn_impl == "flash":
            # under a sharded plan (active mesh) the kernel runs per device
            # in a shard_map, on its local heads when mp_axis is set; plain
            # kernel otherwise — see flash_attention_head_parallel
            o = flash_attention_head_parallel(q, k, v, axis=self.mp_axis, causal=True)
        elif self.attn_impl == "ring":
            o = ring_attention(q, k, v, axis_name=self.sp_axis, causal=True)
        else:
            o = attention_reference(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, c)
        o = nn.Dense(c, use_bias=False, name="proj", dtype=self.dtype)(o)
        if self.dropout_rate:
            o = nn.Dropout(self.dropout_rate, deterministic=not train)(o)
        return o


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attn_impl: str = "xla"
    sp_axis: str = "sp"
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    # model-parallel mesh axis: when set, the MLP hidden activation is
    # pinned to the column-parallel layout of the Dense_0 kernel and the
    # block output to the replicated boundary layout (the Megatron
    # between-blocks contract) — see parallel/rules.py act_spec
    mp_axis: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        from fedml_tpu.parallel.rules import constrain

        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + MultiHeadSelfAttention(
            self.num_heads, self.attn_impl, self.sp_axis, self.dropout_rate,
            dtype=self.dtype, mp_axis=self.mp_axis,
        )(h, train=train)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        c = x.shape[-1]
        m = nn.Dense(self.mlp_ratio * c, dtype=self.dtype)(h)
        if self.mp_axis:
            m = constrain(m, (None, None, self.mp_axis))
        m = nn.gelu(m)
        m = nn.Dense(c, dtype=self.dtype)(m)
        if self.dropout_rate:
            m = nn.Dropout(self.dropout_rate, deterministic=not train)(m)
        out = x + m
        if self.mp_axis:
            out = constrain(out, (None, None, None))
        return out


class TransformerLM(nn.Module):
    """Causal LM. Position embedding is computed from the *global* token
    position: under sequence parallelism each shard adds ``pos_offset`` (set
    by the SP train step) so token-locality is preserved."""

    vocab_size: int = 90
    embed_dim: int = 128
    num_layers: int = 2
    num_heads: int = 4
    max_len: int = 4096
    attn_impl: str = "xla"
    sp_axis: str = "sp"
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.float32
    # model-parallel mesh axis for tensor-parallel plans (docs/
    # PERFORMANCE.md "Sharded client models"): threaded to every Block so
    # block-boundary activations carry explicit sharding constraints. The
    # engine sets it automatically when a TP rule set is active
    # (sim/engine.py); leave None for unsharded / FSDP-gather execution.
    mp_axis: str | None = None
    # LM-head matmul dtype, independent of the block compute dtype: an f32
    # head keeps the logits and their gradient out of bf16. While a trainer
    # trains, head and loss run in chunks of rows where the float32 logits
    # are large (ops/head_loss.py: no [B, T, V] array, float32 or bf16, is
    # held), in this dtype and at jax's default precision as the plain head
    # does. In cgpt13b_silo2 (V 50,257) head and loss are head_loss_time_pct
    # 21.07 of the busy time (ledger, PR 44), the three products 16.5 of it
    # at the MXU's peak (PERF.md sections 5, 6); no chip run has a bf16 head
    head_dtype: jnp.dtype = jnp.float32
    # rematerialize each block in the backward pass (jax.checkpoint) under
    # ops/remat.py's policy: a block keeps its input and the named values that
    # are small to hold and dear to recompute (under attn_impl="flash" the
    # kernels' residuals q, k, v, output and log-sum-exp, so neither the
    # flash forward kernel nor the qkv product runs twice) and computes its
    # norms, its output projection and its MLP again. The trade when HBM, not
    # the MXU, binds the batch size; no cell runs this model with it on
    # (docs/PERFORMANCE.md)
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False, pos_offset: int | jnp.ndarray = 0):
        b, t = x.shape
        tok = nn.Embed(self.vocab_size, self.embed_dim, name="tok_embed",
                       dtype=self.dtype)(x)
        pos_table = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (self.max_len, self.embed_dim),
        )
        pos_idx = pos_offset + jnp.arange(t)
        h = tok + jnp.take(pos_table, pos_idx, axis=0)[None].astype(self.dtype)
        # train selects the dropout branch: it must be static under remat
        block_cls = remat.block(Block, static_argnums=(2,)) if self.remat else Block
        for i in range(self.num_layers):
            h = block_cls(
                self.num_heads,
                attn_impl=self.attn_impl,
                sp_axis=self.sp_axis,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                mp_axis=self.mp_axis,
                name=f"block_{i}",
            )(h, train)
        h = nn.LayerNorm(dtype=self.dtype, name="ln_f")(h)
        # the loss always receives f32 logits (softmax headroom); with a
        # bf16 head they are bf16-quantized before the upcast
        return decoder_head(self, h, train, dense=nn.Dense(
            self.vocab_size, name="head", dtype=self.head_dtype))
