"""What a rematerialised decoder block keeps for its backward pass.

A block under a bare ``jax.checkpoint`` keeps its input alone, and the
backward pass runs the whole forward again, Mosaic kernels included. The
blocks here are rematerialised under a policy that saves values *by name*
(``jax.checkpoint_policies.save_only_these_names``): the code that computes
a value small to hold and dear to recompute tags it with :func:`keep`, the
policy saves every tagged value, and the recomputed forward loses whatever
only fed those values (XLA and jax's own dead-code pass drop a kernel call
whose outputs nobody reads). Everything untagged (norms, the router, the
output projection, row gathers) is recomputed as before. A tag helps only
where the backward pass reads the *tagged* value: a custom VJP's residuals
tagged inside its forward rule, or a value whose consumers are ordinary
equations.

One name is kept for agreement and not for time: the router's choice
(``moe/ids``). The kept sorted layout was made from the first forward's
choice, and a router may read a stream the block recomputes (one placed
after attention does), which can differ from the first in its last bits and
turn a near tie the other way. Whatever then read the second choice beside
the kept layout (which assignments are held, the chosen scores) would take
one expert's row for another's, or a row nobody wrote. So ``route`` tags the
ids under every scoring and a block chooses once; ``top_k`` and ``softmax``
keep their own untagged outputs, so the name saves no time.

The routed layer's kept values have the rows of its buffers, not of the
assignments: ``moe/gate_out`` and ``moe/up_out`` are ``[C, F]`` with ``C``
the layer's capacity (``ops/moe.py`` ``capacity``: one and a half times the
share of the router's outputs held), tagged inside the layer's own forward
rule; ``moe/order``, ``moe/pos`` and ``moe/ids`` stay ``T x k`` int32. The
rare step that passes the capacity keeps nothing more: its overflow is
recomputed from the layout, tile by tile, in the backward pass.

A delta-attention block (``models/mla_moe_transformer.py`` ``DeltaAttention``)
keeps five: the q, k and v projections *before* their convolutions
(``kda/q``, ``kda/k``, ``kda/v``: they are ``ops/kda.py`` ``conv_act``'s input,
which is all its backward reads, and the operator's forward, one pass over
the projection, costs little to run again), and, tagged inside
the scan's forward rule (``ops/kda.py``), its output ``kda/out`` and the state
that entered each group of chunks ``kda/states``, so that the second forward
runs no scan and the backward starts each group from a kept state. The
log-decays are never kept: they are remade from a ``[T, rank]`` product and
one pass of ``decay``. The mixer's three operators (``conv_act``, ``decay``,
``gated_norm``) save nothing of their own: each backward remakes its chain
from the operator's inputs, so nothing more is kept than before them.

A short-convolution block (``ShortConv`` there) keeps one: its input
projection's output ``shortconv/in`` (``[tokens, 3 D]``: every step of the
elementwise chain and of its backward reads a chunk of it, and the chain
itself, ``B * z``, the taps and ``C * c``, costs less to run again than its
values cost to hold). A grouped-query attention block keeps the flash
kernels' five, as every attention block does.

An EVA block (``EvaAttention`` there; ``ops/eva.py``) keeps the residuals of
its two flash calls under names of its own (``ops/attention.py``
``flash_attention_lse``'s ``keep``): q, k, v and the local call's output and
log-sum-exp, the chunk summaries and the remote call's output and
log-sum-exp; q is one array under two shapes and is kept once. The summaries
and the merge cost little and are remade only as far as the backward reads
them.

A sparse-attention block (``ops/dsa.py``) keeps, beside the flash kernels'
five, **its selection** (``dsa/rows``, ``dsa/cols``, ``dsa/tiles``: the chosen
set's packed bits both ways and the tiles' counts, 16.8 MB a layer at T
8,192): kept for agreement as ``moe/ids`` is, and for time. A selection made
again from index scores that differ in their last bits would choose another
key near rank ``topk`` while the backward kernel read the first forward's
log-sum-exp, so every reader of the set (the masked kernels both ways, the
index loss) reads the kept bits, and the recomputed forward runs neither the
index scores nor the selection. And the index loss's three gradients
(``dsa/dq_index``, ``dsa/dk_index``, ``dsa/dw_index``, 18.1 MB a layer): the
loss's forward rule makes them in its one pass over the scores, so the
backward is a scaling and the second forward runs no such pass.

Outside a rematerialised block a tag is an identity that lowers to nothing,
so a model with ``remat`` off compiles to the program it had without tags.
The list is fixed here and follows no option: a name costs memory, and what
fits was sized on the cells that need remat (``PERF.md`` sections 5 and 6).
"""

from __future__ import annotations

import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu.obs import trace

# ops/attention.py _fwd_rule: the flash kernels' residuals
ATTN_RESIDUALS = ("attn/q", "attn/k", "attn/v", "attn/out", "attn/lse")
MOE_ORDER, MOE_POS, MOE_SIZES = "moe/order", "moe/pos", "moe/sizes"  # ops/moe.py sorted_layout
MOE_GATE_OUT, MOE_UP_OUT = "moe/gate_out", "moe/up_out"  # glu_experts
# ops/moe.py route: the choice the kept layout was made from (see above)
MOE_IDS = "moe/ids"

# models/mla_moe_transformer.py DeltaAttention: the three projections before
# their convolutions; ops/kda.py _fwd_rule: the scan's output and the state
# that entered each group of chunks
KDA_Q, KDA_K, KDA_V = "kda/q", "kda/k", "kda/v"
KDA_OUT, KDA_STATES = "kda/out", "kda/states"
KDA_KEPT = (KDA_Q, KDA_K, KDA_V, KDA_OUT, KDA_STATES)

# models/mla_moe_transformer.py ShortConv: the input projection's [B | C | z]
SHORTCONV_IN = "shortconv/in"

# ops/eva.py: the local and the remote flash call's residuals, in
# ATTN_RESIDUALS' order (q, k, v, out, lse); q is one array under two shapes
EVA_LOCAL = ("eva/q", "eva/k", "eva/v", "eva/local_out", "eva/local_lse")
EVA_REMOTE = ("eva/q", "eva/k_sum", "eva/v_sum", "eva/remote_out", "eva/remote_lse")

# ops/dsa.py: the chosen set as the masked kernels read it (select), and the
# index loss's gradients by the indexer's three outputs (index_loss)
DSA_SELECTION = ("dsa/rows", "dsa/cols", "dsa/tiles")
DSA_INDEX_GRADS = ("dsa/dq_index", "dsa/dk_index", "dsa/dw_index")

KEPT = (*ATTN_RESIDUALS, MOE_ORDER, MOE_POS, MOE_SIZES, MOE_GATE_OUT, MOE_UP_OUT, MOE_IDS,
        *KDA_KEPT, SHORTCONV_IN, *dict.fromkeys(EVA_LOCAL + EVA_REMOTE),
        *DSA_SELECTION, *DSA_INDEX_GRADS)
NOTE = "remat/kept"


class _Inside(threading.local):
    depth = 0  # rematerialised blocks being traced on this thread


_inside = _Inside()


def keep(name: str, x):
    """``x`` tagged ``name`` (one of ``KEPT``) for the blocks' policy. Traced
    inside a rematerialised block it also leaves a ``remat/kept`` program
    note (``obs/trace.py``): the name and the bytes it holds a layer."""
    assert name in KEPT, name
    if _inside.depth:
        dtype = jnp.dtype(x.dtype)
        trace.program_note(NOTE, kept=name, shape=tuple(x.shape), dtype=dtype.name,
                           bytes=x.size * dtype.itemsize)
    return checkpoint_name(x, name)


def block(cls, **remat_kwargs):
    """The flax module ``cls`` rematerialised under the policy. While one of
    its instances is called (which is when jax traces the block, forward and
    backward rules alike) the tags inside note what they keep."""
    lifted = nn.remat(cls, policy=jax.checkpoint_policies.save_only_these_names(*KEPT),
                      **remat_kwargs)

    class Kept(lifted):
        def __call__(self, *args, **kwargs):
            _inside.depth += 1
            try:
                return super().__call__(*args, **kwargs)
            finally:
                _inside.depth -= 1

    Kept.__name__ = Kept.__qualname__ = lifted.__name__
    return Kept
