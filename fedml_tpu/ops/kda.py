"""Kimi Delta Attention's recurrence (a gated delta rule with one decay a key
channel) in chunked form, forward and backward, the short causal convolution
that feeds it, and the mixer's three pointwise chains as one operator each.

A head keeps a state ``S`` ``[d_k, d_v]`` that every token first decays, a
key channel at its own rate, then rewrites by a delta rule, then reads
(:func:`kda_reference` runs exactly this, token by token, in float32):

    S~  = Diag(exp g_t) S_{t-1}                     g_t <= 0: log-decays [d_k]
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T        beta_t in (0, 1)
    o_t = S_t^T q_t                                 S_0 = 0

:func:`kda` computes the same in chunks of ``C`` tokens. With ``gamma_r`` the
log-decays cumulated from the chunk's start and ``S_0`` the state that
enters, the delta rule's writes ``u`` solve a unit lower-triangular system

    (I + Diag(beta) strict_tril(A)) U = Diag(beta) (V - K+ S_0)
    A_ij = sum_c k_ic k_jc exp(gamma_ic - gamma_jc)  (i > j),   K+_i = k_i * exp(gamma_i)
    o_r  = (q_r * exp gamma_r)^T S_0 + sum_{i<=r} B_ri u_i
    B_ri = sum_c q_rc k_ic exp(gamma_rc - gamma_ic)           (r >= i)
    S_C  = Diag(exp gamma_C) S_0 + sum_i (k_i * exp(gamma_C - gamma_i)) u_i^T

**Every exponent formed is a difference with the later index first, so it is
<= 0**: ``exp(-gamma)`` alone, which overflows float32 once a chunk's
cumulated log-decay passes -88, is never computed. ``A`` and ``B`` are built
over sub-blocks of ``SUB`` rows. Rows in *different* sub-blocks meet on the
MXU, a level a product from the chunk's halves down to pairs of sub-blocks:
a level's second blocks' rows against its first blocks' columns, both
factors taken against the second block's first row (``exp(gamma_i - rho) *
exp(rho - gamma_j)``, each <= 1; ``rho`` cancels, so it bears no gradient).
Rows of one sub-block meet on the VPU in float32, a diagonal at a time (a
row against the row ``d`` before it, one lane reduction), the mask applied to
the exponent and not to its ``exp`` (an ``inf`` selected away would still put a
NaN in a gradient). The unit triangular system is inverted in float32:
blocks of ``ELIMINATED`` rows on the diagonal by elimination on the VPU, a
column a step, then blocks that double (``[[P, 0], [X, Q]]^-1 = [[P^-1, 0],
[-Q^-1 X P^-1, Q^-1]]``: no power of the system is formed, so nothing grows
that the inverse does not hold). The log-decays are cumulated by float32
adds (shifts that double). The state, the cumulated log-decays and the solve
are float32 whatever the products' dtype, and every float32 product asks
Mosaic for ``contract_precision<fp32>``.

The program is two Mosaic kernels, ``kda_fwd`` and ``kda_bwd``
(``trace.KDA_FWD_KERNEL_NAME`` / ``KDA_BWD_KERNEL_NAME``; interpreted on the
CPU). A grid step holds a group of ``GROUP`` tokens of one head in VMEM; the
group axis is sequential and carries the state, transposed (``[d_v, d_k]``: a
key channel's decay is a lane's), in scratch, so the walk over chunks is
inside and only the five operands, the output and one state a group (67 MB
a layer at T 8192) cross HBM. A group's chunks' local quantities are formed
side by side (arrays ``[R, C, ...]``), so that one chunk's waits on the MXU
and on lane reductions are another's work; only the walk takes the chunks in
turn. ``kda_bwd`` (under ``jax.custom_vjp``) takes the groups last to first: it
forms a group's local quantities and walks it again from its entering
state, walks back over its chunks carrying the state's cotangent, then
forms the chunks' five gradients by hand, side by side (the inverse's
cotangent is ``-T^T dT T^T`` below the diagonal, the cumulated sum's a
cumulated sum reversed). Under a
rematerialised block the output and the groups' states are kept by name
(``ops/remat.py``: ``kda/out``, ``kda/states``) and the block's second forward
runs no kernel. Mosaic tiles every shape the repo calls with (``d_k`` 16 to
128); a chunk that is no power of two of 16 rows or more is refused on
every backend.

Scope ``attn/kda/scan`` (``obs/trace.py``) is round both kernels' calls, so
their custom calls' ``op_name`` holds it; every call leaves a ``kda/call``
program note.

**The chains between the projections and the scan** are three operators,
each a ``jax.custom_vjp`` over two small Mosaic kernels (``CHAIN_KERNELS``;
interpreted on the CPU), a forward and a backward written by hand:

    conv_act(y, w)             y [B, T, n d] kept projection, w [K, n d] taps
                               -> [B, n, T, d]: short_conv, SiLU, and for q and
                               k the l2norm over a head (q's times d ** -0.5)
    decay(a, dt_bias, A_log)   a [B, T, n d] -> g [B, n, T, d] float32:
                               -exp(A_log) a head times softplus(a + dt_bias)
    gated_norm(o, gate, w)     o [B, n, T, d], gate [B, T, n d] -> [B, T, n d]:
                               RMSNorm over a head under w, times sigmoid(gate)

A grid step holds ``BLOCK`` tokens of one head (a head's ``d`` columns are a
block of lanes, so the move between ``[B, T, n d]`` and ``[B, n, T, d]`` is
the BlockSpecs' index maps and costs no pass), reads each operand once,
holds float32 in VMEM only and writes each result once; inside, one loop
walks the block in pieces of ``PIECE`` rows, short enough that a piece's
float32 values stay in registers (a whole block's spill: twice the bundles,
compiled for the v5e) and one piece is all the kernel's program holds (eight
unrolled doubled the time jax takes to trace and lower a layer). The taps
read the ``HALO`` rows before a block through a second BlockSpec on the same
array (zeros before the first token), and the convolution's backward the ``HALO``
rows after it (of the input and of the cotangent: it remakes the chain there
too, so the input's gradient reads the convolution's cotangent shifted
forward in time with zeros after the last token and no block waits for
another). A backward remakes what it needs from the operator's own inputs:
SiLU, the norms' sums a row, softplus. Nothing is saved, so a rematerialised
block keeps no new name (its second forward runs the forward kernels again);
the parameters' gradients (the ``K`` taps', ``dt_bias``'s, ``A_log``'s, the
norm weight's) are sums over tokens held in an output block across a head's
sequential grid steps. The forwards call :func:`short_conv` and
:func:`l2norm` as this module holds them when the operator is traced (the
benchmark's controls stand other functions in their place). Float32 runs
from the operands to each result: the composition's bfloat16 stops after the
convolution, after SiLU, after the norm and after the sigmoid are gone, none
is added; the logistic is one ``tanh`` (:func:`_sigmoid`). Mosaic takes a
head's columns as lanes: on the TPU ``d`` is a multiple of 128 or there is
one head. Scopes ``attn/kda/conv``, ``attn/kda/decay`` and ``attn/kda/gate``
(``CHAIN_SCOPES``) are round the kernels' calls, forward
and backward, and every operator leaves a ``kda/chain`` program note (``op``,
``rows``, ``columns``, the ``bytes`` one forward has to move). ``*_reference``
are the plain XLA compositions the operators replace, which the tests hold
them to: compiled for the v5e inside the round program those moved 16.5 GB a
layer-step (two float32 transposes a stream for the move to heads alone)
where the operators move 3 (``PERF.md`` sections 5 and 6, PR 46).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.obs import trace
from fedml_tpu.ops import remat
from fedml_tpu.ops.attention import _interpret_on

HI = jax.lax.Precision.HIGHEST
CHUNK = 64  # tokens a chunk: the one rule (PERF.md section 6, PR 38)
SUB = 8  # rows of a sub-block of A and B: a float32 sublane tile
ELIMINATED = 16  # rows of a diagonal block of the triangular system inverted on the VPU
# tokens a grid step, and between kept states: a state a chunk would be 268 MB
# a layer at T 8192 where a state a group of 256 is 67 (PERF.md section 6, PR 39)
GROUP = 256


L2_EPS = 1e-6  # of l2norm, and of conv_act's backward, which remakes the norm by hand
CHAIN_NOTE = "kda/chain"  # the program note each of the three operators below leaves


def short_conv(x, w):
    """Causal depthwise convolution along the sequence: ``x`` ``[B, T, C]``,
    ``w`` ``[K, C]`` (tap ``K - 1`` meets the token itself), zeros before the
    first token, no bias: ``y_t = sum_j w_j * x_{t - (K - 1) + j}``.
    Accumulated in float32, returned in ``x``'s dtype. The zeros are put
    before the sequence in ``x``'s dtype and a tap's slice is widened as it is
    read (``ops/shortconv.py`` ``_taps``' form, the same values bit for bit):
    no float32 copy of the padded input exists."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(taps))
    return y.astype(x.dtype)


def l2norm(x, eps: float = L2_EPS):
    """``x * rsqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# The mixer's three pointwise chains (the module docstring's last part): for
# each the plain composition, the two kernels, and the operator over them.
# ---------------------------------------------------------------------------

BLOCK = 2048  # tokens a grid step (a step costs 0.35 us beside its work: PERF.md section 6, PR 46)
HALO = 16  # rows read beside a block for the taps: bfloat16's sublane tile, at least K - 1
PIECE = 128  # rows a kernel works at a time: a piece's float32 values stay in registers
# a chain's scope; its kernels are ``kda_<chain>_fwd`` and ``kda_<chain>_bwd``
CHAIN_SCOPES = {"conv": trace.SCOPE_KDA_CONV, "decay": trace.SCOPE_KDA_DECAY,
                "gate": trace.SCOPE_KDA_GATE}
CHAIN_KERNELS = tuple(f"kda_{chain}_{side}" for chain in CHAIN_SCOPES for side in ("fwd", "bwd"))


def _heads(x, n):
    """``[B, T, n d] -> [B, n, T, d]``: the layout the scan kernels take."""
    b, t, c = x.shape
    return x.reshape(b, t, n, c // n).transpose(0, 2, 1, 3)


def _flat(x):
    """``[B, n, T, d] -> [B, T, n d]``: the layout the projections take."""
    b, n, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n * d)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _sigmoid(x):
    """The logistic function by one ``tanh`` (a transcendental unit's op) in
    place of an ``exp`` and a division refined on the VPU: within float32's
    rounding of ``jax.nn.sigmoid`` (7e-8 absolute)."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _whole_blocks(x, axis, t):
    """``x`` with its token axis padded by zeros to ``t``: whole blocks."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, t - x.shape[axis])
    return jnp.pad(x, pad) if pad[axis][1] else x


def _row(x):
    """A parameter ``[width]`` as the float32 row ``[1, width]`` a kernel reads."""
    return x.astype(jnp.float32).reshape(1, -1)


class _Blocks:
    """The BlockSpecs of a chain's kernels over the grid (head, batch,
    block): ``bt`` tokens a block of ``t`` (padded to whole blocks), a head's
    ``d`` columns."""

    def __init__(self, tokens, d):
        self.d = d
        unit = HALO if tokens <= PIECE else PIECE  # a block is one short piece or whole pieces
        self.bt = min(BLOCK, -(-tokens // unit) * unit)
        self.t = -(-tokens // self.bt) * self.bt
        self.per, self.last = self.bt // HALO, self.t // HALO - 1

    def grid(self, heads, batch):
        return heads, batch, self.t // self.bt

    def _halo(self, after):  # the HALO rows before a block (clamped at 0) or after it (at the end)
        if after:
            return lambda j: jnp.minimum((j + 1) * self.per, self.last)
        return lambda j: jnp.maximum(j * self.per - 1, 0)

    def flat(self, halo=None):
        """Of ``[B, T, n d]``: a block's rows, or the HALO rows ``halo`` it."""
        if halo is None:
            return pl.BlockSpec((None, self.bt, self.d), lambda h, b, j: (b, j, h))
        at = self._halo(halo == "after")
        return pl.BlockSpec((None, HALO, self.d), lambda h, b, j: (b, at(j), h))

    def head(self, halo=None):
        """Of ``[B, n, T, d]``."""
        if halo is None:
            return pl.BlockSpec((None, None, self.bt, self.d), lambda h, b, j: (b, h, j, 0))
        at = self._halo(halo == "after")
        return pl.BlockSpec((None, None, HALO, self.d), lambda h, b, j: (b, h, at(j), 0))

    def columns(self, rows):
        """Of ``[rows, n d]``: a head's columns of a parameter or of a sum."""
        return pl.BlockSpec((rows, self.d), lambda h, b, j: (0, h))

    def shared(self):
        """Of ``[1, d]``: a row every head reads whole."""
        return pl.BlockSpec((1, self.d), lambda h, b, j: (0, 0))


def _chain_call(body, chain, backward, blocks, heads, batch, operands, **specs):
    """The forward or the ``backward`` kernel of ``chain`` on ``operands`` under
    the chain's scope. A backward sums parameters' gradients over tokens in an
    output block: its batch and block axes are sequential."""
    along = "arbitrary" if backward else "parallel"
    with jax.named_scope(CHAIN_SCOPES[chain]):
        return pl.pallas_call(
            body, name=f"kda_{chain}_{'bwd' if backward else 'fwd'}",
            grid=blocks.grid(heads, batch),
            interpret=_interpret_on(jax.default_backend()),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", along, along)),
            **specs)(*operands)


def _first_step():
    return (pl.program_id(1) == 0) & (pl.program_id(2) == 0)


def _pieces(rows, body, carry=None):
    """``body(piece, r0, size, carry) -> carry`` over a block's ``rows`` in
    pieces of ``PIECE`` (one loop, so a kernel's program holds one piece)."""
    size = min(PIECE, rows)

    def step(piece, carry):
        return body(piece, pl.multiple_of(piece * size, size), size, carry)

    return jax.lax.fori_loop(0, rows // size, step, carry)


def _halo_before(before_ref):
    """The HALO rows the second BlockSpec read before a block: zeros before
    the first token."""
    before = before_ref[...]
    return jnp.where(pl.program_id(2) == 0, jnp.zeros_like(before), before)


def _halo_after(after_ref, zeros_after_the_last):
    """The HALO rows the BlockSpec after a block read (clamped at the
    sequence's end: zeros there where the caller's arithmetic needs them)."""
    after = after_ref[...]
    if zeros_after_the_last:
        after = jnp.where(pl.program_id(2) == pl.num_programs(2) - 1, jnp.zeros_like(after), after)
    return after


def _rows_before(ref, before, piece, r0):
    """The HALO rows before row ``r0`` of a block: the block's own, or for its
    first piece ``before``."""
    own = ref[pl.ds(pl.multiple_of(jnp.maximum(r0 - HALO, 0), HALO), HALO)]
    return jnp.where(piece == 0, before, own)


def _rows_after(ref, after, r1):
    """The HALO rows from row ``r1`` of a block on: the block's own, or past
    its last piece ``after``."""
    own = ref[pl.ds(pl.multiple_of(jnp.minimum(r1, ref.shape[0] - HALO), HALO), HALO)]
    return jnp.where(r1 >= ref.shape[0], after, own)


def _chain_note(op, x, moved):
    """``kda/chain``: the operator, its rows and columns, and the bytes one
    forward call has to move (each operand read once, each result written
    once)."""
    trace.program_note(CHAIN_NOTE, op=op, rows=x.shape[0] * x.shape[1], columns=x.shape[2],
                       bytes=moved)


def conv_act_reference(y, w, *, heads, norm, scale=1.0):
    """The composition :func:`conv_act` replaces, as the module wrote it:
    convolution, SiLU, the move to heads, and for q and k the l2norm over a
    head times ``scale``."""
    a = _heads(jax.nn.silu(short_conv(y, w)), heads)
    if norm:
        a = l2norm(a) * scale
    return a.astype(y.dtype)


def _conv_fwd_kernel(y_ref, before_ref, w_ref, out_ref, *, norm, scale):
    w, before = w_ref[...], _halo_before(before_ref)

    def piece(i, r0, rows, _):
        x = jnp.concatenate([_rows_before(y_ref, before, i, r0), y_ref[pl.ds(r0, rows)]], axis=0)
        c = short_conv(x.astype(jnp.float32)[None], w)[0, HALO:]
        a = c * _sigmoid(c)
        if norm:
            a = l2norm(a) * scale
        out_ref[pl.ds(r0, rows)] = a.astype(out_ref.dtype)

    _pieces(y_ref.shape[0], piece)


def _conv_bwd_kernel(y_ref, before_ref, after_ref, w_ref, d_ref, d_after_ref, d_y_ref, d_w_ref,
                     *, norm, scale):
    """A piece's rows and the HALO after them: the chain is remade in float32
    up to the SiLU (and the norm's two sums a row), the input's gradient reads
    the convolution's cotangent shifted forward in time (zeros after the last
    token) and the taps' gradients are ``K`` sums of it against the shifted
    input."""
    f32, taps = jnp.float32, w_ref.shape[0]
    w, before = w_ref[...].astype(f32), _halo_before(before_ref)
    after, d_after = _halo_after(after_ref, False), _halo_after(d_after_ref, True)

    def piece(i, r0, rows, d_w):
        x = jnp.concatenate([_rows_before(y_ref, before, i, r0), y_ref[pl.ds(r0, rows)],
                             _rows_after(y_ref, after, r0 + rows)], axis=0).astype(f32)
        c = short_conv(x[None], w)[0, HALO:]
        s = _sigmoid(c)
        d_a = jnp.concatenate([d_ref[pl.ds(r0, rows)],
                               _rows_after(d_ref, d_after, r0 + rows)], axis=0).astype(f32)
        if norm:
            a = c * s
            r = jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
            d_a = scale * r * (d_a - a * (r * r * jnp.sum(d_a * a, axis=-1, keepdims=True)))
        d_c = d_a * (s * (1.0 + c * (1.0 - s)))
        d_y = sum(d_c[taps - 1 - j:taps - 1 - j + rows] * w[j:j + 1] for j in range(taps))
        d_y_ref[pl.ds(r0, rows)] = d_y.astype(d_y_ref.dtype)
        first = HALO - (taps - 1)  # of the rows the taps met
        met = [x[first + j:first + j + rows] for j in range(taps)]
        return tuple(d_w[j] + jnp.sum(d_c[:rows] * met[j], axis=0, keepdims=True)
                     for j in range(taps))

    d_w = _pieces(y_ref.shape[0], piece, (jnp.zeros((1, w.shape[1]), f32),) * taps)

    @pl.when(_first_step())
    def _():
        d_w_ref[...] = jnp.zeros_like(d_w_ref)

    for j in range(taps):
        d_w_ref[j:j + 1, :] += d_w[j]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_act(y, w, heads, norm, scale):
    b, t, c = y.shape
    blocks = _Blocks(t, c // heads)
    y = _whole_blocks(y, 1, blocks.t)
    out = _chain_call(
        functools.partial(_conv_fwd_kernel, norm=norm, scale=scale), "conv", False, blocks,
        heads, b, (y, y, w),
        in_specs=[blocks.flat(), blocks.flat("before"), blocks.columns(w.shape[0])],
        out_specs=blocks.head(),
        out_shape=jax.ShapeDtypeStruct((b, heads, blocks.t, blocks.d), y.dtype))
    return out[:, :, :t]


def _conv_act_fwd(y, w, heads, norm, scale):
    return _conv_act(y, w, heads, norm, scale), (y, w)


def _conv_act_bwd(heads, norm, scale, res, d_out):
    y, w = res
    b, t, c = y.shape
    blocks = _Blocks(t, c // heads)
    y, d_out = _whole_blocks(y, 1, blocks.t), _whole_blocks(d_out.astype(y.dtype), 2, blocks.t)
    d_y, d_w = _chain_call(
        functools.partial(_conv_bwd_kernel, norm=norm, scale=scale), "conv", True, blocks,
        heads, b, (y, y, y, w, d_out, d_out),
        in_specs=[blocks.flat(), blocks.flat("before"), blocks.flat("after"),
                  blocks.columns(w.shape[0]), blocks.head(), blocks.head("after")],
        out_specs=[blocks.flat(), blocks.columns(w.shape[0])],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)])
    return d_y[:, :t], d_w.astype(w.dtype)


_conv_act.defvjp(_conv_act_fwd, _conv_act_bwd)


def conv_act(y, w, *, heads, norm, scale=1.0):
    """A kept projection ``y`` ``[B, T, n d]`` under its taps ``w`` ``[K, n
    d]`` (``K - 1 <= HALO``) -> ``[B, n, T, d]`` in ``y``'s dtype:
    :func:`short_conv`, SiLU, and where ``norm`` the :func:`l2norm` over a head
    times ``scale``, float32 from the convolution's output on."""
    if w.shape[0] - 1 > HALO:
        raise ValueError(
            f"conv_act: {w.shape[0]} taps reach past the {HALO} rows read before a block")
    _chain_note("conv_act", y, 2 * y.size * jnp.dtype(y.dtype).itemsize)
    return _conv_act(y, w, heads, norm, scale)


def decay_reference(a, dt_bias, a_log):
    """The composition :func:`decay` replaces: ``-exp(A_log)`` a head times
    ``softplus(a + dt_bias)``, float32, head-major."""
    rate = jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    return -jnp.exp(a_log)[:, None, None] * _heads(rate, a_log.shape[0])


def _decay_fwd_kernel(a_ref, bias_ref, rate_ref, g_ref):
    bias, rate = bias_ref[...], rate_ref[...]

    def piece(i, r0, rows, _):
        g_ref[pl.ds(r0, rows)] = rate * _softplus(a_ref[pl.ds(r0, rows)].astype(jnp.float32) + bias)

    _pieces(a_ref.shape[0], piece)


def _decay_bwd_kernel(a_ref, bias_ref, rate_ref, d_g_ref, d_a_ref, d_bias_ref, d_log_ref):
    """``softplus`` is remade, its slope is the sigmoid, and both parameters'
    gradients are column sums of the same pass (``A_log``'s is the sum of
    ``d_g * g`` over its head's columns)."""
    bias, rate = bias_ref[...], rate_ref[...]

    def piece(i, r0, rows, sums):
        x = a_ref[pl.ds(r0, rows)].astype(jnp.float32) + bias
        through = rate * d_g_ref[pl.ds(r0, rows)]
        d_x = through * _sigmoid(x)
        d_a_ref[pl.ds(r0, rows)] = d_x.astype(d_a_ref.dtype)
        return (sums[0] + jnp.sum(d_x, axis=0, keepdims=True),
                sums[1] + jnp.sum(through * _softplus(x), axis=0, keepdims=True))

    d_bias, d_log = _pieces(a_ref.shape[0], piece, (jnp.zeros_like(bias),) * 2)

    @pl.when(_first_step())
    def _():
        d_bias_ref[...] = jnp.zeros_like(d_bias_ref)
        d_log_ref[...] = jnp.zeros_like(d_log_ref)

    d_bias_ref[...] += d_bias
    d_log_ref[...] += d_log


def _decay_operands(a, dt_bias, a_log):
    """The two parameters a row of ``[1, n d]`` each: the bias, and a head's
    rate ``-exp(A_log)`` on each of its columns."""
    rate = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), a.shape[2] // a_log.shape[0])
    return _row(dt_bias), _row(rate)


@jax.custom_vjp
def _decay(a, dt_bias, a_log):
    b, t, c = a.shape
    n = a_log.shape[0]
    blocks = _Blocks(t, c // n)
    g = _chain_call(
        _decay_fwd_kernel, "decay", False, blocks, n, b,
        (_whole_blocks(a, 1, blocks.t), *_decay_operands(a, dt_bias, a_log)),
        in_specs=[blocks.flat(), blocks.columns(1), blocks.columns(1)], out_specs=blocks.head(),
        out_shape=jax.ShapeDtypeStruct((b, n, blocks.t, blocks.d), jnp.float32))
    return g[:, :, :t]


def _decay_fwd(a, dt_bias, a_log):
    return _decay(a, dt_bias, a_log), (a, dt_bias, a_log)


def _decay_bwd(res, d_g):
    a, dt_bias, a_log = res
    b, t, c = a.shape
    n = a_log.shape[0]
    blocks = _Blocks(t, c // n)
    row = jax.ShapeDtypeStruct((1, c), jnp.float32)
    d_a, d_bias, d_log = _chain_call(
        _decay_bwd_kernel, "decay", True, blocks, n, b,
        (_whole_blocks(a, 1, blocks.t), *_decay_operands(a, dt_bias, a_log),
         _whole_blocks(d_g.astype(jnp.float32), 2, blocks.t)),
        in_specs=[blocks.flat(), blocks.columns(1), blocks.columns(1), blocks.head()],
        out_specs=[blocks.flat(), blocks.columns(1), blocks.columns(1)],
        out_shape=[jax.ShapeDtypeStruct((b, blocks.t, c), a.dtype), row, row])
    return (d_a[:, :t], d_bias.reshape(dt_bias.shape).astype(dt_bias.dtype),
            jnp.sum(d_log.reshape(n, -1), axis=1).astype(a_log.dtype))


_decay.defvjp(_decay_fwd, _decay_bwd)


def decay(a, dt_bias, a_log):
    """The low-rank gate's output ``a`` ``[B, T, n d]``, ``dt_bias`` ``[n d]``
    and ``A_log`` ``[n]`` -> the log-decays ``g`` ``[B, n, T, d]`` float32
    (<= 0): ``-exp(A_log) * softplus(a + dt_bias)``."""
    _chain_note("decay", a, a.size * (jnp.dtype(a.dtype).itemsize + 4))
    return _decay(a, dt_bias, a_log)


def gated_norm_reference(o, gate, weight, *, eps):
    """The composition :func:`gated_norm` replaces: RMSNorm over a head
    (float32 statistics, the output in ``gate``'s dtype), the move back to
    ``[B, T, n d]``, times the sigmoid of ``gate``."""
    o = o.astype(jnp.float32)
    normed = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps) * weight
    return _flat(normed.astype(gate.dtype)) * jax.nn.sigmoid(gate)


def _normed(o, eps):
    """``(o / rms(o), 1 / rms(o))`` of a piece's rows, float32."""
    o = o.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * r, r


def _gate_fwd_kernel(o_ref, gate_ref, weight_ref, out_ref, *, eps):
    weight = weight_ref[...]

    def piece(i, r0, rows, _):
        normed, _ = _normed(o_ref[pl.ds(r0, rows)], eps)
        out = normed * weight * _sigmoid(gate_ref[pl.ds(r0, rows)].astype(jnp.float32))
        out_ref[pl.ds(r0, rows)] = out.astype(out_ref.dtype)

    _pieces(o_ref.shape[0], piece)


def _gate_bwd_kernel(o_ref, gate_ref, weight_ref, d_ref, d_o_ref, d_gate_ref, d_weight_ref, *,
                     eps):
    """Both gradients, each in its operand's layout, and the norm weight's
    sum over this head's rows (the heads' sums are added outside)."""
    weight = weight_ref[...]

    def piece(i, r0, rows, d_weight):
        normed, r = _normed(o_ref[pl.ds(r0, rows)], eps)
        s = _sigmoid(gate_ref[pl.ds(r0, rows)].astype(jnp.float32))
        d_normed = d_ref[pl.ds(r0, rows)].astype(jnp.float32) * s
        through = d_normed * normed
        d_gate_ref[pl.ds(r0, rows)] = (through * weight * (1.0 - s)).astype(d_gate_ref.dtype)
        d_o = r * (d_normed * weight
                   - normed * jnp.mean(through * weight, axis=-1, keepdims=True))
        d_o_ref[pl.ds(r0, rows)] = d_o.astype(d_o_ref.dtype)
        return d_weight + jnp.sum(through, axis=0, keepdims=True)

    d_weight = _pieces(o_ref.shape[0], piece, jnp.zeros_like(weight))

    @pl.when(_first_step())
    def _():
        d_weight_ref[...] = jnp.zeros_like(d_weight_ref)

    d_weight_ref[...] += d_weight


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_norm(o, gate, weight, eps):
    b, n, t, d = o.shape
    blocks = _Blocks(t, d)
    out = _chain_call(
        functools.partial(_gate_fwd_kernel, eps=eps), "gate", False, blocks, n, b,
        (_whole_blocks(o, 2, blocks.t), _whole_blocks(gate, 1, blocks.t), _row(weight)),
        in_specs=[blocks.head(), blocks.flat(), blocks.shared()], out_specs=blocks.flat(),
        out_shape=jax.ShapeDtypeStruct((b, blocks.t, n * d), gate.dtype))
    return out[:, :t]


def _gated_norm_fwd(o, gate, weight, eps):
    return _gated_norm(o, gate, weight, eps), (o, gate, weight)


def _gated_norm_bwd(eps, res, d_out):
    o, gate, weight = res
    b, n, t, d = o.shape
    blocks = _Blocks(t, d)
    padded = [_whole_blocks(x, axis, blocks.t) for x, axis in ((o, 2), (gate, 1), (d_out, 1))]
    d_o, d_gate, d_weight = _chain_call(
        functools.partial(_gate_bwd_kernel, eps=eps), "gate", True, blocks, n, b,
        (padded[0], padded[1], _row(weight), padded[2].astype(gate.dtype)),
        in_specs=[blocks.head(), blocks.flat(), blocks.shared(), blocks.flat()],
        out_specs=[blocks.head(), blocks.flat(), blocks.columns(1)],
        out_shape=[jax.ShapeDtypeStruct(padded[0].shape, o.dtype),
                   jax.ShapeDtypeStruct(padded[1].shape, gate.dtype),
                   jax.ShapeDtypeStruct((1, n * d), jnp.float32)])
    return (d_o[:, :, :t], d_gate[:, :t],
            jnp.sum(d_weight.reshape(n, d), axis=0).astype(weight.dtype))


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def gated_norm(o, gate, weight, *, eps):
    """The scan's output ``o`` ``[B, n, T, d]`` and the output gate's
    pre-activation ``gate`` ``[B, T, n d]`` -> ``[B, T, n d]`` in ``gate``'s
    dtype: RMSNorm over a head under ``weight`` ``[d]``, times
    ``sigmoid(gate)``, float32 up to the result."""
    _chain_note("gated_norm", gate, 3 * gate.size * jnp.dtype(gate.dtype).itemsize)
    return _gated_norm(o, gate, weight, eps)


def kda_reference(q, k, v, g, beta):
    """The recurrence token by token in float32: ``q``, ``k``, ``g`` ``[B, H,
    T, d_k]``, ``v`` ``[B, H, T, d_v]``, ``beta`` ``[B, H, T]`` -> ``[B, H, T,
    d_v]`` float32. The definition the chunked form is held to, and the
    ``xla`` path of the module."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=HI))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HI)

    s0 = jnp.zeros((*q.shape[:2], q.shape[-1], v.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2)


def decay_floor(g, chunk: int = CHUNK):
    """The most negative log-decay a chunk of ``g`` ``[..., T, d_k]``
    cumulates (a scalar): how far below 0 the chunked form's exponents
    reach."""
    t = g.shape[-2]
    g = jnp.pad(g, [(0, 0)] * (g.ndim - 2) + [(0, -t % chunk), (0, 0)])
    return jnp.min(jnp.sum(g.reshape(*g.shape[:-2], -1, chunk, g.shape[-1]), axis=-2))


# ---------------------------------------------------------------------------
# Mosaic kernels. A grid step holds a group of chunks of one head in VMEM and
# walks them in order; the state is carried transposed (``[d_v, d_k]``, so a
# key channel's decay is a lane's) in scratch over the sequential group axis.
# ---------------------------------------------------------------------------

NEG = -1e30  # a masked exponent: its exp is 0, and no inf enters the arithmetic


def _dot(a, b, form):
    """``a @ b`` (``nn``), ``a @ b.T`` (``nt``) or ``a.T @ b`` (``tn``) over the
    last two axes, the leading one a batch: in the operands' dtype with
    float32 accumulation; float32 operands at ``highest``, which Mosaic gives
    only when asked (``contract_precision<fp32>``)."""
    lead = a.ndim - 2
    lhs, rhs = {"nn": (1, 0), "nt": (1, 1), "tn": (0, 0)}[form]
    batch = tuple(range(lead))
    return jax.lax.dot_general(a, b, (((lead + lhs,), (lead + rhs,)), (batch, batch)),
                               preferred_element_type=jnp.float32,
                               precision=HI if a.dtype == jnp.float32 else None)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _square(c, sub):
    """Where an entry of a ``[C, C]`` square lies, as the masks are cut from
    it by one comparison each: ``(rows - columns, rows ^ columns, rows, rows'
    place in their sub-block)``. Blocks are powers of two, so two indices
    share a block of ``n`` rows iff their exclusive-or is under ``n``."""
    ri, ci, row = _iota((c, c), 0), _iota((c, c), 1), _iota((c, 1), 0)
    return ri - ci, ri ^ ci, row, row & (sub - 1)


def _shifted(x, d):
    """Row ``t`` of every chunk holds ``x[t - d]`` (the first ``d`` rows wrap:
    callers mask); a negative ``d`` shifts back."""
    return pltpu.roll(x, d % x.shape[-2], x.ndim - 2) if d else x


def _levels(c, sub):
    """Half-widths of the blocks whose lower-left quarters go through the
    MXU: from the chunk's halves down to pairs of sub-blocks."""
    out, h = [], c // 2
    while h >= sub:
        out.append(h)
        h //= 2
    return out


def _far_factors(gamma, h):
    """``(rows, cols)`` ``[R, C, d_k]`` of a level of ``h``-row blocks, taken
    in pairs against the cumulated log-decay ``rho`` of a pair's second
    block's first row (later than every row of the first block, no later
    than any of the second): ``exp(gamma_i - rho)`` on the second block and 0
    on the first, ``exp(rho - gamma_j)`` on the first and 0 on the second:
    every exponent <= 0."""
    r, c, d_k = gamma.shape
    second = _iota((c, 1), 0) & h != 0
    rho = jnp.concatenate([jnp.broadcast_to(gamma[:, p + h:p + h + 1], (r, 2 * h, d_k))
                           for p in range(0, c, 2 * h)], axis=1)
    f = jnp.exp(jnp.where(second, gamma - rho, rho - gamma))
    rows = jnp.where(second, f, 0.0)
    return rows, f - rows


def _cumulated(x, row, reverse=False):
    """The sum over a chunk's rows up to and including each row (``reverse``:
    from each row on), by shifts that double: float32 adds on the VPU."""
    c, s = x.shape[-2], 1
    while s < c:
        if reverse:
            x = x + jnp.where(row < c - s, _shifted(x, -s), 0.0)
        else:
            x = x + jnp.where(row >= s, _shifted(x, s), 0.0)
        s *= 2
    return x


def _near(kf, gamma, d, pos):
    """Diagonal ``d`` of a sub-block: ``(k[t - d] * decay, decay)``, ``decay`` =
    ``exp(gamma[t] - gamma[t - d])`` on the rows that have a row ``d`` before
    them in their sub-block and 0 on the others (the mask is on the
    exponent)."""
    decay = jnp.exp(jnp.where(pos >= d, gamma - _shifted(gamma, d), NEG))
    return _shifted(kf, d) * decay, decay


def _local(q, k, v, g, beta_row, square, sub):
    """The local quantities of a group's ``R`` chunks from their operands
    (``q``, ``k`` ``[R, C, d_k]``, ``v`` ``[R, C, d_v]`` in the products' dtype,
    ``g`` ``[R, C, d_k]`` float32, ``beta_row`` ``[R, 1, C]`` float32), as values
    in VMEM, the chunks side by side so that one's waits are another's work:
    ``gamma``, ``beta`` as a column, ``A`` (strictly lower), ``B`` (lower), ``(I
    + Diag(beta) A)^-1`` and its product with ``Diag(beta) [V | K+]``, all
    float32. Pairs of rows in different sub-blocks meet on the MXU, level by
    level (:func:`_far_factors`); pairs inside a sub-block are formed a
    diagonal at a time on the VPU in float32, a row against the row ``d``
    before it."""
    dt, f32 = q.dtype, jnp.float32
    r, c = q.shape[:2]
    qf, kf = q.astype(f32), k.astype(f32)
    below, apart, row, pos = square
    gamma = _cumulated(g, row)  # inclusive, <= 0
    beta = jnp.sum(jnp.where(below == 0, beta_row, 0.0), axis=2, keepdims=True)  # [R, C, 1]
    a = b = jnp.zeros((r, c, c), f32)
    for h in _levels(c, sub):
        rows, cols = _far_factors(gamma, h)
        prod = _dot(jnp.concatenate([kf * rows, qf * rows], axis=1).astype(dt),
                    (kf * cols).astype(dt), "nt")  # [R, 2 C, C]
        same = apart < 2 * h
        a, b = a + jnp.where(same, prod[:, :c], 0.0), b + jnp.where(same, prod[:, c:], 0.0)
    for d in range(sub):
        earlier, _ = _near(kf, gamma, d, pos)
        on = below == d
        b = b + jnp.where(on, jnp.sum(qf * earlier, axis=2, keepdims=True), 0.0)
        if d:
            a = a + jnp.where(on, jnp.sum(kf * earlier, axis=2, keepdims=True), 0.0)
    low = beta * a
    # (I + low)^-1: blocks of ELIMINATED rows on the diagonal by elimination, a
    # column a step, then blocks that double (no power of low is formed)
    solve = jnp.broadcast_to(jnp.where(below == 0, 1.0, 0.0), (r, c, c))
    size = min(ELIMINATED, c)
    place = row & (size - 1)
    inside = jnp.where(apart < size, low, 0.0)
    for s in range(size - 1):
        col = jnp.sum(jnp.where(below == place - s, inside, 0.0), axis=2, keepdims=True)
        src = jnp.broadcast_to(solve.reshape(r, c // size, size, c)[:, :, s:s + 1],
                               (r, c // size, size, c)).reshape(r, c, c)
        solve = solve - jnp.where(place > s, col * src, 0.0)
    while size < c:
        quarter = jnp.where((apart >= size) & (apart < 2 * size), low, 0.0)
        solve = solve - _dot(_dot(solve, quarter, "nn"), solve, "nn")
        size *= 2
    y = jnp.concatenate([v.astype(f32), kf * jnp.exp(gamma)], axis=2)
    return gamma, beta, a, b, solve, y, _dot(solve, beta * y, "nn")


def _factors(q, k, gamma, uw, d_v):
    """What the walk multiplies, in the products' dtype: the solved keys
    ``w``, ``q e^gamma``, ``k e^(gamma_C - gamma)``; and what is left of a state
    across a chunk, ``e^gamma_C`` ``[R, 1, d_k]`` float32."""
    dt, f32 = q.dtype, jnp.float32
    last = gamma[:, gamma.shape[1] - 1:]
    return (uw[:, :, d_v:].astype(dt), (q.astype(f32) * jnp.exp(gamma)).astype(dt),
            (k.astype(f32) * jnp.exp(last - gamma)).astype(dt), jnp.exp(last))


def _walk(u, w, q_in, k_out, carried, b, st):
    """One chunk of the walk from the transposed state ``st`` ``[d_v, d_k]``
    float32 that enters: the chunk's writes and outputs, the state that
    leaves, and the entering state as the products saw it."""
    c = u.shape[0]
    entered = st.astype(w.dtype)
    both = _dot(jnp.concatenate([w, q_in], axis=0), entered, "nt")
    new = (u - both[:c]).astype(w.dtype)
    out = both[c:] + _dot(b, new, "nn")
    return new, out, carried * st + _dot(new, k_out, "tn"), entered


def _chunks(ref, chunk):
    """A group's rows ``[R C, d]`` as its chunks ``[R, C, d]``."""
    return ref[...].reshape(-1, chunk, ref.shape[-1])


def _prepared(q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, sub):
    """A group's chunks from its blocks: ``(q, k, square, what _local made,
    what _walk takes of each chunk: u, w, q_in, k_out, carried, b)``."""
    q, k, v = (_chunks(ref, chunk) for ref in (q_ref, k_ref, v_ref))
    d_v = v.shape[2]
    square = _square(chunk, sub)
    local = _local(q, k, v, _chunks(g_ref, chunk), beta_ref[...], square, sub)
    gamma, _, _, b, _, _, uw = local
    walked = (uw[:, :, :d_v], *_factors(q, k, gamma, uw, d_v), b.astype(q.dtype))
    return q, k, square, local, walked


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, states_ref, st_ref, *, chunk,
                sub):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st = st_ref[...]
    states_ref[...] = st
    q, *_, walked = _prepared(q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, sub)
    for r in range(q.shape[0]):
        _, out, st, _ = _walk(*(x[r] for x in walked), st)
        out_ref[r * chunk:(r + 1) * chunk, :] = out.astype(out_ref.dtype)
    st_ref[...] = st


def _walk_bwd(d_out, new, entered, st, w, q_in, k_out, carried, b, d_st):
    """:func:`_walk` backward for one chunk, from ``d_out`` ``[C, d_v]`` and
    the cotangent ``d_st`` of the state that left: the cotangents of ``[u |
    w]``, ``B``, ``q e^gamma``, ``k e^(gamma_C - gamma)`` and ``gamma_C`` (through
    ``e^gamma_C``), and of the state that entered. Every product meets the MXU
    in the dtype it met it in forward."""
    dt = w.dtype
    d_st_dt = d_st.astype(dt)
    d_new = _dot(b, d_out, "tn") + _dot(k_out, d_st_dt, "nt")  # [C, d_v]
    d_new_dt = d_new.astype(dt)
    d_uw = jnp.concatenate([d_new, -_dot(d_new_dt, entered, "nn")], axis=1)
    d_last = jnp.sum(d_st * st, axis=0, keepdims=True) * carried  # [1, d_k]
    return (d_uw, _dot(d_out, new, "nt"), _dot(d_out, entered, "nn"), _dot(new, d_st_dt, "nn"),
            d_last, carried * d_st + _dot(d_out, q_in, "tn") - _dot(d_new_dt, w, "tn"))


def _local_bwd(q, k, gamma, beta, a, solve, y, d_uw, d_b, d_q_in, d_k_out, d_last,
               square, sub):
    """The five gradients of a group's chunks from the cotangents
    :func:`_walk_bwd` left and what :func:`_local` made, the chunks side by
    side. The inverse's cotangent is ``-T^T dT T^T`` below the diagonal; the
    cumulated sum's is a cumulated sum reversed; the anchors of
    :func:`_far_factors` cancel in every entry, so they bear no gradient."""
    dt, f32 = q.dtype, jnp.float32
    r, c = q.shape[:2]
    d_v = y.shape[2] - q.shape[2]
    qf, kf = q.astype(f32), k.astype(f32)
    below, apart, row, pos = square
    d_b = jnp.where(below >= 0, d_b, 0.0)
    # uw = T Diag(beta) y, T = (I + Diag(beta) A)^-1, y = [V | K+]
    d_y = _dot(solve, d_uw, "tn")
    d_solve = _dot(d_uw, beta * y, "nt")
    d_low = jnp.where(below > 0, -_dot(_dot(solve, d_solve, "tn"), solve, "nt"), 0.0)
    d_a = beta * d_low
    d_beta = (jnp.sum(d_low * a, axis=2, keepdims=True)
              + jnp.sum(d_y * y, axis=2, keepdims=True))
    d_y = beta * d_y
    d_k_in = d_y[:, :, d_v:]
    # the decayed factors: q e^gamma, K+ = k e^gamma, k e^(gamma_C - gamma)
    decayed = jnp.exp(gamma)
    left = jnp.exp(gamma[:, c - 1:] - gamma)
    d_q = d_q_in * decayed
    d_k = d_k_in * decayed + d_k_out * left
    through_left = d_k_out * kf * left
    d_gamma = (d_q_in * qf + d_k_in * kf) * decayed - through_left
    d_last = d_last + jnp.sum(through_left, axis=1, keepdims=True)
    # A and B, rows in different sub-blocks
    for h in _levels(c, sub):
        rows, cols = _far_factors(gamma, h)
        same = apart < 2 * h
        d_ab = jnp.concatenate([jnp.where(same, d_a, 0.0), jnp.where(same, d_b, 0.0)],
                               axis=1).astype(dt)  # [R, 2 C, C]
        kq_rows = jnp.concatenate([kf * rows, qf * rows], axis=1)
        k_cols = kf * cols
        d_rows = _dot(d_ab, k_cols.astype(dt), "nn")  # [R, 2 C, d_k]
        d_cols = _dot(d_ab, kq_rows.astype(dt), "tn")  # [R, C, d_k]
        d_k = d_k + d_rows[:, :c] * rows + d_cols * cols
        d_q = d_q + d_rows[:, c:] * rows
        through = d_rows * kq_rows
        d_gamma = d_gamma + through[:, :c] + through[:, c:] - d_cols * k_cols
    # ... and inside one, a diagonal at a time
    for d in range(sub):
        earlier, decay = _near(kf, gamma, d, pos)
        on = below == d
        from_b = jnp.sum(jnp.where(on, d_b, 0.0), axis=2, keepdims=True)
        later = from_b * qf  # what the row d before receives, before its decay
        d_q = d_q + from_b * earlier
        if d:
            from_a = jnp.sum(jnp.where(on, d_a, 0.0), axis=2, keepdims=True)
            later = later + from_a * kf
            d_k = d_k + from_a * earlier
            through = later * earlier
            d_gamma = d_gamma + through - _shifted(through, -d)
        d_k = d_k + _shifted(later * decay, -d)
    d_gamma = d_gamma + jnp.where(row == c - 1, d_last, 0.0)
    d_g = _cumulated(d_gamma, row, reverse=True)
    d_beta_row = jnp.sum(jnp.where(below == 0, d_beta, 0.0), axis=1, keepdims=True)  # [R, 1, C]
    return d_q, d_k, d_y[:, :, :d_v], d_g, d_beta_row


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, d_out_ref, d_q_ref, d_k_ref,
                d_v_ref, d_g_ref, d_beta_ref, d_st_ref, *, chunk, sub):
    """A group of chunks, groups last to first: the forward again from the
    group's entering state, the walk back over its chunks, last to first,
    carrying the state's cotangent, then the chunks' gradients side by side."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        d_st_ref[...] = jnp.zeros_like(d_st_ref)

    q, k, square, (gamma, beta, a, _, solve, y, _), walked = _prepared(
        q_ref, k_ref, v_ref, g_ref, beta_ref, chunk, sub)
    d_out = _chunks(d_out_ref, chunk).astype(q.dtype)
    st, seen = states_ref[...], []
    for r in range(q.shape[0]):
        new, _, st_next, entered = _walk(*(x[r] for x in walked), st)
        seen.append((new, entered, st))
        st = st_next
    d_st, back = d_st_ref[...], []
    for r in reversed(range(q.shape[0])):
        *cotangents, d_st = _walk_bwd(d_out[r], *seen[r], *(x[r] for x in walked[1:]), d_st)
        back.append(cotangents)
    d_st_ref[...] = d_st
    grads = _local_bwd(q, k, gamma, beta, a, solve, y,
                       *(jnp.stack(x) for x in zip(*reversed(back))), square, sub)
    for ref, x in zip((d_q_ref, d_k_ref, d_v_ref, d_g_ref), grads):
        ref[...] = x.reshape(ref.shape).astype(ref.dtype)
    d_beta_ref[...] = grads[4]


def _specs(per_group, chunk, d_k, d_v, reverse=0):
    """BlockSpecs of a group's rows of a head: operands ``[B H, T, d]``, beta
    ``[B H, chunks, 1, C]`` (a chunk's row), states ``[B H, G, d_v, d_k]``;
    the ``reverse`` groups last to first."""
    at = (lambda j: reverse - 1 - j) if reverse else (lambda j: j)
    rows = lambda d: pl.BlockSpec((None, per_group * chunk, d), lambda i, j: (i, at(j), 0))  # noqa: E731
    beta = pl.BlockSpec((None, per_group, 1, chunk), lambda i, j: (i, at(j), 0, 0))
    state = pl.BlockSpec((None, None, d_v, d_k), lambda i, j: (i, at(j), 0, 0))
    return rows, beta, state


def _kernel(body, name, chunk, d_k, d_v):
    """What both ``pallas_call``s share: the kernel with its statics, its
    name in the trace, the carried ``[d_v, d_k]`` float32 scratch, heads in
    parallel over groups in sequence."""
    return dict(
        kernel=functools.partial(body, chunk=chunk, sub=math.gcd(chunk, SUB)), name=name,
        scratch_shapes=[pltpu.VMEM((d_v, d_k), jnp.float32)],
        interpret=_interpret_on(jax.default_backend()),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")))


def _forward(q, k, v, g, beta, chunk, per_group):
    """Operands ``[N, T, d]`` (``N`` = B x H, ``T`` whole groups), ``beta``
    ``[N, chunks, 1, C]`` -> outputs ``[N, T, d_v]`` and the transposed state
    that entered each group ``[N, G, d_v, d_k]`` float32."""
    n, t, d_k = q.shape
    d_v = v.shape[-1]
    groups = t // (per_group * chunk)
    rows, beta_spec, state = _specs(per_group, chunk, d_k, d_v)
    with jax.named_scope(trace.SCOPE_KDA_SCAN):
        return pl.pallas_call(
            grid=(n, groups),
            in_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), beta_spec],
            out_specs=[rows(d_v), state],
            out_shape=[jax.ShapeDtypeStruct((n, t, d_v), v.dtype),
                       jax.ShapeDtypeStruct((n, groups, d_v, d_k), jnp.float32)],
            **_kernel(_fwd_kernel, trace.KDA_FWD_KERNEL_NAME, chunk, d_k, d_v),
        )(q, k, v, g, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, v, g, beta, chunk, per_group):
    return _forward(q, k, v, g, beta, chunk, per_group)[0]


def _fwd_rule(q, k, v, g, beta, chunk, per_group):
    out, states = _forward(q, k, v, g, beta, chunk, per_group)
    # kept by a rematerialised block (ops/remat.py), whose second forward
    # then runs no scan; the operands are remade from the kept projections
    out, states = remat.keep(remat.KDA_OUT, out), remat.keep(remat.KDA_STATES, states)
    return out, (q, k, v, g, beta, states)


def _bwd_rule(chunk, per_group, res, d_out):
    q, k, v, g, beta, states = res
    n, d_k, d_v = q.shape[0], q.shape[-1], v.shape[-1]
    groups = states.shape[1]
    rows, beta_spec, state = _specs(per_group, chunk, d_k, d_v, reverse=groups)
    with jax.named_scope(trace.SCOPE_KDA_SCAN):
        return tuple(pl.pallas_call(
            grid=(n, groups),
            in_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), beta_spec, state, rows(d_v)],
            out_specs=[rows(d_k), rows(d_k), rows(d_v), rows(d_k), beta_spec],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)],
            **_kernel(_bwd_kernel, trace.KDA_BWD_KERNEL_NAME, chunk, d_k, d_v),
        )(q, k, v, g, beta, states, d_out))


_scan.defvjp(_fwd_rule, _bwd_rule)


def kda(q, k, v, g, beta, *, chunk: int = CHUNK):
    """The recurrence in chunks of ``chunk`` tokens: ``q``, ``k`` ``[B, H, T,
    d_k]`` (already normalised and scaled), ``v`` ``[B, H, T, d_v]``, ``g``
    ``[B, H, T, d_k]`` log-decays (<= 0), ``beta`` ``[B, H, T]`` -> ``[B, H, T,
    d_v]`` in ``v``'s dtype. No initial state: a sequence starts at zero. T
    need not be a multiple of the chunk (padded tokens write and decay
    nothing)."""
    b, h, t, d_k = q.shape
    if chunk < 16 or chunk & (chunk - 1):
        raise ValueError(
            f"kda: a chunk of {chunk} tokens is not a power of two of at least 16: the kernels "
            "halve it down to sub-blocks, and 16 rows are bfloat16's sublane tile")
    chunks = -(-t // chunk)
    per_group = min(max(GROUP // chunk, 1), chunks)
    pad = -(-chunks // per_group) * per_group * chunk - t
    trace.program_note("kda/call", impl=trace.KDA_FWD_KERNEL_NAME, chunk=chunk, chunks=chunks,
                       heads=h, d_k=d_k, d_v=v.shape[-1], t=t)

    def rows(x):  # [B, H, T, ...] -> [B H, whole groups, ...]
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
        return x.reshape(b * h, *x.shape[2:])

    out = _scan(rows(q), rows(k), rows(v), rows(g.astype(jnp.float32)),
                rows(beta.astype(jnp.float32)).reshape(b * h, -1, 1, chunk), chunk, per_group)
    return out.reshape(b, h, -1, v.shape[-1])[:, :, :t]
