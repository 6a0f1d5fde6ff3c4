"""The routed-expert layer's buffers of ``C`` rows and the overflow past them
(``fedml_tpu/ops/moe.py`` :func:`capacity`, :func:`expert_layer`): exact
against a dense per-token loop whatever share of the assignments is held,
the two counters, and no more Mosaic kernels than the layer had before.
On the CPU in interpret mode with a row tile of 8, so that a toy layer has a
capacity to pass."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.mla_moe_transformer import MLABlock
from fedml_tpu.models.moe_transformer import MoEBlock
from fedml_tpu.ops import moe, remat
from tests.test_remat_policy import _equations

# 64 tokens top-2 of 8 outputs, experts 2 and 3 held: 128 assignments, an even
# share of 32 and buffers of 48 rows in tiles of 8
T, K, E, D, F, FIRST, HELD, TILE, C = 64, 2, 8, 16, 8, 2, 2, 8, 48
ABSENT = (0, 7)

# name: tokens that chose (both held experts, expert 2 and an absent one,
# expert 3 and an absent one); the rest chose two absent experts
CASES = {
    "below_capacity": (0, 12, 8),
    "exactly_capacity": (0, 30, 18),
    "one_past_capacity": (0, 30, 19),
    "several_tiles_past": (13, 25, 26),
    "every_assignment_held": (64, 0, 0),
    "none_held": (0, 0, 0),
    "one_expert_holds_all": (0, 64, 0),
}


def held_rows(case):
    both, two, three = CASES[case]
    return 2 * both + two + three


@pytest.fixture(autouse=True)
def small_tile(monkeypatch):
    monkeypatch.setattr(moe, "GMM_TILES", (TILE, 1280, 1280))


def _ids(case, seed=0):
    both, two, three = CASES[case]
    rows = ([(2, 3)] * both + [(2, 5)] * two + [(6, 3)] * three
            + [ABSENT] * (T - both - two - three))
    return jnp.asarray(np.random.RandomState(seed).permutation(np.asarray(rows, np.int32)))


def _operands(seed=1, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    u = jax.random.normal(ks[0], (T, D), dtype)
    weights = jax.nn.softmax(jax.random.normal(ks[1], (T, K)), axis=-1)
    gate, up = (jax.random.normal(k, (HELD, D, F)) * D ** -0.5 for k in ks[2:4])
    down = jax.random.normal(ks[4], (HELD, F, D)) * F ** -0.5
    return u, weights, gate, up, down


def _layer(ids, activation=jax.nn.relu, dtype=jnp.float32, outputs=E):
    def f(u, weights, gate, up, down):
        with moe.router_width(outputs):
            return moe.expert_layer(u, ids, weights, gate, up, down, first=FIRST, count=HELD,
                                    dtype=dtype, activation=activation)
    return f


def _dense(ids, activation=jax.nn.relu):
    """Every token through every held expert, weighted by what it chose."""
    def f(u, weights, gate, up, down):
        chose = ids[:, :, None] == FIRST + jnp.arange(HELD)  # [T, k, held]
        w = jnp.sum(jnp.where(chose, weights[:, :, None], 0.0), axis=1)
        h = activation(jnp.einsum("td,edf->tef", u, gate)) * jnp.einsum("td,edf->tef", u, up)
        return jnp.einsum("te,tef,efd->td", w, h, down)
    return f


@pytest.mark.parametrize("case", sorted(CASES))
def test_layer_equals_the_dense_loop_whatever_is_held(case):
    """Values and the five gradients: held rows below the capacity, at it, one
    past it, several tiles past it, all of them, none, one expert's alone."""
    ids, operands = _ids(case), _operands()
    out, stats = _layer(ids)(*operands)
    np.testing.assert_allclose(out, _dense(ids)(*operands), atol=1e-5)
    assert float(stats["moe/assignments_held"]) == held_rows(case)
    c = jax.random.normal(jax.random.key(4), (T, D))
    got = jax.grad(lambda *a: jnp.sum(_layer(ids)(*a)[0] * c), argnums=range(5))(*operands)
    want = jax.grad(lambda *a: jnp.sum(_dense(ids)(*a) * c), argnums=range(5))(*operands)
    for name, a, b in zip(("u", "weights", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    if held_rows(case):
        assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got)


@pytest.mark.parametrize("activation", [jax.nn.relu, jax.nn.silu], ids=["relu", "silu"])
def test_overflow_in_bfloat16_is_the_uncapped_layer(activation):
    """The overflow's arithmetic is the grouped products': a layer whose
    buffers hold every assignment (no router width said) gives the same
    rows, summed in another order."""
    ids = _ids("several_tiles_past")
    operands = _operands(dtype=jnp.bfloat16)
    c = jax.random.normal(jax.random.key(4), (T, D))

    def value_and_grads(outputs):
        layer = _layer(ids, activation, jnp.bfloat16, outputs)
        out, stats = layer(*operands)
        grads = jax.grad(lambda *a: jnp.sum(layer(*a)[0] * c), argnums=range(5))(*operands)
        return out, grads, float(stats["moe/overflow_tiles"])

    out, grads, tiles = value_and_grads(E)
    whole, whole_grads, none = value_and_grads(None)
    assert tiles > 0 and none == 0
    np.testing.assert_allclose(out, whole, atol=1e-5, rtol=1e-5)
    for a, b in zip(grads, whole_grads):
        np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32), atol=0.05,
                                   rtol=0.02)


@pytest.mark.parametrize("assignments,count,outputs,rows", [
    (8192 * 8, 8, 256, 3072),  # joyai_flash_silo2
    (8192 * 6, 16, 64, 18432),  # smallthinker21b_silo2
    (8192 * 6, 64, 64, 8192 * 6),  # the whole layer: every assignment, as before
    (1000, 1, 64, 512),  # a share under one tile still gets one
    (600, 2, 3, 600),  # never more rows than assignments
])
def test_capacity_at_the_cells_shapes(monkeypatch, assignments, count, outputs, rows):
    monkeypatch.undo()  # the real row tile
    assert moe.GMM_TILES[0] == 512 and moe.CAPACITY_FACTOR == 1.5
    assert moe.capacity(assignments, count, outputs) == rows


def test_router_width_is_said_around_the_call_and_not_kept():
    ids, operands = _ids("several_tiles_past"), _operands()

    def touched():
        _, stats = moe.expert_layer(operands[0], ids, *operands[1:], first=FIRST, count=HELD,
                                    dtype=jnp.float32)
        return float(stats["moe/rows_touched"])

    assert touched() == T * K  # no width said: the share is taken for whole
    with moe.router_width(E):
        assert touched() == C + 4 * TILE
        with moe.router_width(HELD):
            assert touched() == T * K
        assert touched() == C + 4 * TILE
    assert touched() == T * K


@pytest.mark.parametrize("case,tiles", [
    ("below_capacity", 0), ("exactly_capacity", 0), ("one_past_capacity", 1),
    ("several_tiles_past", 4), ("one_expert_holds_all", 2), ("every_assignment_held", 10)])
def test_the_two_counters(case, tiles):
    """``moe/rows_touched`` is the buffers' rows plus whole overflow tiles, a
    tile being one expert's: with every assignment held, expert 2 has 16 rows
    past the capacity (two tiles) and expert 3 all its 64 (eight)."""
    _, stats = jax.jit(_layer(_ids(case)))(*_operands())
    assert float(stats["moe/overflow_tiles"]) == tiles
    assert float(stats["moe/rows_touched"]) == C + TILE * tiles
    assert float(stats["moe/assignments_held"]) == held_rows(case)


# -- the kernel count ----------------------------------------------------------
# Megablox' grouped products in the jaxpr of a routed block's value and
# gradient at the parent of the PR that brought the capacity (PR 34; counted
# there by these equations' walk): 3 gmm forward and 3 gmm + 3 tgmm backward, and
# under ops/remat.py's policy the down product once more. The flash kernels
# beside them: one forward, one backward (two before PR 43). A value alone: 3 and 1.
PARENT_KERNELS = {False: {"grouped": 9, "flash": 2}, True: {"grouped": 10, "flash": 2}}
PARENT_KERNELS_VALUE = {"grouped": 3, "flash": 1}


def _kernels(jaxpr):
    return collections.Counter(
        "flash" if kernel else "grouped"
        for primitive, kernel, _ in _equations(jaxpr) if primitive == "pallas_call")


def _block(kind, remat_on):
    if kind == "moe":
        cls = remat.block(MoEBlock) if remat_on else MoEBlock
        return cls("window", 4, 2, 16, E, K, F, FIRST, HELD, 8, 1.5e6, attn_impl="flash")
    cls = remat.block(MLABlock) if remat_on else MLABlock
    return cls(True, 4, 48, 32, 16, 8, 16, 128, E, K, F, 32, 2.5, FIRST, HELD, 32e6,
               attn_impl="flash")


@pytest.mark.parametrize("remat_on", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("kind", ["moe", "mla"])
def test_a_routed_block_holds_no_more_kernels_than_its_parent(kind, remat_on):
    block = _block(kind, remat_on)
    x = jax.random.normal(jax.random.key(0), (1, T, 64))
    params = block.init(jax.random.key(1), x)

    def value(params):
        return jnp.sum(block.apply(params, x)[0])

    assert dict(_kernels(jax.make_jaxpr(jax.value_and_grad(value))(params).jaxpr)) == (
        PARENT_KERNELS[remat_on])
    assert dict(_kernels(jax.make_jaxpr(value)(params).jaxpr)) == PARENT_KERNELS_VALUE
    # and the block's buffers are the capacity's: its statistics say so
    _, stats = block.apply(params, x)
    assert float(stats["moe/rows_touched"]) % TILE == 0
    assert float(stats["moe/rows_touched"]) < T * K
