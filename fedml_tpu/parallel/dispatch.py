"""Compile dispatcher: pjit when sharded, shard_map when purely mapped.

The Titanax pattern (SNIPPETS [3]) adapted to this engine: every compiled
program in the simulator is lowered through :func:`lower`, which inspects
the program's in/out PartitionSpecs and picks the lowering —

- **pjit** (``jax.jit`` with explicit ``in_shardings``/``out_shardings``)
  when any spec partitions an axis beyond the mapped (client) axes. The
  program body is then *global-view*: GSPMD partitions the math, honoring
  ``with_sharding_constraint`` pins. Calls run under the mesh context so
  bare-PartitionSpec constraints inside model code (models/transformer.py
  ``mp_axis``) resolve.
- **shard_map** (the engine's manual lowering, ``jax.shard_map``) when
  the plan is purely client-mapped — per-device bodies with explicit
  collectives, which sidesteps the XLA SPMD limitation on vmapped grouped
  convolutions.

The two lowerings expect different bodies (manual bodies read
``lax.axis_index``; global bodies index with ``jnp.arange``), so the
caller passes the body matching the specs it built — the dispatcher's job
is picking the compilation pipeline and normalizing specs to shardings,
not rewriting the program.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from fedml_tpu.parallel.mesh import CLIENT_AXIS, named_sharding

Pytree = Any

MAPPED_AXES = frozenset({CLIENT_AXIS})


def _spec_leaves(specs):
    return jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )


def spec_is_sharded(spec: P, mapped_axes=MAPPED_AXES) -> bool:
    """True iff the spec partitions an axis beyond the mapped axes."""
    for entry in spec:
        for ax in (entry if isinstance(entry, tuple) else (entry,)):
            if ax is not None and ax not in mapped_axes:
                return True
    return False


def plan_is_sharded(*spec_trees, mapped_axes=MAPPED_AXES) -> bool:
    """True iff any PartitionSpec leaf in the given trees is sharded
    beyond the mapped (client) axes — the pjit-vs-shard_map switch."""
    return any(
        spec_is_sharded(s, mapped_axes)
        for tree in spec_trees
        for s in _spec_leaves(tree)
    )


def to_shardings(mesh, specs):
    """PartitionSpec (sub)trees -> NamedSharding trees (specs are pytree
    leaves, so prefix trees pass through with their structure intact).
    The ONE spec->sharding conversion — the engine's sharded-at-rest
    placement uses it too."""
    return jax.tree_util.tree_map(
        lambda s: named_sharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


@dataclasses.dataclass
class Lowered:
    """A compiled step function plus how it was lowered.

    ``mode`` is ``"pjit"`` or ``"shard_map"``; ``donate_argnums`` records
    the donation actually passed to the compiler. pjit calls enter the
    mesh context so bare-PartitionSpec ``with_sharding_constraint`` pins
    inside the traced body resolve against the plan's mesh."""

    fn: Any
    mode: str
    mesh: Any
    donate_argnums: tuple

    def __call__(self, *args):
        if self.mode == "pjit":
            with self.mesh:
                return self.fn(*args)
        return self.fn(*args)


def lower(
    fn,
    *,
    mesh,
    in_specs,
    out_specs,
    donate_argnums: tuple = (),
    mapped_axes=MAPPED_AXES,
    check_vma: bool = False,
) -> Lowered:
    """Lower ``fn`` for ``mesh`` according to its PartitionSpecs.

    pjit iff any in/out spec is sharded beyond ``mapped_axes``; the
    engine's shard_map manual lowering otherwise. ``donate_argnums`` is
    honored on both paths (on pjit via jit's native donation; on
    shard_map via the jit wrapper exactly as the engine built by hand
    before this dispatcher existed).

    Every round program constructor routes through here — the padded
    pass/aggregate pair AND the packed-lane trio (buffer init, lane pass,
    aggregate; ``sim/engine.py`` ``_packed_*_impl``) — so packed cohorts
    are served by whichever lowering the specs pick: pjit plans when
    ``shard_rules`` shards the model, the shard_map fallback otherwise
    (docs/PERFORMANCE.md "Packed lanes on sharded plans").
    """
    if plan_is_sharded(in_specs, out_specs, mapped_axes=mapped_axes):
        jitted = jax.jit(
            fn,
            in_shardings=to_shardings(mesh, in_specs),
            out_shardings=to_shardings(mesh, out_specs),
            donate_argnums=donate_argnums,
        )
        return Lowered(jitted, "pjit", mesh, tuple(donate_argnums))
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=frozenset(mapped_axes) & set(mesh.axis_names),
        check_vma=check_vma,
    )
    jitted = jax.jit(mapped, donate_argnums=donate_argnums)
    return Lowered(jitted, "shard_map", mesh, tuple(donate_argnums))


def jit_sharded(fn, mesh, donate_argnums: tuple = ()) -> Lowered:
    """Plain ``jax.jit`` that runs under the mesh context (auto sharding
    propagation from the arguments) — for auxiliary programs like eval
    that consume whatever layout the round program left the model in."""
    return Lowered(
        jax.jit(fn, donate_argnums=donate_argnums), "pjit", mesh,
        tuple(donate_argnums),
    )


def replicate(x, mesh):
    """Pin a (pytree of) value(s) to fully-replicated layout inside a
    traced program — the gather-for-compute step of the FSDP-style plans
    (parallel/rules.py ``gather_compute``): one all-gather per leaf, after
    which every arithmetic op sees exactly the tensors the unsharded
    program sees. Uses NamedSharding, so it is mesh-context-free and safe
    in plain-jit programs too."""
    rep = named_sharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.with_sharding_constraint(leaf, rep), x
    )
