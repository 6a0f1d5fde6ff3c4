"""Pytree parameter utilities.

TPU-native analogue of the reference's state_dict manipulation helpers
(reference: fedml_core/robustness/robust_aggregation.py:4-29 `vectorize_weight`,
fedml_api/distributed/fedavg/utils.py:7-16 tensor<->list transforms). Model
parameters here are JAX pytrees; flattening to a single vector is used by
robust aggregation (median / norm clipping) and secure aggregation, and the
flat (f32 array + treedef) pair is the wire format of the comm layer — never
pickled objects.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def tree_vectorize(tree: Pytree, exclude: Callable[[str], bool] | None = None) -> jnp.ndarray:
    """Flatten a pytree of arrays into one 1-D vector.

    ``exclude`` receives the joined key-path string (e.g. ``"BatchNorm_0/mean"``)
    and returns True to skip that leaf — mirroring the reference's policy of
    excluding batch-norm statistics from robust statistics
    (robust_aggregation.py:28-29).
    """
    leaves = tree_leaves_with_paths(tree)
    vecs = [jnp.ravel(v) for k, v in leaves if not (exclude and exclude(k))]
    if not vecs:
        return jnp.zeros((0,), dtype=jnp.float32)
    return jnp.concatenate(vecs)


def tree_leaves_with_paths(tree: Pytree) -> list[tuple[str, jnp.ndarray]]:
    """List of (path-string, leaf) pairs in canonical traversal order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(_path_entry_str(p) for p in path)
        out.append((key, leaf))
    return out


def _path_entry_str(entry) -> str:
    if hasattr(entry, "key"):
        return str(entry.key)
    if hasattr(entry, "idx"):
        return str(entry.idx)
    if hasattr(entry, "name"):
        return str(entry.name)
    return str(entry)


def tree_zeros_like(tree: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    """a - b, leafwise."""
    return jax.tree.map(jnp.subtract, a, b)


def tree_dot(a: Pytree, b: Pytree) -> jnp.ndarray:
    parts = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return jax.tree_util.tree_reduce(jnp.add, parts, jnp.float32(0.0))


def tree_weighted_mean(stacked: Pytree, weights: jnp.ndarray) -> Pytree:
    """Weighted mean over a leading axis present on every leaf.

    ``stacked`` has leaves of shape [C, ...]; ``weights`` is [C] (need not be
    normalized — e.g. raw per-client sample counts, matching the reference's
    sample-count weighting in FedAVGAggregator.py:59-88). Weight normalization
    happens in f32 regardless of leaf dtype.
    """
    w = weights.astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-12)

    def _avg(leaf):
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(leaf.astype(jnp.float32) * wb, axis=0).astype(leaf.dtype)

    return jax.tree.map(_avg, stacked)


def tree_stack(trees: Sequence[Pytree]) -> Pytree:
    """Stack a list of identically-structured pytrees along a new axis 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_size(tree: Pytree) -> int:
    """Total number of scalar parameters."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
