"""The one traffic generator: a workload file's ``traffic`` parameters plus
``--seed`` give the federated job's inputs.

A traffic mix is data (``benchmark/workloads/<cell>.json``); this file is the
only code that reads it. Every seed gives the same *work*: the same multiset
of client sizes (dealt to the clients in another order), the same batch,
steps, cohort size and test frequency. The seed changes the images or tokens,
the labels, and which client holds which shard. Nothing here imports the
program under test, so the reference can call all of it.

Rules the program and the reference both follow, stated once:

- client ``i`` owns the rows ``[offset_i, offset_i + size_i)`` of the train
  arrays; its local epoch is those rows in order, in batches of
  ``batch_size`` (``shuffle_each_round`` is off in every cell; sizes are
  multiples of the batch, so there is no partial batch);
- round ``r``'s cohort is everyone when the cohort is the population, else
  ``numpy.random.RandomState(r).choice(total, per_round, replace=False)``:
  FedML's ``client_sampling`` (FedAVGAggregator.py), which the program
  documents that it reproduces.
"""

from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SEED_FOLD = 2 ** 31 - 1


def seed_key(seed: int, stream: int = 0):
    """A jax key from any whole ``--seed`` up to a little over 2**31 (more
    than an int32 holds): the low 31 bits seed it, the rest is folded in."""
    seed = int(seed)
    key = jax.random.key(seed % SEED_FOLD)
    return jax.random.fold_in(jax.random.fold_in(key, seed // SEED_FOLD), stream)


def host_rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def client_sizes(traffic: dict, seed: int) -> np.ndarray:
    """The cell's fixed multiset of client sizes, dealt from the seed."""
    if "client_sizes" in traffic:
        sizes = np.asarray(traffic["client_sizes"], np.int64)
    else:
        sizes = np.full(traffic["clients_total"], traffic["samples_per_client"], np.int64)
    if len(sizes) != traffic["clients_total"]:
        raise ValueError("client_sizes does not list clients_total sizes")
    if (sizes % traffic["batch_size"]).any():
        raise ValueError("every client size must be a multiple of batch_size")
    return host_rng(seed, 1).permutation(sizes)


def partition(sizes: np.ndarray) -> dict[int, np.ndarray]:
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    return {i: np.arange(offsets[i], offsets[i + 1]) for i in range(len(sizes))}


def cohort(traffic: dict, round_idx: int) -> np.ndarray:
    total, per_round = traffic["clients_total"], traffic["clients_per_round"]
    if total == per_round:
        return np.arange(total)
    return np.random.RandomState(round_idx).choice(total, per_round, replace=False)


def skewed_labels(sizes: np.ndarray, classes: int, alpha: float, seed: int) -> np.ndarray:
    """Labels of the train rows: each client draws its own class mix from
    Dirichlet(alpha), then its labels from that mix."""
    rng = host_rng(seed, 2)
    out = []
    for n in sizes:
        mix = rng.dirichlet(np.full(classes, alpha))
        out.append(rng.choice(classes, size=int(n), p=mix))
    return np.concatenate(out).astype(np.int32)


def class_images(seed: int, labels: np.ndarray, hw: int, classes: int, stream: int):
    """[N, hw, hw, 3] float32 images on the device, in one jitted call: a
    seeded template per class (the same for every ``stream``) under unit
    noise, so the loss can fall."""
    return _class_images(seed_key(seed, 100), seed_key(seed, stream), jnp.asarray(labels),
                         hw, classes)


@partial(jax.jit, static_argnums=(3, 4))
def _class_images(k_templates, k_noise, labels, hw, classes):
    templates = jax.random.normal(k_templates, (classes, hw, hw, 3), jnp.float32)
    noise = jax.random.normal(k_noise, (labels.shape[0], hw, hw, 3), jnp.float32)
    return 0.5 * templates[labels] + noise


def ramp_tokens(seed: int, n: int, length: int, alphabet: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` token rows that all differ, each a ramp over a small alphabet
    with its own start and stride, and their next-token targets: learnable,
    so the loss must fall."""
    rng = host_rng(seed, 3)
    if n > alphabet * (alphabet - 1):
        raise ValueError("more rows than distinct (start, stride) pairs")
    pairs = rng.permutation(alphabet * (alphabet - 1))[:n]
    start, stride = pairs % alphabet, 1 + pairs // alphabet
    pos = np.arange(length + 1)[None]
    seq = (start[:, None] + stride[:, None] * pos) % alphabet
    return seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32)


def init_leaf_rule(path: str, shape: tuple, overrides: dict | None = None) -> tuple[str, float]:
    """(kind, standard deviation or value) of a parameter leaf, by its name:
    the benchmark makes the initial weights itself so that the program and
    the reference start from the same values without either taking the
    other's. ``overrides`` (a configuration's ``init``) maps a regular
    expression, searched in the leaf's path, to [kind, multiplier] of the
    rule below."""
    name = path.rstrip("']").split("'")[-1]
    if "batch_stats" in path:
        kind, value = ("ones", 1.0) if name == "var" else ("zeros", 0.0)
    elif name == "bias":
        kind, value = "zeros", 0.0
    elif name == "scale":
        kind, value = "ones", 1.0
    elif name in ("embedding", "pos_embed"):
        kind, value = "normal", 0.02
    elif name == "kernel":
        kind, value = "normal", float(np.prod(shape[:-1])) ** -0.5
    else:
        raise ValueError(f"no init rule for parameter leaf {path}")
    for pattern, (want, multiplier) in (overrides or {}).items():
        if re.search(pattern, path):
            if want != kind:
                raise ValueError(f"init override {pattern!r} expects a {want} leaf, {path} is {kind}")
            value *= multiplier
    return kind, value


def init_variables(seed: int, shapes, shardings=None, overrides: dict | None = None):
    """Every leaf of ``shapes`` (a tree of ShapeDtypeStruct) from the seed,
    on the device, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    rules = [init_leaf_rule(jax.tree_util.keystr(p), s.shape, overrides) for p, s in flat]

    def make(key):
        leaves = []
        for i, ((_, s), (kind, scale)) in enumerate(zip(flat, rules)):
            if kind == "normal":
                leaf = scale * jax.random.normal(jax.random.fold_in(key, i), s.shape, jnp.float32)
            else:
                leaf = jnp.full(s.shape, scale, jnp.float32)
            leaves.append(leaf.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make, out_shardings=shardings)(seed_key(seed, 4))
