"""Partition rules: regex over param paths -> PartitionSpec (SNIPPETS [2]).

The reference has no model-parallel plane at all — a client model must fit
one worker. Here a *rule set* maps every leaf of a variables (or optimizer
state) pytree to a :class:`~jax.sharding.PartitionSpec` by regex-matching the
leaf's ``/``-joined tree path, the fmengine ``match_partition_rules``
pattern: scalars are always replicated, the first matching rule wins, and an
unmatched non-scalar leaf raises naming the offending path — a silently
replicated tensor on a model that needs sharding is an OOM at full shape,
so the matcher fails loudly at plan time instead.

Because optax optimizer states embed the param tree under their own
prefixes (``0/trace/<param path>`` for SGD momentum, ``0/mu/<param path>``
for Adam), the SAME rules match both: rules are written against param-path
*suffixes* (``re.search``, not ``fullmatch``), and the states' scalar
bookkeeping leaves (step counts) fall under the scalar-replication rule.

Built-in rule sets (:func:`rule_set`) cover the model zoo's two families:

- ``transformer_tp`` / ``transformer_fsdp`` — TransformerLM
  (models/transformer.py). TP is the Megatron split (qkv/MLP-in
  column-parallel, proj/MLP-out row-parallel, embed/head over the model
  axis); FSDP shards every matrix over the model axis *at rest* and
  gathers for compute (``gather_compute=True``), which keeps the round
  bit-identical to the unsharded program (all cross-shard movement is
  concat/slice, never a reassociated reduction).
- ``cnn_tp`` / ``cnn_fsdp`` — the conv zoo (CNN/ResNet/VGG): conv kernels
  shard their output-channel axis, dense kernels their output-feature
  axis; BN parameters and statistics stay replicated (they are small and
  federate as ordinary weights). ``cnn_fsdp`` gathers for compute, which
  also sidesteps the XLA SPMD limitation on vmapped grouped convolutions
  (sim/engine.py's shard_map rationale). Note the gather-compute
  bit-identity contract below is guarded for the transformer path; BN
  models' own batch-statistic reductions fuse differently across the two
  programs and match the unsharded round to ~1 ULP, not bitwise
  (measured: 16/287 ResNet-56 leaves, all ``batch_stats/*/mean``).

Rules are COHORT-LAYOUT-AGNOSTIC: a spec names only model axes, never the
``clients`` axis, so the same rule set serves the padded cohort vmap and
the packed-lane programs unchanged — the engine supplies the client-axis
dimension (cohort slots or lanes) outside the spec, and the planner's
per-shard lane binning never consults the rules (docs/PERFORMANCE.md
"Packed lanes on sharded plans").
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np

import jax
from jax.sharding import PartitionSpec as P

from fedml_tpu.parallel.mesh import MODEL_AXIS

Pytree = Any


def _key_name(entry) -> str:
    """One path entry -> its string name (Dict/Attr/Sequence keys alike)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def match_partition_rules(rules, tree) -> Pytree:
    """Pytree of PartitionSpec matching ``tree``'s structure.

    ``rules`` is a sequence of ``(regex, PartitionSpec)`` pairs tried in
    order against each leaf's ``/``-joined path (``re.search``). Scalar
    leaves (rank 0, or a single element) are replicated without consulting
    the rules. A non-scalar leaf no rule matches raises ``ValueError``
    naming the path; end a rule list with ``(".*", P())`` for an explicit
    replicate-the-rest default. A matched spec longer than the leaf's rank
    also raises naming both — a silent rank mismatch would fail much later
    inside XLA with the param name lost.

    Works on concrete arrays and on ``jax.eval_shape`` output alike (only
    ``.shape`` is consulted), and on optax optimizer states (their leaves
    carry the param-path suffix; their scalar counters replicate).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def spec_for(name: str, leaf) -> P:
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()  # scalars are never partitioned
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                if len(spec) > len(shape):
                    raise ValueError(
                        f"partition rule {rule!r} assigns spec {spec} "
                        f"(rank {len(spec)}) to param '{name}' of shape "
                        f"{shape} (rank {len(shape)})"
                    )
                return spec
        raise ValueError(
            f"no partition rule matched param '{name}' (shape {shape}); "
            "add a rule or end the rule list with ('.*', PartitionSpec()) "
            "to replicate unmatched leaves explicitly"
        )

    specs = [
        spec_for("/".join(_key_name(k) for k in kp), leaf)
        for kp, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


@dataclasses.dataclass(frozen=True)
class RuleSet:
    """A named partition plan: the regex rules plus how to compute with it.

    ``gather_compute=True`` is the FSDP-style contract: parameters are
    sharded over the model axis *at rest* (between rounds: global model,
    new-global output) but replicated for the training math itself — the
    engine inserts one gather at program entry, so every arithmetic op sees
    exactly the tensors the unsharded program sees and the round stays
    bit-identical (guarded by tools/shard_smoke.py for the TransformerLM
    path; BN models match to ~1 ULP, see the module note). ``False`` is true
    tensor parallelism: GSPMD partitions the matmuls themselves, trading
    bit-identity (cross-shard reductions reassociate, ~1 ULP) for sharded
    compute and activations.

    ``act_spec`` names the block-boundary activation constraint axes
    (unbatched rank, e.g. ``(None, None, None)`` for [B, T, D]); the engine
    threads it onto modules exposing an ``mp_axis`` field
    (models/transformer.py).
    """

    name: str
    rules: tuple
    gather_compute: bool = False
    act_spec: tuple | None = None


def _transformer_tp_rules():
    # Megatron split: column-parallel into the block, row-parallel out.
    return (
        (r"qkv/kernel$", P(None, MODEL_AXIS)),
        (r"proj/kernel$", P(MODEL_AXIS, None)),
        (r"Dense_0/kernel$", P(None, MODEL_AXIS)),
        (r"Dense_0/bias$", P(MODEL_AXIS)),
        (r"Dense_1/kernel$", P(MODEL_AXIS, None)),
        (r"tok_embed/embedding$", P(None, MODEL_AXIS)),
        (r"pos_embed$", P(None, MODEL_AXIS)),
        (r"head/kernel$", P(None, MODEL_AXIS)),
        (r"head/bias$", P(MODEL_AXIS)),
        (r".*", P()),  # norms, remaining biases: replicated
    )


def _transformer_fsdp_rules():
    # every matrix sharded on its output/embedding axis at rest; 1-D
    # params stay replicated (negligible storage, always divisible-safe)
    return (
        (r"(kernel|embedding)$", P(None, MODEL_AXIS)),
        (r"pos_embed$", P(None, MODEL_AXIS)),
        (r".*", P()),
    )


def _cnn_rules():
    # conv kernels [kh, kw, cin, cout]: shard output channels; dense
    # kernels [in, out]: shard output features; BN params/stats replicated
    return (
        (r"Conv_\d+/kernel$", P(None, None, None, MODEL_AXIS)),
        (r"(Dense_\d+|fc|head|classifier)/kernel$", P(None, MODEL_AXIS)),
        (r".*", P()),
    )


RULE_SETS: dict[str, RuleSet] = {
    "transformer_tp": RuleSet(
        "transformer_tp", _transformer_tp_rules(), gather_compute=False,
        act_spec=(None, None, None),
    ),
    "transformer_fsdp": RuleSet(
        "transformer_fsdp", _transformer_fsdp_rules(), gather_compute=True,
    ),
    "cnn_tp": RuleSet("cnn_tp", _cnn_rules(), gather_compute=False),
    "cnn_fsdp": RuleSet("cnn_fsdp", _cnn_rules(), gather_compute=True),
}
# the conv rules fit the ResNet/VGG zoo unchanged; keep the names the
# models are asked for by
RULE_SETS["resnet_tp"] = dataclasses.replace(
    RULE_SETS["cnn_tp"], name="resnet_tp")
RULE_SETS["resnet_fsdp"] = dataclasses.replace(
    RULE_SETS["cnn_fsdp"], name="resnet_fsdp")


def rule_set(name: str) -> RuleSet:
    """Look up a built-in rule set; unknown names raise listing the options."""
    try:
        return RULE_SETS[name]
    except KeyError:
        raise ValueError(
            f"unknown shard rule set {name!r}; built-ins: "
            f"{sorted(RULE_SETS)}"
        ) from None


def constrain(x, axes: tuple | None):
    """Block-boundary activation constraint: ``with_sharding_constraint``
    with the given PartitionSpec axes (unbatched rank — under
    ``vmap(spmd_axis_name=...)`` the mapped axis is prepended
    automatically). ``None`` is the no-op so modules can thread an optional
    ``mp_axis`` without branching. Must trace under a mesh context (the
    dispatcher's pjit wrapper provides one); outside a trace (eager model
    init) the constraint is semantically a no-op and is skipped, so module
    construction never requires a mesh."""
    if axes is None or not isinstance(x, jax.core.Tracer):
        return x
    return jax.lax.with_sharding_constraint(x, P(*axes))
