"""Client trainer: the TPU-native replacement for the reference ModelTrainer ABC.

Reference contract (fedml_core/trainer/model_trainer.py:4-37): get/set params,
train(local data, device, args), test. Here the contract is *functional*: a
:class:`ClientTrainer` bundles a Flax module with a task-specific loss/metric
pair, and :func:`make_local_train` compiles "K local epochs of minibatch SGD"
into a single ``lax.scan`` suitable for ``vmap`` over a stacked client axis —
the per-client Python loop of the reference (standalone/fedavg/
my_model_trainer_classification.py:12-60) becomes one XLA program.

Data convention: a *batch* is ``{"x": [B, ...], "y": [B, ...], "mask": [B]}``
(sequence tasks carry a per-token mask ``[B, T]``). Padding examples have
mask 0 and contribute nothing to losses, gradients, or metrics — this is how
ragged per-client datasets live inside fixed-shape jitted code.

Model variables: the full Flax variables dict ``{"params": ..., possibly
"batch_stats": ...}`` is the unit of federation — BN running statistics are
averaged like ordinary weights, matching the reference's deliberate policy
(FedAVGAggregator.py:74-81).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.core import scan as scanlib
from fedml_tpu.obs import trace
from fedml_tpu.ops.head_loss import HEAD_COLLECTION, HeadOperands, head_loss

Pytree = Any
Batch = dict[str, jnp.ndarray]

# A module may ``sow`` per-step statistics of its own (routing counts of an
# expert layer) into this collection while training. They are no model state:
# ``init`` drops them, a training step returns them beside the loss, and
# ``make_local_train`` hands their mean over the client's steps to the round
# program as ``stats/<path>`` metrics.
STATS_COLLECTION = "stats"
STATS_PREFIX = "stats/"
# A module with a multi-token-prediction head ``sow``s, while training, one
# entry into this collection: ``{"logits": [B, T, V], "weight": scalar}``
# (``logits`` what its head gave: the array or the ``HeadOperands``),
# position i predicting the token after the next one. ``init``
# drops it, and ``loss_fn`` adds ``weight * lm_loss`` of those logits against
# the targets one step further on; metrics and eval are of the main logits.
# The collection takes any loss a module owes the trainer: an entry ``{"loss":
# scalar, "weight": scalar}`` is a loss the module computed itself (the index
# loss of a sparse-attention indexer, which no logits express), and
# ``loss_fn`` adds ``weight * loss``. One entry a name, any number of names.
MTP_COLLECTION = "mtp"


def _flat_stats(tree: Pytree) -> dict[str, jnp.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(p, "key", p)) for p in path): leaf for path, leaf in flat}

# ---------------------------------------------------------------------------
# Task losses / metrics
# ---------------------------------------------------------------------------


def _per_unmasked(total: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def _masked_mean(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    return _per_unmasked(jnp.sum(values * mask), mask)


def classification_loss(logits: jnp.ndarray, batch: Batch) -> jnp.ndarray:
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
    return _masked_mean(ce, batch["mask"])


def classification_metrics(logits: jnp.ndarray, batch: Batch) -> dict[str, jnp.ndarray]:
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
    correct = (jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32)
    m = batch["mask"]
    return {
        "test_correct": jnp.sum(correct * m),
        "test_loss": jnp.sum(ce * m),
        "test_total": jnp.sum(m),
    }


def lm_loss(logits: jnp.ndarray, batch: Batch) -> jnp.ndarray:
    """Next-token loss for [B, T, V] logits with per-token mask [B, T]
    (reference my_model_trainer_nwp.py — Shakespeare / StackOverflow NWP).
    ``logits`` may be the :class:`HeadOperands` they would be made from (a
    decoder's, while training): the same loss by ``ops/head_loss.py``, which
    never holds the ``[rows, V]`` array where that is large."""
    if isinstance(logits, HeadOperands):
        return _per_unmasked(head_loss(logits, batch["y"], batch["mask"]), batch["mask"])
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
    return _masked_mean(ce, batch["mask"])


def one_token_further(batch: Batch) -> Batch:
    """``y`` and ``mask`` of a sequence batch moved one position on: position
    i's target becomes ``y[i + 1]``, and a row's last position, which has
    none, leaves the mask."""
    y = jnp.roll(batch["y"], -1, axis=1)
    mask = jnp.roll(batch["mask"], -1, axis=1).at[:, -1].set(0)
    return {**batch, "y": y, "mask": mask}


def lm_metrics(logits: jnp.ndarray, batch: Batch) -> dict[str, jnp.ndarray]:
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
    correct = (jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32)
    m = batch["mask"]
    return {
        "test_correct": jnp.sum(correct * m),
        "test_loss": jnp.sum(ce * m),
        "test_total": jnp.sum(m),
    }


def tag_loss(logits: jnp.ndarray, batch: Batch) -> jnp.ndarray:
    """Multi-label (tag prediction, stackoverflow_lr): sigmoid BCE against a
    multi-hot target (reference my_model_trainer_tag_prediction.py)."""
    bce = optax.sigmoid_binary_cross_entropy(logits, batch["y"]).sum(-1)
    return _masked_mean(bce, batch["mask"])


def tag_metrics(logits: jnp.ndarray, batch: Batch) -> dict[str, jnp.ndarray]:
    bce = optax.sigmoid_binary_cross_entropy(logits, batch["y"]).sum(-1)
    pred = (logits > 0.0).astype(jnp.float32)
    y = batch["y"]
    m = batch["mask"][:, None]
    tp = jnp.sum(pred * y * m)
    return {
        "test_correct": tp,  # reference reports precision-style counts
        "test_loss": jnp.sum(bce * batch["mask"]),
        "test_total": jnp.maximum(jnp.sum(pred * m), 1.0),
        "test_precision": tp / jnp.maximum(jnp.sum(pred * m), 1.0),
        "test_recall": tp / jnp.maximum(jnp.sum(y * m), 1.0),
    }


def _pixel_mask(batch: Batch, ce: jnp.ndarray) -> jnp.ndarray:
    """Broadcast an example-level [B] (or pixel-level [B, H, W]) mask to the
    per-pixel CE shape."""
    m = batch["mask"]
    while m.ndim < ce.ndim:
        m = m[..., None]
    return jnp.broadcast_to(m, ce.shape)


def _masked_seg_ce(logits: jnp.ndarray, batch: Batch):
    """Shared validity contract for the segmentation loss AND metrics: labels
    outside [0, C) (e.g. the 255 ignore label, reference fedseg/utils.py
    Evaluator.add_batch's (gt >= 0) & (gt < num_class)) leave the mask, and CE
    runs on clipped labels — out-of-range labels yield inf, and inf * 0-mask
    is NaN. Returns (ce, mask, clipped labels)."""
    num_classes = logits.shape[-1]
    y = batch["y"]
    valid = ((y >= 0) & (y < num_classes)).astype(jnp.float32)
    y_safe = jnp.clip(y, 0, num_classes - 1)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, y_safe)
    m = _pixel_mask(batch, ce) * valid
    return ce, m, y_safe


def segmentation_loss(logits: jnp.ndarray, batch: Batch) -> jnp.ndarray:
    """Per-pixel CE for [B, H, W, C] logits vs [B, H, W] int labels
    (reference fedml_api/distributed/fedseg/utils.py SegmentationLosses.CELoss)."""
    ce, m, _ = _masked_seg_ce(logits, batch)
    return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)


def segmentation_metrics(logits: jnp.ndarray, batch: Batch) -> dict[str, jnp.ndarray]:
    pred = jnp.argmax(logits, -1)
    num_classes = logits.shape[-1]
    ce, m, y_safe = _masked_seg_ce(logits, batch)
    correct = (pred == batch["y"]).astype(jnp.float32)
    # confusion matrix [C, C] (true, pred) — the fedseg Evaluator's core
    # (reference fedseg/utils.py Evaluator.add_batch confusion accumulation)
    idx = y_safe * num_classes + pred  # in-bounds even for ignored labels (masked to 0)
    conf = jnp.zeros((num_classes * num_classes,), jnp.float32).at[idx.ravel()].add(m.ravel())
    return {
        "test_correct": jnp.sum(correct * m),
        "test_loss": jnp.sum(ce * m),  # per-pixel sum; engine divides by total
        "test_total": jnp.sum(m),
        "confusion": conf.reshape(num_classes, num_classes),
    }


TASKS: dict[str, tuple[Callable, Callable]] = {
    "classification": (classification_loss, classification_metrics),
    "nwp": (lm_loss, lm_metrics),
    "char_lm": (lm_loss, lm_metrics),
    "tag": (tag_loss, tag_metrics),
    "segmentation": (segmentation_loss, segmentation_metrics),
}


# ---------------------------------------------------------------------------
# ClientTrainer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ClientTrainer:
    """Bundles a Flax module with task loss/metrics and local-opt settings.

    ``prox_mu``: FedProx proximal coefficient μ — the term the reference's
    distributed fedprox package *omits* (SURVEY §2.2); implemented here for
    real (loss += μ/2 · ||params − global||²).
    """

    module: Any  # flax.linen.Module
    task: str = "classification"
    optimizer: optax.GradientTransformation = dataclasses.field(
        default_factory=lambda: optax.sgd(0.03)
    )
    epochs: int = 1
    prox_mu: float = 0.0

    @property
    def loss_and_metrics(self):
        return TASKS[self.task]

    def init(self, rng: jax.Array, sample_batch: Batch) -> Pytree:
        variables = self.module.init(
            {"params": rng, "dropout": rng}, sample_batch["x"], train=False
        )
        return {k: v for k, v in variables.items()
                if k not in (STATS_COLLECTION, MTP_COLLECTION)}

    # -- single gradient step on one masked batch ------------------------------

    def loss_fn(self, params: Pytree, model_state: Pytree, global_params: Pytree,
                batch: Batch, rng: jax.Array):
        mutable = [*model_state.keys(), STATS_COLLECTION, MTP_COLLECTION]
        if self.loss_and_metrics[0] is lm_loss:
            mutable.append(HEAD_COLLECTION)  # lm_loss takes a decoder's operands
        out = self.module.apply(
            {"params": params, **model_state},
            batch["x"],
            train=True,
            mutable=mutable,
            rngs={"dropout": rng},
        )
        logits, new_model_state = out
        stats = _flat_stats(new_model_state.pop(STATS_COLLECTION, {}))
        with jax.named_scope(trace.SCOPE_LOSS):
            loss = self.loss_and_metrics[0](logits, batch)
        for owed in (new_model_state.pop(MTP_COLLECTION, None) or {}).values():
            if "loss" in owed:  # a loss the module computed itself
                loss = loss + owed["weight"] * owed["loss"]
                continue
            with jax.named_scope(trace.SCOPE_MTP), jax.named_scope(trace.SCOPE_LOSS):
                loss = loss + owed["weight"] * lm_loss(owed["logits"], one_token_further(batch))
        if self.prox_mu > 0.0:
            from fedml_tpu.core import tree as treelib

            diff = treelib.tree_sub(params, global_params)
            loss = loss + 0.5 * self.prox_mu * treelib.tree_dot(diff, diff)
        return loss, (new_model_state, stats)

    def train_step(self, variables: Pytree, opt_state, global_params: Pytree,
                   batch: Batch, rng: jax.Array):
        return self.train_step_stats(variables, opt_state, global_params, batch, rng)[:3]

    def train_step_stats(self, variables: Pytree, opt_state, global_params: Pytree,
                         batch: Batch, rng: jax.Array):
        """:meth:`train_step`, and the step's ``stats`` ({} from a module
        that sows none)."""
        params = variables["params"]
        model_state = {k: v for k, v in variables.items() if k != "params"}
        # jax marks the backward ops ``transpose(jvp(...))`` inside the scope,
        # which is what splits forward from backward in a device trace
        with jax.named_scope(trace.SCOPE_FWD_BWD):
            (loss, (new_model_state, stats)), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True
            )(params, model_state, global_params, batch, rng)
        with jax.named_scope(trace.SCOPE_OPT):
            # A fully-padded batch (mask all zero) must be a no-op: gradients
            # are already zero there, but guard optimizer statistics too.
            has_data = jnp.sum(batch["mask"]) > 0
            updates, new_opt_state = self.optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(has_data, n, o), new_params, params
            )
            new_opt_state = jax.tree.map(
                lambda n, o: jnp.where(has_data, n, o), new_opt_state, opt_state
            )
            new_model_state = jax.tree.map(
                lambda n, o: jnp.where(has_data, n, o), new_model_state, model_state
            )
        return {"params": new_params, **new_model_state}, new_opt_state, loss, stats

    # -- evaluation ------------------------------------------------------------

    def eval_batch(self, variables: Pytree, batch: Batch) -> dict[str, jnp.ndarray]:
        logits = self.module.apply(variables, batch["x"], train=False)
        return self.loss_and_metrics[1](logits, batch)


# ---------------------------------------------------------------------------
# Local training program: K epochs × steps as one lax.scan
# ---------------------------------------------------------------------------


def make_local_train(trainer: ClientTrainer):
    """Returns ``local_train(global_variables, data, rng, num_steps=None)
    -> (variables, metrics)``.

    ``data`` holds one client's epoch of batches, stacked on a leading steps
    axis: ``{"x": [S, B, ...], "y": [S, B, ...], "mask": [S, B]}``. The
    function runs ``trainer.epochs`` passes over those S batches as a single
    nested scan — the whole thing is jit/vmap-compatible, so a cohort of C
    clients is ``vmap(local_train)`` over a [C, S, B, ...] stack.

    ``num_steps`` (optional scalar, vmappable per client) bounds the local
    work: scan steps with global index >= num_steps are masked no-ops. This
    is the SURVEY "hard parts" mask-based early exit enabling heterogeneous
    local-step counts (FedProx straggler protocol / FedNova per-client τ,
    reference standalone/fednova/fednova.py:79-154) inside the one-compile
    round program: stragglers run e_i < E epochs, i.e. num_steps = e_i · S.

    Replaces the reference hot loop (my_model_trainer_classification.train,
    reference standalone/fedavg/my_model_trainer_classification.py:12: Python
    for-epoch/for-batch with .to(device) per batch).
    """

    def local_train(global_variables: Pytree, data: Batch, rng: jax.Array,
                    num_steps=None):
        global_params = global_variables["params"]
        opt_state = trainer.optimizer.init(global_variables["params"])
        S = jax.tree.leaves(data)[0].shape[0]

        def epoch_body(carry, e):
            variables, opt_state, rng = carry

            def step_body(carry, xs):
                variables, opt_state, rng = carry
                s, batch = xs
                if num_steps is not None:
                    active = ((e * S + s) < num_steps).astype(jnp.float32)
                    batch = dict(batch)
                    batch["mask"] = batch["mask"] * active
                rng, step_rng = jax.random.split(rng)
                variables, opt_state, loss, stats = trainer.train_step_stats(
                    variables, opt_state, global_params, batch, step_rng
                )
                # weight for the loss average: did this step see any data?
                w = (jnp.sum(batch["mask"]) > 0).astype(jnp.float32)
                return (variables, opt_state, rng), (loss, w, stats)

            with trace.loop(trace.SCOPE_LOOP_STEPS, carry):
                (variables, opt_state, rng), (losses, ws, stats) = scanlib.scan(
                    step_body, carry, (jnp.arange(S), data)
                )
            stat_sums = jax.tree.map(lambda s: jnp.tensordot(ws, s, 1), stats)
            return (variables, opt_state, rng), (jnp.sum(losses * ws), jnp.sum(ws), stat_sums)

        carry = (global_variables, opt_state, rng)
        with trace.loop(trace.SCOPE_LOOP_EPOCHS, carry):
            (variables, opt_state, rng), (loss_sums, w_sums, stat_sums) = scanlib.scan(
                epoch_body, carry, jnp.arange(trainer.epochs)
            )
        # mean loss over executed (unmasked) steps of the last executed epoch
        if num_steps is None:
            last = trainer.epochs - 1
        else:
            last = jnp.maximum(
                jnp.minimum((num_steps - 1) // S, trainer.epochs - 1), 0
            )
        metrics = {
            "train_loss": loss_sums[last] / jnp.maximum(w_sums[last], 1.0)
        }
        # the module's own statistics: their mean over the executed steps
        steps = jnp.maximum(jnp.sum(w_sums), 1.0)
        for name, sums in stat_sums.items():
            metrics[STATS_PREFIX + name] = jnp.sum(sums, axis=0) / steps
        return variables, metrics

    return local_train


def make_lane_step(trainer: ClientTrainer):
    """One packed-lane step: ``lane_step(variables, opt_state, global_variables,
    opt0, batch, rng, is_first) -> (variables, opt_state, loss, w)``.

    The packed execution mode (sim/engine.py, SimConfig.pack_lanes) scans a
    lane carrying ONE client's training state at a time; ``is_first`` marks a
    client boundary — the carry is reset to the broadcast global variables and
    the freshly-initialized optimizer state ``opt0`` (a pure select, no
    arithmetic, so the reset is bit-exact) before the ordinary
    :meth:`ClientTrainer.train_step` runs. ``w`` is the step's loss weight
    (did this step see any data), exactly as in :func:`make_local_train`'s
    step body. Designed to be ``vmap``-ed over the lane axis with ``is_first``
    a per-lane scalar."""

    def lane_step(variables: Pytree, opt_state, global_variables: Pytree,
                  opt0, batch: Batch, rng: jax.Array, is_first):
        reset = lambda fresh, carried: jax.tree.map(  # noqa: E731
            lambda a, b: jnp.where(is_first, a, b), fresh, carried
        )
        variables = reset(global_variables, variables)
        opt_state = reset(opt0, opt_state)
        variables, opt_state, loss = trainer.train_step(
            variables, opt_state, global_variables["params"], batch, rng
        )
        w = (jnp.sum(batch["mask"]) > 0).astype(jnp.float32)
        return variables, opt_state, loss, w

    return lane_step


def make_local_update(trainer: ClientTrainer, codec=None, local_train_fn=None):
    """Compressed local-update program: ``local_update(global_variables,
    data, rng, residual=None, num_steps=None) -> (payload, new_residual,
    metrics)``.

    Runs :func:`make_local_train`, takes the model delta, adds the carried
    error-feedback ``residual`` (compress/error_feedback.py), and encodes it
    with ``codec`` (compress/codec.py) — the client side of the
    update-compression subsystem in one jit-compatible function.
    ``codec=None`` returns the raw delta (``payload`` is a pytree);
    otherwise ``payload`` is an ``EncodedUpdate`` and ``metrics`` gains
    ``uplink_bytes``/``uplink_dense_bytes``.
    """
    from fedml_tpu.compress import error_feedback as ef
    from fedml_tpu.compress.codec import tree_bytes
    from fedml_tpu.core import tree as treelib

    local_train = local_train_fn or make_local_train(trainer)

    def local_update(global_variables, data, rng, residual=None, num_steps=None):
        new_vars, metrics = local_train(global_variables, data, rng, num_steps)
        delta = treelib.tree_sub(new_vars, global_variables)
        if codec is None:
            return delta, residual, metrics
        comp = ef.compensate(delta, residual)
        enc, _, new_residual = ef.encode_with_feedback(
            codec, comp, jax.random.fold_in(rng, 0xC0DEC)
        )
        metrics = dict(metrics)
        metrics["uplink_bytes"] = jnp.float32(enc.nbytes)
        metrics["uplink_dense_bytes"] = jnp.float32(tree_bytes(delta))
        return enc, new_residual, metrics

    return local_update


def make_local_eval(trainer: ClientTrainer):
    """``local_eval(variables, data) -> summed metric dict`` over [S, B, ...]
    batches; vmap over clients for the all-client eval the reference does
    serially (FedAVGAggregator.test_on_server_for_all_clients,
    FedAVGAggregator.py:110-164)."""

    def local_eval(variables: Pytree, data: Batch):
        def step(carry, batch):
            m = trainer.eval_batch(variables, batch)
            return carry, m

        _, metrics = scanlib.scan(step, 0, data)
        return jax.tree.map(lambda x: jnp.sum(x, axis=0), metrics)

    return local_eval
