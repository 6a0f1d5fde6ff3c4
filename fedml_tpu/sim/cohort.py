"""Host-side cohort staging: ragged client shards -> fixed-shape device stacks.

The reference swaps per-client torch DataLoaders into a fixed pool of Client
objects each round (standalone/fedavg/fedavg_api.py:32-66). The TPU analogue:
for each round's cohort, gather the sampled clients' samples into one padded
array stack ``[C, S, B, ...]`` (C clients × S steps × B batch) with an example
mask, and ship it to device once. Shapes are identical every round, so the
round program compiles exactly once.

Two device layouts share this staging machinery: the padded layout above
(one lane per client, padded to the cohort max — every client scans S_max
steps), and the packed-lane layout (:func:`pack_cohort` /
:func:`pack_index_map`, SimConfig.pack_lanes) that bin-packs the cohort's
executed-step streams into L fixed-length lanes so skewed cohorts stop
burning FLOPs on straggler padding (docs/PERFORMANCE.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class FederatedArrays:
    """An in-memory federated dataset.

    ``arrays``: field name -> [N, ...] numpy array (must include "x" and "y";
    may include a per-token "mask" for sequence tasks).
    ``partition``: client id -> sorted sample indices into those arrays
    (the 8-tuple contract's train_data_local_dict, flattened to indices).
    """

    arrays: dict[str, np.ndarray]
    partition: dict[int, np.ndarray]

    @property
    def num_clients(self) -> int:
        return len(self.partition)

    @property
    def num_samples(self) -> int:
        return len(self.arrays["y"])

    def client_sizes(self) -> np.ndarray:
        return np.asarray([len(self.partition[i]) for i in range(self.num_clients)])

    def max_client_size(self) -> int:
        return int(self.client_sizes().max())

    def index_csr(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, dict | None]:
        """The vectorized form of ``partition``, in a ragged CSR layout:
        ``flat`` (every client's sample rows concatenated, int32),
        ``offsets`` (int64, client row i owns flat[offsets[i]:offsets[i]+
        sizes[i]]), ``sizes`` (int64), and a client-id -> row lookup (None
        when ids are the usual contiguous 0..N-1, so rows are indexed
        directly; cross-silo keys its single-client shards by global silo
        index, hence the general case). CSR rather than a dense padded
        matrix keeps the cache O(total samples) on skewed populations —
        one giant client must not multiply the whole population's footprint.
        Built once (the only remaining O(num_clients) Python loop) and
        cached — every round's staging reads it, so the partition is
        treated as immutable after the first call."""
        cached = self.__dict__.get("_index_csr")
        if cached is None:
            keys = sorted(self.partition)
            sizes = np.asarray(
                [len(self.partition[k]) for k in keys], np.int64
            )
            flat = (
                np.concatenate(
                    [np.asarray(self.partition[k], np.int32).ravel()
                     for k in keys]
                )
                if keys else np.zeros(0, np.int32)
            )
            offsets = np.zeros(len(keys), np.int64)
            if len(keys):
                np.cumsum(sizes[:-1], out=offsets[1:])
            lookup = (
                None if keys == list(range(len(keys)))
                else {k: row for row, k in enumerate(keys)}
            )
            cached = (flat, offsets, sizes, lookup)
            self.__dict__["_index_csr"] = cached
        return cached


def steps_per_epoch(max_client_size: int, batch_size: int) -> int:
    return max(1, -(-max_client_size // batch_size))


def cohort_index_map(
    data: FederatedArrays,
    client_ids: np.ndarray,
    batch_size: int,
    steps: int | None = None,
    rng: np.random.RandomState | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized cohort staging: the round's [C, S, B] int32 sample-index
    map (-1 = empty slot) and [C] float32 true sample counts, built with a
    fixed number of numpy ops per round instead of a per-client Python loop.

    This is the ONE definition of cohort selection: host batch stacks
    (:func:`stack_cohort` gathers rows through it), the on-device gather
    path, block dispatch, and per-client eval all stage via this map, so
    their shuffle/truncation/zero-fill semantics cannot drift.

    ``rng`` shuffles each client's sample order by drawing one
    [C, max cohort size] uniform block and argsorting each row (padding is
    sunk to the tail) — a uniform per-client permutation in one vectorized
    draw, sized by THIS cohort's largest member, not the population's. Clients with more samples than ``steps * batch_size``
    slots keep the first ``slots`` entries of their (shuffled) order — a
    without-replacement subsample over ALL their samples, exactly the old
    permute-then-truncate semantics; weights still report the true client
    size.
    """
    flat, offsets, sizes, lookup = data.index_csr()
    # negative client ids are EMPTY cohort slots (the population model's
    # availability padding, population/model.py RoundView): zero samples,
    # all-(-1) index rows, zero weight — the same shape-stable padding
    # convention the mesh pad already uses, so churned cohorts never change
    # compiled shapes
    ids = np.asarray(client_ids)
    empty = ids < 0
    if lookup is None:
        rows = np.where(empty, 0, ids).astype(np.intp)
    else:
        rows = np.asarray(
            [0 if e else lookup[int(c)] for c, e in zip(ids, empty)],
            dtype=np.intp,
        )
    sz = sizes[rows]
    if empty.any():
        sz = np.where(empty, 0, sz)
    if steps is None:
        steps = steps_per_epoch(int(sz.max()), batch_size)
    slots = steps * batch_size
    # unshuffled, truncation == keeping each row's first `slots` entries, so
    # the gather can stop there; a shuffle must permute the FULL row first
    width = int(sz.max()) if len(sz) else 0
    if rng is None:
        width = min(width, slots)
    width = max(width, 1)
    col = np.arange(width)
    valid = col[None, :] < sz[:, None]
    all_full = bool(valid.all())
    gather = offsets[rows][:, None] + col[None, :]
    guard = max(len(flat) - 1, 0)
    sel = (
        flat[np.minimum(gather, guard)]
        if len(flat) else np.full(gather.shape, -1, np.int32)
    )
    if not all_full:
        sel[~valid] = -1
    if rng is not None:
        # argsort of iid uniforms = a uniform permutation per row (tie
        # probability ~ C*L^2 * 2^-53, ignorable); +inf sinks the padding
        # to the row tail (every pad slot is the same -1, so pad order is
        # irrelevant and the default sort suffices)
        u = rng.random_sample(sel.shape)
        if not all_full:
            u[~valid] = np.inf
        sel = np.take_along_axis(sel, np.argsort(u, axis=1), axis=1)
    if width < slots:
        sel = np.pad(sel, ((0, 0), (0, slots - width)), constant_values=-1)
    elif width > slots:
        sel = sel[:, :slots]
    return (
        np.ascontiguousarray(sel).reshape(len(rows), steps, batch_size),
        sz.astype(np.float32),
    )


def _cohort_index_map_loop(
    data: FederatedArrays,
    client_ids: np.ndarray,
    batch_size: int,
    steps: int | None = None,
    rng: np.random.RandomState | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Pre-vectorization reference (per-client Python loop), kept as the
    oracle for :func:`cohort_index_map`. Shuffle draws differ by construction
    (per-client ``permutation`` calls vs one block draw), so bit-exact
    comparisons use ``rng=None``."""
    sizes = np.asarray([
        0 if int(c) < 0 else len(data.partition[int(c)])
        for c in client_ids
    ])
    if steps is None:
        steps = steps_per_epoch(int(sizes.max()), batch_size)
    slots = steps * batch_size
    C = len(client_ids)
    idx = np.full((C, slots), -1, np.int32)
    for ci, cid in enumerate(client_ids):
        if int(cid) < 0:  # empty slot (population availability padding)
            continue
        sel = data.partition[int(cid)]
        if rng is not None:
            sel = rng.permutation(sel)
        n = min(len(sel), slots)
        idx[ci, :n] = sel[:n]
    return idx.reshape(C, steps, batch_size), sizes.astype(np.float32)


def gather_index_stack(
    arrays: dict[str, np.ndarray], idx: np.ndarray
) -> dict[str, np.ndarray]:
    """Gather dataset rows through an index map (-1 = empty slot) with the
    canonical zero-fill + example-mask semantics: empty slots are zero rows
    with mask 0, and sequence tasks' per-token mask is combined with example
    validity. ``idx`` may have ANY leading shape — [C, S, B] for the padded
    cohort stack, [L, S_lane, B] for packed lanes — so both layouts share
    ONE definition (the host mirror of ``FedSim._gather_batches``)."""
    lead = idx.shape
    flat = idx.reshape(-1)
    valid = flat >= 0
    safe = np.where(valid, flat, 0)
    out: dict[str, np.ndarray] = {}
    for name, arr in arrays.items():
        gathered = arr[safe]
        gathered[~valid] = 0  # empty slots are zero-filled, exactly as before
        out[name] = gathered.reshape(lead + arr.shape[1:])
    example_mask = valid.astype(np.float32).reshape(lead)
    if "mask" in out:
        # sequence tasks: combine per-token mask with example validity
        tok = out["mask"].astype(np.float32)
        out["mask"] = tok * example_mask.reshape(
            example_mask.shape + (1,) * (tok.ndim - example_mask.ndim)
        )
    else:
        out["mask"] = example_mask
    return out


def stack_cohort(
    data: FederatedArrays,
    client_ids: np.ndarray,
    batch_size: int,
    steps: int | None = None,
    rng: np.random.RandomState | None = None,
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Build the round's training stack.

    Returns ``(batch_stack, num_samples)`` where batch_stack leaves are
    [C, S, B, ...] and num_samples is [C] float32 true sample counts (the
    aggregation weights, FedAVGAggregator.py:59-88). ``steps`` pins S so every
    round has identical shapes; default = fit the largest cohort member.
    ``rng`` shuffles each client's sample order (torch DataLoader shuffle
    semantics). Selection runs through :func:`cohort_index_map`, so the host
    stack is the gathered image of the exact index map the on-device path
    ships — one vectorized gather instead of a per-client copy loop.
    """
    idx, sizes = cohort_index_map(data, client_ids, batch_size, steps=steps, rng=rng)
    return gather_index_stack(data.arrays, idx), sizes


# ---------------------------------------------------------------------------
# Packed-lane execution planning (docs/PERFORMANCE.md "Packed-lane cohort
# execution"): instead of one lane per client padded to the cohort max, the
# cohort's per-client step streams are bin-packed into L fixed-length lanes,
# so device FLOPs scale with the EXECUTED steps, not C x the straggler max.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackPass:
    """One dispatch of the packed lane program: [L, S_lane] per-step plan.

    ``slot``: global cohort slot executing at this lane step (-1 = lane tail
    padding). ``gidx``: the step's global index e*S+s in the client's
    epochs-x-steps chain — drives both the per-step rng-key gather and the
    loss-buffer scatter, so skipped padding steps cannot shift the client's
    rng stream. ``sidx``: the data-step row s into the round's [C, S, B]
    cohort index map (epochs re-read the same rows, exactly as the padded
    scan does). ``boundary``: 1 on the client's last executed step — the
    round program emits the finished client's model into its update-stack
    slot there and resets the lane carry to the global params."""

    slot: np.ndarray      # [L, S_lane] int32
    gidx: np.ndarray      # [L, S_lane] int32
    sidx: np.ndarray      # [L, S_lane] int32
    boundary: np.ndarray  # [L, S_lane] int32


@dataclasses.dataclass(frozen=True)
class PackPlan:
    """A round's lane packing: one or more fixed-shape :class:`PackPass`
    dispatches (overflow cohorts spill to extra sequential passes, keeping
    every pass the same compiled program). ``total_steps`` counts executed
    (data-carrying, in-budget) steps across the cohort; ``capacity`` is
    ``len(passes) * lanes * s_lane`` — their ratio is the lane occupancy
    (the ``engine/lane_occupancy`` gauge)."""

    passes: tuple
    lanes: int
    s_lane: int
    total_steps: int
    capacity: int

    @property
    def padding_frac(self) -> float:
        return 1.0 - self.total_steps / max(self.capacity, 1)


def executed_steps(
    num_steps: np.ndarray, data_steps: np.ndarray, steps_per_epoch: int,
    epochs: int,
) -> np.ndarray:
    """[C, E] executed (parameter-changing) step counts per client per epoch:
    a padded-scan step is a real step iff its batch row carries data
    (``s < data_steps``) AND it is inside the client's straggler budget
    (``e*S + s < num_steps``). Everything else is a masked no-op the packed
    path exists to skip."""
    S = int(steps_per_epoch)
    num_steps = np.asarray(num_steps, np.int64)
    data_steps = np.asarray(data_steps, np.int64)
    budget = np.clip(
        num_steps[:, None] - np.arange(int(epochs))[None, :] * S, 0, S
    )
    return np.minimum(np.maximum(data_steps, 0)[:, None], budget)


def _assign_lanes(bin_totals: np.ndarray, lanes_per_shard: int, s_lane: int,
                  n_shards: int) -> list:
    """The greedy-LPT lane assignment shared by the main packing and the
    dropped-client re-pack: ``assign[p][lane] = clients`` (placement order)
    for pass p. Clients with a zero total are skipped; a client that fits
    no lane of the current pass spills to a fresh pass."""
    c_local = len(bin_totals) // n_shards
    L = lanes_per_shard * n_shards
    assign: list[list[list[int]]] = []
    for shard in range(n_shards):
        slots = np.arange(shard * c_local, (shard + 1) * c_local)
        order = slots[np.argsort(-bin_totals[slots], kind="stable")]
        pending = [int(s) for s in order if bin_totals[s] > 0]
        p = 0
        while pending:
            while len(assign) <= p:
                assign.append([[] for _ in range(L)])
            loads = np.zeros(lanes_per_shard, np.int64)
            lane_clients: list[list[int]] = [[] for _ in range(lanes_per_shard)]
            nxt: list[int] = []
            for s in pending:
                lane = int(np.argmin(loads))
                # the least-loaded lane not fitting means NO lane fits
                if loads[lane] + bin_totals[s] <= s_lane:
                    loads[lane] += bin_totals[s]
                    lane_clients[lane].append(s)
                else:
                    nxt.append(s)
            for li, clients in enumerate(lane_clients):
                assign[p][shard * lanes_per_shard + li] = clients
            pending = nxt
            p += 1
    return assign


def pack_cohort(
    num_steps: np.ndarray,
    data_steps: np.ndarray,
    steps_per_epoch: int,
    epochs: int,
    lanes_per_shard: int,
    s_lane: int,
    n_shards: int = 1,
    predicted_steps: np.ndarray | None = None,
) -> PackPlan:
    """Greedy-LPT bin packing of the cohort's step streams into lanes.

    Clients are packed PER MESH SHARD (slot block ``[d*c_local, (d+1)*
    c_local)`` goes to lane block ``[d*lanes_per_shard, ...)``), so each
    device's lanes only ever emit into its own update-stack block and the
    packed program combines shards with the exact same ``all_gather`` the
    padded program uses — no cross-device scatter arithmetic to perturb
    bit-identity. The same per-shard blocks serve BOTH lowerings of the
    packed programs: the manual shard_map path indexes its block by
    ``axis_index``, and the pjit global-view path lets GSPMD shard the
    lane dimension on the clients axis — the plan is layout-agnostic
    (docs/PERFORMANCE.md "Packed lanes on sharded plans"). Within a shard: longest-processing-time order, each client
    onto the least-loaded lane that still fits; clients that fit no lane of
    the current pass spill to a fresh pass (same shapes, extra sequential
    dispatch). Pure numpy, O(total executed steps) like the CSR staging
    machinery.

    ``predicted_steps`` (docs/PERFORMANCE.md "Heterogeneous populations"):
    the scheduler's per-client step forecast — lane ORDERING and fit
    decisions bin by the predicted executed totals (the planner cannot know
    who will drop mid-round), while placement emits the ACTUAL streams.
    Clients whose actual stream came up short (mid-round dropout truncated
    their budget: ``num_steps < predicted_steps``) are pulled out of their
    predicted lane and RE-PACKED by their actual totals into dedicated
    overflow passes appended after the main ones — every client's executed
    stream is still placed exactly once (tests/test_population.py holds the
    invariant). ``None`` keeps the original actual-steps binning
    bit-identically."""
    num_steps = np.asarray(num_steps, np.int64)
    C = len(num_steps)
    if C % n_shards:
        raise ValueError(f"cohort size {C} not divisible by {n_shards} shards")
    c_local = C // n_shards
    S = int(steps_per_epoch)
    E = int(epochs)
    per_epoch = executed_steps(num_steps, data_steps, S, E)
    totals = per_epoch.sum(axis=1)
    if predicted_steps is None:
        bin_totals = totals
    else:
        predicted_steps = np.asarray(predicted_steps, np.int64)
        if (predicted_steps < num_steps).any():
            bad = int(np.argmax(predicted_steps < num_steps))
            raise ValueError(
                f"cohort slot {bad}: predicted_steps "
                f"{int(predicted_steps[bad])} < actual num_steps "
                f"{int(num_steps[bad])} — dropout only ever truncates a "
                "budget, a larger actual means the prediction wiring is "
                "wrong"
            )
        bin_totals = executed_steps(
            predicted_steps, data_steps, S, E
        ).sum(axis=1)
    if (bin_totals > s_lane).any():
        bad = int(np.argmax(bin_totals))
        raise ValueError(
            f"cohort slot {bad} needs {int(bin_totals[bad])} steps but lanes "
            f"are {s_lane} long — size s_lane to the population max"
        )
    # mid-round-dropped clients: predicted a longer stream than they
    # executed — binned with everyone (the scheduler's view), then pulled
    # and re-packed by ACTUAL totals into overflow passes below
    dropped_mask = bin_totals > totals
    L = lanes_per_shard * n_shards
    assign = _assign_lanes(bin_totals, lanes_per_shard, s_lane, n_shards)
    if dropped_mask.any():
        # dropped clients leave their predicted lanes (the lane slot was
        # reserved by the forecast) and their ACTUAL truncated streams are
        # re-packed into overflow passes appended after the main ones —
        # same compiled shapes, extra sequential dispatches, every client
        # still placed exactly once
        for p_assign in assign:
            for li, clients in enumerate(p_assign):
                p_assign[li] = [s for s in clients if not dropped_mask[s]]
        assign.extend(_assign_lanes(
            np.where(dropped_mask, totals, 0), lanes_per_shard, s_lane,
            n_shards,
        ))
        # a main pass whose every client dropped would dispatch a no-op
        assign = [a for a in assign if any(lane for lane in a)]
    passes = []
    for p_assign in assign:
        slot = np.full((L, s_lane), -1, np.int32)
        gidx = np.zeros((L, s_lane), np.int32)
        sidx = np.zeros((L, s_lane), np.int32)
        boundary = np.zeros((L, s_lane), np.int32)
        for li, clients in enumerate(p_assign):
            pos = 0
            for s in clients:
                t = int(totals[s])
                counts = per_epoch[s]
                g = np.concatenate(
                    [e * S + np.arange(c) for e, c in enumerate(counts)]
                )
                sx = np.concatenate([np.arange(c) for c in counts])
                slot[li, pos:pos + t] = s
                gidx[li, pos:pos + t] = g
                sidx[li, pos:pos + t] = sx
                boundary[li, pos + t - 1] = 1
                pos += t
        passes.append(PackPass(slot, gidx, sidx, boundary))
    if not passes:  # an all-empty cohort still needs one (no-op) dispatch
        passes.append(PackPass(
            np.full((L, s_lane), -1, np.int32),
            np.zeros((L, s_lane), np.int32),
            np.zeros((L, s_lane), np.int32),
            np.zeros((L, s_lane), np.int32),
        ))
    return PackPlan(
        tuple(passes), L, int(s_lane), int(totals.sum()),
        len(passes) * L * int(s_lane),
    )


def pack_index_map(idx: np.ndarray, pack_pass: PackPass) -> np.ndarray:
    """Gather the round's [C, S, B] cohort index map into the packed
    [L, S_lane, B] lane layout (-1 = empty slot). Lane steps read the exact
    rows the padded scan would have read, so batch content is bit-identical
    by construction."""
    C, S, _ = idx.shape
    safe_slot = np.clip(pack_pass.slot, 0, C - 1)
    safe_s = np.clip(pack_pass.sidx, 0, S - 1)
    out = idx[safe_slot, safe_s]
    return np.where((pack_pass.slot >= 0)[..., None], out, -1).astype(np.int32)


def batch_array(arrays: dict[str, np.ndarray], batch_size: int) -> dict[str, np.ndarray]:
    """Batch a flat dataset into [S, B, ...] with padding mask — used for
    centralized training and global eval."""
    n = len(arrays["y"])
    steps = steps_per_epoch(n, batch_size)
    slots = steps * batch_size
    out = {}
    for name, arr in arrays.items():
        padded = np.zeros((slots,) + arr.shape[1:], dtype=arr.dtype)
        padded[:n] = arr
        out[name] = padded.reshape((steps, batch_size) + arr.shape[1:])
    mask = np.zeros((slots,), dtype=np.float32)
    mask[:n] = 1.0
    mask = mask.reshape(steps, batch_size)
    if "mask" in out:
        tok = out["mask"].astype(np.float32)
        out["mask"] = tok * mask.reshape(mask.shape + (1,) * (tok.ndim - 2))
    else:
        out["mask"] = mask
    return out


def stack_client_eval(
    data: FederatedArrays, client_ids: np.ndarray, batch_size: int, steps: int | None = None
) -> dict[str, np.ndarray]:
    """[C, S, B, ...] eval stack over given clients (no shuffling)."""
    stack, _ = stack_cohort(data, client_ids, batch_size, steps=steps, rng=None)
    return stack
