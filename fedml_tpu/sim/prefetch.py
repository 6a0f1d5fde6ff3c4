"""Pipelined round-driver plumbing: double-buffered host staging and a
deferred metrics drain.

The engine compiles the device side of a round into one XLA program, but a
serial driver still interleaves three host phases per round — build the
cohort's index map, ``device_put`` it, then block on the round's metrics —
so host staging and device compute never overlap (the classic input-pipeline
bottleneck tf.data/Grain-style prefetch solves for centralized training).
This module overlaps them:

- :class:`Prefetcher` runs the staging function for upcoming rounds on a
  background thread, keeping up to ``depth`` rounds staged (index maps
  built and ``device_put`` issued) ahead of the dispatch loop. Staging is a
  pure function of ``(config, round_idx, root_rng)`` — cohort sampling and
  shuffling are seeded per round — so prefetch order cannot change cohorts,
  rng keys, or metrics: the pipelined driver is bit-identical to the serial
  one. The staged payload is opaque to this module: padded rounds ship
  (data, weights, budgets, key) tuples, packed-lane rounds
  (SimConfig.pack_lanes) ship an ``engine.PackedStaged`` whose lane plan —
  bin-packing included — was likewise built on this thread.
- :class:`MetricsDrain` keeps each round's metrics as device arrays in a
  bounded queue and fetches them a round behind, so the driver only
  synchronizes with the device at eval boundaries and at the end of the run.

Knob: ``SimConfig.pipeline_depth`` (0 = serial, None = auto depth 1).
See docs/PERFORMANCE.md for when the pipeline wins.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable

import jax

from fedml_tpu.obs import trace

THREAD_NAME = "fedsim-prefetch"

_SENTINEL = object()


class Prefetcher:
    """Stage an ordered task list on a background thread.

    ``stage_fn(task)`` is called for each task in order; at most ``depth``
    staged payloads are buffered ahead of the consumer. :meth:`get` returns
    payloads strictly in task order and re-raises any staging exception at
    the consumer's next request. :meth:`close` always stops and joins the
    worker (idempotent) — call it from a ``finally`` so an exception mid-run
    cannot leak the thread or leave a producer blocked on a full queue.
    """

    def __init__(self, tasks: Iterable[Any], stage_fn: Callable[[Any], Any],
                 depth: int = 1):
        self._tasks = list(tasks)
        self._stage = stage_fn
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._exc: BaseException | None = None
        self._thread = threading.Thread(
            target=self._work, name=THREAD_NAME, daemon=True
        )
        self._thread.start()

    def _work(self) -> None:
        try:
            for task in self._tasks:
                if self._stop.is_set():
                    return
                with trace.span("prefetch/stage", task=str(task)):
                    payload = self._stage(task)
                if not self._offer((task, payload)):
                    return
        except BaseException as e:  # noqa: BLE001 — must reach the consumer
            self._exc = e
            self._offer((_SENTINEL, None))

    def _offer(self, item) -> bool:
        """Bounded put that never wedges: gives up when close() fires."""
        try:
            # fast path: room in the queue, the producer is ahead of the
            # consumer (the healthy pipelined state)
            self._q.put_nowait(item)
        except queue.Full:
            # the producer is blocked on a full queue — the device side is
            # the bottleneck. A span per blocked wait makes that visible.
            with trace.span("prefetch/producer_blocked"):
                while True:
                    if self._stop.is_set():
                        return False
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        trace.gauge("prefetch/queue_depth", self._q.qsize())
        return True

    def get(self, task: Any) -> Any:
        """Return the staged payload for ``task`` — which must be the next
        task in submission order (the driver consumes the same plan it
        handed the prefetcher)."""
        try:
            # fast path: the payload is already staged (pipeline keeping up)
            staged_task, payload = self._q.get_nowait()
        except queue.Empty:
            # the consumer is stalled waiting on staging — host staging is
            # the bottleneck for this round
            with trace.span("prefetch/consumer_stall", task=str(task)):
                staged_task, payload = self._wait_for_item(task)
        trace.gauge("prefetch/queue_depth", self._q.qsize())
        if staged_task is _SENTINEL:
            raise self._exc
        if staged_task != task:
            raise RuntimeError(
                f"prefetch order violated: staged {staged_task!r}, "
                f"requested {task!r}"
            )
        return payload

    def _wait_for_item(self, task: Any) -> tuple:
        """Blocking wait for the next staged item, robust to a worker that
        died (re-raises its exception) or exited short."""
        while True:
            try:
                return self._q.get(timeout=0.2)
            except queue.Empty:
                if not self._thread.is_alive():
                    # the worker may have enqueued its final payload and
                    # exited between our timeout and this check — drain
                    # before concluding it died short
                    try:
                        return self._q.get_nowait()
                    except queue.Empty:
                        if self._exc is not None:
                            raise self._exc
                        raise RuntimeError(
                            f"prefetch worker exited before staging {task!r}"
                        ) from None

    def close(self) -> None:
        """Stop the worker and join it. Safe to call repeatedly, safe to
        call with staged-but-unconsumed rounds in the queue (they are
        dropped — staging is pure, nothing to roll back)."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():
            # stage_fn is wedged (e.g. a blocked device_put). The thread
            # is daemonic so it cannot block exit, but say so instead of
            # silently breaking the join guarantee.
            import logging

            logging.warning(
                "prefetch worker still alive 10s after close() — staging "
                "call is blocked; continuing without it"
            )

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class MetricsDrain:
    """A bounded queue of not-yet-fetched round metrics (device arrays).

    :meth:`push` enqueues a dispatched round's (or block's) metrics and
    returns whatever fell off the back — fetched to host numpy; :meth:`flush`
    fetches everything still queued. Keeping up to ``depth`` entries on
    device means the driver never blocks on the round it just dispatched:
    metric fetches land a round behind and are forced only at eval
    boundaries and at the end of the run. ``depth=0`` degrades to the serial
    fetch-every-round behavior.
    """

    def __init__(self, depth: int = 1):
        self.depth = max(0, int(depth))
        self._q: list[tuple[Any, Any, float]] = []

    def push(self, tag: Any, metrics: Any) -> list[tuple[Any, Any]]:
        self._q.append((tag, metrics, time.perf_counter()))
        out = []
        while len(self._q) > self.depth:
            out.append(self._fetch(self._q.pop(0)))
        return out

    def flush(self) -> list[tuple[Any, Any]]:
        out = [self._fetch(item) for item in self._q]
        self._q.clear()
        return out

    @staticmethod
    def _fetch(item: tuple[Any, Any, float]) -> tuple[Any, Any]:
        tag, metrics, pushed = item
        # behind_s = how long these metrics sat on device before the driver
        # fetched them — the pipeline's fetch-behind latency per round
        with trace.span("prefetch/drain_fetch", tag=str(tag),
                        behind_s=round(time.perf_counter() - pushed, 6)):
            return tag, jax.device_get(metrics)
