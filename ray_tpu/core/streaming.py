"""Streaming generator returns.

Ref analogue: ObjectRefGenerator / streaming_generator.py — a task
declared ``num_returns="streaming"`` yields values; each yield is sealed
into the object store AS IT IS PRODUCED (index-derived ObjectIDs), so the
consumer iterates results while the producer is still running —
backpressure-free pipelining for long producers.

Protocol. An item's id is derived, not announced: item i of a task is
``ObjectID.from_index(task_id, STREAM_BASE | (i+1))``, so the consumer
knows it before the item exists.

- The producer publishes nothing but the objects themselves: the worker
  seals item i with one pinned ref (a ``put`` to its node manager), and
  when the generator is exhausted the task's one return slot, the
  completion ref, seals with the item count (or with the task's error).
- The consumer makes one request an item: a ``wait([item_i,
  completion])`` on its node manager that parks until the producer's
  seal wakes it (``_parked_waits``; an item sealed on another node wakes
  it through the GCS object directory's long-poll). No look comes before
  it and no timer is on the way: a token reaches ``next()`` when it is
  sealed. The reply carries what ``get`` would have asked for, the
  location of each ready id that a process of this node can read as it
  stands (inline bytes, which so ride in the reply, or this node's
  store); the consumer keeps it for the ref it hands out, and
  ``get(ref)`` asks nothing. An item of a producer on another node, or a
  spilled one, comes without, and its ``get`` asks ``get_locations``,
  which pulls or restores. The reply also says whether the wait
  ``parked``: then, and only then, the node manager kept the blocked
  book of a consumer task round it (the CPU it holds is free for its
  producer meanwhile); the consumer sends no frame for that.
  Item sealed: adopt it (the consumer's +1 cancels the producer's pin
  via coalesced delta flushing). Completion sealed and item not: end of
  stream, or the task's error. Items of one node are sealed before their
  completion, in order; an item still on its way from another node when
  the count arrives is waited for alone, and one still on its way when
  an ERROR arrives is cut off by that error.
- The retry record ``__stream__/<task>`` holds the consumer's position
  and exists only for a producer that can be retried (``max_retries``):
  a retried attempt re-runs the generator from the start and must not
  re-seal, and so re-pin, what the consumer already took and dropped.
  The consumer writes its position as it adopts an item; only a retried
  attempt reads it; it is deleted with the stream.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from ..util.metrics import Counter, Histogram, ItemTally
from .ids import ObjectID, TaskID
from .reference import ObjectRef

# High bit block distinct from return slots (small ints) and put-ids
# (0x8000_0000 block).
STREAM_BASE = 0x4000_0000

# Delivery accounting at both ends of a stream, in the process of each.
# No item's path calls the metrics registry: a stream adds up its own
# counts and seconds and records them every ``ItemTally.FLUSH_ITEMS``
# items and when it ends (or is abandoned).
#
# Producer side: what a seal costs the producing thread (serialize,
# store put, the ``put`` frame to the node manager).
STREAM_ITEMS_SEALED = Counter(
    "ray_tpu_stream_items_sealed_total",
    "Streaming-generator items sealed by their producer.",
)
STREAM_ITEM_SEAL_S = Counter(
    "ray_tpu_stream_item_seal_seconds_total",
    "Time producers spent sealing items (serialize, store put, send).",
)
# Consumer side. A blocked share near 100% with waits near the producer's
# cadence is a consumer that keeps up; a low blocked share is a consumer
# that lags (items were waiting for it). Blocked is what the node
# manager says of the item's one request: it ``parked``. The carried
# share is ~1 for a stream of this node and 0 for one from another.
STREAM_ITEMS = Counter(
    "ray_tpu_stream_items_total",
    "Streaming-generator items handed to a consumer.",
)
STREAM_ITEMS_BLOCKED = Counter(
    "ray_tpu_stream_item_blocked_total",
    "Items the consumer had to block for (not yet sealed when it asked).",
)
STREAM_ITEMS_CARRIED = Counter(
    "ray_tpu_stream_items_carried_total",
    "Items whose location came with the reply of the wait for their seal "
    "(their get makes no request).",
)
STREAM_ITEM_WAIT = Histogram(
    "ray_tpu_stream_item_wait_seconds",
    "Time a consumer spent blocked until the item it asked for sealed.",
    boundaries=[0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0],
)

# How many item ids an abandoned stream's release asks about at once.
_RELEASE_WINDOW = 64


def stream_item_id(task_id: TaskID, index: int) -> ObjectID:
    return ObjectID.from_index(task_id, STREAM_BASE | (index + 1))


def stream_key(task_id: TaskID) -> str:
    """KV key of a retriable stream's retry record (consumer position)."""
    return f"__stream__/{task_id.hex()}"


def consumed_upto(rt, task_id: TaskID) -> int:
    """Producer side, retried attempts only: how many leading items the
    consumer has already taken (0 when it took none)."""
    blob = rt.kv_get(stream_key(task_id))
    return int(blob) if blob else 0


class ObjectRefGenerator:
    """Iterator over a streaming task's yielded ObjectRefs (ref:
    ObjectRefGenerator). ``next()`` blocks on the node manager until the
    producer SEALS the next item (or the task completes) and returns the
    item's ObjectRef; nothing on the way sleeps or polls. Iteration ends
    when the producer's generator is exhausted. The completion ref
    resolves to the item count (and surfaces the task's exception, if
    any). ``retriable`` says the producing task may be re-run after a
    crash: only then is the retry record (module docstring) kept."""

    def __init__(self, task_id: TaskID, completion_ref: ObjectRef,
                 retriable: bool = False):
        self._task_id = task_id
        self._completion_ref = completion_ref
        self._retriable = retriable
        self._next = 0
        self._count: Optional[int] = None
        self._released = False
        # Optional per-item production deadline (serve SSE guard).
        self.item_timeout_s = None
        # Items handed over and how many of them were carried, and the
        # blocked waits, since they were last recorded
        # (``_record_delivery``).
        self._tally = ItemTally(STREAM_ITEMS, STREAM_ITEMS_CARRIED)
        self._waits: List[float] = []

    @property
    def completed(self) -> ObjectRef:
        """The task's completion ref (item count / error carrier)."""
        return self._completion_ref

    def __iter__(self) -> "ObjectRefGenerator":
        return self

    def __next__(self) -> ObjectRef:
        from .runtime_context import current_runtime

        rt = current_runtime()
        if self._count is not None and self._next >= self._count:
            _record_delivery(self._tally, self._waits)
            raise StopIteration
        item = stream_item_id(self._task_id, self._next)
        try:
            sealed, loc = self._await_item(rt, item)
        except BaseException:
            _record_delivery(self._tally, self._waits)
            raise
        if not sealed:
            _record_delivery(self._tally, self._waits)
            if self._retriable:
                _drop_retry_record(rt, self._task_id)
            raise StopIteration
        self._next += 1
        ref = ObjectRef(item, _register=True)
        # Cancel the producer-side pin: the +1 just registered and this -1
        # coalesce locally, leaving the seal-time pin as the user ref's
        # count until the ref is dropped.
        rt.refs.decr(item)
        if loc is not None:
            # After the decr, which drops what the process knew of the id.
            rt._carry_location(item, loc)
        if self._retriable:
            _write_retry_record(rt, self._task_id, self._next)
        return ref

    def _await_item(self, rt, item: ObjectID):
        """Block until ``item`` is sealed or the stream ended before it:
        ``(sealed, the item's location if it came with the reply)``;
        raises the task's error, or GetTimeoutError after
        ``item_timeout_s`` without either."""
        import ray_tpu

        clock = time.perf_counter
        completion = self._completion_ref.id()
        ids = [item, completion]
        while True:
            t0 = clock()
            ready, locations, parked = rt._wait_carrying(
                ids, self.item_timeout_s)
            if not ready:
                # A wedged producer must not hold consumers (serve
                # proxy threads) forever — surface a timeout instead.
                from .exceptions import GetTimeoutError

                raise GetTimeoutError(
                    f"stream item {self._next} not produced within "
                    f"{self.item_timeout_s}s"
                )
            if item in ready:
                if parked:
                    self._waits.append(clock() - t0)
                loc = locations.get(item)
                if self._tally.item(loc is not None):
                    _record_delivery(self._tally, self._waits)
                return True, loc
            # The task is over and this item is not sealed here: finished
            # (the count says whether the item exists) or failed (get
            # raises the task's error).
            if completion in locations:
                rt._carry_location(completion, locations[completion])
            self._count = ray_tpu.get(self._completion_ref)
            if self._next >= self._count:
                return False, None
            # It exists, and is only still on its way from the producer's
            # node: wait for it alone.
            ids = [item]

    def __del__(self):
        """Abandoned mid-stream: release the producer pins of every
        unconsumed item sealed so far and drop the retry record, so a
        consumer that stops early doesn't leak object-store memory.

        The cleanup does BLOCKING control-plane calls, and __del__ can
        fire on ANY thread the garbage collector happens to run on —
        including the node-manager event loop itself (observed: gc
        during frame pickling on the NM loop → a call_sync onto the same
        loop → the whole runtime deadlocks). So the work is handed to a
        short-lived daemon thread, never run inline."""
        try:
            from .runtime_context import current_runtime_or_none

            rt = current_runtime_or_none()
            if rt is None:
                return
            if self._released or (
                    self._count is not None and self._next >= self._count):
                return  # released already, or consumed to its end
            self._released = True
            threading.Thread(
                target=_release_abandoned_stream,
                args=(rt, self._task_id, self._next, self._retriable,
                      self._tally, self._waits),
                name="stream-gc",
                daemon=True,
            ).start()
        except Exception:
            pass  # interpreter shutting down / runtime gone

    def __repr__(self):
        return (f"ObjectRefGenerator(task={self._task_id.hex()[:8]}, "
                f"next={self._next})")


def _record_delivery(tally: ItemTally, waits: List[float]) -> None:
    """A consumer's counts since they were last recorded, to the metrics
    registry: items and how many were carried, and the blocked waits."""
    tally.flush()
    if waits:
        STREAM_ITEMS_BLOCKED.inc(len(waits))
        STREAM_ITEM_WAIT.observe_many(waits)
        waits.clear()


def _write_retry_record(rt, task_id: TaskID, position: int) -> None:
    # The record guards a retry against a leak; a stream must not fail
    # over it.
    try:
        rt.kv_put(stream_key(task_id), str(position).encode())
    except Exception:  # rtlint: disable=swallowed-failure
        pass


def _drop_retry_record(rt, task_id: TaskID) -> None:
    try:
        rt.kv_del(stream_key(task_id))
    except Exception:  # rtlint: disable=swallowed-failure
        pass


def _release_abandoned_stream(rt, task_id, next_idx: int, retriable: bool,
                              tally: ItemTally, waits: List[float]) -> None:
    """Off-thread body of ObjectRefGenerator.__del__ (see there): what
    the consumer counted and had not recorded, then the sealed items
    from ``next_idx`` on, asked for a window at a time."""
    try:
        _record_delivery(tally, waits)
        while True:
            window = [stream_item_id(task_id, next_idx + k)
                      for k in range(_RELEASE_WINDOW)]
            sealed = set(rt._wait(window, len(window), 0))
            for oid in window:
                if oid in sealed:
                    rt.refs.decr(oid)
            if len(sealed) < len(window):
                break
            next_idx += len(window)
        if retriable:
            _drop_retry_record(rt, task_id)
    except Exception:
        pass
