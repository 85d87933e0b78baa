"""Streaming generator returns.

Ref analogue: ObjectRefGenerator / streaming_generator.py — a task
declared ``num_returns="streaming"`` yields values; each yield reaches
its consumer AS IT IS PRODUCED (index-derived ObjectIDs), so the
consumer iterates results while the producer is still running —
backpressure-free pipelining for long producers.

An item's id is derived, not announced: item i of a task is
``ObjectID.from_index(task_id, STREAM_BASE | (i+1))``, so the consumer
knows it before the item exists. A stream's one return slot, the
completion ref, seals with the item count when the generator is
exhausted (or with the task's error). Each item is pinned once, by its
producer's seal, until the consumer's ref drops: the consumer adopts
the pin (its +1 and the adopting -1 coalesce in its delta table, so the
seal-time pin stands for the user's ref). Those three things, and
``ObjectRefGenerator``'s interface, are all the two routes share.

Which producer takes which route is said by its spec
(``BaseRuntime._submit_actor_task``), by no switch of its own: an ACTOR
call with no retries left to it whose direct channel is ready (and
``direct_actor_calls`` on, the plane's switch) takes the direct route;
a streaming TASK (it has no channel: ``ray_tpu.data``'s), a retriable
stream (``max_retries``), and an actor call whose channel is not ready
yet or died take the node-manager route.

The direct route (a serving replica's tokens). The item travels on the
direct actor-call channel its call went out on, from the worker that
yields it to the process that consumes it; the node manager is off its
path.

- The producer (``worker_main.Worker._run_task``'s ``stream_item`` of a
  task that came on a direct connection) serializes the value and sends
  ONE ``stream_item`` frame on that connection at once: the index, and
  the value as a direct reply carries a result (inline bytes, or the
  location of a store put). No store put for an inline value, no
  ``put`` frame, nothing debounced. The seal the node manager needs
  (for a third party's ``get`` or dependency, and for the pin's book)
  goes into the worker's ``direct_done_batch`` buffer, which leaves
  every 16 entries or 50 ms (or ahead of the worker's next request to
  its node manager): one frame for ~16 tokens. To a caller on
  ANOTHER node a store put is told to the producer's node manager
  first, with a hold for that caller (the ``held`` discipline of a
  direct reply), and an inline value is not told there at all. The
  completion is the call's ordinary direct reply, behind the items on
  the same socket, and its seal is behind theirs in the same buffer.
- The consumer: the channel's reader thread puts each frame's location
  into the stream's ``DirectStream``; ``ObjectRefGenerator._await_item``
  waits THERE (an item, the completion, the channel's death, or
  ``item_timeout_s``) and sends nothing. Taking an item it registers
  the id with its own node manager in its coalesced side bookkeeping
  (``_direct_on_item``: a placeholder, so that a ref handed to a third
  task finds an entry to wait on whichever of the two sockets is
  ahead; for a remote producer also the pin and the seal, as a remote
  direct result's) and keeps the location (``rt._carry_location``), so
  ``get(ref)`` asks nothing.
- The books. The placeholder goes out on the consumer's socket before
  any release of that id, so a release that overtakes the producer's
  batch finds the entry and takes it to -1; the batch's pin brings it
  to 0 and the sweep collects it: no leak, no double free. (An entry
  below zero is owed its pin: the sweep leaves it twenty times
  ``gc_grace_period_s``, the batch being 50 ms behind.) An abandoned
  stream (``__del__``) releases what came and was not taken, and its
  ``DirectStream`` releases what still comes. An item whose frame
  could not be sent is not pinned.
- The channel's death. ``_DirectChannel``'s failure path replays
  unanswered calls over the node-manager route; a stream that has been
  handed a frame is exempt (a replay would run the generator, and its
  side effects, a second time): what came stays readable, then the
  stream ends with ``ActorDiedError`` (and its producer with the send
  that fails). A stream that has been handed nothing is replayed like
  any call, and goes on below: the replay runs it there, or, where the
  generator was already running when the channel died and nothing of
  it had left, that run seals the rest as below and the replay brings
  its completion (``_direct_seen``).

The node-manager route (tasks, retriable streams, no channel).

- The producer publishes nothing but the objects themselves: the worker
  seals item i with one pinned ref (a store put and a ``put`` to its
  node manager), and the completion seals through the task's
  ``task_done``.
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
  Item sealed: adopt it. Completion sealed and item not: end of
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
from collections import deque
from typing import List, Optional

from ..util.metrics import Counter, Histogram, ItemTally
from .ids import ObjectID, TaskID
from .object_store import InlineLocation
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
# manager says of the item's one request (it ``parked``) or, on the
# direct route, that no frame had come when the consumer asked. direct ÷
# items is the direct route's hit share: ~1 for an actor's stream (every
# serve stream), 0 for a task's or a retriable one. Of the node-manager
# route's items the carried share is ~1 for a stream of this node and 0
# for one from another.
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
STREAM_ITEMS_DIRECT = Counter(
    "ray_tpu_stream_items_direct_total",
    "Items that came on the direct actor channel their call went out on "
    "(the node manager off their path).",
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


class DirectStream:
    """The consumer's end of one stream on a direct actor channel: the
    channel's reader thread puts what comes (each ``stream_item`` frame
    as it was decoded: index ``x``, location ``loc``, and from another
    node the refs inside the value ``n``; in the order of the socket,
    then how the call ended) and the stream's one consumer takes it.
    ``remote`` says the producer is on another node (its store puts come
    as held remote locations), ``readable`` that this process can read a
    store put's location as it stands (same node, store attached).
    ``release`` is what the reader does with
    a frame that comes after the consumer is gone (drop its pin)."""

    DONE, DIED, REROUTED = "done", "died", "rerouted"

    __slots__ = ("remote", "readable", "release", "received", "ended",
                 "_cv", "_items", "_abandoned")

    def __init__(self, remote: bool, readable: bool, release):
        self.remote = remote
        self.readable = readable
        self.release = release
        # Frames that came: a stream that was handed one is never
        # replayed (module docstring).
        self.received = 0
        # How the call ended, once it has: DONE (its reply came: the
        # completion ref says count or error), DIED (the channel died
        # after a frame) or REROUTED (it died before any: the call was
        # replayed over the node-manager route).
        self.ended: Optional[str] = None
        self._cv = threading.Condition(threading.Lock())
        self._items: deque = deque()
        self._abandoned = False

    def put(self, frame: dict) -> None:
        """Reader thread: an item's frame came."""
        with self._cv:
            self.received = frame["x"] + 1
            if not self._abandoned:
                self._items.append(frame)
                self._cv.notify()
                return
        self.release(frame)

    def end(self, how: str) -> None:
        """Reader thread: no frame of this stream comes after this."""
        with self._cv:
            self.ended = how
            self._cv.notify()

    def take(self, timeout: Optional[float]):
        """``(frame, waited)`` of the next item, or ``(None, waited)``
        when the call ended and every item that came was taken
        (``ended`` says how), or when nothing came within ``timeout``
        (``ended`` is still None)."""
        with self._cv:
            waited = not self._items and self.ended is None
            if waited:
                self._cv.wait_for(
                    lambda: self._items or self.ended is not None, timeout)
            if self._items:
                return self._items.popleft(), waited
            return None, waited

    def abandon(self) -> list:
        """The consumer is gone: the frames that came and were not
        taken; what comes from now on goes to ``release``."""
        with self._cv:
            self._abandoned = True
            left = list(self._items)
            self._items.clear()
            return left


class ObjectRefGenerator:
    """Iterator over a streaming task's yielded ObjectRefs (ref:
    ObjectRefGenerator). ``next()`` blocks until the producer's next
    item is there (or the task completes) and returns the item's
    ObjectRef; nothing on the way sleeps or polls. It waits on
    ``stream``, the call's ``DirectStream``, where the call went out on
    a direct actor channel, and on the node manager otherwise (module
    docstring: the two routes). Iteration ends when the producer's
    generator is exhausted. The completion ref resolves to the item
    count (and surfaces the task's exception, if any). ``retriable``
    says the producing task may be re-run after a crash: only then is
    the retry record kept."""

    def __init__(self, task_id: TaskID, completion_ref: ObjectRef,
                 retriable: bool = False,
                 stream: Optional[DirectStream] = None):
        self._task_id = task_id
        self._completion_ref = completion_ref
        self._retriable = retriable
        self._stream = stream
        self._next = 0
        self._count: Optional[int] = None
        self._released = False
        # The channel's death, once it has ended this stream: every
        # later ``next()`` raises it again (the completion ref of a call
        # that died with its channel never seals).
        self._died: Optional[BaseException] = None
        # Optional per-item production deadline (serve SSE guard).
        self.item_timeout_s = None
        # Items handed over and, of them, those that came on the direct
        # channel (or, on the node-manager route, those whose location
        # was carried), and the blocked waits, since they were last
        # recorded (``_record_delivery``).
        self._tally = ItemTally(
            STREAM_ITEMS,
            STREAM_ITEMS_CARRIED if stream is None else STREAM_ITEMS_DIRECT)
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
        if self._died is not None:
            raise self._died
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

        if self._stream is not None:
            got = self._await_direct(rt, self._stream, item)
            if got is not None:
                return got
        clock = time.perf_counter
        completion = self._completion_ref.id()
        ids = [item, completion]
        while True:
            t0 = clock()
            ready, locations, parked = rt._wait_carrying(
                ids, self.item_timeout_s)
            if not ready:
                raise self._timed_out()
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

    def _timed_out(self):
        # A wedged producer must not hold consumers (serve proxy
        # threads) forever — surface a timeout instead.
        from .exceptions import GetTimeoutError

        return GetTimeoutError(
            f"stream item {self._next} not produced within "
            f"{self.item_timeout_s}s"
        )

    def _await_direct(self, rt, stream: DirectStream, item: ObjectID):
        """The direct route's ``_await_item``: wait on the channel's
        ``DirectStream`` and send nothing. None when the stream goes on
        over the node-manager route (its call was replayed there)."""
        clock = time.perf_counter
        t0 = clock()
        frame, waited = stream.take(self.item_timeout_s)
        if frame is not None:
            if waited:
                self._waits.append(clock() - t0)
            loc = frame["loc"]
            rt._direct_on_item(item, frame, stream.remote)
            if self._tally.item(1):
                _record_delivery(self._tally, self._waits)
            # Only what this process can read as it stands is kept for
            # the ref: a store put of another node (or one a thin client
            # cannot map) is read through the node manager, which pulls.
            carried = stream.readable or isinstance(loc, InlineLocation)
            return True, loc if carried else None
        ended = stream.ended
        if ended is None:
            raise self._timed_out()
        if ended != DirectStream.REROUTED:
            import ray_tpu

            try:
                self._count = ray_tpu.get(self._completion_ref)
            except BaseException as e:
                if ended == DirectStream.DIED:
                    self._died = e
                raise
            if self._next >= self._count:
                return False, None
        # Replayed over the node-manager route before any frame came
        # (or, what one ordered socket cannot do, fewer frames than the
        # count): the items are sealed at the node manager under the
        # same ids, and the rest of the stream is that route's.
        _record_delivery(self._tally, self._waits)
        self._tally = ItemTally(STREAM_ITEMS, STREAM_ITEMS_CARRIED)
        self._stream = None
        return None

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
                      self._tally, self._waits, self._stream),
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


def release_direct_item(rt, frame: dict, remote: bool) -> None:
    """Drop the pin of a direct-route item (its ``stream_item`` frame)
    that nobody took: registered as a taken one is, then released."""
    oid = stream_item_id(TaskID(frame["i"]), frame["x"])
    rt._direct_on_item(oid, frame, remote)
    rt.refs.decr(oid)


def _release_abandoned_stream(rt, task_id, next_idx: int, retriable: bool,
                              tally: ItemTally, waits: List[float],
                              stream: Optional[DirectStream] = None) -> None:
    """Off-thread body of ObjectRefGenerator.__del__ (see there): what
    the consumer counted and had not recorded, then, of the direct
    route, the items that came and were not taken (what comes later the
    stream releases itself); of the node-manager route the sealed items
    from ``next_idx`` on, asked for a window at a time."""
    try:
        _record_delivery(tally, waits)
        if stream is not None:
            for frame in stream.abandon():
                release_direct_item(rt, frame, stream.remote)
            if stream.ended != DirectStream.REROUTED:
                return
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
