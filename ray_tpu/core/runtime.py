"""Driver and worker runtimes: the per-process engine behind the public API.

Ref analogue: the CoreWorker (src/ray/core_worker/core_worker.h — SubmitTask/
Put/Get/Wait + ReferenceCounter) plus the Python Worker
(python/ray/_private/worker.py). The driver's runtime calls the in-process
NodeManager directly; worker runtimes speak the framed socket protocol. Both
expose the same interface so ``ray_tpu.get`` etc. work anywhere.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..util import faults
from ..util.backoff import Backoff
from .config import get_config
from .exceptions import GetTimeoutError, ObjectLostError, TaskError
from .function_table import FunctionCache, export_function
from .ids import ActorID, JobID, NodeID, ObjectID, TaskID, WorkerID
from .object_store import InlineLocation, LocalObjectStore, Location, ShmLocation
from .protocol import (DIRECT_BACKPRESSURE_WAIT_S, DIRECT_MAX_UNANSWERED,
                       DIRECT_PROTO_VER, dumps_msg)
from . import frame_pump
from .reference import ObjectRef, ref_without_registration
from .serialization import serialize, serialize_with_refs
from .streaming import DirectStream, release_direct_item
from .task_spec import RefArg, TaskSpec, TaskType, ValueArg


# Read once at import: whether top-level submits record root spans.
import os as _os

_TRACE_SUBMITS = _os.environ.get("RAY_TPU_TRACE_SUBMITS") == "1"


# ---- direct actor-call metrics (ISSUE 5 surface) --------------------------
# Declared at import so tools/check_metric_names.py sees them; handles are
# pre-bound once so the per-call hot path never rebuilds tag dicts (same
# discipline as the transfer plane's with_tags handles).
from ..util.metrics import Counter as _MetricCounter
from ..util.metrics import Gauge as _MetricGauge
from ..util.metrics import Histogram as _MetricHistogram

_ACTOR_CALL_SECONDS = _MetricHistogram(
    "ray_tpu_actor_call_seconds",
    "Actor method-call round-trip latency from submit to completion "
    "reply over the direct actor-call plane, seconds",
    boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.05, 0.25, 1.0],
    tag_keys=("mode",),
)
_ACTOR_CALL_INFLIGHT = _MetricGauge(
    "ray_tpu_actor_call_inflight",
    "Direct actor calls currently awaiting their completion reply",
    tag_keys=("pid",),
)
_ACTOR_CALL_FALLBACKS = _MetricCounter(
    "ray_tpu_actor_call_fallbacks_total",
    "Direct-eligible actor calls routed through the node-manager path "
    "instead (reason=channel_error|unsupported|version_mismatch)",
    tag_keys=("reason",),
)
_CALL_SECONDS_DIRECT = _ACTOR_CALL_SECONDS.with_tags(mode="direct")
_CALL_INFLIGHT = _ACTOR_CALL_INFLIGHT.with_tags(pid=str(_os.getpid()))
_FALLBACK_CHANNEL = _ACTOR_CALL_FALLBACKS.with_tags(reason="channel_error")
_FALLBACK_UNSUPPORTED = _ACTOR_CALL_FALLBACKS.with_tags(reason="unsupported")
_FALLBACK_VERSION = _ACTOR_CALL_FALLBACKS.with_tags(
    reason="version_mismatch"
)


def _log_post_error(fut):
    try:
        fut.result()
    except Exception as e:  # pragma: no cover - diagnostics only
        import sys

        sys.stderr.write(f"[ray_tpu] async control call failed: {e!r}\n")


class RefCountTable:
    """Per-process local refcounts with batched delta flushing to the owner
    directory (ref analogue: local refs in reference_count.h, flushed like
    the batched release RPCs)."""

    def __init__(self, flush_fn, on_zero=None):
        self._local: Dict[ObjectID, int] = {}
        self._deltas: Dict[ObjectID, int] = {}
        # Ids whose pending delta is above zero (``flush_adds``).
        self._adds = 0
        # Re-entrant: the collector can run between two bytecodes of a
        # locked region, and an ObjectRef's __del__ then calls ``decr``
        # on the thread that holds the lock (seen: a driver's ref
        # flusher stuck on itself, every other thread behind it).
        self._lock = threading.RLock()
        self._flush_fn = flush_fn
        # Called (outside the lock) when this process's last local ref
        # to an object drops — the runtime invalidates its location
        # cache so a later stale read misses and resolves (and errors)
        # through the control plane instead of serving freed data.
        self._on_zero = on_zero

    def incr(self, oid: ObjectID):
        with self._lock:
            self._local[oid] = self._local.get(oid, 0) + 1
            delta = self._deltas.get(oid, 0) + 1
            self._deltas[oid] = delta
            if delta == 1:
                self._adds += 1

    def decr(self, oid: ObjectID):
        zero = False
        with self._lock:
            self._local[oid] = self._local.get(oid, 0) - 1
            if self._local[oid] <= 0:
                del self._local[oid]
                zero = True
            delta = self._deltas.get(oid, 0) - 1
            self._deltas[oid] = delta
            if delta == 0:
                self._adds -= 1
        if zero and self._on_zero is not None:
            self._on_zero(oid)

    def flush(self):
        deltas = self.drain()
        if deltas:
            self._flush_fn(deltas)

    def flush_adds(self):
        """Flush if some id's pending delta is an ADD: what a read has
        to have landed first (the borrow protocol). Releases alone wait
        for the flusher's next round, so a consumer that reads a
        carried item and drops the one before sends no frame a read."""
        with self._lock:
            adds = self._adds > 0
        if adds:
            self.flush()

    def drain(self) -> Dict[ObjectID, int]:
        """Take the pending deltas WITHOUT flushing them — they ride an
        outbound completion frame instead, so the control plane applies
        them before dropping the completing task's pins."""
        # Swapped, not read, under the lock: a ``decr`` that re-enters
        # (see ``__init__``) must not change a dict being iterated.
        fresh: Dict[ObjectID, int] = {}
        with self._lock:
            taken, self._deltas = self._deltas, fresh
            self._adds = 0
        return {k: v for k, v in taken.items() if v != 0}


class BaseRuntime:
    """Shared logic: argument preparation, object read path, ref
    accounting, and the direct actor-call plane (driver, worker and
    thin-client runtimes all route eligible actor calls straight to the
    actor's worker; the node manager only does creation/restart/failure
    — ref analogue: direct_actor_task_submitter.h)."""

    # Subclasses that speak the direct actor-call plane flip this on.
    _direct_capable = False
    # Whether this process can read same-node shared-memory result
    # locations (the thin client cannot — it pulls over the wire).
    _direct_store_readable = True

    def __init__(self, job_id: JobID, node_id: NodeID, worker_id: WorkerID):
        self.job_id = job_id
        self.node_id = node_id
        self.worker_id = worker_id
        self.store = LocalObjectStore()
        self.function_cache = FunctionCache()
        self._loc_cache: Dict[ObjectID, Location] = {}
        self.refs = RefCountTable(
            self._flush_deltas,
            on_zero=lambda oid: self._loc_cache.pop(oid, None),
        )
        self._put_counter = itertools.count(1)
        self.current_task_id: Optional[TaskID] = None
        # KV key of this job's published runtime env ("" = none); stamped
        # onto every TaskSpec submitted from this process.
        self.runtime_env_key: str = ""
        self.current_actor_id: Optional[ActorID] = None
        self._registered_functions: set = set()
        self._function_ids: Dict[int, str] = {}
        # ---- direct actor-call plane state (before the flusher starts:
        # _flush_loop touches these) -----------------------------------
        # actor_id bytes -> {"lock", "status": none|discovering|ready|
        # unsupported, "chan", "nm_seq"} — the ordering-preserving
        # switchover state machine (see _submit_actor_task).
        self._direct_states: Dict[bytes, Dict[str, Any]] = {}
        self._direct_states_lock = threading.Lock()
        # oid bytes -> _DirectResult, in the native WaiterTable when the
        # extension is loaded (every op is one GIL-atomic C call — no
        # Python lock round per submit/get/wait) or its PyWaiterTable
        # mirror. Resolved entries are evicted FIFO beyond the cap (the
        # object stays resolvable through the directory).
        self._direct_waiters = frame_pump.new_waiter_table(
            self._DIRECT_WAITER_CAP
        )
        self._dirty_chans: set = set()
        self._dirty_chans_lock = threading.Lock()
        # task id -> DirectStream of a streaming call between its submit
        # and the making of its generator (``take_direct_stream``).
        self._direct_streams: Dict[TaskID, Any] = {}
        # Local mirror of the fallback counter for cheap introspection
        # (rtpu metrics --actors / run_actor_bench).
        self._direct_fallbacks = 0
        self._flusher_stop = threading.Event()
        self._flusher = threading.Thread(
            target=self._flush_loop, name="ray_tpu-ref-flusher", daemon=True
        )
        self._flusher.start()

    # ---- subclass interface ------------------------------------------------

    def _flush_deltas(self, deltas: Dict[ObjectID, int]):
        raise NotImplementedError

    def _submit_spec(self, spec: TaskSpec):
        raise NotImplementedError

    def _get_locations(
        self, ids: List[ObjectID], timeout: Optional[float]
    ) -> List[Tuple[ObjectID, Location]]:
        raise NotImplementedError

    def _wait(
        self, ids: List[ObjectID], num_returns: int, timeout: Optional[float]
    ) -> List[ObjectID]:
        raise NotImplementedError

    def _wait_carrying(
        self, ids: List[ObjectID], timeout: Optional[float]
    ) -> Tuple[List[ObjectID], Dict[ObjectID, Location], bool]:
        """A stream consumer's one request an item
        (``NodeManager.wait_carrying``): ``(ready, locations, parked)``
        once one of ``ids`` is sealed, ``ready`` empty after
        ``timeout``."""
        raise NotImplementedError

    def _register_put(self, oid: ObjectID, loc: Location,
                      nested: Optional[List[ObjectID]] = None):
        raise NotImplementedError

    def _register_function_remote(self, function_id: str, blob: bytes):
        raise NotImplementedError

    # ---- ref plumbing ------------------------------------------------------

    def register_new_ref(self, oid: ObjectID):
        self.refs.incr(oid)

    def add_local_ref(self, oid: ObjectID):
        self.refs.incr(oid)

    def release_local_ref(self, oid: ObjectID):
        self.refs.decr(oid)

    def _flush_loop(self):
        # Also the deferral bound for buffered direct-call frames and NM
        # side-bookkeeping: a fire-and-forget caller that never gets
        # still has its frames shipped within one flush interval.
        cfg = get_config()
        while not self._flusher_stop.wait(cfg.refcount_flush_interval_s):
            try:
                self.refs.flush()
                self._direct_flush_side(force=True)
                self._flush_direct()
                if self._direct_states:
                    _CALL_INFLIGHT.set(self._direct_inflight())
                    self._direct_prune_states()
            except Exception:
                pass

    # ---- put / get / wait --------------------------------------------------

    def _next_put_id(self) -> ObjectID:
        base = self.current_task_id or TaskID.for_driver(self.job_id)
        # High bit marks puts so they never collide with return slots.
        return ObjectID.from_index(base, 0x8000_0000 | next(self._put_counter))

    def put(self, value) -> ObjectRef:
        oid = self._next_put_id()
        # Refs serialized inside the value are reported with the put so
        # the control plane pins them for the containing object's
        # lifetime (ref analogue: AddNestedObjectIds on Put).
        sobj, nested = serialize_with_refs(value)
        if sobj.total_size <= get_config().max_inline_object_size:
            loc: Location = InlineLocation(sobj.to_bytes())
        else:
            loc = self._put_serialized(oid, sobj)
        self._register_put(oid, loc, nested)
        return ObjectRef(oid, _register=True)

    def _put_serialized(self, oid: ObjectID, sobj) -> Location:
        """Large-object write path; the thin client overrides this to
        ship bytes to the head (its local shm is invisible there)."""
        return self.store.put_serialized(oid, sobj)

    def get(self, refs, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list = [refs] if single else list(refs)
        ids = [r.id() for r in ref_list]
        # Direct-call results resolve from the inline reply (the channel
        # reader registers them with the NM asynchronously) — the control
        # plane is off the sync round-trip entirely. Entries flagged for
        # redirect (replayed over the NM path after a channel death, or
        # bytes not readable from this process) fall through to the
        # regular location path below.
        direct_vals: Dict[ObjectID, Any] = {}
        rest_ids = []
        waiters = self._direct_waiters
        deadline = None if timeout is None else time.monotonic() + timeout
        if not len(waiters):
            # No direct calls outstanding anywhere: skip the per-oid
            # waiter-table probes (a 1M-ref drain get() would probe a
            # million times for guaranteed misses). Entries only appear
            # from this process's own direct submits, so the emptiness
            # check cannot race a reply this get() cares about.
            rest_ids = ids
            ids_iter = ()
        else:
            ids_iter = ids
        flushed: set = set()
        for oid in ids_iter:
            if oid in direct_vals:
                continue
            entry = waiters.get(oid.binary())
            if entry is None:
                rest_ids.append(oid)
                continue
            if not entry.event.is_set() and entry.chan is not None \
                    and entry.chan not in flushed:
                # Flush exactly the channel carrying this call — NOT
                # every dirty channel: a sync caller must not do an
                # unrelated pipelined stream's writev on its own round
                # trip (the periodic flusher bounds those).
                flushed.add(entry.chan)
                try:
                    entry.chan.flush()
                except Exception:
                    pass
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            if not entry.event.wait(remaining):
                raise GetTimeoutError(
                    f"get() timed out after {timeout}s waiting for a "
                    f"direct actor call result"
                )
            value = self._resolve_direct(oid, entry)
            waiters.pop(oid.binary())
            if value is _REDIRECT:
                rest_ids.append(oid)
            else:
                direct_vals[oid] = value
        if rest_ids:
            # Falling through to the control plane: every buffered direct
            # frame must be out first (an NM-routed read may dep-wait on
            # a buffered call's seal), and side bookkeeping (seals/unpins
            # for just-resolved replies) must reach the NM before the
            # location lookups below. A read whose every location this
            # process already holds (a streamed item's, carried) looks
            # nothing up and sends nothing.
            cache = self._loc_cache
            if any(oid not in cache for oid in rest_ids):
                self._flush_direct()
                self._direct_flush_side(force=True)
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            try:
                locations = self._cached_locations(rest_ids, remaining)
            except TimeoutError as e:
                raise GetTimeoutError(
                    f"get() timed out after {timeout}s waiting for "
                    f"{len(rest_ids)} objects"
                ) from e
            by_id = dict(locations)
        else:
            by_id = {}
        values = []
        for oid in ids:
            if oid in direct_vals:
                value = direct_vals[oid]
            else:
                loc = by_id.get(oid)
                if loc is None:
                    raise GetTimeoutError(f"object {oid.hex()} unavailable")
                value = self._read_object(oid, loc, timeout)
            if isinstance(value, TaskError):
                raise value.as_raisable()
            values.append(value)
        return values[0] if single else values

    def _resolve_direct(self, oid: ObjectID, entry: _DirectResult):
        msg = entry.payload
        if msg.get("redirect"):
            # Replayed over the NM path after a channel death: the
            # replayed task's seal resolves it through the directory.
            return _REDIRECT
        for roid, loc in msg.get("results", ()):
            if roid == oid:
                if isinstance(loc, InlineLocation) or entry.readable:
                    return self.store.get_object(loc)
                # Shared-memory/remote bytes this process cannot map:
                # resolve through the location path (client pulls over
                # the wire; remote callers pull via their NM).
                return _REDIRECT
        # Channel died before the reply arrived.
        from .exceptions import ActorDiedError

        return ActorDiedError("actor task", msg.get("error", "actor died"))

    def _read_object(self, oid: ObjectID, loc: Location, timeout):
        """Read one object, retrying through fresh locations when the
        storage moved underneath us (spilled/restored between the location
        reply and the read — the window plasma closes with get-time pins)."""
        for _ in range(5):
            try:
                return self.store.get_object(loc)
            except (KeyError, FileNotFoundError):
                # Bypass + invalidate the location cache: the cached
                # location is exactly what just went stale.
                self._loc_cache.pop(oid, None)
                (_, loc), = self._get_locations([oid], timeout)
                if loc is None:
                    # Permanently gone, not slow: no node holds a copy.
                    raise ObjectLostError(
                        f"object {oid.hex()} lost while reading (no "
                        "remaining location)"
                    ) from None
        return self.store.get_object(loc)

    # ---- location cache ----------------------------------------------------
    # Objects are immutable and ObjectIDs are never reused, so a resolved
    # location stays valid until the storage moves (spill/re-home/free) —
    # and _read_object already retries through a fresh lookup for exactly
    # those cases. Caching turns the per-call control-plane round trip of
    # repeated-argument fetches (same ref passed to many actor calls)
    # into a dict hit.

    _LOC_CACHE_CAP = 8192
    _LOC_CACHE_INLINE_MAX = 4096  # don't pin big inline blobs in memory

    def _cached_locations(
        self, ids: List[ObjectID], timeout: Optional[float]
    ) -> List[Tuple[ObjectID, Location]]:
        # The borrow protocol requires this process's +1 deltas to land
        # before any read resolves — including cache-hit reads, where no
        # control-plane lookup (with its own flush) happens. No-op when
        # no add is pending.
        self.refs.flush_adds()
        cache = self._loc_cache
        # Snapshot hits while scanning: the cache is shared across
        # threads (cap clears, stale-read invalidation), so re-reading
        # it at return time could turn a hit into a spurious miss.
        hits: Dict[ObjectID, Location] = {}
        missing: List[ObjectID] = []
        for i in ids:
            loc = cache.get(i)
            if loc is None:
                missing.append(i)
            else:
                hits[i] = loc
        if missing:
            fetched = dict(self._get_locations(missing, timeout))
            if len(missing) > self._LOC_CACHE_CAP:
                # A batch larger than the cache would only churn it
                # (insert + wholesale clear, nothing survives for reuse)
                # — the 1M-task drain get() pays real money here.
                pass
            else:
                if len(cache) + len(fetched) > self._LOC_CACHE_CAP:
                    cache.clear()  # rare; amortized O(1)
                for i, loc in fetched.items():
                    if loc is None:
                        continue
                    if (isinstance(loc, InlineLocation)
                            and len(loc.data) > self._LOC_CACHE_INLINE_MAX):
                        continue
                    cache[i] = loc
        else:
            fetched = {}
        return [(i, hits.get(i, fetched.get(i))) for i in ids]

    def _carry_location(self, oid: ObjectID, loc: Location):
        """Keep a location that came with another reply (a streamed
        item's, with the wait for its seal), so that the ``get`` of a
        ref this process holds makes no request. Whatever its size: it
        leaves with the process's last local ref, like any entry."""
        cache = self._loc_cache
        if len(cache) >= self._LOC_CACHE_CAP:
            cache.clear()
        cache[oid] = loc

    def wait(
        self,
        refs: Sequence[ObjectRef],
        num_returns: int = 1,
        timeout: Optional[float] = None,
    ):
        self._flush_direct()
        refs = list(refs)
        if num_returns > len(refs):
            raise ValueError("num_returns exceeds number of refs")
        # Direct results whose reply already landed are ready NOW: count
        # them from the waiter table so wait() on direct calls does not
        # round-trip the control plane (whose seal may trail the reply
        # by one completion-notification debounce window).
        ready_ids: set = set()
        waiters = self._direct_waiters
        if len(waiters):
            for r in refs:
                e = waiters.get(r.id().binary())
                if (e is not None and e.event.is_set()
                        and e.payload is not None
                        and not e.payload.get("redirect")):
                    ready_ids.add(r.id())
        if len(ready_ids) < num_returns:
            rest = [r.id() for r in refs if r.id() not in ready_ids]
            if rest:
                ready_ids |= set(self._wait(
                    rest, min(num_returns - len(ready_ids), len(rest)),
                    timeout,
                ))
        ready, not_ready = [], []
        for r in refs:
            (ready if r.id() in ready_ids and len(ready) < num_returns
             else not_ready).append(r)
        return ready, not_ready

    # ---- task submission ---------------------------------------------------

    def prepare_args(self, args: Sequence[Any], kwargs: Dict[str, Any]):
        """Convert call arguments into spec args: ObjectRefs pass by
        reference; large values are promoted to objects (ref analogue:
        put_threshold inlining in remote_function._remote). Refs found
        INSIDE serialized values are returned as ``nested`` — the caller
        stamps them onto the spec so the control plane pins them for the
        task's lifetime (for promoted args they ride the promoted
        object's containment pin instead)."""
        cfg = get_config()
        keepalive = []
        nested_all: List[ObjectID] = []

        def conv(v):
            if isinstance(v, ObjectRef):
                keepalive.append(v)
                return RefArg(v.id())
            sobj, nested = serialize_with_refs(v)
            if sobj.total_size <= cfg.max_inline_object_size:
                nested_all.extend(nested)
                return ValueArg(sobj.to_bytes())
            oid = self._next_put_id()
            loc = self._put_serialized(oid, sobj)
            self._register_put(oid, loc, nested)
            ref = ObjectRef(oid, _register=True)
            keepalive.append(ref)
            return RefArg(oid)

        spec_args = [conv(a) for a in args]
        spec_kwargs = {k: conv(v) for k, v in kwargs.items()}
        return spec_args, spec_kwargs, keepalive, tuple(nested_all)

    def ensure_function(self, fn) -> str:
        # Identity-keyed fast path: re-pickling the function on every
        # .remote() call costs more than the whole submit otherwise.
        function_id = self._function_ids.get(id(fn))
        if function_id is not None:
            return function_id
        function_id, blob = export_function(fn)
        if function_id not in self._registered_functions:
            self._register_function_remote(function_id, blob)
            self._registered_functions.add(function_id)
            self.function_cache.add_blob(function_id, blob)
        # The id() key is only valid while fn is alive; evict the entry on
        # collection rather than pinning fn (pinning would leak every
        # dynamically-created function and its captured closure forever).
        self._function_ids[id(fn)] = function_id
        try:
            import weakref

            weakref.finalize(fn, self._function_ids.pop, id(fn), None)
        except TypeError:
            # Not weakref-able (rare: builtins/partials): drop the cache
            # entry immediately — correctness over speed.
            self._function_ids.pop(id(fn), None)
        return function_id

    def _stamp_trace(self, spec: TaskSpec):
        if spec.trace_ctx is not None:
            return
        from .timeline import current_span

        ctx = current_span()
        if ctx is not None:
            spec.trace_ctx = ctx
            return
        # Top-level submit: this task roots a new trace. With submit
        # spans enabled (RAY_TPU_TRACE_SUBMITS=1, read at import), the
        # driver's submit call itself becomes the root span so the
        # exported tree reads driver-submit -> worker-exec -> nested.
        trace_id = spec.task_id.hex()[:16]
        if _TRACE_SUBMITS:
            from .timeline import get_buffer, new_span_id

            sid = new_span_id()
            now = time.time()
            get_buffer().record(
                f"submit:{spec.name or spec.method_name or 'task'}",
                now, now, spec.task_id.hex(),
                trace_id=trace_id, span_id=sid, parent_id="",
            )
            spec.trace_ctx = (trace_id, sid)
        else:
            spec.trace_ctx = (trace_id, "")

    def submit(self, spec: TaskSpec) -> List[ObjectRef]:
        self._stamp_trace(spec)
        if (
            self._direct_capable
            and spec.task_type == TaskType.ACTOR_TASK
            and spec.actor_id is not None
            and get_config().direct_actor_calls
        ):
            return self._submit_actor_task(spec)
        self._submit_spec(spec)
        return [ObjectRef(oid, _register=True) for oid in spec.return_ids()]

    # ---- direct actor-call plane -------------------------------------------

    _DIRECT_WAITER_CAP = 8192

    def _submit_actor_task(self, spec: TaskSpec) -> List[ObjectRef]:
        """Route an actor call: over the direct channel when one is
        ready and the call is eligible, else through the NM path — with
        the switchover discipline that preserves per-handle ordering
        (direct frames can never overtake NM-routed calls and vice
        versa; see _direct_discover)."""
        # Calls carrying retries keep the NM route: its actor-restart
        # replay resubmits them in order; a direct channel can only
        # fail them on worker death. A streaming call (one return, its
        # completion) is a call like any other here: its items come back
        # on the channel it goes out on (core/streaming.py).
        eligible = spec.num_returns == 1 and spec.retries_left == 0
        if eligible:
            # A call chained on a still-pending direct result must not
            # ride the same connection: the worker would execute it
            # while the dependency's reply (and therefore its seal) may
            # still be sitting in a reply batch — route it through the
            # NM, which gates dispatch on sealed deps. Each probe is one
            # GIL-atomic table call (per-call hot path; the old Python
            # lock here contended with the reader at full call rate).
            waiters = self._direct_waiters
            if len(waiters):
                for dep in spec.dependency_ids():
                    entry = waiters.get(dep.binary())
                    if entry is not None and not entry.event.is_set():
                        eligible = False
                        break
        st = self._direct_state(spec.actor_id)
        chan_for_fence = None
        wait_drained = None
        spawn_discovery = False
        with st["lock"]:
            if eligible and st["status"] == "none" and not st["nm_seq"]:
                self._direct_first_call(spec.actor_id, st)
            if eligible and st["status"] == "ready":
                chan = st["chan"]
                try:
                    self._direct_stamp_owner(spec)
                    chan.submit(spec)
                    return [
                        ObjectRef(oid, _register=True)
                        for oid in spec.return_ids()
                    ]
                except Exception:
                    # Dead channel: the reader's failure path replays
                    # its pending calls over the NM route; this call
                    # must queue AFTER them (see wait below). Close the
                    # raw socket (NOT chan.close(), which marks the
                    # teardown deliberate and fails instead of
                    # replaying) so a wedged reader wakes now.
                    try:
                        chan.conn.close()
                    except Exception:
                        pass
                    st["status"] = "none"
                    st["chan"] = None
                    wait_drained = chan
                    self._direct_fallbacks += 1
                    _FALLBACK_CHANNEL.inc()
            # NM path: bump the sequence so a discovery in flight cannot
            # flip to ready underneath this call; discovery is
            # (re)started AFTER the spec is enqueued below, so it cannot
            # observe the actor idle before this call lands.
            st["nm_seq"] += 1
            if st["status"] == "ready":
                # Ineligible call interleaving with direct traffic:
                # fence so it cannot overtake queued direct frames.
                chan_for_fence = st["chan"]
            if st["status"] in ("none", "ready") or (
                st["status"] == "unsupported"
                and time.monotonic() >= st.get("retry_at", 0.0)
            ):
                st["status"] = "discovering"
                spawn_discovery = True
        if chan_for_fence is not None and chan_for_fence.alive:
            try:
                chan_for_fence.fence()
            except Exception:
                # The channel died mid-fence: its failure path is about
                # to replay the queued direct calls over the NM route —
                # order this call behind those replays, exactly like the
                # died-before-fence branch below.
                wait_drained = chan_for_fence
        elif chan_for_fence is not None:
            # The ready channel died before we could fence it: order
            # behind its failure replays instead.
            wait_drained = chan_for_fence
        if wait_drained is not None:
            wait_drained.drained.wait(15.0)
        self._submit_spec(spec)
        refs = [ObjectRef(oid, _register=True) for oid in spec.return_ids()]
        if spawn_discovery:
            # The submit above reached the NM first; the discovery's own
            # control-plane work is processed after it, so the resolve
            # sees this call queued.
            threading.Thread(
                target=self._direct_discover,
                args=(spec.actor_id, st),
                daemon=True,
            ).start()
        return refs

    def _direct_retry_later(self, st: Dict[str, Any],
                            min_delay: float = 0.0) -> None:
        """Schedule the next direct-endpoint re-resolution with shared
        jittered exponential backoff (util/backoff.py) instead of the
        old fixed 10s/30s sleeps: repeated failures (actor restarting,
        endpoint unreachable, injected chaos) space out instead of
        hammering the NM resolve path in lockstep."""
        bo = st.get("resolve_backoff")
        if bo is None:
            bo = st["resolve_backoff"] = Backoff(
                base=1.0, factor=2.0, max_delay=30.0, jitter=0.25
            )
        st["retry_at"] = time.monotonic() + max(min_delay,
                                                bo.next_delay())

    def _direct_state(self, actor_id: ActorID) -> Dict[str, Any]:
        key = actor_id.binary()
        with self._direct_states_lock:
            st = self._direct_states.get(key)
            if st is None:
                st = {"lock": threading.Lock(), "status": "none",
                      "chan": None, "nm_seq": 0}
                self._direct_states[key] = st
            # Touched-at stamp: the pruner must never delete an entry a
            # submitter just fetched (it would act on the orphan — a
            # second channel to the same actor, sequences split).
            st["touched"] = time.monotonic()
            return st

    def _direct_first_call(self, actor_id: ActorID, st: Dict[str, Any]):
        """This process's first call to an actor (nothing of its own
        went the NM route that a direct frame could overtake): ask for
        the endpoint once, now. An actor that is alive and idle at its
        NM answers at once and the channel is ready for this very call;
        any other answer leaves the call to the NM route and the
        switchover to ``_direct_discover``, as ever. Without this a
        caller whose calls leave the actor no idle moment (a serving
        proxy under load: every stream open on the NM route keeps the
        NM's drain gate shut) never gets its channel. Caller holds
        ``st["lock"]``."""
        if threading.current_thread() is getattr(
                getattr(self, "_nm", None), "_thread", None):
            return  # on the NM's own loop: it cannot wait for itself
        try:
            desc = self._direct_resolve(actor_id, 0.0)
            if desc:
                st["chan"] = _DirectChannel(self, actor_id, desc)
                st["status"] = "ready"
        # Not now: the NM route carries the call, discovery follows it.
        except Exception:  # rtlint: disable=swallowed-failure
            pass

    def _direct_discover(self, actor_id: ActorID, st: Dict[str, Any]):
        """Background switchover: resolve the actor's direct endpoint.
        The actor's home NM only answers once the actor is alive with NO
        control-plane calls queued/in flight, and we only flip to ready
        if no new NM-path call raced in (nm_seq unchanged) — so direct
        frames can never overtake NM-routed ones."""
        timeout = get_config().direct_resolve_timeout_s
        while True:
            with st["lock"]:
                seq0 = st["nm_seq"]
            try:
                desc = self._direct_resolve(actor_id, timeout)
            except BaseException:
                # Includes CancelledError (BaseException): NM shutdown
                # cancels in-flight loop tasks; this daemon thread must
                # exit quietly, not print an unhandled traceback.
                desc = None
            if not desc:
                # Unsupported OR just continuously busy for the whole
                # wait window: retry on a later submit rather than
                # pinning the actor to the slow route forever.
                with st["lock"]:
                    st["status"] = "unsupported"
                    self._direct_retry_later(st)
                return
            with st["lock"]:
                if st["nm_seq"] != seq0:
                    continue  # an NM call raced in; wait for drain again
                chan = st["chan"]
                need_new = (chan is None or not chan.alive
                            or chan.desc != desc)
            if need_new:
                # Dial OUTSIDE the state lock: a TCP+TLS handshake must
                # not block submitters on st["lock"].
                try:
                    chan = _DirectChannel(self, actor_id, desc)
                except _DirectVersionMismatch:
                    # A version skew won't heal quickly: floor the
                    # backoff at its cap.
                    with st["lock"]:
                        st["status"] = "unsupported"
                        self._direct_retry_later(st, min_delay=30.0)
                    self._direct_fallbacks += 1
                    _FALLBACK_VERSION.inc()
                    return
                except Exception:
                    with st["lock"]:
                        st["status"] = "unsupported"
                        self._direct_retry_later(st)
                    self._direct_fallbacks += 1
                    _FALLBACK_UNSUPPORTED.inc()
                    return
            with st["lock"]:
                if st["nm_seq"] != seq0:
                    if need_new:
                        chan.close()
                    continue  # raced again; re-verify the drain
                st["chan"] = chan
                st["status"] = "ready"
                bo = st.get("resolve_backoff")
                if bo is not None:
                    bo.reset()  # healthy again: next failure backs off
                return

    def _direct_channel_failed(self, chan: "_DirectChannel"):
        """The channel died (worker exit, socket error, injected fault):
        fall back transparently. Still-unanswered calls replay through
        the NM-mediated path IN SEQUENCE ORDER — the worker dedups
        replayed task ids it already executed, and the NM route gates
        ordering on its own actor queue — so per-handle call order
        survives the failover. get()/wait() waiters parked on a replayed
        call are redirected to the regular location path, where the
        replayed task's seal (or failure) resolves them. A channel WE
        closed (shutdown, explicit teardown) fails its pending calls
        instead: the runtime is going away, replaying would resurrect
        work the caller is abandoning."""
        st = self._direct_state(chan.actor_id)
        with chan.plock:
            chan.failed = True  # later submits raise instead of stranding
            chan.out_buf = []
        # Wake a capped submitter (it re-checks chan.failed), then
        # snapshot + clear the pending table in seq order — the replay
        # contract: still-unanswered calls resubmit in the exact order
        # they were sequenced, worker-side task-id dedup keeps them
        # exactly-once.
        chan.table.fail()
        tids = chan.table.drain()
        calls = chan._calls
        pend = [c for c in (calls.pop(t, None) for t in tids)
                if c is not None]
        # Any call still in _calls was popped from the table by a burst
        # the reader never delivered to Python (a native error between
        # the GIL-free completion application and the waiter wakeups, or
        # a batch malformed past its first bodies): the table alone
        # cannot replay it, so sweep the rich-state dict too — _calls is
        # the authority for WHAT replays, the table only for the order.
        if calls:
            pend.extend(calls.values())
            calls.clear()
            pend.sort(key=lambda c: c.seq)
        # A stream that was handed a frame is not replayed (the replay
        # would run its generator, side effects and all, a second time,
        # and the worker's dedup knows finished calls only): it ends
        # with the death, what came stays readable. One that was handed
        # nothing replays like any call and goes on over the NM route.
        streams, chan._streams = chan._streams, {}

        def stream_of(call):
            return streams.get(call.spec.task_id.binary())

        if chan.closed_by_us:
            failed, pend = pend, []
        else:
            failed = [c for c in pend if getattr(stream_of(c), "received", 0)]
            pend = [c for c in pend if c not in failed]
        try:
            for call in failed:
                call.entry.payload = {
                    "failed": True, "results": [],
                    "error": "actor died (direct channel closed)",
                }
                call.entry.event.set()
                self._direct_waiters.mark_resolved(call.oid.binary())
                if not chan.closed_by_us:
                    # No replay re-pins the args: release the direct pin.
                    self._direct_on_replay(call.dep_ids)
            for call, how in ([(c, DirectStream.DIED) for c in failed]
                              + [(c, DirectStream.REROUTED) for c in pend]):
                stream = stream_of(call)
                if stream is not None:
                    stream.end(how)
            if not pend:
                return
            self._direct_fallbacks += len(pend)
            _FALLBACK_CHANNEL.inc(len(pend))
            for call in pend:
                # Wake parked waiters into the location path BEFORE the
                # NM resubmit: the placeholder from the direct
                # registration is already in the directory, so the
                # redirected read blocks on the replayed task's seal.
                call.entry.payload = {"redirect": True}
                call.entry.event.set()
                self._direct_waiters.pop(call.oid.binary())
                # The direct registration pinned the args; the NM
                # resubmit pins them again — release the direct pin.
                self._direct_on_replay(call.dep_ids)
                # Marked so the NM fails it (like an interrupted
                # NM-routed call) if the actor itself died rather than
                # just the channel — and bound to the incarnation this
                # channel spoke to, so a replay can never land on a
                # RESTARTED incarnation (whose dedup cache knows
                # nothing of this channel's calls: double execution).
                call.spec.direct_replay = True
                call.spec.actor_incarnation = chan.incarnation
                try:
                    self._submit_spec(call.spec)
                except Exception:
                    pass
        finally:
            # Flip the state only AFTER the replays are queued and set
            # ``drained``: a submitter racing the failure (its send
            # raised, or it found the dead channel under the state lock)
            # parks on drained before its own NM submit, so per-handle
            # order survives the failover window.
            with st["lock"]:
                if st.get("chan") is chan:
                    st["status"] = "none"
                    st["chan"] = None
            chan.drained.set()

    def fence_node(self, node_hex: str, epoch: int = 0):
        """Membership fence: tear down every direct channel this
        runtime holds to actors on ``node_hex``. Under an asymmetric
        partition the sockets are perfectly healthy — without this the
        caller keeps executing calls on the fenced incarnation while
        the cluster restarts the actor elsewhere (split brain). The raw
        socket close (NOT chan.close(), which marks the teardown
        deliberate and FAILS pending calls) wakes the reader's failure
        path, which parks in-flight calls into the exactly-once NM
        replay route — where replays bound to the fenced incarnation
        are refused and fresh calls re-resolve to the new one."""
        if not node_hex:
            return
        with self._direct_states_lock:
            states = list(self._direct_states.values())
        torn = 0
        for st in states:
            chan = st.get("chan")
            if chan is None or not chan.alive:
                continue
            if chan.node_hex != node_hex:
                continue
            torn += 1
            try:
                chan.conn.close()
            # Racing its own death: the reader's failure path runs
            # either way.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
        if torn:
            from . import fencing as _fencing

            _fencing.EVENT_CHANNEL_TEARDOWN.inc(torn)

    def _direct_waiters_put(self, oid: ObjectID, entry: _DirectResult):
        # The table evicts RESOLVED entries from the FIFO front beyond
        # its cap (oldest first; the object stays resolvable through
        # the directory). Unresolved entries are genuinely pending
        # calls and are skipped, so one slow in-flight call cannot pin
        # the table's growth under fire-and-forget load. "Resolved" is
        # stamped by mark_resolved at reply/failure time — the table
        # never has to call back into Python to probe an Event.
        key = oid.binary()
        self._direct_waiters.put(key, entry)
        if entry.event.is_set():
            # The reply (or failure) beat this put: its mark_resolved
            # found no entry and no-op'd. Re-stamp after insertion, or
            # a fire-and-forget entry would sit unresolved forever and
            # wedge the FIFO eviction scan once 64 such pile up.
            self._direct_waiters.mark_resolved(key)

    def _mark_chan_dirty(self, chan: "_DirectChannel"):
        with self._dirty_chans_lock:
            self._dirty_chans.add(chan)

    def _flush_direct(self):
        if not self._dirty_chans:
            return
        with self._dirty_chans_lock:
            chans = list(self._dirty_chans)
            self._dirty_chans.clear()
        for chan in chans:
            try:
                chan.flush()
            except Exception:
                pass

    _DIRECT_STATE_CAP = 1024

    def _direct_prune_states(self):
        """Long-lived drivers/serve controllers churn through actors
        (rolling replica generations); their channel-less state entries
        would otherwise accumulate forever and stretch every flusher
        walk. Dropping an idle entry is safe: the next call to that
        actor recreates it and re-runs the drain-gated discovery."""
        if len(self._direct_states) <= self._DIRECT_STATE_CAP:
            return
        cutoff = time.monotonic() - 60.0
        with self._direct_states_lock:
            for key, st in list(self._direct_states.items()):
                # Only prune entries idle for a while: a submitter that
                # fetched an entry uses it within microseconds, so the
                # idle window guarantees nobody is holding it outside
                # the states lock.
                if (st.get("chan") is None
                        and st.get("status") in ("none", "unsupported")
                        and st.get("touched", 0.0) < cutoff):
                    del self._direct_states[key]
                    if len(self._direct_states) <= self._DIRECT_STATE_CAP:
                        break

    def _direct_inflight(self) -> int:
        n = 0
        with self._direct_states_lock:
            chans = [st.get("chan") for st in self._direct_states.values()]
        for chan in chans:
            if chan is not None:
                # Both reads are single GIL-atomic ops; the table size
                # lives off the GIL entirely (no plock round — the
                # flusher must not contend with the submit hot path).
                n += len(chan.table) + len(chan.out_buf)
        return n

    def direct_stats(self) -> Dict[str, Any]:
        """Caller-side direct-plane snapshot (rtpu metrics --actors and
        tools/run_actor_bench.py)."""
        chans = []
        with self._direct_states_lock:
            states = {k: dict(v) for k, v in self._direct_states.items()}
        calls = 0
        py_entries = 0
        frames_in = 0
        completions = 0
        native_tables = 0
        for key, st in states.items():
            chan = st.get("chan")
            probe = chan.gil_probe() if chan is not None else {}
            if chan is not None:
                calls += chan.calls
                py_entries += probe.get("py_entries", 0)
                frames_in += probe.get("frames_in", 0)
                completions += probe.get("pending_table", {}).get("pops", 0)
                if getattr(chan.table, "native", False):
                    native_tables += 1
            chans.append({
                "actor_id": key.hex(),
                "status": st.get("status"),
                "remote": bool(chan is not None and chan.remote),
                "calls": chan.calls if chan is not None else 0,
                **probe,
            })
        return {
            "channels": chans,
            "calls": calls,
            "inflight": self._direct_inflight(),
            "fallbacks": self._direct_fallbacks,
            # GIL-handoff probe (ISSUE 12): interpreter entries the
            # channel readers made vs frames they received — the
            # dispatch core's burst coalescing makes entries << frames.
            "gil_probe": {
                "py_entries": py_entries,
                "frames_in": frames_in,
                "completions": completions,
                "native_tables": native_tables,
            },
        }

    # Subclass hooks for the direct plane. The base implementations are
    # inert so non-capable runtimes cost nothing.

    def _direct_resolve(self, actor_id: ActorID,
                        timeout: float) -> Optional[Dict[str, Any]]:
        """Resolve the actor's direct endpoint descriptor ({"path",
        "addr", "ver", "node"}) via this runtime's control plane; None =
        unsupported/busy."""
        return None

    def _direct_stamp_owner(self, spec: TaskSpec):
        pass

    def _direct_on_reg(self, spec: TaskSpec):
        """Register return slots + pin args with this runtime's NM."""

    def _direct_on_done(self, msg: Dict[str, Any], dep_ids: list,
                        chan: "_DirectChannel"):
        """Seal results / register nested refs / unpin args."""

    def _direct_on_replay(self, dep_ids: list):
        """Release the direct registration's arg pins before an NM-path
        replay re-pins them."""

    def _direct_on_item(self, oid: ObjectID, frame: Dict[str, Any],
                        remote: bool):
        """A streamed item came on a direct channel (core/streaming.py):
        register its id with this runtime's NM, in order before any
        release of it or any task that takes its ref: a placeholder the
        producer's batched seal (and pin) lands on. From a ``remote``
        producer nothing else tells this NM of it: also the pin, the
        seal at ``frame["loc"]`` and the refs inside it,
        ``frame["n"]``."""

    def take_direct_stream(self, task_id: TaskID):
        """The ``DirectStream`` of the streaming call just submitted, if
        it went out on a direct channel (once: it is the caller's
        then)."""
        return self._direct_streams.pop(task_id, None)

    def _direct_flush_side(self, force: bool = False):
        """Flush buffered NM side-bookkeeping (worker/client runtimes)."""

    def new_task_id(self) -> TaskID:
        return TaskID.from_random()

    def shutdown(self):
        self._flusher_stop.set()
        with self._direct_states_lock:
            states = list(self._direct_states.values())
            self._direct_states.clear()
        for st in states:
            chan = st.get("chan")
            if chan is not None:
                chan.close()


class _DirectResult:
    """Pending direct-call reply: the channel reader fills payload and
    sets the event; get() resolves from it without touching the NM.
    ``readable`` records whether shared-memory result locations in the
    reply are readable from this process (same node, store attached);
    when False, non-inline results resolve through the regular location
    path instead. ``chan`` is the channel whose out_buf may still hold
    the call's frame — get() flushes exactly that channel instead of
    every dirty one (a sync caller must not pay for an unrelated
    pipelined stream's writev on its own round trip)."""

    __slots__ = ("event", "payload", "readable", "chan")

    def __init__(self, readable: bool = True, chan=None):
        self.event = threading.Event()
        self.payload = None
        self.readable = readable
        self.chan = chan


# Sentinel: this oid must resolve through the location path after all
# (replayed over the NM route, or bytes not readable from this process).
_REDIRECT = object()


class _DirectVersionMismatch(ConnectionError):
    """The actor's worker speaks a different direct-channel protocol
    version; the caller stays on the NM-mediated path."""


class _PendingCall:
    __slots__ = ("oid", "entry", "dep_ids", "spec", "t0", "seq")

    def __init__(self, oid, entry, dep_ids, spec, t0, seq):
        self.oid = oid
        self.entry = entry
        self.dep_ids = dep_ids
        self.spec = spec
        self.t0 = t0
        self.seq = seq


class _DirectChannel:
    """Caller side of the direct actor-call transport (ref analogue:
    direct_actor_task_submitter.h — actor tasks pushed straight to the
    actor's worker over a dedicated connection; replies carry results
    inline). One connection + reader thread per (runtime, actor): a unix
    socket when the actor lives on this node, a TLS-aware TCP channel
    (the worker advertises both) otherwise — so workers, serve replicas
    and thin clients all ride the same plane. Every call frame carries a
    per-handle monotonic sequence number ``q``; the worker executes in
    sequence order and buffers out-of-order arrivals. On ANY channel
    error the runtime replays still-unanswered calls through the
    NM-mediated submit path in sequence order (the worker dedups task
    ids it already executed), so fallback is transparent."""

    def __init__(self, rt: "BaseRuntime", actor_id: ActorID,
                 desc: Dict[str, Any]):
        from .protocol import Connection, connect_unix

        self.rt = rt
        self.actor_id = actor_id
        self.desc = desc
        self.node_hex = desc.get("node") or rt.node_id.hex()
        self.remote = self.node_hex != rt.node_id.hex()
        ver = desc.get("ver", 1)
        if ver != DIRECT_PROTO_VER:
            raise _DirectVersionMismatch(
                f"worker speaks direct protocol v{ver}, "
                f"caller v{DIRECT_PROTO_VER}"
            )
        path = desc.get("path")
        addr = desc.get("addr")
        # The unix socket only exists on the actor's host. A thin client
        # shares the HEAD's node id, so the node check alone cannot tell
        # a co-located client from one on another machine — require the
        # path to actually exist here before dialing it, else use TCP.
        if path and not self.remote and _os.path.exists(path):
            self.conn = connect_unix(path, timeout=5.0)
        elif addr:
            import socket as _socket

            from .tls import client_ssl_context

            sock = _socket.create_connection(
                (addr[0], int(addr[1])),
                timeout=get_config().transfer_connect_timeout_s,
            )
            ctx = client_ssl_context()
            if ctx is not None:
                sock = ctx.wrap_socket(sock)
            sock.settimeout(None)
            self.conn = Connection(sock)
        else:
            raise ConnectionError("actor advertised no direct endpoint")
        # Hello/welcome handshake: session token, protocol version and
        # the caller's node (the worker holds non-inline results for
        # remote callers until their RemoteLocation entry is collected).
        # "npv" advertises the native frame-pump codec version (0 = this
        # side will speak pickle only); both sides must agree before
        # either emits a native frame, and the magic-byte sniff in
        # loads_msg keeps a half-engaged channel correct regardless.
        # Bounded: a worker that accepted the connection but never
        # replies (wedged, SIGSTOPped, half-open socket) must fail the
        # dial — discovery then retries via the unsupported path —
        # rather than pin this discovery thread forever.
        import ssl as _ssl

        # TLS channels never speak the native dialect (the pump moves
        # raw fd bytes below the SSL layer): advertise npv=0 so the
        # worker doesn't engage either, and count the fallback as what
        # it is.
        sock_pumpable = not isinstance(self.conn._sock, _ssl.SSLSocket)
        my_npv = frame_pump.advertised_ver() if sock_pumpable else 0
        # Incarnation from the NM resolution: the worker refuses a
        # mismatch (fencing — this channel can only ever speak to the
        # exact actor start the control plane resolved).
        self.incarnation = int(desc.get("inc") or 0)
        self.conn.settimeout(10.0)
        self.conn.send({
            "type": "direct_hello", "ver": DIRECT_PROTO_VER,
            "npv": my_npv,
            "token": get_config().session_token,
            "actor_id": actor_id.hex(), "node": rt.node_id.hex(),
            "inc": self.incarnation,
        })
        welcome = self.conn.recv()
        self.conn.settimeout(None)
        if welcome.get("type") != "direct_welcome" or not welcome.get("ok"):
            self.conn.close()
            err = welcome.get("error", "refused")
            if "version" in str(err):
                raise _DirectVersionMismatch(err)
            raise ConnectionError(f"direct hello refused: {err}")
        # Engage the native pump: framing moves into the extension
        # (buffered GIL-released reads, coalesced writev bursts) and the
        # hot call frames use the compact codec. Any engage failure is
        # counted in ray_tpu_native_fallbacks_total and the channel
        # simply stays on the pure-Python pickle path.
        from .rpc import negotiate_codec

        self.native = False
        # Agreed codec version (0 = pickle only): gates which FEATURES
        # of the native dialect this side may emit — trace context rides
        # call frames only at npv >= frame_pump.TRACE_MIN_VER, so a v1
        # peer keeps working (traceless) instead of dropping to pickle.
        self.npv = 0
        if not frame_pump.advertised_ver():
            # Knob off or .so missing: this channel runs pure-Python.
            frame_pump.count_fallback(
                "disabled" if frame_pump.disabled() else "unavailable"
            )
        elif not sock_pumpable:
            frame_pump.count_fallback("tls")
        elif negotiate_codec(welcome.get("npv"),
                             frame_pump.advertised_ver()):
            wrapped = frame_pump.wrap_connection(self.conn)
            if wrapped is not None:
                self.conn = wrapped
                self.native = True
                self.npv = negotiate_codec(welcome.get("npv"),
                                           frame_pump.advertised_ver())
        else:
            frame_pump.count_fallback("no_peer")
        # Can this process read same-node shared-memory result locations?
        self.store_readable = (not self.remote) and rt._direct_store_readable
        self.alive = True
        self.closed_by_us = False
        # Set UNDER plock by the failure path before it drains pending:
        # a submitter that appended earlier is in the drained set (and
        # replays); one that arrives later sees the flag and raises —
        # without this, a submit racing the drain could strand a call
        # that is never sent, never replayed and never failed.
        self.failed = False
        # Set once the failure path has finished replaying/failing this
        # channel's pending calls: a submitter racing the failure parks
        # on it so its NM-path submit cannot overtake the replays.
        self.drained = threading.Event()
        self.plock = threading.Lock()
        # Serializes pop-buffer + socket-send so a fence frame can never
        # overtake frames a concurrent flush already popped but had not
        # yet written (the fence promise covers every EARLIER call).
        self._flush_lock = threading.Lock()
        # The pending/replay table: task-id -> submit seq, off the GIL
        # in the extension (ISSUE 12). The DIRECT_MAX_UNANSWERED
        # backpressure waits on ITS condvar (GIL released), the pump's
        # reader pops it per completion without entering Python, and
        # failover replay snapshots it in seq order. The rich per-call
        # state (waiter entry, spec for replay, arg pins, t0) stays in
        # _calls — a plain dict keyed by task-id bytes whose pops happen
        # only on the reader thread (GIL-atomic; no lock round).
        self.table = frame_pump.new_pending_table()
        self._calls: Dict[bytes, _PendingCall] = {}
        # task-id bytes -> DirectStream of each streaming call in
        # flight: the reader hands ``stream_item`` frames to it, the
        # call's reply (or the channel's death) ends it.
        self._streams: Dict[bytes, Any] = {}
        # GIL-handoff probe: interpreter entries the reader made vs
        # frames received (see gil_probe()).
        self.py_entries = 0
        self.frames_rx = 0
        self.out_buf: List[Dict[str, Any]] = []
        self._fences: Dict[int, threading.Event] = {}
        self._fence_seq = itertools.count(1)
        # Per-handle monotonic call sequence (stamped as "q" on frames).
        self._seq = itertools.count(1)
        # Dapper-style client-span sampling: record the call:<method>
        # round-trip span (and its latency exemplar) for every Nth call.
        self._span_every = max(
            1, int(getattr(get_config(), "trace_client_span_every", 8))
        )
        # Call-frame templates (wire-size fast path): the first call of a
        # given (method, group) shape ships its full spec and registers
        # it under a small id; subsequent calls ship ~60-byte frames of
        # (template id, task id, args) — the per-call TaskSpec pickle
        # (~650 B, ~15 us each way) dominates trivial-call frames.
        self._templates: Dict[tuple, int] = {}
        self._template_seq = itertools.count(1)
        self.calls = 0
        threading.Thread(
            target=self._reader, name="ray_tpu-direct-reader", daemon=True
        ).start()

    def submit(self, spec: TaskSpec):
        """Buffer the call frame; flush() ships the burst as one frame.
        get()/wait()/fence() and the runtime's periodic flusher are the
        flush points — a sync caller flushes on its own get, a pipelined
        burst rides one socket write."""
        if not self.alive:
            raise ConnectionError("direct channel closed")
        # Backpressure: a channel death replays every unanswered call
        # over the NM route, relying on the worker's replay-dedup cache
        # to keep methods exactly-once — so unanswered calls must never
        # outgrow what that cache can remember. The pending table is the
        # single authority (replay needs it anyway); its size read is
        # one atomic call. The wait parks on the TABLE's condition
        # (native: GIL released in the extension; the reader's GIL-free
        # pops signal it) — never while holding plock. Submitters are
        # serialized per channel (the actor state lock), so one blocked
        # waiter here is the only writer.
        full = len(self.table) >= DIRECT_MAX_UNANSWERED
        if full:
            self.flush()  # the calls we wait on must reach the worker
            while (len(self.table) >= DIRECT_MAX_UNANSWERED
                   and not self.failed and self.alive):
                self.table.wait_below(DIRECT_MAX_UNANSWERED,
                                      DIRECT_BACKPRESSURE_WAIT_S)
        oid = spec.return_ids()[0]
        entry = _DirectResult(readable=self.store_readable, chan=self)
        dep_ids = list(spec.pinned_ids())
        # Templatable = everything per-call is carried by the compact
        # frame (task id, args, nested refs). Tracing submit-spans needs
        # the real trace ctx, so templating is off under that flag.
        key = (spec.method_name, spec.concurrency_group)
        frame: Optional[Dict[str, Any]]
        tmpl: Optional[int] = None
        if _TRACE_SUBMITS or spec.streaming:
            frame = {"spec": spec, "function_blob": None}
        else:
            tid = self._templates.get(key)
            if tid is None:
                tid = next(self._template_seq)
                self._templates[key] = tid
                frame = {"spec": spec, "function_blob": None,
                         "tmpl_reg": tid}
            elif self.native:
                # Compact frame on the native codec: encoded (seq and
                # all) under plock below, straight to bytes — no dict,
                # no pickle.
                frame = None
                tmpl = tid
            else:
                frame = {"t": tid, "i": spec.task_id.binary()}
                if spec.args or spec.kwargs:
                    frame["a"] = (spec.args, spec.kwargs)
                if spec.nested_refs:
                    frame["n"] = spec.nested_refs
                if spec.deadline_ts:
                    # Per-call deadline must ride the compact frame too:
                    # the worker's template copy carries the FIRST
                    # call's value, not this one's.
                    frame["d"] = spec.deadline_ts
                if spec.trace_ctx is not None:
                    # Trace context likewise: the template copy carries
                    # the FIRST call's ctx — without this, the compact
                    # dialect severs the proxy→replica→nested tree.
                    frame["tc"] = spec.trace_ctx
        with self.plock:
            if self.failed:
                raise ConnectionError("direct channel failed")
            seq = next(self._seq)
            out: Any
            if frame is None:
                # Trace context rides the native call frame only on
                # channels that negotiated codec v2+; a v1 peer gets
                # byte-identical v1 frames (traceless) instead.
                trace = (spec.trace_ctx
                         if self.npv >= frame_pump.TRACE_MIN_VER
                         else None)
                try:
                    out = frame_pump.encode_call(
                        tmpl, spec.task_id.binary(), seq,
                        spec.deadline_ts or 0.0, spec.args, spec.kwargs,
                        spec.nested_refs, trace,
                    )
                except Exception:
                    frame_pump.count_fallback("codec_error")
                    out = None
                if out is None:
                    # Unencodable shape: this one frame rides pickle.
                    out = {"t": tmpl, "i": spec.task_id.binary(),
                           "q": seq}
                    if spec.args or spec.kwargs:
                        out["a"] = (spec.args, spec.kwargs)
                    if spec.nested_refs:
                        out["n"] = spec.nested_refs
                    if spec.deadline_ts:
                        out["d"] = spec.deadline_ts
                    if spec.trace_ctx is not None:
                        out["tc"] = spec.trace_ctx
            else:
                frame["q"] = seq
                out = frame
            tidb = spec.task_id.binary()
            self._calls[tidb] = _PendingCall(
                oid, entry, dep_ids, spec, time.monotonic(), seq
            )
            if spec.streaming:
                stream = DirectStream(self.remote, self.store_readable,
                                      self._release_item)
                self._streams[tidb] = stream
                self.rt._direct_streams[spec.task_id] = stream
            self.table.add(tidb, seq)
            self.out_buf.append(out)
            self.calls += 1
        self.rt._direct_waiters_put(oid, entry)
        # Return-slot + arg-pin registration with the caller's NM:
        # buffered/coalesced (see the runtime's _direct_on_reg hook);
        # applied before this call's completion post and before any
        # ref-delta flush.
        self.rt._direct_on_reg(spec)
        if spec.streaming:
            # Its consumer waits on the stream, not in a get() that
            # would flush: the call leaves now. A send that fails is the
            # channel's death: the reader's failure path has the call.
            try:
                self.flush()
            except Exception:
                try:
                    self.conn.close()
                except Exception:  # rtlint: disable=swallowed-failure
                    pass
        else:
            self.rt._mark_chan_dirty(self)

    def flush(self, _trailer: Optional[Dict[str, Any]] = None):
        with self._flush_lock:
            with self.plock:
                buf = self.out_buf
                self.out_buf = []
            if buf:
                # Chaos plane: sever the transport like a real network
                # fault — the send below fails, the reader dies, and
                # the failure path replays every unanswered call over
                # the NM route exactly-once (worker-side task-id dedup).
                try:
                    delay = faults.fire(faults.DIRECT_CHANNEL_IO,
                                        actor=self.actor_id.hex()[:8])
                    if delay:
                        time.sleep(delay)
                except faults.InjectedFault:
                    try:
                        self.conn.close()
                    except Exception:
                        pass
            if self.native:
                # Native pump: every buffered frame (codec bytes and the
                # occasional pickled dict) ships as its own message, the
                # whole burst coalesced into one writev. The worker's
                # seq queue reconstitutes ordering; reply batching keys
                # off its read-ahead buffer instead of batch framing.
                if buf or _trailer is not None:
                    payloads = [
                        f if type(f) is bytes
                        else dumps_msg({"type": "execute", **f})
                        for f in buf
                    ]
                    if _trailer is not None:
                        if _trailer.get("type") == "fence":
                            payloads.append(frame_pump.encode_fence(
                                _trailer["msg_id"]))
                        else:
                            payloads.append(dumps_msg(_trailer))
                    self.conn.send_payloads(payloads)
                return
            if buf:
                msg = (
                    {"type": "execute", **buf[0]} if len(buf) == 1
                    else {"type": "execute_batch", "items": buf}
                )
                self.conn.send(msg)
            if _trailer is not None:
                self.conn.send(_trailer)

    def fence(self, timeout: float = 30.0) -> bool:
        """Ack'd once every earlier frame on this connection has been
        EXECUTED at the worker — lets a control-plane-routed call be
        ordered after direct ones. The fence frame rides the flush lock
        as a trailer, so it goes out strictly after every frame buffered
        (or mid-send in a concurrent flush) before it. A False return
        means the actor stayed busy past the deadline; the caller
        proceeds best-effort (the alternative is blocking the submitter
        indefinitely)."""
        ev = threading.Event()
        mid = next(self._fence_seq)
        self._fences[mid] = ev
        self.flush(_trailer={"type": "fence", "msg_id": mid})
        ok = ev.wait(timeout)
        if not ok:
            self._fences.pop(mid, None)
        if self.failed or not self.alive:
            # The reader sets every fence event when the channel dies, so
            # a True wait can mean "channel died", not "frames executed".
            # Raise so the caller parks on the failure replays (drained)
            # instead of letting its NM-routed call overtake them.
            raise ConnectionError("direct channel died during fence")
        return ok

    def _on_reply(self, msg, popped: bool = False):
        """Apply one completion. ``popped=True`` on the burst path: the
        pump already removed the entry from the pending table (GIL-free,
        backpressure signalled) before Python was entered; only the
        rich-state pop and the waiter wakeup remain."""
        tidb = msg["task_id"].binary()
        if not popped:
            self.table.pop(tidb)
        call = self._calls.pop(tidb, None)
        if call is None:
            return
        if self.remote:
            # The bytes live in the actor node's store: non-inline result
            # locations become RemoteLocation entries here, resolved over
            # the transfer plane. held=True — the worker's NM took a hold
            # for this caller; local GC releases it via free_object.
            from .object_store import RemoteLocation

            msg["results"] = [
                (roid,
                 loc if isinstance(loc, InlineLocation)
                 else RemoteLocation(self.node_hex,
                                     getattr(loc, "size", 0), held=True))
                for roid, loc in msg.get("results", ())
            ]
        # Wake the waiter FIRST (on one core every microsecond before the
        # set() is added to the caller's round trip), then register the
        # results with the control plane: other consumers and the
        # location directory stay consistent a beat later.
        entry = call.entry
        entry.payload = msg
        entry.event.set()
        self.rt._direct_waiters.mark_resolved(call.oid.binary())
        if self._streams:
            stream = self._streams.pop(tidb, None)
            if stream is not None:
                stream.end(stream.DONE)
        dur = time.monotonic() - call.t0
        ctx = getattr(call.spec, "trace_ctx", None)
        if ctx is not None and call.seq % self._span_every == 0:
            # Sampled client-side round-trip span + metric exemplar: the
            # queue-wait/execution split lives in the worker's spans;
            # this one bounds the whole submit→reply window and links
            # the latency histogram bucket to a retrievable trace id.
            _CALL_SECONDS_DIRECT.observe(dur, exemplar=ctx[0])
            try:
                from .timeline import record_span

                end = time.time()
                record_span(
                    f"call:{call.spec.method_name or 'task'}",
                    end - dur, end, parent=(ctx[0], ctx[1]),
                )
            # Observability must never fail the call it observes.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
        else:
            _CALL_SECONDS_DIRECT.observe(dur)
        self.rt._direct_on_done(msg, call.dep_ids, self)

    def gil_probe(self) -> Dict[str, int]:
        """Interpreter entries the reader made vs frames received —
        the ISSUE 12 probe run_actor_bench.py records per phase."""
        out = {"py_entries": self.py_entries, "frames_in": self.frames_rx}
        try:
            out["frames_in"] = self.conn.pump_io_stats()["frames_in"]
        except Exception:
            pass
        try:
            out["pending_table"] = self.table.stats()
        except Exception:
            pass
        return out

    def _dispatch(self, msg):
        mtype = msg.get("type")
        if mtype == "task_done":
            self._on_reply(msg)
            self.rt._direct_flush_side()
        elif mtype == "task_done_batch":
            for item in msg["items"]:
                self._on_reply(item)
            self.rt._direct_flush_side()
        elif mtype == "stream_item":
            self._on_stream_item(msg)
        elif mtype == "fence_ack":
            ev = self._fences.pop(msg.get("msg_id"), None)
            if ev is not None:
                ev.set()

    def _on_stream_item(self, frame):
        """One streamed item of a call of this channel: to its stream
        (core/streaming.py, the direct route's consumer)."""
        if self.remote:
            loc = frame["loc"]
            if not isinstance(loc, InlineLocation):
                # As a remote direct result: the bytes live in the
                # actor node's store, under a hold its NM took for this
                # caller.
                from .object_store import RemoteLocation

                frame["loc"] = RemoteLocation(
                    self.node_hex, getattr(loc, "size", 0), held=True)
        stream = self._streams.get(frame["i"])
        if stream is not None:
            stream.put(frame)
        else:
            self._release_item(frame)

    def _release_item(self, frame):
        """An item nobody will take (its consumer is gone): drop the
        pin it came with."""
        release_direct_item(self.rt, frame, self.remote)

    def _reader(self):
        from .protocol import ConnectionClosed, loads_msg

        # Burst mode (the GIL-free dispatch core, ISSUE 12): the pump
        # reads a whole arrived-together burst and applies its native
        # completions to the pending table BEFORE re-entering Python —
        # one interpreter entry per burst, waiter wakeups delivered as
        # one coalesced batch. Needs the native channel AND the native
        # table; any non-connection error drops this channel to the
        # per-frame mirror path (counted), never to a wrong answer.
        use_burst = bool(self.native
                         and getattr(self.table, "native", False)
                         and hasattr(self.conn, "recv_burst"))
        try:
            while True:
                if use_burst:
                    try:
                        dones, others = self.conn.recv_burst(self.table)
                    except (ConnectionClosed, OSError, EOFError):
                        raise
                    except Exception:
                        # A native error here may have consumed frames
                        # whose completions were already popped from the
                        # pending table — continuing on this channel
                        # would strand them. Fail the channel instead:
                        # the failure path sweeps _calls (not just the
                        # table) and replays everything unanswered over
                        # the NM route exactly-once.
                        frame_pump.count_fallback("pump_error")
                        raise
                    self.py_entries += 1
                    self.frames_rx += len(others) + (1 if dones else 0)
                    if others and dones:
                        # The burst's completions come apart from its
                        # other frames: a stream's items, which were
                        # ahead of its completion on the socket, go to
                        # it first.
                        rest = []
                        for payload in others:
                            msg = loads_msg(payload)
                            if msg.get("type") == "stream_item":
                                self._on_stream_item(msg)
                            else:
                                rest.append(msg)
                    else:
                        rest = [loads_msg(payload) for payload in others]
                    for item in dones:
                        self._on_reply(item, popped=True)
                    for msg in rest:
                        self._dispatch(msg)
                    if dones:
                        self.rt._direct_flush_side()
                else:
                    msg = self.conn.recv()
                    self.py_entries += 1
                    self.frames_rx += 1
                    self._dispatch(msg)
        except (ConnectionClosed, OSError, EOFError):
            pass
        except Exception:
            pass
        self.alive = False
        for ev in list(self._fences.values()):
            ev.set()
        self._fences.clear()
        self.rt._direct_channel_failed(self)

    def close(self):
        self.closed_by_us = True
        self.alive = False
        try:
            self.conn.close()
        except Exception:
            pass


class DriverRuntime(BaseRuntime):
    """Runtime embedded in the driver process; owns the NodeManager."""

    _direct_capable = True

    def __init__(self, node_manager, job_id: JobID):
        self._nm = node_manager
        self._submit_lock = threading.Lock()
        self._submit_buf: List[TaskSpec] = []
        self._submit_waking = False
        # Coalesced NM bookkeeping for direct calls: submit/reply posts
        # buffer here and drain in ONE loop callback per burst (three
        # call_soon_threadsafe wakeups per call would cost more than the
        # direct channel saves on a contended host).
        self._dpost_lock = threading.Lock()
        self._dpost_buf: List[tuple] = []
        self._dpost_waking = False
        super().__init__(
            job_id=job_id,
            node_id=node_manager.node_id,
            worker_id=WorkerID.nil(),
        )
        # Membership fence hook: a node_fenced decision tears down this
        # runtime's direct channels to the fenced node (workers/clients
        # get forwarded node_fenced frames instead).
        node_manager.on_node_fenced_runtime = self.fence_node

    # ---- direct actor transport hooks (in-process NM: loop posts) ---------

    def _direct_resolve(self, actor_id: ActorID, timeout: float):
        return self._nm.call_sync(
            self._nm.get_actor_direct(actor_id, timeout=timeout),
            timeout=timeout + 10.0,
        )

    def _direct_on_reg(self, spec: TaskSpec):
        # Buffered without a loop wakeup; applied before this call's
        # reply post and before any ref-delta flush (see _dpost).
        self._dpost(("reg", spec), wake=False)

    def _direct_on_done(self, msg, dep_ids, chan):
        self._dpost(("done", msg["results"], dep_ids or [],
                     msg.get("nested")))

    def _direct_on_replay(self, dep_ids):
        # Unpin-only post: empty results, no nested — releases the
        # direct registration's arg pins before the NM resubmit re-pins.
        self._dpost(("done", [], dep_ids, None))

    def _direct_on_item(self, oid, frame, remote):
        # Buffered like a "reg": every way a ref leaves this process
        # (a submit, a delta flush, a location lookup) drains first.
        self._dpost(("item", oid, frame if remote else None), wake=remote)

    def _dpost(self, item: tuple, wake: bool = True):
        """Queue NM bookkeeping. wake=False defers the drain to the next
        reply/delta-flush (safe for "reg" items: the buffer is FIFO so a
        reg always applies before its own call's "done", and
        _flush_deltas drains first so ref deltas never see a missing
        entry). wake=True schedules a COALESCED drain a couple of
        milliseconds out instead of draining immediately: a tight
        sync-call loop otherwise pays for the previous call's
        seal/unpin work (GIL-held on the NM loop) inside its own send
        path — measured ~100us per call on one core. Consumers in other
        processes see seals at most one coalesce window late."""
        with self._dpost_lock:
            self._dpost_buf.append(item)
            if not wake or self._dpost_waking:
                return
            self._dpost_waking = True
        self._nm._loop.call_soon_threadsafe(self._schedule_dpost_drain)

    _DPOST_COALESCE_S = 0.002

    def _schedule_dpost_drain(self):
        # On the loop: batch the burst behind a short timer; everything
        # posted inside the window drains in one pass.
        self._nm._loop.call_later(self._DPOST_COALESCE_S,
                                  self._drain_dposts)

    def _drain_dposts(self):
        with self._dpost_lock:
            items = self._dpost_buf
            self._dpost_buf = []
            self._dpost_waking = False
        nm = self._nm
        for item in items:
            kind = item[0]
            if kind == "reg":
                spec = item[1]
                for oid in spec.return_ids():
                    nm.directory.add(oid, InlineLocation(b""),
                                     initial_refs=0)
                for oid in spec.pinned_ids():
                    nm._pin_ref_bg(oid)
            elif kind == "item":
                _, oid, frame = item
                nm.directory.add(oid, InlineLocation(b""), initial_refs=0)
                if frame is not None:  # from another node
                    nm.directory.add_ref(oid)
                    nm._seal_object(oid, frame["loc"])
                    if frame.get("n"):
                        nm._register_nested(oid, frame["n"])
            else:  # "done"
                _, results, dep_ids, nested = item
                for roid, loc in results:
                    # The entry exists from the FIFO-earlier "reg" post;
                    # _seal_object swaps the placeholder for the real
                    # location and fires seal events.
                    nm._seal_object(roid, loc)
                for roid, inner in (nested or ()):
                    # Refs inside a direct-call return: pinned at THIS
                    # node (direct results are owned by the caller's NM).
                    nm._register_nested(roid, inner)
                for oid in dep_ids:
                    nm._remove_ref(oid, 1)

    def _flush_deltas(self, deltas: Dict[ObjectID, int]):
        async def _apply():
            # Direct-call registrations must land before ref deltas (a
            # deferred "reg" pins args/return slots the deltas refer to).
            self._drain_dposts()
            for oid, d in deltas.items():
                if d > 0:
                    # Stub-aware: a ref to an object owned by another
                    # node creates a borrow stub + owner registration.
                    self._nm._pin_ref_bg(oid, d)
                else:
                    self._nm._remove_ref(oid, -d)

        self._nm._call(_apply())

    def _post(self, coro):
        """Fire a coroutine onto the node manager's loop without blocking
        the driver thread (the submit/put hot path — reference analogue:
        CoreWorker's async SubmitTask, core_worker.cc:1931, which never
        round-trips to the raylet before returning the ObjectRef).
        Failures surface through the task/object state, not the call."""
        fut = self._nm._call(coro)
        fut.add_done_callback(_log_post_error)

    def _submit_spec(self, spec: TaskSpec):
        # Batch bursts of submits into ONE loop wake-up: each
        # call_soon_threadsafe writes the loop's self-pipe (a syscall that
        # dominates the submit path on small tasks), so a tight
        # `[f.remote() for _ in range(n)]` loop pays it once, not n times.
        with self._submit_lock:
            self._submit_buf.append(spec)
            wake = not self._submit_waking
            self._submit_waking = True
        if wake:
            self._nm._loop.call_soon_threadsafe(self._drain_submits)

    def _drain_submits(self):
        # Buffered direct-call registrations must land before these
        # submits: a spec depending on a direct result needs its return
        # slot in the directory to dep-wait instead of erroring.
        self._drain_dposts()
        with self._submit_lock:
            specs = self._submit_buf
            self._submit_buf = []
            self._submit_waking = False
        nm = self._nm
        for spec in specs:
            try:
                nm.submit_task_sync(spec)
            except Exception as e:  # pragma: no cover - diagnostics only
                import sys

                sys.stderr.write(
                    f"[ray_tpu] submit of {spec.name!r} failed: {e!r}\n"
                )

    def _get_locations(self, ids, timeout):
        # Flush ref deltas first so the NM sees this process's holds
        # (borrow-stub creation) before resolving locations.
        self.refs.flush()
        import asyncio

        async def _locate():
            # What this process registered and has not posted yet (a
            # streamed item's entry) is what the lookup may be for.
            self._drain_dposts()
            return await self._nm.get_locations(ids, timeout)

        try:
            return self._nm.call_sync(_locate())
        except asyncio.TimeoutError as e:
            # py<3.11: asyncio.TimeoutError is NOT builtin TimeoutError,
            # so normalize at the boundary — callers' `except
            # TimeoutError` (get()'s GetTimeoutError translation) must
            # see loop-side timeouts on every supported version.
            raise TimeoutError(str(e)) from e

    def _wait(self, ids, num_returns, timeout):
        return self._nm.call_sync(self._nm.wait_objects(ids, num_returns, timeout))

    def _wait_carrying(self, ids, timeout):
        return self._nm.call_sync(self._nm.wait_carrying(ids, timeout))

    def _register_put(self, oid: ObjectID, loc: Location,
                      nested: Optional[List[ObjectID]] = None):
        self._post(self._nm.put_object(oid, loc, refs=0, nested=nested))

    def _register_function_remote(self, function_id: str, blob: bytes):
        self._nm.call_sync(self._nm.register_function(function_id, blob))

    # Extra control-plane surface used by the public API.

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        self._nm.call_sync(self._nm.kill_actor(actor_id, no_restart))

    def cancel_task(self, task_id: TaskID, force: bool = False):
        self._nm.call_sync(self._nm.cancel_task(task_id, force))

    def get_named_actor_spec(self, name: str):
        return self._nm.call_sync(self._nm.get_named_actor(name))

    def kv_put(self, key: str, value: bytes, overwrite: bool = True) -> bool:
        return self._nm.kv_put(key, value, overwrite)

    def kv_get(self, key: str) -> Optional[bytes]:
        return self._nm.kv_get(key)

    def pubsub_op(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        return self._nm.pubsub_op(dict(msg))

    def kv_keys(self, prefix: str = "") -> List[str]:
        return self._nm.kv_keys(prefix)

    def kv_del(self, key: str) -> bool:
        return self._nm.kv_del(key)

    def stats(self) -> Dict[str, Any]:
        return self._nm.call_sync(self._nm.stats())

    def cluster_state(self) -> Dict[str, Any]:
        """Cluster-wide live-state tables (state API backing)."""
        return self._nm.call_sync(self._nm.cluster_state())

    def list_cluster_events(self, severity=None, source=None,
                            limit: int = 1000) -> Dict[str, Any]:
        """Head aggregator's structured event store (state API backing
        for list_cluster_events / `rtpu events`)."""
        return self._nm.call_sync(
            self._nm._events_list(severity=severity, source=source,
                                  limit=limit)
        )

    def timeseries_query(self, name: str = "", tags=None,
                         since: float = 0.0, limit: int = 0,
                         quantile: float = 0.0,
                         window: float = 60.0) -> Dict[str, Any]:
        """Head TSDB query (backing for /api/timeseries, `rtpu top`,
        `rtpu slo`, `rtpu rpc`). Empty name lists series names + store
        stats; quantile > 0 adds a head-derived histogram quantile."""
        return self._nm.call_sync(
            self._nm._timeseries_query(name=name, tags=tags,
                                       since=since, limit=limit,
                                       quantile=quantile, window=window)
        )

    def slo_status(self) -> Dict[str, Any]:
        """The SLO engine's latest per-deployment evaluation."""
        return self._nm.call_sync(self._nm._slo_status())

    def cluster_stacks(self, timeout: float = 5.0) -> Dict[str, Any]:
        """Cluster-wide stack dumps via the GCS ProfileService (backing
        for util/profiler.cluster_stacks / `rtpu stack`)."""
        return self._nm.call_sync(
            self._nm.cluster_stacks(timeout=timeout),
            timeout=timeout + 15.0,
        )

    def cluster_profile(self, seconds: float = 2.0,
                        hz: int = 100) -> Dict[str, Any]:
        """Cluster-wide sampling profile (backing for
        util/profiler.cluster_profile / `rtpu profile`)."""
        return self._nm.call_sync(
            self._nm.cluster_profile(seconds=seconds, hz=hz),
            timeout=min(float(seconds), 30.0) + 30.0,
        )

    def cluster_traces(self, reason: Optional[str] = None,
                       limit: int = 200) -> Dict[str, Any]:
        """Cluster-wide flight-recorder dump (backing for `rtpu trace` /
        dashboard /api/traces, via the GCS ProfileService fan-out)."""
        return self._nm.call_sync(
            self._nm.cluster_traces(reason=reason, limit=limit),
            timeout=30.0,
        )

    def cluster_objects(self, limit: int = 500) -> Dict[str, Any]:
        """Cluster-wide object census (backing for `rtpu objects` /
        `rtpu memory` / dashboard /api/objects, via the GCS
        ObjectService fan-out)."""
        return self._nm.call_sync(
            self._nm.cluster_objects(limit=limit),
            timeout=30.0,
        )

    def cluster_resources(self) -> Dict[str, float]:
        views = self.nodes()
        if len(views) <= 1:
            return self._nm.node_resources.total.to_dict()
        total: Dict[str, float] = {}
        for v in views:
            if v.get("state") != "alive":
                continue
            for k, amt in v["resources_total"].items():
                total[k] = total.get(k, 0.0) + amt
        return total

    def available_resources(self) -> Dict[str, float]:
        views = self.nodes()
        if len(views) <= 1:
            return self._nm.node_resources.available.to_dict()
        avail: Dict[str, float] = {}
        for v in views:
            if v.get("state") != "alive":
                continue
            src = (
                self._nm.node_resources.available.to_dict()
                if v["node_id"] == self._nm.node_id.hex()
                else v["resources_available"]
            )
            for k, amt in src.items():
                avail[k] = avail.get(k, 0.0) + amt
        return avail

    def nodes(self):
        return self._nm.call_sync(self._nm.cluster_nodes())

    # Placement groups (ref analogue: the GCS PG RPCs the driver issues).

    def pg_create(self, pg_id, bundles, strategy, name="",
                  label_selectors=None):
        self._nm.call_sync(
            self._nm.pg_op(
                {"op": "create", "pg_id": pg_id, "bundles": bundles,
                 "strategy": strategy, "name": name,
                 "label_selectors": label_selectors}
            )
        )

    def pg_wait(self, pg_id, timeout) -> bool:
        return self._nm.call_sync(
            self._nm.pg_op({"op": "wait", "pg_id": pg_id, "timeout": timeout}),
            timeout=timeout + 15.0,
        )["ready"]

    def pg_remove(self, pg_id):
        self._nm.call_sync(self._nm.pg_op({"op": "remove", "pg_id": pg_id}))

    def pg_table(self):
        return self._nm.call_sync(self._nm.pg_op({"op": "table"}))["table"]

    def shutdown(self):
        super().shutdown()  # closes direct channels
        self.refs.flush()
        self._nm.shutdown()
        self.store.shutdown(unlink_created=True)


class WorkerRuntime(BaseRuntime):
    """Runtime inside a worker process; all control-plane calls go over the
    node socket (duplex: replies are matched by msg_id by the reader thread,
    which runs in worker_main). Actor calls ride the direct plane: the
    runtime resolves the actor's endpoint through its NM once, then
    speaks straight to the actor's worker — this is how serve replicas
    and nested actor calls skip the per-call NM hops."""

    _direct_capable = True

    def __init__(self, conn, job_id: JobID, node_id: NodeID, worker_id: WorkerID):
        self._conn = conn
        self._msg_counter = itertools.count(1)
        self._pending: Dict[int, _PendingReply] = {}
        self._pending_lock = threading.Lock()
        # Direct-plane NM side-bookkeeping, coalesced into ONE
        # ``direct_side`` frame per burst (mirror of the driver's dpost
        # buffer; set up BEFORE super().__init__ starts the flusher).
        self._direct_side_lock = threading.Lock()
        self._direct_regs: List[Tuple[list, list]] = []
        self._direct_seals: List[tuple] = []
        self._direct_nested: List[tuple] = []
        self._direct_unpins: Dict[ObjectID, int] = {}
        self._direct_side_first = 0.0
        super().__init__(job_id=job_id, node_id=node_id, worker_id=worker_id)

    # ---- direct actor transport hooks (over the node socket) ---------------

    _DIRECT_SIDE_MAX = 32
    _DIRECT_SIDE_AGE_S = 0.002

    def _direct_stamp_owner(self, spec: TaskSpec):
        spec.owner_id = self.worker_id

    def _direct_resolve(self, actor_id: ActorID, timeout: float):
        reply = self.request(
            {"type": "get_actor_direct", "actor_id": actor_id,
             "timeout": timeout},
            timeout=timeout + 15.0,
        )
        return reply.get("direct")

    def _direct_side_mark_first(self):
        # Caller holds _direct_side_lock.
        if not (self._direct_regs or self._direct_seals
                or self._direct_nested or self._direct_unpins):
            self._direct_side_first = time.monotonic()

    def _direct_on_reg(self, spec: TaskSpec):
        with self._direct_side_lock:
            self._direct_side_mark_first()
            self._direct_regs.append(
                (list(spec.return_ids()), list(spec.pinned_ids()))
            )

    def _direct_on_done(self, msg, dep_ids, chan):
        with self._direct_side_lock:
            self._direct_side_mark_first()
            if chan.remote:
                # The actor lives on another node: register the results
                # here as RemoteLocation seals (already rewritten by the
                # channel) so local consumers resolve and pull them.
                self._direct_seals.extend(msg.get("results", ()))
            for item in (msg.get("nested") or ()):
                self._direct_nested.append(item)
            for oid in dep_ids:
                self._direct_unpins[oid] = self._direct_unpins.get(oid, 0) + 1

    def _direct_on_replay(self, dep_ids):
        with self._direct_side_lock:
            self._direct_side_mark_first()
            for oid in dep_ids:
                self._direct_unpins[oid] = self._direct_unpins.get(oid, 0) + 1
        self._direct_flush_side(force=True)

    def _direct_on_item(self, oid, frame, remote):
        # Leaves with the next ``direct_side`` frame: before any ref
        # delta, submit or request of this process, on the one socket.
        with self._direct_side_lock:
            self._direct_side_mark_first()
            self._direct_regs.append(([oid], [oid] if remote else []))
            if remote:
                self._direct_seals.append((oid, frame["loc"]))
                if frame.get("n"):
                    self._direct_nested.append((oid, frame["n"]))

    def _direct_flush_side(self, force: bool = False):
        with self._direct_side_lock:
            n = (len(self._direct_regs) + len(self._direct_seals)
                 + len(self._direct_nested) + len(self._direct_unpins))
            if not n:
                return
            if (not force and n < self._DIRECT_SIDE_MAX
                    and time.monotonic() - self._direct_side_first
                    < self._DIRECT_SIDE_AGE_S):
                return
            regs, self._direct_regs = self._direct_regs, []
            seals, self._direct_seals = self._direct_seals, []
            nested, self._direct_nested = self._direct_nested, []
            unpins, self._direct_unpins = self._direct_unpins, {}
        msg: Dict[str, Any] = {"type": "direct_side"}
        if regs:
            msg["returns"] = [oid for ret, _ in regs for oid in ret]
            pins = [oid for _, p in regs for oid in p]
            if pins:
                msg["pins"] = pins
        if seals:
            msg["seals"] = seals
        if nested:
            msg["nested"] = nested
        if unpins:
            msg["unpin"] = unpins
        try:
            self._conn.send(msg)
        except Exception:
            pass

    # Called by worker_main's reader thread.
    def handle_reply(self, msg: Dict[str, Any]):
        with self._pending_lock:
            pending = self._pending.pop(msg.get("msg_id"), None)
        if pending is not None:
            pending.payload = msg
            pending.event.set()

    # Set by worker_main: flushes buffered task_done frames before any
    # request that may wait on the node manager (a nested get could
    # otherwise block on a seal sitting in our own outbound buffer).
    before_block = None

    def request(self, msg: Dict[str, Any], timeout: Optional[float] = None):
        if self.before_block is not None:
            self.before_block()
        # Direct-call registrations must reach the NM before any request
        # that may resolve against them (a dep lookup racing an unsent
        # return-slot placeholder would miss and go to object location).
        self._direct_flush_side(force=True)
        msg_id = next(self._msg_counter)
        msg["msg_id"] = msg_id
        pending = _PendingReply()
        with self._pending_lock:
            self._pending[msg_id] = pending
        self._conn.send(msg)
        if not pending.event.wait(timeout if timeout is None else timeout + 5):
            with self._pending_lock:
                self._pending.pop(msg_id, None)
            raise TimeoutError("no reply from node manager")
        return pending.payload

    def _flush_deltas(self, deltas: Dict[ObjectID, int]):
        # Direct-call registrations land first (same discipline as the
        # driver's dpost drain): the deltas may refer to return slots or
        # arg pins a buffered reg creates.
        self._direct_flush_side(force=True)
        adds = [oid for oid, d in deltas.items() for _ in range(max(0, d))]
        removes = {oid: -d for oid, d in deltas.items() if d < 0}
        if adds:
            self._conn.send({"type": "add_refs", "object_ids": adds})
        if removes:
            self._conn.send({"type": "remove_refs", "counts": removes})

    def _submit_spec(self, spec: TaskSpec):
        spec.owner_id = self.worker_id
        # FIFO discipline on the node socket: buffered direct-call
        # registrations land before this submit, so a spec depending on
        # a direct result dep-waits on its placeholder instead of
        # falling into the object-locate path.
        self._direct_flush_side(force=True)
        self._conn.send({"type": "submit", "spec": spec})

    def _get_locations(self, ids, timeout):
        # Ref deltas must land before the lookup: the NM's borrow logic
        # relies on the holder's +1 arriving ahead of the blocking read
        # (frames on this connection are processed in order).
        self.refs.flush()
        self._conn.send({"type": "blocked"})
        try:
            reply = self.request(
                {"type": "get_locations", "object_ids": ids, "timeout": timeout},
                timeout=timeout,
            )
        finally:
            try:
                self._conn.send({"type": "unblocked"})
            except Exception:
                pass
        if reply.get("timeout"):
            raise TimeoutError()
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply["locations"]

    def _wait(self, ids, num_returns, timeout):
        self._conn.send({"type": "blocked"})
        try:
            reply = self.request(
                {
                    "type": "wait",
                    "object_ids": ids,
                    "num_returns": num_returns,
                    "timeout": timeout,
                },
                timeout=timeout,
            )
        finally:
            try:
                self._conn.send({"type": "unblocked"})
            except Exception:
                pass
        return reply["ready"]

    def _wait_carrying(self, ids, timeout):
        # No ``blocked`` / ``unblocked`` frame round it: the node
        # manager keeps that book itself, and only if the wait parks.
        reply = self.request(
            {"type": "wait", "object_ids": ids, "num_returns": 1,
             "timeout": timeout, "carry": True},
            timeout=timeout,
        )
        return reply["ready"], reply["locations"], reply["parked"]

    def _register_put(self, oid: ObjectID, loc: Location,
                      nested: Optional[List[ObjectID]] = None):
        msg = {"type": "put", "object_id": oid, "loc": loc, "refs": 0}
        if nested:
            msg["nested"] = nested
        self._conn.send(msg)

    def _register_function_remote(self, function_id: str, blob: bytes):
        self._conn.send(
            {"type": "register_function", "function_id": function_id, "blob": blob}
        )

    def kv_put(self, key: str, value: bytes, overwrite: bool = True) -> bool:
        return self.request({"type": "kv", "op": "put", "key": key,
                             "value": value, "overwrite": overwrite})["added"]

    def kv_get(self, key: str) -> Optional[bytes]:
        return self.request({"type": "kv", "op": "get", "key": key})["value"]

    def kv_keys(self, prefix: str = "") -> List[str]:
        return self.request({"type": "kv", "op": "keys",
                             "prefix": prefix})["keys"]

    def kv_del(self, key: str) -> bool:
        return self.request({"type": "kv", "op": "del",
                             "key": key})["deleted"]

    def pubsub_op(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        timeout = msg.get("timeout", 30.0) + 15.0
        reply = self.request({**msg, "type": "pubsub"}, timeout=timeout)
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply

    def get_named_actor_spec(self, name: str):
        reply = self.request({"type": "get_named_actor", "name": name})
        return reply["spec"]

    def cluster_state(self) -> Dict[str, Any]:
        return self.request({"type": "state"}, timeout=30.0)["state"]

    def list_cluster_events(self, severity=None, source=None,
                            limit: int = 1000) -> Dict[str, Any]:
        reply = self.request(
            {"type": "events", "severity": severity, "source": source,
             "limit": limit},
            timeout=30.0,
        )
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return {"events": reply["events"], "total": reply["total"],
                "dropped": reply["dropped"]}

    def timeseries_query(self, name: str = "", tags=None,
                         since: float = 0.0, limit: int = 0,
                         quantile: float = 0.0,
                         window: float = 60.0) -> Dict[str, Any]:
        reply = self.request(
            {"type": "timeseries", "name": name, "tags": tags,
             "since": since, "limit": limit, "quantile": quantile,
             "window": window},
            timeout=30.0,
        )
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        out = {"series": reply["series"], "names": reply["names"],
               "stats": reply["stats"]}
        if reply.get("derived") is not None:
            out["derived"] = reply["derived"]
        return out

    def slo_status(self) -> Dict[str, Any]:
        reply = self.request({"type": "slo"}, timeout=30.0)
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return {"deployments": reply["deployments"], "ts": reply["ts"]}

    def cluster_stacks(self, timeout: float = 5.0) -> Dict[str, Any]:
        reply = self.request(
            {"type": "profile", "op": "stacks", "timeout": timeout},
            timeout=timeout + 15.0,
        )
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply["result"]

    def cluster_traces(self, reason: Optional[str] = None,
                       limit: int = 200) -> Dict[str, Any]:
        reply = self.request(
            {"type": "profile", "op": "traces", "reason": reason or "",
             "limit": limit},
            timeout=45.0,
        )
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply["result"]

    def cluster_profile(self, seconds: float = 2.0,
                        hz: int = 100) -> Dict[str, Any]:
        reply = self.request(
            {"type": "profile", "op": "run", "seconds": seconds,
             "hz": hz},
            timeout=min(float(seconds), 30.0) + 30.0,
        )
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply["result"]

    def cluster_objects(self, limit: int = 500) -> Dict[str, Any]:
        reply = self.request(
            {"type": "profile", "op": "objects", "limit": limit},
            timeout=45.0,
        )
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply["result"]

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        self._conn.send({"type": "kill_actor", "actor_id": actor_id,
                         "no_restart": no_restart})

    def cancel_task(self, task_id: TaskID, force: bool = False):
        self._conn.send({"type": "cancel_task", "task_id": task_id, "force": force})

    # Placement groups proxy through the node socket.

    def _pg_request(self, msg, timeout=None):
        msg["type"] = "pg"
        reply = self.request(msg, timeout)
        if reply.get("error"):
            raise RuntimeError(reply["error"])
        return reply

    def pg_create(self, pg_id, bundles, strategy, name="",
                  label_selectors=None):
        self._pg_request(
            {"op": "create", "pg_id": pg_id, "bundles": bundles,
             "strategy": strategy, "name": name,
             "label_selectors": label_selectors}
        )

    def pg_wait(self, pg_id, timeout) -> bool:
        return self._pg_request(
            {"op": "wait", "pg_id": pg_id, "timeout": timeout},
            timeout=timeout + 15.0,
        )["ready"]

    def pg_remove(self, pg_id):
        self._pg_request({"op": "remove", "pg_id": pg_id})

    def pg_table(self):
        return self._pg_request({"op": "table"})["table"]


class _PendingReply:
    __slots__ = ("event", "payload")

    def __init__(self):
        self.event = threading.Event()
        self.payload = None
