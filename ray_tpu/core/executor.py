"""Task execution: resolve args, run the function, package results.

Ref analogue: the execute_task path in python/ray/_raylet.pyx:1644 — resolve
top-level ObjectRef args, look up the function by descriptor, invoke, and
store returns (small inline, large to the shared-memory store).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from .config import get_config
from .exceptions import TaskError
from .ids import ObjectID
from .object_store import InlineLocation, Location
from .serialization import deserialize, serialize, serialize_with_refs
from .task_spec import RefArg, TaskSpec, TaskType, ValueArg


def pack_value(value) -> bytes:
    return serialize(value).to_bytes()


def unpack_value(data: bytes):
    return deserialize(memoryview(data))


def resolve_args(spec: TaskSpec, fetch: Callable[[List[ObjectID]], List[Any]]):
    """Materialize the call's positional/keyword arguments. ``fetch`` returns
    deserialized values for a list of ObjectIDs (blocking until available)."""
    ref_ids = [a.object_id for a in spec.args if isinstance(a, RefArg)]
    ref_ids += [a.object_id for a in spec.kwargs.values() if isinstance(a, RefArg)]
    values = fetch(ref_ids) if ref_ids else []
    by_id = dict(zip(ref_ids, values))
    args = [
        by_id[a.object_id] if isinstance(a, RefArg) else unpack_value(a.data)
        for a in spec.args
    ]
    kwargs = {
        k: by_id[a.object_id] if isinstance(a, RefArg) else unpack_value(a.data)
        for k, a in spec.kwargs.items()
    }
    return args, kwargs


def package_results(
    spec: TaskSpec, value, store_large: Callable[[ObjectID, Any], Location]
) -> Tuple[List[Tuple[ObjectID, Location]], List[Tuple[ObjectID, list]]]:
    """Split the return value into the task's return slots and produce
    (ObjectID, Location) pairs plus, per return, any ObjectRefs found
    serialized INSIDE it (the containment pins the control plane must
    hold for the return's lifetime). ``store_large`` writes one
    serialized object to shm and returns its location."""
    return_ids = spec.return_ids()
    if spec.num_returns == 1:
        values = [value]
    else:
        if not isinstance(value, (tuple, list)) or len(value) != spec.num_returns:
            raise ValueError(
                f"task {spec.name!r} declared num_returns={spec.num_returns} but "
                f"returned {type(value).__name__} of length "
                f"{len(value) if hasattr(value, '__len__') else 'n/a'}"
            )
        values = list(value)
    cfg = get_config()
    out: List[Tuple[ObjectID, Location]] = []
    nested_out: List[Tuple[ObjectID, list]] = []
    for oid, v in zip(return_ids, values):
        sobj, nested = serialize_with_refs(v)
        if nested:
            nested_out.append((oid, nested))
        if sobj.total_size <= cfg.max_inline_object_size:
            out.append((oid, InlineLocation(sobj.to_bytes())))
        else:
            out.append((oid, store_large(oid, sobj)))
    return out, nested_out


class ActorContainer:
    """Holds the live actor instance in an actor worker.

    ASYNC ACTORS (ref analogue: async actors running on a per-actor
    asyncio loop, core_worker fiber/asyncio execution): a class with any
    ``async def`` method gets a dedicated event-loop thread; coroutine
    results run there — concurrent in-flight calls interleave on the
    loop (the caller-side thread pool just awaits), and instance state
    stays loop-confined for async methods."""

    def __init__(self):
        self.instance = None
        self.cls = None
        self.is_async = False
        self._loop = None

    @staticmethod
    def class_is_async(cls) -> bool:
        import inspect

        return any(
            inspect.iscoroutinefunction(v)
            for v in vars(cls).values()
        )

    def create(self, cls, args, kwargs):
        self.cls = cls
        self.is_async = self.class_is_async(cls)
        if self.is_async:
            import asyncio
            import threading

            self._loop = asyncio.new_event_loop()
            t = threading.Thread(
                target=self._loop.run_forever,
                name="ray_tpu-actor-asyncio", daemon=True,
            )
            t.start()
            # Lag watchdog: a CPU-bound await-free method on this loop
            # stalls every other concurrent call of the async actor.
            from ..util import loop_monitor

            loop_monitor.attach("actor_asyncio", self._loop)
        self.instance = cls(*args, **kwargs)

    def call(self, method_name: str, args, kwargs):
        if method_name == "__rtpu_ping__":
            # Built-in liveness probe usable on any actor class (SPMD group
            # health checks; ref analogue: the __ray_ready__ system method).
            # Method calls queue behind the creation task, so a None
            # instance here means the constructor FAILED — report that
            # rather than answering a healthy "ok" (gang barriers rely on
            # this to reject a gang whose members never constructed).
            if self.instance is None:
                raise RuntimeError(
                    "actor instance not created (constructor failed)"
                )
            return "ok"
        if self.instance is None:
            raise RuntimeError("actor instance not created")
        method = getattr(self.instance, method_name)
        result = method(*args, **kwargs)
        if self._loop is not None:
            import asyncio
            import inspect

            if inspect.iscoroutine(result):
                # Run on the actor's loop; this (pool) thread just waits,
                # so other in-flight coroutines interleave.
                return asyncio.run_coroutine_threadsafe(
                    result, self._loop
                ).result()
        return result


def execute_task(
    spec: TaskSpec,
    load_function: Callable[[str], Any],
    fetch: Callable[[List[ObjectID]], List[Any]],
    store_large: Callable[[ObjectID, Any], Location],
    actor: ActorContainer,
    stream_item: Optional[Callable[[int, Any], None]] = None,
) -> Tuple[
    List[Tuple[ObjectID, Location]],
    bool,
    List[Tuple[ObjectID, list]],
    Optional[Dict[str, str]],
]:
    """Run one task; returns (results, failed, nested-refs-per-return,
    error-info). ``error-info`` is None on success, else
    {error_type, error_message, traceback} — the structured failure
    record the node manager retains and the event plane reports."""
    from ..util import overload

    deadline_ts = getattr(spec, "deadline_ts", 0.0) or 0.0
    # Install the request's deadline as this thread's ambient budget so
    # user code hits cooperative cancellation points and NESTED submits
    # inherit the remaining budget (deadline propagation).
    prev_deadline = overload.set_ambient_deadline(deadline_ts)
    try:
        # Refuse-before-execute: an expired request must never occupy
        # this worker (it spent its budget queued — the caller already
        # gave up on it).
        if deadline_ts:
            overload.check_deadline(spec.name or spec.method_name or "task")
        args, kwargs = resolve_args(spec, fetch)
        if spec.task_type == TaskType.ACTOR_CREATION_TASK:
            cls = load_function(spec.function_id)
            actor.create(cls, args, kwargs)
            value = None
        elif spec.task_type == TaskType.ACTOR_TASK:
            value = actor.call(spec.method_name, args, kwargs)
        else:
            fn = load_function(spec.function_id)
            value = fn(*args, **kwargs)
        if spec.streaming and stream_item is not None:
            # Streaming generator: seal items as they are produced; the
            # return slot carries the item count (ref: streaming
            # generators' completion semantics).
            import inspect

            count = 0
            if inspect.isgenerator(value) or hasattr(value, "__next__"):
                for item in value:
                    # Item seams are the cancellation points of a
                    # streaming task: a stream that outlives its budget
                    # stops HERE instead of generating into the void.
                    if deadline_ts:
                        overload.check_deadline(
                            spec.name or spec.method_name or "stream"
                        )
                    stream_item(count, item)
                    count += 1
            elif value is not None:
                stream_item(0, value)
                count = 1
            value = count
        results, nested = package_results(spec, value, store_large)
        return results, False, nested, None
    except Exception as e:  # noqa: BLE001 — user exceptions become TaskError
        err = e if isinstance(e, TaskError) else TaskError.from_exception(
            e, spec.name or spec.method_name
        )
        cause = err.cause if isinstance(err, TaskError) else None
        error_info = {
            "error_type": type(cause if cause is not None else e).__name__,
            "error_message": str(cause if cause is not None else e)[:500],
            "traceback": (err.traceback_str or "")[-2000:],
        }
        cfg = get_config()
        sobj = serialize(err)
        if sobj.total_size <= cfg.max_inline_object_size:
            loc: Location = InlineLocation(sobj.to_bytes())
            results = [(oid, loc) for oid in spec.return_ids()]
        else:
            results = [(oid, store_large(oid, sobj)) for oid in spec.return_ids()]
        return results, True, [], error_info
    finally:
        overload.set_ambient_deadline(prev_deadline)
