"""Task specifications.

Mirrors the reference's TaskSpecification (ref: src/ray/common/task/task_spec.h
over protobuf common.proto TaskSpec): one record describing a normal task, an
actor-creation task, or an actor method call. Functions are distributed by
content hash through the head's function table (ref analogue:
python/ray/_private/function_manager.py exporting pickled functions to GCS KV)
so a function is pickled once per cluster, not once per call.
"""

from __future__ import annotations

import enum
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .ids import ActorID, ObjectID, TaskID, WorkerID
from .resources import ResourceSet


class TaskType(enum.Enum):
    NORMAL_TASK = 0
    ACTOR_CREATION_TASK = 1
    ACTOR_TASK = 2


@dataclass(frozen=True, slots=True)
class RefArg:
    """A top-level ObjectRef argument: resolved to its value by the executing
    worker before the function runs (nested refs pass through untouched, same
    semantics as the reference)."""

    object_id: ObjectID


@dataclass(frozen=True, slots=True)
class ValueArg:
    data: bytes  # framed SerializedObject bytes


@dataclass(slots=True)
class TaskSpec:
    """``slots=True`` across spec/arg records: a 1M-deep task queue holds
    one of each per task, and their per-instance ``__dict__``s were a
    leading slice of the 4.4 GB driver RSS the r5 envelope probe
    measured."""

    task_id: TaskID
    task_type: TaskType
    function_id: str  # content hash into the cluster function table
    args: List[Any] = field(default_factory=list)  # RefArg | ValueArg
    kwargs: Dict[str, Any] = field(default_factory=dict)
    num_returns: int = 1
    # Streaming generator task (ref: num_returns="streaming" →
    # ObjectRefGenerator): yielded items are sealed one by one as
    # stream-indexed objects; the single return slot carries the final
    # item count.
    streaming: bool = False
    # KV key of the submitting job's runtime env ("" = none): workers
    # apply the referenced env before executing (ref: per-job runtime_env
    # propagated through the task spec).
    runtime_env_key: str = ""
    resources: ResourceSet = field(default_factory=ResourceSet)
    name: str = ""
    max_retries: int = 0
    retries_left: int = 0
    # Actor fields
    actor_id: Optional[ActorID] = None
    method_name: str = ""
    class_name: str = ""  # actor class, for the state API / debugging
    max_restarts: int = 0
    max_concurrency: int = 1
    # Concurrency groups (ref: concurrency_group_manager.h): creation
    # tasks carry {group_name: max_concurrency}; method calls carry the
    # group routing them to that group's executor in the actor worker.
    concurrency_groups: Optional[Dict[str, int]] = None
    concurrency_group: str = ""
    # method name -> group (creation tasks; lets handles recovered via
    # get_actor route annotated methods correctly).
    method_groups: Optional[Dict[str, str]] = None
    # Out-of-order actor execution (ref:
    # out_of_order_actor_submit_queue.h): independent method calls may
    # execute as they arrive instead of strictly in submission order.
    allow_out_of_order: bool = False
    # NM-path replay of a call whose direct channel died mid-flight
    # (runtime._direct_channel_failed). If the actor itself is not alive
    # when the replay arrives, the call FAILS like any NM-routed call
    # interrupted by actor death — replays must not re-execute
    # interrupted methods into a restarted actor (at-most-once across
    # restarts; a channel-only fault with the worker alive still
    # replays, deduped by task id at the worker).
    direct_replay: bool = False
    # Actor incarnation this spec is bound to (0 = unbound). On an
    # ACTOR_CREATION_TASK: the GCS-assigned incarnation being started
    # (the worker adopts it for direct-hello validation). On a
    # direct-replay ACTOR_TASK: the incarnation the failed channel
    # spoke to — the home NM REFUSES the replay if the live actor's
    # incarnation differs (a restarted actor has no replay-dedup cache;
    # re-executing a possibly-executed call there would double-execute).
    actor_incarnation: int = 0
    # Owner bookkeeping (worker that submitted the task; nil = driver)
    owner_id: Optional[WorkerID] = None
    # Tracing context (trace_id, parent_span_id) — stamped at submit,
    # consumed by the executing worker to parent its span (ref:
    # tracing_helper.py:165 context injection into the task spec).
    trace_ctx: Optional[Tuple[str, str]] = None
    # Absolute wall-clock deadline (time.time() seconds; 0 = none).
    # Stamped at submit from the caller's ambient deadline
    # (util/overload.py) and re-installed around execution on the
    # worker, so a request's remaining budget propagates through nested
    # calls; the worker REFUSES an already-expired task before running
    # it (ref analogue: serve's end-to-end request_timeout_s).
    deadline_ts: float = 0.0
    # Placement: "DEFAULT" | "SPREAD" | NodeAffinitySchedulingStrategy |
    # NodeLabelSchedulingStrategy (ref analogue: TaskSpec scheduling_strategy
    # in common.proto + util/scheduling_strategies.py)
    scheduling_strategy: Any = None
    # ObjectIDs of refs embedded INSIDE serialized argument values (not
    # top-level RefArgs): pinned for the task's lifetime like
    # dependencies, but never resolved to values (ref analogue: nested
    # ids recorded per task in ReferenceCounter, reference_count.h:61).
    nested_refs: Tuple[ObjectID, ...] = ()

    def return_ids(self) -> Tuple[ObjectID, ...]:
        return tuple(
            ObjectID.from_index(self.task_id, i) for i in range(self.num_returns)
        )

    def dependency_ids(self) -> Tuple[ObjectID, ...]:
        deps = [a.object_id for a in self.args if isinstance(a, RefArg)]
        deps += [a.object_id for a in self.kwargs.values() if isinstance(a, RefArg)]
        return tuple(deps)

    def pinned_ids(self) -> Tuple[ObjectID, ...]:
        """Everything the control plane holds alive while the task is in
        flight: resolved dependencies plus refs smuggled inside argument
        values."""
        return self.dependency_ids() + tuple(self.nested_refs)


# Owner/actor IDs repeated by every call of a hot function: a bounded
# canonicalization table collapses the per-spec copies unpickling creates
# (1M queued tasks from one driver otherwise hold 1M identical WorkerID
# objects). Cleared wholesale on overflow — correctness never depends on
# a hit.
_ID_INTERN_MAX = 4096
_id_intern: Dict[bytes, Any] = {}


def _intern_id(obj):
    if obj is None:
        return None
    key = obj.binary()
    cached = _id_intern.get(key)
    if cached is not None and type(cached) is type(obj):
        return cached
    if len(_id_intern) >= _ID_INTERN_MAX:
        _id_intern.clear()
    _id_intern[key] = obj
    return obj


def intern_spec(spec: TaskSpec) -> TaskSpec:
    """Dedup the fields every record of a hot function repeats — string
    descriptors via ``sys.intern`` plus owner/actor ids via the table
    above. Unpickling (worker submits, peer forwards, client replays)
    materializes fresh copies per spec; the node manager interns at its
    submit/forward entry points so a deep queue stores each descriptor
    once (the 1M-queued-task driver footprint satellite)."""
    spec.function_id = sys.intern(spec.function_id)
    if spec.name:
        spec.name = sys.intern(spec.name)
    if spec.method_name:
        spec.method_name = sys.intern(spec.method_name)
    if spec.class_name:
        spec.class_name = sys.intern(spec.class_name)
    if spec.concurrency_group:
        spec.concurrency_group = sys.intern(spec.concurrency_group)
    if spec.runtime_env_key:
        spec.runtime_env_key = sys.intern(spec.runtime_env_key)
    spec.owner_id = _intern_id(spec.owner_id)
    spec.actor_id = _intern_id(spec.actor_id)
    spec.resources = _intern_resources(spec.resources)
    return spec


# Resource shapes repeat across every call of a function: canonicalize
# identical sets so 1M queued noop tasks share ONE {"CPU": 1} ResourceSet
# instead of holding a dict each. Safe because the scheduler treats a
# spec's ResourceSet as immutable (arithmetic returns new sets).
_RES_INTERN_MAX = 512
_res_intern: Dict[tuple, Any] = {}


def _intern_resources(res):
    if res is None:
        return None
    try:
        key = tuple(sorted(res._amounts.items()))
    except AttributeError:
        return res
    cached = _res_intern.get(key)
    if cached is not None:
        return cached
    if len(_res_intern) >= _RES_INTERN_MAX:
        _res_intern.clear()
    _res_intern[key] = res
    return res
