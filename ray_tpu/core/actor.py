"""Actor classes, handles and methods.

Ref analogue: python/ray/actor.py — ActorClass (:489) created by @remote on a
class, ActorHandle (:113) with ActorMethod proxies; method calls become
ACTOR_TASK specs. In steady state the runtime routes them over the
direct actor-call plane (a persistent framed channel straight to the
actor's worker, sequence-ordered per handle — see runtime._DirectChannel);
the node manager is only involved for creation, restart and failure, and
as the transparent per-call fallback path.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from ..util.overload import ambient_deadline as _ambient_deadline
from .config import get_config
from .ids import ActorID, TaskID
from .remote_function import _build_resources
from .runtime_context import current_runtime
from .task_spec import TaskSpec, TaskType


class ActorMethod:
    def __init__(self, actor_handle: "ActorHandle", method_name: str,
                 num_returns: int = 1, concurrency_group: str = ""):
        self._handle = actor_handle
        self._method_name = method_name
        self._num_returns = num_returns
        self._concurrency_group = concurrency_group

    def options(self, **opts) -> "ActorMethod":
        return ActorMethod(
            self._handle, self._method_name, opts.get("num_returns", 1),
            opts.get("concurrency_group", self._concurrency_group),
        )

    def remote(self, *args, **kwargs):
        rt = current_runtime()
        spec_args, spec_kwargs, keepalive, nested = rt.prepare_args(
            args, kwargs
        )
        num_returns = self._num_returns
        streaming = num_returns in ("streaming", "dynamic")
        if streaming:
            num_returns = 1
        spec = TaskSpec(
            task_id=TaskID.from_random(),
            task_type=TaskType.ACTOR_TASK,
            function_id=self._handle._class_function_id,
            args=spec_args,
            kwargs=spec_kwargs,
            num_returns=num_returns,
            streaming=streaming,
            runtime_env_key=rt.runtime_env_key,
            name=f"{self._handle._class_name}.{self._method_name}",
            actor_id=self._handle._actor_id,
            method_name=self._method_name,
            concurrency_group=(
                self._concurrency_group
                or self._handle._method_groups.get(self._method_name, "")
            ),
            nested_refs=nested,
            deadline_ts=_ambient_deadline(),
        )
        refs = rt.submit(spec)
        del keepalive
        if streaming:
            from .streaming import ObjectRefGenerator

            return ObjectRefGenerator(
                spec.task_id, refs[0], retriable=spec.max_retries > 0,
                stream=rt.take_direct_stream(spec.task_id),
            )
        return refs[0] if num_returns == 1 else refs

    def __call__(self, *args, **kwargs):
        raise TypeError("Actor methods must be called with '.remote()'.")


class ActorHandle:
    def __init__(self, actor_id: ActorID, class_name: str = "",
                 class_function_id: str = "",
                 method_groups: Optional[Dict[str, str]] = None):
        self._actor_id = actor_id
        self._class_name = class_name
        self._class_function_id = class_function_id
        # method name -> concurrency group (from @ray_tpu.method
        # annotations on the class; ref: concurrency groups declared per
        # method, core_worker/transport/concurrency_group_manager.h).
        self._method_groups = dict(method_groups or {})

    def __getattr__(self, name: str) -> ActorMethod:
        # "__rtpu_ping__" is the built-in liveness probe every actor answers
        # (executor.ActorContainer.call); other dunder/private lookups are
        # python machinery, not remote methods.
        if name.startswith("_") and name != "__rtpu_ping__":
            raise AttributeError(name)
        method = ActorMethod(self, name)
        # Cache on the instance: ``a.ping.remote()`` in a tight loop
        # otherwise allocates a fresh proxy per call (measurable on the
        # direct-plane hot path). Instance attributes bypass __getattr__
        # on the next access; __reduce__ rebuilds handles without the
        # cache, so serialized handles stay slim.
        self.__dict__[name] = method
        return method

    @property
    def actor_id(self) -> ActorID:
        return self._actor_id

    def __repr__(self):
        return f"ActorHandle({self._class_name}, {self._actor_id.hex()[:8]})"

    def __reduce__(self):
        return (
            ActorHandle,
            (self._actor_id, self._class_name, self._class_function_id,
             self._method_groups),
        )


class ActorClass:
    def __init__(self, cls, options: Optional[Dict[str, Any]] = None):
        self._cls = cls
        self._options = dict(options or {})
        functools.update_wrapper(self, cls, updated=[])

    def options(self, **opts) -> "ActorClass":
        merged = dict(self._options)
        merged.update(opts)
        return ActorClass(self._cls, merged)

    def bind(self, *args, **kwargs):
        """Build a lazy actor DAG node (ref: ray.dag — cls.bind)."""
        from ..dag import ClassNode

        return ClassNode(self, args, kwargs)

    def remote(self, *args, **kwargs) -> ActorHandle:
        rt = current_runtime()
        function_id = rt.ensure_function(self._cls)
        spec_args, spec_kwargs, keepalive, nested = rt.prepare_args(
            args, kwargs
        )
        actor_id = ActorID.from_random()
        max_restarts = self._options.get("max_restarts", 0)
        # Actors hold their resources for their lifetime. Like the reference,
        # the default is 0 CPUs for a running actor (actor.py: actors don't
        # occupy CPUs after creation unless num_cpus is set explicitly).
        resources = _build_resources(self._options, default_num_cpus=0)
        groups = self._options.get("concurrency_groups")
        # Walk the MRO so annotations on inherited methods count too.
        method_groups = {}
        for klass in reversed(self._cls.__mro__):
            for mname, m in vars(klass).items():
                g = getattr(m, "_rtpu_concurrency_group", "")
                if g:
                    method_groups[mname] = g
        spec = TaskSpec(
            task_id=TaskID.from_random(),
            task_type=TaskType.ACTOR_CREATION_TASK,
            function_id=function_id,
            args=spec_args,
            kwargs=spec_kwargs,
            num_returns=1,
            resources=resources,
            name=self._options.get("name", ""),
            actor_id=actor_id,
            class_name=self._cls.__name__,
            runtime_env_key=rt.runtime_env_key,
            max_restarts=max_restarts,
            max_concurrency=self._options.get("max_concurrency", 1),
            concurrency_groups=dict(groups) if groups else None,
            method_groups=method_groups or None,
            allow_out_of_order=bool(
                self._options.get("allow_out_of_order", False)
            ),
            scheduling_strategy=self._options.get("scheduling_strategy"),
            nested_refs=nested,
        )
        rt.submit(spec)
        del keepalive
        return ActorHandle(
            actor_id,
            class_name=self._cls.__name__,
            class_function_id=function_id,
            method_groups=method_groups,
        )

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"Actor class '{self._cls.__name__}' cannot be instantiated "
            "directly; use '.remote()'."
        )


def method(*, concurrency_group: str = ""):
    """Method annotation (ref analogue: ray.method): declares the
    concurrency group an actor method executes in. Groups are sized at
    class level via @ray_tpu.remote(concurrency_groups={...}). (Use
    ``.options(num_returns=...)`` at the call site for multi-return
    actor methods.)"""

    def wrap(fn):
        if concurrency_group:
            fn._rtpu_concurrency_group = concurrency_group
        return fn

    return wrap


def get_actor(name: str) -> ActorHandle:
    """Look up a named actor (ref analogue: ray.get_actor)."""
    rt = current_runtime()
    spec = rt.get_named_actor_spec(name)
    if spec is None:
        raise ValueError(f"Failed to look up actor with name '{name}'")
    return ActorHandle(
        spec.actor_id, class_name=spec.name,
        class_function_id=spec.function_id,
        method_groups=getattr(spec, "method_groups", None),
    )
