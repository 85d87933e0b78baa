"""@ray_tpu.remote functions.

Ref analogue: python/ray/remote_function.py — RemoteFunction with
``.remote()`` and ``.options()``; submission goes through the runtime's
prepare_args + TaskSpec path (the _remote path at remote_function.py:262).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

from ..util.overload import ambient_deadline as _ambient_deadline
from .config import get_config
from .ids import TaskID
from .resources import CPU, ResourceSet
from .runtime_context import current_runtime
from .task_spec import TaskSpec, TaskType


def _build_resources(opts: Dict[str, Any], default_num_cpus: float) -> ResourceSet:
    amounts = dict(opts.get("resources") or {})
    num_cpus = opts.get("num_cpus")
    amounts[CPU] = default_num_cpus if num_cpus is None else num_cpus
    num_tpus = opts.get("num_tpus")
    if num_tpus:
        amounts["TPU"] = num_tpus
    memory = opts.get("memory")
    if memory:
        amounts["memory"] = memory
    return ResourceSet(amounts)


class RemoteFunction:
    def __init__(self, fn, options: Optional[Dict[str, Any]] = None):
        self._fn = fn
        self._options = dict(options or {})
        functools.update_wrapper(self, fn)

    def options(self, **opts) -> "RemoteFunction":
        merged = dict(self._options)
        merged.update(opts)
        return RemoteFunction(self._fn, merged)

    def bind(self, *args, **kwargs):
        """Build a lazy DAG node (ref: ray.dag — fn.bind)."""
        from ..dag import FunctionNode

        return FunctionNode(self, args, kwargs)

    def remote(self, *args, **kwargs):
        rt = current_runtime()
        function_id = rt.ensure_function(self._fn)
        spec_args, spec_kwargs, keepalive, nested = rt.prepare_args(
            args, kwargs
        )
        num_returns = self._options.get("num_returns", 1)
        streaming = num_returns in ("streaming", "dynamic")
        if streaming:
            num_returns = 1  # the completion slot (item count / error)
        max_retries = self._options.get(
            "max_retries", get_config().default_max_retries
        )
        spec = TaskSpec(
            task_id=TaskID.from_random(),
            task_type=TaskType.NORMAL_TASK,
            function_id=function_id,
            args=spec_args,
            kwargs=spec_kwargs,
            num_returns=num_returns,
            streaming=streaming,
            runtime_env_key=rt.runtime_env_key,
            resources=_build_resources(self._options, default_num_cpus=1),
            name=self._options.get("name", getattr(self._fn, "__name__", "task")),
            max_retries=max_retries,
            retries_left=max_retries,
            scheduling_strategy=self._options.get("scheduling_strategy"),
            nested_refs=nested,
            deadline_ts=_ambient_deadline(),
        )
        refs = rt.submit(spec)
        del keepalive  # deps are pinned by the control plane from here on
        if streaming:
            from .streaming import ObjectRefGenerator

            return ObjectRefGenerator(
                spec.task_id, refs[0], retriable=spec.max_retries > 0
            )
        return refs[0] if num_returns == 1 else refs

    def __call__(self, *args, **kwargs):
        raise TypeError(
            f"Remote function '{getattr(self._fn, '__name__', '?')}' cannot be "
            "called directly; use '.remote()'."
        )
