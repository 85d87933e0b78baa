"""Public core API: init/shutdown/remote/get/put/wait/kill/cancel.

Ref analogue: the global API in python/ray/_private/worker.py (ray.init:1221,
ray.get:2563, ray.put, ray.wait, ray.kill, ray.cancel) and the @ray.remote
decorator in python/ray/__init__.py.
"""

from __future__ import annotations

import atexit
import inspect
import os
import tempfile
import time
import uuid
from typing import Any, Dict, Optional, Sequence

from .actor import ActorClass, ActorHandle, get_actor  # noqa: F401
from .config import Config, get_config, reset_config
from .exceptions import RuntimeNotInitializedError
from .ids import JobID, NodeID
from .node_manager import NodeManager
from .reference import ObjectRef
from .remote_function import RemoteFunction
from .runtime import DriverRuntime
from .tpu import local_chip_count, node_tpu_labels
from . import runtime_context


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    system_config: Optional[Dict[str, Any]] = None,
    runtime_env: Optional[Dict[str, Any]] = None,
    ignore_reinit_error: bool = False,
) -> "DriverRuntime":
    """Start the runtime: head mode (no address) starts an in-process
    node + GCS; ``address="host:port"`` (or env RAY_TPU_ADDRESS, set for
    jobs and `rtpu submit` children) attaches this driver to an existing
    cluster as its own zero-resource node, so its tasks spill to the
    cluster's workers.

    Ref analogue: ray.init starting a local cluster or connecting to an
    existing one (python/ray/_private/worker.py:1221).
    """
    existing = runtime_context.current_runtime_or_none()
    if existing is not None:
        if ignore_reinit_error:
            return existing
        raise RuntimeError("ray_tpu.init() called twice; use shutdown() first.")

    if address is None:
        address = os.environ.get("RAY_TPU_ADDRESS") or None

    reset_config()
    config = get_config()
    config.apply_overrides(system_config)
    if address and address.startswith("rtpu://"):
        # Thin-client mode (ref: ray.init("ray://...") via util/client):
        # no local node; one TCP connection to the head.
        from .client import connect

        rt = connect(address)
        runtime_context.set_runtime(rt)
        if runtime_env:
            from . import runtime_env as renv_mod

            rt.runtime_env_key = renv_mod.publish(
                runtime_env, rt.kv_put, rt.job_id.hex()
            )
        return rt
    if object_store_memory is not None:
        config.object_store_memory = object_store_memory

    res: Dict[str, float] = dict(resources or {})
    if address is None:
        res.setdefault(
            "CPU", num_cpus if num_cpus is not None else os.cpu_count() or 1
        )
    else:
        # Attached drivers contribute no compute by default: work runs on
        # the cluster, not in the client process's node.
        res.setdefault("CPU", num_cpus if num_cpus is not None else 0)
    if num_tpus is not None:
        res["TPU"] = num_tpus
    elif address is None:
        detected = local_chip_count()
        if detected:
            res.setdefault("TPU", detected)

    session_dir = os.path.join(
        tempfile.gettempdir(),
        "ray_tpu",
        f"session-{int(time.time())}-{uuid.uuid4().hex[:8]}",
    )
    os.makedirs(session_dir, exist_ok=True)

    node_id = NodeID.from_random()
    gcs_address = None
    if address is not None:
        host, port_s = address.rsplit(":", 1)
        gcs_address = (host, int(port_s))
    nm = NodeManager(
        node_id, session_dir, res, config,
        is_head=gcs_address is None,
        gcs_address=gcs_address,
        node_ip=config.node_ip,
        labels=node_tpu_labels(),
    )
    nm.start()
    rt = DriverRuntime(nm, job_id=JobID.from_random())
    runtime_context.set_runtime(rt)
    if runtime_env:
        from . import runtime_env as renv_mod

        rt.runtime_env_key = renv_mod.publish(
            runtime_env, rt.kv_put, rt.job_id.hex()
        )
    if config.log_to_driver:
        from .log_monitor import LogMonitor

        rt.log_monitor = LogMonitor(session_dir, nm)
        rt.log_monitor.start()
    atexit.register(_atexit_shutdown)
    return rt


def _atexit_shutdown():
    try:
        shutdown()
    except Exception:
        pass


def shutdown():
    rt = runtime_context.current_runtime_or_none()
    if rt is None:
        return
    try:
        # Local-only usage report into the session dir (zero egress;
        # ref analogue: usage_lib's shutdown report).
        from ..util import usage_stats

        session_dir = getattr(getattr(rt, "_nm", None),
                              "session_dir", None)
        if session_dir:
            usage_stats.write_report(session_dir)
    except Exception:
        pass
    runtime_context.set_runtime(None)
    monitor = getattr(rt, "log_monitor", None)
    if monitor is not None:
        monitor.stop()
    rt.shutdown()


def is_initialized() -> bool:
    return runtime_context.is_initialized()


def kv_put(key: str, value: bytes, overwrite: bool = True) -> bool:
    """Cluster KV store write (ref analogue: ray internal_kv, used by the
    job table, train report channel, and user coordination)."""
    return runtime_context.current_runtime().kv_put(key, value, overwrite)


def kv_get(key: str):
    return runtime_context.current_runtime().kv_get(key)


def remote(*args, **kwargs):
    """@remote decorator for functions and classes, with or without options
    (ref: python/ray/__init__.py ray.remote)."""
    if len(args) == 1 and not kwargs and (
        inspect.isfunction(args[0]) or inspect.isclass(args[0])
    ):
        target = args[0]
        if inspect.isclass(target):
            return ActorClass(target)
        return RemoteFunction(target)
    if args:
        raise TypeError("remote() takes keyword options only, e.g. "
                        "@remote(num_cpus=2)")

    def decorator(target):
        if inspect.isclass(target):
            return ActorClass(target, kwargs)
        return RemoteFunction(target, kwargs)

    return decorator


def put(value) -> ObjectRef:
    return runtime_context.current_runtime().put(value)


def get(refs, *, timeout: Optional[float] = None):
    return runtime_context.current_runtime().get(refs, timeout)


def wait(
    refs: Sequence[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: Optional[float] = None,
):
    return runtime_context.current_runtime().wait(refs, num_returns, timeout)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    runtime_context.current_runtime().kill_actor(actor.actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False):
    runtime_context.current_runtime().cancel_task(ref.id().task_id(), force)


def cluster_resources() -> Dict[str, float]:
    return runtime_context.current_runtime().cluster_resources()


def available_resources() -> Dict[str, float]:
    return runtime_context.current_runtime().available_resources()


def nodes():
    """Cluster node table (ref analogue: ray.nodes() backed by
    GlobalStateAccessor over the GCS node table)."""
    rt = runtime_context.current_runtime()
    views = getattr(rt, "nodes", None)
    if views is None:
        return [
            {
                "NodeID": rt.node_id.hex(),
                "Alive": True,
                "Resources": rt.cluster_resources(),
            }
        ]
    return [
        {
            "NodeID": v["node_id"],
            "Alive": v["state"] == "alive",
            "State": v.get("state"),
            "Resources": v["resources_total"],
            "Available": v.get("resources_available",
                               v["resources_total"]),
            "IsHead": v.get("is_head", False),
            "Host": v.get("host"),
            "Labels": v.get("labels", {}),
            # Membership-fence plane (core/fencing.py): which
            # registration of this node id the row describes, and the
            # cluster epoch the view was taken at.
            "Incarnation": v.get("incarnation", 1),
            "Epoch": v.get("epoch", 0),
        }
        for v in rt.nodes()
    ]


class DrainRefusedError(RuntimeError):
    """The drain was refused by policy (head node, or the node hosts
    the serve controller) — the node is healthy and untouched. Rolling
    restarts must NOT fall back to terminating such a node."""


def drain_node(node_id: str, timeout: Optional[float] = None
               ) -> Dict[str, Any]:
    """Drain ``node_id`` (full hex or unique prefix) and retire it with
    zero downtime (ref analogue: the GCS DrainNode RPC behind kuberay's
    drain-before-delete). Three phases: (1) the GCS marks the node
    draining — schedulers everywhere stop targeting it while in-flight
    traffic keeps flowing; (2) if a serve controller exists, its
    replicas on that node are surge-replaced elsewhere and gracefully
    drained; (3) the node finishes in-flight work, replicates primary
    object copies off-node, acks, and exits — consumers re-locate via
    the GCS, and anything that missed the window replays via lineage.

    Returns the drain report ``{"ok", "replicated",
    "leftover_actors", ...}``; raises on an unknown/ambiguous node or a
    failed drain."""
    rt = runtime_context.current_runtime()
    nm = getattr(rt, "_nm", None)
    if nm is None:
        raise RuntimeError(
            "drain_node needs a cluster-attached driver (thin clients "
            "cannot drive drains)"
        )
    if timeout is None:
        timeout = get_config().drain_timeout_s
    matches = sorted({
        v["node_id"] for v in rt.nodes()
        if v["node_id"].startswith(node_id) and v.get("state") != "dead"
    })
    if not matches:
        raise ValueError(f"no live node matches {node_id!r}")
    if len(matches) > 1:
        raise ValueError(
            f"node id prefix {node_id!r} is ambiguous: "
            f"{[m[:12] for m in matches]}"
        )
    full = matches[0]
    # Snapshot the node's actors BEFORE phase 1: once the node is
    # draining it leaves the alive-state fan-out, so the serve
    # controller could no longer resolve which replicas live there.
    from ..util import state as state_api

    try:
        rows = [a for a in state_api.list_actors()
                if a.get("node_id") == full]
        on_node = [a["actor_id"] for a in rows]
        from ..serve.controller import CONTROLLER_NAME

        if any(a.get("name") == CONTROLLER_NAME for a in rows):
            # The controller is pinned to its creating driver's node;
            # draining that node would kill the serve control plane
            # (no autoscaling/health/rollouts, and the next deploy
            # would orphan the running replicas under a fresh empty
            # controller). Refuse instead of silently beheading serve.
            raise DrainRefusedError(
                f"node {full[:8]} hosts the serve controller — drain "
                f"refused (shut serve down or deploy from another "
                f"node first)"
            )
    except RuntimeError:
        raise
    except Exception as e:
        # Swallowing this would silently skip serve-replica migration
        # and let replicas die with the node while the drain reports
        # ok — abort before phase "begin" instead (nothing to roll
        # back yet).
        raise RuntimeError(
            f"drain of {full[:8]} aborted: could not snapshot the "
            f"node's actors for serve migration ({e!r})"
        ) from e
    reply = nm.call_sync(
        nm._gcs.drain_node(full, phase="begin"), timeout=30.0
    )
    if not reply.get("ok"):
        raise RuntimeError(f"drain begin failed: {reply.get('error')}")
    # From here a failure must roll the node back to "alive": a node
    # left "draining" is reachable but unschedulable forever (silent
    # capacity loss with no operator undo).
    try:
        if on_node:
            # Serve replicas migrate via the controller's drain
            # machinery (surge a replacement, bump the route set, drain
            # the victim).
            try:
                from ..serve.controller import CONTROLLER_NAME

                controller = get_actor(CONTROLLER_NAME)
                get(controller.drain_replicas.remote(on_node),
                    timeout=timeout)
            except ValueError:
                pass  # no serve controller in this cluster
        reply = nm.call_sync(
            nm._gcs.drain_node(full, phase="finish", timeout=timeout),
            timeout=timeout + 30.0,
        )
        if not reply.get("ok"):
            raise RuntimeError(
                f"drain of node {full[:8]} failed: {reply.get('error')}"
            )
    except BaseException:
        try:
            nm.call_sync(
                nm._gcs.drain_node(full, phase="abort"), timeout=30.0
            )
        except Exception:
            pass  # best effort — the original failure is what matters
        raise
    return reply
