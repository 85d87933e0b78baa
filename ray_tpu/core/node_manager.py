"""Per-node control plane: scheduler + worker pool + object directory.

This is the raylet-equivalent (ref: src/ray/raylet/node_manager.h NodeManager,
worker_pool.h WorkerPool, scheduling/cluster_task_manager.h +
local_task_manager.h). It runs an asyncio event loop in a background thread;
workers connect over a unix socket with framed pickled messages
(protocol.py), and peer nodes connect over TCP (peers.py).

Cluster mode: the head node hosts the GCS-equivalent control plane
(gcs.py GcsService) on the same loop; remote nodes (spawned by
cluster_utils.Cluster.add_node or node_main) register with it, gossip load
reports, and learn the cluster view from its broadcasts (ref analogue: the
RaySyncer resource gossip, src/ray/common/ray_syncer/ray_syncer.h:88).
Tasks whose resources don't fit locally — or whose scheduling strategy says
otherwise — are forwarded to the node picked by the hybrid/spread/affinity
policies (scheduling_policy.py), the moral equivalent of the reference's
spillback re-leasing (ref: ClusterTaskManager::ScheduleAndDispatchTasks).
Objects are pulled between nodes on demand and re-homed into the local store
(ref analogue: PullManager + ObjectManagerService Push/Pull).
"""

from __future__ import annotations

import asyncio
import os
import struct
import subprocess
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Set, Tuple


from .config import Config
from .exceptions import (
    ActorDiedError,
    ObjectLostError,
    TaskCancelledError,
    TaskError,
    WorkerCrashedError,
)
from .gcs import GcsClient, GcsService, LocalGcsHandle, RemoteGcsHandle
from .rpc import RpcError
from .ids import ActorID, NodeID, ObjectID, TaskID, WorkerID
from .object_store import (
    ArenaLocation,
    InlineLocation,
    LocalObjectStore,
    Location,
    ObjectDirectory,
    RemoteLocation,
    ShmLocation,
    SpilledLocation,
    current_arena,
    init_arena,
    shutdown_arena,
)
from .spilling import SpillManager
from . import fencing as _fencing
from .peers import PeerClient
from .placement_group import BundleState
from .protocol import AioFramedWriter, aio_read_frame
from .resources import CPU, NodeResources, ResourceSet
from .scheduling_policy import pick_node
from .scheduling_strategies import PlacementGroupSchedulingStrategy
from .task_spec import TaskSpec, TaskType, intern_spec
from .tpu import worker_jax_env
from ..util import dispatch_obs, loop_monitor
from ..util import events as cluster_events
from ..util import faults
from ..util.backoff import Backoff

_HEADER = struct.Struct("<I")


def _free_location(loc) -> None:
    """Release an object's storage: arena delete, shm unlink, or spill-file
    removal."""
    if isinstance(loc, SpilledLocation):
        try:
            os.remove(loc.path)
        except OSError:
            pass
    elif isinstance(loc, ArenaLocation):
        arena = current_arena()
        if arena is not None:
            try:
                arena.delete(loc.oid)
            except Exception:
                pass
    elif isinstance(loc, ShmLocation):
        try:
            from multiprocessing import shared_memory

            seg = shared_memory.SharedMemory(name=loc.name)
            seg.close()
            seg.unlink()
        except FileNotFoundError:
            pass
        except Exception:
            pass


def _resolve(fut: "asyncio.Future") -> None:
    """Wake what awaits ``fut``, unless it was woken or cancelled."""
    if not fut.done():
        fut.set_result(None)


def _read_text_tail(path: str, nbytes: int) -> str:
    """Last ``nbytes`` of a text file via seek (bounded read — never the
    whole file). Executor-thread helper for crash diagnosis; '' on any
    I/O error."""
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - nbytes))
            return f.read(nbytes).decode("utf-8", errors="replace")
    except OSError:
        return ""


def _system_memory_usage_fraction() -> float:
    """System memory usage in [0, 1] from /proc/meminfo (ref analogue:
    MemoryMonitor::GetMemoryBytes, common/memory_monitor.h)."""
    info = {}
    # procfs is memory-backed: this "file" read never touches disk.
    with open("/proc/meminfo") as f:  # rtlint: disable=loop-blocking
        for line in f:
            key, _, rest = line.partition(":")
            try:
                info[key] = int(rest.strip().split()[0])
            except (ValueError, IndexError):
                pass
    total = info.get("MemTotal", 0)
    if total <= 0:
        return 0.0
    return 1.0 - info.get("MemAvailable", total) / total


def _task_worker_type(spec: TaskSpec) -> str:
    """Tasks/actors requesting TPU resources run in workers that keep the
    accelerator environment; everything else runs in fast-starting CPU
    workers (the chip is exclusive-access, so TPU workers are scarce)."""
    return "tpu" if spec.resources.get("TPU") > 0 else "cpu"


# Asyncio framing shared with the GCS/peer channels (protocol.py).
_read_frame = aio_read_frame
_FramedWriter = AioFramedWriter

# Shared empty-location placeholder for pre-registered return slots and
# borrow stubs (frozen dataclass — one instance serves every record).
_RETURN_PLACEHOLDER = InlineLocation(b"")


@dataclass(slots=True)
class TaskRecord:
    """Queue-resident task bookkeeping. ``slots=True``: a 1M-deep queue
    holds 1M of these, and the per-instance ``__dict__`` was the single
    largest slice of the 4.4 GB driver RSS the r5 envelope probe
    measured."""

    spec: TaskSpec
    state: str = "waiting"  # waiting | ready | running | forwarded | finished | failed | cancelled
    worker_id: Optional[WorkerID] = None
    resources_held: bool = False
    deps_unpinned: bool = False
    # Cluster fields: ``origin`` is the hex node id that forwarded this task
    # here (results are pushed back to it); ``target`` is the node this
    # record was forwarded to; ``spillbacks`` bounds forwarding hops.
    origin: Optional[str] = None
    target: Optional[str] = None
    spillbacks: int = 0
    # Bundle this task's resources were acquired from, if placed in a
    # placement group: (pg_id, bundle_index).
    bundle_key: Optional[Tuple[str, int]] = None
    created: float = field(default_factory=time.monotonic)
    # When the record first looked cluster-wide infeasible (grace timing).
    infeasible_since: Optional[float] = None
    # Cached scheduling-class key (shape + strategy + worker type); records
    # of one class are interchangeable for capacity decisions.
    sched_class: Optional[Tuple] = None
    # monotonic time this record was handed to a worker (feeds the
    # per-task-duration histogram in /metrics).
    dispatched: Optional[float] = None
    # Hang detector bookkeeping: the WARNING event fires once per record
    # (re-dispatch after a retry resets it with the record state).
    hang_warned: bool = False


@dataclass
class WorkerHandle:
    worker_id: WorkerID
    writer: _FramedWriter
    proc: Optional[subprocess.Popen] = None
    state: str = "idle"  # idle | busy | blocked | actor | dead
    worker_type: str = "cpu"  # cpu | tpu — tpu workers own the accelerator env
    current: Optional[TaskRecord] = None
    # Pipelined tasks shipped ahead of completion (ref analogue: actor
    # submit pipelining via max_tasks_in_flight_per_worker). Resources are
    # held while queued; a worker that blocks gets them reclaimed.
    pending: Deque[TaskRecord] = field(default_factory=deque)
    # Execute frames still being written by an async _send_execute (blob
    # fetch in flight). While nonzero the send_nowait fast path is off so
    # frames cannot overtake each other (per-caller actor call order).
    slow_sends: int = 0
    # Serializes slow sends themselves: two blob-fetching sends would
    # otherwise race on fetch latency and reorder. FIFO-fair asyncio lock,
    # acquired in frame-submission order.
    send_lock: "asyncio.Lock" = field(default_factory=lambda: asyncio.Lock())
    known_functions: Set[str] = field(default_factory=set)
    actor_id: Optional[ActorID] = None
    last_active: float = field(default_factory=time.monotonic)
    # Open chunked-put writers from a thin-client connection, keyed by
    # object id; aborted if the client dies mid-put.
    client_writers: Dict[ObjectID, Any] = field(default_factory=dict)
    # Execute frames coalesced within one loop iteration and flushed as a
    # single socket write: on a contended host every write wakes the
    # worker process and the kernel's wakeup preemption turns per-frame
    # writes into one context switch per task (the dispatch wall at
    # PERF_r03's 2.5-3k tasks/s).
    exec_buf: List[Dict[str, Any]] = field(default_factory=list)


class _ReadyQueue:
    """Ready tasks bucketed by scheduling class (ref analogue:
    ClusterTaskManager's per-SchedulingClass queues,
    scheduling/cluster_task_manager.h): a dispatch pass visits each CLASS
    once and stops at the first blocked head, so a deep homogeneous queue
    costs O(#classes + #dispatched) — not O(#queued) resource checks."""

    __slots__ = ("classes", "_count", "_keyfn")

    def __init__(self, keyfn):
        self.classes: Dict[Tuple, Deque[TaskRecord]] = {}
        self._count = 0
        self._keyfn = keyfn

    def append(self, rec: "TaskRecord"):
        self.classes.setdefault(self._keyfn(rec), deque()).append(rec)
        self._count += 1

    def popleft(self) -> "TaskRecord":
        for cls, q in self.classes.items():
            rec = q.popleft()
            self._count -= 1
            if not q:
                del self.classes[cls]
            return rec
        raise IndexError("pop from empty ready queue")

    def remove_head(self, cls: Tuple):
        q = self.classes[cls]
        q.popleft()
        self._count -= 1
        if not q:
            del self.classes[cls]

    def count_worker_type(self, wtype: str) -> int:
        return sum(
            len(q) for cls, q in self.classes.items() if cls[2] == wtype
        )

    def __len__(self) -> int:
        return self._count

    def __bool__(self) -> bool:
        return self._count > 0

    def __iter__(self):
        for q in self.classes.values():
            yield from q


@dataclass
class ActorInfo:
    actor_id: ActorID
    creation_spec: TaskSpec
    state: str = "pending"  # pending | alive | restarting | dead
    worker_id: Optional[WorkerID] = None
    queued: Deque[TaskSpec] = field(default_factory=deque)
    inflight: Dict[TaskID, TaskRecord] = field(default_factory=dict)
    restarts_left: int = 0
    restart_count: int = 0
    name: str = ""
    death_cause: str = ""
    # Direct-call endpoints the actor's worker listens on (callers
    # bypass the node manager for method calls; see worker_main
    # _start_direct_listener / runtime._DirectChannel): a unix socket
    # for same-node callers, a TLS-aware TCP (host, port) for remote
    # workers and thin clients, and the worker's direct protocol
    # version (mismatched callers stay on the NM route).
    direct_path: Optional[str] = None
    direct_addr: Optional[Tuple[str, int]] = None
    direct_ver: int = 1
    # GCS-assigned incarnation of the CURRENT start of this actor
    # (bumped on every start/restart cluster-wide). Resolution returns
    # it, the direct hello carries it, and the worker refuses a
    # mismatch — a cached endpoint to a stale incarnation can never
    # execute against the wrong actor state (split-brain fencing).
    incarnation: int = 0


class NodeManager:
    def __init__(
        self,
        node_id: NodeID,
        session_dir: str,
        resources: Dict[str, float],
        config: Config,
        *,
        is_head: bool = True,
        gcs_address: Optional[Tuple[str, int]] = None,
        node_ip: str = "127.0.0.1",
        labels: Optional[Dict[str, str]] = None,
    ):
        self.node_id = node_id
        self.session_dir = session_dir
        self.socket_path = os.path.join(session_dir, "node.sock")
        self.config = config
        self.is_head = is_head
        self.node_ip = node_ip
        self.labels = labels or {}
        self.node_resources = NodeResources(ResourceSet(resources))
        capacity = config.object_store_memory
        self.directory = ObjectDirectory(capacity)
        # Spilling: admit puts over capacity and relieve pressure by moving
        # LRU objects to disk (ref: raylet/local_object_manager.h:41).
        self.spill_manager = SpillManager(os.path.join(session_dir, "spill"))
        if config.object_spilling_enabled:
            self.directory.spill_enabled = True
        self._spilling = False
        self._restores: Dict[ObjectID, asyncio.Future] = {}
        # Native C++ arena store (plasma-equivalent, src/store/): created by
        # the head process; workers attach via RAY_TPU_ARENA. Pure-Python
        # per-object shm remains the fallback when the toolchain is missing.
        self.arena_name: Optional[str] = None
        if config.use_native_store:
            name = f"/rtpu-{node_id.hex()[:16]}"
            if init_arena(name, capacity=capacity or (1 << 30), create=True):
                self.arena_name = name

        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._run_loop, name="ray_tpu-node-manager", daemon=True
        )
        self._server: Optional[asyncio.AbstractServer] = None
        self._started = threading.Event()
        self._shutdown = False
        # Drain lifecycle (gcs.drain_node): once draining, this node is
        # unschedulable cluster-wide, finishes in-flight work, replicates
        # primary object copies off-node, then exits cleanly.
        self._draining = False
        # Host-process hook (node_main): called once the drain state
        # machine finished and the ack is on the wire — the process
        # should exit.
        self.on_drain_complete = None
        # Chaos plane: node-filtered specs need to know where they run.
        faults.set_local_node(node_id.hex())

        # Scheduling state (loop-thread only).
        self._ready = _ReadyQueue(self._sched_class)
        self._sched_pending = False
        # Workers with buffered execute frames awaiting the end-of-
        # iteration flush (see _send_execute_to / _flush_execute_bufs).
        self._exec_dirty: List[WorkerHandle] = []
        self._waiting: Dict[TaskID, Tuple[TaskRecord, Set[ObjectID]]] = {}
        self._dep_index: Dict[ObjectID, Set[TaskID]] = {}
        self._tasks: Dict[TaskID, TaskRecord] = {}

        self._workers: Dict[WorkerID, WorkerHandle] = {}
        self._idle: Dict[str, Deque[WorkerID]] = {"cpu": deque(), "tpu": deque()}
        self._starting_workers = {"cpu": 0, "tpu": 0}
        self._pending_types: Dict[WorkerID, str] = {}

        self._actors: Dict[ActorID, ActorInfo] = {}
        self._named_actors: Dict[str, ActorID] = {}

        self._functions: Dict[str, bytes] = {}
        self._kv: Dict[str, bytes] = {}

        self._sealed: Set[ObjectID] = set()
        self._seal_events: Dict[ObjectID, asyncio.Event] = {}
        # The futures of the wait_objects calls parked on each unsealed
        # object: one a call, on every object it waits for.
        self._parked_waits: Dict[ObjectID, List[asyncio.Future]] = {}
        self._pending_procs: Dict[WorkerID, subprocess.Popen] = {}

        # Cluster plane.
        self.gcs_service: Optional[GcsService] = None  # head only
        self._gcs = None  # LocalGcsHandle | RemoteGcsHandle | None
        self._gcs_client: Optional[GcsClient] = None  # remote only
        self._gcs_address = gcs_address
        self.peer_port: int = 0
        self._peer_server: Optional[asyncio.AbstractServer] = None
        # Striped transfer data plane (core/data_channel.py): raw-socket
        # listener advertised to pullers via the pull_object locate
        # reply; 0 = disabled (control-plane chunks only).
        self.data_port: int = 0
        self._data_server = None
        self._cluster_view: Dict[str, Dict[str, Any]] = {}  # hex -> view
        self._peers: Dict[str, PeerClient] = {}
        self._forwarded: Dict[TaskID, TaskRecord] = {}
        self._actor_homes: Dict[ActorID, str] = {}  # hex node or "dead"
        # Membership-fence plane (core/fencing.py). incarnation/epoch
        # come from the GCS register reply; _fenced_nodes holds peers
        # the GCS declared dead (their frames are refused and our
        # channels to them torn down) until a fresh incarnation of the
        # same node id rejoins; _fenced_self_epoch makes the zombie
        # self-termination idempotent per fence decision.
        self.incarnation = 0
        self.cluster_epoch = 0
        self._fenced_nodes: Dict[str, int] = {}  # hex -> fence epoch
        self._fenced_self_epoch = 0
        # Hook the co-resident driver runtime installs so a fence
        # broadcast tears down ITS direct channels to the fenced node
        # (worker/client runtimes learn via forwarded node_fenced
        # frames instead).
        self.on_node_fenced_runtime = None
        # Restart-elsewhere: the ORIGIN node of a restartable actor
        # creation (max_restarts != 0) pins the creation spec + a
        # restart budget, and re-places the actor on a surviving node
        # when its home is fenced (ref analogue:
        # GcsActorManager::OnNodeDead rescheduling).
        self._actor_creations: Dict[ActorID, TaskSpec] = {}
        self._actor_restart_budget: Dict[ActorID, int] = {}
        # Calls parked while a fenced actor restarts elsewhere: ONE
        # ordered queue per actor, drained FIFO once the new home
        # resolves — independent per-record polls would re-route them
        # in arbitrary order and break per-caller actor-call ordering
        # across the restart boundary.
        self._fence_parked: Dict[ActorID, List[TaskRecord]] = {}
        self._pulls: Dict[ObjectID, asyncio.Future] = {}
        self._heartbeat_task: Optional[asyncio.Task] = None
        # NM-process store client for the pull/push data path.
        self.local_store = LocalObjectStore()
        # Chunked, admission-controlled transfer plane (object_transfer.py).
        from .object_transfer import ObjectTransfer

        self._transfer = ObjectTransfer(self)
        # Placement-group bundles reserved on this node + pg routing cache.
        self._bundles: Dict[Tuple[str, int], BundleState] = {}
        self._pg_nodes: Dict[str, Dict[int, str]] = {}
        # Records parked on an in-flight pg-map resolution, keyed by pg id
        # (one GCS round-trip per group, not per record).
        self._pg_waiters: Dict[str, List[TaskRecord]] = {}

        # Strong refs to fire-and-forget coroutines so they are neither
        # GC'd mid-flight nor dropped unawaited at loop shutdown (advisor
        # r1: drop_named_actor cleanup was lost that way).
        self._bg_tasks: Set[asyncio.Task] = set()

        # Lineage table: return object -> creating TaskSpec, pinned while
        # the object's directory entry lives; re-executed to rebuild lost
        # objects (ref analogue: lineage pinning in reference_count.h:61 +
        # ObjectRecoveryManager re-execution via task_manager.h:195).
        self._lineage: Dict[ObjectID, TaskSpec] = {}
        self._reconstructions: Dict[ObjectID, int] = {}

        # Borrower protocol (ref analogue: reference_count.h:61 borrower
        # tracking). Borrower side: count-only stub entries created when a
        # ref to an object this node does not own is pinned or held here;
        # each registers this node with the owner and releases on local GC.
        self._borrow_stubs: Set[ObjectID] = set()
        self._borrowed_from: Dict[ObjectID, str] = {}  # oid -> owner hex
        # Acked client submits already accepted (bounded FIFO): dedups a
        # reconnect replay even after the task finished and left _tasks.
        from collections import OrderedDict as _OD

        self._recent_client_submits: "_OD[TaskID, None]" = _OD()
        self._borrow_registering: Set[ObjectID] = set()
        # Containment pins: container object -> refs serialized inside it
        # (a put'ed list of refs, a returned dict of refs). Pinned while
        # the container's entry lives; released when it is collected.
        self._nested_pins: Dict[ObjectID, List[ObjectID]] = {}

        # Profiling plane (ref analogue: `ray stack` + the reporter's
        # profile_manager): in-flight stack_dump/profile requests to this
        # node's workers, keyed by req_id (loop-thread only).
        self._profile_pending: Dict[int, asyncio.Future] = {}
        self._profile_req_seq = 0

        # Head-side leak sweep (util/data_obs.py): oids already warned
        # this leak episode (pruned when the object stops looking
        # leaked, so GC clears the dedup and a fresh leak warns again)
        # plus the one-sweep-in-flight guard.
        self._leak_warned: Set[str] = set()
        self._leak_last_sweep = 0.0
        self._leak_sweep_task: Optional[asyncio.Task] = None

        # Failure history: bounded deque of TERMINAL task records (state,
        # duration, error type/message) retained after the live record
        # leaves _tasks, merged into _local_state_snapshot so list_tasks
        # can answer "what failed" (ref analogue: the task-event buffer
        # retaining terminal states behind `ray summary tasks`).
        self._task_history: Deque[Dict[str, Any]] = deque(
            maxlen=config.task_history_size
        )

        self._stats = {
            "tasks_submitted": 0,
            "tasks_finished": 0,
            "tasks_failed": 0,
            "tasks_retried": 0,
            "workers_started": 0,
            "actors_created": 0,
            # Direct actor-call plane: completions reported by this
            # node's actor workers via direct_done_batch notifications,
            # and the number of batch frames that carried them (the
            # ratio shows the debounce coalescing under load).
            "direct_calls_done": 0,
            "direct_done_batches": 0,
        }
        # Dispatch-to-completion wall-time histogram for tasks executed on
        # this node (rendered as ray_tpu_task_duration_seconds by
        # util/prometheus._core_lines; ref analogue: the task-duration
        # metrics in src/ray/stats/metric_defs.h).
        bounds = [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                  60.0]
        self._task_duration = {
            "count": 0,
            "sum": 0.0,
            "bounds": bounds,
            "buckets": [0] * (len(bounds) + 1),
        }

    # ------------------------------------------------------------------ boot

    def start(self):
        self._thread.start()
        self._started.wait(timeout=30)
        if not self._started.is_set():
            raise RuntimeError(
                "node manager failed to start (GCS unreachable?)"
            )
        for _ in range(self.config.num_prestart_workers):
            self._loop.call_soon_threadsafe(self._spawn_worker)
        self.dashboard_agent = None
        if getattr(self.config, "dashboard_agent", True):
            try:
                from ..dashboard_agent import DashboardAgent

                self.dashboard_agent = DashboardAgent(
                    self, host=self.node_ip
                ).start()
            except Exception:
                self.dashboard_agent = None

    def _run_loop(self):
        asyncio.set_event_loop(self._loop)
        self._loop.run_until_complete(self._start_server())
        self._started.set()
        profile_to = os.environ.get("RAY_TPU_PROFILE_NM")
        if profile_to:
            import cProfile

            pr = cProfile.Profile()
            pr.enable()
            self._loop.run_forever()
            pr.disable()
            pr.dump_stats(profile_to)
        else:
            self._loop.run_forever()
        # Drain pending callbacks after stop().
        self._loop.run_until_complete(self._loop.shutdown_asyncgens())
        self._loop.close()

    async def _start_server(self):
        self._server = await asyncio.start_unix_server(
            self._handle_connection, path=self.socket_path
        )
        # Loop-health watchdog + GIL probe: the NM loop is the node's
        # control plane — a stall here stalls every worker frame.
        loop_monitor.attach("nm", self._loop)
        from ..util import profiler as _profiler

        _profiler.start_gil_monitor()
        # JSON control channel for native (C/C++) clients (ref
        # analogue: the cpp/ worker API's core-worker channel).
        from .capi_server import CapiServer

        self.capi_server = CapiServer(self)
        await self.capi_server.start(
            os.path.join(self.session_dir, "capi.sock")
        )
        # Peer channel for node<->node traffic (spillback + object pulls).
        from .tls import server_ssl_context

        self._peer_server = await asyncio.start_server(
            self._handle_peer_connection, host=self.node_ip, port=0,
            ssl=server_ssl_context(),
        )
        self.peer_port = self._peer_server.sockets[0].getsockname()[1]
        # Data plane: object payload rides dedicated raw stream sockets
        # (length-prefixed binary, zero-copy both ends) so a gigabyte
        # pull never holds the pickled control channel. Failure to start
        # is non-fatal — transfers then ride the chunk fallback.
        if self.config.transfer_streams_per_peer > 0:
            try:
                from .data_channel import DataPlaneServer

                self._data_server = DataPlaneServer(
                    self.node_ip, self.config.session_token,
                    self._transfer.open_range,
                    chunk_bytes=self.config.object_transfer_chunk_bytes,
                    max_streams=self.config.serve_chunks_in_flight,
                    on_served=self._transfer.on_range_served,
                    on_range_done=self._transfer.on_range_done,
                    io_timeout=self.config.transfer_io_timeout_s,
                )
                self.data_port = self._data_server.start()
            except Exception:
                self._data_server = None
                self.data_port = 0
        if self.is_head:
            self.gcs_service = GcsService(self.config, self._loop)
            await self.gcs_service.start(
                host=self.node_ip, port=self.config.gcs_port
            )
            self.gcs_service.on_node_added = self._on_gcs_node_added
            self.gcs_service.on_node_dead = self._on_gcs_node_dead
            self.gcs_service.on_load_update = self._on_gcs_load_update
            self.gcs_service.on_pgs_invalidated = self._invalidate_pgs
            self.gcs_service.on_node_draining = self._on_gcs_node_draining
            self.gcs_service.on_node_undrain = self._on_gcs_node_undrain
            self.gcs_service.on_chaos_update = self._on_gcs_chaos_update
            self.gcs_service.on_node_fenced = self._on_gcs_node_fenced
            self._gcs = LocalGcsHandle(self.gcs_service)
            reply = await self.gcs_service.register_node(
                self.node_id,
                self.node_ip,
                self.peer_port,
                self.node_resources.total.to_dict(),
                is_head=True,
                labels=self.labels,
            )
            self.incarnation = int(reply.get("incarnation") or 1)
            self.cluster_epoch = int(reply.get("epoch") or 0)
            self._apply_cluster_views(reply["nodes"])
        elif self._gcs_address is not None:
            await self._connect_gcs()
        self._heartbeat_task = asyncio.ensure_future(self._heartbeat_loop())
        self._gc_task = asyncio.ensure_future(self._gc_loop())
        self._health_task = asyncio.ensure_future(self._health_loop())
        self._memmon_task = asyncio.ensure_future(self._memory_monitor_loop())
        # This process's cluster-event transport: batches publish through
        # our GCS handle on this loop (node-manager processes have no
        # driver runtime for events to route through).
        cluster_events.set_publish_hook(self._publish_event_batch)

    def _publish_event_batch(self, batch: List[Dict[str, Any]]):
        """events.py flusher-thread entry: ship a drained batch via the
        GCS pubsub without blocking the flusher."""
        if self._shutdown or self._gcs is None:
            raise RuntimeError("node manager not connected")
        asyncio.run_coroutine_threadsafe(
            self._publish_events_async(list(batch)), self._loop
        )

    async def _publish_events_async(self, batch: List[Dict[str, Any]]):
        for e in batch:
            if e.get("node_id") is None:
                e["node_id"] = self.node_id.hex()
        try:
            await self._gcs.psub_publish(
                cluster_events.CLUSTER_EVENTS, batch
            )
        except Exception as e:  # noqa: BLE001
            sys.stderr.write(
                f"[ray_tpu] node {self.node_id.hex()[:8]}: cluster-event "
                f"publish failed ({e!r}); {len(batch)} event(s) dropped\n"
            )

    async def _connect_gcs(self):
        """Dial the GCS and register this node (first boot AND after a
        head restart — registration is idempotent by node id)."""
        client = GcsClient(
            self.node_id, self._gcs_address[0], self._gcs_address[1]
        )
        client.on_push = self._on_gcs_push
        await client.connect()
        try:
            reply = await client.request(
                {
                    "op": "register_node",
                    "host": self.node_ip,
                    "peer_port": self.peer_port,
                    "resources": self.node_resources.total.to_dict(),
                    "labels": self.labels,
                }
            )
        except BaseException:
            # A connected-but-unregistered client must not linger: its
            # reader task and on_push hook would mutate node state from an
            # abandoned socket on every retry.
            client.close()
            raise
        self._gcs_client = client
        self._gcs = RemoteGcsHandle(client)
        prev_incarnation = self.incarnation
        self.incarnation = int(reply.get("incarnation") or 1)
        self.cluster_epoch = max(
            self.cluster_epoch, int(reply.get("epoch") or 0)
        )
        self._apply_cluster_views(reply["nodes"])
        # Late joiner / reconnect: adopt the head's current chaos plan
        # (empty = disarm — correct after a head restart too).
        chaos = reply.get("chaos") or {}
        faults.apply_plan(chaos.get("specs") or [], chaos.get("gen"))
        fenced_at = int(reply.get("fenced_at") or 0)
        if fenced_at and prev_incarnation:
            # The reply says this node was declared dead at epoch
            # fenced_at while we were partitioned: the registration that
            # just happened is a FRESH incarnation, and the old one's
            # workers (stale actor incarnations, stale sealed objects)
            # must die before we resume — rejoining a split brain as-is
            # would double-execute calls and resurrect stale locations.
            await self._zombie_self_fence(fenced_at)

    async def _reconnect_gcs(self) -> bool:
        """Head-restart tolerance (ref analogue: NotifyGCSRestart,
        node_manager.proto:361 + gcs_rpc_server_reconnect_timeout_s,
        ray_config_def.h:451): a worker node that loses the GCS retries
        the address with backoff, re-registers, and re-publishes its local
        truth — named actors homed here and sealed object locations — so
        the restarted head rebuilds runtime state from the survivors."""
        wait = Backoff(
            base=0.5, factor=1.5, max_delay=3.0, jitter=0.2,
            deadline_s=self.config.gcs_reconnect_timeout_s,
        )
        sys.stderr.write(
            "[ray_tpu] GCS connection lost; attempting reconnect\n"
        )
        while not wait.expired and not self._shutdown:
            try:
                await self._connect_gcs()
            # The retry loop IS the handler (jittered backoff, deadline
            # bounded); final expiry is reported after the loop.
            except Exception:  # rtlint: disable=swallowed-failure
                if not await wait.async_sleep():
                    break
                continue
            await self._republish_to_gcs()
            sys.stderr.write("[ray_tpu] reconnected to restarted GCS\n")
            return True
        return False

    async def _republish_to_gcs(self):
        """After the head restarts from its snapshot, runtime state lives
        only on surviving nodes: push ours back."""
        # list(): each await below yields the loop to handlers that may
        # mutate _actors mid-iteration.
        for info in list(self._actors.values()):
            if info.state not in ("alive", "restarting", "pending"):
                continue
            spec = info.creation_spec
            try:
                # Reconnect re-registration: pass the incarnation we
                # already run as — the GCS must NOT mint a new one (the
                # actor did not restart, the head did).
                await self._gcs.register_actor_node(
                    spec.actor_id, self.node_id,
                    incarnation=info.incarnation,
                )
                if spec.name:
                    await self._gcs.register_named_actor(
                        spec.name, spec.actor_id, self.node_id, spec
                    )
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(
                    f"[ray_tpu] node {self.node_id.hex()[:8]}: actor "
                    f"{spec.actor_id.hex()[:8]} re-registration after "
                    f"reconnect failed ({e!r}); named lookups may miss "
                    f"it until the next reconnect\n"
                )
        await self._publish_all_sealed()

    async def _zombie_self_fence(self, epoch: int):
        """This node learned it was declared dead at ``epoch`` while it
        was (asymmetrically) partitioned. The cluster has moved on:
        peers tore down their channels, restartable actors restarted
        elsewhere, lineage re-executed what we owned. Resuming the old
        identity would split the brain — callers holding cached direct
        endpoints would execute against stale actor incarnations and
        our sealed-object republish would resurrect locations consumers
        already recovered away from. So: kill the workers (the stale
        incarnations die with them), drop queued work and local state,
        and continue as the fresh incarnation the re-register reply
        assigned — empty, but a first-class member again."""
        if self._fenced_self_epoch >= epoch:
            return  # already fenced for this (or a later) decision
        self._fenced_self_epoch = epoch
        _fencing.ZOMBIE_KILLS.inc()
        workers = [
            w for w in self._workers.values()
            if w.state != "dead" and w.worker_type != "client"
        ]
        cluster_events.emit(
            cluster_events.WARNING, cluster_events.NODE,
            f"node {self.node_id.hex()[:8]} was declared dead at epoch "
            f"{epoch} while partitioned: terminating "
            f"{len(workers)} worker(s) and rejoining as incarnation "
            f"{self.incarnation} with empty state (zombie fencing)",
            node_id=self.node_id.hex(),
            custom_fields={"epoch": epoch,
                           "incarnation": self.incarnation,
                           "workers_killed": len(workers)},
        )
        # Mark every actor dead BEFORE the kills so the worker-death
        # handler cannot restart a stale incarnation locally.
        for info in self._actors.values():
            if info.state == "dead":
                continue
            info.state = "dead"
            info.death_cause = "node fenced (zombie incarnation terminated)"
            info.restarts_left = 0
            for rec in list(info.inflight.values()):
                self._fail_task(
                    rec, ActorDiedError(rec.spec.name, info.death_cause)
                )
            info.inflight.clear()
            self._fail_actor_queue(info, info.death_cause)
        # Cooperative kill first (lets completion buffers and the event
        # ring's tail flush), hard kill whatever outlives the grace.
        for w in workers:
            w._intentional_kill = True
            try:
                await w.writer.send({"type": "kill"})
            # Dying worker — the hard kill below covers it.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
        grace = max(
            0.0, float(getattr(self.config, "fence_kill_grace_s", 1.0))
        )
        deadline = self._loop.time() + grace
        while self._loop.time() < deadline and any(
            w.proc is not None and w.proc.poll() is None for w in workers
        ):
            await asyncio.sleep(0.05)
        for w in workers:
            if w.proc is not None and w.proc.poll() is None:
                try:
                    w.proc.kill()
                # Already reaped between the poll and the kill.
                except Exception:  # rtlint: disable=swallowed-failure
                    pass
        # Stale state must not resurrect: nothing sealed here is
        # publishable (consumers re-located or re-executed during the
        # fence window), queued work was already re-executed by its
        # owners' lineage after the death broadcast, and remote-actor
        # routing caches re-resolve through the GCS.
        self._sealed.clear()
        self._ready = _ReadyQueue(self._sched_class)
        self._waiting.clear()
        self._dep_index.clear()
        self._named_actors.clear()
        self._actor_homes.clear()
        try:
            cluster_events.flush()
        # Event transport mid-reconnect: the ring keeps the record.
        except Exception:  # rtlint: disable=swallowed-failure
            pass

    # ------------------------------------------------------- cluster plumbing

    @property
    def _multi_node(self) -> bool:
        return len(self._cluster_view) > 1

    def _apply_cluster_views(self, views):
        for v in views:
            if v["state"] in ("alive", "draining"):
                # Draining nodes stay REACHABLE (they push replicas at
                # us and answer pulls until exit) — a late joiner must
                # keep them in view or _get_peer fails mid-drain; the
                # schedulers already skip any non-"alive" state.
                self._cluster_view[v["node_id"]] = v
                # A live view of a previously fenced node id is a FRESH
                # incarnation rejoining (the GCS only re-admits after
                # re-registration, and the zombie self-terminated its
                # old incarnation first): stop refusing its frames.
                self._fenced_nodes.pop(v["node_id"], None)
            else:
                self._cluster_view.pop(v["node_id"], None)
            epoch = v.get("epoch")
            if epoch:
                self.cluster_epoch = max(self.cluster_epoch, int(epoch))

    def _local_view(self, include_shapes: bool = False) -> Dict[str, Any]:
        view = {
            "node_id": self.node_id.hex(),
            "host": self.node_ip,
            "peer_port": self.peer_port,
            "resources_total": self.node_resources.total.to_dict(),
            "resources_available": self.node_resources.available.to_dict(),
            "pending_tasks": (
                len(self._ready) + len(self._waiting)
                + sum(len(w.pending) for w in self._workers.values()
                      if w.state != "dead")
            ),
            "is_head": self.is_head,
            # Draining: still reachable, never schedulable (pick_node /
            # place_bundles filter to state == "alive").
            "state": "draining" if self._draining else "alive",
            "labels": self.labels,
            "incarnation": self.incarnation,
            "epoch": self.cluster_epoch,
        }
        if include_shapes:
            # O(queue) — heartbeat-rate only, never per _schedule pass.
            view["pending_shapes"] = self._pending_shapes()
        return view

    def _pending_shapes(self, cap: int = 32):
        """Aggregate queued-task resource shapes for the autoscaler (ref:
        resource_load_by_shape in gcs.proto / resource_demand_scheduler.py).
        Returns [[shape_dict, count], ...], at most ``cap`` distinct shapes.
        Ready records are already class-bucketed (shape at key index 1), so
        this is O(#classes + #waiting), not O(#queued)."""
        counts: Dict[Tuple, int] = {}
        for cls, q in self._ready.classes.items():
            key = cls[1]
            if key not in counts and len(counts) >= cap:
                continue  # cap DISTINCT shapes, keep counting known ones
            counts[key] = counts.get(key, 0) + len(q)
        for rec, _missing in self._waiting.values():
            try:
                shape = rec.spec.resources.to_dict()
            # A malformed shape only drops one row from the autoscaler
            # demand report; the task itself is untouched.
            except Exception:  # rtlint: disable=swallowed-failure
                continue
            key = tuple(sorted(shape.items()))
            if key not in counts and len(counts) >= cap:
                continue
            counts[key] = counts.get(key, 0) + 1
        # Lease riders: tasks queued in a worker's pipeline have NOT
        # started — they are latent demand exactly like ready-queue
        # entries (without this, riding hides parallelizable work from
        # the autoscaler: 6 queued CPU-seconds on a 1-CPU node would
        # look satisfied). Report them under their shape.
        for w in self._workers.values():
            if w.state == "dead" or not w.pending:
                continue
            for rec in w.pending:
                try:
                    shape = rec.spec.resources.to_dict()
                # Same contract as the waiting-queue rows above.
                except Exception:  # rtlint: disable=swallowed-failure
                    continue
                key = tuple(sorted(shape.items()))
                if key not in counts and len(counts) >= cap:
                    continue
                counts[key] = counts.get(key, 0) + 1
        return [[dict(k), n] for k, n in counts.items()]

    def _on_gcs_node_added(self, entry):
        was_single = not self._multi_node
        self._cluster_view[entry.node_id.hex()] = entry.view()
        if was_single and self._multi_node:
            # Objects sealed while the head was alone were never published;
            # back-publish so new nodes can locate them.
            asyncio.ensure_future(self._publish_all_sealed())
        self._schedule()

    async def _publish_all_sealed(self):
        failed = 0
        for oid in list(self._sealed):
            loc = self.directory.lookup(oid)
            if loc is not None and not isinstance(loc, RemoteLocation):
                try:
                    await self._gcs.publish_object(oid, self.node_id)
                # Aggregated into ONE stderr warning below the loop.
                except Exception:  # rtlint: disable=swallowed-failure
                    failed += 1
        if failed:
            sys.stderr.write(
                f"[ray_tpu] node {self.node_id.hex()[:8]}: {failed} "
                f"sealed object(s) failed to re-publish after reconnect; "
                f"remote consumers may need the next reconnect to "
                f"locate them\n"
            )

    def _on_gcs_node_dead(self, entry):
        asyncio.ensure_future(
            self._on_node_dead_hex(entry.node_id.hex(), dead_actors=None)
        )

    def _on_gcs_node_fenced(self, entry, epoch: int):
        """Head-side hook for the GCS fence decision (remote nodes
        learn via the node_fenced broadcast)."""
        self._on_node_fenced(entry.node_id.hex(), epoch,
                             getattr(entry, "incarnation", 0))

    def _on_node_fenced(self, node_hex: str, epoch: int,
                        incarnation: int = 0):
        """The GCS fenced ``node_hex`` at membership epoch ``epoch``:
        stop trusting that incarnation NOW. Our direct channels to it
        are torn down (the co-resident driver runtime via the installed
        hook, worker/client runtimes via forwarded node_fenced frames);
        the reader failure path parks their in-flight calls into the
        exactly-once NM replay path, where calls bound to the fenced
        incarnation are REFUSED rather than re-executed. Subsequent
        peer frames from the fenced node are dropped until a fresh
        incarnation of it rejoins."""
        if epoch:
            self.cluster_epoch = max(self.cluster_epoch, int(epoch))
        if node_hex == self.node_id.hex():
            # We can still hear the GCS but IT declared US dead (e.g. a
            # one-way partition where only our sends are lost): fence
            # ourselves now; the reconnect loop re-registers fresh.
            asyncio.ensure_future(
                self._zombie_self_fence(epoch or self.cluster_epoch)
            )
            return
        self._fenced_nodes[node_hex] = epoch
        _fencing.EVENT_CHANNEL_TEARDOWN.inc()
        hook = self.on_node_fenced_runtime
        if hook is not None:
            try:
                hook(node_hex, epoch)
            except Exception as e:  # noqa: BLE001
                sys.stderr.write(
                    f"[ray_tpu] node {self.node_id.hex()[:8]}: driver "
                    f"fence hook failed ({e!r}); its direct channels "
                    f"to {node_hex[:8]} die on next use instead\n"
                )
        asyncio.ensure_future(
            self._broadcast_fence_to_workers(node_hex, epoch)
        )

    async def _broadcast_fence_to_workers(self, node_hex: str,
                                          epoch: int):
        """Forward the fence decision to every local worker AND thin
        client: their runtimes hold their own direct channels to the
        fenced node's actors (healthy sockets under an asymmetric
        partition — they would keep executing calls on the stale
        incarnation without this)."""
        frame = {"type": "node_fenced", "node_id": node_hex,
                 "epoch": epoch}
        for w in list(self._workers.values()):
            if w.state == "dead":
                continue
            try:
                await w.writer.send(dict(frame))
            # Dying worker/client: its channels die with the process.
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    def _on_gcs_node_draining(self, entry):
        """Head-side hook for the GCS drain RPC (remote nodes learn via
        the node_draining broadcast)."""
        self._on_peer_draining(entry.node_id.hex())

    def _on_peer_draining(self, node_hex: str):
        """A node began draining: keep it REACHABLE (in-flight actor
        traffic and the drain RPC itself still flow) but unschedulable —
        pick_node/place_bundles skip non-alive views, so marking the
        view is enough to stop new forwards/creations landing there.
        When the draining node is THIS one, local workers are told too
        (``node_draining`` frames → core/preemption.py), so cooperative
        tenants — above all a train gang — checkpoint at their next
        step boundary and surrender the node instead of dying with it."""
        if node_hex == self.node_id.hex():
            self._draining = True
            asyncio.ensure_future(self._broadcast_drain_to_workers(True))
            return
        view = self._cluster_view.get(node_hex)
        if view is not None:
            view["state"] = "draining"

    def _on_gcs_node_undrain(self, entry):
        """Head-side hook for a drain rollback (remote nodes learn via
        the node_undrain broadcast)."""
        self._on_peer_undrain(entry.node_id.hex())

    def _on_peer_undrain(self, node_hex: str):
        """A drain was aborted: the node rejoins the schedulable pool."""
        if node_hex == self.node_id.hex():
            self._draining = False
            asyncio.ensure_future(self._broadcast_drain_to_workers(False))
            return
        view = self._cluster_view.get(node_hex)
        if view is not None and view.get("state") == "draining":
            view["state"] = "alive"

    async def _broadcast_drain_to_workers(self, draining: bool):
        """Forward this node's drain state to every local worker
        process (the worker-side signal behind TrainSession.preemption)."""
        frame = {
            "type": "node_draining" if draining else "node_undrain",
            "node_id": self.node_id.hex(),
        }
        for w in list(self._workers.values()):
            if w.state == "dead" or w.worker_type == "client":
                continue
            try:
                await w.writer.send(dict(frame))
            except Exception:  # rtlint: disable=swallowed-failure
                pass  # dying worker; the drain proceeds regardless

    def _on_gcs_chaos_update(self, specs, gen):
        """Head-side hook: the GCS applied the plan in this process
        already; forward it to this node's workers."""
        asyncio.ensure_future(self._broadcast_chaos_to_workers(specs, gen))

    def _apply_chaos(self, specs, gen):
        faults.apply_plan(specs or [], gen)
        asyncio.ensure_future(self._broadcast_chaos_to_workers(specs, gen))

    async def _broadcast_chaos_to_workers(self, specs, gen):
        frame = {"type": "chaos_update", "specs": list(specs or []),
                 "gen": gen}
        for w in list(self._workers.values()):
            if w.state == "dead" or w.worker_type == "client":
                continue
            try:
                await w.writer.send(dict(frame))
            # Dying worker: it re-adopts the current plan in its next
            # registration reply; nothing to do here.
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    def _on_gcs_load_update(self, msg):
        self._apply_cluster_views(msg["nodes"])

    async def _on_gcs_push(self, msg: Dict[str, Any]):
        mtype = msg["type"]
        if mtype == "node_added":
            self._apply_cluster_views([msg["node"]])
            self._schedule()
        elif mtype == "cluster_load":
            self._apply_cluster_views(msg["nodes"])
        elif mtype == "node_fenced":
            self._on_node_fenced(
                msg["node_id"], int(msg.get("epoch") or 0),
                int(msg.get("incarnation") or 0),
            )
        elif mtype == "node_dead":
            self._invalidate_pgs(msg.get("invalid_pgs") or [])
            await self._on_node_dead_hex(
                msg["node_id"], dead_actors=msg.get("dead_actors")
            )
        elif mtype == "node_draining":
            self._on_peer_draining(msg["node_id"])
        elif mtype == "node_undrain":
            self._on_peer_undrain(msg["node_id"])
        elif mtype == "chaos_update":
            self._apply_chaos(msg.get("specs") or [], msg.get("gen"))

    async def _heartbeat_loop(self):
        interval = self.config.heartbeat_interval_s
        while not self._shutdown:
            await asyncio.sleep(interval)
            # Chaos plane: a suppressed heartbeat looks exactly like a
            # lost load report — the GCS death sweep eventually declares
            # this node dead. Only the SEND is faulted: the reconnect
            # branch below stays live, so after the death broadcast the
            # node re-registers and receives the current plan (a
            # disarmed plan heals it; an armed one keeps it flapping,
            # which is what a heartbeat-only partition really does).
            suppressed = False
            try:
                delay = faults.fire(faults.HEARTBEAT)
                if delay:
                    await asyncio.sleep(delay)
            except faults.InjectedFault:
                suppressed = True
            view = self._local_view(include_shapes=True)
            self._cluster_view[view["node_id"]] = view
            if self.is_head and self.gcs_service is not None:
                if not suppressed:
                    self.gcs_service.heartbeat(
                        self.node_id,
                        view["resources_available"],
                        view["pending_tasks"],
                        view.get("pending_shapes"),
                    )
            elif self._gcs_client is not None and not self._gcs_client.closed:
                if not suppressed:
                    try:
                        await self._gcs_client.notify(
                            {
                                "op": "heartbeat",
                                "available": view["resources_available"],
                                "pending": view["pending_tasks"],
                                "shapes": view.get("pending_shapes"),
                                "msg_id": None,
                            }
                        )
                    except Exception:
                        pass
            elif self._gcs_client is not None and self._gcs_client.closed:
                # Head gone: try to ride out a GCS restart before giving
                # up (the node only dies once the reconnect window ends).
                if not await self._reconnect_gcs():
                    sys.stderr.write(
                        "[ray_tpu] GCS gone past reconnect window; "
                        "exiting node\n"
                    )
                    os._exit(1)

    async def _health_loop(self):
        """Detect workers that died before registering (e.g. import errors)
        so pending tasks fail loudly instead of hanging (ref analogue:
        WorkerPool startup-failure handling + GcsHealthCheckManager)."""
        consecutive_failures = 0
        while not self._shutdown:
            await asyncio.sleep(0.5)
            for worker_id, proc in list(self._pending_procs.items()):
                if worker_id not in self._pending_procs:
                    # The log-tail await below yields the loop: a later
                    # snapshot entry may have registered (and been
                    # popped) during an earlier iteration's hop — its
                    # accounting already happened at registration.
                    continue
                if proc.poll() is None:
                    continue
                self._pending_procs.pop(worker_id, None)
                wtype = self._pending_types.pop(worker_id, "cpu")
                self._starting_workers[wtype] = max(
                    0, self._starting_workers[wtype] - 1
                )
                consecutive_failures += 1
                log = os.path.join(
                    self.session_dir, "logs", f"worker-{worker_id.hex()[:8]}.log"
                )
                # Crash diagnosis reads the log tail off the loop: the
                # old inline read pulled the WHOLE file through the loop
                # thread (rtlint loop-blocking).
                detail = await self._loop.run_in_executor(
                    None, _read_text_tail, log, 2000
                )
                sys.stderr.write(
                    f"[ray_tpu] worker {worker_id.hex()[:8]} exited during "
                    f"startup (code {proc.returncode}). Log tail:\n{detail}\n"
                )
                cluster_events.emit(
                    cluster_events.ERROR, cluster_events.WORKER,
                    f"worker {worker_id.hex()[:8]} exited during startup "
                    f"(code {proc.returncode})",
                    node_id=self.node_id.hex(),
                    custom_fields={"exit_code": proc.returncode,
                                   "log_tail": detail[-500:]},
                )
                if consecutive_failures >= 3:
                    # Workers cannot start at all: fail queued work loudly.
                    while self._ready:
                        rec = self._ready.popleft()
                        self._fail_task(
                            rec,
                            TaskError(
                                None,
                                rec.spec.name,
                                f"worker processes fail to start; last log:\n"
                                f"{detail}",
                            ),
                        )
                else:
                    self._schedule()
            if self._workers:
                consecutive_failures = 0
            # Hang/straggler sweep rides the same cadence; detected
            # records warn via background tasks so the stack capture's
            # round-trip never stalls this loop.
            try:
                await self._check_hung_tasks()
            except Exception as e:  # noqa: BLE001
                if not getattr(self, "_hang_sweep_warned", False):
                    self._hang_sweep_warned = True
                    sys.stderr.write(
                        f"[ray_tpu] node {self.node_id.hex()[:8]}: "
                        f"hang-diagnosis sweep failed ({e!r}); further "
                        f"failures suppressed\n"
                    )
            # Data-plane stall watchdog rides the same 0.5 s cadence:
            # publishes the live stalled{peer} gauge and emits one
            # deduped WARNING + flight-recorder record per stall
            # episode (check_stalls itself never raises).
            transfer = getattr(self, "_transfer", None)
            if transfer is not None:
                transfer.check_stalls()
            # Head-side leak sweep: kicks a background census fan-out
            # when due (the fan-out can wait out a dead node's timeout,
            # so it never rides this loop inline).
            if self.is_head:
                self._maybe_leak_sweep()

    def _call(self, coro):
        """Run a coroutine on the loop from a foreign thread."""
        return asyncio.run_coroutine_threadsafe(coro, self._loop)

    def call_sync(self, coro, timeout: Optional[float] = None):
        return self._call(coro).result(timeout)

    # ------------------------------------------------------- worker lifecycle

    def _spawn_worker(self, worker_type: str = "cpu"):
        """Synchronous spawn entry: reserves the starting-worker slot
        immediately so back-to-back scheduler passes can't over-spawn."""
        self._starting_workers[worker_type] += 1
        asyncio.ensure_future(self._spawn_worker_async(worker_type))

    async def _spawn_worker_async(self, worker_type: str = "cpu") -> WorkerID:
        worker_id = WorkerID.from_random()
        try:
            # Chaos plane: a suppressed spawn releases its starting slot
            # so the next scheduler pass simply retries (the advertised
            # degradation for worker_spawn).
            delay = faults.fire(faults.WORKER_SPAWN,
                                worker_type=worker_type)
            if delay:
                await asyncio.sleep(delay)
        except faults.InjectedFault:
            self._starting_workers[worker_type] = max(
                0, self._starting_workers[worker_type] - 1
            )
            self._schedule()
            return worker_id
        env = dict(os.environ)
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        env["RAY_TPU_NODE_SOCKET"] = self.socket_path
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        env["RAY_TPU_WORKER_TYPE"] = worker_type
        # Direct actor-call plane: the worker's TCP listener binds this
        # node's advertised IP, and its hello handshake + TLS wrap need
        # the session security config even when it was set through
        # system_config rather than the environment.
        env["RAY_TPU_NODE_IP"] = self.node_ip
        if self.config.session_token:
            env["RAY_TPU_SESSION_TOKEN"] = self.config.session_token
        if self.config.tls_cert_path:
            env["RAY_TPU_TLS_CERT_PATH"] = self.config.tls_cert_path
            env["RAY_TPU_TLS_KEY_PATH"] = self.config.tls_key_path
            env["RAY_TPU_TLS_CA_PATH"] = self.config.tls_ca_path
        # Task print() output must reach the log file (and the driver's log
        # monitor) as it happens, not at process exit.
        env["PYTHONUNBUFFERED"] = "1"
        if self.arena_name:
            env["RAY_TPU_ARENA"] = self.arena_name
        # Ensure the worker can import this package even when the driver was
        # launched from elsewhere with ray_tpu on sys.path but not installed.
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        existing_pp = env.get("PYTHONPATH", "")
        if pkg_root not in existing_pp.split(os.pathsep):
            env["PYTHONPATH"] = (
                pkg_root + (os.pathsep + existing_pp if existing_pp else "")
            )
        # The chip is exclusive to one process: only "tpu"-typed workers
        # may open it, and for them a chip that cannot be opened is an
        # error, never a CPU run.
        env.update(worker_jax_env(worker_type, env))
        # Type registered BEFORE the executor hop: a worker that boots
        # fast enough to register during the await below must pop its
        # real type (and decrement the right starting slot), not the
        # "cpu" default.
        self._pending_types[worker_id] = worker_type
        # fork+exec and the log-file open are milliseconds of blocking
        # syscalls — off the loop (rtlint loop-blocking), so a spawn
        # burst can't stall heartbeats/dispatch for the whole batch.
        try:
            proc = await self._loop.run_in_executor(
                None, self._spawn_worker_proc, worker_id, env
            )
        except OSError as e:
            # Spawn itself failed (unwritable log dir, EMFILE, ENOMEM):
            # release the starting slot so the scheduler retries instead
            # of waiting forever on a worker that never forked.
            self._pending_types.pop(worker_id, None)
            self._starting_workers[worker_type] = max(
                0, self._starting_workers[worker_type] - 1
            )
            cluster_events.emit(
                cluster_events.WARNING, cluster_events.WORKER,
                f"worker spawn failed before exec: {e!r}",
                node_id=self.node_id.hex(),
                custom_fields={"worker_type": worker_type,
                               "error_type": type(e).__name__},
            )
            self._schedule()
            return worker_id
        self._stats["workers_started"] += 1
        cluster_events.emit(
            cluster_events.DEBUG, cluster_events.WORKER,
            f"worker {worker_id.hex()[:8]} spawned "
            f"(pid {proc.pid}, type {worker_type})",
            node_id=self.node_id.hex(),
            custom_fields={"pid": proc.pid, "worker_type": worker_type},
        )
        if worker_id in self._workers:
            # Registration won the race against this resume: attach the
            # proc to the live handle (shutdown waits on it) instead of
            # parking a stale entry the health loop would misread as a
            # startup crash when the worker eventually exits.
            self._workers[worker_id].proc = proc
            return worker_id
        if worker_id not in self._pending_types:
            # Registered AND died during the hop: registration consumed
            # the type entry and _on_worker_death already did the death
            # accounting. Reap the exit status here; parking the proc
            # would make the health loop double-count the death as a
            # startup crash.
            proc.poll()
            return worker_id
        if self._shutdown:
            # Spawned into a closing node: the shutdown sweep already
            # drained _pending_procs, so reap the orphan here.
            try:
                proc.terminate()
            except OSError:
                pass  # already dead: nothing to reap
            self._pending_types.pop(worker_id, None)
            return worker_id
        # The handle is registered when the worker connects and
        # registers (_pending_types was set before the executor hop).
        self._pending_procs[worker_id] = proc
        return worker_id

    def _spawn_worker_proc(self, worker_id: WorkerID, env) -> "subprocess.Popen":
        """Blocking half of the worker spawn (log dir/file + fork+exec);
        runs in the loop's default executor, never on the loop."""
        log_path = os.path.join(self.session_dir, "logs")
        os.makedirs(log_path, exist_ok=True)
        out = open(os.path.join(
            log_path, f"worker-{worker_id.hex()[:8]}.log"), "wb")
        try:
            return subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.core.worker_main"],
                env=env,
                stdout=out,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        finally:
            out.close()

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ):
        framed = _FramedWriter(writer)
        handle: Optional[WorkerHandle] = None
        try:
            msg = await _read_frame(reader)
            if msg.get("type") != "register":
                framed.close()
                return
            worker_id = WorkerID.from_hex(msg["worker_id"])
            proc = self._pending_procs.pop(worker_id, None)
            wtype = self._pending_types.pop(worker_id, "cpu")
            handle = WorkerHandle(
                worker_id=worker_id, writer=framed, proc=proc, worker_type=wtype
            )
            self._workers[worker_id] = handle
            self._starting_workers[wtype] = max(0, self._starting_workers[wtype] - 1)
            self._idle[wtype].append(worker_id)
            await framed.send({
                "type": "registered", "node_id": self.node_id.hex(),
                # Workers born under an armed chaos plan adopt it with
                # their registration ack (updates arrive as
                # chaos_update frames).
                "chaos": {"specs": faults.current_plan(),
                          "gen": faults.generation()},
            })
            self._schedule()
            while True:
                msg = await _read_frame(reader)
                await self._dispatch_message(handle, msg,
                                             time.monotonic())
        except (asyncio.IncompleteReadError, ConnectionResetError, OSError):
            pass
        finally:
            if handle is not None:
                await self._on_worker_death(handle)
            framed.close()

    async def _dispatch_message(self, w: WorkerHandle, msg: Dict[str, Any],
                                recv_ts: Optional[float] = None):
        """Stage-clocked entry for every worker/client frame: queue-wait
        is recv->here, the handler stage covers the branch body, and
        branches that reply stamp handler_done via _send_reply so the
        flush shows up as reply_send. Deferred branches hand their clock
        to _bg_op and close it when the background handler finishes."""
        clock = dispatch_obs.op_clock("nm", msg.get("type"), recv_ts)
        if clock is not None:
            clock.start()
        try:
            await self._dispatch_message_op(w, msg, clock)
        finally:
            if clock is not None and not clock.deferred:
                clock.done()

    async def _send_reply(self, clock, w: WorkerHandle,
                          payload: Dict[str, Any]):
        if clock is not None:
            clock.handler_done()
        await w.writer.send(payload)

    async def _dispatch_message_op(self, w: WorkerHandle,
                                   msg: Dict[str, Any], clock=None):
        mtype = msg["type"]
        w.last_active = time.monotonic()
        if mtype == "task_done":
            await self._on_task_done(w, msg)
        elif mtype == "task_done_batch":
            # One wakeup for a burst of completions (the worker coalesces
            # dones while more queued tasks are waiting); _schedule() is
            # debounced so the batch costs one dispatch pass.
            for item in msg["items"]:
                await self._on_task_done(w, item)
        elif mtype == "submit":
            spec = msg["spec"]
            # Dedup by task_id: a thin client replaying a submit after a
            # connection blip must not double-queue the task. Live tasks
            # dedup against the record table; FAST tasks that finished
            # during the redial dedup against a bounded recent-ids set
            # (only acked submits are recorded — fire-and-forget worker
            # submits never replay).
            acked = msg.get("msg_id") is not None
            seen = (spec.task_id in self._tasks
                    or spec.task_id in self._recent_client_submits)
            if not seen:
                if acked:
                    self._recent_client_submits[spec.task_id] = None
                    while len(self._recent_client_submits) > 8192:
                        self._recent_client_submits.popitem(last=False)
                await self.submit_task(spec)
            if acked:
                await self._send_reply(clock, w, {
                    "type": "reply", "msg_id": msg["msg_id"], "ok": True,
                })
        elif mtype == "get_locations":
            self._bg_op(clock, self._reply_locations(w, msg))
        elif mtype == "wait":
            # A stream consumer's wait sends no ``blocked`` / ``unblocked``
            # frame: the book is kept here, round the request, and only
            # if it parks. Blocked at the frame's own place in the
            # worker's order, as that frame was: the producer a task
            # submitted just before must not be queued behind it.
            parks = bool(msg.get("carry")) and self._would_park(
                msg["object_ids"], msg.get("timeout"))
            if parks:
                self._on_worker_blocked(w)
            self._bg_op(clock, self._reply_wait(w, msg, parks))
        elif mtype == "put":
            await self.put_object(
                msg["object_id"], msg["loc"], msg.get("refs", 1),
                pin_if_new=msg.get("pin_if_new", False),
                nested=msg.get("nested"),
            )
        elif mtype == "add_refs":
            for oid in msg["object_ids"]:
                self._pin_ref_bg(oid)
        elif mtype == "remove_refs":
            for oid, count in msg["counts"].items():
                self._remove_ref(oid, count)
        elif mtype == "fetch_function":
            await self._send_reply(
                clock, w,
                {
                    "type": "reply",
                    "msg_id": msg["msg_id"],
                    "blob": await self._function_blob(msg["function_id"]),
                }
            )
        elif mtype == "register_function":
            await self.register_function(msg["function_id"], msg["blob"])
        elif mtype == "blocked":
            self._on_worker_blocked(w)
        elif mtype == "unblocked":
            self._on_worker_unblocked(w)
        elif mtype == "reclaimed":
            self._on_tasks_reclaimed(w, msg)
        elif mtype == "kv":
            await self._handle_kv(w, msg)
        elif mtype == "pubsub":
            # Long-polls block; never hold up the worker's message loop.
            self._bg_op(clock, self._handle_pubsub(w, msg))
        elif mtype == "pg":
            self._bg_op(clock, self._handle_pg(w, msg))
        elif mtype == "actor_direct":
            if w.actor_id is not None:
                info = self._actors.get(w.actor_id)
                if info is not None:
                    info.direct_path = msg["path"]
                    addr = msg.get("addr")
                    info.direct_addr = tuple(addr) if addr else None
                    info.direct_ver = msg.get("ver", 1)
        elif mtype == "get_actor_direct":
            # Endpoint resolution long-polls the actor's drain window;
            # never inline it on this worker's message loop.
            self._bg_op(clock, self._reply_actor_direct(w, msg))
        elif mtype == "direct_side":
            # Caller-side bookkeeping for direct calls (the worker/client
            # mirror of the driver's dpost drain): return-slot
            # placeholders + arg pins at submit, seals/nested/unpins at
            # completion — one coalesced frame per burst.
            for oid in msg.get("returns", ()):
                self.directory.add(oid, _RETURN_PLACEHOLDER,
                                   initial_refs=0)
            for oid in msg.get("pins", ()):
                self._pin_ref_bg(oid)
            for oid, loc in msg.get("seals", ()):
                self._seal_object(oid, loc)
            for roid, inner in msg.get("nested", ()):
                self._register_nested(roid, inner)
            for oid, count in (msg.get("unpin") or {}).items():
                self._remove_ref(oid, count)
        elif mtype == "direct_done_batch":
            await self._on_direct_done_batch(w, msg)
        elif mtype == "actor_exit":
            await self._on_actor_graceful_exit(w, msg)
        elif mtype == "kill_actor":
            await self.kill_actor(msg["actor_id"], msg.get("no_restart", True))
        elif mtype == "cancel_task":
            await self.cancel_task(msg["task_id"], msg.get("force", False))
        elif mtype == "get_named_actor":
            spec = await self.get_named_actor(msg["name"])
            await self._send_reply(
                clock, w,
                {"type": "reply", "msg_id": msg["msg_id"], "spec": spec}
            )
        elif mtype == "state":
            state = await self.cluster_state()
            await self._send_reply(
                clock, w,
                {"type": "reply", "msg_id": msg["msg_id"], "state": state}
            )
        elif mtype == "events":
            # Head-store query; the long-path RPC must not stall this
            # worker's message loop.
            self._bg_op(clock, self._handle_events_query(w, msg))
        elif mtype == "timeseries":
            self._bg_op(clock, self._handle_timeseries_query(w, msg))
        elif mtype == "slo":
            self._bg_op(clock, self._handle_slo_query(w, msg))
        elif mtype in ("stack_reply", "profile_reply"):
            # A worker answering our stack_dump/profile fan-out.
            fut = self._profile_pending.pop(msg.get("req_id"), None)
            if fut is not None and not fut.done():
                fut.set_result(msg)
        elif mtype == "profile":
            # Cluster stacks/profile query from a worker or thin client;
            # the fan-out blocks on timeouts, so never inline it here.
            self._bg_op(clock, self._handle_profile_query(w, msg))
        elif mtype == "pull_object":
            # Client-mode read rides the SAME chunked, admission-
            # controlled transfer plane nodes use (small objects answer
            # inline; large ones advertise chunking — no multi-GB frames,
            # no event-loop-sized pickles).
            reply = await self._transfer.serve_pull(msg)
            reply.update({"type": "reply", "msg_id": msg["msg_id"]})
            await self._send_reply(clock, w, reply)
        elif mtype == "pull_chunk":
            reply = await self._transfer.serve_chunk(msg)
            reply.update({"type": "reply", "msg_id": msg["msg_id"]})
            await self._send_reply(clock, w, reply)
        elif mtype == "put_begin":
            # Client-mode put: a chunked writer into THIS node's store.
            try:
                writer = await self._loop.run_in_executor(
                    None, self.local_store.create_writer,
                    msg["object_id"], int(msg["size"]),
                )
                w.client_writers[msg["object_id"]] = writer
                reply = {"ok": True}
            # Reply-carried: the client sees and raises the error.
            except Exception as e:  # rtlint: disable=swallowed-failure
                reply = {"ok": False, "error": str(e)}
            reply.update({"type": "reply", "msg_id": msg["msg_id"]})
            await self._send_reply(clock, w, reply)
        elif mtype == "put_chunk":
            writer = w.client_writers.get(msg["object_id"])
            try:
                if writer is None:
                    raise RuntimeError("no open writer (put_begin missing)")
                await self._loop.run_in_executor(
                    None, writer.write, int(msg["offset"]), msg["data"]
                )
                reply = {"ok": True}
            # Reply-carried: the client sees and raises the error.
            except Exception as e:  # rtlint: disable=swallowed-failure
                reply = {"ok": False, "error": str(e)}
            reply.update({"type": "reply", "msg_id": msg["msg_id"]})
            await self._send_reply(clock, w, reply)
        elif mtype == "put_abort":
            # Client-side failure mid-put: free the reserved block now
            # instead of holding it until the connection drops.
            writer = w.client_writers.pop(msg["object_id"], None)
            if writer is not None:
                try:
                    await self._loop.run_in_executor(None, writer.abort)
                except Exception:
                    pass
            await self._send_reply(
                clock, w,
                {"type": "reply", "msg_id": msg["msg_id"], "ok": True}
            )
        elif mtype == "put_end":
            writer = w.client_writers.pop(msg["object_id"], None)
            finalized = False
            try:
                if writer is None:
                    raise RuntimeError("no open writer (put_begin missing)")
                loc = await self._loop.run_in_executor(
                    None, writer.finalize
                )
                finalized = True
                await self.put_object(msg["object_id"], loc, refs=0)
                reply = {"loc": loc}
            # Reply-carried: the client sees and raises the error.
            except Exception as e:  # rtlint: disable=swallowed-failure
                # The writer left client_writers above, so nothing else
                # can ever free its block — abort it here (only when
                # finalize itself failed: after a successful seal, abort
                # would free a block another path may already reference).
                if writer is not None and not finalized:
                    try:
                        await self._loop.run_in_executor(None, writer.abort)
                    except Exception:
                        pass
                reply = {"loc": None, "error": str(e)}
            reply.update({"type": "reply", "msg_id": msg["msg_id"]})
            await self._send_reply(clock, w, reply)
        elif mtype == "ping":
            await self._send_reply(
                clock, w, {"type": "reply", "msg_id": msg["msg_id"]})
        else:
            raise RuntimeError(f"unknown message type {mtype}")

    async def _on_worker_death(self, w: WorkerHandle):
        if w.state == "dead":
            return
        prev_state = w.state
        w.state = "dead"
        self._workers.pop(w.worker_id, None)
        exit_code = w.proc.poll() if w.proc is not None else None
        if exit_code is None and w.proc is not None and not self._shutdown:
            # The socket closes BEFORE the kernel finishes the exit, so
            # an immediate poll() often races to None and a real crash
            # classifies as a routine lifecycle event (the PR 14 tier-1
            # flake). Reap off the loop for a bounded window so the
            # exit code (or signal class) is actually captured.
            def _reap():
                try:
                    return w.proc.wait(timeout=2.0)
                # Still running past the window (or already reaped):
                # fall back to the None classification below.
                except Exception:  # rtlint: disable=swallowed-failure
                    return w.proc.poll()

            exit_code = await self._loop.run_in_executor(None, _reap)
        # Intentional kills (ray_tpu.kill(actor), force task-cancel) are
        # routine API usage, not crashes: keep them out of the ERROR view.
        graceful = (getattr(w, "_graceful_exit", False)
                    or getattr(w, "_intentional_kill", False))
        if w.worker_type == "client":
            pass  # thin-client disconnects are not worker lifecycle
        elif graceful or self._shutdown or exit_code in (0, None):
            # Clean exit / idle reap / node shutdown: routine lifecycle.
            cluster_events.emit(
                cluster_events.INFO, cluster_events.WORKER,
                f"worker {w.worker_id.hex()[:8]} exited"
                + (f" (code {exit_code})" if exit_code is not None else ""),
                node_id=self.node_id.hex(),
                actor_id=w.actor_id.hex() if w.actor_id else None,
                custom_fields={"exit_code": exit_code,
                               "graceful": graceful},
            )
        else:
            oom = getattr(w, "_oom_killed", False)
            cluster_events.emit(
                cluster_events.ERROR, cluster_events.WORKER,
                f"worker {w.worker_id.hex()[:8]} crashed "
                f"(exit code {exit_code})"
                + (" [killed by memory monitor]" if oom else ""),
                node_id=self.node_id.hex(),
                actor_id=w.actor_id.hex() if w.actor_id else None,
                custom_fields={
                    "exit_code": exit_code,
                    "oom_killed": oom,
                    "running_task": (w.current.spec.name
                                     if w.current is not None else None),
                },
            )
        for writer in w.client_writers.values():
            try:
                writer.abort()  # client died mid-put: free the block
            except Exception:
                pass
        w.client_writers.clear()
        pool = self._idle.get(w.worker_type)
        if pool is not None:  # "client" handles have no idle pool
            try:
                pool.remove(w.worker_id)
            except ValueError:
                pass
        if w.actor_id is not None:
            await self._on_actor_worker_death(w)
        elif w.current is not None or w.pending:
            running = w.current
            queued = list(w.pending)
            w.current = None
            w.pending.clear()
            if running is not None:
                self._release_task_resources(running)
                if running.state == "cancelled":
                    pass
                elif running.spec.retries_left > 0:
                    running.spec.retries_left -= 1
                    running.state = "ready"
                    running.worker_id = None
                    self._stats["tasks_retried"] += 1
                    self._ready.append(running)
                else:
                    detail = (
                        "killed by the node memory monitor (out of memory)"
                        if getattr(w, "_oom_killed", False)
                        else ""
                    )
                    self._fail_task(
                        running, WorkerCrashedError(running.spec.name, detail)
                    )
            for record in queued:
                # Pipelined frames never STARTED on this worker — requeue
                # them without charging a retry (a neighbor's death is not
                # this task's failure).
                self._release_task_resources(record)
                if record.state != "cancelled":
                    record.state = "ready"
                    record.worker_id = None
                    self._ready.append(record)
        elif prev_state in ("busy", "blocked"):
            pass
        if w.proc is not None and w.proc.poll() is None:
            try:
                w.proc.terminate()
            except Exception:
                pass
        self._schedule()

    def _spawn_bg(self, coro) -> asyncio.Task:
        """Run a cleanup coroutine with a strong reference held until done;
        shutdown() drains these so best-effort cleanups actually happen."""
        task = asyncio.ensure_future(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    def _bg_op(self, clock, coro) -> asyncio.Task:
        """ensure_future for a deferred frame op, keeping its stage
        clock honest: the clock re-stamps start when the background
        handler actually runs (so loop scheduling delay lands in
        queue_wait, not handler) and closes when it finishes."""
        if clock is None:
            return asyncio.ensure_future(coro)
        clock.deferred = True

        async def _run():
            clock.start()
            try:
                await coro
            finally:
                clock.done()

        return asyncio.ensure_future(_run())

    # ------------------------------------------------------------ peer plane

    async def _handle_peer_connection(self, reader, writer):
        framed = AioFramedWriter(writer)
        peer_hex = None
        try:
            hello = await aio_read_frame(reader)
            expected = self.config.session_token
            if expected and hello.get("token") != expected:
                framed.close()
                return
            if hello.get("type") == "client_hello":
                # Remote thin driver (ref: util/client proxier): serve
                # the worker protocol over this TCP connection; the
                # handle stays OUT of the schedulable pools.
                await self._serve_client(reader, framed)
                return
            if hello.get("type") != "peer_hello":
                framed.close()
                return
            peer_hex = hello["node_id"]
            if peer_hex in self._fenced_nodes:
                # Fenced incarnation dialing in: refuse — its frames
                # (task results, locates, seal pushes) name state the
                # cluster already recovered away from. A fresh
                # incarnation is unfenced at re-registration.
                _fencing.EVENT_PEER_REFUSED.inc()
                framed.close()
                return
            while True:
                msg = await aio_read_frame(reader)
                if peer_hex in self._fenced_nodes:
                    # Fenced mid-connection: drop the frame and the
                    # channel (the zombie's healthy socket must not
                    # keep feeding us stale results/locates).
                    _fencing.EVENT_PEER_REFUSED.inc()
                    break
                recv_ts = time.monotonic()
                clock = dispatch_obs.op_clock("peer", msg.get("type"),
                                              recv_ts)
                if msg.get("type") in ("stacks_dump", "profile_run",
                                       "traces_dump", "objects_census",
                                       "get_actor_direct_peer",
                                       "drain", "replicate_object"):
                    # Long-running introspection/resolution must not
                    # head-of-line block this channel's read loop (a 15s
                    # profile or a direct-endpoint drain wait would stall
                    # every state_snapshot/pg frame behind it); replies
                    # match by msg_id, so order doesn't matter.
                    self._bg_op(clock, self._peer_reply_async(
                        peer_hex, msg, framed, clock
                    ))
                    continue
                if clock is not None:
                    clock.start()
                try:
                    reply = await self._dispatch_peer(peer_hex, msg,
                                                      clock)
                    if reply is not None:
                        if clock is not None:
                            clock.handler_done()
                        reply["type"] = "reply"
                        reply["msg_id"] = msg.get("msg_id")
                        await framed.send(reply)
                finally:
                    if clock is not None:
                        clock.done()
        except (asyncio.IncompleteReadError, ConnectionResetError, OSError):
            pass
        finally:
            framed.close()

    async def _peer_reply_async(self, peer_hex: str, msg, framed,
                                clock=None):
        """Dispatch a slow peer request off the channel's read loop and
        ship the reply when it completes."""
        try:
            reply = await self._dispatch_peer(peer_hex, msg)
        # Reply-carried: the requesting peer sees and handles the error.
        except Exception as e:  # rtlint: disable=swallowed-failure
            reply = {"error": str(e)}
        if reply is None:
            return
        if clock is not None:
            clock.handler_done()
        reply["type"] = "reply"
        reply["msg_id"] = msg.get("msg_id")
        try:
            await framed.send(reply)
        except Exception:
            pass

    async def _serve_client(self, reader, framed):
        handle: Optional[WorkerHandle] = None
        try:
            msg = await aio_read_frame(reader)
            if msg.get("type") != "register":
                return
            handle = WorkerHandle(
                worker_id=WorkerID.from_hex(msg["worker_id"]),
                writer=framed, worker_type="client", state="client",
            )
            await framed.send(
                {"type": "registered", "node_id": self.node_id.hex()}
            )
            while True:
                msg = await aio_read_frame(reader)
                await self._dispatch_message(handle, msg,
                                             time.monotonic())
        except (asyncio.IncompleteReadError, ConnectionResetError,
                OSError):
            pass
        finally:
            if handle is not None:
                await self._on_worker_death(handle)
            framed.close()

    async def _dispatch_peer(
        self, peer_hex: str, msg: Dict[str, Any], clock=None
    ) -> Optional[Dict[str, Any]]:
        mtype = msg["type"]
        if mtype == "forward_task":
            await self._on_forward_task(peer_hex, msg["spec"], msg["dep_locs"])
            return None
        if mtype == "task_result":
            self._on_remote_task_result(msg)
            return None
        if mtype in ("pull_object", "pull_chunk"):
            # Typed boundary: the transfer service's schemas validate the
            # frame before the handler runs (rpc.py ServiceRegistry). A
            # malformed frame fails THIS request with an error reply —
            # never the whole shared peer channel.
            try:
                return await self._transfer.rpc.dispatch(
                    peer_hex, mtype, msg, clock=clock
                )
            except RpcError as e:
                return {"data": None, "error": str(e)}
        if mtype == "free_object":
            self._remove_ref(msg["object_id"])
            return None
        if mtype == "register_borrow":
            # Owner side: a peer node holds live refs to our object; keep
            # it (and its lineage) until the peer releases the borrow.
            return {"ok": self.directory.add_borrower(
                msg["object_id"], msg["borrower"]
            )}
        if mtype == "release_borrow":
            self.directory.remove_borrower(
                msg["object_id"], msg["borrower"]
            )
            return None
        if mtype == "kill_actor_peer":
            await self.kill_actor(msg["actor_id"], msg.get("no_restart", True))
            return None
        if mtype == "cancel_task_peer":
            await self.cancel_task(msg["task_id"], msg.get("force", False))
            return None
        if mtype == "prepare_bundle":
            return {"ok": self._prepare_bundle(
                msg["pg_id"], msg["index"], msg["resources"]
            )}
        if mtype == "commit_bundle":
            bundle = self._bundles.get((msg["pg_id"], msg["index"]))
            if bundle is not None:
                bundle.state = "committed"
            self._schedule()
            return None
        if mtype == "release_bundle":
            self._release_bundle(msg["pg_id"], msg["index"])
            return None
        if mtype == "get_actor_direct_peer":
            # A remote caller resolving one of our actors' direct
            # endpoints (the UDS path is useless off-node, but the
            # caller filters by node id; the TCP addr is the payload).
            return {"direct": await self.get_actor_direct(
                msg["actor_id"], timeout=msg.get("timeout", 30.0)
            )}
        if mtype == "replicate_object":
            # Drain rider: the draining node asks us to adopt a primary
            # copy before it exits; we pull it over the normal transfer
            # plane and publish the new location.
            return await self._replicate_in(peer_hex, msg["object_id"])
        if mtype == "drain":
            return await self._handle_drain_request(
                msg.get("timeout") or self.config.drain_timeout_s
            )
        if mtype == "state_snapshot":
            return {"state": self._local_state_snapshot()}
        if mtype == "stacks_dump":
            # GCS ProfileService fan-out: this node's dump (head NM
            # included — the GCS reaches its own host over the same
            # peer channel it uses for every other node).
            return {"result": await self.stacks_dump(
                timeout=msg.get("timeout", 5.0)
            )}
        if mtype == "profile_run":
            return {"result": await self.profile_run(
                seconds=msg.get("seconds", 2.0), hz=msg.get("hz", 100)
            )}
        if mtype == "traces_dump":
            # GCS ProfileService fan-out: this node's flight-recorder
            # ring (same reach discipline as stacks_dump).
            return {"result": self.traces_dump(
                reason=msg.get("reason") or None,
                limit=msg.get("limit", 200),
            )}
        if mtype == "objects_census":
            # GCS ObjectService fan-out: this node's bounded object
            # index + store/spill totals (same reach discipline).
            return {"result": self.objects_census(
                limit=msg.get("limit", 500)
            )}
        raise RuntimeError(f"unknown peer message {mtype}")

    # ------------------------------------------------------ bundle resources

    def _prepare_bundle(self, pg_id: str, index: int, resources) -> bool:
        """Reserve a bundle's resources from the node pool (ref:
        PlacementGroupResourceManager::PrepareBundle)."""
        key = (pg_id, index)
        if key in self._bundles:
            return True  # idempotent retry
        req = ResourceSet(resources)
        if not self.node_resources.acquire(req):
            return False
        self._bundles[key] = BundleState(
            pg_id=pg_id,
            index=index,
            resources=req,
            available=ResourceSet(_fixed=dict(req._amounts)),
        )
        return True

    def _release_bundle(self, pg_id: str, index: int):
        """Return a bundle's unused reservation to the node pool; resources
        of still-running bundle tasks flow back on their completion (ref:
        PlacementGroupResourceManager::ReturnBundle)."""
        bundle = self._bundles.pop((pg_id, index), None)
        if bundle is not None:
            self.node_resources.release(bundle.available)
        self._pg_nodes.pop(pg_id, None)
        self._schedule()

    def _invalidate_pgs(self, pg_ids: List[str]):
        """A node death sent these groups back to pending: drop routing
        caches and local bundle reservations so the GCS can re-place them
        with fresh prepares; parked/queued tasks re-resolve via the GCS
        instead of forwarding to a stale node (advisor finding r1)."""
        for pg_id in pg_ids:
            self._pg_nodes.pop(pg_id, None)
            for key in [k for k in self._bundles if k[0] == pg_id]:
                self._release_bundle(*key)

    def _find_local_bundle(
        self, strategy: PlacementGroupSchedulingStrategy, req: ResourceSet
    ) -> Optional[BundleState]:
        idx = strategy.placement_group_bundle_index
        if idx >= 0:
            bundle = self._bundles.get((strategy.pg_id, idx))
            if (
                bundle is not None
                and bundle.state == "committed"
                and req.is_subset_of(bundle.available)
            ):
                return bundle
            return None
        for (pg_id, _i), bundle in sorted(self._bundles.items()):
            if (
                pg_id == strategy.pg_id
                and bundle.state == "committed"
                and req.is_subset_of(bundle.available)
            ):
                return bundle
        return None

    def _acquire_for_record(self, record: TaskRecord) -> bool:
        """Bundle-aware resource acquisition; sets record.bundle_key."""
        strategy = record.spec.scheduling_strategy
        if isinstance(strategy, PlacementGroupSchedulingStrategy):
            bundle = self._find_local_bundle(strategy, record.spec.resources)
            if bundle is None:
                return False
            bundle.available = bundle.available - record.spec.resources
            record.bundle_key = (bundle.pg_id, bundle.index)
            return True
        return self.node_resources.acquire(record.spec.resources)

    def _release_task_resources(self, record: TaskRecord):
        if not record.resources_held:
            return
        record.resources_held = False
        res = record.spec.resources
        if record.bundle_key is not None:
            bundle = self._bundles.get(record.bundle_key)
            if bundle is not None:
                bundle.available = bundle.available + res
                return
            # Bundle released while the task ran: its reservation already
            # excluded these resources, so they rejoin the node pool.
        self.node_resources.release(res)

    def _pg_targets(
        self, strategy: PlacementGroupSchedulingStrategy
    ) -> Optional[List[str]]:
        mapping = self._pg_nodes.get(strategy.pg_id)
        if mapping is None:
            return None
        idx = strategy.placement_group_bundle_index
        if idx >= 0:
            node = mapping.get(idx)
            return [node] if node else []
        return list(dict.fromkeys(mapping.values()))

    def _queue_pg_resolve(self, record: TaskRecord):
        """Park the record on this pg's (single) in-flight map resolution."""
        pg_id = record.spec.scheduling_strategy.pg_id
        waiters = self._pg_waiters.setdefault(pg_id, [])
        waiters.append(record)
        if len(waiters) == 1:
            asyncio.ensure_future(self._resolve_pg(pg_id))

    async def _resolve_pg(self, pg_id: str):
        """Fetch the bundle->node map from the GCS, then re-place every
        record parked on it. A still-*pending* group keeps its records
        parked (the reference queues tasks until the PG is placed or
        removed, it never times them out); only a removed/unknown group
        fails them."""
        ok = False
        while not self._shutdown:
            state = "unknown"
            if self._gcs is None:
                break
            try:
                ok = await self._gcs.pg_wait(
                    pg_id, self.config.object_locate_timeout_s
                )
                info = await self._gcs.pg_get(pg_id)
                state = info.get("state", "unknown")
                nodes = info.get("bundle_nodes")
                if ok and state == "created" and nodes:
                    self._pg_nodes[pg_id] = {int(k): v for k, v in nodes.items()}
                else:
                    ok = False
            except Exception:
                ok = False
            if ok or state in ("removed", "unknown"):
                break
            # Group exists but is still pending: poll again, keeping the
            # records parked.
            await asyncio.sleep(0.2)
        for record in self._pg_waiters.pop(pg_id, []):
            if record.state == "cancelled":
                continue
            if ok:
                record.spillbacks = 0  # fresh map: forwarding budget resets
                self._task_ready(record)
            else:
                self._fail_task(
                    record,
                    TaskError(
                        None,
                        record.spec.name,
                        f"placement group {pg_id[:8]} was removed or is "
                        "unknown",
                    ),
                )

    def _pg_unservable(
        self, strategy: PlacementGroupSchedulingStrategy, req: ResourceSet
    ) -> Optional[str]:
        """A locally-routed PG request that can never be served: request
        exceeds every candidate bundle's total, or the bundles are gone
        (group removed). None means 'may fit later, keep waiting'."""
        idx = strategy.placement_group_bundle_index
        local = [
            b for (pg, i), b in self._bundles.items()
            if pg == strategy.pg_id and (idx < 0 or i == idx)
        ]
        if not local:
            return (
                f"placement group {strategy.pg_id[:8]} has no bundles on "
                "this node (removed?)"
            )
        if all(not req.is_subset_of(b.resources) for b in local):
            return (
                f"request {req.to_dict()} exceeds placement group bundle "
                f"resources"
            )
        return None

    async def _get_peer(self, peer_hex: str) -> PeerClient:
        if peer_hex in self._fenced_nodes:
            raise ConnectionError(
                f"node {peer_hex[:8]} fenced at epoch "
                f"{self._fenced_nodes[peer_hex]}"
            )
        peer = self._peers.get(peer_hex)
        if isinstance(peer, asyncio.Future):
            # A concurrent caller is connecting: share its connection so
            # message order over one socket is preserved.
            return await asyncio.shield(peer)
        if peer is not None and not peer.closed:
            return peer
        view = self._cluster_view.get(peer_hex)
        if view is None:
            raise ConnectionError(f"node {peer_hex[:8]} not in cluster view")
        fut: asyncio.Future = self._loop.create_future()
        self._peers[peer_hex] = fut
        try:
            peer = PeerClient(
                peer_hex, view["host"], view["peer_port"], self.node_id.hex()
            )
            await peer.connect()
        except Exception as e:
            self._peers.pop(peer_hex, None)
            if not fut.done():
                fut.set_exception(e)
                # Consume if nobody awaited, to silence the loop warning.
                fut.exception()
            raise
        self._peers[peer_hex] = peer
        if not fut.done():
            fut.set_result(peer)
        return peer

    def _build_dep_locs(self, spec: TaskSpec) -> Dict[ObjectID, Location]:
        """Location hints shipped with a forwarded task so the target can
        pull arguments without a directory round-trip (ref analogue: the
        lease response's resolved dependency locations)."""
        dep_locs: Dict[ObjectID, Location] = {}
        for oid in spec.dependency_ids():
            loc = self.directory.lookup(oid)
            if loc is None:
                continue
            if isinstance(loc, (InlineLocation, RemoteLocation)):
                dep_locs[oid] = loc
            else:
                dep_locs[oid] = RemoteLocation(self.node_id.hex(), loc.size)
        return dep_locs

    def _forward_record(self, record: TaskRecord, target_hex: str):
        record.state = "forwarded"
        record.target = target_hex
        # The grace window measures CONTINUOUS infeasibility: a task
        # that found a target is feasible again, so a later requeue
        # (forward failure, peer partition) restarts the clock instead
        # of inheriting an already-expired one.
        record.infeasible_since = None
        record.spillbacks += 1
        self._forwarded[record.spec.task_id] = record
        dep_locs = self._build_dep_locs(record.spec)
        asyncio.ensure_future(self._forward_send(record, target_hex, dep_locs))

    async def _forward_send(self, record, target_hex, dep_locs):
        try:
            peer = await self._get_peer(target_hex)
            await peer.notify(
                {
                    "type": "forward_task",
                    "spec": record.spec,
                    "dep_locs": dep_locs,
                }
            )
        except Exception:
            # Target unreachable: treat like a node death for this record.
            self._forwarded.pop(record.spec.task_id, None)
            self._cluster_view.pop(target_hex, None)
            self._requeue_forwarded(record, target_hex)

    def _requeue_forwarded(self, record: TaskRecord, dead_hex: str):
        """Re-place a record whose forward target is gone, respecting the
        task type (an actor task must re-route via the actor directory, not
        the normal ready queue)."""
        record.state = "ready"
        record.target = None
        spec = record.spec
        if spec.task_type == TaskType.ACTOR_TASK:
            if self._actor_homes.get(spec.actor_id) == dead_hex:
                self._actor_homes[spec.actor_id] = "dead"
            self._route_actor_task_cluster(record)
        elif spec.task_type == TaskType.ACTOR_CREATION_TASK:
            if self._actor_homes.get(spec.actor_id) == dead_hex:
                self._actor_homes.pop(spec.actor_id, None)
            self._task_ready(record)
        else:
            self._task_ready(record)

    async def _on_forward_task(self, origin_hex, spec: TaskSpec, dep_locs):
        for oid, loc in dep_locs.items():
            # Only adopt the hint when the object is unknown here; a local
            # placeholder means this node is itself producing it, and the
            # local seal path must win (and will wake waiters).
            if self.directory.lookup(oid) is None:
                self._seal_object(oid, loc)
        await self.submit_task(spec, origin=origin_hex)
        # Hold the return slots on behalf of the origin until it frees them
        # (the origin's directory entry maps here via RemoteLocation).
        for oid in spec.return_ids():
            self.directory.add_ref(oid)

    def _notify_origin(self, record: TaskRecord, failed: bool):
        """Push a forwarded task's results back to the node that sent it."""
        results = []
        for oid in record.spec.return_ids():
            loc = self.directory.lookup(oid)
            if loc is None:
                continue
            if isinstance(loc, (InlineLocation, RemoteLocation)):
                results.append((oid, loc))
                # Inline bytes travel with the message: the origin needs no
                # hold on our copy, so release the one _on_forward_task took.
                self.directory.remove_ref(oid)
                if isinstance(loc, RemoteLocation) and loc.held:
                    # The third-party hold transfers to the origin; clear our
                    # copy's flag so our GC doesn't also free it.
                    self.directory.replace_location(
                        oid, RemoteLocation(loc.node_id, loc.size, held=False)
                    )
            else:
                results.append(
                    (oid, RemoteLocation(self.node_id.hex(), loc.size, held=True))
                )
        origin = record.origin

        async def _send():
            try:
                peer = await self._get_peer(origin)
                await peer.notify(
                    {
                        "type": "task_result",
                        "task_id": record.spec.task_id,
                        "results": results,
                        "failed": failed,
                    }
                )
            except Exception:
                pass  # origin died; its successor will never ask

        asyncio.ensure_future(_send())

    def _on_remote_task_result(self, msg: Dict[str, Any]):
        record = self._forwarded.pop(msg["task_id"], None)
        if record is None:
            return
        for oid, loc in msg["results"]:
            self._seal_object(oid, loc)
        if msg.get("failed"):
            record.state = "failed"
            self._stats["tasks_failed"] += 1
        else:
            record.state = "finished"
            self._stats["tasks_finished"] += 1
        if record.spec.task_type != TaskType.ACTOR_CREATION_TASK:
            self._unpin_deps(record)
            # No history row here: the EXECUTING node already retained
            # the terminal record (with duration + error detail) in its
            # own _on_task_done/_fail_task — a second row at the origin
            # would double-count the task cluster-wide.
            self._tasks.pop(record.spec.task_id, None)

    async def _on_node_dead_hex(self, node_hex: str, dead_actors=None):
        """A peer died: fail/retry work bound to it (ref analogue:
        NodeManager::NodeRemoved + TaskManager retry on node failure)."""
        self._cluster_view.pop(node_hex, None)
        peer = self._peers.pop(node_hex, None)
        if isinstance(peer, PeerClient):
            peer.close()
        elif peer is not None:
            peer.cancel()
        # Its data channels are dead sockets: close them so in-flight
        # stripe reads error out now instead of at the io timeout.
        self._transfer.drop_peer(node_hex)
        # Borrows die with the node: void its registrations in our
        # borrower sets (owner side) and forget owners that vanished
        # (borrower side — releases to a ghost would just error).
        self.directory.drop_borrower_node(node_hex)
        for oid in [o for o, h in self._borrowed_from.items()
                    if h == node_hex]:
            self._borrowed_from.pop(oid, None)
        # Remote actors homed there are gone (mark before requeueing so
        # re-routed actor tasks fail with ActorDiedError, not a plain-worker
        # dispatch). Restartable creations this node owns re-place on a
        # surviving node below (_restart_actor_elsewhere); creations
        # still in flight also retry elsewhere.
        if dead_actors is None:
            dead_actors = [
                aid.hex() for aid, h in self._actor_homes.items() if h == node_hex
            ]
        for aid_hex in dead_actors:
            aid = ActorID.from_hex(aid_hex)
            if self._actor_homes.get(aid) == node_hex:
                self._actor_homes[aid] = "dead"
        # Restart-elsewhere: creations this node owns whose home was
        # just fenced re-place on a surviving node, within the pinned
        # restart budget (ref analogue: GcsActorManager::OnNodeDead
        # rescheduling dead actors onto live raylets).
        for aid_hex in dead_actors:
            aid = ActorID.from_hex(aid_hex)
            if aid in self._actor_creations:
                self._spawn_bg(self._restart_actor_elsewhere(aid))
        # Objects whose only known copy was on the dead node: unseal the
        # ones whose lineage we own so the next consumer (or a dependency
        # resolution) re-executes the creating task instead of pulling from
        # a ghost. Borrowed objects (no lineage here) keep their stale
        # location and fail fast at pull with recovery via the GCS replica
        # set (ref analogue: ObjectRecoveryManager on node removal).
        for oid in self.directory.remote_entries(node_hex):
            if oid in self._lineage:
                self._sealed.discard(oid)
                if (oid in self._dep_index or oid in self._seal_events
                        or oid in self._parked_waits):
                    # Consumers are already parked on this object: kick the
                    # re-execution now, their seal waits stay valid.
                    self._spawn_bg(self._reconstruct_object(oid))
        # Forwarded tasks: retry elsewhere or fail.
        for task_id, record in list(self._forwarded.items()):
            if record.target != node_hex:
                continue
            del self._forwarded[task_id]
            if record.spec.task_type == TaskType.ACTOR_TASK:
                # The actor died with its node; retries can't help.
                self._fail_task(
                    record,
                    ActorDiedError(
                        record.spec.name, f"node {node_hex[:8]} died"
                    ),
                )
            elif record.spec.retries_left > 0:
                record.spec.retries_left -= 1
                self._stats["tasks_retried"] += 1
                self._requeue_forwarded(record, node_hex)
            else:
                self._fail_task(
                    record,
                    WorkerCrashedError(
                        f"{record.spec.name} (node {node_hex[:8]} died)"
                    ),
                )
        self._schedule()

    async def _restart_actor_elsewhere(self, aid: ActorID):
        """Re-place an owned restartable actor whose home node was
        fenced: re-submit the pinned creation spec so the scheduler
        picks a surviving node, under the pinned restart budget. The
        fresh placement gets a NEW GCS-assigned incarnation, so any
        caller still holding the fenced incarnation's endpoint is
        refused at the hello and re-resolves. Calls parked on the
        "dead" home re-route via _route_actor_via_gcs once the new home
        registers; direct-replay calls bound to the fenced incarnation
        stay REFUSED (a restarted actor has no replay-dedup cache —
        executing them could double-execute)."""
        spec = self._actor_creations.get(aid)
        if spec is None:
            return
        if self._actor_homes.get(aid) != "dead":
            return  # recovered (or restarted) already
        budget = self._actor_restart_budget.get(aid, 0)
        if budget == 0:
            cluster_events.emit(
                cluster_events.ERROR, cluster_events.ACTOR,
                f"actor {aid.hex()[:8]} ({spec.class_name}) died with "
                f"its fenced node and has no restarts left",
                node_id=self.node_id.hex(), actor_id=aid.hex(),
            )
            return
        if budget > 0:
            self._actor_restart_budget[aid] = budget - 1
        cluster_events.emit(
            cluster_events.WARNING, cluster_events.ACTOR,
            f"actor {aid.hex()[:8]} ({spec.class_name}) restarting on a "
            f"surviving node after its home was fenced "
            f"({'unlimited' if budget < 0 else budget - 1} restart(s) "
            f"left)",
            node_id=self.node_id.hex(), actor_id=aid.hex(),
            custom_fields={"class_name": spec.class_name},
        )
        oid = spec.return_ids()[0]
        ev = self._seal_events.get(oid)
        if ev is not None:
            ev.clear()
        self._sealed.discard(oid)
        self._actor_homes.pop(aid, None)
        await self.submit_task(spec)

    # ------------------------------------------------------------------ drain

    async def _handle_drain_request(self, timeout: float) -> Dict[str, Any]:
        """Drain state machine (ref analogue: DrainRaylet +
        local_object_manager spill-before-exit). By the time this runs,
        phase "begin" already made the node unschedulable cluster-wide
        (peers mark the view draining; serve replicas were migrated by
        the controller). Here: (1) let in-flight local work finish,
        bounded by ``timeout`` — whatever misses the window replays via
        lineage after the death broadcast; (2) replicate primary object
        copies to surviving nodes so consumers re-locate instead of
        reconstructing; (3) ack, flush events, and fire
        ``on_drain_complete`` so the host process exits cleanly."""
        self._draining = True
        # Idempotent re-signal: a phase="finish"-only caller (or a lost
        # begin-phase frame) must still give cooperative tenants their
        # preemption window before the in-flight wait below starts.
        await self._broadcast_drain_to_workers(True)
        cluster_events.emit(
            cluster_events.INFO, cluster_events.RAYLET,
            f"node {self.node_id.hex()[:8]} drain started "
            f"(timeout {timeout:.0f}s)",
            node_id=self.node_id.hex(),
        )
        loop = self._loop
        deadline = loop.time() + max(1.0, float(timeout))
        wait = Backoff(base=0.05, factor=1.3, max_delay=0.5, jitter=0.0)
        while loop.time() < deadline:
            # In-flight work: queued/running tasks, plus RUNNING actor
            # methods (w.current on an actor worker) — a preempted train
            # gang is mid-checkpoint inside one of those; killing it at
            # the first sweep would waste the cooperative window the
            # node_draining broadcast just opened. Queued-but-unstarted
            # actor calls are NOT waited for (the actor dies with the
            # node either way).
            busy = bool(self._ready) or any(
                (w.current is not None
                 or (w.pending and w.actor_id is None))
                for w in self._workers.values()
                if w.state != "dead" and w.worker_type != "client"
            )
            if not busy:
                break
            await asyncio.sleep(wait.next_delay())
        replicated = await self._replicate_for_drain(deadline)
        leftover = [
            info for info in self._actors.values()
            if info.state in ("alive", "pending", "restarting")
        ]
        if leftover:
            cluster_events.emit(
                cluster_events.WARNING, cluster_events.RAYLET,
                f"node {self.node_id.hex()[:8]} draining with "
                f"{len(leftover)} live actor(s) — they die with the "
                f"node (callers see ActorDiedError)",
                node_id=self.node_id.hex(),
                custom_fields={"leftover_actors": len(leftover)},
            )
        cluster_events.emit(
            cluster_events.INFO, cluster_events.RAYLET,
            f"node {self.node_id.hex()[:8]} drained: replicated "
            f"{replicated} object(s), {len(leftover)} actor(s) left",
            node_id=self.node_id.hex(),
            custom_fields={"replicated": replicated,
                           "leftover_actors": len(leftover)},
        )
        # Ship the tail of the event ring while the transport is up —
        # the process exits right after the ack.
        try:
            cluster_events.flush()
        except Exception:
            pass
        if self.on_drain_complete is not None:
            # After the ack frame is on the wire (the reply is sent by
            # the peer handler right after this returns).
            loop.call_later(0.5, self._fire_drain_complete)
        return {"ok": True, "replicated": replicated,
                "leftover_actors": len(leftover), "error": ""}

    def _fire_drain_complete(self):
        if not self._draining:
            # The drain was aborted between our ack and this timer (ack
            # reply lost → GCS reported failure → phase="abort" rolled
            # us back to alive): exiting now would kill a node the
            # operator was just told is back in service.
            return
        try:
            if self.on_drain_complete is not None:
                self.on_drain_complete()
        except Exception:
            pass

    async def _replicate_for_drain(self, deadline: float) -> int:
        """Push every primary (locally-stored, sealed) object copy to a
        surviving node before exit (ref analogue: the reference's
        drain-time object spilling; here the replica is re-homed into a
        peer's store and published, so borrowers re-locate through the
        GCS instead of pulling from a ghost)."""
        me = self.node_id.hex()
        # Only durable nodes may adopt primary copies: a 0-resource
        # view is an ephemeral attach driver (the `rtpu drain` CLI
        # itself registers one and shuts it down right after the
        # drain) — re-homing an object's only copy there loses it.
        targets = [
            h for h, v in self._cluster_view.items()
            if h != me and v.get("state", "alive") == "alive"
            and any(amt > 0 for amt in
                    (v.get("resources_total") or {}).values())
        ]
        if not targets:
            return 0
        # Fan out with a bounded window: sequential one-request-at-a-
        # time replication caps throughput at one object per round trip
        # and an object-heavy node blows the drain deadline with most
        # of its store abandoned to lineage re-execution. The target
        # side already spawns replicate_object off its dispatch loop,
        # so a window of pulls overlaps cleanly.
        sem = asyncio.Semaphore(8)
        count = 0
        cut_off = 0
        failed = 0

        async def _push(oid: ObjectID, first: int) -> None:
            nonlocal count, cut_off, failed
            async with sem:
                # One retry on the next target: a single full/flaky
                # peer must not silently strand every object that
                # round-robin happened to assign to it.
                for attempt in range(2):
                    if self._loop.time() >= deadline:
                        cut_off += 1
                        return
                    target = targets[(first + attempt) % len(targets)]
                    try:
                        peer = await self._get_peer(target)
                        reply = await peer.request(
                            {"type": "replicate_object",
                             "object_id": oid},
                            timeout=min(30.0, max(
                                5.0, deadline - self._loop.time()
                            )),
                        )
                        if reply.get("ok"):
                            count += 1
                            return
                    except Exception:
                        continue
                failed += 1

        pushes = []
        i = 0
        for oid in list(self._sealed):
            loc = self.directory.lookup(oid)
            if loc is None or isinstance(loc, RemoteLocation):
                continue
            pushes.append(_push(oid, i))
            i += 1
        if pushes:
            await asyncio.gather(*pushes)
        if cut_off or failed:
            cluster_events.emit(
                cluster_events.WARNING, cluster_events.RAYLET,
                f"drain replication incomplete: {count} object(s) "
                f"replicated, {failed} failed on every target, "
                f"{cut_off} abandoned at the deadline; lineage covers "
                f"the rest",
                node_id=me,
            )
        return count

    async def _replicate_in(self, source_hex: str,
                            oid: ObjectID) -> Dict[str, Any]:
        """Adopt a primary copy from a draining peer: pull it over the
        normal transfer plane (data-plane stripes, chunk fallback) and
        publish the new location."""
        loc = self.directory.lookup(oid)
        if loc is not None and not isinstance(loc, RemoteLocation):
            return {"ok": True}
        if loc is None:
            self.directory.add(
                oid, RemoteLocation(source_hex, 0), initial_refs=0,
                owner="replica",
            )
            loc = self.directory.lookup(oid)
        try:
            new_loc = await self._ensure_local(oid, loc)
            self._seal_object(oid, new_loc)
            return {"ok": True}
        # Reply-carried: the drainer counts this object as failed and
        # reports it in the drain WARNING.
        except Exception as e:  # rtlint: disable=swallowed-failure
            return {"ok": False, "error": str(e) or type(e).__name__}

    # ------------------------------------------------------------- scheduling

    async def submit_task(self, spec: TaskSpec, origin: Optional[str] = None):
        self.submit_task_sync(spec, origin)

    def submit_task_sync(self, spec: TaskSpec, origin: Optional[str] = None):
        """Entry point for driver, nested worker, and peer-forwarded
        submissions (ref analogue: ClusterTaskManager::QueueAndScheduleTask).
        Never awaits — the driver's batched submit drain calls it straight
        from a loop callback."""
        self._stats["tasks_submitted"] += 1
        # Unpickled specs carry fresh copies of descriptors every call of
        # a function repeats; intern them so a deep queue stores each once.
        intern_spec(spec)
        record = TaskRecord(spec=spec, origin=origin)
        self._tasks[spec.task_id] = record
        for oid in spec.return_ids():
            # Return slots exist in the directory from submission time so
            # consumers can hold refs before the task runs. One shared
            # placeholder instance — a 1M-deep queue creates 1M slots,
            # and the location is frozen anyway.
            self.directory.add(oid, _RETURN_PLACEHOLDER, initial_refs=0,
                               owner=getattr(spec, "name", "") or "task")
        if (
            origin is None
            and spec.task_type == TaskType.NORMAL_TASK
            and self.config.enable_lineage_reconstruction
        ):
            # This node owns the task: pin its spec so lost return objects
            # can be rebuilt by re-execution (normal tasks only — actor
            # state is recovered by actor restart, not task replay).
            for oid in spec.return_ids():
                self._lineage[oid] = spec
        # Pin dependencies AND refs smuggled inside argument values for
        # the task's lifetime so owners dropping their refs mid-flight
        # cannot free an argument (ref analogue: submitted task references
        # + nested ids in ReferenceCounter).
        for oid in spec.pinned_ids():
            self._pin_ref_bg(oid)
        if spec.task_type == TaskType.ACTOR_CREATION_TASK:
            # Register the pending actor synchronously so method calls that
            # land during async placement queue instead of failing (ref
            # analogue: RegisterActor before CreateActor,
            # gcs_actor_manager.cc:255).
            self._pre_register_actor(spec)
            if origin is None and spec.max_restarts != 0:
                # This node OWNS a restartable creation: pin the spec +
                # a restart budget so a fenced home node re-places the
                # actor on a survivor (setdefault: a restart
                # re-submission must not refill the budget).
                self._actor_creations[spec.actor_id] = spec
                self._actor_restart_budget.setdefault(
                    spec.actor_id, spec.max_restarts
                )
        if spec.task_type == TaskType.ACTOR_TASK:
            # Actor tasks never wait for deps here: the actor's worker
            # resolves arguments at execution, which preserves per-caller
            # submission order (ref analogue: sequential_actor_submit_queue).
            self._route_actor_task_cluster(record)
            return
        missing = {oid for oid in spec.dependency_ids() if oid not in self._sealed}
        if missing:
            record.state = "waiting"
            self._waiting[spec.task_id] = (record, missing)
            for oid in missing:
                self._dep_index.setdefault(oid, set()).add(spec.task_id)
                if (self.directory.lookup(oid) is None
                        or oid in self._borrow_stubs):
                    # Unknown here — or only a count-only borrow stub
                    # (the pin above created one): find the real copy.
                    asyncio.ensure_future(self._locate_missing(oid))
                elif oid in self._lineage:
                    # Entry exists but is unsealed: either its creating task
                    # is in flight (no-op) or its copy died with a node —
                    # re-execute from lineage.
                    self._spawn_bg(self._reconstruct_object(oid))
        else:
            self._task_ready(record)

    def _task_ready(self, record: TaskRecord):
        """Dependencies are available: place the task (ref analogue: the
        hand-off from DependencyManager to ClusterTaskManager dispatch)."""
        spec = record.spec
        if spec.task_type == TaskType.ACTOR_CREATION_TASK:
            self._place_creation(record)
            return
        record.state = "ready"
        self._ready.append(record)
        self._schedule()

    def _place_creation(self, record: TaskRecord):
        """Pick a node for an actor (ref analogue: GcsActorScheduler
        ScheduleByRaylet picking a forward target)."""
        spec = record.spec
        raw_strategy = getattr(spec, "scheduling_strategy", None)
        if isinstance(raw_strategy, PlacementGroupSchedulingStrategy):
            targets = self._pg_targets(raw_strategy)
            if targets is None:
                self._queue_pg_resolve(record)
                return
            if not targets:
                self._fail_task(
                    record,
                    TaskError(
                        None, spec.name,
                        "placement group bundle index out of range",
                    ),
                )
                return
            if self.node_id.hex() in targets or record.origin is not None:
                self._register_actor(record)
            elif record.spillbacks >= self.config.max_task_spillback:
                # Stale routing cache: re-resolve through the GCS.
                self._pg_nodes.pop(raw_strategy.pg_id, None)
                self._queue_pg_resolve(record)
            else:
                self._actor_homes[spec.actor_id] = targets[0]
                info = self._actors.pop(spec.actor_id, None)
                self._forward_record(record, targets[0])
                if info is not None:
                    while info.queued:
                        qspec = info.queued.popleft()
                        qrec = self._tasks.get(qspec.task_id)
                        if qrec is not None and qrec.state != "cancelled":
                            self._forward_record(qrec, targets[0])
            return
        strategy = raw_strategy or "DEFAULT"
        if (
            record.origin is None
            and self._multi_node
            and record.spillbacks < self.config.max_task_spillback
        ):
            self._cluster_view[self.node_id.hex()] = self._local_view()
            target = pick_node(
                spec.resources,
                strategy,
                self.node_id.hex(),
                list(self._cluster_view.values()),
                spread_threshold=self.config.scheduler_spread_threshold,
            )
            if target is None:
                self._fail_task(
                    record,
                    TaskError(
                        None,
                        spec.name,
                        f"infeasible actor resources {spec.resources.to_dict()} "
                        f"on every node in the cluster",
                    ),
                )
                info = self._actors.get(spec.actor_id)
                if info is not None and info.state == "pending":
                    info.state = "dead"
                    info.death_cause = "infeasible actor resources"
                    self._fail_actor_queue(info, info.death_cause)
                return
            if target != self.node_id.hex():
                self._actor_homes[spec.actor_id] = target
                # Calls that queued on the pending pre-registration follow
                # the creation to its home.
                info = self._actors.pop(spec.actor_id, None)
                self._forward_record(record, target)
                if info is not None:
                    while info.queued:
                        qspec = info.queued.popleft()
                        qrec = self._tasks.get(qspec.task_id)
                        if qrec is not None and qrec.state != "cancelled":
                            self._forward_record(qrec, target)
                return
        self._register_actor(record)

    def _route_actor_task_cluster(self, record: TaskRecord):
        """Route an actor call to wherever the actor lives."""
        spec = record.spec
        parked = self._fence_parked.get(spec.actor_id)
        if parked is not None and not getattr(spec, "direct_replay",
                                              False):
            # A restart-elsewhere drain is pending for this actor:
            # queue behind the already-parked calls so per-caller order
            # survives the fence window (routing directly would let
            # this call overtake them).
            parked.append(record)
            record.state = "queued"
            return
        info = self._actors.get(spec.actor_id)
        if info is not None:
            self._route_actor_task(record)
            return
        home = self._actor_homes.get(spec.actor_id)
        if home == "dead":
            if getattr(spec, "direct_replay", False):
                # A direct-channel call parked by the fence: the old
                # incarnation may have executed it (reply lost in the
                # partition) and the restarted incarnation has no
                # replay-dedup record of it — REFUSE rather than risk a
                # double execution on the new incarnation.
                _fencing.REFUSED_REPLAY.inc()
                self._fail_task(
                    record,
                    ActorDiedError(
                        spec.name,
                        "fenced: direct-call replay bound to a dead "
                        "incarnation refused",
                    ),
                )
                return
            if (spec.actor_id in self._actor_creations
                    and self._actor_restart_budget.get(spec.actor_id, 0)
                    != 0):
                # Restart-elsewhere is in flight (kicked by the fence):
                # park the call in the actor's ordered queue; the drain
                # re-routes the whole queue FIFO once the new home
                # resolves.
                record.state = "queued"
                q = self._fence_parked.setdefault(spec.actor_id, [])
                q.append(record)
                if len(q) == 1:
                    self._spawn_bg(
                        self._drain_fence_parked(spec.actor_id)
                    )
                return
            self._fail_task(
                record, ActorDiedError(spec.name, "actor's node died")
            )
            return
        if home is not None:
            self._forward_record(record, home)
            return
        if record.origin is not None or self._gcs is None:
            self._fail_task(
                record, ActorDiedError(spec.name, "actor not found")
            )
            return
        asyncio.ensure_future(self._route_actor_via_gcs(record))

    async def _drain_fence_parked(self, aid: ActorID):
        """Resolve the restarted actor's new home and re-route the
        parked queue FIFO (one drain task per actor; new calls keep
        appending to the queue until it empties, so nothing overtakes).
        The final drain is synchronous — no await between forwards —
        so a call routed right after cannot interleave."""
        deadline = time.monotonic() + self.config.object_locate_timeout_s
        while True:
            if self._shutdown:
                self._fence_parked.pop(aid, None)
                return
            if self._actors.get(aid) is not None:
                for rec in self._fence_parked.pop(aid, []):
                    if rec.state != "cancelled":
                        self._route_actor_task(rec)
                return
            home = self._actor_homes.get(aid)
            if home is not None and home != "dead":
                for rec in self._fence_parked.pop(aid, []):
                    if rec.state != "cancelled":
                        self._forward_record(rec, home)
                return
            nid = None
            if self._gcs is not None:
                try:
                    nid = await self._gcs.get_actor_node(aid)
                # Poll loop IS the handler (GCS blip -> next round).
                except Exception:  # rtlint: disable=swallowed-failure
                    nid = None
            if (nid is not None and nid != self.node_id
                    and nid.hex() not in self._fenced_nodes):
                if self._actor_homes.get(aid) in (None, "dead"):
                    self._actor_homes[aid] = nid.hex()
                continue  # drained via the home branch next iteration
            if time.monotonic() > deadline:
                for rec in self._fence_parked.pop(aid, []):
                    if rec.state != "cancelled":
                        self._fail_task(
                            rec,
                            ActorDiedError(
                                rec.spec.name,
                                "actor not found after fence restart",
                            ),
                        )
                return
            await asyncio.sleep(0.05)

    async def _route_actor_via_gcs(self, record: TaskRecord):
        """Handle deserialized on a node that has never seen this actor:
        resolve its home through the GCS actor directory, polling briefly in
        case creation is still in flight elsewhere."""
        spec = record.spec
        deadline = time.monotonic() + self.config.object_locate_timeout_s
        while True:
            try:
                nid = await self._gcs.get_actor_node(spec.actor_id)
            except Exception:
                nid = None
            if nid is not None:
                if nid == self.node_id:
                    if self._actors.get(spec.actor_id) is not None:
                        self._route_actor_task(record)
                        return
                else:
                    self._actor_homes[spec.actor_id] = nid.hex()
                    self._forward_record(record, nid.hex())
                    return
            if time.monotonic() > deadline:
                self._fail_task(
                    record, ActorDiedError(spec.name, "actor not found")
                )
                return
            await asyncio.sleep(0.05)

    async def _locate_missing(self, oid: ObjectID):
        """A dependency unknown to this node: find it through the GCS object
        directory, re-execute its creating task if we own the lineage, or
        fail the tasks waiting on it loudly."""
        found = await self._locate_via_gcs(oid)
        if found:
            return  # _locate_via_gcs sealed it; waiters have been woken.
        if await self._reconstruct_object(oid):
            return  # waiters stay parked; the re-executed task's seal wakes them
        waiters = self._dep_index.pop(oid, set())
        for tid in waiters:
            entry = self._waiting.pop(tid, None)
            if entry is None:
                continue
            rec, _missing = entry
            self._fail_task(
                rec,
                TaskError(
                    None,
                    rec.spec.name,
                    f"argument object {oid.hex()} is unknown or has been "
                    "freed; keep a live ObjectRef to it",
                ),
            )

    async def _locate_via_gcs(self, oid: ObjectID) -> bool:
        if self._gcs is None or not self._multi_node:
            return False
        try:
            nid = await self._gcs.locate_object(
                oid, timeout=self.config.object_locate_timeout_s
            )
        except Exception:
            return False
        if nid is None or nid == self.node_id:
            return False
        # The borrow is registered with the owner here (owner already
        # resolved — pass it through instead of repeating the locate
        # RPC). The holder's +1 delta lands BEFORE the blocking lookup
        # that triggered this (runtimes flush ref deltas ahead of
        # blocking requests on the same connection), so the count here is
        # already the holder's — no compensating pin (the old interim
        # scheme's) is needed.
        self._adopt_remote_object(oid, nid)
        await self._register_borrow(oid, owner_hex=nid.hex())
        return True

    def _infeasible_may_wait(self, record: TaskRecord) -> bool:
        """Whether a cluster-wide-infeasible task should stay queued
        (``infeasible_grace_s`` window) so an autoscaler can provision a
        fitting node, instead of failing fast. Schedules a re-check at
        grace expiry so the eventual failure does not need an event."""
        grace = self.config.infeasible_grace_s
        if grace <= 0:
            return False
        now = time.monotonic()
        if record.infeasible_since is None:
            record.infeasible_since = now
            try:
                loop = asyncio.get_event_loop()
                loop.call_later(grace + 0.05, self._schedule)
            except Exception:
                pass
            return True
        return (now - record.infeasible_since) < grace

    def _sched_class(self, record: TaskRecord) -> Tuple:
        """Scheduling-class key (ref analogue: SchedulingClassDescriptor —
        task_spec.h GetSchedulingClass): tasks with the same resource
        shape, strategy, and worker type hit identical capacity walls, so
        one representative's failure defers the whole class this pass."""
        if record.sched_class is None:
            spec = record.spec
            strat = getattr(spec, "scheduling_strategy", None)
            if isinstance(strat, PlacementGroupSchedulingStrategy):
                skey = ("pg", strat.pg_id, getattr(strat, "bundle_index", -1))
            elif strat is None or isinstance(strat, str):
                skey = ("s", strat)
            else:
                # Unknown strategy object: never group (unique per record).
                skey = ("u", id(record))
            record.sched_class = (
                skey,
                tuple(sorted(spec.resources.to_dict().items())),
                _task_worker_type(spec),
                # Forwarded records route differently from locally-owned
                # ones — never let one block the other's class.
                record.origin is None,
            )
        return record.sched_class

    def _schedule(self):
        """Request a dispatch pass. Debounced: any number of triggers in
        one loop iteration (a burst of submits or completions) coalesce
        into ONE pass on the next callback slot."""
        if self._sched_pending or self._shutdown:
            return
        self._sched_pending = True
        self._loop.call_soon(self._schedule_pass)

    def _schedule_pass(self):
        """Dispatch ready tasks to idle workers while resources allow
        (ref analogue: LocalTaskManager::DispatchScheduledTasksToWorkers).
        Visits each scheduling class once, dispatching from its head until
        the class hits a capacity wall — a deep homogeneous queue costs
        O(#classes + #dispatched), not O(#queued)."""
        self._sched_pending = False
        if self._shutdown:
            return
        spawn_needed: Set[str] = set()
        if self._multi_node:
            self._cluster_view[self.node_id.hex()] = self._local_view()
        ready = self._ready
        for cls in list(ready.classes.keys()):
            while True:
                q = ready.classes.get(cls)
                if q is None:
                    break  # class drained (deque deleted by remove_head)
                record = q[0]
                if self._dispatch_record(record, spawn_needed):
                    ready.remove_head(cls)
                else:
                    break  # head blocked on capacity: skip rest of class
        for wtype in spawn_needed:
            self._maybe_spawn_worker(wtype)

    def _dispatch_record(self, record: TaskRecord,
                         spawn_needed: Set[str]) -> bool:
        """Try to place one ready record. True = record consumed (it was
        dispatched, forwarded, failed, or re-queued elsewhere) — remove it
        from its class queue; False = blocked on capacity, leave it at the
        head and skip the rest of its class this pass."""
        if record.state == "cancelled":
            return True
        spec = record.spec
        raw_strategy = getattr(spec, "scheduling_strategy", None)
        if isinstance(raw_strategy, PlacementGroupSchedulingStrategy):
            # Placement-group routing: the bundle map decides the node;
            # resources come from the bundle reservation.
            targets = self._pg_targets(raw_strategy)
            if targets is None:
                record.state = "pg_resolving"
                self._queue_pg_resolve(record)
                return True
            if not targets:
                self._fail_task(
                    record,
                    TaskError(
                        None, spec.name,
                        "placement group bundle index out of range",
                    ),
                )
                return True
            if self.node_id.hex() not in targets:
                if record.spillbacks >= self.config.max_task_spillback:
                    # Routing cache may be stale (group re-placed after a
                    # node death): drop it and re-resolve via the GCS
                    # instead of spinning forward/requeue (advisor r1).
                    self._pg_nodes.pop(raw_strategy.pg_id, None)
                    record.state = "pg_resolving"
                    self._queue_pg_resolve(record)
                    return True
                if record.origin is None:
                    self._forward_record(record, targets[0])
                    return True
                return False
            if self._find_local_bundle(raw_strategy, spec.resources) is None:
                reason = self._pg_unservable(raw_strategy, spec.resources)
                if reason is not None:
                    self._fail_task(
                        record, TaskError(None, spec.name, reason)
                    )
                    return True
                return False  # bundle busy, wait
        else:
            strategy = raw_strategy or "DEFAULT"
            if (
                record.origin is None
                and self._multi_node
                and record.spillbacks < self.config.max_task_spillback
                and (
                    strategy != "DEFAULT"
                    or not self.node_resources.can_fit(spec.resources)
                )
            ):
                target = pick_node(
                    spec.resources,
                    strategy,
                    self.node_id.hex(),
                    list(self._cluster_view.values()),
                    spread_threshold=self.config.scheduler_spread_threshold,
                )
                if target is None:
                    if self._infeasible_may_wait(record):
                        return False
                    self._fail_task(
                        record,
                        TaskError(
                            None,
                            spec.name,
                            f"infeasible resource request "
                            f"{spec.resources.to_dict()} on every node in "
                            f"the cluster",
                        ),
                    )
                    return True
                if target != self.node_id.hex():
                    self._forward_record(record, target)
                    return True
            if not self.node_resources.can_fit(spec.resources):
                if not self.node_resources.is_feasible(spec.resources):
                    if self._infeasible_may_wait(record):
                        return False
                    self._fail_task(
                        record,
                        TaskError(
                            None,
                            spec.name,
                            f"infeasible resource request "
                            f"{spec.resources.to_dict()} on node with "
                            f"{self.node_resources.total.to_dict()}",
                        ),
                    )
                    return True
                # Node full: ride an existing same-shape hold instead of
                # blocking — this is what keeps a saturated node streaming
                # batches of small tasks through its workers.
                rider = self._pipeline_candidate(
                    _task_worker_type(spec), spec
                )
                if rider is not None:
                    return self._dispatch_as_rider(record, rider)
                return False
        wtype = _task_worker_type(spec)
        worker = self._take_idle_worker(wtype)
        if worker is None:
            # Prefer a NEW worker while the pool can still grow (pipelining
            # onto a busy worker would serialize tasks with CPUs free);
            # ride a busy worker's hold only once the pool is saturated.
            if not self._can_grow_pool(wtype):
                rider = self._pipeline_candidate(wtype, spec)
                if rider is not None:
                    return self._dispatch_as_rider(record, rider)
            spawn_needed.add(wtype)
            return False
        if not self._acquire_for_record(record):
            # Lost the race (bundle drained between check and acquire).
            self._idle[worker.worker_type].appendleft(worker.worker_id)
            return False
        record.resources_held = True
        record.state = "running"
        record.worker_id = worker.worker_id
        record.dispatched = time.monotonic()
        record.hang_warned = False  # fresh run: the detector re-arms
        worker.state = "busy"
        worker.current = record
        self._send_execute_to(worker, spec)
        return True

    def _can_grow_pool(self, wtype: str) -> bool:
        """Whether another worker process could still be added and used
        (mirrors _maybe_spawn_worker's bound: dispatchable slots = CPUs
        plus blocked workers, capped by max_workers)."""
        if len(self._workers) + self._num_starting() >= self.config.max_workers:
            return False
        cpu_total = max(1, int(self.node_resources.total.get(CPU)))
        n_blocked = sum(
            1 for w in self._workers.values() if w.state == "blocked"
        )
        active = sum(
            1 for w in self._workers.values() if w.state != "dead"
        )
        return active + self._num_starting() < cpu_total + n_blocked

    def _pipeline_candidate(
        self, wtype: str, spec: TaskSpec
    ) -> Optional[WorkerHandle]:
        """A busy (non-actor, non-blocked) worker whose CURRENT task holds
        the same resource shape: the next task rides that worker's
        existing resource hold ("lease") and its socket buffer — no
        per-task acquire/release, no dispatch round-trip (ref analogue:
        direct_task_transport.cc OnWorkerIdle reusing the leased worker
        for queued tasks of the same scheduling class)."""
        depth = self.config.worker_pipeline_depth
        if depth <= 1 or spec.task_type != TaskType.NORMAL_TASK:
            return None
        if isinstance(
            getattr(spec, "scheduling_strategy", None),
            PlacementGroupSchedulingStrategy,
        ):
            # PG tasks must go through bundle acquisition — a rider would
            # bypass the bundle's reservation and break PG isolation.
            return None
        shape = spec.resources.to_dict()
        best = None
        for w in self._workers.values():
            if (
                w.state == "busy"
                and w.worker_type == wtype
                and w.actor_id is None
                and w.current is not None
                and w.current.bundle_key is None
                and w.current.spec.task_type == TaskType.NORMAL_TASK
                and len(w.pending) < depth - 1
                and w.current.spec.resources.to_dict() == shape
            ):
                if best is None or len(w.pending) < len(best.pending):
                    best = w
        return best

    def _dispatch_as_rider(
        self, record: TaskRecord, worker: WorkerHandle
    ) -> bool:
        """Queue a record onto a busy worker under that worker's existing
        resource hold. Riders never hold resources themselves; the hold
        is transferred head-to-head as tasks complete (_on_task_done)."""
        record.resources_held = False
        record.state = "running"
        record.worker_id = worker.worker_id
        record.dispatched = time.monotonic()
        record.hang_warned = False  # fresh run: the detector re-arms
        worker.pending.append(record)
        self._send_execute_to(worker, record.spec)
        return True

    def _take_idle_worker(self, worker_type: str = "cpu") -> Optional[WorkerHandle]:
        pool = self._idle[worker_type]
        while pool:
            wid = pool.popleft()
            w = self._workers.get(wid)
            if w is not None and w.state == "idle":
                return w
        return None

    def _num_starting(self) -> int:
        return sum(self._starting_workers.values())

    def _maybe_spawn_worker(self, worker_type: str = "cpu"):
        """Spawn workers demand-driven but bounded by schedulable slots:
        more worker processes than CPU slots can dispatch is pure thrash
        (ref analogue: worker_pool.h PopWorker-triggered starts bounded by
        maximum_startup_concurrency)."""
        demand = self._ready.count_worker_type(worker_type)
        if demand == 0:
            return
        capacity = len(self._workers) + self._num_starting()
        if capacity >= self.config.max_workers:
            return
        cpu_total = max(1, int(self.node_resources.total.get(CPU)))
        n_blocked = sum(1 for w in self._workers.values() if w.state == "blocked")
        # Blocked workers released their CPU, so extra tasks may run.
        want = min(demand, cpu_total + n_blocked)
        n_idle = len(self._idle[worker_type])
        usable = n_idle + self._starting_workers[worker_type]
        if usable < want:
            self._spawn_worker(worker_type)

    async def _send_execute(self, worker: WorkerHandle, spec: TaskSpec):
        blob = None
        if spec.function_id not in worker.known_functions:
            blob = await self._function_blob(spec.function_id)
            worker.known_functions.add(spec.function_id)
        try:
            await worker.writer.send(
                {"type": "execute", "spec": spec, "function_blob": blob}
            )
        except Exception:
            await self._on_worker_death(worker)

    def _send_execute_to(self, worker: WorkerHandle, spec: TaskSpec):
        """Ship one execute frame, preserving per-worker frame order: the
        synchronous fast path only runs while no async send (blob fetch)
        is still in flight, else a later frame could overtake it. Fast
        frames are coalesced per loop iteration and flushed as ONE
        socket write per worker (_flush_execute_bufs)."""
        if (
            spec.function_id in worker.known_functions
            and worker.slow_sends == 0
        ):
            if not worker.exec_buf and not self._exec_dirty:
                self._loop.call_soon(self._flush_execute_bufs)
            if not worker.exec_buf:
                self._exec_dirty.append(worker)
            worker.exec_buf.append(
                {"spec": spec, "function_blob": None}
            )
            return
        # Slow path (blob fetch): flush this worker's buffered fast
        # frames NOW so the async frame cannot overtake them.
        self._flush_worker_exec_buf(worker)

        async def _ordered():
            # The lock is taken before the first await inside, and tasks
            # start in ensure_future order, so frames go out in submission
            # order even when blob fetches finish out of order.
            async with worker.send_lock:
                try:
                    await self._send_execute(worker, spec)
                finally:
                    worker.slow_sends -= 1

        worker.slow_sends += 1
        asyncio.ensure_future(_ordered())

    def _flush_worker_exec_buf(self, worker: WorkerHandle):
        buf = worker.exec_buf
        if not buf:
            return
        worker.exec_buf = []
        msg = (
            {"type": "execute", **buf[0]}
            if len(buf) == 1
            else {"type": "execute_batch", "items": buf}
        )
        try:
            worker.writer.send_nowait(msg)
        except Exception:
            asyncio.ensure_future(self._on_worker_death(worker))

    def _flush_execute_bufs(self):
        dirty = self._exec_dirty
        self._exec_dirty = []
        for worker in dirty:
            self._flush_worker_exec_buf(worker)

    def _advance_worker_pipeline(
        self, w: WorkerHandle, task_id: TaskID,
        record: Optional[TaskRecord],
    ):
        """Advance current/pending past a completed non-actor task and
        move the resource hold: the worker's chain rides ONE hold, passed
        head-to-head so completion costs no release/acquire round trip
        (ref analogue: direct_task_transport.cc worker-lease reuse)."""
        if w.current is not None and w.current.spec.task_id == task_id:
            fin = w.current
            nxt = w.pending.popleft() if w.pending else None
            if (
                fin.resources_held
                and nxt is not None
                and not nxt.resources_held
            ):
                fin.resources_held = False
                nxt.resources_held = True
                nxt.bundle_key = fin.bundle_key
            else:
                self._release_task_resources(fin)
            w.current = nxt
        elif record is not None:
            # Out-of-order completion (reclaim/cancel races): drop by
            # identity; riders hold nothing so release is a no-op.
            self._release_task_resources(record)
            try:
                w.pending.remove(record)
            except ValueError:
                w.current = None
        else:
            for r in list(w.pending):
                if r.spec.task_id == task_id:
                    self._release_task_resources(r)
                    w.pending.remove(r)
                    break
        if w.current is None and w.state != "dead":
            w.state = "idle"
            self._idle[w.worker_type].append(w.worker_id)

    async def _on_task_done(self, w: WorkerHandle, msg: Dict[str, Any]):
        task_id: TaskID = msg["task_id"]
        record = self._tasks.get(task_id)
        results: List[Tuple[ObjectID, Location]] = msg["results"]
        # Apply the worker's ref deltas FIRST — even for a record already
        # dropped by cancellation/failure: drain() removed them from the
        # worker's table, so this frame is their only carrier; dropping
        # them would desynchronize counts permanently.
        deltas = msg.get("ref_deltas")
        if deltas:
            await self._apply_ref_deltas(deltas)
        if record is None:
            # Cancelled/failed while the done frame was in flight: the
            # seals already happened (_fail_task), but the worker's
            # pipeline bookkeeping must still advance or its hold leaks.
            if w.actor_id is None:
                self._advance_worker_pipeline(w, task_id, None)
                self._schedule()
            return
        for oid, loc in results:
            self._seal_object(oid, loc)
        # Returns' contained refs BEFORE dropping the task's pins /
        # notifying the origin: a ref returned inside a container must be
        # pinned — and any cross-node borrow registered with its owner —
        # while the submission-time pin still protects the object.
        for roid, nested in (msg.get("nested") or ()):
            self._register_nested(roid, nested)
        # A "duplicate" completion is an NM-path replay of a direct call
        # the worker already executed (and already reported through its
        # direct_done_batch notification): the record still finishes,
        # but stats/duration/history were counted once already.
        duplicate = bool(msg.get("duplicate"))
        if msg.get("failed"):
            if not duplicate:
                self._stats["tasks_failed"] += 1
            record.state = "failed"
        else:
            if not duplicate:
                self._stats["tasks_finished"] += 1
            record.state = "finished"
        if record.dispatched is not None and not duplicate:
            self._observe_task_duration(
                time.monotonic() - record.dispatched
            )
        if record.origin is not None:
            self._notify_origin(record, failed=bool(msg.get("failed")))
        # Creation-task deps stay pinned while the actor may restart (the
        # creation spec re-executes with the same arguments). Terminal
        # normal/actor-task records are dropped to keep the head's memory
        # bounded (the spec holds serialized args) — their outcome is
        # retained in the bounded failure history instead.
        if record.spec.task_type != TaskType.ACTOR_CREATION_TASK:
            self._unpin_deps(record)
            if not duplicate:
                self._record_terminal_task(
                    record,
                    error_type=msg.get("error_type"),
                    error_message=msg.get("error_message"),
                    resource_usage=msg.get("resource_usage"),
                )
            self._tasks.pop(task_id, None)
        elif msg.get("failed"):
            self._unpin_deps(record)
        if w.actor_id is not None:
            info = self._actors.get(w.actor_id)
            if info is not None:
                info.inflight.pop(task_id, None)
                if record.spec.task_type == TaskType.ACTOR_CREATION_TASK:
                    if msg.get("failed"):
                        info.state = "dead"
                        info.death_cause = "actor constructor failed"
                        info.restarts_left = 0
                        cluster_events.emit(
                            cluster_events.ERROR, cluster_events.ACTOR,
                            f"actor {info.actor_id.hex()[:8]} "
                            f"({record.spec.class_name}) constructor "
                            f"failed: "
                            f"{msg.get('error_type') or 'Exception'}",
                            node_id=self.node_id.hex(),
                            actor_id=info.actor_id.hex(),
                            custom_fields={
                                "error_type": msg.get("error_type"),
                                "cause": "constructor failed",
                            },
                        )
                        self._fail_actor_queue(info)
                        if info.name:
                            self._named_actors.pop(info.name, None)
                        await self.kill_actor(info.actor_id)
                    else:
                        info.state = "alive"
                        self._flush_actor_queue(info)
        else:
            self._advance_worker_pipeline(w, task_id, record)
        self._schedule()

    async def _on_direct_done_batch(self, w: WorkerHandle, msg):
        """Completion notifications for calls executed over the direct
        actor-call plane (the worker already replied to the caller
        inline): the NM-side _on_task_done bookkeeping still fires here
        — ref deltas, seals for third-party consumers, holds for remote
        callers' RemoteLocation entries, duration telemetry and the
        terminal task history — one debounced batch frame per burst
        (see worker_main._note_direct_done)."""
        items = msg.get("items", ())
        self._stats["direct_done_batches"] += 1
        for item in items:
            sealed = item.get("stream_item")
            if sealed is not None:
                # An item of a stream that went to its consumer on the
                # direct channel (core/streaming.py): the entry a third
                # party resolves, with the item's one pin (on the
                # consumer's placeholder if that came first, a release
                # that overtook this batch already taken off it), or
                # the hold for a consumer on another node.
                oid, loc = sealed
                await self.put_object(oid, loc, item["refs"],
                                      nested=item.get("nested"))
                if item.get("held"):
                    self.directory.add_ref(oid)
                continue
            self._stats["direct_calls_done"] += 1
            deltas = item.get("ref_deltas")
            if deltas:
                await self._apply_ref_deltas(deltas)
            held = item.get("held")
            for oid, loc in item["results"]:
                self._seal_object(oid, loc)
                if held and not isinstance(loc, InlineLocation):
                    # The caller's node sealed a held RemoteLocation for
                    # this result; keep our copy until it frees it.
                    self.directory.add_ref(oid)
            dur = item.get("duration_s")
            if dur is not None:
                self._observe_task_duration(dur)
            if item.get("failed"):
                self._stats["tasks_failed"] += 1
            else:
                self._stats["tasks_finished"] += 1
            self._task_history.append({
                "task_id": item["task_id"].hex(),
                "name": item.get("name") or "task",
                "state": "failed" if item.get("failed") else "finished",
                "type": "ACTOR_TASK",
                "via": "direct",
                "node_id": self.node_id.hex(),
                "actor_id": item.get("actor_id"),
                "duration_s": round(dur, 6) if dur is not None else None,
                "error_type": item.get("error_type"),
                "error_message": (item.get("error_message") or "")[:500]
                                 or None,
                "cpu_time_s": None,
                "max_rss_bytes": None,
                "retry_count": 0,
                "retries_left": 0,
                "end_ts": time.time(),
                "retained": True,
            })
        self._schedule()

    def _seal_object(self, oid: ObjectID, loc: Location):
        existing = self.directory.lookup(oid)
        if existing is not None and oid in self._sealed:
            return
        if existing is None:
            self.directory.add(oid, loc, initial_refs=0)
        else:
            self.directory.seal_over_placeholder(oid, loc)
        self._sealed.add(oid)
        self._maybe_spill()
        ev = self._seal_events.pop(oid, None)
        if ev is not None:
            ev.set()
        for fut in self._parked_waits.pop(oid, ()):
            _resolve(fut)
        waiters = self._dep_index.pop(oid, None)
        if waiters:
            for tid in waiters:
                entry = self._waiting.get(tid)
                if entry is None:
                    continue
                rec, missing = entry
                missing.discard(oid)
                if not missing:
                    del self._waiting[tid]
                    self._task_ready(rec)
        if self._gcs is not None and (self._multi_node or not self.is_head) \
                and not isinstance(loc, RemoteLocation):
            asyncio.ensure_future(self._publish_seal(oid))

    async def _publish_seal(self, oid: ObjectID):
        try:
            await self._gcs.publish_object(oid, self.node_id)
        except Exception:
            pass

    def _unpin_deps(self, record: TaskRecord):
        if record.deps_unpinned:
            return
        record.deps_unpinned = True
        for oid in record.spec.pinned_ids():
            self.directory.remove_ref(oid)

    def _record_terminal_task(self, record: TaskRecord, *,
                              error_type: Optional[str] = None,
                              error_message: Optional[str] = None,
                              resource_usage: Optional[Dict[str, Any]]
                              = None):
        """Retain a terminal task's outcome in the bounded failure
        history (it is about to leave the live table)."""
        spec = record.spec
        dur = (time.monotonic() - record.dispatched
               if record.dispatched is not None else None)
        usage = resource_usage or {}
        self._task_history.append({
            # Worker-side resource sampler deltas (util/profiler
            # TaskResourceSampler): CPU seconds burned and peak RSS.
            "cpu_time_s": usage.get("cpu_s"),
            "max_rss_bytes": usage.get("max_rss_bytes"),
            "task_id": spec.task_id.hex(),
            "name": spec.name or spec.method_name or "task",
            "state": record.state,
            "type": spec.task_type.name,
            "node_id": self.node_id.hex(),
            "actor_id": spec.actor_id.hex() if spec.actor_id else None,
            "duration_s": round(dur, 6) if dur is not None else None,
            "error_type": error_type,
            "error_message": (error_message or "")[:500] or None,
            # retries_left counts DOWN from max_retries as crashes retry:
            # together they answer "did this task exhaust its retries?".
            "retry_count": spec.max_retries - spec.retries_left,
            "retries_left": spec.retries_left,
            "end_ts": time.time(),
            "retained": True,
        })

    def _fail_task(self, record: TaskRecord, error: TaskError):
        cancelled = isinstance(error, TaskCancelledError)
        record.state = "cancelled" if cancelled else "failed"
        self._stats["tasks_failed"] += 1
        self._unpin_deps(record)
        etype = type(error).__name__
        detail = (getattr(error, "traceback_str", "") or str(error)).strip()
        last_line = detail.splitlines()[-1] if detail else ""
        if record.spec.task_type != TaskType.ACTOR_CREATION_TASK:
            self._record_terminal_task(
                record, error_type=etype, error_message=detail
            )
            self._tasks.pop(record.spec.task_id, None)
        if not cancelled:
            # System-level failures (worker crash, actor death, node
            # loss): there is no worker alive to report the traceback, so
            # the control plane records the ERROR event itself.
            cluster_events.emit(
                cluster_events.ERROR, cluster_events.TASK,
                f"task '{record.spec.name or record.spec.method_name}' "
                f"failed: {etype}: {last_line}",
                node_id=self.node_id.hex(),
                task_id=record.spec.task_id.hex(),
                actor_id=(record.spec.actor_id.hex()
                          if record.spec.actor_id else None),
                custom_fields={"error_type": etype},
            )
        try:
            from .serialization import serialize

            blob = serialize(error).to_bytes()
        except Exception:
            from .serialization import serialize

            blob = serialize(
                TaskError(None, record.spec.name, "unserializable failure")
            ).to_bytes()
        for oid in record.spec.return_ids():
            self._seal_object(oid, InlineLocation(blob))
        if record.origin is not None:
            self._notify_origin(record, failed=True)

    # ------------------------------------------------------------------ actors

    def _pre_register_actor(self, spec: TaskSpec):
        if spec.actor_id in self._actors:
            return
        self._actors[spec.actor_id] = ActorInfo(
            actor_id=spec.actor_id,
            creation_spec=spec,
            restarts_left=spec.max_restarts,
            name=spec.name,
        )

    def _register_actor(self, record: TaskRecord):
        spec = record.spec
        info = self._actors.get(spec.actor_id)
        if info is None:
            info = ActorInfo(
                actor_id=spec.actor_id,
                creation_spec=spec,
                restarts_left=spec.max_restarts,
                name=spec.name,
            )
            self._actors[spec.actor_id] = info
        # Home + incarnation registration happens inside _place_actor
        # (the GCS assigns the incarnation the creation spec carries to
        # the worker — registering here too would mint a second one).
        asyncio.ensure_future(self._place_actor(info, record))

    async def _claim_actor_name(self, spec: TaskSpec) -> bool:
        """Atomically claim a named-actor slot (ref analogue: the name
        registry in GcsActorManager::HandleRegisterActor)."""
        if self._gcs is not None:
            try:
                return await self._gcs.register_named_actor(
                    spec.name, spec.actor_id, self.node_id, spec
                )
            except Exception:
                return False
        existing = self._named_actors.get(spec.name)
        if existing is not None:
            return existing == spec.actor_id
        self._named_actors[spec.name] = spec.actor_id
        return True

    async def _place_actor(self, info: ActorInfo, record: TaskRecord):
        spec = info.creation_spec
        # Every start/restart gets a GCS-assigned incarnation (the same
        # call records this node as the actor's home). The creation
        # spec carries it to the worker, which refuses direct hellos
        # naming any other incarnation — the fencing half of the direct
        # plane's stale-endpoint discipline.
        if self._gcs is not None:
            try:
                info.incarnation = await self._gcs.register_actor_node(
                    spec.actor_id, self.node_id
                )
            except Exception as e:  # noqa: BLE001
                # GCS unreachable mid-placement: fall back to a local
                # bump so restarts still move forward; the reconnect
                # republish ratchets the GCS counter up to ours.
                info.incarnation = max(1, info.incarnation + 1)
                sys.stderr.write(
                    f"[ray_tpu] actor {spec.actor_id.hex()[:8]} "
                    f"incarnation assignment via GCS failed ({e!r}); "
                    f"using local {info.incarnation}\n"
                )
        else:
            info.incarnation = max(1, info.incarnation + 1)
        spec.actor_incarnation = info.incarnation
        if spec.name:
            if not await self._claim_actor_name(spec):
                self._fail_task(
                    record,
                    TaskError(None, spec.name, f"actor name {spec.name!r} taken"),
                )
                info.state = "dead"
                info.death_cause = "name taken"
                return
            self._named_actors[spec.name] = spec.actor_id
        if not self.node_resources.is_feasible(spec.resources):
            self._fail_task(
                record,
                TaskError(
                    None, spec.name, f"infeasible actor resources "
                    f"{spec.resources.to_dict()}"
                ),
            )
            info.state = "dead"
            return
        wtype = _task_worker_type(spec)
        # Atomically acquire resources (acquire() both checks and takes, so
        # two concurrently-placing actors can't share an exclusive resource),
        # then wait for a worker without blocking the loop. PG-scheduled
        # actors draw from their bundle reservation instead of the pool.
        while not self._acquire_for_record(record):
            strategy = spec.scheduling_strategy
            if isinstance(strategy, PlacementGroupSchedulingStrategy):
                reason = self._pg_unservable(strategy, spec.resources)
                if reason is not None:
                    self._fail_task(record, TaskError(None, spec.name, reason))
                    info.state = "dead"
                    info.death_cause = reason
                    self._fail_actor_queue(info, reason)
                    return
            await asyncio.sleep(0.01)
            if self._shutdown:
                return
        worker = self._take_idle_worker(wtype)
        while worker is None:
            self._maybe_spawn_worker_for_actor(wtype)
            await asyncio.sleep(0.01)
            if self._shutdown:
                record.resources_held = True
                self._release_task_resources(record)
                return
            worker = self._take_idle_worker(wtype)
        worker.state = "actor"
        worker.actor_id = spec.actor_id
        info.worker_id = worker.worker_id
        record.state = "running"
        record.worker_id = worker.worker_id
        record.resources_held = True
        info.inflight[spec.task_id] = record
        self._stats["actors_created"] += 1
        # The actor transitions to "alive" (or "dead") in _on_task_done when
        # the creation task reports back.
        await self._send_execute(worker, spec)

    def _maybe_spawn_worker_for_actor(self, worker_type: str = "cpu"):
        capacity = len(self._workers) + self._num_starting()
        if capacity < self.config.max_workers and not self._idle[worker_type] \
                and self._starting_workers[worker_type] == 0:
            self._spawn_worker(worker_type)

    def _route_actor_task(self, record: TaskRecord):
        spec = record.spec
        info = self._actors.get(spec.actor_id)
        if info is None or info.state == "dead":
            cause = info.death_cause if info else "actor not found"
            self._fail_task(record, ActorDiedError(spec.name, cause))
            return
        if info.state in ("pending", "restarting"):
            if getattr(spec, "direct_replay", False):
                # A direct-channel call interrupted by the actor's death:
                # fails like NM-routed in-flight calls do on restart —
                # replaying it into the restarted actor would re-execute
                # an interrupted (possibly non-idempotent) method.
                self._fail_task(
                    record,
                    ActorDiedError(
                        spec.name,
                        "actor restarting (interrupted direct call)",
                    ),
                )
                return
            info.queued.append(spec)
            record.state = "queued"
            return
        if (getattr(spec, "direct_replay", False)
                and spec.actor_incarnation
                and info.incarnation
                and spec.actor_incarnation != info.incarnation):
            # Replay bound to an EARLIER incarnation of a now-alive
            # actor (restarted before the replay landed): the new
            # incarnation's replay-dedup cache knows nothing of the old
            # channel's calls — refuse instead of double-executing.
            _fencing.REFUSED_REPLAY.inc()
            self._fail_task(
                record,
                ActorDiedError(
                    spec.name,
                    f"fenced: replay bound to incarnation "
                    f"{spec.actor_incarnation}, actor is now "
                    f"incarnation {info.incarnation}",
                ),
            )
            return
        self._forward_actor_task(info, record)

    def _forward_actor_task(self, info: ActorInfo, record: TaskRecord):
        worker = self._workers.get(info.worker_id)
        if worker is None:
            info.queued.append(record.spec)
            return
        record.state = "running"
        record.worker_id = worker.worker_id
        info.inflight[record.spec.task_id] = record
        self._send_execute_to(worker, record.spec)

    def _flush_actor_queue(self, info: ActorInfo):
        while info.queued:
            spec = info.queued.popleft()
            record = self._tasks.get(spec.task_id)
            if record is None or record.state == "cancelled":
                continue
            self._forward_actor_task(info, record)

    def _fail_actor_queue(self, info: ActorInfo, cause: str = "actor died"):
        for spec in info.queued:
            rec = self._tasks.get(spec.task_id)
            if rec is not None:
                self._fail_task(rec, ActorDiedError(spec.name, cause))
        info.queued.clear()

    async def _on_actor_worker_death(self, w: WorkerHandle):
        info = self._actors.get(w.actor_id)
        if info is None:
            return
        creation_record = self._tasks.get(info.creation_spec.task_id)
        if creation_record is not None:
            self._release_task_resources(creation_record)
        graceful = getattr(w, "_graceful_exit", False)
        cause = "graceful exit" if graceful else "actor worker process died"
        inflight = list(info.inflight.values())
        info.inflight.clear()
        # A creation task that never reported back counts as failed.
        creation_pending = any(
            rec.spec.task_type == TaskType.ACTOR_CREATION_TASK for rec in inflight
        )
        if info.state == "dead":
            return
        # Old worker's direct endpoints are gone either way; callers'
        # channels die with the sockets and re-resolve after restart.
        info.direct_path = None
        info.direct_addr = None
        if not graceful and info.restarts_left != 0 and not self._shutdown:
            info.state = "restarting"
            if info.restarts_left > 0:
                info.restarts_left -= 1
            info.restart_count += 1
            cluster_events.emit(
                cluster_events.WARNING, cluster_events.ACTOR,
                f"actor {info.actor_id.hex()[:8]} "
                f"({info.creation_spec.class_name}) restarting "
                f"after worker death (restart #{info.restart_count}, "
                f"{info.restarts_left} left)",
                node_id=self.node_id.hex(),
                actor_id=info.actor_id.hex(),
                custom_fields={"class_name": info.creation_spec.class_name,
                               "restart_count": info.restart_count},
            )
            # Actor tasks are NOT retried by default (ref: max_task_retries=0
            # in the reference); interrupted calls fail with ActorDiedError
            # unless they carry retries, in which case they resubmit in order.
            for rec in reversed(inflight):
                if rec.spec.task_type != TaskType.ACTOR_TASK:
                    continue
                if rec.spec.retries_left > 0:
                    rec.spec.retries_left -= 1
                    info.queued.appendleft(rec.spec)
                else:
                    self._fail_task(
                        rec, ActorDiedError(rec.spec.name, "actor restarting")
                    )
            new_record = TaskRecord(spec=info.creation_spec)
            asyncio.ensure_future(self._restart_actor(info, new_record))
        else:
            info.state = "dead"
            info.death_cause = cause
            intentional = graceful or getattr(w, "_intentional_kill", False)
            cluster_events.emit(
                cluster_events.INFO if intentional else cluster_events.ERROR,
                cluster_events.ACTOR,
                f"actor {info.actor_id.hex()[:8]} "
                f"({info.creation_spec.class_name}) died: "
                + ("killed via ray_tpu.kill" if intentional and not graceful
                   else cause),
                node_id=self.node_id.hex(),
                actor_id=info.actor_id.hex(),
                custom_fields={"class_name": info.creation_spec.class_name,
                               "cause": cause,
                               "restart_count": info.restart_count},
            )
            if creation_pending and creation_record is not None:
                self._fail_task(
                    creation_record, ActorDiedError(info.creation_spec.name, cause)
                )
            for rec in inflight:
                if rec.spec.task_type == TaskType.ACTOR_TASK:
                    self._fail_task(rec, ActorDiedError(rec.spec.name, cause))
            self._fail_actor_queue(info, cause)
            if creation_record is not None:
                self._unpin_deps(creation_record)
            if info.name:
                self._named_actors.pop(info.name, None)
                if self._gcs is not None:
                    self._spawn_bg(
                        self._gcs.drop_named_actor(info.name, info.actor_id)
                    )

    async def _restart_actor(self, info: ActorInfo, record: TaskRecord):
        # Re-run the creation task on a fresh worker (ref analogue:
        # GcsActorManager::RestartActor).
        spec = info.creation_spec
        self._tasks[spec.task_id] = record
        ev = self._seal_events.get(spec.return_ids()[0])
        if ev is not None:
            ev.clear()
        self._sealed.discard(spec.return_ids()[0])
        await self._place_actor(info, record)

    async def _on_actor_graceful_exit(self, w: WorkerHandle, msg):
        w._graceful_exit = True

    async def kill_actor(self, actor_id: ActorID, no_restart: bool = True):
        if no_restart:
            # An intentional permanent kill also retires the owner-side
            # restart-elsewhere pin (no fence may resurrect it).
            self._actor_creations.pop(actor_id, None)
            self._actor_restart_budget.pop(actor_id, None)
        info = self._actors.get(actor_id)
        if info is None:
            home = self._actor_homes.get(actor_id)
            if home and home != "dead":
                try:
                    peer = await self._get_peer(home)
                    await peer.notify(
                        {
                            "type": "kill_actor_peer",
                            "actor_id": actor_id,
                            "no_restart": no_restart,
                        }
                    )
                except Exception:
                    pass
            return
        if no_restart:
            info.restarts_left = 0
        worker = self._workers.get(info.worker_id) if info.worker_id else None
        if worker is not None:
            try:
                await worker.writer.send({"type": "kill"})
            except Exception:
                pass
            if worker.proc is not None:
                worker._intentional_kill = True
                try:
                    worker.proc.kill()
                except Exception:
                    pass

    async def get_named_actor(self, name: str) -> Optional[TaskSpec]:
        if self._gcs is not None:
            entry = await self._gcs.get_named_actor(name)
            if entry is None:
                return None
            actor_id, node_id, spec = entry
            if node_id != self.node_id and actor_id not in self._actors:
                self._actor_homes.setdefault(actor_id, node_id.hex())
            return spec
        actor_id = self._named_actors.get(name)
        if actor_id is None:
            return None
        return self._actors[actor_id].creation_spec

    # ---------------------------------------------------------------- objects

    async def put_object(self, object_id: ObjectID, loc: Location,
                         refs: int = 1, *, pin_if_new: bool = False,
                         nested: Optional[List[ObjectID]] = None):
        # pin_if_new: carry ``refs`` only when the directory has no entry
        # yet (streaming re-seal after a retry — a surviving original entry
        # keeps its original pin; adding more would leak it permanently).
        if pin_if_new and self.directory.lookup(object_id) is not None:
            refs = 0
        self.directory.add(object_id, loc, initial_refs=refs, owner="put")
        self._seal_object(object_id, loc)
        if nested:
            # Refs serialized inside the put value stay alive as long as
            # the containing object does (AddNestedObjectIds analogue).
            self._register_nested(object_id, nested)

    async def get_locations(
        self, object_ids: List[ObjectID], timeout: Optional[float] = None
    ) -> List[Tuple[ObjectID, Location]]:
        events = []
        for oid in object_ids:
            if oid not in self._sealed:
                if (self.directory.lookup(oid) is None
                        or oid in self._borrow_stubs):
                    # Never registered here (or only as a count-only
                    # borrow stub): try the GCS object directory
                    # (cross-node borrow), then lineage re-execution, else
                    # fail loudly — waiting would hang forever (ref analogue:
                    # OwnershipBasedObjectDirectory lookup before PullManager
                    # engages).
                    if await self._locate_via_gcs(oid):
                        continue
                    if await self._reconstruct_object(oid):
                        events.append(
                            self._seal_events.setdefault(oid, asyncio.Event())
                        )
                        continue
                    raise ObjectLostError(
                        f"object {oid.hex()} is unknown or has been freed; "
                        "if it was only referenced from inside a container "
                        "argument, keep a live ObjectRef to it"
                    )
                events.append(self._seal_events.setdefault(oid, asyncio.Event()))
                if oid in self._lineage:
                    # No-op while the creating task is in flight; re-executes
                    # it when the entry was unsealed by a node death.
                    await self._reconstruct_object(oid)
        if events:
            if any(not ev.is_set() for ev in events):
                # ONE task for the whole set (wait_for wraps the helper
                # once) instead of gather's Task per object: a deep
                # drain get() used to mint 1M asyncio Tasks here.
                # Sequential awaits are equivalent — every event must be
                # set before returning, and they fire independently of
                # the await order.
                async def _wait_all(evs=events):
                    for ev in evs:
                        if not ev.is_set():
                            await ev.wait()

                await asyncio.wait_for(_wait_all(), timeout)
        out: List[Tuple[ObjectID, Location]] = []
        for oid in object_ids:
            loc = self.directory.lookup(oid)
            if isinstance(loc, RemoteLocation):
                loc = await self._ensure_local(oid, loc)
            if isinstance(loc, SpilledLocation):
                loc = await self._restore_spilled(oid, loc)
            out.append((oid, loc))
        return out

    async def _ensure_local(self, oid: ObjectID, loc: RemoteLocation) -> Location:
        """Pull a remote object's bytes and re-home them locally, deduping
        concurrent pulls (ref analogue: PullManager bundles + the object
        buffer pool's single in-flight chunk set per object). A failed pull
        goes through object recovery (replica re-locate, then lineage
        re-execution) before surfacing ObjectLostError."""
        while True:
            fut = self._pulls.get(oid)
            if fut is None:
                fut = asyncio.ensure_future(self._pull_object(oid, loc))
                self._pulls[oid] = fut

                def _cleanup(f, oid=oid):
                    if self._pulls.get(oid) is f:
                        del self._pulls[oid]

                fut.add_done_callback(_cleanup)
            try:
                return await asyncio.shield(fut)
            except ObjectLostError:
                if not await self._recover_object(oid, exclude_hex=loc.node_id):
                    raise
                new_loc = await self._wait_recovered(oid)
                if not isinstance(new_loc, RemoteLocation):
                    return new_loc
                loc = new_loc

    # --------------------------------------------------------- object recovery

    def _can_reconstruct(self, oid: ObjectID) -> bool:
        return (
            oid in self._lineage
            and self._reconstructions.get(oid, 0)
            < self.config.max_object_reconstructions
        )

    async def _recover_object(
        self, oid: ObjectID, exclude_hex: Optional[str] = None
    ) -> bool:
        """Make a lost object readable again: prefer another live replica
        from the GCS directory, else re-execute the creating task from
        lineage (ref analogue: ObjectRecoveryManager::RecoverObject —
        PinExistingObjectCopy first, ReconstructObject second)."""
        self._sealed.discard(oid)
        if self._gcs is not None and self._multi_node:
            try:
                nid = await self._gcs.locate_object(oid, timeout=0)
            except Exception:
                nid = None
            if (
                nid is not None
                and nid != self.node_id
                and nid.hex() != exclude_hex
                and nid.hex() in self._cluster_view
            ):
                self.directory.replace_location(oid, RemoteLocation(nid.hex(), 0))
                self._seal_object(oid, RemoteLocation(nid.hex(), 0))
                return True
        return await self._reconstruct_object(oid)

    async def _reconstruct_object(self, oid: ObjectID) -> bool:
        """Re-execute the creating task of a lost object, within the
        per-object reconstruction budget."""
        if not self._can_reconstruct(oid):
            return False
        spec = self._lineage[oid]
        live = self._tasks.get(spec.task_id)
        if live is not None and live.state in (
            "waiting", "ready", "running", "forwarded", "pg_resolving"
        ):
            # The creating task is already in flight (sibling return slot
            # kicked off recovery, or a retry is running): wait for its seal.
            return True
        self._reconstructions[oid] = self._reconstructions.get(oid, 0) + 1
        self._stats["tasks_retried"] += 1
        for rid in spec.return_ids():
            self._sealed.discard(rid)
        await self.submit_task(spec)
        return True

    async def _wait_recovered(self, oid: ObjectID) -> Location:
        """Block until the recovered object (or its failure blob) seals."""
        if oid not in self._sealed:
            ev = self._seal_events.setdefault(oid, asyncio.Event())
            await ev.wait()
        return self.directory.lookup(oid)

    # ----------------------------------------------------------- spilling

    def _maybe_spill(self, need: int = 0):
        """Start one spill pass when store usage crosses the high-water
        mark, or when a caller explicitly needs ``need`` bytes freed
        regardless of the mark (pull admission below high water; ref
        analogue: LocalObjectManager::SpillObjectUptoMaxThroughput
        triggered from the eviction path)."""
        cap = self.directory.capacity_bytes
        if not self.directory.spill_enabled or self._spilling or cap <= 0:
            return
        if (
            need <= 0
            and self.directory.used_bytes
            <= cap * self.config.spill_high_water_frac
        ):
            return
        self._spilling = True
        self._spawn_bg(self._spill_pass(need))

    async def _spill_pass(self, extra_need: int = 0):
        """Move LRU local objects to disk until under the low-water mark
        (or until ``extra_need`` bytes are freed, whichever is more).
        Byte IO runs in executor threads; the directory entry swaps via
        compare-and-swap so racing reads/GC stay correct."""
        try:
            target = int(
                self.directory.capacity_bytes * self.config.spill_low_water_frac
            )
            need = max(self.directory.used_bytes - target, extra_need)
            if need <= 0:
                return
            spilled_n = spilled_bytes = 0
            for oid, loc in self.directory.spill_candidates(need):
                try:
                    data = self.local_store.get_bytes(loc)
                except Exception:
                    continue  # lost the race with GC
                try:
                    sloc = await self._loop.run_in_executor(
                        None, self.spill_manager.write, oid, data
                    )
                except Exception:
                    continue  # disk trouble: skip, keep relieving others
                if self.directory.replace_if(oid, loc, sloc):
                    _free_location(loc)
                    spilled_n += 1
                    spilled_bytes += len(data)
                else:
                    self.spill_manager.delete(sloc)
            if spilled_n:
                cluster_events.emit(
                    cluster_events.INFO, cluster_events.OBJECT_STORE,
                    f"spilled {spilled_n} object(s) "
                    f"({spilled_bytes} bytes) to disk",
                    node_id=self.node_id.hex(),
                    custom_fields={"objects": spilled_n,
                                   "bytes": spilled_bytes},
                )
        finally:
            self._spilling = False
            # Puts/restores that landed mid-pass can leave usage above the
            # mark with no future trigger — re-check so pressure can't get
            # stranded between passes. Delayed, so a pass that cannot make
            # progress (full disk, all candidates raced) does not respawn
            # itself in a tight loop.
            self._loop.call_later(0.2, self._maybe_spill)

    async def _restore_spilled(
        self, oid: ObjectID, sloc: SpilledLocation
    ) -> Location:
        """Bring a spilled object back into the store, deduping concurrent
        restores (ref analogue: the restore IO-worker path of
        LocalObjectManager + PinObjectIDs)."""
        fut = self._restores.get(oid)
        if fut is None:
            fut = asyncio.ensure_future(self._restore_io(oid, sloc))
            self._restores[oid] = fut

            def _cleanup(f, oid=oid):
                if self._restores.get(oid) is f:
                    del self._restores[oid]

            fut.add_done_callback(_cleanup)
        return await asyncio.shield(fut)

    async def _restore_io(self, oid: ObjectID, sloc: SpilledLocation) -> Location:
        data = await self._loop.run_in_executor(
            None, self.spill_manager.read, sloc
        )
        if len(data) <= self.config.max_inline_object_size:
            new_loc: Location = InlineLocation(bytes(data))
        else:
            new_loc = self.local_store.put_raw(oid, data)
        if self.directory.replace_if(oid, sloc, new_loc):
            self.spill_manager.delete(sloc)
            cluster_events.emit(
                cluster_events.DEBUG, cluster_events.OBJECT_STORE,
                f"restored object {oid.hex()[:8]} from disk "
                f"({len(data)} bytes)",
                node_id=self.node_id.hex(),
                custom_fields={"object_id": oid.hex(),
                               "bytes": len(data)},
            )
            self._maybe_spill()  # restoring may re-cross the high-water mark
            return new_loc
        cur = self.directory.lookup(oid)
        return cur if cur is not None else new_loc

    # ------------------------------------------------------ memory monitor

    async def _memory_monitor_loop(self):
        """Kill the newest retriable running task's worker under system
        memory pressure (ref: MemoryMonitor common/memory_monitor.h:52 +
        retriable-FIFO policy worker_killing_policy_retriable_fifo.h)."""
        thresh = self.config.memory_usage_threshold
        if thresh <= 0:
            return
        while not self._shutdown:
            await asyncio.sleep(self.config.memory_monitor_interval_s)
            try:
                frac = _system_memory_usage_fraction()
            except Exception:
                continue
            if frac < thresh:
                continue
            victim = self._pick_oom_victim()
            if victim is None:
                continue
            worker, record = victim
            sys.stderr.write(
                f"[ray_tpu] memory pressure ({frac:.0%}): killing task "
                f"'{record.spec.name}' (worker {worker.worker_id.hex()[:8]})\n"
            )
            cluster_events.emit(
                cluster_events.ERROR, cluster_events.RAYLET,
                f"memory pressure ({frac:.0%}): OOM-killing task "
                f"'{record.spec.name}' "
                f"(worker {worker.worker_id.hex()[:8]}, "
                f"retries_left={record.spec.retries_left})",
                node_id=self.node_id.hex(),
                task_id=record.spec.task_id.hex(),
                custom_fields={"memory_usage_frac": round(frac, 4),
                               "retriable": record.spec.retries_left > 0},
            )
            worker._oom_killed = True
            if worker.proc is not None:
                try:
                    worker.proc.kill()
                except Exception:
                    pass

    def _pick_oom_victim(self):
        """Newest running non-actor task, preferring one with retries left
        so the kill is survivable (retriable-FIFO, ref:
        worker_killing_policy_retriable_fifo.h:34)."""
        retriable, any_task = None, None
        for w in self._workers.values():
            if w.state != "busy" or w.current is None or w.actor_id is not None:
                continue
            rec = w.current
            if any_task is None or rec.created > any_task[1].created:
                any_task = (w, rec)
            if rec.spec.retries_left > 0 and (
                retriable is None or rec.created > retriable[1].created
            ):
                retriable = (w, rec)
        return retriable or any_task

    async def _pull_object(self, oid: ObjectID, loc: RemoteLocation) -> Location:
        try:
            peer = await self._get_peer(loc.node_id)
            got = await self._transfer.pull(peer, oid)
        except Exception as e:
            raise ObjectLostError(
                f"object {oid.hex()} unavailable from node "
                f"{loc.node_id[:8]}: {e}"
            ) from e
        if isinstance(got, (bytes, bytearray, memoryview)):
            if len(got) <= self.config.max_inline_object_size:
                new_loc: Location = InlineLocation(bytes(got))
            else:
                new_loc = self.local_store.put_raw(oid, got)
        else:
            # Chunked pull: bytes already landed in the local store.
            new_loc = got
        self.directory.replace_location(oid, new_loc)
        # The pulled copy is now the locatable one (the source may free and
        # unpublish its copy once the hold is released).
        if self._gcs is not None and (self._multi_node or not self.is_head):
            asyncio.ensure_future(self._publish_seal(oid))
        if loc.held:
            # Release the hold the remote node keeps on our behalf.
            try:
                await peer.notify({"type": "free_object", "object_id": oid})
            except Exception:
                pass
        return new_loc

    async def wait_objects(
        self,
        object_ids: List[ObjectID],
        num_returns: int,
        timeout: Optional[float],
    ) -> List[ObjectID]:
        """The sealed ones of ``object_ids``, once ``num_returns`` of
        them are or ``timeout`` has run out. A call that has to wait
        parks ONE future on every object it still waits for
        (``_parked_waits``); ``_seal_object`` resolves it."""
        sealed = self._sealed
        ready = [oid for oid in object_ids if oid in sealed]
        if len(ready) >= num_returns or (timeout is not None
                                         and timeout <= 0):
            return ready
        deadline = None if timeout is None else time.monotonic() + timeout
        waits = self._parked_waits
        locating: List[asyncio.Future] = []
        if self._gcs is not None and self._multi_node:
            for oid in object_ids:
                if oid not in sealed and (
                        not self.directory.has_entry(oid)
                        or oid in self._borrow_stubs):
                    # Nothing on this node will ever seal it (a streamed
                    # item of a producer elsewhere, a ref borrowed
                    # unsealed): its publication in the GCS object
                    # directory has to.
                    locating.append(asyncio.ensure_future(
                        self._seal_when_published(oid)
                    ))
        try:
            while True:
                fut = self._loop.create_future()
                parked = [oid for oid in object_ids if oid not in sealed]
                for oid in parked:
                    waits.setdefault(oid, []).append(fut)
                timer = None
                if deadline is not None:
                    timer = self._loop.call_later(
                        deadline - time.monotonic(), _resolve, fut)
                try:
                    await fut
                finally:
                    if timer is not None:
                        timer.cancel()
                    for oid in parked:
                        futs = waits.get(oid)
                        if futs is None or fut not in futs:
                            continue  # sealed: the seal took the list
                        futs.remove(fut)
                        if not futs:
                            # The last wait on an object that has not
                            # come to be (a stream's item past its end)
                            # leaves nothing of it behind.
                            del waits[oid]
                ready = [oid for oid in object_ids if oid in sealed]
                if len(ready) >= num_returns or (
                        deadline is not None
                        and time.monotonic() >= deadline):
                    return ready
        finally:
            for t in locating:
                t.cancel()

    def _would_park(self, object_ids: List[ObjectID],
                    timeout: Optional[float]) -> bool:
        """Whether a wait for the first of ``object_ids`` has to wait."""
        sealed = self._sealed
        return (timeout is None or timeout > 0) and not any(
            oid in sealed for oid in object_ids)

    async def wait_carrying(
        self, object_ids: List[ObjectID], timeout: Optional[float],
    ) -> Tuple[List[ObjectID], Dict[ObjectID, Location], bool]:
        """A stream consumer's one request an item: park until the first
        of ``object_ids`` is sealed (the item, or its stream's
        completion) and bring back what a ``get_locations`` for it would
        say. ``(ready, locations, parked)``: ``locations`` holds every
        ready id whose entry a process of this node can read as it
        stands (inline bytes, which so ride along, or this node's
        store); a remote or spilled one is left out and its reader asks
        ``get_locations``, which pulls or restores. ``parked`` says the
        call had to wait."""
        parked = self._would_park(object_ids, timeout)
        ready = await self.wait_objects(object_ids, 1, timeout)
        locations = {}
        for oid in ready:
            loc = self.directory.lookup(oid)
            if isinstance(loc, (InlineLocation, ShmLocation, ArenaLocation)):
                locations[oid] = loc
        return ready, locations, parked

    async def _seal_when_published(self, oid: ObjectID):
        """``wait_objects``' arm for an object another node will seal:
        park on the GCS object directory's long-poll and seal the object
        here, as a remote location, the moment its node publishes it. The
        long-poll is re-armed when it runs out; nothing waits for that."""
        while oid not in self._sealed:
            t0 = time.monotonic()
            try:
                nid = await self._gcs.locate_object(
                    oid, timeout=self.config.object_locate_timeout_s
                )
            # An unreachable GCS reads as "not published yet".
            except Exception:  # rtlint: disable=swallowed-failure
                nid = None
            if nid is not None and nid != self.node_id:
                self._adopt_remote_object(oid, nid)
                # Not awaited: the waiter this seal wakes cancels us.
                self._spawn_bg(self._register_borrow(oid, owner_hex=nid.hex()))
                return
            if time.monotonic() - t0 < 1.0:
                # Came back empty at once (GCS down, a stale claim of
                # this very node): do not spin on it.
                await asyncio.sleep(1.0)

    def _adopt_remote_object(self, oid: ObjectID, nid: NodeID):
        """Seal ``oid`` here as living on node ``nid``. Any entry for a
        remotely-owned object is a borrow this node must register with
        the owner (the caller does, by its own discipline)."""
        self._seal_object(oid, RemoteLocation(nid.hex(), 0))
        self._borrow_stubs.add(oid)

    def _remove_ref(self, object_id: ObjectID, count: int = 1):
        self.directory.remove_ref(object_id, count)

    # ------------------------------------------------------ borrower protocol

    def _pin_ref(self, oid: ObjectID, count: int = 1) -> bool:
        """Stub-aware increment (NM loop only). When this node has no
        entry for ``oid`` — a ref to an object owned elsewhere — create a
        count-only borrow stub and register this node as a borrower with
        the owner (async). Returns True when a NEW stub was created, so
        completion paths can await the registration explicitly."""
        created = self.directory.add_ref_or_create(
            oid, count, _RETURN_PLACEHOLDER
        )
        if created:
            self._borrow_stubs.add(oid)
        return created

    def _pin_ref_bg(self, oid: ObjectID, count: int = 1):
        """_pin_ref + fire-and-forget borrow registration (callers that
        have no async context)."""
        if self._pin_ref(oid, count):
            self._spawn_bg(self._register_borrow(oid))

    async def _register_borrow(self, oid: ObjectID,
                               owner_hex: Optional[str] = None):
        """Resolve the owner of a borrow stub through the GCS object
        directory (unless the caller already knows it) and register this
        node in its borrower set. Idempotent; a failure leaves the stub
        unregistered (reads fail loudly if the owner frees it — same
        contract as an unregistered smuggled ref in the reference before
        the borrow lands)."""
        if self._gcs is None or not self._multi_node:
            return
        if oid in self._borrowed_from or oid in self._borrow_registering:
            return
        if oid not in self._borrow_stubs:
            return
        self._borrow_registering.add(oid)
        try:
            if owner_hex is None:
                try:
                    nid = await self._gcs.locate_object(
                        oid, timeout=self.config.object_locate_timeout_s
                    )
                except Exception:
                    return
                if nid is None or nid == self.node_id:
                    return
                owner_hex = nid.hex()
            try:
                peer = await self._get_peer(owner_hex)
                reply = await peer.request(
                    {"type": "register_borrow", "object_id": oid,
                     "borrower": self.node_id.hex()}
                )
            except Exception:
                return
            if reply.get("ok"):
                if oid in self._borrow_stubs:
                    self._borrowed_from[oid] = owner_hex
                else:
                    # The local entry was collected while the
                    # registration was in flight: undo it at the owner
                    # now, or the borrow pins the object forever.
                    self._spawn_bg(self._release_borrow(owner_hex, oid))
        finally:
            self._borrow_registering.discard(oid)

    async def _release_borrow(self, owner_hex: str, oid: ObjectID):
        try:
            peer = await self._get_peer(owner_hex)
            await peer.notify(
                {"type": "release_borrow", "object_id": oid,
                 "borrower": self.node_id.hex()}
            )
        except Exception:
            pass  # owner gone: nothing to release

    async def _apply_ref_deltas(self, deltas: Dict[ObjectID, int]):
        """Apply a worker's ref deltas shipped inside its task-completion
        frame — BEFORE the task's pins are dropped, so a ref the worker
        still holds (stored in actor state, returned inside a container)
        is counted, and any new cross-node borrow is REGISTERED with the
        owner, while the submission-time pin still protects the object."""
        new_stubs = []
        for oid, d in deltas.items():
            if d > 0:
                if self._pin_ref(oid, d):
                    new_stubs.append(oid)
            elif d < 0:
                self._remove_ref(oid, -d)
        for oid in new_stubs:
            await self._register_borrow(oid)

    def _register_nested(self, container: ObjectID,
                         nested: List[ObjectID]):
        """Pin refs serialized inside ``container`` until its directory
        entry is collected (ref analogue: AddNestedObjectIds)."""
        if not nested:
            return
        prior = self._nested_pins.setdefault(container, [])
        for oid in nested:
            prior.append(oid)
            self._pin_ref_bg(oid)

    async def _gc_loop(self):
        grace = self.config.gc_grace_period_s
        while not self._shutdown:
            await asyncio.sleep(min(1.0, grace / 2))
            for oid, loc in self.directory.collect_garbage(grace):
                self._sealed.discard(oid)
                self._seal_events.pop(oid, None)
                self._lineage.pop(oid, None)
                self._reconstructions.pop(oid, None)
                # This node's borrow of the object ends with its entry.
                self._borrow_stubs.discard(oid)
                owner_hex = self._borrowed_from.pop(oid, None)
                if owner_hex is not None:
                    # _spawn_bg: strong ref + drained at shutdown, so the
                    # release cannot be dropped mid-flight.
                    self._spawn_bg(self._release_borrow(owner_hex, oid))
                # Refs contained in this object lose their containment pin.
                for nested_oid in self._nested_pins.pop(oid, ()):
                    self._remove_ref(nested_oid)
                if isinstance(loc, RemoteLocation):
                    if loc.held:
                        # Release the hold the remote node keeps for us.
                        asyncio.ensure_future(self._free_remote(loc.node_id, oid))
                else:
                    _free_location(loc)
                    if self._gcs is not None and (
                        self._multi_node or not self.is_head
                    ):
                        asyncio.ensure_future(self._unpublish(oid))
            # Reclaim arena blocks stuck in pending-delete because a pinning
            # reader died without unpinning (ref analogue: plasma client
            # disconnect releasing its objects).
            arena = current_arena()
            if arena is not None:
                try:
                    arena.purge_dead_pins()
                except Exception:
                    pass

    async def _free_remote(self, node_hex: str, oid: ObjectID):
        try:
            peer = await self._get_peer(node_hex)
            await peer.notify({"type": "free_object", "object_id": oid})
        except Exception:
            pass

    async def _unpublish(self, oid: ObjectID):
        try:
            await self._gcs.unpublish_object(oid, self.node_id)
        except Exception:
            pass

    async def _reply_locations(self, w: WorkerHandle, msg):
        try:
            locs = await self.get_locations(msg["object_ids"], msg.get("timeout"))
            await w.writer.send(
                {"type": "reply", "msg_id": msg["msg_id"], "locations": locs}
            )
        except asyncio.TimeoutError:
            await w.writer.send(
                {"type": "reply", "msg_id": msg["msg_id"], "timeout": True}
            )
        # Reply-carried; the nested send races the worker's death —
        # a dead requester needs no reply.
        except Exception as e:  # rtlint: disable=swallowed-failure
            try:
                await w.writer.send(
                    {"type": "reply", "msg_id": msg["msg_id"], "error": str(e)}
                )
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    async def _reply_wait(self, w: WorkerHandle, msg, parks: bool):
        reply = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            if msg.get("carry"):
                reply["ready"], reply["locations"], reply["parked"] = (
                    await self.wait_carrying(
                        msg["object_ids"], msg.get("timeout")))
            else:
                reply["ready"] = await self.wait_objects(
                    msg["object_ids"], msg["num_returns"],
                    msg.get("timeout"))
        finally:
            if parks:
                self._on_worker_unblocked(w)
        await w.writer.send(reply)

    # --------------------------------------------------------------------- kv

    async def _handle_kv(self, w: WorkerHandle, msg):
        """Cluster KV (ref analogue: GCS InternalKV, gcs_kv_manager.h) —
        authoritative store lives at the GCS; the per-node dict is only a
        fallback for GCS-less unit setups."""
        op = msg["op"]
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        if self._gcs is not None:
            try:
                if op == "put":
                    out["added"] = await self._gcs.kv_put(
                        msg["key"], msg["value"], msg.get("overwrite", True)
                    )
                elif op == "get":
                    out["value"] = await self._gcs.kv_get(
                        msg["key"], msg.get("wait_timeout") or 0
                    )
                elif op == "del":
                    out["deleted"] = await self._gcs.kv_del(msg["key"])
                elif op == "keys":
                    out["keys"] = await self._gcs.kv_keys(msg.get("prefix", ""))
            # Reply-carried: the worker's kv call raises it.
            except Exception as e:  # rtlint: disable=swallowed-failure
                out["error"] = str(e)
            await w.writer.send(out)
            return
        if op == "put":
            overwrite = msg.get("overwrite", True)
            if not overwrite and msg["key"] in self._kv:
                out["added"] = False
            else:
                self._kv[msg["key"]] = msg["value"]
                out["added"] = True
        elif op == "get":
            out["value"] = self._kv.get(msg["key"])
        elif op == "del":
            out["deleted"] = self._kv.pop(msg["key"], None) is not None
        elif op == "keys":
            prefix = msg.get("prefix", "")
            out["keys"] = [k for k in self._kv if k.startswith(prefix)]
        await w.writer.send(out)

    # -------------------------------------------------------- pubsub proxy

    async def _handle_pubsub(self, w: WorkerHandle, msg):
        """Driver/worker access to the GCS pubsub (ref analogue: workers
        reach GCS pubsub through their raylet-side gcs client;
        gcs_service.proto:595 InternalPubSub). The proxy keeps pubsub on
        the same authenticated node↔GCS channel everything else uses."""
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            out.update(await self._pubsub_op(msg))
        # Reply-carried: pubsub_op raises it caller-side.
        except Exception as e:  # rtlint: disable=swallowed-failure
            out["error"] = str(e)
        try:
            await w.writer.send(out)
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # dead requester needs no reply

    async def _pubsub_op(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        if self._gcs is None:
            raise RuntimeError("pubsub requires the cluster GCS")
        op = msg["op"]
        if op == "subscribe":
            await self._gcs.psub_subscribe(
                msg["subscriber_id"], msg["channels"]
            )
            return {"ok": True}
        if op == "poll":
            return await self._gcs.psub_poll(
                msg["subscriber_id"], msg.get("timeout", 30.0),
                msg.get("max_events", 1000),
            )
        if op == "publish":
            return {"seq": await self._gcs.psub_publish(
                msg["channel"], msg["data"], key=msg.get("key")
            )}
        if op == "unsubscribe":
            await self._gcs.psub_unsubscribe(
                msg["subscriber_id"], msg.get("channels")
            )
            return {"ok": True}
        if op == "describe":
            return {"services": await self._gcs.rpc_describe()}
        raise RuntimeError(f"unknown pubsub op {op}")

    def pubsub_op(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """Sync entry for the in-process driver runtime."""
        return self.call_sync(self._pubsub_op(msg))

    # ------------------------------------------------- cluster-event query

    async def _handle_events_query(self, w: WorkerHandle, msg):
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            out.update(await self._events_list(
                severity=msg.get("severity"), source=msg.get("source"),
                limit=msg.get("limit", 1000),
            ))
        # Reply-carried: list_cluster_events raises it caller-side.
        except Exception as e:  # rtlint: disable=swallowed-failure
            out["error"] = str(e)
        try:
            await w.writer.send(out)
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # dead requester needs no reply

    async def _events_list(self, severity=None, source=None,
                           limit: int = 1000) -> Dict[str, Any]:
        """Fetch the head aggregator's event store (ref analogue:
        `ray list cluster-events` hitting the GCS)."""
        if self._gcs is None:
            raise RuntimeError("cluster events require the cluster GCS")
        return await self._gcs.events_list(
            severity=severity, source=source, limit=limit
        )

    async def _handle_timeseries_query(self, w: WorkerHandle, msg):
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            out.update(await self._timeseries_query(
                name=msg.get("name", ""), tags=msg.get("tags"),
                since=msg.get("since", 0.0), limit=msg.get("limit", 0),
                quantile=msg.get("quantile", 0.0),
                window=msg.get("window", 60.0),
            ))
        # Reply-carried: timeseries_query raises it caller-side.
        except Exception as e:  # rtlint: disable=swallowed-failure
            out["error"] = str(e)
        try:
            await w.writer.send(out)
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # dead requester needs no reply

    async def _handle_slo_query(self, w: WorkerHandle, msg):
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            out.update(await self._slo_status())
        # Reply-carried: slo_status raises it caller-side.
        except Exception as e:  # rtlint: disable=swallowed-failure
            out["error"] = str(e)
        try:
            await w.writer.send(out)
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # dead requester needs no reply

    async def _timeseries_query(self, name="", tags=None, since=0.0,
                                limit: int = 0, quantile: float = 0.0,
                                window: float = 60.0) -> Dict[str, Any]:
        """Query the head TSDB (ref analogue: the dashboard hitting the
        metrics head). ``quantile`` > 0 adds a head-derived histogram
        quantile over the trailing ``window`` seconds."""
        if self._gcs is None:
            raise RuntimeError("timeseries require the cluster GCS")
        return await self._gcs.timeseries_query(
            name=name, tags=tags, since=since, limit=limit,
            quantile=quantile, window=window
        )

    async def _slo_status(self) -> Dict[str, Any]:
        if self._gcs is None:
            raise RuntimeError("SLO status requires the cluster GCS")
        return await self._gcs.slo_status()

    # ------------------------------------------------- profiling plane

    def _worker_frame_future(self, w: WorkerHandle,
                             frame: Dict[str, Any]):
        """Send one stack_dump/profile frame to a worker and return
        (req_id, future) for its reply — the single place that owns the
        pending-table bookkeeping. (None, None) if the send failed.
        Loop-thread only."""
        self._profile_req_seq += 1
        req_id = self._profile_req_seq
        fut: asyncio.Future = self._loop.create_future()
        self._profile_pending[req_id] = fut
        try:
            w.writer.send_nowait({**frame, "req_id": req_id})
        except Exception:
            self._profile_pending.pop(req_id, None)
            return None, None
        return req_id, fut

    def _profile_fanout_workers(self, frame: Dict[str, Any]):
        """Send a stack_dump/profile frame to every live worker; returns
        [(handle, req_id, future), ...] for the replies. Loop-thread
        only."""
        waits = []
        for w in list(self._workers.values()):
            if w.state in ("dead", "client") or w.worker_type == "client":
                continue
            req_id, fut = self._worker_frame_future(w, frame)
            if fut is not None:
                waits.append((w, req_id, fut))
        return waits

    async def _gather_profile_replies(self, waits, timeout: float):
        """Await the fan-out replies; a worker that never answers (dead,
        wedged reader) is dropped from the result instead of hanging the
        whole dump. Returns (replies, missing_worker_hexes)."""
        if waits:
            await asyncio.wait([f for _, _, f in waits], timeout=timeout)
        replies, missing = [], []
        for w, req_id, fut in waits:
            if fut.done():
                # done() checked: result() returns immediately.
                replies.append(fut.result())  # rtlint: disable=loop-blocking
            else:
                self._profile_pending.pop(req_id, None)
                missing.append(w.worker_id.hex())
        return replies, missing

    async def stacks_dump(self, timeout: float = 5.0) -> Dict[str, Any]:
        """One-shot stack dump of this node: the node-manager process
        plus every live worker (ref analogue: `ray stack` against one
        node). Workers that do not answer within ``timeout`` degrade to
        a partial result listed under ``missing_workers``."""
        from ..util import profiler

        procs = [{
            "pid": os.getpid(),
            "kind": "node_manager",
            "worker_id": None,
            "threads": profiler.dump_stacks(),
        }]
        waits = self._profile_fanout_workers({"type": "stack_dump"})
        replies, missing = await self._gather_profile_replies(
            waits, timeout
        )
        for r in replies:
            procs.append({
                "pid": r.get("pid"),
                "kind": "worker",
                "worker_id": r.get("worker_id"),
                "threads": r.get("threads", []),
            })
        return {
            "node_id": self.node_id.hex(),
            "is_head": self.is_head,
            "procs": procs,
            "missing_workers": missing,
        }

    async def profile_run(self, seconds: float = 2.0,
                          hz: int = 100) -> Dict[str, Any]:
        """Timed sampling profile of this node: the node-manager process
        (sampled OFF this event loop, in the default executor) plus
        every live worker, merged to collapsed-stack counts keyed
        ``pid:<pid>(<kind>);<thread>;<frames...>``."""
        from ..util import profiler

        seconds = max(0.0, min(float(seconds),
                               profiler.MAX_SAMPLE_SECONDS))
        hz = max(1, min(int(hz), profiler.MAX_SAMPLE_HZ))
        local_fut = self._loop.run_in_executor(
            None, profiler.sample, seconds, hz
        )
        waits = self._profile_fanout_workers(
            {"type": "profile", "seconds": seconds, "hz": hz}
        )
        # Gather runs CONCURRENTLY with the local sample: its timeout
        # clock starts now, so a wedged worker bounds the whole node
        # reply at ~seconds+5 — within the GCS's per-node timeout —
        # instead of 2*seconds+5, which would drop the node (and every
        # healthy worker's samples) from the cluster reply.
        gather_task = asyncio.ensure_future(
            self._gather_profile_replies(waits, seconds + 5.0)
        )
        local = await local_fut
        replies, missing = await gather_task
        counts: Dict[str, int] = {}
        samples = local.get("samples", 0)

        def fold(pid, kind, src):
            prefix = f"pid:{pid}({kind})"
            for stack, n in (src or {}).items():
                key = f"{prefix};{stack}"
                counts[key] = counts.get(key, 0) + n

        fold(os.getpid(), "node_manager", local.get("counts"))
        for r in replies:
            fold(r.get("pid"), "worker", r.get("counts"))
            samples += r.get("samples", 0)
        return {
            "node_id": self.node_id.hex(),
            "is_head": self.is_head,
            "seconds": seconds,
            "hz": hz,
            "counts": counts,
            "samples": samples,
            "missing_workers": missing,
        }

    async def cluster_stacks(self, timeout: float = 5.0) -> Dict[str, Any]:
        """Cluster-wide stack dump via the GCS ProfileService (falls
        back to this node alone in GCS-less unit setups)."""
        if self._gcs is None:
            return {"nodes": [await self.stacks_dump(timeout)],
                    "errors": {}}
        return await self._gcs.stacks_dump(timeout=timeout)

    async def cluster_profile(self, seconds: float = 2.0,
                              hz: int = 100) -> Dict[str, Any]:
        """Cluster-wide sampling profile via the GCS ProfileService."""
        if self._gcs is None:
            return {"nodes": [await self.profile_run(seconds, hz)],
                    "errors": {}}
        return await self._gcs.profile_run(seconds=seconds, hz=hz)

    def traces_dump(self, reason: Optional[str] = None,
                    limit: int = 200) -> Dict[str, Any]:
        """This node's tail-sampled flight-recorder ring (the node
        manager shares a process with the driver/head ingress, so the
        proxy's retained requests live here; worker rings mirror through
        the cluster KV)."""
        from ..util import flight_recorder

        rec = flight_recorder.get_recorder()
        return {
            "node_id": self.node_id.hex(),
            "is_head": self.is_head,
            "records": rec.list(reason=reason, limit=limit),
            "stats": rec.stats(),
        }

    async def cluster_traces(self, reason: Optional[str] = None,
                             limit: int = 200) -> Dict[str, Any]:
        """Cluster-wide flight-recorder dump via the GCS fan-out."""
        if self._gcs is None:
            return {"nodes": [self.traces_dump(reason, limit)],
                    "errors": {}}
        return await self._gcs.traces_dump(reason=reason or "",
                                           limit=limit)

    def objects_census(self, limit: int = 500) -> Dict[str, Any]:
        """This node's slice of the cluster object census (ref analogue:
        the GCS object table + local_object_manager stats, merged): the
        directory's per-object rows enriched with a coarse lifecycle
        state (in-memory / spilled / inflight / remote), the borrow
        owner's node hex where known, plus store/spill/pull accounting
        so the head can aggregate without a second round trip."""
        rows = self.directory.census_rows(limit=limit)
        transfer = getattr(self, "_transfer", None)
        inflight = (transfer.inflight_pulls()
                    if transfer is not None else [])
        pulling = {p.get("oid") for p in inflight}
        for r in rows:
            where = r["where"]
            if where in ("shm", "inline", "arena"):
                r["state"] = "in-memory"
            elif where == "spilled":
                r["state"] = "spilled"
            elif where == "remote":
                r["state"] = ("inflight" if r["object_id"] in pulling
                              else "remote")
            else:
                r["state"] = where
            owner_hex = self._borrowed_from.get(
                ObjectID.from_hex(r["object_id"]))
            if owner_hex:
                r["owner_node"] = owner_hex
        spill = getattr(self, "spill_manager", None)
        return {
            "node_id": self.node_id.hex(),
            "is_head": self.is_head,
            "objects": rows,
            "used_bytes": self.directory.used_bytes,
            "capacity_bytes": self.directory.capacity_bytes,
            "num_objects": self.directory.num_objects(),
            "spilled_bytes": (spill.used_bytes() if spill is not None
                              else 0),
            "inflight_pulls": inflight,
        }

    async def cluster_objects(self, limit: int = 500) -> Dict[str, Any]:
        """Cluster-wide object census via the GCS fan-out (same
        partial-tolerant shape as cluster_stacks/cluster_traces)."""
        if self._gcs is None:
            return {"nodes": [self.objects_census(limit)], "errors": {}}
        return await self._gcs.objects_census(limit=limit)

    # ---------------------------------------------------- leak detection

    def _maybe_leak_sweep(self) -> None:
        """Kick one background leak sweep when due (head only). Cadence
        scales with the warn threshold so a leak is flagged within
        ``object_leak_warn_s`` of crossing it without hammering the
        census fan-out on the default 5-minute threshold."""
        from ..util import data_obs

        warn_s = getattr(self.config, "object_leak_warn_s", 0.0)
        if warn_s <= 0 or not data_obs.ENABLED:
            return
        if (self._leak_sweep_task is not None
                and not self._leak_sweep_task.done()):
            return
        interval = max(0.5, min(warn_s / 2.0, 30.0))
        now = time.monotonic()
        if now - self._leak_last_sweep < interval:
            return
        self._leak_last_sweep = now
        self._leak_sweep_task = asyncio.ensure_future(self._leak_sweep())

    async def _leak_sweep(self) -> None:
        """One head-side leak pass over the cluster census: a sealed
        object is leaked when it has sat at zero live refs past
        ``object_leak_warn_s``, or when it is a borrow whose owner node
        is dead/fenced. Publishes the leak gauges every pass (so GC
        clears them) and emits ONE deduped WARNING OBJECT_STORE event
        per offender per episode. Never raises."""
        from ..util import data_obs

        try:
            warn_s = float(self.config.object_leak_warn_s)
            census = await self.cluster_objects(limit=2000)
            me = self.node_id.hex()
            leaked = []  # (holder node hex, row, why)
            for node in census.get("nodes", []):
                holder = node.get("node_id", "")
                for r in node.get("objects", []):
                    if r.get("state") == "inflight":
                        continue
                    why = ""
                    zero = r.get("zero_ref_s")
                    if zero is not None and zero > warn_s:
                        why = f"zero refs for {zero:.0f}s"
                    owner_node = r.get("owner_node")
                    if not why and owner_node and owner_node != me:
                        view = self._cluster_view.get(owner_node)
                        state = (view or {}).get("state", "dead")
                        if (owner_node in self._fenced_nodes
                                or state not in ("alive", "draining")):
                            why = (f"owner node {owner_node[:8]} is "
                                   f"{state}")
                    if why:
                        leaked.append((holder, r, why))
            data_obs.set_leaked(
                len(leaked),
                sum(r.get("size_bytes") or 0 for _, r, _ in leaked),
            )
            current = set()
            for holder, r, why in leaked:
                oid = r["object_id"]
                current.add(oid)
                if oid in self._leak_warned:
                    continue
                self._leak_warned.add(oid)
                cluster_events.emit(
                    cluster_events.WARNING, cluster_events.OBJECT_STORE,
                    f"LEAK suspected: object {oid[:8]} "
                    f"({r.get('size_bytes') or 0} bytes, "
                    f"owner {r.get('owner') or '?'}) on node "
                    f"{holder[:8]}: {why}",
                    node_id=holder,
                    custom_fields={
                        "object_id": oid,
                        "size_bytes": r.get("size_bytes") or 0,
                        "owner": r.get("owner") or "",
                        "state": r.get("state") or "",
                        "age_s": r.get("age_s"),
                        "why": why,
                    },
                )
            # Offenders that stopped looking leaked (GC'd, or refs
            # re-appeared) leave the dedup set: a future re-leak of the
            # same oid warns again instead of staying silent forever.
            self._leak_warned &= current
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # telemetry sweep must never take the loop down

    async def _handle_profile_query(self, w: WorkerHandle, msg):
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            if msg.get("op") == "stacks":
                out["result"] = await self.cluster_stacks(
                    timeout=msg.get("timeout", 5.0)
                )
            elif msg.get("op") == "run":
                out["result"] = await self.cluster_profile(
                    seconds=msg.get("seconds", 2.0),
                    hz=msg.get("hz", 100),
                )
            elif msg.get("op") == "traces":
                out["result"] = await self.cluster_traces(
                    reason=msg.get("reason") or None,
                    limit=msg.get("limit", 200),
                )
            elif msg.get("op") == "objects":
                out["result"] = await self.cluster_objects(
                    limit=msg.get("limit", 500)
                )
            else:
                out["error"] = f"unknown profile op {msg.get('op')!r}"
        # Reply-carried: the rtpu profile caller shows it.
        except Exception as e:  # rtlint: disable=swallowed-failure
            out["error"] = str(e)
        try:
            await w.writer.send(out)
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # dead requester needs no reply

    # ---------------------------------------------------- hang detector

    async def _check_hung_tasks(self):
        """Flag tasks running longer than ``hang_task_warn_s``: capture
        the owning worker's stack and emit a WARNING cluster event (ref
        analogue: the reference's "task is hung" debugging loop — `ray
        stack` by hand — folded into the control plane)."""
        thresh = getattr(self.config, "hang_task_warn_s", 0.0)
        if thresh <= 0:
            return
        now = time.monotonic()
        for record in list(self._tasks.values()):
            if (
                record.state != "running"
                or record.hang_warned
                or record.dispatched is None
                or now - record.dispatched < thresh
            ):
                continue
            worker = self._workers.get(record.worker_id)
            if worker is None or worker.current is not record:
                # Pipelined rider still queued on its worker: it is not
                # EXECUTING yet — warning now would blame it for the
                # head task's runtime and capture the wrong stack.
                continue
            record.hang_warned = True
            self._spawn_bg(self._warn_hung_task(
                record, now - record.dispatched, thresh
            ))

    async def _warn_hung_task(self, record: TaskRecord, elapsed: float,
                              thresh: float):
        from ..util import profiler

        worker = self._workers.get(record.worker_id)
        stack_text = ""
        worker_pid = None
        if worker is not None and worker.state != "dead":
            worker_pid = worker.proc.pid if worker.proc else None
            req_id, fut = self._worker_frame_future(
                worker, {"type": "stack_dump"}
            )
            if fut is not None:
                try:
                    reply = await asyncio.wait_for(fut, timeout=2.0)
                    stack_text = profiler.format_stack_text(
                        reply.get("threads", [])
                    )
                except Exception:
                    self._profile_pending.pop(req_id, None)
        name = record.spec.name or record.spec.method_name or "task"
        captured = ("worker stack captured" if stack_text
                    else "worker stack capture failed")
        cluster_events.emit(
            cluster_events.WARNING, cluster_events.TASK,
            f"task '{name}' has been running for {elapsed:.1f}s "
            f"(> hang_task_warn_s={thresh:g}); {captured}",
            node_id=self.node_id.hex(),
            task_id=record.spec.task_id.hex(),
            actor_id=(record.spec.actor_id.hex()
                      if record.spec.actor_id else None),
            custom_fields={
                "elapsed_s": round(elapsed, 3),
                "threshold_s": thresh,
                "worker_pid": worker_pid,
                "stack": stack_text[:8000],
            },
        )

    # ------------------------------------------------- placement-group proxy

    async def _handle_pg(self, w: WorkerHandle, msg):
        out: Dict[str, Any] = {"type": "reply", "msg_id": msg["msg_id"]}
        try:
            out.update(await self.pg_op(msg))
        # Reply-carried: the placement-group API raises it caller-side.
        except Exception as e:  # rtlint: disable=swallowed-failure
            out["error"] = str(e)
        try:
            await w.writer.send(out)
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # dead requester needs no reply

    async def pg_op(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        if self._gcs is None:
            raise RuntimeError("placement groups require the cluster GCS")
        op = msg["op"]
        if op == "create":
            await self._gcs.pg_create(
                msg["pg_id"], msg["bundles"], msg["strategy"],
                msg.get("name", ""),
                label_selectors=msg.get("label_selectors"),
            )
            return {"ok": True}
        if op == "wait":
            return {"ready": await self._gcs.pg_wait(msg["pg_id"], msg["timeout"])}
        if op == "remove":
            await self._gcs.pg_remove(msg["pg_id"])
            self._pg_nodes.pop(msg["pg_id"], None)
            return {"ok": True}
        if op == "table":
            return {"table": await self._gcs.pg_table()}
        raise RuntimeError(f"unknown pg op {op}")

    def kv_put(self, key: str, value: bytes, overwrite: bool = True) -> bool:
        async def _put():
            if self._gcs is not None:
                return await self._gcs.kv_put(key, value, overwrite)
            if not overwrite and key in self._kv:
                return False
            self._kv[key] = value
            return True

        return self.call_sync(_put())

    def kv_get(self, key: str) -> Optional[bytes]:
        async def _get():
            if self._gcs is not None:
                return await self._gcs.kv_get(key)
            return self._kv.get(key)

        return self.call_sync(_get())

    def kv_keys(self, prefix: str = "") -> List[str]:
        async def _keys():
            if self._gcs is not None:
                return await self._gcs.kv_keys(prefix)
            return [k for k in self._kv if k.startswith(prefix)]

        return self.call_sync(_keys())

    def kv_del(self, key: str) -> bool:
        async def _del():
            if self._gcs is not None:
                return await self._gcs.kv_del(key)
            return self._kv.pop(key, None) is not None

        return self.call_sync(_del())

    # ----------------------------------------------------------- cancellation

    async def get_actor_direct(
        self, actor_id: ActorID, timeout: float = 30.0
    ) -> Optional[Dict[str, Any]]:
        """Resolve an actor's direct-call endpoint descriptor
        ({"path": uds, "addr": (host, port), "ver", "node"}). A local
        actor answers only once it is alive, has advertised endpoints,
        AND has no node-manager-routed calls queued or in flight — the
        caller's switch to the direct channel therefore cannot overtake
        any call routed through here (per-caller actor ordering). An
        actor homed on a peer node resolves through that node's NM,
        which applies the same drain gate."""
        if actor_id not in self._actors:
            home = self._actor_homes.get(actor_id)
            if home and home != "dead":
                try:
                    peer = await self._get_peer(home)
                    reply = await peer.request(
                        {"type": "get_actor_direct_peer",
                         "actor_id": actor_id, "timeout": timeout},
                        timeout=timeout + 10.0,
                    )
                    return reply.get("direct")
                except Exception:
                    return None
            return None
        start = self._loop.time()
        deadline = start + timeout
        alive_no_path_since = None
        while True:
            if self._shutdown:
                return None  # don't outlive the loop (pending-task warning)
            info = self._actors.get(actor_id)
            if info is None or info.state == "dead":
                return None
            if info.state == "alive":
                if info.direct_path is None and info.direct_addr is None:
                    # Worker predates direct support or the advert is in
                    # flight; give it a moment then report unsupported.
                    now = self._loop.time()
                    if alive_no_path_since is None:
                        alive_no_path_since = now
                    elif now - alive_no_path_since > 1.0:
                        return None
                elif not info.queued and not info.inflight:
                    return {
                        "path": info.direct_path,
                        "addr": info.direct_addr,
                        "ver": info.direct_ver,
                        "node": self.node_id.hex(),
                        # Incarnation rides the descriptor into the
                        # direct hello; the worker refuses a mismatch
                        # (fencing: a recycled endpoint or restarted
                        # actor can never serve a stale resolution).
                        "inc": info.incarnation,
                    }
            now = self._loop.time()
            if now > deadline:
                return None
            # Adaptive poll: fine-grained while the drain window is hot
            # (the common sync case resolves in ms), coarse afterwards so
            # a long-busy actor does not ride the control loop at 200 Hz.
            await asyncio.sleep(0.005 if now - start < 0.25 else 0.05)

    async def _reply_actor_direct(self, w: WorkerHandle, msg):
        """Worker/client-side get_actor_direct request: long-polls the
        drain window off the message loop and replies when resolved."""
        try:
            timeout = msg.get("timeout")
            desc = await self.get_actor_direct(
                msg["actor_id"],
                timeout=30.0 if timeout is None else float(timeout),
            )
        except Exception:
            desc = None
        try:
            await w.writer.send({"type": "reply", "msg_id": msg["msg_id"],
                                 "direct": desc})
        except Exception:
            pass

    async def cancel_task(self, task_id: TaskID, force: bool = False):
        record = self._tasks.get(task_id)
        if record is None or record.state in ("finished", "failed", "cancelled"):
            return
        if record.state == "forwarded" and record.target is not None:
            try:
                peer = await self._get_peer(record.target)
                await peer.notify(
                    {"type": "cancel_task_peer", "task_id": task_id,
                     "force": force}
                )
            except Exception:
                pass
            return
        if record.state in ("waiting", "ready", "queued"):
            prev = record.state
            record.state = "cancelled"
            if prev == "waiting":
                self._waiting.pop(task_id, None)
            self._fail_task(record, TaskCancelledError(record.spec.name))
            record.state = "cancelled"
        elif record.state == "running" and force:
            worker = self._workers.get(record.worker_id)
            record.state = "cancelled"
            self._fail_task(record, TaskCancelledError(record.spec.name))
            record.state = "cancelled"
            if worker is not None and record in worker.pending:
                # Only QUEUED on the worker (pipelined frame, not yet
                # executing): reclaim the frame instead of killing the
                # process — the kill would take down the unrelated task
                # actually running there. Flush buffered execute frames
                # first so the reclaim cannot overtake this record's own
                # frame on the socket.
                self._flush_worker_exec_buf(worker)
                try:
                    worker.writer.send_nowait(
                        {"type": "reclaim",
                         "task_ids": [record.spec.task_id]}
                    )
                except Exception:
                    pass
            elif worker is not None and worker.proc is not None:
                worker._intentional_kill = True
                try:
                    worker.proc.kill()
                except Exception:
                    pass

    # ------------------------------------------------------ functions / stats

    async def register_function(self, function_id: str, blob: bytes):
        self._functions[function_id] = blob
        # Export to the cluster function table so every node can lazy-import
        # (ref analogue: function_manager.py export to GCS KV).
        if self._gcs is not None:
            asyncio.ensure_future(self._export_function(function_id, blob))

    async def _export_function(self, function_id: str, blob: bytes):
        try:
            await self._gcs.register_function(function_id, blob)
        except Exception:
            pass

    async def _function_blob(self, function_id: str) -> Optional[bytes]:
        blob = self._functions.get(function_id)
        if blob is None and self._gcs is not None:
            try:
                blob = await self._gcs.fetch_function(function_id)
            except Exception:
                blob = None
            if blob is not None:
                self._functions[function_id] = blob
        return blob

    def _observe_task_duration(self, seconds: float) -> None:
        h = self._task_duration
        h["count"] += 1
        h["sum"] += seconds
        for i, b in enumerate(h["bounds"]):
            if seconds <= b:
                h["buckets"][i] += 1
                return
        h["buckets"][-1] += 1

    async def stats(self) -> Dict[str, Any]:
        return {
            **self._stats,
            "num_workers": len(self._workers),
            "num_actors_alive": sum(
                1 for a in self._actors.values() if a.state == "alive"
            ),
            "object_store_used_bytes": self.directory.used_bytes,
            "num_objects": self.directory.num_objects(),
            "available_resources": self.node_resources.available.to_dict(),
            "total_resources": self.node_resources.total.to_dict(),
            "pending_tasks": len(self._ready) + len(self._waiting),
            "num_nodes": max(1, len(self._cluster_view)),
            "tasks_forwarded": len(self._forwarded),
        }

    async def cluster_nodes(self) -> List[Dict[str, Any]]:
        """Alive-node views (ref analogue: ray.nodes() via
        GlobalStateAccessor)."""
        if self.is_head and self.gcs_service is not None:
            return self.gcs_service.nodes_view()
        self._cluster_view[self.node_id.hex()] = self._local_view()
        return list(self._cluster_view.values())

    # ------------------------------------------------------------- state API

    def _local_state_snapshot(self) -> Dict[str, List[Dict[str, Any]]]:
        """This node's live-state tables in wire form (ref analogue: the
        raylet's contribution to ray.util.state — NodeManagerService
        GetTasksInfo / GetObjectsInfo handlers)."""
        node = self.node_id.hex()
        tasks = []
        for tid, rec in self._tasks.items():
            tasks.append({
                "task_id": tid.hex(),
                "name": rec.spec.name,
                "state": rec.state,
                "node_id": node,
                "type": rec.spec.task_type.name,
                "actor_id": (rec.spec.actor_id.hex()
                             if rec.spec.actor_id else None),
                "age_s": round(time.monotonic() - rec.created, 3),
            })
        # Terminal records retained after leaving the live table: the
        # failure history list_tasks needs to answer "what failed".
        tasks.extend(dict(row) for row in self._task_history)
        actors = []
        for aid, info in self._actors.items():
            w = self._workers.get(info.worker_id)
            actors.append({
                "actor_id": aid.hex(),
                "class_name": info.creation_spec.class_name,
                "state": info.state,
                "name": info.name,
                "node_id": node,
                "pid": (w.proc.pid if w is not None and w.proc else None),
                "restart_count": info.restart_count,
                "pending_calls": len(info.queued) + len(info.inflight),
            })
        from ..util.profiler import process_stats

        workers = []
        now = time.monotonic()
        for wid, w in self._workers.items():
            pid = w.proc.pid if w.proc else None
            row = {
                "worker_id": wid.hex(),
                "pid": pid,
                "state": w.state,
                "worker_type": w.worker_type,
                "node_id": node,
                "actor_id": w.actor_id.hex() if w.actor_id else None,
                # Current activity ("what is it doing right now"):
                # running task + live cpu/rss from /proc.
                "current_task": (w.current.spec.name
                                 or w.current.spec.method_name
                                 if w.current is not None else None),
                "current_task_id": (w.current.spec.task_id.hex()
                                    if w.current is not None else None),
                "running_for_s": (
                    round(now - w.current.dispatched, 3)
                    if w.current is not None
                    and w.current.dispatched is not None else None
                ),
            }
            if pid is not None:
                row.update(process_stats(pid))
            workers.append(row)
        objects = []
        for oid, size, where, refs in self.directory.entries_view():
            objects.append({
                "object_id": oid.hex(),
                "size_bytes": size,
                "where": where,
                "state": ("in-memory"
                          if where in ("shm", "inline", "arena")
                          else where),
                "owner": self.directory.owner_of(oid),
                "refcount": refs,
                "node_id": node,
            })
        return {
            "tasks": tasks,
            "actors": actors,
            "workers": workers,
            "objects": objects,
        }

    async def cluster_state(self) -> Dict[str, List[Dict[str, Any]]]:
        """Aggregate state across every alive node: own snapshot plus a
        fan-out ``state_snapshot`` peer query (ref analogue:
        util/state/api.py querying the GCS + each raylet)."""
        merged = self._local_state_snapshot()
        me = self.node_id.hex()
        peer_ids = [
            hex_id for hex_id, view in self._cluster_view.items()
            if hex_id != me and view.get("state", "alive") == "alive"
        ]

        async def query(hex_id: str):
            try:
                peer = await self._get_peer(hex_id)
                reply = await peer.request(
                    {"type": "state_snapshot"}, timeout=5.0
                )
                return reply.get("state")
            except Exception:
                return None

        for snap in await asyncio.gather(*(query(h) for h in peer_ids)):
            if snap:
                for kind in merged:
                    merged[kind].extend(snap.get(kind, []))
        return merged

    # ---------------------------------------------------------------- blocked

    def _on_worker_blocked(self, w: WorkerHandle):
        """Worker blocked in get(): release its task's resources so other
        tasks can run (ref analogue: NodeManager::HandleNotifyWorkerBlocked +
        the CPU release in local_task_manager)."""
        if w.state == "busy" and w.current is not None and w.current.resources_held:
            bundle_key = w.current.bundle_key  # keep for re-acquire
            self._release_task_resources(w.current)
            w.current.bundle_key = bundle_key
            w.state = "blocked"
            if w.pending:
                # Pipelined tasks behind a blocked task could DEADLOCK (the
                # blocked task may be waiting on one of them). Reclaim every
                # not-yet-started frame; the worker replies with what it
                # actually pulled back and those requeue elsewhere. Flush
                # buffered execute frames FIRST: the reclaim must arrive
                # after them on the socket or it misses frames still in
                # our buffer (the worker only scans its own queue).
                self._flush_worker_exec_buf(w)
                asyncio.ensure_future(self._send_reclaim(
                    w, [r.spec.task_id for r in w.pending]))
            self._schedule()

    async def _send_reclaim(self, w: WorkerHandle, task_ids: List[TaskID]):
        # Behind every execute frame still being written (a blob fetch
        # in flight holds ``send_lock``): one that reached the worker
        # after the reclaim would stay queued behind the blocked task.
        async with w.send_lock:
            try:
                w.writer.send_nowait({"type": "reclaim", "task_ids": task_ids})
            except Exception:
                await self._on_worker_death(w)

    def _on_tasks_reclaimed(self, w: WorkerHandle, msg: Dict[str, Any]):
        """Worker returned pipelined frames it had not started: requeue
        them for dispatch elsewhere."""
        reclaimed = set(msg["task_ids"])

        def _requeue(record: TaskRecord):
            self._release_task_resources(record)
            record.worker_id = None
            if record.state != "cancelled":
                record.state = "ready"
                self._ready.append(record)

        kept: Deque[TaskRecord] = deque()
        for record in w.pending:
            if record.spec.task_id in reclaimed:
                _requeue(record)
            else:
                kept.append(record)
        w.pending = kept
        # Race: a completion that beat this reply may have PROMOTED a
        # reclaimed task to w.current — the worker will never run it (its
        # frame left the queue), so it must requeue too or it hangs.
        while w.current is not None and w.current.spec.task_id in reclaimed:
            _requeue(w.current)
            w.current = w.pending.popleft() if w.pending else None
        if w.current is None and w.state == "busy":
            w.state = "idle"
            self._idle[w.worker_type].append(w.worker_id)
        self._schedule()

    def _on_worker_unblocked(self, w: WorkerHandle):
        if w.state == "blocked" and w.current is not None:
            # Oversubscribe if necessary: clamp availability at zero rather
            # than deadlocking (the reference behaves the same way when a
            # blocked worker resumes).
            record = w.current
            res = record.spec.resources

            def _force_take(avail: ResourceSet) -> ResourceSet:
                fixed = dict(avail._amounts)
                for k, v in res._amounts.items():
                    fixed[k] = max(0, fixed.get(k, 0) - v)
                return ResourceSet(_fixed=fixed)

            if record.bundle_key is not None and (
                bundle := self._bundles.get(record.bundle_key)
            ) is not None:
                if res.is_subset_of(bundle.available):
                    bundle.available = bundle.available - res
                else:
                    bundle.available = _force_take(bundle.available)
            elif not self.node_resources.acquire(res):
                # Includes the bundle-released-while-blocked case: the
                # reservation rejoined the pool, so take from (and later
                # release to) the pool.
                record.bundle_key = None
                self.node_resources.available = _force_take(
                    self.node_resources.available
                )
            record.resources_held = True
            w.state = "busy"

    # --------------------------------------------------------------- shutdown

    def shutdown(self):
        if self._shutdown:
            return
        # Ship the event ring's tail while this process's transport is
        # still installed — after clear_publish_hook the buffered events
        # (crash-adjacent ERROR/CHAOS context included) have no way out.
        try:
            cluster_events.flush()
        except Exception:
            pass
        cluster_events.clear_publish_hook(self._publish_event_batch)
        self._shutdown = True
        if getattr(self, "dashboard_agent", None) is not None:
            self.dashboard_agent.stop()
        if getattr(self, "capi_server", None) is not None:
            self.capi_server.stop()
        # Data plane first: closing the listener + channel sockets makes
        # in-flight stripe workers error out instead of blocking the io
        # pool through the loop teardown below.
        if getattr(self, "_data_server", None) is not None:
            self._data_server.stop()
        self._transfer.close()

        async def _stop():
            if self._bg_tasks:
                try:
                    await asyncio.wait_for(
                        asyncio.gather(*list(self._bg_tasks),
                                       return_exceptions=True),
                        2.0,
                    )
                except Exception:
                    pass
            if getattr(self, "_gc_task", None) is not None:
                self._gc_task.cancel()
            if getattr(self, "_health_task", None) is not None:
                self._health_task.cancel()
            if getattr(self, "_memmon_task", None) is not None:
                self._memmon_task.cancel()
            if self._heartbeat_task is not None:
                self._heartbeat_task.cancel()
            for peer in self._peers.values():
                if isinstance(peer, PeerClient):
                    peer.close()
                else:
                    peer.cancel()
            if self._gcs_client is not None:
                self._gcs_client.close()
            if self.gcs_service is not None:
                self.gcs_service.stop()
            if self._peer_server is not None:
                self._peer_server.close()
            for w in list(self._workers.values()):
                try:
                    await asyncio.wait_for(w.writer.send({"type": "kill"}), 1.0)
                except Exception:
                    pass
            if self._server is not None:
                self._server.close()
            # Cancel stragglers (e.g. a get_actor_direct discovery poll
            # issued via call_sync) so the loop closes without "Task was
            # destroyed but it is pending" noise — and WAIT for the
            # cancellations to retire (a finally needing one more await
            # would otherwise still be pending at loop close).
            me = asyncio.current_task()
            others = [t for t in asyncio.all_tasks() if t is not me]
            for task in others:
                task.cancel()
            if others:
                try:
                    await asyncio.wait_for(
                        asyncio.gather(*others, return_exceptions=True),
                        1.0,
                    )
                except Exception:
                    pass

        # Cancel the watchdog tick while the loop still runs, so a
        # closed loop never holds a stale callback.
        loop_monitor.detach("nm")
        try:
            self._call(_stop()).result(timeout=5)
        except Exception:
            pass
        for w in list(self._workers.values()):
            if w.proc is not None:
                try:
                    w.proc.terminate()
                except Exception:
                    pass
        for w in list(self._workers.values()):
            if w.proc is not None:
                try:
                    w.proc.wait(timeout=2)
                except Exception:
                    try:
                        w.proc.kill()
                    except Exception:
                        pass
        for proc in getattr(self, "_pending_procs", {}).values():
            try:
                proc.terminate()
            except Exception:
                pass
        # Unlink all remaining shm segments we know about, then the arena.
        for oid in list(self.directory._entries):
            _free_location(self.directory._entries.get(oid))
        if self.arena_name:
            shutdown_arena(unlink=True)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
