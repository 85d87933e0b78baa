"""GCS-equivalent cluster control plane.

Plays the role of the reference's GCS server (ref: src/ray/gcs/gcs_server/
gcs_server.h — GcsNodeManager, GcsActorManager's name registry, InternalKV
via gcs_kv_manager.h, GcsHealthCheckManager) plus the resource-usage gossip
of the RaySyncer (ref: src/ray/common/ray_syncer/ray_syncer.h:88). One
instance runs on the head node's event loop; remote node managers connect
over TCP with the same framed-pickle protocol the workers use and exchange:

- node registration / heartbeat load reports (→ broadcast cluster view)
- cluster KV (function table, user KV, rendezvous)
- global named-actor registry and actor→node directory
- object→node location directory for cross-node borrows
- node-death broadcast (connection close or missed heartbeats)

The head node manager talks to the same tables through ``LocalGcsHandle``
(direct coroutine calls, no socket); remote nodes use ``RemoteGcsHandle``.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable, Dict, List, Optional, Tuple

from ..util import dispatch_obs, faults, loop_monitor
from .config import Config, get_config
from .ids import ActorID, NodeID, ObjectID
from .protocol import AioFramedWriter as _FramedWriter
from .protocol import aio_read_frame as _read_frame
from .pubsub import (
    ACTOR_STATE,
    CLUSTER_EVENTS,
    ERROR_INFO,
    NODE_STATE,
    Publisher,
)
from .rpc import Method, RpcError, ServiceRegistry, ServiceSpec

# Typed service surface (ref analogue: the 11 service blocks of
# src/ray/protobuf/gcs_service.proto — NodeInfo:643, InternalKV:522,
# Actor:163, PlacementGroup:400, InternalPubSub:595, ...). The registry
# validates every inbound frame against these schemas before a handler
# runs; `rpc_describe` returns them to clients (the .proto equivalent).
GCS_SERVICES = (
    ServiceSpec("NodeInfoService", (
        Method("register_node",
               request=(("host", "str"), ("peer_port", "int"),
                        ("resources", "dict"),
                        ("labels", "dict", False)),
               # epoch/incarnation/fenced_at: the membership-fence
               # plane (core/fencing.py). fenced_at != 0 tells a
               # re-registering node it was declared dead at that epoch
               # while partitioned — it must self-terminate its old
               # incarnation's workers before resuming.
               reply=(("nodes", "list"), ("chaos", "dict", False),
                      ("epoch", "int", False, 0),
                      ("incarnation", "int", False, 1),
                      ("fenced_at", "int", False, 0))),
        Method("heartbeat",
               request=(("available", "dict"), ("pending", "int"),
                        ("shapes", "list", False)),
               notify=True),
        Method("get_nodes", reply=(("nodes", "list"),)),
        # Drain lifecycle (ref analogue: the DrainNode GCS RPC behind
        # kuberay's drain-before-delete): "begin" marks the node
        # draining (schedulers stop targeting it), "finish" tells the
        # node to run its drain state machine and exit; "full" = both;
        # "abort" rolls a draining node back to alive/schedulable.
        Method("drain_node",
               request=(("node_id", "str"),
                        ("phase", "str", False, "full"),
                        ("timeout", "float", False, 60.0)),
               reply=(("ok", "bool"), ("error", "str"),
                      ("replicated", "int", False, 0),
                      ("leftover_actors", "int", False, 0))),
    )),
    ServiceSpec("ChaosService", (
        # Cluster-wide deterministic fault injection (util/faults.py):
        # arm replaces the whole plan and pushes it to every node
        # manager + worker; disarm arms the empty plan.
        Method("chaos_arm",
               request=(("specs", "list"),),
               reply=(("gen", "int"),)),
        Method("chaos_disarm", reply=(("gen", "int"),)),
        Method("chaos_list",
               reply=(("specs", "list"), ("gen", "int"))),
    )),
    ServiceSpec("InternalKVService", (
        Method("kv_put",
               request=(("key", "str"), ("value", "any"),
                        ("overwrite", "bool", False, True)),
               reply=(("added", "bool"),)),
        Method("kv_get",
               request=(("key", "str"),
                        ("wait_timeout", "float", False, 0)),
               reply=(("value", "any"),)),
        Method("kv_del", request=(("key", "str"),),
               reply=(("deleted", "bool"),)),
        Method("kv_keys", request=(("prefix", "str", False, ""),),
               reply=(("keys", "list"),)),
    )),
    ServiceSpec("FunctionService", (
        Method("register_function",
               request=(("function_id", "str"), ("blob", "bytes")),
               reply=(("ok", "bool"),)),
        Method("fetch_function", request=(("function_id", "str"),),
               reply=(("blob", "any"),)),
    )),
    ServiceSpec("ActorInfoService", (
        Method("register_named_actor",
               request=(("name", "str"), ("actor_id", "str"),
                        ("node_id", "str"), ("spec", "any")),
               reply=(("added", "bool"),)),
        Method("get_named_actor", request=(("name", "str"),),
               reply=(("found", "bool"), ("actor_id", "any"),
                      ("node_id", "any"), ("spec", "any"))),
        Method("drop_named_actor",
               request=(("name", "str"), ("actor_id", "str")),
               notify=True),
        Method("register_actor_node",
               # No longer a notify: the reply carries the GCS-assigned
               # actor incarnation (bumped on every start/restart when
               # the caller passes none; a reconnect re-registration
               # passes its existing incarnation to keep it).
               request=(("actor_id", "str"), ("node_id", "str"),
                        ("incarnation", "int", False, 0)),
               reply=(("incarnation", "int"),)),
        Method("get_actor_node", request=(("actor_id", "str"),),
               reply=(("node_id", "any"),)),
    )),
    ServiceSpec("ObjectDirectoryService", (
        Method("publish_object", request=(("object_id", "any"),),
               notify=True),
        Method("unpublish_object", request=(("object_id", "any"),),
               notify=True),
        Method("locate_object",
               request=(("object_id", "any"),
                        ("timeout", "float", False, 0)),
               reply=(("node_id", "any"),)),
    )),
    ServiceSpec("PlacementGroupService", (
        Method("pg_create",
               request=(("pg_id", "str"), ("bundles", "list"),
                        ("strategy", "str"), ("name", "str", False, ""),
                        ("label_selectors", "list", False)),
               reply=(("ok", "bool"),)),
        Method("pg_wait",
               request=(("pg_id", "str"), ("timeout", "float")),
               reply=(("ready", "bool"),)),
        Method("pg_remove", request=(("pg_id", "str"),),
               reply=(("ok", "bool"),)),
        Method("pg_get", request=(("pg_id", "str"),),
               reply=(("state", "str"), ("bundle_nodes", "any"))),
        Method("pg_table", reply=(("table", "dict"),)),
    )),
    ServiceSpec("InternalPubSubService", (
        Method("psub_subscribe",
               request=(("subscriber_id", "str"), ("channels", "list")),
               reply=(("ok", "bool"),)),
        Method("psub_poll",
               request=(("subscriber_id", "str"),
                        ("timeout", "float", False, 30.0),
                        ("max_events", "int", False, 1000)),
               reply=(("events", "list"), ("dropped", "int"))),
        Method("psub_publish",
               request=(("channel", "str"), ("data", "any"),
                        ("key", "str", False)),
               reply=(("seq", "int"),)),
        Method("psub_unsubscribe",
               request=(("subscriber_id", "str"),
                        ("channels", "list", False)),
               notify=True),
    )),
    ServiceSpec("EventService", (
        Method("events_list",
               request=(("severity", "str", False),
                        ("source", "str", False),
                        ("limit", "int", False, 1000)),
               reply=(("events", "list"), ("total", "int"),
                      ("dropped", "int"))),
    )),
    ServiceSpec("ProfileService", (
        # Cluster-wide introspection (ref analogue: `ray stack` + the
        # dashboard reporter's profile endpoints): both fan out over the
        # node peer channels with a timeout, so a dead node degrades the
        # reply to a partial result (its hex lands in `errors`), never a
        # hang.
        Method("stacks_dump",
               request=(("timeout", "float", False, 5.0),),
               reply=(("nodes", "list"), ("errors", "dict"))),
        Method("profile_run",
               request=(("seconds", "float", False, 2.0),
                        ("hz", "int", False, 100)),
               reply=(("nodes", "list"), ("errors", "dict"))),
        Method("traces_dump",
               # Flight-recorder fan-out (util/flight_recorder.py): each
               # node returns its tail-sampled request-record ring.
               request=(("reason", "str", False, ""),
                        ("limit", "int", False, 200)),
               reply=(("nodes", "list"), ("errors", "dict"))),
    )),
    ServiceSpec("ObjectService", (
        # Data-plane census (ref analogue: `ray memory` over the GCS
        # object-location table): every node returns its bounded object
        # index — (oid, size, state, owner, refcount, age) rows plus
        # store/spill totals and in-flight pull snapshots — over the
        # same partial-tolerant peer fan-out the profile dumps use.
        Method("objects_census",
               request=(("limit", "int", False, 500),),
               reply=(("nodes", "list"), ("errors", "dict"))),
    )),
    ServiceSpec("MetricsService", (
        # SLO plane (util/tsdb.py + util/slo.py): the head GCS samples
        # the `__metrics__` KV pipeline into a bounded in-process TSDB
        # and evaluates declared SLO specs on it; these RPCs expose the
        # history + verdicts to the dashboard/CLI without a collector.
        Method("timeseries_query",
               request=(("name", "str", False, ""),
                        ("tags", "dict", False),
                        ("since", "float", False, 0.0),
                        ("limit", "int", False, 0),
                        # Head-side histogram derivation: quantile > 0
                        # asks for the q-quantile (plus count/sum) of
                        # the merged bucket deltas over the trailing
                        # window — buckets never leave the head.
                        ("quantile", "float", False, 0.0),
                        ("window", "float", False, 60.0)),
               reply=(("series", "list"), ("names", "list"),
                      ("stats", "dict"), ("derived", "dict", False))),
        Method("slo_status",
               reply=(("deployments", "dict"), ("ts", "float"))),
    )),
    ServiceSpec("MetaService", (
        Method("rpc_describe", reply=(("services", "dict"),)),
    )),
)


@dataclass
class NodeEntry:
    """GCS-side record of one node (ref analogue: GcsNodeInfo in
    gcs.proto + the per-node NodeState the syncer versions)."""

    node_id: NodeID
    host: str
    peer_port: int
    resources_total: Dict[str, float]
    resources_available: Dict[str, float] = field(default_factory=dict)
    pending_tasks: int = 0
    # [[shape_dict, count], ...] of queued work (autoscaler demand input;
    # ref analogue: resource_load_by_shape in gcs.proto).
    pending_shapes: List[Any] = field(default_factory=list)
    is_head: bool = False
    state: str = "alive"  # alive | dead
    last_heartbeat: float = field(default_factory=time.monotonic)
    labels: Dict[str, str] = field(default_factory=dict)
    # Membership-fence plane: which registration of this node id this
    # entry is (a zombie rejoin gets a fresh one; stale-incarnation
    # traffic is refused by peers and workers).
    incarnation: int = 1

    def view(self) -> Dict[str, Any]:
        return {
            "node_id": self.node_id.hex(),
            "host": self.host,
            "peer_port": self.peer_port,
            "resources_total": self.resources_total,
            "resources_available": self.resources_available,
            "pending_tasks": self.pending_tasks,
            "pending_shapes": self.pending_shapes,
            "is_head": self.is_head,
            "state": self.state,
            "labels": self.labels,
            "incarnation": self.incarnation,
        }


class _LocateEvent(asyncio.Event):
    """An object's "published" event, with the long-polls parked on it."""

    waiters = 0


class GcsService:
    """The control-plane tables + TCP server. Lives on the head node
    manager's asyncio loop; every public coroutine is loop-thread-only."""

    def __init__(self, config: Config, loop: asyncio.AbstractEventLoop):
        self.config = config
        self._loop = loop
        self._server: Optional[asyncio.AbstractServer] = None
        self.address: Optional[Tuple[str, int]] = None

        self._nodes: Dict[NodeID, NodeEntry] = {}
        self._conns: Dict[NodeID, _FramedWriter] = {}
        self._kv: Dict[str, bytes] = {}
        self._kv_events: Dict[str, asyncio.Event] = {}
        self._functions: Dict[str, bytes] = {}
        # name -> (actor_id, node_id, creation_spec)
        self._named_actors: Dict[str, Tuple[ActorID, NodeID, Any]] = {}
        self._actor_nodes: Dict[ActorID, NodeID] = {}
        # Object location directory: per-node location *sets* so a node
        # GC-ing its pulled replica cannot delete the producer's entry (ref
        # analogue: ObjectDirectory's per-object node sets).
        self._object_nodes: Dict[ObjectID, set] = {}
        self._object_events: Dict[ObjectID, _LocateEvent] = {}
        self._job_counter = 0
        # Placement groups (ref analogue: GcsPlacementGroupManager +
        # GcsPlacementGroupScheduler 2PC across raylets).
        self._pgs: Dict[str, Dict[str, Any]] = {}
        self._pg_peers: Dict[str, Any] = {}  # node hex -> PeerClient

        # Callbacks into the head node manager (same loop, no locking).
        self.on_node_added: Optional[Callable[[NodeEntry], None]] = None
        self.on_node_dead: Optional[Callable[[NodeEntry], None]] = None
        self.on_load_update: Optional[Callable[[Dict[str, Any]], None]] = None
        self.on_pgs_invalidated: Optional[Callable[[List[str]], None]] = None
        self.on_node_draining: Optional[Callable[[NodeEntry], None]] = None
        self.on_node_undrain: Optional[Callable[[NodeEntry], None]] = None
        # Fence decision hook (head NM): tear down local direct
        # channels to the fenced node and forward node_fenced frames to
        # this node's workers (remote NMs learn via the broadcast).
        self.on_node_fenced: Optional[
            Callable[[NodeEntry, int], None]
        ] = None
        self.on_chaos_update: Optional[
            Callable[[List[Dict[str, Any]], int], None]
        ] = None

        # Chaos plane: the armed fault-injection plan, pushed to every
        # node (chaos_update broadcast) and handed to late joiners in
        # their register_node reply.
        self.chaos_specs: List[Dict[str, Any]] = []
        self.chaos_gen = 0
        self._chaos_spec_seq = 0

        # Membership-fence plane (core/fencing.py): the monotonic
        # cluster epoch bumps on EVERY node death and registration and
        # is persisted in the snapshot (monotonic across head
        # restarts). Node/actor incarnation counters make every
        # registration distinguishable from its predecessors;
        # _fenced_nodes remembers "declared dead at epoch E" until the
        # node re-registers, so the rejoin reply can tell a zombie to
        # self-terminate its old incarnation.
        self.cluster_epoch = 0
        self._node_incarnations: Dict[str, int] = {}  # node hex -> last
        self._actor_incarnations: Dict[str, int] = {}  # actor hex -> last
        self._fenced_nodes: Dict[str, int] = {}  # node hex -> epoch

        self._health_task: Optional[asyncio.Task] = None
        # Durable-table persistence (ref analogue: gcs_storage /
        # RedisStoreClient behind GcsTableStorage — gcs_server keeps its
        # tables restorable across head restarts).
        self._storage_path: str = getattr(config, "gcs_storage_path", "")
        self._dirty = False
        # General pubsub (ref: src/ray/pubsub/publisher.h) + the typed
        # service registry all inbound frames dispatch through.
        self.pubsub = Publisher()
        self._rpc = ServiceRegistry()
        for spec in GCS_SERVICES:
            self._rpc.register(spec, self)
        # Cluster event aggregator (ref analogue: the GCS export-event
        # buffer behind `ray list cluster-events`): everything published
        # on the cluster_events channel — by remote nodes, local workers,
        # or this service itself — lands in the bounded store below via
        # the aggregator subscription drained in _event_aggregator_loop.
        from ..util.events import EventStore

        self.events = EventStore(
            maxlen=getattr(config, "event_store_size", 10_000),
            jsonl_path=getattr(config, "event_export_path", ""),
        )
        self._event_sub_id = "__event_aggregator__"
        self.pubsub.subscribe(self._event_sub_id, [CLUSTER_EVENTS])
        self._events_task: Optional[asyncio.Task] = None
        # SLO plane: bounded TSDB fed by the `__metrics__` KV pipeline
        # (no new wire protocol — _metrics_sample_loop aggregates the
        # flushed blobs already in self._kv) + the burn-rate engine
        # evaluating declared specs on it.
        from ..util.slo import SloEngine
        from ..util.tsdb import TSDB

        self.tsdb = TSDB(
            samples_per_series=getattr(
                config, "tsdb_samples_per_series", 4096),
            max_series=getattr(config, "tsdb_max_series", 2000),
        )
        self.slo_engine = SloEngine(emit_event=self._emit_slo_event)
        self._metrics_task: Optional[asyncio.Task] = None
        # `__metrics__` keys first seen orphaned (writer dead/stale) at
        # a monotonic time; reaped after the grace window.
        self._metrics_orphans: Dict[str, float] = {}

    # ------------------------------------------------------------------ boot

    async def start(self, host: str = "127.0.0.1", port: int = 0):
        if self._storage_path:
            self._restore_snapshot()
        from .tls import server_ssl_context

        self._server = await asyncio.start_server(
            self._handle_connection, host=host, port=port,
            ssl=server_ssl_context(),
        )
        sock = self._server.sockets[0]
        self.address = sock.getsockname()[:2]
        self._health_task = asyncio.ensure_future(self._health_loop())
        # One coalesced cluster-view broadcast per interval, not one per
        # received heartbeat (which would be O(n^2) messages per interval).
        self._broadcast_task = asyncio.ensure_future(self._broadcast_loop())
        self._events_task = asyncio.ensure_future(
            self._event_aggregator_loop()
        )
        self._metrics_task = asyncio.ensure_future(
            self._metrics_sample_loop()
        )
        # Second watchdog on the head's shared loop: same thread as the
        # NM's "nm" monitor, but scoped so a head stall is attributable
        # to the GCS plane in `rtpu rpc` output.
        loop_monitor.attach("gcs", asyncio.get_event_loop())

    async def _event_aggregator_loop(self):
        """Drain the cluster_events channel into the head store: events
        keep pubsub ordering (publish seq) regardless of which node or
        worker produced them."""
        while True:
            try:
                reply = await self.pubsub.poll(
                    self._event_sub_id, timeout=30.0, max_events=1000
                )
            except asyncio.CancelledError:
                raise
            except Exception as e:
                sys.stderr.write(
                    f"[gcs] WARNING: event aggregator poll failed "
                    f"({type(e).__name__}: {e}); retrying\n"
                )
                await asyncio.sleep(1.0)
                continue
            if reply.get("unknown"):
                # Subscription reaped (e.g. the loop stalled past the
                # idle timeout): resubscribe instead of busy-spinning on
                # instant empty replies.
                self.pubsub.subscribe(self._event_sub_id, [CLUSTER_EVENTS])
                await asyncio.sleep(0.5)
                continue
            if reply.get("dropped"):
                self.events.note_dropped(reply["dropped"])
            batch = []
            for ev in reply.get("events", ()):
                data = ev.get("data")
                batch.extend(data if isinstance(data, list) else [data])
            if batch:
                self.events.add_batch(batch)

    def _record_event(self, severity: str, source: str, message: str,
                      **fields):
        """GCS-internal emission: publish onto the events channel (the
        aggregator loop stores it; external followers see it too)."""
        from ..util.events import make_event

        try:
            self.pubsub.publish(
                CLUSTER_EVENTS,
                make_event(severity, source, message, **fields),
            )
        except Exception as e:
            # The event plane itself failing must not be invisible.
            sys.stderr.write(
                f"[gcs] WARNING: event publish failed "
                f"({type(e).__name__}: {e}); dropped {source} event\n"
            )

    async def _broadcast_loop(self):
        while True:
            await asyncio.sleep(self.config.heartbeat_interval_s)
            if self._conns or self.on_load_update is not None:
                await self._broadcast_load()
            # Resources freed by finishing tasks must retrigger placement of
            # pending groups, not just node joins (advisor finding r1).
            await self._retry_pending_pgs()
            self._maybe_snapshot()

    # --------------------------------------------------- durable persistence

    SNAPSHOT_MIN_INTERVAL_S = 2.0

    def mark_dirty(self):
        self._dirty = True

    def _maybe_snapshot(self):
        """Rate-limited; the table COPY happens on the loop (consistent
        view) but pickling + file I/O run in the default executor so a
        busy KV channel can't stall the control plane. The shutdown path
        uses :meth:`_snapshot_final` instead — keeping the inline write
        out of this method means the loop-side callers provably never
        touch the filesystem (rtlint loop-blocking)."""
        if not self._storage_path or not self._dirty:
            return
        now = time.monotonic()
        if getattr(self, "_snapshot_inflight", False):
            return
        if now - getattr(self, "_last_snapshot", 0.0) < \
                self.SNAPSHOT_MIN_INTERVAL_S:
            return
        self._dirty = False
        self._last_snapshot = now
        snap = self._build_snapshot()
        self._snapshot_inflight = True

        def write():
            try:
                self._persist_snapshot(snap)
            finally:
                self._snapshot_inflight = False

        try:
            self._loop.run_in_executor(None, write)
        except Exception as e:
            self._snapshot_inflight = False
            sys.stderr.write(
                f"[gcs] WARNING: could not schedule snapshot write "
                f"({type(e).__name__}: {e})\n"
            )

    def _snapshot_final(self):
        """Synchronous last snapshot on shutdown (stop() runs off the
        serving path; durability beats latency here)."""
        if not self._storage_path or not self._dirty:
            return
        self._dirty = False
        self._last_snapshot = time.monotonic()
        self._persist_snapshot(self._build_snapshot())

    def _build_snapshot(self):
        return {
            "kv": dict(self._kv),
            "functions": dict(self._functions),
            "named_actors": {
                name: (aid.hex(), nid.hex(), spec)
                for name, (aid, nid, spec) in self._named_actors.items()
            },
            "job_counter": self._job_counter,
            # Fence plane: the epoch and incarnation counters must stay
            # monotonic across head restarts, or a post-restart
            # registration could reuse an incarnation a stale channel
            # still names (the exact confusion fencing exists to stop).
            "cluster_epoch": self.cluster_epoch,
            "node_incarnations": dict(self._node_incarnations),
            "actor_incarnations": dict(self._actor_incarnations),
        }

    def _persist_snapshot(self, snap):
        import pickle

        try:
            tmp = self._storage_path + ".tmp"
            os.makedirs(os.path.dirname(self._storage_path) or ".",
                        exist_ok=True)
            with open(tmp, "wb") as f:
                pickle.dump(snap, f)
            os.replace(tmp, self._storage_path)
        except Exception as e:
            # A silently failing snapshot means a head restart loses the
            # KV/actor tables with no warning beforehand.
            sys.stderr.write(
                f"[gcs] WARNING: snapshot persist to "
                f"{self._storage_path} failed ({type(e).__name__}: {e})\n"
            )

    def _restore_snapshot(self):
        """Reload durable tables after a head restart (ref:
        gcs_server restart path over persisted table storage). Node /
        object / PG state is runtime state: nodes re-register and
        republish; it is intentionally not restored."""
        import pickle

        try:
            # Boot path: start() restores BEFORE the server accepts its
            # first connection, so there is nothing to stall yet.
            with open(self._storage_path, "rb") as f:  # rtlint: disable=loop-blocking
                snap = pickle.load(f)
        except FileNotFoundError:
            return
        except Exception as e:
            sys.stderr.write(
                f"[gcs] WARNING: snapshot restore from "
                f"{self._storage_path} failed ({type(e).__name__}: {e}); "
                f"starting with empty durable tables\n"
            )
            return
        self._kv.update(snap.get("kv", {}))
        self._functions.update(snap.get("functions", {}))
        for name, (aid_hex, nid_hex, spec) in snap.get(
                "named_actors", {}).items():
            self._named_actors[name] = (
                ActorID.from_hex(aid_hex), NodeID.from_hex(nid_hex), spec
            )
        self._job_counter = max(
            self._job_counter, snap.get("job_counter", 0)
        )
        self.cluster_epoch = max(
            self.cluster_epoch, int(snap.get("cluster_epoch", 0))
        )
        for hex_id, inc in (snap.get("node_incarnations") or {}).items():
            self._node_incarnations[hex_id] = max(
                self._node_incarnations.get(hex_id, 0), int(inc)
            )
        for hex_id, inc in (snap.get("actor_incarnations") or {}).items():
            self._actor_incarnations[hex_id] = max(
                self._actor_incarnations.get(hex_id, 0), int(inc)
            )

    def stop(self):
        self._snapshot_final()
        loop_monitor.detach("gcs")
        if self._metrics_task is not None:
            self._metrics_task.cancel()
        if self._events_task is not None:
            self._events_task.cancel()
        self.events.close()
        if self._health_task is not None:
            self._health_task.cancel()
        if getattr(self, "_broadcast_task", None) is not None:
            self._broadcast_task.cancel()
        if self._server is not None:
            self._server.close()
        for conn in self._conns.values():
            conn.close()
        for peer in self._pg_peers.values():
            if hasattr(peer, "close"):
                peer.close()
            else:
                peer.cancel()

    # --------------------------------------------------------------- serving

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ):
        framed = _FramedWriter(writer)
        node_id: Optional[NodeID] = None
        try:
            hello = await _read_frame(reader)
            if hello.get("type") != "gcs_hello":
                framed.close()
                return
            expected = self.config.session_token
            if expected and hello.get("token") != expected:
                import sys

                print(
                    "ray_tpu gcs: rejected connection with bad session "
                    "token", file=sys.stderr,
                )
                try:
                    await framed.send(
                        {"type": "gcs_error",
                         "error": "bad or missing session token (set "
                                  "RAY_TPU_SESSION_TOKEN on every node)"}
                    )
                # Courtesy reply to a client we are rejecting anyway; it
                # hanging up first changes nothing (the refusal is
                # already printed above).
                except Exception:  # rtlint: disable=swallowed-failure
                    pass
                framed.close()
                return
            node_id = NodeID.from_hex(hello["node_id"])
            self._conns[node_id] = framed
            await framed.send({"type": "gcs_welcome"})
            while True:
                msg = await _read_frame(reader)
                recv_ts = time.monotonic()
                if self._is_blocking_op(msg):
                    # Long-poll ops must not stall this connection's
                    # dispatch loop (heartbeats arrive on the same socket;
                    # stalling them would false-positive the health sweep).
                    asyncio.ensure_future(
                        self._dispatch_and_reply(node_id, msg, framed,
                                                 recv_ts)
                    )
                else:
                    await self._dispatch_and_reply(node_id, msg, framed,
                                                   recv_ts)
        except (asyncio.IncompleteReadError, ConnectionResetError, OSError):
            pass
        finally:
            framed.close()
            if node_id is not None:
                self._conns.pop(node_id, None)
                entry = self._nodes.get(node_id)
                # "alive" OR "draining": a drained node's clean exit
                # still needs the death cleanup (location/actor purge +
                # broadcast) — everything it owned already migrated.
                if entry is not None and entry.state != "dead":
                    await self._mark_node_dead(entry, "connection closed")

    @staticmethod
    def _is_blocking_op(msg: Dict[str, Any]) -> bool:
        op = msg.get("op")
        return (
            op == "pg_wait"
            # drain_node phase=finish awaits the target node's whole
            # drain state machine (up to drain_timeout_s); inline it
            # would stall this connection's heartbeat reads and the
            # health sweep would declare the CALLER dead mid-drain.
            or op == "drain_node"
            or (op == "kv_get" and msg.get("wait_timeout"))
            or (op == "locate_object" and msg.get("timeout"))
        )

    async def _dispatch_and_reply(self, node_id, msg, framed,
                                  recv_ts=None):
        clock = dispatch_obs.op_clock("gcs", msg.get("op"), recv_ts)
        replied = False
        try:
            try:
                reply = await self._dispatch(node_id, msg, clock)
            # Surfaced to the caller: handler exceptions travel back in
            # the reply's error field and raise RuntimeError at the call
            # site.
            except Exception as e:  # rtlint: disable=swallowed-failure
                reply = {"error": str(e)}
            if reply is not None:
                reply["type"] = "reply"
                reply["msg_id"] = msg.get("msg_id")
                replied = True
                try:
                    await framed.send(reply)
                except Exception as e:
                    # Lost reply to a live caller = silent client timeout;
                    # make the drop visible (dead conns are reaped by the
                    # reader loop right after).
                    sys.stderr.write(
                        f"[gcs] WARNING: reply send to node "
                        f"{node_id.hex()[:8]} failed "
                        f"({type(e).__name__}: {e})\n"
                    )
        finally:
            if clock is not None:
                clock.done(replied=replied)

    async def _dispatch(
        self, node_id: NodeID, msg: Dict[str, Any], clock=None
    ) -> Optional[Dict[str, Any]]:
        """Typed dispatch: every inbound frame is validated against the
        GCS_SERVICES schemas (unknown op / missing field / wrong type
        raise RpcError back to the caller) and routed to its `_rpc_*`
        handler by the registry."""
        return await self._rpc.dispatch(node_id, msg["op"], msg,
                                        clock=clock)

    # ------------------------------------------------- typed rpc handlers

    async def _rpc_register_node(self, node_id, host, peer_port,
                                 resources, labels=None):
        return await self.register_node(
            node_id, host, peer_port, resources, labels=labels or {}
        )

    async def _rpc_heartbeat(self, node_id, available, pending,
                             shapes=None):
        self.heartbeat(node_id, available, pending, shapes)

    async def _rpc_get_nodes(self, node_id):
        return {"nodes": [e.view() for e in self._nodes.values()]}

    async def _rpc_drain_node(self, _ctx, node_id, phase="full",
                              timeout=60.0):
        from ..util import events as _events

        try:
            nid = NodeID.from_hex(node_id)
        # Reported, not raised: the refusal travels in the RPC reply.
        except Exception:  # rtlint: disable=swallowed-failure
            return {"ok": False, "error": f"bad node id {node_id!r}"}
        entry = self._nodes.get(nid)
        if entry is None or entry.state == "dead":
            return {"ok": False,
                    "error": f"node {node_id[:8]} unknown or dead"}
        if entry.is_head:
            return {"ok": False, "error": "refusing to drain the head "
                                          "node (it hosts the GCS)"}
        if phase not in ("begin", "finish", "full", "abort"):
            return {"ok": False, "error": f"unknown phase {phase!r}"}
        if phase == "abort":
            await self._drain_rollback(entry, node_id)
            return {"ok": True, "error": ""}
        if phase in ("begin", "full") and entry.state != "draining":
            # Phase 1: the node becomes unschedulable everywhere while
            # staying reachable (peers mark it draining, the pg placer
            # and pick_node skip non-alive views), so replacements land
            # elsewhere while in-flight traffic keeps flowing.
            entry.state = "draining"
            await self._broadcast(
                {"type": "node_draining", "node_id": node_id}
            )
            self.pubsub.publish(
                NODE_STATE,
                {"event": "draining", "node_id": node_id},
                key=node_id,
            )
            self._record_event(
                _events.INFO, _events.GCS,
                f"node {node_id[:8]} draining",
                node_id=node_id,
            )
            if self.on_node_draining is not None:
                self.on_node_draining(entry)
        if phase in ("finish", "full"):
            # Phase 2: the node runs its drain state machine (finish
            # in-flight work, replicate primary object copies off-node)
            # and exits cleanly after acking.
            try:
                peer = await self._pg_peer(node_id)
                reply = await peer.request(
                    {"type": "drain", "timeout": timeout},
                    timeout=timeout + 15.0,
                )
            # rtlint: disable=swallowed-failure — reported in the reply
            except Exception as e:  # noqa: BLE001 — reported, not raised
                if phase == "full":
                    # One-shot callers have no begin/finish/abort
                    # sequence of their own: roll the node back here so
                    # a failed full drain never strands it "draining".
                    await self._drain_rollback(entry, node_id)
                return {"ok": False, "error": str(e) or type(e).__name__}
            if phase == "full" and not reply.get("ok"):
                await self._drain_rollback(entry, node_id)
            self._record_event(
                _events.INFO, _events.GCS,
                f"node {node_id[:8]} drained "
                f"(replicated {reply.get('replicated', 0)} object(s), "
                f"{reply.get('leftover_actors', 0)} actor(s) left)",
                node_id=node_id,
                custom_fields={
                    "replicated": reply.get("replicated", 0),
                    "leftover_actors": reply.get("leftover_actors", 0),
                },
            )
            return {"ok": bool(reply.get("ok")),
                    "error": str(reply.get("error") or ""),
                    "replicated": int(reply.get("replicated") or 0),
                    "leftover_actors":
                        int(reply.get("leftover_actors") or 0)}
        return {"ok": True, "error": ""}

    async def _drain_rollback(self, entry, node_id: str) -> None:
        """Roll a draining node back to alive/schedulable (a failed
        drain must never strand a node "draining" forever — reachable
        but excluded from pick_node/place_bundles, silent capacity
        loss with no operator undo)."""
        from ..util import events as _events

        if entry.state != "draining":
            return
        entry.state = "alive"
        await self._broadcast(
            {"type": "node_undrain", "node_id": node_id}
        )
        self.pubsub.publish(
            NODE_STATE,
            {"event": "undrain", "node_id": node_id},
            key=node_id,
        )
        self._record_event(
            _events.WARNING, _events.GCS,
            f"node {node_id[:8]} drain aborted — back to alive",
            node_id=node_id,
        )
        if self.on_node_undrain is not None:
            self.on_node_undrain(entry)

    async def _rpc_chaos_arm(self, _ctx, specs):
        from ..util import events as _events
        from ..util import faults

        normalized = [faults.validate_spec(s) for s in (specs or [])]
        self.chaos_gen += 1
        # Stamp each spec with a stable id: entries retained across an
        # append (the CLI re-arms current-plan + new-spec) keep their
        # id, so apply_plan preserves their hit/fire counters and an
        # exhausted once/max_fires spec does NOT fire again just
        # because an unrelated spec was armed. Brand-new entries (no
        # id, or an id the current plan doesn't hold) get a fresh one
        # and start from zero.
        known = {s.get("id") for s in self.chaos_specs}
        for s in normalized:
            if s.get("id") is None or s["id"] not in known:
                s["id"] = f"cs{self.chaos_gen}-{self._chaos_spec_seq}"
                self._chaos_spec_seq += 1
        self.chaos_specs = normalized
        # This (head) process arms immediately; remote nodes via the
        # broadcast; the head's workers via the on_chaos_update hook.
        faults.apply_plan(normalized, self.chaos_gen)
        await self._broadcast({
            "type": "chaos_update", "specs": normalized,
            "gen": self.chaos_gen,
        })
        if self.on_chaos_update is not None:
            self.on_chaos_update(normalized, self.chaos_gen)
        self._record_event(
            _events.WARNING if normalized else _events.INFO,
            _events.GCS,
            f"chaos plan armed: {len(normalized)} spec(s) "
            f"(gen {self.chaos_gen})" if normalized
            else f"chaos plan disarmed (gen {self.chaos_gen})",
            custom_fields={"specs": normalized, "gen": self.chaos_gen},
        )
        return {"gen": self.chaos_gen}

    async def _rpc_chaos_disarm(self, _ctx):
        return await self._rpc_chaos_arm(_ctx, [])

    async def _rpc_chaos_list(self, _ctx):
        return {"specs": list(self.chaos_specs), "gen": self.chaos_gen}

    async def _rpc_kv_put(self, node_id, key, value, overwrite=True):
        return {"added": self.kv_put(key, value, overwrite)}

    async def _rpc_kv_get(self, node_id, key, wait_timeout=0):
        if wait_timeout:
            return {"value": await self.kv_wait(key, wait_timeout)}
        return {"value": self._kv.get(key)}

    async def _rpc_kv_del(self, node_id, key):
        deleted = self._kv.pop(key, None) is not None
        if deleted:
            self._dirty = True
        return {"deleted": deleted}

    async def _rpc_kv_keys(self, node_id, prefix=""):
        return {"keys": [k for k in self._kv if k.startswith(prefix)]}

    async def _rpc_register_function(self, node_id, function_id, blob):
        self._functions[function_id] = blob
        self._dirty = True
        return {"ok": True}

    async def _rpc_fetch_function(self, node_id, function_id):
        return {"blob": self._functions.get(function_id)}

    async def _rpc_register_named_actor(self, _ctx, name, actor_id,
                                        node_id, spec=None):
        ok = self.register_named_actor(
            name, ActorID.from_hex(actor_id), NodeID.from_hex(node_id),
            spec,
        )
        return {"added": ok}

    async def _rpc_get_named_actor(self, node_id, name):
        entry = self._named_actors.get(name)
        if entry is None:
            return {"found": False, "actor_id": None, "node_id": None,
                    "spec": None}
        aid, nid, spec = entry
        return {"found": True, "actor_id": aid.hex(),
                "node_id": nid.hex(), "spec": spec}

    async def _rpc_drop_named_actor(self, node_id, name, actor_id):
        cur = self._named_actors.get(name)
        if cur is not None and cur[0].hex() == actor_id:
            self._named_actors.pop(name, None)
            self._dirty = True
            self.pubsub.publish(
                ACTOR_STATE,
                {"event": "named_actor_dropped", "name": name,
                 "actor_id": actor_id},
                key=name,
            )

    async def _rpc_register_actor_node(self, _ctx, actor_id, node_id,
                                       incarnation=0):
        return {
            "incarnation": self.register_actor_node(
                ActorID.from_hex(actor_id), NodeID.from_hex(node_id),
                incarnation=incarnation,
            )
        }

    def register_actor_node(self, actor_id: ActorID, node_id: NodeID,
                            incarnation: int = 0) -> int:
        """Record the actor's home and assign its incarnation: 0 (the
        default, a fresh start or restart) bumps the actor's counter —
        every start across the whole cluster lifetime gets a distinct,
        monotonically increasing incarnation; a nonzero value is a
        reconnect re-registration keeping the incarnation it already
        runs as (the counter only ratchets up)."""
        hex_id = actor_id.hex()
        if incarnation:
            inc = int(incarnation)
            if inc > self._actor_incarnations.get(hex_id, 0):
                self._actor_incarnations[hex_id] = inc
                self._dirty = True
        else:
            inc = self._actor_incarnations.get(hex_id, 0) + 1
            self._actor_incarnations[hex_id] = inc
            self._dirty = True
        self._actor_nodes[actor_id] = node_id
        return inc

    async def _rpc_get_actor_node(self, node_id, actor_id):
        nid = self._actor_nodes.get(ActorID.from_hex(actor_id))
        return {"node_id": nid.hex() if nid else None}

    async def _rpc_publish_object(self, node_id, object_id):
        self.publish_object(object_id, node_id)

    async def _rpc_unpublish_object(self, node_id, object_id):
        self.unpublish_object(object_id, node_id)

    async def _rpc_locate_object(self, node_id, object_id, timeout=0):
        nid = await self.locate_object(object_id, timeout)
        return {"node_id": nid.hex() if nid else None}

    async def _rpc_pg_create(self, node_id, pg_id, bundles, strategy,
                             name="", label_selectors=None):
        await self.pg_create(pg_id, bundles, strategy, name,
                             label_selectors=label_selectors)
        return {"ok": True}

    async def _rpc_pg_wait(self, node_id, pg_id, timeout):
        return {"ready": await self.pg_wait(pg_id, timeout)}

    async def _rpc_pg_remove(self, node_id, pg_id):
        await self.pg_remove(pg_id)
        return {"ok": True}

    async def _rpc_pg_get(self, node_id, pg_id):
        return self.pg_get(pg_id)

    async def _rpc_pg_table(self, node_id):
        return {"table": self.pg_table()}

    async def _rpc_psub_subscribe(self, node_id, subscriber_id,
                                  channels):
        self.pubsub.subscribe(subscriber_id, channels)
        return {"ok": True}

    async def _rpc_psub_poll(self, node_id, subscriber_id, timeout=30.0,
                             max_events=1000):
        return await self.pubsub.poll(subscriber_id, timeout,
                                      max_events)

    async def _rpc_psub_publish(self, node_id, channel, data, key=None):
        return {"seq": self.pubsub.publish(channel, data, key=key)}

    async def _rpc_psub_unsubscribe(self, node_id, subscriber_id,
                                    channels=None):
        self.pubsub.unsubscribe(subscriber_id, channels)

    async def _rpc_events_list(self, node_id, severity=None, source=None,
                               limit=1000):
        stats = self.events.stats()
        return {
            "events": self.events.list(severity=severity, source=source,
                                       limit=limit),
            "total": stats["total"],
            "dropped": stats["dropped"],
        }

    # ----------------------------------------------------------- SLO plane

    # A `__metrics__` blob whose writer looks dead must stay orphaned
    # this long (monotonic) before it is reaped — a process mid-GC-pause
    # or briefly partitioned resumes refreshing its ts and is spared.
    METRICS_GC_GRACE_S = 10.0
    # A v2 blob whose embedded ts is older than this is a dead pid's
    # leftover (live processes refresh every PROC_SAMPLE_INTERVAL_S).
    METRICS_STALE_S = 30.0

    async def _metrics_sample_loop(self):
        """Ingest tick: aggregate the flushed `__metrics__` KV blobs
        into the TSDB each KV flush interval (the pipeline IS the wire
        protocol), reap dead writers' blobs, and evaluate declared SLO
        specs every ``slo_eval_interval_s``."""
        from ..util import metrics as user_metrics

        interval = user_metrics.FLUSH_INTERVAL_S
        eval_interval = max(interval, float(getattr(
            self.config, "slo_eval_interval_s", 5.0)))
        last_eval = 0.0
        while True:
            await asyncio.sleep(interval)
            try:
                now = time.time()
                self._sample_metrics_once(now)
                if time.monotonic() - last_eval >= eval_interval:
                    last_eval = time.monotonic()
                    self._evaluate_slo(now)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                sys.stderr.write(
                    f"[gcs] WARNING: metrics sample tick failed "
                    f"({type(e).__name__}: {e}); retrying\n"
                )

    def _sample_metrics_once(self, now: float) -> Dict[str, Dict]:
        """One pass over the `__metrics__` keys: decode each blob once,
        GC orphans (dead node, stale ts, corrupt), aggregate the live
        ones, append one TSDB sample per series."""
        from ..util import metrics as user_metrics

        prefix = user_metrics.KV_PREFIX
        alive = {e.node_id.hex() for e in self._nodes.values()
                 if e.state == "alive"}
        mono = time.monotonic()
        report: Dict[str, Dict] = {}
        for key in [k for k in self._kv if k.startswith(prefix)]:
            rel = key[len(prefix):]
            node_hex = rel.split("/", 1)[0] if "/" in rel else ""
            snapshot = None
            ts = 0.0
            try:
                snapshot, ts = user_metrics.decode_snapshot(self._kv[key])
            except Exception:  # rtlint: disable=swallowed-failure
                pass  # corrupt blob: treated as orphaned below (GC'd)
            orphaned = (
                snapshot is None
                or (node_hex and node_hex not in alive)
                or (ts and now - ts > self.METRICS_STALE_S)
            )
            if not orphaned:
                self._metrics_orphans.pop(key, None)
                user_metrics.merge_snapshot(report, snapshot)
                continue
            # Orphans stop aggregating immediately (ghost gauges must
            # not skew the report) but are only DELETED past the grace
            # window — a writer that resumes clears the timer.
            first = self._metrics_orphans.setdefault(key, mono)
            if mono - first >= self.METRICS_GC_GRACE_S:
                self._kv.pop(key, None)
                self._metrics_orphans.pop(key, None)
        for key in [k for k in self._metrics_orphans
                    if k not in self._kv]:
            self._metrics_orphans.pop(key, None)
        self.tsdb.ingest_report(report, now)
        return report

    def _evaluate_slo(self, now: float) -> None:
        import json

        from ..util import slo as slo_mod

        specs = slo_mod.decode_specs({
            k: v for k, v in self._kv.items()
            if k.startswith(slo_mod.SPEC_PREFIX)
        })
        status = self.slo_engine.evaluate(self.tsdb, specs, now)
        self.kv_put(slo_mod.STATUS_KEY,
                    json.dumps(status, default=str).encode(), True)
        self._publish_head_metrics()

    def _publish_head_metrics(self) -> None:
        """A standalone head (no driver runtime in this process) has no
        flusher transport for the ray_tpu_slo_* gauges the engine just
        set — write the registry snapshot into the KV table directly
        (pid-scoped key, fresh ts, so the GC above keeps it)."""
        from ..core import runtime_context
        from ..util import metrics as user_metrics

        if runtime_context.current_runtime_or_none() is not None:
            return  # the normal flusher owns this process's blob
        try:
            import cloudpickle

            self._kv[f"{user_metrics.KV_PREFIX}{os.getpid()}"] = \
                cloudpickle.dumps({
                    "v": 2, "ts": time.time(), "pid": os.getpid(),
                    "node": "", "metrics": user_metrics.local_snapshot(),
                })
        except Exception:  # rtlint: disable=swallowed-failure
            pass  # exposition-only convenience; the RPC path still works

    def _emit_slo_event(self, severity: str, message: str,
                        fields: Dict[str, Any]) -> None:
        from ..util import events as events_mod

        self._record_event(severity, events_mod.SLO, message,
                           custom_fields=fields)

    async def _rpc_timeseries_query(self, node_id, name="", tags=None,
                                    since=0.0, limit=0, quantile=0.0,
                                    window=60.0):
        if not name:
            # Discovery form: what series exist + store accounting.
            return {"series": [], "names": self.tsdb.names(),
                    "stats": self.tsdb.stats()}
        out = {
            "series": self.tsdb.query(name, tags=tags or None,
                                      since=since, limit=limit),
            "names": [], "stats": self.tsdb.stats(),
        }
        if quantile and quantile > 0.0:
            window = max(1.0, float(window))
            d = self.tsdb.hist_delta(name, tags=tags or None,
                                     window_s=window) or {}
            from ..util.tsdb import quantile_from_histogram

            qv = None
            if d.get("buckets"):
                qv = quantile_from_histogram(d["bounds"], d["buckets"],
                                             quantile)
            out["derived"] = {
                "quantile": qv,
                "q": float(quantile),
                "count": d.get("count", 0),
                "sum": d.get("sum", 0.0),
                "window_s": window,
            }
        return out

    async def _rpc_slo_status(self, node_id):
        return {"deployments": dict(self.slo_engine.status),
                "ts": time.time()}

    async def _rpc_stacks_dump(self, node_id, timeout=5.0):
        return await self._profile_fanout(
            {"type": "stacks_dump", "timeout": max(0.5, timeout)},
            per_node_timeout=max(1.0, timeout) + 2.0,
        )

    async def _rpc_profile_run(self, node_id, seconds=2.0, hz=100):
        from ..util.profiler import MAX_SAMPLE_SECONDS

        # Nodes clamp to the sampler's hard cap; apply the same cap here
        # so the per-node wait cannot be inflated past the real
        # sampling time.
        seconds = max(0.0, min(float(seconds), MAX_SAMPLE_SECONDS))
        return await self._profile_fanout(
            {"type": "profile_run", "seconds": seconds, "hz": hz},
            per_node_timeout=seconds + 10.0,
        )

    async def _rpc_traces_dump(self, node_id, reason="", limit=200):
        return await self._profile_fanout(
            {"type": "traces_dump", "reason": reason, "limit": limit},
            per_node_timeout=10.0,
        )

    async def _rpc_objects_census(self, node_id, limit=500):
        return await self._profile_fanout(
            {"type": "objects_census", "limit": int(limit)},
            per_node_timeout=10.0,
        )

    async def _profile_fanout(self, frame, per_node_timeout: float):
        """ProfileService core: issue ``frame`` to every alive node over
        its peer channel concurrently; unreachable/late nodes land in
        ``errors`` instead of stalling the aggregate reply."""
        alive = [e for e in self._nodes.values() if e.state == "alive"]
        errors: Dict[str, str] = {}

        async def query(entry):
            hex_id = entry.node_id.hex()
            try:
                peer = await self._pg_peer(hex_id)
                reply = await peer.request(
                    dict(frame), timeout=per_node_timeout
                )
                if reply.get("error"):
                    # The node answered but its dump raised: that's a
                    # partial result too — it must land in `errors`,
                    # not silently vanish from both lists.
                    errors[hex_id] = str(reply["error"])
                    return None
                return reply.get("result")
            # rtlint: disable=swallowed-failure — recorded in `errors`
            except Exception as e:  # noqa: BLE001 — partial > hang
                errors[hex_id] = str(e) or type(e).__name__
                return None

        results = await asyncio.gather(*(query(e) for e in alive))
        return {"nodes": [r for r in results if r], "errors": errors}

    async def _rpc_rpc_describe(self, node_id):
        return {"services": self._rpc.describe()}

    # ------------------------------------------------------ placement groups

    async def pg_create(
        self, pg_id: str, bundles: List[Dict[str, float]], strategy: str,
        name: str = "", label_selectors: Optional[List[Dict[str, str]]] = None,
    ):
        self._pgs[pg_id] = {
            "pg_id": pg_id,
            "bundles": bundles,
            "strategy": strategy,
            "name": name,
            "label_selectors": label_selectors,
            "state": "pending",
            "nodes": None,
            "event": asyncio.Event(),
        }
        await self._try_place_pg(pg_id)

    async def _try_place_pg(self, pg_id: str):
        from .resources import ResourceSet
        from .scheduling_policy import place_bundles

        pg = self._pgs.get(pg_id)
        if pg is None or pg["state"] != "pending" or pg.get("placing"):
            return
        pg["placing"] = True
        try:
            reqs = [ResourceSet(b) for b in pg["bundles"]]
            # place_bundles filters to state == "alive" itself; draining
            # and dead nodes never receive new bundles.
            chosen = place_bundles(
                reqs, pg["strategy"], self.nodes_view(),
                label_selectors=pg.get("label_selectors"),
            )
            if chosen is None:
                return  # stays pending; retried on node join / wait poll
            # Two-phase commit: prepare everywhere, then commit; roll back
            # the prepared subset on any failure or concurrent removal (ref:
            # PrepareBundleResources / CommitBundleResources,
            # node_manager.proto:382-386).
            prepared: List[int] = []
            ok = True
            for idx, node_hex in enumerate(chosen):
                try:
                    peer = await self._pg_peer(node_hex)
                    reply = await peer.request(
                        {
                            "type": "prepare_bundle",
                            "pg_id": pg_id,
                            "index": idx,
                            "resources": pg["bundles"][idx],
                        },
                        timeout=10.0,
                    )
                    if not reply.get("ok"):
                        ok = False
                        break
                    prepared.append(idx)
                except Exception as e:
                    self._record_event(
                        "WARNING", "GCS",
                        f"placement group {pg_id[:8]} bundle {idx} "
                        f"prepare failed on node {node_hex[:8]} "
                        f"({type(e).__name__}: {e}); re-placing",
                    )
                    ok = False
                    break
            # Removed (or node lost) while the prepares were in flight?
            if self._pgs.get(pg_id, {}).get("state") != "pending":
                ok = False
            if not ok:
                await self._release_prepared(pg_id, chosen, prepared)
                return
            for idx, node_hex in enumerate(chosen):
                try:
                    peer = await self._pg_peer(node_hex)
                    await peer.notify(
                        {"type": "commit_bundle", "pg_id": pg_id, "index": idx}
                    )
                except Exception as e:
                    self._record_event(
                        "WARNING", "GCS",
                        f"placement group {pg_id[:8]} bundle {idx} "
                        f"commit notify to node {node_hex[:8]} failed "
                        f"({type(e).__name__}: {e}); node-death "
                        f"re-placement will recover it",
                    )
            if self._pgs.get(pg_id, {}).get("state") != "pending":
                await self._release_prepared(pg_id, chosen, prepared)
                return
            pg["nodes"] = chosen
            pg["state"] = "created"
            pg["event"].set()
        finally:
            pg["placing"] = False

    async def _release_prepared(self, pg_id, chosen, prepared):
        for idx in prepared:
            try:
                peer = await self._pg_peer(chosen[idx])
                await peer.notify(
                    {"type": "release_bundle", "pg_id": pg_id, "index": idx}
                )
            # Best-effort release toward a node that likely just died
            # (that is why we are rolling back); its reservations die
            # with it, and a live node re-syncs on the next placement.
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    async def pg_wait(self, pg_id: str, timeout: float) -> bool:
        pg = self._pgs.get(pg_id)
        if pg is None:
            return False
        if pg["state"] == "created":
            return True
        await self._try_place_pg(pg_id)
        pg = self._pgs.get(pg_id)
        if pg is None:
            return False
        try:
            await asyncio.wait_for(pg["event"].wait(), timeout)
        except asyncio.TimeoutError:
            return False
        return pg["state"] == "created"

    async def pg_remove(self, pg_id: str):
        pg = self._pgs.get(pg_id)
        if pg is None:
            return
        nodes = pg.get("nodes") or []
        pg["state"] = "removed"
        pg["event"].set()
        for idx, node_hex in enumerate(nodes):
            try:
                peer = await self._pg_peer(node_hex)
                await peer.notify(
                    {"type": "release_bundle", "pg_id": pg_id, "index": idx}
                )
            # Best-effort: the PG is already marked removed; a node that
            # missed the release reclaims the bundle when it next syncs
            # (or is dead and needs no release at all).
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    def pg_get(self, pg_id: str) -> Dict[str, Any]:
        pg = self._pgs.get(pg_id)
        if pg is None:
            return {"state": "unknown", "bundle_nodes": None}
        return {
            "state": pg["state"],
            "bundle_nodes": (
                {i: n for i, n in enumerate(pg["nodes"])}
                if pg["nodes"] is not None
                else None
            ),
        }

    def pg_table(self) -> Dict[str, Dict[str, Any]]:
        return {
            pg_id: {
                "bundles": pg["bundles"],
                "strategy": pg["strategy"],
                "name": pg["name"],
                "state": pg["state"],
                "nodes": pg["nodes"],
            }
            for pg_id, pg in self._pgs.items()
        }

    async def _pg_peer(self, node_hex: str):
        from .peers import PeerClient

        peer = self._pg_peers.get(node_hex)
        if isinstance(peer, asyncio.Future):
            return await asyncio.shield(peer)
        if peer is not None and not peer.closed:
            return peer
        entry = self._nodes.get(NodeID.from_hex(node_hex))
        # Draining nodes stay reachable: the drain RPC itself and any
        # in-flight PG release must still get through.
        if entry is None or entry.state not in ("alive", "draining"):
            raise ConnectionError(f"node {node_hex[:8]} not alive")
        fut: asyncio.Future = self._loop.create_future()
        self._pg_peers[node_hex] = fut
        try:
            peer = PeerClient(node_hex, entry.host, entry.peer_port, "gcs")
            await peer.connect()
        except Exception as e:
            self._pg_peers.pop(node_hex, None)
            if not fut.done():
                fut.set_exception(e)
                fut.exception()
            raise
        self._pg_peers[node_hex] = peer
        if not fut.done():
            fut.set_result(peer)
        return peer

    # ----------------------------------------------------------------- nodes

    async def register_node(
        self,
        node_id: NodeID,
        host: str,
        peer_port: int,
        resources: Dict[str, float],
        *,
        is_head: bool = False,
        labels: Optional[Dict[str, str]] = None,
    ) -> Dict[str, Any]:
        hex_id = node_id.hex()
        # Membership-fence bookkeeping: every registration bumps the
        # cluster epoch and gets the next incarnation of this node id.
        # A node previously declared dead learns so via fenced_at in
        # the reply (and must self-terminate its old incarnation's
        # workers before resuming); its fence record clears here — the
        # fresh incarnation is a first-class member again.
        self.cluster_epoch += 1
        incarnation = self._node_incarnations.get(hex_id, 0) + 1
        self._node_incarnations[hex_id] = incarnation
        fenced_at = self._fenced_nodes.pop(hex_id, 0)
        self._dirty = True
        entry = NodeEntry(
            node_id=node_id,
            host=host,
            peer_port=peer_port,
            resources_total=dict(resources),
            resources_available=dict(resources),
            is_head=is_head,
            labels=labels or {},
            incarnation=incarnation,
        )
        self._nodes[node_id] = entry
        await self._broadcast(
            {"type": "node_added", "node": entry.view(),
             "epoch": self.cluster_epoch}, exclude=node_id
        )
        self.pubsub.publish(
            NODE_STATE, {"event": "added", "node": entry.view()},
            key=hex_id,
        )
        from ..util import events as _events

        self._record_event(
            _events.WARNING if fenced_at else _events.INFO,
            _events.NODE if fenced_at else _events.GCS,
            f"node {hex_id[:8]} registered as incarnation "
            f"{incarnation} (epoch {self.cluster_epoch})"
            + (f" — rejoin after fence at epoch {fenced_at}"
               if fenced_at else f" (host={host})"),
            node_id=hex_id,
            custom_fields={"host": host, "is_head": is_head,
                           "incarnation": incarnation,
                           "epoch": self.cluster_epoch,
                           "fenced_at": fenced_at},
        )
        if self.on_node_added is not None:
            self.on_node_added(entry)
        # New capacity may unblock pending placement groups.
        asyncio.ensure_future(self._retry_pending_pgs())
        return {
            "nodes": [e.view() for e in self._nodes.values()],
            # Late joiners arm the current chaos plan immediately (an
            # empty plan disarms — correct after a head restart too).
            "chaos": {"specs": list(self.chaos_specs),
                      "gen": self.chaos_gen},
            "epoch": self.cluster_epoch,
            "incarnation": incarnation,
            "fenced_at": fenced_at,
        }

    async def _retry_pending_pgs(self):
        for pg_id, pg in list(self._pgs.items()):
            if pg["state"] == "pending":
                await self._try_place_pg(pg_id)

    def heartbeat(
        self, node_id: NodeID, available: Dict[str, float], pending: int,
        shapes: Optional[List[Any]] = None,
    ):
        entry = self._nodes.get(node_id)
        if entry is None or entry.state == "dead":
            return
        entry.resources_available = available
        entry.pending_tasks = pending
        if shapes is not None:
            entry.pending_shapes = shapes
        entry.last_heartbeat = time.monotonic()

    async def _broadcast_load(self):
        views = [e.view() for e in self._nodes.values() if e.state == "alive"]
        msg = {"type": "cluster_load", "nodes": views,
               "epoch": self.cluster_epoch}
        await self._broadcast(msg)
        if self.on_load_update is not None:
            self.on_load_update(msg)

    async def _health_loop(self):
        period = self.config.gcs_health_check_period_s
        timeout = self.config.node_death_timeout_s
        while True:
            await asyncio.sleep(period)
            now = time.monotonic()
            for entry in list(self._nodes.values()):
                if entry.is_head or entry.state == "dead":
                    continue
                if now - entry.last_heartbeat > timeout:
                    await self._mark_node_dead(entry, "missed heartbeats")
            self.pubsub.reap_idle()

    async def _mark_node_dead(self, entry: NodeEntry, reason: str):
        entry.state = "dead"
        # Fence the death at a new membership epoch: peers must stop
        # trusting this incarnation NOW (tear down direct/data channels,
        # refuse its frames), and if the node is actually alive behind
        # an asymmetric partition, its eventual re-register reply will
        # carry this epoch so it self-terminates instead of resuming.
        self.cluster_epoch += 1
        dead_hex = entry.node_id.hex()
        self._fenced_nodes[dead_hex] = self.cluster_epoch
        self._dirty = True
        from . import fencing as _fencing

        _fencing.EVENT_NODE_FENCED.inc()
        conn = self._conns.pop(entry.node_id, None)
        if conn is not None:
            conn.close()
        peer = self._pg_peers.pop(entry.node_id.hex(), None)
        if peer is not None and hasattr(peer, "close"):
            peer.close()
        # Purge location/actor records pointing at the dead node.
        for oid in list(self._object_nodes):
            self.unpublish_object(oid, entry.node_id)
        dead_actors = [
            aid for aid, nid in self._actor_nodes.items() if nid == entry.node_id
        ]
        for aid in dead_actors:
            del self._actor_nodes[aid]
        self._named_actors = {
            name: rec for name, rec in self._named_actors.items()
            if rec[1] != entry.node_id
        }
        # Placement groups with a bundle on the dead node go back to pending
        # and are re-placed; node managers drop their bundle reservations and
        # routing caches so tasks re-resolve instead of forwarding into the
        # void (ref analogue: GcsPlacementGroupManager::OnNodeDead
        # rescheduling).
        invalid_pgs: List[str] = []
        for pg_id, pg in self._pgs.items():
            if pg["state"] == "created" and pg["nodes"] and dead_hex in pg["nodes"]:
                pg["state"] = "pending"
                pg["nodes"] = None
                pg["event"] = asyncio.Event()
                invalid_pgs.append(pg_id)
        # Fence broadcast rides the same channel as node_draining: every
        # peer NM tears down its direct channels and data pools to the
        # fenced node and refuses the fenced incarnation's frames. Sent
        # BEFORE node_dead so teardown precedes the death cleanup.
        await self._broadcast(
            {
                "type": "node_fenced",
                "node_id": dead_hex,
                "epoch": self.cluster_epoch,
                "incarnation": entry.incarnation,
            }
        )
        await self._broadcast(
            {
                "type": "node_dead",
                "node_id": dead_hex,
                "reason": reason,
                "dead_actors": [a.hex() for a in dead_actors],
                "invalid_pgs": invalid_pgs,
                "epoch": self.cluster_epoch,
            }
        )
        self.pubsub.publish(
            NODE_STATE,
            {"event": "dead", "node_id": dead_hex, "reason": reason,
             "dead_actors": [a.hex() for a in dead_actors]},
            key=dead_hex,
        )
        from ..util import events as _events

        self._record_event(
            _events.WARNING, _events.NODE,
            f"FENCE: node {dead_hex[:8]} (incarnation "
            f"{entry.incarnation}) fenced at epoch "
            f"{self.cluster_epoch}: {reason}",
            node_id=dead_hex,
            custom_fields={
                "reason": reason,
                "epoch": self.cluster_epoch,
                "incarnation": entry.incarnation,
            },
        )
        self._record_event(
            _events.ERROR, _events.GCS,
            f"node {dead_hex[:8]} died: {reason}",
            node_id=dead_hex,
            custom_fields={
                "reason": reason,
                "dead_actors": len(dead_actors),
                "invalidated_pgs": len(invalid_pgs),
            },
        )
        if invalid_pgs and self.on_pgs_invalidated is not None:
            self.on_pgs_invalidated(invalid_pgs)
        # Fence teardown BEFORE the death cleanup: the head's direct
        # channels to the fenced node must stop carrying calls before
        # replay/restart bookkeeping runs.
        if self.on_node_fenced is not None:
            self.on_node_fenced(entry, self.cluster_epoch)
        if self.on_node_dead is not None:
            self.on_node_dead(entry)
        if invalid_pgs:
            asyncio.ensure_future(self._retry_pending_pgs())

    async def _broadcast(self, msg: Dict[str, Any], exclude: Optional[NodeID] = None):
        for nid, conn in list(self._conns.items()):
            if nid == exclude:
                continue
            try:
                await conn.send(msg)
            # Broadcasts are idempotent state pushes re-sent every
            # heartbeat interval; a dead conn is detected and reaped by
            # its reader loop, which also fires the node-death path.
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    # -------------------------------------------------------------------- kv

    def kv_put(self, key: str, value: bytes, overwrite: bool = True) -> bool:
        if not overwrite and key in self._kv:
            return False
        self._kv[key] = value
        self._dirty = True
        ev = self._kv_events.pop(key, None)
        if ev is not None:
            ev.set()
        return True

    async def kv_wait(self, key: str, timeout: float) -> Optional[bytes]:
        """Blocking get used for rendezvous barriers (ref analogue: the
        NCCLUniqueIDStore named actor the reference's collectives poll)."""
        if key in self._kv:
            return self._kv[key]
        ev = self._kv_events.setdefault(key, asyncio.Event())
        try:
            await asyncio.wait_for(ev.wait(), timeout)
        except asyncio.TimeoutError:
            return None
        return self._kv.get(key)

    # ---------------------------------------------------------------- actors

    def register_named_actor(
        self, name: str, actor_id: ActorID, node_id: NodeID, spec: Any
    ) -> bool:
        existing = self._named_actors.get(name)
        if existing is not None:
            # Idempotent for the same actor (restart re-claims its name).
            return existing[0] == actor_id
        self._named_actors[name] = (actor_id, node_id, spec)
        self._dirty = True
        self.pubsub.publish(
            ACTOR_STATE,
            {"event": "named_actor_registered", "name": name,
             "actor_id": actor_id.hex(), "node_id": node_id.hex()},
            key=name,
        )
        return True

    # --------------------------------------------------------------- objects

    def publish_object(self, object_id: ObjectID, node_id: NodeID):
        # Fence guard: a location claim from a node we do not currently
        # hold alive is a stale republish from a fenced incarnation (or
        # a ghost) — recording it would resurrect a location consumers
        # already recovered away from.
        entry = self._nodes.get(node_id)
        if entry is None or entry.state == "dead":
            return
        self._object_nodes.setdefault(object_id, set()).add(node_id)
        ev = self._object_events.pop(object_id, None)
        if ev is not None:
            ev.set()

    def unpublish_object(self, object_id: ObjectID, node_id: Optional[NodeID]):
        """Remove only the *sender's* replica registration; other nodes'
        copies stay locatable."""
        nodes = self._object_nodes.get(object_id)
        if nodes is None:
            return
        if node_id is not None:
            nodes.discard(node_id)
        if not nodes or node_id is None:
            self._object_nodes.pop(object_id, None)

    def _pick_object_node(self, object_id: ObjectID) -> Optional[NodeID]:
        best = None
        for nid in self._object_nodes.get(object_id, ()):  # any live replica
            entry = self._nodes.get(nid)
            if entry is None:
                continue
            if entry.state == "alive":
                return nid
            if entry.state == "draining" and best is None:
                # Still readable, but prefer a replica that will outlive
                # the drain when one exists.
                best = nid
        return best

    async def locate_object(
        self, object_id: ObjectID, timeout: float = 0
    ) -> Optional[NodeID]:
        nid = self._pick_object_node(object_id)
        if nid is not None or timeout <= 0:
            return nid
        ev = self._object_events.setdefault(object_id, _LocateEvent())
        ev.waiters += 1
        try:
            await asyncio.wait_for(ev.wait(), timeout)
        except asyncio.TimeoutError:
            return None
        finally:
            # The last waiter of an object that was never published (a
            # stream's item past its end, a lost object) takes the event
            # with it, timed out or cancelled.
            ev.waiters -= 1
            if (not ev.waiters and not ev.is_set()
                    and self._object_events.get(object_id) is ev):
                del self._object_events[object_id]
        return self._pick_object_node(object_id)

    def nodes_view(self) -> List[Dict[str, Any]]:
        views = [e.view() for e in self._nodes.values()]
        for v in views:
            # Cluster epoch stamped per row so every nodes() consumer
            # (rtpu nodes, /api/nodes, thin clients) sees it without a
            # second RPC.
            v["epoch"] = self.cluster_epoch
        return views


# Ops the gcs_rpc injection point never faults: the chaos plane's own
# control traffic and node registration. Without this, arming gcs_rpc
# with mode=always leaves no working path to disarm (every disarm RPC
# and every re-register after a drop self-faults until head restart).
_GCS_RPC_FAULT_EXEMPT_OPS = frozenset(
    {"chaos_arm", "chaos_disarm", "chaos_list", "register_node"}
)


class GcsClient:
    """Remote node manager's connection to the GCS, living on the node
    manager's asyncio loop (ref analogue: gcs_client/gcs_client.h GcsClient
    + the syncer's client side)."""

    def __init__(self, node_id: NodeID, host: str, port: int):
        self.node_id = node_id
        self.host = host
        self.port = port
        self._writer: Optional[_FramedWriter] = None
        self._reader_task: Optional[asyncio.Task] = None
        self._pending: Dict[int, asyncio.Future] = {}
        self._msg_counter = 0
        # Push handler installed by the node manager.
        self.on_push: Optional[Callable[[Dict[str, Any]], Awaitable[None]]] = None
        self.closed = False

    async def connect(self):
        from .tls import client_ssl_context

        reader, writer = await asyncio.open_connection(
            self.host, self.port, ssl=client_ssl_context()
        )
        self._writer = _FramedWriter(writer)
        await self._writer.send(
            {"type": "gcs_hello", "node_id": self.node_id.hex(),
             "token": get_config().session_token}
        )
        welcome = await _read_frame(reader)
        if welcome.get("type") == "gcs_error":
            raise ConnectionError(f"GCS refused connection: "
                                  f"{welcome.get('error')}")
        assert welcome["type"] == "gcs_welcome", welcome
        self._reader_task = asyncio.ensure_future(self._reader_loop(reader))

    async def _reader_loop(self, reader: asyncio.StreamReader):
        try:
            while True:
                msg = await _read_frame(reader)
                if msg.get("type") == "reply":
                    fut = self._pending.pop(msg.get("msg_id"), None)
                    if fut is not None and not fut.done():
                        fut.set_result(msg)
                elif self.on_push is not None:
                    await self.on_push(msg)
        except (asyncio.IncompleteReadError, ConnectionResetError, OSError):
            self.closed = True
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(ConnectionError("GCS connection lost"))
            self._pending.clear()

    async def request(self, msg: Dict[str, Any], timeout: float = 30.0):
        if self.closed or self._writer is None:
            raise ConnectionError("GCS connection lost")
        # Chaos plane: an injected error here surfaces exactly like a
        # lost GCS round trip (callers retry/backoff or reconnect).
        # Chaos-control and registration ops are exempt: faulting
        # chaos_disarm would make an armed cluster un-disarmable, and
        # faulting register_node would keep a partitioned node from
        # ever rejoining to receive the disarm — the kill switch must
        # always work.
        if msg.get("op") not in _GCS_RPC_FAULT_EXEMPT_OPS:
            delay = faults.fire(faults.GCS_RPC, op=msg.get("op"))
            if delay:
                await asyncio.sleep(delay)
        self._msg_counter += 1
        msg_id = self._msg_counter
        msg["msg_id"] = msg_id
        fut: asyncio.Future = asyncio.get_event_loop().create_future()
        self._pending[msg_id] = fut
        await self._writer.send(msg)
        try:
            reply = await asyncio.wait_for(fut, timeout)
        finally:
            self._pending.pop(msg_id, None)
        if reply.get("error"):
            raise RuntimeError(f"GCS error: {reply['error']}")
        return reply

    async def notify(self, msg: Dict[str, Any]):
        if self.closed or self._writer is None:
            return
        try:
            await self._writer.send(msg)
        # Surfaced through the closed flag: the next request() fails
        # fast and the owner's reconnect path (jittered backoff) logs.
        except Exception:  # rtlint: disable=swallowed-failure
            self.closed = True

    def close(self):
        self.closed = True
        if self._reader_task is not None:
            self._reader_task.cancel()
        if self._writer is not None:
            self._writer.close()


class LocalGcsHandle:
    """Head node manager's view of the in-process GCS (direct calls)."""

    def __init__(self, service: GcsService):
        self._svc = service

    async def kv_put(self, key, value, overwrite=True) -> bool:
        return self._svc.kv_put(key, value, overwrite)

    async def kv_get(self, key, wait_timeout: float = 0):
        if wait_timeout:
            return await self._svc.kv_wait(key, wait_timeout)
        return self._svc._kv.get(key)

    async def kv_del(self, key) -> bool:
        return self._svc._kv.pop(key, None) is not None

    async def kv_keys(self, prefix=""):
        return [k for k in self._svc._kv if k.startswith(prefix)]

    async def register_function(self, function_id, blob):
        self._svc._functions[function_id] = blob

    async def fetch_function(self, function_id):
        return self._svc._functions.get(function_id)

    async def register_named_actor(self, name, actor_id, node_id, spec) -> bool:
        return self._svc.register_named_actor(name, actor_id, node_id, spec)

    async def get_named_actor(self, name):
        entry = self._svc._named_actors.get(name)
        if entry is None:
            return None
        return entry

    async def drop_named_actor(self, name, actor_id):
        cur = self._svc._named_actors.get(name)
        if cur is not None and cur[0] == actor_id:
            self._svc._named_actors.pop(name, None)

    async def register_actor_node(self, actor_id, node_id,
                                  incarnation: int = 0) -> int:
        return self._svc.register_actor_node(
            actor_id, node_id, incarnation=incarnation
        )

    async def get_actor_node(self, actor_id):
        return self._svc._actor_nodes.get(actor_id)

    async def publish_object(self, object_id, node_id):
        self._svc.publish_object(object_id, node_id)

    async def unpublish_object(self, object_id, node_id=None):
        self._svc.unpublish_object(object_id, node_id)

    async def locate_object(self, object_id, timeout=0):
        return await self._svc.locate_object(object_id, timeout)

    async def pg_create(self, pg_id, bundles, strategy, name="",
                        label_selectors=None):
        await self._svc.pg_create(
            pg_id, bundles, strategy, name, label_selectors=label_selectors
        )

    async def pg_wait(self, pg_id, timeout) -> bool:
        return await self._svc.pg_wait(pg_id, timeout)

    async def pg_remove(self, pg_id):
        await self._svc.pg_remove(pg_id)

    async def pg_get(self, pg_id):
        return self._svc.pg_get(pg_id)

    async def pg_table(self):
        return self._svc.pg_table()

    async def psub_subscribe(self, subscriber_id, channels):
        self._svc.pubsub.subscribe(subscriber_id, channels)

    async def psub_poll(self, subscriber_id, timeout=30.0,
                        max_events=1000):
        return await self._svc.pubsub.poll(subscriber_id, timeout,
                                           max_events)

    async def psub_publish(self, channel, data, key=None) -> int:
        return self._svc.pubsub.publish(channel, data, key=key)

    async def psub_unsubscribe(self, subscriber_id, channels=None):
        self._svc.pubsub.unsubscribe(subscriber_id, channels)

    async def events_list(self, severity=None, source=None, limit=1000):
        stats = self._svc.events.stats()
        return {
            "events": self._svc.events.list(
                severity=severity, source=source, limit=limit
            ),
            "total": stats["total"],
            "dropped": stats["dropped"],
        }

    async def timeseries_query(self, name="", tags=None, since=0.0,
                               limit=0, quantile=0.0, window=60.0):
        return await self._svc._rpc_timeseries_query(
            None, name=name, tags=tags, since=since, limit=limit,
            quantile=quantile, window=window
        )

    async def slo_status(self):
        return await self._svc._rpc_slo_status(None)

    async def drain_node(self, node_id, phase="full", timeout=60.0):
        return await self._svc._rpc_drain_node(
            None, node_id, phase=phase, timeout=timeout
        )

    async def chaos_arm(self, specs):
        return await self._svc._rpc_chaos_arm(None, specs)

    async def chaos_disarm(self):
        return await self._svc._rpc_chaos_disarm(None)

    async def chaos_list(self):
        return await self._svc._rpc_chaos_list(None)

    async def stacks_dump(self, timeout=5.0):
        return await self._svc._rpc_stacks_dump(None, timeout=timeout)

    async def profile_run(self, seconds=2.0, hz=100):
        return await self._svc._rpc_profile_run(
            None, seconds=seconds, hz=hz
        )

    async def traces_dump(self, reason="", limit=200):
        return await self._svc._rpc_traces_dump(
            None, reason=reason, limit=limit
        )

    async def objects_census(self, limit=500):
        return await self._svc._rpc_objects_census(None, limit=limit)

    async def rpc_describe(self):
        return self._svc._rpc.describe()


class RemoteGcsHandle:
    """Remote node manager's view of the GCS over its client connection."""

    def __init__(self, client: GcsClient):
        self._client = client

    async def kv_put(self, key, value, overwrite=True) -> bool:
        r = await self._client.request(
            {"op": "kv_put", "key": key, "value": value, "overwrite": overwrite}
        )
        return r["added"]

    async def kv_get(self, key, wait_timeout: float = 0):
        r = await self._client.request(
            {"op": "kv_get", "key": key, "wait_timeout": wait_timeout},
            timeout=max(30.0, wait_timeout + 10.0),
        )
        return r["value"]

    async def kv_del(self, key) -> bool:
        return (await self._client.request({"op": "kv_del", "key": key}))["deleted"]

    async def kv_keys(self, prefix=""):
        return (await self._client.request({"op": "kv_keys", "prefix": prefix}))[
            "keys"
        ]

    async def register_function(self, function_id, blob):
        await self._client.request(
            {"op": "register_function", "function_id": function_id, "blob": blob}
        )

    async def fetch_function(self, function_id):
        r = await self._client.request(
            {"op": "fetch_function", "function_id": function_id}
        )
        return r["blob"]

    async def register_named_actor(self, name, actor_id, node_id, spec) -> bool:
        r = await self._client.request(
            {
                "op": "register_named_actor",
                "name": name,
                "actor_id": actor_id.hex(),
                "node_id": node_id.hex(),
                "spec": spec,
            }
        )
        return r["added"]

    async def get_named_actor(self, name):
        r = await self._client.request({"op": "get_named_actor", "name": name})
        if not r["found"]:
            return None
        return (
            ActorID.from_hex(r["actor_id"]),
            NodeID.from_hex(r["node_id"]),
            r["spec"],
        )

    async def drop_named_actor(self, name, actor_id):
        await self._client.notify(
            {"op": "drop_named_actor", "name": name, "actor_id": actor_id.hex(),
             "msg_id": None}
        )

    async def register_actor_node(self, actor_id, node_id,
                                  incarnation: int = 0) -> int:
        r = await self._client.request(
            {"op": "register_actor_node", "actor_id": actor_id.hex(),
             "node_id": node_id.hex(), "incarnation": incarnation}
        )
        return int(r.get("incarnation") or 0)

    async def get_actor_node(self, actor_id):
        r = await self._client.request(
            {"op": "get_actor_node", "actor_id": actor_id.hex()}
        )
        return NodeID.from_hex(r["node_id"]) if r["node_id"] else None

    async def publish_object(self, object_id, node_id):
        await self._client.notify(
            {"op": "publish_object", "object_id": object_id, "msg_id": None}
        )

    async def unpublish_object(self, object_id, node_id=None):
        # The server attributes the removal to this connection's node.
        await self._client.notify(
            {"op": "unpublish_object", "object_id": object_id, "msg_id": None}
        )

    async def locate_object(self, object_id, timeout=0):
        r = await self._client.request(
            {"op": "locate_object", "object_id": object_id, "timeout": timeout},
            timeout=max(30.0, timeout + 10.0),
        )
        return NodeID.from_hex(r["node_id"]) if r["node_id"] else None

    async def pg_create(self, pg_id, bundles, strategy, name="",
                        label_selectors=None):
        await self._client.request(
            {"op": "pg_create", "pg_id": pg_id, "bundles": bundles,
             "strategy": strategy, "name": name,
             "label_selectors": label_selectors}
        )

    async def pg_wait(self, pg_id, timeout) -> bool:
        r = await self._client.request(
            {"op": "pg_wait", "pg_id": pg_id, "timeout": timeout},
            timeout=timeout + 15.0,
        )
        return r["ready"]

    async def pg_remove(self, pg_id):
        await self._client.request({"op": "pg_remove", "pg_id": pg_id})

    async def pg_get(self, pg_id):
        return await self._client.request({"op": "pg_get", "pg_id": pg_id})

    async def pg_table(self):
        return (await self._client.request({"op": "pg_table"}))["table"]

    async def psub_subscribe(self, subscriber_id, channels):
        await self._client.request(
            {"op": "psub_subscribe", "subscriber_id": subscriber_id,
             "channels": list(channels)}
        )

    async def psub_poll(self, subscriber_id, timeout=30.0,
                        max_events=1000):
        r = await self._client.request(
            {"op": "psub_poll", "subscriber_id": subscriber_id,
             "timeout": timeout, "max_events": max_events},
            timeout=timeout + 15.0,
        )
        return {"events": r["events"], "dropped": r["dropped"]}

    async def psub_publish(self, channel, data, key=None) -> int:
        r = await self._client.request(
            {"op": "psub_publish", "channel": channel, "data": data,
             "key": key}
        )
        return r["seq"]

    async def psub_unsubscribe(self, subscriber_id, channels=None):
        await self._client.notify(
            {"op": "psub_unsubscribe", "subscriber_id": subscriber_id,
             "channels": channels, "msg_id": None}
        )

    async def events_list(self, severity=None, source=None, limit=1000):
        msg = {"op": "events_list", "limit": limit}
        # Optional str fields must be absent, not None, to pass the
        # request schema's type check.
        if severity is not None:
            msg["severity"] = severity
        if source is not None:
            msg["source"] = source
        r = await self._client.request(msg)
        return {"events": r["events"], "total": r["total"],
                "dropped": r["dropped"]}

    async def timeseries_query(self, name="", tags=None, since=0.0,
                               limit=0, quantile=0.0, window=60.0):
        msg = {"op": "timeseries_query", "name": name, "since": since,
               "limit": limit, "quantile": quantile, "window": window}
        # Optional dict field must be absent, not None, to pass the
        # request schema's type check.
        if tags is not None:
            msg["tags"] = tags
        r = await self._client.request(msg)
        out = {"series": r["series"], "names": r["names"],
               "stats": r["stats"]}
        if r.get("derived") is not None:
            out["derived"] = r["derived"]
        return out

    async def slo_status(self):
        r = await self._client.request({"op": "slo_status"})
        return {"deployments": r["deployments"], "ts": r["ts"]}

    async def drain_node(self, node_id, phase="full", timeout=60.0):
        r = await self._client.request(
            {"op": "drain_node", "node_id": node_id, "phase": phase,
             "timeout": timeout},
            timeout=timeout + 30.0,
        )
        return {"ok": r["ok"], "error": r["error"],
                "replicated": r.get("replicated", 0),
                "leftover_actors": r.get("leftover_actors", 0)}

    async def chaos_arm(self, specs):
        return {"gen": (await self._client.request(
            {"op": "chaos_arm", "specs": list(specs)}
        ))["gen"]}

    async def chaos_disarm(self):
        return {"gen": (await self._client.request(
            {"op": "chaos_disarm"}
        ))["gen"]}

    async def chaos_list(self):
        r = await self._client.request({"op": "chaos_list"})
        return {"specs": r["specs"], "gen": r["gen"]}

    async def stacks_dump(self, timeout=5.0):
        r = await self._client.request(
            {"op": "stacks_dump", "timeout": timeout},
            timeout=timeout + 15.0,
        )
        return {"nodes": r["nodes"], "errors": r["errors"]}

    async def profile_run(self, seconds=2.0, hz=100):
        r = await self._client.request(
            {"op": "profile_run", "seconds": seconds, "hz": hz},
            timeout=seconds + 30.0,
        )
        return {"nodes": r["nodes"], "errors": r["errors"]}

    async def traces_dump(self, reason="", limit=200):
        r = await self._client.request(
            {"op": "traces_dump", "reason": reason, "limit": limit},
            timeout=30.0,
        )
        return {"nodes": r["nodes"], "errors": r["errors"]}

    async def objects_census(self, limit=500):
        r = await self._client.request(
            {"op": "objects_census", "limit": limit},
            timeout=30.0,
        )
        return {"nodes": r["nodes"], "errors": r["errors"]}

    async def rpc_describe(self):
        return (await self._client.request({"op": "rpc_describe"}))[
            "services"
        ]
