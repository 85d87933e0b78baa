"""Worker process entry point.

Ref analogue: python/ray/_private/workers/default_worker.py + the task
execution loop in _raylet.pyx (run_task_loop / task_execution_handler). A
reader thread demultiplexes the duplex socket: execute requests go to the
main-thread task queue; replies resolve pending runtime requests.
"""

from __future__ import annotations

import os
from collections import deque
import sys
import threading
import time
from typing import List

from . import frame_pump
from .executor import ActorContainer, execute_task
from .function_table import FunctionCache
from .ids import JobID, NodeID, ObjectID, TaskID, WorkerID
from .object_store import InlineLocation, Location
from .protocol import Connection, ConnectionClosed, connect_unix
from .runtime import WorkerRuntime
from .serialization import SerializedObject
from .task_spec import TaskSpec, TaskType
from . import runtime_context


# Completions buffered before a mid-queue flush (see _main_loop).
_DONE_FLUSH_BATCH = 16


class Worker:
    def __init__(self, conn: Connection, worker_id: WorkerID):
        self.conn = conn
        self.worker_id = worker_id
        # Reclaimable task queue (deque + condition instead of
        # queue.Queue): pipelined frames must support removal when the
        # node manager reclaims not-yet-started tasks from a blocked
        # worker (see _reader_loop "reclaim").
        self._tq: "deque" = deque()
        self._tq_cv = threading.Condition()
        self.actor = ActorContainer()
        self.runtime: WorkerRuntime | None = None
        self._alive = True
        # Completed-task messages coalesced while more tasks are queued:
        # one task_done_batch frame = one node-manager wakeup for the
        # whole burst (the contended-host dispatch wall; see node_manager
        # _flush_execute_bufs for the mirror-image direction). Guarded by
        # _done_lock because the runtime's before-blocking hook may flush
        # from an actor pool thread.
        self._done_buf: List[dict] = []
        self._done_lock = threading.Lock()
        # Direct actor-call channels (ref analogue: direct actor task
        # submission, core_worker/transport/direct_actor_task_submitter.h
        # — callers push actor tasks straight to the actor's worker; the
        # control plane only does lifecycle). The listeners start after a
        # successful actor creation: a unix socket for same-node callers
        # AND a TLS-aware TCP endpoint for remote workers/thin clients;
        # frames execute in per-connection sequence order (out-of-order
        # arrivals buffered), replies return inline on the calling
        # connection.
        self._direct_srv = None
        self._direct_tcp_srv = None
        self._direct_path: str | None = None
        self._direct_addr: tuple | None = None
        # Lightweight completion notifications to the node manager for
        # direct executions: the NM's _on_task_done bookkeeping (seals
        # for third-party consumers, task history, telemetry) still
        # fires, one debounced direct_done_batch frame per burst.
        self._nm_done_buf: List[dict] = []
        self._nm_done_lock = threading.Lock()
        self._nm_done_first = 0.0
        self._done_flush_batch = _DONE_FLUSH_BATCH
        self._done_flush_age = 0.05
        # Recently-executed direct task ids -> completion record: an
        # NM-path replay after a channel death (reply lost in flight)
        # returns the recorded completion instead of double-executing
        # actor state (per-handle ordering + exactly-once surface).
        from collections import OrderedDict

        self._direct_seen: "OrderedDict[bytes, dict]" = OrderedDict()
        self._direct_seen_lock = threading.Lock()
        # Per-connection direct reply batches (instance state so the
        # before-blocking hook can flush them: a direct task that blocks
        # on a nested get must not strand earlier replies — and their
        # seals — in a local buffer).
        self._dr_lock = threading.Lock()
        self._dr_bufs: dict = {}  # id(conn) -> (conn, [reply, ...])
        # Serializes actor-task execution between the main loop and
        # direct-connection serve threads (concurrency-1 actors execute
        # direct frames INLINE in the serve thread — one fewer thread
        # handoff per call; the lock preserves the one-task-at-a-time
        # actor invariant).
        self._serial_lock = threading.Lock()
        # Threaded actor concurrency (ref analogue: max_concurrency actors
        # via ConcurrencyGroupManager, core_worker/transport/
        # concurrency_group_manager.h): creation tasks with
        # max_concurrency > 1 switch execution to a thread pool.
        self._pool = None
        # Concurrency groups: {name: ThreadPoolExecutor} — annotated
        # methods run in their group's pool, concurrently with other
        # groups AND with the default path (ref:
        # concurrency_group_manager.h per-group executors).
        self._group_pools: dict = {}

    def start(self):
        self.conn.send({"type": "register", "worker_id": self.worker_id.hex()})
        ack = self.conn.recv()
        assert ack["type"] == "registered", ack
        # Move the node socket's framing onto the native pump (payloads
        # stay pickle, so the asyncio node manager needs no negotiation):
        # buffered GIL-released reads slice an execute_batch burst out of
        # one read(2), sends skip the per-frame concatenation. Falls back
        # to the plain Connection silently (counted) when unavailable.
        wrapped = frame_pump.wrap_connection(self.conn)
        if wrapped is not None:
            self.conn = wrapped
        node_id = NodeID.from_hex(ack["node_id"])
        # Chaos plane: adopt the cluster's armed plan at birth (updates
        # arrive as chaos_update frames on the reader loop).
        from ..util import faults

        faults.set_local_node(node_id.hex())
        chaos = ack.get("chaos") or {}
        faults.apply_plan(chaos.get("specs") or [], chaos.get("gen"))
        self.runtime = WorkerRuntime(
            self.conn,
            job_id=JobID.nil(),
            node_id=node_id,
            worker_id=self.worker_id,
        )
        runtime_context.set_runtime(self.runtime)
        # GIL-contention proxy: workers run user code, so their
        # ray_tpu_gil_wait_ratio{pid} series is where a CPU-bound task
        # holding the GIL shows up.
        from ..util import profiler

        profiler.start_gil_monitor()
        # Flush buffered dones before any blocking runtime request: a
        # nested get could otherwise wait on an object whose seal is
        # sitting in our own outbound buffer (deadlock).
        self.runtime.before_block = self._flush_before_block
        reader = threading.Thread(target=self._reader_loop, daemon=True)
        reader.start()
        self._main_loop()

    def _apply_runtime_env(self, meta_key: str):
        """Apply the env a task's spec references (job-scoped key, so
        concurrent jobs don't cross-contaminate; "" = task has no env =
        zero overhead). Idempotent per key."""
        if not meta_key or getattr(self, "_renv_key", None) == meta_key:
            return
        try:
            from . import runtime_env as renv

            if renv.apply_in_worker(
                self.runtime.kv_get,
                os.environ.get("RAY_TPU_SESSION_DIR", "."),
                meta_key,
            ):
                self._renv_key = meta_key
                # Nested submissions from this task carry the same env.
                self.runtime.runtime_env_key = meta_key
        except Exception as e:  # noqa: BLE001 — env failure must be loud
            self._renv_key = meta_key  # don't loop a broken env per task
            print(f"ray_tpu worker: runtime_env setup failed: {e!r}",
                  file=sys.stderr)

    def _tq_put(self, msg):
        with self._tq_cv:
            self._tq.append(msg)
            self._tq_cv.notify()

    def _tq_get(self):
        with self._tq_cv:
            while not self._tq:
                self._tq_cv.wait()
            return self._tq.popleft()

    def _reader_loop(self):
        try:
            while self._alive:
                msg = self.conn.recv()
                mtype = msg["type"]
                if mtype == "execute":
                    if not self._route_group(msg):
                        self._tq_put(msg)
                elif mtype == "execute_batch":
                    rest = [m for m in msg["items"]
                            if not self._route_group(m)]
                    if rest:
                        with self._tq_cv:
                            self._tq.extend(rest)
                            self._tq_cv.notify()
                elif mtype == "reply":
                    self.runtime.handle_reply(msg)
                elif mtype == "reclaim":
                    # Hand back pipelined tasks that have NOT started (the
                    # main thread is blocked or busy): the node manager
                    # redispatches exactly the ids we confirm.
                    wanted = set(msg["task_ids"])
                    removed = []
                    with self._tq_cv:
                        kept = deque()
                        for m in self._tq:
                            spec = m.get("spec") if m else None
                            if spec is not None and spec.task_id in wanted:
                                removed.append(spec.task_id)
                            else:
                                kept.append(m)
                        self._tq.clear()
                        self._tq.extend(kept)
                    self.conn.send(
                        {"type": "reclaimed", "task_ids": removed}
                    )
                elif mtype == "stack_dump":
                    # Answered HERE, on the reader thread: the whole
                    # point is seeing what the (possibly wedged) main
                    # thread is doing right now — queueing the request
                    # behind it would deadlock the diagnosis.
                    self._reply_stack_dump(msg)
                elif mtype == "profile":
                    # Timed sampling must not stall the reader loop for
                    # its full duration (replies/reclaims keep flowing);
                    # a dedicated thread samples and ships the result.
                    threading.Thread(
                        target=self._profile_and_reply, args=(msg,),
                        name="ray_tpu-profile", daemon=True,
                    ).start()
                elif mtype == "chaos_update":
                    from ..util import faults

                    faults.apply_plan(msg.get("specs") or [],
                                      msg.get("gen"))
                elif mtype == "node_fenced":
                    # Membership fence: the GCS declared a node dead at
                    # an epoch. Our runtime may hold healthy direct
                    # channels to actors on it (asymmetric partition) —
                    # tear them down so in-flight calls park into the
                    # exactly-once NM replay path instead of executing
                    # on the fenced incarnation.
                    try:
                        self.runtime.fence_node(
                            msg.get("node_id") or "",
                            int(msg.get("epoch") or 0),
                        )
                    except Exception as e:  # noqa: BLE001
                        print(
                            f"ray_tpu worker: fence teardown failed "
                            f"({e!r}); channels die on next use",
                            file=sys.stderr,
                        )
                elif mtype == "node_draining":
                    # This worker's host is surrendering: raise the
                    # cooperative preemption signal long-running code
                    # (TrainSession.preemption) polls at safe points.
                    from . import preemption

                    preemption.signal_local_drain(
                        msg.get("node_id") or ""
                    )
                elif mtype == "node_undrain":
                    from . import preemption

                    preemption.clear_local_drain()
                elif mtype == "kill":
                    self._alive = False
                    self._tq_put(None)
                    break
        except (ConnectionClosed, OSError):
            self._alive = False
            self._tq_put(None)

    def _reply_stack_dump(self, msg):
        from ..util import profiler

        try:
            threads = profiler.dump_stacks()
        # Diagnosis must not kill us: an empty reply IS the signal the
        # NM-side merge shows for a sampler that failed here.
        except Exception:  # rtlint: disable=swallowed-failure
            threads = []
        try:
            self.conn.send({
                "type": "stack_reply",
                "req_id": msg.get("req_id"),
                "pid": os.getpid(),
                "worker_id": self.worker_id.hex(),
                "threads": threads,
            })
        # Reply to a dying node socket: the NM treats the missing reply
        # as missing_workers (partial diagnosis, not a hang).
        except Exception:  # rtlint: disable=swallowed-failure
            pass

    def _profile_and_reply(self, msg):
        from ..util import profiler

        try:
            prof = profiler.sample(
                msg.get("seconds", 2.0), msg.get("hz", 100)
            )
        # Same diagnostics contract: a zero-sample reply marks this
        # worker's sampler as failed in the cluster-wide merge.
        except Exception:  # rtlint: disable=swallowed-failure
            prof = {"counts": {}, "samples": 0}
        try:
            self.conn.send({
                "type": "profile_reply",
                "req_id": msg.get("req_id"),
                "pid": os.getpid(),
                "worker_id": self.worker_id.hex(),
                "counts": prof.get("counts", {}),
                "samples": prof.get("samples", 0),
            })
        # Same contract as the stack reply: a dead conn degrades the
        # fan-out to a partial profile, never an error loop here.
        except Exception:  # rtlint: disable=swallowed-failure
            pass

    def _route_group(self, m) -> bool:
        """Reader-thread routing for concurrency-group methods: they
        must reach their group's pool WITHOUT queueing behind whatever
        the main thread is executing (that's the whole point of groups).
        Returns True when the frame was dispatched to a group pool."""
        spec = m.get("spec") if isinstance(m, dict) else None
        if (
            spec is None
            or spec.task_type != TaskType.ACTOR_TASK
            or not self._group_pools
        ):
            return False
        gp = self._group_pools.get(getattr(spec, "concurrency_group", ""))
        if gp is None:
            return False
        gp.submit(self._run_task_direct, spec, m.get("function_blob"))
        return True

    def _main_loop(self):
        while self._alive:
            msg = self._tq_get()
            if msg is None:
                break
            spec = msg["spec"]
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                concurrency = spec.max_concurrency
                if concurrency <= 1:
                    # Async actor classes default to high concurrency
                    # (ref: async actors' max_concurrency=1000 default) —
                    # awaiting calls park on the actor's event loop.
                    try:
                        fn_blob = msg.get("function_blob")
                        cache = self.runtime.function_cache
                        if fn_blob is not None:
                            cache.add_blob(spec.function_id, fn_blob)
                        if cache.has(spec.function_id):
                            cls = cache.load(spec.function_id)
                            if ActorContainer.class_is_async(cls):
                                concurrency = 100
                    except Exception as e:  # noqa: BLE001
                        # A failed async-class probe silently pins the
                        # actor to serial execution — worth a breadcrumb.
                        print(
                            f"ray_tpu worker: async-actor detection "
                            f"failed ({e!r}); actor runs serial",
                            file=sys.stderr,
                        )
                if concurrency > 1 or getattr(
                        spec, "allow_out_of_order", False):
                    from concurrent.futures import ThreadPoolExecutor

                    # Out-of-order actors keep their max_concurrency
                    # thread count (1 stays serial — only ORDER
                    # commitment is relaxed, matching the reference's
                    # out_of_order_actor_submit_queue semantics; true
                    # parallelism still requires max_concurrency > 1).
                    self._pool = ThreadPoolExecutor(
                        max_workers=max(1, concurrency),
                        thread_name_prefix="actor-concurrency",
                    )
            if spec.task_type == TaskType.ACTOR_TASK:
                gp = self._group_pools.get(
                    getattr(spec, "concurrency_group", "")
                )
                if gp is not None:
                    gp.submit(
                        self._run_task_direct, spec,
                        msg.get("function_blob"),
                    )
                    continue
                if self._pool is not None:
                    self._pool.submit(
                        self._run_task_direct, spec,
                        msg.get("function_blob"),
                    )
                    continue
            with self._serial_lock:
                done = self._run_task(spec, msg.get("function_blob"),
                                      to_nm=True)
            if (
                spec.task_type == TaskType.ACTOR_CREATION_TASK
                and not done.get("failed")
                and self._direct_srv is None
            ):
                # Group pools install only AFTER __init__ succeeded: a
                # group frame routed earlier would execute against an
                # actor instance that does not exist yet.
                if getattr(spec, "concurrency_groups", None):
                    from concurrent.futures import ThreadPoolExecutor

                    self._group_pools = {
                        name: ThreadPoolExecutor(
                            max_workers=max(1, int(n)),
                            thread_name_prefix=f"cg-{name}",
                        )
                        for name, n in spec.concurrency_groups.items()
                    }
                self._start_direct_listener(
                    spec.actor_id,
                    getattr(spec, "actor_incarnation", 0),
                )
            with self._done_lock:
                self._done_buf.append(done)
                pending_dones = len(self._done_buf)
            with self._tq_cv:
                more = bool(self._tq)
            # Flush every few completions so the node manager refills our
            # queue while we chew through the rest, and always when the
            # queue drains. The constant is deliberately independent of
            # the node manager's worker_pipeline_depth config (workers
            # don't see it).
            if not more or pending_dones >= _DONE_FLUSH_BATCH:
                self._flush_dones()
        # Flush refcounts + user metrics before exit (os._exit skips
        # atexit, and the head's accounting must stay sane).
        self._flush_dones()
        try:
            self.runtime.refs.flush()
        except Exception as e:  # noqa: BLE001
            print(f"ray_tpu worker: exit refcount flush failed ({e!r}); "
                  f"head-side release relies on worker-death cleanup",
                  file=sys.stderr)
        try:
            from ..util.metrics import _registry

            _registry.flush()
        except Exception as e:  # noqa: BLE001
            print(f"ray_tpu worker: exit metrics flush failed ({e!r})",
                  file=sys.stderr)
        os._exit(0)

    def _start_direct_listener(self, actor_id, incarnation: int = 0):
        """Listen for direct caller connections and advertise the
        endpoints to the node manager: one UDS beside the node socket
        for same-node callers, plus a TLS-aware TCP endpoint so remote
        workers and thin clients ride the same plane. The NM hands the
        descriptor to callers through get_actor_direct."""
        import socket as _socket

        from .config import get_config
        from .protocol import DIRECT_PROTO_VER

        cfg = get_config()
        self._done_flush_batch = max(1, int(cfg.direct_done_flush_batch))
        self._done_flush_age = max(0.001, cfg.direct_done_flush_ms / 1e3)
        self._direct_actor_id = actor_id.hex() if actor_id else None
        # GCS-assigned incarnation of THIS start of the actor (stamped
        # on the creation spec by the home NM): hellos naming any other
        # incarnation are refused — split-brain fencing's guarantee
        # that a stale resolution can never execute here.
        self._direct_incarnation = int(incarnation or 0)
        base = os.environ.get("RAY_TPU_NODE_SOCKET", "/tmp/rtpu")
        path = f"{base}.d{os.getpid()}"
        try:
            os.unlink(path)
        except OSError:
            pass
        srv = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
        try:
            srv.bind(path)
            srv.listen(64)
        except OSError:
            return  # no direct path; callers fall back to the NM route
        self._direct_srv = srv
        self._direct_path = path
        threading.Thread(
            target=self._direct_accept_loop, args=(srv, False), daemon=True
        ).start()
        # TCP endpoint (best effort — the UDS plane works without it).
        host = os.environ.get("RAY_TPU_NODE_IP", "127.0.0.1")
        try:
            tcp = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
            tcp.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            tcp.bind((host, 0))
            tcp.listen(64)
            self._direct_tcp_srv = tcp
            self._direct_addr = (host, tcp.getsockname()[1])
            threading.Thread(
                target=self._direct_accept_loop, args=(tcp, True),
                daemon=True,
            ).start()
        except OSError:
            self._direct_addr = None
        threading.Thread(
            target=self._nm_done_ticker, daemon=True
        ).start()
        self.conn.send({
            "type": "actor_direct", "path": path,
            "addr": self._direct_addr, "ver": DIRECT_PROTO_VER,
        })

    def _direct_accept_loop(self, srv, tls: bool):
        while self._alive:
            try:
                sock, _ = srv.accept()
            except OSError:
                return
            threading.Thread(
                target=self._direct_conn_entry, args=(sock, tls),
                daemon=True,
            ).start()

    def _direct_conn_entry(self, sock, tls: bool):
        from .protocol import Connection as _Conn

        try:
            if tls:
                # TLS wrap (and its handshake) on the CONNECTION thread:
                # a caller stalling mid-handshake must not block accepts.
                from .tls import server_ssl_context

                ctx = server_ssl_context()
                if ctx is not None:
                    sock.settimeout(30.0)
                    sock = ctx.wrap_socket(sock, server_side=True)
                    sock.settimeout(None)
            conn = _Conn(sock)
        except (OSError, ValueError):
            try:
                sock.close()
            except OSError:
                pass
            return
        self._direct_serve(conn, tls=tls)

    def _direct_serve(self, conn, tls: bool = False):
        """One caller connection: frames execute in SEQUENCE order ("q",
        per-handle monotonic) — INLINE in this thread for concurrency-1
        actors (under the serial lock), via the pool for concurrent
        actors. Out-of-order arrivals are buffered until the gap fills;
        frames below the expected sequence are duplicates of calls that
        already executed and are dropped. Replies batch while a frame
        batch is being chewed through. A fence frame acks once every
        earlier frame from this connection has executed — callers use it
        to order a control-plane-routed call after direct ones.

        The connection opens with a direct_hello/direct_welcome
        handshake carrying the session token, the protocol version (a
        mismatch is refused — the caller falls back to the NM route) and
        the caller's node id (non-inline results for remote callers get
        a refcount hold at this node until the caller's RemoteLocation
        entry is collected).

        Frames come in two shapes: full ({"spec", "function_blob"},
        optionally registering a template via "tmpl_reg") and compact
        ({"t": template id, "i": task id bytes, "a": (args, kwargs),
        "n": nested refs}) — the caller ships each (method, group)
        shape's spec once and then ~60-byte frames (see
        _DirectChannel.submit)."""
        import copy as _copy

        from .config import get_config
        from .protocol import DIRECT_PROTO_VER

        try:
            # Bounded: a caller that connected but never says hello must
            # not pin this connection thread forever.
            conn.settimeout(30.0)
            hello = conn.recv()
            conn.settimeout(None)
        except (ConnectionClosed, OSError):
            return
        if hello.get("type") != "direct_hello":
            conn.close()
            return
        token = get_config().session_token
        if token and hello.get("token") != token:
            try:
                conn.send({"type": "direct_welcome", "ok": False,
                           "error": "bad session token"})
            # Refusal to a conn that died first: same outcome (no
            # direct channel), caller stays on the NM route.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
            conn.close()
            return
        if hello.get("ver") != DIRECT_PROTO_VER:
            try:
                conn.send({
                    "type": "direct_welcome", "ok": False,
                    "error": f"direct protocol version mismatch "
                             f"(worker v{DIRECT_PROTO_VER})",
                })
            # As above: a lost refusal just leaves the caller on the
            # NM fallback route.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
            conn.close()
            return
        want = hello.get("actor_id")
        if want is not None and want != getattr(
                self, "_direct_actor_id", None):
            # Stale endpoint: the caller resolved a descriptor whose
            # pid/port has been recycled by a worker hosting a DIFFERENT
            # actor. Refuse so the caller falls back to the NM route and
            # re-resolves — silently accepting would execute methods
            # against the wrong actor's state.
            try:
                conn.send({"type": "direct_welcome", "ok": False,
                           "error": "actor mismatch (stale endpoint)"})
            # Lost refusal == refused: the caller times out and
            # re-resolves through the NM either way.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
            conn.close()
            return
        want_inc = hello.get("inc")
        my_inc = getattr(self, "_direct_incarnation", 0)
        if want_inc and my_inc and int(want_inc) != my_inc:
            # Incarnation fencing: the caller resolved an EARLIER (or,
            # under a split brain, a later) start of this actor — its
            # per-handle sequences and replay-dedup assumptions belong
            # to a different incarnation's state. Refuse; the caller
            # invalidates its endpoint cache and re-resolves through
            # the NM, exactly like the stale-pid refusal above.
            from . import fencing as _fencing

            _fencing.REFUSED_HELLO.inc()
            try:
                conn.send({
                    "type": "direct_welcome", "ok": False,
                    "error": f"incarnation mismatch (caller resolved "
                             f"{want_inc}, actor is {my_inc})",
                })
            # Lost refusal == refused, as above.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
            conn.close()
            return
        node_hex = self.runtime.node_id.hex() if self.runtime else None
        remote = hello.get("node") not in (None, node_hex)
        # Native frame-pump negotiation: agree only when the caller
        # advertised our codec version AND the pump can engage here.
        # The magic-byte sniff in loads_msg keeps a half-engaged channel
        # correct either way — npv only gates who EMITS native frames.
        from .rpc import negotiate_codec

        agreed_npv = 0 if tls else negotiate_codec(
            hello.get("npv"), frame_pump.advertised_ver()
        )
        want_native = bool(agreed_npv)
        try:
            # Echo the AGREED version (min of the two offers), not our
            # own: a v2 worker facing a v1 caller replies npv=1 so both
            # sides emit v1 frames — the caller's trace block (v2) never
            # reaches a decoder that cannot read it.
            conn.send({"type": "direct_welcome", "ok": True,
                       "ver": DIRECT_PROTO_VER,
                       "npv": agreed_npv,
                       "inc": getattr(self, "_direct_incarnation", 0)})
        # Caller hung up before the welcome: nothing to serve; its
        # submit path falls back to the NM route and retries.
        except Exception:  # rtlint: disable=swallowed-failure
            return
        if want_native:
            wrapped = frame_pump.wrap_connection(conn)
            if wrapped is not None:
                conn = wrapped

        group_futs: list = []
        templates: dict = {}  # per-connection template id -> TaskSpec
        # Per-channel monotonic-seq dispatch: in-order admission,
        # out-of-order parking, replay-duplicate drop — in the extension
        # when available (frames execute without re-entering Python for
        # the bookkeeping), PySeqQueue otherwise.
        seqq = frame_pump.new_seq_queue()

        def decode(m):
            tid = m.get("t")
            if tid is None:
                spec = m["spec"]
                reg = m.get("tmpl_reg")
                if reg is not None:
                    templates[reg] = spec
                return spec, m.get("function_blob")
            tmpl = templates[tid]
            spec = _copy.copy(tmpl)
            spec.task_id = TaskID(m["i"])
            a = m.get("a")
            if a is not None:
                spec.args, spec.kwargs = a
            else:
                spec.args, spec.kwargs = [], {}
            spec.nested_refs = m.get("n", ())
            # Codec v2 / compact-dict frames carry the caller's trace
            # context as "tc"; without it the span derives from the new
            # task id (a fresh root — exactly the severed-tree bug this
            # field exists to prevent).
            tc = m.get("tc")
            spec.trace_ctx = tuple(tc) if tc else None
            # Always reset: the template was copied from the FIRST call
            # of this shape and carries that call's deadline.
            spec.deadline_ts = m.get("d", 0.0)
            return spec, None

        def in_seq_order(items):
            """Admit frames in sequence order through the dispatch
            queue; out-of-order arrivals park, duplicates (seq below
            expected = already executed) drop."""
            run = []
            for m in items:
                q = m.get("q")
                if q is None:
                    run.append(m)
                else:
                    run.extend(seqq.push(q, m))
            return run

        # Native channels deliver a pipelined burst as individual frames
        # (the caller coalesces them into one writev, not one batch
        # message): drain every COMPLETE frame already buffered BEFORE
        # executing, so an arrived-together burst processes — and
        # answers — as one batch, while a frame arriving mid-execution
        # can never defer an already-finished call's reply behind its
        # own (possibly long) execution. recv_many does the whole drain
        # in ONE interpreter entry (first read blocks GIL-released,
        # buffered frames slice out in C) — the worker-side half of the
        # ISSUE 12 GIL-handoff cut.
        if getattr(conn, "native", False):
            from .protocol import loads_msg as _loads

            def recv_batch():
                return [_loads(p) for p in conn.recv_many()]
        else:
            def recv_batch():
                return [conn.recv()]

        def ack_fence(msg_id):
            # The ack promises every earlier frame on this connection
            # has EXECUTED — including frames handed to group pools OR
            # the shared concurrency pool, both of which run
            # asynchronously.
            for f in group_futs:
                try:
                    f.result(timeout=60)
                # The task's own failure already shipped in its reply
                # frame; the fence only needs "finished", not "ok".
                except Exception:  # rtlint: disable=swallowed-failure
                    pass
            group_futs.clear()
            self._flush_direct_replies(conn)
            if getattr(conn, "native", False):
                conn.send_payloads([frame_pump.encode_fence_ack(msg_id)])
            else:
                conn.send({"type": "fence_ack", "msg_id": msg_id})

        try:
            while self._alive:
                items: list = []
                fences: list = []
                for msg in recv_batch():
                    mtype = msg.get("type")
                    if mtype == "execute":
                        items.append(msg)
                    elif mtype == "execute_batch":
                        items.extend(msg["items"])
                    elif mtype == "fence":
                        # Acked after this gather executes: the frames
                        # collected before it are exactly its "earlier"
                        # frames (later ones executing too only makes
                        # the promise stronger).
                        fences.append(msg.get("msg_id"))
                if items:
                    if seqq.parked > 4096:
                        return  # runaway gap: drop the connection
                    if len(group_futs) > 4096:
                        group_futs = [f for f in group_futs if not f.done()]
                    # Frame-arrival stamp: execution start minus this is
                    # the call's queue wait (seq parking + pool queueing),
                    # recorded as its own span beside the execution span.
                    recv_ts = time.time()
                    routed = []
                    for m in in_seq_order(items):
                        spec, blob = decode(m)
                        gp = self._group_pools.get(
                            getattr(spec, "concurrency_group", "")
                        )
                        if gp is not None:
                            group_futs.append(gp.submit(
                                self._run_direct, conn, spec, blob, remote,
                                recv_ts,
                            ))
                        else:
                            routed.append((spec, blob))
                    if self._pool is not None:
                        for spec, blob in routed:
                            group_futs.append(self._pool.submit(
                                self._run_direct, conn, spec, blob, remote,
                                recv_ts,
                            ))
                    else:
                        for spec, blob in routed:
                            with self._serial_lock:
                                done = self._run_task(
                                    spec, blob, sample_resources=False,
                                    queued_ts=recv_ts,
                                    caller=(conn, remote))
                            self._note_direct_done(done, spec, remote)
                            with self._dr_lock:
                                _, buf = self._dr_bufs.setdefault(
                                    id(conn), (conn, [])
                                )
                                buf.append(done)
                                n = len(buf)
                            if n >= _DONE_FLUSH_BATCH:
                                self._flush_direct_replies(conn)
                        self._flush_direct_replies(conn)
                for msg_id in fences:
                    ack_fence(msg_id)
        except (ConnectionClosed, OSError):
            pass

    def _flush_direct_replies(self, conn=None):
        with self._dr_lock:
            if conn is not None:
                entries = [self._dr_bufs.pop(id(conn), None)]
            else:
                entries = list(self._dr_bufs.values())
                self._dr_bufs.clear()
        for entry in entries:
            if not entry:
                continue
            c, replies = entry
            if not replies:
                continue
            try:
                self._send_replies(c, replies)
            # Dead direct channel: the caller detects the death and
            # replays unanswered calls over the NM route (exactly-once
            # via the replay-dedup cache) — the reply is not lost.
            except Exception:  # rtlint: disable=swallowed-failure
                pass

    def _send_replies(self, c, replies):
        """Ship a reply burst: the native codec (one bytes frame, no
        pickle) when the channel is on the pump and every reply has the
        compact shape; the pickle dialect otherwise."""
        if getattr(c, "native", False):
            payload = (
                frame_pump.encode_done(replies[0]) if len(replies) == 1
                else frame_pump.encode_done_batch(replies)
            )
            if payload is not None:
                c.send_payloads([payload])
                return
        if len(replies) == 1:
            c.send(replies[0])
        else:
            c.send({"type": "task_done_batch", "items": replies})

    def _flush_before_block(self):
        """Runtime before-blocking hook: ship every buffered completion
        (NM dones, direct replies AND direct completion notifications)
        plus pending ref deltas before waiting on the node manager — a
        nested get must never wait on a seal stranded in our own
        outbound buffers, and the NM's borrow logic needs our +1s
        applied before it resolves the read."""
        self._flush_dones()
        self._flush_direct_replies()
        self._flush_nm_dones(force=True)
        try:
            self.runtime.refs.flush()
        except Exception as e:  # noqa: BLE001
            print(f"ray_tpu worker: pre-block refcount flush failed "
                  f"({e!r}); a borrowed-object release may be delayed",
                  file=sys.stderr)

    def _run_direct(self, conn, spec, function_blob, remote=False,
                    queued_ts: float = 0.0):
        done = self._run_task(spec, function_blob, sample_resources=False,
                              queued_ts=queued_ts, caller=(conn, remote))
        self._note_direct_done(done, spec, remote)
        try:
            self._send_replies(conn, [done])
        # Same NM-replay contract as the batched reply path above.
        except Exception:  # rtlint: disable=swallowed-failure
            pass

    def _note_direct_done(self, done: dict, spec, remote: bool):
        """Queue the lightweight completion notification the node
        manager needs for its _on_task_done bookkeeping (seals for
        third-party consumers, duration telemetry, task history) —
        debounced into direct_done_batch frames so a call burst costs
        one NM wakeup, not one per completion. Also records the
        completion for NM-path replay dedup (see _run_task)."""
        if done.get("duplicate"):
            return  # dedup-cache hit: already noted by the original run
        tid = done["task_id"].binary()
        with self._direct_seen_lock:
            self._direct_seen[tid] = done
            # Invariant: the cache must cover every call a failing
            # channel could replay. Callers cap unanswered calls per
            # channel at DIRECT_MAX_UNANSWERED (protocol.py), so 8192
            # covers several simultaneously-failing callers before an
            # eviction could surface as a double execution.
            while len(self._direct_seen) > 8192:
                self._direct_seen.popitem(last=False)
        item = {
            "task_id": done["task_id"],
            "results": done["results"],
            "failed": done.get("failed", False),
            "duration_s": done.get("duration_s"),
            "name": spec.name or spec.method_name or "task",
            "actor_id": spec.actor_id.hex() if spec.actor_id else None,
        }
        if done.get("failed"):
            item["error_type"] = done.get("error_type")
            item["error_message"] = done.get("error_message")
        if remote:
            # Non-inline results leave on the caller's RemoteLocation
            # entry; the NM holds them until the caller frees its copy.
            item["held"] = True
        # Ride the worker's pending ref deltas with the notification
        # (same carrier discipline as NM-path task_done frames).
        deltas = self.runtime.refs.drain()
        if deltas:
            item["ref_deltas"] = deltas
        with self._nm_done_lock:
            if not self._nm_done_buf:
                self._nm_done_first = time.monotonic()
            self._nm_done_buf.append(item)
            n = len(self._nm_done_buf)
        if remote or n >= self._done_flush_batch:
            # Remote callers pull non-inline results the moment the
            # reply lands: their seal (and hold) must reach our NM
            # BEFORE the reply can trigger the pull, so remote
            # completions flush eagerly instead of debouncing.
            self._flush_nm_dones(force=True)

    def _note_stream_seal(self, oid, loc, nested=None, refs: int = 1,
                          held: bool = False):
        """Queue the seal of a streamed item that went to its consumer
        on the direct channel: what the node manager needs of it (the
        entry a third party resolves, and ``refs``, the item's one pin,
        or for a caller on another node the hold), in the debounced
        direct_done_batch: one frame for a burst of items, off the
        item's path. ``held`` items flush at once: the caller pulls the
        bytes the moment the item's frame lands."""
        item = {"stream_item": (oid, loc), "refs": refs}
        if nested:
            item["nested"] = nested
        if held:
            item["held"] = True
        with self._nm_done_lock:
            if not self._nm_done_buf:
                self._nm_done_first = time.monotonic()
            self._nm_done_buf.append(item)
            n = len(self._nm_done_buf)
        if held or n >= self._done_flush_batch:
            self._flush_nm_dones(force=True)

    def _flush_nm_dones(self, force: bool = False):
        with self._nm_done_lock:
            n = len(self._nm_done_buf)
            if not n:
                return
            if (not force
                    and n < self._done_flush_batch
                    and time.monotonic() - self._nm_done_first
                    < self._done_flush_age):
                return
            buf = self._nm_done_buf
            self._nm_done_buf = []
        try:
            self.conn.send({"type": "direct_done_batch", "items": buf})
        # Node socket gone == this worker is dying; the NM's worker-
        # death cleanup reconciles the unflushed completions.
        except Exception:  # rtlint: disable=swallowed-failure
            pass

    def _nm_done_ticker(self):
        """Age bound for buffered completion notifications: a caller
        that stops calling still gets its last completions' seals and
        telemetry to the NM within one flush interval."""
        while self._alive:
            time.sleep(self._done_flush_age)
            self._flush_nm_dones()

    def _flush_dones(self):
        with self._done_lock:
            buf = self._done_buf
            self._done_buf = []
        if not buf:
            return
        if len(buf) == 1:
            self.conn.send(buf[0])
        else:
            self.conn.send({"type": "task_done_batch", "items": buf})

    def _run_task_direct(self, spec: TaskSpec, function_blob):
        """Pool-thread path (concurrent actor methods): completions are
        sent immediately — there is no queue-drain point to batch on."""
        self.conn.send(self._run_task(spec, function_blob))

    def _run_task(self, spec: TaskSpec, function_blob,
                  to_nm: bool = False, sample_resources: bool = True,
                  queued_ts: float = 0.0, caller=None) -> dict:
        """``caller``: for a call that came on a direct connection,
        ``(that connection, whether its caller is on another node)``: a
        streaming call's items go back on it."""
        if spec.task_type == TaskType.ACTOR_TASK:
            with self._direct_seen_lock:
                cached = self._direct_seen.get(spec.task_id.binary())
            if cached is not None:
                # NM-path replay of a call the direct plane already ran
                # (the channel died holding the reply): return the
                # recorded completion instead of double-executing actor
                # state — per-handle ordering survives the failover
                # with exactly-once method execution. Marked duplicate
                # so the NM skips stats/duration/history it already
                # counted from the direct_done_batch notification.
                done = dict(cached)
                done.pop("ref_deltas", None)
                done["duplicate"] = True
                if to_nm:
                    deltas = self.runtime.refs.drain()
                    if deltas:
                        done["ref_deltas"] = deltas
                return done
        self._apply_runtime_env(spec.runtime_env_key)
        rt = self.runtime
        cache: FunctionCache = rt.function_cache
        if function_blob is not None:
            cache.add_blob(spec.function_id, function_blob)

        def load_function(function_id: str):
            if not cache.has(function_id):
                reply = rt.request(
                    {"type": "fetch_function", "function_id": function_id}
                )
                if reply.get("blob") is None:
                    raise RuntimeError(f"function {function_id} not found")
                cache.add_blob(function_id, reply["blob"])
            return cache.load(function_id)

        def fetch(ids: List[ObjectID]):
            from .reference import ref_without_registration

            # Values come straight from locations; errors raise (propagating
            # dependency failures, matching the reference's semantics).
            locations = rt._cached_locations(ids, None)
            values = []
            from .exceptions import TaskError

            for oid, loc in locations:
                # _read_object retries through fresh locations if the bytes
                # were spilled/restored between the reply and the read.
                value = rt._read_object(oid, loc, None)
                if isinstance(value, TaskError):
                    raise value.as_raisable()
                values.append(value)
            return values

        def store_large(oid: ObjectID, sobj: SerializedObject) -> Location:
            return rt.store.put_serialized(oid, sobj)

        import time as _time

        seals = None
        if spec.streaming:
            from ..util.metrics import ItemTally
            from .config import get_config
            from .serialization import serialize_with_refs as _ser_refs
            from .streaming import (STREAM_ITEM_SEAL_S, STREAM_ITEMS_SEALED,
                                    consumed_upto, stream_item_id)

            inline_limit = get_config().max_inline_object_size

            # Items this task seals and the seconds that takes its
            # thread, recorded every ItemTally.FLUSH_ITEMS items and
            # when the task ends.
            seals = ItemTally(STREAM_ITEMS_SEALED, STREAM_ITEM_SEAL_S)

        def stream_item_direct(index: int, value):
            """One streamed yield of a call that came on a direct
            connection: ONE frame to its consumer on that connection,
            now; the node manager hears of it in the debounced batch
            (core/streaming.py, the direct route)."""
            nonlocal caller
            if caller is None:
                return stream_item(index, value)
            sealing = _time.perf_counter()
            conn, remote = caller
            oid = stream_item_id(spec.task_id, index)
            sobj, nested = _ser_refs(value)
            inline = sobj.total_size <= inline_limit
            loc = (InlineLocation(sobj.to_bytes()) if inline
                   else rt.store.put_serialized(oid, sobj))
            frame = {"type": "stream_item", "i": spec.task_id.binary(),
                     "x": index, "loc": loc}
            if not remote:
                # Pinned only if it left: a consumer that never gets
                # the frame never releases it.
                try:
                    conn.send(frame)
                except Exception:
                    if index:
                        # Its consumer may hold what left before: the
                        # stream ends here (store bytes that did not
                        # leave are sealed unpinned, for the sweep).
                        if not inline:
                            self._note_stream_seal(oid, loc, refs=0)
                        raise
                    # Nothing of this stream left: its consumer takes
                    # the call's replay (this run's completion, by
                    # ``_direct_seen``) and reads the node-manager
                    # route, which this run takes from here.
                    caller = None
                    msg = {"type": "put", "object_id": oid, "loc": loc,
                           "refs": 1, "pin_if_new": True}
                    if nested:
                        msg["nested"] = nested
                    self.conn.send(msg)
                else:
                    self._note_stream_seal(oid, loc, nested)
            else:
                # The caller's own node manager is told of the item by
                # the caller; this one only of bytes it has to hold for
                # it, and first.
                if nested:
                    frame["n"] = nested
                if not inline:
                    self._note_stream_seal(oid, loc, refs=0, held=True)
                conn.send(frame)
            seals.item(_time.perf_counter() - sealing)

        def stream_item(index: int, value):
            """Seal one streamed yield: the seal is what wakes the
            consumer (see core/streaming.py, the node-manager route)."""
            sealing = _time.perf_counter()
            # A retried attempt re-runs the generator from its start:
            # what the consumer already took is not sealed, and so not
            # pinned, a second time (the retry record). A first attempt
            # asks nothing.
            if (spec.retries_left < spec.max_retries
                    and index < consumed_upto(rt, spec.task_id)):
                return
            oid = stream_item_id(spec.task_id, index)
            sobj, nested = _ser_refs(value)
            loc = rt.store.put_serialized(oid, sobj)
            # Seal with one pinned ref (consumed by the reader's adopt).
            # pin_if_new: if a prior attempt's entry survived in this
            # node's directory (worker crash, store alive), its pin is
            # still held — adding another would leak; if the object died
            # with its node, the fresh entry needs its own pin or the
            # consumer's register/decr coalesce could GC it unread.
            msg = {"type": "put", "object_id": oid, "loc": loc,
                   "refs": 1, "pin_if_new": True}
            if nested:
                msg["nested"] = nested
            self.conn.send(msg)
            seals.item(_time.perf_counter() - sealing)

        rt.current_task_id = spec.task_id
        if spec.task_type in (TaskType.ACTOR_CREATION_TASK, TaskType.ACTOR_TASK):
            rt.current_actor_id = spec.actor_id
        from .timeline import enter_span, exit_span, new_span_id

        ctx = getattr(spec, "trace_ctx", None)
        trace_id = ctx[0] if ctx else spec.task_id.hex()[:16]
        parent_id = ctx[1] if ctx else ""
        span_id = new_span_id()
        prev_span = enter_span(trace_id, span_id)
        _t0 = _time.time()
        _m0 = _time.monotonic()
        # Per-task CPU/RSS deltas for the terminal task record (the
        # "where did the step time go" companion to the duration the
        # node manager already histograms). Direct hot-path calls skip
        # the sampler: its two /proc reads cost ~20us per call — real
        # money at 5k calls/s — and sub-millisecond actor methods have
        # no step time to attribute anyway.
        _rsamp = None
        if sample_resources:
            from ..util.profiler import TaskResourceSampler

            _rsamp = TaskResourceSampler().start()
        try:
            results, failed, nested, error_info = execute_task(
                spec, load_function, fetch, store_large, self.actor,
                stream_item=(None if not spec.streaming
                             else stream_item if caller is None
                             else stream_item_direct),
            )
        finally:
            rt.current_task_id = None
            exit_span(prev_span)
            if seals is not None:
                seals.flush()
            try:
                from .timeline import get_buffer

                get_buffer().record(
                    spec.name or spec.method_name or "task",
                    _t0, _time.time(), spec.task_id.hex(),
                    trace_id=trace_id, span_id=span_id,
                    parent_id=parent_id,
                )
                if queued_ts and _t0 > queued_ts:
                    # Queue-wait half of the direct-call server split:
                    # frame arrival -> execution start (seq parking +
                    # pool queueing), a sibling of the execution span.
                    get_buffer().record(
                        f"queue:{spec.name or spec.method_name or 'task'}",
                        queued_ts, _t0, spec.task_id.hex(),
                        trace_id=trace_id, span_id=new_span_id(),
                        parent_id=parent_id,
                    )
            # Observability must never fail the task it observes.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
        done = {
            "type": "task_done",
            "task_id": spec.task_id,
            "results": results,
            "failed": failed,
            "duration_s": _time.monotonic() - _m0,
        }
        if _rsamp is not None:
            try:
                done["resource_usage"] = _rsamp.finish()
            # A failed usage sample only blanks one telemetry row.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
        if failed and error_info is not None:
            # Structured failure record: the node manager retains the
            # error type/message in its terminal-task history, and the
            # event below carries the traceback's provenance (worker pid
            # + node) to the cluster event plane.
            done["error_type"] = error_info["error_type"]
            done["error_message"] = error_info["error_message"]
            try:
                from ..util import events as cluster_events

                cluster_events.emit(
                    cluster_events.ERROR, cluster_events.TASK,
                    f"task '{spec.name or spec.method_name}' failed: "
                    f"{error_info['error_type']}: "
                    f"{error_info['error_message']}",
                    task_id=spec.task_id.hex(),
                    actor_id=(spec.actor_id.hex()
                              if spec.actor_id else None),
                    custom_fields={
                        "error_type": error_info["error_type"],
                        "traceback": error_info["traceback"],
                        "worker_pid": os.getpid(),
                    },
                )
                # Publish NOW, not on the 0.25s cadence: the next task on
                # this worker may os._exit before the flusher ticks, and
                # a failure event is the one record worth a sync hop.
                cluster_events.flush()
            except Exception as e:  # noqa: BLE001
                # The failure still ships in the task_done frame; only
                # the event-plane copy is lost — note it for the logs.
                print(f"ray_tpu worker: failure-event publish failed "
                      f"({e!r})", file=sys.stderr)
        if nested:
            # Refs serialized inside return values: the NM pins them for
            # each return's lifetime (AddNestedObjectIds analogue).
            done["nested"] = nested
        if to_nm:
            # Ship this worker's pending ref deltas WITH the completion
            # so the NM counts refs we still hold (e.g. stored in actor
            # state) before it drops the task's submission-time pins —
            # the flush race the old interim scheme papered over with the
            # GC grace period. Direct-path completions bypass our NM (the
            # frame goes to the caller), so there the periodic flusher
            # keeps carrying the deltas to the right directory.
            deltas = rt.refs.drain()
            if deltas:
                done["ref_deltas"] = deltas
        return done


def main():
    worker_id = WorkerID.from_hex(os.environ["RAY_TPU_WORKER_ID"])
    socket_path = os.environ["RAY_TPU_NODE_SOCKET"]
    profile_to = os.environ.get("RAY_TPU_PROFILE_WORKER")
    if profile_to:
        # Per-worker cProfile dump (os._exit skips atexit: dump from the
        # main loop's exit path via threading.setprofile won't fire, so
        # hook the Worker main loop exit through sys.settrace-free
        # profiling of the whole process lifetime).
        import cProfile

        pr = cProfile.Profile()
        pr.enable()
        _orig_exit = os._exit

        def _dump_and_exit(code):
            pr.disable()
            try:
                pr.dump_stats(f"{profile_to}.{os.getpid()}")
            # Diagnostics-only path (RAY_TPU_PROFILE_WORKERS): a failed
            # dump must not change the worker's exit code.
            except Exception:  # rtlint: disable=swallowed-failure
                pass
            _orig_exit(code)

        os._exit = _dump_and_exit
    arena = os.environ.get("RAY_TPU_ARENA")
    if arena:
        from .object_store import init_arena

        if not init_arena(arena, create=False):
            # Puts fall back to per-object shm, but gets of ArenaLocation
            # objects will fail — make the root cause findable in the log.
            print(
                f"ray_tpu worker: failed to attach arena {arena}; "
                "native store disabled in this worker",
                file=sys.stderr,
                flush=True,
            )
    conn = connect_unix(socket_path)
    worker = Worker(conn, worker_id)
    try:
        worker.start()
    finally:
        # Ship the event ring's tail (task failures, CHAOS firings)
        # while the runtime transport still exists — worker exits often
        # end in os._exit, which skips atexit.
        try:
            from ..util import events as _events

            _events.flush()
        # Transport already gone at teardown: the ring's tail is lost
        # with the process either way; nothing actionable here.
        except Exception:  # rtlint: disable=swallowed-failure
            pass


if __name__ == "__main__":
    sys.exit(main())
