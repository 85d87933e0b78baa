"""Direct actor-call plane: fallback discipline + cross-runtime riders
(runtime._DirectChannel <-> worker_main._direct_serve, ISSUE 5).

Covers what tests/test_direct_actor.py (the happy-path suite) does not:
channel death mid-call -> NM-path replay preserving per-handle call
ordering with exactly-once method execution; actor restart re-resolving
the endpoint; serve handles and worker-runtime callers riding the same
plane; out-of-order sequence frames buffered by the worker; and the
PeerClient.close() fast-fail regression."""

import threading
import time

import pytest

import ray_tpu


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=2, system_config={"log_to_driver": False})
    yield
    ray_tpu.shutdown()


def _runtime():
    from ray_tpu.core import runtime_context

    return runtime_context.current_runtime()


def _engage(handle, call, deadline_s=15.0):
    """Drive calls until the handle's direct channel is ready; returns
    the state dict."""
    deadline = time.time() + deadline_s
    st = None
    while time.time() < deadline:
        ray_tpu.get(call())
        st = _runtime()._direct_states.get(handle.actor_id.binary())
        if st is not None and st["status"] == "ready":
            return st
        time.sleep(0.02)
    raise AssertionError(f"direct channel never engaged: {st}")


def test_channel_death_replays_in_order(rt):
    """Injected channel death mid-burst: unanswered calls replay over
    the NM path IN ORDER, later calls queue behind them, every call
    executes exactly once, and the channel re-engages afterwards with
    no steady-state fallbacks."""

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    st = _engage(c, lambda: c.inc.remote())
    runtime = _runtime()
    base = ray_tpu.get(c.inc.remote())
    fallbacks_before = runtime._direct_fallbacks

    refs = [c.inc.remote() for _ in range(20)]
    st["chan"].conn.close()  # injected fault: kill the raw socket
    refs += [c.inc.remote() for _ in range(20)]
    vals = ray_tpu.get(refs, timeout=60)
    # Strict submission order AND exactly-once execution across the
    # failover (the worker dedups replayed task ids it already ran).
    assert vals == list(range(base + 1, base + 41))
    assert runtime._direct_fallbacks > fallbacks_before

    # Automatic recovery: the channel re-engages and fallbacks stop.
    _engage(c, lambda: c.inc.remote())
    steady = runtime._direct_fallbacks
    cur = ray_tpu.get(c.inc.remote())
    assert ray_tpu.get([c.inc.remote() for _ in range(50)], timeout=30) \
        == list(range(cur + 1, cur + 51))
    assert runtime._direct_fallbacks == steady  # zero steady-state fallbacks


def test_backpressure_cap_and_death_through_pending_table(rt, monkeypatch):
    """ISSUE 12: the pending/replay table enforces the unanswered-call
    cap (a pipelined stream far deeper than the cap completes — the
    submitter parks on the table's condvar, the reader's completion
    pops release it) and a channel killed while calls are parked
    replays them exactly-once in order. Runs on whichever table the
    build provides (native or PyPendingTable) — the semantics must be
    identical."""
    from ray_tpu.core import runtime as rt_mod

    monkeypatch.setattr(rt_mod, "DIRECT_MAX_UNANSWERED", 8)

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    st = _engage(c, lambda: c.inc.remote())
    chan = st["chan"]
    base = ray_tpu.get(c.inc.remote(), timeout=30)
    # 64-deep pipeline against a cap of 8: submit() must park and
    # resume repeatedly; the table can never exceed the cap.
    refs = [c.inc.remote() for _ in range(64)]
    assert len(chan.table) <= 8
    vals = ray_tpu.get(refs, timeout=60)
    assert vals == list(range(base + 1, base + 65))
    assert len(chan.table) == 0
    stats = _runtime().direct_stats()
    assert stats["gil_probe"]["py_entries"] > 0
    # Now kill the socket with calls in flight: drain() snapshots in
    # seq order, the NM replay keeps them exactly-once.
    refs = [c.inc.remote() for _ in range(20)]
    chan.conn.close()
    refs += [c.inc.remote() for _ in range(5)]
    vals = ray_tpu.get(refs, timeout=60)
    assert vals == list(range(base + 65, base + 90))
    st2 = _engage(c, lambda: c.inc.remote())
    assert st2["chan"] is not chan


def test_failure_sweeps_calls_popped_but_undelivered(rt):
    """A native burst can pop completions from the pending table and
    then die before Python ever sees them. The failure path must
    replay from the rich-state dict (_calls), not the table alone —
    otherwise those calls are never resolved and never replayed."""

    @ray_tpu.remote
    class Slow:
        def __init__(self):
            self.n = 0

        def inc(self):
            time.sleep(0.05)
            self.n += 1
            return self.n

    s = Slow.remote()
    st = _engage(s, lambda: s.inc.remote())
    chan = st["chan"]
    base = ray_tpu.get(s.inc.remote(), timeout=30)
    refs = [s.inc.remote() for _ in range(10)]
    # Simulate the undelivered-burst window: drop some in-flight task
    # ids from the table (as a dying recv_burst would), then sever the
    # channel. The sweep in _direct_channel_failed must still replay
    # every call exactly-once in order.
    for call in list(chan._calls.values())[:3]:
        chan.table.pop(call.spec.task_id.binary())
    chan.conn.close()
    vals = ray_tpu.get(refs, timeout=60)
    assert vals == list(range(base + 1, base + 11))


def test_actor_restart_reresolves_endpoint(rt):
    """Worker death with restarts left: calls fall back to the NM route
    (which queues through the restart), and the handle re-resolves the
    NEW worker's direct endpoint afterwards."""

    @ray_tpu.remote(max_restarts=1)
    class Flaky:
        def __init__(self):
            self.calls = 0

        def bump(self):
            self.calls += 1
            return self.calls

        def die(self):
            import os

            os._exit(1)

    f = Flaky.remote()
    st = _engage(f, lambda: f.bump.remote())
    old_chan = st["chan"]
    f.die.remote()
    # Post-restart state is fresh (__init__ re-ran); calls must succeed
    # again without manual re-resolution.
    deadline = time.time() + 30
    val = None
    while time.time() < deadline:
        try:
            val = ray_tpu.get(f.bump.remote(), timeout=10)
            break
        except Exception:
            time.sleep(0.2)
    assert val is not None and val >= 1
    st = _engage(f, lambda: f.bump.remote(), deadline_s=20)
    assert st["chan"] is not old_chan  # new endpoint, new channel


def test_worker_caller_rides_direct_plane(rt):
    """A task running INSIDE a worker calls an actor handle: the worker
    runtime opens its own direct channel (the serve-replica pattern),
    results flow, and the actor's NM sees the completion notifications."""

    @ray_tpu.remote
    class Adder:
        def add(self, a, b):
            return a + b

    @ray_tpu.remote
    def burst(handle, n):
        # Sequential gets so the worker runtime's discovery (spawned on
        # the first NM-routed call) gets a drain window to flip the
        # channel ready mid-burst; the worker process — and therefore
        # its runtime and channel — persists across burst() calls.
        return [ray_tpu.get(handle.add.remote(i, 1)) for i in range(n)]

    a = Adder.remote()
    _engage(a, lambda: a.add.remote(0, 0))
    # Drive worker-caller bursts until the NM has seen direct
    # completion notifications (worker channels engage across bursts).
    nm = _runtime()._nm
    deadline = time.time() + 45
    while time.time() < deadline:
        out = ray_tpu.get(burst.remote(a, 25), timeout=60)
        assert out == [i + 1 for i in range(25)]
        if nm._stats["direct_calls_done"] > 0:
            break
    assert nm._stats["direct_calls_done"] > 0
    assert nm._stats["direct_done_batches"] > 0


def test_serve_handle_rides_direct_plane(rt):
    """Serve replicas are actor handles: after a few requests the
    router's replica calls run over a ready direct channel and the
    request path answers correctly."""
    from ray_tpu import serve

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    handle = serve.run(Doubler.bind())
    try:
        assert handle.remote(21).result(timeout=30) == 42
        for i in range(30):
            assert handle.remote(i).result(timeout=30) == 2 * i
        # The handle's submits happen in the driver process here; its
        # runtime must hold a ready channel to the replica actor.
        states = _runtime()._direct_states
        deadline = time.time() + 15
        ready = False
        while time.time() < deadline and not ready:
            handle.remote(1).result(timeout=30)
            ready = any(
                s["status"] == "ready" for s in list(states.values())
            )
            time.sleep(0.05)
        assert ready, {
            k.hex()[:8]: s["status"] for k, s in states.items()
        }
    finally:
        serve.shutdown()


def test_out_of_order_frames_execute_in_sequence(rt):
    """Protocol-level: frames arriving with shuffled sequence numbers
    execute in sequence order (the worker parks the gap until it
    fills). Speaks the direct protocol over a raw connection."""
    from ray_tpu.core.ids import TaskID
    from ray_tpu.core.protocol import DIRECT_PROTO_VER, connect_unix
    from ray_tpu.core.task_spec import TaskSpec, TaskType

    @ray_tpu.remote
    class Rec:
        def __init__(self):
            self.seen = []

        def note(self, tag):
            self.seen.append(tag)
            return list(self.seen)

        def seen_list(self):
            return list(self.seen)

    r = Rec.remote()
    ray_tpu.get(r.seen_list.remote())
    runtime = _runtime()
    desc = runtime._nm.call_sync(
        runtime._nm.get_actor_direct(r.actor_id, timeout=15.0),
        timeout=30.0,
    )
    assert desc is not None and desc["path"]
    conn = connect_unix(desc["path"], timeout=5.0)
    try:
        conn.send({
            "type": "direct_hello", "ver": DIRECT_PROTO_VER, "token": "",
            "actor_id": r.actor_id.hex(), "node": runtime.node_id.hex(),
        })
        welcome = conn.recv()
        assert welcome.get("ok"), welcome

        def spec_for(tag):
            return TaskSpec(
                task_id=TaskID.from_random(),
                task_type=TaskType.ACTOR_TASK,
                function_id=r._class_function_id,
                args=[], kwargs={},
                num_returns=1,
                name="Rec.note",
                actor_id=r.actor_id,
                method_name="note",
            )

        from ray_tpu.core.task_spec import ValueArg
        from ray_tpu.core.serialization import serialize

        def arg(v):
            return ValueArg(serialize(v).to_bytes())

        s1, s2, s3 = spec_for("a"), spec_for("b"), spec_for("c")
        s1.args, s2.args, s3.args = [arg("a")], [arg("b")], [arg("c")]
        # Send seq 2 and 3 FIRST, then seq 1: the worker must buffer
        # them and execute a, b, c in sequence order.
        conn.send({"type": "execute", "spec": s2, "function_blob": None,
                   "q": 2})
        conn.send({"type": "execute", "spec": s3, "function_blob": None,
                   "q": 3})
        time.sleep(0.3)  # give the gap a chance to (wrongly) execute
        conn.send({"type": "execute", "spec": s1, "function_blob": None,
                   "q": 1})
        got = []
        deadline = time.time() + 20
        while len(got) < 3 and time.time() < deadline:
            msg = conn.recv()
            if msg.get("type") == "task_done":
                got.append(msg)
            elif msg.get("type") == "task_done_batch":
                got.extend(msg["items"])
        assert len(got) == 3
    finally:
        conn.close()
    assert ray_tpu.get(r.seen_list.remote(), timeout=15) == ["a", "b", "c"]


def test_version_mismatch_falls_back_to_nm_path(rt):
    """A hello with the wrong protocol version is refused; calls keep
    flowing over the NM route (transparent fallback, correct results)."""
    from ray_tpu.core.protocol import connect_unix

    @ray_tpu.remote
    class P:
        def ping(self):
            return b"ok"

    p = P.remote()
    st = _engage(p, lambda: p.ping.remote())
    desc = dict(st["chan"].desc)
    conn = connect_unix(desc["path"], timeout=5.0)
    try:
        conn.send({
            "type": "direct_hello", "ver": 999999, "token": "",
            "actor_id": p.actor_id.hex(), "node": "feedface",
        })
        welcome = conn.recv()
        assert not welcome.get("ok")
        assert "version" in welcome.get("error", "")
    finally:
        conn.close()
    # The real channel is untouched; calls still work.
    assert ray_tpu.get(p.ping.remote(), timeout=15) == b"ok"


def test_peer_close_fails_pending_requests_immediately():
    """PeerClient.close() must fail in-flight request() futures NOW —
    not after the 60s default timeout — including when close() is
    driven from a foreign thread (node-death handling)."""
    import asyncio

    from ray_tpu.core.peers import PeerClient
    from ray_tpu.core.protocol import AioFramedWriter, aio_read_frame

    async def scenario():
        async def silent_server(reader, writer):
            # Accept the hello, then never reply to anything.
            try:
                framed = AioFramedWriter(writer)
                while True:
                    await aio_read_frame(reader)
            except Exception:
                pass
            finally:
                del framed
                # Python 3.12's Server.wait_closed() waits for every
                # accepted connection to be closed, not just the listener.
                writer.close()

        server = await asyncio.start_server(
            silent_server, "127.0.0.1", 0
        )
        port = server.sockets[0].getsockname()[1]
        peer = PeerClient("deadbeef" * 4, "127.0.0.1", port,
                          "cafebabe" * 4)
        await peer.connect()

        async def do_request():
            t0 = time.monotonic()
            with pytest.raises(ConnectionError):
                # Default timeout is 60s; close() must beat it by far.
                await peer.request({"type": "state_snapshot"})
            return time.monotonic() - t0

        task = asyncio.ensure_future(do_request())
        await asyncio.sleep(0.2)  # request is in flight, unanswered
        loop = asyncio.get_running_loop()
        # Foreign-thread close, like the NM's node-death teardown path.
        t = threading.Thread(target=peer.close)
        t.start()
        elapsed = await asyncio.wait_for(task, timeout=10)
        t.join(timeout=5)
        server.close()
        await server.wait_closed()
        return elapsed

    elapsed = asyncio.new_event_loop().run_until_complete(scenario())
    assert elapsed < 5.0, (
        f"pending request survived {elapsed:.1f}s after close() — "
        "futures must fail immediately on peer death"
    )
