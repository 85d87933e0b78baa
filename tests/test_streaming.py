"""Streaming generator tests (ref analogue:
python/ray/tests/test_streaming_generator.py)."""

import time

import pytest

import ray_tpu


def test_streaming_generator_basic(ray_tpu_start):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    refs = list(gen.remote(5))
    assert [ray_tpu.get(r) for r in refs] == [0, 10, 20, 30, 40]


def test_streaming_yields_before_completion(ray_tpu_start):
    """Items are consumable while the producer is still running."""

    @ray_tpu.remote
    def warm():
        return 1

    ray_tpu.get(warm.remote())

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(4):
            time.sleep(0.3)
            yield i

    t0 = time.monotonic()
    stamps = []
    for ref in slow_gen.remote():
        stamps.append(time.monotonic() - t0)
        ray_tpu.get(ref)
    # First item arrives ~0.3s in, not at the ~1.2s completion.
    assert stamps[0] < stamps[-1] - 0.5, stamps


def test_streaming_generator_error_propagates(ray_tpu_start):
    @ray_tpu.remote(num_returns="streaming")
    def bad():
        yield 1
        raise ValueError("stream broke")

    vals = []
    with pytest.raises(ValueError, match="stream broke"):
        for r in bad.remote():
            vals.append(ray_tpu.get(r))
    assert vals == [1]


_START = {"num_prestart_workers": 2, "refcount_flush_interval_s": 0.1,
          "gc_grace_period_s": 1.0}


@pytest.fixture(params=["node_manager", "direct"])
def actor_route(request):
    """A runtime whose actors' streams take the named route
    (core/streaming.py): the direct actor-call plane off, or on with
    ``_on_route`` waiting for the actor's channel."""
    config = dict(_START)
    if request.param == "node_manager":
        config["direct_actor_calls"] = False
    ray_tpu.init(num_cpus=4, system_config=config)
    yield request.param
    ray_tpu.shutdown()


def _on_route(actor, route, timeout=30.0):
    """``actor`` (it has a ``ping``) with its direct channel ready, so
    that its next stream rides it; nothing to wait for on the
    node-manager route."""
    from ray_tpu.core.runtime_context import current_runtime

    if route == "node_manager":
        return actor
    state = current_runtime()._direct_state(actor._actor_id)
    deadline = time.monotonic() + timeout
    while state["status"] != "ready":
        assert time.monotonic() < deadline, state
        ray_tpu.get(actor.ping.remote(), timeout=30)
        time.sleep(0.02)
    return actor


def _direct_items():
    """Items of this process's streams that came on a direct channel."""
    from ray_tpu.util.metrics import local_snapshot

    return _series(local_snapshot(), "ray_tpu_stream_items_direct_total")


def test_streaming_actor_method(actor_route):
    @ray_tpu.remote
    class Producer:
        def ping(self):
            return 1

        def chunks(self, n):
            for i in range(n):
                yield {"chunk": i}

    p = _on_route(Producer.remote(), actor_route)
    before, direct0 = _stream_counters()[0], _direct_items()
    gen = p.chunks.options(num_returns="streaming").remote(3)
    assert [ray_tpu.get(r)["chunk"] for r in gen] == [0, 1, 2]
    assert ray_tpu.get(gen.completed, timeout=30) == 3
    assert _stream_counters()[0] - before == 3
    assert _direct_items() - direct0 == (3 if actor_route == "direct" else 0)


def test_streaming_empty_generator(ray_tpu_start):
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        if False:
            yield 1

    assert list(empty.remote()) == []


def test_generator_del_on_node_manager_loop_does_not_deadlock(
        ray_tpu_start):
    """Regression: gc can fire ObjectRefGenerator.__del__ on ANY
    thread — including the node-manager event loop (observed mid-frame
    pickling). The old inline cleanup issued a blocking call_sync back
    onto that same loop and froze the entire runtime; cleanup now runs
    on a detached thread, so the loop must stay responsive."""
    import threading

    from ray_tpu.core.runtime_context import current_runtime

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(6):
            yield i

    g = gen.remote()
    assert ray_tpu.get(next(g)) == 0
    assert ray_tpu.get(next(g)) == 1

    nm = current_runtime()._nm
    ran = threading.Event()

    def fire_del_on_loop():
        try:
            g.__del__()  # simulate gc running on the loop thread
        finally:
            ran.set()

    nm._loop.call_soon_threadsafe(fire_del_on_loop)
    assert ran.wait(timeout=10), "__del__ blocked the NM loop"
    # The loop survived: control-plane ops still complete.
    import ray_tpu as rt

    assert rt.kv_put("post_del_probe", b"ok")
    assert rt.kv_get("post_del_probe") == b"ok"

    @ray_tpu.remote
    def ping():
        return 41

    assert rt.get(ping.remote(), timeout=30) == 41


# ---- delivery: a consumer blocks on the producer's seal (PR 31) ----------


def _stream_counters():
    """(items, blocked, waits observed, seconds waited) of this process's
    stream-delivery counters."""
    from ray_tpu.util.metrics import local_snapshot

    snap = local_snapshot()

    def series(name):
        return snap.get(name, ("", {}, ""))[1].get((), None)

    wait = series("ray_tpu_stream_item_wait_seconds") or {
        "count": 0, "sum": 0.0}
    return (series("ray_tpu_stream_items_total") or 0.0,
            series("ray_tpu_stream_item_blocked_total") or 0.0,
            wait["count"], wait["sum"])


def test_item_is_delivered_at_its_seal_not_at_a_tick(ray_tpu_start,
                                                     monkeypatch):
    """A producer 5 ms an item is consumed 5 ms an item: no sleep is on
    the delivery path (the 20 ms KV poll made every gap 20+ ms)."""
    import statistics
    import types

    from ray_tpu.core import streaming

    def no_sleep(_):
        raise AssertionError("the delivery path slept")

    monkeypatch.setattr(streaming, "time", types.SimpleNamespace(
        perf_counter=time.perf_counter, sleep=no_sleep))

    @ray_tpu.remote(num_returns="streaming")
    def paced(n):
        for i in range(n):
            time.sleep(0.005)
            yield i

    stamps, values = [], []
    for ref in paced.remote(40):
        values.append(ray_tpu.get(ref))
        stamps.append(time.monotonic())
    assert values == list(range(40))
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    assert statistics.median(gaps) < 0.015, sorted(gaps)


def test_slow_producer_costs_blocked_waits_not_polls(ray_tpu_start,
                                                     monkeypatch):
    """1.5 s between two items is one parked wait, not ~75 polls."""
    from ray_tpu.core.runtime_context import current_runtime

    rt = current_runtime()
    calls = {"kv_get": 0, "_wait": 0, "_wait_carrying": 0}

    def counted(name):
        real = getattr(rt, name)

        def wrapper(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)

        return wrapper

    for name in calls:
        monkeypatch.setattr(rt, name, counted(name))

    @ray_tpu.remote(num_returns="streaming")
    def slow():
        yield "a"
        time.sleep(1.5)
        yield "b"

    t0 = time.monotonic()
    assert [ray_tpu.get(r) for r in slow.remote()] == ["a", "b"]
    assert time.monotonic() - t0 >= 1.5
    # Two items and the end, each one parked wait and no look before it.
    assert calls == {"kv_get": 0, "_wait": 0, "_wait_carrying": 3}, calls


@pytest.mark.parametrize("pace", ["consumer_keeps_up", "consumer_lags"])
def test_stream_counters_follow_the_pace(ray_tpu_start, pace):
    n = 12

    @ray_tpu.remote(num_returns="streaming")
    def produce(gap_s):
        for i in range(n):
            time.sleep(gap_s)
            yield i

    before = _stream_counters()
    if pace == "consumer_keeps_up":
        gen = produce.remote(0.05)
    else:
        gen = produce.remote(0.0)
        ray_tpu.get(gen.completed, timeout=60)  # every item sealed first
    assert [ray_tpu.get(r) for r in gen] == list(range(n))
    items, blocked, waits, waited_s = (
        a - b for a, b in zip(_stream_counters(), before))
    assert items == n
    assert waits == blocked
    if pace == "consumer_keeps_up":
        # It blocked for (nearly) every item, about the producer's gap.
        assert blocked >= n - 2, blocked
        assert 0.03 * blocked < waited_s < 0.2 * blocked, waited_s
    else:
        assert blocked == 0 and waited_s == 0


@ray_tpu.remote
class _StreamActor:
    def ping(self):
        return 1

    def produce(self, k, fail):
        for i in range(k):
            yield i
        if fail:
            raise ValueError("stream broke")


@ray_tpu.remote(num_returns="streaming")
def _stream_task(k, fail):
    for i in range(k):
        yield i
    if fail:
        raise ValueError("stream broke")


@pytest.mark.parametrize("kind", ["task", "actor_method",
                                  "actor_method_direct"])
@pytest.mark.parametrize("k,fail", [(0, False), (3, True)],
                         ids=["empty", "error_after_3"])
def test_stream_ends_at_once(ray_tpu_start, kind, k, fail):
    """The end of a stream and a producer's error are seals like any
    other (on the direct route: frames like any other): neither waits
    for ``item_timeout_s``."""
    if kind == "task":
        gen = _stream_task.remote(k, fail)
    else:
        actor = _StreamActor.remote()
        if kind == "actor_method_direct":
            _on_route(actor, "direct")
        gen = actor.produce.options(num_returns="streaming").remote(k, fail)
        if kind == "actor_method_direct":
            assert gen._stream is not None
    gen.item_timeout_s = 120.0
    t0 = time.monotonic()
    got = []
    if fail:
        with pytest.raises(ValueError, match="stream broke"):
            for r in gen:
                got.append(ray_tpu.get(r))
    else:
        got = [ray_tpu.get(r) for r in gen]
        with pytest.raises(StopIteration):
            next(gen)
    assert got == list(range(k))
    assert time.monotonic() - t0 < 30


def test_item_timeout_on_a_wedged_producer(ray_tpu_start):
    from ray_tpu.core.exceptions import GetTimeoutError

    @ray_tpu.remote(num_returns="streaming")
    def wedge():
        yield 0
        time.sleep(30)
        yield 1

    gen = wedge.remote()
    assert ray_tpu.get(next(gen)) == 0
    gen.item_timeout_s = 0.5
    t0 = time.monotonic()
    with pytest.raises(GetTimeoutError):
        next(gen)
    assert 0.5 <= time.monotonic() - t0 < 10


def test_retried_producer_skips_what_the_consumer_took(ray_tpu_start,
                                                       tmp_path):
    """A crashed producer is re-run from its start; the consumer goes on
    where it was, and the retry record (the consumer's position) keeps
    the second attempt from re-sealing what was already taken."""
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.core.streaming import stream_item_id, stream_key

    marker = str(tmp_path / "attempted")

    @ray_tpu.remote(num_returns="streaming", max_retries=2)
    def flaky(marker):
        import os

        first = not os.path.exists(marker)
        for i in range(6):
            if first and i == 3:
                open(marker, "w").close()
                time.sleep(0.5)  # let the consumer take items 0..2
                os._exit(1)
            yield i

    rt = current_runtime()
    gen = flaky.remote(marker)
    task_id = gen._task_id
    got = []
    for ref in gen:
        got.append(ray_tpu.get(ref))
        if len(got) == 3:
            assert rt.kv_get(stream_key(task_id)) == b"3"
            del ref  # taken AND dropped: only the record remembers it
    assert got == list(range(6))
    assert rt.kv_get(stream_key(task_id)) is None  # gone with the stream
    # The second attempt did not seal items 0..2 again: past the grace
    # period nothing holds them.
    deadline = time.monotonic() + 20
    first3 = [stream_item_id(task_id, i) for i in range(3)]
    while rt._wait(first3, 3, 0) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert rt._wait(first3, 3, 0) == []


def test_abandoned_stream_releases_its_sealed_items(ray_tpu_start):
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.core.streaming import stream_item_id

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(5):
            yield i

    rt = current_runtime()
    g = gen.remote()
    task_id = g._task_id
    assert ray_tpu.get(next(g)) == 0
    ray_tpu.get(g.completed, timeout=60)
    rest = [stream_item_id(task_id, i) for i in range(1, 5)]
    assert len(rt._wait(rest, 4, 0)) == 4  # sealed, pinned, untaken
    del g
    deadline = time.monotonic() + 20
    while rt._wait(rest, 4, 0) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert rt._wait(rest, 4, 0) == []


def test_finished_streams_leave_no_event_behind(ray_tpu_start):
    """Every stream's last wait names an item that never comes to be;
    its seal event leaves with the wait."""
    from ray_tpu.core.runtime_context import current_runtime

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    nm = current_runtime()._nm
    for _ in range(5):
        assert [ray_tpu.get(r) for r in gen.remote(3)] == [0, 1, 2]
    assert not nm._parked_waits
    assert not nm._seal_events, list(nm._seal_events)


def test_stream_from_another_node_wakes_on_its_seal():
    """A producer on another node: its seal reaches the consumer's wait
    through the GCS object directory's long-poll, not through a timer;
    the end of the stream and an error do too."""
    import statistics

    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_resources={"CPU": 2},
                system_config={"log_to_driver": False})
    try:
        c.add_node(num_cpus=2, resources={"gadget": 1})
        c.wait_for_nodes(2)

        @ray_tpu.remote(num_returns="streaming", resources={"gadget": 1})
        def far(n, gap_s, fail):
            for i in range(n):
                time.sleep(gap_s)
                yield i
            if fail:
                raise ValueError("stream broke")

        assert [ray_tpu.get(r) for r in far.remote(2, 0.0, False)] == [0, 1]
        stamps, values = [], []
        for ref in far.remote(20, 0.02, False):
            values.append(ray_tpu.get(ref))
            stamps.append(time.monotonic())
        assert values == list(range(20))
        gaps = [b - a for a, b in zip(stamps, stamps[1:])]
        assert statistics.median(gaps) < 0.1, sorted(gaps)

        t0 = time.monotonic()
        assert list(far.remote(0, 0.0, False)) == []
        got = []
        with pytest.raises(ValueError, match="stream broke"):
            for r in far.remote(3, 0.05, True):
                got.append(ray_tpu.get(r))
        assert got == [0, 1, 2][:len(got)]
        assert time.monotonic() - t0 < 20
    finally:
        c.shutdown()


# ---- both ends of a stream, hop by hop, off the registry (PR 40) ----------


def _series(snapshot_or_report, name, **tags):
    """One series of a ``local_snapshot()`` or a
    ``get_metrics_report()``, by its tags; 0.0 where it is not there."""
    entry = snapshot_or_report.get(name)
    if entry is None:
        return 0.0
    series = entry["series"] if isinstance(entry, dict) else entry[1]
    return series.get(tuple(sorted(tags.items())), 0.0)


def _sealed():
    """(items, seconds) the producers of this cluster sealed, as their
    processes last flushed them to the KV."""
    from ray_tpu.util.metrics import get_metrics_report

    report = get_metrics_report()
    return (_series(report, "ray_tpu_stream_items_sealed_total"),
            _series(report, "ray_tpu_stream_item_seal_seconds_total"))


def _carried():
    """Items of this process's streams whose location came with the
    reply of the wait for their seal."""
    from ray_tpu.util.metrics import local_snapshot

    return _series(local_snapshot(), "ray_tpu_stream_items_carried_total")


def _wait_for(read, want, timeout=20.0):
    deadline = time.monotonic() + timeout
    while True:
        got = read()
        if want(got) or time.monotonic() > deadline:
            return got
        time.sleep(0.1)


@pytest.mark.parametrize("paced", ["producer", "consumer"])
def test_both_ends_count_items_and_seconds_whoever_sets_the_pace(
        ray_tpu_start, paced):
    n = 12

    @ray_tpu.remote(num_returns="streaming")
    def produce(gap_s):
        for i in range(n):
            time.sleep(gap_s)
            yield i

    sealed0, seal_s0 = _sealed()
    before, carried0 = _stream_counters(), _carried()
    got = []
    for ref in produce.remote(0.03 if paced == "producer" else 0.0):
        got.append(ray_tpu.get(ref))
        if paced == "consumer":
            time.sleep(0.03)
    items, blocked, waits, waited_s = (
        a - b for a, b in zip(_stream_counters(), before))
    assert got == list(range(n)) and items == n and waits == blocked
    # One request a hand-over, and its reply brought the item's location
    # whether it had to park for the seal or found it.
    assert _carried() - carried0 == n
    if paced == "producer":
        assert blocked >= n - 2 and waited_s > 0.02 * blocked
    else:
        # What a consumer finds sealed it does not block for (the first
        # item waits for the task to start).
        assert blocked <= 2
    # The producer's side, from its own process: every item sealed, in
    # seconds that do not hold the generator's own sleeps.
    sealed, seal_s = _wait_for(_sealed, lambda s: s[0] - sealed0 >= n)
    assert sealed - sealed0 == n
    assert 0 < seal_s - seal_s0 < 0.02 * n


def test_an_abandoned_stream_still_records_what_it_counted(ray_tpu_start):
    @ray_tpu.remote(num_returns="streaming")
    def produce():
        for i in range(10):
            yield i

    before, carried0 = _stream_counters(), _carried()
    gen = produce.remote()
    assert [ray_tpu.get(next(gen)) for _ in range(3)] == [0, 1, 2]
    # Nothing is recorded an item: three are under the flush's 64.
    assert _stream_counters() == before and _carried() == carried0
    del gen
    got = _wait_for(_stream_counters, lambda c: c[0] - before[0] >= 3)
    assert got[0] - before[0] == 3 and got[2] == got[1]
    assert _carried() - carried0 == 3


def test_the_consumers_item_path_makes_no_registry_call(ray_tpu_start,
                                                        monkeypatch):
    """200 items cost the consumer's process a flush every 64 and one at
    the end, not three registry calls an item."""
    from ray_tpu.util import metrics

    n = 200

    @ray_tpu.remote(num_returns="streaming")
    def produce():
        for i in range(n):
            yield i

    calls = []
    real = metrics._registry.record

    def counted(name, kind, tags_key, update):
        if name.startswith("ray_tpu_stream"):
            calls.append(name)
        return real(name, kind, tags_key, update)

    monkeypatch.setattr(metrics._registry, "record", counted)
    before = _stream_counters()
    assert [ray_tpu.get(r) for r in produce.remote()] == list(range(n))
    items, blocked, waits, _ = (
        a - b for a, b in zip(_stream_counters(), before))
    assert items == n and waits == blocked
    # Four flushes (64, 128, 192, the end) of at most four series.
    assert 2 <= len(calls) <= 16, calls
    assert calls.count("ray_tpu_stream_items_carried_total") == 4
    assert calls.count("ray_tpu_stream_items_total") == 4


def test_a_served_stream_counts_its_fetches_and_writes_and_records_one_span(
        ray_tpu_start):
    """Through ``handle.stream`` and the proxy's SSE reply: items and
    seconds at the ``fetch`` and ``write`` hops, tagged by deployment,
    and one ``stream.deliver`` span under the consumer's own span."""
    import json
    import sys
    import urllib.request

    import ray_tpu.core.timeline  # noqa: F401
    from ray_tpu import serve
    from ray_tpu.serve.http_proxy import start_proxy, stop_proxy
    from ray_tpu.util.metrics import local_snapshot

    timeline = sys.modules["ray_tpu.core.timeline"]
    n = 5

    @serve.deployment(num_replicas=1)
    class Tokens:
        def stream(self, _):
            for i in range(n):
                time.sleep(0.01)
                yield {"token": i}

    def hop(name):
        snap = local_snapshot()
        return tuple(_series(snap, series, deployment="toks", hop=name)
                     for series in (
                         "ray_tpu_serve_stream_items_total",
                         "ray_tpu_serve_stream_item_seconds_total"))

    handle = serve.run(Tokens.bind(), name="toks")
    try:
        trace_id, span_id = "ab" * 16, "cd" * 8
        prev = timeline.enter_span(trace_id, span_id)
        started = time.time()
        try:
            got = [item["token"] for item
                   in handle.options(method="stream").stream(None)]
        finally:
            timeline.exit_span(prev)
        ended = time.time()
        assert got == list(range(n))
        items, seconds = hop("fetch")
        assert items == n and 0 < seconds < ended - started
        assert hop("write") == (0.0, 0.0)
        spans = [e for e in timeline.get_buffer()._events
                 if e["name"] == "stream.deliver"
                 and e["trace_id"] == trace_id]
        assert len(spans) == 1 and spans[0]["parent_id"] == span_id
        # First item handed over to the consumer back from the last.
        assert started < spans[0]["ts"] <= spans[0]["ts"] + spans[0]["dur"] \
            <= ended
        assert spans[0]["dur"] >= 0.01 * (n - 2)

        port = start_proxy(0)
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/toks/stream", data=b"null",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(request, timeout=60) as reply:
            lines = [raw.decode().strip() for raw in reply]
        tokens = [json.loads(line[5:])["token"] for line in lines
                  if line.startswith("data:") and "token" in line]
        assert tokens == list(range(n)), lines
        assert _wait_for(lambda: hop("write")[0], lambda w: w >= n) == n
        assert 0 < hop("write")[1] < 1.0
        assert hop("fetch")[0] == 2 * n
    finally:
        stop_proxy()
        serve.shutdown()


# ---- one node-manager request an item (PR 41) ------------------------------


def _paced_items():
    """A streaming task ``(n, gap_s)``, and ``counts()`` = this
    process's (items, blocked, carried); both made here so that a worker
    gets them by value (it cannot import this module)."""

    @ray_tpu.remote(num_returns="streaming")
    def paced_items(n, gap_s):
        for i in range(n):
            time.sleep(gap_s)
            yield i

    def counts():
        from ray_tpu.util.metrics import local_snapshot

        snap = local_snapshot()
        return tuple(
            snap.get(f"ray_tpu_stream_{name}_total", ("", {}, ""))[1].get(
                (), 0.0)
            for name in ("items", "item_blocked", "items_carried"))

    return paced_items, counts


def _consumer_actor():
    paced_items, counts = _paced_items()

    @ray_tpu.remote
    class Consumer:
        """A consumer in a worker's process: what it asks of the node
        manager crosses a socket, frame by frame."""

        def open(self, n, gap_s, sealed_first):
            from ray_tpu.core.runtime_context import current_runtime

            self.gen = paced_items.remote(n, gap_s)
            if sealed_first:
                ray_tpu.get(self.gen.completed, timeout=60)
            return current_runtime().worker_id

        def drain(self):
            before = counts()
            got = [ray_tpu.get(ref) for ref in self.gen]
            return got, tuple(a - b for a, b in zip(counts(), before))

    return Consumer.remote()


def _count_frames(nm, monkeypatch):
    """``{(worker id, frame type): frames}`` as the node manager's
    dispatch sees them from now on."""
    import collections

    frames = collections.Counter()
    real = nm._dispatch_message_op

    async def counted(w, msg, clock=None):
        frames[w.worker_id, msg["type"]] += 1
        return await real(w, msg, clock)

    monkeypatch.setattr(nm, "_dispatch_message_op", counted)
    return frames


@pytest.mark.parametrize("pace", ["sealed_first", "paced_producer"])
def test_a_local_item_costs_its_consumer_one_wait_and_nothing_else(
        ray_tpu_start, monkeypatch, pace):
    """N items are N + 1 ``wait`` requests (the last finds the end), no
    ``get_locations`` and no ``blocked`` / ``unblocked`` frame: sealed
    before they are asked for none parks, behind a paced producer every
    one does, and the node manager keeps that book itself."""
    from ray_tpu.core.runtime_context import current_runtime

    n = 10
    consumer = _consumer_actor()
    sealed_first = pace == "sealed_first"
    worker = ray_tpu.get(consumer.open.remote(
        n, 0.0 if sealed_first else 0.03, sealed_first), timeout=60)
    frames = _count_frames(current_runtime()._nm, monkeypatch)
    got, (items, blocked, carried) = ray_tpu.get(consumer.drain.remote(),
                                                 timeout=60)
    assert got == list(range(n)) and items == n and carried == n
    assert frames[worker, "wait"] == n + 1, frames
    for kind in ("get_locations", "blocked", "unblocked"):
        assert frames[worker, kind] == 0, frames
    if sealed_first:
        assert blocked == 0
    else:
        assert blocked >= n - 1, blocked  # item 0 may be sealed by now


def test_a_consumer_on_the_only_cpu_gives_it_up_to_its_producer():
    """The consumer task holds the node's one CPU and its producer needs
    it: the parked wait releases it at the node manager, with no frame
    from the worker, and takes it back with the reply."""
    ray_tpu.init(num_cpus=1, system_config={"num_prestart_workers": 1})
    try:
        paced_items, counts = _paced_items()

        @ray_tpu.remote(num_cpus=1)
        def consume(n):
            before = counts()
            got = [ray_tpu.get(ref) for ref in paced_items.remote(n, 0.01)]
            return got, tuple(a - b for a, b in zip(counts(), before))

        got, (items, blocked, carried) = ray_tpu.get(consume.remote(8),
                                                     timeout=120)
        assert got == list(range(8))
        assert (items, blocked, carried) == (8, 8, 8)
    finally:
        ray_tpu.shutdown()


def _no_request_from_this_thread(rt, monkeypatch):
    """Calls of ``rt._get_locations`` made by the calling thread, as a
    list that the wrapper it installs appends to."""
    import threading

    me, asked = threading.get_ident(), []
    real = rt._get_locations

    def counted(ids, timeout):
        if threading.get_ident() == me:
            asked.append(list(ids))
        return real(ids, timeout)

    monkeypatch.setattr(rt, "_get_locations", counted)
    return asked


def test_an_item_over_the_inline_limit_is_carried_as_its_store_location(
        ray_tpu_start, monkeypatch):
    import numpy as np

    from ray_tpu.core.config import get_config
    from ray_tpu.core.object_store import InlineLocation
    from ray_tpu.core.runtime_context import current_runtime

    n, words = 4, get_config().max_inline_object_size // 4

    @ray_tpu.remote(num_returns="streaming")
    def big(n):
        for i in range(n):
            yield np.full(words, i, dtype=np.int64)  # twice the limit

    rt = current_runtime()
    asked = _no_request_from_this_thread(rt, monkeypatch)
    carried0 = _carried()
    for i, ref in enumerate(big.remote(n)):
        loc = rt._loc_cache[ref.id()]
        assert not isinstance(loc, InlineLocation), loc
        value = ray_tpu.get(ref)
        assert value.shape == (words,) and (value == i).all()
    assert i == n - 1 and _carried() - carried0 == n
    assert asked == []


def test_an_item_from_another_node_is_not_carried_and_is_pulled():
    """The fall-through: the reply of the wait names a remote item ready
    and brings no location; ``get`` asks ``get_locations``, which pulls
    it. Same values, same order."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_resources={"CPU": 2},
                system_config={"log_to_driver": False})
    try:
        c.add_node(num_cpus=2, resources={"gadget": 1})
        c.wait_for_nodes(2)

        @ray_tpu.remote(num_returns="streaming", resources={"gadget": 1})
        def far(n):
            for i in range(n):
                yield {"item": i}

        n = 6
        before, carried0 = _stream_counters(), _carried()
        got = [ray_tpu.get(r)["item"] for r in far.remote(n)]
        assert got == list(range(n))
        assert _stream_counters()[0] - before[0] == n
        assert _carried() == carried0
    finally:
        c.shutdown()


@pytest.mark.parametrize("kind,carried", [
    ("inline", True), ("store", True), ("remote", False),
    ("spilled", False)])
def test_the_waits_reply_carries_what_a_process_of_the_node_can_read(
        ray_tpu_start, kind, carried):
    """The node manager chooses by the entry it finds: no flag of the
    caller's. What it leaves out is ready all the same."""
    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.object_store import (InlineLocation, RemoteLocation,
                                           ShmLocation, SpilledLocation)
    from ray_tpu.core.runtime_context import current_runtime

    nm = current_runtime()._nm
    loc = {"inline": InlineLocation(b"x" * 8192),
           "store": ShmLocation("rtpu-test-none", 1 << 20),
           "remote": RemoteLocation("ab" * 16, 64),
           "spilled": SpilledLocation("/nonexistent/spill", 64)}[kind]
    oid, unsealed = ObjectID.from_random(), ObjectID.from_random()

    async def seal_and_wait():
        nm.directory.add(oid, loc, initial_refs=1)
        nm._sealed.add(oid)
        try:
            return await nm.wait_carrying([oid, unsealed], 5.0)
        finally:
            nm._sealed.discard(oid)
            nm.directory._entries.pop(oid, None)

    ready, locations, parked = nm.call_sync(seal_and_wait())
    assert ready == [oid] and not parked
    assert locations == ({oid: loc} if carried else {})
    assert not nm._parked_waits


@pytest.mark.parametrize("ending", ["error", "timeout"])
def test_a_stream_that_ends_badly_leaves_nothing_pinned_or_kept(
        ray_tpu_start, ending):
    """After a producer's error or a consumer's timeout, with the refs
    dropped: no item stays sealed at the node manager, and none of the
    carried locations stays in the consumer's process."""
    from ray_tpu.core.exceptions import GetTimeoutError
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.core.streaming import stream_item_id

    @ray_tpu.remote(num_returns="streaming")
    def produce(ending):
        yield 0
        yield 1
        if ending == "error":
            raise ValueError("stream broke")
        time.sleep(60)
        yield 2

    rt = current_runtime()
    gen = produce.remote(ending)
    gen.item_timeout_s = 1.0 if ending == "timeout" else None
    ids = [stream_item_id(gen._task_id, i) for i in range(2)]
    refs = [next(gen), next(gen)]
    assert all(oid in rt._loc_cache for oid in ids)
    assert [ray_tpu.get(r) for r in refs] == [0, 1]
    with pytest.raises(ValueError if ending == "error" else GetTimeoutError):
        next(gen)
    del refs, gen
    assert not any(oid in rt._loc_cache for oid in ids)
    deadline = time.monotonic() + 20
    while rt._wait(ids, 2, 0) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert rt._wait(ids, 2, 0) == []


def test_a_served_streams_fetch_asks_the_node_manager_nothing(
        actor_route, monkeypatch):
    """``handle.stream``: the ``fetch`` hop, ``ray_tpu.get(ref)``, reads
    what the wait's reply brought, or on the direct route what the
    item's frame brought; the stream's end does too."""
    from ray_tpu import serve
    from ray_tpu.core.runtime_context import current_runtime

    n = 6

    @serve.deployment(num_replicas=1)
    class Tokens:
        def stream(self, _):
            for i in range(n):
                time.sleep(0.01)
                yield {"token": i}

    handle = serve.run(Tokens.bind(), name="toks41")
    try:
        stream = handle.options(method="stream")

        def one_stream():
            carried0, direct0 = _carried(), _direct_items()
            got = [item["token"] for item in stream.stream(None)]
            assert got == list(range(n))
            return _carried() - carried0, _direct_items() - direct0

        want = (0, n) if actor_route == "direct" else (n, 0)
        deadline = time.monotonic() + 30
        while one_stream() != want:  # routes resolved, channel ready
            assert time.monotonic() < deadline
        asked = _no_request_from_this_thread(current_runtime(), monkeypatch)
        assert one_stream() == want
        assert asked == []
    finally:
        serve.shutdown()


# ---- an actor's stream on the direct channel its call went out on (PR 56) --


@ray_tpu.remote
class _Chunks:
    """A producer with a ``ping`` (``_on_route``) whose streams say how
    far they got (``sent``)."""

    def __init__(self):
        self.sent = 0

    def ping(self):
        return self.sent

    def nap(self, seconds):
        time.sleep(seconds)
        return seconds

    def hold_seals(self, hold):
        """Keep this worker's ``direct_done_batch`` buffer from leaving
        (whatever would flush it: its size, its age, a request of any
        thread) until told to let it go."""
        from ray_tpu.core.runtime_context import current_runtime

        worker = current_runtime().before_block.__self__
        if hold:
            worker._flush_nm_dones = lambda force=False: None
        else:
            del worker._flush_nm_dones
            worker._flush_nm_dones(force=True)

    def produce(self, n, gap_s=0.0, fail_at=None, words=0, hold_after=None):
        import numpy as np

        for i in range(n):
            if i == fail_at:
                raise ValueError("stream broke")
            time.sleep(gap_s)
            self.sent = i + 1
            yield np.full(words, i, dtype=np.int64) if words else i
            if i == hold_after:
                time.sleep(30)


def _stream(actor, *args, **kwargs):
    """A stream of ``_Chunks.produce`` none of whose items is waited
    for longer than a minute (no test below can hang)."""
    gen = actor.produce.options(num_returns="streaming").remote(
        *args, **kwargs)
    gen.item_timeout_s = 60.0
    return gen


def _store_bytes():
    from ray_tpu.core.runtime_context import current_runtime

    return current_runtime()._nm.directory.used_bytes


def _sealed_items(task_id, n):
    """Which of a stream's first ``n`` items the node manager holds."""
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.core.streaming import stream_item_id

    ids = [stream_item_id(task_id, i) for i in range(n)]
    return current_runtime()._wait(ids, n, 0)


def _over_the_inline_limit():
    from ray_tpu.core.config import get_config

    return get_config().max_inline_object_size // 4  # int64s: twice it


@pytest.mark.parametrize("case", [
    "order_and_count", "error_mid_stream", "item_timeout",
    "abandoned_releases_its_items", "a_ref_handed_to_a_third_task"])
def test_an_actors_stream_on_either_route(actor_route, case):
    """What a consumer sees of an actor's stream is the same on both
    routes: order, count, error, timeout, what an abandoned stream
    leaves behind (nothing) and a ref that goes on to another task."""
    from ray_tpu.core.exceptions import GetTimeoutError

    actor = _on_route(_Chunks.remote(), actor_route)
    direct0 = _direct_items()
    if case == "order_and_count":
        n = 150
        gen = _stream(actor, n)
        assert [ray_tpu.get(r) for r in gen] == list(range(n))
        assert ray_tpu.get(gen.completed, timeout=30) == n
        with pytest.raises(StopIteration):
            next(gen)
    elif case == "error_mid_stream":
        n, got = 3, []
        gen = _stream(actor, 5, fail_at=3)
        with pytest.raises(ValueError, match="stream broke"):
            for ref in gen:
                got.append(ray_tpu.get(ref))
        assert got == [0, 1, 2]
        with pytest.raises(ValueError, match="stream broke"):
            next(gen)  # and again, as often as it is asked
    elif case == "item_timeout":
        n = 1
        gen = _stream(actor, 2, hold_after=0)
        assert ray_tpu.get(next(gen)) == 0
        gen.item_timeout_s = 0.5
        t0 = time.monotonic()
        with pytest.raises(GetTimeoutError):
            next(gen)
        assert 0.5 <= time.monotonic() - t0 < 10
    elif case == "abandoned_releases_its_items":
        n, total, words = 1, 5, _over_the_inline_limit()
        start = _store_bytes()
        gen = _stream(actor, total, words=words)
        task_id = gen._task_id
        first = ray_tpu.get(next(gen))
        assert first.shape == (words,) and (first == 0).all()
        ray_tpu.get(gen.completed, timeout=60)
        _wait_for(lambda: len(_sealed_items(task_id, total)),
                  lambda k: k == total)
        assert _store_bytes() >= start + total * words * 8
        del gen, first
        assert _wait_for(lambda: (_sealed_items(task_id, total),
                                  _store_bytes()),
                         lambda left: left == ([], start)) == ([], start)
    else:
        n = 4

        @ray_tpu.remote
        def double(x):
            return 2 * x

        # At once, whichever of the item's frame and its seal at the
        # node manager is ahead.
        doubled = [double.remote(ref) for ref in _stream(actor, n)]
        assert ray_tpu.get(doubled, timeout=60) == [0, 2, 4, 6]
    assert _direct_items() - direct0 == (n if actor_route == "direct" else 0)


def test_a_release_that_overtakes_its_seal_leaves_the_count_at_zero(
        ray_tpu_start):
    """The consumer's release and the producer's seal come on two
    sockets: a release that is first takes the consumer's placeholder
    below zero, where the sweep leaves it past the grace; the seal's pin
    brings it back to zero, and the entry, with its store bytes, goes."""
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.core.streaming import stream_item_id

    rt = current_runtime()
    counts = rt._nm.directory._refcounts
    actor = _on_route(_Chunks.remote(), "direct")
    ray_tpu.get(actor.hold_seals.remote(True), timeout=30)
    start, words = _store_bytes(), _over_the_inline_limit()
    gen = _stream(actor, 1, words=words)
    assert gen._stream is not None
    oid = stream_item_id(gen._task_id, 0)
    ref = next(gen)
    assert (ray_tpu.get(ref) == 0).all()
    assert oid not in counts  # neither socket has told of it
    del ref
    rt.refs.flush()  # the release, and before it the placeholder
    assert _wait_for(lambda: counts.get(oid), lambda c: c == -1) == -1
    with pytest.raises(StopIteration):
        next(gen)
    time.sleep(2.5)  # the grace is 1 s: an entry AT zero would be gone
    assert counts.get(oid) == -1 and _store_bytes() == start
    ray_tpu.get(actor.hold_seals.remote(False), timeout=30)
    # The batch: pinned on the placeholder, so at zero; then collected.
    assert _wait_for(lambda: counts.get(oid), lambda c: c == 0) == 0
    assert _wait_for(lambda: (counts.get(oid), _store_bytes()),
                     lambda left: left == (None, start)) == (None, start)


@pytest.mark.parametrize("producer", ["retriable_actor_call", "task"])
def test_a_stream_that_may_be_retried_or_has_no_channel_stays_with_the_node_manager(
        ray_tpu_start, monkeypatch, producer):
    """The route is what the spec says: hit share 0, carried share 1."""
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.core.streaming import stream_key

    rt, n = current_runtime(), 5
    direct0, carried0 = _direct_items(), _carried()
    if producer == "task":
        gen = _stream_task.remote(n, False)
    else:
        actor = _on_route(_Chunks.remote(), "direct")
        submit = rt.submit

        def retriable(spec):
            spec.max_retries = spec.retries_left = 1
            return submit(spec)

        monkeypatch.setattr(rt, "submit", retriable)
        gen = _stream(actor, n)
        assert gen._retriable
    assert gen._stream is None
    first = next(gen)
    if producer != "task":
        assert rt.kv_get(stream_key(gen._task_id)) == b"1"
    assert [ray_tpu.get(r) for r in [first, *gen]] == list(range(n))
    assert _direct_items() == direct0 and _carried() - carried0 == n


def _kill_channel(actor):
    """Cut the direct channel to ``actor`` as a network fault would (the
    raw socket: not a close of ours, which fails its calls)."""
    from ray_tpu.core.runtime_context import current_runtime

    chan = current_runtime()._direct_state(actor._actor_id)["chan"]
    chan.conn.close()
    return chan


def test_a_channel_killed_mid_stream_ends_it_with_the_actors_death(
        ray_tpu_start):
    """A stream that was handed an item is not replayed: what came stays
    readable, the next ``next()`` raises ActorDiedError (each time), the
    generator ran once, and the next stream falls back and completes."""
    from ray_tpu.core.exceptions import ActorDiedError

    actor = _on_route(_Chunks.remote(), "direct")
    gen = _stream(actor, 4, gap_s=0.5)
    first = next(gen)
    _kill_channel(actor).drained.wait(10)
    for _ in range(2):
        with pytest.raises(ActorDiedError):
            next(gen)
    assert ray_tpu.get(first) == 0
    direct0, carried0 = _direct_items(), _carried()
    again = _stream(actor, 3)
    assert again._stream is None
    assert [ray_tpu.get(r) for r in again] == [0, 1, 2]
    assert (_direct_items(), _carried() - carried0) == (direct0, 3)
    # The first generator ran once and stopped where its send failed.
    assert ray_tpu.get(actor.ping.remote(), timeout=30) == 3


def test_a_stream_that_was_handed_nothing_is_replayed_over_the_node_manager(
        ray_tpu_start):
    """Its channel dies while the call still waits behind another: it
    replays like any call and the consumer goes on over the
    node-manager route, every item, once."""
    actor = _on_route(_Chunks.remote(), "direct")
    carried0, direct0 = _carried(), _direct_items()
    nap = actor.nap.remote(1.0)
    gen = _stream(actor, 4)
    assert gen._stream is not None
    time.sleep(0.2)  # both frames are at the worker, the nap running
    _kill_channel(actor)
    assert [ray_tpu.get(r) for r in gen] == [0, 1, 2, 3]
    assert ray_tpu.get(nap, timeout=30) == 1.0
    assert gen._stream is None
    assert (_direct_items(), _carried() - carried0) == (direct0, 4)
    assert ray_tpu.get(actor.ping.remote(), timeout=30) == 4


def test_an_actors_stream_from_another_node_rides_the_channel_too():
    """A producer on another node: inline items come in their frames, a
    store put comes as a held remote location that the node manager
    pulls (told of it by the consumer, as of a remote direct result), a
    ref goes on to a task, and the consumer's own node manager counts
    each taken item once until its ref drops."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core.runtime_context import current_runtime

    c = Cluster(head_resources={"CPU": 2},
                system_config=dict(_START, log_to_driver=False))
    try:
        c.add_node(num_cpus=2, resources={"gadget": 1})
        c.wait_for_nodes(2)
        actor = _on_route(
            _Chunks.options(resources={"gadget": 1}).remote(), "direct")
        rt = current_runtime()
        assert rt._direct_state(actor._actor_id)["chan"].remote
        direct0, words = _direct_items(), _over_the_inline_limit()
        assert [ray_tpu.get(r) for r in _stream(actor, 5)] == list(range(5))
        refs = list(_stream(actor, 3, words=words))
        assert [(v.shape, int(v[0])) for v in
                (ray_tpu.get(r) for r in refs)] == [
                    ((words,), i) for i in range(3)]

        @ray_tpu.remote
        def double(x):
            return 2 * x

        doubled = [double.remote(r) for r in _stream(actor, 3)]
        assert ray_tpu.get(doubled, timeout=60) == [0, 2, 4]
        assert _direct_items() - direct0 == 11
        counts, ids = rt._nm.directory._refcounts, [r.id() for r in refs]
        assert [counts.get(oid) for oid in ids] == [1, 1, 1]
        del refs
        assert _wait_for(lambda: [counts.get(oid) for oid in ids],
                         lambda left: left == [None] * 3) == [None] * 3
    finally:
        c.shutdown()


def test_a_direct_streams_two_threads_lose_nothing_under_a_short_switch_interval():
    """``DirectStream`` alone, more threads than cores: one reader puts
    24 streams' frames, a consumer a stream takes them; every frame
    comes once, in order, each stream's end after its last frame, and a
    stream abandoned half way hands the rest to ``release``."""
    import sys
    import threading

    from ray_tpu.core.streaming import DirectStream

    n, streams, released = 400, 24, []
    made = [DirectStream(False, True, released.append)
            for _ in range(streams)]
    got = [[] for _ in made]
    ends = [None] * streams

    def consume(k):
        while True:
            frame, _ = made[k].take(30.0)
            if frame is None:
                ends[k] = made[k].ended
                return
            got[k].append(frame["x"])
            if k == 0 and len(got[k]) == n // 2:
                got[k].extend(f["x"] for f in made[k].abandon())
                ends[k] = "abandoned"
                return

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=consume, args=(k,))
                   for k in range(streams)]
        for t in threads:
            t.start()
        for x in range(n):
            for stream in made:
                stream.put({"i": b"", "x": x, "loc": None})
        for stream in made:
            stream.end(DirectStream.DONE)
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert got[1:] == [list(range(n))] * (streams - 1)
    assert ends[1:] == [DirectStream.DONE] * (streams - 1)
    assert ends[0] == "abandoned"
    assert got[0] + [f["x"] for f in released] == list(range(n))
    assert made[0].received == n
