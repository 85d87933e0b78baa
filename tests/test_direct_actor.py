"""Direct actor-call transport (runtime._DirectChannel + worker_main
_direct_serve): same-node callers bypass the node manager for actor
method calls; replies return inline. Ref analogue:
core_worker/transport/direct_actor_task_submitter.h."""

import time

import pytest

import ray_tpu


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=2, system_config={"log_to_driver": False})
    yield
    ray_tpu.shutdown()


def _direct_states(runtime=None):
    from ray_tpu.core import runtime_context

    rt = runtime or runtime_context.current_runtime()
    return rt._direct_states


def test_ordering_across_switchover(rt):
    """Calls issued before and after the NM→direct switchover observe
    strict submission order (the discovery only completes once the NM
    queue for the actor drained)."""

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    vals = ray_tpu.get([c.inc.remote() for _ in range(300)])
    assert vals == list(range(1, 301))


def test_direct_channel_engages(rt):
    @ray_tpu.remote
    class A:
        def ping(self):
            return b"ok"

    a = A.remote()
    ray_tpu.get(a.ping.remote())
    deadline = time.time() + 10
    st = None
    while time.time() < deadline:
        ray_tpu.get(a.ping.remote())
        states = _direct_states()
        st = states.get(a.actor_id.binary())
        if st is not None and st["status"] == "ready":
            break
        time.sleep(0.05)
    assert st is not None and st["status"] == "ready", st


def test_ref_args_and_result_reuse(rt):
    """Object args resolve through the worker; direct results are
    registered with the NM so non-caller consumers can read them."""

    @ray_tpu.remote
    class Echo:
        def echo(self, x):
            return x * 2

    @ray_tpu.remote
    def consume(x):
        return x + 1

    e = Echo.remote()
    ray_tpu.get(e.echo.remote(1))  # switch to direct
    ref = ray_tpu.put(21)
    out = e.echo.remote(ref)       # ref arg over the direct channel
    assert ray_tpu.get(consume.remote(out)) == 43  # result feeds a task


def test_kill_fails_pending_direct_calls(rt):
    from ray_tpu.core.exceptions import ActorDiedError, TaskError

    @ray_tpu.remote
    class Slow:
        def ping(self):
            return b"ok"

        def nap(self, s):
            time.sleep(s)
            return "done"

    s = Slow.remote()
    for _ in range(3):
        ray_tpu.get(s.ping.remote())  # ensure direct channel is live
    ref = s.nap.remote(30)
    time.sleep(0.2)
    ray_tpu.kill(s)
    with pytest.raises((ActorDiedError, TaskError)):
        ray_tpu.get(ref, timeout=10)


def test_streaming_call_fences_direct_traffic(rt):
    """A streaming call interleaved with direct calls must not overtake
    them: it rides the channel in its sequence (since PR 56; before, the
    submit path fenced the channel and routed it through the NM, as it
    still does for a call that may be retried)."""

    @ray_tpu.remote
    class Gen:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def stream(self, k):
            for i in range(k):
                yield (self.n, i)

    g = Gen.remote()
    for _ in range(5):
        ray_tpu.get(g.bump.remote())  # direct channel live
    # burst of direct calls, then immediately a streaming call: the
    # generator must observe all 10 bumps.
    for _ in range(5):
        g.bump.remote()
    items = [ray_tpu.get(r) for r in
             g.stream.options(num_returns="streaming").remote(3)]
    assert [i for _, i in items] == [0, 1, 2]
    assert items[0][0] == 10
    # and afterwards order still holds
    assert ray_tpu.get(g.bump.remote()) == 11


def test_concurrent_actor_pool_direct(rt):
    """max_concurrency actors serve direct calls via the pool."""

    @ray_tpu.remote(max_concurrency=4)
    class Pooled:
        def __init__(self):
            import threading

            # All four calls must be IN FLIGHT at once to pass the
            # barrier; serial execution breaks it (no wall clock).
            self.barrier = threading.Barrier(4)

        def rendezvous(self):
            self.barrier.wait(timeout=30)
            return "x"

    p = Pooled.remote()
    out = ray_tpu.get([p.rendezvous.remote() for _ in range(4)],
                      timeout=60)
    assert out == ["x"] * 4


def test_named_actor_from_second_handle(rt):
    """A handle recreated by name reaches the same direct actor."""

    @ray_tpu.remote(name="direct-named")
    class N:
        def __init__(self):
            self.v = 0

        def setv(self, v):
            self.v = v
            return self.v

        def getv(self):
            return self.v

    n = N.remote()
    ray_tpu.get(n.setv.remote(7))
    h = ray_tpu.get_actor("direct-named")
    assert ray_tpu.get(h.getv.remote()) == 7


def test_chained_pending_direct_result(rt):
    """A call whose argument is a still-pending direct result routes via
    the NM (dep-gated) instead of riding the channel — the worker would
    otherwise execute it while the dependency's seal sits in a reply
    batch (review finding: chained-call deadlock)."""

    @ray_tpu.remote
    class Chain:
        def f(self):
            return 10

        def g(self, x):
            return x + 5

    c = Chain.remote()
    for _ in range(3):
        ray_tpu.get(c.f.remote())  # engage the direct channel
    r1 = c.f.remote()
    r2 = c.g.remote(r1)
    assert ray_tpu.get(r2, timeout=30) == 15
    # and a longer chain
    r = c.f.remote()
    for _ in range(5):
        r = c.g.remote(r)
    assert ray_tpu.get(r, timeout=30) == 35


def test_a_served_token_stream_rides_the_channel_and_the_node_manager_hears_in_batches(
        monkeypatch):
    """A tiny engine's tokens through a per-node proxy actor (a worker:
    what it asks of the node manager crosses a socket): every token
    comes, over the streams no ``wait`` and no ``put`` frame reaches the
    node manager from anyone, and every item the proxy was handed came
    on the direct channel (README "Stream delivery": the hit share)."""
    import collections
    import json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.serve import http_proxy
    from ray_tpu.serve.llm import LLMDeployment
    from ray_tpu.util.metrics import get_metrics_report

    def delivered():
        report = get_metrics_report()
        return tuple(
            report.get(f"ray_tpu_stream_items{kind}_total",
                       {"series": {}})["series"].get((), 0.0)
            for kind in ("", "_direct"))

    def tokens_of(port, new):
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/tiny/stream",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new_tokens": new}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(request, timeout=120) as reply:
            lines = [raw.decode().strip() for raw in reply]
        return [json.loads(line[5:])["token"] for line in lines
                if line.startswith("data:") and "token" in line]

    ray_tpu.init(num_cpus=4, system_config={
        "log_to_driver": False})
    proxies = {}
    try:
        dep = serve.deployment(LLMDeployment).options(
            name="tiny",
            ray_actor_options={"max_concurrency": 8, "num_cpus": 1})
        serve.run(dep.bind(max_batch=2, max_len=64), name="tiny")
        proxies = http_proxy.start_per_node_proxies(port=0)
        (_, port), = proxies.values()
        items0, direct0 = delivered()
        # The proxy's first call to the replica finds its channel.
        assert len(tokens_of(port, 2)) == 2
        frames = collections.Counter()
        nm = current_runtime()._nm
        real = nm._dispatch_message_op

        async def counted(w, msg, clock=None):
            frames[msg["type"]] += 1
            return await real(w, msg, clock)

        monkeypatch.setattr(nm, "_dispatch_message_op", counted)
        n, streams = 24, 3
        for _ in range(streams):
            assert len(tokens_of(port, n)) == n
        assert frames["wait"] == 0 and frames["put"] == 0, frames
        # ~16 items a batch; the completions' seals ride them too.
        assert 0 < frames["direct_done_batch"] <= streams * n // 4, frames
        deadline = time.time() + 30
        while time.time() < deadline:
            items, direct = (a - b for a, b in zip(delivered(),
                                                   (items0, direct0)))
            if items >= 2 + streams * n:
                break
            time.sleep(0.2)
        assert (items, direct) == (2 + streams * n, 2 + streams * n)
    finally:
        for actor, _ in proxies.values():
            ray_tpu.get(actor.shutdown.remote(), timeout=30)
            ray_tpu.kill(actor)
        serve.shutdown()
        ray_tpu.shutdown()
