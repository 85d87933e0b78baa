"""Observability: tracing pipeline, metrics merge/exposition, serve
request telemetry, device metrics (ref analogue: test_metrics_agent.py +
test_tracing.py + serve's metrics tests)."""

import importlib.util
import json
import os
import re
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import metrics, prometheus

# ray_tpu.core re-exports the timeline() FUNCTION under the same name as
# the module; grab the module itself.
import ray_tpu.core.timeline  # noqa: E402
import sys  # noqa: E402

timeline = sys.modules["ray_tpu.core.timeline"]


@pytest.fixture
def serve_cluster(ray_tpu_start):
    yield ray_tpu_start
    serve.shutdown()


def _poll(fn, timeout=12.0, interval=0.2):
    """Poll fn() until it returns a truthy value (workers flush metric
    and span buffers on a 0.5s cadence)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(interval)
    return fn()


# ------------------------------------------------------------- tracing


def test_otlp_parent_child_linkage(ray_tpu_start):
    """Nested task spans share one trace; the child's parentSpanId is
    the submitting span's hashed id (satellite: timeline_otlp linkage)."""

    @ray_tpu.remote
    def inner():
        return 1

    @ray_tpu.remote
    def outer():
        return ray_tpu.get(inner.remote())

    assert ray_tpu.get(outer.remote(), timeout=30) == 1

    def spans_ready():
        evs = timeline.timeline()
        names = {e["name"] for e in evs}
        if {"outer", "inner"} <= names:
            return evs
        return None

    evs = _poll(spans_ready)
    by_name = {e["name"]: e for e in evs if e["name"] in ("outer", "inner")}
    assert set(by_name) == {"outer", "inner"}, by_name
    o, i = by_name["outer"]["args"], by_name["inner"]["args"]
    assert o["trace_id"] and o["trace_id"] == i["trace_id"]
    assert i["parent_id"] == o["span_id"]

    payload = timeline.timeline_otlp()
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    named = {s["name"]: s for s in spans if s["name"] in ("outer", "inner")}
    assert named["inner"]["parentSpanId"] == named["outer"]["spanId"]
    assert named["inner"]["traceId"] == named["outer"]["traceId"]
    assert named["inner"]["parentSpanId"] == timeline._otlp_id(
        o["span_id"], 8
    )


def test_timeline_chrome_rows_grouped_by_node(ray_tpu_start):
    """Chrome-trace rows group by node (pid) and worker process (tid)
    (satellite: chrome-trace grouping was untested)."""

    @ray_tpu.remote
    def work():
        return os.getpid()

    ray_tpu.get([work.remote() for _ in range(4)], timeout=30)
    evs = _poll(lambda: [e for e in timeline.timeline()
                         if e["name"] == "work"] or None)
    node8 = ray_tpu_start.node_id.hex()[:8]
    for e in evs:
        assert e["pid"] == f"node:{node8}"
        assert e["tid"].startswith("worker:")
        assert e["ph"] == "X"


def test_trace_propagation_proxy_to_replica(serve_cluster):
    """Acceptance: one HTTP request yields a single trace spanning
    proxy -> replica with correct parentSpanId links, honoring the
    incoming W3C traceparent."""

    @serve.deployment
    def obs(x):
        return x

    handle = serve.run(obs.bind(), route_prefix="obs")
    ext_trace = "a" * 32
    ext_span = "b" * 16
    req = urllib.request.Request(
        f"http://127.0.0.1:{handle.http_port}/obs",
        data=json.dumps(7).encode(),
        headers={"Content-Type": "application/json",
                 "traceparent": f"00-{ext_trace}-{ext_span}-01"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert json.loads(resp.read()) == {"result": 7}

    def linked():
        evs = timeline.timeline()
        proxies = [e for e in evs if e["name"] == "http:obs"]
        if not proxies:
            return None
        proxy = proxies[0]["args"]
        children = [
            e for e in evs
            if e["args"]["parent_id"] == proxy["span_id"]
            and e["name"] != "http:obs"
        ]
        return (proxy, children) if children else None

    proxy, children = _poll(linked)
    # The proxy span joined the EXTERNAL trace and parents to it.
    assert proxy["trace_id"] == ext_trace
    assert proxy["parent_id"] == ext_span
    replica_span = children[0]["args"]
    assert replica_span["trace_id"] == ext_trace

    payload = timeline.timeline_otlp()
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    trace_spans = [
        s for s in spans if s["traceId"] == timeline._otlp_id(ext_trace, 16)
    ]
    assert len(trace_spans) >= 2  # proxy + replica execution
    proxy_otlp = next(s for s in trace_spans if s["name"] == "http:obs")
    assert proxy_otlp["parentSpanId"] == timeline._otlp_id(ext_span, 8)
    child_otlp = [
        s for s in trace_spans
        if s.get("parentSpanId") == proxy_otlp["spanId"]
    ]
    assert child_otlp, trace_spans


def test_traceparent_parse_and_format():
    assert timeline.parse_traceparent(None) is None
    assert timeline.parse_traceparent("garbage") is None
    assert timeline.parse_traceparent("00-" + "0" * 32 + "-" + "1" * 16
                                      + "-01") is None  # all-zero trace
    tid, sid = "ab" * 16, "cd" * 8
    assert timeline.parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid)
    hdr = timeline.format_traceparent("1234abcd" * 2, "feed" * 4)
    assert timeline.parse_traceparent(hdr) is not None


# ------------------------------------------------------------- metrics


def test_histogram_merge_union_bounds(ray_tpu_start):
    """Satellite regression: two processes observing one histogram with
    DIFFERENT boundaries merge on the union instead of zip-truncating."""
    import cloudpickle

    h = metrics.Histogram("merge_hist_seconds", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(5.0)
    # A second "process" flushed the same metric with other bounds.
    fake = {
        "merge_hist_seconds": (
            "histogram",
            {(): {"count": 2, "sum": 0.4, "bounds": [0.2],
                  "buckets": [1, 1]}},
            "",
        )
    }
    ray_tpu_start.kv_put("__metrics__/999999", cloudpickle.dumps(fake))
    series = metrics.get_metrics_report()["merge_hist_seconds"]["series"][()]
    assert series["bounds"] == [0.1, 0.2, 1.0]
    assert series["count"] == 4
    assert series["sum"] == pytest.approx(5.45)
    # 0.05 -> le=0.1; fake's (0, 0.2] -> le=0.2; overflows add up.
    assert series["buckets"] == [1, 1, 0, 2]
    assert sum(series["buckets"]) == series["count"]


def test_metric_kind_conflict_warns_and_keeps_first():
    """Satellite: re-registering a name under another kind warns once
    and does NOT corrupt the original series."""
    c = metrics.Counter("kindconflict_metric_total")
    c.inc(2)
    with pytest.warns(UserWarning, match="conflicting kind"):
        g = metrics.Gauge("kindconflict_metric_total")
        g.set(99.0)
    with metrics._registry.lock:
        kind, series = metrics._registry.metrics[
            "kindconflict_metric_total"
        ]
    assert kind == "counter"
    assert series[()] == 2.0  # the gauge write was dropped, not merged


def test_user_lines_help_and_newline_escaping():
    """Satellite: user metrics get # HELP lines; newlines in label
    values are escaped (raw ones corrupt the exposition document)."""
    report = {
        "app_things_total": {
            "type": "counter",
            "help": "Line one\nline two",
            "series": {(("path", 'a\nb"c\\d'),): 3},
        }
    }
    text = "\n".join(prometheus._user_lines(report))
    assert "# HELP app_things_total Line one\\nline two" in text
    assert '# TYPE app_things_total counter' in text
    assert 'path="a\\nb\\"c\\\\d"' in text
    # Exactly 3 lines: HELP, TYPE, and ONE sample (the raw newline in
    # the label value did not split the sample line).
    assert len(text.split("\n")) == 3


def test_serve_request_telemetry(serve_cluster):
    """Acceptance: after a test_serve-style workload the exposition
    contains the serve latency histogram (cumulative, with +Inf),
    ongoing-request gauge, and at least one device series."""
    from ray_tpu.util import device_metrics

    @serve.deployment
    def double(x):
        return x * 2

    def recorded(text):
        """HTTP requests of ``double`` the proxy has recorded: the
        counter it bumps last, behind the latency's observation."""
        count = re.search(
            r'ray_tpu_serve_requests_total\{code="200",deployment="double"'
            r',protocol="http"\} (\d+)', text)
        return int(count.group(1)) if count else 0

    handle = serve.run(double.bind(), route_prefix="double")
    futs = [handle.remote(i) for i in range(8)]
    assert [f.result(timeout=30) for f in futs] == [i * 2 for i in range(8)]
    before = recorded(prometheus.render())
    for _ in range(3):
        req = urllib.request.Request(
            f"http://127.0.0.1:{handle.http_port}/double",
            data=json.dumps(21).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert json.loads(resp.read()) == {"result": 42}

    import jax  # noqa: F401 — device sampling is gated on jax presence

    device_metrics._last_sample = 0.0  # defeat the sampling throttle
    # The proxy records a request after it has written the reply: the
    # third one's observation may trail the read of its body.
    def page():
        text = prometheus.render()
        return text if recorded(text) >= before + 3 else None

    text = _poll(page)
    assert re.search(
        r'ray_tpu_serve_request_latency_seconds_bucket\{deployment="double"'
        r',protocol="http",le="0\.005"\} \d+', text), text[:2000]
    inf = re.search(
        r'ray_tpu_serve_request_latency_seconds_bucket\{deployment="double"'
        r',protocol="http",le="\+Inf"\} (\d+)', text)
    cnt = re.search(
        r'ray_tpu_serve_request_latency_seconds_count\{deployment="double"'
        r',protocol="http"\} (\d+)', text)
    # Cumulative buckets: +Inf equals _count; 3 requests observed here
    # (the process-wide registry may carry observations from other tests
    # in this process, so >= not ==).
    assert inf and cnt and inf.group(1) == cnt.group(1)
    assert int(cnt.group(1)) >= 3
    ok = re.search(
        r'ray_tpu_serve_requests_total\{code="200",deployment="double"'
        r',protocol="http"\} (\d+(\.\d+)?)', text)
    assert ok and float(ok.group(1)) >= 3
    assert "ray_tpu_serve_ongoing_requests" in text
    assert "ray_tpu_device_" in text
    assert "# HELP ray_tpu_serve_request_latency_seconds " in text
    # Core per-task-duration histogram joined the exposition.
    assert "ray_tpu_task_duration_seconds_bucket" in text
    assert re.search(r"ray_tpu_task_duration_seconds_count \d+", text)


def test_replica_queue_and_processing_metrics(serve_cluster):
    """Replica-side queue-wait and execution-time histograms flow back
    through the KV pipeline from the replica worker process."""

    @serve.deployment
    def slowish(x):
        time.sleep(0.02)
        return x

    handle = serve.run(slowish.bind())
    futs = [handle.remote(i) for i in range(6)]
    assert [f.result(timeout=30) for f in futs] == list(range(6))

    def replica_series():
        report = metrics.get_metrics_report()
        proc = report.get("ray_tpu_serve_replica_processing_seconds")
        wait = report.get("ray_tpu_serve_queue_wait_seconds")
        if not proc or not wait:
            return None
        total = sum(v["count"] for v in proc["series"].values())
        return (proc, wait) if total >= 6 else None

    proc, wait = _poll(replica_series)
    (tags_key, point) = next(iter(proc["series"].items()))
    tags = dict(tags_key)
    assert tags["deployment"] == "slowish"
    assert point["sum"] >= 6 * 0.02 * 0.5  # execution time was measured
    assert sum(v["count"] for v in wait["series"].values()) >= 6


def test_dashboard_serve_and_device_routes(serve_cluster):
    """New dashboard JSON routes: /api/serve_metrics and /api/devices."""
    from ray_tpu import dashboard

    @serve.deployment
    def ping(x):
        return x

    handle = serve.run(ping.bind())
    assert handle.remote(1).result(timeout=30) == 1
    port = dashboard.start_dashboard(port=0)
    try:
        def fetch(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                return json.loads(r.read())

        sm = _poll(lambda: (fetch("/api/serve_metrics")["metrics"]
                            or None))
        assert any(k.startswith("ray_tpu_serve_") for k in sm)
        devices = fetch("/api/devices")
        assert devices["local"], devices  # 8 virtual CPU devices
        assert all("device" in d for d in devices["local"])
    finally:
        dashboard.stop_dashboard()


def test_device_metrics_sample_and_jit_counter(ray_tpu_start):
    """device_metrics: sample() publishes per-device gauges;
    instrumented_jit counts compiles (one per new input shape)."""
    import jax.numpy as jnp

    from ray_tpu.util import device_metrics

    snap = device_metrics.sample(force=True)
    assert len(snap) >= 1
    with metrics._registry.lock:
        kind, series = metrics._registry.metrics["ray_tpu_device_count"]
    assert kind == "gauge"
    # One series per (node, platform); the process registry may carry
    # tags from earlier clusters in this pytest process.
    node = device_metrics.node_tag()
    assert series[
        (("node", node), ("platform", snap[0]["platform"]))
    ] == len(snap)

    calls = {"n": 0}

    def f(x):
        calls["n"] += 1
        return x + 1

    jf = device_metrics.instrumented_jit(f)
    jf(jnp.ones((2,)))
    jf(jnp.ones((2,)))  # cache hit
    jf(jnp.ones((3,)))  # new shape -> recompile
    assert calls["n"] == 2  # traced twice, cached once
    with metrics._registry.lock:
        _, series = metrics._registry.metrics[
            "ray_tpu_device_jit_compiles_total"
        ]
    assert sum(v for k, v in series.items()
               if ("fn", "f") in k) >= 2
    with metrics._registry.lock:
        _, secs = metrics._registry.metrics[
            "ray_tpu_device_jit_compile_seconds_total"
        ]
    assert sum(v for k, v in secs.items() if ("fn", "f") in k) > 0


def test_collective_counters(ray_tpu_start):
    """In-graph collectives count once per trace; host-level broadcast
    counts payload bytes."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import collectives

    mesh_devices = jax.local_devices()

    @jax.jit
    def summed(x):
        return jax.shard_map(
            lambda v: collectives.allreduce(v, axis="dp"),
            mesh=jax.make_mesh((len(mesh_devices),), ("dp",)),
            in_specs=jax.sharding.PartitionSpec("dp"),
            out_specs=jax.sharding.PartitionSpec("dp"),
        )(x)

    try:
        summed(jnp.ones((len(mesh_devices) * 2,)))
    except Exception:
        # shard_map API drift across jax versions: fall back to counting
        # via the host-level path only.
        pass
    g = collectives.init_collective_group(1, 0, "obs_grp")
    g.barrier(timeout_s=10)
    g.broadcast_obj({"x": 1}, root=0)
    report = metrics.get_metrics_report()
    calls = report.get("ray_tpu_device_collective_calls_total")
    assert calls is not None
    ops = {dict(k).get("op") for k in calls["series"]}
    assert "host_barrier" in ops and "host_broadcast" in ops
    assert "ray_tpu_device_collective_bytes_total" in report


# ------------------------------------------------------------- tooling


def _load_checker():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "check_metric_names.py")
    spec = importlib.util.spec_from_file_location(
        "check_metric_names", os.path.abspath(path)
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_metric_names_rules():
    """CI lint rules: invalid names, counters missing _total, and kind
    conflicts are all reported; the package's own metrics pass."""
    mod = _load_checker()
    fails = mod.validate(
        {"bad name": ("gauge", ""), "requests": ("counter", ""),
         "ok_total": ("counter", ""), "fine_seconds": ("histogram", "")},
        {"dup": ("counter", "gauge")},
    )
    assert len(fails) == 3
    assert any("bad name" in f for f in fails)
    assert any("requests" in f and "_total" in f for f in fails)
    assert any("dup" in f for f in fails)
    # Everything this test process has declared so far (the whole serve +
    # device metric surface) is lint-clean, except names test cases above
    # registered deliberately.
    declared = {
        k: v for k, v in metrics.declared_metrics().items()
        if k.startswith("ray_tpu_")
    }
    assert declared, "package metrics should be registered by now"
    assert mod.validate(declared, {}) == []
