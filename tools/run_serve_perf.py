"""Serve performance probe (BASELINE north-star: req/s + TTFT).

Workload shape follows the reference's serve release benchmark
(release/serve_tests/workloads/single_deployment_1k_noop_replica.py):
N concurrent HTTP clients -> per-node proxy -> deployment. Two probes:

1. noop deployment: request throughput + latency percentiles.
2. LLMDeployment (tiny model) via SSE streaming: client-measured TTFT
   percentiles + aggregate decode tokens/s under continuous batching.
   The replica asks for the chip (a ``tpu`` worker); a replica that
   finds another platform fails the probe — TTFT and tokens/s are
   device numbers. The driver never imports jax.

Usage: python tools/run_serve_perf.py [out.json]
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _pct(sorted_vals, p):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(p / 100.0 * len(sorted_vals)))
    return sorted_vals[i]


def noop_probe(port: int, clients: int = 8, seconds: float = 10.0):
    url = f"http://127.0.0.1:{port}/noop"
    lat = []
    lock = threading.Lock()
    stop = time.monotonic() + seconds

    def client():
        mine = []
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            req = urllib.request.Request(
                url, data=b"null",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                r.read()
            mine.append(time.perf_counter() - t0)
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    lat.sort()
    return {
        "clients": clients,
        "requests": len(lat),
        "req_per_s": len(lat) / dt,
        "p50_latency_s": _pct(lat, 50),
        "p99_latency_s": _pct(lat, 99),
    }


def llm_probe(port: int, clients: int = 4, requests_per_client: int = 3,
              max_new_tokens: int = 16):
    url = f"http://127.0.0.1:{port}/llm/stream"
    ttfts, totals = [], []
    tokens_count = [0]
    lock = threading.Lock()

    def client(i):
        for k in range(requests_per_client):
            body = json.dumps({"prompt": [1 + i, 2 + k, 3],
                               "max_new_tokens": max_new_tokens}).encode()
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json",
                         "Accept": "text/event-stream"})
            t0 = time.perf_counter()
            first = None
            n = 0
            with urllib.request.urlopen(req, timeout=300) as r:
                buf = b""
                while True:
                    chunk = r.read1(4096)
                    if not chunk:
                        break
                    buf += chunk
                    while b"\n\n" in buf:
                        frame, buf = buf.split(b"\n\n", 1)
                        if frame.startswith(b"data: "):
                            if first is None:
                                first = time.perf_counter() - t0
                            n += 1
                        elif frame.startswith(b"event: end"):
                            buf = b""
                            break
            with lock:
                if first is not None:
                    ttfts.append(first)
                totals.append(time.perf_counter() - t0)
                tokens_count[0] += n

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    ttfts.sort()
    return {
        "clients": clients,
        "requests": clients * requests_per_client,
        "max_new_tokens": max_new_tokens,
        "p50_ttft_s": _pct(ttfts, 50),
        "p99_ttft_s": _pct(ttfts, 99),
        "decode_tokens_per_s": tokens_count[0] / dt,
        "req_per_s": len(totals) / dt,
    }


def main():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.tpu import require_driver_off_jax
    from ray_tpu.serve import http_proxy
    from ray_tpu.serve.llm import LLMDeployment

    ray_tpu.init(num_cpus=max(2, (os.cpu_count() or 1)), num_tpus=1,
                 system_config={"log_to_driver": False})
    out = {}
    proxies = {}
    try:
        @serve.deployment(num_replicas=2)
        def noop(_):
            return "ok"

        serve.run(noop.bind(), name="noop")
        proxies = http_proxy.start_per_node_proxies(port=0)
        (_, port), = list(proxies.values())[:1]
        # warmup
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/noop", data=b"null",
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=60).read()
        out["noop_http"] = noop_probe(port)

        require_driver_off_jax()
        dep = serve.deployment(LLMDeployment).options(
            name="llm",
            ray_actor_options={"max_concurrency": 8, "num_tpus": 1},
        )
        llm = serve.run(dep.bind(max_batch=4, max_len=64), name="llm")
        stats = llm.options(method="stats").remote().result(timeout=300)
        if stats["platform"] != "tpu":
            raise SystemExit(
                f"llm probe needs the TPU; the replica's jax found "
                f"{stats['platform']!r}"
            )
        # warmup (compiles the tiny model's prefill/decode)
        wreq = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(wreq, timeout=300).read()
        out["llm_sse"] = llm_probe(port)
        out["llm_sse"]["device"] = {"platform": stats["platform"],
                                    "kind": stats["device_kind"]}
    finally:
        for actor, _ in proxies.values():
            try:
                ray_tpu.get(actor.shutdown.remote(), timeout=10)
                ray_tpu.kill(actor)
            except Exception:
                pass
        serve.shutdown()
        ray_tpu.shutdown()
    text = json.dumps(out, indent=1)
    print(text)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
