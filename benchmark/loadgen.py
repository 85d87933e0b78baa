"""Load from a seed: the schedule, and the clients that keep it.

A traffic file fixes two length distributions and either a rate (an
open loop) or a number of streams (a closed loop). From them comes one
fixed multiset of prompt lengths, answer lengths and, for a rate, gaps
between arrivals (the distributions' quantiles, evenly spaced), so that
every seed offers the same work; the seed shuffles the lists and draws
the token ids. In an open loop requests go out when they are due whether
or not earlier ones have finished, and each is timed from when it was
due. In a closed loop ``concurrency`` clients each send the list's next
request the moment their last stream ends, and each is timed from when
it was sent.

Parameters a traffic file gives, so that a new mix of these is a data
file: ``rate_per_s`` (Poisson arrivals) or ``concurrency`` with
``requests`` (the length of the list the clients draw from), and
``prompt`` and ``output`` as ``lognormal`` (``median``, ``sigma``) or
``loguniform``, clipped to ``min`` and ``max``. Bursts, shared prefixes
or two classes of request come with the cell that needs them, as a
generator of its own.
"""

from __future__ import annotations

import http.client
import json
import math
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist
from typing import Dict, List, Mapping

import numpy as np


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec: Mapping, n: int) -> List[int]:
    """``n`` lengths at the evenly spaced quantiles of ``spec``'s
    distribution, clipped to its ``min`` and ``max``."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        raw = [math.exp(mu + sigma * NormalDist().inv_cdf(q))
               for q in _quantiles(n)]
    elif spec["dist"] == "loguniform":
        raw = [lo * (hi / lo) ** q for q in _quantiles(n)]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(min(hi, max(lo, round(x)))) for x in raw]


def arrival_gaps(n: int, seconds: float) -> List[float]:
    """``n`` exponential gaps (a Poisson process's quantiles) whose sum
    keeps every arrival inside ``seconds``."""
    raw = [-math.log(1.0 - q) for q in _quantiles(n)]
    scale = seconds * (1.0 - 0.5 / n) / sum(raw)
    return [g * scale for g in raw]


def schedule(traffic: Mapping, seed: int, seconds: float, vocab: int
             ) -> List[Dict]:
    """The requests of one run, a pure function of its arguments. A
    file with ``concurrency`` gives its whole list whatever ``seconds``
    is, in the order the clients take it, and no request is due at a
    time of its own (``due_s`` None: ``run_closed_loop`` stamps it)."""
    closed = "concurrency" in traffic
    n = (traffic["requests"] if closed
         else max(1, round(traffic["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(lengths(traffic["prompt"], n))
    outputs = rng.permutation(lengths(traffic["output"], n))
    due = ([None] * n if closed else
           np.cumsum(rng.permutation(arrival_gaps(n, seconds))).tolist())
    return [{"id": i, "due_s": due[i],
             "prompt": rng.integers(0, vocab, int(prompts[i])).tolist(),
             "max_new_tokens": int(outputs[i])}
            for i in range(n)]


class _Cut:
    """The connections still open, so that the window's end can close
    them: a stream cut there is not a failure, its tokens so far count."""

    def __init__(self):
        self.lock = threading.Lock()
        self.open = set()
        self.done = False

    def close_all(self) -> None:
        with self.lock:
            self.done = True
            for sock in self.open:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass  # already closed by the other side


def _stream(host: str, port: int, path: str, request: Mapping, t0: float,
            timeout: float, cut: _Cut) -> Dict:
    """One SSE request; every token is stamped as its line arrives."""
    sample = {"id": request["id"], "due_s": request["due_s"],
              "prompt_len": len(request["prompt"]),
              "max_new_tokens": request["max_new_tokens"],
              "tokens": [], "token_s": [], "error": None, "cut": False}
    body = json.dumps({"id": request["id"], "prompt": request["prompt"],
                       "max_new_tokens": request["max_new_tokens"]})
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    event = sock = None
    try:
        sample["sent_s"] = time.perf_counter() - t0
        conn.connect()
        # The socket itself: a "Connection: close" reply makes conn drop
        # its own reference while the response still reads from it.
        sock = conn.sock
        with cut.lock:
            cut.open.add(sock)
            if cut.done:  # closed since a closed loop's client looked
                sock.shutdown(socket.SHUT_RDWR)
        conn.request("POST", path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            sample["error"] = f"HTTP {resp.status}: {resp.read(200)!r}"
            return sample
        for raw in resp:
            line = raw.strip()
            if line.startswith(b"event:"):
                event = line[6:].strip()
            elif line.startswith(b"data:") and event is None:
                sample["token_s"].append(time.perf_counter() - t0)
                sample["tokens"].append(json.loads(line[5:])["token"])
            elif line.startswith(b"data:") and event == b"error":
                sample["error"] = line[5:].strip().decode()
    except (OSError, http.client.HTTPException, ValueError) as e:
        sample["error"] = f"{type(e).__name__}: {e}"
    finally:
        sample["done_s"] = time.perf_counter() - t0
        with cut.lock:
            cut.open.discard(sock)
        conn.close()
    if event != b"end" and cut.done:
        sample["cut"], sample["error"] = True, None
    elif sample["error"] is None and (
            event != b"end"
            or len(sample["tokens"]) != request["max_new_tokens"]):
        sample["error"] = (f"stream ended after {len(sample['tokens'])} of "
                           f"{request['max_new_tokens']} tokens")
    return sample


def one_request(host: str, port: int, path: str, request: Mapping,
                timeout: float = 900.0) -> Dict:
    """A single request run to its end (the warm-up's)."""
    return _stream(host, port, path, request, time.perf_counter(), timeout,
                   _Cut())


def run_open_loop(host: str, port: int, path: str, requests: List[Mapping],
                  clients: int, seconds: float, grace_s: float,
                  timeout: float = 300.0) -> Dict:
    """Send every request when it is due; ``grace_s`` after the window's
    end, close the streams still open. Times in the samples are seconds
    from the window's start."""
    t0, t0_wall = time.perf_counter(), time.time()
    cut = _Cut()
    with ThreadPoolExecutor(max_workers=clients) as pool:
        futures = []
        for request in requests:
            wait = t0 + request["due_s"] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(_stream, host, port, path, request,
                                       t0, timeout, cut))
        wait = t0 + seconds + grace_s - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        cut.close_all()
        samples = [f.result() for f in futures]
    return {"t0_wall": t0_wall, "samples": samples,
            "closed_s": time.perf_counter() - t0}


def run_closed_loop(host: str, port: int, path: str, requests: List[Mapping],
                    concurrency: int, seconds: float, grace_s: float,
                    timeout: float = 300.0) -> Dict:
    """``concurrency`` clients, all started at the window's start; each
    sends the list's next request the moment its last stream ends, until
    the streams are closed ``grace_s`` after the window's end: as many
    streams are open in every gap that is measured. A request is due
    when it is sent. A list that runs dry before the close is an error:
    fewer streams would be open than the cell states."""
    t0, t0_wall = time.perf_counter(), time.time()
    cut, lock = _Cut(), threading.Lock()
    waiting, samples = iter(requests), []

    def client() -> None:
        while not cut.done:
            with lock:
                request = next(waiting, None)
            if request is None:
                raise RuntimeError(
                    f"the list of {len(requests)} requests ran dry "
                    f"{time.perf_counter() - t0:.1f} s into a run that "
                    f"closes at {seconds + grace_s:.1f} s")
            sample = _stream(
                host, port, path,
                {**request, "due_s": time.perf_counter() - t0}, t0, timeout,
                cut)
            with lock:
                samples.append(sample)

    with ThreadPoolExecutor(max_workers=concurrency) as pool:
        clients = [pool.submit(client) for _ in range(concurrency)]
        wait = t0 + seconds + grace_s - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        cut.close_all()
        for done in clients:
            done.result()
    return {"t0_wall": t0_wall,
            "samples": sorted(samples, key=lambda s: s["id"]),
            "closed_s": time.perf_counter() - t0}
