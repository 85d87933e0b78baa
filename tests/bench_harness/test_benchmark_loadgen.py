"""The load generator's closed loop against a stub SSE server, the serve
job's choice between the two loops, and its verdict on a run. CPU, a
few threads, windows of well under a second."""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import loadgen, sweep  # noqa: E402
from benchmark.jobs import serve  # noqa: E402

TOKEN_S = 0.004


class _Stub(ThreadingHTTPServer):
    """Streams ``max_new_tokens`` tokens, one every ``TOKEN_S``, as the
    proxy frames them, and counts the streams it has open."""

    daemon_threads = True

    def __init__(self):
        super().__init__(("127.0.0.1", 0), _Handler)
        self.lock = threading.Lock()
        self.open = self.most_open = 0
        self.prompts = {}


class _Handler(BaseHTTPRequestHandler):
    def log_message(self, *_):
        pass

    def do_POST(self):
        server = self.server
        request = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        with server.lock:
            server.open += 1
            server.most_open = max(server.most_open, server.open)
            server.prompts[request["id"]] = request["prompt"]
        try:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Connection", "close")
            self.end_headers()
            for i in range(request["max_new_tokens"]):
                time.sleep(TOKEN_S)
                self.wfile.write(f'data: {{"token": {i}}}\n\n'.encode())
                self.wfile.flush()
            self.wfile.write(b"event: end\ndata: null\n\n")
            self.wfile.flush()
        except OSError:
            pass  # the client cut the stream
        finally:
            with server.lock:
                server.open -= 1


@pytest.fixture
def stub():
    server = _Stub()
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server
    server.shutdown()
    server.server_close()
    thread.join(timeout=10)
    assert not thread.is_alive()


def _traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _small(requests):
    """chat-closed-c16's shape at a size a test can hold: answers of
    5-12 tokens, so a stream lasts 20-50 ms."""
    return {**_traffic("chat-closed-c16"), "concurrency": 4,
            "requests": requests,
            "prompt": {"dist": "lognormal", "median": 8, "sigma": 1.0,
                       "min": 2, "max": 32},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.25,
                       "min": 5, "max": 12}}


def _closed(stub, traffic, seed=3, seconds=0.5, grace_s=0.1):
    requests = loadgen.schedule(traffic, seed, seconds, 1000)
    load = loadgen.run_closed_loop(
        "127.0.0.1", stub.server_address[1], "/llm/stream", requests,
        traffic["concurrency"], seconds, grace_s)
    return requests, load


def test_closed_loop_never_has_more_streams_open_than_it_states(stub):
    _, load = _closed(stub, _small(400))
    assert stub.most_open == 4
    assert not [s["error"] for s in load["samples"] if s["error"]]
    # A request is due when it is sent, and is timed from then.
    assert all(s["due_s"] == pytest.approx(s["sent_s"], abs=0.02)
               and s["token_s"][0] > s["due_s"]
               for s in load["samples"] if s["token_s"])


def test_closed_loop_never_has_fewer_while_requests_remain(stub):
    _, load = _closed(stub, _small(400))
    samples = load["samples"]
    ended = [s for s in samples if not s["cut"]]
    assert all(len(s["token_s"]) == s["max_new_tokens"] for s in ended)
    # All four start at the window's start ...
    assert sorted(s["sent_s"] for s in samples)[3] < 0.1
    # ... every stream that ended was followed by the list's next
    # request (but one that ended as the streams were being closed) ...
    followed = len(samples) - 4
    before_close = [s for s in ended if s["done_s"] < 0.5 + 0.1 - 0.05]
    assert len(before_close) <= followed <= len(ended)
    assert followed >= 12
    # ... at once: the time with fewer than four open is the clients'
    # turn-round, a small part of the run.
    events = sorted([(s["sent_s"], 1) for s in samples]
                    + [(s["done_s"], -1) for s in samples])
    open_now, under, last = 0, 0.0, None
    for at, step in events:
        if last is not None and open_now < 4 and 0.1 <= at <= 0.55:
            under += at - max(last, 0.1)
        open_now, last = open_now + step, at
    assert under < 0.2 * 0.45
    # The streams open at the close are cut, not failed.
    assert 1 <= sum(s["cut"] for s in samples) <= 4
    assert load["closed_s"] < 0.5 + 0.1 + 0.3


def test_closed_loop_hands_requests_out_in_the_seeds_order(stub):
    traffic = _small(400)
    requests, load = _closed(stub, traffic, seed=2 ** 31 + 11)
    again = loadgen.schedule(traffic, 2 ** 31 + 11, 51, 1000)
    other = loadgen.schedule(traffic, 5, 0.5, 1000)
    assert requests == again and requests != other
    # Every seed the same multiset, in another order.
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, requests)) == sorted(map(key, other))
    by_send = sorted(load["samples"], key=lambda s: s["sent_s"])
    assert sorted(s["id"] for s in by_send) == list(range(len(by_send)))
    # Handed out in the list's order: at most the three requests that
    # the other clients hold lie between a request and its place.
    assert all(abs(s["id"] - i) < 4 for i, s in enumerate(by_send))
    assert all(stub.prompts[s["id"]] == requests[s["id"]]["prompt"]
               and s["max_new_tokens"] == requests[s["id"]]["max_new_tokens"]
               for s in by_send if s["token_s"])


def test_a_list_that_runs_dry_is_an_error_and_not_a_short_run(stub):
    with pytest.raises(RuntimeError, match="list of 6 requests ran dry"):
        _closed(stub, _small(6))
    assert stub.most_open <= 4


def test_a_stream_opened_as_the_streams_are_closed_is_cut(stub):
    cut = loadgen._Cut()
    cut.close_all()
    request = {"id": 0, "due_s": 0.0, "prompt": [1, 2], "max_new_tokens": 50}
    started = time.perf_counter()
    sample = loadgen._stream("127.0.0.1", stub.server_address[1],
                             "/llm/stream", request, started, 10.0, cut)
    assert sample["cut"] and sample["error"] is None
    assert len(sample["token_s"]) < 50
    assert time.perf_counter() - started < 50 * TOKEN_S


@pytest.mark.parametrize("name,loop,streams", [
    ("chat-open", "run_open_loop", 96),
    ("chat-closed-c16", "run_closed_loop", 16)])
def test_the_traffic_file_chooses_the_loop(monkeypatch, name, loop, streams):
    calls = []
    for fn in ("run_open_loop", "run_closed_loop"):
        monkeypatch.setattr(
            loadgen, fn, lambda *a, _fn=fn: calls.append((_fn, a)) or {})
    traffic = _traffic(name)
    assert ("rate_per_s" in traffic) == (loop == "run_open_loop")
    assert serve.offer_load(4321, ["r"], traffic, 51.0) == {}
    assert calls == [(loop, ("127.0.0.1", 4321, "/llm/stream", ["r"],
                             streams, 51.0, traffic["grace_s"]))]


def test_sweep_says_a_closed_loop_has_no_knee():
    with pytest.raises(SystemExit, match="no knee to find"):
        sweep.main(["--workload", "serve-olmoe-c16", "--rates", "1,2"])


def _samples(error=None):
    return [{"id": 0, "error": None}, {"id": 1, "error": error}]


@pytest.mark.parametrize("samples,margins,programs,correct", [
    (_samples(), [[0.0, 0.05], [0.19]], 0, True),
    # One served token altered where it is produced: it trails the
    # reference's best logit by more than the configuration allows.
    (_samples(), [[0.0, 0.05], [0.0, 3.1, 0.0]], 0, False),
    (_samples("stream ended after 3 of 9 tokens"), [[0.0]], 0, False),
    (_samples(), [[0.0]], 1, False),
    (_samples(), [], 0, False),
], ids=["sound", "token-altered", "request-failed", "compiled-in-window",
        "nothing-compared"])
def test_the_verdict_on_a_run(samples, margins, programs, correct):
    got, check = serve.judge(samples, margins, programs, 0.2)
    assert got is correct
    assert check["tol"] == 0.2 and check["programs_in_window"] == programs
    assert check["failed"] == sum(s["error"] is not None for s in samples)
    if margins:
        assert check["worst_margin"] == max(m for r in margins for m in r)
        assert check["tokens"] == sum(map(len, margins))
