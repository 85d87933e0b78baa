"""Job kind ``serve``: an ``LLMDeployment`` replica on a ``tpu`` worker
behind the per-node HTTP proxy, driven by the load generator: its open
loop for a traffic file with ``rate_per_s``, its closed loop for one
with ``concurrency``.

From chip_smoke.py's ``_llm_deployment``/``serve_phase``/``_sse`` (PR 21).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Dict, List, Mapping

# What the harness gives each of its own calls into the replica
# (``facts``, ``trace_start``, ``trace_stop``, ``margins``): stopping a
# trace of some hundred decode steps alone can take over a minute.
CONTROL_TIMEOUT_S = 900.0


def _deployment():
    """The deployment class, built on first use: importing this module
    must not import the serving stack."""
    from ray_tpu.serve.llm import LLMDeployment

    from .. import arch, worker

    class BenchLLM(LLMDeployment):
        """``LLMDeployment`` on weights made in one jitted call from the
        seed, plus what the benchmark asks from inside the replica.
        Requests go through the inherited ``stream``."""

        def __init__(self, config: Mapping, engine: Mapping, seed: int,
                     chips: int):
            import jax

            from ray_tpu.models import init_params

            started = time.time()
            self._compiles = worker.CompileLog()  # before the first compile
            self._device = worker.device_facts(chips)
            self._config = config
            self._reference = arch.reference(config)
            cfg = arch.program_config(config)
            params = jax.block_until_ready(
                jax.jit(lambda key: init_params(cfg, key))(
                    worker.prng_key(seed)))
            weights_at = time.time()
            super().__init__(cfg, params, seed=seed, **engine)
            self._phases = {"worker_started": started,
                            "weights_made": weights_at,
                            "engine_built": time.time()}
            self._entered: Dict[int, float] = {}
            self._tracer = worker.Tracer()

        def stream(self, request):
            self._entered[request["id"]] = time.time()
            yield from super().stream(request)

        def facts(self) -> Dict:
            import os

            return {"pid": os.getpid(), "device": self._device,
                    "engine": self.engine.stats(),
                    "phases": self._phases,
                    "entered": dict(self._entered),
                    "memory_peak_bytes": worker.memory_peak_bytes(),
                    "margin_tol": self._reference.LOGIT_MARGIN_TOL[
                        self._config["dtype"]],
                    **self._compiles.facts()}

        def trace_start(self) -> None:
            self._tracer.start()

        def trace_stop(self) -> Dict:
            return self._tracer.stop()

        def margins(self, pairs: List) -> List[List[float]]:
            """Teacher-forced check, through the plain reference: for
            each (prompt, output), how far each engine token's logit
            trails the reference's best at its position. One padded
            shape, one sequence at a time."""
            import jax
            import numpy as np

            eng = self.engine
            margins, config = self._reference.logit_margins, self._config
            fn = jax.jit(lambda p, t: margins(p, t, config))
            out = []
            for prompt, output in pairs:
                seq = np.zeros((1, eng.max_len + 1), np.int32)
                seq[0, :len(prompt) + len(output)] = prompt + output
                row = np.asarray(fn(eng.params, seq))[0]
                start = len(prompt) - 1
                out.append(row[start:start + len(output)].tolist())
            return out

    return BenchLLM


def bucket(n: int, page_size: int, max_len: int) -> int:
    """The prefill bucket ``LLMEngine`` pads a prompt of ``n`` tokens
    to: the engine's rule, restated to choose the warm-up's shapes."""
    size = page_size
    while size < n:
        size *= 2
    return min(size, max_len)


def warmup_requests(requests: List[Mapping], engine: Mapping) -> List[Dict]:
    """One short request for every prefill bucket the schedule uses."""
    by_bucket = {}
    for r in requests:
        b = bucket(len(r["prompt"]), engine["page_size"], engine["max_len"])
        by_bucket.setdefault(b, r)
    return [{"id": -1 - i, "due_s": 0.0, "prompt": r["prompt"],
             "max_new_tokens": 2}
            for i, (_, r) in enumerate(sorted(by_bucket.items()))]


def control_call(handle, method: str, *args):
    """One of the harness's own calls into the replica, under the
    harness's deadline and not the one serve gives a user's request
    that brings none (``serve_default_request_timeout_s``)."""
    from ray_tpu.util import overload

    with overload.deadline_scope(time.time() + CONTROL_TIMEOUT_S):
        reply = handle.options(method=method).remote(*args)
    return reply.result(timeout=CONTROL_TIMEOUT_S)


@contextlib.contextmanager
def deployed(cell: Mapping, config: Mapping, traffic: Mapping, seed: int):
    """The system up with one replica behind the proxy; yields
    ``(port, call)`` where ``call(method, *args)`` asks the replica."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import http_proxy

    from .. import driver

    clients = traffic["clients"]
    with driver.system(cell["chips"], config.get("system_config")):
        dep = serve.deployment(_deployment()).options(
            name="llm", max_concurrent_queries=clients + 8,
            ray_actor_options={"max_concurrency": clients + 8,
                               "num_tpus": cell["chips"]})
        proxies, pid = {}, None
        try:
            handle = serve.run(dep.bind(config, config["engine"], seed,
                                        cell["chips"]), name="llm")

            call = functools.partial(control_call, handle)
            pid = call("facts")["pid"]
            proxies = http_proxy.start_per_node_proxies(port=0)
            (_, port), = proxies.values()
            yield port, call
        finally:
            for actor, _ in proxies.values():
                ray_tpu.get(actor.shutdown.remote(), timeout=30)
                ray_tpu.kill(actor)
            serve.shutdown()
        if pid is not None:
            driver.wait_chip_released(pid)


def warm_up(port: int, requests: List[Mapping], engine: Mapping) -> None:
    """Every prefill bucket the schedule uses, and the decode program,
    through the same SSE path as the window."""
    from .. import loadgen

    for r in warmup_requests(requests, engine):
        warm = loadgen.one_request("127.0.0.1", port, "/llm/stream", r)
        if warm["error"]:
            raise RuntimeError(f"warm-up failed: {warm['error']}")


def offer_load(port: int, requests: List[Mapping], traffic: Mapping,
               seconds: float) -> Dict:
    """The window's load: a closed loop for a traffic file that holds
    ``concurrency`` streams open, an open loop for one with a rate."""
    from .. import loadgen

    if "concurrency" in traffic:
        return loadgen.run_closed_loop(
            "127.0.0.1", port, "/llm/stream", requests,
            traffic["concurrency"], seconds, traffic["grace_s"])
    return loadgen.run_open_loop(
        "127.0.0.1", port, "/llm/stream", requests, traffic["clients"],
        seconds, traffic["grace_s"])


def judge(samples: List[Mapping], margins: List[List[float]],
          programs_in_window: int, tol: float):
    """``(correct, check)``: no request failed, nothing compiled or
    loaded inside the window, and no served token of the checked sample
    trails the reference's best logit at its position by more than
    ``tol``. ``check`` holds each number compared and its limit."""
    failed = [s for s in samples if s["error"] is not None]
    worst = max((m for row in margins for m in row), default=float("inf"))
    correct = (not failed and bool(margins) and programs_in_window == 0
               and worst <= tol)
    return correct, {"worst_margin": worst, "tol": tol,
                     "tokens": sum(len(r) for r in margins),
                     "argmax": sum(m == 0 for r in margins for m in r),
                     "failed": len(failed),
                     "programs_in_window": programs_in_window,
                     "errors": [s["error"] for s in failed][:3]}


def run(cell: Mapping, config: Mapping, traffic: Mapping, seed: int,
        seconds: float, trace: bool) -> Dict:
    import random

    from .. import loadgen

    # A closed loop's schedule is its whole list: the warm-up then loads
    # every prefill bucket a window can meet, whichever requests it takes.
    requests = loadgen.schedule(traffic, seed, seconds, config["vocab_size"])
    traced: Dict = {}
    with deployed(cell, config, traffic, seed) as (port, call):
        deployed_at = time.time()
        warm_up(port, requests, config["engine"])
        before = call("facts")

        def trace_part():
            time.sleep(traffic["trace_at_s"])
            call("trace_start")
            time.sleep(traffic["trace_seconds"])
            traced.update(call("trace_stop"))

        tracer = threading.Thread(target=trace_part) if trace else None
        if tracer:
            tracer.start()
        load = offer_load(port, requests, traffic, seconds)
        if tracer:
            tracer.join(timeout=300)
        after = call("facts")

        done = [s for s in load["samples"]
                if s["error"] is None and not s["cut"]]
        sample = random.Random(seed).sample(
            done, min(traffic["check_requests"], len(done)))
        margins = call("margins", [
            (requests[s["id"]]["prompt"], s["tokens"]) for s in sample])

    for s in load["samples"]:
        del s["tokens"]
    programs_in_window = after["programs"] - before["programs"]
    correct, check = judge(load["samples"], margins, programs_in_window,
                           after["margin_tol"])
    return {
        "correct": correct, "attempted": len(load["samples"]),
        "failed": check["failed"],
        "worker": {**after, "window_start": load["t0_wall"],
                   "programs_in_window": programs_in_window,
                   "check": check,
                   "stop_trace_s": traced.get("stop_trace_s"),
                   "engine_before": before["engine"],
                   "phases": {**before["phases"],
                              "deployed": deployed_at}},
        "client": load,
        "trace": traced or None,
    }
