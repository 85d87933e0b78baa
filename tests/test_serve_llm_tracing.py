"""The serving engine measured from inside: ``LLMEngine.stats()``'s
counters and request rows, the request's ``core/timeline`` spans, and the
loop's profiler annotations. CPU, tiny model."""

import glob
import os
import random
import statistics
import sys
import threading
import time
from bisect import bisect_right

import pytest

jax = pytest.importorskip("jax")

import ray_tpu.core.timeline  # noqa: E402,F401
from ray_tpu.serve.llm import (  # noqa: E402
    HIST_EDGES_S, LLMDeployment, LLMEngine)
from ray_tpu.util.tsdb import quantile_from_histogram  # noqa: E402

# The package exports a function of the same name over the module.
timeline = sys.modules["ray_tpu.core.timeline"]
LOOP_PHASES = ("admit", "inputs", "decode", "readback", "emit", "idle")
FEEDS = ("fed", "starved_host", "starved_prefill", "starved_lull")


def hist_quantile(hist, q):
    """Percentile ``q`` of a histogram of ``stats()["stream"]``, as an
    operator reads it."""
    return quantile_from_histogram(HIST_EDGES_S, hist, q / 100.0)


HISTS = ("emit_gap_hist", "taken_lag_hist", "held_hist")
# ``stats()["stream"]`` of an engine that has made no token, but for its
# histograms (all zeros then) and their edges.
NO_TOKENS = {"tokens_emitted": 0, "tokens_taken": 0, "taken_lag_s": 0.0,
             "held_s": 0.0, "held_timed_s": 0.0, "held_cpu_s": 0.0,
             "backlog": 0}


def _admit_waits(stats):
    return [row[1] - row[0] for row in sorted(stats["requests"])]


def _numbers(stream):
    """``stats()["stream"]`` without its histograms and their edges."""
    return {key: value for key, value in stream.items()
            if not isinstance(value, list)}


def _window(after, before, name):
    """A histogram of ``stats()["stream"]`` between two readings."""
    return [a - b for a, b in zip(after["stream"][name],
                                  before["stream"][name])]


def _counted_from(hist, seconds):
    """Samples of ``seconds`` or more, by the buckets that lie over it."""
    return sum(count for count, low in zip(hist[1:], HIST_EDGES_S)
               if low >= seconds)


def test_stats_conserve_requests_tokens_and_time(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    started = time.perf_counter()
    try:
        news = [3, 7, 5, 4, 6]
        reqs = [engine.submit([1, 2, 3, 4 + i], n)
                for i, n in enumerate(news)]
        assert [len(r.result(timeout=120)) for r in reqs] == news
        stats = engine.stats()
        ran_s = time.perf_counter() - started
    finally:
        engine.shutdown()
    n = len(news)
    assert stats["submitted"] == stats["admitted"] == stats["finished"] == n
    assert stats["prefills"] == n and stats["failed"] == 0
    assert stats["queued"] == stats["page_waits"] == stats["cache_resets"] == 0
    # A request's first token comes from its prefill, the rest one a step.
    assert stats["decode_slot_steps"] == sum(news) - n
    assert stats["decode_slot_steps"] <= stats["decode_steps"] * 2
    # Each step attends to the prompt and the tokens generated before it.
    assert stats["decode_kv_tokens"] == sum(
        4 + k for new in news for k in range(1, new))
    assert stats["prefill_tokens"] == 4 * n
    assert stats["prefill_bucket_tokens"] == 16 * n
    assert len(stats["requests"]) == n
    for row, req in zip(sorted(stats["requests"]), reqs):
        t_submit, t_admit, t_first, t_done, prompt_len, bucket = row[:6]
        assert t_submit <= t_admit <= t_first <= t_done
        assert (prompt_len, bucket) == (4, 16)
        assert req.ttft_s == t_first - t_submit
    phase_s = stats["phase_s"]
    assert set(phase_s) == set(LOOP_PHASES) | {"admit_stalling"}
    assert phase_s["admit_stalling"] <= phase_s["admit"]
    assert sum(phase_s[p] for p in LOOP_PHASES) == pytest.approx(
        ran_s, rel=0.1)


def _count_admission_rounds(engine):
    """``rounds``, to which every admission round from now on appends
    ``(a stream was open when it was entered, the prefills it made, the
    seconds they took by the loop's clock)``, and ``gate``: while it is
    clear the loop stops at the top of its next round, so that requests
    submitted meanwhile are all in the queue when admission next runs."""
    rounds, gate = [], threading.Event()
    gate.set()
    admit, streaming = engine._admit, engine.scheduler.streaming

    def counted():
        gate.wait()
        stalling, began = streaming(), time.perf_counter()
        made = admit()
        rounds.append((stalling, made, time.perf_counter() - began))
        return made

    engine._admit = counted
    return rounds, gate


def test_late_requests_wait_for_a_slot_and_admission_stalls_streams(
        tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        rounds, gate = _count_admission_rounds(engine)
        engine.generate([9, 9, 9], 2, timeout=120)  # both programs compiled
        before, alone = engine.stats(), list(rounds)
        # The second request ends first, so the third is prefilled while
        # the first still streams.
        gate.clear()
        reqs = [engine.submit([1, 2, 3, 4 + i], n)
                for i, n in enumerate([12, 3, 3, 3])]
        gate.set()
        for r in reqs:
            r.result(timeout=120)
        stats = engine.stats()
    finally:
        gate.set()
        engine.shutdown()
    # One stream alone stalls behind no prefill: only the empty rounds
    # entered while its last token was in flight. Rounds, not seconds: a
    # loaded machine stretches an empty round as it does a prefill.
    assert sum(made for _, made, _ in alone) == 1
    assert not any(made for stalling, made, _ in alone if stalling)
    # The third and the fourth request were prefilled in rounds that the
    # first one's stream sat through, and the seconds those prefills took
    # (each inside its round's lap) are in the stall.
    late = rounds[len(alone):]
    assert sum(made for _, made, _ in late) == 4
    assert sum(made for stalling, made, _ in late if stalling) >= 2
    assert stats["phase_s"]["admit_stalling"] \
        - before["phase_s"]["admit_stalling"] \
        >= sum(took for stalling, made, took in late if stalling and made) \
        > 0
    waits = _admit_waits(stats)[-4:]
    assert min(waits[2:]) > max(waits[:2])


def test_page_waits_count_rounds_stopped_for_want_of_pages(tiny_model):
    cfg, params = tiny_model
    # One page in the pool and one a request: the second waits for the
    # first one's page (test_paged_admission_waits_for_pages's set-up).
    engine = LLMEngine(cfg, params, max_batch=2, max_len=32,
                       page_size=16, total_pages=1)
    try:
        a = engine.submit([1, 2, 3], max_new_tokens=4)
        b = engine.submit([4, 5, 6], max_new_tokens=4)
        assert len(a.result(timeout=180)) == len(b.result(timeout=180)) == 4
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert stats["page_waits"] > 0
    assert stats["admitted"] == stats["finished"] == 2
    assert stats["queued"] == 0 and stats["free_pages"] == 1


def test_a_failed_prefill_is_counted_and_its_row_kept(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)

    def broken(*args):
        raise RuntimeError("prefill fell over")

    try:
        engine.runner.prefill = broken
        req = engine.submit([1, 2, 3], 4)
        with pytest.raises(RuntimeError, match="fell over"):
            req.result(timeout=120)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert (stats["admitted"], stats["failed"], stats["finished"]) == (1, 1, 0)
    assert stats["prefills"] == 0 and stats["cache_resets"] == 1
    (t_submit, t_admit, t_first, t_done, _, bucket, rid, t_last_put), = \
        stats["requests"]
    assert t_submit <= t_admit <= t_done and t_first is None and bucket == 16
    assert req.ttft_s is None
    # No token was made: none emitted, none taken, nothing put.
    assert (rid, t_last_put) == (None, None)
    assert _numbers(stats["stream"]) == NO_TOKENS
    assert not any(sum(stats["stream"][name]) for name in HISTS)
    # ... and no decode step was dispatched.
    assert stats["decode_dispatch"] == dict.fromkeys(FEEDS, 0)


# ---- the way back: stats()["stream"], "t", a row's id and t_last_put ------


def _emitted_by_the_counts(stats):
    """Tokens the loop made, from the step and prefill counts: every
    prefill here produces one, every slot of a step one, less those
    thrown away behind an ``eos_token``."""
    return (stats["prefills"] + stats["decode_slot_steps"]
            - stats["decode_slot_steps_discarded"])


def _take(req, pause_s=0.0):
    tokens = []
    for tok in req.tokens(timeout=120):
        tokens.append(tok)
        time.sleep(pause_s)
    return tokens


def test_stream_counts_conserve_against_steps_and_prefills(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        first = engine.stats()
        assert _numbers(first["stream"]) == NO_TOKENS
        assert first["stream"]["hist_edges_s"] == list(HIST_EDGES_S)
        assert all(first["stream"][name] == [0] * (len(HIST_EDGES_S) + 1)
                   for name in HISTS)
        news = [3, 7, 5, 4]
        reqs = [engine.submit([1, 2, 3, 4 + i], n, request_id=f"r{i}")
                for i, n in enumerate(news)]
        # Two are streamed, one of them stopped by its eos_token (a
        # discarded slot-step); two are read by result() alone.
        eos = engine.generate([1, 2, 3, 4], 3)[1]
        before = engine.stats()
        stopped = engine.submit([1, 2, 3, 4], 3, eos_token=eos)
        streamed = [_take(r) for r in (reqs[0], reqs[1], stopped)]
        for r in reqs:
            r.result(timeout=120)
        # The step queued behind the eos_token is counted when read.
        deadline = time.monotonic() + 60
        while engine.stats()["free_slots"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        after = engine.stats()
    finally:
        engine.shutdown()
    assert [len(t) for t in streamed] == [3, 7, 2]
    for stats in (before, after):
        assert stats["stream"]["tokens_emitted"] == \
            _emitted_by_the_counts(stats)
    assert after["decode_slot_steps_discarded"] == 1
    stream = after["stream"]
    # Taken: what the three iterators took, and never more than made.
    assert stream["tokens_taken"] == 3 + 7 + 2
    assert stream["tokens_taken"] < stream["tokens_emitted"] \
        == sum(news) + 3 + 2
    assert stream["backlog"] == 0
    assert stream["taken_lag_s"] > 0 and stream["held_s"] > 0
    # A reading carries its own time, on the rows' clock.
    assert first["t"] < before["t"] < after["t"] <= time.time()
    assert after["requests"][0][0] < after["t"]


def test_a_slow_consumer_shows_as_lag_and_backlog_not_as_other_tokens(
        tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        engine.generate([9, 9, 9], 2, timeout=120)  # programs compiled
        base = engine.stats()["stream"]
        quick = engine.submit([1, 2, 3], 12)
        quick_tokens = _take(quick)
        fast = engine.stats()["stream"]
        slow = engine.submit([1, 2, 3], 12)
        seen = {}

        def consume():
            seen["tokens"] = _take(slow, pause_s=0.05)

        consumer = threading.Thread(target=consume)
        consumer.start()
        slow.result(timeout=120)  # the engine is done with it ...
        mid = engine.stats()["stream"]
        consumer.join(timeout=120)
        lagged = engine.stats()["stream"]
    finally:
        engine.shutdown()
    # ... while its consumer still has most of the tokens in front of it.
    assert mid["backlog"] >= 6
    assert mid["tokens_emitted"] - mid["tokens_taken"] >= mid["backlog"]
    assert lagged["backlog"] == 0
    assert lagged["tokens_taken"] - fast["tokens_taken"] == 12 \
        == fast["tokens_taken"] - base["tokens_taken"]
    # 0.05 s between two takes: the queue's wait is in taken_lag_s, the
    # consumer's own time in held_s; a consumer that keeps up has neither.
    assert lagged["held_s"] - fast["held_s"] > 11 * 0.045
    assert lagged["taken_lag_s"] - fast["taken_lag_s"] > 1.0
    assert fast["held_s"] - base["held_s"] < 0.1
    assert fast["taken_lag_s"] - base["taken_lag_s"] < 0.5
    assert seen["tokens"] == quick_tokens == quick.result(timeout=1)


def test_rows_keep_six_fields_and_gain_the_callers_id_and_the_last_put(
        tiny_model):
    cfg, params = tiny_model
    dep = LLMDeployment(cfg, params, max_batch=2, max_len=64)
    try:
        request = {"prompt": [1, 2, 3], "max_new_tokens": 4}
        named = [item["token"] for item in dep.stream({**request, "id": 17})]
        anonymous = dep(request)["tokens"]
        rows = sorted(dep.stats()["requests"])
    finally:
        dep.engine.shutdown()
    assert named == anonymous and len(rows) == 2
    for row in rows:
        t_submit, t_admit, t_first, t_done, prompt_len, bucket = row[:6]
        assert t_submit <= t_admit <= t_first <= t_done
        assert (prompt_len, bucket, len(row)) == (3, 16, 8)
    (_, _, _, t_done, _, _, rid, t_last_put), by_call = rows
    # Streamed: the caller's id, and the consumer was back for more
    # after the engine was done. Read by __call__: no id, never put.
    assert rid == 17 and t_done <= t_last_put <= time.time()
    assert by_call[6:] == [None, None]


# ---- a gap taken apart: cadence, the hops' tails, CPU time, the feed ------


def _slow_prefill(engine, seconds):
    """Every prefill from now on takes ``seconds`` longer."""
    real = engine.runner.prefill

    def slow(*args):
        time.sleep(seconds)
        return real(*args)

    engine.runner.prefill = slow


def test_a_prefill_is_one_long_gap_for_every_open_stream_and_none_for_its_own(
        tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=3, max_len=64)
    try:
        engine.generate([9, 9, 9], 2, timeout=120)  # programs compiled
        before = engine.stats()
        open_streams = [engine.submit([1, 2, 3, 4 + i], 56) for i in range(2)]
        takers = [r.tokens(timeout=120) for r in open_streams]
        firsts = [next(t) for t in takers]  # both stream, both prefills done
        _slow_prefill(engine, 0.5)
        late = engine.submit([7, 8, 9], 6)
        late.result(timeout=120)
        outputs = [[first] + list(t) for first, t in zip(firsts, takers)]
        after = engine.stats()
    finally:
        engine.shutdown()
    assert outputs == [r.output for r in open_streams]
    gaps = _window(after, before, "emit_gap_hist")
    # A request's first token opens no gap, as a client counts them.
    emitted = after["stream"]["tokens_emitted"] \
        - before["stream"]["tokens_emitted"]
    assert emitted == 56 + 56 + 6 and sum(gaps) == emitted - 3
    # Both open streams stood still through the late one's prefill; its
    # own gaps are a step each.
    assert _counted_from(gaps, 0.4) == 2
    assert hist_quantile(gaps, 50) < 0.1 < 0.4 < hist_quantile(gaps, 99.5)
    assert after["decode_dispatch"]["starved_prefill"] \
        - before["decode_dispatch"]["starved_prefill"] == 1


def test_hop_histograms_merge_ended_and_running_iterators(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        engine.generate([9, 9, 9], 2, timeout=120)  # programs compiled
        base = engine.stats()
        assert len(_take(engine.submit([1, 2, 3], 10))) == 10   # ended
        quick = engine.stats()
        slow = engine.submit([1, 2, 3], 10)
        taker = slow.tokens(timeout=120)
        for _ in range(4):      # running: its fourth token is in hand
            next(taker)
            time.sleep(0.05)
        mid = engine.stats()["stream"]
        assert len(list(taker)) == 6
        done = engine.stats()
    finally:
        engine.shutdown()
    assert mid["tokens_taken"] - base["stream"]["tokens_taken"] == 14
    assert sum(mid["taken_lag_hist"]) == mid["tokens_taken"]
    assert sum(mid["held_hist"]) == mid["tokens_taken"] - 1
    stream = done["stream"]
    assert sum(stream["taken_lag_hist"]) == sum(stream["held_hist"]) \
        == stream["tokens_taken"] == base["stream"]["tokens_taken"] + 20
    # The consumer that slept with a token in hand: four holds of 50 ms
    # among its ten, none among the quick one's; and it slept off the CPU.
    assert _counted_from(_window(done, quick, "held_hist"), 0.045) == 4
    assert _counted_from(_window(quick, base, "held_hist"), 0.045) == 0
    assert stream["held_s"] - quick["stream"]["held_s"] > 4 * 0.045
    # Of an iterator's holds the first and every eighth behind it are
    # also timed on the thread's CPU clock: here the slow one's first,
    # 50 ms asleep, and its ninth.
    for stats in (base, quick, done):
        timed = stats["stream"]
        assert 0 <= timed["held_cpu_s"] <= timed["held_timed_s"] \
            <= timed["held_s"]
    assert 0.045 < stream["held_timed_s"] - quick["stream"]["held_timed_s"] \
        < stream["held_s"] - quick["stream"]["held_s"] - 3 * 0.045
    assert stream["held_cpu_s"] - quick["stream"]["held_cpu_s"] < 0.02


def test_phase_cpu_is_a_part_of_each_phase_and_waiting_is_not_in_it(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        reqs = [engine.submit([1, 2, 3, 4 + i], 24) for i in range(2)]
        for r in reqs:
            r.result(timeout=120)
        time.sleep(0.3)   # the loop polls, asleep
        stats = engine.stats()
    finally:
        engine.shutdown()
    phase_s, phase_cpu_s = stats["phase_s"], stats["phase_cpu_s"]
    assert set(phase_cpu_s) == set(LOOP_PHASES)
    for phase in LOOP_PHASES:
        # Two clocks: a tick of room.
        assert 0 <= phase_cpu_s[phase] <= phase_s[phase] * 1.05 + 0.02
    assert phase_s["idle"] > 0.25 and phase_cpu_s["idle"] < 0.5 * phase_s["idle"]
    assert sum(phase_cpu_s.values()) > 0


def _dispatched(after, before):
    return {feed: after["decode_dispatch"][feed]
            - before["decode_dispatch"][feed] for feed in FEEDS}


def test_every_decode_dispatch_is_counted_by_what_it_found(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        engine.generate([9, 9, 9], 2, timeout=120)  # programs compiled
        start = engine.stats()
        # After a lull: the step behind the prefill finds nothing in flight.
        engine.generate([1, 2, 3], 12, timeout=120)
        alone = engine.stats()
        # A prefill with a stream open; then the loop held past a step's
        # end once: the step in flight is done before the next is sent.
        first = engine.submit([1, 2, 3], 56)
        taker = first.tokens(timeout=120)
        next(taker)
        engine.submit([4, 5, 6], 8).result(timeout=120)
        real = engine.scheduler.next_step
        held = []

        def late(flying):
            if flying is not None and not held:
                held.append(time.sleep(0.2))
            return real(flying)

        engine.scheduler.next_step = late
        mid = engine.stats()   # a step may be in flight
        first.result(timeout=120)
        deadline = time.monotonic() + 60
        while engine.stats()["free_slots"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        end = engine.stats()
    finally:
        engine.shutdown()
    found = _dispatched(alone, start)
    assert found["starved_lull"] == 1 and found["starved_prefill"] == 0
    assert sum(found.values()) == alone["decode_steps"] - start["decode_steps"] \
        == 11
    found = _dispatched(end, alone)
    assert held and (found["starved_lull"], found["starved_prefill"]) == (1, 1)
    assert found["starved_host"] >= 1 and found["fed"] >= 1
    assert sum(found.values()) == end["decode_steps"] - alone["decode_steps"]
    # Counted at the dispatch, where ``decode_steps`` counts at the
    # emit: a reading holds the steps dispatched and not emitted yet,
    # the one in flight and the one being read before it.
    assert sum(_dispatched(mid, start).values()) \
        - (mid["decode_steps"] - start["decode_steps"]) in (0, 1, 2)


@pytest.mark.parametrize("q", [50, 90, 99])
@pytest.mark.parametrize("shape", ["cadence", "lognormal", "two_modes"])
def test_a_quantile_read_from_a_histogram_is_the_samples_own(shape, q):
    rng = random.Random(f"{shape}-{q}")
    if shape == "cadence":      # a step, its jitter, and a prefill in 3%
        samples = [0.0098 * rng.uniform(0.99, 1.01)
                   + (rng.uniform(0.05, 0.4) if rng.random() < 0.03 else
                      rng.expovariate(1 / 0.0005))
                   for _ in range(40_000)]
    elif shape == "lognormal":  # a hop: tens of microseconds to seconds
        samples = [rng.lognormvariate(-6.5, 1.5) for _ in range(40_000)]
    else:
        samples = [rng.gauss(0.0021, 0.00002) if rng.random() < 0.8
                   else rng.gauss(0.0234, 0.0004) for _ in range(40_000)]
    hist = [0] * (len(HIST_EDGES_S) + 1)
    for sample in samples:
        hist[bisect_right(HIST_EDGES_S, sample)] += 1
    exact = statistics.quantiles(samples, n=1000)[q * 10 - 1]
    assert hist_quantile(hist, q) == pytest.approx(exact, rel=0.025)


def test_the_histograms_edges_and_their_open_ends():
    assert HIST_EDGES_S[0] <= 100e-6 and HIST_EDGES_S[-1] >= 60.0
    assert all(1.0 < b / a <= 1.025
               for a, b in zip(HIST_EDGES_S, HIST_EDGES_S[1:]))
    hist = [0] * (len(HIST_EDGES_S) + 1)
    assert hist_quantile(hist, 50) is None
    hist[bisect_right(HIST_EDGES_S, -0.001)] += 1   # a clock set back
    assert hist[0] == 1 and 0 <= hist_quantile(hist, 50) <= HIST_EDGES_S[0]
    hist[bisect_right(HIST_EDGES_S, 3600.0)] += 2
    assert hist[-1] == 2 and hist_quantile(hist, 90) == HIST_EDGES_S[-1]


@pytest.mark.parametrize("entry", ["stream", "call"])
def test_request_spans_join_the_callers_trace_from_its_own_thread(
        tiny_model, monkeypatch, entry):
    cfg, params = tiny_model
    recorded = []
    real = timeline.record_span

    def spy(name, start, end, parent=None):
        recorded.append((name, start, end,
                         parent or timeline.current_span(),
                         threading.get_ident()))
        return real(name, start, end, parent)

    monkeypatch.setattr(timeline, "record_span", spy)
    dep = LLMDeployment(cfg, params, max_batch=2, max_len=64)
    trace_id, span_id = "ab" * 16, "cd" * 8
    request = {"prompt": [1, 2, 3], "max_new_tokens": 4}
    prev = timeline.enter_span(trace_id, span_id)
    try:
        if entry == "stream":
            tokens = [item["token"] for item in dep.stream(request)]
        else:
            tokens = dep(request)["tokens"]
    finally:
        timeline.exit_span(prev)
        dep.engine.shutdown()
    assert len(tokens) == 4
    assert [r[0] for r in recorded] == [
        "engine.queued", "engine.prefill", "engine.decode"]
    # Under the caller's span, end to end without a hole, and never from
    # the engine's thread (a span may flush the buffer to the KV inline).
    assert all(r[3] == (trace_id, span_id) for r in recorded)
    assert all(a[2] == b[1] for a, b in zip(recorded, recorded[1:]))
    assert all(r[1] <= r[2] for r in recorded)
    assert {r[4] for r in recorded} == {threading.get_ident()}
    assert dep.engine._thread.ident != threading.get_ident()
    mine = [e for e in timeline.get_buffer()._events
            if e["trace_id"] == trace_id and e["name"].startswith("engine.")]
    assert len(mine) >= 3 and all(e["parent_id"] == span_id for e in mine)


def _host_events(trace_dir):
    path, = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    return [(event.name, dict(event.stats))
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for event in line.events]


def test_loop_phases_land_in_a_profiler_trace_and_tokens_are_the_same_without(
        tiny_model, tmp_path):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        untraced = engine.generate([5, 6, 7], 5, timeout=120)
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            traced = engine.generate([5, 6, 7], 5, timeout=120)
        finally:
            jax.profiler.stop_trace()
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert traced == untraced and stats["finished"] == 2
    events = _host_events(str(tmp_path))
    names = [name for name, _ in events]
    # 5 tokens: one from the prefill, four decode steps.
    for phase in ("inputs", "decode", "readback", "emit"):
        assert names.count(f"engine.{phase}") == 4
    assert names.count("engine.admit") >= 4
    (prefill,) = [s for name, s in events if name == "engine.prefill"]
    assert prefill["bucket"] == 16 and prefill["slot"] in (0, 1)
    # A decode dispatch says what it found on the device, as the
    # counters do: the step behind the prefill found nothing in flight.
    feeds = [s["feed"] for name, s in events if name == "engine.decode"]
    assert feeds[0] == "starved_lull" and set(feeds) <= set(FEEDS)
    assert all(not s for name, s in events
               if name in ("engine.inputs", "engine.readback", "engine.emit"))
