"""The serving engine measured from inside: ``LLMEngine.stats()``'s
counters and request rows, the request's ``core/timeline`` spans, and the
loop's profiler annotations. CPU, tiny model."""

import glob
import os
import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")

import ray_tpu.core.timeline  # noqa: E402,F401
from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.serve.llm import LLMDeployment, LLMEngine  # noqa: E402

# The package exports a function of the same name over the module.
timeline = sys.modules["ray_tpu.core.timeline"]
LOOP_PHASES = ("admit", "inputs", "decode", "readback", "emit", "idle")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _admit_waits(stats):
    return [row[1] - row[0] for row in sorted(stats["requests"])]


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


def test_late_requests_wait_for_a_slot_and_admission_stalls_streams(
        tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        engine.generate([9, 9, 9], 2, timeout=120)  # both programs compiled
        before = engine.stats()
        # The second request ends first, so the third is prefilled while
        # the first still streams.
        reqs = [engine.submit([1, 2, 3, 4 + i], n)
                for i, n in enumerate([12, 3, 3, 3])]
        for r in reqs:
            r.result(timeout=120)
        stats = engine.stats()
    finally:
        engine.shutdown()
    # One stream alone stalls behind no prefill: only the empty round
    # entered while its last token was in flight.
    assert before["phase_s"]["admit_stalling"] < 1e-3
    assert stats["phase_s"]["admit_stalling"] \
        > 2 * before["phase_s"]["admit_stalling"]
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
    assert stats["stream"] == {"tokens_emitted": 0, "tokens_taken": 0,
                               "taken_lag_s": 0.0, "held_s": 0.0,
                               "backlog": 0}


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
        assert first["stream"] == {"tokens_emitted": 0, "tokens_taken": 0,
                                   "taken_lag_s": 0.0, "held_s": 0.0,
                                   "backlog": 0}
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
    # Only the prefill span carries arguments: none on a per-step span.
    assert all(not s for name, s in events if name == "engine.decode")
