"""Continuous-batching LLM engine tests."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_single_request_matches_naive_greedy(tiny_model, naive_greedy):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=4, max_len=64)
    try:
        prompt = list(np.random.RandomState(0).randint(0, 256, 6))
        expected = naive_greedy(params, prompt, cfg, 8)
        got = engine.generate(prompt, max_new_tokens=8)
        assert got == expected
    finally:
        engine.shutdown()


def test_engine_paged_decode_agrees_with_naive_greedy(tiny_model,
                                                     naive_greedy):
    """The engine's paged decode against greedy decoding by the full
    forward pass with nothing cached, token for token: prompts of mixed
    lengths over page edges, one request that ends at ``max_len``,
    slots idle beside busy ones, and a slot taken again after its
    request finished (its table row and length are the last request's
    until then)."""
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=3, max_len=64, page_size=16)
    try:
        assert engine.stats()["decode_attention"] == "gather"  # a CPU
        rng = np.random.RandomState(7)
        # (prompt length, new tokens): 20 + 44 fills max_len.
        shapes = [(5, 10), (20, 44), (17, 3), (9, 6)]
        prompts = [list(rng.randint(0, 256, n)) for n, _ in shapes]
        expected = [naive_greedy(params, p, cfg, n)
                    for p, (_, n) in zip(prompts, shapes)]
        first = [engine.submit(p, n)
                 for p, (_, n) in zip(prompts[:3], shapes[:3])]
        assert first[2].result(timeout=180) == expected[2]
        # Every slot has been taken once: this one gets a used slot,
        # while the long request is still decoding.
        again = engine.submit(prompts[3], shapes[3][1])
        assert again.result(timeout=180) == expected[3]
        assert first[0].result(timeout=180) == expected[0]
        assert first[1].result(timeout=180) == expected[1]
        stats = engine.stats()
        assert stats["admitted"] == stats["finished"] == 4
        # Slots idled: fewer sequences decoded than 3 a step.
        assert stats["decode_slot_steps"] < 3 * stats["decode_steps"]
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_engine_concurrent_requests_continuous_batching(tiny_model,
                                                        naive_greedy):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=4, max_len=64)
    try:
        rng = np.random.RandomState(1)
        prompts = [list(rng.randint(0, 256, int(n))) for n in (4, 6, 5, 7)]
        lens = [10, 3, 7, 5]
        expected = [naive_greedy(params, p, cfg, n)
                    for p, n in zip(prompts, lens)]
        # Submit all concurrently: they share the decode loop.
        reqs = [engine.submit(p, n) for p, n in zip(prompts, lens)]
        results = [r.result(timeout=120) for r in reqs]
        assert results == expected
        # Batched decode actually happened: fewer steps than total tokens.
        stats = engine.stats()
        assert stats["decode_steps"] < sum(lens)
    finally:
        engine.shutdown()


def test_engine_more_requests_than_slots(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10], [11, 12]]
        reqs = [engine.submit(p, 4) for p in prompts]
        results = [r.result(timeout=120) for r in reqs]
        assert all(len(r) == 4 for r in results)
    finally:
        engine.shutdown()


def test_engine_ttft_recorded(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        req = engine.submit([1, 2, 3, 4], 4)
        req.result(timeout=120)
        assert req.ttft_s is not None and req.ttft_s > 0
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_llm_serve_deployment(ray_tpu_start):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment

    dep = serve.deployment(LLMDeployment).options(
        name="llm",
        ray_actor_options={"max_concurrency": 8, "num_cpus": 1},
    )
    handle = serve.run(dep.bind(max_batch=4, max_len=64))
    try:
        futs = [
            handle.remote({"prompt": [1, 2, 3 + i], "max_new_tokens": 5})
            for i in range(6)
        ]
        outs = [f.result(timeout=180) for f in futs]
        assert all(len(o["tokens"]) == 5 for o in outs)
        stats = serve.get_deployment_handle("llm").options(
            method="stats"
        ).remote().result(timeout=60)
        assert stats["decode_steps"] >= 1
    finally:
        serve.shutdown()


def test_paged_cache_page_reuse(tiny_model):
    """Pages recycle across requests: an oversubscribed pool (too small
    for all slots at max_len) still serves sequential waves, and the free
    count returns to total when idle."""
    cfg, params = tiny_model
    # 4 slots x max_len 64 would need 16 pages; give only 6 (page=16).
    engine = LLMEngine(cfg, params, max_batch=4, max_len=64,
                       page_size=16, total_pages=6)
    try:
        for wave in range(3):
            outs = [
                engine.submit([1, 2, 3 + wave + i], max_new_tokens=4)
                for i in range(4)
            ]
            for r in outs:
                assert len(r.result(timeout=180)) == 4
        stats = engine.stats()
        assert stats["free_pages"] == stats["total_pages"] == 6
        assert stats["active_slots"] == 0
    finally:
        engine.shutdown()


def test_paged_admission_waits_for_pages(tiny_model):
    """A request that cannot reserve pages queues until a running one
    releases them (admission control instead of OOM)."""
    cfg, params = tiny_model
    # One page per request wave: prompt+max_new <= 16 -> 1 page each, but
    # give the pool only 1 page total so requests serialize.
    engine = LLMEngine(cfg, params, max_batch=2, max_len=32,
                       page_size=16, total_pages=1)
    try:
        a = engine.submit([1, 2, 3], max_new_tokens=4)
        b = engine.submit([4, 5, 6], max_new_tokens=4)
        assert len(a.result(timeout=180)) == 4
        assert len(b.result(timeout=180)) == 4
        assert engine.stats()["free_pages"] == 1
    finally:
        engine.shutdown()


def test_engine_token_streaming(tiny_model):
    """req.tokens() yields tokens incrementally and matches the final
    output list."""
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        req = engine.submit([7, 8, 9], max_new_tokens=6)
        streamed = list(req.tokens(timeout=120))
        assert streamed == req.result(timeout=1)
        assert len(streamed) == 6
    finally:
        engine.shutdown()


@pytest.mark.slow
def test_llm_serve_sse_streaming(ray_tpu_start):
    """End-to-end: HTTP proxy streams SSE tokens from the LLM decode loop
    as they are generated (VERDICT r2 ask #4)."""
    import json as _json
    import urllib.request

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.http_proxy import start_proxy, stop_proxy
    from ray_tpu.serve.llm import LLMDeployment

    dep = serve.deployment(LLMDeployment).options(
        name="llmstream",
        ray_actor_options={"max_concurrency": 8, "num_cpus": 1},
    )
    serve.run(dep.bind(max_batch=2, max_len=64))
    port = start_proxy(0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llmstream/stream",
            data=_json.dumps(
                {"prompt": [1, 2, 3], "max_new_tokens": 5}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        tokens = []
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.headers.get("Content-Type") == "text/event-stream"
            for raw in r:
                line = raw.decode().strip()
                if line.startswith("data:"):
                    payload = _json.loads(line[5:].strip())
                    if payload is not None and "token" in payload:
                        tokens.append(payload["token"])
        assert len(tokens) == 5
    finally:
        stop_proxy()
        serve.shutdown()


def test_engine_counts_expert_load_for_a_moe_model_only(tiny_model,
                                                        naive_greedy):
    """``stats()["moe"]``: every real token of a prefill and every live
    slot of a decode step is given ``top_k`` experts in each layer, and
    nothing else is (bucket padding, idle slots); absent for a dense
    model."""
    cfg, params = tiny_model
    dense = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        dense.generate([1, 2, 3], max_new_tokens=2)
        assert "moe" not in dense.stats()
    finally:
        dense.shutdown()

    cfg = LlamaConfig.tiny(moe=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    engine = LLMEngine(cfg, params, max_batch=4, max_len=64)
    try:
        prompts = [list(np.random.RandomState(i).randint(0, 256, n))
                   for i, n in enumerate((5, 17, 9))]
        reqs = [engine.submit(p, 6) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        expected = [naive_greedy(params, p, cfg, 6) for p in prompts]
        assert outs == expected
        stats = engine.stats()
        moe = stats["moe"]
        per_token = cfg.top_k * cfg.num_layers
        assert moe["decode_assignments"] == \
            stats["decode_slot_steps"] * per_token
        assert moe["assignments"] == (
            stats["prefill_tokens"] + stats["decode_slot_steps"]) * per_token
        assert sum(moe["expert_tokens"]) == moe["assignments"]
        assert len(moe["expert_tokens"]) == cfg.n_experts
        assert moe["layer_steps"] == stats["decode_steps"] * cfg.num_layers
        # A layer of a decode step reaches between top_k experts and all.
        assert cfg.top_k * moe["layer_steps"] <= moe["experts_reached"] \
            <= cfg.n_experts * moe["layer_steps"]
        assert cfg.top_k * cfg.num_layers * 3 \
            <= moe["prefill_experts_reached"] \
            <= cfg.n_experts * cfg.num_layers * 3
    finally:
        engine.shutdown()


def test_engine_counts_the_programs_built_with_the_small_rows_kernel(
        monkeypatch, naive_greedy):
    """``stats()["moe"]``'s ``layer_calls`` is expert layers x programs
    run, and ``small_rows_layer_calls`` those whose program the rule of
    ops/grouped_matmul.py gave the kernel for few rows a group: none on
    the CPU; with a rule that gives it to the decode program alone (and
    the kernel interpreted), every decode step and no prefill, and the
    tokens are still a plain greedy decode's."""
    from ray_tpu.ops import grouped_matmul as gm

    cfg = LlamaConfig.tiny(moe=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompts = [list(np.random.RandomState(i).randint(0, 256, n))
               for i, n in enumerate((5, 17, 9))]

    def serve():
        engine = LLMEngine(cfg, params, max_batch=8, max_len=64)
        try:
            reqs = [engine.submit(p, 4) for p in prompts]
            assert [r.result(timeout=180) for r in reqs] == [
                naive_greedy(params, p, cfg, 4) for p in prompts]
            return engine.stats()
        finally:
            engine.shutdown()

    stats = serve()
    programs = stats["decode_steps"] + stats["prefills"]
    assert stats["moe"]["layer_calls"] == cfg.num_layers * programs
    assert stats["moe"]["small_rows_layer_calls"] == 0

    decode_rows = 8 * cfg.top_k
    kernel, traced = gm.small_rows_grouped_matmul, []
    monkeypatch.setattr(
        gm, "grouped_path", lambda rows, experts, mesh=None:
        "small_rows" if rows == decode_rows else "ragged_dot")
    monkeypatch.setattr(
        gm, "small_rows_grouped_matmul",
        lambda rows, weights, group_sizes, walk, epilogue: traced.append(
            rows.shape) or kernel(rows, weights, group_sizes, None, epilogue,
                                  None, True))
    stats = serve()
    # Gate and up in one call, down in a second, of one traced layer.
    assert traced == [(decode_rows, cfg.hidden_size),
                      (decode_rows, cfg.intermediate_size)]
    assert stats["prefills"] == 3 and stats["decode_steps"] >= 3
    assert stats["moe"]["layer_calls"] == cfg.num_layers * (
        stats["decode_steps"] + stats["prefills"])
    assert stats["moe"]["small_rows_layer_calls"] \
        == cfg.num_layers * stats["decode_steps"]


# ---- the loop one decode step ahead of its read-back (PR 39) ----------------

def _hold_admission(engine):
    """Stop the loop at the top of its next turn; ``.set()`` what is
    returned to let it go. Requests submitted meanwhile are all in the
    queue when admission next runs: a schedule that does not depend on
    when each ``submit`` returned."""
    import threading

    entered, gate = threading.Event(), threading.Event()
    admit = engine._admit

    def gated():
        entered.set()
        gate.wait()
        return admit()

    engine._admit = gated
    assert entered.wait(60)
    return gate


def _four_requests():
    """(prompts, new tokens): lengths on both sides of a page edge, four
    different counts, more requests than the engine below has slots."""
    rng = np.random.RandomState(11)
    news = [10, 14, 3, 6]
    return [list(rng.randint(0, 256, n)) for n in (5, 20, 17, 9)], news


def test_engine_a_step_ahead_serves_naive_greedys_tokens(tiny_model,
                                                         naive_greedy):
    """Step k+1 is queued before step k's tokens are read, in most
    steps, and nobody's tokens change: a slot that ends by its count is
    out of the step queued next, a waiting request takes it after."""
    cfg, params = tiny_model
    prompts, news = _four_requests()
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        gate = _hold_admission(engine)
        reqs = [engine.submit(p, n) for p, n in zip(prompts, news)]
        gate.set()
        outs = [r.result(timeout=180) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert outs == [naive_greedy(params, p, cfg, n)
                    for p, n in zip(prompts, news)]
    assert stats["decode_slot_steps"] == sum(news) - 4
    assert stats["decode_steps_ahead"] / stats["decode_steps"] > 0.5
    assert stats["decode_slot_steps_discarded"] == 0


def _host_split_chain(params, cfg, prompts, news, slots, temperature):
    """What a loop that reads every step before the next, and splits
    ``PRNGKey(0)`` on the host once a step, serves at ``temperature``:
    in a straight line, every token from the full ``forward`` with
    nothing cached. One rule is the engine's own: a slot freed by step
    j's tokens is taken in the turn after step j+1 was formed."""
    import jax.numpy as jnp

    from ray_tpu.models import forward
    from ray_tpu.models.generation import sample_logits

    last_logits = jax.jit(lambda params, row, n: forward(
        params, row[None], cfg)[0][0, n - 1])

    def logits(seq):
        row = np.zeros(64, np.int32)
        row[:len(seq)] = seq
        return last_logits(params, jnp.asarray(row), len(seq))

    outs = [[] for _ in prompts]
    queue, free, held, freed = list(range(len(prompts))), \
        list(range(slots)), {}, []
    rng = jax.random.PRNGKey(0)
    while queue or held:
        while free and queue:
            i, slot = queue.pop(0), free.pop()
            outs[i].append(int(sample_logits(
                logits(prompts[i])[None], jax.random.PRNGKey(0),
                temperature=temperature)[0]))
            held[slot] = i
        free, freed = free + freed, []
        if not held:
            continue
        rng, key = jax.random.split(rng)
        batch = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
        for slot, i in held.items():
            batch = batch.at[slot].set(logits(prompts[i] + outs[i]))
        nxt = sample_logits(batch, key, temperature=temperature)
        for slot, i in list(held.items()):
            outs[i].append(int(nxt[slot]))
            if len(outs[i]) == news[i]:
                freed.append(slot)
                del held[slot]
    return outs


def test_sampling_engine_draws_the_host_split_chain(tiny_model):
    """The key is split inside the decode program and stays on the
    device; the stream is the one the host-side split drew."""
    cfg, params = tiny_model
    prompts, news = _four_requests()
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64,
                       temperature=0.7)
    try:
        gate = _hold_admission(engine)
        reqs = [engine.submit(p, n) for p, n in zip(prompts, news)]
        gate.set()
        outs = [r.result(timeout=180) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    expected = _host_split_chain(params, cfg, prompts, news, 2, 0.7)
    assert outs == expected
    greedy = _host_split_chain(params, cfg, prompts, news, 2, 0.0)
    assert outs != greedy            # it did sample
    assert stats["decode_steps_ahead"] / stats["decode_steps"] > 0.5


def test_eos_drops_the_step_in_flight_and_the_next_tenant_decodes_right(
        tiny_model, naive_greedy):
    """An ``eos_token`` is known only at the read-back: the step
    already queued for the slot is thrown away (counted), nothing is
    emitted after the token, and slot and pages come back behind that
    step, to a tenant that decodes correctly from them."""
    cfg, params = tiny_model
    for seed in range(20):
        prompt = list(np.random.RandomState(seed).randint(0, 256, 7))
        expected = naive_greedy(params, prompt, cfg, 8)
        if expected[2] not in expected[:2]:
            break
    engine = LLMEngine(cfg, params, max_batch=1, max_len=32, total_pages=2)
    try:
        req = engine.submit(prompt, 8, eos_token=expected[2])
        assert list(req.tokens(timeout=120)) == expected[:3]
        assert req.result(timeout=1) == expected[:3]
        _wait_until(lambda: engine.stats()["free_pages"] == 2)
        stats = engine.stats()
        assert stats["decode_slot_steps_discarded"] == 1
        # Two served, one thrown away: the device ran all three.
        assert stats["decode_slot_steps"] == stats["decode_steps"] == 3
        assert (stats["finished"], stats["active_slots"],
                stats["free_slots"]) == (1, 0, 1)
        # Both pages again: 20 + 12 fill them to the last row.
        tenant = list(np.random.RandomState(99).randint(0, 256, 20))
        assert engine.generate(tenant, 12, timeout=120) == \
            naive_greedy(params, tenant, cfg, 12)
        assert engine.stats()["decode_slot_steps_discarded"] == 1
    finally:
        engine.shutdown()


def test_no_step_writes_past_the_pages_a_slot_holds(tiny_model,
                                                    naive_greedy):
    """A request whose prompt and answer end exactly on a page edge
    ends by its count while the other slot holds page 0, which is what
    a column past a slot's pages reads: it is out of the step queued
    behind its last one, its length stops a row short of the edge, and
    the other slot's tokens are its solo run's."""
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=32, page_size=16,
                       total_pages=3)
    try:
        # Pages are handed out from the list's end: the first request
        # gets page 2, the second pages 1 and 0.
        assert engine.books.free["full"] == [0, 1, 2]
        rng = np.random.RandomState(5)
        edge, other = list(rng.randint(0, 256, 10)), \
            list(rng.randint(0, 256, 20))
        gate = _hold_admission(engine)
        reqs = [engine.submit(edge, 6), engine.submit(other, 12)]
        gate.set()
        outs = [r.result(timeout=180) for r in reqs]
        _wait_until(lambda: engine.stats()["free_pages"] == 3)
        assert outs == [naive_greedy(params, edge, cfg, 6),
                        naive_greedy(params, other, cfg, 12)]
        # Rows written: the prompt's and every token's but the last.
        assert sorted(np.asarray(engine.runner.cache.lengths).tolist()) == [
            10 + 5, 20 + 11]
        assert engine.stats()["decode_slot_steps"] == 5 + 11
    finally:
        engine.shutdown()


@pytest.mark.parametrize("at", ["lull", "shutdown"])
def test_moe_counters_are_whole_with_a_step_in_flight(tiny_model, at):
    """The expert load is read a step behind the dispatch: at a lull
    the last step has been read, at a shutdown the step in flight is in
    no counter, so the counters agree with each other whenever read."""
    cfg = LlamaConfig.tiny(moe=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    engine = LLMEngine(cfg, params, max_batch=4, max_len=64)
    try:
        reqs = [engine.submit([1, 2, 3 + i], 50 if at == "shutdown" else 7)
                for i in range(3)]
        if at == "lull":
            for r in reqs:
                r.result(timeout=180)
            _wait_until(lambda: engine.stats()["active_slots"] == 0)
        else:
            _wait_until(lambda: engine.stats()["decode_steps"] >= 5)
    finally:
        engine.shutdown()
    stats = engine.stats()
    moe = stats["moe"]
    assert stats["decode_steps_ahead"] > 0
    if at == "lull":
        assert stats["decode_slot_steps"] == 3 * 6
    assert moe["layer_steps"] == stats["decode_steps"] * cfg.num_layers
    assert moe["decode_assignments"] == \
        stats["decode_slot_steps"] * cfg.top_k * cfg.num_layers
    assert moe["assignments"] == (
        stats["prefill_tokens"] + stats["decode_slot_steps"]
    ) * cfg.top_k * cfg.num_layers


@pytest.mark.parametrize("failing", ["dispatch", "readback"])
def test_a_failed_decode_with_a_step_in_flight_fails_each_request_once(
        tiny_model, naive_greedy, failing):
    """The third decode step fails, at its dispatch or when its tokens
    are read, with the second in flight or read: one cache reset, each
    open request failed once, and the engine serves the next one."""
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    real, calls = engine.runner.decode_step, []

    class Unreadable:
        def copy_to_host_async(self):
            pass

        def is_ready(self):     # what the next dispatch asks of it
            return True

        def __array__(self, *args, **kwargs):
            raise RuntimeError("decode fell over")

    def flaky(*args):
        calls.append(1)
        if len(calls) == 3 and failing == "dispatch":
            raise RuntimeError("decode fell over")
        out, *carry = real(*args)
        return (Unreadable() if len(calls) == 3 else out, *carry)

    flaky.flush_taps = real.flush_taps
    try:
        engine.runner.decode_step = flaky
        gate = _hold_admission(engine)
        reqs = [engine.submit([1, 2, 3], 20), engine.submit([4, 5], 20)]
        gate.set()
        for req in reqs:
            with pytest.raises(RuntimeError, match="fell over"):
                req.result(timeout=120)
        stats = engine.stats()
        assert (stats["failed"], stats["cache_resets"]) == (2, 1)
        assert stats["active_slots"] == 0 and stats["free_slots"] == 2
        assert stats["free_pages"] == stats["total_pages"]
        assert engine.generate([7, 8, 9], 8, timeout=120) == \
            naive_greedy(params, [7, 8, 9], cfg, 8)
        assert engine.stats()["finished"] == 1
    finally:
        engine.shutdown()


# ---- a model with window layers: one allocator, a pool a kind (PR 38) ------

@pytest.fixture(scope="module")
def window_model():
    """1 dense + 4 expert layers of kinds S S F S S, window 32: the
    benchmark's tiny Trinity (tests/bench_harness/trinity_tiny)."""
    import json
    import os

    from benchmark import arch

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", "trinity_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    return config, cfg, jax.jit(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(3))


def _wait_until(predicate, timeout=60.0):
    import time

    deadline = time.time() + timeout
    while not predicate():
        assert time.time() < deadline, "timed out"
        time.sleep(0.01)


def test_window_engine_serves_within_tolerance_of_the_reference(window_model):
    """Through the engine, four streams at once: prompts under the
    window, crossing it while decoding, over it and far over it, 90
    tokens each. Every served token's logit lies within 1e-4 of the
    plain reference's best at its position (teacher-forced, one full
    forward, no cache, no ring)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = window_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=256, page_size=16,
                       total_pages=48)
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 90) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    # One forward of the reference for all four, padded behind their ends.
    seqs = np.zeros((4, max(len(p) for p in prompts) + 90), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 90] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 89].max() <= 1e-4


def test_window_pool_holds_a_ring_and_the_full_pool_everything(window_model):
    """A 200-token context (120 + 80) holds window / page + 1 = 3 pages
    in the window pool and 13 in the full one, from admission to its
    end; both return on finish. The counters' arithmetic by hand: the
    step at context c reads c rows in the full layer and min(c, 32) in
    each of 4 window layers, and holds 13 + 4 x 3 pages against 5 x 13
    with one table."""
    _, cfg, params = window_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=32)
    try:
        assert engine.stats()["pages"] == {
            "window": {"layers": 4, "total": 2 * 3, "free": 6},
            "full": {"layers": 1, "total": 32, "free": 32}}
        req = engine.submit(list(range(120)), max_new_tokens=80)
        _wait_until(lambda: engine.stats()["active_slots"] == 1)
        held = engine.stats()
        assert held["pages"]["window"]["free"] == 6 - 3
        assert held["pages"]["full"]["free"] == 32 - 13
        assert held["free_pages"] == 32 - 13       # the pool that keeps all
        assert len(req.result(timeout=300)) == 80
        stats = engine.stats()
        assert stats["pages"]["window"]["free"] == 6
        assert stats["pages"]["full"]["free"] == stats["free_pages"] == 32
        # 79 decode steps, at contexts 121 .. 199 (the first token came
        # from the prefill).
        steps = stats["decode_steps"]
        assert steps == 79 and stats["decode_slot_steps"] == 79
        contexts = range(121, 200)
        assert stats["decode_kv_tokens"] == sum(contexts)
        assert stats["decode_kv_rows_read"] == sum(
            c + 4 * min(c, 32) for c in contexts)
        assert stats["kv_page_steps_held"] == steps * (13 + 4 * 3)
        assert stats["kv_page_steps_one_table"] == steps * 5 * 13
        # Experts: 4 of the 5 layers have them.
        assert stats["moe"]["layer_steps"] == steps * 4
        assert stats["moe"]["decode_assignments"] == steps * 4 * cfg.top_k
    finally:
        engine.shutdown()


def test_a_uniform_model_counts_rows_as_tokens_times_layers(tiny_model):
    """One definition: without window layers ``decode_kv_rows_read`` is
    ``decode_kv_tokens`` x L, and the pages held are one table's."""
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64)
    try:
        engine.generate([1, 2, 3, 4, 5], max_new_tokens=9)
        stats = engine.stats()
        assert set(stats["pages"]) == {"full"}
        assert stats["decode_kv_rows_read"] == \
            stats["decode_kv_tokens"] * cfg.num_layers > 0
        assert stats["kv_page_steps_held"] == \
            stats["kv_page_steps_one_table"] == \
            stats["decode_steps"] * cfg.num_layers * 1
    finally:
        engine.shutdown()


def test_a_pool_that_cannot_hold_a_request_refuses_it_at_submit(window_model):
    _, cfg, params = window_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=8)
    try:
        with pytest.raises(ValueError, match="full pool has only 8"):
            engine.submit(list(range(100)), max_new_tokens=100)
        # Its ring it could have had: the window pool refuses nothing
        # that fits a slot.
        assert len(engine.generate(list(range(100)), max_new_tokens=20)) == 20
    finally:
        engine.shutdown()


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_waits_on_whichever_pool_is_short(window_model, short):
    """Two requests, and one of the pools can hold only one of them at
    a time: the second waits for the first's pages (``page_waits``
    counts the rounds) and both finish. The window pool is sized for
    every slot, so it is short only with pages taken out of it."""
    _, cfg, params = window_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=128, page_size=16,
                       total_pages=5 if short == "full" else 16)
    try:
        if short == "window":
            del engine.books.free["window"][3:]     # one ring is left
        a = engine.submit(list(range(40)), max_new_tokens=30)   # 5 pages
        b = engine.submit(list(range(40, 80)), max_new_tokens=30)
        assert len(a.result(timeout=300)) == 30
        assert len(b.result(timeout=300)) == 30
        stats = engine.stats()
        assert stats["page_waits"] >= 1
        assert stats["pages"][short]["free"] == (5 if short == "full" else 3)
    finally:
        engine.shutdown()


# ---- a model with latent attention: one pool of rows (PR 42) ---------------

@pytest.fixture(scope="module")
def latent_model():
    """1 dense + 3 expert layers, q.k 24 beside v 12, ranks 24 and 32:
    the benchmark's tiny JoyAI (tests/bench_harness/joyai_tiny)."""
    import json
    import os

    from benchmark import arch

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", "joyai_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    return config, cfg, jax.jit(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(3))


def test_latent_engine_serves_within_tolerance_of_the_reference(latent_model):
    """Through the engine, four streams at once at different lengths, 60
    tokens each: prefill rebuilds k and v, decode attends absorbed over
    the latent pool. Every served token's logit lies within 1e-4 of the
    plain reference's best at its position (teacher-forced, one full
    forward, no cache, no absorption)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = latent_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=256, page_size=16,
                       total_pages=48)
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 60) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    seqs = np.zeros((4, max(len(p) for p in prompts) + 60), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 60] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 59].max() <= 1e-4


@pytest.fixture(scope="module")
def selecting_model():
    """Latent attention under a learned selection of 24 positions, 4 of
    8 experts held: the benchmark's tiny GLM-5.2 share
    (tests/bench_harness/glm52_tiny)."""
    import json
    import os

    from benchmark import arch

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", "glm52_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    return config, cfg, init_params(cfg, jax.random.PRNGKey(3))


def test_selecting_engine_serves_within_tolerance_of_the_reference(
        selecting_model):
    """Through the engine, four streams at once at different lengths, 60
    tokens each, contexts on both sides of ``index_topk`` 24: every
    served token's logit lies within 1e-4 of the plain reference's best
    at its position. And the engine's account of it: two pools on one
    table, the rows the selection kept beside the rows held, the
    assignments that fell on the other chip's experts."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = selecting_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=256, page_size=16,
                       total_pages=48)
    try:
        before = engine.stats()
        assert before["pages"] == {
            "latent": {"layers": 4, "total": 48, "free": 48},
            "index": {"layers": 2, "total": 48, "free": 48}}
        assert before["kv_row_bytes"] == {"latent": 160 * 4, "index": 16 * 4}
        assert before["decode_attention"] == "gather"          # on the CPU
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 60) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    seqs = np.zeros((4, max(len(p) for p in prompts) + 60), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 60] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 59].max() <= 1e-4
    assert stats["pages"]["index"]["free"] == 48
    assert stats["decode_kv_rows_read"] == 4 * stats["decode_kv_tokens"]
    # A step at context c takes min(c, 24) rows in each of 4 layers.
    assert 0 < stats["decode_kv_rows_selected"] < stats["decode_kv_rows_read"]
    assert stats["decode_kv_rows_selected"] <= 4 * 24 * stats[
        "decode_slot_steps"]
    moe = stats["moe"]
    assert len(moe["expert_tokens"]) == 4
    assert moe["assignments"] == sum(moe["expert_tokens"])
    # Three expert layers, two experts a token, every token of every
    # prompt and every decode step: what was not held went elsewhere.
    tokens = sum(map(len, prompts)) + stats["decode_slot_steps"]
    assert moe["assignments"] + moe["assignments_elsewhere"] == 3 * 2 * tokens
    assert 0.3 < moe["assignments"] / (3 * 2 * tokens) < 0.7


def test_latent_pool_pages_are_held_from_admission_to_finish(latent_model):
    """A 100-token context (60 + 40) holds 7 pages of the one pool, of
    kind "latent", from admission to its end; they return on finish.
    The counters: a step at context c reads c rows in each of 4 layers;
    a row is 32 + 128 float32 values."""
    _, cfg, params = latent_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=20)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "latent": {"layers": 4, "total": 20, "free": 20}}
        assert stats["kv_row_bytes"] == {"latent": 160 * 4}
        assert stats["decode_attention"] == "gather"          # on the CPU
        req = engine.submit(list(range(60)), max_new_tokens=40)
        _wait_until(lambda: engine.stats()["active_slots"] == 1)
        held = engine.stats()
        assert held["pages"]["latent"]["free"] == held["free_pages"] == 20 - 7
        assert len(req.result(timeout=300)) == 40
        stats = engine.stats()
        assert stats["pages"]["latent"]["free"] == stats["free_pages"] == 20
        contexts = range(61, 100)        # 39 decode steps after the prefill
        assert stats["decode_steps"] == 39
        assert stats["decode_kv_tokens"] == sum(contexts)
        assert stats["decode_kv_rows_read"] == 4 * sum(contexts)
        assert stats["kv_page_steps_held"] == \
            stats["kv_page_steps_one_table"] == 39 * 4 * 7
        assert stats["moe"]["layer_steps"] == 39 * 3
    finally:
        engine.shutdown()


def test_a_k_and_v_pool_says_what_its_rows_hold(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    try:
        # k and v, 2 KV heads of 16 float32 values each.
        assert engine.stats()["kv_row_bytes"] == {"full": 2 * 2 * 16 * 4}
    finally:
        engine.shutdown()


@pytest.fixture(scope="module")
def state_model():
    """3 retention layers, 4 query heads on 2 KV heads of 16: the
    benchmark's tiny Brumby (tests/bench_harness/brumby_tiny)."""
    import json
    import os

    from benchmark import arch

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", "brumby_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    return config, cfg, jax.jit(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(3))


def test_state_engine_serves_within_tolerance_of_the_reference(state_model):
    """Through the engine, six requests over four slots (two slots are
    taken again, one after a longer request), 40 tokens each: the
    chunked scan lays a state into the slot, decode updates it in place.
    Every served token's logit lies within 1e-3 of the plain reference's
    best at its position (teacher-forced, the attention form, no
    state)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = state_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, **config["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n))
                   for n in (10, 25, 150, 100, 17, 64)]
        reqs = [engine.submit(p, 40) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    seqs = np.zeros((6, 257), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 40] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 39].max() <= 1e-3


def test_a_state_engine_admits_by_slots_alone_and_counts_states(state_model):
    """A pool of states has no pages: nothing to reserve, wait for or
    return; two slots admit two requests whatever their lengths and the
    third waits for a slot. The gauge says what a slot holds in a layer,
    the counter how many states the decode steps moved."""
    _, cfg, params = state_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=1)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "state": {"layers": 3, "total": 0, "free": 0}}
        assert stats["kv_row_bytes"] == {} and stats["free_pages"] == 0
        # 2 KV heads x 9 turns x 24 rows x 16 float32.
        assert stats["state_slot_bytes"] == {"state": 2 * 9 * 24 * 16 * 4}
        assert stats["decode_attention"] == "xla"             # on the CPU
        reqs = [engine.submit(list(range(n)), max_new_tokens=m)
                for n, m in ((200, 40), (9, 30), (60, 20))]
        assert [len(r.result(timeout=300)) for r in reqs] == [40, 30, 20]
        stats = engine.stats()
        assert stats["page_waits"] == 0 and stats["finished"] == 3
        assert stats["free_slots"] == 2
        assert stats["decode_slot_steps"] == 39 + 29 + 19
        assert stats["decode_state_slot_layers"] == 3 * (39 + 29 + 19)
        assert stats["decode_kv_rows_read"] == 0
        assert stats["kv_page_steps_held"] == 0
        # Longer than max_len is still refused: the positions' bound.
        with pytest.raises(ValueError, match="max_len"):
            engine.submit(list(range(250)), max_new_tokens=10)
    finally:
        engine.shutdown()


def test_a_paged_engine_has_no_state_gauge_and_counts_no_states(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    try:
        assert len(engine.generate(list(range(5)), max_new_tokens=4)) == 4
        stats = engine.stats()
        assert stats["state_slot_bytes"] == {}
        assert stats["decode_state_slot_layers"] == 0
    finally:
        engine.shutdown()


# ---- stats()'s key tree: what benchmark/readers/*.py were written against --

# Written from the output of the engine as one class (PR 51's tree).
_STATS_KEYS = [
    "active_slots", "admitted", "cache_resets", "decode_attention",
    "decode_delta", "decode_dispatch", "decode_kv_rows_read", "decode_kv_rows_selected", "decode_kv_tokens",
    "decode_slot_steps",
    "decode_slot_steps_discarded", "decode_state_slot_layers",
    "decode_steps", "decode_steps_ahead", "device_kind", "failed",
    "finished", "free_pages", "free_slots", "kv_page_steps_held",
    "kv_page_steps_one_table", "kv_row_bytes", "latent_walk_step_tokens",
    "page_size", "page_waits",
    "page_walk_step_tokens", "pages", "phase_cpu_s", "phase_s", "platform",
    "prefill_bucket_tokens",
    "prefill_streamed_bucket_tokens", "prefill_tokens", "prefills", "queued",
    "requests", "state_slot_bytes", "stream", "submitted", "t", "total_pages"]
_NESTED_KEYS = {
    "phase_s": ["admit", "admit_stalling", "decode", "emit", "idle",
                "inputs", "readback"],
    "phase_cpu_s": ["admit", "decode", "emit", "idle", "inputs", "readback"],
    "decode_dispatch": ["fed", "starved_host", "starved_lull",
                        "starved_prefill"],
    "stream": ["backlog", "emit_gap_hist", "held_cpu_s", "held_hist",
               "held_s", "held_timed_s", "hist_edges_s", "taken_lag_hist",
               "taken_lag_s",
               "tokens_emitted", "tokens_taken"],
    "moe": ["assignments", "decode_assignments", "expert_tokens",
            "experts_reached", "layer_calls", "layer_steps",
            "prefill_experts_reached", "small_rows_layer_calls"]}
_NOT_INT = {"decode_attention": str, "decode_delta": str, "device_kind": str, "platform": str,
            "t": float, "requests": list, "kv_row_bytes": dict,
            "latent_walk_step_tokens": dict,
            "page_walk_step_tokens": dict, "pages": dict, "phase_s": dict,
            "phase_cpu_s": dict, "decode_dispatch": dict,
            "state_slot_bytes": dict, "stream": dict, "moe": dict}


@pytest.fixture(scope="module")
def hybrid_model():
    """Delta layers among latent ones, K K K M K K M, the first FFN
    dense, 2 of 32 experts held: the benchmark's tiny Kimi-Linear
    (tests/bench_harness/kimi_tiny)."""
    import json
    import os

    from benchmark import arch

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", "kimi_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    return config, cfg, jax.jit(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(3))


def test_hybrid_engine_serves_within_tolerance_of_the_reference(hybrid_model):
    """Through the engine, seven requests over four slots (three slots
    are taken again, one after a longer request), 40 tokens each: a
    prefill lays a slot's delta states, its convolution histories and
    its latent pages from one prompt, decode steps all three. Every
    served token's logit lies within 1e-4 of the plain reference's best
    at its position (teacher-forced, the delta rule token by token, no
    cache)."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = hybrid_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, **config["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n))
                   for n in (10, 25, 150, 100, 17, 64, 3)]
        reqs = [engine.submit(p, 40) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    finally:
        engine.shutdown()
    seqs = np.zeros((len(prompts), 257), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 40] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: reference.logit_margins(params, seqs, config))(
            params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 39].max() <= 1e-4


def test_a_hybrid_engine_admits_by_slot_and_latent_pages_together(
        hybrid_model):
    """One admission: a slot (the delta pools have no pages) and the
    latent pool's pages. Two slots and pages for 288 tokens: the third
    request waits for a slot; the gauges say what a token and a slot
    hold, the counters what the steps read of each."""
    _, cfg, params = hybrid_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=256, page_size=16,
                       total_pages=18)
    try:
        stats = engine.stats()
        assert stats["pages"] == {
            "delta": {"layers": 5, "total": 0, "free": 0},
            "latent": {"layers": 2, "total": 18, "free": 18}}
        assert stats["free_pages"] == 18
        # A latent row: 32 + 8 values on a lane tile of their own.
        assert stats["kv_row_bytes"] == {"latent": (32 + 128) * 4}
        # 4 heads of 16 x 16 float32, and 3 rows of q|k|v of 4 x 16.
        assert stats["state_slot_bytes"] == {
            "delta": 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4}
        assert (stats["decode_attention"], stats["decode_delta"]) == (
            "gather", "xla")                                  # on the CPU
        reqs = [engine.submit(list(range(n)), max_new_tokens=m)
                for n, m in ((200, 40), (9, 30), (60, 20))]
        assert [len(r.result(timeout=300)) for r in reqs] == [40, 30, 20]
        stats = engine.stats()
        assert stats["finished"] == 3 and stats["free_slots"] == 2
        assert stats["pages"]["latent"]["free"] == 18
        steps = 39 + 29 + 19
        assert stats["decode_slot_steps"] == steps
        assert stats["decode_state_slot_layers"] == 5 * steps
        contexts = sum(n + i for n, m in ((200, 40), (9, 30), (60, 20))
                       for i in range(1, m))
        assert stats["decode_kv_tokens"] == contexts
        assert stats["decode_kv_rows_read"] == 2 * contexts
        assert stats["moe"]["assignments_elsewhere"] > 0
    finally:
        engine.shutdown()


@pytest.mark.parametrize("model, pools, slot_pools, moe", [
    ("tiny_model", ["full"], [], False),
    ("tiny_moe", ["full"], [], True),
    ("window_model", ["full", "window"], [], True),
    ("latent_model", ["latent"], [], True),
    ("state_model", [], ["state"], False)])
def test_stats_keys_and_types_are_the_one_class_engines(
        request, model, pools, slot_pools, moe):
    """Every key, nested key and type of ``stats()``, after one served
    request, against a literal list: the three owners' readings merge to
    what the readers of ``benchmark/readers`` index."""
    if model == "tiny_moe":
        cfg = LlamaConfig.tiny(moe=True)
        params = init_params(cfg, jax.random.PRNGKey(1))
    else:
        cfg, params = request.getfixturevalue(model)[-2:]
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    try:
        assert len(engine.generate([1, 2, 3], max_new_tokens=4)) == 4
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert sorted(stats) == sorted(_STATS_KEYS + ["moe"] * moe)
    assert {key: type(value) for key, value in stats.items()} == {
        key: _NOT_INT.get(key, int) for key in stats}
    for key, nested in _NESTED_KEYS.items():
        assert sorted(stats.get(key, nested)) == nested
    assert isinstance(stats.get("moe", {}).get("expert_tokens", []), list)
    assert sorted(stats["pages"]) == sorted(pools + slot_pools)
    assert all(sorted(pool) == ["free", "layers", "total"]
               for pool in stats["pages"].values())
    assert sorted(stats["kv_row_bytes"]) == pools
    assert sorted(stats["state_slot_bytes"]) == slot_pools
    (row,) = stats["requests"]
    assert [type(field) for field in row] == [
        float, float, float, float, int, int, type(None), type(None)]


# ---- a looped model: passes, a deeper pool, an exit gate -------------------

@pytest.fixture(scope="module")
def looped_model():
    """3 layers run 4 times, four norms a layer, an exit gate: the
    benchmark's tiny Ouro (tests/bench_harness/ouro_tiny)."""
    import json
    import os

    from benchmark import arch

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "bench_harness", "ouro_tiny",
                           "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    return config, cfg, jax.jit(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(3))


def test_looped_engine_serves_the_reference_and_counts_its_loop(looped_model):
    """Through the engine, three streams at once over a pool 12 layers
    deep: every served token's logit within 1e-4 of the plain
    reference's best at its position, and ``stats()["loop"]``: four
    passes a decode step, one token counted a prefill and a slot a step,
    the mean exit pass the reference's own exit distribution gives for
    the served tokens, a token's bytes over all 12 pool layers; rows
    read and pages held are counted at that depth."""
    import jax.numpy as jnp

    from benchmark import arch

    config, cfg, params = looped_model
    reference = arch.reference(config)
    engine = LLMEngine(cfg, params, max_batch=4, max_len=128, page_size=16,
                       total_pages=24)
    new = 30
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 60)]
        reqs = [engine.submit(p, new) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    seqs = np.zeros((3, max(len(p) for p in prompts) + new), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + new] = prompt + out
    margins, exits = jax.jit(lambda params, seqs: (
        reference.logit_margins(params, seqs, config),
        reference.exit_distribution(params, seqs[:, :-1], config)))(
            params, jnp.asarray(seqs))
    margins, exits = np.asarray(margins), np.asarray(exits)
    expected = 0.0
    for row, p, prompt in zip(margins, exits, prompts):
        made = slice(len(prompt) - 1, len(prompt) + new - 1)
        assert row[made].max() <= 1e-4
        expected += (p[made] * np.arange(1, 5)).sum()
    loop = stats["loop"]
    assert sorted(loop) == ["exit_pass_sum", "exit_tokens", "kv_token_bytes",
                            "passes"]
    assert loop["passes"] == 4 * stats["decode_steps"]
    assert loop["exit_tokens"] == 3 * new == (
        stats["prefills"] + stats["decode_slot_steps"])
    assert loop["exit_pass_sum"] == pytest.approx(expected, rel=1e-4)
    assert 1.0 < loop["exit_pass_sum"] / loop["exit_tokens"] < 4.0
    # 12 pool layers of a key and a value row of 4 heads of 16, float32.
    assert stats["kv_row_bytes"] == {"full": 2 * 4 * 16 * 4}
    assert loop["kv_token_bytes"] == 12 * 512
    assert stats["pages"]["full"]["layers"] == 12
    assert stats["decode_kv_rows_read"] == 12 * stats["decode_kv_tokens"]
    assert stats["kv_page_steps_held"] == stats["kv_page_steps_one_table"]
    assert stats["free_pages"] == 24


def test_an_exit_threshold_under_one_is_refused_by_name(looped_model):
    import dataclasses

    _, cfg, params = looped_model
    with pytest.raises(NotImplementedError, match="stop at different passes"):
        LLMEngine(dataclasses.replace(cfg, exit_threshold=0.9), params,
                  max_batch=2, max_len=64, page_size=16)


def test_a_model_of_one_pass_has_no_loop_in_its_stats(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    try:
        engine.generate([1, 2, 3], max_new_tokens=3)
        assert "loop" not in engine.stats()
    finally:
        engine.shutdown()
