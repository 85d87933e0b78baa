"""Continuous-batching LLM engine tests: the engine on the tiny dense and
mixture-of-experts models, the loop a step ahead of its read-back, and
``stats()``'s key tree. An attention kind's engine is
tests/test_serve_llm_<kind>.py; the models are ``tests/conftest.py``'s."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402


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
        wait_until, tiny_model, naive_greedy):
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
        wait_until(lambda: engine.stats()["free_pages"] == 2)
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


def test_no_step_writes_past_the_pages_a_slot_holds(
        wait_until, tiny_model, naive_greedy):
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
        wait_until(lambda: engine.stats()["free_pages"] == 3)
        assert outs == [naive_greedy(params, edge, cfg, 6),
                        naive_greedy(params, other, cfg, 12)]
        # Rows written: the prompt's and every token's but the last.
        assert sorted(np.asarray(engine.runner.cache.lengths).tolist()) == [
            10 + 5, 20 + 11]
        assert engine.stats()["decode_slot_steps"] == 5 + 11
    finally:
        engine.shutdown()


@pytest.mark.parametrize("at", ["lull", "shutdown"])
def test_moe_counters_are_whole_with_a_step_in_flight(wait_until,
                                                      tiny_model, at):
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
            wait_until(lambda: engine.stats()["active_slots"] == 0)
        else:
            wait_until(lambda: engine.stats()["decode_steps"] >= 5)
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


def test_a_k_and_v_pool_says_what_its_rows_hold(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    try:
        # k and v, 2 KV heads of 16 float32 values each.
        assert engine.stats()["kv_row_bytes"] == {"full": 2 * 2 * 16 * 4}
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
    "decode_linear", "decode_slot_steps",
    "decode_slot_steps_discarded", "decode_state_slot_layers",
    "decode_steps", "decode_steps_ahead", "device_kind", "failed",
    "finished", "free_pages", "free_slots", "kv_page_steps_held",
    "kv_page_steps_one_table", "kv_row_bytes", "latent_walk_step_tokens",
    "page_size", "page_waits",
    "page_walk_step_tokens", "pages", "phase_cpu_s", "phase_s", "platform",
    "prefill_bucket_tokens",
    "prefill_streamed_bucket_tokens", "prefill_tokens", "prefills", "queued",
    "requests", "state_slot_bytes", "stream", "submitted", "t", "total_pages",
    "weights"]
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
    "weights": ["bytes_turned", "leaves_turned", "turn_s"],
    "moe": ["assignments", "decode_assignments", "expert_tokens",
            "experts_reached", "layer_calls", "layer_steps",
            "prefill_experts_reached", "small_rows_layer_calls"]}
_NOT_INT = {"decode_attention": str, "decode_delta": str,
            "decode_linear": str, "device_kind": str, "platform": str,
            "t": float, "requests": list, "kv_row_bytes": dict,
            "latent_walk_step_tokens": dict,
            "page_walk_step_tokens": dict, "pages": dict, "phase_s": dict,
            "phase_cpu_s": dict, "decode_dispatch": dict,
            "state_slot_bytes": dict, "stream": dict, "moe": dict,
            "weights": dict}


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


def test_a_model_of_one_pass_has_no_loop_in_its_stats(tiny_model):
    cfg, params = tiny_model
    engine = LLMEngine(cfg, params, max_batch=2, max_len=64, page_size=16)
    try:
        engine.generate([1, 2, 3], max_new_tokens=3)
        assert "loop" not in engine.stats()
    finally:
        engine.shutdown()
