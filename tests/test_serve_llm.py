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
