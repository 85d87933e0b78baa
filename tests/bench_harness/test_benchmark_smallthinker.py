"""SmallThinker's configuration (``benchmark/smallthinker_*.py``,
``benchmark/readers/smallthinker.py``): the file against the catalog's
row, the counts at the published widths, the float32 reference against
the program at a tiny size (``smallthinker_tiny/config.json``: hidden 64,
14 heads on 2 of 16, groups of 7, 8 ReGLU experts top-3, two periods
F S S S F S S S, window 32, page 16), the published router against the
program's, five single departures from the published layer each refused
a thousand times over, the engine serving it, and the new readers on
hand-made records. CPU, no processes."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, smallthinker_counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import smallthinker as readers  # noqa: E402
from benchmark.readers import window as window_readers  # noqa: E402

TOL = 1e-4
CELL = "serve-smallthinker-c16-8k"


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "smallthinker-21b-a3b-L8.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "smallthinker_tiny", "config.json")


PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


def test_file_holds_the_catalogs_row_and_cuts_depth_alone(config):
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    entry, = [c for c in bench_run.load_benchmark()["configs"]
              if c["name"] == "smallthinker-21b-a3b-L8"]
    assert entry["reduced"] == ["num_hidden_layers"]
    arch.check_reduced(entry, config)
    assert (config["reduced"]["num_hidden_layers"]["published"],
            config["num_hidden_layers"]) == (52, 8)
    # The lists are copied whole; the eight layers that run are their
    # first eight, two whole periods with the full layer first.
    assert smallthinker_counts.layer_windows(config) == [
        None, 4096, 4096, 4096] * 2
    assert smallthinker_counts.layer_rotary(config) == [
        False, True, True, True] * 2
    for key in ("assumed", "not_served", "stands_for", "engine_why"):
        assert config[key]
    assert config["engine"] == {"max_batch": 16, "max_len": 16384,
                                "page_size": 16, "total_pages": 16384}
    # Not the source's key: the accepted routed-matmul readers' name for
    # an expert's width, and the same number.
    assert config["moe_intermediate_size"] == config["moe_ffn_hidden_size"]


def test_the_cell_is_the_accepted_traffic_file_unchanged():
    bench = bench_run.load_benchmark()
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-L8", "chat-closed-c16-8k", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "experts_reached_mean.chat", "expert_load_max_over_mean.chat",
        "routed_matmul_time_share.chat", "routed_matmul_roofline.chat",
        "page_walk_roofline.chat", "prefill_flash_roofline.chat",
        "decode_step_roofline_rows.chat", "window_kv_read_share.chat",
        "kv_held_share.chat", "prefill_stream_roofline.chat",
        "prefill_streamed_share.chat"}
    assert "grouped_small_rows_share.chat" not in listed
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("prefill_stream")}
    assert all(m["workloads"] == [CELL] and m["layer"] == "prefill program"
               and m["moves"] == "gap_p90_s" for m in new.values())
    assert (new["prefill_stream_roofline.chat"]["source"],
            new["prefill_streamed_share.chat"]["source"]) == (
                "device_trace", "program_counter")


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.expert_size, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads, cfg.dh, cfg.vocab_size) == (
                2560, 768, 8, 28, 4, 128, 151936)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts,
            cfg.num_dense_layers) == (64, 6, 0, 0)
    assert (cfg.router_score, cfg.router_bias, cfg.route_norm,
            cfg.route_scale, cfg.router_input, cfg.expert_act) == (
                "softmax", False, True, 1.0, "attention", "relu")
    assert cfg.layer_types == ("full", "window", "window", "window") * 2
    assert (cfg.sliding_window, cfg.rope_full_layers, cfg.qk_norm,
            cfg.attn_gate, cfg.post_norms, cfg.rope_theta, cfg.rms_eps) == (
                4096, False, False, False, False, 1.5e6, 1e-6)
    assert str(cfg.dtype) == "bfloat16"
    unlike = dict(config, rope_layout=[1] * 52)
    with pytest.raises(NotImplementedError, match="differ"):
        arch.program_config(unlike)


def test_the_stack_is_runs_of_alike_layers(config):
    """F, S S S, F, S S S: four runs, the full layer first in its
    period, the KV layers numbered within their kind."""
    import jax
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import PagedKVCache
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = arch.program_config(config)
    assert [tuple(r) for r in layer_runs(cfg)] == [
        (0, 1, True, "full", 0), (1, 3, True, "window", 0),
        (4, 1, True, "full", 1), (5, 3, True, "window", 3)]
    assert kv_layers(cfg) == {"full": 2, "window": 6}
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    stacks = shapes["layers"]
    assert [s["wq"].shape for s in stacks] == [
        (n, 2560, 28, 128) for n in (1, 3, 1, 3)]
    assert stacks[1]["router"].shape == (3, 2560, 64)
    assert stacks[1]["w_gate"].shape == (3, 64, 2560, 768)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == smallthinker_counts.param_counts(config)["total"]
    # A ring of 4096 / 16 + 1 pages a slot beside the pool that keeps
    # everything.
    engine = config["engine"]
    sizes = PagedKVCache.sizes(cfg, engine["max_batch"],
                               engine["total_pages"], engine["page_size"],
                               engine["max_len"] // engine["page_size"])
    assert sizes == {"full": (2, 16384, 1024), "window": (6, 16 * 257, 257)}


def test_counts_at_the_published_widths(config):
    sizes = smallthinker_counts.param_counts(config)
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    expert = 3 * 2560 * 768
    assert (sizes["attn"], sizes["expert"], sizes["router"]) == (
        attn, expert, 163840) == (20971520, 5898240, 163840)
    assert sizes["layer"] == attn + 163840 + 64 * expert == 398622720
    assert sizes["matmul"] == 8 * (attn + 163840 + 6 * expert) + 2560 * 151936
    assert sizes["total"] == 8 * 398622720 + 2 * 388956160 + 17 * 2560
    assert round(sizes["total"] * 2 / 1e9, 2) == 7.93
    assert smallthinker_counts.kv_row_bytes(config) == 2048
    assert smallthinker_counts.kv_bytes_per_token(config) == 8 * 2048
    # Pairs: all of the triangle under the window, a band over it; a
    # window layer's work at 16,384 tokens is its window's.
    pairs = smallthinker_counts.attended_pairs
    assert pairs(16384, 4096) == 4096 * 4097 // 2 + 12288 * 4096
    assert smallthinker_counts.flash_streamed_flops(config, 16384) == (
        4 * 28 * 128 * (2 * pairs(16384) + 6 * pairs(16384, 4096)))
    assert smallthinker_counts.flash_prefill_flops(config, 3000) == (
        4 * 28 * 128 * 8 * pairs(3000))
    assert smallthinker_counts.flash_streamed_bytes(config, 16384) == (
        8 * (2 * 28 + 2 * 4) * 16384 * 128 * 2)
    # The interface's decode counts are the rows counts with every layer
    # reading the whole context and an even router.
    even = 8 * smallthinker_counts.experts_reached_even(config, 16)
    assert 8 * 50 < even < 8 * 51
    assert smallthinker_counts.decode_step_bytes(config, 16, 160000) == \
        smallthinker_counts.decode_step_bytes_rows(
            config, 16, 8 * 160000, even)
    # The issue's step: 16 contexts of ~10k, ~51 experts a layer.
    step = smallthinker_counts.decode_step_bytes_rows(
        config, 16, 16 * (2 * 10000 + 6 * 4096), even)
    assert 6.5e9 < step < 7.5e9
    assert "jax" not in {m.split(".")[0] for m in vars(smallthinker_counts)
                         if hasattr(vars(smallthinker_counts)[m], "__file__")}


# ---- the program against the reference, float32, tiny ----------------------

def _tiny_model(tiny, seed=3, **changes):
    import jax
    from ray_tpu.models import init_params

    cfg = dataclasses.replace(arch.program_config(tiny), **changes)
    return cfg, init_params(cfg, jax.random.PRNGKey(seed))


def _program_logits(cfg, params, seqs, prompt_lens, steps, page=16):
    """Each sequence's prompt through ``paged_prefill`` into a slot of
    its own, then ``steps`` teacher-forced ``paged_decode`` steps with
    every slot live, slots at different lengths: {slot: logits
    [1 + steps, V]} at the positions the programs computed."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.generation import (
        PagedKVCache, paged_decode, paged_prefill)

    prefill = jax.jit(lambda params, tokens, n, cache, slot, pages:
                      paged_prefill(params, tokens, n, cache, cfg, slot, pages))
    decode = jax.jit(lambda params, last, cache, active:
                     paged_decode(params, last, cache, cfg, active=active))
    slots = len(seqs) + 1                       # the last one stays idle
    per_seq = 256 // page
    cache = PagedKVCache.create(cfg, slots, slots * per_seq, page, per_seq)
    sizes = PagedKVCache.sizes(cfg, slots, slots * per_seq, page, per_seq)
    tables = {kind: np.zeros((slots, columns), np.int32)
              for kind, (_, _, columns) in sizes.items()}
    out = {}
    for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        bucket = page
        while bucket < n:
            bucket *= 2
        pages = {}
        for kind, (_, pool, columns) in sizes.items():
            # The slot's pages, from the pool's end and out of order.
            ids = (pool - 1 - slot * columns - np.arange(columns))[::-1]
            tables[kind][slot] = ids
            pages[kind] = jnp.asarray(ids[:min(bucket // page, columns)])
        cache = cache._replace(page_table={
            k: jnp.asarray(t) for k, t in tables.items()})
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = seq[:n]
        logits, cache, _ = prefill(
            params, jnp.asarray(padded), jnp.asarray(n, jnp.int32), cache,
            slot, pages)
        out[slot] = [np.asarray(logits)[0]]
    active = jnp.asarray(np.arange(slots) < len(seqs))
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
            last[slot] = seq[n + i]
        logits, cache, _ = decode(params, jnp.asarray(last), cache, active)
        for slot in out:
            out[slot].append(np.asarray(logits)[slot])
    return {slot: np.stack(rows) for slot, rows in out.items()}


def _worst_difference(tiny, cfg, params, prompt_lens, steps, ref_params=None):
    import jax
    import jax.numpy as jnp

    reference = arch.reference(tiny)
    rng = np.random.RandomState(sum(prompt_lens))
    seqs = [rng.randint(0, 256, n + steps) for n in prompt_lens]
    got = _program_logits(cfg, params, seqs, prompt_lens, steps)
    # One forward of the reference for all of them, padded behind their
    # ends (to whole blocks of its queries): a causal model's logits do
    # not see what follows.
    longest = max(map(len, seqs))
    padded = np.zeros((len(seqs), -(-longest // 128) * 128), np.int32)
    for row, seq in zip(padded, seqs):
        row[:len(seq)] = seq
    want = np.asarray(jax.jit(
        lambda params, tokens: reference.logits(params, tokens, tiny))(
            ref_params or params, jnp.asarray(padded)))
    return max(np.abs(got[slot] - want[slot, n - 1:n + steps]).max()
               for slot, n in enumerate(prompt_lens))


def test_prefill_then_decode_equals_the_reference(tiny):
    """Prompts under the window (10), crossing it while decoding (25),
    over it (40, a bucket of 64 over a ring of 3 pages) and far over it
    (100), in one batch whose slots are at different lengths, 84 decode
    steps through the ring and the full pool, 7 query heads a KV head:
    every logit within 1e-4 of the reference's full forward."""
    cfg, params = _tiny_model(tiny)
    assert cfg.num_heads // cfg.num_kv_heads == 7
    assert _worst_difference(tiny, cfg, params, (10, 25, 40, 100), 84) < TOL


DEPARTURES = {
    "the-router-reads-the-ffns-input": {"router_input": "ffn"},
    "silu-for-relu": {"expert_act": "silu"},
    "no-renormalisation": {"route_norm": False},
    "rotary-on-the-full-layers": {"rope_full_layers": True},
    "window-off-by-one": {"sliding_window": 31},
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_a_single_departure_fails_a_thousand_times_over(tiny, departure):
    """Each way the program could leave the published layer moves a
    logit by at least 1000 x the tolerance it is held to: the router's
    placement and ReGLU among them."""
    cfg, params = _tiny_model(tiny, **DEPARTURES[departure])
    assert _worst_difference(tiny, cfg, params, (40,), 6) > 1000 * TOL


def test_the_published_router_is_the_programs(tiny):
    """Top-k of the logits then a softmax over the chosen (the release,
    the reference) = a softmax over all, the k largest, renormalised
    (``route(score="softmax", renormalize=True)``, the program)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel.moe import route

    reference = arch.reference(tiny)
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (512, 8))
    want = np.asarray(reference.route(logits, tiny))       # [T, E]
    _, gates, experts = route(
        logits, tiny["moe_num_active_primary_experts"], score="softmax",
        renormalize=True)
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(experts), np.asarray(gates), -1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert ((want > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)
    # ... and neither is the gates as they fall (OLMoE's).
    _, plain, _ = route(logits, 3, score="softmax")
    assert np.abs(np.asarray(plain).sum(-1) - 1.0).max() > 0.1


def test_the_loss_of_a_stack_that_trains_equals_the_reference(tiny):
    """With every layer full (one run: what ``causal_lm_loss`` takes) the
    train step's loss, the router ahead of the attention and ReGLU
    experts in it, is the reference's, and a gradient reaches the router
    through the gates."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss, init_params

    full = dict(tiny, rope_layout=[0] * 8, sliding_window_layout=[0] * 8)
    cfg = arch.program_config(full)
    params = init_params(cfg, jax.random.PRNGKey(4))
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, 256, (2, 65)), jnp.int32)
    loss = lambda p: causal_lm_loss(p, tokens, cfg, aux_weight=0.0)
    got, grads = jax.value_and_grad(loss)(params)
    want = arch.reference(full).loss({**params, "layers": (params["layers"],)},
                                     tokens, full)
    assert abs(float(got) - float(want)) < TOL
    assert float(jnp.abs(grads["layers"]["router"]).max()) > 0


def test_training_this_architecture_raises_by_name(tiny):
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss

    cfg, params = _tiny_model(tiny)
    with pytest.raises(NotImplementedError, match="not uniform"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)


def test_the_engine_serves_it_within_tolerance_of_the_reference(tiny):
    """Through ``LLMEngine``, four streams at once at different lengths,
    60 tokens each, both pools: every served token's logit lies within
    1e-4 of the reference's best at its position."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = _tiny_model(tiny)
    engine = LLMEngine(cfg, params, **tiny["engine"])
    try:
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, 256, n)) for n in (10, 25, 40, 100)]
        reqs = [engine.submit(p, 60) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert set(stats["pages"]) == {"full", "window"}
    assert stats["moe"]["assignments"] > 0
    # Off a TPU no prefill streams: the counter is there and stays 0.
    assert stats["prefill_streamed_bucket_tokens"] == 0
    assert stats["prefill_bucket_tokens"] == 16 + 32 + 64 + 128
    seqs = np.zeros((4, 256 + 1), np.int32)
    for row, prompt, out in zip(seqs, prompts, outs):
        row[:len(prompt) + 60] = prompt + out
    margins = np.asarray(jax.jit(
        lambda params, seqs: arch.reference(tiny).logit_margins(
            params, seqs, tiny))(params, jnp.asarray(seqs)))
    for row, prompt in zip(margins, prompts):
        assert row[len(prompt) - 1:len(prompt) + 59].max() <= TOL


def test_which_prefill_buckets_stream(config, monkeypatch):
    """On a TPU the cell's buckets of 4,096 and 8,192 keep a head's K and
    V in VMEM and 16,384 streams them; off one every bucket is the
    einsum's and none counts."""
    import importlib

    from ray_tpu.models.llama import prefill_attention_path

    cfg = arch.program_config(config)
    assert prefill_attention_path(cfg, 16384) == "einsum"
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    assert [prefill_attention_path(cfg, b) for b in (64, 4096, 8192, 16384)] \
        == ["einsum", "resident", "resident", "streamed"]


# ---- the readers on hand-made records --------------------------------------

def _record(config, engine=None, before=None, trace=None):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return {"config": config, "trace": trace,
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


STREAMED_16K = "pallas_f32_28_1_16384_bf16_28_16384_128"
RESIDENT_8K = "pallas_bf16_28_8192_128_f32_28_1_8192"


def _traced(config):
    """Three prefills in the window, of 5000, 9000 and 12000 tokens in
    buckets of 8192, 16384 and 16384; the trace saw one of 8192 and one
    of 16384, eight layers each, all times made up."""
    after = {"decode_steps": 3, "prefills": 3,
             "prefill_bucket_tokens": 1000 + 8192 + 2 * 16384,
             "prefill_streamed_bucket_tokens": 2 * 16384,
             "requests": [[101.0, 101.0, 101.2, None, 5000, 8192],
                          [102.0, 102.0, 102.2, None, 9000, 16384],
                          [103.0, 103.0, 103.2, None, 12000, 16384]]}
    before = {"decode_steps": 0, "prefills": 0,
              "prefill_bucket_tokens": 1000,
              "prefill_streamed_bucket_tokens": 0, "requests": []}
    trace = {"busy_s": 0.5, "window_s": 1.0,
             "modules": {"prefill": [0.2, 0.4]},
             "ops": [[STREAMED_16K, 8, 0.3], [RESIDENT_8K, 8, 0.05],
                     ["fusion_bf16_32_2048", 9, 0.004]]}
    return _record(config, after, before, trace)


def test_the_new_readers_on_a_hand_made_record(config):
    from benchmark import flops

    record = _traced(config)
    assert readers.prefill_streamed_share(record) == pytest.approx(
        100 * 2 * 16384 / (8192 + 2 * 16384))
    peak = flops.peaks("TPU v5 lite")
    least = flops.roofline_s(
        smallthinker_counts.flash_streamed_flops(config, 10500),
        smallthinker_counts.flash_streamed_bytes(config, 10500), peak)
    assert readers.prefill_stream_roofline(record) == pytest.approx(
        100 * least / 0.3)
    assert 0 < readers.prefill_stream_roofline(record) < 100
    # The resident form's accepted reader sees the 8192 call alone.
    resident = flops.roofline_s(
        smallthinker_counts.flash_prefill_flops(config, 5000),
        smallthinker_counts.flash_prefill_bytes(config, 5000), peak)
    assert window_readers.prefill_flash_roofline(record) == pytest.approx(
        100 * resident / 0.05)


@pytest.mark.parametrize("dtype", ["bf16", "f32"])
def test_neither_form_matches_the_others_reader(dtype):
    resident = f"pallas_{dtype}_28_16384_128_f32_28_1_16384"
    streamed = f"pallas_f32_28_1_16384_{dtype}_28_16384_128"
    assert window_readers.FLASH.match(resident)
    assert readers.STREAMED.match(streamed)
    assert not window_readers.FLASH.match(streamed)
    assert not readers.STREAMED.match(resident)
    assert readers.STREAMED.match(streamed).group(2) == "16384"
    for other in ("pallas_bf16_256_768", "pallas_bf16_98304_2560",
                  "pallas_bf16_16_28_128_bf16_2_4_16384_16_128_"
                  "bf16_2_4_16384_16_128"):
        assert not readers.STREAMED.match(other)


@pytest.mark.parametrize("name", ["prefill_stream_roofline",
                                  "prefill_streamed_share"])
def test_a_reader_finds_nothing_and_says_none(config, name):
    """The parent's engine has no such counter, an untraced run no
    trace, a trace of the resident form no such operation, and another
    configuration's counts no such function: None each time, no raise."""
    reader = getattr(readers, name)
    trinity = _load("benchmark", "configs", "trinity-mini-L6.json")
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {},
            "ops": [[RESIDENT_8K, 8, 0.05]]}
    old = {"decode_steps": 3, "prefills": 3, "prefill_bucket_tokens": 9,
           "requests": traced["worker"]["engine"]["requests"]}
    records = [_record(config), _record(config, old, old),
               _record(config, old, old, bare)]
    if name == "prefill_stream_roofline":
        records += [
            _record(config, traced["worker"]["engine"],
                    traced["worker"]["engine_before"], bare),
            _record(trinity, traced["worker"]["engine"],
                    traced["worker"]["engine_before"], traced["trace"])]
    for record in records:
        assert reader(record) is None
    metric, = [m for m in bench_run.load_benchmark()["per_layer"]
               if m["name"] == name + ".chat"]
    assert bench_run.read_metrics([metric], records[1]) == {}
