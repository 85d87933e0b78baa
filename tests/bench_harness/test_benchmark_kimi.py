"""Kimi-Linear's configuration as one of 16 chips' share, at its whole
depth (``benchmark/kimi_*.py``, ``benchmark/readers/hybrid.py``): the
file against the catalog's row and its two cuts, the cell against ISSUE
62's letter, the counts at the published widths, the float32 reference
(the delta rule a token at a time) against the program at a tiny size
(``kimi_tiny/config.json``: hidden 64; K K K M K K M, the first FFN
dense; KDA 4 heads of 16 behind a convolution of 4; MLA q.k 24 = 16 + 8
shared and unrotated beside v 16 from a latent of 32; 2 of 32 experts
held top-4 with a selection bias and a shared one; page 16), a slot
taken again, single departures from the published layer each refused a
hundred times over, the bfloat16-state control, the sixteen shares
adding up, the new readers on hand-made records, and the kernels' names
against every other reader's pattern. CPU, no processes."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, kimi_counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import hybrid as readers  # noqa: E402

TOL = 1e-4
CELL = "serve-kimilinear-c16-8k"
CONFIG = "kimi-linear-48b-a3b-ep16"


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "kimi_tiny", "config.json")


KDA = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25,
       26]
MLA = [4, 8, 12, 16, 20, 24, 27]
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": MLA, "head_dim": 128, "kda_layers": KDA,
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}


def test_file_holds_the_catalogs_row_and_cuts_only_the_two(config):
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    cuts = {k: (b["published"], b["here"], config[k])
            for k, b in config["reduced"].items()}
    assert cuts == {"num_experts": (256, 16, 16),
                    "vocab_size": (163840, 20480, 20480)}
    # The whole depth: no layer is cut.
    assert config["num_hidden_layers"] == 27
    assert config["deployment"]["chips_sharing_a_layer"] == 16
    assert config["deployment"]["experts_key"] == "num_experts"
    assert "no further stage" in config["deployment"]["how"]
    kinds = kimi_counts.layer_kinds(config)
    assert "".join(k[0] for k in kinds) == "dddl" * 6 + "ddl"
    assert set(config["assumed"]) >= {
        "kda_sizes", "kda_gate", "kda_gate_init", "kda_gate_init_why",
        "kda_qk_norm", "kda_state", "latent_norms",
        "e_score_correction_bias", "router_dtype", "grouped_topk"}
    assert set(config["not_read"]) >= {
        "head_dim", "rope_theta", "qk_rope_head_dim", "num_key_value_heads",
        "model_max_length"}
    assert config["engine"] == {"max_batch": 16, "max_len": 16384,
                                "page_size": 16, "total_pages": 16384}
    assert "1/16 of the deployment's" in config["stands_for"]
    # The benchmark's own rule takes the file as it is.
    bench = bench_run.load_benchmark()
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_experts", "vocab_size"]
    arch.check_reduced(entry, config)
    assert len(entry["why"]) <= 200
    # Every number of the catalog's row, where the catalog is at hand.
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
        assert entry["source"] == config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config[k] != v}
        assert differs == {"num_experts", "vocab_size"}


def test_the_cell_is_the_issues_letter_for_letter():
    bench = bench_run.load_benchmark()
    cell, _, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-closed-c16-8k", 1)
    assert len(cell["why"]) <= 200
    assert bench["workloads"][-1] == cell and bench["configs"][-1][
        "name"] == CONFIG
    assert (traffic["kind"], traffic["concurrency"], traffic["clients"],
            traffic["requests"]) == ("serve", 16, 16, 192)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.4, "min": 4096, "max": 15360}
    assert traffic["output"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.25, "min": 512, "max": 1024}
    assert (traffic["check_requests"], traffic["trace_at_s"],
            traffic["trace_seconds"]) == (4, 20, 4)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(mine)
    assert {n: (m["unit"], m["better"], m["source"], m["layer"], m["moves"])
            for n, m in mine.items()} == {
        "delta_step_time_share.chat":
            ("%", "lower", "device_trace", "decode program", "gap_p90_s"),
        "delta_step_roofline.chat":
            ("%", "higher", "device_trace", "decode program", "gap_p90_s"),
        "hybrid_latent_walk_roofline.chat":
            ("%", "higher", "device_trace", "decode program", "gap_p90_s"),
        "decode_step_roofline_hybrid.chat":
            ("%", "higher", "device_trace", "decode program", "gap_p90_s"),
        "delta_slot_bytes.chat":
            ("bytes", "lower", "program_counter", "kv cache manager",
             "gap_p90_s")}
    # The lists that gained the cell, and those that must not have.
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "gap_p90_s", "programs_loaded_s.serve", "device_idle_share.chat",
        "decode_step_device_s_p50.chat", "engine_prefill_s_p50.chat",
        "decode_batch_mean.chat", "engine_host_s_per_step.chat",
        "tokens_emitted_per_s.chat", "last_token_lag_s_p50.chat",
        "emit_gap_s_p90.chat", "stream_seal_s_p90.chat",
        "decode_starved_share.chat", "experts_reached_mean.chat",
        "expert_load_max_over_mean.chat", "routed_matmul_time_share.chat",
        "routed_matmul_roofline.chat"}
    # Readers that count a prefill as calls / num_hidden_layers are wrong
    # by 27/7 or 27/20 here; lists that a test of another configuration
    # pins to its cell stay as they are; and NO metric that reads a
    # prefill out of the trace lists the cell: its traced 4 s, 20 s into
    # the window, fall between the first sixteen prefills and the first
    # answer's end, and hold none (PERF.md section 7: the two such
    # metrics ISSUE 62 named come with the PR whose trace holds a
    # prefill; what is judged by a prefill here is engine_prefill_s_p50).
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "metrics", "prefill_delta_roofline.chat.json"))
    assert not listed & {
        "prefill_device_s_p50.chat",
        "prefill_flash_roofline.chat", "prefill_retention_roofline.chat",
        "prefill_stream_roofline.chat", "prefill_streamed_share.chat",
        "latent_walk_roofline.chat", "latent_walk_time_share.chat",
        "latent_row_bytes.chat", "state_walk_roofline.chat",
        "state_walk_time_share.chat", "state_slot_bytes.chat",
        "decode_step_roofline_state.chat", "decode_step_roofline_rows.chat",
        "routed_here_share.chat", "grouped_small_rows_share.chat"}
    # The warm-up loads every bucket whatever the seed, nothing is longer
    # than max_len, and every token is in the slice of the vocabulary.
    from benchmark import loadgen
    from benchmark.jobs import serve

    requests = loadgen.schedule(traffic, 2 ** 31 + 5, 51.0, 20480)
    assert sorted({serve.bucket(len(r["prompt"]), 16, 16384)
                   for r in requests}) == [4096, 8192, 16384]
    assert max(len(r["prompt"]) + r["max_new_tokens"]
               for r in requests) <= 16384
    assert max(max(r["prompt"]) for r in requests) < 20480


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.expert_size,
            cfg.num_layers, cfg.num_heads, cfg.vocab_size) == (
                2304, 9216, 1024, 27, 32, 20480)
    assert (cfg.delta_heads, cfg.delta_head_dim, cfg.delta_conv,
            cfg.delta_row) == (32, 128, 4, 3 * 4096)
    assert (cfg.q_lora_rank, cfg.latent_rope, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.dh, cfg.latent_row) == (0, False, 512, 128, 64, 128, 192, 640)
    assert cfg.dh == kimi_counts.head_dim(config) != config["head_dim"]
    assert cfg.layer_types == kimi_counts.layer_kinds(config)
    # The router at its published width, sixteen of them held.
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_here, cfg.top_k,
            cfg.n_shared_experts, cfg.num_dense_layers) == (
                256, (0, 16), 16, 8, 1, 1)
    assert (cfg.router_score, cfg.router_bias, cfg.route_norm,
            cfg.route_scale, cfg.rms_eps) == (
                "sigmoid", True, True, 2.446, 1e-5)
    assert str(cfg.dtype) == "bfloat16"
    with pytest.raises(NotImplementedError, match="grouped top-k"):
        arch.program_config({**config, "num_expert_group": 8,
                             "topk_group": 4})
    with pytest.raises(NotImplementedError, match="q bottleneck"):
        arch.program_config({**config, "q_lora_rank": 1536})
    with pytest.raises(NotImplementedError, match="q bottleneck"):
        arch.program_config({**config, "mla_use_nope": False})
    with pytest.raises(ValueError, match="each layer once"):
        arch.program_config({**config, "linear_attn_config": {
            **config["linear_attn_config"], "kda_layers": KDA[:-1]}})


def test_the_stack_is_fifteen_runs_over_three_pools(config):
    import jax
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import PagedKVCache
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = arch.program_config(config)
    runs = layer_runs(cfg)
    assert [(r.n, r.moe, r.kind) for r in runs] == (
        [(1, False, "delta"), (2, True, "delta"), (1, True, "latent")]
        + [(3, True, "delta"), (1, True, "latent")] * 5
        + [(2, True, "delta"), (1, True, "latent")])
    assert [r.kv_offset for r in runs if r.kind == "latent"] == list(range(7))
    assert [r.kv_offset for r in runs if r.kind == "delta"] == [
        0, 1, 3, 6, 9, 12, 15, 18]
    assert kv_layers(cfg) == {"delta": 20, "latent": 7}
    engine = config["engine"]
    assert PagedKVCache.sizes(cfg, 16, engine["total_pages"], 16, 1024) == {
        "delta": (20, 0, 0), "latent": (7, 16384, 1024)}
    # The delta pools as the engine would allocate them: the states
    # float32 (assumed.kda_state: the cell's comparison cannot refuse a
    # bfloat16 state, benchmark/kimi_reference.py says why, so this pin
    # and delta_slot_bytes.chat are what hold it), the histories in the
    # model's dtype, and a slot's bytes what the counts say.
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        cfg, 16, engine["total_pages"], 16, 1024))
    state, history = cache.k["delta"], cache.v["delta"]
    assert (state.shape, state.dtype) == ((20, 16, 32, 128, 128), np.float32)
    assert (history.shape, str(history.dtype)) == (
        (20, 3, 16, 12288), "bfloat16")
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in (state, history)) == 20 * 16 * 2170880
    assert kimi_counts.delta_slot_bytes(config) == 2170880
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    dense, kda, mla = shapes["layers"][:3]
    assert {n: kda[n].shape[1:] for n in (
        "wq", "wk", "wv", "wo", "conv_w", "wf_a", "wf_b", "a_log",
        "dt_bias", "wb", "wg_a", "wg_b", "o_norm")} == {
        "wq": (2304, 32, 128), "wk": (2304, 32, 128), "wv": (2304, 32, 128),
        "wo": (32, 128, 2304), "conv_w": (4, 12288), "wf_a": (2304, 128),
        "wf_b": (128, 32, 128), "a_log": (32,), "dt_bias": (32, 128),
        "wb": (2304, 32), "wg_a": (2304, 128), "wg_b": (128, 32, 128),
        "o_norm": (128,)}
    assert {n: mla[n].shape[1:] for n in (
        "wq", "wkv_a", "kv_a_norm", "wk_b", "wv_b", "wo")} == {
        "wq": (2304, 32, 192), "wkv_a": (2304, 576), "kv_a_norm": (512,),
        "wk_b": (512, 32, 128), "wv_b": (512, 32, 128),
        "wo": (32, 128, 2304)}
    assert not {"wq_a", "q_a_norm", "wq_b"} & set(mla)
    assert dense["w_gate"].shape == (1, 2304, 9216)
    assert kda["w_gate"].shape == (2, 16, 2304, 1024)
    assert kda["router"].shape == (2, 2304, 256)
    assert kda["expert_bias"].shape == (2, 256)
    assert shapes["embed"].shape == (20480, 2304)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == kimi_counts.param_counts(config)["total"]


def test_counts_at_the_published_widths(config):
    sizes = kimi_counts.param_counts(config)
    kda = (3 * 2304 * 4096, 4096 * 2304, 2304 * 128 + 128 * 4096,
           2304 * 128 + 128 * 4096, 2304 * 32)
    assert [round(p / 1e6, 2) for p in kda] == [28.31, 9.44, 0.82, 0.82, 0.07]
    assert sizes["delta_attn"] == sum(kda) == 39_460_864
    mla = (2304 * 32 * 192, 2304 * 576, 512 * 32 * 256, 4096 * 2304)
    assert [round(p / 1e6, 2) for p in mla] == [14.16, 1.33, 4.19, 9.44]
    assert sizes["latent_attn"] == sum(mla) == 29_114_368
    assert sizes["expert"] == 3 * 2304 * 1024 == 7_077_888
    assert sizes["router"] == 2304 * 256
    assert round(26 * 16 * sizes["expert"] / 1e6) == 2944
    assert round((sizes["embed"] + sizes["lm_head"]) / 1e6, 1) == 94.4
    assert round(sizes["total"] / 1e6) == 4296
    # bfloat16 but the routers and selection biases, which are float32.
    assert round((sizes["total"] * 2 + 26 * (2304 + 1) * 256 * 2) / 1e9,
                 2) == 8.62
    # What a sequence holds: a state and a convolution history a KDA
    # layer, a row a token an MLA layer.
    assert kimi_counts.delta_slot_bytes(config) == (
        32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2) == 2_170_880
    assert kimi_counts.latent_row_bytes(config) == 1152
    assert kimi_counts.kv_bytes_per_token(config) == 7 * 1152
    assert round(20 * 16 * 2_170_880 / 1e9, 2) == 0.69
    assert round(16 * 16384 * 7 * 1280 / 1e9, 2) == 2.35
    # The delta rule: 7 d^2 a head a token; a state moved twice.
    assert kimi_counts.delta_step_flops(config, 1) == 32 * 7 * 128 * 128
    assert kimi_counts.delta_step_bytes(config, 1) == 2 * 32 * 128 * 128 * 4
    assert round(kimi_counts.delta_step_bytes(config, 320) / 1e9, 2) == 1.34
    assert kimi_counts.latent_walk_flops(config, 1) == 2 * 32 * (576 + 512)
    assert kimi_counts.latent_walk_bytes(config, 1) == 1152
    assert kimi_counts.delta_prefill_flops(config, 8192) == pytest.approx(
        20 * 8192 * 32 * (6 * 128 * 128 + 10 * 128 * 64.5))
    assert kimi_counts.flash_prefill_flops(config, 8192) == (
        2 * 7 * 32 * (192 + 128) * (8192 * 8193 // 2))
    # A decode step of 16 contexts of ~9k: ~6.4 of the 16 held experts a
    # layer when the router is even; the others are not read.
    even = kimi_counts.experts_reached_even(config, 16)
    assert 6 < even < 7
    step = kimi_counts.decode_step_bytes_hybrid(
        config, 16, 7 * 16 * 9000, 20 * 16, 26 * even)
    assert kimi_counts.decode_step_bytes(config, 16, 16 * 9000) == \
        pytest.approx(step)
    assert 7.2e9 < step < 7.8e9
    states = kimi_counts.delta_step_bytes(config, 320)
    rows = kimi_counts.latent_walk_bytes(config, 7 * 16 * 9000)
    assert 0.17 < states / step < 0.19 and 0.15 < rows / step < 0.17
    assert "jax" not in {m.split(".")[0] for m in vars(kimi_counts)
                         if hasattr(vars(kimi_counts)[m], "__file__")}


# ---- the program against the reference, float32, tiny ----------------------

def _tiny_model(tiny, seed=3, **changes):
    import jax
    from ray_tpu.models import init_params

    cfg = dataclasses.replace(arch.program_config(tiny), **changes)
    return cfg, init_params(cfg, jax.random.PRNGKey(seed))


def _programs(cfg):
    import jax
    from ray_tpu.models.generation import paged_decode, paged_prefill

    prefill = jax.jit(lambda params, tokens, n, cache, slot, pages:
                      paged_prefill(params, tokens, n, cache, cfg, slot, pages))
    decode = jax.jit(lambda params, last, cache, active:
                     paged_decode(params, last, cache, cfg, active=active))
    return prefill, decode


def _prefilled(prefill, params, cache, table, slot, seq, n, page=16,
               padding=7):
    """``seq[:n]`` through ``paged_prefill`` into ``slot``, in the bucket
    the engine would choose, the bucket's padding tokens NOT zero."""
    import jax.numpy as jnp

    per_seq = table.shape[1]
    bucket = page
    while bucket < n:
        bucket *= 2
    # The slot's pages, from the pool's end and out of order.
    ids = (table.shape[0] * per_seq - 1 - slot * per_seq
           - np.arange(per_seq))[::-1]
    table[slot] = ids
    cache = cache._replace(page_table={
        "latent": jnp.asarray(table), "delta": cache.page_table["delta"]})
    padded = np.full((1, bucket), padding, np.int32)
    padded[0, :n] = seq[:n]
    logits, cache, _ = prefill(
        params, jnp.asarray(padded), jnp.asarray(n, jnp.int32), cache, slot,
        {"latent": jnp.asarray(ids[:bucket // page]),
         "delta": jnp.zeros((0,), jnp.int32)})
    return np.asarray(logits)[0], cache


def _program_logits(cfg, params, seqs, prompt_lens, steps, page=16,
                    first=None):
    """Each sequence's prompt through ``paged_prefill`` into a slot of
    its own, then ``steps`` teacher-forced ``paged_decode`` steps with
    every slot live but the last, slots at different lengths: {slot:
    logits [1 + steps, V]}. ``first``: a sequence prefilled and decoded
    in slot 0 BEFORE, whose slot is then taken again."""
    import jax.numpy as jnp
    from ray_tpu.models.generation import PagedKVCache

    prefill, decode = _programs(cfg)
    slots = len(seqs) + 1                       # the last one stays idle
    per_seq = 256 // page
    cache = PagedKVCache.create(cfg, slots, slots * per_seq, page, per_seq)
    table = np.zeros((slots, per_seq), np.int32)
    if first is not None:
        _, cache = _prefilled(prefill, params, cache, table, 0, first,
                              len(first) - 4)
        for tok in first[-4:]:
            last = np.zeros(slots, np.int32)
            last[0] = tok
            _, cache, _ = decode(params, jnp.asarray(last), cache,
                                 jnp.asarray(np.arange(slots) == 0))
    out = {}
    for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        logits, cache = _prefilled(prefill, params, cache, table, slot, seq, n)
        out[slot] = [logits]
    active = jnp.asarray(np.arange(slots) < len(seqs))
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
            last[slot] = seq[n + i]
        logits, cache, _ = decode(params, jnp.asarray(last), cache, active)
        for slot in out:
            out[slot].append(np.asarray(logits)[slot])
    return {slot: np.stack(rows) for slot, rows in out.items()}


def _worst_difference(tiny, cfg, params, prompt_lens, steps, ref_params=None,
                      first=None):
    import jax
    import jax.numpy as jnp

    reference = arch.reference(tiny)
    rng = np.random.RandomState(sum(prompt_lens))
    seqs = [rng.randint(0, 256, n + steps) for n in prompt_lens]
    got = _program_logits(cfg, params, seqs, prompt_lens, steps, first=first)
    padded = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for row, seq in zip(padded, seqs):
        row[:len(seq)] = seq
    want = np.asarray(jax.jit(
        lambda params, tokens: reference.logits(params, tokens, tiny))(
            ref_params or params, jnp.asarray(padded)))
    return max(np.abs(got[slot] - want[slot, n - 1:n + steps]).max()
               for slot, n in enumerate(prompt_lens))


def test_prefill_then_decode_equals_the_reference(tiny):
    """Prompts of 2, 10, 25, 100 and 150 in one batch (the first shorter
    than the convolution, the last over a chunk of the delta prefill),
    each padded to its bucket with tokens that are not zero,
    then 30 decode steps with the slots at unequal lengths and one slot
    idle: both pools are laid from one prompt and stepped together.
    Every logit lies within 1e-4 of the reference's full forward, which
    runs the delta rule a token at a time from nothing and keeps no
    cache."""
    cfg, params = _tiny_model(tiny)
    assert _worst_difference(tiny, cfg, params, (2, 10, 25, 100, 150),
                             30) < TOL


def test_a_slot_taken_again_keeps_nothing_of_the_request_before(tiny):
    """Slot 0 first serves a sequence of 150 tokens (prefill and four
    decode steps), then is taken by a prompt of 9: the state and the
    convolution's history are overwritten whole, and a prompt shorter
    than the history's three rows leaves zeros in the rest of it, not
    the old request's rows."""
    cfg, params = _tiny_model(tiny)
    first = np.random.RandomState(5).randint(0, 256, 150)
    assert _worst_difference(tiny, cfg, params, (9, 2, 40), 12,
                             first=first) < TOL


def _no_decay(params):
    def flat(stack):
        if "a_log" not in stack:
            return stack
        return {**stack, "a_log": stack["a_log"] - 30.0}

    return {**params, "layers": tuple(map(flat, params["layers"]))}


def _shared_expert_twice(params):
    def twice(stack):
        if "ws_down" not in stack:
            return stack
        return {**stack, "ws_down": stack["ws_down"] * 2.0}

    return {**params, "layers": tuple(map(twice, params["layers"]))}


def _taps_reversed(params):
    def turned(stack):
        if "conv_w" not in stack:
            return stack
        return {**stack, "conv_w": stack["conv_w"][:, ::-1]}

    return {**params, "layers": tuple(map(turned, params["layers"]))}


def _history_at_the_buckets_end(monkeypatch):
    """The convolution's history taken at the bucket's end, not at the
    last real token."""
    import jax
    from ray_tpu.models import generation

    real = jax.lax.dynamic_slice_in_dim

    def at_the_end(rows, start, size, axis=0):
        if size == 3 and rows.ndim == 2:
            start = rows.shape[0] - size
        return real(rows, start, size, axis)

    monkeypatch.setattr(generation.jax.lax, "dynamic_slice_in_dim",
                        at_the_end)


def _padding_reaches_the_state(monkeypatch):
    from ray_tpu.models import generation

    prefill = generation.delta_prefill

    def unmasked(q, k, v, log_a, beta):
        return prefill(q, k, v, log_a - 0.05, beta + 0.5)

    monkeypatch.setattr(generation, "delta_prefill", unmasked)


# name -> (changes to the program's config, to its weights, a patch)
DEPARTURES = {
    "latent-layers-rotated": ({"latent_rope": True}, None, None),
    "no-decay": ({}, _no_decay, None),
    "taps-in-the-other-order": ({}, _taps_reversed, None),
    "shared-expert-counted-twice": ({}, _shared_expert_twice, None),
    "another-chips-experts-gates": ({"experts_held": (2, 2)}, None, None),
    "history-taken-at-the-buckets-end":
        ({}, None, _history_at_the_buckets_end),
    "padding-reaches-the-state": ({}, None, _padding_reaches_the_state),
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_a_single_departure_fails_a_hundred_times_over(tiny, departure,
                                                       monkeypatch):
    """Each way the program could leave the published layer or this
    chip's share of it moves a logit by at least 100 x the tolerance it
    is held to."""
    changes, reweigh, patch = DEPARTURES[departure]
    if patch:
        patch(monkeypatch)
    cfg, ref_params = _tiny_model(tiny)
    cfg = dataclasses.replace(cfg, **changes)
    params = reweigh(ref_params) if reweigh else ref_params
    assert _worst_difference(tiny, cfg, params, (40, 100), 6,
                             ref_params=ref_params) > 100 * TOL


def test_the_bfloat16_state_control_trails_where_float32_does_not(tiny):
    """``control_margins`` at the tiny size IN FLOAT32, 2 x 192
    positions: a model whose delta states are kept in bfloat16 between
    tokens puts a token first that trails the reference's best by over
    ten times the float32 limit; with a float32 state it is the
    reference again and trails by nothing. This says the control does
    what it says, NOT that the cell's bfloat16 limit refuses such a
    state: on the chip at the published widths it does not
    (benchmark/kimi_reference.py)."""
    import jax
    import jax.numpy as jnp

    reference = arch.reference(tiny)
    _, params = _tiny_model(tiny)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 192)))
    got = {name: np.asarray(jax.jit(
        lambda p, t, d=dtype: reference.control_margins(p, t, tiny, d))(
            params, tokens))
        for name, dtype in (("bfloat16", jnp.bfloat16),
                            ("float32", jnp.float32))}
    assert got["float32"].shape == (2, 192) and not got["float32"].any()
    assert got["bfloat16"].max() > 10 * TOL
    assert (got["bfloat16"] > 0).mean() > 0.01


def test_the_sixteen_shares_add_up_to_the_uncut_layer(tiny):
    """The tiny fixture's expert layer on each of the 16 chips that
    share it (2 of 32 experts each, the router whole): the routed parts
    and the shared expert ONCE add up to what one chip holding every
    expert gives, and what the chips count as gone elsewhere is
    everything they did not take themselves."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import llama

    cfg, _ = _tiny_model(tiny)
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    stack = llama._init_stack(
        whole_cfg, iter(jax.random.split(jax.random.PRNGKey(2), 32)), 1,
        True, "delta")
    lp = {n: w[0] for n, w in stack.items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 11, 64))
    whole, _, load = llama.ffn(whole_cfg, lp, x)
    shared = llama.swiglu(llama.rms_norm(x, lp["mlp_norm"], cfg.rms_eps),
                          lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, elsewhere = x + shared, 0
    for chip in range(16):
        here = slice(2 * chip, 2 * chip + 2)
        part_cfg = dataclasses.replace(cfg, experts_held=(2 * chip, 2))
        part_lp = {**lp, **{n: lp[n][here] for n in llama.EXPERT_WEIGHTS}}
        part, _, part_load = llama.ffn(part_cfg, part_lp, x)
        assert list(np.asarray(part_load[:2])) == list(np.asarray(load[here]))
        elsewhere += int(part_load[2])
        total = total + (part - x - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=3e-6, rtol=3e-6)
    assert int(load.sum()) == 11 * 4
    assert elsewhere == 15 * 11 * 4


def test_training_this_architecture_raises_by_name(tiny):
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss

    cfg, params = _tiny_model(tiny)
    with pytest.raises(NotImplementedError, match="served only"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)


def test_what_may_stand_beside_what(tiny):
    from ray_tpu.models.llama import layer_runs

    cfg = arch.program_config(tiny)
    assert len(layer_runs(cfg)) == 5
    # Delta layers alone, latent layers alone: fine.
    assert len(layer_runs(dataclasses.replace(
        cfg, layer_types=("delta",) * 7))) == 2
    assert len(layer_runs(dataclasses.replace(cfg, layer_types=None))) == 2
    for types in (("delta", "full") + ("latent",) * 5,
                  ("state",) + ("latent",) * 6, ("delta",) * 6):
        with pytest.raises(ValueError, match="layer_types"):
            layer_runs(dataclasses.replace(cfg, layer_types=types))
    with pytest.raises(ValueError, match="delta_conv"):
        layer_runs(dataclasses.replace(cfg, delta_conv=1))
    # Without latent attention a delta layer has nothing to stand among.
    with pytest.raises(ValueError, match="'full' or 'window'"):
        layer_runs(dataclasses.replace(cfg, kv_lora_rank=0, head_dim=16))


# ---- the readers on hand-made records --------------------------------------

DELTA_STEP = "pallas_f32_16_1_32_128_f32_20_16_32_128_128"
DELTA_SCAN_8K = "pallas_bf16_32_8192_128_f32_32_128_128"
DELTA_SCAN_16K = "pallas_bf16_32_16384_128_f32_32_128_128"
LATENT_WALK = "pallas_bf16_16_32_512_bf16_7_16384_16_640"
ROUTED = ["pallas_bf16_128_1024", "pallas_bf16_128_2304"]
# Other configurations' kernels, as their own tests name them.
OTHERS = {
    "retention step": "pallas_f32_16_8_5_128_f32_6_16_8_65_136_128",
    "retention scan": "pallas_bf16_8_5_8192_128_f32_8_65_136_128",
    "page walk": "pallas_bf16_16_28_128_bf16_2_4_16384_16_128_bf16_2_4_16384",
    "index walk": "pallas_f32_8_1_64_256_bf16_2_8192_16_128",
    "sparse walk": "pallas_bf16_8_1_64_512_bf16_5_8192_16_640",
    "select tiles": "pallas_s8_128_32_128_512",
    "sparse flash": "pallas_bf16_64_16384_256",
    "grouped": "pallas_bf16_64_2048",
    "flash": "pallas_bf16_32_4096_128_f32_32_1_4096",
    "streamed flash": "pallas_f32_28_1_16384_bf16_28_16384_128",
    "latent walk": "pallas_bf16_32_32_512_bf16_5_8192_16_640",
}


def test_the_kernels_names_are_nobody_elses():
    """The new kernels' stable names match none of the existing readers'
    patterns, the existing kernels' names not the new reader's, and the
    new pattern matches its own kernel."""
    from benchmark.readers import (latent, moe, smallthinker, sparse, state,
                                   window)

    theirs = {"STATE_STEP": state.STATE_STEP, "CHUNK_SCAN": state.CHUNK_SCAN,
              "LATENT_WALK": latent.LATENT_WALK, "GROUPED": moe.GROUPED,
              "PAGE_WALK": window.PAGE_WALK, "FLASH": window.FLASH,
              "STREAMED": smallthinker.STREAMED,
              "FOUR_AND_FOUR": sparse.FOUR_AND_FOUR,
              "SELECT_TILES": sparse.SELECT_TILES,
              "SPARSE_FLASH": sparse.SPARSE_FLASH}
    mine = {"DELTA_STEP": readers.DELTA_STEP}
    for name in (DELTA_STEP, DELTA_SCAN_8K, DELTA_SCAN_16K,
                 DELTA_SCAN_8K.replace("bf16", "f32")):
        assert [n for n, p in theirs.items() if p.match(name)] == [], name
    for kernel, name in OTHERS.items():
        assert [n for n, p in mine.items() if p.match(name)] == [], kernel
    assert readers.DELTA_STEP.match(DELTA_STEP)
    for name in (DELTA_SCAN_8K, DELTA_SCAN_16K):
        assert not readers.DELTA_STEP.match(name)
    # The names are what the reducer makes of what the calls write.
    from benchmark import trace_reduce

    assert trace_reduce.stable_name(
        "%custom-call.7 = (f32[16,1,32,128]{3,2,1,0}, "
        "f32[20,16,32,128,128]{4,3,2,1,0}) custom-call(%a, %b), "
        'custom_call_target="tpu_custom_call"') == DELTA_STEP
    assert trace_reduce.stable_name(
        "%custom-call.9 = (bf16[32,8192,128]{2,1,0}, f32[32,128,128]{2,1,0}) "
        'custom-call(%a), custom_call_target="tpu_custom_call"'
    ) == DELTA_SCAN_8K


def _record(config, engine=None, before=None, trace=None):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return {"config": config, "trace": trace,
            "traffic": {"trace_at_s": 20.0, "trace_seconds": 4.0},
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


def _traced(config, run_down_steps=0):
    """A window of 10 decode steps of 16 sequences at a context of 9,000
    and then ``run_down_steps`` of 8 (what is left of a window while the
    trace is written and nobody is admitted), three of the 16-sequence
    steps traced, from 20.0 s to 20.6 s into the window; all times made
    up. Sixteen requests hold a slot through the trace, one of them
    taking over at 120.3 from one that ends there; one ended before it
    and one is admitted after it."""
    slot_steps = 10 * 16 + run_down_steps * 8
    tokens = slot_steps * 9000
    held = [[101.0 + i, 101.1 + i, 102.0 + i, None if i % 2 else 130.0,
             7000, 8192, i, None] for i in range(15)]
    after = {"decode_steps": 13 + run_down_steps,
             "decode_slot_steps": 5 + slot_steps,
             "decode_kv_tokens": 900 + tokens,
             "decode_kv_rows_read": 700 + 7 * tokens,
             "decode_state_slot_layers": 60 + slot_steps * 20,
             "state_slot_bytes": {"delta": 2170880},
             "kv_row_bytes": {"latent": 1280},
             "requests": held + [
                 [100.5, 100.6, 101.5, 120.3, 12000, 16384, 15, None],
                 [120.3, 120.3, 120.5, None, 12000, 16384, 16, None],
                 [100.2, 100.3, 100.9, 119.0, 7000, 8192, 17, None],
                 [120.7, 120.8, None, None, 7000, 8192, 18, None],
                 [121.0, None, None, None, 7000, 8192, 19, None]],
             "moe": {"experts_reached": 400 + slot_steps * 26 * 6 // 16}}
    before = {"decode_steps": 3, "decode_slot_steps": 5,
              "decode_kv_tokens": 900, "decode_kv_rows_read": 700,
              "decode_state_slot_layers": 60,
              "moe": {"experts_reached": 400}}
    trace = {"busy_s": 0.5, "window_s": 0.6,
             "modules": {"decode_step": [0.013, 0.012, 0.014]},
             "ops": [[DELTA_STEP, 60, 0.01], [LATENT_WALK, 21, 0.008]]
             + [[name, 3, 0.01] for name in OTHERS.values()
                if name not in (OTHERS["flash"], OTHERS["streamed flash"],
                                OTHERS["latent walk"])]}
    return _record(config, after, before, trace)


ROOFLINES = ("delta_step_roofline", "hybrid_latent_walk_roofline",
             "decode_step_roofline_hybrid")


def test_readers_on_a_hand_made_record(config):
    from benchmark import flops

    record = _traced(config)
    peak = flops.peaks("TPU v5 lite")
    assert readers.delta_slot_bytes(record) == 2170880
    assert readers.delta_step_time_share(record) == pytest.approx(2.0)
    # The states: 3 traced steps of 320 (slot, layer) pairs, each read
    # and written; 2.3 operations a byte, so the bytes bound it.
    moved = 3 * 320 * 2 * 32 * 128 * 128 * 4
    assert readers.delta_step_roofline(record) == pytest.approx(
        100 * moved / peak["hbm_bytes_per_s"] / 0.01)
    # The latent walk against JoyAI's counts, which count rows and no
    # layers: 7 x 16 x 9,000 rows a step.
    walk = bench_run.find_reader("hybrid_latent_walk_roofline.chat")
    assert walk is readers.hybrid_latent_walk_roofline
    rows = 3 * 7 * 16 * 9000
    assert walk(record) == pytest.approx(
        100 * rows * 1152 / peak["hbm_bytes_per_s"] / 0.008)
    least = flops.roofline_s(
        kimi_counts.decode_step_flops_hybrid(config, 16, 7 * 16 * 9000, 320),
        kimi_counts.decode_step_bytes_hybrid(config, 16, 7 * 16 * 9000, 320,
                                             156), peak)
    assert readers.decode_step_roofline_hybrid(record) == pytest.approx(
        100 * least / 0.013)
    for name in ROOFLINES:
        assert 0 < getattr(readers, name)(record) < 100
    # Through the harness, under the metrics' own names.
    bench = bench_run.load_benchmark()
    got = bench_run.read_metrics(
        [m for m in bench["per_layer"] if m.get("workloads") == [CELL]],
        record)
    assert {k: v["unit"] for k, v in got.items()} == {
        "delta_step_time_share.chat": "%", "delta_step_roofline.chat": "%",
        "hybrid_latent_walk_roofline.chat": "%",
        "decode_step_roofline_hybrid.chat": "%",
        "delta_slot_bytes.chat": "bytes"}


@pytest.mark.parametrize("name", ROOFLINES)
def test_a_window_that_runs_down_reads_what_the_traced_steps_carried(
        config, name):
    """While ``stop_trace`` holds the replica nobody is admitted and the
    window's mean batch falls (12 here, 11.9-12.4 on the chip); the
    traced steps carried 16, and a roofline reads the same as in a
    window that held 16 throughout."""
    from benchmark.readers import engine

    full, run_down = _traced(config), _traced(config, run_down_steps=10)
    assert engine.decode_batch_mean(full) == 16
    assert engine.decode_batch_mean(run_down) == 12
    assert readers._traced_sequences(run_down) == pytest.approx(16)
    assert getattr(readers, name)(run_down) == pytest.approx(
        getattr(readers, name)(full))
    # A trace over the opening: the two admitted first and six of the
    # fifteen that follow a second apart hold a slot by then.
    early = _traced(config)
    early["traffic"] = {"trace_at_s": 6.35}
    assert readers._traced_sequences(early) == pytest.approx(2 + 6)


@pytest.mark.parametrize("name", ("delta_step_time_share",) + ROOFLINES + (
    "delta_slot_bytes",))
def test_a_reader_finds_nothing_and_says_none(config, name):
    """The parent's engine has no such gauge, an untraced run no trace, a
    trace of another model no such kernel, and another configuration's
    counts no such function: None each time, no raise."""
    reader = getattr(readers, name)
    brumby = _load("benchmark", "configs", "brumby-14b-base-L6.json")
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {},
            "ops": [[n, 3, 0.5] for n in OTHERS.values()
                    if n not in (OTHERS["flash"], OTHERS["streamed flash"])]}
    old_engine = {"decode_steps": 3, "decode_kv_tokens": 9,
                  "decode_kv_rows_read": 45, "requests": [],
                  "kv_row_bytes": {"latent": 1280},
                  "state_slot_bytes": {"state": 36208640},
                  "moe": {"assignments": 5}}
    records = [_record(config), _record(config, old_engine, old_engine),
               _record(config, old_engine, old_engine, bare),
               _record(brumby, old_engine, old_engine, bare)]
    if name != "delta_step_time_share":     # a share asks no counts
        records.append(_record(brumby, old_engine, old_engine,
                               traced["trace"]))
    if name != "delta_slot_bytes":
        records.append(_record(config, traced["worker"]["engine"],
                               traced["worker"]["engine_before"], bare))
    if name in ROOFLINES:                   # no rows: nothing was carried
        engine = dict(traced["worker"]["engine"], requests=[])
        records.append(_record(config, engine,
                               traced["worker"]["engine_before"],
                               traced["trace"]))
    for record in records:
        assert reader(record) is None
