"""GLM-5.2's configuration as one of 16 chips' share
(``benchmark/glm52_*.py``, ``benchmark/readers/sparse.py``): the file
against the catalog's row and its three cuts, the cell against ISSUE
54's letter, the counts at the published widths, the float32 reference
against the program at a tiny size (``glm52_tiny/config.json``: hidden
64, 4 heads of q.k 24 = 16 + 8 rotated beside v 12, ranks 24 and 32, an
indexer of 4 heads of 16 that keeps 24 positions, 4 of 8 experts held
top-2 with a selection bias and a shared one; dense+indexing, two expert
layers that share its selection, an expert layer that indexes; page 16),
single departures from the published layer each refused a hundred times
over, the float8 control, the new readers on hand-made records. CPU, no
processes."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, glm52_counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import sparse as readers  # noqa: E402

TOL = 1e-4
CELL = "serve-glm52-c8-16k"


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "glm-5.2-L5-ep16.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "glm52_tiny", "config.json")


PERIOD = ["full", "shared", "shared", "shared"]
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32,
    "index_share_for_mtp_iteration": True, "index_skip_topk_offset": 3,
    "index_topk": 2048, "index_topk_freq": 4, "index_topk_pattern": None,
    "indexer_rope_interleave": True,
    "indexer_types": ["full", "full"] + PERIOD * 19,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75,
    "model_type": "glm_moe_dsa", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256}


def test_file_holds_the_catalogs_row_and_cuts_only_the_three(config):
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert len(config["indexer_types"]) == len(config["mlp_layer_types"]) == 78
    cuts = {k: (b["published"], b["here"], config[k])
            for k, b in config["reduced"].items()}
    assert cuts == {"num_hidden_layers": (78, 5, 5),
                    "n_routed_experts": (256, 16, 16),
                    "vocab_size": (154880, 19360, 19360)}
    assert config["deployment"]["chips_sharing_a_layer"] == 16
    assert config["deployment"]["experts_key"] == "n_routed_experts"
    # Published layers 2-6, read where the lists have them.
    assert config["layers_from"] == 2
    assert glm52_counts.layer_kinds(config) == (
        ("dense",) + ("sparse",) * 4,
        ("full", "shared", "shared", "shared", "full"))
    assert set(config["assumed"]) >= {
        "latent_norms", "e_score_correction_bias", "router_dtype",
        "indexer_key_norm", "indexer_rotary", "indexer_dtype",
        "selection_ties", "head_dim_is_the_unrotated_width"}
    assert "num_nextn_predict_layers" in config["not_served"]
    assert config["engine"] == {"max_batch": 8, "max_len": 16384,
                                "page_size": 16, "total_pages": 8192}
    assert "1/16 of the deployment's" in config["stands_for"]
    # The benchmark's own rule takes the file as it is.
    bench = bench_run.load_benchmark()
    entry, = [c for c in bench["configs"] if c["name"] == "glm-5.2-L5-ep16"]
    arch.check_reduced(entry, config)
    assert len(entry["why"]) <= 200


def test_the_cell_is_the_issues_letter_for_letter():
    bench = bench_run.load_benchmark()
    cell, _, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5.2-L5-ep16", "chat-closed-c8-16k", 1)
    assert len(cell["why"]) <= 200
    assert (traffic["kind"], traffic["concurrency"], traffic["clients"],
            traffic["requests"]) == ("serve", 8, 8, 96)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 10240,
                                 "sigma": 0.25, "min": 6144, "max": 14336}
    assert traffic["output"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.25, "min": 768, "max": 2048}
    assert (traffic["grace_s"], traffic["check_requests"],
            traffic["trace_at_s"], traffic["trace_seconds"]) == (5, 4, 20, 4)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert {n: (m["unit"], m["source"], m["layer"], m["moves"])
            for n, m in mine.items()} == {
        "index_score_time_share.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "index_score_roofline.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "sparse_walk_time_share.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "sparse_walk_roofline.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "prefill_sparse_roofline.chat":
            ("%", "device_trace", "prefill program", "gap_p90_s"),
        "selected_rows_share.chat":
            ("%", "program_counter", "kv cache manager", "gap_p90_s"),
        "index_row_bytes.chat":
            ("bytes", "program_counter", "kv cache manager", "gap_p90_s"),
        "routed_here_share.chat":
            ("%", "program_counter", "expert dispatch", "gap_p90_s")}
    # The lists that gained the cell, and those that must not have.
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "gap_p90_s", "programs_loaded_s.serve", "device_idle_share.chat",
        "decode_step_device_s_p50.chat", "prefill_device_s_p50.chat",
        "decode_batch_mean.chat", "engine_host_s_per_step.chat",
        "tokens_emitted_per_s.chat", "last_token_lag_s_p50.chat",
        "experts_reached_mean.chat", "expert_load_max_over_mean.chat",
        "routed_matmul_time_share.chat", "routed_matmul_roofline.chat",
        "decode_step_roofline_rows.chat"}
    # No decode step calls the latent walk without a selection; and two
    # lists ISSUE 54 names stay as they were, because tests under
    # tests/bench_harness that this PR may not edit pin them to their
    # cells (test_benchmark_joyai.py, test_benchmark_grouped.py):
    # PERF.md section 7.
    assert not listed & {"decode_step_roofline_counted.chat",
                         "latent_walk_roofline.chat",
                         "latent_walk_time_share.chat",
                         "latent_row_bytes.chat",
                         "grouped_small_rows_share.chat"}
    # Every context is over index_topk, the warm-up loads both buckets
    # whatever the seed, and nothing is longer than max_len.
    from benchmark import loadgen
    from benchmark.jobs import serve

    requests = loadgen.schedule(traffic, 2 ** 31 + 5, 51.0, 19360)
    assert sorted({serve.bucket(len(r["prompt"]), 16, 16384)
                   for r in requests}) == [8192, 16384]
    assert min(len(r["prompt"]) for r in requests) >= 6144 > 2048
    assert max(len(r["prompt"]) + r["max_new_tokens"]
               for r in requests) <= 16384
    assert max(max(r["prompt"]) for r in requests) < 19360


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.expert_size,
            cfg.num_layers, cfg.num_heads, cfg.vocab_size) == (
                6144, 12288, 2048, 5, 64, 19360)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.dh, cfg.latent_row) == (
                2048, 512, 192, 64, 256, 256, 640)
    assert cfg.dh == glm52_counts.head_dim(config) == config["qk_head_dim"]
    assert (cfg.rope_theta, cfg.rope_interleave, cfg.rms_eps) == (
        8e6, True, 1e-5)
    assert (cfg.index_topk, cfg.index_n_heads, cfg.index_head_dim,
            cfg.index_rope_interleave, cfg.indexer_types) == (
                2048, 32, 128, True,
                ("full", "shared", "shared", "shared", "full"))
    # The router at its published width, sixteen of them held.
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_here, cfg.top_k,
            cfg.n_shared_experts, cfg.num_dense_layers) == (
                256, (0, 16), 16, 8, 1, 1)
    assert (cfg.router_score, cfg.router_bias, cfg.route_norm,
            cfg.route_scale) == ("sigmoid", True, True, 2.5)
    assert str(cfg.dtype) == "bfloat16"
    with pytest.raises(NotImplementedError, match="grouped top-k"):
        arch.program_config({**config, "n_group": 8, "topk_group": 4})
    with pytest.raises(NotImplementedError, match="dense layers behind"):
        arch.program_config({**config, "layers_from": 1, "mlp_layer_types":
                             ["dense", "dense", "sparse", "dense"] * 20})


def test_the_stack_is_the_period_of_the_selection(config):
    import jax
    from ray_tpu.models import init_params
    from ray_tpu.models.llama import index_offsets, kv_layers, layer_runs

    cfg = arch.program_config(config)
    assert [tuple(r) for r in layer_runs(cfg)] == [
        (0, 1, False, "latent_index", 0), (1, 3, True, "latent_shared", 1),
        (4, 1, True, "latent_index", 4)]
    assert index_offsets(cfg) == (0, 1, 1)
    assert kv_layers(cfg) == {"latent": 5, "index": 2}
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    dense, shared, indexing = shapes["layers"]
    for stack in (dense, indexing):
        assert {n: stack[n].shape[1:] for n in (
            "wi_q", "wi_k", "wi_w", "i_k_norm", "i_k_bias")} == {
            "wi_q": (2048, 32, 128), "wi_k": (6144, 128),
            "wi_w": (6144, 32), "i_k_norm": (128,), "i_k_bias": (128,)}
    assert not [n for n in shared if n.startswith(("wi_", "i_k"))]
    assert dense["w_gate"].shape == (1, 6144, 12288)
    assert shared["w_gate"].shape == (3, 16, 6144, 2048)
    assert shared["router"].shape == (3, 6144, 256)
    assert shared["expert_bias"].shape == (3, 256)
    assert shapes["embed"].shape == (19360, 6144)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == glm52_counts.param_counts(config)["total"]


def test_counts_at_the_published_widths(config):
    sizes = glm52_counts.param_counts(config)
    parts = (6144 * 2048, 2048 * 64 * 256, 6144 * 576, 512 * 64 * 448,
             64 * 256 * 6144)
    assert [round(p / 1e6, 2) for p in parts] == [
        12.58, 33.55, 3.54, 14.68, 100.66]
    assert sizes["attn"] == sum(parts) == 165_019_648
    assert sizes["indexer"] == (2048 * 32 * 128 + 6144 * 128
                                + 6144 * 32) == 9_371_648
    assert sizes["expert"] == 3 * 6144 * 2048 == 37_748_736
    assert sizes["router"] == 6144 * 256
    assert sizes["dense_layer"] - sizes["attn"] == 226_492_416
    assert round((sizes["dense_layer"] + sizes["indexer"]) / 1e6, 1) == 400.9
    assert sizes["layer"] == (sizes["attn"] + sizes["router"]
                              + 17 * sizes["expert"])
    assert round(sizes["layer"] / 1e6, 1) == 808.3
    assert round((sizes["layer"] + sizes["indexer"]) / 1e6, 1) == 817.7
    assert round((sizes["embed"] + sizes["lm_head"]) / 1e6, 1) == 237.9
    assert round(sizes["total"] / 1e6) == 3882      # 3,881.5 M
    assert round(sizes["total"] * 2 / 1e9, 2) == 7.76
    # A token over both pools: five latent rows and two indexer keys.
    assert glm52_counts.pool_bytes_per_token(config) == 5 * 1280 + 2 * 256 \
        == 6912
    assert glm52_counts.latent_row_bytes(config) == 1152
    assert glm52_counts.index_row_bytes(config) == 256
    assert round(8 * 16384 * 6912 / 1e9, 2) == 0.91
    # The selection's work: a key is 2 x 32 x 128 operations and 256 B;
    # a selected row as JoyAI's, at 64 heads.
    assert glm52_counts.index_score_flops(config, 1) == 2 * 32 * 128
    assert glm52_counts.index_score_bytes(config, 1) == 256
    assert glm52_counts.sparse_walk_flops(config, 1) == 2 * 64 * (576 + 512)
    assert glm52_counts.sparse_walk_bytes(config, 1) == 1152
    # A prefill's restricted pairs: the triangle up to 2048, then 2048 a
    # token.
    assert glm52_counts.restricted_pairs(config, 2048) == 2048 * 2049 // 2
    assert glm52_counts.restricted_pairs(config, 10240) == (
        2048 * 2049 // 2 + 8192 * 2048) == sum(
            min(t + 1, 2048) for t in range(10240))
    assert glm52_counts.prefill_sparse_flops(config, 10240) == (
        2 * 2 * 32 * 128 * (10240 * 10241 // 2)
        + 2 * 5 * 64 * 512 * glm52_counts.restricted_pairs(config, 10240))
    # A decode step of 8 contexts of ~11k: ~4 of the 64 (layer, held
    # expert) pairs are reached when the router is even; the held
    # experts not reached are not read.
    even = 4 * glm52_counts.experts_reached_even(config, 8)
    assert 14 < even < 15
    step = glm52_counts.decode_step_bytes_rows(config, 8, 5 * 88000, even)
    whole = sizes["total"] - sizes["embed"] - (64 - even) * sizes["expert"]
    assert step == pytest.approx(
        2 * whole + 2 * 88000 * 256 + 5 * 8 * 2048 * 1152 + 8 * 6144 * 2)
    assert 3.7e9 < step < 4.1e9
    assert glm52_counts.decode_step_bytes(config, 8, 88000) == pytest.approx(
        step)
    assert "jax" not in {m.split(".")[0] for m in vars(glm52_counts)
                         if hasattr(vars(glm52_counts)[m], "__file__")}


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
    table = np.zeros((slots, per_seq), np.int32)
    out = {}
    for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        bucket = page
        while bucket < n:
            bucket *= 2
        # The slot's pages, from the pool's end and out of order.
        ids = (slots * per_seq - 1 - slot * per_seq
               - np.arange(per_seq))[::-1]
        table[slot] = ids
        cache = cache._replace(page_table={"latent": jnp.asarray(table)})
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = seq[:n]
        logits, cache, _ = prefill(
            params, jnp.asarray(padded), jnp.asarray(n, jnp.int32), cache,
            slot, {"latent": jnp.asarray(ids[:bucket // page])})
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
    padded = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for row, seq in zip(padded, seqs):
        row[:len(seq)] = seq
    want = np.asarray(jax.jit(
        lambda params, tokens: reference.logits(params, tokens, tiny))(
            ref_params or params, jnp.asarray(padded)))
    return max(np.abs(got[slot] - want[slot, n - 1:n + steps]).max()
               for slot, n in enumerate(prompt_lens))


def test_prefill_then_decode_equals_the_reference(tiny):
    """Prompts of 10, 25, 40 and 100 in one batch, 40 decode steps, with
    an ``index_topk`` of 24: the first prompt and its first steps select
    every token (the causal attention of a latent layer without an
    indexer), the others cross or start over 24, so prefills select in
    blocks of queries, decode steps select over the pool of indexer
    keys, the two expert layers in between attend over the dense
    layer's selection and the last layer over its own; 4 of 8 experts
    are held. Every logit lies within 1e-4 of the reference's full
    forward, which has no cache, no absorption and makes its selection
    with ``jax.lax.top_k``."""
    cfg, params = _tiny_model(tiny)
    assert tiny["index_topk"] == 24
    assert _worst_difference(tiny, cfg, params, (10, 25, 40, 100), 40) < TOL


def _reindexed(params):
    """The two layers that share a selection given indexers, the LAST
    layer's: a selection recomputed where it is to be shared, with
    another layer's weights."""
    import jax.numpy as jnp

    dense, shared, indexing = params["layers"]
    indexer = {n: jnp.concatenate([indexing[n]] * 2)
               for n in indexing if n.startswith(("wi_", "i_k"))}
    both = {n: jnp.concatenate([{**shared, **indexer}[n], indexing[n]])
            for n in indexing}
    return {**params, "layers": (dense, both)}


def _among_the_held_only(params):
    """A router that knows the held experts alone."""
    def cut(stack):
        if "router" not in stack:
            return stack
        held = stack["w_gate"].shape[1]
        return {**stack, "router": stack["router"][..., :held],
                "expert_bias": stack["expert_bias"][..., :held]}

    return {**params, "layers": tuple(map(cut, params["layers"]))}


def _shared_expert_twice(params):
    def twice(stack):
        if "ws_down" not in stack:
            return stack
        return {**stack, "ws_down": stack["ws_down"] * 2.0}

    return {**params, "layers": tuple(map(twice, params["layers"]))}


def _key_unrotated(monkeypatch):
    from ray_tpu.models import llama

    rotate = llama._rope_head

    def heads_only(cfg, x, positions):
        return x if x.shape[-2] == 1 else rotate(cfg, x, positions)

    monkeypatch.setattr(llama, "_rope_head", heads_only)


# name -> (changes to the program's config, to its weights, a patch)
DEPARTURES = {
    "no-selection": ({"index_topk": 4096}, None, None),
    "selection-recomputed-with-another-layers-weights":
        ({"indexer_types": ("full",) * 4}, _reindexed, None),
    "gates-renormalised-over-the-held-experts-only":
        ({"n_experts": 4, "experts_held": None}, _among_the_held_only, None),
    "indexer-key-unrotated": ({}, None, _key_unrotated),
    "shared-expert-counted-twice": ({}, _shared_expert_twice, None),
    "indexer-rotary-on-halves": ({"index_rope_interleave": False}, None, None),
    "every-expert-held": ({"experts_held": (4, 4)}, None, None),
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_a_single_departure_fails_a_hundred_times_over(tiny, departure,
                                                       monkeypatch):
    """Each way the program could leave the published layer or this
    chip's share of it moves a logit by at least 100 x the tolerance it
    is held to. (``every-expert-held``: the other chip's four experts'
    gates on this chip's weights.)"""
    changes, reweigh, patch = DEPARTURES[departure]
    if patch:
        patch(monkeypatch)
    cfg, ref_params = _tiny_model(tiny)
    cfg = dataclasses.replace(cfg, **changes)
    params = reweigh(ref_params) if reweigh else ref_params
    assert _worst_difference(tiny, cfg, params, (40, 100), 6,
                             ref_params=ref_params) > 100 * TOL


def test_the_float8_control_trails_where_bfloat16_hardly_does(tiny):
    """``control_margins`` at the tiny size, 256 positions: a model
    computed on float8_e4m3 operands (its indexer's too) puts a token
    first that is the reference's best less often than one on bfloat16
    operands does and trails it on average several times as far, and the
    reference itself in the program's place trails by nothing."""
    import jax
    import jax.numpy as jnp

    reference = arch.reference(tiny)
    _, params = _tiny_model(tiny)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 128)))
    got = {name: np.asarray(jax.jit(
        lambda p, t, d=dtype: reference.control_margins(p, t, tiny, d))(
            params, tokens))
        for name, dtype in (("float8", jnp.float8_e4m3fn),
                            ("bfloat16", jnp.bfloat16), ("float32", None))}
    assert got["float32"].shape == (2, 128) and not got["float32"].any()
    assert got["float8"].mean() > 4 * got["bfloat16"].mean() > 0.0
    assert (got["float8"] == 0).mean() < (got["bfloat16"] == 0).mean()
    assert got["float8"].max() > got["bfloat16"].max()
    # The selections: bfloat16 operands keep nearly all of float32's.
    agree = {name: float(jax.jit(
        lambda p, t, d=dtype: reference.selection_agreement(p, t, tiny, d))(
            params, tokens))
        for name, dtype in (("float8", jnp.float8_e4m3fn),
                            ("bfloat16", jnp.bfloat16))}
    assert agree["float8"] < agree["bfloat16"] <= 1.0
    assert agree["bfloat16"] > 0.97


def test_training_this_architecture_raises_by_name(tiny):
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss

    cfg, params = _tiny_model(tiny)
    with pytest.raises(NotImplementedError, match="served only"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)


def test_a_shared_layer_needs_a_full_one_before_it(tiny):
    from ray_tpu.models.llama import layer_runs

    cfg = arch.program_config(tiny)
    for types in (("shared", "full", "shared", "full"), ("full",) * 3,
                  ("full", "window", "shared", "full"), None):
        with pytest.raises(ValueError, match="indexer_types"):
            layer_runs(dataclasses.replace(cfg, indexer_types=types))


# ---- the readers on hand-made records --------------------------------------

INDEX_WALK = "pallas_f32_8_1_64_256_bf16_2_8192_16_128"
SPARSE_WALK = "pallas_bf16_8_1_64_512_bf16_5_8192_16_640"
TILES = "pallas_s8_128_32_128_512"
FLASH = "pallas_bf16_64_16384_256"
OTHERS = [["pallas_bf16_32_32_512_bf16_5_8192_16_640", 15, 0.5],   # JoyAI's
          ["pallas_bf16_32_4096_128_f32_32_1_4096", 5, 0.5],       # flash
          ["pallas_bf16_64_2048", 24, 0.5],                        # grouped
          ["pallas_f32_16_8_5_128_f32_6_16_8_2_136_128", 3, 0.5],  # Brumby's
          ["fusion_bf16_8_6144", 9, 0.004]]


def _record(config, engine=None, before=None, trace=None):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return {"config": config, "trace": trace,
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


def _traced(config):
    """Three decode steps of 8 sequences at a context of 11,000 and one
    prefill of 12,000 tokens in the 16,384 bucket, all times made up."""
    tokens = 10 * 8 * 11000
    after = {"decode_steps": 13, "decode_kv_tokens": 900 + tokens,
             "decode_kv_rows_read": 700 + 5 * tokens,
             "decode_kv_rows_selected": 500 + 10 * 5 * 8 * 2048,
             "kv_row_bytes": {"latent": 1280, "index": 256},
             "pages": {"latent": {"layers": 5, "total": 8192, "free": 0},
                       "index": {"layers": 2, "total": 8192, "free": 0}},
             "requests": [[101.0, 101.1, 102.0, None, 12000, 16384, 7, None]],
             "moe": {"assignments": 1000 + 625,
                     "assignments_elsewhere": 9000 + 9375}}
    before = {"decode_steps": 3, "decode_kv_tokens": 900,
              "decode_kv_rows_read": 700, "decode_kv_rows_selected": 500,
              "moe": {"assignments": 1000, "assignments_elsewhere": 9000}}
    trace = {"busy_s": 0.5, "window_s": 0.6,
             "modules": {"decode_step": [0.011, 0.010, 0.012],
                         "prefill": [0.9]},
             "ops": [[INDEX_WALK, 6, 0.005], [SPARSE_WALK, 15, 0.01],
                     [TILES, 2, 0.1], [FLASH, 5, 0.3]] + OTHERS}
    return _record(config, after, before, trace)


def test_readers_on_a_hand_made_record(config):
    from benchmark import flops

    record = _traced(config)
    peak = flops.peaks("TPU v5 lite")
    assert readers.index_row_bytes(record) == 256
    assert readers.selected_rows_share(record) == pytest.approx(
        100 * 2048 / 11000)
    assert readers.routed_here_share(record) == pytest.approx(6.25)
    assert readers.index_score_time_share(record) == pytest.approx(1.0)
    assert readers.sparse_walk_time_share(record) == pytest.approx(2.0)
    # Scoring: 32 operations a byte, under the ridge: the bytes bound it.
    keys = 3 * 2 * 8 * 11000
    assert readers.index_score_roofline(record) == pytest.approx(
        100 * keys * 256 / peak["hbm_bytes_per_s"] / 0.005)
    # The selected rows alone: 2,048 a sequence a layer.
    rows = 3 * 5 * 8 * 2048
    assert readers.sparse_walk_roofline(record) == pytest.approx(
        100 * rows * 1152 / peak["hbm_bytes_per_s"] / 0.01)
    # One prefill (five calls, one a layer) of the bucket's mean prompt.
    least = flops.roofline_s(
        glm52_counts.prefill_sparse_flops(config, 12000),
        glm52_counts.prefill_sparse_bytes(config, 12000), peak)
    assert readers.prefill_sparse_roofline(record) == pytest.approx(
        100 * least / 0.4)
    for name in ("index_score_roofline", "sparse_walk_roofline",
                 "prefill_sparse_roofline"):
        assert 0 < getattr(readers, name)(record) < 100
    # Through the harness, under the metrics' own names.
    bench = bench_run.load_benchmark()
    got = bench_run.read_metrics(
        [m for m in bench["per_layer"] if m.get("workloads") == [CELL]],
        record)
    assert {k: v["unit"] for k, v in got.items()} == {
        "index_score_time_share.chat": "%", "index_score_roofline.chat": "%",
        "sparse_walk_time_share.chat": "%", "sparse_walk_roofline.chat": "%",
        "prefill_sparse_roofline.chat": "%", "selected_rows_share.chat": "%",
        "index_row_bytes.chat": "bytes", "routed_here_share.chat": "%"}


@pytest.mark.parametrize("name", [
    "index_score_time_share", "index_score_roofline",
    "sparse_walk_time_share", "sparse_walk_roofline",
    "prefill_sparse_roofline", "selected_rows_share", "index_row_bytes",
    "routed_here_share"])
def test_a_reader_finds_nothing_and_says_none(config, name):
    """The parent's engine has no such gauge or counter, an untraced run
    no trace, a trace of another model no such kernel, and another
    configuration's counts no such function: None each time, no raise."""
    reader = getattr(readers, name)
    joyai = _load("benchmark", "configs", "joyai-llm-flash-L5.json")
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {}, "ops": OTHERS}
    old_engine = {"decode_steps": 3, "decode_kv_tokens": 9,
                  "decode_kv_rows_read": 45, "requests": [],
                  "kv_row_bytes": {"latent": 1280},
                  "moe": {"assignments": 5}}
    records = [_record(config), _record(config, old_engine, old_engine),
               _record(config, old_engine, old_engine, bare),
               _record(joyai, old_engine, old_engine, bare),
               _record(joyai, old_engine, old_engine, traced["trace"])]
    traced_only = ("index_score_time_share", "index_score_roofline",
                   "sparse_walk_time_share", "sparse_walk_roofline",
                   "prefill_sparse_roofline")
    if name in traced_only:
        records.append(_record(config, traced["worker"]["engine"],
                               traced["worker"]["engine_before"], bare))
    for record in records:
        assert reader(record) is None
