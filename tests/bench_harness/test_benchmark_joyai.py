"""JoyAI-LLM-Flash's configuration (``benchmark/joyai_*.py``,
``benchmark/readers/latent.py``): the file against the catalog's row, the
counts at the published widths, the float32 reference against the
program at a tiny size (``joyai_tiny/config.json``: hidden 64, 4 heads
of q.k 24 = 16 + 8 rotated beside v 12, ranks 24 and 32, 8 experts top-2
with a selection bias and a shared one, 1 dense + 3 expert layers, page
16), single departures from the published layer each refused a hundred
times over, the float8 control, the new readers on hand-made records,
and that a seed's weights are what they were for the configurations
that were there. CPU, no processes."""

import dataclasses
import hashlib
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, joyai_counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import latent as readers  # noqa: E402

TOL = 1e-4
CELL = "serve-joyai-c16-4k"


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "joyai-llm-flash-L5.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "joyai_tiny", "config.json")


def test_file_holds_the_catalogs_row_and_cuts_depth_alone(config):
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    assert {k: config[k] for k in published} == published
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert (config["reduced"]["num_hidden_layers"]["published"],
            config["num_hidden_layers"]) == (40, 5)
    assert set(config["assumed"]) >= {
        "latent_norms", "e_score_correction_bias", "router_dtype"}
    # The multi-token-prediction module: published, and not served.
    assert "num_nextn_predict_layers" in config["not_served"]
    assert config["engine"] == {"max_batch": 32, "max_len": 8192,
                                "page_size": 16, "total_pages": 8192}


def test_the_cell_is_the_issues_letter_for_letter():
    bench = bench_run.load_benchmark()
    cell, _, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash-L5", "chat-closed-c16-4k", 1)
    assert (traffic["kind"], traffic["concurrency"], traffic["clients"],
            traffic["requests"]) == ("serve", 16, 16, 192)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 4096,
                                 "sigma": 0.4, "min": 2048, "max": 7168}
    assert traffic["output"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.25, "min": 512, "max": 1024}
    assert (traffic["grace_s"], traffic["check_requests"],
            traffic["trace_at_s"], traffic["trace_seconds"]) == (5, 4, 20, 3)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert {n: (m["unit"], m["source"], m["layer"], m["moves"])
            for n, m in mine.items()} == {
        "latent_walk_time_share.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "latent_walk_roofline.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "latent_row_bytes.chat":
            ("bytes", "program_counter", "kv cache manager", "gap_p90_s")}
    # The warm-up loads the buckets 2048 to 8192 whatever the seed.
    from benchmark import loadgen
    from benchmark.jobs import serve

    requests = loadgen.schedule(traffic, 2 ** 31 + 5, 51.0, 129280)
    assert sorted({serve.bucket(len(r["prompt"]), 16, 8192)
                   for r in requests}) == [2048, 4096, 8192]
    assert max(len(r["prompt"]) + r["max_new_tokens"]
               for r in requests) <= 8192


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.expert_size,
            cfg.num_layers, cfg.num_heads, cfg.vocab_size) == (
                2048, 7168, 768, 5, 32, 129280)
    # dh is the q.k width; the rows of the cache hold 512 + a lane tile.
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.dh, cfg.latent_row) == (
                1536, 512, 128, 64, 128, 192, 640)
    assert cfg.dh == joyai_counts.head_dim(config) == config["qk_head_dim"]
    assert (cfg.rope_theta, cfg.rope_interleave, cfg.rms_eps) == (
        32e6, True, 1e-6)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts,
            cfg.num_dense_layers) == (256, 8, 1, 1)
    assert (cfg.router_score, cfg.router_bias, cfg.route_norm,
            cfg.route_scale) == ("sigmoid", True, True, 2.5)
    assert str(cfg.dtype) == "bfloat16"
    with pytest.raises(NotImplementedError, match="grouped top-k"):
        arch.program_config({**config, "n_group": 8, "topk_group": 4})


def test_the_stack_is_a_dense_layer_and_four_expert_layers(config):
    import jax
    from ray_tpu.models import init_params
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = arch.program_config(config)
    assert [tuple(r) for r in layer_runs(cfg)] == [
        (0, 1, False, "latent", 0), (1, 4, True, "latent", 1)]
    assert kv_layers(cfg) == {"latent": 5}
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    dense, sparse = shapes["layers"]
    assert "wq" not in sparse and "wk" not in sparse and "wv" not in sparse
    assert {n: sparse[n].shape[1:] for n in (
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wk_b", "wv_b",
        "wo")} == {
        "wq_a": (2048, 1536), "q_a_norm": (1536,),
        "wq_b": (1536, 32, 192), "wkv_a": (2048, 576), "kv_a_norm": (512,),
        "wk_b": (512, 32, 128), "wv_b": (512, 32, 128),
        "wo": (32, 128, 2048)}
    assert dense["w_gate"].shape == (1, 2048, 7168)
    assert sparse["w_gate"].shape == (4, 256, 2048, 768)
    assert sparse["ws_gate"].shape == (4, 2048, 768)
    assert sparse["expert_bias"].shape == (4, 256)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == joyai_counts.param_counts(config)["total"]


def test_counts_at_the_published_widths(config):
    sizes = joyai_counts.param_counts(config)
    attn = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
            + 4096 * 2048)
    expert = 3 * 2048 * 768
    assert (sizes["attn"], sizes["expert"]) == (attn, expert) == (
        26_345_472, 4_718_592)
    assert sizes["dense_layer"] == attn + 3 * 2048 * 7168 == 70_385_664
    assert sizes["layer"] == attn + 2048 * 256 + 257 * expert == 1_239_547_904
    assert sizes["embed"] + sizes["lm_head"] == 529_530_880
    assert sizes["matmul"] == (sizes["dense_layer"] + 4 * (
        attn + 2048 * 256 + 9 * expert) + 2048 * 129280)
    assert round(sizes["total"] / 1e6, 1) == 5558.1
    assert round(sizes["total"] * 2 / 1e9, 2) == 11.12
    # A token's latent and rotary key in one layer, and in all five.
    assert joyai_counts.latent_row_bytes(config) == 1152
    assert joyai_counts.kv_bytes_per_token(config) == 5760
    assert joyai_counts.latent_walk_flops(config, 1) == 69_632
    assert joyai_counts.latent_walk_bytes(config, 1) == 1152
    # A prefill rebuilt: 192-wide q.k and 128-wide p.v over the triangle.
    assert joyai_counts.flash_prefill_flops(config, 4096) == (
        2 * 5 * 32 * 320 * 4096 * 4097 // 2)
    assert joyai_counts.flash_prefill_bytes(config, 4096) == (
        5 * 4096 * (32 * (192 + 128 + 128 + 128) + 64) * 2)
    even = 4 * joyai_counts.experts_reached_even(config, 16)
    assert 100 < even / 4 < 102
    assert joyai_counts.decode_step_bytes(config, 16, 75000) == \
        joyai_counts.decode_step_bytes_rows(config, 16, 5 * 75000, even)
    assert joyai_counts.decode_step_flops(config, 16, 75000) == \
        joyai_counts.decode_step_flops_rows(config, 16, 5 * 75000)
    # ISSUE 42's step: 16 contexts of ~4.7k, ~101 experts a layer.
    step = joyai_counts.decode_step_bytes_rows(config, 16, 5 * 75000, even)
    assert 4.6e9 < step < 5.3e9
    assert "jax" not in {m.split(".")[0] for m in vars(joyai_counts)
                         if hasattr(vars(joyai_counts)[m], "__file__")}


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
    """Prompts of 10, 25, 40 and 100 in one batch whose slots are at
    different lengths, 60 decode steps: the prefill attends with k and v
    rebuilt and lays the rows into the slot's pages, each decode step
    attends absorbed over them, and every logit lies within 1e-4 of the
    reference's full forward, which does neither."""
    cfg, params = _tiny_model(tiny)
    assert _worst_difference(tiny, cfg, params, (10, 25, 40, 100), 60) < TOL


def _unnormed_latent(params):
    """kv_a_norm's weight cannot undo the norm; doubling it shows that
    the norm's weight is read (a norm left out would differ more)."""
    def double(stack):
        return {**stack, "kv_a_norm": stack["kv_a_norm"] * 2.0}

    return {**params, "layers": tuple(map(double, params["layers"]))}


def _biased_gate(monkeypatch):
    """The selection bias left in the gate."""
    from ray_tpu.parallel import moe

    plain = moe.route

    def route(logits, k, *, select_bias=None, **how):
        import jax

        scores, _, experts = plain(logits, k, select_bias=select_bias,
                                   **{**how, "renormalize": False,
                                      "scale": 1.0})
        gates = jax.numpy.take_along_axis(scores + select_bias, experts, -1)
        gates = gates / gates.sum(-1, keepdims=True) * how["scale"]
        return scores, gates, experts

    monkeypatch.setattr(moe, "route", route)
    return {}


DEPARTURES = {
    "rotary-on-halves": {"rope_interleave": False},
    "softmax-for-sigmoid": {"router_score": "softmax"},
    "bias-in-the-gate": _biased_gate,
    "no-renormalisation": {"route_norm": False},
    "no-scaling-factor": {"route_scale": 1.0},
    "another-theta": {"rope_theta": 5000.0},
    "the-latent-norms-weight-unread": {},
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_a_single_departure_fails_a_hundred_times_over(tiny, departure,
                                                       monkeypatch):
    """Each way the program could leave the published layer moves a
    logit by at least 100 x the tolerance it is held to."""
    change = DEPARTURES[departure]
    if callable(change):
        change = change(monkeypatch)
    cfg, params = _tiny_model(tiny, **change)
    _, ref_params = _tiny_model(tiny)
    if departure == "the-latent-norms-weight-unread":
        ref_params = _unnormed_latent(ref_params)
    assert _worst_difference(tiny, cfg, params, (40,), 6,
                             ref_params=ref_params) > 100 * TOL


def test_the_float8_control_trails_where_bfloat16_hardly_does(tiny):
    """``control_margins`` at the tiny size, 256 positions: a model
    computed on float8_e4m3 operands puts a token first that is the
    reference's best less than half as often as one on bfloat16 operands
    does and trails it on average several times as far, and the
    reference itself in the program's place trails by nothing. (What the
    limit on the chip stands between is the two worst readings at the
    published widths: the reference's docstring.)"""
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
    assert (got["float8"] == 0).mean() < 0.6 < (got["bfloat16"] == 0).mean()
    assert got["float8"].max() > got["bfloat16"].max()


def test_training_this_architecture_raises_by_name(tiny):
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss

    cfg, params = _tiny_model(tiny)
    with pytest.raises(NotImplementedError, match="served only"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)


# ---- a seed's weights are what they were -----------------------------------

def _digest(cfg, seed=7):
    import jax
    from ray_tpu.models import init_params

    params = init_params(cfg, jax.random.PRNGKey(seed))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,want", [
    ("tiny", "b503b9d967e08363"), ("moe_tiny", "9a8ae068580211dd"),
    ("olmoe_tiny", "77cea8ebf3f74a43"), ("trinity_tiny", "a9f16ed46a2a96c7")])
def test_init_params_of_what_was_there_is_bit_for_bit_what_it_was(name, want):
    """Digests taken at the parent commit (PR 41) of the tiny presets of
    every architecture the benchmark had: the leaves are drawn in the
    order and from the keys they always were."""
    from ray_tpu.models import LlamaConfig

    cfg = (LlamaConfig.tiny() if name == "tiny" else arch.program_config(
        _load("tests", "bench_harness", name, "config.json")))
    assert _digest(cfg) == want


def test_no_leaf_that_was_there_is_drawn_in_slices():
    """The five configurations the benchmark had, at their published
    widths: every leaf is at most ``_WHOLE_LEAF_ELEMENTS`` and so drawn
    whole, as it always was. JoyAI's experts are over it and are drawn a
    layer at a time; the slices are the leaf's leading axis, each from a
    key of its own."""
    import jax
    from ray_tpu.models import init_params, llama

    bench = bench_run.load_benchmark()
    largest = {}
    for entry in bench["configs"]:
        cfg = arch.program_config(_load(entry["file"]))
        shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        largest[entry["name"]] = max(
            int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    mine = largest.pop("joyai-llm-flash-L5")
    assert max(largest.values()) == llama._WHOLE_LEAF_ELEMENTS == 2 ** 30
    assert mine == 4 * 256 * 2048 * 768 > 2 ** 30
    # Drawn in slices: reproducible, normal, and not one key repeated.
    sliced = llama._normal(jax.random.PRNGKey(1), (3, 8, 16), 0.5, "float32")
    whole = llama._normal(jax.random.PRNGKey(1), (3, 8, 16), 0.5, "float32")
    np.testing.assert_array_equal(np.asarray(sliced), np.asarray(whole))
    old = llama._WHOLE_LEAF_ELEMENTS
    try:
        llama._WHOLE_LEAF_ELEMENTS = 100
        sliced = np.asarray(llama._normal(
            jax.random.PRNGKey(1), (3, 8, 16), 0.5, "float32"))
    finally:
        llama._WHOLE_LEAF_ELEMENTS = old
    assert sliced.shape == (3, 8, 16) and 0.3 < sliced.std() < 0.7
    assert not np.array_equal(sliced[0], sliced[1])


# ---- the readers on hand-made records --------------------------------------

WALK = "pallas_bf16_32_32_512_bf16_5_8192_16_640"


def _record(config, engine=None, before=None, trace=None):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return {"config": config, "trace": trace,
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


def _traced(config):
    """Three decode steps of 16 sequences at a context of 4,700, all
    times made up; a page walk, a flash kernel and a grouped matmul of
    other models beside the latent walk."""
    after = {"decode_steps": 13, "decode_kv_rows_read": 700 + 10 * 5 * 75200,
             "kv_row_bytes": {"latent": 1280}}
    before = {"decode_steps": 3, "decode_kv_rows_read": 700}
    trace = {"busy_s": 0.03, "window_s": 0.04,
             "modules": {"decode_step": [0.011, 0.010, 0.012]},
             "ops": [[WALK, 15, 0.003],
                     ["pallas_bf16_32_32_128_bf16_1_4_8192_16_128"
                      "_bf16_1_4_8192_16_128", 3, 0.5],
                     ["pallas_bf16_32_4096_128_f32_32_1_4096", 5, 0.5],
                     ["pallas_bf16_256_768", 24, 0.5],
                     ["fusion_bf16_32_2048", 9, 0.004]]}
    return _record(config, after, before, trace)


def test_readers_on_a_hand_made_record(config):
    from benchmark import flops

    record = _traced(config)
    assert readers.latent_row_bytes(record) == 1280
    assert readers.latent_walk_time_share(record) == pytest.approx(10.0)
    rows = 3 * 5 * 75200
    peak = flops.peaks("TPU v5 lite")
    # 60 operations a byte against a ridge of 240: the bytes bound it.
    least = rows * 1152 / peak["hbm_bytes_per_s"]
    assert least > rows * 69_632 / peak["bf16_flops_per_s"]
    assert readers.latent_walk_roofline(record) == pytest.approx(
        100 * least / 0.003)
    assert 0 < readers.latent_walk_roofline(record) < 100
    # Through the harness, under the metrics' own names.
    bench = bench_run.load_benchmark()
    got = bench_run.read_metrics(
        [m for m in bench["per_layer"] if m.get("workloads") == [CELL]],
        record)
    assert {k: v["unit"] for k, v in got.items()} == {
        "latent_walk_time_share.chat": "%", "latent_walk_roofline.chat": "%",
        "latent_row_bytes.chat": "bytes"}


@pytest.mark.parametrize("name", ["latent_walk_time_share",
                                  "latent_walk_roofline", "latent_row_bytes"])
def test_a_reader_finds_nothing_and_says_none(config, name):
    """The parent's engine has no such gauge, an untraced run no trace,
    a trace of another model no such kernel, and another configuration's
    counts no such function: None each time, no raise."""
    reader = getattr(readers, name)
    trinity = _load("benchmark", "configs", "trinity-mini-L6.json")
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {},
            "ops": [["fusion_bf16_32_2048", 9, 0.004],
                    ["pallas_bf16_32_4096_128_f32_32_1_4096", 5, 0.5]]}
    old_engine = {"decode_steps": 3, "decode_kv_tokens": 9, "requests": []}
    records = [_record(config), _record(config, old_engine, old_engine),
               _record(config, old_engine, old_engine, bare)]
    if name != "latent_row_bytes":
        records.append(_record(config, traced["worker"]["engine"],
                               traced["worker"]["engine_before"], bare))
    if name == "latent_walk_roofline":
        records.append(_record(trinity, traced["worker"]["engine"],
                               traced["worker"]["engine_before"],
                               traced["trace"]))
    for record in records:
        assert reader(record) is None
