"""Trinity-Mini's configuration (``benchmark/trinity_*.py``,
``benchmark/readers/window.py``): the file against the catalog's row, the
counts at the published widths, the float32 reference against the
program at a tiny size (``trinity_tiny/config.json``: hidden 64, 4 heads
on 2 of 16, 8 experts top-2 and a shared one, 1 dense + 4 expert layers
of kinds S S F S S, window 32, page 16), eight single departures from the
published layer each refused a hundred times over, and the new readers
on hand-made records. CPU, no processes."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, trinity_counts  # noqa: E402
from benchmark.readers import window as readers  # noqa: E402

TOL = 1e-4


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "trinity-mini-L6.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "trinity_tiny", "config.json")


def test_file_holds_the_catalogs_row_and_cuts_depth_alone(config):
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: config[k] for k in published} == published
    # The nested group is copied whole; the six layers that run are its
    # first six: two dense window layers, then S F S S with experts.
    assert config["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert trinity_counts.layer_types(config) == [
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention", "sliding_attention", "sliding_attention"]
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert (config["reduced"]["num_hidden_layers"]["published"],
            config["num_hidden_layers"]) == (32, 6)
    assert set(config["assumed"]) >= {"block", "router_dtype", "expert_bias"}
    assert config["engine"] == {"max_batch": 32, "max_len": 8192,
                                "page_size": 16, "total_pages": 8192}


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.expert_size,
            cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.dh,
            cfg.vocab_size) == (2048, 6144, 1024, 6, 32, 4, 128, 200192)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts,
            cfg.num_dense_layers) == (128, 8, 1, 2)
    assert (cfg.router_score, cfg.router_bias, cfg.route_norm,
            cfg.route_scale) == ("sigmoid", True, True, 2.826)
    assert cfg.layer_types == ("window",) * 3 + ("full",) + ("window",) * 2
    assert (cfg.sliding_window, cfg.rope_full_layers, cfg.qk_norm,
            cfg.qk_norm_per_head, cfg.attn_gate, cfg.post_norms) == (
                2048, False, True, True, True, True)
    assert cfg.embed_scale == 2048 ** 0.5
    assert str(cfg.dtype) == "bfloat16"


def test_the_stack_is_runs_of_alike_layers(config):
    """2 dense window layers, then S, F, S S with experts: four runs, a
    stack of weights each, the KV layers numbered within their kind."""
    import jax
    from ray_tpu.models import init_params
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = arch.program_config(config)
    assert [tuple(r) for r in layer_runs(cfg)] == [
        (0, 2, False, "window", 0), (2, 1, True, "window", 2),
        (3, 1, True, "full", 0), (4, 2, True, "window", 3)]
    assert kv_layers(cfg) == {"window": 5, "full": 1}
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    stacks = shapes["layers"]
    assert [s["wq"].shape[0] for s in stacks] == [2, 1, 1, 2]
    assert ["router" in s for s in stacks] == [False, True, True, True]
    assert stacks[0]["w_gate"].shape == (2, 2048, 6144)
    assert stacks[3]["w_gate"].shape == (2, 128, 2048, 1024)
    assert stacks[3]["ws_gate"].shape == (2, 2048, 1024)
    assert stacks[3]["expert_bias"].shape == (2, 128)
    assert stacks[3]["q_norm"].shape == (2, 128)
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == trinity_counts.param_counts(config)["total"]


def test_counts_at_the_published_widths(config):
    sizes = trinity_counts.param_counts(config)
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 1024
    assert (sizes["attn"], sizes["expert"]) == (attn, expert)
    assert sizes["dense_layer"] == attn + 3 * 2048 * 6144
    assert sizes["layer"] == attn + 2048 * 128 + 129 * expert
    assert sizes["matmul"] == (2 * sizes["dense_layer"] + 4 * (
        attn + 2048 * 128 + 9 * expert) + 2048 * 200192)
    assert round(sizes["total"] * 2 / 1e9, 2) == 8.61
    # One token's key and value in one layer, and in all six.
    assert trinity_counts.kv_row_bytes(config) == 2048
    assert trinity_counts.kv_bytes_per_token(config) == 6 * 2048
    # Pairs: all of the triangle under the window, a band over it.
    pairs = trinity_counts.attended_pairs
    assert pairs(100) == pairs(100, 2048) == 5050
    assert pairs(4096, 2048) == 2048 * 2049 // 2 + 2048 * 2048
    assert trinity_counts.flash_prefill_flops(config, 4096) == 4 * 4096 * (
        5 * pairs(4096, 2048) + pairs(4096))
    # The interface's decode counts are the rows counts with every layer
    # reading the whole context and an even router.
    even = 4 * trinity_counts.experts_reached_even(config, 16)
    assert trinity_counts.decode_step_bytes(config, 16, 57600) == \
        trinity_counts.decode_step_bytes_rows(config, 16, 6 * 57600, even)
    assert trinity_counts.decode_step_flops(config, 16, 57600) == \
        trinity_counts.decode_step_flops_rows(config, 16, 6 * 57600)
    # ISSUE 38's step: 16 contexts of 3,600, ~81 experts a layer.
    step = trinity_counts.decode_step_bytes_rows(
        config, 16, 16 * (3600 + 5 * 2048), 4 * 81.2)
    assert 5.8e9 < step < 6.0e9
    assert "jax" not in {m.split(".")[0] for m in vars(trinity_counts)
                         if hasattr(vars(trinity_counts)[m], "__file__")}


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
    # ends: a causal model's logits do not see what follows.
    padded = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
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
    steps: every logit within 1e-4 of the reference's full forward."""
    cfg, params = _tiny_model(tiny)
    assert _worst_difference(tiny, cfg, params, (10, 25, 40, 100), 84) < TOL


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


def _whole_projection_norm(params):
    """q_norm and k_norm of the whole projection's width, still ones."""
    import jax.numpy as jnp

    def widen(stack):
        n, heads = stack["wq"].shape[0], stack["wq"].shape[2]
        kv_heads, dh = stack["wk"].shape[2:]
        return {**stack, "q_norm": jnp.ones((n, heads * dh)),
                "k_norm": jnp.ones((n, kv_heads * dh))}

    return {**params, "layers": tuple(map(widen, params["layers"]))}


DEPARTURES = {
    "softmax-for-sigmoid": {"router_score": "softmax"},
    "bias-in-the-gate": _biased_gate,
    "no-renormalisation": {"route_norm": False},
    "rotary-on-the-full-layer": {"rope_full_layers": True},
    "no-output-gate": {"attn_gate": False},
    "a-post-norm-left-out": {"post_norms": False},
    "qk-norm-over-the-projection": {"qk_norm_per_head": False},
    "window-off-by-one": {"sliding_window": 31},
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
    if departure == "qk-norm-over-the-projection":
        params = _whole_projection_norm(params)
    assert _worst_difference(tiny, cfg, params, (40,), 6,
                             ref_params=ref_params) > 100 * TOL


def test_the_router_selects_by_bias_and_gates_by_score():
    import jax.numpy as jnp
    from ray_tpu.parallel.moe import route

    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    bias = jnp.asarray([-5.0, 0.0, 0.0, 3.0])
    scores, gates, experts = route(logits, 2, score="sigmoid",
                                   select_bias=bias, renormalize=True,
                                   scale=2.826)
    sig = 1 / (1 + np.exp(-np.asarray(logits)[0]))
    np.testing.assert_allclose(np.asarray(scores)[0], sig, rtol=1e-6)
    # The bias lifts expert 3 over 0 and 2; the gate is the score alone.
    assert sorted(np.asarray(experts)[0]) == [1, 3]
    want = sig[[3, 1]] / sig[[3, 1]].sum() * 2.826
    np.testing.assert_allclose(np.asarray(gates)[0], want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(), 2.826, rtol=1e-6)
    # The default is OLMoE's: softmax over all, gates as they fall.
    probs, gates, experts = route(logits, 2)
    soft = np.exp(np.asarray(logits)[0])
    soft /= soft.sum()
    assert list(np.asarray(experts)[0]) == [0, 1]
    np.testing.assert_allclose(np.asarray(gates)[0], soft[:2], rtol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        route(logits, 2, score="tanh")


def test_training_this_architecture_raises_by_name(tiny):
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss

    cfg, params = _tiny_model(tiny)
    with pytest.raises(NotImplementedError, match="not uniform"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)


# ---- the readers on hand-made records --------------------------------------

def _record(config, engine=None, before=None, trace=None):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return {"config": config, "trace": trace,
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


def test_counter_readers_on_a_hand_counted_window(config):
    """10 steps of 2 sequences at contexts 3000 and 1000: a full layer
    reads 4000 rows a step, a window layer 2048 + 1000."""
    before = {"decode_steps": 5, "decode_kv_tokens": 100,
              "decode_kv_rows_read": 600, "kv_page_steps_held": 10,
              "kv_page_steps_one_table": 20}
    rows = 10 * (4000 + 5 * 3048)
    held = 10 * ((188 + 63) + 5 * (129 + 63))
    after = {"decode_steps": 15, "decode_kv_tokens": 100 + 40000,
             "decode_kv_rows_read": 600 + rows,
             "kv_page_steps_held": 10 + held,
             "kv_page_steps_one_table": 20 + 10 * 6 * (188 + 63)}
    record = _record(config, after, before)
    assert readers.window_kv_read_share(record) == pytest.approx(
        100 * rows / (6 * 40000))
    assert readers.kv_held_share(record) == pytest.approx(
        100 * held / (60 * 251))
    assert 0 < readers.kv_held_share(record) < 100


def _traced(config):
    """Three decode steps of 16 sequences and one prefill of 3000 tokens
    in a bucket of 4096, all times made up."""
    moe = {"assignments": 96000 + 3 * 512, "decode_assignments": 3 * 512,
           "experts_reached": 3 * 320, "prefill_experts_reached": 512,
           "layer_steps": 12, "expert_tokens": [0] * 128}
    zero = {k: 0 if k != "expert_tokens" else [0] * 128 for k in moe}
    after = {"decode_steps": 3, "prefills": 1, "decode_slot_steps": 48,
             "decode_kv_tokens": 3 * 57600,
             "decode_kv_rows_read": 3 * 16 * (3600 + 5 * 2048),
             "requests": [[101.0, 101.0, 101.2, None, 3000, 4096]],
             "moe": moe}
    before = {**{k: 0 for k in after if k not in ("requests", "moe")},
              "requests": [], "moe": zero}
    walk_w = "pallas_bf16_32_32_128_bf16_5_4_4128_16_128_bf16_5_4_4128_16_128"
    walk_f = "pallas_bf16_32_32_128_bf16_1_4_8192_16_128_bf16_1_4_8192_16_128"
    trace = {"busy_s": 0.1, "window_s": 0.2,
             "modules": {"decode_step": [0.012, 0.010, 0.011],
                         "prefill": [0.2]},
             "ops": [["pallas_bf16_256_1024", 24, 0.012],
                     ["pallas_bf16_256_2048", 12, 0.006],
                     ["pallas_bf16_32768_1024", 8, 0.02],
                     ["pallas_bf16_32768_2048", 4, 0.01],
                     ["pallas_bf16_256_6144", 3, 0.5],   # no routed width
                     [walk_w, 15, 0.0012], [walk_f, 3, 0.0006],
                     ["pallas_bf16_32_4096_128_f32_32_1_4096", 6, 0.03],
                     ["fusion_bf16_32_2048", 9, 0.004]]}
    return _record(config, after, before, trace)


def test_trace_readers_on_a_hand_made_trace(config):
    from benchmark import flops

    record = _traced(config)
    peak = flops.peaks("TPU v5 lite")
    assert readers.routed_matmul_time_share(record) == pytest.approx(
        100 * 0.048 / 0.1)
    counts = trinity_counts
    least = 3 * flops.roofline_s(
        counts.moe_matmul_flops(config, 512),
        counts.moe_matmul_bytes(config, 512, 320), peak
    ) + flops.roofline_s(counts.moe_matmul_flops(config, 96000),
                         counts.moe_matmul_bytes(config, 96000, 512), peak)
    assert readers.routed_matmul_roofline(record) == pytest.approx(
        100 * least / 0.048)
    rows = 3 * 16 * (3600 + 5 * 2048)
    assert readers.page_walk_roofline(record) == pytest.approx(
        100 * (rows * 2048 / peak["hbm_bytes_per_s"]) / 0.0018)
    assert readers.prefill_flash_roofline(record) == pytest.approx(
        100 * flops.roofline_s(counts.flash_prefill_flops(config, 3000),
                               counts.flash_prefill_bytes(config, 3000),
                               peak) / 0.03)
    step = flops.roofline_s(
        counts.decode_step_flops_rows(config, 16, rows / 3),
        counts.decode_step_bytes_rows(config, 16, rows / 3, 320), peak)
    assert readers.decode_step_roofline_rows(record) == pytest.approx(
        100 * step / 0.011)
    for reader in (readers.routed_matmul_roofline,
                   readers.page_walk_roofline,
                   readers.prefill_flash_roofline,
                   readers.decode_step_roofline_rows):
        assert 0 < reader(record) < 100


ALL_READERS = ("window_kv_read_share", "kv_held_share",
               "routed_matmul_time_share", "routed_matmul_roofline",
               "page_walk_roofline", "prefill_flash_roofline",
               "decode_step_roofline_rows")


@pytest.mark.parametrize("name", ALL_READERS)
def test_a_reader_finds_nothing_and_says_none(config, name):
    """The parent's engine has no such counter, an untraced run no
    trace, a trace of another model no such operation, and another
    configuration's counts no such function: None each time, no raise."""
    reader = getattr(readers, name)
    olmoe = _load("benchmark", "configs", "olmoe-1b-7b-0125-L8.json")
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {},
            "ops": [["fusion_bf16_32_2048", 9, 0.004]]}
    old_engine = {"decode_steps": 3, "decode_kv_tokens": 9, "requests": []}
    records = [_record(config), _record(config, old_engine, old_engine),
               _record(config, old_engine, old_engine, bare),
               _record(olmoe, old_engine, old_engine, traced["trace"])]
    if name not in ("window_kv_read_share", "kv_held_share"):
        # The counters are there, the trace holds none of the kernels.
        records.append(_record(config, traced["worker"]["engine"],
                               traced["worker"]["engine_before"], bare))
    for record in records:
        assert reader(record) is None
