"""LFM2-24B-A2B's configuration (``benchmark/lfm2_*.py``,
``benchmark/readers/conv.py``): the file against the catalog's row, the
cell and its traffic letter for letter, the builder's refusals, the
counts at the published widths, the float32 reference against the
programs at a tiny size (``lfm2_tiny/config.json``: hidden 64, 4 heads
of 16 on 2 KV heads, 10 layers in the published order of kinds with 2
dense in front, 8 experts top-2 of width 32, 3 taps, page 16), a prompt
of one token and one that ends inside its bucket, a slot taken again, six
single departures from the published model each refused, and the readers
on hand-made records. CPU, no processes."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, flops  # noqa: E402
from benchmark import lfm2_counts as counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import conv as readers  # noqa: E402

TOL = 1e-4
CELL = "serve-lfm2-c16-8k"
NEW_METRICS = ["conv_mix_time_share.chat", "conv_slot_bytes.chat",
               "kv_layers_share.chat", "decode_step_roofline_conv.chat",
               "prefill_flash_h64_roofline.chat"]
KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv"]


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "lfm2-24b-a2b-L10.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "lfm2_tiny", "config.json")


def test_file_holds_the_catalogs_row_and_cuts_the_depth_alone(config):
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": (KINDS[2:6] * 10)[-2:]
        + KINDS[2:6] * 9 + KINDS[2:4], "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert len(published["layer_types"]) == 40
    assert published["layer_types"][:10] == KINDS
    assert published["layer_types"].count("full_attention") == 10
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 10
    assert config["layers_kept"] == [0, 9]
    assert counts.layer_types(config) == KINDS
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert config["reduced"]["num_hidden_layers"]["published"] == 40
    bench = bench_run.load_benchmark()
    entry, = [c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b-L10"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    arch.check_reduced(entry, config)
    assumed = config["assumed"]
    assert (assumed["head_dim"], assumed["tie_word_embeddings"],
            assumed["router_dtype"], assumed["norm_topk_epsilon"],
            assumed["qk_norm_init"]) == (64, True, "float32", 1e-06, 2.0)
    assert all(f"{key}_why" in assumed for key in (
        "head_dim", "tie_word_embeddings", "conv_gates", "conv_activation",
        "qk_norm", "qk_norm_init", "intermediate_size", "router_dtype",
        "expert_bias", "norm_topk_epsilon"))
    assert config["engine"] == {"max_batch": 16, "max_len": 16384,
                                "page_size": 16, "total_pages": 16384}
    assert config["arch"] == {
        "program_config": "benchmark.lfm2_program.lfm2_config",
        "reference": "benchmark.lfm2_reference",
        "counts": "benchmark.lfm2_counts"}
    assert config["dtype"] == "bfloat16" and config["engine_why"]


def test_the_cell_and_its_traffic_are_the_issues():
    bench = bench_run.load_benchmark()
    cell, config, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-L10", "chat-closed-c16-8k", 1)
    assert len(cell["why"]) <= 200
    # Behind every cell the parent had; pinned to NO last place: the next
    # configuration's PR appends behind this one.
    names = [c["name"] for c in bench["workloads"]]
    assert names.index(CELL) > names.index("serve-sala-c16-32k")
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == "lfm2-24b-a2b-L10"] == [CELL]
    assert {k: traffic[k] for k in (
        "kind", "concurrency", "clients", "requests", "check_requests",
        "trace_at_s", "trace_seconds")} == {
            "kind": "serve", "concurrency": 16, "clients": 16,
            "requests": 192, "check_requests": 4, "trace_at_s": 20.0,
            "trace_seconds": 4.0}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.4, "min": 4096, "max": 15360}
    assert traffic["output"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.25, "min": 512, "max": 1024}
    engine = config["engine"]
    longest = traffic["prompt"]["max"] + traffic["output"]["max"]
    assert longest == engine["max_len"] == 16384
    assert 16 * longest // engine["page_size"] <= engine["total_pages"]
    # The buckets the flash kernel runs at heads of 64.
    from benchmark import loadgen
    from benchmark.jobs.serve import bucket

    requests = loadgen.schedule(traffic, 2 ** 31 + 7, 51, 65536)
    assert {bucket(len(r["prompt"]), 16, 16384) for r in requests} == {
        4096, 8192, 16384}
    # The new metrics list this cell and no other; no accepted list lost
    # a name or its order.
    new = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "gap_p90_s"
               for m in new)
    assert [(m["unit"], m["better"], m["source"], m["layer"])
            for m in new] == [
        ("%", "lower", "device_trace", "decode program"),
        ("bytes", "lower", "program_counter", "kv cache manager"),
        ("%", "lower", "program_counter", "kv cache manager"),
        ("%", "higher", "device_trace", "decode program"),
        ("%", "higher", "device_trace", "prefill program")]
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {
        "gap_p90_s", "decode_step_device_s_p50.chat",
        "device_idle_share.chat", "programs_loaded_s.serve",
        "emit_gap_s_p90.chat", "decode_starved_share.chat",
        "experts_reached_mean.chat", "expert_load_max_over_mean.chat",
        "routed_matmul_time_share.chat", "routed_matmul_roofline.chat",
        "prefill_device_s_p50.chat", "page_walk_roofline.chat"}
    # These count every layer's k/v, a kernel a layer, or are pinned to
    # their cells by a test: not this cell's.
    assert not listed & {"decode_step_roofline.chat",
                         "decode_step_roofline_counted.chat",
                         "decode_step_roofline_rows.chat",
                         "prefill_flash_roofline.chat",
                         "grouped_small_rows_share.chat"}
    assert all(m["workloads"].index(CELL)
               > m["workloads"].index("serve-mistral7b-chat")
               for m in bench["end_to_end"] + bench["per_layer"]
               if {CELL, "serve-mistral7b-chat"} <= set(m.get("workloads", ())))
    for name in NEW_METRICS:
        assert bench_run.find_reader(name) is getattr(
            readers, name[:-len(".chat")])


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
            cfg.num_heads, cfg.num_kv_heads, cfg.dh, cfg.vocab_size) == (
                2048, 11776, 10, 32, 8, 64, 65536)
    assert (cfg.n_experts, cfg.top_k, cfg.expert_size, cfg.num_dense_layers,
            cfg.n_shared_experts) == (64, 4, 1536, 2, 0)
    assert (cfg.router_score, cfg.router_bias, cfg.route_norm,
            cfg.route_scale) == ("sigmoid", True, True, 1.0)
    assert (cfg.conv_taps, cfg.tied_head, cfg.rope_theta, cfg.rms_eps) == (
        3, True, 1e6, 1e-5)
    assert cfg.qk_norm and cfg.qk_norm_per_head and not cfg.attn_gate
    assert cfg.layer_types == tuple(
        {"conv": "conv", "full_attention": "full"}[k] for k in KINDS)
    from ray_tpu.models.generation import kv_pool_row
    from ray_tpu.models.llama import kv_layers, layer_runs

    assert [(r.kind, r.n, r.moe) for r in layer_runs(cfg)] == [
        ("conv", 2, False), ("full", 1, True), ("conv", 3, True),
        ("full", 1, True), ("conv", 3, True)]
    assert kv_layers(cfg) == {"conv": 8, "full": 2}
    # Heads of 64 two to a lane tile: a token's bytes are the model's.
    assert kv_pool_row(cfg) == (4, 128)


@pytest.mark.parametrize("change,says", [
    ({"conv_bias": True}, "conv_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_type"),
])
def test_builder_refuses_what_it_cannot_build(config, change, says):
    with pytest.raises(NotImplementedError, match=says):
        arch.program_config({**config, **change})


def test_counts_at_the_published_widths(config):
    """The issue's arithmetic, in this repo's bytes."""
    c = counts.param_counts(config)
    assert c["conv"] == 2048 * 6144 + 2048 ** 2 + 3 * 2048      # 16.78 M
    assert round(c["conv"] / 1e6, 2) == 16.78
    assert c["attn"] == 10_485_760                               # 10.49 M
    assert 3 * 2048 * 11776 == 72_351_744                        # 72.35 M
    assert 64 * c["expert"] == 603_979_776                       # 603.98 M
    assert c["embed"] == c["lm_head"] == 134_217_728             # tied: once
    assert round(c["total"] / 1e6) == 5267
    assert round(2 * c["total"] / 1e9, 2) == 10.53
    whole = {**config, "num_hidden_layers": 40, "layers_kept": [0, 39]}
    assert round(counts.param_counts(whole)["total"] / 1e9, 1) == 23.8
    assert round(2 * counts.param_counts(whole)["total"] / 1e9, 1) == 47.7
    assert counts.head_dim(config) == 64
    assert counts.kv_row_bytes(config) == 2048
    assert counts.kv_bytes_per_token(config) == 4096
    assert counts.conv_slot_bytes(config) == 65536
    # Held beside the weights for 16 streams of up to 16,384 tokens.
    assert round(16384 * 16 * counts.kv_bytes_per_token(config) / 1e9,
                 2) == 1.07
    assert 16 * counts.conv_slot_bytes(config) == 2 ** 20
    # A decode step of 16 streams at contexts of 10k: the experts REACHED,
    # the slots' own k/v at 64-wide heads, the histories both ways.
    assert round(counts.experts_reached_even(config, 16)) == 41
    step = counts.decode_step_bytes(config, 16, 16 * 10000)
    reached = 8 * counts.experts_reached_even(config, 16)
    experts = reached * c["expert"] * 2
    kv = 16 * 10000 * 4096
    assert round(experts / 1e9, 2) == 6.14 and round(kv / 1e9, 2) == 0.66
    others = 2 * (c["total"] - 8 * 64 * c["expert"])
    assert step == pytest.approx(
        experts + kv + others + 2 * 16 * 65536 + 16 * 2048 * 2)
    assert 7.6e9 < step < 7.8e9
    assert round(1e3 * step / 819e9, 1) == 9.4
    assert experts / step == pytest.approx(0.80, abs=0.01)
    # The walk's own operations and bytes, kept beside them.
    assert counts.page_walk_bytes(config, 2 * 160000) == 2 * kv / 2
    assert counts.page_walk_flops(config, 1) == 4 * 32 * 64


# ---- the programs against the reference, at the tiny size ---------------


class _Programs:
    """The tiny model's two programs over its pools, jitted once."""

    def __init__(self, config):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import init_params
        from ray_tpu.models.generation import (
            KVBooks, PagedKVCache, paged_decode, paged_prefill)

        self.config, self.cfg = config, arch.program_config(config)
        self.ref = arch.reference(config)
        self.params = jax.jit(lambda key: init_params(self.cfg, key))(
            jax.random.PRNGKey(3))
        eng = config["engine"]
        self.batch, self.page = eng["max_batch"], eng["page_size"]
        geometry = (self.batch, eng["total_pages"], self.page,
                    eng["max_len"] // self.page)
        self.cache = PagedKVCache.create(self.cfg, *geometry)
        self.books = KVBooks(self.cfg, *geometry, self.cache)
        cfg = self.cfg
        self.prefill = jax.jit(lambda p, t, n, c, s, pg: paged_prefill(
            p, t, n, c, cfg, s, pg))
        self.decode = jax.jit(lambda p, t, c, a: paged_decode(
            p, t, c, cfg, active=a))
        self.logits = jax.jit(
            lambda p, t, departure=None: self.ref.logits(
                p, t, config, departure=departure), static_argnums=2)
        self.jnp = jnp

    def serve(self, slot, seq, prompt_len, bucket):
        """Prefill ``seq[:prompt_len]`` into ``slot``, then decode the
        rest teacher-forced; the logits at positions ``prompt_len - 1
        ..``, [len(seq) - prompt_len + 1, V]."""
        jnp = self.jnp
        pages, tables = self.books.reserve(slot, len(seq), bucket)
        self.cache = self.cache._replace(page_table={
            k: jnp.asarray(v) for k, v in tables.items()})
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt_len] = seq[:prompt_len]
        logits, self.cache, _ = self.prefill(
            self.params, jnp.asarray(padded),
            jnp.asarray(prompt_len, jnp.int32), self.cache,
            jnp.asarray(slot, jnp.int32),
            {k: jnp.asarray(v, jnp.int32) for k, v in pages.items()})
        out = [np.asarray(logits)[0]]
        active = np.zeros(self.batch, bool)
        active[slot] = True
        for t in range(prompt_len, len(seq)):
            toks = np.zeros(self.batch, np.int32)
            toks[slot] = seq[t]
            logits, self.cache, _ = self.decode(
                self.params, jnp.asarray(toks), self.cache,
                jnp.asarray(active))
            out.append(np.asarray(logits)[slot])
        return np.stack(out)

    def reference(self, seq, departure=None):
        return np.asarray(self.logits(
            self.params, self.jnp.asarray(np.asarray(seq)[None]),
            departure))[0]


@pytest.fixture(scope="module")
def programs(tiny):
    return _Programs(tiny)


@pytest.fixture(scope="module")
def served(programs):
    """One sequence of 120 tokens: a prompt of 40 in the 64 bucket (the
    history is taken at token 39, not at the bucket's end), then 80
    decode steps through both pools, across a page's end."""
    seq = np.random.default_rng(0).integers(0, 256, 120).astype(np.int32)
    return seq, programs.serve(1, seq, 40, 64), programs.reference(seq)


def test_prefill_then_decode_equals_the_reference(served):
    seq, got, want = served
    assert np.abs(got - want[39:]).max() < TOL
    assert np.abs(want).max() > 0.5      # and the logits are not nothing


def test_a_prompt_of_one_token_leaves_zeros_in_front(programs, served):
    """A prompt of 1: the history a prefill lays is (0, z_0), and every
    later token comes by a decode step."""
    seq, _, want = served
    got = programs.serve(2, seq[:50], 1, 16)
    assert np.abs(got - want[:50]).max() < TOL
    programs.books.release(2)


def test_a_slot_taken_again_keeps_nothing_of_the_request_before(programs,
                                                                served):
    """Slot 1 again, with other pages and a shorter prompt: a prefill
    overwrites the slot's histories whole, nothing is zeroed between."""
    programs.books.release(1)
    held = programs.books.reserve(3, 64, 64)     # so the pages differ
    seq = np.random.default_rng(2).integers(0, 256, 70).astype(np.int32)
    got = programs.serve(1, seq, 2, 16)
    assert held is not None
    assert np.abs(got - programs.reference(seq)[1:]).max() < TOL
    programs.books.release(1)
    programs.books.release(3)


@pytest.mark.parametrize("departure", [
    "no_history", "swap_bc", "two_taps", "bias_in_gate", "no_renorm",
    "rope_on_conv"])
def test_a_single_departure_is_refused(programs, served, departure):
    """Each departure from the published model moves the reference's
    logits at the served positions by over a thousand times the
    tolerance: the comparison that passes above would fail it."""
    seq, got, _ = served
    departed = programs.reference(seq, departure)
    assert np.abs(got - departed[39:]).max() > 1000 * TOL


def test_the_per_head_norms_weights_are_drawn_and_nothing_else_moves(
        programs):
    """``qk_norm_init`` 2: q_norm and k_norm are drawn about 2, a weight
    a channel, in the attention runs alone; at 1 they are ones, and every
    other leaf is the same array either way (the draw comes last)."""
    import dataclasses

    import jax

    from ray_tpu.models import init_params

    at_one = dataclasses.replace(programs.cfg, qk_norm_init=1.0)
    ones = jax.jit(lambda key: init_params(at_one, key))(
        jax.random.PRNGKey(3))
    drawn = 0
    for with_ones, stack in zip(ones["layers"], programs.params["layers"]):
        assert set(with_ones) == set(stack)
        for name in stack:
            if name in ("q_norm", "k_norm"):
                w = np.asarray(stack[name])
                assert (np.asarray(with_ones[name]) == 1).all()
                assert 1.5 < w.mean() < 2.5 and w.std() > 0.2
                drawn += 1
            else:
                assert (np.asarray(with_ones[name])
                        == np.asarray(stack[name])).all()
    assert drawn == 4              # two runs of one attention layer each


def test_training_this_architecture_raises_by_name(programs):
    import jax.numpy as jnp

    from ray_tpu.models import forward

    with pytest.raises(NotImplementedError, match="tied_head"):
        forward(programs.params, jnp.zeros((1, 8), jnp.int32), programs.cfg)
    import dataclasses

    untied = dataclasses.replace(programs.cfg, tied_head=False)
    with pytest.raises(NotImplementedError, match="'conv'"):
        forward(programs.params, jnp.zeros((1, 8), jnp.int32), untied)


def test_the_reference_is_float32_and_shares_nothing_with_the_program():
    path = os.path.join(REPO, "benchmark", "lfm2_reference.py")
    with open(path) as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "ray_tpu" not in body and "pallas" not in body
    assert "precision=_HI" in body
    from benchmark import lfm2_reference as ref

    assert set(ref.LOGIT_MARGIN_TOL) == set(ref.LOSS_ATOL) == {
        "bfloat16", "float32"}
    assert ref.LOGIT_MARGIN_TOL["float32"] == TOL
    with pytest.raises(ValueError, match="departure"):
        ref.hidden(None, np.zeros((1, 8), np.int32), {}, departure="other")


def test_the_controls_take_the_programs_place(programs):
    """``control_margins``: the reference itself trails itself by
    nothing; with operands rounded to float8 it does not, nor with the
    k/v rounded alone, a 128-wide head's score scale or each query head
    reading the other KV head of its pair."""
    import jax.numpy as jnp

    seq = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 192)),
                      jnp.int32)
    ref, config = programs.ref, programs.config
    assert float(ref.control_margins(programs.params, seq, config).max()) == 0
    rounded = ref.control_margins(programs.params, seq, config,
                                  inputs=jnp.float8_e4m3fn)
    assert float(rounded.max()) > 100 * TOL
    exact = ref.logits(programs.params, seq, config)
    for departure in ("scale_128", "kv_float8", "kv_pair_swapped"):
        moved = ref.logits(programs.params, seq, config, departure=departure)
        assert float(abs(exact - moved).max()) > 100 * TOL


# ---- the readers ----------------------------------------------------------


def _record(config):
    """A window of 100 decode steps of 16 sequences at contexts of 10k
    that reached 41 experts a layer, of which a trace saw 10."""
    before = {"decode_steps": 10, "decode_slot_steps": 160,
              "decode_kv_rows_read": 0,
              "moe": {"experts_reached": 0},
              "conv": {"slot_layers": 0, "slot_bytes": 65536, "layers": 8,
                       "layers_in_all": 10}}
    after = {"decode_steps": 110, "decode_slot_steps": 1760,
             "decode_kv_rows_read": 100 * 16 * 2 * 10000,
             "moe": {"experts_reached": 100 * 8 * 41},
             "active_slots": 16, "free_slots": 0,
             # Two prompts of the 16,384 bucket and one of the 8,192,
             # submitted inside the window, and one from before it.
             "requests": [[1.0, 1.1, 1.5, None, 12000, 16384, 7, None],
                          [2.0, 2.1, 2.5, None, 14000, 16384, 8, None],
                          [3.0, 3.1, 3.3, None, 6000, 8192, 9, None],
                          [-5.0, -4.9, -4.5, 1.0, 5000, 8192, 3, None]],
             "conv": {"slot_layers": 1600 * 8, "slot_bytes": 65536,
                      "layers": 8, "layers_in_all": 10}}
    trace = {"busy_s": 0.2, "window_s": 0.25,
             "modules": {"decode_step": [0.0125] * 10, "prefill": [2.0]},
             "ops": [["fusion_bf16_16_6144", 80, 0.004],
                     ["convolution_fusion_bf16_16_1_6144", 80, 0.002],
                     ["fusion_bf16_8_2_16_2048", 80, 0.0004],
                     ["dynamic-slice_bitcast_fusion_bf16_16_2_2048", 80,
                      0.0001],
                     # A prefill's gates, and every layer's way back into
                     # the residual: neither is counted.
                     ["convolution_bitcast_fusion_bf16_1_16384_6144", 8,
                      0.07],
                     ["bitcast_add_fusion_bf16_16_1_2048", 100, 0.003],
                     ["fusion_bf16_16_2048", 500, 0.01],
                     ["pallas_bf16_16_32_128_bf16_2_4_16384_16_128_"
                      "bf16_2_4_16384_16_128", 20, 0.02],
                     # The flash forward, streamed (two prefills of two
                     # attention layers) and resident (one).
                     ["pallas_f32_32_1_16384_bf16_32_16384_64", 4, 0.124],
                     ["pallas_bf16_32_8192_64_f32_32_1_8192", 2, 0.011]]}
    return {"config": config, "trace": trace,
            "traffic": {"trace_at_s": 20.0},
            "worker": {"engine": after, "engine_before": before,
                       "window_start": 0.0,
                       "device": {"kind": "TPU v5 lite"}}}


def test_conv_readers_on_a_hand_counted_window(config):
    record = _record(config)
    assert readers.conv_slot_bytes(record) == 65536
    assert readers.kv_layers_share(record) == pytest.approx(20.0)
    # The gates' products and the histories' writes, of 10 steps' time.
    assert readers.conv_mix_time_share(record) == pytest.approx(
        100 * (0.004 + 0.002 + 0.0005) / 0.125)
    least = flops.roofline_s(
        counts.decode_step_flops_rows(config, 16, 16 * 2 * 10000),
        counts.decode_step_bytes_rows(config, 16, 16 * 2 * 10000, 8 * 41),
        flops.peaks("TPU v5 lite"))
    assert readers.decode_step_roofline_conv(record) == pytest.approx(
        100 * least / 0.0125)
    assert 70 < readers.decode_step_roofline_conv(record) < 80
    # The accepted walk's reader takes the head-64 walk for the page walk
    # it is, and counts its rows at the model's own 2,048 B.
    from benchmark.readers import window

    rows = 10 * 16 * 2 * 10000
    assert window.page_walk_roofline(record) == pytest.approx(
        100 * flops.roofline_s(4 * rows * 32 * 64, rows * 2048,
                               flops.peaks("TPU v5 lite")) / 0.02)


def test_flash_reader_counts_a_call_an_attention_layer(config):
    """Four streamed calls are two prefills of the 16,384 bucket at the
    window's mean prompt of it, two resident ones one of the 8,192; the
    other pallas calls of the step are not its."""
    record = _record(config)
    peak = flops.peaks("TPU v5 lite")
    least = sum(n * flops.roofline_s(
        counts.flash_prefill_flops(config, tokens),
        counts.flash_prefill_bytes(config, tokens), peak)
        for n, tokens in ((2, 13000), (1, 6000)))
    assert readers.prefill_flash_h64_roofline(record) == pytest.approx(
        100 * least / 0.135)
    assert 10 < readers.prefill_flash_h64_roofline(record) < 40
    # A bucket the window's requests did not have, heads of 128: nothing.
    record["trace"]["ops"][-1][0] = "pallas_bf16_32_4096_64_f32_32_1_4096"
    assert readers.prefill_flash_h64_roofline(record) is None
    record["trace"]["ops"] = [["pallas_bf16_32_8192_128_f32_32_1_8192", 2,
                               0.01]]
    assert readers.prefill_flash_h64_roofline(record) is None


@pytest.mark.parametrize("reader", NEW_METRICS)
def test_a_reader_finds_nothing_where_nothing_is(config, reader):
    """None, never an exception: an engine from before the counters, a
    run that was not traced, a trace without the operations, a
    configuration of another architecture."""
    read = getattr(readers, reader[:-len(".chat")])
    older = _record(config)
    for side in ("engine", "engine_before"):
        older["worker"][side].pop("conv")
        older["worker"][side].pop("requests", None)
    assert read(older) is None
    if reader in ("conv_mix_time_share.chat",
                  "decode_step_roofline_conv.chat",
                  "prefill_flash_h64_roofline.chat"):   # read the trace
        untraced = {**_record(config), "trace": None}
        assert read(untraced) is None
        empty = _record(config)
        empty["trace"] = {**empty["trace"], "ops": [], "modules": {}}
        assert read(empty) is None
    other = _record(_load("benchmark", "configs", "olmoe-1b-7b-0125-L8.json"))
    for side in ("engine", "engine_before"):
        other["worker"][side].pop("conv")
    assert read(other) is None
