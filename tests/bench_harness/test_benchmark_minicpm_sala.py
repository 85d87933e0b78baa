"""MiniCPM-SALA's configuration (``benchmark/minicpm_sala_*.py``,
``benchmark/readers/blocks.py``): the file against the catalog's row, the
cell and its traffic letter for letter, the builder's refusals, the
counts at the published widths, the float32 reference against the
programs at a tiny size (``minicpm_sala_tiny/config.json``: hidden 64, 4
heads of 16 on 2 KV heads, 8 layers in the published order of kinds,
page 16, ``topk`` 4, ``window_size`` 64, ``dense_len`` 320), a slot taken
again, six single departures from the published model each refused, and
the readers on hand-made records. CPU, no processes."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, flops  # noqa: E402
from benchmark import minicpm_sala_counts as counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import blocks as readers  # noqa: E402

TOL = 1e-4
CELL = "serve-sala-c16-32k"
NEW_METRICS = [
    "block_select_time_share.chat", "block_walk_roofline.chat",
    "linear_step_time_share.chat", "linear_step_roofline.chat",
    "decode_step_roofline_blocks.chat", "prefill_block_sparse_roofline.chat",
    "prefill_linear_roofline.chat", "selected_pages_share.chat",
    "linear_slot_bytes.chat"]


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "minicpm-sala-L12.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "minicpm_sala_tiny", "config.json")


def test_file_holds_the_catalogs_row_and_cuts_the_depth_alone(config):
    mixers = (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
              + ["lightning-attn"] * 6 + ["minicpm4"] * 2
              + ["lightning-attn"] * 4 + ["minicpm4"]
              + ["lightning-attn"] * 6 + ["minicpm4"] * 3)
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "mixer_types": mixers, "num_attention_heads": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
        "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True}
    assert len(mixers) == 32
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 12
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert config["reduced"]["num_hidden_layers"]["published"] == 32
    bench = bench_run.load_benchmark()
    entry, = [c for c in bench["configs"] if c["name"] == "minicpm-sala-L12"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    arch.check_reduced(entry, config)
    # Published layers 16-27: m m L L L L m L L L L L.
    assert config["layers_kept"] == [16, 27]
    assert counts.mixers(config) == (
        ["minicpm4"] * 2 + ["lightning-attn"] * 4 + ["minicpm4"]
        + ["lightning-attn"] * 5)
    assert config["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert all(f"{key}_why" in config["assumed"] for key in (
        "sparse_config", "dense_switch", "qk_norm", "output_norm",
        "lightning_slopes", "exact_logsumexp", "lightning_state"))
    assert set(config["not_read"]) >= {"mup_denominator", "rand_init"}
    assert config["engine"] == {"max_batch": 16, "max_len": 34816,
                                "page_size": 16, "total_pages": 34816}
    assert config["arch"] == {
        "program_config": "benchmark.minicpm_sala_program.sala_config",
        "reference": "benchmark.minicpm_sala_reference",
        "counts": "benchmark.minicpm_sala_counts"}


def test_the_cell_and_its_traffic_are_the_issues():
    bench = bench_run.load_benchmark()
    cell, config, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala-L12", "chat-closed-c16-32k", 1)
    assert len(cell["why"]) <= 200
    # Behind every cell the parent had; pinned to NO last place: the next
    # configuration's PR appends behind this one.
    names = [c["name"] for c in bench["workloads"]]
    assert names.index(CELL) > names.index("serve-ouro-c8-640")
    assert {k: traffic[k] for k in (
        "kind", "concurrency", "clients", "requests", "grace_s",
        "check_requests")} == {
            "kind": "serve", "concurrency": 16, "clients": 16,
            "requests": 96, "grace_s": 5.0, "check_requests": 4}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 24576,
                                 "sigma": 0.2, "min": 18432, "max": 32768}
    assert traffic["output"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 0.25, "min": 768, "max": 2048}
    assert set(traffic) == set(_load("benchmark", "traffic",
                                     "chat-closed-c8-16k.json"))
    engine = config["engine"]
    longest = traffic["prompt"]["max"] + traffic["output"]["max"]
    assert longest == engine["max_len"] == 34816
    assert 16 * longest // engine["page_size"] <= engine["total_pages"]
    # One prefill bucket, and every decode step past dense_len.
    from benchmark import loadgen
    from benchmark.jobs.serve import bucket

    requests = loadgen.schedule(traffic, 2 ** 31 + 5, 51, 73448)
    assert {bucket(len(r["prompt"]), 16, 34816) for r in requests} == {32768}
    assert min(len(r["prompt"]) for r in requests) > config["assumed"][
        "sparse_config"]["dense_len"]
    # The new metrics list this cell and no other, in one run at the end
    # of nothing in particular; no accepted list lost a name or its order.
    new = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "gap_p90_s"
               for m in new)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {"gap_p90_s", "decode_step_device_s_p50.chat",
                      "device_idle_share.chat", "programs_loaded_s.serve",
                      "emit_gap_s_p90.chat", "decode_starved_share.chat"}
    # These count a dense walk: not this cell's.
    assert not listed & {"decode_step_roofline.chat",
                         "decode_step_roofline_counted.chat",
                         "page_walk_roofline.chat"}
    assert all(m["workloads"].index(CELL)
               > m["workloads"].index("serve-mistral7b-chat")
               for m in bench["end_to_end"] + bench["per_layer"]
               if {CELL, "serve-mistral7b-chat"} <= set(m.get("workloads", ())))


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
            cfg.num_heads, cfg.num_kv_heads, cfg.dh, cfg.vocab_size) == (
                4096, 16384, 12, 32, 2, 128, 73448)
    assert (cfg.linear_heads, cfg.linear_head_dim,
            cfg.linear_decay_layers) == (32, 128, (16, 32))
    assert cfg.layer_types == ("full",) * 2 + ("linear",) * 4 + (
        "full",) + ("linear",) * 5
    assert tuple(cfg.block_select) == (32, 16, 64, 1, 2048, 64, 8192)
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert (cfg.logit_divisor, cfg.embed_scale) == (16.0, 12.0)
    assert not cfg.rope_full_layers and cfg.attn_gate
    assert cfg.qk_norm and cfg.qk_norm_per_head
    from ray_tpu.models.llama import kv_layers, layer_runs

    assert [(r.kind, r.n) for r in layer_runs(cfg)] == [
        ("blocks", 2), ("linear", 4), ("blocks", 1), ("linear", 5)]
    assert kv_layers(cfg) == {"full": 3, "linear": 9, "mean": 3}


@pytest.mark.parametrize("change,says", [
    ({"attn_use_rope": True}, "rotary"),
    ({"lightning_nkv": 8}, "MHA"),
    ({"use_output_norm": False}, "use_output_norm"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
])
def test_builder_refuses_what_it_cannot_build(config, change, says):
    with pytest.raises(NotImplementedError, match=says):
        arch.program_config({**config, **change})


def test_counts_at_the_published_widths(config):
    """The issue's arithmetic, in this repo's bytes."""
    c = counts.param_counts(config)
    assert c["linear_layer"] - 2 * 4096 - 3 * 128 == 285_212_672   # 285.21 M
    assert c["sparse_layer"] - 2 * 4096 - 2 * 128 == 253_755_392   # 253.76 M
    assert c["embed"] + c["lm_head"] == 601_686_016                 # 601.7 M
    assert round(2 * c["total"] / 1e9, 2) == 7.86
    whole = {**config, "num_hidden_layers": 32, "layers_kept": [0, 31]}
    assert round(2 * counts.param_counts(whole)["total"] / 1e9, 2) == 18.95
    assert counts.kv_row_bytes(config) == 1024
    assert counts.mean_row_bytes(config) == 512
    assert counts.linear_slot_bytes(config) == 2 * 2 ** 20
    # Held beside the weights for 16 streams of up to 34,816 tokens.
    pages = 34816
    assert round(pages * 16 * 3 * 1024 / 1e9, 2) == 1.71
    assert round(pages * 3 * 512 / 1e6) == 53
    assert round(16 * 9 * counts.linear_slot_bytes(config) / 1e6) == 302
    # A decode step of 16 streams at contexts of 25k: the SELECTED pages,
    # the page means, the states read and written.
    step = counts.decode_step_bytes(config, 16, 16 * 25000)
    weights = 2 * (c["matmul"] + c["norms"])
    assert round(weights / 1e9, 2) == 7.26
    selected = 16 * 3 * 64 * 64 * 1024
    means = 3 * 16 * 25000 / 16 * 512
    states = 16 * 9 * 2 * 2 * 2 ** 20
    assert step == pytest.approx(weights + selected + means + states
                                 + 16 * 4096 * 2)
    assert round(selected / 1e9, 2) == 0.20 and round(states / 1e9, 2) == 0.60
    assert 8.0e9 < step < 8.2e9
    # Before dense_len everything is read; past it topk blocks, flat.
    assert counts.selected_tokens(config, 5000) == 5001
    assert counts.selected_tokens(config, 8192) == 4096
    assert counts.selected_tokens(config, 30000) == 4096
    assert counts.kv_bytes_per_token(config) == 3 * (1024 + 32)


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
        self.jnp = jnp

    def serve(self, slot, seq, prompt_len, bucket, params=None):
        """Prefill ``seq[:prompt_len]`` into ``slot``, then decode the
        rest teacher-forced; the logits at positions ``prompt_len - 1
        ..``, [len(seq) - prompt_len + 1, V]."""
        jnp, params = self.jnp, params or self.params
        pages, tables = self.books.reserve(slot, len(seq), bucket)
        self.cache = self.cache._replace(page_table={
            k: jnp.asarray(v) for k, v in tables.items()})
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt_len] = seq[:prompt_len]
        logits, self.cache, _ = self.prefill(
            params, jnp.asarray(padded), jnp.asarray(prompt_len, jnp.int32),
            self.cache, jnp.asarray(slot, jnp.int32),
            {k: jnp.asarray(v, jnp.int32) for k, v in pages.items()})
        out = [np.asarray(logits)[0]]
        active = np.zeros(self.batch, bool)
        active[slot] = True
        for t in range(prompt_len, len(seq)):
            toks = np.zeros(self.batch, np.int32)
            toks[slot] = seq[t]
            logits, self.cache, _ = self.decode(
                params, jnp.asarray(toks), self.cache, jnp.asarray(active))
            out.append(np.asarray(logits)[slot])
        return np.stack(out)

    def reference(self, seq, departure=None):
        return np.asarray(self.ref.logits(
            self.params, self.jnp.asarray(np.asarray(seq)[None]),
            self.config, departure=departure))[0]


@pytest.fixture(scope="module")
def programs(tiny):
    return _Programs(tiny)


@pytest.fixture(scope="module")
def served(programs):
    """One sequence of 400 tokens: a prompt of 200 in the 256 bucket
    (under ``dense_len`` 320: a causal prefill), then decode steps across
    ``dense_len`` and into a selection of 4 of 7 blocks."""
    seq = np.random.default_rng(0).integers(0, 256, 400).astype(np.int32)
    return seq, programs.serve(1, seq, 200, 256), programs.reference(seq)


def test_prefill_then_decode_equals_the_reference(served):
    seq, got, want = served
    assert np.abs(got - want[199:]).max() < TOL
    assert np.abs(want).max() > 0.5      # and the logits are not nothing


def test_a_prompt_past_dense_len_is_prefilled_under_its_selection(programs):
    """A prompt of 450 in the 512 bucket: the prefill selects a block of
    queries at a time and lays the page means and the open page's sum
    that the decode steps behind it select from."""
    seq = np.random.default_rng(1).integers(0, 256, 480).astype(np.int32)
    got = programs.serve(2, seq, 450, 512)
    assert np.abs(got - programs.reference(seq)[449:]).max() < TOL
    programs.books.release(2)


def test_a_slot_taken_again_keeps_nothing_of_the_request_before(programs,
                                                                served):
    """Slot 1 again, with other pages: the Lightning states, the page
    means and the open page's sum are the new request's alone."""
    programs.books.release(1)
    held = programs.books.reserve(3, 64, 64)     # so the pages differ
    seq = np.random.default_rng(2).integers(0, 256, 360).astype(np.int32)
    got = programs.serve(1, seq, 330, 512)
    assert held is not None
    assert np.abs(got - programs.reference(seq)[329:]).max() < TOL
    programs.books.release(1)
    programs.books.release(3)


@pytest.mark.parametrize("departure", [
    "no_residual_scale", "rope_selected", "no_decay", "topk_halved",
    "no_local_blocks", "no_dense_len"])
def test_a_single_departure_is_refused(programs, served, departure):
    """Each departure from the published model moves the reference's
    logits at the served positions by over ten times the tolerance: the
    comparison that passes above would fail it."""
    seq, got, _ = served
    departed = programs.reference(seq, departure)
    assert np.abs(got - departed[199:]).max() > 10 * TOL


def test_training_this_architecture_raises_by_name(programs):
    import jax.numpy as jnp

    from ray_tpu.models import forward

    with pytest.raises(NotImplementedError, match="'linear'"):
        forward(programs.params, jnp.zeros((1, 8), jnp.int32), programs.cfg)


def test_the_reference_is_float32_and_shares_nothing_with_the_program():
    path = os.path.join(REPO, "benchmark", "minicpm_sala_reference.py")
    with open(path) as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "ray_tpu" not in body and "pallas" not in body
    assert "precision=_HI" in body
    from benchmark import minicpm_sala_reference as ref

    assert set(ref.LOGIT_MARGIN_TOL) == set(ref.LOSS_ATOL) == {
        "bfloat16", "float32"}
    assert ref.LOGIT_MARGIN_TOL["float32"] == TOL
    with pytest.raises(ValueError, match="departure"):
        ref.hidden(None, np.zeros((1, 8), np.int32), {}, departure="other")


def test_the_controls_take_the_programs_place(programs):
    """``control_margins``: the reference itself trails itself by
    nothing; with operands rounded to float8 it does not."""
    import jax.numpy as jnp

    seq = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 192)),
                      jnp.int32)
    ref, config = programs.ref, programs.config
    assert float(ref.control_margins(programs.params, seq, config).max()) == 0
    rounded = ref.control_margins(programs.params, seq, config,
                                  inputs=jnp.float8_e4m3fn)
    assert float(rounded.max()) > 100 * TOL
    # The state rounded to bfloat16 behind every token: a token at a
    # time, and not the same logits (at this size no argmax turns).
    exact, state = (ref.logits(programs.params, seq, config, departure=d)
                    for d in (None, "state_bfloat16"))
    assert float(abs(exact - state).max()) > 10 * TOL


# ---- the readers ----------------------------------------------------------


def _record(config):
    """A window of 100 decode steps of 16 sequences at contexts of 25k,
    of which a trace saw 10, and one prefill of 24,000 tokens."""
    before = {"decode_steps": 10, "decode_slot_steps": 160,
              "blocks": {"pages_read": 0, "pages_held": 0,
                         "steps_dense": 0, "steps_selected": 0,
                         "mean_row_bytes": 512},
              "linear": {"slot_layers": 0, "slot_bytes": 9 * 2 ** 21}}
    after = {"decode_steps": 110, "decode_slot_steps": 1760,
             "blocks": {"pages_read": 1600 * 3 * 256,
                        "pages_held": 1600 * 3 * 1563,
                        "steps_dense": 0, "steps_selected": 1600,
                        "mean_row_bytes": 512},
             "linear": {"slot_layers": 1600 * 9, "slot_bytes": 9 * 2 ** 21},
             "requests": [[0.5, 1.0, 3.0, None, 24000, 32768, i, None]
                          for i in range(16)]}
    trace = {"busy_s": 0.2, "window_s": 0.25,
             "modules": {"decode_step": [0.012] * 10, "prefill": [2.0]},
             "ops": [["pallas_s32_1_1_32_2176", 30, 0.003],
                     ["pallas_bf16_16_2_16_128_bf16_3_2_34816_16_128_"
                      "bf16_3_2_34816_16_128", 30, 0.012],
                     ["pallas_f32_16_2_16_1_128_f32_9_16_32_128_128", 90, 0.04],
                     ["pallas_bf16_32_1_32768_128_f32_32_128_128", 9, 0.09],
                     ["pallas_bf16_2_16_32768_128", 3, 0.3],
                     ["fusion_bf16_16_4096", 100, 0.01]]}
    return {"config": config, "trace": trace,
            "traffic": {"trace_at_s": 36.0},
            "worker": {"engine": after, "engine_before": before,
                       "window_start": 0.0,
                       "device": {"kind": "TPU v5 lite"}}}


def test_block_readers_on_a_hand_counted_window(config):
    record = _record(config)
    peak = flops.peaks("TPU v5 lite")
    assert readers.selected_pages_share(record) == pytest.approx(
        100 * 256 / 1563)
    assert readers.linear_slot_bytes(record) == 9 * 2 ** 21
    assert readers.block_select_time_share(record) == pytest.approx(1.5)
    assert readers.linear_step_time_share(record) == pytest.approx(20.0)
    # Every traced step carried the 16 sequences that held a slot.
    pages, held, states = 10 * 16 * 3 * 256, 10 * 16 * 3 * 1563, 10 * 16 * 9
    assert readers.block_walk_roofline(record) == pytest.approx(
        100 * flops.roofline_s(
            counts.block_walk_flops(config, pages, held),
            counts.block_walk_bytes(config, pages, held), peak) / 0.015)
    assert readers.linear_step_roofline(record) == pytest.approx(
        100 * (2 * states * 2 ** 21 / 819e9) / 0.04)
    step = flops.roofline_s(
        counts.decode_step_flops_blocks(config, 16, pages / 10, held / 10,
                                        states / 10),
        counts.decode_step_bytes_blocks(config, 16, pages / 10, held / 10,
                                        states / 10), peak)
    assert readers.decode_step_roofline_blocks(record) == pytest.approx(
        100 * step / 0.012)
    assert 60 < readers.decode_step_roofline_blocks(record) < 100
    # The prefill's kernels: one prefill of the bucket, 24,000 real tokens.
    assert readers.prefill_linear_roofline(record) == pytest.approx(
        100 * flops.roofline_s(counts.linear_prefill_flops(config, 24000),
                               counts.linear_prefill_bytes(config, 24000),
                               peak) / 0.09)
    assert readers.prefill_block_sparse_roofline(record) == pytest.approx(
        100 * flops.roofline_s(counts.block_prefill_flops(config, 24000),
                               counts.block_prefill_bytes(config, 24000),
                               peak) / 0.3)
    for name in NEW_METRICS:
        share = bench_run.find_reader(name)(record)
        assert share is not None and (name.endswith("bytes.chat")
                                      or 0 < share < 100), name


def test_the_kernels_names_match_no_older_pattern_and_theirs_no_older_kernel():
    from benchmark.readers import (hybrid, latent, moe, smallthinker, sparse,
                                   state, window)

    mine = {readers.BLOCK_SELECT: "pallas_s32_1_1_32_2176",
            readers.BLOCK_WALK: "pallas_bf16_16_2_16_128_bf16_3_2_34816_16_"
                                "128_bf16_3_2_34816_16_128",
            readers.LIGHTNING_STEP:
                "pallas_f32_16_2_16_1_128_f32_9_16_32_128_128",
            readers.LIGHTNING_SCAN:
                "pallas_bf16_32_1_32768_128_f32_32_128_128",
            readers.BLOCK_FLASH: "pallas_bf16_2_16_32768_128"}
    older = {
        window.PAGE_WALK: "pallas_bf16_16_32_128_bf16_8_4_8192_16_128_"
                          "bf16_8_4_8192_16_128",
        window.FLASH: "pallas_bf16_32_8192_128_f32_32_1_8192",
        latent.LATENT_WALK: "pallas_bf16_16_32_512_bf16_4_8192_16_640",
        moe.GROUPED: "pallas_bf16_128_1024",
        smallthinker.STREAMED: "pallas_f32_28_1_16384_bf16_28_16384_128",
        sparse.FOUR_AND_FOUR: "pallas_f32_8_1_64_256_bf16_3_8192_16_128",
        sparse.SELECT_TILES: "pallas_s8_128_32_128_512",
        sparse.SPARSE_FLASH: "pallas_bf16_64_16384_128",
        state.STATE_STEP: "pallas_f32_16_8_5_128_f32_6_16_8_65_136_128",
        state.CHUNK_SCAN: "pallas_bf16_8_5_16384_128_f32_8_65_136_128",
        hybrid.DELTA_STEP: "pallas_f32_16_1_32_128_f32_20_16_32_128_128",
        # The delta rule's prefill (no pattern reads it): three and three.
        None: "pallas_bf16_32_16384_128_f32_32_128_128"}
    for pattern, name in mine.items():
        assert pattern.match(name), name
        assert not any(p.match(name) for p in older if p is not None), name
        assert not any(pattern.match(other) for other in older.values()), name
        assert sum(bool(p.match(name)) for p in mine) == 1, name
    for pattern, name in older.items():
        assert pattern is None or pattern.match(name), name


def _without(record, what):
    worker = record["worker"]
    if what == "no trace":
        return {**record, "trace": None}
    if what == "another trace":
        return {**record, "trace": {**record["trace"], "ops": [
            ["pallas_bf16_16_32_128_bf16_8_4_8192_16_128_bf16_8_4_8192_16_128",
             10, 0.01]]}}
    if what == "an older engine":
        stats = {"decode_steps": 110, "decode_slot_steps": 1760}
        return {**record, "worker": {**worker, "engine": stats,
                                     "engine_before": {
                                         "decode_steps": 10,
                                         "decode_slot_steps": 160}}}
    older = _load("benchmark", "configs", "mistral-7b-v0.3-L16.json")
    return {**record, "config": older}


@pytest.mark.parametrize("what", ["no trace", "another trace",
                                  "an older engine", "another configuration"])
@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_and_says_none(config, name, what):
    """On the parent's program (no ``blocks``, no ``linear``), on a run
    that was not traced, on a trace without the kernels and on another
    configuration's record: None, and no raise."""
    reader = bench_run.find_reader(name)
    value = reader(_without(_record(config), what))
    reads_trace = name not in ("selected_pages_share.chat",
                               "linear_slot_bytes.chat")
    if what == "an older engine" and name == "block_select_time_share.chat":
        return      # a kernel's share needs no counter
    if what == "an older engine" and name == "linear_step_time_share.chat":
        return
    if what == "another configuration" and (
            not reads_trace or name.endswith("time_share.chat")):
        return      # the engine's own counts, whatever the file says
    if what in ("no trace", "another trace") and not reads_trace:
        return
    if what == "another trace" and name == "decode_step_roofline_blocks.chat":
        return      # the whole step against the counters: no kernel's name
    assert value is None, (name, what, value)
