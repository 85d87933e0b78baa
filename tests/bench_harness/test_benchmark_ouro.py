"""Ouro-2.6B's configuration (``benchmark/ouro_*.py``,
``benchmark/readers/loop.py``): the file against the catalog's row, the
cell and its traffic letter for letter, the builder's refusals, the
counts at the published widths, the float32 reference against the
programs at a tiny size (``ouro_tiny/config.json``: hidden 64, 4 heads
of 16, 3 layers run 4 times, a pool 12 layers deep, page 16), a slot
taken again, six single departures from the published model each refused
a hundred times over, and the readers on hand-made records. CPU, no
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

from benchmark import arch, flops, ouro_counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import engine as engine_readers  # noqa: E402
from benchmark.readers import loop as readers  # noqa: E402
from benchmark.readers import trace as trace_readers  # noqa: E402
from benchmark.readers import window as window_readers  # noqa: E402

TOL = 1e-4
CELL = "serve-ouro-c8-640"


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "ouro-2.6b.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "ouro_tiny", "config.json")


def test_file_holds_the_catalogs_row_and_cuts_nothing(config):
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == {}
    bench = bench_run.load_benchmark()
    entry, = [c for c in bench["configs"] if c["name"] == "ouro-2.6b"]
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    arch.check_reduced(entry, config)
    assert set(config["assumed"]) >= {
        "sandwich_norms", "norm_between_passes", "kv_cache_index",
        "exit_gate", "gate_bias", "attention_bias", "rotary"}
    # What the file assumes comes with its reason.
    assert all(f"{key}_why" in config["assumed"] for key in (
        "sandwich_norms", "norm_between_passes", "kv_cache_index",
        "exit_gate", "attention_bias", "rotary"))
    assert (config["assumed"]["attention_bias"],
            config["assumed"]["gate_bias"]) == (False, True)
    engine = config["engine"]
    assert (engine["max_batch"], engine["max_len"], engine["page_size"]) == (
        8, 640, 16)
    assert engine["total_pages"] >= 320
    assert config["arch"] == {
        "program_config": "benchmark.ouro_program.ouro_config",
        "reference": "benchmark.ouro_reference",
        "counts": "benchmark.ouro_counts"}


def test_the_cell_and_its_traffic_are_the_issues():
    bench = bench_run.load_benchmark()
    cell, config, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "chat-closed-c8-640", 1)
    # Behind every cell the parent had; NOT pinned to the last place: the
    # next configuration's PR appends behind this one and may edit no
    # file here (tests/bench_harness/test_benchmark_kimi.py pins Kimi's
    # cell to the end of each list, and fails by construction since).
    names = [c["name"] for c in bench["workloads"]]
    assert names.index(CELL) > names.index("serve-kimilinear-c16-8k")
    assert {k: traffic[k] for k in (
        "kind", "concurrency", "clients", "requests", "grace_s",
        "check_requests", "trace_at_s")} == {
            "kind": "serve", "concurrency": 8, "clients": 8, "requests": 96,
            "grace_s": 5.0, "check_requests": 4, "trace_at_s": 20.0}
    assert traffic["prompt"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.5, "min": 48, "max": 256}
    assert traffic["output"] == {"dist": "lognormal", "median": 320,
                                 "sigma": 0.15, "min": 256, "max": 384}
    # The same keys as the closed cell beside it.
    assert set(traffic) == set(_load("benchmark", "traffic",
                                     "chat-closed-c8-16k.json"))
    # The longest request fills a slot's table, eight of them the pool.
    engine = config["engine"]
    longest = traffic["prompt"]["max"] + traffic["output"]["max"]
    assert longest == engine["max_len"] == 640
    assert 8 * longest // engine["page_size"] <= engine["total_pages"]
    # The schedule's buckets are the three the warm-up loads.
    from benchmark import loadgen
    from benchmark.jobs.serve import bucket

    requests = loadgen.schedule(traffic, 2 ** 31 + 5, 51, 49152)
    assert {bucket(len(r["prompt"]), 16, 640) for r in requests} == {
        64, 128, 256}
    # The new metrics list this cell and no other; no accepted metric's
    # list lost a name or its order.
    new = [m for m in bench["per_layer"] if m["name"].startswith("loop_")]
    assert [m["name"] for m in new] == [
        "loop_passes_per_step.chat", "loop_kv_token_bytes.chat",
        "loop_exit_pass_mean.chat", "loop_weight_bytes_share.chat"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "gap_p90_s"
               and m["layer"] == "pass loop" for m in new)
    at = bench["per_layer"].index(new[0])
    assert bench["per_layer"][at:at + 4] == new
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= {"gap_p90_s", "decode_step_roofline.chat",
                      "decode_step_roofline_counted.chat",
                      "page_walk_roofline.chat",
                      "decode_step_device_s_p50.chat",
                      "device_idle_share.chat", "programs_loaded_s.serve"}
    # The traced second holds no prefill (the traffic file's
    # trace_seconds_why): the cell is on no list that reads one.
    assert not listed & {"prefill_device_s_p50.chat",
                         "prefill_flash_roofline.chat"}
    assert all(m["workloads"].index(CELL)
               > m["workloads"].index("serve-mistral7b-chat")
               for m in bench["end_to_end"] + bench["per_layer"]
               if {CELL, "serve-mistral7b-chat"} <= set(m.get("workloads", ())))


def test_builder_takes_each_key_by_name(config):
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
            cfg.num_heads, cfg.num_kv_heads, cfg.dh, cfg.vocab_size) == (
                2048, 5632, 48, 16, 16, 128, 49152)
    assert (cfg.rope_theta, cfg.rms_eps) == (1e6, 1e-6)
    assert (cfg.passes, cfg.exit_gate, cfg.exit_threshold,
            cfg.post_norms) == (4, True, 1.0, True)
    assert cfg.layer_types is None and cfg.n_experts == 0
    assert str(cfg.dtype) == "bfloat16"


@pytest.mark.parametrize("change,says", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"use_sliding_window": True, "sliding_window": 4096}, "sliding window"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"early_exit_threshold": 0.9}, "early_exit_threshold"),
])
def test_builder_refuses_what_it_cannot_build(config, change, says):
    with pytest.raises(NotImplementedError, match=says):
        arch.program_config({**config, **change})


def test_counts_at_the_published_widths(config):
    sizes = ouro_counts.param_counts(config)
    assert sizes["attn"] == 4 * 2048 * 2048 == 16_777_216
    assert sizes["layer_matmul"] == 16_777_216 + 34_603_008
    assert sizes["layer"] == 51_388_416
    assert 48 * sizes["layer"] == 2_466_643_968
    assert sizes["embed"] + sizes["lm_head"] == 201_326_592
    assert sizes["gate"] == 2_049
    assert sizes["total"] == 2_667_974_657
    assert round(sizes["total"] * 2 / 1e9, 2) == 5.34
    assert sizes["matmul"] == 4 * (48 * sizes["layer_matmul"] + 2048) \
        + 2048 * 49152
    assert ouro_counts.kv_row_bytes(config) == 8_192
    assert ouro_counts.kv_bytes_per_token(config) == 1_572_864
    assert 16 * ouro_counts.kv_bytes_per_token(config) == 25_165_824
    assert round(320 * 25_165_824 / 1e9, 2) == 8.05
    # A decode step at 8 sequences holding 2,600 tokens: the layers four
    # times, the head once, the rows held; ~24 GB, ~29 ms on a v5e.
    weights = ouro_counts.decode_step_weight_bytes(config)
    assert weights["later_passes"] == 3 * weights["first_pass"]
    assert round(4 * weights["first_pass"] / 1e9, 2) == 19.73
    step = ouro_counts.decode_step_bytes(config, 8, 2600)
    assert step == (sum(weights.values()) + 2600 * 1_572_864
                    + 8 * 2048 * 2)
    assert round(step / 1e9, 1) == 24.0
    peak = flops.peaks("TPU v5 lite")
    least = flops.roofline_s(ouro_counts.decode_step_flops(config, 8, 2600),
                             step, peak)
    assert 0.028 < least < 0.031
    assert ouro_counts.decode_step_flops(config, 8, 2600) == (
        2 * sizes["matmul"] * 8 + 4 * 192 * 2600 * 2048)
    # One pass's flash calls; a prefill makes four times as many.
    assert ouro_counts.flash_prefill_flops(config, 256) == (
        4 * 48 * 2048 * 256 * 257 // 2)
    assert ouro_counts.flash_prefill_bytes(config, 256) == (
        48 * 64 * 256 * 128 * 2)
    assert 5.0e12 < ouro_counts.prefill_flops(config, 256) < 5.2e12
    assert "jax" not in {m.split(".")[0] for m in vars(ouro_counts)
                         if isinstance(vars(ouro_counts)[m], type(os))}


def test_the_program_holds_what_the_counts_say(config):
    import jax
    from ray_tpu.models import init_params
    from ray_tpu.models.generation import PagedKVCache
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = arch.program_config(config)
    assert [tuple(r) for r in layer_runs(cfg)] == [(0, 48, False, "full", 0)]
    assert kv_layers(cfg) == {"full": 192}
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["layers"]["wq"].shape == (48, 2048, 16, 128)
    assert (shapes["exit_w"].shape, shapes["exit_b"].shape) == ((2048, 1),
                                                                (1,))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == ouro_counts.param_counts(config)["total"]
    engine = config["engine"]
    sizes = PagedKVCache.sizes(cfg, 8, engine["total_pages"], 16, 40)
    assert sizes == {"full": (192, engine["total_pages"], 40)}


# ---- the programs against the reference ------------------------------------

def _tiny_model(tiny, seed=3, **changes):
    import jax
    from ray_tpu.models import init_params

    cfg = dataclasses.replace(arch.program_config(tiny), **changes)
    full = arch.program_config(tiny)
    return cfg, init_params(full, jax.random.PRNGKey(seed))


def _program_outputs(cfg, params, seqs, prompt_lens, steps, first=None,
                     before_decode=None, page=16):
    """Each sequence's prompt through ``paged_prefill`` into a slot of
    its own, then ``steps`` teacher-forced ``paged_decode`` steps with
    every slot live, slots at different lengths: ({slot: logits
    [1 + steps, V]}, {slot: exit distributions [1 + steps, passes]}) at
    the positions the programs computed. ``first``: a sequence that
    slot 0 serves before (prefill and four decode steps), in the pages
    the next request of slot 0 then takes. ``before_decode()``: called
    after the last prefill and before the decode program is traced."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.generation import (
        PagedKVCache, paged_decode, paged_prefill)

    prefill = jax.jit(lambda params, tokens, n, cache, slot, pages:
                      paged_prefill(params, tokens, n, cache, cfg, slot, pages))
    slots = len(seqs) + 1                       # the last one stays idle
    per_seq = 256 // page
    cache = PagedKVCache.create(cfg, slots, slots * per_seq, page, per_seq)
    sizes = PagedKVCache.sizes(cfg, slots, slots * per_seq, page, per_seq)
    (_, pool, columns), = sizes.values()
    table = np.zeros((slots, columns), np.int32)

    def prefill_into(cache, slot, seq, n):
        bucket = page
        while bucket < n:
            bucket *= 2
        # The slot's pages, from the pool's end and out of order.
        ids = (pool - 1 - slot * columns - np.arange(columns))[::-1]
        table[slot] = ids
        cache = cache._replace(page_table={"full": jnp.asarray(table)})
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = seq[:n]
        return prefill(params, jnp.asarray(padded), jnp.asarray(n, jnp.int32),
                       cache, slot, {"full": jnp.asarray(ids[:bucket // page])})

    def decode_fn():
        return jax.jit(lambda params, last, cache, active:
                       paged_decode(params, last, cache, cfg, active=active))

    if first is not None:
        _, cache, *_ = prefill_into(cache, 0, first, len(first) - 4)
        only = jnp.asarray(np.arange(slots) == 0)
        step = decode_fn()
        for tok in first[-4:]:
            last = np.zeros(slots, np.int32)
            last[0] = tok
            _, cache, *_ = step(params, jnp.asarray(last), cache, only)
    logits, exits = {}, {}
    for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
        out, cache, _, *p = prefill_into(cache, slot, seq, n)
        logits[slot] = [np.asarray(out)[0]]
        exits[slot] = [np.asarray(p[0])[0]] if p else []
    if before_decode is not None:
        before_decode()
    decode = decode_fn()
    active = jnp.asarray(np.arange(slots) < len(seqs))
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        for slot, (seq, n) in enumerate(zip(seqs, prompt_lens)):
            last[slot] = seq[n + i]
        out, cache, _, *p = decode(params, jnp.asarray(last), cache, active)
        for slot in logits:
            logits[slot].append(np.asarray(out)[slot])
            if p:
                exits[slot].append(np.asarray(p[0])[slot])
    return ({slot: np.stack(rows) for slot, rows in logits.items()},
            {slot: np.stack(rows) for slot, rows in exits.items() if rows})


def _worst_differences(tiny, cfg, params, prompt_lens, steps, **how):
    """(logits, exit distributions): the programs' largest departure
    from the reference's full forward over every position computed."""
    import jax
    import jax.numpy as jnp

    reference = arch.reference(tiny)
    rng = np.random.RandomState(sum(prompt_lens))
    seqs = [rng.randint(0, 256, n + steps) for n in prompt_lens]
    logits, exits = _program_outputs(cfg, params, seqs, prompt_lens, steps,
                                     **how)
    # One forward of the reference for all of them, padded behind their
    # ends: a causal model's logits do not see what follows.
    padded = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for row, seq in zip(padded, seqs):
        row[:len(seq)] = seq
    want, want_p = jax.jit(lambda params, tokens: (
        reference.logits(params, tokens, tiny),
        reference.exit_distribution(params, tokens, tiny)))(
            params, jnp.asarray(padded))
    want, want_p = np.asarray(want), np.asarray(want_p)
    spans = {slot: slice(n - 1, n + steps)
             for slot, n in enumerate(prompt_lens)}
    return (max(np.abs(logits[s] - want[s, spans[s]]).max() for s in spans),
            max((np.abs(exits[s] - want_p[s, spans[s]]).max()
                 for s in exits), default=None))


def test_prefill_then_decode_equals_the_reference(tiny):
    """Prompts of 10, 25, 40 and 100 tokens (buckets 16, 32, 64, 128) in
    one batch whose slots are at different lengths, 40 decode steps,
    every pass writing and walking its own three layers of the pool:
    every logit within 1e-4 of the reference's full forward, and the
    exit distribution the programs return within 1e-5 of the
    reference's."""
    cfg, params = _tiny_model(tiny)
    logits, exits = _worst_differences(tiny, cfg, params, (10, 25, 40, 100),
                                       40)
    assert logits < TOL
    assert exits < 1e-5


def test_the_exit_distribution_is_one_and_not_degenerate(tiny):
    """With the seeded gate a token's probabilities over the four passes
    sum to 1, every pass has some, and the mean pass a token would leave
    at is well inside 1..4 (the cell reads ~1.9)."""
    import jax
    import jax.numpy as jnp

    _, params = _tiny_model(tiny)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 64)))
    p = np.asarray(jax.jit(lambda params, tokens: arch.reference(
        tiny).exit_distribution(params, tokens, tiny))(params, tokens))
    assert p.shape == (2, 64, 4)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    assert (p.mean((0, 1)) > 0.02).all()
    assert 1.3 < (p * np.arange(1, 5)).sum(-1).mean() < 3.0


def test_a_slot_taken_again_keeps_nothing_of_the_request_before(tiny):
    """Slot 0 first serves a sequence of 150 tokens (prefill and four
    decode steps), then is taken by a prompt of 9 in the same pages: in
    none of the 4 x 3 pool layers does the new request read a row of
    the old one."""
    cfg, params = _tiny_model(tiny)
    first = np.random.RandomState(5).randint(0, 256, 150)
    logits, exits = _worst_differences(tiny, cfg, params, (9, 2, 40), 12,
                                       first=first)
    assert logits < TOL and exits < 1e-5


def _patches(monkeypatch, params, eps):
    """The departures that are no setting of the program: each a
    function that changes ``ray_tpu.models.generation`` for the programs
    traced after it."""
    import jax.numpy as jnp
    from ray_tpu.models import generation, llama

    norm, passes_of = generation.rms_norm, generation._passes

    def no_norm_between_passes():
        # The norm behind every pass left out, and one put behind the last.
        def once(cfg, one_pass, x, pools):
            x, *rest = passes_of(cfg, one_pass, x, pools)
            return (norm(x, params["final_norm"], eps), *rest)

        monkeypatch.setattr(generation, "rms_norm", lambda x, w, eps: x)
        monkeypatch.setattr(generation, "_passes", once)

    def one_pool_for_all_passes():
        monkeypatch.setattr(generation, "kv_layers_a_pass",
                            lambda cfg: {"full": 0})

    def the_pass_befores_pool():
        def shifted(cfg, one_pass, x, pools):
            return passes_of(cfg, lambda t, *carry: one_pass(
                (t + cfg.passes - 1) % cfg.passes, *carry), x, pools)

        monkeypatch.setattr(generation, "_passes", shifted)

    def gate_before_the_norm():
        seen = []

        def spy(x, w, eps):
            seen.append(x)
            return norm(x, w, eps)

        monkeypatch.setattr(generation, "rms_norm", spy)
        monkeypatch.setattr(
            generation, "_exit_gate",
            lambda cfg, params, h: llama.exit_gate_logit(
                params, seen[-1][:, 0].astype(jnp.float32)))

    return {"no-norm-between-passes": no_norm_between_passes,
            "one-pass's-kv-read-by-all": one_pool_for_all_passes,
            "pass-t-reads-pass-t-1's-pool": the_pass_befores_pool,
            "gate-read-before-the-norm": gate_before_the_norm}


DEPARTURES = {
    # name: (config changes, patch by name, whether both programs or the
    # decode program alone are built under it, what is compared)
    "three-passes-for-four": ({"passes": 3, "exit_gate": False}, None,
                              "both", "logits"),
    "no-norm-between-passes": ({}, "no-norm-between-passes", "both",
                               "logits"),
    "one-pass's-kv-read-by-all": ({}, "one-pass's-kv-read-by-all", "both",
                                  "logits"),
    "pass-t-reads-pass-t-1's-pool": ({}, "pass-t-reads-pass-t-1's-pool",
                                     "decode", "logits"),
    "no-norm-behind-attention-and-ffn": ({"post_norms": False}, None,
                                         "both", "logits"),
    "gate-read-before-the-norm": ({}, "gate-read-before-the-norm", "decode",
                                  "exits"),
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES))
def test_a_single_departure_fails_a_hundred_times_over(tiny, departure,
                                                       monkeypatch):
    """Each way the programs could leave the published model moves a
    logit (the gate's: the exit distribution) by at least 100 x the
    tolerance it is held to."""
    changes, patch, where, compared = DEPARTURES[departure]
    cfg, params = _tiny_model(tiny, **changes)
    apply = _patches(monkeypatch, params, cfg.rms_eps).get(patch)
    how = {}
    if apply and where == "both":
        apply()
    elif apply:
        how["before_decode"] = apply
    logits, exits = _worst_differences(tiny, cfg, params, (40,), 6, **how)
    assert (logits if compared == "logits" else exits) > 100 * TOL


def test_training_this_architecture_raises_by_name(tiny):
    import jax.numpy as jnp
    from ray_tpu.models import causal_lm_loss

    cfg, params = _tiny_model(tiny)
    with pytest.raises(NotImplementedError, match="looped model"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)


def test_the_reference_is_float32_and_shares_nothing_with_the_program():
    import inspect

    reference = arch.reference(_load("benchmark", "configs",
                                     "ouro-2.6b.json"))
    source = inspect.getsource(reference)
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in source
    assert "lax.scan" in source and "for _ in range(config[" in source
    assert set(reference.LOGIT_MARGIN_TOL) == {"bfloat16", "float32"}


def test_the_float8_control_rounds_every_matmul_input(tiny):
    """The control that sets the tolerance: with every matmul input
    rounded to float8_e4m3 the reference's own first token trails its
    float32 best by far more than the float32 tolerance, and with
    float32 inputs by nothing."""
    import jax
    import jax.numpy as jnp

    reference = arch.reference(tiny)
    _, params = _tiny_model(tiny)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 256, (1, 96)))
    control = jax.jit(lambda params, tokens, inputs=None: (
        reference.control_margins(params, tokens, tiny, inputs)),
        static_argnums=2)
    assert float(control(params, tokens, jnp.float32).max()) == 0.0
    assert float(control(params, tokens, jnp.float8_e4m3fn).max()) > 100 * TOL


# ---- the readers on hand-made records --------------------------------------

def _record(config, engine=None, before=None, trace=None, traffic=None,
            samples=()):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return {"config": config, "trace": trace, "traffic": traffic or {},
            "client": {"samples": list(samples)},
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


WALK = "pallas_bf16_8_16_128_bf16_192_16_320_16_128_bf16_192_16_320_16_128"
FLASH = "pallas_bf16_16_256_128_f32_16_1_256"


def _traced(config):
    """Ten decode steps of 8 sequences holding 2,600 tokens, three of
    them and one whole prefill of 200 tokens (bucket 256: 192 flash
    calls) in the trace; all times made up."""
    loop = {"passes": 40, "exit_tokens": 81, "exit_pass_sum": 81 * 1.9,
            "kv_token_bytes": 1_572_864}
    after = {"decode_steps": 10, "prefills": 1, "decode_slot_steps": 80,
             "decode_kv_tokens": 10 * 2600,
             "decode_kv_rows_read": 10 * 192 * 2600,
             "requests": [[101.0, 101.0, 101.2, None, 200, 256]],
             "loop": loop}
    before = {**{k: 0 for k in after if k not in ("requests", "loop")},
              "requests": [],
              "loop": {**dict.fromkeys(loop, 0),
                       "kv_token_bytes": 1_572_864}}
    trace = {"busy_s": 0.1, "window_s": 0.2,
             "modules": {"decode_step": [0.034, 0.032, 0.033],
                         "prefill": [0.045]},
             "ops": [[WALK, 3 * 192, 3 * 0.008], [FLASH, 192, 0.004],
                     ["fusion_bf16_8_2048", 9, 0.004]]}
    traffic = {"trace_at_s": 20.0, "trace_seconds": 1.0}
    samples = [{"token_s": [10.0 + 0.03 * i for i in range(650)],
                "prompt_len": 0}] * 8
    return _record(config, after, before, trace, traffic, samples)


def test_loop_readers_on_a_hand_counted_window(config):
    record = _traced(config)
    assert readers.loop_passes_per_step(record) == 4.0
    assert readers.loop_kv_token_bytes(record) == 1_572_864 \
        == ouro_counts.kv_bytes_per_token(config)
    assert readers.loop_exit_pass_mean(record) == pytest.approx(1.9)
    weights = ouro_counts.decode_step_weight_bytes(config)
    step = ouro_counts.decode_step_bytes(config, 8, 2600)
    assert readers.loop_weight_bytes_share(record) == pytest.approx(
        100 * weights["later_passes"] / step)
    assert 60 < readers.loop_weight_bytes_share(record) < 63


def test_the_accepted_readers_this_cell_joins_on_a_hand_made_trace(config):
    """The three device-trace readers the cell joins (and the flash
    prefill's), each against the counts by hand: every one counts four
    passes and none passes 100%."""
    record = _traced(config)
    peak = flops.peaks("TPU v5 lite")
    step = flops.roofline_s(ouro_counts.decode_step_flops(config, 8, 2600),
                            ouro_counts.decode_step_bytes(config, 8, 2600),
                            peak)
    assert engine_readers.decode_step_roofline_counted(record) == \
        pytest.approx(100 * step / 0.033)
    # The client's samples: 8 streams open through the traced second,
    # each counted at half the tokens it streamed, 325.
    sampled = trace_readers.decode_step_roofline(record)
    held = 8 * 650 / 2
    assert sampled == pytest.approx(100 * flops.roofline_s(
        ouro_counts.decode_step_flops(config, 8, held),
        ouro_counts.decode_step_bytes(config, 8, held), peak) / 0.033)
    rows = 3 * 192 * 2600
    assert window_readers.page_walk_roofline(record) == pytest.approx(
        100 * (rows * 8192 / peak["hbm_bytes_per_s"]) / 0.024)
    # 192 calls are one prefill: four times one pass's 48.
    assert window_readers.prefill_flash_roofline(record) == pytest.approx(
        100 * 4 * flops.roofline_s(
            ouro_counts.flash_prefill_flops(config, 200),
            ouro_counts.flash_prefill_bytes(config, 200), peak) / 0.004)
    for value in (engine_readers.decode_step_roofline_counted(record),
                  sampled, window_readers.page_walk_roofline(record),
                  window_readers.prefill_flash_roofline(record)):
        assert 0 < value < 100


ALL_READERS = [
    (readers, "loop_passes_per_step"), (readers, "loop_kv_token_bytes"),
    (readers, "loop_exit_pass_mean"), (readers, "loop_weight_bytes_share"),
    (engine_readers, "decode_step_roofline_counted"),
    (trace_readers, "decode_step_roofline"),
    (window_readers, "page_walk_roofline"),
    (window_readers, "prefill_flash_roofline")]


@pytest.mark.parametrize("module,name", ALL_READERS,
                         ids=[name for _, name in ALL_READERS])
def test_a_reader_finds_nothing_and_says_none(config, module, name):
    """An engine without ``stats()["loop"]`` (every model of one pass,
    the parent's program), a ``loop`` without the gauge or the gate's
    sums, an untraced run, a trace without the kernel, a window without
    a decode step: None each time, no raise."""
    reader = getattr(module, name)
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {},
            "ops": [["fusion_bf16_32_2048", 9, 0.004]]}
    old_engine = {"decode_steps": 3, "decode_kv_tokens": 9,
                  "decode_slot_steps": 3, "decode_kv_rows_read": 9,
                  "requests": []}
    still = traced["worker"]["engine_before"]
    records = [
        _record(config, traffic=traced["traffic"]),
        _record(config, old_engine, old_engine, traffic=traced["traffic"]),
        _record(config, old_engine, old_engine, bare, traced["traffic"]),
    ]
    if name != "loop_kv_token_bytes":
        # A window in which nothing was decoded (a gauge needs none).
        records.append(_record(config, still, still, bare,
                               traced["traffic"]))
    if module is readers:
        # The counters of a looped model of another kind: passes and
        # nothing of a gate, nor the gauge.
        mute = {**old_engine, "loop": {"passes": 4}}
        if name in ("loop_kv_token_bytes", "loop_exit_pass_mean"):
            records.append(_record(config, {**mute, "decode_steps": 4},
                                   mute, traffic=traced["traffic"]))
    else:
        # The counters are there; the run was not traced, or its trace
        # holds neither the programs nor the kernels.
        records.append(_record(config, traced["worker"]["engine"], still,
                               None, traced["traffic"],
                               traced["client"]["samples"]))
        records.append(_record(config, traced["worker"]["engine"], still,
                               bare, traced["traffic"],
                               traced["client"]["samples"]))
    for record in records:
        assert reader(record) is None
