"""Brumby's configuration (``benchmark/brumby_*.py``,
``benchmark/readers/state.py``): the file against the catalog's row, the
counts against hand arithmetic at the published widths, the float32
reference (the attention form) against the program (the recurrent form,
through the engine's cache) at a tiny size (``brumby_tiny/config.json``:
hidden 64, 4 query heads on 2 KV heads of 16, 3 retention layers, page
16), single departures each refused, the bfloat16-state control, the new
readers on hand-made records. CPU, no processes."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import arch, brumby_counts  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.readers import state as readers  # noqa: E402

TOL = 1e-3
CELL = "serve-brumby-c16-8k"
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def _load(*path):
    with open(os.path.join(REPO, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load("benchmark", "configs", "brumby-14b-base-L6.json")


@pytest.fixture(scope="module")
def tiny():
    return _load("tests", "bench_harness", "brumby_tiny", "config.json")


def test_file_holds_the_catalogs_row_and_cuts_depth_alone(config):
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == {"num_hidden_layers"} == set(config["reduced"])
    assert config["reduced"]["num_hidden_layers"]["published"] == 40
    assert config["num_hidden_layers"] == 6
    # Everything the program needed beyond the row is assumed, each
    # with its reason; what is kept and read by nothing is named.
    assumed = config["assumed"]
    stated = {k for k in assumed if not k.endswith("_why")}
    assert stated == {"power_degree", "gate", "gate_bias", "qk_norm",
                      "rotary", "scale", "normaliser", "state",
                      "switch_over"}
    assert all(assumed[k + "_why"] for k in stated)
    assert set(config["not_read"]) == {
        "model_type", "max_window_layers", "sliding_window",
        "use_sliding_window", "max_position_embeddings"}
    assert config["engine"] == {"max_batch": 16, "max_len": 16384,
                                "page_size": 16, "total_pages": 1}
    assert config["dtype"] == "bfloat16"


def test_the_cell_is_the_issues_letter_for_letter():
    bench = bench_run.load_benchmark()
    cell, _, traffic = bench_run.load_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-base-L6", "chat-closed-c16-8k", 1)
    assert (traffic["kind"], traffic["concurrency"], traffic["clients"],
            traffic["requests"]) == ("serve", 16, 16, 192)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.4, "min": 4096, "max": 15360}
    assert traffic["output"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.25, "min": 512, "max": 1024}
    # ISSUE 45's numbers but for the traced part: 4 s and not 3, so that
    # it holds a whole prefill (the traffic file's ``trace_seconds_why``).
    assert (traffic["grace_s"], traffic["check_requests"],
            traffic["trace_at_s"], traffic["trace_seconds"]) == (5, 4, 20, 4)
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert {n: (m["unit"], m["source"], m["layer"], m["moves"])
            for n, m in mine.items()} == {
        "state_walk_time_share.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "state_walk_roofline.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "decode_step_roofline_state.chat":
            ("%", "device_trace", "decode program", "gap_p90_s"),
        "prefill_retention_roofline.chat":
            ("%", "device_trace", "prefill program", "gap_p90_s"),
        "state_slot_bytes.chat":
            ("bytes", "program_counter", "kv cache manager", "gap_p90_s")}
    # Neither of the two layers whose metric lists other tests pin.
    assert not {m["layer"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())} & {
        "engine host loop", "stream path: the way back"} - {
        m["layer"] for m in bench["per_layer"]
        if m["name"] == "batch_occupancy.chat"}
    # The warm-up loads the buckets 4096 to 16384 whatever the seed.
    from benchmark import loadgen
    from benchmark.jobs import serve

    requests = loadgen.schedule(traffic, 2 ** 31 + 5, 51.0, 151936)
    assert sorted({serve.bucket(len(r["prompt"]), 16, 16384)
                   for r in requests}) == [4096, 8192, 16384]
    assert max(len(r["prompt"]) + r["max_new_tokens"]
               for r in requests) <= 16384


def test_builder_takes_each_key_by_name(config):
    import jax.numpy as jnp

    cfg = arch.program_config(config)
    assert (cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size,
            cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.dh) == (
        151936, 5120, 17408, 6, 40, 8, 128)
    assert (cfg.rope_theta, cfg.rms_eps, cfg.dtype) == (
        1e6, 1e-6, jnp.bfloat16)
    assert cfg.layer_types == ("state",) * 6 and cfg.retention
    assert (cfg.qk_norm, cfg.qk_norm_per_head, cfg.attn_gate) == (
        True, True, False)
    assert cfg.sliding_window is None and cfg.n_experts == 0
    for key, value in (("rope_scaling", {"factor": 2}),
                       ("sliding_window", 4096),
                       ("use_sliding_window", True),
                       ("attention_bias", True)):
        with pytest.raises(NotImplementedError):
            arch.program_config({**config, key: value})


def test_counts_against_hand_arithmetic(config):
    counts = brumby_counts.param_counts(config)
    q_o, k_v = 2 * 5120 * 40 * 128, 2 * 5120 * 8 * 128
    ffn, gate = 3 * 5120 * 17408, 5120 * 8
    assert (q_o, k_v, ffn, gate) == (52_428_800, 10_485_760, 267_386_880,
                                     40_960)
    assert counts["layer"] == q_o + k_v + ffn + gate == 330_342_400
    norms_a_layer = 2 * 5120 + 2 * 128 + 8
    assert counts["norms"] == 6 * norms_a_layer + 5120
    # 330.35 M a layer with its norms; 7.08 GB of bf16 in all.
    assert round((counts["layer"] + norms_a_layer) / 1e6, 2) == 330.35
    assert counts["embed"] == counts["lm_head"] == 151936 * 5120
    assert counts["total"] == 6 * counts["layer"] + 2 * 777_912_320 + \
        counts["norms"]
    assert round(2 * counts["total"] / 1e9, 2) == 7.08
    # The program's tree holds exactly these.
    import jax

    from ray_tpu.models import init_params
    from ray_tpu.models.llama import num_params

    cfg = arch.program_config(config)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert num_params(shapes) == counts["total"]
    # The state: 8 KV heads x 8,256 pairs x (128 + 1) float32.
    assert brumby_counts.state_width(config) == 8256
    assert brumby_counts.state_slot_bytes(config) == 8 * 8256 * 129 * 4 \
        == 34_080_768
    assert brumby_counts.kv_bytes_per_token(config) == 0
    # A step of 16 sequences: 6.54 GB of state beside 5.52 GB of weights,
    # whatever the contexts; 54% of the bytes.
    step = brumby_counts.decode_step_bytes(config, 16, 0)
    assert step == brumby_counts.decode_step_bytes(config, 16, 160_000)
    walk = brumby_counts.state_walk_bytes(config, 16 * 6)
    assert walk == 2 * 96 * 34_080_768
    weights = 2 * (counts["matmul"] + counts["norms"])
    assert step == weights + walk + 16 * 5120 * 2
    assert 0.54 < walk / step < 0.55
    assert brumby_counts.decode_step_flops(config, 16, 0) == \
        brumby_counts.decode_step_flops(config, 16, 99) == \
        2 * counts["matmul"] * 16 + 96 * 8256 * 129 * (3 * 8 + 2 * 40)
    # A prefill token: 0.6 GFLOP of retention beside 5.5 of matmuls.
    per_token = brumby_counts.retention_prefill_flops(config, 8192) / 8192
    assert per_token == 6 * (2 * 8256 * 129 * 48 + 4 * 128 * 40 * 257 / 2)
    assert 0.6e9 < per_token < 0.65e9
    assert brumby_counts.retention_prefill_bytes(config, 8192) == 6 * (
        8192 * (96 * 128 * 2 + 8 * 4) + 34_080_768)


def _programs(tiny):
    import jax

    from ray_tpu.models import init_params

    cfg = arch.program_config(tiny)
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


def _served_logits(cfg, params, tokens, prompt, bucket, slot, batch=3):
    """Logits at positions ``prompt - 1 ..`` of ``tokens``: a prefill of
    the first ``prompt`` into ``slot``, then the rest a decode step at a
    time, the other slots idle."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generation import (PagedKVCache, paged_decode,
                                           paged_prefill)

    cache = PagedKVCache.create(cfg, batch, 1, 16, 16)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt] = tokens[:prompt]
    first, cache, _ = paged_prefill(
        params, jnp.asarray(padded), jnp.int32(prompt), cache, cfg,
        jnp.int32(slot), {"state": jnp.zeros((0,), jnp.int32)})
    active = jnp.arange(batch) == slot
    step = jax.jit(lambda tok, cache: paged_decode(
        params, tok, cache, cfg, active=active)[:2])
    rows = [first[0]]
    for tok in tokens[prompt:-1]:
        logits, cache = step(jnp.full((batch,), tok, jnp.int32), cache)
        rows.append(logits[slot])
    return np.stack(rows)


@pytest.mark.parametrize("bucket", [32, 64])
def test_prefill_then_decode_equals_the_reference(tiny, bucket):
    import jax.numpy as jnp

    cfg, params = _programs(tiny)
    reference = arch.reference(tiny)
    tokens = np.random.default_rng(5).integers(0, 256, 45)
    want = np.asarray(reference.logits(
        params, jnp.asarray(tokens[None, :-1]), tiny))[0, 20:]
    got = _served_logits(cfg, params, tokens, 21, bucket, slot=1)
    assert got.shape == want.shape == (24, 256)
    assert np.abs(got - want).max() < TOL
    margins = np.asarray(reference.logit_margins(
        params, jnp.asarray(tokens[None]), tiny))
    assert margins.shape == (1, 44) and (margins >= 0).all()


def _drop_pairs(phi):
    """A state that forgets the pairs at the largest distance."""
    def wrong(x):
        out = phi(x)
        return out.at[..., -1, :].set(0.0)
    return wrong


DEPARTURES = {
    # what is changed in the program -> how
    "no_gate_bias": lambda params: {**params, "layers": {
        **params["layers"], "bg": params["layers"]["bg"] * 0}},
    "no_decay": lambda params: {**params, "layers": {
        **params["layers"], "bg": params["layers"]["bg"] + 30}},
}


@pytest.mark.parametrize("departure", sorted(DEPARTURES) + [
    "lost_pairs", "softmax", "no_qk_norm"])
def test_a_single_departure_fails_many_times_over(tiny, departure,
                                                  monkeypatch):
    """Each departure from the layer as the reference writes it moves
    the served logits past the float32 limit by two orders or more."""
    import jax.numpy as jnp

    from ray_tpu.ops import retention

    cfg, params = _programs(tiny)
    reference = arch.reference(tiny)
    tokens = np.random.default_rng(5).integers(0, 256, 45)
    want = np.asarray(reference.logits(
        params, jnp.asarray(tokens[None, :-1]), tiny))[0, 20:]
    if departure in DEPARTURES:
        params = DEPARTURES[departure](params)
    elif departure == "lost_pairs":
        monkeypatch.setattr(retention, "phi", _drop_pairs(retention.phi))
    elif departure == "softmax":
        cfg = dataclasses.replace(cfg, layer_types=None)
    else:
        cfg = dataclasses.replace(cfg, qk_norm=False)
    if departure == "softmax":
        from ray_tpu.models import forward

        layers = {k: v for k, v in params["layers"].items()
                  if k not in ("wg", "bg")}
        got = np.asarray(forward(
            {**params, "layers": layers},
            jnp.asarray(tokens[None, :-1]), cfg)[0])[0, 20:]
    else:
        got = _served_logits(cfg, params, tokens, 21, 32, slot=0)
    assert np.abs(got - want).max() > 100 * TOL


def test_the_bfloat16_state_control_trails_where_float32_does_not(tiny):
    """``control_margins``: the recurrent form a token at a time. With a
    float32 state it is the reference by another route; with a bfloat16
    state, under gates that remember a thousand tokens, it is not."""
    import jax.numpy as jnp

    cfg, params = _programs(tiny)
    reference = arch.reference(tiny)
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(0, 256, (1, 256)))
    exact = np.asarray(reference.control_margins(
        params, tokens, tiny, jnp.float32))
    assert exact.max() < TOL
    rounded = np.asarray(reference.control_margins(
        params, tokens, tiny, jnp.bfloat16))
    assert rounded.max() > 30 * TOL


def test_training_this_architecture_raises_by_name(tiny):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import forward, param_logical_axes

    cfg, params = _programs(tiny)
    with pytest.raises(NotImplementedError, match="'state'"):
        param_logical_axes(cfg)
    with pytest.raises(NotImplementedError, match="power retention"):
        forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    assert jax.tree.leaves(params)  # and the weights were made


STEP = "pallas_f32_16_8_5_128_f32_6_16_8_65_136_128"
SCAN = "pallas_bf16_8_5_8192_128_f32_8_65_136_128"


def _record(config, engine=None, before=None, trace=None,
            device=("tpu", "TPU v5 lite", 1)):
    device = dict(zip(("platform", "kind", "count"), device))
    return {"config": config, "trace": trace,
            "worker": {"device": device, "window_start": 100.0,
                       "engine": engine or {},
                       "engine_before": before or {}}}


def _traced(config):
    """Three decode steps of 16 sequences and one prefill of 6,000
    tokens in a bucket of 8192, all times made up; a page walk, a latent
    walk, a flash kernel and a grouped matmul of other models beside the
    two retention kernels."""
    after = {"decode_steps": 13, "decode_slot_steps": 7 + 160,
             "decode_state_slot_layers": 42 + 960,
             "state_slot_bytes": {"state": 36_208_640},
             "requests": [[101.0, 101.1, 102.0, None, 6000, 8192, 7, None],
                          [99.0, 99.1, 99.5, 130.0, 9000, 16384, 3, None]]}
    before = {"decode_steps": 3, "decode_slot_steps": 7,
              "decode_state_slot_layers": 42}
    trace = {"busy_s": 0.4, "window_s": 0.5,
             "modules": {"decode_step": [0.021, 0.020, 0.022],
                         "prefill": [0.6]},
             "ops": [[STEP, 18, 0.04], [SCAN, 6, 0.2],
                     ["pallas_bf16_32_32_128_bf16_1_4_8192_16_128"
                      "_bf16_1_4_8192_16_128", 3, 0.5],
                     ["pallas_bf16_16_32_512_bf16_5_8192_16_640", 15, 0.5],
                     ["pallas_bf16_32_4096_128_f32_32_1_4096", 5, 0.5],
                     ["pallas_bf16_256_768", 24, 0.5],
                     ["fusion_bf16_32_2048", 9, 0.004]]}
    return _record(config, after, before, trace)


def test_kernel_names_match_nothing_else():
    from benchmark.readers import latent, moe, window

    others = (latent.LATENT_WALK, window.PAGE_WALK, window.FLASH,
              moe.GROUPED)
    assert not any(p.match(n) for p in others for n in (STEP, SCAN))
    theirs = [op[0] for op in _traced({})["trace"]["ops"][2:]]
    assert not any(p.match(n) for p in (readers.STATE_STEP,
                                        readers.CHUNK_SCAN) for n in theirs)
    assert readers.STATE_STEP.match(STEP) and not readers.STATE_STEP.match(SCAN)
    assert readers.CHUNK_SCAN.match(SCAN).group(1) == "8192"
    assert not readers.CHUNK_SCAN.match(STEP)


def test_names_are_what_the_reducer_gives_the_kernels_outputs():
    from benchmark import trace_reduce

    step = ("%attn.state.3 = (f32[16,8,5,128]{3,2,1,0}, "
            "f32[6,16,8,65,136,128]{5,4,3,2,1,0}) custom-call(%a, %b), "
            "custom_call_target=\"tpu_custom_call\"")
    scan = ("%attn.state.9 = (bf16[8,5,8192,128]{3,2,1,0}, "
            "f32[8,65,136,128]{3,2,1,0}) custom-call(%a), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.stable_name(step) == STEP
    assert trace_reduce.stable_name(scan) == SCAN


def test_readers_on_a_hand_made_record(config):
    from benchmark import flops

    record = _traced(config)
    peak = flops.peaks("TPU v5 lite")
    assert readers.state_slot_bytes(record) == 36_208_640
    assert readers.state_walk_time_share(record) == pytest.approx(10.0)
    # 96 states a step, three steps: the bytes bound it.
    states = 3 * 96
    least = 2 * states * 34_080_768 / peak["hbm_bytes_per_s"]
    assert least > states * 8256 * 129 * 104 / peak["bf16_flops_per_s"]
    assert readers.state_walk_roofline(record) == pytest.approx(
        100 * least / 0.04)
    step_bytes = brumby_counts.decode_step_bytes_state(config, 16, 96)
    assert readers.decode_step_roofline_state(record) == pytest.approx(
        100 * step_bytes / peak["hbm_bytes_per_s"] / 0.021)
    # One prefill of the bucket (6 calls, 6 layers) at the window's mean
    # prompt of that bucket, 6,000 real tokens: the operations bound it.
    ops = brumby_counts.retention_prefill_flops(config, 6000)
    assert ops / peak["bf16_flops_per_s"] > brumby_counts.\
        retention_prefill_bytes(config, 6000) / peak["hbm_bytes_per_s"]
    assert readers.prefill_retention_roofline(record) == pytest.approx(
        100 * ops / peak["bf16_flops_per_s"] / 0.2)
    for name in ("state_walk_roofline", "decode_step_roofline_state",
                 "prefill_retention_roofline"):
        assert 0 < getattr(readers, name)(record) < 100
    # Through the harness, under the metrics' own names.
    bench = bench_run.load_benchmark()
    got = bench_run.read_metrics(
        [m for m in bench["per_layer"] if m.get("workloads") == [CELL]],
        record)
    assert {k: v["unit"] for k, v in got.items()} == {
        "state_walk_time_share.chat": "%", "state_walk_roofline.chat": "%",
        "decode_step_roofline_state.chat": "%",
        "prefill_retention_roofline.chat": "%",
        "state_slot_bytes.chat": "bytes"}


@pytest.mark.parametrize("name", [
    "state_walk_time_share", "state_walk_roofline",
    "decode_step_roofline_state", "prefill_retention_roofline",
    "state_slot_bytes"])
def test_a_reader_finds_nothing_and_says_none(config, name):
    """The parent's engine has no such gauge or counter, an untraced run
    no trace, a trace of another model no such kernel, and another
    configuration's counts no such function: None each time, no raise."""
    reader = getattr(readers, name)
    joyai = _load("benchmark", "configs", "joyai-llm-flash-L5.json")
    traced = _traced(config)
    bare = {"busy_s": 0.1, "window_s": 0.2, "modules": {},
            "ops": [["fusion_bf16_32_2048", 9, 0.004],
                    ["pallas_bf16_32_4096_128_f32_32_1_4096", 5, 0.5]]}
    old_engine = {"decode_steps": 3, "decode_kv_tokens": 9,
                  "decode_slot_steps": 5, "requests": []}
    later = {**old_engine, "decode_steps": 9, "decode_slot_steps": 50}
    records = [_record(config), _record(config, old_engine, old_engine),
               _record(config, old_engine, old_engine, bare),
               # The parent's engine under this PR's benchmark files: the
               # kernels' names in a trace, no counter, no gauge.
               _record(config, later, old_engine,
                       {**traced["trace"], "ops": bare["ops"]})]
    if name in ("state_walk_roofline", "decode_step_roofline_state"):
        records.append(_record(config, later, old_engine, traced["trace"]))
    if name != "state_slot_bytes":
        records.append(_record(config, traced["worker"]["engine"],
                               traced["worker"]["engine_before"], bare))
    if name not in ("state_slot_bytes", "state_walk_time_share"):
        records.append(_record(joyai, traced["worker"]["engine"],
                               traced["worker"]["engine_before"],
                               traced["trace"]))
    for record in records:
        assert reader(record) is None
