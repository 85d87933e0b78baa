"""The dense serving programs at the 8B-shaped widths, and the program
the engine jits, compiled for a described v5e (tests/tpu_rehearsal.py)."""

import math

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import paged_attention  # noqa: E402
from tpu_rehearsal import (  # noqa: E402
    CHAT_CELL, CHAT_POOL_PAGES, D, HLO_INSTRUCTION, PAGE, arr,
    assert_pool_stays_in_place, assert_projections_stay_in_place,
    decode_program, decode_shapes, olmoe_cfg, prefill_program, serve_cfg,
    serve_shapes)

BUCKET = 512


@decode_shapes
def test_paged_decode_program_compiles_for_v5e(v5e, as_tpu, batch,
                                               pages_per_seq, pool_pages):
    """The decode program as the code builds it for a TPU: the page walk
    inside the layer scan, chosen from platform and shape; the pool in
    the scan's carry, never sliced, re-stacked or re-laid."""
    assert paged_attention.decode_attention_path(PAGE, D) == "page_walk"
    cfg = serve_cfg()
    params, cache = serve_shapes(cfg, v5e, batch, pool_pages, pages_per_seq)
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    pool = cache.k["full"].shape
    assert "tpu_custom_call" in compiled.as_text()
    assert_pool_stays_in_place(compiled, pool)
    # The donated pools are the outputs: both aliased at the entry.
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 2 * math.prod(pool)


@pytest.mark.parametrize("bucket,flash", [(64, False), (BUCKET, True)])
def test_paged_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket, flash):
    """Which causal attention a prefill bucket runs on the chip
    (``ops/flash_attention.py`` owns the rule): a several-hundred-token
    prompt lands in the 512 bucket, whole 128-row blocks, and runs the
    flash kernel; a bucket under a block (16, 32, 64) runs the XLA
    einsum. A dense model's prefill holds no other custom call."""
    cfg = serve_cfg()
    compiled = prefill_program(cfg, v5e, *serve_shapes(cfg, v5e), bucket)
    assert ("tpu_custom_call" in compiled.as_text()) is flash


def test_prefill_program_carries_nothing_of_the_backwards_naming(
        v5e, as_tpu, monkeypatch):
    """``ops/flash_attention.py:_core_fwd`` names the kernel's ``o3`` and
    ``lse`` for the ``"dots"`` remat policy (PR 74). A serving prefill
    differentiates nothing and runs the primal, which names nothing: the
    512 bucket's jaxpr holds the kernel's call and no equation of that
    name, and its compiled text is the same with the naming taken away."""
    import importlib

    from ray_tpu.models import generation

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    cfg = serve_cfg()
    params, cache = serve_shapes(cfg, v5e)
    pages = {kind: arr(v5e, (BUCKET // PAGE,), jnp.int32)
             for kind in cache.page_table}
    jaxpr = str(jax.make_jaxpr(
        lambda params, cache, tokens, real_len, slot, pages:
        generation.paged_prefill(params, tokens, real_len, cache, cfg, slot,
                                 pages))(
        params, cache, arr(v5e, (1, BUCKET), jnp.int32),
        arr(v5e, (), jnp.int32), arr(v5e, (), jnp.int32), pages))
    assert "pallas_call" in jaxpr
    assert fa.FLASH_SAVED not in jaxpr
    texts = []
    for naming in (fa.checkpoint_name, lambda x, name: x):
        monkeypatch.setattr(fa, "checkpoint_name", naming)
        texts.append(  # one call site: the text holds its line
            prefill_program(cfg, v5e, params, cache, BUCKET).as_text())
    assert texts[0] == texts[1]


# What would take a step's inputs or outputs through the host.
_HOST_OPS = {"send", "send-done", "recv", "recv-done", "infeed", "outfeed"}


@pytest.mark.parametrize("model,temperature", [
    ("dense", 0.0), ("olmoe", 0.0), ("dense", 0.7)])
def test_engine_decode_program_carries_tokens_and_key_on_the_device(
        v5e, as_tpu, model, temperature):
    """The program the engine jits (``serve/llm.py:serving_programs``)
    at the chat cells' engine shapes: it takes the last tokens, the
    active mask and the PRNG key and returns the last tokens and the
    key for the call after it beside the read-back (for a MoE model the
    expert load behind the tokens), so that the loop can queue step k+1
    before it has read step k. Around ``paged_decode`` the pool still
    stays in place (the PR 29 guard), and nothing in it calls the host."""
    from ray_tpu.serve.llm import serving_programs

    cfg = olmoe_cfg() if model == "olmoe" else serve_cfg()
    batch, pages_per_seq = CHAT_CELL
    params, cache = serve_shapes(cfg, v5e, batch, CHAT_POOL_PAGES,
                                 pages_per_seq)
    decode_step, _ = serving_programs(cfg, temperature)
    args = (params, cache, arr(v5e, (batch,), jnp.int32),
            arr(v5e, (batch,), jnp.bool_), arr(v5e, (2,), jnp.uint32))
    readback, _, last_tok, rng = jax.eval_shape(decode_step, *args)
    extra = cfg.n_experts + 1 if cfg.n_experts else 0
    assert (readback.shape, readback.dtype) == ((batch + extra,), jnp.int32)
    assert (last_tok.shape, last_tok.dtype) == ((batch,), jnp.int32)
    assert (rng.shape, rng.dtype) == ((2,), jnp.uint32)

    compiled = jax.jit(decode_step, donate_argnums=(1,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the page walk
    pool = cache.k["full"].shape
    assert_pool_stays_in_place(compiled, pool)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 2 * math.prod(pool)
    assert "callback" not in text.lower()
    assert not _HOST_OPS & {m["op"] for m in HLO_INSTRUCTION.finditer(text)}
