"""``kimi-linear-48b-a3b-ep16``: delta states beside a latent pool, all
27 layers (PR 62): the two delta kernels alone, the decode program, the
three prefill buckets and the weights' program, compiled for a described
v5e (tests/tpu_rehearsal.py)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_rehearsal import (  # noqa: E402
    HLO_INSTRUCTION, PAGE, arr, assert_pool_stays_in_place,
    assert_projections_stay_in_place, cell_shapes, decode_program,
    fits_one_chip, prefill_program, weights_program)

# 20 layers, 16 slots, 32 heads of 128 x 128: 0.67 GB.
DELTA_POOL = (20, 16, 32, 128, 128)


@pytest.fixture(scope="module")
def kimi(v5e):
    return cell_shapes("kimi-linear-48b-a3b-ep16", v5e)


def test_delta_step_kernel_compiles_for_v5e(v5e):
    """The decode delta-rule kernel at the published shapes: a slot's 32
    states of 64 KB go through VMEM as one block of 2 MB and come back
    through the output aliased to the pool; it writes four dimensions
    and five, by which the trace reader knows it."""
    from ray_tpu.ops import delta_attention

    assert delta_attention.state_shape(20, 16, 32, 128) == DELTA_POOL
    compiled = jax.jit(delta_attention.delta_step, donate_argnums=(5,)).lower(
        arr(v5e, (16, 32, 128)), arr(v5e, (16, 32, 128)),
        arr(v5e, (16, 32, 128)), arr(v5e, (16, 32, 128), jnp.float32),
        arr(v5e, (16, 32), jnp.float32), arr(v5e, DELTA_POOL, jnp.float32),
        arr(v5e, (), jnp.int32), arr(v5e, (16,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    call, = [m for m in HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call"]
    assert re.match(r"\(f32\[16,1,32,128\]\S*, f32\[20,16,32,128,128\]",
                    call["result"]), call["result"]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(DELTA_POOL)
    # Beside the pool: the heads' vectors as columns, no second pool.
    assert memory.temp_size_in_bytes < 4 * math.prod(DELTA_POOL[1:])


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_delta_scan_kernel_compiles_for_v5e(v5e, bucket):
    """The chunked prefill kernel at the cell's smallest and largest
    bucket: a head's state stays in VMEM over its chunks; float32
    matmuls at "highest" and the product against a turned operand lower
    for the chip."""
    from ray_tpu.ops import delta_attention

    compiled = jax.jit(delta_attention.delta_scan).lower(
        arr(v5e, (bucket, 32, 128)), arr(v5e, (bucket, 32, 128)),
        arr(v5e, (bucket, 32, 128)),
        arr(v5e, (bucket, 32, 128), jnp.float32),
        arr(v5e, (bucket, 32), jnp.float32),
    ).compile()
    calls = [m["result"] for m in HLO_INSTRUCTION.finditer(
        compiled.as_text()) if m["op"] == "custom-call"]
    assert any(re.match(rf"\(bf16\[32,{bucket},128\]\S*, f32\[32,128,128\]",
                        call) for call in calls), calls


def test_kimi_decode_program_compiles_for_v5e(v5e, as_tpu, kimi):
    """Fifteen scans over three pools: the 20 delta layers' states and
    convolution histories and the 7 latent layers' rows, each carried
    whole and updated in place beside 8.6 GB of weights."""
    cfg, engine, params, cache = kimi
    assert {k: v.shape for k, v in cache.k.items()} == {
        "delta": DELTA_POOL, "latent": (7, 16384, PAGE, 640)}
    assert {k: v.shape for k, v in cache.v.items()} == {
        "delta": (20, 3, 16, 12288)}
    assert cache.page_table["delta"].shape == (16, 0)
    assert cache.page_table["latent"].shape == (16, 1024)
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    text = compiled.as_text()
    assert "f32[16,1,32,128]" in text           # the delta step
    assert_pool_stays_in_place(compiled, cache.k["latent"].shape)
    memory = compiled.memory_analysis()
    pools = (4 * math.prod(DELTA_POOL)
             + 2 * math.prod(cache.k["latent"].shape)
             + 2 * math.prod(cache.v["delta"].shape))
    assert memory.alias_size_in_bytes >= pools
    # Nothing the size of the states beside them: a copy would be 0.67 GB.
    assert memory.temp_size_in_bytes < 2 * math.prod(DELTA_POOL)
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket", [4096, 8192, 16384])
def test_kimi_prefill_program_compiles_for_v5e(v5e, as_tpu, kimi, bucket):
    """The cell's three buckets: the chunked delta rule in 20 layers and
    the flash kernel (q.k 192 beside v 128) in 7, both pools of a slot
    laid from one prompt, beside 11.7 GB of weights, states and rows."""
    cfg, engine, params, cache = kimi
    compiled = prefill_program(cfg, v5e, params, cache, bucket, {"delta": 0})
    text = compiled.as_text()
    assert f"bf16[32,{bucket},128]" in text     # the delta scan
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_kimi_weights_are_made_within_one_chip(v5e, kimi):
    assert fits_one_chip(weights_program(kimi[0], v5e))
