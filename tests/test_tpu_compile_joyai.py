"""``joyai-llm-flash-L5``: latent attention over one pool of rows, 256
experts a layer, and the latent walk alone, compiled for a described
v5e (tests/tpu_rehearsal.py)."""

import functools
import math

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import paged_attention  # noqa: E402
from tpu_rehearsal import (  # noqa: E402
    PAGE, arr, assert_pool_stays_in_place, assert_projections_stay_in_place,
    cell_shapes, decode_program, fits_one_chip, prefill_program,
    weights_program)

LATENT_POOL = (5, 8192, PAGE, 640)


@pytest.fixture(scope="module")
def joyai(v5e):
    return cell_shapes("joyai-llm-flash-L5", v5e)


def test_latent_walk_kernel_compiles_for_v5e(v5e):
    """32 absorbed query rows of a slot against its rows of 640 (the
    latent's 512, the rotary key's 64 on a lane tile of its own), the
    values the rows' first 512: the pool goes in whole and comes back
    through the aliased output."""
    batch, pages_per_seq = 32, 8192 // PAGE
    compiled = jax.jit(
        functools.partial(paged_attention.paged_latent_decode_attention,
                          scale=192 ** -0.5, values=512),
        donate_argnums=(2,),
    ).lower(
        arr(v5e, (batch, 32, 640)), arr(v5e, (batch, 640)),
        arr(v5e, LATENT_POOL), arr(v5e, (), jnp.int32),
        arr(v5e, (batch, pages_per_seq), jnp.int32),
        arr(v5e, (batch,), jnp.int32), arr(v5e, (batch,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert_pool_stays_in_place(compiled, LATENT_POOL)


def test_joyai_decode_program_compiles_for_v5e(v5e, as_tpu, joyai):
    """Two scans (the dense layer, four expert layers) over the one
    latent pool, 256 experts a layer read in place: the pool is neither
    copied, sliced nor re-stacked, and no k or v pool exists."""
    cfg, engine, params, cache = joyai
    assert {k: v.shape for k, v in cache.k.items()} == {"latent": LATENT_POOL}
    assert cache.v == {}
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert_pool_stays_in_place(compiled, LATENT_POOL)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * math.prod(LATENT_POOL)


@pytest.mark.parametrize("bucket", [2048, 8192])
def test_joyai_prefill_program_compiles_for_v5e(v5e, as_tpu, joyai, bucket):
    """The cell's smallest and largest bucket: the flash kernel with a
    head's whole K (192 wide) and V (128 wide) in VMEM and no
    [32, bucket, bucket] of scores anywhere, 8 x bucket rows through the
    grouped matmuls of 1024 groups, beside 11.1 GB of weights."""
    cfg, engine, params, cache = joyai
    compiled = prefill_program(cfg, v5e, params, cache, bucket)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"f32[32,{bucket},{bucket}]" not in text
    assert f"f32[1,32,{bucket},{bucket}]" not in text
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


def test_joyai_weights_are_made_within_one_chip(v5e, joyai):
    """``init_params`` as the benchmark jits it: the experts' leaves are
    drawn a layer at a time, so the float32 temporaries beside 11.1 GB
    of weights stay under the chip's 16 GB."""
    assert fits_one_chip(weights_program(joyai[0], v5e))
