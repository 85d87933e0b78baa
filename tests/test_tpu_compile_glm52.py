"""``glm-5.2-L5-ep16``: latent attention under a learned selection, a
pool of indexer keys on the latent pool's page table, 16 of 256 experts
held: the decode program, the three prefill buckets and the weights'
program, compiled for a described v5e (tests/tpu_rehearsal.py)."""

import math

import pytest

jax = pytest.importorskip("jax")

from tpu_rehearsal import (  # noqa: E402
    PAGE, assert_pool_stays_in_place, assert_projections_stay_in_place,
    cell_shapes, decode_program, fits_one_chip, prefill_program,
    weights_program)

GLM_POOLS = {"latent": (5, 8192, PAGE, 640), "index": (2, 8192, PAGE, 128)}


@pytest.fixture(scope="module")
def glm52(v5e):
    return cell_shapes("glm-5.2-L5-ep16", v5e)


def test_glm52_decode_program_compiles_for_v5e(v5e, as_tpu, glm52):
    """Three scans (dense+indexing, three expert layers that share its
    selection, an expert layer that indexes) over the latent pool and
    the pool of indexer keys on the same page table: the index walk and
    the walk under a selection are custom calls, both pools stay in
    place, and 16 of 256 experts a layer are read in place."""
    cfg, engine, params, cache = glm52
    assert {k: v.shape for k, v in cache.k.items()} == GLM_POOLS
    assert cache.v == {} and set(cache.page_table) == {"latent"}
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    for kind, pool in GLM_POOLS.items():
        assert_pool_stays_in_place(compiled, pool, kind == "latent")
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * sum(map(math.prod, GLM_POOLS.values()))


@pytest.mark.parametrize("bucket", [2048, 8192, 16384])
def test_glm52_prefill_program_compiles_for_v5e(v5e, as_tpu, glm52, bucket):
    """A bucket that selects everything (the causal flash kernel, as
    JoyAI's) and the cell's two: the selection as int8 tiles, never a
    float32 [bucket, bucket], beside 7.76 GB of weights."""
    cfg, engine, params, cache = glm52
    compiled = prefill_program(cfg, v5e, params, cache, bucket)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    selects = bucket > cfg.index_topk
    assert not selects or f"f32[{bucket},{bucket}]" not in text
    assert f"f32[64,{bucket},{bucket}]" not in text
    assert (f"s8[{bucket // 128},{bucket // 512},128,512]" in text) \
        == selects
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


def test_glm52_weights_are_made_within_one_chip(v5e, glm52):
    assert fits_one_chip(weights_program(glm52[0], v5e))
