"""``smallthinker-21b-a3b-L8``: 28 heads on 4, F S S S, a 16,384 bucket
through the streamed flash forward (PR 57), compiled for a described
v5e (tests/tpu_rehearsal.py)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")

from tpu_rehearsal import (  # noqa: E402
    PAGE, assert_pool_stays_in_place, assert_projections_stay_in_place,
    cell_shapes, decode_program, fits_one_chip, prefill_program)


@pytest.fixture(scope="module")
def smallthinker(v5e):
    return cell_shapes("smallthinker-21b-a3b-L8", v5e)


def test_smallthinker_decode_program_compiles_for_v5e(v5e, as_tpu,
                                                      smallthinker):
    """Two pools, four scans (F, S S S, F, S S S): six window layers over
    rings of 257 pages a slot and two full layers over the 16,384-page
    pool, groups of 7 query heads in the page walk, 64 ReGLU experts
    read in place. Neither pool is copied, sliced or re-stacked."""
    cfg, engine, params, cache = smallthinker
    assert {k: v.shape for k, v in cache.k.items()} == {
        "full": (2, 4, 16384, PAGE, 128),
        "window": (6, 4, 16 * 257, PAGE, 128)}
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    assert_pool_stays_in_place(compiled, cache.k["full"].shape)
    assert_pool_stays_in_place(compiled, cache.k["window"].shape,
                               temporaries=False)
    pools = sum(2 * 2 * math.prod(p.shape) for p in cache.k.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


@pytest.mark.parametrize("bucket", [8192, 16384])
def test_smallthinker_prefill_program_compiles_for_v5e(v5e, as_tpu,
                                                       smallthinker, bucket):
    """The resident form's largest bucket and the streamed form's: the
    flash kernel on the full and the window layers, 6 x bucket rows
    through the grouped matmuls, beside 7.9 GB of weights and both
    pools, inside the chip."""
    cfg, engine, params, cache = smallthinker
    compiled = prefill_program(cfg, v5e, params, cache, bucket,
                               {"window": 257})
    text = compiled.as_text()
    assert "vmem_limit_bytes" not in text
    streamed = len(re.findall(r"f32\[28,1,16384\]\S*, bf16\[28,16384,128\]",
                              text))
    assert (streamed > 0) is (bucket == 16384)
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")
