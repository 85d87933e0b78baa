"""``trinity-mini-L6``: two pools (window rings and a full pool), 128
experts, compiled for a described v5e (tests/tpu_rehearsal.py)."""

import math

import pytest

jax = pytest.importorskip("jax")

from tpu_rehearsal import (  # noqa: E402
    PAGE, assert_pool_stays_in_place, assert_projections_stay_in_place,
    cell_shapes, decode_program, fits_one_chip, prefill_program)


@pytest.fixture(scope="module")
def trinity(v5e):
    return cell_shapes("trinity-mini-L6", v5e)


def test_trinity_decode_program_compiles_for_v5e(v5e, as_tpu, trinity):
    """Two pools, five scans: 5 window layers over rings of 129 pages a
    slot and one full layer over the 8192-page pool, 128 experts read in
    place. Neither pool is copied, sliced or re-stacked, and the weights
    of a run's layers are read where they lie."""
    cfg, engine, params, cache = trinity
    assert {k: v.shape for k, v in cache.k.items()} == {
        "window": (5, 4, 32 * 129, PAGE, 128), "full": (1, 4, 8192, PAGE, 128)}
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    # The window pool is the larger: the temporaries' bound is its slice.
    assert_pool_stays_in_place(compiled, cache.k["window"].shape)
    assert_pool_stays_in_place(compiled, cache.k["full"].shape)
    pools = sum(2 * 2 * math.prod(p.shape) for p in cache.k.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


@pytest.mark.parametrize("bucket", [4096, 8192])
def test_trinity_prefill_program_compiles_for_v5e(v5e, as_tpu, trinity,
                                                  bucket):
    """The two buckets no cell had before: the flash kernel with a whole
    4096- or 8192-row K and V of a head in VMEM, with and without the
    window's lower bound, 8 x bucket rows through the grouped matmuls,
    beside 8.6 GB of weights and both pools."""
    cfg, engine, params, cache = trinity
    compiled = prefill_program(cfg, v5e, params, cache, bucket,
                               {"window": 129})
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")
