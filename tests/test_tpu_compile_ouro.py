"""``ouro-2.6b``: 48 layers four times over one set of weights, a pool
192 layers deep (PR 65), compiled for a described v5e
(tests/tpu_rehearsal.py)."""

import math

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import generation  # noqa: E402
from tpu_rehearsal import (  # noqa: E402
    PAGE, arr, assert_pool_stays_in_place, assert_projections_stay_in_place,
    cell_shapes, decode_program, fits_one_chip, prefill_program,
    weights_program)


@pytest.fixture(scope="module")
def ouro(v5e):
    return cell_shapes("ouro-2.6b", v5e)


def test_ouro_decode_program_compiles_for_v5e(v5e, as_tpu, ouro):
    """A scan over the four passes around the layer scan, over ONE set
    of stacked weights, each pass walking its own 48 layers of a pool of
    192: 8.05 GB carried whole through both scans and updated in place
    beside 5.34 GB of weights; the exit distribution comes back beside
    the logits. Until PR 69 the temporaries were 1.13 GiB, a re-layout
    of the stacked q, k and v weights as published ([48, 2048, 16, 128],
    3 x 0.2 GB and as much again beside them) that XLA moved out of the
    pass loop and copied every step; the serving tree keeps them
    [48, 16, 128, 2048] and nothing of a weight is copied
    (``assert_projections_stay_in_place``): 0.6 MB of temporaries. The
    bound is what keeps such a copy, or one of a pool's layers, from
    coming back unseen."""
    cfg, engine, params, cache = ouro
    pool = (4 * 48, 16, engine["total_pages"], PAGE, 128)
    assert {k: v.shape for k, v in cache.k.items()} == {"full": pool}
    assert cache.page_table["full"].shape == (8, 40)
    batch = engine["max_batch"]
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    assert_pool_stays_in_place(compiled, pool, temporaries=False)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool)
    assert memory.temp_size_in_bytes < 64 * 2**20
    out = jax.eval_shape(
        lambda params, cache, tok, active: generation.paged_decode(
            params, tok, cache, cfg, active=active),
        params, cache, arr(v5e, (batch,), jnp.int32),
        arr(v5e, (batch,), jnp.bool_))
    assert out[3].shape == (batch, 4) and out[3].dtype == jnp.float32
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket,flash", [(64, False), (256, True)])
def test_ouro_prefill_program_compiles_for_v5e(v5e, as_tpu, ouro, bucket,
                                               flash):
    """The cell's smallest and largest bucket: every pass's k and v of
    48 layers laid into that pass's layers of the slot's pages, beside
    13.4 GB of weights and pool; the 256 bucket through the flash
    kernel at 16 x 128. A prefill reads the serving tree's q, k and v
    in place too (1.17 GiB of temporaries at the 256 bucket as
    published, 0.05 GiB so)."""
    cfg, engine, params, cache = ouro
    compiled = prefill_program(cfg, v5e, params, cache, bucket)
    assert fits_one_chip(compiled)
    assert_projections_stay_in_place(compiled, params)
    assert ("tpu_custom_call" in compiled.as_text()) == flash
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_ouro_weights_are_made_within_one_chip(v5e, ouro):
    assert fits_one_chip(weights_program(ouro[0], v5e))
