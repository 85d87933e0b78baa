"""OLMoE-1B-7B at its published widths, depth 8 (``olmoe-1b-7b-0125-L8``):
64 experts top-8 through the grouped matmuls, compiled for a described
v5e (tests/tpu_rehearsal.py)."""

import pytest

jax = pytest.importorskip("jax")

from tpu_rehearsal import (  # noqa: E402
    CHAT_CELL, CHAT_POOL_PAGES, HLO_INSTRUCTION, assert_pool_stays_in_place,
    assert_projections_stay_in_place, decode_program, fits_one_chip, olmoe_cfg,
    prefill_program, serve_shapes)


@pytest.fixture(scope="module")
def olmoe(v5e):
    """(cfg, params, cache) at the chat cell's engine shapes."""
    cfg = olmoe_cfg()
    return cfg, *serve_shapes(cfg, v5e, CHAT_CELL[0], CHAT_POOL_PAGES,
                              CHAT_CELL[1])


def test_olmoe_decode_program_compiles_for_v5e(v5e, as_tpu, olmoe):
    """``serve-olmoe-c16``'s decode step with the grouped expert matmuls
    in the layer scan: 32 slots x 8 experts a token are 256 rows. The
    page walk at ``Hkv`` 16 leaves this pool in place too."""
    cfg, params, cache = olmoe
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert "tpu_custom_call" in compiled.as_text()  # the page walk
    assert fits_one_chip(compiled)
    assert_pool_stays_in_place(compiled, cache.k["full"].shape)


def test_olmoe_prefill_program_compiles_for_v5e(v5e, as_tpu, olmoe):
    """The largest bucket, 2048 tokens: 16,384 rows through the grouped
    matmuls beside 7.1 GB of weights and the 2.1 GB pool."""
    compiled = prefill_program(olmoe[0], v5e, *olmoe[1:], 2048)
    assert "tpu_custom_call" in compiled.as_text()  # flash prefill
    assert fits_one_chip(compiled)


@pytest.mark.parametrize("bucket,kernel", [(16, True), (512, True),
                                           (1024, False)])
def test_olmoe_prefill_buckets_choose_their_grouped_matmul(v5e, as_tpu, olmoe,
                                                           bucket, kernel):
    """8 x bucket rows over 64 experts: up to 64 rows an expert (the
    512-token bucket's 4096 rows) a prefill runs the kernel for few rows
    a group, as the decode step does; past it ``ragged_dot``'s own
    custom calls. Either way the grouped matmuls are the program's
    two-dimensional custom calls, rows by the expert's or the model's
    width."""
    from ray_tpu.ops import grouped_matmul as gm

    cfg, params, cache = olmoe
    assert (gm.grouped_path(8 * bucket, cfg.n_experts) == "small_rows") \
        is kernel
    text = prefill_program(cfg, v5e, params, cache, bucket).as_text()
    calls = [m["result"] for m in HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call" and "tpu_custom_call" in m["rest"]]
    grouped = [r for r in calls if r.startswith(f"bf16[{8 * bucket},")]
    assert len(grouped) == (2 if kernel else 3), calls
    assert ("ragged-dot" in text) is not kernel
