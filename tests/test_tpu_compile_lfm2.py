"""``lfm2-24b-a2b-L10``: gated short-convolution layers among
grouped-query layers with heads of 64 over 64 experts of width 1,536
(PR 73): the page walk over a pool of paired heads, the flash forward at
``d = 64``, the few-rows grouped matmul at this width, the decode program
and the three prefill buckets, compiled for a described v5e
(tests/tpu_rehearsal.py)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_rehearsal import (  # noqa: E402
    HLO_INSTRUCTION, PAGE, arr, assert_pool_stays_in_place,
    assert_projections_stay_in_place, cell_shapes, decode_program,
    fits_one_chip, prefill_program, weights_program)

# 2 attention layers, 8 KV heads of 64 as 4 rows of 128, 16,384 pages.
KV_POOL = (2, 4, 16384, PAGE, 128)
# 8 conv layers, 2 rows, 16 slots, hidden 2048.
HISTORIES = (8, 2, 16, 2048)


@pytest.fixture(scope="module")
def lfm2(v5e):
    return cell_shapes("lfm2-24b-a2b-L10", v5e)


def test_page_walk_at_heads_of_64_compiles_for_v5e(v5e, as_tpu):
    """32 query heads of 64 on 8 KV heads: the pool holds two heads a
    lane tile, the walk's call writes a slot's rows [B, H, 128] and both
    pools (the page walk's name to the trace readers: three dimensions,
    five and five), its buffers are those of 4 heads of 128, and the
    pools come back through the aliased outputs."""
    from ray_tpu.ops import paged_attention as pa

    assert pa.decode_attention_path(PAGE, 128) == "page_walk"
    pool = arr(v5e, KV_POOL)
    compiled = jax.jit(pa.decode_attention, donate_argnums=(3, 4)).lower(
        arr(v5e, (16, 32, 64)), arr(v5e, (16, 8, 64)), arr(v5e, (16, 8, 64)),
        pool, pool, arr(v5e, (), jnp.int32),
        arr(v5e, (16, 1024), jnp.int32), arr(v5e, (16,), jnp.int32),
        arr(v5e, (16,), jnp.bool_)).compile()
    call, = [m for m in HLO_INSTRUCTION.finditer(compiled.as_text())
             if m["op"] == "custom-call"]
    assert re.match(r"\(bf16\[16,32,128\]\S*, bf16\[2,4,16384,16,128\]\S*, "
                    r"bf16\[2,4,16384,16,128\]", call["result"]), call["result"]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(KV_POOL)
    assert memory.temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("bucket", [4096, 8192, 16384])
def test_flash_forward_at_heads_of_64_compiles_for_v5e(v5e, as_tpu, bucket):
    """The cell's prefill buckets at ``d = dv = 64``, 32 heads on 8: the
    kernel, resident up to 8,192 keys and streamed at 16,384 (a head's K
    and V are counted at the 128 lanes VMEM pads them to), never the
    einsum."""
    import importlib

    # ray_tpu.ops re-exports the function under the module's own name.
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    path = fa.forward_path(bucket, bucket, 64, 64, 32, 8, 2)
    assert path == ("streamed" if bucket == 16384 else "resident")
    compiled = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True)).lower(
            arr(v5e, (1, bucket, 32, 64)), arr(v5e, (1, bucket, 8, 64)),
            arr(v5e, (1, bucket, 8, 64))).compile()
    text = compiled.as_text()
    calls = [m["result"] for m in HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call"]
    assert any(f"bf16[32,{bucket},64]" in call for call in calls), calls
    # The name the benchmark finds it by in a trace, whichever of the two
    # the call writes first (benchmark/readers/conv.py); the accepted
    # readers' page walk does not match it.
    from benchmark import trace_reduce
    from benchmark.readers import conv, window

    name, = {trace_reduce.stable_name(line.strip())
             for line in text.splitlines() if "custom-call(" in line}
    assert name in (f"pallas_bf16_32_{bucket}_64_f32_32_1_{bucket}",
                    f"pallas_f32_32_1_{bucket}_bf16_32_{bucket}_64"), name
    assert conv.FLASH_H64.match(name) and not window.PAGE_WALK.match(name)


@pytest.mark.parametrize("rows", [16, 32, 64])
def test_grouped_matmul_takes_64_groups_of_width_1536(v5e, as_tpu, rows):
    """A decode step's 4 to 16 streams x top-4 rows over 64 experts of
    width 1,536 (12 lane tiles, no whole number of 1,024): the few-rows
    kernel, a whole expert's columns a block both ways."""
    from ray_tpu.ops import grouped_matmul as gm

    assert gm.grouped_path(rows, 64) == "small_rows"
    assert gm.row_tile(rows) == rows
    assert gm.choose_block_n(2048, 1536, 2, 2) == 1536
    assert gm.choose_block_n(1536, 2048, 1, 2) == 2048

    def experts(x, w_gate, w_up, w_down, sizes):
        matmul = gm.grouped_matmul(rows, sizes, experts=64)
        h = matmul(x, (w_gate, w_up),
                   lambda g, u: jax.nn.silu(g) * u)
        return matmul(h, (w_down,))

    compiled = jax.jit(experts).lower(
        arr(v5e, (rows, 2048)), arr(v5e, (64, 2048, 1536)),
        arr(v5e, (64, 2048, 1536)), arr(v5e, (64, 1536, 2048)),
        arr(v5e, (64,), jnp.int32)).compile()
    calls = [m["result"] for m in HLO_INSTRUCTION.finditer(
        compiled.as_text()) if m["op"] == "custom-call"]
    assert any(f"bf16[{rows},1536]" in c for c in calls), calls
    assert any(f"bf16[{rows},2048]" in c for c in calls), calls


def test_lfm2_decode_program_compiles_for_v5e(v5e, as_tpu, lfm2):
    """Five scans over two pools: the 2 attention layers' paired rows,
    carried whole and written through the walk's aliased call, and the 8
    conv layers' histories, beside 10.5 GB of weights; no stacked
    projection re-laid, the head read from the embedding where it lies."""
    cfg, engine, params, cache = lfm2
    assert "lm_head" not in params
    assert {k: v.shape for k, v in cache.k.items()} == {
        "conv": HISTORIES, "full": KV_POOL}
    assert {k: v.shape for k, v in cache.v.items()} == {"full": KV_POOL}
    assert cache.page_table["conv"].shape == (16, 0)
    assert cache.page_table["full"].shape == (16, 1024)
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    text = compiled.as_text()
    assert "bf16[16,32,128]" in text             # the head-64 walk
    assert_pool_stays_in_place(compiled, KV_POOL, temporaries=False)
    memory = compiled.memory_analysis()
    pools = 2 * (2 * math.prod(KV_POOL) + math.prod(HISTORIES))
    assert memory.alias_size_in_bytes >= pools
    # No copy of the embedding (268 MB) for the head, none of a pool.
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    print("decode", memory.temp_size_in_bytes / 2**20, "MiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket", [4096, 8192, 16384])
def test_lfm2_prefill_program_compiles_for_v5e(v5e, as_tpu, lfm2, bucket):
    """The cell's three buckets: the flash kernel at heads of 64 in two
    layers, the taps in eight, both pools of a slot laid from one
    prompt, beside 11.6 GB of weights and rows."""
    cfg, engine, params, cache = lfm2
    compiled = prefill_program(cfg, v5e, params, cache, bucket, {"conv": 0})
    text = compiled.as_text()
    assert f"bf16[32,{bucket},64]" in text       # the flash forward
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_lfm2_weights_are_made_within_one_chip(v5e, lfm2):
    assert fits_one_chip(weights_program(lfm2[0], v5e))
