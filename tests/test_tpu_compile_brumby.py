"""``brumby-14b-base-L6``: retention layers over one pool of states, and
its two kernels alone, compiled for a described v5e
(tests/tpu_rehearsal.py)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_rehearsal import (  # noqa: E402
    arr, assert_projections_stay_in_place, cell_shapes, decode_program,
    fits_one_chip, prefill_program)

# 6 layers, 16 slots, 8 KV heads, 65 turns of 136 rows of 128: 3.48 GB.
STATE_POOL = (6, 16, 8, 65, 136, 128)


@pytest.fixture(scope="module")
def brumby(v5e):
    return cell_shapes("brumby-14b-base-L6", v5e)


def test_state_step_kernel_compiles_for_v5e(v5e):
    """The decode retention kernel at the published shapes: 40 query
    heads on 8 KV heads of 128, 16 slots; a (slot, KV head)'s state
    block of 4.5 MB goes through VMEM and comes back through the output
    aliased to the pool; q and k go in as they are."""
    from benchmark import trace_reduce
    from benchmark.readers import state
    from ray_tpu.ops import retention

    assert retention.state_shape(6, 16, 8, 128) == STATE_POOL
    compiled = jax.jit(retention.state_step, donate_argnums=(4,)).lower(
        arr(v5e, (16, 40, 128)), arr(v5e, (16, 8, 128)),
        arr(v5e, (16, 8, 128)), arr(v5e, (16, 8), jnp.float32),
        arr(v5e, STATE_POOL, jnp.float32), arr(v5e, (), jnp.int32),
        arr(v5e, (16,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(STATE_POOL)
    # Beside the pool: the operands' rows, no second pool.
    assert memory.temp_size_in_bytes < 4 * math.prod(STATE_POOL[1:])
    # The call writes the read-outs first and the pool second, float32
    # both and nothing else: the name the benchmark finds it by in a
    # trace (benchmark/readers/state.py).
    call, = (line.strip() for line in text.splitlines()
             if "custom-call(" in line)
    name = trace_reduce.stable_name(call)
    assert name == "pallas_f32_16_8_5_128_f32_6_16_8_65_136_128"
    assert state.STATE_STEP.match(name)
    # phi(q) and phi(k) are made inside it (PR 72): nothing but the pool
    # has an axis of the 65 turns.
    shapes = {tuple(map(int, dims.split(",")))
              for dims in re.findall(r"\[([0-9]+(?:,[0-9]+)*)\]", text)}
    assert {shape for shape in shapes if 65 in shape} == {STATE_POOL}


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_chunk_scan_kernel_compiles_for_v5e(v5e, bucket):
    """The chunked prefill kernel at the cell's smallest and largest
    bucket: a KV head's state stays in VMEM over its chunks."""
    from ray_tpu.ops import retention

    compiled = jax.jit(retention.chunk_scan).lower(
        arr(v5e, (bucket, 40, 128)), arr(v5e, (bucket, 8, 128)),
        arr(v5e, (bucket, 8, 128)), arr(v5e, (bucket, 8), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_brumby_decode_program_compiles_for_v5e(v5e, as_tpu, brumby):
    """One scan of six retention layers over the one pool of states: the
    pool is carried whole and updated in place, no KV pool exists, and
    7.08 GB of weights beside 3.48 GB of state fit the chip."""
    cfg, engine, params, cache = brumby
    assert {k: v.shape for k, v in cache.k.items()} == {"state": STATE_POOL}
    assert cache.v == {} and cache.page_table["state"].shape == (16, 0)
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(STATE_POOL)
    # Nothing pool-sized beside the pool: a copy would be 3.48 GB.
    assert memory.temp_size_in_bytes < 2 * math.prod(STATE_POOL)
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_brumby_prefill_program_compiles_for_v5e(v5e, as_tpu, brumby, bucket):
    """The cell's smallest and largest bucket: the chunked scan a layer,
    the slot's states laid into the pool in place, beside 10.6 GB of
    weights and state."""
    cfg, engine, params, cache = brumby
    compiled = prefill_program(cfg, v5e, params, cache, bucket, {"state": 0})
    assert "tpu_custom_call" in compiled.as_text()
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(STATE_POOL)
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")
