"""``minicpm-sala-L12``: block-selected attention over the paged pool
beside Lightning states (PR 70): the five kernels alone, the decode
program, the 32,768 prefill bucket and the weights' program, compiled
for a described v5e (tests/tpu_rehearsal.py)."""

import math
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_rehearsal import (  # noqa: E402
    HLO_INSTRUCTION, PAGE, arr, assert_pool_stays_in_place,
    assert_projections_stay_in_place, cell_shapes, decode_program,
    fits_one_chip, prefill_program, weights_program)

# 9 layers, 16 slots, 32 heads of 128 x 128 float32: 302 MB.
LINEAR_POOL = (9, 16, 32, 128, 128)
# 3 layers, 2 KV heads, 34,816 pages of 16 x 128: 0.86 GB each of k, v.
KV_POOL = (3, 2, 34816, PAGE, 128)


@pytest.fixture(scope="module")
def sala(v5e):
    return cell_shapes("minicpm-sala-L12", v5e)


def _calls(compiled):
    return [m["result"] for m in HLO_INSTRUCTION.finditer(compiled.as_text())
            if m["op"] == "custom-call"]


def test_lightning_step_kernel_compiles_for_v5e(v5e):
    """The decode kernel at the published shapes: 16 heads' states of 64
    KB a grid step, back through the output aliased to the pool; it
    writes five dimensions and five, by which the trace reader knows it."""
    from ray_tpu.ops import lightning_attention as la

    assert la.state_shape(9, 16, 32, 128) == LINEAR_POOL
    step = lambda q, k, v, g, pool, layer, active: la.lightning_step(  # noqa: E731
        q, k, v, g, pool, layer, active, scale=128 ** -0.5)
    compiled = jax.jit(step, donate_argnums=(4,)).lower(
        arr(v5e, (16, 32, 128)), arr(v5e, (16, 32, 128)),
        arr(v5e, (16, 32, 128)), arr(v5e, (16, 32), jnp.float32),
        arr(v5e, LINEAR_POOL, jnp.float32), arr(v5e, (), jnp.int32),
        arr(v5e, (16,), jnp.bool_)).compile()
    call, = _calls(compiled)
    assert re.match(r"\(f32\[16,1,32,1,128\]\S*, f32\[9,16,32,128,128\]",
                    call), call
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(LINEAR_POOL)
    assert memory.temp_size_in_bytes < 4 * math.prod(LINEAR_POOL[1:])


def test_lightning_scan_kernel_compiles_for_v5e(v5e):
    """The chunked prefill kernel at the cell's bucket: a head's state
    stays in VMEM over its 128 chunks; the float32 matmuls at "highest"
    lower for the chip."""
    from ray_tpu.ops import lightning_attention as la

    scan = lambda q, k, v, g: la.lightning_scan(  # noqa: E731
        q, k, v, g, scale=128 ** -0.5)
    compiled = jax.jit(scan).lower(
        *[arr(v5e, (32768, 32, 128))] * 3,
        arr(v5e, (32768, 32), jnp.float32)).compile()
    assert any(re.match(r"\(bf16\[32,1,32768,128\]\S*, f32\[32,128,128\]",
                        call) for call in _calls(compiled)), _calls(compiled)


def _dma_starts(jaxpr, loops=()):
    """Every ``dma_start`` of a traced function, each with the loops it
    stands in: ((primitive, static length or None) .., the equation)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dma_start":
            yield loops, str(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _dma_starts(inner, loops + (
                        (eqn.primitive.name, eqn.params.get("length")),))


def test_block_walk_kernel_compiles_for_v5e(v5e):
    """The walk under a selection at the cell's geometry: 32 (slot, KV
    head) units of up to 128 blocks, a block of one head a copy: a whole
    step of 128 pages issues 32 descriptors a pool, each a run of 4
    pages, where a page a descriptor was 128; the pools come back
    through aliased outputs; it writes four dimensions and five, five."""
    from ray_tpu.ops import block_attention as ba

    sizes = ba.BlockSizes(32, 16, 64, 1, 2048, 64, 8192)
    walk = lambda q, k, v, kp, vp, layer, table, lengths, active, chosen: (  # noqa: E731
        ba.paged_block_decode_attention(q, k, v, kp, vp, layer, table,
                                        lengths, active, chosen, sizes=sizes))
    operands = (
        arr(v5e, (16, 32, 128)), arr(v5e, (16, 2, 128)),
        arr(v5e, (16, 2, 128)), arr(v5e, KV_POOL), arr(v5e, KV_POOL),
        arr(v5e, (), jnp.int32), arr(v5e, (16, 2176), jnp.int32),
        arr(v5e, (16,), jnp.int32), arr(v5e, (16,), jnp.bool_),
        arr(v5e, (16, 2, 2176), jnp.bool_))
    compiled = jax.jit(walk, donate_argnums=(3, 4)).lower(*operands).compile()
    assert any(re.match(
        r"\(bf16\[16,2,16,128\]\S*, bf16\[3,2,34816,16,128\]", call)
        for call in _calls(compiled)), _calls(compiled)
    assert_pool_stays_in_place(compiled, KV_POOL, temporaries=False)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(KV_POOL)
    # The copies, from the traced text: every read of a pool is a run of
    # ``ratio`` 4 pages of one head into 4 pages of a step's buffer, and
    # the whole step's straight runs (the one loop of a static length)
    # issue a step's pages over the ratio, k and v.
    from ray_tpu.ops.paged_attention import walk_step_tokens

    step_pages = walk_step_tokens(2 * 128 * 2, PAGE, 512) // PAGE
    assert step_pages == 128
    starts = list(_dma_starts(jax.make_jaxpr(walk)(*operands).jaxpr))
    a_run = re.compile(r" \w+\[\w+,\w+,(\w+):\1\+4,:,:\] -> "
                       r"\w+\[\w+,(\w+):\2\+4,:,:\] ")
    a_page = re.compile(r" \w+\[\w+,\w+,:,:\] -> \w+\[\w+,\w+,\w+,:,:\] ")
    reads = [loops for loops, text in starts if a_run.search(text)]
    whole = [loops[-1][1] for loops in reads if loops[-1][1] is not None]
    assert sum(whole) == 2 * step_pages // sizes.ratio == 64
    # What is no run is the new row's page going back alone, k and v.
    assert [bool(a_page.search(text)) for _, text in starts
            if not a_run.search(text)] == [True, True]


def test_block_select_kernel_compiles_for_v5e(v5e):
    """The decode selection at the cell's geometry: a slot a grid step,
    2,176 pages along the lanes (17 tiles), rolled by one to four lanes;
    one int32 output of four dimensions."""
    from ray_tpu.ops import block_attention as ba

    sizes = ba.BlockSizes(32, 16, 64, 1, 2048, 64, 8192)
    assert ba.selectable(16, 128, 2176, sizes)
    select = lambda q, rows, lengths: ba.paged_block_select(  # noqa: E731
        q, rows, lengths, sizes=sizes)
    compiled = jax.jit(select).lower(
        arr(v5e, (16, 32, 128)), arr(v5e, (16, 2176, 256)),
        arr(v5e, (16,), jnp.int32)).compile()
    assert any(re.match(r"s32\[1,1,32,2176\]", call)
               for call in _calls(compiled)), _calls(compiled)


def test_block_flash_kernel_compiles_for_v5e(v5e, as_tpu):
    """The restricted flash attention at the cell's bucket: a KV head's
    16 query heads a step against 2,048 keys, the selection 32 blocks a
    step; one output of four dimensions."""
    from ray_tpu.ops import block_attention as ba

    sizes = ba.BlockSizes(32, 16, 64, 1, 2048, 64, 8192)
    assert ba.prefill_path(32768, 128, sizes) == "block_flash"
    attend = lambda q, k, v, chosen: ba.block_prefill_attention(  # noqa: E731
        q, k, v, chosen, sizes=sizes)
    compiled = jax.jit(attend).lower(
        arr(v5e, (32768, 32, 128)), arr(v5e, (32768, 2, 128)),
        arr(v5e, (32768, 2, 128)), arr(v5e, (2, 32768, 512), jnp.bool_),
    ).compile()
    assert any(re.match(r"bf16\[2,16,32768,128\]", call)
               for call in _calls(compiled)), _calls(compiled)


def test_sala_decode_program_compiles_for_v5e(v5e, as_tpu, sala):
    """Four scans over three pools and a rider: the k/v pool of the 3
    selected layers with its page means, the 9 lightning layers' states,
    each carried whole and updated in place beside 7.9 GB of weights."""
    cfg, engine, params, cache = sala
    assert {k: v.shape for k, v in cache.k.items()} == {
        "full": KV_POOL, "linear": LINEAR_POOL, "mean": (3, 34816, 256)}
    assert {k: v.shape for k, v in cache.v.items()} == {
        "full": KV_POOL, "mean": (3, 16, 256)}
    assert cache.page_table["full"].shape == (16, 2176)
    assert cache.page_table["linear"].shape == (16, 0)
    compiled = decode_program(cfg, v5e, params, cache)
    assert_projections_stay_in_place(compiled, params)
    assert fits_one_chip(compiled)
    text = compiled.as_text()
    assert "f32[16,1,32,1,128]" in text         # the lightning step
    assert "s32[1,1,32,2176]" in text           # the block selection
    assert "bf16[16,2,16,128]" in text          # the block walk
    assert_pool_stays_in_place(compiled, KV_POOL, temporaries=False)
    memory = compiled.memory_analysis()
    pools = 4 * math.prod(LINEAR_POOL) + 4 * math.prod(KV_POOL)
    assert memory.alias_size_in_bytes >= pools
    # Nothing the size of a layer of pages beside them: the slot's page
    # means gathered by its table (18 MB a layer) and their float32 forms.
    assert memory.temp_size_in_bytes < 2 * math.prod(KV_POOL[1:]) // 2
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_sala_prefill_program_compiles_for_v5e(v5e, as_tpu, sala):
    """The cell's one bucket, 32,768: the chunked lightning scan in 9
    layers, the selection and the restricted flash kernel in 3, all
    three pools of a slot laid from one prompt beside 9.9 GB held."""
    cfg, engine, params, cache = sala
    compiled = prefill_program(cfg, v5e, params, cache, 32768, {"linear": 0})
    text = compiled.as_text()
    assert "bf16[32,1,32768,128]" in text       # the lightning scan
    assert "bf16[2,16,32768,128]" in text       # the block flash
    assert fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(32768, memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_sala_weights_are_made_within_one_chip(v5e, sala):
    assert fits_one_chip(weights_program(sala[0], v5e))
