"""What the ``test_tpu_compile_*.py`` files share: they compile the main
path's kernels and serving programs for a TPU v5e that is described, not
attached (on-chip-measurement guide, rehearsal 3).

The TPU compiler installed with JAX lowers Mosaic kernels and whole
programs for ``v5e:2x2`` without a chip, so what it would refuse on the
machine (a slice not aligned to the tiling, too much VMEM, a program over
HBM) fails here first, at no chip time. Nothing executes: these tests
say nothing about results or speed.

One file an architecture, because tier-1 is scheduled by file
(``--dist loadfile``) and ends no sooner than its longest one: a new
model adds ``tests/test_tpu_compile_<name>.py`` and lengthens no file
another model is in. The fixtures (``v5e_host``, ``v5e``, ``as_tpu``)
are ``tests/conftest.py``'s.

The dense shapes are the 8B-shaped config ``chip_smoke.py`` runs:
32 query / 8 KV heads of dim 128, hidden 4096, b8 x 2048 for training,
page 16 for serving.
"""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import LlamaConfig, generation, init_params, llama

B, S, H, HKV, D = 8, 2048, 32, 8, 128
PAGE, POOL_PAGES, PAGES_PER_SEQ = 16, 4096, 64
# The chat cell's engine: 32 slots of 128 pages over a 2048-page pool.
CHAT_CELL, CHAT_POOL_PAGES = (32, 128), 2048
decode_shapes = pytest.mark.parametrize(
    "batch,pages_per_seq,pool_pages",
    [(B, PAGES_PER_SEQ, POOL_PAGES), (*CHAT_CELL, CHAT_POOL_PAGES)],
    ids=["b8", "chat-cell"],
)


def shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def arr(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def serve_cfg():
    return LlamaConfig(
        vocab_size=32_768, hidden_size=4096, intermediate_size=14_336,
        num_layers=4, num_heads=H, num_kv_heads=HKV, dtype=jnp.bfloat16,
    )


def olmoe_cfg():
    """OLMoE-1B-7B at its published widths and the benchmark's depth of
    8: the configuration ``olmoe-1b-7b-0125-L8``."""
    return LlamaConfig(
        vocab_size=50_304, hidden_size=2048, intermediate_size=1024,
        num_layers=8, num_heads=16, num_kv_heads=16, head_dim=128,
        rope_theta=10_000.0, dtype=jnp.bfloat16, n_experts=64, top_k=8,
        qk_norm=True,
    )


def serve_shapes(cfg, sharding, batch=B, pool_pages=POOL_PAGES,
                 pages_per_seq=PAGES_PER_SEQ):
    """``(params, cache)`` as an engine holds them: the serving tree."""
    params = jax.eval_shape(
        lambda: llama.serving_tree(init_params(cfg, jax.random.PRNGKey(0)))
    )
    cache = jax.eval_shape(
        lambda: generation.PagedKVCache.create(
            cfg, batch, pool_pages, PAGE, pages_per_seq
        )
    )
    return shapes(params, sharding), shapes(cache, sharding)


def configured(name):
    """``benchmark/configs/<name>.json`` as the benchmark builds it, and
    its engine."""
    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            name + ".json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def cell_shapes(name, sharding):
    """``(cfg, engine, params, cache)`` of that configuration at its
    engine's own geometry; a file asks once (a module-scoped fixture)
    and every program of the architecture is lowered against it."""
    cfg, engine = configured(name)
    params, cache = serve_shapes(
        cfg, sharding, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


def decode_program(cfg, sharding, params, cache):
    """``paged_decode`` over every slot of ``cache``, compiled with the
    cache donated."""
    batch = cache.lengths.shape[0]

    def decode(params, cache, tok, active):
        return generation.paged_decode(
            params, tok, cache, cfg, active=active
        )

    return jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, arr(sharding, (batch,), jnp.int32),
        arr(sharding, (batch,), jnp.bool_),
    ).compile()


def prefill_program(cfg, sharding, params, cache, bucket, columns=None):
    """``paged_prefill`` of one ``bucket``, compiled with the cache
    donated. ``columns``: the table columns handed over for each pool
    kind that is not laid a page a 16 tokens of the bucket (a ring's
    columns; 0 for a pool of slots)."""
    pages = {kind: bucket // PAGE for kind in cache.page_table}
    pages.update(columns or {})

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages
        )

    return jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, arr(sharding, (1, bucket), jnp.int32),
        arr(sharding, (), jnp.int32), arr(sharding, (), jnp.int32),
        {kind: arr(sharding, (n,), jnp.int32) for kind, n in pages.items()},
    ).compile()


def weights_program(cfg, sharding):
    """``init_params`` as the benchmark jits it."""
    return jax.jit(lambda key: init_params(cfg, key)).lower(
        arr(sharding, (2,), jnp.uint32)).compile()


def fits_one_chip(compiled):
    memory = compiled.memory_analysis()
    return (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 15.75 * 2**30


# What may have a pool-shaped result in the decode program: the pool on
# its way into, round and out of the layer scan, and the kernel call
# whose aliased outputs carry it on.
_POOL_CARRIERS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                  "while"}
HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.-]+) = (?P<result>.*?) "
    r"(?P<op>[\w-]+)\((?P<rest>.*)$", re.MULTILINE)


def assert_pool_stays_in_place(compiled, pool_shape, temporaries=True):
    """The guard against pool-sized copies in a decode step (ROADMAP
    S5; 70% of the step before PR 29): the program's temporaries are
    under one layer's slice of one pool (``temporaries``: asked of a
    model's largest pool), and no instruction of the
    optimized HLO but the pool's carriers and the kernel call has a
    result of the pool's or a layer slice's shape."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert not temporaries or temp < 2 * math.prod(pool_shape[1:]), \
        f"{temp} B of temporaries"
    shapes = ["bf16[%s]" % ",".join(map(str, dims))
              for dims in (pool_shape, pool_shape[1:], (1, *pool_shape[1:]))]
    offenders = []
    for m in HLO_INSTRUCTION.finditer(compiled.as_text()):
        if m["op"] in _POOL_CARRIERS or not any(
                shape in m["result"] for shape in shapes):
            continue
        if m["op"] == "custom-call" and "tpu_custom_call" in m["rest"]:
            continue
        offenders.append(m[0].strip()[:160])
    assert not offenders, offenders


_HLO_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def _array_sizes(text):
    """``{(dtype, elements)}`` of every array type written in ``text``."""
    return {(dtype, math.prod(int(d) for d in dims.split(",") if d))
            for dtype, dims in _HLO_ARRAY.findall(text)}


def assert_projections_stay_in_place(compiled, params):
    """The guard against a re-laid weight in a serving program (ROADMAP
    S10(vii); Ouro's 3 x 1.22 ms of a 40 ms step before PR 69): no
    ``copy`` and no copy fusion (``copy_bitcast_fusion``) of the
    optimized HLO has a stacked projection of ``llama.SERVING_ORDER`` as
    its operand or result: whole, or one layer's slice, told by the
    element type and the count of elements so that a copy of a reshaped
    leaf is seen too. An asynchronous ``copy-start`` of a layer's slice
    is a prefetch into another memory space in the SAME layout, and is
    not counted. ``params`` is the tree the program was compiled for."""
    stacks = (llama.turning_leaves(params, back=True),
              llama.turning_leaves(params))
    sizes = set()
    for w in (w for kind in stacks for stack in kind
              for w in stack.values()):
        dtype = {"bfloat16": "bf16", "float32": "f32"}[w.dtype.name]
        sizes |= {(dtype, math.prod(w.shape)), (dtype, math.prod(w.shape[1:]))}
    assert sizes, "the tree has no stacked projection"
    text = compiled.as_text()
    results = {m["name"]: m["result"] for m in HLO_INSTRUCTION.finditer(text)}
    offenders = []
    for m in HLO_INSTRUCTION.finditer(text):
        if m["op"] != "copy" and not (m["op"] == "fusion"
                                      and "copy" in m["name"]):
            continue
        touched = _array_sizes(m["result"])
        for operand in re.findall(r"%([\w.-]+)", m["rest"].split(")")[0]):
            touched |= _array_sizes(results.get(operand, ""))
        if touched & sizes:
            offenders.append(f"{m['name']} = {m['result'][:80]}")
    assert not offenders, offenders
