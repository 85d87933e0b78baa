"""Compile the main path's kernels and serving programs for a TPU v5e
that is described, not attached (on-chip-measurement guide, rehearsal 3).

The TPU compiler installed with JAX lowers Mosaic kernels and whole
programs for ``v5e:2x2`` without a chip, so what it would refuse on the
machine (a slice not aligned to the tiling, too much VMEM, a program over
HBM) fails here first, at no chip time. Nothing executes: these tests
say nothing about results or speed.

Shapes are the 8B-shaped config ``chip_smoke.py`` runs:
32 query / 8 KV heads of dim 128, hidden 4096, b8 x 2048 for training,
page 16 for serving.
"""

import dataclasses
import functools
import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.compilation_cache import (  # noqa: E402
    compilation_cache,
)
from jax.sharding import SingleDeviceSharding  # noqa: E402

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.models import generation  # noqa: E402
from ray_tpu.ops import paged_attention  # noqa: E402

# ray_tpu.ops re-exports the function under the module's own name.
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")

B, S, H, HKV, D = 8, 2048, 32, 8, 128
PAGE, POOL_PAGES, PAGES_PER_SEQ = 16, 4096, 64
# The chat cell's engine: 32 slots of 128 pages over a 2048-page pool.
CHAT_CELL, CHAT_POOL_PAGES = (32, 128), 2048
decode_shapes = pytest.mark.parametrize(
    "batch,pages_per_seq,pool_pages",
    [(B, PAGES_PER_SEQ, POOL_PAGES), (*CHAT_CELL, CHAT_POOL_PAGES)],
    ids=["b8", "chat-cell"],
)
BUCKET = 512


@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a described v5e 2x2 host."""
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    # A compile for a described device is written to the persistent
    # cache but cannot be read back without the chip (JAX warns and
    # recompiles); keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_host):
    """Sharding on one device of that host."""
    return SingleDeviceSharding(v5e_host[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The process's default backend is the CPU, so the kernels'
    platform dispatch would take the XLA path; steer it here."""
    monkeypatch.setattr(flash_mod, "_on_tpu", lambda: True)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _arr(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _serve_cfg():
    return LlamaConfig(
        vocab_size=32_768, hidden_size=4096, intermediate_size=14_336,
        num_layers=4, num_heads=H, num_kv_heads=HKV, dtype=jnp.bfloat16,
    )


def _serve_shapes(cfg, sharding, batch=B, pool_pages=POOL_PAGES,
                  pages_per_seq=PAGES_PER_SEQ):
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))
    )
    cache = jax.eval_shape(
        lambda: generation.PagedKVCache.create(
            cfg, batch, pool_pages, PAGE, pages_per_seq
        )
    )
    return _shapes(params, sharding), _shapes(cache, sharding)


def test_flash_forward_compiles_for_v5e(v5e, as_tpu):
    fn = jax.jit(
        lambda q, k, v: flash_mod.flash_attention(q, k, v, causal=True)
    )
    compiled = fn.lower(
        _arr(v5e, (B, S, H, D)), _arr(v5e, (B, S, HKV, D)),
        _arr(v5e, (B, S, HKV, D)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_backward_compiles_for_v5e(v5e, as_tpu):
    def loss(q, k, v):
        out = flash_mod.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _arr(v5e, (B, S, H, D)), _arr(v5e, (B, S, HKV, D)),
        _arr(v5e, (B, S, HKV, D)),
    ).compile()
    # Forward (for residuals) + the dQ kernel + the dK/dV kernel.
    assert compiled.as_text().count("tpu_custom_call") >= 3


# What may have a pool-shaped result in the decode program: the pool on
# its way into, round and out of the layer scan, and the kernel call
# whose aliased outputs carry it on.
_POOL_CARRIERS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                  "while"}
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.-]+ = (?P<result>.*?) (?P<op>[\w-]+)\((?P<rest>.*)$",
    re.MULTILINE)


def _assert_pool_stays_in_place(compiled, pool_shape, temporaries=True):
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
    for m in _HLO_INSTRUCTION.finditer(compiled.as_text()):
        if m["op"] in _POOL_CARRIERS or not any(
                shape in m["result"] for shape in shapes):
            continue
        if m["op"] == "custom-call" and "tpu_custom_call" in m["rest"]:
            continue
        offenders.append(m[0].strip()[:160])
    assert not offenders, offenders


@decode_shapes
def test_paged_decode_kernel_compiles_for_v5e(v5e, batch, pages_per_seq,
                                              pool_pages):
    """All 8 KV heads' 32 query rows of a slot in one program, which
    also writes the slot's new row: the pools go in whole and come back
    through aliased outputs."""
    pool = (4, HKV, pool_pages, PAGE, D)
    compiled = jax.jit(
        paged_attention.paged_decode_attention, donate_argnums=(3, 4)
    ).lower(
        _arr(v5e, (batch, H, D)),
        _arr(v5e, (batch, HKV, D)), _arr(v5e, (batch, HKV, D)),
        _arr(v5e, pool), _arr(v5e, pool), _arr(v5e, (), jnp.int32),
        _arr(v5e, (batch, pages_per_seq), jnp.int32),
        _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_pool_stays_in_place(compiled, pool)


@pytest.mark.parametrize("batch,heads,columns,window,layers", [
    (16, 28, 257, 4096, 6), (16, 28, 257, None, 6),
    (16, 28, 1024, None, 2), (16, 28, 1024, 4096, 2),
    (32, 32, 129, 2048, 5), (32, 32, 512, None, 1)],
    ids=["smallthinker-ring", "smallthinker-ring-as-table",
         "smallthinker-16k", "smallthinker-16k-window",
         "trinity-ring", "trinity-8k"])
def test_page_walk_compiles_at_the_long_context_cells_shapes(
        v5e, batch, heads, columns, window, layers):
    """The walk at 4 KV heads, where a compute step is 512 tokens
    (``walk_step_tokens``): SmallThinker's 28 query rows on 4 over rings
    of 257 columns and tables of 1,024, Trinity's 32 on 4 over 129 and
    512, with and without a ``window``; the buffers of such steps fit
    the VMEM a kernel has by default (nothing asks for more), and the
    pools come back through the aliased outputs."""
    assert paged_attention.walk_step_tokens(
        2 * 4 * D * 2, PAGE, columns) == 512
    pool = (layers, 4, batch * columns, PAGE, D)
    compiled = jax.jit(
        functools.partial(paged_attention.paged_decode_attention,
                          window=window),
        donate_argnums=(3, 4),
    ).lower(
        _arr(v5e, (batch, heads, D)),
        _arr(v5e, (batch, 4, D)), _arr(v5e, (batch, 4, D)),
        _arr(v5e, pool), _arr(v5e, pool), _arr(v5e, (), jnp.int32),
        _arr(v5e, (batch, columns), jnp.int32),
        _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_limit_bytes" not in text
    _assert_pool_stays_in_place(compiled, pool)


def _decode_program(cfg, v5e, batch, pool_pages, pages_per_seq):
    params, cache = _serve_shapes(cfg, v5e, batch, pool_pages,
                                  pages_per_seq)

    def decode(params, cache, tok, active):
        return generation.paged_decode(
            params, tok, cache, cfg, active=active
        )

    return jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_),
    ).compile(), cache.k["full"].shape


@decode_shapes
def test_paged_decode_program_compiles_for_v5e(v5e, as_tpu, batch,
                                               pages_per_seq, pool_pages):
    """The decode program as the code builds it for a TPU: the page walk
    inside the layer scan, chosen from platform and shape; the pool in
    the scan's carry, never sliced, re-stacked or re-laid."""
    assert paged_attention.decode_attention_path(PAGE, D) == "page_walk"
    compiled, pool = _decode_program(_serve_cfg(), v5e, batch, pool_pages,
                                     pages_per_seq)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_pool_stays_in_place(compiled, pool)
    # The donated pools are the outputs: both aliased at the entry.
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 2 * math.prod(pool)


@pytest.mark.parametrize("bucket,flash", [(64, False), (BUCKET, True)])
def test_paged_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket, flash):
    """Which causal attention a prefill bucket runs on the chip
    (``ops/flash_attention.py`` owns the rule): a several-hundred-token
    prompt lands in the 512 bucket, whole 128-row blocks, and runs the
    flash kernel; a bucket under a block (16, 32, 64) runs the XLA
    einsum. A dense model's prefill holds no other custom call."""
    cfg = _serve_cfg()
    params, cache = _serve_shapes(cfg, v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages
        )

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"full": _arr(v5e, (bucket // PAGE,), jnp.int32)},
    ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) is flash


def test_flash_falls_back_off_tpu():
    """Unsteered, the CPU process takes the XLA reference: the kernel
    is chosen by platform name, not by a user option."""
    assert flash_mod._on_tpu() is False
    cfg = dataclasses.replace(LlamaConfig.tiny(), use_flash=True)
    q = jnp.ones((1, 128, cfg.num_heads, cfg.dh), jnp.float32)
    k = jnp.ones((1, 128, cfg.num_kv_heads, cfg.dh), jnp.float32)
    text = jax.jit(
        lambda q, k, v: flash_mod.flash_attention(q, k, v, causal=True)
    ).lower(q, k, k).as_text()
    assert "tpu_custom_call" not in text


def _olmoe_cfg():
    """OLMoE-1B-7B at its published widths and the benchmark's depth of
    8: the configuration ``olmoe-1b-7b-0125-L8``."""
    return LlamaConfig(
        vocab_size=50_304, hidden_size=2048, intermediate_size=1024,
        num_layers=8, num_heads=16, num_kv_heads=16, head_dim=128,
        rope_theta=10_000.0, dtype=jnp.bfloat16, n_experts=64, top_k=8,
        qk_norm=True,
    )


def _fits_one_chip(compiled):
    memory = compiled.memory_analysis()
    return (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < 15.75 * 2**30


def test_olmoe_decode_program_compiles_for_v5e(v5e, as_tpu):
    """``serve-olmoe-c16``'s decode step with the grouped expert matmuls
    in the layer scan: 32 slots x 8 experts a token are 256 rows. The
    page walk at ``Hkv`` 16 leaves this pool in place too."""
    compiled, pool = _decode_program(_olmoe_cfg(), v5e, CHAT_CELL[0],
                                     CHAT_POOL_PAGES, CHAT_CELL[1])
    assert "tpu_custom_call" in compiled.as_text()  # the page walk
    assert _fits_one_chip(compiled)
    _assert_pool_stays_in_place(compiled, pool)


@pytest.mark.parametrize("groups,K,N,stacks", [
    (512, 2048, 1024, 2), (512, 1024, 2048, 1),
    (1024, 2048, 768, 2), (1024, 768, 2048, 1)],
    ids=["olmoe-trinity-in", "olmoe-trinity-down", "joyai-in", "joyai-down"])
def test_grouped_matmul_kernels_compile_for_v5e(v5e, groups, K, N, stacks):
    """The two calls a decode step's expert layer makes, at the three
    MoE cells' shapes: 256 rows against a stack of ``L * E`` experts,
    gate and up with the activation in one call and down in a second.
    Each is ONE custom call that writes one ``[256, N]`` array (the
    benchmark's readers find the grouped matmuls by that), beside the
    walk's scalar kernel; no operation has a result as large as one
    expert's weights, so the stack is read where it lies."""
    from ray_tpu.ops import grouped_matmul as gm

    def gated(h, g):
        return jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h

    def call(rows, group_sizes, *weights):
        return gm.small_rows_grouped_matmul(
            rows, weights, group_sizes, None, gated if stacks == 2 else None)

    compiled = jax.jit(call).lower(
        _arr(v5e, (256, K)), _arr(v5e, (groups,), jnp.int32),
        *[_arr(v5e, (groups, K, N))] * stacks).compile()
    calls = [m for m in _HLO_INSTRUCTION.finditer(compiled.as_text())
             if m["op"] == "custom-call" and "tpu_custom_call" in m["rest"]]
    wide = [m["result"] for m in calls if "bf16" in m["result"]]
    assert len(calls) == 2 and len(wide) == 1
    assert wide[0].startswith(f"bf16[256,{N}]")
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * K * N


# What would take a step's inputs or outputs through the host.
_HOST_OPS = {"send", "send-done", "recv", "recv-done", "infeed", "outfeed"}


@pytest.mark.parametrize("model,temperature", [
    ("dense", 0.0), ("olmoe", 0.0), ("dense", 0.7)])
def test_engine_decode_program_carries_tokens_and_key_on_the_device(
        v5e, as_tpu, model, temperature):
    """The program the engine jits (``serve/llm.py:serving_programs``)
    at the chat cells' engine shapes: it takes the last tokens, the
    active mask and the PRNG key and returns the last tokens and the
    key for the call after it beside the read-back (for a MoE model the
    expert load behind the tokens), so that the loop can queue step k+1
    before it has read step k. Around ``paged_decode`` the pool still
    stays in place (the PR 29 guard), and nothing in it calls the host."""
    from ray_tpu.serve.llm import serving_programs

    cfg = _olmoe_cfg() if model == "olmoe" else _serve_cfg()
    batch, pages_per_seq = CHAT_CELL
    params, cache = _serve_shapes(cfg, v5e, batch, CHAT_POOL_PAGES,
                                  pages_per_seq)
    decode_step, _ = serving_programs(cfg, temperature)
    args = (params, cache, _arr(v5e, (batch,), jnp.int32),
            _arr(v5e, (batch,), jnp.bool_), _arr(v5e, (2,), jnp.uint32))
    readback, _, last_tok, rng = jax.eval_shape(decode_step, *args)
    extra = cfg.n_experts + 1 if cfg.n_experts else 0
    assert (readback.shape, readback.dtype) == ((batch + extra,), jnp.int32)
    assert (last_tok.shape, last_tok.dtype) == ((batch,), jnp.int32)
    assert (rng.shape, rng.dtype) == ((2,), jnp.uint32)

    compiled = jax.jit(decode_step, donate_argnums=(1,)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the page walk
    pool = cache.k["full"].shape
    _assert_pool_stays_in_place(compiled, pool)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 2 * math.prod(pool)
    assert "callback" not in text.lower()
    assert not _HOST_OPS & {m["op"] for m in _HLO_INSTRUCTION.finditer(text)}


def test_olmoe_prefill_program_compiles_for_v5e(v5e, as_tpu):
    """The largest bucket, 2048 tokens: 16,384 rows through the grouped
    matmuls beside 7.1 GB of weights and the 2.1 GB pool."""
    cfg = _olmoe_cfg()
    params, cache = _serve_shapes(cfg, v5e, CHAT_CELL[0], CHAT_POOL_PAGES,
                                  CHAT_CELL[1])

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages
        )

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, 2048), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"full": _arr(v5e, (2048 // PAGE,), jnp.int32)},
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # flash prefill
    assert _fits_one_chip(compiled)


@pytest.mark.parametrize("bucket,kernel", [(16, True), (512, True),
                                           (1024, False)])
def test_olmoe_prefill_buckets_choose_their_grouped_matmul(v5e, as_tpu,
                                                           bucket, kernel):
    """8 x bucket rows over 64 experts: up to 64 rows an expert (the
    512-token bucket's 4096 rows) a prefill runs the kernel for few rows
    a group, as the decode step does; past it ``ragged_dot``'s own
    custom calls. Either way the grouped matmuls are the program's
    two-dimensional custom calls, rows by the expert's or the model's
    width."""
    from ray_tpu.ops import grouped_matmul as gm

    cfg = _olmoe_cfg()
    assert (gm.grouped_path(8 * bucket, cfg.n_experts) == "small_rows") \
        is kernel
    params, cache = _serve_shapes(cfg, v5e, CHAT_CELL[0], CHAT_POOL_PAGES,
                                  CHAT_CELL[1])

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages
        )

    text = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"full": _arr(v5e, (bucket // PAGE,), jnp.int32)},
    ).compile().as_text()
    calls = [m["result"] for m in _HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call" and "tpu_custom_call" in m["rest"]]
    grouped = [r for r in calls if r.startswith(f"bf16[{8 * bucket},")]
    assert len(grouped) == (2 if kernel else 3), calls
    assert ("ragged-dot" in text) is not kernel


def _dense_cfg(vocab_size=512):
    """The Nemo cell's trainer settings at tiny widths."""
    return LlamaConfig(
        vocab_size=vocab_size, hidden_size=256, intermediate_size=512,
        num_layers=4, num_heads=2, num_kv_heads=2, head_dim=128,
        dtype=jnp.bfloat16, remat_policy="dots", scan_layers=True,
        scan_chunk=2, loss_chunk=256,
    )


def _dense_train_step(cfg, mesh):
    """The text of ``cfg``'s train step under ``mesh``, b4 x 512 tokens,
    traced anew."""
    from ray_tpu.train.compiled_step import CompiledTrainStep

    step = CompiledTrainStep(cfg, mesh=mesh, learning_rate=1e-5)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    with jax.threefry_partitionable(True):
        state = jax.eval_shape(step._init, key)
        shardings = step._init.lower(key).compile().output_shardings
    params, opt_state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, shardings)
    tokens = jax.ShapeDtypeStruct((4, 513), jnp.int32,
                                  sharding=step.token_sharding())
    train = step._step.__wrapped_jit__.lower(params, opt_state, tokens)
    return train.compile().as_text()


def _dense_programs(v5e, mesh):
    """The texts of a dense model's train step under ``mesh`` and of its
    decode step on one device, each traced anew."""
    cfg = _dense_cfg()
    decode, _ = _decode_program(
        dataclasses.replace(cfg, remat_policy="none", scan_chunk=0), v5e,
        B, POOL_PAGES, PAGES_PER_SEQ)
    return _dense_train_step(cfg, mesh), decode.as_text()


def test_programs_without_experts_are_the_same_either_way(
        v5e, v5e_host, as_tpu, monkeypatch):
    """``train-nemo12b-4chip``, ``train-mistral7b-1chip``,
    ``serve-mistral7b-chat`` and ``serve-brumby-c16-8k`` run no expert
    layer, so nothing asks ops/grouped_matmul.py for a path: a dense
    model's train step under an ``fsdp=2 x tp=2`` mesh of a described
    v5e host, as the Nemo cell's, and its decode step compile to the
    same text whichever answer ``grouped_path`` would give. Those cells
    cannot tell a tree with the kernel from one without."""
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh(devices=v5e_host, dp=1, fsdp=2, tp=2)
    asked, texts = [], []
    for answer in ("small_rows", "ragged_dot"):
        monkeypatch.setattr(
            gm, "grouped_path",
            lambda *a, answer=answer, **k: asked.append(a) or answer)
        texts.append(_dense_programs(v5e, mesh))
    assert not asked
    assert texts[0] == texts[1]
    train, decode = texts[0]
    assert "all-reduce" in train or "all-gather" in train
    assert "tpu_custom_call" in train             # the flash kernels
    assert "ragged" not in train + decode


_COLLECTIVE = re.compile(
    r" = \(?\w+\[([\d,]+)\]\S* "
    r"(all-gather|all-reduce|reduce-scatter|fusion)(?:-start)?\(")


def _head_collectives(text, shapes):
    """``(kind, in_a_while_body)`` of every collective in a compiled
    step's text whose result has one of ``shapes``: an all-gather, an
    all-reduce, a reduce-scatter, or a fusion that wraps one (XLA:TPU's
    ``all-reduce-scatter``, which carries no collective's name)."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))  # counted at the call
    found, computation = [], None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            computation = computation.lstrip("%")
            continue
        m = _COLLECTIVE.search(line)
        if not m or m.group(1) not in shapes or computation in fused:
            continue
        kind = m.group(2)
        if kind == "fusion":
            wrapped = re.search(
                r"calls=%?[\w.\-]*(reduce-scatter|all-reduce|all-gather)",
                line)
            if not wrapped:
                continue
            kind = wrapped.group(1)
        found.append((kind, computation in bodies))
    return found


def test_the_head_is_gathered_once_a_step_not_once_a_loss_chunk(
        v5e_host, as_tpu):
    """``train-nemo12b-4chip``'s loss at tiny widths (vocabulary 4096, so
    that no other operand has the head's shapes; two loss chunks): under
    ``fsdp=2 x tp=2`` ``lm_head`` ``[256, 4096]`` lies ``[128, 2048]`` a
    chip and is ``[256, 2048]`` once gathered over ``fsdp``. No collective
    with either shape stands inside a ``while`` body, and the whole step
    has at most two such gathers and exactly one such reduction, the
    gradient's over ``fsdp`` after the backward scan.

    Fails on the tree before PR 55 (an all-gather in each loss scan's
    body and an ``all-reduce-scatter`` fusion in the backward one's: the
    head gathered and its gradient reduced once a chunk) and passes
    since (models/llama.py:causal_lm_loss)."""
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh(devices=v5e_host, dp=1, fsdp=2, tp=2)
    cfg = _dense_cfg(vocab_size=4096)
    M, V = cfg.hidden_size, cfg.vocab_size
    text = _dense_train_step(cfg, mesh)
    assert len(re.findall(r"body=", text)) >= 3   # layers and both losses
    found = _head_collectives(text, {f"{M},{V // 2}", f"{M // 2},{V // 2}"})
    assert found
    assert not [kind for kind, in_body in found if in_body], found
    kinds = [kind for kind, _ in found]
    assert 1 <= kinds.count("all-gather") <= 2, found
    assert len(kinds) - kinds.count("all-gather") == 1, found


_MOVES_ROWS = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def _collectives_in_loops(text):
    """``(kind, result shapes, body)`` of every collective that stands in
    a ``while`` body of a compiled step's text (an asynchronous one at
    its ``-start``), a fusion that wraps one under the wrapped kind."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    found, computation = [], None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            computation = line.split()[1 if line.startswith("ENTRY") else 0]
            computation = computation.lstrip("%")
            continue
        m = _HLO_INSTRUCTION.match(line)
        if computation not in bodies or not m:
            continue
        result, opcode = m["result"], m["op"]
        kind = opcode.removesuffix("-start")
        if opcode == "fusion":
            wrapped = re.search(
                r"calls=%?[\w.\-]*(reduce-scatter|all-reduce|all-gather)",
                line)
            kind = wrapped.group(1) if wrapped else None
        if kind in _MOVES_ROWS:
            found.append((kind, set(re.findall(r"\w+\[([\d,]+)\]", result)),
                          computation))
    return found


def test_the_residual_lies_over_tp_between_the_matmul_pairs(
        v5e_host, as_tpu):
    """``train-nemo12b-4chip``'s layers at tiny widths under
    ``fsdp=2 x tp=2``: a chip's residual is ``[b, S, M]`` = [2, 512, 256].
    No all-reduce (nor a fusion that wraps one) with that result stands
    in a ``while`` body, forward or backward: between a row-parallel
    matmul and the next column-parallel one the rows lie split over
    ``tp``, and what moves them there has ``[b, S/2, M]``, a
    collective-permute beside the matmuls in both of the layer scans'
    bodies (parallel/collective_matmul.py).

    Fails on the tree before PR 61: there each of the two bodies holds
    four all-reduces of ``[2, 512, 256]`` (``wo``'s and ``w_down``'s
    outputs summed whole, nothing running beside them) and nothing of
    ``[2, 256, 256]``."""
    from ray_tpu.parallel import make_mesh

    mesh = make_mesh(devices=v5e_host, dp=1, fsdp=2, tp=2)
    cfg = _dense_cfg()
    b, S, M = 4 // 2, 512, cfg.hidden_size
    found = _collectives_in_loops(_dense_train_step(cfg, mesh))
    whole, half = f"{b},{S},{M}", f"{b},{S // 2},{M}"
    assert not [(kind, body) for kind, shapes, body in found
                if kind == "all-reduce" and whole in shapes], found
    halves = [(kind, body) for kind, shapes, body in found
              if half in shapes]
    assert {kind for kind, _ in halves} <= {
        "collective-permute", "reduce-scatter", "all-gather"}, halves
    # Forward and backward scan, a hop a matmul site a layer or more.
    per_body = {body: sum(1 for _, at in halves if at == body)
                for _, body in halves}
    assert len(per_body) == 2 and min(per_body.values()) >= 8, per_body


def test_one_device_step_holds_no_collective(v5e_host, as_tpu):
    """``train-mistral7b-1chip``'s side of the same rule: on a mesh of
    one described v5e device every axis is pruned, the ring's size is 1
    and the compiled step's text holds no collective of any kind."""
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.collective_matmul import ring_size

    mesh = make_mesh(devices=v5e_host[:1], dp=1, fsdp=1, tp=1)
    assert ring_size(mesh, 512) == 1
    text = _dense_train_step(_dense_cfg(), mesh)
    assert "tpu_custom_call" in text              # the flash kernels
    assert not re.search("|".join(_MOVES_ROWS), text)


def _trinity():
    """``trinity-mini-L6`` as the benchmark builds it, and its engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "trinity-mini-L6.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _trinity_shapes(v5e):
    cfg, engine = _trinity()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


def test_trinity_decode_program_compiles_for_v5e(v5e, as_tpu):
    """Two pools, five scans: 5 window layers over rings of 129 pages a
    slot and one full layer over the 8192-page pool, 128 experts read in
    place. Neither pool is copied, sliced or re-stacked, and the weights
    of a run's layers are read where they lie."""
    cfg, engine, params, cache = _trinity_shapes(v5e)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "window": (5, 4, 32 * 129, PAGE, 128), "full": (1, 4, 8192, PAGE, 128)}
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    # The window pool is the larger: the temporaries' bound is its slice.
    _assert_pool_stays_in_place(compiled, cache.k["window"].shape)
    _assert_pool_stays_in_place(compiled, cache.k["full"].shape)
    pools = sum(2 * 2 * math.prod(p.shape) for p in cache.k.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


@pytest.mark.parametrize("bucket", [4096, 8192])
def test_trinity_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket):
    """The two buckets no cell had before: the flash kernel with a whole
    4096- or 8192-row K and V of a head in VMEM, with and without the
    window's lower bound, 8 x bucket rows through the grouped matmuls,
    beside 8.6 GB of weights and both pools."""
    cfg, engine, params, cache = _trinity_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"full": _arr(v5e, (bucket // PAGE,), jnp.int32),
         "window": _arr(v5e, (129,), jnp.int32)},
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert _fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


def _joyai():
    """``joyai-llm-flash-L5`` as the benchmark builds it, and its engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "joyai-llm-flash-L5.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _joyai_shapes(v5e):
    cfg, engine = _joyai()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


LATENT_POOL = (5, 8192, PAGE, 640)


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
        _arr(v5e, (batch, 32, 640)), _arr(v5e, (batch, 640)),
        _arr(v5e, LATENT_POOL), _arr(v5e, (), jnp.int32),
        _arr(v5e, (batch, pages_per_seq), jnp.int32),
        _arr(v5e, (batch,), jnp.int32), _arr(v5e, (batch,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_pool_stays_in_place(compiled, LATENT_POOL)


def test_joyai_decode_program_compiles_for_v5e(v5e, as_tpu):
    """Two scans (the dense layer, four expert layers) over the one
    latent pool, 256 experts a layer read in place: the pool is neither
    copied, sliced nor re-stacked, and no k or v pool exists."""
    cfg, engine, params, cache = _joyai_shapes(v5e)
    assert {k: v.shape for k, v in cache.k.items()} == {"latent": LATENT_POOL}
    assert cache.v == {}
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_pool_stays_in_place(compiled, LATENT_POOL)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * math.prod(LATENT_POOL)


@pytest.mark.parametrize("bucket", [2048, 8192])
def test_joyai_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket):
    """The cell's smallest and largest bucket: the flash kernel with a
    head's whole K (192 wide) and V (128 wide) in VMEM and no
    [32, bucket, bucket] of scores anywhere, 8 x bucket rows through the
    grouped matmuls of 1024 groups, beside 11.1 GB of weights."""
    cfg, engine, params, cache = _joyai_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"latent": _arr(v5e, (bucket // PAGE,), jnp.int32)},
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"f32[32,{bucket},{bucket}]" not in text
    assert f"f32[1,32,{bucket},{bucket}]" not in text
    assert _fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


def test_joyai_weights_are_made_within_one_chip(v5e):
    """``init_params`` as the benchmark jits it: the experts' leaves are
    drawn a layer at a time, so the float32 temporaries beside 11.1 GB
    of weights stay under the chip's 16 GB."""
    cfg, _ = _joyai()
    compiled = jax.jit(lambda key: init_params(cfg, key)).lower(
        _arr(v5e, (2,), jnp.uint32)).compile()
    assert _fits_one_chip(compiled)


def _glm52():
    """``glm-5.2-L5-ep16`` as the benchmark builds it, and its engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "glm-5.2-L5-ep16.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _glm52_shapes(v5e):
    cfg, engine = _glm52()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


GLM_POOLS = {"latent": (5, 8192, PAGE, 640), "index": (2, 8192, PAGE, 128)}


def test_glm52_decode_program_compiles_for_v5e(v5e, as_tpu):
    """Three scans (dense+indexing, three expert layers that share its
    selection, an expert layer that indexes) over the latent pool and
    the pool of indexer keys on the same page table: the index walk and
    the walk under a selection are custom calls, both pools stay in
    place, and 16 of 256 experts a layer are read in place."""
    cfg, engine, params, cache = _glm52_shapes(v5e)
    assert {k: v.shape for k, v in cache.k.items()} == GLM_POOLS
    assert cache.v == {} and set(cache.page_table) == {"latent"}
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    for kind, pool in GLM_POOLS.items():
        _assert_pool_stays_in_place(compiled, pool, kind == "latent")
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * sum(map(math.prod, GLM_POOLS.values()))


@pytest.mark.parametrize("bucket", [2048, 8192, 16384])
def test_glm52_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket):
    """A bucket that selects everything (the causal flash kernel, as
    JoyAI's) and the cell's two: the selection as int8 tiles, never a
    float32 [bucket, bucket], beside 7.76 GB of weights."""
    cfg, engine, params, cache = _glm52_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"latent": _arr(v5e, (bucket // PAGE,), jnp.int32)},
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    selects = bucket > cfg.index_topk
    assert not selects or f"f32[{bucket},{bucket}]" not in text
    assert f"f32[64,{bucket},{bucket}]" not in text
    assert (f"s8[{bucket // 128},{bucket // 512},128,512]" in text) \
        == selects
    assert _fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


def test_glm52_weights_are_made_within_one_chip(v5e):
    cfg, _ = _glm52()
    compiled = jax.jit(lambda key: init_params(cfg, key)).lower(
        _arr(v5e, (2,), jnp.uint32)).compile()
    assert _fits_one_chip(compiled)


def _brumby():
    """``brumby-14b-base-L6`` as the benchmark builds it, and its engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "brumby-14b-base-L6.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _brumby_shapes(v5e):
    cfg, engine = _brumby()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


# 6 layers, 16 slots, 8 KV heads, 65 turns of 136 rows of 128: 3.48 GB.
STATE_POOL = (6, 16, 8, 65, 136, 128)


def test_state_step_kernel_compiles_for_v5e(v5e):
    """The decode retention kernel at the published shapes: 40 query
    heads on 8 KV heads of 128, 16 slots; a (slot, KV head)'s state
    block of 4.5 MB goes through VMEM and comes back through the output
    aliased to the pool."""
    from ray_tpu.ops import retention

    assert retention.state_shape(6, 16, 8, 128) == STATE_POOL
    compiled = jax.jit(retention.state_step, donate_argnums=(4,)).lower(
        _arr(v5e, (16, 40, 128)), _arr(v5e, (16, 8, 128)),
        _arr(v5e, (16, 8, 128)), _arr(v5e, (16, 8), jnp.float32),
        _arr(v5e, STATE_POOL, jnp.float32), _arr(v5e, (), jnp.int32),
        _arr(v5e, (16,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(STATE_POOL)
    # Beside the pool: phi(q), phi(k) and the like, no second pool.
    assert memory.temp_size_in_bytes < 4 * math.prod(STATE_POOL[1:])


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_chunk_scan_kernel_compiles_for_v5e(v5e, bucket):
    """The chunked prefill kernel at the cell's smallest and largest
    bucket: a KV head's state stays in VMEM over its chunks."""
    from ray_tpu.ops import retention

    compiled = jax.jit(retention.chunk_scan).lower(
        _arr(v5e, (bucket, 40, 128)), _arr(v5e, (bucket, 8, 128)),
        _arr(v5e, (bucket, 8, 128)), _arr(v5e, (bucket, 8), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_brumby_decode_program_compiles_for_v5e(v5e, as_tpu):
    """One scan of six retention layers over the one pool of states: the
    pool is carried whole and updated in place, no KV pool exists, and
    7.08 GB of weights beside 3.48 GB of state fit the chip."""
    cfg, engine, params, cache = _brumby_shapes(v5e)
    assert {k: v.shape for k, v in cache.k.items()} == {"state": STATE_POOL}
    assert cache.v == {} and cache.page_table["state"].shape == (16, 0)
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(STATE_POOL)
    # Nothing pool-sized beside the pool: a copy would be 3.48 GB.
    assert memory.temp_size_in_bytes < 2 * math.prod(STATE_POOL)
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_brumby_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket):
    """The cell's smallest and largest bucket: the chunked scan a layer,
    the slot's states laid into the pool in place, beside 10.6 GB of
    weights and state."""
    cfg, engine, params, cache = _brumby_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"state": _arr(v5e, (0,), jnp.int32)},
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(STATE_POOL)
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


# ---- SmallThinker: 28 heads on 4, F S S S, a 16,384 bucket (PR 57) ---------

def _smallthinker():
    """``smallthinker-21b-a3b-L8`` as the benchmark builds it, and its
    engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "smallthinker-21b-a3b-L8.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _smallthinker_shapes(v5e):
    cfg, engine = _smallthinker()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


@pytest.mark.parametrize("window", [None, 4096])
def test_streamed_flash_forward_compiles_for_v5e(v5e, as_tpu, window):
    """16,384 keys of a head are over the VMEM a kernel gets without
    asking: the forward streams them, 28 query heads on 4, full and
    window, and asks for no more (no ``vmem_limit_bytes``); it writes
    the log-sum-exp first, by which the trace reader knows this form."""
    S, heads, kv_heads = 16384, 28, 4
    assert flash_mod.forward_path(S, S, D, D, heads, kv_heads, 2) == "streamed"
    assert flash_mod.forward_path(8192, 8192, D, D, heads, kv_heads,
                                  2) == "resident"
    compiled = jax.jit(lambda q, k, v: flash_mod.flash_attention(
        q, k, v, causal=True, window=window)).lower(
            _arr(v5e, (1, S, heads, D)), _arr(v5e, (1, S, kv_heads, D)),
            _arr(v5e, (1, S, kv_heads, D))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_limit_bytes" not in text
    call, = [m for m in _HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call"]
    assert re.match(r"\(f32\[28,1,16384\]\S*, bf16\[28,16384,128\]",
                    call["result"]), call["result"]


def test_smallthinker_decode_program_compiles_for_v5e(v5e, as_tpu):
    """Two pools, four scans (F, S S S, F, S S S): six window layers over
    rings of 257 pages a slot and two full layers over the 16,384-page
    pool, groups of 7 query heads in the page walk, 64 ReGLU experts
    read in place. Neither pool is copied, sliced or re-stacked."""
    cfg, engine, params, cache = _smallthinker_shapes(v5e)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "full": (2, 4, 16384, PAGE, 128),
        "window": (6, 4, 16 * 257, PAGE, 128)}
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    _assert_pool_stays_in_place(compiled, cache.k["full"].shape)
    _assert_pool_stays_in_place(compiled, cache.k["window"].shape,
                                temporaries=False)
    pools = sum(2 * 2 * math.prod(p.shape) for p in cache.k.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


@pytest.mark.parametrize("bucket", [8192, 16384])
def test_smallthinker_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket):
    """The resident form's largest bucket and the streamed form's: the
    flash kernel on the full and the window layers, 6 x bucket rows
    through the grouped matmuls, beside 7.9 GB of weights and both
    pools, inside the chip."""
    cfg, engine, params, cache = _smallthinker_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"full": _arr(v5e, (bucket // PAGE,), jnp.int32),
         "window": _arr(v5e, (257,), jnp.int32)},
    ).compile()
    text = compiled.as_text()
    assert "vmem_limit_bytes" not in text
    streamed = len(re.findall(r"f32\[28,1,16384\]\S*, bf16\[28,16384,128\]",
                              text))
    assert (streamed > 0) is (bucket == 16384)
    assert _fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries")


# ---- Kimi-Linear: delta states beside a latent pool, all 27 layers (PR 62) --

def _kimi():
    """``kimi-linear-48b-a3b-ep16`` as the benchmark builds it, and its
    engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "kimi-linear-48b-a3b-ep16.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _kimi_shapes(v5e):
    cfg, engine = _kimi()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


# 20 layers, 16 slots, 32 heads of 128 x 128: 0.67 GB.
DELTA_POOL = (20, 16, 32, 128, 128)


def test_delta_step_kernel_compiles_for_v5e(v5e):
    """The decode delta-rule kernel at the published shapes: a slot's 32
    states of 64 KB go through VMEM as one block of 2 MB and come back
    through the output aliased to the pool; it writes four dimensions
    and five, by which the trace reader knows it."""
    from ray_tpu.ops import delta_attention

    assert delta_attention.state_shape(20, 16, 32, 128) == DELTA_POOL
    compiled = jax.jit(delta_attention.delta_step, donate_argnums=(5,)).lower(
        _arr(v5e, (16, 32, 128)), _arr(v5e, (16, 32, 128)),
        _arr(v5e, (16, 32, 128)), _arr(v5e, (16, 32, 128), jnp.float32),
        _arr(v5e, (16, 32), jnp.float32), _arr(v5e, DELTA_POOL, jnp.float32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (16,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    call, = [m for m in _HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call"]
    assert re.match(r"\(f32\[16,1,32,128\]\S*, f32\[20,16,32,128,128\]",
                    call["result"]), call["result"]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(DELTA_POOL)
    # Beside the pool: the heads' vectors as columns, no second pool.
    assert memory.temp_size_in_bytes < 4 * math.prod(DELTA_POOL[1:])


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_delta_scan_kernel_compiles_for_v5e(v5e, bucket):
    """The chunked prefill kernel at the cell's smallest and largest
    bucket: a head's state stays in VMEM over its chunks; float32
    matmuls at "highest" and the product against a turned operand lower
    for the chip."""
    from ray_tpu.ops import delta_attention

    compiled = jax.jit(delta_attention.delta_scan).lower(
        _arr(v5e, (bucket, 32, 128)), _arr(v5e, (bucket, 32, 128)),
        _arr(v5e, (bucket, 32, 128)),
        _arr(v5e, (bucket, 32, 128), jnp.float32),
        _arr(v5e, (bucket, 32), jnp.float32),
    ).compile()
    calls = [m["result"] for m in _HLO_INSTRUCTION.finditer(
        compiled.as_text()) if m["op"] == "custom-call"]
    assert any(re.match(rf"\(bf16\[32,{bucket},128\]\S*, f32\[32,128,128\]",
                        call) for call in calls), calls


def test_kimi_decode_program_compiles_for_v5e(v5e, as_tpu):
    """Fifteen scans over three pools: the 20 delta layers' states and
    convolution histories and the 7 latent layers' rows, each carried
    whole and updated in place beside 8.6 GB of weights."""
    cfg, engine, params, cache = _kimi_shapes(v5e)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "delta": DELTA_POOL, "latent": (7, 16384, PAGE, 640)}
    assert {k: v.shape for k, v in cache.v.items()} == {
        "delta": (20, 3, 16, 12288)}
    assert cache.page_table["delta"].shape == (16, 0)
    assert cache.page_table["latent"].shape == (16, 1024)
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    text = compiled.as_text()
    assert "f32[16,1,32,128]" in text           # the delta step
    _assert_pool_stays_in_place(compiled, cache.k["latent"].shape)
    memory = compiled.memory_analysis()
    pools = (4 * math.prod(DELTA_POOL)
             + 2 * math.prod(cache.k["latent"].shape)
             + 2 * math.prod(cache.v["delta"].shape))
    assert memory.alias_size_in_bytes >= pools
    # Nothing the size of the states beside them: a copy would be 0.67 GB.
    assert memory.temp_size_in_bytes < 2 * math.prod(DELTA_POOL)
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket", [4096, 8192, 16384])
def test_kimi_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket):
    """The cell's three buckets: the chunked delta rule in 20 layers and
    the flash kernel (q.k 192 beside v 128) in 7, both pools of a slot
    laid from one prompt, beside 11.7 GB of weights, states and rows."""
    cfg, engine, params, cache = _kimi_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"delta": _arr(v5e, (0,), jnp.int32),
         "latent": _arr(v5e, (bucket // PAGE,), jnp.int32)},
    ).compile()
    text = compiled.as_text()
    assert f"bf16[32,{bucket},128]" in text     # the delta scan
    assert _fits_one_chip(compiled)
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_kimi_weights_are_made_within_one_chip(v5e):
    cfg, _ = _kimi()
    compiled = jax.jit(lambda key: init_params(cfg, key)).lower(
        _arr(v5e, (2,), jnp.uint32)).compile()
    assert _fits_one_chip(compiled)


# ---- Ouro-2.6B: 48 layers four times over, a pool 192 layers deep (PR 65) --

def _ouro():
    """``ouro-2.6b`` as the benchmark builds it, and its engine."""
    import json

    from benchmark import arch

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "ouro-2.6b.json")) as f:
        config = json.load(f)
    return arch.program_config(config), config["engine"]


def _ouro_shapes(v5e):
    cfg, engine = _ouro()
    params, cache = _serve_shapes(
        cfg, v5e, engine["max_batch"], engine["total_pages"],
        engine["max_len"] // PAGE)
    return cfg, engine, params, cache


def test_ouro_decode_program_compiles_for_v5e(v5e, as_tpu):
    """A scan over the four passes around the layer scan, over ONE set
    of stacked weights, each pass walking its own 48 layers of a pool of
    192: 8.05 GB carried whole through both scans and updated in place
    beside 5.34 GB of weights; the exit distribution comes back beside
    the logits. The temporaries are NOT under a layer's slice as in every
    other decode program: XLA moves a re-layout of the stacked q, k and v
    weights (3 x 0.2 GB, and as much again beside them) out of the pass
    loop, 1.13 GiB copied every step (PERF.md section 7, Open after
    PR 65); the bound here is what keeps a second such copy, or one of
    the pool, from passing unseen."""
    cfg, engine, params, cache = _ouro_shapes(v5e)
    pool = (4 * 48, 16, engine["total_pages"], PAGE, 128)
    assert {k: v.shape for k, v in cache.k.items()} == {"full": pool}
    assert cache.page_table["full"].shape == (8, 40)
    batch = engine["max_batch"]

    def decode(params, cache, tok, active):
        return generation.paged_decode(params, tok, cache, cfg, active=active)

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (batch,), jnp.int32),
        _arr(v5e, (batch,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled)
    _assert_pool_stays_in_place(compiled, pool, temporaries=False)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool)
    assert memory.temp_size_in_bytes < 1.25 * 2**30
    out = jax.eval_shape(decode, params, cache,
                         _arr(v5e, (batch,), jnp.int32),
                         _arr(v5e, (batch,), jnp.bool_))
    assert out[3].shape == (batch, 4) and out[3].dtype == jnp.float32
    print("decode", memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


@pytest.mark.parametrize("bucket,flash", [(64, False), (256, True)])
def test_ouro_prefill_program_compiles_for_v5e(v5e, as_tpu, bucket, flash):
    """The cell's smallest and largest bucket: every pass's k and v of
    48 layers laid into that pass's layers of the slot's pages, beside
    13.4 GB of weights and pool; the 256 bucket through the flash
    kernel at 16 x 128."""
    cfg, engine, params, cache = _ouro_shapes(v5e)

    def prefill(params, cache, tokens, real_len, slot, pages):
        return generation.paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages)

    compiled = jax.jit(prefill, donate_argnums=(1,)).lower(
        params, cache, _arr(v5e, (1, bucket), jnp.int32),
        _arr(v5e, (), jnp.int32), _arr(v5e, (), jnp.int32),
        {"full": _arr(v5e, (bucket // PAGE,), jnp.int32)},
    ).compile()
    assert _fits_one_chip(compiled)
    assert ("tpu_custom_call" in compiled.as_text()) == flash
    memory = compiled.memory_analysis()
    print(bucket, memory.temp_size_in_bytes / 2**30, "GiB of temporaries",
          memory.argument_size_in_bytes / 2**30, "GiB of arguments")


def test_ouro_weights_are_made_within_one_chip(v5e):
    cfg, _ = _ouro()
    compiled = jax.jit(lambda key: init_params(cfg, key)).lower(
        _arr(v5e, (2,), jnp.uint32)).compile()
    assert _fits_one_chip(compiled)
