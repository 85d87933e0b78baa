"""The kernels every architecture shares, each alone, compiled for a
described v5e (tests/tpu_rehearsal.py): the flash forward and backward,
the streamed forward, the page walk and the grouped matmuls."""

import dataclasses
import functools
import importlib
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig  # noqa: E402
from ray_tpu.ops import paged_attention  # noqa: E402
from tpu_rehearsal import (  # noqa: E402
    B, D, H, HKV, HLO_INSTRUCTION, PAGE, S, arr, assert_pool_stays_in_place,
    decode_shapes)

# ray_tpu.ops re-exports the function under the module's own name.
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")


def test_flash_forward_compiles_for_v5e(v5e, as_tpu):
    fn = jax.jit(
        lambda q, k, v: flash_mod.flash_attention(q, k, v, causal=True)
    )
    compiled = fn.lower(
        arr(v5e, (B, S, H, D)), arr(v5e, (B, S, HKV, D)),
        arr(v5e, (B, S, HKV, D)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_backward_compiles_for_v5e(v5e, as_tpu):
    def loss(q, k, v):
        out = flash_mod.flash_attention(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arr(v5e, (B, S, H, D)), arr(v5e, (B, S, HKV, D)),
        arr(v5e, (B, S, HKV, D)),
    ).compile()
    # Forward (for residuals) + the dQ kernel + the dK/dV kernel.
    assert compiled.as_text().count("tpu_custom_call") >= 3


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
            arr(v5e, (1, S, heads, D)), arr(v5e, (1, S, kv_heads, D)),
            arr(v5e, (1, S, kv_heads, D))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_limit_bytes" not in text
    call, = [m for m in HLO_INSTRUCTION.finditer(text)
             if m["op"] == "custom-call"]
    assert re.match(r"\(f32\[28,1,16384\]\S*, bf16\[28,16384,128\]",
                    call["result"]), call["result"]


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
        arr(v5e, (batch, H, D)),
        arr(v5e, (batch, HKV, D)), arr(v5e, (batch, HKV, D)),
        arr(v5e, pool), arr(v5e, pool), arr(v5e, (), jnp.int32),
        arr(v5e, (batch, pages_per_seq), jnp.int32),
        arr(v5e, (batch,), jnp.int32),
        arr(v5e, (batch,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert_pool_stays_in_place(compiled, pool)


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
        arr(v5e, (batch, heads, D)),
        arr(v5e, (batch, 4, D)), arr(v5e, (batch, 4, D)),
        arr(v5e, pool), arr(v5e, pool), arr(v5e, (), jnp.int32),
        arr(v5e, (batch, columns), jnp.int32),
        arr(v5e, (batch,), jnp.int32),
        arr(v5e, (batch,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vmem_limit_bytes" not in text
    assert_pool_stays_in_place(compiled, pool)


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
        arr(v5e, (256, K)), arr(v5e, (groups,), jnp.int32),
        *[arr(v5e, (groups, K, N))] * stacks).compile()
    calls = [m for m in HLO_INSTRUCTION.finditer(compiled.as_text())
             if m["op"] == "custom-call" and "tpu_custom_call" in m["rest"]]
    wide = [m["result"] for m in calls if "bf16" in m["result"]]
    assert len(calls) == 2 and len(wide) == 1
    assert wide[0].startswith(f"bf16[256,{N}]")
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * K * N
