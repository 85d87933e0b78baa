"""The block walk alone, on the chip: one layer's call of MiniCPM-SALA's
cell (16 slots, 2 KV heads of 128, a pool of 34,816 pages of 16, each
unit keeping 64 blocks of 4 pages past ``dense_len``) at contexts
8k-34k, and what its time is made of.

Two tables: ``fresh``, sixteen slots filled from a new free list by the
allocator of the tree given by ``--tree`` (``generation.KVBooks``;
default this tree), whose ids lie one after another as a benchmark run's
mostly do (a stack of freed pages keeps long stretches whole), and
``shuffled``, a permutation of the pool's runs of 4 pages, the worst a
long life of mixed lengths can make of it (good for either tree's walk).

Readings a table and a context (copies and arithmetic at 19k alone),
each the mean of N calls in one program whose every call waits for the
one before; a kernel's time is its device time in a profiler trace of
one such program:

- ``kernel_us``: the Pallas call of
  ``ops/block_attention.paged_block_decode_attention`` of that tree, the
  selection (``block_select_decode``) made once outside the program;
  ``whole_us`` the host's clock over the same program, a call: the
  kernel, the kept blocks put in order in XLA before it and the loop
  around both (what a microbenchmark adds, not the cell);
- ``copies_us``: the walk's copies and waits with no arithmetic, by a
  kernel of this file that issues them as the walk does (a step's
  descriptors in runs of eight, k and v, two buffers, one wait a pool a
  step) from the unit's own pages, once a page a descriptor
  (``pages_a_copy`` 1, the walk before PR 71) and once a block (4);
- ``arithmetic_us``: the walk's steps on buffers nothing is copied
  into: the scores of a KV head's 16 query rows over a step's 2,048
  tokens, the running softmax, the values.

The two kernels here stand outside the serving path so that the walk
itself carries no switch. A time is a device time: the script refuses
to run off a TPU.

Usage: python tools/time_block_walk.py [--tree DIR] [out.json]
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B, H, HKV, D, PAGE, PMAX, POOL = 16, 32, 2, 128, 16, 2176, 34816
RATIO, STEP_PAGES = 4, 128
CONTEXTS = (8200, 19000, 34000)
N = 20


def _copies_kernel(pid_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, *,
                   steps: int, pages_a_copy: int):
    """``steps`` steps of the walk's copies: pid_ref [steps * copies]
    the first page of each copy, a step's head its parity."""
    copies = STEP_PAGES // pages_a_copy

    def start(g):
        buf, head = g % 2, g % HKV

        def start_run(r, _):
            for j in range(8):
                c = r * 8 + j
                src = pl.ds(pid_ref[g * copies + c], pages_a_copy)
                dst = pl.ds(c * pages_a_copy, pages_a_copy)
                for hbm, ref, sem in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    pltpu.make_async_copy(hbm.at[0, head, src],
                                          ref.at[buf, dst],
                                          sems.at[sem, buf]).start()
            return 0

        jax.lax.fori_loop(0, copies // 8, start_run, 0)

    start(0)

    def body(g, _):
        @pl.when(g + 1 < steps)
        def _next():
            start(g + 1)

        for ref, sem in ((k_buf, 0), (v_buf, 1)):
            pltpu.make_async_copy(ref.at[g % 2], ref.at[g % 2],
                                  sems.at[sem, g % 2]).wait()
        return 0

    jax.lax.fori_loop(0, steps, body, 0)
    o_ref[...] = k_buf[0, 0] + v_buf[1, 0]


def _arithmetic_kernel(q_ref, o_ref, k_buf, v_buf, *, steps: int):
    """``steps`` steps of the walk's arithmetic over buffers of zeros."""
    G = q_ref.shape[2]
    k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
    v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    def body(g, carry):
        m, l, acc = carry
        buf = g % 2
        k = k_buf[buf].reshape(STEP_PAGES * PAGE, D)
        v = v_buf[buf].reshape(STEP_PAGES * PAGE, D)
        s = jax.lax.dot_general(
            q_ref[g % B, g % HKV], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at < 2047 - g, s * D ** -0.5, -1e30)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        prob = jnp.exp(s - m_new)
        l = alpha * l + prob.sum(axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(prob.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, steps, body, (
        jnp.full((G, 1), -1e30, jnp.float32), jnp.zeros((G, 1), jnp.float32),
        jnp.zeros((G, D), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _tables(generation, cfg):
    """{"fresh", "shuffled"}: [B, PMAX] tables of sixteen slots that
    hold the longest context each: by the tree's own allocator from a
    new free list, and a permutation of the pool's runs."""
    geometry = (cfg, B, POOL, PAGE, PMAX)
    books = generation.KVBooks(*geometry, jax.eval_shape(
        lambda: generation.PagedKVCache.create(*geometry)))
    for slot in range(B):
        if books.reserve(slot, PMAX * PAGE, PAGE) is None:
            raise SystemExit("the pool does not hold sixteen slots")
    runs = np.random.default_rng(0).permutation(POOL // RATIO)
    return {"fresh": books.tables["full"].copy(),
            "shuffled": (runs[:, None] * RATIO + np.arange(RATIO)).reshape(
                B, PMAX).astype(np.int32)}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("out", nargs="?")
    args = parser.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    from benchmark import trace_reduce
    from ray_tpu.models import LlamaConfig, generation
    from ray_tpu.ops import block_attention as ba

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a device time needs the chip, not {device}")

    sizes = ba.BlockSizes(32, 16, 64, 1, 2048, 64, 8192)
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=H * D, intermediate_size=64, num_layers=1,
        num_heads=H, num_kv_heads=HKV, head_dim=D, dtype=jnp.bfloat16,
        block_select=sizes)
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, HKV, POOL, PAGE, D)), dtype)
    k_pool, v_pool = pool(), pool()
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(B, HKV, D)), dtype)
                    for _ in range(2))
    means = jnp.asarray(rng.normal(size=(1, POOL, HKV * D)), dtype)
    sums = jnp.zeros((1, B, HKV * D), jnp.float32)
    active = jnp.ones((B,), bool)
    layer = jnp.asarray(0, jnp.int32)

    def timed(fn, *operands):
        jax.block_until_ready(fn(*operands))
        start = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        return 1e6 * (time.perf_counter() - start) / N

    def whole(table, lengths, kept):
        def run(kp, vp):
            def body(_, carry):
                kp, vp, acc = carry
                out, kp, vp = ba.paged_block_decode_attention(
                    q, k_new, v_new, kp, vp, layer, table, lengths, active,
                    kept, sizes=sizes)
                return kp, vp, acc + out.astype(jnp.float32).sum()
            return jax.lax.fori_loop(
                0, N, body, (kp, vp, jnp.zeros((), jnp.float32)))[2]
        return jax.jit(run)

    def kernel_us(fn, *operands):
        """The Pallas calls of one run of ``fn``, a call, from a trace."""
        jax.block_until_ready(fn(*operands))
        trace = tempfile.mkdtemp(prefix="walk-")
        jax.profiler.start_trace(trace)
        jax.block_until_ready(fn(*operands))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(
            trace, "plugins", "profile", "*", "*.xplane.pb"))
        ops = trace_reduce.reduce_trace(trace_reduce.load(path))["ops"]
        return {name: 1e6 * seconds / n for name, n, seconds in ops
                if name.startswith("pallas")}

    def alone_us(call, first, *rest):
        """The one kernel ``call`` alone, from a trace of N calls, each
        after the one before: the first operand waits for the last
        call's result (which no compiler can know is never negative)."""
        def run(first, *rest):
            def body(_, last):
                out = call(first + jnp.minimum(last, 0).astype(first.dtype),
                           *rest)
                return jnp.abs(out[0, 0]).astype(jnp.int32)
            return jax.lax.fori_loop(0, N, body, jnp.zeros((), jnp.int32))
        us, = kernel_us(jax.jit(run), first, *rest).values()
        return us

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kv_buf = pltpu.VMEM((2, STEP_PAGES, PAGE, D), dtype)

    def copies_us(first, pages_a_copy):
        """``first`` [steps, STEP_PAGES / RATIO]: each block's first page."""
        steps = first.shape[0]
        first = (first[..., None] + jnp.arange(0, RATIO, pages_a_copy)
                 ).reshape(-1).astype(jnp.int32)
        call = pl.pallas_call(
            functools.partial(_copies_kernel, steps=steps,
                              pages_a_copy=pages_a_copy),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,), in_specs=[hbm, hbm],
                out_specs=vmem,
                scratch_shapes=[kv_buf, kv_buf,
                                pltpu.SemaphoreType.DMA((2, 2))]),
            out_shape=jax.ShapeDtypeStruct((PAGE, D), dtype))
        return alone_us(call, first, k_pool, v_pool)

    def arithmetic_us(steps):
        call = pl.pallas_call(
            functools.partial(_arithmetic_kernel, steps=steps),
            in_specs=[vmem], out_specs=vmem,
            scratch_shapes=[kv_buf, kv_buf],
            out_shape=jax.ShapeDtypeStruct((H // HKV, D), dtype))
        return alone_us(call, q.reshape(B, HKV, H // HKV, D))

    results = {"device": device.device_kind, "tree": args.tree, "calls": N,
               "tables": {}}
    for order, table in _tables(generation, cfg).items():
        rows = results["tables"][order] = {}
        table = jnp.asarray(table)
        for context in CONTEXTS:
            lengths = jnp.full((B,), context, jnp.int32)
            kept = jax.jit(lambda: ba.block_select_decode(
                q, k_new, means, sums, layer, table, lengths, active,
                sizes=sizes)[0])()
            # Every unit's kept blocks' first pages, whole steps of them.
            blocks = np.asarray(kept)[..., ::RATIO]
            step_blocks = STEP_PAGES // RATIO
            first = np.concatenate([
                np.resize(np.asarray(table)[b, RATIO * np.flatnonzero(unit)],
                          -(-int(unit.sum()) // step_blocks) * step_blocks)
                for b, slot in enumerate(blocks) for unit in slot])
            # (Aligned, so that a run lies inside the pool whatever the
            # tree's table holds.)
            first = jnp.asarray(first.reshape(-1, step_blocks)
                                // RATIO * RATIO)
            program = whole(table, lengths, kept)
            row = {"blocks_a_unit": int(blocks.sum()) // (B * HKV),
                   "steps": first.shape[0],
                   "whole_us": timed(program, k_pool, v_pool),
                   "kernel_us": kernel_us(program, k_pool, v_pool)}
            if context == CONTEXTS[1]:
                row["copies_us"] = {str(n): copies_us(first, n)
                                    for n in (1, RATIO)}
                row["arithmetic_us"] = arithmetic_us(first.shape[0])
            rows[str(context)] = row
            print(order, context, json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
