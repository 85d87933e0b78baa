"""The decode retention kernel alone, on the chip: one layer's call of
Brumby's cell (16 slots, 40 query heads on 8 KV heads of 128, the pool
of states ``[6, 16, 8, 65, 136, 128]`` float32, a (slot, KV head)'s block
4.526 MB) at 16, 12 and 1 active slots, and what its time is made of.

The kernel is ``ops/retention.state_step`` of the tree given by
``--tree`` (default this tree). Readings an active count, each the mean
of N calls in one program whose every call waits for the one before, a
kernel's time its device time in a profiler trace of one such program:

- ``kernel_us``: the tree's Pallas call; ``phi_us``: what XLA runs
  beside it a call (before PR 72 the fusions that make ``phi(q)`` and
  ``phi(k)`` and turn them; the operands' stacking; this script's own
  add that makes a call wait for the last), ``xla_ops_us`` by name;
- ``copies_us``: the tree's own call (its grid, its block specs, its
  prefetched scalars, its aliased pool) around a body that touches one
  tile of the block, so that every block is brought in and written back
  and nothing is computed;
- ``arithmetic_us``: the tree's own kernel body on two blocks of VMEM
  nothing is copied into, the small operands brought as in the call;
- ``plain_us``: a read-and-write of the same blocks by a pipeline with
  nothing else in it: grid (active slots, KV heads), one block in, the
  same block out, aliased, the body one assignment.

``us_a_block`` divides each by the blocks the call moves. The copy-only
and arithmetic-only calls are built here from what the tree hands
``pl.pallas_call`` (taken by standing in for it once): they stand
outside the serving path so that the kernel carries no switch. They
rely on the kernel's contract: the pool is the call's last operand and
its second result, the read-outs the first. A time is a device time:
the script refuses to run off a TPU.

Usage: python tools/time_state_step.py [--tree DIR] [out.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B, H, HKV, D, LAYERS, LAYER = 16, 40, 8, 128, 6, 3
ACTIVE = (16, 12, 1)
N = 20
# A v5e's HBM (Google Cloud documentation, "TPU v5e").
HBM_BYTES_S = 819e9


def _taken(retention, *args):
    """What ``state_step`` hands ``pl.pallas_call``: (the kernel, the
    call's keywords, its operands but the pool)."""
    seen = {}

    def stand_in(kernel, **kw):
        def call(*operands):
            seen.update(kernel=kernel, kw=kw, operands=operands[:-1])
            return [jnp.zeros(s.shape, s.dtype) for s in kw["out_shape"]]
        return call

    real, pl.pallas_call = pl.pallas_call, stand_in
    try:
        retention.state_step(*args)
    finally:
        pl.pallas_call = real
    return seen["kernel"], seen["kw"], seen["operands"]


def _ops_us(reduce, fn, *operands, donate=False):
    """(every leaf operation of one run of ``fn``, us a call, from a
    trace reduced by ``reduce``; the run's results). A donated first
    operand comes back as the first result."""
    fn = jax.jit(fn, donate_argnums=(0,) if donate else ())
    for tracing in (False, True):
        if tracing:
            trace = tempfile.mkdtemp(prefix="state-")
            jax.profiler.start_trace(trace)
        out = jax.block_until_ready(fn(*operands))
        if donate:
            operands = (out[0],) + operands[1:]
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        trace, "plugins", "profile", "*", "*.xplane.pb"))
    return {name: 1e6 * seconds / N for name, _, seconds in reduce(path)}, out


def _pallas_us(ops):
    """The program's longest custom call (XLA may have one of its own,
    a few microseconds)."""
    return max(t for name, t in ops.items() if name.startswith("pallas"))


def _waits(x, last):
    """``x``, once ``last`` is known (which no compiler can know is never
    negative)."""
    return x + jnp.minimum(last, 0).astype(x.dtype)


def measure(retention, reduce, pool, n_active):
    """(the readings at ``n_active`` slots, the pool)."""
    T, R = pool.shape[-3:-1]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (B, H, D)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, HKV, D)).astype(jnp.bfloat16)
            for key in keys[1:3])
    log_g = jax.nn.log_sigmoid(jax.random.normal(keys[3], (B, HKV)) + 4.0)
    layer = jnp.asarray(LAYER, jnp.int32)
    active = jnp.arange(B) < n_active
    pool_shape = jax.ShapeDtypeStruct(pool.shape, pool.dtype)

    def step(pool, q):
        def body(_, carry):
            pool, last = carry
            y, pool = retention.state_step(
                _waits(q, last), k, v, log_g, pool, layer, active)
            return pool, jnp.abs(y[0, 0, 0]).astype(jnp.float32)
        return jax.lax.fori_loop(
            0, N, body, (pool, jnp.zeros((), jnp.float32)))

    ops, (pool, _) = _ops_us(reduce, step, pool, q, donate=True)
    row = {"kernel_us": _pallas_us(ops)}
    xla = {name: t for name, t in ops.items() if t != row["kernel_us"]}
    row = {**row, "phi_us": sum(xla.values()),
           "xla_ops_us": {name: t for name, t in xla.items() if t >= 0.5}}

    taken = {}

    def operands():
        taken["kernel"], taken["kw"], small = _taken(
            retention, q, k, v, log_g, pool_shape, layer, active)
        return small

    small = jax.jit(operands)()
    kernel, kw = taken["kernel"], taken["kw"]
    spec = kw["grid_spec"]
    # A body's references: the prefetched scalars and the small operands,
    # then the pool's block, the read-outs, the pool's block again.
    n_small = spec.num_scalar_prefetch + len(spec.in_specs) - 1

    def touch(*refs):
        s_in, y_ref, s_out = refs[n_small:n_small + 3]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
        s_out[0, 0:8, :] = s_in[0, 0:8, :]

    copies = pl.pallas_call(touch, **kw)
    ops, (pool,) = _ops_us(reduce, lambda pool, small: (jax.lax.fori_loop(
        0, N, lambda _, pool: copies(*small, pool)[1], pool),),
        pool, small, donate=True)
    row["copies_us"] = _pallas_us(ops)

    def on_scratch(*refs):
        y_ref, s_a, s_b = refs[n_small:n_small + 3]

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _first():
            s_a[...] = jnp.zeros(s_a.shape, s_a.dtype)

        kernel(*refs[:n_small], s_a, y_ref, s_b, *refs[n_small + 3:])

    state = pltpu.VMEM((T, R, D), jnp.float32)
    arithmetic = pl.pallas_call(
        on_scratch,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=spec.num_scalar_prefetch, grid=spec.grid,
            in_specs=list(spec.in_specs)[:-1], out_specs=spec.out_specs[0],
            scratch_shapes=[state, state, *spec.scratch_shapes]),
        out_shape=kw["out_shape"][0], compiler_params=kw["compiler_params"])
    at = spec.num_scalar_prefetch          # the first operand of floats

    def alone(small):
        def body(_, last):
            y = arithmetic(*small[:at], _waits(small[at], last),
                           *small[at + 1:])
            return jnp.abs(y[0, 0, 0, 0])
        return jax.lax.fori_loop(0, N, body, jnp.zeros((), jnp.float32))

    row["arithmetic_us"] = _pallas_us(_ops_us(reduce, alone, small)[0])

    block = pl.BlockSpec((None, None, None, T, R, D),
                         lambda b, n: (LAYER, b, n, 0, 0, 0))

    def assign(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    plain = pl.pallas_call(
        assign, grid=(n_active, HKV), in_specs=[block], out_specs=block,
        out_shape=pool_shape, input_output_aliases={0: 0},
        compiler_params=kw["compiler_params"])
    ops, (pool,) = _ops_us(reduce, lambda pool: (jax.lax.fori_loop(
        0, N, lambda _, pool: plain(pool), pool),), pool, donate=True)
    row["plain_us"] = _pallas_us(ops)
    row["us_a_block"] = {
        name[:-3]: row[name] / (n_active * HKV) for name in (
            "kernel_us", "copies_us", "arithmetic_us", "plain_us")}
    return row, pool


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    parser.add_argument("out", nargs="?")
    args = parser.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    from benchmark import trace_reduce
    from ray_tpu.ops import retention

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a device time needs the chip, not {device}")

    def reduce(path):
        return trace_reduce.reduce_trace(trace_reduce.load(path))["ops"]

    shape = retention.state_shape(LAYERS, B, HKV, D)
    pool = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(1), shape))()
    block_bytes = 4 * math.prod(shape[3:])
    results = {"device": device.device_kind, "tree": args.tree, "calls": N,
               "block_bytes": block_bytes,
               "bytes_us_a_block": 2e6 * block_bytes / HBM_BYTES_S,
               "active": {}}
    for n_active in ACTIVE:
        row, pool = measure(retention, reduce, pool, n_active)
        results["active"][str(n_active)] = row
        print(n_active, json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
