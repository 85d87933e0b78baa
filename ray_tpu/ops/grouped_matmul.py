"""A grouped matmul for few rows a group: a decode step's experts.

``grouped_matmul(m, group_sizes)`` gives the function that multiplies
``m`` sorted rows by their groups' weights: ``matmul(rows, weights,
epilogue)`` multiplies the rows ``[m, K]`` of each group by that group's
``[K, N]`` of every stack in ``weights`` (each ``[G, K, N]``), rounds
each product to the rows' dtype as ``jax.lax.ragged_dot`` does, and
returns ``epilogue(*products)`` ``[m, N]`` (the products' first without
one). A mixture of experts (parallel/moe.py) calls it twice a layer:
gate and up with the activation as the epilogue, then down.

Two implementations, chosen by :func:`grouped_path` from what the code
can see (platform, rows, experts, mesh), never by a user:

``"ragged_dot"`` — ``jax.lax.ragged_dot`` a stack, then the epilogue:
any platform, any shape, any mesh (GSPMD partitions it). XLA's TPU
compiler tiles it at 256 rows (read in the compiled decode program:
``ragged_dot_tiling="256,512,512"``), so a decode step's 256 rows are
ONE row tile and every reached group, with its 1.4 to 2.3 rows, is
multiplied as 256: ~100 x the FLOPs, more time than reading the group's
weights takes (8.3 us a group against 5.1 at HBM's rate; ledger, PR 45).

``"small_rows"`` — the Pallas TPU kernel here, for a TPU, one device
and up to ``MAX_ROWS`` rows at ``ROWS_PER_EXPERT`` a group. Row tiles of
:func:`row_tile` rows; the grid walks VISITS, the (row tile, group)
pairs in which a group has a row, in row order: empty groups (every
other layer's of a ``[L * E]`` stack, every expert no row reached) and
row tiles behind the last group are never visited, and the number of
visits is the grid's own, dynamic, size. The walk (:func:`visits`) is a
scalar kernel of its own, run once for all the calls over the same
groups. A visit brings the group's weights in where they lie in the
stack, the whole contraction by all the columns where that fits (4 MB
at 2048 x 1024: contiguous, and a grid step's ~0.35 us is little beside
its DMA), multiplies the row tile by each stack's block (operands as
they come, bfloat16 to the MXU, float32 accumulation over the whole
contraction in one dot), rounds, applies the epilogue and stores the
group's rows under a mask; a group that straddles two row tiles is
visited twice running with the same weight block, which the pipeline
does not fetch again. The rows are read once for all stacks and the
products never leave the chip. One output, ``[m, N]``: the benchmark's
readers find a grouped matmul by that (benchmark/readers/moe.py).

Rounding. A product is rounded to the rows' dtype where ``ragged_dot``
writes it as an array of that dtype; the epilogue is then given the
rounded values in float32 and its result is rounded once at the store.
That is what XLA's fusion of the same source computes on the TPU, which
keeps an elementwise chain in float32 between the arrays it reads and
writes (``xla_allow_excess_precision``): one product of the kernel is
``ragged_dot``'s bit for bit on the v5e (chip run, PR 47: PERF.md §6).

Differentiation: the kernel has no transpose rule. It sits behind a
``jax.custom_vjp`` whose backward is the ``ragged_dot`` path's own
(``jax.vjp`` of it on the saved operands, so the forward is computed
again there): right whoever differentiates, at the price of a second
forward that no training step of this repo's shapes pays.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# The kernel where rows <= MAX_ROWS and rows <= ROWS_PER_EXPERT * experts:
# the rule stops where the measurements do. On the v5e over rows 256 ..
# 4096 x experts 64 .. 256 (PERF.md §6, PR 47) the kernel takes 0.43 to
# 0.67 of ragged_dot's time at every point, 64 rows an expert among them,
# so the crossover lies past them; ragged_dot's 256-row tiles run near
# the MXU's peak at a prefill's thousands of rows a group.
MAX_ROWS = 4096
ROWS_PER_EXPERT = 64
# Weight blocks in flight (each stack's, double-buffered) stay under
# this; the kernel asks for what it counts plus room for the rest.
_WEIGHT_VMEM = 40 * 2 ** 20
_OTHER_VMEM = 8 * 2 ** 20


def row_tile(m: int) -> Optional[int]:
    """Rows a tile of the kernel holds for ``m`` rows: the largest of
    128 .. 16 (whole bfloat16 sublane tiles) that divides them, or None.
    Up to 128 rows the MXU's time a visit is its weight loads', the same
    for any tile, and a larger tile is crossed by fewer groups (chip
    run, PR 47: 5.80, 5.73, 5.69, 5.70 us a group a matmul at 16, 32,
    64, 128 rows a tile)."""
    return next((t for t in (128, 64, 32, 16) if m % t == 0), None)


def grouped_path(rows: int, experts: int, mesh=None) -> str:
    """``"small_rows"`` or ``"ragged_dot"``: what :func:`grouped_matmul`
    runs here for ``rows`` sorted rows over a layer's ``experts`` (the
    groups that can hold a row: of a stack ``[L * E]`` one layer's).
    ``mesh``: the mesh the caller's program is partitioned over, if any;
    GSPMD cannot partition a Mosaic kernel, so any mesh of more than one
    device (``ep`` > 1 among them) keeps ``ragged_dot``.
    ``LLMEngine.stats()["moe"]`` counts the programs built with each."""
    from .flash_attention import _on_tpu

    if not _on_tpu() or (mesh is not None and mesh.size > 1):
        return "ragged_dot"
    if row_tile(rows) is None or rows > min(MAX_ROWS,
                                            ROWS_PER_EXPERT * experts):
        return "ragged_dot"
    return "small_rows"


def ragged_grouped_matmul(rows, weights, group_sizes, epilogue=None):
    """The XLA path of :func:`grouped_matmul`."""
    products = [jax.lax.ragged_dot(rows, w, group_sizes) for w in weights]
    return products[0] if epilogue is None else epilogue(*products)


def _walk_kernel(sizes_ref, group_ref, tile_ref, lo_ref, hi_ref, n_ref, *,
                 tile):
    """All in SMEM, one scalar loop over the groups: each group's visits
    written where the walk has come to. n_ref [2]: the visits, and the
    rows that belong to a group."""
    def group(g, carry):
        start, v = carry
        end = start + sizes_ref[g]

        def crossed(v):
            first = start // tile

            def visit(i, v):
                group_ref[v] = g
                tile_ref[v] = first + i
                lo_ref[v] = start
                hi_ref[v] = end
                return v + 1

            return jax.lax.fori_loop(
                0, (end - 1) // tile - first + 1, visit, v)

        return end, jax.lax.cond(end > start, crossed, lambda v: v, v)

    n_ref[1], n_ref[0] = jax.lax.fori_loop(
        0, sizes_ref.shape[0], group, (jnp.int32(0), jnp.int32(0)))


def visits(group_sizes: jax.Array, m: int, tile: int, *, interpret=False):
    """The kernel's walk over ``m`` rows in tiles of ``tile``: for each
    visit its group, its row tile and the group's first and past-last
    row, each ``[V]`` int32 with ``V = m // tile + min(G, m) - 1`` (no
    walk is longer: a group with a row is visited once, and once more
    for every tile boundary it crosses), then ``[2]``: the number of
    visits (entries behind it are unwritten) and of rows in a group.
    One small kernel of its own, as ``ragged_dot``'s bookkeeping is on a
    TPU (11 us a layer of OLMoE's decode step where that takes 22; the
    same walk in XLA is some twenty operations and no faster: chip runs,
    PR 46 and 47)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G = group_sizes.shape[0]
    walk = jax.ShapeDtypeStruct((m // tile + min(G, m) - 1,), jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_walk_kernel, tile=tile),
        in_specs=[smem], out_specs=[smem] * 5,
        out_shape=[walk] * 4 + [jax.ShapeDtypeStruct((2,), jnp.int32)],
        interpret=interpret,
    )(group_sizes.astype(jnp.int32))


def _kernel(group_ref, tile_ref, lo_ref, hi_ref, x_ref, *refs, epilogue):
    """Grid (column blocks, visits). x_ref [tile, K]: the visit's row
    tile; refs: each stack's block [K, block_n] of the visit's group,
    then the output block [tile, block_n], which stays in VMEM while the
    visits stay in the row tile. The epilogue is given float32."""
    from jax.experimental import pallas as pl

    *w_refs, out_ref = refs
    v = pl.program_id(1)
    tile = tile_ref[v]

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != tile))
    def _first_visit_of_the_tile():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    x = x_ref[...]
    products = [
        jnp.dot(x, w[...], preferred_element_type=jnp.float32
                ).astype(out_ref.dtype).astype(jnp.float32) for w in w_refs]
    result = products[0] if epilogue is None else epilogue(*products)
    row = tile * out_ref.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    mine = (row >= lo_ref[v]) & (row < hi_ref[v])
    out_ref[...] = jnp.where(mine, result.astype(out_ref.dtype),
                             out_ref[...])


def choose_block_n(K: int, N: int, stacks: int, itemsize: int) -> int:
    """Columns of a weight block: all ``N`` where every stack's block,
    double-buffered, fits ``_WEIGHT_VMEM``; else the largest divisor of
    ``N`` in whole 128-lane tiles that does."""
    fits = [n for n in range(N, 0, -128)
            if N % n == 0 and 2 * stacks * K * n * itemsize <= _WEIGHT_VMEM]
    return fits[0] if fits else min(N, 128)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def small_rows_grouped_matmul(rows, weights, group_sizes, walk=None,
                              epilogue: Optional[Callable] = None,
                              block_n: Optional[int] = None,
                              interpret: bool = False):
    """The kernel. ``walk``: :func:`visits` of ``group_sizes`` over the
    rows in tiles of :func:`row_tile`, made here if not given; the rest
    as the function :func:`grouped_matmul` returns, and rows behind the
    last group give zero."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, K = rows.shape
    N = weights[0].shape[2]
    tile = row_tile(m)
    itemsize = weights[0].dtype.itemsize
    bn = block_n or choose_block_n(K, N, len(weights), itemsize)
    if walk is None:
        walk = visits(group_sizes, m, tile, interpret=interpret)
    *walk, n = walk

    weight_spec = pl.BlockSpec(
        (None, K, bn), lambda j, v, group, *_: (group[v], 0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, epilogue=epilogue),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // bn, n[0]),
            in_specs=[pl.BlockSpec((tile, K),
                                   lambda j, v, _, at, *__: (at[v], 0)),
                      *[weight_spec] * len(weights)],
            out_specs=pl.BlockSpec((tile, bn),
                                   lambda j, v, _, at, *__: (at[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(2 * len(weights) * K * bn * itemsize
                              + _OTHER_VMEM)),
        interpret=interpret,
    )(*walk, rows, *weights)
    # Row tiles behind the last group were never written.
    return jnp.where((jnp.arange(m) < n[1])[:, None], out, 0)


def _forward(rows, weights, group_sizes, walk, *static):
    return (small_rows_grouped_matmul(rows, weights, group_sizes, walk,
                                      *static),
            (rows, weights, group_sizes))


def _backward(epilogue, block_n, interpret, saved, ct):
    rows, weights, group_sizes = saved
    _, vjp = jax.vjp(
        lambda r, w: ragged_grouped_matmul(r, w, group_sizes, epilogue),
        rows, weights)
    return (*vjp(ct), None, None)


small_rows_grouped_matmul.defvjp(_forward, _backward)


def grouped_matmul(m: int, group_sizes: jax.Array, *,
                   experts: Optional[int] = None, mesh=None) -> Callable:
    """The grouped matmul of ``m`` sorted rows whose groups, in order,
    hold ``group_sizes`` [G] int32 of them: ``matmul(rows, weights,
    epilogue=None)`` is ``epilogue(*(the grouped product of rows [m, K]
    with w [G, K, N] for w in weights))`` ``[m, N]`` in the rows' dtype,
    each product accumulated in float32 and rounded first, ``epilogue``
    elementwise; rows behind the last group give zero from the kernel
    and whatever the backend's ``ragged_dot`` leaves from the XLA path.
    ``experts``: the groups that can hold a row, where ``G`` counts a
    whole stack's; with ``mesh`` it is :func:`grouped_path`'s to choose
    by. The kernel's walk over the groups is made here, once for every
    call of the function."""
    experts = experts or group_sizes.shape[0]
    if grouped_path(m, experts, mesh) == "ragged_dot":
        return lambda rows, weights, epilogue=None: ragged_grouped_matmul(
            rows, tuple(weights), group_sizes, epilogue)
    walk = visits(group_sizes, m, row_tile(m))
    return lambda rows, weights, epilogue=None: small_rows_grouped_matmul(
        rows, tuple(weights), group_sizes, walk, epilogue)
