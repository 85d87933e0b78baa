"""Tensor-parallel matmuls whose collective runs beside them.

Megatron's split leaves two collectives a matmul pair: the rows of a
row-parallel matmul's output (``wo``, ``w_down``) are summed over ``tp``,
and the next column-parallel matmul (``wq|wk|wv``, ``w_gate|w_up``) reads
all of them. Where the activation between the two lies sharded along its
rows (the sequence) over ``tp``, the sum is a reduce-scatter and the read
an all-gather, and each can be cut into the ``tp`` chunks of rows it
moves: a chunk travels one hop round the ring while the matmul works on
the chunk already here. That is all this module does, as two functions of
global arrays, each a ``shard_map`` island over the one axis (every other
mesh axis stays the partitioner's: ``fsdp`` gathers the weights and
reduces their gradients as it does everywhere else):

``gather_matmul(mesh, x, ws)``   x [B,S,M] rows over ``tp``; each w
                                 [M,N,...] columns over ``tp``
                                 -> [B,S,N,...] each, rows whole
``matmul_scatter(mesh, a, w)``   a [B,S,N,...] columns over ``tp``; w
                                 [N,...,M] rows over ``tp``
                                 -> [B,S,M] summed, rows over ``tp``

Each is the other's transpose, so each one's backward is the other's
ring (a ``custom_vjp``: the ring is written once forward and once
backward, and a weight's gradient is ONE contraction over all the rows,
as without the ring, not a sum of rounded partial ones). The sums are the
ones an all-reduce takes: ``tp`` terms in the operands' dtype.

The chunks are Python-unrolled, ``tp`` matmuls and ``tp - 1`` hops a call
(two and one on a 2x2 host), and each function is a ``jax.jit`` of its
own: the layers of an unrolled scan chunk call it with the same shapes,
so it is traced and lowered once a site and not once a layer.

What a chunked result costs is putting it back in the devices' order.
A ``dynamic_update_slice`` a chunk into a buffer copies the WHOLE buffer
each time on a v5e (0.6 ms for [4,2048,5120]; 63 ms of a 750 ms step,
more than the ring hid: my chip run, PR 61), so the chunks are
concatenated once, in a branch chosen by the device's place on the ring.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .collectives import shift
from .mesh import mesh_axis_size

AXIS = "tp"


def ring_size(mesh, rows: int) -> int:
    """How many chunks the matmuls' rows are cut into under ``mesh``:
    the size of its ``tp`` axis where that is over 1, the rows divide by
    it and no ``sp`` axis has the sequence already (ring attention's
    layout is left as it is); else 1, and nothing here is called."""
    if mesh is None or mesh_axis_size(mesh, "sp") > 1:
        return 1
    n = mesh_axis_size(mesh, AXIS)
    return n if n > 1 and rows % n == 0 else 1


def _ring_chunks(x, n: int):
    """The ring's chunks of rows as they arrive here, nearest first:
    chunk t is device ``place - t``'s. A hop depends on nothing computed
    from the chunk before it, so it runs beside that."""
    chunks = [x]
    for _ in range(n - 1):
        chunks.append(shift(chunks[-1], AXIS))
    return chunks


def _in_order(chunk_lists, place, n: int):
    """Each list of ``_ring_chunks``' chunks (or of what was computed
    from each) laid along the rows (axis 1) in the devices' order: one
    concatenation a list, in the order this device's place gives."""
    def laid(first):
        return lambda lists: tuple(
            jnp.concatenate([cs[(first - p) % n] for p in range(n)], axis=1)
            for cs in lists)

    return lax.switch(place, [laid(i) for i in range(n)],
                      tuple(tuple(cs) for cs in chunk_lists))


def _scatter_ring(partial_of, rows: int, place, n: int):
    """The sum over the ring of every device's ``partial_of(start)``
    (its addend for the ``rows`` rows from ``start``), each device left
    with the sum for its own chunk: the running sum for chunk c visits
    every device and ends at device c, and each hop runs beside the next
    device's matmul."""
    acc = None
    for t in range(n):
        part = partial_of(((place + n - 1 - t) % n) * rows)
        acc = part if acc is None else acc + part
        if t < n - 1:
            acc = shift(acc, AXIS)
    return acc


def _rows_by_cols(x, w):
    """x [B,s,M] @ w [M,N,...] -> [B,s,N,...]."""
    return jnp.tensordot(x, w, axes=1)


def _cols_by_rows(a, w):
    """a [B,s,N,...] @ w [N,...,M] -> [B,s,M]."""
    return jnp.tensordot(a, w, axes=w.ndim - 1)


def _cols_t(dy, w):
    """dy [B,s,N,...] against w [M,N,...] -> [B,s,M]."""
    k = w.ndim - 1
    return jnp.tensordot(
        dy, w, axes=(tuple(range(2, 2 + k)), tuple(range(1, 1 + k))))


def _rows_t(dz, w):
    """dz [B,s,M] against w [N,...,M] -> [B,s,N,...]."""
    return jnp.tensordot(dz, w, axes=((2,), (w.ndim - 1,)))


def _over_rows(x, y):
    """x [B,S,...] and y [B,S,...] contracted over B and S."""
    return jnp.tensordot(x, y, axes=((0, 1), (0, 1)))


_ROWS = P(None, AXIS)


def _cols(ndim: int, at: int):
    return P(*([None] * at + [AXIS] + [None] * (ndim - at - 1)))


def _island(mesh: Mesh, body, in_specs, out_specs):
    """``body(place, *args)`` on every device of the ring, manual over
    the one axis. ``place`` is the device's index on it, read from an
    iota laid over the axis: ``lax.axis_index`` is a partition id there,
    which the partitioner refuses to branch on in an island that leaves
    the other axes to it."""
    n = mesh_axis_size(mesh, AXIS)
    island = jax.shard_map(
        lambda places, *args: body(places[0], *args), mesh=mesh,
        in_specs=(P(AXIS),) + tuple(in_specs), out_specs=out_specs,
        axis_names=frozenset({AXIS}), check_vma=False)
    return lambda *args: island(jnp.arange(n, dtype=jnp.int32), *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_matmul(mesh: Mesh, x, ws: Tuple[jax.Array, ...]):
    n = mesh_axis_size(mesh, AXIS)

    def body(place, x, *ws):
        chunks = _ring_chunks(x, n)
        return _in_order(
            [[_rows_by_cols(c, w) for c in chunks] for w in ws], place, n)

    return _island(
        mesh, body, (_ROWS,) + tuple(_cols(w.ndim, 1) for w in ws),
        tuple(_cols(w.ndim + 1, 2) for w in ws))(x, *ws)


def _gather_matmul_fwd(mesh, x, ws):
    return _gather_matmul(mesh, x, ws), (x, ws)


def _gather_matmul_bwd(mesh, saved, dys):
    """dx is the transposed matmuls' sum, reduce-scattered round the
    ring; x goes round again beside that, for the weights' gradients."""
    x, ws = saved
    n = mesh_axis_size(mesh, AXIS)
    rows = x.shape[1] // n

    def body(place, x, *rest):
        ws, dys = rest[:len(rest) // 2], rest[len(rest) // 2:]
        whole, = _in_order([_ring_chunks(x, n)], place, n)

        def partial_of(start):
            return functools.reduce(jnp.add, [
                _cols_t(lax.dynamic_slice_in_dim(dy, start, rows, 1), w)
                for dy, w in zip(dys, ws)])

        dx = _scatter_ring(partial_of, rows, place, n)
        return (dx.astype(x.dtype),) + tuple(
            _over_rows(whole, dy).astype(w.dtype) for dy, w in zip(dys, ws))

    w_specs = tuple(_cols(w.ndim, 1) for w in ws)
    dy_specs = tuple(_cols(w.ndim + 1, 2) for w in ws)
    out = _island(mesh, body, (_ROWS,) + w_specs + dy_specs,
                  (_ROWS,) + w_specs)(x, *ws, *dys)
    return out[0], tuple(out[1:])


_gather_matmul.defvjp(_gather_matmul_fwd, _gather_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _matmul_scatter(mesh: Mesh, a, w):
    n = mesh_axis_size(mesh, AXIS)
    rows = a.shape[1] // n

    def body(place, a, w):
        return _scatter_ring(
            lambda start: _cols_by_rows(
                lax.dynamic_slice_in_dim(a, start, rows, 1), w),
            rows, place, n)

    return _island(mesh, body, (_cols(a.ndim, 2), _cols(w.ndim, 0)),
                   _ROWS)(a, w)


def _matmul_scatter_fwd(mesh, a, w):
    return _matmul_scatter(mesh, a, w), (a, w)


def _matmul_scatter_bwd(mesh, saved, dz):
    """da is dz's chunks going round the ring, each multiplied as it
    arrives; the chunks, laid in order, are the weight's gradient's."""
    a, w = saved
    n = mesh_axis_size(mesh, AXIS)

    def body(place, a, w, dz):
        chunks = _ring_chunks(dz, n)
        da, whole = _in_order([[_rows_t(c, w) for c in chunks], chunks],
                              place, n)
        return da.astype(a.dtype), _over_rows(a, whole).astype(w.dtype)

    a_spec, w_spec = _cols(a.ndim, 2), _cols(w.ndim, 0)
    return _island(mesh, body, (a_spec, w_spec, _ROWS),
                   (a_spec, w_spec))(a, w, dz)


_matmul_scatter.defvjp(_matmul_scatter_fwd, _matmul_scatter_bwd)

# The two functions the module's text describes, each traced and lowered
# once a site (``ws`` is a tuple).
gather_matmul = jax.jit(_gather_matmul, static_argnums=0)
matmul_scatter = jax.jit(_matmul_scatter, static_argnums=0)
