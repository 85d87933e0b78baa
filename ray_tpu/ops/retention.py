"""Power retention of degree 2: a gated linear attention whose weights
are ``(q . k)^2 / d``, served from a state of fixed size a slot.

The function, for one KV head ``n`` and a query head ``h`` of its group
(``d`` the head width, ``g_t`` in (0, 1] the token's gate)::

    a_tj = exp(sum_{i=j+1..t} log g_i) * (q_t . k_j)^2 / d     j <= t
    y_t  = sum_j a_tj v_j / (sum_j a_tj + EPS)

and as a recurrence, which is what both programs here compute::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + EPS)

``phi(x) . phi(y) = (x . y)^2 / d``. :func:`phi` holds each unordered
pair of coordinates once, laid as TURNS of the vector against itself:
``phi(x)[t, a] = c_t x[a] x[(a - t) mod d]`` for ``t = 0 .. d/2``, with
``c_0 = c_{d/2} = d^-1/2`` and ``sqrt(2) d^-1/2`` between. Turn 0 is the
squares, turns ``1 .. d/2 - 1`` hold every pair at that distance once,
and turn ``d/2`` holds each of its ``d/2`` pairs twice at half the
weight (so that a turn is a whole row of ``d`` lanes): ``T = d/2 + 1``
turns of ``d``, 8,320 values at ``d`` 128 where the pairs alone are
8,256.

The state pool (models/generation.py ``PagedKVCache``, kind "state") is
``[L, B, Hkv, T, R, d]`` float32, ``R = d + 8``: at turn ``t`` rows
``0 .. d-1`` are ``S^T`` (row ``r`` the state's column for ``v``'s
coordinate ``r``), row ``d`` is the normaliser ``z`` (``v`` extended by
a one), rows ``d+1 .. d+7`` are zeros that make the rows a whole number
of float32 sublane tiles. One layout for both programs: a turn is a
leading index, ``[R, d]`` is whole (8, 128) tiles.

:func:`retention_decode` — one token a slot. Read the slot's state at
``layer``, decay it, add the token's ``phi(k) v^T``, write it back,
read out ``phi(q)`` against the new state. :func:`retention_prefill` —
one prompt, in chunks of ``CHUNK`` tokens: inside a chunk the masked,
decayed ``(Q K^T)^2``, between chunks the state; returns the outputs
and the state after the last token whose gate and key were not masked
(the caller masks a bucket's padding: a gate of 1 and a key of 0 leave
the state as it is, exactly).

Each has two implementations, chosen by :func:`retention_path` from
platform and shape, never by a user: Pallas TPU kernels for heads of
128 (``state_step``: grid (slot, KV head), the state block brought in
and written back by the pipeline through an output aliased to the pool,
an idle slot's steps pointed at a neighbour's block so that nothing is
moved for them; it is handed q and k as they are and makes ``phi`` of
both itself, a lane rotation a turn, and it takes a few row tiles at a
time through all the turns so that the read-outs' sums stay in vector
registers and a register of state is loaded once and stored once;
``chunk_scan``: grid (KV head, chunk), the state resident in the output
block across a head's chunks), and plain XLA for any platform and shape
(tier-1 runs it on the CPU).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6
# Tokens a chunk of the prefill covers: the intra-chunk part is
# quadratic in it, the number of passes over the state inverse to it.
CHUNK = 256
# float32 sublanes: the rows of a turn are padded to a multiple.
_ROW_PAD = 8
# The decode kernel's inner loop: turns it writes out an iteration (as
# many of these as divide the turns: 65 at a head of 128), and the vector
# registers, of 64, its carried values may take.
_TURN_UNROLL = 5
_PASS_REGISTERS = 48
_HI = jax.lax.Precision.HIGHEST


def turns(d: int) -> int:
    return d // 2 + 1


def state_rows(d: int) -> int:
    """Rows of a turn: ``d`` of the state, the normaliser, padding."""
    return d + _ROW_PAD


def state_shape(layers: int, batch: int, kv_heads: int, d: int):
    return (layers, batch, kv_heads, turns(d), state_rows(d), d)


def _coefficients(d: int) -> jax.Array:
    t = jnp.arange(turns(d))
    return jnp.where((t == 0) | (t == d // 2), 1.0, math.sqrt(2.0)) * d ** -0.5


def phi(x: jax.Array) -> jax.Array:
    """[..., d] -> [..., T, d] float32, the module docstring's map."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"phi needs an even width, not {d}")
    xf = x.astype(jnp.float32)
    rolled = jnp.stack([jnp.roll(xf, t, axis=-1) for t in range(turns(d))],
                       axis=-2)
    return xf[..., None, :] * rolled * _coefficients(d)[:, None]


def _extended(v: jax.Array) -> jax.Array:
    """v [..., d] -> [..., R] float32: v, a one, zeros."""
    tail = jnp.zeros(v.shape[:-1] + (_ROW_PAD,), jnp.float32).at[..., 0].set(1)
    return jnp.concatenate([v.astype(jnp.float32), tail], axis=-1)


def retention_path(head_dim: int, tokens: int = CHUNK) -> str:
    """``"state_kernel"`` or ``"xla"``: what the two programs run here
    for heads of ``head_dim`` (and a prefill of ``tokens``)."""
    from .flash_attention import _on_tpu

    if _on_tpu() and head_dim == 128 and tokens % CHUNK == 0:
        return "state_kernel"
    return "xla"


# ---- decode ---------------------------------------------------------------


def xla_retention_decode(q, k, v, log_g, pool, layer, active):
    """The XLA path of :func:`retention_decode`."""
    B, H, d = q.shape
    Hkv = k.shape[1]
    state = pool[layer]                                   # [B,Hkv,T,R,d]
    with jax.named_scope("ret.update"):
        g = jnp.exp(log_g.astype(jnp.float32))[..., None, None, None]
        new = g * state + (phi(k)[..., :, None, :]
                           * _extended(v)[..., None, :, None])
        new = jnp.where(active[:, None, None, None, None], new, state)
    with jax.named_scope("ret.read"):
        out = jnp.einsum("bngta,bntra->bngr",
                         phi(q).reshape(B, Hkv, H // Hkv, turns(d), d), new,
                         precision=_HI)
        y = out[..., :d] / (out[..., d:d + 1] + EPS)
    return y.reshape(B, H, d).astype(q.dtype), pool.at[layer].set(new)


def _state_step_kernel(slot_ref, head_ref, act_ref, layer_ref, qk_ref,
                       gv_ref, s_in, y_ref, s_out, phi_ref, acc_ref):
    """Grid (B, Hkv). slot_ref, head_ref [B]: the state block an idle
    slot's steps are pointed at (``_idle_blocks``); act_ref [B]; layer_ref
    [1]. qk_ref [Q, d] float32: rows ``0 .. G-1`` the group's queries, row
    ``G`` the key, zeros up to whole tiles; gv_ref [R, 2] (column 0 the
    extended v, column 1 the gate); s_in/s_out [T, R, d], the same block
    of the pool; y_ref [G, d] float32: each query head's read-out.
    Scratch: phi_ref [T, Q, d], ``phi`` of every row of qk_ref, made
    here; acc_ref [G, R, d], the read-outs' sums over the turns, still to
    be summed over lanes.

    The turns are the INNER loop: a pass takes a few row tiles (8 rows,
    one vector register a turn) through all T turns, so that its sums
    (G a tile) stay in registers from the first turn to the last, and a
    state register is loaded once, stored once and multiplied in place
    (5, 5, 5 and 2 of the 17 tiles at G = 5)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    G = y_ref.shape[0]
    T, R, d = s_in.shape
    c_edge, c_mid = d ** -0.5, math.sqrt(2.0) * d ** -0.5

    @pl.when(act_ref[b] == 1)
    def _step():
        x = qk_ref[...]

        def turn(t, _):
            coef = jnp.where((t == 0) | (t == T - 1), c_edge, c_mid)
            phi_ref[t] = x * pltpu.roll(x, t, 1) * coef
            return 0

        jax.lax.fori_loop(0, T, turn, 0)
        gate = jnp.broadcast_to(gv_ref[0:_ROW_PAD, 1:2], (_ROW_PAD, d))
        # (Mosaic lowers a loop whole or not unrolled at all.)
        unroll = math.gcd(T, _TURN_UNROLL)

        def row(t, h):
            return jnp.broadcast_to(phi_ref[t, h:h + 1, :], (_ROW_PAD, d))

        # A pass holds G sums, v and the new state of each of its tiles
        # across the turns, and a turn's G + 1 rows of phi and the gate:
        # within the 64 vector registers with room for the compiler's.
        most = max(1, (_PASS_REGISTERS - G - 2) // (G + 2))
        for first in range(0, R, most * _ROW_PAD):
            tiles = [pl.ds(at, _ROW_PAD) for at in range(
                first, min(first + most * _ROW_PAD, R), _ROW_PAD)]
            v = [jnp.broadcast_to(gv_ref[rows, 0:1], (_ROW_PAD, d))
                 for rows in tiles]

            def body(t, acc):
                pk = row(t, G)
                new = []
                for rows, v_tile in zip(tiles, v):
                    new.append(gate * s_in[t, rows, :] + v_tile * pk)
                    s_out[t, rows, :] = new[-1]
                return tuple(
                    tuple(a + tile * pq for a, tile in zip(sums, new))
                    for sums, pq in zip(acc, (row(t, h) for h in range(G))))

            def several(i, acc):
                for j in range(unroll):
                    acc = body(i * unroll + j, acc)
                return acc

            acc = jax.lax.fori_loop(
                0, T // unroll, several,
                tuple(tuple(jnp.zeros((_ROW_PAD, d), jnp.float32)
                            for _ in tiles) for _ in range(G)))
            for h, sums in enumerate(acc):
                for rows, a in zip(tiles, sums):
                    acc_ref[h, rows, :] = a
        for h in range(G):
            # Sums over lanes come out as columns; turned, the d
            # numerators are one row of lanes.
            a = acc_ref[h]
            num = jnp.sum(a[0:d].T, axis=0, keepdims=True)    # [1, d]
            den = jnp.sum(a[d:d + 1], axis=1, keepdims=True)  # [1, 1]
            y_ref[h:h + 1, :] = num / (den + EPS)

    @pl.when(act_ref[b] == 0)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        # No active slot before this one: the block under the output is
        # one this call has not computed yet (or, with nobody active,
        # never will), so what goes back is what came in.
        @pl.when(head_ref[b] == 0)
        def _keep():
            s_out[...] = s_in[...]


def _idle_blocks(active):
    """For each slot the (slot, head) whose state block its steps use:
    its own heads in turn where it is active; else the last head of the
    nearest active slot before it, a block the pipeline already holds
    and writes back once; else the first head of the nearest after it;
    else (nobody active) slot 0's, copied through."""
    B = active.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(active, idx, -1))
    after = jax.lax.cummin(jnp.where(active, idx, B), reverse=True)
    slot = jnp.where(before >= 0, before, jnp.where(after < B, after, 0))
    return slot.astype(jnp.int32), (before >= 0).astype(jnp.int32)


def state_step(q, k, v, log_g, pool, layer, active, *, interpret=False):
    """The decode kernel. Arguments and results as
    :func:`retention_decode`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    Hkv = k.shape[1]
    G, T, R = H // Hkv, turns(d), state_rows(d)
    slot, last = _idle_blocks(active)
    # A KV head's queries and its key as rows of whole float32 tiles.
    Q = -(-(G + 1) // _ROW_PAD) * _ROW_PAD
    qk = jnp.concatenate(
        [q.reshape(B, Hkv, G, d), k[:, :, None, :],
         jnp.zeros((B, Hkv, Q - G - 1, d), q.dtype)],
        axis=2).astype(jnp.float32)
    gate = jnp.broadcast_to(
        jnp.exp(log_g.astype(jnp.float32))[..., None], (B, Hkv, R))
    gv = jnp.stack([_extended(v), gate], axis=-1)         # [B,Hkv,R,2]

    def own(b, n, *_):
        return (b, n, 0, 0)

    def state_block(b, n, slot_ref, head_ref, act_ref, layer_ref):
        idle_head = head_ref[b] * (Hkv - 1)
        head = jnp.where(act_ref[b] == 1, n, idle_head)
        return (layer_ref[0], slot_ref[b], head, 0, 0, 0)

    state_spec = pl.BlockSpec((None, None, None, T, R, d), state_block)
    y, pool = pl.pallas_call(
        _state_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, Hkv),
            in_specs=[pl.BlockSpec((None, None, Q, d), own),
                      pl.BlockSpec((None, None, R, 2), own),
                      state_spec],
            out_specs=[pl.BlockSpec((None, None, G, d), own), state_spec],
            scratch_shapes=[pltpu.VMEM((T, Q, d), jnp.float32),
                            pltpu.VMEM((G, R, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operands count the four prefetched scalars: the pool is 6.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(slot, last, active.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qk, gv, pool)
    return y.reshape(B, H, d).astype(q.dtype), pool


def retention_decode(q, k, v, log_g, pool, layer, active):
    """One token a slot. q [B, H, d]; k, v [B, Hkv, d]; log_g [B, Hkv]
    the token's log gate; pool [L, B, Hkv, T, R, d] float32; ``layer``
    the pool's layer; ``active`` [B] bool. Each active slot's state at
    ``layer`` is decayed by its gate and takes the token's
    ``phi(k) v^T`` (the token's own term undecayed), an idle slot's is
    left as it is. Returns (the read-out [B, H, d], zeros or unread for
    idle slots; the pool)."""
    path = (state_step if retention_path(q.shape[-1]) == "state_kernel"
            else xla_retention_decode)
    return path(q, k, v, log_g, pool, layer, active)


# ---- prefill --------------------------------------------------------------


def _chunks(x, chunk):
    """[S, ...] -> [S // chunk, chunk, ...]."""
    return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])


def xla_retention_prefill(q, k, v, log_g, *, chunk=CHUNK):
    """The XLA path of :func:`retention_prefill`: a scan over chunks."""
    S, H, d = q.shape
    Hkv = k.shape[1]
    G, C = H // Hkv, chunk
    causal = jnp.tril(jnp.ones((C, C), bool))
    # Whole chunks whatever S is, the tail masked as a caller masks a
    # bucket's padding: a token's chunk, and so the order of every sum
    # it is part of, does not depend on how many tokens follow it.
    tail = -S % C
    q, k, v, log_g = (jnp.pad(x, ((0, tail),) + ((0, 0),) * (x.ndim - 1))
                      for x in (q, k, v, log_g))

    def one(state, xs):
        qc, kc, vc, lg = xs             # [C,H,d] [C,Hkv,d] [C,Hkv,d] [C,Hkv]
        cum = jnp.cumsum(lg, axis=0)                      # [C, Hkv]
        qg = qc.reshape(C, Hkv, G, d).astype(jnp.float32)
        kf, ve = kc.astype(jnp.float32), _extended(vc)    # ve [C,Hkv,R]
        with jax.named_scope("ret.read"):
            s = jnp.einsum("tngd,jnd->ngtj", qg, kf, precision=_HI)
            gap = cum.T[:, :, None] - cum.T[:, None, :]   # [Hkv, t, j]
            a = (s * s / d) * jnp.where(
                causal, jnp.exp(jnp.minimum(gap, 0.0)), 0.0)[:, None]
            intra = jnp.einsum("ngtj,jnr->tngr", a, ve, precision=_HI)
            inter = jnp.einsum(
                "tngxa,nxra->tngr",
                phi(qg) * jnp.exp(cum)[:, :, None, None, None], state,
                precision=_HI)
            out = intra + inter
            y = out[..., :d] / (out[..., d:d + 1] + EPS)
        with jax.named_scope("ret.update"):
            to_end = jnp.exp(cum[-1] - cum)               # [C, Hkv]
            state = (jnp.exp(cum[-1])[:, None, None, None] * state
                     + jnp.einsum("jnxa,jnr->nxra",
                                  phi(kf) * to_end[..., None, None], ve,
                                  precision=_HI))
        return state, y.reshape(C, H, d).astype(q.dtype)

    state0 = jnp.zeros(state_shape(1, 1, Hkv, d)[2:], jnp.float32)
    state, y = jax.lax.scan(
        one, state0, tuple(_chunks(x, C) for x in (
            q, k, v, log_g.astype(jnp.float32))))
    return y.reshape(S + tail, H, d)[:S], state


def _chunk_scan_kernel(q_ref, k_ref, v_ref, vt_ref, cum_ref, y_ref, st_ref,
                       qe_ref, qr_ref, kr_ref, num_ref, den_ref):
    """Grid (Hkv, S // C). q_ref/y_ref [G, C, d]; k_ref, v_ref [C, d];
    vt_ref [d, C]; cum_ref [1, C] the chunk's running sum of log gates;
    st_ref [T, R, d] the head's state, resident over its chunks. Scratch,
    float32: qe_ref [G*C, d] the queries decayed from the chunk's start,
    qr_ref [G*C, d] and kr_ref [C, d] q and k turned so far; num_ref,
    den_ref [G*C, d] the read-out against the state before this chunk
    (the normaliser's still to be summed over lanes)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, C, d = q_ref.shape
    T = st_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _fresh():
        st_ref[...] = jnp.zeros(st_ref.shape, st_ref.dtype)

    row = cum_ref[...]                                    # [1, C]
    t_at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j_at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    col = jnp.sum(jnp.where(t_at == j_at, row, 0.0), axis=1, keepdims=True)
    decay = jnp.where(j_at <= t_at,
                      jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)
    # The running sum at the chunk's end: a log gate is never above 0,
    # so the last is the least.
    total = jnp.min(row, axis=1, keepdims=True)           # [1, 1]
    g_chunk = jnp.exp(total)
    from_start = jnp.exp(col)                             # [C, 1]
    to_end = jnp.exp(total - col)

    k = k_ref[...]
    kf = k.astype(jnp.float32)
    kw = kf * to_end
    kr_ref[...] = kf
    for h in range(G):
        rows = pl.ds(h * C, C)
        qf = q_ref[h].astype(jnp.float32)
        qr_ref[rows, :] = qf
        qe_ref[rows, :] = qf * from_start
    num_ref[...] = jnp.zeros(num_ref.shape, jnp.float32)
    den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
    vt = vt_ref[...]
    c_edge, c_mid = d ** -0.5, math.sqrt(2.0) * d ** -0.5

    def body(t, _):
        coef = jnp.where((t == 0) | (t == T - 1), c_edge, c_mid)
        s_t = st_ref[t, 0:d, :]                           # [d(v), d]
        z_t = st_ref[t, d:d + 1, :]                       # [1, d]
        # The turn's coefficient goes to the state's side of the
        # read-out, [d, d] values where the queries' are [G*C, d].
        s_bf = (s_t * coef).astype(jnp.bfloat16)
        z_c = z_t * coef
        for h in range(G):
            rows = pl.ds(h * C, C)
            qr = qr_ref[rows, :]
            pq = qe_ref[rows, :] * qr
            num_ref[rows, :] += jax.lax.dot_general(
                pq.astype(jnp.bfloat16), s_bf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            den_ref[rows, :] += pq * z_c
            qr_ref[rows, :] = pltpu.roll(qr, 1, 1)
        kr = kr_ref[...]
        pk = kw * (kr * coef)                             # [C, d]
        st_ref[t, 0:d, :] = g_chunk * s_t + jnp.dot(
            vt, pk.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        st_ref[t, d:d + 1, :] = g_chunk * z_t + jnp.sum(
            pk, axis=0, keepdims=True)
        kr_ref[...] = pltpu.roll(kr, 1, 1)
        return 0

    jax.lax.fori_loop(0, T, body, 0)

    v = v_ref[...]
    for h in range(G):
        rows = pl.ds(h * C, C)
        s = jax.lax.dot_general(
            q_ref[h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [C, C]
        a = (s * s) * (1.0 / d) * decay
        num = num_ref[rows, :] + jnp.dot(
            a.astype(v.dtype), v, preferred_element_type=jnp.float32)
        den = (jnp.sum(den_ref[rows, :], axis=1, keepdims=True)
               + jnp.sum(a, axis=1, keepdims=True))
        y_ref[h] = (num / (den + EPS)).astype(y_ref.dtype)


def chunk_scan(q, k, v, log_g, *, interpret=False):
    """The prefill kernel. Arguments and results as
    :func:`retention_prefill`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, d = q.shape
    Hkv = k.shape[1]
    G, C, T, R = H // Hkv, CHUNK, turns(d), state_rows(d)
    n_chunks = S // C
    dtype = q.dtype
    qh = q.reshape(S, Hkv, G, d).transpose(1, 2, 0, 3)    # [Hkv,G,S,d]
    kh = k.transpose(1, 0, 2).astype(dtype)               # [Hkv,S,d]
    vh = v.transpose(1, 0, 2).astype(dtype)
    cum = jnp.cumsum(_chunks(log_g.astype(jnp.float32), C), axis=1)
    cum = cum.transpose(2, 0, 1)[:, :, None, :]           # [Hkv,nC,1,C]
    y, state = pl.pallas_call(
        _chunk_scan_kernel,
        grid=(Hkv, n_chunks),
        in_specs=[
            pl.BlockSpec((None, G, C, d), lambda n, c: (n, 0, c, 0)),
            pl.BlockSpec((None, C, d), lambda n, c: (n, c, 0)),
            pl.BlockSpec((None, C, d), lambda n, c: (n, c, 0)),
            pl.BlockSpec((None, d, C), lambda n, c: (n, 0, c)),
            pl.BlockSpec((None, None, 1, C), lambda n, c: (n, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, G, C, d), lambda n, c: (n, 0, c, 0)),
            pl.BlockSpec((None, T, R, d), lambda n, c: (n, 0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((Hkv, G, S, d), dtype),
                   jax.ShapeDtypeStruct((Hkv, T, R, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G * C, d), jnp.float32),
                        pltpu.VMEM((G * C, d), jnp.float32),
                        pltpu.VMEM((C, d), jnp.float32),
                        pltpu.VMEM((G * C, d), jnp.float32),
                        pltpu.VMEM((G * C, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(qh, kh, vh, vh.transpose(0, 2, 1), cum)
    return y.transpose(2, 0, 1, 3).reshape(S, H, d), state


def retention_prefill(q, k, v, log_g):
    """One prompt from an empty state. q [S, H, d]; k, v [S, Hkv, d];
    log_g [S, Hkv]. Returns (the outputs [S, H, d], the state
    [Hkv, T, R, d] float32 after the last token). A token whose gate is
    1 (log 0) and whose key is 0 leaves the state exactly as it was:
    how the caller keeps a bucket's padding out of it."""
    path = (chunk_scan
            if retention_path(q.shape[-1], q.shape[0]) == "state_kernel"
            else xla_retention_prefill)
    return path(q, k, v, log_g)
