"""The gated delta rule with a decay a channel: a linear attention whose
state forgets a channel at a time and corrects what it already holds for
a key before it writes that key's value.

The function, for one head (``d`` its width; ``q_t``, ``k_t``, ``v_t``
[d]; ``a_t`` in (0, 1]^d the token's decay, a channel of k each, given as
``log a_t``; ``beta_t`` in [0, 1] its write strength; ``S`` [d, d], rows
the key's channels, columns the value's, float32)::

    S~  = diag(a_t) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

What the caller has done before: q and k normed and scaled, the short
convolution, the gates (models/llama.py). What is kept a slot is ``S``:
the pool (models/generation.py ``PagedKVCache``, kind "delta") is
``[L, B, H, d, d]`` float32.

:func:`delta_decode` — one token a slot: the three lines above, the
slot's state at ``layer`` read, written back in place. :func:`delta_prefill`
— one prompt from an empty state, ``CHUNK`` tokens at a time, in the WY
form. With ``g_t`` the running sum of ``log a`` from the chunk's start,
``A[t, i] = beta_t sum_c k_t[c] k_i[c] exp(g_t[c] - g_i[c])`` for
``i < t`` and ``T = (I + A)^-1``::

    U = T diag(beta) (V - (K * exp g) S)
    O = (Q * exp g) S + tril(QK) U        QK[t, i] like A without beta
    S' = diag(exp g_C) S + (K * exp(g_C - g))^T U

**Where the decays are referred to.** ``exp(g_t - g_i)`` is at most 1,
but as the product ``exp(g_t) exp(-g_i)`` of a matmul's two sides its
second factor overflows float32 as soon as a channel decays by e^-88
inside a chunk, which one token of a strongly decaying channel can do.
So no factor is ever referred to the chunk's start: the decays are
referred to the MIDPOINTS OF NESTED BLOCKS. A pair ``(t, i)``, ``i < t``,
belongs to the one LEVEL ``b`` in (2, 4, .. CHUNK) at which ``t`` and
``i`` lie in the same aligned block of ``b`` tokens, ``t`` in its second
half and ``i`` in its first; the level's pairs are one matmul whose two
sides are both referred to the running sum at the last token of the
block's first half, ``rho``: ``exp(g_t - rho)`` and ``exp(rho - g_i)``
are then both at most 1 for every pair of the level (the others are
clamped and masked out), and since both sides take the same ``rho``,
whatever rounding leaves of it cancels in their product.
``log2(CHUNK)`` matmuls of the chunk's size instead of one, each right
wherever float32 can hold the answer. The same levels invert ``I + A``
exactly, a block's inverse from its halves': ``D <- D - D A_b D``,
``A_b`` the level's pairs, starting from ``D = I``; no power of ``A`` is
ever formed (with every key alike they grow as binomials). The state's
own factors, ``exp g`` and ``exp(g_C - g)``, are at most 1 as they
stand.

A token with ``log a = 0`` and ``beta = 0`` leaves the state exactly as
it was and takes part in no other token's output: how a caller keeps a
bucket's padding out.

Each has two implementations, chosen by :func:`delta_path` from platform
and shape, never by a user: Pallas TPU kernels for heads of 128
(``delta_step``: grid (slot,), a slot's states of every head brought in
and written back by the pipeline through an output aliased to the pool,
an idle slot's step pointed at a neighbour's block so that nothing is
moved for it; ``delta_scan``: grid (head, chunk), the head's state
resident in its output block across the chunks), and plain XLA for any
platform and shape (tier-1 runs it on the CPU). Both prefill paths run
the same chunk arithmetic, :func:`_chunk`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens a chunk of the prefill covers: the MXU's own 128 rows.
CHUNK = 128
# An exponent of a pair that is masked out anyway is cut here, so that
# what the mask drops is finite.
_CLAMP = 10.0
_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def state_shape(layers: int, batch: int, heads: int, d: int):
    return (layers, batch, heads, d, d)


def delta_path(head_dim: int, heads: int = 32, tokens: int = CHUNK) -> str:
    """``"delta_kernel"`` or ``"xla"``: what the two programs run here
    for ``heads`` heads of ``head_dim`` (and a prefill of ``tokens``).
    The decode kernel lays four vectors a head as columns of one lane
    tile, so whole tiles of 128 take 32 heads each."""
    from .flash_attention import _on_tpu

    if (_on_tpu() and head_dim == 128 and heads % 32 == 0
            and tokens % CHUNK == 0):
        return "delta_kernel"
    return "xla"


# ---- decode ---------------------------------------------------------------


def xla_delta_decode(q, k, v, log_a, beta, pool, layer, active):
    """The XLA path of :func:`delta_decode`."""
    state = pool[layer]                                   # [B,H,d,d]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    with jax.named_scope("delta.update"):
        decayed = jnp.exp(log_a.astype(jnp.float32))[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", kf, decayed, precision=_HI)
        u = beta.astype(jnp.float32)[..., None] * (vf - seen)
        new = decayed + kf[..., :, None] * u[..., None, :]
        new = jnp.where(active[:, None, None, None], new, state)
    with jax.named_scope("delta.read"):
        out = jnp.einsum("bhk,bhkv->bhv", qf, new, precision=_HI)
    return out.astype(q.dtype), pool.at[layer].set(new)


def _delta_step_kernel(slot_ref, seen_ref, act_ref, layer_ref, cols_ref,
                       vb_ref, s_in, y_ref, s_out):
    """Grid (B,). slot_ref [B]: the slot whose states an idle slot's
    step is pointed at, seen_ref [B]: whether an active slot lies at or
    before it (``_idle_blocks``); act_ref [B]; layer_ref [1]. cols_ref
    [d, 4H]: column ``h`` the head's q, ``H + h`` its k, ``2H + h``
    beta k, ``3H + h`` its decay. vb_ref [H, d]: beta v. s_in/s_out
    [H, d, d], the same block of the pool; y_ref [H, d] float32."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    H = vb_ref.shape[0]

    @pl.when(act_ref[b] == 1)
    def _step():
        cols = cols_ref[...]
        for h in range(H):
            q, k, kb, a = (cols[:, i * H + h:i * H + h + 1]
                           for i in range(4))             # [d, 1] each
            decayed = a * s_in[h]                         # [d(k), d(v)]
            u = vb_ref[h:h + 1, :] - jnp.sum(kb * decayed, axis=0,
                                             keepdims=True)
            new = decayed + k * u
            s_out[h] = new
            y_ref[h:h + 1, :] = jnp.sum(q * new, axis=0, keepdims=True)

    @pl.when(act_ref[b] == 0)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        # No active slot before this one: the block under the output is
        # one this call has not computed yet (or, with nobody active,
        # never will), so what goes back is what came in.
        @pl.when(seen_ref[b] == 0)
        def _keep():
            s_out[...] = s_in[...]


def _idle_blocks(active):
    """For each slot the slot whose state block its step uses: its own
    where it is active; else the nearest active slot before it, a block
    the pipeline already holds and writes back once; else the nearest
    after it; else (nobody active) slot 0, copied through."""
    B = active.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(active, idx, -1))
    after = jax.lax.cummin(jnp.where(active, idx, B), reverse=True)
    slot = jnp.where(before >= 0, before, jnp.where(after < B, after, 0))
    return slot.astype(jnp.int32), (before >= 0).astype(jnp.int32)


def delta_step(q, k, v, log_a, beta, pool, layer, active, *,
               interpret=False):
    """The decode kernel. Arguments and results as :func:`delta_decode`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    slot, seen = _idle_blocks(active)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    bf = beta.astype(jnp.float32)[..., None]
    # [B, 4H, d] -> [B, d, 4H]: a head's vectors as columns, so that the
    # kernel multiplies the state's rows (k's channels) without a turn.
    cols = jnp.concatenate(
        [qf, kf, bf * kf, jnp.exp(log_a.astype(jnp.float32))],
        axis=1).transpose(0, 2, 1)

    def own(b, *_):
        return (b, 0, 0)

    def state_block(b, slot_ref, seen_ref, act_ref, layer_ref):
        return (layer_ref[0], slot_ref[b], 0, 0, 0)

    state_spec = pl.BlockSpec((None, None, H, d, d), state_block)
    y, pool = pl.pallas_call(
        _delta_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, d, 4 * H), own),
                      pl.BlockSpec((None, H, d), own),
                      state_spec],
            out_specs=[pl.BlockSpec((None, None, H, d),
                                    lambda b, *_: (b, 0, 0, 0)),
                       state_spec],
        ),
        # The read-outs with a dimension of one, so that the call writes
        # four dimensions and five: no other kernel's name in a trace.
        out_shape=[jax.ShapeDtypeStruct((B, 1, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operands count the four prefetched scalars: the pool is 6.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(slot, seen, active.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), cols, bf * vf, pool)
    return y.reshape(B, H, d).astype(q.dtype), pool


def delta_decode(q, k, v, log_a, beta, pool, layer, active):
    """One token a slot. q, k, v [B, H, d]; log_a [B, H, d] the token's
    log decays (a channel of k each, at most 0); beta [B, H]; pool
    [L, B, H, d, d] float32; ``layer`` the pool's layer; ``active`` [B]
    bool. Each active slot's state at ``layer`` is decayed, corrected
    and written, an idle slot's is left as it is. Returns (the read-out
    [B, H, d], zeros or unread for idle slots; the pool)."""
    kernel = delta_path(q.shape[-1], q.shape[1]) == "delta_kernel"
    return (delta_step if kernel else xla_delta_decode)(
        q, k, v, log_a, beta, pool, layer, active)


# ---- prefill --------------------------------------------------------------


def _levels(chunk: int):
    """The block sizes 2, 4, .. ``chunk`` (a power of two)."""
    if chunk & (chunk - 1):
        raise ValueError(f"a chunk is a power of two tokens, not {chunk}")
    return [1 << s for s in range(1, chunk.bit_length())]


def _chunk(q, k, v, g, beta, s0):
    """One chunk of one head: q, k, v [C, d] in the model's dtype; g
    [C, d] float32, the running sum of ``log a`` from the chunk's first
    token (its own included); beta [C, 1] float32; s0 [d, d] float32,
    the state before the chunk. Returns (o [C, d] float32, the state
    after it). The module docstring has the algebra and where each
    exponent is referred to.

    Precision. A float32 model: every product at "highest". A bfloat16
    model: an operand that is one of the model's own activations, or one
    rounding away from them (the decayed keys and queries, a level's
    pairs, U and W), goes to the MXU as one bfloat16 value; what is
    ACCUMULATED over tokens, the running sums, the inverse and the
    state, as two (value = hi + lo, 16 bits of mantissa), each product
    summed in float32."""
    C, d = q.shape
    exact = q.dtype == jnp.float32
    f32, bf16 = jnp.float32, jnp.bfloat16

    def dot(a, b, dims=_NN):
        if exact:
            return jax.lax.dot_general(a, b, dims, precision=_HI)
        return jax.lax.dot_general(a.astype(bf16), b.astype(bf16), dims,
                                   preferred_element_type=f32)

    def parts(x):
        hi = x.astype(bf16)
        return hi, (x - hi.astype(f32)).astype(bf16)

    def dot_two(a, b, dims=_NN, two=1):
        """``dot`` with operand ``two`` (0: a, 1: b) in two parts."""
        if exact:
            return dot(a, b, dims)
        if two:
            return sum(dot(a, part, dims) for part in parts(b))
        return sum(dot(part, b, dims) for part in parts(a))

    qf, kf, vf = (x.astype(f32) for x in (q, k, v))
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    inverse = (row == col).astype(f32)
    qk = jnp.zeros((C, C), f32)
    for shift, b in enumerate(_levels(C), start=1):
        half = b // 2
        block, at = row >> shift, row & (b - 1)
        # rho: the running sum at the last token of the first half of
        # each token's block, picked by a matrix of ones. Both sides of
        # a pair take the SAME rho, so what rounding leaves of it
        # cancels in their product; the clamp only keeps the pairs that
        # are masked out finite.
        last_of_first = (block << shift) + (half - 1)
        rho = dot_two((col == last_of_first).astype(f32), g)
        from_rho = jnp.exp(jnp.minimum(g - rho, _CLAMP))  # second half's
        to_rho = jnp.exp(jnp.minimum(rho - g, _CLAMP))    # first half's
        keys = kf * to_rho
        pairs = ((block == (col >> shift)) & (at >= half)
                 & ((col & (b - 1)) < half))
        a_b = jnp.where(pairs, beta * dot(kf * from_rho, keys, _NT), 0.0)
        qk = qk + jnp.where(pairs, dot(qf * from_rho, keys, _NT), 0.0)
        inverse = inverse - dot_two(dot_two(inverse, a_b, two=0), inverse)
    qk = qk + jnp.where(row == col,
                        jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
    from_start = jnp.exp(g)                               # [C, d], <= 1
    u = dot_two(inverse, beta * vf, two=0)
    w = dot_two(inverse, beta * kf * from_start, two=0)
    u = u - dot_two(w, s0)
    o = dot_two(qf * from_start, s0) + dot(qk, u)
    g_end = g[C - 1:C, :]                                 # [1, d]
    # exp(g_end) as a column over the state's rows: the row's values on
    # a diagonal, summed over lanes.
    eye = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
    decay = jnp.sum(jnp.where(eye, jnp.exp(g_end), 0.0), axis=1,
                    keepdims=True)                        # [d, 1]
    s1 = decay * s0 + dot(kf * jnp.exp(g_end - g), u, _TN)
    return o, s1


def _padded_chunks(q, k, v, log_a, beta, chunk):
    """Whole chunks whatever S is, the tail as a caller's padding (no
    decay, no write): (q, k, v [n, C, H, d], g [n, C, H, d] the running
    sums inside each chunk, beta [n, C, H])."""
    S = q.shape[0]
    tail = -S % chunk

    def chunks(x):
        x = jnp.pad(x, ((0, tail),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])

    g = jnp.cumsum(chunks(log_a.astype(jnp.float32)), axis=1)
    return (chunks(q), chunks(k), chunks(v), g,
            chunks(beta.astype(jnp.float32)))


def xla_delta_prefill(q, k, v, log_a, beta, *, chunk=CHUNK):
    """The XLA path of :func:`delta_prefill`: a scan over chunks of
    ``_chunk`` mapped over the heads."""
    S, H, d = q.shape
    heads = jax.vmap(_chunk, in_axes=(1, 1, 1, 1, 1, 0), out_axes=(1, 0))

    def one(state, xs):
        qc, kc, vc, gc, bc = xs
        o, state = heads(qc, kc, vc, gc, bc[..., None], state)
        return state, o

    state, o = jax.lax.scan(
        one, jnp.zeros((H, d, d), jnp.float32),
        _padded_chunks(q, k, v, log_a, beta, chunk))
    return o.reshape(-1, H, d)[:S].astype(q.dtype), state


def _delta_scan_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref):
    """Grid (H, S // C). q_ref, k_ref, v_ref, o_ref [C, d]; g_ref [C, d]
    float32; beta_ref [1, C]; s_ref [d, d] the head's state, resident
    over its chunks."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _fresh():
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

    C = q_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    beta = jnp.sum(jnp.where(eye, beta_ref[...], 0.0), axis=1,
                   keepdims=True)                         # [C, 1]
    o, s1 = _chunk(q_ref[...], k_ref[...], v_ref[...], g_ref[...], beta,
                   s_ref[...])
    o_ref[...] = o.astype(o_ref.dtype)
    s_ref[...] = s1


def delta_scan(q, k, v, log_a, beta, *, interpret=False):
    """The prefill kernel. Arguments and results as
    :func:`delta_prefill`; S a multiple of ``CHUNK``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, d = q.shape
    C = CHUNK
    qc, kc, vc, g, bc = _padded_chunks(q, k, v, log_a, beta, C)

    def heads_first(x):                                   # -> [H, S, d]
        return x.reshape(S, H, d).transpose(1, 0, 2)

    rows = pl.BlockSpec((None, C, d), lambda h, c: (h, c, 0))
    o, state = pl.pallas_call(
        _delta_scan_kernel,
        grid=(H, S // C),
        in_specs=[rows, rows, rows, rows,
                  pl.BlockSpec((None, None, 1, C),
                               lambda h, c: (h, c, 0, 0))],
        out_specs=[rows, pl.BlockSpec((None, d, d), lambda h, c: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((H, S, d), q.dtype),
                   jax.ShapeDtypeStruct((H, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(heads_first(qc), heads_first(kc), heads_first(vc), heads_first(g),
      bc.transpose(2, 0, 1)[:, :, None, :])
    return o.transpose(1, 0, 2), state


def delta_prefill(q, k, v, log_a, beta):
    """One prompt from an empty state. q, k, v [S, H, d]; log_a
    [S, H, d]; beta [S, H]. Returns (the outputs [S, H, d], the state
    [H, d, d] float32 after the last token). A token whose log decays
    and beta are 0 leaves the state exactly as it was: how the caller
    keeps a bucket's padding out of it."""
    kernel = delta_path(q.shape[-1], q.shape[1], q.shape[0]) == "delta_kernel"
    return (delta_scan if kernel else xla_delta_prefill)(
        q, k, v, log_a, beta)
