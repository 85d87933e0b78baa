"""Lightning attention: a linear attention decayed by a constant a head,
served from a state of fixed size a slot.

The function, for one head of width ``d`` whose decay is ``lambda`` in
(0, 1) (``log_g = log lambda``, the same for every token of the head;
MHA: a key and a value head for every query head)::

    S_t = lambda S_{t-1} + k_t v_t^T          S [d, d] float32
    o_t = S_t^T q_t * scale

No normaliser and no gate a token: what tells it from power retention
(ops/retention.py: degree 2, a gate a token, a normaliser row) and from
the delta rule (ops/delta_attention.py: a decay a channel and a
correction). As an attention it is ``o_t = scale sum_{j<=t}
lambda^(t-j) (q_t . k_j) v_j``, which is what a plain reference computes.

The state pool (models/generation.py ``PagedKVCache``, kind "linear") is
``[L, B, H, d, d]`` float32, rows the key's coordinates and columns the
value's: the delta rule's layout (``delta_attention.state_shape``).

:func:`lightning_decode` — one token a slot: decay the slot's state at
``layer``, add ``k v^T``, write it back, read ``q`` out of the new state.
:func:`lightning_prefill` — one prompt in chunks of ``CHUNK`` tokens:
inside a chunk the masked, decayed ``Q K^T``, between chunks the state.
Both take the decay as a LOG GATE A TOKEN (``log_g`` [.., H]), so that a
caller keeps a bucket's padding out of the state as it does for
retention: a token whose log gate is 0 and whose key is 0 leaves the
state exactly as it was.

Each has two implementations, chosen by :func:`lightning_path` from
platform and shape, never by a user: Pallas TPU kernels for heads of 128
(``lightning_step``: grid (slot, block of up to 32 heads), the state block
brought in and written back by the pipeline through an output aliased to
the pool, an idle slot's steps pointed at a neighbour's block as the
retention step points them, ``retention._idle_blocks``;
``lightning_scan``: grid (head, chunk), the state resident in the output
block across a head's chunks), and plain XLA for any platform and shape
(tier-1 runs it on the CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .retention import CHUNK, _chunks, _idle_blocks

_HI = jax.lax.Precision.HIGHEST
# Heads a grid step of the decode kernel moves: 32 states of 128 x 128
# float32 are two mebibytes each way.
_STEP_HEADS = 32


def _step_heads(heads: int) -> int:
    """The largest count of heads up to ``_STEP_HEADS`` that divides
    ``heads``."""
    return max(n for n in range(1, min(heads, _STEP_HEADS) + 1)
               if heads % n == 0)


def state_shape(layers: int, batch: int, heads: int, d: int):
    return (layers, batch, heads, d, d)


def log_decays(heads: int, layer: int, layers: int) -> jax.Array:
    """[heads] float32, ``log lambda_n`` of head ``n`` (0-based) in the
    published layer ``layer`` of ``layers``: Lightning Attention-2's
    slopes, ``-2^(-8 (n + 1) / heads)``, times ``1 - layer / (layers - 1)
    + 1e-5`` (the deeper the layer, the longer its memory)."""
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                    / heads)
    return -slope * (1.0 - layer / max(layers - 1, 1) + 1e-5)


def lightning_path(head_dim: int, heads: int, tokens: int = CHUNK) -> str:
    """``"lightning_kernel"`` or ``"xla"``: what the two programs run
    here for ``heads`` heads of ``head_dim`` (and a prefill of
    ``tokens``)."""
    from .flash_attention import _on_tpu

    if _on_tpu() and head_dim == 128 and tokens % CHUNK == 0:
        return "lightning_kernel"
    return "xla"


# ---- decode ---------------------------------------------------------------


def xla_lightning_decode(q, k, v, log_g, pool, layer, active, *, scale):
    """The XLA path of :func:`lightning_decode`."""
    state = pool[layer]                                   # [B, H, d, d]
    with jax.named_scope("lin.update"):
        g = jnp.exp(log_g.astype(jnp.float32))[..., None, None]
        new = g * state + (k.astype(jnp.float32)[..., :, None]
                           * v.astype(jnp.float32)[..., None, :])
        new = jnp.where(active[:, None, None, None], new, state)
    with jax.named_scope("lin.read"):
        out = jnp.einsum("bhde,bhd->bhe", new,
                         q.astype(jnp.float32) * scale, precision=_HI)
    return out.astype(q.dtype), pool.at[layer].set(new)


def _lightning_step_kernel(slot_ref, head_ref, act_ref, layer_ref, q_ref,
                           k_ref, v_ref, g_ref, s_in, y_ref, s_out):
    """Grid (B, H / hb). slot_ref, head_ref [B]: the state block an idle
    slot's steps are pointed at (``retention._idle_blocks``); act_ref
    [B]; layer_ref [1]. q_ref (times the scale), k_ref, v_ref [hb, d]
    float32 rows, g_ref [1, hb] the decays; s_in/s_out [hb, d, d], the
    same block of the pool; y_ref [hb, 1, d] float32 each head's
    read-out. q and k are turned here, a head a column: as columns in
    HBM (a last dimension of one) each would lie a value a lane tile
    and weigh what the states weigh."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)

    @pl.when(act_ref[b] == 1)
    def _step():
        q_t, k_t = q_ref[...].T, k_ref[...].T             # [d, hb]
        for h in range(s_in.shape[0]):
            new = (g_ref[:, h:h + 1] * s_in[h]
                   + k_t[:, h:h + 1] * v_ref[h:h + 1, :])  # [d, d]
            s_out[h] = new
            y_ref[h] = jnp.sum(new * q_t[:, h:h + 1], axis=0, keepdims=True)

    @pl.when(act_ref[b] == 0)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        # No active slot before this one: the block under the output is
        # one this call has not computed yet, so what goes back is what
        # came in (the retention step's reason).
        @pl.when(head_ref[b] == 0)
        def _keep():
            s_out[...] = s_in[...]


def lightning_step(q, k, v, log_g, pool, layer, active, *, scale,
                   interpret=False):
    """The decode kernel. Arguments and results as
    :func:`lightning_decode`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    hb = _step_heads(H)
    blocks = H // hb
    slot, last = _idle_blocks(active)
    f32 = jnp.float32
    g = jnp.exp(log_g.astype(f32)).reshape(B, blocks, 1, hb)

    def rows(x):
        return x.astype(f32).reshape(B, blocks, hb, d)

    def own(b, n, *_):
        return (b, n, 0, 0)

    def state_block(b, n, slot_ref, head_ref, act_ref, layer_ref):
        head = jnp.where(act_ref[b] == 1, n, head_ref[b] * (blocks - 1))
        return (layer_ref[0], slot_ref[b], head, 0, 0)

    state_spec = pl.BlockSpec((None, None, hb, d, d), state_block)
    row_spec = pl.BlockSpec((None, None, hb, d), own)
    y, pool = pl.pallas_call(
        _lightning_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, blocks),
            in_specs=[row_spec, row_spec, row_spec,
                      pl.BlockSpec((None, None, 1, hb), own), state_spec],
            out_specs=[pl.BlockSpec((None, None, hb, 1, d),
                                    lambda b, n, *_: (b, n, 0, 0, 0)),
                       state_spec],
        ),
        # The read-outs in five dimensions beside the pool's five: no
        # other kernel's name in a trace (the delta step writes four and
        # five, the page walk three and five).
        out_shape=[jax.ShapeDtypeStruct((B, blocks, hb, 1, d), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # Operands count the four prefetched scalars: the pool is 8.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(slot, last, active.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), rows(q) * scale, rows(k),
      rows(v), g, pool)
    return y.reshape(B, H, d).astype(q.dtype), pool


def lightning_decode(q, k, v, log_g, pool, layer, active, *, scale):
    """One token a slot. q, k, v [B, H, d]; log_g [B, H] the token's log
    decay; pool [L, B, H, d, d] float32; ``layer`` the pool's layer;
    ``active`` [B] bool. Each active slot's state at ``layer`` is
    decayed and takes the token's ``k v^T`` (the token's own term
    undecayed), an idle slot's is left as it is. Returns (the read-out
    ``scale S^T q`` [B, H, d], zeros or unread for idle slots; the
    pool)."""
    path = (lightning_step
            if lightning_path(q.shape[-1], q.shape[1]) == "lightning_kernel"
            else xla_lightning_decode)
    return path(q, k, v, log_g, pool, layer, active, scale=scale)


# ---- prefill --------------------------------------------------------------


def xla_lightning_prefill(q, k, v, log_g, *, scale, chunk=CHUNK):
    """The XLA path of :func:`lightning_prefill`: a scan over chunks."""
    S, H, d = q.shape
    C = chunk
    causal = jnp.tril(jnp.ones((C, C), bool))
    # Whole chunks whatever S is, the tail masked as a caller masks a
    # bucket's padding (``xla_retention_prefill``'s reason).
    tail = -S % C
    q, k, v, log_g = (jnp.pad(x, ((0, tail),) + ((0, 0),) * (x.ndim - 1))
                      for x in (q, k, v, log_g))

    def one(state, xs):
        qc, kc, vc, lg = xs                               # [C,H,d] x3, [C,H]
        cum = jnp.cumsum(lg, axis=0)                      # [C, H]
        qf, kf, vf = (x.astype(jnp.float32) for x in (qc, kc, vc))
        with jax.named_scope("lin.read"):
            s = jnp.einsum("thd,jhd->htj", qf, kf, precision=_HI)
            gap = cum.T[:, :, None] - cum.T[:, None, :]   # [H, t, j]
            a = s * jnp.where(causal, jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
            y = (jnp.einsum("htj,jhe->the", a, vf, precision=_HI)
                 + jnp.einsum("thd,hde->the", qf * jnp.exp(cum)[..., None],
                              state, precision=_HI)) * scale
        with jax.named_scope("lin.update"):
            to_end = jnp.exp(cum[-1] - cum)               # [C, H]
            state = (jnp.exp(cum[-1])[:, None, None] * state
                     + jnp.einsum("jhd,jhe->hde", kf * to_end[..., None], vf,
                                  precision=_HI))
        return state, y.astype(q.dtype)

    state, y = jax.lax.scan(
        one, jnp.zeros((H, d, d), jnp.float32),
        tuple(_chunks(x, C) for x in (q, k, v, log_g.astype(jnp.float32))))
    return y.reshape(S + tail, H, d)[:S], state


def _lightning_scan_kernel(q_ref, k_ref, kt_ref, v_ref, cum_ref, y_ref,
                           st_ref, *, scale: float):
    """Grid (H, S // C). q_ref, k_ref, v_ref [C, d]; kt_ref [d, C];
    cum_ref [1, C] the chunk's running sum of log gates; y_ref [C, d];
    st_ref [d, d] the head's state, resident over its chunks."""
    from jax.experimental import pallas as pl

    C = q_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _fresh():
        st_ref[...] = jnp.zeros(st_ref.shape, st_ref.dtype)

    row = cum_ref[...]                                    # [1, C]
    t_at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j_at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    col = jnp.sum(jnp.where(t_at == j_at, row, 0.0), axis=1, keepdims=True)
    decay = jnp.where(j_at <= t_at, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)
    # The running sum at the chunk's end: a log gate is never above 0,
    # so the last is the least.
    total = jnp.min(row, axis=1, keepdims=True)           # [1, 1]
    state = st_ref[...]
    q, v = q_ref[...], v_ref[...]
    s = jax.lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [C, C]
    intra = jnp.dot((s * decay).astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    inter = jnp.dot(q.astype(jnp.float32) * jnp.exp(col), state,
                    preferred_element_type=jnp.float32, precision=_HI)
    y_ref[...] = ((intra + inter) * scale).astype(y_ref.dtype)
    kw = kt_ref[...].astype(jnp.float32) * jnp.exp(total - row)   # [d, C]
    st_ref[...] = jnp.exp(total) * state + jnp.dot(
        kw, v.astype(jnp.float32), preferred_element_type=jnp.float32,
        precision=_HI)


def lightning_scan(q, k, v, log_g, *, scale, interpret=False):
    """The prefill kernel. Arguments and results as
    :func:`lightning_prefill`."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, d = q.shape
    C = CHUNK
    dtype = q.dtype
    qh, kh, vh = (x.transpose(1, 0, 2).astype(dtype) for x in (q, k, v))
    cum = jnp.cumsum(_chunks(log_g.astype(jnp.float32), C), axis=1)
    cum = cum.transpose(2, 0, 1)[:, :, None, :]           # [H, nC, 1, C]
    rows = pl.BlockSpec((None, C, d), lambda h, c: (h, c, 0))
    y, state = pl.pallas_call(
        functools.partial(_lightning_scan_kernel, scale=scale),
        grid=(H, S // C),
        in_specs=[rows, rows,
                  pl.BlockSpec((None, d, C), lambda h, c: (h, 0, c)), rows,
                  pl.BlockSpec((None, None, 1, C), lambda h, c: (h, c, 0, 0))],
        # The outputs with a dimension of one, so that the call writes
        # four dimensions and three: the delta rule's prefill writes
        # three and three, the retention's four and four.
        out_specs=[pl.BlockSpec((None, None, C, d), lambda h, c: (h, 0, c, 0)),
                   pl.BlockSpec((None, d, d), lambda h, c: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((H, 1, S, d), dtype),
                   jax.ShapeDtypeStruct((H, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
    )(qh, kh, kh.transpose(0, 2, 1), vh, cum)
    return y[:, 0].transpose(1, 0, 2), state


def lightning_prefill(q, k, v, log_g, *, scale):
    """One prompt from an empty state. q, k, v [S, H, d]; log_g [S, H].
    Returns (the outputs [S, H, d], the state [H, d, d] float32 after
    the last token). A token whose log gate is 0 and whose key is 0
    leaves the state exactly as it was: how the caller keeps a bucket's
    padding out of it."""
    path = (lightning_scan
            if lightning_path(q.shape[-1], q.shape[1], q.shape[0])
            == "lightning_kernel" else xla_lightning_prefill)
    return path(q, k, v, log_g, scale=scale)
