"""The plain reference for Brumby (``model_type`` brumby: power retention
of degree 2 on Qwen3-14B's widths): float32 ``jax.numpy``, every matmul
at ``precision="highest"``, no state, no chunks, no kernel, no cache, and
no code of the program.

It computes the ATTENTION form of the layer, quadratic in the sequence;
the program serves the recurrent form (a state a slot, a chunked scan
for the prompt). That the two are different forms of one function is
what makes the comparison tell: a wrong decay, a lost pair of the
symmetric power, padding that reached the state or a slot that kept
another request's state all part them.

The layer; x [S, hidden], every norm an RMSNorm with ``rms_norm_eps``
and a learned weight, no bias on a projection (``attention_bias``
false), ``d`` = ``head_dim``, query head ``h`` reads KV head
``n = h // (num_attention_heads / num_key_value_heads)``:

1. ``y = norm_in(x)``. ``q = y W_q`` (``num_attention_heads`` heads),
   ``k = y W_k``, ``v = y W_v`` (``num_key_value_heads`` heads).
   ``log g = log_sigmoid(y W_g + b_g)``: one scalar a KV head a token.
2. ``q = rotary(norm_q(q))``, ``k = rotary(norm_k(k))``: an RMSNorm over
   each head's ``d`` (Qwen3's block), then rotary over all of ``d`` at
   ``rope_theta``, unscaled, the two halves of ``d`` turned together.
3. ``a_tj = exp(sum_{i=j+1..t} log g_i[n]) (q_t[h] . k_j[n])^2 / d`` for
   ``j <= t`` (the token's own term undecayed), else 0.
   ``o_t[h] = sum_j a_tj v_j[n] / (sum_j a_tj + 1e-6)``.
   ``x = x + concat_h(o[h]) W_o``.
4. ``x = x + W_down(silu(W_gate y') * W_up y')``, ``y' = norm_mlp(x)``.
5. After the last layer the final RMSNorm and the untied head.

``config.json`` gives the widths, ``rope_theta``, ``rms_norm_eps``, no
bias, an untied head, and no key of the retention. Everything in 1-3
beyond q, k, v and o is the configuration file's ``assumed``, each with
its reason: the degree 2, the gate and its bias, the per-head norms, the
``1/d`` in the weights, the normaliser and its 1e-6, and that the decay
is the running sum of log gates taken as ``cum_t - cum_j``.

It takes the program's parameter tree (one stacked tree of layers, with
``wg`` [hidden, Hkv] and ``bg`` [Hkv] beside the usual leaves). For
memory, none changing a result: a layer's weights are cast to float32
inside the layer scan, queries work in blocks of ``Q_BLOCK`` against all
keys, the FFN and the head in blocks of ``ROW_BLOCK`` positions.

``control_margins`` is the control's handle: the RECURRENT form, a token
at a time, with the state and the normaliser kept in a given dtype. In
float32 it is this reference again by another route; in bfloat16, the
precision below the one the configuration states for the state, it is
what ``LOGIT_MARGIN_TOL`` has to refuse.

Tolerances, and why. float32: both sides in float32, differing in the
form (a state against a sum over the past) and the order of sums; the
limit is 1e-3 on logits of spread ~1, where the CPU tests read ~1e-5
at a tiny size. bfloat16 ``LOSS_ATOL``: the Mistral reference's; no cell
reads it. bfloat16 ``LOGIT_MARGIN_TOL``: from two readings on the v5e
at the published widths, L6 (my chip runs, PR 45; PERF.md section 6).
The system: over fifteen runs on as many seeds of ``serve-brumby-c16-8k``
(four finished requests a run, 2,900-4,100 served tokens at contexts
4.6k-16k) a run's worst margin read 0.056-0.095, and 95% of served
tokens are the reference's argmax. ``control_margins`` with a bfloat16
state on 8,192 seeded tokens, two seeds: the token it puts first trails
the float32 reference's best by 4.44-5.77 at worst and by 0.76-0.86 over
the later half of the sequence (p99 0.48-0.51, 61-65% argmax): not
correct. With a float32 state 0.0015-0.0018 (99.8% argmax). The limit is
0.25: 2.6 times the largest the system gave, a third of the control's
smallest late reading. What goes wrong with a bfloat16 state is the
heads whose gates remember a thousand tokens and more (the
configuration's ``assumed.gate_bias``): a token's increment is then
under the state's resolution and is rounded away.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _HI, _f32, _rms_norm, _rotary

Q_BLOCK = 128
ROW_BLOCK = 512
EPS = 1e-6
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin (0.095) and the
# bfloat16-state control's smallest (0.76); the docstring has both.
LOGIT_MARGIN_TOL = {"bfloat16": 0.25, "float32": 1e-3}


def _blocks(x, block):
    """[B, S, ...] -> [S // block, B, block, ...]."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape((b, s // block, block) + x.shape[2:]), 1, 0)


def _unblocks(x):
    """[n, B, block, ...] -> [B, n * block, ...]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _retention(q, k, v, log_g):
    """The attention form: q [B, S, H, d]; k, v [B, S, Hkv, d]; log_g
    [B, S, Hkv]. In blocks of queries against all keys."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block = min(Q_BLOCK, s)
    cum = jnp.cumsum(log_g, axis=1)                       # [B, S, Hkv]
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, cum_q, start = args         # [B, block, Hkv, G, d], [B, block, Hkv]
        dots = jnp.einsum("bqngd,bsnd->bngqs", qb, k, precision=_HI)
        gap = (jnp.moveaxis(cum_q, 1, 2)[:, :, :, None]
               - jnp.moveaxis(cum, 1, 2)[:, :, None, :])  # [B, Hkv, q, s]
        q_pos = start + jnp.arange(block)
        past = key_pos[None, :] <= q_pos[:, None]
        decay = jnp.where(past, jnp.exp(jnp.where(past, gap, 0.0)), 0.0)
        a = dots * dots / d * decay[:, :, None]
        out = jnp.einsum("bngqs,bsnd->bqngd", a, v, precision=_HI)
        total = jnp.moveaxis(a.sum(-1), 3, 1)             # [B, q, Hkv, G]
        return out / (total[..., None] + EPS)

    out = jax.lax.map(one_block, (
        _blocks(q.reshape(b, s, hkv, h // hkv, d), block),
        _blocks(cum, block), jnp.arange(s // block) * block))
    return _unblocks(out).reshape(b, s, h, d)


def _mlp(y, w):
    block = min(ROW_BLOCK, y.shape[1])

    def one_block(yb):
        gate = jnp.einsum("bsm,mf->bsf", yb, w["w_gate"], precision=_HI)
        up = jnp.einsum("bsm,mf->bsf", yb, w["w_up"], precision=_HI)
        return jnp.einsum("bsf,fm->bsm", jax.nn.silu(gate) * up,
                          w["w_down"], precision=_HI)

    return _unblocks(jax.lax.map(one_block, _blocks(y, block)))


def _projections(x, w, config):
    """Steps 1 and 2: q, k rotated, v, and the log gates."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    y = _rms_norm(x, w["attn_norm"], eps)
    q = jnp.einsum("bsm,mhd->bshd", y, w["wq"], precision=_HI)
    k = jnp.einsum("bsm,mhd->bshd", y, w["wk"], precision=_HI)
    v = jnp.einsum("bsm,mhd->bshd", y, w["wv"], precision=_HI)
    log_g = jax.nn.log_sigmoid(
        jnp.einsum("bsm,mn->bsn", y, w["wg"], precision=_HI) + w["bg"])
    q = _rotary(_rms_norm(q, w["q_norm"], eps), theta)
    k = _rotary(_rms_norm(k, w["k_norm"], eps), theta)
    return q, k, v, log_g


def hidden(params, tokens, config, retention=_retention):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S a
    multiple of ``ROW_BLOCK`` or smaller than ``Q_BLOCK``."""
    eps = config["rms_norm_eps"]
    x = _f32(params["embed"][tokens])

    def layer(x, w):
        w = jax.tree.map(_f32, w)
        a = retention(*_projections(x, w, config))
        x = x + jnp.einsum("bshd,hdm->bsm", a, w["wo"], precision=_HI)
        return x + _mlp(_rms_norm(x, w["mlp_norm"], eps), w), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms_norm(x, _f32(params["final_norm"]), eps)


def _per_block(x, head, reduce_logits, *others):
    """``reduce_logits(logits [B, block, V], *others' blocks)`` over
    blocks of positions, so [B, S, V] never exists at once."""
    block = min(ROW_BLOCK, x.shape[1])

    def one_block(args):
        xb, *rest = args
        return reduce_logits(
            jnp.einsum("bsm,mv->bsv", xb, head, precision=_HI), *rest)

    return _unblocks(jax.lax.map(
        one_block, tuple(_blocks(a, block) for a in (x,) + others)))


def logits(params, tokens, config):
    """[B, S, V] logits whole: for a test at a tiny size."""
    return jnp.einsum("bsm,mv->bsv", hidden(params, tokens, config),
                      _f32(params["lm_head"]), precision=_HI)


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1]."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["lm_head"]), nll, tokens[:, 1:]).mean()


def _margin(logits, targets):
    chosen = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return logits.max(-1) - chosen


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no state."""
    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["lm_head"]), _margin, tokens[:, 1:])


def _recurrent(state_dtype):
    """The recurrent form, a token at a time, the state [d, d] whole (no
    symmetric packing: ``(q.k)^2 = q^T (k k^T) q``) and the normaliser
    kept in ``state_dtype`` between tokens."""
    def retention(q, k, v, log_g):
        b, s, h, d = q.shape
        hkv = k.shape[2]

        def step(carry, xs):
            state, norm = carry                # [B,Hkv,d,d,d], [B,Hkv,d,d]
            qt, kt, vt, lg = xs
            kk = kt[..., :, None] * kt[..., None, :] / d
            g = jnp.exp(lg)[..., None, None]
            state = (g[..., None] * _f32(state)
                     + kk[..., None] * vt[..., None, None, :])
            norm = g * _f32(norm) + kk
            qg = qt.reshape(b, hkv, h // hkv, d)
            qq = qg[..., :, None] * qg[..., None, :]       # [B,Hkv,G,d,d]
            state, norm = state.astype(state_dtype), norm.astype(state_dtype)
            out = jnp.einsum("bngxy,bnxyd->bngd", qq, _f32(state),
                             precision=_HI)
            total = jnp.einsum("bngxy,bnxy->bng", qq, _f32(norm),
                               precision=_HI)
            return (state, norm), (out / (total[..., None] + EPS)
                                   ).reshape(b, h, d)

        zeros = (jnp.zeros((b, hkv, d, d, d), state_dtype),
                 jnp.zeros((b, hkv, d, d), state_dtype))
        _, out = jax.lax.scan(step, zeros, tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_g)))
        return jnp.moveaxis(out, 0, 1)

    return retention


def control_margins(params, tokens, config, state_dtype):
    """The control: the recurrent form with its state in ``state_dtype``
    put in the program's place. For tokens [B, S]: at each position, how
    far the token such a model puts first trails the float32 reference's
    best logit, [B, S]; what ``LOGIT_MARGIN_TOL`` has to refuse for a
    bfloat16 state and pass for a float32 one."""
    head = _f32(params["lm_head"])
    first = _per_block(
        hidden(params, tokens, config, _recurrent(state_dtype)), head,
        lambda logits: logits.argmax(-1))
    return _per_block(hidden(params, tokens, config), head, _margin, first)
