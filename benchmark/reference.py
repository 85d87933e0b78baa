"""The plain reference: a Mistral-style decoder in float32 ``jax.numpy``.

It follows the published architecture (RMSNorm before attention and MLP,
rotary embedding on halves as in the released implementation, grouped
queries, SiLU-gated MLP, untied head). No kernel, no cache, no batching
tricks: every matmul in float32 at ``precision="highest"``. It takes the
program's parameter tree (``embed``, stacked ``layers``, ``final_norm``,
``lm_head``) so that both sides run the same weights, and shares no code
with the program. Two departures from a textbook loop, both for memory
and neither changing a result: layers run under ``lax.scan`` with the
cast to float32 inside the body (one layer's float32 copy at a time),
and queries attend in blocks of ``Q_BLOCK`` rows against all keys.

Tolerances, and why. The system computes in bfloat16 (8 bits of
mantissa); the reference in float32 on the same bfloat16 weights.
``LOSS_ATOL``: a mean over thousands of tokens of a loss near 10.9
averages the rounding away: the v5e measured |difference| of at most
0.0006 over 14 seeds (L4, my chip runs, PR 24), and the bound is three
times that, far under what an 8-bit matmul would move it by. ``LOGIT_MARGIN_TOL``:
the engine picks its token from bfloat16 logits after 16 layers of
bfloat16 arithmetic, so where the float32 reference puts two logits
close together the engine may take the second. About 1 token in 25 is
not the reference's argmax, and the worst of 23 different samples trailed
its best by 0.054 (L16, my chip runs, PR 24; chip_smoke.py, against a
bfloat16 forward at 4 layers, saw 0.0065). The bound is one and a half
times that, 0.08, a fiftieth of the logits' range (~4): 8-bit
arithmetic, a dropped layer or a wrong position would pass it by far.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
LOGIT_MARGIN_TOL = {"bfloat16": 0.08, "float32": 1e-4}

_HI = jax.lax.Precision.HIGHEST


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, theta):
    """x [B, S, H, D]: rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention, q [B, S, H, D], k and v [B, S, Hkv, D];
    query head h reads key head h // (H / Hkv)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block = min(Q_BLOCK, s)
    q = q.reshape(b, s // block, block, hkv, h // hkv, d)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, start = args  # [B, block, Hkv, G, D]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qb, k,
                            precision=_HI) * d ** -0.5
        q_pos = start + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        return jnp.einsum("bkgqs,bskd->bqkgd",
                          jax.nn.softmax(scores, -1), v, precision=_HI)

    out = jax.lax.map(one_block, (jnp.moveaxis(q, 1, 0),
                                  jnp.arange(s // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def hidden(params, tokens, config):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S must be a
    multiple of ``Q_BLOCK`` or smaller than it. ``config`` is the
    configuration file's dict, read for ``rope_theta`` and
    ``rms_norm_eps`` (Python floats: close over it before ``jax.jit``)."""
    theta, eps = config["rope_theta"], config["rms_norm_eps"]
    x = _f32(params["embed"][tokens])

    def layer(x, w):
        w = jax.tree.map(_f32, w)
        y = _rms_norm(x, w["attn_norm"], eps)
        q = jnp.einsum("bsm,mhd->bshd", y, w["wq"], precision=_HI)
        k = jnp.einsum("bsm,mhd->bshd", y, w["wk"], precision=_HI)
        v = jnp.einsum("bsm,mhd->bshd", y, w["wv"], precision=_HI)
        a = _attention(_rotary(q, theta), _rotary(k, theta), v)
        x = x + jnp.einsum("bshd,hdm->bsm", a, w["wo"], precision=_HI)
        y = _rms_norm(x, w["mlp_norm"], eps)
        gate = jnp.einsum("bsm,mf->bsf", y, w["w_gate"], precision=_HI)
        up = jnp.einsum("bsm,mf->bsf", y, w["w_up"], precision=_HI)
        x = x + jnp.einsum("bsf,fm->bsm", jax.nn.silu(gate) * up,
                           w["w_down"], precision=_HI)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms_norm(x, _f32(params["final_norm"]), eps)


def _per_block(params, tokens, config, reduce_logits):
    """``reduce_logits(logits [B, block, V], targets [B, block])`` over
    blocks of positions, so [B, S, V] never exists at once."""
    x = hidden(params, tokens[:, :-1], config)
    targets = tokens[:, 1:]
    b, s, m = x.shape
    block = min(Q_BLOCK, s)
    head = _f32(params["lm_head"])

    def one_block(args):
        xb, tb = args
        logits = jnp.einsum("bsm,mv->bsv", xb, head, precision=_HI)
        return reduce_logits(logits, tb)

    out = jax.lax.map(one_block, (
        jnp.moveaxis(x.reshape(b, s // block, block, m), 1, 0),
        jnp.moveaxis(targets.reshape(b, s // block, block), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s)


def token_nll(params, tokens, config):
    """Next-token negative log likelihood [B, S] for tokens [B, S+1]."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    return _per_block(params, tokens, config, nll)


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1]."""
    return token_nll(params, tokens, config).mean()


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no cache."""
    def margin(logits, targets):
        chosen = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return logits.max(-1) - chosen

    return _per_block(params, tokens, config, margin)
