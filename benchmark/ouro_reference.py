"""The plain reference for Ouro (a looped language model): float32
``jax.numpy``, every matmul at ``jax.default_matmul_precision("highest")``,
no kernel, no cache, no code of the program.

The model (``config.json``'s keys; what it leaves unsaid is the released
``modeling_ouro.py``'s and the family's description, "Scaling Latent
Reasoning via Looped Language Models", and is listed in the
configuration file's ``assumed``). ``N(.)`` is an RMSNorm with
``rms_norm_eps`` and a learned weight of its own:

    layer l:  a  = x + N2_l( Attn_l( N1_l(x) ) )
              x' = a + N4_l( SwiGLU_l( N3_l(a) ) )
    model:    x = E[tokens]
              for t in 0 .. total_ut_steps - 1:
                  x = layer_{L-1}( .. layer_0(x) )    the SAME weights
                  h_t = Nf(x);  x = h_t;  g_t = h_t . w_gate + b_gate
              logits = h_last W_head

``Attn``: q, k, v without biases, rotary over the two halves of a head
(``rope_theta``, no scaling) on q and k, causal softmax over every
earlier token at scale ``head_dim ** -0.5``, query head h on KV head
``h // (H / Hkv)``, then ``Wo``. A pass attends over ITS OWN keys and
values: pass t's layer l sees what pass t's layer l made of every
earlier token, which in a forward pass without a cache is simply the
layer applied to the pass's input. The final norm ``Nf`` stands behind
EVERY pass and its output is the next pass's input.

The exit distribution: ``lambda_t = sigmoid(g_t)``; ``p_t = lambda_t
prod_{j<t} (1 - lambda_j)`` for every pass but the last, and the last
takes what is left, ``prod_{j<last} (1 - lambda_j)``. A token leaves at
the first pass whose cumulative ``p`` reaches ``early_exit_threshold``;
at the published 1.0 that is the last pass, always, so what is served is
the last pass's logits. ``loss`` is therefore the LAST pass's next-token
cross entropy, what a threshold of 1.0 serves, and NO training
objective: the family trains on every pass's loss weighted by ``p_t``
less an entropy term whose weight ``config.json`` does not give.

It takes the program's parameter tree (``embed``, ONE stacked ``layers``
tree, ``final_norm``, ``lm_head``, ``exit_w`` [M, 1], ``exit_b`` [1]).
The passes are a Python loop, written out, so that nothing hides them;
inside a pass the layers run under ``lax.scan`` with the cast to float32
in the body (one layer's float32 copy at a time). For memory, neither
changing a result: queries attend in blocks of at most ``BLOCK`` rows
against all keys, and the head multiplies blocks of at most ``BLOCK``
positions (the largest divisor of the length under it: 640 = 5 x 128),
so that 640 positions x 49,152 logits in float32 fit beside an engine
that holds 13.4 of the chip's 15.75 GiB.

``inputs``, on ``hidden_passes`` and what calls it: a dtype to which
EVERY matmul input is rounded (and taken back to float32) before the
product: the control that says what a lower precision would read. None:
float32 as it comes.

Tolerances, and why. float32: both sides in float32, differing in the
order of sums; at the tiny size on the CPU
(tests/bench_harness/test_benchmark_ouro.py) the engine's logits agree
with this reference within 1e-4 and each of six single departures moves
a logit, or the exit distribution, by more than a hundred times that.
bfloat16 ``LOSS_ATOL``: the Mistral reference's, for its reason; no cell
reads it. bfloat16 ``LOGIT_MARGIN_TOL``: MEASURED, not copied: 192 layer
bodies in bfloat16 drift further than 8 or 27 do (ten times Mistral's 16).
The two readings are written where the number is set, below.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 128
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16, from two readings on the v5e at the published widths, all 48
# layers and 4 passes (my chip runs, PR 65; PERF.md section 6). The
# system: over 14 runs on 14 seeds of ``serve-ouro-c8-640`` (four
# finished requests a run, 1,127-1,414 served tokens) a run's worst margin
# read 0.750-1.469, median 0.99, and 30-75% of served tokens are the
# reference's argmax. That is bfloat16's own distance through 192 layer bodies and no
# kernel's: the program's logits (prefill through the flash kernel, 56
# decode steps through the page walk) and ``llama.forward``'s (no cache,
# the XLA attention, the same bfloat16) lie equally far from this
# reference (|difference| 1.07 / 0.98 at worst and 0.171 / 0.169 in the
# mean on one seed, 1.18 / 1.22 and 0.195 / 0.208 on another; half as far
# from each other), with logits of unit spread whose best two lie 0.15-0.17
# apart in the mean. The control, ``control_margins`` with every matmul
# input rounded to float8_e4m3, the precision below bfloat16, put in the
# program's place on two seeded sequences of 640 tokens a seed, two seeds:
# the token it puts first trails the float32 best by 5.19-6.17 at worst
# (p99 4.8-5.8, 0-2% argmax): not correct. (With bfloat16 INPUTS alone,
# the activations left in float32: 0.28-0.50, 67-84% argmax; the program
# also keeps its residual in bfloat16.) The limit is 2.5: 1.7 times the
# largest the system gave, and the control's smallest reading is 2.1
# times the limit.
LOGIT_MARGIN_TOL = {"bfloat16": 2.5, "float32": 1e-4}


def _f32(x):
    return x.astype(jnp.float32)


def _mm(spec, a, b, inputs=None):
    """``einsum`` in float32 at the highest precision; with ``inputs``
    both operands rounded to that dtype first."""
    if inputs is not None:
        a, b = (_f32(x.astype(inputs)) for x in (a, b))
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(spec, a, b)


def _norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, theta):
    """x [B, S, H, D]: the pair (i, i + D/2) turned by position times
    ``theta ** (-i / (D/2))``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _block(n: int) -> int:
    """The largest divisor of ``n`` that is at most ``BLOCK``."""
    return max(b for b in range(1, min(BLOCK, n) + 1) if n % b == 0)


def _attention(q, k, v, inputs):
    """Causal softmax attention in blocks of queries: q [B, S, H, D], k
    and v [B, S, Hkv, D]."""
    b, s, h, d = q.shape
    hkv, block = k.shape[2], _block(s)
    q = q.reshape(b, s // block, block, hkv, h // hkv, d)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, start = args  # [B, block, Hkv, G, D]
        scores = _mm("bqkgd,bskd->bkgqs", qb, k, inputs) * d ** -0.5
        q_pos = start + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        return _mm("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v,
                   inputs)

    out = jax.lax.map(one_block, (jnp.moveaxis(q, 1, 0),
                                  jnp.arange(s // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _layer(x, w, config, inputs):
    """One layer, four norms ("sandwich"): ``w`` its own weights."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    w = jax.tree.map(_f32, w)
    y = _norm(x, w["attn_norm"], eps)
    q = _mm("bsm,mhd->bshd", y, w["wq"], inputs)
    k = _mm("bsm,mhd->bshd", y, w["wk"], inputs)
    v = _mm("bsm,mhd->bshd", y, w["wv"], inputs)
    a = _attention(_rotary(q, theta), _rotary(k, theta), v, inputs)
    a = _mm("bshd,hdm->bsm", a, w["wo"], inputs)
    x = x + _norm(a, w["post_attn_norm"], eps)
    y = _norm(x, w["mlp_norm"], eps)
    gate = _mm("bsm,mf->bsf", y, w["w_gate"], inputs)
    up = _mm("bsm,mf->bsf", y, w["w_up"], inputs)
    m = _mm("bsf,fm->bsm", jax.nn.silu(gate) * up, w["w_down"], inputs)
    return x + _norm(m, w["post_mlp_norm"], eps)


def hidden_passes(params, tokens, config, inputs=None):
    """``[h_0, .., h_last]``, each [B, S, M]: the final norm's output
    behind every pass, for tokens [B, S]."""
    eps = config["rms_norm_eps"]
    final_norm = _f32(params["final_norm"])
    x = _f32(params["embed"][tokens])
    out = []
    for _ in range(config["total_ut_steps"]):
        x, _ = jax.lax.scan(
            lambda x, w: (_layer(x, w, config, inputs), None), x,
            params["layers"])
        x = _norm(x, final_norm, eps)
        out.append(x)
    return out


def hidden(params, tokens, config, inputs=None):
    """The last pass's normed hidden states [B, S, M]: what the head
    reads at ``early_exit_threshold`` 1.0."""
    return hidden_passes(params, tokens, config, inputs)[-1]


def exit_distribution(params, tokens, config):
    """[B, S, passes]: the probability that a token leaves at each
    pass, from the gate behind every pass's norm."""
    gates = [_mm("bsm,mo->bso", h, _f32(params["exit_w"]))[..., 0]
             + _f32(params["exit_b"])[0]
             for h in hidden_passes(params, tokens, config)]
    out, stays = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        leave = jax.nn.sigmoid(g)
        out.append(leave * stays)
        stays = stays * (1.0 - leave)
    return jnp.stack(out + [stays], axis=-1)


def _per_block(x, targets, head, reduce_logits, inputs=None):
    """``reduce_logits(logits [B, block, V], targets [B, block])`` over
    blocks of positions, so [B, S, V] never exists at once."""
    b, s, m = x.shape
    block = _block(s)

    def one_block(args):
        xb, tb = args
        return reduce_logits(_mm("bsm,mv->bsv", xb, head, inputs), tb)

    out = jax.lax.map(one_block, (
        jnp.moveaxis(x.reshape(b, s // block, block, m), 1, 0),
        jnp.moveaxis(targets.reshape(b, s // block, block), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s)


def logits(params, tokens, config):
    """[B, S, V] logits whole: for a test at a tiny size."""
    return _mm("bsm,mv->bsv", hidden(params, tokens, config),
               _f32(params["lm_head"]))


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1] under the LAST
    pass's logits: what a threshold of 1.0 serves, and no training
    objective (the module docstring says why)."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, tokens[:, 1:], _f32(params["lm_head"]), nll).mean()


def _margin(logits, targets):
    chosen = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return logits.max(-1) - chosen


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no cache."""
    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, tokens[:, 1:], _f32(params["lm_head"]), _margin)


def control_margins(params, tokens, config, inputs):
    """[B, S]: how far the token that this reference puts FIRST when
    every matmul input is rounded to ``inputs`` trails the float32
    reference's best, for tokens [B, S]: what an engine computing in
    that precision would read against ``LOGIT_MARGIN_TOL``."""
    head = _f32(params["lm_head"])
    first = _per_block(
        hidden(params, tokens, config, inputs), tokens, head,
        lambda logits, _: logits.argmax(-1).astype(tokens.dtype), inputs)
    return _per_block(hidden(params, tokens, config), first, head, _margin)
