"""The plain reference for Trinity (AFMoE): float32 ``jax.numpy``, every
matmul at ``precision="highest"``, no kernel, no cache, no sort, no
grouping, and no code of the program.

The published layer (HF ``transformers`` ``models/afmoe/modeling_afmoe.py``;
keys from ``config.json``), for layer ``i`` of kind ``layer_types[i]``,
all norms RMSNorm with ``rms_norm_eps`` and a learned weight:

``x0 = embed[tokens] * sqrt(hidden_size)`` (``mup_enabled``).

1. ``h = norm_in(x)``; ``q = h Wq`` [S, H, D]; ``k = h Wk``, ``v = h Wv``
   [S, Hkv, D]; ``g = h Wg`` [S, H, D]; no biases.
2. ``q = rms(q)``, ``k = rms(k)`` over each head's D (``q_norm``,
   ``k_norm`` of width D).
3. ``sliding_attention``: rotary on halves (``rope_theta``) on q and k,
   then token t attends to j with ``t - sliding_window < j <= t``.
   ``full_attention``: NO rotary, causal over everything. Scale
   ``D ** -0.5``, query head h reads KV head ``h // (H / Hkv)``.
4. ``a = attention(q, k, v) * sigmoid(g)``; ``x = x + norm_post_attn(a Wo)``.
5. ``h = norm_pre_mlp(x)``. Layers ``i < num_dense_layers``:
   ``m = Wdown(silu(Wgate h) * Wup h)``. Others: ``s = sigmoid(h Wr)``
   over the experts; ``chosen = top_k(s + expert_bias)``; ``w = s[chosen]``
   (the bias selects and is not in the gate); ``w = w / (sum w + 1e-20)``
   (``route_norm``); ``w = w * route_scale``; ``m = shared(h) +
   sum_{e in chosen} w_e expert_e(h)``, each a SiLU-gated MLP.
6. ``x = x + norm_post_mlp(m)``. After the last layer the final RMSNorm
   and the untied ``lm_head``.

Departures from the released code, each stated in the configuration
file's ``assumed``: the router multiplies in float32 (released: in the
model's dtype, sigmoid in float32). Nothing else.

It takes the program's parameter tree: ``layers`` is a tuple of stacked
trees, consecutive layers that are alike stacked together, in layer
order; a stack with a ``router`` holds expert layers. The reference
walks the stacks one layer at a time and reads the layer's kind from the
configuration's ``layer_types``. Dropless by construction: each expert in
turn multiplies ALL tokens, weighted by the token's gate for it, zero
where the token did not choose it (E/k = 16 times the program's work).
For memory, neither changing a result: a layer's dense weights and one
expert's at a time are cast to float32 (experts under ``lax.scan``), and
attention works in blocks of ``Q_BLOCK`` queries and the head in the
OLMoE reference's blocks of 512 positions (its ``_per_block``), so 8192
positions x 200,192 logits fit beside the engine.

``loss`` is the mean next-token cross entropy alone: ``load_balance_coeff``
drives ``expert_bias`` in training and is no term of the loss.

Tolerances, and why. float32: both sides in float32, differing in the
order of sums; at a tiny size on the CPU the programs' logits agree with
this reference within 4.1e-6 over 84 decode steps of four slots
(tests/bench_harness/test_benchmark_trinity.py); the limit is 1e-4, and
each of eight single departures (softmax for sigmoid, the bias in the
gate, no renormalisation, rotary on the full layer, no output gate, the
post-norms left out, QK-norm over the projection, a window off by one)
moves a logit by 1.1-4.3 there, ten thousand times the limit. bfloat16 ``LOSS_ATOL``:
the Mistral reference's, for its reason; no cell reads it.

bfloat16 ``LOGIT_MARGIN_TOL``, set as PR 28 set OLMoE's, from two
readings on the v5e at the published widths, L6, at the cell's lengths
(my chip runs, PR 38; PERF.md section 6). The system: over 14 runs on 14
seeds of ``serve-trinity-c16-long`` (four finished requests a run,
1,750-2,511 served tokens, contexts 1.4k-6.9k) a run's worst margin read
0.634-1.261, median 0.86, and 88-91% of served tokens are the
reference's argmax. This reference with every matmul input rounded to
float8_e4m3, the precision below bfloat16, put in the program's place on
two seeded sequences of 4,096 tokens a seed, two seeds: the token it puts
first trails the float32 reference's best by 3.04-3.54 at worst (p99
2.1-2.2, 19-20% argmax): not correct. With bfloat16 inputs, what the
engine may do: 0.95-1.15 (p99 0.30-0.33, 92-93% argmax), so the system's
readings are bfloat16's own. They are ten times OLMoE's (0.08 at worst)
because of the router, not of a fault (both window kernels agree with
their XLA paths on the chip to 0.002 and 0.016 on unit-variance inputs).
By my estimate, not a measurement: with seeded normal weights the router
logits of the 8th and 9th of 128 experts lie ~0.06 apart, bfloat16
arithmetic upstream moves a logit by ~0.01, and a token whose 8th expert
flips trades an eighth of its routed output at a gate of 2.826 / 8, where
OLMoE's softmax gate for its 8th expert is a few hundredths. The logits'
spread is ~1 by construction (a unit-RMS state against a head of
variance 1/M). The limit is 2.0: 1.59 times the
largest the system gave, and the control's smallest reading is 1.52
times the limit. No other departure was read on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .olmoe_reference import _per_block
from .reference import _HI, _f32, _rms_norm, _rotary

Q_BLOCK = 256
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
LOGIT_MARGIN_TOL = {"bfloat16": 2.0, "float32": 1e-4}

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _attention(q, k, v, window):
    """Causal softmax attention in blocks of queries, q [B, S, H, D], k
    and v [B, S, Hkv, D]; with ``window``, of the last ``window`` keys."""
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
        attends = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            attends &= key_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(attends, scores, -jnp.inf)
        return jnp.einsum("bkgqs,bskd->bqkgd",
                          jax.nn.softmax(scores, -1), v, precision=_HI)

    out = jax.lax.map(one_block, (jnp.moveaxis(q, 1, 0),
                                  jnp.arange(s // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _mlp(y, w_gate, w_up, w_down):
    a = jnp.einsum("bsm,mf->bsf", y, _f32(w_gate), precision=_HI)
    b = jnp.einsum("bsm,mf->bsf", y, _f32(w_up), precision=_HI)
    return jnp.einsum("bsf,fm->bsm", jax.nn.silu(a) * b, _f32(w_down),
                      precision=_HI)


def _experts(y, gate_of, w):
    """Sum over experts of gate * expert(y); y [B, S, M], gate_of
    [B, S, E], w one layer's expert weights [E, ..]."""
    def one(total, args):
        w_gate, w_up, w_down, gate = args  # gate [B, S]
        return total + _mlp(y, w_gate, w_up, w_down) * gate[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(gate_of, -1, 0)))
    return total


def _layer(x, w, kind, config):
    """One layer; ``w`` its own weights, ``kind`` its ``layer_types``."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    dense = {n: _f32(w[n]) for n in w if n not in _EXPERT_WEIGHTS}
    y = _rms_norm(x, dense["attn_norm"], eps)
    q = jnp.einsum("bsm,mhd->bshd", y, dense["wq"], precision=_HI)
    k = jnp.einsum("bsm,mhd->bshd", y, dense["wk"], precision=_HI)
    v = jnp.einsum("bsm,mhd->bshd", y, dense["wv"], precision=_HI)
    g = jnp.einsum("bsm,mhd->bshd", y, dense["wg"], precision=_HI)
    q = _rms_norm(q, dense["q_norm"], eps)
    k = _rms_norm(k, dense["k_norm"], eps)
    if kind == "sliding_attention":
        a = _attention(_rotary(q, theta), _rotary(k, theta), v,
                       config["sliding_window"])
    else:
        a = _attention(q, k, v, None)
    a = jnp.einsum("bshd,hdm->bsm", a * jax.nn.sigmoid(g), dense["wo"],
                   precision=_HI)
    x = x + _rms_norm(a, dense["post_attn_norm"], eps)
    y = _rms_norm(x, dense["mlp_norm"], eps)
    if "router" not in w:
        m = _mlp(y, w["w_gate"], w["w_up"], w["w_down"])
    else:
        scores = jax.nn.sigmoid(jnp.einsum(
            "bsm,me->bse", y, dense["router"], precision=_HI))
        _, chosen = jax.lax.top_k(scores + dense["expert_bias"],
                                  config["num_experts_per_tok"])
        gates = jnp.take_along_axis(scores, chosen, -1)
        if config["route_norm"]:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        gates = gates * config["route_scale"]
        picked = jax.nn.one_hot(chosen, config["num_experts"])
        gate_of = (picked * gates[..., None]).sum(-2)        # [B, S, E]
        m = _experts(y, gate_of, w) + _mlp(
            y, w["ws_gate"], w["ws_up"], w["ws_down"])
    return x + _rms_norm(m, dense["post_mlp_norm"], eps)


def hidden(params, tokens, config):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S a
    multiple of 512 (the head's block) or smaller than ``Q_BLOCK``."""
    x = _f32(params["embed"][tokens])
    if config["mup_enabled"]:
        x = x * config["hidden_size"] ** 0.5
    kinds = iter(config["layer_types"][:config["num_hidden_layers"]])
    for stack in params["layers"]:
        for i in range(stack["attn_norm"].shape[0]):
            x = _layer(x, jax.tree.map(lambda p: p[i], stack), next(kinds),
                       config)
    return _rms_norm(x, _f32(params["final_norm"]), config["rms_norm_eps"])


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
    return _per_block(x, tokens[:, 1:], _f32(params["lm_head"]), nll).mean()


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no cache."""
    def margin(logits, targets):
        chosen = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return logits.max(-1) - chosen

    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, tokens[:, 1:], _f32(params["lm_head"]), margin)
