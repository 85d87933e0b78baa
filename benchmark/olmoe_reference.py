"""The plain reference for OLMoE: float32 ``jax.numpy``, every matmul at
``precision="highest"``, no kernel, no cache, no batching, and no code of
the program's dispatch.

The published block (HF ``modeling_olmoe.py``), pre-norm: RMSNorm; q, k,
v projections without bias; ``q_norm`` over the whole 2048-wide q
projection and ``k_norm`` over the whole k projection, before the split
into heads and before rotary; rotary on halves; causal softmax attention
(the Mistral reference's, imported: 16 heads on 16 KV heads is its group
of one); output projection. Then RMSNorm; router logits; softmax over
ALL experts; the top ``num_experts_per_tok`` gates, NOT renormalised
(``norm_topk_prob`` false); every expert ``down(silu(gate(x)) * up(x))``;
no shared expert. It takes the program's parameter tree.

Dropless by construction, and nothing is sorted or grouped: each expert
in turn multiplies ALL tokens, and its result is weighted by the token's
gate for it, zero where the token did not choose it. That is E/k = 8
times the program's work, which a check of a few sequences can pay. Two
departures for memory, neither changing a result: layers and experts run
under ``lax.scan`` with the cast to float32 inside the body (one
expert's float32 weights at a time, so a 2049-token sequence at the
published widths fits beside the engine), and attention and the head
work in blocks of ``Q_BLOCK`` positions as in ``benchmark/reference.py``.

``loss`` holds the Switch load-balancing term of every layer, E * sum_e
(share of assignments to e) * (mean router probability of e), summed
over layers, at the configuration's ``router_aux_loss_coef`` (under
``assumed``), as the program's training loss does.

Tolerances, and why. float32: both sides compute in float32 and differ
in the order of sums alone; at a tiny size on the CPU the loss agrees
within 5e-7 and the margins within 4e-6 over four seeds
(tests/bench_harness/test_benchmark_olmoe.py); the limit is 1e-4, which
renormalised gates or a norm left out exceed a hundred times over.
bfloat16 ``LOSS_ATOL``: the Mistral reference's, for its reason (a mean
over thousands of tokens averages the rounding away); no cell reads it
yet. bfloat16 ``LOGIT_MARGIN_TOL``: the engine picks from bfloat16
logits after 8 layers of bfloat16 arithmetic, and where two gates nearly
tie it routes a token to another 8th expert than the float32 router
does, so the margins have a longer tail than a dense model's. Two
readings on the v5e at the published widths, L8 (my chip runs, PR 28;
PERF.md section 6). The system: the worst margin of a run was
0.0027-0.0736 over ten seeds (median 0.019), 1-5% of tokens not the
reference's argmax. This reference with every matmul input rounded to
float8_e4m3, the precision below bfloat16: 0.355-0.428 over five seeds,
not correct; with bfloat16 inputs, what the engine may do: 0.013-0.042.
The limit is 0.2: 2.7 times the largest the system gave (the Mistral
limit's 0.08 would refuse one run in twenty-five on these readings),
about half the smallest float8 reading, and a fiftieth of the logits'
range here (9.0-10.2; Mistral's 0.08 is a fiftieth of its ~4). What
else it refuses: the parent's capacity rule (1.25 k T / E, tokens over
it dropped) 0.221-0.572; gates renormalised 0.78-1.01; one token in
eight losing its experts 0.162-0.397, so on two seeds of three. What no
limit can refuse: every token's 8th expert dropped reads 0.047-0.117,
inside bfloat16's own noise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _HI, Q_BLOCK, _attention, _f32, _rms_norm, _rotary

LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
LOGIT_MARGIN_TOL = {"bfloat16": 0.2, "float32": 1e-4}


def _experts(y, gate_of, w):
    """Sum over experts of gate * down(silu(gate_proj(y)) * up(y)); y
    [B, S, M], gate_of [B, S, E], w the layer's expert weights [E, ..]."""
    def one(total, args):
        w_gate, w_up, w_down, gate = args  # gate [B, S]
        a = jnp.einsum("bsm,mf->bsf", y, _f32(w_gate), precision=_HI)
        b = jnp.einsum("bsm,mf->bsf", y, _f32(w_up), precision=_HI)
        out = jnp.einsum("bsf,fm->bsm", jax.nn.silu(a) * b, _f32(w_down),
                         precision=_HI)
        return total + out * gate[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        w["w_gate"], w["w_up"], w["w_down"], jnp.moveaxis(gate_of, -1, 0)))
    return total


def hidden(params, tokens, config):
    """(final-norm hidden states [B, S, M], load-balancing term) for
    tokens [B, S]; S a multiple of ``Q_BLOCK`` or smaller than it."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    experts, k = config["num_experts"], config["num_experts_per_tok"]
    x = _f32(params["embed"][tokens])
    b, s, _ = x.shape

    def layer(x, w):
        dense = {n: _f32(w[n]) for n in w
                 if n not in ("w_gate", "w_up", "w_down")}
        y = _rms_norm(x, dense["attn_norm"], eps)
        q = jnp.einsum("bsm,mhd->bshd", y, dense["wq"], precision=_HI)
        key = jnp.einsum("bsm,mhd->bshd", y, dense["wk"], precision=_HI)
        v = jnp.einsum("bsm,mhd->bshd", y, dense["wv"], precision=_HI)
        q = _rms_norm(q.reshape(b, s, -1), dense["q_norm"],
                      eps).reshape(q.shape)
        key = _rms_norm(key.reshape(b, s, -1), dense["k_norm"],
                        eps).reshape(key.shape)
        a = _attention(_rotary(q, theta), _rotary(key, theta), v)
        x = x + jnp.einsum("bshd,hdm->bsm", a, dense["wo"], precision=_HI)
        y = _rms_norm(x, dense["mlp_norm"], eps)
        probs = jax.nn.softmax(jnp.einsum(
            "bsm,me->bse", y, dense["router"], precision=_HI), -1)
        gates, chosen = jax.lax.top_k(probs, k)
        picked = jax.nn.one_hot(chosen, experts)             # [B, S, k, E]
        gate_of = (picked * gates[..., None]).sum(-2)        # [B, S, E]
        x = x + _experts(y, gate_of, w)
        share = picked.sum(-2).mean((0, 1)) / k              # per expert
        return x, experts * (share * probs.mean((0, 1))).sum()

    x, balance = jax.lax.scan(layer, x, params["layers"])
    return _rms_norm(x, _f32(params["final_norm"]), eps), balance.sum()


def _per_block(x, targets, head, reduce_logits):
    """``reduce_logits(logits [B, block, V], targets [B, block])`` over
    blocks of positions, so [B, S, V] never exists at once."""
    b, s, m = x.shape
    block = min(Q_BLOCK, s)

    def one_block(args):
        xb, tb = args
        logits = jnp.einsum("bsm,mv->bsv", xb, head, precision=_HI)
        return reduce_logits(logits, tb)

    out = jax.lax.map(one_block, (
        jnp.moveaxis(x.reshape(b, s // block, block, m), 1, 0),
        jnp.moveaxis(targets.reshape(b, s // block, block), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s)


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1] plus the
    load-balancing term at ``router_aux_loss_coef``."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    x, balance = hidden(params, tokens[:, :-1], config)
    coef = config["assumed"]["router_aux_loss_coef"]
    return _per_block(x, tokens[:, 1:], _f32(params["lm_head"]),
                      nll).mean() + coef * balance


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no cache."""
    def margin(logits, targets):
        chosen = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return logits.max(-1) - chosen

    x, _ = hidden(params, tokens[:, :-1], config)
    return _per_block(x, tokens[:, 1:], _f32(params["lm_head"]), margin)
