"""The fixture's plain reference: the Mistral reference's attention with
a dropless mixture of experts in the MLP's place, float32. Every token
is multiplied by every expert and the result weighted by its top-k
gates (exact and affordable at the fixture's size only): softmax over
the experts, top-k, gates not renormalised, SiLU-gated experts. The
loss holds the Switch load-balancing term, summed over layers, at the
configuration's ``router_aux_loss_coef``, as the train step's does."""

import jax
import jax.numpy as jnp

from benchmark.reference import (  # noqa: F401  (the tolerances are the interface's)
    _HI, LOGIT_MARGIN_TOL, LOSS_ATOL, _attention, _f32, _rms_norm, _rotary,
)


def _logits(params, tokens, config):
    """(logits [B, S, V], load-balancing term) for tokens [B, S]."""
    theta, eps = config["rope_theta"], config["rms_norm_eps"]
    experts, k = config["num_experts"], config["num_experts_per_tok"]
    x, balance = _f32(params["embed"][tokens]), 0.0
    for i in range(config["num_hidden_layers"]):
        w = jax.tree.map(lambda leaf: _f32(leaf[i]), params["layers"])
        y = _rms_norm(x, w["attn_norm"], eps)
        q, key, v = (jnp.einsum("bsm,mhd->bshd", y, w[n], precision=_HI)
                     for n in ("wq", "wk", "wv"))
        a = _attention(_rotary(q, theta), _rotary(key, theta), v)
        x = x + jnp.einsum("bshd,hdm->bsm", a, w["wo"], precision=_HI)
        y = _rms_norm(x, w["mlp_norm"], eps)
        probs = jax.nn.softmax(
            jnp.einsum("bsm,me->bse", y, w["router"], precision=_HI), -1)
        gates, chosen = jax.lax.top_k(probs, k)
        picked = jax.nn.one_hot(chosen, experts)            # [B, S, k, E]
        weight = (picked * gates[..., None]).sum(-2)        # [B, S, E]
        gate = jnp.einsum("bsm,emf->bsef", y, w["w_gate"], precision=_HI)
        up = jnp.einsum("bsm,emf->bsef", y, w["w_up"], precision=_HI)
        out = jnp.einsum("bsef,efm->bsem", jax.nn.silu(gate) * up,
                         w["w_down"], precision=_HI)
        x = x + (out * weight[..., None]).sum(-2)
        routed = picked.sum(-2).mean((0, 1)) / k            # share per expert
        balance += experts * (routed * probs.mean((0, 1))).sum()
    x = _rms_norm(x, _f32(params["final_norm"]), eps)
    return jnp.einsum("bsm,mv->bsv", x, _f32(params["lm_head"]),
                      precision=_HI), balance


def loss(params, tokens, config):
    logits, balance = _logits(params, tokens[:, :-1], config)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               tokens[:, 1:, None], -1)[..., 0]
    return nll.mean() + config["router_aux_loss_coef"] * balance


def logit_margins(params, tokens, config):
    logits, _ = _logits(params, tokens[:, :-1], config)
    chosen = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return logits.max(-1) - chosen
