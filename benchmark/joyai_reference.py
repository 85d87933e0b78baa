"""The plain reference for JoyAI-LLM-Flash (``model_type`` joyai_llm_flash,
every key DeepSeek-V3's): float32 ``jax.numpy``, every matmul at
``precision="highest"``, no kernel, no cache, no absorption, no sort, no
grouping, and no code of the program.

The published layer, from the keys of ``config.json``; x [S, hidden],
every norm an RMSNorm with ``rms_norm_eps`` and a learned weight, no
biases (``attention_bias`` false):

1. ``h = norm_in(x)``. ``c_q = norm_q(h W_qa)`` (``q_lora_rank``).
   ``q = c_q W_qb``: ``num_attention_heads`` heads of ``qk_head_dim`` =
   ``q_nope`` (``qk_nope_head_dim``) beside ``q_rope``
   (``qk_rope_head_dim``).
2. ``[c_kv, k_r] = h W_kva`` (``kv_lora_rank`` beside
   ``qk_rope_head_dim``). ``c = norm_kv(c_kv)``. ``k_rope = rotary(k_r)``,
   ONE a token, shared by all heads; ``q_rope = rotary(q_rope)``. Rotary
   over ``qk_rope_head_dim`` at ``rope_theta``, no scaling
   (``rope_scaling`` null), turning ADJACENT pairs (2i, 2i+1) by
   ``position * theta ** (-2i / qk_rope_head_dim)`` (``rope_interleave``
   true).
3. ``k_nope_h = c W_UK,h``, ``v_h = c W_UV,h`` (the two halves of the
   published ``kv_b_proj``, ``qk_nope_head_dim`` and ``v_head_dim`` a
   head). ``s_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) /
   sqrt(qk_head_dim)``, causal softmax, ``o_h = sum p v_h``, ``x = x +
   concat(o_h) W_o``.
4. ``h = norm_mlp(x)``. Layers before ``first_k_dense_replace``: a
   SiLU-gated MLP of ``intermediate_size``. The others: ``s = sigmoid(h
   W_r)`` in float32 over ``n_routed_experts``; the
   ``num_experts_per_tok`` experts of the largest ``s + b``
   (``e_score_correction_bias``; ``n_group`` 1 and ``topk_group`` 1: no
   group step); gates ``s`` of the chosen ones, divided by their sum +
   1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``; ``x = x +
   shared(h) + sum_k g_k expert_k(h)``, each a SiLU-gated MLP of
   ``moe_intermediate_size``.
5. After the last layer the final RMSNorm and the untied head.

What a serving program may do instead of 3 and still be this function:
cache ``(c, k_rope)`` alone and attend absorbed, ``q~_h = q_nope_h
W_UK,h^T``, ``s_h = (q~_h . c + q_rope_h . k_rope) / sqrt(qk_head_dim)``,
``o_h = (sum p c) W_UV,h``. This reference never does.

NOT computed, here or in the program: the multi-token-prediction module
(``num_nextn_predict_layers`` 1: layer index 40 of the release, an
``eh_proj``, two norms, one more block and the shared head). It adds
nothing to the model's own logits and the family's released inference
code drops it on load; the configuration file lists the key under
``not_served``.

Departures from the released code, each in the configuration file's
``assumed``: the two latent norms and the selection bias have no key in
``config.json`` and are the released implementation's; the router
multiplies in float32 (as for OLMoE and Trinity). Nothing else.

It takes the program's parameter tree: ``layers`` is a tuple of stacked
trees, consecutive layers that are alike stacked together; a stack with
a ``router`` holds expert layers; ``wk_b`` and ``wv_b`` are W_UK and W_UV
[kv_lora_rank, heads, width]. Dropless by construction: each expert in
turn multiplies ALL tokens, weighted by the token's gate for it (E/k =
32 times the program's work). For memory, neither changing a result: a
layer's dense weights and one expert's at a time are cast to float32
(experts under ``lax.scan``, each read where it lies in the stack: a
layer's 256 experts whole would be 4.8 GB of float32, and even their
bfloat16 slice 2.4 GB a layer, beside 11.1 GB of the program's),
attention works in blocks of ``Q_BLOCK`` queries and the head in blocks
of ``HEAD_BLOCK`` positions.

``inputs``, the control's handle: with a dtype, every matmul operand is
rounded to it first (and computed on in float32). ``control_margins``
puts such a reference in the program's place.

Tolerances, and why. float32: both sides in float32, differing in the
order of sums and in the absorption; at a tiny size on the CPU the
programs' logits agree with this reference within 5e-6
(tests/bench_harness/test_benchmark_joyai.py); the limit is 1e-4.
bfloat16 ``LOSS_ATOL``: the Mistral reference's; no cell reads it.

bfloat16 ``LOGIT_MARGIN_TOL``, set as PR 38 set Trinity's, from two
readings on the v5e at the published widths, L5, at the cell's lengths
(my chip runs, PR 42; PERF.md section 6). The system: over 31 runs
on as many seeds of ``serve-joyai-c16-4k`` (four finished requests a
run, 2,575-3,667 served tokens, contexts 2k-8.2k) a run's worst margin
read 2.25-3.23, median 2.69, and 75-78% of served tokens are the
reference's argmax. ``control_margins`` at float8_e4m3, the precision
below bfloat16, on two seeded sequences of 4,096 tokens a seed, two
seeds: the token it
puts first trails the float32 reference's best by 6.02-6.92 at worst
(p99 5.01-5.04, 2% argmax: a token drawn at random reads about as much):
not correct. With bfloat16 operands, what the engine may do: 2.44-2.61
(p99 1.21-1.32, 80-81% argmax), so the system's readings are bfloat16's
own, a little over the control's because the engine also keeps its
activations in bfloat16. Both kernels agree with their XLA paths on the
chip (the latent walk with the gather to 0.008 on rows of RMS 0.10, the
flash kernel at 192/128 with the einsum to 0.016 on 0.07). The readings
are 2.5 times Trinity's (0.63-1.26 against a bfloat16 control of
0.95-1.15) because of the model, not of the program: the control is this
file alone. By my estimate, not a measurement: twice the experts at the
same top-8 put the 8th and 9th router scores as close together as
Trinity's, a flipped expert trades an eighth of a routed output that is
scaled by 2.5 beside an unscaled shared one, and four such layers follow
a latent attention whose two bottleneck norms renormalise what rounding
moved. The logits' spread is ~1 by construction (a unit-RMS state against
a head of variance 1/M), the best of 129,280 lies near 4.5. The limit is
4.5: 1.39 times the largest the system gave, and the control's smallest
reading is 1.34 times the limit. No other departure was read on the
chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _HI, _f32, _rms_norm

Q_BLOCK = 256
HEAD_BLOCK = 512
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin (3.23) and the
# float8 control's smallest (6.02); the docstring has both readings.
LOGIT_MARGIN_TOL = {"bfloat16": 4.5, "float32": 1e-4}

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _mm(equation, a, b, inputs):
    """One matmul in float32; the operands first rounded to ``inputs``."""
    if inputs is not None:
        a, b = (_f32(x.astype(inputs)) for x in (a, b))
    return jnp.einsum(equation, a, b, precision=_HI)


def _rotary_pairs(x, theta):
    """x [B, S, H, D]: turn the adjacent pairs (2i, 2i+1) by position."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _attention(q_nope, q_rope, k_nope, k_rope, v, inputs):
    """Causal softmax attention in blocks of queries: q_nope, k_nope
    [B, S, H, Dn], q_rope [B, S, H, Dr], k_rope [B, S, Dr] (one for all
    heads), v [B, S, H, Dv]."""
    b, s, h, dn = q_nope.shape
    scale = (dn + q_rope.shape[-1]) ** -0.5
    block = min(Q_BLOCK, s)
    key_pos = jnp.arange(s)

    def blocks(x):
        return jnp.moveaxis(
            x.reshape((b, s // block, block) + x.shape[2:]), 1, 0)

    def one_block(args):
        qn, qr, start = args  # [B, block, H, D]
        scores = (_mm("bqhd,bshd->bhqs", qn, k_nope, inputs)
                  + _mm("bqhd,bsd->bhqs", qr, k_rope, inputs)) * scale
        q_pos = start + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        return _mm("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), v, inputs)

    out = jax.lax.map(one_block, (blocks(q_nope), blocks(q_rope),
                                  jnp.arange(s // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def _mlp(y, w_gate, w_up, w_down, inputs):
    a = _mm("bsm,mf->bsf", y, _f32(w_gate), inputs)
    b = _mm("bsm,mf->bsf", y, _f32(w_up), inputs)
    return _mm("bsf,fm->bsm", jax.nn.silu(a) * b, _f32(w_down), inputs)


def _experts(y, gate_of, stack, i, inputs):
    """Sum over experts of gate * expert(y); y [B, S, M], gate_of
    [B, S, E]; the experts' weights are read one expert at a time where
    they lie in ``stack`` [L, E, ..], at layer ``i`` (a layer's slice
    would be a copy of 2.4 GB, and XLA makes all four at once)."""
    def one(total, args):
        e, gate = args  # gate [B, S]
        w_gate, w_up, w_down = (stack[n][i, e] for n in _EXPERT_WEIGHTS)
        return total + _mlp(y, w_gate, w_up, w_down,
                            inputs) * gate[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        jnp.arange(gate_of.shape[-1]), jnp.moveaxis(gate_of, -1, 0)))
    return total


def _layer(x, stack, i, config, inputs):
    """Layer ``i`` of ``stack``, a run of alike layers' stacked weights."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    nope = config["qk_nope_head_dim"]
    rank = config["kv_lora_rank"]
    moe = "router" in stack
    w = {n: stack[n][i] for n in stack
         if not (moe and n in _EXPERT_WEIGHTS)}
    dense = {n: _f32(w[n]) for n in w if n not in _EXPERT_WEIGHTS}
    y = _rms_norm(x, dense["attn_norm"], eps)
    c_q = _rms_norm(_mm("bsm,mr->bsr", y, dense["wq_a"], inputs),
                    dense["q_a_norm"], eps)
    q = _mm("bsr,rhd->bshd", c_q, dense["wq_b"], inputs)
    q_nope, q_rope = q[..., :nope], _rotary_pairs(q[..., nope:], theta)
    kv = _mm("bsm,mr->bsr", y, dense["wkv_a"], inputs)
    c = _rms_norm(kv[..., :rank], dense["kv_a_norm"], eps)
    k_rope = _rotary_pairs(kv[..., None, rank:], theta)[..., 0, :]
    k_nope = _mm("bsr,rhd->bshd", c, dense["wk_b"], inputs)
    v = _mm("bsr,rhd->bshd", c, dense["wv_b"], inputs)
    a = _attention(q_nope, q_rope, k_nope, k_rope, v, inputs)
    x = x + _mm("bshd,hdm->bsm", a, dense["wo"], inputs)
    y = _rms_norm(x, dense["mlp_norm"], eps)
    if not moe:
        return x + _mlp(y, w["w_gate"], w["w_up"], w["w_down"], inputs)
    # The router in float32 whatever ``inputs``: it is float32 in the
    # program too (the configuration's ``assumed.router_dtype``).
    scores = jax.nn.sigmoid(jnp.einsum(
        "bsm,me->bse", y, dense["router"], precision=_HI))
    _, chosen = jax.lax.top_k(scores + dense["expert_bias"],
                              config["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, chosen, -1)
    if config["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * config["routed_scaling_factor"]
    picked = jax.nn.one_hot(chosen, config["n_routed_experts"])
    gate_of = (picked * gates[..., None]).sum(-2)            # [B, S, E]
    return (x + _experts(y, gate_of, stack, i, inputs)
            + _mlp(y, w["ws_gate"], w["ws_up"], w["ws_down"], inputs))


def hidden(params, tokens, config, inputs=None):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S a
    multiple of ``HEAD_BLOCK`` or smaller than ``Q_BLOCK``."""
    x = _f32(params["embed"][tokens])
    for stack in params["layers"]:
        for i in range(stack["attn_norm"].shape[0]):
            x = _layer(x, stack, i, config, inputs)
    return _rms_norm(x, _f32(params["final_norm"]), config["rms_norm_eps"])


def _per_block(x, head, reduce_logits, *others, inputs=None):
    """``reduce_logits(logits [B, block, V], *others' blocks)`` over
    blocks of positions, so [B, S, V] never exists at once."""
    b, s, m = x.shape
    block = min(HEAD_BLOCK, s)

    def blocks(a):
        return jnp.moveaxis(
            a.reshape((b, s // block, block) + a.shape[2:]), 1, 0)

    def one_block(args):
        xb, *rest = args
        return reduce_logits(_mm("bsm,mv->bsv", xb, head, inputs), *rest)

    out = jax.lax.map(one_block, tuple(map(blocks, (x,) + others)))
    return jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])


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
    argmax). Teacher-forced: one full forward, no cache."""
    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["lm_head"]), _margin, tokens[:, 1:])


def control_margins(params, tokens, config, inputs):
    """The control: this reference with every matmul operand rounded to
    ``inputs`` (``jnp.float8_e4m3fn``, the precision below bfloat16, or
    ``jnp.bfloat16``, what the engine may do), put in the program's
    place. For tokens [B, S]: at each position, how far the token such a
    model puts first trails the float32 reference's best logit, [B, S];
    what ``LOGIT_MARGIN_TOL`` has to refuse for float8 and pass for
    bfloat16."""
    head = _f32(params["lm_head"])
    first = _per_block(hidden(params, tokens, config, inputs), head,
                       lambda logits: logits.argmax(-1), inputs=inputs)
    return _per_block(hidden(params, tokens, config), head, _margin, first)
