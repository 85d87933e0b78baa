"""The plain reference for GLM-5.2 (``model_type`` glm_moe_dsa) as ONE CHIP'S
SHARE of a layer holds it: float32 ``jax.numpy``, every matmul at
``precision="highest"``, no kernel, no cache, no absorption, no sort of
assignments, no grouping, and no code of the program.

The published layer ``l``, from the keys of ``config.json``; x [S,
hidden], every norm an RMSNorm with ``rms_norm_eps`` and a learned weight
unless said otherwise, no biases (``attention_bias`` false):

1. ``h = norm_in(x)``. ``c_q = norm_q(h W_qa)`` (``q_lora_rank``).
   ``q = c_q W_qb``: ``num_attention_heads`` heads of ``qk_head_dim`` =
   ``q_nope`` (``qk_nope_head_dim``) beside ``q_rope``
   (``qk_rope_head_dim``). ``[c_kv, k_r] = h W_kva``; ``c =
   norm_kv(c_kv)``; ``k_rope = rotary(k_r)``, ONE a token; ``q_rope =
   rotary(q_rope)``; rotary over ADJACENT pairs (``rope_interleave``) at
   ``rope_parameters.rope_theta``. ``k_nope_h = c W_UK,h``, ``v_h = c
   W_UV,h``; ``s_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) /
   sqrt(qk_head_dim)``.
2. THE SELECTION. Where ``indexer_types[l]`` is "full": ``q_j = (c_q
   W_iq)[j]`` for ``index_n_heads`` heads of ``index_head_dim``; ``k =
   LayerNorm(h W_ik)`` (weight and bias), one a token; rotary on the first
   ``qk_rope_head_dim`` of both (adjacent pairs where
   ``indexer_rope_interleave``); ``w = h W_iw * index_n_heads ** -0.5 *
   index_head_dim ** -0.5``; ``I[t, s] = sum_j w[t, j] relu(q[t, j] .
   k[s])`` for ``s <= t``; ``S_t`` = the ``min(index_topk, t + 1)``
   positions of the largest ``I[t, s]``, ties towards the lower position
   (``jax.lax.top_k``, which is stable). Where it is "shared": the
   ``S_t`` of the nearest "full" layer before it. The softmax of 1 is over
   ``s in S_t`` only; ``o_h = sum p v_h``, ``x = x + concat(o_h) W_o``.
3. ``h = norm_mlp(x)``. ``mlp_layer_types[l]`` "dense": a SiLU-gated MLP
   of ``intermediate_size``. "sparse": ``s = sigmoid(h W_r)`` in float32
   over ALL ``R`` experts the router has (its published 256 columns); the
   ``num_experts_per_tok`` of the largest ``s + b``; gates ``s`` of the
   chosen, divided by their sum + 1e-20 (``norm_topk_prob``), times
   ``routed_scaling_factor``. **This chip holds experts ``0 ..
   n_routed_experts - 1`` of them** (the file's ``n_routed_experts`` is
   the held 16): ``x = x + shared(h) + sum over the chosen experts THAT
   ARE HELD of g_k expert_k(h)``. A gate whose expert lies on another
   chip multiplies nothing here and is NOT renormalised away: the sum of
   the sixteen chips' routed parts is the uncut layer's.
4. After the last layer the final RMSNorm and the head's held columns
   (``vocab_size`` rows of the published 154,880: logits, best and margin
   are over the slice this chip holds).

The file's five layers are published layers 2-6; the stacks of the
program's tree come in that order, and a stack with ``wi_q`` is an
indexing ("full") one.

NOT computed, here or in the program: the multi-token-prediction module
(``num_nextn_predict_layers`` 1), under ``not_served`` in the file.

Departures from the released code, each in the file's ``assumed``: the two
latent norms, the selection bias, the router in float32 (as JoyAI's); the
indexer's q and k as the model's dtype has them and their dot products
summed in float32 (the release rounds both to float8 after a Hadamard
rotation: the rotation is orthogonal and leaves q . k as it is, float8 is
a precision the configuration's ``dtype`` does not state); the LayerNorm
with bias on the indexer's key; the FIRST ``qk_rope_head_dim`` of an
indexer head rotated.

It takes the program's parameter tree. For memory, none changing a
result: a layer's dense weights and one expert's at a time are cast to
float32, the selection is made a block of ``Q_BLOCK`` queries at a time
and kept as a boolean [S, S] (268 MB at 16,384 tokens), attention works a
group of ``HEAD_GROUP`` heads at a time (their q, k and v are made and
dropped in turn: all 64 at 16,384 tokens would be 3.2 GB of float32) in
blocks of ``Q_BLOCK`` queries, and the head in blocks of ``HEAD_BLOCK``
positions.

``inputs``, the control's handle: with a dtype, every matmul operand is
rounded to it first (and computed on in float32), the indexer's too.

Tolerances, and why. float32: both sides in float32, differing in the
order of sums, in the absorption and, where two index scores lie within
float32 rounding of the ``index_topk``-th, in one selected position; at a
tiny size on the CPU the programs' logits agree with this reference
within 6e-6 with contexts on both sides of ``index_topk``
(tests/bench_harness/test_benchmark_glm52.py); the limit is 1e-4.
bfloat16 ``LOSS_ATOL``: the Mistral reference's; no cell reads it.

bfloat16 ``LOGIT_MARGIN_TOL``, from two readings on the v5e at the
published widths, this chip's share, at the cell's lengths (my chip
runs, PR 54; PERF.md section 6). The system: over 10 runs on as many
seeds of ``serve-glm52-c8-16k`` (four finished requests a run,
3,387-5,208 served tokens, contexts 6k-16k, every one over
``index_topk``) a run's worst margin read 1.14-1.74, median 1.26, and
69-73% of served tokens are the reference's argmax. ``control_margins``
at float8_e4m3, the precision below bfloat16, on one seeded sequence of
16,384 tokens a seed, two seeds: the token it puts first trails the
float32 reference's best by 6.05-6.11 at worst (p99 4.09-4.11, 3.5-3.9%
argmax): not correct. With bfloat16 operands, what the engine may do:
1.24-1.25 at worst (p99 0.49-0.51, 78-79% argmax), so the system's
readings are bfloat16's own. **The two selections**
(``selection_agreement``, the first layer's, same two sequences): a
selection made from bfloat16 operands keeps 99.77% of the float32
selection's (token, position) pairs, one from float8 operands 94.57%;
the 0.23% that differ are positions whose scores lie within bfloat16's
rounding of the 2,048th, and what they cost is inside the bfloat16
control's 1.24-1.25, which selects with rounded operands too and reads
no higher than the system. Neither side drops its selection. The limit
is 3.5: twice the largest the system gave, and the control's smallest
reading is 1.73 times the limit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _HI, _f32, _rms_norm

Q_BLOCK = 256
HEAD_BLOCK = 512
HEAD_GROUP = 16
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin (1.74) and the
# float8 control's smallest (6.05); the docstring has both readings.
LOGIT_MARGIN_TOL = {"bfloat16": 3.5, "float32": 1e-4}

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _mm(equation, a, b, inputs):
    """One matmul in float32; the operands first rounded to ``inputs``."""
    if inputs is not None:
        a, b = (_f32(x.astype(inputs)) for x in (a, b))
    return jnp.einsum(equation, a, b, precision=_HI)


def _rotary_pairs(x, theta, interleave=True):
    """x [B, S, H, D]: turn pair i of D/2 by ``position * theta ** (-i /
    (D/2))``; the pair is (2i, 2i+1), or without ``interleave`` (i, i +
    D/2)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks(x, block):
    """[B, S, ..] -> [S / block, B, block, ..]."""
    b, s = x.shape[:2]
    return jnp.moveaxis(
        x.reshape((b, s // block, block) + x.shape[2:]), 1, 0)


def _unblocks(x):
    """[n, B, block, ..] -> [B, n * block, ..]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def selection(y, c_q, dense, config, inputs=None):
    """[B, S, S] bool: S_t as a row of booleans, from the layer's normed
    input y and normed q bottleneck c_q."""
    theta = float(config["rope_parameters"]["rope_theta"])
    rot, topk = config["qk_rope_head_dim"], config["index_topk"]
    pairs = config["indexer_rope_interleave"]
    heads, width = config["index_n_heads"], config["index_head_dim"]
    b, s, _ = y.shape

    def rotate(x):
        return jnp.concatenate(
            [_rotary_pairs(x[..., :rot], theta, pairs), x[..., rot:]], -1)

    q = rotate(_mm("bsr,rhd->bshd", c_q, dense["wi_q"], inputs))
    k = _mm("bsm,md->bsd", y, dense["wi_k"], inputs)
    k = k - k.mean(-1, keepdims=True)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True)
                          + config["rms_norm_eps"])
    k = rotate((k * dense["i_k_norm"] + dense["i_k_bias"])[:, :, None])[
        :, :, 0]
    w = _mm("bsm,mh->bsh", y, dense["wi_w"], inputs) * (
        heads ** -0.5 * width ** -0.5)
    block = min(Q_BLOCK, s)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, wb, start = args
        dots = jax.nn.relu(_mm("bqhd,bsd->bqhs", qb, k, inputs))
        score = jnp.einsum("bqhs,bqh->bqs", dots, wb, precision=_HI)
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        _, chosen = jax.lax.top_k(score, min(topk, s))
        picked = jax.vmap(jax.vmap(lambda row, at: row.at[at].set(True)))(
            jnp.zeros(score.shape, bool), chosen)
        return picked & seen

    return _unblocks(jax.lax.map(one_block, (
        _blocks(q, block), _blocks(w, block),
        jnp.arange(s // block) * block)))


def _attention(c_q, c, k_rope, dense, config, selected, inputs):
    """The layer's attention output through W_o, [B, S, hidden]: softmax
    over the ``selected`` [B, S, S] positions, a group of heads and a
    block of queries at a time."""
    theta = float(config["rope_parameters"]["rope_theta"])
    nope, heads = config["qk_nope_head_dim"], config["num_attention_heads"]
    b, s, _ = c_q.shape
    scale = (nope + config["qk_rope_head_dim"]) ** -0.5
    block, group = min(Q_BLOCK, s), min(HEAD_GROUP, heads)

    def heads_of(name):
        w = dense[name]
        axis = 0 if name == "wo" else 1
        return jnp.moveaxis(w.reshape(
            w.shape[:axis] + (heads // group, group) + w.shape[axis + 1:]),
            axis, 0)

    def one_group(total, args):
        wq_b, wk_b, wv_b, wo = args
        q = _mm("bsr,rhd->bshd", c_q, wq_b, inputs)
        q_nope = q[..., :nope]
        q_rope = _rotary_pairs(q[..., nope:], theta,
                               config["rope_interleave"])
        k_nope = _mm("bsr,rhd->bshd", c, wk_b, inputs)
        v = _mm("bsr,rhd->bshd", c, wv_b, inputs)

        def one_block(args):
            qn, qr, sel = args
            scores = (_mm("bqhd,bshd->bhqs", qn, k_nope, inputs)
                      + _mm("bqhd,bsd->bhqs", qr, k_rope, inputs)) * scale
            scores = jnp.where(sel[:, None], scores, -jnp.inf)
            return _mm("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), v,
                       inputs)

        a = _unblocks(jax.lax.map(one_block, (
            _blocks(q_nope, block), _blocks(q_rope, block),
            _blocks(selected, block))))
        return total + _mm("bshd,hdm->bsm", a, wo, inputs), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros(c_q.shape[:2] + (dense["wo"].shape[-1],)),
        tuple(heads_of(n) for n in ("wq_b", "wk_b", "wv_b", "wo")))
    return total


def _mlp(y, w_gate, w_up, w_down, inputs):
    a = _mm("bsm,mf->bsf", y, _f32(w_gate), inputs)
    b = _mm("bsm,mf->bsf", y, _f32(w_up), inputs)
    return _mm("bsf,fm->bsm", jax.nn.silu(a) * b, _f32(w_down), inputs)


def _experts(y, gate_of, stack, i, inputs):
    """Sum over the HELD experts of gate * expert(y); gate_of [B, S,
    held]; one expert at a time, read where it lies in ``stack``."""
    def one(total, args):
        e, gate = args
        w_gate, w_up, w_down = (stack[n][i, e] for n in _EXPERT_WEIGHTS)
        return total + _mlp(y, w_gate, w_up, w_down,
                            inputs) * gate[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        jnp.arange(gate_of.shape[-1]), jnp.moveaxis(gate_of, -1, 0)))
    return total


def routed_gates(y, dense, config):
    """[B, S, R]: each token's gate for every expert the ROUTER has, 0
    for those it did not choose."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "bsm,me->bse", y, dense["router"], precision=_HI))
    _, chosen = jax.lax.top_k(scores + dense["expert_bias"],
                              config["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, chosen, -1)
    if config["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * config["routed_scaling_factor"]
    picked = jax.nn.one_hot(chosen, scores.shape[-1])
    return (picked * gates[..., None]).sum(-2)


def _layer(x, stack, i, config, selected, inputs):
    """Layer ``i`` of ``stack``; returns (x, the selection it used)."""
    theta, eps = (float(config["rope_parameters"]["rope_theta"]),
                  config["rms_norm_eps"])
    rank = config["kv_lora_rank"]
    moe = "router" in stack
    w = {n: stack[n][i] for n in stack
         if not (moe and n in _EXPERT_WEIGHTS)}
    dense = {n: _f32(w[n]) for n in w if n not in _EXPERT_WEIGHTS}
    y = _rms_norm(x, dense["attn_norm"], eps)
    c_q = _rms_norm(_mm("bsm,mr->bsr", y, dense["wq_a"], inputs),
                    dense["q_a_norm"], eps)
    kv = _mm("bsm,mr->bsr", y, dense["wkv_a"], inputs)
    c = _rms_norm(kv[..., :rank], dense["kv_a_norm"], eps)
    k_rope = _rotary_pairs(kv[..., None, rank:], theta,
                           config["rope_interleave"])[..., 0, :]
    if "wi_q" in dense:
        selected = selection(y, c_q, dense, config, inputs)
    x = x + _attention(c_q, c, k_rope, dense, config, selected, inputs)
    y = _rms_norm(x, dense["mlp_norm"], eps)
    if not moe:
        return x + _mlp(y, w["w_gate"], w["w_up"], w["w_down"],
                        inputs), selected
    # The router in float32 whatever ``inputs``, over all it scores; the
    # held experts are the first ``n_routed_experts`` of them.
    gate_of = routed_gates(y, dense, config)[..., :config["n_routed_experts"]]
    return (x + _experts(y, gate_of, stack, i, inputs)
            + _mlp(y, w["ws_gate"], w["ws_up"], w["ws_down"], inputs),
            selected)


def hidden(params, tokens, config, inputs=None):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S a
    multiple of ``HEAD_BLOCK`` or smaller than ``Q_BLOCK``."""
    x = _f32(params["embed"][tokens])
    selected = None
    for stack in params["layers"]:
        for i in range(stack["attn_norm"].shape[0]):
            x, selected = _layer(x, stack, i, config, selected, inputs)
    return _rms_norm(x, _f32(params["final_norm"]), config["rms_norm_eps"])


def _per_block(x, head, reduce_logits, *others, inputs=None):
    """``reduce_logits(logits [B, block, V], *others' blocks)`` over
    blocks of positions, so [B, S, V] never exists at once."""
    block = min(HEAD_BLOCK, x.shape[1])

    def one_block(args):
        xb, *rest = args
        return reduce_logits(_mm("bsm,mv->bsv", xb, head, inputs), *rest)

    return _unblocks(jax.lax.map(one_block, tuple(
        _blocks(a, block) for a in (x,) + others)))


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
    model puts first trails the float32 reference's best logit, [B, S]."""
    head = _f32(params["lm_head"])
    first = _per_block(hidden(params, tokens, config, inputs), head,
                       lambda logits: logits.argmax(-1), inputs=inputs)
    return _per_block(hidden(params, tokens, config), head, _margin, first)


def selection_agreement(params, tokens, config, inputs):
    """How far a selection made from operands rounded to ``inputs``
    (``jnp.bfloat16``: what the program's indexer has) is this file's
    float32 one, on the FIRST layer's input, where both see the same
    residual: the share of (token, selected position) pairs of the
    float32 selection that the rounded one selects too."""
    stack = params["layers"][0]
    dense = {n: _f32(stack[n][0]) for n in stack
             if n not in _EXPERT_WEIGHTS}
    x = _f32(params["embed"][tokens])
    eps = config["rms_norm_eps"]
    y = _rms_norm(x, dense["attn_norm"], eps)

    def made(inputs):
        c_q = _rms_norm(_mm("bsm,mr->bsr", y, dense["wq_a"], inputs),
                        dense["q_a_norm"], eps)
        return selection(y, c_q, dense, config, inputs)

    exact, rounded = made(None), made(inputs)
    return (exact & rounded).sum() / exact.sum()
