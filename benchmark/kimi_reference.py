"""The plain reference for Kimi-Linear (``model_type`` kimi_linear) as ONE
CHIP'S SHARE of a layer holds it: float32 ``jax.numpy``, every matmul at
``precision="highest"``, the delta rule a token at a time, no chunk, no
kernel, no cache, no absorption, no sort, no grouping, and no code of the
program.

Pre-norm residual blocks, every norm an RMSNorm with ``rms_norm_eps`` and
a learned weight, no bias anywhere, no rotary anywhere (``mla_use_nope``),
untied head. ``h = norm_in(x)``; x [S, hidden].

A KDA layer (``linear_attn_config.kda_layers``, counted from 1; H =
``num_heads`` heads of d = ``head_dim``)::

    q~, k~, v~ = h Wq, h Wk, h Wv
    q^, k^, v^ = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
        conv: causal, a channel, ``short_conv_kernel_size`` taps, no
        bias: y_t = sum_i w_i x_{t - taps + 1 + i}, zeros before token 0
    q_t = q^_t / |q^_t| a head * d^-0.5;  k_t = k^_t / |k^_t|;  v_t
    log a_t = -exp(A_log[head]) softplus((h Wf_a) Wf_b + dt_bias)   [H, d]
    beta_t  = sigmoid(h Wb)                                         [H]
    S~  = diag(a_t) S_{t-1}                       S [d, d] a head
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t
    y_t = (norm_head(o_t; w_n) * sigmoid((h Wg_a) Wg_b)) Wo

An MLA layer (``full_attn_layers``): ``q = h Wq`` [heads, 192] (no
bottleneck: ``q_lora_rank`` null); ``[c_kv, k_r] = h Wkv_a`` (512 + 64);
``c = norm_kv(c_kv)``, ``k_r`` as it is; a head's ``k = [c W_UK (128),
k_r (64)]``, ``v = c W_UV (128)``; causal softmax of ``q . k / sqrt(192)``;
``Wo``.

The FFN: layer 1 a SiLU-gated MLP of ``intermediate_size``. The others:
``s = sigmoid(h W_r)`` in float32 over ALL the router's experts (its
published 256 columns); the ``num_experts_per_token`` of the largest
``s + b`` (``use_grouped_topk`` with one group of one: plain); gates ``s``
of the chosen, over their sum + 1e-20 (``moe_renormalize``), times
``routed_scaling_factor``. **This chip holds experts ``0 .. num_experts -
1``** (the file's ``num_experts`` is the held 16): ``x = x + shared(h) +
sum over the chosen experts THAT ARE HELD of g_k expert_k(h)``; a gate
whose expert lies on another chip multiplies nothing here and is NOT
renormalised away: the sixteen chips' routed parts add up to the uncut
layer's. After the last layer the final RMSNorm and the head's held
columns.

Departures from the published description, each in the configuration
file's ``assumed``: everything about a KDA layer beyond its head count,
width and kernel size (``config.json`` has no other key of it: the sizes
and the gates are the family's released layer's), the latent norm, the
selection bias, the router in float32.

It takes the program's parameter tree: ``layers`` a tuple of stacked
trees, consecutive alike layers together; a stack with ``conv_w`` holds
KDA layers, one with ``router`` expert layers. It WALKS THE TREE A LAYER
AT A TIME: one layer's dense weights and one expert's at a time are cast
to float32 (4.3 B parameters in float32 do not fit beside the engine).
For memory, none changing a result: the MLA attention works a group of
``HEAD_GROUP`` heads and a block of ``Q_BLOCK`` queries at a time, the
head in blocks of ``HEAD_BLOCK`` positions.

``control_margins`` is the controls' handle: this reference with the
delta state kept in a given dtype between tokens and every matmul operand
outside the recurrence rounded to a given dtype first (``inputs``, as
JoyAI's and GLM-5.2's references have it). With neither it is the
reference again.

**What ``correct`` holds the system to here, and what it does NOT.** It
refuses operands below bfloat16 (the float8 controls, whatever the
state's dtype). **It does NOT refuse a delta state kept in bfloat16**:
the file's ``assumed.kda_state`` (float32, the released layer's) is held
by construction (the pool's dtype, pinned by
``tests/bench_harness/test_benchmark_kimi.py`` and shown a run by
``delta_slot_bytes.chat``) and is NOT verified by the comparison.
ISSUE 62 asked that the bfloat16-state control fail the limit; the
readings below say why no limit on what the harness hands over can make
it, and PERF.md section 7 what a ``benchmark`` PR would have to compare
instead.

Tolerances, and why. float32: both sides in float32, differing in the
form (a chunked solve against a token loop, absorbed against rebuilt
attention) and the order of sums; at a tiny size on the CPU the programs'
logits agree with this reference within 2e-5
(tests/bench_harness/test_benchmark_kimi.py) and a bfloat16 state trails
by over ten times the limit; the limit is 1e-4. bfloat16 ``LOSS_ATOL``:
the Mistral reference's; no cell reads it.

bfloat16 ``LOGIT_MARGIN_TOL``, from readings on the v5e at the published
widths, all 27 layers (my chip runs, PR 62; PERF.md section 6). The
system: over its runs on as many seeds of ``serve-kimilinear-c16-8k``
(four finished requests a run, ~3,500 served tokens at contexts 4.6k-16k)
a run's worst margin read 0.64-1.31 over fifteen runs and 72-79% of
served tokens are the reference's argmax (PERF.md has every run).
``control_margins`` on one seeded sequence of 8,192 tokens a seed, two
seeds, the token it puts first against the float32 reference's best:

  float8_e4m3 operands, bfloat16 state (the precision below the
  configuration's in both respects): 5.05-5.13 at worst (p99 3.6-3.7,
  6% argmax): not correct;
  float8_e4m3 operands, float32 state: 4.90-4.98: not correct;
  bfloat16 operands, float32 state (what the engine may do): 0.96-1.07
  (p99 0.31, mean 0.021, 80-81% argmax);
  bfloat16 operands, bfloat16 state: 1.01-1.09 (p99 0.42, mean 0.035,
  75% argmax): PASSES;
  float32 operands, bfloat16 state: 0.83-1.21 (p99 0.39-0.41, mean
  0.029-0.031, 76-77% argmax): PASSES.

The limit is 3.0: 2.3 times the largest the system gave, and the
smallest reading of the control in the precision below (5.05) is 1.7
times the limit.

Why a bfloat16 state cannot be refused on a served token's logits, by
the worst margin or by any other statistic of them. (1) What it costs
(the mean margin 1.4-1.7 times, the argmax share 81% -> 75%, p99 0.31 ->
0.42, the worst case not at all) lies inside what bfloat16 activations
and a flipped expert already give, and INSIDE THE SYSTEM'S OWN SPREAD
FROM SEED TO SEED: its argmax share over fourteen seeds, 72-79%, straddles
the bfloat16-state control's 75%, so a fixed limit on the mean margin or
on the argmax share (what REVIEW of PR 62 proposed for ``judge``) would
refuse sound runs or pass this one. (2) The delta rule corrects itself
(my reckoning, from the recurrence and ``assumed``'s decays, not a
reading): the term ``v - S^T k`` feeds a rounding of S back into the
next update along k; where a channel remembers a thousand tokens an
increment is still ~3% of a state's entry, 16 times bfloat16's step, so
none is swallowed, and the roundings of a thousand tokens add up to ~3%
of the state, the size of what 27 layers of bfloat16 activations add. Brumby's
state has no such term, and its dense layers leave the system's own
worst case small (0.095 against the control's 0.76); 26 layers of top-8
routing over 256 experts do not. (3) Only a comparison on ONE seed tells
the two apart: the parent's and the change's ``check`` numbers (worst
margin, tokens, argmax) are equal to the last digit where the programs
are the same (every pair of PR 62's), and with its pool rounded to
bfloat16 the system read, on one seed and the same 2,767 tokens, a worst
margin of 1.133 for 0.636 and 1,982 argmax tokens for 2,124, ``correct``
true (my chip run, PR 62; PERF.md section 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import _HI, _f32, _rms_norm

Q_BLOCK = 256
HEAD_BLOCK = 512
HEAD_GROUP = 8
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin and the smallest
# of the control with float8 operands and a bfloat16 state (5.05); the
# docstring has every reading.
LOGIT_MARGIN_TOL = {"bfloat16": 3.0, "float32": 1e-4}

_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _mm(equation, a, b, inputs=None):
    """One matmul in float32; the operands first rounded to ``inputs``."""
    if inputs is not None:
        a, b = (_f32(x.astype(inputs)) for x in (a, b))
    return jnp.einsum(equation, a, b, precision=_HI)


def _blocks(x, block):
    """[B, S, ..] -> [S / block, B, block, ..]."""
    b, s = x.shape[:2]
    return jnp.moveaxis(
        x.reshape((b, s // block, block) + x.shape[2:]), 1, 0)


def _unblocks(x):
    """[n, B, block, ..] -> [B, n * block, ..]."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


# ---- a KDA layer ----------------------------------------------------------

def _conv(x, taps):
    """x [B, S, C], taps [K, C]: y_t = sum_i taps[i] x[t - K + 1 + i]."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * taps[i] for i in range(k))


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_rule(q, k, v, log_a, beta, state_dtype):
    """Token by token. q, k, v, log_a [B, S, H, d]; beta [B, S, H]. The
    state [B, H, d, d] is kept in ``state_dtype`` between tokens."""
    b, _, h, d = q.shape

    def step(state, xs):
        qt, kt, vt, la, bt = xs
        decayed = jnp.exp(la)[..., None] * _f32(state)
        seen = _mm("bhk,bhkv->bhv", kt, decayed)
        state = decayed + kt[..., None] * (
            bt[..., None] * (vt - seen))[..., None, :]
        state = state.astype(state_dtype)
        return state, _mm("bhk,bhkv->bhv", qt, _f32(state))

    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, d, d), state_dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_a, beta)))
    return jnp.moveaxis(out, 0, 1)


def _kda(y, w, eps, state_dtype, inputs):
    """The attention half of a KDA layer on the normed input y
    [B, S, M]; returns what is added to the residual. ``inputs`` rounds
    the projections' operands; the recurrence itself is float32 but for
    the state's own dtype."""
    b, s, _ = y.shape
    heads, d = w["wq"].shape[1:]

    mm = functools.partial(_mm, inputs=inputs)
    pre = jnp.concatenate(
        [mm("bsm,mhd->bshd", y, w[n]).reshape(b, s, heads * d)
         for n in ("wq", "wk", "wv")], -1)
    mixed = jax.nn.silu(_conv(pre, w["conv_w"])).reshape(b, s, 3, heads, d)
    q = _unit(mixed[:, :, 0]) * d ** -0.5
    k, v = _unit(mixed[:, :, 1]), mixed[:, :, 2]
    f = mm("bsr,rhd->bshd", mm("bsm,mr->bsr", y, w["wf_a"]), w["wf_b"])
    log_a = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(f + w["dt_bias"])
    beta = jax.nn.sigmoid(mm("bsm,mh->bsh", y, w["wb"]))
    o = _delta_rule(q, k, v, log_a, beta, state_dtype)
    gate = jax.nn.sigmoid(mm(
        "bsr,rhd->bshd", mm("bsm,mr->bsr", y, w["wg_a"]), w["wg_b"]))
    return mm("bshd,hdm->bsm", _rms_norm(o, w["o_norm"], eps) * gate,
               w["wo"])


# ---- an MLA layer ---------------------------------------------------------

def _mla(y, w, config, inputs):
    """The attention half of an MLA layer on the normed input y; a group
    of heads and a block of queries at a time."""
    mm = functools.partial(_mm, inputs=inputs)
    nope, rank = config["qk_nope_head_dim"], config["kv_lora_rank"]
    heads = config["num_attention_heads"]
    b, s, _ = y.shape
    scale = (nope + config["qk_rope_head_dim"]) ** -0.5
    block, group = min(Q_BLOCK, s), min(HEAD_GROUP, heads)
    kv = mm("bsm,mr->bsr", y, w["wkv_a"])
    c = _rms_norm(kv[..., :rank], w["kv_a_norm"], config["rms_norm_eps"])
    k_shared = kv[..., rank:]                              # [B, S, 64]
    key_pos = jnp.arange(s)

    def heads_of(name):
        x = w[name]
        axis = 0 if name == "wo" else 1
        return jnp.moveaxis(x.reshape(
            x.shape[:axis] + (heads // group, group) + x.shape[axis + 1:]),
            axis, 0)

    def one_group(total, args):
        wq, wk_b, wv_b, wo = args
        q = mm("bsm,mhd->bshd", y, wq)
        k_nope = mm("bsr,rhd->bshd", c, wk_b)
        v = mm("bsr,rhd->bshd", c, wv_b)

        def one_block(args):
            qb, start = args
            scores = (mm("bqhd,bshd->bhqs", qb[..., :nope], k_nope)
                      + mm("bqhd,bsd->bhqs", qb[..., nope:], k_shared)
                      ) * scale
            q_pos = start + jnp.arange(block)
            scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                               -jnp.inf)
            return mm("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), v)

        a = _unblocks(jax.lax.map(one_block, (
            _blocks(q, block), jnp.arange(s // block) * block)))
        return total + mm("bshd,hdm->bsm", a, wo), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros(y.shape[:2] + (w["wo"].shape[-1],)),
        tuple(heads_of(n) for n in ("wq", "wk_b", "wv_b", "wo")))
    return total


# ---- the FFN --------------------------------------------------------------

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


def routed_gates(y, w, config):
    """[B, S, R]: each token's gate for every expert the ROUTER has, 0
    for those it did not choose."""
    scores = jax.nn.sigmoid(_mm("bsm,me->bse", y, w["router"]))
    _, chosen = jax.lax.top_k(scores + w["expert_bias"],
                              config["num_experts_per_token"])
    gates = jnp.take_along_axis(scores, chosen, -1)
    if config["moe_renormalize"]:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * config["routed_scaling_factor"]
    picked = jax.nn.one_hot(chosen, scores.shape[-1])
    return (picked * gates[..., None]).sum(-2)


def _layer(x, stack, i, config, state_dtype, inputs):
    """Layer ``i`` of ``stack``, a run of alike layers' stacked weights."""
    eps = config["rms_norm_eps"]
    moe = "router" in stack
    w = {n: stack[n][i] for n in stack
         if not (moe and n in _EXPERT_WEIGHTS)}
    dense = {n: _f32(w[n]) for n in w if n not in _EXPERT_WEIGHTS}
    y = _rms_norm(x, dense["attn_norm"], eps)
    if "conv_w" in dense:
        x = x + _kda(y, dense, eps, state_dtype, inputs)
    else:
        x = x + _mla(y, dense, config, inputs)
    y = _rms_norm(x, dense["mlp_norm"], eps)
    if not moe:
        return x + _mlp(y, w["w_gate"], w["w_up"], w["w_down"], inputs)
    # The router in float32 whatever ``inputs`` (it is float32 in the
    # program too), over all it scores; the held experts are the first
    # ``num_experts`` of them.
    gate_of = routed_gates(y, dense, config)[..., :config["num_experts"]]
    return (x + _experts(y, gate_of, stack, i, inputs)
            + _mlp(y, w["ws_gate"], w["ws_up"], w["ws_down"], inputs))


def hidden(params, tokens, config, state_dtype=jnp.float32, inputs=None):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S a
    multiple of ``HEAD_BLOCK`` or smaller than ``Q_BLOCK``."""
    x = _f32(params["embed"][tokens])
    for stack in params["layers"]:
        for i in range(stack["attn_norm"].shape[0]):
            x = _layer(x, stack, i, config, state_dtype, inputs)
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
    return _mm("bsm,mv->bsv", hidden(params, tokens, config),
               _f32(params["lm_head"]))


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
    argmax). Teacher-forced: one full forward, no state kept."""
    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["lm_head"]), _margin, tokens[:, 1:])


def control_margins(params, tokens, config, state_dtype=jnp.float32,
                    inputs=None):
    """The control: this reference with the delta state kept in
    ``state_dtype`` between tokens and every matmul operand outside the
    recurrence rounded to ``inputs`` first, put in the program's place.
    For tokens [B, S]: at each position, how far the token such a model
    puts first trails the float32 reference's best logit, [B, S]. With
    ``jnp.float8_e4m3fn`` operands it is what ``LOGIT_MARGIN_TOL`` has
    to refuse; with a float32 state and bfloat16 operands, what the
    engine may do, it has to pass; with a bfloat16 state alone it passes
    too at the published widths (the module's docstring says why);
    with neither it trails by nothing."""
    head = _f32(params["lm_head"])
    first = _per_block(hidden(params, tokens, config, state_dtype, inputs),
                       head, lambda logits: logits.argmax(-1), inputs=inputs)
    return _per_block(hidden(params, tokens, config), head, _margin, first)
