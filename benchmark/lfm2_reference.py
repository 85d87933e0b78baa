"""The plain reference for LFM2 (``model_type`` lfm2_moe): float32
``jax.numpy``, every matmul at ``precision="highest"``, no kernel, no
cache, no page, no convolution history, no sort, no grouped matmul, and
no code of the program.

The published stack, from the keys of ``config.json`` (the release: HF
``transformers`` ``models/lfm2_moe``); every norm an RMSNorm with
``norm_eps`` and a learned weight, no bias anywhere (``conv_bias``
false):

``x0 = E[token]``, no scale. Layer ``l`` of kind ``layer_types[l]``:
``x <- x + Op_l(operator_norm_l(x))``, then ``x <- x + FFN_l(
ffn_norm_l(x))``. After the last layer one RMSNorm (the release's
``embedding_norm``) and ``logits = h E^T``: the head is the embedding.

1. A ``conv`` layer's operator (the release's ``Lfm2ShortConv``):
   ``u = h W_in`` [S, 3 M]; ``(B, C, X)`` = its three M-wide thirds, in
   this order; ``z_t = B_t * X_t``; ``y_t = w_0 z_{t-2} + w_1 z_{t-1} +
   w_2 z_t`` with one weight ``w`` [``conv_L_cache`` = 3, M] a layer, a
   depthwise causal convolution, ``z`` before the first token 0, no
   activation; ``out = (C_t * y_t) W_out``. Computed here as three
   shifted copies of ``z`` over the whole sequence: there is no history
   to keep.
2. A ``full_attention`` layer's operator: ``q = h W_q`` (H heads of D =
   ``hidden_size / num_attention_heads`` = 64), ``k = h W_k``, ``v = h
   W_v`` (Hkv heads of D); an RMSNorm over each head's D on q and on k
   (``q_layernorm``, ``k_layernorm``, one weight [D] each); rotary on
   the two halves of D at ``rope_parameters.rope_theta``; causal softmax
   attention at ``D ** -0.5``, query head h reads KV head ``h // (H /
   Hkv)``; ``W_o``.
3. The FFN. Layers ``l < num_dense_layers``: ``W_2 (silu(W_1 h) * W_3
   h)`` of ``intermediate_size``. The others: ``s = sigmoid(h W_r)``
   over the ``num_experts``, the product in float32; the
   ``num_experts_per_tok`` experts of largest ``s + b``
   (``use_expert_bias``: ``b`` selects and is not in the gate); ``g = s``
   at those, divided by their sum + 1e-6 (``norm_topk_prob``), times
   ``routed_scaling_factor``; ``out = sum_e g_e W_2e (silu(W_1e h) *
   W_3e h)`` of ``moe_intermediate_size``; no shared expert.

Assumptions, each in the configuration file's ``assumed`` with its
reason: the head's width 64, the tied head, the order (B, C, X), no
activation in the operator, the per-head norms, ``intermediate_size`` as
it stands, the router in float32, ``expert_bias`` from the seed.
Departure of the PROGRAM from this file, one: its route adds 1e-20 to the
chosen scores' sum where the release, and this file, add 1e-6
(``assumed.norm_topk_epsilon``); four sigmoid scores sum to about 2, so
a gate differs by a factor of 1 + 5e-7, under float32's own rounding of
a logit of size 1.

It takes the program's parameter tree: ``layers`` is a tuple of stacked
trees, consecutive layers that are alike stacked together, in layer
order; a stack with ``w_in`` holds conv layers, one with a ``router``
expert layers; ``embed`` [V, M] is the head too (the tree has no
``lm_head``). Dropless by construction: each expert in turn multiplies
ALL tokens, weighted by the token's gate for it, zero where
the token did not choose it (E/k = 16 times the program's work). For
memory, none changing a result: a layer's dense weights and one expert's
at a time are cast to float32 (an expert read where it lies in its
run's stack, under ``lax.scan``), the dense FFNs work in blocks of
``TOKEN_BLOCK`` tokens (two [16384, 11776] float32 intermediates would
be 1.5 GB), attention in blocks of ``Q_BLOCK`` queries and the head in
blocks of ``HEAD_BLOCK`` positions, so that 16,384 positions x 65,536
logits fit beside the engine (1.8 GiB of temporaries compiled for a
v5e).

``inputs`` and ``departure``, the controls' handles. With ``inputs`` a
dtype, every matmul operand is rounded to it first (and computed on in
float32); the router stays in float32. ``departure`` names ONE wrong
model, each of which must fail the comparison (``DEPARTURES``):
``no_history`` (the taps see zeros for ``z_{t-1}`` and ``z_{t-2}``: what
a decode step reads from histories that were zeroed), ``swap_bc`` (B and
C exchanged), ``two_taps`` (``w_0 z_{t-2}`` left out), ``bias_in_gate``
(``g = s + b`` at the chosen), ``no_renorm`` (the chosen scores not
divided by their sum), ``rope_on_conv`` (z rotated as heads of D before
the taps), ``scale_128`` (the attention's scores at ``128 ** -0.5``, a
128-wide head's), ``kv_float8`` (k and v rounded to float8_e4m3 before
the attention: a k/v pool a precision lower), ``kv_pair_swapped`` (each
query head reads KV head ``kv ^ 1``, the other half of the 128-wide row
that a pool of heads of 64 lays its pair in: a wrong half in the walk).

Tolerances, and why. float32: both sides in float32, differing in the
order of sums and in the 5e-7 above; at a tiny size on the CPU the
programs' logits agree with this reference within 1e-5 over a prefill
and decode steps through both pools (tests/bench_harness/
test_benchmark_lfm2.py); the limit is 1e-4, and each of the six
departures of that test moves a logit by far more. Where two experts'
``s + b`` lie within the arithmetic's rounding of the fourth largest,
one side takes the other expert: in float32 that did not occur at the
tiny size; in bfloat16 it is the bulk of the margin (below). bfloat16
``LOSS_ATOL``: the Mistral reference's, for its reason; no cell reads
it.

bfloat16 ``LOGIT_MARGIN_TOL``, from two readings on the v5e at the
published widths, L10, at the cell's lengths (my chip runs, PR 73;
PERF.md section 6), on the weights the file states (``weights``: the
per-head q/k norm weights drawn about 2, ``assumed.qk_norm_init``; on
ones the seeded softmax was almost even over thousands of keys, the
attention layers' output a fiftieth of a conv layer's, and nothing
below could see them). The system: over 12 runs on 12 seeds of
``serve-lfm2-c16-8k`` (four finished requests a run, 2,714-3,911 served
tokens, contexts 4.6k-16k) a run's worst margin read 1.176-1.415, median
1.33, and 64-67% of served tokens are the reference's argmax (on the
first weights 14 runs read 1.06-1.75, thirteen under 1.43: a run's worst
margin is the largest of ~3,300 tokens' and has a long tail).
``control_margins`` at float8_e4m3, the precision below bfloat16, on one
seeded sequence of 8,192 tokens a seed, two seeds: the token it puts
first trails the float32 reference's best by 4.09 and 4.15 at worst
(mean 0.99 and 0.98, 9% argmax): not correct. With bfloat16 operands,
what the engine may do: 1.49 and 1.17 (mean 0.054 and 0.051, 70%
argmax), so the system's readings are bfloat16's own. They are Trinity's
size and for its reason, the router: where the fourth and fifth of 64
experts' ``s + b`` lie within bfloat16's rounding of the residual one
expert of four differs, a quarter of the routed output, in each of eight
expert layers; the logits' spread is ~1 by construction (a unit-RMS
state against a tied head of variance 1/M). The limit is 2.5: 1.77 times
the largest the system gave on these weights (1.43 times the largest on
the first), and the float8 control's smallest reading is 1.64 times the
limit.

The departures on the chip, same sequence (seed 3100730201), worst
margin (mean): ``no_history`` 7.90 (3.97), ``swap_bc`` 7.85 (4.12),
``no_renorm`` 6.56 (2.28), ``kv_pair_swapped`` 6.02 (1.66): not correct,
each by over twice the limit. ``scale_128`` 2.43 (0.49, 23% argmax),
``kv_float8`` 1.58 (0.10, 58%) and ``bias_in_gate`` 1.08 (0.029, 77%)
are under it. A worst margin is bounded by the logits' spread and
bfloat16's flipped experts already reach 1.2-1.5 of it, so it parts a
wrong model from a sound one only where most tokens' logits move by
their own size; the MEAN margin parts the first two (9 and 2 times
bfloat16's 0.054), and the harness's ``judge`` holds the worst alone
(PERF.md section 7: what would need which edit). By a simulation of
8,192 random keys the 128-wide scale moves the attention's output most
at these norm weights (0.22 of a value row's size), and float8 k and v
move it by 0.04 at these and by 0.09 at scores four times as wide: no
draw makes a k/v pool a precision lower move most tokens' logits.

**In float32 every one of them is refused on the chip at the published
widths.** The same file with ``dtype`` float32 on layers 0-2 (conv,
conv, attention; dense, dense, experts; 6.4 GB) through the cell's own
harness, ``jax_default_matmul_precision`` highest: 3,022 served tokens,
every one the reference's argmax, worst margin 0.0 of the limit 1e-4
(walk, flash forward, grouped matmul and taps compiled for float32).
Against that limit, on 8,192 seeded tokens of the same model:
``bias_in_gate`` 0.034, ``kv_float8`` 0.59, ``scale_128`` 0.78,
bfloat16 operands 0.52. The float32 comparison at the tiny size holds
the same in tier-1 (``bias_in_gate`` moves a logit by 1.6, the others by
over 100 times 1e-4: tests/bench_harness/test_benchmark_lfm2.py), beside
the walk against the gather on the chip and interpreted
(tests/test_paged_attention_heads64.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .glm52_reference import _blocks, _margin, _mlp, _mm, _unblocks
from .reference import _HI, _f32, _rms_norm, _rotary

Q_BLOCK = 256
HEAD_BLOCK = 512
TOKEN_BLOCK = 2048
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin and the float8
# control's smallest; the docstring has both readings.
LOGIT_MARGIN_TOL = {"bfloat16": 2.5, "float32": 1e-4}

DEPARTURES = ("no_history", "swap_bc", "two_taps", "bias_in_gate",
              "no_renorm", "rope_on_conv", "scale_128", "kv_float8",
              "kv_pair_swapped")
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _attention(q, k, v, scale, inputs):
    """Causal softmax attention in blocks of queries, q [B, S, H, D], k
    and v [B, S, Hkv, D]."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block = min(Q_BLOCK, s)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, start = args  # [B, block, Hkv, G, D]
        scores = _mm("bqkgd,bskd->bkgqs", qb, k, inputs) * scale
        q_pos = start + jnp.arange(block)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        return _mm("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v,
                   inputs)

    out = jax.lax.map(one_block, (
        _blocks(q.reshape(b, s, hkv, h // hkv, d), block),
        jnp.arange(s // block) * block))
    return _unblocks(out).reshape(b, s, h, d)


def _shifted(z, by):
    """z [B, S, M] delayed by ``by`` tokens, zeros in front."""
    return jnp.pad(z, ((0, 0), (by, 0), (0, 0)))[:, :z.shape[1]]


def _conv_operator(y, dense, config, inputs, departure):
    m = y.shape[-1]
    u = _mm("bsm,mn->bsn", y, dense["w_in"], inputs)
    gate_b, gate_c, x = u[..., :m], u[..., m:2 * m], u[..., 2 * m:]
    if departure == "swap_bc":
        gate_b, gate_c = gate_c, gate_b
    z = gate_b * x
    if departure == "rope_on_conv":
        d = m // config["num_attention_heads"]
        z = _rotary(z.reshape(*z.shape[:2], -1, d),
                    float(config["rope_parameters"]["rope_theta"])
                    ).reshape(z.shape)
    w = dense["conv_w"]                        # [taps, M]: w_0 the oldest
    taps = w.shape[0]
    past = range(taps - 1)
    if departure == "no_history":
        past = ()
    elif departure == "two_taps":
        past = range(1, taps - 1)
    mixed = w[taps - 1] * z + sum(
        w[i] * _shifted(z, taps - 1 - i) for i in past)
    return _mm("bsm,mn->bsn", gate_c * mixed, dense["w_out"], inputs)


def _attention_operator(y, dense, config, inputs, departure):
    eps = config["norm_eps"]
    theta = float(config["rope_parameters"]["rope_theta"])
    q = _mm("bsm,mhd->bshd", y, dense["wq"], inputs)
    k = _mm("bsm,mhd->bshd", y, dense["wk"], inputs)
    v = _mm("bsm,mhd->bshd", y, dense["wv"], inputs)
    q = _rotary(_rms_norm(q, dense["q_norm"], eps), theta)
    k = _rotary(_rms_norm(k, dense["k_norm"], eps), theta)
    if departure == "kv_float8":
        k, v = (_f32(x.astype(jnp.float8_e4m3fn)) for x in (k, v))
    elif departure == "kv_pair_swapped":
        other = jnp.arange(k.shape[2]) ^ 1
        k, v = k[:, :, other], v[:, :, other]
    scale = (128 if departure == "scale_128" else q.shape[-1]) ** -0.5
    a = _attention(q, k, v, scale, inputs)
    return _mm("bshd,hdm->bsm", a, dense["wo"], inputs)


def routed_gates(y, dense, config, departure=None):
    """[B, S, E]: each token's gate for each expert, zero for the experts
    it did not choose. In float32 whatever the controls round."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "bsm,me->bse", y, dense["router"], precision=_HI))
    biased = scores + dense["expert_bias"]
    _, chosen = jax.lax.top_k(biased, config["num_experts_per_tok"])
    gates = jnp.take_along_axis(
        biased if departure == "bias_in_gate" else scores, chosen, -1)
    if config["norm_topk_prob"] and departure != "no_renorm":
        gates = gates / (gates.sum(-1, keepdims=True)
                         + config["assumed"]["norm_topk_epsilon"])
    gates = gates * config["routed_scaling_factor"]
    picked = jax.nn.one_hot(chosen, config["num_experts"])
    return (picked * gates[..., None]).sum(-2)


def _experts(y, gate_of, stack, i, inputs):
    """Sum over experts of gate * expert(y); y [B, S, M], gate_of
    [B, S, E]: each expert of layer ``i`` a dense product under its
    gates, one at a time, read where it lies in ``stack``."""
    def one(total, args):
        e, gate = args                                   # gate [B, S]
        w_gate, w_up, w_down = (stack[n][i, e] for n in _EXPERT_WEIGHTS)
        return total + _mlp(y, w_gate, w_up, w_down,
                            inputs) * gate[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        jnp.arange(gate_of.shape[-1]), jnp.moveaxis(gate_of, -1, 0)))
    return total


def _dense_ffn(y, w, inputs):
    block = min(TOKEN_BLOCK, y.shape[1])
    return _unblocks(jax.lax.map(
        lambda yb: _mlp(yb, w["w_gate"], w["w_up"], w["w_down"], inputs),
        _blocks(y, block)))


def _layer(x, stack, i, config, inputs, departure):
    """Layer ``i`` of the run ``stack``. A run with ``w_in`` holds conv
    layers, one with a ``router`` expert layers."""
    eps = config["norm_eps"]
    moe = "router" in stack
    w = {n: stack[n][i] for n in stack
         if not (moe and n in _EXPERT_WEIGHTS)}
    dense = {n: _f32(w[n]) for n in w if n not in _EXPERT_WEIGHTS}
    y = _rms_norm(x, dense["attn_norm"], eps)
    operator = _conv_operator if "w_in" in w else _attention_operator
    x = x + operator(y, dense, config, inputs, departure)
    y = _rms_norm(x, dense["mlp_norm"], eps)
    if not moe:
        return x + _dense_ffn(y, w, inputs)
    return x + _experts(y, routed_gates(y, dense, config, departure), stack,
                        i, inputs)


def hidden(params, tokens, config, inputs=None, departure=None):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S a
    multiple of ``TOKEN_BLOCK`` or smaller than ``Q_BLOCK``."""
    if departure is not None and departure not in DEPARTURES:
        raise ValueError(f"departure {departure!r} is none of {DEPARTURES}")
    x = _f32(params["embed"][tokens])
    for stack in params["layers"]:
        for i in range(stack["attn_norm"].shape[0]):
            x = _layer(x, stack, i, config, inputs, departure)
    return _rms_norm(x, _f32(params["final_norm"]), config["norm_eps"])


def _per_block(x, embed, reduce_logits, *others, inputs=None):
    """``reduce_logits(logits [B, block, V], *others' blocks)`` over
    blocks of positions, so [B, S, V] never exists at once. The head is
    the embedding: ``logits = h E^T``."""
    block = min(HEAD_BLOCK, x.shape[1])

    def one_block(args):
        xb, *rest = args
        return reduce_logits(_mm("bsm,vm->bsv", xb, embed, inputs), *rest)

    return _unblocks(jax.lax.map(one_block, tuple(
        _blocks(a, block) for a in (x,) + others)))


def logits(params, tokens, config, departure=None):
    """[B, S, V] logits whole: for a test at a tiny size."""
    return jnp.einsum(
        "bsm,vm->bsv", hidden(params, tokens, config, departure=departure),
        _f32(params["embed"]), precision=_HI)


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1]."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["embed"]), nll, tokens[:, 1:]).mean()


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no cache."""
    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["embed"]), _margin, tokens[:, 1:])


def control_margins(params, tokens, config, inputs=None, departure=None):
    """The controls: this reference with every matmul operand rounded to
    ``inputs`` (``jnp.float8_e4m3fn``, the precision below bfloat16, or
    ``jnp.bfloat16``, what the engine may do) or with ONE ``departure``,
    put in the program's place. For tokens [B, S]: at each position, how
    far the token such a model puts first trails the float32 reference's
    best logit, [B, S]; all zeros with neither."""
    embed = _f32(params["embed"])
    first = _per_block(hidden(params, tokens, config, inputs, departure),
                       embed, lambda logits: logits.argmax(-1), inputs=inputs)
    return _per_block(hidden(params, tokens, config), embed, _margin, first)
