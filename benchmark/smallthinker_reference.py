"""The plain reference for SmallThinker (PowerInfer, 21B-A3B): float32
``jax.numpy``, every matmul at ``precision="highest"`` (what
``jax.default_matmul_precision("highest")`` sets, spelled at each one), no
kernel, no cache, no sort, no grouping, and no code of the program or of
another reference.

The published layer, from the catalog row's ``config`` and its
``described_as`` ("SWA(4096); NoPE global"; "64 experts, top-6, 0 shared;
sparse ReGLU; router placed before attention"), for layer ``l``; x is
[S, hidden_size], every norm an RMSNorm with ``rms_norm_eps`` and a
learned weight, no bias anywhere, embedding and head untied:

1. ``h = norm_in(x)``: the attention's input, and the ROUTER's.
2. ``r = h W_r`` [S, moe_num_primary_experts], float32. ``E`` = the
   ``moe_num_active_primary_experts`` largest of r; ``g = softmax(r[E])``
   over the chosen alone (``moe_primary_router_apply_softmax`` with
   ``norm_topk_prob``: a softmax over all, the chosen renormalised, is
   the same numbers, and the program computes it that way:
   tests/bench_harness/test_benchmark_smallthinker.py holds the two
   equal).
3. ``q = h Wq`` [S, H, D], ``k = h Wk``, ``v = h Wv`` [S, Hkv, D].
   ``rope_layout[l] == 1``: rotary over the two halves of D
   (``rope_theta``) on q and k. ``sliding_window_layout[l] == 1``: token
   t attends to j with ``t - sliding_window_size < j <= t``; else to
   every j <= t. (In the published lists the two are the same list:
   layers 0, 4, 8, .. attend to everything and are not rotated.) Scale
   ``D ** -0.5``; query head h reads KV head ``h // (H / Hkv)``, groups
   of 7.
4. ``x = x + attention(q, k, v) Wo``.
5. ``u = norm_mlp(x)``; ``y = sum_{e in E} g_e Wdown_e(relu(Wgate_e u) *
   Wup_e u)``: ReGLU experts of ``moe_ffn_hidden_size``, routed by step
   2's choice, made from the attention's input, and multiplying the
   FFN's. ``x = x + y``. After the last layer the final RMSNorm and
   ``lm_head``.

Not built, on either side: the "secondary experts" ``described_as``
names have no key in the config (the file's ``not_served``).
Departures from the release, each in the file's ``assumed``: the router
multiplies in float32; rotary over halves.

It takes the program's parameter tree: ``layers`` is a tuple of stacked
trees, consecutive alike layers stacked together, in layer order (here F,
S S S, F, S S S). Dropless by construction: each expert in turn
multiplies ALL tokens, weighted by the token's gate for it, zero where
the token did not choose it (64/6 = 10.7 times the program's work). For
memory, neither changing a result: a layer's weights outside the experts
and one expert's at a time are cast to float32 (an expert is read where
it lies in the stack: a layer's slice would be a copy of 755 MB),
attention works in blocks of ``Q_BLOCK`` queries against all keys (28 x
128 x 16,384 scores, 235 MB) and the head in blocks of ``Q_BLOCK``
positions, so 16,384 positions x 151,936 logits fit beside the engine.

``inputs``, the control's handle: with a dtype, every matmul operand is
rounded to it first (and computed on in float32), the router's too.

``loss`` is the mean next-token cross entropy alone.

Tolerances, and why. float32: both sides in float32, differing in the
order of sums; at a tiny size on the CPU the programs' logits agree with
this reference within 6.4e-6 over 84 decode steps of four slots with
contexts on both sides of the window; the limit is 1e-4, and each of five
single departures (the router reading the FFN's input, SiLU for ReLU, no
renormalisation, rotary on the full layers, a window off by one) moves a
logit by 0.6-3.3 there, six thousand times the limit or more
(tests/bench_harness/test_benchmark_smallthinker.py). bfloat16
``LOSS_ATOL``: the Mistral reference's, for its reason; no cell reads it.

bfloat16 ``LOGIT_MARGIN_TOL``, from two readings on the v5e at the
published widths, L8, at the cell's lengths (my chip runs, PR 57;
PERF.md section 6). The system: over 7 runs on 7 seeds of
``serve-smallthinker-c16-8k`` (four finished requests a run, 2,840-3,496
served tokens, contexts 4.6k-16k) a run's worst margin read 0.977-1.591,
median 1.33, and 82-87% of served tokens are the reference's argmax.
``control_margins`` at float8_e4m3, the precision below bfloat16, on one
seeded sequence of 16,384 tokens a seed, two seeds: the token it puts
first trails this reference's best by 8.07-8.15 at worst (p99 6.4-6.6,
3.1% argmax): not correct. With bfloat16 operands, what the engine may
do: 1.90-2.05 at worst over all 16,384 positions (p99 0.50-0.53, 86-87%
argmax), five times as many tokens as a run's check reads and so a
larger extreme of the same distribution: the system's readings are
bfloat16's own, as Trinity's and GLM-5.2's are, for Trinity's reason (a
top-k router downstream of bfloat16 arithmetic: where the 6th and 7th
logits lie within its rounding a token trades an expert). The limit is
3.5: 2.2 times the largest the system gave, 1.7 times the bfloat16
control's largest, and the float8 control's smallest reading is 2.3
times the limit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 128
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin (1.59) and the
# float8 control's smallest (8.07); the docstring has both readings.
LOGIT_MARGIN_TOL = {"bfloat16": 3.5, "float32": 1e-4}

_HI = jax.lax.Precision.HIGHEST
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _f32(x):
    return x.astype(jnp.float32)


def _mm(equation, a, b, inputs=None):
    """One matmul in float32; the operands first rounded to ``inputs``."""
    if inputs is not None:
        a, b = (_f32(x.astype(inputs)) for x in (a, b))
    return jnp.einsum(equation, a, b, precision=_HI)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rotary(x, theta):
    """x [B, S, H, D]: pair i of D/2 is dimensions (i, i + D/2), turned
    by ``position * theta ** (-i / (D/2))``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks(s):
    """Positions a block: all of a short sequence, else ``Q_BLOCK``."""
    if s > Q_BLOCK and s % Q_BLOCK:
        raise ValueError(
            f"{s} positions: a sequence over {Q_BLOCK} is whole blocks")
    return min(Q_BLOCK, s)


def _attention(q, k, v, window, inputs):
    """Causal softmax attention a block of queries at a time, q [B, S, H,
    D], k and v [B, S, Hkv, D]; with ``window``, over the last ``window``
    keys, the query's own among them."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block = _blocks(s)
    q = q.reshape(b, s // block, block, hkv, h // hkv, d)
    key_pos = jnp.arange(s)

    def one_block(args):
        qb, start = args  # [B, block, Hkv, G, D]
        scores = _mm("bqkgd,bskd->bkgqs", qb, k, inputs) * d ** -0.5
        q_pos = start + jnp.arange(block)
        attends = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            attends &= key_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(attends, scores, -jnp.inf)
        return _mm("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v,
                   inputs)

    out = jax.lax.map(one_block, (jnp.moveaxis(q, 1, 0),
                                  jnp.arange(s // block) * block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def route(logits, config):
    """The published router: the top ``moe_num_active_primary_experts``
    of the logits [.., E], then a softmax over those alone. Returns each
    token's gate for every expert [.., E], zero where it did not choose
    it."""
    top, chosen = jax.lax.top_k(
        logits, config["moe_num_active_primary_experts"])
    gates = jax.nn.softmax(top, -1)
    picked = jax.nn.one_hot(chosen, config["moe_num_primary_experts"])
    return (picked * gates[..., None]).sum(-2)


def _experts(u, gate_of, stack, i, inputs):
    """sum_e gate_e * Wdown_e(relu(Wgate_e u) * Wup_e u) over ALL the
    experts of layer ``i`` of ``stack``; u [B, S, M], gate_of [B, S, E]."""
    def one(total, e):
        w_gate, w_up, w_down = (stack[n][i, e] for n in _EXPERT_WEIGHTS)
        a = _mm("bsm,mf->bsf", u, w_gate, inputs)
        b = _mm("bsm,mf->bsf", u, w_up, inputs)
        y = _mm("bsf,fm->bsm", jax.nn.relu(a) * b, w_down, inputs)
        gate = jax.lax.dynamic_index_in_dim(gate_of, e, -1, keepdims=True)
        return total + y * gate, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            jnp.arange(stack["w_up"].shape[1]))
    return total


def _layer(x, stack, i, window, rotary, config, inputs):
    """Layer ``i`` of the run ``stack``."""
    theta, eps = float(config["rope_theta"]), config["rms_norm_eps"]
    w = {n: _f32(stack[n][i]) for n in stack if n not in _EXPERT_WEIGHTS}
    h = _rms_norm(x, w["attn_norm"], eps)
    gate_of = route(_mm("bsm,me->bse", h, w["router"], inputs), config)
    q = _mm("bsm,mhd->bshd", h, w["wq"], inputs)
    k = _mm("bsm,mhd->bshd", h, w["wk"], inputs)
    v = _mm("bsm,mhd->bshd", h, w["wv"], inputs)
    if rotary:
        q, k = _rotary(q, theta), _rotary(k, theta)
    a = _attention(q, k, v, window, inputs)
    x = x + _mm("bshd,hdm->bsm", a, w["wo"], inputs)
    u = _rms_norm(x, w["mlp_norm"], eps)
    return x + _experts(u, gate_of, stack, i, inputs)


def hidden(params, tokens, config, inputs=None):
    """Final-norm hidden states [B, S, M] for tokens [B, S]; S whole
    blocks of ``Q_BLOCK`` or no more than one."""
    layers = config["num_hidden_layers"]
    windows = [config["sliding_window_size"] if flag else None
               for flag in config["sliding_window_layout"][:layers]]
    rotary = config["rope_layout"][:layers]
    x = _f32(params["embed"][tokens])
    at = 0
    for stack in params["layers"]:
        for i in range(stack["attn_norm"].shape[0]):
            x = _layer(x, stack, i, windows[at], bool(rotary[at]), config,
                       inputs)
            at += 1
    if at != layers:
        raise ValueError(f"{at} layers of weights, {layers} configured")
    return _rms_norm(x, _f32(params["final_norm"]), config["rms_norm_eps"])


def logits(params, tokens, config):
    """[B, S, V] logits whole: for a test at a tiny size."""
    return _mm("bsm,mv->bsv", hidden(params, tokens, config),
               _f32(params["lm_head"]))


def _per_block(x, head, reduce_logits, *others, inputs=None):
    """``reduce_logits(logits [B, block, V], *others' blocks)`` over
    blocks of positions, so [B, S, V] never exists at once."""
    b, s, m = x.shape
    block = _blocks(s)

    def blocked(a):
        return jnp.moveaxis(
            a.reshape((b, s // block, block) + a.shape[2:]), 1, 0)

    def one_block(args):
        xb, *rest = args
        return reduce_logits(_mm("bsm,mv->bsv", xb, head, inputs), *rest)

    out = jax.lax.map(one_block, tuple(map(blocked, (x,) + others)))
    return jnp.moveaxis(out, 0, 1).reshape(b, s)


def _margin(logits, targets):
    chosen = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return logits.max(-1) - chosen


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1]."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["lm_head"]), nll, tokens[:, 1:]).mean()


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
