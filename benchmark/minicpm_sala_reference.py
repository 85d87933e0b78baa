"""The plain reference for MiniCPM-SALA (``model_type`` minicpm_sala):
float32 ``jax.numpy``, every matmul at ``precision="highest"``, no kernel,
no cache, no page, no chunked scan and no code of the program.

**The stack (MiniCPM's muP).** ``x0 = scale_emb E[token]``. Every
sub-block ``x <- x + r f(RMSNorm(x))`` with ``r = scale_depth /
sqrt(num_hidden_layers)`` of the PUBLISHED depth
(``reduced.num_hidden_layers.published``: a cut of the depth keeps each
layer what it is). The FFN is SiLU-gated. ``rms_norm_eps``, a learned
weight on every norm, no biases. Behind the last layer the final RMSNorm,
then ``logits = (h / (hidden_size / dim_model_base)) W_head``, untied.

**A "minicpm4" layer** (``num_attention_heads`` heads on
``num_key_value_heads`` KV heads of ``head_dim``, ``G`` query heads a KV
head ``g``). ``q, k, v = h W_q, h W_k, h W_v``; an RMSNorm over each
head's width on q and on k (one weight ``[head_dim]`` each); no rotary
(``attn_use_rope`` false); scores scaled by ``head_dim ** -0.5``. For the
query at position ``t`` (0-based), with ``assumed.sparse_config``:

1. ``t < dense_len``: causal softmax attention over all ``i <= t``.
2. otherwise, for KV head ``g``: compressed keys ``C_j = mean(k_i, i =
   kernel_stride j .. kernel_stride j + kernel_size - 1)`` for every
   ``j`` whose last token is ``<= t``; ``p_h = softmax_j(q_h . C_j /
   sqrt(head_dim))`` for each head of the group; ``r_j = sum_h p_h[j]``;
   block ``b`` (tokens ``block_size b .. block_size b + block_size - 1``)
   scores ``R_b = max r_j`` over the ``j`` whose window overlaps it and
   that exist; kept are blocks ``0 .. init_blocks - 1``, the last
   ``window_size / block_size`` blocks up to the token's own, and of the
   rest those of largest ``R_b``, ``topk`` blocks in all among ``b <=
   floor(t / block_size)``, ties towards the lower block
   (``jax.lax.top_k``, which is stable); every head of the group attends,
   by softmax, over the tokens ``i <= t`` of the kept blocks with the
   real k and v.
3. ``o = o * sigmoid(h W_g)`` with ``h`` the layer's normed input, then
   ``W_o``.

**A "lightning-attn" layer** (``lightning_nh`` heads of
``lightning_head_dim``, a key and value head each). ``q, k, v`` as above
with the per-head norms, then rotary on q and k (``rope_theta``, the two
halves). For head ``n`` (0-based) of PUBLISHED layer ``l``: ``o_t =
sum_{j <= t} lambda_n^(t - j) (q_t . k_j) v_j / sqrt(d)``, ``lambda_n =
exp(-2^(-8 (n + 1) / heads) (1 - l / (layers - 1) + 1e-5))`` with the
published ``layers``: the recurrence ``S_t = lambda S_{t-1} + k_t v_t^T``,
``o_t = S_t^T q_t / sqrt(d)`` written as a masked, decayed ``Q K^T``, a
block of queries at a time. Then an RMSNorm over each head's width (one
weight), ``* sigmoid(h W_g)``, ``W_o``.

The file's layers are published layers ``layers_kept[0] ..
layers_kept[1]``; the stacks of the program's tree come in that order and
a stack with ``o_norm`` is a lightning one (its ``log_decay`` leaf is NOT
read: the decays are computed here from the formula).

Departures from the released code, each in the file's ``assumed``: the
switch to the selection by QUERY POSITION (the release switches by the
key length of a call; by position is the only rule under which a prefill
and then decode steps equal one full pass); the exact softmax over the
compressed keys (the release's kernels approximate its log-sum-exp with
coarser means); the norms a head; the decays' formula; the state in
float32.

``departure``, the controls' handle: one of ``DEPARTURES`` computes
another model on purpose (no residual scale, rotary on the selected
layers, no decay, ``topk`` halved, no forced local blocks but the
token's own, ``dense_len`` ignored, the lightning state rounded to
bfloat16 after every token, which runs the recurrence a token at a
time). ``inputs``: with a dtype, every matmul operand is rounded to it
first and computed on in float32.

For memory, none changing a result: a layer's weights are cast to float32
one layer at a time, attention works a KV head's group (a lightning
layer: ``HEAD_GROUP`` heads) at a time in blocks of ``Q_BLOCK`` queries,
the FFN in blocks of ``ROW_BLOCK`` rows and ``FFN_BLOCK`` of its columns,
the head in ``VOCAB_PARTS`` parts of the vocabulary and blocks of
``HEAD_BLOCK`` positions. A sequence is padded at its end to whole blocks
(causal: a token sees nothing behind it).

Tolerances, and why. float32: both sides in float32, differing in the
order of sums, in the chunked scan against the attention form and, where
two block scores lie within float32 rounding of the ``topk``-th, in one
selected block; at the tiny size on the CPU the programs' logits agree
with this reference within 2e-5 with contexts on both sides of
``dense_len`` and of every forced block
(tests/bench_harness/test_benchmark_minicpm_sala.py); the limit is 1e-4.
bfloat16 ``LOSS_ATOL``: the Mistral reference's; no cell reads it.

bfloat16 ``LOGIT_MARGIN_TOL``, from two readings on the v5e at the
published widths (my chip runs, PR 70; PERF.md section 6). The logits
are small by construction (the head's input over 16, every sub-block's
output times 0.25), and so are the margins. The system: over twenty-one
runs on as many seeds of ``serve-sala-c16-32k`` (four finished requests
a run, 3,139-4,020 served tokens, contexts 19k-35k, every one past
``dense_len``) a run's worst margin read 0.0052-0.0079, and 92.7-94.3%
of served tokens are the reference's argmax. ``control_margins`` on one
seeded sequence of 24,576 tokens: at float8_e4m3fn, the precision below
bfloat16, the token it puts first trails the float32 reference's best by
0.129 at worst (p99 0.085, 31% argmax): not correct. With bfloat16
operands, what the engine may do: 0.0038 at worst (96.5% argmax), so the
system's readings are bfloat16's own. The departures, each alone:
``topk`` halved 0.036 (77% argmax past ``dense_len``), the forced local
blocks dropped 0.023 (81%), the decay left out 0.45 (0.3%): each not
correct. The limit is 0.015: 1.9 times the largest the system gave, and
the smallest failing control is 1.5 times the limit.
**What the comparison does NOT hold: the state's precision.** With the
Lightning state rounded to bfloat16 behind every token
(``state_bfloat16``, the recurrence a token at a time) the worst margin
is 0.00014 and 99.9% of tokens are the argmax: at these decays (a head
forgets over 2 to 700 tokens) a state kept in bfloat16 is inside
bfloat16's own noise. What holds the pool to float32 is its dtype,
pinned by tests/test_generation_blocks.py, and
``linear_slot_bytes.chat`` on every traced run's line, as Kimi-Linear's
delta state is held.
**What one block costs.** Where two block scores lie within bfloat16's
rounding of the ``topk``-th, the program keeps another block than this
file: 64 tokens of 4,096 attended by one KV head's group in one of the
three ``minicpm4`` layers, behind an output gate and a residual scale of
0.25; the blocks at stake are by construction the least of those kept,
so what they carry of the softmax is its tail. Neither side drops its
selection: the bfloat16 control selects from rounded operands too and
reads under the system's own worst (0.0038 against 0.0052-0.0079).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference import _HI, _f32, _rms_norm

Q_BLOCK = 128
FFN_BLOCK = 2048
ROW_BLOCK = 2048
HEAD_BLOCK = 512
HEAD_GROUP = 4
VOCAB_PARTS = 8
LOSS_ATOL = {"bfloat16": 0.002, "float32": 1e-4}
# bfloat16: between the system's largest worst margin (0.0079) and the
# smallest failing control's (0.023; float8: 0.129); the docstring has
# the readings.
LOGIT_MARGIN_TOL = {"bfloat16": 0.015, "float32": 1e-4}
DEPARTURES = ("no_residual_scale", "rope_selected", "no_decay",
              "topk_halved", "no_local_blocks", "no_dense_len",
              "state_bfloat16")


def _mm(equation, a, b, inputs):
    """One matmul in float32; the operands first rounded to ``inputs``.
    A weight comes as it is stored and is cast here, where it is used."""
    if inputs is not None:
        a, b = (x.astype(inputs) for x in (a, b))
    return jnp.einsum(equation, _f32(a), _f32(b), precision=_HI)


def _rotary(x, theta):
    """x [S, H, D]: rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks(x, block):
    """[S, ..] -> [S / block, block, ..]."""
    return x.reshape((x.shape[0] // block, block) + x.shape[1:])


def _published_layers(config):
    return config.get("reduced", {}).get("num_hidden_layers", {}).get(
        "published", config["num_hidden_layers"])


def _sparse(config, departure):
    sizes = dict(config["assumed"]["sparse_config"])
    if departure == "topk_halved":
        sizes["topk"] //= 2
    if departure == "no_local_blocks":
        sizes["window_size"] = 0
    if departure == "no_dense_len":
        sizes["dense_len"] = 0
    return sizes


def kept_blocks(q, k, start, sizes, inputs=None):
    """[block of queries, blocks] bool for one KV head: q [Q, G, d] the
    group's queries at positions ``start ..``, k [S, d] the head's keys.
    Steps 1-2 of the docstring's selection."""
    Q, S, d = q.shape[0], k.shape[0], q.shape[-1]
    size, stride, block = (sizes["kernel_size"], sizes["kernel_stride"],
                           sizes["block_size"])
    t = start + jnp.arange(Q)
    n_keys = (S - size) // stride + 1
    first = jnp.arange(n_keys) * stride
    # C_j, by the definition: the mean of the window's keys.
    windows = first[:, None] + jnp.arange(size)[None, :]
    c = k[windows].mean(axis=1)                               # [keys, d]
    exists = (first + size - 1)[None, :] <= t[:, None]        # [Q, keys]
    s = _mm("qgd,jd->gqj", q, c, inputs) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(exists[None], s, -jnp.inf), axis=-1)
    r = jnp.where(exists, jnp.nan_to_num(p).sum(axis=0), -jnp.inf)
    n_blocks = S // block
    b = jnp.arange(n_blocks)
    # The windows that overlap block b: stride j + size > block b and
    # stride j < block (b + 1), a run of j; those that exist.
    low = (block * b - size) // stride + 1
    reach = -(-(block + size) // stride)
    window = low[:, None] + jnp.arange(reach)[None, :]        # [nb, reach]
    overlaps = ((window >= 0) & (window < n_keys)
                & (window * stride < (block * (b + 1))[:, None]))
    score = jnp.where(overlaps[None],
                      r[:, jnp.clip(window, 0, n_keys - 1)], -jnp.inf
                      ).max(axis=-1)                          # [Q, nb]
    own = t // block
    seen = b[None, :] <= own[:, None]
    forced = ((b[None, :] < sizes["init_blocks"])
              | (b[None, :] > own[:, None] - sizes["window_size"] // block))
    score = jnp.where(seen, jnp.where(forced, jnp.inf, score), -jnp.inf)
    _, chosen = jax.lax.top_k(score, min(sizes["topk"], n_blocks))
    picked = jax.vmap(lambda row, at: row.at[at].set(True))(
        jnp.zeros(score.shape, bool), chosen)
    return jnp.where((t < sizes["dense_len"])[:, None], seen, picked & seen)


def _sparse_attention(h, w, config, departure, inputs):
    """A ``minicpm4`` layer's attention output through W_o, [S, hidden]:
    a KV head's group at a time, a block of queries at a time."""
    S = h.shape[0]
    heads, kv_heads = w["wq"].shape[1], w["wk"].shape[1]
    d, group = w["wq"].shape[2], heads // kv_heads
    eps, sizes = config["rms_norm_eps"], _sparse(config, departure)
    block = min(Q_BLOCK, S)
    key_at = jnp.arange(S)

    def grouped(name):
        x = w[name]
        axis = 0 if name == "wo" else 1
        return jnp.moveaxis(x.reshape(
            x.shape[:axis] + (kv_heads, group) + x.shape[axis + 1:]), axis, 0)

    def one_group(total, args):
        wq, wk, wv, wg, wo = args
        q = _rms_norm(_mm("sm,mgd->sgd", h, wq, inputs), w["q_norm"], eps)
        k = _rms_norm(_mm("sm,md->sd", h, wk[:, 0], inputs), w["k_norm"], eps)
        v = _mm("sm,md->sd", h, wv[:, 0], inputs)
        if departure == "rope_selected":
            q = _rotary(q, float(config["rope_theta"]))
            k = _rotary(k[:, None], float(config["rope_theta"]))[:, 0]

        def one_block(args):
            qb, start = args
            keep = kept_blocks(qb, k, start, sizes, inputs)
            keep = (jnp.repeat(keep, sizes["block_size"], axis=-1)
                    & (key_at[None, :] <= (start + jnp.arange(block))[:, None]))
            s = _mm("qgd,td->gqt", qb, k, inputs) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
            return _mm("gqt,td->qgd", p, v, inputs)

        o = jax.lax.map(one_block, (_blocks(q, block),
                                    jnp.arange(S // block) * block))
        o = o.reshape(S, group, d) * jax.nn.sigmoid(
            _mm("sm,mgd->sgd", h, wg, inputs))
        return total + _mm("sgd,gdm->sm", o, wo, inputs), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros((S, w["wo"].shape[-1])),
        (grouped("wq"), w["wk"].transpose(1, 0, 2)[:, :, None],
         w["wv"].transpose(1, 0, 2)[:, :, None], grouped("wg"),
         grouped("wo")))
    return total


def log_decays(heads, layer, layers):
    """[heads]: ``log lambda_n`` of published layer ``layer`` of
    ``layers``."""
    n = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -(2.0 ** (-8.0 * n / heads)) * (1.0 - layer / (layers - 1) + 1e-5)


def _lightning_recurrent(q, k, v, log_lambda, state_dtype):
    """The recurrence a token at a time, the state rounded to
    ``state_dtype`` behind every token: the ``state_bfloat16`` control.
    q, k, v [S, H, d]; log_lambda [H]."""
    def step(state, args):
        qt, kt, vt = args
        state = (jnp.exp(log_lambda)[:, None, None] * state
                 + kt[:, :, None] * vt[:, None, :])
        state = _f32(state.astype(state_dtype))
        return state, jnp.einsum("hde,hd->he", state, qt, precision=_HI)

    heads, d = q.shape[1:]
    _, out = jax.lax.scan(step, jnp.zeros((heads, d, d)), (q, k, v))
    return out


def _lightning_attention(h, w, layer, config, departure, inputs):
    """A ``lightning-attn`` layer's output through W_o, [S, hidden]:
    ``HEAD_GROUP`` heads at a time, a block of queries at a time."""
    S = h.shape[0]
    heads, d = w["wq"].shape[1:]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    group = min(HEAD_GROUP, heads)
    block = min(Q_BLOCK, S)
    key_at = jnp.arange(S)
    decays = log_decays(heads, layer, _published_layers(config))
    if departure == "no_decay":
        decays = jnp.zeros_like(decays)

    def grouped(x, axis):
        return jnp.moveaxis(x.reshape(
            x.shape[:axis] + (heads // group, group) + x.shape[axis + 1:]),
            axis, 0)

    def one_group(total, args):
        wq, wk, wv, wg, wo, log_lambda = args
        q = _rotary(_rms_norm(_mm("sm,mhd->shd", h, wq, inputs),
                              w["q_norm"], eps), theta)
        k = _rotary(_rms_norm(_mm("sm,mhd->shd", h, wk, inputs),
                              w["k_norm"], eps), theta)
        v = _mm("sm,mhd->shd", h, wv, inputs)

        def one_block(args):
            qb, start = args
            gap = (start + jnp.arange(block))[:, None] - key_at[None, :]
            weight = jnp.where(
                gap >= 0, jnp.exp(log_lambda[:, None, None]
                                  * jnp.maximum(gap, 0)[None]), 0.0)
            s = _mm("qhd,thd->hqt", qb, k, inputs) * weight
            return _mm("hqt,thd->qhd", s, v, inputs)

        if departure == "state_bfloat16":
            o = _lightning_recurrent(q, k, v, log_lambda, jnp.bfloat16)
        else:
            o = jax.lax.map(one_block, (
                _blocks(q, block), jnp.arange(S // block) * block)
            ).reshape(S, group, d)
        o = _rms_norm(o / math.sqrt(d), w["o_norm"], eps) * jax.nn.sigmoid(
            _mm("sm,mhd->shd", h, wg, inputs))
        return total + _mm("shd,hdm->sm", o, wo, inputs), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros((S, w["wo"].shape[-1])),
        (grouped(w["wq"], 1), grouped(w["wk"], 1), grouped(w["wv"], 1),
         grouped(w["wg"], 1), grouped(w["wo"], 0), grouped(decays, 0)))
    return total


def _mlp(x, w, eps, inputs):
    """The SiLU-gated FFN of ``RMSNorm(x)``, ``ROW_BLOCK`` rows at a time
    and for each ``FFN_BLOCK`` of its intermediate columns in turn (their
    contributions summed): a block's weights are cast to float32 when
    its turn comes, never the whole layer's, and no [S, intermediate]
    array exists."""
    width = w["w_gate"].shape[1]
    block = min(FFN_BLOCK, width)

    def cut(a, axis):
        return jnp.moveaxis(a.reshape(
            a.shape[:axis] + (width // block, block) + a.shape[axis + 1:]),
            axis, 0)

    columns = (cut(w["w_gate"], 1), cut(w["w_up"], 1), cut(w["w_down"], 0))

    def some_rows(xb):
        y = _rms_norm(xb, w["mlp_norm"], eps)

        def some_columns(total, args):
            w_gate, w_up, w_down = args
            a = _mm("sm,mf->sf", y, w_gate, inputs)
            b = _mm("sm,mf->sf", y, w_up, inputs)
            return total + _mm("sf,fm->sm", jax.nn.silu(a) * b, w_down,
                               inputs), None

        return jax.lax.scan(some_columns, jnp.zeros_like(xb), columns)[0]

    return jax.lax.map(
        some_rows, _blocks(x, min(ROW_BLOCK, x.shape[0]))).reshape(x.shape)


def _padded(tokens):
    """How long a sequence of ``tokens`` runs here: whole blocks."""
    unit = ROW_BLOCK if tokens > ROW_BLOCK else (
        Q_BLOCK if tokens > Q_BLOCK else 64)
    return -(-tokens // unit) * unit


def hidden(params, tokens, config, inputs=None, departure=None):
    """The head's input [B, S, M] for tokens [B, S]: behind the final
    norm, divided by ``hidden_size / dim_model_base``."""
    if departure is not None and departure not in DEPARTURES:
        raise ValueError(f"departure {departure!r} is none of {DEPARTURES}")
    eps = config["rms_norm_eps"]
    scale = config["scale_depth"] / math.sqrt(_published_layers(config))
    if departure == "no_residual_scale":
        scale = 1.0
    first = config["layers_kept"][0]
    S = tokens.shape[1]
    tokens = jnp.pad(tokens, ((0, 0), (0, _padded(S) - S)))

    def one(row):
        x = _f32(params["embed"][row]) * config["scale_emb"]
        layer = first
        for stack in params["layers"]:
            for i in range(stack["attn_norm"].shape[0]):
                w = {n: stack[n][i] for n in stack}
                y = _rms_norm(x, w["attn_norm"], eps)
                if "o_norm" in w:
                    a = _lightning_attention(y, w, layer, config, departure,
                                             inputs)
                else:
                    a = _sparse_attention(y, w, config, departure, inputs)
                x = x + scale * a
                x = x + scale * _mlp(x, w, eps, inputs)
                layer += 1
        x = _rms_norm(x, _f32(params["final_norm"]), eps)
        return x / (config["hidden_size"] / config["dim_model_base"])

    return jnp.stack([one(row) for row in tokens])[:, :S]


def _per_block(x, head, reduce_logits, *others, inputs=None):
    """``reduce_logits(logits [B, block, V], *others' blocks)`` over
    blocks of positions, so [B, S, V] never exists at once."""
    S = x.shape[1]
    block = min(HEAD_BLOCK, S)
    pad = -S % block

    def cut(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(
            (a.shape[0], (S + pad) // block, block) + a.shape[2:]), 1, 0)

    def one_block(args):
        xb, *rest = args
        return reduce_logits(_mm("bsm,mv->bsv", xb, head, inputs), *rest)

    out = jax.lax.map(one_block, tuple(cut(a) for a in (x,) + others))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], S + pad) + out.shape[3:])[:, :S]


def logits(params, tokens, config, departure=None):
    """[B, S, V] logits whole: for a test at a tiny size."""
    return jnp.einsum("bsm,mv->bsv",
                      hidden(params, tokens, config, departure=departure),
                      _f32(params["lm_head"]), precision=_HI)


def loss(params, tokens, config):
    """Mean next-token cross entropy of tokens [B, S+1]."""
    def nll(logits, targets):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    x = hidden(params, tokens[:, :-1], config)
    return _per_block(x, _f32(params["lm_head"]), nll, tokens[:, 1:]).mean()


def _best_and_chosen(x, head, targets, inputs=None):
    """For the head's input x [B, S, M] and ``targets`` [B, S]: (the
    best logit, the token that has it, the logit of ``targets``), each
    [B, S]; ``VOCAB_PARTS`` parts of the vocabulary in turn (each cast to
    float32 when its turn comes) and ``HEAD_BLOCK`` positions at a time,
    so that neither [B, S, V] nor a float32 head exists at once."""
    B, S, _ = x.shape
    V = head.shape[1]
    parts = VOCAB_PARTS if V % VOCAB_PARTS == 0 else 1
    width = V // parts
    block = min(HEAD_BLOCK, S)
    pad = -S % block

    def cut(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(
            (B, (S + pad) // block, block) + a.shape[2:]), 1, 0)

    xs, ts = cut(x), cut(targets)

    def one_part(carry, args):
        columns, first = args

        def one_block(args):
            xb, tb = args
            logits = _mm("bsm,mv->bsv", xb, columns, inputs)
            here = (tb >= first) & (tb < first + width)
            chosen = jnp.take_along_axis(
                logits, jnp.clip(tb - first, 0, width - 1)[..., None],
                -1)[..., 0]
            return (logits.max(-1), logits.argmax(-1) + first,
                    jnp.where(here, chosen, 0.0))

        best, token, chosen = jax.lax.map(one_block, (xs, ts))
        better = best > carry[0]          # a tie keeps the lower token
        return (jnp.where(better, best, carry[0]),
                jnp.where(better, token, carry[1]), carry[2] + chosen), None

    shape = xs.shape[:3]
    (best, token, chosen), _ = jax.lax.scan(
        one_part, (jnp.full(shape, -jnp.inf), jnp.zeros(shape, jnp.int32),
                   jnp.zeros(shape)),
        (jnp.moveaxis(head.reshape(head.shape[0], parts, width), 1, 0),
         jnp.arange(parts) * width))

    def whole(a):
        return jnp.moveaxis(a, 0, 1).reshape(B, S + pad)[:, :S]

    return whole(best), whole(token), whole(chosen)


def logit_margins(params, tokens, config):
    """For tokens [B, S+1]: at each position, how far the logit of the
    token that follows trails the best logit (0 where it is the
    argmax). Teacher-forced: one full forward, no cache."""
    x = hidden(params, tokens[:, :-1], config)
    best, _, chosen = _best_and_chosen(x, params["lm_head"], tokens[:, 1:])
    return best - chosen


def control_margins(params, tokens, config, inputs=None, departure=None):
    """The control: this reference computed otherwise (every matmul
    operand rounded to ``inputs``, or one of ``DEPARTURES``), put in the
    program's place. For tokens [B, S]: at each position, how far the
    token such a model puts first trails the float32 reference's best
    logit, [B, S]."""
    head = params["lm_head"]
    _, first, _ = _best_and_chosen(
        hidden(params, tokens, config, inputs, departure), head,
        jnp.zeros_like(tokens), inputs)
    best, _, chosen = _best_and_chosen(hidden(params, tokens, config), head,
                                       first)
    return best - chosen
