"""The serving programs over the paged KV cache against the plain full
forward pass, and the decode attention against a reference of its own."""

import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, forward, init_params  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    PagedKVCache,
    paged_decode,
    paged_prefill,
)


def _reference_decode_attention(q, ck, cv, page_table, lengths):
    """Plain float32 over one layer's pool [Hkv, P, page, D]: a slot's
    real tokens, positions 0..lengths[b], sliced out of its pages in
    table order; one softmax a query row."""
    q, ck, cv = (jnp.asarray(x, jnp.float32) for x in (q, ck, cv))
    B, H, D = q.shape
    rep = H // ck.shape[0]
    rows = []
    for b in range(B):
        n = int(lengths[b]) + 1
        heads = []
        for h in range(H):
            k = jnp.concatenate([ck[h // rep, p] for p in page_table[b]])[:n]
            v = jnp.concatenate([cv[h // rep, p] for p in page_table[b]])[:n]
            prob = jax.nn.softmax(k @ q[b, h] * D ** -0.5)
            heads.append(prob @ v)
        rows.append(jnp.stack(heads))
    return np.asarray(jnp.stack(rows))


# name: (H, Hkv, pool dtype, lengths, active, pages a slot, layers, the
# layer decoded). Pages are 16 tokens and, under a table of 10 or 17
# columns, the kernel's step 8 or 16 pages (``walk_step_tokens``), so
# 10 pages a slot make a second, partly filled step. ``lengths[b]`` is
# where the new row goes: 16 is the first row of a fresh page, 128 (of
# 10 pages) and 256 (of 17) the first of a fresh step, 159 of 10 pages
# the last cell of the slot's last page.
_PAGED_CASES = {
    "length_0": (4, 2, "float32", [0], [True], 4, 2, 1),
    "length_15_page_end": (4, 2, "float32", [15], [True], 4, 2, 0),
    "length_16_page_start": (4, 2, "float32", [16], [True], 4, 2, 1),
    "length_17": (4, 2, "float32", [17], [True], 4, 2, 0),
    "last_cell_of_last_page": (4, 2, "float32", [159, 127, 128], [True] * 3,
                               10, 2, 1),
    "inactive_slot_stale_row": (4, 2, "float32", [40, 150, 3],
                                [True, False, True], 10, 2, 0),
    "gqa_rep4_hkv8": (32, 8, "float32", [5, 131], [True, True], 10, 2, 1),
    "mha_rep1": (4, 4, "float32", [33, 64], [True, True], 10, 2, 0),
    "bf16_pool": (32, 8, "bfloat16", [0, 100, 159], [True] * 3, 10, 2, 1),
    "block_boundaries": (4, 2, "float32", [127, 128, 256, 255], [True] * 4,
                         17, 1, 0),
    "all_slots_inactive": (4, 2, "float32", [16, 130], [False, False], 10,
                           2, 1),
    "layer_0_of_3": (4, 2, "float32", [20, 143], [True, True], 10, 3, 0),
    "layer_last_of_3": (4, 2, "float32", [20, 143], [True, True], 10, 3, 2),
    "mha_hkv16_bf16": (16, 16, "bfloat16", [31, 144, 7],
                       [True, True, False], 10, 2, 1),
    # 28 rows of queries: no whole number of sublane tiles (8 of
    # float32, 16 of bfloat16), and groups of 7 (SmallThinker).
    "gqa_rep7_hkv4": (28, 4, "float32", [5, 131, 159], [True] * 3, 10, 2, 1),
    "gqa_rep7_hkv4_bf16": (28, 4, "bfloat16", [0, 100, 159, 16],
                           [True, True, True, False], 10, 2, 0),
    # A compute step is as long as its bytes say (``walk_step_tokens``):
    # 512 tokens at 4 KV heads of bfloat16 or 2 of float32, 256 at 8,
    # 128 at 16. 511 is the last row of a step, 512 the first of the
    # next, which then holds one page; 1024 opens a third step.
    "step_edges_hkv2": (4, 2, "float32", [511, 512, 513, 1023, 1024],
                        [True] * 5, 65, 1, 0),
    "step_edges_hkv4_bf16": (28, 4, "bfloat16", [511, 512, 513, 1023, 1024],
                             [True] * 5, 65, 2, 1),
    "step_edges_hkv8_bf16": (32, 8, "bfloat16", [255, 256, 257, 511, 512],
                             [True] * 5, 33, 2, 0),
    # A slot of 3 tokens beside one of 1,500, between them an idle slot
    # with the row and the length its last request left: no step of the
    # short slot reads past its one page, nothing of the idle one moves.
    "short_idle_long_hkv4_bf16": (28, 4, "bfloat16", [3, 700, 1500],
                                  [True, False, True], 96, 2, 1),
    "short_idle_long_hkv8_bf16": (32, 8, "bfloat16", [3, 700, 1500],
                                  [True, False, True], 96, 2, 0),
    "short_idle_long_hkv16_bf16": (16, 16, "bfloat16", [3, 700, 1500],
                                   [True, False, True], 96, 1, 0),
    "short_idle_long_hkv2": (4, 2, "float32", [3, 700, 1500],
                             [True, False, True], 96, 1, 0),
}


@pytest.mark.parametrize("case", list(_PAGED_CASES))
@pytest.mark.parametrize("path", ["page_walk", "gather"])
def test_paged_decode_attention_matches_reference(path, case):
    """Both decode attentions (the Pallas page walk in interpret mode,
    the XLA gather): the new K/V row of each active slot lands in
    ``[layer, :, page_table[b, len // page], len % page]`` and every
    other cell of both pools is bit-identical (an inactive slot writes
    nothing); the attention equals the float32 reference above over the
    pool so written. Every case walks pages out of order; a slot's
    unused table cells hold 0, the id of a page another slot uses; an
    inactive slot keeps the row and the length its last request left."""
    from ray_tpu.ops import paged_attention as pa

    H, Hkv, dtype, lengths, active, pmax, n_layers, layer = \
        _PAGED_CASES[case]
    B, D, page = len(lengths), 128, 16
    n_pool = B * pmax
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(B, H, D), dtype)
    k_new = jnp.asarray(rng.randn(B, Hkv, D), dtype)
    v_new = jnp.asarray(rng.randn(B, Hkv, D), dtype)
    ck = jnp.asarray(rng.randn(n_layers, Hkv, n_pool, page, D), dtype)
    cv = jnp.asarray(rng.randn(n_layers, Hkv, n_pool, page, D), dtype)
    order = rng.permutation(n_pool)
    order[np.argmin(order)], order[0] = order[0], 0  # slot 0 owns page 0
    table = np.zeros((B, pmax), np.int32)
    for b, n in enumerate(lengths):
        used = n // page + 1
        table[b, :used] = order[b * pmax:b * pmax + used]
    active = np.asarray(active)
    want_k, want_v = np.array(ck), np.array(cv)
    for b in np.flatnonzero(active):
        cell = (layer, slice(None), table[b, lengths[b] // page],
                lengths[b] % page)
        want_k[cell], want_v[cell] = k_new[b], v_new[b]
    lengths = jnp.asarray(lengths, jnp.int32)
    args = (q, k_new, v_new, ck, cv, jnp.asarray(layer, jnp.int32),
            jnp.asarray(table), lengths, jnp.asarray(active))
    if path == "page_walk":
        out, got_k, got_v = pa.paged_decode_attention(*args, interpret=True)
        assert not np.asarray(out, np.float32)[~active].any()
    else:
        out, got_k, got_v = pa.gather_decode_attention(*args)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    assert got_k.dtype == ck.dtype and got_v.dtype == cv.dtype
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = _reference_decode_attention(q, want_k[layer], want_v[layer], table,
                                      lengths)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[active],
                               ref[active], atol=tol, rtol=tol)


_WINDOW_CASES = {
    # lengths (position of the new row), window; page 16
    "under_the_window": ([5, 20], 32),
    "crossing_it": ([31, 32, 33], 32),
    "far_over_wrapping": ([47, 48, 200, 1000], 32),
    "window_no_page_multiple": ([70, 129, 7], 40),
    "an_idle_slot": ([300, 90], 64),
    # 28 query heads on 4 (the third and fourth entries: H, Hkv).
    "groups_of_7": ([47, 300, 33], 32, 28, 4),
    # A window of 64 pages under steps of 32 (float32, 2 KV heads): rings
    # of 65 columns that wrapped four times and once, walks of 65 pages
    # (two steps and one of a single page) from columns 53 and 1 and of
    # 64 from column 54, so each step crosses what a step was on the
    # ring's first turn, and the first two the ring's end.
    "wrapped_ring_of_steps": ([5000, 5007, 2090, 40], 1024),
}


@pytest.mark.parametrize("case", list(_WINDOW_CASES))
@pytest.mark.parametrize("path", ["page_walk", "gather"])
def test_window_decode_attention_matches_the_masked_einsum(path, case):
    """A window layer's decode attention over a RING of ``ring_pages``
    columns, on both paths: each slot's whole history is laid into its
    ring the way prefill and earlier steps would have left it (position t
    in column ``(t // page) % columns``, later pages over earlier ones);
    the new row lands in the ring's cell for ``lengths[b]`` and nowhere
    else, and the output equals a plain softmax over positions
    ``lengths[b] - window < t <= lengths[b]`` of the history, on float32
    values, whether the slot is under the window, crosses it or has
    wrapped its ring many times. A slot's unused columns hold page 0."""
    from ray_tpu.ops import paged_attention as pa

    lengths, window, H, Hkv = (*_WINDOW_CASES[case], 4, 2)[:4]
    B, D, page, layer = len(lengths), 128, 16, 1
    columns = pa.ring_pages(window, page, 4096)
    assert columns == -(-window // page) + 1
    active = np.ones(B, bool)
    if case == "an_idle_slot":
        active[1] = False
    rng = np.random.RandomState(len(case))
    n_pool = B * columns + 1
    q = rng.randn(B, H, D).astype(np.float32)
    hist_k = [rng.randn(n + 1, Hkv, D).astype(np.float32) for n in lengths]
    hist_v = [rng.randn(n + 1, Hkv, D).astype(np.float32) for n in lengths]
    ck = rng.randn(2, Hkv, n_pool, page, D).astype(np.float32)
    cv = rng.randn(2, Hkv, n_pool, page, D).astype(np.float32)
    order = 1 + rng.permutation(B * columns)          # page 0 is no one's
    table = np.zeros((B, columns), np.int32)
    for b, n in enumerate(lengths):
        used = min(n // page + 1, columns)
        table[b, :used] = order[b * columns:b * columns + used]
        for t in range(n):                             # the rows before
            cell = (layer, slice(None), table[b, (t // page) % columns],
                    t % page)
            ck[cell], cv[cell] = hist_k[b][t], hist_v[b][t]
    want_k, want_v = ck.copy(), cv.copy()
    for b in np.flatnonzero(active):
        n = lengths[b]
        cell = (layer, slice(None), table[b, (n // page) % columns], n % page)
        want_k[cell], want_v[cell] = hist_k[b][n], hist_v[b][n]
    args = (jnp.asarray(q), jnp.asarray(np.stack([h[-1] for h in hist_k])),
            jnp.asarray(np.stack([h[-1] for h in hist_v])), jnp.asarray(ck),
            jnp.asarray(cv), jnp.asarray(layer, jnp.int32),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active))
    if path == "page_walk":
        out, got_k, got_v = pa.paged_decode_attention(
            *args, window=window, interpret=True)
    else:
        out, got_k, got_v = pa.gather_decode_attention(*args, window=window)
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    for b in np.flatnonzero(active):
        n = lengths[b]
        lo = max(0, n + 1 - window)
        k, v = hist_k[b][lo:n + 1], hist_v[b][lo:n + 1]   # [T, Hkv, D]
        qg = q[b].reshape(Hkv, H // Hkv, D)
        s = np.einsum("hgd,thd->hgt", qg, k) * D ** -0.5
        prob = np.exp(s - s.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        ref = np.einsum("hgt,thd->hgd", prob, v).reshape(H, D)
        np.testing.assert_allclose(np.asarray(out)[b], ref, atol=2e-5,
                                   rtol=2e-5)


def test_a_window_of_none_is_the_walk_over_everything():
    """``window=None`` changes nothing: the same jaxpr as a call that
    does not name it, on both paths, and a window wider than the
    context gives the same numbers over a table that holds it all."""
    from ray_tpu.ops import paged_attention as pa

    B, H, Hkv, D, page, pmax = 2, 4, 2, 128, 16, 4
    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.randn(B, H, D), jnp.float32),
            jnp.asarray(rng.randn(B, Hkv, D), jnp.float32),
            jnp.asarray(rng.randn(B, Hkv, D), jnp.float32),
            jnp.asarray(rng.randn(1, Hkv, B * pmax, page, D), jnp.float32),
            jnp.asarray(rng.randn(1, Hkv, B * pmax, page, D), jnp.float32),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(rng.permutation(B * pmax).reshape(B, pmax), jnp.int32),
            jnp.asarray([37, 9], jnp.int32), jnp.asarray([True, True]))
    for fn in (functools.partial(pa.paged_decode_attention, interpret=True),
               pa.gather_decode_attention):
        plain = jax.make_jaxpr(fn)(*args)
        named = jax.make_jaxpr(functools.partial(fn, window=None))(*args)
        assert str(plain) == str(named)
        wide = fn(*args, window=4096)
        for a, b in zip(fn(*args), wide):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, rtol=1e-6)


def test_decode_attention_path_follows_platform_and_shape(monkeypatch):
    """One choice, from what the code can see: the page walk on a TPU
    for shapes it tiles, the gather everywhere else."""
    import importlib

    from ray_tpu.ops import paged_attention as pa

    # ray_tpu.ops re-exports the function under the module's own name.
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    assert pa.decode_attention_path(16, 128) == "gather"  # this is a CPU
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    assert pa.decode_attention_path(16, 128) == "page_walk"
    assert pa.decode_attention_path(16, 64) == "gather"
    assert pa.decode_attention_path(8, 128) == "gather"


@pytest.mark.parametrize("elements,dtype,columns,tokens", [
    # a k and a v row of every KV head of 128: 2 * kv_heads * 128
    (1024, "bfloat16", 257, 512),  # SmallThinker's rings (28 on 4)
    (1024, "bfloat16", 1024, 512),  # ... and its tables of 16k
    (1024, "bfloat16", 129, 512),  # Trinity's rings (32 on 4)
    (1024, "bfloat16", 512, 512),
    (2048, "bfloat16", 128, 256),  # Mistral (32 on 8)
    (4096, "bfloat16", 128, 128),  # OLMoE (16 on 16): what it had
    (8192, "bfloat16", 128, 128),  # never under a lane tile of scores
    (512, "float32", 65, 512),     # the float32 pools of these tests
    (1024, "bfloat16", 20, 256),   # a table shorter than a step:
    (1024, "bfloat16", 8, 128),    # whole pages, a power of two of them,
    (1024, "bfloat16", 4, 64),     # never more than the columns
    (1024, "bfloat16", 1, 16),
    # one latent row for all heads (512 of latent, the rotary key's tile)
    # (51 pages to the megabyte: 64, the power of two nearest in ratio)
    (640, "bfloat16", 1024, 1024),  # Kimi-Linear's and GLM-5.2's 16k
    (640, "bfloat16", 512, 1024),  # JoyAI's 8k
    (640, "bfloat16", 40, 512),    # a table shorter than a step
    (256, "float32", 170, 1024),   # the float32 pools of these tests
    (720, "bfloat16", 1024, 512),  # 45 pages to the megabyte: 32
    (736, "bfloat16", 1024, 512),  # 44 pages
    (704, "bfloat16", 1024, 1024), # 46 pages: 64
])
def test_walk_step_follows_the_bytes_of_a_token(elements, dtype, columns,
                                                tokens):
    """A walk's compute step, from shapes alone: about a megabyte of the
    pool (``elements * itemsize`` bytes a token in one layer), at least
    the 128 lanes of a score tile, a power of two of pages, never longer
    than the table's columns."""
    from ray_tpu.ops import paged_attention as pa

    got = pa.walk_step_tokens(elements * jnp.dtype(dtype).itemsize, 16,
                              columns)
    assert got == tokens
    assert got <= columns * 16 and got % 16 == 0
    assert (got // 16) & (got // 16 - 1) == 0


def _tiny_olmoe():
    """(the configuration dict, the program's cfg, seeded float32
    weights): OLMoE's block at a tiny size, 8 experts top-2, QK-norm,
    built by the benchmark's own builder."""
    import json
    import os

    from benchmark import arch

    with open(os.path.join(os.path.dirname(__file__), "bench_harness",
                           "olmoe_tiny", "config.json")) as f:
        config = json.load(f)
    cfg = arch.program_config(config)
    params = init_params(cfg, jax.random.PRNGKey(3))
    # Norm weights off one, so that a norm left out cannot pass.
    rng = np.random.RandomState(4)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        leaf = params["layers"][name]
        params["layers"][name] = leaf + jnp.asarray(
            rng.uniform(-0.3, 0.3, leaf.shape), leaf.dtype)
    return config, cfg, params


def _reference_logits(config, params, tokens):
    from benchmark import olmoe_reference as reference

    x, _ = reference.hidden(params, tokens, config)
    return jnp.einsum("bsm,mv->bsv", x, params["lm_head"],
                      precision="highest")


def test_tiny_olmoe_has_the_qk_norm_and_the_training_block_uses_it():
    config, cfg, params = _tiny_olmoe()
    assert cfg.qk_norm and cfg.n_experts == 8 and cfg.top_k == 2
    assert params["layers"]["q_norm"].shape == (2, 64)
    assert params["layers"]["k_norm"].shape == (2, 64)
    tokens = jnp.asarray(np.random.RandomState(5).randint(0, 256, (2, 24)))
    logits, _ = forward(params, tokens, cfg)
    expected = _reference_logits(config, params, tokens)
    # float32 on both sides: the order of sums alone differs.
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expected),
                               atol=1e-4, rtol=1e-4)
    flat = dict(params, layers=dict(
        params["layers"], q_norm=jnp.ones_like(params["layers"]["q_norm"])))
    off, _ = forward(flat, tokens, cfg)
    assert float(jnp.abs(off - expected).max()) > 1e-2


def _model(name):
    """(cfg, seeded float32 weights, tokens [B, S] -> the logits of a
    full forward pass that caches nothing): the tiny dense model and the
    tiny mixture of experts against the program's own ``forward``, the
    tiny OLMoE against the benchmark's reference, which shares no code
    with the program."""
    if name == "olmoe":
        config, cfg, params = _tiny_olmoe()
        return cfg, params, lambda t: _reference_logits(config, params, t)
    cfg = LlamaConfig.tiny(moe=name == "moe")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, lambda t: forward(params, t, cfg)[0]


@pytest.mark.parametrize("real_len", [5, 16, 27])
@pytest.mark.parametrize("model", ["dense", "moe", "olmoe"])
def test_paged_prefill_then_decode_equals_the_full_forward(model, real_len):
    """A prompt padded to its bucket through ``paged_prefill``, then
    tokens one at a time through ``paged_decode`` beside idle slots,
    against a FULL forward pass of the same sequence at its exact
    length: logits compared, so a padded prefill equals an exact one at
    the last real position and decode continues from what it cached.
    Tolerance 1e-4: float32 on both sides, the sums in another order; a
    bucket's padding or an idle slot reaching an expert, a norm left
    out or a dropped assignment is off by more than 1e-2."""
    cfg, params, full_forward = _model(model)
    rng = np.random.RandomState(real_len)
    steps, page, slots, slot = 4, 16, 3, 1
    seq = rng.randint(0, 256, real_len + steps)
    bucket = 16 if real_len <= 16 else 32
    expected = np.asarray(full_forward(jnp.asarray(seq[None])))[0]

    cache = PagedKVCache.create(cfg, slots, 8, page, 4)
    pages = [5, 2, 7]                      # the slot's pages, out of order
    table = np.zeros((slots, 4), np.int32)
    table[slot, :3] = pages
    cache = cache._replace(page_table={"full": jnp.asarray(table)})
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :real_len] = seq[:real_len]
    logits, cache, load = paged_prefill(
        params, jnp.asarray(padded), jnp.asarray(real_len, jnp.int32),
        cache, cfg, slot,
        {"full": jnp.asarray(pages[:bucket // page], jnp.int32)})
    np.testing.assert_allclose(np.asarray(logits)[0], expected[real_len - 1],
                               atol=1e-4, rtol=1e-4)
    assert list(np.asarray(cache.lengths)) == [0, real_len, 0]
    per_token = cfg.top_k * cfg.num_layers
    if cfg.n_experts == 0:
        assert load is None
    else:
        # The bucket's padding reached no expert: real tokens x k x layers.
        assert int(load.expert_tokens.sum()) == real_len * per_token
        # (layer, expert) pairs: at least the experts seen in any layer.
        assert int((np.asarray(load.expert_tokens) > 0).sum()) \
            <= int(load.experts_reached) \
            <= min(cfg.n_experts * cfg.num_layers, real_len * per_token)

    active = jnp.asarray(np.arange(slots) == slot)
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        last[slot] = seq[real_len + i]
        logits, cache, load = paged_decode(
            params, jnp.asarray(last), cache, cfg, active=active)
        np.testing.assert_allclose(
            np.asarray(logits)[slot], expected[real_len + i],
            atol=1e-4, rtol=1e-4)
        if cfg.n_experts > 0:
            # One live slot: k experts in each layer, whatever idles.
            assert int(load.expert_tokens.sum()) == per_token
            assert int(load.experts_reached) == per_token
    assert list(np.asarray(cache.lengths)) == [0, real_len + steps, 0]


def test_paged_decode_leaves_an_inactive_slot_as_it_was():
    """Two prefilled slots, one decode step with the second inactive:
    its length and every cell of its pages are what they were, the
    first slot's length is one more and its new row is the only cell of
    either pool that changed."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    page, lens, pages = 16, (5, 9), ([3], [1])
    cache = PagedKVCache.create(cfg, 2, 4, page, 2)
    table = np.zeros((2, 2), np.int32)
    table[0, 0], table[1, 0] = pages[0][0], pages[1][0]
    cache = cache._replace(page_table={"full": jnp.asarray(table)})
    rng = np.random.RandomState(0)
    for slot, n in enumerate(lens):
        prompt = np.zeros((1, page), np.int32)
        prompt[0, :n] = rng.randint(0, 256, n)
        _, cache, _ = paged_prefill(
            params, jnp.asarray(prompt), jnp.asarray(n, jnp.int32), cache,
            cfg, slot, {"full": jnp.asarray(pages[slot], jnp.int32)})
    _, after, _ = paged_decode(
        params, jnp.asarray([5, 9], jnp.int32), cache, cfg,
        active=jnp.asarray([True, False]))
    assert list(np.asarray(after.lengths)) == [lens[0] + 1, lens[1]]
    for old, new in ((cache.k["full"], after.k["full"]),
                     (cache.v["full"], after.v["full"])):
        changed = np.argwhere((np.asarray(old) != np.asarray(new)).any(-1))
        # [layer, kv head, page, row]: slot 0's row 5 of page 3 alone.
        assert {tuple(c[2:]) for c in changed} == {(pages[0][0], lens[0])}
        assert len(changed) == cfg.num_layers * cfg.num_kv_heads


def test_dense_programs_return_no_expert_load():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = PagedKVCache.create(cfg, 2, 4, 16, 2)
    _, _, load = paged_decode(params, jnp.zeros(2, jnp.int32), cache, cfg,
                              active=jnp.asarray([True, False]))
    assert load is None


# ---- latent attention: one row a token for all heads (PR 42) ---------------

_LATENT_CASES = {
    # lengths, active, pages a slot, layers, layer[, pool dtype]. A
    # compute step is as long as the row's bytes and the table's columns
    # say (``walk_step_tokens``): of these float32 rows of 256, 256
    # tokens under 16 columns, 512 under 32, 1,024 under 64 or more.
    "mid_page_and_page_ends": ([37, 0, 255, 16], [True, True, True, True],
                               16, 2, 1),
    "an_inactive_slot": ([37, 200, 90], [True, False, True], 16, 3, 0),
    "all_inactive": ([5, 70], [False, False], 8, 1, 0),
    "over_a_block": ([300, 511, 256], [True, True, True], 32, 2, 1),
    # Under a step, idle, a step to the row, idle, two steps and a part,
    # a step and the first row of the next, idle: the list of steps runs
    # on from a slot's last step to the next walking slot's first.
    "mixed_steps_and_idle_slots": (
        [100, 700, 1023, 5, 2600, 1024, 33],
        [True, False, True, False, True, True, False], 170, 2, 1),
    # The new row's page is the first of its step (the write-back's row
    # offset is 0 in a step that holds one page), its last row and its
    # first; 511 closes a step.
    "last_page_opens_a_step": ([512, 527, 511, 1039], [True] * 4, 66, 1, 0),
    "first_and_last_slot_idle": ([900, 64, 1500, 2047, 10],
                                 [False, True, True, True, False], 128, 3,
                                 2),
    "one_walking_slot_of_many": ([0, 0, 1300, 0, 0],
                                 [False, False, True, False, False], 96, 2,
                                 0),
    # The cells' own row: 640 of bfloat16, steps of 1,024 tokens.
    "bf16_rows_of_640": ([3, 1023, 700, 1024, 2100],
                         [True, True, False, True, True], 140, 2, 1,
                         "bfloat16"),
}


@pytest.mark.parametrize("case", list(_LATENT_CASES))
@pytest.mark.parametrize("path", ["latent_walk", "gather"])
def test_latent_decode_attention_matches_reference(path, case):
    """Both decode attentions over a latent pool (the Pallas latent walk
    in interpret mode, the XLA gather): the new row of each active slot
    lands in ``[layer, page_table[b, len // page], len % page]`` and
    every other cell of the pool is bit-identical (an inactive slot
    writes nothing); the attention is, by hand, every head's softmax of
    ``scale * q . row`` over rows ``0 .. len`` times the rows' first
    ``values``. Contexts end mid-page, on a page's last row, on a
    step's last row and past several steps; pages are walked out of
    order; an inactive slot keeps the length its last request left."""
    from ray_tpu.ops import paged_attention as pa

    lengths, active, pmax, n_layers, layer, *dtype = _LATENT_CASES[case]
    dtype = jnp.dtype(*dtype or ["float32"])
    B, H, page, scale = len(lengths), 8, 16, 0.07
    W, values, tol = (256, 128, 2e-5) if dtype == jnp.float32 else (
        640, 512, 2e-2)
    n_pool = B * pmax
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(B, H, W), dtype)
    new = jnp.asarray(rng.randn(B, W), dtype)
    pool = jnp.asarray(rng.randn(n_layers, n_pool, page, W), dtype)
    table = rng.permutation(n_pool).reshape(B, pmax).astype(np.int32)
    active = np.asarray(active)
    want = np.array(pool)
    for b in np.flatnonzero(active):
        want[layer, table[b, lengths[b] // page], lengths[b] % page] = new[b]
    args = (q, new, pool, jnp.asarray(layer, jnp.int32), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active))
    if path == "latent_walk":
        out, got = pa.paged_latent_decode_attention(
            *args, scale=scale, values=values, interpret=True)
        assert not np.asarray(out, np.float32)[~active].any()
    else:
        out, got = pa.gather_latent_decode_attention(
            *args, scale=scale, values=values)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert out.shape == (B, H, values) and got.dtype == pool.dtype
    for b in np.flatnonzero(active):
        rows = want[layer][table[b]].reshape(pmax * page, W)[
            :lengths[b] + 1].astype(np.float32)
        s = np.asarray(q, np.float32)[b] @ rows.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        ref = (p / p.sum(-1, keepdims=True)) @ rows[:, :values]
        np.testing.assert_allclose(np.asarray(out, np.float32)[b], ref,
                                   atol=tol, rtol=tol)


def _latent_cfg(**changes):
    return LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=24, rope_theta=10_000.0,
        dtype=jnp.float32, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        rope_interleave=True, **changes)


def test_absorbed_attention_is_the_rebuilt_one_on_the_same_weights():
    """One latent layer's weights, 50 tokens. Rebuilt: k and v of every
    head from the rows, causal attention (a prefill's). Absorbed: W_UK
    into the query, every head over the rows themselves, W_UV behind (a
    decode step's), here for the last token over a pool that holds the
    49 before it. The same [H, v_head_dim], to float32's rounding."""
    from ray_tpu.models import llama
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops.attention import mha_attention

    cfg = _latent_cfg()
    assert cfg.latent and cfg.latent_row == 32 + 128
    lp = jax.tree.map(lambda p: p[1],
                      init_params(cfg, jax.random.PRNGKey(2))["layers"])
    T, page = 50, 16
    x = jnp.asarray(np.random.RandomState(0).randn(1, T, 32), jnp.float32)
    q, rows = llama.latent_proj(cfg, lp, x, jnp.arange(T))
    assert q.shape == (1, T, 4, 24) and rows.shape == (1, T, 160)
    assert not np.asarray(rows)[..., 40:].any()          # the lane padding
    k, v = llama.latent_kv(cfg, lp, rows)
    assert k.shape == (1, T, 4, 24) and v.shape == (1, T, 4, 12)
    rebuilt = mha_attention(q, k, v, causal=True)[0, -1]
    # The pool: the first 49 rows in pages 3, 1, 0, 2; the 50th is new.
    table = jnp.asarray([[3, 1, 0, 2]], jnp.int32)
    held = jnp.zeros((64, 160)).at[:T - 1].set(rows[0, :T - 1])
    pool = jnp.zeros((1, 4, page, 160)).at[0, table[0]].set(
        held.reshape(4, page, 160))
    q_lat = llama.latent_absorb_q(cfg, lp, q[:, -1:])
    assert q_lat.shape == (1, 1, 4, 160)
    out, pool = pa.gather_latent_decode_attention(
        q_lat[:, 0], rows[:, -1], pool, jnp.asarray(0), table,
        jnp.asarray([T - 1]), jnp.asarray([True]), scale=24 ** -0.5,
        values=32)
    absorbed = llama.latent_absorb_out(cfg, lp, out[:, None])[0, 0]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(rebuilt),
                               atol=2e-6, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(pool[0, 2, 1]),  # 49 = 3 * 16 + 1
                                  np.asarray(rows[0, -1]))


def test_latent_cache_is_one_pool_of_rows_and_training_raises_by_name():
    from ray_tpu.models import causal_lm_loss
    from ray_tpu.models.llama import kv_layers, layer_runs

    cfg = _latent_cfg()
    assert [tuple(r) for r in layer_runs(cfg)] == [(0, 2, False, "latent", 0)]
    assert kv_layers(cfg) == {"latent": 2}
    cache = PagedKVCache.create(cfg, 3, 12, 16, 4)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "latent": (2, 12, 16, 160)}
    assert cache.v == {} and cache.page_size == 16
    assert cache.page_table["latent"].shape == (3, 4)
    assert PagedKVCache.sizes(cfg, 3, 12, 16, 4) == {"latent": (2, 12, 4)}
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="attention is latent"):
        causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)
    with pytest.raises(ValueError, match="q.k width"):
        layer_runs(_latent_cfg(layer_types=("full", "full")))


def _selecting_cfg():
    """Latent attention under a selection: an indexing layer, a layer
    that shares its selection, an indexing layer."""
    import dataclasses

    return dataclasses.replace(
        _latent_cfg(), num_layers=3, index_topk=24, index_n_heads=2,
        index_head_dim=16, indexer_types=("full", "shared", "full"))


def test_the_pool_of_indexer_keys_rides_on_the_latent_pools_page_table():
    """A second pool, of the indexing layers' keys, beside the latent
    rows: as many pages at the same ids, no table and no free list of
    its own. One reservation a slot covers both; released pages are the
    next slot's in both; a slot reused finds the table row zeroed and
    re-laid; what a page weighs counts both pools' layers."""
    from ray_tpu.models.generation import KVBooks
    from ray_tpu.models.llama import index_offsets, kv_layers, layer_runs

    cfg = _selecting_cfg()
    assert [tuple(r) for r in layer_runs(cfg)] == [
        (0, 1, False, "latent_index", 0), (1, 1, False, "latent_shared", 1),
        (2, 1, False, "latent_index", 2)]
    assert index_offsets(cfg) == (0, 1, 1)
    assert kv_layers(cfg) == {"latent": 3, "index": 2}
    geometry = (cfg, 3, 12, 16, 8)
    assert PagedKVCache.sizes(*geometry) == {"latent": (3, 12, 8),
                                             "index": (2, 12, 0)}
    cache = PagedKVCache.create(*geometry)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "latent": (3, 12, 16, 160), "index": (2, 12, 16, 16)}
    assert cache.v == {} and cache.page_size == 16
    assert set(cache.page_table) == {"latent"}
    assert cache.pools("index") == (cache.k["index"],)
    books = KVBooks(*geometry, cache)
    assert set(books.free) == set(books.tables) == {"latent"}
    reading = books.reading()
    assert reading["kv_row_bytes"] == {"latent": 160 * 4, "index": 16 * 4}
    assert reading["pages"] == {
        "latent": {"layers": 3, "total": 12, "free": 12},
        "index": {"layers": 2, "total": 12, "free": 12}}
    pages, tables = books.reserve(0, 40, 32)            # 3 pages
    assert set(pages) == set(tables) == {"latent"} and len(pages["latent"]) == 2
    first = tables["latent"][0].copy()
    assert np.count_nonzero(first) >= 2 and books.reserve(1, 64, 64)
    assert books.reading()["pages"]["index"]["free"] == 12 - 3 - 4
    # The third slot's 6 pages are not there: nothing is taken.
    assert books.reserve(2, 96, 64) is None
    assert books.reading()["pages"]["latent"]["free"] == 5
    books.account([0, 1], [40, 60])
    counts = books.counts
    assert counts["decode_kv_rows_read"] == 3 * 100
    assert counts["decode_kv_rows_selected"] == 3 * (24 + 24)
    assert counts["kv_page_steps_held"] == (3 + 2) * (3 + 4)
    books.release(0)
    assert not books.tables["latent"][0].any()
    assert books.reading()["pages"]["index"]["free"] == 8
    pages, tables = books.reserve(0, 96, 64)            # the slot reused
    assert len(pages["latent"]) == 4
    assert set(first[:3]) <= set(tables["latent"][0][:6].tolist())
    assert books.reading()["pages"]["latent"]["free"] == 2


# ---- retention layers: a state a slot, no pages -----------------------------


def _state_model():
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
                      rope_theta=10_000.0, dtype=jnp.float32,
                      layer_types=("state",) * 3, qk_norm=True,
                      qk_norm_per_head=True)
    return cfg, init_params(cfg, jax.random.PRNGKey(2))


def _state_prefill(cfg, params, cache, prompt, bucket, slot):
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    return paged_prefill(params, jnp.asarray(padded),
                         jnp.int32(len(prompt)), cache, cfg,
                         jnp.int32(slot), {"state": jnp.zeros((0,), jnp.int32)})


def test_state_cache_is_one_pool_of_slots_with_no_pages():
    from ray_tpu.ops import retention

    cfg, _ = _state_model()
    assert PagedKVCache.sizes(cfg, 4, 99, 16, 8) == {"state": (3, 0, 0)}
    cache = PagedKVCache.create(cfg, 4, 99, 16, 8)
    assert set(cache.k) == {"state"} and cache.v == {}
    # [L, B, Hkv, d/2 + 1 turns, d + 8 rows, d] float32.
    assert cache.k["state"].shape == (3, 4, 2, 9, 24, 16) == \
        retention.state_shape(3, 4, 2, 16)
    assert cache.k["state"].dtype == jnp.float32
    assert cache.page_table["state"].shape == (4, 0)
    assert cache.page_size is None and cache.pools("state") == (
        cache.k["state"],)


@pytest.mark.parametrize("prompt_len", [9, 16, 31])
def test_a_prompt_leaves_the_same_state_in_any_bucket(prompt_len):
    """Padding must not reach the state. The retention itself: the same
    real tokens, the padding masked as ``paged_prefill`` masks it (a
    gate of 1, a key of 0), in a bucket of their own, one twice and one
    four times as long leave the same state and outputs BIT FOR BIT.
    Through the whole model the same to float32's rounding (what differs
    there is XLA's own matmul of 32 rows against one of 64)."""
    from ray_tpu.ops import retention

    ks = jax.random.split(jax.random.PRNGKey(prompt_len), 4)
    real = jnp.arange(128) < prompt_len
    q = jax.random.normal(ks[0], (128, 4, 16))
    k = jnp.where(real[:, None, None],
                  jax.random.normal(ks[1], (128, 2, 16)), 0)
    v = jax.random.normal(ks[2], (128, 2, 16))
    log_g = jnp.where(real[:, None], jax.nn.log_sigmoid(
        jax.random.normal(ks[3], (128, 2)) + 3.0), 0.0)
    outs = [retention.retention_prefill(q[:n], k[:n], v[:n], log_g[:n])
            for n in (32, 64, 128)]
    assert np.asarray(outs[0][1]).any()
    for y, state in outs[1:]:
        assert np.array_equal(np.asarray(state), np.asarray(outs[0][1]))
        assert np.array_equal(np.asarray(y[:prompt_len]),
                              np.asarray(outs[0][0][:prompt_len]))

    cfg, params = _state_model()
    prompt = np.random.default_rng(prompt_len).integers(0, 256, prompt_len)
    states, logits = [], []
    for bucket in (32, 64, 128):
        cache = PagedKVCache.create(cfg, 2, 1, 16, 8)
        out, cache, _ = _state_prefill(cfg, params, cache, prompt, bucket, 1)
        states.append(np.asarray(cache.k["state"]))
        logits.append(np.asarray(out))
        assert int(cache.lengths[1]) == prompt_len
        assert not states[-1][:, 0].any()         # the other slot untouched
    scale = np.abs(states[0]).max()
    assert all(np.abs(states[0] - s).max() < 1e-5 * scale
               for s in states[1:])
    assert all(np.abs(logits[0] - x).max() < 1e-5 for x in logits[1:])


def test_a_slot_reused_after_a_longer_request_carries_nothing_over():
    """Nothing is zeroed at release: the next prefill overwrites the
    slot's state whole. A short request in a slot that just held a long
    one decodes exactly as in a fresh cache."""
    cfg, params = _state_model()
    rng = np.random.default_rng(0)
    long, short = rng.integers(0, 256, 100), rng.integers(0, 256, 11)
    active = jnp.asarray([True, False])

    def decode(cache, n=5):
        rows = []
        for tok in range(n):
            out, cache, _ = paged_decode(
                params, jnp.full((2,), tok, jnp.int32), cache, cfg,
                active=active)
            rows.append(np.asarray(out[0]))
        return np.stack(rows), cache

    used = PagedKVCache.create(cfg, 2, 1, 16, 8)
    _, used, _ = _state_prefill(cfg, params, used, long, 128, 0)
    _, used = decode(used, 7)
    first_used, used, _ = _state_prefill(cfg, params, used, short, 16, 0)
    rows_used, used = decode(used)
    fresh = PagedKVCache.create(cfg, 2, 1, 16, 8)
    first_fresh, fresh, _ = _state_prefill(cfg, params, fresh, short, 16, 0)
    rows_fresh, fresh = decode(fresh)
    assert np.array_equal(np.asarray(first_used), np.asarray(first_fresh))
    assert np.array_equal(rows_used, rows_fresh)
    assert np.array_equal(np.asarray(used.k["state"]),
                          np.asarray(fresh.k["state"]))


def _kernel_inputs(S, H, Hkv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (S, H, 128)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (S, Hkv, 128)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (S, Hkv, 128)).astype(jnp.bfloat16)
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (S, Hkv)) + 4.0)
    return q, k, v, log_g


def test_chunk_scan_kernel_matches_the_xla_path():
    """The Pallas prefill kernel, interpreted: two chunks of a head, so
    the second reads the state the first left. bfloat16 operands to the
    MXU, float32 state: within bfloat16's rounding of the XLA path."""
    from ray_tpu.ops import retention

    q, k, v, log_g = _kernel_inputs(2 * retention.CHUNK, 2, 1)
    want, state = retention.xla_retention_prefill(q, k, v, log_g)
    got, got_state = retention.chunk_scan(q, k, v, log_g, interpret=True)
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) < 0.02 * scale
    assert float(jnp.abs(got_state - state).max()) < 0.01 * float(
        jnp.abs(state).max())


@pytest.mark.parametrize("active", [
    (True, True, True), (False, True, False), (True, False, True),
    (False, False, True), (True, False, False), (False, False, False)],
    ids=lambda a: "".join("x" if s else "-" for s in a))
def test_state_step_kernel_matches_the_xla_path_and_skips_idle_slots(active):
    """The Pallas decode kernel, interpreted: every pattern of idle
    slots before, between and after active ones, and nobody active.
    An idle slot's state is what it was, bit for bit, and so is every
    other layer."""
    from ray_tpu.ops import retention

    q, k, v, log_g = _kernel_inputs(3, 4, 2, seed=1)
    pool = jax.random.normal(jax.random.PRNGKey(7),
                             retention.state_shape(2, 3, 2, 128)) + 3.0
    on = jnp.asarray(active)
    want, want_pool = retention.xla_retention_decode(q, k, v, log_g, pool,
                                                     1, on)
    got, got_pool = retention.state_step(q, k, v, log_g, pool, 1, on,
                                         interpret=True)
    idle = ~np.asarray(active)
    assert np.array_equal(np.asarray(got_pool[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(got_pool[1])[idle],
                          np.asarray(pool[1])[idle])
    assert np.allclose(got_pool, want_pool, rtol=1e-6, atol=1e-6)
    busy = np.asarray(active)
    assert np.allclose(np.asarray(got, np.float32)[busy],
                       np.asarray(want, np.float32)[busy], rtol=0.02,
                       atol=0.02)


# ---- delta-rule layers among latent ones (PR 62) ----------------------------

def _hybrid_model():
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=4, head_dim=24, dtype=jnp.float32,
        q_lora_rank=0, latent_rope=False, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        layer_types=("delta", "delta", "latent", "delta"),
        delta_heads=4, delta_head_dim=16, delta_conv=4)
    return cfg, init_params(cfg, jax.random.PRNGKey(2))


def _hybrid_prefill(cfg, params, cache, prompt, bucket, slot, pages):
    padded = np.full((1, bucket), 9, np.int32)
    padded[0, :len(prompt)] = prompt
    return paged_prefill(
        params, jnp.asarray(padded), jnp.int32(len(prompt)), cache, cfg,
        jnp.int32(slot), {"latent": jnp.asarray(pages, jnp.int32),
                          "delta": jnp.zeros((0,), jnp.int32)})


def test_delta_cache_is_two_pools_of_slots_beside_the_latent_pool():
    cfg, _ = _hybrid_model()
    assert PagedKVCache.sizes(cfg, 4, 99, 16, 8) == {
        "delta": (3, 0, 0), "latent": (1, 99, 8)}
    cache = PagedKVCache.create(cfg, 4, 99, 16, 8)
    assert set(cache.k) == {"delta", "latent"} and set(cache.v) == {"delta"}
    # States [L, B, H, D, D] float32; histories [L, taps - 1, B, 3 H D].
    assert cache.k["delta"].shape == (3, 4, 4, 16, 16)
    assert cache.k["delta"].dtype == jnp.float32
    assert cache.v["delta"].shape == (3, 3, 4, 3 * 64)
    assert cache.k["latent"].shape == (1, 99, 16, 32 + 128)
    assert cache.page_table["delta"].shape == (4, 0)
    assert cache.page_table["latent"].shape == (4, 8)
    assert cache.page_size == 16
    assert cache.pools("delta") == (cache.k["delta"], cache.v["delta"])


@pytest.mark.parametrize("prompt_len", [2, 9, 31])
def test_a_prompt_leaves_the_same_delta_pools_in_any_bucket(prompt_len):
    """Padding must reach neither the state nor the convolution's
    history: the same prompt in a bucket of 32, 64 and 128 (the last a
    whole chunk of the delta prefill) leaves the same states, the same
    three history rows, those of the last REAL tokens (zeros where the
    prompt is shorter than the history), and the same logits, and
    touches no other slot."""
    cfg, params = _hybrid_model()
    prompt = np.random.default_rng(prompt_len).integers(0, 256, prompt_len)
    got = []
    for bucket in (32, 64, 128):
        cache = PagedKVCache.create(cfg, 2, 16, 16, 8)
        out, cache, _ = _hybrid_prefill(cfg, params, cache, prompt, bucket,
                                        1, np.arange(bucket // 16))
        assert int(cache.lengths[1]) == prompt_len
        assert not np.asarray(cache.k["delta"])[:, 0].any()
        assert not np.asarray(cache.v["delta"])[:, :, 0].any()
        got.append([np.asarray(x) for x in (
            cache.k["delta"][:, 1], cache.v["delta"][:, :, 1], out)])
    for states, history, logits in got[1:]:
        assert np.abs(states - got[0][0]).max() < 1e-5
        assert np.abs(history - got[0][1]).max() < 1e-5
        assert np.abs(logits - got[0][2]).max() < 1e-5
    history = got[0][1]
    assert history[:, -min(prompt_len, 3):].any()
    assert not history[:, :max(3 - prompt_len, 0)].any()


# ---- a looped model: the stack run several times over one set of weights ---

def _looped(kinds, passes):
    """(cfg, weights, the same weights as ONE stack of an all-"full"
    model for ``forward``, which takes no stack in runs)."""
    cfg = dataclasses.replace(
        LlamaConfig.tiny(), num_layers=len(kinds), num_kv_heads=4,
        post_norms=True, passes=passes,
        layer_types=None if set(kinds) == {"full"} else kinds,
        sliding_window=None if set(kinds) == {"full"} else 32)
    params = init_params(cfg, jax.random.PRNGKey(2))
    stacks = llama.layer_stacks(params)
    flat = {**params, "layers": jax.tree.map(
        lambda *leaves: jnp.concatenate(leaves), *stacks)}
    return cfg, params, dataclasses.replace(
        cfg, layer_types=None, sliding_window=None), flat


@pytest.mark.parametrize("kinds,passes", [
    (("full", "full", "full"), 1), (("full", "full", "full"), 2),
    (("full", "full", "full"), 3), (("window", "full", "window"), 2)],
    ids=["one-pass", "two-passes", "three-passes", "window-and-full"])
def test_a_looped_stack_through_the_cache_equals_the_full_forward(kinds,
                                                                  passes):
    """``passes`` walks of the same stacked weights, ``final_norm``
    behind each: prefill then decode through pools ``passes`` times as
    deep as the stack, each pass in layers of its own, against
    ``forward``, which runs the loop and caches nothing. One pass is the
    program it always was: a pool as deep as the stack and one layer
    scan, no scan around it. The window layers' context stays under the
    window (20 + 4 of 32), where a window layer IS a full one and
    ``forward`` (which takes no window) can say what is right: the case
    holds the two pools' places under the loop, not the window's mask."""
    cfg, params, flat_cfg, flat = _looped(kinds, passes)
    per_pass = {kind: kinds.count(kind) for kind in dict.fromkeys(kinds)}
    assert llama.kv_layers_a_pass(cfg) == per_pass
    assert llama.kv_layers(cfg) == {
        kind: n * passes for kind, n in per_pass.items()}
    rng = np.random.RandomState(passes)
    real_len, steps, page, slots, slot = 20, 4, 16, 3, 1
    seq = rng.randint(0, 256, real_len + steps)
    expected = np.asarray(
        forward(flat, jnp.asarray(seq[None]), flat_cfg)[0])[0]

    cache = PagedKVCache.create(cfg, slots, 8, page, 4)
    assert {kind: pool.shape[0] for kind, pool in cache.k.items()} == \
        llama.kv_layers(cfg)
    sizes = PagedKVCache.sizes(cfg, slots, 8, page, 4)
    tables, pages = {}, {}
    for kind, (_, pool_pages, columns) in sizes.items():
        ids = np.arange(pool_pages)[::-1][slot * columns:][:columns]
        tables[kind] = np.zeros((slots, columns), np.int32)
        tables[kind][slot] = ids
        pages[kind] = jnp.asarray(ids[:min(32 // page, columns)], jnp.int32)
    cache = cache._replace(page_table={
        kind: jnp.asarray(table) for kind, table in tables.items()})
    padded = np.zeros((1, 32), np.int32)
    padded[0, :real_len] = seq[:real_len]
    logits, cache, load = paged_prefill(
        params, jnp.asarray(padded), jnp.asarray(real_len, jnp.int32),
        cache, cfg, slot, pages)
    assert load is None
    np.testing.assert_allclose(np.asarray(logits)[0], expected[real_len - 1],
                               atol=1e-4, rtol=1e-4)
    active = jnp.asarray(np.arange(slots) == slot)
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        last[slot] = seq[real_len + i]
        logits, cache, _ = paged_decode(
            params, jnp.asarray(last), cache, cfg, active=active)
        np.testing.assert_allclose(
            np.asarray(logits)[slot], expected[real_len + i],
            atol=1e-4, rtol=1e-4)
    # Every (pass, layer) of a pool kept rows of its own.
    for kind, pool in cache.k.items():
        filled = np.abs(np.asarray(pool)).sum(axis=(1, 2, 3, 4)) > 0
        assert filled.all() and len(filled) == per_pass[kind] * passes
    scans = str(jax.make_jaxpr(lambda params, cache: paged_decode(
        params, jnp.zeros(slots, jnp.int32), cache, cfg, active=active))(
            params, cache)).count("scan[")
    runs = len(llama.layer_runs(cfg))
    assert scans == (runs if passes == 1 else runs + 1)


def test_another_number_of_passes_is_another_model():
    """The same weights walked twice and three times differ by far more
    than the tolerance: the loop is in the arithmetic, not beside it."""
    two, _, flat_cfg, flat = _looped(("full",) * 3, 2)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (1, 24)))
    a = forward(flat, tokens, flat_cfg)[0]
    b = forward(flat, tokens, dataclasses.replace(flat_cfg, passes=3))[0]
    assert float(jnp.abs(a - b).max()) > 1e-2


@pytest.mark.parametrize("changes,says", [
    ({"layer_types": ("state",) * 2}, "retention state a slot a PASS"),
    ({"layer_types": ("latent", "delta"), "kv_lora_rank": 32,
      "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
      "delta_heads": 2, "delta_head_dim": 16, "delta_conv": 4},
     "delta-rule state"),
    ({"kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 16}, "latent row a token a PASS"),
    ({"n_experts": 4}, "expert-load counters a pass"),
], ids=["state", "delta", "latent", "experts"])
def test_a_loop_over_what_has_no_place_a_pass_is_refused_by_name(changes,
                                                                 says):
    cfg = dataclasses.replace(LlamaConfig.tiny(), passes=2, **changes)
    with pytest.raises(NotImplementedError, match=says):
        llama.layer_runs(cfg)
    with pytest.raises(NotImplementedError, match="passes=2"):
        PagedKVCache.sizes(cfg, 2, 8, 16, 4)


def test_a_loop_is_served_and_not_trained_and_a_gate_needs_two_passes():
    cfg = dataclasses.replace(LlamaConfig.tiny(), passes=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="looped model"):
        llama.causal_lm_loss(params, jnp.zeros((1, 9), jnp.int32), cfg)
    for bad in ({"passes": 0}, {"passes": 1, "exit_gate": True}):
        with pytest.raises(ValueError, match="passes is"):
            llama.layer_runs(dataclasses.replace(LlamaConfig.tiny(), **bad))
    # The gate's two leaves are drawn last: a seed's other weights are
    # what they are without it.
    gated = init_params(dataclasses.replace(cfg, exit_gate=True),
                        jax.random.PRNGKey(0))
    assert sorted(set(gated) - set(params)) == ["exit_b", "exit_w"]
    assert (gated["exit_w"].shape, gated["exit_b"].shape) == ((64, 1), (1,))
    np.testing.assert_array_equal(np.asarray(gated["lm_head"]),
                                  np.asarray(params["lm_head"]))
    # The distribution, by hand: lambda = 1/2 everywhere.
    p = np.asarray(llama.exit_distribution(jnp.zeros((2, 4))))
    np.testing.assert_allclose(p, [[0.5, 0.25, 0.125, 0.125]] * 2)
