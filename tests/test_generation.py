"""KV-cache decode correctness: cached generation must match the naive
full-recompute argmax loop exactly (greedy)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, forward, init_params  # noqa: E402
from ray_tpu.models.generation import (  # noqa: E402
    KVCache,
    forward_with_cache,
    generate,
)


def _naive_greedy(params, prompt, cfg, n):
    seq = prompt
    out = []
    for _ in range(n):
        logits, _ = forward(params, seq, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def test_cached_prefill_matches_forward():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 8)))
    full_logits, _ = forward(params, prompt, cfg)
    cache = KVCache.create(cfg, 2, 32)
    cached_logits, cache = forward_with_cache(params, prompt, cache, cfg)
    np.testing.assert_allclose(
        np.asarray(cached_logits), np.asarray(full_logits[:, -1]),
        atol=1e-4, rtol=1e-4,
    )
    assert list(np.asarray(cache.lengths)) == [8, 8]


@pytest.mark.slow
def test_generate_matches_naive():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 6)))
    expected = _naive_greedy(params, prompt, cfg, 5)
    got = generate(params, prompt, cfg, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


def test_decode_respects_active_mask():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = KVCache.create(cfg, 2, 16)
    prompt = jnp.asarray(np.random.RandomState(2).randint(0, 256, (2, 4)))
    _, cache = forward_with_cache(params, prompt, cache, cfg)
    tok = jnp.asarray([[5], [9]], dtype=jnp.int32)
    active = jnp.asarray([True, False])
    _, cache2 = forward_with_cache(params, tok, cache, cfg, active=active)
    assert list(np.asarray(cache2.lengths)) == [5, 4]
    # Inactive slot's cache rows untouched.
    np.testing.assert_array_equal(
        np.asarray(cache2.k[:, 1]), np.asarray(cache.k[:, 1])
    )


@pytest.mark.slow
def test_moe_cached_decode_matches_naive():
    """MoE models decode through the KV cache (r1 gap: generation.py
    raised NotImplementedError for MoE). The dispatch drops no token,
    so full-sequence and incremental evaluation agree."""
    cfg = LlamaConfig.tiny(moe=True)
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompt = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 6)))
    naive = _naive_greedy(params, prompt, cfg, 5)
    out = generate(params, prompt, cfg, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))


def test_bucketed_prefill_matches_exact():
    """Padded (bucketed) prefill with last_index/append_len produces the
    same logits and cache lengths as exact-length prefill."""
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(2)
    real_len = 5
    prompt = jnp.asarray(rs.randint(0, 256, (1, real_len)))
    exact_logits, exact_cache = forward_with_cache(
        params, prompt, KVCache.create(cfg, 1, 32), cfg
    )
    bucket = 8
    padded = jnp.concatenate(
        [prompt, jnp.zeros((1, bucket - real_len), jnp.int32)], axis=1
    )
    padded_logits, padded_cache = forward_with_cache(
        params, padded, KVCache.create(cfg, 1, 32), cfg,
        last_index=jnp.asarray([real_len - 1]),
        append_len=jnp.asarray(real_len),
    )
    np.testing.assert_allclose(
        np.asarray(padded_logits), np.asarray(exact_logits),
        atol=1e-4, rtol=1e-4,
    )
    assert int(padded_cache.lengths[0]) == real_len
    # Decode continues identically from either cache.
    nxt = jnp.argmax(exact_logits, -1).astype(jnp.int32)[:, None]
    l1, _ = forward_with_cache(params, nxt, exact_cache, cfg)
    l2, _ = forward_with_cache(params, nxt, padded_cache, cfg)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=1e-4, rtol=1e-4)


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
# layer decoded). Pages are 16 tokens and the kernel's block 8 pages, so
# 10 pages a slot make a second, partly filled block. ``lengths[b]`` is
# where the new row goes: 16 is the first row of a fresh page, 128 and
# 256 the first of a fresh block, 159 of 10 pages the last cell of the
# slot's last page.
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


@pytest.mark.parametrize("real_len", [5, 16, 27])
def test_tiny_olmoe_paged_prefill_then_decode_equals_the_reference(real_len):
    """A prompt padded to its bucket through ``paged_prefill``, then
    tokens one at a time through ``paged_decode`` beside idle slots,
    against the float32 reference's FULL forward pass of the same
    sequence: logits compared. Tolerance 1e-4: float32 on both sides,
    the sums in another order; a bucket's padding or an idle slot
    reaching an expert, a norm left out or a dropped assignment is off
    by more than 1e-2."""
    from ray_tpu.models.generation import (
        PagedKVCache, paged_decode, paged_prefill)

    config, cfg, params = _tiny_olmoe()
    rng = np.random.RandomState(real_len)
    steps, page, slots, slot = 4, 16, 3, 1
    seq = rng.randint(0, 256, real_len + steps)
    bucket = 16 if real_len <= 16 else 32
    expected = np.asarray(_reference_logits(
        config, params, jnp.asarray(seq[None])))[0]

    cache = PagedKVCache.create(cfg, slots, 8, page, 4)
    pages = [5, 2, 7]                      # the slot's pages, out of order
    table = np.zeros((slots, 4), np.int32)
    table[slot, :3] = pages
    cache = cache._replace(page_table=jnp.asarray(table))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :real_len] = seq[:real_len]
    logits, cache, load = paged_prefill(
        params, jnp.asarray(padded), jnp.asarray(real_len, jnp.int32),
        cache, cfg, slot, jnp.asarray(pages[:bucket // page], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits)[0], expected[real_len - 1],
                               atol=1e-4, rtol=1e-4)
    # The bucket's padding reached no expert: real tokens x k x layers.
    assert int(load.expert_tokens.sum()) == real_len * 2 * 2
    # (layer, expert) pairs: at least the experts seen in any layer.
    assert int((np.asarray(load.expert_tokens) > 0).sum()) \
        <= int(load.experts_reached) <= min(16, real_len * 2 * 2)

    active = jnp.asarray(np.arange(slots) == slot)
    for i in range(steps):
        last = np.zeros(slots, np.int32)
        last[slot] = seq[real_len + i]
        logits, cache, load = paged_decode(
            params, jnp.asarray(last), cache, cfg, active=active)
        np.testing.assert_allclose(
            np.asarray(logits)[slot], expected[real_len + i],
            atol=1e-4, rtol=1e-4)
        # One live slot: 2 experts in each of 2 layers, whatever idles.
        assert int(load.expert_tokens.sum()) == 4
        assert int(load.experts_reached) == 4
    assert int(cache.lengths[slot]) == real_len + steps


def test_dense_programs_return_no_expert_load():
    from ray_tpu.models.generation import PagedKVCache, paged_decode

    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = PagedKVCache.create(cfg, 2, 4, 16, 2)
    _, _, load = paged_decode(params, jnp.zeros(2, jnp.int32), cache, cfg,
                              active=jnp.asarray([True, False]))
    assert load is None
