"""``llama.layer_runs`` and the pools of a model with "linear" layers
among "full" ones and a selection of blocks (PR 70), and the runs its
"full" pool is handed out in (PR 71)."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import LlamaConfig, init_params  # noqa: E402
from ray_tpu.models.generation import KVBooks, PagedKVCache  # noqa: E402
from ray_tpu.models.llama import kv_layers, layer_runs  # noqa: E402
from ray_tpu.ops.block_attention import BlockSizes  # noqa: E402

SIZES = BlockSizes(32, 16, 64, 1, 64, 4, 320)


def _cfg(**changes):
    return dataclasses.replace(LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=8, dtype=jnp.float32,
        layer_types=("full", "linear", "linear", "full"), linear_heads=2,
        linear_head_dim=16, linear_decay_layers=(3, 8), block_select=SIZES,
        rope_full_layers=False, qk_norm=True, qk_norm_per_head=True,
        attn_gate=True), **changes)


def test_runs_and_pools_of_a_selecting_hybrid():
    cfg = _cfg()
    assert [(r.kind, r.n, r.kv_offset) for r in layer_runs(cfg)] == [
        ("blocks", 1, 0), ("linear", 2, 0), ("blocks", 1, 1)]
    assert kv_layers(cfg) == {"full": 2, "linear": 2, "mean": 2}
    cache = PagedKVCache.create(cfg, 3, 12, 16, 4)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "full": (2, 2, 12, 16, 8), "linear": (2, 3, 2, 16, 16),
        "mean": (2, 12, 16)}
    assert {k: v.shape for k, v in cache.v.items()} == {
        "full": (2, 2, 12, 16, 8), "mean": (2, 3, 16)}
    assert cache.k["linear"].dtype == cache.v["mean"].dtype == jnp.float32
    # The page means ride on the k/v pool's table: no table of their own.
    assert set(cache.page_table) == {"full", "linear"}
    assert cache.page_table["linear"].shape == (3, 0)
    assert PagedKVCache.sizes(cfg, 3, 12, 16, 4) == {
        "full": (2, 12, 4), "linear": (2, 0, 0), "mean": (2, 12, 0)}
    books = KVBooks(cfg, 3, 12, 16, 4, cache)
    pages, _ = books.reserve(0, 40, 32)
    assert set(pages) == {"full", "linear"} and pages["linear"] == []
    # Three pages of context are one run of four; the bucket takes two.
    assert pages["full"] == [8, 9] and books.free["full"] == [0, 4]
    assert books.tables["full"][0].tolist() == [8, 9, 10, 11]
    assert books.reading()["pages"]["full"]["free"] == 8
    books.release(0)
    assert sorted(books.free["full"]) == [0, 4, 8]
    assert books.reading()["free_pages"] == 12


def _books(cfg, slots, total, columns):
    geometry = (cfg, slots, total, 16, columns)
    return KVBooks(*geometry, jax.eval_shape(
        lambda: PagedKVCache.create(*geometry)))


def _rounds(books, seed, rounds=300):
    """Seeded reserve/release rounds of mixed lengths and buckets over
    ``books``' slots; yields (slot, tokens, bucket, what ``reserve``
    gave) after each reservation tried and (slot, None, None, None)
    after each release."""
    rng = np.random.default_rng(seed)
    slots = books.tables["full"].shape[0]
    held = set()
    for _ in range(rounds):
        free = sorted(set(range(slots)) - held)
        if free and (not held or rng.random() < 0.6):
            slot = int(rng.choice(free))
            tokens = int(rng.integers(1, 520))
            bucket = int(rng.choice([16, 64, 256, 512]))
            got = books.reserve(slot, tokens, bucket)
            if got is not None:
                held.add(slot)
            yield slot, tokens, bucket, got
        else:
            slot = int(rng.choice(sorted(held)))
            held.discard(slot)
            books.release(slot)
            yield slot, None, None, None
    for slot in sorted(held):
        books.release(slot)
        yield slot, None, None, None


def test_a_selecting_models_pages_are_aligned_ascending_runs():
    """The contract the block walk reads by: after any history of
    reservations and releases every block of every slot's table is one
    aligned ascending run of ``ratio`` page ids, no page is held twice,
    a pool that is short takes nothing, and everything comes back."""
    books = _books(_cfg(), 5, 90, 33)     # 22 runs; 2 pages over; 8 runs
    everything = sorted(books.free["full"])     # and a page a table
    assert everything == list(range(0, 88, 4))
    refused = 0
    for slot, tokens, bucket, got in _rounds(books, 0):
        if tokens is None:
            continue
        before = sorted(books.free["full"])
        runs = -(-min(max(bucket // 16, -(-tokens // 16)), 33) // 4)
        if got is None:
            refused += 1
            assert runs > len(before) and slot not in books._pages
            continue
        pages, tables = got
        row = books.tables["full"][slot]
        ids = np.asarray(books._pages[slot]["full"])
        assert len(ids) == 4 * runs
        firsts = ids.reshape(-1, 4)[:, 0]
        assert (firsts % 4 == 0).all()
        np.testing.assert_array_equal(ids, (firsts[:, None]
                                            + np.arange(4)).reshape(-1))
        # The table's 33 columns cut the ninth run to its first page.
        np.testing.assert_array_equal(row[:len(ids)], ids[:33])
        assert not row[len(ids):].any()
        assert pages["full"] == ids[:bucket // 16].tolist()
        out = [p for held in books._pages.values() for p in held["full"]]
        free = [p + i for p in books.free["full"] for i in range(4)]
        assert sorted(out + free) == list(range(88))
    assert refused > 10
    assert sorted(books.free["full"]) == everything
    assert not books.tables["full"].any()


def test_a_pool_short_by_one_run_refuses_and_takes_nothing():
    books = _books(_cfg(), 3, 90, 32)
    assert books.reserve(0, 512, 16) is not None        # 8 of 22 runs
    assert books.reserve(1, 16 * 4 * 7, 16) is not None     # 7 more
    free = list(books.free["full"])
    assert len(free) == 7
    # Seven runs and one page: eight runs.
    assert books.reserve(2, 16 * 4 * 7 + 1, 16) is None
    assert books.free["full"] == free and 2 not in books._pages
    assert not books.tables["full"][2].any()
    assert books.reserve(2, 16 * 4 * 7, 16) is not None
    assert books.free["full"] == [] and books.reading()["free_pages"] == 0
    # What no release could ever give: the pool's whole runs are 88 pages.
    small = _books(_cfg(), 3, 30, 32)
    assert small.refusal(16 * 28, 16) is None
    says = small.refusal(16 * 28 + 1, 16)
    assert "needs 32 pages" in says and "has only 28" in says
    assert "runs of 4" in says and "2 of its 30" in says


def test_a_model_that_selects_no_blocks_allocates_page_by_page_as_before():
    """The same rounds over a dense model: a run is one page, and every
    reservation gives the ids the parent's allocator gave, single pages
    popped off the end of one free list."""
    books = _books(LlamaConfig.tiny(), 5, 90, 33)
    assert books.free["full"] == list(range(90))
    free = list(range(90))
    mirror = {}
    for slot, tokens, bucket, got in _rounds(books, 0):
        if tokens is None:
            free.extend(mirror.pop(slot))
            continue
        need = min(max(bucket // 16, -(-tokens // 16)), 33)
        if got is None:
            assert need > len(free)
            continue
        mirror[slot] = [free.pop() for _ in range(need)]
        assert books._pages[slot]["full"] == mirror[slot]
        assert books.free["full"] == free
        assert got[0]["full"] == mirror[slot][:bucket // 16]
        assert books.refusal(tokens, bucket) is None
    assert sorted(free) == list(range(90)) == sorted(books.free["full"])
    says = _books(LlamaConfig.tiny(), 3, 30, 32).refusal(16 * 31, 16)
    assert "needs 31 pages" in says and "has only 30" in says
    assert "runs" not in says


def test_a_linear_layers_weights_are_its_own_heads_and_decays():
    from ray_tpu.ops.lightning_attention import log_decays

    cfg = _cfg()
    blocks, linear, _ = init_params(cfg, jax.random.PRNGKey(0))["layers"]
    assert blocks["wk"].shape == (1, 32, 2, 8)
    assert linear["wk"].shape == linear["wq"].shape == (2, 32, 2, 16)
    assert linear["o_norm"].shape == (2, 16) and "o_norm" not in blocks
    # Layers 1 and 2 here are published layers 4 and 5 of 8.
    assert jnp.array_equal(linear["log_decay"], jnp.stack(
        [log_decays(2, 4, 8), log_decays(2, 5, 8)]))


@pytest.mark.parametrize("changes,says", [
    (dict(layer_types=("full", "state", "state", "full")), "power retention"),
    (dict(layer_types=("full", "delta", "linear", "full")),
     "delta-rule layer beside k/v rows"),
    (dict(layer_types=("window", "linear", "linear", "full"),
          sliding_window=32), "stands beside"),
    (dict(linear_heads=0), "linear_heads"),
    (dict(linear_decay_layers=None), "linear_decay_layers"),
    (dict(layer_types=("linear",) * 4), "block_select"),
    (dict(layer_types=("full", "scan", "linear", "full")), "must name"),
    (dict(passes=2), "Lightning state a slot a PASS"),
])
def test_what_cannot_be_run_is_refused_by_mechanism(changes, says):
    with pytest.raises((ValueError, NotImplementedError), match=says):
        layer_runs(_cfg(**changes))


def test_older_models_runs_are_what_they_were():
    tiny = LlamaConfig.tiny()
    assert [(r.kind, r.n) for r in layer_runs(tiny)] == [("full", 2)]
    mixed = dataclasses.replace(tiny, num_layers=3, sliding_window=8,
                                layer_types=("window", "full", "window"))
    assert [r.kind for r in layer_runs(mixed)] == ["window", "full", "window"]
    with pytest.raises(ValueError, match="sliding_window"):
        layer_runs(dataclasses.replace(mixed, sliding_window=None))
    assert kv_layers(tiny) == {"full": 2}
