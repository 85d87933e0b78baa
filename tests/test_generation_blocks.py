"""``llama.layer_runs`` and the pools of a model with "linear" layers
among "full" ones and a selection of blocks (PR 70)."""

import dataclasses

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
    assert len(pages["full"]) == 2 and len(books.free["full"]) == 9
    books.release(0)
    assert len(books.free["full"]) == 12


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
