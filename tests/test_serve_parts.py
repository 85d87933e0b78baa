"""The serving engine's owners, each alone (``serve/llm.py``'s module
docstring draws them): the scheduler against fakes for the other two,
the cache's books (``models/generation.py:KVBooks``) over the four tiny
models' pools, and what ``serve/llm.py`` may not name."""

import ast
import json
import os
import threading

import pytest

from ray_tpu.serve import llm

HERE = os.path.dirname(os.path.abspath(__file__))


# ---- (a) the scheduler: requests only, no engine, no jax -------------------

class FakeBooks:
    """What ``_Scheduler`` asks of the books, written down."""

    def __init__(self):
        self.refuse = None     # refusal()'s answer
        self.short = 0         # reserve() says None this many times
        self.calls = []

    def refusal(self, tokens, bucket):
        return self.refuse

    def reserve(self, slot, tokens, bucket):
        self.calls.append(("reserve", slot, tokens, bucket))
        if self.short:
            self.short -= 1
            return None
        return {"pages": [slot]}, "tables"

    def release(self, slot):
        self.calls.append(("release", slot))

    def account(self, slots, contexts):
        self.calls.append(("account", sorted(slots), sorted(contexts)))

    def reset(self):
        self.calls.append(("reset",))

    def of(self, name):
        return [call[1:] for call in self.calls if call[0] == name]


@pytest.fixture
def scheduler():
    books = FakeBooks()
    return llm._Scheduler(books, threading.Lock(), max_batch=2, max_len=64,
                          min_bucket=16), books


def _admit(scheduler, first, *args, **kwargs):
    """Submit, pick and answer the prefill with ``first``; the slot."""
    req = scheduler.submit(*args, **kwargs)
    slot, prompt, bucket, held = scheduler.pick()
    assert prompt == req.prompt and bucket == req.bucket
    scheduler.first_token(first)
    return slot, req


def test_scheduler_leaves_a_slot_one_short_of_its_count_out_of_the_next_step(
        scheduler):
    """Who decodes in the step queued next counts the token in flight:
    a slot whose last token is on its way is not in the step behind it,
    before that token is read."""
    scheduler, books = scheduler
    a, req_a = _admit(scheduler, 5, [1, 2, 3], 2, None, "a")
    b, req_b = _admit(scheduler, 6, [4, 5], 5, None, "b")
    first = scheduler.next_step(None)
    assert set(first.slots) == {a, b} and not first.ahead
    second = scheduler.next_step(first)
    assert set(second.slots) == {b} and second.ahead
    tokens = {a: 7, b: 8}
    scheduler.emit(first, tokens, second)
    assert req_a.result(timeout=1) == [5, 7] and req_b.output == [6, 8]
    # It ended by its count with no step queued for it: released at once.
    assert books.of("release") == [(a,)]
    assert books.of("account") == [(sorted([a, b]), [2 + 1, 3 + 1])]
    reading = scheduler.reading({})
    assert (reading["decode_steps"], reading["decode_slot_steps"],
            reading["decode_steps_ahead"], reading["finished"]) == (1, 2, 0, 1)
    assert (reading["active_slots"], reading["free_slots"]) == (1, 1)
    assert reading["stream"]["tokens_emitted"] == 4


def test_scheduler_drops_the_step_queued_for_a_slot_that_ended_on_eos(
        scheduler):
    """``eos_token`` is known only at the read: the slot is dropped
    from the step already queued, released when that one is read, and
    its token there is not emitted."""
    scheduler, books = scheduler
    slot, req = _admit(scheduler, 3, [1, 2, 3], 8, 7, None)
    first = scheduler.next_step(None)
    second = scheduler.next_step(first)
    assert set(second.slots) == {slot}
    scheduler.emit(first, {slot: 7}, second)
    assert req.result(timeout=1) == [3, 7]
    assert second.dropped == [slot] and books.of("release") == []
    assert scheduler.reading({})["free_slots"] == 1       # of two: held yet
    assert scheduler.next_step(second) is None
    scheduler.emit(second, {slot: 9}, None)
    assert req.output == [3, 7] and list(req.tokens(timeout=1)) == [3, 7]
    assert books.of("release") == [(slot,)]
    reading = scheduler.reading({})
    assert reading["decode_slot_steps_discarded"] == 1
    assert reading["decode_slot_steps"] == reading["decode_steps"] == 2
    assert reading["stream"]["tokens_emitted"] == 2
    assert (reading["free_slots"], reading["finished"]) == (2, 1)


def test_scheduler_keeps_a_request_the_books_refused_first_in_line(scheduler):
    scheduler, books = scheduler
    first = scheduler.submit([1, 2, 3], 4, None, "first")
    scheduler.submit([4, 5], 4, None, "second")
    books.short = 2
    assert scheduler.pick() is None and scheduler.pick() is None
    reading = scheduler.reading({})
    assert (reading["page_waits"], reading["queued"], reading["admitted"],
            reading["free_slots"]) == (2, 2, 0, 2)
    slot, prompt, bucket, held = scheduler.pick()
    assert prompt == [1, 2, 3] and first.t_admit is not None
    # The same question each time: the context's tokens, the bucket.
    assert books.of("reserve") == [(slot, 3 + 4, 16)] * 3
    assert held == ({"pages": [slot]}, "tables")
    scheduler.prefill_failed(RuntimeError("fell over"))
    with pytest.raises(RuntimeError, match="fell over"):
        first.result(timeout=1)
    assert books.of("release") == [(slot,)]
    assert scheduler.pick()[1] == [4, 5]


@pytest.mark.parametrize("why", ["books", "max_len"])
def test_scheduler_refuses_at_submit_what_could_never_run(scheduler, why):
    scheduler, books = scheduler
    books.refuse = "request needs 9 pages but the pool has only 8"
    prompt = [1] * (10 if why == "books" else 61)
    with pytest.raises(ValueError, match="only 8" if why == "books"
                       else "max_len"):
        scheduler.submit(prompt, 4, None, None)
    reading = scheduler.reading({})
    assert reading["submitted"] == reading["queued"] == 0
    assert scheduler.pick() is None


def test_scheduler_reset_fails_open_requests_once_and_frees_every_slot(
        scheduler):
    scheduler, books = scheduler
    _, a = _admit(scheduler, 5, [1, 2, 3], 9, None, None)
    _, b = _admit(scheduler, 6, [4, 5], 9, None, None)
    scheduler.reset(RuntimeError("decode fell over"))
    for req in (a, b):
        with pytest.raises(RuntimeError, match="cache reset.*fell over"):
            req.result(timeout=1)
    reading = scheduler.reading({})
    assert (reading["failed"], reading["active_slots"],
            reading["free_slots"]) == (2, 0, 2)
    assert books.of("reset") == [()] and scheduler.next_step(None) is None


# ---- (b) the books: pages and holdings only --------------------------------

def _tiny(name):
    from benchmark import arch
    from ray_tpu.models import LlamaConfig

    if name == "tiny_model":
        return LlamaConfig.tiny()
    with open(os.path.join(HERE, "bench_harness", name, "config.json")) as f:
        return arch.program_config(json.load(f))


# Fixture of tests/conftest.py -> the benchmark's tiny preset.
MODELS = {"tiny_model": "tiny_model", "window_model": "trinity_tiny",
          "latent_model": "joyai_tiny", "state_model": "brumby_tiny"}
BATCH, TOTAL, PAGE, MAX_LEN = 3, 40, 16, 256


@pytest.fixture(scope="module", params=sorted(MODELS))
def books(request):
    """(cfg, fresh books over a cache of 3 slots, 40 pages of 16)."""
    from ray_tpu.models.generation import KVBooks, PagedKVCache

    cfg = _tiny(MODELS[request.param])
    geometry = (cfg, BATCH, TOTAL, PAGE, MAX_LEN // PAGE)
    return cfg, KVBooks(*geometry, PagedKVCache.create(*geometry))


def _free(books):
    return {kind: sorted(ids) for kind, ids in books.free.items()}


def test_books_reserve_and_release_conserve_every_pool(books):
    """Three contexts (under a page, over the tiny window, the longest)
    held and returned: every page of every pool is free or in exactly
    one slot's row, a ring is never over its columns, a pool of states
    has no page to give, and the tables are zero again at the end."""
    import numpy as np

    from ray_tpu.models.llama import kv_layers

    cfg, books = books
    books.reset()
    all_free = _free(books)
    assert set(all_free) == set(kv_layers(cfg))
    held = {}
    for slot, (tokens, bucket) in enumerate(((9, 16), (100, 64),
                                             (MAX_LEN, 256))):
        pages, tables = books.reserve(slot, tokens, bucket)
        held[slot] = pages
        assert tables is books.tables
        for kind, (_, total, columns) in books.pools.items():
            want = min(max(bucket // PAGE, -(-tokens // PAGE)), columns)
            row = books.tables[kind][slot]
            assert np.count_nonzero(row[want:]) == 0 and len(row) == columns
            assert len(pages[kind]) == min(bucket // PAGE, want) <= columns
            assert pages[kind] == row[:len(pages[kind])].tolist()
            if not total:       # states: a slot is all a request holds
                assert pages[kind] == [] and books.free[kind] == []
    for kind, (_, total, _) in books.pools.items():
        taken = [p for slot in held for p in books.tables[kind][slot]
                 [:len(books._pages[slot][kind])].tolist()]
        assert sorted(taken + books.free[kind]) == list(range(total))
    reading = books.reading()
    assert {k: v["free"] for k, v in reading["pages"].items()} == {
        kind: len(ids) for kind, ids in books.free.items()}
    for slot in held:
        books.release(slot)
    assert _free(books) == all_free
    assert all(not table.any() for table in books.tables.values())
    assert books.reading()["free_pages"] == (
        TOTAL if any(t for _, t, _ in books.pools.values()) else 0)


def test_books_give_a_reservation_or_nothing(books):
    """A pool that is short takes nothing from any pool; a context that
    a pool could never hold is refused by the pool's name."""
    from ray_tpu.models.generation import KVBooks, PagedKVCache

    cfg, books = books
    books.reset()
    paged = any(total for _, total, _ in books.pools.values())
    assert books.reserve(0, MAX_LEN, 256) is not None
    assert books.reserve(1, MAX_LEN, 256) is not None
    before = _free(books)
    # 16 + 16 of 40 pages are out: a third such context finds 8.
    third = books.reserve(2, MAX_LEN, 256)
    assert (third is None) == paged and (paged or third[0] == {"state": []})
    if paged:
        assert _free(books) == before and 2 not in books._pages
    assert books.refusal(MAX_LEN, 256) is None
    geometry = (cfg, BATCH, 8, PAGE, MAX_LEN // PAGE)
    small = KVBooks(*geometry, PagedKVCache.create(*geometry))
    assert small.refusal(8 * PAGE, 64) is None
    refusal = small.refusal(8 * PAGE + 1, 64)
    assert (refusal is None) != paged
    assert not paged or "needs 9 pages" in refusal and "has only 8" in refusal


def test_books_count_what_a_step_read_and_held(books):
    """``account`` against the definitions in ``LLMEngine.stats()``: a
    layer that keeps every token reads the context, a window layer at
    most the window, a state layer a state and no row; without window
    layers the rows are tokens x layers and the pages one table's."""
    from ray_tpu.models.llama import kv_layers

    cfg, books = books
    books.reset()
    start = dict(books.counts)
    spans = {0: (40, 64), 1: (200, 256)}     # slot: (tokens, bucket)
    for slot, (tokens, bucket) in spans.items():
        books.reserve(slot, tokens, bucket)
    contexts = [33, 150]
    books.account(spans.keys(), contexts)
    got = {k: v - start[k] for k, v in books.counts.items()}
    layers = kv_layers(cfg)
    states = layers.get("state", 0)
    window = layers.get("window", 0)
    rows = (cfg.num_layers - states - window) * sum(contexts) + window * sum(
        min(c, cfg.sliding_window or 0) for c in contexts)
    pages = {slot: max(bucket // PAGE, -(-tokens // PAGE))
             for slot, (tokens, bucket) in spans.items()}
    assert got == {
        "decode_kv_tokens": sum(contexts),
        "decode_kv_rows_read": rows,
        # No layer of these models attends over a selection: what it
        # read, it took.
        "decode_kv_rows_selected": rows,
        "decode_state_slot_layers": 2 * states,
        "kv_page_steps_held": sum(
            n * min(p, columns) for p in pages.values()
            for n, _, columns in books.pools.values()),
        "kv_page_steps_one_table": 0 if states else cfg.num_layers * sum(
            pages.values()),
    }
    if not window and not states:
        assert got["decode_kv_rows_read"] == \
            got["decode_kv_tokens"] * cfg.num_layers
        assert got["kv_page_steps_held"] == got["kv_page_steps_one_table"]
    if window:
        assert got["decode_kv_rows_read"] < \
            got["decode_kv_tokens"] * cfg.num_layers
        assert got["kv_page_steps_held"] < got["kv_page_steps_one_table"]


def test_books_say_what_the_page_walk_was_built_with(monkeypatch):
    """``page_walk_step_tokens``: one number a pool the page walk walks,
    the rule ``paged_decode_attention`` sizes its buffers by at that
    pool's table (a ring of 3 columns holds a step of 2 pages); nothing
    where the decode program was built with another attention."""
    import dataclasses
    import importlib

    import jax.numpy as jnp

    from ray_tpu.models.generation import KVBooks, PagedKVCache
    from ray_tpu.ops.paged_attention import walk_step_tokens

    def reading(cfg):
        geometry = (cfg, BATCH, TOTAL, PAGE, MAX_LEN // PAGE)
        books = KVBooks(*geometry, PagedKVCache.create(*geometry))
        return books, books.reading()["page_walk_step_tokens"]

    cfg = dataclasses.replace(_tiny("trinity_tiny"), head_dim=128)
    assert reading(cfg)[1] == {}                         # a CPU: "gather"
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    books, steps = reading(cfg)
    assert books.decode_attention == "page_walk"
    assert steps == {
        kind: walk_step_tokens(
            2 * cfg.num_kv_heads * 128 * jnp.dtype(cfg.dtype).itemsize,
            PAGE, columns)
        for kind, (_, _, columns) in books.pools.items()}
    assert steps == {"window": 2 * PAGE, "full": MAX_LEN}
    assert books.reading()["latent_walk_step_tokens"] == {}
    for other in ("joyai_tiny", "brumby_tiny"):          # no k/v pool
        assert reading(_tiny(other))[1] == {}


@pytest.mark.parametrize("tiny,path,pools", [
    ("joyai_tiny", "latent_walk", {"latent"}),
    ("glm52_tiny", "sparse_walk", {"latent", "index"}),
    ("kimi_tiny", "latent_walk", {"latent", "delta"}),
])
def test_books_say_what_the_latent_walk_was_built_with(monkeypatch, tiny,
                                                       path, pools):
    """``latent_walk_step_tokens``: the one number of the latent pool,
    the rule ``paged_latent_decode_attention`` sizes its buffer by (a
    row's bytes, the page, the table's columns), with or without a
    selection; a pool that rides on the latent pool's table or has no
    pages has none; nothing on a CPU, where the path is the gather."""
    import dataclasses
    import importlib

    import jax.numpy as jnp

    from ray_tpu.models.generation import KVBooks, PagedKVCache
    from ray_tpu.ops.paged_attention import walk_step_tokens

    # The tiny configurations' rows are narrower than a lane tile.
    cfg = dataclasses.replace(_tiny(tiny), kv_lora_rank=128)
    geometry = (cfg, BATCH, TOTAL, PAGE, MAX_LEN // PAGE)

    def reading():
        return KVBooks(*geometry, PagedKVCache.create(*geometry)).reading()

    assert reading()["latent_walk_step_tokens"] == {}    # a CPU: "gather"
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    got = reading()
    assert got["decode_attention"] == path and set(got["pages"]) == pools
    assert got["page_walk_step_tokens"] == {}
    assert got["latent_walk_step_tokens"] == {"latent": walk_step_tokens(
        cfg.latent_row * jnp.dtype(cfg.dtype).itemsize, PAGE,
        MAX_LEN // PAGE)} == {"latent": MAX_LEN}


# ---- (c) what serve/llm.py may not name -------------------------------------

def _tree(path):
    with open(path) as f:
        return ast.parse(f.read())


def test_serve_llm_names_no_attention_kind_and_none_of_its_config_fields():
    """What a kind is, is known behind ``models/generation.py``: the
    engine's module holds no kind's name and reads none of the five
    ``cfg`` fields that tell the kinds apart."""
    kinds = {"full", "window", "latent", "state"}
    fields = {"retention", "latent", "latent_row", "kv_lora_rank",
              "sliding_window"}
    found = []
    for node in ast.walk(_tree(llm.__file__)):
        if isinstance(node, ast.Constant) and node.value in kinds:
            found.append((node.lineno, node.value))
        if isinstance(node, ast.Attribute) and node.attr in fields:
            found.append((node.lineno, "." + node.attr))
    assert found == []


def test_the_scheduler_imports_nothing_and_touches_no_array():
    """``_Scheduler``'s code: no import of its own, and neither jax nor
    numpy by the names the module gives them."""
    (scheduler,) = [node for node in _tree(llm.__file__).body
                    if isinstance(node, ast.ClassDef)
                    and node.name == "_Scheduler"]
    nodes = list(ast.walk(scheduler))
    assert not [n for n in nodes if isinstance(n, (ast.Import,
                                                   ast.ImportFrom))]
    assert not {n.id for n in nodes if isinstance(n, ast.Name)} & {
        "jax", "jnp", "np"}
