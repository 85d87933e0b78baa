"""Operator-DAG plans (zip/union fan-in) + resource-aware backpressure
(ref analogue: the operator graph in
data/_internal/execution/streaming_executor_state.py and the policies in
data/_internal/execution/backpressure_policy/)."""

import time

import numpy as np
import pytest

import ray_tpu.data as rd
from ray_tpu.data.context import DataContext


def test_union_local():
    a = rd.from_items([{"x": i} for i in range(6)])
    b = rd.from_items([{"x": 100 + i} for i in range(4)])
    u = a.union(b)
    xs = [r["x"] for r in u.take_all()]
    assert xs == list(range(6)) + [100 + i for i in range(4)]
    assert u.count() == 10
    assert u.num_blocks() == a.num_blocks() + b.num_blocks()


def test_union_multiway_with_transform_local():
    a = rd.range(5).map(lambda r: {"id": r["id"] * 10})
    b = rd.range(3)
    c = rd.range(2).map(lambda r: {"id": -r["id"]})
    u = a.union(b, c).map(lambda r: {"id": r["id"] + 1})
    ids = [r["id"] for r in u.take_all()]
    assert ids == [1, 11, 21, 31, 41, 1, 2, 3, 1, 0]


def test_zip_local():
    # from_items stripes rows across blocks; both sides stripe
    # identically, so zip stays row-aligned (y == 2x pairwise).
    a = rd.from_items([{"x": i} for i in range(8)], override_num_blocks=4)
    b = rd.from_items([{"y": i * 2} for i in range(8)],
                      override_num_blocks=4)
    z = a.zip(b)
    rows = z.take_all()
    assert sorted(r["x"] for r in rows) == list(range(8))
    assert all(r["y"] == 2 * r["x"] for r in rows)


def test_zip_name_collision_suffix_local():
    a = rd.from_items([{"v": i} for i in range(4)], override_num_blocks=2)
    b = rd.from_items([{"v": -i} for i in range(4)],
                      override_num_blocks=2)
    rows = a.zip(b).take_all()
    assert sorted(r["v"] for r in rows) == [0, 1, 2, 3]
    assert all(r["v_1"] == -r["v"] for r in rows)


def test_zip_block_mismatch_raises_local():
    a = rd.from_items([{"x": i} for i in range(8)], override_num_blocks=4)
    b = rd.from_items([{"y": i} for i in range(8)], override_num_blocks=2)
    with pytest.raises(ValueError, match="zip"):
        a.zip(b).take_all()


def test_union_zip_distributed(ray_tpu_start):
    a = rd.range(6, override_num_blocks=3).map(
        lambda r: {"id": r["id"], "sq": r["id"] ** 2}
    )
    b = rd.range(6, override_num_blocks=3).map(
        lambda r: {"cube": r["id"] ** 3}
    )
    z = a.zip(b)
    rows = z.take_all()
    assert [r["sq"] for r in rows] == [i * i for i in range(6)]
    assert [r["cube"] for r in rows] == [i ** 3 for i in range(6)]

    u = a.union(a).map(lambda r: {"id": r["id"]})
    assert u.count() == 12
    # downstream global op over a DAG plan (forces the materialize path)
    assert sorted(r["id"] for r in u.random_shuffle().take_all()) == sorted(
        list(range(6)) * 2
    )


def test_union_streams_without_driver_materialize(ray_tpu_start):
    """Union output arrives as refs (streaming fan-in), and stats record
    the union node."""
    a = rd.range(4, override_num_blocks=2)
    b = rd.range(4, override_num_blocks=2)
    u = a.union(b)
    total = u.count()
    assert total == 8
    s = u.stats()
    assert "Union" in s


def test_streaming_split_over_union(ray_tpu_start):
    """streaming_split of a DAG plan goes through the shared coordinator
    (no upfront materialize): every row arrives exactly once across
    shards, consumed concurrently."""
    import threading

    a = rd.range(8, override_num_blocks=4)
    b = rd.range(8, override_num_blocks=4).map(
        lambda r: {"id": r["id"] + 100}
    )
    u = a.union(b)
    shards = u.streaming_split(2)
    got = [[], []]

    def consume(i):
        for row in shards[i].iter_rows():
            got[i].append(row["id"])

    ts = [threading.Thread(target=consume, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    allv = sorted(got[0] + got[1])
    assert allv == sorted(list(range(8)) + [i + 100 for i in range(8)])
    assert got[0] and got[1]  # both shards actually consumed


def test_store_backpressure_bounds_producer(monkeypatch):
    """A consumer that sits on its blocks must bound producer memory:
    with the store-usage policy active, in-store bytes stay under the cap
    plus the in-flight window, and the policy is what held the producer
    back (ref: resource-aware backpressure policies).

    Nothing here races: the consumer pulls a block, waits until it is
    sealed and KEEPS it while the store is under the cap, so the store
    crosses the cap after a number of pulls that depends on no timing,
    and pulls once more over it, so the policy is asked there; then it
    lets go of what it kept, waits for the store to fall under the cap
    again and consumes the rest a block at a time. Submissions the
    policy refused are counted at the policy itself."""
    import ray_tpu
    from ray_tpu.core.runtime_context import current_runtime
    from ray_tpu.data.streaming_executor import StoreUsagePolicy

    ray_tpu.init(num_cpus=2, object_store_memory=256 * 1024 * 1024,
                 system_config={"log_to_driver": False,
                                "refcount_flush_interval_s": 0.1,
                                "gc_grace_period_s": 0.5})
    ctx = DataContext.get_current()
    old_frac, old_inflight = (ctx.store_usage_cap_fraction,
                              ctx.max_in_flight_tasks)
    ctx.store_usage_cap_fraction = 0.25
    # Small enough that 32 blocks are kept (the cap) before all 40 are
    # submitted: the policy is asked while there is something to hold.
    ctx.max_in_flight_tasks = 4
    held_back = []
    can_submit = StoreUsagePolicy.can_submit

    def counted(self, num_inflight):
        ok = can_submit(self, num_inflight)
        if not ok:
            held_back.append(num_inflight)
        return ok

    monkeypatch.setattr(StoreUsagePolicy, "can_submit", counted)
    try:
        nm = current_runtime()._nm
        cap = nm.directory.capacity_bytes
        assert cap > 0
        limit = cap * 0.25
        block_bytes = 2 * 1024 * 1024
        nblocks = 40
        window = ctx.max_in_flight_tasks

        def gen_block(r):
            return {"data": np.zeros(block_bytes // 8, dtype=np.float64)}

        def run_consumer():
            ds = rd.range(nblocks, override_num_blocks=nblocks).map_batches(
                gen_block, batch_size=None
            )
            peak = seen = 0
            kept, crossed = [], False
            for ref in ds.iter_blocks_refs():
                ray_tpu.wait([ref], num_returns=1, timeout=60)
                peak = max(peak, nm.directory.used_bytes)
                seen += 1
                if crossed:
                    kept.clear()
                else:
                    kept.append(ref)
                    if nm.directory.used_bytes >= limit:
                        crossed = True  # the next pull finds it over the cap
                        continue
                del ref
                if crossed:
                    # One block at a time from here: the store lets go
                    # of what was consumed before the next is pulled.
                    deadline = time.monotonic() + 60
                    while nm.directory.used_bytes >= limit:
                        assert time.monotonic() < deadline, "nothing freed"
                        time.sleep(0.02)
            assert seen == nblocks
            return peak, crossed

        peak_on, crossed = run_consumer()
        assert crossed and nblocks * block_bytes > limit + window * block_bytes
        # Hard bound: once usage crosses cap*frac, submission stops;
        # only the already-in-flight window can still land.
        assert peak_on <= cap * 0.25 + window * block_bytes, (
            f"peak {peak_on} vs cap {cap}*0.25 + {window} blocks"
        )
        # The policy did it: it refused submissions, each with work in
        # flight (its progress guarantee never refuses the first).
        assert held_back and min(held_back) >= 1, held_back
        # Contrast: without the store policy nothing is ever held back.
        ctx.store_usage_cap_fraction = 0.0
        del held_back[:]
        time.sleep(1.5)  # let the previous run's blocks GC
        run_consumer()
        assert held_back == []
    finally:
        ctx.store_usage_cap_fraction = old_frac
        ctx.max_in_flight_tasks = old_inflight
        ray_tpu.shutdown()
