"""Borrower/ownership protocol — adversarial reference-counting cases
(ref analogue: python/ray/tests/test_reference_counting_2.py over
src/ray/core_worker/reference_count.h:61: borrower registration, nested
containment pins, borrows outliving tasks, owner death).

These run on a real multi-process cluster with a TIGHT GC (0.5 s grace,
0.1 s delta flush) so any hole in the protocol frees objects that are
still reachable — the old interim pin-while-referenced scheme fails
every cross-node case here.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster

# Test classes pickle by reference and would be unimportable in workers.
import cloudpickle as _cloudpickle
import sys as _sys

_cloudpickle.register_pickle_by_value(_sys.modules[__name__])

TIGHT_GC = {
    "gc_grace_period_s": 0.5,
    "refcount_flush_interval_s": 0.1,
    "log_to_driver": False,
}


def _big():
    # Large enough to live in shared memory (never inlined).
    return np.arange(300_000, dtype=np.float64)


@ray_tpu.remote
class Keeper:
    """Stores whatever container it is handed (refs stay smuggled)."""

    def __init__(self):
        self.box = None

    def stash(self, box):
        self.box = box
        return "stashed"

    def read(self, timeout=20):
        return ray_tpu.get(self.box[0], timeout=timeout)

    def handoff(self, other):
        # Nested borrow: pass the borrowed ref (inside a container) to
        # another actor without the owner's involvement.
        return ray_tpu.get(other.stash.remote(self.box), timeout=30)


@pytest.fixture
def edge_cluster():
    """Head + one worker node carrying resource {edge: 2}."""
    cluster = Cluster(head_resources={"CPU": 2}, system_config=TIGHT_GC)
    cluster.add_node(num_cpus=2, resources={"edge": 2})
    yield cluster
    cluster.shutdown()


def test_smuggled_container_ref_survives_owner_release(edge_cluster):
    """A ref inside a list arg to a REMOTE actor keeps the object alive
    after the driver (owner-side holder) drops its own ref — the remote
    node registers as a borrower with the owner."""
    k = Keeper.options(resources={"edge": 1}).remote()
    ref = ray_tpu.put(_big())
    assert ray_tpu.get(k.stash.remote([ref]), timeout=60) == "stashed"
    del ref
    time.sleep(3.0)  # several GC sweeps at 0.5 s grace
    out = ray_tpu.get(k.read.remote(), timeout=30)
    assert isinstance(out, np.ndarray) and out.shape == (300_000,)


def test_borrowed_ref_outliving_task_then_released(edge_cluster):
    """The borrow ends when the borrower drops the ref: the owner's
    entry must then actually be collected (no leak from the protocol)."""
    k = Keeper.options(resources={"edge": 1}).remote()
    ref = ray_tpu.put(_big())
    oid = ref.id()
    assert ray_tpu.get(k.stash.remote([ref]), timeout=60) == "stashed"
    del ref
    time.sleep(2.0)
    # Borrow still live: readable.
    assert ray_tpu.get(k.read.remote(), timeout=30).shape == (300_000,)
    # Borrower drops its container -> release_borrow -> owner frees.
    assert ray_tpu.get(k.stash.remote([None]), timeout=30) == "stashed"
    from ray_tpu.core.runtime_context import current_runtime

    rt = current_runtime()
    deadline = time.time() + 20
    while time.time() < deadline:
        if not rt._nm.directory.has_entry(oid):
            break
        time.sleep(0.3)
    assert not rt._nm.directory.has_entry(oid), (
        "owner never collected the object after the borrow was released"
    )


def test_nested_ref_inside_put_object(edge_cluster):
    """put([inner_ref]): the containing object pins the inner one
    (AddNestedObjectIds) — dropping the inner ref must not free it while
    the outer object lives, even for a remote borrower."""
    inner = ray_tpu.put(_big())
    outer = ray_tpu.put({"payload": [inner]})
    del inner
    time.sleep(2.5)
    k = Keeper.options(resources={"edge": 1}).remote()
    assert ray_tpu.get(
        k.stash.remote([outer]), timeout=60
    ) == "stashed"

    @ray_tpu.remote(resources={"edge": 1})
    def read_inner(container):
        return ray_tpu.get(container["payload"][0], timeout=20).shape

    assert tuple(
        ray_tpu.get(read_inner.remote(outer), timeout=60)
    ) == (300_000,)


def test_ref_returned_inside_container(edge_cluster):
    """A task that returns [ref] — the return object pins the inner ref
    (reported in the completion frame) until the return itself dies."""

    @ray_tpu.remote(resources={"edge": 1})
    def make_box():
        inner = ray_tpu.put(np.ones(300_000))
        return [inner]  # inner's only live handle rides the return

    box_ref = make_box.remote()
    box = ray_tpu.get(box_ref, timeout=60)
    time.sleep(2.5)  # old scheme: inner's worker ref died with the task
    out = ray_tpu.get(box[0], timeout=30)
    assert float(out.sum()) == 300_000.0


def test_borrow_chain_second_hop(edge_cluster):
    """B borrows from the owner, then hands the ref to C (nested
    borrow). After the owner's holder AND B drop, C must still read."""
    a = Keeper.options(resources={"edge": 1}).remote()
    b = Keeper.options(num_cpus=1).remote()  # head node
    ref = ray_tpu.put(_big())
    assert ray_tpu.get(a.stash.remote([ref]), timeout=60) == "stashed"
    del ref
    # A hands its borrowed container to B.
    assert ray_tpu.get(a.handoff.remote(b), timeout=60) == "stashed"
    # A drops; only B (a second-hop borrower) still holds.
    assert ray_tpu.get(a.stash.remote([None]), timeout=30) == "stashed"
    time.sleep(3.0)
    out = ray_tpu.get(b.read.remote(), timeout=30)
    assert isinstance(out, np.ndarray) and out.shape == (300_000,)


@pytest.mark.slow
def test_borrow_then_owner_node_dies():
    """The owner node dies while a borrow is live: the borrower's read
    must fail CLEANLY (or reconstruct) — never hang (ref analogue:
    OwnerDiedError semantics)."""
    cluster = Cluster(head_resources={"CPU": 2}, system_config=TIGHT_GC)
    owner_node = cluster.add_node(num_cpus=1, resources={"owner": 1})
    cluster.add_node(num_cpus=1, resources={"edge": 1})
    try:
        @ray_tpu.remote(resources={"owner": 1})
        class Producer:
            def make(self):
                return [ray_tpu.put(np.ones(300_000))]

        p = Producer.remote()
        box = ray_tpu.get(p.make.remote(), timeout=60)
        k = Keeper.options(resources={"edge": 1}).remote()
        assert ray_tpu.get(k.stash.remote(box), timeout=60) == "stashed"
        # Kill the owner node (holds the only data copy).
        cluster.remove_node(owner_node)
        time.sleep(2.0)
        t0 = time.monotonic()
        with pytest.raises(Exception):
            ray_tpu.get(k.read.remote(timeout=15), timeout=45)
        assert time.monotonic() - t0 < 60  # failed, not hung
    finally:
        cluster.shutdown()


@pytest.mark.parametrize("where", ["flush", "drain", "incr", "decr"])
def test_a_ref_dropped_inside_the_tables_lock_does_not_deadlock(where):
    """The collector can run an ObjectRef's ``__del__`` between two
    bytecodes of the thread that holds the ref table's lock (seen: a
    driver's ref flusher stuck on itself in ``flush``, every thread of
    the process behind it). Stood in for here by a dict that drops a ref
    when the table reads it."""
    import threading

    from ray_tpu.core.ids import ObjectID
    from ray_tpu.core.runtime import RefCountTable

    flushed = []
    table = RefCountTable(flushed.append)
    held, other = ObjectID.from_random(), ObjectID.from_random()

    class Dropping(dict):
        armed = True

        def _drop(self):
            if Dropping.armed:
                Dropping.armed = False
                table.decr(other)

        def items(self):
            self._drop()
            return super().items()

        def get(self, *a):
            self._drop()
            return super().get(*a)

    table.incr(held)
    table.incr(other)
    if where in ("flush", "drain"):
        table._deltas = Dropping(table._deltas)
    else:
        table._local = Dropping(table._local)
    call = {"flush": table.flush,
            "drain": lambda: flushed.append(table.drain()),
            "incr": lambda: table.incr(held),
            "decr": lambda: table.decr(held)}[where]
    thread = threading.Thread(target=call, daemon=True)
    thread.start()
    thread.join(timeout=10)
    assert not thread.is_alive(), f"{where} deadlocked on its own lock"
    assert not Dropping.armed
    # Nothing was lost: what was taken and what is left add up.
    total = {held: 0, other: 0}
    for deltas in flushed + [table.drain()]:
        for oid, d in deltas.items():
            total[oid] += d
    assert total == {held: {"incr": 2, "decr": 0}.get(where, 1), other: 0}
