"""Shared-memory object store (plasma-equivalent).

Plays the role of the reference's plasma store + store providers (ref:
src/ray/object_manager/plasma/store.h PlasmaStore,
object_lifecycle_manager.h, python side store_provider/plasma_store_provider.h):
immutable, sealed-once objects in POSIX shared memory, read zero-copy by every
process on the node via mmap. Differences by design: one shm segment per
object (the kernel is the arena allocator) instead of a dlmalloc arena over a
single mapping, and the object *directory* lives in the head process's
control plane rather than a separate store daemon — on TPU hosts the store
only needs to feed jax.device_put, so the simpler layout wins.

Small objects bypass shm entirely and travel inline in control messages
(ref analogue: the in-process CoreWorkerMemoryStore for small returns).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from multiprocessing import shared_memory, resource_tracker
from typing import Dict, Optional, Union

from .ids import ObjectID
from .serialization import SerializedObject, deserialize

# Census bookkeeping (creation ts + owner labels) rides the data-obs
# kill switch: RTPU_NO_DATA_OBS=1 drops it to zero cost and the census
# degrades to age-less rows.
from ..util.data_obs import ENABLED as _CENSUS


class ObjectStoreFullError(Exception):
    pass


@dataclass(frozen=True, slots=True)
class InlineLocation:
    data: bytes


@dataclass(frozen=True, slots=True)
class ShmLocation:
    name: str
    size: int


@dataclass(frozen=True, slots=True)
class ArenaLocation:
    """Object stored in the node's native C++ arena store (src/store/).

    Lookup is by object id (the arena keeps its own table); ``size`` is the
    sealed payload size for directory accounting."""

    arena: str
    oid: bytes
    size: int


@dataclass(frozen=True, slots=True)
class RemoteLocation:
    """Object whose bytes live on another node; resolved by pulling over the
    peer channel and re-homing locally (ref analogue: an object-directory
    entry whose location set names a remote plasma store, fetched via
    ObjectManagerService Push/Pull — object_manager.proto:61).

    ``held`` marks that the remote node keeps a refcount hold on our behalf
    (forwarded-task return slots); the holder sends ``free_object`` exactly
    once — after pulling or when its own entry is collected."""

    node_id: str  # hex
    size: int
    held: bool = False


@dataclass(frozen=True, slots=True)
class SpilledLocation:
    """Object whose bytes were spilled to external storage under memory
    pressure; restored into the store on next access (ref analogue: a
    spilled-object URL pinned by LocalObjectManager,
    raylet/local_object_manager.h:41)."""

    path: str
    size: int


Location = Union[
    InlineLocation, ShmLocation, ArenaLocation, RemoteLocation, SpilledLocation
]


class ObjectWriter:
    """Incremental chunk writer returned by ``SharedStore.create_writer``:
    space allocated up front, chunks written at their offsets, then sealed
    (arena) or left in place (shm segment)."""

    def __init__(self, *, kind: str, loc, view: memoryview,
                 arena=None, raw=None, seg=None):
        self.kind = kind
        self.loc = loc
        self._view = view
        self._arena = arena
        self._raw = raw  # arena View (keeps the creator pin)
        self._seg = seg

    def write(self, offset: int, data) -> None:
        self._view[offset:offset + len(data)] = data

    def readinto_view(self, offset: int, length: int) -> memoryview:
        """Writable window over ``[offset, offset+length)`` of the
        pre-allocated block: the data-plane receiver ``recv_into``s
        payload straight off the socket into shared memory — no staging
        bytes object, no second memmove (the zero-copy receive half of
        core/data_channel.py)."""
        return self._view[offset:offset + length]

    def finalize(self):
        if self.kind == "arena":
            self._view.release()
            self._arena.seal(self.loc.oid)
            self._raw.release()
        return self.loc

    def abort(self) -> None:
        try:
            if self.kind == "arena":
                self._view.release()
                self._raw.release()
                self._arena.abort(self.loc.oid)
            else:
                self._seg.close()
                shared_memory.SharedMemory(name=self.loc.name).unlink()
        except Exception:
            pass


class _RawPayload:
    """Adapter presenting already-framed object bytes (as pulled from a
    remote node) with the SerializedObject write interface."""

    __slots__ = ("data",)

    def __init__(self, data):
        self.data = data

    @property
    def total_size(self) -> int:
        return len(self.data)

    def write_into(self, dest: memoryview) -> int:
        dest[: len(self.data)] = self.data
        return len(self.data)


# ---------------------------------------------------------------------------
# Native arena store (one per node, created by the node manager, attached by
# every worker via the RAY_TPU_ARENA env var). Module-level singleton: all
# runtimes in a process share one mapping.
# ---------------------------------------------------------------------------

_arena = None
_arena_lock = threading.Lock()


def init_arena(name: str, capacity: int = 0, create: bool = False) -> bool:
    """Create or attach the node arena. Returns True when the native store
    is active in this process; False leaves the pure-Python fallback."""
    global _arena
    from ray_tpu._native import load_rtstore

    mod = load_rtstore()
    if mod is None:
        return False
    with _arena_lock:
        if _arena is not None:
            return True
        try:
            if create:
                _arena = mod.create(name, capacity)
            else:
                _arena = mod.attach(name)
        except OSError:
            _arena = None
            return False
    return True


def current_arena():
    return _arena


def shutdown_arena(unlink: bool):
    global _arena
    with _arena_lock:
        store, _arena = _arena, None
    if store is not None:
        name = store.name
        store.close()
        if unlink:
            from ray_tpu._native import load_rtstore

            mod = load_rtstore()
            if mod is not None:
                try:
                    mod.unlink(name)
                except OSError:
                    pass


def _shm_name(object_id: ObjectID) -> str:
    # Full 40-char hex: driver puts share their 16-byte TaskID prefix and
    # differ only in the trailing index, so truncation would collide every
    # driver-put segment onto one name.
    return "rtpu-" + object_id.hex()


def _attach_untracked(name: str) -> shared_memory.SharedMemory:
    """Attach to an existing segment without registering with the
    multiprocessing resource tracker (which would unlink it when *this*
    process exits; the creating node manager owns cleanup)."""
    seg = shared_memory.SharedMemory(name=name)
    try:
        resource_tracker.unregister(seg._name, "shared_memory")  # noqa: SLF001
    except Exception:
        pass
    return seg


class LocalObjectStore:
    """Per-process object store client.

    Writers create + fill + seal segments; readers attach and get zero-copy
    views. The authoritative directory (ObjectID -> Location) is kept by the
    node's control plane; this class only manages segments and the local
    attachment cache.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._segments: Dict[str, shared_memory.SharedMemory] = {}
        self._created: Dict[str, shared_memory.SharedMemory] = {}

    # -- write path ---------------------------------------------------------

    def put_serialized(self, object_id: ObjectID, sobj: SerializedObject) -> Location:
        arena = current_arena()
        if arena is not None:
            loc = self._put_arena(arena, object_id, sobj)
            if loc is not None:
                return loc
            # Arena full: fall through to a per-object segment (the
            # plasma-equivalent of fallback allocation to filesystem shm).
        return self._put_segment(object_id, sobj)

    @staticmethod
    def _arena_alloc(arena, oid_bytes: bytes, size: int):
        """Alloc-or-replace an arena block (same id rewritten on task
        retry: never trust old contents). None when the arena is full."""
        try:
            return arena.alloc(oid_bytes, size)
        except FileExistsError:
            arena.delete(oid_bytes)
            try:
                return arena.alloc(oid_bytes, size)
            except (FileExistsError, MemoryError):
                return None
        except MemoryError:
            return None

    def _acquire_segment(self, name: str, size: int):
        """Create (or reuse / grow-by-recreate) a shm segment of at least
        ``size`` bytes, register it in the local maps, and untrack every
        freshly-created segment from the multiprocessing resource tracker
        — otherwise tracker cleanup unlinks LIVE objects at process exit
        (the directory owns segment lifecycle)."""
        created = True
        try:
            seg = shared_memory.SharedMemory(
                name=name, create=True, size=size
            )
        except FileExistsError:
            seg = _attach_untracked(name)
            if seg.size < size:
                seg.close()
                old = shared_memory.SharedMemory(name=name)
                old.unlink()
                old.close()
                seg = shared_memory.SharedMemory(
                    name=name, create=True, size=size
                )
            else:
                created = False
        if created:
            try:
                resource_tracker.unregister(seg._name, "shared_memory")  # noqa: SLF001
            except Exception:
                pass
        with self._lock:
            self._segments[name] = seg
            if created:
                self._created[name] = seg
        return seg

    def _put_arena(self, arena, object_id: ObjectID, sobj: SerializedObject):
        oid = object_id.binary()
        size = sobj.total_size
        view = self._arena_alloc(arena, oid, size)
        if view is None:
            return None
        try:
            mv = memoryview(view)
            sobj.write_into(mv)
            del mv
            arena.seal(oid)
        except BaseException:
            try:
                arena.abort(oid)
            except Exception:
                pass
            raise
        finally:
            view.release()  # drop the creator pin
        return ArenaLocation(arena.name, oid, size)

    def put_raw(self, object_id: ObjectID, data) -> Location:
        """Store already-framed object bytes (pulled from a remote node)."""
        return self.put_serialized(object_id, _RawPayload(data))

    def create_writer(self, object_id: ObjectID, size: int) -> "ObjectWriter":
        """Allocate ``size`` bytes up front and return an incremental
        writer: chunked pulls land each chunk directly in shared memory,
        so a 1 GiB transfer needs 1 GiB of store — never a second
        staging copy (ref analogue: the plasma CreateObject the object
        manager writes received chunks into, object_buffer_pool.h)."""
        arena = current_arena()
        if arena is not None:
            oid = object_id.binary()
            view = self._arena_alloc(arena, oid, size)
            if view is not None:
                return ObjectWriter(
                    kind="arena", arena=arena, raw=view,
                    view=memoryview(view),
                    loc=ArenaLocation(arena.name, oid, size),
                )
        name = _shm_name(object_id)
        seg = self._acquire_segment(name, size)
        return ObjectWriter(
            kind="shm", seg=seg, view=seg.buf,
            loc=ShmLocation(name, size),
        )

    def get_bytes(self, loc: Location) -> bytes:
        """Copy out the framed bytes of a local object (the push side of
        inter-node transfer)."""
        view = self.get_view(loc)
        try:
            return bytes(view)
        finally:
            view.release()

    def get_view_range(self, loc: Location, offset: int, length: int):
        """``(memoryview, release)`` over one byte range of a sealed
        object — the zero-copy send half of the transfer data plane
        (``socket.sendall`` on the slice moves shm bytes to the NIC with
        no ``bytes()`` staging). ``release`` drops both the slice and
        the underlying view/pin; call it once the send completes."""
        view = self.get_view(loc)
        sub = view[offset:offset + length]

        def release():
            sub.release()
            if hasattr(view, "release"):
                view.release()

        return sub, release

    def _put_segment(self, object_id: ObjectID, sobj: SerializedObject) -> ShmLocation:
        # Same object id written twice (e.g. a task retry after the first
        # writer crashed mid-write): _acquire_segment reuses or recreates;
        # either way the contents are rewritten below.
        name = _shm_name(object_id)
        seg = self._acquire_segment(name, sobj.total_size)
        sobj.write_into(seg.buf)
        return ShmLocation(name, sobj.total_size)

    # -- read path ----------------------------------------------------------

    def get_view(self, loc: Location) -> memoryview:
        if isinstance(loc, InlineLocation):
            return memoryview(loc.data)
        if isinstance(loc, SpilledLocation):
            # Direct read of a spilled object (normally the node manager
            # restores it into the store first; this path keeps readers
            # correct if they race a spill).
            with open(loc.path, "rb") as f:
                return memoryview(f.read())
        if isinstance(loc, ArenaLocation):
            arena = current_arena()
            if arena is None:
                raise RuntimeError(
                    f"object in arena {loc.arena} but no arena attached"
                )
            view = arena.get(loc.oid)
            if view is None:
                raise KeyError(f"object {loc.oid.hex()} lost from arena")
            # The memoryview keeps the View (and its pin) alive; numpy arrays
            # deserialized zero-copy chain to it via their .base.
            return memoryview(view)[: loc.size]
        with self._lock:
            seg = self._segments.get(loc.name)
            if seg is None:
                seg = _attach_untracked(loc.name)
                self._segments[loc.name] = seg
        return seg.buf[: loc.size]

    def get_object(self, loc: Location):
        return deserialize(self.get_view(loc))

    # -- lifecycle ----------------------------------------------------------

    def release(self, loc: ShmLocation, *, unlink: bool):
        """Close the local mapping; unlink destroys the segment node-wide
        (called only by the owner when the global refcount hits zero)."""
        with self._lock:
            seg = self._segments.pop(loc.name, None)
            self._created.pop(loc.name, None)
        if seg is not None:
            try:
                seg.close()
            except BufferError:
                # A deserialized view still pins the mapping; leave the
                # mapping open (the segment file can still be unlinked).
                self._segments[loc.name] = seg
                seg = None
        if unlink:
            try:
                shared_memory.SharedMemory(name=loc.name).unlink()
            except FileNotFoundError:
                pass

    def shutdown(self, *, unlink_created: bool):
        with self._lock:
            segments = dict(self._segments)
            created = set(self._created)
            self._segments.clear()
            self._created.clear()
        for name, seg in segments.items():
            try:
                seg.close()
            except BufferError:
                pass
            if unlink_created and name in created:
                try:
                    seg.unlink()
                except FileNotFoundError:
                    pass


class ObjectDirectory:
    """Node-wide object table kept by the control plane (head process).

    Tracks location, size, aggregated local reference counts, AND the set
    of peer nodes borrowing each object (ref analogue: ReferenceCounter,
    src/ray/core_worker/reference_count.h — local refs + the borrower
    set). An entry is freed only when its local count is <=0 AND no
    borrower node is registered; lineage entries keyed on the object
    survive exactly as long as the entry does, so lineage stays pinned
    under borrowing.
    """

    def __init__(self, capacity_bytes: int):
        self.capacity_bytes = capacity_bytes
        self.used_bytes = 0
        # When True (node manager runs a spill loop), adds over capacity are
        # admitted and relieved by spilling instead of refused (ref analogue:
        # CreateRequestQueue fallback allocation vs. OutOfMemory reply).
        self.spill_enabled = False
        self._entries: Dict[ObjectID, Location] = {}
        self._refcounts: Dict[ObjectID, int] = {}
        self._zero_since: Dict[ObjectID, float] = {}
        self._access: Dict[ObjectID, int] = {}
        # oid -> set of peer node hexes holding live borrows of this
        # object (owner-side borrower tracking, reference_count.h:61).
        self._borrowers: Dict[ObjectID, set] = {}
        # Census sidecars (util/data_obs.py plane): wall-clock creation
        # ts + a free-form owner label ("task name" for returns, "put"
        # for driver puts, ...). Only populated while the data-obs plane
        # is enabled — the census degrades to age-less rows otherwise.
        self._created_ts: Dict[ObjectID, float] = {}
        self._owners: Dict[ObjectID, str] = {}
        self._access_counter = 0
        self._lock = threading.Lock()

    def add(self, object_id: ObjectID, loc: Location, initial_refs: int = 1,
            owner: str = ""):
        with self._lock:
            if object_id in self._entries:
                self._refcounts[object_id] += initial_refs
                return
            shared = isinstance(loc, (ShmLocation, ArenaLocation))
            size = (
                loc.size if shared
                else len(loc.data) if isinstance(loc, InlineLocation) else 0
            )
            if shared and self.capacity_bytes > 0 and not self.spill_enabled:
                if self.used_bytes + size > self.capacity_bytes:
                    raise ObjectStoreFullError(
                        f"object store over capacity: {self.used_bytes + size} "
                        f"> {self.capacity_bytes} bytes"
                    )
            self.used_bytes += size if shared else 0
            self._entries[object_id] = loc
            self._refcounts[object_id] = initial_refs
            self._access_counter += 1
            self._access[object_id] = self._access_counter
            if _CENSUS:
                import time

                self._created_ts[object_id] = time.time()
                if owner:
                    self._owners[object_id] = owner
            if initial_refs <= 0:
                import time

                self._zero_since[object_id] = time.monotonic()

    def lookup(self, object_id: ObjectID) -> Optional[Location]:
        with self._lock:
            loc = self._entries.get(object_id)
            if loc is not None:
                self._access_counter += 1
                self._access[object_id] = self._access_counter
            return loc

    def seal_over_placeholder(self, object_id: ObjectID, loc: Location):
        """Replace a pre-registered (placeholder) entry with its real
        location once the producing task finishes."""
        with self._lock:
            old = self._entries.get(object_id)
            if isinstance(old, (ShmLocation, ArenaLocation)):
                self.used_bytes -= old.size
            self._entries[object_id] = loc
            if isinstance(loc, (ShmLocation, ArenaLocation)):
                self.used_bytes += loc.size

    def replace_location(self, object_id: ObjectID, loc: Location):
        """Swap an entry's location (remote -> pulled-local re-home),
        preserving its refcount."""
        with self._lock:
            old = self._entries.get(object_id)
            if old is None:
                return
            if isinstance(old, (ShmLocation, ArenaLocation)):
                self.used_bytes -= old.size
            if isinstance(loc, (ShmLocation, ArenaLocation)):
                self.used_bytes += loc.size
            self._entries[object_id] = loc

    def add_ref(self, object_id: ObjectID, count: int = 1):
        with self._lock:
            if object_id in self._refcounts:
                self._refcounts[object_id] += count
                if self._refcounts[object_id] > 0:
                    self._zero_since.pop(object_id, None)

    def remove_ref(self, object_id: ObjectID, count: int = 1):
        """Decrement; collection is deferred to ``collect_garbage`` so that
        out-of-order refcount flushes from different processes cannot free
        a still-referenced object, and skipped entirely while peer nodes
        hold registered borrows."""
        import time

        with self._lock:
            if object_id not in self._refcounts:
                return
            self._refcounts[object_id] -= count
            if self._refcounts[object_id] <= 0:
                self._zero_since.setdefault(object_id, time.monotonic())

    # ---- borrower tracking (owner side) -------------------------------

    def has_entry(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._entries

    def add_ref_or_create(self, object_id: ObjectID, count: int,
                          stub_loc: Location) -> bool:
        """Increment if the entry exists; otherwise create a count-only
        borrow stub at ``stub_loc``. Returns True when a stub was created
        (single lock acquisition — this sits on the task-submit path)."""
        with self._lock:
            if object_id in self._refcounts:
                self._refcounts[object_id] += count
                if self._refcounts[object_id] > 0:
                    self._zero_since.pop(object_id, None)
                return False
            self._entries[object_id] = stub_loc
            self._refcounts[object_id] = count
            self._access_counter += 1
            self._access[object_id] = self._access_counter
            if _CENSUS:
                import time

                self._created_ts[object_id] = time.time()
                self._owners[object_id] = "borrow"
            if count <= 0:
                import time

                self._zero_since[object_id] = time.monotonic()
            return True

    def add_borrower(self, object_id: ObjectID, node_hex: str) -> bool:
        """Register a peer node as a borrower. False = the object is
        already gone (the borrower's reads will fail loudly)."""
        with self._lock:
            if object_id not in self._entries:
                return False
            self._borrowers.setdefault(object_id, set()).add(node_hex)
            return True

    def remove_borrower(self, object_id: ObjectID, node_hex: str):
        import time

        with self._lock:
            s = self._borrowers.get(object_id)
            if not s:
                return
            s.discard(node_hex)
            if not s:
                del self._borrowers[object_id]
                if self._refcounts.get(object_id, 0) <= 0:
                    # Fresh grace window: the release may race late
                    # re-borrow registrations.
                    self._zero_since[object_id] = time.monotonic()

    def drop_borrower_node(self, node_hex: str):
        """A node died: its borrows are void (ref analogue: borrower
        cleanup on node removal)."""
        import time

        with self._lock:
            for oid in [o for o, s in self._borrowers.items()
                        if node_hex in s]:
                s = self._borrowers[oid]
                s.discard(node_hex)
                if not s:
                    del self._borrowers[oid]
                    if self._refcounts.get(oid, 0) <= 0:
                        self._zero_since[oid] = time.monotonic()

    def borrower_count(self, object_id: ObjectID) -> int:
        with self._lock:
            return len(self._borrowers.get(object_id, ()))

    def collect_garbage(self, grace_s: float, limit: int = 4096):
        """Pop and return [(oid, loc)] for entries at refcount <= 0 for
        longer than ``grace_s`` seconds. ``limit`` bounds one sweep so a
        burst of dead objects (a put-heavy benchmark, a dropped dataset)
        cannot stall the event loop under this lock — the rest goes next
        sweep."""
        import time

        now = time.monotonic()
        out = []
        with self._lock:
            expired = []
            for oid, t in self._zero_since.items():
                count = self._refcounts.get(oid, 0)
                # An entry BELOW zero is owed an add that is on its way
                # on another socket (a streamed item's release overtook
                # its producer's batched seal and pin, which would find
                # no entry and pin a new one for ever): it is given
                # twenty times as long.
                if (now - t >= (grace_s if count == 0 else 20 * grace_s)
                        and count <= 0
                        and oid not in self._borrowers):
                    expired.append(oid)
                    if len(expired) >= limit:
                        break
            for oid in expired:
                loc = self._entries.pop(oid, None)
                self._refcounts.pop(oid, None)
                self._zero_since.pop(oid, None)
                self._access.pop(oid, None)
                self._borrowers.pop(oid, None)
                self._created_ts.pop(oid, None)
                self._owners.pop(oid, None)
                if loc is None:
                    continue
                if isinstance(loc, (ShmLocation, ArenaLocation)):
                    self.used_bytes -= loc.size
                out.append((oid, loc))
        return out

    def num_objects(self) -> int:
        with self._lock:
            return len(self._entries)

    def entries_view(self):
        """(object_id, size_bytes, where, refcount) rows for the state
        API (the refcount column is what `rtpu memory` surfaces — ref
        analogue: `ray memory`'s per-object reference table)."""
        with self._lock:
            out = []
            for oid, loc in self._entries.items():
                refs = self._refcounts.get(oid, 0)
                if isinstance(loc, (ShmLocation, ArenaLocation)):
                    out.append((oid, loc.size, "shm", refs))
                elif isinstance(loc, InlineLocation):
                    out.append((oid, len(loc.data), "inline", refs))
                elif isinstance(loc, SpilledLocation):
                    out.append((oid, getattr(loc, "size", 0), "spilled",
                                refs))
                elif isinstance(loc, RemoteLocation):
                    out.append((oid, 0, "remote", refs))
                else:
                    out.append((oid, 0, type(loc).__name__, refs))
            return out

    def set_owner(self, object_id: ObjectID, owner: str) -> None:
        """Stamp the census owner label (first writer wins: the creation
        site knows the producer; later relabels would lie)."""
        if not _CENSUS or not owner:
            return
        with self._lock:
            if object_id in self._entries:
                self._owners.setdefault(object_id, owner)

    def owner_of(self, object_id: ObjectID) -> str:
        """The census owner label, or "" (plane off / never stamped)."""
        return self._owners.get(object_id, "")

    def census_rows(self, limit: int = 0) -> list:
        """Bounded per-object census rows for the cluster object census
        (ref analogue: the ObjectTableData the GCS object table serves).
        Each row: oid hex, size, where, refcount, borrower count, owner
        label, created wall ts (None when the data-obs plane is off),
        and how long the entry has sat at zero refs. ``limit`` keeps the
        reply frame bounded — largest entries win the cut."""
        import time

        now_w, now_m = time.time(), time.monotonic()
        with self._lock:
            rows = []
            for oid, loc in self._entries.items():
                if isinstance(loc, (ShmLocation, ArenaLocation)):
                    size, where = loc.size, "shm"
                elif isinstance(loc, InlineLocation):
                    size, where = len(loc.data), "inline"
                elif isinstance(loc, SpilledLocation):
                    size, where = getattr(loc, "size", 0), "spilled"
                elif isinstance(loc, RemoteLocation):
                    size, where = getattr(loc, "size", 0), "remote"
                else:
                    size, where = 0, type(loc).__name__
                created = self._created_ts.get(oid)
                zero = self._zero_since.get(oid)
                rows.append({
                    "object_id": oid.hex(),
                    "size_bytes": size,
                    "where": where,
                    "refcount": self._refcounts.get(oid, 0),
                    "borrowers": len(self._borrowers.get(oid, ())),
                    "owner": self._owners.get(oid, ""),
                    "created_ts": created,
                    "age_s": (round(now_w - created, 3)
                              if created is not None else None),
                    "zero_ref_s": (round(now_m - zero, 3)
                                   if zero is not None else None),
                })
        if limit and len(rows) > limit:
            rows.sort(key=lambda r: -(r["size_bytes"] or 0))
            rows = rows[:limit]
        return rows

    def spill_candidates(self, bytes_needed: int):
        """Least-recently-accessed local shared-memory objects summing to at
        least ``bytes_needed`` (ref analogue: the LRU EvictionPolicy choosing
        spill victims, object_manager/plasma/eviction_policy.h)."""
        with self._lock:
            local = [
                (self._access.get(oid, 0), oid, loc)
                for oid, loc in self._entries.items()
                if isinstance(loc, (ShmLocation, ArenaLocation))
            ]
        local.sort()
        out, total = [], 0
        for _seq, oid, loc in local:
            if total >= bytes_needed:
                break
            out.append((oid, loc))
            total += loc.size
        return out

    def replace_if(self, object_id: ObjectID, old: Location, new: Location) -> bool:
        """Compare-and-swap a location; False if the entry changed or was
        collected while the caller (spill/restore IO) ran."""
        with self._lock:
            if self._entries.get(object_id) is not old:
                return False
            if isinstance(old, (ShmLocation, ArenaLocation)):
                self.used_bytes -= old.size
            if isinstance(new, (ShmLocation, ArenaLocation)):
                self.used_bytes += new.size
            self._entries[object_id] = new
            return True

    def remote_entries(self, node_hex: str):
        """Snapshot of object ids whose location points at ``node_hex``
        (used to invalidate locations when that node dies)."""
        with self._lock:
            return [
                oid
                for oid, loc in self._entries.items()
                if isinstance(loc, RemoteLocation) and loc.node_id == node_hex
            ]
