"""User-defined metrics.

Ref analogue: python/ray/util/metrics.py (Counter/Gauge/Histogram) over
the metrics agent pipeline (src/ray/stats/) — here each process batches
its metric values and flushes them to the cluster KV under
``__metrics__/<process>``; ``get_metrics_report()`` aggregates across
every process for dashboards/tests (the Prometheus exposition layer can
read the same table).
"""

from __future__ import annotations

import atexit
import os
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle

FLUSH_INTERVAL_S = 0.5
KV_PREFIX = "__metrics__/"
# Every PROC_SAMPLE_INTERVAL_S the flusher re-records this process's
# cpu/rss gauges, which (a) feeds the per-node rows of `rtpu top` and
# (b) acts as a liveness refresh: the v2 snapshot's `ts` stays fresh
# while the process lives, so the head-side GC (core/gcs.py) can reap
# blobs whose writer died without aggregating ghosts forever.
PROC_SAMPLE_INTERVAL_S = 5.0


class _Registry:
    def __init__(self):
        self.lock = threading.Lock()
        # name -> ("counter"|"gauge"|"histogram", {tags_key: value})
        self.metrics: Dict[str, Tuple[str, Dict]] = {}
        # name -> (kind, description), recorded at metric construction —
        # feeds `# HELP` lines and tools/check_metric_names.py.
        self.meta: Dict[str, Tuple[str, str]] = {}
        # Names re-declared or re-recorded under a conflicting kind.
        self.kind_conflicts: Dict[str, Tuple[str, str]] = {}
        self._warned_kinds: set = set()
        self._flusher: Optional[threading.Thread] = None
        self._dirty = False

    def ensure_flusher(self):
        if self._flusher is None:
            self._flusher = threading.Thread(
                target=self._flush_loop, daemon=True
            )
            self._flusher.start()
            atexit.register(self.flush)

    def declare(self, name: str, kind: str, description: str):
        with self.lock:
            old = self.meta.get(name)
            if old is not None and old[0] != kind:
                self.kind_conflicts[name] = (old[0], kind)
                self._warn_kind_conflict(name, old[0], kind)
                return
            if old is None or (description and not old[1]):
                self.meta[name] = (kind, description)

    def _warn_kind_conflict(self, name: str, old: str, new: str):
        # Caller holds self.lock.
        if name in self._warned_kinds:
            return
        self._warned_kinds.add(name)
        warnings.warn(
            f"metric {name!r} already registered as a {old}; ignoring "
            f"records under conflicting kind {new!r} (the series would "
            f"be corrupted)",
            UserWarning,
            stacklevel=3,
        )

    def record(self, name: str, kind: str, tags_key: tuple, update):
        with self.lock:
            kind_, series = self.metrics.setdefault(name, (kind, {}))
            if kind_ != kind:
                # A second metric object reused the name with a different
                # kind: recording its update would write, say, a float
                # into a histogram series dict. Warn once and drop.
                self.kind_conflicts[name] = (kind_, kind)
                self._warn_kind_conflict(name, kind_, kind)
                return
            series[tags_key] = update(series.get(tags_key))
            self._dirty = True
        self.ensure_flusher()

    def _flush_loop(self):
        last_proc = 0.0
        while True:
            time.sleep(FLUSH_INTERVAL_S)
            try:
                now = time.monotonic()
                if now - last_proc >= PROC_SAMPLE_INTERVAL_S:
                    last_proc = now
                    _sample_process_stats()
                self.flush()
            except Exception:
                pass

    def flush(self):
        from ..core import runtime_context

        rt = runtime_context.current_runtime_or_none()
        if rt is None:
            return
        with self.lock:
            if not self._dirty:
                return
            self._dirty = False
            snapshot = {
                name: (kind, dict(series),
                       self.meta.get(name, ("", ""))[1])
                for name, (kind, series) in self.metrics.items()
            }
        # v2 envelope: the writer's node scopes the key (one node's
        # blobs GC together when it dies) and `ts` dates the snapshot
        # (a stale ts marks a dead pid's blob for head-side GC).
        node = getattr(rt, "node_id", None)
        node_hex = node.hex() if hasattr(node, "hex") else ""
        suffix = f"{node_hex}/{os.getpid()}" if node_hex else str(os.getpid())
        rt.kv_put(
            f"{KV_PREFIX}{suffix}",
            cloudpickle.dumps({
                "v": 2, "ts": time.time(), "pid": os.getpid(),
                "node": node_hex, "metrics": snapshot,
            }),
        )


_registry = _Registry()


class _Metric:
    KIND = ""

    def __init__(self, name: str, description: str = "",
                 tag_keys: Tuple[str, ...] = ()):
        self._name = name
        self._description = description
        self._tag_keys = tuple(tag_keys)
        self._default_tags: Dict[str, str] = {}
        _registry.declare(name, self.KIND, description)

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _key(self, tags: Optional[Dict[str, str]]) -> tuple:
        merged = dict(self._default_tags)
        if tags:
            merged.update(tags)
        return tuple(sorted(merged.items()))


class _BoundCounter:
    """Pre-resolved (name, tags-key) counter handle — see
    ``_Metric.with_tags``."""

    __slots__ = ("_name", "_key")

    def __init__(self, name: str, key: tuple):
        self._name = name
        self._key = key

    def inc(self, value: float = 1.0):
        _registry.record(
            self._name, "counter", self._key,
            lambda cur: (cur or 0.0) + value,
        )


class _BoundGauge:
    __slots__ = ("_name", "_key")

    def __init__(self, name: str, key: tuple):
        self._name = name
        self._key = key

    def set(self, value: float):
        _registry.record(self._name, "gauge", self._key, lambda cur: value)


class _BoundHistogram:
    __slots__ = ("_name", "_key", "_bounds")

    def __init__(self, name: str, key: tuple, bounds: List[float]):
        self._name = name
        self._key = key
        self._bounds = bounds

    def observe(self, value: float, exemplar: Optional[str] = None):
        Histogram._observe(self._name, self._bounds, self._key, (value,),
                           exemplar)


class Counter(_Metric):
    KIND = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None):
        _registry.record(
            self._name, self.KIND, self._key(tags),
            lambda cur: (cur or 0.0) + value,
        )

    def with_tags(self, **tags) -> _BoundCounter:
        """Resolve the tag set ONCE and return a slim recorder: hot
        paths (per-token decode taps, per-stripe transfer accounting)
        skip the dict merge + sort every ``inc`` otherwise pays."""
        return _BoundCounter(self._name, self._key(tags))


class Gauge(_Metric):
    KIND = "gauge"

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        _registry.record(
            self._name, self.KIND, self._key(tags), lambda cur: value
        )

    def with_tags(self, **tags) -> _BoundGauge:
        """Pre-resolved handle; see ``Counter.with_tags``."""
        return _BoundGauge(self._name, self._key(tags))


class Histogram(_Metric):
    KIND = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Optional[List[float]] = None,
                 tag_keys: Tuple[str, ...] = ()):
        super().__init__(name, description, tag_keys)
        self._boundaries = sorted(boundaries or
                                  [0.01, 0.1, 1.0, 10.0, 100.0])

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None,
                exemplar: Optional[str] = None):
        """``exemplar`` is a trace id attached to the bucket this value
        lands in (OpenMetrics exemplar: latest observation wins) — the
        one-hop link from a latency bucket to a recorded waterfall."""
        self._observe(self._name, self._boundaries, self._key(tags),
                      (value,), exemplar)

    def observe_many(self, values,
                     tags: Optional[Dict[str, str]] = None):
        """Every one of ``values`` in ONE registry call: for a path
        that keeps its observations and records them in batches."""
        if values:
            self._observe(self._name, self._boundaries, self._key(tags),
                          values)

    def with_tags(self, **tags) -> _BoundHistogram:
        """Pre-resolved handle; see ``Counter.with_tags``."""
        return _BoundHistogram(self._name, self._key(tags),
                               self._boundaries)

    @staticmethod
    def _observe(name: str, bounds: List[float], key: tuple, values,
                 exemplar: Optional[str] = None):
        ex_ts = time.time() if exemplar else 0.0

        def update(cur):
            cur = cur or {"count": 0, "sum": 0.0, "bounds": list(bounds),
                          "buckets": [0] * (len(bounds) + 1)}
            le: Any = "+Inf"
            for value in values:
                le = "+Inf"
                for i, b in enumerate(bounds):
                    if value <= b:
                        cur["buckets"][i] += 1
                        le = b
                        break
                else:
                    cur["buckets"][-1] += 1
                cur["count"] += 1
                cur["sum"] += value
            if exemplar:
                cur.setdefault("exemplars", {})[le] = {
                    "trace_id": exemplar, "value": value, "ts": ex_ts,
                }
            return cur

        _registry.record(name, "histogram", key, update)


class ItemTally:
    """The items of one stream that passed one hop and the seconds the
    hop took them (or, for a hop that counts a kind of item beside all
    of them, 1 for each of that kind), summed here and recorded every
    ``FLUSH_ITEMS`` items and at ``flush()``: ``Counter.inc`` takes the
    registry's lock and builds a closure, which a per-item path cannot
    pay several times over. One thread writes a tally; ``items_to`` and
    ``seconds_to`` are counters, or ``with_tags`` handles of counters."""

    FLUSH_ITEMS = 64

    __slots__ = ("_items_to", "_seconds_to", "items", "seconds")

    def __init__(self, items_to, seconds_to):
        self._items_to = items_to
        self._seconds_to = seconds_to
        self.items = 0
        self.seconds = 0.0

    def item(self, seconds: float) -> bool:
        """One more item; True if that recorded the sums so far."""
        self.items += 1
        self.seconds += seconds
        if self.items >= self.FLUSH_ITEMS:
            self.flush()
            return True
        return False

    def flush(self) -> None:
        if self.items:
            self._items_to.inc(self.items)
            self._seconds_to.inc(self.seconds)
            self.items = 0
            self.seconds = 0.0


# Per-process resource series, recorded by the flusher's periodic
# liveness sample (`_sample_process_stats`). Identity tags (node, pid)
# keep writers distinct; sum over pid for a node's total RSS, rate the
# cpu counter for CPU%.
PROCESS_CPU = Counter(
    "ray_tpu_process_cpu_seconds_total",
    "Cumulative CPU seconds (user+sys) of one ray_tpu process.",
    tag_keys=("node", "pid"),
)
PROCESS_RSS = Gauge(
    "ray_tpu_process_rss_bytes",
    "Resident set size of one ray_tpu process.",
    tag_keys=("node", "pid"),
)
_last_cpu_seconds = 0.0


def declared_metrics() -> Dict[str, Tuple[str, str]]:
    """Every metric declared in this process: name -> (kind, description).
    Data source for tools/check_metric_names.py."""
    with _registry.lock:
        return dict(_registry.meta)


def declaration_conflicts() -> Dict[str, Tuple[str, str]]:
    """Names registered under two different kinds: name -> (old, new)."""
    with _registry.lock:
        return dict(_registry.kind_conflicts)


def _merge_histogram(cur: Dict, value: Dict) -> Dict:
    """Merge two histogram series points. Identical boundaries sum
    bucket-wise; DIFFERENT boundaries merge on the union of bounds —
    each source bucket (b_{i-1}, b_i] lands in the union bucket whose
    upper edge is exactly b_i, so cumulative counts stay exact at every
    original boundary. (The old zip() truncated the longer bucket list
    silently, dropping observations.) Exemplars are keyed by their `le`
    bound, so they merge independently of rebucketing — the newest
    observation per bound wins, matching OpenMetrics semantics."""
    if cur.get("bounds", []) == value.get("bounds", []):
        return {
            "count": cur["count"] + value["count"],
            "sum": cur["sum"] + value["sum"],
            "bounds": list(cur.get("bounds", [])),
            "buckets": [
                a + b for a, b in zip(cur["buckets"], value["buckets"])
            ],
            **_merged_exemplars(cur, value),
        }
    bounds = sorted(set(cur.get("bounds", [])) | set(value.get("bounds", [])))
    index = {b: i for i, b in enumerate(bounds)}

    def rebucket(src: Dict) -> List[float]:
        out = [0] * (len(bounds) + 1)
        src_bounds = src.get("bounds", [])
        for i, c in enumerate(src["buckets"]):
            if i < len(src_bounds):
                out[index[src_bounds[i]]] += c
            else:
                out[-1] += c  # overflow bucket maps to union overflow
        return out

    return {
        "count": cur["count"] + value["count"],
        "sum": cur["sum"] + value["sum"],
        "bounds": bounds,
        "buckets": [a + b for a, b in zip(rebucket(cur), rebucket(value))],
        **_merged_exemplars(cur, value),
    }


def _merged_exemplars(cur: Dict, value: Dict) -> Dict:
    """Union of two histogram points' exemplar maps (newest ts wins per
    `le` key); {} when neither side carries any — the merged point then
    has no "exemplars" key at all, like an unobserved series."""
    a = cur.get("exemplars") or {}
    b = value.get("exemplars") or {}
    if not a and not b:
        return {}
    merged = dict(a)
    for le, ex in b.items():
        old = merged.get(le)
        if old is None or ex.get("ts", 0.0) >= old.get("ts", 0.0):
            merged[le] = ex
    return {"exemplars": merged}


def _sample_process_stats() -> None:
    """Record this process's cpu/rss (from /proc, psutil-free) into the
    standard pipeline — the per-node resource rows of `rtpu top` and
    the head TSDB derive CPU use via counter->rate (no-op off Linux)."""
    from ..core import runtime_context

    rt = runtime_context.current_runtime_or_none()
    node = getattr(rt, "node_id", None) if rt is not None else None
    tags = {"node": node.hex() if hasattr(node, "hex") else "",
            "pid": str(os.getpid())}
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        PROCESS_RSS.set(pages * os.sysconf("SC_PAGE_SIZE"), tags=tags)
        with open("/proc/self/stat") as f:
            parts = f.read().split()
        tick = os.sysconf("SC_CLK_TCK")
        cpu = (int(parts[13]) + int(parts[14])) / tick
    except Exception:
        return
    global _last_cpu_seconds
    if cpu > _last_cpu_seconds:
        PROCESS_CPU.inc(cpu - _last_cpu_seconds, tags=tags)
        _last_cpu_seconds = cpu


def decode_snapshot(blob: bytes) -> Tuple[Dict, float]:
    """One flushed KV blob -> (metrics dict, snapshot ts). Accepts both
    the v2 envelope and the pre-envelope bare dict (ts 0.0: age
    unknown, exempt from staleness GC)."""
    snapshot = cloudpickle.loads(blob)
    if isinstance(snapshot, dict) and snapshot.get("v") == 2:
        return snapshot.get("metrics") or {}, float(snapshot.get("ts", 0.0))
    return snapshot, 0.0


def merge_snapshot(out: Dict[str, Dict], snapshot: Dict) -> None:
    """Fold one process snapshot into a report accumulator: counters and
    histograms sum across processes; gauges keep the latest write per
    tag set (identity tags keep writers distinct — see _telemetry)."""
    for name, item in snapshot.items():
        kind, series = item[0], item[1]
        help_ = item[2] if len(item) > 2 else ""
        entry = out.setdefault(
            name, {"type": kind, "series": {}, "help": ""}
        )
        if help_ and not entry.get("help"):
            entry["help"] = help_
        for tags_key, value in series.items():
            cur = entry["series"].get(tags_key)
            if kind == "counter":
                entry["series"][tags_key] = (cur or 0.0) + value
            elif kind == "gauge":
                entry["series"][tags_key] = value
            elif cur is None:  # histogram, first sighting
                entry["series"][tags_key] = dict(value)
            else:
                entry["series"][tags_key] = _merge_histogram(cur, value)


def aggregate_blobs(blobs) -> Dict[str, Dict]:
    """Aggregate an iterable of flushed KV blobs into one report dict.
    Shared by the driver-side report below and the head GCS's TSDB
    sampler (core/gcs.py), which reads its KV table directly. Corrupt
    blobs are skipped — one wedged writer must not blind the report."""
    out: Dict[str, Dict] = {}
    for blob in blobs:
        if not blob:
            continue
        try:
            snapshot, _ts = decode_snapshot(blob)
        except Exception:
            continue
        merge_snapshot(out, snapshot)
    return out


def local_snapshot() -> Dict[str, Tuple]:
    """This process's registry in flushed-snapshot form, without going
    through (or requiring) a runtime. The head GCS uses it to publish
    its own ray_tpu_slo_* gauges when it runs standalone."""
    with _registry.lock:
        return {
            name: (kind, dict(series),
                   _registry.meta.get(name, ("", ""))[1])
            for name, (kind, series) in _registry.metrics.items()
        }


def get_metrics_report() -> Dict[str, Dict]:
    """Aggregate every process's flushed metrics (ref analogue: scraping
    the metrics agents). Counters/histograms sum across processes; gauges
    keep the latest non-None value per tag set."""
    from ..core import runtime_context

    rt = runtime_context.current_runtime()
    _registry.flush()
    return aggregate_blobs(
        rt.kv_get(key) for key in rt.kv_keys(KV_PREFIX)
    )
