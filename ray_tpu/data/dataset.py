"""Dataset: lazy, streaming, distributed data over blocks.

Ref analogue: python/ray/data/dataset.py Dataset (:158) with the logical
plan + streaming execution model of _internal/execution/ (SURVEY.md §2.3):
transforms build a lazy STAGE pipeline (streaming_executor.py) — fused
per-block task chains plus actor-pool stages for stateful transforms —
executed with per-stage bounded in-flight windows (backpressure). Global
ops (shuffle/sort/repartition) run as distributed two-stage shuffles
(shuffle.py) whose intermediate partitions never touch the driver.
"""

from __future__ import annotations

import inspect
import itertools
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

from .block import (
    Block,
    BlockAccessor,
    batch_to_format,
    concat_blocks,
    from_numpy_dict,
    normalize_to_block,
)
from .context import DataContext
from .streaming_executor import (
    ActorStage,
    TaskStage,
    UnionSource,
    ZipSource,
    execute,
    execute_refs,
)


# ----------------------------------------------------------- logical plan

class _Op:
    """A per-block transform (fusable)."""

    def apply(self, block: Block) -> Block:
        raise NotImplementedError


class _MapBatches(_Op):
    def __init__(self, fn, batch_format: str, batch_size: Optional[int]):
        self.fn = fn
        self.batch_format = batch_format
        self.batch_size = batch_size

    def apply(self, block: Block) -> Block:
        acc = BlockAccessor(block)
        n = acc.num_rows()
        bs = self.batch_size or max(n, 1)
        out = []
        for start in range(0, max(n, 1), bs):
            sub = acc.slice(start, min(start + bs, n)) if n else block
            batch = batch_to_format(sub, self.batch_format)
            res = self.fn(batch)
            out.append(normalize_to_block(res))
            if n == 0:
                break
        return concat_blocks(out) if out else block


class _MapRows(_Op):
    def __init__(self, fn):
        self.fn = fn

    def apply(self, block: Block) -> Block:
        from .block import from_rows

        rows = [self.fn(dict(r)) for r in BlockAccessor(block).iter_rows()]
        return from_rows(rows)


class _FlatMapRows(_Op):
    def __init__(self, fn):
        self.fn = fn

    def apply(self, block: Block) -> Block:
        from .block import from_rows

        rows = []
        for r in BlockAccessor(block).iter_rows():
            rows.extend(self.fn(dict(r)))
        return from_rows(rows)


class _FilterRows(_Op):
    def __init__(self, fn):
        self.fn = fn

    def apply(self, block: Block) -> Block:
        acc = BlockAccessor(block)
        keep = np.asarray(
            [bool(self.fn(dict(r))) for r in acc.iter_rows()], dtype=bool
        )
        return acc.take_indices(np.nonzero(keep)[0])


def _apply_chain(source: Callable[[], Block], ops: Sequence[_Op]) -> Block:
    block = source()
    for op in ops:
        block = op.apply(block)
    return block


# ---------------------------------------------------------- dlpack export

def _dlpack_alias(arr: np.ndarray) -> np.ndarray:
    """Writable-FLAGGED alias of a store-backed array for DLPack export
    (SURVEY.md §5.8 zero-copy hand-off). The store's sealed views are
    readonly, and numpy refuses to export readonly arrays through
    DLPack (the protocol cannot signal readonly); jax arrays are
    immutable, so letting jax alias the immutable store page is sound —
    the flag flip exists ONLY to satisfy the export check. Never write
    through the returned array. The alias carries a reference chain
    (jax capsule -> alias -> ctypes buffer -> original array -> store
    mapping) so the shm pages outlive every consumer."""
    if arr.flags.writeable:
        return arr
    if not arr.flags.c_contiguous:
        raise ValueError("dlpack export needs a contiguous array")
    import ctypes

    buf = (ctypes.c_char * arr.nbytes).from_address(
        arr.ctypes.data
    )
    buf._rtpu_pin = arr  # keeps the readonly view (and its mapping) alive
    return np.frombuffer(buf, dtype=arr.dtype).reshape(arr.shape)


# -------------------------------------------------------------- the API

class Dataset:
    def __init__(self, sources: List[Callable[[], Block]],
                 ops: Optional[List[Any]] = None, *, _pin: Any = None):
        # sources: zero-arg callables producing the input blocks (read tasks
        # or in-memory closures); ops: stage pipeline — a legacy flat op
        # list is wrapped into one fused TaskStage. _pin keeps upstream
        # shuffle partitions alive while this dataset's refs are consumed.
        self._sources = sources
        if ops and not isinstance(ops[0], (TaskStage, ActorStage)):
            ops = [TaskStage(ops)]
        self._stages: List[Any] = list(ops) if ops else [TaskStage([])]
        self._pin = _pin

    @property
    def _ops(self) -> List[_Op]:
        """Flat fused op chain (only valid for single-task-stage plans)."""
        assert len(self._stages) == 1 and isinstance(
            self._stages[0], TaskStage
        ), "plan has actor stages; use _stages"
        return self._stages[0].ops

    # ---- construction helpers (used by read_api) ----

    @classmethod
    def from_blocks(cls, blocks: List[Block], *, _pin: Any = None
                    ) -> "Dataset":
        return cls([(lambda b=b: b) for b in blocks], _pin=_pin)

    @classmethod
    def _from_refs(cls, refs: List[Any], *, _pin: Any = None) -> "Dataset":
        """Blocks already in the object store (e.g. shuffle output): each
        source pulls its ref where it executes — never via the driver."""

        def make(ref):
            def pull():
                import ray_tpu

                return ray_tpu.get(ref)

            return pull

        ds = cls([make(r) for r in refs], _pin=(_pin, refs))
        return ds

    # ---- lazy transforms (per-block: fused) ----

    def _with_op(self, op: _Op) -> "Dataset":
        last = self._stages[-1]
        if isinstance(last, TaskStage):
            stages = self._stages[:-1] + [last.with_op(op)]
        else:
            stages = self._stages + [TaskStage([op])]
        return Dataset(self._sources, stages, _pin=self._pin)

    def map_batches(self, fn, *, batch_format: str = "numpy",
                    batch_size: Optional[int] = None,
                    concurrency: Optional[int] = None,
                    fn_constructor_args: tuple = (),
                    fn_constructor_kwargs: Optional[dict] = None,
                    ray_remote_args: Optional[dict] = None) -> "Dataset":
        """Per-batch transform. A CLASS argument becomes a stateful
        actor-pool stage of ``concurrency`` members, each constructing the
        class once (ref: actor_pool_map_operator.py — the operator for
        model-loading transforms)."""
        if inspect.isclass(fn):
            stage = ActorStage(
                fn, fn_constructor_args, fn_constructor_kwargs or {},
                concurrency or 2, batch_format, batch_size,
                ray_remote_args,
            )
            return Dataset(
                self._sources, self._stages + [stage], _pin=self._pin
            )
        return self._with_op(_MapBatches(fn, batch_format, batch_size))

    def map(self, fn) -> "Dataset":
        return self._with_op(_MapRows(fn))

    def flat_map(self, fn) -> "Dataset":
        return self._with_op(_FlatMapRows(fn))

    def filter(self, fn) -> "Dataset":
        return self._with_op(_FilterRows(fn))

    def add_column(self, name: str, fn) -> "Dataset":
        def add(batch: Dict[str, np.ndarray]):
            batch[name] = np.asarray(fn(batch))
            return batch

        return self.map_batches(add)

    def drop_columns(self, cols: List[str]) -> "Dataset":
        return self.map_batches(
            lambda b: {k: v for k, v in b.items() if k not in cols}
        )

    def select_columns(self, cols: List[str]) -> "Dataset":
        return self.map_batches(
            lambda b: {k: v for k, v in b.items() if k in cols}
        )

    # ---- global ops (distributed two-stage shuffles) ----

    def _use_remote(self) -> bool:
        from ..core import runtime_context

        ctx = DataContext.get_current()
        return ctx.use_remote_tasks and runtime_context.is_initialized()

    def _shuffle_plan(self, *, materialize: bool = False):
        """(sources, fusable ops, hold) for a shuffle's map stage: the
        fused op chain when the plan is one task stage, else the
        pre-executed block refs (actor stages must run before
        partitioning; sort also materializes so boundary sampling doesn't
        execute the chain twice). ``hold`` must stay pinned until the
        shuffle output is consumed — it keeps the intermediate refs alive
        past this driver frame."""
        single_task = (
            not self._is_node_plan()
            and len(self._stages) == 1
            and isinstance(self._stages[0], TaskStage)
        )
        if single_task and not materialize:
            return self._sources, self._stages[0].ops, None
        refs = list(execute_refs(self._sources, self._stages))

        def make(ref):
            def pull():
                import ray_tpu

                return ray_tpu.get(ref)

            return pull

        return [make(r) for r in refs], [], refs

    def _shuffled(self, num: int, assigner: str, arg=None,
                  postprocess=None) -> "Dataset":
        from . import shuffle as _shuffle

        srcs, ops, hold = self._shuffle_plan()
        reduce_refs, pin = _shuffle.shuffle(
            srcs, ops, num, assigner, arg, postprocess
        )
        return Dataset._from_refs(
            reduce_refs, _pin=(self._pin, pin, hold)
        )

    def repartition(self, num_blocks: int) -> "Dataset":
        if self._use_remote():
            return self._shuffled(num_blocks, "contiguous")
        full = self._materialize_table()
        n = full.num_rows
        sizes = [n // num_blocks + (1 if i < n % num_blocks else 0)
                 for i in range(num_blocks)]
        blocks, start = [], 0
        for s in sizes:
            blocks.append(full.slice(start, s))
            start += s
        return Dataset.from_blocks(blocks)

    def random_shuffle(self, *, seed: Optional[int] = None) -> "Dataset":
        if self._use_remote():
            import random as _random

            num = max(1, self.num_blocks())
            return self._shuffled(
                num, "random",
                seed if seed is not None else _random.randrange(2 ** 31),
            )
        full = self._materialize_table()
        idx = np.random.RandomState(seed).permutation(full.num_rows)
        shuffled = BlockAccessor(full).take_indices(idx)
        num = max(1, self.num_blocks())
        return Dataset.from_blocks([shuffled]).repartition(num)

    def sort(self, key: str, *, descending: bool = False) -> "Dataset":
        if self._use_remote():
            from . import shuffle as _shuffle

            # Materialize once: boundary sampling + shuffle both read the
            # stored blocks instead of re-running the upstream chain.
            srcs, ops, hold = self._shuffle_plan(materialize=True)
            num = max(1, len(srcs))
            bounds = _shuffle.sample_sort_boundaries(srcs, ops, key, num)
            reduce_refs, pin = _shuffle.shuffle(
                srcs, ops, num, "range", (key, bounds, descending),
                _shuffle._SortBlock(key, descending),
            )
            return Dataset._from_refs(
                reduce_refs, _pin=(self._pin, pin, hold)
            )
        full = self._materialize_table()
        col = BlockAccessor(full).to_numpy()[key]
        idx = np.argsort(col, kind="stable")
        if descending:
            idx = idx[::-1]
        return Dataset.from_blocks([BlockAccessor(full).take_indices(idx)])

    def union(self, *others: "Dataset") -> "Dataset":
        """Streaming concatenation: upstream datasets execute their own
        chains and their block streams concatenate in order — an
        operator-DAG fan-in, nothing materializes on the driver (ref:
        Dataset.union over the executor's operator graph)."""
        inputs = [self, *others]
        return Dataset(UnionSource(inputs),
                       _pin=tuple(d._pin for d in inputs))

    def zip(self, other: "Dataset") -> "Dataset":
        """Pairwise block zip: block i of ``self`` merges columns with
        block i of ``other`` (right-side name collisions get a ``_1``
        suffix). Both datasets must be identically blocked — same block
        count and per-block row counts (ref: Dataset.zip)."""
        return Dataset(ZipSource(self, other),
                       _pin=(self._pin, other._pin))

    def _is_node_plan(self) -> bool:
        return isinstance(self._sources, (UnionSource, ZipSource))

    def _ensure_flat(self) -> "Dataset":
        """A dataset whose sources are a flat thunk list — node-sourced
        plans (union/zip) materialize their blocks first (needed by the
        source-indexed paths: split, streaming_split, shuffles)."""
        return self.materialize() if self._is_node_plan() else self

    def limit(self, n: int) -> "Dataset":
        out, taken = [], 0
        for block in self._iter_blocks():
            if taken >= n:
                break
            take = min(n - taken, block.num_rows)
            out.append(block.slice(0, take))
            taken += take
        return Dataset.from_blocks(out or [from_numpy_dict({})])

    def groupby(self, key: str):
        from .grouped_data import GroupedData

        return GroupedData(self, key)

    # ---- execution ----

    def _iter_blocks(self) -> Iterator[Block]:
        """Streaming execution through the stage pipeline (per-stage
        bounded windows = per-operator backpressure; see
        streaming_executor.py)."""
        from .streaming_executor import ExecStats

        self._last_stats = ExecStats()
        yield from execute(self._sources, self._stages, self._last_stats)

    def iter_batches(
        self,
        *,
        batch_size: Optional[int] = 256,
        batch_format: str = "numpy",
        drop_last: bool = False,
    ) -> Iterator[Any]:
        """NOTE: numpy batches may be READ-ONLY views over the shared
        object store (the zero-copy read path); copy before mutating in
        place (``batch["x"] = batch["x"] * s``, not ``*=``)."""
        leftover: Optional[Block] = None
        for block in self._iter_blocks():
            if leftover is not None and leftover.num_rows:
                block = concat_blocks([leftover, block])
                leftover = None
            if batch_size is None:
                yield batch_to_format(block, batch_format)
                continue
            acc = BlockAccessor(block)
            n = acc.num_rows()
            start = 0
            while n - start >= batch_size:
                yield batch_to_format(
                    acc.slice(start, start + batch_size), batch_format
                )
                start += batch_size
            if start < n:
                leftover = acc.slice(start, n)
        if leftover is not None and leftover.num_rows and not drop_last:
            yield batch_to_format(leftover, batch_format)

    def iter_blocks_refs(self) -> Iterator[Any]:
        """Streaming execution yielding per-block ObjectRefs (the blocks
        stay in the object store; nothing materializes on the driver) —
        the consumption surface backpressure acts through."""
        from .streaming_executor import ExecStats

        self._last_stats = ExecStats()
        yield from execute_refs(self._sources, self._stages,
                                self._last_stats)

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for block in self._iter_blocks():
            yield from BlockAccessor(block).iter_rows()

    def iter_torch_batches(self, *, batch_size: int = 256,
                           dtypes=None, device=None,
                           drop_last: bool = False) -> Iterator[Any]:
        """Batches as {column: torch.Tensor} dicts (ref:
        iterator.py iter_torch_batches:242). Tensors wrap the numpy
        batch buffers without copy where torch allows (the store's
        read-only views are cloned first — torch cannot alias
        non-writable memory without a warning)."""
        import numpy as np
        import torch

        for batch in self.iter_batches(
            batch_size=batch_size, batch_format="numpy",
            drop_last=drop_last,
        ):
            out = {}
            for k, v in batch.items():
                arr = np.ascontiguousarray(v) if not (
                    isinstance(v, np.ndarray) and v.flags["C_CONTIGUOUS"]
                ) else v
                if isinstance(arr, np.ndarray) and \
                        not arr.flags.writeable:
                    arr = arr.copy()
                if arr.dtype == object:
                    out[k] = list(arr)  # strings/ragged pass through
                    continue
                t = torch.from_numpy(arr)
                if dtypes is not None:
                    want = (dtypes.get(k) if isinstance(dtypes, dict)
                            else dtypes)
                    if want is not None:
                        t = t.to(want)
                if device is not None:
                    t = t.to(device)
                out[k] = t
            yield out

    def iter_jax_batches(self, *, batch_size: int = 256, device=None,
                         drop_last: bool = True,
                         zero_copy: Optional[bool] = None
                         ) -> Iterator[Any]:
        """Batches as jax arrays with one-batch device prefetch (the HBM
        double-buffering path — SURVEY.md §7 phase 8).

        The batch arrays are numpy VIEWS over the shared-memory object
        store (the store's 64-byte-aligned layout exists for this;
        SURVEY.md §5.8's zero-copy hand-off). ``zero_copy=True`` imports
        them into jax via dlpack — NO copy at all on the CPU backend
        (the jax array aliases the store pages); on accelerators the
        view feeds ``device_put``'s DMA directly, skipping the
        staging copy ``jnp.asarray`` of a non-owned buffer can make.
        Default: dlpack on the CPU backend, device_put elsewhere.
        NOTE (dlpack aliasing): jax must not be handed writable aliases
        of live store pages lightly — the store is immutable by
        contract, so read-only aliasing is sound here."""
        import jax
        import jax.numpy as jnp

        if zero_copy is None:
            zero_copy = jax.default_backend() == "cpu" and device is None
        # dlpack aliasing only lands on HOST memory: with a non-CPU
        # target (explicit device, or an accelerator default backend)
        # the data must move — fall through to device_put/asarray so
        # zero_copy=True cannot silently pin batches to CPU.
        if zero_copy and (
            (device is not None
             and getattr(device, "platform", "cpu") != "cpu")
            or (device is None and jax.default_backend() != "cpu")
        ):
            zero_copy = False

        def convert(v):
            if zero_copy:
                try:
                    # copy=False: alias or raise (never silently copy —
                    # jax's copying dlpack import is SLOWER than
                    # asarray, so only the true zero-copy path is worth
                    # taking). Store buffers are 64-byte aligned by the
                    # serialization layout precisely for this.
                    return jnp.from_dlpack(_dlpack_alias(v), copy=False)
                except Exception:
                    pass  # non-contiguous/unaligned/exotic: fall through
            if device is not None:
                return jax.device_put(v, device)
            return jnp.asarray(v)

        def put(batch):
            return {k: convert(v) for k, v in batch.items()}

        # Profiler annotations (inert unless a jax.profiler trace is
        # open): the wait on the upstream batch and the transfer's
        # enqueue, on the device trace's clock beside the step they feed.
        span = jax.profiler.TraceAnnotation
        it = self.iter_batches(batch_size=batch_size, drop_last=drop_last)
        prev = None
        while True:
            with span("data.next_batch"):
                batch = next(it, None)
            if batch is None:
                break
            with span("data.device_put"):
                nxt = put(batch)  # enqueue transfer before yielding previous
            if prev is not None:
                yield prev
            prev = nxt
        if prev is not None:
            yield prev

    # ---- consumption ----

    def _materialize_table(self) -> Block:
        return concat_blocks(list(self._iter_blocks()))

    def materialize(self) -> "Dataset":
        if self._use_remote():
            from .streaming_executor import ExecStats

            self._last_stats = ExecStats()
            refs = list(execute_refs(self._sources, self._stages,
                                     self._last_stats))
            out = Dataset._from_refs(refs, _pin=self._pin)
            out._last_stats = self._last_stats
            return out
        return Dataset.from_blocks(list(self._iter_blocks()))

    def take(self, n: int = 20) -> List[Dict[str, Any]]:
        return list(itertools.islice(self.iter_rows(), n))

    def take_all(self) -> List[Dict[str, Any]]:
        return list(self.iter_rows())

    def count(self) -> int:
        return sum(b.num_rows for b in self._iter_blocks())

    def schema(self):
        for block in self._iter_blocks():
            return block.schema
        return None

    def columns(self) -> List[str]:
        s = self.schema()
        return list(s.names) if s else []

    def num_blocks(self) -> int:
        if isinstance(self._sources, UnionSource):
            return sum(d.num_blocks() for d in self._sources.datasets)
        if isinstance(self._sources, ZipSource):
            return self._sources.left.num_blocks()
        return len(self._sources)

    def show(self, n: int = 20):
        for row in self.take(n):
            print(row)

    def to_numpy(self) -> Dict[str, np.ndarray]:
        return BlockAccessor(self._materialize_table()).to_numpy()

    def to_pandas(self):
        return self._materialize_table().to_pandas()

    def stats(self) -> str:
        """Per-stage / per-operator execution stats of the LAST executed
        pipeline on this dataset (wall per op, rows, bytes, blocks — ref
        analogue: data/_internal/stats.py ds.stats()); falls back to the
        static plan description before any execution."""
        last = getattr(self, "_last_stats", None)
        if last is not None and last.stage_names:
            return last.summary()
        nops = sum(
            len(s.ops) if isinstance(s, TaskStage) else 1
            for s in self._stages
        )
        return (f"Dataset(blocks={self.num_blocks()}, "
                f"stages={len(self._stages)}, ops={nops})")

    # ---- write sinks (distributed per-block writes) ----

    def write_parquet(self, path: str, **kw) -> List[str]:
        """One parquet file per block, written by remote tasks (ref:
        dataset.py write_parquet:2823)."""
        from .datasink import write_blocks

        return write_blocks(self, path, "parquet", **kw)

    def write_csv(self, path: str, **kw) -> List[str]:
        from .datasink import write_blocks

        return write_blocks(self, path, "csv", **kw)

    def write_json(self, path: str, **kw) -> List[str]:
        from .datasink import write_blocks

        return write_blocks(self, path, "json", **kw)

    def write_tfrecords(self, path: str, **kw) -> List[str]:
        """One TFRecord file of tf.train.Example protos per block (ref:
        dataset write_tfrecords; codec in data/tfrecords.py)."""
        from .datasink import write_blocks

        return write_blocks(self, path, "tfrecords", **kw)

    def write_avro(self, path: str, **kw) -> List[str]:
        """One Avro Object Container File per block (ref:
        write_avro; codec in data/avro.py)."""
        from .datasink import write_blocks

        return write_blocks(self, path, "avro", **kw)

    def write_webdataset(self, path: str, **kw) -> List[str]:
        """One WebDataset tar shard per block (ref: write_webdataset)."""
        from .datasink import write_blocks

        return write_blocks(self, path, "webdataset", **kw)

    def write_numpy(self, path: str, *, column: str = "data") -> List[str]:
        from .datasink import write_blocks

        return write_blocks(
            self.select_columns([column]), path, "npy"
        )

    def to_random_access(self, key: str, *, num_workers: int = 2):
        """Materialize into a range-partitioned actor pool supporting
        O(1) point lookups by ``key`` (ref analogue:
        Dataset.to_random_access_dataset / random_access_dataset.py)."""
        from .random_access import RandomAccessDataset

        return RandomAccessDataset(self, key, num_workers=num_workers)

    # ---- splitting for train ingest ----

    def streaming_split(self, n: int, *, equal: bool = True
                        ) -> List["DataIterator"]:
        """Per-worker shard iterators (ref: dataset.py:1269
        streaming_split). Shard i consumes source blocks i, i+n, ..."""
        from .iterator import DataIterator

        if self._is_node_plan() and self._use_remote():
            # DAG plans stream through ONE shared coordinator actor
            # (executes the plan once, deals blocks round-robin with
            # bounded buffers) — splitting must not materialize the
            # upstream (ref: OutputSplitter behind streaming_split).
            import cloudpickle

            import ray_tpu
            from .iterator import _SplitCoordinator

            coord = ray_tpu.remote(max_concurrency=n + 1)(
                _SplitCoordinator
            ).remote(cloudpickle.dumps(self), n)
            return [DataIterator(self, shard_index=i, num_shards=n,
                                 coordinator=coord)
                    for i in range(n)]
        flat = self._ensure_flat()
        return [DataIterator(flat, shard_index=i, num_shards=n)
                for i in range(n)]

    def split(self, n: int) -> List["Dataset"]:
        flat = self._ensure_flat()
        return [
            Dataset(flat._sources[i::n], list(flat._stages),
                    _pin=flat._pin)
            for i in range(n)
        ]

    def split_at_indices(self, indices: List[int]) -> List["Dataset"]:
        """Split at global row offsets (ref: dataset.split_at_indices);
        materializes block boundaries."""
        bounds = list(indices) + [None]
        out: List[List[Block]] = [[] for _ in bounds]
        row = 0
        part = 0
        for block in self._iter_blocks():
            off = 0
            while off < block.num_rows:
                end = bounds[part]
                if end is None:
                    out[part].append(block.slice(
                        off, block.num_rows - off
                    ))
                    off = block.num_rows
                    continue
                take = min(block.num_rows - off, end - row)
                if take > 0:
                    out[part].append(block.slice(off, take))
                    off += take
                    row += take
                if row >= end:
                    part += 1
            # blocks exhausted; advance parts with zero-length bounds
            while bounds[part] is not None and row >= bounds[part]:
                part += 1
        from .block import from_numpy_dict

        return [
            Dataset.from_blocks(blocks or [from_numpy_dict({})],
                                _pin=self._pin)
            for blocks in out
        ]

    def split_proportionately(self, proportions: List[float]
                              ) -> List["Dataset"]:
        """Split by fractions; the remainder forms the final split
        (ref: dataset.split_proportionately)."""
        if not proportions or sum(proportions) >= 1.0 or \
                any(p <= 0 for p in proportions):
            raise ValueError(
                "proportions must be positive and sum to < 1"
            )
        n = self.count()
        indices, acc = [], 0
        for p in proportions:
            acc += int(n * p)
            indices.append(acc)
        return self.split_at_indices(indices)

    def train_test_split(self, test_size: float, *,
                         shuffle: bool = False,
                         seed: Optional[int] = None
                         ) -> List["Dataset"]:
        """(train, test) split (ref: dataset.train_test_split)."""
        if not 0 < test_size < 1:
            raise ValueError("test_size must be in (0, 1)")
        ds = self.random_shuffle(seed=seed) if shuffle else self
        train, test = ds.split_proportionately([1.0 - test_size])
        return [train, test]

    def random_sample(self, fraction: float, *,
                      seed: Optional[int] = None) -> "Dataset":
        """Bernoulli row sample (ref: dataset.random_sample); lazy."""
        if not 0 <= fraction <= 1:
            raise ValueError("fraction must be in [0, 1]")

        def sample(batch):
            import zlib

            import numpy as _np

            n = len(next(iter(batch.values()), []))
            if seed is not None:
                # Derive a per-batch stream by mixing the seed with the
                # batch CONTENT — the same closure runs in every block's
                # worker, so reusing `seed` directly would draw the same
                # mask offsets in every block (position-correlated, not
                # i.i.d.).
                first = _np.ascontiguousarray(
                    next(iter(batch.values()))
                )
                salt = zlib.crc32(first.tobytes())
                rng = _np.random.default_rng((seed, salt))
            else:
                rng = _np.random.default_rng()
            mask = rng.random(n) < fraction
            return {k: _np.asarray(v)[mask] for k, v in batch.items()}

        return self.map_batches(sample, batch_format="numpy")

    def unique(self, column: str) -> List[Any]:
        """Distinct values of one column (ref: dataset.unique)."""
        seen = {}
        for batch in self.select_columns([column]).iter_batches(
            batch_format="numpy"
        ):
            for v in batch[column]:
                key = v.item() if hasattr(v, "item") else v
                seen.setdefault(key, None)
        return list(seen)

    def rename_columns(self, mapping: Dict[str, str]) -> "Dataset":
        """Rename columns lazily (ref: dataset.rename_columns)."""

        def rename(batch):
            return {mapping.get(k, k): v for k, v in batch.items()}

        return self.map_batches(rename, batch_format="numpy")

    # -- column aggregates (ref: dataset.sum/min/max/mean/std) --

    def _agg_column(self, on: str):
        import numpy as _np

        parts = [
            _np.asarray(b[on])
            for b in self.select_columns([on]).iter_batches(
                batch_format="numpy"
            )
            if len(b[on])
        ]
        return _np.concatenate(parts) if parts else _np.asarray([])

    def sum(self, on: str):
        vals = self._agg_column(on)
        return vals.sum().item() if vals.size else None

    def min(self, on: str):
        vals = self._agg_column(on)
        return vals.min().item() if vals.size else None

    def max(self, on: str):
        vals = self._agg_column(on)
        return vals.max().item() if vals.size else None

    def mean(self, on: str):
        vals = self._agg_column(on)
        return vals.mean().item() if vals.size else None

    def std(self, on: str, ddof: int = 1):
        vals = self._agg_column(on)
        return (vals.std(ddof=ddof).item()
                if vals.size > ddof else None)

    def __repr__(self):
        return self.stats()
