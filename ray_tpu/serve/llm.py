"""Continuous-batched LLM serving on TPU with a paged KV cache.

The capability the reference lacks (SURVEY.md §7 hard parts: "continuous
batching + paged KV cache on TPU for Serve; reference has only
request-level batching"): an engine where requests JOIN and LEAVE the
running decode loop — each decode step batches every active slot into one
[B, 1] forward pass (HBM-bandwidth bound; batching amortizes the weight
reads), while prefill runs per admission into power-of-two length buckets.

KV memory is PAGED (models/generation.py PagedKVCache): a shared pool of
fixed-size token pages with a per-slot page table. A request reserves only
the pages its prompt + max_new_tokens need — not a dense max_len row — so
total KV is bounded by actual demand, long-context requests coexist with
short ones, and pages recycle the moment a request finishes. Admission
waits for pages instead of OOMing. All shapes stay static for XLA.

A model with window layers has a pool and a page table for each
attention kind, under the one allocator: a request reserves, at
admission and per kind, every page of its context in the "full" pool and
at most a ring of ``window / page + 1`` in the "window" pool, which the
decode programs then turn through by themselves. The loop allocates and
frees nothing between admission and finish.

A model of retention layers (kind "state") holds a state of fixed size
a slot and no pages: its pool has none, a request needs none, and
admission is by slot alone. ``max_len`` then bounds the rotary
positions and the prefill bucket, not memory.

The loop runs ONE decode step ahead of the one it reads. What a step
needs lies on the device: the last tokens (a step's output is the next
one's input; a prefill sets its slot's), the PRNG key (split inside the
program) and the active mask (sent again only when membership changes).
One turn of ``LLMEngine._loop``:

1. admit: prefill queued requests into free slots, blocking for each
   first token (the prefill queues behind the step in flight);
2. inputs: who decodes in the step queued next: every open slot that
   its token count, the token in flight included, has not ended;
3. decode: dispatch step k+1, start its read-back's copy to the host;
4. readback: block on step k's tokens while step k+1 runs;
5. emit: step k's tokens to their requests, the counters, the finishes.

So no step writes a K/V row past the pages its slot holds: a slot that
ends by its count is left out of the next step before its last token is
read. Only ``eos_token`` is known at the read-back alone: the one step
already queued for that slot is a wasted row inside its own pages, its
token is dropped, and slot and pages return to the allocator when that
step is read, so a prefill that reuses them queues behind the stray
write. A slot freed at step k's read-back is therefore taken by a
waiting request one step later than a loop that read each step before
the next would give it.
"""

from __future__ import annotations

import collections
import math
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


class _Streams:
    """Who takes tokens from the engine's requests, for
    ``LLMEngine.stats()["stream"]``: the ``_Request.tokens()`` iterators
    now running, and the sums of those that have ended. A request's own
    account is written by its taking thread alone; the lock is taken
    when an iterator starts, when it ends, and by a reading."""

    def __init__(self):
        self.lock = threading.Lock()
        self.running: set = set()
        self.ended = {"tokens_taken": 0, "taken_lag_s": 0.0, "held_s": 0.0}

    def begin(self, req: "_Request") -> None:
        with self.lock:
            self.running.add(req)

    def end(self, req: "_Request") -> None:
        with self.lock:
            if req in self.running:
                self.running.remove(req)
                for key, value in req.taken_account().items():
                    self.ended[key] += value

    def read(self) -> Dict[str, Any]:
        """The sums over ended and running iterators, and ``backlog``:
        tokens emitted to a running iterator that it has not taken."""
        with self.lock:
            out = dict(self.ended)
            backlog = 0
            for req in self.running:
                account = req.taken_account()
                for key, value in account.items():
                    out[key] += value
                backlog += max(
                    0, len(req.output) - account["tokens_taken"])
        out["backlog"] = backlog
        return out


class _Request:
    def __init__(self, prompt: List[int], max_new_tokens: int,
                 eos_token: Optional[int], request_id: Any,
                 streams: _Streams):
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.eos_token = eos_token
        # The caller's name for the request (an operator's request id),
        # carried to its ``stats()["requests"]`` row.
        self.id = request_id
        self.output: List[int] = []
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        # Lifecycle on ``time.time()``, the clock of ``core/timeline``
        # spans: submitted, slot and pages assigned, first token out,
        # finished or failed. The engine's loop only assigns the floats.
        self.t_submit: Optional[float] = None
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.prompt_len = len(self.prompt)
        self.bucket: Optional[int] = None
        # Incremental consumers (token streaming) read from here: each
        # token with the ``time.time()`` at which the loop put it; None
        # is the end-of-stream sentinel.
        self._live: "queue.Queue[Optional[tuple]]" = queue.Queue()
        # The way back, accounted by the thread that runs ``tokens()``
        # and written by it alone: tokens taken, seconds from emitted to
        # taken, seconds from handing a token over to being asked for
        # the next, and when the consumer came back after the last one.
        self._streams = streams
        self.taken = 0
        self.taken_lag_s = 0.0
        self.held_s = 0.0
        self.t_last_put: Optional[float] = None
        # The row ``_close`` kept for stats(): ``t_last_put`` is known
        # only later, and is written into it in place.
        self._row: Optional[List] = None

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit to first token, queue wait and prefill together."""
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    def row(self) -> List:
        """This request as ``LLMEngine.stats()["requests"]`` shows it."""
        return [self.t_submit, self.t_admit, self.t_first, self.t_done,
                self.prompt_len, self.bucket, self.id, self.t_last_put]

    def taken_account(self) -> Dict[str, Any]:
        return {"tokens_taken": self.taken, "taken_lag_s": self.taken_lag_s,
                "held_s": self.held_s}

    def record_spans(self, parent: Optional[tuple] = None) -> None:
        """The engine's part of this request as ``core/timeline`` spans
        under ``parent`` ((trace_id, span_id), or the calling thread's
        active span): ``engine.queued`` (submit to admit),
        ``engine.prefill`` (admit to first token), ``engine.decode``
        (first token to done). Call it from the request's own thread,
        never from the engine's loop: recording may flush the whole span
        buffer to the KV inline, and every open stream would wait."""
        from ..core.timeline import record_span

        stamps = (self.t_submit, self.t_admit, self.t_first, self.t_done)
        for name, start, end in zip(
                ("engine.queued", "engine.prefill", "engine.decode"),
                stamps, stamps[1:]):
            if start is not None and end is not None:
                record_span(name, start, end, parent)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if self.error:
            raise self.error
        return self.output

    def tokens(self, timeout: Optional[float] = None) -> Iterator[int]:
        """Yield tokens as the decode loop produces them, and account
        for the way back on the calling thread (``stats()["stream"]``):
        three clock reads and four adds a token, no lock."""
        wall, clock = time.time, time.perf_counter
        self._streams.begin(self)
        back = None  # perf_counter() when the consumer last came back
        try:
            while True:
                item = self._live.get(timeout=timeout)
                if item is None:
                    if back is not None:
                        # On time.time(), by way of the lap since.
                        self.t_last_put = wall() - (clock() - back)
                        # ``_close`` kept the row before it put the None.
                        self._row[_LAST_PUT] = self.t_last_put
                    if self.error:
                        raise self.error
                    return
                tok, emitted = item
                self.taken_lag_s += wall() - emitted
                self.taken += 1
                handed = clock()
                yield tok
                back = clock()
                self.held_s += back - handed
        finally:
            self._streams.end(self)


# LLMEngine.stats(): the monotonic counts, the loop's phases, and how many
# finished requests' rows it keeps.
_COUNTERS = ("decode_slot_steps", "decode_kv_tokens", "decode_kv_rows_read",
             "kv_page_steps_held", "kv_page_steps_one_table", "prefills",
             "prefill_tokens", "prefill_bucket_tokens", "submitted",
             "admitted", "finished", "failed", "cache_resets", "page_waits",
             "decode_steps_ahead", "decode_slot_steps_discarded",
             "decode_state_slot_layers")
_PHASES = ("admit", "admit_stalling", "inputs", "decode", "readback",
           "emit", "idle")
_REQUEST_ROWS = 1024
# Where a ``requests`` row keeps ``t_last_put``.
_LAST_PUT = 7


class _Step:
    """A decode step dispatched and not yet read."""

    __slots__ = ("out", "slots", "ahead", "dropped")

    def __init__(self, out, slots: Dict[int, _Request], ahead: bool):
        self.out = out        # the packed read-back, on its way to the host
        self.slots = slots    # who decodes in it
        self.ahead = ahead    # dispatched while the step before was unread
        # Slots that ended on ``eos_token`` in the step before: their
        # token is dropped, slot and pages released when this one is read.
        self.dropped: List[int] = []


def serving_programs(cfg, temperature: float):
    """``(decode_step, prefill)``, the two functions the engine jits.

    ``decode_step(params, cache, last_tok, active, rng)`` returns
    ``(read-back, cache, last_tok, rng)``: what the next call takes, so
    that nothing of a step's inputs passes through the host.
    ``prefill(params, cache, last_tok, tokens, real_len, slot, pages)``
    returns ``(cache, last_tok, read-back)``, its first token set at
    ``slot`` (traced: one program a bucket, whatever the slot). A
    read-back is the sampled tokens ([B], of a prefill one) and, for a
    MoE model, the program's expert load behind them in the same int32
    vector: one read a step, as for a dense model."""
    import jax
    import jax.numpy as jnp

    from ..models.generation import paged_decode, paged_prefill, sample_logits

    def with_load(tokens, load):
        if load is None:
            return tokens
        return jnp.concatenate([
            tokens, load.expert_tokens, load.experts_reached[None]])

    def decode_step(params, cache, last_tok, active, rng):
        # The chain a loop that split on the host would draw: the same
        # seed, the same tokens.
        rng, key = jax.random.split(rng)
        logits, cache, load = paged_decode(
            params, last_tok, cache, cfg, active=active
        )
        nxt = sample_logits(logits, key, temperature=temperature)
        return with_load(nxt, load), cache, nxt, rng

    def prefill(params, cache, last_tok, tokens, real_len, slot, pages):
        logits, cache, load = paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages
        )
        nxt = sample_logits(logits, jax.random.PRNGKey(0),
                            temperature=temperature)
        return (cache, last_tok.at[slot].set(nxt[0]),
                nxt[0] if load is None else with_load(nxt, load))

    return decode_step, prefill


class LLMEngine:
    """Paged continuous-batching decode engine over the Llama family."""

    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 512, temperature: float = 0.0,
                 page_size: int = 16, total_pages: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from ..models.generation import PagedKVCache
        from ..models.llama import layer_runs
        from ..ops.paged_attention import decode_attention_path
        from ..ops.retention import retention_path

        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.temperature = temperature
        if max_len % page_size != 0:
            # paged_prefill reshapes bucket rows into whole pages; a
            # clamped bucket that is not a page multiple would blow up
            # inside the jitted reshape with an opaque XLA error.
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of page_size "
                f"({page_size})"
            )
        self.page_size = page_size
        # What the decode program below is built with: the same call
        # paged_decode's attention makes when the program is traced.
        if cfg.retention:
            self._decode_attention = retention_path(cfg.dh)
        elif cfg.latent:
            self._decode_attention = decode_attention_path(
                page_size, cfg.latent_row, cfg.kv_lora_rank)
        else:
            self._decode_attention = decode_attention_path(page_size, cfg.dh)
        self.max_pages_per_seq = math.ceil(max_len / page_size)
        # Default pool: enough for every slot at max_len (same worst case
        # as a dense cache); pass a smaller total_pages to oversubscribe.
        self.total_pages = total_pages or (
            max_batch * self.max_pages_per_seq
        )
        self._jnp = jnp
        self._jax = jax
        self._device = jax.devices()[0]

        self.cache = PagedKVCache.create(
            cfg, max_batch, self.total_pages, page_size,
            self.max_pages_per_seq,
        )
        # The allocator's books, one entry a KV pool (an attention kind
        # the model has): {kind: (layers, pool pages, table columns)}.
        self._pools = PagedKVCache.sizes(
            cfg, max_batch, self.total_pages, page_size,
            self.max_pages_per_seq)
        # What a token holds in one layer of each pool that has pages,
        # and what a slot holds in one layer of one that has none, as
        # allocated.
        held = {kind: sum(pool.nbytes for pool in self.cache.pools(kind))
                for kind in self._pools}
        self._row_bytes = {
            kind: held[kind] // (layers * pages * page_size)
            for kind, (layers, pages, _) in self._pools.items() if pages}
        self._slot_bytes = {
            kind: held[kind] // (layers * max_batch)
            for kind, (layers, pages, _) in self._pools.items() if not pages}
        self._state_layers = self._pools.get("state", (0,))[0]
        self._free_pages: Dict[str, List[int]] = {}
        self._table: Dict[str, np.ndarray] = {}
        self._new_books()
        self._slot_free = list(range(max_batch))
        self._slot_req: Dict[int, _Request] = {}
        self._slot_pages: Dict[int, Dict[str, List[int]]] = {}
        # Per slot, fixed from admission to finish so that a decode step
        # only adds them up: (pages held, each times its pool's layers;
        # what one table for every layer would hold).
        self._slot_held: Dict[int, tuple] = {}
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._waiting: List[_Request] = []  # admitted-but-no-pages
        self._lock = threading.Lock()
        self._stop = False
        self._step_count = 0
        # What stats() reports beside the gauges. Written by the loop
        # thread alone (plain adds, no lock) except ``submitted``, which
        # callers' threads bump under the lock.
        self._counts = dict.fromkeys(_COUNTERS, 0)
        self._phase_s = dict.fromkeys(_PHASES, 0.0)
        self._finished_rows: "collections.deque[List]" = collections.deque(
            maxlen=_REQUEST_ROWS)
        # The way back (stats()["stream"]): tokens put on a request's
        # ``_live``, counted by the loop thread, and who has taken them.
        self._tokens_emitted = 0
        self._streams = _Streams()
        # Expert load of a MoE model, read back behind each program's
        # tokens (``serving_programs``); None for a dense one.
        self._moe: Optional[Dict[str, Any]] = None
        self._expert_layers = sum(
            run.n for run in layer_runs(cfg) if run.moe)
        if cfg.n_experts > 0:
            self._moe = {"assignments": 0, "decode_assignments": 0,
                         "experts_reached": 0, "layer_steps": 0,
                         "prefill_experts_reached": 0,
                         "layer_calls": 0, "small_rows_layer_calls": 0,
                         "expert_tokens": np.zeros(cfg.n_experts, np.int64)}

        from ..util.device_metrics import instrumented_jit

        # Donate the cache: the paged pool updates IN PLACE instead of
        # being copied every step (a pool-sized copy per step would make
        # paging cost scale with pool size). Jit through the instrumented
        # compile path: serving recompiles (shape changes, evictions)
        # surface as ray_tpu_device_jit_* series instead of silent
        # latency spikes. The per-token tap rides a ring flushed once
        # every 64 steps (and at every burst boundary — see _loop /
        # stats), not per token, so the executable cache is not polled
        # around every [B,1] decode step.
        decode_step, prefill = serving_programs(cfg, temperature)
        self._decode = instrumented_jit(decode_step, donate_argnums=(1,),
                                        tap_stride=64)
        self._prefill = instrumented_jit(prefill, donate_argnums=(1,))
        self._new_carry()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # ---- public API --------------------------------------------------------

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_token: Optional[int] = None,
               request_id: Any = None) -> _Request:
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
                f"engine max_len({self.max_len})"
            )
        req = _Request(prompt, max_new_tokens, eos_token, request_id,
                       self._streams)
        need = self._pages_needed(req, self._bucket(len(prompt)))
        for kind, (_, pages, _) in self._pools.items():
            if need[kind] > pages:
                # Unsatisfiable EVER: waiting would head-of-line block
                # the admission queue forever.
                raise ValueError(
                    f"request needs {need[kind]} pages but the {kind} "
                    f"pool has only {pages} (page_size={self.page_size})"
                )
        with self._lock:
            self._counts["submitted"] += 1
        req.t_submit = time.time()
        self._queue.put(req)
        return req

    def generate(self, prompt: List[int], max_new_tokens: int = 32,
                 eos_token: Optional[int] = None,
                 timeout: float = 300.0) -> List[int]:
        return self.submit(prompt, max_new_tokens, eos_token).result(timeout)

    def stats(self) -> Dict[str, Any]:
        """Gauges of the engine now, and monotonic counts since it
        started; take two readings and subtract for a window.

        Gauges: ``active_slots``, ``free_slots``, ``free_pages`` (of the
        pool that keeps everything, "full", or of the only pool),
        ``pages`` (``{kind: {"layers", "total", "free"}}``, every pool),
        ``kv_row_bytes`` (``{kind: bytes}``: what a token holds in one
        layer of that pool, the pool's bytes over its tokens and layers:
        k and v of every KV head, or a latent pool's one row, padding
        and all; a pool without pages has no entry),
        ``state_slot_bytes`` (``{kind: bytes}``: what a slot holds in one
        layer of a pool that has no pages, a retention layer's state and
        normaliser, padding and all; empty for a model that has none),
        ``queued`` (submitted, not yet admitted), beside the constants
        ``platform``, ``device_kind``, ``total_pages``, ``page_size`` and
        ``decode_attention`` (``"page_walk"``, for a latent pool
        ``"latent_walk"``, or ``"gather"``: the path of
        ops/paged_attention.py the decode program was built with; for a
        model of retention layers ops/retention.py's ``"state_kernel"``
        or ``"xla"``).

        Counts: ``decode_steps``; ``decode_slot_steps`` (sequences, summed
        over decode steps) and ``decode_kv_tokens`` (their cached tokens,
        prompt and generated so far, summed likewise), so that the mean
        batch and context of a step are quotients of differences;
        ``decode_kv_rows_read`` (the rows the steps' attention read,
        summed over steps, sequences and layers: a sequence's cached
        tokens, on a window layer at most the window; without window
        layers ``decode_kv_tokens`` times the layers; a latent layer's
        rows are one a token, whatever the heads; a retention layer
        reads none); ``decode_state_slot_layers`` (the states the steps
        read and wrote: sequences times retention layers, summed over
        decode steps; times ``state_slot_bytes``, the bytes of state a
        step moved each way);
        ``kv_page_steps_held`` (pages the live sequences held, each
        times its pool's layers, summed over decode steps) and
        ``kv_page_steps_one_table`` (what they would have held with one
        table for every layer: the same number without window layers);
        ``prefills``, ``prefill_tokens`` (real) and
        ``prefill_bucket_tokens`` (padded to the bucket); ``submitted``,
        ``admitted``, ``finished``, ``failed`` (requests); ``cache_resets``;
        ``page_waits`` (admission rounds that stopped for want of pages);
        ``decode_steps_ahead`` (decode steps dispatched while the step
        before was still unread: over ``decode_steps``, how often the
        loop ran ahead of its read-back; the first step after a lull or
        a reset does not) and ``decode_slot_steps_discarded`` (the
        part of ``decode_slot_steps`` thrown away: the one step already
        queued for a slot when its ``eos_token`` was read). Every count
        of a decode step advances when the step is read and emitted,
        never at its dispatch: a reading taken with a step in flight
        does not hold that step.

        ``phase_s``: seconds the loop thread has spent in each phase, from
        the ``perf_counter()`` boundaries that also delimit its
        ``engine.*`` profiler annotations: ``admit`` (admission rounds,
        prefills included), ``admit_stalling`` (the part of ``admit`` in
        rounds entered with a stream open, which all of them wait
        through), ``inputs`` (who decodes next; the mask, when it
        changed), ``decode`` (the dispatch of the next step),
        ``readback`` (the host waiting for the step before it: the
        device was given the next step first, so this is DEVICE-BOUND
        waiting, about a step's time where the device sets the pace,
        and no sign of a slow host), ``emit``, ``idle`` (the 2 ms poll).

        ``moe``, for a model with experts only: ``expert_tokens`` (a list
        of E: (token, expert) assignments each expert was given, prefills
        and decode steps, summed over layers), ``assignments`` (their
        sum) and ``decode_assignments`` (the decode steps' part of it);
        ``experts_reached`` ((layer, expert) pairs that a decode
        step gave at least one token, summed over decode steps) over
        ``layer_steps`` (decode steps x layers that have experts) is the
        experts such a layer of a decode step read;
        ``prefill_experts_reached`` is the same count over prefills;
        ``layer_calls`` (expert layers x programs run, decode steps and
        prefills) and ``small_rows_layer_calls``, those whose program
        was built with the grouped matmul for few rows a group
        (ops/grouped_matmul.py: the same rule, by the program's rows).

        ``requests``: the newest requests that have finished and those now
        decoding, each ``[t_submit, t_admit, t_first, t_done or None,
        prompt_len, bucket, id, t_last_put or None]`` in ``time.time()``
        seconds and tokens: ``id`` is what the caller passed as
        ``request_id`` (None without one), ``t_last_put`` when the
        consumer of ``tokens()`` came back after the request's last
        token (that token's seal was then on its way to the node
        manager), so ``t_last_put - t_done`` is how far the replica's
        own part of the way back trailed the engine.

        ``t``: ``time.time()`` of this reading, so that a rate between
        two readings needs no outside clock.

        ``stream``, the way back from the loop to whoever iterates
        ``_Request.tokens()``: monotonic ``tokens_emitted`` (tokens the
        loop put on a request's live queue: prefills that produced one
        plus ``decode_slot_steps`` less ``decode_slot_steps_discarded``),
        ``tokens_taken`` (by a ``tokens()`` iterator; a request read by
        ``result()`` alone is never taken), ``taken_lag_s`` (emitted to
        taken, summed over taken tokens: the iterator's wake-up and what
        queued in front of it) and ``held_s`` (from a token's ``yield``
        to the consumer asking for the next: what ``LLMDeployment.stream``,
        the executor and the seal cost on that thread), and the gauge
        ``backlog`` (emitted to a running iterator, not yet taken).
        Stamps are ``time.time()`` of one process; a token's is its
        step's, one clock read a step."""
        # Telemetry read: publish whatever the decode tap ring has
        # accumulated so /metrics never lags a long burst.
        self._decode.flush_taps()
        stream = self._streams.read()
        with self._lock:
            return {
                **self._counts,
                "t": time.time(),
                "stream": {"tokens_emitted": self._tokens_emitted, **stream},
                "queued": self._queue.qsize() + len(self._waiting),
                "phase_s": dict(self._phase_s),
                "requests": list(self._finished_rows) + [
                    req.row() for req in self._slot_req.values()],
                # Where the engine's programs run: a rate read from
                # these stats is a device number only on a "tpu".
                "platform": self._device.platform,
                "device_kind": self._device.device_kind,
                "active_slots": len(self._slot_req),
                "free_slots": len(self._slot_free),
                "decode_steps": self._step_count,
                "free_pages": len(self._free_pages.get(
                    "full", next(iter(self._free_pages.values())))),
                "pages": {kind: {"layers": layers, "total": total,
                                 "free": len(self._free_pages[kind])}
                          for kind, (layers, total, _) in
                          self._pools.items()},
                "kv_row_bytes": dict(self._row_bytes),
                "state_slot_bytes": dict(self._slot_bytes),
                "total_pages": self.total_pages,
                "page_size": self.page_size,
                "decode_attention": self._decode_attention,
                **({"moe": {**self._moe, "expert_tokens":
                            self._moe["expert_tokens"].tolist()}}
                   if self._moe else {}),
            }

    def shutdown(self):
        self._stop = True
        self._thread.join(timeout=5)
        try:
            self._decode.flush_taps()
        except Exception:
            pass

    # ---- page accounting ---------------------------------------------------

    def _new_books(self):
        """Every page free, every table zero."""
        for kind, (_, pages, columns) in self._pools.items():
            self._free_pages[kind] = list(range(pages))
            self._table[kind] = np.zeros((self.max_batch, columns),
                                         dtype=np.int32)

    def _new_carry(self):
        """What a decode step takes from the one before, on the device,
        as at the engine's start: last tokens, the PRNG key (a reset
        draws the seed's stream again), nobody active, no step in
        flight."""
        jnp = self._jnp
        self._last_tok = jnp.zeros((self.max_batch,), dtype=jnp.int32)
        self._rng = self._jax.random.PRNGKey(0)
        self._active = jnp.zeros((self.max_batch,), dtype=bool)
        self._active_slots: frozenset = frozenset()
        self._flying: Optional[_Step] = None

    def _pages_needed(self, req: _Request, bucket: int) -> Dict[str, int]:
        """Pages of each pool the request holds from admission to its
        end: its bucket's or its whole context's, whichever is more, in
        a window pool no more than the ring (the table's columns), and
        of a pool of states, whose table has no column, none."""
        decode_span = math.ceil(
            (len(req.prompt) + req.max_new_tokens) / self.page_size
        )
        span = max(bucket // self.page_size, decode_span)
        return {kind: min(span, columns)
                for kind, (_, _, columns) in self._pools.items()}

    def _reset_cache(self, cause: Exception):
        """Recover from a failed donated call: the old pool's buffers
        are gone, so rebuild a fresh cache and fail in-flight requests
        with the root cause (they cannot be resumed without their KV).
        A step in flight goes with them: what it returns may be the
        failed call's."""
        from ..models.generation import PagedKVCache

        with self._lock:
            victims = list(self._slot_req.items())
            self._slot_req.clear()
            self._slot_free = list(range(self.max_batch))
            self._slot_pages.clear()
            self._slot_held.clear()
            self._new_books()
        self._counts["cache_resets"] += 1
        for _slot, req in victims:
            if not req.done.is_set():
                self._close(req, RuntimeError(
                    f"engine cache reset after runtime failure: {cause!r}"
                ))
        self.cache = PagedKVCache.create(
            self.cfg, self.max_batch, self.total_pages, self.page_size,
            self.max_pages_per_seq,
        )
        self._new_carry()

    def _close(self, req: _Request, error: Optional[BaseException] = None):
        """End of a request, finished or failed: wake its waiters and
        keep its row for stats()."""
        req.t_done = time.time()
        req.error = error
        self._counts["failed" if error else "finished"] += 1
        req._row = req.row()
        with self._lock:
            self._finished_rows.append(req._row)
        req.done.set()
        req._live.put(None)

    def _release_slot(self, slot: int):
        self._slot_held.pop(slot, None)
        held = self._slot_pages.pop(slot, {})
        for kind, pages in held.items():
            self._free_pages[kind].extend(pages)
            self._table[kind][slot, :] = 0
        self._slot_free.append(slot)

    # ---- engine loop -------------------------------------------------------

    def _bucket(self, n: int) -> int:
        bucket = self.page_size
        while bucket < n:
            bucket *= 2
        return min(bucket, self.max_len)

    def _admit(self):
        """One admission round: prefill queued requests into free slots
        until slots, pages or the queue run out. A prefill queues
        behind the decode step in flight and this thread waits for its
        first token, so that step's tokens are emitted after it."""
        jnp = self._jnp
        counts = self._counts
        while self._slot_free:
            if self._waiting:
                req = self._waiting.pop(0)
            else:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    return
            real_len = req.prompt_len
            bucket = self._bucket(real_len)
            need = self._pages_needed(req, bucket)
            if any(n > len(self._free_pages[kind])
                   for kind, n in need.items()):
                # Paged admission control: wait for pages to recycle, in
                # whichever pool is short, instead of OOMing or
                # over-reserving a dense max_len row.
                self._waiting.insert(0, req)
                counts["page_waits"] += 1
                return
            slot = self._slot_free.pop()
            pages = {kind: [self._free_pages[kind].pop() for _ in range(n)]
                     for kind, n in need.items()}
            req.bucket = bucket
            req.t_admit = time.time()
            counts["admitted"] += 1
            try:
                # Table upload, padding, dispatch and the wait for the
                # first token: what every open stream stalls through.
                with self._jax.profiler.TraceAnnotation(
                        "engine.prefill", bucket=bucket, slot=slot):
                    self._slot_pages[slot] = pages
                    self._slot_held[slot] = (
                        sum(self._pools[kind][0] * n
                            for kind, n in need.items()),
                        self.cfg.num_layers * max(need.values()))
                    for kind, ids in pages.items():
                        self._table[kind][slot, :] = 0
                        self._table[kind][slot, :len(ids)] = ids
                    # What paged_prefill lays the bucket into: its pages
                    # of a pool that keeps everything, of a ring no more
                    # than the ring has.
                    prefill_pages = {
                        kind: jnp.asarray(
                            ids[: bucket // self.page_size], dtype=jnp.int32)
                        for kind, ids in pages.items()}
                    self.cache = self.cache._replace(
                        page_table={kind: jnp.asarray(table) for kind, table
                                    in self._table.items()})
                    padded = req.prompt + [0] * (bucket - real_len)
                    tokens = jnp.asarray([padded], dtype=jnp.int32)
                    self.cache, self._last_tok, first = self._prefill(
                        self.params, self.cache, self._last_tok, tokens,
                        jnp.asarray(real_len, dtype=jnp.int32),
                        jnp.asarray(slot, dtype=jnp.int32),
                        prefill_pages,
                    )
                    first = int(self._tokens(
                        np.asarray(first).reshape(-1), 1, bucket)[0])
            except Exception as e:  # noqa: BLE001
                self._close(req, e)
                self._release_slot(slot)
                # The cache was DONATED into the failed call — its
                # buffers may already be invalid. Rebuild the pool and
                # fail every in-flight request rather than serving from
                # dead buffers (engine reset; callers see clean errors).
                self._reset_cache(e)
                continue
            req.t_first = time.time()
            counts["prefills"] += 1
            counts["prefill_tokens"] += real_len
            counts["prefill_bucket_tokens"] += bucket
            req.output.append(first)
            req._live.put((first, req.t_first))
            self._tokens_emitted += 1
            with self._lock:
                self._slot_req[slot] = req
            # No step in flight decodes for a slot admitted after it.
            if self._ended(req, first):
                self._finish(slot, req)
                self._release_slot(slot)

    def _tokens(self, out: np.ndarray, n: int,
                bucket: Optional[int] = None) -> np.ndarray:
        """The ``n`` tokens at the head of a program's read-back, a
        decode step's or with ``bucket`` that bucket's prefill's; what a
        MoE model's program packed behind them (``with_load``) goes to
        the expert-load counters, a decode step's apart from a
        prefill's where ``stats()`` tells them apart."""
        moe = self._moe
        if moe is not None:
            from ..ops.grouped_matmul import grouped_path

            expert_tokens = out[n:-1]
            # What moe_ffn asked when the program was traced.
            small_rows = grouped_path(
                (bucket or self.max_batch) * self.cfg.top_k,
                self.cfg.n_experts) == "small_rows"
            with self._lock:
                assignments = int(expert_tokens.sum())
                moe["expert_tokens"] += expert_tokens
                moe["assignments"] += assignments
                moe["layer_calls"] += self._expert_layers
                moe["small_rows_layer_calls"] += (
                    self._expert_layers * small_rows)
                if bucket is None:
                    moe["decode_assignments"] += assignments
                    moe["experts_reached"] += int(out[-1])
                    moe["layer_steps"] += self._expert_layers
                else:
                    moe["prefill_experts_reached"] += int(out[-1])
        return out[:n]

    @staticmethod
    def _ended(req: _Request, tok: int) -> bool:
        return (len(req.output) >= req.max_new_tokens
                or (req.eos_token is not None and tok == req.eos_token))

    def _finish(self, slot: int, req: _Request):
        """The request's end. Its slot and pages go back apart from it
        (``_release_slot``): at once, or when a step in flight that
        still writes into them has been read."""
        with self._lock:
            self._slot_req.pop(slot, None)
        self._close(req)

    def _emit(self, step: _Step, out: np.ndarray, queued: Optional[_Step]):
        """A step's tokens to their requests, once read: the counters,
        the finishes. ``queued`` is the step dispatched after it."""
        counts = self._counts
        now = time.time()  # the step's tokens are emitted now
        self._step_count += 1
        counts["decode_steps_ahead"] += step.ahead
        counts["decode_slot_steps_discarded"] += len(step.dropped)
        nxt = self._tokens(out, self.max_batch)
        counts["decode_slot_steps"] += len(step.slots)
        self._tokens_emitted += len(step.slots) - len(step.dropped)
        # The step attended to each prompt and every token generated
        # before this one: in a window layer to no more of them than
        # the window.
        contexts = [req.prompt_len + len(req.output)
                    for req in step.slots.values()]
        tokens = sum(contexts)
        counts["decode_kv_tokens"] += tokens
        counts["decode_kv_rows_read"] += sum(
            layers * (tokens if kind != "window" else sum(
                min(c, self.cfg.sliding_window) for c in contexts))
            for kind, (layers, pages, _) in self._pools.items() if pages)
        counts["decode_state_slot_layers"] += (
            len(step.slots) * self._state_layers)
        for slot, req in step.slots.items():
            held, one_table = self._slot_held[slot]
            counts["kv_page_steps_held"] += held
            counts["kv_page_steps_one_table"] += one_table
            if slot in step.dropped:
                self._release_slot(slot)
                continue
            tok = int(nxt[slot])
            req.output.append(tok)
            req._live.put((tok, now))
            if self._ended(req, tok):
                self._finish(slot, req)
                if queued is not None and slot in queued.slots:
                    # It ended on its eos_token, which no count foretold.
                    queued.dropped.append(slot)
                else:
                    self._release_slot(slot)

    def _loop(self):
        """Admit, dispatch the next decode step, then read and emit the
        one before it (the module docstring has the order and why it is
        safe). Each phase is a profiler annotation (inert unless a
        ``jax.profiler`` trace is open; then it lands in the trace's
        host plane, on the device trace's clock) and, from the same
        ``perf_counter()`` boundaries, a running total in ``phase_s``."""
        jnp = self._jnp
        span = self._jax.profiler.TraceAnnotation
        clock = time.perf_counter
        phase_s = self._phase_s
        t = clock()

        def lap(phase: str) -> float:
            """Close ``phase`` at a boundary shared with the next one."""
            nonlocal t
            now = clock()
            dt, t = now - t, now
            phase_s[phase] += dt
            return dt

        while not self._stop:
            # Only this thread adds slots, so no lock to look.
            stalling = bool(self._slot_req)
            with span("engine.admit"):
                self._admit()
            dt = lap("admit")
            if stalling:
                phase_s["admit_stalling"] += dt
            flying = self._flying
            # Who decodes next: every open slot short of its count, the
            # token in flight included.
            pending = flying.slots if flying else ()
            slots = {slot: req for slot, req in self._slot_req.items()
                     if len(req.output) + (slot in pending)
                     < req.max_new_tokens}
            queued = None
            try:
                if slots:
                    with span("engine.inputs"):
                        if slots.keys() != self._active_slots:
                            active = np.zeros((self.max_batch,), dtype=bool)
                            active[list(slots)] = True
                            self._active = jnp.asarray(active)
                            self._active_slots = frozenset(slots)
                    lap("inputs")
                    with span("engine.decode"):
                        out, self.cache, self._last_tok, self._rng = \
                            self._decode(self.params, self.cache,
                                         self._last_tok, self._active,
                                         self._rng)
                        out.copy_to_host_async()
                    lap("decode")
                    queued = _Step(out, slots, ahead=flying is not None)
                if flying is not None:
                    with span("engine.readback"):
                        out = np.asarray(flying.out)
                    lap("readback")
            except Exception as e:  # noqa: BLE001
                # The cache was donated into the failed call — recover
                # like the prefill path: rebuild the pool, fail in-flight
                # requests cleanly, keep the loop alive for new work.
                self._reset_cache(e)
                continue
            self._flying = queued
            if flying is not None:
                with span("engine.emit"):
                    self._emit(flying, out, queued)
                lap("emit")
            elif queued is None:
                # Burst boundary: the decode loop went idle, the last
                # step read — flush the batched metric taps accumulated
                # over the burst.
                self._decode.flush_taps()
                time.sleep(0.002)
                lap("idle")


class LLMDeployment:
    """Serve deployment wrapping an engine; deploy with
    ray_actor_options={"max_concurrency": N} so concurrent requests join
    the running decode loop (continuous batching). ``stream`` yields
    tokens as generated — route it through the proxy's SSE path
    (``POST /<name>/stream``) for live token streaming."""

    def __init__(self, cfg=None, params=None, *, checkpoint_path=None,
                 max_batch: int = 8, max_len: int = 512,
                 temperature: float = 0.0, seed: int = 0,
                 page_size: int = 16,
                 total_pages: Optional[int] = None):
        from ..models import LlamaConfig, init_params

        if cfg is None:
            cfg = LlamaConfig.tiny()
        if params is None and checkpoint_path:
            from ..train.checkpoint import Checkpoint

            params = Checkpoint(checkpoint_path).as_pytree()
        if params is None:
            import jax

            params = init_params(cfg, jax.random.PRNGKey(seed))
        self.engine = LLMEngine(cfg, params, max_batch=max_batch,
                                max_len=max_len, temperature=temperature,
                                page_size=page_size,
                                total_pages=total_pages)

    def _submit(self, request: Dict[str, Any]) -> _Request:
        return self.engine.submit(
            list(request["prompt"]),
            int(request.get("max_new_tokens", 32)),
            request.get("eos_token"),
            request.get("id"),
        )

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        req = self._submit(request)
        try:
            return {"tokens": req.result(300.0)}
        finally:
            req.record_spans()

    def stream(self, request: Dict[str, Any]):
        """Generator endpoint: one token per yield, as decoded. When the
        stream ends, the engine's part of the request joins its trace
        (``_Request.record_spans``), from this thread and not the
        engine's."""
        from ..core.timeline import current_span

        # Now: a generator is resumed wherever its consumer runs, but it
        # is entered under the replica's span of this request.
        parent = current_span()
        req = self._submit(request)
        try:
            for tok in req.tokens(timeout=300.0):
                yield {"token": tok}
        finally:
            req.record_spans(parent)

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()
