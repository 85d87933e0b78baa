"""Continuous-batched LLM serving on TPU with a paged KV cache.

The capability the reference lacks (SURVEY.md §7 hard parts: "continuous
batching + paged KV cache on TPU for Serve; reference has only
request-level batching"): an engine where requests JOIN and LEAVE the
running decode loop — each decode step batches every active slot into one
[B, 1] forward pass (HBM-bandwidth bound; batching amortizes the weight
reads), while prefill runs per admission into power-of-two length buckets.

KV memory is PAGED (models/generation.py, which says what each kind of
attention layer keeps): a request reserves at admission only what its
prompt + max_new_tokens need — not a dense max_len row — and holds it to
its end, so admission waits for pages instead of OOMing. All shapes stay
static for XLA.

The engine is a loop over three owners, and the arrows point one way
(each class says what it hides):

    LLMEngine        the thread, the loop and its phases, stats(), reset
       |-- _Scheduler   who decides. Requests only: no page, no kind,
       |       v        no jax; it asks the books, never the runner.
       |-- KVBooks      (models/generation.py) who keeps the cache's
       |                books. Pages and holdings: no request, no program.
       '-- _Runner      who runs the programs and what they carry on the
                        device. Slots, tokens, page ids: no request.

The loop runs ONE decode step ahead of the one it reads. What a step
needs lies on the device: the last tokens (a step's output is the next
one's input; a prefill sets its slot's), the PRNG key (split inside the
program) and the active mask (sent again only when membership changes).
One turn of ``LLMEngine._loop``:

1. admit: the scheduler picks a queued request for a free slot, the
   books reserve its pages (or it waits, first in line), the runner
   prefills, blocking for the first token (the prefill queues behind
   the step in flight), and the scheduler takes the token;
2. inputs: who decodes in the step queued next: every open slot that
   its token count, the token in flight included, has not ended;
3. decode: dispatch step k+1, start its read-back's copy to the host;
4. readback: block on step k's tokens while step k+1 runs;
5. emit: step k's tokens to their requests, the counters, the finishes.

So no step writes a K/V row past the pages its slot holds: a slot that
ends by its count is left out of the next step before its last token is
read. Only ``eos_token`` is known at the read-back alone: the one step
already queued for that slot is a wasted row inside its own pages, its
token is dropped, and slot and pages return to the books when that
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
from bisect import bisect_right
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


# The upper edges, in seconds, of the buckets of the three histograms of
# ``stats()["stream"]``: 2% apart from 10 us to 63 s, so that a quantile
# read back from one lies within 2% of the samples' own whatever they
# were. A histogram is one count more than the edges: ``counts[i]`` is
# the samples in ``[edges[i - 1], edges[i])``, the first everything
# under ``edges[0]``, the last everything from ``edges[-1]`` on: the
# layout ``util/tsdb.quantile_from_histogram(edges, counts, q)`` reads.
HIST_EDGES_S = tuple(1e-5 * 1.02 ** i for i in range(792))


# Of a stream's holds, which are also timed on the thread's CPU clock:
# the first of every eight. ``time.thread_time()`` is a system call, two
# a timed hold, and where one costs microseconds (6.6 us on the hosts
# the benchmark runs on) sixteen stream threads pay them under one GIL.
_CPU_TIMED_HOLD = 8


def _new_hist() -> List[int]:
    return [0] * (len(HIST_EDGES_S) + 1)


def _merge(into: Dict[str, Any], account: Dict[str, Any]) -> None:
    """Add a request's account to sums of accounts: numbers, and
    histograms count by count into a new list."""
    for key, value in account.items():
        if isinstance(value, list):
            into[key] = [a + b for a, b in zip(into[key], value)]
        else:
            into[key] += value


class _Streams:
    """Who takes tokens from the engine's requests, for
    ``LLMEngine.stats()["stream"]``: the ``_Request.tokens()`` iterators
    now running, and the sums of those that have ended. A request's own
    account is written by its taking thread alone; the lock is taken
    when an iterator starts, when it ends, and by a reading."""

    def __init__(self):
        self.lock = threading.Lock()
        self.running: set = set()
        self.ended = {"tokens_taken": 0, "taken_lag_s": 0.0, "held_s": 0.0,
                      "held_cpu_s": 0.0, "held_timed_s": 0.0,
                      "taken_lag_hist": _new_hist(),
                      "held_hist": _new_hist()}

    def begin(self, req: "_Request") -> None:
        with self.lock:
            self.running.add(req)

    def end(self, req: "_Request") -> None:
        with self.lock:
            if req in self.running:
                self.running.remove(req)
                _merge(self.ended, req.taken_account())

    def read(self) -> Dict[str, Any]:
        """The sums over ended and running iterators, and ``backlog``:
        tokens emitted to a running iterator that it has not taken."""
        with self.lock:
            out = dict(self.ended)
            backlog = 0
            for req in self.running:
                account = req.taken_account()
                _merge(out, account)
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
        # When the loop last put a token on ``_live``: the next one's
        # gap is counted from it (the loop thread's alone).
        self.t_emit: Optional[float] = None
        # The way back, accounted by the thread that runs ``tokens()``
        # and written by it alone: tokens taken, seconds from emitted to
        # taken, seconds from handing a token over to being asked for
        # the next (of every eighth such hold, its seconds and those the
        # thread was on the CPU), each token's two waits counted into a
        # histogram, and when the consumer came back after the last one.
        self._streams = streams
        self.taken = 0
        self.taken_lag_s = 0.0
        self.held_s = 0.0
        self.held_cpu_s = 0.0
        self.held_timed_s = 0.0
        self.taken_lag_hist = _new_hist()
        self.held_hist = _new_hist()
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
                "held_s": self.held_s, "held_cpu_s": self.held_cpu_s,
                "held_timed_s": self.held_timed_s,
                "taken_lag_hist": self.taken_lag_hist,
                "held_hist": self.held_hist}

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
        three clock reads, two bucket lookups and six adds a token, no
        lock; every eighth hold two reads of the thread's CPU clock
        more. ``held_cpu_s`` is the CPU time of the thread that resumes
        the iterator: one thread a stream, as the worker runs one."""
        wall, clock, cpu = time.time, time.perf_counter, time.thread_time
        edges, lag_hist, held_hist = (
            HIST_EDGES_S, self.taken_lag_hist, self.held_hist)
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
                lag = wall() - emitted
                self.taken_lag_s += lag
                lag_hist[bisect_right(edges, lag)] += 1
                timed = not self.taken % _CPU_TIMED_HOLD
                self.taken += 1
                handed = clock()
                if timed:
                    handed_cpu = cpu()
                yield tok
                back = clock()
                held = back - handed
                self.held_s += held
                held_hist[bisect_right(edges, held)] += 1
                if timed:
                    self.held_cpu_s += cpu() - handed_cpu
                    self.held_timed_s += held
        finally:
            self._streams.end(self)



# LLMEngine.stats(): the scheduler's monotonic counts (the books and
# the runner keep their own), the loop's phases, and how many finished
# requests' rows are kept.
_COUNTERS = ("decode_slot_steps", "prefills", "prefill_tokens",
             "prefill_bucket_tokens", "submitted", "admitted", "finished",
             "failed", "page_waits", "decode_steps_ahead",
             "decode_slot_steps_discarded")
_PHASES = ("admit", "admit_stalling", "inputs", "decode", "readback",
           "emit", "idle")
# What a decode step's dispatch found: the device with work queued, or
# empty behind a late loop, behind a prefill that open streams waited
# through, or after a lull.
_FEEDS = ("fed", "starved_host", "starved_prefill", "starved_lull")
_REQUEST_ROWS = 1024
# Where a ``requests`` row keeps ``t_last_put``.
_LAST_PUT = 7


class _Step:
    """A decode step dispatched and not yet read."""

    __slots__ = ("out", "slots", "ahead", "dropped")

    def __init__(self, slots: Dict[int, _Request], ahead: bool):
        self.out = None       # the runner's read-back, on its way to the host
        self.slots = slots    # who decodes in it
        self.ahead = ahead    # dispatched while the step before was unread
        # Slots that ended on ``eos_token`` in the step before: their
        # token is dropped, slot and pages released when this one is read.
        self.dropped: List[int] = []


def serving_weights(params, turn):
    """``(serving tree, counter)``: the tree the serving programs take,
    made once from the published tree an engine is given
    (``llama.serving_tree``), and what making it cost
    (``stats()["weights"]``). Every stacked projection of
    ``llama.SERVING_ORDER`` is stored with the axis the decode step
    contracts last (why: there), by ONE call of ``turn`` (the runner's
    jitted ``llama.turn_leaves``) over those leaves, one program more at
    set-up whatever the runs and leaves; every other leaf is the given
    array, shared. Nothing is donated: the given tree is its owner's,
    and serves other engines."""
    import jax

    from ..models import llama

    published = llama.turning_leaves(params)
    started = time.perf_counter()
    serving = jax.block_until_ready(llama.serving_tree(params, turn))
    return serving, {
        "leaves_turned": sum(len(stack) for stack in published),
        "bytes_turned": sum(int(w.nbytes) for stack in published
                            for w in stack.values()),
        # Trace, compile and run of the one call.
        "turn_s": time.perf_counter() - started,
    }


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
    vector (the held experts' tokens, the experts reached and, of a
    model that holds a share of its experts, the assignments that went
    elsewhere): one read a step, as for a dense model. For a model with
    an exit gate (``cfg.exit_gate``) two values more at the vector's
    end: the expected exit pass ``sum_t (t + 1) p_t`` summed over the
    tokens the program made (a decode step's active slots, a prefill's
    one; float32, its bits as an int32) and how many those were.

    The gate decides nothing here: every token runs every pass, which
    is what ``exit_threshold`` 1.0 says. Under a lower one the
    sequences of a batch would leave at different passes: the step
    would need a batch that thins from pass to pass, and a token that
    left early would still owe the skipped passes' K and V to the
    tokens behind it. Neither exists, so another threshold is refused."""
    import jax
    import jax.numpy as jnp

    from ..models.generation import paged_decode, paged_prefill, sample_logits

    if cfg.exit_gate and cfg.exit_threshold != 1.0:
        raise NotImplementedError(
            f"exit_threshold={cfg.exit_threshold}: only 1.0 is built "
            f"(every token runs all {cfg.passes} passes). A lower one "
            f"needs a decode batch whose sequences stop at different "
            f"passes, and the K/V of the passes a token skipped, which "
            f"the tokens behind it attend to")

    def with_load(tokens, load, exits, made):
        tail = []
        if load is not None:
            tail += [load.expert_tokens, load.experts_reached[None]]
            tail += [] if load.elsewhere is None else [load.elsewhere[None]]
        for p in exits:
            # The one [B, passes] of a model with an exit gate, of which
            # the rows ``made`` [B] bool are tokens.
            passes = jnp.arange(1, p.shape[-1] + 1, dtype=jnp.float32)
            expected = jnp.where(made, p @ passes, 0.0).sum()
            tail += [jax.lax.bitcast_convert_type(expected, jnp.int32)[None],
                     made.sum(dtype=jnp.int32)[None]]
        return jnp.concatenate([tokens, *tail]) if tail else tokens

    def decode_step(params, cache, last_tok, active, rng):
        # The chain a loop that split on the host would draw: the same
        # seed, the same tokens.
        rng, key = jax.random.split(rng)
        logits, cache, load, *exits = paged_decode(
            params, last_tok, cache, cfg, active=active
        )
        nxt = sample_logits(logits, key, temperature=temperature)
        return with_load(nxt, load, exits, active), cache, nxt, rng

    def prefill(params, cache, last_tok, tokens, real_len, slot, pages):
        logits, cache, load, *exits = paged_prefill(
            params, tokens, real_len, cache, cfg, slot, pages
        )
        nxt = sample_logits(logits, jax.random.PRNGKey(0),
                            temperature=temperature)
        last_tok = last_tok.at[slot].set(nxt[0])
        out = with_load(nxt, load, exits, jnp.ones((1,), dtype=bool))
        return cache, last_tok, nxt[0] if out is nxt else out

    return decode_step, prefill


class _Scheduler:
    """Who decides: which queued request takes a free slot, who decodes
    in the step queued next, when a request has ended and when its slot
    goes back. Requests only: no page, no kind, no jax. ``books`` is
    asked one question at submit (``refusal``: could such a context ever
    be held) and one at admission (``reserve``: a reservation or
    nothing), and told a slot's ``release``, a ``reset`` and what each
    step read (``account``). The loop thread calls everything but
    ``submit`` and ``reading``; ``lock`` is the engine's, taken where
    another thread's ``reading`` sees what is written."""

    def __init__(self, books, lock: threading.Lock, *, max_batch: int,
                 max_len: int, min_bucket: int):
        self._books, self._lock = books, lock
        self._max_batch, self._max_len = max_batch, max_len
        self._min_bucket = min_bucket
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._waiting: List[_Request] = []  # picked, but the books refused
        self._slot_free = list(range(max_batch))
        self._slot_req: Dict[int, _Request] = {}
        # Picked, its prefill not yet answered: (slot, request).
        self._admitting: Optional[tuple] = None
        # Written by the loop thread alone (plain adds, no lock) except
        # ``submitted``, which callers' threads bump under the lock.
        self.counts = dict.fromkeys(_COUNTERS, 0)
        self._steps = 0
        self._finished_rows: "collections.deque[List]" = collections.deque(
            maxlen=_REQUEST_ROWS)
        # The way back (stats()["stream"]): tokens put on a request's
        # ``_live``, counted by the loop thread, and who has taken them.
        self._tokens_emitted = 0
        # Seconds between two tokens of one request as the loop put
        # them, a histogram over every request; a first token opens none.
        self._emit_gaps = _new_hist()
        self.streams = _Streams()

    def bucket(self, n: int) -> int:
        bucket = self._min_bucket
        while bucket < n:
            bucket *= 2
        return min(bucket, self._max_len)

    def submit(self, prompt: List[int], max_new_tokens: int,
               eos_token: Optional[int], request_id: Any) -> _Request:
        if len(prompt) + max_new_tokens > self._max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
                f"engine max_len({self._max_len})"
            )
        # Unsatisfiable EVER: waiting would head-of-line block the
        # admission queue forever.
        refusal = self._books.refusal(len(prompt) + max_new_tokens,
                                      self.bucket(len(prompt)))
        if refusal:
            raise ValueError(refusal)
        req = _Request(prompt, max_new_tokens, eos_token, request_id,
                       self.streams)
        with self._lock:
            self.counts["submitted"] += 1
        req.t_submit = time.time()
        self._queue.put(req)
        return req

    def streaming(self) -> bool:
        """A slot is open. Only the loop thread adds slots, so it looks
        without the lock."""
        return bool(self._slot_req)

    def pick(self) -> Optional[tuple]:
        """The next request into a free slot: ``(slot, prompt, bucket,
        reservation)`` for its prefill, which ``first_token`` or
        ``prefill_failed`` answers. None without a slot or a request,
        and where the books refuse (paged admission control: wait for
        pages to recycle instead of OOMing or over-reserving): the
        request is then the first to be picked again."""
        if not self._slot_free:
            return None
        if self._waiting:
            req = self._waiting.pop(0)
        else:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return None
        bucket = self.bucket(req.prompt_len)
        slot = self._slot_free[-1]
        held = self._books.reserve(
            slot, req.prompt_len + req.max_new_tokens, bucket)
        if held is None:
            self._waiting.insert(0, req)
            self.counts["page_waits"] += 1
            return None
        self._slot_free.pop()
        req.bucket = bucket
        req.t_admit = time.time()
        self.counts["admitted"] += 1
        self._admitting = (slot, req)
        return slot, req.prompt, bucket, held

    def first_token(self, first: int) -> None:
        """The picked request's prefill gave its first token."""
        slot, req = self._admitting
        counts = self.counts
        req.t_first = req.t_emit = time.time()
        counts["prefills"] += 1
        counts["prefill_tokens"] += req.prompt_len
        counts["prefill_bucket_tokens"] += req.bucket
        req.output.append(first)
        req._live.put((first, req.t_first))
        self._tokens_emitted += 1
        with self._lock:
            self._slot_req[slot] = req
        # No step in flight decodes for a slot admitted after it.
        if self._ended(req, first):
            self._finish(slot, req)
            self._release(slot)

    def prefill_failed(self, error: BaseException) -> None:
        slot, req = self._admitting
        self._close(req, error)
        self._release(slot)

    def next_step(self, flying: Optional[_Step]) -> Optional[_Step]:
        """Who decodes in the step queued behind ``flying``: every open
        slot short of its count, the token in flight included. None for
        nobody."""
        pending = flying.slots if flying else ()
        slots = {slot: req for slot, req in self._slot_req.items()
                 if len(req.output) + (slot in pending)
                 < req.max_new_tokens}
        return _Step(slots, ahead=flying is not None) if slots else None

    def emit(self, step: _Step, nxt, queued: Optional[_Step]) -> None:
        """A step's tokens ``nxt`` (one a slot) to their requests, once
        read: the counters, the finishes. ``queued`` is the step
        dispatched after it."""
        counts, gaps, edges = self.counts, self._emit_gaps, HIST_EDGES_S
        now = time.time()  # the step's tokens are emitted now
        self._steps += 1
        counts["decode_steps_ahead"] += step.ahead
        counts["decode_slot_steps_discarded"] += len(step.dropped)
        counts["decode_slot_steps"] += len(step.slots)
        self._tokens_emitted += len(step.slots) - len(step.dropped)
        self._books.account(
            step.slots.keys(), [req.prompt_len + len(req.output)
                                for req in step.slots.values()])
        for slot, req in step.slots.items():
            if slot in step.dropped:
                self._release(slot)
                continue
            tok = int(nxt[slot])
            req.output.append(tok)
            req._live.put((tok, now))
            gaps[bisect_right(edges, now - req.t_emit)] += 1
            req.t_emit = now
            if self._ended(req, tok):
                self._finish(slot, req)
                if queued is not None and slot in queued.slots:
                    # It ended on its eos_token, which no count foretold.
                    queued.dropped.append(slot)
                else:
                    self._release(slot)

    def reset(self, cause: Exception) -> None:
        """After a failed donated call: every slot free, the books new,
        and the open requests failed with the root cause (they cannot
        be resumed without their KV)."""
        with self._lock:
            victims = list(self._slot_req.values())
            self._slot_req.clear()
            self._slot_free = list(range(self._max_batch))
            self._books.reset()
        for req in victims:
            if not req.done.is_set():
                self._close(req, RuntimeError(
                    f"engine cache reset after runtime failure: {cause!r}"
                ))

    def reading(self, taken: Dict[str, Any]) -> Dict[str, Any]:
        """``taken``: ``streams.read()``, which has a lock of its own,
        read before the engine's is taken for this."""
        return {
            **self.counts,
            "decode_steps": self._steps,
            "stream": {"tokens_emitted": self._tokens_emitted,
                       "emit_gap_hist": list(self._emit_gaps),
                       "hist_edges_s": list(HIST_EDGES_S), **taken},
            "queued": self._queue.qsize() + len(self._waiting),
            "requests": list(self._finished_rows) + [
                req.row() for req in self._slot_req.values()],
            "active_slots": len(self._slot_req),
            "free_slots": len(self._slot_free),
        }

    @staticmethod
    def _ended(req: _Request, tok: int) -> bool:
        return (len(req.output) >= req.max_new_tokens
                or (req.eos_token is not None and tok == req.eos_token))

    def _finish(self, slot: int, req: _Request):
        """The request's end. Its slot and pages go back apart from it
        (``_release``): at once, or when a step in flight that still
        writes into them has been read."""
        with self._lock:
            self._slot_req.pop(slot, None)
        self._close(req)

    def _close(self, req: _Request, error: Optional[BaseException] = None):
        """End of a request, finished or failed: wake its waiters and
        keep its row for stats()."""
        req.t_done = time.time()
        req.error = error
        self.counts["failed" if error else "finished"] += 1
        req._row = req.row()
        with self._lock:
            self._finished_rows.append(req._row)
        req.done.set()
        req._live.put(None)

    def _release(self, slot: int):
        self._books.release(slot)
        self._slot_free.append(slot)


class _Runner:
    """Who runs the programs: the two jitted functions of
    ``serving_programs`` (``decode_step``, ``prefill``), what a decode
    step takes from the one before on the device (the cache, the last
    tokens, the PRNG key, the active mask), donation and the rebuild
    after a donated call failed, and the read-back's format, which
    ``with_load`` packs and ``unpack`` alone takes apart, with the MoE
    counters it feeds. Slots, token lists and page ids: no request.
    ``params`` is the SERVING tree (``serving_weights``), the only tree
    the engine holds; ``published_params`` turns it back for whoever
    reads weights as published (a reference's ``forward``)."""

    def __init__(self, cfg, params, temperature: float, max_batch: int,
                 new_cache, lock: threading.Lock):
        import jax

        from ..models import llama
        from ..models.llama import layer_runs, prefill_attention_path
        from ..ops.grouped_matmul import grouped_path
        from ..util.device_metrics import instrumented_jit

        # The serving tree, before the pool is allocated: the engine's
        # one resident copy of each weight (the published leaves that
        # turned are the caller's to let go).
        self._turn = instrumented_jit(llama.turn_leaves, static_argnums=1)
        self.params, self._weights = serving_weights(params, self._turn)
        self._max_batch = max_batch
        self._new_cache = new_cache
        self._lock = lock
        self._device = jax.devices()[0]
        # Expert load of a MoE model, read back behind each program's
        # tokens; None for a dense one.
        self._moe: Optional[Dict[str, Any]] = None
        self._expert_layers = sum(
            run.n for run in layer_runs(cfg) if run.moe)
        if cfg.n_experts > 0:
            self._moe = {"assignments": 0, "decode_assignments": 0,
                         "experts_reached": 0, "layer_steps": 0,
                         "prefill_experts_reached": 0,
                         "layer_calls": 0, "small_rows_layer_calls": 0,
                         "expert_tokens": np.zeros(cfg.experts_here,
                                                   np.int64)}
            if cfg.experts_held:
                self._moe["assignments_elsewhere"] = 0
        # A looped model's counters (stats()["loop"]); None for a model
        # of one pass. Its gate's two values ride behind each program's
        # read-back (``serving_programs``).
        self._passes, self._exit_gate = cfg.passes, cfg.exit_gate
        self._loop: Optional[Dict[str, Any]] = (
            {"passes": 0, "exit_tokens": 0, "exit_pass_sum": 0.0}
            if cfg.passes > 1 else None)
        # Bucket tokens of the prefills whose attention over k and v
        # rows ran the flash forward's streamed form.
        self._streamed_bucket_tokens = 0
        self._streamed = lambda bucket: prefill_attention_path(
            cfg, bucket) == "streamed"
        # What moe_ffn asked when a program of so many tokens was traced.
        self._small_rows = lambda tokens: grouped_path(
            tokens * cfg.top_k, cfg.experts_here) == "small_rows"
        # Donate the cache: the paged pool updates IN PLACE instead of
        # being copied every step (a pool-sized copy per step would make
        # paging cost scale with pool size). Jit through the instrumented
        # compile path: serving recompiles (shape changes, evictions)
        # surface as ray_tpu_device_jit_* series instead of silent
        # latency spikes. The per-token tap rides a ring flushed once
        # every 64 steps (and at every burst boundary: LLMEngine._loop,
        # stats), not per token, so the executable cache is not polled
        # around every [B,1] decode step.
        decode_step, prefill = serving_programs(cfg, temperature)
        self.decode_step = instrumented_jit(
            decode_step, donate_argnums=(1,), tap_stride=64)
        self.prefill = instrumented_jit(prefill, donate_argnums=(1,))
        self.reset()

    def reset(self):
        """A new cache and, as at the engine's start, what a decode step
        takes from the one before: last tokens, the PRNG key (a reset
        draws the seed's stream again), nobody active. After a failed
        donated call the old pool's buffers are gone, and what a step in
        flight returns may be the failed call's."""
        import jax
        import jax.numpy as jnp

        self._cache = None
        self._last_tok = jnp.zeros((self._max_batch,), dtype=jnp.int32)
        self._rng = jax.random.PRNGKey(0)
        self._active = jnp.zeros((self._max_batch,), dtype=bool)
        self._active_slots: frozenset = frozenset()

    @property
    def cache(self):
        """The KV cache the programs carry, allocated when first asked
        for (a request's prefill; ``stats()`` and the books need its
        sizes alone): whoever built the engine still holds the published
        leaves that turned while its constructor runs, and the pool is
        not made to stand beside two copies of a weight."""
        if self._cache is None:
            self._cache = self._new_cache()
        return self._cache

    @cache.setter
    def cache(self, cache) -> None:
        self._cache = cache

    def run_prefill(self, slot: int, prompt: List[int], bucket: int,
                    pages: Dict[str, List[int]], tables) -> int:
        """Prefill ``prompt``, padded to ``bucket``, into ``slot`` and
        its ``pages`` (of each pool, those that take the bucket), the
        host's ``tables`` uploaded whole, and wait for the token."""
        import jax.numpy as jnp

        self.cache = self.cache._replace(
            page_table={kind: jnp.asarray(table)
                        for kind, table in tables.items()})
        padded = prompt + [0] * (bucket - len(prompt))
        self.cache, self._last_tok, first = self.prefill(
            self.params, self.cache, self._last_tok,
            jnp.asarray([padded], dtype=jnp.int32),
            jnp.asarray(len(prompt), dtype=jnp.int32),
            jnp.asarray(slot, dtype=jnp.int32),
            {kind: jnp.asarray(ids, dtype=jnp.int32)
             for kind, ids in pages.items()},
        )
        first = self.unpack(np.asarray(first).reshape(-1), 1, bucket)[0]
        if self._streamed(bucket):
            with self._lock:
                self._streamed_bucket_tokens += bucket
        return int(first)

    def activate(self, slots) -> None:
        """``slots`` (a set of them) decode in the next step: the mask
        goes to the device only when the set changed."""
        if slots != self._active_slots:
            import jax.numpy as jnp

            active = np.zeros((self._max_batch,), dtype=bool)
            active[list(slots)] = True
            self._active = jnp.asarray(active)
            self._active_slots = frozenset(slots)

    def step(self):
        """Dispatch a decode step; its read-back, on its way to the
        host."""
        out, self.cache, self._last_tok, self._rng = self.decode_step(
            self.params, self.cache, self._last_tok, self._active,
            self._rng)
        out.copy_to_host_async()
        return out

    def done(self, out) -> bool:
        """Whether the step of this read-back has finished on the
        device, without waiting for it."""
        return out.is_ready()

    def fetch(self, out) -> np.ndarray:
        """Block for a step's read-back."""
        return np.asarray(out)

    def unpack(self, out: np.ndarray, n: int,
               bucket: Optional[int] = None) -> np.ndarray:
        """The ``n`` tokens at the head of a program's read-back, a
        decode step's or with ``bucket`` that bucket's prefill's; what a
        MoE model's program packed behind them (``with_load``) goes to
        the expert-load counters, a decode step's apart from a
        prefill's where ``stats()`` tells them apart; a looped model's
        passes and its gate's two values go to the loop's counters."""
        if self._loop is not None:
            with self._lock:
                self._loop["passes"] += self._passes * (bucket is None)
                if self._exit_gate:
                    self._loop["exit_pass_sum"] += float(
                        out[-2:-1].view(np.float32)[0])
                    self._loop["exit_tokens"] += int(out[-1])
            if self._exit_gate:
                out = out[:-2]
        moe = self._moe
        if moe is not None:
            if "assignments_elsewhere" in moe:
                out, elsewhere = out[:-1], int(out[-1])
                with self._lock:
                    moe["assignments_elsewhere"] += elsewhere
            expert_tokens = out[n:-1]
            small_rows = self._small_rows(bucket or self._max_batch)
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

    def published_params(self):
        """The tree the engine was given, leaf for leaf and bit for bit:
        the serving tree with its turned leaves turned back, made anew
        at every call and the caller's to keep or drop (the engine
        holds the serving tree alone); the other leaves are shared."""
        from ..models import llama

        return llama.serving_tree(self.params, self._turn, back=True)

    def reading(self) -> Dict[str, Any]:
        return {
            # Where the engine's programs run: a rate read from these
            # stats is a device number only on a "tpu".
            "platform": self._device.platform,
            "device_kind": self._device.device_kind,
            "weights": dict(self._weights),
            "prefill_streamed_bucket_tokens": self._streamed_bucket_tokens,
            **({"moe": {**self._moe, "expert_tokens":
                        self._moe["expert_tokens"].tolist()}}
               if self._moe else {}),
            **({"loop": dict(self._loop)} if self._loop else {}),
        }


class LLMEngine:
    """Paged continuous-batching decode engine over the Llama family:
    the thread and the loop over ``scheduler``, ``books`` and ``runner``
    (the module docstring says who owns what)."""

    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 512, temperature: float = 0.0,
                 page_size: int = 16, total_pages: Optional[int] = None):
        import jax

        from ..models.generation import KVBooks, PagedKVCache

        self.cfg = cfg
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
        max_pages_per_seq = math.ceil(max_len / page_size)
        # Default pool: enough for every slot at max_len (same worst case
        # as a dense cache); pass a smaller total_pages to oversubscribe.
        self.total_pages = total_pages or max_batch * max_pages_per_seq
        geometry = (cfg, max_batch, self.total_pages, page_size,
                    max_pages_per_seq)

        def new_cache():
            return PagedKVCache.create(*geometry)

        self._lock = threading.Lock()
        self.runner = _Runner(cfg, params, temperature, max_batch,
                              new_cache, self._lock)
        self.books = KVBooks(*geometry, jax.eval_shape(new_cache))
        self.scheduler = _Scheduler(
            self.books, self._lock, max_batch=max_batch, max_len=max_len,
            min_bucket=page_size)
        self._stop = False
        self._flying: Optional[_Step] = None
        self._phase_s = dict.fromkeys(_PHASES, 0.0)
        # ``admit_stalling`` is a part of ``admit`` that no lap closes.
        self._phase_cpu_s = dict.fromkeys(
            (p for p in _PHASES if p != "admit_stalling"), 0.0)
        self._dispatch = dict.fromkeys(_FEEDS, 0)
        self._cache_resets = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # ---- public API --------------------------------------------------------

    @property
    def params(self):
        """The published tree the engine was given (what a reference's
        ``forward`` reads), turned back from the serving tree when asked:
        ``_Runner.published_params``. The programs' own tree is
        ``runner.params``."""
        return self.runner.published_params()

    def submit(self, prompt: List[int], max_new_tokens: int = 32,
               eos_token: Optional[int] = None,
               request_id: Any = None) -> _Request:
        return self.scheduler.submit(prompt, max_new_tokens, eos_token,
                                     request_id)

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
        k and v of every KV head, or a latent pool's one row, or under
        "index" an indexer's one key, padding and all; a pool without
        pages has no entry),
        ``state_slot_bytes`` (``{kind: bytes}``: what a slot holds in one
        layer of a pool that has no pages: under "state" a retention
        layer's state and normaliser, padding and all; under "delta" a
        delta layer's state and its convolution's history; under "conv"
        a short convolution's history alone; empty for a model that has
        none of them),
        ``queued`` (submitted, not yet admitted), beside the constants
        ``platform``, ``device_kind``, ``total_pages``, ``page_size`` and
        ``decode_attention`` (``"page_walk"``, for a latent pool
        ``"latent_walk"``, under a selection ``"sparse_walk"``, or
        ``"gather"``: the path of
        ops/paged_attention.py the decode program was built with; for a
        model of retention layers ops/retention.py's ``"state_kernel"``
        or ``"xla"``), ``decode_delta`` (ops/delta_attention.py's
        ``"delta_kernel"`` or ``"xla"`` for a model with delta layers,
        beside the latent layers' ``decode_attention``; ``"none"`` for
        any other), ``page_walk_step_tokens`` and
        ``latent_walk_step_tokens`` (``{kind: tokens}``: what a compute
        step of the page walk, or of the latent walk with or without a
        selection, covers in each pool it walks, ops/paged_attention.py
        ``walk_step_tokens``; each empty on any other path).

        Counts: ``decode_steps``; ``decode_slot_steps`` (sequences, summed
        over decode steps) and ``decode_kv_tokens`` (their cached tokens,
        prompt and generated so far, summed likewise), so that the mean
        batch and context of a step are quotients of differences;
        ``decode_kv_rows_read`` (the rows the steps' attention read,
        summed over steps, sequences and layers: a sequence's cached
        tokens, on a window layer at most the window; without window
        layers ``decode_kv_tokens`` times the layers; a latent layer's
        rows are one a token, whatever the heads; a retention layer
        reads none); ``decode_kv_rows_selected`` (of those rows, the ones
        the attention took into its softmax: all of them, but in a layer
        that attends over a selection the ``index_topk`` it keeps at
        most); ``decode_state_slot_layers`` (the states the steps
        read and wrote: sequences times retention or delta layers, summed
        over decode steps; times ``state_slot_bytes``, the bytes of state a
        step moved each way); of a model that selects blocks of its k/v
        pool ``blocks`` (absent otherwise): ``pages_read`` of
        ``pages_held`` (the pages of the kept blocks a KV head, of the
        pages a sequence held, each times the selecting layers, summed
        over decode steps and sequences), ``copies`` (the descriptors
        the steps' walks issued for those reads, counted as the pages
        are, a KV head and a pool: a kept block is one, its pages one
        aligned run of the pool, so ``copies / pages_read`` is a little
        over one in ``ratio`` pages a block: the last block's copy
        counts one for the 1 to ``ratio`` pages up to the token's own),
        ``steps_dense`` and
        ``steps_selected`` (sequence-steps before and past
        ``dense_len``), ``mean_row_bytes`` (a page's mean key, every KV
        head's, in one layer); of a model with Lightning layers
        ``linear`` (absent otherwise): ``slot_layers`` (states stepped:
        sequences times such layers, summed over decode steps) and
        ``slot_bytes`` (a slot's states over all of them); of a model
        with short-convolution layers ``conv`` (absent otherwise):
        ``slot_layers`` (histories shifted: sequences times such layers,
        summed over decode steps), ``slot_bytes`` (a slot's histories
        over all of them, the last ``conv_taps - 1`` rows a layer in the
        model's dtype: all such a layer keeps of a request), ``layers``
        (the layers that keep no pages) of ``layers_in_all``;
        ``kv_page_steps_held`` (pages the live sequences held, each
        times its pool's layers, summed over decode steps) and
        ``kv_page_steps_one_table`` (what they would have held with one
        table for every layer: the same number without window layers);
        ``prefills``, ``prefill_tokens`` (real) and
        ``prefill_bucket_tokens`` (padded to the bucket), of which
        ``prefill_streamed_bucket_tokens`` in prefills whose attention
        over k and v rows streamed K and V by block
        (ops/flash_attention.py ``forward_path``: a bucket whose K and V
        of a head do not fit VMEM whole; 0 off a TPU); ``submitted``,
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
        ``phase_cpu_s``: of each phase but ``admit_stalling``, the seconds
        the loop thread was ON the CPU (``time.thread_time()`` at the same
        boundaries). A phase's wall time less its CPU time is what the
        thread spent runnable or blocked and not running: in ``inputs``
        and ``emit``, which are Python alone, waiting for the GIL; in
        ``decode`` the GIL and whatever the dispatch blocks on;
        ``readback`` and ``idle`` wait by design. The CPU clock is the
        kernel's: where it ticks (10 ms on some hosts) and books a
        thread's time when the thread next enters the kernel, a phase
        may hold what the phase before it burnt, so read sums of
        neighbouring phases over tens of seconds, not one phase of one
        step.

        ``decode_dispatch``: every decode step dispatched, counted at its
        dispatch by what it found: ``fed`` (the step before it was still
        running: the device had work queued and never waited),
        ``starved_host`` (the step before it had ALREADY finished and no
        prefill ran in the round: the loop itself was late, and over
        ``decode_steps`` this is "my host is the bottleneck"),
        ``starved_prefill`` (a step was in flight and the round's
        admission ran a prefill, whose first token the loop waits for
        behind it: the device is empty when it comes, and every open
        stream stood still) and ``starved_lull`` (no step was in flight:
        nobody was open, this is the first step behind the prefill that
        ended a lull). Their sum is ``decode_steps`` plus the steps
        dispatched and not yet emitted (at most two in a reading) plus
        those a cache reset threw away. The class rides on the step's
        ``engine.decode`` annotation as ``feed``.

        ``moe``, for a model with experts only: ``expert_tokens`` (a list
        of E: (token, expert) assignments each expert was given, prefills
        and decode steps, summed over layers), ``assignments`` (their
        sum) and ``decode_assignments`` (the decode steps' part of it);
        for a model that holds a share of its experts E is the held
        ones, and ``assignments_elsewhere`` the assignments the router
        gave to experts on other chips, which nothing here computed;
        ``experts_reached`` ((layer, expert) pairs that a decode
        step gave at least one token, summed over decode steps) over
        ``layer_steps`` (decode steps x layers that have experts) is the
        experts such a layer of a decode step read;
        ``prefill_experts_reached`` is the same count over prefills;
        ``layer_calls`` (expert layers x programs run, decode steps and
        prefills) and ``small_rows_layer_calls``, those whose program
        was built with the grouped matmul for few rows a group
        (ops/grouped_matmul.py: the same rule, by the program's rows).

        ``weights``, constants of the engine's start (``serving_weights``):
        ``leaves_turned`` (stacked projections of the given tree that the
        engine stores in the serving order, ``llama.SERVING_ORDER``, a
        run of layers counting its own), ``bytes_turned`` (theirs; the
        engine's own copy of them, every other leaf being the given
        array) and ``turn_s`` (the seconds the one jitted call over
        them took, traced, compiled and run, before the pool was
        allocated).

        ``loop``, for a looped model only (``cfg.passes`` > 1): ``passes``
        (passes over the stack the decode steps ran: a step adds the
        model's passes, every one of them, since no token leaves early),
        ``exit_tokens`` and ``exit_pass_sum`` (of a model with an exit
        gate: the tokens made, a prefill's first and a decode step's one
        an active slot, and over them the sum of the pass each would
        leave at in expectation under the gate's own distribution,
        ``sum_t (t + 1) p_t``, formed on the device; their quotient lies
        between 1 and the passes) and the gauge ``kv_token_bytes`` (what
        a token holds over all the pools that keep a row a token, as
        allocated: ``kv_row_bytes`` times each pool's layers, which are
        the stack's once for every pass).

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
        ``held_timed_s`` and ``held_cpu_s``: the part of ``held_s`` in
        every eighth hold of an iterator, from its first, and of that
        the seconds the taking thread was on the CPU; the rest it
        waited, for the GIL above all (a sample: the CPU clock is a
        system call). Three
        histograms over ``hist_edges_s`` (``HIST_EDGES_S``: upper edges
        2% apart from 10 us to 63 s, one count more than edges, the first
        and last open; subtract two readings count by count for a
        window, and ``util/tsdb.quantile_from_histogram(edges, counts,
        0.9)`` reads its p90 to within 2.5% of the samples' own):
        ``emit_gap_hist``, seconds between two tokens of one request as
        the loop put them (a first token opens no gap, as a client
        counts: the cadence the engine made, a prefill's stand-still in
        every open stream included), ``taken_lag_hist`` and
        ``held_hist``, each taken token's part of ``taken_lag_s`` and
        ``held_s`` (a hold is counted when the consumer comes back).
        Stamps are ``time.time()`` of one process; a token's is its
        step's, one clock read a step."""
        # Telemetry read: publish whatever the decode tap ring has
        # accumulated so /metrics never lags a long burst.
        self.runner.decode_step.flush_taps()
        taken = self.scheduler.streams.read()
        with self._lock:
            out = {
                **self.scheduler.reading(taken),
                **self.books.reading(),
                **self.runner.reading(),
                "t": time.time(),
                "phase_s": dict(self._phase_s),
                "phase_cpu_s": dict(self._phase_cpu_s),
                "decode_dispatch": dict(self._dispatch),
                "cache_resets": self._cache_resets,
            }
        if "loop" in out:
            out["loop"]["kv_token_bytes"] = self.books.kv_token_bytes
        return out

    def shutdown(self):
        self._stop = True
        self._thread.join(timeout=5)
        try:
            self.runner.decode_step.flush_taps()
        except Exception:
            pass

    # ---- engine loop -------------------------------------------------------

    def _reset(self, cause: Exception):
        """Recover from a failed donated call: the cache's buffers may
        already be invalid, so rather than serve from dead buffers fail
        every open request with the root cause (the scheduler, which
        renews the books under the same lock), rebuild cache and carry
        (the runner), and forget the step in flight."""
        self._cache_resets += 1
        self.scheduler.reset(cause)
        self.runner.reset()
        self._flying = None

    def _admit(self) -> int:
        """One admission round: prefill queued requests into free slots
        until slots, pages or the queue run out, and say how many
        prefills ran. A prefill queues behind the decode step in flight
        and this thread waits for its first token, so that step's
        tokens are emitted after it, and the device has nothing queued
        when the round returns."""
        scheduler, runner = self.scheduler, self.runner
        prefills = 0
        while True:
            picked = scheduler.pick()
            if picked is None:
                return prefills
            import jax

            slot, prompt, bucket, (pages, tables) = picked
            try:
                # Table upload, padding, dispatch and the wait for the
                # first token: what every open stream stalls through.
                with jax.profiler.TraceAnnotation(
                        "engine.prefill", bucket=bucket, slot=slot):
                    first = runner.run_prefill(slot, prompt, bucket, pages,
                                               tables)
            except Exception as e:  # noqa: BLE001
                scheduler.prefill_failed(e)
                self._reset(e)
                continue
            prefills += 1
            scheduler.first_token(first)

    def _loop(self):
        """Admit, dispatch the next decode step, then read and emit the
        one before it (the module docstring has the order and why it is
        safe). Each phase is a profiler annotation (inert unless a
        ``jax.profiler`` trace is open; then it lands in the trace's
        host plane, on the device trace's clock) and, from the same
        ``perf_counter()`` boundaries, a running total in ``phase_s``
        and, of this thread's CPU time, in ``phase_cpu_s``. Each decode
        dispatch is counted by what it found on the device
        (``decode_dispatch``) and says so on its annotation (``feed``)."""
        import jax

        scheduler, runner = self.scheduler, self.runner
        span = jax.profiler.TraceAnnotation
        clock, cpu = time.perf_counter, time.thread_time
        phase_s, phase_cpu_s = self._phase_s, self._phase_cpu_s
        dispatch = self._dispatch
        t, t_cpu = clock(), cpu()

        def lap(phase: str) -> float:
            """Close ``phase`` at a boundary shared with the next one."""
            nonlocal t, t_cpu
            now, now_cpu = clock(), cpu()
            dt, t = now - t, now
            phase_s[phase] += dt
            phase_cpu_s[phase] += now_cpu - t_cpu
            t_cpu = now_cpu
            return dt

        while not self._stop:
            stalling = scheduler.streaming()
            with span("engine.admit"):
                prefilled = self._admit()
            dt = lap("admit")
            if stalling:
                phase_s["admit_stalling"] += dt
            flying = self._flying
            queued = scheduler.next_step(flying)
            try:
                if queued is not None:
                    with span("engine.inputs"):
                        runner.activate(queued.slots.keys())
                    lap("inputs")
                    # Nothing in flight: nobody was open, this step
                    # follows the prefill that ended the lull. A
                    # prefill's first token was waited for behind the
                    # step in flight; a step in flight whose tokens are
                    # ready left the device empty before this turn.
                    feed = ("starved_lull" if flying is None
                            else "starved_prefill" if prefilled
                            else "starved_host" if runner.done(flying.out)
                            else "fed")
                    with span("engine.decode", feed=feed):
                        queued.out = runner.step()
                    dispatch[feed] += 1
                    lap("decode")
                if flying is not None:
                    with span("engine.readback"):
                        out = runner.fetch(flying.out)
                    lap("readback")
            except Exception as e:  # noqa: BLE001
                # The cache was donated into the failed call: recover
                # like the prefill path, keep the loop alive for new work.
                self._reset(e)
                continue
            self._flying = queued
            if flying is not None:
                with span("engine.emit"):
                    scheduler.emit(
                        flying, runner.unpack(out, self.max_batch), queued)
                lap("emit")
            elif queued is None:
                # Burst boundary: the decode loop went idle, the last
                # step read — flush the batched metric taps accumulated
                # over the burst.
                runner.decode_step.flush_taps()
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
