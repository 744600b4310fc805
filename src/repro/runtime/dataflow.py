"""Streaming dataflow executor — the Klepsydra-style staged serving pipeline.

The paper's runtime gets its throughput from a *dataflow-oriented, lock-free
streaming* structure: compute is decomposed into stages connected by bounded
queues, and data moves through the stages continuously instead of being
batch-synchronized.  This module is that structure for the serving path:

    submit ─▶ [admit] ─▶ [prefill] ─▶ [decode] ─▶ [certify] ─▶ [release]
                 │           │            │            │            │
              admission   per-req      slotted     release      finished
              control     prefill      batch,      gate (hook)  stream
                          (unpadded    continuous
                          recurrent)   batching

  * Every arrow is a bounded single-producer/single-consumer ``Channel`` —
    the same queue primitive ``data/pipeline.prefetch`` streams host batches
    through (one shared implementation, two drivers).
  * The **decode** stage does continuous batching: requests join free slots
    of the fixed-capacity KV-cache/recurrent-state batch and leave it
    mid-flight, with no re-padding and no drain barrier (slot state is data,
    not structure, so the jitted step never recompiles).
  * The **certify** stage is the release gate.  Engines run it pass-through;
    a fleet installs its certify-before-release hook here, so withholding a
    finished request until its replica proves clean is a *pipeline stage*,
    not an inline call buried in a monolithic step loop.
  * SEU injection is per-stage: ``StreamingExecutor.strike`` routes a fault
    to the stage that owns the site (decode owns ``kv_cache`` and
    ``decode_state``, the parameter store owns ``weights``), which is how
    the campaign engine drills the pipeline.

Two drivers share the stage/queue primitives:

  * the **cooperative driver** (``StreamingExecutor.step``) pumps the stages
    in topological order on the caller's thread.  It takes no locks and its
    schedule is a pure function of the submission order, so decode streams
    — and therefore fleet failover replays — are bit-exact, the property
    every dependability campaign certifies.
  * the **threaded driver** (``ThreadedSource``) runs a producer stage on a
    daemon thread blocking on its outbox — the host-boundary streaming mode
    (data prefetch overlapping device compute).

Device-fault recovery (snapshot/rollback, decode-state scrubbing) lives at
the executor level because a consistent restore spans admit bookkeeping and
decode state together; see docs/streaming.md and docs/recovery.md.

Observability (``repro.obs``) runs on two clocks:

  * the **profiler's clock**, always on: every pump opens a host span
    (``obs.span``, a ``jax.profiler.TraceAnnotation``) at each layer
    boundary — ``engine/state_scrub`` and ``engine/storage_scrub`` (each
    with ``engine/rollback`` inside when it restores), ``engine/admit``,
    per prefilled request ``engine/prefill`` and
    ``engine/prefill_readback``, ``engine/join``, ``engine/snapshot``,
    ``engine/decode_window`` (or ``engine/decode_step``),
    ``engine/decode_readback``, ``engine/decode_replay``,
    ``engine/state_refresh``, ``engine/certify``, ``engine/release`` (and
    ``engine/report`` around ``Engine.dependability_report``'s readback) —
    so a profile attributes the device's idle time to what the host was
    doing.
    Every request carries five stage stamps on ``time.perf_counter()``
    (``Request.t_submit`` … ``t_release``), and each release logs them to
    ``obs.trace.RELEASED``.
  * the executor's deterministic **tick clock** (one tick per cooperative
    pump cycle), through three optional hooks that cost nothing when
    absent:

    - ``tracer=``    a ``SpanTracer``: per-request spans for every stage
      residency (admit → prefill → decode → certify) plus release instants
      and per-pump queue-depth / slot-occupancy counter tracks.  Exports
      Chrome ``trace_event`` JSON; byte-identical across same-seed runs.
    - ``event_log=`` an ``EventLog``: typed dependability events (strike /
      detection / rollback) with fault provenance, the substrate campaign
      reports reconstruct injection→detection→recovery timelines from.
    - ``metrics=``   a ``Registry``: streaming counters/gauges/histograms
      (released requests, release-latency ticks, queue depths) — bounded
      memory regardless of run length.

The FFN's in-graph ABFT checks report through the programs' outputs: the
prefill, decode-step and decode-window programs each return the summed
DependabilityStats of the checks they ran, which the executor folds into
its dependability counters.

See docs/observability.md.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import abft
from repro.core.dependability import DependabilityStats, collect_stats
from repro.models import api as model_api
from repro.models.config import ArchConfig
from repro.obs import trace as obs_trace
from repro.obs.trace import span

# Every program the engine runs is a named function, so its XLA module
# reads ``jit_<name>`` in a profile: ``jit_engine_prefill``,
# ``jit_engine_decode_step``, ``jit_engine_decode_window``,
# ``jit_splice_slot``, ``jit_state_checksums``, ``jit_storage_verify``
# (the deploy-time weight checksums stay ``jit_storage_checksums``).


_storage_checksums = jax.jit(abft.storage_checksums)


@jax.jit
def state_checksums(state):
    """Decode-state checksums: the storage-scrub identity applied to the
    live KV cache / recurrent state + token buffer (scrub, refresh and
    snapshot); jitted once per cache structure."""
    return abft.storage_checksums(state)


@jax.jit
def storage_verify(params, checks):
    """The in-serve weight verify against the golden storage checksums."""
    return abft.verify_storage(params, checks)


@jax.jit
def splice_slot(batch_cache, one_cache, tokens, slot, first_tok, n):
    """Join-time slot splice, fused into one compiled call: write the
    prefilled request's cache rows and first token into ``slot`` of the live
    batch.  Module-level jit so every executor (and every fleet replica)
    shares one compile cache entry per cache structure."""
    cache = model_api.cache_write_slot(batch_cache, one_cache, slot, n)
    return cache, tokens.at[slot].set(first_tok)


def decode_step_fn(cfg: ArchConfig):
    """The jitted decode step ``(params, tokens, cache) -> (next tokens,
    cache, stats)``: greedy next token of every slot, and the summed
    DependabilityStats of the in-graph FFN checks the step ran."""
    def engine_decode_step(params, tokens, cache):
        with collect_stats() as sink:
            logits, cache = model_api.decode_step(cfg, params, tokens, cache)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache,
                sink.stats)
    return jax.jit(engine_decode_step)


def prefill_fn(cfg: ArchConfig, max_len: int):
    """The jitted prefill ``(params, tokens) -> (logits, cache, stats)``."""
    def engine_prefill(params, tokens):
        with collect_stats() as sink:
            logits, cache = model_api.prefill(cfg, params, tokens, max_len)
        return logits, cache, sink.stats
    return jax.jit(engine_prefill)


def _checks_equal(a, b) -> bool:
    """Host verdict: does every leaf checksum match?"""
    return all(bool(x) for x in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda p, q: p == q, a, b)))


# Multi-step decode windows: one jitted N-step scan per (decode fn, N,
# eos, max_len), over a ``decode_step_fn`` program.  Keyed on the decode fn
# *object* (a strong reference is kept so ids cannot be recycled), which is
# how fleet replicas / benchmark reps sharing a ``compiled`` pair also share
# one window compilation.
_DECODE_WINDOW_CACHE: dict = {}


def _decode_window_fn(decode_fn, n_steps: int, eos_id: int, max_len: int):
    """Build (or fetch) the jitted N-step decode window.

    The scan carries (tokens, cache, remaining, pos, active-mask) on device
    and emits per-step (next-token, finished-mask) — join/EOS/max-len
    accounting is evaluated in device-side masks, so the host reads back
    once per window instead of once per step.  Every slot steps every
    inner step (slot rows are independent and a later join splices whole
    rows), which is exactly the per-step engine's behavior for slots that
    finished but have not been re-filled yet.  Returns (carry, (next
    tokens, finished masks), stats summed over the window's N steps).
    """
    key = (id(decode_fn), n_steps, eos_id, max_len)
    hit = _DECODE_WINDOW_CACHE.get(key)
    if hit is not None:
        return hit[1]

    def engine_decode_window(params, tokens, cache, remaining, pos, active):
        def body(carry, _):
            (tokens, cache, remaining, pos, active), stats = carry
            nxt, cache, step_stats = decode_fn(params, tokens, cache)
            remaining = jnp.where(active, remaining - 1, remaining)
            pos = jnp.where(active, pos + 1, pos)
            finished = active & ((remaining <= 0) | (nxt == eos_id)
                                 | (pos >= max_len - 1))
            return (((nxt, cache, remaining, pos, active & ~finished),
                     DependabilityStats.merge(stats, step_stats)),
                    (nxt, finished))
        (carry, stats), emitted = jax.lax.scan(
            body, ((tokens, cache, remaining, pos, active),
                   DependabilityStats.zero()),
            None, length=n_steps)
        return carry, emitted, stats

    fn = jax.jit(engine_decode_window)
    _DECODE_WINDOW_CACHE[key] = (decode_fn, fn)
    return fn


# ---------------------------------------------------------------------------
# Queue/stage primitives (shared with data/pipeline.prefetch)
# ---------------------------------------------------------------------------


class Closed(Exception):
    """Raised by blocking Channel ops once the channel is closed."""


class Channel:
    """Bounded single-producer/single-consumer queue between two stages.

    Two APIs over one deque:

      * cooperative — ``try_put``/``try_get`` never block and take no locks
        (single-thread pipeline pumping; deque ops are atomic under the
        interpreter), so the deterministic driver is lock-free on its hot
        path;
      * streaming — ``put``/``get`` block on capacity/emptiness and wake on
        ``close()`` (the threaded host-boundary driver).

    ``capacity=0`` means unbounded (terminal channels that are drained every
    pump cycle).
    """

    _EMPTY = object()

    def __init__(self, capacity: int = 0, name: str = ""):
        self.capacity = int(capacity)
        self.name = name
        self.items: deque = deque()
        self._closed = False
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)

    # ---------------------------------------------------------- cooperative
    def full(self) -> bool:
        return self.capacity > 0 and len(self.items) >= self.capacity

    def try_put(self, item) -> bool:
        if self.full():
            return False
        self.items.append(item)
        return True

    def try_get(self):
        """Next item or ``Channel.EMPTY`` — non-blocking."""
        if not self.items:
            return self._EMPTY
        return self.items.popleft()

    @classmethod
    def is_empty_token(cls, item) -> bool:
        return item is cls._EMPTY

    def drain(self) -> list:
        out = list(self.items)
        self.items.clear()
        return out

    def __len__(self) -> int:
        return len(self.items)

    def __iter__(self):
        return iter(self.items)

    # ------------------------------------------------------------ streaming
    def put(self, item):
        with self._not_full:
            while self.full() and not self._closed:
                self._not_full.wait()
            if self._closed:
                raise Closed(self.name)
            self.items.append(item)
            self._not_empty.notify()

    def get(self):
        with self._not_empty:
            while not self.items and not self._closed:
                self._not_empty.wait()
            if not self.items:
                raise Closed(self.name)
            item = self.items.popleft()
            self._not_full.notify()
            return item

    def close(self):
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()
            self._not_full.notify_all()


class Stage:
    """One pipeline stage: pull from ``inbox``, push to ``outbox``.

    ``pump()`` moves as much work as channel capacity allows and returns
    whether any progress was made; drivers decide *when* to pump (the
    cooperative driver in topological order, a threaded driver in a loop).
    """

    name = "stage"

    def pump(self) -> bool:
        raise NotImplementedError


class SourceStage(Stage):
    """Producer stage: pushes ``produce(i)`` for i = start, start+1, … into
    its outbox — the generalization of the hand-rolled prefetch thread."""

    name = "source"

    def __init__(self, produce: Callable[[int], Any], outbox: Channel,
                 start: int = 0):
        self.produce = produce
        self.outbox = outbox
        self._i = start
        self._pending = Channel._EMPTY   # produced but not yet enqueued

    def pump(self) -> bool:
        moved = False
        while True:
            if Channel.is_empty_token(self._pending):
                self._pending = self.produce(self._i)
                self._i += 1
            if not self.outbox.try_put(self._pending):
                return moved
            self._pending = Channel._EMPTY
            moved = True

    def pump_blocking(self):
        """Streaming-driver variant: block on outbox space (raises Closed)."""
        if Channel.is_empty_token(self._pending):
            self._pending = self.produce(self._i)
            self._i += 1
        self.outbox.put(self._pending)
        self._pending = Channel._EMPTY


class ThreadedSource:
    """Drive a ``SourceStage`` on a daemon thread — the streaming driver for
    host-side stages (batch synthesis overlapping device compute).  The
    consumer reads the stage's outbox; ``close()`` unblocks the producer and
    joins the thread."""

    def __init__(self, stage: SourceStage):
        self.stage = stage
        self._thread = threading.Thread(
            target=self._run, name=f"stage-{stage.name}", daemon=True)

    def start(self) -> "ThreadedSource":
        self._thread.start()
        return self

    def _run(self):
        try:
            while True:
                self.stage.pump_blocking()
        except Closed:
            pass

    def close(self):
        self.stage.outbox.close()
        self._thread.join(timeout=5.0)


# ---------------------------------------------------------------------------
# Pipeline payloads
# ---------------------------------------------------------------------------


STAGE_STAMPS = ("t_submit", "t_prefill", "t_join", "t_finish", "t_release")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 16
    # filled by the pipeline
    output: Optional[List[int]] = None
    # stage stamps on time.perf_counter(), always taken (0.0 = not reached):
    # submitted, prefill started, joined a decode slot, last token decoded,
    # released.  A rollback that replays or requeues a request clears the
    # stamps it will take again.
    t_submit: float = 0.0
    t_prefill: float = 0.0
    t_join: float = 0.0
    t_finish: float = 0.0
    t_release: float = 0.0
    # the pump ticks of submit and finish on the executor's deterministic
    # clock (-1 = not stamped)
    submitted_tick: int = -1
    finished_tick: int = -1

    def stamps(self) -> List[float]:
        return [getattr(self, k) for k in STAGE_STAMPS]

    def clear_stamps(self, after: str) -> None:
        """Zero every stage stamp after ``after`` (a ``STAGE_STAMPS``
        name): the stages the request will pass again."""
        for k in STAGE_STAMPS[STAGE_STAMPS.index(after) + 1:]:
            setattr(self, k, 0.0)

    # ------------------------------------------------- transport (wire form)
    def to_doc(self) -> dict:
        """JSON-safe wire form for the fleet's process-isolation transport.
        Token ids are coerced to plain ints (device readbacks may be numpy
        scalars) so the frame header serializes with the stdlib encoder."""
        return {
            "uid": int(self.uid),
            "prompt": [int(t) for t in self.prompt],
            "max_new_tokens": int(self.max_new_tokens),
            "output": (None if self.output is None
                       else [int(t) for t in self.output]),
            "stamps": [float(t) for t in self.stamps()],
            "submitted_tick": int(self.submitted_tick),
            "finished_tick": int(self.finished_tick),
        }

    @classmethod
    def from_doc(cls, doc: dict) -> "Request":
        return cls(
            uid=int(doc["uid"]),
            prompt=[int(t) for t in doc["prompt"]],
            max_new_tokens=int(doc.get("max_new_tokens", 16)),
            output=(None if doc.get("output") is None
                    else [int(t) for t in doc["output"]]),
            **dict(zip(STAGE_STAMPS, doc.get("stamps",
                                             [0.0] * len(STAGE_STAMPS)))),
            submitted_tick=int(doc.get("submitted_tick", -1)),
            finished_tick=int(doc.get("finished_tick", -1)),
        )

    def sync_from_doc(self, doc: dict) -> "Request":
        """Fold a wire copy's pipeline-filled fields back into this (the
        canonical, parent-side) object — the certify upcall path."""
        self.output = (None if doc.get("output") is None
                       else [int(t) for t in doc["output"]])
        for k, t in zip(STAGE_STAMPS, doc.get("stamps", ())):
            setattr(self, k, float(t))
        self.finished_tick = int(doc.get("finished_tick", -1))
        return self


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    tokens_out: int = 0
    replays: int = 0
    faults_detected: int = 0

    def tokens_per_step(self) -> float:
        return self.tokens_out / max(self.steps, 1)


@dataclasses.dataclass
class _Prefilled:
    """A request that cleared the prefill stage: its single-request cache,
    first sampled token, and true (unpadded) prompt length."""
    req: Request
    cache: Any
    first_token: int
    prompt_len: int


# ---------------------------------------------------------------------------
# Stages of the serving pipeline
# ---------------------------------------------------------------------------


class AdmitStage(Stage):
    """Submission queue → prefill inbox, gated on slot reservations.

    A request is admitted only when the decode batch will have a free slot
    for it once prefilled: reservable = free slots − requests already in
    flight through the prefill stage.  FIFO order is preserved — admission
    order is what makes replay deterministic.

    ``drain_barrier=True`` degrades admission to pad-and-step static
    batching: a new group is admitted only once the decode batch has fully
    drained, so a freed slot idles until the group's longest request
    finishes.  This is the monolith-equivalent scheduling baseline the
    serving benchmark prices continuous batching against — never what a
    production engine should run."""

    name = "admit"

    def __init__(self, inbox: Channel, outbox: Channel,
                 prefill: "PrefillStage", decode: "DecodeStage",
                 drain_barrier: bool = False):
        self.inbox = inbox
        self.outbox = outbox
        self.prefill = prefill
        self.decode = decode
        self.drain_barrier = drain_barrier

    def reservable(self) -> int:
        if self.drain_barrier and self.decode.active:
            return 0                   # barrier: wait for a full drain
        in_prefill = len(self.outbox) + len(self.prefill.outbox)
        return self.decode.n_free() - in_prefill

    def pump(self) -> bool:
        moved = False
        tr = self.decode.ex.tracer
        while (self.inbox.items and self.reservable() > 0
               and not self.outbox.full()):
            req = self.inbox.items.popleft()
            self.outbox.try_put(req)
            if tr is not None:
                tr.close_span(req.uid, "admit")
                tr.open_span(req.uid, "prefill", prompt_len=len(req.prompt))
            moved = True
        return moved


class PrefillStage(Stage):
    """Per-request prefill: prompt → (single-request cache, first token).

    Attention caches mask past each row's length, so right-padding prompts
    to a bucket is free and bounds compile count; recurrent state integrates
    every token it sees, so state families prefill the exact prompt (one
    compile per distinct length instead of per bucket)."""

    name = "prefill"

    def __init__(self, ex: "StreamingExecutor", inbox: Channel,
                 outbox: Channel):
        self.ex = ex
        self.inbox = inbox
        self.outbox = outbox

    def _prefill_one(self, req: Request) -> _Prefilled:
        ex = self.ex
        # reserve cache rows for the token budget, but never truncate the
        # prompt to nothing: a budget >= max_len used to slice to an empty
        # prompt and crash the whole engine (losing every in-flight request);
        # generation is truncated at the cache edge by the decode-stage
        # max_len guard instead
        req.t_prefill = time.perf_counter()
        prompt = req.prompt[: max(1, ex.max_len - req.max_new_tokens)]
        if ex.cfg.recurrent is not None:
            pad = len(prompt)
        else:
            pad = -(-len(prompt) // ex.prefill_pad) * ex.prefill_pad
        with span("engine/prefill", uid=req.uid, tokens=pad):
            toks = jnp.asarray([prompt + [0] * (pad - len(prompt))],
                               jnp.int32)
            logits, cache1, stats = ex._prefill(ex.params, toks)
            ex.record_dependability(stats)
        with span("engine/prefill_readback"):
            nxt = int(jnp.argmax(logits[0, len(prompt) - 1]))
        return _Prefilled(req, cache1, nxt, len(prompt))

    def pump(self) -> bool:
        moved = False
        while not self.outbox.full():
            req = self.inbox.try_get()
            if Channel.is_empty_token(req):
                break
            self.outbox.try_put(self._prefill_one(req))
            moved = True
        return moved


class DecodeStage(Stage):
    """The continuous-batching core: owns the slotted decode batch.

    State is one fixed-capacity KV-cache/recurrent-state pytree plus the
    per-slot token buffer and bookkeeping vectors.  ``join()`` splices
    prefilled requests into free slot rows (``models/common.cache_write_slot``
    — no re-padding, no drain of in-flight slots); ``decode_once()`` steps
    the whole batch and emits finished requests downstream.  Each pump is
    join + at most one step, so requests enter and leave the batch while
    their neighbors keep decoding."""

    name = "decode"

    def __init__(self, ex: "StreamingExecutor", inbox: Channel,
                 outbox: Channel):
        self.ex = ex
        self.inbox = inbox
        self.outbox = outbox
        self.reset_state()

    def reset_state(self):
        ex = self.ex
        self.cache = ex.place(
            model_api.init_cache(ex.cfg, ex.capacity, ex.max_len))
        self.tokens = ex.place(jnp.zeros((ex.capacity,), jnp.int32))
        self.slot_pos = np.zeros(ex.capacity, np.int32)
        self.slot_remaining = np.zeros(ex.capacity, np.int32)
        self.active: dict = {}                    # slot -> Request
        # finished requests the (bounded) outbox refused: held here and
        # re-offered every pump — backpressure must never *drop* a request
        self._pending: deque = deque()

    def n_free(self) -> int:
        return self.ex.capacity - len(self.active)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.ex.capacity) if s not in self.active]

    def _emit(self, req: Request) -> None:
        """Hand a finished request downstream, FIFO: anything already held
        goes first, and a full outbox parks the request instead of losing
        it (the unchecked ``try_put`` drop bug)."""
        ex = self.ex
        req.finished_tick = ex.tick
        if ex.tracer is not None:
            ex.tracer.close_span(req.uid, "decode",
                                 tokens=len(req.output or ()))
            ex.tracer.open_span(req.uid, "certify")
        self._pending.append(req)
        self.flush_pending()

    def flush_pending(self) -> bool:
        moved = False
        while self._pending and self.outbox.try_put(self._pending[0]):
            self._pending.popleft()
            moved = True
        return moved

    def join(self) -> bool:
        """Splice prefilled requests into free slots (continuous batching).
        Requests whose prompt already produced their only token finish at
        admission and go straight downstream."""
        ex = self.ex
        moved = self.flush_pending()
        for slot in self.free_slots():
            item = self.inbox.try_get()
            if Channel.is_empty_token(item):
                break
            req, n = item.req, item.prompt_len
            req.t_join = time.perf_counter()
            ex._since_snapshot.append(req)
            if ex.tracer is not None:
                ex.tracer.close_span(req.uid, "prefill")
                ex.tracer.open_span(req.uid, "decode", slot=slot,
                                    prompt_len=n)
            self.cache, self.tokens = splice_slot(
                self.cache, item.cache, self.tokens,
                jnp.int32(slot), jnp.int32(item.first_token), jnp.int32(n))
            self.slot_pos[slot] = n
            # the prefill itself produced the first new token
            self.slot_remaining[slot] = req.max_new_tokens - 1
            req.output = [item.first_token]
            self.active[slot] = req
            moved = True
            # finish at admission: budget exhausted by the prefill token, or
            # the prefill token itself is EOS (burning the whole budget on a
            # request that already terminated would waste its slot)
            if self.slot_remaining[slot] <= 0 or item.first_token == ex.eos_id:
                req.t_finish = time.perf_counter()
                del self.active[slot]
                self._emit(req)
        return moved

    def decode_once(self) -> bool:
        """One decode step for every active slot; finished requests are
        emitted to the certify stage."""
        ex = self.ex
        if not self.active:
            return False
        with span("engine/decode_step"):
            nxt, self.cache, stats = ex._decode(ex.params, self.tokens,
                                                self.cache)
            self.tokens = nxt
            ex.record_dependability(stats)
        with span("engine/decode_readback"):
            nxt_host = np.asarray(nxt)
        with span("engine/decode_replay"):
            ex.stats.steps += 1
            done_slots = []
            for slot, req in list(self.active.items()):
                req.output.append(int(nxt_host[slot]))
                self.slot_pos[slot] += 1
                self.slot_remaining[slot] -= 1
                ex.stats.tokens_out += 1
                if (self.slot_remaining[slot] <= 0
                        or int(nxt_host[slot]) == ex.eos_id
                        or self.slot_pos[slot] >= ex.max_len - 1):
                    req.t_finish = time.perf_counter()
                    done_slots.append(slot)
            for slot in done_slots:
                self._emit(self.active.pop(slot))
        return True

    def decode_window(self) -> bool:
        """Multi-step dispatch: one jitted ``multi_step``-deep scan over the
        slot batch, then a single host readback of the per-step token /
        finished-mask trajectory.  Host bookkeeping replays the window from
        the device masks — token streams are bit-identical to per-step
        decoding because slots are independent and joins (which only happen
        between windows) splice whole slot rows."""
        ex = self.ex
        if not self.active:
            return False
        window = _decode_window_fn(ex._decode, ex.multi_step, ex.eos_id,
                                   ex.max_len)
        active_mask = np.zeros(ex.capacity, bool)
        active_mask[list(self.active)] = True
        with span("engine/decode_window", slots=len(self.active)):
            (tokens, cache, _, _, _), (nxt_all, fin_all), stats = window(
                ex.params, self.tokens, self.cache,
                jnp.asarray(self.slot_remaining), jnp.asarray(self.slot_pos),
                jnp.asarray(active_mask))
            self.tokens, self.cache = tokens, cache
            ex.record_dependability(stats)
        with span("engine/decode_readback"):
            nxt_host = np.asarray(nxt_all)        # (N, capacity)
            fin_host = np.asarray(fin_all)
        with span("engine/decode_replay"):
            for i in range(ex.multi_step):
                if not self.active:
                    break              # trailing idle steps are not counted
                ex.stats.steps += 1
                done_slots = []
                for slot, req in list(self.active.items()):
                    req.output.append(int(nxt_host[i, slot]))
                    self.slot_pos[slot] += 1
                    self.slot_remaining[slot] -= 1
                    ex.stats.tokens_out += 1
                    if fin_host[i, slot]:
                        req.t_finish = time.perf_counter()
                        done_slots.append(slot)
                for slot in done_slots:
                    self._emit(self.active.pop(slot))
        return True

    def decode_any(self) -> bool:
        """Per-step or windowed decode, per the executor's ``multi_step``."""
        if self.ex.multi_step > 1:
            return self.decode_window()
        return self.decode_once()

    def pump(self) -> bool:
        joined = self.join()
        return self.decode_any() or joined


class CertifyStage(Stage):
    """The release gate.  ``hook(req) -> bool`` decides whether a finished
    request flows on to release (True) or is withheld — the hook's owner
    (e.g. a fleet running certify-before-release weight scrubs) takes
    custody of withheld requests and settles them out of band.  No hook
    means trivially certified (a bare engine trusts its own scrubs)."""

    name = "certify"

    def __init__(self, ex: "StreamingExecutor", inbox: Channel,
                 outbox: Channel):
        self.ex = ex
        self.inbox = inbox
        self.outbox = outbox
        # certified requests a full release channel refused — retried every
        # pump rather than silently dropped
        self._pending: deque = deque()

    def _forward(self, req: Request) -> None:
        if self._pending or not self.outbox.try_put(req):
            self._pending.append(req)

    def pump(self) -> bool:
        moved = False
        while self._pending and self.outbox.try_put(self._pending[0]):
            self._pending.popleft()
            moved = True
        tr = self.ex.tracer
        while True:
            req = self.inbox.try_get()
            if Channel.is_empty_token(req):
                return moved
            moved = True
            hook = self.ex.certify
            if hook is None or hook(req):
                if tr is not None:
                    tr.close_span(req.uid, "certify", certified=True)
                self._forward(req)
            elif tr is not None:
                # withheld: the hook's owner (fleet) takes custody and
                # settles out of band — close the span with the verdict
                # rather than leaving it dangling forever
                tr.close_span(req.uid, "certify", certified=False,
                              withheld=True)


class ReleaseStage(Stage):
    """Terminal stage: certified requests accumulate here until the caller
    collects them (``StreamingExecutor.step`` drains once per pump cycle)."""

    name = "release"

    def __init__(self, inbox: Channel):
        self.inbox = inbox

    def pump(self) -> bool:
        return False                               # terminal — nothing to move

    def collect(self) -> List[Request]:
        return self.inbox.drain()


# ---------------------------------------------------------------------------
# The executor: stages + cooperative driver + fault tolerance
# ---------------------------------------------------------------------------


class StreamingExecutor:
    """Staged streaming executor with a deterministic cooperative driver.

    One ``step()`` pumps every stage once in topological order — the
    synchronous-dataflow schedule.  Because stage order and channel order
    are fixed, the token streams are a pure function of submission order:
    the bit-exact-replay property fleets and campaigns certify.

    Fault tolerance spans the stages:

      * every ``snapshot_every`` steps the decode-stage state plus admission
        bookkeeping is snapshotted (checksummed, so a struck snapshot is
        refused at restore);
      * ``state_scrub`` runs the decode-state checksum scrub as a pipeline
        guard before the decode stage consumes its state ("detect" raises
        events for a supervisor, "rollback" restores the verified snapshot
        in place);
      * ``strike(site, fault, key)`` is the per-stage SEU injection surface
        for campaigns.
    """

    def __init__(self, cfg: ArchConfig, params, capacity: int = 8,
                 max_len: int = 512, prefill_pad: int = 64,
                 snapshot_every: int = 32, eos_id: int = -1,
                 compiled=None, state_scrub: str = "off",
                 storage_scrub: str = "off", storage_scrub_every: int = 1,
                 certify: Optional[Callable[[Request], bool]] = None,
                 drain_barrier: bool = False, multi_step: int = 1,
                 tracer=None, event_log=None, metrics=None, device=None):
        self.cfg = cfg
        # the device that holds this executor's params and decode state
        # (a fleet puts each replica on its own chip); None leaves placement
        # to JAX's default device
        self.device = device
        self.params = params
        self.capacity = capacity
        self.max_len = max_len
        self.prefill_pad = prefill_pad
        self.eos_id = eos_id
        self.snapshot_every = snapshot_every
        self.certify = certify
        if multi_step < 1:
            raise ValueError(f"multi_step must be >= 1, got {multi_step}")
        # N=1: per-step decode (host readback every step, joins between
        # every step).  N>1: jitted N-step windows with device-side finish
        # masks — same token streams, 1/N host syncs, joins at window edges.
        self.multi_step = multi_step
        self.stats = EngineStats()

        # observability — pure observers, all optional (see repro.obs).
        # tick is the deterministic pump-cycle clock spans/events key on;
        # it advances once per step() and never rolls back.
        self.tick = 0
        self.tracer = tracer
        self.event_log = event_log
        self.metrics = metrics
        if metrics is not None:
            self._m_submitted = metrics.counter(
                "engine_requests_submitted_total", "requests submitted")
            self._m_released = metrics.counter(
                "engine_requests_released_total",
                "requests that cleared the release stage")
            self._m_tokens = metrics.counter(
                "engine_tokens_out_total", "decoded tokens")
            self._m_steps = metrics.counter(
                "engine_decode_steps_total", "decode steps executed")
            self._m_latency = metrics.histogram(
                "engine_release_latency_ticks",
                "submit-to-release latency in pump ticks",
                buckets=tuple(float(2 ** i) for i in range(14)))
            self._m_qdepth = metrics.gauge(
                "engine_queue_depth", "requests queued before decode")
            self._m_slots = metrics.gauge(
                "engine_active_slots", "occupied decode slots")
            self._mm_steps = 0          # last stats.steps folded into counters
            self._mm_tokens = 0

        if compiled is not None:
            # replica fleets share one jitted (decode, prefill) pair so N
            # executors over the same config compile once, not N times
            self._decode, self._prefill = compiled
        else:
            self._decode = decode_step_fn(cfg)
            self._prefill = prefill_fn(cfg, max_len)

        # channels: submission is unbounded (admission control is a policy
        # above the engine); prefill channels are slot-bounded; certify/
        # release are drained every cycle
        self.submit_ch = Channel(0, "submit")
        self._admit_ch = Channel(capacity, "admitted")
        self._prefill_ch = Channel(capacity, "prefilled")
        self._certify_ch = Channel(0, "finished")
        self._release_ch = Channel(0, "certified")

        self.prefill = PrefillStage(self, self._admit_ch, self._prefill_ch)
        self.decode = DecodeStage(self, self._prefill_ch, self._certify_ch)
        self.admit = AdmitStage(self.submit_ch, self._admit_ch,
                                self.prefill, self.decode,
                                drain_barrier=drain_barrier)
        self.certifier = CertifyStage(self, self._certify_ch,
                                      self._release_ch)
        self.release = ReleaseStage(self._release_ch)
        self.stages: List[Stage] = [self.admit, self.prefill, self.decode,
                                    self.certifier, self.release]

        self._snapshot = None
        self._snapshot_step = 0
        self._since_snapshot: List[Request] = []   # admitted after snapshot
        self.dependability = DependabilityStats.zero()

        # decode-state scrubbing: "off" | "detect" | "rollback"
        if state_scrub not in ("off", "detect", "rollback"):
            raise ValueError(f"state_scrub must be off|detect|rollback, "
                             f"got {state_scrub!r}")
        self.state_scrub = state_scrub
        self._expected_check = None        # checksums after last mutation
        self.state_events: List[dict] = []  # drained by fleets / campaigns

        # in-serve weight-storage scrubbing: verify the live parameters
        # against construction-time storage checksums on a tick cadence.
        #   "off"       no storage scrub (a fleet/deploy layer may own it)
        #   "detect"    alarm-only — run at every-pump cadence so detection
        #               latency is bounded (the corrupted stream still
        #               ships; detect-only coverage is only as good as how
        #               fast it raises the alarm)
        #   "rollback"  restore the golden (construction-time) parameters —
        #               healing is retroactive, so the cadence can be
        #               amortized (``storage_scrub_every`` ticks per verify)
        # The baseline is blessed at construction and deliberately NOT
        # refreshed by ``reset(params=)`` — a reset handing over corrupted
        # params must still be caught.  Intentional weight swaps (rolling
        # deploys) call ``refresh_storage_baseline()``.
        if storage_scrub not in ("off", "detect", "rollback"):
            raise ValueError(f"storage_scrub must be off|detect|rollback, "
                             f"got {storage_scrub!r}")
        self.storage_scrub = storage_scrub
        self.storage_scrub_every = max(1, int(storage_scrub_every))
        self._storage_checks = None
        self._golden_params = None
        self._storage_alarmed = False
        if storage_scrub != "off":
            self.refresh_storage_baseline()

    @property
    def compiled(self):
        """The jitted (decode, prefill) pair, shareable with same-config
        executors via the ``compiled=`` constructor argument."""
        return (self._decode, self._prefill)

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = self.place(value)

    def place(self, tree):
        """Commit ``tree`` to this executor's device (as is when unset)."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    def reset(self, params=None):
        """Return run state (channels, slots, cache, per-run stats) to
        fresh, optionally with new (same-shaped) params.  Lifetime
        dependability counters survive resets — a campaign accumulates
        verdicts across many reset+run trials — and compiled functions are
        kept (params are traced arguments, so swapping them is free)."""
        if params is not None:
            self.params = params
        for ch in (self.submit_ch, self._admit_ch, self._prefill_ch,
                   self._certify_ch, self._release_ch):
            ch.items.clear()
        self.decode.reset_state()
        self.certifier._pending.clear()
        self.stats = EngineStats()
        if self.metrics is not None:
            self._mm_steps = 0
            self._mm_tokens = 0
        self._snapshot = None
        self._snapshot_step = 0
        self._since_snapshot = []
        self._expected_check = None
        self.state_events = []
        self._storage_alarmed = False

    # ------------------------------------------------------- dependability
    def _device_state(self) -> dict:
        """The device-resident decode-stage state the scrub covers (host-side
        slot bookkeeping lives in ECC'd host memory in the deployment this
        models, so it is outside the SEU threat surface)."""
        return {"cache": self.decode.cache, "tokens": self.decode.tokens}

    def _refresh_state_check(self):
        """Re-checksum after a legitimate mutation — the running 'expected'
        fingerprint every later scrub compares against."""
        if self.state_scrub != "off":
            with span("engine/state_refresh"):
                self._expected_check = state_checksums(self._device_state())

    def scrub_decode_state(self) -> bool:
        """Verify the live decode state against the post-mutation checksum;
        True == clean.  A mismatch means an SEU struck the KV cache /
        recurrent state or the token buffer *between* pump cycles — the
        transient site no weight scrub can see."""
        if self._expected_check is None:
            return True
        fresh = state_checksums(self._device_state())
        clean = _checks_equal(fresh, self._expected_check)
        # emit_events=False: _scrub_and_recover emits the (site-attributed)
        # detection event itself — one detection, one event
        self.record_dependability({
            "faults_detected": jnp.int32(0 if clean else 1),
            "checks_run": jnp.int32(1)}, emit_events=False)
        return clean

    def _scrub_and_recover(self):
        """The pre-decode scrub guard: detect, and under ``rollback`` restore
        the last verified snapshot (checkpoint/restart at decode
        granularity).  Appends one event per detection so fleets/campaigns
        can account recoveries and measure recovery latency."""
        if self.scrub_decode_state():
            return
        event = {"step": self.stats.steps, "recovered": False,
                 "seconds": 0.0, "steps_replayed": 0}
        if self.tracer is not None:
            self.tracer.instant("scrub_detection", site="decode_state")
        if self.event_log is not None:
            self.event_log.emit("detection", tick=self.tick,
                                site="decode_state",
                                detail={"check": "state_scrub"})
        if self.state_scrub == "rollback" and self._snapshot is not None:
            t0 = time.perf_counter()
            try:
                with span("engine/rollback", site="decode_state"):
                    event["steps_replayed"] = self.restore_snapshot()
                event["recovered"] = True
                event["seconds"] = time.perf_counter() - t0
                self.record_dependability({"faults_recovered": jnp.int32(1)})
                if self.tracer is not None:
                    self.tracer.instant(
                        "rollback", steps_replayed=event["steps_replayed"])
                if self.event_log is not None:
                    self.event_log.emit(
                        "rollback", tick=self.tick, site="decode_state",
                        seconds=event["seconds"],
                        detail={"steps_replayed": event["steps_replayed"]})
            except RuntimeError:
                # snapshot itself failed verification — leave recovered
                # False; the supervisor's drain+replay is the fallback
                pass
        if not event["recovered"]:
            # accept the corrupted fingerprint as the new baseline so one
            # strike raises one alarm, not one per remaining step
            self._refresh_state_check()
        self.state_events.append(event)

    def refresh_storage_baseline(self):
        """Bless the *current* parameters as the golden storage state:
        recompute the deploy-time checksums and retain the params as the
        rollback target.  Called at construction and by intentional weight
        swaps (rolling deploys); never implicitly by ``reset``."""
        self._golden_params = self.params
        self._storage_checks = _storage_checksums(self.params)
        self._storage_alarmed = False

    def scrub_storage(self) -> bool:
        """Verify live parameters against the golden storage checksums;
        True == clean.  Counts one check (and the detection, if any) into
        the dependability rollup."""
        if self._storage_checks is None:
            return True
        ok = storage_verify(self.params, self._storage_checks)
        clean = all(bool(x) for x in jax.tree_util.tree_leaves(ok))
        self.record_dependability({
            "faults_detected": jnp.int32(0 if clean else 1),
            "checks_run": jnp.int32(1)}, emit_events=False)
        return clean

    def _storage_scrub_and_recover(self):
        """The in-serve storage scrub: detect a weight-memory SEU against
        the golden checksums; under ``rollback`` restore the golden
        parameters in place (retroactively heals every read since the
        strike would have been re-issued from clean storage — decode state
        repairs ride the decode-state scrub/snapshot machinery)."""
        if self._storage_alarmed or self.scrub_storage():
            return
        event = {"step": self.stats.steps, "site": "weights",
                 "recovered": False, "seconds": 0.0, "steps_replayed": 0}
        if self.tracer is not None:
            self.tracer.instant("scrub_detection", site="weights")
        if self.event_log is not None:
            self.event_log.emit("detection", tick=self.tick, site="weights",
                                detail={"check": "storage_scrub"})
        if self.storage_scrub == "rollback":
            t0 = time.perf_counter()
            with span("engine/rollback", site="weights"):
                self.params = self._golden_params
            event["recovered"] = True
            event["seconds"] = time.perf_counter() - t0
            self.record_dependability({"faults_recovered": jnp.int32(1)})
            if self.tracer is not None:
                self.tracer.instant("rollback", site="weights")
            if self.event_log is not None:
                self.event_log.emit(
                    "rollback", tick=self.tick, site="weights",
                    seconds=event["seconds"],
                    detail={"action": "golden_restore"})
        else:
            # detect-only: one strike raises one alarm — the baseline stays
            # golden (storage semantics), so latch instead of re-blessing;
            # reset()/refresh_storage_baseline() clear the latch
            self._storage_alarmed = True
        self.state_events.append(event)

    def drain_state_events(self) -> List[dict]:
        ev, self.state_events = self.state_events, []
        return ev

    def record_dependability(self, stats: dict, emit_events: bool = True):
        """Fold a DependabilityStats pytree (from dependable ops or a
        campaign's detection verdicts) into the executor-lifetime counters.
        With an event log attached, positive detection counts from
        core/dependability checks also surface as ``detection`` events
        (``emit_events=False`` for callers that emit their own)."""
        self.dependability = DependabilityStats.merge(self.dependability, stats)
        if emit_events and self.event_log is not None \
                and isinstance(stats, dict):
            detected = int(stats.get("faults_detected", 0))
            if detected > 0:
                self.event_log.emit(
                    "detection", tick=self.tick,
                    detail={"check": "dependability", "count": detected})

    # ------------------------------------------------- per-stage injection
    def strike(self, site: str, fault, key) -> None:
        """Campaign hook: inject an SEU into the state the named stage owns.

        ``kv_cache`` / ``decode_state`` strike the decode stage's cache and
        token buffer; ``weights`` strikes the parameter store every stage
        reads.  Routing faults by stage (instead of reaching into a
        monolith) is what lets a campaign attribute coverage per stage.
        """
        from repro.core.fault_injection import inject_pytree_with
        if site == "kv_cache":
            self.decode.cache = inject_pytree_with(self.decode.cache, key,
                                                   fault)
        elif site == "decode_state":
            self.decode.tokens = fault(self.decode.tokens, key)
        elif site == "weights":
            self.params = inject_pytree_with(self.params, key, fault)
        else:
            raise ValueError(
                f"no stage owns fault site {site!r} "
                f"(known: kv_cache, decode_state, weights)")
        fault_name = getattr(fault, "name", getattr(fault, "__name__", ""))
        if self.tracer is not None:
            self.tracer.instant("strike", site=site, fault=fault_name)
        if self.event_log is not None:
            self.event_log.emit("strike", tick=self.tick, site=site,
                                fault=fault_name)

    # ------------------------------------------------------------- driving
    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        req.clear_stamps("t_submit")
        req.submitted_tick = self.tick
        self.submit_ch.items.append(req)
        if self.tracer is not None:
            self.tracer.open_span(req.uid, "admit",
                                  prompt_len=len(req.prompt),
                                  max_new_tokens=req.max_new_tokens)
        if self.metrics is not None:
            self._m_submitted.inc()

    def cancel(self, uid: int) -> bool:
        """Evict a request from any stage it occupies (deadline/abort path).
        Slot cache rows go stale but are overwritten by the next join's
        prefill.  Also purged from snapshot bookkeeping so a later
        ``restore_snapshot`` cannot resurrect cancelled work.  Returns True
        if the request was found live in the pipeline."""
        if self.tracer is not None:
            for stage in ("admit", "prefill", "decode", "certify"):
                self.tracer.cancel_span(uid, stage)
        self._since_snapshot = [r for r in self._since_snapshot
                                if r.uid != uid]
        if self._snapshot is not None:
            for slot, r in list(self._snapshot["active"].items()):
                if r.uid == uid:
                    del self._snapshot["active"][slot]
                    del self._snapshot["outputs"][slot]
        for ch in (self.submit_ch, self._admit_ch):
            for i, r in enumerate(ch.items):
                if r.uid == uid:
                    del ch.items[i]
                    return True
        for i, item in enumerate(self._prefill_ch.items):
            if item.req.uid == uid:
                del self._prefill_ch.items[i]
                return True
        for slot, r in list(self.decode.active.items()):
            if r.uid == uid:
                del self.decode.active[slot]
                self.decode.slot_remaining[slot] = 0
                return True
        for held in (self.decode._pending, self.certifier._pending):
            for r in list(held):
                if r.uid == uid:
                    held.remove(r)
                    return True
        for ch in (self._certify_ch, self._release_ch):
            for i, r in enumerate(ch.items):
                if r.uid == uid:
                    del ch.items[i]
                    return True
        return False

    def step(self) -> List[Request]:
        """One cooperative pump cycle: admit → prefill → decode-join →
        snapshot cadence → decode step → certify → release.  Returns the
        requests that cleared the release stage this cycle (certify-hook
        holds excluded)."""
        self.tick += 1
        if self.tracer is not None:
            self.tracer.tick_to(self.tick)
        # scrub BEFORE this cycle consumes decode state (and before a join
        # mutates it): anything that changed since the last legitimate
        # mutation is an SEU, and under "rollback" we restart from the
        # last verified snapshot instead of decoding from corrupted state
        if self.state_scrub != "off" and self.decode.active:
            with span("engine/state_scrub"):
                self._scrub_and_recover()
        # storage scrub on its own cadence, before any stage reads weights
        # this cycle: detect mode runs every pump (bounded detection
        # latency), rollback mode amortizes over storage_scrub_every ticks
        if self.storage_scrub != "off" \
                and self.tick % self.storage_scrub_every == 0:
            with span("engine/storage_scrub"):
                self._storage_scrub_and_recover()
        with span("engine/admit"):
            self.admit.pump()
        self.prefill.pump()
        with span("engine/join"):
            self.decode.join()
        if self.decode.active:
            # cadence by steps-since-snapshot (≡ steps % snapshot_every for
            # per-step decode; windowed decode advances steps by up to N per
            # pump, which a bare modulo check would skip over)
            if (self._snapshot is None
                    or self.stats.steps - self._snapshot_step
                    >= self.snapshot_every):
                with span("engine/snapshot"):
                    self._take_snapshot()
            self.decode.decode_any()
        self._refresh_state_check()
        # certify/release pump AFTER the decode state is settled: a certify
        # hook may re-enter the executor (fleet recalls, resets, replays)
        with span("engine/certify"):
            self.certifier.pump()
        with span("engine/release"):
            released = self._release()
        return released

    def _release(self) -> List[Request]:
        """Collect what cleared the release stage: stamp it, log its stage
        stamps (``obs.trace.RELEASED``), and feed the tracer and metrics."""
        self.release.pump()
        released = self.release.collect()
        now = time.perf_counter()
        for req in released:
            req.t_release = now
            obs_trace.RELEASED.append(
                (req.uid, len(req.prompt), len(req.output or ()),
                 *req.stamps()))
        if self.tracer is not None:
            for req in released:
                self.tracer.instant("release", stage="release", uid=req.uid,
                                    tokens=len(req.output or ()))
            self.tracer.counter(
                "queue_depth", submit=len(self.submit_ch),
                admitted=len(self._admit_ch),
                prefilled=len(self._prefill_ch),
                parked=len(self.decode._pending)
                + len(self.certifier._pending))
            self.tracer.counter("slots", active=len(self.decode.active),
                                capacity=self.capacity)
        if self.metrics is not None:
            self._m_released.inc(len(released))
            self._m_steps.inc(self.stats.steps - self._mm_steps)
            self._m_tokens.inc(self.stats.tokens_out - self._mm_tokens)
            self._mm_steps = self.stats.steps
            self._mm_tokens = self.stats.tokens_out
            self._m_qdepth.set(len(self.submit_ch) + len(self._admit_ch)
                               + len(self._prefill_ch))
            self._m_slots.set(len(self.decode.active))
            for req in released:
                if req.submitted_tick >= 0:
                    self._m_latency.observe(self.tick - req.submitted_tick)
        return released

    def busy(self) -> bool:
        """Work anywhere in the pipeline before the release stage?
        Includes requests parked behind a full downstream channel — they
        still need pump cycles to flush."""
        return bool(self.submit_ch.items or self._admit_ch.items
                    or self._prefill_ch.items or self.decode.active
                    or self.decode._pending or self.certifier._pending)

    def in_flight(self) -> List[Request]:
        """Every request the pipeline currently owns, in deterministic
        stage-then-slot order (failover drains replay in this order).
        Requests held behind a full channel come after the decode slots —
        they are finished, downstream of decode, not yet released."""
        return (list(self.submit_ch) + list(self._admit_ch)
                + [item.req for item in self._prefill_ch]
                + [self.decode.active[s] for s in sorted(self.decode.active)]
                + list(self.decode._pending) + list(self.certifier._pending))

    def pending_count(self) -> int:
        """How many requests the pipeline owns — O(1) (router cost metric;
        ``in_flight()`` materializes the list, this just counts it)."""
        return (len(self.submit_ch) + len(self._admit_ch)
                + len(self._prefill_ch) + len(self.decode.active)
                + len(self.decode._pending) + len(self.certifier._pending))

    def run(self, max_steps: int = 10_000) -> EngineStats:
        """Drain the pipeline."""
        while self.busy() and self.stats.steps < max_steps:
            self.step()
        return self.stats

    # ----------------------------------------------------- fault tolerance
    def _take_snapshot(self):
        d = self.decode
        self._snapshot = {
            "cache": d.cache,
            "tokens": d.tokens,
            "slot_pos": d.slot_pos.copy(),
            "slot_remaining": d.slot_remaining.copy(),
            "active": dict(d.active),
            "outputs": {s: list(r.output) for s, r in d.active.items()},
            "steps": self.stats.steps,
            "tokens_out": self.stats.tokens_out,
            # golden-snapshot integrity: checksummed at capture so a later
            # restore can refuse a snapshot that was itself struck
            "check": (state_checksums(
                {"cache": d.cache, "tokens": d.tokens})
                if self.state_scrub != "off" else None),
        }
        self._snapshot_step = self.stats.steps
        self._since_snapshot = []

    def restore_snapshot(self) -> int:
        """Roll back to the last snapshot (device-fault recovery path).

        The snapshot round-trips the *whole* decode state: cache, token
        buffer, per-slot bookkeeping, active-set membership, request outputs
        and the step/token counters — so ``tokens_per_step()`` and token
        accounting stay exact across a replay, and requests that finished or
        were admitted after the snapshot are correctly re-decoded / requeued.
        ``replays`` and ``faults_detected`` are lifetime counters and are
        never rolled back.

        Returns the number of steps replayed (lost work bound =
        snapshot_every).
        """
        if self._snapshot is None:
            raise RuntimeError("no snapshot taken yet")
        snap = self._snapshot
        if snap["check"] is not None:
            fresh = state_checksums(
                {"cache": snap["cache"], "tokens": snap["tokens"]})
            if not _checks_equal(fresh, snap["check"]):
                raise RuntimeError(
                    "snapshot failed checksum verification (SEU struck the "
                    "golden snapshot itself) — refusing to restore; escalate "
                    "to drain + failover")
        d = self.decode
        d.cache = snap["cache"]
        d.tokens = snap["tokens"]
        d.slot_pos = snap["slot_pos"].copy()
        d.slot_remaining = snap["slot_remaining"].copy()
        # active set as of the snapshot: resurrects requests that finished
        # after it (their post-snapshot tokens are suspect) and drops ones
        # admitted after it (requeued below; the cache rollback erased their
        # prefill rows)
        d.active = dict(snap["active"])
        # a request that finished after the snapshot may still be parked
        # behind a full channel; its resurrected copy re-decodes, so the
        # parked (suspect) copy must not also flush downstream
        resurrected = {r.uid for r in d.active.values()}
        d._pending = deque(r for r in d._pending
                           if r.uid not in resurrected)
        tr = self.tracer
        for s, req in d.active.items():
            req.output = list(snap["outputs"][s])
            req.clear_stamps("t_join")
            req.finished_tick = -1
            if tr is not None:
                # resurrected: back in decode; a suspect copy may have
                # already closed its decode span and opened certify —
                # re-open decode (restart) and drop the stale certify span
                tr.cancel_span(req.uid, "certify")
                tr.open_span(req.uid, "decode", slot=s, replayed=True)
        for req in reversed(self._since_snapshot):
            req.output = None
            req.clear_stamps("t_submit")
            req.finished_tick = -1
            self.submit_ch.items.appendleft(req)
            if tr is not None:
                # requeued from scratch: whatever stage it reached is void
                for stage in ("prefill", "decode", "certify"):
                    tr.cancel_span(req.uid, stage)
                tr.open_span(req.uid, "admit", requeued=True)
        self._since_snapshot = []
        lost = self.stats.steps - snap["steps"]
        self.stats.steps = snap["steps"]
        self.stats.tokens_out = snap["tokens_out"]
        self.stats.replays += 1
        self._refresh_state_check()
        return lost
