"""Batched serving engine — a thin facade over the streaming dataflow
executor (``runtime/dataflow.py``).

Historically this module held a 450-line monolithic ``Engine.step()`` that
admitted, prefilled, decoded, scrubbed and released in one blocking pass.
The paper's runtime is the opposite shape — a dataflow-oriented, lock-free
streaming pipeline (Klepsydra on the HPDP) — and the implementation now
matches: admit → prefill → decode → certify → release are explicit stages
connected by bounded SPSC channels, with continuous batching in the decode
stage and certification as the release gate.  See ``dataflow.py`` for the
pipeline itself and docs/streaming.md for the semantics.

``Engine`` keeps the public surface every caller already speaks —
``submit``/``step``/``run``/``snapshot``/``restore_snapshot``, the
``DependabilityStats`` rollup and the drained ``state_events`` — and adds
the per-stage surfaces the pipeline makes possible:

  * ``certify=`` installs a release-gate hook (the fleet's
    certify-before-release runs *in the certify stage*, not in fleet code
    wrapped around the engine);
  * ``strike(site, fault, key)`` injects an SEU into the stage that owns
    the site (decode owns ``kv_cache``/``decode_state``, the parameter
    store owns ``weights``) — the campaign engine's per-stage drill surface.

Single-process implementation (CPU or one TPU slice) with the same
state-machine a multi-host engine needs; the cooperative stage schedule is
deliberately deterministic so replay-after-fault is bit-exact.
"""
from __future__ import annotations

from typing import Callable, List, Optional

from repro.core.dependability import Policy
from repro.models import api as model_api
from repro.models.config import ArchConfig
from repro.runtime.dataflow import (     # noqa: F401 — public re-exports
    Channel, EngineStats, Request, StreamingExecutor)


class Engine:
    """Fixed-capacity continuous-batching engine over the staged executor.

    capacity: decode batch width (slots).  Each slot is free or holds one
    request.  Prefill runs per-request (right-padded to ``prefill_pad``
    buckets to bound compile count); decode steps the whole batch while
    requests join and leave mid-flight.
    """

    def __init__(self, cfg: ArchConfig, params, capacity: int = 8,
                 max_len: int = 512, prefill_pad: int = 64,
                 snapshot_every: int = 32, eos_id: int = -1,
                 compiled=None, backend: Optional[str] = None,
                 policy_map=None, state_scrub: str = "off",
                 storage_scrub: Optional[str] = None,
                 storage_scrub_every: Optional[int] = None,
                 certify: Optional[Callable[[Request], bool]] = None,
                 drain_barrier: bool = False, multi_step: int = 1,
                 tracer=None, event_log=None, metrics=None, device=None):
        # engine-level execution-backend override for the quantized hot
        # paths (core/backend registry); baked into cfg so the jitted
        # decode/prefill pair and any compiled-pair sharing stay consistent
        cfg = model_api.with_backend(cfg, backend)
        # policy_map= is the engine's selective-hardening surface
        # (core/policy_map.py; PolicyMap | JSON doc/text/path).  The map is
        # baked into cfg — the jitted decode/prefill pair executes the
        # mapped ``ffn.*`` policies in-graph — and the engine derives its
        # scrub schedule from the state sites unless the caller pinned one:
        #   kv_cache/decode_state policies -> state_scrub (PolicyMap.
        #       scrub_mode: CKPT⇒rollback, ABFT⇒detect)
        #   weights policy -> storage_scrub: ABFT⇒detect at every-pump
        #       cadence (detection latency is the product), CKPT⇒rollback
        #       amortized over snapshot_every ticks (golden restore heals
        #       retroactively)
        cfg = model_api.with_policy_map(cfg, policy_map)
        if policy_map is not None:
            pm = cfg.policy_map
            if state_scrub == "off":
                state_scrub = pm.scrub_mode()
            if storage_scrub is None:
                storage_scrub = {Policy.ABFT: "detect",
                                 Policy.CKPT: "rollback"}.get(
                    pm.storage_policy(), "off")
        if storage_scrub is None:
            storage_scrub = "off"
        if storage_scrub_every is None:
            storage_scrub_every = 1 if storage_scrub == "detect" \
                else snapshot_every
        self._ex = StreamingExecutor(
            cfg, params, capacity=capacity, max_len=max_len,
            prefill_pad=prefill_pad, snapshot_every=snapshot_every,
            eos_id=eos_id, compiled=compiled, state_scrub=state_scrub,
            storage_scrub=storage_scrub,
            storage_scrub_every=storage_scrub_every,
            certify=certify, drain_barrier=drain_barrier,
            multi_step=multi_step, tracer=tracer, event_log=event_log,
            metrics=metrics, device=device)

    # ------------------------------------------------------------- pipeline
    @property
    def executor(self) -> StreamingExecutor:
        """The staged pipeline this engine fronts (stages, channels,
        per-stage injection)."""
        return self._ex

    @property
    def cfg(self):
        return self._ex.cfg

    @property
    def compiled(self):
        """The jitted (decode, prefill) pair, shareable with same-config
        engines via the ``compiled=`` constructor argument."""
        return self._ex.compiled

    # --------------------------------------------------- state pass-through
    # Mutable run state lives in the stages; these properties keep the
    # monolith-era surface (fleet, campaigns, tests) working unchanged.
    @property
    def params(self):
        return self._ex.params

    @params.setter
    def params(self, value):
        self._ex.params = value

    @property
    def capacity(self):
        return self._ex.capacity

    @property
    def max_len(self):
        return self._ex.max_len

    @property
    def prefill_pad(self):
        return self._ex.prefill_pad

    @property
    def snapshot_every(self):
        return self._ex.snapshot_every

    @property
    def eos_id(self):
        return self._ex.eos_id

    @property
    def multi_step(self):
        """Decode steps per jitted dispatch window (1 = per-step)."""
        return self._ex.multi_step

    @property
    def queue(self):
        """The submission channel's deque (admit-stage inbox)."""
        return self._ex.submit_ch.items

    @property
    def active(self):
        """slot -> Request mapping of the decode stage's live batch."""
        return self._ex.decode.active

    @property
    def slot_pos(self):
        return self._ex.decode.slot_pos

    @property
    def slot_remaining(self):
        return self._ex.decode.slot_remaining

    @property
    def cache(self):
        return self._ex.decode.cache

    @cache.setter
    def cache(self, value):
        self._ex.decode.cache = value

    @property
    def tokens(self):
        return self._ex.decode.tokens

    @tokens.setter
    def tokens(self, value):
        self._ex.decode.tokens = value

    @property
    def stats(self) -> EngineStats:
        return self._ex.stats

    @property
    def certify(self):
        return self._ex.certify

    @certify.setter
    def certify(self, hook):
        self._ex.certify = hook

    @property
    def state_scrub(self) -> str:
        return self._ex.state_scrub

    @state_scrub.setter
    def state_scrub(self, mode: str):
        if mode not in ("off", "detect", "rollback"):
            raise ValueError(f"state_scrub must be off|detect|rollback, "
                             f"got {mode!r}")
        self._ex.state_scrub = mode

    @property
    def policy_map(self):
        """The per-site dependability assignment baked into the config
        (None for the legacy single-policy engine)."""
        return self._ex.cfg.policy_map

    @property
    def storage_scrub(self) -> str:
        return self._ex.storage_scrub

    @property
    def storage_scrub_every(self) -> int:
        return self._ex.storage_scrub_every

    @property
    def state_events(self):
        return self._ex.state_events

    # ------------------------------------------------------- observability
    @property
    def tick(self) -> int:
        """The executor's deterministic pump-cycle clock."""
        return self._ex.tick

    @property
    def tracer(self):
        return self._ex.tracer

    @tracer.setter
    def tracer(self, value):
        self._ex.tracer = value

    @property
    def event_log(self):
        return self._ex.event_log

    @event_log.setter
    def event_log(self, value):
        self._ex.event_log = value

    @property
    def metrics(self):
        return self._ex.metrics

    @property
    def dependability(self):
        return self._ex.dependability

    @property
    def _snapshot(self):
        return self._ex._snapshot

    @_snapshot.setter
    def _snapshot(self, value):
        self._ex._snapshot = value

    # ------------------------------------------------------------ lifecycle
    def reset(self, params=None):
        """Return the engine's run state (channels, slots, cache, per-run
        stats) to fresh, optionally with new (same-shaped) params.  Lifetime
        dependability counters survive resets; compiled fns are kept."""
        self._ex.reset(params=params)

    def submit(self, req: Request):
        self._ex.submit(req)

    def cancel(self, uid: int) -> bool:
        """Evict a request from whichever stage holds it (deadline/abort
        path); True if it was found live anywhere in the pipeline."""
        return self._ex.cancel(uid)

    def step(self) -> List[Request]:
        """One cooperative pump of every stage; returns requests that
        cleared the release stage this cycle."""
        return self._ex.step()

    def run(self, max_steps: int = 10_000) -> EngineStats:
        """Drain the pipeline."""
        return self._ex.run(max_steps=max_steps)

    # ------------------------------------------------------- dependability
    def scrub_decode_state(self) -> bool:
        return self._ex.scrub_decode_state()

    def scrub_storage(self) -> bool:
        """Verify live params against the golden storage checksums
        (True == clean); no-op True when storage scrubbing is off."""
        return self._ex.scrub_storage()

    def refresh_storage_baseline(self):
        """Re-bless the current params as golden (rolling-deploy hook)."""
        self._ex.refresh_storage_baseline()

    def drain_state_events(self) -> List[dict]:
        return self._ex.drain_state_events()

    def record_dependability(self, stats: dict):
        self._ex.record_dependability(stats)

    def strike(self, site: str, fault, key) -> None:
        """Per-stage SEU injection (campaign drill surface)."""
        self._ex.strike(site, fault, key)

    def dependability_report(self) -> dict:
        """Host-side dependability summary: detection counters + the
        replay/snapshot state a campaign needs to judge recovery cost."""
        from repro.core.dependability import DependabilityStats
        ex = self._ex
        out = DependabilityStats.to_host(ex.dependability)
        out.update(steps=ex.stats.steps, replays=ex.stats.replays,
                   tokens_out=ex.stats.tokens_out,
                   snapshot_every=ex.snapshot_every,
                   state_scrub=ex.state_scrub,
                   storage_scrub=ex.storage_scrub,
                   state_events_pending=len(ex.state_events))
        return out

    # ----------------------------------------------------- fault tolerance
    def restore_snapshot(self) -> int:
        """Roll back to the last (checksum-verified) snapshot; returns the
        number of steps replayed."""
        return self._ex.restore_snapshot()
