"""Streaming dataflow executor: queue/stage primitives, continuous-batching
bit-identity with mid-decode joins across model families, per-stage fault
injection, the certify release gate, and the pad-and-step drain barrier."""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import fault_injection as fi
from repro.models import api as model_api
from repro.models.config import reduced
from repro.runtime import dataflow as df
from repro.runtime.serving import Engine, Request

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Channel / stage primitives
# ---------------------------------------------------------------------------


def test_channel_fifo_and_capacity():
    ch = df.Channel(2, "t")
    assert ch.try_put(1) and ch.try_put(2)
    assert ch.full() and not ch.try_put(3)
    assert ch.try_get() == 1
    assert ch.try_put(3)
    assert [ch.try_get(), ch.try_get()] == [2, 3]
    assert df.Channel.is_empty_token(ch.try_get())


def test_channel_unbounded_and_drain():
    ch = df.Channel(0)
    for i in range(100):
        assert ch.try_put(i)
    assert not ch.full()
    assert len(ch) == 100
    assert ch.drain() == list(range(100))
    assert len(ch) == 0


def test_channel_blocking_put_unblocks_on_get():
    ch = df.Channel(1)
    ch.put("a")
    got = []

    def producer():
        ch.put("b")               # blocks until the consumer makes room
        got.append("sent")

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    time.sleep(0.05)
    assert not got                # still blocked at capacity
    assert ch.get() == "a"
    t.join(timeout=2.0)
    assert got == ["sent"] and ch.get() == "b"


def test_channel_close_raises_closed():
    ch = df.Channel(1)
    ch.close()
    with pytest.raises(df.Closed):
        ch.put(1)
    with pytest.raises(df.Closed):
        ch.get()


def test_source_stage_cooperative_pump_is_ordered():
    out = df.Channel(3)
    stage = df.SourceStage(lambda i: i * 10, out, start=4)
    assert stage.pump()           # fills to capacity, then parks the next
    assert list(out) == [40, 50, 60]
    assert out.try_get() == 40
    stage.pump()
    assert list(out) == [50, 60, 70]


def test_threaded_source_streams_deterministically():
    out = df.Channel(2)
    driver = df.ThreadedSource(df.SourceStage(lambda i: i, out)).start()
    assert [out.get() for _ in range(20)] == list(range(20))
    driver.close()


# ---------------------------------------------------------------------------
# Continuous batching: bit-identity with requests joining mid-decode
# ---------------------------------------------------------------------------


def greedy_reference(cfg, params, prompt, n_new, max_len=96):
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache = model_api.prefill(cfg, params, toks, max_len)
    out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    tok = jnp.asarray([out[-1]], jnp.int32)
    for _ in range(n_new - 1):
        logits, cache = model_api.decode_step(cfg, params, tok, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


FAMILY_ARCHS = ["smollm-135m", "rwkv6-1.6b", "recurrentgemma-2b"]


@pytest.fixture(scope="module", params=FAMILY_ARCHS)
def family(request):
    cfg = reduced(registry.get(request.param))
    params = model_api.init_params(cfg, jax.random.key(0))
    return cfg, params


def test_mid_decode_joins_are_bit_identical(family):
    """Requests that join the slotted batch while neighbors are mid-decode
    must produce exactly the tokens a solo greedy decode produces — the
    continuous-batching invariant, across transformer/rwkv/hybrid."""
    cfg, params = family
    early = [[5, 9, 2], [3, 1, 4, 1]]
    late = [[2, 7, 1], [8, 8]]
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=8)
            for i, p in enumerate(early)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()                       # both early requests mid-decode
    late_reqs = [Request(uid=10 + i, prompt=list(p), max_new_tokens=8)
                 for i, p in enumerate(late)]
    for r in late_reqs:
        eng.submit(r)                    # join as early slots free up
    eng.run()
    for r, p in zip(reqs + late_reqs, early + late):
        assert r.output == greedy_reference(cfg, params, p, 8), f"uid {r.uid}"


def test_drain_barrier_changes_schedule_not_tokens(family):
    """The pad-and-step baseline mode (drain_barrier) must decode more steps
    on a mixed-length trace (idle slots) yet emit the identical streams —
    scheduling policy can never change tokens."""
    cfg, params = family
    prompts = [[5, 9, 2], [3, 1, 4, 1], [2, 7], [8, 8, 6]]
    budgets = [2, 8, 2, 8]

    def serve(drain):
        eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                     drain_barrier=drain)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [list(r.output) for r in reqs], eng.stats.steps

    streamed, s_steps = serve(False)
    padded, p_steps = serve(True)
    assert streamed == padded
    assert p_steps > s_steps             # the barrier wastes slot-steps


# ---------------------------------------------------------------------------
# Pipeline structure, per-stage injection, certify gate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced(registry.get("smollm-135m"))
    params = model_api.init_params(cfg, jax.random.key(0))
    return cfg, params


def test_stage_topology_and_in_flight_order(smollm):
    cfg, params = smollm
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    ex = eng.executor
    assert [s.name for s in ex.stages] == [
        "admit", "prefill", "decode", "certify", "release"]
    reqs = [Request(uid=i, prompt=[1 + i, 2], max_new_tokens=4)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    assert [r.uid for r in ex.in_flight()] == [0, 1, 2, 3]
    eng.step()
    # two in decode slots, two still queued — stage-then-slot order
    assert [r.uid for r in ex.in_flight()] == [2, 3, 0, 1]
    eng.run()


def test_strike_decode_state_is_caught_by_scrub(smollm):
    """Per-stage injection drills the decode stage's token buffer; the
    pre-decode scrub guard must catch it before the next step consumes it."""
    cfg, params = smollm
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 snapshot_every=2, state_scrub="rollback")
    eng.submit(Request(uid=0, prompt=[5, 9, 2], max_new_tokens=6))
    eng.step()
    eng.step()
    eng.strike("decode_state", fi.flip_one_bit, jax.random.key(3))
    eng.run()
    events = eng.drain_state_events()
    assert len(events) == 1 and events[0]["recovered"]


def test_strike_kv_cache_and_weights_route_to_owners(smollm):
    cfg, params = smollm
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    before_params = jax.tree_util.tree_leaves(eng.params)
    eng.submit(Request(uid=0, prompt=[5, 9, 2], max_new_tokens=4))
    eng.step()
    # host copies: the next decode consumes (donates) the live cache
    before_cache = [np.asarray(x) for x in jax.tree_util.tree_leaves(eng.cache)]
    eng.strike("kv_cache", fi.flip_one_bit, jax.random.key(1))
    eng.strike("weights", fi.flip_one_bit, jax.random.key(2))
    after_cache = jax.tree_util.tree_leaves(eng.cache)
    after_params = jax.tree_util.tree_leaves(eng.params)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(before_cache, after_cache))
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(before_params, after_params))
    with pytest.raises(ValueError, match="no stage owns"):
        eng.strike("flux_capacitor", fi.flip_one_bit, jax.random.key(0))


def test_certify_hook_withholds_and_releases(smollm):
    """The certify stage is the release gate: a False-returning hook keeps
    finished requests out of step()'s released stream (the hook's owner has
    custody); a True-returning hook passes them through."""
    cfg, params = smollm
    held = []
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 certify=lambda req: (held.append(req), False)[1])
    reqs = [Request(uid=i, prompt=[1 + i, 5], max_new_tokens=3)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    released = []
    while eng.executor.busy():
        released += eng.step()
    assert released == []
    assert sorted(r.uid for r in held) == [0, 1]
    assert all(r.t_finish > 0 for r in held)   # finished, just not released

    eng.certify = lambda req: True
    eng.reset()
    for r in reqs:
        r.output = None
        r.t_finish = 0.0
        eng.submit(r)
    released = []
    while eng.executor.busy():
        released += eng.step()
    assert sorted(r.uid for r in released) == [0, 1]


def test_fleet_release_gate_lives_in_certify_stage(smollm):
    """A scrub-gated fleet must flow finished requests through the replica
    engines' certify stages: engines release nothing themselves, the
    replica's uncertified list takes custody until the weight scrub."""
    from repro.core.dependability import Policy
    from repro.fleet import Fleet
    cfg, params = smollm
    fleet = Fleet(cfg, params, n_replicas=2, policy=Policy.ABFT,
                  capacity=2, max_len=96, prefill_pad=8, scrub_every=1000)
    try:
        assert all(r.engine.certify is not None for r in fleet.replicas)
        req = Request(uid=0, prompt=[5, 9, 2], max_new_tokens=3)
        assert fleet.submit(req)
        for _ in range(10):
            fleet.tick()
        # finished but withheld: certification (scrub cadence) never came
        assert req.t_finish > 0
        assert req.uid not in fleet.released
        assert any(any(q.uid == req.uid for q in r.uncertified)
                   for r in fleet.replicas)
        fleet.run()                       # final certification settles it
        assert req.uid in fleet.released
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Decode-path request-loss regressions + multi-step dispatch
# ---------------------------------------------------------------------------


def test_finished_requests_survive_full_outbox(smollm):
    """Regression: finished requests used to be handed to ``outbox.try_put``
    unchecked — a full bounded channel silently dropped them.  Rewire the
    decode→certify and certify→release hops to capacity-1 channels and
    finish two requests in the same pump: hold-and-retry must deliver both."""
    cfg, params = smollm
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    ex = eng.executor
    certify_ch = df.Channel(1, "finished")
    release_ch = df.Channel(1, "certified")
    ex._certify_ch, ex._release_ch = certify_ch, release_ch
    ex.decode.outbox = certify_ch
    ex.certifier.inbox, ex.certifier.outbox = certify_ch, release_ch
    ex.release.inbox = release_ch

    prompts = [[5, 9, 2], [3, 1, 4]]
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    released = []
    while eng.executor.busy():
        released += eng.step()
    assert sorted(r.uid for r in released) == [0, 1]      # none dropped
    for r, p in zip(reqs, prompts):
        assert r.output == greedy_reference(cfg, params, p, 4), f"uid {r.uid}"


def test_prefill_eos_finishes_at_admission(smollm):
    """A request whose *first* generated token is EOS must finish at join —
    previously the EOS check only ran in the decode loop, so the request
    burned its whole token budget decoding past its own terminator."""
    cfg, params = smollm
    prompt = [5, 9, 2]
    t0 = greedy_reference(cfg, params, prompt, 1)[0]
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 eos_id=t0)
    req = Request(uid=0, prompt=list(prompt), max_new_tokens=8)
    other = Request(uid=1, prompt=[8, 8, 6], max_new_tokens=3)
    eng.submit(req)
    eng.submit(other)
    released = []
    while eng.executor.busy():
        released += eng.step()
    assert req.output == [t0]                 # terminated at admission
    assert req.t_finish > 0
    assert sorted(r.uid for r in released) == [0, 1]
    assert len(other.output) >= 1             # neighbor unaffected


@pytest.mark.parametrize("multi_step", [1, 4])
def test_decode_truncates_at_max_len(smollm, multi_step):
    """The ``slot_pos >= max_len - 1`` guard: a budget larger than the
    remaining cache rows must truncate the stream exactly at the cache edge,
    not overrun the buffer.  Regression: a budget >= max_len used to slice
    the prompt to *empty* at prefill and crash the engine (killing every
    in-flight request); now the prompt keeps at least one token and
    generation fills the remaining cache rows."""
    cfg, params = smollm
    max_len, prompt = 12, [5, 9, 2]
    eng = Engine(cfg, params, capacity=2, max_len=max_len, prefill_pad=8,
                 multi_step=multi_step)
    req = Request(uid=0, prompt=list(prompt), max_new_tokens=64)
    eng.submit(req)
    eng.run()
    # budget (64) >= max_len reserves all but one cache row for generation:
    # effective prompt is prompt[:1], stream truncates at pos == max_len - 1
    eff = prompt[:1]
    want_len = max_len - len(eff)
    assert len(req.output) == want_len
    assert req.output == greedy_reference(cfg, params, eff, want_len,
                                          max_len=max_len)
    assert req.t_finish > 0


def test_multi_step_windows_are_bit_identical(family):
    """The tentpole invariant: an N-step on-device decode window (one host
    readback per window) must emit exactly the per-step schedule's tokens —
    across the transformer / rwkv / hybrid families."""
    cfg, params = family
    prompts = [[5, 9, 2], [3, 1, 4, 1], [2, 7], [8, 8, 6]]
    budgets = [2, 8, 2, 8]

    def serve(multi_step):
        eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                     multi_step=multi_step)
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [list(r.output) for r in reqs], eng.stats.steps

    per_step, s1 = serve(1)
    windowed, s4 = serve(4)
    assert windowed == per_step
    # windowed decode may burn drain-tail slot-steps, never fewer steps
    assert s4 >= s1


def test_multi_step_snapshot_rollback_still_bit_exact(smollm):
    """Snapshots land on window boundaries under multi-step dispatch; a
    mid-run state strike must roll back and still finish bit-exact."""
    cfg, params = smollm
    prompt, n_new = [5, 9, 2], 16    # budget must outlive two 4-step windows
    golden = greedy_reference(cfg, params, prompt, n_new)
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 multi_step=4, snapshot_every=2, state_scrub="rollback")
    req = Request(uid=0, prompt=list(prompt), max_new_tokens=n_new)
    eng.submit(req)
    eng.step()
    eng.step()
    eng.strike("decode_state", fi.flip_one_bit, jax.random.key(3))
    eng.run()
    events = eng.drain_state_events()
    assert len(events) == 1 and events[0]["recovered"]
    assert req.output == golden


def _aliased_params(compiled_text: str) -> set:
    """Entry parameters the compiled module aliases to an output."""
    m = re.search(r"input_output_alias=\{(.*?) \}, ", compiled_text)
    return {int(i) for i in re.findall(r": \((\d+),", m.group(1))} if m else set()


@pytest.mark.parametrize("quant_kv", [True, False], ids=["int8kv", "float"])
def test_decode_programs_update_the_cache_in_place(quant_kv):
    """The decode window, the decode step and the join splice take the cache
    donated and alias every cache leaf to an output, and the layer scan
    emits the token's rows, (L, B, KV, hd) and (L, B, KV), not the
    layers' (B, T, ...) pages: nothing re-stacks the cache."""
    cfg = dataclasses.replace(reduced(registry.get("smollm-135m")),
                              quant_kv=quant_kv)
    params = model_api.init_params(cfg, jax.random.key(0))
    B, max_len = 2, 96
    cache = model_api.init_cache(cfg, B, max_len)
    L, _, T, KV, hd = cache.k.shape
    vec = jnp.zeros((B,), jnp.int32)

    jaxpr = jax.make_jaxpr(
        lambda p, t, c: model_api.decode_step(cfg, p, t, c))(params, vec,
                                                              cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    ys = [v.aval.shape for v in scans[0].outvars[scans[0].params["num_carry"]:]]
    assert ys == [(L, B, KV, hd)] * 2 + ([(L, B, KV)] * 2 if quant_kv else [])

    n_params = len(jax.tree_util.tree_leaves(params))
    n_cache = len(jax.tree_util.tree_leaves(cache))
    decode = df.decode_step_fn(cfg)
    window = df._decode_window_fn(decode, 2, -1, max_len)
    one = model_api.init_cache(cfg, 1, max_len)
    # (lowered program, index of its first cache parameter)
    lowered = [
        (window.lower(params, vec, cache, vec, vec, jnp.ones((B,), bool)),
         n_params + 1),
        (decode.lower(params, vec, cache), n_params + 1),
        (df.splice_slot.lower(cache, one, vec, jnp.int32(0), jnp.int32(1),
                              jnp.int32(3)), 0),
    ]
    for low, first in lowered:
        hlo = low.compile().as_text()
        assert set(range(first, first + n_cache)) <= _aliased_params(hlo)


def test_donated_windows_roll_back_bit_exact_twice(smollm):
    """A snapshot, two donated decode windows and a join splice, then a
    rollback: the snapshot owns its copy of the cache, so the streams stay
    bit-exact with an undisturbed run, the snapshot still verifies after
    the restore, and a second rollback to it works too."""
    cfg, params = smollm
    prompts, budgets = [[5, 9, 2], [3, 1, 4, 1]], [14, 6]

    def serve(rollbacks):
        eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                     multi_step=2, snapshot_every=100,
                     state_scrub="rollback")
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, budgets))]
        eng.submit(reqs[0])
        eng.step()                     # join, snapshot, window 1
        eng.step()                     # window 2
        eng.submit(reqs[1])
        eng.step()                     # join splice, window 3
        ex = eng.executor
        for _ in range(rollbacks):
            assert eng.restore_snapshot() > 0
            snap = ex._snapshot
            assert df._checks_equal(
                df.state_checksums({"cache": snap["cache"],
                                    "tokens": snap["tokens"]}),
                snap["check"])
            eng.step()
        eng.run()
        assert eng.drain_state_events() == []
        return [list(r.output) for r in reqs]

    golden = serve(0)
    assert golden[0] == greedy_reference(cfg, params, prompts[0], budgets[0])
    assert serve(2) == golden


def test_failover_bit_exact_hybrid_family():
    """Fleet failover replay on the staged executor, hybrid (griffin)
    family: killing a replica mid-decode must not change any released
    token."""
    from repro.core.dependability import Policy
    from repro.fleet import Fleet, ReplicaState
    cfg = reduced(registry.get("recurrentgemma-2b"))
    params = model_api.init_params(cfg, jax.random.key(0))
    prompts = [[5, 9, 2], [3, 1, 4, 1], [2, 7]]
    fleet = Fleet(cfg, params, n_replicas=2, policy=Policy.NONE,
                  capacity=2, max_len=96, prefill_pad=8)
    try:
        def serve(kill):
            fleet.reset()
            reqs = [Request(uid=i, prompt=list(p), max_new_tokens=5)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                assert fleet.submit(r)
            if kill:
                fleet.tick()
                fleet.tick()
                fleet.kill_replica(0)
            fleet.run()
            return [list(r.output) for r in reqs]

        golden = serve(kill=False)
        replayed = serve(kill=True)
        assert fleet.replicas[0].state is ReplicaState.DEAD
        assert fleet.metrics.failovers > 0
        assert replayed == golden
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Channel property tests: randomized interleavings (seeded, deterministic)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_channel_random_interleaving_fifo_no_loss_no_dup(seed):
    """Property: under any seeded schedule of try_put/try_get, a channel
    never loses, duplicates, or reorders an item — accepted puts come out
    exactly once, in order, and rejections happen iff the channel was full
    (/empty) at the call."""
    import random
    rng = random.Random(seed)
    cap = rng.choice([0, 1, 2, 5])
    ch = df.Channel(cap, f"prop{seed}")
    sent, got = [], []
    nxt = 0
    for _ in range(500):
        if rng.random() < 0.5:
            was_full = ch.full()
            accepted = ch.try_put(nxt)
            assert accepted == (not was_full)
            if accepted:
                sent.append(nxt)
                nxt += 1
        else:
            was_empty = len(ch) == 0
            item = ch.try_get()
            if was_empty:
                assert df.Channel.is_empty_token(item)
            else:
                assert not df.Channel.is_empty_token(item)
                got.append(item)
    assert got + ch.drain() == sent


@pytest.mark.parametrize("seed", range(4))
def test_channel_streaming_close_propagates_exactly_once(seed):
    """Property: closing a channel under concurrent blocking put/get wakes
    both sides, each side sees ``Closed`` exactly once, and every item the
    producer successfully put is delivered (close never drops queued
    work)."""
    import random
    rng = random.Random(seed)
    ch = df.Channel(rng.choice([1, 2, 4]), f"close{seed}")
    produced, consumed = [], []
    closed_seen = {"producer": 0, "consumer": 0}

    def producer():
        i = 0
        while True:
            try:
                ch.put(i)
            except df.Closed:
                closed_seen["producer"] += 1
                return
            produced.append(i)
            i += 1

    def consumer():
        while True:
            try:
                consumed.append(ch.get())
            except df.Closed:
                closed_seen["consumer"] += 1
                return

    tp = threading.Thread(target=producer)
    tc = threading.Thread(target=consumer)
    tp.start()
    tc.start()
    time.sleep(0.01 + rng.random() * 0.03)
    ch.close()
    tp.join(timeout=5)
    tc.join(timeout=5)
    assert not tp.is_alive() and not tc.is_alive()
    assert closed_seen == {"producer": 1, "consumer": 1}
    # no loss, no dup, FIFO: the consumer drained everything that was put
    assert consumed == produced


def test_channel_cooperative_spsc_threaded_no_loss():
    """The cooperative API's lock-free claim, exercised for real: one
    producer spinning try_put against a bounded channel, one consumer
    spinning try_get — every item arrives exactly once, in order."""
    ch = df.Channel(4, "spsc")
    n = 2000
    got = []

    def produce():
        i = 0
        while i < n:
            if ch.try_put(i):
                i += 1

    def consume():
        while len(got) < n:
            item = ch.try_get()
            if not df.Channel.is_empty_token(item):
                got.append(item)

    tp = threading.Thread(target=produce)
    tc = threading.Thread(target=consume)
    tp.start()
    tc.start()
    tp.join(timeout=30)
    tc.join(timeout=30)
    assert got == list(range(n))


# ---------------------------------------------------------------------------
# Profiler spans, program names, stage stamps, FFN check counters
# ---------------------------------------------------------------------------

STATE_MAP = {"default": "none", "rules": [
    {"pattern": "kv_cache", "policy": "ckpt"},
    {"pattern": "decode_state", "policy": "ckpt"},
    {"pattern": "weights", "policy": "ckpt"}]}

FFN_MAP = {"default": "none", "rules": [{"pattern": "ffn.*", "policy": "abft"}]}

# one pump's top-level spans, in order (``*`` = once per prefilled request)
PUMP = re.compile(
    r"^(state_scrub )?(storage_scrub )?admit (prefill prefill_readback )*"
    r"join (snapshot )?((decode_window|decode_step) decode_readback "
    r"decode_replay )?(state_refresh )?certify release $")


class SpanRecorder:
    """Stands in for ``obs.span``: records (pump, depth, name) of every
    span the engine opens."""

    def __init__(self):
        self.events, self.depth, self.pump = [], 0, 0

    @contextlib.contextmanager
    def __call__(self, name, **args):
        self.events.append((self.pump, self.depth, name))
        self.depth += 1
        try:
            yield
        finally:
            self.depth -= 1

    def pumps(self):
        """Per pump: the names of its top-level spans, prefix dropped."""
        out = collections.defaultdict(str)
        for pump, depth, name in self.events:
            if depth == 0:
                out[pump] += name.split("/", 1)[1] + " "
        return [out[p] for p in sorted(out)]


def _drive(eng, rec, reqs, strike_after=None):
    for r in reqs:
        eng.submit(r)
    while eng.executor.busy():
        rec.pump += 1
        eng.step()
        if rec.pump == strike_after:
            eng.strike("decode_state", fi.flip_one_bit, jax.random.key(3))


@pytest.mark.parametrize("policy_map", [STATE_MAP, None])
def test_engine_spans_in_pump_order(smollm, monkeypatch, policy_map):
    """Every pump opens its boundary spans in stage order, one level deep;
    a rollback nests inside the scrub that ran it.  Scrub and refresh spans
    appear only where the policy map turns the scrubs on; snapshots keep
    their cadence either way."""
    cfg, params = smollm
    rec = SpanRecorder()
    monkeypatch.setattr(df, "span", rec)
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 multi_step=2, snapshot_every=2, policy_map=policy_map)
    reqs = [Request(uid=i, prompt=[3 + i, 7, 1], max_new_tokens=7)
            for i in range(3)]
    _drive(eng, rec, reqs, strike_after=1 if policy_map else None)
    names = {n for _, _, n in rec.events}
    assert all(n.startswith("engine/") for n in names)
    for pump in rec.pumps():
        assert PUMP.match(pump), pump
    nested = [(d, n) for _, d, n in rec.events if d > 0]
    scrubs = {"engine/state_scrub", "engine/state_refresh",
              "engine/storage_scrub", "engine/rollback"}
    assert "engine/snapshot" in names
    assert sum(n == "engine/prefill" for _, _, n in rec.events) == 3
    if policy_map is None:
        assert not names & scrubs and not nested
        return
    assert scrubs <= names
    # the struck state was rolled back from inside the pre-decode scrub,
    # which re-checksums the restored state
    assert nested == [(1, "engine/rollback"), (2, "engine/state_refresh")]
    at = [i for i, e in enumerate(rec.events) if e[2] == "engine/rollback"][0]
    assert rec.events[at - 1][1:] == (0, "engine/state_scrub")
    # the storage scrub runs on its own cadence (every snapshot_every pumps)
    ticks = {p for p, _, n in rec.events if n == "engine/storage_scrub"}
    assert ticks == {p for p in range(1, rec.pump + 1) if p % 2 == 0}


def test_engine_programs_have_stable_module_names(smollm):
    """Each program the engine runs lowers to a module named after it, as a
    profile shows it."""
    cfg, params = smollm
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 multi_step=2, policy_map=STATE_MAP)
    ex = eng.executor
    toks = jnp.zeros((1, 8), jnp.int32)
    one = ex._prefill(params, toks)[1]
    vec = jnp.zeros((2,), jnp.int32)
    window = df._decode_window_fn(ex._decode, 2, -1, 96)
    lowered = {
        "jit_engine_prefill": ex._prefill.lower(params, toks),
        "jit_engine_decode_step": ex._decode.lower(params, vec, eng.cache),
        "jit_engine_decode_window": window.lower(
            params, vec, eng.cache, vec, vec, jnp.ones((2,), bool)),
        "jit_splice_slot": df.splice_slot.lower(
            eng.cache, one, vec, jnp.int32(0), jnp.int32(1), jnp.int32(3)),
        "jit_state_checksums": df.state_checksums.lower(
            ex._device_state()),
        "jit_storage_verify": df.storage_verify.lower(
            params, ex._storage_checks),
        "jit_storage_checksums": df._storage_checksums.lower(params),
        "jit_snapshot_copy": df.snapshot_copy.lower(eng.cache),
    }
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text(), name


def test_stage_stamps_monotone_across_rollback(smollm):
    """submit <= prefill <= join <= finish <= release for every released
    request, with a snapshot rollback mid-run; the release log holds each
    release's stamps."""
    from repro.obs import trace as obs_trace
    cfg, params = smollm
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 snapshot_every=2, state_scrub="rollback")
    reqs = [Request(uid=100 + i, prompt=[2 + i, 5], max_new_tokens=3 + 2 * i)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    released = []
    for pump in range(200):
        if not eng.executor.busy():
            break
        released += eng.step()
        if pump == 2:
            eng.strike("decode_state", fi.flip_one_bit, jax.random.key(5))
    assert eng.drain_state_events()[0]["recovered"]
    assert sorted(r.uid for r in released) == [100, 101, 102, 103]
    for r in released:
        st = r.stamps()
        assert 0 < st[0] and st == sorted(st), (r.uid, st)
    log = {row[0]: row for row in list(obs_trace.RELEASED)[-len(released):]}
    for r in released:
        assert log[r.uid][1:3] == (len(r.prompt), len(r.output))
        assert list(log[r.uid][3:]) == r.stamps()


def test_request_stamps_restart_on_resubmit():
    req = Request(uid=1, prompt=[1], max_new_tokens=2, t_submit=1.0,
                  t_prefill=2.0, t_join=3.0, t_finish=4.0, t_release=5.0)
    req.clear_stamps("t_join")
    assert req.stamps() == [1.0, 2.0, 3.0, 0.0, 0.0]
    req.clear_stamps("t_submit")
    assert req.stamps() == [1.0, 0.0, 0.0, 0.0, 0.0]


@pytest.fixture(scope="module")
def smollm_w8a8():
    cfg = dataclasses.replace(reduced(registry.get("smollm-135m")),
                              quant="w8a8_ffn")
    return cfg, model_api.init_params(cfg, jax.random.key(1))


@pytest.mark.parametrize("multi_step", [1, 3])
def test_ffn_checks_reach_the_engine(smollm_w8a8, monkeypatch, multi_step):
    """A clean run counts 3 FFN checks a layer for every decode step the
    device runs (a window runs all its steps, idle trailing ones included)
    and for each of prefill's two FFN passes, and detects nothing."""
    cfg, params = smollm_w8a8
    rec = SpanRecorder()
    monkeypatch.setattr(df, "span", rec)
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 multi_step=multi_step, policy_map=FFN_MAP)
    reqs = [Request(uid=i, prompt=[4 + i, 9, 2], max_new_tokens=4 + 3 * i)
            for i in range(3)]
    _drive(eng, rec, reqs)
    dispatches = sum(n in ("engine/decode_window", "engine/decode_step")
                     for _, _, n in rec.events)
    prefills = sum(n == "engine/prefill" for _, _, n in rec.events)
    rep = eng.dependability_report()
    assert prefills == 3 and dispatches > 0
    assert rep["checks_run"] == 3 * cfg.n_layers * (
        multi_step * dispatches + 2 * prefills)
    assert rep["faults_detected"] == rep["faults_corrected"] == 0


def test_ffn_accumulator_fault_is_counted_and_corrected(smollm_w8a8,
                                                        monkeypatch):
    """A fault planted in every protected FFN accumulator (through
    ``dependable_matmul_acc``'s ``inject``) is detected and corrected in
    place, and the engine counts both; the tokens match a clean run."""
    from repro.core import dependability as dep
    cfg, params = smollm_w8a8

    def serve():
        eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                     multi_step=2, policy_map=FFN_MAP)
        reqs = [Request(uid=i, prompt=[6 + i, 1], max_new_tokens=5)
                for i in range(2)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.output for r in reqs], eng.dependability_report()

    clean, _ = serve()
    acc_fn = dep.dependable_matmul_acc

    def struck(policy, x_q, w_q, **kw):
        return acc_fn(policy, x_q, w_q,
                      inject=lambda a: a.at[0, 0].add(1 << 12), **kw)

    monkeypatch.setattr(dep, "dependable_matmul_acc", struck)
    outputs, rep = serve()
    assert rep["faults_detected"] > 0
    assert rep["faults_corrected"] == rep["faults_detected"]
    assert rep["faults_detected"] == rep["checks_run"]
    assert outputs == clean
