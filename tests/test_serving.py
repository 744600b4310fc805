"""Serving engine: continuous batching, correctness vs plain decode,
snapshot/rollback fault recovery."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import api as model_api
from repro.models.config import reduced
from repro.runtime.serving import Engine, Request

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def served():
    cfg = reduced(registry.get("smollm-135m"))
    params = model_api.init_params(cfg, jax.random.key(0))
    return cfg, params


def greedy_reference(cfg, params, prompt, n_new, max_len=96):
    """Plain prefill + decode loop (no engine)."""
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache = model_api.prefill(cfg, params, toks, max_len)
    out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    tok = jnp.asarray([out[-1]], jnp.int32)
    for _ in range(n_new - 1):
        logits, cache = model_api.decode_step(cfg, params, tok, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


def test_single_request_matches_reference(served):
    cfg, params = served
    prompt = [5, 9, 2, 7]
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    req = Request(uid=0, prompt=prompt, max_new_tokens=6)
    eng.submit(req)
    eng.run()
    want = greedy_reference(cfg, params, prompt, 6)
    assert req.output == want


def test_batched_requests_match_individual(served):
    """Continuous batching must not change any request's tokens."""
    cfg, params = served
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]]
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, p in zip(reqs, prompts):
        assert r.output == greedy_reference(cfg, params, p, 5), f"req {r.uid}"


def test_more_requests_than_capacity(served):
    cfg, params = served
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8)
    reqs = [Request(uid=i, prompt=[i + 1, i + 2], max_new_tokens=3)
            for i in range(5)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    assert all(len(r.output) == 3 for r in reqs)
    assert stats.tokens_out >= 5 * 2        # decode tokens counted

def test_snapshot_restore_round_trips_stats_and_finished_requests(served):
    """Regression: restore_snapshot must roll back tokens_out (not just
    steps) and resurrect requests that finished after the snapshot, so
    token accounting never inflates across a replay."""
    cfg, params = served
    prompts = [[5, 9, 2, 7], [3, 1]]

    def fresh():
        eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                     snapshot_every=2)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, (8, 3)))]
        for r in reqs:
            eng.submit(r)
        return eng, reqs

    eng, reqs = fresh()
    clean_stats = eng.run()
    golden = [list(r.output) for r in reqs]

    eng, reqs = fresh()
    eng.step()
    eng.step()          # req 1 (max_new=3) finishes here, after the snapshot
    assert reqs[1].t_finish > 0
    eng.tokens = eng.tokens.at[0].set(123)        # SEU in decode state
    eng.restore_snapshot()
    # the finished request was resurrected — its post-snapshot tokens were
    # produced after the corruption window and must be re-decoded
    assert reqs[1].t_finish == 0.0
    eng.run()
    assert [list(r.output) for r in reqs] == golden
    assert eng.stats.steps == clean_stats.steps
    assert eng.stats.tokens_out == clean_stats.tokens_out
    assert eng.stats.tokens_per_step() == clean_stats.tokens_per_step()
    assert eng.stats.replays == 1


def test_restore_requeues_requests_admitted_after_snapshot(served):
    """A request admitted after the snapshot loses its prefill rows in the
    cache rollback; restore must send it back to the queue, not strand it."""
    cfg, params = served
    prompts = [[5, 9, 2], [4, 4, 8, 1]]
    golden = [greedy_reference(cfg, params, p, 3) for p in prompts]

    eng = Engine(cfg, params, capacity=1, max_len=96, prefill_pad=8,
                 snapshot_every=4)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()      # snapshot@0; req0 finishes; req1 admitted at step 2
    assert reqs[0].t_finish > 0 and reqs[1].output is not None
    eng.tokens = eng.tokens.at[0].set(77)
    eng.restore_snapshot()
    assert reqs[1] in eng.queue                   # requeued, prefill redone
    eng.run()
    assert [list(r.output) for r in reqs] == golden
    assert eng.stats.replays == 1


def test_cancelled_request_stays_cancelled_after_restore(served):
    """cancel() must purge snapshot bookkeeping so a rollback cannot
    resurrect (and silently serve) aborted work."""
    cfg, params = served
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 snapshot_every=2)
    a = Request(uid=0, prompt=[5, 9, 2], max_new_tokens=6)
    b = Request(uid=1, prompt=[3, 1, 4], max_new_tokens=6)
    eng.submit(a)
    eng.submit(b)
    eng.step()                      # snapshot@0 captures both as active
    assert eng.cancel(b.uid)
    out_b = list(b.output)
    eng.restore_snapshot()
    eng.run()
    assert b.output == out_b        # never decoded further
    assert all(r.uid != b.uid for r in eng.active.values())
    assert a.output == greedy_reference(cfg, params, a.prompt, 6)


def test_snapshot_rollback_replays_identically(served):
    """Device-fault drill: corrupt decode state, roll back, tokens identical."""
    cfg, params = served
    prompt = [3, 1, 4, 1, 5]
    want = greedy_reference(cfg, params, prompt, 8)

    eng = Engine(cfg, params, capacity=1, max_len=96, prefill_pad=8,
                 snapshot_every=2)
    req = Request(uid=0, prompt=prompt, max_new_tokens=8)
    eng.submit(req)
    for _ in range(4):
        eng.step()
    # SEU strikes the decode token buffer
    eng.tokens = eng.tokens.at[0].set(123)
    lost = eng.restore_snapshot()   # rollback restores tokens AND req.output
    assert lost >= 0
    eng.run()
    assert req.output == want


# ----------------------- decode-state scrubbing -----------------------------


from repro.core import fault_injection as fi


def _serve_with_scrub(cfg, params, mode, strike=None, strike_at=2):
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 snapshot_every=2, state_scrub=mode)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=6)
            for i, p in enumerate([[5, 9, 2], [3, 1, 4, 1]])]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while (eng.queue or eng.active) and steps < 200:
        eng.step()
        steps += 1
        if steps == strike_at and strike is not None:
            strike(eng)
    return [tuple(r.output) for r in reqs], eng


def _hit_tokens(eng):
    eng.tokens = fi.flip_one_bit(eng.tokens, jax.random.key(3))


def _hit_cache(eng):
    eng.cache = fi.inject_pytree_with(eng.cache, jax.random.key(7),
                                      fi.flip_one_bit)


@pytest.mark.parametrize("strike", [_hit_tokens, _hit_cache],
                         ids=["decode_state", "kv_cache"])
def test_state_scrub_rollback_restores_golden_stream(served, strike):
    """A transient SEU in live decode state under ``rollback`` mode: the
    checksum scrub detects it before the next step consumes it, the engine
    rolls back to its verified snapshot, and the final streams are
    bit-identical to a fault-free run."""
    cfg, params = served
    golden, _ = _serve_with_scrub(cfg, params, "off")
    out, eng = _serve_with_scrub(cfg, params, "rollback", strike)
    assert out == golden
    events = eng.drain_state_events()
    assert len(events) == 1 and events[0]["recovered"]
    assert events[0]["seconds"] > 0
    assert int(eng.dependability["faults_detected"]) == 1
    assert int(eng.dependability["faults_recovered"]) == 1
    assert eng.stats.replays == 1


def test_state_scrub_detect_mode_raises_alarm_only(served):
    cfg, params = served
    out, eng = _serve_with_scrub(cfg, params, "detect", _hit_tokens)
    events = eng.drain_state_events()
    assert len(events) == 1 and not events[0]["recovered"]
    assert eng.stats.replays == 0
    assert int(eng.dependability["faults_detected"]) == 1
    assert int(eng.dependability["faults_recovered"]) == 0


def test_state_scrub_clean_run_no_false_positives(served):
    cfg, params = served
    golden, _ = _serve_with_scrub(cfg, params, "off")
    out, eng = _serve_with_scrub(cfg, params, "rollback")
    assert out == golden
    assert eng.drain_state_events() == []
    assert int(eng.dependability["faults_detected"]) == 0
    # the scrub did actually run every step
    assert int(eng.dependability["checks_run"]) > 0


def test_state_scrub_recurrent_family(served):
    """Recurrent caches mutate in place each step (not append-only) — the
    post-mutation re-checksum covers them identically."""
    cfg = reduced(registry.get("rwkv6-1.6b"))
    params = model_api.init_params(cfg, jax.random.key(0))
    golden, _ = _serve_with_scrub(cfg, params, "off")
    out, eng = _serve_with_scrub(cfg, params, "rollback", _hit_cache)
    assert out == golden
    ev = eng.drain_state_events()
    assert len(ev) == 1 and ev[0]["recovered"]


def test_corrupted_snapshot_is_refused(served):
    """If the SEU strikes the golden snapshot itself, restore must refuse
    (checksum mismatch) rather than roll back to corrupted state."""
    cfg, params = served
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 snapshot_every=2, state_scrub="rollback")
    eng.submit(Request(uid=0, prompt=[5, 9, 2], max_new_tokens=6))
    eng.step()
    eng.step()
    assert eng._snapshot is not None
    eng._snapshot["tokens"] = fi.flip_one_bit(eng._snapshot["tokens"],
                                              jax.random.key(1))
    with pytest.raises(RuntimeError, match="snapshot failed checksum"):
        eng.restore_snapshot()


def test_state_scrub_invalid_mode_rejected(served):
    cfg, params = served
    with pytest.raises(ValueError, match="state_scrub"):
        Engine(cfg, params, state_scrub="sometimes")
