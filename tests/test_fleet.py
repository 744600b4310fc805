"""Dependable serving fleet: routing determinism, admission control,
bit-exact failover across model families, weight-SEU recovery
(quarantine → checkpoint reload → re-verify → readmit), DMR pair-serving,
deadlines, metrics export, and the fleet-level campaign certification.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.campaign import CampaignSpec, classify_counts, resolve_fault_model, trial_keys
from repro.configs import registry
from repro.core import fault_injection as fi
from repro.core.dependability import Policy
from repro.fleet import Fleet, ReplicaState, Router
from repro.models import api as model_api
from repro.models.config import reduced
from repro.runtime.serving import Engine, Request

jax.config.update("jax_platform_name", "cpu")

PROMPTS = [[5, 9, 2], [3, 1, 4, 1], [2, 7], [8, 8, 6], [1, 6, 1, 8]]
N_NEW = 5


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


@pytest.fixture(scope="module", params=["smollm-135m", "rwkv6-1.6b"])
def family_fleet(request):
    """One 2-replica fleet per model family (compiled once, reset per test)."""
    cfg = reduced(registry.get(request.param))
    params = model_api.init_params(cfg, jax.random.key(0))
    fleet = Fleet(cfg, params, n_replicas=2, policy=Policy.NONE,
                  capacity=2, max_len=96, prefill_pad=8, scrub_every=3)
    return cfg, params, fleet


@pytest.fixture(scope="module")
def smollm_fleet():
    cfg = reduced(registry.get("smollm-135m"))
    params = model_api.init_params(cfg, jax.random.key(0))
    fleet = Fleet(cfg, params, n_replicas=3, policy=Policy.NONE,
                  capacity=2, max_len=96, prefill_pad=8, scrub_every=3)
    return cfg, params, fleet


def _serve(fleet, prompts, policy, n_new=N_NEW, mid_run=None):
    """Reset + submit + (optional mid-run drill) + drain; returns requests."""
    fleet.reset(policy=policy)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        assert fleet.submit(r)
    if mid_run is not None:
        fleet.tick()
        fleet.tick()
        mid_run(fleet)
    fleet.run()
    return reqs


# ---------------------------------------------------------------------------
# baseline correctness: a fleet serves exactly what one engine would
# ---------------------------------------------------------------------------


def test_fleet_matches_single_engine_reference(family_fleet):
    cfg, params, fleet = family_fleet
    reqs = _serve(fleet, PROMPTS, Policy.NONE)
    for r, p in zip(reqs, PROMPTS):
        assert r.uid in fleet.released
        assert r.output == greedy_reference(cfg, params, p, N_NEW), f"req {r.uid}"
    assert fleet.metrics.released == len(PROMPTS)


# ---------------------------------------------------------------------------
# router: determinism + admission control
# ---------------------------------------------------------------------------


def test_hash_router_is_deterministic_and_stable(smollm_fleet):
    _, _, fleet = smollm_fleet
    fleet.reset()
    router = Router("hash")
    picks = [router.pick(uid, fleet.replicas).rid for uid in range(20)]
    assert picks == [router.pick(uid, fleet.replicas).rid for uid in range(20)]
    assert len(set(picks)) > 1            # spreads over replicas


def test_least_loaded_router_prefers_idle_lowest_rid(smollm_fleet):
    _, _, fleet = smollm_fleet
    fleet.reset()
    router = Router("least_loaded")
    assert router.pick(0, fleet.replicas).rid == 0     # all idle → lowest rid
    fleet.replicas[0].engine.submit(Request(uid=90, prompt=[1], max_new_tokens=2))
    assert router.pick(1, fleet.replicas).rid == 1     # 0 now loaded


def test_admission_control_rejects_when_full(smollm_fleet):
    _, _, fleet = smollm_fleet
    fleet.reset()
    old = fleet.router
    try:
        fleet.router = Router("least_loaded", admit_limit=1)
        assert fleet.submit(Request(uid=0, prompt=[1, 2], max_new_tokens=2))
        assert fleet.submit(Request(uid=1, prompt=[1, 2], max_new_tokens=2))
        assert fleet.submit(Request(uid=2, prompt=[1, 2], max_new_tokens=2))
        # all three replicas now hold one request each — fleet is full
        assert not fleet.submit(Request(uid=3, prompt=[1, 2], max_new_tokens=2))
        assert fleet.metrics.rejected == 1
        fleet.run()
        assert fleet.metrics.released == 3
    finally:
        fleet.router = old


def test_deadline_miss_expires_request(smollm_fleet):
    _, _, fleet = smollm_fleet
    fleet.reset()
    req = Request(uid=0, prompt=[5, 9, 2], max_new_tokens=30)
    assert fleet.submit(req, deadline_ticks=2)
    fleet.run()
    assert fleet.metrics.deadline_misses == 1
    assert req.uid not in fleet.released


# ---------------------------------------------------------------------------
# deterministic failover — same tokens with or without a mid-decode kill,
# across two model families (satellite requirement)
# ---------------------------------------------------------------------------


def test_failover_after_kill_is_bit_exact(family_fleet):
    cfg, params, fleet = family_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.NONE)]

    reqs = _serve(fleet, PROMPTS, Policy.NONE,
                  mid_run=lambda f: f.kill_replica(0))
    assert fleet.replicas[0].state is ReplicaState.DEAD
    assert fleet.metrics.failovers > 0
    assert [list(r.output) for r in reqs] == golden
    assert fleet.metrics.released == len(PROMPTS)


def test_heartbeat_timeout_declares_paused_replica_dead(smollm_fleet):
    _, _, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.NONE)]
    reqs = _serve(fleet, PROMPTS, Policy.NONE,
                  mid_run=lambda f: f.pause_replica(0))
    assert any("heartbeat timeout" in e for e in fleet.supervisor.events)
    assert [list(r.output) for r in reqs] == golden


# ---------------------------------------------------------------------------
# weight-SEU recovery: quarantine → checkpoint reload → re-verify → readmit
# ---------------------------------------------------------------------------


def _corrupt_weights(fleet, key=jax.random.key(11)):
    victim = fleet.replicas[0]
    victim.engine.params = fi.inject_pytree_with(
        victim.engine.params, key, fi.flip_one_bit)


def test_abft_scrub_recovers_weight_seu(smollm_fleet):
    cfg, params, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.ABFT)]
    assert fleet.metrics.detections == 0          # clean pass: no false alarms

    reqs = _serve(fleet, PROMPTS, Policy.ABFT,
                  mid_run=lambda f: _corrupt_weights(f))
    assert fleet.metrics.detections >= 1
    assert fleet.metrics.recoveries == 1
    assert fleet.replicas[0].state is ReplicaState.HEALTHY   # readmitted
    assert fleet.replicas[0].scrub() == []                   # re-verified
    assert [list(r.output) for r in reqs] == golden          # zero SDC
    assert fleet.metrics.released == len(PROMPTS)


def test_dmr_detects_transient_decode_fault(smollm_fleet):
    cfg, params, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.DMR)]
    assert fleet.metrics.detections == 0

    def strike(f):
        v = f.replicas[0]
        v.engine.tokens = v.engine.tokens ^ 1     # flip every active token

    reqs = _serve(fleet, PROMPTS, Policy.DMR, mid_run=strike)
    assert fleet.metrics.detections >= 1
    assert fleet.metrics.recoveries == 0          # transient: weights clean
    assert [list(r.output) for r in reqs] == golden
    assert fleet.metrics.released == len(PROMPTS)


# ---------------------------------------------------------------------------
# CKPT fleet policy: incremental restore + decode-state rollback
# ---------------------------------------------------------------------------


def test_ckpt_weight_seu_incremental_restore(smollm_fleet):
    """CKPT is scrub-gated like ABFT but recovers by restoring only the
    corrupted leaves from the golden checkpoint — measured, incremental."""
    cfg, params, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.CKPT)]
    assert fleet.metrics.detections == 0          # clean pass: no false alarms

    reqs = _serve(fleet, PROMPTS, Policy.CKPT,
                  mid_run=lambda f: _corrupt_weights(f))
    m = fleet.metrics
    assert m.detections >= 1
    assert m.recoveries == 1
    assert m.incremental_restores == 1            # partial restore served it
    assert m.full_reloads == 0
    assert m.leaves_restored >= 1
    assert m.recovery_seconds.count == 1 and m.recovery_seconds.sum > 0
    assert m.to_json()["recovery_mean_seconds"] > 0
    assert fleet.replicas[0].state is ReplicaState.HEALTHY
    assert [list(r.output) for r in reqs] == golden
    assert m.released == len(PROMPTS)


def test_ckpt_decode_state_seu_rolls_back_in_place(smollm_fleet):
    """Transient SEU in the token buffer under CKPT: the engine's own
    snapshot rollback heals it — no failover, stream golden."""
    cfg, params, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.CKPT)]

    def strike(f):
        v = f.replicas[0]
        v.engine.tokens = fi.flip_one_bit(v.engine.tokens, jax.random.key(5))

    reqs = _serve(fleet, PROMPTS, Policy.CKPT, mid_run=strike)
    m = fleet.metrics
    assert m.state_scrub_detections >= 1
    assert m.state_rollbacks >= 1                 # healed in place…
    assert m.recoveries == 0                      # …not via quarantine
    assert [list(r.output) for r in reqs] == golden
    assert m.released == len(PROMPTS)


def test_recovery_survives_crashed_checkpoint_writer(smollm_fleet):
    """Crash-consistency at fleet level: an orphaned step_N.tmp (writer
    killed mid-publish) in the golden checkpoint dir must be invisible —
    quarantine-recovery restores from the durable manifest and the engine
    state it rebuilds is bit-exact (same released stream)."""
    from pathlib import Path
    cfg, params, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.CKPT)]

    orphan = Path(fleet.ckpt_dir) / "step_0000000099.tmp"
    orphan.mkdir()
    (orphan / "chunks.npz").write_bytes(b"torn write")
    try:
        reqs = _serve(fleet, PROMPTS, Policy.CKPT,
                      mid_run=lambda f: _corrupt_weights(f))
        assert fleet.metrics.recoveries == 1
        assert fleet.replicas[0].scrub() == []         # bit-exact params
        assert [list(r.output) for r in reqs] == golden
    finally:
        if orphan.exists():
            import shutil
            shutil.rmtree(orphan)


def test_abft_decode_state_seu_drains_and_replays(smollm_fleet):
    """The same strike under ABFT: detect-only scrub, fleet drains the
    replica and replays on verified replicas — stream still golden."""
    cfg, params, fleet = smollm_fleet
    golden = [list(r.output) for r in _serve(fleet, PROMPTS, Policy.ABFT)]

    def strike(f):
        v = f.replicas[0]
        v.engine.tokens = fi.flip_one_bit(v.engine.tokens, jax.random.key(5))

    reqs = _serve(fleet, PROMPTS, Policy.ABFT, mid_run=strike)
    m = fleet.metrics
    assert m.state_scrub_detections >= 1
    assert m.state_drains >= 1
    assert m.state_rollbacks == 0
    assert [list(r.output) for r in reqs] == golden
    assert m.released == len(PROMPTS)


# ---------------------------------------------------------------------------
# metrics export
# ---------------------------------------------------------------------------


def test_metrics_json_round_trip(smollm_fleet, tmp_path):
    _, _, fleet = smollm_fleet
    _serve(fleet, PROMPTS, Policy.ABFT)
    m = fleet.metrics.to_json()
    for k in ("released", "p50_latency_ticks", "p99_latency_ticks",
              "tokens_per_tick", "recoveries", "failovers",
              "lost_work_bound_tokens", "scrubs"):
        assert k in m, k
    assert m["released"] == len(PROMPTS)
    assert m["p50_latency_ticks"] <= m["p99_latency_ticks"]
    p = fleet.metrics.dump(tmp_path / "fleet.json")
    assert json.loads(p.read_text())["released"] == len(PROMPTS)
    report = fleet.report()
    assert len(report["replicas"]) == 3
    json.dumps(report)                            # fully serializable


# ---------------------------------------------------------------------------
# fleet campaign certification (acceptance criteria)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_case():
    from repro.campaign.runner import build_case
    return build_case("fleet", 0)


def test_fleet_campaign_abft_zero_sdc_none_nonzero_100_trials(fleet_case):
    """≥100 seeded weight-SEU trials: ABFT scrub+failover ⇒ every trial
    detected_corrected and fleet SDC = 0; NONE ⇒ nonzero SDC."""
    case = fleet_case
    fault = resolve_fault_model("single_bitflip")

    spec_a = CampaignSpec("fleet", Policy.ABFT, "weights",
                          "single_bitflip", trials=100, seed=0)
    det, mis = case.run_trials(Policy.ABFT, "weights", fault.apply,
                               trial_keys(spec_a))
    counts = classify_counts(det, mis)
    assert counts["sdc"] == 0
    assert counts["detected_uncorrected"] == 0
    assert counts["detected_corrected"] == 100    # every flip caught + healed

    spec_n = CampaignSpec("fleet", Policy.NONE, "weights",
                          "single_bitflip", trials=100, seed=0)
    det, mis = case.run_trials(Policy.NONE, "weights", fault.apply,
                               trial_keys(spec_n))
    counts = classify_counts(det, mis)
    assert not det.any()
    assert counts["sdc"] > 0                      # undefended fleet corrupts


def test_fleet_campaign_dmr_covers_transient_site(fleet_case):
    case = fleet_case
    fault = resolve_fault_model("single_bitflip")
    spec = CampaignSpec("fleet", Policy.DMR, "decode_state",
                        "single_bitflip", trials=40, seed=1)
    det, mis = case.run_trials(Policy.DMR, "decode_state", fault.apply,
                               trial_keys(spec))
    counts = classify_counts(det, mis)
    assert counts["sdc"] == 0
    assert counts["detected_corrected"] > 0


@pytest.mark.parametrize("policy", [Policy.ABFT, Policy.CKPT])
@pytest.mark.parametrize("site", ["decode_state", "kv_cache"])
def test_fleet_scrub_policies_cover_transient_sites(fleet_case, policy, site):
    """The decode-state scrub closes the old ABFT blind spot: transient
    SEUs in the KV cache / token buffer are detected by checksum and healed
    — CKPT by in-place engine rollback, ABFT by drain + failover — with
    zero SDC on the released stream."""
    case = fleet_case
    fault = resolve_fault_model("single_bitflip")
    spec = CampaignSpec("fleet", policy, site, "single_bitflip",
                        trials=20, seed=3)
    det, mis = case.run_trials(policy, site, fault.apply, trial_keys(spec))
    counts = classify_counts(det, mis)
    assert counts["sdc"] == 0
    assert counts["detected_corrected"] == 20      # detected AND healed
    stats = case.drain_recovery_stats()
    assert stats["faults_recovered"] >= 20
    assert stats["recovery_ms_mean"] > 0.0


def test_fleet_ckpt_weight_seu_recovers_incrementally(fleet_case):
    """CKPT fleet trial: weight SEU → scrub detect → *incremental* restore
    of only the corrupted leaves → released stream golden, recovery timed."""
    case = fleet_case
    fault = resolve_fault_model("single_bitflip")
    spec = CampaignSpec("fleet", Policy.CKPT, "weights",
                        "single_bitflip", trials=20, seed=4)
    det, mis = case.run_trials(Policy.CKPT, "weights", fault.apply,
                               trial_keys(spec))
    counts = classify_counts(det, mis)
    assert counts["sdc"] == 0
    assert counts["detected_corrected"] == 20
    m = case.fleet.metrics
    assert m.incremental_restores >= 1             # partial restore, not reload
    assert m.full_reloads == 0
    assert m.leaves_restored >= 1


# ---------------------------------------------------------------------------
# Process transport: framing, wire round trips, cross-process bit identity
# ---------------------------------------------------------------------------


def test_transport_frame_round_trip():
    from repro.fleet import transport as tp
    arrays = {
        "i32": np.arange(6, dtype=np.int32).reshape(2, 3),
        "f32": np.asarray([[1.5, -2.25]], np.float32),
        "i8": np.asarray([-128, 127], np.int8),
        "scalar": np.asarray(3.0, np.float64),
    }
    payload = {"x": 1, "s": "y", "nested": {"a": [1, 2]}}
    buf = tp.encode_frame(7, "step", payload, arrays)
    seq, op, got_payload, got_arrays = tp.decode_frame(buf)
    assert (seq, op, got_payload) == (7, "step", payload)
    assert set(got_arrays) == set(arrays)
    for name, a in arrays.items():
        g = got_arrays[name]
        assert g.dtype == a.dtype and g.shape == a.shape
        assert np.array_equal(g, a)


def test_transport_frame_rejects_garbage():
    from repro.fleet import transport as tp
    good = tp.encode_frame(0, "ping", {}, {})
    with pytest.raises(tp.ProtocolError):
        tp.decode_frame(b"XXXX" + good[4:])        # bad magic
    with pytest.raises(tp.ProtocolError):
        tp.decode_frame(good[:-1])                 # truncated
    with pytest.raises(tp.ProtocolError):
        tp.decode_frame(good + b"\x00")            # trailing bytes


def test_pipe_channel_enforces_consecutive_seq():
    import multiprocessing as mp_proc
    from repro.fleet import transport as tp
    a, b = mp_proc.Pipe()
    ch = tp.PipeChannel(a, "seqtest")
    b.send_bytes(tp.encode_frame(1, "first", {}, {}))
    op, _, _ = ch.get(5)
    assert op == "first"
    b.send_bytes(tp.encode_frame(3, "gap", {}, {}))  # skipped seq 2
    with pytest.raises(tp.ProtocolError):
        ch.get(5)
    a.close()
    b.close()


def test_request_wire_doc_round_trip():
    req = Request(uid=3, prompt=[1, 2, 3], max_new_tokens=5)
    clone = Request.from_doc(req.to_doc())
    assert (clone.uid, clone.prompt, clone.max_new_tokens) == (3, [1, 2, 3], 5)
    finished = Request.from_doc(req.to_doc())
    finished.output = [9, 8]
    finished.finished_tick = 4
    finished.t_submit, finished.t_finish = 1.5, 2.5
    req.sync_from_doc(finished.to_doc())
    assert req.output == [9, 8] and req.finished_tick == 4
    assert req.stamps() == [1.5, 0.0, 0.0, 2.5, 0.0]


@pytest.mark.parametrize("arch", [
    "smollm-135m",
    pytest.param("rwkv6-1.6b", marks=pytest.mark.slow),
])
def test_proc_fleet_bit_identical_with_failover(arch):
    """The transport acceptance gate: a 3-replica process fleet releases
    byte-identical token streams to the in-process fleet — including when
    one worker is SIGKILLed mid-run and its work fails over."""
    cfg = reduced(registry.get(arch))
    params = model_api.init_params(cfg, jax.random.key(0))
    prompts = PROMPTS[:3]

    def serve(fleet, kill=False):
        fleet.reset()
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            assert fleet.submit(r)
        if kill:
            fleet.tick()
            fleet.tick()
            fleet.replicas[0].handle.proc.kill()     # SIGKILL, no goodbye
        fleet.run()
        return [tuple(fleet.released[r.uid].output) for r in reqs]

    ref = Fleet(cfg, params, n_replicas=3, policy=Policy.NONE,
                capacity=2, max_len=96, prefill_pad=8)
    try:
        golden = serve(ref)
    finally:
        ref.close()

    fleet = Fleet(cfg, params, n_replicas=3, policy=Policy.NONE,
                  capacity=2, max_len=96, prefill_pad=8, transport="proc")
    try:
        assert serve(fleet) == golden                # clean cross-process pass
        assert serve(fleet, kill=True) == golden     # mid-run worker loss
        assert fleet.metrics.recoveries + fleet.metrics.failovers > 0
        assert all(r.state is ReplicaState.HEALTHY for r in fleet.replicas)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Zero-drain rolling weight deploys
# ---------------------------------------------------------------------------


def _deploy_fleet(policy=Policy.ABFT):
    cfg = reduced(registry.get("smollm-135m"))
    pa = model_api.init_params(cfg, jax.random.key(0))
    fleet = Fleet(cfg, pa, n_replicas=2, policy=policy,
                  capacity=2, max_len=96, prefill_pad=8, scrub_every=3)
    return cfg, pa, fleet


def _serve_with_deploy(fleet, prompts, n_new, deploy_to=None, mid_swap=None):
    fleet.reset()
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        assert fleet.submit(r)
    summary = None
    if deploy_to is not None:
        fleet.tick()
        fleet.tick()
        summary = fleet.deploy(params=deploy_to, mid_swap=mid_swap)
    fleet.run()
    outs = [tuple(fleet.released[r.uid].output) if r.uid in fleet.released
            else None for r in reqs]
    return outs, summary


def test_rolling_deploy_swaps_weights_without_draining():
    """Deploying genuinely different weights mid-serve: every in-flight
    request still releases (zero drain), both replicas re-verify against
    the *new* checksums, and the released tokens change — proof the swap
    reached the live engines."""
    cfg, pa, fleet = _deploy_fleet()
    pb = model_api.init_params(cfg, jax.random.key(1))
    try:
        golden_a, _ = _serve_with_deploy(fleet, PROMPTS[:3], 5)
        mixed, summary = _serve_with_deploy(fleet, PROMPTS[:3], 5,
                                            deploy_to=pb)
        assert summary["swapped"] == [0, 1] and not summary["failed"]
        assert summary["changed"] > 0                # a real weight diff
        assert None not in mixed                     # zero drain: all released
        assert mixed != golden_a                     # new weights are live
        assert fleet.metrics.deploys == 1
        assert fleet.metrics.replicas_swapped == 2
        # ABFT certify gating ran scrubs against the *new* golden the whole
        # time — any stale-checksum bug would have shown up as a detection
        assert fleet.metrics.detections == 0
        kinds = [e.kind for e in fleet.event_log]
        assert kinds.count("deploy_start") == 1
        assert kinds.count("replica_swapped") == 2
        # the fleet now serves the new weights steady-state
        post, _ = _serve_with_deploy(fleet, PROMPTS[:3], 5)
        assert post != golden_a
        assert all(r.routable and r.state is ReplicaState.HEALTHY
                   for r in fleet.replicas)
    finally:
        fleet.close()


def test_rolling_deploy_mid_swap_strike_detected_and_healed():
    """An SEU striking replica 0 while replica 1 is mid-swap — the hardest
    window — must be detected by the post-deploy certify gating and healed,
    with the released stream still byte-identical to the fault-free run."""
    cfg, pa, fleet = _deploy_fleet()
    try:
        golden, _ = _serve_with_deploy(fleet, PROMPTS[:3], 6, deploy_to=pa)

        def mid_swap(rid):
            if rid == 1:
                fleet.strike(0, "weights", fi.flip_one_bit,
                             jax.random.key(11))

        struck, summary = _serve_with_deploy(fleet, PROMPTS[:3], 6,
                                             deploy_to=pa, mid_swap=mid_swap)
        assert struck == golden
        assert fleet.metrics.detections >= 1
        assert fleet.metrics.recoveries >= 1
        assert all(r.state is ReplicaState.HEALTHY and r.routable
                   for r in fleet.replicas)
        kinds = [e.kind for e in fleet.event_log]
        assert "strike" in kinds and "recovery" in kinds
        # the strike landed inside the deploy window
        assert kinds.index("deploy_start") < kinds.index("strike")
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Speculative backup dispatch
# ---------------------------------------------------------------------------


def test_speculative_backup_wins_when_primary_stalls(smollm_fleet):
    """A straggling primary gets its in-flight request re-issued to a warm
    spare; when the primary stalls outright, the backup's release wins and
    carries the exact bytes the primary would have produced."""
    cfg, params, fleet = smollm_fleet
    fleet.reset()
    prompt = [5, 9, 2]
    req = Request(uid=0, prompt=list(prompt), max_new_tokens=5)
    assert fleet.submit(req)
    fleet.tick()                                     # request is in flight
    # synthetic step-time history: replica 0 is 900× slower than the median
    for i in range(5):
        for rid, dt in ((0, 9.0), (1, 0.01), (2, 0.01)):
            fleet.supervisor.heartbeat(rid, i + 1, dt, fleet.tick_no)
    assert fleet.supervisor.stragglers() == [0]
    fleet._dispatch_backups([0])
    rec = fleet.records[0]
    assert rec.backup is not None and rec.backup_rid != 0
    assert fleet.metrics.backup_dispatches == 1
    assert [e.kind for e in fleet.event_log].count("backup_dispatch") == 1
    fleet.replicas[0].paused = True                  # primary stalls outright
    fleet.run()
    fleet.replicas[0].paused = False
    assert fleet.metrics.backups_won == 1
    assert fleet.released[0] is rec.backup           # the backup's copy won
    assert list(fleet.released[0].output) == greedy_reference(
        cfg, params, prompt, 5)


# ---------------------------------------------------------------------------
# Chaos: SIGKILL under load and mid-deploy (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_proc_fleet_chaos_sigkill_mid_decode_and_mid_deploy():
    """Soak the worst windows: SIGKILL one worker mid-decode, then SIGKILL
    another *during its own weight swap*.  Both must come back through
    quarantine → restore → re-verify, every replayed token must match the
    fault-free run, and the event log must record the full chain."""
    cfg = reduced(registry.get("smollm-135m"))
    params = model_api.init_params(cfg, jax.random.key(0))
    fleet = Fleet(cfg, params, n_replicas=2, policy=Policy.ABFT,
                  capacity=2, max_len=96, prefill_pad=8, scrub_every=3,
                  transport="proc")
    try:
        golden, _ = _serve_with_deploy(fleet, PROMPTS[:3], 6, deploy_to=params)

        fleet.reset()
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=6)
                for i, p in enumerate(PROMPTS[:3])]
        for r in reqs:
            assert fleet.submit(r)
        fleet.tick()
        fleet.tick()
        fleet.replicas[0].handle.proc.kill()         # chaos 1: mid-decode
        fleet.tick()                                 # detect, respawn, replay

        def mid_swap(rid):
            if rid == 1:
                fleet.replicas[1].handle.proc.kill() # chaos 2: mid-own-swap
        summary = fleet.deploy(params=params, mid_swap=mid_swap)
        fleet.run()

        outs = [tuple(fleet.released[r.uid].output) for r in reqs]
        assert outs == golden                        # replay is bit-exact
        assert summary["step"] == 2
        assert fleet.metrics.recoveries >= 2
        assert all(r.state is ReplicaState.HEALTHY and r.routable and r.alive
                   for r in fleet.replicas)
        kinds = [e.kind for e in fleet.event_log]
        assert kinds.count("detection") >= 2         # both transport deaths
        assert kinds.count("quarantine") >= 2
        assert kinds.count("recovery") >= 2
        # the second death happened inside the deploy window and the swap
        # still completed (recovery respawned onto the *new* step)
        dep = kinds.index("deploy_start")
        assert "detection" in kinds[dep:] and "recovery" in kinds[dep:]
        assert kinds[dep:].count("replica_swapped") == 2
    finally:
        fleet.close()
