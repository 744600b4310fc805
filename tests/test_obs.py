"""Observability layer: metrics registry semantics, byte-identical
same-seed span traces (transformer + rwkv, multi-step, under rollback),
the structured dependability event log, FleetMetrics export stability, and
the campaign report's timeline columns."""
from __future__ import annotations

import json

import jax
import pytest

from repro.configs import registry
from repro.core import fault_injection as fi
from repro.core.dependability import Policy
from repro.models import api as model_api
from repro.models.config import reduced
from repro.obs import (EventLog, Histogram, Registry, SpanTracer,
                       exp_buckets, merge_traces)
from repro.runtime.serving import Engine, Request

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_fns():
    # This module compiles engine variants for two model families (traced,
    # untraced, multi-step, rollback); holding those executables for the rest
    # of the suite pushes the process's accumulated XLA compile state past
    # what later large compiles (transformer w8a8) survive.  Drop them when
    # the module is done — later tests recompile what they need.
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_semantics():
    reg = Registry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(4)
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert c.value == 5 and g.value == 5
    # get-or-create: same name returns the same instrument…
    assert reg.counter("reqs_total") is c
    # …and a kind clash is an error, not a silent shadow
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")


def test_histogram_exact_stats_and_bounded_memory():
    h = Histogram("lat", buckets=exp_buckets(1.0, 2.0, 8))
    n_buckets = len(h.to_dict()["buckets"])
    for i in range(10_000):
        h.observe(float(i % 250))
    assert h.count == 10_000
    assert h.min == 0.0 and h.max == 249.0
    assert h.mean() == pytest.approx(124.5)
    p50 = h.percentile(0.5)
    assert h.min <= p50 <= h.max
    # streaming: absorbing 10k samples must not grow the representation
    assert len(h.to_dict()["buckets"]) == n_buckets


def test_histogram_percentile_clamped_to_observed_range():
    h = Histogram("x", buckets=(1.0, 10.0, 100.0))
    for v in (3.0, 4.0, 5.0):
        h.observe(v)
    assert h.percentile(0.0) >= h.min
    assert h.percentile(1.0) <= h.max


def test_registry_snapshot_and_prometheus_render():
    reg = Registry()
    reg.counter("a_total", "a").inc(3)
    reg.histogram("h", "h", buckets=(1.0, 2.0)).observe(1.5)
    snap = reg.snapshot()
    assert list(snap) == ["a_total", "h"]      # registration order
    text = reg.render_prometheus()
    assert "a_total 3" in text
    assert 'h_bucket{le="2"' in text or 'h_bucket{le="2.0"}' in text
    assert "h_sum" in text and "h_count 1" in text


def test_registry_dump_json_and_prom(tmp_path):
    reg = Registry()
    reg.counter("c_total").inc()
    jpath = reg.dump(tmp_path / "m.json")
    assert json.loads(jpath.read_text())["c_total"]["value"] == 1
    ppath = reg.dump(tmp_path / "m.prom")
    assert "c_total 1" in ppath.read_text()


# ---------------------------------------------------------------------------
# Span tracer primitives
# ---------------------------------------------------------------------------


def test_tracer_span_lifecycle_and_canonical_bytes():
    def build():
        tr = SpanTracer()
        tr.tick_to(1)
        tr.open_span(0, "admit", prompt_len=3)
        tr.tick_to(2)
        tr.close_span(0, "admit")
        tr.instant("strike", site="kv_cache")
        tr.counter("queue_depth", submit=2)
        tr.open_span(1, "decode")          # left open: flushed as unfinished
        return tr

    a, b = build(), build()
    assert a.to_bytes() == b.to_bytes()
    doc = a.to_chrome_trace()
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {"admit", "decode"} == {e["name"] for e in spans}
    admit = next(e for e in spans if e["name"] == "admit")
    assert admit["ts"] == 1 and admit["dur"] == 1
    assert admit["args"]["uid"] == 0 and admit["args"]["prompt_len"] == 3
    open_flush = next(e for e in spans if e["name"] == "decode")
    assert open_flush["args"]["unfinished"] is True
    assert doc["metadata"]["clock"] == "ticks"


def test_tracer_cancel_drops_span_silently():
    tr = SpanTracer()
    tr.open_span(7, "prefill")
    tr.cancel_span(7, "prefill")
    tr.close_span(7, "prefill")            # not open: silent no-op
    assert not [e for e in tr.events if e["ph"] == "X"]


def test_merge_traces_keeps_pids_distinct():
    a, b = SpanTracer(name="replica0", pid=0), SpanTracer(name="replica1",
                                                          pid=1)
    for tr in (a, b):
        tr.open_span(0, "decode")
        tr.tick_to(3)
        tr.close_span(0, "decode")
    doc = merge_traces([a, b])
    assert {e["pid"] for e in doc["traceEvents"]} == {0, 1}
    assert doc["metadata"]["tracer"] == "replica0+replica1"


# ---------------------------------------------------------------------------
# Engine trace determinism (the byte-identity acceptance criterion)
# ---------------------------------------------------------------------------

TRACE_ARCHS = ["smollm-135m", "rwkv6-1.6b"]


@pytest.fixture(scope="module", params=TRACE_ARCHS)
def traced_family(request):
    cfg = reduced(registry.get(request.param))
    params = model_api.init_params(cfg, jax.random.key(0))
    return cfg, params


def _traced_serve(cfg, params, *, multi_step=1, rollback=False):
    tracer = SpanTracer()
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 multi_step=multi_step,
                 snapshot_every=2 if rollback else 32,
                 state_scrub="rollback" if rollback else "off",
                 tracer=tracer)
    prompts = [[5, 9, 2], [3, 1, 4, 1], [2, 7]]
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    if rollback:
        for _ in range(3):
            eng.step()
        eng.strike("decode_state", fi.flip_one_bit, jax.random.key(3))
    eng.run()
    return tracer, [list(r.output) for r in reqs]


def test_same_seed_traces_are_byte_identical(traced_family):
    cfg, params = traced_family
    tr_a, out_a = _traced_serve(cfg, params)
    tr_b, out_b = _traced_serve(cfg, params)
    assert out_a == out_b
    assert tr_a.to_bytes() == tr_b.to_bytes()
    spans = [e for e in tr_a.to_chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"admit", "prefill", "decode",
                                          "certify"}
    # every request leaves a full certified span chain
    certified = [e for e in spans if e["name"] == "certify"]
    assert len(certified) == 3
    assert all(e["args"]["certified"] for e in certified)


def test_multi_step_traces_are_byte_identical(traced_family):
    cfg, params = traced_family
    tr_a, out_a = _traced_serve(cfg, params, multi_step=4)
    tr_b, out_b = _traced_serve(cfg, params, multi_step=4)
    assert out_a == out_b
    assert tr_a.to_bytes() == tr_b.to_bytes()


def test_rollback_traces_are_byte_identical(traced_family):
    """Snapshot rollback repairs the span state deterministically: the
    same strike at the same tick replays to the same byte stream."""
    cfg, params = traced_family
    tr_a, out_a = _traced_serve(cfg, params, multi_step=2, rollback=True)
    tr_b, out_b = _traced_serve(cfg, params, multi_step=2, rollback=True)
    assert out_a == out_b
    assert tr_a.to_bytes() == tr_b.to_bytes()
    names = [e["name"] for e in tr_a.events if e["ph"] == "i"]
    assert "strike" in names and "rollback" in names


def test_tracing_is_a_pure_observer(traced_family):
    """Token streams with tracing on must equal the untraced streams."""
    cfg, params = traced_family
    _, traced = _traced_serve(cfg, params, multi_step=2, rollback=True)
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 multi_step=2, snapshot_every=2, state_scrub="rollback")
    assert eng.tracer is None            # disabled by default: None hooks
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=4)
            for i, p in enumerate([[5, 9, 2], [3, 1, 4, 1], [2, 7]])]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    eng.strike("decode_state", fi.flip_one_bit, jax.random.key(3))
    eng.run()
    assert [list(r.output) for r in reqs] == traced


def test_engine_metrics_counters_match_stats(traced_family):
    cfg, params = traced_family
    reg = Registry()
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 metrics=reg)
    reqs = [Request(uid=i, prompt=[5, 2, 9], max_new_tokens=3)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    snap = reg.snapshot()
    assert snap["engine_requests_submitted_total"]["value"] == 3
    assert snap["engine_requests_released_total"]["value"] == 3
    # mirrors stats.tokens_out: decode-step tokens (each request's first
    # token comes from prefill, not a decode step)
    assert snap["engine_tokens_out_total"]["value"] == eng.stats.tokens_out
    assert snap["engine_release_latency_ticks"]["count"] == 3


# ---------------------------------------------------------------------------
# Event log
# ---------------------------------------------------------------------------


def test_event_log_validates_kind_and_merges_ctx():
    log = EventLog(policy="ckpt", replica=2)
    ev = log.emit("strike", tick=4, site="kv_cache", fault="single_bitflip")
    assert ev.policy == "ckpt" and ev.replica == 2 and ev.site == "kv_cache"
    with pytest.raises(ValueError):
        log.emit("meteor", tick=5)


def test_event_log_timeline_reconstruction():
    log = EventLog(policy="ckpt")
    log.emit("strike", tick=10, site="kv_cache")
    log.emit("detection", tick=12, site="decode_state")
    log.emit("rollback", tick=13, seconds=0.5)
    log.emit("strike", tick=20, site="weights")      # undetected chain
    tls = log.timelines()
    assert len(tls) == 2
    first, second = tls
    assert first["detected"] and first["detection_latency_ticks"] == 2
    assert first["recovered"] and first["recovery_latency_ticks"] == 3
    assert first["recovery_seconds"] == 0.5
    assert not second["detected"] and not second["recovered"]
    summary = log.latency_summary()["ckpt"]
    assert summary["strikes"] == 2 and summary["detected"] == 1
    assert summary["detection_ticks_mean"] == 2.0


def test_event_log_wall_flag_strips_seconds():
    log = EventLog()
    log.emit("strike", tick=1)
    log.emit("recovery", tick=2, seconds=1.25)
    with_wall = log.to_json(wall=True)
    without = log.to_json(wall=False)
    assert with_wall["events"][1]["seconds"] == 1.25
    assert all("seconds" not in e for e in without["events"])
    assert all("recovery_seconds" not in t for t in without["timelines"])


def test_engine_emits_provenance_stamped_events(traced_family):
    cfg, params = traced_family
    log = EventLog(policy="ckpt")
    eng = Engine(cfg, params, capacity=2, max_len=96, prefill_pad=8,
                 snapshot_every=2, state_scrub="rollback", event_log=log)
    reqs = [Request(uid=i, prompt=[5, 2, 9], max_new_tokens=6)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(2):
        eng.step()
    eng.strike("decode_state", fi.flip_one_bit, jax.random.key(3))
    eng.run()
    kinds = [e.kind for e in log]
    assert kinds.count("strike") == 1
    assert "detection" in kinds and "rollback" in kinds
    strike = log.of_kind("strike")[0]
    assert strike.site == "decode_state" and strike.fault == "flip_one_bit"
    assert strike.policy == "ckpt"
    (tl,) = log.timelines()
    assert tl["detected"] and tl["recovered"]
    assert tl["detection_latency_ticks"] >= 0


# ---------------------------------------------------------------------------
# Campaign integration: one strike per trial, policy-resolved chains
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_campaign():
    from repro.campaign import faultload as fl
    from repro.campaign.runner import run_campaign
    specs = fl.expand_grid(
        ["serving"], [Policy.NONE, Policy.ABFT, Policy.CKPT],
        ["kv_cache", "weights"], ["single_bitflip"], 2, 0)
    sink = []
    results = run_campaign(specs, event_sink=sink)
    return {(r.policy, r.site): r for r in results}, \
        {e["config"]: e["timelines"] for e in sink}


def test_campaign_logs_exactly_one_strike_per_trial(serving_campaign):
    results, _ = serving_campaign
    for r in results.values():
        assert r.strikes_logged == r.trials, (r.policy, r.site)


def test_campaign_detection_recovery_under_policies(serving_campaign):
    results, timelines = serving_campaign
    for site in ("kv_cache", "weights"):
        for policy in ("abft", "ckpt"):
            r = results[(policy, site)]
            assert r.detections_logged == r.trials, (policy, site)
            tls = timelines[f"serving/{policy}/{site}/single_bitflip"]
            assert all(t["detected"] for t in tls)
            assert all(t["detection_latency_ticks"] >= 0 for t in tls)
            if policy == "ckpt":
                assert all(t["recovered"] for t in tls), site
                r_lat = [t["recovery_latency_ticks"] for t in tls]
                assert all(lat >= 0 for lat in r_lat)


def test_campaign_none_policy_detects_nothing(serving_campaign):
    results, timelines = serving_campaign
    for site in ("kv_cache", "weights"):
        r = results[("none", site)]
        assert r.detections_logged == 0, site
        tls = timelines[f"serving/none/{site}/single_bitflip"]
        assert all(not t["detected"] and not t["recovered"] for t in tls)


def test_campaign_accumulator_site_synthesized_timelines():
    """Kernel (in-graph) workloads cannot emit host events mid-vmap; the
    runner synthesizes the chains from trial verdicts — ABFT detects every
    accumulator strike, NONE never does."""
    from repro.campaign import faultload as fl
    from repro.campaign.runner import run_campaign
    specs = fl.expand_grid(["qmatmul"], [Policy.NONE, Policy.ABFT,
                                         Policy.CKPT],
                           ["accumulator"], ["single_bitflip"], 8, 0)
    sink = []
    results = {r.policy: r for r in run_campaign(specs, event_sink=sink)}
    assert results["abft"].strikes_logged == 8
    assert results["abft"].detections_logged == 8
    assert results["none"].detections_logged == 0
    ck = results["ckpt"]
    assert ck.detections_logged == 8 and ck.faults_recovered == 8
    # in-op detection is same-tick: zero-latency chains
    assert ck.detection_ticks_max == 0 and ck.recovery_ticks_max == 0


def test_config_result_timeline_columns_round_trip():
    from repro.campaign.report import ConfigResult, to_markdown
    r = ConfigResult(workload="serving", policy="ckpt", site="kv_cache",
                     fault_model="single_bitflip", trials=4, masked=0,
                     detected_corrected=4, detected_uncorrected=0, sdc=0,
                     faults_recovered=4, strikes_logged=4,
                     detections_logged=4, detection_ticks_mean=1.5,
                     detection_ticks_max=3, recovery_ticks_mean=2.0,
                     recovery_ticks_max=4)
    again = ConfigResult.from_dict(r.to_dict())
    assert again == r
    # reports written before the timeline columns still load
    legacy = {k: v for k, v in r.to_dict().items()
              if not k.startswith(("strikes_", "detections_",
                                   "detection_", "recovery_ticks"))}
    old = ConfigResult.from_dict(legacy)
    assert old.strikes_logged == 0 and old.detection_ticks_mean == 0.0
    md = to_markdown([r])
    assert "det. lat ticks (mean/max)" in md
    assert "| 1.5/3 |" in md and "| 2.0/4 |" in md


# ---------------------------------------------------------------------------
# FleetMetrics export stability
# ---------------------------------------------------------------------------


def test_fleet_metrics_attribute_routing_and_json_keys():
    from repro.fleet.metrics import FleetMetrics
    m = FleetMetrics(lost_work_bound_tokens=12)
    m.detections += 1
    m.observe_release(4, 2)
    m.observe_release(8, 3)
    m.released += 1
    m.observe_recovery(0.5, leaves=2, incremental=True)
    assert m.released == 3 and m.detections == 1 and m.tokens_out == 5
    doc = m.to_json()
    for key in ("released", "detections", "recoveries", "failovers",
                "scrubs", "lost_work_bound_tokens", "p50_latency_ticks",
                "p99_latency_ticks", "tokens_per_tick", "recovery_count",
                "recovery_mean_seconds", "recovery_max_seconds"):
        assert key in doc, key
    assert doc["lost_work_bound_tokens"] == 12
    assert doc["recovery_count"] == 1
    assert doc["recovery_mean_seconds"] == pytest.approx(0.5)
    assert m.incremental_restores == 1
    # wall-clock numbers are opt-in so default reports diff cleanly
    assert "tokens_per_second" not in doc and "wall_seconds" not in doc
    wall = m.to_json(wall=True)
    assert "tokens_per_second" in wall and "wall_seconds" in wall


def test_fleet_metrics_histograms_are_streaming():
    from repro.fleet.metrics import FleetMetrics
    m = FleetMetrics()
    for i in range(50_000):
        m.observe_release(i % 128, 1)
    assert m.latencies.count == 50_000
    assert m.p50_ticks <= m.p99_ticks <= m.latencies.max


def test_profiler_span_lands_on_the_host_line(tmp_path):
    """``obs.span`` writes a host event into the profiler's trace, under
    its own name, with its arguments as the event's stats."""
    import glob

    from jax.profiler import ProfileData

    from repro.obs import trace as obs_trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("engine/test_span", uid=7):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    found = [dict(e.stats) for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events
             if e.name == "engine/test_span"]
    assert found == [{"uid": 7}]
