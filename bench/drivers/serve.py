"""Serving cells: open-loop requests into the protected smollm engine, or
into a fleet of its replicas, one replica a chip.

Set-up makes the weights on the device from the seed, builds the server,
warms up every prefill bucket the traffic's prompts fall into and the
decode program, then serves the first ``warmup_s`` seconds of the
schedule.  The window opens there and lasts ``--seconds``.  Each request is
timed from its scheduled arrival to its certified release; every request
due in the window is followed until it is released, up to ``drain_cap_s``
past the close, while the schedule keeps arriving.

After the window: the chip's peak memory is read, the server is freed, and
the float32 reference (``reference/smollm.py``) is run over a sample of the
window's released requests drawn from the seed, the longest among them.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import numpy as np

import harness
from harness import annotate


def arch_config(cfg: dict, rehearse: bool):
    """The program's ArchConfig for a configuration file."""
    from repro.models.config import ArchConfig, reduced
    s = cfg["serving"]
    arch = ArchConfig(
        name=cfg["name"], family="transformer",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        quant=s["quant"], quant_kv=s["quant_kv"])
    if rehearse:
        arch = dataclasses.replace(reduced(arch), quant=s["quant"],
                                   quant_kv=s["quant_kv"])
    return arch


def reference_dims(arch) -> dict:
    """The configuration's numbers as the reference reads them (the
    rehearsal's reduced sizes where it runs)."""
    return {"num_hidden_layers": arch.n_layers, "hidden_size": arch.d_model,
            "num_attention_heads": arch.n_heads,
            "num_key_value_heads": arch.n_kv_heads,
            "head_dim": arch.head_dim, "intermediate_size": arch.d_ff,
            "vocab_size": arch.vocab_size, "rms_norm_eps": arch.norm_eps,
            "rope_theta": arch.rope_theta}


def make_weights(arch, key):
    """The bench's weights, checked against the tree the program takes."""
    import jax
    from reference import smollm
    from repro.models import api
    params = smollm.make_weights(reference_dims(arch), key)
    want = jax.eval_shape(lambda k: api.init_params(arch, k), key)
    got, exp = (jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
                for t in (params, want))
    if got != exp:
        raise RuntimeError(f"weights do not match the program's tree: "
                           f"{got} vs {exp}")
    return jax.block_until_ready(params)


# ----------------------------------------------------------------- servers

class EngineServer:
    """One protected engine (runtime.serving.Engine) on one chip."""

    def __init__(self, arch, params, cfg: dict, engine_kw: dict, devs):
        from repro.runtime.serving import Engine
        s = cfg["serving"]
        self.engine = Engine(arch, params, backend=s["backend"],
                             policy_map=s["policy_map"], **engine_kw)
        self.capacity = engine_kw["capacity"]
        self.replicas = 1

    def submit(self, req) -> bool:
        with annotate("submit"):
            self.engine.submit(req)
        return True

    def busy(self) -> bool:
        return self.engine.executor.busy()

    def outstanding(self) -> int:
        """Requests submitted and not yet released."""
        return self.engine.executor.pending_count()

    def pump(self) -> list:
        with annotate("engine.step"):
            return self.engine.step()

    def counters(self) -> dict:
        st = self.engine.stats
        dep = self.engine.dependability_report()
        return {"steps": [st.steps], "tokens_out": [st.tokens_out],
                "faults_detected": int(dep["faults_detected"]),
                "checks_run": int(dep["checks_run"])}

    def replica_of(self, uid: int) -> int:
        return 0

    def rearm(self, params):
        """Serve new weights: fresh run state, new golden weights."""
        self.engine.reset(params=params)
        self.engine.refresh_storage_baseline()

    def reset(self):
        """Fresh run state, same weights."""
        self.engine.reset()

    def close(self):
        self.engine = None


class FleetServer:
    """ABFT replicas behind the fleet's router, replica i on chip i."""

    def __init__(self, arch, params, cfg: dict, engine_kw: dict, devs):
        from repro.core.dependability import Policy
        from repro.fleet import Fleet
        self._args = (arch, cfg, engine_kw, devs)
        s = cfg["serving"]
        kw = {k: v for k, v in engine_kw.items() if k != "multi_step"}
        self.fleet = Fleet(arch, params, n_replicas=len(devs),
                           policy=Policy.ABFT, router="least_loaded",
                           backend=s["backend"], policy_map=s["policy_map"],
                           **kw)
        self.capacity = engine_kw["capacity"] * len(devs)
        self.replicas = len(devs)
        self._outstanding = set()
        self._seen = 0

    def submit(self, req) -> bool:
        with annotate("submit"):
            ok = self.fleet.submit(req)
        if ok:
            self._outstanding.add(req.uid)
        return ok

    def busy(self) -> bool:
        return bool(self._outstanding)

    def outstanding(self) -> int:
        """Requests submitted and not yet released."""
        return len(self._outstanding)

    def pump(self) -> list:
        with annotate("Fleet.tick"):
            self.fleet.tick()
        done = list(self.fleet.released.values())[self._seen:]
        self._seen += len(done)
        for r in done:
            self._outstanding.discard(r.uid)
        return done

    def counters(self) -> dict:
        reps = self.fleet.replicas
        return {"steps": [r.engine.stats.steps for r in reps],
                "tokens_out": [r.engine.stats.tokens_out for r in reps],
                "faults_detected": int(self.fleet.metrics.detections),
                "checks_run": int(self.fleet.metrics.scrubs)}

    def replica_of(self, uid: int) -> int:
        return self.fleet.records[uid].primary_rid

    def rearm(self, params):
        """Serve new weights: a new fleet over them."""
        arch, cfg, engine_kw, devs = self._args
        self.close()
        self.__init__(arch, params, cfg, engine_kw, devs)

    def reset(self):
        """Fresh run state, same weights."""
        self.fleet.reset()
        self._outstanding.clear()
        self._seen = 0

    def close(self):
        self.fleet.close()
        self.fleet = None


SERVERS = {"engine": EngineServer, "fleet": FleetServer}


# -------------------------------------------------------------------- run

def warm_compiles(server, arch, traffic: dict, engine_kw: dict, rng):
    """One request per prefill bucket the traffic's prompts fall into,
    each long enough to pass a snapshot and a storage scrub, served to the
    end: compiles every program the window runs."""
    from repro.runtime.serving import Request
    pad = engine_kw["prefill_pad"]
    new = engine_kw["multi_step"] * (engine_kw["snapshot_every"] + 2) + 1
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    lens = sorted({min(hi, b) for b in range(-(-lo // pad) * pad, hi + pad,
                                              pad)})
    uid = -1
    for n in lens:
        for _ in range(server.replicas):
            server.submit(Request(uid=uid, prompt=rng.integers(
                1, arch.vocab_size, n).tolist(), max_new_tokens=new))
            uid -= 1
    while server.busy():
        server.pump()


class Setup:
    """Everything a window needs: the cell's settings, the weights and the
    server, warmed up."""

    def __init__(self, cell, seed: int, devs, rehearse: bool, server=None):
        import jax
        spec, traffic = dict(cell.spec), dict(cell.traffic)
        if rehearse:
            spec.update(spec.get("rehearsal", {}))
            for k in ("prompt_tokens", "output_tokens"):
                traffic[k] = dict(traffic[k], **spec.get(k, {}))
        self.cell, self.spec, self.traffic, self.devs = cell, spec, traffic, devs
        self.engine_kw = dict(cell.config["serving"]["engine"])
        self.engine_kw.update(spec.get("engine", {}))
        self.arch = arch_config(cell.config, rehearse)
        ss = np.random.SeedSequence(seed % 2 ** 64)
        k_weights, self.k_traffic, self.k_sample, k_warm = ss.spawn(4)
        t0 = time.perf_counter()
        self.params = make_weights(self.arch, jax.random.key(
            int(k_weights.generate_state(1)[0] & 0x7FFFFFFF)))
        t1 = time.perf_counter()
        if server is None:
            server = SERVERS[spec["deployment"]](
                self.arch, self.params, cell.config, self.engine_kw, devs)
        else:
            server.rearm(self.params)
        self.server = server
        t2 = time.perf_counter()
        warm_compiles(server, self.arch, traffic, self.engine_kw,
                      np.random.default_rng(k_warm))
        self.setup_phases = {"weights_s": t1 - t0, "server_s": t2 - t1,
                             "warm_compiles_s": time.perf_counter() - t2}

    def schedule(self, rate_per_s: float, seconds: float) -> list:
        from repro.runtime.serving import Request
        gen = harness.load_module("traffic", self.traffic["generator"])
        sched = gen.generate(
            self.traffic, np.random.default_rng(self.k_traffic),
            rate_per_s=rate_per_s,
            segments=[self.spec["warmup_s"], seconds,
                      self.spec["drain_cap_s"]],
            vocab_size=self.arch.vocab_size)
        return [(r["due_s"], Request(uid=i, prompt=r["prompt"],
                                     max_new_tokens=r["max_new_tokens"]))
                for i, r in enumerate(sched)]


def serve_window(st: Setup, rate_per_s: float, seconds: float, clock,
                 profiler=None) -> dict:
    """Serve the schedule: warm-up, the window, then every request due in
    the window until it is released or the cap passes."""
    server, spec = st.server, st.spec
    sched = st.schedule(rate_per_s, seconds)
    reqs = [r for _, r in sched]
    due = np.array([t for t, _ in sched])
    warm_s, cap_s = spec["warmup_s"], spec["drain_cap_s"]
    in_window = [i for i, t in enumerate(due)
                 if warm_s <= t < warm_s + seconds]
    trace_s = min(spec["trace_seconds"], seconds)
    released_at, outputs, lag, rejected = {}, {}, [], set()
    summary = None
    pending = set(in_window)
    c_open = c_close = None
    tracing = False
    i = 0
    base = time.perf_counter()
    t_open, t_close = base + warm_s, base + warm_s + seconds
    while True:
        now = time.perf_counter()
        while i < len(reqs) and base + due[i] <= now:
            lag.append(now - base - due[i])
            if not server.submit(reqs[i]):
                rejected.add(i)
                pending.discard(i)
            i += 1
        if c_open is None and now >= t_open:
            c_open = (server.counters(), clock.count)
            backlog_open = server.outstanding()
        if profiler and not tracing and now >= t_close - trace_s:
            profiler.start()
            tracing = True
        if c_close is None and now >= t_close:
            c_close = (server.counters(), clock.count)
            if profiler:
                profiler.stop()
            backlog_close = server.outstanding()
        if server.busy():
            for r in server.pump():
                released_at[r.uid] = time.perf_counter()
                outputs[r.uid] = list(r.output)
                pending.discard(r.uid)
        elif i < len(reqs):
            time.sleep(max(0.0, min(base + due[i], t_close) - now))
        if now >= t_close and (not pending or now >= t_close + cap_s):
            break
    if profiler:
        summary = profiler.summary()
    in_win_rel = [u for u, t in released_at.items() if t_open <= t < t_close]
    lat = [released_at[u] - (base + due[u]) if u in released_at
           else math.inf for u in in_window]
    return {
        "t_open": t_open, "reqs": reqs, "outputs": outputs,
        "in_window": in_window, "rejected": rejected, "summary": summary,
        "trace_s": trace_s, "latencies": lat, "lag": lag,
        "tokens_per_s": sum(len(outputs[u]) for u in in_win_rel) / seconds,
        "unreleased": [u for u in in_window if u not in released_at],
        "backlog": (backlog_open, backlog_close),
        "compiles_in_window": c_close[1] - c_open[1],
        "counters": {
            "prompt_lens": [len(reqs[u].prompt) for u in in_win_rel],
            "released": [len(outputs[u]) for u in in_win_rel],
            "replica_tokens": np.bincount(
                [server.replica_of(u) for u in in_win_rel],
                weights=[len(outputs[u]) for u in in_win_rel],
                minlength=server.replicas).tolist(),
            "steps": np.subtract(c_close[0]["steps"],
                                 c_open[0]["steps"]).tolist(),
            "tokens_out": np.subtract(c_close[0]["tokens_out"],
                                      c_open[0]["tokens_out"]).tolist(),
            "capacity": server.capacity,
        },
    }


def run(cell, args, devs, clock, t_start, rehearse: bool) -> dict:
    st = Setup(cell, args.seed, devs, rehearse)
    profiler = harness.Profiler(f"{cell.name}-{args.seed}") if args.trace \
        else None
    w = serve_window(st, st.spec["rate_per_s"], args.seconds, clock, profiler)
    stats = st.server.counters()
    peak = harness.memory_peak_bytes(devs)
    st.server.close()
    st.server = None
    gc.collect()

    reqs, outputs, in_window = w["reqs"], w["outputs"], w["in_window"]
    checks = [{"name": "requests_unreleased", "value": len(w["unreleased"]),
               "limit": 0, "ok": not w["unreleased"]}]
    wrong_len = [u for u in in_window if u in outputs
                 and len(outputs[u]) != reqs[u].max_new_tokens]
    checks.append({"name": "wrong_length", "value": len(wrong_len),
                   "limit": 0, "ok": not wrong_len})
    det = stats["faults_detected"]
    checks.append({"name": "detections", "value": det, "limit": 0,
                   "ok": det == 0})
    sample = sample_requests(in_window, outputs, st.spec["check"]["sample"],
                             np.random.default_rng(st.k_sample))
    checks.append(gap_check(st, reference_gap(st, reqs, outputs, sample),
                            sample))
    checks.append({"name": "tokens_compared", "value": sum(
        len(outputs[u]) for u in sample), "limit": None, "ok": True})
    lag = w["lag"]
    return {
        "attempted": len(in_window),
        "failed": len(set(w["unreleased"]) | (w["rejected"]
                                               & set(in_window))),
        "metrics": {"setup_s": w["t_open"] - t_start,
                    "tokens_per_s": w["tokens_per_s"],
                    "request_latency_p95_s": harness.percentile(
                        w["latencies"], 95)},
        "checks": checks, "memory_peak_bytes": peak,
        "counters": w["counters"], "trace": w["summary"],
        "extra": {"requests_due": len(in_window),
                  "requests_released_in_window": len(
                      w["counters"]["released"]),
                  "latency_p50_s": harness.percentile(w["latencies"], 50),
                  "submit_lag_p50_s": float(np.median(lag)) if lag else 0.0,
                  "submit_lag_max_s": float(max(lag)) if lag else 0.0,
                  "compiles_in_window": w["compiles_in_window"],
                  "scrubs_run": stats["checks_run"],
                  "setup_phases": st.setup_phases}}


def gap_check(st: Setup, gap: float, sample: list) -> dict:
    """The cell's comparison of the widest logit gap with its limit."""
    limit = st.spec["check"]["max_logit_gap"]
    return {"name": "max_logit_gap", "value": gap, "limit": limit,
            "ok": bool(sample) and gap <= limit}


def sample_requests(in_window, outputs, k: int, rng) -> list:
    """``k`` released requests of the window drawn from the seed, with the
    one that released the most tokens among them."""
    done = [u for u in in_window if u in outputs]
    if not done:
        return []
    longest = max(done, key=lambda u: (len(outputs[u]), -u))
    rest = [u for u in done if u != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def reference_gap(st: Setup, reqs, outputs, sample,
                  lower: bool = False) -> float:
    """Widest gap over the sample's released tokens (see reference/smollm)."""
    from reference import smollm
    t = st.traffic
    pad = t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
    worst = 0.0
    for u in sample:
        g = smollm.gaps(reference_dims(st.arch), st.params, reqs[u].prompt,
                        outputs[u], pad_to=pad, lower=lower)
        worst = max(worst, float(g.max()))
    return worst
