"""Start the dependable-inference system on the TPU and check what comes out.

    python chip_smoke.py [--seed N]        # one chip: serve, then shipdet
    python chip_smoke.py --chips 4         # four chips: the replica fleet

Phases on one chip:

  serve    smollm-135m at its published widths (30 layers, d_model 576,
           vocab 49152; random weights from --seed), W8A8 FFN + int8 KV
           cache, protected by reports/dse/best_map.json on the pallas
           backend, through runtime.serving.Engine with multi-step decode.
           8 requests with prompts of 16-200 tokens, 32 new tokens each.
           Checks: the released tokens are bit-identical to the jnp
           backend's; a clean run detects nothing; a weights SEU struck
           through Engine.strike is detected, healed, and the released
           stream stays bit-exact.
  shipdet  the ship-detection CNN on 388x388x3 frames, every layer ABFT on
           the pallas backend with deploy-time weight checks.  Checks:
           bit-identical to the jnp backend, clean (no detection), and
           within 4 output quantization steps of ``float_forward``.

With --chips 4 the script runs only the fleet: 4 ABFT replicas behind the
router, replica i on chip i, against the same requests on one replica.
Checks: identical released streams, and every chip holds memory.

Each phase prints the device, its compile seconds, and how many Pallas
kernels its programs hold against how many Mosaic ``tpu_custom_call``s the
compiled programs hold (equal: every kernel runs compiled, none
interpreted).  No speed is measured.  The last line of a passing run is
``{"ok": true, "device": {...}}``; any failed check raises, so the exit code
is non-zero and that line is not printed.  The script refuses to run where
JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_REQUESTS = 8
NEW_TOKENS = 32
PROMPT_LENS = (16, 200)
BEST_MAP = ROOT / "reports" / "dse" / "best_map.json"


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Sums XLA's backend compile durations (seconds) as JAX reports them."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def _pallas_calls(jaxpr):
    """Pallas kernels a program holds, counted once per static call site;
    each kernel is staged twice (interpreted for the CPU, compiled for the
    chip: repro.device.pallas_call) and only the compiled one counts."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and not eqn.params["interpret"]:
            n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if hasattr(sub, "eqns"):
                    n += _pallas_calls(sub)
                elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    n += _pallas_calls(sub.jaxpr)
    return n


def kernels_compiled(name: str, fn, *args) -> None:
    """Check that every Pallas kernel of ``fn(*args)`` compiled to Mosaic."""
    import jax
    n_pallas = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    hlo = fn.lower(*args).compile().as_text()
    n_custom = hlo.count('custom_call_target="tpu_custom_call"')
    print(f"  {name}: {n_pallas} pallas kernels, {n_custom} tpu_custom_call",
          flush=True)
    check(n_pallas > 0 and n_custom == n_pallas,
          f"{name}: every Pallas kernel lowered to tpu_custom_call")


def smollm_config():
    from repro.configs import registry
    return dataclasses.replace(registry.get("smollm-135m"), quant="w8a8_ffn",
                               quant_kv=True)


def make_requests(cfg, seed):
    import numpy as np
    from repro.runtime.serving import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [Request(uid=i, prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(lens)]


def smollm_params(cfg, seed):
    import jax
    from repro.models import api as model_api
    return jax.jit(lambda k: model_api.init_params(cfg, k))(
        jax.random.key(seed))


ENGINE_KW = dict(capacity=N_REQUESTS, max_len=320, prefill_pad=128,
                 snapshot_every=8, multi_step=4, policy_map=str(BEST_MAP))


def serve(engine, reqs, strike=None):
    """Drain ``reqs`` through ``engine``; ``strike(engine)`` runs once, just
    before the first pump whose storage scrub follows a decode step."""
    engine.reset()
    for r in reqs:
        engine.submit(dataclasses.replace(r, output=[]))
    submitted = list(engine.queue)
    struck = strike is None
    every = engine.executor.storage_scrub_every
    while engine.executor.busy():
        if not struck and engine.stats.steps > 0 \
                and (engine.executor.tick + 1) % every == 0:
            strike(engine)
            struck = True
        engine.step()
    check(struck, "the strike landed mid-run")
    return [list(r.output) for r in submitted]


def serve_phase(seed, clock):
    import jax
    from repro.core import fault_injection as fi
    from repro.runtime.serving import Engine

    print("phase serve: smollm-135m, W8A8 FFN + int8 KV, best_map.json, "
          "Engine(multi_step=4)", flush=True)
    cfg = smollm_config()
    params = smollm_params(cfg, seed)
    reqs = make_requests(cfg, seed)
    print(f"  {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
          f"{cfg.vocab_size}; prompt lengths {[len(r.prompt) for r in reqs]}",
          flush=True)
    clock.lap()
    outputs, engines = {}, {}
    for backend in ("pallas", "jnp"):
        engines[backend] = Engine(cfg, params, backend=backend, **ENGINE_KW)
        outputs[backend] = serve(engines[backend], reqs)
        print(f"  {backend}: compile {clock.lap():.1f} s", flush=True)
    eng = engines["pallas"]
    check(all(len(o) == NEW_TOKENS for o in outputs["pallas"]),
          f"every request released {NEW_TOKENS} tokens")
    check(outputs["pallas"] == outputs["jnp"],
          "pallas tokens bit-identical to jnp")
    clean = eng.dependability_report()
    print(f"  clean pallas run: {clean}", flush=True)
    check(clean["checks_run"] > 0 and clean["faults_detected"] == 0,
          "clean run: scrubs ran, 0 detections")
    eng.executor.drain_state_events()

    def strike(engine):
        engine.strike("weights", fi.flip_one_bit, jax.random.key(seed + 1))

    struck = serve(eng, reqs, strike=strike)
    after = eng.dependability_report()
    events = eng.executor.drain_state_events()
    print(f"  struck run: {after}; events {events}", flush=True)
    check(after["faults_detected"] - clean["faults_detected"] >= 1,
          "weights SEU detected")
    check(any(e.get("site") == "weights" and e["recovered"] for e in events),
          "weights SEU healed (golden restore)")
    check(struck == outputs["pallas"], "struck stream bit-exact")
    kernels_compiled("decode step", eng.compiled[0], eng.params, eng.tokens,
                     eng.cache)
    toks = jax.numpy.zeros((1, ENGINE_KW["prefill_pad"]), jax.numpy.int32)
    kernels_compiled("prefill", eng.compiled[1], eng.params, toks)
    print(f"  lowering checks: compile {clock.lap():.1f} s", flush=True)


def shipdet_phase(seed, clock, n_frames=4, img=388):
    import jax
    import numpy as np
    from repro.core.dependability import Policy
    from repro.models import shipdet

    print(f"phase shipdet: {n_frames} frames {img}x{img}x3, ABFT every layer, "
          f"deploy-time weight checks", flush=True)
    specs = shipdet.network_specs()
    params = shipdet.init_params(specs, jax.random.key(seed))
    checks = shipdet.deploy_checks(params)
    frames = jax.random.uniform(jax.random.key(seed + 1),
                                (n_frames, img, img, 3))
    fwd = {be: jax.jit(lambda p, c, x, be=be: shipdet.forward(
        specs, p, x, policy=Policy.ABFT, backend=be, w_checks=c))
        for be in ("pallas", "jnp")}
    clock.lap()
    out = {}
    for be, f in fwd.items():
        y, stats = f(params, checks, frames)
        out[be] = (np.asarray(y), {k: int(v) for k, v in stats.items()})
        print(f"  {be}: out {y.shape}, stats {out[be][1]}, compile "
              f"{clock.lap():.1f} s", flush=True)
    y = out["pallas"][0]
    check(np.isfinite(y).all(), "finite detection map")
    check(np.array_equal(y, out["jnp"][0]), "pallas bit-identical to jnp")
    check(out["pallas"][1]["faults_detected"] == 0
          and out["pallas"][1]["checks_run"] == len(specs),
          "clean run: every layer checked, 0 detections")
    ref = np.asarray(jax.jit(lambda p, x: shipdet.float_forward(
        specs, p, x))(params, frames))
    step = float(params[-1]["out_scale"])
    err = float(np.abs(y - ref).max())
    print(f"  quantized vs float_forward: max abs {err:.6f} = "
          f"{err / step:.3f} output steps", flush=True)
    check(err < 4 * step, "within 4 quantization steps of float_forward")
    kernels_compiled("shipdet forward", fwd["pallas"], params, checks,
                     frames)
    print(f"  lowering check: compile {clock.lap():.1f} s", flush=True)


def devices_hold_memory(devices) -> None:
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    print(f"  bytes_in_use per device: {in_use}", flush=True)
    check(all(b > 0 for b in in_use), "every device holds memory")


def fleet_phase(seed, clock, n_replicas):
    import jax
    from repro.core.dependability import Policy
    from repro.fleet import Fleet

    print(f"phase fleet: {n_replicas} ABFT replicas of smollm-135m vs one",
          flush=True)
    cfg = smollm_config()
    params = smollm_params(cfg, seed)
    reqs = make_requests(cfg, seed)
    kw = dict(policy=Policy.ABFT, capacity=4, max_len=320, prefill_pad=128,
              backend="pallas", policy_map=str(BEST_MAP))
    clock.lap()
    streams = {}
    for n in (n_replicas, 1):
        fleet = Fleet(cfg, params, n_replicas=n, **kw)
        try:
            for r in reqs:
                fleet.submit(dataclasses.replace(r, output=[]))
            fleet.run()
            streams[n] = [list(fleet.released[r.uid].output) for r in reqs]
            homes = [{d.id for leaf in jax.tree_util.tree_leaves(
                rep.engine.params) for d in leaf.devices()}
                for rep in fleet.replicas]
            print(f"  {n} replica(s): released {len(fleet.released)}, "
                  f"replica devices {homes}, compile {clock.lap():.1f} s",
                  flush=True)
            if n == n_replicas:
                check(homes == [{d.id} for d in jax.devices()[:n]],
                      "replica i holds its params on device i")
                devices_hold_memory(jax.devices()[:n])
        finally:
            fleet.close()
    check(all(len(s) == NEW_TOKENS for s in streams[1]),
          f"every request released {NEW_TOKENS} tokens")
    check(streams[n_replicas] == streams[1],
          f"{n_replicas}-replica streams identical to one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replica fleet, one replica a chip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro import device
    cache = device.enable_compile_cache()
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}; compile cache {cache}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        fleet_phase(args.seed, clock, n_replicas=4)
    else:
        serve_phase(args.seed, clock)
        shipdet_phase(args.seed, clock)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s wall "
          f"(compile included)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
