"""Frame cells: back-to-back batches of tiles through the ship detector,
ABFT on every layer with deploy-time weight checks, on one chip.

Set-up makes the weights and the tile pool on the device from the seed,
computes the deploy-time checks, and compiles the one batch shape.  In the
window one client sends a batch, reads its detection map and ABFT stats
back to the host, and sends the next.  Afterwards a sample of the window's
batches drawn from the seed is compared with the float32 reference
(``reference/shipdet.py``) over all layers, in output quantization steps.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import harness
from harness import annotate


def program_params(weights: list):
    """The bench's weights in the program's per-layer bundle."""
    from repro.kernels.qconv2d.ops import QConvParams
    return [{"qconv": QConvParams(w["w_q"], w["w_scale"], w["colsum"],
                                  w["bias_f"]),
             "in_scale": w["in_scale"], "in_zp": w["in_zp"],
             "out_scale": w["out_scale"], "out_zp": w["out_zp"]}
            for w in weights]


def program_specs(cfg: dict, tile: int):
    """The program's ConvSpec list for the configuration (geometry as it
    runs from ``tile``)."""
    from repro.models.shipdet import ConvSpec
    import opcount
    out, side = [], tile
    for s in cfg["layers"]:
        out.append(ConvSpec(s["name"], s["kh"], s["kw"], s["cin"], s["cout"],
                            side, side, s["stride"]))
        side = opcount.conv_out(side, s["stride"])
    return out


class Setup:
    """The cell's settings, the weights and the tile pool made from the
    seed, and the forward program compiled for the one batch shape."""

    def __init__(self, cell, seed: int, rehearse: bool):
        import jax
        from repro.core.dependability import Policy
        from repro.models import shipdet
        from reference import shipdet as ref

        spec, traffic = dict(cell.spec), dict(cell.traffic)
        if rehearse:
            reh = dict(spec.get("rehearsal", {}))
            spec["check"] = dict(spec["check"], **reh.pop("check", {}))
            traffic.update(reh)
        self.spec, self.traffic, self.cfg = spec, traffic, cell.config
        ss = np.random.SeedSequence(seed % 2 ** 64)
        k_weights, k_pool, self.k_order, self.k_sample = ss.spawn(4)

        def key(s):
            return jax.random.key(int(s.generate_state(1)[0] & 0x7FFFFFFF))

        t0 = time.perf_counter()
        self.weights = ref.make_weights(self.cfg, key(k_weights))
        self.params = program_params(self.weights)
        specs = program_specs(self.cfg, traffic["tile"])
        self.checks = shipdet.deploy_checks(self.params)
        self.gen = harness.load_module("traffic", traffic["generator"])
        pool = self.gen.make_pool(traffic, key(k_pool))
        self.scenes = [pool[s] for s in range(traffic["scenes"])]
        backend = self.cfg["serving"]["backend"]

        def shipdet_forward(p, c, x):
            return shipdet.forward(specs, p, x, policy=Policy.ABFT,
                                   backend=backend, w_checks=c)

        self.fwd = jax.jit(shipdet_forward)
        self.n_layers = len(specs)
        t1 = time.perf_counter()
        for x in self.scenes[:2]:            # compile, then one warm call
            jax.block_until_ready(self.fwd(self.params, self.checks, x))
        self.phases = {"weights_pool_checks_s": t1 - t0,
                       "compile_and_warm_s": time.perf_counter() - t1}

    def reference_steps(self, scene: int, y, lower: bool = False) -> float:
        """Widest difference of ``y`` (the program's map of ``scene``, or
        with ``lower`` the control's) from the reference, in output steps."""
        from reference import shipdet as ref
        x = self.scenes[scene]
        want = np.asarray(ref.forward(self.cfg, self.weights, x))
        if lower:
            y = ref.forward(self.cfg, self.weights, x, lower=True)
        return float(np.abs(np.asarray(y) - want).max()) \
            / self.cfg["activation_scale"]


def steps_check(su: Setup, worst: float, compared: int) -> dict:
    """The cell's comparison of the widest output difference, in steps,
    with its limit."""
    limit = su.spec["check"]["max_output_steps"]
    return {"name": "max_output_steps", "value": worst, "limit": limit,
            "ok": compared > 0 and worst <= limit}


def run(cell, args, devs, clock, t_start, rehearse: bool) -> dict:
    import jax

    su = Setup(cell, args.seed, rehearse)
    spec, traffic = su.spec, su.traffic
    params, checks, scenes, fwd = su.params, su.checks, su.scenes, su.fwd
    batch = traffic["tiles_per_scene"]
    seconds = args.seconds
    order = su.gen.order(traffic, np.random.default_rng(su.k_order))
    # which occurrence of each scene is compared: drawn from the seed
    keep_occ = np.random.default_rng(su.k_sample).integers(
        0, spec["check"]["occurrence_below"], size=traffic["scenes"])
    seen = np.zeros(traffic["scenes"], np.int64)
    profiler = harness.Profiler(f"{cell.name}-{args.seed}") if args.trace \
        else None
    trace_s = min(spec["trace_seconds"], seconds)
    kept, detected, checked, frames, n = {}, 0, 0, 0, 0
    compiles0 = clock.count
    t_open = time.perf_counter()
    t_close = t_open + seconds
    tracing = False
    summary = None
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if profiler and not tracing and now >= t_close - trace_s:
            profiler.start()
            tracing = True
        s = int(order[n % len(order)])
        with annotate("shipdet.forward"):
            y, st = fwd(params, checks, scenes[s])
        with annotate("shipdet.readback"):
            y_host = np.asarray(y)
            st_host = jax.device_get(st)
        if time.perf_counter() <= t_close:
            frames += batch
        detected += int(st_host["faults_detected"])
        checked += int(st_host["checks_run"])
        if seen[s] <= keep_occ[s]:
            kept[s] = y_host              # the drawn occurrence, or the last
        seen[s] += 1
        n += 1
    compiles = clock.count - compiles0
    if profiler:
        profiler.stop()
        summary = profiler.summary()
    peak = harness.memory_peak_bytes(devs)
    del fwd, params, checks
    su.fwd = su.params = su.checks = None
    gc.collect()

    # ---------------------------------------------------------- correctness
    worst = max((su.reference_steps(s, y_host) for s, y_host in kept.items()),
                default=0.0)
    out_checks = [
        steps_check(su, worst, len(kept)),
        {"name": "scenes_compared", "value": len(kept),
         "limit": traffic["scenes"], "ok": len(kept) == traffic["scenes"]},
        {"name": "detections", "value": detected, "limit": 0,
         "ok": detected == 0},
        {"name": "layers_checked_per_batch", "value": checked / max(n, 1),
         "limit": su.n_layers, "ok": checked == n * su.n_layers},
    ]
    return {"attempted": n * batch, "failed": 0,
            "metrics": {"setup_s": t_open - t_start,
                        "frames_per_s": frames / seconds},
            "checks": out_checks, "memory_peak_bytes": peak,
            "counters": {"frames": frames, "batches": n, "batch": batch},
            "trace": summary, "trace_window_s": trace_s,
            "extra": {"compiles_in_window": compiles,
                      "setup_phases": su.phases}}
