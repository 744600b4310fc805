"""Dependability-policy overhead + adaptive-campaign bench.

Measures the steady-state cost of each policy on the quantized matmul and
conv primitives (the Safe-NEureka-style hybrid-redundancy comparison: how
much throughput does each protection level buy its coverage with), the
campaign engine's trial rate per workload, and the headline speedup of the
adaptive engine: a sequential-sampling campaign reaching the same verdicts
as a fixed-budget one at equal CI precision, in a fraction of the trials.
``--backends jnp,pallas`` benchmarks the FPGA/VPU-style same-workload
cross-backend comparison; the pallas numbers are interpreter wall-clock off
TPU, so only the jnp rows are throughput claims there.

    PYTHONPATH=src python -m benchmarks.campaign_bench [--fast] \
        [--out BENCH_campaign.json]

Prints ``campaign_bench,<name>,<key>=<val>,...`` CSV-ish lines like the
other benches and writes the committed summary JSON to ``--out``.  CPU
wall-clock: relative overhead / trial-count ratios are the signal,
absolute latency is not a TPU claim.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import device
from repro.core.dependability import Policy, dependable_qconv2d, dependable_qmatmul


def _time(f, *args, reps: int = 20):
    out = f(*args)                      # compile
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / reps


# every policy, so selective-hardening consumers (the DSE cost oracle) and
# the printed table read one number set — not the markdown-era NONE/ABFT/TMR
# subset
BENCH_POLICIES = (Policy.NONE, Policy.ABFT, Policy.DMR, Policy.TMR,
                  Policy.CKPT)


def bench_policy_overhead(m=256, k=512, n=256, reps=20, backends=("jnp",)):
    """Per-policy qmatmul cost; returns machine-readable rows (one dict per
    backend × policy) that main() embeds verbatim in the summary JSON."""
    print(f"\n=== policy overhead: qmatmul ({m}x{k}x{n} int8) ===")
    rng = np.random.default_rng(0)
    x_q = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int32), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int32), jnp.int8)
    bias = jnp.asarray(rng.integers(-500, 500, (n,), dtype=np.int32))
    scale = jnp.full((n,), 1e-3, jnp.float32)
    zp = jnp.int32(0)

    rows = []
    for backend in backends:
        base = None
        for policy in BENCH_POLICIES:
            f = jax.jit(lambda xq, wq, p=policy, be=backend: dependable_qmatmul(
                p, xq, zp, wq, bias, scale, zp, backend=be)[0])
            t = _time(f, x_q, w_q, reps=reps)
            base = base or t
            gmacs = m * k * n / t / 1e9
            rows.append({"backend": backend, "policy": policy.value,
                         "ms": round(t * 1e3, 4),
                         "overhead_x": round(t / base, 3),
                         "gmacs": round(gmacs, 2)})
            print(f"campaign_bench,qmatmul_policy={policy.value},"
                  f"backend={backend},ms={t * 1e3:.3f},"
                  f"overhead_x={t / base:.2f},gmacs={gmacs:.2f}")
    return rows


def bench_conv_policy_overhead(h=32, w=32, cin=32, cout=32, reps=10,
                               backends=("jnp",)):
    print(f"\n=== policy overhead: qconv2d ({h}x{w}x{cin}->{cout} 3x3) ===")
    rng = np.random.default_rng(1)
    x_q = jnp.asarray(rng.integers(-128, 128, (1, h, w, cin), dtype=np.int32), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (3, 3, cin, cout), dtype=np.int32), jnp.int8)
    bias = jnp.asarray(rng.integers(-100, 100, (cout,), dtype=np.int32))
    scale = jnp.full((cout,), 1e-3, jnp.float32)
    zp = jnp.int32(0)

    rows = []
    for backend in backends:
        base = None
        for policy in BENCH_POLICIES:
            f = jax.jit(lambda xq, wq, p=policy, be=backend: dependable_qconv2d(
                p, xq, zp, wq, bias, scale, zp, backend=be)[0])
            t = _time(f, x_q, w_q, reps=reps)
            base = base or t
            rows.append({"backend": backend, "policy": policy.value,
                         "ms": round(t * 1e3, 4),
                         "overhead_x": round(t / base, 3)})
            print(f"campaign_bench,qconv2d_policy={policy.value},"
                  f"backend={backend},ms={t * 1e3:.3f},"
                  f"overhead_x={t / base:.2f}")
    return rows


def bench_trial_rate(trials=200, workloads=("qmatmul", "serving"), cache=None):
    """Trials/s per workload: the kernel path amortizes across one vmapped
    XLA call; the host-side serving path is one engine run per trial."""
    from repro.campaign import CampaignSpec, run_campaign
    out = {}
    cache = {} if cache is None else cache
    for workload in workloads:
        site = "accumulator" if workload == "qmatmul" else "kv_cache"
        n = trials if workload == "qmatmul" else max(trials // 4, 10)
        print(f"\n=== campaign trial rate: {workload} ({n} trials/config) ===")
        specs = [CampaignSpec(workload, p, site, "single_bitflip", n, seed=0)
                 for p in (Policy.NONE, Policy.ABFT)]
        run_campaign(specs[:1], cache=cache)      # warm build + compile
        t0 = time.perf_counter()
        results = run_campaign(specs, cache=cache)
        dt = time.perf_counter() - t0
        total = sum(r.trials for r in results)
        rate = total / dt
        print(f"campaign_bench,trial_rate,workload={workload},trials={total},"
              f"seconds={dt:.2f},trials_per_s={rate:.1f}")
        out[workload] = {"trials": total, "seconds": round(dt, 3),
                         "trials_per_s": round(rate, 1)}
    return out


def bench_adaptive_vs_fixed(trials=100, ci_halfwidth=0.1, cache=None):
    """The adaptive engine's headline: equal-precision verdicts, fewer
    trials.  Both runs execute prefixes of the same key stream, so the
    adaptive run's verdict is a true early decision, not a reseed."""
    from repro.campaign import CampaignSpec, SamplingPlan, run_campaign
    print(f"\n=== adaptive vs fixed: serving/abft/kv_cache "
          f"(cap {trials}, target halfwidth {ci_halfwidth:g}) ===")
    spec = CampaignSpec("serving", Policy.ABFT, "kv_cache",
                        "single_bitflip", trials, seed=0)
    cache = {} if cache is None else cache
    run_campaign([CampaignSpec("serving", Policy.ABFT, "kv_cache",
                               "single_bitflip", 2, seed=0)], cache=cache)

    t0 = time.perf_counter()
    fixed = run_campaign([spec], cache=cache)[0]
    fixed_s = time.perf_counter() - t0

    plan = SamplingPlan(ci_halfwidth=ci_halfwidth, chunk=25, min_trials=25)
    t0 = time.perf_counter()
    adaptive = run_campaign([spec], plan=plan, cache=cache)[0]
    adaptive_s = time.perf_counter() - t0

    trial_speedup = fixed.trials / max(adaptive.trials, 1)
    wall_speedup = fixed_s / max(adaptive_s, 1e-9)
    verdict_match = (adaptive.sdc_rate == fixed.sdc_rate == 0.0
                     and adaptive.detection_rate == fixed.detection_rate)
    print(f"campaign_bench,adaptive_vs_fixed,fixed_trials={fixed.trials},"
          f"adaptive_trials={adaptive.trials},"
          f"trial_speedup={trial_speedup:.2f},wall_speedup={wall_speedup:.2f},"
          f"verdict_match={verdict_match},"
          f"adaptive_sdc_ci_hi={adaptive.sdc_ci_hi:.4f}")
    return {
        "workload": spec.workload, "policy": spec.policy.value,
        "site": spec.site, "fault_model": spec.fault_model,
        "ci_halfwidth": ci_halfwidth, "confidence": plan.confidence,
        "ci_method": plan.ci_method,
        "fixed": {"trials": fixed.trials, "seconds": round(fixed_s, 3),
                  "sdc_rate": fixed.sdc_rate,
                  "detection_rate": fixed.detection_rate},
        "adaptive": {"trials": adaptive.trials,
                     "seconds": round(adaptive_s, 3),
                     "sdc_rate": adaptive.sdc_rate,
                     "detection_rate": adaptive.detection_rate,
                     "sdc_ci_hi": round(adaptive.sdc_ci_hi, 6),
                     "early_stopped": adaptive.early_stopped},
        "trial_speedup": round(trial_speedup, 2),
        "wall_speedup": round(wall_speedup, 2),
        "verdict_match": verdict_match,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--backends", default="jnp",
                    help="comma list of execution backends to compare "
                         "(jnp, ref, pallas)")
    ap.add_argument("--out", default="BENCH_campaign.json",
                    help="summary JSON path ('' skips writing)")
    args = ap.parse_args(argv)
    device.enable_compile_cache()
    backends = tuple(b.strip() for b in args.backends.split(",") if b.strip())
    reps = 5 if args.fast else 20
    qm_shape = (256, 512, 256)
    conv_shape = (32, 32, 32, 32)
    qm_rows = bench_policy_overhead(*qm_shape, reps=reps, backends=backends)
    conv_rows = bench_conv_policy_overhead(
        *conv_shape, reps=max(reps // 2, 3), backends=backends)
    cache = {}
    rates = bench_trial_rate(trials=50 if args.fast else 200, cache=cache)
    adaptive = bench_adaptive_vs_fixed(trials=50 if args.fast else 100,
                                       cache=cache)
    if args.out:
        doc = {
            "bench": "campaign",
            "fast": bool(args.fast),
            # the per-policy overhead tables the printed CSV shows, as JSON
            # — the DSE cost oracle (repro/dse/cost.py) and humans read the
            # same numbers
            "policy_overhead": {
                "qmatmul": {"shape_mkn": list(qm_shape), "rows": qm_rows},
                "qconv2d": {"shape_hwcc": list(conv_shape),
                            "rows": conv_rows},
            },
            "trial_rate": rates,
            "adaptive_vs_fixed": adaptive,
        }
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
