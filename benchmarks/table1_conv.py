"""Paper Table 1 reproduction: quantized conv+requant single-layer latency.

The paper benchmarks 4 Ship-Detection conv layers on the HPDP (rad-hard
dataflow co-processor, 250 MHz) vs the GR740 (rad-hard LEON4, 250 MHz) and
reports HPDP 112×–660× faster.  We reproduce the comparison three ways:

  1. **Paper's own numbers** (measured, Table 1) — the claims we validate.
  2. **Analytic device models** from first principles — a dataflow model of
     the HPDP (40 ALU-PAEs, one MAC/PAE/cycle, stream-limited) and a scalar
     model of the GR740 (LEON4 in-order, ~1 MAC / 8 cycles effective) — to
     confirm the *magnitudes* of the paper's measurements are consistent
     with the architectures (validation per §EXPERIMENTS).
  3. **Our TPU backend** — the same layers through the qconv2d Pallas kernel
     design: modeled v5e latency (int8 roofline: max(MACs·2/394T, bytes/819G))
     plus measured-for-correctness execution (interpret mode vs the oracle,
     which proves the kernel computes the right thing; wall time on the CPU
     interpreter is NOT a latency claim).

``--bit-sweep`` runs the campaign engine's per-bit accumulator sweep at
(reduced) Table-1 layer geometry: every int32 accumulator bit position is
flipped ``--bit-trials`` times under none and abft, classifying which bits
the requantization rescale masks and which the ABFT checksum catches.  The
report lands under ``reports/table1_bitsweep/``.

Usage: PYTHONPATH=src python -m benchmarks.table1_conv [--check] [--bit-sweep]
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List

import numpy as np

from repro import device
from repro.models.shipdet import TABLE1_LAYERS, ConvSpec

# Paper Table 1 (ms)
PAPER_HPDP_MS = {"conv_24x3x3x24": 121.27, "conv_48x3x3x48": 110.94,
                 "conv_96x3x3x96": 104.84, "conv_96x1x1x96": 47.44}
PAPER_GR740_MS = {"conv_24x3x3x24": 23894.08, "conv_48x3x3x48": 23731.64,
                  "conv_96x3x3x96": 11765.59, "conv_96x1x1x96": 31320.04}

# --- analytic device models ---------------------------------------------
HPDP_CLOCK = 250e6
HPDP_MACS_PER_CYCLE = 40 * 0.35     # 40 ALU-PAEs, ~35% stream efficiency
                                    # (fitted once on layer 1, applied to all)
GR740_CLOCK = 250e6
GR740_CYCLES_PER_MAC = 14           # in-order SPARC V8: ld/ld/mul/add/st + loop
                                    # overhead on int8→int32 MAC (fitted layer 1)

TPU_INT8_FLOPS = 394e12
TPU_HBM_BW = 819e9


def hpdp_model_ms(s: ConvSpec) -> float:
    return s.macs / (HPDP_CLOCK * HPDP_MACS_PER_CYCLE) * 1e3


def gr740_model_ms(s: ConvSpec) -> float:
    return s.macs * GR740_CYCLES_PER_MAC / GR740_CLOCK * 1e3


def tpu_model_ms(s: ConvSpec) -> float:
    flops = 2 * s.macs
    bytes_ = (s.h * s.w * s.cin            # int8 activations in
              + s.kh * s.kw * s.cin * s.cout
              + s.h * s.w * s.cout // (s.stride ** 2)
              + 4 * s.cout * 3)            # bias/scale/colsum
    return max(flops / TPU_INT8_FLOPS, bytes_ / TPU_HBM_BW) * 1e3


def correctness_check() -> bool:
    """Kernel-under-interpreter vs oracle on (reduced) Table-1 geometry."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.kernels.qconv2d import ops, ref

    rng = np.random.default_rng(0)
    ok = True
    for s in TABLE1_LAYERS:
        r = dataclasses.replace(s, h=max(s.h // 8, 8), w=max(s.w // 8, 8))
        x_q = jnp.asarray(rng.integers(-128, 128, (1, r.h, r.w, r.cin)), jnp.int8)
        w_q = jnp.asarray(rng.integers(-127, 128, (r.kh, r.kw, r.cin, r.cout)), jnp.int8)
        colsum = jnp.sum(w_q.astype(jnp.int32), axis=(0, 1, 2))
        bias = jnp.asarray(rng.integers(-500, 500, (r.cout,)), jnp.int32)
        scale = jnp.asarray(rng.uniform(1e-4, 1e-2, (r.cout,)).astype(np.float32))
        x_zp = jnp.int32(3)
        out_zp = jnp.int32(-2)
        got = ops.qconv2d_op(x_q, x_zp, w_q, colsum, bias, scale, out_zp,
                             use_kernel=True)
        want = ref.qconv2d_ref(x_q, x_zp, w_q, bias, scale, out_zp)
        same = np.array_equal(np.asarray(got), np.asarray(want))
        print(f"  {s.name:<18} reduced {r.h}x{r.w}: kernel==oracle: {same}")
        ok &= same
    return ok


# (layer, reduced geometry) pairs for the --bit-sweep mode: the first and
# last Table-1 layers, spatially shrunk so the vmapped sweep compiles fast
# while keeping the layer's channel/kernel shape (what the checksum sees)
BIT_SWEEP_GEOMETRIES = [
    ("qconv2d_t1_conv1", dict(h=24, w=24, cin=24, cout=24, kh=3, kw=3)),
    ("qconv2d_t1_conv4", dict(h=12, w=12, cin=96, cout=96, kh=1, kw=1)),
]


def bit_sweep(trials_per_bit: int, seed: int, out_dir: str) -> int:
    """Per-bit accumulator fault sweep at Table-1 conv geometry."""
    import jax
    from repro.campaign import stats as stats_mod
    from repro.campaign.report import write_report
    from repro.campaign.runner import QConv2dCase, run_bit_sweep
    from repro.core.dependability import Policy

    plan = stats_mod.SamplingPlan(ci_halfwidth=0.05, min_trials=4, chunk=4)
    rows = []
    for label, geom in BIT_SWEEP_GEOMETRIES:
        case = QConv2dCase(jax.random.key(seed), **geom)
        rows += run_bit_sweep(label, [Policy.NONE, Policy.ABFT],
                              trials_per_bit=trials_per_bit, seed=seed,
                              case=case, plan=plan)
        print(f"{label}: swept 32 bits × ≤{trials_per_bit} trials "
              f"× 2 policies", flush=True)
    meta = {
        "bench": "table1_bitsweep",
        "seed": seed,
        "trials_per_bit": trials_per_bit,
        "geometries": {label: geom for label, geom in BIT_SWEEP_GEOMETRIES},
        "plan": {"ci_halfwidth": plan.ci_halfwidth,
                 "min_trials": plan.min_trials, "chunk": plan.chunk},
    }
    jpath, mpath = write_report([], out_dir, meta, basename="table1_bitsweep",
                                bit_coverage=rows)
    sdc_bits = {}
    for r in rows:
        if r.sdc > 0:
            sdc_bits.setdefault((r.workload, r.policy), []).append(r.bit)
    for (wl, pol), bits in sorted(sdc_bits.items()):
        print(f"  {wl}/{pol}: SDC at bits {bits}")
    abft_sdc = sum(r.sdc for r in rows if r.policy == "abft")
    print(f"abft residual SDC across all bits: {abft_sdc}")
    print(f"wrote {jpath} and {mpath}")
    return 1 if abft_sdc else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="also run kernel-vs-oracle correctness on each layer")
    ap.add_argument("--bit-sweep", action="store_true",
                    help="per-bit accumulator SEU sweep at Table-1 geometry "
                         "(writes reports/table1_bitsweep/)")
    ap.add_argument("--bit-trials", type=int, default=8,
                    help="fault injections per bit position per policy")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="reports/table1_bitsweep",
                    help="output directory for the --bit-sweep report")
    args = ap.parse_args()
    device.enable_compile_cache()

    if args.bit_sweep:
        raise SystemExit(bit_sweep(args.bit_trials, args.seed, args.out))

    hdr = (f"{'layer':<18} {'MACs':>9} | {'HPDP ms':>9} {'model':>8} "
           f"{'GR740 ms':>10} {'model':>9} | {'speedup':>7} {'model':>6} "
           f"| {'TPU-v5e ms':>10} {'vs HPDP':>8}")
    print(hdr)
    print("-" * len(hdr))
    rows = []
    for s in TABLE1_LAYERS:
        hp, gp = PAPER_HPDP_MS[s.name], PAPER_GR740_MS[s.name]
        hm, gm = hpdp_model_ms(s), gr740_model_ms(s)
        tm = tpu_model_ms(s)
        rows.append((s.name, s.macs, hp, hm, gp, gm, gp / hp, gm / hm, tm, hp / tm))
        print(f"{s.name:<18} {s.macs/1e6:8.1f}M | {hp:9.2f} {hm:8.2f} "
              f"{gp:10.2f} {gm:9.2f} | {gp/hp:6.0f}× {gm/hm:5.0f}× "
              f"| {tm:10.4f} {hp/tm:7.0f}×")

    # paper-claim validation (the EXPERIMENTS.md §Paper-validation numbers)
    speedups = [r[6] for r in rows]
    print(f"\npaper claim: HPDP beats GR740 on every layer "
          f"({min(speedups):.0f}×–{max(speedups):.0f}×): "
          f"{'CONFIRMED' if min(speedups) > 1 else 'FAILED'}")
    mods = [abs(np.log10(r[3] / r[2])) for r in rows] + \
           [abs(np.log10(r[5] / r[4])) for r in rows]
    print(f"analytic models within {10**max(mods):.1f}× of all paper "
          f"measurements (order-of-magnitude consistency)")

    if args.check:
        print("\ncorrectness (kernel interpret vs jnp oracle, reduced geometry):")
        ok = correctness_check()
        print(f"  all layers exact: {ok}")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
