"""Benchmark entrypoint: one function per paper table/figure + framework
benches.  Prints ``name,value,derived`` CSV lines and human-readable tables.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Sections:
  table1   — paper Table 1 / Figure 5 reproduction (+ kernel correctness)
  roofline — three-term roofline per dry-run artifact (§Roofline)
  kernels  — CPU wall-clock of the jnp oracles + interpret-mode kernels
             (correctness-bearing; CPU wall time is not a TPU latency claim)
  serving  — continuous-batching engine throughput on a reduced config
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro import device


def bench_table1(check: bool = True):
    print("\n=== Table 1 / Figure 5: conv+requant latency (paper repro) ===")
    import benchmarks.table1_conv as t1
    rows = []
    for s in t1.TABLE1_LAYERS:
        hp, gp = t1.PAPER_HPDP_MS[s.name], t1.PAPER_GR740_MS[s.name]
        tm = t1.tpu_model_ms(s)
        rows.append((s.name, hp, gp, gp / hp, tm))
        print(f"table1,{s.name},paper_hpdp_ms={hp},paper_gr740_ms={gp},"
              f"speedup={gp/hp:.0f}x,tpu_model_ms={tm:.4f}")
    if check:
        ok = t1.correctness_check()
        print(f"table1,correctness,{ok}")
        assert ok
    return rows


def bench_roofline():
    print("\n=== Roofline (from dry-run artifacts) ===")
    from benchmarks import roofline as rl
    rows = rl.load_all()
    if not rows:
        print("roofline,SKIPPED,no artifacts (run repro.launch.dryrun --all)")
        return []
    for r in rows:
        print(f"roofline,{r['cell']},bottleneck={r['bottleneck']},"
              f"t_bound_s={r['t_bound_s']:.4g},useful={r['useful_ratio']:.3f},"
              f"frac={r['roofline_fraction']:.4f}")
    return rows


def _time(f, *args, reps=3):
    f(*args)                      # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    try:
        out.block_until_ready()
    except AttributeError:
        pass
    return (time.perf_counter() - t0) / reps * 1e6   # us


def bench_kernels(fast: bool = False):
    print("\n=== Kernel microbenches (CPU oracle wall time; correctness-bearing) ===")
    import jax
    import jax.numpy as jnp
    from repro.kernels.qmatmul.kernel import qmatmul
    from repro.kernels.qmatmul.ref import qmatmul_ref
    from repro.kernels.flashattn.kernel import flash_attention
    from repro.kernels.flashattn.ref import attention_ref

    rng = np.random.default_rng(0)
    m = 64 if fast else 256
    x = jnp.asarray(rng.integers(-128, 128, (m, 256)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (256, 128)), jnp.int8)
    colsum = jnp.sum(w.astype(jnp.int32), axis=0)
    bias = jnp.zeros((128,), jnp.int32)
    scale = jnp.full((128,), 1e-3, jnp.float32)
    zps = jnp.asarray([0, 0], jnp.int32)

    t_ref = _time(jax.jit(lambda: qmatmul_ref(x, jnp.int32(0), w, bias,
                                              scale, jnp.int32(0))))
    print(f"kernels,qmatmul_ref_{m}x256x128,us_per_call={t_ref:.0f}")
    t_int = _time(lambda: qmatmul(x, w, colsum, bias, scale, zps))
    print(f"kernels,qmatmul_interpret_{m}x256x128,us_per_call={t_int:.0f},"
          f"derived=interpreter_overhead_{t_int/max(t_ref,1):.0f}x")

    S = 128 if fast else 256
    q = jnp.asarray(rng.standard_normal((1, 4, S, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, S, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, S, 32)), jnp.float32)
    t_ref = _time(jax.jit(lambda: attention_ref(q, k, v)))
    print(f"kernels,flashattn_ref_S{S},us_per_call={t_ref:.0f}")
    t_int = _time(lambda: flash_attention(q, k, v,
                                          block_q=64, block_k=64))
    print(f"kernels,flashattn_interpret_S{S},us_per_call={t_int:.0f}")


def bench_serving(fast: bool = False):
    print("\n=== Serving engine throughput (reduced config, CPU) ===")
    import jax
    from repro.configs import registry
    from repro.models import api as model_api
    from repro.models.config import reduced
    from repro.runtime.serving import Engine, Request

    cfg = reduced(registry.get("smollm-135m"))
    params = model_api.init_params(cfg, jax.random.key(0))
    n_req = 4 if fast else 8
    eng = Engine(cfg, params, capacity=4, max_len=128, prefill_pad=16)
    rng = np.random.default_rng(0)
    for i in range(n_req):
        eng.submit(Request(uid=i,
                           prompt=rng.integers(1, 200, size=5).tolist(),
                           max_new_tokens=8))
    t0 = time.perf_counter()
    stats = eng.run()
    dt = time.perf_counter() - t0
    print(f"serving,reduced_smollm,tokens={stats.tokens_out},"
          f"tok_per_s={stats.tokens_out/dt:.1f},"
          f"tokens_per_step={stats.tokens_per_step():.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    device.enable_compile_cache()

    t0 = time.time()
    bench_table1(check=True)
    bench_roofline()
    bench_kernels(fast=args.fast)
    bench_serving(fast=args.fast)
    print(f"\nbenchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
