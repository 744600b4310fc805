"""Continuous batching vs pad-and-step: the streaming-executor benchmark.

Serves one mixed-length request trace two ways and compares:

  * **streamed** — the staged dataflow engine (runtime/dataflow.py):
    requests join and leave the slotted decode batch mid-flight, so a slot
    freed by a short request is refilled while its neighbors keep decoding.
  * **padded** — the monolith-equivalent pad-and-step baseline: the same
    engine with ``drain_barrier=True``, so a group of ``capacity`` requests
    is admitted, decoded until *every* member has its full token budget,
    and only then is the next group admitted.  Short requests idle their
    slot for the group's max — exactly the barrier the staged pipeline
    removes.

Both paths run the identical jitted per-step decode, prefill machinery, and
host loop over the same fixed batch width — the only difference is the
admission policy — so the tokens/s ratio prices continuous batching itself
(batch occupancy), which is the paper's streaming-throughput claim at
serving granularity.  ``--check-bit-identity`` additionally verifies the
streamed outputs against the plain greedy reference — continuous batching
must never change tokens.

    PYTHONPATH=src python -m benchmarks.serving_bench --requests 24 \
        --out BENCH_serving.json

Writes tokens/s, mean batch occupancy, and p50/p99 release latency for both
paths plus the speedup ratio to ``--out`` (default: BENCH_serving.json at
the repo root).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import device
from repro.configs import registry
from repro.models import api as model_api
from repro.models.config import reduced
from repro.runtime.serving import Engine, Request


def make_trace(cfg, n_requests: int, seed: int):
    """Mixed-length trace: short prompts, heavy-tailed token budgets (the
    serving-realistic shape that punishes a drain barrier most — every
    static group inherits its longest member's budget while the short
    majority idles)."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n_requests):
        prompt = rng.integers(1, cfg.vocab_size, size=int(
            rng.integers(3, 8))).tolist()
        max_new = int(rng.choice([4, 6, 8, 64]))
        trace.append((prompt, max_new))
    return trace


def greedy_reference(cfg, params, prompt, n_new, max_len):
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache = model_api.prefill(cfg, params, toks, max_len)
    out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    tok = jnp.asarray([out[-1]], jnp.int32)
    for _ in range(n_new - 1):
        logits, cache = model_api.decode_step(cfg, params, tok, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    return out


def _latency_stats(latencies):
    if not latencies:
        return {"p50_latency_s": 0.0, "p99_latency_s": 0.0}
    arr = np.asarray(latencies)
    return {"p50_latency_s": round(float(np.percentile(arr, 50)), 4),
            "p99_latency_s": round(float(np.percentile(arr, 99)), 4)}


def run_engine(cfg, params, trace, capacity, max_len, prefill_pad,
               drain_barrier=False, compiled=None, multi_step=1,
               tracer=None, metrics=None, policy_map=None):
    """Serve the trace through the staged engine (continuous batching, or
    the pad-and-step baseline under ``drain_barrier``); returns
    (report, reqs, compiled-pair).  ``policy_map`` engages the per-site
    dependability policies (in-graph FFN hardening + the engine's derived
    scrub schedules) — mapped engines compile their own decode graphs, so
    never share ``compiled`` across different maps."""
    eng = Engine(cfg, params, capacity=capacity, max_len=max_len,
                 prefill_pad=prefill_pad, drain_barrier=drain_barrier,
                 compiled=compiled, multi_step=multi_step,
                 tracer=tracer, metrics=metrics, policy_map=policy_map)

    def serve():
        eng.reset()
        reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
                for i, (p, n) in enumerate(trace)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return reqs

    serve()                                         # warmup / compile
    dt = float("inf")
    for _ in range(3):                              # best-of-3: shed noise
        t0 = time.perf_counter()
        reqs = serve()
        dt = min(dt, time.perf_counter() - t0)
    tokens = sum(len(r.output) for r in reqs)
    report = {
        "tokens": tokens,
        "decode_steps": eng.stats.steps,
        "wall_s": round(dt, 4),
        "tokens_per_s": round(tokens / dt, 1),
        "occupancy": round(eng.stats.tokens_per_step() / capacity, 4),
        **_latency_stats([r.t_release - r.t_submit for r in reqs]),
    }
    return report, reqs, eng.compiled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.serving_bench")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-pad", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multi-step", type=int, default=4,
                    help="decode-dispatch window: steps decoded on device "
                         "per host readback (1 = per-step dispatch)")
    ap.add_argument("--quant-kv", action="store_true",
                    help="serve with the int8-quantized KV cache "
                         "(ArchConfig.quant_kv)")
    ap.add_argument("--check-bit-identity", action="store_true",
                    help="also verify streamed outputs == greedy reference "
                         "(slow: one reference decode per request)")
    ap.add_argument("--policy-map", default=None, metavar="JSON",
                    help="selective-hardening comparison: serve the trace "
                         "on the W8A8 FFN path under this per-site policy "
                         "map (path or inline JSON, e.g. "
                         "reports/dse/best_map.json), against the "
                         "uniform-ABFT and unprotected corners — reports "
                         "the mapped-vs-uniform speedup and asserts all "
                         "three decode streams are bit-identical "
                         "(docs/dse.md)")
    ap.add_argument("--trace-out", default=None,
                    help="re-serve the streamed trace with span tracing on "
                         "and write the Chrome trace_event JSON; also "
                         "reports trace_overhead_frac vs the untraced run")
    ap.add_argument("--metrics-out", default=None,
                    help="write the traced run's metrics registry snapshot "
                         "(.prom extension → Prometheus text format)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)
    device.enable_compile_cache()

    cfg = reduced(registry.get(args.arch))
    if args.quant_kv:
        import dataclasses
        cfg = dataclasses.replace(cfg, quant_kv=True)
    params = model_api.init_params(cfg, jax.random.key(args.seed))
    trace = make_trace(cfg, args.requests, args.seed)

    streamed, reqs, compiled = run_engine(
        cfg, params, trace, args.capacity, args.max_len, args.prefill_pad,
        multi_step=args.multi_step)
    # same compiled (decode, prefill) pair: the baseline pays no extra
    # compiles, so the ratio isolates the admission policy
    padded, _, _ = run_engine(
        cfg, params, trace, args.capacity, args.max_len, args.prefill_pad,
        drain_barrier=True, compiled=compiled)

    multi_step_bit_identical = None
    per_step = None
    if args.multi_step > 1:
        # the multi-step window must be a pure dispatch optimization: the
        # per-step schedule (N=1) serves the same trace and every token
        # stream must match bit-for-bit
        per_step, reqs_1, _ = run_engine(
            cfg, params, trace, args.capacity, args.max_len,
            args.prefill_pad, compiled=compiled, multi_step=1)
        multi_step_bit_identical = all(
            a.output == b.output for a, b in zip(reqs, reqs_1))
        assert multi_step_bit_identical, \
            "multi-step decode changed tokens vs per-step dispatch"

    bit_identical = None
    if args.check_bit_identity:
        bit_identical = all(
            r.output == greedy_reference(cfg, params, p, n, args.max_len)
            for r, (p, n) in zip(reqs, trace))

    traced = None
    trace_overhead_frac = None
    if args.trace_out or args.metrics_out:
        # observability cost: same trace, same compiled functions, tracing
        # and metrics on — tokens/s delta vs the untraced streamed run is
        # the overhead the < 3 % budget (docs/observability.md) bounds
        from repro.obs import Registry, SpanTracer
        tracer = SpanTracer(name="serving_bench") if args.trace_out else None
        reg = Registry() if args.metrics_out else None
        traced, traced_reqs, _ = run_engine(
            cfg, params, trace, args.capacity, args.max_len,
            args.prefill_pad, compiled=compiled, multi_step=args.multi_step,
            tracer=tracer, metrics=reg)
        assert all(a.output == b.output
                   for a, b in zip(reqs, traced_reqs)), \
            "tracing changed tokens — observer effect"
        trace_overhead_frac = round(
            1.0 - traced["tokens_per_s"] / streamed["tokens_per_s"], 4)
        if tracer is not None:
            tracer.dump(args.trace_out)
        if reg is not None:
            reg.dump(args.metrics_out)

    policy_map_section = None
    policy_map_speedup = None
    if args.policy_map:
        import dataclasses
        from repro.core.dependability import Policy
        from repro.core.policy_map import PolicyMap, as_policy_map
        pm = as_policy_map(args.policy_map)
        # all three corners serve the same quantized path (the mapped ffn.*
        # sites only exist there), so the ratio prices the policies alone
        qcfg = dataclasses.replace(cfg, quant="w8a8_ffn")
        qparams = model_api.init_params(qcfg, jax.random.key(args.seed))
        runs = {}
        reqs_by = {}
        for label, this_map in (
                ("none", None),
                ("mapped", pm),
                ("uniform_abft", PolicyMap.uniform(Policy.ABFT))):
            runs[label], reqs_by[label], _ = run_engine(
                qcfg, qparams, trace, args.capacity, args.max_len,
                args.prefill_pad, multi_step=args.multi_step,
                policy_map=this_map)
        # the dependability contract: policies never change clean tokens —
        # mapped and uniform streams must equal the unprotected stream
        map_bit_identical = all(
            all(a.output == b.output
                for a, b in zip(reqs_by["none"], reqs_by[label]))
            for label in ("mapped", "uniform_abft"))
        assert map_bit_identical, \
            "policy map changed clean decode tokens vs uniform/unprotected"
        policy_map_speedup = round(
            runs["mapped"]["tokens_per_s"]
            / max(runs["uniform_abft"]["tokens_per_s"], 1e-9), 3)
        none_tps = max(runs["none"]["tokens_per_s"], 1e-9)
        policy_map_section = {
            "map": pm.to_doc(),
            "quant": "w8a8_ffn",
            "runs": runs,
            "overhead_vs_none": {
                label: round(none_tps / max(r["tokens_per_s"], 1e-9), 3)
                for label, r in runs.items()},
            "bit_identical": map_bit_identical,
        }

    speedup = streamed["tokens_per_s"] / max(padded["tokens_per_s"], 1e-9)
    result = {
        "arch": cfg.name,
        "capacity": args.capacity,
        "requests": args.requests,
        "seed": args.seed,
        "multi_step": args.multi_step,
        "quant_kv": bool(args.quant_kv),
        "trace_max_new": [n for _, n in trace],
        "streamed": streamed,
        "per_step": per_step,
        "padded": padded,
        "speedup_tokens_per_s": round(speedup, 3),
        "multi_step_speedup": (round(streamed["tokens_per_s"]
                                     / max(per_step["tokens_per_s"], 1e-9), 3)
                               if per_step else None),
        "multi_step_bit_identical": multi_step_bit_identical,
        "decode_bit_identical": bit_identical,
        "traced": traced,
        "trace_overhead_frac": trace_overhead_frac,
        "policy_map": policy_map_section,
        "policy_map_speedup": policy_map_speedup,
    }
    out = Path(args.out)
    out.write_text(json.dumps(result, indent=2) + "\n")
    print(f"streamed: {streamed['tokens_per_s']:8.1f} tok/s  "
          f"occupancy {streamed['occupancy']:.2f}  "
          f"p99 {streamed['p99_latency_s']:.2f}s  "
          f"(multi_step={args.multi_step})")
    if per_step is not None:
        print(f"per-step: {per_step['tokens_per_s']:8.1f} tok/s  "
              f"(bit-identical to multi-step: {multi_step_bit_identical})")
    print(f"padded:   {padded['tokens_per_s']:8.1f} tok/s  "
          f"occupancy {padded['occupancy']:.2f}  "
          f"p99 {padded['p99_latency_s']:.2f}s")
    print(f"continuous batching speedup: {speedup:.2f}×"
          + (f"  (bit-identical to reference: {bit_identical})"
             if bit_identical is not None else ""))
    if traced is not None:
        print(f"traced:   {traced['tokens_per_s']:8.1f} tok/s  "
              f"(overhead {trace_overhead_frac * 100:.1f}%)")
    if policy_map_section is not None:
        r = policy_map_section["runs"]
        print(f"policy map (w8a8): none {r['none']['tokens_per_s']:.1f} | "
              f"mapped {r['mapped']['tokens_per_s']:.1f} | "
              f"uniform-abft {r['uniform_abft']['tokens_per_s']:.1f} tok/s"
              f"  -> mapped vs uniform {policy_map_speedup:.2f}x "
              f"(bit-identical: {policy_map_section['bit_identical']})")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
