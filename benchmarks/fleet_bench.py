"""Fleet scaling + dependability-policy overhead benchmark.

Measures released-token throughput of the serving fleet as replicas and
policies vary — the serving-side companion of benchmarks/campaign_bench.py
(which prices the op-level policies).  The interesting ratios:

  * none → abft: the cost of certify-before-release (periodic pytree
    checksums + release latency, no extra decode), and
  * none → dmr: the cost of pair-serving (2× decode of every request).

``--transport proc`` benches the process-isolation transport instead; each
proc row also replays the same request stream through an in-process fleet
and asserts the released token streams are byte-identical
(``bit_identical_to_inproc`` in the row) — throughput with a built-in
correctness gate.

    PYTHONPATH=src python -m benchmarks.fleet_bench --fast
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import device
from repro.core.dependability import Policy
from repro.fleet import Fleet
from repro.runtime.serving import Request


def _released_streams(fleet, n_requests):
    return tuple(tuple(fleet.released[uid].output)
                 if uid in fleet.released else None
                 for uid in range(n_requests))


def bench(arch: str, n_replicas: int, policy: Policy, n_requests: int,
          max_new: int, seed: int = 0, transport: str = "inproc"):
    from repro.configs import registry
    from repro.models import api as model_api
    from repro.models.config import reduced

    cfg = reduced(registry.get(arch))
    params = model_api.init_params(cfg, jax.random.key(seed))
    fleet = Fleet(cfg, params, n_replicas=n_replicas, policy=policy,
                  capacity=4, max_len=96, prefill_pad=8, scrub_every=4,
                  transport=transport)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=4).tolist()
               for _ in range(n_requests)]

    def run_once(fl):
        fl.reset(policy=policy)
        for i, p in enumerate(prompts):
            fl.submit(Request(uid=i, prompt=list(p), max_new_tokens=max_new))
        fl.run()
        return fl.metrics

    run_once(fleet)                              # warmup / compile
    t0 = time.perf_counter()
    m = run_once(fleet)
    dt = time.perf_counter() - t0
    row = {
        "arch": cfg.name, "replicas": n_replicas, "policy": policy.value,
        "transport": transport,
        "released": m.released, "tokens": m.tokens_out, "ticks": m.ticks,
        "tok_per_s": m.tokens_out / dt,
        "p50_ticks": m.p50_ticks, "p99_ticks": m.p99_ticks,
        "metrics": m.to_json(),
    }
    if transport != "inproc":
        # correctness gate: the same stream through an in-process fleet
        # must release byte-identical tokens (docs/multihost.md)
        proc_out = _released_streams(fleet, n_requests)
        ref = Fleet(cfg, params, n_replicas=n_replicas, policy=policy,
                    capacity=4, max_len=96, prefill_pad=8, scrub_every=4)
        run_once(ref)
        ref_out = _released_streams(ref, n_requests)
        ref.close()
        row["bit_identical_to_inproc"] = proc_out == ref_out
        if not row["bit_identical_to_inproc"]:
            raise AssertionError(
                f"{transport} released stream diverged from inproc: "
                f"{proc_out} != {ref_out}")
    fleet.close()
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmarks.fleet_bench")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--replicas", default="1,2,4")
    ap.add_argument("--policies", default="none,abft,dmr")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--fast", action="store_true",
                    help="2 replicas only, 6 requests")
    ap.add_argument("--transport", default="inproc",
                    choices=["inproc", "proc"],
                    help="proc: one worker process per replica; every row "
                         "is also checked bit-identical against inproc")
    ap.add_argument("--metrics-out", default=None,
                    help="write every row's full FleetMetrics snapshot "
                         "(registry counters + latency histograms) as JSON")
    args = ap.parse_args(argv)
    device.enable_compile_cache()

    replica_counts = [2] if args.fast else [
        int(x) for x in args.replicas.split(",")]
    n_requests = 6 if args.fast else args.requests
    policies = [Policy(p) for p in args.policies.split(",")]

    rows = []
    for n in replica_counts:
        for pol in policies:
            if pol == Policy.DMR and n < 2:
                continue                          # pair-serving needs 2
            r = bench(args.arch, n, pol, n_requests, args.max_new_tokens,
                      transport=args.transport)
            rows.append(r)
            ident = ("  bit-identical=yes"
                     if r.get("bit_identical_to_inproc") else "")
            print(f"{r['arch']}  replicas={r['replicas']}  "
                  f"policy={r['policy']:>4}  {r['tok_per_s']:8.1f} tok/s  "
                  f"p50={r['p50_ticks']:.0f}t p99={r['p99_ticks']:.0f}t  "
                  f"({r['released']} released){ident}", flush=True)

    base = {r["replicas"]: r["tok_per_s"] for r in rows
            if r["policy"] == "none"}
    for r in rows:
        if r["policy"] != "none" and r["replicas"] in base:
            print(f"  overhead {r['policy']} @ {r['replicas']} replicas: "
                  f"{base[r['replicas']] / max(r['tok_per_s'], 1e-9):.2f}×")
    if args.metrics_out:
        import json
        import pathlib
        mpath = pathlib.Path(args.metrics_out)
        mpath.parent.mkdir(parents=True, exist_ok=True)
        mpath.write_text(json.dumps({"rows": rows}, indent=2,
                                    sort_keys=True) + "\n")
        print(f"wrote {mpath}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
