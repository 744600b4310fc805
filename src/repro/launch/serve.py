"""Serving launcher — batched requests through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 16 --max-new 24 [--dependability snapshot]

The paper's execution flow in TPU terms: the Engine (Klepsydra analogue)
admits requests into a fixed decode batch, the jitted step (HPDP analogue)
streams tokens out, and snapshots bound the replay window after a fault.
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax

from repro import device
from repro.configs import registry
from repro.models import api as model_api
from repro.models.config import reduced
from repro.runtime.serving import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.names())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-drill", action="store_true",
                    help="inject an SEU mid-serve and prove recovery")
    args = ap.parse_args()
    device.enable_compile_cache()

    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    print(f"[serve] arch={cfg.name} capacity={args.capacity} "
          f"requests={args.requests}")
    params = model_api.init_params(cfg, jax.random.key(args.seed))
    eng = Engine(cfg, params, capacity=args.capacity, max_len=args.max_len,
                 snapshot_every=8)

    import numpy as np
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(3, 17))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        r = Request(uid=i, prompt=prompt, max_new_tokens=args.max_new)
        reqs.append(r)
        eng.submit(r)

    t0 = time.time()
    if args.fault_drill:
        for _ in range(5):
            eng.step()
        print("[serve] injecting SEU into decode state …")
        eng.tokens = eng.tokens.at[0].set(99999 % cfg.vocab_size)
        lost = eng.restore_snapshot()
        print(f"[serve] rolled back {lost} steps from snapshot")
    stats = eng.run()
    dt = time.time() - t0

    lat = [r.t_release - r.t_submit for r in reqs if r.t_release]
    print(f"[serve] {stats.tokens_out} tokens in {dt:.2f}s "
          f"({stats.tokens_out/dt:.1f} tok/s), steps={stats.steps}, "
          f"replays={stats.replays}")
    if lat:
        print(f"[serve] latency p50={statistics.median(lat):.2f}s "
              f"max={max(lat):.2f}s")
    assert all(len(r.output) >= 1 for r in reqs)
    print("[serve] all requests completed")


if __name__ == "__main__":
    main()
