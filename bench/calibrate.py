"""Readings that set a cell's rate and its correctness limits, on the chip.

    python3 bench/calibrate.py sweep  --workload <cell> --rates 2,3,4 --seconds 20
    python3 bench/calibrate.py limits --workload <cell> --seeds 1,2,3 --seconds 8

``sweep`` serves the cell's traffic at each rate in turn, in one process
with one set of weights, and prints per rate the released tokens per
second, the latency percentiles and the backlog left at the window's
close: the knee is the highest rate whose backlog does not grow.

``limits`` runs, for each seed, the cell's timed path (for serving, a short
window at the cell's rate; for frames, one batch of each scene) and prints
the number the cell compares for the program and for the control (the
reference one precision step below the configuration's), on the same
inputs, each put through the cell's own check (``ok`` or ``FAILED``).  The
lower reading of a limit is the largest program reading over the seeds,
the upper the smallest control reading.  Neither mode is part of a
benchmark run.
"""
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import argparse  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402


def verdict(check: dict) -> str:
    return "ok" if check["ok"] else "FAILED"


def sweep(cell, args, devs, clock):
    from drivers import serve
    st = serve.Setup(cell, args.seeds[0], devs, rehearse=args.rehearse_cpu)
    for rate in args.rates:
        st.server.reset()
        w = serve.serve_window(st, rate, args.seconds, clock)
        lat = w["latencies"]
        print(json.dumps({
            "rate_per_s": rate, "tokens_per_s": w["tokens_per_s"],
            "due": len(w["in_window"]),
            "outstanding_at_open_close": w["backlog"],
            "unreleased": len(w["unreleased"]),
            "p50_s": harness.percentile(lat, 50),
            "p95_s": harness.percentile(lat, 95),
            "slot_steps": w["counters"]["steps"],
            "tokens_out": w["counters"]["tokens_out"],
            "submit_lag_max_s": max(w["lag"]) if w["lag"] else 0.0,
            "compiles_in_window": w["compiles_in_window"]}), flush=True)


def limits_serve(cell, args, devs, clock):
    from drivers import serve
    server = None
    for seed in args.seeds:
        t0 = time.perf_counter()
        st = serve.Setup(cell, seed, devs, args.rehearse_cpu, server=server)
        server = st.server
        w = serve.serve_window(st, st.spec["rate_per_s"], args.seconds, clock)
        sample = serve.sample_requests(
            w["in_window"], w["outputs"], st.spec["check"]["sample"],
            np.random.default_rng(st.k_sample))
        prog = serve.reference_gap(st, w["reqs"], w["outputs"], sample)
        ctrl = serve.reference_gap(st, w["reqs"], w["outputs"], sample,
                                   lower=True)
        print(json.dumps({
            "seed": seed, "program_max_logit_gap": prog,
            "program_check": verdict(serve.gap_check(st, prog, sample)),
            "control_max_logit_gap": ctrl,
            "control_check": verdict(serve.gap_check(st, ctrl, sample)),
            "requests_compared": len(sample),
            "tokens_compared": sum(len(w["outputs"][u]) for u in sample),
            "unreleased": len(w["unreleased"]),
            "seconds": time.perf_counter() - t0}), flush=True)


def limits_frames(cell, args, devs, clock):
    from drivers import frames
    for seed in args.seeds:
        st = frames.Setup(cell, seed, args.rehearse_cpu)
        prog = ctrl = 0.0
        for s, x in enumerate(st.scenes):
            y, _ = st.fwd(st.params, st.checks, x)
            prog = max(prog, st.reference_steps(s, y))
            ctrl = max(ctrl, st.reference_steps(s, None, lower=True))
        n = len(st.scenes)
        print(json.dumps({
            "seed": seed, "program_max_output_steps": prog,
            "program_check": verdict(frames.steps_check(st, prog, n)),
            "control_max_output_steps": ctrl,
            "control_check": verdict(frames.steps_check(st, ctrl, n))}),
            flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    args.rates = [float(r) for r in args.rates.split(",") if r]
    args.seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.Cell.load(args.workload)
    try:
        devs = harness.devices(cell.chips, args.rehearse_cpu)
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"cell": cell.name, "device": harness.device_info(devs),
                      "compile_cache": harness.enable_compile_cache()}),
          flush=True)
    clock = harness.CompileClock()
    if args.mode == "sweep":
        sweep(cell, args, devs, clock)
    elif cell.spec["driver"] == "frames":
        limits_frames(cell, args, devs, clock)
    else:
        limits_serve(cell, args, devs, clock)
    return 0


if __name__ == "__main__":
    sys.exit(main())
