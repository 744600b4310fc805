"""The benchmark: one cell of BENCHMARK.json, measured on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``bench/workloads/<cell>.json``: its configuration
(``bench/configs/``), its traffic mix (``bench/traffic/``), its driver
(``bench/drivers/``) and the chips it needs.  With ``--trace 0`` the run
prints the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics (``bench/metrics/<name>.py``, read from a profiler trace of the
window's last ``trace_seconds`` and from the run's counters).  Every run
checks what the timed path produced against a float32 reference and
prints each compared number beside its limit, last on stderr and under
``checks`` in the result.  The last line of stdout is the result as one
JSON object.

Where JAX finds no accelerator, or fewer chips than the cell asks for, the
run exits with code 2 and prints no result.  ``--rehearse-cpu`` runs the
cell on the CPU at the configuration's reduced sizes to check the harness;
its output names the CPU and is not a result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU at reduced sizes (not a result)")
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    try:
        devs = harness.devices(cell.chips, args.rehearse_cpu)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    info = harness.device_info(devs)
    print(f"bench: cell {cell.name} on platform {info['platform']}, "
          f"device_kind {info['kind']}, count {info['count']}; compile "
          f"cache {harness.enable_compile_cache()}; devices ready "
          f"{time.perf_counter() - T_START:.2f} s after start",
          file=sys.stderr, flush=True)
    clock = harness.CompileClock()
    driver = harness.load_module("drivers", cell.spec["driver"])
    out = driver.run(cell, args, devs, clock, T_START, args.rehearse_cpu)

    wanted = harness.cell_metrics(cell.name, bool(args.trace))
    device = dict(info, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": all(c["ok"] for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        import peaks
        summary = out["trace"]
        ctx = {"cell": cell, "seconds": args.seconds, "trace": summary,
               "counters": out["counters"],
               "peaks": peaks.peaks_for(info["kind"]) if not
               args.rehearse_cpu else None}
        result["metrics"] = harness.read_per_layer(wanted, ctx)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    else:
        result["metrics"] = {m["name"]: {"value": out["metrics"][m["name"]],
                                         "unit": m["unit"]} for m in wanted}
        result["device"] = device
    for k, v in out["extra"].items():
        print(f"bench: {k} = {v!r}", file=sys.stderr)
    if not args.trace:
        print(f"bench: end-to-end = {out['metrics']!r}", file=sys.stderr)
    harness.emit(result, out["checks"], args.rehearse_cpu)
    return 0


def same_hash_seed() -> None:
    """Run this process again with ``PYTHONHASHSEED=0``, keeping its start.

    With Python's string hashes drawn anew in every process, the ship
    detector's forward got a new key in the compile cache each time and was
    compiled again in set-up (10 s of 25 on a TPU v5e).  The exec happens
    before JAX is imported; ``perf_counter`` is the system's monotonic clock,
    so the start time carries over."""
    global T_START
    if os.environ.get("PYTHONHASHSEED") == "0":
        T_START = float(os.environ.pop("BENCH_T_START", T_START))
        return
    os.environ.update(PYTHONHASHSEED="0", BENCH_T_START=repr(T_START))
    os.execv(sys.executable, [sys.executable] + sys.argv)


if __name__ == "__main__":
    same_hash_seed()
    sys.exit(main())
