"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, the set-up clock, the profiler window, the
per-layer metric readers, and the result line.
"""
from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
from typing import Dict, List

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_traces"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ by name

def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (BENCH / kind).glob("*.json"))
        raise SystemExit(f"bench: no {kind[:-1]} {name!r}; known: {known}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict            # bench/workloads/<name>.json
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    chips: int

    @classmethod
    def load(cls, name: str) -> "Cell":
        spec = load_json("workloads", name)
        return cls(name, spec, load_json("configs", spec["config"]),
                   load_json("traffic", spec["traffic"]), spec["chips"])


# ------------------------------------------------------------------- device

def devices(chips: int, rehearse: bool):
    """The first ``chips`` accelerator devices; NoDevice where JAX finds no
    accelerator or too few.  ``rehearse`` accepts the CPU."""
    import jax
    found = jax.devices()
    if found[0].platform == "cpu" and not rehearse:
        raise NoDevice("JAX found no accelerator (platform cpu); the "
                       "benchmark measures only on the chip")
    if len(found) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(found)} ({found[0].platform})")
    return found[:chips]


def device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or else at ``.jax_cache`` in the checkout (a fixed path: the path is
    part of the cache key).  Every program is cached, however quick its
    compile, so that a warm run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts XLA backend compiles and their seconds as JAX reports them."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


# ----------------------------------------------------------------- profiler

class Profiler:
    """A profiler window around part of a run, reduced in place.

    ``start()`` and ``stop()`` bracket the traced stretch, which the host
    span ``bench.window`` marks in the trace; ``summary()``, called once the
    run no longer needs the host, returns the reduced trace
    (``trace_reduce.Summary``) and deletes the files the profiler wrote."""

    def __init__(self, tag: str):
        self.dir = TRACE_DIR / tag
        self._span = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = annotate("bench.window")
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self):
        import trace_reduce
        try:
            path = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                             recursive=True)[0]
            return trace_reduce.summarize(trace_reduce.load(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------------ metrics

def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation; inf where a
    value is inf (a request that failed misses every latency limit)."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]):
        return float("inf")
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def cell_metrics(cell: str, trace: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end ones without a trace,
    per-layer ones with it, each where its ``workloads`` lists the cell
    (or, without that key, wherever the metric it moves is reported)."""
    spec = benchmark_spec()
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in moved]


def read_per_layer(metrics: List[dict], ctx: dict) -> Dict[str, dict]:
    """Run each per-layer metric's reader (``bench/metrics/<name>.py``);
    a reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in metrics:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ------------------------------------------------------------------- output

def emit(result: dict, checks: List[dict], rehearsal: bool) -> None:
    """Print the compared numbers beside their limits, last on stderr, and
    the result as the last line of stdout with ``checks`` as its last key.
    A rehearsal's line names the CPU and is no result line."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    line = json.dumps(result)
    if rehearsal:
        line = "rehearsal on the CPU, not a measurement: " + line
    print(line, flush=True)
