"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler writes and keeps, in a
plain form, what the reduction needs: every event of each TPU's
``XLA Ops`` and ``XLA Modules`` lines (an operation's event is named by its
HLO instruction, shapes included; a program's by its module), and the
events of the host thread that carries the harness's annotations.
``summarize`` reduces that to a ``Summary``:

  * the traced window: the harness's host span ``bench.window``
    (a ``TraceAnnotation``), so the profiler's start and stop lie outside
    it;
  * per chip: busy time, the union of the intervals in which an operation
    ran, clipped to the window; self time per operation (a loop's time less
    the operations inside it) and time per program;
  * idle gaps on the first chip, each named by the host spans that cover
    its middle.

``load`` also reads the plain form from a ``.json`` file, which is how a
small recorded trace is kept for the tests.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LINE = "python"      # the main thread: named after the executable


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def load(path: str) -> dict:
    """An ``.xplane.pb`` (or a saved ``.json``) in the plain form:
    ``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]}``."""
    if str(path).endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    planes = []
    for p in ProfileData.from_file(str(path)).planes:
        if is_device(p.name):
            wanted = (OPS_LINE, MODULES_LINE).__contains__
        elif p.name == "/host:CPU":
            wanted = (lambda n: n.startswith(HOST_LINE))
        else:
            continue
        lines = [{"name": ln.name,
                  "events": [[e.name, e.start_ns, e.duration_ns]
                             for e in ln.events]}
                 for ln in p.lines if wanted(ln.name)]
        planes.append({"name": p.name, "lines": lines})
    return {"planes": planes}


def line(plane: dict, name: str) -> List[list]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[list] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def self_times(events: List[list], lo: float, hi: float) -> Dict[str, float]:
    """Seconds per operation name in [lo, hi], less the time of the
    operations nested inside it (a loop's body runs as its own events)."""
    out: Dict[str, float] = {}
    stack: List[list] = []           # [name, end, child_ns, start]

    def close(item):
        name, end, child, start = item
        d = min(end, hi) - max(start, lo)
        if d > 0:
            d = max(d - child, 0.0)
            out[name] = out.get(name, 0.0) + d / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            c = min(start + dur, hi) - max(start, lo)
            stack[-1][2] += max(c, 0.0)
        stack.append([name, start + dur, 0.0, start])
    while stack:
        close(stack.pop())
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[32,576]{...} fusion(...)`` -> ``fusion.12
    bf16[32,576]``: the instruction and its result's shape."""
    head = event_name.split(" = ", 1)
    if len(head) < 2:
        return event_name[:120]
    m = SHAPE.search(head[1])
    result = f"{m.group(1)}[{m.group(2)}]" if m else ""
    return f"{head[0].lstrip('%')} {result}"[:120]


@dataclasses.dataclass
class Chip:
    name: str
    busy_s: float
    ops: Dict[str, float]            # op -> self seconds in the window
    modules: Dict[str, float]        # program -> seconds in the window
    module_events: List[list]
    op_events: List[list]


@dataclasses.dataclass
class Summary:
    window_s: float
    lo_ns: float
    hi_ns: float
    chips: List[Chip]
    gaps: List[Tuple[str, float]]    # (host spans over the gap, seconds)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips (0 with no device)."""
        if not self.chips:
            return 0.0
        return sum(c.busy_s for c in self.chips) / len(self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        total: Dict[str, float] = {}
        for c in self.chips:
            for k, v in c.ops.items():
                k = op_name(k)
                total[k] = total.get(k, 0.0) + v / len(self.chips)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def intersect(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
              ) -> float:
    """Nanoseconds in both of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_in(chip: Chip, module: str, lo: float, hi: float) -> float:
    """Busy seconds of ``chip`` inside executions of the program
    ``module`` (an XLA module name without its id), within [lo, hi]."""
    busy = union([(e[1], e[1] + e[2]) for e in chip.op_events], lo, hi)
    runs = union([(e[1], e[1] + e[2]) for e in chip.module_events
                  if e[0].split("(")[0] == module], lo, hi)
    return intersect(busy, runs) / 1e9


WINDOW = "bench.window"


def _host(trace: dict):
    """(the window span, all events) of the host thread that carries the
    harness's window span."""
    for p in trace["planes"]:
        if is_device(p["name"]):
            continue
        for ln in p["lines"]:
            for e in ln["events"]:
                if e[0] == WINDOW:
                    return e, ln["events"]
    raise RuntimeError(f"no host span {WINDOW!r} in the trace")


def summarize(trace: dict) -> Summary:
    span, host = _host(trace)
    lo, hi = span[1], span[1] + span[2]
    chips = []
    for p in sorted((p for p in trace["planes"] if is_device(p["name"])),
                    key=lambda p: p["name"]):
        ops = line(p, OPS_LINE)
        if not ops:
            continue
        busy = union([(e[1], e[1] + e[2]) for e in ops], lo, hi)
        mods = line(p, MODULES_LINE)
        per_mod: Dict[str, float] = {}
        for e in mods:
            d = min(e[1] + e[2], hi) - max(e[1], lo)
            if d > 0:
                name = e[0].split("(")[0]
                per_mod[name] = per_mod.get(name, 0.0) + d / 1e9
        chips.append(Chip(p["name"], sum(b - a for a, b in busy) / 1e9,
                          self_times(ops, lo, hi), per_mod, mods, ops))
    gaps = []
    if chips:
        busy0 = union([(e[1], e[1] + e[2]) for e in chips[0].op_events],
                      lo, hi)
        edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_host_at(host, (a + b) / 2), (b - a) / 1e9))
        gaps.sort(key=lambda g: -g[1])
    return Summary((hi - lo) / 1e9, lo, hi, chips, gaps)


def _host_at(host: List[list], t: float) -> str:
    """The host spans covering ``t``, outermost first."""
    cover = [e for e in host if e[1] <= t < e[1] + e[2] and e[0] != WINDOW]
    cover.sort(key=lambda e: (e[1], -e[2]))
    return " > ".join(e[0] for e in cover[:4]) or "host: no span"


SHAPE = re.compile(r"\b([su](?:8|16|32)|bf16|f32|pred)\[([0-9,]*)\]")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array shape an HLO instruction's text names,
    result first, then operands."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in SHAPE.findall(text)]


MOSAIC = 'custom_call_target="tpu_custom_call"'
OPERANDS = re.compile(r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")


def kernel_events(chip: Chip, lo: float, hi: float) -> List[list]:
    """Pallas kernel calls (Mosaic ``tpu_custom_call`` instructions) wholly
    inside [lo, hi]."""
    return [e for e in chip.op_events
            if MOSAIC in e[0] and e[1] >= lo and e[1] + e[2] <= hi]


def kernel_operands(event_name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of a kernel call's operands, in order."""
    m = OPERANDS.search(event_name)
    return shapes(m.group(1)) if m else []
