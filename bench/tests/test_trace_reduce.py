"""The trace reduction, on a small recorded trace and on hand-made
events."""
import numpy as np
import pytest

import harness
import opcount
import peaks
import trace_reduce as tr

DATA = harness.BENCH / "tests" / "data" / "trace_shipdet_batch.json"


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(tr.load(str(DATA)))


def _timeline(events, lo, hi):
    """Busy nanoseconds by brute force: a 1 ns-resolution mask (the
    recorded times are whole nanoseconds after the cut)."""
    t = np.zeros(int(hi - lo) + 1, bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            t[int(round(a - lo)):int(round(b - lo))] = True
    return t.sum() / 1e9


def test_busy_and_idle_against_brute_force(summary):
    chip = summary.chips[0]
    want = _timeline(chip.op_events, summary.lo_ns, summary.hi_ns)
    assert abs(chip.busy_s - want) < 2e-8 * len(chip.op_events)
    assert summary.window_s == pytest.approx(0.014921079)
    assert 0 < summary.idle_share() < 1
    assert summary.busy_s == chip.busy_s          # one chip


def test_self_times_add_up_to_busy(summary):
    """Operations on one line never overlap except by nesting, so their
    self times sum to the busy time."""
    chip = summary.chips[0]
    assert sum(chip.ops.values()) == pytest.approx(chip.busy_s, rel=1e-9)


def test_gaps_tile_the_idle_time(summary):
    idle = summary.window_s - summary.busy_s
    assert sum(g for _, g in summary.gaps) == pytest.approx(idle, rel=1e-9)
    assert summary.gaps[0][0].startswith("shipdet.forward > ")
    out = summary.breakdown()
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10


def test_qconv2d_roofline_by_hand(summary):
    """The reader against an explicit loop over the 8 kernel calls of the
    batch, each matched to its layer by its weight operand."""
    cell = harness.Cell.load("shipdet-scene")
    p = peaks.peaks_for("TPU v5 lite")
    calls = tr.kernel_events(summary.chips[0], summary.lo_ns, summary.hi_ns)
    assert len(calls) == 8
    sides = [388, 194, 194, 97, 97, 49, 49, 49]
    least = 0.0
    for layer, side in zip(cell.config["layers"], sides):
        w = (layer["kh"], layer["kw"], layer["cin"], layer["cout"] + 4)
        (call,) = [e for e in calls if ("s8", w) in tr.kernel_operands(e[0])]
        work = opcount.qconv2d(16, side, side, layer["cin"], layer["cout"],
                               layer["kh"], layer["kw"], layer["stride"])
        least += max(work["int8_ops"] / p["int8_ops"],
                     work["bytes"] / p["hbm_bytes_per_s"])
    spent = sum(e[2] for e in calls) / 1e9
    got = harness.load_module("metrics", "qconv2d_roofline.shipdet").read(
        {"trace": summary, "peaks": p, "cell": cell})
    assert got == pytest.approx(100 * least / spent, rel=1e-12)
    assert 0 < got <= 100


def test_union_self_and_intersect_by_hand():
    ev = [["loop", 0, 100], ["a", 10, 20], ["b", 40, 10], ["c", 150, 50]]
    assert tr.union([(e[1], e[1] + e[2]) for e in ev], 0, 180) == [
        (0, 100), (150, 180)]
    st = tr.self_times(ev, 0, 1e9)
    assert st == {"loop": 70e-9, "a": 20e-9, "b": 10e-9, "c": 50e-9}
    assert tr.intersect([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_no_window_span_is_an_error():
    with pytest.raises(RuntimeError):
        tr.summarize({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["engine.step", 0, 5]]}]}]})
