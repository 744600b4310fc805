"""The readers of the program's own spans, stable program names and stage
stamps: ``protection_share.serve``, ``prefill_busy_share.serve`` and
``slot_wait_share.serve``, on one pump of a recorded chip trace of the
smollm-chat cell (TPU v5 lite: the pump's host spans and device events,
cut from the pre-decode scrub to the decode window's start, the cut's
bounds as the window) and on traces worked by hand."""
import numpy as np
import pytest

import harness
import trace_reduce as tr

PUMP = harness.BENCH / "tests" / "data" / "trace_smollm_chat_pump.json"
SHIPDET = harness.BENCH / "tests" / "data" / "trace_shipdet_batch.json"


def _read(name, trace=None, counters=None):
    return harness.load_module("metrics", name).read(
        {"trace": trace, "counters": counters or {"released": []},
         "cell": None, "seconds": 51.0, "peaks": None})


@pytest.fixture(scope="module")
def pump():
    return tr.summarize(tr.load(str(PUMP)))


def _mask(intervals, lo, hi):
    """A 1 ns-resolution mask of the union of ``intervals`` in [lo, hi)."""
    m = np.zeros(int(hi - lo), bool)
    for a, b in intervals:
        a, b = int(max(a, lo) - lo), int(min(b, hi) - lo)
        if b > a:
            m[a:b] = True
    return m


def _runs(chip, module):
    return [(e[1], e[1] + e[2]) for e in chip.module_events
            if e[0].split("(")[0] == module]


def test_recorded_pump_holds_the_programs_and_spans(pump):
    chip = pump.chips[0]
    assert {"jit_engine_prefill", "jit_engine_decode_window",
            "jit_state_checksums", "jit_splice_slot"} <= set(chip.modules)
    assert not {"jit__lambda", "jit__window"} & set(chip.modules)
    names = {e[0].split(" = ")[0].split(".")[0]
             for e in tr.kernel_events(chip, pump.lo_ns, pump.hi_ns)}
    assert names == {"%qmatmul_acc_checksum"}
    # every idle gap of 1 ms or more lies under one of the engine's spans
    long = [label for label, sec in pump.gaps if sec >= 1e-3]
    assert long and all("engine/" in label for label in long)


def test_prefill_busy_share_on_the_recorded_pump(pump):
    chip = pump.chips[0]
    busy = _mask([(e[1], e[1] + e[2]) for e in chip.op_events],
                 pump.lo_ns, pump.hi_ns)
    runs = _mask(_runs(chip, "jit_engine_prefill"), pump.lo_ns, pump.hi_ns)
    want = 100.0 * (busy & runs).sum() / busy.sum()
    got = _read("prefill_busy_share.serve", pump)
    assert got == pytest.approx(want, rel=1e-6)
    assert 10 < got < 90           # the pump's one prefill is its main work


def test_protection_share_on_the_recorded_pump(pump):
    chip = pump.chips[0]
    lo, hi = pump.lo_ns, pump.hi_ns
    busy = _mask([(e[1], e[1] + e[2]) for e in chip.op_events], lo, hi)
    device = (busy & _mask(_runs(chip, "jit_state_checksums"), lo, hi)).sum()
    host = sum(sec for label, sec in pump.gaps
               if "engine/state_scrub" in label
               or "engine/snapshot" in label)
    want = 100.0 * (device / 1e9 + host) / pump.window_s
    got = _read("protection_share.serve", pump)
    assert got == pytest.approx(want, rel=1e-6)
    assert host > 0 and device > 0


def _hand_trace(modules):
    """1 ms window; the device busy in four programs, the host in a pump
    whose spans cover the gaps between them."""
    ops = [[f"%op.{i} = f32[1]", a, b - a]
           for i, (_, a, b) in enumerate(modules)]
    mods = [[f"{name}(1)", a, b - a] for name, a, b in modules]
    host = [["bench.window", 0, 1_000_000], ["engine.step", 0, 1_000_000],
            ["engine/state_scrub", 20_000, 240_000],
            ["engine/storage_scrub", 290_000, 70_000],
            ["engine/decode_readback", 400_000, 510_000],
            ["engine/state_refresh", 910_000, 30_000]]
    return tr.summarize({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host}]}]})


NAMED = [("jit_state_checksums", 100_000, 200_000),
         ("jit_storage_verify", 300_000, 350_000),
         ("jit_engine_decode_window", 500_000, 900_000),
         ("jit_engine_prefill", 950_000, 1_000_000)]


def test_readers_by_hand():
    """Busy 600 us of 1 ms.  Gaps [0, 100), [200, 300), [350, 500) and
    [900, 950) us have their middles at 50, 250, 425 and 925 us: the first
    two in the state scrub span, the last in the refresh span, the third in
    the decode readback.  Protection: 100 + 50 us in the checksum and
    verify programs, 100 + 100 + 50 us of idle under protection spans, 40%
    of the window.  Prefill: 50 of 600 busy us."""
    s = _hand_trace(NAMED)
    assert s.window_s == pytest.approx(1e-3)
    assert _read("protection_share.serve", s) == pytest.approx(40.0)
    assert _read("prefill_busy_share.serve", s) == pytest.approx(
        100 * 50 / 600)
    # named programs but no prefill in the window: 0, not left out
    no_prefill = _hand_trace(NAMED[:3])
    assert _read("prefill_busy_share.serve", no_prefill) == 0.0


def test_readers_leave_out_a_program_without_the_names():
    """A program that names neither its protection programs nor its
    prefill (as before the names) leaves both metrics out, as does a
    trace of another cell."""
    old = _hand_trace([("jit__lambda", 100_000, 200_000),
                       ("jit__window", 500_000, 900_000),
                       ("jit_storage_checksums", 950_000, 1_000_000)])
    ship = tr.summarize(tr.load(str(SHIPDET)))
    for s in (old, ship, None):
        assert _read("protection_share.serve", s) is None
        assert _read("prefill_busy_share.serve", s) is None


LOG = [(-1, 64, 9, 0.0, 0.1, 0.2, 0.5, 0.6),        # warm-up
       (0, 10, 5, 0.0, 9.0, 9.5, 9.9, 10.0),        # an earlier run ...
       (1, 20, 6, 0.0, 9.0, 9.5, 9.9, 10.0),        # ... of the same work
       (0, 10, 5, 0.0, 1.0, 1.5, 3.0, 4.0),         # wait 1.0 of 4.0
       (1, 20, 6, 1.0, 1.5, 2.0, 5.0, 6.0),         # wait 0.5 of 5.0
       (2, 30, 7, 2.0, 2.0, 2.5, 6.0, 7.0)]         # past the window


def test_slot_wait_share_by_hand(monkeypatch):
    from repro.obs import trace as obs_trace
    monkeypatch.setattr(obs_trace, "RELEASED", list(LOG))
    counters = {"prompt_lens": [10, 20], "released": [5, 6]}
    got = _read("slot_wait_share.serve", counters=counters)
    assert got == pytest.approx(100 * 1.5 / 9.0)


def test_slot_wait_share_left_out_when_unmatched(monkeypatch):
    from repro.obs import trace as obs_trace
    monkeypatch.setattr(obs_trace, "RELEASED", list(LOG))
    assert _read("slot_wait_share.serve", counters={
        "prompt_lens": [10, 30], "released": [5, 7]}) is None
    assert _read("slot_wait_share.serve") is None
    # a program without the log
    monkeypatch.delattr(obs_trace, "RELEASED")
    assert _read("slot_wait_share.serve", counters={
        "prompt_lens": [10], "released": [5]}) is None
