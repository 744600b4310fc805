"""Readers leave out what they cannot read, and the traffic generators give
every seed the same work."""
import dataclasses

import numpy as np
import pytest

import harness
import peaks
import trace_reduce as tr

DATA = harness.BENCH / "tests" / "data" / "trace_shipdet_batch.json"


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(tr.load(str(DATA)))


def _reader(name):
    return harness.load_module("metrics", name)


@pytest.mark.parametrize("operands, want", [
    ([("s8", (32, 576)), ("s8", (576, 1536)), ("s8", (576, 4))],
     (32, 576, 1536, True)),
    ([("s8", (128, 1536)), ("s8", (1536, 576)), ("f32", (1, 576))],
     (128, 1536, 576, False)),
    ([("bf16", (32, 3, 64)), ("s8", (2048, 64)), ("s8", (2048, 64))], None),
    ([("s8", (16, 388, 388, 3)), ("s8", (3, 3, 3, 28))], None),
    ([("s8", (32, 576)), ("s8", (576, 1536)), ("s8", (1536, 4))], None),
])
def test_qmatmul_signature(operands, want):
    assert _reader("qmatmul_roofline.serve").signature(operands) == want


def _renamed(summary, old, new):
    """The summary with the program ``old`` renamed ``new``."""
    chips = [dataclasses.replace(
        c, modules={new if k == old else k: v for k, v in c.modules.items()},
        module_events=[[new + e[0][len(old):] if e[0].split("(")[0] == old
                        else e[0]] + list(e[1:]) for e in c.module_events])
        for c in summary.chips]
    return dataclasses.replace(summary, chips=chips)


def test_serving_readers_leave_out_a_trace_without_their_work(summary):
    """The recorded trace is a shipdet batch: its Pallas calls are convs,
    and with its program named as the frame driver names it no module is
    the prefill program's, so both serving readers return nothing."""
    summary = _renamed(summary, "jit__lambda", "jit_shipdet_forward")
    ctx = {"trace": summary, "peaks": peaks.peaks_for("TPU v5 lite")}
    assert tr.kernel_events(summary.chips[0], summary.lo_ns, summary.hi_ns)
    assert _reader("qmatmul_roofline.serve").read(ctx) is None
    assert _reader("prefill_share.serve").read(ctx) is None


def _schedule(seed, mix):
    gen = harness.load_module("traffic", mix["generator"])
    return gen.generate(mix, np.random.default_rng(seed), rate_per_s=3.2,
                        segments=[10.0, 51.0, 60.0], vocab_size=49152)


def test_chat_every_seed_gets_the_same_work():
    """Seeds differ in token ids only: arrivals and sizes are the mix's."""
    mix = harness.load_json("traffic", "chat")
    a, b = _schedule(1, mix), _schedule(2 ** 31 + 7, mix)
    assert len(a) == len(b) == round(3.2 * 10) + round(3.2 * 51) + \
        round(3.2 * 60)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in a] \
        == [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert _schedule(1, mix) == a
    for r in a:
        assert 32 <= len(r["prompt"]) <= 1024
        assert 32 <= r["max_new_tokens"] <= 512


def test_scenes_cycle_in_a_seeded_order():
    mix = harness.load_json("traffic", "scene16")
    gen = harness.load_module("traffic", mix["generator"])
    orders = [gen.order(mix, np.random.default_rng(s)) for s in (3, 4)]
    for o in orders:
        assert sorted(o.tolist()) == list(range(mix["scenes"]))
    assert orders[0].tolist() != orders[1].tolist()
