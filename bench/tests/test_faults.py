"""A run with its timed path broken underneath comes out not correct.

Each test drives ``run.main`` on the CPU at the cells' reduced sizes
(``--rehearse-cpu`` skips the look for a chip; everything else is the run
as on the chip) with one fault planted in the program:

  * serving (one engine, and the four-replica fleet): a token altered
    where the decode stage produces it; a decode step that returns its
    cache unchanged;
  * frames: an answer altered where the network produces it; half of the
    batch left out (its maps copied from the other half).

The cells have no exchange between chips: the fleet's replicas share
nothing, so that fault has no place to be planted.
"""
import json

import jax.numpy as jnp
import pytest

import run


def _result(capsys, argv):
    assert run.main(argv + ["--rehearse-cpu"]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line.split(": ", 1)[1])


def _serve(capsys, seed=11, cell="smollm-chat"):
    return _result(capsys, ["--workload", cell, "--seed", str(seed),
                            "--seconds", "3", "--trace", "0"])


def _frames(capsys, seed=11):
    return _result(capsys, ["--workload", "shipdet-scene", "--seed",
                            str(seed), "--seconds", "2", "--trace", "0"])


def test_sound_runs_are_correct(capsys):
    assert _serve(capsys)["correct"]
    assert _serve(capsys, cell="smollm-fleet4-chat")["correct"]
    assert _frames(capsys)["correct"]


@pytest.mark.parametrize("cell", ["smollm-chat", "smollm-fleet4-chat"])
def test_altered_token(monkeypatch, capsys, cell):
    from repro.runtime import dataflow
    emit = dataflow.DecodeStage._emit

    def altered(self, req):
        req.output[-1] = (req.output[-1] + 1) % self.ex.cfg.vocab_size
        emit(self, req)

    monkeypatch.setattr(dataflow.DecodeStage, "_emit", altered)
    out = _serve(capsys, cell=cell)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_step_returns_state_unchanged(monkeypatch, capsys):
    from repro.models import api
    step = api.decode_step

    def stale(cfg, params, token, cache, ctx=None, embed=None):
        logits, _ = step(cfg, params, token, cache, ctx, embed=embed)
        return logits, cache

    monkeypatch.setattr(api, "decode_step", stale)
    assert not _serve(capsys)["correct"]


def test_altered_answer(monkeypatch, capsys):
    from repro.models import shipdet
    forward = shipdet.forward

    def altered(*a, **k):
        y, stats = forward(*a, **k)
        return y.at[0].set(jnp.roll(y[0], 1, axis=0)), stats

    monkeypatch.setattr(shipdet, "forward", altered)
    assert not _frames(capsys)["correct"]


def test_half_the_batch_left_out(monkeypatch, capsys):
    from repro.models import shipdet
    forward = shipdet.forward

    def half(specs, params, x, **k):
        h = x.shape[0] // 2
        y, stats = forward(specs, params, x[:h], **k)
        return jnp.concatenate([y, y]), stats

    monkeypatch.setattr(shipdet, "forward", half)
    assert not _frames(capsys)["correct"]
