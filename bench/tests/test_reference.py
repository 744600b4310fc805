"""The plain float32 references against the program, at reduced sizes on
the CPU, and the controls (the reference one precision step lower) against
the same readings."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from drivers import frames, serve
from reference import shipdet as ref_shipdet
from reference import smollm as ref_smollm


@pytest.fixture(scope="module")
def smollm_setup():
    cell = harness.Cell.load("smollm-chat")
    arch = serve.arch_config(cell.config, rehearse=True)
    params = serve.make_weights(arch, jax.random.key(11))
    return cell, arch, params


def _served(cell, arch, params, backend):
    """Two requests served through the engine with the cell's policy map."""
    from repro.runtime.serving import Engine, Request
    eng = Engine(arch, params, backend=backend,
                 policy_map=cell.config["serving"]["policy_map"],
                 capacity=2, max_len=256, prefill_pad=32, multi_step=4,
                 snapshot_every=8)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i, prompt=rng.integers(1, arch.vocab_size, n).tolist(),
                    max_new_tokens=m) for i, (n, m) in enumerate([(40, 24),
                                                                  (90, 16)])]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs


def test_smollm_logits_match_program(smollm_setup):
    """Prefill logits of the program against the reference.  The program
    runs bf16 operands (2^-8 relative rounding per operation) and int8
    feed-forward activations per row (up to max|x|/254 per element), so its
    logits carry an error of a few percent of their spread; a fifth of the
    reference logits' standard deviation bounds it with room (measured
    about an eighth at these sizes)."""
    from repro.models import api
    cell, arch, params = smollm_setup
    toks = jnp.asarray(np.random.default_rng(0).integers(
        1, arch.vocab_size, 48), jnp.int32)
    arch_p = dataclasses.replace(arch, backend="pallas")
    logits, _ = api.prefill(arch_p, params, toks[None], 256)
    ref = ref_smollm.logits(ref_smollm.dims(serve.reference_dims(arch)),
                            params, toks)
    err = float(jnp.abs(logits[0].astype(jnp.float32) - ref).max())
    assert err <= 0.2 * float(ref.std()), (err, float(ref.std()))


def test_smollm_served_tokens_and_control(smollm_setup):
    """Tokens released through prefill and int8-cache decode lie within a
    small gap of the reference's best (bf16 and int8 rounding can reorder
    near-ties only), and the control (the reference in float8 and int4)
    lies at least three times further off: the check separates them."""
    cell, arch, params = smollm_setup
    reqs = _served(cell, arch, params, "pallas")
    dims = serve.reference_dims(arch)
    prog = max(float(ref_smollm.gaps(dims, params, r.prompt, r.output,
                                     pad_to=128).max()) for r in reqs)
    ctrl = max(float(ref_smollm.gaps(dims, params, r.prompt, r.output,
                                     pad_to=128, lower=True).max())
               for r in reqs)
    limit = cell.spec["rehearsal"]["check"]["max_logit_gap"]
    assert prog <= limit < ctrl, (prog, limit, ctrl)
    assert ctrl >= 3 * max(prog, 1e-3), (prog, ctrl)


def test_shipdet_map_and_control():
    """The program's detection map against the reference, in output steps
    (0.05).  The program quantizes the tile to steps of 0.05 and every
    layer's output to int8 steps of 0.05, rounding to nearest, so each of
    the 8 layers adds up to half a step that the next layers carry on.  The
    cell's limit (25 steps) lies between what the program reads (3.6-6.0 at
    the cell's size on a TPU v5e, 3-5 here) and what the control reads
    (int4 weights and activations: 60 and more there, about 40 here)."""
    from repro.core.dependability import Policy
    from repro.models import shipdet
    cell = harness.Cell.load("shipdet-scene")
    cfg = cell.config
    tile = cell.spec["rehearsal"]["tile"]
    w = ref_shipdet.make_weights(cfg, jax.random.key(5))
    params = frames.program_params(w)
    specs = frames.program_specs(cfg, tile)
    x = jax.random.uniform(jax.random.key(6), (2, tile, tile, 3))
    y, stats = shipdet.forward(specs, params, x, policy=Policy.ABFT,
                               backend="pallas",
                               w_checks=shipdet.deploy_checks(params))
    step = cfg["activation_scale"]
    prog = float(jnp.abs(y - ref_shipdet.forward(cfg, w, x)).max()) / step
    ctrl = float(jnp.abs(ref_shipdet.forward(cfg, w, x, lower=True)
                         - ref_shipdet.forward(cfg, w, x)).max()) / step
    assert int(stats["faults_detected"]) == 0
    assert int(stats["checks_run"]) == len(specs)
    assert prog <= cell.spec["check"]["max_output_steps"] < ctrl, (prog, ctrl)
    assert ctrl >= 3 * prog
