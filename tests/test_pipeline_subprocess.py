"""Run the pipeline-parallel correctness check under 8 fake CPU devices.

The main pytest process must keep the default single-device view (smoke
tests and benches depend on it), so multi-device pipeline coverage runs in
a subprocess with XLA_FLAGS set — the same trick launch/dryrun.py uses.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.parallel import pipeline as pp

mesh = make_mesh((4,), ("stage",))
d, n_micro, mb = 8, 6, 2
ks = jax.random.split(jax.random.key(0), 4)
plist = [{"w": jax.random.normal(k, (d, d)) * 0.3, "b": jnp.zeros((d,))}
         for k in ks]
stage_fn = lambda p, x: jnp.tanh(x @ p["w"] + p["b"])
stacked = pp.stack_stage_params(plist)
x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))

got = pp.pipeline_apply(stage_fn, stacked, x, mesh)
want = x
for p in plist:
    want = jax.vmap(lambda m: stage_fn(p, m))(want)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-5, atol=1e-5)

# grads flow through ppermute and match sequential
def loss(params):
    return jnp.mean(pp.pipeline_apply(stage_fn, params, x, mesh) ** 2)
g = jax.grad(loss)(stacked)

def seq_loss(pl):
    out = x
    for p in pl:
        out = jax.vmap(lambda m: stage_fn(p, m))(out)
    return jnp.mean(out ** 2)
g_seq = pp.stack_stage_params(jax.grad(seq_loss)(plist))
jax.tree_util.tree_map(
    lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                            rtol=1e-4, atol=1e-5),
    g, g_seq)
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_pipeline_parallel_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_OK" in out.stdout
