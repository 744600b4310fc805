"""Collective helpers (ring all-gather, reduce-scatter, bf16 grad compression)
vs their XLA-native equivalents, on 8 fake devices in a subprocess."""
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
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.parallel import collectives as coll

mesh = make_mesh((8,), ("x",))
x = jnp.arange(8 * 4 * 6, dtype=jnp.float32).reshape(8 * 4, 6)

# ring all-gather == native all-gather (every shard holds the full array,
# P() output = replicated)
ring_full = shard_map(lambda s: coll.ring_all_gather(s, "x", axis=0),
                      mesh=mesh, in_specs=P("x"), out_specs=P(),
                      check_vma=False)
native = shard_map(lambda s: jax.lax.all_gather(s, "x", axis=0, tiled=True),
                   mesh=mesh, in_specs=P("x"), out_specs=P(),
                   check_vma=False)
np.testing.assert_allclose(np.asarray(ring_full(x)), np.asarray(native(x)))
np.testing.assert_allclose(np.asarray(ring_full(x)), np.asarray(x))
print("RING_OK")

# reduce-scatter: sum over axis then scatter == psum sliced
rs = shard_map(lambda s: coll.reduce_scatter(s, "x", axis=0),
               mesh=mesh, in_specs=P(None), out_specs=P("x"),
               check_vma=False)(x)
np.testing.assert_allclose(np.asarray(rs), np.asarray(x) * 8)
print("RS_OK")

# bf16 grad compression: psum in bf16, correct up to bf16 rounding
g = {"w": jnp.ones((8, 4)) * 0.1}
out = shard_map(lambda t: coll.grad_allreduce_bf16(t, "x"),
                mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False)(g)
np.testing.assert_allclose(np.asarray(out["w"]), 0.8, rtol=2e-2)
assert out["w"].dtype == g["w"].dtype
print("GRADBF16_OK")
"""


@pytest.mark.slow
def test_collectives_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    for tag in ("RING_OK", "RS_OK", "GRADBF16_OK"):
        assert tag in out.stdout
