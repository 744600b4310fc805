"""The rules of ``repro.device``: where kernels run, which processes may
spawn workers, where the compile cache lives — and that the chip smoke and
the fleet keep to them."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import device
from repro.kernels.qmatmul.kernel import qmatmul_acc

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def _interpret_flags(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["interpret"]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _interpret_flags(inner)


def test_pallas_kernels_interpret_only_when_lowered_for_cpu():
    """Both variants are staged; the CPU lowering holds no Mosaic call."""
    x = jnp.ones((8, 128), jnp.int8)
    w = jnp.ones((128, 128), jnp.int8)
    jaxpr = jax.make_jaxpr(qmatmul_acc)(x, w).jaxpr
    assert sorted(_interpret_flags(jaxpr)) == [False, True]
    assert "tpu_custom_call" not in jax.jit(qmatmul_acc).lower(x, w).as_text()
    np.testing.assert_array_equal(np.asarray(qmatmul_acc(x, w)),
                                  np.full((8, 128), 128, np.int32))


def test_child_processes_refused_off_cpu(monkeypatch):
    from repro.campaign.engine import CampaignPool
    from repro.fleet.transport import WorkerHandle
    device.forbid_child_processes("test")          # the CPU is fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="campaign worker pool.*tpu"):
        CampaignPool(2)
    handle = WorkerHandle(0)
    with pytest.raises(RuntimeError, match="proc transport.*tpu"):
        handle.spawn()
    assert handle.proc is None                     # nothing was started


def test_compile_cache_follows_env_else_fixed_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert device.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = device.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_the_cpu():
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_env(JAX_PLATFORMS="cpu"), capture_output=True,
                         text=True, timeout=120)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


FLEET_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro.configs import registry
from repro.core.dependability import Policy
from repro.fleet import Fleet
from repro.models import api as model_api
from repro.models.config import reduced
from repro.runtime.serving import Request

cfg = reduced(registry.get("smollm-135m"))
params = model_api.init_params(cfg, jax.random.key(0))
prompts = [[5, 9, 2], [3, 1, 4, 1], [2, 7], [8, 8, 6], [1, 6, 1, 8]]
out = {}
for n in (4, 1):
    fleet = Fleet(cfg, params, n_replicas=n, policy=Policy.ABFT,
                  capacity=2, max_len=64, prefill_pad=8)
    for i, p in enumerate(prompts):
        fleet.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    fleet.run()
    out[n] = {
        "streams": [list(fleet.released[i].output)
                    for i in range(len(prompts))],
        "homes": [sorted({d.id for leaf in jax.tree_util.tree_leaves(
            (r.engine.params, r.engine.cache)) for d in leaf.devices()})
            for r in fleet.replicas]}
    fleet.close()
print(json.dumps(out))
"""


def test_fleet_replicas_on_distinct_devices():
    res = subprocess.run([sys.executable, "-c", FLEET_SCRIPT],
                         env=_env(JAX_PLATFORMS="cpu"), capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["4"]["homes"] == [[0], [1], [2], [3]]
    assert out["4"]["streams"] == out["1"]["streams"]
