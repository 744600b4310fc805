"""Pipeline parallelism (HPDP→HPDP chaining analogue): correctness vs
sequential execution, differentiability, bubble accounting."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.parallel import pipeline as pp

jax.config.update("jax_platform_name", "cpu")

N_DEV = jax.device_count()


def make_stage_params(key, n_stages, d):
    ks = jax.random.split(key, n_stages)
    return [{"w": jax.random.normal(k, (d, d)) * 0.3,
             "b": jnp.zeros((d,))} for k in ks]


def stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def sequential(param_list, mb):
    out = mb
    for p in param_list:
        out = jax.vmap(lambda m: stage_fn(p, m))(out)
    return out


@pytest.mark.skipif(N_DEV < 2, reason="needs >=2 devices (set XLA flag)")
def test_pipeline_matches_sequential():
    mesh = make_mesh((N_DEV,), ("stage",))
    n_stages, n_micro, mb, d = N_DEV, 6, 2, 8
    plist = make_stage_params(jax.random.key(0), n_stages, d)
    stacked = pp.stack_stage_params(plist)
    x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
    got = pp.pipeline_apply(stage_fn, stacked, x, mesh)
    want = sequential(plist, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(N_DEV < 2, reason="needs >=2 devices")
def test_pipeline_grads_flow():
    """Autodiff through ppermute: every stage's params get nonzero grads."""
    mesh = make_mesh((N_DEV,), ("stage",))
    n_stages, n_micro, mb, d = N_DEV, 4, 2, 8
    plist = make_stage_params(jax.random.key(0), n_stages, d)
    stacked = pp.stack_stage_params(plist)
    x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))

    def loss(params):
        out = pp.pipeline_apply(stage_fn, params, x, mesh)
        return jnp.mean(out ** 2)

    g = jax.grad(loss)(stacked)
    for leaf in jax.tree_util.tree_leaves(g):
        per_stage = np.asarray(jnp.sum(jnp.abs(leaf), axis=tuple(
            range(1, leaf.ndim))))
        assert (per_stage > 0).all(), "a stage got zero gradient"

    # gradient agrees with the sequential reference
    def seq_loss(plist_flat):
        out = sequential(plist_flat, x)
        return jnp.mean(out ** 2)

    g_seq = jax.grad(seq_loss)(plist)
    g_seq_stacked = pp.stack_stage_params(jax.tree_util.tree_map(
        lambda x: x, g_seq))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        g, g_seq_stacked)


def test_bubble_fraction():
    assert pp.bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert pp.bubble_fraction(2, 30) == pytest.approx(1 / 31)
    # more microbatches shrink the bubble monotonically
    fr = [pp.bubble_fraction(8, m) for m in (8, 16, 32, 64)]
    assert fr == sorted(fr, reverse=True)
