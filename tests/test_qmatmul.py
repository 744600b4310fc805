"""qmatmul Pallas kernel vs pure-jnp oracle — shape/dtype/qparam sweeps.

This reproduces the paper's validation methodology (Fig. 4): the kernel
executed under the Pallas interpreter (the stand-in for the HPDP cycle-level
simulator) is numerically compared against an independently implemented
reference, inside a unit-test framework.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quant
from repro.kernels.qmatmul.kernel import qmatmul
from repro.kernels.qmatmul.ref import qmatmul_acc_ref, qmatmul_ref
from repro.kernels.qmatmul import ops

jax.config.update("jax_platform_name", "cpu")


def _random_case(rng, m, k, n):
    x_q = jnp.asarray(rng.integers(-128, 128, size=(m, k), dtype=np.int32), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, size=(k, n), dtype=np.int32), jnp.int8)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0)
    bias = jnp.asarray(rng.integers(-1000, 1000, size=(n,), dtype=np.int32))
    scale = jnp.asarray(rng.uniform(1e-4, 2e-2, size=(n,)).astype(np.float32))
    x_zp = jnp.int32(int(rng.integers(-10, 10)))
    out_zp = jnp.int32(int(rng.integers(-10, 10)))
    return x_q, w_q, colsum, bias, scale, x_zp, out_zp


SHAPES = [
    (8, 16, 8),          # tiny
    (128, 128, 128),     # exactly one block
    (256, 512, 384),     # multi-block all dims
    (1, 4096, 128),      # decode-like (M=1)
    (130, 257, 129),     # ragged — exercises padding/masking
]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_qmatmul_kernel_matches_ref(m, k, n):
    rng = np.random.default_rng(m * 7919 + k * 31 + n)
    x_q, w_q, colsum, bias, scale, x_zp, out_zp = _random_case(rng, m, k, n)
    zps = jnp.stack([x_zp, out_zp])

    got = qmatmul(x_q, w_q, colsum, bias, scale, zps)
    want = qmatmul_ref(x_q, x_zp, w_q, bias, scale, out_zp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bm,bn,bk", [(32, 32, 64), (64, 128, 128), (128, 64, 32)])
def test_qmatmul_block_shape_sweep(bm, bn, bk):
    rng = np.random.default_rng(42)
    x_q, w_q, colsum, bias, scale, x_zp, out_zp = _random_case(rng, 96, 160, 96)
    zps = jnp.stack([x_zp, out_zp])
    got = qmatmul(x_q, w_q, colsum, bias, scale, zps,
                  block_m=bm, block_n=bn, block_k=bk)
    want = qmatmul_ref(x_q, x_zp, w_q, bias, scale, out_zp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_qmatmul_acc_int_exact_vs_numpy(seed):
    """int32 accumulator path is exact vs int64 numpy (no hidden float)."""
    rng = np.random.default_rng(seed)
    m, k, n = (int(rng.integers(1, 64)) for _ in range(3))
    x_q, w_q, colsum, bias, scale, x_zp, out_zp = _random_case(rng, m, k, n)
    acc = qmatmul_acc_ref(x_q, x_zp, w_q, bias)
    want = (np.asarray(x_q, np.int64) - int(x_zp)) @ np.asarray(w_q, np.int64) \
        + np.asarray(bias, np.int64)
    np.testing.assert_array_equal(np.asarray(acc, np.int64), want)


def test_qlinear_act_end_to_end_accuracy():
    """float→int8→float round trip approximates the float matmul."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32) * 0.05)
    b = jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * 0.1)

    params = ops.make_qlinear_params(w, b)
    y_f = x @ w + b
    x_scale, x_zp = quant.affine_qparams(jnp.min(x), jnp.max(x))
    o_scale, o_zp = quant.affine_qparams(jnp.min(y_f), jnp.max(y_f))

    y_q = ops.qlinear_act(x, params, x_scale, x_zp, o_scale, o_zp,
                          use_kernel=True)
    rel = np.linalg.norm(np.asarray(y_q - y_f)) / np.linalg.norm(np.asarray(y_f))
    assert rel < 0.02, rel


def test_qlinear_bf16out_matches_float():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(128, 32)).astype(np.float32) * 0.02)
    params = ops.make_qlinear_params(w)
    x_scale, x_zp = quant.affine_qparams(jnp.min(x), jnp.max(x))
    y = ops.qlinear_int8_bf16out(x, params, x_scale, x_zp)
    y_f = x @ w
    rel = np.linalg.norm(np.asarray(y - y_f)) / np.linalg.norm(np.asarray(y_f))
    assert rel < 0.02, rel
