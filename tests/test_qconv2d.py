"""qconv2d Pallas kernel vs pure-jnp oracle — the paper's validation (Fig. 4).

Sweeps cover the exact Table-1 layer geometries from the paper plus stride,
padding, ragged channel counts, and hypothesis-driven random cases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import quant
from repro.kernels.qconv2d import ops
from repro.kernels.qconv2d.ref import qconv2d_ref

jax.config.update("jax_platform_name", "cpu")

# The paper's Table-1 layers: (kernel: Cout x KH x KW x Cin, image: H x W x Cin)
# exercised at reduced spatial size for test speed; the benchmark harness runs
# the full sizes.
PAPER_LAYERS = [
    # (kh, kw, cin, cout, h, w)
    (3, 3, 24, 24, 48, 48),     # 24x3x3x24 @ 194x194x24 (reduced spatially)
    (3, 3, 48, 48, 24, 24),     # 48x3x3x48 @ 98x98x48
    (3, 3, 96, 96, 12, 12),     # 96x3x3x96 @ 50x50x96
    (1, 1, 96, 96, 24, 24),     # 96x1x1x96 @ 96x96x96
]


def _random_conv_case(rng, n, h, w, cin, kh, kw, cout):
    x_q = jnp.asarray(rng.integers(-128, 128, (n, h, w, cin), dtype=np.int32), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (kh, kw, cin, cout), dtype=np.int32), jnp.int8)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=(0, 1, 2))
    bias = jnp.asarray(rng.integers(-1000, 1000, (cout,), dtype=np.int32))
    scale = jnp.asarray(rng.uniform(1e-4, 5e-3, (cout,)).astype(np.float32))
    x_zp = jnp.int32(int(rng.integers(-10, 10)))
    out_zp = jnp.int32(int(rng.integers(-10, 10)))
    return x_q, w_q, colsum, bias, scale, x_zp, out_zp


@pytest.mark.parametrize("kh,kw,cin,cout,h,w", PAPER_LAYERS)
def test_paper_table1_layers(kh, kw, cin, cout, h, w):
    rng = np.random.default_rng(kh * 100 + cin)
    x_q, w_q, colsum, bias, scale, x_zp, out_zp = _random_conv_case(
        rng, 1, h, w, cin, kh, kw, cout)
    got = ops.qconv2d_op(x_q, x_zp, w_q, colsum, bias, scale, out_zp,
                         stride=(1, 1), padding="SAME",
                         use_kernel=True)
    want = qconv2d_ref(x_q, x_zp, w_q, bias, scale, out_zp,
                       stride=(1, 1), padding="SAME")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("stride", [(1, 1), (2, 2), (2, 1)])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_stride_padding_sweep(stride, padding):
    rng = np.random.default_rng(7)
    x_q, w_q, colsum, bias, scale, x_zp, out_zp = _random_conv_case(
        rng, 2, 17, 19, 8, 3, 3, 16)
    got = ops.qconv2d_op(x_q, x_zp, w_q, colsum, bias, scale, out_zp,
                         stride=stride, padding=padding,
                         use_kernel=True)
    want = qconv2d_ref(x_q, x_zp, w_q, bias, scale, out_zp,
                       stride=stride, padding=padding)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_qconv2d_random_cases(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 3))
    h = int(rng.integers(4, 20))
    w = int(rng.integers(4, 20))
    cin = int(rng.integers(1, 32))
    cout = int(rng.integers(1, 48))
    kh = int(rng.choice([1, 3, 5]))
    kw = int(rng.choice([1, 3]))
    if kh > h or kw > w:
        kh, kw = 1, 1
    x_q, w_q, colsum, bias, scale, x_zp, out_zp = _random_conv_case(
        rng, n, h, w, cin, kh, kw, cout)
    got = ops.qconv2d_op(x_q, x_zp, w_q, colsum, bias, scale, out_zp,
                         stride=(1, 1), padding="SAME",
                         use_kernel=True)
    want = qconv2d_ref(x_q, x_zp, w_q, bias, scale, out_zp,
                       stride=(1, 1), padding="SAME")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qconv_act_end_to_end_accuracy():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 24, 24)).astype(np.float32) * 0.05)
    b = jnp.asarray(rng.normal(size=(24,)).astype(np.float32) * 0.1)
    params = ops.make_qconv_params(w, b)
    y_f = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    x_scale, x_zp = quant.affine_qparams(jnp.min(x), jnp.max(x))
    o_scale, o_zp = quant.affine_qparams(jnp.min(y_f), jnp.max(y_f))
    y_q = ops.qconv_act(x, params, x_scale, x_zp, o_scale, o_zp,
                        use_kernel=True)
    rel = np.linalg.norm(np.asarray(y_q - y_f)) / np.linalg.norm(np.asarray(y_f))
    assert rel < 0.02, rel
