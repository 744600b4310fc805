"""Quickstart: the paper's core op as a composable JAX module.

Runs the HPDP-style quantized conv+requant backend on one Ship-Detection
layer, verifies it against the float reference, then shows the same
parameter-driven design for a transformer qlinear — the "configure once,
stream parameters" idea that lets one compiled kernel serve every layer.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.core import quant

print("=" * 70)
print("1. Paper's op: int8 conv + fused requantization (one compiled config,")
print("   weights/bias/requant params are runtime operands)")
print("=" * 70)

rng = np.random.default_rng(0)
# a reduced Table-1 layer: 24×3×3×24 on a 24×24×24 map
x = jnp.asarray(rng.standard_normal((1, 24, 24, 24)), jnp.float32) * 0.5
w = jnp.asarray(rng.standard_normal((3, 3, 24, 24)), jnp.float32) * 0.2
b = jnp.asarray(rng.standard_normal((24,)), jnp.float32) * 0.1

params = kernels.make_qconv_params(w, b)          # int8 weights + colsum
y_float = jax.lax.conv_general_dilated(
    x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

# calibrated activation qparams (min/max observer, as in core.quant)
x_scale, x_zp = quant.affine_qparams(float(x.min()), float(x.max()))
out_scale, out_zp = quant.affine_qparams(float(y_float.min()),
                                         float(y_float.max()))

y = kernels.qconv_act(x, params, x_scale, x_zp, out_scale, out_zp,
                        use_kernel=True)
err = float(jnp.abs(y - y_float).max())
print(f"conv out {y.shape}, max |int8 path − float path| = {err:.4f} "
      f"(≤ a few quantization steps of {float(out_scale):.4f})")
assert err < 6 * float(out_scale)

# same compiled configuration, NEW layer parameters — no recompilation
w2 = jnp.asarray(rng.standard_normal((3, 3, 24, 24)), jnp.float32) * 0.3
params2 = kernels.make_qconv_params(w2, b)
y2 = kernels.qconv_act(x, params2, x_scale, x_zp, out_scale, out_zp,
                         use_kernel=True)
print(f"second layer through the SAME kernel config: out {y2.shape} ✓")

print()
print("=" * 70)
print("2. Transformer-shaped rendition: int8 qlinear with fused requant")
print("=" * 70)
xt = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
wt = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32) * 0.1
lp = kernels.make_qlinear_params(wt)
xs, xzp = quant.affine_qparams(float(xt.min()), float(xt.max()))
os_, ozp = quant.affine_qparams(-8.0, 8.0)
yt = kernels.qlinear_act(xt, lp, xs, xzp, os_, ozp,
                             use_kernel=True)
yt_ref = xt @ wt
rel = float(jnp.linalg.norm(yt - yt_ref) / jnp.linalg.norm(yt_ref))
print(f"qlinear out {yt.shape}, relative error vs float = {rel:.4f}")
assert rel < 0.05

print()
print("=" * 70)
print("3. Dependability: exact integer ABFT catches an injected SEU")
print("=" * 70)
from repro.core import abft

x_q = jnp.asarray(rng.integers(-128, 128, (16, 64)), jnp.int8)
w_q = jnp.asarray(rng.integers(-127, 128, (64, 32)), jnp.int8)
acc = jnp.einsum("mk,kn->mn", x_q.astype(jnp.int32), w_q.astype(jnp.int32))
flipped = acc.at[3, 7].add(1 << 12)                  # single bit flip
wc = abft.checksum_vector(w_q)
clean_rows = abft.verify_rows(x_q, flipped, wc)      # True == clean
flagged = np.flatnonzero(~np.asarray(clean_rows))
print(f"ABFT flagged rows: {flagged} (expected [3])")
assert list(flagged) == [3]
res = abft.abft_qmatmul(x_q, jnp.int32(0), w_q, jnp.zeros((32,), jnp.int32),
                        inject=lambda a: a.at[3, 7].add(1 << 12))
np.testing.assert_array_equal(np.asarray(res.acc), np.asarray(acc))
print("recomputed flagged rows → output exact despite the fault ✓")

print("\nquickstart OK")
