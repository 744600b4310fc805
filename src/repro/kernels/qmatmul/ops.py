"""jit'd public wrapper around the qmatmul Pallas kernel.

``qlinear`` is the layer-level entry point used by the model zoo: it takes a
float activation + pre-quantized weight bundle and produces a float
activation, running the hot matmul entirely in int8/int32 (the paper's
technique), with requantization fused.

The kernel runs compiled on the TPU; lowered for the CPU it executes in
Pallas interpret mode (``repro.device.pallas_call``), the same "cycle-level
simulator stands in for hardware" methodology the paper uses (XDBG / HPDP
simulator vs the flight unit).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.qmatmul.kernel import qmatmul as qmatmul_pallas
from repro.kernels.qmatmul.ref import qmatmul_ref


class QLinearParams(NamedTuple):
    """Pre-quantized weight bundle for one linear layer (pytree-compatible)."""

    w_q: jax.Array       # (K, N) int8, per-output-channel symmetric
    w_scale: jax.Array   # (N,) f32
    colsum: jax.Array    # (N,) int32 — sum_k w_q
    bias_f: jax.Array    # (N,) f32 — kept in float; int32 bias derives per input scale


def make_qlinear_params(w: jax.Array, bias: jax.Array | None = None) -> QLinearParams:
    """Quantize a float (K, N) weight into the runtime parameter bundle."""
    qt = quant.quantize_weight(w, axis=-1)
    colsum = jnp.sum(qt.q.astype(jnp.int32), axis=0)
    if bias is None:
        bias = jnp.zeros((w.shape[-1],), jnp.float32)
    return QLinearParams(qt.q, qt.scale, colsum, bias.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def qmatmul_op(
    x_q: jax.Array, x_zp: jax.Array, w_q: jax.Array, colsum: jax.Array,
    bias_i32: jax.Array, scale: jax.Array, out_zp: jax.Array,
    *, use_kernel: bool = True,
) -> jax.Array:
    """int8 in → int8 out quantized matmul. Dispatches kernel vs jnp ref."""
    if use_kernel:
        zps = jnp.stack([x_zp.astype(jnp.int32), out_zp.astype(jnp.int32)])
        return qmatmul_pallas(x_q, w_q, colsum, bias_i32, scale, zps)
    return qmatmul_ref(x_q, x_zp, w_q, bias_i32, scale, out_zp)


def qlinear_act(
    x: jax.Array,                 # (..., K) float
    params: QLinearParams,
    x_scale: jax.Array, x_zp: jax.Array,       # calibrated input qparams
    out_scale: jax.Array, out_zp: jax.Array,   # calibrated output qparams
    *, use_kernel: bool = False,
) -> jax.Array:
    """float → [quantize] → int8 matmul+requant → [dequantize] → float.

    This is the "simulated quantized inference" layer API: models call it with
    calibrated static qparams; everything between quantize and dequantize is
    integer, exactly as executed on the HPDP / TPU MXU.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    x_q = quant.quantize(x.reshape(-1, K), x_scale, x_zp)
    bias_i32 = jnp.round(params.bias_f / (x_scale * params.w_scale)).astype(jnp.int32)
    rq_scale = quant.requant_scale(x_scale, params.w_scale, out_scale)
    y_q = qmatmul_op(x_q, x_zp, params.w_q, params.colsum, bias_i32, rq_scale,
                     out_zp, use_kernel=use_kernel)
    y = (y_q.astype(jnp.float32) - out_zp.astype(jnp.float32)) * out_scale
    return y.reshape(*lead, -1)


def qlinear_int8_bf16out(
    x: jax.Array,                 # (..., K) float (bf16/f32)
    params: QLinearParams,
    x_scale: jax.Array, x_zp: jax.Array,
) -> jax.Array:
    """W8A8 linear with float output (no output requantization).

    The serving fast path used by the LM archs: dynamic per-tensor activation
    quantization, int8 MXU matmul, fp32 dequantize epilogue.  XLA fuses the
    dequant into the matmul consumer; on TPU this hits the 394-TOPS int8 MXU
    path.  (The fully-quantized int8-chain variant above is the
    paper-faithful mode; this is the beyond-paper throughput mode.)
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    x_q = quant.quantize(x.reshape(-1, K), x_scale, x_zp)
    acc = jax.lax.dot_general(
        x_q, params.w_q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc = acc - x_zp.astype(jnp.int32) * params.colsum[None, :]
    y = acc.astype(jnp.float32) * (x_scale * params.w_scale)[None, :] + params.bias_f
    return y.reshape(*lead, -1).astype(x.dtype)
