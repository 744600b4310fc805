"""jit'd public wrapper for the qconv2d Pallas kernel.

Handles zero-point padding, parameter bundle preparation and kernel-vs-ref
dispatch.  The kernel row-tiles the image, so every size runs on it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.qconv2d.kernel import qconv2d as qconv2d_pallas
from repro.kernels.qconv2d.ref import qconv2d_ref


class QConvParams(NamedTuple):
    """Runtime parameter bundle for one conv layer (the HPDP 'stream inputs')."""

    w_q: jax.Array       # (KH, KW, Cin, Cout) int8
    w_scale: jax.Array   # (Cout,) f32
    colsum: jax.Array    # (Cout,) int32
    bias_f: jax.Array    # (Cout,) f32


def make_qconv_params(w: jax.Array, bias: jax.Array | None = None) -> QConvParams:
    qt = quant.quantize_weight(w, axis=-1)
    colsum = jnp.sum(qt.q.astype(jnp.int32), axis=(0, 1, 2))
    if bias is None:
        bias = jnp.zeros((w.shape[-1],), jnp.float32)
    return QConvParams(qt.q, qt.scale, colsum, bias.astype(jnp.float32))


def _same_pads(h: int, w: int, kh: int, kw: int, sh: int, sw: int):
    oh = -(-h // sh)
    ow = -(-w // sw)
    ph = max((oh - 1) * sh + kh - h, 0)
    pw = max((ow - 1) * sw + kw - w, 0)
    return ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2))


@functools.partial(jax.jit, static_argnames=("stride", "padding", "use_kernel"))
def qconv2d_op(
    x_q: jax.Array, x_zp: jax.Array, w_q: jax.Array, colsum: jax.Array,
    bias_i32: jax.Array, scale: jax.Array, out_zp: jax.Array,
    *, stride: Tuple[int, int] = (1, 1), padding: str = "SAME",
    use_kernel: bool = True,
) -> jax.Array:
    """int8 NHWC in → int8 NHWC out quantized conv+requant."""
    n, h, w, cin = x_q.shape
    kh, kw, _, cout = w_q.shape
    sh, sw = stride
    if padding == "SAME":
        pads = _same_pads(h, w, kh, kw, sh, sw)
    elif padding == "VALID":
        pads = ((0, 0), (0, 0))
    else:
        pads = tuple(padding)

    if use_kernel:
        # zero-point padding: padded taps contribute (x_zp - x_zp)·w == 0,
        # i.e. padding with the zp value is exactly "pad with real 0.0"
        xp = jax.lax.pad(
            x_q, x_zp.astype(jnp.int8),
            ((0, 0, 0),
             (pads[0][0], pads[0][1], 0),
             (pads[1][0], pads[1][1], 0),
             (0, 0, 0)),
        )
        zps = jnp.stack([x_zp.astype(jnp.int32), out_zp.astype(jnp.int32)])
        return qconv2d_pallas(xp, w_q, colsum, bias_i32, scale, zps,
                              stride=stride)
    return qconv2d_ref(x_q, x_zp, w_q, bias_i32, scale, out_zp,
                       stride=stride, padding=pads if padding not in ("SAME", "VALID") else padding)


def qconv_act(
    x: jax.Array,                 # (N, H, W, Cin) float
    params: QConvParams,
    x_scale: jax.Array, x_zp: jax.Array,
    out_scale: jax.Array, out_zp: jax.Array,
    *, stride: Tuple[int, int] = (1, 1), padding: str = "SAME",
    use_kernel: bool = False,
) -> jax.Array:
    """float → int8 conv+requant → float, integer arithmetic in between."""
    x_q = quant.quantize(x, x_scale, x_zp)
    bias_i32 = jnp.round(params.bias_f / (x_scale * params.w_scale)).astype(jnp.int32)
    rq_scale = quant.requant_scale(x_scale, params.w_scale, out_scale)
    y_q = qconv2d_op(x_q, x_zp, params.w_q, params.colsum, bias_i32, rq_scale,
                     out_zp, stride=stride, padding=padding,
                     use_kernel=use_kernel)
    return (y_q.astype(jnp.float32) - out_zp.astype(jnp.float32)) * out_scale
