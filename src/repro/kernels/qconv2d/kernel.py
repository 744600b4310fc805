"""Pallas TPU kernel: int8 NHWC conv2d + fused requantization.

The paper's core contribution is this exact op on the HPDP's XPP dataflow
array: convolution and re-quantization executing *in parallel on the stream*,
configured once, driven by runtime parameters (weights, bias, activations,
requant params).  TPU adaptation:

  * The XPP's 4D-DMA complex addressing → a shifted-window direct
    convolution over a flattened image.  The wrapper splits the zero-point-
    padded input into its stride phases (phase (p, q) holds pixels
    (p + sh·r, q + sw·c)), so every tap of a strided conv is a stride-1
    window of one phase, and flattens each phase to (rows·cols, Cin).  Tap
    (i, j) is then one contiguous window of that matrix at a static row
    offset, feeding one int8 MXU matmul (rows, Cin) × (Cin, Cout_tile).
    Output pixels are computed at every phase column and the wrapper crops
    the columns past the output width.  No strided value slice and no
    3-D → 2-D reshape happen in the kernel, and there is no im2col in HBM.
  * Row tiles: each grid step holds one slab of ``block_rows`` output rows
    plus the halo its taps reach into (the wrapper materializes the small
    overlap), so the per-step working set is bounded at any image size.
  * Zero-point padding: the caller pads the input with x_zp, so padded taps
    contribute exactly zero after the zero-point correction (standard
    integer-conv identity, also what the HPDP bias path folds in).
  * Requantization is fused in the epilogue — int32 accumulator never leaves
    VMEM (the paper: "these two operations process the data stream in
    parallel, ensuring continuous execution without introducing additional
    delays").
  * ABFT: the checksum variant appends the four int8 limbs of the Cout-
    summed check filter (``abft.int8_limbs``) as four extra output channels
    of the same MXU matmul — the Huang–Abraham column-checksum matrix.  The
    check accumulates in its own columns, independent of the Cout columns
    it verifies.

Taps (KH·KW) are unrolled in Python — static 1–9 iterations for the paper's
1×1/3×3 layers, each a dense MXU call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import abft
from repro.device import pallas_call

_PARALLEL3 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _tap_acc(x_ref, w_ref, *, stride, wph, rows):
    """Shifted-window tap loop over the flattened stride phases."""
    sh, sw = stride
    kh, kw = w_ref.shape[0], w_ref.shape[1]
    acc = None
    for i in range(kh):
        for j in range(kw):
            phase = (i % sh) * sw + j % sw
            off = (i // sh) * wph + j // sw
            part = jax.lax.dot_general(
                x_ref[0, 0, phase, pl.ds(off, rows), :], w_ref[i, j],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = part if acc is None else acc + part
    return acc


def _qconv2d_acc_kernel(x_ref, w_ref, colsum_ref, zp_ref, out_ref,
                        *, stride, wph, rows):
    acc = _tap_acc(x_ref, w_ref, stride=stride, wph=wph, rows=rows)
    # conv(x_p - zp, w) == conv(x_p, w) - zp * sum(w): every output pixel
    # covers all kh·kw·cin taps because x is pre-padded with the zero point
    out_ref[0, 0] = acc - zp_ref[0] * colsum_ref[...]


def _qconv2d_kernel(x_ref, w_ref, colsum_ref, bias_ref, scale_ref, zps_ref,
                    out_ref, *, stride, wph, rows):
    acc = _tap_acc(x_ref, w_ref, stride=stride, wph=wph, rows=rows)
    x_zp = zps_ref[0]
    out_zp = zps_ref[1]
    acc = acc - x_zp * colsum_ref[...] + bias_ref[...]
    y = acc.astype(jnp.float32) * scale_ref[...]
    y = jnp.round(y) + out_zp.astype(jnp.float32)
    out_ref[0, 0] = jnp.clip(y, -128.0, 127.0).astype(jnp.int8)


def _fit(a, length, axis):
    """Crop or zero-pad ``a`` along ``axis`` to exactly ``length``."""
    a = jax.lax.slice_in_dim(a, 0, min(length, a.shape[axis]), axis=axis)
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, length - a.shape[axis])
    return jnp.pad(a, pad)


def _conv_slabs(xp, kh, kw, stride, block_rows):
    """Phase-split, flatten and row-tile the zp-padded input.

    Returns (slabs, geometry): slabs (N, T, sh·sw, rows + halo, Cin), where
    tile t holds flattened phase rows [t·rows, (t+1)·rows + halo)."""
    n, hp, wp, cin = xp.shape
    sh, sw = stride
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sw + 1
    hph, wph = -(-hp // sh), -(-wp // sw)
    # cells past the image only ever feed cropped output columns/rows
    xp = _fit(_fit(xp, hph * sh, 1), wph * sw, 2)
    phases = xp.reshape(n, hph, sh, wph, sw, cin).transpose(0, 2, 4, 1, 3, 5)
    flat = phases.reshape(n, sh * sw, hph * wph, cin)
    th = max(1, min(oh, block_rows // wph))
    t = -(-oh // th)
    rows = th * wph
    halo = ((kh - 1) // sh) * wph + (kw - 1) // sw
    extra = -(-halo // rows)
    flat = _fit(flat, (t + extra) * rows, 2)
    shifted = [flat[:, :, s * rows:(s + t) * rows].reshape(
        n, sh * sw, t, rows, cin) for s in range(extra + 1)]
    slabs = jnp.concatenate(shifted, axis=3)[:, :, :, :rows + halo]
    return slabs.transpose(0, 2, 1, 3, 4), (oh, ow, wph, th, t, rows)


def _conv_call(kernel, xp, w_q, operands, operand_specs, out_dtype, *,
               stride, block_cout, block_rows, name):
    """Run one conv kernel over the slabs; crop back to (N, OH, OW, Cout).
    ``name`` names the kernel call in the compiled program."""
    n = xp.shape[0]
    kh, kw, cin, cout = w_q.shape
    assert xp.shape[3] == cin, (xp.shape, w_q.shape)
    slabs, (oh, ow, wph, th, t, rows) = _conv_slabs(xp, kh, kw, stride,
                                                     block_rows)
    block_cout = min(block_cout, cout)
    out = pallas_call(
        functools.partial(kernel, stride=stride, wph=wph, rows=rows),
        name=name,
        grid=(n, t, pl.cdiv(cout, block_cout)),
        in_specs=[
            pl.BlockSpec((1, 1) + slabs.shape[2:],
                         lambda b, r, c: (b, r, 0, 0, 0)),
            pl.BlockSpec((kh, kw, cin, block_cout),
                         lambda b, r, c: (0, 0, 0, c)),
        ] + operand_specs(block_cout),
        out_specs=pl.BlockSpec((1, 1, rows, block_cout),
                               lambda b, r, c: (b, r, 0, c)),
        out_shape=jax.ShapeDtypeStruct((n, t, rows, cout), out_dtype),
        compiler_params=_PARALLEL3,
    )(slabs, w_q, *operands)
    return out.reshape(n, t * th, wph, cout)[:, :oh, :ow, :]


def _row_spec(block_cout):
    return pl.BlockSpec((1, block_cout), lambda b, r, c: (0, c))


_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(
    jax.jit, static_argnames=("stride", "block_cout", "block_rows")
)
def qconv2d_acc(
    x_q: jax.Array,          # (N, Hp, Wp, Cin) int8 — already zp-padded
    w_q: jax.Array,          # (KH, KW, Cin, Cout) int8
    colsum: jax.Array,       # (Cout,) int32 — sum over (KH, KW, Cin)
    zp: jax.Array,           # (1,) int32 — input zero point
    *,
    stride: tuple = (1, 1),
    block_cout: int = 128,
    block_rows: int = 2048,
) -> jax.Array:
    """Raw int32 conv accumulator conv(x - zp, w) — backend-registry entry.

    ``block_rows`` bounds the flattened output rows (output rows × phase
    width) one grid step computes."""
    return _acc_call(x_q, w_q, colsum, zp, stride=stride,
                     block_cout=block_cout, block_rows=block_rows,
                     name="qconv2d_acc")


def _acc_call(x_q, w_q, colsum, zp, *, stride, block_cout, block_rows,
              name):
    return _conv_call(
        _qconv2d_acc_kernel, x_q, w_q, (colsum.reshape(1, -1), zp),
        lambda bc: [_row_spec(bc), _SCALARS], jnp.int32,
        stride=stride, block_cout=block_cout, block_rows=block_rows,
        name=name)


@functools.partial(
    jax.jit, static_argnames=("stride", "block_cout", "block_rows")
)
def qconv2d_acc_checksum(
    x_q: jax.Array,          # (N, Hp, Wp, Cin) int8 — already zp-padded
    w_q: jax.Array,          # (KH, KW, Cin, Cout) int8
    colsum: jax.Array,       # (Cout,) int32
    w_check: jax.Array,      # (KH, KW, Cin, 1) int32 — conv_checksum_weight(w)
    zp: jax.Array,           # (1,) int32
    *,
    stride: tuple = (1, 1),
    block_cout: int = 128,
    block_rows: int = 2048,
):
    """(acc, want): conv accumulator plus the fused per-pixel ABFT channel.

    want (N, OH, OW) i32 equals the Cout-sum of acc mod 2^32 on a fault-free
    pass; see core/abft.abft_qconv2d.  The check filter's int8 limbs ride as
    four extra output channels of the one kernel call."""
    cout = w_q.shape[3]
    limbs = abft.int8_limbs(w_check[..., 0])              # (KH, KW, Cin, 4)
    w_ext = jnp.concatenate([w_q, limbs], axis=3)
    colsum_ext = jnp.concatenate(
        [colsum, jnp.sum(limbs.astype(jnp.int32), axis=(0, 1, 2))])
    acc = _acc_call(x_q, w_ext, colsum_ext, zp, stride=stride,
                    block_cout=block_cout, block_rows=block_rows,
                    name="qconv2d_acc_checksum")
    return acc[..., :cout], abft.from_limbs(acc[..., cout:])


@functools.partial(
    jax.jit, static_argnames=("stride", "block_cout", "block_rows")
)
def qconv2d(
    x_q: jax.Array,          # (N, Hp, Wp, Cin) int8 — already zp-padded
    w_q: jax.Array,          # (KH, KW, Cin, Cout) int8
    colsum: jax.Array,       # (Cout,) int32 — sum over (KH, KW, Cin)
    bias: jax.Array,         # (Cout,) int32
    scale: jax.Array,        # (Cout,) f32
    zps: jax.Array,          # (2,) int32 — [x_zp, out_zp]
    *,
    stride: tuple = (1, 1),
    block_cout: int = 128,
    block_rows: int = 2048,
) -> jax.Array:
    return _conv_call(
        _qconv2d_kernel, x_q, w_q,
        (colsum.reshape(1, -1), bias.reshape(1, -1), scale.reshape(1, -1),
         zps),
        lambda bc: [_row_spec(bc)] * 3 + [_SCALARS], jnp.int8,
        stride=stride, block_cout=block_cout, block_rows=block_rows,
        name="qconv2d")
