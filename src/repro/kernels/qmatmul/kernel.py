"""Pallas TPU kernel: int8 matmul with int32 accumulation + fused requantization.

This is the transformer-shaped rendition of the paper's HPDP dataflow
configuration: *one* compiled kernel whose weights, bias, zero-points and
requantization scales are all runtime operands — every layer of every model
reuses the same configuration, exactly like the XPP array is configured once
and then driven purely by streamed parameters.

Design notes (TPU codesign):
  * int8 × int8 → int32 runs natively on the MXU (v5e: 394 TOPS int8, 2× bf16).
  * The K reduction is the innermost grid dimension; an int32 VMEM scratch
    accumulator carries partial sums across K steps (revisiting pattern).
  * Requantization is fused into the epilogue of the *last* K step: the
    accumulator never leaves VMEM — one HBM write of int8 output instead of
    int32 intermediate + separate requant pass (4× less traffic than an
    unfused pipeline, mirroring the paper's "conv and requant process the
    stream in parallel" design).
  * fp32 requantization (round-half-to-even) — see core/quant.py docstring.
  * Default blocks: (128, 128) output tile, K-block 512.  MXU-aligned
    (multiples of 128 on both matmul dims); working set 128·512 + 512·128 int8
    + 128·128 int32 acc ≈ 192 KiB — comfortable in 16 MiB VMEM with double
    buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import abft
from repro.device import pallas_call


def _qmatmul_kernel(x_ref, w_ref, colsum_ref, bias_ref, scale_ref, zps_ref,
                    out_ref, acc_ref, *, k_total: int):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _mask_k_tail(w_ref[...], k, k_total)

    # int8 × int8 → int32 on the MXU
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        x_zp = zps_ref[0]
        out_zp = zps_ref[1]
        acc = acc_ref[...]
        acc = acc - x_zp * colsum_ref[...] + bias_ref[...]
        y = acc.astype(jnp.float32) * scale_ref[...]
        y = jnp.round(y) + out_zp.astype(jnp.float32)
        out_ref[...] = jnp.clip(y, -128.0, 127.0).astype(jnp.int8)


def _mask_k_tail(block: jax.Array, k: jax.Array, k_total: int) -> jax.Array:
    """Zero the out-of-bounds rows of a padded K-tail block (undefined data
    must not pollute the reduction)."""
    block_k = block.shape[0]
    if k_total % block_k == 0:
        return block
    row = k * block_k + jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.where(row < k_total, block, 0)


def _qmatmul_acc_kernel(x_ref, w_ref, out_ref, *, k_total: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = _mask_k_tail(w_ref[...], k, k_total)
    out_ref[...] += jax.lax.dot_general(
        x_ref[...], w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _qmatmul_acc_checksum_kernel(x_ref, w_ref, limbs_ref, out_ref, check_ref,
                                 *, k_total: int):
    """Accumulator kernel with the ABFT check vector fused in: alongside each
    (block_m, block_k) × (block_k, block_n) MXU step, one extra (block_k, 4)
    int8 MXU product accumulates X · limbs(w_check) into a second output —
    the four int8 limbs of the int32 check vector (``abft.int8_limbs``), as
    the MXU takes no int32 operand.  Detection costs ~4/block_n extra work
    inside the kernel instead of a separate matvec pass over X."""
    n = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((k == 0) & (n == 0))
    def _init_check():
        check_ref[...] = jnp.zeros_like(check_ref)

    w = _mask_k_tail(w_ref[...], k, k_total)
    out_ref[...] += jax.lax.dot_general(
        x_ref[...], w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    # the check column is N-independent: accumulate it once per (m, k) tile
    @pl.when(n == 0)
    def _check():
        limbs = _mask_k_tail(limbs_ref[...], k, k_total)
        check_ref[...] += jax.lax.dot_general(
            x_ref[...], limbs,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )


def _acc_grid(M, N, K, block_m, block_n, block_k):
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    grid = (pl.cdiv(M, block_m), pl.cdiv(N, block_n), pl.cdiv(K, block_k))
    return grid, block_m, block_n, block_k


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k")
)
def qmatmul_acc(
    x_q: jax.Array,          # (M, K) int8
    w_q: jax.Array,          # (K, N) int8
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    """Raw int32 accumulator X·W — the backend-registry entry point.

    Unlike ``qmatmul`` the accumulator leaves the kernel, so the
    dependability layer can inject faults into it, checksum it, and share
    the zero-point/bias/requant epilogue across every backend."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    grid, block_m, block_n, block_k = _acc_grid(M, N, K, block_m, block_n,
                                                block_k)
    return pallas_call(
        functools.partial(_qmatmul_acc_kernel, k_total=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(x_q, w_q)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k")
)
def qmatmul_acc_checksum(
    x_q: jax.Array,          # (M, K) int8
    w_q: jax.Array,          # (K, N) int8
    w_check: jax.Array,      # (K,) int32 — deploy-time checksum_vector(w)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
):
    """(acc, want): accumulator plus the fused ABFT check vector.

    Returns acc (M, N) i32 and want (M,) i32 with want == rowsum(acc) mod
    2^32 on a fault-free pass; any single accumulator bit-flip breaks the
    identity exactly (see core/abft.py)."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    grid, block_m, block_n, block_k = _acc_grid(M, N, K, block_m, block_n,
                                                block_k)
    acc, parts = pallas_call(
        functools.partial(_qmatmul_acc_checksum_kernel, k_total=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
            pl.BlockSpec((block_k, 4), lambda m, n, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
            # revisited across n and k → n must be "arbitrary" below
            pl.BlockSpec((block_m, 4), lambda m, n, k: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int32),
            jax.ShapeDtypeStruct((M, 4), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(x_q, w_q, abft.int8_limbs(w_check))
    return acc, abft.from_limbs(parts)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k")
)
def qmatmul(
    x_q: jax.Array,          # (M, K) int8
    w_q: jax.Array,          # (K, N) int8
    colsum: jax.Array,       # (N,)  int32 — sum_k w_q[k, n]
    bias: jax.Array,         # (N,)  int32
    scale: jax.Array,        # (N,)  f32 — s_in * s_w / s_out (per-channel)
    zps: jax.Array,          # (2,)  int32 — [x_zp, out_zp]
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)

    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    grid = (pl.cdiv(M, block_m), pl.cdiv(N, block_n), pl.cdiv(K, block_k))

    return pallas_call(
        functools.partial(_qmatmul_kernel, k_total=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_k, block_n), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, block_n), lambda m, n, k: (0, n)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(x_q, w_q, colsum.reshape(1, -1), bias.reshape(1, -1),
      scale.reshape(1, -1), zps)
