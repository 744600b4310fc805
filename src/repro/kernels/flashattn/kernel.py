"""Pallas TPU kernel: fused causal (optionally sliding-window) attention.

Beyond-paper optimization in the paper's own spirit: the HPDP insight is
*keep the stream inside the array* — conv and requant execute back-to-back
with no HBM round-trip.  Prefill attention has the same structure at
transformer scale: QKᵀ → softmax → PV materializes an (S × S) score matrix
in HBM if done naively.  This kernel streams K/V blocks through VMEM with an
online-softmax accumulator, so scores never leave the chip.

TPU codesign notes:
  * Grid (B·H, S/bq, S/bk), K innermost ("arbitrary"); the (bq, hd) f32
    accumulator + (bq,) running max/denominator live in VMEM scratch across
    K steps (the same revisiting pattern as qmatmul's int32 accumulator).
  * Causality is exploited at *grid* granularity: blocks entirely above the
    diagonal are skipped via ``pl.when`` (≈2× prefill FLOPs saved), and
    entirely-valid blocks skip the mask computation.
  * GQA folds into the grid: q-head h reads kv-head h // (H/KV) via the
    K/V BlockSpec index_map — no KV replication in HBM.
  * Sliding window (mixtral, recurrentgemma local attn) masks per-element
    and skips out-of-window blocks at grid level.
  * bq = bk = 128 default: MXU-aligned; working set ≈ 128·hd·(3 f32) +
    128·128 f32 ≈ 0.3 MB for hd=128 — double-buffers comfortably in VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import pallas_call

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, seq_len: int, block_q: int, block_k: int,
                  window: int | None, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k

    # does this block intersect the causal/window band at all?
    intersects = True
    if causal:
        intersects = k_lo <= q_lo + block_q - 1          # not above diagonal
    if window is not None:
        # lowest visible key for the *last* query row of the block
        intersects = jnp.logical_and(
            intersects, k_lo + block_k - 1 >= q_lo - window)

    @pl.when(intersects)
    def _attend():
        q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
        # K-tail: out-of-bounds rows of the padded block are undefined; a
        # masked probability of exactly 0 still yields NaN via 0·NaN in p@v,
        # so zero the rows themselves.
        vrow = k_lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vrow < seq_len, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len                             # K tail padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)                   # rescale old acc
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,            # (B, H, S, hd)
    k: jax.Array,            # (B, KV, S, hd)
    v: jax.Array,            # (B, KV, S, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    B, H, S, hd = q.shape
    KV = k.shape[1]
    assert H % KV == 0, (H, KV)
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    block_q = min(block_q, S)
    block_k = min(block_k, S)
    grid = (B * H, pl.cdiv(S, block_q), pl.cdiv(S, block_k))

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        h = bh % H
        b = bh // H
        return (b * KV + h // G, ki, 0)

    qr = q.reshape(B * H, S, hd)
    kr = k.reshape(B * KV, S, hd)
    vr = v.reshape(B * KV, S, hd)

    out = pallas_call(
        functools.partial(_flash_kernel, scale=scale, seq_len=S,
                          block_q=block_q, block_k=block_k,
                          window=window, causal=causal),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd)


# ---------------------------------------------------------------------------
# Checked forward: fused two-tier ABFT outputs (docs/backends.md)
#
# Attention is float, so the exact mod-2^32 operand identity qmatmul enjoys
# does not exist for the *compute* path.  The checked kernel therefore emits
# two check outputs per query row, fused into the same pass:
#
#   check  (f32)  — an independent accumulation of rowsum_hd(out), carried
#                   through the online softmax alongside m/l/acc
#                   (c ← c·α + p · rowsum_hd(v)); verified with a tolerance,
#                   this covers the compute path (MXU/accumulator faults that
#                   perturb the math).
#   csum  (u32)   — the exact per-row mod-2^32 sum of the emitted output's
#                   bit patterns (``abft.storage_checksums`` at row
#                   granularity), computed in the epilogue from the very
#                   block written to HBM.  Verification is bit-exact, so ANY
#                   single-bit flip of the output between kernel and consumer
#                   is detected — zero false negatives, certifiable at 1.0.
# ---------------------------------------------------------------------------


def _flash_checked_kernel(q_ref, k_ref, v_ref, o_ref, chk_ref, csum_ref,
                          m_ref, l_ref, acc_ref, c_ref, *,
                          scale: float, seq_len: int, block_q: int,
                          block_k: int, window: int | None, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k
    intersects = True
    if causal:
        intersects = k_lo <= q_lo + block_q - 1
    if window is not None:
        intersects = jnp.logical_and(
            intersects, k_lo + block_k - 1 >= q_lo - window)

    @pl.when(intersects)
    def _attend():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        vrow = k_lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vrow < seq_len, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # in-path check column: contract the probabilities with rowsum_hd(v)
        # — an accumulation independent of the (bq, hd) accumulator above,
        # tracking rowsum_hd(acc) through the same online rescaling
        v1 = jnp.sum(v, axis=-1)                          # (bk,)
        c_ref[...] = c_ref[...] * alpha + jnp.sum(p * v1[None, :], axis=-1)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-30)
        out = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0] = out
        chk_ref[0, 0] = c_ref[...] / l
        # the bit patterns are summed as int32 (Mosaic reduces no unsigned
        # type); two's-complement wrap makes it the same sum mod 2^32
        if out.dtype == jnp.float32:
            bits = jax.lax.bitcast_convert_type(out, jnp.int32)
        else:                                             # bf16 / f16 I/O
            bits = jax.lax.bitcast_convert_type(out, jnp.int16).astype(
                jnp.int32) & 0xFFFF
        csum_ref[0, 0] = jnp.sum(bits, axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k"))
def flash_attention_checked(
    q: jax.Array,            # (B, H, S, hd)
    k: jax.Array,            # (B, KV, S, hd)
    v: jax.Array,            # (B, KV, S, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
):
    """Forward attention returning ``(out, check, csum)``.

    ``out`` (B,H,S,hd) as ``flash_attention``; ``check`` (B,H,S) f32 is the
    fused independent rowsum-of-output column (tolerance-verified);
    ``csum`` (B,H,S) u32 is the exact per-row bit checksum of ``out``
    (bit-exact verification; see ``core.abft.output_row_checksums``).
    """
    B, H, S, hd = q.shape
    KV = k.shape[1]
    assert H % KV == 0, (H, KV)
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    grid = (B * H, pl.cdiv(S, block_q), pl.cdiv(S, block_k))

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        h = bh % H
        b = bh // H
        return (b * KV + h // G, ki, 0)

    def row_map(bh, qi, ki):
        return (bh, 0, qi)

    qr = q.reshape(B * H, S, hd)
    kr = k.reshape(B * KV, S, hd)
    vr = v.reshape(B * KV, S, hd)
    out, check, csum = pallas_call(
        functools.partial(_flash_checked_kernel, scale=scale, seq_len=S,
                          block_q=block_q, block_k=block_k,
                          window=window, causal=causal),
        name="flash_attention_checked",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=[pl.BlockSpec((1, block_q, hd), q_map),
                   pl.BlockSpec((1, 1, block_q), row_map),
                   pl.BlockSpec((1, 1, block_q), row_map)],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qr, kr, vr)
    csum = jax.lax.bitcast_convert_type(csum, jnp.uint32)
    return (out.reshape(B, H, S, hd), check.reshape(B, H, S),
            csum.reshape(B, H, S))


# ---------------------------------------------------------------------------
# Backward kernels (Dao 2022 two-pass formulation, TPU-adapted)
#
#   D  = rowsum(dO ∘ O)                       (computed outside, elementwise)
#   P  = exp(QKᵀ·s − L)            (recomputed per block from the saved lse)
#   dV = Pᵀ dO
#   dP = dO Vᵀ
#   dQ = s · [P ∘ (dP − D)] K      (kernel 1: grid over q blocks, scan kv)
#   dK = s · [P ∘ (dP − D)]ᵀ Q     (kernel 2: grid over kv blocks, scan q·G)
#
# The dkv kernel grids over B·KV (not B·H) so GQA head-group gradients
# accumulate in VMEM scratch instead of colliding across grid cells.
# ---------------------------------------------------------------------------


def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                          m_ref, l_ref, acc_ref, *,
                          scale, seq_len, block_q, block_k, window, causal):
    """Forward that also emits the logsumexp rows needed by the backward."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k
    intersects = True
    if causal:
        intersects = k_lo <= q_lo + block_q - 1
    if window is not None:
        intersects = jnp.logical_and(
            intersects, k_lo + block_k - 1 >= q_lo - window)

    @pl.when(intersects)
    def _attend():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        vrow = k_lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(vrow < seq_len, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _epilogue():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _recompute_p(q, k, lse_rows, q_lo, k_lo, *, scale, seq_len, block_q,
                 block_k, window, causal):
    """Rebuild the probability block from saved logsumexp rows."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_len
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos >= qpos - window)
    p = jnp.where(mask, jnp.exp(s - lse_rows[:, None]), 0.0)
    # q tail rows (beyond seq_len) have lse=0 → exp(s) garbage; zero them
    qvalid = qpos < seq_len
    return jnp.where(qvalid, p, 0.0)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                         dq_ref, acc_ref, *,
                         scale, seq_len, block_q, block_k, window, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k
    intersects = True
    if causal:
        intersects = k_lo <= q_lo + block_q - 1
    if window is not None:
        intersects = jnp.logical_and(
            intersects, k_lo + block_k - 1 >= q_lo - window)

    @pl.when(intersects)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        krow = k_lo + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        k = jnp.where(krow < seq_len, k, 0.0)
        v = jnp.where(krow < seq_len, v, 0.0)
        p = _recompute_p(q, k, lse_ref[0, 0], q_lo, k_lo, scale=scale,
                         seq_len=seq_len, block_q=block_q, block_k=block_k,
                         window=window, causal=causal)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # q-tail rows: OOB dvec/lse are undefined; 0·NaN = NaN would leak
        qrow1 = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0)
        dvec = jnp.where(qrow1 < seq_len, dvec_ref[0, 0], 0.0)
        ds = p * (dp - dvec[:, None]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          scale, seq_len, block_q, block_k, window, causal,
                          n_q_steps):
    ki = pl.program_id(1)
    step = pl.program_id(2)          # enumerates (g, qi) pairs

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qi = step % n_q_steps
    q_lo = qi * block_q
    k_lo = ki * block_k
    intersects = True
    if causal:
        intersects = k_lo <= q_lo + block_q - 1
    if window is not None:
        intersects = jnp.logical_and(
            intersects, k_lo + block_k - 1 >= q_lo - window)

    @pl.when(intersects)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        qrow = q_lo + jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
        q = jnp.where(qrow < seq_len, q, 0.0)
        do = jnp.where(qrow < seq_len, do, 0.0)
        p = _recompute_p(q, k, lse_ref[0, 0], q_lo, k_lo, scale=scale,
                         seq_len=seq_len, block_q=block_q, block_k=block_k,
                         window=window, causal=causal)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # q-tail rows: OOB dvec is undefined; 0·NaN would poison the
        # q-contraction in dk below
        qrow1 = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0)
        dvec = jnp.where(qrow1 < seq_len, dvec_ref[0, 0], 0.0)
        ds = p * (dp - dvec[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(2) - 1)
    def _epilogue():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k"))
def flash_attention_fwd_lse(q, k, v, *, causal=True, window=None,
                            block_q=128, block_k=128):
    """Forward returning (out, lse); layout as flash_attention."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    grid = (B * H, pl.cdiv(S, block_q), pl.cdiv(S, block_k))

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        h = bh % H
        b = bh // H
        return (b * KV + h // G, ki, 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    qr = q.reshape(B * H, S, hd)
    kr = k.reshape(B * KV, S, hd)
    vr = v.reshape(B * KV, S, hd)
    out, lse = pallas_call(
        functools.partial(_flash_fwd_lse_kernel, scale=scale, seq_len=S,
                          block_q=block_q, block_k=block_k, window=window,
                          causal=causal),
        name="flash_attention_fwd_lse",
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_q, hd), q_map),
                  pl.BlockSpec((1, block_k, hd), kv_map),
                  pl.BlockSpec((1, block_k, hd), kv_map)],
        out_specs=[pl.BlockSpec((1, block_q, hd), q_map),
                   pl.BlockSpec((1, 1, block_q), lse_map)],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr)
    return out.reshape(B, H, S, hd), lse.reshape(B, H, S)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k"))
def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=None,
                        block_q=128, block_k=128):
    """Returns (dq, dk, dv). q (B,H,S,hd), k/v (B,KV,S,hd)."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)

    dvec = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)                                   # (B, H, S)
    qr = q.reshape(B * H, S, hd)
    kr = k.reshape(B * KV, S, hd)
    vr = v.reshape(B * KV, S, hd)
    dor = do.reshape(B * H, S, hd)
    lser = lse.reshape(B * H, 1, S)
    dvr = dvec.reshape(B * H, 1, S)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        h = bh % H
        b = bh // H
        return (b * KV + h // G, ki, 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    dq = pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, seq_len=S,
                          block_q=block_q, block_k=block_k, window=window,
                          causal=causal),
        name="flash_attention_bwd_dq",
        grid=(B * H, nq, nk),
        in_specs=[pl.BlockSpec((1, block_q, hd), q_map),
                  pl.BlockSpec((1, block_k, hd), kv_map),
                  pl.BlockSpec((1, block_k, hd), kv_map),
                  pl.BlockSpec((1, block_q, hd), q_map),
                  pl.BlockSpec((1, 1, block_q), lse_map),
                  pl.BlockSpec((1, 1, block_q), lse_map)],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, dvr)

    # dkv: grid over B·KV so head-group grads accumulate in scratch
    def kv_map2(bkv, ki, step):
        return (bkv, ki, 0)

    def q_map2(bkv, ki, step):
        b = bkv // KV
        kvh = bkv % KV
        g = step // nq
        qi = step % nq
        return (b * H + kvh * G + g, qi, 0)

    def lse_map2(bkv, ki, step):
        b = bkv // KV
        kvh = bkv % KV
        g = step // nq
        qi = step % nq
        return (b * H + kvh * G + g, 0, qi)

    dk, dv = pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, seq_len=S,
                          block_q=block_q, block_k=block_k, window=window,
                          causal=causal, n_q_steps=nq),
        name="flash_attention_bwd_dkv",
        grid=(B * KV, nk, G * nq),
        in_specs=[pl.BlockSpec((1, block_q, hd), q_map2),
                  pl.BlockSpec((1, block_k, hd), kv_map2),
                  pl.BlockSpec((1, block_k, hd), kv_map2),
                  pl.BlockSpec((1, block_q, hd), q_map2),
                  pl.BlockSpec((1, 1, block_q), lse_map2),
                  pl.BlockSpec((1, 1, block_q), lse_map2)],
        out_specs=[pl.BlockSpec((1, block_k, hd), kv_map2),
                   pl.BlockSpec((1, block_k, hd), kv_map2)],
        out_shape=[jax.ShapeDtypeStruct((B * KV, S, hd), k.dtype),
                   jax.ShapeDtypeStruct((B * KV, S, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr, dor, lser, dvr)

    return (dq.reshape(B, H, S, hd), dk.reshape(B, KV, S, hd),
            dv.reshape(B, KV, S, hd))
