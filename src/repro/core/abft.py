"""Algorithm-Based Fault Tolerance for integer matmul/conv (exact checksums).

The paper achieves dependability *physically* (radiation-hardened silicon).
On a commodity TPU fleet the equivalent threat — SEU bit-flips causing silent
data corruption — is answered *algorithmically*: Huang–Abraham checksums.

The key observation this module exploits: because the paper's technique makes
the hot path **integer** (int8 × int8 → int32), checksums are **exact in
modular arithmetic**.  XLA integer ops wrap (two's complement), so every sum
below is computed mod 2^32, and the identity

    rowsum_N( X·W )  ==  X · (W · 1_N)        (mod 2^32)

holds bit-for-bit.  A flipped bit b < 32 in any accumulator or operand changes
the checksum by ±2^b ≠ 0 (mod 2^32), so single-fault detection has **zero
false positives and zero false negatives** — impossible with float ABFT,
where roundoff forces tolerance windows.  This is a genuine dependability
*improvement* unlocked by the paper's integer-only design.

Detection granularity is per output row; recovery recomputes the affected
block (faults are rare, so `lax.cond` makes the recompute cost ~0 amortized).

The accumulator and check vector both come from the pluggable execution
backend (``core.backend`` / ``kernels.dispatch``): on ``backend="pallas"``
the check vector is fused into the kernel itself — one extra int8 MXU
product of the check vector's four limbs per K step — so detection covers
the paper's actual co-processor path with no separate checksum pass (see
docs/backends.md).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend as backend_mod


class AbftResult(NamedTuple):
    acc: jax.Array        # (M, N) int32 accumulator (possibly corrected)
    ok: jax.Array         # () bool — no fault detected (after correction)
    faults_detected: jax.Array  # () int32 — rows flagged in the first pass


def _dot_i32(x_q: jax.Array, w_q: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


def checksum_vector(w_q: jax.Array) -> jax.Array:
    """W · 1_N — the column-sum check vector, precomputable per layer. (K,) i32."""
    return jnp.sum(w_q.astype(jnp.int32), axis=1)


def int8_limbs(v: jax.Array) -> jax.Array:
    """Split int32 ``v`` into four int8 limbs, shape ``v.shape + (4,)``, with
    sum_l limbs[..., l] << 8l == v (mod 2^32).

    The MXU multiplies int8 by int8 and nothing wider, so a kernel that
    needs X · v for an int32 check vector v computes the four int8 products
    X · limb_l on the MXU and recombines them with ``from_limbs``: exact,
    because the identity holds mod 2^32 and every product is exact."""
    v = v.astype(jnp.int32)
    limbs = []
    for _ in range(4):
        d = ((v + 128) & 255) - 128                       # low byte, signed
        limbs.append(d)
        v = (v - d) >> 8                                  # exact mod 2^32
    return jnp.stack(limbs, axis=-1).astype(jnp.int8)


def from_limbs(parts: jax.Array) -> jax.Array:
    """Inverse of ``int8_limbs`` over products: (..., 4) i32 -> (...) i32."""
    parts = parts.astype(jnp.int32)
    return sum(parts[..., l] << (8 * l) for l in range(4))


def zp_bias_correct(acc_dot: jax.Array, x_zp: jax.Array, w_q: jax.Array,
                    bias: jax.Array) -> jax.Array:
    """The matmul dequant algebra, in exactly one place: the zero-point
    correction hoisted out of the inner product plus the bias,
    acc = X·W - zp·colsum(W) + bias.  Shared by the ABFT path here and by
    every non-ABFT policy in core/dependability.py, so the epilogue cannot
    drift between them."""
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0)
    return acc_dot - x_zp.astype(jnp.int32) * colsum[None, :] + bias[None, :]


def verify_rows(x_q: jax.Array, acc_dot: jax.Array, w_check: jax.Array) -> jax.Array:
    """Per-row fault mask for acc_dot = X·W. True == row is clean (mod 2^32)."""
    got = jnp.sum(acc_dot, axis=1)                       # rowsum, wraps mod 2^32
    want = _dot_i32(x_q, w_check[:, None])[:, 0]         # X · (W·1)
    return got == want


def abft_qmatmul(
    x_q: jax.Array,          # (M, K) int8
    x_zp: jax.Array,         # scalar i32
    w_q: jax.Array,          # (K, N) int8
    bias: jax.Array,         # (N,)  i32
    *,
    inject=None,             # optional fn(acc)->acc used by tests to corrupt
    w_check=None,            # precomputed checksum_vector(w) from *deploy time*
    backend: backend_mod.BackendLike = None,
) -> AbftResult:
    """Checksummed quantized matmul accumulator with detect + recompute-recover.

    Overhead: one (M,K)×(K,1) matvec + one row reduction ≈ 1/N of the matmul
    FLOPs (0.8 % for N=128); on ``backend="pallas"`` the matvec is fused into
    the kernel itself (four int8 limb columns per K step, no second pass
    over X).

    ``w_check`` lets the caller supply the check vector computed from a known-
    good weight copy (e.g. at checkpoint load).  With it, ABFT also catches
    weight-memory SEUs: a flipped ``w_q`` no longer matches the stored
    checksum.  Without it the checksum is derived from the (possibly already
    corrupted) live weights, so only compute-path faults are covered.
    """
    be = backend_mod.resolve(backend)
    if w_check is None:
        w_check = checksum_vector(w_q)
    acc_dot, want = be.matmul_acc_checksum(x_q, w_q, w_check)
    if inject is not None:
        acc_dot = inject(acc_dot)

    row_ok = jnp.sum(acc_dot, axis=1) == want        # rowsum wraps mod 2^32
    faults = jnp.sum(~row_ok).astype(jnp.int32)

    def recover(acc):
        # Recompute the full product (fault rate is tiny; the recompute branch
        # is taken ~never, so its cost does not affect steady-state throughput).
        fresh = be.matmul_acc(x_q, w_q)
        return jnp.where(row_ok[:, None], acc, fresh)

    acc_dot = jax.lax.cond(faults > 0, recover, lambda a: a, acc_dot)
    ok = jnp.all(jnp.sum(acc_dot, axis=1) == want)
    return AbftResult(zp_bias_correct(acc_dot, x_zp, w_q, bias), ok, faults)


# ---------------------------------------------------------------------------
# Storage scrubbing: the w_check idea generalized to whole parameter pytrees
# ---------------------------------------------------------------------------


def storage_checksums(params):
    """Per-leaf mod-2^32 storage checksums for an arbitrary parameter pytree.

    ``checksum_vector`` protects one matmul's weights; a serving fleet needs
    the same deploy-time guarantee over *every* stored tensor (float params
    included).  Each leaf is bitcast to its same-width unsigned view and
    summed mod 2^32: a flipped bit b changes the sum by ±2^b ≠ 0 (mod 2^32),
    so any single-bit weight-memory SEU is detected exactly — zero false
    positives, zero false negatives, dtype-uniform.

    Returns a pytree of () uint32 leaves mirroring ``params``; compute it
    from the known-good copy at deploy/checkpoint time and scrub live
    replicas against it (``verify_storage``).
    """
    from repro.core.fault_injection import _as_bits

    def one(x):
        bits, _ = _as_bits(jnp.asarray(x))
        return jnp.sum(bits.astype(jnp.uint32))

    return jax.tree_util.tree_map(one, params)


def verify_storage(params, checks):
    """Pytree of () bool leaves: True == leaf still matches its deploy-time
    checksum.  ``jax.tree_util.tree_all`` of the result is the scrub verdict."""
    fresh = storage_checksums(params)
    return jax.tree_util.tree_map(lambda a, b: a == b, fresh, checks)


def output_row_checksums(x: jax.Array) -> jax.Array:
    """``storage_checksums`` at row granularity: the exact mod-2^32 sum of
    ``x``'s bit patterns over its last axis, uint32 with the last axis
    reduced away.

    This is the verification side of the float-op output checksum: a kernel
    that emits its own per-row bit checksum alongside the output (e.g.
    ``kernels.flashattn.flash_attention_checked``) lets the consumer compare
    bit-exactly, so any single-bit flip of the *emitted output* is detected
    with zero false positives/negatives — even though the float compute path
    itself only admits tolerance-based checking.
    """
    from repro.core.fault_injection import _as_bits
    bits, _ = _as_bits(jnp.asarray(x))
    return jnp.sum(bits.astype(jnp.uint32), axis=-1)


# ---------------------------------------------------------------------------
# Conv variant: checksum over output channels
# ---------------------------------------------------------------------------


def conv_checksum_weight(w_q: jax.Array) -> jax.Array:
    """(KH, KW, Cin, Cout) → (KH, KW, Cin, 1): the Cout-summed check filter."""
    return jnp.sum(w_q.astype(jnp.int32), axis=3, keepdims=True)


def abft_qconv2d(
    x_q: jax.Array, x_zp: jax.Array, w_q: jax.Array, bias: jax.Array,
    stride=(1, 1), padding="SAME", *, inject=None, w_check=None,
    backend: backend_mod.BackendLike = None,
) -> AbftResult:
    """Checksummed quantized conv accumulator (detection per output pixel).

    ``w_check`` — optional precomputed ``conv_checksum_weight`` from a known-
    good weight copy; see ``abft_qmatmul``.
    """
    be = backend_mod.resolve(backend)
    if w_check is None:
        w_check = conv_checksum_weight(w_q)
    acc_dot, want = be.conv_acc_checksum(x_q, x_zp, w_q, w_check, stride,
                                         padding)
    if inject is not None:
        acc_dot = inject(acc_dot)

    got = jnp.sum(acc_dot, axis=3)
    pix_ok = got == want                                 # (N, OH, OW)
    faults = jnp.sum(~pix_ok).astype(jnp.int32)

    def recover(acc):
        fresh = be.conv_acc(x_q, x_zp, w_q, stride, padding)
        return jnp.where(pix_ok[..., None], acc, fresh)

    acc_dot = jax.lax.cond(faults > 0, recover, lambda a: a, acc_dot)
    ok = jnp.all(jnp.sum(acc_dot, axis=3) == want)
    return AbftResult(acc_dot + bias[None, None, None, :], ok, faults)
