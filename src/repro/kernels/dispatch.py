"""Built-in execution backends for the quantized primitives.

Registers the ``ref`` / ``jnp`` / ``pallas`` implementations of the
accumulator-level qmatmul / qconv2d entries into ``core.backend``'s
registry (see that module for the contract and selection precedence).
Importing this module is what makes the built-ins available; the registry
imports it lazily so ``core/`` never depends on ``kernels/`` at load time.

All three backends are bit-identical: the hot path is integer (int8 × int8
→ int32, wrapping mod 2^32), so accumulation order cannot change results.
``tests/test_backend.py`` enforces the parity across every policy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import abft as abft_mod
from repro.core import backend as backend_mod
from repro.kernels.flashattn.kernel import (
    flash_attention as flash_attention_pallas,
    flash_attention_checked as flash_attention_checked_pallas)
from repro.kernels.flashattn.ref import attention_ref
from repro.kernels.qconv2d.kernel import (
    qconv2d_acc as qconv2d_acc_pallas,
    qconv2d_acc_checksum as qconv2d_acc_checksum_pallas)
from repro.kernels.qmatmul.kernel import (
    qmatmul_acc as qmatmul_acc_pallas,
    qmatmul_acc_checksum as qmatmul_acc_checksum_pallas)


# ---------------------------------------------------------------------------
# jnp — XLA-native int8 dot / conv (the historical inlined path)
# ---------------------------------------------------------------------------


def _matmul_acc_jnp(x_q, w_q):
    return jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)


def _matmul_acc_checksum_jnp(x_q, w_q, w_check):
    acc = _matmul_acc_jnp(x_q, w_q)
    want = jax.lax.dot_general(
        x_q, w_check[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)[:, 0]
    return acc, want


def _conv_i32(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w, stride, padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)


def _conv_acc_jnp(x_q, x_zp, w_q, stride, padding):
    x = x_q.astype(jnp.int32) - x_zp.astype(jnp.int32)
    return _conv_i32(x, w_q.astype(jnp.int32), stride, padding)


def _conv_acc_checksum_jnp(x_q, x_zp, w_q, w_check, stride, padding):
    x = x_q.astype(jnp.int32) - x_zp.astype(jnp.int32)
    acc = _conv_i32(x, w_q.astype(jnp.int32), stride, padding)
    want = _conv_i32(x, w_check, stride, padding)[..., 0]
    return acc, want


# ---------------------------------------------------------------------------
# ref — independent oracle: int32-upcast matmul, explicit tap-loop conv
# ---------------------------------------------------------------------------


def _matmul_acc_ref(x_q, w_q):
    return jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32))


def _matmul_acc_checksum_ref(x_q, w_q, w_check):
    acc = _matmul_acc_ref(x_q, w_q)
    want = jnp.matmul(x_q.astype(jnp.int32), w_check)
    return acc, want


def _resolve_pads(h, w, kh, kw, stride, padding):
    from repro.kernels.qconv2d.ops import _same_pads
    if padding == "SAME":
        return _same_pads(h, w, kh, kw, *stride)
    if padding == "VALID":
        return ((0, 0), (0, 0))
    return tuple(padding)


def _tap_loop_conv(x, w, stride, pads):
    """Direct shifted-window convolution in plain jnp — structurally the
    Pallas kernel's tap loop, independently implemented (no XLA conv op)."""
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    sh, sw = stride
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sw + 1
    acc = jnp.zeros((n, oh, ow, cout), jnp.int32)
    for i in range(kh):
        for j in range(kw):
            patch = jax.lax.slice(
                xp, (0, i, j, 0),
                (n, i + (oh - 1) * sh + 1, j + (ow - 1) * sw + 1, cin),
                (1, sh, sw, 1))
            acc = acc + jnp.einsum("nhwc,cf->nhwf", patch, w[i, j],
                                   preferred_element_type=jnp.int32)
    return acc


def _conv_acc_ref(x_q, x_zp, w_q, stride, padding):
    n, h, wd, _ = x_q.shape
    kh, kw = w_q.shape[0], w_q.shape[1]
    pads = _resolve_pads(h, wd, kh, kw, stride, padding)
    x = x_q.astype(jnp.int32) - x_zp.astype(jnp.int32)
    return _tap_loop_conv(x, w_q.astype(jnp.int32), stride, pads)


def _conv_acc_checksum_ref(x_q, x_zp, w_q, w_check, stride, padding):
    acc = _conv_acc_ref(x_q, x_zp, w_q, stride, padding)
    n, h, wd, _ = x_q.shape
    kh, kw = w_q.shape[0], w_q.shape[1]
    pads = _resolve_pads(h, wd, kh, kw, stride, padding)
    x = x_q.astype(jnp.int32) - x_zp.astype(jnp.int32)
    want = _tap_loop_conv(x, w_check, stride, pads)[..., 0]
    return acc, want


# ---------------------------------------------------------------------------
# pallas — the co-processor path (interpreted when lowered for the CPU, per
# the paper's simulator-stands-in-for-hardware methodology; repro.device)
# ---------------------------------------------------------------------------


def _matmul_acc_pallas(x_q, w_q):
    return qmatmul_acc_pallas(x_q, w_q)


def _matmul_acc_checksum_pallas(x_q, w_q, w_check):
    return qmatmul_acc_checksum_pallas(x_q, w_q, w_check)


def _pad_zp(x_q, x_zp, pads):
    """Zero-point padding: padded taps contribute (zp - zp)·w == 0, i.e.
    padding with the zp value is exactly 'pad with real 0.0'."""
    return jax.lax.pad(
        x_q, x_zp.astype(jnp.int8),
        ((0, 0, 0),
         (pads[0][0], pads[0][1], 0),
         (pads[1][0], pads[1][1], 0),
         (0, 0, 0)))


def _conv_acc_pallas(x_q, x_zp, w_q, stride, padding):
    n, h, wd, _ = x_q.shape
    kh, kw = w_q.shape[0], w_q.shape[1]
    pads = _resolve_pads(h, wd, kh, kw, stride, padding)
    xp = _pad_zp(x_q, x_zp, pads)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=(0, 1, 2))
    zp = x_zp.astype(jnp.int32).reshape(1)
    return qconv2d_acc_pallas(xp, w_q, colsum, zp, stride=stride)


def _conv_acc_checksum_pallas(x_q, x_zp, w_q, w_check, stride, padding):
    n, h, wd, _ = x_q.shape
    kh, kw = w_q.shape[0], w_q.shape[1]
    pads = _resolve_pads(h, wd, kh, kw, stride, padding)
    xp = _pad_zp(x_q, x_zp, pads)
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=(0, 1, 2))
    zp = x_zp.astype(jnp.int32).reshape(1)
    return qconv2d_acc_checksum_pallas(xp, w_q, colsum, w_check, zp,
                                       stride=stride)


# ---------------------------------------------------------------------------
# attention — the float hot kernel, per backend
#
# Attention has no integer operand identity, so the checksummed entry is
# two-tier (core/backend.py docstring): a float check column verified with
# a tolerance plus an exact bit checksum of the emitted output rows.  On
# the pallas backend both are fused into the kernel epilogue; jnp/ref
# compute them as separate passes in the execution path, exactly as their
# qmatmul checksums are separate dots.
# ---------------------------------------------------------------------------


def _attn_check_column(q, k, v, *, causal, window):
    """Independent rowsum_hd(out) accumulation: softmax probabilities
    contracted with rowsum_hd(v) — never touches the (hd-wide) output
    accumulation it checks."""
    import math
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    kk = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    v1 = jnp.sum(jnp.repeat(v, G, axis=1).astype(jnp.float32), axis=-1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk) \
        / math.sqrt(hd)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos >= qpos - window)
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhk->bhq", p, v1)


def _attn_jnp(q, k, v, *, causal=True, window=None):
    return attention_ref(q, k, v, causal=causal, window=window)


def _attn_checksum_jnp(q, k, v, *, causal=True, window=None):
    out = attention_ref(q, k, v, causal=causal, window=window)
    check = _attn_check_column(q, k, v, causal=causal, window=window)
    return out, check, abft_mod.output_row_checksums(out)


def _attn_ref(q, k, v, *, causal=True, window=None):
    """Independent oracle: explicit two-pass softmax (max/exp/normalize),
    no ``jax.nn.softmax``."""
    import math
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    kk = jnp.repeat(k, G, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, G, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk) \
        / math.sqrt(hd)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos >= qpos - window)
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv).astype(q.dtype)


def _attn_checksum_ref(q, k, v, *, causal=True, window=None):
    out = _attn_ref(q, k, v, causal=causal, window=window)
    check = _attn_check_column(q, k, v, causal=causal, window=window)
    return out, check, abft_mod.output_row_checksums(out)


def _attn_pallas(q, k, v, *, causal=True, window=None):
    return flash_attention_pallas(q, k, v, causal=causal, window=window)


def _attn_checksum_pallas(q, k, v, *, causal=True, window=None):
    return flash_attention_checked_pallas(q, k, v, causal=causal,
                                          window=window)


# ---------------------------------------------------------------------------
# registration + convenience dispatchers
# ---------------------------------------------------------------------------

for _be in (
    backend_mod.Backend(
        name="jnp",
        matmul_acc=_matmul_acc_jnp,
        matmul_acc_checksum=_matmul_acc_checksum_jnp,
        conv_acc=_conv_acc_jnp,
        conv_acc_checksum=_conv_acc_checksum_jnp,
        attn=_attn_jnp,
        attn_checksum=_attn_checksum_jnp,
        description="XLA-native int8 dot_general / conv_general_dilated"),
    backend_mod.Backend(
        name="ref",
        matmul_acc=_matmul_acc_ref,
        matmul_acc_checksum=_matmul_acc_checksum_ref,
        conv_acc=_conv_acc_ref,
        conv_acc_checksum=_conv_acc_checksum_ref,
        attn=_attn_ref,
        attn_checksum=_attn_checksum_ref,
        description="independent jnp oracle (int32 upcast / tap loop)"),
    backend_mod.Backend(
        name="pallas",
        matmul_acc=_matmul_acc_pallas,
        matmul_acc_checksum=_matmul_acc_checksum_pallas,
        conv_acc=_conv_acc_pallas,
        conv_acc_checksum=_conv_acc_checksum_pallas,
        attn=_attn_pallas,
        attn_checksum=_attn_checksum_pallas,
        description="Pallas TPU kernels with fused ABFT checksum "
                    "(interpreted when lowered for the CPU)"),
):
    backend_mod.register_backend(_be, overwrite=True)
del _be


def matmul_acc(x_q, w_q, *, backend: backend_mod.BackendLike = None):
    """Raw int32 accumulator X·W on the selected backend."""
    return backend_mod.resolve(backend).matmul_acc(x_q, w_q)


def matmul_acc_checksum(x_q, w_q, w_check, *,
                        backend: backend_mod.BackendLike = None):
    """(acc, want) with the ABFT check vector computed in the execution path."""
    return backend_mod.resolve(backend).matmul_acc_checksum(x_q, w_q, w_check)


def conv_acc(x_q, x_zp, w_q, stride=(1, 1), padding="SAME", *,
             backend: backend_mod.BackendLike = None):
    """Raw int32 conv accumulator conv(x - zp, w) on the selected backend."""
    return backend_mod.resolve(backend).conv_acc(x_q, x_zp, w_q, stride,
                                                 padding)


def conv_acc_checksum(x_q, x_zp, w_q, w_check, stride=(1, 1), padding="SAME",
                      *, backend: backend_mod.BackendLike = None):
    """(acc, want) conv accumulator plus the fused per-pixel ABFT channel."""
    return backend_mod.resolve(backend).conv_acc_checksum(
        x_q, x_zp, w_q, w_check, stride, padding)


def attn(q, k, v, *, causal=True, window=None,
         backend: backend_mod.BackendLike = None):
    """Fused attention (B,H,S,hd layout) on the selected backend."""
    return backend_mod.resolve(backend).attn(q, k, v, causal=causal,
                                             window=window)


def attn_checksum(q, k, v, *, causal=True, window=None,
                  backend: backend_mod.BackendLike = None):
    """(out, check, csum): attention plus the two-tier ABFT check outputs
    (float check column + exact output-row bit checksum), fused into the
    kernel on the pallas backend."""
    return backend_mod.resolve(backend).attn_checksum(q, k, v, causal=causal,
                                                      window=window)
