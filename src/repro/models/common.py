"""Shared model building blocks: norms, RoPE, attention (chunked-causal,
GQA, sliding-window), losses, initializers.

Everything is functional: params are plain pytrees (dicts of arrays), modules
are pure functions.  Compute dtype is bf16 by default with f32 for norms,
softmax and the loss — the MaxText-style mixed-precision recipe.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return y.astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                                  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                            # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — chunked causal (flash-style online softmax in pure JAX)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def chunked_causal_attention(
    q: jax.Array,             # (B, S, H, hd)
    k: jax.Array,             # (B, S, KV, hd)
    v: jax.Array,             # (B, S, KV, hd)
    *,
    window: Optional[int] = None,   # sliding-window size (None = full causal)
    chunk: int = 512,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Memory-bounded causal attention with GQA and optional sliding window.

    Never materializes the (S, S) score matrix: iterates KV chunks per Q
    chunk with an online-softmax carry — the pure-JAX rendition of flash
    attention (the Pallas TPU kernel in kernels/flashattn specializes this).
    Peak live memory is O(S·chunk) per head instead of O(S²).
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    chunk = min(chunk, S)
    n = -(-S // chunk)
    Sp = n * chunk
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    scale = 1.0 / math.sqrt(hd)
    # (n, B, C, KV, G, hd) queries / (n, B, C, KV, hd) keys
    qc = q.reshape(B, n, chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, chunk, KV, hd).transpose(1, 0, 2, 3, 4)

    idx = jnp.arange(chunk)

    # NOTE: chunk indices (qi, kj) are threaded as loop CARRIES, not scan
    # inputs.  If they were scan inputs, XLA hoists the per-pair masks out of
    # both loops and materializes a (n, n, B, C, KV, C) boolean tensor —
    # tens of GB at production shapes.  Carry-derived values cannot be
    # hoisted, so the mask stays a (C, C) transient inside the loop body.
    def q_chunk_body(qi, q_i):
        def kv_body(carry, inputs):
            kv_idx, m_prev, l_prev, acc = carry
            kj, vj = inputs
            # scores: (B, C_q, KV, G, C_k).  Operands stay in the compute
            # dtype (bf16 on the MXU fast path — half the HBM traffic per
            # materialized chunk); accumulation is always f32 via
            # preferred_element_type, so the online softmax is stable.
            s = jnp.einsum("bqkgh,bckh->bqkgc", q_i, kj,
                           preferred_element_type=jnp.float32) * scale
            q_pos = qi * chunk + idx                       # (C_q,)
            k_pos = kv_idx * chunk + idx                   # (C_k,)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            # p is post-max-subtraction (≤ 1), safe to carry at the compute
            # dtype into the PV matmul (the flash-kernel convention)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqkgc,bckh->bqkgh", p.astype(q_i.dtype), vj,
                preferred_element_type=jnp.float32)
            return (kv_idx + 1, m_new, l_new, acc), None

        m0 = jnp.full((B, chunk, KV, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, chunk, KV, G), jnp.float32)
        a0 = jnp.zeros((B, chunk, KV, G, hd), jnp.float32)
        (_, m, l, acc), _ = jax.lax.scan(
            kv_body, (jnp.zeros((), jnp.int32), m0, l0, a0), (kc, vc))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    # remat per q-chunk: backward recomputes the kv sweep for one chunk at a
    # time instead of stacking all (nq × nk) score residuals — O(S·C) peak
    # attention memory, the flash-attention recipe expressed through remat.
    q_chunk_body = jax.checkpoint(
        q_chunk_body, policy=jax.checkpoint_policies.nothing_saveable,
        prevent_cse=False)

    def q_scan_body(qi, q_i):
        return qi + 1, q_chunk_body(qi, q_i)

    _, out = jax.lax.scan(q_scan_body, jnp.zeros((), jnp.int32), qc)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, H, hd)
    return out[:, :S]


def decode_attention(
    q: jax.Array,             # (B, 1, H, hd)
    k_cache: jax.Array,       # (B, T, KV, hd) — compute dtype or int8
    v_cache: jax.Array,       # (B, T, KV, hd)
    cur_len: jax.Array,       # (B,) or scalar — number of valid cache slots
    k_scale: Optional[jax.Array] = None,   # (B, T, KV) int8-KV scales
    v_scale: Optional[jax.Array] = None,
    new: Optional[tuple] = None,
) -> jax.Array:
    """Single-token attention over a (ring-buffered) KV cache.

    ``new = (slot, k, v[, k_scale, v_scale])`` — slot (B,), rows (B, KV, hd)
    in the cache's dtype, scales (B, KV) — is the token's own K/V, attended
    as if it were already written at ``slot``: its score replaces the
    page's at that slot (whatever the page holds there, e.g. the oldest
    entry of a full ring buffer, is masked out) and its value term is added
    after the page's.  The page is only read, so the caller writes the rows
    afterwards and the result equals write-then-attend up to the order of
    the value sum."""
    B, T, KV, hd = k_cache.shape
    H = q.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    if k_scale is not None:
        # int8 KV: quantize q per (b, kv, g) row, int8×int8→int32 on the MXU,
        # rescale by q-scale × per-row k-scale.  V dequantizes at page level
        # (probabilities carry per-T structure that can't fold into the dot).
        q_s = jnp.max(jnp.abs(qg.astype(jnp.float32)), axis=-1)
        q_s = jnp.maximum(q_s, 1e-8) / 127.0
        q_q = jnp.clip(jnp.round(qg.astype(jnp.float32) / q_s[..., None]),
                       -127, 127).astype(jnp.int8)

        def scores(k, ks):                      # (B, t, KV, hd), (B, t, KV)
            s32 = jnp.einsum("bkgh,btkh->bkgt", q_q, k,
                             preferred_element_type=jnp.int32)
            ks_t = jnp.moveaxis(ks, 1, 2)[:, :, None, :]       # (B, KV, 1, t)
            return s32.astype(jnp.float32) * q_s[..., None] * ks_t * scale

        def values(v, vs):
            return (v.astype(jnp.float32) * vs[..., None]).astype(q.dtype)
    else:
        # operands stay in the cache dtype (bf16): no f32 copy of the (T,·)
        # cache pages — accumulation is f32 via preferred_element_type
        # (decode is cache-read bound; an astype would double the traffic)
        def scores(k, _):
            return jnp.einsum("bkgh,btkh->bkgt", qg, k,
                              preferred_element_type=jnp.float32) * scale

        def values(v, _):
            return v
    s = scores(k_cache, k_scale)
    if new is not None:
        slot, k_new, v_new, *row_scales = new
        ks_new, vs_new = row_scales or (None, None)
        at = (jnp.arange(T)[None, :] == slot[:, None])[:, None, None, :]
        s_new = scores(k_new[:, None],
                       None if ks_new is None else ks_new[:, None])
        s = jnp.where(at, s_new, s)
    valid = jnp.arange(T)[None, :] < jnp.broadcast_to(jnp.atleast_1d(cur_len), (B,))[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    v_page = values(v_cache, v_scale)
    p_page = p if new is None else jnp.where(at, 0.0, p)
    out = jnp.einsum("bkgt,btkh->bkgh", p_page.astype(v_page.dtype), v_page,
                     preferred_element_type=jnp.float32)
    if new is not None:
        v_row = values(v_new[:, None], None if vs_new is None
                       else vs_new[:, None])[:, 0]              # (B, KV, hd)
        p_new = jnp.sum(jnp.where(at, p, 0.0), axis=-1)        # (B, KV, G)
        out = out + (p_new.astype(v_row.dtype).astype(jnp.float32)[..., None]
                     * v_row.astype(jnp.float32)[:, :, None, :])
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Slot-wise cache plumbing (continuous batching)
# ---------------------------------------------------------------------------


def cache_write_slot(batch_cache, one_cache, slot: int, n: int):
    """Copy a single-request prefill cache into row ``slot`` of a batch
    cache — the splice that lets a request join a live decode batch without
    re-padding or draining its neighbors (runtime/dataflow DecodeStage).

    Works on any family's cache pytree: leaves are (L, B, T, ...) for KV or
    (L, B, ...) for recurrent state (batch at dim 1); per-row length vectors
    are (B,) int (batch at dim 0, set to ``n``); scalar counters are maxed.
    """
    def write(bc, oc):
        if bc.ndim == 0:
            return jnp.maximum(bc, oc)
        if bc.ndim == 1 and jnp.issubdtype(bc.dtype, jnp.integer):
            return bc.at[slot].set(n)          # per-row length vector
        # one_cache leaf has batch=1 at dim 1
        row = jax.lax.dynamic_slice_in_dim(oc, 0, 1, axis=1)
        if bc.ndim >= 3 and bc.shape[2] != row.shape[2]:
            # time-indexed leaf with different max_len: copy the prefix
            pad = [(0, 0)] * row.ndim
            pad[2] = (0, bc.shape[2] - row.shape[2])
            row = jnp.pad(row, pad)
        return jax.lax.dynamic_update_slice_in_dim(bc, row.astype(bc.dtype),
                                                   slot, axis=1)

    return jax.tree_util.tree_map(write, batch_cache, one_cache)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token CE. logits (B, S, V) any float dtype, labels (B, S) i32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
