"""jit'd public wrapper for flash attention (fwd + custom-VJP bwd kernels).

On TPU the Pallas kernels run compiled; lowered for the CPU they execute
in Pallas interpret mode (``repro.device.pallas_call``).  Layout adapter:
models carry (B, S, H, hd); the kernel wants (B, H, S, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flashattn.kernel import (
    flash_attention, flash_attention_bwd, flash_attention_fwd_lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attn_diff(q, k, v, causal=True, window=None, block_q=128,
                    block_k=128):
    """Differentiable flash attention: fwd AND bwd are Pallas kernels.

    q (B,H,S,hd), k/v (B,KV,S,hd) → (B,H,S,hd).  The backward recomputes
    probability blocks from the saved logsumexp (Dao 2022) — the (S,S)
    score matrix never exists in HBM in either pass.
    """
    out, _ = flash_attention_fwd_lse(q, k, v, causal=causal, window=window,
                                     block_q=block_q, block_k=block_k)
    return out


def _fad_fwd(q, k, v, causal, window, block_q, block_k):
    out, lse = flash_attention_fwd_lse(q, k, v, causal=causal, window=window,
                                       block_q=block_q, block_k=block_k)
    return out, (q, k, v, out, lse)


def _fad_bwd(causal, window, block_q, block_k, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, do, causal=causal,
                                     window=window, block_q=block_q,
                                     block_k=block_k)
    return dq, dk, dv


flash_attn_diff.defvjp(_fad_fwd, _fad_bwd)


def flash_attn(q, k, v, *, causal: bool = True,
               window: int | None = None) -> jax.Array:
    """q (B, S, H, hd), k/v (B, S, KV, hd) → (B, S, H, hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, window=window)
    return jnp.swapaxes(out, 1, 2)


def flash_attn_model(q, k, v, *, causal=True, window=None,
                     block_q=128, block_k=128):
    """Differentiable model-layout wrapper: (B, S, H, hd) in/out, Pallas
    fwd+bwd kernels underneath."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    S = qt.shape[2]
    bq, bk = min(block_q, S), min(block_k, S)
    out = flash_attn_diff(qt, kt, vt, causal, window, bq, bk)
    return jnp.swapaxes(out, 1, 2)
