"""Flash attention Pallas kernel (interpret mode) vs materialized oracle.

Same validation methodology as the paper (Fig. 4): kernel-under-interpreter
compared against an independent reference across shape/dtype/GQA/window
sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flashattn.kernel import flash_attention
from repro.kernels.flashattn.ops import flash_attn
from repro.kernels.flashattn.ref import attention_ref

jax.config.update("jax_platform_name", "cpu")


def qkv(key, B, H, KV, S, hd, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, H, S, hd), dtype)
    k = jax.random.normal(k2, (B, KV, S, hd), dtype)
    v = jax.random.normal(k3, (B, KV, S, hd), dtype)
    return q, k, v


CASES = [
    # B, H, KV, S, hd, window
    (1, 2, 2, 128, 32, None),          # one block exactly
    (2, 4, 2, 256, 64, None),          # GQA 2:1, multi-block
    (1, 4, 1, 96, 16, None),           # MQA, ragged S < block
    (1, 2, 2, 200, 32, None),          # ragged S, multi-block
    (1, 4, 2, 256, 32, 64),            # sliding window
    (1, 2, 1, 160, 32, 32),            # window smaller than block
]


@pytest.mark.parametrize("B,H,KV,S,hd,window", CASES)
def test_flash_matches_ref(B, H, KV, S, hd, window):
    q, k, v = qkv(jax.random.key(0), B, H, KV, S, hd)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_io():
    q, k, v = qkv(jax.random.key(1), 1, 2, 2, 128, 32, jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = attention_ref(q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_noncausal():
    q, k, v = qkv(jax.random.key(2), 1, 2, 2, 128, 32)
    got = flash_attention(q, k, v, causal=False,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ops_layout_adapter():
    """(B,S,H,hd) wrapper agrees with the model-layout reference."""
    B, S, H, KV, hd = 2, 96, 4, 2, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    got = flash_attn(q, k, v)
    want = jnp.swapaxes(attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)), 1, 2)
    assert got.shape == (B, S, H, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_block_shape_independence():
    """Different BlockSpec tilings must give identical results."""
    q, k, v = qkv(jax.random.key(4), 1, 2, 2, 256, 32)
    a = flash_attention(q, k, v, block_q=64, block_k=64)
    b = flash_attention(q, k, v, block_q=128, block_k=64)
    c = flash_attention(q, k, v, block_q=64, block_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-6, atol=1e-6)


# --------------------------- backward kernels --------------------------------

from repro.kernels.flashattn.kernel import (     # noqa: E402
    flash_attention_bwd, flash_attention_fwd_lse)
from repro.kernels.flashattn.ops import flash_attn_diff  # noqa: E402

BWD_CASES = [
    # B, H, KV, S, hd, window
    (1, 2, 2, 128, 32, None),
    (1, 4, 2, 128, 16, None),          # GQA 2:1 — head-group accumulation
    (1, 4, 1, 96, 16, None),           # MQA, ragged S
    (1, 2, 2, 192, 32, 64),            # sliding window
]


@pytest.mark.parametrize("B,H,KV,S,hd,window", BWD_CASES)
def test_flash_bwd_matches_ref_grads(B, H, KV, S, hd, window):
    q, k, v = qkv(jax.random.key(7), B, H, KV, S, hd)
    dout = jax.random.normal(jax.random.key(8), (B, H, S, hd))

    def f_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True, window=window)
                       * dout)

    def f_flash(q, k, v):
        return jnp.sum(flash_attn_diff(q, k, v, True, window, 64, 64)
                       * dout)

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_fwd_lse_matches_plain_fwd():
    q, k, v = qkv(jax.random.key(9), 1, 2, 2, 128, 32)
    o1 = flash_attention(q, k, v, block_q=64, block_k=64)
    o2, lse = flash_attention_fwd_lse(q, k, v,
                                      block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-6, atol=1e-6)
    # lse is the true logsumexp of masked scores
    import math as _math
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / _math.sqrt(32)
    mask = jnp.tril(jnp.ones((128, 128), bool))
    s = jnp.where(mask, s, -1e30)
    want = jax.nn.logsumexp(s, axis=-1).reshape(1, 2, 128)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ----------------------- checked (two-tier ABFT) kernel ----------------------

from repro.core import abft                      # noqa: E402
from repro.kernels.flashattn.kernel import (     # noqa: E402
    flash_attention_checked)
from repro.kernels.flashattn.ops import flash_attn_model  # noqa: E402

CHECKED_CASES = [
    # B, H, KV, S, hd, window
    (1, 2, 2, 128, 32, None),
    (1, 4, 2, 200, 16, None),          # GQA, ragged S
    (1, 2, 1, 160, 32, 32),            # MQA + sliding window
]


@pytest.mark.parametrize("B,H,KV,S,hd,window", CHECKED_CASES)
def test_checked_kernel_two_tier_outputs(B, H, KV, S, hd, window):
    """The checked kernel must (a) emit the plain kernel's output
    bit-for-bit — recovery recomputes from the plain path, so any drift
    would turn every correction into a false mismatch — (b) carry a float
    check column equal to rowsum_hd(out) up to roundoff, and (c) emit the
    exact mod-2^32 bit checksum ``abft.output_row_checksums`` recomputes."""
    q, k, v = qkv(jax.random.key(11), B, H, KV, S, hd)
    plain = flash_attention(q, k, v, causal=True, window=window,
                            block_q=64, block_k=64)
    out, check, csum = flash_attention_checked(
        q, k, v, causal=True, window=window, block_q=64, block_k=64)
    assert out.shape == (B, H, S, hd)
    assert check.shape == csum.shape == (B, H, S)
    assert csum.dtype == jnp.uint32
    assert bool(jnp.all(out == plain))                       # (a) bit-exact
    np.testing.assert_allclose(                              # (b) float tier
        np.asarray(jnp.sum(out, axis=-1)), np.asarray(check),
        rtol=1e-4, atol=1e-4)
    assert bool(jnp.all(abft.output_row_checksums(out) == csum))   # (c)


def test_checked_kernel_bf16_checksum_is_exact():
    q, k, v = qkv(jax.random.key(12), 1, 2, 2, 128, 32, jnp.bfloat16)
    out, check, csum = flash_attention_checked(q, k, v, block_q=64,
                                               block_k=64)
    assert out.dtype == jnp.bfloat16
    assert bool(jnp.all(abft.output_row_checksums(out) == csum))


def test_output_bit_checksum_detects_every_flip():
    """The exact tier's reason to exist: a *lowest-mantissa* flip is far
    below any float tolerance, yet the bit checksum must still flag the
    row — and only that row."""
    q, k, v = qkv(jax.random.key(13), 1, 2, 2, 128, 32)
    out, check, csum = flash_attention_checked(q, k, v, block_q=64,
                                               block_k=64)
    for bit in (0, 12, 23, 31):                  # mantissa → sign sweep
        bits = jax.lax.bitcast_convert_type(out, jnp.uint32)
        bits = bits.at[0, 1, 77, 5].set(bits[0, 1, 77, 5] ^ jnp.uint32(1 << bit))
        bad = jax.lax.bitcast_convert_type(bits, jnp.float32)
        row_ok = abft.output_row_checksums(bad) == csum
        assert not bool(row_ok[0, 1, 77]), f"bit {bit} escaped"
        assert int(jnp.sum(~row_ok)) == 1, f"bit {bit} flagged extra rows"


@pytest.mark.parametrize("S", [5, 37, 100])
def test_flash_attn_model_ragged_small_S(S):
    """flash_attn_model clamps block sizes with ``min(block_q, S)``: model
    layouts shorter than the default 128 block (short prefills) must still
    match the reference, forward and backward."""
    B, H, KV, hd = 1, 2, 2, 16
    ks = jax.random.split(jax.random.key(14), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    dout = jax.random.normal(ks[3], (B, S, H, hd))

    got = flash_attn_model(q, k, v)
    want = jnp.swapaxes(attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2)), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def f_model(q, k, v):
        return jnp.sum(flash_attn_model(q, k, v) * dout)

    def f_ref(q, k, v):
        return jnp.sum(jnp.swapaxes(attention_ref(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2)), 1, 2) * dout)

    g_model = jax.grad(f_model, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_model, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch (S={S})")
