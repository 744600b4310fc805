"""Unified transformer: forward/grad/decode consistency on reduced configs."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models import transformer as tfm
from repro.models.config import ArchConfig, MoEConfig

jax.config.update("jax_platform_name", "cpu")


def small_dense(**kw) -> ArchConfig:
    # f32 compute: the consistency tests compare two execution orders of the
    # same math, so they must not be at the mercy of bf16 routing near-ties
    base = dict(name="t", family="transformer", n_layers=2, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128, head_dim=8,
                compute_dtype="float32")
    base.update(kw)
    return ArchConfig(**base)


def small_moe(**kw) -> ArchConfig:
    # capacity_factor=8 ⇒ effectively dropless: batch forward and
    # token-by-token decode then agree exactly (capacity drops are a batch-
    # mode effect, so consistency tests must run dropless)
    return small_dense(
        moe=MoEConfig(n_experts=4, top_k=2, d_expert=16, n_shared_experts=1,
                      n_dense_layers=1, capacity_factor=8.0),
        **kw)


def one_device_ctx():
    mesh = make_mesh((1, 1), ("data", "model"))
    return tfm.ShardCtx(mesh=mesh)


@pytest.mark.parametrize("cfg", [
    small_dense(),
    small_dense(qk_norm=True),
    small_dense(use_bias=True),
    small_dense(swa_window=8),
    small_dense(tie_embeddings=True),
], ids=["plain", "qknorm", "bias", "swa", "tied"])
def test_dense_forward_shapes_and_finite(cfg):
    params = tfm.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    out = tfm.forward(cfg, params, tokens)
    assert out.logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(out.logits, np.float32)).all()


def test_moe_forward_single_device():
    cfg = small_moe()
    params = tfm.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    out = tfm.forward(cfg, params, tokens)
    assert out.logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(out.logits, np.float32)).all()
    assert float(out.aux_loss) > 0.0


def test_moe_shardmap_matches_single():
    cfg = small_moe()
    params = tfm.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    ref = tfm.forward(cfg, params, tokens)
    ctx = one_device_ctx()
    with ctx.mesh:
        got = jax.jit(lambda p, t: tfm.forward(cfg, p, t, ctx))(params, tokens)
    np.testing.assert_allclose(np.asarray(got.logits, np.float32),
                               np.asarray(ref.logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_grad_flows_and_finite():
    cfg = small_moe()
    params = tfm.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: tfm.loss_fn(cfg, p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in leaves)
    # routed expert weights must receive gradient (routing is differentiable
    # through gates)
    g = np.asarray(grads["moe_blocks"]["we_i"], np.float32)
    assert np.abs(g).max() > 0


@pytest.mark.parametrize("cfg", [
    small_dense(), small_dense(swa_window=8), small_moe(),
    small_dense(quant_kv=True), small_dense(swa_window=8, quant_kv=True),
    small_moe(quant_kv=True),
], ids=["dense", "swa", "moe", "dense-int8kv", "swa-int8kv", "moe-int8kv"])
def test_decode_matches_forward(cfg):
    """Teacher-forced decode step-by-step must reproduce forward() logits."""
    params = tfm.init_params(cfg, jax.random.key(0))
    B, S = 2, 12
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    full = tfm.forward(cfg, params, tokens)

    cache = tfm.init_cache(cfg, B, max_len=S)
    outs = []
    for t in range(S):
        logits, cache = tfm.decode_step(cfg, params, tokens[:, t], cache)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    # an int8 K/V row is off by up to half a step of its row max / 127, so
    # its logits leave the float forward's by up to ~0.1 at these sizes
    tol = 0.1 if cfg.quant_kv else 3e-2
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full.logits, np.float32),
                               rtol=tol, atol=tol)


def test_prefill_then_decode_continues_correctly():
    cfg = small_dense()
    params = tfm.init_params(cfg, jax.random.key(0))
    B, S = 2, 10
    tokens = jax.random.randint(jax.random.key(1), (B, S + 1), 0, cfg.vocab_size)

    # ground truth: forward on S+1 tokens, logits at position S
    full = tfm.forward(cfg, params, tokens)

    logits_p, cache = tfm.prefill(cfg, params, tokens[:, :S], max_len=S + 4)
    np.testing.assert_allclose(np.asarray(logits_p[:, -1], np.float32),
                               np.asarray(full.logits[:, S - 1], np.float32),
                               rtol=3e-2, atol=3e-2)
    logits_d, cache = tfm.decode_step(cfg, params, tokens[:, S], cache)
    np.testing.assert_allclose(np.asarray(logits_d, np.float32),
                               np.asarray(full.logits[:, S], np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("quant_kv", [False, True], ids=["float", "int8kv"])
def test_swa_ring_buffer_decode_long(quant_kv):
    """Decoding past the window: ring buffer must match forward() with SWA.
    Each step past the window attends before it overwrites the oldest slot,
    which must then drop out of the softmax."""
    cfg = small_dense(swa_window=8, quant_kv=quant_kv)
    params = tfm.init_params(cfg, jax.random.key(0))
    B, S = 1, 20                      # > 2× window
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    full = tfm.forward(cfg, params, tokens)

    cache = tfm.init_cache(cfg, B, max_len=S)   # ring of size window=8
    assert cache.k.shape[2] == 8
    outs = []
    for t in range(S):
        logits, cache = tfm.decode_step(cfg, params, tokens[:, t], cache)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full.logits, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("quant_kv", [False, True], ids=["float", "int8kv"])
@pytest.mark.parametrize("pos", [[3, 5], [9, 21]], ids=["filling", "wrapped"])
def test_attend_then_write_matches_write_then_attend(quant_kv, pos):
    """``decode_attention`` over the unmodified page plus the token's own
    row (``new``) equals attention over the page with the row written at
    ``pos % T``: the same scores and weights, only the value sum's order
    differs.  ``wrapped`` rows have filled the ring, so the overwritten
    (oldest) entry must drop out."""
    from repro.models import common
    B, T, KV, H, hd = 2, 8, 2, 4, 16
    keys = jax.random.split(jax.random.key(0), 5)
    q = jax.random.normal(keys[0], (B, 1, H, hd))
    k_page = jax.random.normal(keys[1], (B, T, KV, hd))
    v_page = jax.random.normal(keys[2], (B, T, KV, hd))
    k_row = jax.random.normal(keys[3], (B, KV, hd))
    v_row = jax.random.normal(keys[4], (B, KV, hd))
    pos = jnp.asarray(pos)
    slot, valid, b = pos % T, jnp.minimum(pos + 1, T), jnp.arange(B)
    if quant_kv:
        (kq, ksc), (vq, vsc) = map(tfm._quantize_kv_rows, (k_page, v_page))
        (kr, krs), (vr, vrs) = map(tfm._quantize_kv_rows, (k_row, v_row))
        pages, rows = [kq, vq, ksc, vsc], [kr, vr, krs, vrs]
    else:
        pages, rows = [k_page, v_page], [k_row, v_row]
    written = [p.at[b, slot].set(r) for p, r in zip(pages, rows)]
    want = common.decode_attention(q, written[0], written[1], valid,
                                   *written[2:])
    got = common.decode_attention(q, pages[0], pages[1], valid, *pages[2:],
                                  new=(slot, *rows))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_embedding_input_mode():
    cfg = small_dense(input_mode="embeddings")
    params = tfm.init_params(cfg, jax.random.key(0))
    embeds = jax.random.normal(jax.random.key(2), (2, 8, cfg.d_model))
    out = tfm.forward(cfg, params, None, embeds=embeds)
    assert out.logits.shape == (2, 8, cfg.vocab_size)
    assert np.isfinite(np.asarray(out.logits, np.float32)).all()
