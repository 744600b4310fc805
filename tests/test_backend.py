"""Execution-backend registry: parity, fused checksums, selection rules.

The paper's swappable-co-processor claim, as testable properties:

  * ref / jnp / pallas (interpreted) are **bit-identical** for qmatmul and
    qconv2d under every dependability policy — the integer hot path is exact
    mod 2^32, so where the accumulator is computed cannot change it.
  * The fused pallas checksum (emitted as a second kernel output) satisfies
    the Huang–Abraham identity want == rowsum(acc) on clean runs and detects
    every injected accumulator bit-flip — certifying ABFT on the paper's
    actual kernel path, not just the jnp stand-in.
  * Selection precedence: per-call beats the ``use_backend`` scope, which
    beats the process default.
  * TMR reports the faults its majority vote masks (``faults_corrected``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import abft, backend as backend_mod
from repro.core.dependability import (
    DependabilityStats, Policy, dependable_qconv2d, dependable_qmatmul)
from repro.kernels import dispatch

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ("ref", "jnp", "pallas")
POLICIES = (Policy.NONE, Policy.ABFT, Policy.DMR, Policy.TMR)


def _mm_case(rng, m=17, k=70, n=24):
    x_q = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int32), jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int32), jnp.int8)
    bias = jnp.asarray(rng.integers(-500, 500, (n,), dtype=np.int32))
    scale = jnp.full((n,), 1e-3, jnp.float32)
    return x_q, w_q, bias, scale


def _conv_case(rng, h=9, w=9, cin=5, cout=6):
    x_q = jnp.asarray(rng.integers(-128, 128, (2, h, w, cin), dtype=np.int32),
                      jnp.int8)
    w_q = jnp.asarray(rng.integers(-127, 128, (3, 3, cin, cout), dtype=np.int32),
                      jnp.int8)
    bias = jnp.asarray(rng.integers(-100, 100, (cout,), dtype=np.int32))
    scale = jnp.full((cout,), 1e-3, jnp.float32)
    return x_q, w_q, bias, scale


# ---------------------------------------------------------------------------
# Bit-identical parity across backends, every policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("policy", POLICIES)
def test_qmatmul_backend_parity(backend, policy):
    rng = np.random.default_rng(11)
    x_q, w_q, bias, scale = _mm_case(rng)
    y, _ = dependable_qmatmul(policy, x_q, jnp.int32(3), w_q, bias, scale,
                              jnp.int32(0), backend=backend)
    y_jnp, _ = dependable_qmatmul(policy, x_q, jnp.int32(3), w_q, bias, scale,
                                  jnp.int32(0), backend="jnp")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_jnp))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("stride,padding", [((1, 1), "SAME"),
                                            ((2, 2), "SAME"),
                                            ((1, 1), "VALID")])
def test_qconv2d_backend_parity(backend, policy, stride, padding):
    rng = np.random.default_rng(7)
    x_q, w_q, bias, scale = _conv_case(rng)
    y, _ = dependable_qconv2d(policy, x_q, jnp.int32(2), w_q, bias, scale,
                              jnp.int32(0), stride=stride, padding=padding,
                              backend=backend)
    y_jnp, _ = dependable_qconv2d(policy, x_q, jnp.int32(2), w_q, bias, scale,
                                  jnp.int32(0), stride=stride, padding=padding,
                                  backend="jnp")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_jnp))


@pytest.mark.parametrize("backend", BACKENDS)
def test_raw_accumulator_parity(backend):
    """The registry's accumulator-level contract itself (no policy layer)."""
    rng = np.random.default_rng(3)
    x_q, w_q, _, _ = _mm_case(rng, m=33, k=130, n=40)
    acc = dispatch.matmul_acc(x_q, w_q, backend=backend)
    want = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want))


def test_pallas_acc_kernels_multiblock_with_tails():
    """Forced multi-block grids with ragged K/N tails: the k-tail masking and
    the cross-block (n==0 / c==0) fused-checksum accumulation paths, which
    default block sizes never reach at test geometry."""
    from repro.kernels.qconv2d.kernel import qconv2d_acc_checksum
    from repro.kernels.qmatmul.kernel import qmatmul_acc, qmatmul_acc_checksum
    rng = np.random.default_rng(31)
    x_q, w_q, _, _ = _mm_case(rng, m=33, k=130, n=70)
    want = jnp.matmul(x_q.astype(jnp.int32), w_q.astype(jnp.int32))
    acc = qmatmul_acc(x_q, w_q, block_m=16, block_n=32, block_k=48)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want))
    w_check = abft.checksum_vector(w_q)
    acc, got = qmatmul_acc_checksum(x_q, w_q, w_check, block_m=16, block_n=32,
                                    block_k=48)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.sum(want, axis=1)))

    # conv: cout split across blocks (the check limbs with them), and one
    # output row per tile, so each tile's halo spans several tiles
    x_c, w_c, _, _ = _conv_case(rng, h=8, w=8, cin=4, cout=10)
    zp = jnp.int32(2)
    from repro.kernels.dispatch import _pad_zp, _resolve_pads
    pads = _resolve_pads(8, 8, 3, 3, (1, 1), "SAME")
    xp = _pad_zp(x_c, zp, pads)
    colsum = jnp.sum(w_c.astype(jnp.int32), axis=(0, 1, 2))
    wc = abft.conv_checksum_weight(w_c)
    acc, got = qconv2d_acc_checksum(xp, w_c, colsum, wc,
                                    zp.reshape(1), block_cout=4,
                                    block_rows=8)
    ref = dispatch.conv_acc(x_c, zp, w_c, backend="jnp")
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.sum(ref, axis=3)))


# ---------------------------------------------------------------------------
# Fused checksum on the pallas path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_matmul_checksum_identity_clean(backend):
    rng = np.random.default_rng(5)
    x_q, w_q, _, _ = _mm_case(rng)
    w_check = abft.checksum_vector(w_q)
    acc, want = dispatch.matmul_acc_checksum(x_q, w_q, w_check,
                                             backend=backend)
    np.testing.assert_array_equal(np.asarray(jnp.sum(acc, axis=1)),
                                  np.asarray(want))


def test_pallas_fused_checksum_detects_every_bit():
    """ABFT on backend=pallas: the in-kernel check vector flags any single
    accumulator bit-flip and recovery restores the clean result exactly."""
    rng = np.random.default_rng(9)
    x_q, w_q, bias, scale = _mm_case(rng, m=8, k=40, n=12)
    clean, _ = dependable_qmatmul(Policy.ABFT, x_q, jnp.int32(3), w_q, bias,
                                  scale, jnp.int32(0), backend="pallas")
    for bit in (0, 7, 15, 23, 31):
        r, c = int(rng.integers(0, 8)), int(rng.integers(0, 12))

        def inject(acc, bit=bit, r=r, c=c):
            return acc.at[r, c].set(
                acc[r, c] ^ jnp.int32(np.int32(np.uint32(1) << np.uint32(bit))))

        y, st = dependable_qmatmul(Policy.ABFT, x_q, jnp.int32(3), w_q, bias,
                                   scale, jnp.int32(0), backend="pallas",
                                   inject=inject)
        assert int(st["faults_detected"]) >= 1, bit
        assert int(st["faults_corrected"]) >= 1, bit
        np.testing.assert_array_equal(np.asarray(y), np.asarray(clean))


def test_pallas_fused_conv_checksum_detects():
    rng = np.random.default_rng(13)
    x_q, w_q, bias, scale = _conv_case(rng)
    clean, _ = dependable_qconv2d(Policy.ABFT, x_q, jnp.int32(2), w_q, bias,
                                  scale, jnp.int32(0), backend="pallas")

    def inject(acc):
        return acc.at[1, 3, 2, 4].add(jnp.int32(1 << 19))

    y, st = dependable_qconv2d(Policy.ABFT, x_q, jnp.int32(2), w_q, bias,
                               scale, jnp.int32(0), backend="pallas",
                               inject=inject)
    assert int(st["faults_detected"]) >= 1
    np.testing.assert_array_equal(np.asarray(y), np.asarray(clean))


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_selection_precedence():
    assert backend_mod.default_backend() == "jnp"
    with backend_mod.use_backend("ref"):
        assert backend_mod.default_backend() == "ref"
        assert backend_mod.resolve(None).name == "ref"
        # per-call beats the scoped default
        assert backend_mod.resolve("pallas").name == "pallas"
        with backend_mod.use_backend("jnp"):
            assert backend_mod.resolve(None).name == "jnp"
        assert backend_mod.default_backend() == "ref"
    assert backend_mod.default_backend() == "jnp"


def test_unknown_backend_raises():
    with pytest.raises(KeyError, match="unknown backend"):
        backend_mod.get_backend("hpdp")
    with pytest.raises(KeyError):
        dependable_qmatmul(Policy.NONE, jnp.zeros((2, 2), jnp.int8),
                           jnp.int32(0), jnp.zeros((2, 2), jnp.int8),
                           jnp.zeros((2,), jnp.int32),
                           jnp.ones((2,), jnp.float32), jnp.int32(0),
                           backend="hpdp")


def test_backend_instances_resolve_directly():
    be = backend_mod.get_backend("ref")
    assert backend_mod.resolve(be) is be


def test_use_backend_routes_dependable_ops():
    """The scoped default reaches ops that never mention a backend."""
    rng = np.random.default_rng(21)
    x_q, w_q, bias, scale = _mm_case(rng, m=4, k=8, n=6)
    y_default, _ = dependable_qmatmul(Policy.NONE, x_q, jnp.int32(1), w_q,
                                      bias, scale, jnp.int32(0))
    with backend_mod.use_backend("pallas"):
        y_pallas, _ = dependable_qmatmul(Policy.NONE, x_q, jnp.int32(1), w_q,
                                         bias, scale, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(y_default), np.asarray(y_pallas))


# ---------------------------------------------------------------------------
# TMR correction counting (satellite: no more silent masking)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_tmr_counts_corrected_faults(backend):
    rng = np.random.default_rng(17)
    x_q, w_q, bias, scale = _mm_case(rng, m=8, k=16, n=12)

    def inject(acc):
        return acc.at[2, 5].add(jnp.int32(1 << 20))

    y_clean, st = dependable_qmatmul(Policy.TMR, x_q, jnp.int32(3), w_q, bias,
                                     scale, jnp.int32(0), backend=backend)
    assert int(st["faults_detected"]) == 0
    assert int(st["faults_corrected"]) == 0

    y, st = dependable_qmatmul(Policy.TMR, x_q, jnp.int32(3), w_q, bias,
                               scale, jnp.int32(0), inject=inject,
                               backend=backend)
    assert int(st["faults_detected"]) == 1
    assert int(st["faults_corrected"]) == 1          # the vote masked it
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_clean))

    # DMR detects the same fault but corrects nothing — the gap is the
    # failover layer's workload
    _, st = dependable_qmatmul(Policy.DMR, x_q, jnp.int32(3), w_q, bias,
                               scale, jnp.int32(0), inject=inject,
                               backend=backend)
    assert int(st["faults_detected"]) == 1
    assert int(st["faults_corrected"]) == 0


def test_w8a8_transformer_backend_parity():
    """The per-layer rung end to end: a W8A8 transformer forward through
    models/api is bit-identical on cfg.backend = jnp vs pallas."""
    import dataclasses

    from repro.configs import registry
    from repro.models import api as model_api
    from repro.models import transformer
    from repro.models.config import reduced

    cfg = dataclasses.replace(reduced(registry.get("smollm-135m")),
                              quant="w8a8_ffn")
    params = model_api.init_params(cfg, jax.random.key(0))
    params = transformer.quantize_ffn_params(cfg, params)
    toks = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    lo_jnp = model_api.forward(cfg, params, toks).logits
    lo_pal = model_api.forward(model_api.with_backend(cfg, "pallas"),
                               params, toks).logits
    np.testing.assert_array_equal(np.asarray(lo_jnp), np.asarray(lo_pal))


def test_stats_merge_tolerates_missing_keys():
    old = {"faults_detected": jnp.int32(2), "checks_run": jnp.int32(5)}
    merged = DependabilityStats.merge(DependabilityStats.zero(), old)
    assert int(merged["faults_detected"]) == 2
    assert int(merged["faults_corrected"]) == 0
    assert int(merged["checks_run"]) == 5


# ---------------------------------------------------------------------------
# Attention registry entries (the float hot kernel)
# ---------------------------------------------------------------------------


def _attn_case(seed=21, B=1, H=2, S=48, hd=16):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (B, H, S, hd)),
            jax.random.normal(kk, (B, H, S, hd)),
            jax.random.normal(kv, (B, H, S, hd)))


@pytest.mark.parametrize("backend", BACKENDS)
def test_attn_registry_close_across_backends(backend):
    """Float attention is tolerance-parity across backends (unlike the
    exact integer ops); within one backend the checked entry must agree
    with the plain entry bit-for-bit."""
    q, k, v = _attn_case()
    out = dispatch.attn(q, k, v, backend=backend)
    out_jnp = dispatch.attn(q, k, v, backend="jnp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_jnp),
                               rtol=2e-5, atol=2e-5)
    out2, check, csum = dispatch.attn_checksum(q, k, v, backend=backend)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    np.testing.assert_allclose(np.asarray(jnp.sum(out2, axis=-1)),
                               np.asarray(check), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(abft.output_row_checksums(out2)), np.asarray(csum))


def test_attn_entries_registered_on_all_builtins():
    for name in backend_mod.available_backends():
        be = backend_mod.get_backend(name)
        assert be.attn is not None and be.attn_checksum is not None, name
