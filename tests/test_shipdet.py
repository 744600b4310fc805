"""Ship-detection CNN (the paper's workload): end-to-end quantized inference,
kernel-vs-ref agreement at network level, ABFT policy recovery."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dependability import Policy
from repro.models import shipdet

jax.config.update("jax_platform_name", "cpu")


def _setup():
    specs = shipdet.reduced_specs()
    params = shipdet.init_params(specs, jax.random.key(0))
    x = jax.random.uniform(jax.random.key(1), (1, specs[0].h, specs[0].w, 3))
    return specs, params, x


def test_forward_shapes_and_finite():
    specs, params, x = _setup()
    y, stats = shipdet.forward(specs, params, x)
    assert y.shape[-1] == 6                      # det head channels
    assert np.isfinite(np.asarray(y)).all()


def test_kernel_path_matches_ref_path():
    """Whole-network agreement between Pallas(interpret) and jnp reference —
    the paper's Fig. 4 validation applied end-to-end instead of per-layer."""
    specs, params, x = _setup()
    y_ref, _ = shipdet.forward(specs, params, x, use_kernel=False)
    y_ker, _ = shipdet.forward(specs, params, x, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_ker))


def test_abft_policy_detects_and_recovers():
    specs, params, x = _setup()
    y_clean, stats = shipdet.forward(specs, params, x, policy=Policy.ABFT)
    assert int(stats["checks_run"]) == len(specs)
    assert int(stats["faults_detected"]) == 0

    def inject(acc):
        return acc.at[0, 1, 1, 0].add(jnp.int32(1 << 18))

    y_faulty, stats = shipdet.forward(specs, params, x, policy=Policy.ABFT,
                                      inject=inject)
    assert int(stats["faults_detected"]) >= 1
    np.testing.assert_array_equal(np.asarray(y_faulty), np.asarray(y_clean))


def test_table1_specs_match_paper():
    """Guard: the benchmark layer geometry is exactly the paper's Table 1."""
    t = shipdet.TABLE1_LAYERS
    assert (t[0].cout, t[0].kh, t[0].kw, t[0].cin) == (24, 3, 3, 24)
    assert (t[0].h, t[0].w) == (194, 194)
    assert (t[1].cout, t[1].cin, t[1].h) == (48, 48, 98)
    assert (t[2].cout, t[2].cin, t[2].h) == (96, 96, 50)
    assert (t[3].kh, t[3].kw, t[3].h) == (1, 1, 96)
