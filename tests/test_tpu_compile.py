"""Compile the main path's Pallas kernels for a TPU v5e, without the chip.

Each test lowers one kernel at the shapes the system runs (smollm-135m's
FFN at decode batch 8, the ship detector's layers, attention with
head_dim 64) for a described ``v5e:2x2`` topology and compiles it with the
installed TPU compiler: what Mosaic would refuse on the chip fails here.
Every kernel must come out as a ``tpu_custom_call`` (compiled, not
interpreted).  Nothing runs, so results and times are not checked here.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.flashattn.kernel import (
    flash_attention_bwd, flash_attention_checked, flash_attention_fwd_lse)
from repro.kernels.qmatmul.kernel import qmatmul, qmatmul_acc_checksum
from repro.models import shipdet

I8, I32, F32, BF16 = jnp.int8, jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo


@pytest.mark.parametrize("k,n", [(576, 1536), (1536, 576)])
def test_qmatmul_acc_checksum_decode(one_chip, k, n):
    """The fused ABFT check of every protected FFN matmul (int8 limbs)."""
    _compile(qmatmul_acc_checksum, one_chip, ((8, k), I8), ((k, n), I8),
             ((k,), I32))


def test_qmatmul_requant(one_chip):
    _compile(qmatmul, one_chip, ((8, 576), I8), ((576, 1536), I8),
             ((1536,), I32), ((1536,), I32), ((1536,), F32), ((2,), I32))


@pytest.mark.parametrize("layer,hw", [("conv_24x3x3x24", 194),
                                      ("down1", 194)])
def test_qconv2d_acc_checksum_shipdet(one_chip, layer, hw):
    """One stride-1 and one stride-2 layer of the ship detector, through
    the pallas backend's entry (zero-point padding included)."""
    s = next(s for s in shipdet.network_specs() if s.name == layer)
    fn = functools.partial(dispatch.conv_acc_checksum,
                           stride=(s.stride, s.stride), padding="SAME",
                           backend="pallas")
    _compile(fn, one_chip, ((1, hw, hw, s.cin), I8), ((), I32),
             ((s.kh, s.kw, s.cin, s.cout), I8), ((s.kh, s.kw, s.cin, 1), I32))


def test_flash_attention_checked(one_chip):
    q, kv = ((1, 9, 256, 64), BF16), ((1, 3, 256, 64), BF16)
    _compile(flash_attention_checked, one_chip, q, kv, kv)


def test_flash_attention_fwd_lse_and_bwd(one_chip):
    q, kv = ((1, 9, 256, 64), BF16), ((1, 3, 256, 64), BF16)
    _compile(flash_attention_fwd_lse, one_chip, q, kv, kv)
    _compile(flash_attention_bwd, one_chip, q, kv, kv, q,
             ((1, 9, 256), F32), q)
