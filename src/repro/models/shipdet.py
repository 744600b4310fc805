"""Ship Detection CNN — the paper's own workload (OBPMark-ML, YoloX-style).

A compact quantized detector backbone whose middle layers are *exactly* the
four Table-1 layers of the paper (kernel / image geometry):

    conv1:  24×3×3×24  @ 194×194×24
    conv2:  48×3×3×48  @  98× 98×48
    conv3:  96×3×3×96  @  50× 50×96
    conv4:  96×1×1×96  @  96× 96×96   (parallel 1×1 branch)

Every convolution executes as int8 conv + fused re-quantization through
kernels/qconv2d — i.e. the exact op the HPDP runs — composed into a network
by the framework (the role Klepsydra AI + RTG4 orchestration plays in the
paper).  Dependability policy applies per layer (core/dependability).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import abft as abft_mod
from repro.core import quant
from repro.core.dependability import (
    DependabilityStats, Policy, dependable_qconv2d)
from repro.kernels.qconv2d import ops as qconv_ops


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    name: str
    kh: int
    kw: int
    cin: int
    cout: int
    h: int                 # input spatial (square images per the paper's table)
    w: int
    stride: int = 1

    @property
    def macs(self) -> int:
        return self.h * self.w * self.cin * self.cout * self.kh * self.kw // (self.stride ** 2)


# The paper's Table-1 layers, exact geometry.
TABLE1_LAYERS = [
    ConvSpec("conv_24x3x3x24", 3, 3, 24, 24, 194, 194),
    ConvSpec("conv_48x3x3x48", 3, 3, 48, 48, 98, 98),
    ConvSpec("conv_96x3x3x96", 3, 3, 96, 96, 50, 50),
    ConvSpec("conv_96x1x1x96", 1, 1, 96, 96, 96, 96),
]


def network_specs(img: int = 194) -> List[ConvSpec]:
    """Full ship-detector: stem + Table-1 trunk + head."""
    return [
        ConvSpec("stem", 3, 3, 3, 24, img * 2, img * 2, stride=2),
        TABLE1_LAYERS[0],
        ConvSpec("down1", 3, 3, 24, 48, 194, 194, stride=2),
        TABLE1_LAYERS[1],
        ConvSpec("down2", 3, 3, 48, 96, 98, 98, stride=2),
        TABLE1_LAYERS[2],
        ConvSpec("head1x1", 1, 1, 96, 96, 50, 50),
        ConvSpec("det_head", 1, 1, 96, 6, 50, 50),     # 1 class + 4 box + obj
    ]


def reduced_specs() -> List[ConvSpec]:
    """Small variant for CPU smoke tests (same topology, 16× smaller maps)."""
    full = network_specs()
    out = []
    for s in full:
        out.append(dataclasses.replace(s, h=max(s.h // 8, 4), w=max(s.w // 8, 4)))
    return out


def init_params(specs: List[ConvSpec], key: jax.Array) -> List[Dict[str, Any]]:
    """Float master weights + static activation qparams per layer (calibrated)."""
    params = []
    keys = jax.random.split(key, len(specs))
    for s, k in zip(specs, keys):
        w = jax.random.normal(k, (s.kh, s.kw, s.cin, s.cout)) * (
            1.0 / jnp.sqrt(s.kh * s.kw * s.cin))
        b = jnp.zeros((s.cout,), jnp.float32)
        params.append({
            "qconv": qconv_ops.make_qconv_params(w, b),
            # static calibration (identity-ish ranges; real deployments run
            # the MinMaxObserver over a calibration set)
            "in_scale": jnp.float32(0.05), "in_zp": jnp.int32(0),
            "out_scale": jnp.float32(0.05), "out_zp": jnp.int32(0),
        })
    return params


def deploy_checks(params: List[Dict[str, Any]]) -> List[jax.Array]:
    """Deploy-time per-layer weight checksums (the Huang–Abraham conv
    identity over the known-good quantized weights).  Shipped alongside the
    model exactly like the fleet's storage checksums: a later ``forward``
    with ``w_checks=`` verifies the *live* weights against these, so a
    weight-memory SEU between deploy and execution is detected (ABFT) or
    healed by rollback to ``golden_weights`` (CKPT)."""
    return [abft_mod.conv_checksum_weight(p["qconv"].w_q) for p in params]


def golden_weights(params: List[Dict[str, Any]]) -> List[jax.Array]:
    """The known-good quantized weights per layer — the operand checkpoint
    CKPT rolls back to when a deploy-time check fails."""
    return [p["qconv"].w_q for p in params]


def forward(specs: List[ConvSpec], params: List[Dict[str, Any]], x: jax.Array,
            *, policy: Policy = Policy.NONE, policy_map=None,
            use_kernel: bool = False, inject=None, inject_layer=None,
            backend=None, w_checks: Optional[List[jax.Array]] = None,
            golden_wq: Optional[List[jax.Array]] = None
            ) -> Tuple[jax.Array, Dict]:
    """x: (N, H, W, 3) float in [0,1]. Returns (det map, dependability stats).

    ``backend`` selects the quantized-conv execution engine (core/backend
    registry): a single name applies network-wide, a sequence applies
    per-layer — the software rendition of the paper reserving the rad-hard
    HPDP for the convolution trunk while other layers run elsewhere.

    ``w_checks`` (from ``deploy_checks``) turns ABFT/CKPT layers into
    deploy-time weight scrubs: the per-layer checksum is verified against
    the shipped value instead of one recomputed from the (possibly struck)
    live weights.  ``golden_wq`` (from ``golden_weights``) additionally
    gives CKPT layers a rollback target, so a weight SEU is *healed* by
    re-executing from the known-good weights, not just flagged.

    ``policy_map`` (core/policy_map.py) replaces the single network-wide
    ``policy`` with a per-layer assignment resolved by ``ConvSpec.name`` —
    the Python layer loop gives the CNN true per-layer granularity, so
    selective-hardening DSE searches this space directly.  Under a map,
    DMR/TMR run *in the op* per layer (layer-level temporal redundancy)
    rather than via network-level replication; clean outputs stay
    bit-identical to the unmapped path for every policy (exact integer
    checks never fire, votes of equal replicas are the replica).  Exactly
    one of ``policy`` / ``policy_map`` may be non-trivial.

    ``inject_layer`` overrides the default mid-network accumulator
    injection site with an explicit layer index (per-layer fault-injection
    campaigns; None keeps the legacy mid-layer hook).
    """
    if policy_map is not None and policy is not Policy.NONE:
        raise ValueError("pass either policy= or policy_map=, not both")
    stats = DependabilityStats.zero()
    if backend is None or isinstance(backend, str):
        layer_backends = [backend] * len(specs)
    else:
        layer_backends = list(backend)
        assert len(layer_backends) == len(specs), \
            (len(layer_backends), len(specs))
    hook_layer = len(specs) // 2 if inject_layer is None else inject_layer
    for i, (s, p) in enumerate(zip(specs, params)):
        stride = (s.stride, s.stride)
        layer_be = layer_backends[i]
        # uniform accumulator injection site: the mid-layer int32 accumulator
        # is reachable under every policy, so fault-injection campaigns
        # measure all policies on the same hook
        layer_inject = inject if i == hook_layer else None
        if policy_map is not None:
            layer_policy, pm_backend = policy_map.resolve(s.name)
            layer_be = pm_backend or layer_be
            in_op_policy = layer_policy
        else:
            layer_policy = policy
            # ABFT and CKPT run inside the op (checksum detect; recompute-
            # vs rollback-recover); NMR policies replicate at the network
            # level, so their per-layer call is the plain path
            in_op_policy = policy if policy in (Policy.ABFT, Policy.CKPT) \
                else Policy.NONE
        if layer_policy != Policy.NONE or layer_inject is not None \
                or layer_be is not None:
            x_q = quant.quantize(x, p["in_scale"], p["in_zp"])
            bias_i32 = jnp.round(
                p["qconv"].bias_f / (p["in_scale"] * p["qconv"].w_scale)
            ).astype(jnp.int32)
            rq = quant.requant_scale(p["in_scale"], p["qconv"].w_scale,
                                     p["out_scale"])
            y_q, lstats = dependable_qconv2d(
                in_op_policy,
                x_q, p["in_zp"], p["qconv"].w_q, bias_i32, rq, p["out_zp"],
                stride=stride, padding="SAME", inject=layer_inject,
                backend=layer_be,
                w_check=w_checks[i] if w_checks is not None else None,
                ckpt=((x_q, golden_wq[i]) if golden_wq is not None
                      else None))
            x = (y_q.astype(jnp.float32) - p["out_zp"]) * p["out_scale"]
            stats = DependabilityStats.merge(stats, lstats)
        else:
            x = qconv_ops.qconv_act(
                x, p["qconv"], p["in_scale"], p["in_zp"],
                p["out_scale"], p["out_zp"], stride=stride, padding="SAME",
                use_kernel=use_kernel)
        if i < len(specs) - 1:
            x = jax.nn.relu(x)
    return x, stats


def layer_forward(s: ConvSpec, p: Dict[str, Any], x: jax.Array,
                  quantized: bool = True) -> jax.Array:
    """One layer, float in → float out; quantized=False is the float oracle
    (dequantized weights, float conv) used by the Fig.-4-style validation."""
    stride = (s.stride, s.stride)
    if quantized:
        return qconv_ops.qconv_act(
            x, p["qconv"], p["in_scale"], p["in_zp"],
            p["out_scale"], p["out_zp"], stride=stride, padding="SAME",
            use_kernel=True)
    w = p["qconv"].w_q.astype(jnp.float32) * p["qconv"].w_scale
    y = jax.lax.conv_general_dilated(
        x, w, stride, "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["qconv"].bias_f


def float_forward(specs: List[ConvSpec], params: List[Dict[str, Any]],
                  x: jax.Array) -> jax.Array:
    """Float-oracle network forward (dequantized weights)."""
    for i, (s, p) in enumerate(zip(specs, params)):
        x = layer_forward(s, p, x, quantized=False)
        if i < len(specs) - 1:
            x = jax.nn.relu(x)
    return x
