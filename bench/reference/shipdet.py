"""The ship-detection CNN: the weights the benchmark deploys, and a plain
float32 reference of the network, independent of the program under test.

The network is the paper's detector as the configuration file lists it:
a stride-2 stem, the Table-1 trunk at its exact geometry, two stride-2
downsamples, a 1x1 head and a 6-channel detection head, ReLU between
layers, 'SAME' padding.  Weights are int8 with a float32 scale per output
channel; each layer's activations have a fixed scale and zero point.

``make_weights`` builds the per-layer parameters (int8 weights, their
scales and column sums, biases, activation scales) on the device in one
jitted call.  ``forward`` is the float32
reference on the dequantized weights (the network a quantized deployment
approximates); with ``lower=True`` it runs one step below the stated int8:
weights and every layer's input activations as int4, on the same ranges.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layers(cfg: dict) -> list:
    return cfg["layers"]


def make_weights(cfg: dict, key) -> list:
    """Per-layer parameters, made on the device from ``key``."""
    specs = layers(cfg)
    act = cfg["activation_scale"]

    @jax.jit
    def make(key):
        out = []
        for s, k in zip(specs, jax.random.split(key, len(specs))):
            shape = (s["kh"], s["kw"], s["cin"], s["cout"])
            # He scaling: activations keep their spread through ReLU
            w = jax.random.normal(k, shape) * jnp.sqrt(
                2.0 / (s["kh"] * s["kw"] * s["cin"]))
            scale = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1, 2)),
                                1e-9) / 127.0
            w_q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
            out.append({
                "w_q": w_q, "w_scale": scale.astype(jnp.float32),
                "colsum": jnp.sum(w_q.astype(jnp.int32), axis=(0, 1, 2)),
                "bias_f": jnp.zeros((s["cout"],), jnp.float32),
                "in_scale": jnp.float32(act), "in_zp": jnp.int32(0),
                "out_scale": jnp.float32(act), "out_zp": jnp.int32(0),
            })
        return out

    return make(key)


def _int4_columns(w):
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=(0, 1, 2), keepdims=True),
                    1e-9) / 7.0
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def forward(cfg: dict, params: list, x, lower: bool = False):
    """(N, H, W, 3) float32 tiles -> (N, h, w, 6) float32 detection map."""
    specs = layers(cfg)
    with jax.default_matmul_precision("highest"):
        for i, (s, p) in enumerate(zip(specs, params)):
            w = p["w_q"].astype(jnp.float32) * p["w_scale"]
            if lower:
                # int4 on the stated int8 ranges: 16 levels where int8 has
                # 256 (signed weights, unsigned post-ReLU activations)
                w = _int4_columns(w)
                step = p["in_scale"] * 255.0 / 15.0
                x = jnp.clip(jnp.round(x / step), -8, 7) * step
            x = jax.lax.conv_general_dilated(
                x, w, (s["stride"], s["stride"]), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = x + p["bias_f"]
            if i < len(specs) - 1:
                x = jax.nn.relu(x)
    return x
