"""The paper's full application: Ship-Detection CNN on the quantized backend.

Satellite frames stream through the quantized CNN (OBPMark-ML Ship
Detection topology, the paper's Table-1 trunk) exactly as the HPDP system
runs it: every conv layer executes as int8 conv + fused requantization with
layer parameters streamed in — and layer outputs chain directly into the
next layer (the HPDP→HPDP path).  Float reference runs side by side as the
validation (paper Fig. 4).

    PYTHONPATH=src python examples/shipdet_pipeline.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import shipdet

specs = shipdet.reduced_specs()      # same topology, CPU-sized maps
print(f"ship-detector: {len(specs)} conv layers "
      f"({sum(s.macs for s in specs)/1e6:.1f} M MACs reduced geometry)")

params = shipdet.init_params(specs, jax.random.key(0))

rng = np.random.default_rng(0)
frames = jnp.asarray(rng.standard_normal((2, specs[0].h, specs[0].w, 3)),
                     jnp.float32)

t0 = time.time()
q_out, _ = shipdet.forward(specs, params, frames, use_kernel=True)
t_q = time.time() - t0
f_out = shipdet.float_forward(specs, params, frames)

err = float(jnp.abs(q_out - f_out).max())
step = float(params[-1]["out_scale"])
print(f"detection head out {q_out.shape}  (cls+box+obj per cell)")
print(f"quantized-vs-float: max abs {err:.4f} "
      f"({err/step:.1f} quantization steps of {step})")
assert err < 4 * step, "int8 pipeline diverged from float reference"

# per-layer agreement (the unit-test methodology of paper Fig. 4)
x = frames
print(f"\n{'layer':<12} {'out shape':<20} {'rel err':>8}")
for s, p in zip(specs, params):
    xq = shipdet.layer_forward(s, p, x, quantized=True)
    xf = shipdet.layer_forward(s, p, x, quantized=False)
    rel = float(jnp.linalg.norm(xq - xf) / (jnp.linalg.norm(xf) + 1e-9))
    print(f"{s.name:<12} {str(xq.shape):<20} {rel:8.4f}")
    x = jax.nn.relu(xq)          # chain the QUANTIZED stream (HPDP→HPDP)

print(f"\nforward wall time (quantized, CPU): {t_q*1e3:.1f} ms")
print("shipdet_pipeline OK")
