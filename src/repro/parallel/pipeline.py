"""Pipeline parallelism: shard_map + ppermute microbatch pipeline.

This is the TPU-native rendition of the paper's **HPDP→HPDP chaining**: the
RTG4 can route one co-processor's output feature map *directly into another
HPDP*, which "immediately processes the next AI layer without additional data
transfer".  On a TPU mesh the same pattern is a pipeline stage axis: each
stage owns a contiguous block of layers, activations hop stage→stage over ICI
with ``lax.ppermute`` (never through the host), and microbatches keep every
stage busy — the dataflow-streaming idea at mesh scale.

Schedule: GPipe-style fill/steady/drain loop written with ``lax.fori_loop``
(so the HLO is one while loop regardless of microbatch count).  Autodiff
through ``ppermute`` transposes to the reverse permutation, so the same code
trains (the backward pass drains the pipeline in reverse) — no hand-written
1F1B needed for correctness; the forward schedule's bubble fraction is
(S-1)/(M+S-1), reported by ``bubble_fraction``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe schedule — the PP napkin-math term."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def stack_stage_params(param_list):
    """Stack per-stage param pytrees along a new leading 'stage' axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any,
                   microbatches: jax.Array,
                   mesh: Mesh,
                   axis: str = "stage",
                   checkpoint_stages: bool = True) -> jax.Array:
    """Run ``microbatches`` through a pipeline of stages over mesh axis ``axis``.

    stage_fn: (per-stage params, activation (mb, ...)) -> activation
    stage_params: pytree stacked on a leading stage axis (len = axis size)
    microbatches: (n_micro, mb, ...) — identical pytree structure in/out.

    Returns (n_micro, mb, ...) outputs (replicated over ``axis``).
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    total = n_micro + n_stages - 1
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn

    def body(carry_mb):
        """Per-device body under shard_map."""
        params, mb = carry_mb            # params: this stage's block params
        stage = lax.axis_index(axis)
        state = jnp.zeros_like(mb[0])    # live activation on this stage
        out = jnp.zeros_like(mb)         # collected on the last stage
        perm = [(i, i + 1) for i in range(n_stages - 1)]   # stage i -> i+1

        def tick(t, loop):
            state, out = loop
            # stage 0 ingests microbatch t during the fill/steady phase
            incoming = lax.dynamic_index_in_dim(
                mb, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)
            state = jnp.where(stage == 0, incoming, state)
            state = fn(params, state)
            # last stage emits microbatch t-(S-1) once the pipe is full
            emit_idx = jnp.maximum(t - (n_stages - 1), 0)
            emitted = lax.dynamic_update_index_in_dim(
                out, state, emit_idx, axis=0)
            take = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            out = jnp.where(take, emitted, out)
            # hop the live activation to the next stage
            state = lax.ppermute(state, axis, perm)
            return state, out

        state, out = lax.fori_loop(0, total, tick, (state, out))
        # only the last stage holds real outputs; broadcast them
        out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
        return lax.psum(out, axis)

    # params: leading stage axis sharded over `axis` (each device = its block);
    # microbatches replicated (stage 0 is the only consumer).
    pparams_spec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def per_device(params, mb):
        # shard_map gives a size-1 stage slice; drop the leading axis
        params = jax.tree_util.tree_map(lambda x: x[0], params)
        return body((params, mb))

    out = shard_map(
        per_device, mesh=mesh,
        in_specs=(pparams_spec, P()), out_specs=P(),
        check_vma=False,   # carry becomes stage-varying after the first hop
    )(stage_params, microbatches)
    return out


def pipeline_loss(stage_fn, stage_params, microbatches, targets_fn,
                  mesh: Mesh, axis: str = "stage"):
    """Mean loss over microbatches, differentiable through the pipeline."""
    out = pipeline_apply(stage_fn, stage_params, microbatches, mesh, axis)
    return targets_fn(out)
