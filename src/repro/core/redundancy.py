"""N-modular redundancy with bitwise majority voting.

The classical alternative to rad-hard silicon (and the one the paper cites as
"redundant execution").  Two deployment shapes:

* ``vote`` / ``tmr_apply`` — temporal redundancy: the same computation
  evaluated multiple times (with independent fault injection points in
  tests).  NOTE: XLA will CSE bit-identical pure subgraphs, so temporal
  redundancy against *hardware* faults must go through distinct devices; the
  pure form exists for the fault-injection harness and for voting on values
  that already come from different replicas.

* ``replicated_vote`` — spatial redundancy: `shard_map` over a replica mesh
  axis; each device computes the full function on identical inputs, then an
  all-gather + bitwise-majority vote masks any single-replica corruption.
  This is the cluster rendition of flying three flight computers.

Bitwise majority of three: maj(a,b,c) = (a&b) | (b&c) | (a&c) applied on the
bit-pattern (works for every dtype via bitcast, exact, branch-free, VPU-friendly).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.fault_injection import _as_bits


def _bitwise_majority3(a: jax.Array, b: jax.Array, c: jax.Array) -> jax.Array:
    ab, u = _as_bits(a)
    bb, _ = _as_bits(b)
    cb, _ = _as_bits(c)
    maj = (ab & bb) | (bb & cb) | (ab & cb)
    return jax.lax.bitcast_convert_type(maj, a.dtype)


def vote(replicas: Sequence[jax.Array]) -> jax.Array:
    """Majority vote across replica outputs (pytree-compatible leaves).

    3 replicas → bitwise majority (corrects any single corrupted replica).
    2 replicas → detection only: returns replica 0; use ``agree`` to check.
    """
    if len(replicas) == 3:
        return jax.tree_util.tree_map(_bitwise_majority3, *replicas)
    if len(replicas) == 2:
        return replicas[0]
    raise ValueError(f"vote() supports 2 or 3 replicas, got {len(replicas)}")


def agree(replicas: Sequence[jax.Array]) -> jax.Array:
    """() bool — all replicas bit-identical (DMR detection predicate)."""
    flat0 = jax.tree_util.tree_leaves(replicas[0])
    ok = jnp.array(True)
    for other in replicas[1:]:
        for a, b in zip(flat0, jax.tree_util.tree_leaves(other)):
            ab, _ = _as_bits(a)
            bb, _ = _as_bits(b)
            ok = ok & jnp.all(ab == bb)
    return ok


def dmr_apply(f: Callable, *args, injectors: Sequence[Callable | None] = (None, None)):
    """Dual modular redundancy, detect-only: run ``f`` twice (each pass
    optionally perturbed by an injector) and compare bit-for-bit.

    Returns ``(y0, detected)`` — replica 0's output plus a () bool that is
    True when the replicas disagree.  DMR cannot vote a fault away (no
    majority exists); its role is the cheap detect-then-escalate partner of
    a failover layer: half the cost of TMR, full single-fault detection.
    """
    outs = []
    for inj in injectors:
        y = f(*args)
        if inj is not None:
            y = jax.tree_util.tree_map(inj, y)
        outs.append(y)
    return outs[0], ~agree(outs)


def tmr_apply(f: Callable, *args, injectors: Sequence[Callable | None] = (None, None, None)):
    """Run ``f`` three times, each optionally perturbed by an injector
    (tests thread fault injection through here), and vote."""
    outs = []
    for inj in injectors:
        y = f(*args)
        if inj is not None:
            y = jax.tree_util.tree_map(inj, y)
        outs.append(y)
    return vote(outs)


def replicated_vote(f: Callable, mesh: jax.sharding.Mesh, axis: str = "replica"):
    """Spatial TMR: each device along ``axis`` (size 3) computes f fully,
    results are all-gathered and majority-voted on every device.

    Returns a function with the same signature as f; inputs must be
    replicated along ``axis``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def voted(*args):
        y = f(*args)

        def gather_vote(leaf):
            allr = jax.lax.all_gather(leaf, axis)          # (3, ...)
            return _bitwise_majority3(allr[0], allr[1], allr[2])

        return jax.tree_util.tree_map(gather_vote, y)

    return shard_map(voted, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)
