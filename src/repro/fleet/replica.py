"""One supervised serving replica: an Engine plus its dependability lifecycle.

The replica is the fleet's unit of failure.  Its state machine is the
recovery loop the ROADMAP asked for (quarantine → reload → re-verify →
readmit), driven by the supervisor:

    HEALTHY ──scrub fail / heartbeat loss──▶ QUARANTINED
    QUARANTINED ──checkpoint reload──▶ RECOVERING
    RECOVERING ──re-verify ok──▶ HEALTHY   (readmitted, recoveries += 1)
    RECOVERING ──re-verify fail──▶ DEAD
    any ──kill──▶ DEAD

Weight integrity is judged against deploy-time ABFT storage checksums
(``core.abft.storage_checksums``): computed once from the known-good params,
carried by every replica, exact mod 2^32 — the same Huang–Abraham identity
that guards the matmul accumulator, applied to the parameter store.
"""
from __future__ import annotations

import enum
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import abft
from repro.models.config import ArchConfig
from repro.runtime.serving import Engine, Request
from repro.train import checkpoint as ckpt_mod

# jitted once per pytree structure, shared by all replicas
_checksums_jit = jax.jit(abft.storage_checksums)
_verify_jit = jax.jit(abft.verify_storage)


class ReplicaState(str, enum.Enum):
    HEALTHY = "healthy"
    QUARANTINED = "quarantined"
    RECOVERING = "recovering"
    DEAD = "dead"


class Replica:
    """An ``Engine`` wrapped with identity, health state, and scrub support."""

    def __init__(self, rid: int, cfg: ArchConfig, params, *,
                 capacity: int = 4, max_len: int = 128, prefill_pad: int = 8,
                 snapshot_every: int = 16, eos_id: int = -1,
                 golden=None, compiled=None, backend: Optional[str] = None,
                 state_scrub: str = "off", device=None):
        self.rid = rid
        self.engine = Engine(cfg, params, capacity=capacity, max_len=max_len,
                             prefill_pad=prefill_pad,
                             snapshot_every=snapshot_every, eos_id=eos_id,
                             compiled=compiled, backend=backend,
                             state_scrub=state_scrub, device=device)
        self.state = ReplicaState.HEALTHY
        self.paused = False          # test hook: stop heartbeating (looks dead)
        self.routable = True         # False while a rolling deploy swaps us
        self.golden = golden if golden is not None else _checksums_jit(params)
        self.uncertified: List[Request] = []   # finished, awaiting clean scrub
        self.recoveries = 0
        self.last_clean_scrub_tick = 0
        self.last_scrub_bad: List[str] = []    # verdict of the newest scrub

    def install_certifier(self, gate) -> None:
        """Wire the fleet's release gate into this replica's certify stage:
        every request the engine finishes passes through
        ``gate(replica, req)`` before it may release — certify-before-
        release as a pipeline stage, not a wrapper."""
        self.engine.certify = lambda req: gate(self, req)

    # --------------------------------------------------------------- status
    @property
    def healthy(self) -> bool:
        return self.state is ReplicaState.HEALTHY and not self.paused

    def load(self) -> int:
        """Requests this replica's pipeline currently owns — router's cost."""
        return self.engine.executor.pending_count()

    def in_flight(self) -> List[Request]:
        """Every request in the replica's pipeline, in deterministic
        stage-then-slot order (the order failover drains replay in)."""
        return self.engine.executor.in_flight()

    # ---------------------------------------------------------------- scrub
    def scrub(self) -> List[str]:
        """Verify live weights against deploy-time checksums; returns the
        paths of corrupted leaves ([] == clean).  Paths use the checkpoint
        manifest's encoding (``train/checkpoint.path_str``), so a scrub
        verdict is directly a ``restore_leaves`` read-list — the link that
        makes quarantine-recovery incremental."""
        ok_tree = _verify_jit(self.engine.params, self.golden)
        flat, _ = jax.tree_util.tree_flatten_with_path(ok_tree)
        bad = []
        for path, ok in flat:
            if not bool(ok):
                bad.append(ckpt_mod.path_str(path))
        self.last_scrub_bad = bad
        return bad

    # ------------------------------------------------------------- recovery
    def reload(self, params):
        """Replace params with a known-good copy and clear all run state
        (the reload step of the recovery loop; compiled fns are kept)."""
        params = jax.tree_util.tree_map(jnp.asarray, params)
        self.engine.reset(params=params)
        self.uncertified = []

    def reload_leaves(self, leaves: Dict[str, np.ndarray]):
        """Incremental reload: patch only the named leaves (checkpoint-
        manifest paths → golden bytes) into the live params, then clear run
        state.  The quarantine-recovery fast path — a replica with two
        corrupted tensors re-reads two tensors, not the whole model."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.engine.params)
        patched = []
        for path, leaf in flat:
            p = ckpt_mod.path_str(path)
            if p in leaves:
                leaf = jnp.asarray(leaves[p], dtype=leaf.dtype).reshape(
                    leaf.shape)
            patched.append(leaf)
        self.engine.reset(params=jax.tree_util.tree_unflatten(treedef, patched))
        self.uncertified = []

    def patch_leaves(self, leaves: Dict[str, np.ndarray], golden=None):
        """Live weight swap for zero-drain rolling deploys: patch the named
        leaves into the running engine *without* resetting its pipeline —
        params are traced arguments of the compiled step fns, so in-flight
        decodes simply see the new weights on their next step.  ``golden``
        (the new deploy's storage checksums, computed from the checkpoint
        store, never from live weights) replaces the scrub baseline so
        re-verification certifies against what was *deployed*."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.engine.params)
        patched = []
        for path, leaf in flat:
            p = ckpt_mod.path_str(path)
            if p in leaves:
                leaf = jnp.asarray(leaves[p], dtype=leaf.dtype).reshape(
                    leaf.shape)
            patched.append(leaf)
        self.engine.params = jax.tree_util.tree_unflatten(treedef, patched)
        if golden is not None:
            self.golden = golden

    def reset(self, params=None):
        """Full revival for a new trial/run: fresh engine state, HEALTHY."""
        if params is not None:
            params = jax.tree_util.tree_map(jnp.asarray, params)
        self.engine.reset(params=params)
        self.uncertified = []
        self.state = ReplicaState.HEALTHY
        self.paused = False
        self.last_clean_scrub_tick = 0
        self.last_scrub_bad = []
