"""Dependability policy layer — composes ABFT / NMR / retry around the
quantized compute primitives.

This is the framework-level rendition of the paper's thesis: *dependable AI
execution is a property of the execution system, not of the model*.  Models
ask for a ``qlinear``; the policy decides how it is executed:

  NONE  — plain fused kernel (maximum throughput; rad-hard hardware assumed,
          as on the HPDP itself).
  ABFT  — exact integer checksum verify + recompute-recover (default for
          fleet deployment; ~1/N FLOP overhead).
  DMR   — dual execution + bitwise compare (2× cost, detect-only): raises
          the alarm but returns replica 0's output unchanged.  The cheap
          partner of a failover layer — the fleet supervisor quarantines the
          flagged replica and replays the work elsewhere.
  TMR   — triple execution + bitwise majority vote (3× cost; for the few
          layers whose corruption is mission-fatal, e.g. the final
          classification head of the ship detector).
  CKPT  — checkpoint/restart: detect via the same exact mod-2^32 checksum
          ABFT uses, but recover by *rolling back to the golden
          checkpointed operands and re-executing the whole op* instead of
          ABFT's selective row recompute.  With a golden operand checkpoint
          (``ckpt=``) the rollback also heals weight-memory SEUs — the one
          storage fault class ABFT can detect but never repair in place.
          Detection cost is ABFT's ~1/N; recovery cost is one full
          re-execution, paid only on (rare) detection.  See
          docs/recovery.md.

Policies are data (config enums), so a deployment can mix them per layer —
matching how the paper reserves the rad-hard HPDP for the convolution hot
path while the RTG4 handles orchestration.

*Where* the accumulator is computed is equally data: every policy is built
around a pluggable execution backend (``core.backend``), so the same
NONE/ABFT/DMR/TMR algebra runs unchanged on the jnp path, the independent
ref oracle, or the Pallas kernels — the swappable-co-processor property the
paper claims for the HPDP.  The zero-point/bias dequant algebra lives in
one shared helper (``abft.zp_bias_correct``), used by every backend and
policy, so the epilogue cannot drift between paths.
"""
from __future__ import annotations

import enum
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import abft as abft_mod
from repro.core import backend as backend_mod
from repro.core import redundancy
from repro.core.quant import requantize


class Policy(str, enum.Enum):
    NONE = "none"
    ABFT = "abft"
    DMR = "dmr"
    TMR = "tmr"
    CKPT = "ckpt"


class DependabilityStats:
    """Host-side counters exported by dependable ops (pytree of scalars).

    ``faults_detected``  checks that flagged a divergence (ABFT checksum
                         mismatch, DMR/TMR replica disagreement).
    ``faults_corrected`` detected faults the op also healed in-place (ABFT
                         recompute-recovery that re-verified clean, TMR
                         majority votes that out-voted the bad replica).
                         DMR never corrects — its count stays 0 and the gap
                         vs ``faults_detected`` is exactly the failover
                         layer's workload.
    ``faults_recovered`` detected faults healed by *rollback* — checkpoint/
                         restart re-execution from golden state (CKPT ops,
                         engine snapshot restores, fleet incremental
                         restores).  Disjoint accounting from
                         ``faults_corrected`` so reports can separate
                         in-place correction from restart recovery.
    ``checks_run``       how many verification opportunities executed.
    """

    @staticmethod
    def zero():
        return {"faults_detected": jnp.zeros((), jnp.int32),
                "faults_corrected": jnp.zeros((), jnp.int32),
                "faults_recovered": jnp.zeros((), jnp.int32),
                "checks_run": jnp.zeros((), jnp.int32)}

    @staticmethod
    def merge(a: dict, b: dict) -> dict:
        """Keywise sum over the union of two stats pytrees (campaign /
        engine rollups; tolerant of older dicts missing newer counters)."""
        zero = jnp.zeros((), jnp.int32)
        return {k: a.get(k, zero) + b.get(k, zero)
                for k in {*a, *b}}

    @staticmethod
    def to_host(stats: dict) -> dict:
        """Device scalars → plain ints, for JSON reports and log lines."""
        return {k: int(v) for k, v in stats.items()}


class collect_stats:
    """Trace-time sink for the stats of in-graph checks.

    ``with collect_stats() as sink:`` around a model call, in the function
    being traced, gathers what the model ``deposit``s while it is traced;
    ``sink.stats`` is then their sum, an output the caller can return.  The
    model keeps its signature: a model traced with no sink open drops its
    stats.  Deposits must come from the trace the sink was opened in (a
    model sums its per-layer stats through its layer scan and deposits
    once, outside it)."""

    _open = threading.local()

    def __init__(self):
        self.stats = DependabilityStats.zero()

    def __enter__(self) -> "collect_stats":
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self._open.stack.pop()

    @classmethod
    def deposit(cls, stats: dict) -> None:
        """Add ``stats`` to the innermost open sink (none open: dropped)."""
        stack = getattr(cls._open, "stack", None)
        if stack:
            sink = stack[-1]
            sink.stats = DependabilityStats.merge(sink.stats, stats)


def _bump(stats: dict, detected, corrected, recovered=False) -> dict:
    """One verification round folded into the running counters."""
    return {
        "faults_detected": stats["faults_detected"]
        + jnp.asarray(detected).astype(jnp.int32),
        "faults_corrected": stats.get("faults_corrected", jnp.int32(0))
        + jnp.asarray(corrected).astype(jnp.int32),
        "faults_recovered": stats.get("faults_recovered", jnp.int32(0))
        + jnp.asarray(recovered).astype(jnp.int32),
        "checks_run": stats["checks_run"] + 1,
    }


def dependable_qmatmul(
    policy: Policy,
    x_q: jax.Array, x_zp: jax.Array, w_q: jax.Array, bias: jax.Array,
    scale: jax.Array, out_zp: jax.Array,
    *, inject=None, stats: Optional[dict] = None, w_check=None,
    ckpt=None, backend: backend_mod.BackendLike = None,
):
    """Quantized matmul + requant executed under a dependability policy.

    ``inject`` corrupts the int32 accumulator (the campaign engine's
    accumulator injection site); ``w_check`` is the optional deploy-time
    checksum vector (see ``abft.abft_qmatmul``); ``ckpt`` is the optional
    golden operand checkpoint ``(x_q, w_q)`` the CKPT policy rolls back to
    (defaults to the live operands — transient coverage only); ``backend``
    picks the execution engine (per-call > per-layer > global, see
    core/backend.py).  Returns (y_q int8, stats).
    """
    if stats is None:
        stats = DependabilityStats.zero()
    be = backend_mod.resolve(backend)

    def finish(acc_dot):
        # shared dequant epilogue (abft.zp_bias_correct is the same algebra
        # the ABFT path applies), then requant
        return requantize(abft_mod.zp_bias_correct(acc_dot, x_zp, w_q, bias),
                          scale, out_zp)

    if policy == Policy.ABFT:
        res = abft_mod.abft_qmatmul(x_q, x_zp, w_q, bias, inject=inject,
                                    w_check=w_check, backend=be)
        y = requantize(res.acc, scale, out_zp)
        corrected = res.faults_detected * res.ok.astype(jnp.int32)
        return y, _bump(stats, res.faults_detected, corrected)

    if policy == Policy.CKPT:
        # checkpoint/restart: checksum-detect, then roll back to the golden
        # operand checkpoint and re-execute everything (epilogue included —
        # a corrupted w_q must not leak through zp/colsum algebra)
        ck_x, ck_w = (x_q, w_q) if ckpt is None else ckpt
        wc = w_check if w_check is not None else abft_mod.checksum_vector(ck_w)
        acc_dot, want = be.matmul_acc_checksum(x_q, w_q, wc)
        if inject is not None:
            acc_dot = inject(acc_dot)
        detected = jnp.any(jnp.sum(acc_dot, axis=1) != want)

        def rollback(_):
            return be.matmul_acc(ck_x, ck_w), ck_w

        acc_dot, w_eff = jax.lax.cond(
            detected, rollback, lambda a: (a, w_q), acc_dot)
        # re-verify the restart: clean ⇒ the fault did not recur
        recovered = detected & jnp.all(jnp.sum(acc_dot, axis=1) == want)
        y = requantize(abft_mod.zp_bias_correct(acc_dot, x_zp, w_eff, bias),
                       scale, out_zp)
        return y, _bump(stats, detected, False, recovered)

    def run(inj):
        # inject corrupts replica 0's accumulator — the same site as the
        # ABFT/NONE paths, so policy sweeps compare like for like
        acc = be.matmul_acc(x_q, w_q)
        if inj is not None:
            acc = inj(acc)
        return finish(acc)

    if policy == Policy.DMR:
        # detect-only: replica 0 (possibly faulted) is returned as-is;
        # disagreement with the clean re-execution raises the alarm
        y = run(inject)
        detected = ~redundancy.agree([y, run(None)])
        return y, _bump(stats, detected, False)

    if policy == Policy.TMR:
        r0, r1 = run(inject), run(None)
        # replicas 1–2 are clean, so r0-vs-r1 disagreement is exactly the
        # set of faults the majority vote is about to mask — count them
        disagreed = ~redundancy.agree([r0, r1])
        y = redundancy.vote([r0, r1, run(None)])
        return y, _bump(stats, disagreed, disagreed)

    # Policy.NONE — plain path
    return run(inject), stats


def dependable_matmul_acc(
    policy: Policy,
    x_q: jax.Array, w_q: jax.Array,
    *, inject=None, stats: Optional[dict] = None, w_check=None,
    backend: backend_mod.BackendLike = None,
):
    """Bare int32 accumulator ``x_q @ w_q`` under a dependability policy —
    the building block :class:`~repro.core.policy_map.PolicyMap` threads
    into hot paths that own their *own* dequant epilogue (the transformer's
    W8A8 FFN ``_qdot``), where ``dependable_qmatmul``'s zero-point/requant
    algebra does not apply.

    All policies are bit-identical to the plain ``be.matmul_acc`` on clean
    runs: the math is exact integer, checks never fire, votes of equal
    replicas are the replica.  Per policy:

      ABFT  Huang–Abraham row-checksum verify; flagged *rows* recompute
            under ``lax.cond`` (exact math ⇒ bit-stable) and the fresh rows
            are selected in.  Heals transient accumulator faults in place.
      CKPT  same detection; rollback re-executes the *whole* op from the
            live operands under ``lax.cond``.
      DMR   dual execution; detect-only.  NOTE: inside a ``lax.scan`` layer
            stack the alarm has no surface to escape through, so the
            serving DSE search space excludes DMR at FFN sites — the stats
            counter is the only witness.
      TMR   triple execution + bitwise majority vote.  Under jit, XLA CSE
            may collapse bit-identical clean replicas — temporal redundancy
            is modeled, not physically enforced; the measured cost oracle
            (repro/dse/cost.py) reports whatever the compiled program
            actually costs.

    Returns ``(acc int32, stats)``.
    """
    if stats is None:
        stats = DependabilityStats.zero()
    be = backend_mod.resolve(backend)

    if policy in (Policy.ABFT, Policy.CKPT):
        wc = w_check if w_check is not None else abft_mod.checksum_vector(w_q)
        acc, want = be.matmul_acc_checksum(x_q, w_q, wc)
        if inject is not None:
            acc = inject(acc)
        row_bad = jnp.sum(acc, axis=1) != want
        detected = jnp.any(row_bad)

        # the re-verify runs inside the recovery branch, so a clean pass
        # pays for no second row sum
        def reverified(a):
            return a, jnp.all(jnp.sum(a, axis=1) == want)

        if policy == Policy.ABFT:
            def recover(a):
                fresh = be.matmul_acc(x_q, w_q)
                return reverified(jnp.where(row_bad[:, None], fresh, a))
        else:
            def recover(_):
                return reverified(be.matmul_acc(x_q, w_q))
        acc, healed = jax.lax.cond(detected, recover,
                                   lambda a: (a, jnp.bool_(False)), acc)
        corrected = healed if policy == Policy.ABFT else False
        recovered = healed if policy == Policy.CKPT else False
        return acc, _bump(stats, detected, corrected, recovered)

    def run(inj):
        acc = be.matmul_acc(x_q, w_q)
        if inj is not None:
            acc = inj(acc)
        return acc

    if policy == Policy.DMR:
        acc = run(inject)
        detected = ~redundancy.agree([acc, run(None)])
        return acc, _bump(stats, detected, False)

    if policy == Policy.TMR:
        r0, r1 = run(inject), run(None)
        disagreed = ~redundancy.agree([r0, r1])
        acc = redundancy.vote([r0, r1, run(None)])
        return acc, _bump(stats, disagreed, disagreed)

    return run(inject), stats


def dependable_attention(
    policy: Policy,
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal=True, window=None,
    inject=None, stats: Optional[dict] = None,
    backend: backend_mod.BackendLike = None, tol: float = 1e-3,
):
    """Fused attention (B,H,S,hd) under a dependability policy — the float
    twin of ``dependable_qmatmul`` covering the one hot kernel the integer
    quantization story cannot absorb.

    Float math admits no exact compute checksum, so ABFT here is two-tier
    (see kernels/flashattn and docs/backends.md):

      * a float check column accumulated *in the execution path* alongside
        the output, verified as ``|rowsum_hd(out) - check| <= tol*(|check|+1)``
        — tolerance-based, covers the softmax/accumulate compute path;
      * an exact mod-2^32 bit checksum of the emitted output rows, verified
        bit-for-bit — covers the emitted result itself, so any single bit
        flip of the output is detected with zero false negatives (the float
        tier alone would miss low-mantissa flips).

    ``inject`` corrupts the kernel output (the campaign's activations site);
    recovery recomputes flagged rows from the plain ``be.attn`` path, which
    is bit-identical to the checked kernel's output (enforced by
    tests/test_flashattn.py), so ABFT correction is bit-exact.
    Returns (out, stats).
    """
    if stats is None:
        stats = DependabilityStats.zero()
    be = backend_mod.resolve(backend)
    if be.attn is None or be.attn_checksum is None:
        raise ValueError(f"backend {be.name!r} does not register attention")

    def plain(inj):
        out = be.attn(q, k, v, causal=causal, window=window)
        if inj is not None:
            out = inj(out)
        return out

    def row_ok_mask(out, check, csum):
        bit_ok = abft_mod.output_row_checksums(out) == csum
        flt_ok = jnp.abs(jnp.sum(out.astype(jnp.float32), axis=-1) - check) \
            <= tol * (jnp.abs(check) + 1.0)
        return bit_ok & flt_ok

    # NOTE on recovery: integer ABFT recomputes under ``lax.cond`` because
    # exact math is bit-stable across compilation contexts.  Float attention
    # is not — a cond branch compiles as its own fused XLA program whose
    # low-order bits can differ from the in-context result — so both float
    # policies recompute *unconditionally in the same execution context* and
    # select.  Eagerly the recompute dispatches the same ops (bit-identical);
    # under jit/vmap both calls live in one program and CSE collapses them,
    # so recovery is bit-exact and the recompute is free on the clean path.

    if policy == Policy.ABFT:
        out, check, csum = be.attn_checksum(q, k, v, causal=causal,
                                            window=window)
        if inject is not None:
            out = inject(out)
        row_ok = row_ok_mask(out, check, csum)
        faults = jnp.sum(~row_ok).astype(jnp.int32)
        fresh = be.attn(q, k, v, causal=causal, window=window)
        out = jnp.where(row_ok[..., None], out, fresh)
        ok = jnp.all(row_ok_mask(out, check, csum))
        corrected = faults * ok.astype(jnp.int32)
        return out, _bump(stats, faults, corrected)

    if policy == Policy.CKPT:
        # detect via the fused two-tier check, recover by re-executing the
        # whole op from the operands instead of selective rows
        out, check, csum = be.attn_checksum(q, k, v, causal=causal,
                                            window=window)
        if inject is not None:
            out = inject(out)
        detected = jnp.any(~row_ok_mask(out, check, csum))
        fresh = be.attn(q, k, v, causal=causal, window=window)
        out = jnp.where(detected, fresh, out)
        recovered = detected & jnp.all(row_ok_mask(out, check, csum))
        return out, _bump(stats, detected, False, recovered)

    if policy == Policy.DMR:
        out = plain(inject)
        detected = ~redundancy.agree([out, plain(None)])
        return out, _bump(stats, detected, False)

    if policy == Policy.TMR:
        r0, r1 = plain(inject), plain(None)
        disagreed = ~redundancy.agree([r0, r1])
        out = redundancy.vote([r0, r1, plain(None)])
        return out, _bump(stats, disagreed, disagreed)

    return plain(inject), stats


def dependable_qconv2d(
    policy: Policy,
    x_q: jax.Array, x_zp: jax.Array, w_q: jax.Array, bias: jax.Array,
    scale: jax.Array, out_zp: jax.Array,
    *, stride=(1, 1), padding="SAME",
    inject=None, stats: Optional[dict] = None, w_check=None,
    ckpt=None, backend: backend_mod.BackendLike = None,
):
    """Quantized NHWC conv + requant under a dependability policy — the conv
    twin of ``dependable_qmatmul`` so every campaign injection site drives
    matmul and conv through one uniform hook surface.

    Returns (y_q int8, stats dict).
    """
    if stats is None:
        stats = DependabilityStats.zero()
    be = backend_mod.resolve(backend)

    def finish(acc):
        return requantize(acc + bias[None, None, None, :], scale, out_zp)

    if policy == Policy.ABFT:
        res = abft_mod.abft_qconv2d(x_q, x_zp, w_q, bias, stride=stride,
                                    padding=padding, inject=inject,
                                    w_check=w_check, backend=be)
        y = requantize(res.acc, scale, out_zp)
        corrected = res.faults_detected * res.ok.astype(jnp.int32)
        return y, _bump(stats, res.faults_detected, corrected)

    if policy == Policy.CKPT:
        ck_x, ck_w = (x_q, w_q) if ckpt is None else ckpt
        wc = w_check if w_check is not None \
            else abft_mod.conv_checksum_weight(ck_w)
        acc_dot, want = be.conv_acc_checksum(x_q, x_zp, w_q, wc, stride,
                                             padding)
        if inject is not None:
            acc_dot = inject(acc_dot)
        detected = jnp.any(jnp.sum(acc_dot, axis=3) != want)

        def rollback(_):
            return be.conv_acc(ck_x, x_zp, ck_w, stride, padding)

        acc_dot = jax.lax.cond(detected, rollback, lambda a: a, acc_dot)
        recovered = detected & jnp.all(jnp.sum(acc_dot, axis=3) == want)
        y = finish(acc_dot)
        return y, _bump(stats, detected, False, recovered)

    def run(inj):
        acc = be.conv_acc(x_q, x_zp, w_q, stride, padding)
        if inj is not None:
            acc = inj(acc)
        return finish(acc)

    if policy == Policy.DMR:
        y = run(inject)
        detected = ~redundancy.agree([y, run(None)])
        return y, _bump(stats, detected, False)

    if policy == Policy.TMR:
        r0, r1 = run(inject), run(None)
        disagreed = ~redundancy.agree([r0, r1])
        y = redundancy.vote([r0, r1, run(None)])
        return y, _bump(stats, disagreed, disagreed)

    return run(inject), stats
