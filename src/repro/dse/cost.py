"""Measured cost oracle for the selective-hardening DSE.

The throughput axis of the Pareto search is *measured*, not modeled: every
(site × policy) combination in a search space is microbenchmarked at the
shapes the real workload executes — the transformer FFN sites on the
engine's own multi-step scanned decode window (mapped config, argmax
decode step), the shipdet conv
layers through ``dependable_qconv2d``, and the engine-level scrub machinery
(storage-checksum verify, decode-state checksum) that the engine pays on
its pump cadence.  The result is one machine-readable JSON document
(``measure(...)`` → ``CostModel.to_doc``) that the search consumes as its
cost objective and the committed reports quote verbatim — the same numbers
``benchmarks/campaign_bench.py`` prints for its kernel-scale table
(``policy_overhead`` section of ``BENCH_campaign.json``) at campaign
shapes.

``CostModel.predict(space, genome)`` combines the measurements
analytically into an estimated cost per decode step (serving) or per
forward (shipdet):

    serving:  Σ_site (ms[site][gene] − ms[site][none])   # mapped decode-step Δ
              + storage-verify ms ÷ cadence(weights gene)
              + state-scrub ms by derived mode (detect: one checksum per
                pump; rollback: checksum + snapshot bookkeeping, ≈ 2×)
    shipdet:  Σ_layer ms[layer][gene]

Costs are CPU wall-clock — relative ordering is the signal (the same
caveat every bench in this repo carries); the certified end-to-end ratio
comes from ``benchmarks/serving_bench --policy-map``, not from this model.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dependability import Policy


def _time_jit(f, *args, reps: int = 20) -> float:
    """Median-free best-effort ms/op: compile, then time ``reps`` calls."""
    out = f(*args)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    return (time.perf_counter() - t0) / reps * 1e3


def _serving_site_shapes(cfg, batch: int):
    """(M, K, N) per FFN matmul site at decode-batch geometry."""
    return {"ffn.wg": (batch, cfg.d_model, cfg.d_ff),
            "ffn.wi": (batch, cfg.d_model, cfg.d_ff),
            "ffn.wd": (batch, cfg.d_ff, cfg.d_model)}


def measure_serving(cfg, *, batch: int = 8, reps: int = 30,
                    backend: Optional[str] = None, seed: int = 0,
                    n_steps: int = 4, rounds: int = 4) -> dict:
    """ms per decode step for every (FFN site × policy) plus the engine
    scrub costs, at the given config's geometry.

    FFN site costs are measured on the *real decode window* — the engine's
    jitted ``multi_step``-deep ``lax.scan`` over argmax decode steps with a
    single-site PolicyMap baked into the config — not on an isolated
    matmul: inside the scanned decode graph the policies price differently
    than standalone (in-graph CKPT's re-execution branch costs ~nothing on
    an isolated op but a few percent per step here), and the isolated-op
    deltas drown in timer noise.  All variants are timed in *interleaved
    rounds* (round-robin, per-variant min) so CPU frequency drift over the
    measurement run cancels out of the deltas.  The stored per-site numbers
    are whole-step ms; the predictor uses the delta over the unmapped
    step."""
    from repro.core import abft as abft_mod
    from repro.core.policy_map import PolicyMap, PolicyRule
    from repro.models import api as model_api
    from repro.runtime.dataflow import _decode_window_fn, decode_step_fn
    rng = np.random.default_rng(seed)
    cfg = model_api.with_backend(cfg, backend)
    params = model_api.init_params(cfg, jax.random.key(seed))
    max_len = 96
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch,)),
                         jnp.int32)
    rem = jnp.full((batch,), 64, jnp.int32)
    pos = jnp.full((batch,), 8, jnp.int32)
    act = jnp.ones((batch,), bool)

    def window_for(policy_map):
        mcfg = model_api.with_policy_map(cfg, policy_map)
        win = _decode_window_fn(decode_step_fn(mcfg), n_steps, eos_id=-1,
                                max_len=max_len)
        state = {"cache": model_api.init_cache(mcfg, batch, max_len)}

        def run():
            # the window consumes (donates) its cache: thread it through
            out = win(params, tokens, state["cache"], rem, pos, act)
            state["cache"] = out[0][1]
            return out
        out = run()    # compile + warm
        jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
        return run

    variants: Dict[tuple, Callable] = {("__base__", "none"): window_for(None)}
    for site in _serving_site_shapes(cfg, batch):
        for pol in Policy:
            if pol is Policy.NONE:
                continue
            pm = PolicyMap(rules=(PolicyRule(site, pol),),
                           default=Policy.NONE)
            variants[(site, pol.value)] = window_for(pm)

    best: Dict[tuple, float] = {k: float("inf") for k in variants}
    for _ in range(rounds):
        for key, run in variants.items():
            t0 = time.perf_counter()
            for _ in range(reps):
                out = run()
            jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
            ms = (time.perf_counter() - t0) / reps / n_steps * 1e3
            best[key] = min(best[key], ms)

    base_ms = best[("__base__", "none")]
    sites: Dict[str, dict] = {}
    for site, (m, k, n) in _serving_site_shapes(cfg, batch).items():
        per_policy = {"none": round(base_ms, 5)}
        for pol in Policy:
            if pol is Policy.NONE:
                continue
            per_policy[pol.value] = round(best[(site, pol.value)], 5)
        sites[site] = {"shape_mkn": [m, k, n], "ms": per_policy}

    # engine scrub costs on the real parameter pytree: one storage verify
    # (the weights-site scrub the engine pays per cadence tick) and one
    # storage checksum (the baseline/bless cost, paid per deploy)
    params = model_api.init_params(cfg, jax.random.key(seed))
    checks = jax.jit(abft_mod.storage_checksums)(params)
    verify = jax.jit(abft_mod.verify_storage)
    scrub = {
        "storage_verify_ms": round(
            _time_jit(lambda: verify(params, checks), reps=reps), 5),
        "storage_checksum_ms": round(
            _time_jit(jax.jit(abft_mod.storage_checksums), params,
                      reps=reps), 5),
    }
    return {"arch": cfg.name, "batch": batch, "n_layers": cfg.n_layers,
            "sites": sites, "scrub": scrub}


def measure_shipdet(*, reps: int = 10, backend: Optional[str] = None,
                    seed: int = 0, reduced: bool = True) -> dict:
    """ms per call for every (conv layer × policy) of the ship detector."""
    from repro.core.dependability import dependable_qconv2d
    from repro.models import shipdet
    from repro.core import quant
    specs = shipdet.reduced_specs() if reduced else shipdet.network_specs()
    params = shipdet.init_params(specs, jax.random.key(seed))
    rng = np.random.default_rng(seed)
    layers: Dict[str, dict] = {}
    for s, p in zip(specs, params):
        x_q = jnp.asarray(rng.integers(-127, 128, (1, s.h, s.w, s.cin)),
                          jnp.int8)
        bias_i32 = jnp.round(
            p["qconv"].bias_f / (p["in_scale"] * p["qconv"].w_scale)
        ).astype(jnp.int32)
        rq = quant.requant_scale(p["in_scale"], p["qconv"].w_scale,
                                 p["out_scale"])
        per_policy = {}
        for pol in Policy:
            f = jax.jit(lambda x, w, p_=pol, zp=p["in_zp"], b=bias_i32,
                        r=rq, oz=p["out_zp"], st=(s.stride, s.stride),
                        be=backend:
                        dependable_qconv2d(p_, x, zp, w, b, r, oz,
                                           stride=st, padding="SAME",
                                           backend=be)[0])
            per_policy[pol.value] = round(
                _time_jit(f, x_q, p["qconv"].w_q, reps=reps), 5)
        layers[s.name] = {"macs": s.macs, "ms": per_policy}
    return {"reduced": reduced, "layers": layers}


def measure(*, arch: str = "smollm-135m", batch: int = 8, reps: int = 30,
            backend: Optional[str] = None, seed: int = 0,
            spaces=("serving", "shipdet")) -> "CostModel":
    """The full oracle: measure every space's site table; returns the
    CostModel (call ``.save(path)`` for the JSON artifact)."""
    import dataclasses as _dc
    from repro.configs import registry
    from repro.models.config import reduced as reduced_cfg
    doc: dict = {"meta": {"arch": arch, "batch": batch, "reps": reps,
                          "backend": backend or "jnp", "seed": seed}}
    if "serving" in spaces:
        cfg = _dc.replace(reduced_cfg(registry.get(arch)), quant="w8a8_ffn")
        doc["serving"] = measure_serving(cfg, batch=batch, reps=reps,
                                         backend=backend, seed=seed)
    if "shipdet" in spaces:
        doc["shipdet"] = measure_shipdet(reps=max(reps // 3, 3),
                                         backend=backend, seed=seed)
    return CostModel(doc)


@dataclasses.dataclass
class CostModel:
    """Measured (site × policy) → ms table + analytic genome predictor."""

    doc: dict

    # cadence assumptions mirrored from Engine(policy_map=...) defaults:
    # ABFT storage scrub runs every pump, CKPT amortizes over the snapshot
    # cadence (snapshot_every defaults near this in the serving cases)
    CKPT_SCRUB_CADENCE = 8

    def to_doc(self) -> dict:
        return self.doc

    def save(self, path) -> pathlib.Path:
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.doc, indent=2, sort_keys=True) + "\n")
        return p

    @classmethod
    def load(cls, path) -> "CostModel":
        return cls(json.loads(pathlib.Path(path).read_text()))

    def predict_serving(self, genes: Dict[str, str]) -> float:
        """Estimated dependability cost per decode step, ms.  FFN site
        entries are whole-decode-step measurements (see
        ``measure_serving``); what a gene *costs* is its delta over the
        unmapped step."""
        sv = self.doc["serving"]
        total = 0.0
        for site, entry in sv["sites"].items():
            ms = entry["ms"]
            total += max(ms[genes.get(site, "none")] - ms["none"], 0.0)
        storage = genes.get("weights", "none")
        if storage == "abft":
            total += sv["scrub"]["storage_verify_ms"]
        elif storage == "ckpt":
            total += sv["scrub"]["storage_verify_ms"] / self.CKPT_SCRUB_CADENCE
        # transient-state scrub: the engine derives ONE mode from the
        # kv_cache/decode_state genes (PolicyMap.scrub_mode — the stronger
        # ask wins), so the charge is per-mode, not per-site:
        #   detect (any abft/dmr)   — one state checksum per pump
        #   rollback (any ckpt/tmr) — checksum + snapshot bookkeeping per
        #       pump, measured end-to-end at roughly twice the detect cost
        #       (serving_bench --policy-map; a rollback-mode map gives back
        #       everything the amortized storage scrub saved)
        transient = {genes.get("kv_cache", "none"),
                     genes.get("decode_state", "none")}
        if transient & {"ckpt", "tmr"}:
            total += (sv["scrub"]["storage_verify_ms"]
                      + sv["scrub"]["storage_checksum_ms"])
        elif transient & {"abft", "dmr"}:
            total += sv["scrub"]["storage_verify_ms"]
        return total

    def predict_shipdet(self, genes: Dict[str, str]) -> float:
        """Estimated forward cost, ms (full network, mapped policies)."""
        layers = self.doc["shipdet"]["layers"]
        return sum(entry["ms"][genes.get(name, "none")]
                   for name, entry in layers.items())

    def predict(self, space_name: str, genes: Dict[str, str]) -> float:
        if space_name == "serving":
            return self.predict_serving(genes)
        if space_name == "shipdet":
            return self.predict_shipdet(genes)
        raise KeyError(f"no cost table for space {space_name!r}")
