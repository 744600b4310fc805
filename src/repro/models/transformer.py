"""Unified decoder-only transformer LM.

One parameterized implementation covers 8 of the 10 assigned architectures:
dense GQA (smollm, qwen3 w/ qk-norm, command-r+, llama3-405b), MoE (mixtral
8×7b w/ SWA, kimi-k2 384-expert w/ shared expert + leading dense layer), and
the embedding-input backbones (musicgen, llava-next).

Structure:
  * params are plain pytrees; layers are stacked on a leading axis and the
    forward pass is a `lax.scan` over them — 126-layer llama405b lowers to the
    same compact HLO as 2-layer smollm (essential for 512-device dry-run
    compile times).
  * attention is the chunked online-softmax from models/common.py (never
    materializes S×S).
  * the routed-expert FFN runs inside `shard_map` (explicit EP over the model
    axis + FSDP all-gather of expert weights over the data axes), because
    sort-and-scatter token routing is something GSPMD cannot be trusted to
    partition well — see DESIGN.md §6.  Everything else is GSPMD (pjit +
    sharding constraints).
  * quantized serving: every linear can execute as W8A8 int8 (the paper's
    technique) via `quant_mode="int8"` — weights are pre-quantized once
    (`quantize_params`) and matmuls run int8×int8→int32 on the MXU with a
    fused dequant epilogue.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.models.config import ArchConfig
from repro.core import quant
from repro.core.dependability import DependabilityStats, collect_stats


class ShardCtx(NamedTuple):
    """Mesh context threaded through model code.

    dp: tuple of data-parallel mesh axis names (("data",) or ("pod", "data")).
    model: the tensor/expert-parallel axis name.
    mesh: the jax Mesh (required for the shard_map MoE block).
    batch: axes the *activation batch* shards over. Defaults to ``dp``;
      set to ``()`` when global_batch isn't divisible by the dp extent
      (e.g. long_500k decode with batch=1) — weights stay FSDP over ``dp``
      while activations replicate.
    """
    mesh: Any
    dp: Tuple[str, ...] = ("data",)
    model: str = "model"
    batch: Any = None                    # None → same as dp

    @property
    def batch_axes(self):
        """Activation-batch mesh axes; None (replicated) if empty."""
        b = self.dp if self.batch is None else self.batch
        return b or None

    @property
    def dp_size(self) -> int:
        return int(__import__("numpy").prod([self.mesh.shape[a] for a in self.dp]))

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape[self.model])


def _pdt(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def _cdt(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def _w(cfg: ArchConfig, w):
    """Cast a weight to the compute dtype at point of use."""
    return w.astype(_cdt(cfg))


# ------------------------- W8A8 (the paper's technique) --------------------
#
# cfg.quant == "w8a8_ffn" stores every FFN / expert weight as int8 with a
# per-output-channel scale and runs the matmul as int8×int8→int32 with a
# fused float rescale (Jacob et al., the paper's conv+requant scheme applied
# to the transformer's matmul-shaped hot spot).  On the TPU MXU the int8
# path doubles peak FLOPs and quarters weight HBM traffic vs f32.


def quantize_ffn_weight(w: jax.Array):
    """Per-channel symmetric int8 over the contraction dim (axis -2).

    (..., K, N) → int8 (..., K, N), f32 scale (..., N).  Works on stacked
    (L, ..., K, N) weights — scales stay per-(layer, channel).
    """
    a = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
    scale = jnp.maximum(a, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[..., None, :]),
                   -127, 127).astype(jnp.int8)
    return w_q, scale


_FFN_WEIGHTS = ("wi", "wg", "wd", "we_g", "we_i", "we_o", "ws_g", "ws_i",
                "ws_o")


def quantize_ffn_params(cfg: ArchConfig, params):
    """Replace FFN weight leaves with {name}_q int8 + {name}_s f32 scales."""
    def conv_block(bp):
        if bp is None:
            return None
        out = dict(bp)
        for name in _FFN_WEIGHTS:
            if name in out:
                w_q, w_s = quantize_ffn_weight(out.pop(name))
                out[name + "_q"] = w_q
                out[name + "_s"] = w_s
        return out

    p = dict(params)
    for blk in ("dense_blocks", "moe_blocks"):
        if p.get(blk) is not None:
            p[blk] = conv_block(p[blk])
    return p


def _quantize_act(x):
    """Dynamic symmetric per-row int8 activation quant (serving-style)."""
    x_s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    x_s = jnp.maximum(x_s, 1e-8) / 127.0
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / x_s),
                   -127, 127).astype(jnp.int8)
    return x_q, x_s


def _qdot(cfg: ArchConfig, x, bp, name):
    """(x @ W[name], stats), W8A8 when quantized params are present.

    The int8 accumulator comes from the execution-backend registry
    (``cfg.backend``): jnp dot_general by default, the Pallas qmatmul
    kernel when the config asks for the co-processor path.  Bit-identical
    either way (integer accumulation, exact mod 2^32).

    With ``cfg.policy_map`` set, the site ``ffn.<name>`` resolves to a
    dependability policy (and optionally a backend) and the accumulator
    runs through ``dependable_matmul_acc`` — selective hardening of the
    FFN hot path.  Its integer accumulators equal the unmapped dispatch's
    for every policy (exact integer checks never fire).  On the CPU the
    outputs are bit-identical too; on the TPU the map's extra integer ops
    change how XLA fuses the bf16 ops around them, and XLA's excess bf16
    precision inside a fusion can then move the last bits.  The scan over
    layers means the assignment is per-matmul-name, uniform across the
    layer stack (see core/policy_map.py).

    ``stats`` is the DependabilityStats of the check the site ran (zeros
    where it ran none)."""
    from repro.core import dependability as dep
    stats = DependabilityStats.zero()
    if name + "_q" in bp:
        from repro.kernels import dispatch
        x_q, x_s = _quantize_act(x)
        w_q = bp[name + "_q"]
        lead = x_q.shape[:-1]
        # Row-major, as a Pallas kernel takes it.  Left free, XLA lays the
        # operand of its own int8 dot out differently and propagates that
        # layout into the neighbouring float matmuls, whose accumulation
        # order then changes: on the TPU the jnp and pallas backends would
        # release different tokens from identical integer accumulators.
        x2 = with_layout_constraint(x_q.reshape(-1, x_q.shape[-1]),
                                    Layout(major_to_minor=(0, 1)))
        if cfg.policy_map is not None:
            pol, pm_backend = cfg.policy_map.resolve("ffn." + name)
            be = pm_backend or cfg.backend
            if pol is dep.Policy.NONE:
                acc = dispatch.matmul_acc(x2, w_q, backend=be)
            else:
                acc, stats = dep.dependable_matmul_acc(pol, x2, w_q,
                                                       backend=be)
        else:
            acc = dispatch.matmul_acc(x2, w_q, backend=cfg.backend)
        acc = acc.reshape(*lead, w_q.shape[-1])
        y = acc.astype(jnp.float32) * x_s * bp[name + "_s"]
        return y.astype(x.dtype), stats
    return x @ _w(cfg, bp[name]), stats


def _qeinsum(cfg: ArchConfig, spec, x, bp, name):
    """Expert einsum (ecd,edf->ecf / ecf,efd->ecd), W8A8 when quantized."""
    if name + "_q" in bp:
        x_q, x_s = _quantize_act(x)              # (E, C, K), (E, C, 1)
        acc = jnp.einsum(spec, x_q, bp[name + "_q"],
                         preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * x_s * bp[name + "_s"][..., None, :]
        return y.astype(x.dtype)
    return jnp.einsum(spec, x, _w(cfg, bp[name]))


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def init_params(cfg: ArchConfig, key: jax.Array) -> Dict[str, Any]:
    """Build the parameter pytree. Layers stacked on axis 0 for scan."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV, ff, V = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size
    pdt = _pdt(cfg)
    keys = iter(jax.random.split(key, 64))

    def dense(shape, k=None):
        return common.dense_init(next(keys) if k is None else k, shape, dtype=pdt)

    def stack(shape, n):
        return common.dense_init(next(keys), (n,) + shape, in_axis=1, dtype=pdt)

    n_moe = 0
    n_dense = cfg.n_layers
    if cfg.moe is not None:
        n_moe = cfg.n_layers - cfg.moe.n_dense_layers
        n_dense = cfg.moe.n_dense_layers

    def block_params(n, moe: bool):
        if n == 0:
            return None
        p = {
            "ln1": jnp.zeros((n, d), pdt),
            "ln2": jnp.zeros((n, d), pdt),
            "wq": stack((d, H * hd), n),
            "wk": stack((d, KV * hd), n),
            "wv": stack((d, KV * hd), n),
            "wo": stack((H * hd, d), n),
        }
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((n, hd), pdt)
            p["k_norm"] = jnp.zeros((n, hd), pdt)
        if cfg.use_bias:
            p["bq"] = jnp.zeros((n, H * hd), pdt)
            p["bk"] = jnp.zeros((n, KV * hd), pdt)
            p["bv"] = jnp.zeros((n, KV * hd), pdt)
        if not moe:
            p.update({
                "wi": stack((d, ff), n),
                "wg": stack((d, ff), n),
                "wd": stack((ff, d), n),
            })
        else:
            m = cfg.moe
            p.update({
                "router": stack((d, m.n_experts), n).astype(jnp.float32),
                "we_g": stack((m.n_experts, d, m.d_expert), n),
                "we_i": stack((m.n_experts, d, m.d_expert), n),
                "we_o": stack((m.n_experts, m.d_expert, d), n),
            })
            if m.n_shared_experts:
                ds = m.d_expert * m.n_shared_experts
                p.update({
                    "ws_g": stack((d, ds), n),
                    "ws_i": stack((d, ds), n),
                    "ws_o": stack((ds, d), n),
                })
        return p

    params = {
        "embed": common.embed_init(next(keys), (V, d), dtype=pdt),
        "final_norm": jnp.zeros((d,), pdt),
        "dense_blocks": block_params(n_dense, moe=False),
        "moe_blocks": block_params(n_moe, moe=True),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense((d, V))
    params = {k: v for k, v in params.items() if v is not None}
    if cfg.quant == "w8a8_ffn":
        params = quantize_ffn_params(cfg, params)
    return params


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------


def _attention(cfg: ArchConfig, bp, x, positions, ctx: Optional[ShardCtx]):
    """Pre-norm GQA attention (full-sequence / training / prefill)."""
    with jax.named_scope("attention"):
        return _attention_block(cfg, bp, x, positions, ctx)


def _attention_block(cfg: ArchConfig, bp, x, positions,
                     ctx: Optional[ShardCtx]):
    B, S, d = x.shape
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    h = common.rms_norm(x, bp["ln1"], cfg.norm_eps)
    q = h @ _w(cfg, bp["wq"])
    k = h @ _w(cfg, bp["wk"])
    v = h @ _w(cfg, bp["wv"])
    if cfg.use_bias:
        q, k, v = q + _w(cfg, bp["bq"]), k + _w(cfg, bp["bk"]), v + _w(cfg, bp["bv"])
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = common.rms_norm(q, bp["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, bp["k_norm"], cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    if ctx is not None:
        # TP over heads only when they divide the model axis; GQA KV heads
        # (usually 8 < model=16) stay replicated over model — the MaxText
        # recipe for TP > n_kv_heads.
        msize = ctx.model_size
        bax = ctx.batch_axes
        # no head sharding when the model axis is folded into dp (layout=dp)
        tp_ok = ctx.model not in ctx.dp
        qspec = P(bax, None, ctx.model, None) if H % msize == 0 and tp_ok \
            else P(bax, None, None, None)
        kvspec = P(bax, None, ctx.model, None) if KV % msize == 0 and tp_ok \
            else P(bax, None, None, None)
        q = jax.lax.with_sharding_constraint(q, jax.sharding.NamedSharding(ctx.mesh, qspec))
        k = jax.lax.with_sharding_constraint(k, jax.sharding.NamedSharding(ctx.mesh, kvspec))
        v = jax.lax.with_sharding_constraint(v, jax.sharding.NamedSharding(ctx.mesh, kvspec))
    o = _attention_core(cfg, q, k, v, positions, ctx)
    return x + o.reshape(B, S, H * hd) @ _w(cfg, bp["wo"])


def _attention_core(cfg: ArchConfig, q, k, v, positions, ctx):
    """Dispatch chunked-jnp vs Pallas flash (fwd+bwd kernels).

    Flash under a mesh runs inside shard_map — attention is batch/head
    parallel, so the body needs no collectives; heads shard over the model
    axis when they divide it (same rule as the constraint above), otherwise
    the kernel runs replicated over model (layout="dp" folds it into batch).
    """
    if cfg.attn_impl != "flash":
        return common.chunked_causal_attention(q, k, v, window=cfg.swa_window,
                                               positions=positions)
    from repro.kernels.flashattn.ops import flash_attn_model
    if ctx is None:
        return flash_attn_model(q, k, v, window=cfg.swa_window)

    from jax import shard_map
    H, KV = cfg.n_heads, cfg.n_kv_heads
    msize = ctx.model_size
    tp_ok = (ctx.model not in ctx.dp and H % msize == 0 and KV % msize == 0)
    hax = ctx.model if tp_ok else None
    bax = ctx.batch_axes
    qs = P(bax, None, hax, None)
    fn = shard_map(
        lambda q, k, v: flash_attn_model(q, k, v, window=cfg.swa_window),
        mesh=ctx.mesh, in_specs=(qs, qs, qs), out_specs=qs,
        check_vma=False,
    )
    return fn(q, k, v)


def _dense_ffn(cfg: ArchConfig, bp, x, stats):
    """The FFN block; ``stats`` plus the checks of its three matmuls."""
    with jax.named_scope("ffn"):
        h = common.rms_norm(x, bp["ln2"], cfg.norm_eps)
        g, sg = _qdot(cfg, h, bp, "wg")
        i, si = _qdot(cfg, h, bp, "wi")
        y, sd = _qdot(cfg, jax.nn.silu(g) * i, bp, "wd")
        for s in (sg, si, sd):
            stats = DependabilityStats.merge(stats, s)
        return x + y, stats


# --------------------------- MoE (shard_map EP) ----------------------------


def _local_route(xf, router_w, m, e_lo, E_loc, capacity):
    """Sort-based capacity routing for the E_loc experts starting at e_lo.

    ``E_loc`` is static (python int); ``e_lo`` may be traced (axis_index).

    xf: (n, d) local tokens. Returns (gather_idx (E_loc*C,), gates (E_loc*C,),
    keep mask (E_loc*C,)) mapping buffer rows → token rows.
    """
    n = xf.shape[0]
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (n, E)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)                 # (n, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)       # renormalize

    flat_e = top_i.reshape(-1)                                   # (n*k,)
    flat_g = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(n), m.top_k)                  # token ids

    local_e = flat_e - e_lo
    is_local = (local_e >= 0) & (local_e < E_loc)
    sort_key = jnp.where(is_local, local_e, E_loc)               # invalid last
    order = jnp.argsort(sort_key)
    se = sort_key[order]                                          # sorted expert ids
    st = flat_t[order]
    sg = flat_g[order]
    # position within each expert's contiguous run
    starts = jnp.searchsorted(se, jnp.arange(E_loc + 1))
    pos = jnp.arange(se.shape[0]) - starts[jnp.clip(se, 0, E_loc)]
    keep = (se < E_loc) & (pos < capacity)
    slot = jnp.where(keep, se * capacity + pos, E_loc * capacity)  # overflow slot

    # buffer row r ← token index; build inverse map via scatter
    gather_idx = jnp.zeros((E_loc * capacity + 1,), jnp.int32).at[slot].set(
        st.astype(jnp.int32), mode="drop")
    gates = jnp.zeros((E_loc * capacity + 1,), jnp.float32).at[slot].set(
        sg, mode="drop")
    filled = jnp.zeros((E_loc * capacity + 1,), jnp.bool_).at[slot].set(
        keep, mode="drop")
    # aux-loss ingredients (load balance over the *global* expert set)
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(jax.nn.one_hot(top_i, probs.shape[-1], dtype=jnp.float32),
                  axis=(0, 1))
    aux = probs.shape[-1] * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gather_idx[:-1], gates[:-1], filled[:-1], aux, z_loss


def _moe_ffn_local(cfg: ArchConfig, bp, x, ctx: ShardCtx, mode: str = "ep"):
    """Per-device MoE FFN body (runs under shard_map).

    x: (B_loc, S, d) — batch sharded over ctx.batch, replicated over model.

    mode="ep"  (n_experts % model_size == 0): experts sharded over the model
      axis (E_loc = E/msize each), d_expert FSDP-sharded over dp and gathered
      before compute.  The classic expert-parallel layout.
    mode="etp" (n_experts < model_size, e.g. mixtral 8e on a 16-way axis):
      every device holds ALL experts but only a 1/msize slice of d_expert
      (tensor parallelism *within* each expert); d_model is FSDP over dp and
      gathered.  The closing psum over the model axis then sums d_expert
      partial products instead of disjoint expert sets — same math, and the
      per-device matmul volume is identical (E·d·de/msize).
    """
    m = cfg.moe
    B, S, d = x.shape
    n = B * S
    xf = x.reshape(n, d)
    h = common.rms_norm(xf, bp["ln2"], cfg.norm_eps)

    if mode == "ep":
        E_loc = m.n_experts // ctx.model_size
        midx = jax.lax.axis_index(ctx.model)
        e_lo = midx * E_loc
    else:
        E_loc = m.n_experts
        e_lo = 0

    capacity = max(int(m.top_k * n * m.capacity_factor / m.n_experts), 4)

    gather_idx, gates, filled, aux, z_loss = _local_route(
        h, bp["router"], m, e_lo, E_loc, capacity)

    # FSDP: gather the dp-sharded weight dim (de for ep, d for etp)
    def gather_w(w, axis):
        for a in reversed(ctx.dp):
            w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
        return w

    quant = "we_g_q" in bp
    suffix = "_q" if quant else ""
    if mode == "ep":
        we_g = gather_w(bp["we_g" + suffix], 2)      # (E_loc, d, de)
        we_i = gather_w(bp["we_i" + suffix], 2)
        we_o = gather_w(bp["we_o" + suffix], 1)      # (E_loc, de, d)
        if quant:   # per-out-channel scales follow their channel dim
            we_g_s = gather_w(bp["we_g_s"], 1)       # (E_loc, de)
            we_i_s = gather_w(bp["we_i_s"], 1)
            we_o_s = bp["we_o_s"]                    # (E_loc, d) unsharded
    else:
        we_g = gather_w(bp["we_g" + suffix], 1)      # (E, d, de_loc)
        we_i = gather_w(bp["we_i" + suffix], 1)
        we_o = gather_w(bp["we_o" + suffix], 2)      # (E, de_loc, d)
        if quant:
            we_g_s = bp["we_g_s"]                    # (E, de_loc)
            we_i_s = bp["we_i_s"]
            we_o_s = gather_w(bp["we_o_s"], 1)       # (E, d)

    buf = jnp.where(filled[:, None], h[gather_idx], 0)            # (E_loc*C, d)
    buf = buf.reshape(E_loc, capacity, d)

    def expert_mm(spec, x, w, w_s):
        if not quant:
            return jnp.einsum(spec, x, _w(cfg, w))
        x_q, x_s = _quantize_act(x)
        acc = jnp.einsum(spec, x_q, w, preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * x_s
                * w_s[..., None, :]).astype(x.dtype)

    act = jax.nn.silu(expert_mm("ecd,edf->ecf", buf, we_g,
                                we_g_s if quant else None)) * \
        expert_mm("ecd,edf->ecf", buf, we_i, we_i_s if quant else None)
    out = expert_mm("ecf,efd->ecd", act, we_o,
                    we_o_s if quant else None)                     # (E_loc, C, d)
    out = out.reshape(E_loc * capacity, d) * gates[:, None]

    combined = jnp.zeros((n, d), out.dtype).at[gather_idx].add(
        jnp.where(filled[:, None], out, 0))
    combined = jax.lax.psum(combined, ctx.model)

    # shared experts: plain dense FFN, tensor-parallel over model axis
    if m.n_shared_experts:
        # the shared experts' check stats stay inside the expert layer
        sact = (jax.nn.silu(_qdot(cfg, h, bp, "ws_g")[0])
                * _qdot(cfg, h, bp, "ws_i")[0])
        sout = _qdot(cfg, sact, bp, "ws_o")[0]
        combined = combined + jax.lax.psum(sout, ctx.model)

    aux = jax.lax.pmean(aux, ctx.dp + (ctx.model,))
    z_loss = jax.lax.pmean(z_loss, ctx.dp + (ctx.model,))
    return (x + combined.reshape(B, S, d).astype(x.dtype)), aux, z_loss


def moe_mode(cfg: ArchConfig, model_size: int) -> str:
    """'ep' when experts divide the model axis, else expert-TP fallback."""
    return "ep" if cfg.moe.n_experts % model_size == 0 else "etp"


def _moe_ffn(cfg: ArchConfig, bp, x, ctx: ShardCtx):
    """shard_map wrapper: explicit EP (or expert-TP) + FSDP for the experts."""
    from jax import shard_map
    m = cfg.moe
    dp = ctx.dp
    mode = moe_mode(cfg, ctx.model_size)

    bax = ctx.batch_axes
    x_spec = P(bax, None, None)
    specs = {"ln2": P(None), "router": P(None, None)}
    quant = "we_g_q" in bp
    sfx = "_q" if quant else ""
    if mode == "ep":
        # (E, d, de): E → model, de → dp (FSDP)
        specs["we_g" + sfx] = P(ctx.model, None, dp)
        specs["we_i" + sfx] = P(ctx.model, None, dp)
        specs["we_o" + sfx] = P(ctx.model, dp, None)
        if quant:   # scales: (E, de) / (E, d)
            specs["we_g_s"] = P(ctx.model, dp)
            specs["we_i_s"] = P(ctx.model, dp)
            specs["we_o_s"] = P(ctx.model, None)
    else:
        # (E, d, de): de → model (TP within expert), d → dp (FSDP)
        specs["we_g" + sfx] = P(None, dp, ctx.model)
        specs["we_i" + sfx] = P(None, dp, ctx.model)
        specs["we_o" + sfx] = P(None, ctx.model, dp)
        if quant:
            specs["we_g_s"] = P(None, ctx.model)
            specs["we_i_s"] = P(None, ctx.model)
            specs["we_o_s"] = P(None, dp)
    if m.n_shared_experts:
        specs["ws_g" + sfx] = P(None, ctx.model)
        specs["ws_i" + sfx] = P(None, ctx.model)
        specs["ws_o" + sfx] = P(ctx.model, None)
        if quant:   # scales: (ds,) / (d,)
            specs["ws_g_s"] = P(ctx.model)
            specs["ws_i_s"] = P(ctx.model)
            specs["ws_o_s"] = P(None)

    bp_in = {k: bp[k] for k in specs}

    fn = shard_map(
        functools.partial(_moe_ffn_local, cfg, ctx=ctx, mode=mode),
        mesh=ctx.mesh,
        in_specs=(dict(specs), x_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )
    return fn(bp_in, x)


def _moe_ffn_single(cfg: ArchConfig, bp, x):
    """Meshless fallback (unit tests / reference): all experts local."""
    m = cfg.moe
    B, S, d = x.shape
    n = B * S
    xf = x.reshape(n, d)
    h = common.rms_norm(xf, bp["ln2"], cfg.norm_eps)
    capacity = max(int(m.top_k * n * m.capacity_factor / m.n_experts), 4)
    gather_idx, gates, filled, aux, z_loss = _local_route(
        h, bp["router"], m, 0, m.n_experts, capacity)
    buf = jnp.where(filled[:, None], h[gather_idx], 0).reshape(m.n_experts, capacity, d)
    act = jax.nn.silu(_qeinsum(cfg, "ecd,edf->ecf", buf, bp, "we_g")) * \
        _qeinsum(cfg, "ecd,edf->ecf", buf, bp, "we_i")
    out = _qeinsum(cfg, "ecf,efd->ecd", act, bp, "we_o").reshape(-1, d) * gates[:, None]
    combined = jnp.zeros((n, d), out.dtype).at[gather_idx].add(
        jnp.where(filled[:, None], out, 0))
    if m.n_shared_experts:
        sact = (jax.nn.silu(_qdot(cfg, h, bp, "ws_g")[0])
                * _qdot(cfg, h, bp, "ws_i")[0])
        combined = combined + _qdot(cfg, sact, bp, "ws_o")[0]
    return x + combined.reshape(B, S, d).astype(x.dtype), aux, z_loss


# ---------------------------------------------------------------------------
# Full forward (training / prefill)
# ---------------------------------------------------------------------------


class ForwardOut(NamedTuple):
    logits: jax.Array
    aux_loss: jax.Array
    z_loss: jax.Array


def _remat_policy(cfg: ArchConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "full":
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims


def forward(cfg: ArchConfig, params, tokens: jax.Array,
            ctx: Optional[ShardCtx] = None,
            embeds: Optional[jax.Array] = None) -> ForwardOut:
    """tokens: (B, S) int32 — or embeds (B, S, d) for audio/vlm stub inputs.

    The dense FFN checks' stats go to an open ``collect_stats`` sink."""
    if embeds is not None:
        x = embeds.astype(_cdt(cfg))
        B, S, _ = embeds.shape
    else:
        B, S = tokens.shape
        x = params["embed"][tokens]
    x = x.astype(_cdt(cfg))
    positions = jnp.arange(S)[None, :]
    aux = jnp.zeros((), jnp.float32)
    zl = jnp.zeros((), jnp.float32)
    stats = DependabilityStats.zero()

    policy = _remat_policy(cfg)

    def seq_sp(x):
        """Sequence parallelism: pin inter-block activations to a seq-sharded
        layout.  The row-parallel psum after wo/wd then lowers as
        reduce-scatter (+ all-gather before the next block's column-parallel
        matmuls) — half the bytes of the pure-TP all-reduce, and the
        norms/elementwise between blocks run on S/msize rows per device."""
        if ctx is None or not cfg.seq_shard:
            return x
        if ctx.model in ctx.dp or x.shape[1] % ctx.model_size != 0:
            return x
        spec = P(ctx.batch_axes, ctx.model, None)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(ctx.mesh, spec))

    def dense_body(carry, bp):
        x, stats = carry
        x = _attention(cfg, bp, x, positions, ctx)
        x, stats = _dense_ffn(cfg, bp, x, stats)
        return (seq_sp(x), stats), None

    def moe_body(carry, bp):
        x, aux, zl = carry
        x = _attention(cfg, bp, x, positions, ctx)
        if ctx is not None:
            x, a, z = _moe_ffn(cfg, bp, x, ctx)
        else:
            x, a, z = _moe_ffn_single(cfg, bp, x)
        return (seq_sp(x), aux + a, zl + z), None

    if policy is not None:
        dense_body = jax.checkpoint(dense_body, policy=policy, prevent_cse=False)
        moe_body = jax.checkpoint(moe_body, policy=policy, prevent_cse=False)

    if params.get("dense_blocks") is not None:
        (x, stats), _ = jax.lax.scan(dense_body, (x, stats),
                                     params["dense_blocks"])
    if params.get("moe_blocks") is not None:
        (x, aux, zl), _ = jax.lax.scan(moe_body, (x, aux, zl), params["moe_blocks"])
    collect_stats.deposit(stats)

    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    n_moe = cfg.n_layers - (cfg.moe.n_dense_layers if cfg.moe else 0)
    denom = max(n_moe, 1)
    return ForwardOut(logits, aux / denom, zl / denom)


def loss_fn(cfg: ArchConfig, params, batch, ctx: Optional[ShardCtx] = None):
    out = forward(cfg, params, batch["tokens"], ctx,
                  embeds=batch.get("embeds"))
    loss = common.cross_entropy_loss(out.logits, batch["labels"],
                                     batch.get("mask"))
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss * out.aux_loss + cfg.moe.router_z_loss * out.z_loss
    return loss, {"ce": loss, "aux": out.aux_loss, "z": out.z_loss}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with (ring-buffer) KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array          # (L, B, T, KV, hd) — compute dtype, or int8 when
    v: jax.Array          #   cfg.quant_kv (k_s/v_s hold per-row scales)
    length: jax.Array     # (B,) int32 — per-row tokens currently in cache
    k_s: Any = None       # (L, B, T, KV) f32 — int8-KV scales (else None)
    v_s: Any = None


def _quantize_kv_rows(x: jax.Array):
    """Per-(…, KV)-row symmetric int8 over hd: (..., KV, hd) → q, scale."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.maximum(s, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def cache_len(cfg: ArchConfig, max_len: int) -> int:
    """SWA archs only need a window-sized ring buffer."""
    if cfg.swa_window is not None:
        return min(cfg.swa_window, max_len)
    return max_len


def init_cache(cfg: ArchConfig, B: int, max_len: int, dtype=None) -> KVCache:
    dtype = dtype or _cdt(cfg)
    T = cache_len(cfg, max_len)
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (cfg.n_layers, B, T, KV, hd)
    # per-row lengths: the serving engine admits requests with ragged prompt
    # lengths into one decode batch (continuous batching)
    if cfg.quant_kv:
        return KVCache(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                       jnp.zeros((B,), jnp.int32),
                       jnp.zeros(shape[:-1], jnp.float32),
                       jnp.zeros(shape[:-1], jnp.float32))
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((B,), jnp.int32))


def _block_decode(cfg: ArchConfig, bp, x, pages, pos, T):
    """One block's single-token attention. x: (B, 1, d), pos: (B,) per-row
    positions (ragged continuous batching), pages: this layer's cache pages
    (k, v) — plus the (B, T, KV) scale pages (k_s, v_s) when cfg.quant_kv —
    which are read, not written.  Returns new x and the token's cache rows,
    (B, KV, hd) each (+ (B, KV) scales), for the caller to write at
    ``pos % T``."""
    with jax.named_scope("attention"):
        return _block_decode_attention(cfg, bp, x, pages, pos, T)


def _block_decode_attention(cfg: ArchConfig, bp, x, pages, pos, T):
    B = x.shape[0]
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    h = common.rms_norm(x, bp["ln1"], cfg.norm_eps)
    q = (h @ _w(cfg, bp["wq"])).reshape(B, 1, H, hd)
    k = (h @ _w(cfg, bp["wk"])).reshape(B, 1, KV, hd)
    v = (h @ _w(cfg, bp["wv"])).reshape(B, 1, KV, hd)
    if cfg.use_bias:
        q = q + _w(cfg, bp["bq"]).reshape(1, 1, H, hd)
        k = k + _w(cfg, bp["bk"]).reshape(1, 1, KV, hd)
        v = v + _w(cfg, bp["bv"]).reshape(1, 1, KV, hd)
    if cfg.qk_norm:
        q = common.rms_norm(q, bp["q_norm"], cfg.norm_eps)
        k = common.rms_norm(k, bp["k_norm"], cfg.norm_eps)
    pos_b = pos[:, None]                             # (B, 1) per-row positions
    q = common.apply_rope(q, pos_b, cfg.rope_theta)
    k = common.apply_rope(k, pos_b, cfg.rope_theta)

    with jax.named_scope("kv_update"):
        if cfg.quant_kv:
            k_q, k_sc = _quantize_kv_rows(k[:, 0])   # (B, KV, hd), (B, KV)
            v_q, v_sc = _quantize_kv_rows(v[:, 0])
            rows = (k_q, v_q, k_sc, v_sc)
        else:
            rows = (k[:, 0].astype(pages[0].dtype),
                    v[:, 0].astype(pages[1].dtype))
    valid = jnp.minimum(pos + 1, T)                  # (B,)
    o = common.decode_attention(q, *pages[:2], valid, *pages[2:],
                                new=(pos % T, *rows))
    x = x + (o.reshape(B, 1, H * hd) @ _w(cfg, bp["wo"])).astype(x.dtype)
    return x, rows


def _write_rows(arrays, rows, slot):
    """Write row b of every layer, ``rows[i][:, b]``, at ``[:, b, slot[b]]``
    of ``arrays[i]`` (L, B, T, ...): one dynamic-update-slice per batch row.
    Unrolled, each write takes the cache in the layout its readers want; a
    scatter, or the same writes in a loop, picks a layout of its own, and
    XLA then converts the whole cache to it and back every step."""
    arrays = tuple(arrays)
    for b in range(slot.shape[0]):
        arrays = tuple(
            jax.lax.dynamic_update_slice(
                a, r[:, b, None, None], (0, b, slot[b]) + (0,) * (a.ndim - 3))
            for a, r in zip(arrays, rows))
    return arrays


def decode_step(cfg: ArchConfig, params, token: jax.Array, cache: KVCache,
                ctx: Optional[ShardCtx] = None,
                embed: Optional[jax.Array] = None):
    """token: (B,) int32 (or embed (B, d)). Returns (logits (B, V), cache).

    Attend, then write: the layer scan reads each layer's cache page (a
    dynamic index into the stacked cache, which it never re-stacks),
    attends over it together with the token's own K/V
    (``common.decode_attention``'s ``new``), and emits only the token's
    rows.  After the scan ``_write_rows`` writes them at ``[:, b, pos %
    T]``, in place when the caller donates the cache (the engine's decode
    programs do).

    The dense FFN checks' stats, summed over layers, go to an open
    ``collect_stats`` sink.
    """
    if embed is not None:
        x = embed[:, None, :].astype(_cdt(cfg))
        B = embed.shape[0]
    else:
        B = token.shape[0]
        x = params["embed"][token][:, None, :].astype(_cdt(cfg))
    pos = cache.length
    T = cache.k.shape[2]
    arrays = ((cache.k, cache.v, cache.k_s, cache.v_s) if cfg.quant_kv
              else (cache.k, cache.v))

    def make_body(moe: bool):
        def body(carry, layer):
            x, stats = carry
            bp, li = layer
            pages = tuple(jax.lax.dynamic_index_in_dim(a, li, 0,
                                                       keepdims=False)
                          for a in arrays)
            x, rows = _block_decode(cfg, bp, x, pages, pos, T)
            if moe:
                if ctx is not None:
                    x, _, _ = _moe_ffn(cfg, bp, x, ctx)
                else:
                    x, _, _ = _moe_ffn_single(cfg, bp, x)
            else:
                x, stats = _dense_ffn(cfg, bp, x, stats)
            return (x, stats), rows
        return body

    parts = []
    carry = (x, DependabilityStats.zero())
    lo = 0
    for moe, blocks in ((False, params.get("dense_blocks")),
                        (True, params.get("moe_blocks"))):
        if blocks is None:
            continue
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        carry, rows = jax.lax.scan(make_body(moe), carry,
                                   (blocks, jnp.arange(lo, lo + n)))
        parts.append(rows)
        lo += n
    x, stats = carry
    collect_stats.deposit(stats)

    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head.astype(x.dtype)).reshape(B, -1)

    # rows: (L, B, KV, hd) / (L, B, KV) — the token's row of every layer
    rows = [p[0] if len(parts) == 1 else jnp.concatenate(p)
            for p in zip(*parts)]
    slot = pos % T
    with jax.named_scope("kv_update"):
        arrays = _write_rows(arrays, rows, slot)
    return logits, KVCache(arrays[0], arrays[1], cache.length + 1,
                           *arrays[2:])


def prefill(cfg: ArchConfig, params, tokens: jax.Array, max_len: int,
            ctx: Optional[ShardCtx] = None,
            embeds: Optional[jax.Array] = None):
    """Full-sequence forward that also fills the KV cache (teacher-forced).

    Implemented as forward() for logits + a lightweight second pass that
    recomputes per-layer K/V into the cache (scan, no attention) — keeps one
    code path for attention math.  Returns (logits, cache).

    Both passes run the FFN, so an open ``collect_stats`` sink receives two
    FFN checks' stats per matmul and layer: the forward's and the second
    pass's.
    """
    out = forward(cfg, params, tokens, ctx, embeds=embeds)
    B, S = (embeds.shape[:2] if embeds is not None else tokens.shape)
    cache = init_cache(cfg, B, max_len)
    T = cache.k.shape[2]
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    positions = jnp.arange(S)[None, :]

    if embeds is not None:
        x = embeds.astype(_cdt(cfg))
    else:
        x = params["embed"][tokens].astype(_cdt(cfg))

    def kv_body(carry, bp):
        x, stats = carry
        with jax.named_scope("kv_update"):
            h = common.rms_norm(x, bp["ln1"], cfg.norm_eps)
            k = (h @ _w(cfg, bp["wk"])).reshape(B, S, KV, hd)
            v = (h @ _w(cfg, bp["wv"])).reshape(B, S, KV, hd)
            if cfg.use_bias:
                k = k + _w(cfg, bp["bk"]).reshape(1, 1, KV, hd)
                v = v + _w(cfg, bp["bv"]).reshape(1, 1, KV, hd)
            if cfg.qk_norm:
                k = common.rms_norm(k, bp["k_norm"], cfg.norm_eps)
            k = common.apply_rope(k, positions, cfg.rope_theta)
        # recompute the block output to feed the next layer
        x = _attention(cfg, bp, x, positions, ctx)
        if "wd" in bp or "wd_q" in bp:   # dense block (float or W8A8)
            x, stats = _dense_ffn(cfg, bp, x, stats)
        elif ctx is not None:
            x, _, _ = _moe_ffn(cfg, bp, x, ctx)
        else:
            x, _, _ = _moe_ffn_single(cfg, bp, x)
        # keep last T positions (ring layout: slot = pos % T)
        sl = jnp.maximum(S - T, 0)
        kk = jax.lax.dynamic_slice_in_dim(k, sl, min(T, S), axis=1)
        vv = jax.lax.dynamic_slice_in_dim(v, sl, min(T, S), axis=1)
        return (x, stats), (kk, vv)

    ks, vs = [], []
    carry = (x, DependabilityStats.zero())
    if params.get("dense_blocks") is not None:
        carry, (kk, vv) = jax.lax.scan(kv_body, carry, params["dense_blocks"])
        ks.append(kk)
        vs.append(vv)
    if params.get("moe_blocks") is not None:
        carry, (kk, vv) = jax.lax.scan(kv_body, carry, params["moe_blocks"])
        ks.append(kk)
        vs.append(vv)
    collect_stats.deposit(carry[1])
    k_all = jnp.concatenate(ks)           # (L, B, min(T,S), KV, hd)
    v_all = jnp.concatenate(vs)

    Tc = k_all.shape[2]
    ks_all = vs_all = None
    if cfg.quant_kv:
        k_all, ks_all = _quantize_kv_rows(k_all)
        v_all, vs_all = _quantize_kv_rows(v_all)
    if cfg.swa_window is not None and S >= T:
        # ring alignment: token position p sits at slot p % T
        idx = (jnp.arange(Tc) + (S - Tc)) % T
        kc = jnp.zeros_like(cache.k).at[:, :, idx].set(k_all.astype(cache.k.dtype))
        vc = jnp.zeros_like(cache.v).at[:, :, idx].set(v_all.astype(cache.v.dtype))
        if cfg.quant_kv:
            ks_all = jnp.zeros_like(cache.k_s).at[:, :, idx].set(ks_all)
            vs_all = jnp.zeros_like(cache.v_s).at[:, :, idx].set(vs_all)
    else:
        kc = jax.lax.dynamic_update_slice(
            cache.k, k_all.astype(cache.k.dtype), (0, 0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache.v, v_all.astype(cache.v.dtype), (0, 0, 0, 0, 0))
        if cfg.quant_kv:
            ks_all = jax.lax.dynamic_update_slice(
                cache.k_s, ks_all, (0, 0, 0, 0))
            vs_all = jax.lax.dynamic_update_slice(
                cache.v_s, vs_all, (0, 0, 0, 0))
    return out.logits, KVCache(kc, vc, jnp.full((B,), S, jnp.int32),
                               ks_all, vs_all)
