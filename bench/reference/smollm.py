"""SmolLM-135M: the weights the benchmark serves, and a plain float32
reference of the model, independent of the program under test.

The layer equations are the llama layout of HuggingFaceTB/SmolLM-135M
(pre-norm RMSNorm, grouped-query attention with rotary embeddings, SwiGLU
feed-forward, tied input and output embeddings), written with the
parameter conventions the served program takes: RMSNorm gains stored as
``1 + g``, rotary embedding on split halves, feed-forward weights stored
int8 with a float32 scale per output channel.

``make_weights`` builds that parameter tree on the device in one jitted
call from the seed.  ``gaps`` runs the reference over prompts with the
tokens a server released and returns, for each released token, how far its
reference logit lies below the reference's best logit at that position.
With ``lower=True`` the same reference runs one precision step below what
the configuration states (bf16 operands as float8 e4m3; the int8 feed-
forward activations, feed-forward weights and K/V as int4) and the gap is
read for the token that lower precision ranks first: the control.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's numbers."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=h,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), ff=cfg["intermediate_size"],
                V=cfg["vocab_size"], eps=cfg["rms_norm_eps"],
                theta=cfg["rope_theta"])


def _quantize_columns(w):
    """Symmetric int8 per output channel over the contraction axis -2."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2), 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / s[..., None, :]), -127, 127).astype(jnp.int8)
    return q, s


def make_weights(cfg: dict, key) -> dict:
    """Random weights in the served layout, made on the device."""
    m = dims(cfg)
    L, d, H, KV, hd, ff, V = (m[k] for k in ("L", "d", "H", "KV", "hd", "ff",
                                             "V"))

    @jax.jit
    def make(key):
        k = iter(jax.random.split(key, 12))

        def dense(shape):          # (L, fan_in, fan_out), std 1/sqrt(fan_in)
            return jax.random.normal(next(k), shape) / math.sqrt(shape[1])

        blocks = {
            "ln1": 0.1 * jax.random.normal(next(k), (L, d)),
            "ln2": 0.1 * jax.random.normal(next(k), (L, d)),
            "wq": dense((L, d, H * hd)),
            "wk": dense((L, d, KV * hd)),
            "wv": dense((L, d, KV * hd)),
            "wo": dense((L, H * hd, d)),
        }
        for name, shape in (("wi", (L, d, ff)), ("wg", (L, d, ff)),
                            ("wd", (L, ff, d))):
            blocks[name + "_q"], blocks[name + "_s"] = _quantize_columns(
                dense(shape))
        return {"embed": 0.02 * jax.random.normal(next(k), (V, d)),
                "final_norm": 0.1 * jax.random.normal(next(k), (d,)),
                "dense_blocks": blocks}

    return make(key)


# ----------------------------------------------------------------- forward

def _f8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _int4_rows(x):
    """Symmetric int4 over the last axis, per row."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 7.0
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _int4_columns(w):
    """Symmetric int4 per output channel over axis -2."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-8) / 7.0
    return jnp.clip(jnp.round(w / s), -7, 7) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + g)


def _rope(x, theta):
    """x: (S, heads, hd), positions 0..S-1, rotation on split halves."""
    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits(m: dict, params, tokens, lower: bool = False):
    """(S,) token ids -> (S, V) float32 logits, causal, positions 0..S-1."""
    with jax.default_matmul_precision("highest"):
        return _logits(m, params, tokens, lower)


def _logits(m, params, tokens, lower):
    S = tokens.shape[0]
    H, KV, hd, eps = m["H"], m["KV"], m["hd"], m["eps"]
    op = _f8 if lower else (lambda a: a)            # bf16-stated operands
    q4 = _int4_rows if lower else (lambda a: a)     # int8-stated activations
    embed = params["embed"].astype(jnp.float32)
    x = embed[tokens]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, bp):
        h = _rms(x, bp["ln1"], eps)
        q = (op(h) @ op(bp["wq"])).reshape(S, H, hd)
        k = (op(h) @ op(bp["wk"])).reshape(S, KV, hd)
        v = (op(h) @ op(bp["wv"])).reshape(S, KV, hd)
        q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
        k, v = q4(k), q4(v)                          # the K/V cache
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", op(q), op(k)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", op(p), op(v)).reshape(S, H * hd)
        x = x + op(o) @ op(bp["wo"])
        h = _rms(x, bp["ln2"], eps)

        def ffn(a, name):
            w = bp[name + "_q"].astype(jnp.float32) * bp[name + "_s"][None, :]
            if lower:
                w = _int4_columns(w)
            return q4(a) @ w

        a = jax.nn.silu(ffn(h, "wg")) * ffn(h, "wi")
        return x + ffn(a, "wd"), None

    blocks = params["dense_blocks"]
    x, _ = jax.lax.scan(layer, x, blocks)
    x = _rms(x, params["final_norm"], eps)
    return op(x) @ op(embed).T


@partial(jax.jit, static_argnames=("m_items", "lower"))
def _gaps_padded(m_items, params, tokens, targets, lower):
    """Gap of each target token below the reference's best, per position.

    ``targets[i]`` is the token released after position ``i`` (-1 where
    nothing was released).  With ``lower`` the gap is that of the token the
    lower precision ranks first at each released position."""
    m = dict(m_items)
    ref = logits(m, params, tokens)
    best = ref.max(-1)
    if lower:
        pick = jnp.argmax(logits(m, params, tokens, lower=True), -1)
    else:
        pick = jnp.maximum(targets, 0)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.where(targets >= 0, best - got, 0.0)


def gaps(cfg: dict, params, prompt, released, *, pad_to: int,
         lower: bool = False) -> np.ndarray:
    """Per released token, the reference's best logit minus the logit of
    the released token (``lower=False``) or of the token the lower
    precision ranks first (``lower=True``)."""
    m = dims(cfg)
    seq = list(prompt) + list(released[:-1])
    n = len(seq)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds pad {pad_to}")
    toks = np.zeros(pad_to, np.int32)
    toks[:n] = seq
    tgt = np.full(pad_to, -1, np.int32)
    tgt[len(prompt) - 1:n] = released
    out = _gaps_padded(tuple(sorted(m.items())), params, jnp.asarray(toks),
                       jnp.asarray(tgt), lower)
    return np.asarray(out)[len(prompt) - 1:n]
