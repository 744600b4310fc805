"""Operations and bytes that each measured piece of work needs, from its
shapes.  These are the numerators of every roofline and utilization share
the benchmark reports; they count what the algorithm needs, never padding,
recomputation or masked positions.
"""
from __future__ import annotations


# ------------------------------------------------------------ qmatmul kernel

def qmatmul(M: int, K: int, N: int, checksum: bool = True) -> dict:
    """int8 (M, K) x (K, N) -> int32 (M, N) accumulator.

    With ``checksum`` the kernel also takes the (K, 4) int8 limbs of the
    weight's check vector and emits an (M, 4) int32 check: 4 more output
    columns of work, as the kernel computes them.
    """
    cols = N + (4 if checksum else 0)
    ops = 2 * M * K * cols
    nbytes = M * K + K * cols + 4 * M * cols
    return {"int8_ops": ops, "bytes": nbytes}


# ------------------------------------------------------------ qconv2d kernel

def conv_out(size: int, stride: int) -> int:
    """Output side of a 'SAME' convolution."""
    return -(-size // stride)


def qconv2d(n: int, h: int, w: int, cin: int, cout: int, kh: int, kw: int,
            stride: int, checksum: bool = True) -> dict:
    """int8 NHWC (n, h, w, cin) conv with a (kh, kw, cin, cout) int8 weight,
    'SAME' padding -> int32 accumulator (n, ho, wo, cout).

    With ``checksum`` the kernel appends the 4 int8 limb columns of the
    weight's check vector to the weight and emits 4 more int32 columns.
    """
    ho, wo = conv_out(h, stride), conv_out(w, stride)
    cols = cout + (4 if checksum else 0)
    ops = 2 * n * ho * wo * kh * kw * cin * cols
    nbytes = n * h * w * cin + kh * kw * cin * cols + 4 * n * ho * wo * cols
    return {"int8_ops": ops, "bytes": nbytes}


def shipdet_macs_per_frame(cfg: dict) -> int:
    """Multiply-accumulates of one tile through the network as it runs."""
    side, macs = cfg["tile"], 0
    for s in cfg["layers"]:
        out = conv_out(side, s["stride"])
        macs += out * out * s["kh"] * s["kw"] * s["cin"] * s["cout"]
        side = out
    return macs


# ---------------------------------------------------------------- smollm step

def smollm_token_ops(cfg: dict, position: int, logits: bool) -> dict:
    """Operations to run one token at ``position`` (0-based) through the
    model: bf16 projections and attention over the ``position + 1`` cached
    positions it sees, int8 feed-forward, and the output head when the
    token's logits are needed."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim", d // H)
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    proj = 2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d
    attn = 2 * 2 * (position + 1) * H * hd
    bf16 = L * (proj + attn) + (2 * d * V if logits else 0)
    return {"bf16_ops": bf16, "int8_ops": L * 3 * 2 * d * ff}


def smollm_request_ops(cfg: dict, prompt_len: int, released: int) -> dict:
    """Operations a request needs: every prompt token and every released
    token but the last goes through the model once; logits are needed at
    the last prompt position and after each released token but the last."""
    out = {"bf16_ops": 0, "int8_ops": 0}
    n = prompt_len + released - 1
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // H)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    per = smollm_token_ops(cfg, 0, logits=False)
    # closed form of the sum over positions 0..n-1 of the attention term
    out["bf16_ops"] = n * (per["bf16_ops"] - L * 2 * 2 * H * hd) \
        + L * 2 * 2 * H * hd * n * (n + 1) // 2 + released * 2 * d * V
    out["int8_ops"] = n * per["int8_ops"]
    return out


def least_time_s(ops: dict, peaks: dict) -> float:
    """Least time the chip needs for ``ops`` at its peaks (compute only)."""
    return (ops.get("bf16_ops", 0) / peaks["bf16_flops"]
            + ops.get("int8_ops", 0) / peaks["int8_ops"])


def roofline_time_s(work: dict, peaks: dict) -> tuple:
    """(least time, bound) of a kernel call: the larger of its operations
    at the int8 peak and its bytes at the memory bandwidth."""
    t_ops = work["int8_ops"] / peaks["int8_ops"]
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
