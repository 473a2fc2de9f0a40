"""Operations and bytes, computed from shapes.

Per kernel call (for roofline shares) and per model token (for MFU).  A
roofline share divides the least time these imply by measured device
time, so every count here is of work the algorithm needs, never more:
causal attention counts only the query-key pairs a causal mask keeps,
and bytes are each operand read once and each result written once.
"""
from __future__ import annotations



# ---------------------------------------------------------------- models --
def matmul_params(cfg) -> int:
    """Weights that take part in a matrix product per token: the
    projections of every layer and the output head (an embedding lookup
    is no product)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    per_layer = d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def causal_pairs(sq: int, skv: int, q_offset: int = 0,
                 window: int = 0) -> int:
    """Query-key pairs with key position <= query position (and, with a
    sliding ``window``, > query position - window), for queries at
    ``q_offset .. q_offset + sq - 1`` and keys at ``0 .. skv - 1``."""
    total = 0
    for q in range(q_offset, q_offset + sq):
        hi = min(q + 1, skv)
        lo = max(0, q - window + 1) if window else 0
        total += max(0, hi - lo)
    return total


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward and backward model FLOPs per trained token, recomputation
    not counted: 6 per matmul weight, plus causal attention's QK^T and PV
    (2 FLOPs per multiply-add, each over head_dim, three passes)."""
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    mean_ctx = causal_pairs(seq, seq, 0, cfg["sliding_window"]) / seq
    attn = 3 * 2 * 2 * nh * hd * mean_ctx * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) + attn


# --------------------------------------------------------------- kernels --
# head-dim widths of the products each flash kernel makes per query-key
# pair: forward QK^T and PV; dq: QK^T, dO V^T, dS K; dk/dv: QK^T, dO V^T,
# P^T dO, dS^T Q
ATTENTION_PRODUCTS = {"fwd": ("k", "v"), "dq": ("k", "v", "k"),
                      "dkv": ("k", "v", "v", "k")}


def attention_flops(kind: str, bh: int, sq: int, skv: int, dk: int,
                    dv: int, *, causal: bool, q_offset: int = 0,
                    window: int = 0) -> float:
    """FLOPs of one flash-attention kernel call over ``bh`` heads,
    counting only the pairs a causal mask (and window) keeps."""
    pairs = (causal_pairs(sq, skv, q_offset, window) if causal
             else sq * skv)
    width = sum(dk if w == "k" else dv for w in ATTENTION_PRODUCTS[kind])
    return 2.0 * bh * pairs * width


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at the least: compute- or memory-bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
