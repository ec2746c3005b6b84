"""Work counters: operations and bytes from shapes alone.

These count the work the algorithm needs, never what an implementation
happens to do: no padded tiles, no recomputation.  A kernel's roofline
share divides the least time this work can take on the chip by the
kernel's measured device time, so padding and recomputation show as a
lower share.  Operations are multiply-adds times two; bytes are bf16
activations and weights (2 bytes) unless said otherwise.
"""
from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2
F32 = 4


def n_mats(cfg: Dict) -> int:
    """Weight matrices per expert: 3 for a gated (GLU) FFN, else 2."""
    return 3 if cfg["act"].endswith("_glu") else 2


def model_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward plus backward operations per trained token: attention
    projections, causal scores, router, the top-k routed experts and the
    output head, times 3 (forward, and the two products of the backward).
    Recomputation is not counted."""
    d, h, kv, hd = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                    cfg["head_dim"])
    moe = cfg["moe"]
    proj = 2 * d * (2 * h * hd + 2 * kv * hd)
    # causal: token i attends to i + 1 positions, (S + 1) / 2 on average
    scores = 2 * 2 * h * hd * (seq_len + 1) / 2
    router = 2 * d * moe["num_experts"]
    experts = moe["experts_per_token"] * 2 * d * moe["d_ff"] * n_mats(cfg)
    head = 2 * d * cfg["vocab_size"]
    fwd = cfg["num_layers"] * (proj + scores + router + experts) + head
    return 3.0 * fwd


def grouped_mlp(cfg: Dict, rows: float, experts_used: int,
                kind: str) -> Dict[str, float]:
    """One grouped-MLP call over ``rows`` kept (token, expert) rows that
    land on ``experts_used`` experts.  ``kind`` is ``fwd`` (y from x),
    ``dgrad`` (dx from dy) or ``wgrad`` (the weight gradients, written
    in float32)."""
    d, f, m = cfg["d_model"], cfg["moe"]["d_ff"], n_mats(cfg)
    flops = 2.0 * rows * d * f * m
    weights = float(m * d * f * experts_used)
    if kind == "fwd":
        nbytes = BF16 * (weights + 2 * rows * d)          # W, x in, y out
    elif kind == "dgrad":
        nbytes = BF16 * (weights + 3 * rows * d)          # W, x, dy, dx
    elif kind == "wgrad":
        nbytes = BF16 * 2 * rows * d + F32 * weights      # x, dy; dW out
    else:
        raise ValueError(kind)
    return {"flops": flops, "bytes": nbytes}


def flash_attention_fwd(batch: int, seq_len: int, heads: int,
                        head_dim: int) -> Dict[str, float]:
    """One causal flash-attention forward over (batch, seq, heads, hd):
    QK^T and PV over the S(S+1)/2 causal pairs; q, k, v in, o out."""
    pairs = seq_len * (seq_len + 1) / 2
    flops = 2 * 2 * batch * heads * head_dim * pairs
    nbytes = BF16 * 4 * batch * seq_len * heads * head_dim
    return {"flops": float(flops), "bytes": float(nbytes)}


def paged_decode_attention(lengths: Sequence[int], page_size: int,
                           heads: int, kv_heads: int,
                           head_dim: int) -> Dict[str, float]:
    """One paged decode step: each live sequence of length L reads its
    ceil(L / page) KV pages (k and v) and its query, and writes its
    output; scores and PV over its L positions."""
    pages = sum(-(-n // page_size) for n in lengths)
    kv = BF16 * 2 * pages * page_size * kv_heads * head_dim
    qo = BF16 * 2 * len(lengths) * heads * head_dim
    flops = sum(2 * 2 * heads * head_dim * n for n in lengths)
    return {"flops": float(flops), "bytes": float(kv + qo)}


def least_seconds(work: Dict[str, float], peaks: Dict) -> float:
    """The roofline: the larger of operations over peak operations and
    bytes over peak bandwidth."""
    return max(work["flops"] / peaks["bf16_flops"],
               work["bytes"] / peaks["hbm_bytes_per_s"])


def bound(work: Dict[str, float], peaks: Dict) -> str:
    """Which roof limits ``work``: ``compute`` or ``memory``."""
    return ("compute" if work["flops"] / peaks["bf16_flops"]
            >= work["bytes"] / peaks["hbm_bytes_per_s"] else "memory")
