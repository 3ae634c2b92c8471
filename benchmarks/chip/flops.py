"""Operations and bytes from a configuration's sizes (no program code).

``matmul_params`` is N, the parameters that enter a matrix product once
per token: the attention and FFN projections of every layer and the
(tied) output head.  The embedding lookup, norm scales and biases do no
matrix work and are left out.  A training step needs 6 N FLOPs per token
(forward 2 N, backward 4 N) plus causal attention's
12 L S_eff H hd, S_eff = (S + 1) / 2 keys per query on average.
Recomputed operations are not counted.
"""
from __future__ import annotations

from chip.reference import sizes


def matmul_params(config: dict) -> int:
    s = sizes(config)
    d, q, kv = s["d"], s["h"] * s["hd"], s["kv"] * s["hd"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * s["ff"]
    return s["layers"] * per_layer + s["vocab"] * d


def attention_flops_per_token(config: dict, seq_len: int) -> float:
    s = sizes(config)
    return 12.0 * s["layers"] * (seq_len + 1) / 2 * s["h"] * s["hd"]


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(config) + attention_flops_per_token(config,
                                                                   seq_len)


def flash_attention_cost(config: dict, seq_len: int, batch: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes one training step's flash-attention kernels
    need, over all layers and the whole batch.

    Each (row, head) sees S (S + 1) / 2 causal query-key pairs; a matrix
    product over them costs 2 hd FLOPs a pair.  The forward takes two
    (Q K^T, P V), the backward five (Q K^T again, dP = dO V^T, dV = P^T dO,
    dQ = dS K, dK = dS^T Q).  Bytes: the forward reads q, k, v and writes
    o and the row log-sum-exp; the backward reads q, k, v, dO, the
    log-sum-exp and the row dot(dO, o), and writes dq, dk, dv.
    """
    s = sizes(config)
    L, H, Kv, D = s["layers"], s["h"], s["kv"], s["hd"]
    pairs = seq_len * (seq_len + 1) / 2
    mm = 2.0 * D * pairs * batch * H
    q = batch * seq_len * H * D * itemsize
    kv = batch * seq_len * Kv * D * itemsize
    row = batch * seq_len * H * 4
    return {"flops": L * 7 * mm,
            "bytes": L * ((q + 2 * kv + q + row)
                          + (q + 2 * kv + q + 2 * row + q + 2 * kv))}


def roofline(flops: float, nbytes: float, seconds: float, peak: dict):
    """-> (share of the roofline in %, the bound: 'compute' or 'memory')."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c >= t_m
                                              else "memory")
