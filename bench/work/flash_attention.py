"""Causal attention, forward and backward, for one sequence in one layer.

FLOPs: two matmuls forward (Q K^T, P V) and four backward (dV, dP, dQ,
dK), each 2 * D multiply-adds per (query, key) pair and head, over the
causal half of the S x S pairs.  Recomputing P in the backward is not
counted.  Bytes: q, k, v, o, dO, dq, dk, dv and the log-sum-exp rows,
each moved once, in the configuration's dtype.
"""
from __future__ import annotations

from typing import Dict, Tuple

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def work(cfg: Dict) -> Tuple[float, float]:
    S = cfg["deployment"]["seq_len"]
    H, D = cfg["num_heads"], cfg["head_dim"]
    size = ITEMSIZE[cfg["deployment"]["dtype"]]
    pairs = S * S / 2
    flops = 6 * 2 * D * pairs * H
    nbytes = (8 * S * H * D + S * H) * size
    return flops, nbytes
