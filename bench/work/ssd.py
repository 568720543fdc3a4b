"""The SSD chunked scan (Mamba2), forward and backward, for one sequence
in one layer, from the SSD equations at the configuration's chunk size Q.

Per chunk and head, the forward is C B^T and (L o C B^T) x over the
causal half of the Q x Q pairs, the incoming state's contribution C h,
and the state update B^T (w x): Q^2 N + Q^2 P + 4 Q N P FLOPs.  The
backward takes two matmuls for each forward one, so fwd + bwd is three
times the forward.  Bytes: x, y, dx, dy, dt, d(dt), and B, C, dB, dC
per group, each moved once.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def work(cfg: Dict) -> Tuple[float, float]:
    s = cfg["ssm"]
    S = cfg["deployment"]["seq_len"]
    Q, N, P = s["chunk_size"], s["state_size"], s["head_dim"]
    H = s["expand"] * cfg["d_model"] // P
    chunks = math.ceil(S / Q)
    fwd = Q * Q * N + Q * Q * P + 4 * Q * N * P
    flops = 3 * fwd * chunks * H
    size = ITEMSIZE[cfg["deployment"]["dtype"]]
    nbytes = (4 * S * H * P + 2 * S * H + 4 * S * s["n_groups"] * N) * size
    return flops, nbytes
