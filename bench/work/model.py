"""Model FLOPs per trained token: 6 N over the matmul parameters (the
LM head included, the embedding gather and the depthwise convolution
not), plus causal attention's 6 L S d, plus the SSD scan's work.
Recomputation is not counted."""
from __future__ import annotations

from typing import Dict

from bench.work import ssd


def matmul_params(cfg: Dict) -> int:
    d, L, V = cfg["d_model"], cfg["num_layers"], cfg["vocab_size"]
    if cfg["family"] == "ssm":
        s = cfg["ssm"]
        inner = s["expand"] * d
        heads = inner // s["head_dim"]
        in_dim = 2 * inner + 2 * s["n_groups"] * s["state_size"] + heads
        block = d * in_dim + inner * d
    else:
        hd = cfg["num_heads"] * cfg["head_dim"]
        kv = cfg["num_kv_heads"] * cfg["head_dim"]
        mats = 3 if cfg["mlp_variant"] == "swiglu" else 2
        block = d * hd + 2 * d * kv + hd * d + mats * d * cfg["d_ff"]
    return L * block + V * d


def flops_per_token(cfg: Dict) -> float:
    S, L = cfg["deployment"]["seq_len"], cfg["num_layers"]
    total = 6.0 * matmul_params(cfg)
    if cfg["family"] == "ssm":
        total += L * ssd.work(cfg)[0] / S
    else:
        total += 6.0 * L * S * cfg["num_heads"] * cfg["head_dim"]
    return total
