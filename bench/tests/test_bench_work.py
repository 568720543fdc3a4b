"""Operations and bytes from shapes (bench/work) and the peak table."""
import json

import pytest

from bench import harness
from bench.peaks import peaks
from bench.work import flash_attention, model, ssd

ROOT = harness.ROOT


def config(name):
    return harness.load_json(ROOT / "bench" / "configs" / f"{name}.json")


def test_gpt3_medium_flops_per_token_by_hand():
    cfg = config("gpt3_medium")
    d, L, S, V, f = 1024, 8, 2048, 50257, 4096
    n = L * (4 * d * d + 2 * d * f) + V * d      # blocks + LM head
    assert model.matmul_params(cfg) == n
    assert model.flops_per_token(cfg) == 6 * n + 6 * L * S * d


def test_mamba2_flops_per_token_adds_the_scan():
    cfg = config("mamba2_780m")
    d, L, V = 1536, 8, 50288
    inner, heads, N = 2 * d, 2 * d // 64, 128
    n = L * (d * (2 * inner + 2 * N + heads) + inner * d) + V * d
    assert model.matmul_params(cfg) == n
    scan = L * ssd.work(cfg)[0] / 2048
    assert model.flops_per_token(cfg) == pytest.approx(6 * n + scan)
    assert 0 < scan < 0.1 * 6 * n


def test_flash_work_by_hand_at_a_small_shape():
    cfg = {"num_heads": 2, "head_dim": 8,
           "deployment": {"seq_len": 4, "dtype": "float32"}}
    flops, nbytes = flash_attention.work(cfg)
    # causal half of 4 x 4 pairs = 8; 6 matmuls x 2 x D per pair and head
    assert flops == 6 * 2 * 8 * 8 * 2
    # q k v o dO dq dk dv: 8 x S x H x D floats, plus S x H lse
    assert nbytes == (8 * 4 * 2 * 8 + 4 * 2) * 4
    half = dict(cfg, deployment={"seq_len": 4, "dtype": "bfloat16"})
    assert flash_attention.work(half) == (flops, (8 * 4 * 2 * 8 + 4 * 2) * 2)


def test_ssd_work_follows_the_configured_chunk():
    cfg = config("mamba2_780m")
    flops, _ = ssd.work(cfg)
    Q, N, P, H, chunks = 256, 128, 64, 48, 8
    assert flops == 3 * (Q * Q * N + Q * Q * P + 4 * Q * N * P) * chunks * H
    half = json.loads(json.dumps(cfg))
    half["ssm"]["chunk_size"] = 128
    small, _ = ssd.work(half)
    # halving the chunk halves the quadratic part and keeps the rest
    quad = (Q * Q * (N + P)) * chunks * H * 3
    assert small == pytest.approx(flops - quad / 2)
    assert ssd.work(half)[1] == ssd.work(cfg)[1]


def test_peaks_raise_on_an_unknown_device_kind():
    row = peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert row["source"]
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
