"""GQA attention: training/prefill (full, blocked/online-softmax, or
Pallas kernel) and single-token decode against a KV cache.

Three prefill paths with identical semantics:
  * ``naive``   — materializes the [S, S] score matrix; fine for smoke
    tests and short sequences.
  * ``blocked`` — lax.scan over KV blocks with online softmax (the
    flash-attention recurrence in pure XLA).  HBM traffic is O(S) instead
    of O(S^2); this path is also the kernel's numerical oracle.
  * ``kernel``  — the Pallas flash-attention kernel through kernels/ops.py
    with its registered Pallas BACKWARD (custom_vjp) and autotuned block
    sizes: compiled on an accelerator (a kernel that does not lower
    raises, DESIGN.md §13), interpreted on the CPU.  This is the stage
    hot path the per-template compiled programs run.

``fused=True`` additionally routes the QKV projection through
ops.fused_qkv — ONE GEMM against the concatenated [d, (H+2KV)*hd]
weight with the bias folded into the epilogue — on the training/prefill
path only (decode's [B, 1, d] activations are dispatch-bound, not
GEMM-bound, so fusion buys nothing there).

GQA is expressed by reshaping Q to [B, S, KV, G, D] (G = heads-per-kv
group) so K/V are never materialized at Q's head count.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope, rms_norm


def init_attention(rng, arch: ArchConfig, dtype=jnp.float32):
    d, H, KV, hd = arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim
    ks = jax.random.split(rng, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, H * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, KV * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, KV * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (H * hd, d), dtype) * (H * hd) ** -0.5,
    }
    if arch.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if arch.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(params, arch: ArchConfig, x: jax.Array, positions: jax.Array,
                 *, fused: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, KV, hd = arch.num_heads, arch.num_kv_heads, arch.head_dim
    if fused and S > 1:
        from repro.kernels import ops as kops
        bias = ((params["bq"], params["bk"], params["bv"])
                if arch.qkv_bias else (None, None, None))
        q, k, v = kops.fused_qkv(x, params["wq"], params["wk"],
                                 params["wv"], *bias)
    else:
        q = x @ params["wq"].astype(x.dtype)
        k = x @ params["wk"].astype(x.dtype)
        v = x @ params["wv"].astype(x.dtype)
        if arch.qkv_bias:
            q = q + params["bq"].astype(x.dtype)
            k = k + params["bk"].astype(x.dtype)
            v = v + params["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if arch.qk_norm:
        q = rms_norm(params["q_norm"].astype(x.dtype), q, arch.rms_norm_eps)
        k = rms_norm(params["k_norm"].astype(x.dtype), k, arch.rms_norm_eps)
    q = apply_rope(q, positions, arch.rope_theta)
    k = apply_rope(k, positions, arch.rope_theta)
    return q, k, v


def _sdpa_naive(q, k, v, *, causal: bool, window: int, q_offset: int = 0):
    """q: [B,Sq,H,D], k/v: [B,Sk,KV,D] -> [B,Sq,H,D]."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(D).astype(q.dtype)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores.astype(jnp.float32),
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, D)


def _sdpa_blocked(q, k, v, *, causal: bool, window: int,
                  block_kv: int = 512):
    """Online-softmax over KV blocks: O(S) memory. Shapes as naive."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    nblk = -(-Sk // block_kv)
    pad = nblk * block_kv - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(B, Sq, KV, G, D)
    scale = 1.0 / jnp.sqrt(D)
    qpos = jnp.arange(Sq)

    def step(carry, blk):
        acc, m, l, j = carry
        kj, vj = blk                                  # [B, bk, KV, D]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj).astype(jnp.float32) * scale
        kpos = j * block_kv + jnp.arange(block_kv)
        mask = kpos[None, :] < Sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): contribute nothing
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), vj)
        acc_new = acc * corr[..., None].astype(acc.dtype) + pv
        return (acc_new, m_new, l_new, j + 1), None

    acc0 = jnp.zeros((B, KV, G, Sq, D), q.dtype)
    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(step, (acc0, m0, l0, 0), (kb, vb))
    out = acc / jnp.maximum(l, 1e-20)[..., None].astype(acc.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)


def attention(params, arch: ArchConfig, x: jax.Array, *,
              positions: Optional[jax.Array] = None,
              impl: str = "blocked", window_override: Optional[int] = None,
              block_kv: int = 512, fused: bool = False) -> jax.Array:
    """Training/prefill attention. x: [B, S, d_model]."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(params, arch, x, positions, fused=fused)
    window = (arch.sliding_window if window_override is None
              else window_override)
    if impl == "kernel" and S > 1:
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, window=window)
    elif impl == "blocked" and S > 1:
        o = _sdpa_blocked(q, k, v, causal=True, window=window,
                          block_kv=min(block_kv, S))
    else:
        o = _sdpa_naive(q, k, v, causal=True, window=window)
    return o.reshape(B, S, -1) @ params["wo"].astype(x.dtype)


# ----------------------------------------------------------------------
# Decode path (KV cache)
# ----------------------------------------------------------------------
def init_kv_cache(arch: ArchConfig, batch: int, max_len: int, dtype):
    KV, hd = arch.num_kv_heads, arch.head_dim
    cache_len = min(max_len, arch.sliding_window) if arch.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, cache_len, KV, hd), dtype),
        "v": jnp.zeros((batch, cache_len, KV, hd), dtype),
    }


def decode_attention(params, arch: ArchConfig, x: jax.Array, cache: dict,
                     pos: jax.Array, constrain=None) -> Tuple[jax.Array, dict]:
    """One-token decode. x: [B, 1, d]; pos: [] scalar current position,
    or [B] per-example positions (the serving plane's slot caches: every
    slot decodes at its own offset, so the cache write is a per-row
    scatter and the validity mask is per-row).

    With a sliding window the cache is a ring buffer of window size;
    otherwise it is the full sequence.  ``constrain`` (optional) pins
    q/k/v to the cache's sharding (e.g. head_dim under TP serving) so
    GSPMD updates the cache in place instead of gathering it per layer.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos)
    vec = pos.ndim == 1                       # per-slot positions
    positions = (pos[:, None] if vec
                 else jnp.broadcast_to(pos[None], (B, 1)))
    q, k, v = _project_qkv(params, arch, x, positions)
    if constrain is not None:
        q = constrain(q, "heads4d")
        k = constrain(k, "heads4d")
        v = constrain(v, "heads4d")
    cache_len = cache["k"].shape[1]
    slot = (pos % cache_len) if arch.sliding_window else pos
    if vec:
        rows = jnp.arange(B)
        ck = cache["k"].at[rows, slot].set(k[:, 0])
        cv = cache["v"].at[rows, slot].set(v[:, 0])
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    KV, hd = arch.num_kv_heads, arch.head_dim
    H = arch.num_heads
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd)
    idx = jnp.arange(cache_len)
    if vec:
        if arch.sliding_window:
            valid = (idx[None] <= slot[:, None]) | (pos[:, None] >= cache_len)
        else:
            valid = idx[None] <= pos[:, None]
        valid = valid[:, None, None, :]       # [B, 1, 1, cache_len]
    else:
        if arch.sliding_window:
            valid = (idx <= slot) | (pos >= cache_len)  # ring buffer filled
        else:
            valid = idx <= pos
        valid = valid[None, None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", probs, cv).reshape(B, 1, H * hd)
    out = o @ params["wo"].astype(x.dtype)
    return out, {"k": ck, "v": cv}
