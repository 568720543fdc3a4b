"""Causal GQA flash-attention forward AND backward — single-writer
Pallas kernels, compiled by Mosaic on TPU and interpreted on the CPU.

Every reduction axis lives INSIDE the kernel body, so no output block
is written from more than one grid cell and the whole grid may be
declared parallel (kernels/gridcheck.py enforces the discipline):

    fwd : grid (B, H, q_blocks).  One ``fori_loop`` over kv blocks
          carries (acc, m, l) as loop values; k/v are whole-
          (padded-)sequence VMEM refs sliced with ``pl.ds``.
    bwd : THREE single-writer calls, each accumulating only along its
          own in-body loop —
          dq : grid (B, H, q_blocks),  loop over kv blocks
          dk : grid (B, H, kv_blocks), loop over q blocks
          dv : grid (B, H, kv_blocks), loop over q blocks
          dk/dv are emitted at Q-head resolution; the GQA group fold is
          one jnp reshape-sum outside.

The loop bounds are data-independent functions of the block row/column:
causality skips kv blocks above the diagonal, a sliding window skips
blocks left of it.

The backward stays the standard two-pass recompute-free formulation
(FlashAttention-2 §3.2): the forward saves (out, lse); ``delta`` =
rowsum(dO ∘ O) is a cheap jnp preprocess; p = exp(s - lse) is rebuilt
blockwise from the saved lse — no O(S²) probability matrix ever exists,
unlike the jnp-oracle backward ops.py retains as the parity reference.
Per-row statistics (lse, delta) travel as ``[B, H, 1, S]`` rows: Mosaic
tiles the last two block dims (8, 128), which a bare ``[.., block_q]``
block violates.

Block shapes default to (128, 128) so the MXU sees aligned GEMMs; the
whole-sequence k/v refs cost S·D·4B VMEM each (512 KiB at S=2048,
D=64), far under budget.  The autotuner (kernels/autotune.py) picks
larger q/k blocks where grid overhead dominates (the CPU interpreter).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gridcheck import checked_pallas_call

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kv_bounds(q_start, block_q: int, block_k: int, window: int,
               num_kv_blocks: int):
    """[lo, hi) kv-block range a q block attends to (causal + window)."""
    hi = jnp.minimum((q_start + block_q - 1) // block_k + 1, num_kv_blocks)
    if window > 0:
        lo = jnp.maximum((q_start - window + 1) // block_k, 0)
    else:
        lo = 0
    return lo, hi


def _q_bounds(k_start, block_q: int, block_k: int, window: int,
              num_q_blocks: int):
    """[lo, hi) q-block range that attends to a kv block (transpose of
    ``_kv_bounds``: iq in range iff k_start <= q_start + block_q - 1 and
    k_start + block_k - 1 > q_start - window)."""
    lo = k_start // block_q
    if window > 0:
        hi = jnp.minimum((k_start + block_k + window - 2) // block_q + 1,
                         num_q_blocks)
    else:
        hi = num_q_blocks
    return lo, hi


def _scores(q, k, *, q_start, k_start, seq_len: int, window: int):
    """Scaled masked scores for one (q block, kv block) pair."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    s = s * (1.0 / math.sqrt(q.shape[-1]))              # [bq, bk]
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (kpos <= qpos) & (kpos < seq_len)
    if window > 0:
        mask &= kpos > qpos - window
    return s, mask


def _recompute_p(q, k, lse, *, q_start, k_start, seq_len: int, window: int):
    """Backward block recompute: p = exp(s - lse), masked."""
    s, mask = _scores(q, k, q_start=q_start, k_start=k_start,
                      seq_len=seq_len, window=window)
    return jnp.where(mask, jnp.exp(s - lse), 0.0)       # [bq, bk]


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                  block_k: int, seq_len: int, window: int,
                  num_kv_blocks: int):
    iq = pl.program_id(2)
    q_start = iq * block_q
    q = q_ref[0, 0].astype(jnp.float32)                 # [bq, d]
    d = q.shape[-1]
    lo, hi = _kv_bounds(q_start, block_q, block_k, window, num_kv_blocks)

    def body(ik, carry):
        acc, m_prev, l_prev = carry
        k_start = ik * block_k
        k = k_ref[0, 0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        s, mask = _scores(q, k, q_start=q_start, k_start=k_start,
                          seq_len=seq_len, window=window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, -1, keepdims=True)
        acc = (acc * corr
               + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        return acc, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        lo, hi, body,
        (jnp.zeros((block_q, d), jnp.float32),
         jnp.full((block_q, 1), NEG_INF, jnp.float32),
         jnp.zeros((block_q, 1), jnp.float32)))
    l = jnp.maximum(l, 1e-20)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l)).reshape(1, block_q)


def _pad_tr(t: jax.Array, pad: int) -> jax.Array:
    """[B, S, H, D] -> [B, H, S + pad, D]."""
    return jnp.pad(t.transpose(0, 2, 1, 3),
                   ((0, 0), (0, 0), (0, pad), (0, 0)))


def _fwd_call(q, k, v, *, window: int, block_q: int, block_k: int,
              interpret: bool) -> Tuple[jax.Array, jax.Array]:
    B, S, H, D = q.shape
    KV = k.shape[2]
    assert H % KV == 0, (H, KV)
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = -(-S // block_q)
    nk = -(-S // block_k)
    Sk = nk * block_k
    qt = _pad_tr(q, nq * block_q - S)
    kt = _pad_tr(k, Sk - S)
    vt = _pad_tr(v, Sk - S)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_len=S,
        window=window, num_kv_blocks=nk)
    out, lse = checked_pallas_call(
        "flash_fwd", kernel,
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, iq: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, iq: (b, h // G, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, iq: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * block_q, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, nq * block_q), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out, lse


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """Causal GQA attention.

    q: [B, S, H, D]; k/v: [B, S, KV, D]; H % KV == 0.  Returns [B, S, H, D].
    """
    S = q.shape[1]
    out, _ = _fwd_call(q, k, v, window=window, block_q=block_q,
                       block_k=block_k, interpret=interpret)
    return out[:, :, :S].transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Forward that also returns the softmax log-sum-exp residual.

    Returns (out [B, S, H, D], lse [B, H, S] fp32) — exactly the
    residuals the two-pass backward needs besides (q, k, v, out).
    """
    S = q.shape[1]
    out, lse = _fwd_call(q, k, v, window=window, block_q=block_q,
                         block_k=block_k, interpret=interpret)
    return out[:, :, :S].transpose(0, 2, 1, 3), lse[:, :, 0, :S]


# ----------------------------------------------------------------------
# Backward kernels (two-pass, recompute-free, single-writer)
# ----------------------------------------------------------------------
def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                         dq_ref, *, block_q: int, block_k: int,
                         seq_len: int, window: int, num_kv_blocks: int):
    iq = pl.program_id(2)
    q_start = iq * block_q
    q = q_ref[0, 0].astype(jnp.float32)                 # [bq, d]
    g = g_ref[0, 0].astype(jnp.float32)                 # [bq, d]
    lse = lse_ref[0, 0].reshape(block_q, 1)
    delta = d_ref[0, 0].reshape(block_q, 1)
    scale = 1.0 / math.sqrt(q.shape[-1])
    lo, hi = _kv_bounds(q_start, block_q, block_k, window, num_kv_blocks)

    def body(ik, dq):
        k_start = ik * block_k
        k = k_ref[0, 0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        p = _recompute_p(q, k, lse, q_start=q_start, k_start=k_start,
                         seq_len=seq_len, window=window)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    dq = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dk_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, d_ref,
                         dk_ref, *, block_q: int, block_k: int,
                         seq_len: int, window: int, num_q_blocks: int):
    ik = pl.program_id(2)
    k_start = ik * block_k
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    v = v_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    scale = 1.0 / math.sqrt(k.shape[-1])
    lo, hi = _q_bounds(k_start, block_q, block_k, window, num_q_blocks)

    def body(iq, dk):
        q_start = iq * block_q
        q = q_ref[0, 0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        g = g_ref[0, 0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, pl.ds(q_start, block_q)].reshape(block_q, 1)
        delta = d_ref[0, 0, :, pl.ds(q_start, block_q)].reshape(block_q, 1)
        p = _recompute_p(q, k, lse, q_start=q_start, k_start=k_start,
                         seq_len=seq_len, window=window)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * scale                   # [bq, bk]
        return dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    dk = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((block_k, k.shape[-1]), jnp.float32))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)


def _flash_bwd_dv_kernel(q_ref, k_ref, g_ref, lse_ref, dv_ref, *,
                         block_q: int, block_k: int, seq_len: int,
                         window: int, num_q_blocks: int):
    ik = pl.program_id(2)
    k_start = ik * block_k
    k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
    lo, hi = _q_bounds(k_start, block_q, block_k, window, num_q_blocks)

    def body(iq, dv):
        q_start = iq * block_q
        q = q_ref[0, 0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        g = g_ref[0, 0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, pl.ds(q_start, block_q)].reshape(block_q, 1)
        p = _recompute_p(q, k, lse, q_start=q_start, k_start=k_start,
                         seq_len=seq_len, window=window)
        return dv + jax.lax.dot_general(p, g, (((0,), (0,)), ((), ())))

    dv = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((block_k, k.shape[-1]), jnp.float32))
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret"))
def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        out: jax.Array, lse: jax.Array, g: jax.Array, *,
                        window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Two-pass flash-attention backward (three single-writer kernels).

    q/g/out: [B, S, H, D]; k/v: [B, S, KV, D]; lse: [B, H, S] fp32.
    Returns (dq, dk, dv) with the primals' layouts and dtypes.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = -(-S // block_q)
    nk = -(-S // block_k)
    Sq = nq * block_q
    Sk = nk * block_k
    qt = _pad_tr(q, Sq - S)
    kt = _pad_tr(k, Sk - S)
    vt = _pad_tr(v, Sk - S)
    gt = _pad_tr(g, Sq - S)
    # delta = rowsum(dO * O) — the cheap preprocessing pass
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.pad(delta.transpose(0, 2, 1),
                    ((0, 0), (0, 0), (0, Sq - S)))[:, :, None]
    lse_p = jnp.pad(lse, ((0, 0), (0, 0), (0, Sq - S)))[:, :, None]

    q_blk = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0))
    q_all = pl.BlockSpec((1, 1, Sq, D), lambda b, h, i: (b, h, 0, 0))
    kv_blk = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i: (b, h // G, i, 0))
    kv_all = pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // G, 0, 0))
    # per-row statistics ride as [B, H, 1, Sq] rows: Mosaic tiles the
    # last two block dims (8, 128), which a bare [.., block_q] violates
    row_blk = pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i: (b, h, 0, i))
    row_all = pl.BlockSpec((1, 1, 1, Sq), lambda b, h, i: (b, h, 0, 0))
    kv_out = pl.BlockSpec((1, 1, block_k, D), lambda b, h, i: (b, h, i, 0))

    dq = checked_pallas_call(
        "flash_bwd_dq",
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, seq_len=S, window=window,
                          num_kv_blocks=nk),
        grid=(B, H, nq),
        in_specs=[q_blk, kv_all, kv_all, q_blk, row_blk, row_blk],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(qt, kt, vt, gt, lse_p, delta)

    dk_h = checked_pallas_call(
        "flash_bwd_dk",
        functools.partial(_flash_bwd_dk_kernel, block_q=block_q,
                          block_k=block_k, seq_len=S, window=window,
                          num_q_blocks=nq),
        grid=(B, H, nk),
        in_specs=[q_all, kv_blk, kv_blk, q_all, row_all, row_all],
        out_specs=kv_out,
        out_shape=jax.ShapeDtypeStruct((B, H, Sk, D), k.dtype),
        interpret=interpret,
    )(qt, kt, vt, gt, lse_p, delta)

    dv_h = checked_pallas_call(
        "flash_bwd_dv",
        functools.partial(_flash_bwd_dv_kernel, block_q=block_q,
                          block_k=block_k, seq_len=S, window=window,
                          num_q_blocks=nq),
        grid=(B, H, nk),
        in_specs=[q_all, kv_blk, q_all, row_all],
        out_specs=kv_out,
        out_shape=jax.ShapeDtypeStruct((B, H, Sk, D), v.dtype),
        interpret=interpret,
    )(qt, kt, gt, lse_p)

    dq = dq[:, :, :S].transpose(0, 2, 1, 3)
    # GQA: per-Q-head dk/dv fold onto the KV heads with one reshape-sum
    dk = dk_h[:, :, :S].reshape(B, KV, G, S, D).sum(axis=2)
    dv = dv_h[:, :, :S].reshape(B, KV, G, S, D).sum(axis=2)
    return (dq, dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))
