"""Fused stage epilogues — Pallas kernels + XLA-fused references.

Two fusions that sit on the per-template scan-program hot path
(DESIGN.md §13):

  * ``add_rmsnorm``: residual-add + RMSNorm as ONE kernel returning
    BOTH the new residual stream and the normed branch input — the
    ``x = x + branch; h = rms_norm(ln2, x)`` seam inside every
    transformer block, which unfused costs two extra HBM round-trips
    of the [B, S, d] activation.
  * ``qkv``: the three Q/K/V projections as ONE tiled GEMM against the
    concatenated weight (bias add in the kernel epilogue) — one MXU
    pass over x instead of three, one dispatch instead of six.

Both are single-writer parallel-grid kernels (kernels/gridcheck.py) —
the fwd AND the custom_vjp bwd.  ``dw`` for the norm weight reduces
across row blocks which live on a parallel grid axis, so the kernel
emits one [1, d] partial per row block (as ``[row_blocks, 1, d]``, a
legal Mosaic tile) and the cross-block sum happens outside
(single-writer discipline; same shape as the SSD dA partials).

``add_rmsnorm_ref`` / ``qkv_ref`` are the XLA formulations: identical
math in one traced expression, used BOTH as the parity oracles and as
the CPU path — an *interpreted* Pallas matmul would lose to XLA by
orders of magnitude, so on the CPU kernels/ops.py lets XLA fuse it
rather than run the interpreter.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gridcheck import checked_pallas_call

DEFAULT_BLOCK_ROWS = 128
DEFAULT_BLOCK_COLS = 128


# ----------------------------------------------------------------------
# Fused residual-add + RMSNorm
# ----------------------------------------------------------------------
def _add_norm_fwd_kernel(x_ref, r_ref, w_ref, res_ref, h_ref, *,
                         eps: float):
    res = x_ref[...] + r_ref[...]                      # [bm, d], in dtype
    res32 = res.astype(jnp.float32)
    var = jnp.mean(res32 * res32, axis=-1, keepdims=True)
    n = (res32 * jax.lax.rsqrt(var + eps)).astype(res.dtype)
    res_ref[...] = res
    h_ref[...] = n * w_ref[...]


def _add_norm_bwd_kernel(res_ref, w_ref, gres_ref, gh_ref, dres_ref,
                         dw_ref, *, eps: float):
    res32 = res_ref[...].astype(jnp.float32)           # [bm, d]
    var = jnp.mean(res32 * res32, axis=-1, keepdims=True)
    rs = jax.lax.rsqrt(var + eps)
    n = (res32 * rs).astype(res_ref.dtype)             # fwd's rounded n
    gh32 = gh_ref[...].astype(jnp.float32)
    # dw partial for THIS row block (cross-block sum outside)
    dw_ref[...] = jnp.sum(gh32 * n.astype(jnp.float32), axis=0,
                          keepdims=True)
    dn = gh32 * w_ref[...].astype(jnp.float32)
    d = res32.shape[-1]
    proj = jnp.sum(dn * res32, axis=-1, keepdims=True) / (d * (var + eps))
    dres32 = rs * (dn - res32 * proj)
    dres_ref[...] = (dres32
                     + gres_ref[...].astype(jnp.float32)
                     ).astype(dres_ref.dtype)


def _row_call(name, kernel, inputs, out_cols, out_dtypes, *, block_rows,
              interpret, partial_out: bool = False):
    """Run a row-blocked (grid = row blocks) kernel over 2D inputs."""
    M, d = inputs[0].shape
    bm = min(block_rows, M)
    nm = -(-M // bm)
    pad = nm * bm - M
    padded = [jnp.pad(t, ((0, pad), (0, 0))) if t.shape[0] == M else t
              for t in inputs]
    row_spec = pl.BlockSpec((bm, d), lambda i: (i, 0))
    one_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    in_specs = [row_spec if t.shape[0] != 1 else one_spec for t in padded]
    out_specs, out_shape = [], []
    for cols, dt, is_partial in zip(out_cols, out_dtypes, partial_out):
        if is_partial:
            # one [1, cols] row per block, as [nm, 1, cols]: Mosaic tiles
            # the last two block dims, and (1, cols) of [nm, cols] is not
            # a legal tile where nm > 1
            out_specs.append(pl.BlockSpec((None, 1, cols),
                                          lambda i: (i, 0, 0)))
            out_shape.append(jax.ShapeDtypeStruct((nm, 1, cols), dt))
        else:
            out_specs.append(pl.BlockSpec((bm, cols), lambda i: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((nm * bm, cols), dt))
    outs = checked_pallas_call(
        name, kernel, grid=(nm,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret)(*padded)
    return [o if p else o[:M] for o, p in zip(outs, partial_out)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _add_rmsnorm_p(x2, r2, w2, eps: float, block_rows: int,
                   interpret: bool):
    res, h = _row_call(
        "fused_norm_fwd",
        functools.partial(_add_norm_fwd_kernel, eps=eps),
        [x2, r2, w2], [x2.shape[1]] * 2, [x2.dtype] * 2,
        block_rows=block_rows, interpret=interpret,
        partial_out=(False, False))
    return res, h


def _add_rmsnorm_p_fwd(x2, r2, w2, eps, block_rows, interpret):
    res, h = _add_rmsnorm_p(x2, r2, w2, eps, block_rows, interpret)
    return (res, h), (res, w2)


def _add_rmsnorm_p_bwd(eps, block_rows, interpret, saved, g):
    res, w2 = saved
    gres, gh = g
    d = res.shape[1]
    dres, dwp = _row_call(
        "fused_norm_bwd",
        functools.partial(_add_norm_bwd_kernel, eps=eps),
        [res, w2, gres, gh], [d, d], [res.dtype, jnp.float32],
        block_rows=block_rows, interpret=interpret,
        partial_out=(False, True))
    dw = jnp.sum(dwp, axis=0).astype(w2.dtype)
    # res = x + r: both addends receive the full residual cotangent
    return dres, dres, dw


_add_rmsnorm_p.defvjp(_add_rmsnorm_p_fwd, _add_rmsnorm_p_bwd)


def add_rmsnorm(x: jax.Array, r: jax.Array, w: jax.Array, *,
                eps: float = 1e-6,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused (res, h) = (x + r, rms_norm(w, x + r)) — Pallas kernel.

    x/r: [..., d]; w: [d] already in x.dtype.  Returns both the updated
    residual stream and the normed branch input, each shaped like x.
    """
    d = x.shape[-1]
    res2, h2 = _add_rmsnorm_p(x.reshape(-1, d), r.reshape(-1, d),
                              w.reshape(1, d), float(eps),
                              int(block_rows), bool(interpret))
    return res2.reshape(x.shape), h2.reshape(x.shape)


def add_rmsnorm_ref(x: jax.Array, r: jax.Array, w: jax.Array, *,
                    eps: float = 1e-6) -> Tuple[jax.Array, jax.Array]:
    """XLA formulation — parity oracle AND the CPU path (identical math
    to models/layers.rms_norm applied to x + r)."""
    res = x + r
    res32 = res.astype(jnp.float32)
    var = jnp.mean(res32 * res32, axis=-1, keepdims=True)
    h = (res32 * jax.lax.rsqrt(var + eps)).astype(res.dtype) * w
    return res, h


# ----------------------------------------------------------------------
# Fused QKV projection (tiled single-GEMM with bias epilogue)
# ----------------------------------------------------------------------
def _matmul_kernel(x_ref, w_ref, b_ref, o_ref):
    acc = jax.lax.dot_general(x_ref[...], w_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _matmul_call(x2, w, b, *, block_m: int, block_n: int,
                 interpret: bool) -> jax.Array:
    M, K = x2.shape
    N = w.shape[1]
    bm = min(block_m, M)
    bn = min(block_n, N)
    nm = -(-M // bm)
    nn = -(-N // bn)
    if nm * bm - M:
        x2 = jnp.pad(x2, ((0, nm * bm - M), (0, 0)))
    if nn * bn - N:
        w = jnp.pad(w, ((0, 0), (0, nn * bn - N)))
        b = jnp.pad(b, ((0, 0), (0, nn * bn - N)))
    out = checked_pallas_call(
        "fused_qkv_matmul", _matmul_kernel,
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), x2.dtype),
        interpret=interpret,
    )(x2, w, b)
    return out[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _matmul_p(x2, w, b, block_m: int, block_n: int, interpret: bool):
    return _matmul_call(x2, w, b, block_m=block_m, block_n=block_n,
                        interpret=interpret)


def _matmul_p_fwd(x2, w, b, block_m, block_n, interpret):
    return (_matmul_call(x2, w, b, block_m=block_m, block_n=block_n,
                         interpret=interpret), (x2, w))


def _matmul_p_bwd(block_m, block_n, interpret, saved, g):
    x2, w = saved
    zb = jnp.zeros((1, x2.shape[1]), g.dtype)
    dx = _matmul_call(g, w.T, zb, block_m=block_m, block_n=block_n,
                      interpret=interpret)
    zb2 = jnp.zeros((1, g.shape[1]), g.dtype)
    dw = _matmul_call(x2.T, g, zb2, block_m=block_m, block_n=block_n,
                      interpret=interpret).astype(w.dtype)
    db = jnp.sum(g.astype(jnp.float32), axis=0, keepdims=True)
    return dx, dw, db.astype(g.dtype)


_matmul_p.defvjp(_matmul_p_fwd, _matmul_p_bwd)


def qkv(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
        bq: Optional[jax.Array] = None, bk: Optional[jax.Array] = None,
        bv: Optional[jax.Array] = None, *,
        block_m: int = DEFAULT_BLOCK_ROWS,
        block_n: int = DEFAULT_BLOCK_COLS,
        interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused QKV: one tiled GEMM against the concatenated weight.

    x: [..., d]; wq/wk/wv: [d, cols_*].  Returns the three flat
    projections [..., cols_*] (head reshape stays with the caller).
    """
    d = x.shape[-1]
    cq, ck = wq.shape[1], wk.shape[1]
    wcat = jnp.concatenate([wq, wk, wv], axis=1).astype(x.dtype)
    if bq is not None:
        bcat = jnp.concatenate([bq, bk, bv]).astype(x.dtype).reshape(1, -1)
    else:
        bcat = jnp.zeros((1, wcat.shape[1]), x.dtype)
    y2 = _matmul_p(x.reshape(-1, d), wcat, bcat, int(block_m),
                   int(block_n), bool(interpret))
    y = y2.reshape(x.shape[:-1] + (y2.shape[-1],))
    return tuple(jnp.split(y, [cq, cq + ck], axis=-1))


def qkv_ref(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
            bq: Optional[jax.Array] = None,
            bk: Optional[jax.Array] = None,
            bv: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA formulation: three dots + bias epilogues in a SINGLE traced
    expression (one program, epilogues fused) — the CPU path and the
    parity oracle versus the Pallas tiles.

    Deliberately NOT the concatenated-weight GEMM: without a tiled
    kernel to exploit the wider N, XLA:CPU runs the wide GEMM slightly
    slower than three narrow ones and pays a full weight copy for the
    concat plus three slice copies for the split.  The CPU path's win
    over the unfused path is program fusion (one dispatch, fused
    epilogues), so it keeps the GEMM shapes the backend prefers."""
    outs = []
    for w, b in ((wq, bq), (wk, bk), (wv, bv)):
        y = x @ w.astype(x.dtype)
        if b is not None:
            y = y + b.astype(x.dtype)
        outs.append(y)
    return tuple(outs)
