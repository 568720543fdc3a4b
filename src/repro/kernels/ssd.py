"""Mamba2 SSD chunked scan — Pallas kernels (forward AND backward) with
an explicitly SEQUENTIAL chunk axis.

Grid (batch, heads, chunks), built through ``checked_pallas_call``
(kernels/gridcheck.py) with the chunk axis declared sequential and the
inter-chunk SSM state carried in VMEM scratch along it:
``dimension_semantics=("parallel", "parallel", "arbitrary")``, so batch
and heads may still be distributed while the [P, N] fp32 recurrence
costs no HBM round-trips.

Blocks are head-major (``[b, H, S, *]``, transposed outside the call)
with ``dt`` as a ``[b, H, 1, S]`` lane row: Mosaic tiles the last two
block dims (8, 128), so a size-1 head axis cannot sit second-minor.
Mosaic has no ``cumsum``; cumulative sums are triangular matmuls.

Per chunk the kernel computes, entirely in VMEM:
    cum      = cumsum(dt * A)                       [Q,1]
    y_intra  = ((C B^T) ∘ decay ∘ dt) x             [Q,P]  (masked lower-tri)
    y_inter  = (C ∘ exp(cum)) state^T               [Q,P]
    state   <- state * exp(cum_Q) + (x ∘ w_last)^T B [P,N]

Block shapes: Q = chunk length (default 128 — MXU-aligned), P = head dim,
N = SSM state size.  The working set Q*Q + Q*(P+2N) fp32 stays well under
VMEM for every assigned config (mamba2: P=64, N=128; hymba: P=64, N=16).

The backward mirrors the recurrence in REVERSE chunk order (index maps
c -> nc-1-c), carrying the state cotangent dS in the same scratch slot
the forward carries the state in — the ONLY cross-iteration state.  The
scalar dA reduction is a per-chunk partial output ([b, H, nc, 1, 1],
one block per grid cell — single-writer) summed outside: the kernel has no
finalize step and no write that depends on grid position.  It is
recompute-free in the flash-attention sense: the forward saves only the
[P, N] state at each chunk BOUNDARY (``ssd_fwd``'s third output, S/Q of
them) and every intra-chunk quantity (cum, decay, W) is rebuilt
blockwise in VMEM — never the O(S·Q) full set.  All decay-product terms
mask with ``jnp.where(tri, ..., 0)`` AFTER the multiply: above-diagonal
decays can overflow to inf and 0*inf would poison the block with NaNs.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gridcheck import checked_pallas_call

DEFAULT_CHUNK = 128


def _tri(chunk: int):
    """(lower, upper) [Q, Q] triangles: lower[i, j] = i >= j."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return ii >= jj, ii <= jj


def _scalar(t):
    """[1, 1] tile -> scalar.  Mosaic cannot broadcast a [1, 1] vector
    along sublanes and lanes at once; a scalar splats anywhere."""
    return jnp.sum(t)


def _tri_matvec(mask, v):
    """``where(mask, 1, 0) @ v`` at full fp32 precision — a cumulative
    sum of the [Q, 1] column ``v`` (Mosaic has no cumsum primitive)."""
    return jax.lax.dot_general(jnp.where(mask, 1.0, 0.0), v,
                               (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref,
                *rest, chunk: int):
    # the fwd-for-bwd variant adds a cstates output (the state ENTERING
    # each chunk); the plain forward pays nothing for it
    if len(rest) == 2:
        cstates_ref, state_scratch = rest
    else:
        cstates_ref, (state_scratch,) = None, rest
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scratch[...] = jnp.zeros_like(state_scratch)

    x = x_ref[0, 0].astype(jnp.float32)                # [Q, P]
    dt_row = dt_ref[0, 0].astype(jnp.float32)          # [1, Q]
    dt = dt_row.reshape(chunk, 1)                      # [Q, 1]
    A = _scalar(a_ref[0])                              # (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)               # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)               # [Q, N]

    tri, _ = _tri(chunk)
    cum = _tri_matvec(tri, dt * A)                     # [Q, 1] cumsum

    # intra-chunk: W[i,j] = exp(cum_i - cum_j) * (C_i . B_j) * dt_j, j <= i
    decay = jnp.exp(cum - cum.reshape(1, chunk))       # [Q, Q]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # [Q, Q]
    w = jnp.where(tri, cb * decay, 0.0) * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())))     # [Q, P]

    # inter-chunk: y += (C * exp(cum)) @ state^T
    state = state_scratch[...]                         # [P, N]
    if cstates_ref is not None:
        cstates_ref[0, 0, 0] = state                   # bwd residual
    c_scaled = Cm * jnp.exp(cum)                       # [Q, N]
    y = y + jax.lax.dot_general(c_scaled, state,
                                (((1,), (1,)), ((), ())))        # [Q, P]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state * exp(cum_Q) + (x ∘ w_last)^T @ B
    cum_last = _scalar(cum[chunk - 1:, :])
    w_last = jnp.exp(cum_last - cum) * dt              # [Q, 1]
    xw = x * w_last                                    # [Q, P]
    new_state = (state * jnp.exp(cum_last)
                 + jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ()))))
    state_scratch[...] = new_state
    state_ref[0, 0] = new_state


def _pad_seq(t: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))


def _head_major(x, dt, B, C, pad: int):
    """[b, S, H, *] -> [b, H, S_p, *] and dt [b, S, H] -> [b, H, 1, S_p]:
    Mosaic tiles the last two block dims (8, 128), so the head axis
    cannot sit second-minor with block size 1; dt rides as a lane row."""
    if pad:
        x, dt, B, C = (_pad_seq(t, pad) for t in (x, dt, B, C))
    tr = lambda t: t.transpose(0, 2, 1, 3)
    return tr(x), dt.transpose(0, 2, 1)[:, :, None, :], tr(B), tr(C)


def _ssd_call(x, dt, A, B, C, *, chunk: int, interpret: bool,
              with_cstates: bool):
    b, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, max(S, 8))
    pad = (-S) % chunk
    S_p = S + pad
    nc = S_p // chunk
    xt, dtt, Bt, Ct = _head_major(x, dt, B, C, pad)
    a3 = A.reshape(H, 1, 1)

    seq = lambda i, h, c: (i, h, c, 0)
    out_specs = [
        pl.BlockSpec((1, 1, chunk, P), seq),
        # final state: every chunk writes the same block — legal only
        # because axis 2 is declared sequential (last write wins)
        pl.BlockSpec((1, 1, P, N), lambda i, h, c: (i, h, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, H, S_p, P), x.dtype),
        jax.ShapeDtypeStruct((b, H, P, N), jnp.float32),
    ]
    if with_cstates:
        out_specs.append(
            pl.BlockSpec((1, 1, 1, P, N), lambda i, h, c: (i, h, c, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, H, nc, P, N), jnp.float32))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    outs = checked_pallas_call(
        "ssd_fwd", kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), seq),
            pl.BlockSpec((1, 1, 1, chunk), lambda i, h, c: (i, h, 0, c)),
            pl.BlockSpec((1, 1, 1), lambda i, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), seq),
            pl.BlockSpec((1, 1, chunk, N), seq),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        sequential_axes=(2,),
        scratch_carry_axes=(2,),
    )(xt, dtt, a3, Bt, Ct)
    y = outs[0][:, :, :S].transpose(0, 2, 1, 3)
    return y, outs[1], (outs[2] if with_cstates else None)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, *, chunk: int = DEFAULT_CHUNK,
        interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD.  x: [b,S,H,P]; dt: [b,S,H]; A: [H]; B/C: [b,S,H,N].

    Returns (y [b,S,H,P], final_state [b,H,P,N] fp32).
    """
    y, state, _ = _ssd_call(x, dt, A, B, C, chunk=chunk,
                            interpret=interpret, with_cstates=False)
    return y, state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_fwd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, *, chunk: int = DEFAULT_CHUNK,
            interpret: bool = False
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forward that also returns the chunk-boundary states
    (``cstates [b, H, nc, P, N]`` fp32, the state ENTERING each chunk) —
    the only residual the backward kernel needs beyond the inputs."""
    return _ssd_call(x, dt, A, B, C, chunk=chunk, interpret=interpret,
                     with_cstates=True)


# ----------------------------------------------------------------------
# Backward kernel (reverse chunk order, sequential dstate carry)
# ----------------------------------------------------------------------
def _ssd_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, gy_ref,
                    gstate_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                    dstate_scratch, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        dstate_scratch[...] = gstate_ref[0, 0]

    x = x_ref[0, 0].astype(jnp.float32)                # [Q, P]
    dt_row = dt_ref[0, 0].astype(jnp.float32)          # [1, Q]
    dt = dt_row.reshape(chunk, 1)                      # [Q, 1]
    A = _scalar(a_ref[0])
    Bm = b_ref[0, 0].astype(jnp.float32)               # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)               # [Q, N]
    S0 = s0_ref[0, 0, 0]                               # [P, N]
    G = gy_ref[0, 0].astype(jnp.float32)               # [Q, P]
    dS1 = dstate_scratch[...]                          # [P, N]

    tri, upper = _tri(chunk)
    cum = _tri_matvec(tri, dt * A)                     # [Q, 1] cumsum
    decay = jnp.exp(cum - cum.reshape(1, chunk))       # [Q, Q]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # [Q, Q]
    W = jnp.where(tri, cb * decay, 0.0) * dt_row       # [Q, Q]
    ecum = jnp.exp(cum)                                # [Q, 1]
    Cs = Cm * ecum                                     # [Q, N]
    cum_last = _scalar(cum[chunk - 1:, :])
    eQ = jnp.exp(cum_last)
    w_last = jnp.exp(cum_last - cum) * dt              # [Q, 1]

    # --- y_intra = W x ------------------------------------------------
    dW = jax.lax.dot_general(G, x, (((1,), (1,)), ((), ())))      # [Q, Q]
    # --- S1 = S0 * eQ + (x ∘ w_last)^T B ------------------------------
    BH = jax.lax.dot_general(Bm, dS1, (((1,), (1,)), ((), ())))   # [Q, P]
    dx = (jax.lax.dot_general(W, G, (((0,), (0,)), ((), ())))     # W^T G
          + BH * w_last)
    dx_ref[0, 0] = dx.astype(dx_ref.dtype)

    # d(cb) = tri * dW * decay * dt_j  (mask AFTER multiply: above-diag
    # decay can be inf; 0 * inf = NaN)
    dcb = jnp.where(tri, dW * decay, 0.0) * dt_row                # [Q, Q]
    GS0 = jax.lax.dot_general(G, S0, (((1,), (0,)), ((), ())))    # [Q, N]
    xdS1 = jax.lax.dot_general(x, dS1, (((1,), (0,)), ((), ())))  # [Q, N]
    dC = (jax.lax.dot_general(dcb, Bm, (((1,), (0,)), ((), ())))
          + GS0 * ecum)
    dB = (jax.lax.dot_general(dcb, Cm, (((0,), (0,)), ((), ())))
          + xdS1 * w_last)
    dc_ref[0, 0] = dC.astype(dc_ref.dtype)
    db_ref[0, 0] = dB.astype(db_ref.dtype)

    # --- cum cotangent ------------------------------------------------
    TW = dW * W                                        # [Q, Q], tri via W
    dcum = (jnp.sum(TW, axis=1, keepdims=True)         # decay's +cum_i
            - jnp.sum(TW, axis=0).reshape(chunk, 1)    # decay's -cum_j
            + jnp.sum(GS0 * Cs, axis=1, keepdims=True))  # y_inter's e^cum
    dw = jnp.sum(xdS1 * Bm, axis=1, keepdims=True)     # [Q, 1] d(w_last)
    V = dw * w_last
    dcum = dcum - V                                    # w_last's -cum_j
    # cum_{Q-1} terms: S1's e^{cum_Q} and w_last's +cum_Q
    last = (jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            == chunk - 1)
    dcum = dcum + jnp.where(
        last, jnp.sum(dS1 * S0) * eQ + jnp.sum(V), 0.0)

    # --- dt cotangent -------------------------------------------------
    ddt = (jnp.sum(jnp.where(tri, dW * decay, 0.0) * cb,
                   axis=0).reshape(chunk, 1)           # W's dt_j factor
           + dw * jnp.exp(cum_last - cum))             # w_last's dt
    # cumsum backward: da_i = sum_{i' >= i} dcum_{i'}
    da = _tri_matvec(upper, dcum)
    ddt = ddt + da * A
    ddt_ref[0, 0] = ddt.reshape(1, chunk).astype(ddt_ref.dtype)
    # dA partial for THIS chunk — one [1, 1] block per grid cell
    # (single-writer; the cross-chunk/batch sum happens outside)
    da_ref[0, 0, 0] = jnp.sum(da * dt, axis=0, keepdims=True)

    # --- state cotangent for the PRECEDING chunk ----------------------
    dstate_scratch[...] = (eQ * dS1
                           + jax.lax.dot_general(G, Cs,
                                                 (((0,), (0,)), ((), ()))))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bwd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, cstates: jax.Array, gy: jax.Array,
            gstate: jax.Array, *, chunk: int = DEFAULT_CHUNK,
            interpret: bool = False):
    """Reverse-chunk SSD backward.

    Inputs are the forward primals, the saved chunk-boundary states and
    the cotangents (gy for y, gstate for the final state).  Returns
    (dx, ddt, dA, dB, dC) with the primals' layouts and dtypes.
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, max(S, 8))
    pad = (-S) % chunk
    S_p = S + pad
    nc = S_p // chunk
    xt, dtt, Bt, Ct = _head_major(x, dt, B, C, pad)
    gyt = _pad_seq(gy, pad).transpose(0, 2, 1, 3)
    a3 = A.reshape(H, 1, 1)

    seq_p = lambda i, h, c: (i, h, nc - 1 - c, 0)      # reversed chunks
    row_p = lambda i, h, c: (i, h, 0, nc - 1 - c)
    kernel = functools.partial(_ssd_bwd_kernel, chunk=chunk)
    dx, ddt, dB, dC, dA5 = checked_pallas_call(
        "ssd_bwd", kernel,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), seq_p),
            pl.BlockSpec((1, 1, 1, chunk), row_p),
            pl.BlockSpec((1, 1, 1), lambda i, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), seq_p),
            pl.BlockSpec((1, 1, chunk, N), seq_p),
            pl.BlockSpec((1, 1, 1, P, N),
                         lambda i, h, c: (i, h, nc - 1 - c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, P), seq_p),
            pl.BlockSpec((1, 1, P, N), lambda i, h, c: (i, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), seq_p),
            pl.BlockSpec((1, 1, 1, chunk), row_p),
            pl.BlockSpec((1, 1, chunk, N), seq_p),
            pl.BlockSpec((1, 1, chunk, N), seq_p),
            pl.BlockSpec((1, 1, 1, 1, 1),
                         lambda i, h, c: (i, h, nc - 1 - c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, S_p, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, 1, S_p), dt.dtype),
            jax.ShapeDtypeStruct((b, H, S_p, N), B.dtype),
            jax.ShapeDtypeStruct((b, H, S_p, N), C.dtype),
            jax.ShapeDtypeStruct((b, H, nc, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((P, N), jnp.float32),           # dstate carry
        ],
        interpret=interpret,
        sequential_axes=(2,),
        scratch_carry_axes=(2,),
    )(xt, dtt, a3, Bt, Ct, cstates, gyt, gstate)
    dA = jnp.sum(dA5, axis=(0, 2, 3, 4)).astype(A.dtype)
    seq_major = lambda t: t[:, :, :S].transpose(0, 2, 1, 3)
    return (seq_major(dx), ddt[:, :, 0, :S].transpose(0, 2, 1), dA,
            seq_major(dB), seq_major(dC))
