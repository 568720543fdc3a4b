"""Jitted public wrappers around the Pallas kernels (DESIGN.md §11, §13).

Where a kernel runs is decided by the backend, with no silent fallback:

  * CPU has no compiled Pallas.  The attention/SSD kernels run under
    the Pallas interpreter, and the fused epilogues use their XLA
    formulation (an interpreted elementwise kernel would lose to XLA's
    own fusion).
  * Every other backend runs every kernel compiled.  The first query
    per kind on the live backend try-compiles a small representative
    instance (``kernel_lowers``); a kernel that fails to lower raises
    ``KernelLoweringError`` with the compiler's message, so a chip run
    never quietly degrades to the interpreter or the XLA substitute.

Because lowering is resolved at trace time, it is part of program
identity: any cache of traced programs must carry
``backend_signature()`` — (backend, process topology, per-kind lowering
plan) — in its key (the runtime's ProgramCache does; see
runtime/executor.py).

Both kernels carry a ``jax.custom_vjp`` whose backward is ALSO a Pallas
kernel (kernels/flash_attention.py, kernels/ssd.py).  The pure-jnp
oracles (kernels/ref.py) remain the parity references —
``oracle_attention_vjp`` / ``oracle_ssd_vjp`` are the OLD
recompute-through-oracle backward rules, retained for tests and the
roofline benchmark's baseline.

Block sizes default to the autotuner's (backend, dtype, shape-bucket)
table (kernels/autotune.py); explicit ``block_q``/``block_k``/``chunk``
arguments override it.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import flash_attention as _fa
from repro.kernels import fused as _fused
from repro.kernels import ref as _ref
from repro.kernels import ssd as _ssd

#: Kernel structures the probe resolves independently.
KERNEL_KINDS = ("flash_fwd", "flash_bwd", "ssd_fwd", "ssd_bwd",
                "fused_norm", "fused_qkv")

_LOWERING_CACHE: Dict[Tuple[str, str], bool] = {}


class KernelLoweringError(RuntimeError):
    """A Pallas kernel does not compile on an accelerator backend."""


def resolve_backend() -> str:
    return jax.default_backend()


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _probe_flash_fwd():
    q = _aval((1, 128, 2, 64), jnp.float32)
    k = _aval((1, 128, 1, 64), jnp.float32)
    _fa.flash_attention.lower(q, k, k, window=0, block_q=128, block_k=128,
                              interpret=False).compile()


def _probe_flash_bwd():
    q = _aval((1, 128, 2, 64), jnp.float32)
    k = _aval((1, 128, 1, 64), jnp.float32)
    lse = _aval((1, 2, 128), jnp.float32)
    _fa.flash_attention_bwd.lower(q, k, k, q, lse, q, window=0,
                                  block_q=128, block_k=128,
                                  interpret=False).compile()


def _probe_ssd_fwd():
    x = _aval((1, 128, 1, 64), jnp.float32)
    dt = _aval((1, 128, 1), jnp.float32)
    A = _aval((1,), jnp.float32)
    B = _aval((1, 128, 1, 16), jnp.float32)
    _ssd.ssd_fwd.lower(x, dt, A, B, B, chunk=128,
                       interpret=False).compile()


def _probe_ssd_bwd():
    x = _aval((1, 128, 1, 64), jnp.float32)
    dt = _aval((1, 128, 1), jnp.float32)
    A = _aval((1,), jnp.float32)
    B = _aval((1, 128, 1, 16), jnp.float32)
    cst = _aval((1, 1, 1, 64, 16), jnp.float32)
    gst = _aval((1, 1, 64, 16), jnp.float32)
    _ssd.ssd_bwd.lower(x, dt, A, B, B, cst, x, gst, chunk=128,
                       interpret=False).compile()


def _probe_fused_norm():
    x = _aval((128, 64), jnp.float32)
    w = _aval((64,), jnp.float32)

    def f(x, r, w):
        res, h = _fused.add_rmsnorm(x, r, w, block_rows=128,
                                    interpret=False)
        return jnp.sum(res) + jnp.sum(h)

    jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(x, x, w).compile()


def _probe_fused_qkv():
    x = _aval((128, 64), jnp.float32)
    w = _aval((64, 128), jnp.float32)

    def f(x, wq, wk, wv):
        q, k, v = _fused.qkv(x, wq, wk, wv, block_m=128, block_n=128,
                             interpret=False)
        return jnp.sum(q) + jnp.sum(k) + jnp.sum(v)

    jax.jit(jax.grad(f, argnums=(0, 1, 2, 3))).lower(x, w, w, w).compile()


_PROBES = {
    "flash_fwd": _probe_flash_fwd,
    "flash_bwd": _probe_flash_bwd,
    "ssd_fwd": _probe_ssd_fwd,
    "ssd_bwd": _probe_ssd_bwd,
    "fused_norm": _probe_fused_norm,
    "fused_qkv": _probe_fused_qkv,
}


def kernel_lowers(kind: str, backend: Optional[str] = None) -> bool:
    """True iff ``kind`` runs compiled on ``backend``.

    CPU answers False (interpreter / XLA formulation).  Any other
    backend answers True or raises: on the live backend a one-shot
    try-compile of a representative instance must succeed, and its
    failure raises ``KernelLoweringError`` carrying the compiler's
    message.  A TPU that is not the live backend answers True — the
    structures are compiled for a described v5e by
    tests/test_tpu_compile.py."""
    if kind not in KERNEL_KINDS:
        raise ValueError(f"unknown kernel kind {kind!r}")
    backend = backend or resolve_backend()
    if backend == "cpu":
        return False
    key = (kind, backend)
    if key not in _LOWERING_CACHE:
        if backend == jax.default_backend():
            try:
                _PROBES[kind]()
            except Exception as e:
                raise KernelLoweringError(
                    f"Pallas kernel {kind!r} does not compile on "
                    f"{backend}: {e}") from e
        elif backend != "tpu":
            raise KernelLoweringError(
                f"no Pallas lowering is maintained for {backend!r}")
        _LOWERING_CACHE[key] = True
    return _LOWERING_CACHE[key]


def _reset_lowering_cache() -> None:
    """Test hook: forget probe verdicts (e.g. after monkeypatching)."""
    _LOWERING_CACHE.clear()


def lowering_plan(backend: Optional[str] = None
                  ) -> Tuple[Tuple[str, bool], ...]:
    """Per-kind lowering verdicts, in KERNEL_KINDS order (hashable)."""
    backend = backend or resolve_backend()
    return tuple((k, kernel_lowers(k, backend)) for k in KERNEL_KINDS)


def process_topology() -> Tuple[int, int, Tuple[int, ...]]:
    """(process_count, process_index, local device ids) — the process
    placement a program was traced under.  Worker launchers
    (runtime/multihost.py) pin it via ``REPRO_PROC_COUNT`` /
    ``REPRO_PROC_INDEX`` before jax initializes; otherwise it reflects
    ``jax.process_count()`` (1 on a single-controller run)."""
    import os
    count = os.environ.get("REPRO_PROC_COUNT")
    index = os.environ.get("REPRO_PROC_INDEX")
    if count is not None:
        return (int(count), int(index or 0),
                tuple(d.id for d in jax.local_devices()))
    return (jax.process_count(), jax.process_index(),
            tuple(d.id for d in jax.local_devices()))


def backend_signature() -> Tuple:
    """(backend, process topology, per-kind lowering plan) — REQUIRED
    component of any cache key over traced programs that may contain
    these kernels (the bug this fixes: lowering is resolved at trace
    time, so a program cached on the CPU default would run interpreted
    when reused on an accelerator mesh — and, since the probe is per
    kernel, two backends may compile different SUBSETS of the kinds).
    The topology component keeps single-process and multi-process
    compilations of the SAME template from ever colliding in a shared
    cache: a program traced for one process's local device set is not
    interchangeable with one traced for another (ISSUE 10 satellite)."""
    backend = resolve_backend()
    return (backend, process_topology(), lowering_plan(backend))


# ----------------------------------------------------------------------
# Flash attention
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, window: int, block_q: int, block_k: int,
           interpret: bool):
    return _fa.flash_attention(q, k, v, window=window, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def _flash_fwd(q, k, v, window, block_q, block_k, interpret):
    out, lse = _fa.flash_attention_fwd(
        q, k, v, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(window, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _fa.flash_attention_bwd(
        q, k, v, out, lse, g, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, window: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Causal GQA attention with a Pallas forward AND backward.

    q: [B, S, H, D]; k/v: [B, S, KV, D].  Block sizes default to the
    autotuner's choice for (backend, dtype, S-bucket, D).
    """
    backend = resolve_backend()
    if block_q is None or block_k is None:
        cfg = autotune.flash_config(backend, q.dtype, q.shape[1],
                                    q.shape[3])
        block_q = block_q or cfg["block_q"]
        block_k = block_k or cfg["block_k"]
    compiled = (kernel_lowers("flash_fwd", backend)
                and kernel_lowers("flash_bwd", backend))
    return _flash(q, k, v, window, block_q, block_k, not compiled)


# ----------------------------------------------------------------------
# SSD (Mamba2 chunked scan)
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_p(x, dt, A, B, C, chunk: int,
           interpret: bool) -> Tuple[jax.Array, jax.Array]:
    return _ssd.ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dt, A, B, C, chunk, interpret):
    y, state, cstates = _ssd.ssd_fwd(x, dt, A, B, C, chunk=chunk,
                                     interpret=interpret)
    return (y, state), (x, dt, A, B, C, cstates)


def _ssd_bwd(chunk, interpret, res, g):
    x, dt, A, B, C, cstates = res
    gy, gstate = g
    return _ssd.ssd_bwd(x, dt, A, B, C, cstates, gy,
                        gstate.astype(jnp.float32), chunk=chunk,
                        interpret=interpret)


_ssd_p.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C,
        chunk: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD with a Pallas forward AND backward.

    x: [b,S,H,P]; dt: [b,S,H]; A: [H]; B/C: [b,S,H,N].  Returns
    (y, final_state).  ``chunk`` defaults to the autotuner's choice.
    """
    backend = resolve_backend()
    if chunk is None:
        chunk = autotune.ssd_config(backend, x.dtype, x.shape[1],
                                    x.shape[3], B.shape[-1])["chunk"]
    compiled = (kernel_lowers("ssd_fwd", backend)
                and kernel_lowers("ssd_bwd", backend))
    return _ssd_p(x, dt, A, B, C, chunk, not compiled)


# ----------------------------------------------------------------------
# Fused stage epilogues (kernels/fused.py)
# ----------------------------------------------------------------------
def fused_add_rmsnorm(x, r, w, eps: float = 1e-6
                      ) -> Tuple[jax.Array, jax.Array]:
    """Fused (res, h) = (x + r, rms_norm(w, x + r)).

    The compiled Pallas kernel on an accelerator; on the CPU the
    single-expression XLA formulation (an INTERPRETED Pallas elementwise
    kernel would lose to XLA's own fusion).  ``w`` must already be in
    x.dtype.
    """
    backend = resolve_backend()
    if kernel_lowers("fused_norm", backend):
        rows = x.size // x.shape[-1]
        cfg = autotune.fused_config(backend, x.dtype, rows, x.shape[-1])
        return _fused.add_rmsnorm(x, r, w, eps=eps,
                                  block_rows=cfg["block_rows"],
                                  interpret=False)
    return _fused.add_rmsnorm_ref(x, r, w, eps=eps)


def fused_qkv(x, wq, wk, wv, bq=None, bk=None, bv=None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused QKV projection, one program either way: on an accelerator,
    Pallas tiles over the concatenated weight (one wide GEMM + bias
    epilogue); on the CPU, a single XLA program of three dots with fused
    bias epilogues (XLA:CPU prefers the narrow GEMM shapes — see
    fused.qkv_ref)."""
    backend = resolve_backend()
    if kernel_lowers("fused_qkv", backend):
        rows = x.size // x.shape[-1]
        cols = wq.shape[1] + wk.shape[1] + wv.shape[1]
        cfg = autotune.fused_config(backend, x.dtype, rows, cols)
        return _fused.qkv(x, wq, wk, wv, bq, bk, bv,
                          block_m=cfg["block_rows"],
                          block_n=cfg["block_cols"], interpret=False)
    return _fused.qkv_ref(x, wq, wk, wv, bq, bk, bv)


# ----------------------------------------------------------------------
# Retained oracle backward rules (parity references + bench baselines)
# ----------------------------------------------------------------------
def oracle_attention_vjp(q, k, v, g, window: int = 0):
    """The pre-§11 backward: recompute the forward through the pure-jnp
    oracle and backprop through it (O(S²) score materialization)."""
    _, vjp = jax.vjp(
        lambda q, k, v: _ref.attention_ref(q, k, v, window=window), q, k, v)
    return vjp(g)


def oracle_ssd_vjp(x, dt, A, B, C, g):
    """The pre-§11 backward: recompute through the per-timestep scan
    oracle and backprop through it (S sequential steps)."""
    _, vjp = jax.vjp(lambda *a: _ref.ssd_ref(*a), x, dt, A, B, C)
    return vjp(g)
