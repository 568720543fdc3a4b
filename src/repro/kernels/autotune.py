"""Block-size autotuner for the Pallas kernels (DESIGN.md §11, §13).

Every kernel call needs block sizes (flash: block_q/block_k, SSD: the
chunk length, fused epilogues: row/col tiles).  The right values depend
on the backend (MXU alignment on TPU, grid-step overhead under the CPU
interpreter), the dtype and the problem shape — so they are resolved
through a table keyed by

    (kernel kind, backend, dtype, shape bucket)

Sequence lengths bucket to powers of two, EXCEPT ragged (non-pow2)
lengths, which keep their own identity under the pow2 roof: a length
that pads and tiles differently must not inherit blocks tuned for the
exact power (the old pow2-only bucket collided e.g. 1000 onto 1024's
entry — and served 1000's partial-tail tiling the block tuned for the
clean shape).  Head/state dims are always keyed exactly.

On an accelerator, blocks come only from the PACKAGED offline table
(``autotune_offline.json`` next to this module — measured entries
checked into the tree, regenerated by ``python -m
repro.kernels.autotune``) and then the deterministic heuristic below,
so a chip run's blocks depend on the checkout alone.  On the CPU a
measured entry wins first: the in-memory cache, then the JSON file
named by ``REPRO_AUTOTUNE_CACHE`` (nothing is persisted without it).

Measured tuning (``tune_flash``/``tune_ssd``/``tune_fused``) runs ONLY
when invoked explicitly or when ``REPRO_AUTOTUNE=1`` — CI and the
zero-recompile warm path always hit the deterministic tables, so
program-cache keys never depend on wall-clock measurements.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

_ENV_PATH = "REPRO_AUTOTUNE_CACHE"
_ENV_ENABLE = "REPRO_AUTOTUNE"

ShapeEntry = Union[int, str]


def _bucket(n: int, floor: int = 16) -> int:
    """Power-of-two roof for a sequence length."""
    b = floor
    while b < n:
        b *= 2
    return b


def shape_bucket(n: int, floor: int = 16) -> str:
    """Bucket label for a sequence length.  Exact powers of two share
    one entry; RAGGED lengths keep their identity under the pow2 roof
    (``"<roof>r<n>"``) so distinct tilings never share a tuned entry."""
    b = _bucket(n, floor)
    return str(b) if n == b else f"{b}r{n}"


def _seq_of(entry: ShapeEntry) -> int:
    """Actual sequence length from a shape-bucket entry (int or str)."""
    return int(str(entry).rsplit("r", 1)[-1])


def _key(kind: str, backend: str, dtype, shape: Tuple[ShapeEntry, ...]
         ) -> str:
    return "|".join([kind, backend, str(jnp.dtype(dtype)),
                     "x".join(str(s) for s in shape)])


# ----------------------------------------------------------------------
# Deterministic offline tables (packaged + heuristic)
# ----------------------------------------------------------------------
_PACKAGED_PATH = os.path.join(os.path.dirname(__file__),
                              "autotune_offline.json")
_PACKAGED: Optional[Dict[str, Dict[str, int]]] = None


def _packaged() -> Dict[str, Dict[str, int]]:
    global _PACKAGED
    if _PACKAGED is None:
        try:
            with open(_PACKAGED_PATH) as f:
                _PACKAGED = {k: {a: int(b) for a, b in v.items()}
                             for k, v in json.load(f).items()}
        except (OSError, ValueError):
            _PACKAGED = {}
    return _PACKAGED


def _offline(kind: str, backend: str, dtype,
             shape: Tuple[ShapeEntry, ...]) -> Dict[str, int]:
    """Deterministic fallback — packaged measured entry if one exists
    for this exact key, else a pure function of (kind, capability,
    bucket) so CI and warm_templates() never depend on wall-clock.

    Compiled backends get 128 — MXU-aligned, small VMEM working set;
    the CPU interpreter gets blocks as large as the bucket allows,
    because per-grid-step overhead dominates it (measured 2-3x over 128
    at 2k sequence).
    """
    pkg = _packaged().get(_key(kind, backend, dtype, shape))
    if pkg is not None:
        return dict(pkg)
    compiled = backend != "cpu"
    seq = _seq_of(shape[0])
    if kind == "flash":
        blk = 128 if compiled else min(512, _bucket(seq))
        return {"block_q": blk, "block_k": blk}
    if kind == "ssd":
        return {"chunk": 128 if compiled else min(128, _bucket(seq))}
    if kind == "fused":
        rows = 128 if compiled else min(512, _bucket(seq))
        return {"block_rows": rows, "block_cols": 128}
    raise KeyError(f"unknown kernel kind {kind!r}")


# ----------------------------------------------------------------------
# The cache
# ----------------------------------------------------------------------
class AutotuneCache:
    """(kind, backend, dtype, bucket) -> block config, with an optional
    persisted JSON table (``path``, default ``$REPRO_AUTOTUNE_CACHE``)
    behind the in-memory dict."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or os.environ.get(_ENV_PATH)
        self._mem: Dict[str, Dict[str, int]] = {}
        self._disk_loaded = False

    # -- persistence ---------------------------------------------------
    def _load_disk(self) -> None:
        if self._disk_loaded or not self.path:
            return
        self._disk_loaded = True
        try:
            with open(self.path) as f:
                table = json.load(f)
            for k, v in table.items():
                self._mem.setdefault(k, {str(a): int(b)
                                         for a, b in v.items()})
        except (OSError, ValueError):
            pass

    def save(self) -> None:
        """Atomically persist the current table (tmp + rename), merged
        over what is already on disk — a fresh process tuning ONE shape
        must not clobber previously persisted entries."""
        if not self.path:
            return
        self._load_disk()
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._mem, f, indent=2, sort_keys=True)
        os.replace(tmp, self.path)

    # -- lookup --------------------------------------------------------
    def peek(self, kind: str, backend: str, dtype,
             shape: Tuple[ShapeEntry, ...]) -> Optional[Dict[str, int]]:
        """Tuned entry from memory or disk, or None.  Offline-table
        fallbacks are NOT consulted (and never stored in ``_mem``, so
        ``save()`` persists only genuinely measured entries — a stale
        snapshot of the offline defaults would shadow future updates)."""
        key = _key(kind, backend, dtype, shape)
        cfg = self._mem.get(key)
        if cfg is None:
            self._load_disk()
            cfg = self._mem.get(key)
        return cfg

    def get(self, kind: str, backend: str, dtype,
            shape: Tuple[ShapeEntry, ...]) -> Dict[str, int]:
        cfg = self.peek(kind, backend, dtype, shape)
        return cfg if cfg is not None else _offline(kind, backend, dtype,
                                                    shape)

    def put(self, kind: str, backend: str, dtype,
            shape: Tuple[ShapeEntry, ...], cfg: Dict[str, int],
            persist: bool = True) -> None:
        self._mem[_key(kind, backend, dtype, shape)] = dict(cfg)
        if persist:
            try:
                self.save()
            except OSError:
                pass               # read-only FS: stay in-memory


_CACHE = AutotuneCache()


def tuning_enabled() -> bool:
    return os.environ.get(_ENV_ENABLE, "") == "1"


def _config(kind: str, backend: str, dtype,
            shape: Tuple[ShapeEntry, ...], tune) -> Dict[str, int]:
    """Measured entries (and REPRO_AUTOTUNE=1 tuning) steer the CPU
    only; an accelerator reads the packaged table and the heuristic."""
    if backend == "cpu":
        cfg = _CACHE.peek(kind, backend, dtype, shape)
        if cfg is None and tuning_enabled():
            cfg = tune()
        if cfg is not None:
            return cfg
    return _offline(kind, backend, dtype, shape)


def flash_config(backend: str, dtype, seq_len: int, head_dim: int
                 ) -> Dict[str, int]:
    return _config("flash", backend, dtype,
                   (shape_bucket(seq_len), head_dim),
                   lambda: tune_flash(backend, dtype, seq_len, head_dim))


def ssd_config(backend: str, dtype, seq_len: int, head_dim: int,
               state: int) -> Dict[str, int]:
    return _config("ssd", backend, dtype,
                   (shape_bucket(seq_len), head_dim, state),
                   lambda: tune_ssd(backend, dtype, seq_len, head_dim,
                                    state))


def fused_config(backend: str, dtype, rows: int, cols: int
                 ) -> Dict[str, int]:
    """Row/col tiles for the fused epilogues (kernels/fused.py); keyed
    by (token-rows bucket, output cols)."""
    return _config("fused", backend, dtype, (shape_bucket(rows), cols),
                   lambda: tune_fused(backend, dtype, rows, cols))


# ----------------------------------------------------------------------
# Measured tuning (explicit or REPRO_AUTOTUNE=1 — never CI's default)
# ----------------------------------------------------------------------
def _time(fn, *args, iters: int = 3) -> float:
    """Min over repeats: the noise-robust estimator — scheduler hiccups
    only ever ADD time, so the minimum is the cleanest measurement (and
    what the roofline's bwd-beats-oracle CI gate compares)."""
    jax.block_until_ready(fn(*args))        # compile outside the clock
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def tune_flash(backend: str, dtype, seq_len: int, head_dim: int, *,
               batch: int = 1, heads: int = 2,
               candidates: Optional[List[int]] = None,
               persist: bool = True) -> Dict[str, int]:
    """Measure fwd+bwd across candidate square blocks; cache the best."""
    from repro.kernels import flash_attention as _fa
    from repro.kernels import ops as _ops
    interp_f = not _ops.kernel_lowers("flash_fwd", backend)
    interp_b = not _ops.kernel_lowers("flash_bwd", backend)
    if candidates is None:
        candidates = [64, 128, 256, 512]
    candidates = sorted({min(c, _bucket(seq_len)) for c in candidates})
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (batch, seq_len, heads, head_dim), dtype)
    k = jax.random.normal(ks[1], (batch, seq_len, heads, head_dim), dtype)
    v = jax.random.normal(ks[2], (batch, seq_len, heads, head_dim), dtype)
    g = jax.random.normal(ks[3], q.shape, dtype)
    best, best_t = None, float("inf")
    for blk in candidates:
        def run(q, k, v, g, blk=blk):
            out, lse = _fa.flash_attention_fwd(
                q, k, v, block_q=blk, block_k=blk, interpret=interp_f)
            return _fa.flash_attention_bwd(
                q, k, v, out, lse, g, block_q=blk, block_k=blk,
                interpret=interp_b)
        t = _time(run, q, k, v, g)
        if t < best_t:
            best, best_t = blk, t
    cfg = {"block_q": best, "block_k": best}
    _CACHE.put("flash", backend, dtype, (shape_bucket(seq_len), head_dim),
               cfg, persist=persist)
    return cfg


def tune_ssd(backend: str, dtype, seq_len: int, head_dim: int, state: int,
             *, batch: int = 1, heads: int = 2,
             candidates: Optional[List[int]] = None,
             persist: bool = True) -> Dict[str, int]:
    """Measure fwd+bwd across candidate chunk lengths; cache the best."""
    from repro.kernels import ops as _ops
    from repro.kernels import ssd as _ssd
    interp_f = not _ops.kernel_lowers("ssd_fwd", backend)
    interp_b = not _ops.kernel_lowers("ssd_bwd", backend)
    if candidates is None:
        candidates = [32, 64, 128, 256]
    candidates = sorted({min(c, _bucket(seq_len)) for c in candidates})
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (batch, seq_len, heads, head_dim), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq_len, heads)))
    A = -jnp.exp(jax.random.normal(ks[2], (heads,)) * 0.5)
    B = jax.random.normal(ks[3], (batch, seq_len, heads, state), dtype)
    C = jax.random.normal(ks[4], (batch, seq_len, heads, state), dtype)
    best, best_t = None, float("inf")
    for chunk in candidates:
        def run(x, dt, A, B, C, chunk=chunk):
            y, st, cst = _ssd.ssd_fwd(x, dt, A, B, C, chunk=chunk,
                                      interpret=interp_f)
            return _ssd.ssd_bwd(x, dt, A, B, C, cst, y, st, chunk=chunk,
                                interpret=interp_b)
        t = _time(run, x, dt, A, B, C)
        if t < best_t:
            best, best_t = chunk, t
    cfg = {"chunk": best}
    _CACHE.put("ssd", backend, dtype,
               (shape_bucket(seq_len), head_dim, state), cfg,
               persist=persist)
    return cfg


def tune_fused(backend: str, dtype, rows: int, cols: int, *,
               candidates: Optional[List[int]] = None,
               persist: bool = True) -> Dict[str, int]:
    """Measure the fused add+RMSNorm fwd+bwd across candidate row
    blocks; cache the best (col tile stays 128 — the fused matmul's N
    tiling is MXU-bound, not occupancy-bound, at assigned widths)."""
    from repro.kernels import fused as _fused
    from repro.kernels import ops as _ops
    interpret = not _ops.kernel_lowers("fused_norm", backend)
    if candidates is None:
        candidates = [64, 128, 256, 512]
    candidates = sorted({min(c, _bucket(rows)) for c in candidates})
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    d = min(cols, 1024)
    x = jax.random.normal(ks[0], (rows, d), dtype)
    r = jax.random.normal(ks[1], (rows, d), dtype)
    w = jax.random.normal(ks[2], (d,), dtype)
    best, best_t = None, float("inf")
    for bm in candidates:
        def run(x, r, w, bm=bm):
            def f(x, r, w):
                res, h = _fused.add_rmsnorm(x, r, w, block_rows=bm,
                                            interpret=interpret)
                return jnp.sum(res) + jnp.sum(h)
            return jax.grad(f, argnums=(0, 1, 2))(x, r, w)
        t = _time(run, x, r, w)
        if t < best_t:
            best, best_t = bm, t
    cfg = {"block_rows": best, "block_cols": 128}
    _CACHE.put("fused", backend, dtype, (shape_bucket(rows), cols), cfg,
               persist=persist)
    return cfg


# ----------------------------------------------------------------------
# Packaged-table regeneration: python -m repro.kernels.autotune
# ----------------------------------------------------------------------
def emit_offline(path: str = _PACKAGED_PATH) -> Dict[str, Dict[str, int]]:
    """Measure the benchmark shapes on the CURRENT backend and write the
    packaged offline table (merging over existing entries for other
    backends/dtypes)."""
    backend = jax.default_backend()
    table: Dict[str, Dict[str, int]] = {}
    try:
        with open(path) as f:
            table = {k: dict(v) for k, v in json.load(f).items()}
    except (OSError, ValueError):
        pass
    dt = jnp.float32
    for seq, hd in [(1024, 64), (2048, 64)]:
        cfg = tune_flash(backend, dt, seq, hd, persist=False)
        table[_key("flash", backend, dt, (shape_bucket(seq), hd))] = cfg
    for seq, p, n in [(1024, 32, 32), (2048, 64, 32)]:
        cfg = tune_ssd(backend, dt, seq, p, n, persist=False)
        table[_key("ssd", backend, dt, (shape_bucket(seq), p, n))] = cfg
    for rows, cols in [(512, 512), (2048, 768)]:
        cfg = tune_fused(backend, dt, rows, cols, persist=False)
        table[_key("fused", backend, dt, (shape_bucket(rows), cols))] = cfg
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    global _PACKAGED
    _PACKAGED = None                       # force reload
    return table


if __name__ == "__main__":
    import sys
    out = emit_offline(sys.argv[1] if len(sys.argv) > 1 else _PACKAGED_PATH)
    print(json.dumps(out, indent=2, sort_keys=True))
