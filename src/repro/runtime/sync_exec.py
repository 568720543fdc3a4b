"""Compiled bucketed gradient-sync data plane (DESIGN.md §10).

The planner's sync plan (core/sync.py) says WHAT synchronizes — layer
buckets with identical peer structure, deepest-first.  Until this module
the runtime ignored it and walked an eager per-layer ``jax.tree.map``
chain: O(layers x replicas) tiny dispatches per step for the weighted
average, plus a second O(layers x leaves) chain for the global-norm
clip, plus one update-program call per layer per replica.  This module
executes the plan instead:

  * each ``SyncBucket``'s layers are FLATTENED into one contiguous fp32
    buffer (``pack``), and sync + norm + clip + AdamW run as a small
    family of cached, donated programs keyed by (bucket structure,
    codec) — one collective-equivalent weighted reduction per bucket;
  * buckets are issued deepest-first (the plan's order), so on real
    hardware the reduction of deep buckets overlaps the remaining
    backward — the same schedule `core.sync.SyncCostModel` prices;
  * when a bucket's peer group spans pods, the reduction runs the
    two-level hierarchical path: partial sums within each pod (ICI),
    one exchange across pod leads (DCN), broadcast back.  Numerically
    this only reassociates the sum; every replica still consumes the
    SAME reduced buffer, so replicas stay bit-identical;
  * the wire codec (runtime/compression.py) encodes each replica's
    weighted contribution per bucket — one int8 scale per bucket — with
    per-(bucket, replica) error-feedback residuals.  Residuals are keyed
    by bucket signature and dropped on reconfiguration (a stale residual
    would shape-mismatch the new layout);
  * program identity depends only on the bucket's LAYER STRUCTURE (the
    per-layer leaf specs), not its depth, node placement, or replica
    count — all block layers look alike, so ``warm()`` covers every
    bucket layout any reachable instance set can produce by cap-splitting
    every span between template stage boundaries (`core.sync.split_span`
    is shared with ``build_sync_plan``), keeping reconfiguration
    zero-compile for bucket programs too.

``perlayer_sync`` keeps the original eager per-layer path verbatim: it
is the parity oracle — bitwise-equal synced gradients for codec="none"
(same multiply/add order per element), bounded error for bf16/int8.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Hashable, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.sync import SyncBucket, split_span
from repro.optim import adamw
from repro.runtime.compression import (CODEC_WIRE, ErrorFeedback,
                                       decode_flat, encode_flat)
from repro.runtime.executor import ProgramCache, tree_spec

LayerState = Dict[str, Any]


# ----------------------------------------------------------------------
# The eager per-layer oracle (the pre-data-plane runtime path, verbatim)
# ----------------------------------------------------------------------
def perlayer_sync(all_grads: Sequence[Dict[int, Any]],
                  weights: Sequence[float], num_layers: int
                  ) -> Dict[int, Any]:
    """Layer-granular cross-replica weighted average (Figure 9): the
    readable spec of what the bucketed plane fuses.  Weights are
    minibatch sizes, so the result is the global-batch mean gradient."""
    wsum = float(sum(weights))
    synced: Dict[int, Any] = {}
    for l in range(num_layers):
        contribs = [(w / wsum, g[l]) for w, g in zip(weights, all_grads)
                    if l in g]
        acc = jax.tree.map(lambda t: t * contribs[0][0], contribs[0][1])
        for w, g in contribs[1:]:
            acc = jax.tree.map(lambda a, t: a + t * w, acc, g)
        synced[l] = acc
    return synced


def perlayer_global_sumsq(synced: Dict[int, Any], num_layers: int
                          ) -> jax.Array:
    """Sum of squared gradient elements across the WHOLE model, per-leaf
    accumulation order (the global-norm-clip input)."""
    sq = jnp.zeros((), jnp.float32)
    for l in range(num_layers):
        for t in jax.tree.leaves(synced[l]):
            sq = sq + jnp.sum(jnp.square(t.astype(jnp.float32)))
    return sq


# ----------------------------------------------------------------------
# Bucket execution plan
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class BucketExec:
    """One sync bucket bound for execution."""

    lids: Tuple[int, ...]                       # ascending layer ids
    specs: Tuple                                # program identity (structure)
    n: int                                      # flat fp32 element count
    pod_groups: Tuple[Tuple[int, ...], ...]     # replica indices per pod

    @property
    def signature(self) -> Tuple:
        """Bucket signature: the residual/staging key component — the
        layer span AND its structure (a reconfiguration that changes
        either invalidates carried error-feedback residuals)."""
        return (self.lids, self.n)

    @property
    def hierarchical(self) -> bool:
        return len(self.pod_groups) > 1


@dataclasses.dataclass
class SyncReduceResult:
    """Everything the reduce phase produced, with NO state mutated:
    the optimizer commit (and the residual commit that rides with it)
    happens only after the caller's sync-phase fault seam passes."""

    flats: List[jax.Array]                      # per bucket, reduced
    sumsqs: List[jax.Array]                     # per bucket, scalar
    staged_residuals: Dict[Hashable, jax.Array]


def _aval_size(aval) -> int:
    return int(math.prod(aval.shape)) if aval.shape else 1


class BucketedSync:
    """The compiled bucketed sync/clip/update tail.

    Owns no layer state — it reads per-replica gradient dicts and writes
    ``run.states`` through donated update programs.  All executables
    live in the trainer's ProgramCache, so the §8 zero-recompilation
    contract extends to the sync tail.
    """

    def __init__(self, cache: ProgramCache, opt_cfg: adamw.AdamWConfig,
                 layer_avals: Sequence[Any], codec: str = "none"):
        if codec not in CODEC_WIRE:
            raise ValueError(f"unknown codec {codec!r}")
        self.cache = cache
        self.opt_cfg = opt_cfg
        self.layer_avals = list(layer_avals)
        self.codec = codec
        self.ef = ErrorFeedback(codec)

    # ------------------------------------------------------------------
    # Plan construction
    # ------------------------------------------------------------------
    def exec_plan(self, sync_plan: Sequence[SyncBucket],
                  replica_pods: Optional[Sequence[Sequence[Hashable]]] = None
                  ) -> List[BucketExec]:
        """Bind the planner's buckets for execution.  ``replica_pods[b]``
        gives, per bucket, the pod of each replica's lead owner — the
        grouping for the hierarchical ICI/DCN path; None means one pod
        (flat chain)."""
        out: List[BucketExec] = []
        for i, b in enumerate(sync_plan):
            lids = tuple(range(b.layer_start, b.layer_end))
            specs = tuple(tree_spec(self.layer_avals[l]) for l in lids)
            n = sum(_aval_size(a) for l in lids
                    for a in jax.tree.leaves(self.layer_avals[l]))
            pods = (replica_pods[i] if replica_pods is not None else None)
            out.append(BucketExec(lids=lids, specs=specs, n=n,
                                  pod_groups=self._group(pods)))
        return out

    @staticmethod
    def _group(pods: Optional[Sequence[Hashable]]
               ) -> Tuple[Tuple[int, ...], ...]:
        if not pods:
            return ((),)        # filled lazily per replica count at reduce
        groups: List[List[int]] = []
        index: Dict[Hashable, int] = {}
        for r, pod in enumerate(pods):
            if pod not in index:
                index[pod] = len(groups)
                groups.append([])
            groups[index[pod]].append(r)
        return tuple(tuple(g) for g in groups)

    # ------------------------------------------------------------------
    # Program family (all cached; keys carry structure, never placement)
    # ------------------------------------------------------------------
    def _layer_state_aval(self, l: int):
        aval = self.layer_avals[l]
        f32 = lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32)  # noqa: E731
        return {"p": aval, "m": jax.tree.map(f32, aval),
                "v": jax.tree.map(f32, aval)}

    def _pack_prog(self, b: BucketExec) -> Callable:
        key = ("bpack", b.specs)

        def build() -> Callable:
            def bucket_pack(layers):
                parts = [jnp.ravel(leaf).astype(jnp.float32)
                         for lt in layers for leaf in jax.tree.leaves(lt)]
                return jnp.concatenate(parts)
            avals = [self.layer_avals[l] for l in b.lids]
            return jax.jit(bucket_pack).lower(avals).compile()

        return self.cache.get_or_build(key, build)

    def _scale_prog(self, n: int) -> Callable:
        key = ("bscale", n)

        def build() -> Callable:
            def bucket_scale(x, w):
                return x * w
            flat = jax.ShapeDtypeStruct((n,), jnp.float32)
            w = jax.ShapeDtypeStruct((), jnp.float32)
            return jax.jit(bucket_scale).lower(flat, w).compile()

        return self.cache.get_or_build(key, build)

    def _add_prog(self, n: int) -> Callable:
        key = ("badd", n)

        def build() -> Callable:
            def bucket_add(acc, x):
                return acc + x
            flat = jax.ShapeDtypeStruct((n,), jnp.float32)
            return jax.jit(bucket_add,
                           donate_argnums=(0,)).lower(flat, flat).compile()

        return self.cache.get_or_build(key, build)

    def _sumsq_prog(self, n: int) -> Callable:
        key = ("bsumsq", n)

        def build() -> Callable:
            def bucket_sumsq(x):
                return jnp.sum(jnp.square(x))
            flat = jax.ShapeDtypeStruct((n,), jnp.float32)
            return jax.jit(bucket_sumsq).lower(flat).compile()

        return self.cache.get_or_build(key, build)

    def _ef_prog(self, n: int) -> Callable:
        """codec roundtrip + error feedback for one replica's weighted
        bucket contribution: what goes on the wire, and what the codec
        lost (carried into the next step)."""
        key = ("bef", self.codec, n)
        codec = self.codec

        def build() -> Callable:
            def bucket_ef(c, res):
                c = c + res
                sent = decode_flat(encode_flat(c, codec), codec)
                return sent, c - sent
            flat = jax.ShapeDtypeStruct((n,), jnp.float32)
            return jax.jit(bucket_ef, donate_argnums=(0,)).lower(
                flat, flat).compile()

        return self.cache.get_or_build(key, build)

    def _zeros(self, n: int) -> jax.Array:
        return jnp.zeros((n,), jnp.float32)

    def _update_prog(self, b: BucketExec) -> Callable:
        """Donated per-bucket AdamW: unflatten the reduced buffer back
        into the bucket's layers and update them all in ONE program —
        the bucketed replacement for per-layer update calls."""
        key = ("bupdate", b.specs)

        def build() -> Callable:
            layer_cfg = dataclasses.replace(self.opt_cfg, clip_norm=0.0)

            def bucket_update(states, flat, scale, step):
                out, off = [], 0
                for st in states:
                    leaves, treedef = jax.tree_util.tree_flatten(st["p"])
                    gl = []
                    for leaf in leaves:
                        sz = int(math.prod(leaf.shape)) if leaf.shape else 1
                        gl.append(flat[off:off + sz].reshape(leaf.shape)
                                  * scale)
                        off += sz
                    g = jax.tree_util.tree_unflatten(treedef, gl)
                    new_p, new_opt, _ = adamw.apply(
                        layer_cfg, st["p"], g,
                        adamw.AdamWState(step, st["m"], st["v"]))
                    out.append({"p": new_p, "m": new_opt.m, "v": new_opt.v})
                return out

            states_aval = [self._layer_state_aval(l) for l in b.lids]
            flat_aval = jax.ShapeDtypeStruct((b.n,), jnp.float32)
            scalar = jax.ShapeDtypeStruct((), jnp.float32)
            step_aval = jax.ShapeDtypeStruct((), jnp.int32)
            return jax.jit(bucket_update, donate_argnums=(0,)).lower(
                states_aval, flat_aval, scalar, step_aval).compile()

        return self.cache.get_or_build(key, build)

    # ------------------------------------------------------------------
    # Warming
    # ------------------------------------------------------------------
    def bind_plan(self, plan: Sequence[BucketExec]) -> None:
        """Ensure every program the CURRENT plan needs is cached."""
        for b in plan:
            self._pack_prog(b)
            self._scale_prog(b.n)
            self._add_prog(b.n)
            self._sumsq_prog(b.n)
            self._update_prog(b)
            if self.codec != "none":
                self._ef_prog(b.n)
                self._zeros(b.n)        # residual-init fill, shape-keyed

    def warm(self, templates: Iterable[Any], layer_bytes: Sequence[int],
             bucket_cap_bytes: int) -> None:
        """Precompile bucket programs for EVERY layout any reachable
        instance set can produce: bucket spans are cap-splits of runs
        between peer-structure change points, and every change point is
        a stage boundary of some template — so cap-splitting every span
        between template boundary pairs (same `split_span` the planner
        uses) over-covers the reachable set.  Structure-keyed programs
        collapse the span count to a handful of distinct compiles."""
        num_layers = len(self.layer_avals)
        bounds = {0, num_layers}
        for t in templates:
            for st in t.stages:
                bounds.add(int(st.layer_start))
                bounds.add(int(st.layer_end))
        pts = sorted(p for p in bounds if 0 <= p <= num_layers)
        seen: set = set()
        for i, s in enumerate(pts):
            for e in pts[i + 1:]:
                for (lo, hi) in split_span(s, e, layer_bytes,
                                           bucket_cap_bytes):
                    if (lo, hi) in seen:
                        continue
                    seen.add((lo, hi))
        for (lo, hi) in sorted(seen):
            fake = SyncBucket(lo, hi, ((),), 0)
            self.bind_plan(self.exec_plan([fake]))

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------
    def contributions(self, plan: Sequence[BucketExec],
                      grads_by_replica: Dict[int, Dict[int, Any]],
                      weights: Sequence[float]
                      ) -> Tuple[Dict[int, List[jax.Array]],
                                 Dict[Hashable, jax.Array]]:
        """Per-replica weighted bucket contributions: pack each bucket's
        layer grads into one flat fp32 buffer, scale by the replica's
        batch weight, and (if a codec is configured) run the error-
        feedback roundtrip.  ``grads_by_replica`` maps GLOBAL replica
        index -> that replica's per-layer grads — a multi-process worker
        passes only the replicas it executes; single-process passes all.
        Returns ({replica: [flat per bucket]}, staged residuals).  These
        buffers are exactly what crosses the wire between processes."""
        wsum = float(sum(weights))
        w_dev = {r: jnp.asarray(weights[r] / wsum, jnp.float32)
                 for r in grads_by_replica}
        out: Dict[int, List[jax.Array]] = {r: [] for r in grads_by_replica}
        staged: Dict[Hashable, jax.Array] = {}
        for b in plan:
            pack = self._pack_prog(b)
            for r, g in grads_by_replica.items():
                missing = [l for l in b.lids if l not in g]
                assert not missing, \
                    f"replica {r} lacks grads for layers {missing}"
                flat = pack([g[l] for l in b.lids])
                c = self._scale_prog(b.n)(flat, w_dev[r])
                if self.codec != "none":
                    res_key = ("ef", b.signature, self.codec, r)
                    res = self.ef.get(res_key)
                    if res is None:
                        res = self._zeros(b.n)
                    c, new_res = self._ef_prog(b.n)(c, res)
                    staged[res_key] = new_res
                out[r].append(c)
        return out, staged

    def combine(self, plan: Sequence[BucketExec],
                contribs_by_replica: Dict[int, Sequence[Any]]
                ) -> Tuple[List[jax.Array], List[jax.Array]]:
        """Reduce the full contribution set: per bucket, partial sums
        within each pod group (ICI legs) then one exchange across pods
        (DCN leg), plus the per-bucket sumsq.  Deterministic left-to-
        right chains — every caller holding the same contributions
        computes the SAME bits, which is what lets every process in a
        multi-host run execute this redundantly and stay bit-identical
        (and what makes codec="none" bitwise-equal to the per-layer
        oracle on a single pod)."""
        R = len(contribs_by_replica)
        assert sorted(contribs_by_replica) == list(range(R)), \
            f"combine needs contributions from ALL replicas, got " \
            f"{sorted(contribs_by_replica)}"
        flats: List[jax.Array] = []
        sumsqs: List[jax.Array] = []
        for i, b in enumerate(plan):
            groups = (b.pod_groups if b.pod_groups != ((),)
                      else (tuple(range(R)),))
            contribs = [contribs_by_replica[r][i] for r in range(R)]
            partials: List[jax.Array] = []
            for grp in groups:
                acc = contribs[grp[0]]
                for r in grp[1:]:
                    acc = self._add_prog(b.n)(acc, contribs[r])
                partials.append(acc)
            total = partials[0]
            for p in partials[1:]:
                total = self._add_prog(b.n)(total, p)
            flats.append(total)
            sumsqs.append(self._sumsq_prog(b.n)(total))
        return flats, sumsqs

    def reduce(self, plan: Sequence[BucketExec],
               all_grads: Sequence[Dict[int, Any]],
               weights: Sequence[float]) -> SyncReduceResult:
        """Weighted cross-replica reduction of every bucket, issued
        deepest-first (the plan's order): contributions + combine in one
        process.  Pure with respect to trainer state: residual updates
        are STAGED, committed by the caller only after the sync-phase
        fault seam passes — an aborted iteration leaves residuals
        exactly as they were (§3.3 lost-iteration semantics)."""
        contribs, staged = self.contributions(
            plan, {r: g for r, g in enumerate(all_grads)}, weights)
        flats, sumsqs = self.combine(plan, contribs)
        return SyncReduceResult(flats=flats, sumsqs=sumsqs,
                                staged_residuals=staged)

    def commit_residuals(self, result: SyncReduceResult) -> None:
        for k, v in result.staged_residuals.items():
            self.ef.put(k, v)

    def retain_residuals(self, plan: Sequence[BucketExec],
                         num_replicas: int) -> int:
        """Drop error-feedback residuals the current bucket layout can
        no longer use (recover/join changed spans or replica count)."""
        valid = {("ef", b.signature, self.codec, r)
                 for b in plan for r in range(num_replicas)}
        return self.ef.retain(valid)

    def update(self, plan: Sequence[BucketExec], flats: Sequence[jax.Array],
               states: Dict[int, LayerState], scale: jax.Array,
               step: jax.Array) -> None:
        """Apply the donated per-bucket AdamW programs to ONE replica's
        layer states, in place (dict entries are replaced)."""
        for b, flat in zip(plan, flats):
            new_states = self._update_prog(b)(
                [states[l] for l in b.lids], flat, scale, step)
            for l, st in zip(b.lids, new_states):
                states[l] = st
