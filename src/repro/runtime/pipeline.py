"""Heterogeneous pipeline execution engine (paper §6) at array level.

Each PipelineInstance from the core engine is bound to concrete arrays:
every stage holds ONLY its layers' params + Adam moments (layer-indexed,
the paper's unit of state).  A training step:

  1. per pipeline: ONE compiled, cached step program — a
     ``lax.scan`` over the microbatch axis with in-program 1F1B
     gradient accumulation — returns per-layer gradient sums and the
     per-microbatch NLL as an ARRAY (no host sync inside the schedule).
     Programs live in a template-keyed ProgramCache
     (runtime/executor.py, DESIGN.md §8): key = (template signature,
     microbatch count, shapes), warmed at bootstrap for the whole
     template set so reconfiguration swaps programs by lookup — the
     execution-side mirror of the planner's precompute-everything
     design;
  2. cross-pipeline sync at LAYER granularity (Figure 9): a weighted
     average over replicas, weights = minibatch sizes, so the result is
     exactly the global-batch mean gradient.  Compiled mode executes
     the engine's BUCKET plan through the sync data plane
     (runtime/sync_exec.py, DESIGN.md §10): each bucket flattened to
     one buffer, reduced deepest-first, hierarchically across pods,
     optionally codec-compressed with error feedback;
  3. identical AdamW update on every replica through compiled, DONATED
     update programs (per BUCKET in compiled mode, per layer on the
     eager oracle path) — replicas stay bit-identical, which is what
     makes step 4 sound;
  4. on failure: the core engine reinstantiates pipelines from templates
     and emits a copy plan; we rebuild stage arrays from the surviving
     replicas' layer states (params AND moments): a state that stays on
     its node is bound in place, one that moves is copied by one
     compiled program per layer — recovery without any checkpoint, the
     paper's headline mechanism — and the new pipeline set's programs
     come straight from the cache.

``mode="eager"`` keeps the original per-microbatch ``jax.vjp``-chain
schedule walker as the parity reference (it shares the sync/update path
and, per the compiled contract, never syncs the host mid-schedule).

This path runs real heterogeneous sets (different stage counts per
pipeline) — the thing single-program SPMD cannot express; the SPMD fast
path (runtime/spmd.py) covers the homogeneous zero-failure case.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.adapt import AdaptationError
from repro.core.engine import OobleckEngine
from repro.kernels import ops as kops
from repro.core.reconfigure import PipelineInstance
from repro.models import Model
from repro.models.layers import cross_entropy, embed, unembed
from repro.optim import adamw
from repro.runtime.executor import (Executor, ProgramCache,
                                    avals_of as _avals_of,
                                    template_signature, tree_spec)
from repro.runtime.schedule import flat_schedule
from repro.runtime.sync_exec import (BucketedSync, perlayer_global_sumsq,
                                     perlayer_sync)
from repro.utils.spans import span

LayerState = Dict[str, Any]     # {"p": params, "m": moment1, "v": moment2}


# ----------------------------------------------------------------------
# Canonical layer-indexed parameter view
# ----------------------------------------------------------------------
def split_into_layers(model: Model, params: Dict) -> List[Dict]:
    """Full param tree -> [embed, block_0..block_{L-1}, head] per the
    cost-model layer indexing (embed = layer 0, head = layer L+1).

    Tied-embedding models are AUTO-UNTIED here: pipeline stages own
    disjoint layer sets, so the head stage gets its own copy of the
    table (trained independently thereafter).  This is the standard
    pipeline-parallel treatment when first/last stages differ.
    """
    L = model.arch.num_layers
    layers: List[Dict] = [{"embed": params["embed"]}]
    for i in range(L):
        layers.append(jax.tree.map(lambda t: t[i], params["blocks"]))
    tail = {"final_norm": params["final_norm"]}
    tail["head"] = params.get("head", jax.tree.map(jnp.copy, params["embed"]))
    layers.append(tail)
    return layers


def zeros_like_tree(tree):
    return jax.tree.map(lambda t: jnp.zeros_like(t, dtype=jnp.float32), tree)


def layer_copy(state: LayerState) -> LayerState:
    """Every leaf of one layer state into a new buffer (the body of the
    recovery copy program)."""
    return jax.tree.map(jnp.copy, state)


# shared with the sync data plane's program keys (runtime/executor.py)
_tree_spec = tree_spec


# ----------------------------------------------------------------------
# Stage program
# ----------------------------------------------------------------------
def make_stage_fn(model: Model, kinds: Sequence[str]) -> Callable:
    """Stage program over its layer list.  Signature:
    fn(layer_params, carry, labels, fe) -> carry' | (loss, metrics)
    carry = (x, aux) with x = tokens for the first stage."""
    arch = model.arch

    def fn(layer_params: List[Dict], carry, labels, fe):
        x, aux = carry
        for kind, lp in zip(kinds, layer_params):
            if kind == "embed":
                x = embed(lp["embed"], x, model.dtype)
                if fe is not None:
                    x = jnp.concatenate([fe.astype(model.dtype), x], axis=1)
            elif kind == "block":
                x, aux = model.block(lp, x, aux)
            else:  # head
                x = model._norm(lp["final_norm"], x)
                logits = unembed(lp["head"], x)
                ft = logits.shape[1] - labels.shape[1]
                if ft:
                    logits = logits[:, ft:]
                # labels are pre-shifted next-token targets; the final
                # position is excluded from the mean (S-1 reduction,
                # bit-exact compiled/eager parity)
                nll = cross_entropy(logits[:, :-1], labels[:, :-1])
                coef = (arch.moe.router_aux_loss_coef
                        if arch.moe is not None else 0.0)
                return nll + coef * aux, nll
        return x, aux
    return fn


def make_grads_fn(model: Model, stage_kinds: Sequence[Sequence[str]]
                  ) -> Callable:
    """One pipeline's step: fn(stage_params, tokens [M, b, s], labels,
    *fe) -> (per-layer grad means, per-microbatch NLL [M]).  A scan over
    microbatches with in-program gradient accumulation."""
    fns = [make_stage_fn(model, k) for k in stage_kinds]

    def loss_of(stage_params, tok, lab, fe):
        carry = (tok, jnp.zeros((), jnp.float32))
        for fn, sp in zip(fns, stage_params):
            carry = fn(sp, carry, lab, fe)
        loss, nll = carry
        return loss, nll

    def grads_fn(stage_params, tokens, labels, *fe_args):
        def body(gsum, xs):
            tok, lab = xs[0], xs[1]
            fe = xs[2] if len(xs) > 2 else None
            (_, nll), g = jax.value_and_grad(
                loss_of, has_aux=True)(stage_params, tok, lab, fe)
            return jax.tree.map(jnp.add, gsum, g), nll

        zeros = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                             stage_params)
        xs = (tokens, labels) + tuple(fe_args)
        gsum, nlls = jax.lax.scan(body, zeros, xs)
        gsum = jax.tree.map(lambda g: g / tokens.shape[0], gsum)
        return gsum, nlls

    return grads_fn


# ----------------------------------------------------------------------
# One bound pipeline
# ----------------------------------------------------------------------
@dataclasses.dataclass
class PipelineRun:
    instance: PipelineInstance
    # per stage: list of layer ids and their states
    stage_layers: List[List[int]]
    states: Dict[int, LayerState]          # layer id -> state (this replica)
    stage_fns: List[Callable]

    @property
    def num_stages(self) -> int:
        return len(self.stage_layers)

    @property
    def signature(self) -> Tuple[Tuple[int, int], ...]:
        return template_signature(self.instance.template)

    def stage_params(self, s: int) -> List[Dict]:
        return [self.states[l]["p"] for l in self.stage_layers[s]]

    def all_stage_params(self) -> List[List[Dict]]:
        return [[self.states[l]["p"] for l in lids]
                for lids in self.stage_layers]


class HeteroTrainer(Executor):
    """Drives N heterogeneous pipeline replicas through train steps and
    failure recovery, using the core engine for all planning and a
    template-keyed ProgramCache for all execution."""

    def __init__(self, model: Model, engine: OobleckEngine,
                 params: Dict, opt_cfg: adamw.AdamWConfig,
                 mode: str = "compiled",
                 cache: Optional[ProgramCache] = None,
                 codec: str = "none",
                 sync_mode: Optional[str] = None):
        assert mode in ("compiled", "eager"), mode
        self.model = model
        self.engine = engine
        self.opt_cfg = opt_cfg
        self.mode = mode
        self.cache = cache or ProgramCache()
        # Sync tail implementation (DESIGN.md §10): "bucketed" executes
        # the engine's sync plan through compiled per-bucket programs;
        # "perlayer" keeps the eager jax.tree.map chain as the parity
        # oracle.  Compiled mode defaults to bucketed; eager mode stays
        # the end-to-end reference on the per-layer path.
        self.sync_mode = sync_mode or (
            "bucketed" if mode == "compiled" else "perlayer")
        assert self.sync_mode in ("bucketed", "perlayer"), self.sync_mode
        assert codec == "none" or self.sync_mode == "bucketed", \
            "wire codecs ride the bucketed data plane only"
        self.codec = codec
        # fault-injection seam (tests/test_fault_injection.py): called at
        # the step's phase boundaries — "grads" after each pipeline's
        # forward/backward, "sync" after the cross-replica gradient
        # average, BEFORE any state mutation.  A failure raised from
        # either phase therefore aborts the iteration with every layer
        # state untouched (the lost-iteration semantics of §3.3); the
        # optimizer commit is the only mutating phase and runs last.
        self.on_phase: Optional[Callable[[str], None]] = None
        self.opt_step = jnp.zeros((), jnp.int32)
        layers = split_into_layers(model, params)
        self.num_layers = len(layers)
        self._kind = (["embed"] + ["block"] * model.arch.num_layers
                      + ["head"])
        # shape/dtype skeleton of every layer: lets warm() compile
        # programs for templates that are not currently instantiated
        self._layer_avals = [_avals_of(l) for l in layers]
        # each layer state's structure key and bytes, spelled out once:
        # the copy phase reads both per bound state
        self._state_specs = [_tree_spec(self._state_aval(l))
                             for l in range(self.num_layers)]
        self._state_bytes = [
            sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
                for a in jax.tree.leaves(self._state_aval(l)))
            for l in range(self.num_layers)]
        self._bsync = BucketedSync(self.cache, opt_cfg, self._layer_avals,
                                   codec=codec)
        self._bucket_plan_cache = None   # rebuilt whenever bind() runs
        self.runs: List[PipelineRun] = [
            self._bind_run(inst, layers) for inst in self._bound_instances()]
        if hasattr(engine, "attach_executor"):
            engine.attach_executor(self)
        self.bind()

    def _bound_instances(self) -> List[PipelineInstance]:
        """Which pipeline instances THIS process binds full state for.
        The single-controller trainer binds all of them; the multi-host
        shard trainer (runtime/multihost.py) overrides this to bind only
        the replicas its process leads."""
        return list(self.engine.instances)

    # ------------------------------------------------------------------
    def _bind_run(self, inst: PipelineInstance, layers: Optional[List[Dict]],
                  state_fn: Optional[Callable[[str, int], LayerState]] = None
                  ) -> PipelineRun:
        """Bind one pipeline's layer states: fresh from ``layers`` (zero
        moments), or, on the data-plane path, ``state_fn(node, layer)``,
        the state the layer's owning node binds (``_rebind``)."""
        stage_layers = [list(range(st.layer_start, st.layer_end))
                        for st in inst.template.stages]
        states: Dict[int, LayerState] = {}
        for lids in stage_layers:
            for l in lids:
                if state_fn is None:
                    # one holder per buffer (``_rebind``): every replica
                    # gets its own copy of the fresh params
                    p = layers[l]
                    states[l] = {"p": jax.tree.map(jnp.copy, p),
                                 "m": zeros_like_tree(p),
                                 "v": zeros_like_tree(p)}
                else:
                    states[l] = state_fn(inst.layer_owners(l)[0], l)
        fns = [make_stage_fn(self.model, [self._kind[l] for l in lids])
               for lids in stage_layers]
        return PipelineRun(inst, stage_layers, states, fns)

    def _rebind(self, state_fn: Callable[[str, int], Tuple[LayerState, bool]]
                ) -> Dict[str, int]:
        """Rebind every pipeline this process leads, each layer from
        ``state_fn(node, layer) -> (state, moved)``, where ``moved`` says
        the state comes from another node than the layer's owner.

        One holder per buffer: update programs donate their input
        buffers, so no two replicas may hold the same one.  A held state
        (not moved) that no new run has taken yet is bound in place; any
        other state is copied into new buffers by its layer's copy
        program.  The old runs, dropped after the event, keep every held
        state alive until then, so ids are stable.  Returns the bytes
        bound in place (``in_place_bytes``), copied on the device
        (``state_copy_bytes``) and, of those, of moved layers
        (``moved_state_bytes``)."""
        claimed: Set[int] = set()
        counts = {"in_place_bytes": 0, "state_copy_bytes": 0,
                  "moved_state_bytes": 0}

        def bound(node: str, l: int) -> LayerState:
            st, moved = state_fn(node, l)
            nbytes = self._state_bytes[l]
            if not moved and id(st) not in claimed:
                claimed.add(id(st))
                counts["in_place_bytes"] += nbytes
                return st
            counts["state_copy_bytes"] += nbytes
            if moved:
                counts["moved_state_bytes"] += nbytes
            return self._copy_program(l)(st)

        self.runs = [self._bind_run(inst, None, bound)
                     for inst in self._bound_instances()]
        return counts

    # ------------------------------------------------------------------
    # Program cache plumbing
    # ------------------------------------------------------------------
    def _stage_avals(self, sig: Tuple[Tuple[int, int], ...]) -> List[List]:
        return [[self._layer_avals[l] for l in range(u, v)]
                for (u, v) in sig]

    def _batch_avals(self, M: int) -> Tuple:
        b = self.engine.config.microbatch
        s = self.engine.profile.seq_len
        tok = jax.ShapeDtypeStruct((M, b, s), jnp.int32)
        return tok, tok

    def _grads_program(self, sig: Tuple[Tuple[int, int], ...],
                       tok_aval, lab_aval, fe_aval=None) -> Callable:
        """Compiled per-(template-signature, microbatch-count) step
        program: scan over microbatches, in-program 1F1B gradient
        accumulation, per-microbatch NLL returned as an array."""
        # backend_signature: a stage program may contain Pallas kernels
        # whose interpret-vs-compiled lowering is resolved at TRACE time;
        # without it a program traced under the CPU default would be
        # silently reused (interpreted!) on an accelerator mesh.
        key = ("grads", kops.backend_signature(), sig,
               _tree_spec(tok_aval), _tree_spec(lab_aval),
               _tree_spec(fe_aval) if fe_aval is not None else None)

        def build() -> Callable:
            kinds = [[self._kind[l] for l in range(u, v)] for (u, v) in sig]
            avals = (self._stage_avals(sig), tok_aval, lab_aval)
            if fe_aval is not None:
                avals = avals + (fe_aval,)
            return jax.jit(make_grads_fn(self.model, kinds)).lower(
                *avals).compile()

        return self.cache.get_or_build(key, build)

    def _update_program(self, state: LayerState, grad) -> Callable:
        """Compiled per-layer-structure AdamW update with the state
        buffers DONATED — the optimizer writes in place."""
        s_aval, g_aval = _avals_of(state), _avals_of(grad)
        key = ("update", _tree_spec(s_aval), _tree_spec(g_aval))

        def build() -> Callable:
            layer_cfg = dataclasses.replace(self.opt_cfg, clip_norm=0.0)

            def layer_update(st, g, scale, step):
                g = jax.tree.map(lambda t: t * scale, g)
                new_p, new_opt, _ = adamw.apply(
                    layer_cfg, st["p"], g,
                    adamw.AdamWState(step, st["m"], st["v"]))
                return {"p": new_p, "m": new_opt.m, "v": new_opt.v}

            scale_aval = jax.ShapeDtypeStruct((), jnp.float32)
            step_aval = jax.ShapeDtypeStruct((), jnp.int32)
            return jax.jit(layer_update, donate_argnums=(0,)).lower(
                s_aval, g_aval, scale_aval, step_aval).compile()

        return self.cache.get_or_build(key, build)

    def _copy_program(self, l: int) -> Callable:
        """Compiled copy of layer ``l``'s whole ``{p, m, v}`` state tree
        into new buffers, cached per layer structure.  Nothing is
        donated: the source stays live in its replica."""
        key = ("lcopy", self._state_specs[l])
        return self.cache.get_or_build(
            key, lambda: jax.jit(layer_copy).lower(
                self._state_aval(l)).compile())

    def _state_aval(self, l: int) -> LayerState:
        """Shape/dtype skeleton of layer ``l``'s state: params as built,
        both Adam moments in float32."""
        p = self._layer_avals[l]
        f32 = lambda t: jax.ShapeDtypeStruct(t.shape, jnp.float32)
        return {"p": p, "m": jax.tree.map(f32, p), "v": jax.tree.map(f32, p)}

    # ------------------------------------------------------------------
    # Warming: precompute-everything, execution edition
    # ------------------------------------------------------------------
    def _bucket_plan(self):
        """The engine's sync plan bound for execution (cached until the
        next bind): per bucket, the replica lead owners' pods drive the
        hierarchical ICI/DCN reduction path."""
        if self._bucket_plan_cache is None:
            sync_plan = self.engine.sync_plan()
            topo = self.engine.topology
            pods = [[topo.pod_of(inst.layer_owners(b.layer_start)[0])
                     for inst in self.engine.instances]
                    for b in sync_plan]
            self._bucket_plan_cache = self._bsync.exec_plan(sync_plan, pods)
        return self._bucket_plan_cache

    def bind(self) -> None:
        """Ensure programs for the CURRENT pipeline set + batch plan are
        cached (cheap after warm_templates(): pure lookups)."""
        self._bucket_plan_cache = None
        if self.mode != "compiled":
            return
        mb_of = {id(inst): M for inst, M in zip(
            self.engine.instances, self.engine.batch.num_microbatches)}
        for run in self.runs:
            tok, lab = self._batch_avals(mb_of[id(run.instance)])
            self._grads_program(run.signature, tok, lab)
        if self.sync_mode == "bucketed":
            plan = self._bucket_plan()
            self._bsync.bind_plan(plan)
            # a reconfiguration may have changed the bucket layout or
            # replica count: stale error-feedback residuals would
            # shape-mismatch the new buckets — drop them
            self._bsync.retain_residuals(plan, len(self.engine.instances))
            return
        # per-layer update path: seed every distinct layer structure
        # (embed / block / head)
        for l, aval in enumerate(self._layer_avals):
            self._update_program(self._state_aval(l), aval)

    def warm_templates(self, mb_counts: Optional[Iterable[int]] = None
                       ) -> Dict[str, int]:
        """Precompile step programs for EVERY template in the engine's
        set x every reachable microbatch count, so any reconfiguration
        the reconfigurator can emit swaps programs by cache lookup with
        ZERO compilation.  Counts default to 1..total_mb — the exact
        reachable set, since batch distribution gives every pipeline at
        least one of the total_mb microbatches."""
        if self.mode != "compiled":
            return self.cache.stats.as_dict()
        if mb_counts is None:
            total_mb = (self.engine.config.global_batch
                        // self.engine.config.microbatch)
            mb_counts = range(1, total_mb + 1)
        mb_counts = list(mb_counts)
        for tpl in self.engine.templates.values():
            sig = template_signature(tpl)
            for M in mb_counts:
                tok, lab = self._batch_avals(M)
                self._grads_program(sig, tok, lab)
        # Warm the eager GLUE around the cached programs too: stacking M
        # microbatches and reducing the M-length NLL are shape-keyed op
        # dispatches that would otherwise compile on the first step after
        # a reconfiguration lands on a previously-unseen microbatch
        # count — exactly the moment the zero-recompilation contract is
        # supposed to protect.
        b = self.engine.config.microbatch
        s = self.engine.profile.seq_len
        host = np.zeros((b, s), np.int32)
        for M in mb_counts:
            stacked = jnp.stack([jnp.asarray(host)] * M).astype(jnp.int32)
            nll = jnp.zeros((M,), jnp.float32)
            (jnp.sum(nll) / float(M)).block_until_ready()
            del stacked
        if self.sync_mode == "bucketed":
            # bucket programs for EVERY layout any reachable instance
            # set can produce (structure-keyed, so this is a handful of
            # distinct compiles) + the scalar glue around them — a
            # reconfiguration must not compile in the sync tail either
            self._bsync.warm(
                self.engine.templates.values(),
                [l.param_bytes for l in self.engine.profile.layers],
                self.engine.config.bucket_cap_bytes)
            self._warm_clip_glue()
        # the recovery copy phase's per-layer-structure copy programs
        for l in range(self.num_layers):
            self._copy_program(l)
        self.bind()
        return self.cache.stats.as_dict()

    def _warm_clip_glue(self) -> None:
        """Dispatch the scalar ops of the norm/clip glue once (sqrt,
        min/max, division on () arrays are shape-keyed op dispatches)."""
        sq = jnp.zeros((), jnp.float32)
        sq = sq + jnp.zeros((), jnp.float32)
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, 1.0 / jnp.maximum(norm, 1e-12))
        scale.astype(jnp.float32).block_until_ready()
        jnp.ones(()).astype(jnp.float32).block_until_ready()

    # ------------------------------------------------------------------
    # One pipeline's iteration -> per-layer grad means + per-mb NLL
    # ------------------------------------------------------------------
    def _run_compiled(self, run: PipelineRun, microbatches: List[Dict]
                      ) -> Tuple[Dict[int, Any], jax.Array]:
        with span("oobleck.step.inputs"):
            tokens = jnp.stack([jnp.asarray(b["tokens"])
                                for b in microbatches]).astype(jnp.int32)
            labels = jnp.stack([jnp.asarray(b["labels"])
                                for b in microbatches]).astype(jnp.int32)
            fes = [b.get("frontend_embeds") for b in microbatches]
            fe = (jnp.stack([jnp.asarray(f) for f in fes])
                  if fes[0] is not None else None)
        prog = self._grads_program(
            run.signature, _avals_of(tokens), _avals_of(labels),
            _avals_of(fe) if fe is not None else None)
        args = (run.all_stage_params(), tokens, labels)
        if fe is not None:
            args = args + (fe,)
        gstages, nll = prog(*args)
        grads: Dict[int, Any] = {}
        for s, lids in enumerate(run.stage_layers):
            for j, l in enumerate(lids):
                grads[l] = gstages[s][j]
        return grads, nll

    def _run_eager(self, run: PipelineRun, microbatches: List[Dict]
                   ) -> Tuple[Dict[int, Any], jax.Array]:
        """Reference path: walks the explicit 1F1B schedule with
        per-microbatch jax.vjp chains.  Kept for parity testing and as
        the readable spec of what the compiled program fuses; it must
        never force a host sync mid-schedule (losses stay on device)."""
        S = run.num_stages
        M = len(microbatches)
        sched = flat_schedule(S, M)
        acts: Dict[Tuple[int, int], Any] = {}
        cots: Dict[Tuple[int, int], Any] = {}
        vjps: Dict[Tuple[int, int], Any] = {}
        gsum: List[Any] = [None] * S
        losses: List[jax.Array] = []

        for (s, op, mb) in sched:
            batch = microbatches[mb]
            labels = jnp.asarray(batch["labels"])
            fe = batch.get("frontend_embeds")
            fe = jnp.asarray(fe) if fe is not None else None
            if op == "F":
                if s == 0:
                    carry = (jnp.asarray(batch["tokens"]),
                             jnp.zeros((), jnp.float32))
                else:
                    carry = acts[(s - 1, mb)]
                out, vjp = jax.vjp(
                    lambda lp, c: run.stage_fns[s](lp, c, labels, fe),
                    run.stage_params(s), carry)
                vjps[(s, mb)] = vjp
                if s == S - 1:
                    loss, nll = out
                    losses.append(nll)          # device array, no sync
                    cots[(s, mb)] = (jnp.ones(()), jnp.zeros(()))
                else:
                    acts[(s, mb)] = out
            else:  # backward
                ct = cots.pop((s, mb))
                gparams, gcarry = vjps.pop((s, mb))(ct)
                if s > 0:
                    cots[(s - 1, mb)] = gcarry
                    acts.pop((s - 1, mb), None)
                gsum[s] = (gparams if gsum[s] is None else
                           jax.tree.map(jnp.add, gsum[s], gparams))

        grads: Dict[int, Any] = {}
        for s, lids in enumerate(run.stage_layers):
            for j, l in enumerate(lids):
                grads[l] = jax.tree.map(lambda g: g / M, gsum[s][j])
        return grads, jnp.stack(losses)

    def _run_pipeline(self, run: PipelineRun, microbatches: List[Dict]
                      ) -> Tuple[Dict[int, Any], jax.Array]:
        if self.mode == "compiled":
            return self._run_compiled(run, microbatches)
        return self._run_eager(run, microbatches)

    # ------------------------------------------------------------------
    def train_step(self, per_pipeline_batches: List[List[Dict]]) -> Dict:
        """per_pipeline_batches[i] = list of N_b,i microbatch dicts.
        Returns metrics as DEVICE ARRAYS — nothing here blocks on the
        device; callers convert when they want to look."""
        assert len(per_pipeline_batches) == len(self.runs)
        all_grads: List[Dict[int, Any]] = []
        nlls, weights = [], []
        for run, mbs in zip(self.runs, per_pipeline_batches):
            with span("oobleck.step.grads"):
                g, nll = self._run_pipeline(run, mbs)
            all_grads.append(g)
            nlls.append(nll)
            weights.append(len(mbs))
            if self.on_phase is not None:
                self.on_phase("grads")

        grad_norm = self._sync_and_update(all_grads, weights)
        loss = sum(jnp.sum(n) for n in nlls) / float(sum(weights))
        return {"loss": loss, "grad_norm": grad_norm,
                "num_pipelines": len(self.runs)}

    # ------------------------------------------------------------------
    # The sync tail: cross-replica sync + global-norm clip + AdamW
    # (runtime/sync_exec.py, DESIGN.md §10)
    # ------------------------------------------------------------------
    def _sync_and_update(self, all_grads: List[Dict[int, Any]],
                         weights: List[int]) -> jax.Array:
        """Route the step's tail through the sync data plane and commit
        the optimizer update on every replica; returns the global grad
        norm as a device array.  ``sync_mode="bucketed"`` executes the
        engine's bucket plan as compiled per-bucket programs (deepest
        first, hierarchical across pods, optional wire codec);
        ``"perlayer"`` is the eager per-layer oracle."""
        if self.sync_mode == "bucketed":
            plan = self._bucket_plan()
            with span("oobleck.step.sync"):
                red = self._bsync.reduce(plan, all_grads, weights)
                sq = jnp.zeros((), jnp.float32)
                for s in red.sumsqs:
                    sq = sq + s
                grad_norm = jnp.sqrt(sq)
                scale = self._clip_scale(grad_norm)
            if self.on_phase is not None:
                self.on_phase("sync")
            # ---- commit phase: the ONLY mutating part of the step ----
            with span("oobleck.step.update"):
                self._bsync.commit_residuals(red)
                step_in = self.opt_step         # adamw.apply increments
                self.opt_step = self.opt_step + 1
                for run in self.runs:
                    self._bsync.update(plan, red.flats, run.states, scale,
                                       step_in)
            return grad_norm

        # ---- per-layer oracle (Figure 9, the pre-§10 runtime path) ----
        with span("oobleck.step.sync"):
            synced = perlayer_sync(all_grads, weights, self.num_layers)
            # global-norm clip across the WHOLE model (clipping per layer
            # would diverge from the SPMD fast path); all-device
            # arithmetic: the scale is folded into the compiled update,
            # never forced to the host
            grad_norm = jnp.sqrt(perlayer_global_sumsq(synced,
                                                       self.num_layers))
            scale = self._clip_scale(grad_norm)
        if self.on_phase is not None:
            self.on_phase("sync")
        with span("oobleck.step.update"):
            step_in = self.opt_step             # adamw.apply increments
            self.opt_step = self.opt_step + 1
            for run in self.runs:
                for l in sorted(run.states):
                    st = run.states[l]
                    prog = self._update_program(st, synced[l])
                    run.states[l] = prog(st, synced[l], scale, step_in)
        return grad_norm

    def _clip_scale(self, grad_norm: jax.Array) -> jax.Array:
        if self.opt_cfg.clip_norm:
            scale = jnp.minimum(
                1.0, self.opt_cfg.clip_norm / jnp.maximum(grad_norm, 1e-12))
        else:
            scale = jnp.ones(())
        return scale.astype(jnp.float32)

    # Executor interface --------------------------------------------------
    def step(self, batches: List[List[Dict]]) -> Dict:
        return self.train_step(batches)

    # ------------------------------------------------------------------
    # Failure recovery: the data plane copies layer states from the
    # SCHEDULED surviving replicas (runtime/transfer.py, DESIGN.md §9)
    # ------------------------------------------------------------------
    def _states_by_node(self, exclude: Set[str] = frozenset()
                        ) -> Dict[str, Dict[int, LayerState]]:
        """node -> layer -> state, for every surviving owner.  A node's
        layer states survive iff the node survives; every node of a
        multi-node stage holds the stage's states."""
        by_node: Dict[str, Dict[int, LayerState]] = {}
        for run in self.runs:
            for l, st in run.states.items():
                for node in run.instance.layer_owners(l):
                    if node not in exclude:
                        by_node.setdefault(node, {})[l] = st
        return by_node

    def _apply_transfer_plan(self, result, dead: Set[str],
                             phases: Dict[str, float]) -> Dict:
        """Rebind every pipeline, sourcing each moved layer from the
        replica the transfer scheduler routed it from (pod-local first,
        least-loaded sender), then swap programs by cache lookup.  Runs
        the transfer_plan, copy and bind phases into ``phases``."""
        # (schedule_transfers validates the plan against ``dead`` and the
        # copy plan's byte total)
        with span("oobleck.recover.transfer_plan") as sp:
            plan = self.engine.transfer_plan(result, dead=dead)
            stats = plan.stats()      # prices the makespan once
        phases["transfer_plan"] = sp.seconds
        with span("oobleck.recover.copy") as sp:
            # the runs still hold the instances from before the replan
            by_node = self._states_by_node(exclude=dead)
            fallback: Dict[int, LayerState] = {}
            for node_states in by_node.values():
                for l, st in node_states.items():
                    fallback.setdefault(l, st)
            missing = [l for l in range(self.num_layers)
                       if l not in fallback]
            assert not missing, \
                f"layers {missing} lost (>f failures in a stage)"

            def state_for(node: str, layer: int) -> Tuple[LayerState, bool]:
                held = by_node.get(node, {})
                if layer in held:      # the node already owns this layer
                    return held[layer], False
                src = plan.source_of(node, layer)
                if src is not None and layer in by_node.get(src, {}):
                    return by_node[src][layer], True
                return fallback[layer], True

            copied = self._rebind(state_for)
        phases["copy"] = sp.seconds
        with span("oobleck.recover.bind") as sp:
            self.bind()    # swap programs by lookup (zero compiles if warm)
        phases["bind"] = sp.seconds
        return {"copied_bytes": result.copy_bytes(),
                "num_pipelines": len(self.runs),
                "cache": self.cache.stats.as_dict(),
                "transfer": stats,
                "breakdown": {"replan": result.replan_seconds,
                              "transfer": stats["seconds"]},
                **copied}

    def _apply_adaptation(self, plan, breakdown: Dict[str, float],
                          phases: Dict[str, float]) -> Dict:
        """Commit a ReCycle adaptation the engine has applied: drop the
        damaged replicas' runs, keep the survivors' layer states
        untouched (every replica holds the full model, so re-routed
        microbatches compute the same math on the host), and rebind —
        programs for the survivors' new microbatch counts are already
        warm, so this is copy-free AND compile-free."""
        with span("oobleck.recover.bind") as sp:
            kept = {id(inst) for inst in plan.instances}
            self.runs = [run for run in self.runs
                         if id(run.instance) in kept]
            self.bind()    # pure cache lookups after warm_templates()
        phases["bind"] = sp.seconds
        return {"copied_bytes": 0, "num_pipelines": len(self.runs),
                "parked_nodes": list(plan.parked_nodes),
                "cache": self.cache.stats.as_dict(),
                "breakdown": breakdown}

    def _event_info(self, info: Dict, phases: Dict[str, float],
                    compiles: int) -> Dict:
        """What every recovery/join reports besides its path's own keys:
        the measured ``phases`` (seconds; ``replan`` spans the policy
        choice as well as the planner's own ``breakdown["replan"]``) and
        ``breakdown["compile"]``, the ProgramCache misses the event
        caused (0 on a warm cache)."""
        info["phases"] = phases
        info["breakdown"]["compile"] = self.cache.stats.compiles - compiles
        return info

    def handle_failure(self, dead_nodes: set, drained: bool = False,
                       policy: Optional[str] = None) -> Dict:
        """Route a failure event through the configured recovery policy
        (engine config's ``recovery_policy`` unless overridden).  "auto"
        selects per event from predicted downtime; "adapt"/"spare" fall
        back to the full replan path when infeasible."""
        dead = set(dead_nodes)
        policy = policy or getattr(self.engine.config,
                                   "recovery_policy", "replan")
        compiles = self.cache.stats.compiles
        with span("oobleck.recover.replan") as sp:
            decision = adapt = None
            if policy == "auto":
                decision = self.engine.select_recovery_policy(dead)
                policy = decision["policy"]
            if policy == "adapt":
                try:
                    adapt = self.engine.plan_adaptation(dead)
                    # price the reroute exposure against the replan
                    # alternative, before the adaptation applies
                    breakdown = self.engine.adapt_cost_model().breakdown(
                        adapt, self.engine.adaptation_reference_iteration(
                            dead))
                    self.engine.apply_adaptation(adapt, dead=dead,
                                                 drained=drained)
                except AdaptationError:
                    policy = "replan"
            if policy == "spare":
                try:
                    result = self.engine.plan_spare_promotion(dead)
                    self.engine.apply_spare_promotion(result, dead=dead,
                                                      drained=drained)
                except AdaptationError:
                    policy = "replan"
            if policy == "replan":
                result = self.engine.handle_failure(dead, drained=drained)
        phases = {"replan": sp.seconds}
        if policy == "adapt":
            info = self._apply_adaptation(adapt, breakdown, phases)
        else:
            info = self._apply_transfer_plan(result, dead, phases)
        info["policy"] = policy
        if decision is not None:
            info["decision"] = decision["policy"]
        return self._event_info(info, phases, compiles)

    def handle_join(self, new_nodes: list) -> Dict:
        """Elastic scale-up: re-plan globally over the larger cluster and
        seed every new pipeline's layer states from existing replicas
        (the same copy path as failure recovery — §5 applies to joins)."""
        compiles = self.cache.stats.compiles
        with span("oobleck.recover.replan") as sp:
            result = self.engine.handle_join(list(new_nodes))
        phases = {"replan": sp.seconds}
        info = self._apply_transfer_plan(result, set(), phases)
        return self._event_info(info, phases, compiles)

    def recover(self, dead: Set[str], drained: bool = False) -> Dict:
        return self.handle_failure(set(dead), drained=drained)

    def join(self, nodes: List[str]) -> Dict:
        return self.handle_join(list(nodes))

    # ------------------------------------------------------------------
    def replica_divergence(self) -> float:
        """Max abs param difference across replicas (must be ~0)."""
        worst = 0.0
        for l in range(self.num_layers):
            reps = [r.states[l]["p"] for r in self.runs if l in r.states]
            base = reps[0]
            for other in reps[1:]:
                d = jax.tree.map(
                    lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                                       - b.astype(jnp.float32)))),
                    base, other)
                worst = max(worst, max(jax.tree.leaves(d), default=0.0))
        return worst

    def _assemble(self, field: str) -> Dict:
        """Reassemble a canonical full tree ('p'/'m'/'v') from replica-0
        layer states.  Leaves are COPIES: later (donating) train steps
        must not invalidate what we hand out."""
        states: Dict[int, LayerState] = {}
        for run in self.runs:
            for l, st in run.states.items():
                states.setdefault(l, st)
        blocks = [states[1 + i][field]
                  for i in range(self.model.arch.num_layers)]
        tree = {
            "embed": jax.tree.map(jnp.copy, states[0][field]["embed"]),
            "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
            "final_norm": jax.tree.map(
                jnp.copy, states[self.num_layers - 1][field]["final_norm"]),
        }
        if "head" in states[self.num_layers - 1][field]:
            tree["head"] = jax.tree.map(
                jnp.copy, states[self.num_layers - 1][field]["head"])
        return tree

    def full_params(self) -> Dict:
        """Canonical full param tree from replica 0's layers (for
        checkpointing / evaluation)."""
        return self._assemble("p")

    def snapshot(self, data_state: Optional[Dict] = None,
                 rng_seed: int = 0):
        """Host-side TrainState (ckpt/checkpoint.py format): params and
        both Adam moments reassembled into the canonical stacked-block
        layout.  The one place a host sync is the point."""
        from repro.ckpt import TrainState
        params = self._assemble("p")
        opt = adamw.AdamWState(self.opt_step, self._assemble("m"),
                               self._assemble("v"))
        return TrainState(step=int(self.opt_step), params=params,
                          opt_state=opt, data_state=data_state or {},
                          rng_seed=rng_seed)
