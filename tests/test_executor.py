"""Executor interface + compiled per-template program cache (DESIGN.md §8).

The contract under test:

  1. PARITY — the cached per-(template, microbatch-count) step program
     computes the SAME training step as the eager 1F1B reference:
     per-microbatch NLL and per-layer gradients equal to float32 ULP
     noise (XLA fuses the compiled program, so last-bit rounding can
     differ from the op-by-op eager chain), and the
     trajectory stays locked through a failure -> recover -> step cycle.
  2. ZERO RECOMPILATION — after warm_templates(), a failure, recovery
     and the first post-recovery step trigger no program-cache compiles
     AND no XLA backend compiles (jax.monitoring instrumentation).
  3. NO HOST SYNCS — a train step runs under
     jax.transfer_guard_device_to_host("disallow"): nothing in the
     schedule (compiled or eager reference) forces a device->host copy.
  4. The SPMD fast path and the simulator policy implement the same
     Executor interface.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core import EngineConfig, OobleckEngine, build_profile
from repro.data import GlobalBatchDispenser, SyntheticLM
from repro.models import Model
from repro.optim import adamw
from repro.runtime import (Executor, ExecutorUnsupported, HeteroTrainer,
                           SPMDExecutor, track_compiles,
                           track_host_transfers)

RNG = jax.random.PRNGKey(11)
GB, MB, SEQ = 16, 2, 16
# XLA fuses the compiled forward's log-sum-exp differently from the
# op-by-op eager chain, so the NLL's last bits may round differently
# (2 ULP observed); 4 fp32 epsilons relative allows a few ULP and no more
NLL_RTOL = 4 * float(np.finfo(np.float32).eps)


def make_setup(n_nodes=5, f=1, arch_name="gpt3_medium", layers=4):
    arch = reduced(get_arch(arch_name), layers=layers)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive",
                  scan_layers=False)
    params = model.init(RNG)
    profile = build_profile(arch, microbatch=MB, seq_len=SEQ)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)

    def mk_engine():
        return OobleckEngine(
            profile, [f"n{i}" for i in range(n_nodes)],
            EngineConfig(fault_tolerance=f, global_batch=GB, microbatch=MB,
                         gpus_per_node=1, n0_override=2))
    return arch, model, params, opt_cfg, mk_engine


def microbatches(batch, mb_size):
    n = batch["tokens"].shape[0] // mb_size
    return [{k: v[i * mb_size:(i + 1) * mb_size] for k, v in batch.items()
             if not k.startswith("_")} for i in range(n)]


def tree_allclose_ulp(a, b, atol=5e-7, rtol=5e-4):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=rtol)


def assert_params_track(a, b, lr=1e-3):
    """Post-Adam param agreement: Adam normalizes the update, so a
    gradient element whose ULP noise straddles zero moves by a full
    lr regardless of magnitude — isolated elements may differ by
    O(lr) while any SYSTEMATIC divergence (wrong sync weights, missed
    recovery copy, stale program) moves most elements.  Assert the
    max is bounded by a couple of lr and the differing fraction is
    negligible."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        diff = np.abs(x - y)
        assert diff.max() <= 2.5 * lr, diff.max()
        assert (diff > lr / 10).mean() < 1e-3, (diff > lr / 10).mean()


# ----------------------------------------------------------------------
# 1. Parity
# ----------------------------------------------------------------------
def test_compiled_matches_eager_reference():
    arch, model, params, opt_cfg, mk_engine = make_setup()
    tc = HeteroTrainer(model, mk_engine(), params, opt_cfg, mode="compiled")
    te = HeteroTrainer(model, mk_engine(), params, opt_cfg, mode="eager")
    src = SyntheticLM(arch.vocab_size, SEQ, seed=5)
    dc, de = GlobalBatchDispenser(src), GlobalBatchDispenser(src)

    for step in range(2):
        bc = dc.next_step(tc.engine.batch.minibatch_sizes())
        be = de.next_step(te.engine.batch.minibatch_sizes())
        pbc = [microbatches(b, MB) for b in bc]
        pbe = [microbatches(b, MB) for b in be]

        # per-pipeline: NLL and grads equal to fp32 ULP noise
        for rc, re_, mc, me in zip(tc.runs, te.runs, pbc, pbe):
            gc, nc = tc._run_pipeline(rc, mc)
            ge, ne = te._run_pipeline(re_, me)
            np.testing.assert_allclose(np.asarray(nc), np.asarray(ne),
                                       rtol=NLL_RTOL, atol=0)
            assert sorted(gc) == sorted(ge)
            for l in gc:
                tree_allclose_ulp(gc[l], ge[l])

        oc = tc.train_step(pbc)
        oe = te.train_step(pbe)
        if step == 0:
            # identical params -> NLL means equal to a few ULP
            np.testing.assert_allclose(float(oc["loss"]), float(oe["loss"]),
                                       rtol=NLL_RTOL, atol=0)
        else:
            # params have drifted by grad ULP noise * Adam by now
            assert abs(float(oc["loss"]) - float(oe["loss"])) < 1e-4

    assert_params_track(tc.full_params(), te.full_params())
    assert tc.replica_divergence() == 0.0


def test_parity_holds_through_failure_recover_step():
    """Immediately after a failure -> recover -> step cycle the compiled
    path must still track the eager reference — and serve the step from
    the warmed cache without a single compile."""
    arch, model, params, opt_cfg, mk_engine = make_setup()
    tc = HeteroTrainer(model, mk_engine(), params, opt_cfg, mode="compiled")
    te = HeteroTrainer(model, mk_engine(), params, opt_cfg, mode="eager")
    tc.warm_templates()
    src = SyntheticLM(arch.vocab_size, SEQ, seed=9)
    dc, de = GlobalBatchDispenser(src), GlobalBatchDispenser(src)

    def drive(tr, disp):
        batches = disp.next_step(tr.engine.batch.minibatch_sizes())
        return tr.train_step([microbatches(b, MB) for b in batches])

    drive(tc, dc), drive(te, de)
    victim = tc.engine.instances[0].nodes[0]
    compiles_before = tc.cache.stats.compiles
    tc.recover({victim})
    te.recover({victim})
    oc, oe = drive(tc, dc), drive(te, de)
    assert tc.cache.stats.compiles == compiles_before, \
        "recovery must swap programs by cache lookup, not compile"
    assert abs(float(oc["loss"]) - float(oe["loss"])) < 1e-4
    assert_params_track(tc.full_params(), te.full_params())
    assert tc.replica_divergence() == 0.0
    assert te.replica_divergence() == 0.0


# ----------------------------------------------------------------------
# 2. Zero recompilation after reconfiguration
# ----------------------------------------------------------------------
def test_recover_step_is_recompile_free_for_warmed_set():
    arch, model, params, opt_cfg, mk_engine = make_setup()
    trainer = HeteroTrainer(model, mk_engine(), params, opt_cfg)
    stats = trainer.warm_templates()
    # the warmed set covers every (template, microbatch-count) pair the
    # batch planner can emit for this global batch
    n_templates = len(trainer.engine.templates)
    assert stats["compiles"] >= n_templates * (GB // MB)
    # ... and the recovery copy phase's copy program for each layer
    # structure (embed, block, head)
    copy_keys = [k for k in trainer.cache.keys() if k[0] == "lcopy"]
    assert len(copy_keys) == 3
    src = SyntheticLM(arch.vocab_size, SEQ, seed=3)
    disp = GlobalBatchDispenser(src)

    def drive():
        batches = disp.next_step(trainer.engine.batch.minibatch_sizes())
        return trainer.train_step([microbatches(b, MB) for b in batches])

    out = drive()                      # steady state: all ops traced once
    out["loss"].block_until_ready()
    victim = trainer.engine.instances[0].nodes[-1]
    lowered = []

    def on_event(name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        with track_compiles() as log:
            info = trainer.recover({victim})
            out = drive()
            out["loss"].block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert info["state_copy_bytes"] > 0      # the copy programs ran
    assert log.backend_compiles == 0, \
        f"{log.backend_compiles} XLA compiles during recover->step"
    assert not lowered, f"{len(lowered)} programs lowered during recover->step"


# ----------------------------------------------------------------------
# 3. No host transfers mid-schedule
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["compiled", "eager"])
def test_train_step_issues_no_host_transfers(mode):
    """The historical bug this pins: the 1F1B walker called float(nll)
    after every last-stage forward, a blocking d2h sync per microbatch.
    Neither path may materialize ANY device array on the host during a
    step (losses/metrics come back as device arrays)."""
    arch, model, params, opt_cfg, mk_engine = make_setup()
    trainer = HeteroTrainer(model, mk_engine(), params, opt_cfg, mode=mode)
    src = SyntheticLM(arch.vocab_size, SEQ, seed=7)
    disp = GlobalBatchDispenser(src)
    batches = disp.next_step(trainer.engine.batch.minibatch_sizes())
    per_pipe = [microbatches(b, MB) for b in batches]
    trainer.train_step(per_pipe)       # trace/compile outside the guard

    # control: the instrumentation really does catch a d2h sync
    with track_host_transfers() as ctl:
        float(jnp.ones(()) + 1)
    assert ctl.device_to_host >= 1

    batches = disp.next_step(trainer.engine.batch.minibatch_sizes())
    per_pipe = [microbatches(b, MB) for b in batches]
    with track_host_transfers() as log:
        out = trainer.train_step(per_pipe)
    assert log.device_to_host == 0, \
        f"{log.device_to_host} device->host transfers inside a train step"
    assert float(out["loss"]) > 0      # sync AFTER the step is fine


# ----------------------------------------------------------------------
# 4. The other executors honour the same interface
# ----------------------------------------------------------------------
def test_spmd_executor_trains_and_refuses_reconfig():
    arch = reduced(get_arch("gpt3_medium"), layers=2)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive")
    params = model.init(RNG)
    opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, weight_decay=0.0)
    ex = SPMDExecutor(model, params, opt_cfg)
    assert isinstance(ex, Executor)
    src = SyntheticLM(arch.vocab_size, SEQ, seed=2)
    batch = src.batch(np.arange(8))    # fixed batch: loss must overfit
    losses = [float(ex.step(batch)["loss"]) for _ in range(4)]
    assert ex.cache.stats.compiles == 1, "steady state must reuse ONE program"
    assert losses[-1] < losses[0]
    with pytest.raises(ExecutorUnsupported):
        ex.recover({"node0"})
    snap = ex.snapshot()
    assert snap.step == 4
    # snapshot leaves survive later (donating) steps
    emb = np.asarray(snap.params["embed"]["table"]).copy()
    ex.step(src.batch(np.arange(8)))
    np.testing.assert_array_equal(emb, np.asarray(snap.params["embed"]["table"]))


def test_monitor_failure_with_spmd_executor_still_updates_plan():
    """A FAIL event routed to an executor that cannot reconfigure
    (ExecutorUnsupported) must still update the engine's PLAN — the
    caller then rebinds a HeteroTrainer from snapshot() against it."""
    from repro.core.monitor import NodeChangeMonitor
    arch, model, params, opt_cfg, mk_engine = make_setup()
    engine = mk_engine()
    ex = SPMDExecutor(model, params, opt_cfg, engine=engine)
    assert engine.executor is ex
    victim = engine.instances[0].nodes[-1]
    engine.monitor.inject(NodeChangeMonitor.FAIL, [victim])
    engine.monitor.poll(now=0.0)
    assert victim not in set(engine.nodes)
    assert engine.metrics.reconfigurations == 1


def test_oobleck_policy_is_an_executor():
    from repro.core import build_profile
    from repro.sim.policies import OobleckPolicy
    arch = reduced(get_arch("gpt2"), layers=8)
    profile = build_profile(arch, microbatch=2, seq_len=64)
    nodes = [f"n{i}" for i in range(6)]
    pol = OobleckPolicy(profile, nodes, f=1, global_batch=32, microbatch=2,
                        n0=2)
    assert isinstance(pol, Executor)
    assert pol.engine.executor is pol
    out = pol.step()
    assert out["sim_seconds"] > 0 and out["samples"] == 32
    victim = pol.engine.instances[0].nodes[0]
    rec = pol.recover({victim})
    assert rec["downtime_seconds"] > 0
    snap = pol.snapshot()
    assert snap["instances"] and snap["num_microbatches"]


def test_hetero_trainer_snapshot_roundtrips_through_ckpt(tmp_path):
    from repro.ckpt import CheckpointManager
    arch, model, params, opt_cfg, mk_engine = make_setup()
    trainer = HeteroTrainer(model, mk_engine(), params, opt_cfg)
    src = SyntheticLM(arch.vocab_size, SEQ, seed=4)
    disp = GlobalBatchDispenser(src)
    batches = disp.next_step(trainer.engine.batch.minibatch_sizes())
    trainer.train_step([microbatches(b, MB) for b in batches])
    snap = trainer.snapshot(data_state={"cursor": 16}, rng_seed=11)
    assert snap.step == 1
    mgr = CheckpointManager(str(tmp_path), num_layers=arch.num_layers)
    mgr.save(snap, block=True)
    template_opt = adamw.init(snap.params)
    restored = mgr.restore(snap.params, template_opt)
    assert restored.step == 1
    assert restored.data_state == {"cursor": 16}
    for a, b in zip(jax.tree.leaves(restored.params),
                    jax.tree.leaves(snap.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # moments are REAL (non-zero after a step), not re-initialized
    assert any(float(jnp.max(jnp.abs(m))) > 0
               for m in jax.tree.leaves(restored.opt_state.m))


# ----------------------------------------------------------------------
# 5. Kernel hot path (DESIGN.md §11): Pallas fwd+bwd inside the cached
#    per-template programs, still zero-compile across reconfiguration
# ----------------------------------------------------------------------
def test_kernel_path_recover_step_zero_compiles():
    """With attn_impl='kernel', ssd_impl='kernel' AND fuse='fused' the
    per-template step programs contain the Pallas forward AND backward
    kernels plus the fused residual+RMSNorm / QKV epilogues (the hybrid
    arch exercises flash-attention and SSD both).  warm_templates must
    still make failure -> recover -> first-step run with ZERO XLA
    backend compiles, and every grads program key must carry the kernel
    backend signature (the per-kind lowering plan is part of cache
    identity)."""
    from repro.kernels import ops as kops
    arch = reduced(get_arch("hymba_1_5b"), layers=2)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="kernel",
                  ssd_impl="kernel", fuse="fused", scan_layers=False)
    assert model.fuse == "fused"
    params = model.init(RNG)
    profile = build_profile(arch, microbatch=MB, seq_len=SEQ)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)
    from repro.core import EngineConfig, OobleckEngine
    engine = OobleckEngine(
        profile, [f"n{i}" for i in range(5)],
        EngineConfig(fault_tolerance=1, global_batch=8, microbatch=MB,
                     gpus_per_node=1, n0_override=2))
    trainer = HeteroTrainer(model, engine, params, opt_cfg)
    trainer.warm_templates()
    for key in trainer.cache.keys():
        if key[0] == "grads":
            assert key[1] == kops.backend_signature(), key

    src = SyntheticLM(arch.vocab_size, SEQ, seed=21)
    disp = GlobalBatchDispenser(src)

    def drive():
        batches = disp.next_step(trainer.engine.batch.minibatch_sizes())
        return trainer.train_step([microbatches(b, MB) for b in batches])

    out = drive()
    out["loss"].block_until_ready()
    assert bool(jnp.isfinite(out["loss"]))
    victim = trainer.engine.instances[0].nodes[-1]
    with track_compiles() as log:
        trainer.recover({victim})
        out = drive()
        out["loss"].block_until_ready()
    assert log.backend_compiles == 0, \
        f"{log.backend_compiles} XLA compiles during recover->step on " \
        f"the kernel path"
