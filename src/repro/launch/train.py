"""End-to-end resilient training driver (deliverable b).

Runs REAL training (forward/backward/optimizer on actual arrays) through
the Oobleck stack: planner -> templates -> heterogeneous pipeline
instances -> compiled per-template step programs -> layer-granular sync
-> AdamW, with failure injection, recovery-from-replicas,
checkpointing, and restart.  The runtime sits behind the Executor
interface (runtime/executor.py): training steps are cached-program
calls, reconfiguration swaps programs by cache lookup, and checkpoint
hooks go through ``Executor.snapshot()``.

Container-friendly: uses a reduced config by default.  ``--full`` keeps
every published width; ``--full --layers N`` then cuts depth only (how
chip_smoke.py fits GPT-3 Medium on one chip).

    PYTHONPATH=src python -m repro.launch.train \
        --arch glm4-9b --nodes 5 --f 1 --steps 6 --kill-at 3
"""
from __future__ import annotations

import argparse
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_arch, sized
from repro.core import EngineConfig, OobleckEngine, build_profile
from repro.data import ByteCorpus, GlobalBatchDispenser, SyntheticLM

_TEXT = (b"Oobleck enables resilient distributed training of large models "
         b"with guaranteed fault tolerance using pipeline templates. "
         b"It instantiates f+1 logically equivalent heterogeneous pipeline "
         b"replicas and recovers from failures by copying model states "
         b"from surviving replicas instead of restarting from checkpoints. ")
from repro.models import Model
from repro.optim import adamw
from repro.runtime import HeteroTrainer
from repro.utils.compile_cache import enable_compile_cache


def microbatches(batch, mb_size):
    n = batch["tokens"].shape[0] // mb_size
    return [{k: v[i * mb_size:(i + 1) * mb_size] for k, v in batch.items()
             if not k.startswith("_")} for i in range(n)]


def _multiproc_hosting(nodes, procs):
    """node -> worker rank.  The LAST rank hosts exactly one node, so
    killing it (--kill-at) drops one node — the smallest failure a
    process death can model — and leaves the survivors above the
    (f+1)*n0 floor in the default 5-node/f=1 setup."""
    ranks = list(range(procs))
    host = {nodes[-1]: ranks[-1]}
    rest = nodes[:-1]
    per = -(-len(rest) // max(1, procs - 1)) if procs > 1 else len(rest)
    for i, n in enumerate(rest):
        host[n] = min(i // per, procs - 2) if procs > 1 else 0
    return host


def run_multiproc(args) -> dict:
    """--procs N: the same training loop through the multi-process
    backend (runtime/multihost.py) — coordinator here, N spawned worker
    processes execute; --kill-at SIGKILLs a worker and recovery runs
    from heartbeat detection, not an injected event."""
    from repro.runtime.multihost import MultiHostExecutor, make_job_spec

    nodes = [f"node{i}" for i in range(args.nodes)]
    spec = make_job_spec(
        arch=args.arch, layers=args.layers or 4, seq_len=args.seq_len,
        microbatch=args.microbatch, global_batch=args.global_batch,
        f=args.f, n0=args.n0, nodes=nodes, nodes_per_pod=args.pods,
        hosting=_multiproc_hosting(nodes, args.procs), procs=args.procs,
        seed=args.seed,
        opt={"lr": 3e-3, "warmup_steps": 0, "weight_decay": 0.0})
    source = ByteCorpus(_TEXT * 50, seq_len=args.seq_len)
    disp = GlobalBatchDispenser(source)
    losses = []
    with MultiHostExecutor(spec) as mh:
        engine = mh.engine
        print(f"[plan] procs={args.procs} hosting={mh.hosting} "
              f"pipelines={[i.template.num_nodes for i in engine.instances]}")
        t0 = time.perf_counter()
        mh.warm_templates()
        print(f"[warm] all workers warm in {time.perf_counter() - t0:.1f}s")
        for step in range(args.steps):
            if step == args.kill_at:
                victim = max(mh.procs)
                mh.kill_worker(victim)
                dead, ranks = mh.detected_dead(timeout=30.0)
                t0 = time.perf_counter()
                info = mh.recover(dead)
                bd = info["breakdown"]
                print(f"[fail] SIGKILL rank {victim} -> heartbeat detected "
                      f"{sorted(dead)} dead; recovered in "
                      f"{time.perf_counter() - t0:.2f}s (epoch "
                      f"{info['epoch']}, {info['fetched_bytes'] / 1e6:.1f}MB "
                      f"pulled cross-process in {info['fetches']} fetches, "
                      f"replan {bd['replan'] * 1e3:.0f}ms, commit "
                      f"{bd['commit'] * 1e3:.0f}ms)")
            batches = disp.next_step(engine.batch.minibatch_sizes())
            out = mh.step(
                [microbatches(b, args.microbatch) for b in batches])
            losses.append(float(out["loss"]))
            print(f"[step {step}] loss={losses[-1]:.4f} "
                  f"pipelines={out['num_pipelines']} "
                  f"divergence={mh.replica_divergence()}")
        compiles = mh.compile_counts()
        print(f"[done] loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
              f"worker compiles since warm: {compiles}")
    assert losses[-1] < losses[0], "training must reduce the loss"
    return {"losses": losses}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt3-medium")
    ap.add_argument("--nodes", type=int, default=5)
    ap.add_argument("--f", type=int, default=1)
    ap.add_argument("--n0", type=int, default=2)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--layers", type=int, default=None,
                    help="block count: the reduced config's (default 4), "
                         "or with --full a depth cut of the published one")
    ap.add_argument("--pods", type=int, default=8,
                    help="nodes per pod for the recovery data plane "
                         "(intra-pod copies ride ICI, cross-pod DCN)")
    ap.add_argument("--kill-at", type=int, default=-1,
                    help="inject a node failure before this step")
    ap.add_argument("--join-at", type=int, default=-1)
    ap.add_argument("--recovery-policy", default="replan",
                    choices=["replan", "adapt", "auto"],
                    help="failure response: 'replan' reconfigures from "
                         "templates and copies state from replicas; "
                         "'adapt' re-routes the damaged replica's "
                         "microbatches to surviving peers (ReCycle-style, "
                         "zero copy, zero recompile); 'auto' picks per "
                         "event by predicted downtime")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--codec", default="none",
                    choices=["none", "bf16", "int8"],
                    help="wire codec for cross-replica gradient sync "
                         "(bucketed data plane, with per-bucket error "
                         "feedback; 'none' is bitwise-exact)")
    ap.add_argument("--eager", action="store_true",
                    help="use the eager reference path instead of the "
                         "compiled per-template program cache")
    ap.add_argument("--no-warm", action="store_true",
                    help="skip bootstrap warming of the full template set")
    ap.add_argument("--attn-impl", default="naive",
                    choices=["naive", "blocked", "kernel", "auto"],
                    help="attention path for stage layers; 'kernel' is "
                         "the Pallas fwd+bwd hot path, 'auto' selects it "
                         "wherever a compiled lowering exists")
    ap.add_argument("--ssd-impl", default="chunked",
                    choices=["chunked", "scan", "kernel", "auto"],
                    help="SSD path for Mamba2/hybrid stage layers")
    ap.add_argument("--full", action="store_true",
                    help="every published width (see --layers)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--procs", type=int, default=0,
                    help="run through the multi-process backend with N "
                         "worker processes (runtime/multihost.py); "
                         "--kill-at then SIGKILLs a worker and recovery "
                         "runs from heartbeat detection")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.procs > 0:
        return run_multiproc(args)

    if args.eager and args.codec != "none":
        # the eager per-layer oracle has no wire codec; keep the engine's
        # pricing and the [sync] report consistent with what actually runs
        print(f"[sync] --eager ignores --codec {args.codec}: the per-layer "
              f"reference path syncs uncompressed")
        args.codec = "none"
    arch = sized(get_arch(args.arch), full=args.full, layers=args.layers,
                 smoke_layers=4)
    model = Model(arch, dtype=jnp.float32, remat=False,
                  attn_impl=args.attn_impl, ssd_impl=args.ssd_impl,
                  scan_layers=False)
    params = model.init(jax.random.PRNGKey(args.seed))

    profile = build_profile(arch, microbatch=args.microbatch,
                            seq_len=args.seq_len)
    nodes = [f"node{i}" for i in range(args.nodes)]
    engine = OobleckEngine(profile, nodes, EngineConfig(
        fault_tolerance=args.f, global_batch=args.global_batch,
        microbatch=args.microbatch, gpus_per_node=1, n0_override=args.n0,
        nodes_per_pod=args.pods, codec=args.codec,
        recovery_policy=args.recovery_policy))
    print(f"[plan] templates={list(engine.templates)} "
          f"pipelines={[i.template.num_nodes for i in engine.instances]} "
          f"microbatches={engine.batch.num_microbatches}")
    sched = engine.sync_schedule()
    print(f"[sync] {len(sched)} buckets, codec={args.codec}, "
          f"wire={sum(r.wire_bytes for r in sched) / 1e6:.1f}MB, "
          f"modeled exposed tail {engine._sync_tail_seconds() * 1e3:.2f}ms "
          f"on target hw")

    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=0, weight_decay=0.0)
    trainer = HeteroTrainer(model, engine, params, opt_cfg,
                            mode="eager" if args.eager else "compiled",
                            codec=args.codec)
    warm_s = 0.0
    if not args.eager and not args.no_warm:
        t0 = time.perf_counter()
        stats = trainer.warm_templates()
        warm_s = time.perf_counter() - t0
        print(f"[warm] {stats['compiles']} programs compiled for "
              f"{len(engine.templates)} templates in {warm_s:.1f}s — any "
              f"reconfiguration now swaps programs by lookup")
    warm_compiles = trainer.cache.stats.compiles
    source = ByteCorpus(_TEXT * 50, seq_len=args.seq_len)
    disp = GlobalBatchDispenser(source)
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, num_layers=arch.num_layers)
        # the engine checkpoints through the executor snapshot on an
        # unrecoverable shrink (< (f+1)*n0 nodes), §3.4
        engine.on_checkpoint = lambda: mgr.save(
            trainer.snapshot(disp.state(), args.seed), block=True)

    losses, step_s, first_batches, recover_s = [], [], None, None
    for step in range(args.steps):
        if step == args.kill_at:
            victim = engine.instances[0].nodes[-1]
            t0 = time.perf_counter()
            info = trainer.recover({victim})
            jax.block_until_ready([r.states for r in trainer.runs])
            wall = recover_s = time.perf_counter() - t0
            if info["policy"] == "adapt":
                bd = info["breakdown"]
                print(f"[fail] killed {victim}: adapted schedule in "
                      f"{wall:.2f}s (zero state copied, re-routed "
                      f"microbatches to {info['num_pipelines']} surviving "
                      f"pipelines, parked {info['parked_nodes']} as spares, "
                      f"modeled reroute exposure {bd['reroute'] * 1e3:.1f}ms "
                      f"on target hw, program cache: {info['cache']})")
            else:
                xfer = info["transfer"]
                phases = " ".join(f"{k} {v * 1e3:.1f}ms"
                                  for k, v in info["phases"].items())
                print(f"[fail] killed {victim}: recovered from replicas in "
                      f"{wall:.2f}s ({info['policy']}; "
                      f"copied {info['copied_bytes'] / 1e6:.0f}MB of state over "
                      f"{xfer['streams']} streams, "
                      f"{xfer['pod_local_fraction']:.0%} pod-local, modeled "
                      f"transfer {xfer['seconds'] * 1e3:.1f}ms on target hw; "
                      f"measured {phases}, "
                      f"{info['state_copy_bytes'] / 1e6:.0f}MB copied on "
                      f"device ({info['moved_state_bytes'] / 1e6:.0f}MB "
                      f"moved), {info['in_place_bytes'] / 1e6:.0f}MB bound "
                      f"in place, program cache: {info['cache']}), "
                      f"pipelines={[i.template.num_nodes for i in engine.instances]}")
        if step == args.join_at:
            raise SystemExit("join-at requires the elastic example; see "
                             "examples/spot_trace_replay.py")
        batches = disp.next_step(engine.batch.minibatch_sizes())
        mbs = [microbatches(b, args.microbatch) for b in batches]
        if first_batches is None:
            first_batches = [mb for per in mbs for mb in per]
        t0 = time.perf_counter()
        out = trainer.step(mbs)
        # the step ends when every replica's optimizer update has landed
        jax.block_until_ready((out["loss"],
                               [r.states for r in trainer.runs]))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(out["loss"]))
        print(f"[step {step}] loss={losses[-1]:.4f} "
              f"pipelines={out['num_pipelines']} "
              f"divergence={trainer.replica_divergence():.2e} "
              f"wall={step_s[-1]:.3f}s")
        if mgr and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            mgr.save(trainer.snapshot(disp.state(), args.seed))
    if mgr:
        mgr.wait()
    assert losses[-1] < losses[0], "training must reduce the loss"
    print(f"[done] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(cache: {trainer.cache.stats.as_dict()})")
    result = {"losses": losses, "step_seconds": step_s,
              "warm_seconds": warm_s, "recover_seconds": recover_s,
              "warm_compiles": warm_compiles,
              "compiles": trainer.cache.stats.compiles,
              "divergence": trainer.replica_divergence(),
              "first_batches": first_batches, "arch": arch}
    # free the replicas' device state before the caller uses the device
    del trainer, engine
    gc.collect()
    return result


if __name__ == "__main__":
    main()
