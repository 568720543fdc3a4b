"""Multi-process execution backend (DESIGN.md §15), REAL processes.

The acceptance contract of the ConfigurationEngine/ExecutionEngine
split, against subprocess-spawned workers on localhost:

  1. LIFECYCLE (3 workers) — train in bitwise lockstep with the
     single-process HeteroTrainer; SIGKILL a worker: the death is
     detected through the coordination channel (socket EOF /
     heartbeat — no injected event), survivors agree on a
     reconfiguration epoch, layer state moves between processes as
     actual socket transfers, the survivors recompile NOTHING, and the
     post-recovery losses are BITWISE equal to the single-process
     trainer driven through the same failure trace.  Checkpoints from
     the surviving processes elect one manifest writer.
  2. CONFORMANCE + JOIN + FAULT INJECTION (2 workers) —
     MultiHostExecutor honours the same Executor interface as every
     other runtime: step parity, snapshot round-trip, elastic join
     through the same two-phase commit; then SIGKILL the lead rank
     MID-STEP — the in-flight iteration is lost without mutating state
     (§3.3, WorkerLost), and the survivor recovers and continues the
     reference trace bitwise.

Heavy (each worker compiles its program set); guarded by the same
REPRO_DRYRUN_TIMEOUT budget as the other subprocess suites.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.configs import get_arch, reduced
from repro.core import EngineConfig, OobleckEngine, build_profile
from repro.data import GlobalBatchDispenser, SyntheticLM
from repro.models import Model
from repro.optim import adamw
from repro.runtime import Executor, HeteroTrainer, WorkerLost
from repro.runtime.multihost import (MultiHostExecutor, ShardTrainer,
                                     make_job_spec)

GB, MB, SEQ, L = 16, 2, 16, 4
NODES = [f"n{i}" for i in range(5)]
# explicit hosting: rank 1 hosts exactly n2 — a NON-lead member of
# replica (n0, n1, n2) — so SIGKILLing it damages one replica while
# both surviving ranks keep their steady-state lead assignments (the
# strict zero-recompile window applies: no survivor traces anything
# new), stays above the (f+1)*n0 floor, and the shrunk replica's
# rebind still moves layer state between processes
HOSTING = {"n0": 0, "n1": 0, "n2": 1, "n3": 2, "n4": 2}
TIMEOUT = float(os.environ.get("REPRO_DRYRUN_TIMEOUT", "600"))


def _spec(hosting, procs):
    return make_job_spec(arch="gpt3_medium", layers=L, seq_len=SEQ,
                         microbatch=MB, global_batch=GB, f=1, n0=2,
                         nodes=NODES, hosting=hosting, procs=procs,
                         seed=11)


def _reference():
    arch = reduced(get_arch("gpt3_medium"), layers=L)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive",
                  scan_layers=False)
    params = model.init(jax.random.PRNGKey(11))
    profile = build_profile(arch, microbatch=MB, seq_len=SEQ)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)
    engine = OobleckEngine(profile, list(NODES),
                           EngineConfig(fault_tolerance=1, global_batch=GB,
                                        microbatch=MB, gpus_per_node=1,
                                        n0_override=2))
    trainer = HeteroTrainer(model, engine, params, opt_cfg, mode="compiled")
    return arch, trainer


def _microbatches(batch):
    n = batch["tokens"].shape[0] // MB
    return [{k: v[i * MB:(i + 1) * MB] for k, v in batch.items()
             if not k.startswith("_")} for i in range(n)]


def _feed(disp, engine):
    return [_microbatches(b)
            for b in disp.next_step(engine.batch.minibatch_sizes())]


def _bitwise(a, b):
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_multihost_is_an_executor_subclass():
    assert issubclass(MultiHostExecutor, Executor)
    assert issubclass(ShardTrainer, Executor)


def test_replan_fingerprint_is_hash_seed_independent():
    """Every process dry-runs the failure plan independently; the plan
    fingerprint (which includes the copy plan's source picks) must not
    depend on the interpreter's string-hash seed.  Regression: the copy
    planner used to break load ties by SET iteration order."""
    import json
    import subprocess
    import sys

    import repro
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    prog = (
        "import json, sys\n"
        "from repro.runtime.multihost import build_setup, make_job_spec\n"
        "spec = json.loads(sys.argv[1])\n"
        "*_, engine = build_setup(spec)\n"
        "spares = [n for n in engine.spare_nodes if n != 'n2']\n"
        "r = engine.reconf.on_failure(engine.instances, {'n2'},"
        " spares=spares)\n"
        "print(engine.plan_fingerprint(r))\n")
    fps = set()
    for seed in ("0", "1", "2"):
        env = dict(os.environ,
                   PYTHONHASHSEED=seed,
                   PYTHONPATH=src + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""),
                   JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", prog, json.dumps(_spec(HOSTING, 3))],
            env=env, capture_output=True, text=True, timeout=TIMEOUT)
        assert out.returncode == 0, out.stderr
        fps.add(out.stdout.strip())
    assert len(fps) == 1, fps


def test_coordinator_plans_without_touching_a_device():
    """On an accelerator host every chip belongs to a worker: the
    coordinator's setup plans on shapes and never initializes a JAX
    backend."""
    import json
    import subprocess
    import sys

    import repro
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    prog = (
        "import json, sys\n"
        "from jax._src import xla_bridge\n"
        "from repro.runtime.multihost import build_setup\n"
        "*_, engine = build_setup(json.loads(sys.argv[1]), abstract=True)\n"
        "print(xla_bridge.backends_are_initialized(),"
        " engine.plan_fingerprint())\n")
    env = dict(os.environ, PYTHONPATH=src + os.pathsep + os.environ.get(
        "PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-c", prog, json.dumps(_spec(HOSTING, 3))],
        env=env, capture_output=True, text=True, timeout=TIMEOUT)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[0] == "False", out.stdout


def test_worker_device_check_one_process_per_chip():
    from repro.runtime.multihost import _check_worker_devices
    # CPU workers share the host's cores freely
    _check_worker_devices({r: {"platform": "cpu", "local_devices": 1}
                           for r in range(3)})
    _check_worker_devices({0: {"platform": "tpu", "local_devices": 4}})
    # a worker that could not open a held chip fell back to the CPU
    with pytest.raises(RuntimeError, match="different platforms"):
        _check_worker_devices({0: {"platform": "tpu", "local_devices": 1},
                               1: {"platform": "cpu", "local_devices": 1}})
    with pytest.raises(RuntimeError, match="one worker per chip"):
        _check_worker_devices({r: {"platform": "tpu", "local_devices": 1}
                               for r in range(2)})


def test_sigkill_lifecycle_parity_zero_compiles(tmp_path):
    arch, ref = _reference()
    ref.warm_templates()
    src = SyntheticLM(arch.vocab_size, SEQ, seed=5)
    d_ref, d_mh = GlobalBatchDispenser(src), GlobalBatchDispenser(src)

    with MultiHostExecutor(_spec(HOSTING, 3), rpc_timeout=TIMEOUT) as mh:
        assert mh.engine.plan_fingerprint() == ref.engine.plan_fingerprint()
        mh.warm_templates()

        # bitwise lockstep with the single-process trainer
        for _ in range(2):
            o_ref = ref.step(_feed(d_ref, ref.engine))
            o_mh = mh.step(_feed(d_mh, mh.engine))
            assert _bitwise(o_ref["loss"], o_mh["loss"])
            assert _bitwise(o_ref["grad_norm"], o_mh["grad_norm"])
        assert mh.replica_divergence() == 0
        mh.mark_compiles()      # steady state: all step glue ops traced

        # SIGKILL a worker; detection comes from the channel
        # (EOF/heartbeat), NOT from an injected event
        mh.kill_worker(1)
        dead, ranks = mh.detected_dead(timeout=30.0)
        assert dead == {"n2"} and ranks == {1}

        # two-phase agreed reconfiguration; the replacement node's
        # state crosses processes over the data plane
        info = mh.recover(dead)
        ref.recover({"n2"})
        assert info["epoch"] == ref.engine.epoch == 1
        assert info["fetched_bytes"] > 0 and info["fetches"] >= 1
        # same plan as the single-process trainer, structurally (the
        # fingerprint's instance ids differ: the two-phase protocol
        # consumes extra reconfigurator ids for its PREPARE dry-run)
        assert ([i.nodes for i in mh.engine.instances]
                == [i.nodes for i in ref.engine.instances])
        assert (mh.engine.batch.num_microbatches
                == ref.engine.batch.num_microbatches)

        # post-recovery: bitwise lockstep continues, survivors
        # recompiled NOTHING
        for _ in range(2):
            o_ref = ref.step(_feed(d_ref, ref.engine))
            o_mh = mh.step(_feed(d_mh, mh.engine))
            assert _bitwise(o_ref["loss"], o_mh["loss"])
        compiles = mh.compile_counts()
        assert sorted(compiles) == [0, 2]
        assert all(v == 0 for v in compiles.values()), compiles
        assert mh.replica_divergence() == 0

        # full state: snapshot params bitwise-equal to the reference
        snap_mh, snap_ref = mh.snapshot(), ref.snapshot()
        assert snap_mh.step == snap_ref.step
        for x, y in zip(jax.tree.leaves(snap_mh.params),
                        jax.tree.leaves(snap_ref.params)):
            assert _bitwise(x, y)

        # multi-writer checkpoint: every lead writes shards, exactly
        # one elected process commits the manifest
        stats = mh.save_checkpoint(str(tmp_path))
        wrote = [r for r, s in stats.items() if s["manifests_skipped"] == 0]
        assert len(wrote) == 1
        mgr = CheckpointManager(str(tmp_path), num_layers=arch.num_layers,
                                async_mode=False)
        assert mgr.list_steps() == [snap_mh.step]
        assert mgr.verify(snap_mh.step)


def test_two_proc_conformance_step_snapshot_join():
    arch, ref = _reference()
    src = SyntheticLM(arch.vocab_size, SEQ, seed=9)
    d_ref, d_mh = GlobalBatchDispenser(src), GlobalBatchDispenser(src)
    hosting = {"n0": 0, "n1": 0, "n2": 0, "n3": 1, "n4": 1}

    with MultiHostExecutor(_spec(hosting, 2), rpc_timeout=TIMEOUT) as mh:
        assert isinstance(mh, Executor)
        o_ref = ref.step(_feed(d_ref, ref.engine))
        o_mh = mh.step(_feed(d_mh, mh.engine))
        assert _bitwise(o_ref["loss"], o_mh["loss"])

        # elastic join rides the same two-phase commit
        info = mh.join(["n5"])
        ref.join(["n5"])
        assert info["epoch"] == ref.engine.epoch
        assert mh.engine.plan_fingerprint() == ref.engine.plan_fingerprint()
        assert "n5" in mh.hosting

        o_ref = ref.step(_feed(d_ref, ref.engine))
        o_mh = mh.step(_feed(d_mh, mh.engine))
        assert _bitwise(o_ref["loss"], o_mh["loss"])
        assert mh.replica_divergence() == 0

        snap_mh, snap_ref = mh.snapshot(), ref.snapshot()
        for x, y in zip(jax.tree.leaves(snap_mh.params),
                        jax.tree.leaves(snap_ref.params)):
            assert _bitwise(x, y)

        # fault injection: SIGKILL the rank leading replica(s) while a
        # step is in flight — the iteration is LOST (§3.3), nothing
        # commits anywhere, and both sides drop the batch
        batches = _feed(d_mh, mh.engine)
        _feed(d_ref, ref.engine)
        mh.kill_worker(1)
        with pytest.raises(WorkerLost) as e:
            mh.step(batches)
        assert 1 in e.value.ranks
        dead, ranks = mh.detected_dead(timeout=30.0)
        assert dead == {"n3", "n4"} and ranks == {1}

        info = mh.recover(dead)
        ref.recover({"n3", "n4"})
        assert info["epoch"] == ref.engine.epoch
        assert ([i.nodes for i in mh.engine.instances]
                == [i.nodes for i in ref.engine.instances])

        # the lost iteration left state untouched: the sole survivor
        # continues in bitwise lockstep with the reference trace
        o_ref = ref.step(_feed(d_ref, ref.engine))
        o_mh = mh.step(_feed(d_mh, mh.engine))
        assert _bitwise(o_ref["loss"], o_mh["loss"])
