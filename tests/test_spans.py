"""Host spans and counters of the trainer's recovery and step
(utils/spans.py):

  1. a span makes no profiler call while no trace is collected, and
     writes nested annotations into a trace when one is;
  2. every recovery and join reports its measured phases, the bytes it
     bound in place, the bytes it copied on the device and the bytes of
     layers that came from another node — exactly, against the bound
     states and the old ownership — and the ProgramCache misses it
     caused;
  3. a step runs the compiled programs the bucket plan fixes (the count
     the benchmark's ``step_programs`` reads from the device trace), and
     every program carries its kind's name;
  4. the planning layer times itself with spans and loads no JAX.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, reduced
from repro.core import EngineConfig, OobleckEngine, build_profile
from repro.data import GlobalBatchDispenser, SyntheticLM
from repro.models import Model
from repro.optim import adamw
from repro.runtime import HeteroTrainer
from repro.utils import spans
from repro.utils.spans import span

GB, MB, SEQ = 16, 2, 16
PHASES = {"replan", "transfer_plan", "copy", "bind"}


class Spy(jax.profiler.TraceAnnotation):
    """A TraceAnnotation that records every one made."""
    made: list = []

    def __init__(self, name):
        Spy.made.append(name)
        super().__init__(name)


@pytest.fixture
def spy(monkeypatch):
    Spy.made = []
    monkeypatch.setattr(spans, "_TraceAnnotation", Spy)
    return Spy


def test_a_span_makes_no_profiler_call_when_no_trace_is_collected(spy):
    with span("oobleck.test.outer") as outer:
        with span("oobleck.test.inner") as inner:
            pass
    assert spy.made == []
    assert 0.0 <= inner.seconds <= outer.seconds


def test_spans_nest_in_a_profiler_trace(spy, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("oobleck.test.outer") as outer:
            with span("oobleck.test.inner") as inner:
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert spy.made == ["oobleck.test.outer", "oobleck.test.inner"]
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    got = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
           for plane in ProfileData.from_file(str(path)).planes
           for line in plane.lines for ev in line.events
           if ev.name.startswith("oobleck.test.")}
    (o0, o1), (i0, i1) = got["oobleck.test.outer"], got["oobleck.test.inner"]
    assert o0 <= i0 < i1 <= o1
    # each annotation encloses the block its span timed (a loaded CPU may
    # preempt between the two clocks, hence the generous upper side)
    for (t0, t1), sp in (((o0, o1), outer), ((i0, i1), inner)):
        assert sp.seconds - 1e-6 <= (t1 - t0) / 1e9 < sp.seconds + 0.25


# ----------------------------------------------------------------------
# A tiny trainer
# ----------------------------------------------------------------------
def make_trainer(sync_mode=None, codec="none", policy="replan"):
    arch = reduced(get_arch("gpt3_medium"), layers=2)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive",
                  scan_layers=False)
    profile = build_profile(arch, microbatch=MB, seq_len=SEQ)
    engine = OobleckEngine(
        profile, [f"n{i}" for i in range(5)],
        EngineConfig(fault_tolerance=1, global_batch=GB, microbatch=MB,
                     gpus_per_node=1, n0_override=2, codec=codec,
                     recovery_policy=policy))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)
    tr = HeteroTrainer(model, engine, model.init(jax.random.PRNGKey(3)),
                       opt_cfg, codec=codec, sync_mode=sync_mode)
    return arch, tr


def step(tr, disp):
    batches = disp.next_step(tr.engine.batch.minibatch_sizes())
    per_pipe = [[{k: b[k][i:i + MB] for k in ("tokens", "labels")}
                 for i in range(0, b["tokens"].shape[0], MB)]
                for b in batches]
    return tr.train_step(per_pipe)


def state_bytes(st):
    return sum(leaf.nbytes for leaf in jax.tree.leaves(st))


@pytest.mark.parametrize("event", ["fail", "join"])
def test_recovery_reports_phases_and_exact_copy_counters(event):
    arch, tr = make_trainer()
    held = {(node, l) for run in tr.runs for l in run.states
            for node in run.instance.layer_owners(l)}
    compiles = tr.cache.stats.compiles
    if event == "fail":
        info = tr.recover({tr.engine.instances[0].nodes[-1]})
    else:
        info = tr.join(["n9"])
    assert set(info["phases"]) == PHASES
    assert all(s >= 0.0 for s in info["phases"].values())
    # the planner's own clock, inside the span that also holds the
    # policy choice
    assert info["breakdown"]["replan"] == \
        tr.engine.last_reconfig.replan_seconds
    assert 0.0 < info["breakdown"]["replan"] <= info["phases"]["replan"]
    # nothing was warmed: the new layout's programs were cache misses
    assert info["breakdown"]["compile"] == tr.cache.stats.compiles - compiles
    assert info["breakdown"]["compile"] > 0

    bound = in_place = moved = 0
    for run in tr.runs:
        for l, st in run.states.items():
            bound += state_bytes(st)
            if (run.instance.layer_owners(l)[0], l) in held:
                in_place += state_bytes(st)
            else:
                moved += state_bytes(st)
    # every stage lives on one node, so each held state has one holder
    # and is bound in place; only moved layers are copied
    assert info["in_place_bytes"] == in_place
    assert info["state_copy_bytes"] == bound - in_place
    assert info["in_place_bytes"] + info["state_copy_bytes"] == bound
    assert info["moved_state_bytes"] == moved
    assert 0 < info["moved_state_bytes"] <= info["state_copy_bytes"]
    # every moved layer is one the copy plan routes to its new owner
    routed = {(t.dst_node, t.layer)
              for t in tr.engine.last_reconfig.copy_plan}
    assert {(run.instance.layer_owners(l)[0], l) for run in tr.runs
            for l in run.states} - held <= routed


def test_adaptation_reports_the_phases_it_runs():
    arch, tr = make_trainer(policy="adapt")
    tr.warm_templates()
    info = tr.recover(set(tr.engine.instances[0].nodes))
    assert info["policy"] == "adapt"
    assert set(info["phases"]) == {"replan", "bind"}
    assert info["breakdown"]["compile"] == 0


@pytest.mark.parametrize("sync_mode,codec", [
    ("bucketed", "none"), ("bucketed", "int8"), ("perlayer", "none")])
def test_step_counts_the_programs_the_bucket_plan_fixes(sync_mode, codec):
    arch, tr = make_trainer(sync_mode=sync_mode, codec=codec)
    disp = GlobalBatchDispenser(SyntheticLM(arch.vocab_size, SEQ, seed=9))
    R = len(tr.runs)
    if sync_mode == "perlayer":
        want = R + R * tr.num_layers         # grads + per-layer update
    else:
        B = len(tr.engine.sync_plan())
        ef = R * B if codec != "none" else 0
        want = R + 2 * R * B + (R - 1) * B + B + R * B + ef
    step(tr, disp)                           # builds every program
    runs = []

    def counted(prog):
        def run(*args):
            runs.append(1)
            return prog(*args)
        return run

    for key, prog in tr.cache._programs.items():
        tr.cache._programs[key] = counted(prog)
    for n in range(1, 3):
        step(tr, disp)
        assert len(runs) == n * want


def test_every_program_carries_its_kinds_name():
    names = {"grads": "jit_grads_fn", "bpack": "jit_bucket_pack",
             "bscale": "jit_bucket_scale", "badd": "jit_bucket_add",
             "bsumsq": "jit_bucket_sumsq", "bef": "jit_bucket_ef",
             "bupdate": "jit_bucket_update", "update": "jit_layer_update",
             "lcopy": "jit_layer_copy"}
    seen = set()
    for sync_mode, codec in [("bucketed", "int8"), ("perlayer", "none")]:
        arch, tr = make_trainer(sync_mode=sync_mode, codec=codec)
        tr.recover({tr.engine.instances[0].nodes[-1]})
        for key, prog in tr.cache._programs.items():
            module = prog.as_text().split(None, 2)[1].rstrip(",")
            assert module == names[key[0]], key[0]
            seen.add(key[0])
    assert seen == set(names)


def test_the_planning_layer_loads_no_jax():
    code = ("import sys; import repro.core, repro.core.engine, "
            "repro.core.reconfigure; from repro.utils.spans import span\n"
            "with span('oobleck.test.plan') as sp: pass\n"
            "sys.exit('jax' in sys.modules or sp.seconds <= 0)")
    src = Path(__file__).resolve().parent.parent / "src"
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=str(src)))
