"""The recovery copy phase's one-holder-per-buffer rule
(``HeteroTrainer._rebind``, DESIGN.md §9):

  1. after a failure and after a join, no two layer-state leaves of any
     two runs share a device buffer;
  2. a state that stays on its node is the very object the old run held
     (bound in place); a moved state is new buffers equal in value to
     the survivors' copy of the layer;
  3. a state that two new owners both claim as held (what two owners of
     one old multi-node stage would do) has exactly one in-place holder;
     the second claimant gets a copy;
  4. the replicas stay bit-identical through the following steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core import EngineConfig, OobleckEngine, build_profile
from repro.data import GlobalBatchDispenser, SyntheticLM
from repro.models import Model
from repro.optim import adamw
from repro.runtime import HeteroTrainer

GB, MB, SEQ = 8, 2, 16


def make_trainer():
    arch = reduced(get_arch("gpt3_medium"), layers=2)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive",
                  scan_layers=False)
    engine = OobleckEngine(
        build_profile(arch, microbatch=MB, seq_len=SEQ),
        [f"n{i}" for i in range(5)],
        EngineConfig(fault_tolerance=1, global_batch=GB, microbatch=MB,
                     gpus_per_node=1, n0_override=2))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)
    tr = HeteroTrainer(model, engine, model.init(jax.random.PRNGKey(5)),
                       opt_cfg)
    tr.warm_templates()
    disp = GlobalBatchDispenser(SyntheticLM(arch.vocab_size, SEQ, seed=4))
    return tr, disp


def step(tr, disp):
    batches = disp.next_step(tr.engine.batch.minibatch_sizes())
    per_pipe = [[{k: b[k][i:i + MB] for k in ("tokens", "labels")}
                 for i in range(0, b["tokens"].shape[0], MB)]
                for b in batches]
    out = tr.train_step(per_pipe)
    jax.block_until_ready((out["loss"], [r.states for r in tr.runs]))
    return out


def leaves(st):
    return jax.tree.leaves(st)


def state_bytes(st):
    return sum(leaf.nbytes for leaf in leaves(st))


def buffers(states):
    return [leaf.unsafe_buffer_pointer() for st in states
            for leaf in leaves(st)]


def assert_one_holder_per_buffer(tr):
    ptrs = buffers(st for run in tr.runs for st in run.states.values())
    assert len(ptrs) == len(set(ptrs))


@pytest.mark.parametrize("event", ["fail", "join"])
def test_held_states_bind_in_place_and_moved_states_are_copied(event):
    tr, disp = make_trainer()
    step(tr, disp)
    old = {(node, l): st for run in tr.runs for l, st in run.states.items()
           for node in run.instance.layer_owners(l)}
    old_ptrs = set(buffers(old.values()))
    # every replica is bit-identical, so any survivor's copy of a layer
    # is the value a moved layer must arrive with
    values = {l: [np.asarray(x) for x in leaves(st)]
              for (_, l), st in old.items()}
    if event == "fail":
        info = tr.recover({tr.engine.instances[0].nodes[-1]})
    else:
        info = tr.join(["n9"])

    assert_one_holder_per_buffer(tr)
    in_place = moved = 0
    for run in tr.runs:
        for l, st in run.states.items():
            held = old.get((run.instance.layer_owners(l)[0], l))
            if held is not None:
                assert st is held
                in_place += state_bytes(st)
                continue
            assert not set(buffers([st])) & old_ptrs
            for got, want in zip(leaves(st), values[l]):
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(np.asarray(got), want)
            moved += state_bytes(st)
    assert in_place > 0 and moved > 0
    assert info["in_place_bytes"] == in_place
    assert info["state_copy_bytes"] == info["moved_state_bytes"] == moved
    assert info["breakdown"]["compile"] == 0

    for _ in range(2):
        step(tr, disp)
    assert_one_holder_per_buffer(tr)
    assert tr.replica_divergence() == 0.0


def test_a_second_claimant_of_a_held_state_gets_a_copy():
    # the planner keeps every stage on one node (DESIGN.md §2), so two
    # owners of one held state are driven through the rule directly:
    # every new run claims replica 0's states as held
    tr, disp = make_trainer()
    step(tr, disp)
    base = dict(tr.runs[0].states)
    values = {l: [np.asarray(x) for x in leaves(st)]
              for l, st in base.items()}
    counts = tr._rebind(lambda node, l: (base[l], False))
    tr.bind()

    assert len(tr.runs) > 1
    assert_one_holder_per_buffer(tr)
    for l, st in base.items():
        holders = [run for run in tr.runs if run.states[l] is st]
        assert len(holders) == 1
        for run in tr.runs:
            for got, want in zip(leaves(run.states[l]), values[l]):
                np.testing.assert_array_equal(np.asarray(got), want)
    nbytes = sum(state_bytes(st) for st in base.values())
    assert counts == {"in_place_bytes": nbytes,
                      "state_copy_bytes": (len(tr.runs) - 1) * nbytes,
                      "moved_state_bytes": 0}

    for _ in range(2):
        step(tr, disp)
    assert_one_holder_per_buffer(tr)
    assert tr.replica_divergence() == 0.0
