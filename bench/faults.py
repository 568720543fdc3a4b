"""Faults planted under the timed path, and the control, for showing that
the correctness check fails where it should.

Each fault patches the trainer's class in this process only, for the
length of a ``with planted(name):`` block:

* ``state_unchanged``: the step computes but commits no update;
* ``half_batch``: every pipeline runs only the first half of its
  microbatches, and the mean is taken over those;
* ``no_exchange``: the cross-replica reduction is left out, each
  replica steps on its own gradient;
* ``loss_altered``: the loss is altered where the stage program
  produces it.

The control is the system with its own bfloat16 compute path switched
on (``Model(dtype=bfloat16)``, parameters and optimizer still float32):
one precision below the configuration's float32, the step a later change
would be tempted to take.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import time
from typing import Dict, Iterator

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "no_exchange", "loss_altered")


@contextlib.contextmanager
def planted(fault: str) -> Iterator[None]:
    import jax.numpy as jnp
    from repro.runtime.pipeline import HeteroTrainer

    name = {"state_unchanged": "_sync_and_update",
            "no_exchange": "_sync_and_update",
            "half_batch": "train_step",
            "loss_altered": "_run_compiled"}[fault]
    orig = getattr(HeteroTrainer, name)

    def unchanged(self, all_grads, weights):
        return jnp.zeros((), jnp.float32)

    def no_exchange(self, all_grads, weights):
        plan = self._bucket_plan()
        step_in = self.opt_step
        self.opt_step = self.opt_step + 1
        norm = None
        for run, grads, w in zip(self.runs, all_grads, weights):
            flats = self._bsync.contributions(plan, {0: grads}, [w])[0][0]
            norm = jnp.sqrt(sum(self._bsync._sumsq_prog(b.n)(f)
                                for b, f in zip(plan, flats)))
            self._bsync.update(plan, flats, run.states,
                               self._clip_scale(norm), step_in)
        return norm

    def half_batch(self, per_pipeline_batches):
        return orig(self, [mbs[:max(1, len(mbs) // 2)]
                           for mbs in per_pipeline_batches])

    def loss_altered(self, run, microbatches):
        grads, nll = orig(self, run, microbatches)
        return grads, nll * 1.01

    patch = {"state_unchanged": unchanged, "no_exchange": no_exchange,
             "half_batch": half_batch, "loss_altered": loss_altered}[fault]
    setattr(HeteroTrainer, name, patch)
    try:
        yield
    finally:
        setattr(HeteroTrainer, name, orig)


def control(cell, seed: int, seconds: float) -> Dict:
    """The checks of a run of ``cell`` with the system computing in
    bfloat16."""
    from bench import harness
    low = dataclasses.replace(cell, config=copy.deepcopy(cell.config))
    low.config["deployment"]["dtype"] = "bfloat16"
    return harness.run_cell(low, seed, seconds, False,
                            time.perf_counter())["checks"]


def values(checks: Dict) -> Dict[str, float]:
    return {k: float(np.float64(c["value"])) for k, c in checks.items()}
