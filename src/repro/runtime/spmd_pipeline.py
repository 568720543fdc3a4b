"""SPMD pipeline-parallel train/forward via shard_map (the TPU-native
mapping of one Oobleck pipeline template — DESIGN.md §2, §8).

Each stage of a (uniform) template owns L/S consecutive blocks; the
template's schedule is a static loop of M + S - 1 ticks in which every
stage computes one microbatch and hands its activation to stage+1 with
``jax.lax.ppermute``.  This is the program a pipeline instance launches
per microbatch wave on real hardware; the single-controller
HeteroTrainer (pipeline.py) remains the reference for heterogeneous
stage layouts (SPMD requires every shard to run the same program, so
stages must be uniform here — Oobleck's planner emits near-uniform
splits for homogeneous-cost blocks, making this the production fast
path).

Training runs in ONE SPMD program (``make_pipeline_train_step``):
differentiating through the scheduled scan transposes every
``ppermute``, so the backward pass is the same pipeline run in reverse
— activations hop forward, cotangents hop backward, per-stage gradient
accumulation falls out of the scan transpose exactly as 1F1B
accumulates per-microbatch grads.  Loss and optimizer update live in
the same jitted program with params/opt-state donated, so the
homogeneous zero-failure case trains with no per-step host round trips
at all.

Correctness is pinned by tests/test_spmd_pipeline.py: the pipelined
forward equals the plain forward bit-for-bit on a multi-device host
mesh, and the pipelined train step tracks a plain full-model step.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import Model
from repro.optim import adamw


def stack_by_stage(params_blocks, num_stages: int):
    """[L, ...] stacked blocks -> [S, L/S, ...]."""
    L = jax.tree.leaves(params_blocks)[0].shape[0]
    assert L % num_stages == 0, (L, num_stages)
    return jax.tree.map(
        lambda t: t.reshape(num_stages, L // num_stages, *t.shape[1:]),
        params_blocks)


def pipeline_forward(model: Model, params: Dict, x_mb: jax.Array,
                     mesh: Mesh, stage_axis: str = "stage") -> jax.Array:
    """Pipelined hidden-state forward.

    x_mb: [M, b, s, d_model] pre-embedded microbatches.  Returns
    [M, b, s, d_model] block-stack outputs (before final norm/head).
    """
    S = mesh.shape[stage_axis]
    M = x_mb.shape[0]
    blocks = stack_by_stage(params["blocks"], S)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def stage_program(stage_blocks, xs):
        # stage_blocks: [1, L/S, ...] local slice; xs: [M, b, s, d] replicated
        local = jax.tree.map(lambda t: t[0], stage_blocks)
        idx = jax.lax.axis_index(stage_axis)
        b, s, d = xs.shape[1:]
        # per-stage state: declared varying over the stage axis, so both
        # branches of the completion cond below agree on their types
        buf = jax.lax.pcast(jnp.zeros((b, s, d), xs.dtype), stage_axis,
                            to="varying")             # activation register
        outs = jax.lax.pcast(jnp.zeros_like(xs), stage_axis, to="varying")

        def tick(carry, t):
            buf, outs = carry
            inp = jax.lax.ppermute(buf, stage_axis, perm)
            feed = jnp.where(t < M, t, 0)
            inp = jnp.where(idx == 0, xs[feed], inp)
            out, _ = model.run_blocks(local, inp, jnp.zeros((), jnp.float32))
            # last stage finishes microbatch t - (S - 1) at tick t
            done = t - (S - 1)
            valid = (idx == S - 1) & (done >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice(
                    o, out[None], (jnp.maximum(done, 0), 0, 0, 0)),
                lambda o: o, outs)
            return (out, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(M + S - 1))
        # every stage holds its own `outs`; only the last stage's is real
        return outs

    fn = jax.shard_map(
        stage_program, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(stage_axis),
        check_vma=True)
    stacked = fn(blocks, x_mb)          # [S*M, b, s, d] stage-major
    return stacked.reshape(S, M, *x_mb.shape[1:])[-1]


# ----------------------------------------------------------------------
# Training: the same schedule, differentiated — one SPMD program
# ----------------------------------------------------------------------
def pipeline_loss(model: Model, params: Dict, tokens_mb: jax.Array,
                  labels_mb: jax.Array, mesh: Mesh,
                  stage_axis: str = "stage") -> jax.Array:
    """Mean next-token NLL over [M, b, s] microbatches through the
    pipelined forward.  Differentiable: the ppermute/scan schedule
    transposes into the reverse-order backward pipeline."""
    from repro.models.layers import cross_entropy
    logits = pipeline_logits(model, params, tokens_mb, mesh, stage_axis)
    nll = jax.vmap(lambda lg, lb: cross_entropy(lg[:, :-1], lb[:, :-1]))(
        logits, labels_mb)
    return jnp.mean(nll)


def make_pipeline_train_step(model: Model, opt_cfg: adamw.AdamWConfig,
                             mesh: Mesh, stage_axis: str = "stage",
                             donate: bool = True):
    """Jitted train step for the homogeneous fast path: pipelined
    forward, transposed-pipeline backward, AdamW — a single donated
    SPMD program, so a zero-failure cluster never leaves the device
    between steps.  tokens_mb/labels_mb: [M, b, s]."""
    def step(params, opt_state, tokens_mb, labels_mb):
        loss, grads = jax.value_and_grad(
            lambda p: pipeline_loss(model, p, tokens_mb, labels_mb,
                                    mesh, stage_axis))(params)
        params2, opt2, stats = adamw.apply(opt_cfg, params, grads, opt_state)
        return params2, opt2, {"loss": loss, **stats}

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def pipeline_logits(model: Model, params: Dict, tokens_mb: jax.Array,
                    mesh: Mesh, stage_axis: str = "stage") -> jax.Array:
    """Embed -> pipelined blocks -> final norm + head. tokens: [M, b, s]."""
    from repro.models.layers import embed, rms_norm, unembed
    x = jax.vmap(lambda t: embed(params["embed"], t, model.dtype))(tokens_mb)
    h = pipeline_forward(model, params, x, mesh, stage_axis)
    h = rms_norm(params["final_norm"].astype(h.dtype), h,
                 model.arch.rms_norm_eps)
    head = params.get("head", params["embed"])
    return jax.vmap(lambda v: unembed(head, v))(h)
