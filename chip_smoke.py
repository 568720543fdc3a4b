#!/usr/bin/env python3
"""On-chip smoke test of the resilient training path (TPU v5e).

    python3 chip_smoke.py             # one chip (the default)
    python3 chip_smoke.py --chips 4   # the four-chip pipeline phase only

One chip: every Pallas kernel kind must compile (the lowering plan) and
agree with its ``kernels/ref.py`` oracle at real widths; then
``repro.launch.train.main`` — the HeteroTrainer's compiled per-template
programs — trains GPT-3 Medium (paper Table 1) at its published widths
with depth the only cut: warm the template set, take steps, kill one
logical node, recover from the surviving replica, take more steps.  The
run fails unless the loss is finite and falls, the replicas stay
identical, nothing compiles after warm-up, and the first step's loss
matches a plain highest-precision XLA reference on the same params and
batch.

Four chips: the shard_map pipeline train step
(``runtime/spmd_pipeline.py``) over a 4-stage mesh at the same widths,
against the single-device full-model step.

Run from the root of a checkout.  Without a TPU, or without the
checkout's ``src/``, it exits nonzero and prints no result.  The last
line of a passing run is the JSON device record.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The cuts.  Depth and batch only; every width is GPT-3 Medium's.  Sized
# from the v5e compile of one grads program (8 layers, microbatch 1,
# seq 2048: 0.76 GiB of params in, 0.76 GiB of grads out, 1.58 GiB of
# temporaries): two replicas' params + Adam moments (24 B/param), both
# pipelines' grads and the sync buffers come to ~44 B/param, ~9 GB at
# 204M params, which leaves room on a 16 GB chip; 12 layers would not.
LAYERS, MICROBATCH, GLOBAL_BATCH, SEQ_LEN = 8, 1, 4, 2048
STEPS, KILL_AT, SEED = 6, 3, 0
PUBLISHED_LAYERS = 24
# Four chips: one block per stage.  Memory is not the bound there (the
# v5e compile puts ~4 GiB on each chip at 8 layers); compile time is.
PIPELINE_LAYERS, PIPELINE_MICROBATCHES = 4, 2

# The TPU's default matmul precision rounds f32 dot inputs to bf16
# (2^-9 relative) where the reference computes at full precision.  That
# perturbs each logit by ~1e-3 with a random sign, so the mean NLL over
# the first step's 8188 tokens moves by far less than 5e-3 nats; an
# attention or epilogue bug moves whole residual streams, and the loss
# with them.
REF_LOSS_ATOL = 5e-3
# Kernel vs oracle at real widths, relative to the oracle's largest
# magnitude: bf16 MXU passes give ~2^-9 relative per product; a layout,
# mask or carry bug gives O(1).
KERNEL_RTOL = 2e-2


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ----------------------------------------------------------------------
# Kernels: lowering plan and parity at real widths
# ----------------------------------------------------------------------
def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    t0 = time.perf_counter()
    try:
        plan = ops.lowering_plan()
    except ops.KernelLoweringError as e:
        fail(str(e))
    log(f"lowering plan {dict(plan)} ({time.perf_counter() - t0:.1f}s)")
    if not all(ok for _, ok in plan):
        fail(f"kernel kinds not compiled: {[k for k, ok in plan if not ok]}")

    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    normal = lambda shape, s=1.0: s * jax.random.normal(next(ks), shape)
    highest = lambda f: jax.jit(jax.default_matmul_precision("highest")(f))

    def check(name, fn, oracle, *args):
        got = jax.jit(fn)(*args)
        want = highest(oracle)(*args)
        err = scale = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if not bool(jnp.all(jnp.isfinite(g))):
                fail(f"{name}: non-finite kernel output")
            err = max(err, float(jnp.max(jnp.abs(g - w))))
            scale = max(scale, float(jnp.max(jnp.abs(w))))
        log(f"parity {name}: max|kernel - oracle| {err:.3e} "
            f"(oracle max {scale:.3e})")
        if err > KERNEL_RTOL * max(scale, 1.0):
            fail(f"{name} disagrees with its oracle")

    def with_grads(f, n):
        def run(*a):
            out, vjp = jax.vjp(f, *a[:n])
            return out, vjp(jax.tree.map(jnp.ones_like, out))
        return run

    q, k, v = (normal((1, SEQ_LEN, 16, 64)) for _ in range(3))
    check("flash fwd+bwd", with_grads(ops.flash_attention, 3),
          with_grads(ref.attention_ref, 3), q, k, v)

    x, r = normal((SEQ_LEN, 1024)), normal((SEQ_LEN, 1024))
    w = 1.0 + normal((1024,), 0.1)
    from repro.kernels.fused import add_rmsnorm_ref, qkv_ref
    check("fused_norm fwd+bwd", with_grads(ops.fused_add_rmsnorm, 3),
          with_grads(add_rmsnorm_ref, 3), x, r, w)
    wq, wk, wv = (normal((1024, 1024), 0.03) for _ in range(3))
    check("fused_qkv fwd+bwd", with_grads(ops.fused_qkv, 4),
          with_grads(qkv_ref, 4), x, wq, wk, wv)

    # mamba2-780m's SSD widths: 48 heads of P 64, N 128, chunk 256
    xs = normal((1, SEQ_LEN, 48, 64))
    dt = jax.nn.softplus(normal((1, SEQ_LEN, 48)) - 2.0)
    A = -jnp.exp(normal((48,), 0.5))
    B, C = normal((1, SEQ_LEN, 48, 128), 0.1), normal((1, SEQ_LEN, 48, 128),
                                                      0.1)
    check("ssd fwd+bwd",
          with_grads(lambda *a: ops.ssd(*a, chunk=256), 5),
          with_grads(ref.ssd_ref, 5), xs, dt, A, B, C)


# ----------------------------------------------------------------------
# One chip: the resilient training path end to end
# ----------------------------------------------------------------------
def reference_loss(arch, batches) -> float:
    """Mean first-step NLL from the plain XLA model (naive attention, no
    fused epilogues) at highest matmul precision, same params and data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import Model

    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive",
                  fuse="none")
    params = model.init(jax.random.PRNGKey(SEED))
    nll = jax.jit(jax.default_matmul_precision("highest")(
        lambda p, t, l: model.loss(p, {"tokens": t, "labels": l})[1]["nll"]))
    return float(np.mean([nll(params, mb["tokens"], mb["labels"])
                          for mb in batches]))


def one_chip_phase() -> None:
    import jax
    import numpy as np
    from repro.launch import train

    reduced = {"layers": [LAYERS, PUBLISHED_LAYERS],
               "microbatch": MICROBATCH, "global_batch": GLOBAL_BATCH,
               "seq_len": SEQ_LEN, "steps": STEPS}
    log(f"reduced {json.dumps(reduced)} (depth and batch only; widths are "
        f"GPT-3 Medium's)")
    res = train.main([
        "--arch", "gpt3_medium", "--full", "--layers", str(LAYERS),
        "--seq-len", str(SEQ_LEN), "--microbatch", str(MICROBATCH),
        "--global-batch", str(GLOBAL_BATCH), "--nodes", "5", "--f", "1",
        "--n0", "2", "--steps", str(STEPS), "--kill-at", str(KILL_AT),
        "--attn-impl", "kernel", "--seed", str(SEED)])
    losses = res["losses"]
    steps = res["step_seconds"]
    log(f"warm-up {res['warm_seconds']:.1f}s, "
        f"{res['warm_compiles']} programs compiled")
    log(f"step wall seconds {[round(s, 4) for s in steps]} "
        f"(ending in block_until_ready)")
    log(f"recovery {res['recover_seconds']:.3f}s")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    if not np.all(np.isfinite(losses)):
        fail(f"non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        fail(f"loss did not fall: {losses}")
    if res["divergence"] != 0:
        fail(f"replica divergence {res['divergence']}")
    grown = res["compiles"] - res["warm_compiles"]
    log(f"compiles after warm-up (through kill and recovery): {grown}")
    if grown:
        fail(f"{grown} programs compiled after warm-up")
    ref = reference_loss(res["arch"], res["first_batches"])
    log(f"first-step loss {losses[0]:.6f} vs highest-precision XLA "
        f"reference {ref:.6f}: |diff| {abs(losses[0] - ref):.3e} "
        f"(tolerance {REF_LOSS_ATOL})")
    if not abs(losses[0] - ref) <= REF_LOSS_ATOL:
        fail("first-step loss disagrees with the reference")


# ----------------------------------------------------------------------
# Four chips: the shard_map pipeline step vs the single-device step
# ----------------------------------------------------------------------
def pipeline_setup():
    """The model and optimizer of the four-chip phase (also what a
    compile rehearsal against a described topology lowers)."""
    import jax.numpy as jnp
    from repro.configs import get_arch, sized
    from repro.models import Model
    from repro.optim import adamw

    arch = sized(get_arch("gpt3_medium"), full=True, layers=PIPELINE_LAYERS)
    # naive attention, no fused epilogues, remat: the plain XLA model, so
    # the comparison isolates the schedule (as tests/test_spmd_pipeline.py)
    model = Model(arch, dtype=jnp.float32, remat=True, attn_impl="naive",
                  fuse="none")
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)
    return model, opt_cfg


def pipeline_programs(model, opt_cfg, mesh):
    """(pipelined grads, pipelined train step, reference grads,
    reference update), jitted at highest matmul precision so the
    comparison measures the schedule, not the MXU's bf16 passes."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import cross_entropy
    from repro.optim import adamw
    from repro.runtime.spmd_pipeline import (make_pipeline_train_step,
                                             pipeline_loss)

    def ref_loss(p, tokens, labels):
        nll = [cross_entropy(model.forward(p, tokens[i])[0][:, :-1],
                             labels[i][:, :-1])
               for i in range(tokens.shape[0])]
        return jnp.mean(jnp.stack(nll))

    hp = jax.default_matmul_precision("highest")
    pipe_grads = jax.jit(hp(jax.grad(
        lambda p, t, l: pipeline_loss(model, p, t, l, mesh))))
    step = hp(make_pipeline_train_step(model, opt_cfg, mesh, donate=False))
    ref_grads = jax.jit(hp(jax.grad(ref_loss)))
    ref_update = jax.jit(hp(lambda p, g, o: adamw.apply(opt_cfg, p, g, o)))
    return pipe_grads, step, ref_grads, ref_update


def four_chip_phase() -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.optim import adamw

    devices = jax.devices()[:4]
    if len(devices) < 4:
        fail(f"--chips 4 needs four devices, JAX sees {len(jax.devices())}")
    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,),
                         devices=devices)
    ids = sorted(d.id for d in mesh.devices.flat)
    log(f"mesh {dict(mesh.shape)} over device ids {ids}")
    if len(set(ids)) != 4:
        fail("the stage mesh does not span four devices")

    model, opt_cfg = pipeline_setup()
    M = PIPELINE_MICROBATCHES
    params = model.init(jax.random.PRNGKey(SEED))
    kt, kl = jax.random.split(jax.random.PRNGKey(SEED + 1))
    vocab = model.arch.vocab_size
    tokens = jax.random.randint(kt, (M, MICROBATCH, SEQ_LEN), 0, vocab)
    labels = jax.random.randint(kl, (M, MICROBATCH, SEQ_LEN), 0, vocab)
    opt = adamw.init(params)
    cuts = {"layers": [PIPELINE_LAYERS, PUBLISHED_LAYERS], "stages": 4,
            "microbatches": M, "microbatch": MICROBATCH, "seq_len": SEQ_LEN}
    log(f"reduced {json.dumps(cuts)}")

    pipe_grads, step, ref_grads, ref_update = pipeline_programs(
        model, opt_cfg, mesh)
    on_mesh = jax.device_put((params, opt, tokens, labels),
                             NamedSharding(mesh, P()))
    on_one = jax.device_put((params, opt, tokens, labels), devices[0])

    t0 = time.perf_counter()
    with mesh:
        gp = jax.block_until_ready(pipe_grads(*on_mesh[:1], *on_mesh[2:]))
        p2, _, stats = jax.block_until_ready(step(*on_mesh))
    t_pipe = time.perf_counter() - t0
    t0 = time.perf_counter()
    gr = jax.block_until_ready(ref_grads(on_one[0], *on_one[2:]))
    p_ref, _, _ = jax.block_until_ready(ref_update(on_one[0], gr, on_one[1]))
    t_ref = time.perf_counter() - t0
    log(f"pipelined grads + step {t_pipe:.1f}s, single-device grads + "
        f"update {t_ref:.1f}s (compiles included)")

    host = lambda t: [np.asarray(x) for x in jax.tree.leaves(t)]
    gerr = max(float(np.max(np.abs(a - b)))
               for a, b in zip(host(gp), host(gr)))
    diffs = np.concatenate([np.abs(a - b).ravel()
                            for a, b in zip(host(p2), host(p_ref))])
    perr, pfrac = float(diffs.max()), float((diffs > opt_cfg.lr / 10).mean())
    loss = float(stats["loss"])
    log(f"pipeline vs single-device: grad max|diff| {gerr:.3e}, "
        f"param max|diff| {perr:.3e}, fraction of params off by > lr/10 "
        f"{pfrac:.2e}, loss {loss:.4f}")
    # the bounds of tests/test_spmd_pipeline.py, with its reasons
    if not np.isfinite(loss) or not gerr < 1e-5:
        fail("pipelined grads disagree with the single-device step")
    if not (perr <= 2 * opt_cfg.lr and pfrac < 1e-3):
        fail("pipelined update disagrees with the single-device step")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: the training path on one chip; 4: the "
                         "shard_map pipeline step over four chips")
    args = ap.parse_args()
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no src/repro beside {__file__}: run from a checkout")
    sys.path.insert(0, src)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX finds no TPU (platform {dev.platform!r})")
    from repro.utils.compile_cache import enable_compile_cache
    log(f"device {dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{enable_compile_cache()}")

    if args.chips == 4:
        four_chip_phase()
    else:
        kernel_phase()
        one_chip_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
