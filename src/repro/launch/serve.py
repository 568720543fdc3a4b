"""Resilient serving driver: continuous batching over slot caches with
template-based inference fault tolerance (runtime/serve_exec.py,
DESIGN.md §14).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --requests 8 --batch 4 --prompt-len 8 --decode-steps 16 \
        --temperature 0.8 --fail-at 4

Builds an OobleckEngine over a synthetic node set, registers a
ServeExecutor as its runtime, streams a request trace through the
continuous-batching scheduler, and (optionally) injects a node failure
mid-traffic through the monitor — the decode pipelines replan from the
precomputed template set and every in-flight request completes.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, sized
from repro.core import build_profile
from repro.core.engine import EngineConfig, OobleckEngine
from repro.models import Model
from repro.runtime.serve_exec import SamplingParams, ServeExecutor
from repro.utils.compile_cache import enable_compile_cache


def build_serving_engine(arch, *, nodes, fault_tolerance: int = 1,
                         n0: int = 2, nodes_per_pod: int = 2,
                         seq_len: int = 32) -> OobleckEngine:
    """Engine wired for serving: the instance set is the decode-replica
    set; templates/reconfigurator/topology work unchanged."""
    profile = build_profile(arch, microbatch=1, seq_len=seq_len)
    cfg = EngineConfig(fault_tolerance=fault_tolerance, global_batch=8,
                       microbatch=1, n0_override=n0,
                       nodes_per_pod=nodes_per_pod)
    return OobleckEngine(profile, list(nodes), cfg)


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else 0.0


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots per replica")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-steps", type=int, default=16,
                    help="generated tokens per request")
    ap.add_argument("--requests", type=int, default=0,
                    help="request count (default: one per slot)")
    ap.add_argument("--layers", type=int, default=None,
                    help="block count: the reduced config's (default 2), "
                         "or with --full a depth cut of the published one")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=6)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a node failure after this many ticks")
    ap.add_argument("--full", action="store_true",
                    help="every published width (see --layers)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = sized(get_arch(args.arch), full=args.full, layers=args.layers)
    model = Model(arch, dtype=jnp.float32, remat=False)
    # independent keys for params, data and sampling (a shared key would
    # correlate the prompts with the weights)
    k_init, k_data, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 3)
    params = model.init(k_init)

    n_req = args.requests or args.batch
    prompts = [np.asarray(jax.random.randint(
        jax.random.fold_in(k_data, i), (args.prompt_len,), 0,
        arch.vocab_size), np.int32) for i in range(n_req)]

    engine = build_serving_engine(
        arch, nodes=[f"node{i}" for i in range(args.nodes)])
    t0 = time.perf_counter()
    ex = ServeExecutor(
        model, params, engine, num_slots=args.batch,
        max_len=args.prompt_len + args.decode_steps,
        max_new_cap=args.decode_steps,
        sampling=SamplingParams(args.temperature, args.top_k),
        sample_key=k_sample)
    warm_s = time.perf_counter() - t0
    for p in prompts:
        ex.submit(p, max_new=args.decode_steps)

    t0 = time.perf_counter()
    ticks = 0
    while ex.queue or any(r.active_mask().any() for r in ex.replicas):
        if ticks == args.fail_at:
            victim = engine.instances[0].nodes[0]
            engine.monitor.inject("fail", [victim])
            engine.monitor.poll(time.perf_counter())
            print(f"[serve] killed {victim}: {ex.last_recovery}")
        ex.tick()
        ticks += 1
    wall_s = time.perf_counter() - t0

    total_tokens = sum(r.max_new for r in ex.completed)
    ttft = [r.first_token_s - r.arrival_s for r in ex.completed
            if r.first_token_s is not None]
    ms_per_token = wall_s / max(total_tokens, 1) * 1e3
    print(f"[serve] replicas={len(ex.replicas)} slots={args.batch} "
          f"requests={len(ex.completed)}/{n_req} warm={warm_s:.1f}s")
    print(f"[serve] {total_tokens} tokens in {wall_s * 1e3:.0f}ms "
          f"({total_tokens / wall_s:.1f} tok/s, {ms_per_token:.2f}"
          f"ms/token), ttft p50={percentile(ttft, 50) * 1e3:.1f}ms "
          f"p99={percentile(ttft, 99) * 1e3:.1f}ms")
    r0 = min(ex.completed, key=lambda r: r.rid)
    print(f"[serve] sample continuation (request 0): "
          f"{r0.tokens[:16].tolist()}")
    assert len(ex.completed) == n_req, "not all requests completed"
    toks = np.stack([r.tokens for r in
                     sorted(ex.completed, key=lambda r: r.rid)])
    return {"tokens": toks, "ms_per_token": ms_per_token,
            "tokens_per_s": total_tokens / wall_s,
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p99_ms": percentile(ttft, 99) * 1e3,
            "recovery": ex.last_recovery}


if __name__ == "__main__":
    main()
