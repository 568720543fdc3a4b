"""End-to-end mini dry-run in a SUBPROCESS with a small forced device
count (8 devices, 2x4 mesh) — validates the whole lower->compile->
roofline pipeline without polluting this process's 1-device backend.
The production 512-device sweep runs via launch/dryrun.py."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys, dataclasses
    import jax
    from repro.configs import get_arch, reduced, ShapeConfig
    from repro.runtime.sharding import ShardingStrategy
    from repro.runtime import spmd
    from repro.launch import specs as sp
    from repro.launch.hloparse import analyze
    from repro.optim import adamw

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    arch = reduced(get_arch(sys.argv[1]), layers=2, d_model=64, vocab=512)
    shape = ShapeConfig("tiny", seq_len=64, global_batch=8, kind=sys.argv[2])
    strategy = ShardingStrategy(strategy="fsdp", data_axes=("data",))
    model = spmd.build_model(arch, strategy, mesh, shape.global_batch)
    model = dataclasses.replace(model, loss_chunk=16)
    pshape = sp.params_shape(model)
    with mesh:
        if shape.kind == "train":
            oshape = sp.opt_shape(model, pshape)
            bundle = spmd.train_bundle(model, adamw.AdamWConfig(), strategy,
                                       mesh, pshape, oshape, shape)
            lowered = bundle.jit().lower(pshape, oshape,
                                         sp.batch_specs(arch, shape))
        else:
            tok, cache, pos = sp.decode_specs(arch, shape, model)
            bundle = spmd.decode_bundle(model, strategy, mesh, pshape,
                                        cache, shape)
            lowered = bundle.jit().lower(pshape, tok, cache, pos)
        compiled = lowered.compile()
    st = analyze(compiled.as_text(), default_group=4)
    ma = compiled.memory_analysis()
    print(json.dumps({
        "flops": st.dot_flops,
        "coll": st.collective_bytes,
        "temps": ma.temp_size_in_bytes,
        "xla_flops": compiled.cost_analysis().get("flops", 0.0),
    }))
""")


# slow container / CI runners can override the subprocess budget
TIMEOUT = int(os.environ.get("REPRO_DRYRUN_TIMEOUT", "600"))


def run(arch, kind):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch, kind],
                         capture_output=True, text=True, env=env,
                         timeout=TIMEOUT)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch,kind", [
    ("qwen3_1_7b", "train"),
    ("granite_moe_1b_a400m", "train"),
    ("mamba2_780m", "train"),
    ("hymba_1_5b", "decode"),
    ("qwen2_5_3b", "decode"),
])
def test_mini_dryrun_compiles_and_counts(arch, kind):
    r = run(arch, kind)
    assert r["flops"] > 0
    assert r["temps"] > 0
    # trip-count-aware parse must cover XLA's loop-once count; decode
    # programs are tiny, so non-dot (elementwise) flops — which the
    # parser deliberately ignores — carry more relative weight there.
    floor = 0.9 if kind == "train" else 0.6
    assert r["flops"] >= floor * r["xla_flops"]


# ----------------------------------------------------------------------
# the resilient-training driver, per recovery policy, in a subprocess
# (mirrors the README quickstart: tiny model, kill a node mid-run)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["replan", "adapt", "auto"])
def test_train_driver_recovers_under_each_policy(policy):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--nodes", "9", "--n0", "2", "--f", "1",
         "--global-batch", "12", "--microbatch", "2", "--seq-len", "16",
         "--layers", "2", "--steps", "4", "--kill-at", "1", "--no-warm",
         "--recovery-policy", policy],
        capture_output=True, text=True, env=env, timeout=TIMEOUT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[fail] killed" in out.stdout
    assert "[done]" in out.stdout
    if policy == "adapt":
        assert "adapted schedule" in out.stdout
        assert "zero state copied" in out.stdout
