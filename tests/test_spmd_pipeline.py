"""shard_map pipeline-parallel forward == plain forward, on a real
multi-device host mesh (subprocess with 4 forced devices)."""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch, reduced
    from repro.models import Model
    from repro.runtime.spmd_pipeline import pipeline_logits

    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    arch = reduced(get_arch("gpt3_medium"), layers=8)   # 8 blocks / 4 stages
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive")
    params = model.init(jax.random.PRNGKey(0))
    M, B, S = 3, 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (M, B, S), 0,
                                arch.vocab_size)
    with mesh:
        piped = pipeline_logits(model, params, tokens, mesh)
    ref = jnp.stack([model.forward(params, tokens[i])[0] for i in range(M)])
    err = float(jnp.max(jnp.abs(piped - ref)))
    print(json.dumps({"err": err, "shape": list(piped.shape)}))
""")


def test_shard_map_pipeline_matches_forward():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["err"] < 1e-4, r
    assert r["shape"][0] == 3


TRAIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch, reduced
    from repro.models import Model
    from repro.models.layers import cross_entropy
    from repro.optim import adamw
    from repro.runtime.spmd_pipeline import (make_pipeline_train_step,
                                             pipeline_loss)

    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    arch = reduced(get_arch("gpt3_medium"), layers=8)
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="naive")
    params = model.init(jax.random.PRNGKey(0))
    M, B, S = 3, 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (M, B, S), 0,
                                arch.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (M, B, S), 0,
                                arch.vocab_size)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, clip_norm=1.0,
                                weight_decay=0.0)

    def ref_loss(p):
        nll = jnp.stack([cross_entropy(model.forward(p, tokens[i])[0][:, :-1],
                                       labels[i][:, :-1]) for i in range(M)])
        return jnp.mean(nll)

    with mesh:
        # the SAME schedule differentiates: grads through the pipelined
        # scan/ppermute program equal plain full-model grads
        gp = jax.grad(lambda p: pipeline_loss(model, p, tokens, labels,
                                              mesh))(params)
        gr = jax.grad(ref_loss)(params)
        gerr = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)))

        # one donated SPMD program trains end to end
        step = make_pipeline_train_step(model, opt_cfg, mesh)
        opt = adamw.init(params)
        p_ref, o_ref, _ = adamw.apply(opt_cfg, params, gr, opt)
        p2, o2, stats = step(params, opt, tokens, labels)
        diffs = [np.abs(np.asarray(a) - np.asarray(b)).ravel()
                 for a, b in zip(jax.tree.leaves(p2),
                                 jax.tree.leaves(p_ref))]
        diffs = np.concatenate(diffs)
    print(json.dumps({"gerr": gerr, "perr": float(diffs.max()),
                      "pfrac": float((diffs > opt_cfg.lr / 10).mean()),
                      "lr": opt_cfg.lr, "loss": float(stats["loss"])}))
""")


def test_shard_map_pipeline_train_step_matches_reference():
    """Backward through the shard_map schedule (transposed ppermutes) +
    in-program AdamW == plain full-model training, on 4 real devices."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", TRAIN_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["gerr"] < 1e-5, r
    # Adam's first step moves each element by lr * g / (|g| + eps), so
    # grad ULP noise on an element near zero can move it by up to 2*lr
    # whatever the noise's size; a systematic fault (wrong stage, missed
    # microbatch) would move most elements instead of a few
    assert r["perr"] <= 2 * r["lr"], r
    assert r["pfrac"] < 1e-3, r
    assert 0 < r["loss"] < 20
