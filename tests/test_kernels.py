"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU) + hypothesis
properties.  Since §11, the BACKWARD is a Pallas kernel too: parity of
the registered custom_vjp rules against the oracle gradients is swept
across dtypes and odd (non-block-multiple) shapes, and the backward is
asserted to actually BE the Pallas path (not an oracle recompute)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                     # optional locally; CI installs it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels import autotune, ops, ref
from repro.kernels.flash_attention import (flash_attention as fa_kernel,
                                           flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.ssd import ssd as ssd_kernel, ssd_bwd, ssd_fwd

RNG = jax.random.PRNGKey(3)


def _qkv(B, S, H, KV, D, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D)).astype(dtype)
    return q, k, v


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# Flash attention sweeps
# ----------------------------------------------------------------------
@pytest.mark.parametrize("S", [16, 64, 100, 160])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_shapes(S, H, KV):
    q, k, v = _qkv(2, S, H, KV, 16, jnp.float32)
    out = fa_kernel(q, k, v, block_q=32, block_k=32, interpret=True)
    exp = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(out, exp, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q, k, v = _qkv(1, 64, 4, 2, 32, dtype)
    out = fa_kernel(q, k, v, block_q=32, block_k=32, interpret=True)
    exp = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [8, 32, 200])
def test_flash_attention_window(window):
    q, k, v = _qkv(1, 96, 4, 2, 16, jnp.float32)
    out = fa_kernel(q, k, v, window=window, block_q=32, block_k=32,
                    interpret=True)
    exp = ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(out, exp, rtol=2e-5, atol=2e-5)


def test_flash_attention_block_shape_invariance():
    q, k, v = _qkv(1, 128, 4, 2, 16, jnp.float32)
    a = fa_kernel(q, k, v, block_q=32, block_k=64, interpret=True)
    b = fa_kernel(q, k, v, block_q=128, block_k=16, interpret=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# Flash attention BACKWARD (Pallas two-pass kernels)
# ----------------------------------------------------------------------
def _flash_grads(fn, q, k, v, g, window=0):
    _, vjp = jax.vjp(lambda q, k, v: fn(q, k, v), q, k, v)
    return vjp(g)


@pytest.mark.parametrize("S,H,KV,window", [
    (48, 2, 2, 0),        # block-multiple
    (100, 4, 2, 0),       # odd S: padding rows in both bwd kernels
    (37, 4, 1, 8),        # odd S + MQA + window
    (96, 8, 2, 24),       # GQA group sum + window
])
def test_flash_attention_bwd_matches_oracle(S, H, KV, window):
    q, k, v = _qkv(1, S, H, KV, 16, jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    gk = _flash_grads(
        lambda q, k, v: ops.flash_attention(q, k, v, window, 32, 32),
        q, k, v, g)
    gr = _flash_grads(
        lambda q, k, v: ref.attention_ref(q, k, v, window=window),
        q, k, v, g)
    for a, b, n in zip(gk, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=n)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_bwd_dtypes(dtype):
    q, k, v = _qkv(1, 64, 4, 2, 32, dtype)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape).astype(dtype)
    gk = _flash_grads(
        lambda q, k, v: ops.flash_attention(q, k, v, 0, 32, 32), q, k, v, g)
    gr = _flash_grads(lambda q, k, v: ref.attention_ref(q, k, v), q, k, v, g)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4)
    for a, b in zip(gk, gr):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_flash_attention_bwd_block_invariance():
    q, k, v = _qkv(1, 128, 4, 2, 16, jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), q.shape)
    out, lse = flash_attention_fwd(q, k, v, block_q=32, block_k=64,
                                   interpret=True)
    a = flash_attention_bwd(q, k, v, out, lse, g, block_q=32, block_k=64,
                            interpret=True)
    b = flash_attention_bwd(q, k, v, out, lse, g, block_q=128, block_k=16,
                            interpret=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_ref():
    q, k, v = _qkv(1, 48, 2, 2, 8, jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, 0, 16, 16) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_registered_bwd_is_pallas_not_oracle():
    """The custom_vjp backward must BE the Pallas kernels: the grad
    jaxpr contains the fwd pallas_call plus the dq and dkv calls — not
    an oracle recompute (which would show exactly one pallas_call)."""
    q, k, v = _qkv(1, 32, 2, 2, 8, jnp.float32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(ops.flash_attention(q, k, v, 0, 16, 16))))(q))
    assert jaxpr.count("pallas_call") >= 3, jaxpr.count("pallas_call")

    x, dt, A, B, C = _ssd_inputs(1, 16, 2, 4, 8)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(ops.ssd(x, dt, A, B, C, 8)[0])))(x))
    assert jaxpr.count("pallas_call") >= 2, jaxpr.count("pallas_call")


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(S=st.integers(4, 80), D=st.sampled_from([8, 16]),
           seed=st.integers(0, 99))
    def test_flash_attention_property(S, D, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, S, 2, D))
        k = jax.random.normal(ks[1], (1, S, 2, D))
        v = jax.random.normal(ks[2], (1, S, 2, D))
        out = fa_kernel(q, k, v, block_q=16, block_k=16, interpret=True)
        exp = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(out, exp, rtol=3e-5, atol=3e-5)
        # rows are convex combinations of V rows: bounded by V extremes
        assert float(jnp.max(out)) <= float(jnp.max(v)) + 1e-4
        assert float(jnp.min(out)) >= float(jnp.min(v)) - 1e-4

    @settings(max_examples=10, deadline=None)
    @given(S=st.integers(4, 60), seed=st.integers(0, 99))
    def test_flash_attention_bwd_property(S, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(ks[0], (1, S, 2, 8))
        k = jax.random.normal(ks[1], (1, S, 2, 8))
        v = jax.random.normal(ks[2], (1, S, 2, 8))
        g = jax.random.normal(ks[3], (1, S, 2, 8))
        gk = _flash_grads(
            lambda q, k, v: ops.flash_attention(q, k, v, 0, 16, 16),
            q, k, v, g)
        gr = _flash_grads(lambda q, k, v: ref.attention_ref(q, k, v),
                          q, k, v, g)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


# ----------------------------------------------------------------------
# SSD sweeps
# ----------------------------------------------------------------------
def _ssd_inputs(b, S, H, P, N, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B = jax.random.normal(ks[3], (b, S, H, N)).astype(dtype)
    C = jax.random.normal(ks[4], (b, S, H, N)).astype(dtype)
    return x, dt, A, B, C


@pytest.mark.parametrize("S,chunk", [(32, 8), (40, 16), (7, 8), (128, 32)])
@pytest.mark.parametrize("P,N", [(8, 16), (16, 8)])
def test_ssd_shapes(S, chunk, P, N):
    x, dt, A, B, C = _ssd_inputs(2, S, 3, P, N)
    y, st_out = ssd_kernel(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, st_ref = ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st_out, st_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_dtypes(dtype):
    x, dt, A, B, C = _ssd_inputs(1, 32, 2, 8, 16, dtype)
    y, _ = ssd_kernel(x, dt, A, B, C, chunk=16, interpret=True)
    yr, _ = ref.ssd_ref(x, dt, A, B, C)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)


def test_ssd_chunk_invariance():
    x, dt, A, B, C = _ssd_inputs(1, 64, 2, 8, 8)
    y1, s1 = ssd_kernel(x, dt, A, B, C, chunk=8, interpret=True)
    y2, s2 = ssd_kernel(x, dt, A, B, C, chunk=32, interpret=True)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------
# SSD BACKWARD (reverse-chunk Pallas kernel)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("S,chunk", [(32, 8), (40, 16), (7, 8), (33, 8)])
def test_ssd_bwd_matches_oracle(S, chunk):
    x, dt, A, B, C = _ssd_inputs(2, S, 3, 8, 16)
    y, state, cst = ssd_fwd(x, dt, A, B, C, chunk=chunk, interpret=True)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    gy = jax.random.normal(ks[0], y.shape)
    gs = jax.random.normal(ks[1], state.shape)   # state cotangent too
    got = ssd_bwd(x, dt, A, B, C, cst, gy, gs, chunk=chunk, interpret=True)
    _, vjp = jax.vjp(lambda *a: ref.ssd_ref(*a), x, dt, A, B, C)
    exp = vjp((gy, gs))
    for a, b, n in zip(got, exp, ("dx", "ddt", "dA", "dB", "dC")):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3, err_msg=n)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_bwd_dtypes(dtype):
    x, dt, A, B, C = _ssd_inputs(1, 24, 2, 4, 8, dtype)

    def f_kernel(x, B, C):
        y, _ = ops.ssd(x, dt, A, B, C, 8)
        return jnp.sum((y.astype(jnp.float32)) ** 2)

    def f_ref(x, B, C):
        y, _ = ref.ssd_ref(x, dt, A, B, C)
        return jnp.sum((y.astype(jnp.float32)) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(x, B, C)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, B, C)
    tol = dict(rtol=1e-1, atol=1e-1) if dtype == jnp.bfloat16 else dict(
        rtol=2e-3, atol=2e-3)
    for a, b in zip(gk, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_ssd_grad_matches_ref():
    x, dt, A, B, C = _ssd_inputs(1, 24, 2, 4, 8)

    def f_kernel(*a):
        y, _ = ops.ssd(*a, 8)
        return jnp.sum(y ** 2)

    def f_ref(*a):
        y, _ = ref.ssd_ref(*a)
        return jnp.sum(y ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 3, 4))(x, dt, A, B, C)
    gr = jax.grad(f_ref, argnums=(0, 1, 3, 4))(x, dt, A, B, C)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_ssd_grad_wrt_A_matches_ref():
    x, dt, A, B, C = _ssd_inputs(1, 40, 3, 4, 8)
    gk = jax.grad(lambda A: jnp.sum(ops.ssd(x, dt, A, B, C, 16)[0] ** 2))(A)
    gr = jax.grad(lambda A: jnp.sum(ref.ssd_ref(x, dt, A, B, C)[0] ** 2))(A)
    np.testing.assert_allclose(gk, gr, rtol=1e-3, atol=1e-3)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(S=st.integers(4, 60), chunk=st.sampled_from([8, 16]),
           seed=st.integers(0, 99))
    def test_ssd_property(S, chunk, seed):
        x, dt, A, B, C = _ssd_inputs(1, S, 2, 4, 8, seed=seed)
        y, st_out = ssd_kernel(x, dt, A, B, C, chunk=chunk, interpret=True)
        yr, st_ref = ref.ssd_ref(x, dt, A, B, C)
        np.testing.assert_allclose(y, yr, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(st_out, st_ref, rtol=5e-4, atol=5e-4)

    @settings(max_examples=10, deadline=None)
    @given(S=st.integers(4, 48), chunk=st.sampled_from([8, 16]),
           seed=st.integers(0, 99))
    def test_ssd_bwd_property(S, chunk, seed):
        x, dt, A, B, C = _ssd_inputs(1, S, 2, 4, 8, seed=seed)
        y, state, cst = ssd_fwd(x, dt, A, B, C, chunk=chunk, interpret=True)
        gy = jax.random.normal(jax.random.PRNGKey(seed + 1), y.shape)
        gs = jnp.zeros_like(state)
        got = ssd_bwd(x, dt, A, B, C, cst, gy, gs, chunk=chunk,
                      interpret=True)
        _, vjp = jax.vjp(lambda *a: ref.ssd_ref(*a), x, dt, A, B, C)
        exp = vjp((gy, gs))
        for a, b in zip(got, exp):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


# ----------------------------------------------------------------------
# Model integration + backend gating + autotuner
# ----------------------------------------------------------------------
def test_model_kernel_path_matches_chunked():
    """Model(ssd_impl='kernel') == Model(ssd_impl='chunked')."""
    from repro.configs import get_arch, reduced
    from repro.models import Model
    arch = reduced(get_arch("mamba2_780m"), layers=2)
    mk = Model(arch, dtype=jnp.float32, remat=False, ssd_impl="kernel")
    mc = Model(arch, dtype=jnp.float32, remat=False, ssd_impl="chunked")
    params = mk.init(RNG)
    tokens = jax.random.randint(RNG, (1, 24), 0, arch.vocab_size)
    lk, _ = mk.forward(params, tokens)
    lc, _ = mc.forward(params, tokens)
    np.testing.assert_allclose(lk, lc, rtol=2e-4, atol=2e-4)


def test_model_attention_kernel_path_matches_blocked():
    """Model(attn_impl='kernel') tracks the blocked oracle through the
    full loss AND its gradient (the Pallas bwd in the stage hot path)."""
    from repro.configs import get_arch, reduced
    from repro.models import Model
    arch = reduced(get_arch("gpt3_medium"), layers=2)
    mk = Model(arch, dtype=jnp.float32, remat=False, attn_impl="kernel")
    mb = Model(arch, dtype=jnp.float32, remat=False, attn_impl="blocked")
    params = mk.init(RNG)
    tokens = jax.random.randint(RNG, (1, 24), 0, arch.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    lk, gk = jax.value_and_grad(lambda p: mk.loss(p, batch)[0])(params)
    lb, gb = jax.value_and_grad(lambda p: mb.loss(p, batch)[0])(params)
    np.testing.assert_allclose(lk, lb, rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


def test_model_auto_impl_resolves_for_backend(monkeypatch):
    """"auto" is the XLA path on the CPU and the kernel on an
    accelerator — where a kernel that does not lower raises instead of
    falling back."""
    from repro.configs import get_arch, reduced
    from repro.models import Model
    arch = reduced(get_arch("gpt3_medium"), layers=2)
    m = Model(arch, attn_impl="auto", ssd_impl="auto")
    assert (m.attn_impl, m.ssd_impl) == ("blocked", "chunked")
    ops._reset_lowering_cache()
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for kind in ops.KERNEL_KINDS:
            monkeypatch.setitem(ops._PROBES, kind, lambda: None)
        m = Model(arch, attn_impl="auto", ssd_impl="auto")
        assert (m.attn_impl, m.ssd_impl) == ("kernel", "kernel")
        ops._reset_lowering_cache()

        def refuse():
            raise ValueError("block shape not tiled")

        monkeypatch.setitem(ops._PROBES, "flash_bwd", refuse)
        with pytest.raises(ops.KernelLoweringError,
                           match="block shape not tiled"):
            Model(arch, attn_impl="auto")
    finally:
        ops._reset_lowering_cache()


def test_backend_signature_gating():
    """Lowering is decided by the backend: CPU (no compiled Pallas)
    interprets every kind, TPU compiles every kind, and a backend with
    no maintained lowering is an error rather than a silent fallback.
    The signature that program caches key on carries the whole per-kind
    plan."""
    for kind in ops.KERNEL_KINDS:
        assert ops.kernel_lowers(kind, "tpu"), kind
        assert not ops.kernel_lowers(kind, "cpu"), kind
    for backend in ("gpu", "cuda", "rocm"):
        with pytest.raises(ops.KernelLoweringError, match=backend):
            ops.kernel_lowers("flash_fwd", backend)
    sig = ops.backend_signature()
    backend = jax.default_backend()
    # (backend, process topology, per-kind plan): the topology leg keeps
    # single- and multi-process compilations of the same template from
    # colliding in a shared cache
    assert sig == (backend, ops.process_topology(),
                   ops.lowering_plan(backend))
    assert sig[1][:2] == (jax.process_count(), jax.process_index())
    assert dict(sig[2]) == {k: ops.kernel_lowers(k, backend)
                            for k in ops.KERNEL_KINDS}


def test_lowering_probe_runs_on_live_backend_and_caches(monkeypatch):
    """On a live accelerator the verdict comes from a one-shot
    try-compile of the kernel structure, cached per (kind, backend); a
    failing compile raises with the compiler's message.  The CPU never
    probes: it has no compiled Pallas."""
    ops._reset_lowering_cache()
    try:
        calls = []
        monkeypatch.setitem(ops._PROBES, "flash_fwd",
                            lambda: calls.append(1))
        assert ops.kernel_lowers("flash_fwd", "cpu") is False
        assert not calls
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ops.kernel_lowers("flash_fwd") is True
        assert ops.kernel_lowers("flash_fwd") is True
        assert len(calls) == 1                      # one-shot, then cached

        def refuse():
            raise ValueError("Mosaic failed to compile")

        monkeypatch.setitem(ops._PROBES, "ssd_bwd", refuse)
        with pytest.raises(ops.KernelLoweringError,
                           match="'ssd_bwd'.*Mosaic failed to compile"):
            ops.kernel_lowers("ssd_bwd")
    finally:
        ops._reset_lowering_cache()


def test_autotune_offline_deterministic(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    cache = autotune.AutotuneCache()
    a = cache.get("flash", "cpu", jnp.float32, (2048, 64))
    b = cache.get("flash", "cpu", jnp.float32, (2048, 64))
    assert a == b and a["block_q"] >= 128   # big blocks for interpreter
    assert cache.get("flash", "tpu", jnp.float32, (2048, 64)) == {
        "block_q": 128, "block_k": 128}     # MXU-aligned
    assert cache.get("ssd", "tpu", jnp.float32, (2048, 64, 128)) == {
        "chunk": 128}
    # tiny shapes never exceed their bucket
    small = cache.get("flash", "cpu", jnp.float32, (16, 16))
    assert small["block_q"] <= 16


def test_autotune_ragged_shapes_get_distinct_entries(tmp_path):
    """Regression: the pow2-only bucket used to collide e.g. seq 1000
    onto 1024's entry — blocks tuned on the clean power were served to
    ragged lengths whose padding/tail tiling is different.  Ragged
    lengths now keep their own identity under the pow2 roof, and head
    dims are always keyed exactly."""
    assert autotune.shape_bucket(1024) == "1024"
    assert autotune.shape_bucket(1000) == "1024r1000"
    assert autotune.shape_bucket(129) != autotune.shape_bucket(256)
    assert autotune._seq_of("1024r1000") == 1000
    path = str(tmp_path / "a.json")
    c = autotune.AutotuneCache(path)
    c.put("flash", "cpu", jnp.float32, (autotune.shape_bucket(1024), 64),
          {"block_q": 512, "block_k": 512})
    # the measured pow2 entry must NOT shadow the ragged length...
    assert c.peek("flash", "cpu", jnp.float32,
                  (autotune.shape_bucket(1000), 64)) is None
    # ...which falls back to the offline default instead
    assert c.get("flash", "cpu", jnp.float32,
                 (autotune.shape_bucket(1000), 64))["block_q"] >= 128
    # non-pow2 head dims never share an entry with pow2 ones
    c.put("flash", "cpu", jnp.float32, ("1024", 80),
          {"block_q": 64, "block_k": 64})
    assert c.get("flash", "cpu", jnp.float32, ("1024", 64)) == {
        "block_q": 512, "block_k": 512}
    assert c.get("flash", "cpu", jnp.float32, ("1024", 80)) == {
        "block_q": 64, "block_k": 64}


def test_flash_config_routes_ragged_seq_via_ragged_bucket(monkeypatch):
    seen = {}
    orig = autotune._CACHE.peek

    def spy(kind, backend, dtype, shape):
        seen["shape"] = shape
        return orig(kind, backend, dtype, shape)

    monkeypatch.setattr(autotune._CACHE, "peek", spy)
    autotune.flash_config("cpu", jnp.float32, 1000, 64)
    assert seen["shape"] == ("1024r1000", 64)


def test_offline_heuristic_is_per_kernel_capability():
    """The offline defaults follow what the backend runs: compiled
    kernels on TPU (MXU-aligned 128), the interpreter on the CPU."""
    c = autotune.AutotuneCache("/nonexistent/never-loaded.json")
    assert c.get("flash", "tpu", jnp.float32, (2048, 64)) == {
        "block_q": 128, "block_k": 128}           # compiled heuristic
    assert c.get("fused", "tpu", jnp.float32,
                 (2048, 768))["block_rows"] == 128
    # seq 64: compiled heuristic says 128, interpreter caps at the bucket
    assert c.get("ssd", "cpu", jnp.float32, (64, 64, 32)) == {"chunk": 64}
    assert c.get("ssd", "tpu", jnp.float32, (64, 64, 32)) == {"chunk": 128}


def test_accelerator_blocks_ignore_measured_entries(tmp_path, monkeypatch):
    """A chip run's blocks come from the checkout alone: measured
    entries and REPRO_AUTOTUNE tuning steer only the CPU."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    cache = autotune.AutotuneCache(str(tmp_path / "m.json"))
    for backend in ("cpu", "tpu"):
        cache.put("flash", backend, jnp.float32, ("2048", 64),
                  {"block_q": 256, "block_k": 256})
    monkeypatch.setattr(autotune, "_CACHE", cache)
    monkeypatch.setattr(autotune, "tune_flash", lambda *a, **k: 1 / 0)
    assert autotune.flash_config("cpu", jnp.float32, 2048, 64) == {
        "block_q": 256, "block_k": 256}
    assert autotune.flash_config("tpu", jnp.float32, 2048, 64) == {
        "block_q": 128, "block_k": 128}
    # nothing is read from or written to a file the run did not name
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune.AutotuneCache().path is None


def test_packaged_offline_table_consulted(monkeypatch):
    """A measured entry checked into autotune_offline.json wins over the
    heuristic for its exact key (and only that key)."""
    key = autotune._key("flash", "tpu", jnp.float32, ("2048", 64))
    monkeypatch.setattr(autotune, "_PACKAGED",
                        {key: {"block_q": 256, "block_k": 256}})
    c = autotune.AutotuneCache("/nonexistent/never-loaded.json")
    assert c.get("flash", "tpu", jnp.float32, ("2048", 64)) == {
        "block_q": 256, "block_k": 256}
    assert c.get("flash", "tpu", jnp.float32, ("1024", 64)) == {
        "block_q": 128, "block_k": 128}


def test_autotune_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "autotune.json")
    c1 = autotune.AutotuneCache(path)
    c1.put("flash", "cpu", jnp.float32, (1024, 64),
           {"block_q": 256, "block_k": 256})
    c2 = autotune.AutotuneCache(path)         # fresh process simulation
    assert c2.get("flash", "cpu", jnp.float32, (1024, 64)) == {
        "block_q": 256, "block_k": 256}
    with open(path) as f:
        table = json.load(f)
    assert any("flash|cpu" in k for k in table)


def test_autotune_offline_fallbacks_not_persisted(tmp_path):
    """save() must only write measured entries: a persisted snapshot of
    the offline defaults would shadow future offline-table updates."""
    path = str(tmp_path / "a.json")
    c = autotune.AutotuneCache(path)
    c.get("flash", "cpu", jnp.float32, (1024, 64))      # offline fallback
    c.put("ssd", "tpu", jnp.float32, (1024, 64, 128), {"chunk": 64})
    with open(path) as f:
        table = json.load(f)
    assert list(table) == ["ssd|tpu|float32|1024x64x128"]


def test_autotune_env_triggers_measured_tuning(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE=1 routes config misses through measured tuning."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setattr(autotune, "_CACHE",
                        autotune.AutotuneCache(str(tmp_path / "x.json")))
    called = {}

    def fake_tune(backend, dtype, seq, d, **kw):
        called["args"] = (backend, seq, d)
        return {"block_q": 64, "block_k": 64}

    monkeypatch.setattr(autotune, "tune_flash", fake_tune)
    cfg = autotune.flash_config("cpu", jnp.float32, 128, 16)
    assert cfg == {"block_q": 64, "block_k": 64}
    assert called["args"] == ("cpu", 128, 16)
    # without the env var, misses fall back to the offline table
    monkeypatch.delenv("REPRO_AUTOTUNE")
    called.clear()
    autotune.flash_config("cpu", jnp.float32, 256, 16)
    assert not called


def test_autotune_config_feeds_ops(monkeypatch):
    """ops.flash_attention with default blocks consults the autotuner."""
    seen = {}
    orig = autotune.flash_config

    def spy(backend, dtype, seq, d):
        seen["args"] = (backend, seq, d)
        return orig(backend, dtype, seq, d)

    monkeypatch.setattr(autotune, "flash_config", spy)
    q, k, v = _qkv(1, 32, 2, 2, 8, jnp.float32)
    ops.flash_attention(q, k, v)
    assert seen["args"] == (jax.default_backend(), 32, 8)
