"""Every Pallas kernel kind, and one GPT-3 Medium block's gradients,
compile for a TPU v5e at the widths the chip runs — no chip needed.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``v5e:2x2``) and not attached.  Interpret-mode tests cannot
see what Mosaic refuses (block tiling, unsupported primitives), so these
tests are what keep the kernels chip-ready between chip runs.  Each
asserts a ``tpu_custom_call`` in the compiled HLO: the kernel really is
in the program, not an XLA substitute.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and it keeps it
until it exits.  The process's own backend stays the CPU, so the tests
steer ``ops.resolve_backend`` to "tpu" to route the model through the
compiled kernels it would run on the chip.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.kernels import ops

# GPT-3 Medium (paper Table 1) at the chip's microbatch, and the SSD at
# mamba2-780m's widths: d_inner 3072 = 48 heads x P 64, N 128, chunk 256
B, S, H, D = 1, 2048, 16, 64
SSD_H, SSD_P, SSD_N, SSD_CHUNK = 48, 64, 128, 256


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "resolve_backend", lambda: "tpu")


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    return compiled, compiled.as_text()


def _sum(tree):
    return sum(jnp.sum(t) for t in jax.tree.leaves(tree))


def _kernel_case(kind, aval):
    f32 = jnp.float32
    if kind.startswith("flash"):
        q = aval((B, S, H, D), f32)
        if kind == "flash_fwd":
            return lambda q, k, v: ops.flash_attention(q, k, v), (q, q, q)
        return (jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention(q, k, v)),
                         argnums=(0, 1, 2)), (q, q, q))
    if kind.startswith("ssd"):
        x = aval((B, S, SSD_H, SSD_P), f32)
        args = (x, aval((B, S, SSD_H), f32), aval((SSD_H,), f32),
                aval((B, S, SSD_H, SSD_N), f32),
                aval((B, S, SSD_H, SSD_N), f32))
        fwd = lambda *a: ops.ssd(*a, chunk=SSD_CHUNK)
        if kind == "ssd_fwd":
            return fwd, args
        return jax.grad(lambda *a: _sum(fwd(*a)), argnums=(0, 1, 2, 3, 4)), args
    x = aval((B * S, 1024), f32)
    if kind == "fused_norm":
        return (jax.grad(lambda x, r, w: _sum(ops.fused_add_rmsnorm(x, r, w)),
                         argnums=(0, 1, 2)), (x, x, aval((1024,), f32)))
    w = aval((1024, 1024), f32)
    return (jax.grad(lambda x, *ws: _sum(ops.fused_qkv(x, *ws)),
                     argnums=(0, 1, 2, 3)), (x, w, w, w))


@pytest.mark.parametrize("kind", ops.KERNEL_KINDS)
def test_kernel_compiles_for_v5e(kind, one_chip, on_tpu):
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    fn, args = _kernel_case(kind, aval)
    _, hlo = _compile(fn, *args)
    assert "tpu_custom_call" in hlo, kind


def test_gpt3_medium_block_grads_compile_for_v5e(one_chip, on_tpu):
    """One block of the chip path's model (Pallas attention, fused
    epilogues) at published widths: its fwd+bwd compiles and fits."""
    from repro.models import Model
    arch = get_arch("gpt3_medium")
    model = Model(arch, dtype=jnp.float32, remat=False, attn_impl="kernel")
    place = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=one_chip)
    bp = jax.tree.map(place, jax.eval_shape(
        lambda: model._init_block(jax.random.PRNGKey(0))))
    x = place(jax.ShapeDtypeStruct((B, S, arch.d_model), jnp.float32))

    def loss(bp, x):
        y, _ = model.block(bp, x, jnp.zeros((), jnp.float32))
        return jnp.sum(y)

    compiled, hlo = _compile(jax.grad(loss, argnums=(0, 1)), bp, x)
    assert hlo.count("tpu_custom_call") >= 3, "flash + fused kernels"
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 16 * 2 ** 30
