"""Grid-write discipline (kernels/gridcheck.py, DESIGN.md §13).

Two layers of coverage: unit tests of the checker itself (revisit
detection, carry rules, Mosaic semantics derivation), and the package
audit — every pallas_call the kernels construct must register a
CallRecord whose outputs are written from exactly one parallel grid
cell (or from declared-sequential axes only)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import gridcheck, ops
from repro.kernels.gridcheck import (CallRecord, GridWriteError, REGISTRY,
                                     check_grid_writes, checked_pallas_call,
                                     revisit_axes)

RNG = jax.random.PRNGKey(11)


# ----------------------------------------------------------------------
# revisit_axes: index-map probing
# ----------------------------------------------------------------------
def test_revisit_axes_detects_ignored_axis():
    # block index ignores axis 1 entirely -> every j writes block (i, 0)
    rev = revisit_axes((4, 8), lambda i, j: (i, 0))
    assert rev == (1,)


def test_revisit_axes_clean_map_has_none():
    assert revisit_axes((4, 8), lambda i, j: (i, j)) == ()


def test_revisit_axes_reversed_map_is_not_a_revisit():
    # reversed iteration still moves the block index every step
    assert revisit_axes((2, 8), lambda i, c: (i, 7 - c)) == ()


def test_revisit_axes_size_one_axis_skipped():
    # a size-1 axis has a single iteration: nothing to race
    assert revisit_axes((1, 8), lambda i, j: (0, j)) == ()


# ----------------------------------------------------------------------
# check_grid_writes: the discipline
# ----------------------------------------------------------------------
def test_check_rejects_parallel_revisit():
    with pytest.raises(GridWriteError, match="not declared sequential"):
        check_grid_writes(
            "bad", grid=(4, 8),
            out_specs=[pl.BlockSpec((1, 1), lambda i, j: (i, 0))])


def test_check_accepts_declared_sequential_revisit():
    rec = check_grid_writes(
        "ok", grid=(4, 8),
        out_specs=[pl.BlockSpec((1, 1), lambda i, j: (i, 0))],
        sequential_axes=(1,))
    assert rec.revisit_axes == ((1,),) and not rec.single_writer


def test_check_rejects_carry_on_parallel_axis():
    with pytest.raises(GridWriteError, match="corrupt the accumulator"):
        check_grid_writes(
            "bad_carry", grid=(4, 8),
            out_specs=[pl.BlockSpec((1, 1), lambda i, j: (i, j))],
            scratch_carry_axes=(1,), num_scratch=1)


def test_check_rejects_parallel_axis_inside_carry():
    # carry on axis 0 with a parallel axis 1 inside it: the carry would
    # interleave with axis-1 iterations
    with pytest.raises(GridWriteError, match="later axes"):
        check_grid_writes(
            "bad_trailing", grid=(4, 8),
            out_specs=[pl.BlockSpec((1, 1), lambda i, j: (i, j))],
            sequential_axes=(0,), scratch_carry_axes=(0,), num_scratch=1)


def test_check_accepts_innermost_sequential_carry():
    rec = check_grid_writes(
        "ok_carry", grid=(4, 8),
        out_specs=[pl.BlockSpec((1, 1), lambda i, j: (i, j))],
        sequential_axes=(1,), scratch_carry_axes=(1,), num_scratch=1)
    assert rec.scratch_carry_axes == (1,) and not rec.single_writer


def test_mosaic_semantics_derivation():
    params = gridcheck._mosaic_params((2, 3, 4), sequential_axes=(2,))
    assert isinstance(params, pltpu.CompilerParams)
    assert tuple(params.dimension_semantics) == (
        "parallel", "parallel", "arbitrary")


def test_checked_pallas_call_executes_and_registers():
    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8)
    y = checked_pallas_call(
        "toy_double", double, grid=(4,),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
        interpret=True)(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2)
    assert REGISTRY["toy_double"].single_writer


def test_checked_pallas_call_raises_before_execution():
    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    with pytest.raises(GridWriteError):
        checked_pallas_call(
            "toy_racy", k, grid=(4, 2),
            in_specs=[pl.BlockSpec((1, 8), lambda i, j: (i, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            interpret=True)


# ----------------------------------------------------------------------
# Package audit: every kernel in the tree obeys the discipline
# ----------------------------------------------------------------------
def _exercise_all_kernels():
    """Run fwd+bwd of every Pallas op so each call registers."""
    ks = jax.random.split(RNG, 8)
    q = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.float32)
    jax.grad(lambda *a: jnp.sum(ops.flash_attention(*a)), argnums=(0, 1, 2))(
        q, k, v)
    x = jax.random.normal(ks[3], (1, 64, 2, 8), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, 64, 2)))
    A = -jnp.exp(jax.random.normal(ks[5], (2,)) * 0.5)
    B = jax.random.normal(ks[6], (1, 64, 2, 4), jnp.float32)
    C = jax.random.normal(ks[7], (1, 64, 2, 4), jnp.float32)
    jax.grad(lambda *a: jnp.sum(ops.ssd(*a)[0]), argnums=(0, 1, 3, 4))(
        x, dt, A, B, C)
    from repro.kernels import fused
    x2 = jax.random.normal(ks[0], (48, 16), jnp.float32)
    r2 = jax.random.normal(ks[1], (48, 16), jnp.float32)
    w = jnp.ones((16,), jnp.float32)
    jax.grad(lambda *a: sum(jnp.sum(t) for t in fused.add_rmsnorm(
        *a, interpret=True)), argnums=(0, 1, 2))(x2, r2, w)
    wq = jax.random.normal(ks[2], (16, 32), jnp.float32)
    jax.grad(lambda x, w: sum(jnp.sum(t) for t in fused.qkv(
        x, w, w, w, interpret=True)), argnums=(0, 1))(x2, wq)


EXPECTED_KERNELS = {
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dk", "flash_bwd_dv",
    "ssd_fwd", "ssd_bwd", "fused_norm_fwd", "fused_norm_bwd",
    "fused_qkv_matmul",
}


def test_every_package_kernel_obeys_grid_discipline():
    """The PR 5 regression pin: no output or scratch ref in the package
    is written from more than one iteration of a parallel grid axis."""
    _exercise_all_kernels()
    missing = EXPECTED_KERNELS - set(REGISTRY)
    assert not missing, f"kernels never registered: {sorted(missing)}"
    for name in EXPECTED_KERNELS:
        rec = REGISTRY[name]
        for i, rev in enumerate(rec.revisit_axes):
            assert set(rev) <= set(rec.sequential_axes), (
                f"{name}: output {i} racy on axes "
                f"{set(rev) - set(rec.sequential_axes)}")
        assert set(rec.scratch_carry_axes) <= set(rec.sequential_axes), name


def test_flash_kernels_are_fully_single_writer():
    """All four flash calls need no sequential axes at all — the entire
    grid may be distributed on any backend."""
    _exercise_all_kernels()
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dk",
                 "flash_bwd_dv"):
        rec = REGISTRY[name]
        assert rec.single_writer, name
        assert rec.sequential_axes == (), name


def test_ssd_kernels_declare_chunk_axis_sequential():
    """SSD keeps its inter-chunk state carry, but on the declared
    sequential chunk axis (innermost) — legal everywhere a lowering
    serializes it."""
    _exercise_all_kernels()
    for name in ("ssd_fwd", "ssd_bwd"):
        rec = REGISTRY[name]
        assert rec.sequential_axes == (2,), name
        assert rec.scratch_carry_axes == (2,), name
        assert len(rec.grid) == 3 and rec.grid[2] >= 1, name
