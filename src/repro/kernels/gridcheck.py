"""Grid-write static check for Pallas kernels (DESIGN.md §13).

A kernel whose output block is written from more than one iteration of
a PARALLEL grid axis — or whose scratch carries state across one — is
correct only while that axis happens to run sequentially; Mosaic may
split a "parallel" axis across TensorCores and corrupt the accumulator.
Every pallas_call in this package is built through
``checked_pallas_call``, which

  1. numerically probes each output BlockSpec index map and derives the
     *revisit axes* — grid axes along which the map keeps returning the
     same block index (i.e. several grid cells write the same block);
  2. asserts revisit axes ⊆ the declared ``sequential_axes`` and that
     scratch state is only carried along declared sequential axes whose
     trailing axes are all sequential too (a carry must ride an
     innermost sequential suffix of the grid);
  3. records the verdict in ``REGISTRY`` so tests/CI can audit every
     kernel structure in one sweep;
  4. passes Mosaic ``dimension_semantics`` from the declaration —
     parallel axes "parallel" (Mosaic may distribute them), sequential
     axes "arbitrary" (Mosaic serializes, which is what makes the carry
     legal there).

A kernel with NO revisit axes and NO scratch carry is single-writer:
every output block is written by exactly one grid cell, so its whole
grid may be parallel.  All flash kernels satisfy this; the SSD kernels
keep their inter-chunk state carry and declare the chunk axis
sequential.

The probe evaluates index maps at integer grid coordinates (axis 0,
then 1 and n-1 per axis, others held at 0); maps here are affine or
reversed-affine in each axis, for which that detects revisits exactly.
Scratch usage itself cannot be introspected from the call signature —
``scratch_carry_axes`` is the author's declaration, and the parity
tests versus the jnp oracles are what keep the declaration honest.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class GridWriteError(AssertionError):
    """A pallas_call writes an output/scratch ref from more than one
    iteration of a parallel grid axis."""


@dataclasses.dataclass(frozen=True)
class CallRecord:
    """Audited structure of one checked pallas_call."""
    name: str
    grid: Tuple[int, ...]
    revisit_axes: Tuple[Tuple[int, ...], ...]   # per output
    sequential_axes: Tuple[int, ...]
    scratch_carry_axes: Tuple[int, ...]
    num_scratch: int

    @property
    def single_writer(self) -> bool:
        return (not self.scratch_carry_axes
                and all(not r for r in self.revisit_axes))


#: name -> most recent CallRecord, for test/CI audits.
REGISTRY: Dict[str, CallRecord] = {}


def _block_index(index_map, coords: Sequence[int]) -> Tuple[int, ...]:
    out = index_map(*coords)
    if not isinstance(out, tuple):
        out = (out,)
    return tuple(int(x) for x in out)


def revisit_axes(grid: Sequence[int], index_map) -> Tuple[int, ...]:
    """Grid axes along which ``index_map`` never moves the block index —
    i.e. every iteration of that axis targets the SAME output block."""
    ndim = len(grid)
    base = [0] * ndim
    ref = _block_index(index_map, base)
    rev = []
    for axis, n in enumerate(grid):
        if n <= 1:
            continue                       # a size-1 axis cannot revisit
        moved = False
        for val in {1, n - 1}:
            probe = list(base)
            probe[axis] = val
            if _block_index(index_map, probe) != ref:
                moved = True
                break
        if not moved:
            rev.append(axis)
    return tuple(rev)


def _normalize_specs(specs) -> Tuple[Any, ...]:
    if isinstance(specs, (list, tuple)):
        return tuple(specs)
    return (specs,)


def check_grid_writes(name: str, *, grid: Sequence[int], out_specs,
                      sequential_axes: Sequence[int] = (),
                      scratch_carry_axes: Sequence[int] = (),
                      num_scratch: int = 0) -> CallRecord:
    """Assert the single-writer/sequential-carry discipline and record
    the verdict.  Raises GridWriteError on violation."""
    grid = tuple(int(g) for g in grid)
    seq = tuple(sorted(set(int(a) for a in sequential_axes)))
    carry = tuple(sorted(set(int(a) for a in scratch_carry_axes)))
    revs = []
    for i, spec in enumerate(_normalize_specs(out_specs)):
        rev = revisit_axes(grid, spec.index_map)
        offending = [a for a in rev if a not in seq]
        if offending:
            raise GridWriteError(
                f"{name}: output {i} is written from every iteration of "
                f"grid axes {offending} (grid {grid}) but those axes are "
                f"not declared sequential ({seq}); a parallel backend "
                f"would race the writes")
        revs.append(rev)
    for a in carry:
        if a not in seq:
            raise GridWriteError(
                f"{name}: scratch carried across grid axis {a} which is "
                f"not declared sequential ({seq}); a parallel backend "
                f"would corrupt the accumulator")
        trailing = [t for t in range(a + 1, len(grid))
                    if grid[t] > 1 and t not in seq]
        if trailing:
            raise GridWriteError(
                f"{name}: scratch carried across axis {a} but later axes "
                f"{trailing} are parallel — the carry would interleave "
                f"with their iterations")
    rec = CallRecord(name=name, grid=grid, revisit_axes=tuple(revs),
                     sequential_axes=seq, scratch_carry_axes=carry,
                     num_scratch=num_scratch)
    REGISTRY[name] = rec
    return rec


def _mosaic_params(grid: Sequence[int],
                   sequential_axes: Sequence[int]) -> pltpu.CompilerParams:
    sems = tuple("arbitrary" if a in sequential_axes else "parallel"
                 for a in range(len(grid)))
    return pltpu.CompilerParams(dimension_semantics=sems)


def checked_pallas_call(name: str, kernel, *, grid, in_specs, out_specs,
                        out_shape, scratch_shapes: Sequence[Any] = (),
                        interpret: bool = False,
                        sequential_axes: Sequence[int] = (),
                        scratch_carry_axes: Sequence[int] = ()):
    """``pl.pallas_call`` behind the grid-write check.

    Raises GridWriteError at call-construction time if any output block
    is written from an undeclared-parallel grid axis, then forwards to
    ``pl.pallas_call`` with Mosaic dimension semantics derived from the
    declaration (parallel axes distributable, sequential serialized).
    """
    check_grid_writes(name, grid=grid, out_specs=out_specs,
                      sequential_axes=sequential_axes,
                      scratch_carry_axes=scratch_carry_axes,
                      num_scratch=len(tuple(scratch_shapes)))
    kwargs: Dict[str, Any] = dict(grid=grid, in_specs=in_specs,
                                  out_specs=out_specs, out_shape=out_shape,
                                  interpret=interpret)
    scratch_shapes = tuple(scratch_shapes)
    if scratch_shapes:
        kwargs["scratch_shapes"] = list(scratch_shapes)
    if not interpret:
        # a Mosaic-side contract; the interpreter has no use for it
        kwargs["compiler_params"] = _mosaic_params(grid, sequential_axes)
    return pl.pallas_call(kernel, **kwargs)
