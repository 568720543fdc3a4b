"""Launch layer: mesh purity, input specs, HLO parser, sharding specs.

NOTE: these tests run with the default 1-device CPU backend — the
512-device dry-run runs in its own process (launch/dryrun.py sets
XLA_FLAGS only when run as a program).  A small-device-count end-to-end dry-run
happens in test_dryrun_subprocess.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, all_archs, all_cells, cells_for, get_arch
from repro.launch.hloparse import analyze, parse_module
from repro.runtime.sharding import ShardingStrategy


def test_mesh_module_import_is_pure():
    """Importing mesh.py must not initialize jax devices."""
    import importlib
    import repro.launch.mesh as m
    importlib.reload(m)
    assert callable(m.make_production_mesh)


def test_cell_enumeration():
    cells = all_cells()
    # 10 archs x 3 shapes + 2 long_500k = 32
    assert len(cells) == 32
    names = {(a.name, s.name) for a, s in cells}
    assert ("mamba2_780m", "long_500k") in names
    assert ("hymba_1_5b", "long_500k") in names
    assert ("qwen2_5_32b", "long_500k") not in names


def test_input_specs_shapes():
    from repro.launch import specs as sp
    from repro.models import Model
    arch = get_arch("phi3_vision_4_2b")
    shape = SHAPES["train_4k"]
    b = sp.batch_specs(arch, shape)
    # frontend tokens are carved out of the text sequence
    assert b["tokens"].shape == (256, 4096 - 576)
    assert b["frontend_embeds"].shape == (256, 576, 3072)
    assert all(isinstance(v, jax.ShapeDtypeStruct) for v in b.values())


def test_hloparse_simple_module():
    text = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %a = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %w = f32[8,8]{1,0} constant(0)
  %dot.1 = f32[8,8]{1,0} dot(%a, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%dot.1), replica_groups=[2,4]<=[8], to_apply=%sum
  %c = s32[] get-tuple-element(%p), index=0
  %one = s32[] constant(1)
  %inc = s32[] add(%c, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%inc, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %c = s32[] get-tuple-element(%p), index=0
  %lim = s32[] constant(5)
  ROOT %cmp = pred[] compare(%c, %lim), direction=LT
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%zero, %x)
  %w2 = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w2), index=1
}
"""
    st = analyze(text)
    # dot: 2*8*8*8 = 1024 flops, x5 trips
    assert st.dot_flops == pytest.approx(1024 * 5)
    # all-reduce: 2*(4-1)/4 * 256B = 384B, x5
    assert st.collective_bytes == pytest.approx(384 * 5)
    assert st.num_whiles == 1


def test_hloparse_real_program():
    """Parser totals must match XLA's own count on a loop-free program."""
    def f(w, x):
        return jnp.sum((x @ w).astype(jnp.float32))
    w = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    c = jax.jit(f).lower(w, x).compile()
    st = analyze(c.as_text())
    xla = c.cost_analysis().get("flops", 0)
    assert st.dot_flops == pytest.approx(2 * 16 * 64 * 32, rel=0.01)
    assert st.dot_flops <= xla * 1.05 + 1e5


# ----------------------------------------------------------------------
# Sharding strategy specs (no multi-device needed: specs are symbolic)
# ----------------------------------------------------------------------
class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


@pytest.mark.parametrize("strategy", ["fsdp", "tp"])
def test_param_spec_divisibility_guard(strategy):
    st = ShardingStrategy(strategy=strategy)
    mesh = FakeMesh({"data": 16, "model": 16})
    # dim divisible -> sharded somewhere; prime dim -> fully replicated
    spec = st.param_spec(mesh, "blocks/attn/wq", (28, 2048, 2048))
    assert "model" in spec
    spec = st.param_spec(mesh, "blocks/attn/wq", (28, 2047, 2047))
    assert all(s is None for s in spec)


def test_tp_row_col_assignment():
    st = ShardingStrategy(strategy="tp")
    mesh = FakeMesh({"data": 16, "model": 16})
    wq = st.param_spec(mesh, "blocks/attn/wq", (28, 2048, 4096))
    assert wq[2] == "model" and wq[1] is None      # column parallel
    wo = st.param_spec(mesh, "blocks/attn/wo", (28, 4096, 2048))
    assert wo[1] == "model" and wo[2] is None      # row parallel
    emb = st.param_spec(mesh, "embed/table", (151936, 2048))
    assert emb[0] == "model"                       # vocab sharded


def test_fsdp_batch_axes_include_model():
    st = ShardingStrategy(strategy="fsdp", data_axes=("pod", "data"))
    assert st.batch_axes == ("pod", "data", "model")
    st2 = ShardingStrategy(strategy="tp", data_axes=("data",))
    assert st2.batch_axes == ("data",)


def test_batch_spec_prefix_fallback():
    st = ShardingStrategy(strategy="fsdp", data_axes=("pod", "data"))
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert st.batch_spec(mesh, 512) == P(("pod", "data", "model"))
    assert st.batch_spec(mesh, 256) == P(("pod", "data"))  # 256 % 512 != 0
    assert st.batch_spec(mesh, 2) == P("pod")
    assert st.batch_spec(mesh, 1) == P()


def test_model_flops_definitions():
    from repro.launch.dryrun import model_flops
    arch = get_arch("qwen2_moe_a2_7b")
    tr = model_flops(arch, SHAPES["train_4k"])
    # MoE uses ACTIVE params
    assert tr == pytest.approx(6 * arch.active_params() * 4096 * 256)
    de = model_flops(arch, SHAPES["decode_32k"])
    assert de == pytest.approx(2 * arch.active_params() * 128)


def test_compile_cache_dir_is_fixed(monkeypatch):
    """The launchers' compile cache lives where JAX_COMPILATION_CACHE_DIR
    says (and nothing else is set), else at <checkout>/.jax_cache — a
    fixed path, since the path is part of what lets a later run hit."""
    import os
    from repro.utils import compile_cache
    set_to = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert not set_to
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert set_to == {"jax_compilation_cache_dir": path}


def test_full_layers_cuts_depth_only():
    from repro.configs import reduced, sized
    pub = get_arch("gpt3_medium")
    cut = sized(pub, full=True, layers=8)
    assert cut.num_layers == 8
    assert cut == dataclasses.replace(pub, num_layers=8)   # every width kept
    assert sized(pub, full=True) == pub
    assert sized(pub, full=False, layers=3) == reduced(pub, layers=3)
    assert sized(pub, full=False, smoke_layers=4).num_layers == 4
