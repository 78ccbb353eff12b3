"""Roofline machinery: HLO collective parsing, term composition, model flops."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.roofline.analysis import (
    collective_bytes,
    model_flops_forward,
    model_flops_train,
    roofline,
)

HLO_FIXTURE = """
  %x = f32[256,4096]{1,0} parameter(0)
  %ar = f32[256,4096]{1,0} all-reduce(f32[256,4096]{1,0} %x), replica_groups={}
  %ag = bf16[64,128]{1,0} all-gather(bf16[32,128]{1,0} %y), dimensions={0}
  %rs = f32[16]{0} reduce-scatter(f32[256]{0} %z), dimensions={0}
  %cp = u32[8,8]{1,0} collective-permute(u32[8,8]{1,0} %w), source_target_pairs={}
  %notacoll = f32[999]{0} add(f32[999]{0} %a, f32[999]{0} %b)
"""


def test_collective_parse_fixture():
    got = collective_bytes(HLO_FIXTURE)
    assert got["all-reduce"] == 256 * 4096 * 4
    assert got["all-gather"] == 64 * 128 * 2
    assert got["reduce-scatter"] == 16 * 4
    assert got["collective-permute"] == 8 * 8 * 4
    assert "add" not in got


def test_collective_parse_real_module():
    """Parse a real SPMD-partitioned module containing a psum."""
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P())) * 2

    txt = jax.jit(f).lower(jax.ShapeDtypeStruct((128,), jnp.float32)).compile().as_text()
    # single-device: no collectives expected, parser must not crash
    assert isinstance(collective_bytes(txt), dict)


def test_roofline_terms_and_bottleneck():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    t = roofline(cost, HLO_FIXTURE, chips=4, model_flops=197e12 * 2)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 2.0) < 1e-9
    assert t.bottleneck == "memory"
    assert abs(t.useful_flops_ratio - 2.0 / 4.0) < 1e-9


def test_model_flops_moe_uses_active_params():
    dense = get_config("gemma-2b")
    moe = get_config("mixtral-8x7b")
    assert model_flops_train(dense, 1000) == 6.0 * dense.param_count() * 1000
    assert model_flops_train(moe, 1000) == 6.0 * moe.active_param_count() * 1000
    assert moe.active_param_count() < moe.param_count() / 2


def test_param_counts_sane():
    """Analytic counts within expected ballparks of the published sizes."""
    approx = {
        "gemma2-27b": 27e9, "starcoder2-15b": 15e9, "mixtral-8x7b": 46e9,
        "mamba2-1.3b": 1.3e9, "gemma-2b": 2.5e9,
    }
    for arch, want in approx.items():
        got = get_config(arch).param_count()
        assert 0.5 * want < got < 1.7 * want, (arch, got, want)
