"""Property tests for the Qm.f fixed-point datapath (hypothesis)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fixed_point import PAPER_FORMATS, QFormat, format_for_bits

FORMATS = list(PAPER_FORMATS.values()) + [QFormat(2, 14), QFormat(1, 30), QFormat(4, 8)]


@st.composite
def fmt_and_raws(draw, n=64):
    fmt = draw(st.sampled_from(FORMATS))
    raws = draw(st.lists(st.integers(0, fmt.max_raw), min_size=n, max_size=n))
    return fmt, np.array(raws, np.uint32)


@given(fmt_and_raws())
@settings(max_examples=50, deadline=None)
def test_mul_matches_bigint(data):
    """The 16-bit-limb uint32 multiply == exact Python bigint (a·b) >> f."""
    fmt, raws = data
    a, b = raws[: len(raws) // 2], raws[len(raws) // 2:]
    got = np.asarray(fmt.mul(jnp.asarray(a), jnp.asarray(b)))
    want = [(int(x) * int(y)) >> fmt.frac_bits for x, y in zip(a, b)]
    assert [int(g) for g in got] == want


@given(fmt_and_raws())
@settings(max_examples=30, deadline=None)
def test_add_saturates(data):
    fmt, raws = data
    a, b = raws[: len(raws) // 2], raws[len(raws) // 2:]
    got = np.asarray(fmt.add(jnp.asarray(a), jnp.asarray(b)))
    want = np.minimum(a.astype(np.uint64) + b.astype(np.uint64), fmt.max_raw)
    assert (got == want.astype(np.uint32)).all()


@given(st.lists(st.floats(0.0, 1.999, allow_nan=False), min_size=8, max_size=8),
       st.sampled_from([f for f in FORMATS if f.frac_bits <= 23]))
@settings(max_examples=50, deadline=None)
def test_f32_grid_matches_integer_path(vals, fmt):
    """quantize_f32 == from_float→to_float while the grid fits the f32 mantissa."""
    x = np.array(vals, np.float32)
    via_int = np.asarray(fmt.to_float(fmt.from_float(x)))
    via_f32 = np.asarray(fmt.quantize_f32(jnp.asarray(x)))
    assert np.array_equal(via_int, via_f32)


@given(st.floats(0.0, 1.999), st.sampled_from(FORMATS))
@settings(max_examples=100, deadline=None)
def test_truncation_towards_zero(v, fmt):
    """Quantization never rounds up (the paper's truncation policy).
    Checked in exact integer→f64 math (to_float's f32 cast may round)."""
    import jax
    with jax.enable_x64():
        raw = int(np.asarray(fmt.from_float(np.float64(v))))
    q = raw / fmt.scale   # exact for ≤53-bit significands
    assert q <= v + 1e-12
    assert v - q < fmt.resolution + 1e-12 or raw == fmt.max_raw


def test_paper_format_table():
    assert format_for_bits(26).frac_bits == 25
    assert format_for_bits(20).frac_bits == 19
    assert format_for_bits(26).name == "Q1.25"
    with pytest.raises(ValueError):
        QFormat(1, 32)  # > 32 bits


def test_mul_extremes():
    fmt = PAPER_FORMATS["Q1.25"]
    m = fmt.max_raw
    got = int(np.asarray(fmt.mul(jnp.asarray(np.uint32(m)), jnp.asarray(np.uint32(m)))))
    assert got == (m * m) >> fmt.frac_bits
