"""Per-kernel validation: shape/dtype sweeps, interpret=True vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.coo import BlockedCOO, COOGraph
from repro.core.fixed_point import Q1_19, Q1_25, QFormat
from repro.core.quantization import quantize_weights
from repro.graphs import erdos_renyi
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _random_graph(v, e, seed):
    return erdos_renyi(v, e, seed=seed)


@pytest.mark.parametrize("v,e,k,v_tile,packet", [
    (256, 1024, 4, 64, 32),
    (500, 3000, 8, 128, 64),
    (1000, 8000, 16, 256, 128),
    (100, 400, 1, 128, 128),      # K=1: plain SpMV
    (64, 64, 2, 64, 32),          # single tile
])
def test_coo_spmv_float_sweep(v, e, k, v_tile, packet):
    g = _random_graph(v, e, seed=v + e)
    rng = np.random.default_rng(0)
    p = (rng.random((v, k)) / v).astype(np.float32)
    blocked = BlockedCOO.build(g, v_tile=v_tile, packet=packet)
    pp = kops.pad_p_for_blocks(jnp.asarray(p), blocked)
    out = np.asarray(kops.coo_spmv(blocked, pp, interpret=True))[:v]
    ref = np.asarray(kref.coo_spmv_ref(
        jnp.asarray(g.x), jnp.asarray(g.y), jnp.asarray(g.val), jnp.asarray(p), v))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("fmt", [Q1_25, Q1_19])
def test_coo_spmv_fixed_bit_exact(fmt):
    v, e, k = 400, 2500, 8
    g = _random_graph(v, e, seed=3)
    rng = np.random.default_rng(1)
    p_raw = rng.integers(0, fmt.scale // v + 2, (v, k)).astype(np.uint32)
    blocked = BlockedCOO.build(g, v_tile=128, packet=64)
    pp = kops.pad_p_for_blocks(jnp.asarray(p_raw), blocked)
    out = np.asarray(kops.coo_spmv(blocked, pp, fmt=fmt, interpret=True))[:v]
    ref = np.asarray(kref.coo_spmv_fixed_ref(
        jnp.asarray(g.x), jnp.asarray(g.y), jnp.asarray(g.quantized_val(fmt)),
        jnp.asarray(p_raw), v, fmt))
    assert (out == ref).all(), "fixed-point kernel must be bit-exact"


@pytest.mark.parametrize("fmt", [None, Q1_25], ids=["f32", "q1.25"])
def test_coo_spmv_multi_launch(monkeypatch, fmt):
    """A schedule cut into several launches (empty dst blocks inside the
    cuts) gives the single-launch result."""
    import repro.kernels.coo_spmv as coo_spmv
    v, k = 700, 4
    g = _random_graph(v, 1500, seed=11)
    rng = np.random.default_rng(2)
    p = (rng.random((v, k)) / v).astype(np.float32)
    if fmt is not None:
        p = np.floor(p * fmt.scale).astype(np.uint32)
    outs = []
    for limit in (coo_spmv.MAX_LAUNCH_STEPS, 16):
        monkeypatch.setattr(coo_spmv, "MAX_LAUNCH_STEPS", limit)
        blocked = BlockedCOO.build(g, v_tile=64, packet=32)
        pp = kops.pad_p_for_blocks(jnp.asarray(p), blocked)
        outs.append(np.asarray(kops.coo_spmv(blocked, pp, fmt=fmt,
                                             interpret=True))[:v])
    assert coo_spmv.dst_chunks(kops.packet_metadata(blocked)[0],
                               blocked.n_dst)[1:], "needs several launches"
    np.testing.assert_array_equal(outs[0], outs[1])


def test_blocked_coo_roundtrip():
    """Blocking preserves the edge multiset (local→global reconstruction)."""
    g = _random_graph(300, 2000, seed=7)
    b = BlockedCOO.build(g, v_tile=64, packet=32)
    n_src = b.n_src
    starts = b.block_starts
    xs, ys, vs = [], [], []
    for blk in range(b.n_dst * n_src):
        lo, hi = starts[blk] * b.packet, starts[blk + 1] * b.packet
        bx, by = blk // n_src, blk % n_src
        val = b.val[lo:hi]
        real = val > 0
        xs.append(b.x_local[lo:hi][real] + bx * b.v_tile)
        ys.append(b.y_local[lo:hi][real] + by * b.v_tile)
        vs.append(val[real])
    got = sorted(zip(np.concatenate(xs).tolist(), np.concatenate(ys).tolist(),
                     np.concatenate(vs).tolist()))
    want = sorted(zip(g.x.tolist(), g.y.tolist(), g.val.tolist()))
    assert got == want


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 384, 512, 128, 128, 128),
    (128, 256, 128, 64, 64, 64),
])
def test_quantized_matmul_sweep(m, k, n, bm, bn, bk):
    rng = np.random.default_rng(m + n)
    a = rng.standard_normal((m, k)).astype(np.float32)
    w = (rng.standard_normal((k, n)) * 0.05).astype(np.float32)
    qt = quantize_weights(jnp.asarray(w))
    out = kops.quantized_matmul(jnp.asarray(a), qt.q, qt.scale,
                                interpret=True, bm=bm, bn=bn, bk=bk)
    ref = kref.quantized_matmul_ref(jnp.asarray(a), qt.q, qt.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_quantized_matmul_shape_check():
    a = jnp.zeros((100, 128), jnp.float32)
    with pytest.raises(ValueError):
        kops.quantized_matmul(a, jnp.zeros((128, 128), jnp.int8),
                              jnp.ones((128,)), interpret=True)


@given(st.integers(2, 6), st.integers(1, 8))
@settings(max_examples=10, deadline=None)
def test_coo_spmv_property_random_shapes(log_v, k):
    """Property: kernel == oracle across random graph sizes and κ widths."""
    v = 2 ** log_v * 16
    g = _random_graph(v, v * 4, seed=log_v * 10 + k)
    rng = np.random.default_rng(k)
    p = (rng.random((v, k)) / v).astype(np.float32)
    blocked = BlockedCOO.build(g, v_tile=32, packet=16)
    pp = kops.pad_p_for_blocks(jnp.asarray(p), blocked)
    out = np.asarray(kops.coo_spmv(blocked, pp, interpret=True))[:v]
    ref = np.asarray(kref.coo_spmv_ref(
        jnp.asarray(g.x), jnp.asarray(g.y), jnp.asarray(g.val), jnp.asarray(p), v))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-8)


def test_packed_indices_uint16():
    """v_tile ≤ 65536 → indices pack to uint16, halving the index stream; the
    kernel consumes the packed form bit-identically."""
    g = _random_graph(500, 3000, seed=9)
    b = BlockedCOO.build(g, v_tile=128, packet=64)
    assert b.index_dtype == np.uint16
    xp_, yp_ = b.packed_indices()
    assert xp_.dtype == np.uint16
    np.testing.assert_array_equal(xp_.astype(np.int32), b.x_local)
    # packed stream bytes: 2+2 index bytes + value
    assert b.edge_stream_bytes(32) == b.num_packets * b.packet * 8
    assert b.edge_stream_bytes(26 // 1) < b.edge_stream_bytes(32)
    big = BlockedCOO.build(g, v_tile=1 << 17, packet=64)
    assert big.index_dtype == np.int32
