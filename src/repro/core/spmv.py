"""Streaming COO SpMV/SpMM — the paper's §4.1.1, in three implementations.

All compute X @ P for X in COO (x=dst rows, y=src cols, val) and dense P [V, K]
(K = κ batched personalization vectors; K=1 recovers plain SpMV).

Paths
-----
1. ``spmv_float``      pure-jnp float32: gather → multiply → segment-sum.  The XLA
                       production path (scatter-add lowers natively); also the
                       oracle shape for the Pallas kernel.
2. ``spmv_fixed``      bit-exact unsigned Qm.f: per-edge truncating multiply
                       (uint32 limb decomposition) then exact raw-domain
                       accumulation — faithful to the FPGA datapath where the
                       dp_buffer multiply truncates and the aggregator adds raw.
                       Given a ``SortedDst`` stream, the accumulation is a
                       prefix sum read at the row boundaries (the aggregator's
                       reliance on x being monotone); given plain ``x``, a
                       segment-sum scatter.
3. ``spmv_pallas``     the Pallas TPU kernel (repro.kernels.coo_spmv) over the
                       2-D BlockedCOO layout.
4. sharded             shard_map multi-device (``make_sharded_spmv`` float /
                       ``make_sharded_spmv_fixed`` bit-exact raw uint32): edges
                       partitioned by dst range on the ceil-division padded
                       layout of ``sharded_vertex_layout``, P_t all-gathered
                       over the mesh axis, each device produces its dst slice —
                       the paper's "partitioning techniques [18, 20]" integrated
                       as a first-class feature.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.fixed_point import QFormat

Array = jax.Array


# ----------------------------------------------------------------------------
# 1. float path
# ----------------------------------------------------------------------------
def spmv_float(x: Array, y: Array, val: Array, p: Array, num_vertices: int) -> Array:
    """out[i, k] = Σ_{e: x[e]=i} val[e] · p[y[e], k]   (float32).

    Padding edges (val=0) contribute nothing regardless of their x/y.
    """
    contrib = val[:, None] * p[y]                     # [E, K] gather + multiply
    return jax.ops.segment_sum(contrib, x, num_segments=num_vertices)


# ----------------------------------------------------------------------------
# 2. bit-exact fixed-point path
# ----------------------------------------------------------------------------
#: edges a stream reduced by ``row_prefix_sum`` is padded to a multiple of.
#: The TPU tiles the [E, κ] uint32 products (8, 128) along E: at a multiple
#: of 8 · 128 the prefix sum of a 2^20-vertex graph's stream compiles in
#: seconds, otherwise in over a minute (TPU v5e).
ROW_PREFIX_ALIGN = 1024


class SortedDst(NamedTuple):
    """An edge stream's ``x`` that is sorted by destination, with its rows.

    ``row_ptr`` [V + 1] int32 holds the CSR row pointers of the unpadded
    sorted prefix: row r owns edges ``[row_ptr[r], row_ptr[r + 1])``.  Edges
    past ``row_ptr[V]`` (the packet pad tail) belong to no row."""
    x: Array
    row_ptr: Array


def row_prefix_sum(prod: Array, row_ptr: Array) -> Array:
    """Per-row sums of ``prod`` [E, K] uint32 over the row ranges of
    ``row_ptr``, as differences of its wrap-around prefix sum.

    uint32 addition is exact modulo 2^32, so ``C[end] − C[start]`` is each
    row's true sum bit for bit whenever that sum is below 2^32 — the same
    condition under which a scatter-add is exact."""
    c = jnp.cumsum(prod, axis=0, dtype=jnp.uint32)    # inclusive, wraps
    # exclusive prefix at each pointer: C[p] = c[p − 1], C[0] = 0
    at = jnp.where((row_ptr > 0)[:, None], c[jnp.maximum(row_ptr - 1, 0)],
                   jnp.uint32(0))
    return at[1:] - at[:-1]


def spmv_fixed(
    x: Union[Array, SortedDst], y: Array, val_raw: Array, p_raw: Array,
    num_vertices: int, fmt: QFormat
) -> Array:
    """Fixed-point SpMM on raw uint32 values.

    Each edge product truncates to the format (the FPGA DSP behaviour); the
    aggregation is exact in the raw domain (sums stay < 2^total_bits because X@p
    entries are ≤ 1 for a stochastic X and probability p — DESIGN.md §2).
    A ``SortedDst`` ``x`` reduces by ``row_prefix_sum``; a plain ``x`` by a
    segment-sum scatter.  Both give the same bits.
    """
    prod = fmt.mul(val_raw[:, None], p_raw[y])        # [E, K] uint32
    if isinstance(x, SortedDst):
        return row_prefix_sum(prod, x.row_ptr)
    # segment_sum on uint32: cast to int32 view is unsafe near 2^31; raw values
    # stay < 2^27 for ≤26-bit formats so int32 accumulation is exact.
    acc = jax.ops.segment_sum(prod.astype(jnp.int32), x, num_segments=num_vertices)
    return acc.astype(jnp.uint32)


# ----------------------------------------------------------------------------
# 3. Pallas kernel path (imported lazily to keep core importable sans kernels)
# ----------------------------------------------------------------------------
def spmv_pallas(blocked, p: Array, *, interpret: Optional[bool] = None) -> Array:
    from repro.kernels import ops as kops

    return kops.coo_spmv(blocked, p, interpret=interpret)


# ----------------------------------------------------------------------------
# 4. sharded path (graph partitioned by destination range)
# ----------------------------------------------------------------------------
def sharded_vertex_layout(num_vertices: int, n_shards: int) -> tuple:
    """(v_local, v_padded) of the ceil-division dst layout shared by the
    partitioner and every sharded kernel: each shard owns ``v_local =
    ceil(V / n_shards)`` destination rows, the concatenated output covers
    ``v_padded = n_shards · v_local ≥ V`` rows, and the ``v_padded − V``
    phantom rows of the last shard receive no edges (they are sliced away
    before anything downstream sees them)."""
    v_local = -(-num_vertices // n_shards)
    return v_local, n_shards * v_local


def make_sharded_spmv(mesh, axis: str, num_vertices: int):
    """Build a shard_map SpMV: edges pre-partitioned by dst into len(axis) shards.

    Each device holds an equal-size (padded) edge shard whose x all fall in its
    dst range, plus the full P (replicated via all-gather by the in_spec).  Output
    is the device's dst slice — concatenated by the out_spec and sliced back to
    ``num_vertices`` rows (the ceil-division layout of ``sharded_vertex_layout``
    pads the vertex space, so any V works on any shard count).  Collective cost:
    one all-gather of P per iteration = V·K·4 bytes — matches the paper's note
    that partitioned designs trade bandwidth for capacity.
    """
    n_shards = mesh.shape[axis]
    v_local, _ = sharded_vertex_layout(num_vertices, n_shards)

    def local_spmv(x_loc, y, val, p):
        # x_loc already local to the shard's dst range; p is full (replicated).
        contrib = val[:, None] * p[y]
        return jax.ops.segment_sum(contrib, x_loc, num_segments=v_local)

    sharded = jax.shard_map(
        local_spmv,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(axis),
    )

    def spmv(x, y, val, p):
        return sharded(x, y, val, p)[:num_vertices]

    return spmv


def make_sharded_spmv_fixed(mesh, axis: str, num_vertices: int, fmt: QFormat):
    """Sharded counterpart of ``spmv_fixed``: raw uint32 domain, truncating
    ``fmt.mul`` per edge, exact raw-domain accumulation per shard.

    Integer accumulation is exact and order-independent, so the concatenated
    result is *bit-identical* to single-device ``spmv_fixed`` — partitioning
    only splits each destination row's sum into per-shard partial sums that
    never mix (each dst row lives on exactly one shard).
    """
    n_shards = mesh.shape[axis]
    v_local, _ = sharded_vertex_layout(num_vertices, n_shards)

    def local_spmv(x_loc, y, val_raw, p_raw):
        prod = fmt.mul(val_raw[:, None], p_raw[y])
        acc = jax.ops.segment_sum(prod.astype(jnp.int32), x_loc,
                                  num_segments=v_local)
        return acc.astype(jnp.uint32)

    sharded = jax.shard_map(
        local_spmv,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(axis),
    )

    def spmv(x, y, val_raw, p_raw):
        return sharded(x, y, val_raw, p_raw)[:num_vertices]

    return spmv


def partition_edges_by_dst(x, y, val, num_vertices: int, n_shards: int, packet: int = 256):
    """Host-side: bucket edges by dst range and pad each shard to equal length.

    Ranges are ceil(num_vertices / n_shards) wide — ``sharded_vertex_layout``,
    the same layout the sharded kernels consume — so when num_vertices does not
    divide evenly the remainder vertices land in the (short) last shard instead
    of a phantom shard ``n_shards`` whose edges were silently dropped.

    ``val``'s dtype is preserved (float32 edge weights and raw uint32 quantized
    values partition through the same code path; pad edges carry val=0, which
    contributes nothing in either domain).
    """
    import numpy as np

    v_local, _ = sharded_vertex_layout(num_vertices, n_shards)
    shard_of = np.asarray(x) // v_local
    shards = []
    max_e = 0
    for s in range(n_shards):
        m = shard_of == s
        xs = np.asarray(x)[m] % v_local
        ys = np.asarray(y)[m]
        vs = np.asarray(val)[m]
        shards.append((xs, ys, vs))
        max_e = max(max_e, xs.shape[0])
    max_e = max(packet, (max_e + packet - 1) // packet * packet)
    X = np.zeros((n_shards, max_e), np.int32)
    Y = np.zeros((n_shards, max_e), np.int32)
    V = np.zeros((n_shards, max_e), np.asarray(val).dtype)
    for s, (xs, ys, vs) in enumerate(shards):
        X[s, : xs.shape[0]] = xs
        Y[s, : ys.shape[0]] = ys
        V[s, : vs.shape[0]] = vs
    return X.reshape(-1), Y.reshape(-1), V.reshape(-1)
