"""Mesh-sharded engines: the paper's multi-channel edge partitioning scaled
to a ``jax.sharding.Mesh`` axis.

The host owns the partitioning/packaging step (the CPU–FPGA synergy argument
of arXiv 2004.13907): edges are bucketed by destination range once per
topology epoch — per quantized format too, through the same dtype-preserving
partitioner, so fixed-point shards stream the exact raw values the
single-device ``FixedEngine`` would.  Per-shard raw accumulation is exact and
each destination row lives on exactly one shard, so ``ShardedFixedEngine`` is
*bit-identical* to ``FixedEngine``; the float pair is numerically equal.

Bucket arrays are placed one shard per device (``NamedSharding`` on the
mesh axis), at registration and at every re-partition.

Delta ingestion re-buckets only the destination ranges a merge touched
(``refresh_partition_after_delta``), falling back to a full re-partition when
the delta moves the ceil-division layout itself (vertex growth changing
``ceil(V / n_shards)``) or an affected bucket outgrows its padding.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.fixed_point import QFormat
from repro.core.ppr import (
    make_ppr_sharded_fixed_step,
    make_ppr_sharded_float_step,
    personalization_matrix,
    personalization_matrix_fixed,
)
from repro.core.spmv import partition_edges_by_dst, sharded_vertex_layout
from repro.ppr_serving.engine.base import WaveEngine, WavePlan, register_engine

__all__ = ["ShardedFloatEngine", "ShardedFixedEngine"]


# ---------------------------------------------------------------------------
# partition state helpers — operate on a ShardedRegisteredGraph's buckets
# ---------------------------------------------------------------------------
def _place(rg, flat: np.ndarray) -> jax.Array:
    """Upload a flat [S · max_e] bucket array one shard per device."""
    return jax.device_put(flat, NamedSharding(rg.mesh, PartitionSpec(rg.axis)))


def partition_topology(rg) -> None:
    """(Re-)bucket the *unpadded* edge stream by destination range; pad edges
    would only inflate shard 0 with zero slots the per-shard packet padding
    already provides.  Re-partitions every known quantized format through the
    same dtype-preserving partitioner."""
    sx, sy, sval = partition_edges_by_dst(
        rg.source.x, rg.source.y, rg.source.val,
        rg.num_vertices, rg.n_shards, packet=rg.packet)
    s = rg.n_shards
    rg._host_x = sx.reshape(s, -1)
    rg._host_y = sy.reshape(s, -1)
    rg._host_val = sval.reshape(s, -1)
    rg.sharded_x = _place(rg, sx)
    rg.sharded_y = _place(rg, sy)
    rg.sharded_val = _place(rg, sval)
    for fmt in set(rg._sharded_quantized) | set(rg._sharded_quant_host):
        _, _, sq = partition_edges_by_dst(
            rg.source.x, rg.source.y, rg._quantize_host(fmt),
            rg.num_vertices, rg.n_shards, packet=rg.packet)
        rg._sharded_quant_host[fmt] = sq.reshape(s, -1)
        rg._sharded_quantized[fmt] = _place(rg, sq)


def partition_format(rg, fmt: QFormat) -> jax.Array:
    """Raw uint32 edge shard values in the partitioned layout (cached)."""
    if fmt not in rg._sharded_quantized:
        _, _, sval = partition_edges_by_dst(
            rg.source.x, rg.source.y, rg._quantize_host(fmt),
            rg.num_vertices, rg.n_shards, packet=rg.packet)
        rg._sharded_quant_host[fmt] = sval.reshape(rg.n_shards, -1)
        rg._sharded_quantized[fmt] = _place(rg, sval)
    return rg._sharded_quantized[fmt]


def refresh_partition_after_delta(rg, info) -> None:
    """Delta ingestion on a meshed graph: re-partition only the destination
    buckets that own a changed or removed edge.

    Falls back to a full re-partition when the delta moves the bucket
    geometry itself (vertex growth changing ``ceil(V / n_shards)``) or an
    affected bucket outgrows the current per-shard padding.  Idempotent per
    delta: both family members are armed on most graphs and each calls in."""
    if not rg._sharded_stale:
        return
    rg._sharded_stale = False
    old_v_local = rg._pre_delta_v_local
    v_local, _ = sharded_vertex_layout(rg.num_vertices, rg.n_shards)
    max_e = rg._host_x.shape[1]
    shard_of = rg.source.x // v_local
    counts = np.bincount(shard_of, minlength=rg.n_shards)
    affected = np.unique(info.changed_dst // v_local).astype(np.int64)
    if v_local != old_v_local or counts[affected].max(initial=0) > max_e:
        partition_topology(rg)
        return
    for s in affected:
        m = shard_of == s
        n = int(counts[s])
        for host in (rg._host_x, rg._host_y, rg._host_val):
            host[s, :] = 0
        rg._host_x[s, :n] = rg.source.x[m] % v_local
        rg._host_y[s, :n] = rg.source.y[m]
        rg._host_val[s, :n] = rg.source.val[m]
        for fmt, hq in rg._sharded_quant_host.items():
            hq[s, :] = 0
            hq[s, :n] = rg._quantized_host[fmt][m]
    rg.sharded_x = _place(rg, rg._host_x.reshape(-1))
    rg.sharded_y = _place(rg, rg._host_y.reshape(-1))
    rg.sharded_val = _place(rg, rg._host_val.reshape(-1))
    for fmt, hq in rg._sharded_quant_host.items():
        rg._sharded_quantized[fmt] = _place(rg, hq.reshape(-1))


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------
@register_engine
class ShardedFloatEngine(WaveEngine):
    """float32 iterations whose SpMV streams mesh-partitioned edge shards."""

    key = "sharded_float"
    family = "sharded"
    fixed = False
    needs_mesh = True

    def prepare(self, rg, fmt: Optional[QFormat] = None) -> None:
        if not hasattr(rg, "_host_x"):
            partition_topology(rg)

    def plan(self, rg, fmt: Optional[QFormat] = None, *, alpha: float,
             iterations: int, convergence=None,
             topk_tile: Optional[int] = None, trace_hook=None) -> WavePlan:
        self.prepare(rg)
        body = make_ppr_sharded_float_step(rg.mesh, rg.axis,
                                           rg.num_vertices, alpha)
        x, y, val = rg.sharded_x, rg.sharded_y, rg.sharded_val
        dangling = rg.dangling
        num_vertices = rg.num_vertices

        def step(Vmat, P):
            return body(x, y, val, dangling, Vmat, P)

        return WavePlan(
            engine=self.key, fixed=False, scale=None,
            initial=lambda pers: personalization_matrix(num_vertices, pers),
            step=step,
            iterate=self._make_iterate(iterations, convergence, False, None,
                                       trace_hook=trace_hook),
            topk=self._make_topk(topk_tile), reduce="scatter")

    def on_delta(self, rg, info) -> None:
        rg.refresh_device_base()
        refresh_partition_after_delta(rg, info)


@register_engine
class ShardedFixedEngine(WaveEngine):
    """Bit-exact reduced-precision iterations over mesh-partitioned raw
    shards — bit-identical to ``FixedEngine`` on any V and shard count."""

    key = "sharded_fixed"
    family = "sharded"
    fixed = True
    needs_mesh = True

    def prepare(self, rg, fmt: Optional[QFormat] = None) -> None:
        if not hasattr(rg, "_host_x"):
            partition_topology(rg)
        if fmt is not None:
            partition_format(rg, fmt)

    def plan(self, rg, fmt: Optional[QFormat] = None, *, alpha: float,
             iterations: int, convergence=None,
             topk_tile: Optional[int] = None, trace_hook=None) -> WavePlan:
        if fmt is None:
            raise ValueError(f"{self.key!r} engine needs a concrete Q format")
        self.prepare(rg)
        body = make_ppr_sharded_fixed_step(fmt, rg.mesh, rg.axis,
                                           rg.num_vertices, alpha)
        x, y = rg.sharded_x, rg.sharded_y
        val_raw = partition_format(rg, fmt)
        dangling = rg.dangling
        num_vertices = rg.num_vertices

        def step(Vmat, P):
            return body(x, y, val_raw, dangling, Vmat, P)

        return WavePlan(
            engine=self.key, fixed=True, scale=fmt.scale,
            initial=lambda pers: personalization_matrix_fixed(
                num_vertices, pers, fmt),
            step=step,
            iterate=self._make_iterate(iterations, convergence, True, fmt.scale,
                                       trace_hook=trace_hook),
            topk=self._make_topk(topk_tile), reduce="scatter")

    def on_delta(self, rg, info) -> None:
        rg.refresh_device_base()
        refresh_partition_after_delta(rg, info)
