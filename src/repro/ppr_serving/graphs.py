"""Registered-graph state holders — host topology + device upload caches.

These classes hold *state*, not datapath logic: the step construction,
quantized partitioning and top-K strategies that used to live here are owned
by the engine backends (``repro.ppr_serving.engine``).  A graph knows its
``engine_family`` ("single" / "sharded"); the service resolves each wave to
the family member for its precision and hands it this state.

What stays here is what every engine shares: the unpadded host graph (the
delta base), packet padding, the out-degree vector, the host-side raw
quantization cache, and the host-side incremental merge of edge deltas —
surviving edges keep their raw bits, only entries whose source out-degree
moved are requantized, bit-identical to quantizing the merged graph from
scratch.  ``epoch`` counts applied deltas; the service stamps it into cache
keys and wave keys so results computed on different topologies never alias.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.coo import COOGraph, EdgeMergeInfo, quantize_values
from repro.core.fixed_point import QFormat
from repro.core.spmv import ROW_PREFIX_ALIGN, SortedDst, sharded_vertex_layout
from repro.graph_updates.delta import EdgeDelta
from repro.ppr_serving.telemetry import SINGLE_DEVICE_KEY

__all__ = ["RegisteredGraph", "ShardedRegisteredGraph"]


class RegisteredGraph:
    """Host-side graph state prepared once at registration and patched in
    place by edge deltas, plus the full-layout device upload cache.

    The full-layout edge stream (``x``/``y``/``val``) is uploaded eagerly —
    every single-device wave reads it.  ``ShardedRegisteredGraph`` defers that
    upload: its waves read only the partitioned shards, and the full layout is
    materialized lazily iff something actually needs it — a meshed graph is
    registered precisely because one device's memory is tight."""

    mesh_key = SINGLE_DEVICE_KEY   # waves on this graph run single-device
    engine_family = "single"

    _defer_full_upload = False
    # the fixed engine reduces the full stream by row prefix, and the float
    # engine reads the same device copy: both get whole row-prefix tiles
    _edge_align = ROW_PREFIX_ALIGN

    def __init__(self, name: str, g: COOGraph, packet: int = 256):
        self.name = name
        self.source = g                      # unpadded host graph (delta base)
        self.packet = packet
        self.epoch = 0
        self._pad_to = math.lcm(packet, self._edge_align)
        self.graph = g.pad_to_packets(self._pad_to)
        self.num_vertices = g.num_vertices
        self.dangling = jnp.asarray(self.graph.dangling)
        self._outdeg = np.bincount(g.y, minlength=g.num_vertices).astype(np.int64)
        self._full_device: Optional[Tuple[jnp.ndarray, ...]] = None
        self._rows = None                     # device_rows() cache
        self._rows_were_materialized = False
        self._quantized: Dict[QFormat, jnp.ndarray] = {}
        self._quantized_host: Dict[QFormat, np.ndarray] = {}   # unpadded uint32
        self._stale_device_formats: set = set()
        self._full_was_materialized = False
        self._armed: Dict[str, object] = {}    # engine key → engine instance
        if not self._defer_full_upload:
            self.device_full()

    # ---- engine bookkeeping -----------------------------------------------
    def arm(self, engine) -> None:
        """Record an engine as serving this graph — armed engines get the
        ``on_delta`` device-refresh callback after each edge delta."""
        self._armed[engine.key] = engine

    def armed_engines(self):
        return tuple(self._armed.values())

    # ---- device upload caches ---------------------------------------------
    def device_full(self) -> Tuple[jnp.ndarray, ...]:
        """The full-layout (packet-padded) device arrays ``(x, y, val)``."""
        if self._full_device is None:
            self._full_device = (jnp.asarray(self.graph.x),
                                 jnp.asarray(self.graph.y),
                                 jnp.asarray(self.graph.val))
        return self._full_device

    def device_rows(self) -> SortedDst:
        """The full-layout ``x`` with the row pointers of the unpadded
        stream, which the fixed-point step reduces by row prefix.
        ``row_ptr`` [V + 1] int32 is uploaded once per topology epoch.

        Raises ``ValueError`` when the stream is not sorted by destination
        (``COOGraph.from_edges`` sorts it, and delta merges keep it so)."""
        if self._rows is None:
            sx = self.source.x
            if np.any(sx[1:] < sx[:-1]):
                raise ValueError(
                    f"graph {self.name!r}: the fixed-point engine needs the "
                    f"edge stream sorted by destination (build it with "
                    f"COOGraph.from_edges)")
            self._rows = SortedDst(self.device_full()[0], jnp.asarray(
                np.searchsorted(sx, np.arange(self.num_vertices + 1))
                .astype(np.int32)))
        return self._rows

    @property
    def x(self) -> jnp.ndarray:
        return self.device_full()[0]

    @property
    def y(self) -> jnp.ndarray:
        return self.device_full()[1]

    @property
    def val(self) -> jnp.ndarray:
        return self.device_full()[2]

    def _quantize_host(self, fmt: QFormat) -> np.ndarray:
        """Raw uint32 values of the *unpadded* edge stream (host-side cache —
        the base incremental requantization patches on delta application)."""
        if fmt not in self._quantized_host:
            self._quantized_host[fmt] = self.source.quantized_val(fmt)
        return self._quantized_host[fmt]

    def quantized(self, fmt: QFormat) -> jnp.ndarray:
        """Padded raw uint32 device values for ``fmt`` (cached upload)."""
        if fmt not in self._quantized:
            raw = self._quantize_host(fmt)
            pad = self.graph.num_edges - raw.shape[0]
            if pad:
                raw = np.concatenate([raw, np.zeros(pad, np.uint32)])
            self._quantized[fmt] = jnp.asarray(raw)
        return self._quantized[fmt]

    # ---- delta ingestion --------------------------------------------------
    def apply_delta(self, delta: EdgeDelta) -> EdgeMergeInfo:
        """Merge an edge delta into the host state; bumps ``epoch``.

        Pre-registered Q formats are requantized incrementally: surviving
        edges keep their raw bits (copied through the merge's old→new index
        map), only ``changed_mask`` entries — edges of sources whose
        out-degree moved — go through the quantizer again.  The result is
        bit-identical to quantizing the merged graph from scratch.

        Device caches become stale here; the graph's armed engines refresh
        them through ``on_delta`` (the service drives that loop), so device
        costs are paid at delta time, not smeared over the next waves."""
        new_g, info = delta.apply(self.source, outdeg=self._outdeg)
        self._outdeg = info.new_outdeg
        self.source = new_g
        self.graph = new_g.pad_to_packets(self._pad_to)
        self.num_vertices = new_g.num_vertices
        self.dangling = jnp.asarray(self.graph.dangling)
        for fmt, old_raw in list(self._quantized_host.items()):
            new_raw = np.zeros(new_g.num_edges, np.uint32)
            new_raw[info.new_pos_of_kept] = old_raw[info.kept_old_idx]
            if info.changed_mask.any():
                new_raw[info.changed_mask] = quantize_values(
                    new_g.val[info.changed_mask], fmt)
            self._quantized_host[fmt] = new_raw
        self._stale_device_formats |= set(self._quantized)
        self._quantized.clear()
        self._full_was_materialized = self._full_device is not None
        self._full_device = None
        self._rows_were_materialized |= self._rows is not None
        self._rows = None
        self.epoch += 1
        return info

    def refresh_device_base(self) -> None:
        """Re-upload the base device caches a delta invalidated — previously
        uploaded quantized formats, and the full layout if it was materialized
        (or this graph uploads eagerly).  Idempotent across armed engines."""
        for fmt in tuple(self._stale_device_formats):
            self.quantized(fmt)
        self._stale_device_formats.clear()
        if self._full_was_materialized or not self._defer_full_upload:
            self.device_full()
        if self._rows_were_materialized:
            self.device_rows()
            self._rows_were_materialized = False


class ShardedRegisteredGraph(RegisteredGraph):
    """A registered graph whose edge stream is partitioned over a
    ``jax.sharding.Mesh`` axis (the paper's multi-channel partitioning, scaled
    to multi-device): waves on it run the sharded engines.

    Holds the bucketed host layout (``_host_x``/``_host_y``/``_host_val``,
    one row per shard) and per-format raw shard caches; the partitioning and
    per-bucket delta refresh that fill them live in
    ``repro.ppr_serving.engine.sharded``."""

    engine_family = "sharded"

    _defer_full_upload = True
    _edge_align = 1           # its engines read the shards, never row prefix

    def __init__(self, name: str, g: COOGraph, mesh, axis: Optional[str] = None,
                 packet: int = 256):
        super().__init__(name, g, packet=packet)
        self.mesh = mesh
        self.axis = axis if axis is not None else mesh.axis_names[0]
        if self.axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {self.axis!r} "
                             f"(axes: {mesh.axis_names})")
        self.n_shards = int(mesh.shape[self.axis])
        self.mesh_key = f"mesh:{self.axis}x{self.n_shards}"
        self._sharded_quantized: Dict[QFormat, jnp.ndarray] = {}
        self._sharded_quant_host: Dict[QFormat, np.ndarray] = {}  # [S, max_e]
        self._sharded_stale = False
        self._pre_delta_v_local = 0
        from repro.ppr_serving.engine.sharded import partition_topology
        partition_topology(self)

    def sharded_quantized(self, fmt: QFormat) -> jnp.ndarray:
        """Raw uint32 edge shard values in the partitioned layout (cached)."""
        from repro.ppr_serving.engine.sharded import partition_format
        return partition_format(self, fmt)

    def apply_delta(self, delta: EdgeDelta) -> EdgeMergeInfo:
        """Host merge plus the bookkeeping the sharded engines' per-bucket
        refresh needs: the pre-merge ceil-division layout (vertex growth may
        move it) and a staleness latch making the refresh idempotent across
        the family's two armed engines."""
        self._pre_delta_v_local, _ = sharded_vertex_layout(self.num_vertices,
                                                           self.n_shards)
        info = super().apply_delta(delta)
        self._sharded_stale = True
        return info
