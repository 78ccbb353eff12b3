"""Pallas-fused engines: one kernel launch per eq. (1) iteration.

The family (``register_graph(..., engine="pallas")``) serves the same waves
as the "single" family but through ``repro.kernels.fused_ppr``: SpMV, the
eq. (1) axpy, the dangling-mass fold and the (L1, ∞, Σd²) residual reduction
execute as a single ``pallas_call`` over the dst-major packetized edge
stream.  The fixed member is bit-identical (raw uint32) to ``FixedEngine``;
the float member matches ``FloatEngine`` to f32 accumulation-order noise.

State layout (on ``PallasRegisteredGraph``): the packetized ``FusedLayout``
plus device uploads of its schedule/topology, the float value rows, and one
raw uint32 value row-set per prepared Q format.  ``on_delta`` re-packetizes
only the dst blocks an edge delta touched (``changed_dst // v_tile``) —
per-block rebuilds are deterministic, so the incremental layout is
array-equal to a fresh registration of the merged graph — behind a staleness
latch (both family members are armed and each gets the callback).

The early-exit driver reuses the kernel's residual output instead of
``ConvergenceMonitor``'s separate device reductions, with identical exit
decisions: a zero ∞-residual *is* the monitor's exact integer equality (the
minimum nonzero raw diff, 1.0, is exactly representable in f32), period-2
cycles are still caught by comparing against S_{t-2}, and the parity of the
remaining budget picks the bit-identical return state.

On the CPU backend the kernels run under ``interpret=True`` (slow,
bit-exact), so the family stays testable in CI; on the chip they are always
compiled.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.autotune.convergence import ConvergencePolicy, states_equal
from repro.core.coo import COOGraph
from repro.core.fixed_point import QFormat
from repro.core.ppr import personalization_matrix, personalization_matrix_fixed
from repro.kernels.fused_ppr import (
    assemble_value_rows,
    build_fused_layout,
    default_interpret,
    fused_ppr_iteration,
    quantize_layout_rows,
)
from repro.ppr_serving.engine.base import WaveEngine, WavePlan, register_engine
from repro.ppr_serving.graphs import RegisteredGraph

__all__ = ["PallasRegisteredGraph", "PallasFloatEngine", "PallasFixedEngine",
           "fused_step_operands"]

DEFAULT_V_TILE = 512


class PallasRegisteredGraph(RegisteredGraph):
    """Registered graph carrying the fused dst-major packetized layout.

    Defers the full-layout upload (fused waves never read it; it is still
    materialized lazily for shadow scoring through the base class) and owns
    the fused caches: the host ``FusedLayout``, its device schedule/topology,
    the float value rows, and per-format raw uint32 value rows."""

    engine_family = "pallas"

    _defer_full_upload = True
    _edge_align = 1           # fused waves read their own layout

    def __init__(self, name: str, g: COOGraph, packet: int = 256,
                 v_tile: int = DEFAULT_V_TILE):
        self.v_tile = int(v_tile)
        self._fused_layout = None
        self._fused_dev = None                 # schedule + topology uploads
        self._fused_val_dev = {}               # None | QFormat → [rows, packet]
        self._fused_raw_rows = {}              # QFormat → per-dst-block rows
        self._fused_stale = False
        self._fused_full_rebuild = False
        self._fused_dirty: set = set()
        super().__init__(name, g, packet=packet)

    # ---- fused caches ------------------------------------------------------
    def fused_layout(self):
        if self._fused_layout is None:
            self._fused_layout = build_fused_layout(self.source, self.v_tile,
                                                    self.packet)
        return self._fused_layout

    def fused_topology(self):
        """Device uploads of the schedule + localized edge topology."""
        if self._fused_dev is None:
            lay = self.fused_layout()
            self._fused_dev = {
                "step_row": jnp.asarray(lay.step_row),
                "step_dst": jnp.asarray(lay.step_dst),
                "step_src": jnp.asarray(lay.step_src),
                "step_first": jnp.asarray(lay.step_first),
                "step_last": jnp.asarray(lay.step_last),
                "x2": jnp.asarray(lay.x2),
                "y2": jnp.asarray(lay.y2),
            }
        return self._fused_dev

    def fused_values(self, fmt: Optional[QFormat] = None):
        """[num_rows, packet] value operand — f32 (fmt=None) or raw uint32."""
        if fmt not in self._fused_val_dev:
            lay = self.fused_layout()
            if fmt is None:
                self._fused_val_dev[fmt] = jnp.asarray(lay.val2)
            else:
                rows = quantize_layout_rows(lay, fmt)
                self._fused_raw_rows[fmt] = rows
                self._fused_val_dev[fmt] = jnp.asarray(
                    assemble_value_rows(rows, lay.packet))
        return self._fused_val_dev[fmt]

    # ---- delta ingestion ---------------------------------------------------
    def apply_delta(self, delta):
        """Host merge plus dirty-dst-block tracking for the fused layout.

        ``changed_dst`` covers every destination whose incident edge set or
        edge values moved (including removed edges' old rows); vertex growth
        that changes the block count forces a full re-packetization."""
        info = super().apply_delta(delta)
        if self._fused_layout is not None:
            n_blk = max(1, -(-self.num_vertices // self.v_tile))
            if n_blk != self._fused_layout.n_blk:
                self._fused_full_rebuild = True
            else:
                self._fused_dirty.update(
                    int(b) for b in np.unique(info.changed_dst // self.v_tile))
            self._fused_stale = True
        return info

    def refresh_fused(self) -> None:
        """Re-packetize dirty dst blocks and re-upload the fused caches.
        Idempotent across the family's two armed engines (staleness latch)."""
        if not self._fused_stale:
            return
        self._fused_stale = False
        old, dirty = self._fused_layout, self._fused_dirty
        self._fused_dirty = set()
        full = self._fused_full_rebuild or old is None
        self._fused_full_rebuild = False
        lay = build_fused_layout(self.source, self.v_tile, self.packet,
                                 reuse=None if full else old,
                                 dirty=None if full else dirty)
        self._fused_layout = lay
        self._fused_dev = None
        new_vals, new_rows = {}, {}
        for fmt, rows_old in self._fused_raw_rows.items():
            rows = quantize_layout_rows(lay, fmt,
                                        reuse_rows=None if full else rows_old,
                                        dirty=None if full else dirty)
            new_rows[fmt] = rows
            new_vals[fmt] = jnp.asarray(assemble_value_rows(rows, lay.packet))
        if None in self._fused_val_dev:
            new_vals[None] = jnp.asarray(lay.val2)
        self._fused_raw_rows = new_rows
        self._fused_val_dev = new_vals
        self.fused_topology()


# ---------------------------------------------------------------------------
# wave plumbing
# ---------------------------------------------------------------------------
def fused_step_operands(rg: PallasRegisteredGraph, fmt: Optional[QFormat],
                        alpha: float):
    """``(graph operands, statics)`` of ``fused_ppr_iteration`` on ``rg``'s
    current fused device state; a call appends ``(Vmat, P)``."""
    lay = rg.fused_layout()
    dev = rg.fused_topology()
    operands = (dev["step_row"], dev["step_dst"], dev["step_src"],
                dev["step_first"], dev["step_last"], dev["x2"], dev["y2"],
                rg.fused_values(fmt), rg.dangling)
    statics = dict(v_tile=lay.v_tile, packet=lay.packet, n_blk=lay.n_blk,
                   chunks=lay.chunks, num_vertices=lay.num_vertices,
                   alpha=alpha, fmt=fmt, interpret=default_interpret())
    return operands, statics


def _bind_fused_step(rg: PallasRegisteredGraph, fmt: Optional[QFormat],
                     alpha: float, cell: dict):
    """Step closure over the graph's current fused device state.  Each call
    parks the iteration's [3, K] residual in ``cell`` for the iterate driver."""
    operands, statics = fused_step_operands(rg, fmt, alpha)

    def step(Vmat, P):
        P_next, res = fused_ppr_iteration(*operands, Vmat, P, **statics)
        cell["res"] = res
        return P_next

    return step


def _residual_delta(res, scale: Optional[int]) -> float:
    """max-over-columns L2 state change in value units (``wave_delta`` on the
    kernel's Σd² row — max ∘ sqrt = sqrt ∘ max)."""
    d = float(jnp.sqrt(res[2].max()))
    return d / scale if scale else d


def _make_fused_iterate(engine: WaveEngine, iterations: int,
                        convergence: Optional[ConvergencePolicy],
                        fixed: bool, scale: Optional[int], cell: dict,
                        trace_hook=None):
    """The ``run_until_converged`` contract driven off the kernel's fused
    residual: same check cadence, same exit conditions, same parity-correct
    return states as ``ConvergenceMonitor`` — without its per-check
    full-array device comparisons (the ∞-residual is already on device)."""
    if convergence is None:
        return engine._make_iterate(iterations, None, fixed, scale,
                                    trace_hook=trace_hook)
    pol = convergence
    track = trace_hook is not None

    def finish(P, t, deltas):
        if track:
            trace_hook({
                "iterations_run": t, "budget": iterations,
                "early_exit": t < iterations,
                "residual": float(deltas[-1]) if deltas else None,
            })
        return P, t

    def iterate(step, P0):
        deltas = []
        P, prev2 = P0, None
        for t in range(1, iterations + 1):
            P_next = step(P)
            res = cell["res"]
            checking = t % pol.check_every == 0
            prev2, prev2_at_check = (P, prev2) if fixed else (None, None)
            if checking:
                if fixed:
                    # zero ∞-residual ⇔ exact integer state equality: raw
                    # diffs are whole numbers, the smallest nonzero one (1.0)
                    # is exactly representable in f32 and a max never rounds
                    # a nonzero operand to zero.
                    strict = bool(res[1].max() == 0.0)
                    if track:
                        deltas.append(0.0 if strict else
                                      _residual_delta(res, scale))
                    if t >= pol.min_iterations:
                        if strict:
                            return finish(P_next, t, deltas)
                        if prev2_at_check is not None and states_equal(
                                P_next, prev2_at_check):
                            # period-2 absorbing cycle: parity of the
                            # remaining budget picks the bit-identical state
                            if (iterations - t) % 2 != 0:
                                return finish(P, t, deltas)
                            return finish(P_next, t, deltas)
                else:
                    delta = _residual_delta(res, scale)
                    deltas.append(delta)
                    if t >= pol.min_iterations and delta < pol.epsilon:
                        return finish(P_next, t, deltas)
            P = P_next
        return finish(P, iterations, deltas)

    return iterate


# ---------------------------------------------------------------------------
# the engines
# ---------------------------------------------------------------------------
@register_engine
class PallasFloatEngine(WaveEngine):
    """float32 fused-launch iterations over the packetized edge stream."""

    key = "pallas_float"
    family = "pallas"
    fixed = False

    def make_graph(self, name: str, g, packet: int = 256,
                   mesh=None, mesh_axis=None):
        return PallasRegisteredGraph(name, g, packet=packet)

    def prepare(self, rg, fmt: Optional[QFormat] = None) -> None:
        rg.fused_topology()
        rg.fused_values(None)

    def plan(self, rg, fmt: Optional[QFormat] = None, *, alpha: float,
             iterations: int, convergence=None,
             topk_tile: Optional[int] = None, trace_hook=None) -> WavePlan:
        self.prepare(rg)
        num_vertices = rg.num_vertices
        cell = {"res": None}
        return WavePlan(
            engine=self.key, fixed=False, scale=None,
            initial=lambda pers: personalization_matrix(num_vertices, pers),
            step=_bind_fused_step(rg, None, alpha, cell),
            iterate=_make_fused_iterate(self, iterations, convergence, False,
                                        None, cell, trace_hook=trace_hook),
            topk=self._make_topk(topk_tile), reduce="fused")

    def on_delta(self, rg, info) -> None:
        rg.refresh_device_base()
        rg.refresh_fused()


@register_engine
class PallasFixedEngine(WaveEngine):
    """Bit-exact reduced-precision fused-launch iterations (raw uint32)."""

    key = "pallas_fixed"
    family = "pallas"
    fixed = True

    def make_graph(self, name: str, g, packet: int = 256,
                   mesh=None, mesh_axis=None):
        return PallasRegisteredGraph(name, g, packet=packet)

    def prepare(self, rg, fmt: Optional[QFormat] = None) -> None:
        if fmt is None:
            raise ValueError(f"{self.key!r} engine needs a concrete Q format")
        rg.fused_topology()
        rg.fused_values(fmt)

    def plan(self, rg, fmt: Optional[QFormat] = None, *, alpha: float,
             iterations: int, convergence=None,
             topk_tile: Optional[int] = None, trace_hook=None) -> WavePlan:
        if fmt is None:
            raise ValueError(f"{self.key!r} engine needs a concrete Q format")
        self.prepare(rg, fmt)
        num_vertices = rg.num_vertices
        cell = {"res": None}
        return WavePlan(
            engine=self.key, fixed=True, scale=fmt.scale,
            initial=lambda pers: personalization_matrix_fixed(
                num_vertices, pers, fmt),
            step=_bind_fused_step(rg, fmt, alpha, cell),
            iterate=_make_fused_iterate(self, iterations, convergence, True,
                                        fmt.scale, cell,
                                        trace_hook=trace_hook),
            topk=self._make_topk(topk_tile), reduce="fused")

    def on_delta(self, rg, info) -> None:
        rg.refresh_device_base()
        rg.refresh_fused()
