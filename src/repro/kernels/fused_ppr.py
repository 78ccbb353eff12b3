"""Fused Pallas PPR iteration: SpMV + eq. (1) axpy + residual in one pass.

The paper's core claim is a *streaming fused* pipeline (§4.1): SpMV, the
eq. (1) axpy and the dangling-mass fold execute as one pass over the edge
stream.  ``coo_spmv.py`` maps the §4.1.1 SpMV stage alone; this module fuses
the iteration

    P_{t+1} = α·X·P_t + α/|V|·(d̄ᵀP_t)·1 + (1−α)·V̄        (eq. 1)

into one pass over the dst-major packetized edge stream.  The dangling mass
d̄ᵀP_t is reduced first, with the same op as the composed step (for fixed
point an int32 sum, exact mod 2^32), and fed to every launch as a [1, K]
operand.  Then one grid step per edge packet runs the one-hot-MXU SpMV
accumulation of ``coo_spmv.py``.  On the *last* packet of each dst block the
kernel applies the eq. (1) combine in place — for fixed point, the exact
``_fixed_combine`` nesting of truncating limb multiplies and saturating adds,
so results are bit-identical (raw uint32) to the composed
``make_ppr_fixed_step`` datapath — and folds |ΔP| into a [3, K] residual
output (L1 / ∞ / Σd² per column) for the early-exit driver, replacing the
separate host-synced reductions of ``ConvergenceMonitor``.

The schedule of one iteration is cut on whole dst blocks into launches whose
scalar-prefetch operands fit SMEM (``coo_spmv.dst_chunks``).  Each launch
writes its own dst blocks; the outputs concatenate and the per-launch
residuals combine exactly (L1 and Σd² add, ∞ takes the max).

Empty dst blocks get a sentinel step over a shared all-zero edge row so every
output block is still zeroed + combined (a vertex with no in-edges keeps its
(1−α)·V̄ + dangling terms).  Pad rows of the trailing ragged block are masked
to zero after the combine, so the next iteration's pads stay zero.

``interpret=True`` runs the same kernel through the Pallas interpreter —
slow, but bit-exact, which keeps CPU-only CI meaningful.
``default_interpret()`` picks it on the CPU backend only; on the chip the
kernel is always compiled.

Layout construction/incremental re-packetization lives in ``FusedLayout`` /
``build_fused_layout`` below; the serving integration is
``repro.ppr_serving.engine.pallas``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coo import COOGraph, quantize_values
from repro.core.fixed_point import QFormat
from repro.core.ppr import _fixed_consts, _fixed_dangling_mass
from repro.kernels.coo_spmv import (
    ROW_BLOCK,
    _fixed_mul_u32,
    default_interpret,
    dst_chunks,
    pad_rows,
    spmv_accumulate,
)

__all__ = [
    "FusedLayout", "build_fused_layout", "quantize_layout_rows",
    "assemble_value_rows", "fused_ppr_iteration", "default_interpret",
]


# ---------------------------------------------------------------------------
# host-side layout: dst-major packetized edge stream + per-step schedule
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FusedLayout:
    """Packetized dst-major edge layout + the kernel's per-step schedule.

    Per dst block ``d`` the edges are grouped by source block and padded to
    whole packets (``row_*[d]``: [p_d, packet] with local indices; pad entries
    are zero-valued self-edges to local vertex 0 — they contribute nothing).
    The assembled arrays end in all-zero rows up to a whole ``ROW_BLOCK``
    tile; the first of them is the sentinel row that the step of every empty
    dst block addresses.

    The rebuild is per-dst-block and deterministic, so an incremental rebuild
    of only the dirty blocks is array-equal to a fresh build of the merged
    graph (tested) — the ``on_delta`` contract of the pallas engine family.
    """
    num_vertices: int
    num_edges: int
    v_tile: int
    packet: int
    n_blk: int
    row_x: List[np.ndarray]      # per dst block: [p_d, packet] int32 local dst
    row_y: List[np.ndarray]      # per dst block: [p_d, packet] int32 local src
    row_val: List[np.ndarray]    # per dst block: [p_d, packet] f64 edge values
    x2: np.ndarray               # [num_rows, packet] int32 (+ zero rows)
    y2: np.ndarray               # [num_rows, packet] int32
    val2: np.ndarray             # [num_rows, packet] f32
    step_row: np.ndarray         # [num_steps] int32  step → edge row
    step_dst: np.ndarray         # [num_steps] int32  step → dst block
    step_src: np.ndarray         # [num_steps] int32  step → src block
    step_first: np.ndarray       # [num_steps] int32  1 = zero the dst block
    step_last: np.ndarray        # [num_steps] int32  1 = combine + residual

    @functools.cached_property
    def chunks(self):
        """The launches of one iteration (``coo_spmv.dst_chunks``)."""
        return dst_chunks(self.step_dst, self.n_blk)

    @property
    def num_steps(self) -> int:
        return int(self.step_row.shape[0])

    @property
    def num_rows(self) -> int:
        return int(self.x2.shape[0])


def _build_dst_row(x, y, val, v_tile: int, packet: int, n_blk: int):
    """One dst block's edges, grouped by src block, packet-padded, localized."""
    src_blk = (np.asarray(y, np.int64) // v_tile)
    order = np.argsort(src_blk, kind="stable")   # keep (dst, src) order inside
    xs = np.asarray(x, np.int64)[order]
    ys = np.asarray(y, np.int64)[order]
    vs = np.asarray(val)[order]
    sbs = src_blk[order]
    counts = np.bincount(sbs, minlength=n_blk).astype(np.int64)
    pad_counts = (counts + packet - 1) // packet * packet
    total = int(pad_counts.sum())
    row_x = np.zeros(total, np.int32)
    row_y = np.zeros(total, np.int32)
    row_val = np.zeros(total, np.float64)
    src_off = np.zeros(n_blk + 1, np.int64)
    np.cumsum(counts, out=src_off[1:])
    dst_off = np.zeros(n_blk + 1, np.int64)
    np.cumsum(pad_counts, out=dst_off[1:])
    for b in np.nonzero(counts)[0]:
        s0, s1 = src_off[b], src_off[b + 1]
        d0 = dst_off[b]
        n = s1 - s0
        row_x[d0:d0 + n] = xs[s0:s1] % v_tile
        row_y[d0:d0 + n] = ys[s0:s1] % v_tile
        row_val[d0:d0 + n] = vs[s0:s1]
    p_d = total // packet
    row_src = np.repeat(np.arange(n_blk, dtype=np.int32),
                        (pad_counts // packet))
    return (row_x.reshape(p_d, packet), row_y.reshape(p_d, packet),
            row_val.reshape(p_d, packet), row_src)


def _assemble_rows(rows: Sequence[np.ndarray], packet: int, dtype) -> np.ndarray:
    """Stack per-block rows, append the all-zero sentinel row and pad to
    whole ``ROW_BLOCK`` tiles."""
    parts = [np.asarray(r, dtype) for r in rows if r.shape[0]]
    parts.append(np.zeros((1, packet), dtype))
    return pad_rows(np.concatenate(parts, axis=0))


def assemble_value_rows(rows: Sequence[np.ndarray], packet: int,
                        dtype=np.uint32) -> np.ndarray:
    """Assemble per-block *value* rows (e.g. per-format raw uint32) into the
    kernel's [num_rows, packet] operand, sentinel row included."""
    return _assemble_rows(rows, packet, dtype)


def build_fused_layout(g: COOGraph, v_tile: int, packet: int,
                       reuse: Optional[FusedLayout] = None,
                       dirty=None) -> FusedLayout:
    """Packetize ``g``'s (unpadded, (dst, src)-lexsorted) edge stream.

    ``reuse``/``dirty``: incremental re-packetization — per-block rows of
    clean dst blocks are taken from ``reuse`` (same arrays, not copies), only
    blocks in ``dirty`` are rebuilt.  Requires an unchanged block count;
    callers fall back to a full rebuild when ``n_blk`` moves.
    """
    v = g.num_vertices
    n_blk = max(1, -(-v // v_tile))
    if reuse is not None and (reuse.n_blk != n_blk or reuse.v_tile != v_tile
                              or reuse.packet != packet):
        raise ValueError("fused layout reuse requires identical block geometry")
    dirty_set = (set(range(n_blk)) if reuse is None or dirty is None
                 else {int(d) for d in dirty})
    # dst-major lexsorted stream ⇒ each dst block is one contiguous slice
    bounds = np.searchsorted(np.asarray(g.x), np.arange(n_blk + 1) * v_tile)
    rows_x, rows_y, rows_v, rows_s = [], [], [], []
    for d in range(n_blk):
        if reuse is not None and d not in dirty_set:
            rx, ry, rv = reuse.row_x[d], reuse.row_y[d], reuse.row_val[d]
            rs = np.full(rx.shape[0], d, np.int32)
        else:
            a, b = int(bounds[d]), int(bounds[d + 1])
            rx, ry, rv, rsrc = _build_dst_row(
                g.x[a:b], g.y[a:b], g.val[a:b], v_tile, packet, n_blk)
            rs = rsrc
        rows_x.append(rx)
        rows_y.append(ry)
        rows_v.append(rv)
        rows_s.append(rs)
    x2 = _assemble_rows(rows_x, packet, np.int32)
    y2 = _assemble_rows(rows_y, packet, np.int32)
    val2 = _assemble_rows(rows_v, packet, np.float32)
    sentinel = sum(r.shape[0] for r in rows_x)
    # schedule: the dst-major stream, with one sentinel step per empty dst block
    srow, sdst, ssrc, sfirst, slast = [], [], [], [], []
    base = 0
    for d in range(n_blk):
        p_d = rows_x[d].shape[0]
        if p_d == 0:
            srow.append(sentinel)
            sdst.append(d)
            ssrc.append(0)
            sfirst.append(1)
            slast.append(1)
            continue
        for j in range(p_d):
            srow.append(base + j)
            sdst.append(d)
            ssrc.append(int(rows_s[d][j]))
            sfirst.append(1 if j == 0 else 0)
            slast.append(1 if j == p_d - 1 else 0)
        base += p_d
    return FusedLayout(
        num_vertices=v, num_edges=int(g.num_edges), v_tile=v_tile,
        packet=packet, n_blk=n_blk,
        row_x=rows_x, row_y=rows_y, row_val=rows_v,
        x2=x2, y2=y2, val2=val2,
        step_row=np.asarray(srow, np.int32),
        step_dst=np.asarray(sdst, np.int32),
        step_src=np.asarray(ssrc, np.int32),
        step_first=np.asarray(sfirst, np.int32),
        step_last=np.asarray(slast, np.int32))


def quantize_layout_rows(layout: FusedLayout, fmt: QFormat,
                         reuse_rows: Optional[List[np.ndarray]] = None,
                         dirty=None) -> List[np.ndarray]:
    """Per-dst-block raw uint32 value rows for ``fmt``.

    The quantizer is per-edge and order-independent, so requantizing only the
    dirty blocks (reusing the rest) equals a from-scratch quantization of the
    merged stream bit-for-bit.  Pad entries quantize 0.0 → raw 0.
    """
    dirty_set = (set(range(layout.n_blk)) if reuse_rows is None or dirty is None
                 else {int(d) for d in dirty})
    rows = []
    for d in range(layout.n_blk):
        if reuse_rows is not None and d not in dirty_set:
            rows.append(reuse_rows[d])
        else:
            rv = layout.row_val[d]
            rows.append(quantize_values(rv.ravel(), fmt).reshape(rv.shape))
    return rows


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------
def _valid_rows(dst_blk, v_tile: int, num_vertices: int):
    """[v_tile, 1] mask of real (non-pad) rows of this dst block."""
    rows = dst_blk * v_tile + jax.lax.broadcasted_iota(
        jnp.int32, (v_tile, 1), 0)
    return rows < num_vertices


def _sat_add_u32(a, b, max_raw):
    """In-kernel replica of ``QFormat.add``: saturating uint32 add."""
    s = a + b
    over = (s < a) | (s > max_raw)
    return jnp.where(over, max_raw, s)


def _combine_float(alpha, num_vertices, xp, dm, vmat):
    """eq. (1) in f32."""
    return alpha * xp + (alpha / num_vertices) * dm + (1.0 - alpha) * vmat


def _absdiff_float(pn, prev):
    return jnp.abs(pn - prev)


def _combine_fixed(frac_bits, alpha_raw, one_minus_alpha_raw, alpha_over_v_raw,
                   max_raw, xp, dm, vmat):
    """The exact ``_fixed_combine`` nesting (truncating limb multiplies,
    saturating adds) — bit-identical to the composed ``make_ppr_fixed_step``."""
    return _sat_add_u32(
        _sat_add_u32(_fixed_mul_u32(alpha_raw, xp, frac_bits),
                     _fixed_mul_u32(alpha_over_v_raw, dm, frac_bits),
                     max_raw),
        _fixed_mul_u32(one_minus_alpha_raw, vmat, frac_bits),
        max_raw)


def _absdiff_fixed(pn, prev):
    """|pn − prev| of raw uint32 values as f32 (in-format values are < 2^31,
    so the int32 difference cannot overflow)."""
    i32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    return jnp.abs(i32(pn) - i32(prev)).astype(jnp.float32)


def _fused_kernel(combine, absdiff, frac_bits, num_vertices,
                  sr, sd, ss, sf, sl,
                  x_ref, y_ref, val_ref, ps_ref, pd_ref, vmat_ref, dm_ref,
                  out_ref, res_ref):
    """One grid step = one edge packet; the last packet of a dst block applies
    the eq. (1) combine in place and folds |ΔP| into the residual."""
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        res_ref[...] = jnp.zeros_like(res_ref)

    @pl.when(sf[s] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    spmv_accumulate(sr[s] % ROW_BLOCK, x_ref, y_ref, val_ref, ps_ref, out_ref,
                    frac_bits)

    @pl.when(sl[s] == 1)
    def _combine():
        v_tile = out_ref.shape[0]
        pn = combine(out_ref[...], dm_ref[...], vmat_ref[...])
        pn = jnp.where(_valid_rows(sd[s], v_tile, num_vertices),
                       pn, jnp.zeros_like(pn))
        out_ref[...] = pn
        d = absdiff(pn, pd_ref[...])          # pad rows: 0 - 0 = 0
        res_ref[0:1, :] += d.sum(0, keepdims=True)
        res_ref[1:2, :] = jnp.maximum(res_ref[1:2, :], d.max(0, keepdims=True))
        res_ref[2:3, :] += (d * d).sum(0, keepdims=True)


# ---------------------------------------------------------------------------
# the launches
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("v_tile", "packet", "n_blk", "chunks", "num_vertices",
                     "alpha", "fmt", "interpret"),
)
def fused_ppr_iteration(
    step_row: jax.Array,     # [num_steps] int32  step → edge row
    step_dst: jax.Array,     # [num_steps] int32  step → dst block
    step_src: jax.Array,     # [num_steps] int32  step → src block
    step_first: jax.Array,   # [num_steps] int32
    step_last: jax.Array,    # [num_steps] int32
    x2: jax.Array,           # [num_rows, packet] int32 local dst
    y2: jax.Array,           # [num_rows, packet] int32 local src
    val2: jax.Array,         # [num_rows, packet] f32 (or uint32 raw if fixed)
    dangling: jax.Array,     # [V] dangling indicator
    vmat: jax.Array,         # [V, K] personalization matrix
    p: jax.Array,            # [V, K] current state
    *,
    v_tile: int,
    packet: int,
    n_blk: int,
    chunks: tuple,
    num_vertices: int,
    alpha: float,
    fmt: Optional[QFormat] = None,
    interpret: bool = False,
):
    """One full eq. (1) iteration: a dangling-mass reduction, then one Pallas
    launch per schedule chunk (``FusedLayout.chunks``).

    Returns ``(P_next [V, K], res [3, K] f32)`` where ``res`` carries the
    per-column (L1, ∞, Σd²) of |P_next − P| — raw units for fixed point.  A
    zero ∞-residual is an exact bit-equality certificate (the minimum nonzero
    raw diff is 1.0, exactly representable in f32), which is what the early
    exit driver keys on.
    """
    k = p.shape[-1]
    grow = n_blk * v_tile - num_vertices
    p_pad = jnp.pad(p, ((0, grow), (0, 0)))
    vmat_pad = jnp.pad(vmat, ((0, grow), (0, 0)))
    if fmt is None:
        dm = (dangling.astype(jnp.float32) @ p)[None, :]
        combine = functools.partial(_combine_float, alpha, num_vertices)
        absdiff, frac_bits = _absdiff_float, None
    else:
        dm = _fixed_dangling_mass(dangling.astype(jnp.uint32), p)[None, :]
        a_raw, oma_raw, aov_raw = _fixed_consts(fmt, num_vertices, alpha)
        combine = functools.partial(_combine_fixed, fmt.frac_bits, a_raw,
                                    oma_raw, aov_raw, np.uint32(fmt.max_raw))
        absdiff, frac_bits = _absdiff_fixed, fmt.frac_bits
    kernel = functools.partial(_fused_kernel, combine, absdiff, frac_bits,
                               num_vertices)

    def tile(i, sr, sd, ss, sf, sl):
        return sr[i] // ROW_BLOCK, 0

    def src(i, sr, sd, ss, sf, sl):
        return ss[i], 0

    def dst(i, sr, sd, ss, sf, sl):
        return sd[i], 0

    def whole(i, sr, sd, ss, sf, sl):
        return 0, 0

    outs, resids = [], []
    for s0, s1, d0, d1 in chunks:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s1 - s0,),
            in_specs=[
                pl.BlockSpec((ROW_BLOCK, packet), tile),     # x
                pl.BlockSpec((ROW_BLOCK, packet), tile),     # y
                pl.BlockSpec((ROW_BLOCK, packet), tile),     # val
                pl.BlockSpec((v_tile, k), src),              # P src
                pl.BlockSpec((v_tile, k), dst),              # P dst
                pl.BlockSpec((v_tile, k), dst),              # V̄ dst
                pl.BlockSpec((1, k), whole),                 # dangling mass
            ],
            out_specs=[
                pl.BlockSpec((v_tile, k),
                             lambda i, sr, sd, ss, sf, sl, d0=d0:
                             (sd[i] - d0, 0)),               # P_next
                pl.BlockSpec((3, k), whole),                 # residual
            ],
        )
        out, res = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(((d1 - d0) * v_tile, k), p.dtype),
                jax.ShapeDtypeStruct((3, k), jnp.float32),
            ],
            interpret=interpret,
        )(step_row[s0:s1], step_dst[s0:s1], step_src[s0:s1],
          step_first[s0:s1], step_last[s0:s1],
          x2, y2, val2, p_pad, p_pad, vmat_pad, dm)
        outs.append(out)
        resids.append(res)
    res = jnp.stack(resids)
    res = jnp.stack([res[:, 0].sum(0), res[:, 1].max(0), res[:, 2].sum(0)])
    return jnp.concatenate(outs, axis=0)[:num_vertices], res
