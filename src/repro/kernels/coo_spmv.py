"""Pallas TPU kernel: streaming COO SpMM (the paper's §4.1.1 pipeline).

TPU mapping of the FPGA architecture (paper §4.1.1; see PAPER.md for the
abstract and README.md "Architecture map" for where this sits in the repo):

  FPGA                                  TPU (this kernel)
  ----------------------------------    ----------------------------------------
  DRAM burst read, 256-bit packets      HBM→VMEM streaming: 1-D grid over edge
                                        packets; BlockSpec auto double-buffers
                                        (ROW_BLOCK, packet) tiles of packets
  URAM-resident P_t                     VMEM-resident (v_tile × K) src slice of P,
                                        selected per packet via scalar-prefetched
                                        packet→src-block map
  P_t[y] read port                      one-hot MXU gather: onehot(y_local) @ P_src
  B×B comparator crossbar aggregator    one-hot MXU matmul:
                                        acc += onehot(x_local)ᵀ @ (val·P[y_local])
  FSM, 2 buffers, 1 write per block     Pallas output revisiting: consecutive
                                        packets of one dst block accumulate in
                                        VMEM; the block is written to HBM once,
                                        when the dst index advances
  fixed-point DSP multiply              uint32 16-bit-limb multiply (bit-exact)

Grid: one step per packet (PACKET edges).  Scalar-prefetch arrays give each
packet its (dst_block, src_block) and a first-packet-of-dst-block flag.
Packets are dst-major sorted, so each output block is revisited consecutively
— the same "write each block exactly once" discipline as the paper's FSM.

TPU constraints the kernel is shaped by (all enforced by the chip's compiler,
none by interpret mode):

- Edge arrays are DMA'd as ``(ROW_BLOCK, packet)`` tiles — the (8, 128) tile
  rule — and a step selects its packet row inside the tile.  Consecutive
  packets share a tile, so the pipeline fetches each tile once.
- There is no in-VMEM vector gather; ``P[y]`` is a one-hot matmul.
- The MXU has no int32 × int32 matmul.  Fixed-point gathers and aggregations
  run as bf16 dots over 8-bit limbs with f32 accumulation: every partial sum
  is an integer below 2^24, so the result is exact mod 2^32 — bit-identical
  to the composed path's int32 ``segment_sum``.
- Scalar-prefetch operands live in SMEM (1 MiB).  One launch takes at most
  ``MAX_LAUNCH_STEPS`` steps; longer schedules are cut on whole dst blocks
  (``dst_chunks``) into several launches.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 8                  # packet rows per DMA tile (sublanes of a tile)
MAX_LAUNCH_STEPS = 32768       # 5 int32 prefetch arrays × 32768 = 640 KiB SMEM
_MASK16 = np.uint32(0xFFFF)
_LIMB_BITS = 8                 # bf16 holds 8 significant bits exactly
_N_LIMBS = 32 // _LIMB_BITS


def _fixed_mul_u32(a, b, frac_bits: int):
    """Bit-exact (a*b) >> f on uint32 via 16-bit limbs (no 64-bit ops) — the
    in-kernel replica of QFormat.mul, kept local so the kernel body has no
    host-side dependencies."""
    a0 = a & _MASK16
    a1 = a >> 16
    b0 = b & _MASK16
    b1 = b >> 16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = lh + hl
    mid_carry = (mid < lh).astype(jnp.uint32)
    # repro: allow[FXP002] carry-tracked — bits >=32 of mid<<16 re-enter via mid>>16 (+ mid_carry) in hi
    lo = ll + (mid << 16)
    carry_lo = (lo < ll).astype(jnp.uint32)
    hi = hh + (mid >> 16) + (mid_carry << 16) + carry_lo
    f = frac_bits
    return (lo >> f) | (hi << (32 - f))


def default_interpret() -> bool:
    """Interpret mode on the CPU backend (tests); compiled everywhere else."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# schedule cutting (host side)
# ---------------------------------------------------------------------------
def pad_rows(a: np.ndarray) -> np.ndarray:
    """Zero-pad a [rows, packet] edge array to whole ``ROW_BLOCK`` tiles."""
    pad = -a.shape[0] % ROW_BLOCK
    return np.pad(a, ((0, pad), (0, 0))) if pad else a


def dst_chunks(step_dst: np.ndarray, n_blk: int,
               max_steps: Optional[int] = None) -> Tuple[Tuple[int, ...], ...]:
    """Cut a dst-major schedule into launches of at most ``max_steps`` steps.

    Cuts fall on dst-block boundaries only, so every output block belongs to
    exactly one launch.  Returns ``((s0, s1, d0, d1), ...)``: steps
    ``[s0, s1)`` write into dst blocks ``[d0, d1)``; the ranges tile
    ``[0, n_blk)`` in order, so the launches' outputs concatenate."""
    max_steps = MAX_LAUNCH_STEPS if max_steps is None else max_steps
    sd = np.asarray(step_dst, np.int64)
    n = sd.shape[0]
    if n == 0:
        return ((0, 0, 0, n_blk),)
    starts = np.flatnonzero(np.r_[True, sd[1:] != sd[:-1]])   # dst-run starts
    ends = np.r_[starts[1:], n]
    longest = int((ends - starts).max())
    if longest > max_steps:
        raise ValueError(f"one dst block needs {longest} steps; "
                         f"a launch holds at most {max_steps}")
    chunks, s0, d0 = [], 0, 0
    for a, b in zip(starts, ends):
        if b - s0 > max_steps:
            d1 = int(sd[a])
            chunks.append((s0, int(a), d0, d1))
            s0, d0 = int(a), d1
    chunks.append((s0, n, d0, n_blk))
    return tuple(chunks)


# ---------------------------------------------------------------------------
# in-kernel building blocks (shared with kernels/fused_ppr.py)
# ---------------------------------------------------------------------------
def _as_i32(v):
    """int32 view of uint32 data; widening of packed uint16 indices."""
    if v.dtype == jnp.int32:
        return v
    if v.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(v, jnp.int32)
    return v.astype(jnp.int32)


def _edge_tile(ref):
    """The edge tile in ``ref`` as f32, or as int32 (uint32 viewed, uint16
    widened)."""
    blk = ref[...]
    return blk if blk.dtype == jnp.float32 else _as_i32(blk)


def _edge_row(ref, r):
    """Packet row ``r`` of a ``(ROW_BLOCK, packet)`` edge tile as [1, packet]."""
    blk = _edge_tile(ref)
    sub = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    return jnp.sum(jnp.where(sub == r, blk, jnp.zeros_like(blk)),
                   axis=0, keepdims=True)


def _edge_col(ref, r):
    """The same packet row as a [packet, 1] column (edges along sublanes)."""
    t = _edge_tile(ref).T
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.sum(jnp.where(lane == r, t, jnp.zeros_like(t)),
                   axis=1, keepdims=True)


def _onehot(mask, dtype):
    return jnp.where(mask, 1.0, 0.0).astype(dtype)


def _limbs(v):
    """uint32 [m, n] → 8-bit limbs as bf16 (exact: each limb < 256)."""
    vi = _as_i32(v)
    return [(jax.lax.shift_right_logical(vi, jnp.int32(_LIMB_BITS * j))
             & jnp.int32(0xFF)).astype(jnp.float32).astype(jnp.bfloat16)
            for j in range(_N_LIMBS)]


def _exact_onehot_dot(onehot_bf16, v_u32):
    """``onehot @ v`` mod 2^32 for a 0/1 matrix and uint32 ``v``, exact while
    a row of ``onehot`` has at most 2^16 ones (limb sums stay < 2^24)."""
    acc = None
    for j, limb in enumerate(_limbs(v_u32)):
        part = jnp.dot(onehot_bf16, limb, preferred_element_type=jnp.float32)
        part = jax.lax.bitcast_convert_type(part.astype(jnp.int32), jnp.uint32)
        part = part << jnp.uint32(_LIMB_BITS * j)
        acc = part if acc is None else acc + part
    return acc


def spmv_accumulate(r, x_ref, y_ref, val_ref, ps_ref, out_ref,
                    frac_bits: Optional[int]):
    """out += X_packet @ P_src for packet row ``r`` of the current edge tiles.

    Float: f32 one-hot dots at HIGHEST precision.  Fixed (``frac_bits``
    set): raw uint32 values, truncating limb multiply, exact limb-dot
    aggregation."""
    v_tile = out_ref.shape[0]
    y = _edge_col(y_ref, r)                                        # [P, 1]
    x = _edge_row(x_ref, r)                                        # [1, P]
    packet = x.shape[1]
    oh_y = y == jax.lax.broadcasted_iota(jnp.int32, (packet, v_tile), 1)
    oh_xt = x == jax.lax.broadcasted_iota(jnp.int32, (v_tile, packet), 0)
    if frac_bits is None:
        dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        contrib = (_edge_col(val_ref, r)
                   * dot(_onehot(oh_y, jnp.float32), ps_ref[...]))
        out_ref[...] += dot(_onehot(oh_xt, jnp.float32), contrib)
    else:
        val = jax.lax.bitcast_convert_type(_edge_col(val_ref, r), jnp.uint32)
        gathered = _exact_onehot_dot(_onehot(oh_y, jnp.bfloat16), ps_ref[...])
        contrib = _fixed_mul_u32(val, gathered, frac_bits)
        out_ref[...] += _exact_onehot_dot(_onehot(oh_xt, jnp.bfloat16), contrib)


def _kernel(frac_bits, s0, dst_blk, src_blk, first,
            x_ref, y_ref, val_ref, p_ref, out_ref):
    """One grid step = one packet of edges.

    x_ref/y_ref/val_ref: [ROW_BLOCK, PACKET] edge tiles holding this packet.
    p_ref:   [v_tile, K]  source slice of P (selected by src_blk[i]).
    out_ref: [v_tile, K]  destination accumulator (selected by dst_blk[i]).
    """
    i = pl.program_id(0)

    @pl.when(first[i] == 1)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    spmv_accumulate((s0 + i) % ROW_BLOCK, x_ref, y_ref, val_ref, p_ref,
                    out_ref, frac_bits)


@functools.partial(
    jax.jit,
    static_argnames=("v_tile", "packet", "chunks", "frac_bits", "interpret"),
)
def coo_spmv_pallas(
    x_local: jax.Array,       # [rows, packet] uint16/int32 dst index local to tile
    y_local: jax.Array,       # [rows, packet] uint16/int32 src index local to tile
    val: jax.Array,           # [rows, packet] f32 (or uint32 raw if fixed)
    p: jax.Array,             # [n_src * v_tile, K]
    packet_dst: jax.Array,    # [num_packets] int32  packet → dst block
    packet_src: jax.Array,    # [num_packets] int32  packet → src block
    packet_first: jax.Array,  # [num_packets] int32  1 = first packet of dst block
    *,
    v_tile: int,
    packet: int,
    chunks: Tuple[Tuple[int, ...], ...],
    frac_bits: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns out [n_dst * v_tile, K]; dst blocks with no packets are NOT
    written (caller masks them — see ops.coo_spmv).  ``rows`` is
    ``num_packets`` padded to whole ``ROW_BLOCK`` tiles (``pad_rows``);
    ``chunks`` is ``dst_chunks(packet_dst, n_dst)``."""
    k = p.shape[-1]
    parts = []
    for s0, s1, d0, d1 in chunks:
        def tile(i, pd, ps, pf, s0=s0):
            return (s0 + i) // ROW_BLOCK, 0

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s1 - s0,),
            in_specs=[
                pl.BlockSpec((ROW_BLOCK, packet), tile),              # x
                pl.BlockSpec((ROW_BLOCK, packet), tile),              # y
                pl.BlockSpec((ROW_BLOCK, packet), tile),              # val
                pl.BlockSpec((v_tile, k),
                             lambda i, pd, ps, pf: (ps[i], 0)),       # P src
            ],
            out_specs=pl.BlockSpec(
                (v_tile, k), lambda i, pd, ps, pf, d0=d0: (pd[i] - d0, 0)),
        )
        parts.append(pl.pallas_call(
            functools.partial(_kernel, frac_bits, s0),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(((d1 - d0) * v_tile, k), p.dtype),
            interpret=interpret,
        )(packet_dst[s0:s1], packet_src[s0:s1], packet_first[s0:s1],
          x_local, y_local, val, p))
    return jnp.concatenate(parts, axis=0)
