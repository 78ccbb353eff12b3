"""jit'd public wrappers around the Pallas kernels.

``coo_spmv`` does the host-side packet→block metadata prep (once per graph,
cached on the BlockedCOO) and the empty-dst-block masking that the kernel's
write-once discipline requires.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coo import BlockedCOO
from repro.core.fixed_point import QFormat
from repro.kernels.coo_spmv import (
    coo_spmv_pallas,
    default_interpret,
    dst_chunks,
    pad_rows,
)
from repro.kernels.fixed_matmul import quantized_matmul_pallas


def packet_metadata(blocked: BlockedCOO):
    """packet→(dst, src, first-of-dst, dst-touched) maps (host-side, O(E))."""
    starts = blocked.block_starts.astype(np.int64)
    n_dst, n_src = blocked.n_dst, blocked.n_src
    counts = np.diff(starts)                       # packets per (dst,src) block
    block_ids = np.nonzero(counts)[0]
    reps = counts[block_ids]
    packet_block = np.repeat(block_ids, reps)      # [num_packets]
    packet_dst = (packet_block // n_src).astype(np.int32)
    packet_src = (packet_block % n_src).astype(np.int32)
    first = np.zeros_like(packet_dst)
    if packet_dst.shape[0]:
        first[0] = 1
        first[1:] = (packet_dst[1:] != packet_dst[:-1]).astype(np.int32)
    touched = np.zeros(n_dst, bool)
    touched[np.unique(packet_dst)] = True
    return packet_dst, packet_src, first.astype(np.int32), touched


def coo_spmv(
    blocked: BlockedCOO,
    p: jax.Array,
    *,
    fmt: Optional[QFormat] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Streaming SpMM via the Pallas kernel.  p: [V_padded, K] where V_padded =
    n_src * v_tile (caller pads).  fmt=None → float; else p/val are raw uint32.
    ``interpret=None`` interprets on the CPU backend and compiles elsewhere."""
    meta = getattr(blocked, "_packet_meta", None)
    if meta is None:
        meta = packet_metadata(blocked)
        object.__setattr__(blocked, "_packet_meta", meta) if hasattr(blocked, "__frozen__") \
            else setattr(blocked, "_packet_meta", meta)
    packet_dst, packet_src, first, touched = meta
    num_packets = packet_dst.shape[0]
    pk = blocked.packet
    xp_, yp_ = blocked.packed_indices()   # uint16 when v_tile ≤ 65536 (½ stream)
    x2 = jnp.asarray(pad_rows(xp_.reshape(num_packets, pk)))
    y2 = jnp.asarray(pad_rows(yp_.reshape(num_packets, pk)))
    if fmt is None:
        val2 = blocked.val
        frac_bits = None
    else:
        val2 = np.minimum(
            np.floor(np.clip(blocked.val.astype(np.float64), 0, None) * fmt.scale),
            fmt.max_raw,
        ).astype(np.uint32)
        frac_bits = fmt.frac_bits
    out = coo_spmv_pallas(
        x2, y2, jnp.asarray(pad_rows(val2.reshape(num_packets, pk))), p,
        jnp.asarray(packet_dst), jnp.asarray(packet_src), jnp.asarray(first),
        v_tile=blocked.v_tile, packet=pk,
        chunks=dst_chunks(packet_dst, blocked.n_dst), frac_bits=frac_bits,
        interpret=default_interpret() if interpret is None else interpret,
    )
    # dst blocks with zero packets hold uninitialized memory — mask them.
    mask = jnp.asarray(np.repeat(touched, blocked.v_tile))
    return jnp.where(mask[:, None], out, jnp.zeros_like(out))


def pad_p_for_blocks(p: jax.Array, blocked: BlockedCOO) -> jax.Array:
    """Pad P [V, K] to [n_src*v_tile, K] for the kernel."""
    target = blocked.n_src * blocked.v_tile
    pad = target - p.shape[0]
    if pad == 0:
        return p
    return jnp.pad(p, ((0, pad), (0, 0)))


def quantized_matmul(a, w_q, scale, *, interpret: bool = True, **tiles):
    """Reduced-precision serving matmul (see fixed_matmul.py)."""
    return quantized_matmul_pallas(a, w_q, scale, interpret=interpret, **tiles)
