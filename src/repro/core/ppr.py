"""Batched Personalized PageRank (paper Alg. 1 / eq. 1) in float and fixed point.

P_{t+1} = α·X·P_t + α/|V|·(d̄ᵀP_t)·1 + (1−α)·V̄       (eq. 1)

κ personalization vertices are batched as columns of P (the paper's key
throughput optimization: every edge read is amortized over κ problems).
The fixed-point variant reproduces the FPGA datapath bit-for-bit:
truncating multiplies, raw-domain accumulation, truncating scale-by-α.

The single-iteration bodies are exposed as ``ppr_step_float`` and
``make_ppr_fixed_step`` so external drivers (repro.ppr_serving's wave
scheduler) can advance one eq. (1) iteration at a time — e.g. to abort on a
deadline or interleave waves — while the ``lax.scan`` drivers below stay the
fast path for fixed iteration counts.  Both drivers share the same body
functions, so step-driven and scanned results are bit-identical.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coo import COOGraph
from repro.core.fixed_point import QFormat
from repro.core.spmv import (
    SortedDst,
    make_sharded_spmv,
    make_sharded_spmv_fixed,
    spmv_fixed,
    spmv_float,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PPRConfig:
    alpha: float = 0.85
    iterations: int = 10          # paper: 10 iterations suffice (§5.1)
    kappa: int = 8                # personalization vertices per pass (paper: 8–16)
    track_convergence: bool = True


def personalization_matrix(num_vertices: int, pers: Array, dtype=jnp.float32) -> Array:
    """V̄ of eq. (1): one-hot column per personalization vertex, [V, κ]."""
    k = pers.shape[0]
    V = jnp.zeros((num_vertices, k), dtype)
    return V.at[pers, jnp.arange(k)].set(jnp.ones((k,), dtype))


def personalization_matrix_fixed(num_vertices: int, pers: Array, fmt: QFormat) -> Array:
    """V̄ in the raw uint32 domain (1.0 is exactly representable in Q1.f)."""
    one_raw = np.uint32(fmt.scale)
    V = jnp.zeros((num_vertices, pers.shape[0]), jnp.uint32)
    return V.at[pers, jnp.arange(pers.shape[0])].set(one_raw)


_personalization_matrix = personalization_matrix  # backwards-compat alias


# ----------------------------------------------------------------------------
# single-iteration bodies (shared by the scan drivers and the step API)
# ----------------------------------------------------------------------------
def _float_combine(xp, dangling_mass, Vmat, *, num_vertices: int, alpha: float):
    """eq. (1) elementwise combine — shared by the single-device and sharded
    steps so both apply bit-identical float ops after the SpMV."""
    return alpha * xp + (alpha / num_vertices) * dangling_mass[None, :] \
        + (1.0 - alpha) * Vmat


def _float_iteration(x, y, val, d, Vmat, P, *, num_vertices: int, alpha: float):
    dangling_mass = d @ P                                        # [K]
    xp = spmv_float(x, y, val, P, num_vertices)
    return _float_combine(xp, dangling_mass, Vmat,
                          num_vertices=num_vertices, alpha=alpha)


def _fixed_consts(fmt: QFormat, num_vertices: int, alpha: float):
    """Datapath scalars encoded in the format, so every multiply truncates
    exactly like the FPGA DSP chain.  α/|V| underflows to 0 when 1/|V| < 2^-f —
    exactly the behaviour the real datapath would exhibit (dangling mass
    vanishes for big V)."""
    return (np.uint32(int(alpha * fmt.scale)),
            np.uint32(int((1.0 - alpha) * fmt.scale)),
            np.uint32(int(alpha / num_vertices * fmt.scale)))


def _fixed_dangling_mass(d_raw, P):
    """Σ_{i dangling} P[i,k] — raw-domain exact sum, [K]."""
    return (d_raw[:, None] * P).astype(jnp.int32).sum(0).astype(jnp.uint32)


def _fixed_combine(xp, dangling_mass, Vmat, *, fmt: QFormat, alpha_raw,
                   one_minus_alpha_raw, alpha_over_v_raw):
    """eq. (1) combine in the raw domain — truncating multiplies, saturating
    adds; shared by the single-device and sharded steps (bit-identical)."""
    return fmt.add(
        fmt.add(fmt.mul(jnp.asarray(alpha_raw), xp),
                fmt.mul(jnp.asarray(alpha_over_v_raw), dangling_mass)[None, :]),
        fmt.mul(jnp.asarray(one_minus_alpha_raw), Vmat),
    )


def _fixed_iteration(x, y, val_raw, d_raw, Vmat, P, *, fmt: QFormat,
                     num_vertices: int, alpha_raw, one_minus_alpha_raw,
                     alpha_over_v_raw):
    dangling_mass = _fixed_dangling_mass(d_raw, P)
    xp = spmv_fixed(x, y, val_raw, P, num_vertices, fmt)
    return _fixed_combine(xp, dangling_mass, Vmat, fmt=fmt, alpha_raw=alpha_raw,
                          one_minus_alpha_raw=one_minus_alpha_raw,
                          alpha_over_v_raw=alpha_over_v_raw)


# ----------------------------------------------------------------------------
# step API — one eq. (1) iteration per call, for external drivers
# ----------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("num_vertices", "alpha"))
def ppr_step_float(
    x: Array, y: Array, val: Array, dangling: Array, Vmat: Array, P: Array,
    *, num_vertices: int, alpha: float,
) -> Array:
    """P_{t+1} from P_t, float32.  ``Vmat`` is the one-hot personalization matrix."""
    return _float_iteration(x, y, val, dangling.astype(jnp.float32), Vmat, P,
                            num_vertices=num_vertices, alpha=alpha)


@functools.lru_cache(maxsize=64)
def make_ppr_fixed_step(fmt: QFormat, num_vertices: int, alpha: float):
    """Jitted bit-exact single iteration in the raw uint32 domain of ``fmt``.

    ``x`` is the stream's destinations, or a ``SortedDst`` that adds its
    row pointers: the SpMV then reduces by row prefix, not by scatter, to
    the same bits (``spmv_fixed``)."""
    a_raw, oma_raw, aov_raw = _fixed_consts(fmt, num_vertices, alpha)

    @jax.jit
    def step(x: Union[Array, SortedDst], y: Array, val_raw: Array, dangling: Array,
             Vmat: Array, P: Array) -> Array:
        return _fixed_iteration(
            x, y, val_raw, dangling.astype(jnp.uint32), Vmat, P,
            fmt=fmt, num_vertices=num_vertices, alpha_raw=a_raw,
            one_minus_alpha_raw=oma_raw, alpha_over_v_raw=aov_raw)

    return step


# ----------------------------------------------------------------------------
# sharded step API — one eq. (1) iteration over a mesh-partitioned edge stream
# ----------------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def make_ppr_sharded_float_step(mesh, axis: str, num_vertices: int, alpha: float):
    """Jitted float32 single iteration whose SpMV runs over a ``jax.sharding``
    mesh (edges pre-partitioned by dst range — ``partition_edges_by_dst``).

    Dangling mass and the eq. (1) combine are computed on the replicated [V, K]
    state with the exact same ops as ``ppr_step_float`` (``_float_combine``), so
    any numeric divergence from the single-device step can only come from the
    per-shard SpMV accumulation order.
    """
    spmv = make_sharded_spmv(mesh, axis, num_vertices)

    @jax.jit
    def step(x: Array, y: Array, val: Array, dangling: Array,
             Vmat: Array, P: Array) -> Array:
        d = dangling.astype(jnp.float32)
        dangling_mass = d @ P
        xp = spmv(x, y, val, P)
        return _float_combine(xp, dangling_mass, Vmat,
                              num_vertices=num_vertices, alpha=alpha)

    return step


@functools.lru_cache(maxsize=32)
def make_ppr_sharded_fixed_step(fmt: QFormat, mesh, axis: str,
                                num_vertices: int, alpha: float):
    """Jitted bit-exact fixed-point single iteration over a mesh.

    Per-shard raw accumulation is exact and each dst row lives on exactly one
    shard, so the result is *bit-identical* to ``make_ppr_fixed_step`` — the
    sharded fixed path inherits the single-device path's determinism.
    """
    a_raw, oma_raw, aov_raw = _fixed_consts(fmt, num_vertices, alpha)
    spmv = make_sharded_spmv_fixed(mesh, axis, num_vertices, fmt)

    @jax.jit
    def step(x: Array, y: Array, val_raw: Array, dangling: Array,
             Vmat: Array, P: Array) -> Array:
        dangling_mass = _fixed_dangling_mass(dangling.astype(jnp.uint32), P)
        xp = spmv(x, y, val_raw, P)
        return _fixed_combine(xp, dangling_mass, Vmat, fmt=fmt, alpha_raw=a_raw,
                              one_minus_alpha_raw=oma_raw, alpha_over_v_raw=aov_raw)

    return step


# ----------------------------------------------------------------------------
# float32 path (the paper's F32 reference architecture)
# ----------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("num_vertices", "iterations", "alpha"))
def ppr_float(
    x: Array, y: Array, val: Array, dangling: Array, pers: Array,
    *, num_vertices: int, iterations: int, alpha: float,
) -> Tuple[Array, Array]:
    """Returns (P [V,K] float32, deltas [iterations] convergence trace)."""
    V = personalization_matrix(num_vertices, pers)
    d = dangling.astype(jnp.float32)

    def body(P, _):
        Pn = _float_iteration(x, y, val, d, V, P,
                              num_vertices=num_vertices, alpha=alpha)
        delta = jnp.linalg.norm(Pn - P, axis=0).max()
        return Pn, delta

    P, deltas = jax.lax.scan(body, V, None, length=iterations)
    return P, deltas


# ----------------------------------------------------------------------------
# fixed-point path (the paper's contribution)
# ----------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def make_ppr_fixed(fmt: QFormat, num_vertices: int, iterations: int, alpha: float):
    """Build a jitted bit-exact fixed-point PPR for one Q format."""
    a_raw, oma_raw, aov_raw = _fixed_consts(fmt, num_vertices, alpha)

    @jax.jit
    def run(x: Array, y: Array, val_raw: Array, dangling: Array, pers: Array):
        Vmat = personalization_matrix_fixed(num_vertices, pers, fmt)
        d_raw = dangling.astype(jnp.uint32)

        def body(P, _):
            Pn = _fixed_iteration(
                x, y, val_raw, d_raw, Vmat, P,
                fmt=fmt, num_vertices=num_vertices, alpha_raw=a_raw,
                one_minus_alpha_raw=oma_raw, alpha_over_v_raw=aov_raw)
            delta = jnp.abs(Pn.astype(jnp.float32) - P.astype(jnp.float32))
            return Pn, jnp.sqrt((delta * delta).sum(0)).max() / fmt.scale

        P, deltas = jax.lax.scan(body, Vmat, None, length=iterations)
        return P, deltas

    return run


# ----------------------------------------------------------------------------
# convenience drivers
# ----------------------------------------------------------------------------
def run_ppr(
    g: COOGraph,
    personalization: np.ndarray,
    cfg: PPRConfig = PPRConfig(),
    fmt: Optional[QFormat] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Run PPR on a host graph.  fmt=None → float32; else bit-exact Qm.f.

    Returns (scores [V,K] float64-ish numpy, convergence deltas [iters]).
    """
    pers = jnp.asarray(np.atleast_1d(personalization), jnp.int32)
    x = jnp.asarray(g.x)
    y = jnp.asarray(g.y)
    dang = jnp.asarray(g.dangling)
    if fmt is None:
        P, deltas = ppr_float(
            x, y, jnp.asarray(g.val), dang, pers,
            num_vertices=g.num_vertices, iterations=cfg.iterations, alpha=cfg.alpha,
        )
        return np.asarray(P), np.asarray(deltas)
    run = make_ppr_fixed(fmt, g.num_vertices, cfg.iterations, cfg.alpha)
    P_raw, deltas = run(x, y, jnp.asarray(g.quantized_val(fmt)), dang, pers)
    return np.asarray(P_raw).astype(np.float64) / fmt.scale, np.asarray(deltas)


def batched_ppr(
    g: COOGraph,
    all_vertices: np.ndarray,
    cfg: PPRConfig = PPRConfig(),
    fmt: Optional[QFormat] = None,
) -> np.ndarray:
    """Process many personalization requests in κ-sized batches (paper §5.1:
    '100 random personalization vertices' per measurement)."""
    out = np.zeros((g.num_vertices, len(all_vertices)))
    for i in range(0, len(all_vertices), cfg.kappa):
        batch = np.asarray(all_vertices[i: i + cfg.kappa])
        pad = cfg.kappa - batch.shape[0]
        padded = np.concatenate([batch, np.zeros(pad, np.int64)]) if pad else batch
        scores, _ = run_ppr(g, padded, cfg, fmt)
        out[:, i: i + batch.shape[0]] = scores[:, : batch.shape[0]]
    return out
