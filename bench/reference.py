"""The plain references the correctness check compares against.

Personalized PageRank by eq. (1) of the paper, for a fixed number of
iterations from the one-hot start:

    P_{t+1} = alpha X P_t + alpha/|V| (d^T P_t) 1 + (1 - alpha) Vbar

with X = (D^-1 A)^T built here from the benchmark's own edge list.  Neither
imports anything of the program.

``Reference`` computes it in float64 (scipy CSR), an equivalent of the
program's ``repro.graphs.reference.ppr_reference``: the reference of the
float32 cells.  ``FixedReference`` computes it in the unsigned Qm.f raw
domain the fixed-point cells state, as the paper's datapath does: every
edge product and every scaling truncated to the format, sums exact, adds
saturating.  Columns are computed a few at a time on threads (scipy's
sparse products and numpy's array loops release the interpreter lock).

``ppr_bf16`` is the recurrence in bfloat16 on the default JAX device: the
control that a float32 cell's comparison has to reject.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

THREADS = min(8, os.cpu_count() or 1)


class Reference:
    def __init__(self, num_vertices: int, src: np.ndarray, dst: np.ndarray,
                 alpha: float, iterations: int):
        self.n = int(num_vertices)
        self.alpha = float(alpha)
        self.iterations = int(iterations)
        outdeg = np.bincount(src, minlength=self.n).astype(np.float64)
        self.dangling = (outdeg == 0).astype(np.float64)
        vals = 1.0 / outdeg[src]
        self.X = sp.csr_matrix((vals, (dst, src)), shape=(self.n, self.n))

    def scores(self, seeds) -> np.ndarray:
        """[V, len(seeds)] float64 scores after ``iterations`` steps."""
        return np.stack(self.map_columns(seeds, lambda _, col: col), axis=1)

    def map_columns(self, seeds, fn, block: int = 8) -> list:
        """``[fn(seed, column) for seed in seeds]``, the columns computed
        ``block`` at a time on up to ``THREADS`` threads and dropped once
        ``fn`` has reduced them."""
        seeds = [int(s) for s in seeds]
        chunks = [seeds[i:i + block] for i in range(0, len(seeds), block)]

        def run(chunk):
            P = self._block(np.asarray(chunk, np.int64))
            return [fn(s, P[:, j]) for j, s in enumerate(chunk)]

        workers = max(1, min(THREADS, len(chunks)))
        with ThreadPoolExecutor(max_workers=workers) as ex:
            return [r for part in ex.map(run, chunks) for r in part]

    def _block(self, seeds: np.ndarray) -> np.ndarray:
        a, n = self.alpha, self.n
        V = np.zeros((n, seeds.shape[0]))
        V[seeds, np.arange(seeds.shape[0])] = 1.0
        P = V.copy()
        for _ in range(self.iterations):
            P = a * (self.X @ P) + (a / n) * (self.dangling @ P)[None, :] \
                + (1.0 - a) * V
        return P


class FixedReference:
    """Eq. (1) in unsigned Q1.f raw integers (int64 on the host).

    The edge weights are the edge stream's float32 ``1/outdeg``, truncated
    into the format; alpha, 1 - alpha and alpha/|V| are truncated the same
    way; ``mul(a, b) = (a * b) >> f``; the SpMV sums each destination's
    truncated products exactly; ``add`` saturates at the format's largest
    raw value.  Scores are raw integers (score = raw / 2^f)."""

    def __init__(self, num_vertices: int, src: np.ndarray, dst: np.ndarray,
                 alpha: float, iterations: int, frac_bits: int,
                 int_bits: int = 1):
        self.n = int(num_vertices)
        self.iterations = int(iterations)
        self.f = int(frac_bits)
        self.scale = 1 << self.f
        self.max_raw = (1 << (self.f + int_bits)) - 1
        outdeg = np.bincount(src, minlength=self.n)
        order = np.lexsort((src, dst))
        self.y = np.asarray(src[order], np.int64)
        x = np.asarray(dst[order], np.int64)
        self.rows, self.starts = np.unique(x, return_index=True)
        weight = (1.0 / outdeg[self.y]).astype(np.float32).astype(np.float64)
        self.val = np.minimum(np.floor(weight * self.scale),
                              self.max_raw).astype(np.int64)
        self.dangling = outdeg == 0
        self.alpha = int(alpha * self.scale)
        self.one_minus_alpha = int((1.0 - alpha) * self.scale)
        self.alpha_over_v = int(alpha / self.n * self.scale)

    def _mul(self, a, b):
        return (a * b) >> self.f

    def _add(self, a, b):
        return np.minimum(a + b, self.max_raw)

    def column(self, seed: int) -> np.ndarray:
        """Raw [V] scores of one personalization vertex."""
        V = np.zeros(self.n, np.int64)
        V[seed] = self.scale
        restart = self._mul(self.one_minus_alpha, V)
        P = V
        for _ in range(self.iterations):
            prod = P[self.y]                       # one [E] temporary, in place
            np.multiply(prod, self.val, out=prod)
            np.right_shift(prod, self.f, out=prod)
            xp = np.zeros(self.n, np.int64)
            xp[self.rows] = np.add.reduceat(prod, self.starts)
            del prod
            mass = int(P[self.dangling].sum())
            P = self._add(self._add(self._mul(self.alpha, xp),
                                    self._mul(self.alpha_over_v, mass)),
                          restart)
        return P

    def map_columns(self, seeds, fn) -> list:
        """``[fn(seed, column(seed)) for seed in seeds]`` on threads."""
        seeds = [int(s) for s in seeds]
        with ThreadPoolExecutor(max_workers=max(1, min(THREADS, len(seeds)))) as ex:
            return list(ex.map(lambda s: fn(s, self.column(s)), seeds))


def ppr_bf16(num_vertices: int, src: np.ndarray, dst: np.ndarray, seeds,
             alpha: float, iterations: int) -> np.ndarray:
    """[V, len(seeds)] float32 copy of eq. (1) computed in bfloat16."""
    import jax
    import jax.numpy as jnp

    n = int(num_vertices)
    outdeg = np.bincount(src, minlength=n)
    dangling = jnp.asarray(outdeg == 0, jnp.bfloat16)
    val = jnp.asarray(1.0 / outdeg[src], jnp.bfloat16)
    x = jnp.asarray(dst, jnp.int32)
    y = jnp.asarray(src, jnp.int32)
    seeds = jnp.asarray(np.asarray(seeds), jnp.int32)
    bf = jnp.bfloat16

    @jax.jit
    def run(x, y, val, dangling, seeds):
        k = seeds.shape[0]
        V = jnp.zeros((n, k), bf).at[seeds, jnp.arange(k)].set(1)
        a = jnp.asarray(alpha, bf)

        def body(_, P):
            xp = jax.ops.segment_sum(val[:, None] * P[y], x, num_segments=n)
            mass = (dangling[:, None] * P).sum(0)
            return (a * xp + (a / n) * mass[None, :]
                    + (jnp.asarray(1.0, bf) - a) * V).astype(bf)

        return jax.lax.fori_loop(0, iterations, body, V)

    return np.asarray(run(x, y, val, dangling, seeds).astype(jnp.float32))
