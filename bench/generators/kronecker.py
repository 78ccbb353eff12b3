"""Graph500 Kronecker (R-MAT) edge generator, made on the device from a seed.

Follows the Graph500 specification's reference ``kronecker_generator``:
``edge_factor * 2^scale`` edge tuples, each placed by ``scale`` independent
quadrant draws with initiator A, B, C (D = 1 - A - B - C), then vertex ids
permuted at random.  As the specification's kernel 1 does, the tuples make
an undirected graph: each becomes two arcs, one each way, and self-loops and
repeated arcs are removed.  One jitted call draws, permutes, mirrors, sorts
by (dst, src) and marks the repeats, so the host only compresses the
result.
"""
from __future__ import annotations

import functools

import numpy as np

# the size a CPU rehearsal of a cell on this generator runs at
REHEARSAL = {"scale": 9}


def _kronecker_device(key, *, scale: int, edge_factor: int, a: float,
                      b: float, c: float):
    import jax
    import jax.numpy as jnp

    n = 1 << scale
    m = edge_factor * n
    k_bits, k_perm = jax.random.split(key)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    level_keys = jax.random.split(k_bits, scale)

    def level(i, carry):
        src, dst = carry
        u = jax.random.uniform(level_keys[i], (2, m))
        src_bit = u[0] > ab
        dst_bit = u[1] > jnp.where(src_bit, c_norm, a_norm)
        return (src | (src_bit.astype(jnp.int32) << i),
                dst | (dst_bit.astype(jnp.int32) << i))

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    src, dst = jnp.concatenate([src, dst]), jnp.concatenate([dst, src])
    dst, src = jax.lax.sort((dst, src), num_keys=2)
    repeat = (dst[1:] == dst[:-1]) & (src[1:] == src[:-1])
    keep = (src != dst) & jnp.concatenate([jnp.ones((1,), bool), ~repeat])
    return src, dst, keep


@functools.lru_cache(maxsize=None)
def _compiled(scale: int, edge_factor: int, a: float, b: float, c: float):
    import jax

    return jax.jit(functools.partial(_kronecker_device, scale=scale,
                                     edge_factor=edge_factor, a=a, b=b, c=c))


def sizes(params: dict):
    """``(num_vertices, arcs)``, the arcs an upper bound: every generated
    tuple both ways, before loops and repeats are removed."""
    n = 1 << int(params["scale"])
    return n, 2 * int(params["edge_factor"]) * n


def device_program(params: dict):
    """The jitted program ``generate`` runs on the device, and the shapes
    of its arguments."""
    import jax

    a, b, c = (float(x) for x in params["initiator"])
    fn = _compiled(int(params["scale"]), int(params["edge_factor"]), a, b, c)
    return fn, (jax.ShapeDtypeStruct((), jax.random.key(0).dtype),)


def generate(params: dict, seed: int):
    """``(num_vertices, src, dst)``: int64 host arrays of the distinct
    non-loop arcs of the undirected graph, sorted by (dst, src)."""
    import jax

    scale = int(params["scale"])
    a, b, c = (float(x) for x in params["initiator"])
    fn = _compiled(scale, int(params["edge_factor"]), a, b, c)
    src, dst, keep = jax.device_get(fn(jax.random.key(seed)))
    keep = np.asarray(keep)
    return (1 << scale, np.asarray(src)[keep].astype(np.int64),
            np.asarray(dst)[keep].astype(np.int64))
