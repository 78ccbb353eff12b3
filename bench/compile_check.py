"""Compile the kron20 cell's generator and wave programs for a described TPU v5e chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py

No chip is needed: the TPU compiler installed with JAX compiles for a
described ``v5e:2x2`` topology and refuses what the chip would refuse (a
program that does not fit, an unsupported op).  Prints each program's
memory analysis.  Nothing runs, so nothing here is a time.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.generators.kronecker import _compiled
    from bench.manifest import Manifest
    from repro.core.fixed_point import PAPER_FORMATS
    from repro.core.ppr import make_ppr_fixed_step, ppr_step_float
    from repro.ppr_serving.topk import topk_dense

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = Manifest(ROOT).config("kron20")
    n = 1 << cfg["graph"]["scale"]
    edges = 2 * cfg["graph"]["edge_factor"] * n      # arcs before dedup: an upper bound
    kappa = cfg["service"]["kappa"]
    alpha = cfg["service"]["alpha"]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    e = (edges + 255) // 256 * 256
    idx = shape((e,), jnp.int32)
    g = cfg["graph"]
    for name, fn, args in (
            ("kronecker generator",
             _compiled(g["scale"], g["edge_factor"], *g["initiator"]),
             (shape((), jax.random.key(0).dtype),)),
            ("fixed step Q1.25",
             make_ppr_fixed_step(PAPER_FORMATS["Q1.25"], n, alpha),
             (idx, idx, shape((e,), jnp.uint32), shape((n,), jnp.bool_),
              shape((n, kappa), jnp.uint32), shape((n, kappa), jnp.uint32))),
            ("float step",
             jax.jit(lambda x, y, v, d, V, P: ppr_step_float(
                 x, y, v, d, V, P, num_vertices=n, alpha=alpha)),
             (idx, idx, shape((e,), jnp.float32), shape((n,), jnp.bool_),
              shape((n, kappa), jnp.float32), shape((n, kappa), jnp.float32))),
            ("top-K", jax.jit(lambda P, ex: topk_dense(P, 10, exclude=ex)),
             (shape((n, kappa), jnp.uint32), shape((kappa,), jnp.int32)))):
        compiled = fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        print(f"{name}: compiled for v5e; arguments "
              f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temp "
              f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, output "
              f"{mem.output_size_in_bytes / 2**30:.3f} GiB", flush=True)


if __name__ == "__main__":
    main()
