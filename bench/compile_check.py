"""Compile what each cell serves for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py

For every configuration and chip count that a cell of ``BENCHMARK.json``
names: the generator's device program (where it has one), the eq. (1) step
of each precision the cells ask for, and the top-K, at the configuration's
sizes (``sizes`` of its generator: the arcs are an upper bound).  On one
chip that is the single engine's step, the fixed-point one on a
``SortedDst`` stream padded to ``ROW_PREFIX_ALIGN`` edges as the service
registers it; on four, the sharded engine's step over the mesh the cell
registers on (``harness.cell_mesh``), the arcs split evenly over its shard
axis.  The TPU compiler installed with JAX compiles for the
described ``v5e:2x2`` topology and refuses what the chip would refuse (a
program that does not fit, an unsupported op).  Prints each program's
memory analysis.  Nothing runs, so nothing here is a time.
"""
from __future__ import annotations

import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _packet() -> int:
    """The edge packet the service registers a graph with by default."""
    import inspect

    from repro.ppr_serving import PPRService

    return inspect.signature(PPRService.register_graph).parameters[
        "packet"].default


def _single(fmts, n, arcs, kappa, alpha, chip):
    """The single engine's programs, as (label, jitted, argument shapes)."""
    import jax
    import jax.numpy as jnp

    from repro.core.ppr import make_ppr_fixed_step, ppr_step_float
    from repro.core.spmv import ROW_PREFIX_ALIGN, SortedDst

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    pad = math.lcm(_packet(), ROW_PREFIX_ALIGN)
    e = -(-arcs // pad) * pad
    idx = shape((e,), jnp.int32)
    for fmt in fmts:
        if fmt is None:
            yield ("float step",
                   jax.jit(lambda x, y, v, d, V, P: ppr_step_float(
                       x, y, v, d, V, P, num_vertices=n, alpha=alpha)),
                   (idx, idx, shape((e,), jnp.float32), shape((n,), jnp.bool_),
                    shape((n, kappa), jnp.float32),
                    shape((n, kappa), jnp.float32)))
        else:
            yield (f"fixed step {fmt.name} on SortedDst",
                   make_ppr_fixed_step(fmt, n, alpha),
                   (SortedDst(idx, shape((n + 1,), jnp.int32)), idx,
                    shape((e,), jnp.uint32), shape((n,), jnp.bool_),
                    shape((n, kappa), jnp.uint32),
                    shape((n, kappa), jnp.uint32)))


def _sharded(fmts, n, arcs, kappa, alpha, mesh, axis):
    """The sharded engine's programs over ``mesh``, the edges split over
    ``axis``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.ppr import (make_ppr_sharded_fixed_step,
                                make_ppr_sharded_float_step)

    part = NamedSharding(mesh, PartitionSpec(axis))
    rep = NamedSharding(mesh, PartitionSpec())
    shards, packet = mesh.shape[axis], _packet()
    e = -(-arcs // (shards * packet)) * packet * shards

    def edges(dtype):
        return jax.ShapeDtypeStruct((e,), dtype, sharding=part)

    def state(dtype):
        return jax.ShapeDtypeStruct((n, kappa), dtype, sharding=rep)

    dangling = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=rep)
    for fmt in fmts:
        if fmt is None:
            yield ("sharded float step",
                   make_ppr_sharded_float_step(mesh, axis, n, alpha),
                   (edges(jnp.int32), edges(jnp.int32), edges(jnp.float32),
                    dangling, state(jnp.float32), state(jnp.float32)))
        else:
            yield (f"sharded fixed step {fmt.name}",
                   make_ppr_sharded_fixed_step(fmt, mesh, axis, n, alpha),
                   (edges(jnp.int32), edges(jnp.int32), edges(jnp.uint32),
                    dangling, state(jnp.uint32), state(jnp.uint32)))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from bench.harness import cell_mesh
    from bench.manifest import Manifest
    from repro.ppr_serving import normalize_precision
    from repro.ppr_serving.topk import topk_dense

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    man = Manifest(ROOT)
    served = {}      # (config, chips) -> the (precision, k) its cells ask for
    for w in man.data["workloads"]:
        traffic = man.traffic(w["traffic"])
        served.setdefault((w["config"], int(w["chips"])), {})[
            (traffic["precision"], int(traffic.get("k", 10)))] = None
    for (name, chips), asked in served.items():
        cfg = man.config(name)
        g, svc = cfg["graph"], cfg["service"]
        gen = man.generator(g["generator"])
        n, arcs = gen.sizes(g)
        kappa, alpha = int(svc["kappa"]), float(svc["alpha"])
        asked = [(normalize_precision(p), k) for p, k in asked]
        fmts = list(dict.fromkeys(fmt for fmt, _ in asked))
        chip = SingleDeviceSharding(topo.devices[0])
        programs = []
        if hasattr(gen, "device_program"):
            fn, args = gen.device_program(g)
            programs.append(("generator", fn, [
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
                for a in args]))
        if chips == 1 and svc["engine"] == "single":
            programs += _single(fmts, n, arcs, kappa, alpha, chip)
            state = chip
        elif svc["engine"] == "sharded":
            mesh, axis, _ = cell_mesh(svc, chips, topo.devices)
            programs += _sharded(fmts, n, arcs, kappa, alpha, mesh,
                                 axis or mesh.axis_names[0])
            state = NamedSharding(mesh, PartitionSpec())
        else:
            raise SystemExit(f"{name}: no compile recipe for engine "
                             f"{svc['engine']!r} on {chips} chips")
        for fmt, k in asked:
            dtype = jnp.float32 if fmt is None else jnp.uint32
            programs.append((
                f"top-{k} of {'f32' if fmt is None else fmt.name}",
                lambda P, ex, k=k: topk_dense(P, k, exclude=ex),
                (jax.ShapeDtypeStruct((n, kappa), dtype, sharding=state),
                 jax.ShapeDtypeStruct((kappa,), jnp.int32, sharding=state))))
        for label, fn, args in programs:
            fn = fn if hasattr(fn, "lower") else jax.jit(fn)
            mem = fn.lower(*args).compile().memory_analysis()
            print(f"{name} on {chips} chip(s), V={n}, arcs <= {arcs}: {label} "
                  f"compiled for v5e; arguments "
                  f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temp "
                  f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, output "
                  f"{mem.output_size_in_bytes / 2**30:.3f} GiB", flush=True)


if __name__ == "__main__":
    main()
