"""Dry-run of the paper's own workload on the production meshes: the
dst-partitioned streaming SpMV PPR iteration, lowered + compiled at pod scale.
Runs on the CPU, on 512 host devices:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.ppr_dryrun [--workload ppr-pod-16m]

The model axis partitions the vertex space (the paper's URAM → per-chip
memory); the data axis batches independent κ-groups of personalization
vertices (the paper's request batching, scaled 16×).
"""
import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.ppr_paper import PPR_WORKLOADS
from repro.launch.mesh import cpu_host_devices, make_production_mesh
from repro.roofline.analysis import HBM_BW, ICI_BW, collective_bytes

SDS = jax.ShapeDtypeStruct


def build_ppr_step(w, mesh):
    """One PPR iteration over the dst-partitioned COO graph, κ batched over
    the data axis.  Edges padded per model-shard; indices local to the shard."""
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"] * mesh.shape.get("pod", 1)
    v_local = w.num_vertices // n_model
    e_shard = w.num_edges // n_model

    def step(x_loc, y, val, p, dangling, pers_mat):
        # p arrives dst-sharded (the previous iteration's output); the step
        # all-gathers it over the model axis — the partitioned design's real
        # per-iteration collective (paper §4.1.2 partitioning trade-off).
        def local(x_l, y_l, v_l, p_shard, dang, pmat):
            p_full = jax.lax.all_gather(p_shard, "model", axis=0, tiled=True)
            contrib = v_l[0][:, None] * p_full[y_l[0]]   # gather full p rows
            xp = jax.ops.segment_sum(contrib, x_l[0], num_segments=v_local)
            dangling_mass = dang @ p_full                # [K]
            return (w.alpha * xp
                    + (w.alpha / w.num_vertices) * dangling_mass[None, :]
                    + (1 - w.alpha) * pmat)

        # κ-groups on the data axis are independent problems: shard P's
        # columns over data so the model-axis all-gather never spans them
        # (16× less collective traffic than gathering all K_total columns).
        kspec = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("model"), P("model"), P("model"),
                      P("model", kspec), P(), P("model", kspec)),
            out_specs=P("model", kspec),
        )(x_loc, y, val, p, dangling, pers_mat)

    k_total = w.kappa * n_data
    specs = (
        SDS((n_model, e_shard), jnp.int32),            # x_local per shard
        SDS((n_model, e_shard), jnp.int32),            # y (global src)
        SDS((n_model, e_shard), jnp.float32),          # val
        SDS((w.num_vertices, k_total), jnp.float32),   # P_t (replicated)
        SDS((w.num_vertices,), jnp.float32),           # dangling
        SDS((w.num_vertices, k_total), jnp.float32),   # personalization
    )
    shardings = (
        NamedSharding(mesh, P("model")),
        NamedSharding(mesh, P("model")),
        NamedSharding(mesh, P("model")),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P("model")),
    )
    return step, specs, shardings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ppr-pod-16m",
                    choices=sorted(PPR_WORKLOADS))
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    cpu_host_devices(512)
    w = PPR_WORKLOADS[args.workload]
    for mesh_name, mesh in [
        ("single_pod_16x16", make_production_mesh(multi_pod=False)),
        ("multi_pod_2x16x16", make_production_mesh(multi_pod=True)),
    ]:
        step, specs, shardings = build_ppr_step(w, mesh)
        kspec = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        shardings = shardings[:3] + (
            NamedSharding(mesh, P("model", kspec)),
            shardings[4],
            NamedSharding(mesh, P("model", kspec)),
        )
        lowered = jax.jit(step, in_shardings=shardings,
                          out_shardings=NamedSharding(mesh, P("model", kspec))).lower(*specs)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        colls = collective_bytes(compiled.as_text())
        flops = float(cost.get("flops", 0))
        by = float(cost.get("bytes accessed", 0))
        cb = float(sum(colls.values()))
        rec = {
            "workload": w.name, "mesh": mesh_name,
            "V": w.num_vertices, "E": w.num_edges,
            "kappa_total": w.kappa * mesh.shape["data"] * mesh.shape.get("pod", 1),
            "flops_per_device": flops, "bytes_per_device": by,
            "collective_bytes_per_device": cb, "collectives": colls,
            "memory_s": by / HBM_BW, "collective_s": cb / ICI_BW,
        }
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"ppr__{w.name}__{mesh_name}.json"), "w") as f:
            json.dump(rec, f, indent=1)
        print(f"PASS  {mesh_name:18s} {w.name}: memory_s={rec['memory_s']:.3e} "
              f"coll_s={rec['collective_s']:.3e} "
              f"(per-iteration, {rec['kappa_total']} concurrent requests)", flush=True)


if __name__ == "__main__":
    main()
