"""Structured (trip-count-correct) roofline for every cell on the single-pod
mesh (§Roofline is single-pod per the run-book).  Runs on the CPU, on 512 host
devices:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.roofline_run
        [--arch A] [--shape S] [--out experiments/roofline] [--variant baseline]
"""
import argparse
import json
import os
import time
import traceback

import jax.numpy as jnp

from repro.configs import LONG_CONTEXT_ARCHS, SHAPES, get_config, list_archs
from repro.launch.mesh import cpu_host_devices, make_production_mesh
from repro.roofline.structured import structured_roofline

MICROBATCHES = {"train_4k": 8}

# §Perf hillclimb variants (hypothesis → change; see EXPERIMENTS.md §Perf).
# "baseline"/"it1_moe_sharding" share overrides={} — the MoE dispatch
# constraint is a library change, so the variant name records WHEN it landed.
VARIANTS = {
    "baseline": {},
    "final": {},            # library after all landed §Perf changes
    "it1_moe_sharding": {},
    # decode: local-attention layers keep only `window` KV entries
    "it_windowed_kv": {"cache_len": "windowed"},
    # decode: KV stored in int8 (the paper's truncation quantization on state)
    "it_int8_kv": {"cache_len": "windowed", "kv_dtype": jnp.int8},
    # decode: + int8 weight streaming (kernels/fixed_matmul serving path)
    "it_int8_weights": {"cache_len": "windowed", "kv_dtype": jnp.int8,
                        "param_dtype": jnp.int8},
    # decode int8 KV without windowing (for full-attention archs)
    "it_int8_kv_only": {"kv_dtype": jnp.int8},
    "it_int8_all": {"kv_dtype": jnp.int8, "param_dtype": jnp.int8},
    # train/prefill: disable sequence parallelism (batch-only activations)
    "it_no_sp": {"sequence_parallel": False},
    # train: 12-bit fixed-point gradient all-reduce w/ error feedback
    # wire format (1 sign + 2 int + 12 frac)/32 = 15/32
    "it_compressed_ar": {"grad_ar_scale": 15.0 / 32.0},
    "it_no_sp_compressed_ar": {"sequence_parallel": False,
                               "grad_ar_scale": 15.0 / 32.0},
    # MoE: tight capacity (1.0) — smaller dispatch buffers, more drops
    "it_cap1": {"cfg": {"moe_capacity_factor": 1.0}},
    "it_cap1_compressed": {"cfg": {"moe_capacity_factor": 1.0},
                           "grad_ar_scale": 15.0 / 32.0},
}


def resolve_overrides(name: str, shape) -> dict:
    ov = dict(VARIANTS[name])
    if ov.get("cache_len") == "windowed":
        smax = shape.seq_len
        ov["cache_len"] = lambda w: min(w, smax) if w else smax
    return ov


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="experiments/roofline")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    cpu_host_devices(512)

    mesh = make_production_mesh(multi_pod=False)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    out_dir = os.path.join(args.out, args.variant)
    os.makedirs(out_dir, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                continue
            fn = os.path.join(out_dir, f"{arch}__{shape_name}.json")
            if args.skip_existing and os.path.exists(fn):
                continue
            cfg = get_config(arch)
            shape = SHAPES[shape_name]
            t0 = time.time()
            try:
                overrides = resolve_overrides(args.variant, shape)
                if "cfg" in overrides:
                    import dataclasses as _dc
                    cfg = _dc.replace(cfg, **overrides.pop("cfg"))
                rec = structured_roofline(
                    cfg, shape, mesh, microbatches=MICROBATCHES.get(shape_name, 1),
                    overrides=overrides)
                rec.update(arch=arch, shape=shape_name, variant=args.variant,
                           wall_s=round(time.time() - t0, 1))
                with open(fn, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"OK    {arch:22s} {shape_name:12s} "
                      f"compute={rec['compute_s']:.3e} memory={rec['memory_s']:.3e} "
                      f"coll={rec['collective_s']:.3e} {rec['bottleneck']:10s} "
                      f"useful={rec['useful_flops_ratio']:.3f} ({rec['wall_s']}s)",
                      flush=True)
            except Exception as e:
                failures.append((arch, shape_name, repr(e)))
                print(f"FAIL  {arch:22s} {shape_name}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} failures")
    print("ALL STRUCTURED ROOFLINES DONE")


if __name__ == "__main__":
    main()
