"""Multi-pod dry-run: lower + compile every (architecture × input shape) on the
production meshes, and extract memory / cost / collective analyses.  Runs on
the CPU, on 512 host devices:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.dryrun [--arch A]
        [--shape S] [--mesh single|multi|both] [--out experiments/dryrun]

Per cell it writes ``<out>/<mesh>/<arch>__<shape>.json`` with:
  - memory_analysis (per-device bytes: args / outputs / temps / peak)
  - cost_analysis   (flops / bytes accessed, per-device SPMD program)
  - collective op result bytes (parsed from compiled HLO)
  - the three roofline terms + bottleneck (§Roofline)

Any sharding mismatch / compile OOM / unsupported collective here is a bug in
the framework — the run fails loudly.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (
    LONG_CONTEXT_ARCHS,
    LONG_SKIP_REASON,
    SHAPES,
    get_config,
    list_archs,
)
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
    set_sharding_context,
)
from repro.launch import specs as S
from repro.launch.mesh import cpu_host_devices, make_production_mesh
from repro.models.transformer import build_model
from repro.roofline.analysis import (
    PEAK_FLOPS,
    model_flops_forward,
    model_flops_train,
    roofline,
)
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import make_train_step

from jax.sharding import NamedSharding, PartitionSpec as P

MICROBATCHES = {"train_4k": 8}


def _mem_analysis(compiled):
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        keys = [
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ]
        return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}
    except Exception as e:  # CPU backend may not implement it fully
        return {"error": str(e)}


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, out_dir: str,
             opt_level: str = "baseline") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = 1
    for n in mesh.shape.values():
        chips *= n
    api = build_model(cfg, remat=(shape.kind == "train"))
    params_s = S.params_specs(api)
    pshard = param_shardings(params_s, mesh, cfg=cfg)
    set_sharding_context(mesh, sequence_parallel=(shape.kind != "decode"))
    t0 = time.time()

    if shape.kind == "train":
        mb = MICROBATCHES.get(shape_name, 1)
        step = make_train_step(api.loss_fn, AdamWConfig(), microbatches=mb)
        state_s = S.train_state_specs(params_s)
        state_shard = type(state_s)(
            params=pshard,
            opt=type(state_s.opt)(
                step=NamedSharding(mesh, P()),
                mu=pshard, nu=pshard),
            residual=None,
        )
        batch_s = S.batch_specs(cfg, shape)
        bshard = batch_shardings(batch_s, mesh)
        jitted = jax.jit(step, in_shardings=(state_shard, bshard),
                         out_shardings=(state_shard, None))
        lowered = jitted.lower(state_s, batch_s)
        tokens = shape.global_batch * shape.seq_len
        mflops = model_flops_train(cfg, tokens)
    elif shape.kind == "prefill":
        batch_s = S.batch_specs(cfg, shape)
        bshard = batch_shardings(batch_s, mesh)
        cache_s = S.cache_specs(api, shape.global_batch, shape.seq_len)
        cshard = cache_shardings(cache_s, mesh, shape.global_batch)
        jitted = jax.jit(api.prefill, in_shardings=(pshard, bshard, cshard),
                         out_shardings=(None, cshard))
        lowered = jitted.lower(params_s, batch_s, cache_s)
        mflops = model_flops_forward(cfg, shape.global_batch * shape.seq_len)
    else:  # decode
        token_s, pos_s, cache_s = S.decode_specs(cfg, shape, api)
        cshard = cache_shardings(cache_s, mesh, shape.global_batch)
        tshard = batch_shardings(token_s, mesh,
                                 batch_divisible=shape.global_batch % 16 == 0)
        jitted = jax.jit(api.decode_step,
                         in_shardings=(pshard, tshard, NamedSharding(mesh, P()), cshard),
                         out_shardings=(None, cshard))
        lowered = jitted.lower(params_s, token_s, pos_s, cache_s)
        mflops = model_flops_forward(cfg, shape.global_batch)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    terms = roofline(cost, hlo, chips, model_flops=mflops)
    mem = _mem_analysis(compiled)
    set_sharding_context(None)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "opt_level": opt_level,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "cost_flops": float(cost.get("flops", 0.0)),
        "cost_bytes": float(cost.get("bytes accessed", 0.0)),
        "roofline": terms.as_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}__{shape_name}.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    cpu_host_devices(512)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    failures = []
    for mesh_name, mesh in meshes:
        out_dir = os.path.join(args.out, mesh_name)
        for arch in archs:
            for shape_name in shapes:
                if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
                    print(f"SKIP  {mesh_name:18s} {arch:22s} {shape_name}: "
                          f"{LONG_SKIP_REASON[arch]}")
                    continue
                fn = os.path.join(out_dir, f"{arch}__{shape_name}.json")
                if args.skip_existing and os.path.exists(fn):
                    print(f"have  {mesh_name:18s} {arch:22s} {shape_name}")
                    continue
                try:
                    rec = run_cell(arch, shape_name, mesh, mesh_name, out_dir)
                    r = rec["roofline"]
                    print(
                        f"PASS  {mesh_name:18s} {arch:22s} {shape_name:12s} "
                        f"compile={rec['compile_s']:.0f}s "
                        f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
                        f"coll={r['collective_s']:.2e}s bottleneck={r['bottleneck']}",
                        flush=True,
                    )
                except Exception as e:
                    failures.append((mesh_name, arch, shape_name, repr(e)))
                    print(f"FAIL  {mesh_name:18s} {arch:22s} {shape_name}: {e!r}",
                          flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        raise SystemExit(1)
    print("\nALL CELLS PASS")


if __name__ == "__main__":
    main()
