import os
# Importing this module rewrites XLA_FLAGS for the whole process (512
# placeholder CPU devices).  Code that runs on a chip (the trainer, the
# benchmarks, chip_smoke.py) must never import it, even indirectly.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

The statements above run before ANY other import (jax locks the device count
on first init): this process sees 512 placeholder CPU devices so
``make_production_mesh`` can build the production meshes.  Nothing is
allocated — inputs are ShapeDtypeStructs, params come from ``eval_shape``.

Per combo this prints/records:
  - compiled.memory_analysis()  (fits-on-chip proof),
  - compiled.cost_analysis()    (FLOPs / bytes for the roofline),
  - parsed collective bytes     (the roofline's third term),
and appends a JSON row under experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k [--multi-pod] [--fsdp auto|on|off] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import SHAPES
from repro.configs.registry import arch_names, get_config
from repro.launch import roofline as rl
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import combo_supported


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            fsdp: str = "auto", server_update: str = "sequential",
            shard_server_batch: bool = False, codec: str = "none",
            params_2d: bool = False,
            cache_layout: str = "seq", mesh_shape=None,
            verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = combo_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    kw = {}
    if shape.kind == "train":
        if fsdp != "auto":
            kw["fsdp_server"] = fsdp == "on"
        kw["server_update"] = server_update
        kw["shard_server_batch"] = shard_server_batch
        kw["codec"] = codec
    if shape.kind == "decode":
        if params_2d:
            kw["params_2d"] = True
        if cache_layout != "seq":
            kw["cache_layout"] = cache_layout
    # ONE deploy lowering: scans + remat exactly as we would run it.
    # Roofline terms come from the trip-count-aware HLO cost walker
    # (rl.hlo_costs) over the optimized module — cost_analysis() visits
    # every while body once and would undercount scanned layers by the
    # trip count, while fully unrolling 80-layer archs is intractable.
    fn, args = steps_mod.build_step(cfg, shape, mesh, **kw)
    t0 = time.time()
    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()
    dt = time.time() - t0

    counts = steps_mod.param_counts(cfg)
    text = compiled.as_text()
    costs = rl.hlo_costs(text)
    ma = compiled.memory_analysis()
    r = rl.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_device=costs["flops"],
        bytes_per_device=costs["bytes"],
        coll_bytes_per_device=int(sum(costs["coll"].values())),
        coll_breakdown=costs["coll"],
        peak_memory_per_device=int(ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes),
        model_flops_global=rl.model_flops(cfg, shape, counts),
        compile_seconds=dt)
    if verbose:
        print(f"== {arch} x {shape_name} x {mesh_name} "
              f"(compile {dt:.1f}s) ==")
        print(f"   memory_analysis: args={ma.argument_size_in_bytes/2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes/2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB per device")
        print(f"   flops/dev={r.flops_per_device:.3e} "
              f"bytes/dev={r.bytes_per_device:.3e} "
              f"coll/dev={r.coll_bytes_per_device:.3e}")
        print(f"   t_compute={r.t_compute*1e3:.2f}ms t_memory={r.t_memory*1e3:.2f}ms "
              f"t_collective={r.t_collective*1e3:.2f}ms -> {r.bottleneck}")
        print(f"   useful_flops_ratio={r.useful_flops_ratio:.3f} "
              f"mfu_bound={r.mfu_bound:.3f}")
    return r.as_dict()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--server-update", default="sequential",
                    choices=["sequential", "batched"])
    ap.add_argument("--shard-server-batch", action="store_true")
    ap.add_argument("--codec", default="none",
                    help="uplink wire codec compiled into the train step "
                         "(any registered repro.transport codec)")
    ap.add_argument("--params-2d", action="store_true")
    ap.add_argument("--cache-layout", default="seq",
                    choices=["seq", "hd", "kvh"])
    ap.add_argument("--mesh-shape", default=None,
                    help="single-pod (data,model) override, e.g. 32x8")
    ap.add_argument("--tag", default=None,
                    help="suffix for the output files (perf variants)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = arch_names() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            try:
                row = run_one(arch, shape, multi_pod=args.multi_pod,
                              fsdp=args.fsdp,
                              server_update=args.server_update,
                              shard_server_batch=args.shard_server_batch,
                              codec=args.codec,
                              params_2d=args.params_2d,
                              cache_layout=args.cache_layout,
                              mesh_shape=tuple(int(x) for x in
                                               args.mesh_shape.split("x"))
                              if args.mesh_shape else None)
            except Exception as e:  # a failure here is a sharding bug
                traceback.print_exc()
                row = {"arch": arch, "shape": shape,
                       "error": f"{type(e).__name__}: {e}"}
            results.append(row)
            tag = "multipod" if args.multi_pod else "singlepod"
            if args.tag:
                tag = f"{tag}-{args.tag}"
            fname = os.path.join(
                args.out, f"{arch}_{shape}_{tag}.json".replace("/", "-"))
            with open(fname, "w") as f:
                json.dump(row, f, indent=1)
    bad = [r for r in results if "error" in r]
    print(f"\n{len(results)} combos, {len(bad)} errors")
    for r in bad:
        print("  ERROR", r["arch"], r["shape"], r["error"])
    raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
