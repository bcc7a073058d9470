"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, record memory/cost/collective analysis.

MUST be run as a script / module (``python -m repro.launch.dryrun``) in
a fresh process: ``main`` sets XLA_FLAGS before any device is used,
giving 512 placeholder CPU devices for the 2x16x16 production mesh.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-4b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out experiments/dryrun
"""

import argparse
import json
import os
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ALL_SHAPES, ARCH_IDS, CodingConfig, get_config)
from repro.dist import coded_train
from repro.dist import sharding as rules
from repro.launch import hlo_analysis
from repro.launch import roofline as rl_mod
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.optim import optimizers as opt_mod


def build_step(cfg, shape, mesh, coding, fsdp=False):
    from repro.models import model as M
    # Sequence/tensor-sharded residual checkpoints (see EXPERIMENTS.md
    # #Perf iteration 1); REPRO_RESIDUAL_SHARDING=0 reproduces the
    # unconstrained baseline.
    mode = os.environ.get("REPRO_RESIDUAL_SHARDING", "dmodel")
    if mode != "0":
        da = ("pod", "data") if "pod" in mesh.axis_names else "data"
        M.set_residual_sharding(batch_axes=da, model_axis="model",
                                mode=mode,
                                model_size=mesh.shape["model"])
    else:
        M.set_residual_sharding()
    spec = specs_mod.make_step_spec(cfg, shape, mesh, coding, fsdp=fsdp)
    if spec.kind == "train":
        optimizer = opt_mod.get_optimizer("adamw", 1e-4)
        # k=16 keeps every assigned config (incl. the 33B dense ones)
        # under the 16 GB v5e HBM budget; the collective term is
        # k-invariant (EXPERIMENTS.md #Perf iteration 3).
        n_micro = int(os.environ.get("REPRO_MICROBATCHES", "16"))
        fn = coded_train.make_train_step(cfg, optimizer,
                                         n_microbatches=n_micro)
    elif spec.kind == "prefill":
        fn = coded_train.make_prefill_step(cfg)
    else:
        fn = coded_train.make_serve_step(cfg, window=spec.window)
    return fn, spec


def param_bytes_per_device(spec, mesh) -> int:
    """Per-device parameter bytes of a StepSpec's placement (metadata
    only -- the FSDP-vs-replicated comparison the dry-run reports)."""
    return rules.bytes_per_device(spec.args[0], spec.in_shardings[0],
                                  mesh)


def specs_one(arch: str, shape_name: str, *, multi_pod: bool,
              fsdp: bool, verbose: bool = True) -> dict:
    """Spec-only dry-run: build the StepSpec (no lower/compile) and
    report the per-device parameter placement bytes. Cheap enough to
    run for every arch under both placements; the FSDP acceptance check
    in tests/test_system.py parses the DRYRUN_SPECS_JSON line."""
    cfg = get_config(arch)
    shape = {s.name: s for s in ALL_SHAPES}[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    coding = CodingConfig(replication=4)
    spec = specs_mod.make_step_spec(cfg, shape, mesh, coding, fsdp=fsdp)
    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "fsdp": fsdp, "status": "ok", "kind": spec.kind,
        "param_bytes_per_device": param_bytes_per_device(spec, mesh),
    }
    if verbose:
        print("DRYRUN_SPECS_JSON:" + json.dumps(result))
        sys.stdout.flush()
    return result


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool,
               fsdp: bool = False, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = {s.name: s for s in ALL_SHAPES}[shape_name]
    if shape.name == "long_500k":
        ok, why = specs_mod.long_500k_supported(cfg)
        if not ok:
            return {"arch": arch, "shape": shape_name,
                    "multi_pod": multi_pod, "status": "skipped",
                    "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    coding = CodingConfig(replication=4)
    fn, spec = build_step(cfg, shape, mesh, coding, fsdp=fsdp)
    t0 = time.time()
    with mesh:
        jitted = jax.jit(fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings)
        lowered = jitted.lower(*spec.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    stats = hlo_analysis.analyze(compiled.as_text())
    n_chips = mesh.devices.size
    model = rl_mod.model_flops(cfg, shape,
                               replication=coding.replication)
    rl = rl_mod.roofline_report(stats, n_chips, model)
    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "fsdp": fsdp,
        "status": "ok",
        "n_chips": int(n_chips),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "generated_code_bytes": int(mem.generated_code_size_in_bytes),
            "param_bytes_per_device": param_bytes_per_device(spec, mesh),
        },
        "model": model,
        "roofline": rl,
        "xla_cost_analysis_uncorrected": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
    }
    if verbose:
        mb = 1024 ** 2
        print(f"[{arch} x {shape_name} x "
              f"{'2x16x16' if multi_pod else '16x16'}] "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
              f"args {mem.argument_size_in_bytes/mb:.0f}MB "
              f"temp {mem.temp_size_in_bytes/mb:.0f}MB | "
              f"Tc {rl['t_compute_s']*1e3:.1f}ms Tm "
              f"{rl['t_memory_s']*1e3:.1f}ms Tx "
              f"{rl['t_collective_s']*1e3:.1f}ms -> {rl['dominant']} | "
              f"useful {rl['useful_flops_ratio']:.2f}")
        sys.stdout.flush()
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard params/opt-state over the worker axes "
                         "(rules.fsdp_specs) instead of replicating")
    ap.add_argument("--specs-only", action="store_true",
                    help="build StepSpecs and report per-device param "
                         "bytes without lowering/compiling")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = ARCH_IDS if args.all or args.arch is None else (args.arch,)
    shapes = [s.name for s in ALL_SHAPES] if args.all or \
        args.shape is None else [args.shape]
    pods = {"single": (False,), "multi": (True,),
            "both": (False, True)}[args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    if args.specs_only:
                        results.append(specs_one(arch, shape,
                                                 multi_pod=mp,
                                                 fsdp=args.fsdp))
                    else:
                        results.append(dryrun_one(arch, shape,
                                                  multi_pod=mp,
                                                  fsdp=args.fsdp))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "multi_pod": mp, "status": "error",
                                    "error": str(e)[:2000]})
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    tag = f"{'multi' if mp else 'single'}"
                    fn = os.path.join(
                        args.out, f"{arch}__{shape}__{tag}.json")
                    with open(fn, "w") as f:
                        json.dump(results[-1], f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"dry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)}")
    if n_err:
        sys.exit(1)


if __name__ == "__main__":
    main()
