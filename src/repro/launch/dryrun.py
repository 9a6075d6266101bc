import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes, record memory/cost analysis + roofline terms.

MUST be run as its own process (one cell per process is the default; the
--all driver spawns subprocesses) because jax locks the device count at
first init — hence the XLA_FLAGS assignment above, before any other
import.

Usage:
    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # every applicable cell
    python -m repro.launch.dryrun --all --multi-pod

Each cell writes results/dryrun/<mesh>/<arch>__<shape>.json with:
    memory_analysis, trip-count-aware HLO cost analysis (flops / bytes /
    collective bytes — see ``hlo_analysis``; XLA's own cost_analysis
    counts scan bodies once and is kept only as a cross-check), the three
    roofline terms, MODEL_FLOPS and the useful-compute ratio (§Roofline).
"""

import argparse
import json
import sys
import time
from typing import Optional

# TPU v5e hardware constants (assignment §Roofline)
PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "results/dryrun",
             guard_enabled: bool = True,
             policy_name: str = "bitwise",
             tag: str = "",
             moe_dispatch: str = "einsum",
             remat_policy: str = "nothing",
             kv_dtype: str = "bf16") -> Optional[dict]:
    import jax
    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.core.fence import FencePolicy
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_step

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        print(f"SKIP {arch} x {shape_name}: {why}")
        return None

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    kw = {}
    if shape.kind != "train":
        kw["kv_dtype"] = kv_dtype
    bundle = build_step(cfg, shape, mesh, guard_enabled=guard_enabled,
                        policy=FencePolicy(policy_name),
                        moe_dispatch=moe_dispatch,
                        remat_policy=remat_policy, **kw)
    jitted = jax.jit(bundle.fn,
                     in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)
    # the mesh context makes bare-PartitionSpec sharding constraints
    # (loop-carry pins inside flash attention etc.) bind to this mesh
    with mesh:
        lowered = jitted.lower(*bundle.in_specs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    from repro.launch.hlo_analysis import analyze_hlo

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    costs = analyze_hlo(hlo)

    flops_dev = float(costs.flops)
    bytes_dev = float(costs.bytes)
    coll_dev = float(costs.collective_bytes)
    coll = {"per_kind": {k: v for k, v in costs.collectives.items() if v},
            "counts": {k: v for k, v in costs.collective_counts.items()
                       if v},
            "total": coll_dev}

    compute_term = flops_dev / PEAK_FLOPS
    memory_term = bytes_dev / HBM_BW
    collective_term = coll_dev / ICI_BW
    terms = {"compute_s": compute_term, "memory_s": memory_term,
             "collective_s": collective_term}
    bottleneck = max(terms, key=terms.get)

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * n_active * tokens
    hlo_total_flops = flops_dev * chips
    useful_ratio = model_flops / hlo_total_flops if hlo_total_flops else 0.0
    # roofline fraction: useful model FLOPs per second achievable given the
    # dominant term, relative to pure-compute peak
    step_time = max(terms.values())
    mfu = (model_flops / chips / step_time) / PEAK_FLOPS if step_time else 0

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "guard": guard_enabled,
        "policy": policy_name,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_live_bytes_per_device":
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes,
        },
        "cost": {"flops_per_device": flops_dev,
                 "vector_flops_per_device": float(costs.vector_flops),
                 "bytes_per_device": bytes_dev,
                 "xla_flops_scan_body_once":
                     float(xla_cost.get("flops", 0.0)),
                 "xla_bytes_scan_body_once":
                     float(xla_cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "roofline": {
            **terms,
            "bottleneck": bottleneck.replace("_s", ""),
            "model_flops": model_flops,
            "hlo_total_flops": hlo_total_flops,
            "useful_ratio": useful_ratio,
            "roofline_fraction_mfu": mfu,
        },
        "params": {"total": n_params, "active": n_active},
    }

    os.makedirs(f"{out_dir}/{result['mesh']}", exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = f"{out_dir}/{result['mesh']}/{arch}__{shape_name}{suffix}.json"
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"OK   {arch:22s} x {shape_name:12s} mesh={result['mesh']} "
          f"compile={t_compile:6.1f}s flops/dev={flops_dev:.3e} "
          f"bottleneck={result['roofline']['bottleneck']} "
          f"mfu={mfu:.3f}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-guard", action="store_true",
                    help="native fast-path (no fence instructions)")
    ap.add_argument("--policy", default="bitwise",
                    choices=["bitwise", "modulo", "check", "none"])
    ap.add_argument("--tag", default="", help="suffix for the result file")
    ap.add_argument("--moe-dispatch", default="einsum",
                    choices=["einsum", "scatter"],
                    help="MoE dispatch impl (einsum=paper-simple baseline, "
                         "scatter=optimized)")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=["nothing", "dots"])
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "f32", "f8"])
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    if args.all:
        # one subprocess per cell: fresh jax, bounded memory
        import subprocess
        from repro.configs import SHAPES, get_config, list_archs, \
            shape_applicable
        failures = []
        for arch in list_archs():
            for shape_name in SHAPES:
                cfg = get_config(arch)
                ok, _ = shape_applicable(cfg, SHAPES[shape_name])
                if not ok:
                    continue
                mesh_tag = "2x16x16" if args.multi_pod else "16x16"
                suffix = f"__{args.tag}" if args.tag else ""
                path = (f"{args.out_dir}/{mesh_tag}/"
                        f"{arch}__{shape_name}{suffix}.json")
                if args.skip_done and os.path.exists(path):
                    print(f"SKIP (done) {arch} x {shape_name}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out-dir", args.out_dir]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                if args.no_guard:
                    cmd.append("--no-guard")
                if args.policy != "bitwise":
                    cmd += ["--policy", args.policy]
                if args.moe_dispatch != "einsum":
                    cmd += ["--moe-dispatch", args.moe_dispatch]
                if args.kv_dtype != "bf16":
                    cmd += ["--kv-dtype", args.kv_dtype]
                if args.remat_policy != "nothing":
                    cmd += ["--remat-policy", args.remat_policy]
                if args.tag:
                    cmd += ["--tag", args.tag]
                # the cell compiles for fake host devices: keep the child
                # off any accelerator this host has
                r = subprocess.run(cmd, env={**os.environ,
                                             "JAX_PLATFORMS": "cpu"})
                if r.returncode != 0:
                    failures.append((arch, shape_name))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("all cells OK")
        return

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    run_cell(args.arch, args.shape, args.multi_pod,
             out_dir=args.out_dir,
             guard_enabled=not args.no_guard,
             policy_name=args.policy, tag=args.tag,
             moe_dispatch=args.moe_dispatch,
             remat_policy=args.remat_policy,
             kv_dtype=args.kv_dtype)


if __name__ == "__main__":
    main()
