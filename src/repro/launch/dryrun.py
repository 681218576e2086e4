import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Fake CPU devices only, here and in every child it starts (they inherit
# the environment): the dry run must never take a chip from another
# process.
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run driver.

For every (architecture x input shape) cell, lower + compile the step on the
production mesh (single-pod 16x16 and multi-pod 2x16x16), print
memory_analysis / cost_analysis, and derive the roofline terms.

Run one cell:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k --mesh single
Run everything (per-cell subprocesses, results appended to a JSON file):
    PYTHONPATH=src python -m repro.launch.dryrun --all \
        --out results/dryrun.json

The XLA_FLAGS and JAX_PLATFORMS lines above MUST stay the first statements:
jax locks the platform and device count at first import.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, get_arch
from repro.launch.mesh import make_production_mesh, mesh_num_chips
from repro.launch import roofline as rl


def run_cell(arch_name: str, shape: str, multi_pod: bool, verbose: bool = True):
    arch = get_arch(arch_name)
    skip = arch.skip_reason(shape)
    mesh_name = "multi" if multi_pod else "single"
    base = {"arch": arch_name, "shape": shape, "mesh": mesh_name}
    if skip:
        return base | {"status": "skip", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_num_chips(mesh)
    t0 = time.time()
    spec = arch.build(shape, mesh)
    fn = jax.jit(
        spec.fn,
        in_shardings=spec.in_shardings,
        out_shardings=spec.out_shardings,
        donate_argnums=spec.donate_argnums,
    )
    lowered = fn.lower(*spec.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if verbose:
        print(f"== {arch_name} x {shape} on {mesh_name} ({chips} chips) ==")
        print("memory_analysis:", mem)
        print("cost_analysis flops:", cost.get("flops"),
              "bytes:", cost.get("bytes accessed"))

    roof = rl.analyze(
        compiled,
        chips,
        model_flops_total=spec.model_flops_total,
        flops_total=spec.flops_total,
        hbm_bytes_per_device=spec.hbm_bytes_per_device,
    )
    return base | {
        "status": "ok",
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "note": spec.note,
        "roofline": roof.as_dict(),
    }


def _run_all(out_path: str, meshes: list[str], only_arch: str | None = None):
    """Spawn one subprocess per cell (keeps compile memory bounded and one
    bad cell from killing the sweep)."""
    results = []
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}
    for arch_name in ARCH_NAMES:
        if only_arch and arch_name != only_arch:
            continue
        arch = get_arch(arch_name)
        for shape in arch.shapes():
            for mesh_name in meshes:
                key = (arch_name, shape, mesh_name)
                if key in done:
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch_name, "--shape", shape,
                    "--mesh", mesh_name, "--json",
                ]
                print(">>", " ".join(cmd), flush=True)
                t0 = time.time()
                proc = subprocess.run(
                    cmd, capture_output=True, text=True, timeout=3600
                )
                dt = time.time() - t0
                rec = None
                for line in reversed(proc.stdout.splitlines()):
                    if line.startswith("{"):
                        try:
                            rec = json.loads(line)
                            break
                        except json.JSONDecodeError:
                            continue
                if rec is None:
                    rec = {
                        "arch": arch_name, "shape": shape, "mesh": mesh_name,
                        "status": "error",
                        "error": proc.stderr[-2000:],
                        "wall_s": round(dt, 1),
                    }
                results.append(rec)
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)
                print(f"   -> {rec['status']} ({dt:.0f}s)", flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--json", action="store_true",
                    help="print a single JSON record on the last line")
    args = ap.parse_args()

    if args.all:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        meshes = ["single", "multi"]
        results = _run_all(args.out, meshes, only_arch=args.arch)
        ok = sum(r["status"] == "ok" for r in results)
        skip = sum(r["status"] == "skip" for r in results)
        err = sum(r["status"] == "error" for r in results)
        print(f"dry-run sweep: {ok} ok, {skip} skip, {err} error")
        sys.exit(1 if err else 0)

    try:
        rec = run_cell(
            args.arch, args.shape, args.mesh == "multi", verbose=not args.json
        )
    except Exception:
        traceback.print_exc()
        rec = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "status": "error", "error": traceback.format_exc()[-2000:],
        }
    print(json.dumps(rec))
    sys.exit(0 if rec["status"] in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
