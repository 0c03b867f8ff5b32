import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
)

"""Multi-pod dry-run driver.

For every (architecture x input shape) cell, ``jit(step).lower(specs)
.compile()`` against the production meshes — 16x16 (single pod) and
2x16x16 (two pods, 512 chips) — and record ``memory_analysis()``,
``cost_analysis()`` and the per-device collective bytes parsed from the
partitioned HLO (the §Roofline inputs).

Usage:
    python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k [--multi-pod]
    python -m repro.launch.dryrun --all [--jobs 4]     # orchestrates subprocesses
    python -m repro.launch.dryrun --report             # prints the result table

Results land in results/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import subprocess
import sys
import time


RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def run_one(arch: str, shape: str, multi_pod: bool, out_path: str | None = None,
            mesh_shape: str | None = None, kv_quant: bool = False):
    import jax

    from repro.launch import roofline as rf
    from repro.launch.build import lower_cell
    from repro.launch.cells import Cell
    from repro.launch.mesh import make_production_mesh

    cell = Cell(arch, shape)
    mesh_name = mesh_shape or ("2x16x16" if multi_pod else "16x16")
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name}
    if cell.skipped:
        rec.update(status="skipped", reason=cell.skipped)
    else:
        if mesh_shape:  # supplementary meshes, e.g. "8x16x16" = 2048 chips
            dims = tuple(int(x) for x in mesh_shape.split("x"))
            axes = ("pod", "data", "model")[-len(dims):]
            mesh = jax.make_mesh(
                dims, axes,
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        nchips = mesh.size
        t0 = time.time()
        lowered, meta = lower_cell(arch, shape, mesh,
                                   overrides={"kv_quant": True} if kv_quant else None)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        roof = rf.analyze(compiled)
        print(mem)   # proves it fits (bytes per device)
        print({k: v for k, v in (compiled.cost_analysis() or {}).items()
               if k in ("flops", "bytes accessed")})
        rec.update(
            status="ok",
            n_chips=nchips,
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            n_params=meta["n_params"],
            n_active_params=meta["n_active_params"],
            tokens=meta.get("tokens"),
            recipe=meta.get("recipe"),
            memory=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                output_bytes=getattr(mem, "output_size_in_bytes", None),
                temp_bytes=getattr(mem, "temp_size_in_bytes", None),
                alias_bytes=getattr(mem, "alias_size_in_bytes", None),
            ),
            roofline=roof.as_dict(),
        )
        kind = meta["kind"]
        mf = (rf.model_flops_train if kind == "train" else rf.model_flops_infer)(
            meta["n_active_params"], meta.get("tokens") or 1
        )
        rec["model_flops"] = mf
        rec["useful_flops_frac"] = mf / max(roof.flops_per_dev * nchips, 1.0)
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    print(json.dumps({k: rec[k] for k in rec if k not in ("roofline",)}, indent=1))
    return rec


def orchestrate(jobs: int, only_missing: bool = True, meshes=("16x16", "2x16x16")):
    """Run every cell in its own subprocess (isolated jax state)."""
    from repro.launch.cells import all_cells

    tasks = []
    for cell in all_cells():
        for mesh in meshes:
            out = os.path.join(
                RESULTS_DIR, f"{cell.arch}__{cell.shape}__{mesh}.json"
            )
            if only_missing and os.path.exists(out):
                continue
            tasks.append((cell.arch, cell.shape, mesh, out))
    print(f"[dryrun] {len(tasks)} cells to run")
    procs: list = []
    failures = []

    def launch(t):
        arch, shape, mesh, out = t
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--out", out]
        if mesh == "2x16x16":
            cmd.append("--multi-pod")
        return (t, subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                    stderr=subprocess.STDOUT, text=True))

    pending = list(tasks)
    while pending or procs:
        while pending and len(procs) < jobs:
            procs.append(launch(pending.pop(0)))
        done = []
        for i, (t, p) in enumerate(procs):
            if p.poll() is not None:
                done.append(i)
                out = p.stdout.read()
                tag = f"{t[0]}/{t[1]}/{t[2]}"
                if p.returncode != 0:
                    failures.append((tag, out[-3000:]))
                    print(f"[dryrun] FAIL {tag}\n{out[-2000:]}")
                else:
                    print(f"[dryrun] ok   {tag}")
        for i in reversed(done):
            procs.pop(i)
        time.sleep(1)
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES")
        for tag, _ in failures:
            print("  ", tag)
        return 1
    print("[dryrun] all cells OK")
    return 0


def report():
    rows = []
    for fn in sorted(os.listdir(RESULTS_DIR)):
        if fn.endswith(".json"):
            rows.append(json.load(open(os.path.join(RESULTS_DIR, fn))))
    for r in rows:
        if r["status"] == "skipped":
            print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} SKIP ({r['reason'][:40]})")
        else:
            m = r["roofline"]
            mem = (r["memory"]["argument_bytes"] or 0) + (r["memory"]["temp_bytes"] or 0)
            print(
                f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
                f"mem/dev {mem/2**30:7.2f}GiB  "
                f"comp {m['compute_s']*1e3:9.3f}ms mem {m['memory_s']*1e3:9.3f}ms "
                f"coll {m['collective_s']*1e3:9.3f}ms  dom={m['dominant']}"
            )
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--mesh-shape", help="supplementary mesh, e.g. 8x16x16")
    ap.add_argument("--kv-quant", action="store_true", help="int8 KV cache")
    args = ap.parse_args()
    if args.report:
        sys.exit(report())
    if args.all:
        sys.exit(orchestrate(args.jobs, only_missing=not args.force))
    assert args.arch and args.shape
    out = args.out or os.path.join(
        RESULTS_DIR,
        f"{args.arch}__{args.shape}__"
        f"{args.mesh_shape or ('2x16x16' if args.multi_pod else '16x16')}.json",
    )
    run_one(args.arch, args.shape, args.multi_pod, out, mesh_shape=args.mesh_shape,
            kv_quant=args.kv_quant)


if __name__ == "__main__":
    main()
