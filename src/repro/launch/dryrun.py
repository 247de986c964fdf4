import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory_analysis / cost_analysis, and dump the
roofline terms.

The three lines above MUST stay the first statements in this module: jax locks
the platform and device count at first init, and the dry-run needs 512
placeholder CPU devices to build the 2x16x16 mesh. Pinning the CPU platform
keeps this process, and the per-cell children `--all` spawns (they inherit
the environment), from loading the TPU library on a machine with a chip.
Nothing else in the repo sets these flags (smoke tests and benches see the
host's single device).

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--out DIR]
  python -m repro.launch.dryrun --list

--all spawns one subprocess per cell (isolates XLA state; a failing cell
cannot poison the rest) and writes one JSON per cell to --out
(default artifacts/dryrun)."""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback


def run_cell(arch_name: str, shape: str, mesh_kind: str, out_dir: str | None) -> dict:
    import jax

    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.analysis.roofline import build_report, parse_collectives

    arch = get_arch(arch_name)
    cell = arch.cell(shape)
    mesh_name = "2x16x16" if mesh_kind == "multi" else "16x16"
    rec = {
        "arch": arch_name, "shape": shape, "mesh": mesh_name,
        "kind": cell.kind, "status": "?",
    }
    if cell.skip:
        rec["status"] = "skip"
        rec["reason"] = cell.skip
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.size

    def lower_compile(mode):
        t0 = time.time()
        spec = arch.build_dryrun(shape, mesh, mode=mode)
        kw = {"in_shardings": spec.in_shardings}
        if spec.out_shardings is not None:
            kw["out_shardings"] = spec.out_shardings
        if getattr(spec, "donate", ()):
            kw["donate_argnums"] = spec.donate
        with mesh:
            lowered = jax.jit(spec.fn, **kw).lower(*spec.args)
            t_lower = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t1
        return spec, compiled, t_lower, t_compile

    # memory mode: production config (microbatched, rolled scans) -> the
    # fits-in-HBM proof. flops mode: unrolled scans, no microbatch scan ->
    # exact per-step HLO flops + collective bytes (XLA's cost_analysis counts
    # a rolled loop body once). Families without loops reuse one compile.
    spec, compiled, t_lower, t_compile = lower_compile("memory")
    mem = compiled.memory_analysis()
    needs_flops_pass = mesh_kind == "single" and (
        (arch.family == "lm" and cell.kind in ("train", "prefill"))
        or (arch.family == "gnn" and spec.meta.get("distributed"))
    )
    seq = spec.meta.get("seq")
    if needs_flops_pass:
        # two-point depth extrapolation (exact: counts are linear in depth;
        # see configs/base.py) -- a 1-group and a 2-group module compile in
        # seconds where the 40-group unrolled module takes ~10 minutes
        from repro.analysis.roofline import build_report_extrapolated

        spec1, comp1, _, t1 = lower_compile("flops1")
        spec2, comp2, _, t2 = lower_compile("flops2")
        rec["t_compile_flops_s"] = round(t1 + t2, 2)
        rep = build_report_extrapolated(
            arch_name, shape, mesh_name, n_dev,
            comp1.cost_analysis(), comp1.as_text(),
            comp2.cost_analysis(), comp2.as_text(),
            groups=spec.meta["n_groups"], mem=mem,
            model_flops=spec.meta.get("model_flops", 0.0), pod_size=256,
            score_dims=(seq, seq) if seq else None,
        )
        cost = {"flops": rep.flops_per_device,
                "bytes accessed": rep.bytes_per_device}
    else:
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        rep = build_report(
            arch_name, shape, mesh_name, n_dev, cost, mem, hlo,
            model_flops=spec.meta.get("model_flops", 0.0),
            pod_size=256,
            score_dims=(seq, seq) if seq else None,
        )
    # donated (aliased) buffers update in place -- they are counted once
    per_dev_bytes = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                     - mem.alias_size_in_bytes)
    rec.update(
        status="ok",
        t_lower_s=round(t_lower, 2),
        t_compile_s=round(t_compile, 2),
        n_devices=n_dev,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "per_device_gb": round(per_dev_bytes / 2**30, 3),
            "fits_16gb_hbm": bool(per_dev_bytes < 16 * 2**30),
        },
        cost={k: v for k, v in cost.items() if "flops" in k or k == "bytes accessed"},
        roofline=rep.row(),
        meta=spec.meta,
    )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = f"{arch_name}__{shape}__{mesh_name}.json".replace("/", "_")
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose artifact already exists")
    args = ap.parse_args()

    from repro.configs import all_cells

    if args.list:
        for name, cell in all_cells():
            print(f"{name:18s} {cell.shape:16s} {cell.kind:10s} "
                  f"{'SKIP: ' + cell.skip if cell.skip else ''}")
        return 0

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        failures = 0
        for name, cell in all_cells():
            for mk in meshes:
                tag = f"{name} x {cell.shape} x {mk}"
                if cell.skip:
                    print(f"[dryrun] SKIP {tag}: {cell.skip}")
                    continue
                mesh_name = "2x16x16" if mk == "multi" else "16x16"
                art = os.path.join(
                    args.out, f"{name}__{cell.shape}__{mesh_name}.json")
                if args.resume and os.path.exists(art):
                    print(f"[dryrun] HAVE {tag}")
                    continue
                t0 = time.time()
                p = subprocess.run(
                    [sys.executable, "-m", "repro.launch.dryrun",
                     "--arch", name, "--shape", cell.shape, "--mesh", mk,
                     "--out", args.out],
                    capture_output=True, text=True, timeout=args.timeout,
                )
                dt = time.time() - t0
                if p.returncode == 0:
                    tail = p.stdout.strip().splitlines()
                    print(f"[dryrun] OK   {tag} ({dt:.0f}s) {tail[-1] if tail else ''}")
                else:
                    failures += 1
                    print(f"[dryrun] FAIL {tag} ({dt:.0f}s)")
                    print(p.stdout[-2000:])
                    print(p.stderr[-4000:])
        print(f"[dryrun] done, {failures} failures")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape or --all required"
    for mk in meshes:
        try:
            rec = run_cell(args.arch, args.shape, mk, args.out)
        except Exception:
            traceback.print_exc()
            return 1
        if rec["status"] == "skip":
            print(f"SKIP: {rec['reason']}")
            continue
        m = rec["memory"]
        r = rec["roofline"]
        print(json.dumps(rec, indent=1, default=str)[:2000])
        print(
            f"RESULT {rec['arch']} {rec['shape']} {rec['mesh']}: "
            f"mem/dev={m['per_device_gb']}GB fits={m['fits_16gb_hbm']} "
            f"bottleneck={r['bottleneck']} "
            f"t=(c {r['t_compute_s']:.2e}, m {r['t_memory_s']:.2e}, "
            f"x {r['t_collective_s']:.2e})s "
            f"roofline_frac={r['roofline_fraction']:.3f}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
