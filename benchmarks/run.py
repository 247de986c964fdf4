"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Each bench prints a CSV-ish table plus [validate] lines checking the
paper's qualitative claims at this scale. The dry-run roofline sweep is a
separate long-running step (python -m repro.launch.dryrun --all); its
artifacts are summarized by bench_roofline."""

from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = [
    ("engine_e2e", "benchmarks.bench_engine"),
    ("fig8_throughput", "benchmarks.bench_throughput"),
    ("fig9_10_scalability", "benchmarks.bench_scalability"),
    ("fig11_cache", "benchmarks.bench_cache"),
    ("fig12_updates", "benchmarks.bench_updates"),
    ("fig13_16_sensitivity", "benchmarks.bench_sensitivity"),
    ("fig17_21_workloads", "benchmarks.bench_workloads"),
    ("tab2_3_preprocessing", "benchmarks.bench_preprocessing"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweeps")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    failures = 0
    t_all = time.time()
    for name, mod_name in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"\n######## {name} ########")
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            mod.main(quick=args.quick)
            print(f"[{name}] done in {time.time() - t0:.1f}s")
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"[{name}] FAILED")
    print(f"\n== benchmarks done in {time.time() - t_all:.1f}s, "
          f"{failures} failures ==")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
