"""Benchmark harness — one module per paper table/figure plus the
time-stepping refresh benchmark and the kernel-tile sweep. Prints
``name,us_per_call,derived`` CSV (see README) and writes a
machine-readable ``BENCH_<rev>.json`` next to it (per-row times +
config) so CI can archive the perf trajectory run over run.

    PYTHONPATH=src python -m benchmarks.run [--only table5_1 fig5_5 ...]
    PYTHONPATH=src python -m benchmarks.run --quick   (CI-sized inputs)
    PYTHONPATH=src python -m benchmarks.run --json out.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def _git_rev() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True,
                              check=True).stdout.strip()
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="smaller N (CI-friendly)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="output path for the machine-readable record "
                         "(default: BENCH_<rev>.json)")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache(os.path.join(os.path.dirname(__file__), ".."))

    from . import (accuracy, batched, fig5_2, fig5_3, fig5_5, fig5_8,
                   fmm_phases, guarded, kernel_tiles, serving, table5_1,
                   timestep)

    quick_kwargs = {
        "table5_1": {"n": 45 * 256},
        "fmm_phases": {"n": 45 * 256},
        "fig5_2": {"n": 1 << 13},
        "fig5_3": {"n": 1 << 12},
        "fig5_5": {},
        "fig5_8": {"n": 1 << 13},
        "accuracy": {"n": 2048},
        "batched": {"n": 1024, "batch": 4},
        "timestep": {"n": 2048, "steps": 3},
        "kernel_tiles": {"n": 1024, "repeats": 1},
        "guarded": {"n": 2048, "repeats": 2},
        "serving": {"n": 512, "num": 10, "median_n": 48},
    }
    benches = {
        "table5_1": table5_1.run,
        "fmm_phases": fmm_phases.run,
        "fig5_2": fig5_2.run,
        "fig5_3": fig5_3.run,
        "fig5_5": fig5_5.run,
        "fig5_8": fig5_8.run,
        "accuracy": accuracy.run,
        "batched": batched.run,
        "timestep": timestep.run,
        "kernel_tiles": kernel_tiles.run,
        "guarded": guarded.run,
        "serving": serving.run,
    }
    names = args.only or list(benches)
    print("name,us_per_call,derived")
    failed = []
    rows = []
    for name in names:
        try:
            kwargs = quick_kwargs.get(name, {}) if args.quick else {}
            for row in benches[name](**kwargs):
                label, us, derived = row
                print(f"{label},{us:.1f},{derived}")
                rows.append({"bench": name, "name": label,
                             "us_per_call": us, "derived": derived})
            sys.stdout.flush()
        except Exception:
            failed.append(name)
            print(f"{name},nan,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)

    import jax
    rev = _git_rev()
    record = {
        "rev": rev,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "quick": args.quick,
        "failed": failed,
        "results": rows,
    }
    path = args.json or f"BENCH_{rev}.json"
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {path}", file=sys.stderr)

    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
