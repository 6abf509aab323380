"""Readings that the limits of ``bench/limits`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control NAME] [--seconds 3] [--out FILE]

Runs the cell once per seed in one process, each run as the benchmark
runs it (set-up, a short window, the check), and prints one JSON line per
seed with the numbers compared and ``correct``. With ``--control`` every
run puts that control in the program's place (``bench.control``:
``direct-bf16``, ``direct-f32``, ``m2l-high`` or ``m2l-bf16``) and goes
through the same check, so a control that the limits refuse reads
``correct: false``. An ``m2l-*`` control changes the programs traced, so
it runs in a process of its own. The benchmark's own runs never run a
control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.Cell.load(harness.ROOT, args.workload)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            result = harness.run_cell(cell, seed, args.seconds, False,
                                      control=args.control)
            line = json.dumps({"workload": cell.name, "seed": seed,
                               "control": args.control,
                               "correct": result["correct"],
                               "checks": result["checks"],
                               "metrics": result["metrics"],
                               "attempted": result["attempted"],
                               "memory_peak_bytes":
                                   result["device"].get("memory_peak_bytes")})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
