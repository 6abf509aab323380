"""End-to-end serving demo: ragged, partially-poisoned traffic through
the serving plane (DESIGN.md §10).

Generates a log-normal request stream (every request a different N, a
configurable fraction poisoned), warms the plane's shape classes, and
serves wave after wave — printing a `ServeReport` line per request and
the plane's cumulative stats (per-bucket cache traffic, straggler
median, deadline misses) at the end. Nothing a request can contain
crashes the plane: it either returns a trustworthy phi or a typed
rejection.

    PYTHONPATH=src python examples/serve_traffic.py --num 24 \
        [--poison 0.2] [--deadline 30] [--median-n 128]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.compile_cache import use_compile_cache

use_compile_cache(".")

import numpy as np

from repro.data.synthetic import ragged_requests
from repro.serve import BucketLattice, Request, ServePlane


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num", type=int, default=24)
    ap.add_argument("--poison", type=float, default=0.2)
    ap.add_argument("--median-n", type=int, default=128)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline budget in seconds")
    ap.add_argument("--waves", type=int, default=2)
    args = ap.parse_args()

    lattice = BucketLattice.geometric(64, 1024)
    plane = ServePlane(lattice, max_batch=4, direct_max=4096,
                       default_deadline_s=args.deadline)
    print(f"lattice: {lattice.sizes}; warming shape classes ...")
    t0 = time.perf_counter()
    plane.warm(batches=(1, 4))
    print(f"warmed {len(plane.cache)} executables "
          f"in {time.perf_counter() - t0:.1f}s")

    for wave in range(args.waves):
        reqs = [Request(z, q) for _, z, q, _ in
                ragged_requests(args.num, seed=wave,
                                median_n=args.median_n, sigma=0.8,
                                n_max=2048, poison_rate=args.poison)]
        t0 = time.perf_counter()
        results = plane.serve(reqs)
        dt = time.perf_counter() - t0
        print(f"\nwave {wave}: {len(reqs)} requests in {dt:.2f}s "
              f"({len(reqs) / dt:.1f} req/s)")
        for phi, report in results:
            print(" ", report.summary())

    stats = plane.stats()
    print("\ncumulative:",
          {k: stats[k] for k in ("requests", "ok", "recovered",
                                 "degraded", "rejected", "dispatches",
                                 "slow_dispatches", "deadline_misses")})
    print("cache (per bucket):",
          {b: "hits={hits} misses={misses} evictions={evictions}".format(**s)
           for b, s in stats["cache"].items()})
    med = stats["dispatch_median_s"]
    if np.isfinite(med):
        print(f"dispatch median: {med * 1e3:.1f}ms")


if __name__ == "__main__":
    main()
