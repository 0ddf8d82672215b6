"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
  PYTHONPATH=src python -m benchmarks.run [--only query,ood,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache

SUITES = ("query", "pruning", "ood", "metrics", "construction", "updates",
          "hardware", "params", "stream", "adaptive", "serving",
          "robustness")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(SUITES))
    args, _ = ap.parse_known_args()
    enable_compile_cache()
    chosen = [s for s in args.only.split(",") if s] or list(SUITES)
    print("name,us_per_call,derived")
    t_all = time.perf_counter()
    failures = []
    for suite in chosen:
        mod = __import__(f"benchmarks.bench_{suite}", fromlist=["main"])
        t0 = time.perf_counter()
        try:
            mod.main()
            print(f"# suite {suite} done in {time.perf_counter()-t0:.1f}s",
                  flush=True)
        except Exception as e:
            failures.append(suite)
            traceback.print_exc()
            print(f"# suite {suite} FAILED: {e}", flush=True)
    print(f"# total {time.perf_counter()-t_all:.1f}s")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
