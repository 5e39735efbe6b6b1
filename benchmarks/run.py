"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV. Default is quick mode (CPU
container-friendly); ``--full`` uses paper-scale settings.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig14,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ("fig8_latency", "fig14_cache_speedup", "fig15_offloading",
          "table3_accuracy", "table4_pmi", "table5_e2e", "serve_throughput",
          "stream_latency", "tiered_latency", "fleet_load", "kernels_bench",
          "roofline_report")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for suite in SUITES:
        if only and suite not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{suite}", fromlist=["run"])
            mod.run(quick=not args.full)
            print(f"# {suite}: done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append(suite)
            print(f"# {suite}: FAILED {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
