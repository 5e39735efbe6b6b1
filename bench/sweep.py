"""Knee sweep: one cell's traffic at a list of session rates, one process.

    python3 bench/sweep.py --workload tinybert.steady --rates 5,10,20 \
        --seconds 15 --seed 1

For each rate, a whole run of the cell (its own schedule, engine and
check) with the traffic file's rate replaced. Prints one JSON line per
rate: offered sessions/s and events/s, events completed per second in
the window, the latency tails, and the generator's lateness (submit -
due) p95 in each quarter of the window. A backlog that grows shows as
lateness rising from quarter to quarter. The benchmark's own runs do
not use this file.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench.configure_jax()
    for rate in [float(r) for r in args.rates.split(",")]:
        seen = {}

        def on_window(win):
            w0, w1 = win.bounds
            q = (w1 - w0) / 4
            sub = win.drive.submit
            quarters = []
            for i in range(4):
                lat = [sub[k] - t for k, t in win.due.items()
                       if w0 + i * q <= t < w0 + (i + 1) * q and k in sub]
                quarters.append(round(float(np.percentile(lat, 95)) * 1e3, 1)
                                if lat else None)
            seen["lateness_p95_ms_by_quarter"] = quarters
            seen["offered_events_per_s"] = len(win.window_arrivals()) / (w1 - w0)
            seen["flushes"] = sum(1 for t, _ in win.drive.flushes
                                  if win.inside(t))
            seen["drain_s"] = win.drive.drain_end - w1

        try:
            res = bench.run(args.workload, args.seed, args.seconds, False,
                            traffic_override={"rate_sessions_per_s": rate},
                            on_window=on_window)
        except Exception as e:  # noqa: BLE001 - report the rate, go on
            print(json.dumps({"rate": rate, "error": repr(e)[:300]}),
                  flush=True)
            continue
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({"rate": rate, **seen, **m,
                          "failed": res["failed"],
                          "checks": res["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
