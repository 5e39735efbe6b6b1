"""Readings that set the ``correct`` limit of a configuration.

    python3 bench/control.py --workload tinybert.steady --seeds 1,2,3 \
        --seconds 8

For each seed, in one process: a whole run of the cell with a short
window (the program's reading: the widest gap between its outputs and
the float32 reference over the run's sample), and the control's reading
on the same sample: the same reference computed in bfloat16, one
precision below the configuration's float32, in the program's place.
Prints one JSON line per seed. The benchmark's own runs do not use this
file.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax.numpy as jnp

import run as bench
from harness import check, reference


def _numbers(got, ref) -> dict:
    return {name: fn(got, ref) for name, fn in check.NUMBERS.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    bench.configure_jax()
    for seed in [int(s) for s in args.seeds.split(",")]:
        seen = {}

        def on_check(family, params, model, rows, ref, got):
            # the reference at the highest and at the default matmul
            # precision, the program and the bfloat16 control against each
            refs = {p: reference.forward(family, params, model, rows,
                                         precision=p)
                    for p in ("highest", "default")}
            low = reference.forward(family, params, model, rows,
                                    dtype=jnp.bfloat16, precision="default")
            for name, r in refs.items():
                seen[f"program_vs_{name}"] = _numbers(got, r)
                seen[f"control_vs_{name}"] = _numbers(low, r)
            seen["ref_abs_max"] = max(float(abs(v).max())
                                      for v in ref.values())
            seen["rows"] = len(rows)

        try:
            res = bench.run(args.workload, seed, args.seconds, False,
                            on_check=on_check)
        except Exception as e:  # noqa: BLE001 - report the seed, go on
            print(json.dumps({"seed": seed, "error": repr(e)[:300]}),
                  flush=True)
            continue
        c = res["checks"]
        print(json.dumps({"seed": seed, **seen, "attempted": res["attempted"],
                          "checks": c}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
