import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (jax locks
# the device count on first init), which is why the module docstring
# below is a plain string and `from __future__` is not used here.

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

For each combination this lowers the appropriate step function
(train_step / prefill_step / serve_step) with ShapeDtypeStruct inputs
carrying production NamedShardings, compiles it, and records
memory_analysis(), cost_analysis(), and the collective schedule parsed
from the compiled HLO into a JSON artifact under
``benchmarks/artifacts/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-nemo-12b \
      --shape decode_32k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.analysis import roofline as RL
from repro.configs import ARCHS, SHAPES, get_config
from repro.distributed.sharding import Policy
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs

# the chip the production meshes model (CPU-emulated devices here)
TARGET_KIND = "TPU v5 lite"
ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"


def run_one(arch: str, shape_name: str, mesh_kind: str, *, save=True,
            keep_hlo=False, tuned=False, strategy="2d"):
    import dataclasses
    cfg = get_config(arch)
    if strategy == "fsdp":
        # pure-FSDP shards batch over all intra-pod chips: one sample
        # per device at train_4k, so no microbatch accumulation
        cfg = dataclasses.replace(cfg, train_microbatches=1)
    shape = SHAPES[shape_name]
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.devices.size
    policy = Policy(cfg, mesh, tuned=tuned, strategy=strategy)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": chips, "tuned": tuned, "strategy": strategy, "ok": False}
    t0 = time.time()
    try:
        fn, args = input_specs(cfg, shape, policy)
        with mesh:
            lowered = jax.jit(fn).lower(*args)
            rec["t_lower_s"] = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            rec["t_compile_s"] = time.time() - t1
        mem = compiled.memory_analysis()
        if mem is not None:
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    rec[k] = int(v)
        cost = compiled.cost_analysis() or {}
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))}
        hlo = compiled.as_text()
        from repro.analysis.hlo_analyzer import analyze_hlo
        totals = analyze_hlo(hlo)
        rec["collectives_bytes"] = totals.coll
        rec["collectives_count"] = totals.coll_count
        roof = RL.analyze(rec["cost"], hlo, cfg, shape, chips,
                          TARGET_KIND,
                          experts_2d=tuned and policy.experts_2d)
        rec["roofline"] = roof.as_dict()
        rec["param_count"] = cfg.param_count()
        rec["active_param_count"] = cfg.active_param_count()
        rec["ok"] = True
        if keep_hlo:
            rec["hlo_lines"] = hlo.count("\n")
    except Exception as e:  # noqa: BLE001 — record failures as data
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["t_total_s"] = time.time() - t0
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        suffix = "_tuned" if tuned else ""
        if strategy != "2d":
            suffix += f"_{strategy}"
        out = ARTIFACTS / f"{arch}_{shape_name}_{mesh_kind}{suffix}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tuned", action="store_true",
                    help="apply the §Perf sharding changes (see "
                         "distributed.sharding.Policy)")
    ap.add_argument("--strategy", default="2d", choices=["2d", "fsdp"])
    args = ap.parse_args()

    archs = ARCHS if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_one(arch, shape, mk, tuned=args.tuned,
                              strategy=args.strategy)
                status = "OK" if rec["ok"] else "FAIL"
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
                extra = ""
                if rec["ok"]:
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" tc={r['t_compute_s']:.3g}s"
                             f" tm={r['t_memory_s']:.3g}s"
                             f" tcoll={r['t_collective_s']:.3g}s")
                else:
                    extra = " " + rec["error"][:200]
                print(f"[{status}] {arch} x {shape} x {mk}"
                      f" ({rec['t_total_s']:.1f}s){extra}", flush=True)
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
