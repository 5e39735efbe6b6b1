"""EMSNet serving benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/``) and
``BENCHMARK.json``. The cell names a configuration file under
``bench/configs`` and a traffic file under ``bench/traffic``; per-layer
metrics are readers under ``bench/metrics``, each found by its name.

A run: checks for the chips the cell asks for (none found: exit 3, no
result), makes the weights on the device from the seed, builds the
program's engine, warms every shape the cell's traffic can reach, and
replays an open-loop schedule (a lead-in, then ``--seconds`` of measured
window) through the program's ``WallClockDriver``. Set-up (``setup_s``)
runs from process start to the first due arrival of the window. After
the drain it reads peak device memory, frees the engine, and compares a
sample of the window's predictions with the plain float32 reference.

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` are also the last lines of standard
error. ``--trace 1`` also records the program's spans and a profiler
trace of the run, and reports the per-layer metrics in place of the
end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".cache" / "jax"
OUT_DIR = BENCH / "out"

sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    model_file = json.loads((ROOT / config["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if applies(m)]
    per_layer = [m for m in spec["per_layer"] if applies(m)]
    return cell, model_file, traffic, e2e, per_layer


def configure_jax():
    """JAX's persistent compilation cache, inside the checkout, keeping
    every program however small or quick to compile."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < n:
        raise NoChip(f"need {n} accelerator chip(s); JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run(cell_name: str, seed: int, seconds: float, traced: bool, *,
        chips_check: bool = True, model_override: dict | None = None,
        serving_override: dict | None = None,
        traffic_override: dict | None = None, limits: dict | None = None,
        on_window=None, on_check=None) -> dict:
    """One run of one cell; returns the result object. The keyword
    arguments let a test drive every step but the look for a chip, at a
    size a CPU can hold, and let the tools beside this file (``sweep.py``,
    ``control.py``) read more of a run: ``on_window(win)`` after the
    drain, ``on_check(params, model, rows, ref, got)`` after the
    reference."""
    cell, model_file, traffic, e2e, per_layer = load_cell(cell_name)
    model = dict(model_file["model"], **(model_override or {}))
    serving = dict(model_file["serving"], **(serving_override or {}))
    traffic = dict(traffic, **(traffic_override or {}))

    import jax
    import numpy as np
    if chips_check:
        devs = require_chips(int(cell["chips"]))
    else:
        devs = jax.devices()
    dev = devs[0]

    from harness import check, gen, layers, reference, serve, trace, window
    from harness.peaks import peaks
    from harness.weights import make_params

    # a run on the CPU (the tests) is reduced against the v5e's peaks
    peak = peaks(dev.device_kind if chips_check else "TPU v5 lite")
    counter = serve.CompileCounter()
    params = make_params(model, seed)
    splits = serve.build_zoo(model)
    tracer = None
    if traced:
        from repro.obs import Tracer
        tracer = Tracer()
    eng = serve.build_engine(splits, params, serving, tracer=tracer)
    log(f"[setup] weights made, engine built at "
        f"{time.perf_counter() - T_PROCESS:.1f} s")
    n_warm = serve.warm(eng, params, model, traffic, serving, log=log)
    warm_compiles = counter.total_compiles
    schedule = gen.build_schedule(traffic, model, seed=seed,
                                  window_s=seconds)
    log(f"[setup] warm-up calls {n_warm}, backend compiles so far "
        f"{warm_compiles}; schedule: {len(schedule.sessions)} sessions, "
        f"{len(schedule.payloads)} arrivals over "
        f"{schedule.lead_in_s + seconds:.1f} s")

    # the harness's own objects (schedule, payloads, weights, programs)
    # are made; keep the collector from scanning them in the window
    gc.collect()
    gc.freeze()
    hooks = {}
    trace_dir = OUT_DIR / f"trace-{cell_name}-{seed}"
    if traced:
        from jax.profiler import TraceAnnotation
        shutil.rmtree(trace_dir, ignore_errors=True)

        def before_run():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

        def mark(name):
            def fn(_t):
                with TraceAnnotation(name):
                    pass
            return fn

        def sleep(s):
            with TraceAnnotation("bench.sleep"):
                time.sleep(s)

        hooks = {"before_run": before_run,
                 "on_window_open": mark(trace.OPEN),
                 "on_window_close": mark(trace.CLOSE),
                 "sleep": sleep,
                 "flush_scope": lambda: TraceAnnotation("bench.flush")}
    drv = serve.drive(eng, schedule, compile_counter=counter, hooks=hooks)
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    setup_s = drv.window[0] - T_PROCESS
    win = window.analyse(schedule, drv)
    e2e_all = window.end_to_end(win)
    n_failed = window.failed(win)
    attempted = len(win.window_arrivals())
    late = win.lateness()
    log(f"[window] {attempted} arrivals due, {len(win.window_sessions())} "
        f"sessions start, {len(drv.flushes)} flushes in the run; "
        f"programs built in the window {drv.window_lowerings}, backend "
        f"compiles {drv.window_compiles} {drv.window_programs[:8]}")
    log("[window] generator lateness (submit - due) p50 "
        f"{_ms(np.percentile(late, 50) if late else None)} ms, p95 "
        f"{_ms(np.percentile(late, 95) if late else None)} ms")
    log("[window] latencies: "
        + ", ".join(f"{k} {v}" for k, v in e2e_all.items()))

    if on_window is not None:
        on_window(win)

    ctx = layers.Context(win=win, model=model, peaks=peak,
                         spans=list(tracer.events) if tracer else [])
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    # the check: outputs to host, the engine freed, then the reference
    sample = check.draw_sample(
        [p for p in win.predictions if win.inside(p["t_emit"])], schedule,
        seed)
    for p in sample:
        p["outputs"] = {k: np.asarray(v) for k, v in p["outputs"].items()}
    rows, subsets = check.expected_rows(sample, schedule)
    wrong_subset = sum(1 for p, s in zip(sample, subsets)
                       if tuple(p["modalities"]) != s)
    del eng, drv, splits, tracer, win.predictions[:]
    for p in ctx.win.drive.flushes:
        p[1].predictions.clear()
        p[1].recommendations.clear()
    gc.collect()
    rule = model_file["correct"]
    t_ref = time.perf_counter()
    ref = reference.forward(params, model, rows,
                            precision=rule["reference_precision"])
    got = check.stack_outputs(sample) if sample else None
    checks = check.judge(got, ref, limits or rule["limits"])
    t_ref = time.perf_counter() - t_ref
    if on_check is not None:
        on_check(params, model, rows, ref, got)
    checks["wrong_subset"] = {"value": wrong_subset, "limit": 0}
    checks["failed"] = {"value": n_failed, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    metrics = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    if traced:
        ctx.trace = trace.load(trace_dir)
        (OUT_DIR / f"trace-{cell_name}-{seed}.summary.json").write_text(
            json.dumps(trace.summary(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s(ctx.trace)
        device["window_s"] = ctx.trace.window_s
        ops = sorted(trace.op_seconds(ctx.trace).items(), key=lambda kv: -kv[1])
        gaps = sorted(trace.idle_gaps(ctx.trace).items(), key=lambda kv: -kv[1])
        breakdown = {"device_ops": [list(kv) for kv in ops[:10]],
                     "idle_gaps": [list(kv) for kv in gaps[:10]]}
    else:
        values = dict(e2e_all, setup_s=setup_s)
        for m in e2e:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"[check] {len(sample)} predictions against the reference in "
        f"{t_ref:.3f} s")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": n_failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _ms(v):
    return None if v is None else round(float(v) * 1e3, 3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(OUT_DIR / "tpu_logs"))
    configure_jax()
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
