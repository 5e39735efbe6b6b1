"""The system under test, built and driven as a deployment runs it.

``build`` makes the program's engine (``build_engine(..., "batch+stream",
share_encoders=True)`` over the 7-model subset zoo, as the program's
``launch/serve.py`` ``build_zoo`` assembles it) with the harness's
weights. ``warm`` runs every shape the cell's traffic can reach once.
``drive`` replays the schedule through the program's ``WallClockDriver``
on the engine's clock and records what the engine returned.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .gen import Schedule


def build_zoo(family, model: dict):
    """The 7 subset models over one parameter pytree (uncompiled), for
    the program's config that the configuration's family gives."""
    from repro.core import emsnet_zoo, split
    zoo = emsnet_zoo(family.program_config(model))
    return {k: split(m) for k, m in zoo.items()}


def build_engine(splits, params, serving: dict, *, tracer=None):
    from repro.core import Bucketer
    from repro.serving.api import build_engine as program_build_engine
    return program_build_engine(
        splits, {k: params for k in splits}, serving["spec"],
        share_encoders=serving["share_encoders"],
        deadline_s=serving["deadline_s"],
        bucketer=Bucketer(max_buckets=dict(serving["bucket_max"])),
        batch_bucket_min=serving["batch_bucket_min"],
        max_coalesce=serving["max_coalesce"], ragged=serving["ragged"],
        tracer=tracer)


def _lengths(spec: dict) -> range:
    return range(int(spec["min"]), int(spec["max"]) + 1)


def warm(eng, params, model: dict, traffic: dict, serving: dict,
         log=None) -> int:
    """Run once every program the cell's traffic can reach: the bucketer's
    pads for every natural length, the batch stacks for every row count
    up to ``max_coalesce``, each encoder at every (rows, length) bucket,
    every subset tail at every row bucket, and the row slices. Returns
    the number of calls made; ``log(msg)``, where given, hears how far
    each modality's part got and when."""
    import jax
    from repro.core.bucketing import next_pow2, stack_bucketed

    b = eng.bucketer
    rows_of = lambda k: max(serving["batch_bucket_min"], next_pow2(k))
    ks = range(1, serving["max_coalesce"] + 1)
    samples = {
        "text": [np.ones((1, n), np.int32)
                 for n in _lengths(traffic["text_len"])],
        "vitals": [np.ones((1, n, model["n_vitals"]), np.float32)
                   for n in _lengths(traffic["vitals_len"])],
        "scene": [np.ones((1, model["scene_dim"]), np.float32)],
    }
    calls, outs = 0, []
    feature_rows = {}
    t0 = time.perf_counter()
    for mod, xs in samples.items():
        runner = next(n for n, sm in eng.models.items()
                      if mod in sm.modalities())
        by_shape, host_shapes = {}, set()
        for x in xs:
            p = b.fit(mod, x)
            calls += 1
            lead = p["x"] if isinstance(p, dict) else p
            by_shape.setdefault(tuple(lead.shape), p)
            if isinstance(lead, np.ndarray) and lead.shape not in host_shapes:
                # an input already at its bucket length stays a host array,
                # and stacking converts it: a program of its own
                host_shapes.add(lead.shape)
                stack_bucketed([p], rows_of(1))
                calls += 1
        for p in by_shape.values():
            stacks = {}
            for k in ks:
                stacks[rows_of(k)] = stack_bucketed([p] * k, rows_of(k))
                calls += 1
            for stacked in stacks.values():
                feats = eng.models[runner].encoders[mod](params, stacked)
                outs.append(feats[0:1])
                outs.append(feats[1:2])
                calls += 3
                feature_rows[mod] = feats[0:1]
        if log is not None:
            jax.block_until_ready(outs)
            log(f"[warm] {mod} done: {calls} calls, "
                f"{time.perf_counter() - t0:.1f} s")
    for name, sm in eng.models.items():
        mods = sm.modalities()
        stacks = {}
        for k in ks:
            stacks[rows_of(k)] = {
                mm: stack_bucketed([feature_rows[mm]] * k, rows_of(k))
                for mm in mods}
            calls += len(mods)
        for stacked in stacks.values():
            o = sm.tail(params, stacked)
            outs.append(jax.tree.map(lambda a: a[1:2], o))
            calls += 2
    jax.block_until_ready(outs)
    if log is not None:
        log(f"[warm] tails done: {calls} calls, "
            f"{time.perf_counter() - t0:.1f} s")
    return calls


class _FirstReadClock:
    """The engine's clock, remembering its first reading:
    ``WallClockDriver.run`` reads it first to anchor the schedule."""

    def __init__(self, fn: Callable[[], float]):
        self.fn = fn
        self.t0: Optional[float] = None

    def __call__(self) -> float:
        t = self.fn()
        if self.t0 is None:
            self.t0 = t
        return t


@dataclass
class Drive:
    """What one replay of the schedule left behind, on the engine clock."""
    t_start: float                       # schedule time 0
    window: Tuple[float, float]          # measured window
    submit: Dict[Tuple[str, int], float] = field(default_factory=dict)
    # (engine clock when flush() was called, its FlushReport), in order
    flushes: List = field(default_factory=list)
    window_compiles: int = 0
    window_lowerings: int = 0
    window_programs: List[str] = field(default_factory=list)
    drain_end: float = 0.0


def drive(eng, schedule: Schedule, *, compile_counter=None, hooks=None) -> Drive:
    """Replay the schedule through ``WallClockDriver`` and drain.

    ``hooks`` may give ``before_run()``, ``on_window_open(t)``,
    ``on_window_close(t)``, ``sleep(s)`` and ``flush_scope()`` (a context
    manager around each flush); the traced run uses them to place
    profiler markers. The engine's ``flush`` is wrapped to keep
    every ``FlushReport`` (the engine itself keeps a bounded history)."""
    from repro.core.episodes import Event
    from repro.serving.event_loop import WallClockDriver

    hooks = hooks or {}
    clk = _FirstReadClock(eng.time_fn)
    lead, end = schedule.window
    rec = Drive(t_start=0.0, window=(0.0, 0.0))
    state = {"open": False, "closed": False}
    on_open = hooks.get("on_window_open")
    on_close = hooks.get("on_window_close")
    flush_scope = hooks.get("flush_scope")

    def mark_window(now):
        t0 = clk.t0
        if not state["open"] and now >= t0 + lead:
            state["open"] = True
            if compile_counter is not None:
                compile_counter.reset()
            if on_open is not None:
                on_open(now)
        if state["open"] and not state["closed"] and now >= t0 + end:
            state["closed"] = True
            if compile_counter is not None:
                rec.window_compiles = compile_counter.compiles
                rec.window_lowerings = compile_counter.lowerings
                rec.window_programs = list(compile_counter.names)
            if on_close is not None:
                on_close(now)

    program_flush = eng.flush     # bound method of the program's class

    def flush():
        now = eng.time_fn()
        mark_window(now)
        if flush_scope is None:
            report = program_flush()
        else:
            with flush_scope():
                report = program_flush()
        rec.flushes.append((now, report))
        return report

    eng.flush = flush

    def payload_fn(sid, ev):
        now = eng.time_fn()
        mark_window(now)
        rec.submit[(sid, ev.index)] = now
        return schedule.payloads[(sid, ev.index)]

    episodes = {sid: [Event(a.index, a.modality, a.t) for a in arr]
                for sid, arr in schedule.sessions.items()}
    driver = WallClockDriver(eng, clock=clk,
                             sleep_fn=hooks.get("sleep", time.sleep))
    if "before_run" in hooks:
        hooks["before_run"]()
    try:
        driver.run(episodes, payload_fn)
    finally:
        del eng.flush             # the class's own method again
    rec.drain_end = eng.time_fn()
    rest = clk.t0 + end - rec.drain_end
    if rest > 0:
        time.sleep(rest)
    mark_window(eng.time_fn())
    rec.t_start = clk.t0
    rec.window = (clk.t0 + lead, clk.t0 + end)
    return rec


class CompileCounter:
    """Counts programs built (lowered) and compiled by the backend, from
    JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.lowerings = 0
        self.total_compiles = 0
        self.names: List[str] = []        # programs built since reset()
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.total_compiles += 1
        elif name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1
            self.names.append(str(kw.get("fun_name")))

    def reset(self):
        self.compiles = 0
        self.lowerings = 0
        self.names = []
