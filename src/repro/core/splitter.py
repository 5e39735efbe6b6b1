"""Modality-aware model splitter (EMSServe §4.2.1).

Decomposes a MultimodalModule into independently-jitted single-modality
callables plus a fused tail. In the PyTorch original this is an offline
graph-surgery step on module objects; in JAX the split boundary is a
pytree of features, so each piece is its own XLA program — which is
exactly what lets EMSServe (a) run one modality the moment it arrives,
(b) cache its output feature, and (c) place each piece on a different
tier.

``split`` also returns the monolithic jitted forward — the "direct
PyTorch" baseline the paper compares against.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict

import jax

from .modular import MultimodalModule


@dataclass
class SplitModel:
    module: MultimodalModule
    encoders: Dict[str, Callable]     # jitted per-modality: (params, x) -> feature
    tail: Callable                    # jitted: (params, feats) -> outputs
    full: Callable                    # jitted monolithic forward (baseline)

    def modalities(self):
        return self.module.modalities

    def submodules(self):
        """The independently *placeable* pieces of this model, in the
        naming the profiling/placement layers address them by: one
        ``"enc:<modality>"`` per encoder plus the fused ``"tail"`` —
        the same keys :func:`profile` emits and
        ``core.offload.MultiTierPolicy`` places (each may land on a
        different hardware tier)."""
        return tuple(f"enc:{m}" for m in self.module.modalities) + ("tail",)

    def compile_count(self) -> int:
        """Total XLA compilations across this model's jitted callables —
        the number the shape bucketer bounds. Non-jitted splits report 0."""
        n = 0
        for fn in (*self.encoders.values(), self.tail, self.full):
            size = getattr(fn, "_cache_size", None)
            n += size() if callable(size) else 0
        return n

    def quantize_params(self, params):
        """Derive the int8 sidecar pytree the SAME jitted encoders
        accept (``layers.dense`` dispatches on the sidecar leaf form).
        Raises for modules without a quantized variant — a precision-
        enabled spec over such a model is a configuration error, not a
        silent fp32 fallback."""
        if self.module.quantize_fn is None:
            raise ValueError(
                f"model {self.module.name!r} declares no quantize_fn; "
                "it cannot serve an int8 precision tier")
        return self.module.quantize_fn(params)


def select_model(models: Dict[str, SplitModel], observed) -> str | None:
    """EMSServe's model-selection rule (paper §4.2): the model consuming
    the most modalities whose inputs have all been observed. Shared by
    the per-event, batched, and streaming engines so their
    recommendations agree.

    Ties (several models consuming the same number of observed
    modalities) break on the lexicographically greatest sorted modality
    tuple, then the model name — NOT on dict insertion order, so two
    engines built from differently-ordered zoos always pick the same
    model."""
    obs = set(observed)
    best, best_key = None, None
    for name, sm in models.items():
        mods = set(sm.modalities())
        if mods <= obs:
            key = (len(mods), tuple(sorted(mods)), name)
            if best_key is None or key > best_key:
                best, best_key = name, key
    return best


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: what jit calls the program it lowers
    (``jit_<name>``), so compile logs and the device trace's module
    line tell the pieces apart."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program


def split(module: MultimodalModule, *, jit: bool = True) -> SplitModel:
    """Each piece as its own program, named ``encode_<modality>``,
    ``tail_<subset>`` and ``full_<subset>`` (the subset's modalities
    joined by ``_``)."""
    wrap = jax.jit if jit else (lambda f: f)
    subset = "_".join(module.modalities)
    encoders = {m: wrap(_named(fn, f"encode_{m}"))
                for m, fn in module.encoder_fns.items()}
    tail = wrap(_named(module.tail_fn, f"tail_{subset}"))
    full = wrap(_named(module.full_fn(), f"full_{subset}"))
    return SplitModel(module=module, encoders=encoders, tail=tail, full=full)


def profile(split_model: SplitModel, params, sample_batch: dict,
            *, iters: int = 5) -> Dict[str, float]:
    """One-time offline inference-time profiling (EMSServe §4.2.2).

    Returns wall-seconds per submodule (and the monolithic model) on
    *this* host — the `t^e` column; tier tables derive `t^g` from it.
    """
    times = {}

    def bench(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)             # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    feats = {}
    for m in split_model.modalities():
        times[f"enc:{m}"] = bench(split_model.encoders[m], params, sample_batch[m])
        feats[m] = split_model.encoders[m](params, sample_batch[m])
    times["tail"] = bench(split_model.tail, params, feats)
    times["full"] = bench(split_model.full, params, sample_batch)
    return times


def payload_nbytes(tree) -> int:
    """Serialized size in bytes of a pytree of device/NumPy arrays:
    ``size * itemsize`` per array leaf, 8 bytes per scalar. THE one
    byte-sizing rule — the tier transport charges with it and the
    benchmarks report with it, so the two can never diverge."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if itemsize is not None and hasattr(leaf, "size"):
            total += int(leaf.size) * int(itemsize)
        else:
            total += 8
    return total


def feature_sizes(split_model: SplitModel, params,
                  sample_batch: dict) -> Dict[str, int]:
    """On-wire bytes of each modality's encoded feature (and the tail's
    head outputs under ``"outputs"``) for a representative batch — what
    the tiered runtime's downlink actually ships, sized from the real
    arrays by :func:`payload_nbytes` rather than guessed. Complements
    :func:`profile` the way the transport complements the profile-table
    clock."""
    feats = {m: split_model.encoders[m](params, sample_batch[m])
             for m in split_model.modalities()}
    sizes = {m: payload_nbytes(f) for m, f in feats.items()}
    sizes["outputs"] = payload_nbytes(split_model.tail(params, feats))
    return sizes
