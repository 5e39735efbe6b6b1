"""Roofline analysis from compiled dry-run artifacts.

Three terms (seconds), per device, from the peaks of the chip named by
its ``device_kind`` (``PEAKS``):
  compute    = HLO_FLOPs / peak_FLOPs
  memory     = HLO_bytes / HBM_bw
  collective = collective_bytes / link_bw

``cost_analysis()`` FLOPs/bytes on a post-SPMD module are already
per-device. Collective bytes are not in cost_analysis: we parse the
compiled HLO text and sum *result* buffer sizes of collective ops (these
shapes are per-device post-partitioning). All-reduce traffic is counted
twice (ring reduce-scatter + all-gather phases).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over 4
# links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; a kind not in ``PEAKS`` is an error."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Per-op-kind result-buffer bytes of collectives in (per-device) HLO."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(out, 0)
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_txt)
        counts[kind] += 1
    return out, counts


def weighted_collective_bytes(by_kind: dict) -> float:
    """Ring-algorithm traffic weights: AR moves ~2x its buffer."""
    w = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
         "all-to-all": 1.0, "collective-permute": 1.0}
    return sum(by_kind[k] * w[k] for k in by_kind)


@dataclass
class Roofline:
    flops: float                 # per device (loop-aware HLO dot count)
    hbm_bytes: float             # per device (analytic model)
    coll_bytes: float            # per device (weighted, loop-aware HLO)
    device_kind: str             # key into PEAKS
    model_flops: float = 0.0     # 6*N*D (useful compute, global)
    chips: int = 256
    hbm_bytes_hlo: float = 0.0   # fusion-naive HLO upper bound (recorded)

    @property
    def t_compute(self):
        return self.flops / peaks(self.device_kind)["bf16_flops"]

    @property
    def t_memory(self):
        return self.hbm_bytes / peaks(self.device_kind)["hbm_bw"]

    @property
    def t_collective(self):
        return self.coll_bytes / peaks(self.device_kind)["link_bw"]

    @property
    def bottleneck(self):
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self):
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    def as_dict(self):
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "hbm_bytes_hlo_upper_bound": self.hbm_bytes_hlo,
        }


def model_flops(cfg, shape) -> float:
    """6*N*D (training) or 2*N*D (inference) with N = active params."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def analytic_hbm_bytes(cfg, shape, *, model_shards=16, data_shards=16,
                       pods=1, experts_2d=False) -> float:
    """First-principles per-device HBM traffic per step (the roofline
    memory term). The HLO-derived byte count is recorded alongside as an
    upper bound: CPU HLO fusion granularity counts scan-internal
    intermediates that live in VMEM on TPU.

    Model: each device streams its tensor-parallel weight slice
    (gathered over the FSDP axis, so the slice is W/model_shards) once
    per forward and once per backward pass per microbatch, plus gradient
    writes, plus activation traffic (remat: one write + two reads of
    layer I/O), plus decode-cache read/write."""
    dtype_b = 2.0                                  # bf16
    total = cfg.param_count()
    if experts_2d and cfg.n_experts:
        # routed experts sharded over data x model, rest over model
        routed = 0
        for specs, count in cfg.groups:
            for s in specs:
                if s.mlp == "moe":
                    routed += count * cfg.n_experts * 3 * cfg.d_model * cfg.moe_d_ff
        W = ((total - routed) / model_shards
             + routed / (model_shards * data_shards)) * dtype_b
    else:
        W = total * dtype_b / model_shards
    d = cfg.d_model
    L = cfg.n_layers
    dp = data_shards * pods

    if shape.kind == "train":
        M = max(cfg.train_microbatches, 1)
        tokens_local = shape.global_batch * shape.seq_len / dp
        act = tokens_local * d * dtype_b * L * 3.0     # write + 2 reads
        grads_opt = 3.0 * W * 2.0                      # f32 grads + opt I/O
        return M * 2.0 * W + grads_opt + act
    if shape.kind == "prefill":
        tokens_local = shape.global_batch * shape.seq_len / dp
        act = tokens_local * d * dtype_b * L * 2.0
        cache = _cache_bytes(cfg, shape, dp)
        return W + act + cache
    # decode: weights once + cache read/write
    return W + 2.0 * _cache_bytes(cfg, shape, dp)


def _cache_bytes(cfg, shape, dp) -> float:
    """Per-device decode-cache bytes for this arch family."""
    from repro.serving.kv_cache import cache_plan
    cache_len, _ = cache_plan(cfg, shape)
    B = shape.global_batch
    dtype_b = 2.0
    total = 0.0
    for specs, count in cfg.groups:
        for s in specs:
            if s.mixer == "attn":
                total += count * B * cache_len * cfg.kv_dim * 2 * dtype_b
            elif s.mixer == "mla":
                m = cfg.mla
                total += count * B * cache_len * (m.kv_lora_rank
                                                  + m.qk_rope_dim) * dtype_b
            elif s.mixer == "mamba":
                di = cfg.mamba.d_inner(cfg.d_model)
                total += count * B * di * (cfg.mamba.d_state * 4
                                           + cfg.mamba.d_conv * dtype_b)
            elif s.mixer == "rwkv6":
                hd = cfg.rwkv_head_dim
                total += count * B * (cfg.d_model // hd) * hd * hd * 4
    return total / dp


def analyze(cost: dict, hlo_text: str, cfg, shape, chips: int,
            device_kind: str, experts_2d: bool = False) -> Roofline:
    """Roofline terms from the compiled per-device HLO.

    Uses the loop-aware walker in ``hlo_analyzer`` (XLA's cost_analysis
    does not multiply through scan-derived while loops, undercounting
    every scanned-layer model by its layer count — the raw
    cost_analysis numbers are still recorded in the dry-run artifact
    for comparison)."""
    from . import hlo_analyzer as H
    t = H.analyze_hlo(hlo_text)
    pods = 2 if chips == 512 else 1
    return Roofline(
        flops=t.flops,
        hbm_bytes=analytic_hbm_bytes(cfg, shape, model_shards=16,
                                     data_shards=16, pods=pods,
                                     experts_2d=experts_2d),
        coll_bytes=weighted_collective_bytes(t.coll),
        device_kind=device_kind,
        model_flops=model_flops(cfg, shape),
        chips=chips,
        hbm_bytes_hlo=t.bytes,
    )
