"""Smoke run of EMSNet serving on one TPU chip, end to end.

    python chip_smoke.py              # one chip: phases 1-7 below
    python chip_smoke.py --chips 4    # four chips: the fleet phase only

Model: EMSNet TinyBERT-GRU-FC at the paper's widths (text 4 x 312, 12
heads, FFN 1200, 64 tokens; GRU vitals, hidden 64, 30 steps; FC scene,
hidden 16), random weights from ``--seed``, the text encoder on the
compiled Pallas flash kernel. Phases, one line each:

  1. device       platform, device kind, device count
  2. batched      16 async-lag sessions through build_engine
                  ("batch+stream", share_encoders) until all are final
  3. ragged       the same sessions with ragged=True (segment kernel)
  4. int8         one flush of the int8 sidecar encoders
  5. correctness  every final and int8 output against a float32
                  reference: the einsum path at "highest" precision
  6. compiled     tpu_custom_call in the compiled text encoders, and a
                  warm pass over the same shapes that compiles nothing
  7. timings      set-up/compile and warm-flush seconds (bring-up
                  observations, not benchmark numbers)

The last line is ``{"ok": true, "device": {...}}``, printed only when
every phase passed on a TPU. Without a TPU the script exits 2 at once;
``--cpu-rehearsal`` runs the phases on the CPU (kernels interpreted)
and still exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import zlib
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

N_SESSIONS = 16

# Tolerances against the float32 reference (einsum attention under
# default_matmul_precision("highest")).
# Float paths: on a TPU an f32 matmul at default precision takes one
# bf16 pass (8-bit mantissa, ~4e-3 relative error per product). The
# error runs through 4 transformer layers, 30 GRU steps and the heads.
# The logits here are O(1), so a budget of 1% of that scale, times a
# margin of 5 for the worst of 16 x 47 outputs, gives 5e-2 absolute.
LOGIT_ATOL = 5e-2
# An argmax may flip only where the reference's top two logits lie
# within 2 x LOGIT_ATOL of each other; every other row must agree.
ARGMAX_MARGIN = 2 * LOGIT_ATOL
# int8: per-channel weights and per-row activations quantized to 127
# levels. The bound is the one the CPU tests hold the int8 encoders to
# (tests/test_quantized.py): max |int8 - ref| / max |ref| < 8%, applied
# to each modality's feature and to the fused logits.
INT8_REL = 0.08

OUTPUT_KEYS = ("protocol_logits", "medicine_logits", "quantity")


def phase(name: str, msg: str) -> None:
    print(f"[{name}] {msg}", flush=True)


def fail(name: str, msg: str):
    print(f"[{name}] FAILED: {msg}", flush=True)
    sys.exit(1)


# ----------------------------------------------------------------- setup

def build(seed: int):
    from repro.configs.emsnet import config as emsnet_config
    from repro.launch.serve import build_zoo
    cfg = emsnet_config(text_encoder="tinybert", vocab_size=2048,
                        use_flash_text=True)
    splits, params = build_zoo(cfg, seed=seed)
    return cfg, splits, params


def episodes(n: int):
    from repro.core import LAG_SCENARIOS, async_episode
    names = sorted(LAG_SCENARIOS)
    return {f"s{i}": async_episode(names[i % len(names)], seed=i,
                                   n_vitals=4, n_scene=2)
            for i in range(n)}


def payload_fn(cfg, seed: int):
    """Deterministic payload per (session, event): text of 1..64 tokens,
    vitals series of 1..30 steps, a scene one-hot."""
    def fn(sid, ev):
        key = zlib.crc32(f"{seed}:{sid}:{ev.modality}:{ev.index}".encode())
        r = np.random.default_rng(key)
        if ev.modality == "text":
            n = int(r.integers(1, cfg.max_text_len + 1))
            return jnp.asarray(r.integers(1, cfg.vocab_size, (1, n)),
                               jnp.int32)
        if ev.modality == "vitals":
            n = int(r.integers(1, cfg.vitals_len + 1))
            return jnp.asarray(r.normal(size=(1, n, cfg.n_vitals)),
                               jnp.float32)
        return jnp.asarray(r.integers(0, 2, (1, cfg.scene_dim)), jnp.float32)
    return fn


def engine(cfg, splits, params, *, ragged=False):
    from repro.core import Bucketer
    from repro.serving.api import build_engine
    return build_engine(
        splits, params, "batch+stream", share_encoders=True,
        deadline_s=None, max_history=None,
        bucketer=Bucketer(max_buckets={"vitals": cfg.vitals_len,
                                       "text": cfg.max_text_len}),
        batch_bucket_min=8, ragged=ragged)


def serve(cfg, splits, params, eps, pay, *, ragged=False):
    eng = engine(cfg, splits, params, ragged=ragged)
    t0 = time.perf_counter()
    eng.run_arrivals(eps, pay, sim_window=0.5)
    jax.block_until_ready([p.outputs for st in eng.sessions.values()
                           for p in st.predictions])
    return eng, time.perf_counter() - t0


def finals(eng, eps):
    out = {}
    for sid in eps:
        st = eng.sessions.get(sid)
        fin = [p for p in (st.predictions if st else []) if p.kind == "final"]
        if not fin:
            return None, sid
        out[sid] = {k: np.asarray(fin[-1].outputs[k], np.float32).reshape(-1)
                    for k in OUTPUT_KEYS}
    return out, None


def stack_inputs(cfg, inputs):
    """Per-session natural-length inputs -> one padded batch in the
    masked forms the encoders take (PAD-suffixed text, length-masked
    vitals)."""
    B = len(inputs)
    text = np.zeros((B, cfg.max_text_len), np.int32)
    vx = np.zeros((B, cfg.vitals_len, cfg.n_vitals), np.float32)
    vlen = np.zeros((B,), np.int32)
    scene = np.zeros((B, cfg.scene_dim), np.float32)
    for i, inp in enumerate(inputs):
        t = np.asarray(inp["text"])[0]
        text[i, :t.shape[0]] = t
        v = np.asarray(inp["vitals"])[0]
        vx[i, :v.shape[0]] = v
        vlen[i] = v.shape[0]
        scene[i] = np.asarray(inp["scene"])[0]
    return {"text": jnp.asarray(text),
            "vitals": {"x": jnp.asarray(vx), "len": jnp.asarray(vlen)},
            "scene": jnp.asarray(scene)}


def reference(cfg, params, batch):
    """float32 reference: einsum attention, matmuls at "highest"."""
    from repro.models import emsnet as E
    ref_cfg = dataclasses.replace(cfg, use_flash_text=False)
    mods = ("text", "vitals", "scene")

    def fwd(p, b):
        feats = {m: E.encode(p, ref_cfg, m, b[m]) for m in mods}
        return feats, E.fuse_and_heads(p["heads"], feats, mods)

    with jax.default_matmul_precision("highest"):
        feats, outs = jax.jit(fwd)(params, batch)
    return ({m: np.asarray(f) for m, f in feats.items()},
            {k: np.asarray(v, np.float32).reshape(len(v), -1)
             for k, v in outs.items()})


def compare_finals(got, ref_out, sids):
    """max |Δ| over every output, and argmax agreement of the protocol
    and medicine heads; fails on a breach of the written tolerance."""
    worst = 0.0
    agree = total = 0
    for i, sid in enumerate(sids):
        for k in OUTPUT_KEYS:
            d = float(np.abs(got[sid][k] - ref_out[k][i]).max())
            worst = max(worst, d)
        for k in ("protocol_logits", "medicine_logits"):
            r = ref_out[k][i]
            top2 = np.sort(r)[-2:]
            same = int(np.argmax(got[sid][k])) == int(np.argmax(r))
            agree += same
            total += 1
            if not same and top2[1] - top2[0] > ARGMAX_MARGIN:
                fail("correctness", f"{sid} {k}: argmax differs with "
                                    f"reference margin "
                                    f"{top2[1] - top2[0]:.4f}")
    if not worst <= LOGIT_ATOL:
        fail("correctness", f"max |d| {worst:.6g} > tolerance {LOGIT_ATOL}")
    return worst, agree, total


def hlo_has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


# ------------------------------------------------------------ one chip

def run_one_chip(args, on_tpu: bool):
    cfg, splits, params = build(args.seed)
    shared = params["text+vitals+scene"]
    full = splits["text+vitals+scene"]
    eps = episodes(N_SESSIONS)
    pay = payload_fn(cfg, args.seed)
    xla_compiles = []

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            xla_compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)

    # 2. batched serving, cold: includes every compile
    eng, cold_s = serve(cfg, splits, params, eps, pay)
    got_batch, missing = finals(eng, eps)
    if got_batch is None:
        fail("batched", f"session {missing} has no final prediction")
    phase("batched", f"{len(got_batch)}/{N_SESSIONS} sessions final, "
                     f"{eng.events_total} arrivals, {eng.flushes_total} "
                     f"flushes, {eng.compile_count()} compiles")

    # 3. ragged serving: the segment-masked kernel over packed rows
    reng, _ = serve(cfg, splits, params, eps, pay, ragged=True)
    got_ragged, missing = finals(reng, eps)
    if got_ragged is None:
        fail("ragged", f"session {missing} has no final prediction")
    phase("ragged", f"{len(got_ragged)}/{N_SESSIONS} sessions final, "
                    f"{reng.flushes_total} flushes, "
                    f"{reng.ragged.n_shapes()} packed shapes")

    # 4. int8: one flush of the sidecar encoders over every session's
    # final inputs, then the (fp32) fused heads
    sids = sorted(eps)
    batch = stack_inputs(cfg, [eng.sessions[s].inputs for s in sids])
    qparams = full.quantize_params(shared)
    q_feats = {m: full.encoders[m](qparams, batch[m])
               for m in full.modalities()}
    q_out = full.tail(qparams, q_feats)
    jax.block_until_ready((q_feats, q_out))
    phase("int8", "features " + ", ".join(
        f"{m} {tuple(f.shape)}" for m, f in q_feats.items())
        + f"; logits {tuple(q_out['protocol_logits'].shape)}")

    # 5. correctness against the float32 reference
    ref_feats, ref_out = reference(cfg, shared, batch)
    b_worst, b_agree, n_arg = compare_finals(got_batch, ref_out, sids)
    r_worst, r_agree, _ = compare_finals(got_ragged, ref_out, sids)
    rel = {m: float(np.abs(np.asarray(q_feats[m]) - ref_feats[m]).max()
                    / (np.abs(ref_feats[m]).max() + 1e-9))
           for m in ref_feats}
    rel["logits"] = float(
        np.abs(np.asarray(q_out["protocol_logits"])
               - ref_out["protocol_logits"]).max()
        / (np.abs(ref_out["protocol_logits"]).max() + 1e-9))
    for k, v in rel.items():
        if not v < INT8_REL:
            fail("correctness", f"int8 {k}: relative error {v:.4f} "
                                f">= {INT8_REL}")
    phase("correctness",
          f"batched max|d| {b_worst:.3e} argmax {b_agree}/{n_arg}; "
          f"ragged max|d| {r_worst:.3e} argmax {r_agree}/{n_arg} "
          f"(atol {LOGIT_ATOL}); int8 rel "
          + ", ".join(f"{k} {v:.4f}" for k, v in rel.items())
          + f" (< {INT8_REL}); ref |logits| max "
          f"{np.abs(ref_out['protocol_logits']).max():.3f}")

    # 6. compiled, not interpreted; then a warm pass compiles nothing
    before = (eng.compile_count(), len(xla_compiles))
    weng, _ = serve(cfg, splits, params, eps, pay)
    after = (weng.compile_count(), len(xla_compiles))
    text_b = batch["text"]
    kernels = {
        "fp32": hlo_has_kernel(full.encoders["text"], shared, text_b),
        "int8": hlo_has_kernel(full.encoders["text"], qparams, text_b)}
    phase("compiled", f"tpu_custom_call in text encoder HLO: {kernels}; "
                      f"warm pass: {after[0] - before[0]} engine compiles, "
                      f"{after[1] - before[1]} XLA compiles")
    if on_tpu and not all(kernels.values()):
        fail("compiled", "a text encoder has no tpu_custom_call")
    if after != before:
        fail("compiled", "the warm pass compiled")

    # 7. timings (observations)
    warm = [f.wall_s for f in weng.flushes]
    phase("timings", f"cold pass (set-up + compile) {cold_s:.3f} s; warm "
                     f"flush mean {np.mean(warm) * 1e3:.3f} ms over "
                     f"{len(warm)} flushes")


# ---------------------------------------------------------- four chips

def run_fleet(args, devs):
    """4 replicas, one per chip, against the same workload on 1."""
    from repro.core import Bucketer
    from repro.fleet import RegionSim, generate_workload
    cfg, splits, params = build(args.seed)
    pay = payload_fn(cfg, args.seed)
    sessions = generate_workload(8.0, 4.0, seed=args.seed, time_scale=0.2)
    kw = dict(bucketer=Bucketer(max_buckets={"vitals": cfg.vitals_len,
                                             "text": cfg.max_text_len}),
              batch_bucket_min=8)
    sim4 = RegionSim(splits, params, n_replicas=4, engine_kw=dict(kw))
    rep4 = sim4.run(sessions, pay)
    sim1 = RegionSim(splits, params, n_replicas=1, engine_kw=dict(kw))
    rep1 = sim1.run(sessions, pay)
    served = [pr["sessions"] for pr in rep4["per_replica"]]
    if min(served) == 0:
        fail("fleet", f"a replica served no session: {served}")
    worst = 0.0
    used = set()
    for s in sessions:
        a, b = sim4.final_outputs(s.sid), sim1.final_outputs(s.sid)
        if a is None or b is None:
            fail("fleet", f"{s.sid} has no final")
        r = sim4.route_of[s.sid]
        # the finals are host rows; the session's cached features show
        # where its encoders ran, and the tail ran beside them
        feats = [e.feature for (key, _), e in sim4.replicas[r].cache.entries()
                 if key == s.sid]
        if not feats:
            fail("fleet", f"{s.sid} has no cached features on replica {r}")
        for f in feats:
            if f.devices() != {devs[r]}:
                fail("fleet", f"{s.sid} on replica {r}: features on "
                              f"{f.devices()}, not {devs[r]}")
            used |= f.devices()
        for k in OUTPUT_KEYS:
            worst = max(worst, float(np.abs(np.asarray(a[k])
                                            - np.asarray(b[k])).max()))
    if len(used) != 4:
        fail("fleet", f"the sessions' features sit on {len(used)} devices, "
                      "not 4")
    if not worst <= LOGIT_ATOL:
        fail("fleet", f"4- vs 1-replica finals: max |d| {worst:.6g} > "
                      f"{LOGIT_ATOL}")
    phase("fleet", f"{len(sessions)} sessions, all final on both; 4 "
                   f"replicas' features on {len(used)} distinct devices, "
                   f"serving {served} sessions; finals "
                   f"vs 1 replica max|d| {worst:.3e} (atol {LOGIT_ATOL}); "
                   f"flushes 4x {sum(p['flushes'] for p in rep4['per_replica'])}"
                   f" / 1x {rep1['per_replica'][0]['flushes']}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the fleet phase, one replica per chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the phases on the CPU; never reports ok")
    args = ap.parse_args()

    devs = jax.devices()
    d0 = devs[0]
    on_tpu = d0.platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print(f"no TPU: jax found {d0.platform}", file=sys.stderr)
        sys.exit(2)
    use_compile_cache()
    phase("device", f"platform {d0.platform}, kind {d0.device_kind}, "
                    f"count {len(devs)}")
    if args.chips == 4:
        if len(devs) < 4:
            fail("device", f"--chips 4 needs 4 devices, found {len(devs)}")
        run_fleet(args, devs)
    else:
        run_one_chip(args, on_tpu)
    if not on_tpu:
        print("cpu rehearsal: every phase ran; no result is reported",
              file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
