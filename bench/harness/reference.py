"""The plain reference forward: the yardstick of ``correct``.

What is the same for every family: the inputs a session held, padded
into blocks of rows (``make_batch``), the cast to the compared
precision, the matmul precision, and the blocking that lets the largest
text block fit beside the weights. The equations are the family's
``reference_outputs`` (``bench/families``), written from the model's
equations and importing nothing of the program.

Inputs are at their natural lengths; a block of rows is padded to one
shape, and the family masks the padding out exactly.

``dtype=float32`` is the reference; ``dtype=bfloat16`` is the control,
the same equations one precision below the configuration's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MODALITIES = ("text", "vitals", "scene")
OUTPUTS = ("protocol_logits", "medicine_logits", "quantity")


@partial(jax.jit, static_argnames=("fn", "mkey", "dtype"))
def _forward_jit(params, batch, *, fn, mkey, dtype):
    m = dict(mkey)
    cast = lambda a: a.astype(dtype) if jnp.issubdtype(
        a.dtype, jnp.floating) else a
    out = fn(jax.tree.map(cast, params), m, jax.tree.map(cast, batch))
    return jax.tree.map(lambda a: a.astype(jnp.float32), out)


def make_batch(rows, m: dict, pad_to: int = 64) -> dict:
    """Host arrays for a block of rows. Each row is a dict of modality ->
    payload (the inputs a session held), absent modalities left out."""
    R = len(rows)
    longest = max([r["text"].shape[1] for r in rows if "text" in r] or [1])
    S = min(m["max_text_len"], -(-longest // pad_to) * pad_to)
    T = m["vitals_len"]
    tokens = np.zeros((R, S), np.int32)
    n_tok = np.ones((R,), np.int32)
    series = np.zeros((R, T, m["n_vitals"]), np.float32)
    n_read = np.zeros((R,), np.int32)
    scene = np.zeros((R, m["scene_dim"]), np.float32)
    present = np.zeros((R, len(MODALITIES)), bool)
    for i, r in enumerate(rows):
        if "text" in r:
            t = np.asarray(r["text"])[0, :S]
            tokens[i, :len(t)] = t
            n_tok[i] = len(t)
            present[i, 0] = True
        if "vitals" in r:
            v = np.asarray(r["vitals"])[0, -T:]
            series[i, :len(v)] = v
            n_read[i] = len(v)
            present[i, 1] = True
        if "scene" in r:
            scene[i] = np.asarray(r["scene"])[0]
            present[i, 2] = True
    return {"tokens": tokens, "n_tok": n_tok, "series": series,
            "n_read": n_read, "scene": scene, "present": present}


def forward(family, params, m: dict, rows, *, dtype=jnp.float32,
            precision: str = "highest", block: int = 32) -> dict:
    """The family's reference outputs for every row, on host, computed in
    blocks of ``block`` rows so that the largest text block fits next to
    the weights, with every matmul at ``precision``."""
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))
    outs = {k: [] for k in OUTPUTS}
    with jax.default_matmul_precision(precision):
        for i in range(0, len(rows), block):
            chunk = rows[i:i + block]
            batch = make_batch(chunk + [chunk[-1]] * (block - len(chunk)),
                               m)
            o = _forward_jit(params, batch, fn=family.reference_outputs,
                             mkey=mkey, dtype=jnp.dtype(dtype))
            for k in OUTPUTS:
                outs[k].append(np.asarray(o[k])[:len(chunk)])
    return {k: np.concatenate(v) for k, v in outs.items()}
