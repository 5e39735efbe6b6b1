"""Plain EMSNet forward in jax.numpy: the yardstick of ``correct``.

Written from the model's equations, not from the program's code, and
importing nothing of it. It follows EMSNet as this repository defines
it, which departs from the published BERT in four ways, each matched
here on purpose: pre-norm blocks with a final LayerNorm (BERT is
post-norm), LayerNorm epsilon 1e-5 (BERT 1e-12), the tanh form of GELU
(BERT uses erf), and a masked mean over the valid tokens in place of
the [CLS] token (no token-type embeddings). The GRU is the original
form (Cho et al. 2014): the candidate state reads ``r * h`` through the
hidden weights, with no hidden bias.

Inputs are at their natural lengths; a block of rows is padded to one
shape, and the padding is masked out exactly (keys and pooling by the
token count, GRU steps by the reading count).

``dtype=float32`` under "highest" matmul precision is the reference;
``dtype=bfloat16`` is the control, the same equations one precision
below the configuration's.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MODALITIES = ("text", "vitals", "scene")
OUTPUTS = ("protocol_logits", "medicine_logits", "quantity")


def _layernorm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _linear(p, x):
    y = x @ p["w"]
    return y + p["b"] if "b" in p else y


def text_features(p, m, tokens, n_tok):
    """tokens (R, S) int32 with valid tokens first; n_tok (R,) counts."""
    R, S = tokens.shape
    d, H = m["text_hidden"], m["text_heads"]
    hd = d // H
    valid = jnp.arange(S)[None, :] < n_tok[:, None]            # (R, S)
    x = p["tok"]["emb"][tokens] + p["pos"]["emb"][:S][None]
    for blk in p["blocks"]:
        h = _layernorm(blk["ln1"], x)
        q, k, v = jnp.split(_linear(blk["wqkv"], h), 3, axis=-1)
        q = q.reshape(R, S, H, hd)
        k = k.reshape(R, S, H, hd)
        v = v.reshape(R, S, H, hd)
        s = jnp.einsum("rqhd,rkhd->rhqk", q, k) / math.sqrt(hd)
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("rhqk,rkhd->rqhd", w, v).reshape(R, S, d)
        x = x + _linear(blk["wo"], att)
        h = _layernorm(blk["ln2"], x)
        x = x + _linear(blk["w2"], _gelu(_linear(blk["w1"], h)))
    x = _layernorm(p["ln"], x)
    mask = valid[..., None].astype(x.dtype)
    return (x * mask).sum(1) / mask.sum(1)


def vitals_features(p, m, series, n_read):
    """series (R, T, n_vitals); the GRU runs over the first n_read steps
    of each row and the state after the last of them is the feature."""
    R, T, _ = series.shape
    hsz = m["vitals_hidden"]
    wx, bx, wh = p["wx"]["w"], p["wx"]["b"], p["wh"]["w"]
    h = jnp.zeros((R, hsz), series.dtype)
    for t in range(T):
        gx = series[:, t] @ wx + bx                               # (R, 3h)
        z = jax.nn.sigmoid(gx[:, :hsz] + h @ wh[:, :hsz])
        r = jax.nn.sigmoid(gx[:, hsz:2 * hsz] + h @ wh[:, hsz:2 * hsz])
        cand = jnp.tanh(gx[:, 2 * hsz:] + (r * h) @ wh[:, 2 * hsz:])
        new = (1.0 - z) * cand + z * h
        h = jnp.where((t < n_read)[:, None], new, h)
    return h


def scene_features(p, scene):
    return jax.nn.relu(_linear(p["fc"], scene))


def heads(p, m, feats, present):
    """Fusion by concatenation, then the three heads, for each row's
    own modality subset: the head's weight rows of an absent modality
    are left out of that row's sum (biases are always added)."""
    widths = {"text": m["text_hidden"], "vitals": m["vitals_hidden"],
              "scene": m["scene_hidden"]}
    out = {}
    for name, key in (("protocol", "protocol_logits"),
                      ("medicine", "medicine_logits"),
                      ("quantity", "quantity")):
        w, b = p[name]["w"], p[name]["b"]
        acc, off = b[None, :], 0
        for i, mod in enumerate(MODALITIES):
            rows = w[off:off + widths[mod]]
            off += widths[mod]
            part = feats[mod] @ rows
            acc = acc + jnp.where(present[:, i:i + 1], part, 0.0)
        out[key] = acc[:, 0] if name == "quantity" else acc
    return out


def _forward(params, m, batch):
    f = {"text": text_features(params["text"], m, batch["tokens"],
                               batch["n_tok"]),
         "vitals": vitals_features(params["vitals"], m, batch["series"],
                                   batch["n_read"]),
         "scene": scene_features(params["scene"], batch["scene"])}
    return heads(params["heads"], m, f, batch["present"])


@partial(jax.jit, static_argnames=("mkey", "dtype"))
def _forward_jit(params, batch, *, mkey, dtype):
    m = dict(mkey)
    cast = lambda a: a.astype(dtype) if jnp.issubdtype(
        a.dtype, jnp.floating) else a
    out = _forward(jax.tree.map(cast, params), m, jax.tree.map(cast, batch))
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


def forward(params, m: dict, rows, *, dtype=jnp.float32,
            precision: str = "highest", block: int = 32) -> dict:
    """Outputs for every row, on host, computed in blocks of ``block``
    rows so that the largest text block fits next to the weights, with
    every matmul at ``precision``."""
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))
    outs = {k: [] for k in OUTPUTS}
    with jax.default_matmul_precision(precision):
        for i in range(0, len(rows), block):
            chunk = rows[i:i + block]
            batch = make_batch(chunk + [chunk[-1]] * (block - len(chunk)),
                               m)
            o = _forward_jit(params, batch, mkey=mkey,
                             dtype=jnp.dtype(dtype))
            for k in OUTPUTS:
                outs[k].append(np.asarray(o[k])[:len(chunk)])
    return {k: np.concatenate(v) for k, v in outs.items()}
