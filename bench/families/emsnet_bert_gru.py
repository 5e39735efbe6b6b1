"""EMSNet with a BERT-class text encoder, a GRU vitals encoder and an FC
scene encoder: the family of ``emsnet-tinybert-gru`` and
``emsnet-bertbase-gru``.

A family module holds all that the benchmark knows of one architecture,
and a configuration file names its family under the key ``family``:

- ``make_params(model, seed)``: the weights, on the device, from the seed;
- ``program_config(model)``: the program's config for the file's sizes;
- ``reference_outputs(params, model, batch)``: the plain forward over one
  padded block from ``reference.make_batch``;
- ``encoder_flops``, ``head_flops``: useful work at natural lengths;
- ``kernel_work(kernel, n, model)``: the work of one text input of ``n``
  tokens in each Pallas kernel the family's program runs, by the
  kernel's op name in the trace.

The weights have the layout the program's encoders read (the program's
parameter format is its interface); the values are the harness's own.

The reference is written from the model's equations, not from the
program's code, and imports nothing of it. It follows EMSNet as this
repository defines it, which departs from the published BERT in four
ways, each matched here on purpose: pre-norm blocks with a final
LayerNorm (BERT is post-norm), LayerNorm epsilon 1e-5 (BERT 1e-12), the
tanh form of GELU (BERT uses erf), and a masked mean over the valid
tokens in place of the [CLS] token (no token-type embeddings). The GRU
is the original form (Cho et al. 2014): the candidate state reads
``r * h`` through the hidden weights, with no hidden bias. Padding is
masked out exactly (keys and pooling by the token count, GRU steps by
the reading count).

Work is counted from what was served (token and reading counts before
any bucketing or packing) and the configuration's widths, never from
padded shapes, so that a change to bucketing cannot make the count
stale. A multiply-add is two operations.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from harness.reference import MODALITIES
from harness.weights import dense, jax_key, norm


# -- weights -----------------------------------------------------------

def _init(key, m: dict):
    L, d, ff = m["text_layers"], m["text_hidden"], m["text_ffn"]
    h = m["vitals_hidden"]
    ks = iter(jax.random.split(key, 16 + 6 * L))
    fc = d + h + m["scene_hidden"]
    return {
        "text": {
            "tok": {"emb": 0.02 * jax.random.normal(
                next(ks), (m["vocab_size"], d), jnp.float32)},
            "pos": {"emb": 0.02 * jax.random.normal(
                next(ks), (m["max_text_len"], d), jnp.float32)},
            "ln": norm(next(ks), d),
            "blocks": [{"ln1": norm(next(ks), d),
                        "wqkv": dense(next(ks), d, 3 * d),
                        "wo": dense(next(ks), d, d),
                        "ln2": norm(next(ks), d),
                        "w1": dense(next(ks), d, ff),
                        "w2": dense(next(ks), ff, d)} for _ in range(L)],
        },
        "vitals": {"wx": dense(next(ks), m["n_vitals"], 3 * h),
                   "wh": dense(next(ks), h, 3 * h, bias=False)},
        "scene": {"fc": dense(next(ks), m["scene_dim"], m["scene_hidden"])},
        "heads": {"protocol": dense(next(ks), fc, m["n_protocols"]),
                  "medicine": dense(next(ks), fc, m["n_medicines"]),
                  "quantity": dense(next(ks), fc, 1)},
    }


def make_params(model: dict, seed: int):
    """All weights of one configuration, float32, on the default device."""
    if model["vitals_encoder"] != "gru":
        raise ValueError("this family makes GRU vitals weights only, got "
                         f"{model['vitals_encoder']!r}")
    fn = jax.jit(lambda k: _init(k, model))
    return jax.block_until_ready(fn(jax_key(seed, 0x3E16)))


# -- the program's config ------------------------------------------------

def program_config(model: dict):
    """The program's ``EMSNetConfig`` for a configuration file's sizes;
    refuses a file whose widths the program's text-encoder table would
    not give."""
    from repro.configs.emsnet import EMSNetConfig
    cfg = EMSNetConfig(
        text_encoder=model["text_encoder"], vocab_size=model["vocab_size"],
        max_text_len=model["max_text_len"],
        vitals_encoder=model["vitals_encoder"], n_vitals=model["n_vitals"],
        vitals_len=model["vitals_len"], vitals_hidden=model["vitals_hidden"],
        scene_dim=model["scene_dim"], scene_hidden=model["scene_hidden"],
        n_protocols=model["n_protocols"], n_medicines=model["n_medicines"],
        dtype=model["dtype"], use_flash_text=model["use_flash_text"],
        flash_block=model["flash_block"])
    want = (model["text_layers"], model["text_hidden"], model["text_heads"],
            model["text_ffn"])
    if tuple(cfg.text_dims) != want:
        raise ValueError(f"the program's {model['text_encoder']!r} text "
                         f"encoder is {cfg.text_dims}, the file says {want}")
    return cfg


# -- the reference -------------------------------------------------------

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


def reference_outputs(params, m: dict, batch: dict) -> dict:
    """``reference.OUTPUTS`` for one padded block of rows."""
    f = {"text": text_features(params["text"], m, batch["tokens"],
                               batch["n_tok"]),
         "vitals": vitals_features(params["vitals"], m, batch["series"],
                                   batch["n_read"]),
         "scene": scene_features(params["scene"], batch["scene"])}
    return heads(params["heads"], m, f, batch["present"])


# -- work ----------------------------------------------------------------

def attention_flops(L: int, m: dict) -> float:
    """QK^T and PV of one text input of ``L`` tokens, all layers."""
    return m["text_layers"] * 4.0 * L * L * m["text_hidden"]


def attention_bytes(L: int, m: dict) -> float:
    """Q, K, V read and O written once per layer, float32."""
    return m["text_layers"] * 4.0 * L * m["text_hidden"] * 4.0


def text_flops(L: int, m: dict) -> float:
    """The text encoder on one input: projections, FFN and attention."""
    d, ff = m["text_hidden"], m["text_ffn"]
    per_token = 2.0 * (d * 3 * d + d * d + 2 * d * ff)
    return m["text_layers"] * per_token * L + attention_flops(L, m)


def gru_flops(n: int, m: dict) -> float:
    """A 3-gate GRU over ``n`` readings: input and hidden matmuls."""
    h = m["vitals_hidden"]
    return n * 3 * (2.0 * m["n_vitals"] * h + 2.0 * h * h)


def scene_flops(m: dict) -> float:
    return 2.0 * m["scene_dim"] * m["scene_hidden"]


FEATURE_WIDTH = {"text": "text_hidden", "vitals": "vitals_hidden",
                 "scene": "scene_hidden"}


def head_flops(modalities, m: dict) -> float:
    """The three heads over the fused features of ``modalities``."""
    fc = sum(m[FEATURE_WIDTH[x]] for x in modalities)
    return 2.0 * fc * (m["n_protocols"] + m["n_medicines"] + 1)


def encoder_flops(modality: str, n: int, m: dict) -> float:
    """One input of ``modality`` with natural length ``n``."""
    if modality == "text":
        return text_flops(n, m)
    if modality == "vitals":
        return gru_flops(n, m)
    return scene_flops(m)


def kernel_work(kernel: str, n: int, m: dict) -> Tuple[float, float]:
    """(operations, bytes) of one text input of ``n`` tokens in the Pallas
    kernel whose op name in the trace is ``kernel``; the flash kernel is
    the only one this family's program runs."""
    if kernel != "flash_attention":
        raise KeyError(f"this family's program runs no Pallas kernel "
                       f"{kernel!r}")
    return attention_flops(n, m), attention_bytes(n, m)
