"""EMSNet: the paper's multimodal multitask model, in JAX.

Three backbone encoders (paper Table 1):
  * text:   TinyBERT / MobileBERT / BERTBase — bidirectional transformer
            over symptom-sentence tokens, masked mean-pooled to F_T.
  * vitals: RNN / LSTM / GRU over the (T, 6) time series -> F_V.
  * scene:  FC over the object-detection one-hot -> F_I.
Feature concatenation F_C = [F_T ; F_V ; F_I] (the fusion the paper
selected over dot-product/weighted-sum/attention), then three headers:
protocol (46-way), medicine type (18-way), quantity (regression).
Tasks 4/5 (dosage via med-math, disease history via dictionary) are
deterministic post-processing in ``repro.core.medmath``.

Every encoder is an independent pure function over its own parameter
subtree — exactly the property EMSServe's modality-aware splitter
exploits.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.emsnet import EMSNetConfig
from repro.kernels import ops
from . import layers as L


# ----------------------------------------------------------------------
# Text encoder (BERT-class, bidirectional)
# ----------------------------------------------------------------------

def _block_init(key, d, heads, ff):
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    return {
        "ln1": L.layernorm_init(d),
        "wqkv": L.dense_init(ks[0], d, 3 * d, bias=True),
        "wo": L.dense_init(ks[1], d, d, bias=True),
        "ln2": L.layernorm_init(d),
        "w1": L.dense_init(ks[2], d, ff, bias=True),
        "w2": L.dense_init(ks[3], ff, d, bias=True),
    }


def text_encoder_init(key, cfg: EMSNetConfig):
    n_layers, d, heads, ff = cfg.text_dims
    ks = jax.random.split(key, n_layers + 3)
    return {
        "tok": L.embedding_init(ks[0], cfg.vocab_size, d),
        "pos": L.embedding_init(ks[1], cfg.max_text_len, d),
        "ln": L.layernorm_init(d),
        "blocks": [_block_init(ks[2 + i], d, heads, ff) for i in range(n_layers)],
    }


def _bert_block(p, x, mask, heads, *, kv_lengths=None, segments=None):
    """``kv_lengths`` (B,) int32 routes attention through the Pallas
    flash kernel (key-padding-masked, non-causal); None keeps the
    materialized einsum path. Both see the same qkv/wo projections.

    ``segments=(seg_ids, use_flash, block)`` is the ragged layout:
    ``seg_ids`` (B, S) int32 gives each position's row id (-1 =
    padding); a query attends a key iff their ids match. With
    ``use_flash`` the segment-masked flash kernel runs at the fixed
    ``block`` size; otherwise a materialized pairwise mask feeds the
    einsum path."""
    B, S, d = x.shape
    hd = d // heads
    h = L.layernorm(p["ln1"], x)
    qkv = L.dense(p["wqkv"], h).reshape(B, S, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if segments is not None:
        seg, use_flash, block = segments
        if use_flash:
            att = ops.flash_attention(q, k, v, causal=False, segment_ids=seg,
                                      block_q=block,
                                      block_k=block).reshape(B, S, d)
        else:
            pair = (seg[:, :, None] == seg[:, None, :]) & (seg >= 0)[:, None, :]
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            s = jnp.where(pair[:, None], s, -1e30)
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
            att = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, d)
    elif kv_lengths is not None:
        att = ops.flash_attention(q, k, v, causal=False,
                                  kv_lengths=kv_lengths).reshape(B, S, d)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, d)
    x = x + L.dense(p["wo"], att)
    h = L.layernorm(p["ln2"], x)
    x = x + L.dense(p["w2"], jax.nn.gelu(L.dense(p["w1"], h)))
    return x


def text_encoder(p, cfg: EMSNetConfig, tokens):
    """tokens: (B, S) int32, 0 = PAD, or a ragged payload dict from
    ``RaggedBatch.pack("text", ...)`` (keys tokens/row_ids/pos/offsets/
    lengths). Returns F_T (B, d_text) — for the ragged form, one feature
    row per packed row.

    The flash path assumes PAD-only suffixes (valid tokens first), which
    both the tokenizer layout and the bucketer's right-padding guarantee;
    the einsum and segment paths handle arbitrary masks. With
    ``cfg.flash_segments`` the natural path runs the segment-masked
    flash kernel at ``cfg.flash_block`` — the bit-parity reference for
    the ragged layout (same kernel, same block reduction shapes).
    """
    if isinstance(tokens, dict):
        return _text_encoder_ragged(p, cfg, tokens)
    _, d, heads, _ = cfg.text_dims
    if cfg.use_flash_text and cfg.flash_segments:
        # pad S to a flash_block multiple: every GEMM then has M >= block
        # like the packed layout (an M=1 row would lower to a
        # differently-accumulated matvec and break bit parity)
        b = cfg.flash_block
        Sp = -(-tokens.shape[1] // b) * b
        tokens = jnp.pad(tokens, ((0, 0), (0, Sp - tokens.shape[1])))
        mask = tokens > 0
        seg = jnp.where(mask, 0, -1).astype(jnp.int32)
        segments = (seg, True, b)
        pos = jnp.minimum(jnp.arange(Sp), cfg.max_text_len - 1)
        x = L.embed(p["tok"], tokens) + p["pos"]["emb"][pos][None]
        for blk in p["blocks"]:
            x = _bert_block(blk, x, mask, heads, segments=segments)
        x = L.layernorm(p["ln"], x)
        m = mask[..., None].astype(x.dtype)
        return (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    mask = tokens > 0
    S = tokens.shape[1]
    kv_lengths = (mask.sum(-1).astype(jnp.int32) if cfg.use_flash_text
                  else None)
    x = L.embed(p["tok"], tokens) + p["pos"]["emb"][None, :S]
    for blk in p["blocks"]:
        x = _bert_block(blk, x, mask, heads, kv_lengths=kv_lengths)
    x = L.layernorm(p["ln"], x)
    m = mask[..., None].astype(x.dtype)
    return (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)


def _text_encoder_ragged(p, cfg: EMSNetConfig, packed):
    """Concatenated ragged text: ONE call encodes every pending row.

    ``packed`` is ``RaggedBatch.pack("text", rows)``. Attention is
    segment-masked (row ids from the pack), the positional table is
    gathered at each position's within-row index, and pooling gathers a
    ``max_text_len`` window at each row's offset — masked mean over
    valid tokens exactly as the natural path. Gap/tail positions carry
    id -1: they are masked as keys in every block and excluded from
    pooling, so their (PAD-embedding) activations never reach a row's
    feature."""
    _, d, heads, _ = cfg.text_dims
    toks = packed["tokens"]                         # (1, T)
    seg = packed["row_ids"][None, :]                # (1, T)
    T = toks.shape[1]
    mask = seg >= 0
    segments = (seg, cfg.use_flash_text, cfg.flash_block)
    x = L.embed(p["tok"], toks) + p["pos"]["emb"][packed["pos"]][None]
    for blk in p["blocks"]:
        x = _bert_block(blk, x, mask, heads, segments=segments)
    x = L.layernorm(p["ln"], x)
    offsets, lengths = packed["offsets"], packed["lengths"]
    cap = min(cfg.max_text_len, T)
    idx = jnp.clip(offsets[:, None] + jnp.arange(cap)[None, :], 0, T - 1)
    xw = x[0][idx]                                  # (R, cap, d)
    tw = toks[0][idx]
    mw = ((jnp.arange(cap)[None, :] < lengths[:, None])
          & (tw > 0))[..., None].astype(x.dtype)
    return (xw * mw).sum(1) / jnp.maximum(mw.sum(1), 1.0)


# ----------------------------------------------------------------------
# Vitals encoder (RNN / LSTM / GRU)
# ----------------------------------------------------------------------

def vitals_encoder_init(key, cfg: EMSNetConfig):
    d_in, h = cfg.n_vitals, cfg.vitals_hidden
    ks = jax.random.split(key, 3)
    gates = {"rnn": 1, "gru": 3, "lstm": 4}[cfg.vitals_encoder]
    return {
        "wx": L.dense_init(ks[0], d_in, gates * h, bias=True),
        "wh": L.dense_init(ks[1], h, gates * h),
    }


def vitals_encoder(p, cfg: EMSNetConfig, vitals):
    """vitals: (B, T, n_vitals) float, a bucketed payload
    ``{"x": (B, T_b, n_vitals), "len": (B,) int32}`` (zero-padded to a
    length bucket), or a ragged payload from
    ``RaggedBatch.pack("vitals", ...)`` (keys x/reset/offsets/lengths —
    many series concatenated along time). Returns F_V (B, vitals_hidden);
    for the ragged form, one feature row per packed row.

    All three forms run ONE scan body: reset gate (zero the carry at a
    packed row's first step), valid gate (freeze the carry on padded
    steps), emit the hidden state. The natural path feeds constant
    all-false/all-true gates through ``optimization_barrier`` so XLA
    fuses the body identically across paths — that shared fusion is what
    makes the ragged final states bit-identical to per-row runs."""
    length = offsets = lengths = reset_in = None
    if isinstance(vitals, dict) and "offsets" in vitals:
        x = vitals["x"]
        reset_in = vitals["reset"]
        offsets, lengths = vitals["offsets"], vitals["lengths"]
    elif isinstance(vitals, dict):
        x, length = vitals["x"], vitals["len"]
    else:
        x = vitals
    B, T, _ = x.shape
    h = cfg.vitals_hidden
    kind = cfg.vitals_encoder
    x_proj = L.dense(p["wx"], x)                     # (B, T, gates*h)

    def rnn_step(hc, xt):
        hp = hc
        out = jnp.tanh(xt + hp @ p["wh"]["w"])
        return out, None

    def gru_step(hc, xt):
        hp = hc
        zr = xt + hp @ p["wh"]["w"]
        z = jax.nn.sigmoid(zr[:, :h])
        r = jax.nn.sigmoid(zr[:, h:2 * h])
        n = jnp.tanh(xt[:, 2 * h:] + (r * hp) @ p["wh"]["w"][:, 2 * h:])
        out = (1 - z) * n + z * hp
        return out, None

    def lstm_step(carry, xt):
        hp, cp = carry
        g = xt + hp @ p["wh"]["w"]
        i = jax.nn.sigmoid(g[:, :h])
        f = jax.nn.sigmoid(g[:, h:2 * h] + 1.0)
        o = jax.nn.sigmoid(g[:, 2 * h:3 * h])
        c = f * cp + i * jnp.tanh(g[:, 3 * h:])
        return (o * jnp.tanh(c), c), None

    xs = jnp.moveaxis(x_proj, 1, 0)                  # (T, B, gates*h)
    h0 = jnp.zeros((B, h), x.dtype)
    step = {"lstm": lstm_step, "gru": gru_step, "rnn": rnn_step}[kind]
    init = (h0, h0) if kind == "lstm" else h0

    if offsets is not None:
        # packed layout is B == 1; the carry crosses row boundaries but
        # the reset gate zeroes it at each row's first step
        reset = jnp.broadcast_to(reset_in, (T, B, 1))
        valid = jax.lax.optimization_barrier(jnp.ones((T, B, 1), bool))
    elif length is not None:
        reset = jax.lax.optimization_barrier(jnp.zeros((T, B, 1), bool))
        valid = (jax.lax.broadcasted_iota(jnp.int32, (T, B, 1), 0)
                 < length[None, :, None])            # (T, B, 1)
    else:
        reset = jax.lax.optimization_barrier(jnp.zeros((T, B, 1), bool))
        valid = jax.lax.optimization_barrier(jnp.ones((T, B, 1), bool))

    def body(carry, inp):
        xt, rt, vt = inp
        c1 = jax.tree.map(lambda c: jnp.where(rt, jnp.zeros_like(c), c), carry)
        new, _ = step(c1, xt)
        out = jax.tree.map(lambda n_, c_: jnp.where(vt, n_, c_), new, c1)
        return out, (out[0] if kind == "lstm" else out)

    carry, ys = jax.lax.scan(body, init, (xs, reset, valid))
    if offsets is not None:
        idx = jnp.clip(offsets + lengths - 1, 0, T - 1)
        hfin = ys[idx, 0]                            # (R, h)
        return jnp.where((lengths > 0)[:, None], hfin, jnp.zeros_like(hfin))
    return carry[0] if kind == "lstm" else carry


# ----------------------------------------------------------------------
# Scene encoder + headers
# ----------------------------------------------------------------------

def scene_encoder_init(key, cfg: EMSNetConfig):
    return {"fc": L.dense_init(key, cfg.scene_dim, cfg.scene_hidden, bias=True)}


def scene_encoder(p, cfg: EMSNetConfig, scene):
    """scene: (B, scene_dim) one-hot-ish floats. Returns F_I."""
    return jax.nn.relu(L.dense(p["fc"], scene))


def heads_init(key, cfg: EMSNetConfig, modalities):
    dims = cfg.feature_dims
    fc_dim = sum(dims[m] for m in modalities)
    ks = jax.random.split(key, 3)
    return {
        "protocol": L.dense_init(ks[0], fc_dim, cfg.n_protocols, bias=True),
        "medicine": L.dense_init(ks[1], fc_dim, cfg.n_medicines, bias=True),
        "quantity": L.dense_init(ks[2], fc_dim, 1, bias=True),
    }


def fuse_and_heads(p, features: dict, modalities):
    """Concatenate per-modality features (paper's fusion) and run headers."""
    fc = jnp.concatenate([features[m] for m in modalities], axis=-1)
    return {
        "protocol_logits": L.dense(p["protocol"], fc),
        "medicine_logits": L.dense(p["medicine"], fc),
        "quantity": L.dense(p["quantity"], fc)[..., 0],
    }


def slice_heads(heads, cfg: EMSNetConfig, all_modalities, subset):
    """Restrict full-fusion head params to a modality subset.

    Because fusion is concatenation followed by a dense layer, a head
    over the subset's features IS the full head with only the weight
    rows belonging to the subset's slice of F_C (biases unchanged).
    This is what lets one trained parameter set serve every partial-
    modality combination — no per-subset heads to train or store.
    """
    dims = cfg.feature_dims
    offs, off = {}, 0
    for m in all_modalities:
        offs[m] = off
        off += dims[m]
    subset = tuple(m for m in all_modalities if m in set(subset))

    def take(p):
        w = jnp.concatenate([p["w"][offs[m]:offs[m] + dims[m]]
                             for m in subset], axis=0)
        return {"w": w, **({"b": p["b"]} if "b" in p else {})}

    return {k: take(v) for k, v in heads.items()}


def partial_forward(params, cfg: EMSNetConfig, batch: dict, subset,
                    all_modalities=("text", "vitals", "scene")):
    """One-shot forward restricted to an observed-modality subset:
    encode only the subset, fuse through the sliced full heads. With
    ``subset == all_modalities`` this equals ``forward`` exactly (the
    row slices reassemble the full weight matrices)."""
    subset = tuple(m for m in all_modalities if m in set(subset))
    feats = {m: encode(params, cfg, m, batch[m]) for m in subset}
    ph = slice_heads(params["heads"], cfg, all_modalities, subset)
    return fuse_and_heads(ph, feats, subset)


# ----------------------------------------------------------------------
# Whole model
# ----------------------------------------------------------------------

ENCODERS = {
    "text": (text_encoder_init, text_encoder),
    "vitals": (vitals_encoder_init, vitals_encoder),
    "scene": (scene_encoder_init, scene_encoder),
}


def init_params(cfg: EMSNetConfig, key, modalities=("text", "vitals", "scene")):
    ks = jax.random.split(key, len(modalities) + 1)
    p = {m: ENCODERS[m][0](ks[i], cfg) for i, m in enumerate(modalities)}
    p["heads"] = heads_init(ks[-1], cfg, modalities)
    return p


def encode(params, cfg: EMSNetConfig, modality: str, inputs):
    return ENCODERS[modality][1](params[modality], cfg, inputs)


def forward(params, cfg: EMSNetConfig, batch: dict,
            modalities=("text", "vitals", "scene"), *, freeze=()):
    """Full multimodal forward. batch keys = modality names."""
    feats = {}
    for m in modalities:
        f = encode(params, cfg, m, batch[m])
        if m in freeze:
            f = jax.lax.stop_gradient(f)
        feats[m] = f
    return fuse_and_heads(params["heads"], feats, modalities)
