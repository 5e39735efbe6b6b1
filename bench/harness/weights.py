"""EMSNet weights made on the device from the seed, in one jitted call.

The pytree has the layout the program's encoders read (the program's
parameter format is its interface); the values are this file's own.
Every bias and norm parameter is random, not zero or one, so that a
program which drops one differs from the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int, tag: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    r = np.random.default_rng([int(seed) % (1 << 63), tag])
    return jax.random.PRNGKey(int(r.integers(0, 2**31 - 1)))


def _dense(key, d_in, d_out, bias=True):
    kw, kb = jax.random.split(key)
    p = {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32)
         / np.sqrt(d_in)}
    if bias:
        p["b"] = 0.02 * jax.random.normal(kb, (d_out,), jnp.float32)
    return p


def _norm(key, d):
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks, (d,), jnp.float32),
            "bias": 0.02 * jax.random.normal(kb, (d,), jnp.float32)}


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
            "ln": _norm(next(ks), d),
            "blocks": [{"ln1": _norm(next(ks), d),
                        "wqkv": _dense(next(ks), d, 3 * d),
                        "wo": _dense(next(ks), d, d),
                        "ln2": _norm(next(ks), d),
                        "w1": _dense(next(ks), d, ff),
                        "w2": _dense(next(ks), ff, d)} for _ in range(L)],
        },
        "vitals": {"wx": _dense(next(ks), m["n_vitals"], 3 * h),
                   "wh": _dense(next(ks), h, 3 * h, bias=False)},
        "scene": {"fc": _dense(next(ks), m["scene_dim"], m["scene_hidden"])},
        "heads": {"protocol": _dense(next(ks), fc, m["n_protocols"]),
                  "medicine": _dense(next(ks), fc, m["n_medicines"]),
                  "quantity": _dense(next(ks), fc, 1)},
    }


def make_params(model: dict, seed: int):
    """All weights of one configuration, float32, on the default device."""
    if model["vitals_encoder"] != "gru":
        raise ValueError("the harness makes GRU vitals weights only, got "
                         f"{model['vitals_encoder']!r}")
    fn = jax.jit(lambda k: _init(k, model))
    return jax.block_until_ready(fn(jax_key(seed, 0x3E16)))
