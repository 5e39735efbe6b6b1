"""Weights made from the seed: the key every family draws from, and the
draws of a dense layer and a LayerNorm.

Each family (``bench/families``) lays out its own pytree and makes it on
the device in one jitted call. Every bias and norm parameter is random,
not zero or one, so that a program which drops one differs from the
reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int, tag: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    r = np.random.default_rng([int(seed) % (1 << 63), tag])
    return jax.random.PRNGKey(int(r.integers(0, 2**31 - 1)))


def dense(key, d_in, d_out, bias=True):
    """A dense layer's ``w`` (d_in, d_out), scaled by 1/sqrt(d_in), and
    its bias ``b``."""
    kw, kb = jax.random.split(key)
    p = {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32)
         / np.sqrt(d_in)}
    if bias:
        p["b"] = 0.02 * jax.random.normal(kb, (d_out,), jnp.float32)
    return p


def norm(key, d):
    """A LayerNorm's ``scale`` about 1 and its ``bias`` about 0."""
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks, (d,), jnp.float32),
            "bias": 0.02 * jax.random.normal(kb, (d,), jnp.float32)}
