"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_raw
from repro.kernels.rwkv6 import rwkv6_scan as rwkv_raw

FA_SHAPES = [
    (2, 64, 64, 4, 4, 16),     # MHA
    (1, 96, 96, 4, 2, 32),     # GQA 2:1
    (2, 48, 128, 8, 2, 64),    # cross-ish Sq != Sk, GQA 4:1
    (1, 33, 65, 2, 1, 8),      # non-divisible by block (padding path)
]


@pytest.mark.parametrize("shape", FA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(key, shape, dtype):
    B, Sq, Sk, H, KV, D = shape
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, D), dtype)
    got = fa_raw(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    atol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=atol)


@pytest.mark.parametrize("window", [8, 32])
def test_flash_attention_window(key, window):
    q = jax.random.normal(key, (1, 64, 4, 16))
    got = fa_raw(q, q, q, causal=True, window=window, block_q=16, block_k=16,
                 interpret=True)
    want = ref.attention_ref(q, q, q, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_flash_attention_kv_lengths(key):
    """Per-row key-padding mask (length-bucketed batches): matches the
    oracle, including a zero-length row which must output exactly 0."""
    ks = jax.random.split(key, 3)
    B, Sq, Sk, H, KV, D = 4, 16, 24, 4, 2, 16
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, KV, D))
    v = jax.random.normal(ks[2], (B, Sk, KV, D))
    lens = jnp.array([24, 9, 1, 0], jnp.int32)
    got = fa_raw(q, k, v, causal=False, kv_lengths=lens,
                 block_q=8, block_k=8, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False, kv_lengths=lens)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(np.asarray(got[3])).max() == 0.0


def test_flash_attention_noncausal(key):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 16))
    k = jax.random.normal(ks[1], (2, 40, 4, 16))
    v = jax.random.normal(ks[2], (2, 40, 4, 16))
    got = fa_raw(q, k, v, causal=False, block_q=8, block_k=8, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_flash_vs_model_flash(key):
    """Pallas kernel and the model's jnp flash implement the same op."""
    from repro.models.attention import flash_attention_jnp
    q = jax.random.normal(key, (1, 64, 4, 16))
    a = fa_raw(q, q, q, causal=True, block_q=16, block_k=16, interpret=True)
    b = flash_attention_jnp(q, q, q, causal=True, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(a, b, atol=2e-5)


RWKV_SHAPES = [(2, 32, 2, 16), (1, 100, 4, 8), (2, 17, 1, 32), (1, 64, 8, 64)]


@pytest.mark.parametrize("shape", RWKV_SHAPES)
def test_rwkv6_kernel_sweep(key, shape):
    B, S, H, n = shape
    ks = jax.random.split(key, 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, n)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, n))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, n)) * 0.1
    y1, s1 = rwkv_raw(r, k, v, w, u, block_t=16, interpret=True)
    y2, s2 = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(y1, y2, atol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4)


def test_rwkv6_initial_state_chunked(key):
    """Chaining two kernel calls via the state equals one long call —
    the chunked-prefill contract."""
    B, S, H, n = 1, 48, 2, 16
    ks = jax.random.split(key, 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, n)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, n))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, n)) * 0.1
    y_full, s_full = rwkv_raw(r, k, v, w, u, block_t=8, interpret=True)
    h = S // 2
    y1, s1 = rwkv_raw(r[:, :h], k[:, :h], v[:, :h], w[:, :h], u,
                      block_t=8, interpret=True)
    y2, s2 = rwkv_raw(r[:, h:], k[:, h:], v[:, h:], w[:, h:], u, s0=s1,
                      block_t=8, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full, atol=1e-4)
    np.testing.assert_allclose(s2, s_full, atol=1e-4)


def test_ops_wrappers_jit(key):
    q = jax.random.normal(key, (1, 32, 2, 16))
    out = ops.flash_attention(q, q, q)
    assert out.shape == q.shape
    r = jax.random.normal(key, (1, 16, 2, 8))
    w = jnp.full((1, 16, 2, 8), 0.9)
    u = jnp.zeros((2, 8))
    y, s = ops.rwkv6_scan(r, r, r, w, u)
    assert y.shape == r.shape and s.shape == (1, 2, 8, 8)
