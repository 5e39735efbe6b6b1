"""Bucketing invariants: monotone bucket selection, masks that zero out
exactly the padded tail, and bucketed-equals-unbucketed encoder math on
the unpadded prefix (including length-0 and length-==-bucket rows)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.emsnet import tiny
from repro.core import Bucketer, bucket_length
from repro.core.bucketing import pad_axis, stack_bucketed
from repro.models import emsnet as E


# ----------------------------------------------------------- monotone

def test_bucket_length_monotone_unclamped():
    bs = [bucket_length(n) for n in range(1, 257)]
    assert all(a <= b for a, b in zip(bs, bs[1:]))


@pytest.mark.parametrize("max_bucket", [4, 16, 48, 64])
def test_bucket_length_monotone_clamped(max_bucket):
    bs = [bucket_length(n, max_bucket=max_bucket) for n in range(1, 257)]
    assert all(a <= b for a, b in zip(bs, bs[1:]))
    assert bs[-1] == max_bucket                    # clamp reached
    assert all(b <= max_bucket for b in bs)        # never past the cap


def test_bucket_length_idempotent():
    """A bucketed length re-buckets to itself: serving a padded payload
    again never grows it."""
    for n in range(1, 129):
        b = bucket_length(n, max_bucket=64)
        assert bucket_length(b, max_bucket=64) == b


# ------------------------------------------------- masks vs padded tail

def test_vitals_mask_covers_exactly_the_padded_tail():
    b = Bucketer(min_bucket=4)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 3)),
                    jnp.float32)
    p = b.fit("vitals", x)
    T_b, n = int(p["x"].shape[1]), int(p["len"][0])
    assert n == 5 and T_b == 8
    np.testing.assert_array_equal(np.asarray(p["x"][:, :n]), np.asarray(x))
    assert np.all(np.asarray(p["x"][:, n:]) == 0.0)   # tail is all-zero


def test_text_pad_is_exactly_the_pad_suffix():
    b = Bucketer(min_bucket=4)
    toks = jnp.asarray([[7, 9, 11]], jnp.int32)
    p = b.fit("text", toks)
    assert p.shape == (1, 4)
    np.testing.assert_array_equal(np.asarray(p[0, :3]), [7, 9, 11])
    assert int(p[0, 3]) == 0                          # PAD id, masked out


def test_stack_bucketed_surplus_rows_are_masked_out():
    """Batch-axis padding rows carry len=0 (vitals) / PAD=0 (text), so
    the encoders' masks zero exactly those rows."""
    rows = [{"x": jnp.ones((1, 4, 2)), "len": jnp.array([4], jnp.int32)}
            for _ in range(3)]
    s = stack_bucketed(rows, 8)
    assert np.all(np.asarray(s["len"][3:]) == 0)
    assert np.all(np.asarray(s["x"][3:]) == 0.0)
    t = stack_bucketed([jnp.full((1, 4), 5, jnp.int32)] * 3, 8)
    assert np.all(np.asarray(t[3:]) == 0)


# --------------------------------- bucketed == unbucketed on the prefix

@pytest.mark.parametrize("kind", ["rnn", "gru", "lstm"])
def test_bucketed_vitals_rows_equal_unpadded_prefix(kind):
    """Each row of a bucketed call equals the unbucketed call on that
    row's unpadded prefix — including a length-0 row (the zero initial
    state) and a length-==-bucket row (no padding at all)."""
    cfg = tiny(vitals_encoder=kind)
    p = E.vitals_encoder_init(jax.random.PRNGKey(0), cfg)
    T_b, lens = 8, [0, 8, 3]                          # empty, full, partial
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(len(lens), T_b, cfg.n_vitals)),
                    jnp.float32)
    # zero the padded tails so the payload matches what the bucketer emits
    mask = (np.arange(T_b)[None, :, None]
            < np.asarray(lens)[:, None, None])
    x = x * jnp.asarray(mask, jnp.float32)
    got = E.vitals_encoder(p, cfg, {"x": x,
                                    "len": jnp.asarray(lens, jnp.int32)})
    for i, n in enumerate(lens):
        want = E.vitals_encoder(p, cfg, x[i:i + 1, :n])
        np.testing.assert_allclose(got[i:i + 1], want, atol=1e-6)


@pytest.mark.parametrize("kind", ["rnn", "gru", "lstm"])
def test_length_zero_row_is_initial_state(kind):
    cfg = tiny(vitals_encoder=kind)
    p = E.vitals_encoder_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 4, cfg.n_vitals)),
                    jnp.float32)
    out = E.vitals_encoder(p, cfg, {"x": x, "len": jnp.zeros((1,), jnp.int32)})
    np.testing.assert_allclose(out, jnp.zeros_like(out), atol=0)


def test_bucketed_text_encoder_equals_unpadded():
    """Padding text to its bucket must not move F_T (key mask + masked
    mean-pool): encoder(bucketed) == encoder(raw)."""
    cfg = tiny()
    p = E.text_encoder_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 5)), jnp.int32)
    want = E.text_encoder(p, cfg, toks)
    got = E.text_encoder(p, cfg, Bucketer().fit("text", toks))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pad_axis_crop_directions():
    x = jnp.arange(8).reshape(1, 8)
    np.testing.assert_array_equal(np.asarray(pad_axis(x, 3, axis=1,
                                                      keep="tail"))[0],
                                  [5, 6, 7])
    np.testing.assert_array_equal(np.asarray(pad_axis(x, 3, axis=1,
                                                      keep="head"))[0],
                                  [0, 1, 2])


# --------------------------------------- host batches vs device batches

# (modality, natural length, length cap): padded, exactly at the bucket,
# and cropped (text keeps its head, vitals their tail); scene is fixed-size
HOST_CASES = [("text", 5, None), ("text", 8, None), ("text", 13, 8),
              ("vitals", 5, None), ("vitals", 8, None), ("vitals", 13, 8),
              ("scene", None, None)]


def _rows(modality, n, seed=0):
    rng = np.random.default_rng(seed)
    if modality == "text":
        return [rng.integers(1, 100, (1, n)).astype(np.int32)
                for _ in range(3)]
    if modality == "vitals":
        return [rng.normal(size=(1, n, 3)).astype(np.float32)
                for _ in range(3)]
    return [rng.integers(0, 2, (1, 3)).astype(np.float32) for _ in range(3)]


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


@pytest.mark.parametrize("modality,n,cap", HOST_CASES)
def test_host_fit_and_stack_stay_numpy_and_equal_the_device_path(
        modality, n, cap):
    """Numpy payloads are padded, cropped and stacked in numpy, with no
    transfer to the device (so no device operation), and give the device
    path's bytes, dtypes and keys."""
    b = Bucketer(min_bucket=4, max_buckets={} if cap is None
                 else {modality: cap})
    rows = _rows(modality, n)
    with jax.transfer_guard_host_to_device("disallow"):
        fitted = [b.fit(modality, x) for x in rows]
        host = stack_bucketed(fitted, 8)
    dev = stack_bucketed([b.fit(modality, jnp.asarray(x)) for x in rows], 8)
    assert all(type(a) is np.ndarray for a in _leaves(fitted) + _leaves(host))
    assert jax.tree_util.tree_structure(host) == \
        jax.tree_util.tree_structure(dev)
    for h, d in zip(_leaves(host), _leaves(dev)):
        assert h.dtype == d.dtype and h.shape == d.shape
        np.testing.assert_array_equal(h, np.asarray(d))


@pytest.mark.parametrize("modality,n,cap", HOST_CASES)
def test_device_stack_equals_concatenate_then_pad(modality, n, cap):
    """Device rows stack in one program to what an eager concatenate
    followed by a pad of the surplus rows gives."""
    b = Bucketer(min_bucket=4, max_buckets={} if cap is None
                 else {modality: cap})
    fitted = [b.fit(modality, jnp.asarray(x)) for x in _rows(modality, n)]
    got = stack_bucketed(fitted, 8)
    for k, g in (got.items() if isinstance(got, dict) else [(None, got)]):
        col = [p[k] if k is not None else p for p in fitted]
        want = jnp.concatenate([jnp.asarray(c) for c in col], axis=0)
        want = jnp.pad(want, [(0, 8 - want.shape[0])]
                       + [(0, 0)] * (want.ndim - 1))
        # `len` is a host leaf whatever the payload's home; x stacks on
        # the device
        assert isinstance(g, np.ndarray if k == "len" else jax.Array)
        assert g.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


@pytest.mark.parametrize("host", [False, True], ids=["device", "host"])
def test_stack_bucketed_refuses_more_rows_than_the_bucket(host):
    rows = [np.ones((1, 4), np.int32)] * 3
    with pytest.raises(ValueError, match="do not fit"):
        stack_bucketed(rows if host else [jnp.asarray(r) for r in rows], 2)
