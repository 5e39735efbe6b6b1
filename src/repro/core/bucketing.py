"""Shape bucketing: bounded static shapes for variable-length inputs.

XLA compiles one program per input shape. An EMS episode grows its
vitals time series with every event and text utterances vary in token
count, so serving them at their natural shapes recompiles the encoders
over and over — the dominant cost on the serving hot path. The bucketer
pads every variable-length input up to the next power-of-two length
(floored at ``min_bucket``, clamped at ``max_bucket``) so each encoder
only ever sees O(log max_len) distinct shapes and the recompile count
plateaus after warmup.

Padding must not change the math:
  * text: PAD id 0 — the text encoder already key-masks ``tokens > 0``
    and mean-pools over the same mask;
  * vitals: zero-padded timesteps plus an explicit ``len`` vector; the
    recurrent encoders freeze their carry on padded steps (see
    ``models.emsnet.vitals_encoder``), so the final state equals the
    unpadded run's;
  * batch axis (multi-session coalescing): rows above ``n`` are
    zero/PAD rows the caller slices away.

Inputs longer than ``max_bucket`` are cropped to it: vitals keep their
most recent steps (a sliding window; NEMSIS caps at 30 per event
anyway), text keeps its leading tokens (the valid prefix).

Where the data lives decides where it is padded and stacked. Host
(numpy) inputs are padded and stacked in numpy and stay numpy: they
reach the device as the jitted encoder's own argument, inside its
dispatch, with no eager device program. Inputs already on the device
(the feature rows a tail consumes) cost one device program per pad or
per stacked leaf. The choice is made per array, so a vitals dict may
carry a device ``x`` beside its host ``len``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def bucket_length(n: int, *, min_bucket: int = 8,
                  max_bucket: Optional[int] = None) -> int:
    b = max(min_bucket, next_pow2(n))
    if max_bucket is not None:
        # the cap itself is the top bucket (NOT rounded up: a
        # non-power-of-two cap like max_text_len=48 must never produce
        # inputs longer than the positional table)
        b = min(b, max_bucket)
    return b


def pad_axis(x, length: int, axis: int, pad_value=0, keep: str = "tail"):
    """Pad ``axis`` to ``length``; when cropping keep the trailing
    (``keep="tail"``, for streams where the recent window matters) or
    leading (``keep="head"``, for right-padded sequences whose valid
    prefix must survive) slice."""
    n = x.shape[axis]
    if n == length:
        return x
    if n > length:
        idx = [slice(None)] * x.ndim
        idx[axis] = (slice(n - length, n) if keep == "tail"
                     else slice(0, length))
        return x[tuple(idx)]
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, length - n)
    if isinstance(x, np.ndarray):
        return np.pad(x, widths, constant_values=pad_value)
    return jnp.pad(x, widths, constant_values=pad_value)


@dataclass
class Bucketer:
    """Pads per-modality payloads to bucketed lengths.

    ``fit`` returns the exact pytree the (mask-aware) encoders consume:
      * text  (B, S) int32  -> (B, S_b) int32, PAD=0
      * vitals (B, T, n)    -> {"x": (B, T_b, n), "len": (B,) int32}
      * anything fixed-size -> passthrough
    Each array stays where it was (numpy in, numpy out); ``len`` is
    always a host array.
    """
    min_bucket: int = 8
    max_buckets: Dict[str, int] = field(default_factory=dict)
    # (modality, bucket) -> times served; bounded <=> compiles bounded
    histogram: Dict[tuple, int] = field(default_factory=dict)

    def bucket(self, modality: str, n: int) -> int:
        return bucket_length(n, min_bucket=self.min_bucket,
                             max_bucket=self.max_buckets.get(modality))

    def _count(self, modality: str, b: int):
        key = (modality, b)
        self.histogram[key] = self.histogram.get(key, 0) + 1

    def fit(self, modality: str, x):
        if modality == "text":
            b = self.bucket(modality, x.shape[1])
            self._count(modality, b)
            # valid tokens are a prefix (PAD suffix): keep the head so a
            # crop drops PAD, not the utterance
            return pad_axis(x, b, axis=1, keep="head")
        if modality == "vitals":
            T = x.shape[1]
            b = self.bucket(modality, T)
            self._count(modality, b)
            return {"x": pad_axis(x, b, axis=1),
                    "len": np.full((x.shape[0],), min(T, b), np.int32)}
        return x

    def n_buckets(self) -> int:
        return len(self.histogram)


def stack_bucketed(payloads, batch_bucket: int):
    """Coalesce per-session payloads (each batch dim 1, same bucketed
    length) into one batch of ``batch_bucket`` rows; surplus rows are
    zero/PAD padding (zero ``len`` for masked-vitals dicts, so padded
    rows encode to the zero initial state). Returns the stacked pytree;
    row i -> session i for the first ``len(payloads)`` rows.

    Each leaf is stacked where its rows live: all numpy rows are written
    into one zero-filled numpy buffer (no device operation); otherwise
    one jitted program concatenates and pads them on the device."""
    if isinstance(payloads[0], dict):
        keys = payloads[0].keys()
        for i, p in enumerate(payloads[1:], start=1):
            if p.keys() != keys:
                raise ValueError(
                    f"stack_bucketed: payload 0 has keys {sorted(keys)} but "
                    f"payload {i} has {sorted(p.keys())}; refusing to drop "
                    "mismatched keys")
        return {k: _stack_rows([p[k] for p in payloads], batch_bucket)
                for k in keys}
    return _stack_rows(list(payloads), batch_bucket)


def _stack_rows(rows, batch_bucket: int):
    n = sum(r.shape[0] for r in rows)
    if n > batch_bucket:
        raise ValueError(f"stack_bucketed: {n} rows do not fit a batch "
                         f"of {batch_bucket}")
    if not all(isinstance(r, np.ndarray) for r in rows):
        return _stack_on_device(tuple(rows), batch_bucket)
    dtype = jax.dtypes.canonicalize_dtype(np.result_type(*rows))
    out = np.zeros((batch_bucket,) + rows[0].shape[1:], dtype)
    i = 0
    for r in rows:
        out[i:i + r.shape[0]] = r
        i += r.shape[0]
    return out


@partial(jax.jit, static_argnums=1)
def _stack_on_device(rows, batch_bucket: int):
    x = jnp.concatenate(rows, axis=0)
    return pad_axis(x, batch_bucket, axis=0)


@dataclass
class RaggedBatch:
    """Concatenated ragged layout: rows of different natural lengths
    packed back-to-back into ONE flat buffer, so a flush issues a single
    encoder call per modality regardless of how many length buckets are
    live (vs one call per ``(modality, bucket)`` for :class:`Bucketer`).

    ``pack`` returns the payload dict the (ragged-aware) encoders
    consume. For ``text`` (B=1 rows of (1, S_i) int32):

      * ``tokens``  (1, T) int32 — rows concatenated, PAD=0 between and
        after; each row starts at a multiple of ``align`` (the flash
        block size: block-aligned row starts are what make the packed
        kernel bit-identical to per-row calls);
      * ``row_ids`` (T,) int32 — position -> row index, -1 on padding
        AND on a row's interior PAD tokens (so the segment mask equals
        the reference ``tokens > 0`` key mask);
      * ``pos``     (T,) int32 — position within the row (for the
        positional embedding gather);
      * ``offsets`` (R,) int32 / ``lengths`` (R,) int32 — row i occupies
        ``[offsets[i], offsets[i] + lengths[i])``; surplus rows (R is
        padded to a power of two) have length 0 and offset == the total
        packed extent, so the segments tile the buffer exactly.

    For ``vitals`` (rows of (1, S_i, n) float): ``x`` (1, T, n) packed
    with ``align=1`` (the segmented scan has no cross-length reduction,
    so alignment buys nothing), plus ``reset`` (T, 1, 1) bool marking
    each row's first step, and the same ``offsets``/``lengths``.

    Both T and R are padded to powers of two (T floored at
    ``min_total``, R at ``min_rows``) so compile counts stay
    O(log² total) like the bucketer's. Rows longer than
    ``max_lengths[modality]`` are cropped exactly like ``Bucketer.fit``
    (text keeps its head, vitals its tail). ``histogram`` counts packed
    ``(modality, (R, T))`` shapes served.
    """
    align: int = 8
    min_total: int = 8
    min_rows: int = 1
    max_lengths: Dict[str, int] = field(default_factory=dict)
    histogram: Dict[tuple, int] = field(default_factory=dict)

    def _crop(self, modality: str, x):
        cap = self.max_lengths.get(modality)
        if cap is not None and x.shape[1] > cap:
            x = pad_axis(x, cap, axis=1,
                         keep="head" if modality == "text" else "tail")
        return x

    def _layout(self, lengths, align: int):
        offs, o = [], 0
        for n in lengths:
            offs.append(o)
            if n:
                o += -(-n // align) * align
        total = o
        T = max(self.min_total, next_pow2(max(total, 1)))
        R = max(self.min_rows, next_pow2(max(len(lengths), 1)))
        return offs, total, T, R

    def _index_vectors(self, offs, lens, total, T, R):
        import numpy as np
        offsets = np.full((R,), total, np.int32)
        lengths = np.zeros((R,), np.int32)
        offsets[:len(offs)] = offs
        lengths[:len(lens)] = lens
        return offsets, lengths

    def pack(self, modality: str, payloads):
        """payloads: list of (1, S_i, ...) arrays (one per session row)."""
        import numpy as np
        rows = [self._crop(modality, p) for p in payloads]
        lens = [int(r.shape[1]) for r in rows]
        if modality == "text":
            offs, total, T, R = self._layout(lens, self.align)
            toks = np.zeros((1, T), np.int32)
            seg = np.full((T,), -1, np.int32)
            pos = np.zeros((T,), np.int32)
            for i, (r, o, n) in enumerate(zip(rows, offs, lens)):
                rv = np.asarray(r[0], np.int32)
                toks[0, o:o + n] = rv
                seg[o:o + n] = np.where(rv > 0, i, -1)
                pos[o:o + n] = np.arange(n)
            offsets, lengths = self._index_vectors(offs, lens, total, T, R)
            self._count(modality, (R, T))
            return {"tokens": jnp.asarray(toks),
                    "row_ids": jnp.asarray(seg),
                    "pos": jnp.asarray(pos),
                    "offsets": jnp.asarray(offsets),
                    "lengths": jnp.asarray(lengths)}
        if modality == "vitals":
            offs, total, T, R = self._layout(lens, 1)
            n_feat = int(rows[0].shape[2])
            x = np.zeros((1, T, n_feat), np.float32)
            reset = np.zeros((T, 1, 1), bool)
            for r, o, n in zip(rows, offs, lens):
                if n:
                    x[0, o:o + n] = np.asarray(r[0], np.float32)
                    reset[o] = True
            offsets, lengths = self._index_vectors(offs, lens, total, T, R)
            self._count(modality, (R, T))
            return {"x": jnp.asarray(x),
                    "reset": jnp.asarray(reset),
                    "offsets": jnp.asarray(offsets),
                    "lengths": jnp.asarray(lengths)}
        raise ValueError(f"RaggedBatch.pack: no ragged layout for "
                         f"modality {modality!r} (fixed-size payloads "
                         "stack on the batch axis instead)")

    def _count(self, modality: str, shape):
        key = (modality, shape)
        self.histogram[key] = self.histogram.get(key, 0) + 1

    def n_shapes(self) -> int:
        return len(self.histogram)
