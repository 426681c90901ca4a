"""On-device DataTransformer — crop/mirror/mean/scale inside the jitted step.

The host path (transforms.DataTransformer -> native transform_batch) ships
float32 *crops* to the device: for CaffeNet that is 227*227*3*4 = 618 KB per
image. On a transfer-bound link (any real host->HBM path) the winning
layout is the reference's own storage layout: ship the raw uint8 source batch (256*256*3 = 196 KB per
image, 3.2x less; 4x less for uncropped CIFAR records) and apply the
reference transform semantics (data_transformer.cpp:42-51:
``top[mirrored_index] = (src[data_index] - mean[data_index]) * scale``)
on-chip, as the first operations of the step (under the ``input_transform``
scope in a trace; a layout copy stands between them and the first conv).

The split of responsibilities keeps the reference's per-record randomness
exactly where it lives in Caffe (host-side ``Rand()`` in the data layer's
transform call) while moving the bandwidth-heavy work on-device:

  host:   draws per-image crop offsets and mirror flags — tiny int arrays
          (a few bytes/image) riding along with the uint8 batch;
  device: selects each image's window with two batched matmuls against
          0/1 selectors built from those arrays — rows
          ``Sy[n,i,h] = (h == y_n + i)``, columns
          ``Sx[n,w,j] = (w == x_n + (flip_n ? crop-1-j : j))``, so the
          mirror is a reversed column index and costs nothing of its own —
          then subtracts the mean (full mean selected at the same source
          window, per-channel mean as is — both per the reference) and
          scales. One routine whatever the configuration: no crop is the
          column selector alone at the record's width, no crop and no
          mirror is a cast.

Why matmuls: XLA:TPU lowers per-image ``lax.dynamic_slice`` offsets to a
serial loop over the batch and a lane-dimension reverse to a slow copy
(together a quarter of a CaffeNet b1536 step, PERF.md PR 26); the MXU does
the same selection in two passes with no loop. Each output element is one
source value times 1 plus zeros, so nothing is rounded: uint8 is exact in
bfloat16 (the MXU's native operand), float32 records and the full-size
mean go through float32 selectors at ``Precision.HIGHEST``. The known
cost is on a CPU backend, where the selectors are more arithmetic than a
slice (about 1.7e8 FLOP an image at 3x256x256 -> 227: 0.7 ms against
0.1 ms an image on the sandbox's CPU, beside CaffeNet's 4.3e9 FLOP an
image); the code does not branch on the backend for it.

The contract: the output is bit-equal to the host kernel (native/pipeline.cpp
transform_batch) on the cropped window, given identical offsets/flags.
tests/test_device_transform.py asserts it for every configuration; after
the exact selection the two paths share the same float32 operation order
(``(v - mean) * scale``), so they agree exactly, not just approximately.
"""

import numpy as np
import jax.numpy as jnp
from jax import lax

from .transforms import DataTransformer


def aux_keys(data_top):
    """Names of the host-side randomness arrays riding with ``data_top``.
    '#' keeps them out of any legal prototxt blob namespace."""
    return (f"{data_top}#y", f"{data_top}#x", f"{data_top}#flip")


def _row_selector(ys, size, extent):
    """Sy[n,i,h] = (h == ys[n] + i)."""
    rows = ys[:, None].astype(jnp.int32) + jnp.arange(size, dtype=jnp.int32)
    return rows[:, :, None] == jnp.arange(extent, dtype=jnp.int32)


def _col_selector(xs, flips, size, extent):
    """Sx[n,w,j] = (w == xs[n] + (flips[n] ? size-1-j : j)); ``xs`` None is
    offset 0, ``flips`` None is no mirror."""
    j = jnp.arange(size, dtype=jnp.int32)[None, :]
    if flips is not None:
        j = jnp.where(flips[:, None] != 0, size - 1 - j, j)
    if xs is not None:
        j = j + xs[:, None].astype(jnp.int32)
    return jnp.arange(extent, dtype=jnp.int32)[None, :, None] == \
        j[:, None, :]


def _window(src, ys, xs, flips, crop):
    """float32(src[n, :, ys[n]:ys[n]+crop, xs[n]:xs[n]+crop]), mirrored
    where ``flips[n]``; ``ys``/``xs`` None keep the whole extent. ``src``
    is (N,C,H,W), or (1,C,H,W) shared by the batch (the mean).

    Two batched matmuls against 0/1 selectors. Each output element is one
    source value times 1 plus zeros, so the result is exact as long as
    the operand type holds the source exactly: uint8 in bfloat16, the
    MXU's native operand; anything else in float32 at Precision.HIGHEST.
    """
    if src.dtype == jnp.uint8:
        dt, prec = jnp.bfloat16, None
    else:
        dt, prec = jnp.float32, lax.Precision.HIGHEST
    shared = src.shape[0] == 1
    h, w = src.shape[2:]
    out = src.astype(dt)
    if ys is not None:
        sy = _row_selector(ys, crop, h).astype(dt)
        out = jnp.einsum("nih,chw->nciw" if shared else "nih,nchw->nciw",
                         sy, out[0] if shared else out, precision=prec,
                         preferred_element_type=jnp.float32).astype(dt)
        shared = False
    if xs is not None or flips is not None:
        sx = _col_selector(xs, flips, w if xs is None else crop, w)
        out = jnp.einsum("ciw,nwj->ncij" if shared else "nciw,nwj->ncij",
                         out[0] if shared else out, sx.astype(dt),
                         precision=prec,
                         preferred_element_type=jnp.float32)
    return out.astype(jnp.float32)


class DeviceTransformer:
    """Device-side twin of a (configured) DataTransformer.

    Wraps the host transformer for its parsed TransformationParameter state
    (scale / mirror / crop_size / mean_file XOR mean_value, phase) and its
    RandomState — the aux draws below consume the rng in the same order as
    DataTransformer.__call__, so a source switched between host and device
    modes sees the identical augmentation stream.
    """

    def __init__(self, host_transformer, data_top="data"):
        self.h = host_transformer
        self.data_top = data_top
        self.ky, self.kx, self.kf = aux_keys(data_top)

    # -- host side ---------------------------------------------------------
    def aux(self, n, record_shape):
        """Per-batch randomness: {aux_key: int array} for ``n`` images of
        ``record_shape`` (C,H,W). TRAIN draws random offsets/flips, TEST
        uses the center window — exactly DataTransformer.__call__'s draws."""
        h_, w_ = record_shape[1], record_shape[2]
        t = self.h
        out = {}
        crop = t.crop_size
        if crop:
            if t.phase == 0:
                ys = t.rng.randint(0, h_ - crop + 1, n).astype(np.int32)
                xs = t.rng.randint(0, w_ - crop + 1, n).astype(np.int32)
            else:
                ys = np.full(n, (h_ - crop) // 2, np.int32)
                xs = np.full(n, (w_ - crop) // 2, np.int32)
            out[self.ky], out[self.kx] = ys, xs
        if t.mirror:
            out[self.kf] = t.rng.randint(0, 2, n).astype(np.uint8)
        return out

    def raw_overrides(self, batch_size, record_shape):
        """check_batch shape overrides for the raw (pre-transform) feed:
        the uint8 source extent plus the aux arrays."""
        over = {self.data_top: (batch_size,) + tuple(record_shape)}
        for k in self.aux(0, record_shape):
            over[k] = (batch_size,)
        return over

    # -- device side -------------------------------------------------------
    def device_fn(self, precropped=False):
        """-> pure fn(batch dict) -> batch dict, jit-traceable and
        shape-polymorphic over the batch dim (works under shard_map slices
        and lax.scan micro-batches). Consumes ``data_top`` (+ aux keys),
        passes every other entry (labels, extra feeds) through.

        ``precropped``: the wire codec already sliced the crop window from
        the uint8 source on the host (data/wire.py), so the record's own
        window starts at 0,0 (no row selector, the column selector only
        mirrors) — but the y/x aux still place the full-size mean's window
        at the ORIGINAL source coordinates, so the output bits are those
        of the uncropped path.
        """
        t = self.h
        crop = t.crop_size
        scale = t.scale
        full_mean = t.full_mean
        mean = None if t.mean is None else jnp.asarray(t.mean, jnp.float32)
        data_top, ky, kx, kf = self.data_top, self.ky, self.kx, self.kf

        def fn(batch):
            batch = dict(batch)
            x = batch.pop(data_top)
            c = x.shape[1]
            flips = batch.pop(kf, None)
            ys = xs = None
            if crop:
                ys, xs = batch.pop(ky), batch.pop(kx)
            # a precropped record's own window starts at 0,0
            ry, rx = (None, None) if precropped else (ys, xs)
            out = _window(x, ry, rx, flips, crop)
            if mean is not None and full_mean:
                # source-indexed: the mean's window sits at the ORIGINAL
                # y/x, and the mirror (a reversed column index) moves both
                # operands of the subtraction alike
                out = out - _window(mean[None], ys, xs, flips, crop)
            elif mean is not None:
                m = mean
                if m.shape[0] == 1 and c > 1:
                    m = jnp.broadcast_to(m, (c,))
                out = out - m.reshape(1, -1, 1, 1)
            if scale != 1.0:
                out = out * scale
            batch[data_top] = out
            return batch

        return fn


def build_device_transformer(tp, phase=0, base_dir="", rng=None,
                             data_top="data"):
    """TransformationParameter -> DeviceTransformer (parsing — incl. the
    mean_file binaryproto load — delegated to the host DataTransformer)."""
    host = DataTransformer(tp, phase=phase, base_dir=base_dir, rng=rng)
    return DeviceTransformer(host, data_top=data_top)
