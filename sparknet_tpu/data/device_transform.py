"""On-device DataTransformer — crop/mirror/mean/scale inside the jitted step.

The host path (transforms.DataTransformer -> native transform_batch) ships
float32 *crops* to the device: for CaffeNet that is 227*227*3*4 = 618 KB per
image. On a transfer-bound link (any real host->HBM path) the winning
layout is the reference's own storage layout: ship the raw uint8 source batch (256*256*3 = 196 KB per
image, 3.2x less; 4x less for uncropped CIFAR records) and apply the
reference transform semantics (data_transformer.cpp:42-51:
``top[mirrored_index] = (src[data_index] - mean[data_index]) * scale``)
on-chip, where XLA fuses them into the first conv's input pipeline.

The split of responsibilities keeps the reference's per-record randomness
exactly where it lives in Caffe (host-side ``Rand()`` in the data layer's
transform call) while moving the bandwidth-heavy work on-device:

  host:   draws per-image crop offsets and mirror flags — tiny int arrays
          (a few bytes/image) riding along with the uint8 batch;
  device: gathers the crop windows (vmapped ``lax.dynamic_slice``), applies
          the mirror, subtracts the mean (full mean source-indexed *before*
          the mirror, per-channel mean after — both per the reference), and
          scales.

Bit-exactness against the native host kernel (native/pipeline.cpp
transform_batch) on identical offsets/flags is asserted by
tests/test_device_transform.py; the two paths share the same float32
operation order so they agree exactly, not just approximately.
"""

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .transforms import DataTransformer


def aux_keys(data_top):
    """Names of the host-side randomness arrays riding with ``data_top``.
    '#' keeps them out of any legal prototxt blob namespace."""
    return (f"{data_top}#y", f"{data_top}#x", f"{data_top}#flip")


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
        the uint8 source on the host (data/wire.py), so skip the crop
        gather — but still consume the y/x aux to slice the full-size mean
        at the ORIGINAL source coordinates, keeping the float32 op order
        (and output bits) identical to the uncropped path: slicing uint8
        then casting equals casting then slicing.
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
            out = x.astype(jnp.float32)
            flips = batch.pop(kf, None)
            if crop:
                ys = batch.pop(ky)
                xs = batch.pop(kx)

                if not precropped:
                    def win(img, y, x0):
                        return lax.dynamic_slice(img, (0, y, x0),
                                                 (c, crop, crop))
                    out = jax.vmap(win)(out, ys, xs)
                if mean is not None and full_mean:
                    # source-indexed mean window, subtracted pre-mirror
                    out = out - jax.vmap(
                        lambda y, x0: lax.dynamic_slice(
                            mean, (0, y, x0), (c, crop, crop)))(ys, xs)
                if flips is not None:
                    out = jnp.where(flips[:, None, None, None] != 0,
                                    out[..., ::-1], out)
            else:
                if mean is not None and full_mean:
                    out = out - mean[None]
                if flips is not None:
                    out = jnp.where(flips[:, None, None, None] != 0,
                                    out[..., ::-1], out)
            if mean is not None and not full_mean:
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
