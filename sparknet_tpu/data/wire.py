"""Compressed wire formats for the host->device feed.

The device-transform split (device_transform.py) already ships raw uint8
records instead of float32 crops — 3.2-4x fewer bytes. This module is the
next turn of the same screw, for links where the H2D wire is the bound
(an earlier rig: pure transfer ~62 img/s at 192 KB/image vs an 11,913
img/s device step):

  precrop  — the host slices each record's crop window (using the SAME
             y/x draws that ride along as aux arrays) before shipping, so
             the wire carries crop^2 pixels instead of src^2. Exact
             integer uint8 slicing, no float math: the device path takes
             the record's window at 0,0 (no row selector) but still
             selects the full-size mean at the ORIGINAL y/x and mirrors
             on-device through its column selector, so every output bit
             is unchanged: bit-equal to the host kernel on the cropped
             window (DeviceTransformer.device_fn(precropped=True)).
             CaffeNet geometry: 256^2 -> 227^2 is 1.27x.
  pack     — lossless bit-pack for low-entropy sources: when every pixel
             value fits in 1/2/4 bits, 8/4/2 pixels share each shipped
             byte; the device unpacks with shifts/masks before the
             transform. The bit width is fixed ONCE (explicitly, or
             inferred from a sample record batch) so shipped shapes are
             static — no recompiles — and a later batch that exceeds the
             width raises instead of clipping: the pack is lossless or it
             is an error. Width 8 is the raw passthrough.

``precrop+pack`` composes both: a 2-bit source at CaffeNet geometry ships
~37.8 KB/image vs the 192 KB raw wire — 5.1x, and >= the 3x target with
room to spare. Gated by SPARKNET_WIRE / `--wire` (default: raw, the
previous behavior, byte for byte).

Echo interaction: data echoing re-draws crop/mirror aux per echo of one
shipped batch — impossible once the crop window is baked into the wire,
so echo>1 refuses precrop modes at the CLI rather than silently shipping
identical crops.
"""

import os

import numpy as np

WIRE_MODES = ("raw", "precrop", "pack", "precrop+pack")
PACK_WIDTHS = (1, 2, 4, 8)


def wire_mode_from_env(default="raw"):
    """SPARKNET_WIRE -> validated wire mode (typos are an error: a
    misspelled lever silently measuring the baseline would fake an A/B)."""
    mode = os.environ.get("SPARKNET_WIRE", "").strip().lower() or default
    if mode not in WIRE_MODES:
        raise ValueError(f"SPARKNET_WIRE={mode!r}: expected one of "
                         f"{', '.join(WIRE_MODES)}")
    return mode


def wire_bits_from_env():
    """SPARKNET_WIRE_BITS -> explicit pack width (None = infer from a
    sample batch at codec construction)."""
    raw = os.environ.get("SPARKNET_WIRE_BITS", "").strip()
    if not raw:
        return None
    bits = int(raw)
    if bits not in PACK_WIDTHS:
        raise ValueError(f"SPARKNET_WIRE_BITS={bits}: expected one of "
                         f"{PACK_WIDTHS}")
    return bits


def infer_pack_bits(sample):
    """Smallest lossless pack width for ``sample``'s value range. A sample
    understates the global max at your own risk: encode() raises on the
    first out-of-range batch (set SPARKNET_WIRE_BITS to be explicit)."""
    mx = int(np.max(sample)) if np.size(sample) else 0
    for bits in PACK_WIDTHS:
        if mx < (1 << bits):
            return bits
    return 8


class WireCodec:
    """Host-side encode + device-side decode around a DeviceTransformer.

    encode() runs where the source yields (host, prefetch worker);
    device_fn() wraps the transformer's jitted transform with the
    matching unpack, so the solver's input-transform hook sees one
    composed fn. raw_overrides() gives check_batch the SHIPPED shapes —
    the solver's h2d byte accounting (tree_bytes of the fed batch) then
    reflects actual wire bytes with no extra plumbing.
    """

    def __init__(self, devt, record_shape, mode="raw", bits=None,
                 sample=None):
        if mode not in WIRE_MODES:
            raise ValueError(f"wire mode {mode!r}: expected one of "
                             f"{', '.join(WIRE_MODES)}")
        self.devt = devt
        self.record_shape = tuple(int(d) for d in record_shape)
        self.mode = mode
        crop = devt.h.crop_size
        # precrop with no crop configured degenerates to raw shipping
        self.precrop = "precrop" in mode and bool(crop)
        self._crop = int(crop) if crop else 0
        self.packing = "pack" in mode
        if self.packing:
            if bits is None:
                if sample is None:
                    raise ValueError(
                        "pack wire mode needs an explicit bit width "
                        "(SPARKNET_WIRE_BITS / --wire-bits) or a sample "
                        "record batch to infer one from")
                bits = infer_pack_bits(sample)
            if bits not in PACK_WIDTHS:
                raise ValueError(f"pack width {bits}: expected one of "
                                 f"{PACK_WIDTHS}")
            if bits == 8:
                self.packing = False    # raw passthrough
        self.bits = int(bits) if self.packing else 8
        c, h, w = self.record_shape
        if self.precrop:
            self.image_shape = (c, self._crop, self._crop)
        else:
            self.image_shape = (c, h, w)
        self._flat_n = int(np.prod(self.image_shape))
        if self.packing:
            self._per_byte = 8 // self.bits
            self._pad = (-self._flat_n) % self._per_byte
            self.wire_shape = ((self._flat_n + self._pad) // self._per_byte,)
        else:
            self.wire_shape = self.image_shape

    # -- host side ---------------------------------------------------------
    def encode(self, batch):
        """Feed dict (device-mode layout: uint8 pixels + aux draws) ->
        same dict with the pixel blob re-encoded for the wire. Aux arrays
        always ship unchanged: the device needs the ORIGINAL y/x for the
        full-mean window even when the crop itself happened here."""
        data_top = self.devt.data_top
        x = batch[data_top]
        if self.precrop:
            ys, xs = batch[self.devt.ky], batch[self.devt.kx]
            crop = self._crop
            out = np.empty((len(x), x.shape[1], crop, crop), x.dtype)
            for i in range(len(x)):
                out[i] = x[i, :, ys[i]:ys[i] + crop, xs[i]:xs[i] + crop]
            x = out
        if self.packing:
            x = self._pack(x)
        if x is not batch[data_top]:
            batch = dict(batch)
            batch[data_top] = x
        return batch

    def _pack(self, x):
        mx = int(x.max(initial=0))
        if mx >= (1 << self.bits):
            raise ValueError(
                f"wire pack width {self.bits} is not lossless for this "
                f"batch (max value {mx}); set SPARKNET_WIRE_BITS to a "
                f"wider width or drop the pack mode")
        flat = np.ascontiguousarray(x, np.uint8).reshape(len(x), -1)
        if self._pad:
            flat = np.concatenate(
                [flat, np.zeros((len(x), self._pad), np.uint8)], axis=1)
        vals = flat.reshape(len(x), -1, self._per_byte).astype(np.uint16)
        shifts = (np.arange(self._per_byte, dtype=np.uint16) * self.bits)
        # each field occupies disjoint bits, so the sum fits a byte
        return (vals << shifts).sum(axis=2).astype(np.uint8)

    # -- device side -------------------------------------------------------
    def device_fn(self, inner=None):
        """Composed jittable fn: unpack (if packing) then the transform.
        ``inner`` overrides the transform stage (bench wraps a dtype
        cast); default is the transformer's precrop-aware device fn."""
        if inner is None:
            inner = self.devt.device_fn(precropped=self.precrop)
        if not self.packing:
            return inner
        import jax.numpy as jnp
        bits, per_byte = self.bits, self._per_byte
        flat_n, shape = self._flat_n, self.image_shape
        mask = (1 << bits) - 1
        data_top = self.devt.data_top

        def fn(batch):
            batch = dict(batch)
            p = batch.pop(data_top)
            shifts = jnp.arange(per_byte, dtype=jnp.uint8) * bits
            vals = (p[:, :, None] >> shifts[None, None, :]) & mask
            flat = vals.reshape(p.shape[0], -1)[:, :flat_n]
            batch[data_top] = flat.reshape((p.shape[0],) + shape)
            return inner(batch)

        return fn

    def raw_overrides(self, batch_size):
        """check_batch shape overrides for the SHIPPED feed."""
        over = self.devt.raw_overrides(batch_size, self.record_shape)
        over[self.devt.data_top] = (batch_size,) + tuple(self.wire_shape)
        return over

    # -- accounting --------------------------------------------------------
    @property
    def raw_kb_per_image(self):
        """The uncompressed device-mode wire (raw uint8 record)."""
        return int(np.prod(self.record_shape)) / 1024.0

    @property
    def kb_per_image(self):
        """Shipped pixel bytes per image under this codec."""
        return int(np.prod(self.wire_shape)) / 1024.0

    def describe(self):
        row = {"wire": self.mode,
               "h2d_kb_per_image": round(self.kb_per_image, 2),
               "wire_reduction": round(
                   self.raw_kb_per_image / max(self.kb_per_image, 1e-9), 2)}
        if self.packing:
            row["wire_bits"] = self.bits
        return row
