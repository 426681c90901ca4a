"""Data-source layers.

In the reference these pull minibatches *inside* the graph — JavaDataLayer
upcalls into the JVM to fill a host buffer mid-forward (java_data_layer.cpp:
37-45), and DataLayer runs LMDB prefetch threads. On TPU the graph is a pure
compiled function, so every data layer becomes a *feed*: its tops are taken
from the ``batch`` dict passed to the compiled step (host loaders in
``sparknet_tpu.data`` produce those arrays and device_put them). This is the
design inversion called out in SURVEY.md section 7: callback-pull becomes
loader-push.

Shape resolution:
  JavaData     java_data_param.shape (one top, reference Layers.scala RDDLayer)
  MemoryData   memory_data_param dims; label top (batch,)
  DummyData    dummy_data_param shapes + fillers (generated in-graph)
  Data/ImageData/HDF5Data/WindowData  from the ``feed_shapes`` build argument
  (their on-disk sources are host-side concerns, see sparknet_tpu.data)
"""

import numpy as np
import jax.numpy as jnp

from ..graph.registry import Layer, register
from ..graph import fillers as F


class FeedLayer(Layer):
    """Tops come from the batch dict, keyed by top name."""

    is_feed = True

    def __init__(self, lp, bottom_shapes, phase, feed_shapes=None):
        super().__init__(lp, bottom_shapes, phase)
        self.feed_shapes = feed_shapes or {}

    def _external_shapes(self, batch_size_hint=None):
        shapes = []
        for top in self.lp.top:
            if top in self.feed_shapes:
                shapes.append(tuple(self.feed_shapes[top]))
            elif top == "label" and batch_size_hint:
                shapes.append((batch_size_hint,))
            else:
                raise ValueError(
                    f"data layer {self.lp.name!r}: provide feed_shapes[{top!r}] "
                    f"at build time (its source is host-side)")
        return shapes

    def out_shapes(self):
        raise NotImplementedError

    def apply(self, params, bottoms, train, rng):
        raise RuntimeError("feed layers are resolved by the compiler")


@register
class JavaData(FeedLayer):
    type_name = "JavaData"

    def out_shapes(self):
        p = self.lp.java_data_param
        shapes = []
        for i, top in enumerate(self.lp.top):
            if top in self.feed_shapes:  # build-time override (e.g. the
                shapes.append(tuple(self.feed_shapes[top]))  # per-shard net)
            elif i == 0 and p.has("shape"):
                # java_data_param.shape describes the FIRST top only
                shapes.append(tuple(int(d) for d in p.shape.dim))
            elif i > 0 and p.has("shape"):
                # trailing tops are labels: (batch,), like Caffe data layers
                shapes.append((int(p.shape.dim[0]),))
            else:
                raise ValueError(
                    f"JavaData layer {self.lp.name!r}: no shape for top "
                    f"{top!r} (provide feed_shapes[{top!r}])")
        return shapes


@register
class Data(FeedLayer):
    type_name = "Data"

    def out_shapes(self):
        bs = int(self.lp.data_param.batch_size) \
            if self.lp.has("data_param") else None
        return self._external_shapes(batch_size_hint=bs)


@register
class ImageData(FeedLayer):
    type_name = "ImageData"

    def out_shapes(self):
        bs = int(self.lp.image_data_param.batch_size) \
            if self.lp.has("image_data_param") else None
        return self._external_shapes(batch_size_hint=bs)


@register
class WindowData(FeedLayer):
    type_name = "WindowData"

    def out_shapes(self):
        bs = int(self.lp.window_data_param.batch_size) \
            if self.lp.has("window_data_param") else None
        return self._external_shapes(batch_size_hint=bs)


@register
class HDF5Data(FeedLayer):
    type_name = "HDF5Data"

    def out_shapes(self):
        bs = int(self.lp.hdf5_data_param.batch_size) \
            if self.lp.has("hdf5_data_param") else None
        return self._external_shapes(batch_size_hint=bs)


@register
class MemoryData(FeedLayer):
    type_name = "MemoryData"

    def out_shapes(self):
        p = self.lp.memory_data_param
        shape = (int(p.batch_size), int(p.channels), int(p.height),
                 int(p.width))
        outs = [shape]
        if len(self.lp.top) > 1:
            outs.append((int(p.batch_size),))
        return outs


@register
class DummyData(Layer):
    """Generates tops from fillers in-graph (dummy_data_layer.cpp). Constant
    fillers are baked; random fillers draw from the step rng."""

    type_name = "DummyData"
    needs_rng = True

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.dummy_data_param
        if p.shape:
            self.shapes = [tuple(int(d) for d in s.dim) for s in p.shape]
        else:
            self.shapes = [(int(p.num[i]), int(p.channels[i]),
                            int(p.height[i]), int(p.width[i]))
                           for i in range(len(p.num))]
        n = len(self.shapes)
        fl = list(p.data_filler)
        if not fl:
            self.fillers = [None] * n
        elif len(fl) == 1:
            self.fillers = fl * n
        else:
            self.fillers = fl

    def out_shapes(self):
        return self.shapes

    def apply(self, params, bottoms, train, rng):
        import jax
        from ..parallel import context
        rng = context.shard_key(rng)
        keys = jax.random.split(rng, len(self.shapes)) if rng is not None \
            else [None] * len(self.shapes)
        return [F.fill(k, s, f) for k, s, f in
                zip(keys, self.shapes, self.fillers)]


@register
class HDF5Output(Layer):
    """Sink layer (reference hdf5_output_layer.cpp wrote bottoms to disk).
    In a pure graph it is a no-op passthrough-to-nowhere; the CLI offers
    blob dumping instead."""

    type_name = "HDF5Output"

    def out_shapes(self):
        return []

    def apply(self, params, bottoms, train, rng):
        return []
