"""Shape/structure layers (reference common_layers.hpp zoo): pure jnp
reshuffles — XLA folds most of these into layout changes, so they cost
nothing at runtime.
"""

import numpy as np
import jax.numpy as jnp

from ..graph.registry import Layer, register


@register
class Softmax(Layer):
    type_name = "Softmax"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        self.axis = self.canonical_axis(lp.softmax_param.axis)

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        x = x - jnp.max(x, axis=self.axis, keepdims=True)
        e = jnp.exp(x)
        return [e / jnp.sum(e, axis=self.axis, keepdims=True)]


@register
class Concat(Layer):
    type_name = "Concat"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        cp = lp.concat_param
        # legacy concat_dim honored when axis unset (concat_layer.cpp)
        axis = cp.axis if cp.has("axis") or not cp.has("concat_dim") \
            else cp.concat_dim
        self.axis = self.canonical_axis(int(axis))

    def out_shapes(self):
        shape = list(self.bottom_shapes[0])
        shape[self.axis] = sum(s[self.axis] for s in self.bottom_shapes)
        return [tuple(shape)]

    def apply(self, params, bottoms, train, rng):
        return [jnp.concatenate(bottoms, axis=self.axis)]


@register
class Slice(Layer):
    type_name = "Slice"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        sp = lp.slice_param
        axis = sp.axis if sp.has("axis") or not sp.has("slice_dim") \
            else sp.slice_dim
        self.axis = self.canonical_axis(int(axis))
        self.n_tops = len(lp.top)
        dim = bottom_shapes[0][self.axis]
        points = list(sp.slice_point)
        if points:
            assert len(points) == self.n_tops - 1
            bounds = [0] + [int(p) for p in points] + [dim]
        else:
            assert dim % self.n_tops == 0
            step = dim // self.n_tops
            bounds = list(range(0, dim + 1, step))
        self.bounds = bounds

    def out_shapes(self):
        base = list(self.bottom_shapes[0])
        outs = []
        for i in range(self.n_tops):
            s = list(base)
            s[self.axis] = self.bounds[i + 1] - self.bounds[i]
            outs.append(tuple(s))
        return outs

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        outs = []
        for i in range(self.n_tops):
            idx = [slice(None)] * x.ndim
            idx[self.axis] = slice(self.bounds[i], self.bounds[i + 1])
            outs.append(x[tuple(idx)])
        return outs


@register
class Split(Layer):
    """Fan-out a blob to several tops. Caffe inserts these to sum gradients
    at fan-out points (util/insert_splits.cpp); under autodiff the fan-out
    gradient accumulation is automatic, so this is pure aliasing."""

    type_name = "Split"

    def out_shapes(self):
        return [self.bottom_shapes[0]] * max(1, len(self.lp.top))

    def apply(self, params, bottoms, train, rng):
        return [bottoms[0]] * max(1, len(self.lp.top))


@register
class Flatten(Layer):
    type_name = "Flatten"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        fp = lp.flatten_param
        nd = len(bottom_shapes[0])
        self.axis = self.canonical_axis(fp.axis)
        self.end_axis = self.canonical_axis(fp.end_axis)

    def out_shapes(self):
        s = self.bottom_shapes[0]
        mid = int(np.prod(s[self.axis:self.end_axis + 1], dtype=np.int64))
        return [tuple(s[:self.axis]) + (mid,) + tuple(s[self.end_axis + 1:])]

    def apply(self, params, bottoms, train, rng):
        return [bottoms[0].reshape(self.out_shapes()[0])]


@register
class Reshape(Layer):
    """Caffe reshape semantics (reshape_layer.cpp): dim 0 copies the bottom
    dim, one dim may be -1 (inferred); axis/num_axes bound the replaced span."""

    type_name = "Reshape"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        rp = lp.reshape_param
        bshape = list(bottom_shapes[0])
        nd = len(bshape)
        axis = rp.axis + nd + 1 if rp.axis < 0 else rp.axis
        num_axes = rp.num_axes
        end = nd if num_axes == -1 else axis + num_axes
        spec = [int(d) for d in rp.shape.dim] if rp.has("shape") else []
        replaced = bshape[axis:end]
        out_mid = []
        infer = -1
        for i, d in enumerate(spec):
            if d == 0:
                out_mid.append(replaced[i])
            elif d == -1:
                infer = i
                out_mid.append(1)
            else:
                out_mid.append(d)
        total = int(np.prod(bshape, dtype=np.int64))
        fixed = int(np.prod(bshape[:axis], dtype=np.int64)) * \
            int(np.prod(out_mid, dtype=np.int64)) * \
            int(np.prod(bshape[end:], dtype=np.int64))
        if infer >= 0:
            out_mid[infer] = total // fixed
        self.new_shape = tuple(bshape[:axis]) + tuple(out_mid) + \
            tuple(bshape[end:])
        assert int(np.prod(self.new_shape, dtype=np.int64)) == total, \
            f"reshape count mismatch {bshape} -> {self.new_shape}"

    def out_shapes(self):
        return [self.new_shape]

    def apply(self, params, bottoms, train, rng):
        return [bottoms[0].reshape(self.new_shape)]


@register
class Eltwise(Layer):
    type_name = "Eltwise"

    PROD, SUM, MAX = 0, 1, 2

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        ep = lp.eltwise_param
        self.op = int(ep.operation)
        coeff = list(ep.coeff)
        if coeff and len(coeff) != len(bottom_shapes):
            raise ValueError("eltwise coeff count must equal bottom count")
        self.coeff = coeff or [1.0] * len(bottom_shapes)

    def out_shapes(self):
        return [self.bottom_shapes[0]]

    def apply(self, params, bottoms, train, rng):
        if self.op == self.PROD:
            y = bottoms[0]
            for b in bottoms[1:]:
                y = y * b
        elif self.op == self.SUM:
            y = self.coeff[0] * bottoms[0]
            for c, b in zip(self.coeff[1:], bottoms[1:]):
                y = y + c * b
        else:
            y = bottoms[0]
            for b in bottoms[1:]:
                y = jnp.maximum(y, b)
        return [y]


@register
class Tile(Layer):
    type_name = "Tile"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        tp = lp.tile_param
        self.axis = self.canonical_axis(tp.axis)
        self.tiles = int(tp.tiles)

    def out_shapes(self):
        s = list(self.bottom_shapes[0])
        s[self.axis] *= self.tiles
        return [tuple(s)]

    def apply(self, params, bottoms, train, rng):
        reps = [1] * bottoms[0].ndim
        reps[self.axis] = self.tiles
        return [jnp.tile(bottoms[0], reps)]


@register
class Shift(Layer):
    """sparknet_tpu extension: y[i] = x[i + offset] along `axis`, `fill`
    where that reads past an end (models/zoo.py:_lm_stack: the labels of a
    second prediction depth)."""
    type_name = "Shift"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        sp = lp.shift_param
        self.axis = self.canonical_axis(sp.axis)
        self.offset = int(sp.offset)
        self.fill = float(sp.fill)

    def out_shapes(self):
        return [tuple(self.bottom_shapes[0])]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        at = jnp.arange(x.shape[self.axis]) + self.offset
        at = at.reshape([-1 if a == self.axis else 1
                         for a in range(x.ndim)])
        inside = (at >= 0) & (at < x.shape[self.axis])
        return [jnp.where(inside, jnp.roll(x, -self.offset, self.axis),
                          jnp.asarray(self.fill, x.dtype))]


@register
class ArgMax(Layer):
    type_name = "ArgMax"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        ap = lp.argmax_param
        self.out_max_val = bool(ap.out_max_val)
        self.top_k = int(ap.top_k)
        self.has_axis = ap.has("axis")
        self.axis = self.canonical_axis(ap.axis) if self.has_axis else None

    def out_shapes(self):
        s = self.bottom_shapes[0]
        if self.has_axis:
            out = list(s)
            out[self.axis] = self.top_k
            return [tuple(out)]
        k = self.top_k
        return [(s[0], 2 if self.out_max_val else 1, k)]

    def apply(self, params, bottoms, train, rng):
        import jax
        x = bottoms[0]
        if self.has_axis:
            moved = jnp.moveaxis(x, self.axis, -1)
            vals, idx = jax.lax.top_k(moved, self.top_k)
            pick = vals if self.out_max_val else idx.astype(x.dtype)
            return [jnp.moveaxis(pick, -1, self.axis).astype(x.dtype)]
        flat = x.reshape(x.shape[0], -1)
        vals, idx = jax.lax.top_k(flat, self.top_k)
        idxf = idx.astype(x.dtype)
        if self.out_max_val:
            return [jnp.stack([idxf, vals.astype(x.dtype)], axis=1)]
        return [idxf[:, None, :]]


@register
class Reduction(Layer):
    type_name = "Reduction"

    SUM, ASUM, SUMSQ, MEAN = 1, 2, 3, 4

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        rp = lp.reduction_param
        self.op = int(rp.operation)
        self.axis = self.canonical_axis(rp.axis)
        self.coeff = float(rp.coeff)

    def out_shapes(self):
        return [tuple(self.bottom_shapes[0][:self.axis])]

    def apply(self, params, bottoms, train, rng):
        x = bottoms[0]
        axes = tuple(range(self.axis, x.ndim))
        if self.op == self.SUM:
            y = jnp.sum(x, axis=axes)
        elif self.op == self.ASUM:
            y = jnp.sum(jnp.abs(x), axis=axes)
        elif self.op == self.SUMSQ:
            y = jnp.sum(x * x, axis=axes)
        else:
            y = jnp.mean(x, axis=axes)
        return [y * self.coeff]


@register
class Silence(Layer):
    """Consumes bottoms, produces nothing (silence_layer.cpp)."""

    type_name = "Silence"

    def out_shapes(self):
        return []

    def apply(self, params, bottoms, train, rng):
        return []


@register
class BatchReindex(Layer):
    """top = bottom[0] gathered by the (static-length) index blob bottom[1]
    (batch_reindex_layer.cpp)."""

    type_name = "BatchReindex"

    def out_shapes(self):
        return [tuple(self.bottom_shapes[1][:1]) +
                tuple(self.bottom_shapes[0][1:])]

    def apply(self, params, bottoms, train, rng):
        return [jnp.take(bottoms[0], bottoms[1].astype(jnp.int32), axis=0)]


@register
class Filter(Layer):
    """Selects batch items whose selector is nonzero (filter_layer.cpp),
    with CAPACITY-PADDED semantics — the documented deviation from Caffe.

    bottom[0..k-1] are the blobs to filter; bottom[k] is the selector:
    shape (N,) or (N, 1, ...) (singleton trailing dims, Reshape's CHECK).
    Caffe shrinks top batch to the selected count — a data-dependent
    shape, which XLA's static-shape compilation model cannot express.
    Here each top keeps the FULL input batch N: selected items are
    compacted to the front in stable order (matching Caffe's
    indices_to_forward_ order) and the tail rows are zero. One OPTIONAL
    extra top (declare k+1 tops) receives the valid count as a scalar so
    downstream consumers can mask: the standard XLA capacity-padding
    discipline (the same trick ops/moe.py uses for expert overflow).

    Backward is jax autodiff of the gather: cotangents scatter home to
    selected rows, zeros elsewhere — exactly filter_layer.cpp's
    Backward_cpu, with no hand-written index bookkeeping."""

    type_name = "Filter"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        sel = bottom_shapes[-1]
        if any(d != 1 for d in sel[1:]):
            raise ValueError(
                f"{lp.name}: selector dims past the first must be "
                f"singletons, got {tuple(sel)}")
        n = sel[0]
        for i, s in enumerate(bottom_shapes[:-1]):
            if s[0] != n:
                raise ValueError(
                    f"{lp.name}: bottom {i} batch {s[0]} != selector "
                    f"batch {n}")
        ndata = len(bottom_shapes) - 1
        if len(lp.top) not in (ndata, ndata + 1):
            raise ValueError(
                f"{lp.name}: Filter needs {ndata} tops (or {ndata + 1} "
                f"with the valid-count top), got {len(lp.top)}")
        self._with_count = len(lp.top) == ndata + 1

    def out_shapes(self):
        shapes = [tuple(s) for s in self.bottom_shapes[:-1]]
        if self._with_count:
            shapes.append(())
        return shapes

    def apply(self, params, bottoms, train, rng):
        sel = bottoms[-1].reshape(bottoms[-1].shape[0])
        keep = sel != 0
        n = keep.shape[0]
        # stable compaction: kept indices first, original order preserved
        order = jnp.argsort(jnp.logical_not(keep), stable=True)
        kmask = keep[order]                       # first count rows True
        tops = []
        for x in bottoms[:-1]:
            y = jnp.take(x, order, axis=0)
            y = y * kmask.reshape((n,) + (1,) * (y.ndim - 1)).astype(y.dtype)
            tops.append(y)
        if self._with_count:
            tops.append(jnp.sum(keep.astype(jnp.int32)))
        return tops
