"""Loss and metric layers.

Each reproduces the corresponding reference layer's scalar semantics exactly
(normalization divisors included) so that loss curves and iters-to-accuracy
are comparable:
  SoftmaxWithLoss  softmax_loss_layer.cpp:51-82 (FLT_MIN clamp, /count or /outer)
  EuclideanLoss    euclidean_loss_layer.cpp (sum sq diff / 2N)
  HingeLoss        hinge_loss_layer.cpp (L1 / squared-L2 margin sum / N)
  SigmoidCrossEntropyLoss  sigmoid_cross_entropy_loss_layer.cpp (/N, stable form)
  MultinomialLogisticLoss  multinomial_logistic_loss_layer.cpp (1e-20 clamp)
  InfogainLoss     infogain_loss_layer.cpp (H matrix from file or bottom[2])
  ContrastiveLoss  contrastive_loss_layer.cpp (legacy_version switch)
  Accuracy         accuracy_layer.cpp (top-k membership, ignore_label)
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..graph.registry import Layer, register

FLT_MIN = np.float32(1.1754944e-38)
LOG_THRESHOLD = 1e-20


def _outer_inner(shape, axis):
    outer = int(np.prod(shape[:axis], dtype=np.int64))
    inner = int(np.prod(shape[axis + 1:], dtype=np.int64))
    return outer, inner


class _Loss(Layer):
    loss_like = True

    def out_shapes(self):
        return [()]


CHUNK_BYTES = 1 << 28       # float32 logits above this go block by block


def _row_chunk(rows, classes):
    """Rows a block for `SoftmaxWithLoss` when float32 logits of (rows,
    classes) pass CHUNK_BYTES: the largest divisor of `rows` whose block
    stays under half of it; 0 = all at once (every CNN head)."""
    if rows * classes * 4 <= CHUNK_BYTES:
        return 0
    limit = max(1, CHUNK_BYTES // 2 // (classes * 4))
    return next((r for r in range(min(limit, rows), 0, -1)
                 if rows % r == 0), 0)


@register
class SoftmaxWithLoss(_Loss):
    type_name = "SoftmaxWithLoss"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        self.axis = self.canonical_axis(lp.softmax_param.axis)
        loss_param = lp.loss_param
        self.normalize = bool(loss_param.normalize)
        self.ignore_label = loss_param.ignore_label \
            if loss_param.has("ignore_label") else None

    def apply(self, params, bottoms, train, rng):
        x, label = bottoms[0], bottoms[1]
        outer, inner = _outer_inner(x.shape, self.axis)
        c = x.shape[self.axis]
        # softmax over self.axis, gathered at the label
        xm = jnp.moveaxis(x, self.axis, -1).reshape(outer * inner, c)
        lab = label.reshape(outer, inner)
        # label memory order is (outer, inner); xm rows are (outer, inner)
        # after moveaxis+reshape? moveaxis gives (outer..., inner..., C) ->
        # rows enumerate outer-major, inner-minor: matches (i * inner + j).
        lab_flat = lab.reshape(-1).astype(jnp.int32)

        def pick(xm, lab_flat):
            logp = jax.nn.log_softmax(xm.astype(jnp.float32), axis=-1)
            # Caffe clamps prob at FLT_MIN -> logp at log(FLT_MIN)
            return jnp.maximum(
                jnp.take_along_axis(logp, lab_flat[:, None], axis=-1)[:, 0],
                np.log(FLT_MIN))
        rows = _row_chunk(outer * inner, c)
        if rows:
            # a language model's logits (tokens x vocabulary): the float32
            # log-softmax and its cotangent are made a block of rows at a
            # time and never stand whole; the backward recomputes a block
            picked = jax.lax.map(
                lambda a: jax.checkpoint(pick)(*a),
                (xm.reshape(-1, rows, c), lab_flat.reshape(-1, rows))
            ).reshape(-1)
        else:
            picked = pick(xm, lab_flat)
        if self.ignore_label is not None:
            valid = (lab_flat != self.ignore_label)
            picked = jnp.where(valid, picked, 0.0)
            count = jnp.maximum(jnp.sum(valid), 1)
        else:
            count = outer * inner
        total = -jnp.sum(picked)
        denom = count if self.normalize else outer
        return [total / denom]


@register
class EuclideanLoss(_Loss):
    type_name = "EuclideanLoss"

    def apply(self, params, bottoms, train, rng):
        a, b = bottoms[0], bottoms[1]
        n = a.shape[0]
        d = (a - b).astype(jnp.float32)
        return [jnp.sum(d * d) / (2.0 * n)]


@register
class HingeLoss(_Loss):
    type_name = "HingeLoss"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        self.norm = int(lp.hinge_loss_param.norm)  # 1=L1, 2=L2

    def apply(self, params, bottoms, train, rng):
        x, label = bottoms[0], bottoms[1]
        n = x.shape[0]
        flat = x.reshape(n, -1).astype(jnp.float32)
        lab = label.reshape(n).astype(jnp.int32)
        sign = jnp.ones_like(flat).at[jnp.arange(n), lab].set(-1.0)
        margins = jnp.maximum(0.0, 1.0 + sign * flat)
        if self.norm == 2:
            return [jnp.sum(margins * margins) / n]
        return [jnp.sum(margins) / n]


@register
class SigmoidCrossEntropyLoss(_Loss):
    type_name = "SigmoidCrossEntropyLoss"

    def apply(self, params, bottoms, train, rng):
        x, t = bottoms[0].astype(jnp.float32), bottoms[1].astype(jnp.float32)
        n = x.shape[0]
        # stable: loss = -[x*(t - (x>=0)) - log(1 + exp(x - 2x*(x>=0)))]
        pos = (x >= 0)
        loss = x * (t - pos) - jnp.log1p(jnp.exp(x - 2 * x * pos))
        return [-jnp.sum(loss) / n]


@register
class MultinomialLogisticLoss(_Loss):
    type_name = "MultinomialLogisticLoss"

    def apply(self, params, bottoms, train, rng):
        prob, label = bottoms[0], bottoms[1]
        n = prob.shape[0]
        flat = prob.reshape(n, -1).astype(jnp.float32)
        lab = label.reshape(n).astype(jnp.int32)
        p = jnp.take_along_axis(flat, lab[:, None], axis=1)[:, 0]
        return [-jnp.sum(jnp.log(jnp.maximum(p, LOG_THRESHOLD))) / n]


@register
class InfogainLoss(_Loss):
    type_name = "InfogainLoss"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        self.H = None
        src = lp.infogain_loss_param.source \
            if lp.has("infogain_loss_param") else None
        if len(bottom_shapes) < 3:
            if not src:
                raise ValueError("InfogainLoss needs a source file or 3rd bottom")
            from ..proto import wire
            blob = wire.load(src, "BlobProto")
            dims = list(blob.shape.dim) if blob.has("shape") else \
                [blob.num, blob.channels, blob.height, blob.width]
            self.H = np.asarray(list(blob.data), np.float32).reshape(
                [d for d in dims if d] or [-1])
            self.H = self.H.reshape(self.H.shape[-2], self.H.shape[-1]) \
                if self.H.ndim > 2 else self.H

    def apply(self, params, bottoms, train, rng):
        prob, label = bottoms[0], bottoms[1]
        H = jnp.asarray(self.H) if self.H is not None else bottoms[2]
        H = H.reshape(H.shape[-2], H.shape[-1]) if H.ndim > 2 else H
        n = prob.shape[0]
        flat = prob.reshape(n, -1).astype(jnp.float32)
        lab = label.reshape(n).astype(jnp.int32)
        logp = jnp.log(jnp.maximum(flat, LOG_THRESHOLD))
        rows = jnp.take(H.astype(jnp.float32), lab, axis=0)
        return [-jnp.sum(rows * logp) / n]


@register
class ContrastiveLoss(_Loss):
    type_name = "ContrastiveLoss"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        p = lp.contrastive_loss_param
        self.margin = float(p.margin)
        self.legacy = bool(p.legacy_version)

    def apply(self, params, bottoms, train, rng):
        a, b, y = bottoms[0], bottoms[1], bottoms[2]
        n = a.shape[0]
        d = (a - b).astype(jnp.float32).reshape(n, -1)
        dist_sq = jnp.sum(d * d, axis=1)
        y = y.reshape(n).astype(jnp.float32)
        if self.legacy:
            dissim = jnp.maximum(self.margin - dist_sq, 0.0)
        else:
            dissim = jnp.maximum(self.margin - jnp.sqrt(dist_sq), 0.0) ** 2
        loss = y * dist_sq + (1.0 - y) * dissim
        return [jnp.sum(loss) / (2.0 * n)]


@register
class Accuracy(Layer):
    """Top-k accuracy metric (not part of the objective: loss_weight 0)."""

    type_name = "Accuracy"

    def __init__(self, lp, bottom_shapes, phase):
        super().__init__(lp, bottom_shapes, phase)
        ap = lp.accuracy_param
        self.top_k = int(ap.top_k)
        self.axis = self.canonical_axis(ap.axis)
        self.ignore_label = ap.ignore_label if ap.has("ignore_label") else None

    def out_shapes(self):
        return [()]

    def apply(self, params, bottoms, train, rng):
        x, label = bottoms[0], bottoms[1]
        outer, inner = _outer_inner(x.shape, self.axis)
        c = x.shape[self.axis]
        xm = jnp.moveaxis(x, self.axis, -1).reshape(outer * inner, c)
        lab = label.reshape(-1).astype(jnp.int32)
        _, topk = jax.lax.top_k(xm, self.top_k)
        hit = jnp.any(topk == lab[:, None], axis=1)
        if self.ignore_label is not None:
            valid = lab != self.ignore_label
            correct = jnp.sum(jnp.where(valid, hit, False))
            count = jnp.maximum(jnp.sum(valid), 1)
        else:
            correct = jnp.sum(hit)
            count = outer * inner
        return [correct.astype(jnp.float32) / count]
