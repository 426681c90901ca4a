"""Plain float32 reference for Caffe-style CNN training: forward, loss,
gradients and the solver's update (SGD with momentum, Adam) in
straightforward jax.numpy / lax, written from the published layer
definitions (Caffe's layer catalogue) and importing nothing of the program
under test. A reference of another kind of model is a file of its own with
`build(config, batch)`; `round_to`, `fake_quant` and `make_update` here are
for it too. `LayerList`, at the end, is what the harness is handed.

A net is the list of the published prototxt's TRAIN-phase layers in the
file's order (`reference/<config>.py` builds it; the accuracy layers, which
the files include in TEST only, are not in it). Shapes follow Caffe: NCHW blobs, conv
weights (out, in/group, kh, kw), inner-product weights (out, in), ceil-mode
pooling, across-channel LRN, SoftmaxWithLoss averaged over the batch.

Everything runs under `jax.default_matmul_precision("highest")` (the caller
sets it): on a TPU a float32 contraction is otherwise done in bf16 passes.
`quant`, when given, rounds the weights at their use and every layer's
output blob to that type (scaled per tensor), values on the way forward and
gradients on the way back, as a program would that kept its activations
and their gradients in it: the lower-precision
control of the correctness check, never the reference.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax


# ------------------------------------------------------------ net building

def conv(name, bottom, num_output, kernel, stride=1, pad=0, group=1,
         filler=("gaussian", 0.01), bias=0.0, top=None):
    return {"type": "conv", "name": name, "bottom": bottom,
            "top": top or name, "num_output": num_output, "kernel": kernel,
            "stride": stride, "pad": pad, "group": group, "filler": filler,
            "bias": bias}


def fc(name, bottom, num_output, filler=("gaussian", 0.01), bias=0.0):
    return {"type": "fc", "name": name, "bottom": bottom, "top": name,
            "num_output": num_output, "filler": filler, "bias": bias}


def relu(name, blob):
    return {"type": "relu", "name": name, "bottom": blob, "top": blob}


def pool(name, bottom, mode, kernel, stride, pad=0):
    return {"type": "pool", "name": name, "bottom": bottom, "top": name,
            "mode": mode, "kernel": kernel, "stride": stride, "pad": pad}


def lrn(name, bottom, size=5, alpha=1e-4, beta=0.75, k=1.0):
    return {"type": "lrn", "name": name, "bottom": bottom, "top": name,
            "size": size, "alpha": alpha, "beta": beta, "k": k}


def dropout(name, blob, ratio):
    return {"type": "dropout", "name": name, "bottom": blob, "top": blob,
            "ratio": ratio}


def concat(name, bottoms):
    return {"type": "concat", "name": name, "bottom": list(bottoms),
            "top": name}


def softmax_loss(name, bottom, weight=1.0):
    return {"type": "loss", "name": name, "bottom": bottom, "top": name,
            "weight": weight}


def feed(name):
    return {"type": "feed", "name": name, "top": name}


# ----------------------------------------------------------------- shapes

def _pool_out(size, k, s, p):
    out = int(math.ceil((size + 2 * p - k) / s)) + 1
    if p and (out - 1) * s >= size + p:
        out -= 1
    return out


def infer_shapes(layers, data_shape):
    """{blob: shape} for a batch of `data_shape` (N, C, H, W)."""
    shapes = {"data": tuple(data_shape), "label": (data_shape[0],)}
    for l in layers:
        t = l["type"]
        if t in ("feed", "loss"):
            continue
        if t == "concat":
            ins = [shapes[b] for b in l["bottom"]]
            shapes[l["top"]] = (ins[0][0], sum(s[1] for s in ins)) \
                + tuple(ins[0][2:])
            continue
        s = shapes[l["bottom"]]
        if t == "conv":
            k, st, p = l["kernel"], l["stride"], l["pad"]
            shapes[l["top"]] = (s[0], l["num_output"],
                                (s[2] + 2 * p - k) // st + 1,
                                (s[3] + 2 * p - k) // st + 1)
        elif t == "fc":
            shapes[l["top"]] = (s[0], l["num_output"])
        elif t == "pool":
            k, st, p = l["kernel"], l["stride"], l["pad"]
            shapes[l["top"]] = (s[0], s[1], _pool_out(s[2], k, st, p),
                                _pool_out(s[3], k, st, p))
        else:                               # relu, lrn, dropout
            shapes[l["top"]] = s
    return shapes


def param_specs(layers, data_shape):
    """[(layer name, [(shape, filler), (shape, ("constant", bias))])] for
    every layer with weights, in list order. A filler is ("gaussian", std),
    ("xavier",) — uniform(+-sqrt(3 / fan_in)), Caffe's default norm — or
    ("constant", value)."""
    shapes = infer_shapes(layers, data_shape)
    out = []
    for l in layers:
        if l["type"] == "conv":
            cin = shapes[l["bottom"]][1]
            w = (l["num_output"], cin // l["group"], l["kernel"],
                 l["kernel"])
        elif l["type"] == "fc":
            w = (l["num_output"], int(math.prod(shapes[l["bottom"]][1:])))
        else:
            continue
        out.append((l["name"], [(w, tuple(l["filler"])),
                                ((l["num_output"],),
                                 ("constant", l["bias"]))]))
    return out


def conv_fc_macs(layers, data_shape):
    """Multiply-accumulates of one forward pass over the batch, conv and
    inner product only (what an MFU counts)."""
    shapes = infer_shapes(layers, data_shape)
    macs = 0
    for l in layers:
        if l["type"] == "conv":
            n, co, ho, wo = shapes[l["top"]]
            ci = shapes[l["bottom"]][1]
            macs += n * co * ho * wo * (ci // l["group"]) * l["kernel"] ** 2
        elif l["type"] == "fc":
            n, co = shapes[l["top"]]
            macs += n * co * int(math.prod(shapes[l["bottom"]][1:]))
    return macs


# ---------------------------------------------------------------- forward

def round_to(x, dtype):
    """x rounded to a float `dtype`'s exponent and mantissa width, a type
    narrower than bfloat16 with a per-tensor scale first. By
    `lax.reduce_precision`: a convert there and back is removed by XLA:TPU
    (it allows excess precision), which left the control unrounded."""
    info = jnp.finfo(dtype)
    if info.nexp >= 8:          # float32's range: no scale needed
        return lax.reduce_precision(x, info.nexp, info.nmant)
    top = (2.0 - 2.0 ** -info.nmant) * 2.0 ** (2 ** (info.nexp - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return lax.reduce_precision(x / scale, info.nexp, info.nmant) * scale


def fake_quant(x, dtype):
    """A blob kept in `dtype`: its value is rounded on the way forward and
    its gradient on the way back, as in a program whose activations and
    their gradients live in that type."""
    @jax.custom_vjp
    def f(x):
        return round_to(x, dtype)
    f.defvjp(lambda x: (round_to(x, dtype), None),
             lambda _, g: (round_to(g, dtype),))
    return f(x)


def _max_pool(x, k, s, p, oh, ow):
    h, w = x.shape[2], x.shape[3]
    rh = max(0, (oh - 1) * s + k - p - h)
    rw = max(0, (ow - 1) * s + k - p - w)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, s, s),
                             ((0, 0), (0, 0), (p, rh), (p, rw)))


def _ave_pool(x, k, s, p, oh, ow):
    """Caffe's AVE: the divisor is the window clipped to [start, in + pad)
    with the raw start, so it differs at the borders."""
    h, w = x.shape[2], x.shape[3]
    rh = max(0, (oh - 1) * s + k - p - h)
    rw = max(0, (ow - 1) * s + k - p - w)
    sums = lax.reduce_window(x, 0.0, lax.add, (1, 1, k, k), (1, 1, s, s),
                             ((0, 0), (0, 0), (p, rh), (p, rw)))

    def counts(size, out):
        starts = jnp.arange(out) * s - p
        return (jnp.minimum(starts + k, size + p) - starts).astype(x.dtype)
    return sums / (counts(h, oh)[:, None] * counts(w, ow)[None, :])


def forward_loss(params, data, labels, layers, masks=None, quant=None):
    """Total training loss of `layers` on one block of rows. `masks` maps a
    dropout layer's name to its keep mask for these rows (None: no
    dropout). The loss is the SUM over rows of the weighted per-row losses:
    the caller divides by the whole batch."""
    def store(x):
        return x if quant is None else fake_quant(x, quant)
    blobs = {"data": store(data.astype(jnp.float32))}
    total = 0.0
    for l in layers:
        t = l["type"]
        if t == "feed":
            continue
        if t == "concat":
            blobs[l["top"]] = jnp.concatenate(
                [blobs[b] for b in l["bottom"]], axis=1)
            continue
        x = blobs[l["bottom"]]
        if t == "conv":
            w, b = params[l["name"]]
            if quant is not None:
                w = fake_quant(w, quant)
            y = lax.conv_general_dilated(
                x, w, (l["stride"],) * 2, [(l["pad"],) * 2] * 2,
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=l["group"])
            y = y + b[None, :, None, None]
        elif t == "fc":
            w, b = params[l["name"]]
            x = x.reshape(x.shape[0], -1)
            if quant is not None:
                w = fake_quant(w, quant)
            y = x @ w.T + b
        elif t == "relu":
            y = jnp.maximum(x, 0)
        elif t == "pool":
            k, s, p = l["kernel"], l["stride"], l["pad"]
            oh = _pool_out(x.shape[2], k, s, p)
            ow = _pool_out(x.shape[3], k, s, p)
            y = (_max_pool if l["mode"] == "MAX" else _ave_pool)(
                x, k, s, p, oh, ow)
        elif t == "lrn":
            half = (l["size"] - 1) // 2
            ssum = lax.reduce_window(
                x * x, 0.0, lax.add, (1, l["size"], 1, 1), (1, 1, 1, 1),
                ((0, 0), (half, l["size"] - 1 - half), (0, 0), (0, 0)))
            y = x * (l["k"] + (l["alpha"] / l["size"]) * ssum) \
                ** (-l["beta"])
        elif t == "dropout":
            if masks is None:
                y = x
            else:
                keep = 1.0 - l["ratio"]
                y = jnp.where(masks[l["name"]], x / keep, 0.0)
        elif t == "loss":
            logp = jax.nn.log_softmax(x, axis=-1)
            picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)
            total = total + l["weight"] * -jnp.sum(picked)
            continue
        else:
            raise ValueError(f"unknown layer type {t!r}")
        blobs[l["top"]] = store(y)
    return total


# --------------------------------------------------------------- training

def dropout_masks(layers, shapes, key):
    """Keep masks for a whole batch, as the configuration's
    `dropout_stream` describes them: the mask of the dropout layer at
    position i of the published layer list is
    bernoulli(fold_in(step key, i), 1 - ratio) over its blob."""
    return {l["name"]: jax.random.bernoulli(
                jax.random.fold_in(key, i), 1.0 - l["ratio"],
                shapes[l["top"]])
            for i, l in enumerate(layers) if l["type"] == "dropout"}


def make_update(solver, mults, masters=None):
    """-> update(params, history, grads) -> (params, history): one
    iteration of Caffe's solver of `solver["type"]` ("SGD" when absent) at
    a fixed rate. Every blob: L2 decay (weight_decay x decay_mult x w)
    added to its gradient, then
      SGD   h = momentum x h + lr x lr_mult x g;  w -= h
      Adam  m1 = b1 x m1 + (1 - b1) x g,  m2 = b2 x m2 + (1 - b2) x g^2;
            w -= lr x lr_mult x sqrt(1 - b2^t) / (1 - b1^t)
                 x m1 / (sqrt(m2) + delta),  t counted from 1
    (sgd_solver.cpp, adam_solver.cpp; b1, b2 = momentum, momentum2).
    `mults` is {layer: [(lr_mult, decay_mult) per blob]}. A history is
    (steps taken, {layer: [[slot, ...] per blob]}), None for a zero one;
    `masters` rounds the stored weights and slots to that type (the
    control's bfloat16 masters)."""
    kind = solver.get("type", "SGD")
    lr, wd = solver["base_lr"], solver["weight_decay"]
    if kind == "SGD":
        mom, n_slots = solver["momentum"], 1

        def one(g, slots, rate, t):
            h = mom * slots[0] + rate * g
            return h, [h]
    elif kind == "Adam":
        b1, b2 = solver["momentum"], solver["momentum2"]
        delta, n_slots = solver["delta"], 2

        def one(g, slots, rate, t):
            m1 = b1 * slots[0] + (1.0 - b1) * g
            m2 = b2 * slots[1] + (1.0 - b2) * g * g
            correction = jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
            return rate * correction * m1 / (jnp.sqrt(m2) + delta), [m1, m2]
    else:
        raise ValueError(f"reference/plain.py has no update for the solver "
                         f"type {kind!r}")

    @jax.jit
    def update(params, history, grads):
        taken, slots = history
        t = (taken + 1).astype(jnp.float32)
        new_p, new_s = {}, {}
        for name, blobs in params.items():
            ps, ss = [], []
            for i, w in enumerate(blobs):
                lr_mult, decay_mult = mults[name][i]
                g = grads[name][i] + wd * decay_mult * w
                u, s = one(g, slots[name][i], lr * lr_mult, t)
                w = w - u
                if masters is not None:
                    w = round_to(w, masters)
                    s = [round_to(x, masters) for x in s]
                ps.append(w)
                ss.append(s)
            new_p[name], new_s[name] = ps, ss
        return new_p, (taken + 1, new_s)

    def from_zero(params, history, grads):
        if history is None:
            history = (jnp.zeros((), jnp.int32), {
                name: [[w * 0 for _ in range(n_slots)] for w in blobs]
                for name, blobs in params.items()})
        return update(params, history, grads)
    return from_zero


def make_step(layers, data_shape, solver, mults, block_rows=None, quant=None,
              masters=None, with_dropout=True):
    """-> step(params, history, data, labels, key) -> (params, history,
    loss, grads). One iteration of Caffe's solver (`make_update`, which
    `mults` is for) on the gradients of the batch-mean loss. Gradients are
    accumulated over blocks of `block_rows` rows so that a float32 pass of
    the whole batch need not fit at once."""
    n = data_shape[0]
    rows = block_rows or n
    if n % rows:
        raise ValueError(f"block of {rows} rows does not divide batch {n}")
    shapes = infer_shapes(layers, data_shape)
    update = make_update(solver, mults, masters)

    @jax.jit
    def block_grad(params, data, labels, masks):
        def lf(p):
            return forward_loss(p, data, labels, layers, masks, quant) / n
        return jax.value_and_grad(lf)(params)

    def step(params, history, data, labels, key):
        masks = dropout_masks(layers, shapes, key) if with_dropout else None
        loss, grads = 0.0, None
        for lo in range(0, n, rows):
            m = None if masks is None else \
                {k: v[lo:lo + rows] for k, v in masks.items()}
            l, g = block_grad(params, data[lo:lo + rows],
                              labels[lo:lo + rows], m)
            loss = loss + l
            grads = g if grads is None else \
                jax.tree_util.tree_map(jnp.add, grads, g)
        params, history = update(params, history, grads)
        return params, history, loss, grads

    return step


# ---------------------------------------------- what the harness is handed

class LayerList:
    """A `reference/<name>.py` that gives a layer list (`layers()` and
    `data_shape()`, no `build`) as the object the harness is handed: what a
    module's own `build(config, batch)` returns.

    specs      [(layer, [(shape, filler, (lr_mult, decay_mult)) per blob])]
    inputs     [(name, shape, type)] of what a step is fed
    make_step  (solver, block_rows, quant, masters) -> step(params,
               history, data, labels, key) -> (params, history, loss,
               grads); the history is the reference's own, None at the
               first step
    """

    def __init__(self, layers, data_shape, solver):
        self.layers, self.data_shape = layers, tuple(data_shape)
        # a layer list's weights take (lr_mult, decay_mult) =
        # solver["weight_mults"], its biases solver["bias_mults"]
        pair = (tuple(solver["weight_mults"]), tuple(solver["bias_mults"]))
        self.specs = [
            (name, [(shape, filler, pair[i])
                    for i, (shape, filler) in enumerate(blobs)])
            for name, blobs in param_specs(layers, data_shape)]
        self.inputs = [("data", self.data_shape, "float32"),
                       ("label", (self.data_shape[0],), "int32")]

    def make_step(self, solver, block_rows=None, quant=None, masters=None):
        mults = {name: [b[2] for b in blobs] for name, blobs in self.specs}
        return make_step(self.layers, self.data_shape, solver, mults,
                         block_rows=block_rows, quant=quant, masters=masters)
