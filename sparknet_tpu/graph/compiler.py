"""NetParameter -> pure init/apply functions: the graph compiler.

The TPU-native replacement for Caffe's Net runtime (reference net.cpp:
FilterNet :287, split insertion :54, AppendTop/Bottom :385/:444, param
ownership & sharing, ForwardFromTo :565). Differences born of the platform:

  * No Split insertion — autodiff accumulates fan-out gradients natively.
  * No Backward graph — ``jax.grad`` of the compiled loss is the backward.
  * In-place ops (ReLU with top==bottom) are SSA rebinds of the blob name.
  * Data layers are feeds (see ops.feed): the compiled step takes a
    ``batch`` dict; nothing inside the graph performs IO.
  * BatchNorm-style mutable blobs are explicit functional state threaded
    through ``apply`` (Caffe mutates blobs_ in place).

The whole forward (and the grad through it) traces into ONE XLA program:
layer fusion, scheduling and memory planning are XLA's job, per the
compilation model in /opt/skills/guides (trace once, static shapes).
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..proto.message import Message
from . import fillers as F
from .registry import get as get_layer, V1_TYPE_MAP
from .remat import KERNEL_OUT
from ..obs.trace import kernel_import

# import for registration side effects
from .. import ops as _ops  # noqa: F401

TRAIN, TEST = 0, 1

REMAT_POLICIES = ("none", "dots", "full")

#: The part of a step that a layer's device time is counted under, by the
#: layer's TYPE: what `CompiledNet.parts()` says of every layer and the
#: solver writes into the ring as `net.parts`, so that whoever adds up a
#: device trace (benchmark/step_parts.py) needs no model's layer names.
#: The list is closed: `conv`, `pool`, `lrn`, `norm`, `final_norm` (a norm
#: read by heads alone), `proj` (an InnerProduct), `head` (one whose top is
#: a loss layer's first bottom), `embed`, `residual` (an Eltwise SUM), `act`
#: (activations, dropout, softmax, an Eltwise PROD or MAX), `shape`
#: (concat, slice, reshape and their kin), `loss` (with accuracy), `other`
#: (a type not listed here). A mixer or an MoE layer counts under the
#: scopes it opens inside its own: `attn_proj_in`, `rope`, `attn_core`,
#: `attn_proj_out` (ops/attention.py); `gdn_proj_in`, `gdn_conv`,
#: `gdn_scan`, `gdn_gate_norm`, `gdn_proj_out` (ops/deltanet.py);
#: `shortconv_in`, `shortconv_mix`, `shortconv_out` (ops/shortconv.py);
#: `ssm_proj_in`, `ssm_conv`, `ssm_scan`, `ssm_gate_norm`, `ssm_proj_out`
#: (ops/mamba2.py);
#: `moe_route`, `moe_dispatch`, `moe_experts`, `moe_combine`, `moe_shared`,
#: `moe_glue` (ops/moe.py); what such a layer traces outside them counts
#: under `attn`, `gdn`, `shortconv`, `ssm`, `moe` and should read nothing (the
#: Switch form of the MoE opens no scope and reads as `moe`). Beside the
#: layers a step has `input_transform`, `total_loss` (the weighted sum of
#: the loss tops), `grad_accum` (iter_size's micro-batches), `update`,
#: `grad_exchange` (parallel/data_parallel.py: the gradients' all-reduce
#: with the buckets laid flat and cut up again round it), and
#: `layer_scan.<first block>` round a scan over blocks (`_apply_scan`).
PART_OF_TYPE = {
    "Convolution": "conv", "Deconvolution": "conv", "Im2col": "conv",
    "Pooling": "pool", "SPP": "pool", "LRN": "lrn",
    "BatchNorm": "norm", "LayerNorm": "norm", "RMSNorm": "norm",
    "MVN": "norm", "InnerProduct": "proj", "Embed": "embed",
    "PositionalEmbed": "embed", "Eltwise": "residual",
    "Attention": "attn", "GatedDeltaNet": "gdn", "ShortConv": "shortconv",
    "Mamba2": "ssm", "MoE": "moe",
    **dict.fromkeys(("ReLU", "PReLU", "Sigmoid", "TanH", "BNLL", "AbsVal",
                     "Power", "Exp", "Log", "Threshold", "Dropout",
                     "Softmax"), "act"),
    **dict.fromkeys(("Concat", "Slice", "Split", "Flatten", "Reshape",
                     "Tile", "ArgMax", "Reduction", "Silence",
                     "BatchReindex", "Filter", "Shift"), "shape"),
    **dict.fromkeys(("SoftmaxWithLoss", "EuclideanLoss", "HingeLoss",
                     "SigmoidCrossEntropyLoss", "MultinomialLogisticLoss",
                     "InfogainLoss", "ContrastiveLoss", "Accuracy"), "loss"),
}


def _env_remat():
    """SPARKNET_REMAT -> policy name. Back-compat: "0"/"1" mean
    none/full (the original boolean env var)."""
    import os
    v = os.environ.get("SPARKNET_REMAT", "").lower()
    pol = {"": "none", "0": "none", "none": "none",
           "1": "full", "full": "full", "dots": "dots"}.get(v)
    if pol is None:
        raise ValueError(
            f"SPARKNET_REMAT={v!r}: want none|dots|full (or 0/1)")
    return pol


def _env_precision():
    """SPARKNET_PRECISION -> compute dtype (the --precision CLI knob):
    "bf16" runs activations in bfloat16 with fp32 master weights
    (Micikevicius et al., 2018); "fp32"/unset is None — the untouched
    full-precision path, bit for bit."""
    import os
    v = os.environ.get("SPARKNET_PRECISION", "").strip().lower()
    if v in ("bf16", "bfloat16"):
        return jnp.bfloat16
    if v in ("", "fp32", "float32", "off"):
        return None
    raise ValueError(f"SPARKNET_PRECISION={v!r}: want bf16|fp32")


def _checkpointed(fn, pol):
    """Wrap fn in jax.checkpoint under the named remat policy: "full"
    recomputes everything in the backward, "dots" saves matmul/conv
    outputs and recomputes the cheap elementwise tails (the standard
    memory/FLOPs middle ground for transformer blocks). Under both, what a
    kernel's wrapper named `KERNEL_OUT` (graph/remat.py: a flash pass's
    output and logsumexp, the delta rule's output, states and inverses)
    is kept, so a pallas kernel's forward runs once, not a second time
    for its own backward: one attention output a layer (and 4 bytes a
    row a head) stays live across the whole backward pass, memory that
    plain jax.checkpoint(fn) did not hold."""
    kept = jax.checkpoint_policies.save_only_these_names(KERNEL_OUT)
    if pol == "dots":
        # a pallas call is no dot: without the name "dots" runs it twice
        kept = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots, kept)
    return jax.checkpoint(fn, policy=kept)


def upgrade_v1(net_param):
    """Upgrade legacy V1 'layers' to V2 'layer' entries (the capability of
    reference util/upgrade_proto.cpp, re-derived from the schema mapping)."""
    if not net_param.layers:
        return net_param
    out = net_param.copy()
    out.clear("layers")
    for v1 in net_param.layers:
        lp = out.add("layer")
        if v1.has("name"):
            lp.name = v1.name
        if v1.has("type"):
            lp.type = V1_TYPE_MAP[v1.enum_name("type")]
        lp.bottom.extend(v1.bottom)
        lp.top.extend(v1.top)
        lp.loss_weight.extend(v1.loss_weight)
        for r in v1.include:
            lp.include.append(r.copy())
        for r in v1.exclude:
            lp.exclude.append(r.copy())
        for b in v1.blobs:
            lp.blobs.append(b.copy())
        # blobs_lr / weight_decay pairs -> ParamSpecs
        n = max(len(v1.blobs_lr), len(v1.weight_decay))
        for i in range(n):
            ps = lp.add("param")
            if i < len(v1.blobs_lr):
                ps.lr_mult = v1.blobs_lr[i]
            if i < len(v1.weight_decay):
                ps.decay_mult = v1.weight_decay[i]
        for f in ("accuracy_param", "argmax_param", "concat_param",
                  "contrastive_loss_param", "convolution_param", "data_param",
                  "dropout_param", "dummy_data_param", "eltwise_param",
                  "exp_param", "hdf5_data_param", "hdf5_output_param",
                  "hinge_loss_param", "image_data_param",
                  "infogain_loss_param", "inner_product_param", "lrn_param",
                  "memory_data_param", "mvn_param", "pooling_param",
                  "power_param", "relu_param", "sigmoid_param",
                  "softmax_param", "slice_param", "tanh_param",
                  "threshold_param", "window_data_param", "transform_param",
                  "loss_param"):
            if v1.has(f):
                setattr(lp, f, getattr(v1, f).copy())
    return out


def _rule_matches(rule, state):
    """NetStateRule vs NetState (reference net.cpp StateMeetsRule)."""
    if rule.has("phase") and rule.phase != state.phase:
        return False
    if rule.has("min_level") and state.level < rule.min_level:
        return False
    if rule.has("max_level") and state.level > rule.max_level:
        return False
    stages = set(state.stage)
    for s in rule.stage:
        if s not in stages:
            return False
    for s in rule.not_stage:
        if s in stages:
            return False
    return True


def filter_net(net_param, phase, level=0, stages=()):
    """Phase/level/stage filtering (reference net.cpp FilterNet :287)."""
    state = Message("NetState", phase=phase, level=level, stage=list(stages))
    out = net_param.copy()
    out.clear("layer")
    for lp in net_param.layer:
        inc = lp.include
        exc = lp.exclude
        if inc and exc:
            raise ValueError(f"layer {lp.name}: both include and exclude rules")
        keep = True
        if inc:
            keep = any(_rule_matches(r, state) for r in inc)
        elif exc:
            keep = not any(_rule_matches(r, state) for r in exc)
        if keep and lp.has("phase") and lp.phase != phase:
            keep = False
        if keep:
            out.layer.append(lp.copy())
    return out


class CompiledNet:
    """A phase-specific executable net.

    build: shape-infers every blob, instantiates layer impls, and indexes
    params (with cross-layer sharing via ParamSpec.name, reference net.cpp
    AppendParam).

      init(rng)                      -> (params, state)
      apply(params, state, batch, train=..., rng=...) -> (blobs, new_state)
      loss_fn(params, state, batch, rng)  -> loss, (blobs, new_state)

    params:  {layer_name: [jnp arrays]}   (owning layers only)
    state:   {layer_name: [jnp arrays]}   (e.g. BatchNorm running stats)
    blobs:   {blob_name: array} after the full forward
    """

    def __init__(self, net_param, phase=TRAIN, feed_shapes=None,
                 dtype=jnp.float32, level=0, stages=(), compute_dtype=None):
        from .upgrade import upgrade_net
        net_param = upgrade_net(net_param)
        self.phase = phase
        self.dtype = dtype
        # mixed precision: params stay `dtype` (f32 masters for the
        # optimizer), activations run `compute_dtype` (bf16 drives the
        # MXU at full rate). Layers cast weights to their input's dtype,
        # so the cast only needs to happen where activations are BORN
        # from params alone — the embedding lookups (ops/dense.py Embed).
        # Float feeds choose their own dtype at the batch boundary.
        # None defers to the SPARKNET_PRECISION env var (the --precision
        # knob), resolved HERE so per-shard twin nets built from
        # net.compute_dtype inherit the resolved policy.
        self.compute_dtype = compute_dtype if compute_dtype is not None \
            else _env_precision()
        self.net_param = filter_net(net_param, phase, level, stages)
        self.name = net_param.name
        feed_shapes = dict(feed_shapes or {})

        self.layers = []          # [(lp, impl, bottoms, tops)]
        self.param_refs = {}      # layer_name -> [(owner_name, idx)]
        self.param_meta = {}      # (owner, idx) -> (shape, filler, lr, decay)
        self.loss_weights = {}    # layer_name -> [w per top]
        shared = {}               # ParamSpec.name -> (owner, idx)
        blob_shapes = {}
        available = {}            # blob name -> producing layer (output tracking)

        # net-level inputs (deploy nets: net.input + input_shape/input_dim)
        self.net_inputs = list(self.net_param.input)
        if self.net_inputs:
            if self.net_param.input_shape:
                in_shapes = [tuple(int(d) for d in s.dim)
                             for s in self.net_param.input_shape]
            else:
                dims = [int(d) for d in self.net_param.input_dim]
                in_shapes = [tuple(dims[i:i + 4])
                             for i in range(0, len(dims), 4)]
            for nm, s in zip(self.net_inputs, in_shapes):
                blob_shapes[nm] = s
                available[nm] = "__input__"

        for li, lp in enumerate(self.net_param.layer):
            cls = get_layer(lp.type)
            bottoms = list(lp.bottom)
            tops = list(lp.top)
            for b in bottoms:
                if b not in blob_shapes:
                    raise ValueError(
                        f"layer {lp.name!r}: bottom {b!r} is undefined")
            bshapes = [blob_shapes[b] for b in bottoms]
            if getattr(cls, "is_feed", False):
                impl = cls(lp, bshapes, phase, feed_shapes=feed_shapes)
            else:
                impl = cls(lp, bshapes, phase)
            impl.compute_dtype = compute_dtype
            tshapes = impl.out_shapes()
            if len(tops) < len(tshapes) and impl.loss_like:
                # Caffe auto-top (net.cpp AppendTop, gated on
                # AutoTopBlobs() == loss layers only): a LOSS layer may
                # declare fewer tops than it produces — commonly none
                # (pascal_finetune's SoftmaxWithLoss) — and the missing
                # blobs get automatic names derived from the layer. For
                # any other layer type an under-declaration stays a hard
                # error (it is almost certainly a typo'd prototxt).
                auto = [lp.name if len(tshapes) - len(tops) == 1
                        else f"{lp.name}_top{i}"
                        for i in range(len(tops), len(tshapes))]
                tops = tops + auto
                lp.top.extend(auto)
            if len(tshapes) != len(tops):
                raise ValueError(
                    f"layer {lp.name!r} ({lp.type}): {len(tops)} tops declared "
                    f"but impl produces {len(tshapes)}")
            for b in bottoms:
                available.pop(b, None)
            for t, s in zip(tops, tshapes):
                blob_shapes[t] = tuple(s)
                available[t] = lp.name
            self.layers.append((lp, impl, bottoms, tops))

            # params (with sharing)
            refs = []
            pshapes = impl.param_shapes()
            for i, (shape, filler, lr_mult, decay_mult) in enumerate(pshapes):
                pname = lp.param[i].name if i < len(lp.param) and \
                    lp.param[i].has("name") else ""
                if pname and pname in shared:
                    owner = shared[pname]
                    oshape = self.param_meta[owner][0]
                    if int(np.prod(oshape)) != int(np.prod(shape)):
                        raise ValueError(
                            f"shared param {pname!r}: count mismatch")
                    refs.append(owner)
                else:
                    key = (lp.name, i)
                    self.param_meta[key] = (tuple(shape), filler,
                                            float(lr_mult), float(decay_mult))
                    if pname:
                        shared[pname] = key
                    refs.append(key)
            self.param_refs[lp.name] = refs

            # loss weights (reference layer.hpp SetLossWeights: *Loss layers
            # default top[0] weight to 1)
            ws = list(lp.loss_weight)
            if not ws:
                ws = [1.0] + [0.0] * (len(tops) - 1) if impl.loss_like \
                    else [0.0] * len(tops)
            elif len(ws) != len(tops):
                raise ValueError(f"layer {lp.name}: loss_weight count mismatch")
            self.loss_weights[lp.name] = ws

        self.blob_shapes = blob_shapes
        # net outputs: produced and never consumed (net.cpp:270-284)
        self.output_blobs = [b for b, l in available.items()
                             if l != "__input__"]
        # perf knobs, settable per-net (Solver.set_remat / CLI --remat);
        # None defers to the SPARKNET_REMAT / SPARKNET_SCAN env vars at
        # trace time
        self.remat = None
        self.scan = None
        # virtual channel-concats (graph/fission.py); False compiles the
        # literal graph, which tests/test_fission.py compares against
        self.fission = True
        self._scan_cache = None
        self._epilogue_cache = None

    # -- feeds -------------------------------------------------------------
    def feed_blobs(self):
        """Blob names the batch dict must provide."""
        names = list(self.net_inputs)
        for lp, impl, bottoms, tops in self.layers:
            if getattr(impl, "is_feed", False):
                names.extend(tops)
        return names

    def feed_shapes(self):
        return {n: self.blob_shapes[n] for n in self.feed_blobs()}

    def parts(self):
        """{layer name: part} of every layer that is no feed, from
        `PART_OF_TYPE` and the two things a type cannot say: which
        InnerProduct is a head (its top is a loss layer's first bottom),
        and which norm is the final one (heads alone read it)."""
        parts, readers = {}, {}
        for lp, impl, bottoms, tops in self.layers:
            if getattr(impl, "is_feed", False):
                continue
            part = PART_OF_TYPE.get(lp.type, "other")
            if lp.type == "Eltwise" and impl.op != impl.SUM:
                part = "act"
            parts[lp.name] = part
            for i, b in enumerate(bottoms):
                readers.setdefault(b, []).append((lp.name, impl, i))
        tops_of = {lp.name: tops for lp, _, _, tops in self.layers}
        for name in [n for n, p in parts.items() if p == "proj"]:
            if any(impl.loss_like and i == 0
                   for t in tops_of[name] for _, impl, i in readers.get(t, ())):
                parts[name] = "head"
        for name in [n for n, p in parts.items() if p == "norm"]:
            read_by = [r for t in tops_of[name]
                       for r, _, _ in readers.get(t, ())]
            if read_by and all(parts[r] == "head" for r in read_by):
                parts[name] = "final_norm"
        return parts

    def prediction_depths(self):
        """A language model's further prediction depths (models/zoo.py:
        `_lm_mtp`), read off the net: [(loss layer, its loss weight)] of
        every loss whose logits come from a layer that owns no blob and
        borrows those of an earlier loss's logits layer — a second head on
        the first one's matrix. A head tied to the embedding's table alone
        is none."""
        made_by = {t: lp.name for lp, _, _, tops in self.layers for t in tops}
        seen, depths = set(), []
        for lp, impl, bottoms, _ in self.layers:
            head = made_by.get(bottoms[0]) if impl.loss_like and bottoms \
                else None
            if head is None:
                continue
            refs = self.param_refs[head]
            if refs and all(k[0] != head and k in seen for k in refs):
                depths.append((lp.name, self.loss_weights[lp.name][0]))
            seen.update(refs)
        return depths

    def shared_params(self):
        """The ParamSpec names that more than one layer gives, sorted."""
        users = {}
        for lp, _, _, _ in self.layers:
            for spec in lp.param:
                if spec.has("name"):
                    users.setdefault(spec.name, set()).add(lp.name)
        return sorted(n for n, ls in users.items() if len(ls) > 1)

    # -- init --------------------------------------------------------------
    def init(self, rng):
        params, state = {}, {}
        keys_needed = sorted(self.param_meta.keys())
        keys = jax.random.split(rng, max(1, len(keys_needed)))
        key_of = dict(zip(keys_needed, keys))
        for lp, impl, bottoms, tops in self.layers:
            owned = [k for k in self.param_refs[lp.name] if k[0] == lp.name]
            if owned:
                blobs = []
                for key in owned:
                    shape, filler, lr, decay = self.param_meta[key]
                    blobs.append(F.fill(key_of[key], shape, filler,
                                        self.dtype))
                params[lp.name] = blobs
            ss = impl.state_shapes()
            if ss:
                state[lp.name] = [jnp.full(shape, val, self.dtype)
                                  for shape, val in ss]
        # pretrained blobs embedded in the prototxt (LayerParameter.blobs)
        self._load_embedded_blobs(params)
        return params, state

    def _load_embedded_blobs(self, params):
        for lp, impl, bottoms, tops in self.layers:
            if lp.blobs and lp.name in params:
                for i, bp in enumerate(lp.blobs):
                    if i < len(params[lp.name]):
                        arr = blob_to_array(bp)
                        params[lp.name][i] = jnp.asarray(
                            arr.reshape(params[lp.name][i].shape), self.dtype)

    def resolve_params(self, params, layer_name):
        out = []
        for owner, idx in self.param_refs[layer_name]:
            out.append(params[owner][idx])
        return out

    # -- forward -----------------------------------------------------------
    def _remat_groups(self):
        """Rematerialization segments: maximal runs of >= 2 consecutive
        layers sharing a name prefix before "/" (the zoo's "block{i}/..."
        convention). Cached; used only when SPARKNET_REMAT is on."""
        if getattr(self, "_remat_cache", None) is not None:
            return self._remat_cache
        groups = {}
        start = None
        prefix = None
        for li, (lp, impl, bottoms, tops) in enumerate(self.layers):
            p = lp.name.split("/")[0] if "/" in lp.name else None
            if p != prefix:
                if prefix is not None and li - start >= 2:
                    groups[start] = li
                start, prefix = li, p
        if prefix is not None and len(self.layers) - start >= 2:
            groups[start] = len(self.layers)
        self._remat_cache = groups
        return groups

    def _epilogue_plan(self):
        """Fusable conv-epilogue sites, cached: {conv_idx: (relu_idx,
        lrn_idx | None)}.

        A site is Convolution (bias_term, single top) immediately
        followed by a zero-slope in-place ReLU on the same blob — the
        zoo/prototxt idiom — optionally followed by an adjacent 4D
        ACROSS_CHANNELS LRN reading that blob. The LRN extension only
        qualifies when nothing ELSE reads the relu'd blob (no later
        consumer, no loss weight, not a net output): the fused kernel
        never materializes it, and the remat discipline applies — absent,
        never stale."""
        if self._epilogue_cache is not None:
            return self._epilogue_cache
        plan = {}
        nl = len(self.layers)
        for ci in range(nl - 1):
            lp, impl, bottoms, tops = self.layers[ci]
            if getattr(impl, "type_name", None) != "Convolution" \
                    or not impl.bias_term or len(tops) != 1 \
                    or any(self.loss_weights[lp.name]):
                continue
            top = tops[0]
            rlp, rimpl, rbot, rtop = self.layers[ci + 1]
            if getattr(rimpl, "type_name", None) != "ReLU" \
                    or rbot != [top] or rtop != [top] \
                    or any(self.loss_weights[rlp.name]):
                continue
            if rlp.has("relu_param") and rlp.relu_param.negative_slope:
                continue
            plan[ci] = (ci + 1, None)
            if ci + 2 >= nl or len(self.blob_shapes[top]) != 4:
                continue
            llp, limpl, lbot, ltop = self.layers[ci + 2]
            if getattr(limpl, "type_name", None) != "LRN" or limpl.within \
                    or lbot != [top]:
                continue
            later = sum(b == top for lj in range(ci + 3, nl)
                        for b in self.layers[lj][2])
            if later == 0 and top not in self.output_blobs:
                plan[ci] = (ci + 1, ci + 2)
        self._epilogue_cache = plan
        return plan

    def _active_epilogue(self):
        """The epilogue sites the SPARKNET_EPILOGUE policy enables for
        this trace: off — none; auto — only the 3-op bias+ReLU+LRN
        fusion, on TPU (plain bias+ReLU is already XLA's conv epilogue,
        and a pallas boundary there costs an extra HBM pass — the
        pallas-LRN lesson from PERF.md round-3); on — every site, any
        backend (CPU runs the kernels in interpret mode: tests)."""
        import os
        mode = os.environ.get("SPARKNET_EPILOGUE", "auto").lower()
        if mode == "off":
            return {}
        plan = self._epilogue_plan()
        if mode == "on":
            return plan
        if jax.default_backend() != "tpu":
            return {}
        return {ci: v for ci, v in plan.items() if v[1] is not None}

    def _apply_range(self, params, state, new_state, blobs, lo, hi, batch,
                     train, rng, ep=None):
        """Run layers [lo, hi) over the mutable blob dict (the body the
        remat segments replay). ``ep``: active epilogue-fusion sites;
        a site engages only when its whole conv/ReLU(/LRN) window lies
        inside [lo, hi), else the layers run unfused (correct either
        way)."""
        skip = set()
        for li in range(lo, hi):
            if li in skip:
                continue
            lp, impl, bottoms, tops = self.layers[li]
            if getattr(impl, "is_feed", False):
                for t in tops:
                    blobs[t] = jnp.asarray(batch[t])
                continue
            # the layer's name on every op it traces (backward ops carry
            # it as transpose(jvp(<name>))): what a device trace is read by
            with jax.named_scope(lp.name):
                self._apply_layer(li, params, state, new_state, blobs,
                                  train, rng, ep, hi, skip)

    def _apply_layer(self, li, params, state, new_state, blobs, train, rng,
                     ep, hi, skip):
        """One non-feed layer of _apply_range — or its whole fused
        conv/ReLU(/LRN) window, whose other layers join ``skip``."""
        from . import fission
        lp, impl, bottoms, tops = self.layers[li]
        lparams = self.resolve_params(params, lp.name)
        bvals = [blobs[b] for b in bottoms]
        lrng = jax.random.fold_in(rng, li) if impl.needs_rng else None
        fuse = ep.get(li) if ep else None
        if fuse is not None and max(x for x in fuse if x is not None) < hi:
            with kernel_import("sparknet_tpu.ops.pallas_epilogue"):
                from ..ops import pallas_epilogue as pe
            ri, lrni = fuse
            bvals = [fission.materialize(v) for v in bvals]
            y = impl.apply_raw(lparams, bvals, train, lrng)
            b = lparams[1]
            skip.add(ri)
            if lrni is None:
                # ReLU is the in-place rebind: the fused output IS
                # the conv/relu blob, bit-for-bit
                blobs[tops[0]] = pe.bias_relu(y, b)
            else:
                lm = self.layers[lrni][1]
                blobs[self.layers[lrni][3][0]] = pe.bias_relu_lrn(
                    y, b, lm.size, lm.alpha, lm.beta, lm.k)
                skip.add(lrni)
                # the relu'd pre-LRN blob is never materialized;
                # absent, never stale (plan proved no consumer)
                blobs.pop(tops[0], None)
            return
        tvals = fission.try_apply(lp, impl, lparams, bvals,
                                  train, lrng) if self.fission else None
        if tvals is None:
            # normal path; any virtual concat bottom materializes here
            bvals = [fission.materialize(v) for v in bvals]
            if impl.has_state:
                tvals, st = impl.apply_stateful(
                    lparams, state[lp.name], bvals, train, lrng)
                new_state[lp.name] = st
            else:
                tvals = impl.apply(lparams, bvals, train, lrng)
        for t, v in zip(tops, tvals):
            blobs[t] = v

    def _scan_runs(self):
        """Scan-over-layers sites, cached: maximal runs of >= 2
        consecutive structurally identical "prefix/" layer groups (the
        zoo's "block{i}/..." transformer convention), each chained
        through a single boundary blob.

        Two groups are identical when every corresponding layer matches
        on type, name suffix, prefix-stripped bottoms/tops, top blob
        shapes, owned param shapes/dtypes and its own settings (the
        layer's fields less its name, bottoms, tops and blobs: a windowed
        attention and a global one of the same shapes are not one body)
        — and is stateless,
        rng-free, feed-free, with no cross-layer param sharing, and
        loss-free but for the scalar loss tops of a layer that says a scan
        may stack them (`scan_loss_tops`: an attention's index loss),
        which ride out of the scan as its per-iteration outputs and land
        in ``blobs`` under every group's own name. Chaining requires
        group i's one external input to be
        group i-1's one externally consumed top, read by nothing else.
        Under those conditions the whole run executes as ONE traced
        block body under lax.scan over stacked per-group params,
        collapsing per-layer trace/dispatch/compile cost from O(depth)
        to O(1) — the d512 LM row's dominant overhead (PERF.md).

        Returns [{lo, hi, glen, n, entry, body_out, out, losses}]: layer
        range, group length/count, group-0's external input blob,
        group-0's boundary top (the scan carry), the LAST group's boundary
        blob name (where the carry lands), and the loss tops as (layer in
        the group, top) indices."""
        if self._scan_cache is not None:
            return self._scan_cache
        pgroups = []                       # (prefix, lo, hi)
        prefix, start = None, 0
        for li, (lp, _, _, _) in enumerate(self.layers):
            p = lp.name.split("/")[0] if "/" in lp.name else None
            if p != prefix:
                if prefix is not None:
                    pgroups.append((prefix, start, li))
                prefix, start = p, li
        if prefix is not None:
            pgroups.append((prefix, start, len(self.layers)))
        nl = len(self.layers)

        def group_info(gi):
            """(signature, entry, boundary) or None if ineligible."""
            pfx, lo, hi = pgroups[gi]
            produced, sig, externals, losses = set(), [], set(), []
            strip = len(pfx) + 1
            for li in range(lo, hi):
                lp, impl, bottoms, tops = self.layers[li]
                if getattr(impl, "is_feed", False) or impl.has_state \
                        or impl.needs_rng:
                    return None
                if any(self.loss_weights[lp.name]):
                    if not getattr(impl, "scan_loss_tops", False):
                        return None
                    losses += [(li - lo, ti) for ti, w in enumerate(
                        self.loss_weights[lp.name]) if w]
                if any(owner != lp.name
                       for owner, _ in self.param_refs[lp.name]):
                    return None
                bsig = []
                for b in bottoms:
                    if b in produced:
                        bsig.append(b[strip:] if b.startswith(pfx + "/")
                                    else b)
                    else:
                        externals.add(b)
                        bsig.append("\x00ENTRY")
                pshapes = tuple(
                    (self.param_meta[k][0],)
                    for k in self.param_refs[lp.name])
                settings = {f: getattr(lp, f) for f in lp.set_fields()
                            if f not in ("name", "bottom", "top", "blobs")}
                sig.append((lp.type, lp.name[strip:], settings, tuple(bsig),
                            tuple(t[strip:] if t.startswith(pfx + "/")
                                  else "\x00T:" + t for t in tops),
                            tuple(tuple(self.blob_shapes[t]) for t in tops),
                            pshapes))
                produced.update(tops)
            if len(externals) != 1:
                return None
            loss_tops = {self.layers[lo + j][3][ti] for j, ti in losses}
            if any(t in self.layers[lj][2] for t in loss_tops
                   for lj in range(nl)):
                return None
            out = {t for li in range(lo, hi) for t in self.layers[li][3]
                   if t in self.output_blobs
                   or any(t in self.layers[lj][2] for lj in range(hi, nl))}
            out -= loss_tops
            if len(out) != 1:
                return None
            return (tuple(sig), next(iter(externals)), next(iter(out)),
                    losses)

        infos = [group_info(gi) for gi in range(len(pgroups))]

        def chains(a, b):
            """Group b continues group a: same structure, b's input is
            a's boundary, and that blob is read by b ALONE."""
            ia, ib = infos[a], infos[b]
            if ia is None or ib is None or ia[0] != ib[0]:
                return False
            if pgroups[a][2] != pgroups[b][1]:     # must be adjacent
                return False
            if ib[1] != ia[2] or ia[2] in self.output_blobs:
                return False
            bhi = pgroups[b][2]
            return not any(ia[2] in self.layers[lj][2]
                           for lj in range(bhi, nl))

        runs, gi = [], 0
        while gi < len(pgroups):
            gj = gi
            while gj + 1 < len(pgroups) and chains(gj, gj + 1):
                gj += 1
            if gj > gi:
                lo, hi = pgroups[gi][1], pgroups[gj][2]
                runs.append({"lo": lo, "hi": hi,
                             "glen": pgroups[gi][2] - pgroups[gi][1],
                             "n": gj - gi + 1,
                             "entry": infos[gi][1],
                             "body_out": infos[gi][2],
                             "out": infos[gj][2],
                             "losses": infos[gi][3]})
            gi = gj + 1
        self._scan_cache = runs
        return runs

    def _scan_enabled(self):
        """SPARKNET_SCAN / self.scan policy: off — unrolled (every blob
        materialized, the extract_features-friendly default off-TPU);
        auto — scan on TPU only (XLA:CPU pessimizes loop bodies, the
        LocalSGD unroll precedent); on — scan everywhere (tests)."""
        import os
        mode = self.scan if self.scan is not None \
            else os.environ.get("SPARKNET_SCAN", "auto").lower()
        if mode == "on":
            return True
        if mode == "auto":
            return jax.default_backend() == "tpu"
        return False

    def _apply_scan(self, run, params, blobs, train, pol):
        """Execute one scan run: stack each group's params on a leading
        scan axis and run group 0's traced body once under lax.scan.
        Group-internal blobs are never materialized (absent, never
        stale); only the final boundary blob lands in ``blobs``. The
        remat policy composes by checkpointing the body — one block of
        activations live at a time in the backward."""
        from . import fission
        lo, glen, n = run["lo"], run["glen"], run["n"]
        g0 = self.layers[lo:lo + glen]
        # the groups' params stacked, the loop, its carry and the buffers
        # jax stacks for the backward pass (with what graph/remat.py:keep
        # names) are the scan's own and no layer's: one scope round them
        # says so in a device trace
        scope = "layer_scan." + g0[0][0].name.split("/")[0]
        stacked = []
        with jax.named_scope(scope):
            for j in range(glen):
                names = [self.layers[lo + g * glen + j][0].name
                         for g in range(n)]
                stacked.append(
                    [jnp.stack([params[nm][i] for nm in names])
                     for i in range(len(params.get(names[0], [])))])
        entry, body_out = run["entry"], run["body_out"]

        def body(x, ps):
            sblobs = {entry: x}
            for j, (lp, impl, bottoms, tops) in enumerate(g0):
                # one traced body serves every group: group 0's names
                with jax.named_scope(lp.name):
                    tvals = impl.apply(ps[j], [sblobs[b] for b in bottoms],
                                       train, None)
                for t, v in zip(tops, tvals):
                    sblobs[t] = v
            return sblobs[body_out], [sblobs[g0[j][3][ti]]
                                      for j, ti in run["losses"]]

        if pol != "none":
            body = _checkpointed(body, pol)
        x0 = fission.materialize(blobs[entry])
        with jax.named_scope(scope):
            xN, losses = jax.lax.scan(body, x0, stacked)
        blobs[run["out"]] = xN
        # a group's loss tops, stacked over the groups, under their names
        for (j, ti), stacked_loss in zip(run["losses"], losses):
            for g in range(n):
                blobs[self.layers[lo + g * glen + j][3][ti]] = \
                    stacked_loss[g]

    def _segment_externals(self, lo, hi):
        """Blob names a [lo, hi) segment must surface: consumed by later
        layers, carrying loss weight, or net outputs."""
        produced = set()
        for li in range(lo, hi):
            produced.update(self.layers[li][3])
        needed = set()
        for li in range(hi, len(self.layers)):
            needed.update(self.layers[li][2])
        for li in range(lo, hi):
            lp = self.layers[li][0]
            for t, w in zip(self.layers[li][3], self.loss_weights[lp.name]):
                if w:
                    needed.add(t)
        needed.update(self.output_blobs)
        return sorted(produced & needed)

    def apply(self, params, state, batch, train=None, rng=None):
        """Run the forward pass. Pure; jit/grad-safe.

        Three trace-time policies compose here (each read once per
        trace, so a long-lived jit never sees them change — toggles go
        through Solver.set_remat/set_scan, which rebuild the jit):

        * remat (--remat / SPARKNET_REMAT: none|dots|full) — with
          train=True, runs of layers sharing a "prefix/" name (the
          zoo's per-block convention) execute under jax.checkpoint with
          the named policy: the backward recomputes their internals
          instead of saving every intermediate activation — except the
          outputs of a segment's pallas kernels, which are kept
          (_checkpointed: a flash pass's output and logsumexp, the delta
          rule's output, states and inverses), so a kernel's forward
          runs once. Segment-INTERNAL blobs are then absent from the
          returned dict (only segment boundaries, loss tops and net
          outputs survive).
        * scan (SPARKNET_SCAN: auto|on|off) — structurally identical
          block chains (_scan_runs) execute as one lax.scan over
          stacked params: one traced body instead of depth copies.
          Block-internal blobs are absent; remat checkpoints the body
          (what it keeps is stacked over the iterations).
        * epilogue (SPARKNET_EPILOGUE: auto|on|off) — conv bias+ReLU
          (+LRN) tails run as one fused pallas pass (_active_epilogue).

        Keep all three off for extract_features-style blob inspection."""
        if train is None:
            train = (self.phase == TRAIN)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        from . import fission
        pol = (self.remat if self.remat is not None else _env_remat()) \
            if train else "none"
        if pol not in REMAT_POLICIES:
            raise ValueError(f"remat={pol!r}: want none|dots|full")
        groups = self._remat_groups() if pol != "none" else {}
        scans = {r["lo"]: r for r in self._scan_runs()} \
            if self._scan_enabled() else {}
        ep = self._active_epilogue()
        blobs = {}
        for n in self.net_inputs:
            blobs[n] = jnp.asarray(batch[n])
        new_state = dict(state)
        li = 0
        while li < len(self.layers):
            run = scans.get(li)
            if run is not None:
                self._apply_scan(run, params, blobs, train, pol)
                li = run["hi"]
                continue
            hi = groups.get(li)
            if hi is None:
                # a fusion site outside any remat segment dispatches its
                # whole conv/ReLU(/LRN) window in one range so the fused
                # branch engages; a window straddling a segment or scan
                # run falls back to unfused (correct either way)
                fuse = ep.get(li) if ep else None
                if fuse is not None:
                    end = max(x for x in fuse if x is not None) + 1
                    if all(j not in groups and j not in scans
                           for j in range(li + 1, end)):
                        self._apply_range(params, state, new_state, blobs,
                                          li, end, batch, train, rng,
                                          ep=ep)
                        li = end
                        continue
                self._apply_range(params, state, new_state, blobs,
                                  li, li + 1, batch, train, rng, ep=ep)
                li += 1
                continue
            # remat segment [li, hi): close over statics, checkpoint the
            # array-valued computation
            lo = li
            in_names = sorted({b for j in range(lo, hi)
                               for b in self.layers[j][2] if b in blobs})
            out_names = self._segment_externals(lo, hi)
            seg_states = sorted({self.layers[j][0].name
                                 for j in range(lo, hi)
                                 if self.layers[j][1].has_state})

            def seg_fn(params, state, in_vals, rng, lo=lo, hi=hi,
                       in_names=in_names, out_names=out_names,
                       seg_states=seg_states):
                sblobs = {n: fission.materialize(v)
                          for n, v in zip(in_names, in_vals)}
                sstate = dict(state)
                self._apply_range(params, state, sstate, sblobs,
                                  lo, hi, batch, train, rng, ep=ep)
                return ([fission.materialize(sblobs[n])
                         for n in out_names],
                        [sstate[n] for n in seg_states])

            out_vals, out_states = _checkpointed(seg_fn, pol)(
                params, state,
                [fission.materialize(blobs[n]) for n in in_names], rng)
            # a blob produced before the segment and overwritten in-place
            # inside it (top==bottom across the boundary) must not survive
            # with its stale pre-segment value — internal blobs are ABSENT,
            # never wrong
            produced = {t for j in range(lo, hi) for t in self.layers[j][3]}
            for n in produced.difference(out_names):
                blobs.pop(n, None)
            for n, v in zip(out_names, out_vals):
                blobs[n] = v
            for n, st in zip(seg_states, out_states):
                new_state[n] = st
            li = hi
        # callers see arrays only; unconsumed materializations are DCE'd
        return {k: fission.materialize(v) for k, v in blobs.items()}, \
            new_state

    def total_loss(self, blobs):
        """Weighted sum of loss tops (reference net.cpp ForwardFromTo loss
        accumulation via loss_weight)."""
        with jax.named_scope("total_loss"):
            total = jnp.zeros((), jnp.float32)
            for lp, impl, bottoms, tops in self.layers:
                for t, w in zip(tops, self.loss_weights[lp.name]):
                    if w:
                        total = total + w * jnp.sum(
                            blobs[t]).astype(jnp.float32)
            return total

    def loss_fn(self, params, state, batch, rng=None):
        blobs, new_state = self.apply(params, state, batch, rng=rng)
        return self.total_loss(blobs), (blobs, new_state)

    # -- weight io ---------------------------------------------------------
    def params_to_netproto(self, params, state=None):
        """Emit a NetParameter with blobs filled — .caffemodel-compatible
        (reference net.cpp ToProto :911)."""
        out = Message("NetParameter", name=self.name or "net")
        for lp, impl, bottoms, tops in self.layers:
            olp = lp.copy()
            olp.clear("blobs")
            merged = []
            if lp.name in params:
                merged += list(params[lp.name])
            if state and lp.name in state:
                merged += list(state[lp.name])
            for arr in merged:
                olp.blobs.append(array_to_blob(np.asarray(arr)))
            out.layer.append(olp)
        return out

    def load_netproto(self, net_proto, params, state=None, strict=False):
        """Copy weights from a NetParameter by layer name (reference
        net.cpp CopyTrainedLayersFrom :805): shapes must match; layers
        absent from either side are skipped unless strict."""
        from .upgrade import upgrade_net
        net_proto = upgrade_net(net_proto)
        by_name = {l.name: l for l in net_proto.layer}
        params = {k: list(v) for k, v in params.items()}
        state = {k: list(v) for k, v in (state or {}).items()}
        for lp, impl, bottoms, tops in self.layers:
            src = by_name.get(lp.name)
            if src is None or not src.blobs:
                if strict and lp.name in params:
                    raise ValueError(f"no weights for layer {lp.name!r}")
                continue
            tgt = list(params.get(lp.name, []))
            n_p = len(tgt)
            sblobs = list(src.blobs)
            for i, bp in enumerate(sblobs):
                arr = blob_to_array(bp)
                if i < n_p:
                    if arr.size != tgt[i].size:
                        raise ValueError(
                            f"layer {lp.name!r} blob {i}: size mismatch "
                            f"{arr.shape} vs {tgt[i].shape}")
                    tgt[i] = jnp.asarray(arr.reshape(tgt[i].shape),
                                         self.dtype)
                elif lp.name in state and i - n_p < len(state[lp.name]):
                    j = i - n_p
                    state[lp.name][j] = jnp.asarray(
                        arr.reshape(state[lp.name][j].shape), self.dtype)
            if tgt:
                params[lp.name] = tgt
        return params, state


def blob_to_array(bp):
    if bp.has("shape"):
        shape = [int(d) for d in bp.shape.dim]
    else:
        shape = [d for d in (bp.num, bp.channels, bp.height, bp.width)]
        # legacy 4D: strip leading 1s only if count matches without them
    data = bp.double_data if len(bp.double_data) else bp.data
    # no intermediate list(): the wire codec hands packed floats back as a
    # numpy array, and RepeatedField is already list-like — a 230MB
    # CaffeNet import must not pay a per-element Python copy here
    arr = np.asarray(data, np.float32)
    if shape and int(np.prod(shape)) == arr.size:
        arr = arr.reshape(shape)
    return arr


def array_to_blob(arr):
    bp = Message("BlobProto")
    bp.ensure("shape").dim.extend(int(d) for d in arr.shape)
    bp.data.extend_np(np.asarray(arr, np.float32).ravel())
    return bp
