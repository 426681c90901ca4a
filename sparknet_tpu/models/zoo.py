"""Programmatic model builders: the LeNet -> CIFAR -> AlexNet/CaffeNet ->
GoogLeNet progression of the reference (caffe/examples/mnist,
caffe/examples/cifar10, caffe/models/bvlc_reference_caffenet,
caffe/models/bvlc_googlenet), re-expressed with the DSL so the framework is
self-contained — no prototxt files needed (though stock ones load too).
"""

from .dsl import (GatedDeltaNetLayer, RMSNormLayer, ShortConvLayer,
                  SigmoidLayer, NetParam, RDDLayer, ConvolutionLayer, PoolingLayer,
                  InnerProductLayer, ReLULayer, SoftmaxWithLoss,
                  AccuracyLayer, LRNLayer, DropoutLayer, ConcatLayer,
                  EltwiseLayer, AttentionLayer, EmbedLayer,
                  PositionalEmbedLayer, LayerNormLayer, MoELayer,
                  Mamba2Layer, ShiftLayer)


def _conv(name, bottom, num_output, kernel, stride=1, pad=0, group=None,
          w_std=0.01, w_type="gaussian", bias_value=0.0, lr=(1, 2),
          decay=(1, 0)):
    wf = dict(type=w_type)
    if w_type == "gaussian":
        wf["std"] = w_std
    lp = ConvolutionLayer(
        name, [bottom], (kernel, kernel), num_output,
        stride=(stride, stride), pad=(pad, pad), group=group,
        weight_filler=wf,
        bias_filler=dict(type="constant", value=bias_value),
        param=[dict(lr_mult=lr[0], decay_mult=decay[0]),
               dict(lr_mult=lr[1], decay_mult=decay[1])])
    return lp


def _fc(name, bottom, num_output, w_std=0.01, w_type="gaussian",
        bias_value=0.0, lr=(1, 2), decay=(1, 0)):
    wf = dict(type=w_type)
    if w_type == "gaussian":
        wf["std"] = w_std
    return InnerProductLayer(
        name, [bottom], num_output, weight_filler=wf,
        bias_filler=dict(type="constant", value=bias_value),
        param=[dict(lr_mult=lr[0], decay_mult=decay[0]),
               dict(lr_mult=lr[1], decay_mult=decay[1])])


def lenet(batch_size=64, with_data=True):
    """LeNet on 28x28x1 (reference examples/mnist/lenet_train_test.prototxt)."""
    layers = []
    if with_data:
        layers += [RDDLayer("data", [batch_size, 1, 28, 28]),
                   RDDLayer("label", [batch_size])]
    layers += [
        _conv("conv1", "data", 20, 5, w_type="xavier"),
        PoolingLayer("pool1", ["conv1"], "MAX", (2, 2), (2, 2)),
        _conv("conv2", "pool1", 50, 5, w_type="xavier"),
        PoolingLayer("pool2", ["conv2"], "MAX", (2, 2), (2, 2)),
        _fc("ip1", "pool2", 500, w_type="xavier"),
        ReLULayer("relu1", ["ip1"], tops=["ip1"]),
        _fc("ip2", "ip1", 10, w_type="xavier"),
        AccuracyLayer("accuracy", ["ip2", "label"]),
        SoftmaxWithLoss("loss", ["ip2", "label"]),
    ]
    return NetParam("LeNet", *layers)


def cifar10_full(batch_size=100, with_data=True):
    """CIFAR10_full (reference examples/cifar10/cifar10_full_train_test.prototxt)."""
    layers = []
    if with_data:
        layers += [RDDLayer("data", [batch_size, 3, 32, 32]),
                   RDDLayer("label", [batch_size])]
    layers += [
        _conv("conv1", "data", 32, 5, pad=2, w_std=0.0001, lr=(1, 2),
              decay=(1, 1)),
        PoolingLayer("pool1", ["conv1"], "MAX", (3, 3), (2, 2)),
        ReLULayer("relu1", ["pool1"], tops=["pool1"]),
        LRNLayer("norm1", ["pool1"], local_size=3, alpha=5e-5, beta=0.75,
                 norm_region="WITHIN_CHANNEL"),
        _conv("conv2", "norm1", 32, 5, pad=2, w_std=0.01, decay=(1, 1)),
        ReLULayer("relu2", ["conv2"], tops=["conv2"]),
        PoolingLayer("pool2", ["conv2"], "AVE", (3, 3), (2, 2)),
        LRNLayer("norm2", ["pool2"], local_size=3, alpha=5e-5, beta=0.75,
                 norm_region="WITHIN_CHANNEL"),
        _conv("conv3", "norm2", 64, 5, pad=2, w_std=0.01, lr=(1, 1),
              decay=(1, 1)),
        ReLULayer("relu3", ["conv3"], tops=["conv3"]),
        PoolingLayer("pool3", ["conv3"], "AVE", (3, 3), (2, 2)),
        InnerProductLayer(
            "ip1", ["pool3"], 10,
            weight_filler=dict(type="gaussian", std=0.01),
            bias_filler=dict(type="constant"),
            param=[dict(lr_mult=1, decay_mult=250),
                   dict(lr_mult=2, decay_mult=0)]),
        AccuracyLayer("accuracy", ["ip1", "label"]),
        SoftmaxWithLoss("loss", ["ip1", "label"]),
    ]
    return NetParam("CIFAR10_full", *layers)


def caffenet(batch_size=256, num_classes=1000, with_data=True,
             crop_size=227):
    """AlexNet-class CaffeNet (reference models/bvlc_reference_caffenet/
    train_val.prototxt): the pool-then-norm AlexNet variant with grouped
    conv2/4/5 — the ImageNetApp workload (ImageNetApp.scala)."""
    layers = []
    if with_data:
        layers += [RDDLayer("data", [batch_size, 3, crop_size, crop_size]),
                   RDDLayer("label", [batch_size])]
    layers += [
        _conv("conv1", "data", 96, 11, stride=4, w_std=0.01),
        ReLULayer("relu1", ["conv1"], tops=["conv1"]),
        PoolingLayer("pool1", ["conv1"], "MAX", (3, 3), (2, 2)),
        LRNLayer("norm1", ["pool1"], local_size=5, alpha=1e-4, beta=0.75),
        _conv("conv2", "norm1", 256, 5, pad=2, group=2, w_std=0.01,
              bias_value=1.0),
        ReLULayer("relu2", ["conv2"], tops=["conv2"]),
        PoolingLayer("pool2", ["conv2"], "MAX", (3, 3), (2, 2)),
        LRNLayer("norm2", ["pool2"], local_size=5, alpha=1e-4, beta=0.75),
        _conv("conv3", "norm2", 384, 3, pad=1, w_std=0.01),
        ReLULayer("relu3", ["conv3"], tops=["conv3"]),
        _conv("conv4", "conv3", 384, 3, pad=1, group=2, w_std=0.01,
              bias_value=1.0),
        ReLULayer("relu4", ["conv4"], tops=["conv4"]),
        _conv("conv5", "conv4", 256, 3, pad=1, group=2, w_std=0.01,
              bias_value=1.0),
        ReLULayer("relu5", ["conv5"], tops=["conv5"]),
        PoolingLayer("pool5", ["conv5"], "MAX", (3, 3), (2, 2)),
        _fc("fc6", "pool5", 4096, w_std=0.005, bias_value=1.0),
        ReLULayer("relu6", ["fc6"], tops=["fc6"]),
        DropoutLayer("drop6", ["fc6"], tops=["fc6"], ratio=0.5),
        _fc("fc7", "fc6", 4096, w_std=0.005, bias_value=1.0),
        ReLULayer("relu7", ["fc7"], tops=["fc7"]),
        DropoutLayer("drop7", ["fc7"], tops=["fc7"], ratio=0.5),
        _fc("fc8", "fc7", num_classes, w_std=0.01),
        AccuracyLayer("accuracy", ["fc8", "label"]),
        SoftmaxWithLoss("loss", ["fc8", "label"]),
    ]
    return NetParam("CaffeNet", *layers)


# GoogLeNet inception tower widths (models/bvlc_googlenet/train_val.prototxt)
_INCEPTION = {
    "3a": (64, 96, 128, 16, 32, 32),
    "3b": (128, 128, 192, 32, 96, 64),
    "4a": (192, 96, 208, 16, 48, 64),
    "4b": (160, 112, 224, 24, 64, 64),
    "4c": (128, 128, 256, 24, 64, 64),
    "4d": (112, 144, 288, 32, 64, 64),
    "4e": (256, 160, 320, 32, 128, 128),
    "5a": (256, 160, 320, 32, 128, 128),
    "5b": (384, 192, 384, 48, 128, 128),
}


def _gconv(name, bottom, num_output, kernel, stride=1, pad=0):
    return _conv(name, bottom, num_output, kernel, stride=stride, pad=pad,
                 w_type="xavier", bias_value=0.2)


def _inception(name, bottom, widths):
    n1, r3, n3, r5, n5, pp = widths
    p = f"inception_{name}"
    layers = [
        _gconv(f"{p}/1x1", bottom, n1, 1),
        ReLULayer(f"{p}/relu_1x1", [f"{p}/1x1"], tops=[f"{p}/1x1"]),
        _gconv(f"{p}/3x3_reduce", bottom, r3, 1),
        ReLULayer(f"{p}/relu_3x3_reduce", [f"{p}/3x3_reduce"],
                  tops=[f"{p}/3x3_reduce"]),
        _gconv(f"{p}/3x3", f"{p}/3x3_reduce", n3, 3, pad=1),
        ReLULayer(f"{p}/relu_3x3", [f"{p}/3x3"], tops=[f"{p}/3x3"]),
        _gconv(f"{p}/5x5_reduce", bottom, r5, 1),
        ReLULayer(f"{p}/relu_5x5_reduce", [f"{p}/5x5_reduce"],
                  tops=[f"{p}/5x5_reduce"]),
        _gconv(f"{p}/5x5", f"{p}/5x5_reduce", n5, 5, pad=2),
        ReLULayer(f"{p}/relu_5x5", [f"{p}/5x5"], tops=[f"{p}/5x5"]),
        PoolingLayer(f"{p}/pool", [bottom], "MAX", (3, 3), (1, 1), pad=1),
        _gconv(f"{p}/pool_proj", f"{p}/pool", pp, 1),
        ReLULayer(f"{p}/relu_pool_proj", [f"{p}/pool_proj"],
                  tops=[f"{p}/pool_proj"]),
        ConcatLayer(f"{p}/output",
                    [f"{p}/1x1", f"{p}/3x3", f"{p}/5x5", f"{p}/pool_proj"]),
    ]
    return layers, f"{p}/output"


def _aux_head(idx, bottom, num_classes):
    p = f"loss{idx}"
    layers = [
        PoolingLayer(f"{p}/ave_pool", [bottom], "AVE", (5, 5), (3, 3)),
        _gconv(f"{p}/conv", f"{p}/ave_pool", 128, 1),
        ReLULayer(f"{p}/relu_conv", [f"{p}/conv"], tops=[f"{p}/conv"]),
        _fc(f"{p}/fc", f"{p}/conv", 1024, w_type="xavier", bias_value=0.2),
        ReLULayer(f"{p}/relu_fc", [f"{p}/fc"], tops=[f"{p}/fc"]),
        DropoutLayer(f"{p}/drop_fc", [f"{p}/fc"], tops=[f"{p}/fc"],
                     ratio=0.7),
        _fc(f"{p}/classifier", f"{p}/fc", num_classes, w_type="xavier"),
    ]
    loss = SoftmaxWithLoss(f"{p}/loss", [f"{p}/classifier", "label"])
    loss.clear("top")
    # the stock prototxt names BOTH aux loss tops ".../loss1"
    # (bvlc_googlenet/train_val.prototxt) — keep the quirk for parity
    loss.top.append(f"{p}/loss{1 if idx == 2 else idx}")
    loss.loss_weight.append(0.3)
    layers.append(loss)
    layers.append(AccuracyLayer(f"{p}/top-1", [f"{p}/classifier", "label"]))
    return layers


def googlenet(batch_size=32, num_classes=1000, with_data=True,
              with_aux=True):
    """GoogLeNet (reference models/bvlc_googlenet/train_val.prototxt):
    9 inception modules, 2 auxiliary train-time classifiers at 0.3 weight."""
    layers = []
    if with_data:
        layers += [RDDLayer("data", [batch_size, 3, 224, 224]),
                   RDDLayer("label", [batch_size])]
    layers += [
        _gconv("conv1/7x7_s2", "data", 64, 7, stride=2, pad=3),
        ReLULayer("conv1/relu_7x7", ["conv1/7x7_s2"], tops=["conv1/7x7_s2"]),
        PoolingLayer("pool1/3x3_s2", ["conv1/7x7_s2"], "MAX", (3, 3), (2, 2)),
        LRNLayer("pool1/norm1", ["pool1/3x3_s2"], local_size=5, alpha=1e-4,
                 beta=0.75),
        _gconv("conv2/3x3_reduce", "pool1/norm1", 64, 1),
        ReLULayer("conv2/relu_3x3_reduce", ["conv2/3x3_reduce"],
                  tops=["conv2/3x3_reduce"]),
        _gconv("conv2/3x3", "conv2/3x3_reduce", 192, 3, pad=1),
        ReLULayer("conv2/relu_3x3", ["conv2/3x3"], tops=["conv2/3x3"]),
        LRNLayer("conv2/norm2", ["conv2/3x3"], local_size=5, alpha=1e-4,
                 beta=0.75),
        PoolingLayer("pool2/3x3_s2", ["conv2/norm2"], "MAX", (3, 3), (2, 2)),
    ]
    bottom = "pool2/3x3_s2"
    for key in ("3a", "3b"):
        ls, bottom = _inception(key, bottom, _INCEPTION[key])
        layers += ls
    layers.append(PoolingLayer("pool3/3x3_s2", [bottom], "MAX", (3, 3),
                               (2, 2)))
    bottom = "pool3/3x3_s2"
    for key in ("4a", "4b", "4c", "4d", "4e"):
        ls, bottom = _inception(key, bottom, _INCEPTION[key])
        layers += ls
        if with_aux and key == "4a":
            layers += _aux_head(1, bottom, num_classes)
        if with_aux and key == "4d":
            layers += _aux_head(2, bottom, num_classes)
    layers.append(PoolingLayer("pool4/3x3_s2", [bottom], "MAX", (3, 3),
                               (2, 2)))
    bottom = "pool4/3x3_s2"
    for key in ("5a", "5b"):
        ls, bottom = _inception(key, bottom, _INCEPTION[key])
        layers += ls
    pool5 = PoolingLayer("pool5/7x7_s1", [bottom], "AVE", (7, 7), (1, 1))
    layers += [
        pool5,
        DropoutLayer("pool5/drop_7x7_s1", ["pool5/7x7_s1"],
                     tops=["pool5/7x7_s1"], ratio=0.4),
        _fc("loss3/classifier", "pool5/7x7_s1", num_classes,
            w_type="xavier"),
    ]
    loss = SoftmaxWithLoss("loss3/loss3", ["loss3/classifier", "label"])
    layers.append(loss)
    layers.append(AccuracyLayer("loss3/top-1", ["loss3/classifier", "label"]))
    return NetParam("GoogleNet", *layers)


# ---------------------------------------------------------- language models

_KEEP = dict(lr_mult=1, decay_mult=1)
_NODECAY = dict(lr_mult=1, decay_mult=0)
_XAVIER = dict(type="xavier")


def _gauss(std=0.02):
    return dict(type="gaussian", std=std)


def _lm_embed(vocab_size, width, positions=None, **embed):
    """The token embedding "tok_embed" and, with `positions`, the learned
    positional table "pos_embed" added to it as "embed". Returns (layers,
    the blob the first block reads)."""
    layers = [EmbedLayer("tok_embed", ["data"], vocab_size, width, **embed)]
    if not positions:
        return layers, "tok_embed"
    layers.append(PositionalEmbedLayer(
        "pos_embed", ["tok_embed"], positions, width,
        weight_filler=embed["weight_filler"], tops=["embed"]))
    return layers, "embed"


def _lm_block(p, x, branches, norm):
    """One block from the description of its pre-norm residual branches:
    for each (ln, body, res) in order, `norm(p + ln, x)`, the layers
    `body(p, p + ln)` builds (the last one's first top is the branch's
    output) and the sum `p + res` of x and that output, which the next
    branch reads. `p` is the block's name prefix, its slash included.
    Returns (layers, the block's boundary blob)."""
    layers = []
    for ln, body, res in branches:
        inner = body(p, p + ln)
        layers += [norm(p + ln, x), *inner,
                   EltwiseLayer(p + res, [x, inner[-1].top[0]])]
        x = p + res
    return layers, x


def _lm_head(x, vocab_size, norm, p="", label="label", loss=None, **head):
    """The final norm "ln_f", the logits "lm_head" (tied to the embedding
    where `head` names the table's `param`) and the mean cross-entropy per
    token "loss" over `label`; `p` before the three names and `loss`
    (SoftmaxWithLoss's keywords) are a further prediction depth's."""
    return [norm(p + "ln_f", x),
            InnerProductLayer(p + "lm_head", [p + "ln_f"], vocab_size, axis=2,
                              **head),
            SoftmaxWithLoss(p + "loss", [p + "lm_head", label], axis=2,
                            **(loss or {}))]


#: the label a further prediction depth's loss ignores (`_lm_mtp`)
IGNORE_LABEL = -1


def _lm_mtp(depth, x, label, vocab_size, width, branches, norm, embed, head,
            loss_weight, proj):
    """Multi-token-prediction module `depth` (1 is the first), DeepSeek-V3's
    form: for position i, h'_i = W_eh [norm_e(Emb(t_{i+depth})) ;
    norm_h(x_i)] with the MAIN model's table (`embed` names it), `x` the
    residual stream of the depth before (the main stack's last boundary
    blob for the first) and `label` that depth's labels, which are this
    depth's input tokens; ONE more block "block_mtp{depth}/" of `branches`
    over h'; a final norm of its own, the MAIN model's head (`head` names
    its blob), and the cross-entropy against `label` moved one place left,
    its last place `IGNORE_LABEL`, which the loss ignores, times
    `loss_weight`. `proj` are the keywords of W_eh's InnerProduct (width x
    2 width, no bias). Returns (layers, the block's boundary blob, this
    depth's labels): the module's layers outside the block are named
    "mtp{depth}_<what>", so the block is a rematerialization segment of
    its own, reads one blob from outside itself and joins no scan."""
    m = f"mtp{depth}_"
    layers = [EmbedLayer(m + "embed", [label], vocab_size, width, **embed),
              norm(m + "ln_e", m + "embed"), norm(m + "ln_h", x),
              ConcatLayer(m + "cat", [m + "ln_e", m + "ln_h"], axis=2),
              InnerProductLayer(m + "proj", [m + "cat"], width, axis=2,
                                bias_term=False, **proj)]
    block, x = _lm_block(f"block_mtp{depth}/", m + "proj", branches, norm)
    layers += block
    layers.append(ShiftLayer(m + "label", [label], offset=1, axis=1,
                             fill=IGNORE_LABEL))
    layers += _lm_head(x, vocab_size, norm, p=m, label=m + "label",
                       loss=dict(ignore_label=IGNORE_LABEL,
                                 loss_weight=loss_weight), **head)
    return layers, x, m + "label"


def _lm_stack(name, batch_size, seq_len, vocab_size, width, blocks, norm,
              embed, head, positions=None, with_data=True, mtp=None):
    """A causal language model as ONE pre-norm stack, the only place that
    spells it: the feeds "data" and "label" (B, S) int32 (token ids, next
    token ids), the embedding (`_lm_embed`), for every (i, branches) of
    `blocks` the block "block{i}/" (`_lm_block`), the final norm, the head
    and the loss (`_lm_head`). `norm(name, bottom)` builds the family's
    norm layer; `embed` and `head` are the keywords of the embedding and
    of the head's InnerProduct.

    THE NAMING CONTRACT, which graph/compiler.py reads and nothing else
    states: every layer of block i is named "block{i}/<suffix>", and
    nothing outside it is; a block reads ONE blob from outside itself,
    the boundary blob of the block before (the embedding's for the
    first), and only its own last sum is read outside it; blocks whose
    descriptions are equal come out layer for layer alike. By the first,
    `CompiledNet._remat_groups` makes a block one rematerialization
    segment (`--remat`); by all three, `CompiledNet._scan_runs` runs
    neighbours that are alike as one `lax.scan` body over their stacked
    blobs (`--scan`). A block that shares a blob with another, reads a
    second outside blob or is named off the prefix forfeits both, in
    silence: so a builder describes its blocks' branches and leaves the
    names, the chaining and the order to this function.

    `mtp` = dict(depths, branches, loss_weight, proj) adds `depths`
    multi-token-prediction modules after the loss (`_lm_mtp`), each with
    one block of `branches`; with one or more the embedding's table and the
    head's blob are named ("tok_embed_table", "lm_head_table", unless `embed` / `head`
    name theirs) and every module shares both, so that their gradients are
    the sums of all their uses."""
    depths = mtp["depths"] if mtp else 0
    if depths:
        keep = dict(lr_mult=1, decay_mult=1)
        embed = dict({"param": [dict(keep, name="tok_embed_table")]}, **embed)
        head = dict({"param": [dict(keep, name="lm_head_table")]}, **head)
    layers = []
    if with_data:
        layers += [RDDLayer("data", [batch_size, seq_len]),
                   RDDLayer("label", [batch_size, seq_len])]
    first, x = _lm_embed(vocab_size, width, positions, **embed)
    layers += first
    for i, branches in blocks:
        block, x = _lm_block(f"block{i}/", x, branches, norm)
        layers += block
    layers += _lm_head(x, vocab_size, norm, **head)
    label = "label"
    for depth in range(1, depths + 1):
        more, x, label = _lm_mtp(
            depth, x, label, vocab_size, width, mtp["branches"], norm,
            embed, head, mtp["loss_weight"], mtp.get("proj", {}))
        layers += more
    return NetParam(name, *layers)


def _layer_norm(name, x):
    return LayerNormLayer(name, [x])


def _transformer_block(d_model, num_heads, d_ff, flash, ring=False, moe=None):
    """transformer_lm's block: ln1 | attn | res1, then ln2 | ffn1 | relu |
    ffn2 | res2 or, with `moe` (MoELayer's keywords), ln2 | moe | res2."""
    def attn(p, h):
        return [AttentionLayer(p + "attn", [h], num_heads, causal=True,
                               flash=flash, ring=ring)]

    def ffn(p, h):
        return [InnerProductLayer(p + "ffn1", [h], d_ff,
                                  weight_filler=_XAVIER, axis=2),
                ReLULayer(p + "relu", [p + "ffn1"], tops=[p + "ffn1"]),
                InnerProductLayer(p + "ffn2", [p + "ffn1"], d_model,
                                  weight_filler=_XAVIER, axis=2)]

    def switch(p, h):
        return [MoELayer(p + "moe", [h], hidden_dim=d_ff,
                         expert_parallel=True, **moe)]
    return [("ln1", attn, "res1"), ("ln2", switch if moe else ffn, "res2")]


def transformer_lm(vocab_size=512, seq_len=256, batch_size=8, d_model=256,
                   num_layers=4, num_heads=8, d_ff=None, max_positions=None,
                   flash=True, ring=False, with_data=True, moe_experts=0,
                   moe_aux_weight=0.01, moe_capacity_factor=None,
                   moe_stats=False):
    """Decoder-only causal transformer LM — the long-context model family.

    No CNN-era reference twin (SURVEY.md section 5: the reference has no
    attention); this is the workload the framework's sequence machinery
    exists for: the Attention layer dispatches to the pallas flash kernel
    per chip (``flash=True``) or ring attention across a "seq" mesh axis
    (``ring=True``), and pre-LN blocks keep bf16 activations stable.
    ``moe_experts > 0`` replaces every block's dense FFN with a
    Switch-MoE of that many experts (expert_parallel engages under an
    "expert" mesh axis), adding the load-balancing aux loss with weight
    ``moe_aux_weight``.

    Blobs: "data" (B, S) int32 token ids, "label" (B, S) int32 next-token
    ids. Loss is mean cross-entropy per token (SoftmaxWithLoss axis=2).

    Every "block{i}/" group comes out of `_lm_stack`'s one loop, which
    states what scan-over-layers (``--scan``) and the per-block remat
    segments (``--remat``) need of the names.
    """
    moe = dict(num_experts=moe_experts, aux_loss_weight=moe_aux_weight,
               capacity_factor=moe_capacity_factor,
               stats=moe_stats) if moe_experts else None
    block = _transformer_block(d_model, num_heads, d_ff or 4 * d_model,
                               flash, ring, moe)
    return _lm_stack(
        "TransformerLM", batch_size, seq_len, vocab_size, d_model,
        [(i, block) for i in range(num_layers)], _layer_norm,
        embed=dict(weight_filler=_XAVIER), head=dict(weight_filler=_XAVIER),
        positions=max_positions or seq_len, with_data=with_data)


def _rms_norm(eps, **kw):
    """The family's RMSNorm as `_lm_stack` takes it: no decay on its
    weight."""
    return lambda name, x: RMSNormLayer(name, [x], eps=eps, param=[_NODECAY],
                                        **kw)


def _silu_ff(intermediate_size, hidden_size, filler):
    """A dense feed-forward W_2 (silu(W_1 x) * W_3 x) as `_lm_block` takes a
    branch's body: ff_gate, ff_up, ff_sig, ff_act, ff_down — three
    InnerProducts without bias, a Sigmoid and a product (silu(a) = a *
    sigmoid(a))."""
    def body(p, h):
        def fc(name, bottom, width):
            return InnerProductLayer(p + name, [bottom], width,
                                     weight_filler=filler, axis=2,
                                     bias_term=False, param=[_KEEP])
        return [fc("ff_gate", h, intermediate_size),
                fc("ff_up", h, intermediate_size),
                SigmoidLayer(p + "ff_sig", [p + "ff_gate"]),
                EltwiseLayer(p + "ff_act", [p + "ff_gate", p + "ff_sig",
                                            p + "ff_up"], operation="PROD"),
                fc("ff_down", p + "ff_act", hidden_size)]
    return body


def qwen3_next(vocab_size=151936, seq_len=8192, batch_size=2,
               hidden_size=2048, num_hidden_layers=48,
               full_attention_interval=4, num_attention_heads=16,
               num_key_value_heads=2, head_dim=256,
               partial_rotary_factor=0.25, rope_theta=1e7,
               rms_norm_eps=1e-6, linear_num_key_heads=16,
               linear_num_value_heads=32, linear_key_head_dim=128,
               linear_value_head_dim=128, linear_conv_kernel_dim=4,
               num_experts=512, num_experts_per_tok=10,
               moe_intermediate_size=512,
               shared_expert_intermediate_size=512, norm_topk_prob=True,
               experts_held=None, first_expert=0, flash=True,
               moe_stats=False, init_std=0.02):
    """Qwen3-Next (`model_type` qwen3_next) as a trainable net: blocks of
    h = x + Mixer(RMSNorm(x)), out = h + MoE(RMSNorm(h)), the mixer gated
    grouped-query attention (query/key RMSNorm, rotary embedding on
    `partial_rotary_factor` of the head, an output gate) in every
    `full_attention_interval`-th block and Gated DeltaNet in the others;
    a no-drop top-k MoE with a shared expert after every mixer; untied
    embedding and head; mean cross-entropy per token. Defaults are the
    published sizes of Qwen3-Next-80B-A3B.

    One chip's share of an expert-parallel group: `experts_held` experts
    from `first_expert` on (the router keeps `num_experts` outputs),
    `vocab_size` the held rows of embedding and head (ids and labels come
    from that slice), `num_hidden_layers` the layers of this pipeline
    stage. Left out: the multi-token-prediction module, the router's
    auxiliary loss, dropout (none published).

    Layers are named block{i}/ln1 | mixer | res1 | ln2 | moe | res2
    (`_lm_stack`): the remat groups are the blocks and a run of like
    blocks scans."""
    gauss = _gauss(init_std)

    def attention(p, h):
        return [AttentionLayer(
            p + "mixer", [h], num_attention_heads, head_dim=head_dim,
            causal=True, flash=flash, num_kv_heads=num_key_value_heads,
            qk_norm=True, rotary_dim=int(head_dim * partial_rotary_factor),
            rope_theta=rope_theta, output_gate=True, norm_eps=rms_norm_eps,
            weight_filler=gauss, param=[_KEEP] * 4 + [_NODECAY] * 2)]

    def deltanet(p, h):
        return [GatedDeltaNetLayer(
            p + "mixer", [h], linear_num_key_heads, linear_num_value_heads,
            linear_key_head_dim, linear_value_head_dim,
            conv_kernel=linear_conv_kernel_dim, norm_eps=rms_norm_eps,
            weight_filler=gauss,
            param=[_KEEP] * 3 + [_NODECAY] * 3 + [_KEEP])]

    def moe(p, h):
        return [MoELayer(
            p + "moe", [h], num_experts, hidden_dim=moe_intermediate_size,
            top_k=num_experts_per_tok, experts_held=experts_held,
            first_expert=first_expert,
            shared_hidden_dim=shared_expert_intermediate_size,
            norm_topk_prob=norm_topk_prob, weight_filler=gauss,
            stats=moe_stats)]
    return _lm_stack(
        "Qwen3Next", batch_size, seq_len, vocab_size, hidden_size,
        [(i, [("ln1", deltanet if (i + 1) % full_attention_interval
               else attention, "res1"), ("ln2", moe, "res2")])
         for i in range(num_hidden_layers)], _rms_norm(rms_norm_eps),
        embed=dict(weight_filler=gauss, bias_term=False),
        head=dict(weight_filler=gauss, bias_term=False))


def smallthinker(vocab_size=151936, seq_len=16384, batch_size=2,
                 hidden_size=2560, num_hidden_layers=52,
                 num_attention_heads=28, num_key_value_heads=4,
                 head_dim=128, rope_theta=1.5e6, rms_norm_eps=1e-6,
                 rope_layout=None, sliding_window_layout=None,
                 sliding_window_size=4096, moe_num_primary_experts=64,
                 moe_num_active_primary_experts=6, moe_ffn_hidden_size=768,
                 norm_topk_prob=True, experts_held=None, first_expert=0,
                 flash=True, moe_stats=False):
    """SmallThinker (`model_name` smallthinker_21b_instruct) as a trainable
    net: blocks of h = RMSNorm(x), y = x + Attn(h), out = y + MoE(RMSNorm(y))
    with the ROUTER READING h, the block's pre-attention norm, and the
    experts the post-attention one (the MoE layer's second bottom);
    grouped-query attention without bias, query/key norm or gate, layer l
    windowed (`sliding_window_size` keys, the query's own among them) where
    `sliding_window_layout[l]` and with rotate-half rotary on the whole
    head where `rope_layout[l]`, global and without any positional encoding
    where they are 0 (both default to 0, 1, 1, 1 repeating: one global
    NoPE layer before every three windowed rotary ones); a no-drop top-k
    MoE of ReLU-gated experts, no shared expert; plain RMSNorm (w filled
    with 1); untied embedding and head; mean cross-entropy per token.
    Defaults are the published sizes of SmallThinker-21BA3B-Instruct.

    One chip's share of an expert-parallel group as in `qwen3_next`:
    `experts_held` from `first_expert` on, `vocab_size` the held rows,
    `num_hidden_layers` this pipeline stage's layers (the first of the
    layouts). Left out: the router's auxiliary loss, dropout.

    Matrices are filled gaussian(0.02), the embedding gaussian(1): at 1 a
    token's own vector, not the blocks' output that all tokens share,
    carries the residual stream from the first step, and the routers see
    the tokens apart (at 0.02 the deeper layers route nearly every token to
    the same six experts).

    Layers are named block{i}/ln1 | attn | res1 | ln2 | moe | res2
    (`_lm_stack`); the three window blocks of a period are alike and scan,
    the global one is a body of its own."""
    period = [0, 1, 1, 1]
    rotary, windowed = (
        list(given) if given is not None
        else [period[i % 4] for i in range(num_hidden_layers)]
        for given in (rope_layout, sliding_window_layout))
    gauss = _gauss()

    def attention(i):
        return lambda p, h: [AttentionLayer(
            p + "attn", [h], num_attention_heads, head_dim=head_dim,
            causal=True, flash=flash, num_kv_heads=num_key_value_heads,
            rotary_dim=head_dim if rotary[i] else 0, rope_theta=rope_theta,
            weight_filler=gauss,
            window=sliding_window_size if windowed[i] else 0,
            param=[_KEEP] * 4)]

    def moe(p, h):
        return [MoELayer(
            p + "moe", [h, p + "ln1"], moe_num_primary_experts,
            hidden_dim=moe_ffn_hidden_size,
            top_k=moe_num_active_primary_experts, experts_held=experts_held,
            first_expert=first_expert, norm_topk_prob=norm_topk_prob,
            expert_activation="relu", weight_filler=gauss, stats=moe_stats)]
    return _lm_stack(
        "SmallThinker", batch_size, seq_len, vocab_size, hidden_size,
        [(i, [("ln1", attention(i), "res1"), ("ln2", moe, "res2")])
         for i in range(num_hidden_layers)],
        _rms_norm(rms_norm_eps, zero_centered=False),
        embed=dict(weight_filler=_gauss(1.0), bias_term=False),
        head=dict(weight_filler=gauss, bias_term=False))


def lfm2_moe(vocab_size=65536, seq_len=8192, batch_size=3,
             hidden_size=2048, intermediate_size=7168,
             moe_intermediate_size=1792, num_hidden_layers=24,
             layer_types=None, num_dense_layers=2, num_attention_heads=32,
             num_key_value_heads=8, head_dim=64, rope_theta=1e6,
             norm_eps=1e-5, conv_L_cache=3, num_experts=32,
             num_experts_per_tok=4, norm_topk_prob=True,
             routed_scaling_factor=1.0, use_expert_bias=True,
             experts_held=None, first_expert=0, flash=True, moe_stats=False):
    """LFM2-MoE (`model_type` lfm2_moe) as a trainable net: blocks of
    y = x + Op(RMSNorm(x)), out = y + FF(RMSNorm(y)), plain RMSNorm (w
    filled with 1). `Op` of a layer whose `layer_types` entry is "conv" is
    the gated short convolution (ops/shortconv.py: [B | C | u] = W_in h,
    a causal depthwise conv of `conv_L_cache` taps over B * u, gated by C,
    W_out; no bias); of a "full_attention" layer grouped-query attention
    without bias, a plain RMSNorm (a weight of `head_dim`) on every query
    and key head before rotate-half rotary on the whole head, causal
    softmax(q k^T / sqrt(head_dim)) v. `FF` of the first `num_dense_layers`
    layers is W_2 (silu(W_1 x) * W_3 x) at `intermediate_size`, built from
    three InnerProducts, a Sigmoid and a product (silu(a) = a * sigmoid(a));
    of the others a no-drop top-k MoE of SiLU-gated experts routed by
    s = sigmoid(W_r x) in float32: the chosen are the largest of s + b (b
    the `expert_bias`, a buffer of zeros no gradient trains), their weights
    the unbiased s divided by (their sum + 1e-6), times
    `routed_scaling_factor`; no shared expert. After the last block a plain
    RMSNorm and logits = h E^T with the EMBEDDING'S OWN table (one blob,
    owned by `tok_embed`, its gradient the sum of both uses); mean
    cross-entropy per token. Defaults are the published sizes of
    LFM2-8B-A1B; `layer_types` defaults to its 24 entries (attention at
    layers 2, 6, 10, 14, 18 and 21, conv elsewhere).

    Departures from the published description, all assumptions where the
    config is silent: `head_dim` hidden / heads = 64; the tied head; the
    chunk order B, C, u of the in projection; the load-balancing update of
    the expert bias and any auxiliary loss are left out.

    One chip's share of an expert-parallel group as in `qwen3_next`:
    `experts_held` from `first_expert` on, `vocab_size` the held rows,
    `num_hidden_layers`, `layer_types` and `num_dense_layers` this pipeline
    stage's.

    Matrices and the embedding are filled gaussian(0.02) (NOT the
    embedding at 1 as in `smallthinker`: the table is the head's too, and a
    token's own logit would be 2048 / rms of the residual stream), the conv
    taps uniform(+-1/sqrt(taps)).

    Layers are named block{i}/ln1 | mixer | res1 | ln2 | ff... | res2 (a
    dense block's feed-forward ff_gate, ff_up, ff_sig, ff_act, ff_down; a
    MoE block's moe; `_lm_stack`), so the remat groups are the blocks and
    the conv blocks that follow one another with a MoE scan as one run."""
    if layer_types is None:
        layer_types = ["full_attention" if i in (2, 6, 10, 14, 18, 21)
                       else "conv" for i in range(24)]
    layer_types = list(layer_types)[:num_hidden_layers]
    if len(layer_types) != num_hidden_layers or \
            set(layer_types) - {"conv", "full_attention"}:
        raise ValueError(f"lfm2_moe: layer_types {layer_types} for "
                         f"{num_hidden_layers} layers")
    gauss = _gauss()
    table = dict(name="tok_embed_table", lr_mult=1, decay_mult=1)

    def conv(p, h):
        return [ShortConvLayer(p + "mixer", [h], kernel=conv_L_cache,
                               weight_filler=gauss, param=[_KEEP] * 3)]

    def attention(p, h):
        return [AttentionLayer(
            p + "mixer", [h], num_attention_heads, head_dim=head_dim,
            causal=True, flash=flash, num_kv_heads=num_key_value_heads,
            qk_norm=True, qk_norm_zero_centered=False, rotary_dim=head_dim,
            rope_theta=rope_theta, norm_eps=norm_eps, weight_filler=gauss,
            param=[_KEEP] * 4 + [_NODECAY] * 2)]

    dense = _silu_ff(intermediate_size, hidden_size, gauss)

    def moe(p, h):
        return [MoELayer(
            p + "moe", [h], num_experts, hidden_dim=moe_intermediate_size,
            top_k=num_experts_per_tok, experts_held=experts_held,
            first_expert=first_expert, norm_topk_prob=norm_topk_prob,
            score_function="sigmoid", selection_bias=bool(use_expert_bias),
            topk_eps=1e-6, routed_scaling_factor=routed_scaling_factor,
            weight_filler=gauss, stats=moe_stats)]
    return _lm_stack(
        "LFM2MoE", batch_size, seq_len, vocab_size, hidden_size,
        [(i, [("ln1", conv if kind == "conv" else attention, "res1"),
              ("ln2", dense if i < num_dense_layers else moe, "res2")])
         for i, kind in enumerate(layer_types)],
        _rms_norm(norm_eps, zero_centered=False),
        embed=dict(weight_filler=gauss, bias_term=False, param=[table]),
        head=dict(bias_term=False, param=[table]))


def keye_vl2(vocab_size=151936, seq_len=32768, batch_size=1,
             hidden_size=2048, num_hidden_layers=48, num_attention_heads=32,
             num_key_value_heads=4, head_dim=128, rope_theta=1e7,
             rms_norm_eps=1e-6, indexer_num_heads=16, indexer_head_dim=64,
             indexer_topk=2048, num_experts=128, num_experts_per_tok=8,
             moe_intermediate_size=768, norm_topk_prob=True,
             experts_held=None, first_expert=0, flash=True, moe_stats=False,
             index_stats=False):
    """Keye-VL-2.0-30B-A3B's decoder (`model_type` KeyeVL2, the language
    model's settings) as a trainable net: blocks of y = x + Attn(RMSNorm(x)),
    out = y + MoE(RMSNorm(y)), plain RMSNorm (w filled with 1), every layer
    alike. `Attn` is grouped-query attention without bias, a plain RMSNorm
    (a weight of `head_dim`) on every query and key head, rotate-half rotary
    on the whole head, over the `indexer_topk` keys s <= t that a lightning
    indexer picks for every query (ops/dsa.py: `indexer_num_heads` index
    queries of `indexer_head_dim`, ONE index key a token, I[t, s] = sum_j
    w[t, j] relu(qI[t, j] . kI[s]); every key while t < `indexer_topk`);
    the layer's second top is the indexer's own loss L_I = mean_t KL(p_t ||
    softmax over the set of I[t]), p_t the main heads' probabilities summed
    and normalised, a constant of that loss. The step's loss is the
    cross-entropy plus the sum of the layers' L_I; the indexer reads
    stop_gradient of the block's norm, so the cross-entropy reaches no
    indexer blob and L_I nothing else. `MoE` is a no-drop top-k
    MoE of SiLU-gated experts routed by softmax(W_r y) in float32 with the
    chosen weights renormalised, no shared expert; untied embedding and
    head; mean cross-entropy per token. Defaults are the published sizes.

    Assumed, where the config has no key (the configuration file lists the
    same): `mrope_section` splits the rotary frequencies over three position
    streams that are one position on text, so the rotary is the plain one;
    the query/key head norms (the decoder family's); the indexer's form from
    DeepSeek-V3.2's report at this config's sizes: a LayerNorm on kI,
    rotate-half rotary on the first half of every index query and of the
    key, w = W_w h / sqrt(heads x head_dim); `q_chunk_size` /
    `kv_chunk_size` are the tiles in which index scores are computed, no
    unit of selection; ties at a query's threshold are all kept; L_I's
    weight 1, summed over the layers; fillers gaussian(0.02) for matrices
    and gaussian(1) for the embedding (as `smallthinker`: the head is
    untied and the first mixer is attention, whose output all tokens
    share). Left out: the vision tower and its projector, V3.2's dense
    warm-up stage of the indexer, the router's auxiliary loss, dropout,
    packing.

    One chip's share of an expert-parallel group as in `qwen3_next`:
    `experts_held` from `first_expert` on, `vocab_size` the held rows,
    `num_hidden_layers` this pipeline stage's layers.

    Layers are named block{i}/ln1 | attn | res1 | ln2 | moe | res2 (the
    attention's second top block{i}/attn_kl; `_lm_stack`): all blocks are
    alike and scan as one run, their L_I riding out of the scan."""
    gauss = _gauss()

    def attention(p, h):
        return [AttentionLayer(
            p + "attn", [h], num_attention_heads, head_dim=head_dim,
            causal=True, flash=flash, num_kv_heads=num_key_value_heads,
            qk_norm=True, qk_norm_zero_centered=False, rotary_dim=head_dim,
            rope_theta=rope_theta, norm_eps=rms_norm_eps,
            weight_filler=gauss, index_heads=indexer_num_heads,
            index_head_dim=indexer_head_dim, index_topk=indexer_topk,
            index_stats=index_stats,
            param=[_KEEP] * 4 + [_NODECAY] * 2 + [_KEEP] * 3
            + [_NODECAY] * 2)]

    def moe(p, h):
        return [MoELayer(
            p + "moe", [h], num_experts, hidden_dim=moe_intermediate_size,
            top_k=num_experts_per_tok, experts_held=experts_held,
            first_expert=first_expert, norm_topk_prob=norm_topk_prob,
            weight_filler=gauss, stats=moe_stats)]
    block = [("ln1", attention, "res1"), ("ln2", moe, "res2")]
    return _lm_stack(
        "KeyeVL2", batch_size, seq_len, vocab_size, hidden_size,
        [(i, block) for i in range(num_hidden_layers)],
        _rms_norm(rms_norm_eps, zero_centered=False),
        embed=dict(weight_filler=_gauss(1.0), bias_term=False),
        head=dict(weight_filler=gauss, bias_term=False))


#: `hybrid_override_pattern` of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16:
#: 23 M (Mamba-2), 23 E (MoE), 6 * (attention); MEMEM*E five times over,
#: then MEMEMEM*E, then MEMEMEME
NEMOTRON_H_PATTERN = "MEMEM*E" * 5 + "MEMEMEM*E" + "MEMEMEME"


def nemotron_h(vocab_size=131072, seq_len=8192, batch_size=2,
               hidden_size=2688, pattern=NEMOTRON_H_PATTERN, layers=None,
               mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
               n_groups=8, conv_kernel=4, chunk_size=128,
               time_step_min=0.001, time_step_max=0.1,
               num_attention_heads=32, num_key_value_heads=2, head_dim=128,
               n_routed_experts=128, num_experts_per_tok=6,
               moe_intermediate_size=1856,
               moe_shared_expert_intermediate_size=3712, norm_topk_prob=True,
               routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
               experts_held=None, first_expert=0, flash=True,
               moe_stats=False, ssm_stats=False):
    """The Nemotron-H tower of Nemotron-Labs-TwoTower-30B-A3B (`model_type`
    nemotron_h) as a trainable causal language model: a net from a PATTERN
    STRING, one letter a block, EVERY block out = x + Mixer(RMSNorm(x))
    with ONE mixer (plain RMSNorm, w filled with 1, eps
    `layer_norm_epsilon`), a final RMSNorm, untied embedding and head, mean
    cross-entropy per token. The mixer by its letter:

      M  Mamba-2 (ops/mamba2.py): `mamba_num_heads` heads of
         `mamba_head_dim`, state `ssm_state_size`, B and C in `n_groups`
         groups, a causal depthwise conv of `conv_kernel` taps with a bias,
         the scan in chunks of `chunk_size`, the gated RMSNorm over each
         group's channels, no bias on either projection
      *  grouped-query attention, causal, no bias, NO positional encoding,
         no head norm, no gate, no window
      E  a no-drop MoE (ops/moe.py): s = sigmoid(W_r h) in float32 over
         `n_routed_experts`; the `num_experts_per_tok` largest of s + b
         (b a buffer of zeros that no gradient trains), their weights the
         unbiased s divided by (their sum + 1e-20), times
         `routed_scaling_factor`; an expert TWO matrices, W_down
         relu(W_up h)^2, and one shared expert of the same form at
         `moe_shared_expert_intermediate_size`, added with no gate

    Defaults are the published sizes. `layers` = (first, end) are the blocks
    of `pattern` that this pipeline stage holds (None: all of them), named
    by their place in the whole pattern.

    Assumed, where the config has no key or the family's code decides (the
    configuration file lists the same): the Mamba-2 inner width is heads x
    head size = 4,096, not `expand` x hidden; attention has no positional
    encoding (the family gives its attention layers none: the state-space
    layers carry order; `rope_theta` and `partial_rotary_factor` are keys
    its code does not read — a rotary variant would be one field,
    `rotary_dim`); the route's correction bias and its 1e-20 (`n_group` and
    `topk_group` 1: no group limit); the group norm's groups are the
    `n_groups` (512 channels each) and it norms AFTER the gate; fillers:
    A_log uniform(0, log 16) (A in [1, 16); the Mamba-2 code draws A
    itself uniformly, a filler the seeded benchmark weights cannot state),
    dt_bias uniform between the inverse softplus of `time_step_min` and of
    `time_step_max` (delta starts in [0.001, 0.1], all but log-uniform;
    `time_step_floor` 1e-4 lies below and never binds; `time_step_limit`
    (0, inf) is no clamp), D and norms 1, the conv's taps and bias
    uniform(+-1/sqrt(taps)) (torch's Conv1d), matrices gaussian(0.02) with
    W_out, W_o and the experts' down projections divided by
    sqrt(len(pattern)) (`rescale_prenorm_residual`: by the WHOLE model's
    depth, whatever this stage holds), the embedding gaussian(1.0) (as
    `smallthinker`: the head is untied). Left out: the second (denoiser)
    tower, adaLN, bidirectional in-block attention, cross-tower
    conditioning, the block-diffusion objective and its noise schedule (no
    key of the config sizes or places them), the bias's load-balancing
    update and any auxiliary loss, dropout, packing and the state's reset
    at a document's start.

    One chip's share of an expert-parallel group as in `qwen3_next`:
    `experts_held` from `first_expert` on, `vocab_size` the held rows.

    Layers are named block{i}/ln | mixer | res (`_lm_stack`): the remat
    groups are the blocks; neighbours are unlike, so no run of blocks
    scans."""
    first, end = (0, len(pattern)) if layers is None else layers
    unknown = sorted(set(pattern) - set("ME*"))
    if unknown:
        raise ValueError(f"nemotron_h: pattern letters {unknown}: want M "
                         "(Mamba-2), E (MoE) or * (attention)")
    if not 0 <= first < end <= len(pattern):
        raise ValueError(f"nemotron_h: layers {(first, end)} lie outside "
                         f"the pattern's {len(pattern)} blocks")
    gauss, small = _gauss(), _gauss(0.02 / len(pattern) ** 0.5)

    def mamba(p, h):
        return [Mamba2Layer(
            p + "mixer", [h], mamba_num_heads, mamba_head_dim,
            ssm_state_size, n_groups, conv_kernel=conv_kernel,
            chunk=chunk_size, norm_eps=layer_norm_epsilon,
            weight_filler=gauss, out_filler=small, dt_min=time_step_min,
            dt_max=time_step_max, stats=ssm_stats,
            param=[_KEEP, _KEEP] + [_NODECAY] * 5 + [_KEEP])]

    def attention(p, h):
        return [AttentionLayer(
            p + "mixer", [h], num_attention_heads, head_dim=head_dim,
            causal=True, flash=flash, num_kv_heads=num_key_value_heads,
            weight_filler=gauss, out_filler=small, param=[_KEEP] * 4)]

    def moe(p, h):
        return [MoELayer(
            p + "mixer", [h], n_routed_experts,
            hidden_dim=moe_intermediate_size, top_k=num_experts_per_tok,
            experts_held=experts_held, first_expert=first_expert,
            shared_hidden_dim=moe_shared_expert_intermediate_size,
            norm_topk_prob=norm_topk_prob, score_function="sigmoid",
            selection_bias=True, topk_eps=1e-20,
            routed_scaling_factor=routed_scaling_factor,
            expert_activation="relu2", expert_gate_matrix=False,
            shared_gate=False, weight_filler=gauss, down_filler=small,
            stats=moe_stats)]
    mixer = {"M": mamba, "*": attention, "E": moe}
    return _lm_stack(
        "NemotronH", batch_size, seq_len, vocab_size, hidden_size,
        [(i, [("ln", mixer[pattern[i]], "res")]) for i in range(first, end)],
        _rms_norm(layer_norm_epsilon, zero_centered=False),
        embed=dict(weight_filler=_gauss(1.0), bias_term=False),
        head=dict(weight_filler=gauss, bias_term=False))


def glm4_moe_lite(vocab_size=154880, seq_len=8192, batch_size=1,
                  hidden_size=2048, intermediate_size=10240,
                  moe_intermediate_size=1536, num_hidden_layers=47,
                  first_k_dense_replace=1, num_attention_heads=20,
                  q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
                  qk_rope_head_dim=64, v_head_dim=256, rope_theta=1e6,
                  rms_norm_eps=1e-5, n_routed_experts=64,
                  num_experts_per_tok=4, n_shared_experts=1,
                  norm_topk_prob=True, routed_scaling_factor=1.8,
                  num_nextn_predict_layers=1, mtp_loss_weight=0.3,
                  experts_held=None, first_expert=0, flash=True,
                  moe_stats=False):
    """GLM-4.7-Flash (`model_type` glm4_moe_lite) as a trainable net: blocks
    of y = x + Attn(RMSNorm(x)), out = y + FF(RMSNorm(y)), plain RMSNorm (w
    filled with 1), a final RMSNorm, untied embedding and head, mean
    cross-entropy per token. `Attn` is multi-head latent attention
    (ops/attention.py's latent form): queries through a latent of
    `q_lora_rank`, keys and values through one of `kv_lora_rank`, each with
    its own RMSNorm; `num_attention_heads` heads of [`qk_nope_head_dim` |
    `qk_rope_head_dim`], rotate-half rotary on the rotary part alone, the
    key's rotary part ONE vector a token shared by all heads; value heads
    of `v_head_dim`; no bias, window, head norm or gate. `FF` of the first
    `first_k_dense_replace` layers is W_2 (silu(W_1 y) * W_3 y) at
    `intermediate_size` (`_silu_ff`); of the others a no-drop MoE
    (ops/moe.py): s = sigmoid(W_r y) in float32 over `n_routed_experts`,
    the `num_experts_per_tok` largest of s + b (b a buffer of zeros that no
    gradient trains; `n_group` and `topk_group` 1: no group limit), their
    weights the unbiased s divided by (their sum + 1e-20), times
    `routed_scaling_factor`; SiLU-gated three-matrix experts at
    `moe_intermediate_size`, and `n_shared_experts` x that width as one
    shared expert of every token, added with no gate. With
    `num_nextn_predict_layers` that many multi-token-prediction modules
    (`_lm_mtp`: the main model's table and head shared, one more MoE block
    each), their losses times `mtp_loss_weight`. Defaults are the published
    sizes.

    Assumed, where the config has no key (the configuration file lists the
    same): rotate-half rotary (DeepSeek's interleaved pairs are the same
    function under a fixed permutation of the rotary rows of W_qb and
    W_kva); the module's form, DeepSeek-V3's, with W_eh reading [embedding
    ; hidden] in that order; `mtp_loss_weight` 0.3; the 1e-20; fillers
    gaussian(0.02) for matrices and gaussian(1) for the embedding (as
    `smallthinker`: the head is untied and the first mixer is attention).
    Left out: the bias's load-balancing update, any auxiliary loss,
    dropout, packing.

    One chip's share of an expert-parallel group as in `qwen3_next`:
    `experts_held` from `first_expert` on, `vocab_size` the held rows,
    `num_hidden_layers` this pipeline stage's layers (the first of the
    model's: `first_k_dense_replace` counts from layer 0).

    Layers are named block{i}/ln1 | attn | res1 | ln2 | ff... | res2 (a
    dense block's feed-forward ff_gate, ff_up, ff_sig, ff_act, ff_down; a
    MoE block's moe; `_lm_stack`): the dense block is a body of its own,
    the MoE blocks are alike and scan as one run, a prediction module's
    block_mtp{k}/ stands outside it."""
    gauss = _gauss()

    def attention(p, h):
        return [AttentionLayer(
            p + "attn", [h], num_attention_heads, causal=True, flash=flash,
            q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, norm_eps=rms_norm_eps,
            weight_filler=gauss,
            param=[_KEEP, _NODECAY, _KEEP, _KEEP, _NODECAY, _KEEP, _KEEP])]

    def moe(p, h):
        return [MoELayer(
            p + "moe", [h], n_routed_experts,
            hidden_dim=moe_intermediate_size, top_k=num_experts_per_tok,
            experts_held=experts_held, first_expert=first_expert,
            shared_hidden_dim=n_shared_experts * moe_intermediate_size,
            norm_topk_prob=norm_topk_prob, score_function="sigmoid",
            selection_bias=True, topk_eps=1e-20,
            routed_scaling_factor=routed_scaling_factor, shared_gate=False,
            weight_filler=gauss, stats=moe_stats)]
    dense = _silu_ff(intermediate_size, hidden_size, gauss)
    sparse = [("ln1", attention, "res1"), ("ln2", moe, "res2")]
    return _lm_stack(
        "GLM4MoELite", batch_size, seq_len, vocab_size, hidden_size,
        [(i, [("ln1", attention, "res1"), ("ln2", dense, "res2")]
          if i < first_k_dense_replace else sparse)
         for i in range(num_hidden_layers)],
        _rms_norm(rms_norm_eps, zero_centered=False),
        embed=dict(weight_filler=_gauss(1.0), bias_term=False),
        head=dict(weight_filler=gauss, bias_term=False),
        mtp=dict(depths=num_nextn_predict_layers, branches=sparse,
                 loss_weight=mtp_loss_weight,
                 proj=dict(weight_filler=gauss, param=[_KEEP])))


#: Laguna's two kinds of attention layer, one period of the published list
LAGUNA_PERIOD = ("full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention")
#: the published `rope_parameters` of Laguna-XS.2, by kind of layer
LAGUNA_ROPE = {
    "full_attention": dict(
        rope_theta=500000, rope_type="yarn", factor=64,
        original_max_position_embeddings=4096, beta_slow=1, beta_fast=64,
        attention_factor=1.4158883083359672, partial_rotary_factor=0.5),
    "sliding_attention": dict(rope_type="default", rope_theta=10000,
                              partial_rotary_factor=1)}


def laguna(vocab_size=100352, seq_len=8192, batch_size=2, hidden_size=2048,
           intermediate_size=8192, num_hidden_layers=40,
           num_key_value_heads=8, head_dim=128, layer_types=None,
           num_attention_heads_per_layer=None, mlp_layer_types=None,
           sliding_window=512, rope_parameters=None, rms_norm_eps=1e-6,
           num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
           shared_expert_intermediate_size=512,
           moe_routed_scaling_factor=2.5, experts_held=None, first_expert=0,
           flash=True, moe_stats=False):
    """Laguna (`model_type` laguna) as a trainable net: blocks of y = x +
    Attn(RMSNorm(x)), out = y + FF(RMSNorm(y)), plain RMSNorm (w filled
    with 1), a final RMSNorm, untied embedding and head, mean cross-entropy
    per token. `Attn` of layer l is grouped-query attention without bias or
    head norm, `num_attention_heads_per_layer[l]` query heads on
    `num_key_value_heads` key-value heads of `head_dim`, with a PER-HEAD
    output gate (o_h * sigmoid(W_g h)_h, W_g one row a head) and the kind
    `layer_types[l]` says: `sliding_attention` sees `sliding_window` keys
    (the query's own among them), `full_attention` the causal half; each
    kind has its own rotary in `rope_parameters[kind]` — `rope_theta`,
    `partial_rotary_factor` of the head turned (rotate-half), and with
    `rope_type` "yarn" the table blended over `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow` with
    `attention_factor` on cos and sin (ops/attention.py:rope_table). `FF`
    is W_2 (silu(W_1 y) * W_3 y) at `intermediate_size` where
    `mlp_layer_types[l]` is `dense`, else a no-drop MoE (ops/moe.py): s =
    sigmoid(W_r y) in float32 over `num_experts`, the
    `num_experts_per_tok` largest, their weights s divided by (their sum +
    1e-20) times `moe_routed_scaling_factor`, on the experts' outputs;
    SiLU-gated three-matrix experts at `moe_intermediate_size` and one
    shared expert at `shared_expert_intermediate_size` of every token,
    added with no gate. Defaults are the published sizes of Laguna-XS.2
    (48 query heads in a full layer, 64 in a window layer, one period of
    `LAGUNA_PERIOD` repeating, layer 0 dense); the three lists are read at
    their first `num_hidden_layers` entries.

    Assumed, where the config has no key (the configuration file lists the
    same): the gate one scalar a head (`gating: true` has no width; the
    published parameter count settles it), sigmoid scores with renormalised
    weights and no correction bias, an ungated shared expert, no head norm,
    rotate-half pairing, the `transformers` form of YaRN, fillers
    gaussian(0.02) for matrices and gaussian(1) for the embedding (as
    `smallthinker`). Left out: the router's auxiliary loss, dropout,
    packing.

    One chip's share of an expert-parallel group as in `qwen3_next`:
    `experts_held` from `first_expert` on, `vocab_size` the held rows,
    `num_hidden_layers` this pipeline stage's layers (the first of the
    model's).

    Layers are named block{i}/ln1 | attn | res1 | ln2 | ff... | res2 (a
    dense block's feed-forward ff_gate, ff_up, ff_sig, ff_act, ff_down; a
    MoE block's moe; `_lm_stack`): neighbours of one kind with one
    feed-forward are alike and scan as one run (the three window layers of
    a period); a full layer between them is a body of its own."""
    n = num_hidden_layers
    kinds = list(layer_types or [LAGUNA_PERIOD[i % 4] for i in range(n)])[:n]
    heads = list(num_attention_heads_per_layer or
                 [48 if k == "full_attention" else 64 for k in kinds])[:n]
    feeds = list(mlp_layer_types or ["dense"] + ["sparse"] * (n - 1))[:n]
    ropes = rope_parameters or LAGUNA_ROPE
    for name, got in (("layer_types", kinds), ("mlp_layer_types", feeds),
                      ("num_attention_heads_per_layer", heads)):
        if len(got) != n:
            raise ValueError(f"laguna: {name} has {len(got)} entries for "
                             f"{n} layers")
    unknown = sorted(set(kinds) - set(ropes)) + sorted(
        set(feeds) - {"dense", "sparse"})
    if unknown:
        raise ValueError(f"laguna: {', '.join(unknown)}: a layer is one of "
                         f"{', '.join(ropes)} with a dense or a sparse "
                         "feed-forward")
    gauss = _gauss()

    def attention(i):
        rp = ropes[kinds[i]]
        rope = {}
        if rp.get("rope_type", "default") == "yarn":
            rope = dict(
                rope_type="yarn", rope_factor=rp["factor"],
                rope_original_positions=rp[
                    "original_max_position_embeddings"],
                rope_beta_fast=rp["beta_fast"],
                rope_beta_slow=rp["beta_slow"])
            if rp.get("attention_factor") is not None:
                rope["rope_scale"] = rp["attention_factor"]
        return lambda p, h: [AttentionLayer(
            p + "attn", [h], heads[i], head_dim=head_dim, causal=True,
            flash=flash, num_kv_heads=num_key_value_heads,
            rotary_dim=int(head_dim * rp.get("partial_rotary_factor", 1)),
            rope_theta=rp["rope_theta"], rope=rope, gate="head",
            weight_filler=gauss,
            window=sliding_window if kinds[i] == "sliding_attention" else 0,
            param=[_KEEP] * 5)]

    def moe(p, h):
        return [MoELayer(
            p + "moe", [h], num_experts, hidden_dim=moe_intermediate_size,
            top_k=num_experts_per_tok, experts_held=experts_held,
            first_expert=first_expert,
            shared_hidden_dim=shared_expert_intermediate_size,
            norm_topk_prob=True, score_function="sigmoid", topk_eps=1e-20,
            routed_scaling_factor=moe_routed_scaling_factor,
            shared_gate=False, weight_filler=gauss, stats=moe_stats)]
    dense = _silu_ff(intermediate_size, hidden_size, gauss)
    return _lm_stack(
        "Laguna", batch_size, seq_len, vocab_size, hidden_size,
        [(i, [("ln1", attention(i), "res1"),
              ("ln2", dense if feeds[i] == "dense" else moe, "res2")])
         for i in range(n)],
        _rms_norm(rms_norm_eps, zero_centered=False),
        embed=dict(weight_filler=_gauss(1.0), bias_term=False),
        head=dict(weight_filler=gauss, bias_term=False))


def transformer_lm_pieces(vocab_size=512, seq_len=256, batch_size=8,
                          d_model=256, num_heads=8, d_ff=None,
                          max_positions=None, flash=True):
    """transformer_lm split for pipeline parallelism: (prefix, block,
    suffix) NetParams.

    The trunk block is expressed ONCE; PipelineLMSolver stacks L inits of
    it on a leading dim and runs them as GPipe stages over a "pipe" mesh
    axis (parallel/pipeline_solver.py). Embedding (prefix) and head+loss
    (suffix) stay outside the pipeline, replicated — the stage-
    heterogeneous ends the pipeline docstring plans for.

    The three are `_lm_stack`'s parts from transformer_lm's own block
    description, the block without its "block{i}/" prefix (ln1/attn/ffn1/
    ffn2...), so params map 1:1 onto "block{i}/<name>" for equivalence
    tests and checkpoint conversion.
    """
    def stream():
        return RDDLayer("x", [batch_size, seq_len, d_model])
    prefix = NetParam(
        "TransformerLM_prefix", RDDLayer("data", [batch_size, seq_len]),
        *_lm_embed(vocab_size, d_model, max_positions or seq_len,
                   weight_filler=_XAVIER)[0])
    block = NetParam(
        "TransformerLM_block", stream(),
        *_lm_block("", "x", _transformer_block(
            d_model, num_heads, d_ff or 4 * d_model, flash), _layer_norm)[0])
    suffix = NetParam(
        "TransformerLM_suffix", stream(),
        RDDLayer("label", [batch_size, seq_len]),
        *_lm_head("x", vocab_size, _layer_norm, weight_filler=_XAVIER))
    return prefix, block, suffix
