"""Model-building DSL: Python builders producing LayerParameter messages.

The capability of the reference's Scala DSL (Layers.scala:18-137 — RDDLayer,
ConvolutionLayer, PoolingLayer, InnerProductLayer, ReLULayer, SoftmaxWithLoss,
NetParam), extended with the builders the bigger nets need (LRN, Dropout,
Concat, Shift, Accuracy, BatchNorm, Eltwise, Attention, GatedDeltaNet,
ShortConv, RMSNorm, MoE). Each returns a proto
Message, so DSL output and parsed prototxt are the same IR.
"""

from ..proto import Message

TRAIN, TEST = "TRAIN", "TEST"


def _base(type_name, name, bottoms=None, tops=None, include=None, **fields):
    lp = Message("LayerParameter", name=name, type=type_name, **fields)
    for b in (bottoms or []):
        lp.bottom.append(b)
    tops = [name] if tops is None else tops
    for t in tops:
        lp.top.append(t)
    if include is not None:
        lp.add("include", phase=include)
    return lp


def RDDLayer(name, shape, include=None):
    """Data feed layer (reference Layers.scala RDDLayer :18-40): one top,
    named after the layer, shape fixed up front."""
    return _base("JavaData", name, include=include,
                 java_data_param=dict(shape=dict(dim=list(shape))))


def ConvolutionLayer(name, bottoms, kernel, num_output, stride=None, pad=None,
                     group=None, weight_filler=None, bias_filler=None,
                     param=None):
    cp = dict(kernel_h=kernel[0], kernel_w=kernel[1], num_output=num_output)
    if stride is not None:
        cp.update(stride_h=stride[0], stride_w=stride[1])
    if pad is not None:
        cp.update(pad_h=pad[0], pad_w=pad[1])
    if group is not None:
        cp["group"] = group
    if weight_filler is not None:
        cp["weight_filler"] = weight_filler
    if bias_filler is not None:
        cp["bias_filler"] = bias_filler
    lp = _base("Convolution", name, bottoms, convolution_param=cp)
    for p in (param or []):
        lp.add("param", **p)
    return lp


def PoolingLayer(name, bottoms, pooling, kernel, stride, pad=None):
    """pooling: 'MAX' | 'AVE' | 'STOCHASTIC' (Layers.scala PoolingLayer)."""
    pp = dict(pool=pooling, kernel_h=kernel[0], kernel_w=kernel[1],
              stride_h=stride[0], stride_w=stride[1])
    if pad is not None:
        pp["pad"] = pad
    return _base("Pooling", name, bottoms, pooling_param=pp)


def InnerProductLayer(name, bottoms, num_output, weight_filler=None,
                      bias_filler=None, param=None, axis=None,
                      bias_term=None):
    ip = dict(num_output=num_output)
    if bias_term is not None:
        ip["bias_term"] = bias_term
    if weight_filler is not None:
        ip["weight_filler"] = weight_filler
    if bias_filler is not None:
        ip["bias_filler"] = bias_filler
    if axis is not None:
        ip["axis"] = axis
    lp = _base("InnerProduct", name, bottoms, inner_product_param=ip)
    for p in (param or []):
        lp.add("param", **p)
    return lp


def ReLULayer(name, bottoms, tops=None):
    return _base("ReLU", name, bottoms, tops=tops)


def SigmoidLayer(name, bottoms, tops=None):
    return _base("Sigmoid", name, bottoms, tops=tops)


def SoftmaxWithLoss(name, bottoms, axis=None, ignore_label=None,
                    loss_weight=None):
    kw = {}
    if axis is not None:
        kw["softmax_param"] = dict(axis=axis)
    if ignore_label is not None:
        kw["loss_param"] = dict(ignore_label=ignore_label)
    lp = _base("SoftmaxWithLoss", name, bottoms, **kw)
    if loss_weight is not None:
        lp.loss_weight.append(float(loss_weight))
    return lp


def AccuracyLayer(name, bottoms, top_k=1, include=TEST):
    return _base("Accuracy", name, bottoms, include=include,
                 accuracy_param=dict(top_k=top_k))


def LRNLayer(name, bottoms, local_size=5, alpha=1.0, beta=0.75,
             norm_region="ACROSS_CHANNELS"):
    return _base("LRN", name, bottoms, lrn_param=dict(
        local_size=local_size, alpha=alpha, beta=beta,
        norm_region=norm_region))


def DropoutLayer(name, bottoms, tops=None, ratio=0.5):
    return _base("Dropout", name, bottoms, tops=tops,
                 dropout_param=dict(dropout_ratio=ratio))


def ShiftLayer(name, bottoms, offset=1, axis=1, fill=0.0):
    """sparknet_tpu extension: y[i] = x[i + offset] along `axis`, `fill`
    where that reads past an end."""
    return _base("Shift", name, bottoms,
                 shift_param=dict(axis=axis, offset=offset, fill=fill))


def ConcatLayer(name, bottoms, axis=1):
    return _base("Concat", name, bottoms, concat_param=dict(axis=axis))


def BatchNormLayer(name, bottoms, tops=None, **kw):
    return _base("BatchNorm", name, bottoms, tops=tops,
                 batch_norm_param=kw or None)


def EltwiseLayer(name, bottoms, operation="SUM", coeff=None):
    ep = dict(operation=operation)
    if coeff:
        ep["coeff"] = list(coeff)
    return _base("Eltwise", name, bottoms, eltwise_param=ep)


def SoftmaxLayer(name, bottoms):
    return _base("Softmax", name, bottoms)


def _with_params(lp, param):
    for p in (param or []):
        lp.add("param", **p)
    return lp


def AttentionLayer(name, bottoms, num_heads, head_dim=None, causal=False,
                   ring=False, flash=False, num_kv_heads=None,
                   qk_norm=False, rotary_dim=0, rope_theta=None,
                   output_gate=False, norm_eps=None, weight_filler=None,
                   param=None, window=None, qk_norm_zero_centered=None,
                   index_heads=None, index_head_dim=None, index_topk=None,
                   index_stats=False, out_filler=None, q_lora_rank=None,
                   kv_lora_rank=None, qk_nope_head_dim=None,
                   qk_rope_head_dim=None, v_head_dim=None, gate=None,
                   rope=None):
    """sparknet_tpu extension for the long-context path (see
    parallel.ring_attention, ops.pallas_attention). `num_kv_heads` selects
    the grouped-query form (bias-free q/k/v/out projections; qk_norm,
    rotary_dim, rope_theta, output_gate belong to it). `window` (with
    causal): a sliding window of that many keys, the query's own among
    them. `qk_norm_zero_centered` False: the plain form of the two norms.
    `index_heads`, `index_head_dim`, `index_topk` (with causal and
    num_kv_heads): a learned index picks every query's `index_topk` keys
    (ops/dsa.py); five more blobs (W_qI, W_kI, W_w, the index key's
    LayerNorm weight and bias) and a second top `<name>_kl`, the index's
    own loss, of weight 1; `index_stats` adds a third (weight 0), the
    share of the selected keys inside a window of `index_topk`.
    `out_filler` fills the grouped-query form's out projection.
    `kv_lora_rank` selects multi-head latent attention (with causal and
    without num_kv_heads; ops/attention.py): `q_lora_rank`,
    `qk_nope_head_dim`, `qk_rope_head_dim` and `v_head_dim` are its other
    sizes, `rope_theta` and `norm_eps` its rotary's and its two latent
    norms'; seven blobs. `gate` (with num_kv_heads) names the output
    gate's form: "elementwise" is `output_gate`, "head" one scalar a head
    from one more blob W_g (num_heads, E). `rope` (with rotary_dim) is a
    dict of the rotary table's fields beside `rope_theta`: `rope_type`
    "yarn" with `rope_factor`, `rope_original_positions`, `rope_beta_fast`,
    `rope_beta_slow`, and `rope_scale`, the factor on cos and sin."""
    if gate not in (None, "none", "elementwise", "head"):
        raise ValueError(f"{name}: gate {gate!r}: none, elementwise or head")
    output_gate = output_gate or gate == "elementwise"
    ap = dict(num_heads=num_heads, causal=causal, ring=ring, flash=flash)
    latent = dict(q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
                  qk_nope_head_dim=qk_nope_head_dim,
                  qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim)
    if kv_lora_rank is not None:
        latent.update(rope_theta=rope_theta, norm_eps=norm_eps)
    ap.update({k: v for k, v in latent.items() if v is not None})
    if out_filler is not None:
        ap["out_filler"] = out_filler
    if window:
        ap["window"] = window
    if qk_norm_zero_centered is not None:
        ap["qk_norm_zero_centered"] = qk_norm_zero_centered
    if head_dim is not None:
        ap["head_dim"] = head_dim
    if num_kv_heads is not None:
        ap.update(num_kv_heads=num_kv_heads, qk_norm=qk_norm,
                  rotary_dim=rotary_dim, output_gate=output_gate)
        if rope_theta is not None:
            ap["rope_theta"] = rope_theta
        if norm_eps is not None:
            ap["norm_eps"] = norm_eps
    if gate == "head":
        ap["head_gate"] = True
    ap.update(rope or {})
    if weight_filler is not None:
        ap["weight_filler"] = weight_filler
    if index_heads is None:
        return _with_params(_base("Attention", name, bottoms,
                                  attention_param=ap), param)
    ap.update(index_heads=index_heads, index_head_dim=index_head_dim,
              index_topk=index_topk, index_stats=index_stats)
    tops = [name, f"{name}_kl"] + ([f"{name}_stats"] if index_stats else [])
    lp = _base("Attention", name, bottoms, tops=tops, attention_param=ap)
    lp.loss_weight.extend([0.0, 1.0] + ([0.0] if index_stats else []))
    return _with_params(lp, param)


def GatedDeltaNetLayer(name, bottoms, num_k_heads, num_v_heads, head_k_dim,
                       head_v_dim, conv_kernel=4, chunk=None, norm_eps=None,
                       weight_filler=None, param=None):
    """sparknet_tpu extension: Gated DeltaNet linear attention
    (ops/deltanet.py); `param` lists its seven blobs' multipliers
    (W_qkvz, W_ba, conv, A_log, dt_bias, norm, W_out)."""
    gp = dict(num_k_heads=num_k_heads, num_v_heads=num_v_heads,
              head_k_dim=head_k_dim, head_v_dim=head_v_dim,
              conv_kernel=conv_kernel)
    if chunk is not None:
        gp["chunk"] = chunk
    if norm_eps is not None:
        gp["norm_eps"] = norm_eps
    if weight_filler is not None:
        gp["weight_filler"] = weight_filler
    return _with_params(_base("GatedDeltaNet", name, bottoms,
                              gated_delta_net_param=gp), param)


def ShortConvLayer(name, bottoms, kernel=None, weight_filler=None,
                   conv_filler=None, param=None):
    """sparknet_tpu extension: the gated short convolution
    (ops/shortconv.py); `param` lists its three blobs' multipliers
    (W_in, conv, W_out)."""
    sp = {}
    for key, val in (("kernel", kernel), ("weight_filler", weight_filler),
                     ("conv_filler", conv_filler)):
        if val is not None:
            sp[key] = val
    return _with_params(_base("ShortConv", name, bottoms,
                              short_conv_param=sp or None), param)


def Mamba2Layer(name, bottoms, num_heads, head_dim, state_size, n_groups,
                conv_kernel=None, chunk=None, norm_eps=None,
                weight_filler=None, out_filler=None, dt_min=None,
                dt_max=None, stats=False, param=None):
    """sparknet_tpu extension: the Mamba-2 state-space mixer
    (ops/mamba2.py); `param` lists its eight blobs' multipliers (W_in,
    conv, conv bias, A_log, D, dt_bias, norm, W_out). stats=True adds a
    (weight-0) top with the share of a state that survives a chunk."""
    mp = dict(num_heads=num_heads, head_dim=head_dim, state_size=state_size,
              n_groups=n_groups)
    for key, val in (("conv_kernel", conv_kernel), ("chunk", chunk),
                     ("norm_eps", norm_eps), ("weight_filler", weight_filler),
                     ("out_filler", out_filler), ("dt_min", dt_min),
                     ("dt_max", dt_max), ("stats", stats or None)):
        if val is not None:
            mp[key] = val
    tops = [name, f"{name}_stats"] if stats else [name]
    lp = _base("Mamba2", name, bottoms, tops=tops, mamba2_param=mp)
    if stats:
        lp.loss_weight.extend([0.0, 0.0])
    return _with_params(lp, param)


def RMSNormLayer(name, bottoms, tops=None, eps=None, zero_centered=None,
                 param=None):
    """sparknet_tpu extension: last-axis RMS norm, one blob."""
    rp = {}
    if eps is not None:
        rp["eps"] = eps
    if zero_centered is not None:
        rp["zero_centered"] = zero_centered
    return _with_params(_base("RMSNorm", name, bottoms, tops=tops,
                              rms_norm_param=rp or None), param)


def EmbedLayer(name, bottoms, input_dim, num_output, weight_filler=None,
               bias_term=None, param=None):
    ep = dict(input_dim=input_dim, num_output=num_output)
    if weight_filler is not None:
        ep["weight_filler"] = weight_filler
    if bias_term is not None:
        ep["bias_term"] = bias_term
    return _with_params(_base("Embed", name, bottoms, embed_param=ep), param)


def PositionalEmbedLayer(name, bottoms, max_positions, num_output,
                         weight_filler=None, tops=None):
    """sparknet_tpu extension: learned positional table added in place."""
    ep = dict(input_dim=max_positions, num_output=num_output)
    if weight_filler is not None:
        ep["weight_filler"] = weight_filler
    return _base("PositionalEmbed", name, bottoms, tops=tops, embed_param=ep)


def MoELayer(name, bottoms, num_experts, hidden_dim=None,
             capacity_factor=None, expert_parallel=False,
             aux_loss_weight=None, weight_filler=None, stats=False,
             top_k=None, experts_held=None, first_expert=None,
             shared_hidden_dim=None, norm_topk_prob=None, tile_rows=None,
             expert_activation=None, score_function=None,
             selection_bias=None, topk_eps=None, routed_scaling_factor=None,
             expert_gate_matrix=None, shared_gate=None, down_filler=None):
    """sparknet_tpu extension: MoE FFN. The top-1 Switch form:
    aux_loss_weight adds a second top carrying the load-balancing loss
    with that loss_weight; stats=True adds a third (weight-0) diagnostics
    top with per-expert token fractions + the overflow fraction. Naming
    `top_k` selects the no-drop form (ops/moe.py: gated SiLU experts,
    top_k of num_experts with renormalised weights, `experts_held` of
    them from `first_expert` on held here, an optional shared expert);
    there stats=True adds one (weight-0) top [share of pairs on held
    experts, largest over mean held load]. `expert_activation` "relu"
    makes the experts ReLU-gated; a second bottom feeds the router.
    `score_function` "sigmoid", `selection_bias`, `topk_eps` and
    `routed_scaling_factor` are the route's (ops/moe.py).
    `expert_gate_matrix` False makes an expert two matrices (with
    `expert_activation` "relu2": W_down relu(W_up x)^2), `shared_gate`
    False adds the shared expert with no sigmoid gate; `down_filler` fills
    the down projections."""
    if top_k is not None:
        mp = dict(num_experts=num_experts, gated_experts=True, top_k=top_k)
        for key, val in (("hidden_dim", hidden_dim),
                         ("experts_held", experts_held),
                         ("first_expert", first_expert),
                         ("shared_hidden_dim", shared_hidden_dim),
                         ("norm_topk_prob", norm_topk_prob),
                         ("tile_rows", tile_rows),
                         ("expert_activation", expert_activation),
                         ("score_function", score_function),
                         ("selection_bias", selection_bias),
                         ("topk_eps", topk_eps),
                         ("routed_scaling_factor", routed_scaling_factor),
                         ("expert_gate_matrix", expert_gate_matrix),
                         ("shared_gate", shared_gate),
                         ("down_filler", down_filler),
                         ("weight_filler", weight_filler)):
            if val is not None:
                mp[key] = val
        tops = [name, f"{name}_stats"] if stats else [name]
        lp = _base("MoE", name, bottoms, tops=tops, moe_param=mp)
        if stats:
            lp.loss_weight.extend([0.0, 0.0])
        return lp
    mp = dict(num_experts=num_experts, expert_parallel=expert_parallel)
    if hidden_dim is not None:
        mp["hidden_dim"] = hidden_dim
    if capacity_factor is not None:
        mp["capacity_factor"] = capacity_factor
    if weight_filler is not None:
        mp["weight_filler"] = weight_filler
    if stats and aux_loss_weight is None:
        aux_loss_weight = 0.0          # stats is top 3; aux must exist
    tops = [name] if aux_loss_weight is None else [name, f"{name}_aux"]
    if stats:
        tops.append(f"{name}_stats")
    lp = _base("MoE", name, bottoms, tops=tops, moe_param=mp)
    if aux_loss_weight is not None:
        lp.loss_weight.extend([0.0, float(aux_loss_weight)]
                              + ([0.0] if stats else []))
    return lp


def LayerNormLayer(name, bottoms, tops=None, eps=None, affine=None):
    """sparknet_tpu extension: last-axis layer norm (transformer blocks)."""
    ln = {}
    if eps is not None:
        ln["eps"] = eps
    if affine is not None:
        ln["affine"] = affine
    return _base("LayerNorm", name, bottoms, tops=tops,
                 layer_norm_param=ln or None)


def NetParam(name, *layers):
    net = Message("NetParameter", name=name)
    for l in layers:
        net.layer.append(l)
    return net
