"""Operations a configuration needs per sample trained, from its shapes.

The standard MFU numerator: 2 FLOPs per multiply-accumulate of every
convolution and inner product in the forward pass, times 3 for training
(the backward pass contracts once for the activations' gradient and once
for the weights'). LRN, pooling and elementwise work is left out, so the
share of the peak slightly understates what the chip does. Recomputed
operations never count: the number comes from the configuration, not from
the compiled program.
"""

import importlib


def reference_net(config, batch):
    """(layers, data shape) of `config`'s plain reference at `batch`."""
    ref = importlib.import_module(f"reference.{config['reference']}")
    args = config.get("builder_args", {})
    shape_args = {k: args[k] for k in ("crop_size",) if k in args}
    return (ref.layers(num_classes=args.get("num_classes", 1000)),
            ref.data_shape(batch, **shape_args))


def conv_fc_train_flops(config):
    """FLOPs to train on one sample of `config` (a loaded configs/*.json)."""
    from reference import plain
    return 3 * 2 * plain.conv_fc_macs(*reference_net(config, 1))


def train_flops_per_sample(config):
    """The configuration names its counting function as `module:function`
    under `flops`; a model whose work is not conv/fc brings a file of its
    own."""
    mod, fn = config.get("flops", "flops:conv_fc_train_flops").split(":")
    return getattr(importlib.import_module(mod), fn)(config)


def peak_for(device_kind, peaks):
    """The published peaks of `device_kind`, or None: a device that is not
    in the table is an error for the caller, never a default."""
    for kind, row in peaks["by_device_kind"].items():
        if kind.lower() == device_kind.lower():
            return row
    return None
