"""BVLC GoogLeNet (caffe/models/bvlc_googlenet/train_val.prototxt), TRAIN
phase, as a plain layer list: 9 inception modules, two auxiliary heads at
loss weight 0.3, dropout 0.7 in the heads and 0.4 before the classifier.
Fillers as published: xavier weights, bias 0.2 on every convolution and on
the heads' first inner product, 0 on the classifiers."""

from . import plain as P

_XAVIER = ("xavier",)

# (1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj)
INCEPTION = {
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


def _conv(name, bottom, n, k, stride=1, pad=0):
    return P.conv(name, bottom, n, k, stride=stride, pad=pad,
                  filler=_XAVIER, bias=0.2)


def _inception(key, bottom):
    n1, r3, n3, r5, n5, pp = INCEPTION[key]
    p = f"inception_{key}"
    return [
        _conv(f"{p}/1x1", bottom, n1, 1),
        P.relu(f"{p}/relu_1x1", f"{p}/1x1"),
        _conv(f"{p}/3x3_reduce", bottom, r3, 1),
        P.relu(f"{p}/relu_3x3_reduce", f"{p}/3x3_reduce"),
        _conv(f"{p}/3x3", f"{p}/3x3_reduce", n3, 3, pad=1),
        P.relu(f"{p}/relu_3x3", f"{p}/3x3"),
        _conv(f"{p}/5x5_reduce", bottom, r5, 1),
        P.relu(f"{p}/relu_5x5_reduce", f"{p}/5x5_reduce"),
        _conv(f"{p}/5x5", f"{p}/5x5_reduce", n5, 5, pad=2),
        P.relu(f"{p}/relu_5x5", f"{p}/5x5"),
        P.pool(f"{p}/pool", bottom, "MAX", 3, 1, pad=1),
        _conv(f"{p}/pool_proj", f"{p}/pool", pp, 1),
        P.relu(f"{p}/relu_pool_proj", f"{p}/pool_proj"),
        P.concat(f"{p}/output", [f"{p}/1x1", f"{p}/3x3", f"{p}/5x5",
                                 f"{p}/pool_proj"]),
    ], f"{p}/output"


def _aux_head(idx, bottom, num_classes):
    p = f"loss{idx}"
    return [
        P.pool(f"{p}/ave_pool", bottom, "AVE", 5, 3),
        _conv(f"{p}/conv", f"{p}/ave_pool", 128, 1),
        P.relu(f"{p}/relu_conv", f"{p}/conv"),
        P.fc(f"{p}/fc", f"{p}/conv", 1024, filler=_XAVIER, bias=0.2),
        P.relu(f"{p}/relu_fc", f"{p}/fc"),
        P.dropout(f"{p}/drop_fc", f"{p}/fc", 0.7),
        P.fc(f"{p}/classifier", f"{p}/fc", num_classes, filler=_XAVIER),
        P.softmax_loss(f"{p}/loss", f"{p}/classifier", weight=0.3),
    ]


def layers(num_classes=1000):
    ls = [
        P.feed("data"), P.feed("label"),
        _conv("conv1/7x7_s2", "data", 64, 7, stride=2, pad=3),
        P.relu("conv1/relu_7x7", "conv1/7x7_s2"),
        P.pool("pool1/3x3_s2", "conv1/7x7_s2", "MAX", 3, 2),
        P.lrn("pool1/norm1", "pool1/3x3_s2"),
        _conv("conv2/3x3_reduce", "pool1/norm1", 64, 1),
        P.relu("conv2/relu_3x3_reduce", "conv2/3x3_reduce"),
        _conv("conv2/3x3", "conv2/3x3_reduce", 192, 3, pad=1),
        P.relu("conv2/relu_3x3", "conv2/3x3"),
        P.lrn("conv2/norm2", "conv2/3x3"),
        P.pool("pool2/3x3_s2", "conv2/norm2", "MAX", 3, 2),
    ]
    bottom = "pool2/3x3_s2"
    for key in ("3a", "3b"):
        mod, bottom = _inception(key, bottom)
        ls += mod
    ls.append(P.pool("pool3/3x3_s2", bottom, "MAX", 3, 2))
    bottom = "pool3/3x3_s2"
    for key in ("4a", "4b", "4c", "4d", "4e"):
        mod, bottom = _inception(key, bottom)
        ls += mod
        if key == "4a":
            ls += _aux_head(1, bottom, num_classes)
        if key == "4d":
            ls += _aux_head(2, bottom, num_classes)
    ls.append(P.pool("pool4/3x3_s2", bottom, "MAX", 3, 2))
    bottom = "pool4/3x3_s2"
    for key in ("5a", "5b"):
        mod, bottom = _inception(key, bottom)
        ls += mod
    ls += [
        P.pool("pool5/7x7_s1", bottom, "AVE", 7, 1),
        P.dropout("pool5/drop_7x7_s1", "pool5/7x7_s1", 0.4),
        P.fc("loss3/classifier", "pool5/7x7_s1", num_classes,
             filler=_XAVIER),
        P.softmax_loss("loss3/loss3", "loss3/classifier"),
    ]
    return ls


def data_shape(batch, crop_size=224):
    return (batch, 3, crop_size, crop_size)
