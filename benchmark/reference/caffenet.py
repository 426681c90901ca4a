"""BVLC reference CaffeNet (caffe/models/bvlc_reference_caffenet/
train_val.prototxt), TRAIN phase, as a plain layer list: AlexNet with
pooling before normalisation, grouped conv2/4/5, two LRN, dropout 0.5 on
fc6 and fc7. Fillers as published: gaussian 0.01 (0.005 on fc6/fc7), bias 0
on conv1, conv3 and fc8 and 1 elsewhere."""

from . import plain as P


def layers(num_classes=1000):
    return [
        P.feed("data"), P.feed("label"),
        P.conv("conv1", "data", 96, 11, stride=4),
        P.relu("relu1", "conv1"),
        P.pool("pool1", "conv1", "MAX", 3, 2),
        P.lrn("norm1", "pool1"),
        P.conv("conv2", "norm1", 256, 5, pad=2, group=2, bias=1.0),
        P.relu("relu2", "conv2"),
        P.pool("pool2", "conv2", "MAX", 3, 2),
        P.lrn("norm2", "pool2"),
        P.conv("conv3", "norm2", 384, 3, pad=1),
        P.relu("relu3", "conv3"),
        P.conv("conv4", "conv3", 384, 3, pad=1, group=2, bias=1.0),
        P.relu("relu4", "conv4"),
        P.conv("conv5", "conv4", 256, 3, pad=1, group=2, bias=1.0),
        P.relu("relu5", "conv5"),
        P.pool("pool5", "conv5", "MAX", 3, 2),
        P.fc("fc6", "pool5", 4096, filler=("gaussian", 0.005), bias=1.0),
        P.relu("relu6", "fc6"),
        P.dropout("drop6", "fc6", 0.5),
        P.fc("fc7", "fc6", 4096, filler=("gaussian", 0.005), bias=1.0),
        P.relu("relu7", "fc7"),
        P.dropout("drop7", "fc7", 0.5),
        P.fc("fc8", "fc7", num_classes),
        P.softmax_loss("loss", "fc8"),
    ]


def data_shape(batch, crop_size=227):
    return (batch, 3, crop_size, crop_size)
