"""Layer implementations (registered by import side effect).

The TPU-native layer zoo replacing reference caffe/src/caffe/layers/* —
jnp/lax expressions traced into one XLA program; kernels come from XLA
(MXU for conv/matmul), not hand-written CUDA.
"""

from . import (  # noqa: F401
    convolution,
    pooling,
    lrn,
    dense,
    activations,
    normalization,
    structural,
    losses,
    feed,
    attention,
    deltanet,
    shortconv,
    mamba2,
    moe,
    python_layer,
)
