"""What a remat policy keeps: the outputs of a block's kernels.

`jax.checkpoint` traces a block again in the backward pass. Where the
block holds a pallas kernel with a `custom_vjp`, whatever the kernel's
backward reads of its forward's results (a flash pass's output and
logsumexp, the delta rule's output, states and inverses) would be made by
running the whole forward kernel a second time. So the kernel's wrapper
passes those arrays through `keep` inside its forward rule, and every
policy of `graph/compiler.py:_checkpointed` saves what carries
`KERNEL_OUT`: the kernel's forward runs once. Without remat the name is an
identity. Neither half moves anything alone.

Core jax only: the CNN path imports this module and must not pay for
`jax.experimental.pallas` (PR 29).
"""

from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import default_tracer

#: the one name, put on by the kernels' wrappers and read by the policies
KERNEL_OUT = "kernel_out"


def keep(x, layer, array):
    """`x` under `KERNEL_OUT`, and one `remat.kept` record in the ring of
    obs/trace.py a trace of the wrapper's forward rule: the `layer` the
    wrapper's caller named (None where it named none), the `array`, its
    `shape`, `dtype` and `bytes` — what a policy holds back from a block's
    forward pass until its backward, and how much memory that is (under a
    scan, times the iterations)."""
    tracer = default_tracer()
    now = tracer.now_ns()
    tracer.record("remat.kept", now, now, layer=layer, array=array,
                  shape=tuple(x.shape), dtype=str(x.dtype),
                  bytes=x.size * x.dtype.itemsize)
    return checkpoint_name(x, KERNEL_OUT)
