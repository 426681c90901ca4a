"""conv1-only fwd+bwd microbench: s2d on vs off (why the end-to-end lost)."""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from sparknet_tpu.utils.compile_cache import configure_compile_cache
configure_compile_cache()

from tests.test_layers import make_layer

BATCH = 256
ITERS = 50
ROUNDS = 5

cases = {
    "caffenet_conv1": ((BATCH, 3, 227, 227), 96, 11, 4, 0),
    "googlenet_conv1": ((BATCH, 3, 224, 224), 64, 7, 2, 3),
}

out = {}
for name, (shape, o, k, s, p) in cases.items():
    layer, _ = make_layer(
        "Convolution", [shape],
        convolution_param=dict(num_output=o, kernel_size=[k], stride=[s],
                               pad=[p]))
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(*layer.weight_shape) * 0.01, jnp.bfloat16)
    b = jnp.zeros((o,), jnp.bfloat16)
    x = jnp.asarray(rs.randn(*shape), jnp.bfloat16)

    fns = {}
    for v in ("off", "on"):
        os.environ["SPARKNET_CONV_S2D"] = v

        def step(wv, xv):
            def f(wv):
                (y,) = layer.apply([wv, b], [xv], True, None)
                return (y.astype(jnp.float32) ** 2).sum()
            l, g = jax.value_and_grad(f)(wv)
            return l, g
        fns[v] = jax.jit(step)
        l, g = fns[v](w, x)
        float(l)
    res = {v: [] for v in fns}
    for r in range(ROUNDS):
        for v in fns:
            t0 = time.perf_counter()
            for _ in range(ITERS):
                l, g = fns[v](w, x)
            float(l)
            res[v].append((time.perf_counter() - t0) / ITERS * 1000)
    out[name] = {v: round(sorted(ds)[len(ds) // 2], 3)
                 for v, ds in res.items()}
print(json.dumps({"batch": BATCH, "median_ms_per_step": out}))
