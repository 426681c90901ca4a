"""Interleaved A/B: stem-conv space-to-depth on/off (CaffeNet/GoogLeNet)."""
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

from sparknet_tpu.models import zoo
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver

MODEL = sys.argv[1] if len(sys.argv) > 1 else "caffenet"
BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 256
ITERS = 20
ROUNDS = 6

side = 227 if MODEL == "caffenet" else 224
rs = np.random.RandomState(0)
batch = {"data": jnp.asarray(rs.randn(BATCH, 3, side, side), jnp.bfloat16),
         "label": jnp.asarray(rs.randint(0, 1000, BATCH), jnp.int32)}

solvers = {}
for v in ("off", "auto"):
    os.environ["SPARKNET_CONV_S2D"] = v
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 momentum=0.9, weight_decay=0.0005, display=0,
                 random_seed=0)
    net = getattr(zoo, MODEL)(batch_size=BATCH, num_classes=1000)
    s = Solver(sp, net_param=net)
    for _ in range(3):
        loss = s.train_step(batch)
    float(loss)
    solvers[v] = s
    print("compiled s2d", v, "loss", float(loss), file=sys.stderr)

dts = {v: [] for v in solvers}
for r in range(ROUNDS):
    for v in solvers:
        s = solvers[v]
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = s.train_step(batch)
        float(loss)
        dts[v].append(time.perf_counter() - t0)

out = {}
for v, ds in dts.items():
    rates = sorted(BATCH * ITERS / dt for dt in ds)
    out[v] = {"best": round(rates[-1], 1),
              "median": round(rates[len(rates) // 2], 1),
              "worst": round(rates[0], 1)}
print(json.dumps({"model": MODEL, "batch": BATCH, "img_per_sec": out}))
