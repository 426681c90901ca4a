"""sparknet_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA rebuild of the capabilities of SparkNet (Berkeley, 2015:
Scala/Spark driver + Caffe/CUDA workers; reference at /root/reference). Caffe-style
NetParameter/prototxt model definitions are compiled to a single jitted XLA train
step; the Spark broadcast -> tau-step local SGD -> collect/average loop and Caffe's
intra-node GPU tree allreduce are both replaced by XLA collectives over a TPU
device mesh (with the tau-step weight-averaging mode kept as a configurable
strategy); data flows from host-sharded loaders straight into device memory.

Layer map (vs reference SURVEY.md section 1):
  proto/     prototxt + binaryproto codecs (replaces protobuf-java + C++ text parse)
  graph/     NetParameter -> init/apply compiler (replaces caffe::Net, net.cpp)
  ops/       layer forward functions on jnp/lax (replaces caffe/src/caffe/layers/*)
  solver/    solver semantics + jitted train step (replaces caffe::Solver hierarchy)
  parallel/  mesh, DP psum, local-SGD averaging, ring attention (replaces Spark
             broadcast/collect + parallel.cpp P2PSync)
  data/      host-side loaders, sampler, prefetch (replaces RDD->JNA callback path)
  models/    NetParam DSL + model builders (replaces Layers.scala)
  utils/     checkpoint, metrics, timing, signals
"""
import time as _time
#: the ring's clock (obs/trace.py) when the package's import began: the
#: process's first Solver writes `package.import` from here
IMPORT_NS = _time.perf_counter_ns()

__version__ = "0.1.0"

# public custom-layer API (see ops/python_layer.py): subclass Layer,
# decorate with @register_layer, and prototxts can use your type string —
# or use type: "Python" + python_param to plug a class in by module path.
from .graph.registry import Layer, register as register_layer  # noqa: E402,F401
