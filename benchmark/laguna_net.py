"""The program's net for the `laguna_xs_2` configuration:
`models/zoo.py:laguna` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.laguna import dims
    with open(os.path.join(HERE, "configs", "laguna_xs_2.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    if not (d.pop("output_gate") and d.pop("yarn_rope")):
        raise SystemExit("benchmark: the program's attention has its "
                         "per-head gate and its full layers their own "
                         "rotary table; `output_gate` or `yarn_rope` false "
                         "is the reference's control")
    held = d.pop("num_experts")
    return zoo.laguna(batch_size=batch_size,
                      num_experts=d.pop("router_outputs"),
                      experts_held=held, **d)
