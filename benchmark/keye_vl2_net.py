"""The program's net for the `keye_vl2_30b_a3b` configuration:
`models/zoo.py:keye_vl2` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.keye_vl2 import dims
    with open(os.path.join(HERE, "configs", "keye_vl2_30b_a3b.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    held = d.pop("num_experts")
    if d.pop("selection") != "index":
        raise SystemExit("benchmark: the program has one selection, the "
                         "index's; `selection` is the reference's control")
    return zoo.keye_vl2(batch_size=batch_size,
                        num_experts=d.pop("router_outputs"),
                        experts_held=held, **d)
