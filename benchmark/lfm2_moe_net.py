"""The program's net for the `lfm2_8b_a1b` configuration:
`models/zoo.py:lfm2_moe` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.lfm2_moe import dims
    with open(os.path.join(HERE, "configs", "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    held = d.pop("num_experts")
    return zoo.lfm2_moe(batch_size=batch_size,
                        num_experts=d.pop("router_outputs"),
                        experts_held=held, **d)
