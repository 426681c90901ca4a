"""The program's net for the `smallthinker_21b_a3b` configuration:
`models/zoo.py:smallthinker` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.smallthinker import dims
    with open(os.path.join(HERE, "configs", "smallthinker_21b_a3b.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    held = d.pop("moe_num_primary_experts")
    return zoo.smallthinker(
        batch_size=batch_size,
        moe_num_primary_experts=d.pop("router_outputs"),
        experts_held=held, **d)
