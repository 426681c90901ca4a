"""The program's net for the `qwen3_next_80b_a3b` configuration:
`models/zoo.py:qwen3_next` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.qwen3_next import dims
    with open(os.path.join(HERE, "configs", "qwen3_next_80b_a3b.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    held, first = d.pop("num_experts"), d.pop("first_expert")
    return zoo.qwen3_next(
        batch_size=batch_size, num_experts=d.pop("router_outputs"),
        experts_held=held, first_expert=first, **d)
