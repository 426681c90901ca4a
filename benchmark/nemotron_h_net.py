"""The program's net for the `nemotron_twotower_30b_a3b` configuration:
`models/zoo.py:nemotron_h` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.nemotron_h import dims
    with open(os.path.join(HERE, "configs",
                           "nemotron_twotower_30b_a3b.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    if not d.pop("carry"):
        raise SystemExit("benchmark: the program's scan carries its state; "
                         "`carry` false is the reference's control")
    held, pattern = d.pop("n_routed_experts"), d.pop("whole_pattern")
    return zoo.nemotron_h(batch_size=batch_size, pattern=pattern,
                          layers=(0, len(d.pop("pattern"))),
                          n_routed_experts=d.pop("router_outputs"),
                          experts_held=held, **d)
