"""The program's net for the `glm_4_7_flash` configuration:
`models/zoo.py:glm4_moe_lite` from the configuration file's published keys,
`builder_args` (the sequence length; a rehearsal's toy sizes) laid over
them, so that no size is written twice."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def net(batch_size, **builder_args):
    from sparknet_tpu.models import zoo
    from reference.glm4_moe_lite import dims
    with open(os.path.join(HERE, "configs", "glm_4_7_flash.json")) as f:
        config = json.load(f)
    d = dims(dict(config, builder_args=dict(config["builder_args"],
                                            **builder_args)))
    if not (d.pop("shared_rope_key") and d.pop("kv_latent_norm")):
        raise SystemExit("benchmark: the program's keys carry the shared "
                         "rotary part and its key-value latent is normed; "
                         "`shared_rope_key` or `kv_latent_norm` false is "
                         "the reference's control")
    held = d.pop("n_routed_experts")
    return zoo.glm4_moe_lite(batch_size=batch_size,
                             n_routed_experts=d.pop("router_outputs"),
                             experts_held=held, **d)
