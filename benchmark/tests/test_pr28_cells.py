"""PR 28's benchmark files on the CPU: the FLOP count of the drawn
configuration, the kernels' costs, the exposed-all-reduce arithmetic and
the rehearsals of both new cells. Run by hand:
`python -m pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def config():
    with open(os.path.join(HERE, "configs", "qwen3_next_80b_a3b.json")) as f:
        return json.load(f)


def test_train_flops_of_the_cut_configuration():
    import qwen3_next_flops as fl
    from reference.qwen3_next import dims, layer_specs
    c = config()
    assert 11.0e12 < fl.train_flops(c) < 12.0e12
    d = dims(c)
    params = sum(int(__import__("math").prod(shape))
                 for _, blobs in layer_specs(d) for shape, *_ in blobs)
    assert params == 424_340_544
    parts = fl.forward_macs(d)
    # the flash pass is about a seventh of the model's operations, the held
    # experts' product about 2%
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert 0.12 < share["attn_core"] < 0.16
    assert 0.01 < share["routed"] < 0.03
    for cost in (fl.gdn_scan_cost, fl.moe_experts_cost, fl.flash_gqa_cost):
        ops, bytes_ = cost(c, 2)
        assert ops > 0 and bytes_ > 0


def test_catalog_keys_are_kept():
    c = config()
    assert (c["hidden_size"], c["head_dim"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["linear_key_head_dim"]) == \
        (2048, 256, 512, 10, 128)
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (4, 16, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert sorted(c["reduced"]) == ["num_experts", "num_hidden_layers",
                                    "vocab_size"]


def test_exposed_all_reduce_is_what_compute_does_not_cover():
    from layer_metrics import allreduce_exposed_ms as m
    assert m.exposed_ns([(0, 10)], [(2, 4), (6, 20)]) == 4.0
    assert m.exposed_ns([(0, 10), (5, 12)], []) == 12.0
    assert m.exposed_ns([(0, 10)], [(0, 10)]) == 0.0
    assert m.read({"xplane": None, "trace": None}) is None
    # by opcode, not by the instruction's name
    for text in ("%psum.79 = f32[96,3,11,11]{3,2,1,0} all-reduce(%x), "
                 "channel_id=1, to_apply=%add", "all-reduce-start.3",
                 "%ag = bf16[8]{0} all-gather-done(%s)",
                 "%cp.1 = f32[4]{0} collective-permute(%x), pairs={{0,1}}"):
        assert m.is_collective(text), text
    for text in ("%fusion.3 = f32[96]{0} fusion(%all-reduce.1), kind=kLoop",
                 "%copy.1 = f32[4]{0} copy(%psum.2)", "fusion.12"):
        assert not m.is_collective(text), text


def rehearse(cell, **env):
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--rehearse", "--seed", "3000000019"], cwd=ROOT,
        env=dict(os.environ, **env), capture_output=True, text=True)


def test_rehearsal_of_the_language_model_cell():
    r = rehearse("qwen3next_ep32_s8192_b2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout


def test_rehearsal_of_the_four_chip_cell_on_four_virtual_devices():
    r = rehearse("caffenet_dp4_b6144",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "device cpu x4" in r.stdout and "correct=True" in r.stdout
