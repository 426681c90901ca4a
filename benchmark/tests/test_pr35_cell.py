"""PR 35's benchmark files on the CPU: the FLOP count of the drawn
configuration, the costs of its kernels and of the memory-bound mix, the
seven readers on a made-up trace, the catalog's keys and the rehearsal of
the new cell. Run by hand: `python -m pytest benchmark/tests -q`."""

import json
import math
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "lfm2moe_ep4_s8192_b3"


def config():
    with open(os.path.join(HERE, "configs", "lfm2_8b_a1b.json")) as f:
        return json.load(f)


def test_train_flops_of_the_cut_configuration():
    import lfm2_moe_flops as fl
    from reference.lfm2_moe import dims, layer_specs
    c = config()
    assert abs(fl.train_flops(c) - 10.63e12) < 0.01e12
    d = dims(c)
    # the stage: layer 0 (dense) and the period 2-5, the router at 32
    assert d["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                "conv"]
    assert (d["num_dense_layers"], d["num_hidden_layers"],
            d["num_experts"], d["router_outputs"]) == (1, 5, 8, 32)
    specs = layer_specs(d)
    params = sum(math.prod(shape) for _, blobs in specs
                 for shape, *_ in blobs)
    assert params == 507_820_288
    # the tied table stands once and the head owns nothing
    assert [n for n, _ in specs if "head" in n] == []
    # the bias: a buffer of the router's width that nothing moves
    assert specs[[n for n, _ in specs].index("block1/moe")][1][-1] == \
        ((32,), ("constant", 0.0), (0.0, 0.0))
    parts = fl.forward_macs(d)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert 0.30 < share["conv_proj"] < 0.32
    assert abs(share["dense_ff"] - share["routed"]) < 1e-12
    assert 0.20 < share["routed"] < 0.21 and 0.15 < share["head"] < 0.16
    assert 0.07 < share["attn_core"] < 0.08 and share["router"] < 0.002
    # the kernels: 7 products over the causal half at head 64; the experts
    # 3 x their forward; the mix bound by memory by orders of magnitude
    ops, bytes_ = fl.flash_h64_cost(c, 3)
    assert ops == 3 * 32 * 7 * 2 * (8192 * 8193 // 2) * 64 and bytes_ > 0
    ops, bytes_ = fl.experts_cost(c, 3)
    assert ops == 3 * 2 * 3 * parts["routed"] and bytes_ > 0
    ops, bytes_ = fl.shortconv_mix_cost(c, 3)
    assert ops / 197e12 < 1e-2 * bytes_ / 819e9


def test_catalog_keys_are_kept():
    c = config()
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 24, "num_experts": 32,
                              "vocab_size": 65536}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == \
        (5, 8, 16384)
    assert len(c["layers_held"]) == c["num_hidden_layers"]
    if not os.path.exists(CATALOG):
        return
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in c, key
        if key not in c["reduced"]:
            assert c[key] == value, key


def ctx(op_seconds, units=3):
    return {"op_seconds": op_seconds, "batch": 3, "sync_every": 1,
            "trace": {"units": units}, "xplane": None,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_made_up_trace():
    import lfm2_moe_flops as fl
    from layer_metrics import (lfm2_device_idle_pct, lfm2_experts_roofline,
                               lfm2_flash_h64_roofline,
                               lfm2_moe_dispatch_combine_ms,
                               lfm2_moe_route_ms, lfm2_shortconv_roofline,
                               lfm2_step_prep_ms)
    c = config()
    # kernels that ran at exactly their roofline read 100, by name
    ops, bytes_ = fl.flash_h64_cost(c, 3)
    t = max(ops / 197e12, bytes_ / 819e9)
    seen = ctx({"flash_fwd.3": 3 * t * 0.5, "flash_dq.1": 3 * t * 0.5,
                "flash_dkv": 3 * t * 1.0, "flash_swa_fwd": 5.0,
                "fusion.7": 9.0})
    assert abs(lfm2_flash_h64_roofline.read(seen) - 50.0) < 1e-9
    # a trace without the kernels, or without a step: nothing, never 0
    assert lfm2_flash_h64_roofline.read(ctx({"fusion.7": 9.0})) is None
    assert lfm2_flash_h64_roofline.read(
        ctx({"flash_fwd": 1.0}, units=0)) is None
    # by scope: no trace file (and on the parent no such scope), nothing
    assert lfm2_shortconv_roofline.read(seen) is None
    assert lfm2_experts_roofline.read(seen) is None
    assert lfm2_moe_route_ms.read(seen) is None
    assert lfm2_moe_dispatch_combine_ms.read(seen) is None
    # the share of a scope's seconds, handed over directly
    ops, bytes_ = fl.shortconv_mix_cost(c, 3)
    least = max(ops / 197e12, bytes_ / 819e9)
    assert abs(fl.roofline_pct(seen, fl.shortconv_mix_cost, 3 * least * 4)
               - 25.0) < 1e-9
    assert fl.roofline_pct(seen, fl.shortconv_mix_cost, 0.0) is None
    # the host's and the device's share
    assert lfm2_step_prep_ms.read(dict(seen, dispatch_s=[])) is None
    assert lfm2_device_idle_pct.read(dict(seen, trace=None)) is None
    assert lfm2_device_idle_pct.read(
        dict(seen, trace={"units": 3, "idle_pct": 0.25})) == 0.25
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    for m in (lfm2_shortconv_roofline, lfm2_flash_h64_roofline,
              lfm2_experts_roofline, lfm2_moe_route_ms,
              lfm2_moe_dispatch_combine_ms, lfm2_step_prep_ms,
              lfm2_device_idle_pct):
        entry = next(e for e in per_layer if e["name"] == m.META["name"])
        assert entry["workloads"] == [CELL]
        assert {k: entry[k] for k in m.META} == m.META


def test_the_benchmark_gains_one_configuration_and_one_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert [c["name"] for c in b["configs"]][-1] == "lfm2_8b_a1b"
    assert len(b["configs"]) == 5 and len(b["workloads"]) == 7
    cell = b["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "lfm2_8b_a1b", "resident_tokens_s8192_b3", 1)
    assert len(cell["why"]) <= 200
    with open(os.path.join(HERE, "traffic",
                           "resident_tokens_s8192_b3.json")) as f:
        traffic = json.load(f)
    assert (traffic["feed"], traffic["batch"], traffic["sync_every"]) == \
        ("resident_tokens", 3, 1)
    # one four-chip cell of seven: a quarter, rounded down
    assert sum(1 for w in b["workloads"] if w["chips"] == 4) == 1


def test_rehearsal_of_the_lfm2_cell():
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3000000019", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout
