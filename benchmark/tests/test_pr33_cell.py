"""PR 33's benchmark files on the CPU: the FLOP count of the drawn
configuration, the kernels' costs over the exact band, the seven readers on
a made-up trace, the catalog's keys and the rehearsal of the new cell. Run
by hand: `python -m pytest benchmark/tests -q`."""

import json
import math
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config():
    with open(os.path.join(HERE, "configs",
                           "smallthinker_21b_a3b.json")) as f:
        return json.load(f)


def test_train_flops_of_the_cut_configuration():
    import smallthinker_flops as fl
    from reference.smallthinker import dims, layer_specs
    c = config()
    assert abs(fl.train_flops(c) - 28.18e12) < 0.01e12
    d = dims(c)
    params = sum(math.prod(shape)
                 for _, blobs in layer_specs(d) for shape, *_ in blobs)
    assert params == 370_547_200
    parts = fl.forward_macs(d)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert 0.26 < share["attn_window"] < 0.28
    assert 0.20 < share["attn_global"] < 0.21
    assert 0.16 < share["head"] < 0.18 and 0.05 < share["routed"] < 0.07
    # the band, exactly: W(W+1)/2 + (S-W)W pairs, 43.7% of the causal half
    assert fl.visible_pairs(16384, 4096) == 58_722_304
    assert fl.visible_pairs(16384) == 134_225_920
    assert fl.visible_pairs(2048, 4096) == fl.visible_pairs(2048)
    swa, nope = fl.swa_flash_cost(c, 2), fl.nope_flash_cost(c, 2)
    assert abs(swa[0] / nope[0] - 3 * 58_722_304 / 134_225_920) < 1e-9
    assert swa[1] == 3 * nope[1]
    ops, bytes_ = fl.reglu_experts_cost(c, 2)
    assert ops == 3 * 2 * 2 * parts["routed"] and bytes_ > 0


def test_catalog_keys_are_kept():
    c = config()
    assert c["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 52,
                              "moe_num_primary_experts": 64,
                              "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["moe_num_primary_experts"],
            c["vocab_size"]) == (4, 8, 18992)
    if not os.path.exists(CATALOG):
        return
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in c, key
        if key not in c["reduced"]:
            assert c[key] == value, key


def ctx(op_seconds, units=3):
    return {"op_seconds": op_seconds, "batch": 2, "sync_every": 1,
            "trace": {"units": units}, "xplane": None,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_made_up_trace():
    import smallthinker_flops as fl
    from layer_metrics import (nope_flash_roofline, reglu_experts_roofline,
                               st_device_idle_pct,
                               st_moe_dispatch_combine_ms, st_moe_route_ms,
                               st_step_prep_ms, swa_flash_roofline)
    c = config()
    # kernels that ran at exactly their roofline read 100, by name
    t_swa = max(fl.swa_flash_cost(c, 2)[0] / 197e12,
                fl.swa_flash_cost(c, 2)[1] / 819e9)
    t_nope = max(fl.nope_flash_cost(c, 2)[0] / 197e12,
                 fl.nope_flash_cost(c, 2)[1] / 819e9)
    seen = ctx({"flash_swa_fwd.3": 3 * t_swa * 0.5,
                "flash_swa_dq.1": 3 * t_swa * 0.2,
                "flash_swa_dkv": 3 * t_swa * 0.3,
                "flash_fwd": 3 * t_nope * 2.0, "fusion.7": 9.0})
    assert abs(swa_flash_roofline.read(seen) - 100.0) < 1e-9
    assert abs(nope_flash_roofline.read(seen) - 50.0) < 1e-9
    # a program without the window kernels (the parent): nothing to read
    parent = ctx({"flash_fwd.1": 0.1, "fusion.7": 9.0})
    assert swa_flash_roofline.read(parent) is None
    assert nope_flash_roofline.read(ctx({"fusion.7": 9.0})) is None
    assert nope_flash_roofline.read(ctx({"flash_fwd": 1.0}, units=0)) is None
    # by scope: no trace file, nothing read
    assert reglu_experts_roofline.read(seen) is None
    assert st_moe_route_ms.read(seen) is None
    assert st_moe_dispatch_combine_ms.read(seen) is None
    # the host's and the device's share: no step in the window and no
    # reduced trace read nothing, a reduced trace reads its idle share
    assert st_step_prep_ms.read(dict(seen, dispatch_s=[])) is None
    assert st_device_idle_pct.read(dict(seen, trace=None)) is None
    assert st_device_idle_pct.read(
        dict(seen, trace={"units": 3, "idle_pct": 0.25})) == 0.25
    for m in (swa_flash_roofline, nope_flash_roofline,
              reglu_experts_roofline, st_moe_route_ms,
              st_moe_dispatch_combine_ms, st_step_prep_ms,
              st_device_idle_pct):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            entry = next(e for e in json.load(f)["per_layer"]
                         if e["name"] == m.META["name"])
        assert entry["workloads"] == ["smallthinker_ep8_s16384_b2"]
        assert {k: entry[k] for k in m.META} == m.META


def test_rehearsal_of_the_smallthinker_cell():
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "smallthinker_ep8_s16384_b2", "--rehearse", "--seed", "3000000019",
         "--trace", "1"], cwd=ROOT, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout
