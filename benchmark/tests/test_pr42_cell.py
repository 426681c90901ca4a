"""PR 42's benchmark files on the CPU: the FLOP count of the drawn
configuration and the shares its cell's `why` states, the costs of its
kernels by the algorithm, the eleven readers on a made-up trace, the
catalog's keys, the deployment's arithmetic against `builder_args` and the
rehearsal of the new cell and of its two controls. Asserts go by name and
by "at least", never by the last entry or a count. Run by hand:
`python -m pytest benchmark/tests -q`."""

import importlib
import json
import math
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron_tt_ep16_s8192_b2"
CONFIG = "nemotron_twotower_30b_a3b"
SCOPE_MS = {"nemotron_ssm_proj_ms": ("ssm_proj_in", "ssm_proj_out"),
            "nemotron_ssm_conv_ms": ("ssm_conv",),
            "nemotron_ssm_gate_norm_ms": ("ssm_gate_norm",),
            "nemotron_moe_shared_ms": ("moe_shared",),
            "nemotron_moe_route_ms": ("moe_route",),
            "nemotron_moe_dispatch_combine_ms": ("moe_dispatch",
                                                 "moe_combine")}
SCOPE_ROOFLINES = {"nemotron_ssd_roofline": "ssm_scan",
                   "nemotron_experts_roofline": "moe_experts"}
READERS = (*SCOPE_MS, *SCOPE_ROOFLINES, "nemotron_flash_g16_roofline",
           "nemotron_step_prep_ms", "nemotron_device_idle_pct")
# the accepted readers of `step_parts`' ledger, whose lists gain the cell;
# not `lm_scan_carry_ms`: no two neighbours of this net are alike, nothing
# scans, and the reader finds no `scan_carry` part to read
PART_READERS = ("step_unscoped_ms", "step_recompute_ms", "lm_proj_ms",
                "lm_head_loss_ms", "lm_glue_ms")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def config():
    with open(os.path.join(HERE, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_train_flops_and_the_parameter_count_of_the_cut_configuration():
    import nemotron_h_flops as fl
    from reference.nemotron_h import dims, layer_specs
    c = config()
    d = dims(c)
    assert (d["pattern"], d["n_routed_experts"], d["router_outputs"],
            d["vocab_size"], d["seq_len"], len(d["whole_pattern"])) == \
        ("MEMEM*E", 8, 128, 16384, 8192, 52)
    params = sum(math.prod(shape) for _, blobs in layer_specs(d)
                 for shape, *_ in blobs)
    m = 27_697_152 + 24_576 + 6_144 + 192 + 4_096 + 11_010_048 + 2_688
    a = 11_010_048 + 2 * 688_128 + 11_010_048 + 2_688
    e = 344_064 + 128 + 19_955_712 + 8 * 9_977_856 + 2_688
    assert (m, a, e) == (38_744_896, 23_399_040, 100_125_440)
    assert params == 3 * m + a + 3 * e + 2 * 16384 * 2688 + 2688 \
        == 528_093_120 == fl.parameters(c)
    assert "528,093,120 parameters" in c["deployment"]["bytes"]
    # 14.4 TFLOP a sequence of 8,192, 3 x 587 MFLOP a token
    assert abs(fl.train_flops(c) - 14.42e12) < 0.01e12
    assert abs(fl.train_flops(c) / 8192 - 3 * 586.8e6) < 0.2e6


def test_the_flop_shares_are_the_ones_the_cell_states():
    import nemotron_h_flops as fl
    from reference.nemotron_h import dims
    parts = fl.forward_macs(dims(config()))
    share = {k: 100 * v / sum(parts.values()) for k, v in parts.items()}
    why = next(w for w in bench()["workloads"] if w["name"] == CELL)["why"]
    stated = dict(re.findall(r"(Mamba-2|scan|shared|head|attention|experts) "
                             r"(\d+(?:\.\d)?)%", why))
    assert stated == {"Mamba-2": "41", "scan": "1.4", "shared": "20.4",
                      "head": "15", "attention": "19.4", "experts": "3.8"}
    assert abs(share["ssm_proj"] + share["ssm_scan"] - 41.0) < 0.1
    assert abs(share["ssm_scan"] - 1.4) < 0.05
    assert abs(share["shared"] - 20.4) < 0.05
    assert abs(share["head"] - 15.0) < 0.05
    assert abs(share["attn_proj"] + share["attn_core"] - 19.4) < 0.05
    assert abs(share["routed"] - 3.8) < 0.05 and share["router"] < 0.5
    # an even routing: 768 pairs a held expert, a sixteenth of 12,288
    assert 16384 * 6 * 8 / 128 / 8 == 768 == 12288 / 16


def test_kernel_costs_are_by_the_algorithm():
    import nemotron_h_flops as fl
    c = config()
    tri = 128 * 129 // 2
    ops, bytes_ = fl.ssd_cost(c, 2)
    # a chunk: the causal half of C B^T a group and of its product a head,
    # the chunk's own state and the carried state's readout
    chunk = tri * (8 * 128 + 64 * 64) + 2 * 128 * 64 * 64 * 128
    assert ops == 3 * 2 * 2 * 3 * 64 * chunk and bytes_ > 0
    # a full square where the lower triangle would do computes 1.24 x that
    assert 1.2 < (128 * 128 * (8 * 128 + 64 * 64)
                  + 2 * 128 * 64 * 64 * 128) / chunk < 1.3
    ops, bytes_ = fl.flash_cost(c, 2)
    assert ops == 2 * 32 * 7 * 2 * (8192 * 8193 // 2) * 128 and bytes_ > 0
    ops, bytes_ = fl.experts_cost(c, 2)
    # TWO products a pair at the published width, not three, not 1,920
    assert ops == 3 * 2 * 2 * 3 * 8192 * 0.375 * 2 * 2688 * 1856
    assert bytes_ > 3 * 8 * 2 * 2688 * 1856 * 8


def test_catalog_keys_are_kept():
    c = config()
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size", "hybrid_override_pattern"]
    assert c["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern": PATTERN}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"],
            c["hybrid_override_pattern"]) == (7, 8, 16384, "MEMEM*E")
    # the published widths
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["ssm_state_size"], c["n_groups"], c["conv_kernel"],
            c["chunk_size"]) == (2688, 64, 64, 128, 8, 4, 128)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (32, 2, 128)
    assert (c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"]) == \
        (1856, 3712, 6, 2.5)
    # the floors: a whole repeated unit, 8 experts, an eighth of the rows
    assert PATTERN.startswith(c["hybrid_override_pattern"] * 5)
    assert len(c["hybrid_override_pattern"]) == c["num_hidden_layers"] >= 5
    assert c["n_routed_experts"] >= 8
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["n_routed_experts"] * c["deployment"]["chips_sharing_a_layer"] \
        == c["published"]["n_routed_experts"]
    assert c["builder_args"] == {"seq_len": 8192}
    entry = next(e for e in bench()["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    for word in ("4,096", "NO positional", "e_score_correction_bias",
                 "1e-20", "two matrices", "AFTER the gate", "sqrt(52)",
                 "gaussian(1.0)", "A_log", "dt_bias", "Adam", "8,192"):
        assert any(word in a for a in c["assumed"]), word
    assert {"second_tower", "adaLN", "bidirectional_in_block_attention",
            "cross_tower_conditioning", "block_diffusion_objective",
            "bias_update", "router_auxiliary_loss", "dropout",
            "packing"} <= set(c["left_out"])
    # the builder's docstring names what the file assumes and leaves out
    sys.path.insert(0, ROOT)
    from sparknet_tpu.models import zoo
    doc = " ".join(zoo.nemotron_h.__doc__.split())
    for word in ("4,096", "no positional encoding", "1e-20", "AFTER the gate",
                 "A_log", "dt_bias", "rescale_prenorm_residual",
                 "gaussian(1.0)", "denoiser", "adaLN", "bidirectional",
                 "cross-tower", "block-diffusion", "noise schedule",
                 "load-balancing", "dropout", "packing", "reset"):
        assert word in doc, word
    if not os.path.exists(CATALOG):
        return
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"]
                   == "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in c, key
        if key not in c["reduced"]:
            assert c[key] == value, key


def test_the_builder_gets_the_deployments_sizes():
    """`builder_args` over the file's keys give the net the deployment's
    share: 8 held of 128 routed, 16,384 rows, the first seven blocks."""
    import nemotron_h_net
    net = nemotron_h_net.net(2)
    by_name = {lp.name: lp for lp in net.layer}
    assert [lp.type for lp in net.layer if lp.name.endswith("/mixer")] == [
        "Mamba2", "MoE", "Mamba2", "MoE", "Mamba2", "Attention", "MoE"]
    mp = by_name["block6/mixer"].moe_param
    assert (mp.num_experts, mp.experts_held, mp.first_expert, mp.top_k,
            mp.hidden_dim, mp.shared_hidden_dim) == (128, 8, 0, 6, 1856, 3712)
    sp = by_name["block0/mixer"].mamba2_param
    assert (sp.num_heads, sp.head_dim, sp.state_size, sp.n_groups,
            sp.conv_kernel, sp.chunk) == (64, 64, 128, 8, 4, 128)
    # the residual branches' last matrices start small by the WHOLE depth
    assert abs(sp.out_filler.std - 0.02 / math.sqrt(52)) < 1e-9
    ap = by_name["block5/mixer"].attention_param
    assert (ap.num_heads, ap.num_kv_heads, ap.head_dim, ap.rotary_dim) == \
        (32, 2, 128, 0)
    assert by_name["lm_head"].inner_product_param.num_output == 16384
    assert by_name["data"].type and tuple(
        by_name["tok_embed"].embed_param.input_dim for _ in "x") == (16384,)


def ctx(op_seconds, units=2):
    return {"op_seconds": op_seconds, "batch": 2, "sync_every": 1,
            "trace": {"units": units}, "xplane": None,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_made_up_trace():
    import nemotron_h_flops as fl
    mods = {n: importlib.import_module(f"layer_metrics.{n}")
            for n in READERS}
    c = config()
    # kernels that ran at twice their roofline's time read 50, by name
    ops, bytes_ = fl.flash_cost(c, 2)
    t = max(ops / 197e12, bytes_ / 819e9)
    seen = ctx({"flash_fwd.3": 2 * t * 0.5, "flash_dq": 2 * t * 0.5,
                "flash_dkv.1": 2 * t * 1.0, "flash_swa_fwd": 5.0,
                "fusion.7": 9.0})
    assert abs(mods["nemotron_flash_g16_roofline"].read(seen) - 50.0) < 1e-9
    # a trace without the kernels, or without a step: nothing, never 0
    assert mods["nemotron_flash_g16_roofline"].read(
        ctx({"flash_swa_fwd": 9.0})) is None
    assert mods["nemotron_flash_g16_roofline"].read(
        ctx({"flash_fwd": 1.0}, units=0)) is None
    # by scope: no trace file (and on the parent no such scope), nothing
    for name in (*SCOPE_MS, *SCOPE_ROOFLINES):
        assert mods[name].read(seen) is None
    # the share of a scope's seconds, handed over directly
    for cost in (fl.ssd_cost, fl.experts_cost):
        ops, bytes_ = cost(c, 2)
        least = max(ops / 197e12, bytes_ / 819e9)
        assert abs(fl.roofline_pct(seen, cost, 2 * least * 4) - 25.0) < 1e-9
        assert fl.roofline_pct(seen, cost, 0.0) is None
    # the host's and the device's share
    assert mods["nemotron_step_prep_ms"].read(
        dict(seen, dispatch_s=[])) is None
    assert mods["nemotron_device_idle_pct"].read(
        dict(seen, trace=None)) is None
    assert mods["nemotron_device_idle_pct"].read(
        dict(seen, trace={"units": 2, "idle_pct": 0.25})) == 0.25
    per_layer = bench()["per_layer"]
    for name, m in mods.items():
        entry = next(e for e in per_layer if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert {k: entry[k] for k in m.META} == m.META


def test_scope_readers_on_a_fixture_trace(monkeypatch):
    """With seconds under their scopes the eight scope readers give a
    number; with none under them, nothing."""
    import scope_seconds
    seen = ctx({}, units=2)
    seen["xplane"] = "made-up"
    under = {"ssm_proj_in": 0.06, "ssm_proj_out": 0.02, "ssm_conv": 0.03,
             "ssm_scan": 0.2, "ssm_gate_norm": 0.01, "moe_shared": 0.05,
             "moe_route": 0.1, "moe_dispatch": 0.05, "moe_combine": 0.05,
             "moe_experts": 0.3}
    monkeypatch.setattr(scope_seconds, "seconds",
                        lambda c, scopes: {s: under[s] for s in scopes})
    for name, scopes in SCOPE_MS.items():
        got = importlib.import_module(f"layer_metrics.{name}").read(seen)
        assert abs(got - 500.0 * sum(under[s] for s in scopes)) < 1e-9, name
    for name in SCOPE_ROOFLINES:
        got = importlib.import_module(f"layer_metrics.{name}").read(seen)
        assert 0.0 < got < 100.0, name
    monkeypatch.setattr(scope_seconds, "seconds",
                        lambda c, scopes: {s: 0.0 for s in scopes})
    for name in (*SCOPE_MS, *SCOPE_ROOFLINES):
        assert importlib.import_module(
            f"layer_metrics.{name}").read(seen) is None, name


def test_the_benchmark_holds_the_configuration_and_its_one_cell():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert len(b["configs"]) >= 7 and len(b["workloads"]) >= 9
    cells = [w for w in b["workloads"] if w["config"] == entry["name"]]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "resident_tokens_s8192_b2", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    with open(os.path.join(HERE, "traffic",
                           "resident_tokens_s8192_b2.json")) as f:
        traffic = json.load(f)
    assert (traffic["feed"], traffic["batch"], traffic["sync_every"]) == \
        ("resident_tokens", 2, 1)
    names = [m["name"] for m in b["per_layer"]]
    assert set(READERS) <= set(names)
    for m in b["per_layer"]:
        if m["name"] in PART_READERS:
            assert CELL in m["workloads"] and m["moves"] == "train_rate"
            assert m["workloads"].index(CELL) >= 4
        if m["name"] == "lm_scan_carry_ms":
            assert CELL not in m["workloads"]
    # a quarter of the cells, rounded down, may take four chips
    assert sum(1 for w in b["workloads"] if w["chips"] == 4) \
        <= max(1, len(b["workloads"]) // 4)
    # a check that makes 2 + 14 runs a cell fits the driver's day
    runs = (2 + 14 * len(b["workloads"])) * (b["run_seconds"] + 60) \
        + 2 * 90 * len(b["workloads"]) + 1200
    assert runs <= 43200


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_nemotron_cell(trace):
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3000000019", "--trace", trace],
        cwd=ROOT, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout


@pytest.mark.parametrize("script,key", [("control_reference.py", None),
                                        ("control_carry.py", "carry")])
def test_rehearsal_of_the_controls(script, key):
    """Both controls drive their control flow at the toy sizes; what they
    read there is no finding (a toy's scan is small beside its D skip: the
    carry-dropped control passes the toy's wide limits, and says so by its
    exit code)."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, script), "--workload", CELL,
         "--rehearse", "--seeds", "3000000019"],
        cwd=ROOT, capture_output=True, text=True)
    rows = [json.loads(line[2:]) for line in r.stdout.splitlines()
            if line.startswith("# {")]
    assert len(rows) == 1, r.stderr[-2000:]
    assert r.returncode == (1 if rows[0]["control_correct"] else 0)
    assert set(rows[0]["control"]) >= {"grad_worst_leaf_rel_diff",
                                       "dparam_worst_leaf_rel_diff"}
    if key:
        assert rows[0][key] is False
