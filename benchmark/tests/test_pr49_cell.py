"""PR 49's benchmark files on the CPU: the cell's files found by name, the
parameter count of the built net against `deployment.bytes`, the FLOP count
of the drawn configuration and the shares the cell's `why` states, the
costs of its kernels by the algorithm (the exact band, never the tiles),
the ten readers on a made-up trace, the catalog's keys, and the rehearsal
of the new cell and of its controls. Asserts go by name and by "at least",
never by the last entry or a count. Run by hand: `python -m pytest
benchmark/tests -q`."""

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
CELL = "laguna_ep16_s8192_b2"
CONFIG = "laguna_xs_2"
TRAFFIC = "resident_tokens_s8192_b2"
PARAMETERS = 490_297_344
SCOPE_MS = {"laguna_attn_gate_ms": ("attn_gate",),
            "laguna_rope_ms": ("rope",),
            "laguna_moe_shared_ms": ("moe_shared",),
            "laguna_moe_route_ms": ("moe_route",),
            "laguna_moe_dispatch_combine_ms": ("moe_dispatch",
                                               "moe_combine")}
SCOPE_ROOFLINES = ("laguna_experts_roofline",)
KERNEL_ROOFLINES = {
    "laguna_swa512_flash_roofline": ("flash_swa_fwd", "flash_swa_dq",
                                     "flash_swa_dkv"),
    "laguna_full_flash_roofline": ("flash_fwd", "flash_dq", "flash_dkv")}
READERS = (*SCOPE_MS, *SCOPE_ROOFLINES, *KERNEL_ROOFLINES,
           "laguna_step_prep_ms", "laguna_device_idle_pct")
# the accepted readers of `step_parts`' ledger, whose lists gain the cell;
# layers 1-3 scan, so `lm_scan_carry_ms` is among them
PART_READERS = ("step_unscoped_ms", "step_recompute_ms", "lm_proj_ms",
                "lm_head_loss_ms", "lm_glue_ms", "lm_scan_carry_ms")


def config():
    with open(os.path.join(HERE, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cells_files_are_found_by_name():
    import harness
    cell = harness.Cell(CELL)
    assert (cell.config["name"], cell.traffic["feed"], cell.chips,
            cell.batch, cell.sync_every) == (CONFIG, "resident_tokens", 1,
                                             2, 1)
    assert cell.data_shape == (2, 8192)
    assert cell.config["builder"] == "laguna_net:net"
    assert callable(harness.by_path(cell.config["builder"]))
    assert cell.config["reference"] == "laguna"
    assert harness.by_path(cell.config["flops"])(cell.sized_config) == \
        cell.flops_per_sample()
    assert cell.file["solver"] == "sparknet_tpu.solver.solver:Solver"
    assert cell.config["solver_args"] == {"compute_dtype": "bfloat16",
                                          "remat": "full"}
    assert set(cell.limits) == {"loss_rel_gap", "grad_worst_leaf_rel_diff",
                                "dparam_worst_leaf_rel_diff"}
    assert len(READERS) == 10
    for reader in READERS:
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           f"{reader}.py")), reader


def test_the_built_nets_parameter_count_is_the_deployments():
    import laguna_flops as fl
    import laguna_net
    from sparknet_tpu.graph.compiler import CompiledNet
    c = config()
    net = CompiledNet(laguna_net.net(2))
    count = sum(math.prod(shape) for shape, *_ in net.param_meta.values())
    assert count == PARAMETERS == fl.parameters(c)
    said = c["deployment"]["bytes"]
    assert said.startswith(f"{PARAMETERS:,} parameters, counted from the "
                           "built net")
    # the parts the text adds up are the net's own
    shapes = {}
    for (layer, _), (shape, *_) in net.param_meta.items():
        for key in (layer.split("/")[0],
                    "full" if layer == "block0/attn" else
                    "window" if layer == "block1/attn" else
                    "moe" if layer == "block1/moe" else None):
            shapes[key] = shapes.get(key, 0) + math.prod(shape)
    assert (shapes["full"], shapes["window"], shapes["moe"]) == \
        (29_458_432, 37_879_808, 54_001_664)
    for part in ("full", "window", "moe", "block0", "block1", "block4"):
        assert f" {shapes[part]:,}" in said, part
    assert shapes["block1"] == shapes["block2"] == shapes["block3"]
    # 20 B a parameter in the check, 16 in the window: a half of the chip
    assert "= 9.81 GB, 7.84 GB in the window" in said
    assert round(PARAMETERS * 20 / 1e9, 2) == 9.81
    assert round(PARAMETERS * 16 / 1e9, 2) == 7.84
    assert c["deployment"]["chips_sharing_a_layer"] == 16
    assert "2 full layers to 3 window ones" in c["deployment"]["depth"]


def test_train_flops_add_up_to_the_shares_the_cell_states():
    import laguna_flops as fl
    c = config()
    shares = fl.shares(c)
    assert abs(sum(shares.values()) - 1.0) < 1e-12
    why = next(w for w in bench()["workloads"] if w["name"] == CELL)["why"]
    said = dict(re.findall(r"([A-Za-z+\- 0-9]+?) (\d+\.\d)%", why))
    got = {"attn proj+gate": shares["attn_proj"] + shares["attn_gate"],
           "full core": shares["attn_full"],
           "window-512 core": shares["attn_window"],
           "dense FF": shares["dense_ff"], "head": shares["head"],
           "MoE": shares["router"] + shares["routed"] + shares["shared"]}
    assert {k.strip(): float(v) for k, v in said.items()} == \
        {k: round(100 * v, 1) for k, v in got.items()}
    assert "16 of 256 experts, 512 pairs each, 1/16 of a deployment's" in why
    # 789M operations a token forward, 38.8 TFLOP a step of two sequences
    d = fl.dims(c)
    per_token = 2 * sum(fl.forward_macs(d).values()) / d["seq_len"]
    assert 0.785e9 < per_token < 0.795e9
    assert abs(fl.train_flops(c) - 3 * per_token * 8192) < 1.0
    assert 38.7e12 < 2 * fl.train_flops(c) < 38.9e12
    # attention at its two head counts is three quarters of the step
    assert 0.74 < shares["attn_proj"] + shares["attn_gate"] \
        + shares["attn_full"] + shares["attn_window"] < 0.77
    # the head counts follow the layer's type: a window layer's projections
    # are 64 heads', a full layer's 48
    macs = fl.forward_macs(d)
    assert macs["attn_proj"] == 8192 * 2048 * (
        2 * (2 * 48 * 128 + 2 * 8 * 128) + 3 * (2 * 64 * 128 + 2 * 8 * 128))
    assert macs["attn_gate"] == 8192 * 2048 * (2 * 48 + 3 * 64)
    assert fl.heads_by_kind(d) == {"full_attention": [48, 48],
                                   "sliding_attention": [64, 64, 64]}


def test_kernel_costs_are_by_the_algorithm():
    """The window layers by the EXACT band of 512 keys a query, half of
    what the kernels' 31 masked tiles of 512 x 512 a head hold."""
    import laguna_flops as fl
    c = config()
    band = 512 * 513 // 2 + 7680 * 512
    assert fl.visible_pairs(8192, 512) == band == 4_063_488
    assert 31 * 512 * 512 == 8_126_464 and 1.99 < 8_126_464 / band < 2.01
    ops, bytes_ = fl.swa_flash_cost(c, 2)
    assert ops == 3 * 2 * 64 * 7 * 2 * band * 128
    qo, kv = 8192 * 64 * 128 * 2, 8192 * 8 * 128 * 2
    assert bytes_ == 3 * 2 * ((2 * qo + 2 * kv + 8192 * 64 * 4)
                              + (4 * qo + 4 * kv + 8192 * 64 * 4))
    pairs = 8192 * 8193 // 2
    ops, bytes_ = fl.full_flash_cost(c, 2)
    assert ops == 2 * 2 * 48 * 7 * 2 * pairs * 128
    qo = 8192 * 48 * 128 * 2
    assert bytes_ == 2 * 2 * ((2 * qo + 2 * kv + 8192 * 48 * 4)
                              + (4 * qo + 4 * kv + 8192 * 48 * 4))
    assert ops / 197e12 > bytes_ / 819e9        # bound by the MXU
    ops, bytes_ = fl.experts_cost(c, 2)
    assert ops == 3 * 2 * 4 * 16 * 512 * 3 * 2048 * 512
    assert bytes_ == 4 * 16 * 3 * 2048 * 512 * 8 + 4 * 8192 * 2048 * 8


def test_catalog_keys_are_kept():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    c, entry = config(), next(e for e in bench()["configs"]
                              if e["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == c["source"]
    differ = {k for k, v in row["config"].items() if c.get(k, "none") != v}
    assert differ == set(entry["reduced"]) == set(c["reduced"])
    assert {k: row["config"][k] for k in differ} == c["published"]
    # the per-layer lists are the published ones' first five entries
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert c[key] == row["config"][key][:5], key
    assert c["rope_parameters"] == row["config"]["rope_parameters"]
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "head_dim",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads", "sliding_window", "rope_parameters",
              "partial_rotary_factor")
    assert not set(widths) & differ
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert any("PER HEAD" in a and "33.44B" in a for a in c["assumed"])
    assert {"router_auxiliary_loss", "dropout", "packing", "serving"} <= \
        set(c["left_out"])


def ctx(op_seconds, units=2):
    return {"op_seconds": op_seconds, "batch": 2, "sync_every": 1,
            "trace": {"units": units}, "xplane": None,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_made_up_trace():
    import laguna_flops as fl
    mods = {n: importlib.import_module(f"layer_metrics.{n}")
            for n in READERS}
    c = config()
    # kernels that ran at twice their roofline's time read 50, by name, and
    # neither family of kernels reads the other's
    for name, cost in (("laguna_swa512_flash_roofline", fl.swa_flash_cost),
                       ("laguna_full_flash_roofline", fl.full_flash_cost)):
        fwd, dq, dkv = KERNEL_ROOFLINES[name]
        other = [k for n, ks in KERNEL_ROOFLINES.items() if n != name
                 for k in ks]
        ops, bytes_ = cost(c, 2)
        t = max(ops / 197e12, bytes_ / 819e9)
        seen = ctx({fwd + ".3": 2 * t * 0.5, dq: 2 * t * 0.5,
                    dkv + ".1": 2 * t * 1.0, other[0]: 5.0,
                    "fusion.7": 9.0})
        assert abs(mods[name].read(seen) - 50.0) < 1e-9
        # a trace without the kernels, or without a step: nothing, never 0
        assert mods[name].read(ctx({other[0]: 9.0})) is None
        assert mods[name].read(ctx({fwd: 1.0}, units=0)) is None
    # by scope: no trace file (and on the parent no such scope), nothing
    for name in (*SCOPE_MS, *SCOPE_ROOFLINES):
        assert mods[name].read(seen) is None
    assert mods["laguna_step_prep_ms"].read(dict(seen, dispatch_s=[])) is None
    assert mods["laguna_device_idle_pct"].read(dict(seen, trace=None)) is None
    assert mods["laguna_device_idle_pct"].read(
        dict(seen, trace={"units": 2, "idle_pct": 0.25})) == 0.25
    per_layer = bench()["per_layer"]
    for name, m in mods.items():
        entry = next(e for e in per_layer if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert {k: entry[k] for k in m.META} == m.META


def test_scope_readers_on_a_fixture_trace(monkeypatch):
    """With seconds under their scopes the six scope readers give a number,
    the share of a roofline between 0 and 100; with none under them,
    nothing."""
    import laguna_flops as fl
    import scope_seconds
    seen = ctx({}, units=2)
    seen["xplane"] = "made-up"
    under = {"attn_gate": 0.01, "rope": 0.12, "moe_shared": 0.05,
             "moe_route": 0.02, "moe_dispatch": 0.01, "moe_combine": 0.03,
             "moe_experts": 0.04}
    monkeypatch.setattr(scope_seconds, "seconds",
                        lambda c, scopes: {s: under[s] for s in scopes})
    for name, scopes in SCOPE_MS.items():
        got = importlib.import_module(f"layer_metrics.{name}").read(seen)
        assert abs(got - 500.0 * sum(under[s] for s in scopes)) < 1e-9, name
    got = importlib.import_module(
        "layer_metrics.laguna_experts_roofline").read(seen)
    ops, bytes_ = fl.experts_cost(config(), 2)
    least = max(ops / 197e12, bytes_ / 819e9)
    assert abs(got - 100.0 * least / (under["moe_experts"] / 2)) < 1e-9
    assert 0.0 < got < 100.0
    monkeypatch.setattr(scope_seconds, "seconds",
                        lambda c, scopes: {s: 0.0 for s in scopes})
    for name in (*SCOPE_MS, *SCOPE_ROOFLINES):
        assert importlib.import_module(
            f"layer_metrics.{name}").read(seen) is None, name


def test_the_benchmark_holds_the_configuration_and_its_one_cell():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert len(b["configs"]) >= 9 and len(b["workloads"]) >= 11
    cells = [w for w in b["workloads"] if w["config"] == entry["name"]]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer"}
    with open(os.path.join(HERE, "traffic", f"{TRAFFIC}.json")) as f:
        traffic = json.load(f)
    assert (traffic["feed"], traffic["batch"], traffic["sync_every"]) == \
        ("resident_tokens", 2, 1)
    names = [m["name"] for m in b["per_layer"]]
    assert set(READERS) <= set(names)
    for m in b["per_layer"]:
        if m["name"] in PART_READERS:
            assert CELL in m["workloads"] and m["moves"] == "train_rate"
            assert m["workloads"].index(CELL) >= 5
    # a quarter of the cells, rounded down, may take four chips
    assert sum(1 for w in b["workloads"] if w["chips"] == 4) \
        <= max(1, len(b["workloads"]) // 4)
    # a check that makes 2 + 14 runs a cell fits the driver's day
    runs = (2 + 14 * len(b["workloads"])) * (b["run_seconds"] + 60) \
        + 2 * 90 * len(b["workloads"]) + 1200
    assert runs <= 43200


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_laguna_cell(trace):
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3000000019", "--trace", trace],
        cwd=ROOT, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout


@pytest.mark.parametrize("script,forms", [
    ("control_reference.py", [None]),
    ("control_laguna.py", ["output_gate", "yarn_rope"])])
def test_rehearsal_of_the_controls(script, forms):
    """The controls drive their control flow at the toy sizes; what they
    read there is no finding (a control may pass the toy's wide limits, and
    says so by its exit code)."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, script), "--workload", CELL,
         "--rehearse", "--seeds", "3000000019"],
        cwd=ROOT, capture_output=True, text=True)
    rows = [json.loads(line[2:]) for line in r.stdout.splitlines()
            if line.startswith("# {")]
    assert len(rows) == len(forms), r.stderr[-2000:]
    assert r.returncode == (1 if any(row["control_correct"]
                                     for row in rows) else 0)
    for row, form in zip(rows, forms):
        assert set(row["control"]) >= {"grad_worst_leaf_rel_diff",
                                       "dparam_worst_leaf_rel_diff"}
        if form:
            assert row[form] is False


def test_the_latent_control_refuses_a_cell_without_its_forms():
    """`control_latent.py`'s forms are the latent attention's; this cell's
    are `control_laguna.py`'s, and the accepted script says so by name."""
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_latent.py"),
         "--workload", CELL, "--rehearse", "--seeds", "1"],
        cwd=ROOT, capture_output=True, text=True)
    assert r.returncode != 0 and "shared_rope_key" in r.stderr
