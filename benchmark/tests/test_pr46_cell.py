"""PR 46's benchmark files on the CPU: the cell's files found by name, the
parameter count of the built net against `deployment.bytes`, the FLOP count
of the drawn configuration and the shares the cell's `why` states, the
costs of its kernels by the algorithm, the eleven readers on a made-up
trace, the catalog's keys, and the rehearsal of the new cell and of its two
controls. Asserts go by name and by "at least", never by the last entry or
a count. Run by hand: `python -m pytest benchmark/tests -q`."""

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
CELL = "glm47flash_ep8_s8192_b1"
CONFIG = "glm_4_7_flash"
TRAFFIC = "resident_tokens_s8192_b1"
PARAMETERS = 591_294_976
SCOPE_MS = {"glm_mla_latent_ms": ("mla_q_latent", "mla_kv_latent"),
            "glm_mla_k_assemble_ms": ("mla_k_assemble",),
            "glm_rope_ms": ("rope",),
            "glm_moe_shared_ms": ("moe_shared",),
            "glm_moe_route_ms": ("moe_route",),
            "glm_moe_dispatch_combine_ms": ("moe_dispatch", "moe_combine")}
SCOPE_ROOFLINES = ("glm_mla_latent_roofline", "glm_experts_roofline")
READERS = (*SCOPE_MS, *SCOPE_ROOFLINES, "glm_flash_mla_roofline",
           "glm_step_prep_ms", "glm_device_idle_pct")
# the accepted readers of `step_parts`' ledger, whose lists gain the cell;
# layers 1-4 scan, so `lm_scan_carry_ms` is among them
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
                                             1, 1)
    assert cell.data_shape == (1, 8192)
    assert cell.config["builder"] == "glm4_moe_lite_net:net"
    assert callable(harness.by_path(cell.config["builder"]))
    assert cell.config["reference"] == "glm4_moe_lite"
    assert harness.by_path(cell.config["flops"])(cell.sized_config) == \
        cell.flops_per_sample()
    assert cell.file["solver"] == "sparknet_tpu.solver.solver:Solver"
    assert cell.config["solver_args"] == {"compute_dtype": "bfloat16",
                                          "remat": "full"}
    assert set(cell.limits) == {"loss_rel_gap", "grad_worst_leaf_rel_diff",
                                "dparam_worst_leaf_rel_diff"}
    for reader in READERS:
        assert os.path.exists(os.path.join(HERE, "layer_metrics",
                                           f"{reader}.py")), reader


def test_the_built_nets_parameter_count_is_the_deployments():
    import glm4_moe_lite_flops as fl
    import glm4_moe_lite_net
    from sparknet_tpu.graph.compiler import CompiledNet
    c = config()
    net = CompiledNet(glm4_moe_lite_net.net(1))
    count = sum(math.prod(shape) for shape, *_ in net.param_meta.values())
    assert count == PARAMETERS == fl.parameters(c)
    said = c["deployment"]["bytes"]
    assert said.startswith(f"{PARAMETERS:,} parameters, counted from the "
                           "built net")
    # the parts the text adds up are the net's own
    shapes = {}
    for (layer, _), (shape, *_) in net.param_meta.items():
        for key in (layer.split("/")[0],
                    "attn" if layer == "block1/attn" else None):
            shapes[key] = shapes.get(key, 0) + math.prod(shape)
    for part in ("attn", "block0", "block1"):
        assert f"= {shapes[part]:,}" in said, part
    assert shapes["attn"] == 21_759_232
    # with the module: what `left_out` says of it
    with_module = CompiledNet(glm4_moe_lite_net.net(
        1, num_nextn_predict_layers=1))
    more = sum(math.prod(shape)
               for shape, *_ in with_module.param_meta.values()) - count
    assert more == 115_223_872
    assert "115,223,872 parameters" in c["left_out"]["multi_token_prediction"]
    assert "706,518,848" in c["left_out"]["multi_token_prediction"]


def test_train_flops_add_up_to_the_shares_the_cell_states():
    import glm4_moe_lite_flops as fl
    c = config()
    shares = fl.shares(c)
    assert abs(sum(shares.values()) - 1.0) < 1e-12
    why = next(w for w in bench()["workloads"] if w["name"] == CELL)["why"]
    said = dict(re.findall(r"([A-Za-z+ 0-9]+?) (\d+\.\d)%", why))
    got = {"flash core at 20 heads of 256": shares["attn_core"],
           "latent+out products": shares["attn_latent"] + shares["attn_out"],
           "dense FF": shares["dense_ff"], "shared": shares["shared"],
           "head": shares["head"], "8 of 64 experts": shares["routed"]}
    assert {k.strip(): float(v) for k, v in said.items()} == \
        {k: round(100 * v, 1) for k, v in got.items()}
    # 956M operations a token forward, 23.5 TFLOP a step trained
    d = fl.dims(c)
    per_token = 2 * sum(fl.forward_macs(d).values()) / d["seq_len"]
    assert 0.95e9 < per_token < 0.96e9
    assert abs(fl.train_flops(c) - 3 * per_token * 8192) < 1.0
    # the module is one more MoE block, the head once more, and W_eh
    on = dict(c, builder_args=dict(c["builder_args"],
                                   num_nextn_predict_layers=1))
    more = fl.forward_macs(fl.dims(on))
    base = fl.forward_macs(d)
    assert more["head"] == 2 * base["head"]
    assert more["mtp_proj"] == 8192 * 2 * 2048 * 2048
    assert more["routed"] * 4 == base["routed"] * 5
    assert more["attn_core"] * 5 == base["attn_core"] * 6


def test_kernel_costs_are_by_the_algorithm():
    import glm4_moe_lite_flops as fl
    c = config()
    pairs = 8192 * 8193 // 2
    ops, bytes_ = fl.flash_cost(c, 1)
    assert ops == 5 * 20 * 7 * 2 * pairs * 256
    q = 8192 * 20 * 256 * 2
    assert bytes_ == 5 * ((4 * q + 8192 * 20 * 4) + (8 * q + 8192 * 20 * 4))
    ops, bytes_ = fl.latent_cost(c, 1)
    four = 768 * 2048 + 5120 * 768 + 576 * 2048 + 8960 * 512
    assert four == 11_272_192
    assert ops == 3 * 2 * 5 * 8192 * four
    assert ops / 197e12 > bytes_ / 819e9        # bound by the MXU
    ops, bytes_ = fl.experts_cost(c, 1)
    assert ops == 3 * 2 * 4 * 8 * 512 * 3 * 2048 * 1536
    assert bytes_ == 4 * 8 * 3 * 2048 * 1536 * 8 + 4 * 4096 * 2048 * 8


def test_catalog_keys_are_kept():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    c, entry = config(), next(e for e in bench()["configs"]
                              if e["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == c["source"]
    differ = {k for k, v in row["config"].items() if c.get(k, "none") != v}
    assert differ == set(entry["reduced"]) == set(c["reduced"])
    assert {k: row["config"][k] for k in differ} == c["published"]
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
              "num_attention_heads")
    assert not set(widths) & differ
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def ctx(op_seconds, units=2):
    return {"op_seconds": op_seconds, "batch": 1, "sync_every": 1,
            "trace": {"units": units}, "xplane": None,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_readers_on_a_made_up_trace():
    import glm4_moe_lite_flops as fl
    mods = {n: importlib.import_module(f"layer_metrics.{n}")
            for n in READERS}
    c = config()
    # kernels that ran at twice their roofline's time read 50, by name
    ops, bytes_ = fl.flash_cost(c, 1)
    t = max(ops / 197e12, bytes_ / 819e9)
    seen = ctx({"flash_fwd.3": 2 * t * 0.5, "flash_dq": 2 * t * 0.5,
                "flash_dkv.1": 2 * t * 1.0, "flash_swa_fwd": 5.0,
                "fusion.7": 9.0})
    assert abs(mods["glm_flash_mla_roofline"].read(seen) - 50.0) < 1e-9
    # a trace without the kernels, or without a step: nothing, never 0
    assert mods["glm_flash_mla_roofline"].read(
        ctx({"flash_swa_fwd": 9.0})) is None
    assert mods["glm_flash_mla_roofline"].read(
        ctx({"flash_fwd": 1.0}, units=0)) is None
    # by scope: no trace file (and on the parent no such scope), nothing
    for name in (*SCOPE_MS, *SCOPE_ROOFLINES):
        assert mods[name].read(seen) is None
    assert mods["glm_step_prep_ms"].read(dict(seen, dispatch_s=[])) is None
    assert mods["glm_device_idle_pct"].read(dict(seen, trace=None)) is None
    assert mods["glm_device_idle_pct"].read(
        dict(seen, trace={"units": 2, "idle_pct": 0.25})) == 0.25
    per_layer = bench()["per_layer"]
    for name, m in mods.items():
        entry = next(e for e in per_layer if e["name"] == name)
        assert entry["workloads"] == [CELL]
        assert {k: entry[k] for k in m.META} == m.META


def test_scope_readers_on_a_fixture_trace(monkeypatch):
    """With seconds under their scopes the eight scope readers give a
    number, the two shares of a roofline between 0 and 100; with none
    under them, nothing."""
    import glm4_moe_lite_flops as fl
    import scope_seconds
    seen = ctx({}, units=2)
    seen["xplane"] = "made-up"
    under = {"mla_q_latent": 0.03, "mla_kv_latent": 0.05,
             "mla_k_assemble": 0.01, "rope": 0.02, "moe_shared": 0.05,
             "moe_route": 0.02, "moe_dispatch": 0.01, "moe_combine": 0.03,
             "moe_experts": 0.04}
    monkeypatch.setattr(scope_seconds, "seconds",
                        lambda c, scopes: {s: under[s] for s in scopes})
    for name, scopes in SCOPE_MS.items():
        got = importlib.import_module(f"layer_metrics.{name}").read(seen)
        assert abs(got - 500.0 * sum(under[s] for s in scopes)) < 1e-9, name
    c = config()
    for name, scopes, cost in (
            ("glm_mla_latent_roofline", ("mla_q_latent", "mla_kv_latent"),
             fl.latent_cost), ("glm_experts_roofline", ("moe_experts",),
                               fl.experts_cost)):
        got = importlib.import_module(f"layer_metrics.{name}").read(seen)
        ops, bytes_ = cost(c, 1)
        least = max(ops / 197e12, bytes_ / 819e9)
        want = 100.0 * least / (sum(under[s] for s in scopes) / 2)
        assert abs(got - want) < 1e-9 and 0.0 < got < 100.0, name
    monkeypatch.setattr(scope_seconds, "seconds",
                        lambda c, scopes: {s: 0.0 for s in scopes})
    for name in (*SCOPE_MS, *SCOPE_ROOFLINES):
        assert importlib.import_module(
            f"layer_metrics.{name}").read(seen) is None, name


def test_the_benchmark_holds_the_configuration_and_its_one_cell():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert len(b["configs"]) >= 8 and len(b["workloads"]) >= 10
    cells = [w for w in b["workloads"] if w["config"] == entry["name"]]
    assert len(cells) == 1
    cell = cells[0]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    with open(os.path.join(HERE, "traffic", f"{TRAFFIC}.json")) as f:
        traffic = json.load(f)
    assert (traffic["feed"], traffic["batch"], traffic["sync_every"]) == \
        ("resident_tokens", 1, 1)
    names = [m["name"] for m in b["per_layer"]]
    assert set(READERS) <= set(names)
    for m in b["per_layer"]:
        if m["name"] in PART_READERS:
            assert CELL in m["workloads"] and m["moves"] == "train_rate"
            assert m["workloads"].index(CELL) >= 4
    # a quarter of the cells, rounded down, may take four chips
    assert sum(1 for w in b["workloads"] if w["chips"] == 4) \
        <= max(1, len(b["workloads"]) // 4)
    # a check that makes 2 + 14 runs a cell fits the driver's day
    runs = (2 + 14 * len(b["workloads"])) * (b["run_seconds"] + 60) \
        + 2 * 90 * len(b["workloads"]) + 1200
    assert runs <= 43200


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_glm_cell(trace):
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse", "--seed", "3000000019", "--trace", trace],
        cwd=ROOT, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "correct=True" in r.stdout


@pytest.mark.parametrize("script,forms", [
    ("control_reference.py", [None]),
    ("control_latent.py", ["shared_rope_key", "kv_latent_norm"])])
def test_rehearsal_of_the_controls(script, forms):
    """Both controls drive their control flow at the toy sizes; what they
    read there is no finding (at the toy's widths and 0.02 fillers the
    scores hardly tell the keys apart: a control may pass the toy's wide
    limits, and says so by its exit code)."""
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
