"""A step's device time as a closed ledger (PR 38): the program names every
operation it traces into a step by part (`net.parts`, the mixers' inner
scopes, the scan's own scope), and `benchmark/step_parts.py` (imported from
where it lies) turns a trace's events into parts x phases that add up to
the busy time. The partition is driven with hand-made events, the names
with the toy nets of the benchmark's cells on the CPU.
"""

import contextlib
import functools
import hashlib
import importlib
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from sparknet_tpu.graph.compiler import PART_OF_TYPE
from sparknet_tpu.models import zoo
from sparknet_tpu.obs.trace import Tracer
from sparknet_tpu.proto import Message
from sparknet_tpu.solver.solver import Solver

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import step_parts  # noqa: E402

MIXERS = {"attn", "gdn", "shortconv", "moe"}


def _cell_net(builder, cell):
    """The net of a benchmark cell at its rehearsal's toy sizes."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        toy = json.load(f)["toy"]
    return importlib.import_module(builder).net(2, **toy["builder_args"])


NETS = {
    "transformer_lm": lambda: zoo.transformer_lm(
        vocab_size=64, seq_len=32, batch_size=2, d_model=32, num_layers=3,
        num_heads=4, flash=False),
    "qwen3_next": lambda: _cell_net("qwen3_next_net",
                                    "qwen3next_ep32_s8192_b2"),
    "smallthinker": lambda: _cell_net("smallthinker_net",
                                      "smallthinker_ep8_s16384_b2"),
    "lfm2_moe": lambda: _cell_net("lfm2_moe_net", "lfm2moe_ep4_s8192_b3"),
    "caffenet": lambda: zoo.caffenet(batch_size=2, num_classes=10),
    "googlenet": lambda: zoo.googlenet(batch_size=2, num_classes=10),
}
LMS = ("transformer_lm", "qwen3_next", "smallthinker", "lfm2_moe")


def _solver(name, remat=None, scan=None):
    tracer = Tracer(None)
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0, random_seed=1)
    s = Solver(sp, net_param=NETS[name](), log_fn=None, tracer=tracer,
               remat=remat)
    if scan:
        s.set_scan(scan)
    batch = {k: np.zeros(v, np.int32 if k == "label" or len(v) == 2
                         else np.float32)
             for k, v in s.net.feed_shapes().items()}
    return s, tracer, batch


def _lowered(s, batch, debug_info=False):
    return s._memory_step_fn(batch).lower(
        *s._memory_step_args(batch)).as_text(debug_info=debug_info)


@functools.lru_cache(maxsize=None)
def _compiled(name, remat=None, scan=None):
    """(solver, tracer, {instruction: path} of the compiled step)."""
    s, tracer, batch = _solver(name, remat, scan)
    return s, tracer, s.op_scopes(batch)


# -- the program's names ------------------------------------------------------

@pytest.mark.parametrize("name", list(NETS))
def test_every_compiled_instruction_with_a_path_has_a_part(name):
    lm = name in LMS
    s, tracer, scopes = _compiled(name, "full" if lm else None,
                                  "on" if lm else None)
    rec = tracer.spans("net.parts")
    assert len(rec) == 1 and rec[0]["net"] == s.net.name
    layers = [lp.name for lp, impl, _, _ in s.net.layers
              if not getattr(impl, "is_feed", False)]
    assert sorted(rec[0]["parts"]) == sorted(layers)
    known = set(PART_OF_TYPE.values()) | {"head", "final_norm", "act"}
    assert set(rec[0]["parts"].values()) <= known
    table = step_parts.Parts(rec[0]["parts"])
    parts = {}
    for ins, path in scopes.items():
        # a parameter's path is its argument's name; a checkpoint's `call`
        # instruction is a container under no scope that owns no time
        if not path.startswith("jit(") or path.endswith("/remat2"):
            continue
        parts.setdefault(table.part_of(ins, path), set()).add(path)
    assert "unscoped" not in parts, sorted(parts["unscoped"])[:5]
    # a mixer traces nothing outside its inner scopes
    assert not MIXERS & set(parts), {m: sorted(parts[m])[:3]
                                     for m in MIXERS & set(parts)}
    assert "update" in parts and "loss" in parts
    if lm:
        assert {"head", "final_norm", "embed", "norm", "residual",
                "scan_carry"} <= set(parts)
    else:
        assert {"conv", "pool", "lrn", "head", "proj", "act"} <= set(parts)


def test_parts_tell_a_head_from_a_projection_and_the_final_norm():
    s, _, _ = _solver("lfm2_moe")
    parts = s.net.parts()
    assert parts["lm_head"] == "head" and parts["ln_f"] == "final_norm"
    assert parts["block0/ff_gate"] == parts["block0/ff_down"] == "proj"
    assert parts["block0/ff_act"] == parts["block0/ff_sig"] == "act"
    assert parts["block0/res1"] == "residual"
    assert parts["block0/ln1"] == "norm" and parts["tok_embed"] == "embed"
    assert parts["block0/mixer"] == "shortconv"
    assert parts["block1/moe"] == "moe" and parts["block1/mixer"] == "attn"
    cnn = _solver("googlenet")[0].net.parts()
    heads = [n for n, p in cnn.items() if p == "head"]
    assert len(heads) == 3 and "loss3/classifier" in heads


@pytest.mark.parametrize("scan", ["on", "off"])
@pytest.mark.parametrize("remat", ["none", "dots", "full"])
def test_mixer_and_scan_scopes_forward_and_backward(scan, remat):
    s, _, scopes = _compiled("qwen3_next", remat, scan)
    paths = {p for p in scopes.values() if p.startswith("jit(step)")}
    fwd = {p for p in paths if "transpose(" not in p}
    bwd = {p for p in paths if "transpose(" in p}
    for scope in ("gdn_proj_in", "gdn_proj_out", "attn_proj_in",
                  "attn_proj_out"):
        for side, name in ((fwd, "forward"), (bwd, "backward")):
            assert any(f"/{scope}/" in p for p in side), (scope, name)
    scanned = {p for p in paths if "layer_scan.block0" in p}
    if scan == "on":
        assert any("transpose(" in p for p in scanned)
        assert any("transpose(" not in p for p in scanned)
        # the scan's own operations: no layer on their path
        table = step_parts.Parts(s.net.parts())
        assert any(table.part_of("x", p) == "scan_carry" for p in scanned)
    else:
        assert not scanned
    replayed = {p for p in bwd if "rematted_computation" in p}
    if remat == "none":
        # the delta rule's own checkpointed groups aside
        assert all("/gdn_scan/" in p for p in replayed)
    else:
        assert any("/gdn_proj_in/" in p for p in replayed)
        assert any("/attn_proj_in/" in p for p in replayed)
        assert {step_parts.phase_of(p) for p in replayed} == {"recompute"}


def test_the_readers_list_of_inner_scopes_is_the_programs():
    import sparknet_tpu.ops as ops
    opened = set()
    for mod in ("attention", "deltanet", "shortconv", "moe", "mamba2"):
        with open(os.path.join(os.path.dirname(ops.__file__),
                               mod + ".py")) as f:
            opened |= set(re.findall(r'named_scope\("(\w+)"\)', f.read()))
    # an index-picked key set's scopes (PR 40: `dsa_index_proj` and the
    # opt-in `dsa_stats` here, `dsa_select` and `dsa_kl` in
    # ops/pallas_dsa.py) are not the benchmark's: an operation under one of
    # them counts under the layer's part, `attn`, which keeps the ledger
    # closed without an edit to `INNER`
    # nor are the state-space mixer's (PR 42): they count under `ssm`; nor
    # the latent attention's three (PR 46), which lie INSIDE `attn_proj_in`
    # and count under it (the deepest element of `INNER` on a path decides)
    # nor the per-head gate's (PR 49), opened inside `attn_proj_in` (its
    # product and sigmoid) and inside `attn_proj_out` (its multiply)
    assert "attn_gate" in opened
    assert {s for s in opened - {"attn_gate"}
            if not s.startswith(("dsa_", "ssm_", "mla_"))} == \
        set(step_parts.INNER)
    assert {s for s in opened if s.startswith("mla_")} == {
        "mla_q_latent", "mla_kv_latent", "mla_k_assemble"}
    assert {s for s in opened if s.startswith("dsa_")} == \
        {"dsa_index_proj", "dsa_stats"}
    assert {s for s in opened if s.startswith("ssm_")} == {
        "ssm_proj_in", "ssm_conv", "ssm_scan", "ssm_gate_norm",
        "ssm_proj_out"}


def test_iter_size_accumulates_under_its_own_scope():
    tracer = Tracer(None)
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0, random_seed=1, iter_size=2)
    s = Solver(sp, net_param=zoo.lenet(batch_size=2), log_fn=None,
               tracer=tracer)
    batch = {k: np.zeros((2, *v), np.int32 if k == "label" else np.float32)
             for k, v in s.net.feed_shapes().items()}
    table = step_parts.Parts(tracer.spans("net.parts")[-1]["parts"])
    parts = {table.part_of(ins, path)
             for ins, path in s.op_scopes(batch).items()
             if path.startswith("jit(")}
    assert "grad_accum" in parts and "unscoped" not in parts


def test_a_data_parallel_steps_exchange_has_its_own_scope():
    from sparknet_tpu.parallel import DataParallelSolver
    sp = Message("SolverParameter", base_lr=0.01, lr_policy="fixed",
                 display=0, random_seed=1, momentum=0.9)
    s = DataParallelSolver(sp, net_param=zoo.lenet(batch_size=8),
                           log_fn=None, tracer=Tracer(None))
    batch = {"data": np.zeros((8, 1, 28, 28), np.float32),
             "label": np.zeros((8,), np.int32)}
    table = step_parts.Parts(s.net.parts())
    text = s._memory_step_fn(batch).lower(
        *s._memory_step_args(batch)).compile().as_text()
    reduces = re.findall(r'^\s*(?:ROOT )?(%?[\w.\-]+ = .*all-reduce\(.*)$',
                         text, re.M)
    assert reduces
    for line in reduces:
        path = re.search(r'op_name="([^"]+)"', line).group(1)
        assert "/grad_exchange/" in path, path
        assert table.part_of(line.split(", metadata=")[0], path) \
            == "collective"
    # without a scope a collective is told by its opcode, tuple or not
    assert table.part_of("%all-reduce.13 = (f32[8]{0}, f32[]) all-reduce("
                         "%a, %b), channel_id=3", "jit(step)/psum") \
        == "collective"
    assert table.part_of("%fusion.2 = f32[8]{0} fusion(%all-reduce.13)",
                         "jit(step)/mul") == "unscoped"


def test_multi_head_attention_reads_like_the_grouped_query_form():
    s, _, batch = _solver("transformer_lm")
    text = _lowered(s, batch, debug_info=True)
    for scope in ("attn_proj_in", "attn_core", "attn_proj_out"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("name", ["qwen3_next", "caffenet"])
def test_the_scopes_leave_the_lowered_step_as_it_is(name, monkeypatch):
    lm = name in LMS

    def sha():
        s, _, batch = _solver(name, remat="full" if lm else None,
                              scan="on" if lm else None)
        return hashlib.sha256(_lowered(s, batch).encode()).hexdigest()

    with_scopes = sha()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    assert sha() == with_scopes


# -- the partition, on hand-made events ----------------------------------------

LAYERS = {"block0/ln1": "norm", "block0/mixer": "gdn", "block0/moe": "moe",
          "lm_head": "head", "ln_f": "final_norm", "loss": "loss",
          "conv1": "conv"}
SCAN = "jit(step)/jvp(layer_scan.block0)/while"
BWD = "jit(step)/transpose(jvp(layer_scan.block0))/while"


def _ms(out, part, phase):
    return out["parts"].get(part, {}).get(phase, 0)


def test_elements_take_transformations_off_and_leave_the_primitive_out():
    def el(path):
        return list(step_parts.elements(path))
    assert el("jit(step)/transpose(jvp(block3/mixer))/attn_proj_in/"
              "dot_general") == ["block3", "mixer", "attn_proj_in"]
    assert el("jit(step)/jvp(tok_embed)/jit(_take)") == ["tok_embed"]
    assert el("jit(step)/jvp(loss)/jit(log_softmax)/reduce_max:") == ["loss"]
    assert el("jit(step)/update/mul;jit(step)/update/add") == ["update"]
    assert el("jit(step)/add") == []


def test_part_rules_in_order():
    part = step_parts.Parts(LAYERS).part_of
    # the deepest inner scope beats the layer and the scan
    assert part("f", SCAN + "/body/closed_call/block0/moe/moe_glue/"
                "jit(_held_fwd)/while/body/moe_experts/moe_gmm_fwd/"
                "custom_call") == "moe_experts"
    # a scope INNER does not know counts under the one it lies inside: the
    # per-head gate's product under attn_proj_in, its multiply (here its
    # gradient) under attn_proj_out
    assert part("f", "jit(step)/jvp(block0/mixer)/attn_proj_in/attn_gate/"
                "dot_general") == "attn_proj_in"
    assert part("f", "jit(step)/transpose(jvp(block0/mixer))/attn_proj_out/"
                "attn_gate/mul") == "attn_proj_out"
    assert part("f", SCAN + "/body/closed_call/block0/ln1/mul") == "norm"
    assert part("f", SCAN + "/body/closed_call/block0/mixer/mul") == "gdn"
    assert part("%while.7 = (f32[8]{0}) while(%t), body=%b", SCAN) \
        == "scan_carry"
    assert part("while.7", SCAN + ":") == "scan_carry"
    assert part("d", BWD + "/body/dynamic_update_slice") == "scan_carry"
    assert part("f", "jit(step)/jvp(ln_f)/mul") == "final_norm"
    assert part("f", "jit(step)/jvp(loss)/jit(log_softmax)/exp") == "loss"
    assert part("f", "jit(step)/jvp(total_loss)/add") == "loss"
    assert part("f", "jit(step)/update/mul") == "update"
    assert part("f", "jit(step)/jvp(input_transform)/dot_general") \
        == "input_transform"
    assert part("%psum.79 = f32[64]{0} all-reduce(%x), channel_id=1",
                "jit(step)/psum") == "collective"
    assert part("%psum.79 = f32[64]{0} all-reduce(%x), channel_id=1",
                "jit(step)/transpose(jvp(conv1))/psum") == "conv"
    assert part("%copy.92 = bf16[8]{0} copy(%x)", "") == "unscoped"
    assert part("f", "jit(_threefry_split)/threefry2x32") == "unscoped"
    # a layer's name is matched whole, element by element
    assert part("f", "jit(step)/jvp(xblock0/ln1)/mul") == "unscoped"


def test_what_xla_made_inside_a_loop_is_unscoped_in_a_trace():
    # it answers with the loop's path: the program named the loop
    made = step_parts.made_inside
    loop = "%while.7 = (f32[8]{0}) while(%t), condition=%c, body=%b"
    copy = "%copy.785 = f32[8]{0} copy(%x)"
    assert made(copy, BWD + ":") and not made(loop, BWD + ":")
    assert made("fusion.9", SCAN + "/body/closed_call")
    assert not made("while.7", SCAN) and not made(copy, SCAN + "/body/mul")
    # a jitted helper's body lies inside one scope: its path is trusted
    assert not made(copy, "jit(step)/jvp(loss)/jit(take_along_axis)")
    ops = [("c0", 0, 100, loop, BWD + ":"), ("c0", 10, 30, copy, BWD + ":"),
           ("c0", 30, 50, "%dus.1 = f32[8]{0} fusion(%x)",
            BWD + "/body/dynamic_update_slice:")]
    out = step_parts.partition(ops, (0, 100), LAYERS)
    assert out["parts"] == {"scan_carry": {"backward": 80},
                            "unscoped": {"backward": 20}}
    assert out["unscoped"] == [["copy.785", 20, "in while.7",
                                "f32[8]{0} copy(%x)"]]


def test_self_times_add_up_to_the_union_whatever_the_overlap():
    spans = [(0, 100), (10, 60), (20, 50), (55, 70), (120, 130)]
    own, inside = step_parts.self_times(spans)
    # (55, 70) starts inside (10, 60) and outlives it: no nesting, still
    # every instant to the span that started last
    assert own == [40, 15, 30, 15, 10] and sum(own) == 100 + 10
    assert inside == [None, 0, 1, 1, None]


def test_nested_loops_give_each_operation_its_self_time():
    ops = [("c0", 0, 100, "while.1", SCAN),
           ("c0", 10, 60, "while.2",
            SCAN + "/body/closed_call/block0/moe/moe_glue/while"),
           ("c0", 20, 50, "fusion.3", SCAN + "/body/closed_call/block0/moe/"
            "moe_glue/while/body/moe_experts/dot_general"),
           ("c0", 60, 90, "fusion.4",
            SCAN + "/body/closed_call/block0/ln1/mul")]
    out = step_parts.partition(ops, (0, 100), LAYERS)
    assert out["busy"] == 100 and out["chips"] == 1
    assert _ms(out, "scan_carry", "forward") == 20     # 0-10 and 90-100
    assert _ms(out, "moe_glue", "forward") == 20       # 10-20 and 50-60
    assert _ms(out, "moe_experts", "forward") == 30
    assert _ms(out, "norm", "forward") == 30
    assert sum(sum(r.values()) for r in out["parts"].values()) == 100


def test_two_chips_are_averaged_and_the_window_clips():
    ops = [("c0", -10, 30, "fusion.1", "jit(step)/jvp(conv1)/conv"),
           ("c0", 50, 70, "fusion.2", "jit(step)/transpose(jvp(conv1))/conv"),
           ("c1", 0, 100, "fusion.1", "jit(step)/jvp(conv1)/conv"),
           ("c1", 90, 130, "fusion.9", "jit(step)/update/mul")]
    out = step_parts.partition(ops, (0, 100), LAYERS)
    assert out["chips"] == 2
    assert out["busy"] == (50 + 100) / 2
    assert _ms(out, "conv", "forward") == (30 + 90) / 2
    assert _ms(out, "conv", "backward") == 20 / 2
    assert _ms(out, "update", "update") == 10 / 2


def test_an_operation_without_a_path_is_unscoped_in_its_containers_phase():
    ops = [("c0", 0, 10, "fusion.1", "jit(step)/jvp(conv1)/conv"),
           ("c0", 10, 15, "copy.5", ""),                   # after a forward
           ("c0", 20, 80, "while.7", BWD),
           ("c0", 30, 40, "copy.6", ""),                   # inside the loop
           ("c0", 40, 50, "fusion.8", BWD + "/body/closed_call/checkpoint/"
            "rematted_computation/block0/ln1/mul"),
           ("c0", 50, 55, "copy.6", ""),
           ("c0", 80, 90, "fusion.9", "jit(step)/update/mul"),
           ("c0", 90, 95, "copy.7", "")]
    out = step_parts.partition(ops, (0, 100), LAYERS)
    assert out["parts"]["unscoped"] == {"forward": 5, "backward": 15,
                                        "update": 5}
    assert out["unscoped"][0] == ["copy.6", 15, "in while.7", "copy.6"]
    assert _ms(out, "norm", "recompute") == 10
    assert _ms(out, "scan_carry", "backward") == 60 - 10 - 10 - 5
    assert out["busy"] == 90


def test_a_checkpoints_replay_is_its_own_phase():
    head = "jit(step)/transpose(jvp(jvp()))/checkpoint"
    ops = [("c0", 0, 10, "f.1", head + "/rematted_computation/block0/mixer/"
            "gdn_proj_in/dot_general"),
           ("c0", 10, 30, "f.2", head + "/block0/mixer/gdn_proj_in/"
            "dot_general"),
           ("c0", 30, 35, "f.3", "jit(step)/jvp(block0/mixer)/gdn_proj_in/"
            "dot_general")]
    out = step_parts.partition(ops, (0, 40), LAYERS)
    assert out["parts"]["gdn_proj_in"] == {"recompute": 10, "backward": 20,
                                           "forward": 5}


def test_a_collective_counts_in_the_backward_pass():
    ops = [("c0", 0, 10, "%psum.3 = f32[8]{0} all-reduce(%g), channel_id=2",
            "jit(step)/psum")]
    out = step_parts.partition(ops, (0, 10), LAYERS)
    assert out["parts"] == {"collective": {"backward": 10}}
    # one scheduled in the middle of the forward pass (a shard's index)
    # leaves what follows it without a path in the forward pass
    ops = [("c0", 0, 10, "fusion.1", "jit(step)/jvp(conv1)/conv"),
           ("c0", 10, 11, "fusion.2",
            "jit(step)/shard_map/grad_exchange/dynamic_slice"),
           ("c0", 11, 20, "copy.5", "")]
    out = step_parts.partition(ops, (0, 20), LAYERS)
    assert out["parts"]["collective"] == {"backward": 1}
    assert out["parts"]["unscoped"] == {"forward": 9}


# -- from a trace file to the `# parts` line -----------------------------------

def _xspace(tmp_path, ops, unit=(0, 1_000_000)):
    """An `.xplane.pb` with one device plane holding `ops` [(start_ps,
    dur_ps, name, tf_op)] and a host plane with one bench.unit span."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    other = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    for i, (start, dur, name, tf_op) in enumerate(ops, 1):
        dev.event_metadata[i].name = name
        if tf_op:
            dev.event_metadata[i].stats.add(metadata_id=1, str_value=tf_op)
        line.events.add(metadata_id=i, offset_ps=start, duration_ps=dur)
    other.events.add(metadata_id=1, offset_ps=0, duration_ps=10 ** 9)
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = "bench.unit"
    host.lines.add(name="python3", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=unit[0], duration_ps=unit[1] - unit[0])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def _ctx(path, units=1):
    return {"xplane": path, "sync_every": 1, "trace": {"units": units}}


def test_from_a_trace_file_to_the_ledger_and_its_line(tmp_path, capsys,
                                                      monkeypatch):
    path = _xspace(tmp_path, [
        (0, 400_000, "%fusion.1 = f32[8]{0} fusion(%p)",
         "jit(step)/jvp(block0/mixer)/gdn_proj_in/dot_general:"),
        (400_000, 100_000, "%copy.2 = f32[8]{0} copy(%p)", ""),
        (600_000, 300_000, "%fusion.3 = f32[8]{0} fusion(%p)",
         "jit(step)/transpose(jvp(lm_head))/dot_general:"),
        (900_000, 300_000, "%fusion.4 = f32[8]{0} fusion(%p)",
         "jit(step)/update/mul:")])
    monkeypatch.setattr(step_parts, "net_parts",
                        lambda: {"net": "toy", "parts": LAYERS})
    step_parts._cache.clear()
    led = step_parts.ledger(_ctx(path))
    assert led["steps"] == 1 and led["chips"] == 1
    assert led["busy_ms"] == pytest.approx(0.9e-3)     # clipped at the unit
    assert led["ms"]["gdn_proj_in"] == pytest.approx([0.4e-3, 0, 0, 0])
    assert led["ms"]["head"] == pytest.approx([0, 0, 0.3e-3, 0])
    assert led["ms"]["update"] == pytest.approx([0, 0, 0, 0.1e-3])
    assert led["unscoped"] == [["copy.2", pytest.approx(0.1e-3), "",
                                "f32[8]{0} copy(%p)"]]
    assert step_parts.ms(_ctx(path), ("unscoped",)) == pytest.approx(0.1e-3)
    assert step_parts.ms(_ctx(path), None, ("backward",)) \
        == pytest.approx(0.3e-3)
    said = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("# parts ")]
    assert len(said) == 1                              # once for all readers
    line = json.loads(said[0][len("# parts "):])
    assert line["net"] == "toy" and line["phases"] == list(step_parts.PHASES)
    assert line["sum_ms"] == pytest.approx(0.9e-3) and not line["busy_ms"]
    assert line["unscoped"][0][0] == "copy.2"


def test_names_older_than_the_program_give_nothing_and_say_so(
        tmp_path, capsys, monkeypatch):
    # a step from a compile cache written before the scopes were closed:
    # the DeltaNet layer's products under the layer's name alone
    path = _xspace(tmp_path, [
        (0, 400_000, "%fusion.1 = f32[8]{0} fusion(%p)",
         "jit(step)/jvp(block0/mixer)/dot_general:")])
    monkeypatch.setattr(step_parts, "net_parts",
                        lambda: {"net": "toy", "parts": LAYERS})
    step_parts._cache.clear()
    assert step_parts.ledger(_ctx(path)) is None
    assert step_parts.ms(_ctx(path), ("unscoped",)) is None
    out = capsys.readouterr().out
    assert "# parts none" in out and "gdn_proj_in" in out \
        and "compile cache" in out
    # and a program without the record (a parent commit)
    monkeypatch.setattr(step_parts, "net_parts", lambda: None)
    step_parts._cache.clear()
    assert step_parts.ledger(_ctx(path)) is None
    assert "no net.parts record" in capsys.readouterr().out


def test_the_six_readers_name_no_layer_and_read_one_ledger(monkeypatch):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = ["step_unscoped_ms", "step_recompute_ms", "lm_proj_ms",
             "lm_head_loss_ms", "lm_glue_ms", "lm_scan_carry_ms"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    led = {"ms": {"unscoped": [1, 0, 2, 0], "gdn_proj_in": [3, 3, 6, 0],
                  "proj": [1, 1, 2, 0], "head": [2, 0, 4, 0],
                  "loss": [1, 0, 1, 0], "final_norm": [.5, 0, .5, 0],
                  "norm": [1, 1, 2, 0], "embed": [1, 0, 1, 0],
                  "moe_glue": [1, 0, 0, 0], "scan_carry": [2, 0, 3, 0],
                  "update": [0, 0, 0, 15]}}
    monkeypatch.setattr(step_parts, "ledger", lambda ctx: led)
    want = {"step_unscoped_ms": 3, "step_recompute_ms": 5, "lm_proj_ms": 16,
            "lm_head_loss_ms": 9, "lm_glue_ms": 7, "lm_scan_carry_ms": 5}
    for name in names:
        mod = importlib.import_module(f"layer_metrics.{name}")
        meta = dict(entries[name])
        cells = meta.pop("workloads")
        assert mod.META == meta
        assert mod.read({}) == want[name]
        # the cells PR 38 gave them come first; a later cell is appended
        first = 4 if name == "step_unscoped_ms" else 0
        assert cells[first:first + 3] == ["qwen3next_ep32_s8192_b2",
                                          "smallthinker_ep8_s16384_b2",
                                          "lfm2moe_ep4_s8192_b3"]
    monkeypatch.setattr(step_parts, "ledger", lambda ctx: None)
    assert all(importlib.import_module(f"layer_metrics.{n}").read({}) is None
               for n in names)
    # no reader knows a model: no layer of any zoo net in their text
    zoo_layers = set()
    for build in NETS.values():
        zoo_layers |= {lp.name for lp in build().layer}
    texts = [open(os.path.join(BENCH, "step_parts.py")).read()] + [
        open(os.path.join(BENCH, "layer_metrics", n + ".py")).read()
        for n in names]
    for text in texts:
        words = set(re.findall(r"[\w/]+", text))
        assert not {n for n in zoo_layers
                    if n in words and n not in ("loss", "data", "label")}
