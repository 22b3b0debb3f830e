"""``scopes_leaf.py``: the leaf, phase and containment rules on ``op_name``s taken
from real traces, and the identities between the new metrics and the accepted
stage metrics on a cut of a recorded v5e trace of each sequence cell and of the
one-chip ALS cell (PR 35; ``*_leaf_v5e.xplane.pb``: one whole step or iteration
of the first device's ``XLA Ops`` line, each instruction's name cut to its
opcode and custom-call target, its ``tf_op`` alone of its stats, and a
``bench.window`` annotation over the run)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from benchmarks import scopes, scopes_leaf, scopes_seq, scopes_sparse, trace_reduce as tr  # noqa: E402
from benchmarks.scopes_leaf import Place  # noqa: E402

OURO, KEYE = "ouro-2.6b-d8.train-histories", "keye-vl2-30b-a3b-ep8.train-lifelong-histories"
ALS1 = "als-ml20m-r16.train-steady"
CUTS = {OURO: os.path.join(HERE, "ouro_leaf_v5e.xplane.pb"),
        KEYE: os.path.join(HERE, "keye_leaf_v5e.xplane.pb"),
        ALS1: os.path.join(HERE, "als_leaf_v5e.xplane.pb")}
BACK = "jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/closed_call/checkpoint"
EXPERTS = BACK + "/moe/experts/while/body/closed_call/checkpoint/cond/branch_1_fun"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


# ---- an op_name taken apart ----------------------------------------------------

@pytest.mark.parametrize("op_name,want", [
    # the backward pass wraps the first component; the layer is run again in it
    ("jit(train_step)/transpose(jvp(seq.pass3))/layers/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/qkv/dot_general:",
     Place("seq", "pass3", "attention", "qkv", "recomputed")),
    (BACK + "/attention/rope/mul:", Place("seq", "pass1", "attention", "rope", "backward")),
    ("jit(train_step)/jvp(seq.pass2)/layers/while/body/closed_call/attention/kernel/pallas_call:",
     Place("seq", "pass2", "attention", "kernel", "forward")),
    # the last component is the primitive, never a scope: a gather under `sum`,
    # the sort primitive of the router's top-k
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/moe/experts/closed_call/"
     "while/body/closed_call/checkpoint/cond/branch_1_fun/give/sum/gather:",
     Place("seq", "pass1", "experts", "sum", "forward")),
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/moe/route/sort:",
     Place("seq", "pass1", "route", None, "forward")),
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/moe/norm/reduce_sum:",
     Place("seq", "pass1", "moe", "norm", "forward")),
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/moe/add:",
     Place("seq", "pass1", "moe", None, "forward")),
    # the experts' backward rule: its forward half says `again`, its pullback
    # wraps that or drops it
    (EXPERTS + "/again/jvp(take)/gather:", Place("seq", "pass1", "experts", "take", "recomputed")),
    (EXPERTS + "/again/jvp()/lt:", Place("seq", "pass1", "experts", None, "recomputed")),
    (EXPERTS + "/transpose(again)/jvp(give)/gather:",
     Place("seq", "pass1", "experts", "give", "backward")),
    (EXPERTS + "/transpose(again)/jvp(take)/sum/while/body/closed_call/gather:",
     Place("seq", "pass1", "experts", "sum", "backward")),
    (EXPERTS + "/transpose(jvp(grouped))/dot_general:",
     Place("seq", "pass1", "experts", "grouped", "backward")),
    (BACK + "/moe/experts/while/body/closed_call/checkpoint/rematted_computation/sort/"
     "jit(argsort)/sort:", Place("seq", "pass1", "experts", "sort", "recomputed")),
    # the exit's norm is the exit's; the scan's own work is the layers'
    ("jit(train_step)/jvp(seq.pass4)/exit/mul:", Place("seq", "pass4", "exit", None, "forward")),
    ("jit(train_step)/transpose(jvp(seq.pass1))/layers/while:",
     Place("seq", "pass1", "layers", None, "backward")),
    ("jit(train_step)/seq.optimizer/add:", Place("seq", "optimizer", None, None, None)),
    ("jit(train_step)/transpose(jvp(seq.embed))/jit(_take)/scatter-add:",
     Place("seq", "embed", None, None, None)),
    # ALS: the leaf after the stage; the exchange is the stage's own child
    ("jit(iteration)/als.user_half_step/bucket0/gram/gather/gather:",
     Place("als", "als.user_half_step", "gram", "gather", None)),
    ("jit(iteration)/als.item_half_step/bucket2/while/body/gram/products/dot_general:",
     Place("als", "als.item_half_step", "gram", "products", None)),
    ("jit(iteration)/als.item_half_step/bucket1/shard_map/gram/exchange/all_to_all:",
     Place("als", "als.item_half_step", "gram", "exchange", None)),
    ("jit(iteration)/als.user_half_step/bucket0/solve/mul:",
     Place("als", "als.user_half_step", "solve", None, None)),
    ("jit(iteration)/als.user_half_step/assemble/exchange/all_gather:",
     Place("als", "als.user_half_step", "assemble", "exchange", None)),
    ("ragged-dot-none:", None),
    ("", None),
])
def test_an_op_name_is_taken_apart_into_stage_leaf_and_phase(op_name, want):
    assert scopes_leaf.place_of(op_name) == want


@pytest.mark.parametrize("hlo,want", [
    ("%while.190 = (s32[]{:T(128)}, f32[2,8192,2048]{2,1,0:T(8,128)}, /*index=5*/f32[6,16]{1,0}) "
     "while((s32[]{:T(128)}, f32[2,8192,2048]{2,1,0:T(8,128)}) %tuple.1), condition=%c, body=%b", "while"),
    ("%cond.119 = (bf16[16384,2048]{1,0:T(8,128)(2,1)}, f32[16384,8]{1,0:T(8,128)}) "
     "conditional(s32[]{:T(128)} %convert.1, () %cond.117), branch_computations={%a, %b}", "conditional"),
    ('%ragged-dot-none.7 = f32[32768,2048]{1,0:T(8,128)} custom-call(s32[1]{0:T(128)} %g, '
     's32[17]{0:T(128)S(1)} %copy-done.155), custom_call_target="tpu_custom_call"', "custom-call"),
    ("%fusion.1043 = bf16[32,256,2048,1]{2,1,3,0:T(8,128)(2,1)} fusion(f32[32,256,2048]{2,1,0} %p), "
     "kind=kLoop, calls=%fused_computation.1", "fusion"),
    ("%copy-done.384 = f32[4]{0:T(128)S(1)} copy-done((f32[4]{0:T(128)S(1)}, u32[]{:S(2)}) %copy-start.384)",
     "copy-done"),
    ("%while.44 = (s32[], f32[8])... while(...", "while"),       # as a recorded cut keeps it
    ("bench.window", ""),
])
def test_the_opcode_of_an_instruction(hlo, want):
    assert scopes_leaf.opcode_of(hlo) == want


# ---- operations without a scope -------------------------------------------------

def test_an_unnamed_operation_takes_the_place_of_the_control_flow_around_it():
    """A backward ``conditional`` without a ``tf_op`` of its own (as a v5e trace
    has them) holds two scoped fusions, a ragged dot and a copy: the two take
    the place the ``conditional`` would have had, the components its scoped
    operations share. The same ragged dot outside it is the experts' by name
    alone, and a copy outside is nowhere."""
    known = {
        "while.1": ("", "while"), "cond.2": ("", "conditional"),
        "fusion.3": (EXPERTS + "/again/jvp(take)/gather:", "fusion"),
        "fusion.4": (EXPERTS + "/transpose(jvp(grouped))/mul:", "fusion"),
        "ragged-dot-none.5 tpu_custom_call": ("ragged-dot-none:", "custom-call"),
        "copy.6": ("", "copy"),
        "fusion.7": (BACK + "/attention/out/add:", "fusion"),
        "ragged-dot-none.8 tpu_custom_call": ("ragged-dot-none:", "custom-call"),
        "copy.9": ("", "copy"),
    }
    ops = [("while.1", 0.0, 10.0), ("fusion.7", 0.5, 1.0), ("cond.2", 1.0, 9.0),
           ("fusion.3", 1.0, 2.0), ("ragged-dot-none.5 tpu_custom_call", 2.0, 4.0),
           ("copy.6", 4.0, 4.5), ("fusion.4", 5.0, 8.0),
           ("ragged-dot-none.8 tpu_custom_call", 11.0, 12.0), ("copy.9", 12.0, 13.0)]
    placed = {(s, e): place for place, s, e in scopes_leaf.place_events(ops, known)}
    assert placed[(1.0, 2.0)] == Place("seq", "pass1", "experts", "take", "recomputed")
    assert placed[(5.0, 8.0)] == Place("seq", "pass1", "experts", "grouped", "backward")
    # inside: the conditional's place, backward; a ragged dot is `grouped` by name
    assert placed[(2.0, 4.0)] == Place("seq", "pass1", "experts", "grouped", "backward",
                                       program=True, placed=True)
    assert placed[(4.0, 4.5)] == Place("seq", "pass1", "experts", None, "backward", placed=True)
    # outside: by name alone, with no pass and no phase; the copy nowhere
    assert placed[(11.0, 12.0)] == Place("seq", "", "experts", "grouped", None,
                                         program=True, placed=True)
    assert (12.0, 13.0) not in placed
    # control flow events are never added: their bodies are
    assert (0.0, 10.0) not in placed and (1.0, 9.0) not in placed
    assert len(placed) == 6


def test_a_control_flow_event_with_a_name_of_its_own_keeps_it_and_nesting_falls_outward():
    """Where a trace does carry a ``while``'s ``op_name`` it is used as it is;
    an inner loop that holds no scoped operation leaves the placing to the loop
    around it; a loop whose operations share no scope places nothing."""
    known = {
        "while.1": (BACK + "/attention/kernel/while:", "while"),
        "while.2": ("", "while"), "copy.3": ("", "copy"),
        "while.4": ("", "while"),
        "fusion.5": ("jit(train_step)/jvp(seq.pass1)/exit/mul:", "fusion"),
        "fusion.6": ("jit(train_step)/seq.optimizer/add:", "fusion"),
        "copy.7": ("", "copy"),
    }
    ops = [("while.1", 0.0, 5.0), ("while.2", 1.0, 4.0), ("copy.3", 2.0, 3.0),
           ("while.4", 6.0, 9.0), ("fusion.5", 6.0, 7.0), ("fusion.6", 7.0, 8.0),
           ("copy.7", 8.0, 9.0)]
    placed = {(s, e): place for place, s, e in scopes_leaf.place_events(ops, known)}
    assert placed[(2.0, 3.0)] == Place("seq", "pass1", "attention", "kernel", "backward",
                                       placed=True)
    assert (8.0, 9.0) not in placed and len(placed) == 3


def test_seconds_are_unions_clipped_to_the_window_and_a_program_without_leaves_gives_nothing():
    window = ("bench.window", 1.0, 3.0)
    known = {"fusion.1": (BACK + "/attention/norm/mul:", "fusion"),
             "fusion.2": (BACK + "/attention/qkv/dot_general:", "fusion"),
             "fusion.3": (BACK + "/attention/qkv/convert:", "fusion")}
    ops = [("fusion.1", 0.5, 1.5), ("fusion.2", 1.5, 2.5), ("fusion.3", 2.0, 3.5)]
    planes = {"/device:TPU:0": {tr.OP_LINE: ops}, "/host:CPU": {"python3": [window]}}
    found = scopes_leaf.reduce_leaves(planes, {"/device:TPU:0": known})
    assert found["busy_s"] == pytest.approx(2.0)
    assert scopes_leaf.seconds(found, lambda p: p.leaf == "qkv") == pytest.approx(1.5)
    assert scopes_leaf.seconds(found, lambda p: p.leaf == "norm") == pytest.approx(0.5)
    assert scopes_leaf.seconds(found, scopes_leaf.named) == pytest.approx(2.0)
    assert scopes_leaf.has_leaves(found)
    bare = {name: (op_name.replace("/norm", "").replace("/qkv", ""), opcode)
            for name, (op_name, opcode) in known.items()}
    found = scopes_leaf.reduce_leaves(planes, {"/device:TPU:0": bare})
    assert not scopes_leaf.has_leaves(found)
    assert scopes_leaf.seconds(found, lambda p: p.stage == "attention") == pytest.approx(2.0)


def test_a_trace_of_a_program_from_before_the_leaves_gives_every_new_reader_nothing(monkeypatch):
    """``train_v5e_scoped.xplane.pb`` and ``seq_train_v5e.xplane.pb`` are traces
    of programs with the stage scopes alone: no reader of this file may raise
    or report on them (the driver lays these files over the parent too)."""
    from run import load_module

    new = [m for m in MANIFEST["per_layer"]
           if "scopes_leaf" in open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                                 m["name"] + ".py")).read()]
    assert len(new) == 15
    for recorded, run in (("seq_train_v5e.xplane.pb", {"trace": {"busy_s": 1.0}, "steps": 1}),
                          ("train_v5e_scoped.xplane.pb", {"trace": {"busy_s": 1.0}, "iterations": 2})):
        monkeypatch.setattr(scopes, "newest_xplane", lambda: os.path.join(HERE, recorded))
        assert scopes_leaf.of_run(run) is None
        for m in new:
            assert load_module("layer_metrics", m["name"]).read(run) is None
        assert load_module("layer_metrics", new[0]["name"]).read({**run, "trace": None}) is None


# ---- on recorded v5e traces -----------------------------------------------------

def _readers(cell, monkeypatch, run):
    """Every new reader listed for ``cell`` on the cell's recorded cut."""
    from run import load_module

    monkeypatch.setattr(scopes, "newest_xplane", lambda: CUTS[cell])
    return {m["name"]: load_module("layer_metrics", m["name"]).read(run)
            for m in MANIFEST["per_layer"]
            if cell in m.get("workloads", ()) and "scopes_leaf" in open(os.path.join(
                ROOT, "benchmarks", "layer_metrics", m["name"] + ".py")).read()}


def _sums(cell):
    planes = tr.read_planes(CUTS[cell])
    found = scopes_leaf.reduce_leaves(planes, scopes_leaf.read_instructions(CUTS[cell]))
    return planes, found, lambda want: 1000.0 * scopes_leaf.seconds(found, want)


def test_on_a_recorded_trace_of_the_looped_cell_the_leaves_add_up_to_the_stages(monkeypatch):
    """``ouro_leaf_v5e.xplane.pb``: the second step of a traced window of
    ``ouro-2.6b-d8.train-histories`` (PR 35, seed 3000000047). The four parts of
    ``attention``, its norms and its self time are ``seq_attention_ms``; the three
    phases are the time under the passes; nothing of ``attention`` is left
    unnamed."""
    got = _readers(OURO, monkeypatch, {"trace": {"busy_s": 1.0}, "steps": 1})
    assert sorted(got) == [
        "seq_attention_kernel_ms", "seq_attention_layout_ms", "seq_attention_proj_ms",
        "seq_attention_rope_ms", "seq_backward_ms", "seq_forward_ms", "seq_leaf_coverage",
        "seq_norm_ms", "seq_recompute_ms"]
    assert got == pytest.approx({
        "seq_attention_proj_ms": 159.150, "seq_attention_rope_ms": 72.670,
        "seq_attention_layout_ms": 46.767, "seq_attention_kernel_ms": 59.609,
        "seq_norm_ms": 32.975, "seq_forward_ms": 229.681, "seq_recompute_ms": 212.781,
        "seq_backward_ms": 458.962, "seq_leaf_coverage": 98.351}, rel=1e-4)
    planes, found, ms = _sums(OURO)
    stages = scopes_seq.reduce_scopes(planes, scopes_seq.read_op_names(CUTS[OURO]))
    attention = 1000.0 * stages["stages"]["attention"]
    under = ms(lambda p: p.stage == "attention" and p.leaf == "norm")
    self_time = ms(lambda p: p.stage == "attention" and p.leaf is None)
    assert self_time == 0.0 and under == pytest.approx(20.896, rel=1e-4)
    assert sum(got[f"seq_attention_{part}_ms"] for part in ("proj", "rope", "layout", "kernel")
               ) + under + self_time == pytest.approx(attention, rel=0.02)
    passes = 1000.0 * sum(stages["passes"].values())
    assert got["seq_forward_ms"] + got["seq_recompute_ms"] + got["seq_backward_ms"] == (
        pytest.approx(passes, rel=0.02))
    # with no self time under `attention`, what is named is what is scoped
    assert got["seq_leaf_coverage"] == pytest.approx(
        100.0 * stages["scoped_s"] / stages["busy_s"], rel=1e-6)
    # the flash calls: a forward, a recomputed forward, a dq and a dkv for each
    # of 24 layer applications
    calls = [place for by_place in found["planes"] for place, ivs in by_place.items()
             if place.program for _ in ivs]
    assert len(calls) == 96 and {(p.stage, p.leaf) for p in calls} == {("attention", "kernel")}
    assert sorted(p.phase for p in calls).count("backward") == 48


def test_on_a_recorded_trace_of_the_sparse_cell_the_leaves_add_up_and_the_ragged_dots_are_placed(
        monkeypatch):
    """``keye_leaf_v5e.xplane.pb``: the second step of a traced window of
    ``keye-vl2-30b-a3b-ep8.train-lifelong-histories`` (PR 35, seed 3000000011).
    The three parts of the experts are ``moe_experts_ms``; the ragged dots, which
    carry no scope, are all placed under the experts by the ``conditional`` that
    holds them, a backward one's as ``backward``; coverage beats
    ``seq_scope_coverage``, which counts them outside."""
    got = _readers(KEYE, monkeypatch, {"trace": {"busy_s": 1.0}, "steps": 1})
    assert sorted(got) == [
        "moe_grouped_ms", "moe_rows_ms", "moe_sum_ms", "seq_attention_kernel_ms",
        "seq_attention_proj_ms", "seq_attention_rope_layout_ms", "seq_backward_ms",
        "seq_forward_ms", "seq_leaf_coverage", "seq_norm_ms", "seq_recompute_ms"]
    assert got == pytest.approx({
        "seq_attention_proj_ms": 92.086, "seq_attention_rope_layout_ms": 82.874,
        "seq_attention_kernel_ms": 433.083, "seq_norm_ms": 21.477,
        "seq_forward_ms": 288.158, "seq_recompute_ms": 218.794, "seq_backward_ms": 404.781,
        "seq_leaf_coverage": 98.953, "moe_grouped_ms": 75.568, "moe_sum_ms": 81.925,
        "moe_rows_ms": 41.283}, rel=1e-4)
    planes, found, ms = _sums(KEYE)
    names = scopes_seq.read_op_names(CUTS[KEYE])
    stages = scopes_seq.reduce_scopes(planes, names)
    sparse = scopes_sparse.reduce_stages(planes, names)["stages"]
    assert got["moe_grouped_ms"] + got["moe_sum_ms"] + got["moe_rows_ms"] == pytest.approx(
        1000.0 * sparse["experts"], rel=0.02)
    under = ms(lambda p: p.stage == "attention" and p.leaf == "norm")
    self_time = ms(lambda p: p.stage == "attention" and p.leaf is None)
    attention = 1000.0 * stages["stages"]["attention"]
    assert (got["seq_attention_proj_ms"] + got["seq_attention_rope_layout_ms"]
            + got["seq_attention_kernel_ms"] + under + self_time
            ) == pytest.approx(attention, rel=0.02)
    assert self_time < 0.05 * attention           # 2.5 of 620 ms
    # the accepted readers count the index, select and attention programs'
    # stages whole: programs and the layout around them
    assert ms(lambda p: p.leaf in ("kernel", "index", "select")) == pytest.approx(
        1000.0 * (sparse["kernel"] + sparse["index"] + sparse["select"]), rel=1e-3)
    # every ragged dot is placed, in a pass, with a phase: a third of them in
    # the forward `conditional`, the rest (worked again, and transposed) in the
    # backward one
    dots = [(place, ivs) for by_place in found["planes"] for place, ivs in by_place.items()
            if place.placed and place.leaf == "grouped"]
    assert {(p.top, p.stage, p.program) for p, _ in dots} == {("pass1", "experts", True)}
    by_phase = {p.phase: sum(e - s for s, e in ivs) for p, ivs in dots}
    assert sorted(by_phase) == ["backward", "forward"]
    assert 1000.0 * sum(by_phase.values()) == pytest.approx(58.6, abs=0.3)
    assert by_phase["backward"] == pytest.approx(3.3 * by_phase["forward"], rel=0.1)
    # the time under the passes now holds them: `seq_scope_coverage` does not
    assert got["seq_forward_ms"] + got["seq_recompute_ms"] + got["seq_backward_ms"] == (
        pytest.approx(1000.0 * sum(stages["passes"].values()) + 1000.0 * sum(by_phase.values()),
                      rel=0.02))
    assert got["seq_leaf_coverage"] > 100.0 * stages["scoped_s"] / stages["busy_s"] + 5.0


def test_on_a_recorded_trace_of_the_one_chip_als_cell_the_gather_and_the_products_are_the_gram(
        monkeypatch):
    """``als_leaf_v5e.xplane.pb``: one iteration of a traced window of
    ``als-ml20m-r16.train-steady`` (PR 35, seed 3000000067), eight blocks worked
    whole: every operation under ``gram`` is the gather's or the products'."""
    got = _readers(ALS1, monkeypatch, {"trace": {"busy_s": 1.0}, "iterations": 1})
    assert got == pytest.approx({"als_gather_ms": 31.1027, "als_products_ms": 24.8395}, rel=1e-4)
    planes, found, ms = _sums(ALS1)
    stages = scopes.reduce_scopes(planes, scopes.read_op_names(CUTS[ALS1]))["stages"]
    assert ms(lambda p: p.stage == "gram" and p.leaf is None) == 0.0
    assert got["als_gather_ms"] + got["als_products_ms"] == pytest.approx(
        1000.0 * stages["gram"], rel=0.02)
    # the accepted stages read the same through this reader; no leaf elsewhere
    for stage in ("gram", "solve", "assemble"):
        assert ms(lambda p: p.stage == stage) == pytest.approx(1000.0 * stages[stage], rel=1e-6)
    assert not [p for by_place in found["planes"] for p in by_place
                if p.leaf and p.stage != "gram"]
    # the sequence cells' readers find nothing of theirs here
    assert scopes_leaf.seconds(found, lambda p: p.family == "seq") == 0.0
