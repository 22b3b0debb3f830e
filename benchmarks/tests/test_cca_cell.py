"""The compressed-convolution decoder's lifelong-histories cell: its rehearsal
and its fourteen controls, its counts against a hand count, its scopes' reader
on hand-made intervals and on a recorded trace of one step of the cell, every
new reader on a run that lacks its source, the configuration against the
published keys."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from benchmarks import counts_zaya, scopes_cca, scopes_leaf, scopes_seq, trace_reduce as tr  # noqa: E402

CELL = "zaya1-8b-ep2.train-lifelong-histories-16k"
DEVICE = "/device:TPU:0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
RECORDED = os.path.join(HERE, "zaya_step_v5e.xplane.pb")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "configs", "zaya1-8b-ep2.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")) as f:
    WORKLOAD = json.load(f)
NEW_READERS = ["cca_mix_ms", "cca_mix_hbm_share", "cca_attention_mxu_share", "router_mlp_ms",
               "moe_skip_share"]
CONTROLS = ["bfloat16", "no_conv0", "no_conv1", "no_qk_mean", "no_value_shift", "no_qk_norm",
            "no_temperature", "whole_rope", "no_carry", "linear_router", "no_bias", "no_skip",
            "no_residual_scale", "untied_head"]


def _reader(name):
    from run import load_module

    return load_module("layer_metrics", name)


# ---- the rehearsal and its controls --------------------------------------------

def test_the_rehearsal_reads_correct_and_every_control_reads_not_correct():
    """``--rehearse 1 --control 1``: the cell's own path on the CPU at its cut
    widths, under the rehearsal's own limits; every control fails by one of
    them at least."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000011", "--seconds", "1", "--rehearse", "1", "--control", "1"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    checks = {line["check"]: line for line in lines if "check" in line}
    assert checks["moe_dropped"]["value"] == 0 and checks["compilations_in_window"]["value"] == 0
    judged = [name for name in checks if name.startswith("seeded_")]
    assert len(judged) == 23 and all(checks[name]["limit"] < float("inf") for name in judged)
    controls = {line["control"]: line for line in lines if "control" in line}
    assert sorted(controls) == sorted(CONTROLS)
    assert not [name for name, line in controls.items() if line["correct"]]
    said = next(line for line in lines if "bias_decided_share" in line)
    assert said["bias_decided_share"] > 0 and said["skip_share"] > 0
    fit = next(line for line in lines if "fit" in line)["fit"]
    assert (fit["backbone"], fit["latent_q_width"], fit["latent_kv_width"]) == ("cca_moe", 64, 32)
    assert (fit["conv_time0"], fit["conv_time1"], fit["router_width"], fit["skip_choices"]) == (
        2, 2, 32, 1)
    assert fit["experts_held"] == 4 and fit["experts_held_share"] == 0.5 and fit["head_tied"] == 1


def test_every_limit_of_the_cell_is_set():
    from benchmarks.drivers import seq_cca_train

    names = ({"loss_abs_err_limit", "adam_update_rel_err_limit", "routing_counts_share_limit",
              "bias_unequal_beyond_one_limit", "bias_flip_load_distance_limit",
              "bias_step_abs_err_limit", "grad_rel_err_limits"})
    for check in (WORKLOAD["traffic"]["correct"], WORKLOAD["traffic"]["rehearsal"]["correct"]):
        for state in seq_cca_train.STATES:
            assert set(check[state]) == names, state
            assert set(check[state]["grad_rel_err_limits"]) == set(seq_cca_train.GRADIENTS)
    assert list(seq_cca_train.CONTROLS) == CONTROLS


# ---- the configuration ---------------------------------------------------------

def test_every_published_number_is_in_the_file_and_the_cut_is_what_reduced_lists():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "zaya1-8b-ep2")
    assert entry["source"] == CONFIG["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    differs = sorted(k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v)
    assert differs == ["layer_types", "num_hidden_layers", "vocab_size"]
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 4      # the floor
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["num_local_experts"] == 8 and CONFIG["num_experts"] == 16
    published = CONFIG["published"]
    assert (published["num_hidden_layers"], published["vocab_size"]) == (40, 262272)
    assert len(CONFIG["assumed"]) >= 9
    # every control is named by the assumption it holds to
    text = json.dumps(CONFIG["assumed"])
    assert not [c for c in CONTROLS if c != "bfloat16" and c not in text]


def test_the_count_is_the_files_to_the_digit():
    d, r, hd = 2048, 256, 128
    attention = d * 1024 + d * 256 + 2 * d * 128 + 1024 * d
    convolutions = 1280 * 2 + 1280 + 10 * 2 * hd * hd + 10 * hd
    router = d * r + 3 * r + 2 * (r * r + r) + r * 17
    layer = attention + convolutions + router + 10 * d + 2 + 8 * 3 * d * 2048
    total = 4 * layer + 32784 * d + d
    assert total == CONFIG["parameters"]["total"] == 494_825_480
    assert CONFIG["data"]["items"] == CONFIG["vocab_size"] - 1
    assert CONFIG["data"]["min_events"] == WORKLOAD["traffic"]["max_len"] == 16384
    assert WORKLOAD["traffic"]["users_per_step"] == 2 and WORKLOAD["traffic"]["warm_steps"] == 1


def test_the_need_against_a_hand_count():
    rows, length = 2, 16384
    step = {"tokens": 32768.0, "targets": 32766.0,
            "causal_pairs": counts_zaya.pairs_of([length] * rows), "moe_held_assignments": 61000.0}
    assert step["causal_pairs"] == rows * length * (length + 1) / 2
    parts = counts_zaya.forward_parts(step, CONFIG, CONFIG["vocab_size"])
    assert parts["causal_pairs"] == 4 * step["causal_pairs"] * 8 * 2 * 2 * 128
    assert parts["cca_projections_and_convolution"] == 32768 * 4 * (
        2 * 2048 * (1024 + 256 + 256 + 1024) + 2 * 1280 * 2 * 128)
    assert parts["routers"] == 32768 * 4 * 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 17)
    assert parts["held_experts"] == 61000 * 6 * 2048 * 2048
    assert parts["head"] == 32766 * 2 * 2048 * 32784
    assert counts_zaya.step_model_flops(step, CONFIG, CONFIG["vocab_size"]) == 3 * sum(
        parts.values())
    assert counts_zaya.attention_flops(step, CONFIG) == 7 * 4 * step["causal_pairs"] * 8 * 256
    # inputs and outputs of the stage, 1,536 floats a token each: twice forward,
    # twice recomputed, three times backward
    assert counts_zaya.mix_bytes(step, CONFIG) == 4 * 32768 * 4 * 1536 * 7


# ---- the scopes' reader --------------------------------------------------------

FWD = "jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/checkpoint"
BWD = ("jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/closed_call/checkpoint"
       "/rematted_computation")


@pytest.mark.parametrize("op_name,place", [
    (FWD + "/attention/mix/mul:", "mix"),
    (BWD + "/attention/mix/dot_general:", "mix"),
    (FWD + "/attention/kernel/pallas_call:", "kernel"),
    (FWD + "/attention/rope/pallas_call:", "rope"),
    (FWD + "/attention/merge/add:", "merge"),
    (FWD + "/moe/route/down/dot_general:", "route/down"),
    (FWD + "/moe/route/carry/add:", "route/carry"),
    (FWD + "/moe/route/mlp/erf:", "route/mlp"),
    (FWD + "/moe/route/choose/argmax:", "route/choose"),
    (FWD + "/moe/route/reshape:", None),
    (FWD + "/moe/experts/sort/sort:", None),
    (FWD + "/moe/merge/add:", None),
    ("jit(iteration)/als.user_half_step/bucket0/gram/mix/x:", None),
    ("", None),
])
def test_place_of(op_name, place):
    assert scopes_cca.place_of(op_name) == place


def _hand_made(monkeypatch):
    names = {
        "fusion.1": FWD + "/attention/mix/mul:",
        "fusion.2": BWD + "/attention/mix/dot_general:",
        "kernel.1 tpu_custom_call": FWD + "/attention/kernel/pallas_call:",
        "kernel.2 tpu_custom_call": FWD + "/attention/rope/pallas_call:",
        "fusion.3": FWD + "/attention/kernel/convert_element_type:",
        "fusion.4": FWD + "/moe/route/down/dot_general:",
        "fusion.5": FWD + "/moe/route/mlp/erf:",
        "fusion.6": FWD + "/moe/route/choose/argmax:",
        "fusion.7": FWD + "/moe/route/carry/add:",
    }
    ops = [("fusion.1", 0.0, 0.5), ("fusion.2", 0.5, 1.0), ("kernel.1 tpu_custom_call", 1.0, 3.0),
           ("kernel.2 tpu_custom_call", 3.0, 3.5), ("fusion.3", 3.5, 3.75),
           ("fusion.4", 4.0, 4.25), ("fusion.5", 4.25, 4.75), ("fusion.6", 4.75, 5.75),
           ("fusion.7", 5.75, 6.0), ("kernel.1 tpu_custom_call", 11.0, 12.0)]  # past the window
    planes = {DEVICE: {tr.OP_LINE: ops}, "/host:CPU": {"main": [(tr.WINDOW_NAME, 0.0, 10.0)]}}
    reduced = scopes_cca.reduce_places(planes, {DEVICE: names})
    monkeypatch.setattr(scopes_cca, "_reduced", lambda path, mtime: reduced)
    monkeypatch.setattr(scopes_cca.scopes, "newest_xplane", lambda: __file__)
    return reduced


def test_the_readers_on_hand_made_intervals(monkeypatch):
    reduced = _hand_made(monkeypatch)
    assert reduced == pytest.approx({
        "mix": 1.0, "kernel": 2.25, scopes_cca.PROGRAMS: 2.0, "rope": 0.5, "route/down": 0.25,
        "route/mlp": 0.5, "route/choose": 1.0, "route/carry": 0.25})
    step = {"tokens": 32768.0, "causal_pairs": counts_zaya.pairs_of([16384, 16384]),
            "moe_skip_assignments": 7000.0, "moe_assignments": 124072.0}
    run = {"trace": {"busy_s": 10.0, "window_s": 10.0}, "steps": 2,
           "device_kind": "TPU v5 lite", "dims": CONFIG, "step_counts": step}
    assert _reader("cca_mix_ms").read(run) == pytest.approx(500.0)
    assert _reader("router_mlp_ms").read(run) == pytest.approx(500.0)     # not ``choose``
    # the attention programs under ``kernel`` alone: 2 s in the window, two steps
    assert _reader("cca_attention_mxu_share").read(run) == pytest.approx(
        100 * (counts_zaya.attention_flops(step, CONFIG) / 197e12) / 1.0)
    assert _reader("cca_mix_hbm_share").read(run) == pytest.approx(
        100 * (counts_zaya.mix_bytes(step, CONFIG) / 819e9) / 0.5)
    assert _reader("moe_skip_share").read(run) == pytest.approx(100 * 7000 / 131072)
    # another decoder's run (its counts, its dims): the shares give nothing
    other = {**run, "step_counts": {"tokens": 16384.0, "window_pairs": 1.0}}
    for name in ("cca_attention_mxu_share", "cca_mix_hbm_share", "moe_skip_share"):
        assert _reader(name).read(other) is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace of the cell")
def test_the_readers_on_a_recorded_step_of_the_cell(monkeypatch):
    """``zaya_step_v5e.xplane.pb``: a traced window of one step of this cell on
    a TPU v5 lite (PR 48; what it read is in ``zaya_step_v5e.json`` beside it).
    The new readers find their scopes, the attention programs are under
    ``kernel`` and the operands' under ``rope``, and the shares stay under 100."""
    with open(RECORDED.replace(".xplane.pb", ".json")) as f:
        said = json.load(f)
    monkeypatch.setattr(scopes_cca.scopes, "newest_xplane", lambda: RECORDED)
    monkeypatch.setattr(scopes_leaf.scopes, "newest_xplane", lambda: RECORDED)
    run = {"trace": {"busy_s": said["busy_s"], "window_s": said["window_s"]},
           "steps": said["steps"], "device_kind": "TPU v5 lite", "dims": CONFIG,
           "step_counts": said["step_counts"]}
    got = {name: _reader(name).read(run) for name in NEW_READERS}
    assert got == pytest.approx(said["metrics"], rel=1e-6)
    assert 0 < got["cca_attention_mxu_share"] < 100 and 0 < got["cca_mix_hbm_share"] < 100
    assert got["router_mlp_ms"] < _reader("moe_route_ms").read(run)
    found = scopes_cca.reduce_places(tr.read_planes(RECORDED), scopes_seq.read_op_names(RECORDED))
    assert found[scopes_cca.PROGRAMS] <= found["kernel"] and found["rope"] > 0
    # the merges are fused into their neighbours' fusions: no operation is rooted at ``merge``
    assert {"mix", "route/down", "route/carry", "route/mlp", "route/choose"} <= set(found)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """An untraced run, and a program that names none of these scopes and
    returns none of these counts (the parent's): None, no raise."""
    reader = _reader(name)
    assert reader.read({"end_to_end": {}, "setup": {}}) is None
    bare = {"trace": {"busy_s": 0.0, "window_s": 1.0, "device_ops": [], "idle_gaps": []},
            "iterations": 3, "device_kind": "TPU v5 lite"}
    assert reader.read(bare) is None


def test_the_new_readers_are_listed_for_this_cell_alone_and_the_cell_reports_the_old_ones():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_iters_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert [m["name"] for m in MANIFEST["per_layer"]][-5:] == NEW_READERS
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [CELL])}
    assert listed == set(NEW_READERS) | {
        "device_idle_share.train", "jit_trace_lower_s", "jit_compile_or_load_s",
        "jit_cache_misses", "seq_step_busy_ms", "seq_step_mfu", "seq_layers_ms", "seq_exit_ms",
        "seq_optimizer_ms", "seq_forward_ms", "seq_recompute_ms", "seq_backward_ms",
        "seq_scope_coverage", "seq_slot_fill", "seq_attention_ms", "seq_attention_proj_ms",
        "seq_attention_kernel_ms", "seq_attention_rope_layout_ms", "moe_route_ms",
        "moe_experts_ms", "moe_experts_mxu_share", "moe_grouped_ms", "moe_rows_ms", "moe_sum_ms",
        "moe_held_share", "moe_load_max_over_mean"}
    train = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_iters_per_s")
    assert train["workloads"][-1] == CELL and train["bound"] == 0.01
    entry = MANIFEST["workloads"][-1]
    assert (entry["name"], entry["config"], entry["chips"], entry["traffic"]) == (
        CELL, "zaya1-8b-ep2", 1, "train-lifelong-histories-16k")
    assert len(entry["why"]) <= 200 and MANIFEST["configs"][-1]["name"] == "zaya1-8b-ep2"
    assert len(MANIFEST["configs"][-1]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
