"""The lifelong-histories cell: its three controls through the rehearsal, its
counts against a hand count, its scopes' reader on hand-made intervals, every
new reader on a run that lacks its source, the configuration against the
published keys, and the data it draws. (Its rehearsal is
``test_rehearsal.py``'s, which walks every file under ``workloads/``.)"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from benchmarks import counts_keye, scopes_sparse, seeded_lifelong, trace_reduce as tr  # noqa: E402

CELL = "keye-vl2-30b-a3b-ep8.train-lifelong-histories"
DEVICE = "/device:TPU:0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "configs", "keye-vl2-30b-a3b-ep8.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")) as f:
    WORKLOAD = json.load(f)
NEW_READERS = ["sparse_index_ms", "sparse_select_ms", "sparse_selected_share",
               "sparse_attention_mxu_share", "sparse_attention_hbm_share", "moe_route_ms",
               "moe_experts_ms", "moe_experts_mxu_share", "moe_held_share",
               "moe_load_max_over_mean"]
APPENDED = ["device_idle_share.train", "jit_trace_lower_s", "jit_compile_or_load_s",
            "jit_cache_misses", "seq_step_busy_ms", "seq_layers_ms", "seq_attention_ms",
            "seq_exit_ms", "seq_optimizer_ms", "seq_scope_coverage", "seq_step_mfu",
            "seq_slot_fill"]


def _reader(name):
    from run import load_module

    return load_module("layer_metrics", name)


# ---- the controls ------------------------------------------------------------

JUDGED = (["loss_abs_err", "ce_abs_err", "aux_loss_abs_err"]
          + [f"grad_{t}_rel_err" for t in (
              "wq_first", "wk_first", "router_first", "router_last", "w_down_first",
              "w_down_last", "final_norm", "head_rows")]
          + ["adam_update_rel_err", "index_score_rel_err", "select_overlap_shortfall"])


def test_the_three_controls_read_not_correct_and_the_run_itself_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse", "1",
         "--control", "1"], capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    controls = {line["control"]: line for line in lines if "control" in line}
    assert list(controls) == ["bfloat16", "window", "unrenormalised"]
    assert not any(line["correct"] for line in controls.values())
    failed = {name: {c["name"].removeprefix("seeded_") for c in line["checks"] if not c["ok"]}
              for name, line in controls.items()}
    # the precision below fails by the loss; a window in place of the indexer's
    # choice by the selection itself (and everything after it); gates left
    # unnormalised by the experts' and the router's gradients
    assert "loss_abs_err" in failed["bfloat16"], controls["bfloat16"]
    assert "select_overlap_shortfall" in failed["window"]
    assert {"grad_w_down_first_rel_err", "grad_w_down_last_rel_err",
            "grad_router_first_rel_err"} <= failed["unrenormalised"]
    assert "select_overlap_shortfall" not in failed["unrenormalised"]
    assert lines[-1]["correct"] is True
    names = [line["check"] for line in lines if "check" in line]
    assert names == (["seeded_" + n for n in JUDGED] + JUDGED
                     + ["moe_dropped", "nonfinite_values", "compilations_in_window"])
    said = next(line for line in lines if "step_counts" in line)
    assert said["sparse_selected_share"] == pytest.approx(
        100 * (32 * 33 / 2 + 96 * 32) / (128 * 129 / 2))       # T 128, topk 32: exact
    assert 0 < said["moe_held_share"] < 100 and said["moe_load_max_over_mean"] >= 1


def test_every_limit_of_the_cell_is_set():
    for where in (WORKLOAD["traffic"]["correct"], WORKLOAD["traffic"]["rehearsal"]["correct"]):
        for state in ("seeded", "trained"):
            limits = where[state]
            assert sorted(limits["grad_rel_err_limits"]) == sorted(
                n.removeprefix("grad_").removesuffix("_rel_err") for n in JUDGED
                if n.startswith("grad_"))
            for name in JUDGED:
                if not name.startswith("grad_"):
                    assert 0 < limits[name + "_limit"] < 1, (state, name)


# ---- the configuration ---------------------------------------------------------

def test_every_published_number_is_in_the_file_and_three_keys_are_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if CONFIG.get(k) != v)
    assert differ == sorted(CONFIG["reduced"]) == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"],
            CONFIG["vocab_size"]) == (6, 16, 151936 // 8)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]


def test_the_engine_parameters_are_the_published_widths_and_the_stated_count():
    from drivers import seq_sparse_train
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import sparse_moe
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    params = seq_sparse_train._algorithm_params(CONFIG, {})
    config = SASRecAlgorithm(Params(params))._config(CONFIG["vocab_size"] - 1, 8192)
    assert sparse_moe.count_params(config) == CONFIG["parameters"]["total"] == 659_187_712
    shapes = seeded_lifelong.param_shapes(
        CONFIG["vocab_size"], 2048, 32, 4, 128, 768, 128, 16, 6, 16, 64)
    assert shapes == sparse_moe.param_shapes(config)
    fixed = sum(int(np.prod(s)) for s in shapes["indexer"].values())
    assert CONFIG["parameters"]["trained"] == 659_187_712 - fixed
    wrong = json.loads(json.dumps(CONFIG))
    wrong["engine"]["algorithms"][0]["params"]["numKvHeads"] = 8
    with pytest.raises(ValueError, match="numKvHeads"):
        seq_sparse_train._algorithm_params(wrong, {})
    traffic = WORKLOAD["traffic"]
    assert (traffic["max_len"], traffic["users_per_step"], traffic["warm_steps"],
            traffic["trace_seconds"], traffic["kind"]) == (8192, 2, 1, 5, "optimizer_steps")


def test_every_history_fills_its_row_and_the_seed_relabels_the_items():
    data = {**CONFIG["data"], "min_events": 64, "mean_events": 90}
    a = seeded_lifelong.make_histories(data, 200, 500, seed=1)
    b = seeded_lifelong.make_histories(data, 200, 500, seed=2147483659)
    assert min(len(h) for h in a) >= 64 and min(len(h) for h in b) >= 64
    assert np.mean([len(h) for h in a]) == pytest.approx(90, rel=0.1)
    every = np.concatenate(a)
    assert every.min() >= 0 and every.max() < 500
    counts = np.sort(np.bincount(every, minlength=500))[::-1]
    assert counts[0] > 3 * counts[250]                     # heavy-tailed popularity
    assert np.argmax(np.bincount(every, minlength=500)) != np.argmax(
        np.bincount(np.concatenate(b), minlength=500))
    queries = seeded_lifelong.probe_queries(8192, 16, 7)
    assert len(queries) == 16 and queries[-1] == 8191 and len(set(queries.tolist())) == 16


# ---- the counts ---------------------------------------------------------------

def test_step_model_flops_against_a_hand_count():
    dims = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_experts": 16, "moe_intermediate_size": 6,
            "num_hidden_layers": 3,
            "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 5}}
    tokens, targets, selected, causal, held = 10.0, 9.0, 100.0, 165.0, 24.0
    trained = (3 * 10 * 2 * 8 * (2 * 16 + 2 * 8 + 16) + 100 * 4 * 16
               + 24 * 6 * 8 * 6 + 9 * 2 * 8 * 50)
    indexer = 3 * 10 * 2 * 8 * (10 + 5 + 2) + 165 * 2 * 10
    assert counts_keye.step_model_flops(
        tokens, targets, selected, causal, held, dims, 50) == 3 * trained + indexer
    # what was not selected and what is not held costs nothing
    assert counts_keye.step_model_flops(tokens, targets, selected, causal, held, dims, 50) < \
        counts_keye.step_model_flops(tokens, targets, causal, causal, 8 * 30, dims, 50)


def test_attention_and_expert_counts_are_of_selected_pairs_and_held_assignments():
    dims = CONFIG
    pairs = 2 * (2048 * 2049 / 2 + 6144 * 2048)              # two full rows of 8,192, a layer
    flops = counts_keye.attention_call_flops(pairs, dims)
    assert flops["forward"] == 2 * 2 * pairs * 32 * 128
    assert flops["backward"] == flops["dq"] + flops["dkv"] == 7 * 2 * pairs * 32 * 128
    moved = counts_keye.attention_call_bytes(16384, pairs, dims)
    wide, narrow, row = 16384 * 4096 * 2, 16384 * 512 * 2, 16384 * 32 * 4
    assert moved["forward"] == 2 * wide + 2 * narrow + row + 2 * pairs
    assert moved["backward"] == 5 * wide + 6 * narrow + 4 * row + 4 * pairs
    assert counts_keye.experts_flops(98304, dims) == 3 * 98304 * 6 * 2048 * 768


# ---- the scopes' reader ---------------------------------------------------------

FWD = "jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name,stage,kind", [
    (FWD + "/attention/index/pallas_call:", "index", None),
    (FWD + "/attention/index/dot_general:", "index", None),
    (FWD + "/attention/select/pallas_call:", "select", None),
    (FWD + "/attention/kernel/pallas_call:", "kernel", "forward"),
    (BWD + "/rematted_computation/attention/kernel/pallas_call:", "kernel", "forward"),
    (BWD + "/attention/kernel/pallas_call:", "kernel", "backward"),
    (BWD + "/attention/kernel/transpose:", "kernel", None),
    (FWD + "/attention/dot_general:", None, None),
    (FWD + "/moe/route/top_k:", "route", None),
    (BWD + "/moe/experts/while/body/closed_call/checkpoint/rematted_computation/sort:",
     "experts", None),
    ("jit(train_step)/jvp(seq.pass1)/exit/dot_general:", None, None),
    ("jit(iteration)/als.user_half_step/bucket0/select/x:", None, None),
    ("ragged-dot-none", None, None),
])
def test_parse_stage_and_kernel_kind(op_name, stage, kind):
    assert scopes_sparse.parse_stage(op_name) == stage
    assert scopes_sparse.kernel_kind(op_name) == kind


def test_the_grouped_matmuls_are_taken_by_their_own_name():
    assert scopes_sparse.stage_of("%ragged-dot-none.3", "ragged-dot-none") == "experts"
    assert scopes_sparse.stage_of("ragged-dot-none.3 tpu_custom_call", "") == "experts"
    assert scopes_sparse.stage_of("fusion.3", "") is None
    assert scopes_sparse.stage_of("fusion.3", FWD + "/moe/route/top_k:") == "route"


def _run_on(monkeypatch, ops, names, window=(0.0, 10.0), **run):
    planes = {DEVICE: {tr.OP_LINE: list(ops)},
              "/host:CPU": {"main": [(tr.WINDOW_NAME, *window)]}}
    reduced = scopes_sparse.reduce_stages(planes, {DEVICE: names})
    monkeypatch.setattr(scopes_sparse, "of_run", lambda r: reduced if r.get("trace") else None)
    return {"trace": {"busy_s": 10.0, "window_s": 10.0}, "steps": 2,
            "device_kind": "TPU v5 lite", "dims": CONFIG, **run}, reduced


def test_the_readers_on_hand_made_intervals(monkeypatch):
    names = {
        "index.1 tpu_custom_call": FWD + "/attention/index/pallas_call:",
        "fusion.1": FWD + "/attention/index/dot_general:",
        "select.1 tpu_custom_call": FWD + "/attention/select/pallas_call:",
        "attn.1 tpu_custom_call": FWD + "/attention/kernel/pallas_call:",
        "attn.2 tpu_custom_call": BWD + "/attention/kernel/pallas_call:",
        "attn.3 tpu_custom_call": BWD + "/attention/kernel/pallas_call:",
        "fusion.2": FWD + "/moe/route/top_k:",
        "sort.1": FWD + "/moe/experts/while/body/closed_call/sort:",
        "ragged-dot-none.1 tpu_custom_call": "",
        "fusion.3": "jit(train_step)/seq.optimizer/add:",
    }
    ops = [("fusion.1", 0.0, 0.5), ("index.1 tpu_custom_call", 0.5, 1.0),
           ("select.1 tpu_custom_call", 1.0, 2.0), ("attn.1 tpu_custom_call", 2.0, 4.0),
           ("fusion.2", 4.0, 4.25), ("sort.1", 4.25, 4.5),
           ("ragged-dot-none.1 tpu_custom_call", 4.5, 5.0),
           ("attn.2 tpu_custom_call", 5.0, 6.0), ("attn.3 tpu_custom_call", 6.0, 8.0),
           ("fusion.3", 8.0, 9.0), ("attn.1 tpu_custom_call", 11.0, 12.0)]   # past the window
    pairs = 6 * 2 * (2048 * 2049 / 2 + 6144 * 2048)
    counts = {"selected_pairs": pairs, "causal_pairs": 6 * 8192 * 8193.0, "tokens": 16384.0,
              "moe_assignments": 786432.0, "moe_held_assignments": 98304.0,
              "moe_held_load_max": 1500.0, "moe_held_load_mean": 1024.0}
    run, reduced = _run_on(monkeypatch, ops, names, step_counts=counts)
    assert reduced["stages"] == pytest.approx(
        {"index": 1.0, "select": 1.0, "kernel": 5.0, "route": 0.25, "experts": 0.75})
    assert reduced["kernel_calls"] == {"forward": 1, "backward": 2}
    assert _reader("sparse_index_ms").read(run) == pytest.approx(500.0)
    assert _reader("sparse_select_ms").read(run) == pytest.approx(500.0)
    assert _reader("moe_route_ms").read(run) == pytest.approx(125.0)
    assert _reader("moe_experts_ms").read(run) == pytest.approx(375.0)
    assert _reader("sparse_selected_share").read(run) == pytest.approx(43.75, abs=0.01)
    assert _reader("moe_held_share").read(run) == pytest.approx(12.5)
    assert _reader("moe_load_max_over_mean").read(run) == pytest.approx(1500 / 1024)
    per_call = counts_keye.attention_call_flops(pairs / 6, CONFIG)
    want = 100 * ((per_call["forward"] + per_call["backward"]) / 197e12) / 5.0
    assert _reader("sparse_attention_mxu_share").read(run) == pytest.approx(want)
    moved = counts_keye.attention_call_bytes(16384.0, pairs / 6, CONFIG)
    assert _reader("sparse_attention_hbm_share").read(run) == pytest.approx(
        100 * ((moved["forward"] + moved["backward"]) / 819e9) / 5.0)
    assert _reader("moe_experts_mxu_share").read(run) == pytest.approx(
        100 * (counts_keye.experts_flops(98304.0, CONFIG) / 197e12) / 0.375)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """An untraced run, and a program that names none of these scopes and
    returns none of these counts (the parent's): None, no raise."""
    reader = _reader(name)
    assert reader.read({"end_to_end": {}, "setup": {}}) is None
    bare = {"trace": {"busy_s": 0.0, "window_s": 1.0, "device_ops": [], "idle_gaps": []},
            "iterations": 3, "device_kind": "TPU v5 lite"}
    assert reader.read(bare) is None


def test_the_new_readers_are_listed_for_this_cell_alone_and_the_old_ones_gained_it():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_iters_per_s"
    assert [m["name"] for m in MANIFEST["per_layer"]][-10:] == NEW_READERS
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL
    train = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_iters_per_s")
    assert train["workloads"][-1] == CELL and train["bound"] == 0.01
    for name in ("seq_attention_mxu_share", "seq_attention_tile_share"):
        assert CELL not in by_name[name]["workloads"]       # the flash kernels' own
    entry = MANIFEST["workloads"][-1]
    assert (entry["name"], entry["chips"], entry["traffic"]) == (CELL, 1, "train-lifelong-histories")
