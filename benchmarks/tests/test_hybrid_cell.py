"""The hybrid decoder's lifelong-histories cell: its four controls through the
rehearsal, its counts against a hand count, its scopes' reader on hand-made
intervals, every new reader on a run that lacks its source, the configuration
against the published keys. (Its rehearsal is ``test_rehearsal.py``'s, which
walks every file under ``workloads/``.)"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from benchmarks import counts_qwen3next, scopes_hybrid, trace_reduce as tr  # noqa: E402

CELL = "qwen3-next-80b-a3b-ep16.train-lifelong-histories"
DEVICE = "/device:TPU:0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b-ep16.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")) as f:
    WORKLOAD = json.load(f)
NEW_READERS = ["linattn_ms", "linattn_delta_ms", "linattn_proj_ms", "linattn_conv_gates_ms",
               "linattn_delta_mxu_share", "linattn_delta_hbm_share", "moe_shared_ms"]


def _reader(name):
    from run import load_module

    return load_module("layer_metrics", name)


# ---- the controls ------------------------------------------------------------

TENSORS = ("w_qkvz_first", "conv_first", "a_log_first", "dt_bias_first", "wq_full",
           "router_first", "router_last", "w_down_first", "shared_gate_last", "final_norm",
           "head_rows")
JUDGED = (["loss_abs_err", "ce_abs_err", "aux_loss_abs_err"]
          + [f"grad_{t}_rel_err" for t in TENSORS] + ["adam_update_rel_err"])


def test_the_four_controls_read_not_correct_and_the_run_itself_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse", "1",
         "--control", "1"], capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    controls = {line["control"]: line for line in lines if "control" in line}
    assert list(controls) == ["bfloat16", "no_decay", "no_delta", "ungated_shared"]
    assert not any(line["correct"] for line in controls.values())
    failed = {name: {c["name"].removeprefix("seeded_") for c in line["checks"] if not c["ok"]}
              for name, line in controls.items()}
    # the precision below fails by the loss; a rule without its decay or
    # without its correction by the linear layer's own gradients; an ungated
    # shared expert by its gate's gradient (zero there) and the loss
    assert "loss_abs_err" in failed["bfloat16"], controls["bfloat16"]
    assert "grad_w_qkvz_first_rel_err" in failed["no_decay"]
    assert "grad_w_qkvz_first_rel_err" in failed["no_delta"]
    assert "grad_shared_gate_last_rel_err" in failed["ungated_shared"]
    assert lines[-1]["correct"] is True
    names = [line["check"] for line in lines if "check" in line]
    assert names == (["seeded_" + n for n in JUDGED] + JUDGED
                     + ["moe_dropped", "nonfinite_values", "compilations_in_window"])
    said = next(line for line in lines if "step_counts" in line)
    assert said["step_counts"]["linear_layers"] == 3
    assert said["step_counts"]["causal_pairs"] == 2 * 128 * 129 / 2        # two full rows
    assert 0 < said["moe_held_share"] < 100 and said["moe_load_max_over_mean"] >= 1


def test_every_limit_of_the_cell_is_set():
    for where in (WORKLOAD["traffic"]["correct"], WORKLOAD["traffic"]["rehearsal"]["correct"]):
        for state in ("seeded", "trained"):
            limits = where[state]
            assert sorted(limits["grad_rel_err_limits"]) == sorted(TENSORS)
            for name in JUDGED:
                if not name.startswith("grad_"):
                    assert 0 < limits[name + "_limit"] < 1, (state, name)


# ---- the configuration ---------------------------------------------------------

def test_every_published_number_is_in_the_file_and_three_keys_are_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if CONFIG.get(k) != v)
    # the source has no key for the experts a chip holds: the file adds it
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 32, 512, 151936 // 8)
    assert CONFIG["published"]["num_local_experts"] == row["config"]["num_experts"] == 512
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]


def test_the_engine_parameters_are_the_published_widths_and_the_stated_count():
    from benchmarks import seeded_hybrid
    from drivers import seq_hybrid_train
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import hybrid
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    params = seq_hybrid_train._algorithm_params(CONFIG, {})
    config = SASRecAlgorithm(Params(params))._config(CONFIG["vocab_size"] - 1, 8192)
    assert hybrid.count_params(config) == CONFIG["parameters"]["total"] == 625_667_136
    assert config.experts_held == (0, 32) and config.learning_rate == 1e-5
    assert (config.periods, config.linear_layers, config.rotary_dim) == (1, 3, 64)
    # the generator's shapes are the program's, at the cell's size and at the rehearsal's
    assert seeded_hybrid.param_shapes(
        CONFIG, CONFIG["vocab_size"], 32) == hybrid.param_shapes(config)
    cut = WORKLOAD["traffic"]["rehearsal"]
    small = SASRecAlgorithm(Params(seq_hybrid_train._algorithm_params(CONFIG, cut)))._config(
        cut["vocab_size"] - 1, cut["max_len"])
    assert (small.hidden_size, small.held, small.num_experts) == (64, 4, 16)
    with pytest.raises(ValueError, match="hiddenSize"):
        seq_hybrid_train._algorithm_params({**CONFIG, "hidden_size": 4096}, {})
    traffic, keye = WORKLOAD["traffic"], json.load(open(os.path.join(
        ROOT, "benchmarks", "workloads", "keye-vl2-30b-a3b-ep8.train-lifelong-histories.json")))
    for key in ("kind", "max_len", "users_per_step", "warm_steps", "trace_seconds"):
        assert traffic[key] == keye["traffic"][key], key      # the same traffic, another backbone
    assert CONFIG["data"] == json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "keye-vl2-30b-a3b-ep8.json")))["data"]


# ---- the counts ---------------------------------------------------------------

DIMS = {"hidden_size": 8, "num_hidden_layers": 4, "full_attention_interval": 4,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 3,
        "linear_value_head_dim": 5, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 6, "num_experts": 16, "moe_intermediate_size": 7,
        "shared_expert_intermediate_size": 9}


def test_step_model_flops_against_a_hand_count():
    tokens, targets, causal, held = 10.0, 9.0, 55.0, 24.0
    rule = 3 * 10 * 3 * 4 * 6 * 3 * 5
    assert counts_qwen3next.delta_rule_flops(tokens, 3, DIMS) == rule
    linear = 2 * 8 * (2 * 2 * 3 + 2 * 4 * 5 + 2 * 4) + 2 * 4 * 5 * 8
    full = 2 * 8 * (2 * 4 * 6 + 2 * 2 * 6) + 2 * 4 * 6 * 8
    every = 2 * 8 * 16 + 6 * 8 * 9
    forward = (10 * (3 * linear + full + 4 * every) + 55 * 4 * 4 * 6
               + 24 * 6 * 8 * 7 + 9 * 2 * 8 * 50)
    assert counts_qwen3next.step_model_flops(
        tokens, targets, causal, held, DIMS, 50) == 3 * forward + rule
    # the rule's need is the recurrence's: no chunk size is in it
    assert counts_qwen3next.delta_rule_flops(16384.0, 3, CONFIG) == \
        3 * 16384 * 3 * 32 * 6 * 128 * 128


def test_the_rules_bytes_are_once_a_token():
    inputs = (2 * 2 * 3 + 4 * 5) * 2 + 2 * 4 * 4
    output = 4 * 5 * 4
    assert counts_qwen3next.delta_rule_bytes(10.0, 3, DIMS) == 10 * 3 * (
        (inputs + output) + (inputs + output + inputs))


# ---- the scopes' reader ---------------------------------------------------------

FWD = "jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/while/body/closed_call"
BWD = ("jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/closed_call/while/body/"
       "closed_call/checkpoint")


@pytest.mark.parametrize("op_name,place", [
    (FWD + "/linear_attention/delta/pallas_call:", ("linear", "delta")),
    (BWD + "/rematted_computation/linear_attention/delta/dot_general:", ("linear", "delta")),
    (BWD + "/linear_attention/conv/checkpoint/rematted_computation/mul:", ("linear", "conv")),
    (FWD + "/linear_attention/qkv/dot_general:", ("linear", "qkv")),
    (FWD + "/linear_attention/gated_norm/mul:", ("linear", "gated_norm")),
    (FWD + "/linear_attention/add:", ("linear", None)),
    (FWD + "/moe/shared/dot_general:", ("shared", None)),
    (FWD + "/moe/route/top_k:", None),
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/attention/kernel/pallas_call:",
     None),
    ("jit(iteration)/als.user_half_step/bucket0/gram/conv/x:", None),
    ("", None),
])
def test_place_of(op_name, place):
    assert scopes_hybrid.place_of(op_name) == place


def test_the_readers_on_hand_made_intervals(monkeypatch):
    names = {
        "fusion.1": FWD + "/linear_attention/qkv/dot_general:",
        "fusion.2": FWD + "/linear_attention/conv/mul:",
        "delta.1 tpu_custom_call": FWD + "/linear_attention/delta/pallas_call:",
        "fusion.3": BWD + "/linear_attention/delta/dot_general:",
        "fusion.4": FWD + "/linear_attention/gated_norm/mul:",
        "fusion.5": FWD + "/linear_attention/out/dot_general:",
        "fusion.6": FWD + "/linear_attention/norm/mul:",
        "fusion.7": FWD + "/moe/shared/dot_general:",
        "fusion.8": FWD + "/moe/route/top_k:",
    }
    ops = [("fusion.6", 0.0, 0.25), ("fusion.1", 0.25, 1.25), ("fusion.2", 1.25, 1.75),
           ("delta.1 tpu_custom_call", 1.75, 3.75), ("fusion.4", 3.75, 4.0),
           ("fusion.5", 4.0, 4.5), ("fusion.7", 4.5, 5.0), ("fusion.8", 5.0, 6.0),
           ("fusion.3", 6.0, 8.0), ("delta.1 tpu_custom_call", 11.0, 12.0)]  # past the window
    planes = {DEVICE: {tr.OP_LINE: ops}, "/host:CPU": {"main": [(tr.WINDOW_NAME, 0.0, 10.0)]}}
    reduced = scopes_hybrid.reduce_places(planes, {DEVICE: names})
    monkeypatch.setattr(scopes_hybrid, "of_run", lambda r: reduced if r.get("trace") else None)
    counts = {"tokens": 16384.0, "linear_layers": 3}
    run = {"trace": {"busy_s": 10.0, "window_s": 10.0}, "steps": 2,
           "device_kind": "TPU v5 lite", "dims": CONFIG, "step_counts": counts}
    assert reduced["linear"] == pytest.approx(6.5) and reduced["shared"] == pytest.approx(0.5)
    assert reduced["leaves"] == pytest.approx(
        {"norm": 0.25, "qkv": 1.0, "conv": 0.5, "delta": 4.0, "gated_norm": 0.25, "out": 0.5})
    assert _reader("linattn_ms").read(run) == pytest.approx(3250.0)
    assert _reader("linattn_delta_ms").read(run) == pytest.approx(2000.0)
    assert _reader("linattn_proj_ms").read(run) == pytest.approx(750.0)
    assert _reader("linattn_conv_gates_ms").read(run) == pytest.approx(375.0)
    assert _reader("moe_shared_ms").read(run) == pytest.approx(250.0)
    flops = counts_qwen3next.delta_rule_flops(16384.0, 3, CONFIG)
    assert _reader("linattn_delta_mxu_share").read(run) == pytest.approx(
        100 * (flops / 197e12) / 2.0)
    moved = counts_qwen3next.delta_rule_bytes(16384.0, 3, CONFIG)
    assert _reader("linattn_delta_hbm_share").read(run) == pytest.approx(
        100 * (moved / 819e9) / 2.0)


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
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [CELL])}
    assert set(NEW_READERS) <= listed
    assert {"device_idle_share.train", "seq_step_busy_ms", "seq_step_mfu", "seq_layers_ms",
            "seq_attention_ms", "moe_experts_ms", "moe_route_ms", "seq_slot_fill"} <= listed
    # the flash kernels', the indexer's and the sparse programs' own: not this cell's
    assert not listed & {"seq_attention_mxu_share", "seq_attention_tile_share",
                         "sparse_index_ms", "sparse_select_ms", "sparse_selected_share",
                         "sparse_attention_mxu_share", "sparse_attention_hbm_share"}
    train = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_iters_per_s")
    assert CELL in train["workloads"] and train["bound"] == 0.01
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"], entry["traffic"]) == (
        "qwen3-next-80b-a3b-ep16", 1, "train-lifelong-histories")
