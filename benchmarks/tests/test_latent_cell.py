"""The latent-attention decoder's lifelong-histories cell: its six controls
through the rehearsal, its counts against a hand count, its scopes' reader on
hand-made intervals, every new reader on a run that lacks its source, the
configuration against the published keys. (Its rehearsal is
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

from benchmarks import counts_joyai, scopes_latent, scopes_leaf, trace_reduce as tr  # noqa: E402

CELL = "joyai-llm-flash-ep16.train-lifelong-histories"
DEVICE = "/device:TPU:0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "configs", "joyai-llm-flash-ep16.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")) as f:
    WORKLOAD = json.load(f)
NEW_READERS = ["mla_attention_mxu_share", "mla_attention_hbm_share", "mla_latent_ms", "mtp_ms"]


def _reader(name):
    from run import load_module

    return load_module("layer_metrics", name)


# ---- the controls ------------------------------------------------------------

TENSORS = ("w_qa_first", "w_kvb_first", "w_qb_last", "w_kva_last", "w_kr_last", "dense_down",
           "router_first", "router_last", "w_down_first", "shared_down_last", "mtp_merge",
           "mtp_router", "final_norm", "head_rows")
BIAS = ["bias_unequal_beyond_one", "bias_flip_load_distance", "bias_step_abs_err"]
JUDGED = (["loss_abs_err", "ce_abs_err", "mtp_ce_abs_err", "balance_abs_err"]
          + [f"grad_{t}_rel_err" for t in TENSORS] + ["adam_update_rel_err"] + BIAS)


def test_the_six_controls_read_not_correct_and_the_run_itself_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse", "1",
         "--control", "1"], capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    controls = {line["control"]: line for line in lines if "control" in line}
    assert list(controls) == ["bfloat16", "no_rope_key", "softmax_router", "no_bias",
                              "unscaled", "no_mtp"]
    assert not any(line["correct"] for line in controls.values())
    failed = {name: {c["name"].removeprefix("seeded_") for c in line["checks"] if not c["ok"]}
              for name, line in controls.items()}
    # the precision below fails by the loss; scores without the rotary key by
    # the only gradient that reaches it (zero in the reference) and the queries'; another router, a selection without
    # the bias and unscaled gates by the experts' gradients; the bias's move
    # follows a selection that is not the program's; a module that weighs
    # nothing by the loss and by its own tensors' gradients (zero there)
    assert "loss_abs_err" in failed["bfloat16"], controls["bfloat16"]
    assert {"grad_w_kr_last_rel_err", "grad_w_qb_last_rel_err"} <= failed["no_rope_key"]
    assert {"grad_w_down_first_rel_err", "grad_router_first_rel_err",
            "balance_abs_err"} <= failed["softmax_router"]
    assert {"grad_w_down_first_rel_err", "bias_flip_load_distance"} <= failed["no_bias"]
    assert "grad_w_down_first_rel_err" in failed["unscaled"]
    assert {"loss_abs_err", "grad_mtp_merge_rel_err", "grad_mtp_router_rel_err"} <= failed["no_mtp"]
    assert lines[-1]["correct"] is True
    names = [line["check"] for line in lines if "check" in line]
    assert names == (["seeded_" + n for n in JUDGED] + JUDGED
                     + ["moe_dropped", "nonfinite_values", "compilations_in_window"])
    said = next(line for line in lines if "step_counts" in line)
    counts = said["step_counts"]
    assert counts["tokens"] == 2 * 128 and counts["causal_pairs"] == 2 * 128 * 129 / 2
    assert counts["mtp_tokens"] == counts["targets"] == 2 * 127
    assert counts["mtp_targets"] == 2 * 126 and counts["mtp_causal_pairs"] == 2 * 127 * 128 / 2
    # K x (two expert layers' tokens + the module's)
    assert counts["moe_assignments"] == 4 * (2 * 256 + 254)
    assert 0 < said["moe_held_share"] < 100 and said["moe_load_max_over_mean"] >= 1
    decided = [line["bias_decided_share"] for line in lines if "bias_decided_share" in line]
    assert len(decided) == 2 and all(0.05 < share < 0.6 for share in decided)


def test_every_limit_of_the_cell_is_set():
    for where in (WORKLOAD["traffic"]["correct"], WORKLOAD["traffic"]["rehearsal"]["correct"]):
        for state in ("seeded", "trained"):
            limits = where[state]
            assert sorted(limits["grad_rel_err_limits"]) == sorted(TENSORS)
            for name in JUDGED:
                if not name.startswith("grad_"):
                    assert 0 <= limits[name + "_limit"] < 64, (state, name)
            assert limits["bias_step_abs_err_limit"] <= 1e-6
            assert limits["loss_abs_err_limit"] < 1e-2


def test_the_bias_rows_tell_a_load_at_the_mean_from_a_rule_that_is_wrong():
    from drivers import seq_latent_train

    load = np.array([[10.0, 14.0, 12.0, 12.5, 11.5, 12.0]])        # mean 12
    want = 1e-3 * np.sign(load.mean(axis=-1, keepdims=True) - load)
    rows = dict(seq_latent_train.bias_rows(want, want, load, 1e-3))
    assert rows == {"bias_unequal_beyond_one": 0.0, "bias_flip_load_distance": 0.0,
                    "bias_step_abs_err": 0.0}
    near = want.copy()
    near[0, 3] = 1e-3                       # an expert half an assignment over the mean
    rows = dict(seq_latent_train.bias_rows(near, want, load, 1e-3))
    assert rows["bias_unequal_beyond_one"] == 0 and rows["bias_flip_load_distance"] == 0.5
    rows = dict(seq_latent_train.bias_rows(-want, want, load, 1e-3))   # the sign the other way
    assert rows["bias_unequal_beyond_one"] == 2 and rows["bias_flip_load_distance"] == 2.0
    rows = dict(seq_latent_train.bias_rows(1.2 * want, want, load, 1e-3))   # another rate
    assert rows["bias_unequal_beyond_one"] == 0 and rows["bias_step_abs_err"] == pytest.approx(2e-4)


# ---- the configuration ---------------------------------------------------------

def test_every_published_number_is_in_the_file_and_three_keys_are_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if CONFIG.get(k) != v)
    # the source has no key for the experts a chip holds: the file adds it
    assert differ == ["num_hidden_layers", "vocab_size"]
    assert sorted(CONFIG["reduced"]) == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"], CONFIG["num_nextn_predict_layers"]) == (
                5, 16, 256, 129280 // 8, 1)
    assert CONFIG["published"]["num_local_experts"] == row["config"]["n_routed_experts"] == 256
    assert CONFIG["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] == 40
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert len(entry["why"]) <= 200


def test_the_engine_parameters_are_the_published_widths_and_the_stated_count():
    from benchmarks import seeded_latent
    from drivers import seq_latent_train
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import latent_moe
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    params = seq_latent_train._algorithm_params(CONFIG, {})
    config = SASRecAlgorithm(Params(params))._config(CONFIG["vocab_size"] - 1, 8192)
    assert latent_moe.count_params(config) == CONFIG["parameters"]["total"] == 680_439_808
    assert config.experts_held == (0, 16) and config.learning_rate == 1e-5
    assert (config.dense_layers, config.expert_layers, config.mtp_depth, config.score_dim,
            config.value_dim) == (1, 4, 1, 192, 128)
    assert (config.mtp_coef, config.balance_coef, config.bias_rate, config.routed_scale) == (
        0.3, 1e-4, 1e-3, 2.5)
    # the generator's shapes are the program's, at the cell's size and at the rehearsal's
    assert seeded_latent.param_shapes(
        CONFIG, CONFIG["vocab_size"], 16) == latent_moe.param_shapes(config)
    cut = WORKLOAD["traffic"]["rehearsal"]
    small = SASRecAlgorithm(Params(seq_latent_train._algorithm_params(CONFIG, cut)))._config(
        cut["vocab_size"] - 1, cut["max_len"])
    assert (small.hidden_size, small.held, small.num_experts, small.shared_expert_dim) == (
        64, 4, 16, 32)
    with pytest.raises(ValueError, match="hiddenSize"):
        seq_latent_train._algorithm_params({**CONFIG, "hidden_size": 4096}, {})
    with pytest.raises(ValueError, match="qk_head_dim"):
        seq_latent_train._algorithm_params({**CONFIG, "qk_head_dim": 128}, {})
    traffic, keye = WORKLOAD["traffic"], json.load(open(os.path.join(
        ROOT, "benchmarks", "workloads", "keye-vl2-30b-a3b-ep8.train-lifelong-histories.json")))
    for key in ("kind", "max_len", "users_per_step", "warm_steps", "trace_seconds"):
        assert traffic[key] == keye["traffic"][key], key      # the same traffic, another backbone
    theirs = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "keye-vl2-30b-a3b-ep8.json")))["data"]
    mine = CONFIG["data"]
    assert mine["items"] == CONFIG["vocab_size"] - 1 == 16159
    for key in ("users", "min_events", "mean_events", "popularity", "published"):
        assert mine[key] == theirs[key], key


def test_the_seeded_bias_is_drawn_and_the_norms_are_about_one():
    from benchmarks import seeded_latent

    cut = {**CONFIG, **{k: v for k, v in WORKLOAD["traffic"]["rehearsal"].items() if k in CONFIG}}
    drawn = seeded_latent.make_params(seeded_latent.param_shapes(cut, 512, 4), 3, 80)
    again = seeded_latent.make_params(seeded_latent.param_shapes(cut, 512, 4), 3, 80)
    bias = drawn["layers"]["router_bias"]
    assert bias.shape == (2, 16) and np.array_equal(bias, again["layers"]["router_bias"])
    assert 0.005 < bias.std() < 0.04 and drawn["mtp"]["layer"]["router_bias"].any()
    assert abs(drawn["layers"]["q_norm"].mean() - 1) < 0.05
    assert abs(drawn["dense"]["w_down"].std() / (0.02 / np.sqrt(80)) - 1) < 0.05
    assert abs(drawn["dense"]["w_qa"].std() / 0.02 - 1) < 0.05 and abs(drawn["embed"].std() - 1) < 0.05


# ---- the counts ---------------------------------------------------------------

DIMS = {"hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "q_lora_rank": 6, "kv_lora_rank": 5, "qk_nope_head_dim": 3,
        "qk_rope_head_dim": 2, "qk_head_dim": 5, "v_head_dim": 7, "intermediate_size": 11,
        "moe_intermediate_size": 9, "n_routed_experts": 16, "n_shared_experts": 1,
        "num_nextn_predict_layers": 1}
STEP = {"tokens": 10.0, "targets": 9.0, "mtp_tokens": 9.0, "mtp_targets": 8.0,
        "causal_pairs": 55.0, "mtp_causal_pairs": 45.0, "moe_held_assignments": 24.0}


def test_step_model_flops_against_a_hand_count():
    pairs = 3 * 55 + 45
    attention = 3 * pairs * 2 * 4 * (5 + 7)
    assert counts_joyai.latent_attention_flops(STEP, DIMS) == attention
    projections = 2 * (8 * 6 + 6 * 4 * 5 + 8 * (5 + 2) + 5 * 4 * (3 + 7) + 4 * 7 * 8)
    assert counts_joyai.projection_flops_a_token(DIMS) == projections
    routed = 2 * 8 * 16 + 6 * 8 * 9
    forward = ((3 * 10 + 9) * projections + 10 * 6 * 8 * 11 + (2 * 10 + 9) * routed
               + 24 * 6 * 8 * 9 + 9 * 4 * 8 * 8 + (9 + 8) * 2 * 8 * 50)
    assert counts_joyai.step_model_flops(STEP, DIMS, 50) == 3 * forward + attention
    # the attention's need is the equations': 192 + 128 a pair a head, six blocks
    full = {"tokens": 16384.0, "mtp_tokens": 16382.0, "causal_pairs": 2 * 8192 * 8193 / 2,
            "mtp_causal_pairs": 2 * 8191 * 8192 / 2}
    assert counts_joyai.latent_attention_flops(full, CONFIG) == 3 * (
        5 * full["causal_pairs"] + full["mtp_causal_pairs"]) * 2 * 32 * (192 + 128)


def test_the_attentions_bytes_hold_the_rotary_key_once_a_position():
    position = (4 * (5 + 3 + 7 + 7) + 2) * 2
    assert counts_joyai.latent_attention_bytes(STEP, DIMS) == 2 * (3 * 10 + 9) * position
    # at the cell's widths: 32 heads of q 192, k_nope 128, v 128, o 128, and 64 of k_r
    assert counts_joyai.latent_attention_bytes(
        {"tokens": 1.0, "mtp_tokens": 0.0, "causal_pairs": 1.0, "mtp_causal_pairs": 0.0},
        {**CONFIG, "num_hidden_layers": 1}) == 2 * (
            32 * 576 + 64) * 2


# ---- the scopes' reader ---------------------------------------------------------

FWD = "jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/checkpoint"
BWD = ("jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/closed_call/checkpoint/"
       "rematted_computation")
MTP = "jit(train_step)/transpose(jvp(seq.pass1))/mtp"


@pytest.mark.parametrize("op_name,places", [
    (FWD + "/attention/qkv/q_latent/dot_general:", ("q_latent",)),
    (BWD + "/attention/qkv/kv_latent/mul:", ("kv_latent",)),
    (FWD + "/attention/qkv/reshape:", ()),
    (FWD + "/attention/kernel/pallas_call:", ()),
    (MTP + "/layers/checkpoint/attention/qkv/q_latent/dot_general:", ("q_latent", "mtp")),
    (MTP + "/merge/checkpoint/dot_general:", ("mtp",)),
    (MTP + "/exit/while/body/checkpoint/dot_general:", ("mtp",)),
    (MTP + "/layers/checkpoint/moe/shared/dot_general:", ("mtp",)),
    ("jit(train_step)/seq.optimizer/bias/sign:", ()),
    ("jit(iteration)/als.user_half_step/bucket0/gram/q_latent/x:", ()),
    ("", ()),
])
def test_places_of(op_name, places):
    assert scopes_latent.places_of(op_name) == places


def test_the_readers_on_hand_made_intervals(monkeypatch):
    names = {
        "fusion.1": FWD + "/attention/qkv/q_latent/dot_general:",
        "fusion.2": BWD + "/attention/qkv/kv_latent/dot_general:",
        "kernel.1 tpu_custom_call": FWD + "/attention/kernel/pallas_call:",
        "fusion.3": FWD + "/attention/norm/mul:",
        "fusion.4": MTP + "/layers/checkpoint/attention/qkv/kv_latent/dot_general:",
        "kernel.2 tpu_custom_call": MTP + "/layers/checkpoint/attention/kernel/pallas_call:",
        "fusion.5": MTP + "/merge/checkpoint/dot_general:",
        "fusion.6": MTP + "/exit/while/body/checkpoint/dot_general:",
        "fusion.7": FWD + "/moe/route/top_k:",
    }
    ops = [("fusion.3", 0.0, 0.25), ("fusion.1", 0.25, 1.25), ("fusion.2", 1.25, 1.75),
           ("kernel.1 tpu_custom_call", 1.75, 3.75), ("fusion.7", 3.75, 4.0),
           ("fusion.5", 4.0, 4.5), ("fusion.4", 4.5, 5.0), ("kernel.2 tpu_custom_call", 5.0, 6.0),
           ("fusion.6", 6.0, 8.0), ("kernel.1 tpu_custom_call", 11.0, 12.0)]  # past the window
    planes = {DEVICE: {tr.OP_LINE: ops}, "/host:CPU": {"main": [(tr.WINDOW_NAME, 0.0, 10.0)]}}
    reduced = scopes_latent.reduce_places(planes, {DEVICE: names})
    assert reduced == pytest.approx({"q_latent": 1.0, "kv_latent": 1.0, "mtp": 4.0})
    monkeypatch.setattr(scopes_latent, "_reduced", lambda path, mtime: reduced)
    monkeypatch.setattr(scopes_latent.scopes, "newest_xplane", lambda: __file__)
    instructions = {DEVICE: {name: (op_name, "fusion") for name, op_name in names.items()}}
    leaves = scopes_leaf.reduce_leaves(planes, instructions)
    monkeypatch.setattr(scopes_leaf, "of_run", lambda r: leaves if r.get("trace") else None)
    step = {"tokens": 16384.0, "mtp_tokens": 16382.0, "causal_pairs": 2 * 8192 * 8193 / 2,
            "mtp_causal_pairs": 2 * 8191 * 8192 / 2}
    run = {"trace": {"busy_s": 10.0, "window_s": 10.0}, "steps": 2,
           "device_kind": "TPU v5 lite", "dims": CONFIG, "step_counts": step}
    assert _reader("mla_latent_ms").read(run) == pytest.approx(1000.0)
    assert _reader("mtp_ms").read(run) == pytest.approx(2000.0)
    flops = counts_joyai.latent_attention_flops(step, CONFIG)
    assert _reader("mla_attention_mxu_share").read(run) == pytest.approx(
        100 * (flops / 197e12) / 1.5)        # the programs: 2 s + 1 s in the window, two steps
    moved = counts_joyai.latent_attention_bytes(step, CONFIG)
    assert _reader("mla_attention_hbm_share").read(run) == pytest.approx(
        100 * (moved / 819e9) / 1.5)
    # another decoder's run (its counts, its dims): the shares give nothing
    other = {**run, "step_counts": {"tokens": 16384.0, "causal_pairs": 1.0}}
    assert _reader("mla_attention_mxu_share").read(other) is None
    assert _reader("mla_attention_hbm_share").read(other) is None


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
            "seq_attention_ms", "seq_attention_kernel_ms", "moe_experts_ms", "moe_route_ms",
            "moe_shared_ms", "seq_slot_fill", "seq_scope_coverage"} <= listed
    # the flash kernels', the indexer's, the sparse programs' and the delta rule's own
    assert not listed & {"seq_attention_mxu_share", "seq_attention_tile_share",
                         "sparse_index_ms", "sparse_select_ms", "sparse_selected_share",
                         "sparse_attention_mxu_share", "sparse_attention_hbm_share",
                         "linattn_ms", "linattn_delta_ms", "linattn_delta_mxu_share"}
    train = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_iters_per_s")
    assert CELL in train["workloads"] and train["bound"] == 0.01
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"], entry["traffic"]) == (
        "joyai-llm-flash-ep16", 1, "train-lifelong-histories")
    assert len(entry["why"]) <= 200 and len(MANIFEST["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
