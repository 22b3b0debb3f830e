"""The window-and-full decoder's lifelong-histories cell: its nine controls
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

from benchmarks import counts_laguna, scopes_leaf, scopes_window, trace_reduce as tr  # noqa: E402

CELL = "laguna-xs2-ep16.train-lifelong-histories"
DEVICE = "/device:TPU:0"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "configs", "laguna-xs2-ep16.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")) as f:
    WORKLOAD = json.load(f)
NEW_READERS = ["window_attention_ms", "window_attention_kernel_ms", "window_attention_mxu_share",
               "window_attention_hbm_share", "window_attention_tile_fill"]


def _reader(name):
    from run import load_module

    return load_module("layer_metrics", name)


# ---- the controls ------------------------------------------------------------

TENSORS = ("wq_window_first", "wk_window_first", "wv_window_first", "wg_window_first",
           "wq_full_last", "wg_full_last", "wo_first", "dense_down", "router_first",
           "router_last", "w_down_first", "shared_down_last", "final_norm", "head_rows")
JUDGED = (["loss_abs_err", "ce_abs_err", "balance_abs_err"]
          + [f"grad_{t}_rel_err" for t in TENSORS] + ["adam_update_rel_err"])


def test_the_nine_controls_read_not_correct_and_the_run_itself_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse", "1",
         "--control", "1"], capture_output=True, text=True, cwd=ROOT, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    controls = {line["control"]: line for line in lines if "control" in line}
    assert list(controls) == ["bfloat16", "no_window", "window_off_by_one", "one_table",
                              "no_yarn", "unscaled_rope", "no_gate", "softmax_router", "unscaled"]
    assert not any(line["correct"] for line in controls.values())
    failed = {name: {c["name"].removeprefix("seeded_") for c in line["checks"] if not c["ok"]}
              for name, line in controls.items()}
    # the precision below fails by the routers' gradients; every causal pair, one
    # key more and the other kind's table by the first window layer's; plain
    # frequencies and an unscaled table by the last full layer's queries; no gate
    # by the gates' own gradients (zero in the reference); another router and
    # unscaled gates by the experts'
    assert "grad_router_last_rel_err" in failed["bfloat16"], controls["bfloat16"]
    window = {f"grad_{t}_window_first_rel_err" for t in ("wq", "wk", "wv", "wg")}
    assert window <= failed["no_window"] and window <= failed["window_off_by_one"]
    assert {"grad_wq_window_first_rel_err", "grad_wk_window_first_rel_err"} <= failed["one_table"]
    assert "grad_wq_full_last_rel_err" in failed["no_yarn"] & failed["unscaled_rope"]
    assert {"grad_wg_window_first_rel_err", "grad_wg_full_last_rel_err"} <= failed["no_gate"]
    assert {"grad_w_down_first_rel_err", "grad_router_first_rel_err",
            "balance_abs_err"} <= failed["softmax_router"]
    assert "grad_w_down_first_rel_err" in failed["unscaled"]
    assert lines[-1]["correct"] is True
    names = [line["check"] for line in lines if "check" in line]
    assert names == (["seeded_" + n for n in JUDGED] + JUDGED
                     + ["moe_dropped", "nonfinite_values", "compilations_in_window"])
    said = next(line for line in lines if "step_counts" in line)
    counts = said["step_counts"]
    assert counts["tokens"] == 2 * 128 and counts["causal_pairs"] == 2 * 128 * 129 / 2
    assert counts["window_pairs"] == 2 * (32 * 33 / 2 + 96 * 32) and counts["targets"] == 2 * 127
    assert counts["moe_assignments"] == 4 * 4 * 256          # K x four expert layers' tokens
    assert 0 < said["moe_held_share"] < 100 and said["moe_load_max_over_mean"] >= 1
    fit = next(line for line in lines if "fit" in line)["fit"]
    assert (fit["backbone"], fit["window"], fit["window_layers"], fit["full_layers"],
            fit["heads_window"], fit["heads_full"], fit["rope_tables"]) == (
                "window_moe", 32, 3, 2, 8, 6, 2)


def test_every_limit_of_the_cell_is_set():
    for where in (WORKLOAD["traffic"]["correct"], WORKLOAD["traffic"]["rehearsal"]["correct"]):
        for state in ("seeded", "trained"):
            limits = where[state]
            assert sorted(limits["grad_rel_err_limits"]) == sorted(TENSORS)
            for name in JUDGED:
                if not name.startswith("grad_"):
                    assert 0 <= limits[name + "_limit"] < 64, (state, name)
            assert limits["loss_abs_err_limit"] < 1e-2


# ---- the configuration ---------------------------------------------------------

def test_every_published_number_is_in_the_file_and_the_depth_the_share_and_the_slice_are_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if CONFIG.get(k) != v)
    # the source has no key for the experts a chip holds: the file adds it
    lists = ["layer_types", "mlp_layer_types", "num_attention_heads_per_layer"]
    assert differ == sorted(lists + ["num_hidden_layers", "vocab_size"])
    assert sorted(CONFIG["reduced"]) == sorted(differ + ["num_local_experts"])
    for name in lists:      # the first five entries, as published
        assert CONFIG[name] == row["config"][name][:5]
    assert CONFIG["rope_parameters"] == row["config"]["rope_parameters"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_local_experts"], CONFIG["num_experts"],
            CONFIG["vocab_size"], CONFIG["sliding_window"]) == (5, 16, 256, 100352 // 8, 512)
    assert CONFIG["published"]["num_local_experts"] == row["config"]["num_experts"] == 256
    assert CONFIG["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] == 40
    assert CONFIG["published"]["vocab_size"] == row["config"]["vocab_size"] == 100352
    assert "16 chips share each layer's experts" in CONFIG["deployment"]["stands_for"]
    assert "vocabulary eight ways" in CONFIG["deployment"]["stands_for"]
    assert "35 layers on further stages" in CONFIG["deployment"]["stands_for"]
    assert {"gate", "router", "shared_expert", "qk_norm", "window", "balance_loss", "histories",
            "initial_parameters", "optimizer"} <= set(CONFIG["assumed"])
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert len(entry["why"]) <= 200 and entry["file"] == "benchmarks/configs/laguna-xs2-ep16.json"


def test_the_engine_parameters_are_the_published_widths_and_the_stated_count():
    from benchmarks import seeded_window
    from drivers import seq_window_train
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import window_moe
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    params = seq_window_train._algorithm_params(CONFIG, {})
    config = SASRecAlgorithm(Params(params))._config(CONFIG["vocab_size"] - 1, 8192)
    assert window_moe.count_params(config) == CONFIG["parameters"]["total"] == 490_297_344
    assert config.experts_held == (0, 16) and config.learning_rate == 1e-5
    assert window_moe.grouping(config) == window_moe.Grouping(1, 3, 0, 48, 64)
    assert (config.window, config.rotary_dim, config.full_rope_factor, config.full_rope_theta,
            config.window_rope_theta, config.balance_coef, config.routed_scale) == (
                512, 64, 64, 500000, 10000, 1e-4, 2.5)
    assert config.full_rope_attention_factor == 1.4158883083359672
    # the generator's shapes are the program's, at the cell's size and at the rehearsal's
    assert seeded_window.param_shapes(
        CONFIG, CONFIG["vocab_size"], 16) == window_moe.param_shapes(config)
    cut = WORKLOAD["traffic"]["rehearsal"]
    small = SASRecAlgorithm(Params(seq_window_train._algorithm_params(CONFIG, cut)))._config(
        cut["vocab_size"] - 1, cut["max_len"])
    assert (small.hidden_size, small.held, small.num_experts, small.shared_expert_dim,
            small.window, small.full_rope_original_len) == (64, 4, 16, 32, 32, 32)
    assert seeded_window.param_shapes({**CONFIG, **cut}, 512, 4) == window_moe.param_shapes(small)
    with pytest.raises(ValueError, match="hiddenSize"):
        seq_window_train._algorithm_params({**CONFIG, "hidden_size": 4096}, {})
    with pytest.raises(ValueError, match="slidingWindow"):
        seq_window_train._algorithm_params({**CONFIG, "sliding_window": 1024}, {})
    other = json.loads(json.dumps(CONFIG))
    other["rope_parameters"]["full_attention"]["factor"] = 32
    with pytest.raises(ValueError, match="rope_parameters.full_attention.factor"):
        seq_window_train._algorithm_params(other, {})
    traffic, keye = WORKLOAD["traffic"], json.load(open(os.path.join(
        ROOT, "benchmarks", "workloads", "keye-vl2-30b-a3b-ep8.train-lifelong-histories.json")))
    for key in ("kind", "max_len", "users_per_step", "warm_steps", "trace_seconds"):
        assert traffic[key] == keye["traffic"][key], key      # the same traffic, another backbone
    theirs = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "keye-vl2-30b-a3b-ep8.json")))["data"]
    mine = CONFIG["data"]
    assert mine["items"] == CONFIG["vocab_size"] - 1 == 12543
    for key in ("users", "min_events", "mean_events", "published"):
        assert mine[key] == theirs[key], key
    assert {k: mine["popularity"][k] for k in ("exponent", "shift")} == {
        k: theirs["popularity"][k] for k in ("exponent", "shift")}


def test_the_seeded_parameters_repeat_and_the_norms_are_about_one():
    from benchmarks import seeded_window

    cut = {**CONFIG, **WORKLOAD["traffic"]["rehearsal"]}
    drawn = seeded_window.make_params(seeded_window.param_shapes(cut, 512, 4), 3, 80)
    again = seeded_window.make_params(seeded_window.param_shapes(cut, 512, 4), 3, 80)
    window = drawn["periods"]["window"]
    assert window["wq"].shape == (1, 3, 64, 8 * 16) and drawn["first"]["wq"].shape == (64, 6 * 16)
    assert window["wg"].shape == (1, 3, 64, 8) and "tail" not in drawn
    assert np.array_equal(window["wg"], again["periods"]["window"]["wg"])
    assert abs(window["n1"].mean() - 1) < 0.05
    assert abs(drawn["first"]["w_down"].std() / (0.02 / np.sqrt(80)) - 1) < 0.05
    assert abs(window["wq"].std() / 0.02 - 1) < 0.05 and abs(drawn["embed"].std() - 1) < 0.05
    with pytest.raises(ValueError, match="whole periods"):
        seeded_window.groups_of({**cut, "layer_types": ["sliding_attention"] * 5})


# ---- the counts ---------------------------------------------------------------

DIMS = {"hidden_size": 8, "num_hidden_layers": 5, "head_dim": 4, "num_key_value_heads": 2,
        "layer_types": CONFIG["layer_types"], "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
        "intermediate_size": 11, "moe_intermediate_size": 9, "num_experts": 16,
        "shared_expert_intermediate_size": 7}
STEP = {"tokens": 10.0, "targets": 9.0, "causal_pairs": 55.0, "window_pairs": 27.0,
        "moe_held_assignments": 24.0}


def test_step_model_flops_against_a_hand_count():
    assert counts_laguna.layers_by_kind(DIMS) == {
        "full_attention": (2, 6), "sliding_attention": (3, 8)}
    assert counts_laguna.pairs_of([10], 3) == (55.0, 27.0)           # 1 + 2 + 3 x 8
    assert counts_laguna.pairs_of([2, 0], 3) == (3.0, 3.0)
    window = 3 * 3 * 27 * 2 * 8 * (4 + 4)
    full = 3 * 2 * 55 * 2 * 6 * (4 + 4)
    assert counts_laguna.window_attention_flops(STEP, DIMS) == window
    assert counts_laguna.full_attention_flops(STEP, DIMS) == full
    project = lambda h: 2 * 8 * (2 * h * 4 + 2 * 2 * 4 + h)  # noqa: E731
    assert counts_laguna.projection_flops_a_token(6, DIMS) == project(6)
    forward = (10 * (2 * project(6) + 3 * project(8)) + 10 * 6 * 8 * 11
               + 10 * 4 * (2 * 8 * 16 + 6 * 8 * 7) + 24 * 6 * 8 * 9 + 9 * 2 * 8 * 50)
    assert counts_laguna.step_model_flops(STEP, DIMS, 50) == 3 * forward + window + full
    assert sum(counts_laguna.forward_parts(STEP, DIMS, 50).values()) == pytest.approx(
        forward + (window + full) / 3)
    # at the cell's widths the need is ISSUE 44's: 12.9 TFLOP forward, the band 0.80 of it
    causal, band = counts_laguna.pairs_of([8192, 8192], 512)
    assert (causal, band) == (2 * 33_558_528, 2 * 4_063_488)
    cell = {"tokens": 16384.0, "targets": 16382.0, "causal_pairs": causal, "window_pairs": band,
            "moe_held_assignments": 16384.0 * 8 * 4 / 16}
    parts = counts_laguna.forward_parts(cell, CONFIG, CONFIG["vocab_size"])
    assert parts["window_pairs"] == pytest.approx(0.80e12, rel=0.01)
    assert parts["full_pairs"] == pytest.approx(3.30e12, rel=0.01)
    assert parts["window_projections"] == pytest.approx(3.72e12, rel=0.01)
    assert parts["full_projections"] == pytest.approx(1.93e12, rel=0.01)
    assert sum(parts.values()) == pytest.approx(12.9e12, rel=0.02)


def test_the_attentions_bytes_hold_each_tensor_once_a_position():
    position = (2 * 8 + 2 * 2) * 4 * 2
    assert counts_laguna.window_attention_bytes(STEP, DIMS) == 2 * 3 * 10 * position
    assert counts_laguna.window_attention_bytes({"tokens": 1.0}, CONFIG) == 2 * 3 * (
        2 * 64 + 2 * 8) * 128 * 2


# ---- the scopes' reader ---------------------------------------------------------

FWD = "jit(train_step)/jvp(seq.pass1)/layers/while/body/while/body/closed_call/checkpoint"
BWD = ("jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/while/body/closed_call/"
       "checkpoint/rematted_computation")
FULL = "jit(train_step)/jvp(seq.pass1)/layers/while/body/checkpoint"


@pytest.mark.parametrize("op_name,place", [
    (FWD + "/window_attention/qkv/dot_general:", ("window", "qkv")),
    (BWD + "/window_attention/kernel/pallas_call:", ("window", "kernel")),
    (FWD + "/window_attention/kernel/convert_element_type:", ("window", "kernel")),
    (FWD + "/window_attention/add:", ("window", None)),
    (FULL + "/attention/kernel/pallas_call:", None),
    (FULL + "/moe/route/top_k:", None),
    ("jit(iteration)/als.user_half_step/bucket0/gram/window_attention/x:", None),
    ("", None),
])
def test_place_of(op_name, place):
    assert scopes_window.place_of(op_name) == place


def test_the_readers_on_hand_made_intervals(monkeypatch):
    names = {
        "fusion.1": FWD + "/window_attention/qkv/dot_general:",
        "kernel.1 tpu_custom_call": FWD + "/window_attention/kernel/pallas_call:",
        "kernel.2 tpu_custom_call": BWD + "/window_attention/kernel/pallas_call:",
        "fusion.2": BWD + "/window_attention/kernel/transpose:",
        "fusion.3": FWD + "/window_attention/norm/mul:",
        "kernel.3 tpu_custom_call": FULL + "/attention/kernel/pallas_call:",
        "fusion.4": FULL + "/attention/qkv/dot_general:",
        "fusion.5": FULL + "/moe/route/top_k:",
    }
    ops = [("fusion.3", 0.0, 0.25), ("fusion.1", 0.25, 1.25),
           ("kernel.1 tpu_custom_call", 1.25, 2.25), ("fusion.2", 2.25, 2.5),
           ("kernel.2 tpu_custom_call", 2.5, 4.5), ("fusion.4", 4.5, 5.0),
           ("kernel.3 tpu_custom_call", 5.0, 9.0), ("fusion.5", 9.0, 9.5),
           ("kernel.1 tpu_custom_call", 11.0, 12.0)]  # past the window
    planes = {DEVICE: {tr.OP_LINE: ops}, "/host:CPU": {"main": [(tr.WINDOW_NAME, 0.0, 10.0)]}}
    reduced = scopes_window.reduce_places(planes, {DEVICE: names})
    assert reduced["window"] == pytest.approx(4.5) and reduced["programs"] == pytest.approx(3.0)
    assert reduced["leaves"] == pytest.approx({"norm": 0.25, "qkv": 1.0, "kernel": 3.25})
    monkeypatch.setattr(scopes_window, "_reduced", lambda path, mtime: reduced)
    monkeypatch.setattr(scopes_window.scopes, "newest_xplane", lambda: __file__)
    instructions = {DEVICE: {name: (op_name, "fusion") for name, op_name in names.items()}}
    leaves = scopes_leaf.reduce_leaves(planes, instructions)
    monkeypatch.setattr(scopes_leaf, "of_run", lambda r: leaves if r.get("trace") else None)
    causal, band = counts_laguna.pairs_of([8192, 8192], 512)
    step = {"tokens": 16384.0, "causal_pairs": causal, "window_pairs": band}
    run = {"trace": {"busy_s": 10.0, "window_s": 10.0}, "steps": 2,
           "device_kind": "TPU v5 lite", "dims": CONFIG, "step_counts": step}
    assert _reader("window_attention_ms").read(run) == pytest.approx(2250.0)
    assert _reader("window_attention_kernel_ms").read(run) == pytest.approx(1500.0)
    # the accepted reader of ``attention``'s programs sees the full layers' alone
    assert _reader("seq_attention_kernel_ms").read(run) == pytest.approx(2000.0)
    flops = counts_laguna.window_attention_flops(step, CONFIG)
    assert flops == 3 * 3 * band * 2 * 64 * 256
    assert _reader("window_attention_mxu_share").read(run) == pytest.approx(
        100 * (flops / 197e12) / 1.5)        # the programs: 3 s in the window, two steps
    moved = counts_laguna.window_attention_bytes(step, CONFIG)
    assert _reader("window_attention_hbm_share").read(run) == pytest.approx(
        100 * (moved / 819e9) / 1.5)
    # another decoder's run (its counts, its dims): the shares give nothing
    other = {**run, "step_counts": {"tokens": 16384.0, "causal_pairs": 1.0}}
    assert _reader("window_attention_mxu_share").read(other) is None
    assert _reader("window_attention_hbm_share").read(other) is None


def test_the_tile_fill_reads_the_fits_span():
    from predictionio_tpu.models.sequence import model as seq_model, window_moe
    from predictionio_tpu.obs.trace import global_tracer

    full, window = window_moe.FULL, window_moe.WINDOW
    config = window_moe.WindowMoEConfig(
        num_items=12_543, max_len=8192, hidden_size=2048,
        layer_types=(full, window, window, window, full),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
        heads_per_layer=(48, 64, 64, 64, 48), num_kv_heads=8, head_dim=128, window=512,
        ffn_dim=8192, expert_dim=512, num_experts=256, experts_per_token=8,
        experts_held=(0, 16), shared_expert_dim=512, batch_size=2)
    with global_tracer().span("seq.fit", attrs=seq_model.fit_attrs(config, 4, 8, 2, "tpu")):
        pass
    # 256 x 512 tiles forward and 512 x 512 backward: two key blocks a query block, half full
    assert _reader("window_attention_tile_fill").read({}) == pytest.approx(
        100 * 4_063_488 / (62 * 256 * 512), rel=1e-4)
    with global_tracer().span("seq.fit", attrs={"backbone": "latent_moe"}):
        pass
    assert _reader("window_attention_tile_fill").read({}) is None


@pytest.mark.parametrize("name", NEW_READERS[:4])
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
    assert set(NEW_READERS) <= listed
    assert {"device_idle_share.train", "seq_step_busy_ms", "seq_step_mfu", "seq_layers_ms",
            "seq_attention_ms", "seq_attention_kernel_ms", "seq_attention_proj_ms",
            "seq_attention_rope_layout_ms", "moe_experts_ms", "moe_route_ms", "moe_shared_ms",
            "moe_experts_mxu_share", "seq_slot_fill", "seq_scope_coverage"} <= listed
    # the readers that look for a leaf under a stage miss the window layers' (their
    # stage is ``layers``): left off, as the hybrid cell leaves them off; and the
    # flash kernels', the indexer's, the delta rule's and the latent programs' own
    assert not listed & {"seq_norm_ms", "seq_leaf_coverage", "seq_attention_mxu_share",
                         "seq_attention_tile_share", "sparse_index_ms", "sparse_select_ms",
                         "sparse_attention_mxu_share", "linattn_ms", "linattn_delta_ms",
                         "mla_attention_mxu_share", "mla_latent_ms", "mtp_ms"}
    train = next(m for m in MANIFEST["end_to_end"] if m["name"] == "train_iters_per_s")
    assert train["workloads"][-1] == CELL and train["bound"] == 0.01
    entry = MANIFEST["workloads"][-1]
    assert (entry["name"], entry["config"], entry["chips"], entry["traffic"]) == (
        CELL, "laguna-xs2-ep16", 1, "train-lifelong-histories")
    assert len(entry["why"]) <= 200 and MANIFEST["configs"][-1]["name"] == "laguna-xs2-ep16"
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
