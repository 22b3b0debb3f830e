"""The sequence cell: its control, its counts against a hand count, its
scopes' reader on hand-made intervals and on a recorded v5e trace, every new
reader on a run that lacks its source, and the configuration against the
published keys. (Its rehearsal is ``test_rehearsal.py``'s, which walks every
file under ``workloads/``.)"""

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

from benchmarks import counts_seq, scopes_seq, trace_reduce as tr  # noqa: E402

CELL = "ouro-2.6b-d8.train-histories"
RECORDED = os.path.join(HERE, "seq_train_v5e.xplane.pb")
DEVICE = "/device:TPU:0"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
SEQ_READERS = [m["name"] for m in MANIFEST["per_layer"] if m["name"].startswith("seq_")]
with open(os.path.join(ROOT, "benchmarks", "configs", "ouro-2.6b-d8.json")) as f:
    CONFIG = json.load(f)


def _reader(name):
    from run import load_module

    return load_module("layer_metrics", name)


# ---- the control -----------------------------------------------------------

JUDGED = ["loss_abs_err", "exit1_loss_abs_err", "exit2_loss_abs_err", "exit3_loss_abs_err",
          "exit4_loss_abs_err", "exit_p_abs_err", "grad_gate_rel_err",
          "grad_final_norm_rel_err", "grad_wq_first_rel_err", "grad_w_down_last_rel_err",
          "grad_head_rows_rel_err", "adam_update_rel_err"]


def test_both_controls_read_not_correct_and_the_run_itself_correct():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0", "--rehearse", "1",
         "--control", "1"], capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    controls = {line["control"]: line for line in lines if "control" in line}
    assert list(controls) == ["bfloat16", "unshared"]
    assert not any(line["correct"] for line in controls.values())
    failed = {name: [c["name"] for c in line["checks"] if not c["ok"]]
              for name, line in controls.items()}
    # the precision below fails by a loss; the wrong loop computes the right
    # forward pass, and fails by the layer tensors' gradients and nothing else
    assert any(name.endswith("loss_abs_err") for name in failed["bfloat16"]), controls
    assert set(failed["unshared"]) <= {
        "seeded_grad_wq_first_rel_err", "seeded_grad_w_down_last_rel_err",
        "grad_w_down_last_rel_err"} and "seeded_grad_wq_first_rel_err" in failed["unshared"]
    assert lines[-1]["correct"] is True
    # layer 0's W_q is judged on the seed's draw, printed on the state the window left
    unjudged = next(line["unjudged"] for line in lines if "unjudged" in line)
    assert list(unjudged) == ["grad_wq_first_rel_err"] and unjudged["grad_wq_first_rel_err"] >= 0
    names = [line["check"] for line in lines if "check" in line]
    assert names == (["seeded_" + n for n in JUDGED]
                     + [n for n in JUDGED if n != "grad_wq_first_rel_err"]
                     + ["nonfinite_values", "compilations_in_window"])


def _a_judged_step(lr=1e-3, count=3):
    """A step as the driver records it, made by NumPy's Adam from a gradient."""
    from drivers import seq_train

    rng = np.random.default_rng(0)
    shapes = {"gate_w": (8,), "gate_b": (), "final_norm": (8,), "wq_first": (8, 8),
              "w_down_last": (16, 8), "head_rows": (4, 8)}
    draw = lambda scale: {k: scale * rng.standard_normal(s) for k, s in shapes.items()}  # noqa: E731
    grads, params, mu = draw(1.0), draw(0.02), draw(0.5)
    nu = {k: np.abs(v) for k, v in draw(0.3).items()}
    new_mu = {k: 0.9 * mu[k] + 0.1 * grads[k] for k in grads}
    moved = {k: params[k] + seq_train.adam_change(grads[k], mu[k], nu[k], count, lr)
             for k in grads}
    p = rng.random((4, 2, 3))
    have = {"loss": 1.0, "exit_ce": np.ones(4), "p": p,
            "old": {"params": params, "mu": mu, "nu": nu, "count": count},
            "new": {"params": moved, "mu": new_mu}}
    want = {"loss": 1.0, "exit_ce": np.ones(4), "p": p, "grads": grads}
    return seq_train, have, want


LIMITS = {"loss_abs_err_limit": 1e-3, "exit_p_abs_err_limit": 1e-3,
          "adam_update_rel_err_limit": 1e-4,
          "grad_rel_err_limits": {"gate": 0.01, "final_norm": 0.01, "w_down_last": 0.01,
                                  "head_rows": 0.01}}


def test_a_sound_step_passes_every_number_and_an_unlimited_tensor_is_printed():
    seq_train, have, want = _a_judged_step()
    rows, unjudged = seq_train.compared(have, want, np.ones((2, 3), bool), LIMITS, 1e-3, "x_")
    assert [name for name, _, _ in rows] == ["x_" + n for n in JUDGED if "wq_first" not in n]
    assert all(value <= 1e-12 for _, value, _ in rows), rows
    assert list(unjudged) == ["x_grad_wq_first_rel_err"]


@pytest.mark.parametrize("fault,fails", [
    ("learning_rate", "adam_update_rel_err"), ("second_moment", "adam_update_rel_err"),
    ("step_count", "adam_update_rel_err"), ("update_not_applied", "adam_update_rel_err"),
    ("one_pass_of_the_sum_lost", "grad_w_down_last_rel_err"),
    ("exit_distribution", "exit_p_abs_err"), ("an_exits_loss", "exit3_loss_abs_err")])
def test_a_planted_fault_fails_its_number(fault, fails):
    """What the optimizer's check sees that Adam's first moment alone does
    not: another learning rate, second moment or step count, an update that
    was computed and not applied."""
    seq_train, have, want = _a_judged_step()
    old, new = have["old"], have["new"]
    if fault == "learning_rate":
        lr = 3e-3
    else:
        lr = 1e-3
    if fault == "second_moment":
        old["nu"] = {k: 2.0 * v for k, v in old["nu"].items()}
    if fault == "step_count":
        old["count"] += 1
    if fault == "update_not_applied":
        new["params"] = dict(old["params"])
    if fault == "one_pass_of_the_sum_lost":
        want["grads"] = dict(want["grads"], w_down_last=0.75 * want["grads"]["w_down_last"])
    if fault == "exit_distribution":
        have["p"] = have["p"] + 0.01
    if fault == "an_exits_loss":
        have["exit_ce"] = have["exit_ce"] + np.array([0, 0, 0.01, 0])
    rows, _ = seq_train.compared(have, want, np.ones((2, 3), bool), LIMITS, lr)
    over = [name for name, value, limit in rows if not value <= limit]
    assert over == [fails], rows


# ---- the configuration ------------------------------------------------------

PUBLISHED = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
             "max_position_embeddings": 65536, "max_window_layers": 48,
             "num_attention_heads": 16, "num_hidden_layers": 48,
             "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
             "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152}


def test_every_published_number_is_in_the_file_and_only_the_depth_is_reduced():
    differs = [k for k, v in PUBLISHED.items() if CONFIG.get(k) != v]
    assert differs == ["num_hidden_layers"]
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert len(CONFIG["layer_types"]) == CONFIG["num_hidden_layers"] >= 4
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "ouro-2.6b-d8")
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert CONFIG["tie_word_embeddings"] is False and CONFIG["model_type"] == "ouro"


def test_the_engine_parameters_are_the_published_widths_and_the_stated_count():
    from benchmarks import seeded_histories

    algo = CONFIG["engine"]["algorithms"][0]["params"]
    assert (algo["hiddenSize"], algo["numHeads"], algo["headDim"], algo["ffnDim"]) == (
        2048, 16, 128, 5632)
    assert algo["backbone"] == "looped" and algo["utSteps"] == 4
    shapes = seeded_histories.param_shapes(49152, 2048, 2048, 5632, algo["numLayers"])
    flat = list(shapes["layers"].values()) + [v for k, v in shapes.items() if k != "layers"]
    assert sum(int(np.prod(s)) for s in flat) == CONFIG["parameters"]["total"]
    cell = json.load(open(os.path.join(ROOT, "benchmarks", "workloads", CELL + ".json")))
    assert cell["traffic"]["max_len"] == 256 and cell["traffic"]["users_per_step"] == 32
    assert algo["batchSize"] == 32 and CONFIG["engine"]["preparator"]["params"]["maxLen"] == 256


# ---- the counts -------------------------------------------------------------

def test_step_model_flops_against_a_hand_count():
    seq = np.array([[5, 6, 7, 0], [9, 0, 0, 0]])        # 3 tokens and 1
    target = np.array([[6, 7, 0, 0], [0, 0, 0, 0]])     # 2 targets
    hidden, attn, ffn, vocab, layers, passes = 8, 4, 16, 32, 2, 3
    per_token = 2 * (4 * 8 * 4 + 3 * 8 * 16)            # 1,024
    pairs = 3 * 4 // 2 + 1                               # 6 + 1
    application = 4 * per_token + pairs * 4 * attn       # 4,096 + 112
    forward = passes * (layers * application + 2 * 2 * hidden * vocab)
    assert forward == 3 * (2 * 4208 + 1024) == 28320
    assert counts_seq.step_model_flops(
        seq, target, hidden, attn, ffn, vocab, layers, passes) == 3 * forward


def test_step_model_flops_counts_no_padding_and_no_recomputation():
    full = np.ones((2, 8), int)
    half = full.copy()
    half[:, 4:] = 0
    args = (16, 16, 32, 64, 2, 4)
    target = lambda s: np.concatenate([s[:, 1:], s[:, :1] * 0], axis=1)  # noqa: E731
    assert counts_seq.step_model_flops(half, target(half), *args) < 0.5 * (
        counts_seq.step_model_flops(full, target(full), *args))


def test_flash_call_flops_against_a_hand_count():
    got = counts_seq.flash_call_flops(rows=2, length=200, heads=3, head_dim=8)
    tile_dot = 2 * 256 * 256 * 8 * 2 * 3                # the padded square, every tile
    assert got == {"forward": 2 * tile_dot, "dq": 3 * tile_dot, "dkv": 4 * tile_dot,
                   "backward": 7 * tile_dot}
    assert counts_seq.mxu_share_pct(197e12, 1.0, "TPU v5 lite") == pytest.approx(100.0)
    with pytest.raises(KeyError):
        counts_seq.mxu_share_pct(1.0, 1.0, "TPU v9")


# ---- the scopes' reader -----------------------------------------------------

BWD = "jit(train_step)/transpose(jvp(seq.pass3))/layers/while/body/closed_call/checkpoint"


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/attention/pallas_call:",
     ("pass1", "attention")),
    (BWD + "/rematted_computation/mlp/dot_general:", ("pass3", "mlp")),
    (BWD + "/attention/add_any:", ("pass3", "attention")),
    ("jit(train_step)/transpose(jvp(seq.pass4))/layers/while/body/dynamic_update_slice:",
     ("pass4", "layers")),
    ("jit(train_step)/jvp(seq.pass2)/exit/while/body/closed_call/jit(take_along_axis)/gather:",
     ("pass2", "exit")),
    ("jit(train_step)/transpose(jvp(seq.embed))/scatter-add:", ("embed", None)),
    ("jit(train_step)/seq.optimizer/mul:", ("optimizer", None)),
    ("jit(iteration)/als.user_half_step/bucket0/gram/dot_general:", None),
    ("params['layers']['wq']:", None),
    ("", None),
])
def test_parse_scope(op_name, want):
    assert scopes_seq.parse_scope(op_name) == want


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/attention/pallas_call:",
     "forward"),
    (BWD + "/rematted_computation/attention/pallas_call:", "forward"),
    (BWD + "/attention/pallas_call:", "backward"),
    (BWD + "/attention/dot_general:", None),
    ("jit(iteration)/als.user_half_step/bucket0/gram/als_gram_rhs/pallas_call:", None),
])
def test_kernel_kind(op_name, want):
    assert scopes_seq.kernel_kind(op_name) == want


def _planes(ops, window):
    return {DEVICE: {tr.OP_LINE: list(ops)},
            "/host:CPU": {"main": [(tr.WINDOW_NAME, *window)]}}


def test_scopes_add_up_and_the_kernels_calls_are_counted():
    fwd = "jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call"
    names = {DEVICE: {
        "gather.1": "jit(train_step)/jvp(seq.embed)/gather:",
        "while.1": "jit(train_step)/jvp(seq.pass1)/layers/while:",
        "attention.1 tpu_custom_call": fwd + "/attention/pallas_call:",
        "fusion.1": fwd + "/mlp/dot_general:",
        "fusion.2": "jit(train_step)/jvp(seq.pass1)/exit/dot_general:",
        "attention.2 tpu_custom_call": BWD + "/attention/pallas_call:",
        "attention.3 tpu_custom_call": BWD + "/attention/pallas_call:",
        "fusion.3": "jit(train_step)/seq.optimizer/add:",
        "copy.1": "",
    }}
    ops = [("gather.1", 0.0, 1.0), ("while.1", 1.0, 5.0),     # the loop holds its body
           ("attention.1 tpu_custom_call", 1.0, 3.0), ("fusion.1", 3.0, 5.0),
           ("fusion.2", 5.0, 6.0), ("attention.2 tpu_custom_call", 6.0, 7.0),
           ("attention.3 tpu_custom_call", 7.0, 9.0), ("fusion.3", 9.0, 9.5),
           ("copy.1", 9.5, 10.0), ("fusion.3", 11.0, 12.0)]   # the last: past the window
    out = scopes_seq.reduce_scopes(_planes(ops, (0.0, 10.0)), names)
    assert out["busy_s"] == pytest.approx(10.0) and out["scoped_s"] == pytest.approx(9.5)
    assert out["stages"] == pytest.approx(
        {"embed": 1.0, "layers": 7.0, "attention": 5.0, "mlp": 2.0, "exit": 1.0,
         "optimizer": 0.5})
    assert out["passes"] == pytest.approx({"pass1": 5.0, "pass3": 3.0})
    assert out["kernel_s"] == pytest.approx({"forward": 2.0, "backward": 3.0})
    assert out["kernel_calls"] == {"forward": 1, "backward": 2}
    assert out["outside"] == [["copy.1", pytest.approx(0.5)]]
    run = {"trace": {"busy_s": 10.0, "window_s": 10.0}, "steps": 2,
           "flash_call": {"rows": 1, "length": 128, "heads": 1, "head_dim": 128},
           "device_kind": "TPU v5 lite"}
    # as the attention share's reader puts the calls and the time together
    per_call = counts_seq.flash_call_flops(**run["flash_call"])
    flops = 1 * per_call["forward"] + 2 * per_call["backward"] / 2
    assert flops == (2 + 7) * 2 * 128 * 128 * 128


def test_on_a_recorded_v5e_trace_the_scopes_cover_the_step_and_the_shares_stay_under_100():
    """``seq_train_v5e.xplane.pb``: the first 1.06 s of a traced window of the
    cell on the chip (PR 30, seed 2147483879), the device's ``XLA Ops`` line
    and the window's annotation: one whole step of 6 layers x 4 passes."""
    planes, names = tr.read_planes(RECORDED), scopes_seq.read_op_names(RECORDED)
    out = scopes_seq.reduce_scopes(planes, names)
    assert out["busy_s"] == pytest.approx(1.05437, rel=1e-4)
    assert 100 * out["scoped_s"] / out["busy_s"] == pytest.approx(98.2, abs=0.1)
    assert out["stages"] == pytest.approx(
        {"embed": 0.003740, "layers": 0.830356, "attention": 0.462647, "mlp": 0.341551,
         "exit": 0.174461, "optimizer": 0.026826}, rel=1e-4)
    assert out["stages"]["layers"] >= out["stages"]["attention"] + out["stages"]["mlp"]
    assert sorted(out["passes"]) == ["pass1", "pass2", "pass3", "pass4"]
    # 24 layer applications: a forward and a recomputed forward, a dq and a dkv each
    assert out["kernel_calls"] == {"forward": 48, "backward": 48}
    run = {"trace": {"busy_s": out["busy_s"]}, "steps": 1, "device_kind": "TPU v5 lite",
           "flash_call": {"rows": 32, "length": 256, "heads": 16, "head_dim": 128}}
    per_call = counts_seq.flash_call_flops(**run["flash_call"])
    flops = 48 * per_call["forward"] + 48 * per_call["backward"] / 2
    share = counts_seq.mxu_share_pct(flops, sum(out["kernel_s"].values()), "TPU v5 lite")
    assert share == pytest.approx(7.16, abs=0.05)


# ---- readers on a run that lacks their source ---------------------------------

@pytest.mark.parametrize("name", SEQ_READERS)
def test_a_reader_gives_nothing_where_there_is_nothing_to_read(name):
    """An untraced run, and a program that names no ``seq.`` scope, records no
    ``seq.pack`` span and reports no steps (the parent's): None, no raise."""
    reader = _reader(name)
    assert reader.read({"end_to_end": {}, "setup": {}}) is None
    bare = {"trace": {"busy_s": 0.0, "window_s": 1.0, "device_ops": [], "idle_gaps": []},
            "iterations": 3, "device_kind": "TPU v5 lite"}
    assert reader.read(bare) is None


def test_the_nine_readers_are_listed_for_this_cell_alone():
    assert len(SEQ_READERS) == 9
    for m in MANIFEST["per_layer"]:
        if m["name"].startswith("seq_"):
            assert m["workloads"] == [CELL] and m["moves"] == "train_iters_per_s"
