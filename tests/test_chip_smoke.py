"""``chip_smoke.py`` rehearsed on the CPU at 1/100 size.

The script is the proof that the system starts on the chip; here it has no
chip, so it must walk every phase (kernels interpreted, as the program does
on a CPU mesh) and then refuse: ``{"ok": false`` last and a non-zero exit.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ["device", "compile_cache", "ingest", "train_als", "als_full_width",
          "serve_als", "train_serve_ncf", "train_sequence_looped",
          "train_sequence_sparse_moe", "train_sequence_hybrid_linear",
          "train_sequence_latent_moe", "train_sequence_window_moe"]


def _run(args, tmp_path, timeout, **env_overrides):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    # one CPU device, and the real cache path logic, in the script's children
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--out", str(tmp_path / "out"), *args],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=str(tmp_path),
    )


def test_rehearsal_reaches_every_phase_then_refuses_the_cpu(tmp_path):
    proc = _run(["--scale", "0.01"], tmp_path, timeout=900)
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    passed = [l["phase"] for l in lines if l.get("phase") in PHASES and l.get("ok")]
    assert passed == PHASES, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith('{"ok": false'), last
    assert json.loads(last)["device"]["platform"] == "cpu"
    assert "not tpu" in json.loads(last)["error"]
    assert proc.returncode != 0
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    assert by_phase["serve_als"]["top10_equal"] is True
    assert by_phase["serve_als"]["mips_kernel"] == "interpreted"
    assert by_phase["train_serve_ncf"]["kernel"] == "interpreted"
    assert by_phase["compile_cache"]["cache_dir"] == str(tmp_path / "jax_cache")
    assert all(r["agrees"] for r in by_phase["als_full_width"]["runs"])
    looped = by_phase["train_sequence_looped"]
    assert looped["backbone"] == "looped" and looped["last_loss"] < looped["first_loss"]
    assert looped["attention_operands"] == "plain"   # no chip: XLA rotates, under ``rope``
    sparse = by_phase["train_sequence_sparse_moe"]
    assert sparse["backbone"] == "sparse_moe" and sparse["last_loss"] < sparse["first_loss"]
    assert (sparse["experts_held"], sparse["experts_total"], sparse["moe_dropped"]) == (4, 16, 0)
    assert 0 < sparse["selected_pairs"] < sparse["causal_pairs"]
    assert sparse["selection_kept_bytes"] == 2 * 8 * 128 * 128 // 8   # layers x rows x T x T bits
    # the compiled steps carry the leaf scopes: five a layer's attention and the
    # mlp's norm; those, the moe's norm and the experts' five
    assert (looped["leaf_scopes"], sparse["leaf_scopes"]) == (6, 11)
    assert sparse["again_in_backward"] > 0
    hybrid = by_phase["train_sequence_hybrid_linear"]
    assert hybrid["backbone"] == "hybrid_linear" and hybrid["last_loss"] < hybrid["first_loss"]
    assert (hybrid["linear_layers"], hybrid["full_layers"], hybrid["experts_shared"]) == (3, 1, 1)
    assert (hybrid["experts_held"], hybrid["experts_total"], hybrid["moe_dropped"]) == (4, 16, 0)
    assert hybrid["delta_state_bytes"] == 3 * 4 * 16 * 16 * 4       # layers x heads x dk x dv
    assert hybrid["delta_kept_bytes"] == 4 * 2 * 4 * 16 * 16 * 4    # rows x chunks x a layer's
    latent = by_phase["train_sequence_latent_moe"]
    assert latent["backbone"] == "latent_moe" and latent["last_loss"] < latent["first_loss"]
    assert (latent["dense_layers"], latent["mtp_depth"], latent["experts_shared"]) == (1, 1, 1)
    assert (latent["experts_held"], latent["experts_total"], latent["moe_dropped"]) == (4, 16, 0)
    assert (latent["score_width"], latent["value_width"], latent["latent_q_rank"],
            latent["latent_kv_rank"], latent["latent_bytes_per_token"]) == (24, 16, 48, 32, 80)
    # two routers, six steps of 0.001 either way from zero
    assert latent["router_bias_leaves"] == 2 and 0 < latent["router_bias_abs_max"] <= 0.006001
    assert latent["mtp_ce"] > 0
    # the sparse step's eleven, the dense layer's norm, the shared expert, the
    # two latent paths, and six of them again under the prediction module
    assert latent["leaf_scopes"] == 21
    window = by_phase["train_sequence_window_moe"]
    assert window["backbone"] == "window_moe" and window["last_loss"] < window["first_loss"]
    assert (window["window_layers"], window["full_layers"], window["experts_shared"]) == (1, 2, 1)
    assert (window["heads_window"], window["heads_full"], window["rope_tables"]) == (8, 6, 2)
    assert (window["experts_held"], window["experts_total"], window["moe_dropped"]) == (4, 16, 0)
    # a window of 32 on rows of 128: 32 x 33 / 2 + 96 x 32 pairs of the triangle's 8,256
    assert (window["window"], window["window_pairs"], window["causal_pairs"]) == (32, 3600, 8256)
    assert window["window_programs"] == {"forward": 0, "backward": 0}   # no chip, no programs
    # nor the operands' programs: XLA works the rotation, and the fits say so
    assert [phase[word] for phase, word in (
        (sparse, "rope_block"), (hybrid, "rope_block"), (latent, "rope_block"),
        (window, "rope_block"), (window, "window_rope_block"))] == ["plain"] * 5
    # the sparse step's eleven, the dense layer's norm, the shared expert and
    # the window layers' five
    assert window["leaf_scopes"] == 18


def test_without_a_chip_the_default_run_stops_at_the_device_phase(tmp_path):
    """As the driver runs it (no --scale): nothing is run on the host."""
    proc = _run([], tmp_path, timeout=300)
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    assert [l["phase"] for l in lines if "phase" in l][:2] == ["device", "device"]
    assert lines[1]["ok"] is False and "no accelerator" in lines[1]["error"]
    assert proc.stdout.strip().splitlines()[-1].startswith('{"ok": false')
    assert proc.returncode != 0


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        capture_output=True, text=True, timeout=60, cwd=str(tmp_path),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
