"""``run.py`` end to end on the CPU at a 1/200 size (``--rehearse 1``).

A rehearsal prints counts only: an empty ``metrics`` and a device that says
``cpu``. Without the switch and without a chip the command exits non-zero and
prints no result. With the timed path broken underneath, ``correct`` is false.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmarks", "workloads"))
               if f.endswith(".json"))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory) -> str:
    """``BENCHMARK.json`` with the entries of the cells it does not list yet
    (``unlisted_cells.json``), so that those are rehearsed too."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        merged = json.load(f)
    with open(os.path.join(HERE, "unlisted_cells.json")) as f:
        for key, entries in json.load(f).items():
            merged[key] = merged[key] + entries
    path = tmp_path_factory.mktemp("manifest") / "BENCHMARK.json"
    path.write_text(json.dumps(merged))
    return str(path)


def _run(*args: str, env=None):
    return subprocess.run([sys.executable, RUN, *args], capture_output=True,
                          text=True, cwd=ROOT, timeout=600, env=env)


def _last(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_walks_the_cell(cell, trace, manifest):
    proc = _run("--workload", cell, "--seed", "3000000019", "--seconds", "2",
                "--trace", trace, "--rehearse", "1", "--manifest", manifest)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = _last(proc.stdout)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, proc.stdout[-2000:]
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("cell", CELLS[:1])
def test_without_a_chip_there_is_no_result(cell):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PIO_PLATFORM")}
    env["JAX_PLATFORMS"] = ""  # JAX's own default, which lands on the CPU here
    proc = _run("--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0", env=env)
    assert proc.returncode != 0
    for line in proc.stdout.strip().splitlines():
        assert "correct" not in line


def _in_process(cell: str, manifest: str, capsys, monkeypatch) -> dict:
    import run as bench_run

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("PIO_FS_BASEDIR", os.environ.get("PIO_FS_BASEDIR", ""))
    assert bench_run.main(["--workload", cell, "--seed", "5", "--seconds", "1",
                           "--trace", "0", "--rehearse", "1",
                           "--manifest", manifest]) == 0
    return _last(capsys.readouterr().out)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(manifest, capsys, monkeypatch):
    from predictionio_tpu.parallel import als

    monkeypatch.setattr(
        als, "make_iteration",
        lambda mesh, config: lambda ub, ib, users, items, reg, alpha: (users, items))
    result = _in_process("als-ml20m-r16.train-steady", manifest, capsys, monkeypatch)
    assert result["correct"] is False


def test_an_iteration_that_leaves_out_the_item_half_step_is_not_correct(
        manifest, capsys, monkeypatch):
    from predictionio_tpu.parallel import als

    stock = als.make_iteration

    def users_only(mesh, config):
        whole = stock(mesh, config)

        def iteration(ub, ib, users, items, reg, alpha):
            kept = items + 0  # the stock program donates its factors
            return whole(ub, ib, users, items, reg, alpha)[0], kept

        return iteration

    monkeypatch.setattr(als, "make_iteration", users_only)
    result = _in_process("als-ml20m-r16.train-steady", manifest, capsys, monkeypatch)
    assert result["correct"] is False


def test_a_score_altered_where_it_is_produced_is_not_correct(manifest, capsys, monkeypatch):
    from predictionio_tpu.models.recommendation import engine

    stock = engine.topk_item_scores

    def off_by_a_thousandth(item_ids, scores, num):
        out = stock(item_ids, scores, num)
        for entry in out["itemScores"]:
            entry["score"] *= 1.001
        return out

    monkeypatch.setattr(engine, "topk_item_scores", off_by_a_thousandth)
    result = _in_process("als-msd-r16.serve-steady", manifest, capsys, monkeypatch)
    assert result["correct"] is False
