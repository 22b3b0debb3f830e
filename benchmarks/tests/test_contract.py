"""``BENCHMARK.json`` against the static part of the benchmark's contract,
and against the files the harness finds by the names in it."""

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def _workload(name: str) -> dict:
    with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmarks"]
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for word in MANIFEST["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word


def test_entries_have_exactly_the_keys_of_the_contract():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200 and len(c["reduced"]) <= 16
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in {"host_clock", "device_trace"}
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_names_are_unique_and_cross_references_resolve():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[key]]
        assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    configs = {c["name"] for c in MANIFEST["configs"]}
    assert {w["config"] for w in cells.values()} == configs  # each is used
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells)
    for m in MANIFEST["per_layer"]:
        moved = end_to_end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert "workloads" not in moved or cell in moved["workloads"]


def test_every_name_has_the_file_the_harness_looks_for():
    for w in MANIFEST["workloads"]:
        cell = _workload(w["name"])
        assert cell["config"] == w["config"]
        assert os.path.exists(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
    for m in MANIFEST["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))


def test_every_cell_reports_set_up_another_end_to_end_and_a_per_layer_metric():
    for w in MANIFEST["workloads"]:
        own = [m for m in MANIFEST["end_to_end"]
               if w["name"] in m.get("workloads", []) and m["name"] != "setup_s"]
        layer = [m for m in MANIFEST["per_layer"] if w["name"] in m.get("workloads", [])]
        assert own and layer


def test_run_py_names_no_cell_configuration_or_metric():
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[key]:
            assert entry["name"] not in text, entry["name"]


def test_cells_kept_for_later_have_entries_that_resolve():
    """The cells whose files are here and which ``BENCHMARK.json`` does not
    list yet (PERF.md, Open questions) are rehearsed under the entries of
    ``unlisted_cells.json``: every one has them, and they find their files."""
    with open(os.path.join(HERE, "unlisted_cells.json")) as f:
        unlisted = json.load(f)
    listed = {w["name"] for w in MANIFEST["workloads"]}
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))}
    assert {w["name"] for w in unlisted["workloads"]} == files - listed
    for m in unlisted["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    for c in unlisted["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
