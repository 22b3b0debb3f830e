"""The ``setup_*`` readers on a made-up table, ring and window opening: what
ended before the window opened is counted and nothing after it, and the
coverage is a union of intervals, not a sum."""

import time

import pytest

from benchmarks import run as run_mod
from benchmarks.layer_metrics import _setup

SETUP_S = 40.0


def _read(name: str, **made_up):
    return run_mod.load_module("layer_metrics", name).read(
        {"end_to_end": {"setup_s": SETUP_S}}, **made_up)


@pytest.fixture
def start():
    from predictionio_tpu.obs.trace import epoch_seconds

    return epoch_seconds(run_mod.T0)


def _row(start, program, begin, trace_s, lower_s, compile_s, cache):
    took = trace_s + lower_s + compile_s
    return {"program": program, "trace_s": trace_s, "lower_s": lower_s,
            "compile_s": compile_s, "cache": cache, "nested_s": 0.0,
            "start_s": start + begin, "end_s": start + begin + took}


def _trace(start, begin, spans):
    """A trace as ``Tracer.snapshot()`` serialises one, ``spans`` as (op,
    seconds from ``begin``, seconds long)."""
    at = start + begin
    stamp = time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(at))
    return {"startTime": stamp + f".{int((at % 1) * 1000):03d}Z",
            "spans": [{"op": op, "offsetMs": 1000.0 * offset, "durationMs": 1000.0 * took}
                      for op, offset, took in spans]}


@pytest.fixture
def made_up(start):
    rows = [
        _row(start, "jit(iteration)", 10.0, 1.0, 2.0, 4.0, "hit"),       # 10..17
        _row(start, "jit(dynamic_slice)", 17.5, 0.25, 0.25, 0.5, "none"),  # 17.5..18.5
        _row(start, "jit(train_step)", 20.0, 2.0, 1.0, 9.0, "miss"),     # 20..32
        # the reference's program: it begins before the window opens and ends after
        _row(start, "jit(reference)", 38.0, 1.0, 1.0, 30.0, "miss"),
        _row(start, "jit(probe)", 90.0, 1.0, 1.0, 5.0, "miss"),
    ]
    traces = [
        _trace(start, 1.0, [("backend.init", 0.0, 3.0)]),                # 1..4
        # the pack holds the first program whole: a union counts its 9 s once
        _trace(start, 9.0, [("als.pack", 0.0, 9.0), ("jit.compile", 4.0, 4.0)]),  # 9..18
        _trace(start, 39.0, [("seq.fit", 0.0, 20.0)]),                   # ends after
    ]
    return {"rows": rows, "traces": traces}


def test_a_row_or_span_that_ends_after_the_opening_is_not_counted(made_up):
    assert _read("setup_backend_s", **made_up) == pytest.approx(3.0, abs=2e-3)
    assert _read("setup_trace_lower_s", **made_up) == pytest.approx(3.0 + 0.5 + 3.0)
    assert _read("setup_compile_s", **made_up) == pytest.approx(0.5 + 9.0)
    assert _read("setup_cache_load_s", **made_up) == pytest.approx(4.0)
    assert _read("setup_programs_missed", **made_up) == 1


def test_coverage_is_a_union_and_not_a_sum(made_up):
    # 1..4 backend, 9..18.5 the pack with two programs in it, 20..32 the step
    covered = 3.0 + 9.5 + 12.0
    assert _read("setup_span_coverage", **made_up) == pytest.approx(
        100.0 * covered / SETUP_S, abs=0.02)


def test_the_parts_stay_under_the_set_up_they_split(made_up):
    parts = sum(_read(name, **made_up) for name in (
        "setup_backend_s", "setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s"))
    assert parts <= SETUP_S
    assert 0.0 < _read("setup_span_coverage", **made_up) <= 100.0


def test_union_clips_to_the_window_and_merges_overlaps():
    assert _setup.union_s([(0, 4), (2, 6), (8, 9), (9.5, 30)], 1, 10) == pytest.approx(6.5)
    assert _setup.union_s([], 0, 10) == 0.0


@pytest.mark.parametrize("name", ["setup_backend_s", "setup_trace_lower_s", "setup_compile_s",
                                  "setup_cache_load_s", "setup_programs_missed",
                                  "setup_span_coverage"])
def test_a_run_with_no_set_up_or_a_full_table_reads_nothing(name, made_up, monkeypatch):
    reader = run_mod.load_module("layer_metrics", name)
    assert reader.read({}, **made_up) is None
    assert reader.read({"end_to_end": {}}, **made_up) is None
    from predictionio_tpu.utils import platform

    # a table at its cap may have dropped the set-up's rows: no number, not a low one
    monkeypatch.setattr(platform, "compile_report",
                        lambda: made_up["rows"][:1] * platform.PROGRAM_ROWS)
    assert reader.read({"end_to_end": {"setup_s": SETUP_S}}, traces=made_up["traces"]) is None


def test_a_program_without_the_table_or_the_clock_reads_nothing(made_up, monkeypatch):
    """The parent commit has neither ``compile_report`` nor ``epoch_seconds``:
    every reader gives None there and does not raise."""
    from predictionio_tpu.utils import platform

    monkeypatch.delattr(platform, "compile_report")
    for name in ("setup_backend_s", "setup_trace_lower_s", "setup_compile_s",
                 "setup_cache_load_s", "setup_programs_missed", "setup_span_coverage"):
        assert _read(name, **made_up) is None
