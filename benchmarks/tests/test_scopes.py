"""Device time by the program's scopes: hand-made interval cases, the wire
walk and the sums on a recorded v5e trace of the scoped program, and every
new reader on a run that lacks its source."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from benchmarks import scopes, trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "train_v5e_scoped.xplane.pb")
DEVICE = "/device:TPU:0"
USER, ITEM = "als.user_half_step", "als.item_half_step"

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    NEW_READERS = [m["name"] for m in json.load(f)["per_layer"]
                   if m["name"].startswith(("als_gram", "als_solve", "als_assemble",
                                            "als_user", "als_item", "als_scope",
                                            "als_slot", "als_pack_span", "jit_"))]


def planes(ops, window=None):
    events = [(tr.WINDOW_NAME, *window)] if window else []
    return {DEVICE: {tr.OP_LINE: list(ops)}, "/host:CPU": {"main": events}}


def op_names(**scoped):
    return {DEVICE: {name: f"jit(iteration)/{scope}/mul:" if scope else ""
                     for name, scope in scoped.items()}}


@pytest.mark.parametrize("op_name,want", [
    ("jit(iteration)/als.user_half_step/bucket0/gram/als_gram_rhs/pallas_call:", (USER, "gram")),
    ("jit(iteration)/als.item_half_step/bucket3/shard_map/solve/div:", (ITEM, "solve")),
    ("jit(iteration)/als.item_half_step/assemble/concatenate:", (ITEM, "assemble")),
    ("jit(iteration)/als.user_half_step/bucket1/dynamic_slice:", (USER, None)),  # a side, no stage
    ("jit(iteration)/mips.stage1/mips_block_topk/pallas_call:", None),
    ("u_blocks[3][1]:", None),
    ("", None),
])
def test_parse_scope(op_name, want):
    assert scopes.parse_scope(op_name) == want


def test_nested_scopes_count_once_in_each_table_and_the_sums_meet_busy_time():
    ops = [("k", 0.0, 4.0), ("s", 4.0, 5.0), ("inner", 4.2, 4.4),  # inner lies inside s
           ("a", 5.0, 5.5), ("k2", 6.0, 7.0), ("c", 7.0, 7.5)]
    names = op_names(k=f"{USER}/bucket0/gram", s=f"{USER}/bucket0/solve",
                     inner=f"{USER}/bucket0/solve", a=f"{USER}/assemble",
                     k2=f"{ITEM}/bucket0/gram", c=None)
    out = scopes.reduce_scopes(planes(ops, window=(0.0, 8.0)), names)
    assert out["busy_s"] == pytest.approx(7.0)
    assert out["stages"] == pytest.approx({"gram": 5.0, "solve": 1.0, "assemble": 0.5})
    assert out["sides"] == pytest.approx({USER: 5.5, ITEM: 1.0})
    assert out["scoped_s"] == pytest.approx(6.5)
    assert sum(out["stages"].values()) == pytest.approx(out["scoped_s"])
    assert sum(out["sides"].values()) == pytest.approx(out["scoped_s"])
    assert out["outside"] == [["c", pytest.approx(0.5)]]
    assert out["busy_s"] == tr.reduce_planes(planes(ops, window=(0.0, 8.0)))["busy_s"]


def test_an_event_that_straddles_the_window_counts_only_inside_it():
    ops = [("k", 0.0, 3.0), ("c", 9.0, 12.0), ("late", 20.0, 21.0)]
    names = op_names(k=f"{USER}/bucket0/gram", c=None, late=f"{ITEM}/bucket0/solve")
    out = scopes.reduce_scopes(planes(ops, window=(1.0, 10.0)), names)
    assert out["stages"] == pytest.approx({"gram": 2.0, "solve": 0.0})
    assert out["outside"] == [["c", pytest.approx(1.0)]]
    assert out["busy_s"] == pytest.approx(3.0)


def test_a_program_without_scopes_reads_nothing_scoped():
    out = scopes.reduce_scopes(planes([("iteration.8 tpu_custom_call", 0.0, 1.0)]),
                               {DEVICE: {"iteration.8 tpu_custom_call": ""}})
    assert out["scoped_s"] == 0.0 and out["stages"] == {} and out["sides"] == {}
    assert scopes.reduce_scopes({"/host:CPU": {}}, {})["busy_s"] == 0.0


def test_two_chips_are_averaged():
    both = planes([("k", 0.0, 1.0)], window=(0.0, 2.0))
    both["/device:TPU:1"] = {tr.OP_LINE: [("k", 0.0, 2.0)]}
    names = {plane: {"k": f"jit(iteration)/{USER}/bucket0/gram/x:"} for plane in both}
    out = scopes.reduce_scopes(both, names)
    assert out["busy_s"] == pytest.approx(1.5)
    assert out["stages"] == pytest.approx({"gram": 1.5})


def test_recorded_v5e_trace_of_the_scoped_program():
    """65 ms of the second iteration of a traced chip run of the train cell
    (PR 24, TPU v5 lite, jax 0.9.0, seed 2147483700), cut to the device's
    ``XLA Ops`` and ``XLA Modules`` lines and the host plane: the last 3 ms of
    the user half-step, the item side's table copy, its first bucket's kernel
    (``als_gram_rhs.12``, whole) and the start of that bucket's solve. The
    window's annotation is kept whole."""
    names = scopes.read_op_names(RECORDED)
    assert set(names) == {DEVICE}
    table = names[DEVICE]
    assert len(table) == 334
    assert scopes.parse_scope(table["als_gram_rhs.12 tpu_custom_call"]) == (ITEM, "gram")
    assert table["als_gram_rhs.12 tpu_custom_call"] == (
        "jit(iteration)/als.item_half_step/bucket0/gram/als_gram_rhs/pallas_call:")
    assert scopes.parse_scope(table["pad_convert_fusion"]) == (ITEM, "gram")
    assert table["copy-done.136"] == ""  # the compiler's own: no op_name at all
    planes_ = tr.read_planes(RECORDED)
    out = scopes.reduce_scopes(planes_, names)
    assert out["busy_s"] == pytest.approx(tr.reduce_planes(planes_)["busy_s"])
    assert out["busy_s"] == pytest.approx(0.062275445)
    assert out["scoped_s"] == pytest.approx(0.062165081)
    assert out["stages"] == pytest.approx(
        {"gram": 0.058994081, "solve": 0.003132989, "assemble": 3.8011e-05})
    assert out["sides"] == pytest.approx({USER: 0.000214975, ITEM: 0.061950106})
    assert sum(out["stages"].values()) == pytest.approx(out["scoped_s"])
    assert sum(out["sides"].values()) == pytest.approx(out["scoped_s"])
    assert out["outside"][0] == ["copy-done.136", pytest.approx(3.154e-05)]
    assert all(name.split(".")[0].split(" ")[0] in
               ("copy-done", "slice-done", "copy-start", "slice-start", "copy", "fusion",
                "multiply_multiply_fusion", "divide_convert_fusion", "dynamic-update-slice")
               for name, _ in out["outside"])


def test_the_trace_of_the_unscoped_program_names_no_scope():
    """PR 23's recording holds no event metadata worth the name: nothing to
    join, nothing scoped, and no error."""
    old = os.path.join(HERE, "train_v5e.xplane.pb")
    out = scopes.reduce_scopes(tr.read_planes(old), scopes.read_op_names(old))
    assert out["busy_s"] == pytest.approx(0.198540337)
    assert out["scoped_s"] == 0.0


# ---- the readers ---------------------------------------------------------

def _reader(name):
    import run as bench_run

    return bench_run.load_module("layer_metrics", name).read


@pytest.fixture
def bare_program(monkeypatch):
    """A registry and a tracer that have seen nothing."""
    from predictionio_tpu.obs import trace
    from predictionio_tpu.utils import metrics

    registry, tracer = metrics.MetricsRegistry(), trace.Tracer()
    monkeypatch.setattr(metrics, "_GLOBAL_REGISTRY", registry)
    monkeypatch.setattr(trace, "_global", tracer)
    return registry, tracer


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_gives_none_without_its_source(name, bare_program):
    assert len(NEW_READERS) == 11
    read = _reader(name)
    assert read({}) is None
    assert read({"setup": {}, "iterations": 6}) is None  # untraced: no trace is looked for


def test_scope_readers_read_this_runs_trace(monkeypatch):
    monkeypatch.setattr(scopes, "newest_xplane", lambda: RECORDED)
    run = {"trace": {"busy_s": 0.062275445}, "iterations": 2}
    assert _reader("als_gram_ms")(run) == pytest.approx(1000 * 0.058994081 / 2)
    assert _reader("als_solve_ms")(run) == pytest.approx(1000 * 0.003132989 / 2)
    assert _reader("als_assemble_ms")(run) == pytest.approx(1000 * 3.8011e-05 / 2)
    assert _reader("als_user_half_step_ms")(run) == pytest.approx(1000 * 0.000214975 / 2)
    assert _reader("als_item_half_step_ms")(run) == pytest.approx(1000 * 0.061950106 / 2)
    assert _reader("als_scope_coverage")(run) == pytest.approx(99.82278)
    # a trace that names no scope: the parent's program
    monkeypatch.setattr(scopes, "newest_xplane",
                        lambda: os.path.join(HERE, "train_v5e.xplane.pb"))
    assert _reader("als_gram_ms")(run) is None
    assert _reader("als_scope_coverage")(run) is None


def test_newest_xplane_is_the_latest_traced_window(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "HERE", str(tmp_path))
    assert scopes.newest_xplane() is None
    for at, cell in enumerate(("a.serve", "b.train")):
        d = tmp_path / ".out" / cell / "trace" / "plugins" / "profile" / "2026_09_27"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (1000 + at, 1000 + at))
    (tmp_path / ".out" / "c.empty" / "trace").mkdir(parents=True)
    assert scopes.newest_xplane().endswith("b.train/trace/plugins/profile/2026_09_27/host.xplane.pb")


def test_program_readers_read_the_programs_own_objects(bare_program):
    registry, tracer = bare_program
    registry.inc("pio_jit_trace_seconds_total", amount=6.5)
    registry.inc("pio_jit_lower_seconds_total", amount=2.25)
    registry.inc("pio_jit_compile_seconds_total", amount=6.0)
    registry.inc("pio_jit_cache_misses_total", amount=0.0)
    with tracer.span("als.pack") as span:
        span.set_attr("edges", 100)
        span.set_attr("by_row", {"retained_edges": 70, "padded_slots": 80, "buckets": 2})
        span.set_attr("by_col", {"retained_edges": 20, "padded_slots": 40, "buckets": 1})
    assert _reader("jit_trace_lower_s")({}) == 8.75
    assert _reader("jit_compile_or_load_s")({}) == 6.0
    assert _reader("jit_cache_misses")({}) == 0.0  # a count of none, not a missing source
    assert _reader("als_slot_fill")({}) == pytest.approx(75.0)
    assert 0.0 <= _reader("als_pack_span_s")({}) < 1.0


def test_the_call_made_for_correct_compiles_nothing(capsys, monkeypatch):
    """The ``jit_*`` readers count the process up to their call, which takes
    in the one call the driver makes after the window: it runs the window's
    own program, so the counters stand where the window left them."""
    import run as bench_run

    from predictionio_tpu.parallel import als
    from predictionio_tpu.utils.metrics import global_registry

    stock, seen = als.make_iteration, []

    def watched(mesh, config):
        whole = stock(mesh, config)

        def iteration(*args):
            before = global_registry().counter_value("pio_jit_compiles_total")
            out = whole(*args)
            seen.append(global_registry().counter_value("pio_jit_compiles_total") - before)
            return out

        return iteration

    als._build_iteration.cache_clear()  # an earlier rehearsal built these shapes
    monkeypatch.setattr(als, "make_iteration", watched)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench_run.main(["--workload", "als-ml20m-r16.train-steady", "--seed", "7",
                           "--seconds", "1", "--trace", "0", "--rehearse", "1"]) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["correct"] is True
    assert seen[0] >= 1          # the first call traces and compiles
    assert seen[-1] == 0 and len(seen) >= 4   # warm-up, the window, then the call for correct
