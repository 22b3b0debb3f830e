"""``als-msd-r128.train-sharded``: the controls of its ``correct``, its
readers on hand-made planes of four chips, and its counts by hand.

The rehearsal of the cell itself is ``test_rehearsal.py``'s (every file under
``workloads/``), on the four host devices ``conftest.py`` asks for.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from benchmarks import (counts_sharded, reference, reference_implicit, scopes_sharded,  # noqa: E402
                        seeded, seeded_plays, trace_reduce as tr)

CELL = "als-msd-r128.train-sharded"
USER, ITEM = "als.user_half_step", "als.item_half_step"
RANK = 128

with open(os.path.join(HERE, "..", "workloads", CELL + ".json")) as f:
    CHECK = json.load(f)["traffic"]["correct"]
with open(os.path.join(HERE, "..", "configs", "als-msd-r128.json")) as f:
    CONFIG = json.load(f)


# ---- the data and the reference ------------------------------------------

def test_plays_are_whole_heavy_tailed_and_leave_the_structure_alone():
    args = (CONFIG["data"], 200_000, 3_000, 1_200)
    users, items, plays = seeded_plays.make_plays(*args, 7)
    again = seeded_plays.make_plays(*args, 7)
    assert all(np.array_equal(a, b) for a, b in zip((users, items, plays), again))
    assert plays.dtype == np.float32 and plays.min() == 1 and np.all(plays == np.rint(plays))
    assert 0.6 < np.mean(plays == 1) < 0.75 and plays.max() > 100  # 68.5% at one play
    # another seed relabels and redraws; each side keeps its multiset of degrees
    users2, items2, plays2 = seeded_plays.make_plays(*args, 8)
    assert not np.array_equal(plays, plays2)
    for a, b in ((users, users2), (items, items2)):
        assert np.array_equal(np.sort(np.bincount(a)), np.sort(np.bincount(b)))


def test_reference_solves_the_papers_normal_equations_for_one_row():
    """Three songs, rank 2, one user who played song 0 twice and song 2 five
    times: Y'Y over all three songs, the confidences on the two played."""
    table = np.array([[1.0, 0.0], [0.0, 2.0], [1.0, 1.0]])
    own, other = np.array([0, 0]), np.array([0, 2])
    plays = np.array([2.0, 5.0], dtype=np.float32)
    reg, alpha = 0.5, 3.0
    yty = table.T @ table
    fix = 6.0 * np.outer(table[0], table[0]) + 15.0 * np.outer(table[2], table[2])
    rhs = 7.0 * table[0] + 16.0 * table[2]
    want = np.linalg.solve(yty + fix + reg * np.eye(2), rhs)
    got = reference_implicit.half_step(own, other, plays, table, [0], reg, alpha)
    assert got[0] == pytest.approx(want, rel=1e-12)
    # a row over the cap keeps its last edges: with cap 1, song 2 alone
    capped = reference_implicit.half_step(own, other, plays, table, [0], reg, alpha, cap=1)
    alone = np.linalg.solve(yty + 15.0 * np.outer(table[2], table[2]) + reg * np.eye(2),
                            16.0 * table[2])
    assert capped[0] == pytest.approx(alone, rel=1e-12)


# ---- the controls of correct: each has to fail ---------------------------

def _small(seed: int):
    """The configuration's plays at a size a test holds, and bfloat16-stored
    rank-128 tables for both sides: the state a half-step starts from."""
    import ml_dtypes

    n_users, n_items = 3_000, 1_200
    users, items, plays = seeded_plays.make_plays(CONFIG["data"], 200_000, n_users,
                                                  n_items, seed)
    engine = CONFIG["engine"]
    params = engine["algorithms"][0]["params"]
    tables = [seeded.make_factors(n, RANK, seed, stream)
              .astype(ml_dtypes.bfloat16).astype(np.float32)
              for n, stream in ((n_users, seeded.USER_STREAM), (n_items, seeded.ITEM_STREAM))]
    return (users, items, plays, tables, params["lambda"], params["alpha"],
            engine["preparator"]["params"]["maxEventsPerUser"])


@pytest.mark.parametrize("side", ["user", "item"])
@pytest.mark.parametrize("seed", [1, 3_000_000_019])
def test_limit_separates_bf16_from_fp8(seed, side):
    users, items, plays, (u, v), reg, alpha, cap = _small(seed)
    own, other, table = (users, items, v) if side == "user" else (items, users, u)
    rows = seeded.sample_rows(int(own.max()) + 1, 200, seed, 3)
    args = (own, other, plays, table, rows, reg, alpha, cap)
    want = reference_implicit.half_step(*args)
    stated = reference_implicit.half_step(*args, precision="bfloat16")
    control = reference_implicit.half_step(*args, precision=CHECK["control_precision"])
    limit = CHECK["half_step_rel_err_limit"]
    assert reference.relative_error(stated, want) * 2 < limit
    assert reference.relative_error(control, want) > limit * 2


def _a_quarter_of_the_edges(users, items, plays, u, v, reg, alpha, cap, rows):
    keep = np.arange(users.size) % 4 == 0
    return reference_implicit.half_step(items[keep], users[keep], plays[keep], u, rows,
                                        reg, alpha, cap)


def _stale_song_factors(users, items, plays, u, v, reg, alpha, cap, rows):
    """The user half-step from the song factors of an iteration before: here,
    songs solved from other users than those the window left."""
    older = seeded.make_factors(u.shape[0], RANK, 99, seeded.USER_STREAM)
    v_old = reference_implicit.half_step(items, users, plays, older,
                                         np.arange(v.shape[0]), reg, alpha, cap)
    return reference_implicit.half_step(users, items, plays, v_old, rows, reg, alpha, cap)


def _the_explicit_equations(users, items, plays, u, v, reg, alpha, cap, rows):
    return reference.half_step(items, users, plays, u, rows, reg, cap)


@pytest.mark.parametrize("fault", [_a_quarter_of_the_edges, _stale_song_factors,
                                   _the_explicit_equations])
def test_a_faulty_half_step_is_outside_the_limit(fault):
    users, items, plays, (u, v), reg, alpha, cap = _small(5)
    stale = fault is _stale_song_factors
    rows = seeded.sample_rows(u.shape[0] if stale else v.shape[0], 200, 5, 4)
    if stale:
        want = reference_implicit.half_step(users, items, plays, v, rows, reg, alpha, cap)
    else:
        want = reference_implicit.half_step(items, users, plays, u, rows, reg, alpha, cap)
    got = fault(users, items, plays, u, v, reg, alpha, cap, rows)
    assert reference.relative_error(got, want) > 3 * CHECK["half_step_rel_err_limit"]


# ---- the readers on four hand-made planes --------------------------------

def _four_chips():
    """Each chip: a gather, an exchange nested in ``gram``, a solve, an
    exchange nested in ``assemble``, an unscoped copy. Chip 3 solves longer."""
    planes, names = {"/host:CPU": {"main": [(tr.WINDOW_NAME, 0.0, 10.0)]}}, {}
    for chip in range(4):
        solve_end = 7.0 + (1.0 if chip == 3 else 0.0)
        planes[f"/device:TPU:{chip}"] = {tr.OP_LINE: [
            ("gather", 0.0, 2.0), ("all_to_all.1", 2.0, 2.5), ("chol", 3.0, solve_end),
            ("all-gather.7", 8.0, 8.0 + 0.25 * (chip + 1)), ("copy.9", 9.0, 9.5),
            ("late", 9.9, 12.0),  # straddles the window's end
        ]}
        names[f"/device:TPU:{chip}"] = {
            "gather": f"jit(iteration)/{USER}/bucket0/shard_map/gram/gather:",
            "all_to_all.1": f"jit(iteration)/{USER}/bucket0/shard_map/while/body/gram/exchange/all_to_all:",
            "chol": f"jit(iteration)/{USER}/bucket0/shard_map/solve/cholesky:",
            "all-gather.7": f"jit(iteration)/{ITEM}/assemble/exchange/sharding_constraint:",
            "copy.9": "",
            "late": f"jit(iteration)/{ITEM}/bucket0/gram/exchange/all_to_all:",
        }
    return planes, names


def test_exchange_is_found_under_either_stage_and_clipped_to_the_window():
    found = scopes_sharded.reduce_planes(*_four_chips(), "exchange")
    assert found["component_s"] == pytest.approx([0.85, 1.1, 1.35, 1.6])  # 0.5 + 0.25k + 0.1
    assert found["busy_s"] == pytest.approx([7.35, 7.6, 7.85, 9.1])
    assert scopes_sharded.has_component(f"jit(f)/{USER}/assemble/exchange/x:", "exchange")
    assert not scopes_sharded.has_component("jit(f)/exchange/x:", "exchange")  # no als. scope
    assert not scopes_sharded.has_component(f"jit(f)/{USER}/bucket0/gram/gather:", "exchange")


def _readers(monkeypatch, reduced):
    from run import load_module

    monkeypatch.setattr(scopes_sharded, "of_run", lambda run, component="exchange": reduced)
    return {name: load_module("layer_metrics", name).read
            for name in ("als_exchange_ms", "als_exchange_ici_share", "als_chip_busy_spread")}


def test_the_three_readers_on_the_four_planes(monkeypatch):
    read = _readers(monkeypatch, scopes_sharded.reduce_planes(*_four_chips(), "exchange"))
    run = {"trace": {"busy_s": 1.0}, "iterations": 2, "device_kind": "TPU v5 lite",
           "exchange_bytes_per_iteration": 20e9}
    assert read["als_exchange_ms"](run) == pytest.approx(1000 * 1.225 / 2)
    # 20e9 bytes at 200e9 bytes/s is 0.1 s; taken in 0.6125 s: 16.3%
    assert read["als_exchange_ici_share"](run) == pytest.approx(100 * 0.1 / 0.6125)
    # busy 7.35 .. 9.1 around a mean of 7.975
    assert read["als_chip_busy_spread"](run) == pytest.approx(100 * 1.75 / 7.975)


@pytest.mark.parametrize("reduced", [
    None,                                                    # an untraced run
    {"busy_s": [5.0], "component_s": [0.0]},                 # one chip, no exchange scope
    {"busy_s": [], "component_s": []},                       # no device plane
])
def test_the_readers_give_none_without_their_source(monkeypatch, reduced):
    read = _readers(monkeypatch, reduced)
    run = {"trace": {"busy_s": 1.0}, "iterations": 2, "device_kind": "TPU v5 lite"}
    assert all(reader(run) is None for reader in read.values())


def test_of_run_reads_nothing_from_an_untraced_run():
    assert scopes_sharded.of_run({"iterations": 3}) is None
    assert scopes_sharded.exchange_ms({"trace": None, "iterations": 3}) is None


# ---- the counts by hand ---------------------------------------------------

def test_exchange_bytes_at_a_toy_shape():
    # 2 x 2 chips, rank 4, bf16 (8 bytes a factor row). 40 + 24 retained edges:
    # a chip gathers 64 / 4 = 16 rows, half of them for the other chip of its
    # model pair: 8 x 8 = 64 bytes. 12 + 8 real rows: a chip solves 5 and sends
    # each to the one other chip of the data axis: 5 x 8 = 40. Y'Y: two 4 x 4
    # float32 partial sums, 128 bytes.
    assert counts_sharded.exchange_bytes_per_chip(40, 24, 12, 8, 4, 2, 2, 2) == 64 + 40 + 128
    # tables not sharded (model axis 1): nothing gathered for another chip
    assert counts_sharded.exchange_bytes_per_chip(40, 24, 12, 8, 4, 2, 4, 1) == 5 * 3 * 8 + 128
    # one chip sends nothing
    assert counts_sharded.exchange_bytes_per_chip(40, 24, 12, 8, 4, 2, 1, 1) == 0


def test_iteration_bytes_per_chip_at_a_toy_shape():
    # an edge: 4 + 4 + 4 x 2 = 16 bytes, 64 edges: 1,024. A row: a 4 x 4 Gram
    # and a 4-vector in float32, 80 bytes, and its own 8 bytes read again for
    # Y'Y: 20 rows x 88 = 1,760. Over 4 chips: 696.
    assert counts_sharded.als_implicit_iteration_bytes_per_chip(40, 24, 12, 8, 4, 2, 4) == 696


def test_ici_share_and_unknown_devices():
    # 200e9 bytes at 200 GB/s is one second; taken in four: 25%
    assert counts_sharded.ici_share_pct(200e9, 4.0, "TPU v5 lite") == pytest.approx(25.0)
    with pytest.raises(KeyError, match="no published inter-chip bandwidth"):
        counts_sharded.ici_peak("cpu")


def test_the_cells_exchange_count_at_its_published_size():
    data = CONFIG["data"]
    sent = counts_sharded.exchange_bytes_per_chip(
        data["by_row"]["retained_edges"], data["by_col"]["retained_edges"],
        data["users"], data["items"], RANK, 2, 2, 2)
    # 69,994,547 edges / 4 x 1/2 x 256 B + 1,403,864 rows / 4 x 256 B + 131,072
    assert sent == pytest.approx(2_239_825_504 + 89_847_296 + 131_072)
