"""The control of ``correct``: the reference one storage precision lower has
to land outside the limit, and the stated precision inside it, with room.

On the chip the same two numbers were read at the cell's own size (PERF.md
section 2 gives the readings the limits were set from); here at a size a test
run holds.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import reference, seeded  # noqa: E402


def _traffic(cell: str) -> dict:
    with open(os.path.join(HERE, "..", "workloads", cell + ".json")) as f:
        return json.load(f)["traffic"]


def _config(name: str) -> dict:
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def _small_als(seed: int):
    """The train configuration's ratings at a size a test holds, its cap, and
    bfloat16-stored factors for both sides: the state a half-step starts from."""
    import ml_dtypes

    config = _config("als-ml20m-r16")
    n_users, n_items = 2_000, 400
    users, items, ratings = seeded.make_ratings(config["data"], 300_000, n_users,
                                                n_items, seed)
    cap = config["engine"]["preparator"]["params"]["maxEventsPerUser"]
    factors = [seeded.make_factors(n, 16, seed, stream)
               .astype(ml_dtypes.bfloat16).astype(np.float32)
               for n, stream in ((n_users, seeded.USER_STREAM),
                                 (n_items, seeded.ITEM_STREAM))]
    return users, items, ratings, cap, factors


@pytest.mark.parametrize("side", ["user", "item"])
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_train_half_step_limit_separates_bf16_from_fp8(seed, side):
    check = _traffic("als-ml20m-r16.train-steady")["correct"]
    limit = check["half_step_rel_err_limit"]
    users, items, ratings, cap, (u, v) = _small_als(seed)
    own, other, gathered = (users, items, v) if side == "user" else (items, users, u)
    rows = seeded.sample_rows(int(own.max()) + 1, 300, seed, 3)
    args = (own, other, ratings, gathered, rows, 0.1, cap)
    want = reference.half_step(*args)
    stated = reference.half_step(*args, precision="bfloat16")
    control = reference.half_step(*args, precision=check["control_precision"])
    assert reference.relative_error(stated, want) * 2 < limit
    assert reference.relative_error(control, want) > limit * 3


def _quarter_of_the_edges(users, items, ratings, u, cap):
    keep = np.arange(users.size) % 4 == 0
    rows = np.arange(int(items.max()) + 1)
    return reference.half_step(items[keep], users[keep], ratings[keep], u, rows, 0.1, cap)


def _first_of_the_cap_not_last(users, items, ratings, u, cap):
    rows = np.arange(int(items.max()) + 1)
    back = slice(None, None, -1)  # the cap then keeps what came first
    return reference.half_step(items[back], users[back], ratings[back], u, rows, 0.1, cap)


def _stale(users, items, ratings, u, cap):
    """Item factors solved against the user factors of an iteration before."""
    rows = np.arange(int(users.max()) + 1)
    v_now = reference.half_step(items, users, ratings, u,
                                np.arange(int(items.max()) + 1), 0.1, cap)
    u_next = reference.half_step(users, items, ratings, v_now, rows, 0.1, cap)
    return v_now, u_next


@pytest.mark.parametrize("fault", ["quarter_of_the_edges", "first_of_the_cap_not_last",
                                   "stale"])
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_item_side_faults_land_outside_the_item_limit(seed, fault):
    """What the fit's RMSE let through: an item update on part of the data, on
    other ratings than the cap keeps, or not made at all."""
    limit = _traffic("als-ml20m-r16.train-steady")["correct"]["half_step_rel_err_limit"]
    users, items, ratings, cap, (u, _) = _small_als(seed)
    rows = np.arange(int(items.max()) + 1)
    if fault == "stale":
        got, u = _stale(users, items, ratings, u, cap)
    else:
        got = globals()["_" + fault](users, items, ratings, u, cap)
    want = reference.half_step(items, users, ratings, u, rows, 0.1, cap)
    assert reference.relative_error(got, want) > limit * 3


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_serve_score_limit_separates_float32_from_bf16(seed):
    check = _traffic("als-msd-r16.serve-steady")["correct"]
    limit = check["score_rel_err_limit"]
    import ml_dtypes

    low = getattr(ml_dtypes, check["control_precision"])
    u = seeded.make_factors(500, 16, seed, seeded.USER_STREAM)
    v = seeded.make_factors(5_000, 16, seed, seeded.ITEM_STREAM)
    u_low, v_low = (a.astype(low).astype(np.float32) for a in (u, v))
    smallest = np.inf
    for user in range(50):
        top = reference.exact_top(u, v, user, 10)
        want = reference.exact_scores(u, v, user, top).astype(np.float64)
        # float32 summed in another order: what a sound program may differ by
        other = (v[top].astype(np.float32)[:, ::-1] @ u[user][::-1]).astype(np.float64)
        assert np.abs(other - want).max() / np.abs(want).max() * 3 < limit
        got = reference.exact_scores(u_low, v_low, user, top)
        smallest = min(smallest, float(np.abs(got - want).max() / np.abs(want).max()))
    assert smallest > limit * 3
