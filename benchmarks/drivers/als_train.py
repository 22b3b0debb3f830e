"""Resident ALS loop: pack and transfer once, then iterations for the window.

Mirrors ``parallel/als.py:als_fit``'s loop (same preparator packing, same
``device_put_blocks``, same ``make_iteration`` program, factors donated from
one call to the next) without calling it: a fit runs a count of iterations,
a window runs for seconds. A change to ``als_fit``'s own dispatch or to
``fit_with_checkpoint`` does not show here (PERF.md, Open questions).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts, reference, seeded, trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.harness import REHEARSAL_CUT, check as _check, traced_window


def _sizes(data: dict, rehearse: bool) -> tuple[int, int, int]:
    if not rehearse:
        return data["users"], data["items"], data["ratings"]
    side = REHEARSAL_CUT ** 0.5
    return (int(data["users"] / side), int(data["items"] / side),
            data["ratings"] // REHEARSAL_CUT)


def _retained(ids: np.ndarray, rows: int, cap: int | None) -> int:
    count = np.bincount(ids, minlength=rows)
    return int(np.minimum(count, cap).sum() if cap else count.sum())


def run(ctx) -> dict:
    import jax
    import ml_dtypes
    from jax.sharding import NamedSharding, PartitionSpec

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models._als_common import (
        prepare_als_data, resolve_factor_sharding)
    from predictionio_tpu.models.recommendation.engine import ALSAlgorithm
    from predictionio_tpu.parallel import als as als_mod
    from predictionio_tpu.parallel.mesh import fetch_global, put_global
    from predictionio_tpu.workflow.context import RuntimeContext

    traffic, engine = ctx.traffic, ctx.config["engine"]
    n_users, n_items, n_edges = _sizes(ctx.config["data"], ctx.rehearse)
    compiles = CompileCounter()
    setup: dict = {}
    clock = time.perf_counter

    t = clock()
    users, items, ratings = seeded.make_ratings(
        ctx.config["data"], n_edges, n_users, n_items, ctx.seed)
    setup["ratings_s"] = clock() - t

    rctx = RuntimeContext({"pio.mesh_shape": [ctx.chips, 1]})
    mesh = rctx.mesh
    prep = Params(engine["preparator"]["params"])
    config = ALSAlgorithm(Params(engine["algorithms"][0]["params"]))._config()
    config = resolve_factor_sharding(config, mesh)
    cap = prep.get_or("maxEventsPerUser", None)

    t = clock()
    data = prepare_als_data(rctx, prep, users, items, ratings, n_users, n_items,
                            times=None)
    setup["als_pack_s"] = clock() - t

    row = NamedSharding(mesh, PartitionSpec("data"))
    rep = NamedSharding(mesh, PartitionSpec())
    put = lambda a: put_global(a, row)  # noqa: E731
    dtype = np.float32 if config.dtype == "float32" else ml_dtypes.bfloat16

    def slotted(side, stream):
        out = np.zeros((side.total_slots, config.rank), dtype=np.float32)
        out[side.slot_of] = seeded.make_factors(side.num_rows, config.rank,
                                                ctx.seed, stream)
        return out.astype(dtype)

    t = clock()
    u_blocks = als_mod.device_put_blocks(data.by_row, put)
    i_blocks = als_mod.device_put_blocks(data.by_col, put)
    uf = put(slotted(data.by_row, seeded.USER_STREAM))
    itf = put(slotted(data.by_col, seeded.ITEM_STREAM))
    reg = put_global(np.float32(config.reg), rep)
    alpha = put_global(np.float32(config.alpha), rep)
    jax.block_until_ready((u_blocks, i_blocks, uf, itf))
    setup["als_h2d_s"] = clock() - t

    iteration = als_mod.make_iteration(mesh, config)
    solver = als_mod.resolve_solver(config.solver, ctx.devices[0].platform)

    def sync(x) -> None:
        np.asarray(jax.device_get(x[:1, :1]))  # a hard sync of the donated chain

    def step(n: int) -> None:
        nonlocal uf, itf
        for _ in range(n):
            uf, itf = iteration(u_blocks, i_blocks, uf, itf, reg, alpha)

    t = clock()
    step(1)
    sync(uf)
    setup["first_call_s"] = clock() - t
    t = clock()
    step(traffic["warm_iterations"])
    sync(uf)
    per_iter = (clock() - t) / traffic["warm_iterations"]
    setup["compile_requests"] = compiles.count
    setup["compile_s"] = compiles.seconds
    ctx.say(setup=setup, solver=solver, warm_s_per_iteration=per_iter,
            shape={"users": n_users, "items": n_items, "ratings": n_edges},
            memory_after_warm=[d.memory_stats() for d in ctx.devices])

    # ---- the window: iterations only ------------------------------------
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    chunk_max = traffic["sync_every"]
    spans: list = []
    done = 0
    compiles.reset()
    with traced_window(ctx.out_dir, ctx.trace) as trace_dir:
        setup_s = clock() - ctx.t0
        w0 = clock()
        while True:
            left = seconds - (clock() - w0)
            if left <= 0 and done:
                break
            n = int(max(1, min(chunk_max, left / per_iter)))
            a = clock()
            step(n)
            b = clock()
            sync(uf)
            c = clock()
            spans += [("bench.dispatch", a - w0, b - w0), ("bench.sync", b - w0, c - w0)]
            done += n
            per_iter = (c - w0) / done
        window_s = clock() - w0
        in_window = compiles.count
    memory_after_window = [d.memory_stats() for d in ctx.devices]

    # ---- correct: the state the window left, one more call of its program;
    # both half-steps of that call against the float64 reference, row by row
    v_prev = fetch_global(itf)[data.by_col.slot_of].astype(np.float32)
    step(1)
    u_new = fetch_global(uf)[data.by_row.slot_of].astype(np.float32)
    v_new = fetch_global(itf)[data.by_col.slot_of].astype(np.float32)
    check = traffic["correct"]
    limit = check["half_step_rel_err_limit"]
    sides = {  # name: own ids, other ids, factors gathered, factors produced, sample's stream
        "user": (users, items, v_prev, u_new, 3),
        "item": (items, users, u_new, v_new, 4),
    }
    checks = []
    for side, (own, other, gathered, produced, stream) in sides.items():
        rows = seeded.sample_rows(produced.shape[0], check["half_step_rows"],
                                  ctx.seed, stream)
        want = reference.half_step(own, other, ratings, gathered, rows, config.reg, cap)
        rel = reference.relative_error(produced[rows], want)
        checks.append(_check(f"{side}_half_step_rel_err", rel, limit))
        if ctx.control:
            low = reference.half_step(own, other, ratings, gathered, rows, config.reg,
                                      cap, precision=check["control_precision"])
            low_rel = reference.relative_error(low, want)
            ctx.say(control=check["control_precision"], side=side,
                    half_step_rel_err=low_rel, limit=limit,
                    correct=bool(low_rel <= limit))
    # a sanity check beside them: the user half-step's own residual on the
    # ratings its sampled rows keep, over that of predicting their mean
    rows = seeded.sample_rows(n_users, check["half_step_rows"], ctx.seed, 3)
    order, starts, ends = reference.kept_edges(users, rows, cap)
    edges = np.concatenate([order[lo:hi] for lo, hi in zip(starts, ends)])
    rmse = reference.rmse(u_new, v_prev, users[edges], items[edges], ratings[edges])
    rmse_ratio = rmse / reference.global_mean_rmse(ratings[edges])
    nonfinite = int((~np.isfinite(u_new)).sum() + (~np.isfinite(v_new)).sum())
    checks += [
        _check("rmse_over_global_mean", rmse_ratio, check["rmse_ratio_limit"]),
        _check("nonfinite_factors", nonfinite, 0),
        _check("compilations_in_window", in_window, 0),
    ]

    retained = {"by_row": _retained(users, n_users, cap),
                "by_col": _retained(items, n_items, cap)}
    least_bytes = counts.als_iteration_bytes(
        retained["by_row"], retained["by_col"], n_users, n_items, config.rank,
        np.dtype(dtype).itemsize)
    ctx.say(window_s=window_s, iterations=done, retained_edges=retained,
            padded_slots={"by_row": data.by_row.padded_slots,
                          "by_col": data.by_col.padded_slots},
            least_bytes_per_iteration=least_bytes,
            memory_after_window=memory_after_window)
    out = {
        "end_to_end": {"train_iters_per_s": done / window_s, "setup_s": setup_s},
        "attempted": done, "failed": 0, "checks": checks, "setup": setup,
        "iterations": done, "least_bytes_per_iteration": least_bytes,
        "device_kind": ctx.devices[0].device_kind,
    }
    if ctx.trace:
        out["trace"] = trace_reduce.reduce_trace(trace_dir, spans)
    return out

