"""Resident implicit ALS loop on a mesh the cell's traffic gives: pack and
transfer once, then whole iterations for the window.

``als_train.py``'s loop with three things of its own: the mesh comes from
``traffic["mesh"]`` (data x model) and the factor tables are placed as
``parallel/als.py:als_fit`` places them for the layout the mesh resolves to
(over ``model`` when it has a model axis, the CSR rows over ``data``); the
interactions are play counts (``seeded_plays.make_plays``); and ``correct`` is
the implicit reference (``reference_implicit.half_step``). The same
``prepare_als_data`` -> ``device_put_blocks`` -> ``make_iteration`` as a fit.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts_sharded, reference, reference_implicit, seeded
from benchmarks import seeded_plays, trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.drivers.als_train import _retained, _sizes
from benchmarks.harness import check as _check, traced_window


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

    if not hasattr(als_mod, "block_plan"):
        # a program from before PR 26 works every block whole: compiled for a
        # described v5e 2x2 this cell's iteration then wants 28.29 GB of a
        # chip's 15.75 (RESOURCE_EXHAUSTED, after two minutes of set-up)
        raise SystemExit(
            f"{ctx.cell}: this program cannot work a block in row chunks"
            " (parallel/als.py has no block_plan), and the cell's rank-128"
            " blocks do not fit a chip whole")
    traffic, engine = ctx.traffic, ctx.config["engine"]
    d, m = traffic["mesh"]["data"], traffic["mesh"]["model"]
    if len(jax.devices()) != d * m:
        raise SystemExit(
            f"{ctx.cell} runs on a data={d} x model={m} mesh; JAX has"
            f" {len(jax.devices())} device(s) (a rehearsal takes them from"
            f" XLA_FLAGS=--xla_force_host_platform_device_count={d * m})")
    n_users, n_items, n_edges = _sizes(ctx.config["data"], ctx.rehearse)
    compiles = CompileCounter()
    setup: dict = {}
    clock = time.perf_counter

    t = clock()
    users, items, plays = seeded_plays.make_plays(
        ctx.config["data"], n_edges, n_users, n_items, ctx.seed)
    setup["ratings_s"] = clock() - t

    rctx = RuntimeContext({"pio.mesh_shape": [d, m]})
    mesh = rctx.mesh
    prep = Params(engine["preparator"]["params"])
    config = ALSAlgorithm(Params(engine["algorithms"][0]["params"]))._config()
    config = resolve_factor_sharding(config, mesh)
    cap = prep.get_or("maxEventsPerUser", None)

    t = clock()
    data = prepare_als_data(rctx, prep, users, items, plays, n_users, n_items,
                            times=None)
    setup["als_pack_s"] = clock() - t

    sharded = config.factor_sharding == "model"
    for side in (data.by_row, data.by_col):  # als_fit's own check of the layout
        if sharded and (side.total_slots % m or any(
                b.indices.shape[0] % (d * m) for b in side.blocks)):
            raise ValueError("bucket rows do not divide over data x model")
    row = NamedSharding(mesh, PartitionSpec("data"))
    fsh = NamedSharding(mesh, PartitionSpec("model")) if sharded else row
    rep = NamedSharding(mesh, PartitionSpec())
    dtype = np.float32 if config.dtype == "float32" else ml_dtypes.bfloat16

    def slotted(side, stream):
        out = np.zeros((side.total_slots, config.rank), dtype=np.float32)
        out[side.slot_of] = seeded.make_factors(side.num_rows, config.rank,
                                                ctx.seed, stream)
        return out.astype(dtype)

    t = clock()
    put_row = lambda a: put_global(a, row)  # noqa: E731
    u_blocks = als_mod.device_put_blocks(data.by_row, put_row)
    i_blocks = als_mod.device_put_blocks(data.by_col, put_row)
    uf = put_global(slotted(data.by_row, seeded.USER_STREAM), fsh)
    itf = put_global(slotted(data.by_col, seeded.ITEM_STREAM), fsh)
    reg = put_global(np.float32(config.reg), rep)
    alpha = put_global(np.float32(config.alpha), rep)
    jax.block_until_ready((u_blocks, i_blocks, uf, itf))
    setup["als_h2d_s"] = clock() - t

    iteration = als_mod.make_iteration(mesh, config)
    blocks = als_mod.block_paths(data, config, mesh)

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
    ctx.say(setup=setup, mesh={"data": d, "model": m},
            factor_sharding=config.factor_sharding, solver=config.solver,
            blocks=blocks, warm_s_per_iteration=per_iter,
            shape={"users": n_users, "items": n_items, "ratings": n_edges},
            packed={"by_row": [b.indices.shape for b in data.by_row.blocks],
                    "by_col": [b.indices.shape for b in data.by_col.blocks]},
            memory_after_warm=[dev.memory_stats() for dev in ctx.devices])

    # ---- the window: whole iterations only -------------------------------
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
    memory_after_window = [dev.memory_stats() for dev in ctx.devices]

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
        args = (own, other, plays, gathered, rows, config.reg, config.alpha, cap)
        want = reference_implicit.half_step(*args)
        rel = reference.relative_error(produced[rows], want)
        checks.append(_check(f"{side}_half_step_rel_err", rel, limit))
        if ctx.control:
            low = reference_implicit.half_step(
                *args, precision=check["control_precision"])
            low_rel = reference.relative_error(low, want)
            ctx.say(control=check["control_precision"], side=side,
                    half_step_rel_err=low_rel, limit=limit,
                    correct=bool(low_rel <= limit))
    nonfinite = int((~np.isfinite(u_new)).sum() + (~np.isfinite(v_new)).sum())
    checks += [
        _check("nonfinite_factors", nonfinite, 0),
        _check("compilations_in_window", in_window, 0),
    ]

    retained = {"by_row": _retained(users, n_users, cap),
                "by_col": _retained(items, n_items, cap)}
    sizes = (retained["by_row"], retained["by_col"], n_users, n_items,
             config.rank, np.dtype(dtype).itemsize)
    least_bytes = counts_sharded.als_implicit_iteration_bytes_per_chip(*sizes, d * m)
    exchange_bytes = counts_sharded.exchange_bytes_per_chip(
        *sizes, d, m if sharded else 1)
    ctx.say(window_s=window_s, iterations=done, retained_edges=retained,
            kept_share={k: v / n_edges for k, v in retained.items()},
            padded_slots={"by_row": data.by_row.padded_slots,
                          "by_col": data.by_col.padded_slots},
            least_bytes_per_iteration=least_bytes,
            exchange_bytes_per_iteration=exchange_bytes,
            memory_after_window=memory_after_window)
    out = {
        "end_to_end": {"train_iters_per_s": done / window_s, "setup_s": setup_s},
        "attempted": done, "failed": 0, "checks": checks, "setup": setup,
        "iterations": done, "least_bytes_per_iteration": least_bytes,
        "exchange_bytes_per_iteration": exchange_bytes,
        "device_kind": ctx.devices[0].device_kind,
    }
    if ctx.trace:
        out["trace"] = trace_reduce.reduce_trace(trace_dir, spans)
    return out
