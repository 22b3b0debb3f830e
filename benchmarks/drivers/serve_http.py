"""Train-persist-deploy through the program's normal path, then HTTP load.

``run_train(variant)`` (persist through ``engine.serialize_models`` and the
model store, as ``pio train`` does) -> ``create_query_server(variant)`` with
the default ``BatchConfig`` -> warm-up -> ``POST /queries.json`` from a child
process that never imports JAX (``traffic.py``). The engine is
``engines.seeded_factors_engine``: stock preparator, serving and query path,
factor tables drawn from the seed. Whether the loop is open or closed is a
parameter of the traffic mix.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchmarks import reference, seeded, trace_reduce, traffic as traffic_mod
from benchmarks.compiles import CompileCounter
from benchmarks.harness import REHEARSAL_CUT, check as _check, traced_window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def scrape(port: int) -> dict:
    """``/metrics`` as ``{name: value}``, label variants of a name summed."""
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
        text = r.read().decode()
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        name = name.partition("{")[0]
        if name.endswith("_bucket"):
            continue
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            pass
    return out


def percentile(sorted_values, q: float) -> float:
    """Nearest rank."""
    k = max(0, min(len(sorted_values) - 1, int(np.ceil(q * len(sorted_values))) - 1))
    return float(sorted_values[k])


def run(ctx) -> dict:
    mix, cfg = ctx.traffic, ctx.config
    cut = REHEARSAL_CUT if ctx.rehearse else 1
    n_users, n_items = cfg["model"]["users"] // cut, cfg["model"]["items"] // cut
    algo = dict(cfg["engine"]["algorithms"][0]["params"], seed=ctx.seed)
    rank = algo["rank"]
    clock = time.perf_counter
    setup: dict = {}

    store = os.path.join(ctx.out_dir, "store")
    shutil.rmtree(store, ignore_errors=True)
    os.makedirs(store)
    os.environ["PIO_FS_BASEDIR"] = store
    from predictionio_tpu.data import storage

    storage.reset()
    engine_dir = os.path.join(ctx.out_dir, "engine")
    os.makedirs(engine_dir, exist_ok=True)
    variant_path = os.path.join(engine_dir, "engine.json")
    with open(variant_path, "w") as f:
        json.dump({
            "id": ctx.cell,
            "engineFactory": cfg["engine"]["engineFactory"],
            "datasource": {"params": {
                "users": n_users, "items": n_items, "seed": ctx.seed,
                "ratings": cfg["model"]["ratings_in_memory"] // cut}},
            "preparator": cfg["engine"]["preparator"],
            "algorithms": [{"name": "als", "params": algo}],
            "serving": {"params": {}},
            "sparkConf": {"pio.mesh_shape": [ctx.chips, 1]},
        }, f)

    import jax

    from predictionio_tpu.models._als_common import retrieval_index
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.create_server import create_query_server
    from predictionio_tpu.workflow.json_extractor import load_engine_variant

    compiles = CompileCounter()
    variant = load_engine_variant(variant_path)
    t = clock()
    run_train(variant)
    setup["train_persist_s"] = clock() - t
    t = clock()
    thread, service = create_query_server(variant, host="127.0.0.1", port=0)
    thread.start()
    port = thread.port
    setup["deploy_s"] = clock() - t

    # warm every shape the batcher can send. The search pads a batch to a
    # power of two, but cuts its result back to the real size on the device,
    # which is one small program for each size up to max_batch_size
    t = clock()
    index = retrieval_index(service.models[0].als, service.algorithms[0]._retrieval)
    if index is not None:
        for size in range(1, service.batching.max_batch_size + 1):
            index.search(np.zeros((size, rank), np.float32))
    warm = traffic_mod.draw_users(mix, n_users, mix["warm_queries"], ctx.seed + 1)
    _warm_http(port, warm, mix["num"])
    setup["warm_s"] = clock() - t
    setup["compile_requests"], setup["compile_s"] = compiles.count, compiles.seconds

    seconds = min(ctx.seconds, mix["trace_seconds"]) if ctx.trace else ctx.seconds
    spec_path = os.path.join(ctx.out_dir, "traffic_spec.json")
    out_path = os.path.join(ctx.out_dir, "traffic_out.json")
    with open(spec_path, "w") as f:
        json.dump({"traffic": mix, "seed": ctx.seed, "seconds": seconds,
                   "host": "127.0.0.1", "port": port, "n_users": n_users,
                   "timeout_s": mix["timeout_s"], "out": out_path,
                   "closed_loop_budget": int(mix.get("budget_per_s", 0) * seconds)}, f)
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmarks.traffic", spec_path], cwd=ROOT,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items() if k != "PIO_FS_BASEDIR"},
    )
    try:
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not come up")
        ctx.say(setup=setup, port=port, shape={"users": n_users, "items": n_items},
                memory_after_warm=[d.memory_stats() for d in ctx.devices])
        before = scrape(port)
        compiles.reset()
        with traced_window(ctx.out_dir, ctx.trace) as trace_dir:
            setup_s = clock() - ctx.t0
            w0 = clock()
            child.stdin.write("go\n")
            child.stdin.flush()
            time.sleep(max(0.0, seconds - (clock() - w0)))
        after = scrape(port)
        in_window = compiles.count
        child.wait(timeout=seconds + mix["timeout_s"] + 60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        thread.stop()
        service.close()
    if child.returncode != 0:
        raise RuntimeError(f"the load generator exited {child.returncode}")

    with open(out_path) as f:
        rec = json.load(f)
    due, sent, done = (np.array(rec[k], dtype=np.float64) for k in ("due", "sent", "done"))
    status = np.array(rec["status"])
    ok = (status == 200) & ~np.isnan(done)
    attempted, failed = int(due.size), int((~ok).sum())
    latency_ms = np.sort(np.where(ok, (done - due) * 1000.0, mix["timeout_s"] * 1000.0))
    lag_ms = np.sort((sent - due) * 1000.0)
    end_to_end = {"setup_s": setup_s}
    if mix["loop"] == "open":
        end_to_end["serve_p50_ms"] = percentile(latency_ms, 0.50)
        end_to_end["serve_p95_ms"] = percentile(latency_ms, 0.95)
    else:
        end_to_end["serve_qps"] = float((ok & (done <= seconds)).sum()) / seconds
    ctx.say(window_s=seconds, attempted=attempted, failed=failed,
            generator_lag_p95_ms=percentile(lag_ms, 0.95),
            generator_lag_max_ms=float(lag_ms[-1]),
            latency_ms={q: percentile(latency_ms, q) for q in (0.5, 0.9, 0.95, 0.99)},
            completed_per_s=float(ok.sum()) / seconds)

    checks = _judge(ctx, rec, ok, n_users, n_items, rank, in_window)
    out = {"end_to_end": end_to_end, "attempted": attempted, "failed": failed,
           "checks": checks, "setup": setup,
           "counters": {"before": before, "after": after}}
    if ctx.trace:
        out["trace"] = trace_reduce.reduce_trace(trace_dir)
    return out


def _warm_http(port: int, rows, num: int) -> None:
    """Queries one at a time, then all at once: both batcher paths."""
    reqs = [traffic_mod.request_bytes("127.0.0.1", port, traffic_mod.body_for(int(r), num))
            for r in rows]
    conn = traffic_mod.Connection("127.0.0.1", port, 60.0)
    for req in reqs[: len(reqs) // 4]:
        conn.exchange(req)
    conn.close()

    def one(req):
        c = traffic_mod.Connection("127.0.0.1", port, 60.0)
        c.exchange(req)
        c.close()

    for _ in range(3):
        threads = [threading.Thread(target=one, args=(r,)) for r in reqs]
        for th in threads:
            th.start()
        for th in threads:
            th.join()


def _judge(ctx, rec, ok, n_users, n_items, rank, in_window) -> list:
    """A seeded sample of the window's answered requests against the exact
    float32 scores and the exact top of tables drawn anew from the seed."""
    check = ctx.traffic["correct"]
    users = seeded.make_factors(n_users, rank, ctx.seed, seeded.USER_STREAM)
    items = seeded.make_factors(n_items, rank, ctx.seed, seeded.ITEM_STREAM)
    answered = np.nonzero(ok)[0]
    rng = seeded.rng_for(ctx.seed, 5)
    sample = rng.choice(answered, size=min(check["sample"], answered.size), replace=False)
    worst_rel, unsorted, unknown_bad, recalls, compared = 0.0, 0, 0, [], 0
    low_rel = []
    if ctx.control:
        import ml_dtypes

        low = getattr(ml_dtypes, check["control_precision"])
        users_low = users.astype(low).astype(np.float32)
        items_low = items.astype(low).astype(np.float32)
    for k in sample:
        row = int(rec["rows"][k])
        listed = json.loads(rec["bodies"][k])["itemScores"]
        if row == traffic_mod.UNKNOWN_USER:
            unknown_bad += bool(listed)
            continue
        got_rows = np.array([seeded.item_row(e["item"]) for e in listed], dtype=np.int64)
        got = np.array([e["score"] for e in listed], dtype=np.float64)
        want = reference.exact_scores(users, items, row, got_rows).astype(np.float64)
        scale = np.abs(want).max()
        worst_rel = max(worst_rel, float(np.abs(got - want).max() / scale))
        unsorted += bool((np.diff(got) > 0).any())
        top = reference.exact_top(users, items, row, ctx.traffic["num"])
        recalls.append(len(set(top.tolist()) & set(got_rows.tolist())) / len(top))
        compared += 1
        if ctx.control:
            lo = reference.exact_scores(users_low, items_low, row, got_rows)
            low_rel.append(float(np.abs(lo - want).max() / scale))
    shortfall = 1.0 - float(np.mean(recalls)) if recalls else 1.0
    if ctx.control:
        ctx.say(control=check["control_precision"], score_rel_err_smallest=min(low_rel),
                score_rel_err_median=float(np.median(low_rel)),
                limit=check["score_rel_err_limit"],
                correct=bool(max(low_rel) <= check["score_rel_err_limit"]))
    ctx.say(compared=compared, sampled=int(sample.size))
    return [
        _check("score_rel_err", worst_rel, check["score_rel_err_limit"]),
        _check("recall_at_num_shortfall", shortfall, check["recall_shortfall_limit"]),
        _check("unsorted_lists", unsorted, 0),
        _check("unknown_user_answers", unknown_bad, 0),
        _check("compilations_in_window", in_window, 0),
        _check("nothing_compared", int(compared == 0), 0),
    ]

