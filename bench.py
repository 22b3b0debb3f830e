"""Round benchmark: ALS iters/sec/chip at MovieLens-20M scale.

Metric definition (BASELINE.json): "ALS iters/sec/chip on MovieLens-20M";
north star >=10x Spark-local ALS wall-clock. The reference publishes no
numbers and Spark is not in this image (BASELINE.md), so ``vs_baseline`` is
the measured speedup over the same computation on the host CPU backend --
the closest available stand-in for the reference's single-machine
``local[*]`` execution.

The dataset is synthetic at ML-20M scale (the real file is unreachable:
zero-egress container): 138k users x 27k items x 20M implicit-ish ratings
with zipf item popularity, per-user history capped at 256 (padded-CSR
truncation, the ALX-style layout choice).

Orchestration. The parent process imports no JAX, so it never holds the
chip; every measurement runs in a child subprocess with a hard timeout, one
at a time, writing its result to a file the parent collects. Phases:

  1. device probe (one attempt, <=120s). No accelerator is an error: the
     parent prints ``{"ok": false, ...}`` and exits non-zero. Nothing is
     ever measured on the CPU and reported under the per-chip metric;
  2. scaled CPU ALS (1/20 scale by default) -- only the denominator of
     ``vs_baseline``;
  3. full-scale run on the chip.

The parent prints exactly ONE JSON line, which names the device the number
came from: at completion, at the internal deadline, or from its SIGTERM
handler if the driver's ``timeout`` fires first. ``PIO_BENCH_PLATFORM=cpu``
asks for a host run on purpose; its number goes under a host metric name.

Env knobs: PIO_BENCH_DEADLINE_S (parent deadline, default 480),
PIO_BENCH_PROBE_BUDGET_S (TPU probe timeout, default 120, capped at 120),
PIO_BENCH_SCALE (edge-count divisor for the full-scale phase, default 1),
PIO_BENCH_PLATFORM=cpu (a host run, reported as such),
PIO_BENCH_ALS_FEED=resident|streamed (the ALS data feed: resident holds
the whole padded edge set in memory -- the historical path, capped near
20M edges on this box -- while streamed runs device-resident epochs over
the ``parallel.stream`` block store with O(block) host memory),
PIO_BENCH_EDGES (absolute edge-count override; counts past ~40M require
the streamed feed -- this is the 20M-cap lift, see tools/als_stream_bench
for the standalone >=100M acceptance run).
"""

from __future__ import annotations

import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
N_USERS_FULL, N_ITEMS_FULL, N_EDGES_FULL = 138_000, 27_000, 20_000_000
RANK = 16

EVIDENCE: dict = {"probes": [], "runs": {}, "phases": []}

#: published peaks per chip, keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).
#: A device that is not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}

METRIC_CHIP = "als_iters_per_sec_per_chip_ml20m_scale"
METRIC_HOST = "als_iters_per_sec_host_cpu_ml20m_scale"


# --------------------------------------------------------------------------
# measurement code (runs in CHILD processes only)
# --------------------------------------------------------------------------

def make_dataset(n_edges: int, n_users: int, n_items: int, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, size=n_edges, dtype=np.int64)
    # zipf-ish item popularity via squared uniform
    items = (np.minimum(rng.random(n_edges) ** 2.2, 0.999999) * n_items).astype(
        np.int64
    )
    ratings = rng.integers(1, 6, size=n_edges).astype(np.float32)
    return users, items, ratings


def run_als(platform: str, data, config, iters_to_time: int) -> float:
    """Return measured seconds per iteration.

    Transfers the CSR blocks to the device ONCE, then times K chained
    iterations in-process, syncing by fetching one scalar of the final
    factors to the host: a hard device sync, and the chain's data
    dependencies (donated factor buffers feed the next call) stop dispatch
    pipelining from faking completion. The earlier two-``als_fit``-call
    delta method died once iterations got fast: it paid the ~500 MB
    host->device transfer twice.

    Two timed blocks; the min is reported. A non-positive or wildly
    inconsistent pair is invalid.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from predictionio_tpu.parallel import als as als_mod
    from predictionio_tpu.parallel.mesh import put_global

    devices = jax.devices(platform)
    mesh = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    row = NamedSharding(mesh, PartitionSpec("data"))
    rng = np.random.default_rng(0)
    scale = 1.0 / np.sqrt(config.rank)

    t0 = time.perf_counter()
    put = lambda a: put_global(np.asarray(a), row)
    u_blocks = als_mod.device_put_blocks(data.by_row, put)
    i_blocks = als_mod.device_put_blocks(data.by_col, put)
    dtype = np.float32 if config.dtype == "float32" else "bfloat16"
    uf = put(
        (rng.normal(size=(data.by_row.total_slots, config.rank)) * scale)
        .astype(dtype)
    )
    itf = put(
        (rng.normal(size=(data.by_col.total_slots, config.rank)) * scale)
        .astype(dtype)
    )
    transfer_s = time.perf_counter() - t0

    iteration = als_mod.make_iteration(mesh, config)
    from jax.sharding import PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    reg = put_global(np.float32(config.reg), rep)
    alpha = put_global(np.float32(config.alpha), rep)

    def sync(x) -> None:
        np.asarray(jax.device_get(x[:1, :1]))  # hard sync: forces the chain

    t0 = time.perf_counter()
    uf, itf = iteration(u_blocks, i_blocks, uf, itf, reg, alpha)
    sync(uf)
    compile_s = time.perf_counter() - t0

    def block() -> float:
        nonlocal uf, itf
        t0 = time.perf_counter()
        for _ in range(iters_to_time):
            uf, itf = iteration(u_blocks, i_blocks, uf, itf, reg, alpha)
        sync(uf)
        return (time.perf_counter() - t0) / iters_to_time

    b1, b2 = block(), block()
    per_iter = min(b1, b2)
    record = {
        "device": str(devices[0]),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "transfer_s": round(transfer_s, 3),
        "compile_and_first_iter_s": round(compile_s, 3),
        "block_sec_per_iter": [round(b1, 5), round(b2, 5)],
        "iters_per_block": iters_to_time,
        "sec_per_iter": round(per_iter, 5),
        "valid": bool(per_iter > 0 and max(b1, b2) < 5 * per_iter),
    }
    EVIDENCE["runs"][platform] = record
    if not record["valid"]:
        raise RuntimeError(
            f"degenerate timing on {platform}: blocks {b1:.4f}/{b2:.4f}"
            " s/iter -- the two timed blocks disagree by more than 5x"
        )
    return per_iter


def run_als_streamed(platform: str, config, n_edges, n_users, n_items,
                     iters_to_time: int) -> tuple[float, dict]:
    """Streamed-feed counterpart of ``run_als``: a chunked synthetic
    source builds the ``parallel.stream`` block store once (disk-cached,
    O(block) host memory), a 1-iteration fit warms every program, then a
    timed fit of ``iters_to_time`` chained iterations runs the real
    steady state -- each iteration re-streams its blocks host->device
    (that cost is the thing being measured; the resident path instead
    holds O(edges) in memory). Returns ``(sec_per_iter, extras)`` with
    the measured-vs-modeled transfer evidence."""
    import dataclasses
    import tempfile

    from predictionio_tpu.parallel.als import als_fit_streamed
    from predictionio_tpu.parallel.stream import (
        StreamStats,
        build_streamed_als_data,
        reship_bytes_per_half_step,
        stream_bytes_per_half_step,
    )
    from predictionio_tpu.tools.als_stream_bench import (
        chunked_synthetic_source,
    )

    import jax
    import numpy as np

    devices = jax.devices(platform)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    source = chunked_synthetic_source(
        n_edges, n_users, n_items, implicit=False
    )
    cache = os.environ.get("PIO_BENCH_STREAM_CACHE")
    tmp_ctx = None
    if cache is None:
        tmp_ctx = tempfile.TemporaryDirectory(prefix="pio-bench-stream-")
        cache = tmp_ctx.name
    try:
        t0 = time.time()
        data = build_streamed_als_data(
            source, n_users, n_items, config, cache
        )
        build_s = time.time() - t0
        warm = dataclasses.replace(config, iterations=1)
        t0 = time.time()
        als_fit_streamed(data, warm, mesh)
        compile_s = time.time() - t0
        timed = dataclasses.replace(config, iterations=iters_to_time)
        stats = StreamStats()
        t0 = time.time()
        model = als_fit_streamed(data, timed, mesh, stats=stats)
        float(model.user_factors[0, 0])  # host sync (host model already)
        sec = (time.time() - t0) / iters_to_time
        itemsize = 2 if config.dtype == "bfloat16" else 4
        specs = [
            s for side in (data.by_row, data.by_col) for s in side.specs
        ]
        extras = {
            "feed": "streamed",
            "flops_per_iter_model": sum(
                _half_step_flops(s.rows, s.pad_len, config.rank)
                for s in specs
            ),
            "bytes_per_iter_model": als_bytes_per_iteration(
                data, config.rank, itemsize
            ),
            "build_seconds": round(build_s, 2),
            "compile_and_first_iter_s": round(compile_s, 2),
            "real_edges": data.real_edges,
            "blocks": len(data.by_row.specs) + len(data.by_col.specs),
            "edges_per_sec": round(data.real_edges / sec, 1),
            "h2d_bytes_per_half_step": stats.bytes_per_half_step,
            "h2d_modeled_bytes_per_half_step": stream_bytes_per_half_step(
                data, config.implicit
            ),
            "reship_bytes_per_half_step": reship_bytes_per_half_step(
                data, config.rank, itemsize
            ),
            "max_inflight_blocks": stats.max_inflight_blocks,
        }
        EVIDENCE["runs"][platform] = {
            "device": str(devices[0]), "sec_per_iter": round(sec, 5),
            **extras,
        }
        return sec, extras
    finally:
        if tmp_ctx is not None:
            tmp_ctx.cleanup()


def _half_step_flops(rows: int, pad_len: float, rank: int) -> float:
    """One half-step over R rows of padded length L with K=rank:
    Gram einsum rlk,rlj->rkj = 2*R*L*K^2; rhs = 2*R*L*K; batched Cholesky
    solve ~ R*(K^3/3 + 2K^2). Padding rows count: the device computes them.
    """
    k = float(rank)
    return (
        2 * rows * pad_len * k * k       # gram
        + 2 * rows * pad_len * k         # rhs
        + rows * (k ** 3 / 3 + 2 * k * k)  # solve
    )


def als_flops_per_iteration(data, rank: int) -> float:
    """FLOPs of one full ALS iteration (both half-steps) on the padded data."""
    return sum(
        _half_step_flops(*block.indices.shape, rank)
        for side in (data.by_row, data.by_col)
        for block in side.blocks
    )


def als_bytes_per_iteration(data, rank: int, itemsize: int) -> float:
    """HBM bytes one full ALS iteration moves through its half-step tails:
    the half-step is gather/bandwidth-bound, so achieved GB/s against this
    model -- NOT the MFU number, which an einsum-heavy but bandwidth-
    starved kernel can keep misleadingly low -- is the efficiency axis
    that matters. One definition, shared with the ``pio train --profile``
    telemetry journal (``parallel.als.modeled_bytes_per_iteration``)."""
    from predictionio_tpu.parallel.als import modeled_bytes_per_iteration

    return modeled_bytes_per_iteration(data, rank, itemsize)


def full_scale_flops_estimate(scale: float) -> float:
    """Analytic FLOPs/iteration at ``scale`` reduction of ML-20M.

    At full scale the 256-cap saturates both orientations (avg user history
    145, zipf item popularity), so pad_len = max_len on both sides; rows
    round up to the lane multiple of 8. Used to scale a small-run
    measurement up to the metric's nominal scale (flagged as an estimate
    in the printed note).
    """
    n_users = int(N_USERS_FULL / max(scale ** 0.5, 1))
    n_items = int(N_ITEMS_FULL / max(scale ** 0.5, 1))

    def side(rows: int) -> float:
        return _half_step_flops(math.ceil(rows / 8) * 8, 256.0, RANK)

    return side(n_users) + side(n_items)


def secondary_main(result_path: str) -> None:
    """Driver-reproducible secondary metrics (BASELINE configs #2-#5).

    Until round 4 these lived as hand-run session notes in BASELINE.md; a
    regression in any of them had no artifact to catch it. Each phase is
    individually budgeted and exception-isolated, and the result file is
    rewritten after every phase so a timeout keeps whatever completed.
    TPU runs use the BASELINE.md round-4 shapes (comparable across
    rounds); a host run uses reduced shapes, recorded alongside the
    numbers.
    """
    from predictionio_tpu.utils.platform import ensure_backend

    platform = ensure_backend()  # the parent named it in JAX_PLATFORMS
    tpu = platform == "tpu"
    deadline = time.time() + float(
        os.environ.get("PIO_BENCH_SECONDARY_BUDGET_S", "240")
    )
    import numpy as np

    results: dict = {"platform": platform}

    def flush() -> None:
        tmp = result_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(results, f)
            f.flush()
            # hours of bench phases feed this file; a crash must not
            # tear the trend line (pio check R003)
            os.fsync(f.fileno())
        os.replace(tmp, result_path)

    def phase(name: str, fn) -> None:
        if time.time() > deadline - 5:
            results[name] = {"skipped": "secondary deadline reached"}
            flush()
            return
        try:
            t0 = time.perf_counter()
            extra = fn() or {}
            results[name] = {
                "seconds": round(time.perf_counter() - t0, 3), **extra
            }
        except Exception as exc:  # one broken phase must not zero the rest
            results[name] = {"error": repr(exc)[:300]}
        flush()

    def nb_fit():
        from predictionio_tpu.ops.classify import train_naive_bayes

        rng = np.random.default_rng(101)  # per-phase rng: a skipped or
        # failed earlier phase must not change later phases' datasets
        n, d = (10_000, 4096) if tpu else (10_000, 1024)
        x = rng.poisson(1.0, size=(n, d)).astype(np.float32)
        y = rng.integers(0, 2, n).astype(np.int32)
        m = train_naive_bayes(x, y, 2)
        np.asarray(m.log_likelihood)  # host sync
        return {"n": n, "d": d, "config": "#2 NaiveBayes"}

    def logreg_fit():
        from predictionio_tpu.ops.classify import train_logistic_regression

        rng = np.random.default_rng(102)
        n, d, iters = (10_000, 1024, 100) if tpu else (5_000, 256, 30)
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = rng.integers(0, 3, n).astype(np.int32)
        m = train_logistic_regression(x, y, 3, iterations=iters)
        np.asarray(m.weights)
        return {"n": n, "d": d, "iterations": iters, "config": "#2 LogReg"}

    def cooc_indicators():
        from predictionio_tpu.ops.cooccurrence import (
            cooccurrence_indicators,
            distinct_user_counts,
        )
        from predictionio_tpu.ops.ragged import pack_padded_csr

        rng = np.random.default_rng(103)
        if tpu:
            n_e, n_u, n_i = 2_000_000, 100_000, 10_000
        else:
            n_e, n_u, n_i = 200_000, 10_000, 2_000
        uu = rng.integers(0, n_u, size=n_e)
        ii = (np.minimum(rng.random(n_e) ** 2.0, 0.999999) * n_i).astype(
            np.int64
        )
        csr = pack_padded_csr(uu, ii, np.ones(n_e, np.float32), n_u, n_i)
        t0 = time.perf_counter()
        counts = distinct_user_counts(csr)
        idx, vals = cooccurrence_indicators(
            csr, top_k=50,
            llr_row_totals=counts, llr_col_totals=counts, total=n_u,
        )
        build_s = time.perf_counter() - t0
        assert idx.shape[1] == 50 and idx.shape[0] >= n_i  # [items_p, k]
        return {
            "build_seconds": round(build_s, 3),  # excl. the host pack
            "events": n_e, "users": n_u, "items": n_i, "top_k": 50,
            "config": "#3/#4 cooccurrence+LLR indicators",
        }

    def ncf_batchpredict():
        import jax

        from predictionio_tpu.models.ncf.kernel import make_batch_scorer
        from predictionio_tpu.models.ncf.model import NCFConfig, NeuMF

        users, items = (2_000, 5_000) if tpu else (500, 2_000)
        config = NCFConfig(
            num_users=users, num_items=items, embed_dim=32, hidden=(64, 32)
        )
        model = NeuMF(config)
        import jax.numpy as jnp

        params = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32),
        )["params"]
        scorer = make_batch_scorer(params, items)
        scorer(np.arange(8, dtype=np.int32))  # compile outside the clock
        t0 = time.perf_counter()
        # ONE call: the scorer chunks internally by its pair budget; an
        # outer chunk loop would fight that padding and understate qps
        scores = scorer(np.arange(users, dtype=np.int32))
        float(scores[-1, -1])  # host sync
        qps = users / (time.perf_counter() - t0)
        return {
            "queries_per_sec": round(qps, 1),
            "users": users, "items": items, "config": "#5 NCF batchpredict",
        }

    def serving_qps():
        """#6: query-server QPS under concurrent load, micro-batching off
        vs on. CPU-only by design (the serving path is host+single-chip);
        on the TPU secondary child the backend is already initialized by
        the earlier phases, so a CPU pin could not take effect -- skip.
        Sizes are trimmed to fit the secondary budget; the full-size A/B
        is `python -m predictionio_tpu.tools.serving_bench`."""
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.serving_bench import run_ab

        rep = run_ab(
            "recommendation",
            concurrency=16,
            requests=480,
            users=300,
            items=30_000,
            events=60_000,
        )
        return {
            "qps_batching_off": rep["batching_off"]["qps"],
            "qps_batching_on": rep["batching_on"]["qps"],
            "p50_ms_batching_on": rep["batching_on"]["p50_ms"],
            "qps_speedup": rep["qps_speedup"],
            "responses_equivalent": rep["responses_equivalent"],
            "config": "#6 serving_qps (16 clients, 30k items, rank 64)",
        }

    def ingest_eps():
        """#7: Event Server ingestion events/sec, per-request durable sync
        commits vs WAL group commit (sqlite, 32 concurrent writers), plus a
        SIGKILL-and-replay exactly-once check. Storage-layer only -- no JAX,
        runs identically on the TPU and CPU secondary children. Full-size
        A/B: `python -m predictionio_tpu.tools.ingest_bench`."""
        from predictionio_tpu.tools.ingest_bench import run_ab

        rep = run_ab(clients=32, events_per_client=25, crash_events=150)
        return {
            "eps_sync_durable": rep["sync"]["eps"],
            "eps_sync_nondurable": rep["sync_nondurable"]["eps"],
            "eps_group_commit": rep["wal"]["eps"],
            "eps_speedup": rep["speedup"],
            "eps_speedup_vs_nondurable": rep["speedup_vs_nondurable_sync"],
            "crash_exactly_once": rep["crash_cycle"]["exactly_once"],
            "crash_replayed": rep["crash_cycle"]["replayed"],
            "config": "#7 ingest_eps (32 writers, sqlite, fsync=always)",
        }

    def ingest_partitioned_eps():
        """#17: partitioned WAL ingest scaling -- the #7 group-commit load
        re-driven at wal-partitions 1/2/4 (eps per P, scaling vs P=1),
        plus a P=4 SIGKILL-and-replay cycle proving exactly-once per
        partition with zero cross-partition routing drift. Storage-layer
        only, like #7. Full sweep (1,2,4,8): `python -m
        predictionio_tpu.tools.ingest_bench --wal-partitions 1,2,4,8`."""
        from predictionio_tpu.tools.ingest_bench import run_sweep

        rep = run_sweep(
            partitions=(1, 2, 4), clients=32, events_per_client=25,
            crash_partitions=4, crash_events=150,
        )
        out = {
            "monotonic": rep["monotonic"],
            "crash_exactly_once": rep["crash_cycle"]["exactly_once"],
            "crash_replayed_per_partition": rep["crash_cycle"][
                "replayed_per_partition"
            ],
            "crash_misrouted": rep["crash_cycle"]["misrouted"],
            "config": "#17 ingest_partitioned_eps (32 writers, sqlite,"
            " fsync=always, P in 1/2/4, crash at P=4)",
        }
        for p, arm in rep["partitions"].items():
            out[f"eps_p{p}"] = arm["eps"]
            out[f"scaling_p{p}"] = arm["scaling_vs_first"]
        return out

    def train_data_eps():
        """#8: training-data extraction events/sec, cold two-scan SQL read
        vs columnar-snapshot memmap replay (sqlite), plus the
        refresh-then-train bit-identity check. Sizes are trimmed for the
        secondary budget; the full-size (2M-event) A/B is
        `python -m predictionio_tpu.tools.train_bench`."""
        from predictionio_tpu.tools.train_bench import run_ab

        rep = run_ab(
            events=120_000, users=8_000, items=2_000, identity_events=20_000
        )
        return {
            "eps_cold_scan": rep["cold"]["eps"],
            "eps_snapshot_replay": rep["replay"]["eps"],
            "eps_speedup": rep["eps_speedup"],
            "snapshot_build_seconds": rep["snapshot_build"]["seconds"],
            "refresh_bit_identical": rep["refresh_identity"]["bit_identical"],
            "config": "#8 train_data_eps (120k events, sqlite, 2-pass read)",
        }

    def mips_topk():
        """#15: two-stage quantized MIPS retrieval (ops/mips) vs the full
        scan over a 1M-item synthetic catalog. TPU: times
        RetrievalIndex.search end-to-end (shortlisted items/sec +
        achieved GB/s against the packed-table bytes model). CPU child:
        the kernel only runs under the Pallas interpreter, and timing it
        at catalog scale would measure the interpreter -- so recall@10 is
        measured through
        the numpy REFERENCE of the same quantized stage-1 math
        (ops.mips.reference_shortlist) and the bytes-model ratio is
        reported; the kernel-timing rerun rides the ROADMAP
        first-real-hardware item."""
        from predictionio_tpu.ops.mips import (
            RetrievalConfig,
            RetrievalIndex,
            mips_bytes,
            reference_shortlist,
            scan_bytes,
        )

        rng = np.random.default_rng(115)
        rank = 16
        # 1M default; PIO_BENCH_MIPS_ITEMS=10000000 is the 10M variant
        # (deliberately not default: it owns ~2 GB of host arrays)
        n_items = int(os.environ.get("PIO_BENCH_MIPS_ITEMS", "1000000"))
        batch = 64 if tpu else 16  # CPU reference math holds [B, items]
        conf = RetrievalConfig(mode="mips")
        b_mips = mips_bytes(
            n_items, rank, batch,
            conf.block_items, conf.block_topk, conf.shortlist,
        )
        b_scan = scan_bytes(n_items, rank, batch)
        res = {
            "items": n_items, "rank": rank, "batch": batch,
            "shortlist": conf.shortlist, "block_topk": conf.block_topk,
            "bytes_mips": b_mips, "bytes_scan": b_scan,
            "model_bytes_ratio": round(b_scan / b_mips, 2),
            "config": "#15 mips_topk (bytes model: ops.mips)",
        }
        factors = rng.standard_normal((n_items, rank)).astype(np.float32)
        queries = rng.standard_normal((batch, rank)).astype(np.float32)
        exact = queries @ factors.T
        true_top = np.argpartition(-exact, 9, axis=1)[:, :10]
        if not tpu:
            sel = reference_shortlist(factors, queries, conf)
            hits = sum(
                len(set(true_top[row].tolist()) & set(sel[row].tolist()))
                for row in range(batch)
            )
            res["recall_at_10"] = round(hits / (batch * 10), 4)
            res["kernel_timing"] = (
                "skipped on CPU (interpret mode times the interpreter;"
                " rerun queued on the ROADMAP first-real-hardware item)"
            )
            return res
        index = RetrievalIndex(factors, conf)
        idx, _ = index.search(queries)  # compile + warm outside the clock
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            idx, _ = index.search(queries)
        sec = (time.perf_counter() - t0) / reps
        hits = sum(
            len(set(true_top[row].tolist()) & set(idx[row].tolist()))
            for row in range(batch)
        )
        res["recall_at_10"] = round(hits / (batch * 10), 4)
        res["sec_per_batch"] = round(sec, 5)
        res["shortlisted_items_per_sec"] = round(n_items * batch / sec, 1)
        res["gbps_packed"] = round(b_mips / sec / 1e9, 2)
        return res

    def trace_overhead_pct():
        """#11: serving qps with the span tracer enabled (the production
        default: headerless roots head-sampled 1-in-8, traceparent'd
        requests always traced) vs disabled, identical micro-batched load
        at 32 clients. Tracing must stay within 2% of the untraced arm --
        the acceptance bar the obs/ subsystem was built against (full
        always-on tracing measures ~10% on this box; sampling is the
        mechanism that buys the bar back). The overhead is the MEDIAN of
        interleaved alternating-order paired rounds (the box's qps drifts
        >20% across sequential arms as in-process caches warm; see
        run_trace_ab). CPU-only like serving_qps (the serving path is
        host+single-chip); bodies must stay equivalent (tracing adds
        headers, never bodies; batch-bucket timing gives the documented
        ulp score drift)."""
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.serving_bench import run_trace_ab

        rep = run_trace_ab(
            "recommendation",
            concurrency=32,
            requests=768,  # ~2.4s windows: 384-req windows are ~1.2s and
            rounds=5,      # per-round qps swings +/-15%, 8x the effect
            users=300,
            items=30_000,
            events=60_000,
        )
        return {
            "qps_tracing_off": rep["tracing_off"]["qps"],
            "qps_tracing_on": rep["tracing_on"]["qps"],
            "p99_ms_tracing_on": rep["tracing_on"]["p99_ms"],
            "overhead_pct": rep["overhead_pct"],
            "overhead_pct_rounds": rep["overhead_pct_rounds"],
            "within_2pct": (
                rep["overhead_pct"] is not None and rep["overhead_pct"] < 2.0
            ),
            "responses_equivalent": rep["responses_equivalent"],
            "config": "#11 trace_overhead_pct (32 clients, 30k items,"
            " production-default sampling, median of 5 paired rounds)",
        }

    def serving_qps_multiproc():
        """#12: aggregate query-server QPS, single-process
        ThreadingHTTPServer vs the multi-process tier (SO_REUSEPORT
        frontend workers + shared-memory rings into one scorer), same
        micro-batched scorer, identical raw-socket load at 32 clients
        (the stock http.client generator saturates near ~600 qps on this
        box -- below the process tier -- so it would measure itself).
        Since PR 12 this is ALSO the scorer dispatch-model A/B: the
        2-worker tier runs once with the sync dispatcher pool and once
        with the async fast path (ring consumer -> micro-batcher future
        -> flusher callback), both CPU-pinned via the --pin-cpus plan,
        with the measured wakeups/request + dispatch-thread gauges
        recorded per arm. PIO_BENCH_DISPATCH=sync|async narrows to one
        arm (e.g. for a quick round); default 'both' captures the
        comparison on any multi-core round without code changes.
        Includes the coalescing identity check: every arm's bodies come
        from the same scorer router. CPU-only like serving_qps."""
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.serving_bench import run_multiproc_ab

        mode = os.environ.get("PIO_BENCH_DISPATCH", "both")
        dispatch = ("sync", "async") if mode == "both" else mode
        rep = run_multiproc_ab(
            "recommendation",
            concurrency=32,
            requests=2000,
            workers=(2,),
            users=300,
            items=30_000,
            events=60_000,
            dispatch=dispatch,
            pin_cpus=True,
        )
        out = {
            "qps_singleproc": rep["singleproc"]["qps"],
            "responses_identical": rep["responses_identical"],
            "responses_equivalent": rep["responses_equivalent"],
            "qps_speedup": rep["qps_speedup"],
            "config": "#12 serving_qps_multiproc (32 raw clients, 30k"
            f" items, rank 64, 2 workers pinned, dispatch={mode})",
        }
        for label, arm in rep.items():
            if not label.startswith("workers_"):
                continue
            out[f"qps_{label}"] = arm["qps"]
            out[f"p50_ms_{label}"] = arm["p50_ms"]
            out[f"failures_{label}"] = arm["failures"]
            if arm.get("wakeups_per_request") is not None:
                out[f"wakeups_per_request_{label}"] = (
                    arm["wakeups_per_request"]
                )
                out[f"dispatch_threads_{label}"] = arm["dispatch_threads"]
        for key in rep:
            if key.startswith("qps_speedup_workers_") or key.startswith(
                "qps_async_over_sync_workers_"
            ):
                out[key] = rep[key]
        return out

    def serving_sharded_qps():
        """#18: aggregate query-server QPS, single-process baseline vs
        the hash-partitioned shard fabric at 2 and 4 scorer shards (each
        shard a separate process holding one partition of the user
        factor table, item side replicated, one SO_REUSEPORT frontend
        routing hash(user) % N). Batch-size-1 probe bodies must be
        byte-identical across every arm -- partitioning selects rows,
        it never changes arithmetic -- so this phase is ALSO a standing
        routing/scatter correctness gate. On the 2-core box the sweep
        measures process overhead, not scaling; the sweep exists as the
        trend line for real multi-core hardware (see
        `serving_bench --scorer-shards 1,2,4,8`). CPU-only like
        serving_qps."""
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.serving_bench import run_sharded_ab

        rep = run_sharded_ab(
            "recommendation",
            concurrency=32,
            requests=1200,
            shards=(1, 2, 4),
            users=300,
            items=30_000,
            events=60_000,
        )
        out = {
            "qps_shards_1": rep["shards_1"]["qps"],
            "responses_identical": rep["responses_identical"],
            "responses_equivalent": rep["responses_equivalent"],
            "qps_speedup": rep["qps_speedup"],
            "config": "#18 serving_sharded_qps (32 raw clients, 30k"
            " items, rank 64, shards 1/2/4)",
        }
        for label, arm in rep.items():
            if not (label.startswith("shards_") and isinstance(arm, dict)):
                continue
            out[f"qps_{label}"] = arm["qps"]
            out[f"p50_ms_{label}"] = arm["p50_ms"]
            out[f"failures_{label}"] = arm["failures"]
        for key in rep:
            if key.startswith("qps_speedup_shards_"):
                out[key] = rep[key]
        return out

    def analysis_findings():
        """#10: the `pio check` static-analysis gate as a zero-cost
        regression metric. `analysis_findings_total` (unsuppressed) must
        stay 0 -- tier-1 gates it -- and `suppressed` (the committed
        baseline) should only ever ratchet down.
        `analysis_runtime_seconds` is the full interprocedural sweep
        (parallel parse + package index + every rule): the tier-1 gate
        enforces <10 s on the 2-core box, and this metric is the trend
        line that shows when the deepening analysis starts eating that
        budget. No JAX, identical on CPU and TPU children."""
        from predictionio_tpu.analysis.engine import (
            all_rules,
            apply_baseline,
            check_paths,
            load_baseline,
        )

        timings: dict = {}
        t0 = time.perf_counter()
        findings = check_paths(timings=timings)
        runtime_s = time.perf_counter() - t0
        unsuppressed, suppressed, stale = apply_baseline(
            findings, load_baseline()
        )
        by_rule: dict = {}
        for f in findings:
            by_rule[f.rule_id] = by_rule.get(f.rule_id, 0) + 1
        return {
            "analysis_findings_total": len(unsuppressed),
            "analysis_runtime_seconds": round(runtime_s, 3),
            # per-family attribution (J = module walks, C = the shared
            # package index is charged to "index" + the C DFS passes,
            # R = flowgraph build + the four leak rules, S = meshflow
            # build + the five sharding rules, P = protocolflow build +
            # the five cross-process ordering rules): the trend line
            # that shows WHICH deepening layer starts eating the budget
            "analysis_runtime_seconds_by_family": {
                fam: round(s, 3)
                for fam, s in sorted(timings.get("families", {}).items())
            },
            "analysis_parse_seconds": round(timings.get("parse", 0.0), 3),
            "analysis_index_seconds": round(timings.get("index", 0.0), 3),
            "analysis_rules_total": len(all_rules()),
            "suppressed": len(suppressed),
            "stale_baseline": len(stale),
            "findings_by_rule": by_rule,
            "config": "#10 analysis_findings (pio check --format json)",
        }

    def online_freshness():
        """#13: continuous-learning freshness -- the wall seconds between
        a durable ingest and the first /queries.json response reflecting
        it, under concurrent serving load, fold-in loop vs the same loop
        forced to full retrains (`pio retrain --follow` A/B). CPU-only
        like serving_qps (the serving+fold path is host+single-chip).
        Full-size knobs: `python -m predictionio_tpu.tools.retrain_bench`.
        """
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.retrain_bench import run_ab

        rep = run_ab(
            events=1_500, users=50, items=25, rank=8, iterations=2,
            probes=3, load_clients=2,
        )
        full = rep.get("full_retrain") or {}
        return {
            "online_freshness_seconds": rep["foldin"]["freshness_s_median"],
            "online_freshness_seconds_max": rep["foldin"]["freshness_s_max"],
            "full_retrain_freshness_seconds": full.get("freshness_s_median"),
            "foldin_speedup": rep.get("foldin_speedup"),
            "probe_timeouts": rep["foldin"]["timeouts"]
            + full.get("timeouts", 0),
            "load_errors": rep["foldin"]["load_errors"]
            + full.get("load_errors", 0),
            "config": "#13 online_freshness (3 probes, 2 load clients,"
            " sqlite, rank 8)",
        }

    def online_freshness_loaded():
        """#18: the #13 fold-in freshness probe re-run against a P=4
        partitioned WAL while background writers keep a sustained durable
        ingest stream flowing (every probe competes with ~10x its own
        write rate): the partitioned follower must keep merged fold-ins
        fresh under write pressure. CPU-only like #13."""
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.retrain_bench import run_ab

        rep = run_ab(
            events=1_500, users=50, items=25, rank=8, iterations=2,
            probes=3, load_clients=1, full_retrain_arm=False,
            wal_partitions=4, ingest_load_clients=2,
        )
        fold = rep["foldin"]
        return {
            "online_freshness_loaded_seconds": fold["freshness_s_median"],
            "online_freshness_loaded_seconds_max": fold["freshness_s_max"],
            "probe_timeouts": fold["timeouts"],
            "load_errors": fold["load_errors"],
            "ingest_load_events": fold["ingest_load_events"],
            "ingest_load_errors": fold["ingest_load_errors"],
            "config": "#18 online_freshness_loaded (3 probes, P=4,"
            " 2 ingest load writers, sqlite, rank 8)",
        }

    def als_stream():
        """#14: device-resident streamed epochs vs the resident feed at an
        equal (small) shape: edges/sec per arm, bit-identity of the
        factors, and the transfer axis -- measured host->device bytes per
        half-step vs the stream model vs the re-ship baseline (the >=3x
        claim). PIO_BENCH_ALS_FEED pins one arm. The >=100M-edge scaling
        run is `python -m predictionio_tpu.tools.als_stream_bench --edges
        100000000` (deliberately NOT run here: it owns the whole budget)."""
        from predictionio_tpu.tools.als_stream_bench import run_ab

        feed = os.environ.get("PIO_BENCH_ALS_FEED", "both")
        if feed == "resident":
            feed_arg = "resident"
        elif feed == "streamed":
            feed_arg = "streamed"
        else:
            feed_arg = "both"
        rep = run_ab(
            edges=1_500_000 if tpu else 400_000,
            users=40_000 if tpu else 12_000,
            items=8_000 if tpu else 3_000,
            iterations=3,
            feed=feed_arg,
        )
        out = {"config": "#14 als_stream (implicit, buckets=2, rank 16)"}
        for arm in ("resident", "streamed"):
            if arm in rep:
                out[f"eps_{arm}"] = rep[arm]["edges_per_sec"]
        if "streamed" in rep:
            s = rep["streamed"]
            out["h2d_bytes_per_half_step"] = s["h2d_bytes_per_half_step"]
            out["h2d_modeled_bytes_per_half_step"] = s[
                "h2d_modeled_bytes_per_half_step"
            ]
            out["reship_bytes_per_half_step"] = s["reship_bytes_per_half_step"]
            out["reship_ratio"] = s["reship_ratio"]
            out["max_inflight_blocks"] = s["max_inflight_blocks"]
        if "factors_identical" in rep:
            out["factors_identical"] = rep["factors_identical"]
            out["factors_equivalent"] = rep["factors_equivalent"]
        if "streamed_vs_resident_eps" in rep:
            out["streamed_vs_resident_eps"] = rep["streamed_vs_resident_eps"]
        return out

    def eval_quality():
        """#15: offline replay evaluation as a standing quality gate --
        `pio eval --replay` on a seeded clique-structured stream:
        eval_ndcg_at_10 / eval_hit_rate_at_10 are the ranking-quality
        trend lines (a speed PR that quietly degrades recommendations
        moves a committed metric), and mips_recall_at_10 /
        response_identity_rate are the scan-vs-mips retrieval guard on
        the same model and split (1.0 / 1.0 at the default shortlist is
        the contract). CPU-only like serving_qps (toy shapes; the eval
        pass is one batched scorer call either way). Full-size knobs:
        `python -m predictionio_tpu.tools.eval_bench`."""
        if tpu:
            return {
                "skipped": "CPU-only phase (TPU child shares an already-"
                "initialized backend)"
            }
        from predictionio_tpu.tools.eval_bench import run_eval_quality

        rep = run_eval_quality(
            events=3_000, users=60, items=128, rank=8, iterations=3,
        )
        return {
            "eval_ndcg_at_10": rep["eval_ndcg_at_10"],
            "eval_hit_rate_at_10": rep["eval_hit_rate_at_10"],
            "mips_recall_at_10": rep["mips_recall_at_10"],
            "response_identity_rate": rep["response_identity_rate"],
            "eval_holdout_users": rep["holdout_users"],
            "replay_seconds": rep["replay_seconds"],
            "config": "#15 eval_quality (3k events, 60 users, 128 items,"
            " rank 8, split 0.8, k 10, sqlite)",
        }

    phase("naive_bayes_fit", nb_fit)
    phase("eval_quality", eval_quality)
    phase("logreg_lbfgs_fit", logreg_fit)
    phase("cooccurrence_llr_indicators", cooc_indicators)
    phase("ncf_batchpredict", ncf_batchpredict)
    phase("serving_qps", serving_qps)
    phase("ingest_eps", ingest_eps)
    phase("train_data_eps", train_data_eps)
    phase("mips_topk", mips_topk)
    phase("trace_overhead_pct", trace_overhead_pct)
    phase("serving_qps_multiproc", serving_qps_multiproc)
    phase("serving_sharded_qps", serving_sharded_qps)
    phase("als_stream", als_stream)
    phase("analysis_findings", analysis_findings)
    phase("online_freshness_seconds", online_freshness)
    phase("ingest_partitioned_eps", ingest_partitioned_eps)
    phase("online_freshness_loaded_seconds", online_freshness_loaded)


def child_main(mode: str, result_path: str) -> None:
    """Measurement child: builds the dataset, times ALS, writes one JSON file.

    ``mode`` is cpu or tpu; the parent sets JAX_PLATFORMS=cpu in the env for
    cpu children (they need no chip), and PIO_BENCH_CHILD_SCALE carries the
    edge-count divisor. Every child passes through ``ensure_backend``: the
    platform it was given comes up or it fails, and it shares the compile
    cache with ``pio train``.
    """
    if mode == "secondary":
        return secondary_main(result_path)

    t0 = time.time()
    scale = float(os.environ.get("PIO_BENCH_CHILD_SCALE", "1"))

    from predictionio_tpu.parallel.als import ALSConfig, build_als_data
    from predictionio_tpu.utils.platform import ensure_backend

    platform = ensure_backend()
    if platform != mode:
        raise SystemExit(
            f"bench child was started for {mode!r} but JAX came up on"
            f" {platform!r}"
        )

    n_users = int(N_USERS_FULL / max(scale ** 0.5, 1))
    n_items = int(N_ITEMS_FULL / max(scale ** 0.5, 1))
    n_edges = int(N_EDGES_FULL / scale)
    feed = os.environ.get("PIO_BENCH_ALS_FEED", "resident")
    env_edges = os.environ.get("PIO_BENCH_EDGES")
    if env_edges:
        # absolute override -- the 20M-cap lift. Entity counts scale like
        # the generator's ML-20M ratios.
        n_edges = int(env_edges)
        grow = max(n_edges / N_EDGES_FULL, 1.0) ** 0.5
        n_users = int(N_USERS_FULL * grow)
        n_items = int(N_ITEMS_FULL * grow)
    if feed not in ("resident", "streamed"):
        raise SystemExit(f"PIO_BENCH_ALS_FEED must be resident|streamed, got {feed!r}")
    if feed == "resident" and n_edges > 40_000_000:
        raise SystemExit(
            f"{n_edges} edges exceed the resident feed's memory envelope "
            "on this box; set PIO_BENCH_ALS_FEED=streamed (device-resident "
            "epochs, O(block) host memory)"
        )
    # TPU runs the TPU-native layout: bf16 factor storage (half the HBM
    # traffic on gathers, native MXU input dtype), f32 Gram accumulation
    # and solve -- measured 2.1x faster per iteration than f32 storage at
    # matched quality (test_bfloat16_factor_mode). The CPU baseline stays
    # f32: it stands in for the reference's Spark-local execution, and
    # bf16 on host CPUs is emulation, not a fair baseline.
    # Length-bucketed packing (TPU only): 4 buckets cut ~25-35% of padded
    # gather slots at ML-20M's zipf history distribution. The CPU baseline
    # stays single-block f32: it stands in for the reference's Spark-local
    # execution, and the TPU-native layout tricks are the thing measured.
    config = ALSConfig(
        rank=RANK,
        reg=0.05,
        max_len=256,
        dtype="bfloat16" if mode == "tpu" else "float32",
        buckets=int(os.environ.get("PIO_BENCH_BUCKETS", "4"))
        if mode == "tpu" else 1,
    )
    itemsize = 2 if config.dtype == "bfloat16" else 4
    # fast TPU iterations need more reps per timed block so the one
    # scalar-fetch sync amortizes out; CPU iterations are seconds each
    # and 2 suffice
    iters_to_time = 20 if mode == "tpu" else 2
    extras: dict = {"feed": feed}
    if feed == "streamed":
        sec, extras = run_als_streamed(
            platform, config, n_edges, n_users, n_items, iters_to_time
        )
        flops = extras.pop("flops_per_iter_model", 0.0)
        bytes_iter = extras.pop("bytes_per_iter_model", 0.0)
    else:
        users, items, ratings = make_dataset(n_edges, n_users, n_items)
        data = build_als_data(users, items, ratings, n_users, n_items, config)
        sec = run_als(platform, data, config, iters_to_time)
        flops = als_flops_per_iteration(data, config.rank)
        bytes_iter = als_bytes_per_iteration(data, config.rank, itemsize)
    out = {
        "mode": mode,
        "scale": scale,
        "edges": n_edges,
        "sec_per_iter": sec,
        "flops_per_iter": flops,
        "bytes_per_iter": bytes_iter,
        **extras,
        "run_record": EVIDENCE["runs"].get(platform),
        "elapsed_s": round(time.time() - t0, 1),
    }
    tmp = result_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, result_path)


# --------------------------------------------------------------------------
# orchestration (PARENT process -- stdlib only, must never hang)
# --------------------------------------------------------------------------

_CURRENT_CHILD: subprocess.Popen | None = None


def _run_child(
    mode: str,
    scale: float,
    timeout_s: float,
    phase: str,
    tpu_platform: str | None = None,
) -> dict | None:
    """Spawn ``bench.py --child`` and collect its result file (or None)."""
    global _CURRENT_CHILD
    result_path = os.path.join(
        tempfile.gettempdir(), f"pio_bench_{os.getpid()}_{phase}.json"
    )
    env = dict(os.environ)
    env["PIO_BENCH_CHILD_SCALE"] = str(scale)
    # every child is told its platform: nothing is left to a default
    env.pop("PIO_PLATFORM", None)
    if mode == "cpu" or (mode == "secondary" and not tpu_platform):
        env["JAX_PLATFORMS"] = "cpu"
    else:
        env["JAX_PLATFORMS"] = "tpu"
    if mode == "secondary":
        env["PIO_BENCH_SECONDARY_BUDGET_S"] = str(max(timeout_s - 15, 30))
    t0 = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", mode, result_path],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    _CURRENT_CHILD = proc
    phase_rec = {"phase": phase, "mode": mode, "scale": scale,
                 "timeout_s": round(timeout_s, 1)}
    try:
        _, err = proc.communicate(timeout=timeout_s)
        phase_rec["rc"] = proc.returncode
        if proc.returncode != 0:
            phase_rec["stderr_tail"] = (err or "")[-500:]
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        phase_rec["rc"] = "timeout"
    finally:
        _CURRENT_CHILD = None
        phase_rec["elapsed_s"] = round(time.time() - t0, 1)
        EVIDENCE["phases"].append(phase_rec)
    try:
        with open(result_path) as f:
            result = json.load(f)
        os.unlink(result_path)
        if "run_record" in result:
            EVIDENCE["runs"][phase] = result["run_record"]
        return result
    except (OSError, json.JSONDecodeError):
        return None


def _probe_tpu(timeout_s: float) -> str | None:
    """Single-attempt device probe in a subprocess (the parent stays off
    JAX). Returns "tpu", or None with the reason in EVIDENCE["probes"]."""
    code = (
        "import jax\n"
        "ds = jax.devices()\n"
        "import jax.numpy as jnp\n"
        "x = (jnp.ones((256, 256)) @ jnp.ones((256, 256))).block_until_ready()\n"
        "print('PLATFORM=' + ds[0].platform)\n"
    )
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # ask JAX what is there, not what was pinned
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
        if proc.returncode != 0:
            diag = f"exit {proc.returncode}; stderr tail: {proc.stderr[-500:]!r}"
            platform = None
        else:
            platform = ""
            for line in proc.stdout.strip().splitlines():
                if line.startswith("PLATFORM="):
                    platform = line[len("PLATFORM="):]
            if platform == "tpu":
                diag = f"ok ({platform})"
            else:
                diag = f"backend resolved to {platform or 'nothing'!r} (not an accelerator)"
                platform = None
    except subprocess.TimeoutExpired as exc:
        tail = ((exc.stderr or b"").decode("utf-8", "replace"))[-500:]
        diag = f"timeout after {int(timeout_s)}s; stderr tail: {tail!r}"
        platform = None
    EVIDENCE["probes"].append(
        {"timeout_s": int(timeout_s), "elapsed_s": round(time.time() - t0, 1),
         "result": diag}
    )
    return platform


class _Bench:
    """Best-result-so-far state; printable at any moment (SIGTERM-safe)."""

    def __init__(self) -> None:
        self.deadline = time.time() + float(
            os.environ.get("PIO_BENCH_DEADLINE_S", "480")
        )
        self.result: dict | None = None   # what the single JSON line reports
        self.error = "no measurement completed before the deadline"
        self.edges = 0
        self.printed = False

    def remaining(self) -> float:
        return self.deadline - time.time()

    def emit(self) -> int:
        """Print the one JSON line; returns the process exit code (0 only
        when a measurement on the requested platform completed)."""
        if self.printed:
            return 0 if self.result else 1
        self.printed = True
        try:
            out_dir = os.path.join(REPO, "chiprun_out")
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, "bench_evidence.json"), "w") as f:
                json.dump(EVIDENCE, f, indent=1)
        except OSError:
            pass
        if self.result is None:
            print(json.dumps({"ok": False, "error": self.error[:600]}), flush=True)
            return 1
        print(
            json.dumps(
                {
                    "ok": True,
                    "metric": self.result["metric"],
                    "value": self.result["value"],
                    "unit": "iters/sec",
                    "device": self.result["device"],
                    "vs_baseline": self.result["vs_baseline"],
                    "note": self.result["note"],
                    "edges": self.edges or N_EDGES_FULL,
                }
            ),
            flush=True,
        )
        return 0


def main() -> int:
    bench = _Bench()

    def on_term(signum, frame):
        if _CURRENT_CHILD is not None:
            try:
                _CURRENT_CHILD.kill()
            except OSError:
                pass
        EVIDENCE["terminated_by_signal"] = signum
        os._exit(bench.emit())

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    try:
        _run_phases(bench)
    except Exception as exc:
        # an orchestrator bug still prints whatever was measured before it,
        # but a run that measured nothing does not exit 0
        EVIDENCE["orchestrator_error"] = repr(exc)
        if bench.result is None:
            bench.error = f"orchestrator error: {exc!r}"
    return bench.emit()


def _device_of(run_record: dict | None, platform: str) -> dict:
    rec = run_record or {}
    return {
        "platform": platform,
        "kind": rec.get("device_kind", "unknown"),
        "count": rec.get("device_count", 0),
    }


def _run_phases(bench: _Bench) -> None:
    want_tpu = os.environ.get("PIO_BENCH_PLATFORM", "tpu") != "cpu"
    full_scale = float(os.environ.get("PIO_BENCH_SCALE", "1"))
    small_scale = max(20.0, full_scale)

    # Phase 1: device probe (single bounded attempt). No chip, no number.
    if want_tpu:
        probe_budget = min(
            120.0, float(os.environ.get("PIO_BENCH_PROBE_BUDGET_S", "120"))
        )
        if _probe_tpu(probe_budget) is None:
            bench.error = (
                "no accelerator: " + EVIDENCE["probes"][-1]["result"]
                + " (PIO_BENCH_PLATFORM=cpu asks for a host run)"
            )
            return

    # Phase 2: scaled CPU measurement -- the vs_baseline denominator, and
    # the result itself only when the host was asked for.
    small = _run_child(
        "cpu", small_scale, min(240.0, max(60.0, bench.remaining() * 0.45)),
        phase="cpu_small",
    )
    cpu_full_sec_est = None
    cpu_note = ""
    if small:
        if small_scale == full_scale:
            cpu_full_sec_est = small["sec_per_iter"]
            cpu_note = f"measured at PIO_BENCH_SCALE={full_scale:g}"
        else:
            ratio = full_scale_flops_estimate(full_scale) / small["flops_per_iter"]
            cpu_full_sec_est = small["sec_per_iter"] * ratio
            cpu_note = (
                f"scaled estimate from 1/{small_scale:g}-scale run"
                f" ({small['sec_per_iter']:.3f} s/iter small, flops ratio"
                f" {ratio:.1f}x)"
            )
    if not want_tpu:
        if small is None:
            bench.error = "the host measurement child failed or timed out"
            return
        bench.edges = int(N_EDGES_FULL / full_scale)
        bench.result = {
            "metric": METRIC_HOST,
            "value": round(1.0 / cpu_full_sec_est, 4),
            "vs_baseline": 1.0,
            "device": _device_of(small.get("run_record"), "cpu"),
            "note": f"host cpu on request (PIO_BENCH_PLATFORM=cpu), {cpu_note}",
        }

    # Phase 3: full-scale measurement on the chip.
    tpu_measured = False
    if want_tpu:
        full = (
            _run_child("tpu", full_scale, bench.remaining() - 30,
                       phase="tpu_full", tpu_platform="tpu")
            if bench.remaining() > 60 else None
        )
        if not full:
            bench.error = (
                "the device probe passed but the tpu measurement child"
                " failed or timed out: "
                + json.dumps(EVIDENCE["phases"][-1:])[:400]
            )
            return
        device = _device_of(full.get("run_record"), "tpu")
        peaks = DEVICE_PEAKS.get(device["kind"])
        if peaks is None:
            bench.error = (
                f"device kind {device['kind']!r} is not in DEVICE_PEAKS:"
                " add its published peaks with their source"
            )
            return
        tpu_measured = True
        tpu_sec = full["sec_per_iter"]
        flops = full["flops_per_iter"]
        achieved = flops / tpu_sec
        # The half-step is BANDWIDTH-bound, so the achieved HBM GB/s
        # against the bytes-moved model (als_bytes_per_iteration) is
        # reported alongside the share of the bf16 peak -- a low FLOP
        # share with high GB/s is the expected healthy profile
        EVIDENCE["mfu"] = {
            "device_kind": device["kind"],
            "flops_per_iteration": flops,
            "achieved_flops_per_s": achieved,
            "peak_bf16_flops_per_s": peaks["bf16_flops_per_s"],
            "mfu_vs_bf16_peak": round(achieved / peaks["bf16_flops_per_s"], 4),
        }
        if full.get("bytes_per_iter"):
            EVIDENCE["mfu"]["hbm_bytes_per_iteration"] = full["bytes_per_iter"]
            EVIDENCE["mfu"]["achieved_hbm_gbps"] = round(
                full["bytes_per_iter"] / tpu_sec / 1e9, 2
            )
        vs = (cpu_full_sec_est / tpu_sec) if cpu_full_sec_est else 0.0
        bench.edges = full["edges"]
        gbps_tail = (
            f"; hbm ~{EVIDENCE['mfu']['achieved_hbm_gbps']:.0f} GB/s"
            if "achieved_hbm_gbps" in EVIDENCE["mfu"]
            else ""
        )
        baseline = (
            f"vs host-cpu baseline {1.0 / cpu_full_sec_est:.3f} it/s ({cpu_note})"
            if cpu_full_sec_est else "no cpu baseline this run"
        )
        bench.result = {
            "metric": METRIC_CHIP,
            "value": round(1.0 / tpu_sec, 4),
            "vs_baseline": round(vs, 3),
            "device": device,
            "note": (
                f"{device['kind']} x{device['count']}, {baseline};"
                f" mfu~{EVIDENCE['mfu']['mfu_vs_bf16_peak']:.1%} of bf16 peak"
                f"{gbps_tail}"
            )[:400],
        }

    # Phase 4: secondary metrics (BASELINE configs #2-#5) on the leftover
    # budget. The primary metric is already banked in bench.result; a
    # secondary failure or timeout cannot affect it.
    if bench.remaining() > 75:
        sec = _run_child(
            "secondary",
            1.0,
            min(bench.remaining() - 30, 420.0),
            phase="secondary",
            tpu_platform="tpu" if tpu_measured else None,
        )
        if sec:
            EVIDENCE["secondary"] = sec


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--child":
        child_main(sys.argv[2], sys.argv[3])
    else:
        sys.exit(main())
