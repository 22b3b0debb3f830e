"""Device-resident streamed ALS epochs (ALX, arxiv 2112.02194).

The contract under test: ``als_fit_streamed`` over a ``parallel.stream``
block store is BIT-IDENTICAL to ``als_fit`` over ``build_als_data`` when
block shapes equal the resident bucket shapes (same plans, same packing,
same half-step, same update order), and ulp-equivalent when a bucket is cut
into smaller blocks (XLA tiles some batch sizes differently -- the PR-1
micro-batching precedent); peak host memory stays O(block), with at most
two blocks in flight through the feeder.
"""

import os
import tempfile
import tracemalloc

import numpy as np
import pytest

from predictionio_tpu.parallel.als import (
    ALSConfig,
    als_fit,
    als_fit_streamed,
    build_als_data,
)
from predictionio_tpu.parallel.mesh import local_mesh
from predictionio_tpu.parallel.reader import array_coo_chunks
from predictionio_tpu.parallel.stream import (
    StreamStats,
    build_streamed_als_data,
    load_streamed_als_data,
    reship_bytes_per_half_step,
    stream_bytes_per_half_step,
)


@pytest.fixture(scope="module")
def synthetic():
    # small on purpose: keeps the whole parity matrix inside the tier-1 budget
    rng = np.random.default_rng(42)
    n_u, n_i = 96, 64
    mask = rng.random((n_u, n_i)) < 0.22
    uu, ii = np.nonzero(mask)
    rr = (rng.normal(size=len(uu)) + 3).astype(np.float32)
    tt = rng.random(len(uu)).astype(np.float64)
    return n_u, n_i, uu, ii, rr, tt


def _fit_both(synthetic, cfg, shards=(1, 1), block_rows=1 << 20,
              values=None, stats=None, budget=0):
    n_u, n_i, uu, ii, rr, tt = synthetic
    vals = rr if values is None else values
    d, m = shards
    data = build_als_data(
        uu, ii, vals, n_u, n_i, cfg, times=tt, num_shards=d, model_shards=m
    )
    mesh = local_mesh(d, m)
    resident = als_fit(data, cfg, mesh)
    with tempfile.TemporaryDirectory() as td:
        streamed_data = build_streamed_als_data(
            array_coo_chunks(uu, ii, vals, times=tt),
            n_u, n_i, cfg, td,
            num_shards=d, model_shards=m, block_rows=block_rows,
        )
        streamed = als_fit_streamed(
            streamed_data, cfg, mesh, stats=stats,
            device_budget_bytes=budget,
        )
        specs = {
            side: [(s.rows, s.pad_len, s.const) for s in
                   getattr(streamed_data, side).specs]
            for side in ("by_row", "by_col")
        }
    return resident, streamed, data, specs


def _assert_bit_identical(resident, streamed):
    np.testing.assert_array_equal(resident.user_factors, streamed.user_factors)
    np.testing.assert_array_equal(resident.item_factors, streamed.item_factors)


def _assert_worked(worked, data, cfg, shards):
    from predictionio_tpu.parallel.als import block_paths

    paths = block_paths(data, cfg, local_mesh(*shards))
    assert paths["chunked"] == (paths["blocks"] if worked == "chunked" else 0)


class TestStreamedResidentParity:
    """Bit-parity at equal shapes across the whole/chunked x mode x dtype
    matrix."""

    @pytest.mark.parametrize(
        "implicit,dtype,worked",
        [
            (False, "float32", "whole"),
            (True, "float32", "whole"),
            (False, "float32", "chunked"),
            (True, "float32", "chunked"),
            (False, "bfloat16", "whole"),
            (True, "bfloat16", "chunked"),
        ],
        indirect=["worked"],
    )
    def test_equal_shapes_bit_identical(self, synthetic, implicit, dtype, worked):
        cfg = ALSConfig(
            rank=8, iterations=2, reg=0.01, seed=1, buckets=2,
            implicit=implicit, alpha=5.0, dtype=dtype,
        )
        resident, streamed, data, _ = _fit_both(synthetic, cfg)
        _assert_worked(worked, data, cfg, (1, 1))
        _assert_bit_identical(resident, streamed)

    @pytest.mark.parametrize("worked", ["whole", "chunked"], indirect=True)
    def test_model_sharded_bit_identical(self, synthetic, worked):
        cfg = ALSConfig(
            rank=8, iterations=2, reg=0.01, seed=1, buckets=2,
            implicit=True, alpha=5.0, factor_sharding="model",
        )
        resident, streamed, data, _ = _fit_both(synthetic, cfg, shards=(2, 2))
        _assert_worked(worked, data, cfg, (2, 2))
        _assert_bit_identical(resident, streamed)

    def test_data_sharded_replicated_bit_identical(self, synthetic):
        cfg = ALSConfig(rank=8, iterations=2, reg=0.01, seed=1, buckets=2)
        resident, streamed, _, _ = _fit_both(synthetic, cfg, shards=(8, 1))
        _assert_bit_identical(resident, streamed)

    def test_uniform_value_elision_bit_identical(self, synthetic):
        """All-ones implicit data: the value stream never ships (blocks
        record a const instead) and the factors are STILL bit-identical --
        padding slots gather the appended zero factor row, so their value
        is don't-care by construction, not by approximation."""
        n_u, n_i, uu, ii, _rr, _tt = synthetic
        cfg = ALSConfig(
            rank=8, iterations=2, reg=0.01, seed=1, buckets=2,
            implicit=True, alpha=5.0,
        )
        ones = np.ones(len(uu), np.float32)
        resident, streamed, _, specs = _fit_both(synthetic, cfg, values=ones)
        assert all(c == 1.0 for _, _, c in specs["by_row"])
        _assert_bit_identical(resident, streamed)

    def test_sub_bucket_blocks_equivalent(self, synthetic):
        """Cutting buckets into smaller blocks keeps per-row math but XLA
        may tile odd batch sizes differently: results stay equivalent at
        ulp scale (and the ragged LAST block of each bucket -- a different
        shape from its siblings -- is exercised here too)."""
        cfg = ALSConfig(rank=8, iterations=3, reg=0.01, seed=1, buckets=2)
        resident, streamed, _, specs = _fit_both(
            synthetic, cfg, block_rows=32
        )
        # the cut actually produced a ragged tail somewhere
        heights = [r for r, _, _ in specs["by_row"]]
        assert len(set(heights)) > 1
        np.testing.assert_allclose(
            resident.user_factors, streamed.user_factors, atol=5e-4, rtol=1e-3
        )
        np.testing.assert_allclose(
            resident.item_factors, streamed.item_factors, atol=5e-4, rtol=1e-3
        )

    def test_all_padding_blocks(self, synthetic):
        """Entities beyond the interacting ones produce whole blocks of
        padding rows; the streamed path must solve them to the resident
        result (zeros for explicit ridge) without a value file."""
        n_u, n_i, uu, ii, rr, tt = synthetic
        wide = (n_u + 250, n_i, uu, ii, rr, tt)  # 250 edge-less users
        cfg = ALSConfig(rank=8, iterations=2, reg=0.01, seed=1)
        resident, streamed, _, specs = _fit_both(wide, cfg, block_rows=64)
        empty_blocks = [s for s in specs["by_row"] if s[2] == 0.0]
        assert empty_blocks, "expected at least one all-padding block"
        _assert_bit_identical(resident, streamed)
        # edge-less users solve to exactly zero (ridge-only system)
        never = np.setdiff1d(np.arange(n_u + 250), uu)
        assert np.all(streamed.user_factors[never] == 0.0)


class TestBlockStore:
    def test_packed_blocks_match_resident_layout(self, synthetic):
        n_u, n_i, uu, ii, rr, tt = synthetic
        cfg = ALSConfig(rank=8, iterations=1, reg=0.01, seed=1, buckets=2)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg, times=tt)
        with tempfile.TemporaryDirectory() as td:
            sd = build_streamed_als_data(
                array_coo_chunks(uu, ii, rr, times=tt), n_u, n_i, cfg, td,
                block_rows=1 << 20,
            )
            for side_name in ("by_row", "by_col"):
                side = getattr(sd, side_name)
                resident_side = getattr(data, side_name)
                np.testing.assert_array_equal(
                    side.slot_of, resident_side.slot_of
                )
                assert side.total_slots == resident_side.total_slots
                for spec, block in zip(side.specs, resident_side.blocks):
                    idx, val, nobs = side.load_block(spec)
                    np.testing.assert_array_equal(idx, block.indices)
                    np.testing.assert_array_equal(val, block.values)
                    np.testing.assert_array_equal(
                        nobs, block.mask.sum(axis=1)
                    )
            assert sd.real_edges == len(uu)

    def test_cache_reuse_skips_rebuild(self, synthetic):
        n_u, n_i, uu, ii, rr, tt = synthetic
        cfg = ALSConfig(rank=8, iterations=1, reg=0.01, seed=1)
        chunks = array_coo_chunks(uu, ii, rr, times=tt)
        with tempfile.TemporaryDirectory() as td:
            first = build_streamed_als_data(chunks, n_u, n_i, cfg, td)
            manifest = os.path.join(first.directory, "manifest.json")
            stamp = os.path.getmtime(manifest)
            again = build_streamed_als_data(chunks, n_u, n_i, cfg, td)
            assert again.directory == first.directory
            assert os.path.getmtime(manifest) == stamp  # loaded, not rebuilt
            # a layout change (different packing knobs) builds fresh
            other = build_streamed_als_data(
                chunks, n_u, n_i, cfg, td, block_rows=64
            )
            assert other.directory != first.directory
            # a VALUE change with identical (user, item) structure must
            # also build fresh: the counts digests cannot see it (an
            # event_values weight edit would otherwise train on the old
            # cached values bit-for-bit)
            reweighted = build_streamed_als_data(
                array_coo_chunks(uu, ii, rr * 2.0, times=tt), n_u, n_i,
                cfg, td,
            )
            assert reweighted.directory != first.directory
            # ... and so must a timestamp change (times drive truncation
            # order inside pack_padded_csr)
            shifted = build_streamed_als_data(
                array_coo_chunks(uu, ii, rr, times=tt[::-1].copy()),
                n_u, n_i, cfg, td,
            )
            assert shifted.directory != first.directory
            # ... and an ENDPOINT change with identical degree histograms
            # (review repro: swapped pairings packed the wrong matrix)
            perm = np.random.default_rng(9).permutation(len(ii))
            repaired = build_streamed_als_data(
                array_coo_chunks(uu, ii[perm], rr, times=tt),
                n_u, n_i, cfg, td,
            )
            assert repaired.directory != first.directory

    def test_torn_store_rejected(self, synthetic):
        n_u, n_i, uu, ii, rr, tt = synthetic
        cfg = ALSConfig(rank=8, iterations=1, reg=0.01, seed=1)
        chunks = array_coo_chunks(uu, ii, rr, times=tt)
        with tempfile.TemporaryDirectory() as td:
            sd = build_streamed_als_data(chunks, n_u, n_i, cfg, td)
            spec = sd.by_row.specs[0]
            with open(sd.by_row._path(spec, "idx"), "ab") as f:
                f.truncate(spec.idx_bytes() - 4)
            assert load_streamed_als_data(sd.directory) is None
            # the builder rebuilds over the torn carcass... by key change?
            # same key -> load fails -> rebuild path
            rebuilt = build_streamed_als_data(chunks, n_u, n_i, cfg, td)
            assert load_streamed_als_data(rebuilt.directory) is not None


class TestFeederResidency:
    def test_at_most_two_blocks_in_flight(self, synthetic):
        cfg = ALSConfig(rank=8, iterations=2, reg=0.01, seed=1)
        stats = StreamStats()
        _fit_both(synthetic, cfg, block_rows=16, stats=stats)
        assert stats.max_inflight_blocks <= 2
        assert stats.blocks_streamed > 8  # the bound was actually exercised

    def test_peak_host_memory_is_block_bounded(self):
        """tracemalloc (which tracks numpy buffers, not XLA's) must show
        the feeder holding O(block), not O(edges): a fit over a store many
        times larger than one block cannot allocate more than a few blocks
        of host memory at peak."""
        rng = np.random.default_rng(7)
        n_u, n_i, n_e = 8192, 512, 800_000
        uu = rng.integers(0, n_u, n_e)
        ii = rng.integers(0, n_i, n_e)
        vv = rng.random(n_e).astype(np.float32)  # mixed: no const elision
        cfg = ALSConfig(rank=8, iterations=2, reg=0.01, seed=1,
                        implicit=True, max_len=128)
        with tempfile.TemporaryDirectory() as td:
            sd = build_streamed_als_data(
                array_coo_chunks(uu, ii, vv), n_u, n_i, cfg, td,
                block_rows=384,
            )
            block_bytes = max(
                s.idx_bytes() + s.val_bytes() + s.nobs_bytes()
                for side in (sd.by_row, sd.by_col) for s in side.specs
            )
            total_bytes = sum(
                s.idx_bytes() + s.val_bytes() + s.nobs_bytes()
                for side in (sd.by_row, sd.by_col) for s in side.specs
            )
            assert total_bytes > 12 * block_bytes
            mesh = local_mesh(1, 1)
            als_fit_streamed(sd, cfg, mesh)  # warm the jit caches first:
            # tracing/compilation allocates ~MBs of host memory once per
            # program and would drown the feeder's footprint
            # tracemalloc counts every thread of the process, so another
            # thread's allocations (seen once in seven whole-suite runs
            # under six workers: 4.36 MB against a 3.9 MB budget) only ever
            # add to the reading: the feeder's bound is the smaller of two
            peaks = []
            for _ in range(2):
                tracemalloc.start()
                try:
                    als_fit_streamed(sd, cfg, mesh)
                    peaks.append(tracemalloc.get_traced_memory()[1])
                finally:
                    tracemalloc.stop()
            peak = min(peaks)
        # feeder bound: 2 blocks in flight + transient copies + factor
        # init/readback (entities * rank, f64) + slack; nothing near the
        # full store size
        factor_bytes = (sd.by_row.total_slots + sd.by_col.total_slots) * 8 * 8
        budget = 3 * block_bytes + 3 * factor_bytes + 1024 * 1024
        assert budget < total_bytes // 2  # the bound is a real distinction
        assert peak < budget, (
            f"peak host alloc {peak} vs block {block_bytes}, "
            f"store {total_bytes}"
        )


class TestTransferAccounting:
    def test_measured_matches_model_and_beats_reship(self, synthetic):
        """The acceptance metric: measured h2d bytes/half-step equals the
        stream model exactly, and on uniform-value implicit data it is
        <= 1/3 of the re-ship baseline (both sides' full CSR + both factor
        tables per half-step)."""
        n_u, n_i, uu, ii, _rr, _tt = synthetic
        cfg = ALSConfig(rank=8, iterations=3, reg=0.01, seed=1,
                        implicit=True, alpha=5.0)
        ones = np.ones(len(uu), np.float32)
        stats = StreamStats()
        with tempfile.TemporaryDirectory() as td:
            sd = build_streamed_als_data(
                array_coo_chunks(uu, ii, ones), n_u, n_i, cfg, td,
                block_rows=64,
            )
            als_fit_streamed(sd, cfg, local_mesh(1, 1), stats=stats)
            modeled = stream_bytes_per_half_step(sd, implicit=True)
            reship = reship_bytes_per_half_step(sd, cfg.rank, 4)
        assert stats.half_steps == 2 * cfg.iterations
        assert stats.bytes_per_half_step == pytest.approx(modeled)
        assert stats.bytes_per_half_step <= reship / 3.0
        # scalars (offset + const per block call) are noise, not a stream
        assert stats.h2d_scalar_bytes < 0.01 * stats.h2d_block_bytes + 4096

    def test_device_budget_pins_blocks(self, synthetic):
        """With a device budget, the first epoch pins blocks resident and
        later iterations hit the pin cache; an unlimited budget degrades
        to one transfer per block TOTAL (the resident path's transfer
        amortization, kept with streaming's O(block) build memory).
        Pinning changes WHEN bytes move, never what the kernels compute --
        the factors stay identical to the unpinned run."""
        cfg = ALSConfig(rank=8, iterations=4, reg=0.01, seed=1)
        pinned_stats = StreamStats()
        _, pinned_model, _, _ = _fit_both(
            synthetic, cfg, block_rows=64, stats=pinned_stats,
            budget=1 << 30,
        )
        nblocks = pinned_stats.blocks_streamed
        assert pinned_stats.pinned_bytes == pinned_stats.h2d_block_bytes
        # every block was put exactly once; later iterations hit the cache
        assert pinned_stats.blocks_pinned == nblocks * (cfg.iterations - 1)
        streamed_stats = StreamStats()
        _, streamed_model, _, _ = _fit_both(
            synthetic, cfg, block_rows=64, stats=streamed_stats
        )
        assert streamed_stats.blocks_pinned == 0
        assert pinned_stats.h2d_block_bytes * cfg.iterations == pytest.approx(
            streamed_stats.h2d_block_bytes
        )
        _assert_bit_identical(pinned_model, streamed_model)


class TestStreamedEpochEndToEnd:
    def test_streamed_epoch_converges(self):
        """The tier-1 streamed-epoch run: a chunk-source-only training pass
        (edges never materialize as one array) converging like the
        resident fit."""
        rng = np.random.default_rng(3)
        n_u, n_i, k = 300, 120, 8
        U = rng.normal(size=(n_u, k)) / np.sqrt(k)
        V = rng.normal(size=(n_i, k)) / np.sqrt(k)
        mask = rng.random((n_u, n_i)) < 0.2
        uu, ii = np.nonzero(mask)
        rr = (np.sum(U[uu] * V[ii], axis=1) + 0.01 * rng.normal(size=len(uu))
              ).astype(np.float32)
        cfg = ALSConfig(rank=8, iterations=6, reg=0.01, seed=1, buckets=2)
        with tempfile.TemporaryDirectory() as td:
            sd = build_streamed_als_data(
                array_coo_chunks(uu, ii, rr, chunk_rows=4096),
                n_u, n_i, cfg, td, block_rows=128,
            )
            model = als_fit_streamed(sd, cfg, local_mesh(1, 1))
        pred = np.sum(model.user_factors[uu] * model.item_factors[ii], axis=1)
        assert np.sqrt(np.mean((pred - rr) ** 2)) < 0.05

    def test_callback_and_divisibility_validation(self, synthetic):
        n_u, n_i, uu, ii, rr, tt = synthetic
        cfg = ALSConfig(rank=8, iterations=3, reg=0.01, seed=1)
        seen = []
        with tempfile.TemporaryDirectory() as td:
            sd = build_streamed_als_data(
                array_coo_chunks(uu, ii, rr, times=tt), n_u, n_i, cfg, td
            )
            als_fit_streamed(
                sd, cfg, local_mesh(1, 1),
                callback=lambda it, u, i: seen.append((it, u.shape)),
            )
            assert seen == [(0, (n_u, 8)), (1, (n_u, 8))]
            # a store whose block heights cannot split over the mesh is
            # rejected up front (forged 12-row spec: 8-multiples always
            # divide this box's meshes, so misalignment is synthesized)
            import dataclasses

            bad_spec = dataclasses.replace(sd.by_row.specs[0], rows=10)
            bad_side = dataclasses.replace(
                sd.by_row, specs=[bad_spec] + sd.by_row.specs[1:]
            )
            bad_data = dataclasses.replace(sd, by_row=bad_side)
            with pytest.raises(ValueError, match="data axis"):
                als_fit_streamed(bad_data, cfg, local_mesh(8, 1))
            bad_cfg = dataclasses.replace(cfg, factor_sharding="model")
            with pytest.raises(ValueError, match="model"):
                als_fit_streamed(bad_data, bad_cfg, local_mesh(2, 2))


@pytest.mark.slow
def test_stream_scale_bench_slow():
    """The >=100M-edge scaling proof is `python -m predictionio_tpu.tools.
    als_stream_bench --edges 100000000`; this slow-marked stand-in runs the
    same tool at a few million edges so CI outside tier-1 exercises the
    full path (generator -> spill -> pack -> streamed epoch -> metrics)."""
    from predictionio_tpu.tools.als_stream_bench import run_scale

    edges = int(os.environ.get("PIO_STREAM_TEST_EDGES", "2000000"))
    rep = run_scale(edges=edges, iterations=1)
    assert rep["edges"] == edges
    assert rep["edges_per_sec"] > 0
    assert rep["peak_rss_mb"] > 0
