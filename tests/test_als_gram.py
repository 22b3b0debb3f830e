"""Fused Pallas gather->Gram half-step kernels (``ops.als_gram``), pinned
against the XLA einsum path in interpret mode on the virtual CPU mesh --
the same kernel code the TPU runs compiled (``ops/flash_attention``
precedent)."""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.als_gram import _pick_chunk, gram_rhs, half_step_bytes
from predictionio_tpu.parallel.als import (
    EINSUM_GATHER_BUDGET_BYTES,
    ALSConfig,
    als_fit,
    block_paths,
    block_solver,
    build_als_data,
    gathered_bytes,
    make_iteration,
    resolve_solver,
)
from predictionio_tpu.parallel.mesh import local_mesh


@pytest.fixture(scope="module")
def synthetic():
    rng = np.random.default_rng(7)
    n_u, n_i, k = 120, 72, 6
    U = rng.normal(size=(n_u, k)) / np.sqrt(k)
    V = rng.normal(size=(n_i, k)) / np.sqrt(k)
    mask = rng.random((n_u, n_i)) < 0.2
    uu, ii = np.nonzero(mask)
    rr = (
        np.sum(U[uu] * V[ii], axis=1) + 0.01 * rng.normal(size=len(uu))
    ).astype(np.float32)
    return n_u, n_i, uu, ii, rr


def _reference(indices, values, table, alpha, implicit):
    """The XLA-path math: gather + einsum, f32 accumulation."""
    g = jnp.asarray(table)[jnp.asarray(indices)].astype(jnp.float32)
    v = jnp.asarray(values)
    if implicit:
        w = alpha * v
        gram = jnp.einsum("rlk,rl,rlj->rkj", g, w, g,
                          preferred_element_type=jnp.float32)
        rhs = jnp.einsum("rlk,rl->rk", g, 1.0 + w,
                         preferred_element_type=jnp.float32)
    else:
        gram = jnp.einsum("rlk,rlj->rkj", g, g,
                          preferred_element_type=jnp.float32)
        rhs = jnp.einsum("rlk,rl->rk", g, v,
                         preferred_element_type=jnp.float32)
    return np.asarray(gram), np.asarray(rhs)


class TestLongBlocksTileOverL:
    """A block longer than ``MAX_TILE_LEN`` is walked by a second grid axis
    that accumulates into the same output block; the last tile may hang
    over the block's end (its stale slots must die like packed padding)."""

    @pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
    @pytest.mark.parametrize("pad_len", [128, 152], ids=["even_tiles", "ragged_last_tile"])
    def test_tiled_matches_einsum_path(self, monkeypatch, pad_len, implicit):
        from predictionio_tpu.ops import als_gram

        monkeypatch.setattr(als_gram, "MAX_TILE_LEN", 64)
        rng = np.random.default_rng(5)
        rows, slots, k = 16, 40, 6
        table = jnp.asarray(np.concatenate(
            [rng.normal(size=(slots, k)), np.zeros((1, k))]), jnp.float32)
        indices = rng.integers(0, slots, size=(rows, pad_len)).astype(np.int32)
        values = rng.normal(size=(rows, pad_len)).astype(np.float32)
        # packed padding at the tail of every row, as pack_padded_csr leaves it
        indices[:, pad_len - 20:] = slots
        values[:, pad_len - 20:] = 0.0
        gram, rhs = gram_rhs(jnp.asarray(indices), jnp.asarray(values), table,
                             3.0, implicit=implicit, interpret=True)
        gram_ref, rhs_ref = _reference(indices, values, table, 3.0, implicit)
        np.testing.assert_allclose(np.asarray(gram), gram_ref, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rhs), rhs_ref, atol=1e-4)


class TestKernelParity:
    """gram_rhs vs the einsum reference on real padded-CSR blocks."""

    @pytest.mark.parametrize("implicit", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_einsum_path(self, synthetic, implicit, dtype):
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        block = data.by_row.blocks[0]
        rng = np.random.default_rng(3)
        table = jnp.asarray(
            np.concatenate([
                rng.normal(size=(data.by_col.total_slots, 6)),
                np.zeros((1, 6)),
            ]),
            dtype,
        )
        alpha = 10.0
        gram, rhs = gram_rhs(
            jnp.asarray(block.indices), jnp.asarray(block.values), table,
            alpha, implicit=implicit, interpret=True,
        )
        gram_ref, rhs_ref = _reference(
            block.indices, block.values, table, alpha, implicit
        )
        assert gram.dtype == jnp.float32 and rhs.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(gram), gram_ref, atol=1e-4)
        np.testing.assert_allclose(np.asarray(rhs), rhs_ref, atol=1e-4)

    def test_padding_rows_contribute_zero(self):
        """The padding invariant inside the kernel: sentinel indices hit
        the appended zero factor row, so an all-padding row's Gram/rhs is
        exactly zero (no mask stream needed)."""
        rng = np.random.default_rng(0)
        s, k, l = 24, 6, 16
        table = jnp.asarray(
            np.concatenate([rng.normal(size=(s, k)), np.zeros((1, k))]),
            jnp.float32,
        )
        idx = np.full((8, l), s, np.int32)      # every slot = sentinel
        idx[0, :4] = [1, 2, 3, 4]               # row 0 has 4 real entries
        val = np.zeros((8, l), np.float32)
        val[0, :4] = 1.0
        gram, rhs = gram_rhs(
            jnp.asarray(idx), jnp.asarray(val), table,
            implicit=True, alpha=5.0, interpret=True,
        )
        assert np.abs(np.asarray(gram[1:])).max() == 0.0
        assert np.abs(np.asarray(rhs[1:])).max() == 0.0
        assert np.abs(np.asarray(gram[0])).max() > 0.0

    def test_uneven_row_blocks_shrink_block_rows(self):
        """Per-device row counts that 8 does not divide (e.g. a 24-row
        block split over a 2-way data axis -> 12 rows) must run at a
        smaller BR, not raise where the XLA path works."""
        rng = np.random.default_rng(1)
        s, k, l = 16, 4, 8
        table = jnp.asarray(
            np.concatenate([rng.normal(size=(s, k)), np.zeros((1, k))]),
            jnp.float32,
        )
        idx = rng.integers(0, s + 1, size=(12, l)).astype(np.int32)
        val = rng.random((12, l)).astype(np.float32)
        gram, rhs = gram_rhs(
            jnp.asarray(idx), jnp.asarray(val), table, interpret=True
        )
        gram_ref, rhs_ref = _reference(idx, val, table, 0.0, False)
        np.testing.assert_allclose(np.asarray(gram), gram_ref, atol=1e-5)
        np.testing.assert_allclose(np.asarray(rhs), rhs_ref, atol=1e-5)

    def test_chunk_picker_covers_8_multiples(self):
        for pad_len in (8, 24, 40, 128, 200, 256, 1024):
            chunk = _pick_chunk(pad_len)
            assert pad_len % chunk == 0 and chunk <= 256
        with pytest.raises(ValueError, match="multiple of 8"):
            _pick_chunk(12)

    def test_bytes_model_fused_beats_unfused(self):
        fused = half_step_bytes(1000, 256, 16, 2, fused=True)
        unfused = half_step_bytes(1000, 256, 16, 2, fused=False)
        assert unfused > 2 * fused  # the dropped [R, L, K] write+reads


class TestSolverSelection:
    def test_invalid_solver_rejected(self, synthetic):
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6, solver="cuda")
        with pytest.raises(ValueError, match="solver"):
            make_iteration(local_mesh(1, 1), cfg)

    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    def test_auto_resolves_to_xla(self, platform):
        """"auto" is the einsum path on both: on CPU the kernel would
        interpret, on the chip it is 15x slower wherever both fit. On a CPU
        mesh the cached program proves the resolution."""
        assert resolve_solver("auto", platform) == "xla"
        assert block_solver("auto", platform, 512, 64, 6, 4) == "xla"
        if platform == "cpu":
            mesh = local_mesh(1, 1)
            auto = make_iteration(mesh, ALSConfig(rank=6, solver="auto"))
            xla = make_iteration(mesh, ALSConfig(rank=6, solver="xla"))
            pallas = make_iteration(mesh, ALSConfig(rank=6, solver="pallas"))
            assert auto is xla
            assert pallas is not xla


#: the blocks of ``als-ml20m-r16.train-steady`` (PERF.md section 4: bf16,
#: rank 16, cap 256, 4 buckets a side; the largest is 2.31 GB of gathered
#: rows) and of the recommendation template's default packing at
#: MovieLens-1M (one bucket, no cap, f32; 45.3 GB for the item side)
CELL_BLOCKS = [
    (35_312, 256), (22_872, 152), (28_696, 88), (51_632, 48),
    (7_648, 256), (2_224, 144), (3_840, 64), (13_048, 16),
]
ML1M_ITEM_BLOCK = (3_712, 23_832)


class TestBlockRule:
    """``block_solver``: the one statement of what "auto" runs, per block."""

    @pytest.mark.parametrize("rows,pad_len", CELL_BLOCKS)
    def test_cell_blocks_take_the_einsum_tail(self, rows, pad_len):
        assert gathered_bytes(rows, pad_len, 16, 2) == rows * pad_len * 256
        assert block_solver("auto", "tpu", rows, pad_len, 16, 2) == "xla"

    def test_template_default_item_block_takes_the_kernel(self):
        rows, pad_len = ML1M_ITEM_BLOCK
        assert gathered_bytes(rows, pad_len, 16, 4) == 45_293_764_608
        assert block_solver("auto", "tpu", rows, pad_len, 16, 4) == "pallas"
        # the same block on a CPU mesh: the kernel would only interpret
        assert block_solver("auto", "cpu", rows, pad_len, 16, 4) == "xla"

    def test_the_budget_is_the_line(self):
        """A full lane row (rank 128) is not padded; the budget is compared
        on one device's rows."""
        fit = EINSUM_GATHER_BUDGET_BYTES // (256 * 128 * 2)
        assert block_solver("auto", "tpu", fit, 256, 128, 2) == "xla"
        assert block_solver("auto", "tpu", fit + 8, 256, 128, 2) == "pallas"
        assert gathered_bytes(8, 8, 129, 4) == 8 * 8 * 256 * 4

    @pytest.mark.parametrize("solver", ["xla", "pallas"])
    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    @pytest.mark.parametrize(
        "rows,pad_len", [CELL_BLOCKS[0], ML1M_ITEM_BLOCK], ids=["cell", "ml1m"]
    )
    def test_a_solver_given_by_name_forces_every_block(
        self, solver, platform, rows, pad_len
    ):
        assert resolve_solver(solver, platform) == solver
        assert block_solver(solver, platform, rows, pad_len, 16, 4) == solver

    def test_invalid_solver_rejected_by_the_rule(self):
        with pytest.raises(ValueError, match="solver"):
            block_solver("cuda", "tpu", 8, 8, 16, 4)

    def test_block_paths_counts_both_sides(self, synthetic):
        """On a CPU mesh every block is on the einsum tail under "auto" and
        on the kernel under "pallas"; the count is over both sides."""
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6, buckets=2)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        n = len(data.by_row.blocks) + len(data.by_col.blocks)
        mesh = local_mesh(1, 1)
        whole = {"chunked": 0, "max_chunks": 1,  # every block in one piece
                 "blocked_solve": 0}              # a CPU mesh solves by LAPACK
        assert block_paths(data, cfg, mesh) == {"xla": n, "pallas": 0, **whole}
        forced = ALSConfig(rank=6, buckets=2, solver="pallas")
        assert block_paths(data, forced, mesh) == {"xla": 0, "pallas": n, **whole}

    def test_als_fit_logs_the_count_of_blocks_on_each_path(
        self, synthetic, caplog
    ):
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6, iterations=2, buckets=2)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        n = len(data.by_row.blocks) + len(data.by_col.blocks)
        with caplog.at_level("INFO", logger="pio.als"):
            als_fit(data, cfg, local_mesh(1, 1))
        (line,) = [r.getMessage() for r in caplog.records
                   if r.getMessage().startswith("als_fit:")]
        assert f"solver=auto blocks_xla={n} blocks_pallas=0" in line


class TestSolverPlumbing:
    def test_cli_flag_parses_into_runtime_conf_key(self):
        from predictionio_tpu.tools.cli import build_parser

        args = build_parser().parse_args(
            ["train", "--als-solver", "pallas"]
        )
        assert args.als_solver == "pallas"

    def test_runtime_conf_overrides_engine_param(self):
        from predictionio_tpu.models._als_common import resolve_solver_override

        class Ctx:
            runtime_conf = {"pio.als_solver": "xla"}

        cfg = ALSConfig(rank=6, solver="pallas")
        assert resolve_solver_override(cfg, Ctx()).solver == "xla"
        # no override -> the engine.json param stands
        class Bare:
            pass

        assert resolve_solver_override(cfg, Bare()).solver == "pallas"


class TestEndToEndParity:
    """als_fit(solver="pallas") vs solver="xla": all four
    explicit/implicit x f32/bf16 combinations (acceptance criterion)."""

    @pytest.mark.parametrize("implicit", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_fit_matches_xla(self, synthetic, implicit, dtype):
        n_u, n_i, uu, ii, rr = synthetic
        vals = np.ones(len(uu), np.float32) if implicit else rr
        kw = dict(rank=6, iterations=2, reg=0.01, seed=1,
                  implicit=implicit, alpha=10.0, dtype=dtype)
        cfg_x = ALSConfig(**kw, solver="xla")
        cfg_p = ALSConfig(**kw, solver="pallas")
        data = build_als_data(uu, ii, vals, n_u, n_i, cfg_x)
        mesh = local_mesh(1, 1)
        m_x = als_fit(data, cfg_x, mesh)
        m_p = als_fit(data, cfg_p, mesh)
        # identical ridge/solve tail; the only fp difference is the Gram
        # reduction order (chunked on-chip vs one einsum). bf16 rounds the
        # stored factors each iteration, so its drift bound is looser.
        atol = 1e-4 if dtype == "float32" else 5e-3
        np.testing.assert_allclose(
            m_x.user_factors, m_p.user_factors, atol=atol
        )
        np.testing.assert_allclose(
            m_x.item_factors, m_p.item_factors, atol=atol
        )

    def test_padding_invariance(self, synthetic):
        """Adding padding slots (bigger shard multiples pad every bucket
        further) never changes the solved factors in original entity
        order -- the property that lets the kernel skip the mask stream."""
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6, iterations=2, reg=0.01, seed=1,
                        solver="pallas")
        lean = build_als_data(uu, ii, rr, n_u, n_i, cfg, num_shards=1)
        padded = build_als_data(uu, ii, rr, n_u, n_i, cfg, num_shards=8)
        assert padded.by_row.total_slots > lean.by_row.total_slots
        mesh = local_mesh(1, 1)
        m_lean = als_fit(lean, cfg, mesh)
        m_pad = als_fit(padded, cfg, mesh)
        np.testing.assert_allclose(
            m_lean.user_factors, m_pad.user_factors, atol=1e-5
        )

    def test_model_sharded_pallas_matches_xla(self, synthetic):
        """The fused local-hit gather + [K, K] psum_scatter exchange
        (solver="pallas", factor_sharding="model") reproduces the XLA
        block exchange on a data x model mesh with bucketed blocks."""
        n_u, n_i, uu, ii, rr = synthetic
        kw = dict(rank=6, iterations=2, reg=0.01, seed=1,
                  factor_sharding="model", buckets=2)
        cfg_x = ALSConfig(**kw, solver="xla")
        cfg_p = ALSConfig(**kw, solver="pallas")
        data = build_als_data(
            uu, ii, rr, n_u, n_i, cfg_x, num_shards=2, model_shards=2
        )
        mesh = local_mesh(2, 2)
        m_x = als_fit(data, cfg_x, mesh)
        m_p = als_fit(data, cfg_p, mesh)
        np.testing.assert_allclose(
            m_x.user_factors, m_p.user_factors, atol=1e-4
        )
        np.testing.assert_allclose(
            m_x.item_factors, m_p.item_factors, atol=1e-4
        )
