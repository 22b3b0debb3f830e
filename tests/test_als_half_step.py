"""The one ALS half-step (``parallel.als``: gather, two einsums, ridge, solve)
against a NumPy float64 statement of the normal equations, whole and in the
row chunks ``block_plan`` cuts a large block into; the rule on the blocks of
the listed cells; and what is left of the ``alsSolver`` selector. CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.parallel import als
from predictionio_tpu.parallel.als import (
    EINSUM_GATHER_BUDGET_BYTES,
    ALSConfig,
    als_fit,
    block_paths,
    block_plan,
    build_als_data,
    gathered_bytes,
    make_iteration,
    normal_equation_bytes,
    resolve_solver,
)
from predictionio_tpu.parallel.mesh import local_mesh

REG, ALPHA = 0.05, 3.0


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


@pytest.fixture(scope="module")
def skewed():
    """The template-default shape in small: one bucket and no cap over 96
    users x 64 items, one item rated by 88 users and the others by 2 to 6,
    so the item block is as long as its longest row: 22 times the median."""
    rng = np.random.default_rng(11)
    n_u, n_i = 96, 64
    raters = [rng.choice(n_u, size=88 if item == 0 else rng.integers(2, 7), replace=False)
              for item in range(n_i)]
    uu = np.concatenate(raters)
    ii = np.repeat(np.arange(n_i), [len(r) for r in raters])
    rr = rng.integers(1, 6, uu.size).astype(np.float32)
    return n_u, n_i, uu, ii, rr


def _float64_rows(indices, values, table, implicit):
    """The rows the half-step must solve, NumPy float64, from the padded
    block itself: ``table``'s last row is the zero row padding points at."""
    t = np.asarray(jnp.asarray(table, jnp.float32), np.float64)
    g, v, k = t[indices], np.asarray(values, np.float64), t.shape[1]
    if implicit:
        w = ALPHA * v
        gram = (np.einsum("rlk,rl,rlj->rkj", g, w, g) + t[:-1].T @ t[:-1]
                + REG * np.eye(k))
        rhs = np.einsum("rlk,rl->rk", g, 1.0 + w)
    else:
        n_obs = np.maximum((indices != t.shape[0] - 1).sum(axis=1), 1)
        gram = np.einsum("rlk,rlj->rkj", g, g) + REG * n_obs[:, None, None] * np.eye(k)
        rhs = np.einsum("rlk,rl->rk", g, v)
    return np.linalg.solve(gram, rhs[..., None])[..., 0]


def _half_step_rows(indices, values, table, implicit):
    """One block through ``_half_steps`` on one device: the step
    ``block_plan`` picks for its shape under the budget in force."""
    rank = table.shape[1]
    pick = als._half_steps(local_mesh(1, 1), implicit, rank, "replicated")
    n_obs = jnp.asarray((indices != table.shape[0] - 1).sum(axis=1), jnp.float32)
    shared = als._shared_gram(table[:-1], jnp.float32(REG), implicit)
    idx = jnp.asarray(indices)
    out = jax.jit(pick(idx, table))(
        idx, jnp.asarray(values), n_obs, table, shared, jnp.float32(REG), jnp.float32(ALPHA))
    return np.asarray(out, np.float32)


def _relative_error(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


#: float32 factors: sums in another order and a float32 solve. bfloat16: the
#: reference starts from the same rounded table, so what is left is the
#: rounding of each solved row to 8 bits of mantissa (tests/
#: test_als_sharded_implicit.py reads 1.7e-3 at rank 128)
TOLERANCE = {jnp.float32: 1e-4, jnp.bfloat16: 4e-3}
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
MODES = pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])


def _table(rng, slots, rank, dtype):
    return jnp.asarray(np.concatenate(
        [rng.normal(size=(slots, rank)) / np.sqrt(rank), np.zeros((1, rank))]), dtype)


class TestAgainstFloat64:
    @MODES
    @DTYPES
    @pytest.mark.parametrize("rank", [6, 16])
    def test_a_packed_block(self, synthetic, implicit, dtype, rank):
        n_u, n_i, uu, ii, rr = synthetic
        data = build_als_data(uu, ii, rr, n_u, n_i, ALSConfig(rank=rank))
        block = data.by_row.blocks[0]
        table = _table(np.random.default_rng(3), data.by_col.total_slots, rank, dtype)
        values = np.abs(block.values)  # a confidence is not negative
        got = _half_step_rows(block.indices, values, table, implicit)
        want = _float64_rows(block.indices, values, table, implicit)
        assert _relative_error(got, want) < TOLERANCE[dtype]

    def test_padding_rows_contribute_zero(self):
        """The padding invariant: sentinel indices hit the appended zero
        factor row, so an all-padding row's Gram and right-hand side are zero
        and its solved row is exactly zero, with no mask stream."""
        s, k, l = 24, 6, 16
        table = _table(np.random.default_rng(0), s, k, jnp.float32)
        idx = np.full((8, l), s, np.int32)      # every slot = sentinel
        idx[0, :4] = [1, 2, 3, 4]               # row 0 has 4 real entries
        val = np.zeros((8, l), np.float32)
        val[0, :4] = 1.0
        rows = _half_step_rows(idx, val, table, implicit=False)
        assert np.abs(rows[1:]).max() == 0.0
        assert np.abs(rows[0]).max() > 0.0

    @MODES
    def test_a_row_count_the_chunks_do_not_divide(self, implicit):
        """20 rows in 3 chunks: runs are padded to 8 x 3 rows with empty
        rows, dropped on the way out; the 20 come out bit for bit."""
        s, k, l = 16, 4, 8
        rng = np.random.default_rng(1)
        table = _table(rng, s, k, jnp.float32)
        idx = jnp.asarray(rng.integers(0, s + 1, size=(20, l)).astype(np.int32))
        val = jnp.asarray(rng.random((20, l)).astype(np.float32))
        n_obs = jnp.asarray((np.asarray(idx) != s).sum(axis=1), jnp.float32)
        shared = als._shared_gram(table[:-1], jnp.float32(REG))
        args = (idx, val, n_obs, table, shared, jnp.float32(REG), jnp.float32(ALPHA))
        step = als._half_steps(local_mesh(1, 1), implicit, k, "replicated")(idx, table)
        whole = jax.jit(step)(*args)
        cut = jax.jit(als._in_row_chunks(step, 3))(*args)
        assert cut.shape == (20, k)
        assert np.array_equal(np.asarray(cut), np.asarray(whole))

    @MODES
    @DTYPES
    @pytest.mark.parametrize("pad_len", [128, 152])
    def test_long_ragged_blocks_in_chunks(self, a_small_als_budget, pad_len, dtype, implicit):
        """Rows much longer than the rank with packed padding at their tails
        (as ``pack_padded_csr`` leaves it), worked in several chunks."""
        rng = np.random.default_rng(5)
        rows, slots, k = 40, 40, 6
        assert block_plan("cpu", rows, pad_len, k, jnp.dtype(dtype).itemsize) >= 3
        table = _table(rng, slots, k, dtype)
        indices = rng.integers(0, slots, size=(rows, pad_len)).astype(np.int32)
        values = rng.random(size=(rows, pad_len)).astype(np.float32)
        tails = rng.integers(20, pad_len, size=rows)
        for r, tail in enumerate(tails):
            indices[r, pad_len - tail:] = slots
            values[r, pad_len - tail:] = 0.0
        got = _half_step_rows(indices, values, table, implicit)
        want = _float64_rows(indices, values, table, implicit)
        assert _relative_error(got, want) < TOLERANCE[dtype]


class TestFitInChunks:
    """``als_fit`` over the template-default shape (one bucket, no cap, the
    item block as long as its longest row): the chunks change nothing."""

    @staticmethod
    def _fit(skewed, cfg, shards=(1, 1), pad_shards=None):
        n_u, n_i, uu, ii, rr = skewed
        vals = np.ones(len(uu), np.float32) if cfg.implicit else rr
        d, m = shards
        data = build_als_data(uu, ii, vals, n_u, n_i, cfg,
                              num_shards=pad_shards or d, model_shards=m)
        mesh = local_mesh(d, m)
        als._build_iteration.cache_clear()
        return als_fit(data, cfg, mesh), block_paths(data, cfg, mesh), data

    @MODES
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("shards", [(1, 1), (2, 1)], ids=["one_device", "data2"])
    def test_chunked_equals_whole(self, skewed, monkeypatch, shards, dtype, implicit):
        cfg = ALSConfig(rank=6, iterations=2, reg=0.01, seed=1, implicit=implicit,
                        alpha=10.0, dtype=dtype)
        whole, paths, _ = self._fit(skewed, cfg, shards)
        assert paths == {"blocks": 2, "chunked": 0, "max_chunks": 1, "blocked_solve": 0,
                         "dual_solve": 0}  # rank 6: no block is short against it
        monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", 1 << 16)
        cut, paths, _ = self._fit(skewed, cfg, shards)
        assert paths["chunked"] == 2 and paths["max_chunks"] >= 3
        assert np.array_equal(cut.user_factors, whole.user_factors)
        assert np.array_equal(cut.item_factors, whole.item_factors)

    def test_model_sharded_chunked_equals_whole(self, skewed, monkeypatch):
        cfg = ALSConfig(rank=6, iterations=2, reg=0.01, seed=1, factor_sharding="model")
        whole, _, _ = self._fit(skewed, cfg, (2, 2))
        monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", 1 << 16)
        cut, paths, _ = self._fit(skewed, cfg, (2, 2))
        assert paths["chunked"] == 2
        assert np.array_equal(cut.user_factors, whole.user_factors)
        assert np.array_equal(cut.item_factors, whole.item_factors)

    @MODES
    def test_padding_invariance(self, skewed, a_small_als_budget, implicit):
        """More padding (a bucket padded to a larger multiple of rows, and
        the empty rows the chunks add) never changes the solved factors in
        original entity order."""
        cfg = ALSConfig(rank=6, iterations=2, reg=0.01, seed=1, implicit=implicit,
                        alpha=10.0)
        lean, _, lean_data = self._fit(skewed, cfg)
        padded, _, padded_data = self._fit(skewed, cfg, pad_shards=8)
        assert padded_data.by_row.total_slots > lean_data.by_row.total_slots
        np.testing.assert_allclose(lean.user_factors, padded.user_factors, atol=1e-5)
        np.testing.assert_allclose(lean.item_factors, padded.item_factors, atol=1e-5)


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
    """``block_plan`` on the shapes that matter (tests/
    test_als_sharded_implicit.py holds the table of the rule)."""

    @pytest.mark.parametrize("rows,pad_len", CELL_BLOCKS)
    def test_cell_blocks_are_worked_whole(self, rows, pad_len):
        assert gathered_bytes(rows, pad_len, 16, 2) == rows * pad_len * 256
        assert block_plan("tpu", rows, pad_len, 16, 2) == 1

    def test_template_default_item_block_goes_in_11_chunks(self):
        rows, pad_len = ML1M_ITEM_BLOCK
        assert gathered_bytes(rows, pad_len, 16, 4) == 45_293_764_608
        assert block_plan("tpu", rows, pad_len, 16, 4) == 11
        # 344 rows a chunk, their gathered rows 3.91 GiB
        assert gathered_bytes(344, pad_len, 16, 4) / (1 << 30) == pytest.approx(3.91, abs=5e-3)

    def test_the_budget_is_the_line(self):
        """A full lane row (rank 128) is not padded; the budget is compared
        on what one device's rows allocate."""
        per_row = gathered_bytes(1, 256, 128, 2) + normal_equation_bytes(1, 128, unroll=False)
        fit = EINSUM_GATHER_BUDGET_BYTES // per_row
        assert block_plan("cpu", fit, 256, 128, 2) == 1
        assert block_plan("cpu", fit + 1, 256, 128, 2) == 2
        assert gathered_bytes(8, 8, 129, 4) == 8 * 8 * 256 * 4

    def test_block_paths_counts_both_sides(self, synthetic):
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6, buckets=2)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        n = len(data.by_row.blocks) + len(data.by_col.blocks)
        assert block_paths(data, cfg, local_mesh(1, 1)) == {
            "blocks": n, "chunked": 0, "max_chunks": 1,  # every block in one piece
            "blocked_solve": 0,                           # a CPU mesh solves by LAPACK
            "dual_solve": 0}                              # explicit

    def test_als_fit_logs_how_the_blocks_are_worked(self, synthetic, caplog):
        n_u, n_i, uu, ii, rr = synthetic
        cfg = ALSConfig(rank=6, iterations=2, buckets=2)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        n = len(data.by_row.blocks) + len(data.by_col.blocks)
        with caplog.at_level("INFO", logger="pio.als"):
            als_fit(data, cfg, local_mesh(1, 1))
        (line,) = [r.getMessage() for r in caplog.records
                   if r.getMessage().startswith("als_fit:")]
        assert f"factor_sharding=replicated blocks={n} blocks_chunked=0 max_chunks=1" in line
        assert "solver" not in line and "pallas" not in line


class TestWhatIsLeftOfTheSelector:
    """``ALSConfig.solver`` and ``resolve_solver`` stay for two lines of the
    benchmark's drivers (ROADMAP.md); nothing else reads them."""

    def test_a_config_that_names_the_kernel_fails_loudly(self):
        with pytest.raises(ValueError, match="removed in PR 28"):
            make_iteration(local_mesh(1, 1), ALSConfig(rank=6, solver="pallas"))
        with pytest.raises(ValueError, match="removed in PR 28"):
            resolve_solver("cuda", "tpu")

    @pytest.mark.parametrize("template", ["recommendation", "ecommerce"])
    def test_an_engine_json_that_names_the_kernel_fails_loudly(self, template):
        from predictionio_tpu.controller.base import Params
        from predictionio_tpu.models._als_common import resolve_factor_sharding
        from predictionio_tpu.models.ecommerce.engine import ECommAlgorithm
        from predictionio_tpu.models.recommendation.engine import ALSAlgorithm

        algorithm = {"recommendation": ALSAlgorithm, "ecommerce": ECommAlgorithm}[template]
        mesh = local_mesh(1, 1)
        config = algorithm(Params({"rank": 6, "alsSolver": "pallas"}))._config()
        with pytest.raises(ValueError, match="removed in PR 28"):
            make_iteration(mesh, resolve_factor_sharding(config, mesh))
        assert algorithm(Params({"rank": 6}))._config().solver == "auto"

    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    def test_auto_and_xla_are_the_one_program(self, platform):
        assert resolve_solver("auto", platform) == resolve_solver("xla", platform) == "xla"
        mesh = local_mesh(1, 1)
        assert (make_iteration(mesh, ALSConfig(rank=6, solver="auto"))
                is make_iteration(mesh, ALSConfig(rank=6, solver="xla")))

    def test_the_cli_has_no_solver_flag(self, capsys):
        from predictionio_tpu.tools.cli import build_parser

        with pytest.raises(SystemExit):
            build_parser().parse_args(["train", "--als-solver", "xla"])
        assert "unrecognized arguments: --als-solver" in capsys.readouterr().err
