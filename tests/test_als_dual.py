"""The implicit tail's dual (Woodbury) form (``parallel.als._dual_solve_implicit``)
against the primal form and against a NumPy float64 statement of the same
normal equations; the shape test that picks it (``takes_dual``); what
``block_plan`` and ``block_paths`` say of a dual block; and that an explicit
program knows nothing of it. CPU: LAPACK solves the ``[L, L]`` systems.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.parallel import als
from predictionio_tpu.parallel.als import (
    ALSConfig, als_fit, block_paths, block_plan, build_als_data, takes_dual)
from predictionio_tpu.parallel.mesh import local_mesh

REG, ALPHA, SLOTS = 0.1, 40.0, 3000
ROWS = 64
#: rows of the block below that state a case of their own
EMPTY, LOUD, REPEATED, SILENT = 0, 1, 2, 3


def _block(pad_len, rank, dtype, seed=0):
    """A ``[64, pad_len]`` block over a seeded table of 3,000 slots: rows of 0
    to ``pad_len`` observations with padding behind them, play counts from a
    power law, and four rows that are cases of their own: one with no
    observation at all; one whose first song was played 9,667 times (MSD's
    most, a confidence of 3.9e5 at alpha 40); one that holds the same song
    twice (the packer keeps a repeated pair as two slots); and one whose
    first slot is a real song of value zero."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(np.concatenate(
        [rng.normal(size=(SLOTS, rank)) / np.sqrt(rank), np.zeros((1, rank))]), dtype)
    idx = rng.integers(0, SLOTS, size=(ROWS, pad_len)).astype(np.int32)
    val = np.minimum(rng.zipf(2.25, size=(ROWS, pad_len)), 9667).astype(np.float32)
    for row in range(ROWS):
        kept = rng.integers(1, pad_len + 1)
        idx[row, kept:], val[row, kept:] = SLOTS, 0.0
    idx[EMPTY], val[EMPTY] = SLOTS, 0.0
    val[LOUD, 0] = 9667.0
    idx[REPEATED, :2], val[REPEATED, :2] = 7, (3.0, 2.0)
    val[SILENT, 0] = 0.0
    return table, idx, val


def _float64_rows(table, idx, val):
    """``(Y'Y + sum_obs alpha r y y' + reg I) x = sum_obs (1 + alpha r) y``,
    NumPy float64, from the padded block itself."""
    t = np.asarray(jnp.asarray(table, jnp.float32), np.float64)
    g, w, k = t[idx], ALPHA * val.astype(np.float64), t.shape[1]
    gram = np.einsum("rlk,rl,rlj->rkj", g, w, g) + t[:-1].T @ t[:-1] + REG * np.eye(k)
    rhs = np.einsum("rlk,rl->rk", g, 1.0 + w)
    return np.linalg.solve(gram, rhs[..., None])[..., 0]


def _relative_error(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


#: against float64. float32 tables: the dual form read 2e-7 to 4e-7 (its
#: ``[L, L]`` system has every eigenvalue >= 1 whatever the play counts), the
#: primal form 8e-5 to 3.2e-4 with a count of 9,667 in the block (its ``[K,
#: K]`` system carries a condition number of 1e6). bfloat16 tables: both read
#: 1.6e-3 to 1.7e-3, the rounding of the solved row (PERF.md section 2)
DUAL_TOLERANCE = {jnp.float32: 5e-6, jnp.bfloat16: 4e-3}
PRIMAL_TOLERANCE = {jnp.float32: 1e-3, jnp.bfloat16: 4e-3}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("rank", [48, 128])
@pytest.mark.parametrize("pad_len", [8, 16, 24, 48, 56, 64])
def test_the_dual_tail_solves_the_primal_tails_equations(pad_len, rank, dtype):
    """The two forms are one equation: rows out of ``_dual_solve_implicit``
    (called outright, so also where the shape test would not pick it: the
    identity holds for any L) against float64 and against the primal tail."""
    table, idx, val = _block(pad_len, rank, dtype, seed=pad_len + rank)
    shared = als._shared_gram(table[:-1], jnp.float32(REG))
    gathered, values = table[jnp.asarray(idx)], jnp.asarray(val)
    dual = np.asarray(jax.jit(
        lambda g, v: als._dual_solve_implicit(g, v, shared[1], ALPHA, False, jnp.float32)
    )(gathered, values))
    primal = np.asarray(jax.jit(
        lambda g, v: als._finish_implicit(
            jnp.einsum("rlk,rl,rlj->rkj", g, ALPHA * v, g, precision="highest",
                       preferred_element_type=jnp.float32),
            jnp.einsum("rlk,rl->rk", g, 1.0 + ALPHA * v, precision="highest",
                       preferred_element_type=jnp.float32),
            shared[0], REG, rank, False, jnp.float32)
    )(gathered, values))
    want = _float64_rows(table, idx, val)
    assert np.isfinite(dual).all()
    assert _relative_error(dual, want) < DUAL_TOLERANCE[dtype]
    assert _relative_error(primal, want) < PRIMAL_TOLERANCE[dtype]
    assert _relative_error(dual, primal) < PRIMAL_TOLERANCE[dtype]
    # the cases of their own, each against float64 (float32 tables: a bfloat16
    # table's own rounding is 4e-3 of a row whatever the form)
    assert np.abs(dual[EMPTY]).max() == 0.0
    if dtype == jnp.float32:
        for row in (LOUD, REPEATED, SILENT):
            assert _relative_error(dual[row], want[row]) < DUAL_TOLERANCE[dtype], row


def _half_step(table, idx, val, rank):
    """The block through ``_half_steps`` on one device, as a program has it:
    the form ``takes_dual`` picks from its shape. Rows and the jaxpr."""
    pick = als._half_steps(local_mesh(1, 1), True, rank, "replicated")
    idx = jnp.asarray(idx)
    args = (idx, jnp.asarray(val), jnp.zeros((idx.shape[0],), jnp.float32), table,
            als._shared_gram(table[:-1], jnp.float32(REG)), jnp.float32(REG),
            jnp.float32(ALPHA))
    step = pick(idx, table)
    return np.asarray(jax.jit(step)(*args), np.float32), str(jax.make_jaxpr(step)(*args))


class TestTheShapeTest:
    def test_the_line_is_half_the_rank(self):
        assert takes_dual(True, 64, 128) and not takes_dual(True, 65, 128)
        assert takes_dual(True, 24, 48) and not takes_dual(True, 25, 48)
        assert takes_dual(True, 8, 16) and not takes_dual(True, 16, 16)
        # ALS-WR's ridge differs by row: an explicit block shares nothing to whiten with
        assert not any(takes_dual(False, pad_len, 128) for pad_len in (8, 64, 256))

    @pytest.mark.parametrize("pad_len,rank,dual", [(64, 128, True), (65, 128, False),
                                                   (24, 48, True), (25, 48, False)])
    def test_each_side_of_the_line_in_the_traced_program(self, pad_len, rank, dual):
        """``2L == K`` makes ``[L, L]`` systems and no ``[K, K]`` Gram; ``2L ==
        K + 2`` the reverse; either way the rows are the float64 rows."""
        table, idx, val = _block(pad_len, rank, jnp.float32, seed=pad_len)
        rows, jaxpr = _half_step(table, idx, val, rank)
        assert (f"f32[{ROWS},{pad_len},{pad_len}]" in jaxpr) == dual
        assert (f"f32[{ROWS},{rank},{rank}]" in jaxpr) == (not dual)
        want = _float64_rows(table, idx, val)
        assert _relative_error(rows, want) < (DUAL_TOLERANCE if dual else PRIMAL_TOLERANCE)[jnp.float32]

    def test_a_chunked_dual_block_equals_the_block_whole(self, monkeypatch):
        table, idx, val = _block(16, 128, jnp.float32)
        whole, _ = _half_step(table, idx, val, 128)
        monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", 1 << 18)
        assert block_plan("cpu", ROWS, 16, 128, 4, implicit=True) >= 3
        cut, jaxpr = _half_step(table, idx, val, 128)
        assert " while" in jaxpr or "scan" in jaxpr
        assert np.array_equal(cut, whole)


class TestWhatTheRuleCounts:
    def test_a_dual_block_holds_whitened_rows_and_small_systems(self):
        """24 slots at rank 128, bf16 tables, on a TPU: 12 KiB of float32
        whitened rows a solved row beside 12 KiB of one lane-padded ``[24,
        24]`` system (the unrolled solve works out of it), where the primal
        form holds 6 KiB of gathered rows and 2.25 Grams of 64 KiB."""
        rows = 100_000
        assert als.dual_block_bytes(rows, rows, 24, 128, 2, unroll=True) == rows * 24 * 1_024
        assert (als.gathered_bytes(rows, 24, 128, 2)
                + als.normal_equation_bytes(rows, 128, unroll=True)) == rows * (6_144 + 147_456)
        # 56 slots: solved blocked, which holds a second system; the model
        # layout gathers twice the rows it solves, still under the systems
        assert als.dual_block_bytes(2 * rows, rows, 56, 128, 2, unroll=True) == rows * 56 * 1_536
        # float32 tables: the gathered rows weigh what the whitened rows do
        assert als.dual_block_bytes(rows, rows, 16, 128, 4, unroll=True) == rows * 16 * 1_024
        assert als.dual_block_bytes(2 * rows, rows, 16, 128, 4, unroll=True) == rows * 16 * 1_536

    def test_on_a_tpu_a_dual_block_goes_4096_solved_rows_a_chunk(self):
        rows = 10 * als.DUAL_CHUNK_ROWS
        for pad_len in (16, 24, 48, 64):  # unrolled or blocked [L, L] solves alike
            assert block_plan("tpu", rows, pad_len, 128, 2, implicit=True) == 10
            assert block_plan("tpu", 2 * rows, pad_len, 128, 2, 2, implicit=True) == 10
        # at rank 16 too, where the primal form's unrolled solve is not cut
        assert block_plan("tpu", rows, 8, 16, 4, implicit=True) == 10
        assert block_plan("tpu", rows, 16, 16, 4, implicit=True) == 1
        # off the TPU the bytes decide alone: 24 slots hold 24 KiB a solved row
        assert block_plan("cpu", rows, 24, 128, 2, implicit=True) == 1
        fit = als.EINSUM_GATHER_BUDGET_BYTES // (24 * 1_024 + 24 * 512)  # LAPACK: two systems
        assert block_plan("cpu", fit, 24, 128, 2, implicit=True) == 1
        assert block_plan("cpu", fit + 1, 24, 128, 2, implicit=True) == 2


#: the listed cells' packed blocks, users then songs or movies (PERF.md section 4)
MSD_R128 = [(79_360, 256), (170_304, 136), (307_392, 56), (462_336, 24),
            (69_312, 256), (29_024, 128), (76_992, 48), (209_280, 16)]
ML20M_R16 = [(35_312, 256), (22_872, 152), (28_696, 88), (51_632, 48),
             (7_648, 256), (2_224, 144), (3_840, 64), (13_048, 16)]


@pytest.mark.parametrize("blocks,config,dual", [
    (MSD_R128, ALSConfig(rank=128, implicit=True, dtype="bfloat16", factor_sharding="model"), 4),
    (ML20M_R16, ALSConfig(rank=16, dtype="bfloat16"), 0),
], ids=["als-msd-r128", "als-ml20m-r16"])
def test_what_block_paths_says_of_the_listed_cells(blocks, config, dual):
    """``als-msd-r128.train-sharded`` holds both sides of the shape test: its
    four blocks of 56, 24, 48 and 16 slots are dual, those of 256, 136 and
    128 primal. ``als-ml20m-r16.train-steady`` is explicit (and its shortest
    block is as long as its rank): the cell the traffic bypasses."""
    from types import SimpleNamespace

    sides = [SimpleNamespace(specs=[SimpleNamespace(rows=r, pad_len=l) for r, l in half])
             for half in (blocks[:4], blocks[4:])]
    d, m = (2, 2) if config.factor_sharding == "model" else (1, 1)
    paths = block_paths(SimpleNamespace(by_row=sides[0], by_col=sides[1]), config,
                        local_mesh(d, m))
    assert paths["blocks"] == 8 and paths["dual_solve"] == dual


@pytest.fixture(scope="module")
def listens():
    """120 users x 90 songs; six users with 44 songs or more and four songs
    with 50 listeners or more, the rest at most 24: at rank 48 in two buckets
    a side, the long one primal, the short one dual. Play counts up to 500
    (the primal form's own float32 error grows with the largest)."""
    rng = np.random.default_rng(29)
    n_users, n_items = 120, 90
    pairs = np.unique(np.concatenate(
        [rng.choice(n_users * n_items, size=700, replace=False)]
        + [u * n_items + rng.choice(n_items, size=44, replace=False) for u in range(6)]
        + [rng.choice(n_users, size=50, replace=False) * n_items + i for i in range(4)]))
    users, items = pairs // n_items, pairs % n_items
    counts = np.minimum(rng.zipf(2.25, size=pairs.size), 500).astype(np.float32)
    return n_users, n_items, users, items, counts


class TestInTheProgram:
    RANK = 48

    def _data(self, listens, implicit=True, **layout):
        n_users, n_items, users, items, counts = listens
        config = ALSConfig(rank=self.RANK, iterations=2, implicit=implicit, alpha=ALPHA,
                           reg=REG, buckets=2, seed=3)
        return build_als_data(users, items, counts, n_users, n_items, config, **layout), config

    def test_block_paths_counts_the_dual_blocks(self, listens):
        data, config = self._data(listens)
        lengths = [b.indices.shape[1] for s in (data.by_row, data.by_col) for b in s.blocks]
        assert sum(2 * length <= self.RANK for length in lengths) == 2 and len(lengths) == 4
        mesh = local_mesh(1, 1)
        assert block_paths(data, config, mesh) == {
            "blocks": 4, "chunked": 0, "max_chunks": 1, "blocked_solve": 0, "dual_solve": 2}
        explicit = ALSConfig(rank=self.RANK, buckets=2)
        assert block_paths(data, explicit, mesh)["dual_solve"] == 0

    def test_als_fit_logs_and_the_fit_span_carries_dual_solve(self, listens, caplog):
        from predictionio_tpu.models._als_common import _layout_attrs

        data, config = self._data(listens)
        mesh = local_mesh(1, 1)
        with caplog.at_level("INFO", logger="pio.als"):
            als_fit(data, config, mesh)
        (line,) = [r.getMessage() for r in caplog.records
                   if r.getMessage().startswith("als_fit:")]
        assert "blocks=4 blocks_chunked=0 max_chunks=1 blocked_solve=0 dual_solve=2" in line
        assert _layout_attrs(block_paths(data, config, mesh), config, mesh)["dual_solve"] == 2

    @pytest.mark.parametrize("layout", [(1, 1, "replicated"), (2, 1, "replicated"),
                                        (2, 2, "model")],
                             ids=["one_device", "data2", "data2_model2_sharded"])
    def test_a_fit_with_dual_blocks_is_the_fit_without(self, listens, monkeypatch, layout):
        """Two iterations, float32 tables, one dual and one primal bucket a
        side, against the same fit with the shape test switched off: the same
        equations, so the same factors to float32 sums in another order."""
        d, m, sharding = layout
        fits = []
        for dual in (True, False):
            if not dual:
                monkeypatch.setattr(als, "takes_dual", lambda *shape: False)
            als._build_iteration.cache_clear()
            data, config = self._data(listens, num_shards=d, model_shards=m)
            config.factor_sharding = sharding
            mesh = local_mesh(d, m)
            assert block_paths(data, config, mesh)["dual_solve"] == (2 if dual else 0)
            fits.append(als_fit(data, config, mesh))
        als._build_iteration.cache_clear()
        with_dual, without = fits
        assert _relative_error(with_dual.user_factors, without.user_factors) < 1e-3
        assert _relative_error(with_dual.item_factors, without.item_factors) < 1e-3

    @pytest.mark.parametrize("worked", ["whole", "chunked"])
    def test_an_explicit_program_knows_nothing_of_it(self, listens, monkeypatch, worked):
        """The traced explicit iteration (short blocks and all) is the same
        jaxpr with the shape test switched off, and factors no matrix."""
        if worked == "chunked":
            monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", 1 << 16)
        data, config = self._data(listens, implicit=False)
        mesh = local_mesh(1, 1)
        assert (block_paths(data, config, mesh)["chunked"] > 0) == (worked == "chunked")
        put = jnp.asarray
        args = (als.device_put_blocks(data.by_row, put), als.device_put_blocks(data.by_col, put),
                jnp.zeros((data.by_row.total_slots, self.RANK)),
                jnp.zeros((data.by_col.total_slots, self.RANK)), jnp.float32(REG), jnp.float32(ALPHA))

        def traced():
            program = als._build_iteration.__wrapped__(mesh, self.RANK, False, "replicated")
            return str(jax.make_jaxpr(program)(*args))

        as_built = traced()
        monkeypatch.setattr(als, "takes_dual", lambda *shape: False)
        assert traced() == as_built
        whitening = "name=cholesky"  # jnp.linalg's, of one matrix: not the rows' lax.linalg solve
        assert whitening not in as_built
        implicit = str(jax.make_jaxpr(
            als._build_iteration.__wrapped__(mesh, self.RANK, True, "replicated"))(*args))
        assert implicit.count(whitening) == 2  # once a half-step


class TestTheFoldIn:
    """``online.foldin.fold_in_users`` pads a touched user's history to a
    power of two (8 at least): at rank 16 and up a short history is dual."""

    @pytest.mark.parametrize("history,dual", [(6, True), (8, True), (12, False), (40, False)])
    def test_both_forms_against_float64(self, history, dual):
        from predictionio_tpu.online import foldin

        rng = np.random.default_rng(history)
        rank, n_items, n_rows = 16, 60, 5
        item_factors = (rng.normal(size=(n_items, rank)) / np.sqrt(rank)).astype(np.float32)
        rows = np.repeat(np.arange(n_rows), history)
        cols = np.concatenate([rng.choice(n_items, size=history, replace=False)
                               for _ in range(n_rows)])
        vals = np.minimum(rng.zipf(2.25, size=rows.size), 9667).astype(np.float32)
        config = ALSConfig(rank=rank, implicit=True, alpha=ALPHA, reg=REG)
        pad_len = foldin._pow2_ceil(history)
        assert takes_dual(True, pad_len, rank) == dual
        got = foldin.fold_in_users(item_factors, rows, cols, vals, n_rows, config)
        y = item_factors.astype(np.float64)
        for row in range(n_rows):
            mine = rows == row
            seen, conf = y[cols[mine]], ALPHA * vals[mine].astype(np.float64)
            gram = y.T @ y + (seen.T * conf) @ seen + REG * np.eye(rank)
            want = np.linalg.solve(gram, seen.T @ (1.0 + conf))
            assert _relative_error(got[row], want) < (1e-5 if dual else 1e-3)
