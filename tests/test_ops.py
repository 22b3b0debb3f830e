"""ops layer tests: ragged packing + batched solves."""

import numpy as np
import pytest

from predictionio_tpu.ops import linalg
from predictionio_tpu.ops.linalg import _unrolled_chol_solve, batched_spd_solve
from predictionio_tpu.ops.ragged import pack_padded_csr


class TestPackPaddedCSR:
    def test_basic_packing(self):
        rows = np.array([0, 0, 2, 2, 2])
        cols = np.array([1, 3, 0, 1, 2])
        vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0], dtype=np.float32)
        p = pack_padded_csr(rows, cols, vals, num_rows=3, num_cols=4)
        assert p.indices.shape[0] >= 3 and p.indices.shape[1] >= 3
        assert p.mask[0].sum() == 2 and p.mask[1].sum() == 0 and p.mask[2].sum() == 3
        # padding slots point at the sentinel column
        assert p.indices[1, 0] == 4
        got = sorted(zip(p.indices[2][p.mask[2] > 0], p.values[2][p.mask[2] > 0]))
        assert got == [(0, 3.0), (1, 4.0), (2, 5.0)]
        assert p.truncated == 0

    def test_truncation_keeps_most_recent(self):
        rows = np.zeros(20, dtype=int)
        cols = np.arange(20)
        vals = np.ones(20, dtype=np.float32)
        times = np.arange(20, dtype=np.float64)
        p = pack_padded_csr(rows, cols, vals, 1, 20, max_len=8, times=times)
        kept = set(p.indices[0][p.mask[0] > 0])
        assert kept == set(range(12, 20))  # most recent 8
        assert p.truncated == 12

    def test_row_multiple_alignment(self):
        p = pack_padded_csr(
            np.array([0]), np.array([0]), np.array([1.0]), 5, 3, row_multiple=8
        )
        assert p.indices.shape[0] == 8
        assert p.num_rows == 5

    def test_pad_len_forces_block_shape(self):
        """Multi-process packs force the GLOBAL padded length even when the
        local maximum is shorter -- every process must agree on shapes."""
        p = pack_padded_csr(
            np.array([0, 0]), np.array([1, 2]), np.ones(2, np.float32),
            num_rows=2, num_cols=5, pad_len=24,
        )
        assert p.indices.shape[1] == 24
        # empty local shard: same forced length
        empty = pack_padded_csr(
            np.array([]), np.array([]), np.array([], np.float32),
            num_rows=2, num_cols=5, pad_len=24,
        )
        assert empty.indices.shape[1] == 24 and empty.mask.sum() == 0
        # pad_len shorter than the longest row without truncation: loud
        with pytest.raises(ValueError, match="pad_len"):
            pack_padded_csr(
                np.zeros(9, int), np.arange(9), np.ones(9, np.float32),
                num_rows=1, num_cols=9, pad_len=8,
            )
        # ... but fine when max_len truncation was requested
        t = pack_padded_csr(
            np.zeros(9, int), np.arange(9), np.ones(9, np.float32),
            num_rows=1, num_cols=9, pad_len=8, max_len=8,
        )
        assert t.truncated == 1 and t.indices.shape[1] == 8

    def test_empty(self):
        p = pack_padded_csr(np.array([]), np.array([]), np.array([]), 4, 7)
        assert p.mask.sum() == 0
        assert (p.indices == 7).all()


class TestBatchedSolve:
    def test_solves_spd_batch(self):
        rng = np.random.default_rng(0)
        a = rng.normal(size=(6, 5, 5)).astype(np.float32)
        gram = np.einsum("bij,bkj->bik", a, a) + 0.5 * np.eye(5, dtype=np.float32)
        x_true = rng.normal(size=(6, 5)).astype(np.float32)
        rhs = np.einsum("bij,bj->bi", gram, x_true)
        x = np.asarray(batched_spd_solve(gram, rhs))
        assert np.abs(x - x_true).max() < 1e-3

    def test_singular_rows_stay_finite(self):
        gram = np.zeros((2, 4, 4), dtype=np.float32)
        rhs = np.zeros((2, 4), dtype=np.float32)
        x = np.asarray(batched_spd_solve(gram, rhs))
        assert np.isfinite(x).all()

    def test_unrolled_matches_lax_path(self):
        # the unrolled batch-major path must agree with lax cholesky+cho_solve
        # (which batched_spd_solve falls back to above _UNROLL_MAX_K)
        import jax.numpy as jnp
        from jax.lax.linalg import cholesky
        from jax.scipy.linalg import cho_solve

        rng = np.random.default_rng(1)
        for k in (3, 8, 16):
            a = rng.normal(size=(64, k, k)).astype(np.float32)
            gram = np.einsum("bij,bkj->bik", a, a) + 2.0 * np.eye(k, dtype=np.float32)
            rhs = rng.normal(size=(64, k)).astype(np.float32)
            ours = np.asarray(_unrolled_chol_solve(jnp.asarray(gram), jnp.asarray(rhs)))
            ref = np.asarray(
                cho_solve((cholesky(jnp.asarray(gram)), True), jnp.asarray(rhs)[..., None])
            )[..., 0]
            np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)

    def test_large_rank_falls_back(self):
        rng = np.random.default_rng(2)
        k = 40  # > _UNROLL_MAX_K
        a = rng.normal(size=(4, k, k)).astype(np.float32)
        gram = np.einsum("bij,bkj->bik", a, a) + 2.0 * np.eye(k, dtype=np.float32)
        x_true = rng.normal(size=(4, k)).astype(np.float32)
        rhs = np.einsum("bij,bj->bi", gram, x_true)
        x = np.asarray(batched_spd_solve(gram, rhs))
        assert np.abs(x - x_true).max() < 5e-2


def implicit_systems(k, rows=48, seed=27):
    """Implicit-shaped normal equations, ``Y'Y + sum_obs c y y' + 0.1 I`` and
    ``sum_obs (1 + c) y`` over 3 to k observations with confidences of 40 x a
    power-law play count, from a table of k rows of N(0, 1/k): condition
    numbers around 1e3. Returned in float64."""
    rng = np.random.default_rng(seed + k)
    table = rng.standard_normal((k, k)) / np.sqrt(k)
    yty = table.T @ table
    grams, rhs = [], []
    for _ in range(rows):
        seen = table[rng.integers(0, k, int(min(k, max(3, rng.zipf(1.6)))))]
        c = 40.0 * np.minimum(rng.zipf(2.25, len(seen)), 500)
        grams.append(yty + (seen.T * c) @ seen + 0.1 * np.eye(k))
        rhs.append(((1.0 + c)[:, None] * seen).sum(0))
    return np.array(grams), np.array(rhs)


def relative_error(got, want) -> float:
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))


def solve_error(solve, k):
    """``solve``'s error on ``implicit_systems(k)`` against NumPy float64,
    beside LAPACK's float32 ``cholesky`` + ``cho_solve`` on the same input."""
    import jax.numpy as jnp

    gram, rhs = implicit_systems(k)
    want = np.linalg.solve(gram, rhs[..., None])[..., 0]
    g32, r32 = jnp.asarray(gram, jnp.float32), jnp.asarray(rhs, jnp.float32)
    lapack = relative_error(batched_spd_solve(g32, r32, unroll=False), want)
    return relative_error(solve(g32, r32), want), lapack


class TestBlockedSolve:
    """The solve above rank 32 on a TPU mesh (``unroll=True``), run here on
    the CPU: the same float32 arithmetic, every matmul exact. Against NumPy
    float64 at condition 1e3 it and LAPACK's float32 Cholesky both read 5e-5
    to 4e-4, the blocked one 0.4 to 1.6 times LAPACK's (the sums are ordered
    differently); the controls read not-a-number (a pivot lost to the
    rounding falls under the guard) and 0.6."""

    @pytest.mark.parametrize("entry", ["direct", "batched_spd_solve"])
    @pytest.mark.parametrize("k", [33, 48, 64, 100, 128])
    def test_no_worse_than_lapack_in_float32(self, k, entry):
        solve = linalg._blocked_chol_solve if entry == "direct" else (
            lambda g, r: batched_spd_solve(g, r, unroll=True))
        ours, lapack = solve_error(solve, k)
        assert np.median(np.linalg.cond(implicit_systems(k)[0])) > 300
        assert ours < 2.5 * lapack and ours < 1e-3, (ours, lapack)

    @pytest.mark.parametrize("fault", ["one_bf16_pass", "a_trailing_update_dropped"])
    def test_a_faulty_blocked_solve_fails_that_assertion(self, monkeypatch, fault):
        """Controls: the einsums at the MXU's default precision (operands
        rounded to bfloat16, one pass), and the last ``panel' panel`` never
        taken off the trailing matrix."""
        import jax.numpy as jnp

        sound = linalg._matmul

        def faulty(spec, *operands):
            if fault == "one_bf16_pass":
                operands = [x.astype(jnp.bfloat16).astype(jnp.float32) for x in operands]
            out = sound(spec, *operands)
            last = spec == "rci,rcj->rij" and out.shape[-1] == linalg._UNROLL_MAX_K
            return 0.0 * out if fault == "a_trailing_update_dropped" and last else out

        monkeypatch.setattr(linalg, "_matmul", faulty)
        ours, lapack = solve_error(linalg._blocked_chol_solve, 128)
        assert not (ours < 2.5 * lapack and ours < 1e-3), (ours, lapack)

    def test_all_zero_systems_stay_finite_and_zero(self):
        gram = np.zeros((3, 128, 128), dtype=np.float32)
        rhs = np.zeros((3, 128), dtype=np.float32)
        x = np.asarray(batched_spd_solve(gram, rhs, unroll=True))
        assert np.isfinite(x).all() and np.abs(x).max() < 1e-6

    @pytest.mark.parametrize("k,unroll,path", [
        (32, True, "unrolled"), (33, True, "blocked"), (40, False, "cholesky"),
        (16, False, "cholesky"),
    ])
    def test_the_path_follows_rank_and_platform(self, monkeypatch, k, unroll, path):
        """Rank 32 still takes ``_unrolled_chol_solve``, rank 40 off the TPU
        still LAPACK: each path's routine is the only one called."""
        import jax.numpy as jnp

        called = []
        for name, routine in (("unrolled", "_unrolled_chol_solve"),
                              ("blocked", "_blocked_chol_solve"), ("cholesky", "cholesky")):
            real = getattr(linalg, routine)
            monkeypatch.setattr(
                linalg, routine,
                lambda *a, _name=name, _real=real: (called.append(_name), _real(*a))[1])
        gram, rhs = (jnp.asarray(a, jnp.float32) for a in implicit_systems(k, rows=4))
        batched_spd_solve(gram, rhs, unroll=unroll)
        assert called == [path] and linalg.solve_path(k, unroll) == path
