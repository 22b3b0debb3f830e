"""Sharded ALS tests on the virtual 8-device CPU mesh (SURVEY.md section 4:
the local[*] analogue)."""

import numpy as np
import pytest

from predictionio_tpu.parallel.als import ALSConfig, als_fit, build_als_data
from predictionio_tpu.parallel.mesh import local_mesh


@pytest.fixture(scope="module")
def synthetic():
    rng = np.random.default_rng(42)
    n_u, n_i, k = 150, 90, 6
    U = rng.normal(size=(n_u, k)) / np.sqrt(k)
    V = rng.normal(size=(n_i, k)) / np.sqrt(k)
    mask = rng.random((n_u, n_i)) < 0.25
    uu, ii = np.nonzero(mask)
    rr = (np.sum(U[uu] * V[ii], axis=1) + 0.01 * rng.normal(size=len(uu))).astype(
        np.float32
    )
    return n_u, n_i, uu, ii, rr, mask


class TestExplicitALS:
    def test_converges_single_device(self, synthetic):
        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg = ALSConfig(rank=6, iterations=10, reg=0.01, seed=1)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        model = als_fit(data, cfg, local_mesh(1, 1))
        pred = np.sum(model.user_factors[uu] * model.item_factors[ii], axis=1)
        assert np.sqrt(np.mean((pred - rr) ** 2)) < 0.05

    def test_sharded_matches_single_device(self, synthetic):
        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg = ALSConfig(rank=6, iterations=5, reg=0.01, seed=1)
        data1 = build_als_data(uu, ii, rr, n_u, n_i, cfg, num_shards=1)
        data8 = build_als_data(uu, ii, rr, n_u, n_i, cfg, num_shards=8)
        m1 = als_fit(data1, cfg, local_mesh(1, 1))
        m8 = als_fit(data8, cfg, local_mesh(8, 1))
        # same math, same seed: factors must agree across shardings
        r1 = m1.user_factors[uu[:50]] @ m1.item_factors[ii[:50]].T
        r8 = m8.user_factors[uu[:50]] @ m8.item_factors[ii[:50]].T
        np.testing.assert_allclose(r1, r8, atol=2e-2)

    def test_bfloat16_factor_mode(self, synthetic):
        """ALX-style mixed precision: bf16 factor storage on device, f32
        Grams/solve. Quality must track the f32 run, the on-device factors
        must actually STAY bf16 across iterations (a promotion anywhere in
        the step would silently upcast after iteration 1), and the serving
        model must come back f32."""
        import jax.numpy as jnp

        from predictionio_tpu.parallel.als import _half_step_explicit

        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg16 = ALSConfig(rank=6, iterations=10, reg=0.01, seed=1, dtype="bfloat16")
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg16)
        model = als_fit(data, cfg16, local_mesh(1, 1))
        assert model.user_factors.dtype == np.float32  # host model is f32
        pred = np.sum(model.user_factors[uu] * model.item_factors[ii], axis=1)
        assert np.sqrt(np.mean((pred - rr) ** 2)) < 0.08  # tracks f32 (<0.05)

        # the step's output dtype == its input factor dtype (no promotion)
        factors16 = jnp.zeros((data.by_col.total_slots + 1, 6), jnp.bfloat16)
        out = _half_step_explicit(
            jnp.asarray(data.by_row.indices),
            jnp.asarray(data.by_row.values),
            jnp.asarray(data.by_row.mask.sum(axis=1)),
            factors16,
            reg=0.01,
            rank=6,
            unroll=False,
        )
        assert out.dtype == jnp.bfloat16

    def test_grid_candidates_share_one_compiled_program(self):
        """reg/alpha are runtime scalars: a pio-eval grid over lambda must
        reuse ONE compiled iteration per (mesh, rank, mode), not compile
        per candidate (minutes each on a remote-compile TPU backend)."""
        from predictionio_tpu.parallel.als import make_iteration
        from predictionio_tpu.parallel.mesh import local_mesh

        mesh = local_mesh(1, 1)
        a = make_iteration(mesh, ALSConfig(rank=6, reg=0.01))
        b = make_iteration(mesh, ALSConfig(rank=6, reg=0.5, alpha=2.0))
        assert a is b
        assert a is not make_iteration(mesh, ALSConfig(rank=8, reg=0.01))

    def test_reg_still_regularizes(self, synthetic):
        """The traced-scalar reg must actually flow into the solve: a huge
        lambda shrinks the factors toward zero."""
        n_u, n_i, uu, ii, rr, _ = synthetic
        small = ALSConfig(rank=6, iterations=4, reg=0.01, seed=1)
        large = ALSConfig(rank=6, iterations=4, reg=1000.0, seed=1)
        data = build_als_data(uu, ii, rr, n_u, n_i, small)
        m_small = als_fit(data, small, local_mesh(1, 1))
        m_large = als_fit(data, large, local_mesh(1, 1))
        assert (
            np.abs(m_large.user_factors).mean()
            < 0.1 * np.abs(m_small.user_factors).mean()
        )

    def test_invalid_factor_dtype_rejected(self, synthetic):
        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg = ALSConfig(rank=6, iterations=1, dtype="int8")
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        with pytest.raises(ValueError, match="float32.*bfloat16"):
            als_fit(data, cfg, local_mesh(1, 1))

    def test_model_scoring_helpers(self, synthetic):
        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg = ALSConfig(rank=6, iterations=3, reg=0.05)
        model = als_fit(build_als_data(uu, ii, rr, n_u, n_i, cfg), cfg)
        assert model.score_items_for_user(0).shape == (n_i,)
        sims = model.similar_items(3)
        assert sims.shape == (n_i,)
        assert sims[3] == pytest.approx(1.0, abs=1e-5)


class TestBucketedPacking:
    """Length-bucketed padded-CSR layout (the ALX-style padding-slot cut)."""

    def _skewed(self, seed=7, n_u=300, n_i=60):
        # zipf-ish history lengths: a few heavy rows, a long light tail --
        # the distribution bucketing exists for
        rng = np.random.default_rng(seed)
        lengths = np.minimum((rng.pareto(1.2, n_u) * 4 + 1).astype(int), n_i)
        uu = np.repeat(np.arange(n_u), lengths)
        ii = np.concatenate([
            rng.choice(n_i, size=l, replace=False) for l in lengths
        ])
        rr = rng.random(uu.size).astype(np.float32) * 4 + 1
        return n_u, n_i, uu.astype(np.int64), ii.astype(np.int64), rr

    def test_bucketing_reduces_padded_slots(self):
        n_u, n_i, uu, ii, rr = self._skewed()
        flat = build_als_data(uu, ii, rr, n_u, n_i, ALSConfig(buckets=1))
        bucketed = build_als_data(uu, ii, rr, n_u, n_i, ALSConfig(buckets=4))
        assert len(bucketed.by_row.blocks) > 1
        assert bucketed.by_row.padded_slots < 0.7 * flat.by_row.padded_slots
        # no interactions lost to the layout change
        assert (
            sum(b.mask.sum() for b in bucketed.by_row.blocks)
            == flat.by_row.mask.sum()
        )

    def test_slot_map_roundtrip(self):
        n_u, n_i, uu, ii, rr = self._skewed()
        data = build_als_data(uu, ii, rr, n_u, n_i, ALSConfig(buckets=3))
        side = data.by_row
        # slots are unique, in-range, and every real row has one
        assert side.slot_of.shape == (n_u,)
        assert len(np.unique(side.slot_of)) == n_u
        assert side.slot_of.max() < side.total_slots
        assert side.total_slots == sum(
            b.indices.shape[0] for b in side.blocks
        )

    def test_bucketed_matches_flat_fixed_seed(self):
        """The quality gate: same seed, same data -- the bucketed layout
        must reproduce the single-block factors (the math is identical;
        only fp reduction order differs)."""
        n_u, n_i, uu, ii, rr = self._skewed()
        cfg1 = ALSConfig(rank=6, iterations=6, reg=0.05, seed=3, buckets=1)
        cfg4 = ALSConfig(rank=6, iterations=6, reg=0.05, seed=3, buckets=4)
        m1 = als_fit(build_als_data(uu, ii, rr, n_u, n_i, cfg1), cfg1)
        m4 = als_fit(build_als_data(uu, ii, rr, n_u, n_i, cfg4), cfg4)
        pred1 = np.sum(m1.user_factors[uu] * m1.item_factors[ii], axis=1)
        pred4 = np.sum(m4.user_factors[uu] * m4.item_factors[ii], axis=1)
        rmse_delta = np.sqrt(np.mean((pred1 - pred4) ** 2))
        assert rmse_delta < 1e-3, rmse_delta
        np.testing.assert_allclose(
            m1.user_factors, m4.user_factors, atol=5e-3
        )

    def test_bucketed_sharded_runs(self):
        """Bucketed blocks each shard over the data axis; the concatenated
        factor matrix re-shards cleanly on an 8-device mesh."""
        n_u, n_i, uu, ii, rr = self._skewed()
        cfg = ALSConfig(rank=6, iterations=3, reg=0.05, seed=3, buckets=3)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg, num_shards=8)
        for b in data.by_row.blocks:
            assert b.indices.shape[0] % 64 == 0  # 8 shards x 8 lanes
        m8 = als_fit(data, cfg, local_mesh(8, 1))
        cfg1 = ALSConfig(rank=6, iterations=3, reg=0.05, seed=3, buckets=1)
        m1 = als_fit(build_als_data(uu, ii, rr, n_u, n_i, cfg1), cfg1)
        np.testing.assert_allclose(
            m1.user_factors, m8.user_factors, atol=5e-3
        )

    def test_bucketed_truncation_keeps_most_recent(self):
        """max_len truncation semantics survive bucketing: the kept entries
        per row match the single-block layout (most recent by time)."""
        n_u, n_i = 40, 30
        rng = np.random.default_rng(0)
        uu = np.repeat(np.arange(n_u), 20)
        ii = np.tile(np.arange(20), n_u).astype(np.int64)
        rr = rng.random(uu.size).astype(np.float32)
        tt = rng.permutation(uu.size).astype(np.float64)
        cfg1 = ALSConfig(max_len=8, buckets=1)
        cfg3 = ALSConfig(max_len=8, buckets=3)
        d1 = build_als_data(uu, ii, rr, n_u, n_i, cfg1, times=tt)
        d3 = build_als_data(uu, ii, rr, n_u, n_i, cfg3, times=tt)
        assert d1.by_row.truncated == d3.by_row.truncated > 0

        def kept(data):
            out = {}
            for off, block in zip(
                np.cumsum([0] + [b.indices.shape[0] for b in data.by_row.blocks])[:-1],
                data.by_row.blocks,
            ):
                for r in range(block.indices.shape[0]):
                    slot = off + r
                    real = block.mask[r] > 0
                    orig = np.nonzero(data.by_row.slot_of == slot)[0]
                    if orig.size:
                        out[int(orig[0])] = set(
                            zip(block.indices[r][real].tolist(),
                                block.values[r][real].tolist())
                        )
            return out

        k1, k3 = kept(d1), kept(d3)

        # compare via original item ids: map column slots back through
        # by_col's slot map (padding holes stay -1 and must never appear)
        def inverse(side):
            inv = np.full(side.total_slots, -1, dtype=np.int64)
            inv[side.slot_of] = np.arange(side.num_rows)
            return inv

        inv1 = inverse(d1.by_col)
        inv3 = inverse(d3.by_col)

        def unmap(kept_map, slot_to_orig):
            return {
                u: {(int(slot_to_orig[c]), v) for c, v in entries}
                for u, entries in kept_map.items()
            }

        assert unmap(k1, inv1) == unmap(k3, inv3)


class TestModelShardedFactors:
    """ALX block model-parallelism: factors sharded over the model axis."""

    def _fit_pair(self, synthetic, implicit: bool):
        n_u, n_i, uu, ii, rr, _ = synthetic
        vals = np.ones(len(uu), np.float32) if implicit else rr
        kw = dict(rank=6, iterations=5, reg=0.01, seed=1, implicit=implicit,
                  alpha=10.0)
        cfg_rep = ALSConfig(**kw)
        cfg_mdl = ALSConfig(**kw, factor_sharding="model", buckets=2)
        m_rep = als_fit(
            build_als_data(uu, ii, vals, n_u, n_i, cfg_rep), cfg_rep,
            local_mesh(1, 1),
        )
        data = build_als_data(
            uu, ii, vals, n_u, n_i, cfg_mdl, num_shards=4, model_shards=2
        )
        m_mdl = als_fit(data, cfg_mdl, local_mesh(4, 2))
        return m_rep, m_mdl

    def test_matches_replicated_explicit(self, synthetic):
        m_rep, m_mdl = self._fit_pair(synthetic, implicit=False)
        np.testing.assert_allclose(
            m_rep.user_factors, m_mdl.user_factors, atol=5e-3
        )
        np.testing.assert_allclose(
            m_rep.item_factors, m_mdl.item_factors, atol=5e-3
        )

    def test_matches_replicated_implicit(self, synthetic):
        m_rep, m_mdl = self._fit_pair(synthetic, implicit=True)
        np.testing.assert_allclose(
            m_rep.user_factors, m_mdl.user_factors, atol=5e-3
        )

    def test_unaligned_blocks_rejected(self, synthetic):
        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg = ALSConfig(rank=6, factor_sharding="model")
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)  # no model_shards
        # 2x3 mesh: the default 8-row padding does not divide d*m = 6
        with pytest.raises(ValueError, match="model_shards"):
            als_fit(data, cfg, local_mesh(2, 3))

    def test_bad_mode_rejected(self, synthetic):
        n_u, n_i, uu, ii, rr, _ = synthetic
        cfg = ALSConfig(rank=6, factor_sharding="sideways")
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg)
        with pytest.raises(ValueError, match="factor_sharding"):
            als_fit(data, cfg, local_mesh(1, 1))


class TestImplicitALS:
    def test_ranks_observed_above_unobserved(self, synthetic):
        n_u, n_i, uu, ii, _, mask = synthetic
        cfg = ALSConfig(rank=6, iterations=8, reg=0.01, implicit=True, alpha=10.0)
        data = build_als_data(uu, ii, np.ones(len(uu), np.float32), n_u, n_i, cfg,
                              num_shards=4)
        model = als_fit(data, cfg, local_mesh(4, 1))
        scores = model.user_factors @ model.item_factors.T
        # direction of separation is the contract; the margin depends on the
        # synthetic's density (25% random mask leaves unobserved pairs weakly
        # structured)
        assert scores[uu, ii].mean() > scores[~mask].mean() + 0.1


class TestDeviceScopes:
    """``jax.named_scope`` inside the iteration: names only, same program,
    whether a block is worked whole or in row chunks."""

    @staticmethod
    def _program_and_args(synthetic, worked, sharding, implicit):
        import jax.numpy as jnp

        from predictionio_tpu.parallel import als

        n_u, n_i, uu, ii, rr, _ = synthetic
        model = 2 if sharding == "model" else 1
        mesh = local_mesh(2, model)
        cfg = ALSConfig(rank=6, buckets=2, implicit=implicit,
                        factor_sharding=sharding)
        data = build_als_data(uu, ii, rr, n_u, n_i, cfg, num_shards=2,
                              model_shards=model)
        paths = als.block_paths(data, cfg, mesh)
        assert paths["chunked"] == (paths["blocks"] if worked == "chunked" else 0)
        blocks = [
            tuple((jnp.asarray(b.indices), jnp.asarray(b.values),
                   jnp.asarray(b.mask.sum(axis=1))) for b in side.blocks)
            for side in (data.by_row, data.by_col)
        ]
        factors = [
            jnp.asarray(als._initial_side_factors(side, 6, seed), jnp.float32)
            for side, seed in ((data.by_row, 1), (data.by_col, 2))
        ]
        # past the per-mesh cache: the rule is asked as a program is traced
        program = als._build_iteration.__wrapped__(mesh, 6, implicit, sharding)
        return program, (*blocks, *factors, jnp.float32(0.05), jnp.float32(2.0))

    @pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
    @pytest.mark.parametrize("sharding", ["replicated", "model"])
    def test_every_scope_is_in_the_traced_program(self, synthetic, worked,
                                                   sharding, implicit):
        from predictionio_tpu.parallel import als

        program, args = self._program_and_args(synthetic, worked, sharding, implicit)
        stacks = set(self._name_stacks(program.trace(*args).jaxpr.jaxpr))
        assert len(args[0]) == 2  # the user side keeps both its buckets
        def under(scope):  # the scope itself, or one nested in it
            return [s for s in stacks if s == scope or s.startswith(scope + "/")]

        for side, blocks in zip(als.SCOPE_HALF_STEP.values(), args):
            assemble = f"{side}/{als.SCOPE_ASSEMBLE}"
            assert under(assemble)
            # what is nested where the work is: YtY (implicit only), and what
            # crosses the chips in the model layout
            assert bool(under(f"{assemble}/{als.SCOPE_YTY}")) == implicit
            assert bool(under(f"{assemble}/{als.SCOPE_EXCHANGE}")) == (sharding == "model")
            for bucket in range(len(blocks)):
                for stage in (als.SCOPE_GRAM, als.SCOPE_SOLVE):
                    # (everything under ``gram`` lies in one of its leaves)
                    assert under(f"{side}/{als.SCOPE_BUCKET.format(bucket)}/{stage}")
                gram = f"{side}/{als.SCOPE_BUCKET.format(bucket)}/{als.SCOPE_GRAM}"
                assert bool(under(f"{gram}/{als.SCOPE_EXCHANGE}")) == (sharding == "model")
        # and nothing of the iteration lies outside them
        assert all(stack.startswith("als.") for stack in stacks), sorted(stacks)[:5]

    @classmethod
    def _name_stacks(cls, jaxpr, outer=""):
        """Every equation's scope, those of nested programs (a shard_map's
        body, a jitted helper) under their caller's."""
        for eqn in jaxpr.eqns:
            here = "/".join(filter(None, [outer, str(eqn.source_info.name_stack)]))
            yield here
            for param in eqn.params.values():
                inner = getattr(param, "jaxpr", param)
                if hasattr(inner, "eqns"):
                    yield from cls._name_stacks(inner, here)

    @pytest.mark.parametrize("sharding", ["replicated", "model"])
    def test_factors_equal_the_unscoped_programs_bit_for_bit(
            self, synthetic, monkeypatch, worked, sharding):
        """The same builder with every ``named_scope`` taken out is the
        program this one replaced."""
        import contextlib

        import jax

        from predictionio_tpu.parallel import als

        scoped, args = self._program_and_args(synthetic, worked, sharding, False)
        scopes = lambda f: set(self._name_stacks(f.trace(*args).jaxpr.jaxpr))
        copy = lambda tree: jax.tree_util.tree_map(lambda a: a + 0, tree)  # donated
        with monkeypatch.context() as patch:  # traced and run with the scopes out
            patch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
            bare, _ = self._program_and_args(synthetic, worked, sharding, False)
            assert not any("als." in stack for stack in scopes(bare))
            want = bare(*copy(args))
        assert all(stack.startswith("als.") for stack in scopes(scoped))
        for got, unscoped in zip(scoped(*copy(args)), want):
            assert np.array_equal(np.asarray(got), np.asarray(unscoped))
