"""Neural-CF template tests: sharded training, Pallas kernel correctness
(interpret mode), checkpoint/resume."""

import numpy as np
import pytest

from predictionio_tpu.models.ncf.kernel import (
    ncf_score_all_items,
    reference_score_all_items,
)
from predictionio_tpu.models.ncf.model import (
    NCFConfig,
    NeuMF,
    make_implicit_batches,
    train_ncf,
)
from predictionio_tpu.parallel.mesh import local_mesh


@pytest.fixture(scope="module")
def tiny_params():
    import jax
    import jax.numpy as jnp

    config = NCFConfig(num_users=10, num_items=1500, embed_dim=8, hidden=(16, 8))
    model = NeuMF(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
    )["params"]
    return config, params


class TestPallasKernel:
    def test_matches_reference_including_ragged_tail(self, tiny_params):
        config, params = tiny_params
        # 1500 items: >1 grid step at TILE_I=1024 (a wrong tile index
        # map would score the tail with tile-0 embeddings) AND a ragged
        # padded tail (1500 -> 2048)
        got = ncf_score_all_items(params, 3, config.num_items, interpret=True)
        want = reference_score_all_items(params, 3, config.num_items)
        assert got.shape == (1500,)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_flax_apply_agrees_with_reference_head(self, tiny_params):
        import jax.numpy as jnp

        config, params = tiny_params
        model = NeuMF(config)
        items = np.arange(20, dtype=np.int32)
        users = np.full(20, 3, dtype=np.int32)
        via_model = np.asarray(model.apply({"params": params}, jnp.asarray(users), jnp.asarray(items)))
        via_ref = reference_score_all_items(params, 3, config.num_items)[:20]
        np.testing.assert_allclose(via_model, via_ref, rtol=1e-4, atol=1e-5)


class TestServingRefusesToDegrade:
    def _model(self, tiny_params, **kw):
        from predictionio_tpu.models.ncf.engine import NCFModel

        config, params = tiny_params
        return NCFModel(
            params=params,
            user_index={"u0": 0},
            item_ids=[f"i{j}" for j in range(config.num_items)],
            item_index={f"i{j}": j for j in range(config.num_items)},
            seen={},
            **kw,
        )

    def test_kernel_build_failure_raises_at_warm_up(self, tiny_params, monkeypatch):
        """With usePallas the scorer is the kernel or the deploy fails:
        a kernel the device refuses must surface at warm_up, not be
        swapped for the XLA reference for the life of the process."""
        from predictionio_tpu.models.ncf import engine as ncf_engine

        def refuse(*args, **kwargs):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(ncf_engine, "make_all_items_scorer", refuse)
        model = self._model(tiny_params, use_pallas=True)
        algo = ncf_engine.NCFAlgorithm({})
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            algo.warm_up(model)
        assert model._scorer is None  # nothing was installed in its place

    def test_warm_up_compiles_the_kernel_it_will_serve(self, tiny_params):
        """On the CPU test backend the same kernel code runs interpreted
        (the platform decides, as for every kernel here) and warm_up has
        already called it once; the registry names which way it ran."""
        from predictionio_tpu.models.ncf import engine as ncf_engine
        from predictionio_tpu.utils.platform import device_report

        config, params = tiny_params
        model = self._model(tiny_params, use_pallas=True)
        ncf_engine.NCFAlgorithm({}).warm_up(model)
        got = np.asarray(model.scorer()(3))
        want = reference_score_all_items(params, 3, config.num_items)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert device_report()["kernels"]["ncf_score_all_items"] == "interpreted"


class TestTraining:
    def _clique_data(self, n_users=32, n_items=16):
        rng = np.random.default_rng(0)
        users, items, labels = [], [], []
        for u in range(n_users):
            clique = u % 2
            for i in range(n_items):
                if rng.random() < 0.6:
                    users.append(u)
                    items.append(i)
                    in_clique = (i < n_items // 2) == (clique == 0)
                    labels.append(5.0 if in_clique else 1.0)
        return (
            np.array(users, np.int32),
            np.array(items, np.int32),
            np.array(labels, np.float32),
        )

    def test_sharded_training_learns_structure(self):
        users, items, labels = self._clique_data()
        config = NCFConfig(
            num_users=32, num_items=16, embed_dim=8, hidden=(16, 8),
            epochs=30, batch_size=64, learning_rate=0.02,
        )
        mesh = local_mesh(4, 2)  # dp=4 x tp=2: the full 8-device mesh
        params, _ = train_ncf(config, users, items, labels, mesh)
        scores_u0 = reference_score_all_items(params, 0, 16)  # clique 0
        assert scores_u0[:8].mean() > scores_u0[8:].mean() + 1.0
        scores_u1 = reference_score_all_items(params, 1, 16)  # clique 1
        assert scores_u1[8:].mean() > scores_u1[:8].mean() + 1.0

    def test_implicit_negative_sampling(self):
        users = np.array([0, 0, 1], np.int64)
        items = np.array([1, 2, 0], np.int64)
        u, i, y = make_implicit_batches(
            users, items, num_items=10, negatives=3, rng=np.random.default_rng(0)
        )
        assert set(zip(u[:3].tolist(), i[:3].tolist())) == {(0, 1), (0, 2), (1, 0)}
        assert (y[:3] == 1).all() and (y[3:] == 0).all()
        # sampled negatives never collide with positives
        pos = set(zip(users.tolist(), items.tolist()))
        assert all((uu, ii) not in pos for uu, ii in zip(u[3:], i[3:]))

    def test_checkpoint_resume(self, tmp_path):
        from predictionio_tpu.workflow.checkpoint import CheckpointManager

        users, items, labels = self._clique_data()
        config = NCFConfig(
            num_users=32, num_items=16, embed_dim=8, hidden=(16, 8),
            epochs=3, batch_size=64,
        )
        mesh = local_mesh(1, 1)
        ckpt = CheckpointManager("run1", base_dir=str(tmp_path))
        train_ncf(config, users, items, labels, mesh, checkpoint=ckpt)
        assert ckpt.latest_step() == 2
        ckpt.close()
        # resume: a fresh manager continues from epoch 3
        ckpt2 = CheckpointManager("run1", base_dir=str(tmp_path))
        config.epochs = 5
        train_ncf(config, users, items, labels, mesh, checkpoint=ckpt2)
        assert ckpt2.latest_step() == 4
        ckpt2.close()


class TestNCFEngine:
    def test_template_end_to_end(self, storage_env):
        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.models.ncf import engine_factory
        from predictionio_tpu.workflow.context import RuntimeContext

        app_id = storage_env.get_meta_data_apps().insert(App(name="NcfApp"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        rng = np.random.default_rng(5)
        events = []
        for u in range(24):
            clique = u % 2
            for i in range(16):
                if rng.random() < 0.6:
                    in_clique = (i < 8) == (clique == 0)
                    events.append(
                        Event(event="rate", entity_type="user", entity_id=f"u{u}",
                              target_entity_type="item", target_entity_id=f"i{i}",
                              properties=DataMap({"rating": 5.0 if in_clique else 1.0}))
                    )
        le.batch_insert(events, app_id=app_id)
        ep = EngineParams.from_json_obj(
            {"datasource": {"params": {"appName": "NcfApp"}},
             "algorithms": [{"name": "ncf", "params": {
                 "embedDim": 8, "hidden": [16, 8], "epochs": 30,
                 "batchSize": 64, "learningRate": 0.02}}]}
        )
        engine = engine_factory()
        models = engine.train(RuntimeContext({"pio.mesh_shape": [2, 1]}), ep)
        a = engine._algorithms(ep)[0]
        # unseenOnly=False: u0 has rated most in-clique items, so the unseen
        # pool alone can't fill top-3 from the clique
        out = a.predict(models[0], {"user": "u0", "num": 3, "unseenOnly": False})
        items = [int(s["item"][1:]) for s in out["itemScores"]]
        assert items and all(i < 8 for i in items), items
        # unseenOnly filters the rated ones out
        rated = {int(s[1:]) for u, s in zip(
            *(lambda evs: ([e.entity_id for e in evs], [e.target_entity_id for e in evs]))(
                list(storage_env.get_l_events().find(app_id, entity_id="u0"))
            )
        )}
        unseen = a.predict(models[0], {"user": "u0", "num": 16})
        assert not ({int(s["item"][1:]) for s in unseen["itemScores"]} & rated)
        assert a.predict(models[0], {"user": "ghost"}) == {"itemScores": []}

    def test_batch_predict_matches_predict(self, storage_env):
        """batch_predict (chunked device scoring) must return exactly what
        per-query predict returns, including exclusions, cold users, and a
        malformed query falling through to predict()'s error path."""
        import pytest

        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.models.ncf import engine_factory
        from predictionio_tpu.workflow.context import RuntimeContext

        app_id = storage_env.get_meta_data_apps().insert(App(name="NcfBatch"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        rng = np.random.default_rng(2)
        events = [
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap({"rating": float(rng.integers(1, 6))}))
            for u in range(12) for i in rng.choice(10, 4, replace=False)
        ]
        le.batch_insert(events, app_id=app_id)
        ep = EngineParams.from_json_obj(
            {"datasource": {"params": {"appName": "NcfBatch"}},
             "algorithms": [{"name": "ncf", "params": {
                 "embedDim": 4, "hidden": [8, 4], "epochs": 3,
                 "batchSize": 16}}]}
        )
        engine = engine_factory()
        models = engine.train(RuntimeContext(), ep)
        a = engine._algorithms(ep)[0]
        queries = [
            (0, {"user": "u0", "num": 3}),
            (1, {"user": "u1", "num": 5, "unseenOnly": False}),
            (2, {"user": "ghost", "num": 3}),                  # cold -> []
            (3, {"user": "u2", "num": 4, "blackList": ["i0", "i1"]}),
        ]
        batched = dict(a.batch_predict(models[0], queries))
        for qid, q in queries:
            single = a.predict(models[0], q)
            # same items in the same order; scores equal up to the float
            # accumulation-order difference between the batched [U, I]
            # forward and the single-user path
            assert [s["item"] for s in batched[qid]["itemScores"]] == [
                s["item"] for s in single["itemScores"]
            ], (qid, batched[qid], single)
            np.testing.assert_allclose(
                [s["score"] for s in batched[qid]["itemScores"]],
                [s["score"] for s in single["itemScores"]],
                rtol=1e-4,
            )
        assert batched[2] == {"itemScores": []}
        black = {s["item"] for s in batched[3]["itemScores"]}
        assert black.isdisjoint({"i0", "i1"})

    def test_deploy_warms_the_scorer(self, storage_env):
        """prepare_deploy must build the serving scorer eagerly (warm_up)
        so the first query after a deploy doesn't pay table upload +
        compile; the pickled blob itself must never carry it."""
        import pickle

        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.models.ncf import engine_factory
        from predictionio_tpu.workflow.context import RuntimeContext

        app_id = storage_env.get_meta_data_apps().insert(App(name="NcfWarm"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        rng = np.random.default_rng(1)
        le.batch_insert(
            [
                Event(event="rate", entity_type="user", entity_id=f"u{u}",
                      target_entity_type="item", target_entity_id=f"i{i}",
                      properties=DataMap({"rating": float(rng.integers(1, 6))}))
                for u in range(8) for i in rng.choice(6, 3, replace=False)
            ],
            app_id=app_id,
        )
        ep = EngineParams.from_json_obj(
            {"datasource": {"params": {"appName": "NcfWarm"}},
             "algorithms": [{"name": "ncf", "params": {
                 "embedDim": 4, "hidden": [8, 4], "epochs": 2, "batchSize": 8}}]}
        )
        engine = engine_factory()
        ctx = RuntimeContext()
        models = engine.train(ctx, ep)
        blob = engine.serialize_models(ctx, ep, "iid", models)
        deployed = engine.prepare_deploy(ctx, ep, "iid", blob)
        assert deployed[0]._scorer is not None        # warmed at deploy
        assert deployed[0]._batch_scorer is not None  # batchpredict path too
        # and the blob round-trip stripped it (no device buffers pickled)
        assert pickle.loads(pickle.dumps(models[0]))._scorer is None


class TestLiveSeenFilter:
    def test_live_filter_agrees_and_sees_fresh_events(self, storage_env):
        """seenFilter "live": the NCF model carries no O(edges) seen map;
        unseenOnly resolves per query from the store, so a fresh rating
        filters with no retrain."""
        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.models.ncf import engine_factory
        from predictionio_tpu.workflow.context import RuntimeContext

        app_id = storage_env.get_meta_data_apps().insert(App(name="NcfLive"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        rng = np.random.default_rng(5)
        le.batch_insert(
            [
                Event(event="rate", entity_type="user", entity_id=f"u{u}",
                      target_entity_type="item", target_entity_id=f"i{i}",
                      properties=DataMap({"rating": float(rng.integers(1, 6))}))
                for u in range(12) for i in range(10) if rng.random() < 0.5
            ],
            app_id=app_id,
        )
        ep = EngineParams.from_json_obj(
            {"datasource": {"params": {"appName": "NcfLive"}},
             "algorithms": [{"name": "ncf", "params": {
                 "embedDim": 4, "hidden": [8, 4], "epochs": 2,
                 "batchSize": 16, "seenFilter": "live"}}]}
        )
        engine = engine_factory()
        model = engine.train(RuntimeContext(), ep)[0]
        assert model.seen == {} and model.seen_mode == "live"
        a = engine._algorithms(ep)[0]
        out = a.predict(model, {"user": "u0", "num": 10})
        served = {s["item"] for s in out["itemScores"]}
        rated = {e.target_entity_id
                 for e in le.find(app_id=app_id, entity_id="u0")}
        assert not (served & rated)
        # fresh event filters immediately
        fresh = next(i for i in served)
        le.insert(
            Event(event="rate", entity_type="user", entity_id="u0",
                  target_entity_type="item", target_entity_id=fresh,
                  properties=DataMap({"rating": 5.0})),
            app_id=app_id,
        )
        after = a.predict(model, {"user": "u0", "num": 10})
        assert fresh not in {s["item"] for s in after["itemScores"]}
