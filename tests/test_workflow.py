"""DASE engine + workflow lifecycle tests (reference EngineTest /
JsonExtractorSuite / EvaluationWorkflowSuite scope, SURVEY.md section 4)."""

import json
import os

import pytest
import requests

from predictionio_tpu.controller import Engine, EngineParams
from predictionio_tpu.controller.metrics import (
    EngineParamsGenerator,
    Evaluation,
    OptionAverageMetric,
)
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage.base import STATUS_COMPLETED, STATUS_FAILED, App
from predictionio_tpu.workflow.context import RuntimeContext
from predictionio_tpu.workflow.core_workflow import run_evaluation, run_train
from predictionio_tpu.workflow.json_extractor import (
    EngineConfigError,
    load_engine_variant,
)

from fake_engine import engine_factory

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def rated_app(storage_env):
    apps = storage_env.get_meta_data_apps()
    app_id = apps.insert(App(name="RateApp"))
    le = storage_env.get_l_events()
    le.init_channel(app_id)
    ratings = [("u1", "i1", 4.0), ("u1", "i2", 2.0), ("u2", "i1", 5.0), ("u2", "i3", 1.0)]
    le.batch_insert(
        [
            Event(event="rate", entity_type="user", entity_id=u,
                  target_entity_type="item", target_entity_id=i,
                  properties=DataMap({"rating": r}))
            for u, i, r in ratings
        ],
        app_id=app_id,
    )
    return app_id


def write_variant(tmp_path, algorithms, factory="fake_engine.engine_factory"):
    import os, sys

    tests_dir = os.path.dirname(os.path.abspath(__file__))
    variant = {
        "id": "default",
        "engineFactory": factory,
        "datasource": {"params": {"appName": "RateApp"}},
        "algorithms": algorithms,
        "sparkConf": {"pio.mesh_shape": [1, 1]},
    }
    path = tmp_path / "engine.json"
    path.write_text(json.dumps(variant))
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    return load_engine_variant(str(path))


class TestJsonExtractor:
    def test_parses_full_shape(self, tmp_path):
        v = write_variant(tmp_path, [{"name": "mean", "params": {"bias": 1.0}}])
        assert v.variant_id == "default"
        assert v.engine_params.data_source_params["appName"] == "RateApp"
        assert v.engine_params.algorithm_params_list == [("mean", {"bias": 1.0})]
        assert v.runtime_conf == {"pio.mesh_shape": [1, 1]}

    def test_missing_factory_rejected(self, tmp_path):
        path = tmp_path / "engine.json"
        path.write_text(json.dumps({"datasource": {}}))
        with pytest.raises(EngineConfigError):
            load_engine_variant(str(path))

    def test_missing_file_and_bad_json(self, tmp_path):
        with pytest.raises(EngineConfigError):
            load_engine_variant(str(tmp_path / "nope.json"))
        bad = tmp_path / "engine.json"
        bad.write_text("{not json")
        with pytest.raises(EngineConfigError):
            load_engine_variant(str(bad))


class TestTrainWorkflow:
    def test_train_records_completed_instance(self, rated_app, tmp_path, storage_env):
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        instance = run_train(variant)
        assert instance.status == STATUS_COMPLETED
        assert storage_env.get_model_data_models().get(instance.id) is not None
        stored = storage_env.get_meta_data_engine_instances().get(instance.id)
        assert json.loads(stored.algorithms_params)[0]["name"] == "mean"

    def test_failed_training_records_failed(self, storage_env, tmp_path):
        storage_env.get_meta_data_apps().insert(App(name="RateApp"))
        storage_env.get_l_events().init_channel(1)  # no rating events -> sanity fails
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        with pytest.raises(ValueError):
            run_train(variant)
        instances = storage_env.get_meta_data_engine_instances().get_all()
        assert instances[0].status == STATUS_FAILED

    def test_multi_algorithm_and_params(self, rated_app, tmp_path):
        variant = write_variant(
            tmp_path,
            [{"name": "mean", "params": {}}, {"name": "mean", "params": {"bias": 1.0}}],
        )
        engine = engine_factory()
        ctx = RuntimeContext()
        models = engine.train(ctx, variant.engine_params)
        assert models[1].mean == pytest.approx(models[0].mean + 1.0)


class TestDeployAndQueryServer:
    def _deploy(self, variant, **kw):
        from predictionio_tpu.workflow.create_server import create_query_server

        thread, service = create_query_server(variant, host="127.0.0.1", port=0, **kw)
        thread.start()
        return thread, service, f"http://127.0.0.1:{thread.port}"

    def test_query_roundtrip_and_info(self, rated_app, tmp_path):
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        run_train(variant)
        thread, service, base = self._deploy(variant)
        try:
            r = requests.post(f"{base}/queries.json", json={"user": "u1"})
            assert r.status_code == 200
            assert r.json()["rating"] == pytest.approx(3.0)
            info = requests.get(f"{base}/").json()
            assert info["status"] == "alive"
            assert info["serverStats"]["queryCount"] == 1
            bad = requests.post(
                f"{base}/queries.json", data="nope",
                headers={"Content-Type": "application/json"},
            )
            assert bad.status_code == 400
        finally:
            thread.stop()

    def test_deploy_without_training_fails(self, rated_app, tmp_path):
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        with pytest.raises(LookupError):
            self._deploy(variant)

    def test_reload_hot_swaps_latest(self, rated_app, tmp_path, storage_env):
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        run_train(variant)
        thread, service, base = self._deploy(variant)
        try:
            first = requests.post(f"{base}/queries.json", json={}).json()["rating"]
            # add a biased run and reload
            variant2 = write_variant(tmp_path, [{"name": "mean", "params": {"bias": 10.0}}])
            run_train(variant2)
            requests.get(f"{base}/reload")
            second = requests.post(f"{base}/queries.json", json={}).json()["rating"]
            assert second == pytest.approx(first + 10.0)
        finally:
            thread.stop()

    def test_stop_endpoint_sets_stop_event(self, rated_app, tmp_path):
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        run_train(variant)
        thread, service, base = self._deploy(variant)
        try:
            requests.post(f"{base}/stop")
            assert service._stop_event.is_set()
        finally:
            thread.stop()

    def test_retrain_on_deploy(self, rated_app, tmp_path):
        variant = write_variant(tmp_path, [{"name": "retrain", "params": {}}])
        instance = run_train(variant)
        thread, service, base = self._deploy(variant)
        try:
            r = requests.post(f"{base}/queries.json", json={})
            assert r.json()["rating"] == pytest.approx(3.0)
        finally:
            thread.stop()

    def test_persistent_model_roundtrip(self, rated_app, tmp_path):
        from fake_engine import SelfSavingModel

        variant = write_variant(tmp_path, [{"name": "persistent", "params": {}}])
        instance = run_train(variant)
        assert instance.id in SelfSavingModel.saved
        thread, service, base = self._deploy(variant)
        try:
            assert requests.post(f"{base}/queries.json", json={}).json()["rating"] == pytest.approx(3.0)
        finally:
            thread.stop()

    def test_feedback_loop_writes_event(self, rated_app, tmp_path, storage_env):
        from predictionio_tpu.data.api.eventserver import create_event_server
        from predictionio_tpu.data.storage.base import AccessKey
        from predictionio_tpu.workflow.create_server import FeedbackConfig

        key = storage_env.get_meta_data_access_keys().insert(
            AccessKey(key="", app_id=rated_app)
        )
        es = create_event_server(host="127.0.0.1", port=0).start()
        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        run_train(variant)
        thread, service, base = self._deploy(
            variant,
            feedback=FeedbackConfig(
                event_server_url=f"http://127.0.0.1:{es.port}", access_key=key
            ),
        )
        try:
            r = requests.post(f"{base}/queries.json", json={"user": "u1"})
            assert "prId" in r.json()
            # feedback is written off the request path; poll briefly
            import time

            fb = []
            for _ in range(50):
                fb = list(
                    storage_env.get_l_events().find(rated_app, event_names=["predict"])
                )
                if fb:
                    break
                time.sleep(0.05)
            assert len(fb) == 1
            assert fb[0].entity_type == "pio_pr"
            assert fb[0].properties["prediction"]["prId"] == r.json()["prId"]
        finally:
            thread.stop()
            es.stop()


class TestEvaluation:
    def test_metric_evaluator_grid(self, rated_app, storage_env):
        engine = engine_factory()

        def absolute_error(eval_info, query, prediction, actual):
            return -abs(prediction["rating"] - actual)

        evaluation = Evaluation(
            engine=engine, metric=OptionAverageMetric(score=absolute_error)
        )
        candidates = [
            EngineParams.from_json_obj(
                {"datasource": {"params": {"appName": "RateApp"}},
                 "algorithms": [{"name": "mean", "params": {"bias": b}}]}
            )
            for b in (0.0, 5.0)
        ]
        instance = run_evaluation(evaluation, EngineParamsGenerator(candidates))
        assert instance.status == STATUS_COMPLETED
        results = json.loads(instance.evaluator_results_json)
        assert results["bestIndex"] == 0  # bias 0 beats bias 5
        assert "BEST" in instance.evaluator_results


class TestBatchPredict:
    def test_batch_predict_file_roundtrip(self, rated_app, tmp_path):
        from predictionio_tpu.workflow.batch_predict import run_batch_predict

        variant = write_variant(tmp_path, [{"name": "mean", "params": {}}])
        run_train(variant)
        qfile = tmp_path / "queries.jsonl"
        qfile.write_text('{"user": "u1"}\n\n{"user": "u2"}\n')
        out = tmp_path / "out.jsonl"
        count = run_batch_predict(variant, str(qfile), str(out))
        assert count == 2
        lines = [json.loads(l) for l in out.read_text().splitlines() if l]
        assert lines[0]["prediction"]["rating"] == pytest.approx(3.0)
        assert lines[1]["query"] == {"user": "u2"}

    def test_malformed_query_yields_error_row_not_lost_chunk(
        self, rated_app, tmp_path
    ):
        """One bad query among good ones: the good ones keep their
        predictions and the bad one gets an error record -- a chunked
        runner must not discard the chunk."""
        from predictionio_tpu.workflow.batch_predict import run_batch_predict

        # the ALS template raises on a query with neither user nor items
        variant = write_variant(
            tmp_path,
            [{"name": "als", "params": {"rank": 4, "numIterations": 2,
                                        "lambda": 0.05}}],
            factory="predictionio_tpu.models.recommendation.engine.engine_factory",
        )
        run_train(variant)
        qfile = tmp_path / "queries.jsonl"
        qfile.write_text('{"user": "u1"}\n{"bogus": true}\n{"user": "u2"}\n')
        out = tmp_path / "out.jsonl"
        count = run_batch_predict(variant, str(qfile), str(out))
        assert count == 3
        lines = [json.loads(l) for l in out.read_text().splitlines() if l]
        assert "prediction" in lines[0] and "prediction" in lines[2]
        assert "error" in lines[1] and lines[1]["query"] == {"bogus": True}

    def test_als_vectorized_batch_matches_looped_predict(self, storage_env):
        """ALSAlgorithm.batch_predict scores a chunk as one matmul; ranking
        (including blackList/unseenOnly filters, cold users, and item
        queries routed to the fallback) must match per-query predict()."""
        import numpy as np

        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.models.recommendation.engine import engine_factory
        from predictionio_tpu.workflow.context import RuntimeContext

        app_id = storage_env.get_meta_data_apps().insert(App(name="BatchApp"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        rng = np.random.default_rng(4)
        le.batch_insert(
            [
                Event(event="rate", entity_type="user", entity_id=f"u{u}",
                      target_entity_type="item", target_entity_id=f"i{int(i)}",
                      properties=DataMap({"rating": float(rng.integers(1, 6))}))
                for u in range(15) for i in rng.choice(10, 4, replace=False)
            ],
            app_id,
        )
        ep = EngineParams.from_json_obj(
            {"datasource": {"params": {"appName": "BatchApp"}},
             "algorithms": [{"name": "als", "params":
                             {"rank": 4, "numIterations": 3, "lambda": 0.05}}]}
        )
        engine = engine_factory()
        models = engine.train(RuntimeContext(), ep)
        algo = engine._algorithms(ep)[0]
        queries = [
            {"user": "u1", "num": 3},
            {"user": "u2", "num": 5, "unseenOnly": False},
            {"user": "u3", "num": 3, "blackList": ["i0", "i1"]},
            {"user": "nobody", "num": 3},          # cold -> fallback
            {"items": ["i2"], "num": 4},            # similarity -> fallback
        ]
        batched = dict(algo.batch_predict(models[0], list(enumerate(queries))))
        for qid, q in enumerate(queries):
            single = algo.predict(models[0], q)
            got, want = batched[qid]["itemScores"], single["itemScores"]
            # gemm vs gemv round differently in the last ulps, and argsort
            # order on near-ties follows those bits: require the same item
            # SET with matching per-item scores, and identical order
            # wherever adjacent score gaps exceed the float tolerance
            got_map = {s["item"]: s["score"] for s in got}
            want_map = {s["item"]: s["score"] for s in want}
            assert got_map.keys() == want_map.keys(), q
            for item, score in got_map.items():
                assert score == pytest.approx(want_map[item], rel=1e-5), (q, item)
            for i in range(len(want) - 1):
                if want[i]["score"] - want[i + 1]["score"] > 1e-4:
                    assert got[i]["item"] == want[i]["item"], (q, i)


class TestEnsureBackend:
    """No fallback that hides the device: the configured platform comes up
    or ``ensure_backend`` raises and names it."""

    @staticmethod
    def _fake_jax(monkeypatch, devices, configured="cpu"):
        import jax

        updates = []
        monkeypatch.delenv("PIO_PLATFORM", raising=False)
        monkeypatch.setattr(jax, "devices", devices)
        monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
        monkeypatch.setattr(type(jax.config), "jax_platforms", configured, raising=False)
        return updates

    @pytest.mark.parametrize("how", ["argument", "PIO_PLATFORM"])
    def test_named_platform_unavailable_raises(self, monkeypatch, how):
        import predictionio_tpu.utils.platform as plat

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        updates = self._fake_jax(monkeypatch, no_backend)
        if how == "argument":
            with pytest.raises(RuntimeError, match=r"'tpu' \(from pio.platform\)"):
                plat.ensure_backend("tpu")
        else:
            monkeypatch.setenv("PIO_PLATFORM", "tpu")
            with pytest.raises(RuntimeError, match=r"'tpu' \(from PIO_PLATFORM\)"):
                plat.ensure_backend()
        # it asked for the named platform and for nothing after it
        assert [v for k, v in updates if k == "jax_platforms"] == ["tpu"]

    def test_default_unavailable_raises(self, monkeypatch):
        """JAX's own configuration (JAX_PLATFORMS) names a backend that does
        not come up: no retry of another list, no CPU."""
        import predictionio_tpu.utils.platform as plat

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        updates = self._fake_jax(monkeypatch, no_backend, configured="tpu")
        with pytest.raises(RuntimeError, match=r"'tpu' \(from JAX_PLATFORMS\)"):
            plat.ensure_backend()
        assert not [v for k, v in updates if k == "jax_platforms"]

    def test_unnamed_default_that_lands_on_cpu_raises(self, monkeypatch):
        """Nothing named a platform and JAX found no accelerator: the CPU
        is never something the program settles for."""
        import predictionio_tpu.utils.platform as plat

        class Dev:
            platform = "cpu"

        self._fake_jax(monkeypatch, lambda: [Dev()], configured=None)
        with pytest.raises(RuntimeError, match="no accelerator found"):
            plat.ensure_backend()

    def test_cpu_when_asked_is_honoured(self, monkeypatch):
        import predictionio_tpu.utils.platform as plat

        class Dev:
            platform = "cpu"

        self._fake_jax(monkeypatch, lambda: [Dev()], configured=None)
        monkeypatch.setenv("PIO_PLATFORM", "cpu")
        assert plat.ensure_backend() == "cpu"
        monkeypatch.delenv("PIO_PLATFORM")
        assert plat.ensure_backend("cpu") == "cpu"

    def test_real_backend_comes_up_on_the_configured_platform(self):
        from predictionio_tpu.utils.platform import device_report, ensure_backend

        assert ensure_backend() == "cpu"  # conftest set JAX_PLATFORMS=cpu
        report = device_report()
        assert report["platform"] == "cpu" and report["count"] == 8


class TestCompileCache:
    PROBE = (
        "import jax\n"
        "from predictionio_tpu.utils.platform import ensure_backend\n"
        "ensure_backend()\n"
        "print('CACHE|' + str(jax.config.jax_compilation_cache_dir) + '|'"
        " + str(jax.config.jax_persistent_cache_min_compile_time_secs))\n"
    )

    def _probe(self, env_overrides):
        import subprocess
        import sys

        env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_overrides}
        env = {k: v for k, v in env.items() if v is not None}
        out = subprocess.run(
            [sys.executable, "-c", self.PROBE], env=env, capture_output=True,
            text=True, timeout=120, cwd=_REPO_ROOT,
        )
        assert out.returncode == 0, out.stderr[-800:]
        line = next(l for l in out.stdout.splitlines() if l.startswith("CACHE|"))
        return line.split("|")[1:]

    def test_env_dir_is_honoured_and_no_other_path_is_set(self, tmp_path):
        path, min_secs = self._probe({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert path == str(tmp_path)
        assert float(min_secs) < 1.0  # lowered, so serving programs are written

    def test_default_is_checkout_jax_cache_and_same_in_two_processes(self):
        first = self._probe({"JAX_COMPILATION_CACHE_DIR": None})
        second = self._probe({"JAX_COMPILATION_CACHE_DIR": None})
        assert first == second
        assert first[0] == os.path.join(_REPO_ROOT, ".jax_cache")

    def test_in_process_env_dir_sets_no_config_path(self, monkeypatch, tmp_path):
        import jax

        import predictionio_tpu.utils.platform as plat

        updates = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append(k))
        assert plat.configure_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

    def test_the_key_covers_the_names_a_trace_is_read_by(self, monkeypatch, tmp_path):
        """A cache written before a ``jax.named_scope`` was added must not serve
        the program under its old names: JAX's key leaves them out unless told."""
        import jax

        import predictionio_tpu.utils.platform as plat

        updates = {}
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(jax.config, "update", updates.__setitem__)
        plat.configure_compile_cache()
        assert updates["jax_compilation_cache_include_metadata_in_key"] is True


