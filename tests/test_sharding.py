"""Sharded serving fabric: hash-partitioned user factors across N scorer
shards with per-shard hot swap.

Layers under test, bottom-up:

- ``serving.shardmap`` -- the stable user -> shard hash (crc32, NOT the
  salted builtin ``hash``) and the frontend's user extraction.
- ``Algorithm.shard_model`` / ``Engine.shard_models`` -- partitioning a
  trained recommendation model keeps every owned user's scores
  byte-identical (compaction, never reordering).
- the registry's shard axis -- ``publish(shard_blobs=...)`` writes
  ``v-NNNNNN/shard-K/model.bin`` with per-shard CRCs.
- ``QueryService(shard=K, num_shards=N)`` -- per-shard swap, the
  ``PIO_SHARD_BUDGET_BYTES`` guard, and the acceptance bar: a model 4x
  larger than one shard's budget serves byte-identically to the
  single-process server from per-shard blobs.
- the fabric itself (``serving.fabric``) -- end-to-end byte-identity
  through real frontend/shard processes, the per-shard swap fan-out with
  its one-swap-window skew bound, and the SIGKILL-a-shard chaos drill
  (survivors unharmed under load, respawn rejoins at the committed
  version).
"""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request
import zlib

import numpy as np
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.serving.shardmap import extract_user, shard_of

RANK = 8
USERS = [f"u{i:03d}" for i in range(160)]
ITEMS = [f"i{j}" for j in range(6)]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

@pytest.fixture()
def rec_app(storage_env):
    """A user-heavy catalog (160 users x 6 items): the user factor table
    dominates the serialized model, which is what makes the per-shard
    budget arithmetic of the 4x test meaningful."""
    app_id = storage_env.get_meta_data_apps().insert(App(name="ShardApp"))
    le = storage_env.get_l_events()
    le.init_channel(app_id)
    rng = np.random.default_rng(11)
    events = []
    for u in USERS:
        for item in rng.choice(ITEMS, size=3, replace=False):
            events.append((u, str(item), float(rng.integers(1, 6))))
    le.batch_insert(
        [
            Event(event="rate", entity_type="user", entity_id=u,
                  target_entity_type="item", target_entity_id=i,
                  properties=DataMap({"rating": r}))
            for u, i, r in events
        ],
        app_id=app_id,
    )
    return app_id


def _train_rec_variant(tmp_path, iterations=3):
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.json_extractor import load_engine_variant

    path = tmp_path / "engine.json"
    path.write_text(json.dumps({
        "id": "shard-test",
        "engineFactory":
            "predictionio_tpu.models.recommendation.engine_factory",
        "datasource": {"params": {"appName": "ShardApp"}},
        "algorithms": [
            {"name": "als",
             "params": {"rank": RANK, "numIterations": iterations,
                        "lambda": 0.05, "seed": 3}}
        ],
    }))
    variant = load_engine_variant(str(path))
    instance = run_train(variant)
    return variant, instance


def _deployable(variant, instance):
    """(engine, engine_params, ctx, models, full_blob) for the trained
    instance -- the retrain loop's publish-side view of the model."""
    from predictionio_tpu.data import storage
    from predictionio_tpu.workflow.context import RuntimeContext
    from predictionio_tpu.workflow.core_workflow import (
        engine_params_from_instance,
    )
    from predictionio_tpu.workflow.json_extractor import build_engine

    engine = build_engine(variant)
    engine_params = engine_params_from_instance(instance)
    ctx = RuntimeContext(instance.runtime_conf)
    record = storage.get_model_data_models().get(instance.id)
    models = engine.prepare_deploy(
        ctx, engine_params, instance.id, record.models
    )
    return engine, engine_params, ctx, models, record.models


def _publish_sharded(variant, instance, num_shards, copies=1,
                     extra_meta=None):
    """Publish ``copies`` registry versions, each carrying the full blob
    plus one serialized slice per shard. Returns (registry, versions,
    full_blob, shard_blobs)."""
    from predictionio_tpu.online.registry import ModelRegistry

    engine, engine_params, ctx, models, full_blob = _deployable(
        variant, instance
    )
    shard_blobs = [
        engine.serialize_models(
            ctx, engine_params, instance.id,
            engine.shard_models(engine_params, models, k, num_shards),
        )
        for k in range(num_shards)
    ]
    registry = ModelRegistry.for_variant(variant)
    meta = {
        "source": "test",
        "instance_id": instance.id,
        "engine_params": engine_params.to_json_obj(),
        **(extra_meta or {}),
    }
    versions = [
        registry.publish(full_blob, meta=meta, shard_blobs=shard_blobs)
        for _ in range(copies)
    ]
    return registry, versions, full_blob, shard_blobs


def _post(port, obj, path="/queries.json", timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read(), dict(exc.headers)


def _get(port, path, timeout=30):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return resp.status, resp.read()


# ---------------------------------------------------------------------------
# shardmap: the routing hash
# ---------------------------------------------------------------------------

class TestShardMap:
    def test_hash_is_crc32_not_builtin(self):
        """The builtin ``hash`` is salted per process (PYTHONHASHSEED);
        routing MUST agree between every frontend and publisher process,
        so the contract is pinned to crc32 of the utf-8 id."""
        for uid in ("alice", "u42", 42, "äöü"):
            expected = zlib.crc32(str(uid).encode("utf-8")) % 4
            assert shard_of(uid, 4) == expected

    def test_single_shard_and_distribution(self):
        assert shard_of("anyone", 1) == 0
        assert shard_of("anyone", 0) == 0
        hit = {shard_of(u, 4) for u in USERS}
        assert hit == {0, 1, 2, 3}

    def test_extract_user(self):
        assert extract_user(b'{"user": "u1", "num": 3}') == "u1"
        assert extract_user(b'{"user": 7}') == "7"
        assert extract_user(b'{"num": 3}') is None
        assert extract_user(b"not json{") is None
        assert extract_user(b'{"user": {"id": 1}}') is None
        assert extract_user(b'{"user": [1]}') is None
        assert extract_user(b'{"user": true}') is None


# ---------------------------------------------------------------------------
# model partitioning
# ---------------------------------------------------------------------------

class TestShardModel:
    def test_owned_users_score_byte_identically(self, rec_app, tmp_path):
        """Partitioning is pure compaction: every user's predictions on
        the shard that owns them serialize to the same bytes as on the
        unsharded model, and unowned users fall back to the cold-user
        path (only replicated item-side state)."""
        variant, instance = _train_rec_variant(tmp_path)
        engine, engine_params, ctx, models, _ = _deployable(
            variant, instance
        )
        algo = engine._algorithms(engine_params)[0]
        n = 4
        sharded = [
            engine.shard_models(engine_params, models, k, n)
            for k in range(n)
        ]
        cold = json.dumps(
            algo.predict(models[0], {"user": "nobody", "num": 2}),
            sort_keys=True,
        )
        for u in USERS[:32]:
            owner = shard_of(u, n)
            full = json.dumps(
                algo.predict(models[0], {"user": u, "num": 2}),
                sort_keys=True,
            )
            got = json.dumps(
                algo.predict(sharded[owner][0], {"user": u, "num": 2}),
                sort_keys=True,
            )
            assert got == full, f"user {u} diverged on its owner shard"
            other = json.dumps(
                algo.predict(
                    sharded[(owner + 1) % n][0], {"user": u, "num": 2}
                ),
                sort_keys=True,
            )
            assert other == cold, f"user {u} leaked into a foreign shard"

    def test_empty_shard_and_validation(self, rec_app, tmp_path):
        variant, instance = _train_rec_variant(tmp_path, iterations=1)
        engine, engine_params, ctx, models, _ = _deployable(
            variant, instance
        )
        # far more shards than users guarantees at least one empty slice
        n = 4096
        counts = [0] * n
        for u in USERS:
            counts[shard_of(u, n)] += 1
        empty = counts.index(0)
        sharded = engine.shard_models(engine_params, models, empty, n)
        assert sharded[0].als.user_factors.shape == (0, RANK)
        assert engine.shard_models(engine_params, models, 0, 1) is not None
        with pytest.raises(ValueError):
            engine.shard_models(engine_params, models, 5, 4)
        with pytest.raises(ValueError):
            engine.shard_models(engine_params, models, -1, 4)


# ---------------------------------------------------------------------------
# registry: the shard axis
# ---------------------------------------------------------------------------

class TestRegistryShardAxis:
    def test_shard_blob_roundtrip_and_crc(self, storage_env, tmp_path):
        from predictionio_tpu.online.registry import (
            ModelRegistry,
            RegistryError,
        )

        registry = ModelRegistry(str(tmp_path / "reg"), "key")
        full = b"full-model-bytes" * 64
        shards = [f"shard-{k}".encode() * 32 for k in range(3)]
        v = registry.publish(full, meta={"source": "test"},
                             shard_blobs=shards)
        entry = registry.latest()
        assert entry.shard_count == 3
        assert entry.load_blob() == full
        for k in range(3):
            assert entry.load_blob(shard=k) == shards[k]
        manifest = entry.manifest["shards"]
        assert manifest["count"] == 3
        assert [b["bytes"] for b in manifest["blobs"]] == [
            len(b) for b in shards
        ]
        with pytest.raises((RegistryError, IndexError, ValueError)):
            entry.load_blob(shard=7)
        # corrupt one shard blob on disk: its CRC must refuse to load,
        # while the sibling shards and the full blob stay loadable
        path = os.path.join(entry.path, "shard-1", "model.bin")
        with open(path, "r+b") as f:
            f.seek(0)
            f.write(b"\xff\xff\xff\xff")
        with pytest.raises(RegistryError):
            entry.load_blob(shard=1)
        assert entry.load_blob(shard=0) == shards[0]
        assert entry.load_blob() == full

    def test_unsharded_publish_has_no_shard_axis(self, tmp_path):
        from predictionio_tpu.online.registry import ModelRegistry

        registry = ModelRegistry(str(tmp_path / "reg"), "key")
        registry.publish(b"just-the-full-blob", meta={"source": "test"})
        entry = registry.latest()
        assert entry.shard_count == 0
        assert "shards" not in entry.manifest


# ---------------------------------------------------------------------------
# retrain loop: publishing the shard axis
# ---------------------------------------------------------------------------

class TestLoopShardBlobs:
    def test_untouched_shards_reuse_bytes_verbatim(
        self, rec_app, tmp_path
    ):
        """A fold-in republish only recomputes the shards owning touched
        users; every other shard's bytes come verbatim from the
        still-latest version (same shard count, same item vocabulary)."""
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

        variant, instance = _train_rec_variant(tmp_path, iterations=1)
        n = 4
        engine, engine_params, ctx, models, _ = _deployable(
            variant, instance
        )
        loop = RetrainLoop.__new__(RetrainLoop)
        loop.config = RetrainConfig(scorer_shards=n)
        loop.engine = engine
        loop.engine_params = engine_params
        loop.ctx = ctx
        loop.instance = instance
        loop.models = models
        # the published version's manifest carries the reuse guard
        registry, _, _, first_blobs = _publish_sharded(
            variant, instance, n,
            extra_meta={"shard_item_count": loop._item_count(models)},
        )
        loop.registry = registry
        assert registry.latest().shard_count == n
        touched = [u for u in USERS if shard_of(u, n) == 2][:3]
        blobs = loop._shard_blobs(models, touched)
        assert len(blobs) == n
        for k in range(n):
            if k == 2:
                # recomputed (may or may not equal the old bytes; it must
                # at least be a loadable serialized slice)
                assert isinstance(blobs[k], bytes) and blobs[k]
            else:
                assert blobs[k] == first_blobs[k], (
                    f"untouched shard {k} was not carried forward verbatim"
                )

    def test_item_growth_recomputes_every_shard(self, rec_app, tmp_path):
        from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

        variant, instance = _train_rec_variant(tmp_path, iterations=1)
        n = 4
        registry, _, _, _ = _publish_sharded(variant, instance, n)
        loop = RetrainLoop.__new__(RetrainLoop)
        loop.config = RetrainConfig(scorer_shards=n)
        loop.registry = registry
        engine, engine_params, ctx, models, _ = _deployable(
            variant, instance
        )
        loop.engine = engine
        loop.engine_params = engine_params
        loop.ctx = ctx
        loop.instance = instance
        loop.models = models
        # the latest manifest has no shard_item_count (published by the
        # raw helper): the guard must fail closed and recompute all
        touched = [USERS[0]]
        blobs = loop._shard_blobs(models, touched)
        fresh = [
            engine.serialize_models(
                ctx, engine_params, instance.id,
                engine.shard_models(engine_params, models, k, n),
            )
            for k in range(n)
        ]
        assert blobs == fresh


# ---------------------------------------------------------------------------
# QueryService in shard mode + the budget guard (acceptance: 4x)
# ---------------------------------------------------------------------------

class TestShardedQueryService:
    def test_4x_model_serves_byte_identical_from_shard_blobs(
        self, rec_app, tmp_path, monkeypatch
    ):
        """THE acceptance bar: with PIO_SHARD_BUDGET_BYTES set so the
        full blob is >= 4x one shard's budget, a sharded deploy still
        swaps (each shard loads only its slice) and serves every user
        byte-identically to the single-process server on the SAME
        registry generation -- and the full blob itself is refused."""
        from predictionio_tpu.workflow.create_server import (
            create_query_server,
        )

        variant, instance = _train_rec_variant(tmp_path)
        n = 8
        registry, versions, full_blob, shard_blobs = _publish_sharded(
            variant, instance, n
        )
        version = versions[0].version
        budget = len(full_blob) // 4
        assert max(len(b) for b in shard_blobs) <= budget, (
            "fixture regression: shard slices must fit the 4x budget "
            f"(full={len(full_blob)}, max shard="
            f"{max(len(b) for b in shard_blobs)}, budget={budget})"
        )

        single_thread, single = create_query_server(
            variant, host="127.0.0.1", port=0, model_version=version
        )
        single_thread.start()
        shard_threads = []
        try:
            monkeypatch.setenv("PIO_SHARD_BUDGET_BYTES", str(budget))
            services = []
            for k in range(n):
                thread, service = create_query_server(
                    variant, host="127.0.0.1", port=0,
                    shard=k, num_shards=n, model_version=version,
                )
                thread.start()
                shard_threads.append(thread)
                services.append((thread, service))
                assert service.model_version == version
            for u in USERS[:24]:
                owner = shard_of(u, n)
                thread, _ = services[owner]
                st_s, body_s, hdr_s = _post(
                    single_thread.port, {"user": u, "num": 2}
                )
                st_k, body_k, hdr_k = _post(
                    thread.port, {"user": u, "num": 2}
                )
                assert (st_s, st_k) == (200, 200)
                assert body_k == body_s, f"user {u} diverged"
                # header and body agree on ONE version per response
                assert hdr_k.get("x-pio-model-version") == str(version)
                assert hdr_s.get("x-pio-model-version") == str(version)
        finally:
            for thread in shard_threads:
                thread.stop()
            single_thread.stop()

    def test_budget_refuses_oversized_full_blob(
        self, rec_app, tmp_path, monkeypatch
    ):
        """A version WITHOUT shard blobs forces the full-blob fallback;
        under the budget that load must fail loudly (the swap errors) --
        never silently serve a model the shard cannot afford."""
        from predictionio_tpu.online.registry import ModelRegistry
        from predictionio_tpu.workflow.create_server import (
            create_query_server,
        )

        variant, instance = _train_rec_variant(tmp_path, iterations=1)
        engine, engine_params, ctx, models, full_blob = _deployable(
            variant, instance
        )
        registry = ModelRegistry.for_variant(variant)
        v = registry.publish(full_blob, meta={
            "source": "test",
            "instance_id": instance.id,
            "engine_params": engine_params.to_json_obj(),
        })
        thread, service = create_query_server(
            variant, host="127.0.0.1", port=0, shard=0, num_shards=2,
        )
        thread.start()
        try:
            monkeypatch.setenv(
                "PIO_SHARD_BUDGET_BYTES", str(len(full_blob) // 4)
            )
            st, body, _ = _post(
                thread.port, {"version": v.version}, path="/models/swap"
            )
            assert st == 500
            assert b"budget" in body
        finally:
            thread.stop()
        # unsharded deploys ignore the budget entirely
        monkeypatch.setenv("PIO_SHARD_BUDGET_BYTES", "1")
        thread2, service2 = create_query_server(
            variant, host="127.0.0.1", port=0,
        )
        thread2.start()
        try:
            st, _, _ = _post(thread2.port, {"user": USERS[0], "num": 2})
            assert st == 200
        finally:
            thread2.stop()

    def test_shard_params_validation(self, rec_app, tmp_path):
        from predictionio_tpu.workflow.create_server import QueryService

        variant, _ = _train_rec_variant(tmp_path, iterations=1)
        with pytest.raises(ValueError):
            QueryService(variant, shard=None, num_shards=2)
        with pytest.raises(ValueError):
            QueryService(variant, shard=2, num_shards=2)


# ---------------------------------------------------------------------------
# the fabric: real frontend + shard processes
# ---------------------------------------------------------------------------

def _start_fabric(variant, num_shards=2, workers=1, model_version=None):
    from predictionio_tpu.serving.procserver import FrontendConfig
    from predictionio_tpu.workflow.create_server import (
        create_sharded_query_server,
    )

    fabric = create_sharded_query_server(
        variant, host="127.0.0.1", port=0, scorer_shards=num_shards,
        frontend=FrontendConfig(workers=workers, spawn_timeout_s=120.0),
        model_version=model_version,
    )
    fabric.start()
    return fabric


class TestOneProcessForEachChip:
    """Scorer shards are N JAX processes on one host: host-only, said by
    the supervisor, and refused for engines that ask for a device path."""

    def test_shard_children_are_told_the_cpu(self, rec_app, tmp_path, monkeypatch):
        from predictionio_tpu.serving.fabric import ShardFabric

        variant, _ = _train_rec_variant(tmp_path)
        monkeypatch.setenv("PIO_PLATFORM", "tpu")
        env = ShardFabric(variant, num_shards=2)._child_env()
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "PIO_PLATFORM" not in env

    @pytest.mark.parametrize("params, named", [
        ({"retrieval": {"mode": "mips"}}, "mips"),
        ({"usePallas": True}, "usePallas"),
    ])
    def test_device_query_paths_are_refused(self, rec_app, tmp_path, params, named):
        from predictionio_tpu.serving.fabric import ShardFabric

        variant, _ = _train_rec_variant(tmp_path)
        variant.engine_params.algorithm_params_list[0][1].update(params)
        with pytest.raises(ValueError, match=named):
            ShardFabric(variant, num_shards=2)


class TestShardFabric:
    def test_byte_identity_and_per_shard_swap(self, rec_app, tmp_path):
        """End-to-end through real processes: every user's response from
        the fabric is byte-identical to the single-process server on the
        same registry generation; one ``POST /models/swap`` fans the next
        epoch out to every shard, with header and body agreeing on one
        version per response."""
        from predictionio_tpu.workflow.create_server import (
            create_query_server,
        )

        variant, instance = _train_rec_variant(tmp_path)
        _, versions, _, _ = _publish_sharded(
            variant, instance, 2, copies=2
        )
        v1, v2 = versions[0].version, versions[1].version
        single_thread, _ = create_query_server(
            variant, host="127.0.0.1", port=0, model_version=v1
        )
        single_thread.start()
        fabric = _start_fabric(variant, model_version=v1)
        try:
            probes = USERS[:16]
            for u in probes:
                st_s, body_s, _ = _post(
                    single_thread.port, {"user": u, "num": 2}
                )
                st_f, body_f, hdr_f = _post(
                    fabric.port, {"user": u, "num": 2}
                )
                assert (st_s, st_f) == (200, 200)
                assert body_f == body_s, f"user {u} diverged"
                assert hdr_f.get("x-pio-model-version") == str(v1)
            # userless queries see only replicated state: any shard
            # answers, and the spread route must still be a 200
            st, _, _ = _post(fabric.port, {"num": 2})
            assert st in (200, 400)  # engine-defined; never a 5xx

            st, body, _ = _post(fabric.port, {}, path="/models/swap")
            assert st == 200, body
            swap = json.loads(body)
            assert swap["status"] == "swapped"
            assert swap["modelVersion"] == v2
            assert [s["modelVersion"] for s in swap["shards"]] == [v2, v2]
            st, body = _get(fabric.port, "/models.json")
            models_info = json.loads(body)
            assert models_info["currentVersion"] == v2
            assert all(
                s["currentVersion"] == v2 for s in models_info["shards"]
            )
            for u in probes[:4]:
                st, _, hdrs = _post(fabric.port, {"user": u, "num": 2})
                assert st == 200
                assert hdrs.get("x-pio-model-version") == str(v2)
            # per-shard gauges on the aggregated scrape
            st, body = _get(fabric.port, "/metrics")
            scrape = body.decode()
            assert "pio_scorer_shard_count 2" in scrape
            assert f'pio_model_version{{shard="0"}} {v2}' in scrape
            assert f'pio_model_version{{shard="1"}} {v2}' in scrape
        finally:
            fabric.stop()
            single_thread.stop()

    def test_sigkill_shard_mid_swap(self, rec_app, tmp_path):
        """The chaos drill: SIGKILL one shard, then drive a swap through
        the dead window under survivor load. Survivors answer
        byte-identically with zero client errors, the swap commits
        partially (skew bounded to the one swap window), and the
        respawned shard rejoins at the COMMITTED version."""
        variant, instance = _train_rec_variant(tmp_path)
        _, versions, _, _ = _publish_sharded(
            variant, instance, 2, copies=2
        )
        v1, v2 = versions[0].version, versions[1].version
        fabric = _start_fabric(variant, model_version=v1)
        try:
            survivors = [u for u in USERS if shard_of(u, 2) == 1][:8]
            victims = [u for u in USERS if shard_of(u, 2) == 0][:4]
            baseline = {}
            for u in survivors + victims:
                st, body, hdrs = _post(fabric.port, {"user": u, "num": 2})
                assert st == 200
                assert hdrs.get("x-pio-model-version") == str(v1)
                baseline[u] = body

            os.kill(fabric._shards[0].proc.pid, signal.SIGKILL)

            errors = []
            stop_load = threading.Event()

            def hammer():
                while not stop_load.is_set():
                    for u in survivors:
                        st, body, _ = _post(fabric.port, {"user": u, "num": 2})
                        if st != 200 or body != baseline[u]:
                            errors.append((u, st, body))

            threads = [threading.Thread(target=hammer) for _ in range(2)]
            for t in threads:
                t.start()
            try:
                # the swap lands in the dead window: partial, survivors on
                # the new epoch -- version skew is this one swap window
                st, body, _ = _post(fabric.port, {}, path="/models/swap")
                assert st == 200, body
                swap = json.loads(body)
                assert swap["status"] == "partial"
                assert swap["modelVersion"] == v2
                by_shard = {s["shard"]: s for s in swap["shards"]}
                assert by_shard[0]["status"] == "error"
                assert by_shard[1]["modelVersion"] == v2

                deadline = time.monotonic() + 120.0
                rejoined = False
                while time.monotonic() < deadline:
                    st, body = _get(fabric.port, "/")
                    info = json.loads(body)
                    shard0 = info["shards"][0]
                    if (
                        shard0.get("status") == "alive"
                        and shard0.get("modelVersion") == v2
                    ):
                        rejoined = True
                        break
                    time.sleep(0.5)
                assert rejoined, "shard 0 never rejoined at the committed version"
            finally:
                stop_load.set()
                for t in threads:
                    t.join(timeout=60)
            assert not errors, errors[:3]

            # the rejoined shard serves its users again, at v2, with the
            # same bytes (both versions carry identical content here)
            for u in victims:
                st, body, hdrs = _post(fabric.port, {"user": u, "num": 2})
                assert st == 200
                assert hdrs.get("x-pio-model-version") == str(v2)
                assert body == baseline[u]
            assert fabric._respawns == 1
        finally:
            fabric.stop()

    def test_sigkill_frontend_respawns(self, rec_app, tmp_path):
        """A dead frontend worker is respawned onto the SAME ring files
        with a bumped rid generation; the fabric serves again without
        touching any shard."""
        variant, instance = _train_rec_variant(tmp_path, iterations=1)
        _publish_sharded(variant, instance, 2)
        fabric = _start_fabric(variant)
        try:
            st, body, _ = _post(fabric.port, {"user": USERS[0], "num": 2})
            assert st == 200
            os.kill(fabric._frontends[0].proc.pid, signal.SIGKILL)
            deadline = time.monotonic() + 60.0
            while fabric._fe_respawns < 1 and time.monotonic() < deadline:
                time.sleep(0.2)
            assert fabric._fe_respawns == 1, "frontend never respawned"
            deadline = time.monotonic() + 30.0
            last = None
            while time.monotonic() < deadline:
                try:
                    st, body2, _ = _post(
                        fabric.port, {"user": USERS[0], "num": 2}, timeout=5
                    )
                    if st == 200:
                        assert body2 == body
                        break
                except (urllib.error.URLError, OSError) as exc:
                    last = exc
                time.sleep(0.2)
            else:
                pytest.fail(f"fabric never served after respawn: {last}")
            assert fabric._respawns == 0  # shards untouched
        finally:
            fabric.stop()


# -- shard-count sweep (real multi-core rounds; slow-marked) ------------------

@pytest.mark.slow
class TestShardSweep:
    def test_sharded_sweep_byte_identity(self):
        """The `serving_bench --scorer-shards 1,2,4` sweep as a runnable
        artifact: single-process baseline vs the 2- and 4-shard fabric
        over the same synthetic catalog. On the 2-core box the qps
        numbers mostly measure process overhead (shards share cores);
        the byte-identity assertion is the real gate -- partitioning
        selects user rows, it must never change a single response byte."""
        from predictionio_tpu.tools.serving_bench import run_sharded_ab

        rep = run_sharded_ab(
            "recommendation",
            concurrency=8,
            requests=240,
            shards=(1, 2, 4),
            users=50,
            items=2_000,
            events=4_000,
        )
        assert rep["responses_identical"], rep
        assert rep["responses_equivalent"], rep
        for n in (1, 2, 4):
            arm = rep[f"shards_{n}"]
            assert arm["failures"] == 0, (n, arm)
            assert arm["qps"] > 0
        assert "qps_speedup_shards_2" in rep
        assert "qps_speedup_shards_4" in rep
