"""Sequence template: SASRec learns a deterministic next-item pattern, the
sp (ring attention) training path agrees with single-device training, and
the DASE engine runs end-to-end from stored events."""

import numpy as np
import pytest

from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.models.sequence import engine_factory
from predictionio_tpu.models.sequence.model import (
    SASRecConfig,
    score_next_items,
    train_sasrec,
)
from predictionio_tpu.workflow.context import RuntimeContext

N_ITEMS = 12
MAX_LEN = 8


def cyclic_sequences(n=96, seed=0):
    """Every sequence walks the item cycle i -> (i+1) % N_ITEMS."""
    rng = np.random.default_rng(seed)
    out = np.zeros((n, MAX_LEN), np.int32)
    for r in range(n):
        start = rng.integers(0, N_ITEMS)
        out[r] = (start + np.arange(MAX_LEN)) % N_ITEMS + 1  # ids shifted +1
    return out


def _mesh(data, seq):
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[: data * seq]).reshape(data, seq)
    return Mesh(devices, ("data", "seq"))


def _config(**kw):
    base = dict(
        num_items=N_ITEMS, max_len=MAX_LEN, embed_dim=16, num_heads=2,
        num_blocks=1, ffn_dim=32, learning_rate=0.01, batch_size=32, epochs=8,
        seed=0,
    )
    base.update(kw)
    return SASRecConfig(**base)


class TestSASRecModel:
    def test_learns_cycle_single_device(self):
        config = _config()
        params, _ = train_sasrec(config, cyclic_sequences(), _mesh(1, 1))
        hits = 0
        for start in range(N_ITEMS):
            prefix = (start + np.arange(4)) % N_ITEMS + 1
            scores = score_next_items(params, config, prefix)
            want = (start + 4) % N_ITEMS  # 0-based next item index
            hits += int(np.argmax(scores) == want)
        assert hits >= 10, f"only {hits}/12 next-items predicted"

    def test_a_seeded_fit_gives_the_losses_it_gave(self):
        """The default backbone has no benchmark cell and no compiled step on
        record: its seeded fit on one CPU device is held to the losses PR 42's
        tree gave (every step's, float32 to the bit), so that a change to the
        trainer or to the pieces the backbones share cannot move it unseen."""
        import jax
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices("cpu")[:1]).reshape(1, 1), ("data", "seq"))
        _, losses = train_sasrec(_config(), cyclic_sequences(), mesh, log_every=1)
        assert [float(x).hex() for x in losses] == [
            "0x1.00539c0000000p+2", "0x1.c2e2340000000p+1", "0x1.7feb660000000p+1",
            "0x1.4cd2d40000000p+1", "0x1.2bf2560000000p+1", "0x1.14f69a0000000p+1",
            "0x1.f8a4e20000000p+0", "0x1.dce09c0000000p+0", "0x1.b7cb2c0000000p+0",
            "0x1.97309a0000000p+0", "0x1.7c849a0000000p+0", "0x1.59d6e60000000p+0",
            "0x1.406a420000000p+0", "0x1.21483e0000000p+0", "0x1.171e460000000p+0",
            "0x1.ec004c0000000p-1", "0x1.c1ba6a0000000p-1", "0x1.a4d0460000000p-1",
            "0x1.8abc920000000p-1", "0x1.48745c0000000p-1", "0x1.2ceae60000000p-1",
            "0x1.0339400000000p-1", "0x1.0aaa340000000p-1", "0x1.e8c7d20000000p-2"]

    def test_sp_training_runs_and_learns(self):
        """dp=2 x sp=4: ring attention on the training path."""
        config = _config()
        params, _ = train_sasrec(config, cyclic_sequences(), _mesh(2, 4))
        hits = 0
        for start in range(N_ITEMS):
            prefix = (start + np.arange(4)) % N_ITEMS + 1
            scores = score_next_items(params, config, prefix)
            hits += int(np.argmax(scores) == (start + 4) % N_ITEMS)
        assert hits >= 10, f"only {hits}/12 next-items predicted under sp"

    def test_sp_loss_matches_single_device(self):
        """One jitted loss/grad eval must agree across mesh layouts."""
        import jax
        import jax.numpy as jnp
        import optax

        from predictionio_tpu.models.sequence.model import SASRec
        from predictionio_tpu.models.sequence.sasrec import logits as _logits

        seqs = cyclic_sequences(n=16)
        targets = np.zeros_like(seqs)
        targets[:, :-1] = seqs[:, 1:]

        def loss_for(mesh):
            config = _config()
            model = SASRec(config, mesh)
            dp = max(mesh.shape.get("data", 1), 1)
            params = model.init(
                jax.random.PRNGKey(0), jnp.zeros((dp, MAX_LEN), jnp.int32)
            )["params"]
            hidden = model.apply({"params": params}, jnp.asarray(seqs))
            logits = _logits(params, hidden)
            mask = (targets > 0).astype(np.float32)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(targets)
            )
            return float((ce * mask).sum() / mask.sum())

        assert abs(loss_for(_mesh(1, 1)) - loss_for(_mesh(2, 4))) < 1e-4


# ---- the table of backbones ----------------------------------------------------

#: what ``SASRecAlgorithm._config(100, maxLen)`` gave on PR 42's tree for each
#: backbone's ``examples/sequence/engine*.json``, field by field
HOW = dict(rms_eps=1e-6, seed=0, epochs=10, seq_parallel="ring", attention="auto",
           compute_dtype="bfloat16", remat=True, head_chunk=None)
EXPERTS = dict(expert_dim=64, num_experts=16, experts_per_token=4, experts_held=(0, 16),
               moe_chunk=None, learning_rate=0.0003, batch_size=16, max_len=256, **HOW)
EXAMPLES = {
    "sasrec": ("engine.json", "SASRecConfig", dict(
        max_len=64, embed_dim=32, num_heads=2, num_blocks=2, ffn_dim=64, dropout=0.0,
        learning_rate=0.001, batch_size=256, epochs=10, seed=0, seq_parallel="ring",
        attention="auto")),
    "looped": ("engine-looped.json", "LoopedConfig", dict(
        max_len=64, hidden_size=128, num_heads=4, head_dim=32, ffn_dim=352, num_layers=2,
        ut_steps=4, rope_theta=1000000.0, exit_beta=0.1, early_exit_threshold=1.0,
        learning_rate=0.0003, batch_size=64, **HOW)),
    "sparse_moe": ("engine-sparse-moe.json", "SparseMoEConfig", dict(
        hidden_size=128, num_heads=8, num_kv_heads=2, head_dim=16, num_layers=2, index_heads=4,
        index_dim=16, index_topk=64, rope_theta=10000000.0, aux_coef=0.001, **EXPERTS)),
    "hybrid_linear": ("engine-hybrid-linear.json", "HybridConfig", dict(
        hidden_size=128, num_layers=4, full_attention_interval=4, linear_key_heads=2,
        linear_value_heads=4, linear_key_dim=32, linear_value_dim=32, conv_kernel=4,
        num_heads=8, num_kv_heads=2, head_dim=32, rotary_fraction=0.25, shared_expert_dim=64,
        rope_theta=10000000.0, aux_coef=0.001, delta_chunk=64, **EXPERTS)),
    "latent_moe": ("engine-latent-moe.json", "LatentMoEConfig", dict(
        hidden_size=128, num_layers=3, dense_layers=1, num_heads=8, q_rank=96, kv_rank=32,
        nope_dim=32, rope_dim=16, value_dim=32, ffn_dim=448, shared_expert_dim=64,
        routed_scale=2.5, mtp_depth=1, mtp_coef=0.3, balance_coef=0.0001, bias_rate=0.001,
        rope_theta=32000000.0, **EXPERTS)),
    # PR 44's backbone: what the same loop gives for its example
    "window_moe": ("engine-window-moe.json", "WindowMoEConfig", dict(
        hidden_size=128,
        layer_types=("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",),
        mlp_layer_types=("dense",) + ("sparse",) * 4, heads_per_layer=(6, 8, 8, 8, 6),
        num_kv_heads=2, head_dim=32, window=64, ffn_dim=448, shared_expert_dim=64,
        routed_scale=2.5, balance_coef=0.0001, full_rope_theta=500000.0, full_rope_factor=4.0,
        full_rope_original_len=64, full_rope_beta_fast=64.0, full_rope_beta_slow=1.0,
        full_rope_attention_factor=1.1386294361119891, full_rotary_fraction=0.5,
        window_rope_theta=10000.0, **EXPERTS)),
    # PR 48's backbone: what the same loop gives for its example
    "cca_moe": ("engine-cca-moe.json", "CcaMoEConfig", dict(
        hidden_size=128, num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16, conv_time0=2,
        conv_time1=2, router_dim=32, bias_rate=0.001, rope_theta=5000000.0,
        rotary_fraction=0.5, **{**EXPERTS, "expert_dim": 128, "num_experts": 8,
                                "experts_per_token": 1, "experts_held": (0, 8),
                                "rms_eps": 1e-5})),
}


@pytest.mark.parametrize("backbone", sorted(EXAMPLES))
def test_a_backbone_is_one_module_behind_the_table(backbone):
    """Every entry of the trainer's table exports the seam's names, the
    engine's ``BACKBONES`` are the table's keys, and the engine's one loop over
    the module's ``ENGINE_PARAMS`` reads the backbone's example ``engine.json``
    to the configuration the ladders gave, values and types."""
    import dataclasses
    import inspect
    import json
    import os

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import model as seq_model
    from predictionio_tpu.models.sequence.engine import FIT_PARAMS, SASRecAlgorithm

    assert SASRecAlgorithm.BACKBONES == tuple(seq_model.BACKBONES) and len(EXAMPLES) == len(
        seq_model.BACKBONES)
    module = seq_model.BACKBONES[backbone]
    file, name, fields = EXAMPLES[backbone]
    assert module.CONFIG.__name__ == name and dataclasses.is_dataclass(module.CONFIG)
    for function, arguments in (("init_params", ["c", "rng"]), ("make_loss", ["c", "mesh"]),
                                ("score_last", ["c", "params", "seqs", "last"]),
                                ("fit_attrs", ["c", "rows", "platform"])):
        assert list(inspect.signature(getattr(module, function)).parameters) == arguments
    assert hasattr(module, "move") <= hasattr(module, "trained_labels")
    known = {f.name for f in dataclasses.fields(module.CONFIG)}
    assert set(module.ENGINE_PARAMS.values()) | set(FIT_PARAMS.values()) <= known
    assert not set(module.ENGINE_PARAMS) & set(FIT_PARAMS)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "examples", "sequence", file)) as f:
        doc = json.load(f)
    params = doc["algorithms"][0]["params"]
    assert params.get("backbone", "sasrec") == backbone
    # every name the example gives is one the engine reads
    assert set(params) - {"backbone"} <= set(module.ENGINE_PARAMS) | set(FIT_PARAMS)
    config = SASRecAlgorithm(Params(params))._config(100, doc["preparator"]["params"]["maxLen"])
    want = module.CONFIG(num_items=100, **fields)
    assert config == want and seq_model.backbone_named(config) == (backbone, module)
    assert len(fields) + 1 == len(known)          # the literal names every field
    for field in known:
        assert type(getattr(config, field)) is type(getattr(want, field)), field


@pytest.fixture()
def browsing_app(storage_env):
    """Users browse the item cycle in order (i0 -> i1 -> ... -> i11 -> i0)."""
    app_id = storage_env.get_meta_data_apps().insert(App(name="ShopApp"))
    le = storage_env.get_l_events()
    le.init_channel(app_id)
    import datetime as dt

    rng = np.random.default_rng(3)
    events = []
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    for u in range(24):
        start = rng.integers(0, N_ITEMS)
        for step in range(MAX_LEN):
            events.append(
                Event(
                    event="view", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{(start + step) % N_ITEMS}",
                    properties=DataMap({}),
                    event_time=t0 + dt.timedelta(seconds=u * 1000 + step),
                )
            )
    le.batch_insert(events, app_id=app_id)
    return app_id


class TestSequenceEngine:
    def _params(self):
        return EngineParams.from_json_obj(
            {
                "datasource": {"params": {"appName": "ShopApp",
                                          "eventNames": ["view"]}},
                "preparator": {"params": {"maxLen": MAX_LEN}},
                "algorithms": [
                    {"name": "sasrec",
                     "params": {"embedDim": 16, "numHeads": 2, "numBlocks": 1,
                                "ffnDim": 32, "epochs": 8, "batchSize": 32,
                                "learningRate": 0.01}}
                ],
            }
        )

    def test_end_to_end_next_item(self, browsing_app):
        engine = engine_factory()
        ctx = RuntimeContext()
        params = self._params()
        models = engine.train(ctx, params)
        algo = engine._algorithms(params)[0]
        # session query: after i3 -> i4 -> i5, the next view should be i6
        result = algo.predict(
            models[0], {"items": ["i3", "i4", "i5"], "num": 3}
        )
        items = [s["item"] for s in result["itemScores"]]
        assert "i6" in items, items
        # user query uses the stored history; unknown user -> empty
        assert algo.predict(models[0], {"user": "nope", "num": 3}) == {
            "itemScores": []
        }
        got = algo.predict(models[0], {"user": "u0", "num": 3})
        assert len(got["itemScores"]) == 3

    def test_eval_protocol_shapes(self, browsing_app):
        engine = engine_factory()
        ctx = RuntimeContext()
        folds = engine.data_source_class(
            self._params().data_source_params
        ).read_eval(ctx)
        assert len(folds) == 1
        train, info, pairs = folds[0]
        assert info.fold == 0
        assert pairs and all(len(actual) == 1 for _, actual in pairs)


class TestSASRecBatchPredict:
    def test_batch_matches_single(self, browsing_app):
        """batch_predict (sliced one-program scoring) must rank exactly
        like per-query predict, with cold users falling through."""
        from predictionio_tpu.models.sequence.engine import engine_factory as ef

        engine = ef()
        ctx = RuntimeContext()
        params = EngineParams.from_json_obj(
            {
                "datasource": {"params": {"appName": "ShopApp",
                                          "eventNames": ["view"]}},
                "preparator": {"params": {"maxLen": MAX_LEN}},
                "algorithms": [
                    {"name": "sasrec",
                     "params": {"embedDim": 8, "numHeads": 2, "numBlocks": 1,
                                "ffnDim": 16, "epochs": 2, "batchSize": 32}}
                ],
            }
        )
        models = engine.train(ctx, params)
        algo = engine._algorithms(params)[0]
        queries = [
            (0, {"user": "u0", "num": 3}),
            (1, {"items": ["i3", "i4"], "num": 4}),
            (2, {"user": "ghost", "num": 2}),              # cold -> []
            (3, {"user": "u1", "num": 5, "unseenOnly": False}),
            (4, {"user": "u2", "num": 3, "blackList": ["i0"]}),
        ]
        batched = dict(algo.batch_predict(models[0], queries))
        for qid, q in queries:
            single = algo.predict(models[0], q)
            assert [s["item"] for s in batched[qid]["itemScores"]] == [
                s["item"] for s in single["itemScores"]
            ], (qid, batched[qid], single)
            np.testing.assert_allclose(
                [s["score"] for s in batched[qid]["itemScores"]],
                [s["score"] for s in single["itemScores"]],
                rtol=1e-4,
            )
        assert batched[2] == {"itemScores": []}
        assert "i0" not in {s["item"] for s in batched[4]["itemScores"]}


class TestLiveHistory:
    def test_live_history_serves_fresh_sessions(self, storage_env):
        """historyMode "live": SASRec continues the user's CURRENT store
        history -- an event ingested after training changes the sequence
        the model continues, with no retrain, and the model carries no
        O(edges) history map."""
        import datetime as dt

        from predictionio_tpu.controller.engine import EngineParams
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.models.sequence import engine_factory
        from predictionio_tpu.workflow.context import RuntimeContext

        app_id = storage_env.get_meta_data_apps().insert(App(name="SeqLive"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        base = dt.datetime(2024, 1, 1, tzinfo=dt.timezone.utc)
        rng = np.random.default_rng(2)
        events = []
        k = 0
        for u in range(10):
            for i in rng.permutation(8)[:4]:
                events.append(
                    Event(event="view", entity_type="user", entity_id=f"u{u}",
                          target_entity_type="item", target_entity_id=f"i{i}",
                          event_time=base + dt.timedelta(seconds=k))
                )
                k += 1
        le.batch_insert(events, app_id=app_id)
        ep = EngineParams.from_json_obj(
            {"datasource": {"params": {"appName": "SeqLive"}},
             "preparator": {"params": {"maxLen": 8}},
             "algorithms": [{"name": "sasrec", "params": {
                 "embedDim": 8, "numHeads": 2, "numBlocks": 1, "ffnDim": 16,
                 "epochs": 2, "batchSize": 8, "historyMode": "live"}}]}
        )
        engine = engine_factory()
        model = engine.train(RuntimeContext(), ep)[0]
        assert model.histories == {} and model.history_mode == "live"
        a = engine._algorithms(ep)[0]
        out = a.predict(model, {"user": "u0", "num": 3})
        assert out["itemScores"]
        # a NEW user with a fresh session gets predictions with no retrain
        assert a.predict(model, {"user": "brand_new"}) == {"itemScores": []}
        le.insert(
            Event(event="view", entity_type="user", entity_id="brand_new",
                  target_entity_type="item", target_entity_id="i3",
                  event_time=base + dt.timedelta(hours=1)),
            app_id=app_id,
        )
        fresh = a.predict(model, {"user": "brand_new", "num": 3})
        assert fresh["itemScores"], "fresh session did not serve"


class TestPreparatorSpan:
    """``seq.pack`` carries what the benchmark's readers divide: slots and
    filled slots, and the attention tiles the flash kernel works of the rows
    (``ops/flash_attention.tiles_worked``: all users, ``maxLen``, causal)."""

    @pytest.mark.parametrize("max_len,lengths,tiles,worked", [
        (64, [3, 64, 200], 3, 3),             # one block a row: nothing to skip
        (256, [20, 128, 129, 256, 400], 20, 1 + 1 + 3 + 3 + 3),
        (300, [10, 130, 290], 27, 1 + 3 + 6),  # three blocks a row
    ])
    def test_counts(self, max_len, lengths, tiles, worked):
        from predictionio_tpu.controller import Params
        from predictionio_tpu.models.sequence.engine import (
            SequencePreparator, SequencesData,
        )
        from predictionio_tpu.obs.trace import global_tracer

        data = SequencesData(
            [np.arange(n, dtype=np.int64) % 7 for n in lengths],
            [f"u{i}" for i in range(len(lengths))], [f"i{i}" for i in range(7)])
        packed = SequencePreparator(Params({"maxLen": max_len})).prepare(None, data)
        # events first, the last maxLen of them, ids shifted by one
        for row, n in enumerate(lengths):
            assert np.count_nonzero(packed.matrix[row]) == min(n, max_len)
            assert packed.matrix[row, : min(n, max_len)].all()
        attrs = next(s for tr in global_tracer().snapshot(limit=50)["recent"]
                     for s in tr["spans"] if s["op"] == "seq.pack")["attrs"]
        assert attrs["users"] == len(lengths)
        assert attrs["slots"] == len(lengths) * max_len
        assert attrs["filled_slots"] == sum(min(n, max_len) for n in lengths)
        assert attrs["attention_tiles"] == tiles
        assert attrs["attention_tiles_worked"] == worked
