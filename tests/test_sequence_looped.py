"""The looped backbone of the sequence template against its plain reference
(``benchmarks/reference_ouro.py``), at a small size with seeded weights:
logits of every exit, the exit distribution, the loss and every gradient;
rematerialisation and the chunked head give the same numbers as without;
each wrong loop fails; the threshold picks the serving exit; the template
trains, persists, deploys and answers with ``backbone: looped``; and an
``engine.json`` without ``backbone`` trains what it trained before."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import reference_ouro as ref
from benchmarks import seeded_histories
from predictionio_tpu.models.sequence import blocks, looped
from predictionio_tpu.models.sequence.looped import LoopedConfig
from predictionio_tpu.models.sequence.model import (
    SASRec,
    SASRecConfig,
    score_next_items_batch,
    train_sasrec,
)
from predictionio_tpu.models.sequence.sasrec import logits as _logits

VOCAB, T, ROWS = 512, 32, 6
DIMS = dict(num_heads=4, head_dim=16, rope_theta=1e6, rms_eps=1e-6, ut_steps=4)
BETA = 0.1
PARAM_NAMES = [
    "embed", "final_norm", "gate_b", "gate_w", "head", "layers.n1", "layers.n2",
    "layers.n3", "layers.n4", "layers.w_down", "layers.w_gate", "layers.w_up",
    "layers.wk", "layers.wo", "layers.wq", "layers.wv",
]


def _config(**kw) -> LoopedConfig:
    base = dict(num_items=VOCAB - 1, max_len=T, hidden_size=64, num_heads=4,
                head_dim=16, ffn_dim=176, num_layers=2, ut_steps=4,
                exit_beta=BETA, compute_dtype="float32", attention="plain",
                remat=True, head_chunk=64)
    base.update(kw)
    return LoopedConfig(**base)


@pytest.fixture(scope="module")
def params():
    return seeded_histories.make_params(
        seeded_histories.param_shapes(VOCAB, 64, 64, 176, 2), seed=3)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    seq = np.zeros((ROWS, T), np.int32)
    for row in range(ROWS):
        n = rng.integers(2, T + 1)
        seq[row, :n] = rng.integers(1, VOCAB, n)
    seq[0] = rng.integers(1, VOCAB, T)  # one full row
    target = np.zeros_like(seq)
    target[:, :-1] = seq[:, 1:]
    return {"seq": seq, "target": target}


@pytest.fixture(scope="module")
def reference(params, batch):
    logits, p = ref.forward(params, batch["seq"], DIMS)
    loss, aux, grads = ref.loss_and_grads(
        params, batch["seq"], batch["target"], DIMS, BETA)
    return {"logits": np.asarray(logits), "p": np.asarray(p), "loss": float(loss),
            "exit_ce": np.asarray(aux["exit_ce"]),
            "grads": jax.tree_util.tree_map(np.asarray, grads)}


def _system(config, params, batch):
    loss_fn = looped.make_loss(config, None)
    (loss, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch, None)
    logits, p = jax.jit(
        lambda pr, s: looped.forward_exits(config, None, pr, s))(params, batch["seq"])
    return {"logits": np.asarray(logits), "p": np.asarray(p), "loss": float(loss),
            "exit_ce": np.asarray(aux["exit_ce"]), "exit_p": np.asarray(aux["exit_p"]),
            "grads": jax.tree_util.tree_map(np.asarray, grads)}


@pytest.fixture(scope="module")
def system(params, batch):
    return _system(_config(), params, batch)


def _leaf(tree, dotted: str):
    for key in dotted.split("."):
        tree = tree[key]
    return tree


def _rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# ---- the system against the reference ------------------------------------

@pytest.mark.parametrize("exit_", range(4))
def test_logits_of_every_exit_match_the_reference(system, reference, batch, exit_):
    real = batch["seq"] > 0
    diff = np.abs(system["logits"][exit_] - reference["logits"][exit_])[real]
    assert diff.max() < 2e-5


def test_exit_distribution_matches_the_reference_and_sums_to_one(
        system, reference, batch):
    real = batch["seq"] > 0
    assert np.abs(system["p"] - reference["p"])[:, real].max() < 1e-6
    np.testing.assert_allclose(system["p"].sum(axis=0), 1.0, atol=1e-6)
    # the gates are not degenerate at these weights: every exit takes a share
    assert system["exit_p"].min() > 0.02


def test_loss_and_each_exits_cross_entropy_match_the_reference(system, reference):
    assert abs(system["loss"] - reference["loss"]) < 1e-5
    np.testing.assert_allclose(system["exit_ce"], reference["exit_ce"], atol=1e-5)


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_every_gradient_matches_the_reference(system, reference, name):
    got, want = _leaf(system["grads"], name), _leaf(reference["grads"], name)
    assert np.linalg.norm(want) > 0
    assert _rel(got, want) < 1e-5


def test_the_parameter_trees_of_both_sides_are_one_layout():
    c = _config()
    ours = looped.param_shapes(c)
    theirs = seeded_histories.param_shapes(c.vocab, 64, 64, 176, 2)
    assert ours == theirs
    assert looped.count_params(c) == sum(
        int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            theirs, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
@pytest.mark.parametrize("head_chunk", [0, 32, 40, 64, None],
                         ids=["whole", "chunk32", "chunk40-padded", "chunk64", "derived"])
def test_remat_and_chunked_head_give_the_same_numbers(
        system, params, batch, remat, head_chunk):
    """40 divides no batch of these positions: they are padded up to whole
    chunks, never worked whole behind the caller's back."""
    assert (batch["seq"].size % 40) and not batch["seq"].size % 32
    other = _system(_config(remat=remat, head_chunk=head_chunk), params, batch)
    assert abs(other["loss"] - system["loss"]) < 1e-6
    for name in PARAM_NAMES:
        assert _rel(_leaf(other["grads"], name), _leaf(system["grads"], name)) < 2e-6, name


def test_the_head_chunk_follows_the_vocabulary_and_no_engine_parameter_reaches_it():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    at = lambda items: looped.head_chunk_of(LoopedConfig(num_items=items))  # noqa: E731
    assert at(49_151) == 2048 and at(1_000_000) == 128 and at(511) == 196_608
    assert 4 * 49_152 * at(49_151) == blocks.HEAD_CHUNK_BYTES
    assert looped.head_chunk_of(LoopedConfig(num_items=9, head_chunk=0)) == 0
    asked = SASRecAlgorithm(Params({
        "backbone": "looped", "computeDtype": "float32", "remat": False,
        "headChunk": 7}))._config(49_151, 256)
    assert (asked.compute_dtype, asked.remat, asked.head_chunk) == ("bfloat16", True, None)


def test_bfloat16_matmul_inputs_stay_close_to_the_reference(params, batch, reference):
    got = _system(_config(compute_dtype="bfloat16"), params, batch)
    assert abs(got["loss"] - reference["loss"]) < 5e-3
    assert 1e-4 < _rel(_leaf(got["grads"], "layers.wq"),
                       _leaf(reference["grads"], "layers.wq")) < 5e-2


def test_flash_attention_in_the_looped_layer_matches_plain(params, batch, system):
    loss_fn = looped.make_loss(_config(attention="flash"), None)
    (loss, _), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch, None)
    assert abs(float(loss) - system["loss"]) < 1e-5
    assert _rel(np.asarray(grads["layers"]["wq"]), system["grads"]["layers"]["wq"]) < 1e-4


@pytest.fixture(scope="module")
def two_block_rows(params):
    """Events-first rows of 256 slots, two of the kernel's blocks: one under
    half of ``max_len`` (its second block holds nothing: three of its four
    tiles are skipped), one that ends on the block edge, one past it, one
    full. The looped loss and its gradients under both attentions."""
    rng = np.random.default_rng(5)
    seq = np.zeros((4, 256), np.int32)
    for row, n in enumerate((100, 128, 200, 256)):
        seq[row, :n] = rng.integers(1, VOCAB, n)
    target = np.zeros_like(seq)
    target[:, :-1] = seq[:, 1:]
    rows = {"seq": seq, "target": target}
    out = {}
    for name in ("plain", "flash"):
        loss_fn = looped.make_loss(_config(max_len=256, attention=name), None)
        (loss, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            params, rows, None)
        out[name] = {"loss": float(loss), "exit_ce": np.asarray(aux["exit_ce"]),
                     "grads": jax.tree_util.tree_map(np.asarray, grads)}
    return out


def test_flash_over_two_blocks_gives_the_plain_loss(two_block_rows):
    flash, plain = two_block_rows["flash"], two_block_rows["plain"]
    assert abs(flash["loss"] - plain["loss"]) < 1e-5
    np.testing.assert_allclose(flash["exit_ce"], plain["exit_ce"], atol=1e-5)


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_flash_over_two_blocks_gives_every_plain_gradient(two_block_rows, name):
    got = _leaf(two_block_rows["flash"]["grads"], name)
    want = _leaf(two_block_rows["plain"]["grads"], name)
    assert np.linalg.norm(want) > 0
    assert _rel(got, want) < 1e-4


@pytest.fixture(scope="module")
def whole_lane_heads():
    """Two heads of 128, the width at which the flash kernel reads q, k, v as
    blocks of the projections' arrays and turns the rotary positions itself
    (``blocks.attention_operands``): ragged rows of 160 slots, two of the
    kernel's blocks once padded. The looped loss and its gradients under both
    attentions, and the leaf scopes of both steps."""
    import re

    params = seeded_histories.make_params(
        seeded_histories.param_shapes(VOCAB, 64, 256, 176, 2), seed=7)
    rng = np.random.default_rng(6)
    seq = np.zeros((3, 160), np.int32)
    for row, n in enumerate((60, 128, 160)):
        seq[row, :n] = rng.integers(1, VOCAB, n)
    target = np.zeros_like(seq)
    target[:, :-1] = seq[:, 1:]
    rows = {"seq": seq, "target": target}
    out = {}
    for name in ("plain", "flash"):
        config = _config(max_len=160, num_heads=2, head_dim=128, attention=name)
        step = jax.jit(jax.value_and_grad(looped.make_loss(config, None), has_aux=True))
        (loss, aux), grads = step(params, rows, None)
        names = re.findall(r'loc\("([^"]*)"', step.lower(params, rows, None).as_text(
            debug_info=True))
        out[name] = {"loss": float(loss), "exit_ce": np.asarray(aux["exit_ce"]),
                     "grads": jax.tree_util.tree_map(np.asarray, grads),
                     "leaves": {leaf for leaf in ("qkv", "rope", "kernel", "out")
                                if any(f"/attention/{leaf}/" in n for n in names)},
                     "operands": looped.fit_attrs(config, 3, "cpu")["attention_operands"]}
    return out


def test_heads_of_whole_lane_tiles_are_rotated_in_the_programs(whole_lane_heads):
    """No ``rope`` leaf where the kernel runs on heads of 128: the programs
    turn q and k; the plain path still rotates under it. The fit says which."""
    flash, plain = whole_lane_heads["flash"], whole_lane_heads["plain"]
    assert flash["leaves"] == {"qkv", "kernel", "out"}
    assert plain["leaves"] == {"qkv", "rope", "kernel", "out"}
    assert (flash["operands"], plain["operands"]) == (
        "in place, rotated in the programs", "plain")
    narrow = _config(attention="flash")
    assert looped.fit_attrs(narrow, 3, "cpu")["attention_operands"] == "transposed"
    assert looped.fit_attrs(_config(attention="auto"), 3, "tpu")["attention_operands"] == (
        "transposed")
    assert abs(flash["loss"] - plain["loss"]) < 1e-5
    np.testing.assert_allclose(flash["exit_ce"], plain["exit_ce"], atol=1e-5)


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_heads_of_whole_lane_tiles_give_every_plain_gradient(whole_lane_heads, name):
    got = _leaf(whole_lane_heads["flash"]["grads"], name)
    want = _leaf(whole_lane_heads["plain"]["grads"], name)
    assert np.linalg.norm(want) > 0
    assert _rel(got, want) < 1e-4


# ---- wrong loops must fail the same comparison ----------------------------

def _wrong_loop(kind: str, params, seq, targets):
    """The reference's pieces put together wrongly, one fault a kind."""
    pad_mask = seq > 0
    n_layers = params["layers"]["wq"].shape[0]
    other = seeded_histories.make_params(
        seeded_histories.param_shapes(VOCAB, 64, 64, 176, 2), seed=4)
    h = params["embed"][seq]
    normed = []
    for t in range(3 if kind == "three_passes" else 4):
        tree = other if kind == "unshared_weights" and t else params
        for l in range(n_layers):
            h = ref.layer(ref.layer_params(tree, l), h, pad_mask, DIMS)
        out = ref.rms_norm(h, params["final_norm"], DIMS["rms_eps"])
        normed.append(out)
        if kind != "unnormed_state_recycled":
            h = out
    lams = [jax.nn.sigmoid(s @ params["gate_w"] + params["gate_b"]) for s in normed]
    if kind == "no_survival_product":
        p = lams[:-1] + [1.0 - sum(lams[:-1])]
    else:
        p = ref.exit_distribution(lams)
    mask = targets > 0
    loss = 0.0
    for p_t, state in zip(p, normed):
        logits = state @ params["head"].T
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[..., None], -1)[..., 0]
        loss = loss + jnp.where(mask, p_t * ce, 0).sum() / mask.sum()
    entropy = -sum(jnp.where(mask, q * jnp.log(jnp.maximum(q, 1e-30)), 0).sum()
                   for q in p) / mask.sum()
    return loss - BETA * entropy


@pytest.mark.parametrize("kind", ["right", "three_passes", "unshared_weights",
                                  "no_survival_product", "unnormed_state_recycled"])
def test_a_wrong_loop_fails_the_comparison(system, params, batch, kind):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: _wrong_loop(kind, p, batch["seq"], batch["target"]))(params)
    loss_err = abs(float(loss) - system["loss"])
    grad_err = _rel(np.asarray(grads["layers"]["wq"]), system["grads"]["layers"]["wq"])
    # the benchmark cell's own limits for its step on the seed's draw (set on
    # the chip, PERF.md section 2), held against the same tensors: a wrong
    # loop fails by at least one
    import os

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmarks", "workloads",
                           "ouro-2.6b-d8.train-histories.json")) as f:
        limits = json.load(f)["traffic"]["correct"]["seeded"]
    last = params["layers"]["wq"].shape[0] - 1
    tensors = {
        "gate": lambda g: np.concatenate([np.ravel(g["gate_w"]), np.ravel(g["gate_b"])]),
        "final_norm": lambda g: np.asarray(g["final_norm"]),
        "wq_first": lambda g: np.asarray(g["layers"]["wq"][0]),
        "w_down_last": lambda g: np.asarray(g["layers"]["w_down"][last]),
        "head_rows": lambda g: np.asarray(g["head"]),
    }
    assert sorted(tensors) == sorted(limits["grad_rel_err_limits"])
    over = [name for name, pick in tensors.items()
            if _rel(pick(grads), pick(system["grads"])) > limits["grad_rel_err_limits"][name]]
    if loss_err > limits["loss_abs_err_limit"]:
        over.append("loss")
    if kind == "right":  # the pieces, put together rightly, pass
        assert loss_err < 1e-5 and grad_err < 1e-5 and not over
    else:
        assert loss_err > 1e-3 or grad_err > 1e-2, (loss_err, grad_err)
        assert grad_err > 1e-2, grad_err
        assert over, "a wrong loop passed every limit of the cell"


# ---- serving ---------------------------------------------------------------

def test_threshold_one_scores_from_the_last_pass(params, batch, reference):
    config = _config()
    prefixes = [batch["seq"][0][:9], batch["seq"][1][:2]]
    scores = score_next_items_batch(params, config, prefixes)
    assert scores.shape == (2, VOCAB - 1)
    seqs = np.zeros((2, T), np.int32)
    for row, prefix in enumerate(prefixes):
        seqs[row, : len(prefix)] = prefix
    logits, _ = ref.forward(params, seqs, DIMS)
    want = np.stack([np.asarray(logits)[-1, 0, 8, 1:], np.asarray(logits)[-1, 1, 1, 1:]])
    np.testing.assert_allclose(scores, want, atol=2e-5)


@pytest.mark.parametrize("threshold", [0.3, 0.6, 0.9])
def test_a_lower_threshold_scores_from_the_first_pass_that_reaches_it(
        params, batch, threshold):
    config = _config(early_exit_threshold=threshold)
    prefixes = [row[row > 0] for row in batch["seq"]]
    scores = score_next_items_batch(params, config, prefixes)
    logits, p = ref.forward(params, batch["seq"], DIMS)
    logits, reached = np.asarray(logits), np.cumsum(np.asarray(p), axis=0)
    exits = []
    for row, prefix in enumerate(prefixes):
        at = len(prefix) - 1
        ok = reached[:, row, at] >= threshold
        ok[-1] = True
        exits.append(int(np.argmax(ok)))
        np.testing.assert_allclose(scores[row], logits[exits[-1], row, at, 1:], atol=2e-5)
    # a higher threshold keeps a row in the loop longer
    assert max(exits) >= {0.3: 0, 0.6: 1, 0.9: 2}[threshold]


# ---- the template ----------------------------------------------------------

def _cyclic(n=96, n_items=12, t=8, seed=0):
    rng = np.random.default_rng(seed)
    out = np.zeros((n, t), np.int32)
    for row in range(n):
        out[row] = (rng.integers(0, n_items) + np.arange(t)) % n_items + 1
    return out


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def test_the_looped_backbone_learns_a_cycle_and_reports_its_fit():
    from predictionio_tpu.obs.trace import global_tracer

    config = LoopedConfig(num_items=12, max_len=8, hidden_size=32, num_heads=2,
                          head_dim=16, ffn_dim=64, num_layers=1, ut_steps=4,
                          learning_rate=0.01, batch_size=32, epochs=12,
                          attention="plain", head_chunk=64)
    params, losses = train_sasrec(config, _cyclic(), _mesh(), log_every=1)
    assert losses[-1] < 0.6 * losses[0]
    hits = 0
    for start in range(12):
        prefix = (start + np.arange(4)) % 12 + 1
        scores = score_next_items_batch(params, config, [prefix])[0]
        hits += int(np.argmax(scores) == (start + 4) % 12)
    assert hits >= 10
    span = next(s for tr in global_tracer().snapshot(limit=50)["recent"]
                for s in tr["spans"] if s["op"] == "seq.fit")
    attrs = span["attrs"]
    assert attrs["backbone"] == "looped" and attrs["passes"] == 4
    assert attrs["layers"] == 1 and attrs["rematerialised"] == "layer"
    assert attrs["selection_kept_bytes"] == 0      # the sparse backbone's alone
    assert attrs["param_bytes"] == 4 * looped.count_params(config)
    assert attrs["state_bytes"] >= 4 * attrs["param_bytes"]
    assert len(attrs["exit_p"]) == 4 and abs(sum(attrs["exit_p"]) - 1) < 1e-5
    assert "chunks of 64" in attrs["head"]


def test_without_backbone_the_template_trains_bit_for_bit_what_it_trained():
    """``train_sasrec`` on a ``SASRecConfig`` against the loop as it stood
    before there was a second backbone, written out here."""
    config = SASRecConfig(num_items=12, max_len=8, embed_dim=16, num_heads=2,
                          num_blocks=1, ffn_dim=32, learning_rate=0.01,
                          batch_size=32, epochs=2, seed=5, dropout=0.1,
                          attention="plain")
    sequences = _cyclic()
    got, _ = train_sasrec(config, sequences, _mesh())

    model = SASRec(config, _mesh())
    rng = jax.random.PRNGKey(config.seed)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    optimizer = optax.adam(config.learning_rate)
    opt_state = optimizer.init(params)

    def loss_fn(p, batch, key):
        hidden = model.apply({"params": p}, batch["seq"], deterministic=False,
                             rngs={"dropout": key})
        mask = (batch["target"] > 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            _logits(p, hidden), batch["target"])
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    @jax.jit
    def step(p, state, batch, key):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch, key)
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state, loss

    inputs = sequences.astype(np.int32)
    targets = np.zeros_like(inputs)
    targets[:, :-1] = inputs[:, 1:]
    np_rng = np.random.default_rng(config.seed)
    n_step = 0
    for _ in range(config.epochs):
        order = np_rng.permutation(len(inputs))
        for start in range(0, len(inputs), config.batch_size):
            take = order[start:start + config.batch_size]
            params, opt_state, _ = step(
                params, opt_state, {"seq": inputs[take], "target": targets[take]},
                jax.random.fold_in(rng, n_step))
            n_step += 1
    want = jax.tree_util.tree_leaves_with_path(params)
    have = dict(jax.tree_util.tree_leaves_with_path(got))
    assert len(want) == len(have)
    for path, array in want:
        assert np.array_equal(np.asarray(array), have[path]), jax.tree_util.keystr(path)


def test_the_algorithm_reads_sasrec_defaults_as_before_and_the_looped_widths():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    plain = SASRecAlgorithm(Params({}))._config(12, 64)
    assert plain == SASRecConfig(num_items=12)
    loop = SASRecAlgorithm(Params({
        "backbone": "looped", "hiddenSize": 2048, "numHeads": 16, "headDim": 128,
        "ffnDim": 5632, "numLayers": 8, "utSteps": 4, "ropeTheta": 1000000,
        "exitBeta": 0.1}))._config(49151, 256)
    assert isinstance(loop, LoopedConfig) and loop.vocab == 49152
    assert looped.count_params(loop) == 612_438_017
    with pytest.raises(ValueError, match="backbone"):
        SASRecAlgorithm(Params({"backbone": "mamba"}))._config(12, 64)


def _post(url, body):
    import urllib.request

    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def test_train_deploy_query_with_the_looped_backbone(storage_env, tmp_path):
    import datetime as dt
    import os

    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.create_server import create_query_server
    from predictionio_tpu.workflow.json_extractor import load_engine_variant

    app_id = storage_env.get_meta_data_apps().insert(App(name="LoopShop"))
    le = storage_env.get_l_events()
    le.init_channel(app_id)
    rng = np.random.default_rng(3)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    le.batch_insert([
        Event(event="view", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{(start + step) % 12}",
              properties=DataMap({}),
              event_time=t0 + dt.timedelta(seconds=u * 1000 + step))
        for u in range(48) for start in [int(rng.integers(0, 12))] for step in range(8)
    ], app_id=app_id)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "examples", "sequence", "engine-looped.json")) as f:
        variant = json.load(f)
    variant["datasource"]["params"].update(appName="LoopShop", eventNames=["view"])
    variant["preparator"]["params"]["maxLen"] = 8
    algo = variant["algorithms"][0]["params"]
    assert algo["backbone"] == "looped"
    algo.update(hiddenSize=32, numHeads=2, headDim=16, ffnDim=64, numLayers=1,
                epochs=12, batchSize=32, learningRate=0.01, attention="plain")
    variant["sparkConf"] = {"pio.mesh_shape": [1, 1], "pio.mesh_axes": ["data", "seq"]}
    path = tmp_path / "engine.json"
    path.write_text(json.dumps(variant))
    loaded = load_engine_variant(str(path))
    run_train(loaded)
    thread, _ = create_query_server(loaded, host="127.0.0.1", port=0)
    thread.start()
    try:
        base = f"http://127.0.0.1:{thread.port}"
        session = _post(f"{base}/queries.json", {"items": ["i3", "i4", "i5"], "num": 3})
        user = _post(f"{base}/queries.json", {"user": "u0", "num": 3})
    finally:
        thread.stop()
    assert "i6" in [s["item"] for s in session["itemScores"]], session
    assert len(user["itemScores"]) == 3
