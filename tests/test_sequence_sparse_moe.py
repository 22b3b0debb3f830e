"""The sparse backbone of the sequence template (learned sparse attention, a
routed mixture of experts of which the program holds a share) against its
plain reference (``benchmarks/reference_keye.py``), at a small size with
seeded weights: loss, auxiliary loss and every gradient with a history
several times ``index_topk`` long; the three programs of
``ops/sparse_attention.py`` in interpret mode against their plain twins; the
k-th largest against ``jax.lax.top_k``, ties included; the eight shares of a
layer add up to the whole layer; the experts' passes under an even, a skewed,
a whole and an empty routing, their rows brought back by position and by runs
(``ops/run_sum.py`` in interpret mode against ``_sum_by_position``, a float32
sum of float32 rows); the selection kept from the forward pass, one bit
a pair, and read by the rematerialised layer in place of working it again;
the engine takes the backbone by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_keye as ref
from benchmarks import seeded_histories
from predictionio_tpu.models.sequence import experts as experts_module, sparse_moe
from predictionio_tpu.models.sequence.model import (
    fit_attrs, make_fit, score_next_items_batch, train_sasrec,
)
from predictionio_tpu.models.sequence.sparse_moe import SparseMoEConfig
from predictionio_tpu.ops import run_sum
from predictionio_tpu.ops import sparse_attention as sa

VOCAB, T, ROWS, TOPK = 256, 64, 3, 16
DIMS = dict(num_heads=4, num_kv_heads=2, head_dim=16, index_heads=2, index_dim=8,
            index_topk=TOPK, experts_per_token=2, experts_held=(2, 6),
            rope_theta=1e7, rms_eps=1e-6, query_block=16)
AUX = 0.01


def _config(**kw) -> SparseMoEConfig:
    base = dict(num_items=VOCAB - 1, max_len=T, hidden_size=32, num_heads=4,
                num_kv_heads=2, head_dim=16, expert_dim=24, num_experts=8,
                experts_per_token=2, experts_held=(2, 6), num_layers=2,
                index_heads=2, index_dim=8, index_topk=TOPK, aux_coef=AUX,
                compute_dtype="float32", attention="plain", head_chunk=64, moe_chunk=64)
    base.update(kw)
    return SparseMoEConfig(**base)


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


@pytest.fixture(scope="module")
def params():
    drawn = seeded_histories.make_params(sparse_moe.param_shapes(_config()), seed=5)
    # an indexer and a router wide enough that neither choice is near a tie
    for name in drawn["indexer"]:
        drawn["indexer"][name] = drawn["indexer"][name] * 10
    drawn["layers"]["router"] = drawn["layers"]["router"] * 10
    return drawn


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(11)
    seq = rng.integers(1, VOCAB, (ROWS, T)).astype(np.int32)
    seq[1, 40:] = 0  # a padded tail: routed nowhere, counted nowhere
    targets = np.zeros_like(seq)
    targets[:, :-1] = seq[:, 1:]
    return seq, targets


def _reference(params, batch, dims=DIMS, how=ref.SOUND):
    seq, targets = (jnp.asarray(a) for a in batch)
    return jax.jit(lambda p: ref.loss_and_grads(p, seq, targets, dims, AUX, how))(params)


@pytest.fixture(scope="module")
def sound(params, batch):
    return _reference(params, batch)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(k.key for k in path): np.asarray(a) for path, a in leaves}


@pytest.mark.parametrize("attention", ["plain", "flash"])
def test_loss_auxiliary_loss_and_every_gradient_match_the_reference(
        params, batch, sound, attention):
    """``T`` is four times ``index_topk``: three quarters of the queries read a
    selection. "flash" is the three Pallas programs, interpreted."""
    config = _config(attention=attention)
    loss_fn = sparse_moe.make_loss(config, _mesh())
    (loss, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}, None)
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 2e-5
    assert abs(float(aux["ce"]) - float(want_aux["ce"])) < 2e-5
    assert abs(float(aux["aux_loss"]) - float(want_aux["aux_loss"])) < 2e-5
    assert float(aux["aux_loss"]) > 1.0       # E sum f P is K when balanced
    have, want_flat = _flat(grads), _flat(want_grads)
    assert sorted(have) == sorted(want_flat)
    for name, g in want_flat.items():
        if name.startswith("indexer."):
            assert not have[name].any() and not g.any(), name
            continue
        scale = np.abs(g).max()
        assert scale > 0, name
        assert np.abs(have[name] - g).max() < 2e-3 * scale, name
    real = int((batch[0] > 0).sum())
    assert int(aux["moe_assignments"]) == 2 * 2 * real     # layers x K x real tokens
    assert 0 < int(aux["moe_held_assignments"]) < int(aux["moe_assignments"])
    assert int(aux["moe_dropped"]) == 0
    lengths = (batch[0] > 0).sum(axis=1)
    causal = int(sum(n * (n + 1) // 2 for n in lengths))
    selected = int(sum(min(t + 1, TOPK) for n in lengths for t in range(n)))
    assert int(aux["causal_pairs"]) == 2 * causal
    assert int(aux["selected_pairs"]) == 2 * selected


@pytest.mark.parametrize("control,tensor", [
    ({"selection": "window"}, "layers.wq"), ({"renormalise": False}, "layers.w_down"),
    ({"precision": "bfloat16"}, "layers.router")])
def test_each_control_of_the_reference_reads_other_gradients(
        params, batch, sound, control, tensor):
    """What the benchmark's ``--control 1`` plants, at this size: each moves a
    gradient of the path it touches by far more than the program differs."""
    sound = _flat(sound[2])[tensor]
    wrong = _flat(_reference(params, batch, how={**ref.SOUND, **control})[2])[tensor]
    assert np.linalg.norm(wrong - sound) > 1e-2 * np.linalg.norm(sound)


#: case -> (how the second step is worked, the most its loss may differ from
#: the default step's, the most a gradient may, as a share of its largest entry)
REWORKED = {"chunks": ({"remat": False, "head_chunk": 0, "moe_chunk": 4 * T}, 1e-5, 1e-4),
            # the rematerialised layer reads the forward pass's own selection, bit
            # for bit (the packing test below): the same loss, and gradients that
            # differ by how XLA fuses a layer worked again, float32 rounding
            # (1.3e-7 read here), where one query's selection changed by one key
            # moves every trained gradient by 1.5e-2 or more
            "selection-kept": ({"remat": False}, 0.0, 2e-6)}


@pytest.mark.parametrize("case", list(REWORKED))
def test_remat_and_chunks_change_nothing(params, batch, case):
    reworked, loss_tolerance, tolerance = REWORKED[case]
    feed = {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}
    values = []
    for how in ({}, reworked):
        config = _config(**how)
        assert (sparse_moe.selection_kept_bytes(config, ROWS) > 0) == config.remat
        fn = sparse_moe.make_loss(config, _mesh())
        (loss, _), grads = jax.jit(jax.value_and_grad(fn, has_aux=True))(params, feed, None)
        values.append((float(loss), _flat(grads)))
    assert abs(values[0][0] - values[1][0]) <= loss_tolerance
    for name, g in values[0][1].items():
        assert np.abs(g - values[1][1][name]).max() <= tolerance * max(np.abs(g).max(), 1e-12), name


def _top_ks(jaxpr, layers: int, scan=None):
    """``(scan, k)`` of every ``top_k`` of ``jaxpr`` and of the programs nested
    in it; ``scan``: which scan over the layers holds it, ``"forward"``,
    ``"backward"`` (a reversed one) or ``None``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            yield scan, eqn.params["k"]
        inside = scan
        if scan is None and eqn.primitive.name == "scan" and eqn.params["length"] == layers:
            inside = "backward" if eqn.params["reverse"] else "forward"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _top_ks(sub, layers, inside)


def test_the_selection_is_worked_in_the_forward_scan_and_not_in_the_backward_scan(params, batch):
    """The gradient of a two-layer step as JAX hands it to XLA: the forward
    scan's body holds the selection's top-k (``index_topk`` wide, the plain
    path's primitive) and keeps the packed mask; the backward scan's body
    starts from the kept bits and holds the router's top-k alone."""
    feed = {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}
    fn = sparse_moe.make_loss(_config(), _mesh())
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: fn(p, feed, None)[0]))(params).jaxpr
    assert sorted(_top_ks(jaxpr, 2)) == [("backward", 2), ("forward", 2), ("forward", TOPK)]
    kept = [v.aval for eqn in jaxpr.eqns if eqn.primitive.name == "scan"
            and not eqn.params["reverse"] for v in eqn.outvars if v.aval.dtype == jnp.uint8]
    assert [(a.shape, a.size) for a in kept] == [
        ((2, ROWS, T // 8, T), sparse_moe.selection_kept_bytes(_config(), ROWS))]


@pytest.mark.parametrize("t,topk", [(64, 16), (256, 48), (512, 200), (1024, 128), (20, 6)],
                         ids=["64", "256-a-query-tile", "512-a-key-tile", "1024", "20-not-in-eights"])
def test_packing_the_selection_and_unpacking_it_returns_the_mask(t, topk):
    """Eight query rows a byte, the keys left where they are; a selection with
    ties at the threshold, at the attention programs' tile widths and at a
    length that is no multiple of eight (the last byte's spare rows are 0)."""
    rng = np.random.default_rng(t)
    scores = jnp.asarray(np.round(rng.standard_normal((2, t, t)) / 0.5) * 0.5 + 0.0, jnp.float32)
    mask = sa.select_topk_plain(scores, topk)
    assert mask.dtype == jnp.int8 and int(mask.sum(-1).max()) == topk
    packed = sparse_moe.pack_rows(mask)
    assert packed.dtype == jnp.uint8 and packed.shape == (2, -(-t // 8), t)
    want = np.zeros((2, -(-t // 8) * 8, t), np.uint8)
    want[:, :t] = np.asarray(mask)
    assert (np.asarray(packed) == np.packbits(
        want.reshape(2, -1, 8, t), axis=2, bitorder="little")[:, :, 0]).all()
    back = sparse_moe.unpack_rows(packed, t)
    assert back.dtype == jnp.int8 and (np.asarray(back) == np.asarray(mask)).all()


# ---- the three programs ------------------------------------------------------

def _scores(rng, b=2, t=256, quantum=None):
    q = jnp.asarray(rng.standard_normal((b, t, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, t, 4)), jnp.float32)
    scores = sa.index_scores_plain(q, k, w)
    if quantum:
        scores = jnp.round(scores / quantum) * quantum + 0.0     # no -0.0: top_k orders it
    return q, k, w, scores


def test_index_scores_program_matches_its_twin_on_the_causal_tiles():
    q, k, w, want = _scores(np.random.default_rng(0))
    have = sa.index_scores(q, k, w, block_q=64, block_k=128, interpret=True)
    causal = np.tril(np.ones((256, 256), bool))
    assert np.abs(np.asarray(have) - np.asarray(want))[:, causal].max() < 1e-4


@pytest.mark.parametrize("quantum", [None, 0.5, 4.0], ids=["distinct", "ties", "mostly-ties"])
@pytest.mark.parametrize("topk", [48, 200])
def test_the_kth_largest_matches_top_k_ties_included(quantum, topk):
    """Against ``jax.lax.top_k`` over the causal scores, which takes the lower
    index of equal values: the program's bisection and its cut of the ties,
    and the plain twin's threshold, select exactly top_k's positions."""
    _, _, _, scores = _scores(np.random.default_rng(1), quantum=quantum)
    t = scores.shape[-1]
    causal = np.tril(np.ones((t, t), bool))
    masked = jnp.where(causal, scores, -jnp.inf)
    _, index = jax.lax.top_k(masked, topk)
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(index), True, axis=-1)
    want &= causal
    if quantum:
        threshold = np.sort(np.where(causal, scores, -np.inf), axis=-1)[..., -topk]
        assert ((np.asarray(scores) == threshold[..., None]) & causal).sum(-1).max() > 1
    twin = np.asarray(sa.select_topk_plain(scores, topk)).astype(bool)
    program = np.asarray(sa.select_topk(scores, topk, rows=32, chunk=128,
                                        interpret=True)).astype(bool)
    assert (twin == want).all()
    assert (program == want).all()
    assert (program.sum(-1)[:, topk:] == topk).all()
    assert (program.sum(-1)[:, :topk] == np.arange(1, topk + 1)).all()


def test_attention_program_matches_masked_plain_attention_forward_and_gradients():
    """Grouped heads (4 query heads a key head), a selection, ``T`` of four
    query blocks and two key blocks; bfloat16 inputs as the backbone hands
    them, the twin on the same inputs."""
    rng = np.random.default_rng(2)
    b, t, h, kv, d = 2, 256, 8, 2, 32
    _, _, _, scores = _scores(rng)
    mask = sa.select_topk_plain(scores, 64)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for shape in ((b, t, h, d), (b, t, kv, d), (b, t, kv, d)))
    weight = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)

    def total(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * weight).sum()

    program = lambda q, k, v: sa.sparse_attention(q, k, v, mask, 64, 128, True)  # noqa: E731
    twin = lambda q, k, v: sa.sparse_attention_plain(q, k, v, mask)  # noqa: E731
    have, want = program(q, k, v), twin(q, k, v)
    assert np.abs(np.asarray(have, np.float32) - np.asarray(want, np.float32)).max() < 2e-2
    have_g = jax.grad(total(program), (0, 1, 2))(q, k, v)
    want_g = jax.grad(total(twin), (0, 1, 2))(q, k, v)
    for a, g in zip(have_g, want_g):
        a, g = np.asarray(a, np.float32), np.asarray(g, np.float32)
        assert np.linalg.norm(a - g) < 2e-2 * np.linalg.norm(g)
    # float32 inputs: the same arithmetic to rounding
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    assert np.abs(np.asarray(program(*f32)) - np.asarray(twin(*f32))).max() < 1e-5
    for a, g in zip(jax.grad(total(program), (0, 1, 2))(*f32),
                    jax.grad(total(twin), (0, 1, 2))(*f32)):
        assert np.abs(np.asarray(a) - np.asarray(g)).max() < 1e-4 * np.abs(np.asarray(g)).max()


#: heads, key-value heads, T, block_q, block_k, how the selection is drawn
BACKWARD_CASES = {
    "empty-tiles-8-heads-a-kv-head": (8, 1, 512, 64, 64, "window"),
    "query-block-over-key-block": (4, 2, 256, 128, 64, "drawn"),
    "key-block-over-query-block": (4, 2, 256, 32, 128, "drawn"),
    "one-block": (4, 2, 48, sa.BLOCK_Q, sa.BLOCK_K, "drawn"),
    "several-kv-heads-a-step": (4, 4, 128, 32, 64, "drawn"),
}


@pytest.mark.parametrize("case", list(BACKWARD_CASES))
def test_the_backward_program_gives_the_twins_three_gradients(case):
    """The one backward program (a tile's ``s``, ``p`` and ``ds`` once; ``dq``
    a query block's sum, ``dk`` and ``dv`` the whole row's, a tile adding into
    its key block's rows) against the plain twin's gradients, in float32: with
    tiles under the diagonal that select nothing (a window of 40 keys: the
    last tile of queries, twice the forward program's, sees none of the first
    two key blocks), with tiles whose two edges differ either way (the
    diagonal's clamp), at one block, and with several key-value heads a step."""
    h, kv, t, bq, bk, drawn = BACKWARD_CASES[case]
    rng = np.random.default_rng(len(case))
    b, d = 2, 32
    pos = np.arange(t)
    causal = pos[None, :] <= pos[:, None]
    if drawn == "window":
        mask = np.broadcast_to(causal & (pos[None, :] > pos[:, None] - 40), (b, t, t))
        assert sa.backward_query_block(1, h, d, d, t, 4, True, bq, bk) == 2 * bq
        assert not mask[:, -2 * bq:, :2 * bk].any()
    else:
        mask = (rng.random((b, t, t)) < 0.4) & causal | np.eye(t, dtype=bool)
    mask = jnp.asarray(mask, jnp.int8)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for shape in ((b, t, h, d), (b, t, kv, d), (b, t, kv, d)))
    weight = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    program = lambda q, k, v: sa.sparse_attention(q, k, v, mask, bq, bk, True)  # noqa: E731
    twin = lambda q, k, v: sa.sparse_attention_plain(q, k, v, mask)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        have = jax.grad(lambda *a: (program(*a) * weight).sum(), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (twin(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    for name, a, g in zip("qkv", have, want):
        assert a.shape == g.shape and a.dtype == g.dtype, name
        assert np.abs(np.asarray(a - g)).max() < 1e-4 * np.abs(np.asarray(g)).max(), name


def test_the_backward_program_takes_its_heads_a_step_from_the_shapes():
    """The cell's shape (4 key-value heads of 8 query heads, 128 + 128, a row
    of 8,192, bfloat16, a mask's tile): one key-value head a step, its ``dk``
    and ``dv`` 8.4 MB of the 17 the step holds; the fit's span says so, and
    says that off the TPU no program runs."""
    cell = _config(num_heads=32, num_kv_heads=4, head_dim=128, max_len=8192,
                   compute_dtype="bfloat16", attention="auto")
    assert sparse_moe.attention_backward_heads_per_step(cell) == 1
    held = sa.backward_step_bytes(1, 8, 128, 128, 8192, 2, True)
    assert 4 * 8192 * 256 < held < 18e6
    # and a tile of 512 queries, twice the forward program's: 25 MB
    assert sa.backward_query_block(1, 8, 128, 128, 8192, 2, True, sa.BLOCK_Q, sa.BLOCK_K) == 512
    assert sa.backward_query_block(1, 8, 128, 128, 768, 2, True, sa.BLOCK_Q, sa.BLOCK_K) == 256
    attrs = fit_attrs(cell, 4, 8, 2, "cpu")
    assert (attrs["attention_backward_programs"], attrs["attention_backward_heads_per_step"]) == (
        0, 1)
    assert fit_attrs(cell, 4, 8, 2, "tpu")["attention_backward_programs"] == 1


# ---- the experts -------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_whole_layer(params):
    """Eight programs, each holding one of 8 experts with the same router,
    add up to the reference's uncut layer (every expert held)."""
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    real = jnp.asarray(np.arange(96) < 90)
    whole_shapes = sparse_moe.param_shapes(_config(experts_held=(0, 8)))["layers"]
    drawn = seeded_histories.make_params(
        {k: whole_shapes[k][1:] for k in ("router", "w_gate", "w_up", "w_down")}, seed=9)
    drawn["router"] = drawn["router"] * 10
    dims = {**DIMS, "experts_held": (0, 8)}
    with jax.default_matmul_precision("highest"):
        _, experts, gates = ref.routing(drawn, u, dims, ref.SOUND)
        want = ref.experts_part(drawn, u, experts, gates, real, dims)
    total, held = 0.0, 0
    for e in range(8):
        config = _config(experts_held=(e, e + 1))
        share = {"router": drawn["router"],
                 **{k: drawn[k][e:e + 1] for k in ("w_gate", "w_up", "w_down")}}
        y, stats = experts_module.moe(config, "cpu", u, share, real)
        assert int(stats["dropped"]) == 0
        held += int(stats["held_assignments"])
        total = total + y
    assert held == int(stats["assignments"]) == 2 * 90
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5
    assert not np.asarray(total)[90:].any()      # a padded slot gets nothing


#: routing -> (experts held, real tokens of the 192, passes run of pass_plan's)
ROUTINGS = {"even": ((2, 4), 186, 1), "skewed": ((2, 4), 192, 3),
            "all-held": ((0, 16), 186, 1), "none-held": ((2, 4), 186, 0)}


#: how a pass's rows come back onto their tokens -> the ``attention`` that asks for it
SUM_PATHS = {"positions": "plain", "runs": "flash"}


@pytest.mark.parametrize("path", list(SUM_PATHS))
@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_passes_give_the_references_output_and_gradients_whatever_the_router_sends(
        routing, path):
    """192 tokens, 2 of 16 experts a token, experts 2 and 3 held: a pass works
    128 sorted rows (twice the even share of 48, in whole 128s) and three
    passes cover the worst case of 384. **even**: a seeded router, one pass
    runs. **skewed**: every token's first choice is expert 2 and its second
    expert 3 (the tokens are positive and those columns of the router outweigh
    every other), eight times the even share: every pass runs, the second
    holds rows of both experts, and every row is worked. **all-held**: the
    bound is the worst case, one pass. **none-held**: no token is sent to a
    held expert: no pass runs, zeros out, finite gradients. Output and the
    gradients to the tokens, the router and the three expert matrices against
    ``reference_keye.experts_part``, with the rows brought back by position
    and by runs (the program interpreted)."""
    held, n_real, run = ROUTINGS[routing]
    n = 3 * T
    config = _config(num_experts=16, experts_held=held, moe_chunk=None,
                     attention=SUM_PATHS[path])
    assert experts_module.sum_path(config, "cpu") == path
    dims = {**DIMS, "experts_held": held}
    assert experts_module.moe_chunk_of(config) >= n      # one chunk of tokens
    assert experts_module.pass_plan(config, n) == ((384, 1) if routing == "all-held" else (128, 3))
    shapes = sparse_moe.param_shapes(config)["layers"]
    layer = seeded_histories.make_params(
        {k: shapes[k][1:] for k in ("router", "w_gate", "w_up", "w_down")}, seed=13)
    layer["router"] = layer["router"] * 10
    heavy = np.abs(layer["router"]).sum(axis=1)
    if routing == "skewed":
        layer["router"][:, 2], layer["router"][:, 3] = heavy + 1.0, heavy + 0.5
    if routing == "none-held":
        layer["router"][:, 2:4] = -heavy[:, None] - 1.0
    rng = np.random.default_rng(6)
    u = jnp.abs(jnp.asarray(rng.standard_normal((n, 32)), jnp.float32))
    weight = jnp.asarray(rng.standard_normal((n, 32)), jnp.float32)
    real = jnp.asarray(np.arange(n) < n_real)

    def program(u, layer):
        y, stats = experts_module.moe(config, "cpu", u, layer, real)
        return (y * weight).sum(), (y, stats)

    def reference(u, layer):
        _, experts, gates = ref.routing(layer, u, dims, ref.SOUND)
        y = ref.experts_part(layer, u, experts, gates, real, dims)
        return (y * weight).sum(), (y, experts)

    (_, (y, stats)), have = jax.jit(jax.value_and_grad(program, (0, 1), has_aux=True))(u, layer)
    with jax.default_matmul_precision("highest"):
        (_, (want_y, experts)), want = jax.jit(
            jax.value_and_grad(reference, (0, 1), has_aux=True))(u, layer)
    assert int(stats["dropped"]) == 0
    assert (int(stats["passes"]), int(stats["passes_run"])) == (1 if routing == "all-held" else 3, run)
    # what the forward sums read: a pass's rows by runs, every token's slots by position
    bound = experts_module.pass_plan(config, n)[0]
    assert int(stats["sum_slots"]) == run * n * 2
    assert int(stats["sum_rows"]) == run * (bound if path == "runs" else n * 2)
    sent = np.isin(np.asarray(experts)[:n_real], np.arange(*held)).sum()
    assert int(stats["held_assignments"]) == sent
    if routing == "skewed":
        assert (np.asarray(experts) == [2, 3]).all()
        assert int(stats["held_load_max"]) == n and sent == 2 * n
    if routing == "none-held":
        assert sent == 0 and not np.asarray(y).any()
    else:
        assert np.abs(np.asarray(want_y)).max() > 1e-3
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 1e-5
    assert not np.asarray(y)[n_real:].any()          # a padded slot gets nothing
    have, want = _flat({"u": have[0], **have[1]}), _flat({"u": want[0], **want[1]})
    for name, g in want.items():
        assert np.isfinite(have[name]).all(), name
        assert np.abs(have[name] - g).max() <= 1e-4 * max(np.abs(g).max(), 1e-6), name
        assert routing == "none-held" or np.abs(g).max() > 0, name


def _a_pass(n, slots, rows, live, pattern, seed):
    """A pass as ``_one_pass`` holds it, drawn: ``live`` of its ``rows`` rows
    belong each to another slot of the ``n`` tokens' ``slots``. ``(row, pos,
    mine)``: a row's assignment ``[rows]``, and for every slot the row that
    holds it and whether one does. ``pattern`` "full": token 3 holds a row in
    every slot and tokens 2 and 4 none."""
    rng = np.random.default_rng(seed)
    free = np.arange(n * slots).reshape(n, slots)
    if pattern == "full":
        taken = np.concatenate([free[3], rng.choice(
            np.delete(free, [2, 3, 4], axis=0).reshape(-1), live - slots, replace=False)])
    else:
        taken = rng.choice(free.reshape(-1), live, replace=False)
    row = np.zeros(rows, np.int32)
    row[:live] = rng.permutation(taken)          # the sort's order is by expert, not by token
    row[live:] = rng.integers(0, n * slots, rows - live)
    pos, mine = np.zeros(n * slots, np.int32), np.zeros(n * slots, bool)
    pos[row[:live]], mine[row[:live]] = np.arange(live), True
    return row, pos.reshape(n, slots), mine.reshape(n, slots)


#: (tokens, slots a token, rows a pass, live rows, pattern): the three cells'
#: ``R / n`` and ``K`` with about half the rows live, then the edges. At 640
#: tokens and 800 rows the blocks are 256 x 256: three token blocks, a last row
#: block of 32 rows, and row blocks that hold rows of two token blocks
RUN_SHAPES = {
    "sparse-cell": (512, 8, 1024, 500, "random"),
    "hybrid-cell": (512, 10, 640, 330, "random"),
    "latent-cell": (512, 8, 512, 250, "random"),
    "ragged-rows": (640, 8, 800, 700, "random"),
    "every-row-live": (320, 4, 384, 384, "random"),
    "a-token-with-every-slot": (64, 8, 128, 40, "full"),
    "no-live-row": (320, 8, 512, 0, "random"),
}


@pytest.mark.parametrize("rows_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [True, False], ids=["gates", "unit"])
@pytest.mark.parametrize("shape", list(RUN_SHAPES))
def test_the_sum_by_runs_is_the_sum_by_position(shape, weighted, rows_dtype):
    """``_sum_by_runs`` (one gather of the pass's rows into token order, one
    program, interpreted here) against ``_sum_by_position`` on a drawn pass:
    weighted by the gates as ``_give_back`` sums, by 0 and 1 as ``_take_rows``'
    transpose does; the rows past the live ones hold NaN, as a grouped matmul
    may leave them. A float32 row arrives whole: the two sums differ by the
    order of a token's additions alone, and the same rows rounded to bfloat16
    on their way are another result, far outside that."""
    n, slots, rows, live, pattern = RUN_SHAPES[shape]
    row, pos, mine = _a_pass(n, slots, rows, live, pattern, seed=len(shape))
    rng = np.random.default_rng(3)
    values = rng.standard_normal((rows, 128)).astype(np.float32)
    values[live:] = np.nan
    values = jnp.asarray(values).astype(rows_dtype)
    gates = (jnp.asarray(rng.uniform(0.05, 1.0, (n, slots)), jnp.float32) if weighted
             else jnp.ones((n, slots), jnp.float32))
    is_live = jnp.arange(rows) < live
    want = np.asarray(experts_module.sum_by_position(
        values, jnp.asarray(pos), jnp.where(jnp.asarray(mine), gates, 0.0)))
    runs = run_sum.plan(jnp.where(is_live, jnp.asarray(row) // slots, run_sum.NO_TOKEN), n)
    by_row = jnp.where(is_live, gates.reshape(-1)[jnp.asarray(row)], 0.0)
    by_runs = lambda v: np.asarray(experts_module.sum_by_runs(  # noqa: E731
        v, by_row, runs, (n, slots), True, unit=not weighted))
    have = by_runs(values)
    assert have.shape == want.shape == (n, 128) and have.dtype == np.float32
    held = mine.sum(axis=1)
    assert not have[held == 0].any() and (held == 0).any()       # a token with no row: zeros
    close = lambda a: np.abs(a - want).max() <= 2e-6 * max(np.abs(want).max(), 1.0)  # noqa: E731
    assert close(have), np.abs(have - want).max()
    if live and rows_dtype == "float32":
        rounded = values.astype(jnp.bfloat16).astype(jnp.float32)
        assert not close(by_runs(rounded))
    if live:
        assert np.abs(want).max() > 1.0
    # the shapes hold what their names say
    tb, rb = run_sum.blocks_of(n, rows)
    spans = np.asarray(runs.count)
    if shape == "ragged-rows":
        assert (tb, rb, rows % rb, len(spans)) == (256, 256, 32, 3) and runs.perm.shape == (1024,)
        # a row block shared by two token blocks: the spans overlap
        assert spans.sum() > len(np.unique(np.concatenate(
            [f + np.arange(c) for f, c in zip(np.asarray(runs.first), spans)])))
    if shape == "a-token-with-every-slot":
        assert held[3] == slots and held[2] == held[4] == 0
    if shape == "no-live-row":
        assert not spans.any() and not have.any()
    if shape == "every-row-live":
        assert held.sum() == rows


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_a_pass_gives_the_same_output_and_gradients_by_runs_and_by_position(compute_dtype):
    """``_one_pass`` with its rows brought back by position and by runs, on
    the two passes a skewed routing takes (``_experts_chunk``: 96 tokens,
    experts 2 and 3 of 16 held, every token's first choice expert 2 and every
    other token's second expert 3: 135 rows in passes of 128): the output and the
    gradients to the tokens, the gates and the three expert matrices. In
    float32 the two differ by the order of a token's additions; with bfloat16
    rows the backward sum is rounded once, after the float32 sum, both ways."""
    config = _config(compute_dtype=compute_dtype, num_experts=16, experts_held=(2, 4))
    dtype = jnp.dtype(compute_dtype)
    rng = np.random.default_rng(8)
    n, slots = 96, 2
    u = jnp.asarray(rng.standard_normal((n, 32)), jnp.float32).astype(dtype)
    second = np.where(np.arange(n) % 2, 3, rng.integers(4, 16, n))
    experts = np.stack([np.full(n, 2), second], axis=1).astype(np.int32)
    gates = jnp.asarray(rng.uniform(0.2, 0.8, (n, slots)), jnp.float32)
    real = jnp.asarray(np.arange(n) < 90)
    shapes = sparse_moe.param_shapes(config)["layers"]
    drawn = seeded_histories.make_params(
        {k: shapes[k][1:] for k in ("w_gate", "w_up", "w_down")}, seed=21)
    weights = tuple(jnp.asarray(drawn[k] * 5).astype(dtype) for k in ("w_gate", "w_up", "w_down"))
    weight = jnp.asarray(rng.standard_normal((n, 32)), jnp.float32)

    def run(interpret):
        def program(u, gates, *weights):
            y, worked, ran = experts_module.experts_chunk(
                config, interpret, *weights, u, jnp.asarray(experts), gates, real)
            return (y * weight).sum(), (y, worked, ran)
        return jax.jit(jax.value_and_grad(program, (0, 1, 2, 3, 4), has_aux=True))(
            u, gates, *weights)

    (_, (want_y, worked, ran)), want = run(None)
    (_, (have_y, worked_too, ran_too)), have = run(True)
    assert int(ran) == int(ran_too) == 2 and int(worked) == int(worked_too) == 90 + 45
    assert experts_module.pass_plan(config, n) == (128, 2)
    tolerance = 2e-6 if compute_dtype == "float32" else 1e-2
    for a, b in zip((have_y, *have), (want_y, *want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0 and np.isfinite(a).all()
        assert np.abs(a - b).max() <= tolerance * np.abs(b).max()


# ---- the template ------------------------------------------------------------

def test_the_engine_takes_the_backbone_and_names_all_three_when_it_refuses():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    config = SASRecAlgorithm(Params({
        "backbone": "sparse_moe", "hiddenSize": 2048, "numHeads": 32, "numKvHeads": 4,
        "headDim": 128, "expertDim": 768, "numExperts": 128, "expertsPerToken": 8,
        "expertsHeld": [0, 16], "numLayers": 6, "indexHeads": 16, "indexDim": 64,
        "indexTopk": 2048, "ropeTheta": 10000000, "batchSize": 2}))._config(18991, 8192)
    assert isinstance(config, SparseMoEConfig) and config.held == 16
    assert sparse_moe.count_params(config) == 659_187_712
    # a whole layer's tokens: a pass of 32,768 rows is twice their even share
    assert experts_module.moe_chunk_of(config) == 16384
    assert experts_module.pass_plan(config, 16384) == (32768, 4)
    whole = SASRecAlgorithm(Params({"backbone": "sparse_moe", "numExperts": 16}))._config(12, 64)
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="'sasrec', 'looped', 'sparse_moe'"):
        SASRecAlgorithm(Params({"backbone": "mamba"}))._config(12, 64)
    with pytest.raises(ValueError, match="experts_held"):
        SASRecAlgorithm(Params({"backbone": "sparse_moe", "expertsHeld": [4, 12]}))._config(12, 64)


def test_the_indexer_is_fixed_and_carries_no_optimizer_state(params, batch):
    config = _config()
    _, place, step_fn, _ = make_fit(config, _mesh())
    placed, opt_state = place(params)
    leaves = jax.tree_util.tree_leaves(opt_state)
    trained = sparse_moe.count_params(config) - sum(
        int(np.prod(s)) for s in sparse_moe.param_shapes(config)["indexer"].values())
    assert sum(a.size for a in leaves if a.ndim) == 2 * trained      # Adam's two moments
    feed = {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}
    after, _, loss, aux = step_fn(placed, opt_state, feed, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss)) and int(aux["moe_dropped"]) == 0
    for name, before in params["indexer"].items():
        assert (np.asarray(after["indexer"][name]) == before).all()
    assert (np.asarray(after["layers"]["router"]) != params["layers"]["router"]).any()


def _cyclic(n_items=12, t=8, rows=96, seed=0):
    starts = np.random.default_rng(seed).integers(0, n_items, rows)
    return ((starts[:, None] + np.arange(t)[None, :]) % n_items + 1).astype(np.int32)


def test_the_backbone_learns_a_cycle_and_reports_its_fit(caplog):
    import logging

    from predictionio_tpu.obs.trace import global_tracer

    config = SparseMoEConfig(
        num_items=12, max_len=8, hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        expert_dim=32, num_experts=4, experts_per_token=2, experts_held=(0, 4),
        num_layers=1, index_heads=2, index_dim=8, index_topk=4, learning_rate=0.01,
        batch_size=32, epochs=12, attention="plain")
    with caplog.at_level(logging.INFO, logger="pio.sequence"):
        trained, losses = train_sasrec(config, _cyclic(), _mesh(), log_every=1)
    assert losses[-1] < 0.6 * losses[0]
    hits = 0
    for start in range(12):
        prefix = (start + np.arange(4)) % 12 + 1
        scores = score_next_items_batch(trained, config, [prefix])[0]
        hits += int(np.argmax(scores) == (start + 4) % 12)
    assert hits >= 10
    attrs = next(s for tr in global_tracer().snapshot(limit=50)["recent"]
                 for s in tr["spans"] if s["op"] == "seq.fit")["attrs"]
    assert attrs["backbone"] == "sparse_moe" and attrs["passes"] == 1
    assert (attrs["experts_total"], attrs["experts_held"], attrs["experts_per_token"],
            attrs["index_topk"], attrs["kv_heads"]) == (4, 4, 2, 4, 2)
    assert attrs["moe_dropped"] == 0 and attrs["moe_held_assignments"] == attrs["moe_assignments"]
    assert attrs["selected_pairs"] == 32 * (1 + 2 + 3 + 4 * 5)
    assert attrs["causal_pairs"] == 32 * 36
    # one layer of 32 rows of 8 positions, a bit a pair, kept for the backward pass
    assert (attrs["rematerialised"], attrs["selection_kept_bytes"]) == ("layer", 32 * 8)
    line = next(r.getMessage() for r in caplog.records if "seq_fit:" in r.getMessage())
    assert (attrs["attention_backward_programs"],
            attrs["attention_backward_heads_per_step"]) == (0, 2)       # ``attention="plain"``
    for word in ("backbone=sparse_moe", "experts_held=4", "experts_total=4", "index_topk=4",
                 "attention_backward_programs=0", "attention_backward_heads_per_step=2",
                 "selection_kept_bytes=256", "moe_dropped=0", "moe_held_load_max=",
                 "selected_pairs="):
        assert word in line, (word, line)
