"""The latent backbone of the sequence template (latent attention with a
rotary key all heads share, a leading dense layer, experts chosen by sigmoid
scores plus a bias no gradient trains, an ungated shared expert, a module that
predicts a second event ahead) against its plain reference
(``benchmarks/reference_joyai.py``), at a small size with seeded weights: the
three terms of the loss and every gradient in float32 and with bfloat16 matmul
inputs, with padded rows; the attention programs with a score width that is
not the value width against their plain twin; the shares of an
expert-parallel deployment, the shared expert counted once, add up to the
uncut layer; selection by score plus bias and gates by score alone; the bias's
move and Adam's step inside one program; the module's reading and scoring; the
engine takes the backbone by name."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_joyai as ref
from benchmarks import seeded_latent
from predictionio_tpu.models.sequence import experts as experts_module, latent_moe
from predictionio_tpu.models.sequence.latent_moe import BIAS, LatentMoEConfig
from predictionio_tpu.models.sequence.model import (
    fit_attrs, make_fit, score_next_items_batch, train_sasrec,
)
from predictionio_tpu.ops import sparse_attention as sa

VOCAB, T, ROWS = 256, 80, 3
#: the configuration file's keys at the test's size, as ``seeded_latent`` reads them
FILE = dict(hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=12, intermediate_size=48,
            moe_intermediate_size=24, n_routed_experts=8, n_shared_experts=1,
            num_nextn_predict_layers=1)
BALANCE, RATE = 1e-2, 1e-3
DIMS = dict(num_heads=4, kv_rank=16, nope_dim=16, rope_dim=8, value_dim=12,
            experts_per_token=2, experts_held=(2, 6), routed_scale=2.5, mtp_coef=0.3,
            balance_coef=BALANCE, bias_rate=RATE, rope_theta=3.2e7, rms_eps=1e-6,
            query_block=16)


def _config(**kw) -> LatentMoEConfig:
    base = dict(num_items=VOCAB - 1, max_len=T, hidden_size=32, num_layers=3, dense_layers=1,
                num_heads=4, q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8, value_dim=12,
                ffn_dim=48, expert_dim=24, num_experts=8, experts_per_token=2,
                experts_held=(2, 6), shared_expert_dim=24, balance_coef=BALANCE,
                bias_rate=RATE, compute_dtype="float32", attention="plain", head_chunk=64,
                moe_chunk=64)
    base.update(kw)
    return LatentMoEConfig(**base)


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def _routers(tree):
    return tree["layers"], tree["mtp"]["layer"]


@pytest.fixture(scope="module")
def params():
    drawn = seeded_latent.make_params(seeded_latent.param_shapes(FILE, VOCAB, 4), 5, 8, 0.05)
    assert jax.tree_util.tree_map(np.shape, drawn) == latent_moe.param_shapes(_config())
    # a router wide enough that no choice is near a tie, under a bias wide
    # enough to decide a third of the choices
    for layer in _routers(drawn):
        layer["router"] = layer["router"] * 10
    return drawn


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(11)
    seq = rng.integers(1, VOCAB, (ROWS, T)).astype(np.int32)
    seq[1, 50:] = 0  # padded tails: they are routed nowhere and have no target
    seq[2, 7:] = 0
    targets = np.zeros_like(seq)
    targets[:, :-1] = seq[:, 1:]
    return seq, targets


def _reference(params, batch, dims=DIMS, how=ref.SOUND):
    seq, targets = (jnp.asarray(a) for a in batch)
    return jax.jit(lambda p: ref.loss_and_grads(p, seq, targets, dims, how))(params)


@pytest.fixture(scope="module")
def sound(params, batch):
    return _reference(params, batch)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(k.key for k in path): np.asarray(a) for path, a in leaves}


def _feed(batch):
    return {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}


def _step(config, params, batch):
    loss_fn = latent_moe.make_loss(config, _mesh())
    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, _feed(batch), None)


@pytest.fixture(scope="module")
def plain_step(params, batch):
    """The step as ``_config()`` has it (float32, the plain paths), worked once
    for the tests that compare against it."""
    return _step(_config(), params, batch)


TRAINED = [".".join(k.key for k in path) for path, _ in jax.tree_util.tree_flatten_with_path(
    latent_moe.param_shapes(_config()), is_leaf=lambda x: isinstance(x, tuple))[0]
    if path[-1].key != BIAS]


@pytest.mark.parametrize("attention", ["plain", "flash"])
def test_the_three_loss_terms_and_every_gradient_match_the_reference(
        params, batch, sound, plain_step, attention):
    """float32 throughout; "flash" is the three attention programs in their
    causal mode at a score width of 24 and a value width of 12, four heads a
    grid step, interpreted. Two rows have padded tails."""
    (loss, aux), grads = (plain_step if attention == "plain" else
                          _step(_config(attention=attention), params, batch))
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 2e-5
    for term in ("ce", "mtp_ce", "balance"):
        assert abs(float(aux[term]) - float(want_aux[term])) < 2e-5, term
    assert float(want_aux["mtp_ce"]) > 1 and float(want_aux["balance"]) > 0.5
    have, want_flat = _flat(grads), _flat(want_grads)
    assert sorted(have) == sorted(want_flat)
    for name in TRAINED:
        scale = np.abs(want_flat[name]).max()
        assert scale > 0, name
        assert np.abs(have[name] - want_flat[name]).max() < 2e-3 * scale, name
    assert np.array_equal(np.asarray(aux["router_load"]), np.asarray(want_aux["load"]))
    assert int(np.asarray(want_aux["decided"]).min()) > 20   # the bias decides choices
    real, ahead = int((batch[0] > 0).sum()), int((batch[1] > 0).sum())
    assert int(aux["moe_assignments"]) == 2 * (2 * real + ahead)  # K x (2 layers + the module)
    assert 0 < int(aux["moe_held_assignments"]) < int(aux["moe_assignments"])
    assert int(aux["moe_dropped"]) == 0


def test_the_bias_is_in_no_gradient(plain_step, sound):
    for grads in (plain_step[1], sound[2]):
        for layer in _routers(grads):
            assert not np.asarray(layer[BIAS]).any()


def test_bfloat16_matmul_inputs_stay_near_the_reference(params, batch, sound):
    """As the cell runs it: bfloat16 into every product, float32 out of it."""
    (loss, aux), grads = _step(_config(compute_dtype="bfloat16"), params, batch)
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 5e-3
    assert abs(float(aux["balance"]) - float(want_aux["balance"])) < 1e-3
    have, want_flat = _flat(grads), _flat(want_grads)
    for name in ("dense.w_qa", "dense.w_kvb", "dense.w_down", "layers.w_qb", "layers.w_kva",
                 "layers.wo", "layers.s_down", "layers.w_down", "mtp.merge", "head"):
        rel = np.linalg.norm(have[name] - want_flat[name]) / np.linalg.norm(want_flat[name])
        assert rel < 0.05, (name, rel)


@pytest.mark.parametrize("control,tensor,least", [
    ({"rope_key": False}, "layers.w_kva.rope", 0.99),
    ({"router": "softmax"}, "layers.router", 1e-1),
    ({"bias": False}, "layers.w_down", 1e-1),
    ({"scaled": False}, "layers.w_down", 1e-1),
    ({"mtp": False}, "mtp.merge", 0.99),
    ({"precision": "bfloat16"}, "layers.router", 1e-3)])
def test_each_control_of_the_reference_reads_other_gradients(
        params, batch, sound, control, tensor, least):
    """What the benchmark's ``--control 1`` plants, at this size: each moves a
    gradient of the path it touches by far more than the program differs
    (1e-6 of the gradient's norm in float32). Without the rotary term nothing
    reaches ``W_kva``'s rotary columns (at these widths and N(0, 0.02) the term
    is a hundredth of a score, so the rest of the matrix hardly moves)."""
    tensor, rope, _ = tensor.partition(".rope")
    columns = slice(DIMS["kv_rank"], None) if rope else slice(None)
    sound = _flat(sound[2])[tensor][..., columns]
    wrong = _flat(_reference(params, batch, how={**ref.SOUND, **control})[2])[tensor][..., columns]
    assert np.linalg.norm(wrong - sound) > least * np.linalg.norm(sound)


REWORKED = {"no-remat": dict(remat=False), "head-whole": dict(head_chunk=0),
            "experts-in-chunks-of-32": dict(moe_chunk=32), "two-dense-layers": None}


@pytest.mark.parametrize("case", list(REWORKED))
def test_remat_and_chunks_change_nothing(params, batch, plain_step, case):
    (loss, _), grads = plain_step
    if case == "two-dense-layers":
        # a stack of two dense layers and one expert layer is scanned as it stands
        config = _config(dense_layers=2)
        drawn = seeded_latent.make_params(seeded_latent.param_shapes(
            {**FILE, "first_k_dense_replace": 2}, VOCAB, 4), 5, 8)
        (loss, aux), grads = _step(config, drawn, batch)
        (other, _), other_grads = _step(dataclasses.replace(config, remat=False), drawn, batch)
        assert aux["router_load"].shape == (2, 8)
    else:
        (other, _), other_grads = _step(_config(**REWORKED[case]), params, batch)
    assert abs(float(loss) - float(other)) < 1e-5
    for name, g in _flat(grads).items():
        assert np.abs(_flat(other_grads)[name] - g).max() < 1e-4 * max(np.abs(g).max(), 1e-8), name


# ---- the attention programs at two widths -----------------------------------

#: query heads, key-value heads -> the key-value heads a grid step works
LAYOUTS = {(4, 4): 4, (32, 32): 8, (6, 3): 3, (16, 2): 1, (12, 6): 3, (5, 5): 5, (7, 7): 7}


@pytest.mark.parametrize("heads,kv", [(4, 4), (6, 3), (16, 2), (5, 5)])
def test_attention_programs_score_over_one_width_and_carry_another(heads, kv):
    """Scores over 24 and values of 16 (one and a half to one, as 192 to 128),
    a row of 96 in tiles of 32 queries and 48 keys (neither is the row, and the
    row is no multiple of ``BLOCK_Q``), against the plain twin and against
    dense attention written out: forward and the three gradients."""
    rng = np.random.default_rng(heads)
    b, t, d, dv = 2, 96, 24, 16
    assert sa.heads_per_step(kv, heads // kv) == LAYOUTS[heads, kv]
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((b, t, heads, d), (b, t, kv, d), (b, t, kv, dv)))
    weight = jnp.asarray(rng.standard_normal((b, t, heads, dv)), jnp.float32)

    def dense(q, k, v):
        k, v = jnp.repeat(k, heads // kv, 2), jnp.repeat(v, heads // kv, 2)
        s = jnp.einsum("bqhd,bshd->bhqs", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        return jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v)

    programs = lambda q, k, v: sa.causal_attention(q, k, v, 32, 48, True)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = dense(q, k, v)
        want_grads = jax.grad(lambda *a: (dense(*a) * weight).sum(), (0, 1, 2))(q, k, v)
        for have in (programs, sa.causal_attention_plain):
            out = have(q, k, v)
            assert out.shape == (b, t, heads, dv)
            assert np.abs(np.asarray(out - want)).max() < 1e-5
            grads = jax.grad(lambda *a: (have(*a) * weight).sum(), (0, 1, 2))(q, k, v)
            for name, a, g in zip("qkv", grads, want_grads):
                assert a.shape == g.shape, name
                assert np.abs(np.asarray(a - g)).max() < 1e-4 * np.abs(np.asarray(g)).max(), name


def test_a_grid_step_takes_key_value_heads_up_to_eight_query_heads():
    """The chooser alone. A key-value head with eight query heads or more has a
    step to itself (the hybrid cell's 2 of 8, the sparse cell's 4 of 8: their
    programs are what they were); heads with a key and value each go eight a
    step, or the most under eight that divide them."""
    for (heads, kv), want in LAYOUTS.items():
        assert sa.heads_per_step(kv, heads // kv) == want, (heads, kv)
    assert sa.heads_per_step(4, 8) == sa.heads_per_step(2, 8) == sa.heads_per_step(2, 16) == 1


@pytest.mark.parametrize("fit", [4, 2, 1])
def test_the_backward_program_takes_fewer_heads_a_step_where_the_row_is_long(fit, monkeypatch):
    """Four heads with a key and a value each, scores over 24 and values of
    16, a row of 96 in tiles of 32 by 48. The forward program takes all four a
    step; the backward program as many as hold ``dk`` and ``dv`` of the whole
    row within the limit, so with room for ``fit`` heads it takes ``fit`` and
    reads the logsumexp laid out for them: the three gradients are the twin's
    whatever it takes, and to the bit the same."""
    rng = np.random.default_rng(11)
    b, t, heads, d, dv = 2, 96, 4, 24, 16
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((b, t, heads, d), (b, t, heads, d), (b, t, heads, dv)))
    weight = jnp.asarray(rng.standard_normal((b, t, heads, dv)), jnp.float32)
    grads = lambda fn: jax.grad(lambda *a: (fn(*a) * weight).sum(), (0, 1, 2))(q, k, v)  # noqa: E731
    programs = lambda q, k, v: sa.causal_attention(q, k, v, 32, 48, True)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = grads(sa.causal_attention_plain)
        whole = grads(programs)
        monkeypatch.setattr(sa, "VMEM_LIMIT_BYTES",
                            sa.backward_step_bytes(fit, 1, d, dv, t, 4, False, 32, 48))
        assert sa.heads_per_step(heads, 1) == 4
        assert sa.backward_heads_per_step(heads, 1, d, dv, t, 4, False, 32, 48) == fit
        have = grads(programs)
    for name, a, w, g in zip("qkv", have, whole, want):
        assert np.array_equal(np.asarray(a), np.asarray(w)), name
        assert np.abs(np.asarray(a - g)).max() < 1e-4 * np.abs(np.asarray(g)).max(), name


def test_the_cells_backward_program_takes_four_heads_a_step():
    """The cell's shape (32 heads with a key and a value each, 192 + 128, a
    row of 8,192, bfloat16): a head's ``dk`` and ``dv`` are 12.6 MB in VMEM
    (192 is held as 256 lanes), so the forward program's eight heads would
    take 100 MB and the backward program takes four, 60.8 MB of the 64 MiB;
    a quarter of the row leaves room for eight, four times the row for one."""
    cell = (32, 1, 192, 128, 8192, 2)
    assert sa.backward_step_bytes(1, *cell[1:], False) - sa.backward_step_bytes(
        1, 1, 192, 128, 4096, 2, False) == 4 * 4096 * (256 + 128)
    assert sa.backward_step_bytes(8, *cell[1:], False) > 100e6
    assert sa.backward_step_bytes(4, *cell[1:], False) == 60_817_408 < sa.VMEM_LIMIT_BYTES
    assert sa.backward_heads_per_step(*cell) == 4
    # the tile of queries stays the forward program's: twice it would take 68 MB
    assert sa.backward_query_block(4, *cell[1:], False, sa.BLOCK_Q, sa.BLOCK_K) == sa.BLOCK_Q
    assert sa.backward_query_block(2, *cell[1:], False, sa.BLOCK_Q, sa.BLOCK_K) == 2 * sa.BLOCK_Q
    assert [sa.backward_heads_per_step(32, 1, 192, 128, t, 2)
            for t in (2048, 4096, 16384, 32768)] == [8, 4, 2, 1]
    with pytest.raises(ValueError, match="a row of 65536 positions is too long"):
        sa.backward_heads_per_step(32, 1, 192, 128, 65536, 2)


def test_a_length_the_block_does_not_divide_is_refused():
    q = jnp.zeros((1, 100, 2, 24))
    with pytest.raises(ValueError, match="not a multiple of the block"):
        sa.causal_attention(q, q, q[..., :16], 32, 48, True)


def test_rotary_pairs_are_interleaved_and_the_key_is_one_for_all_heads():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 40, 3, 8)), jnp.float32)
    have = latent_moe.rotate(x, *latent_moe.rope_tables(40, 8, 3.2e7))
    want = jnp.stack([ref.rope_interleaved(row, 3.2e7) for row in x])
    assert np.abs(np.asarray(have - want)).max() < 1e-6
    # position 0 is left as it is, a pair's norm everywhere
    assert np.array_equal(np.asarray(have[:, 0]), np.asarray(x[:, 0]))
    pairs = lambda a: np.asarray(a).reshape(2, 40, 3, 4, 2)  # noqa: E731
    assert np.abs(np.linalg.norm(pairs(have), axis=-1)
                  - np.linalg.norm(pairs(x), axis=-1)).max() < 1e-5


# ---- the router, its bias and the experts -------------------------------------

def _expert_layer(experts: int, seed: int = 9):
    """One expert layer's parameters with all ``experts`` held, the rows it is
    worked on and which of them are real."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 96, 32)), jnp.float32)
    real = jnp.asarray(np.arange(96) < 90)[None]
    shapes = seeded_latent.param_shapes({**FILE, "n_routed_experts": experts}, VOCAB, experts)
    drawn = seeded_latent.make_params(shapes["mtp"]["layer"], seed, 8, 0.05)
    drawn["router"] = drawn["router"] * 10
    return x, real, drawn


def test_the_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """Sixteen programs, each holding 2 of 32 experts with the same router, the
    same bias and the same shared expert: their routed parts, and the shared
    expert's counted once, add up to the reference's uncut expert layer."""
    x, real, drawn = _expert_layer(32)
    dims = {**DIMS, "experts_per_token": 4, "experts_held": (0, 32)}
    with jax.default_matmul_precision("highest"):
        want = ref.experts_mlp(drawn, x[0], real[0], dims, ref.SOUND)[0] - x[0]
        routed, shared, held = 0.0, None, 0
        route = functools.partial(latent_moe.route, rows=x.shape[0])
        for lo in range(0, 32, 2):
            config = _config(num_experts=32, experts_per_token=4, experts_held=(lo, lo + 2))
            share = {**drawn, **{k: drawn[k][lo:lo + 2] for k in ("w_gate", "w_up", "w_down")}}
            with_shared, stats = experts_module.expert_half(
                config, "cpu", x, share, real, route=route)
            alone = {**share, "s_down": jnp.zeros_like(drawn["s_down"])}
            without, _ = experts_module.expert_half(
                config, "cpu", x, alone, real, route=route)
            assert int(stats["dropped"]) == 0
            held += int(stats["held_assignments"])
            routed = routed + (without - x)
            mine = with_shared - without
            assert shared is None or np.abs(np.asarray(mine - shared)).max() < 1e-6
            shared = mine
    assert held == int(stats["assignments"]) == int(stats["load"].sum()) == 4 * 90
    assert np.abs(np.asarray(shared)).max() > 1e-3
    assert np.abs(np.asarray(routed[0] + shared[0] - want)).max() < 1e-5


def test_selection_reads_the_score_plus_the_bias_and_gates_the_score_alone():
    """A bias that flips a choice changes which experts a token takes, never a
    kept expert's weight before the renormalising: the gates are the scores of
    the chosen, over their sum, times ``routed_scale``."""
    x, real, drawn = _expert_layer(8)
    config = _config(experts_held=(0, 8))
    u = x[0]
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(u @ drawn["router"]))
        route = lambda bias: latent_moe.route(  # noqa: E731
            config, u, {**drawn, BIAS: jnp.asarray(bias, jnp.float32)}, real[0], rows=1)
        plain_experts, plain_gates, _ = route(np.zeros(8))
        biased = np.zeros(8, np.float32)
        biased[3] = 10.0                          # every token takes expert 3
        experts, gates, stats = route(biased)
        want_experts, want_gates = ref.routing(
            {**drawn, "router_bias": jnp.asarray(biased)}, u, DIMS, ref.SOUND)[1:]
    experts, gates = np.asarray(experts), np.asarray(gates)
    assert np.array_equal(np.sort(experts, axis=1), np.sort(np.asarray(want_experts), axis=1))
    assert (experts == 3).any(axis=1).all() and int(stats["load"][3]) == 90
    assert not (np.asarray(plain_experts) == 3).any(axis=1).all()
    best = np.argmax(np.where(np.arange(8) == 3, -1.0, scores), axis=1)   # the best of the rest
    assert np.array_equal(np.sort(experts, axis=1),
                          np.sort(np.stack([np.full(96, 3), best], axis=1), axis=1))
    picked = np.take_along_axis(scores, experts, axis=1)                   # no bias in a gate
    assert np.abs(gates - 2.5 * picked / picked.sum(axis=1, keepdims=True)).max() < 1e-6
    assert np.abs(gates.sum(axis=1) - 2.5).max() < 1e-5
    # a token whose choice the bias did not change keeps its gates to the bit
    same = (np.sort(experts, axis=1) == np.sort(np.asarray(plain_experts), axis=1)).all(axis=1)
    assert 0 < same.sum() < 96
    assert np.array_equal(np.sort(gates[same], axis=1),
                          np.sort(np.asarray(plain_gates)[same], axis=1))


def test_the_softmax_router_is_what_it_was_beside_the_split():
    """``experts.moe`` with no router named is the softmax router's layer:
    its counts and its auxiliary loss, as the sparse and the hybrid backbones
    read them."""
    x, real, drawn = _expert_layer(8)
    config = _config(experts_held=(2, 6))
    share = {**drawn, **{k: drawn[k][2:6] for k in ("w_gate", "w_up", "w_down")}}
    with jax.default_matmul_precision("highest"):
        y, stats = experts_module.moe(config, "cpu", x[0], share, real[0])
        probs = jax.nn.softmax(x[0] @ drawn["router"], axis=-1)
    top = np.asarray(jax.lax.top_k(probs, 2)[1])[:90]
    load = np.bincount(top.reshape(-1), minlength=8)
    assert sorted(stats) == ["assignments", "aux", "dropped", "held_assignments",
                             "held_load_max", "passes", "passes_run", "sum_rows",
                             "sum_slots"]
    assert int(stats["assignments"]) == 180 and int(stats["dropped"]) == 0
    assert int(stats["held_assignments"]) == load[2:6].sum()
    assert int(stats["held_load_max"]) == load[2:6].max()
    want_aux = 8 * (load / 90 * np.asarray(probs)[:90].mean(axis=0)).sum()
    assert abs(float(stats["aux"]) - want_aux) < 1e-5
    assert y.shape == (96, 32) and not np.asarray(y[90:]).any()


def test_a_step_moves_the_bias_against_the_load_and_adam_moves_the_rest(params, batch, sound):
    """One program (``make_fit``'s step): Adam's update of every trained leaf
    from the gradients the reference gives, the bias moved by ``bias_rate``
    against the step's load as ``reference_joyai.bias_after`` moves it, and no
    moments kept for it."""
    config = _config()
    _, place, step_fn, _ = make_fit(config, _mesh())
    placed, opt_state = place(params)
    moments = [a for a in jax.tree_util.tree_leaves(opt_state) if a.ndim]
    assert sum(a.size for a in moments) == 2 * latent_moe.count_params(config)
    assert latent_moe.count_params(config) == sum(
        a.size for name, a in _flat(params).items() if BIAS not in name)
    after, _, loss, aux = step_fn(placed, opt_state, _feed(batch), jax.random.PRNGKey(0))
    _, want_aux, want_grads = sound
    assert abs(float(loss) - float(sound[0])) < 2e-5 and int(aux["moe_dropped"]) == 0
    assert "router_load" not in aux
    before, after, grads = _flat(params), _flat(after), _flat(want_grads)
    for name in TRAINED:
        # Adam's first step: lr g / (|g| + eps (1 - b2)^-1/2 ...) = lr sign(g) where g is not tiny
        g = grads[name].astype(np.float64)
        want = -config.learning_rate * g / (np.abs(g) + 1e-8)
        moved = after[name].astype(np.float64) - before[name]
        assert (moved != 0).any(), name
        assert np.abs(moved - want).max() < 2e-2 * config.learning_rate, name
    want_bias = np.asarray(ref.bias_after(params, want_aux["load"], RATE))
    have_bias = np.concatenate([after[f"layers.{BIAS}"], after[f"mtp.layer.{BIAS}"][None]])
    assert np.array_equal(have_bias, want_bias)
    load = np.asarray(want_aux["load"], np.float64)
    moved = have_bias - np.concatenate([before[f"layers.{BIAS}"], before[f"mtp.layer.{BIAS}"][None]])
    assert np.allclose(moved, RATE * np.sign(load.mean(axis=1, keepdims=True) - load), atol=1e-7)
    assert (moved != 0).any()
    assert abs(float(aux["router_bias_abs_max"]) - np.abs(have_bias).max()) < 1e-7


# ---- the prediction module -----------------------------------------------------

def test_the_module_reads_target_i_and_is_scored_on_the_event_after_it(params):
    """Row 0 is whole, row 1 ends early. ``mtp_ce`` counts the positions whose
    ``target_{i+1}`` is an event; changing the last target of a row (read by
    the module at the last position that has one, scored nowhere by it) moves
    the stack's term and not the positions the module scores before it; a
    padded position's embedding is never read."""
    rng = np.random.default_rng(3)
    seq = rng.integers(1, VOCAB, (2, T)).astype(np.int32)
    seq[1, 30:] = 0
    targets = np.zeros_like(seq)
    targets[:, :-1] = seq[:, 1:]
    loss_fn = jax.jit(latent_moe.make_loss(_config(), _mesh()))
    _, aux = loss_fn(params, _feed((seq, targets)), None)
    want = _reference(params, (seq, targets))[1]
    assert abs(float(aux["mtp_ce"]) - float(want["mtp_ce"])) < 2e-5
    # the module's load counts the positions with a target_i, the stack's the events
    assert int(aux["router_load"][-1].sum()) == 2 * int((targets > 0).sum())
    assert int(aux["router_load"][0].sum()) == 2 * int((seq > 0).sum())
    # the embedding of the padding id is read by no real position of either part
    moved = {**params, "embed": params["embed"].copy()}
    moved["embed"][0] += 1.0
    _, other = loss_fn(moved, _feed((seq, targets)), None)
    assert float(other["ce"]) == float(aux["ce"]) and float(other["mtp_ce"]) == float(aux["mtp_ce"])
    # what the module is scored on: a label two ahead. Changing target_{T-2}
    # of the whole row (the label of position T-3 in the module, of T-2 in the
    # stack, and the module's input at T-2) moves both terms
    changed = targets.copy()
    changed[0, T - 2] = targets[0, T - 2] % (VOCAB - 1) + 1
    _, third = loss_fn(params, _feed((seq, changed)), None)
    assert float(third["ce"]) != float(aux["ce"]) and float(third["mtp_ce"]) != float(aux["mtp_ce"])
    # a module whose weight is 0 receives no gradient; the merge's is the stack's only way to it
    grads = _step(_config(mtp_coef=0.0, balance_coef=0.0), params, (seq, targets))[1]
    assert not any(np.asarray(a).any() for a in jax.tree_util.tree_leaves(grads["mtp"]))
    assert np.asarray(grads["layers"]["wo"]).any()


def test_scores_for_a_query_do_not_depend_on_the_module(params, batch):
    config = _config()
    seqs = jnp.asarray(batch[0])
    last = jnp.asarray([T - 1, 49, 6])
    scores = latent_moe.score_last(config, params, seqs, last)
    assert scores.shape == (ROWS, VOCAB)
    other = {**params, "mtp": jax.tree_util.tree_map(lambda a: a * 0 + 7.0, params["mtp"])}
    assert np.array_equal(np.asarray(latent_moe.score_last(config, other, seqs, last)),
                          np.asarray(scores))
    without = _config(mtp_depth=0)
    stack = {k: v for k, v in params.items() if k != "mtp"}
    assert jax.tree_util.tree_map(np.shape, stack) == latent_moe.param_shapes(without)
    assert np.array_equal(np.asarray(latent_moe.score_last(without, stack, seqs, last)),
                          np.asarray(scores))
    # the logits of the stack's own head at the last event
    with jax.default_matmul_precision("highest"):
        x, _, _ = latent_moe.hidden_states(config, "cpu", params, seqs)
        h = np.asarray(ref.rms_norm(x, params["final_norm"], 1e-6))[np.arange(ROWS), last]
        want = h @ params["head"].T
    assert np.abs(np.asarray(scores) - want).max() < 1e-4


# ---- the template ------------------------------------------------------------

ENGINE_JSON = os.path.join(os.path.dirname(__file__), "..", "examples", "sequence",
                           "engine-latent-moe.json")


def test_the_engine_takes_the_backbone_at_the_cells_sizes():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    config = SASRecAlgorithm(Params({
        "backbone": "latent_moe", "hiddenSize": 2048, "numLayers": 5, "denseLayers": 1,
        "numHeads": 32, "qLoraRank": 1536, "kvLoraRank": 512, "qkNopeHeadDim": 128,
        "qkRopeHeadDim": 64, "vHeadDim": 128, "ffnDim": 7168, "expertDim": 768,
        "numExperts": 256, "expertsPerToken": 8, "expertsHeld": [0, 16],
        "sharedExpertDim": 768, "routedScalingFactor": 2.5, "mtpDepth": 1,
        "ropeTheta": 32000000, "batchSize": 2}))._config(16159, 8192)
    assert isinstance(config, LatentMoEConfig) and config.held == 16
    assert (config.expert_layers, config.routers, config.score_dim) == (4, 5, 192)
    assert latent_moe.count_params(config) == 680_439_808
    shapes = latent_moe.param_shapes(config)
    assert shapes["layers"][BIAS] == (4, 256) and shapes["mtp"]["layer"][BIAS] == (256,)
    assert latent_moe.latent_bytes_per_token(config) == (512 + 64) * 2
    # a whole layer's tokens at once: a pass of 16,384 rows is twice their even share
    assert experts_module.moe_chunk_of(config) >= 16384
    assert experts_module.pass_plan(config, 16384)[0] >= 2 * 16384 * 8 * 16 // 256
    assert sa.heads_per_step(config.num_kv_heads, 1) == 8
    assert latent_moe.attention_backward_heads_per_step(config) == 4
    assert fit_attrs(config, 4, 8, 2, "tpu")["attention_backward_programs"] == 1
    attrs = fit_attrs(config, 4, 8, 2, "cpu")
    assert (attrs["attention_backward_programs"],
            attrs["attention_backward_heads_per_step"]) == (0, 4)
    assert (attrs["backbone"], attrs["layers"], attrs["dense_layers"], attrs["mtp_depth"],
            attrs["experts_shared"], attrs["experts_total"], attrs["experts_held"],
            attrs["experts_per_token"]) == ("latent_moe", 5, 1, 1, 1, 256, 16, 8)
    assert (attrs["latent_q_rank"], attrs["latent_kv_rank"], attrs["score_width"],
            attrs["value_width"], attrs["latent_bytes_per_token"],
            attrs["router_bias_leaves"]) == (1536, 512, 192, 128, 1152, 5)
    assert attrs["rematerialised"] == "mixer and experts"
    whole = SASRecAlgorithm(Params({"backbone": "latent_moe", "numExperts": 16}))._config(12, 64)
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="'hybrid_linear', 'latent_moe'"):
        SASRecAlgorithm(Params({"backbone": "mamba"}))._config(12, 64)
    with pytest.raises(ValueError, match="an expert layer at least"):
        SASRecAlgorithm(Params({"backbone": "latent_moe", "numLayers": 2,
                                "denseLayers": 2}))._config(12, 64)


def test_engine_parameters_round_trip_from_engine_json():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    with open(ENGINE_JSON) as f:
        engine = json.load(f)
    written = engine["algorithms"][0]["params"]
    max_len = engine["preparator"]["params"]["maxLen"]
    config = SASRecAlgorithm(Params(written))._config(40, max_len)
    assert isinstance(config, LatentMoEConfig) and config.max_len == max_len
    names = {"hiddenSize": "hidden_size", "numLayers": "num_layers", "denseLayers": "dense_layers",
             "numHeads": "num_heads", "qLoraRank": "q_rank", "kvLoraRank": "kv_rank",
             "qkNopeHeadDim": "nope_dim", "qkRopeHeadDim": "rope_dim", "vHeadDim": "value_dim",
             "ffnDim": "ffn_dim", "expertDim": "expert_dim", "numExperts": "num_experts",
             "expertsPerToken": "experts_per_token", "sharedExpertDim": "shared_expert_dim",
             "routedScalingFactor": "routed_scale", "mtpDepth": "mtp_depth",
             "mtpLossCoef": "mtp_coef", "balanceLossCoef": "balance_coef",
             "biasUpdateRate": "bias_rate", "ropeTheta": "rope_theta", "rmsNormEps": "rms_eps",
             "learningRate": "learning_rate", "batchSize": "batch_size", "epochs": "epochs"}
    for ours, theirs in names.items():
        assert written[ours] == getattr(config, theirs), ours
    assert tuple(written["expertsHeld"]) == config.experts_held
    # every parameter of the backbone the docstring names is in the example
    assert set(names) | {"backbone", "expertsHeld", "attention"} == set(written)


def test_the_bias_is_kept_with_the_parameters(params, tmp_path):
    """What the model store persists is the parameter tree: the bias is a leaf
    of it and comes back with it."""
    import pickle

    from predictionio_tpu.models.sequence.engine import SASRecModel

    model = SASRecModel(params=params, config=_config(), item_ids=[], item_index={},
                        histories={})
    with open(tmp_path / "model", "wb") as f:
        pickle.dump(model, f)
    with open(tmp_path / "model", "rb") as f:
        back = pickle.load(f)
    assert back.config == _config()
    for have, want in zip(_routers(back.params), _routers(params)):
        assert np.array_equal(have[BIAS], want[BIAS]) and np.asarray(want[BIAS]).any()


def _cyclic(n_items=12, t=8, rows=96, seed=0):
    starts = np.random.default_rng(seed).integers(0, n_items, rows)
    return ((starts[:, None] + np.arange(t)[None, :]) % n_items + 1).astype(np.int32)


def test_the_backbone_learns_a_cycle_and_reports_its_fit(caplog):
    import logging

    from predictionio_tpu.obs.trace import global_tracer

    config = LatentMoEConfig(
        num_items=12, max_len=8, hidden_size=32, num_layers=2, dense_layers=1, num_heads=4,
        q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, value_dim=8, ffn_dim=64, expert_dim=32,
        num_experts=4, experts_per_token=2, experts_held=(0, 4), shared_expert_dim=16,
        learning_rate=0.01, batch_size=32, epochs=12, attention="plain")
    with caplog.at_level(logging.INFO, logger="pio.sequence"):
        trained, losses = train_sasrec(config, _cyclic(), _mesh(), log_every=1)
    assert losses[-1] < 0.6 * losses[0]
    hits = 0
    for start in range(12):
        prefix = (start + np.arange(4)) % 12 + 1
        scores = score_next_items_batch(trained, config, [prefix])[0]
        hits += int(np.argmax(scores) == (start + 4) % 12)
    assert hits >= 10
    # 36 steps of 0.001 either way from 0
    bias = np.concatenate([trained["layers"][BIAS], trained["mtp"]["layer"][BIAS][None]])
    assert 0 < np.abs(bias).max() <= 36 * 1e-3 + 1e-6
    attrs = next(s for tr in global_tracer().snapshot(limit=50)["recent"]
                 for s in tr["spans"] if s["op"] == "seq.fit")["attrs"]
    assert attrs["backbone"] == "latent_moe" and attrs["passes"] == 1
    assert (attrs["dense_layers"], attrs["mtp_depth"], attrs["experts_shared"],
            attrs["experts_held"], attrs["score_width"], attrs["value_width"],
            attrs["router_bias_leaves"]) == (1, 1, 1, 4, 12, 8, 2)
    assert attrs["moe_dropped"] == 0 and attrs["moe_held_assignments"] == attrs["moe_assignments"]
    assert abs(attrs["router_bias_abs_max"] - np.abs(bias).max()) < 1e-6
    assert attrs["mtp_ce"] > 0 and attrs["balance"] > 0 and "router_load" not in attrs
    line = next(r.getMessage() for r in caplog.records if "seq_fit:" in r.getMessage())
    for word in ("backbone=latent_moe", "dense_layers=1", "mtp_depth=1", "latent_q_rank=24",
                 "latent_kv_rank=16", "score_width=12", "value_width=8",
                 "latent_bytes_per_token=40", "router_bias_leaves=2", "experts_shared=1",
                 "moe_dropped=0", "router_bias_abs_max="):
        assert word in line, (word, line)


SCOPES = "jit(train_step)/transpose(jvp(seq.pass1))/"


@pytest.mark.parametrize("name,top_stage,leaf", [
    ("layers/while/body/closed_call/checkpoint/rematted_computation/attention/qkv/q_latent/"
     "dot_general", ("pass1", "attention"), "qkv"),
    ("mtp/layers/checkpoint/attention/kernel/pallas_call", ("pass1", "attention"), "kernel"),
    ("mtp/exit/while/body/checkpoint/dot_general", ("pass1", "exit"), None),
    ("mtp/layers/checkpoint/moe/shared/dot_general", ("pass1", "layers"), None),
    ("mtp/merge/checkpoint/dot_general", ("pass1", None), None)])
def test_the_accepted_readers_place_the_new_scopes(name, top_stage, leaf):
    """The module lies under ``seq.pass1`` with the stack's own stage names
    below it, so the stage readers and the leaf reader count its attention,
    exit and experts where they count the stack's; ``q_latent`` lies inside
    ``qkv`` and is read as ``qkv``."""
    from benchmarks import scopes_leaf, scopes_seq

    assert scopes_seq.parse_scope(SCOPES + name) == top_stage
    place = scopes_leaf.place_of(SCOPES + name)
    assert place.top == "pass1" and place.leaf == leaf and place.phase != "forward"
