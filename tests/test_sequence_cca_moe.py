"""The compressed-convolution backbone of the sequence template (attention in
a compressed latent mixed by two causal convolutions, a router MLP that
carries its state from layer to layer and may send a token past the experts,
scaled merges, a tied head) against its plain reference
(``benchmarks/reference_zaya.py``) at a small size with seeded weights: the
loss, every gradient and one Adam step; each control of the reference reads
other numbers; rematerialisation, chunks and the package's programs change
nothing; the shares of an expert-parallel deployment, the skip adding nothing,
add up to the uncut layer; the carry reaches the next layer and not layer 0;
the convolutions are causal; the engine takes the backbone by name."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import reference_zaya as ref
from benchmarks import seeded_cca
from predictionio_tpu.models.sequence import blocks, cca_moe, experts as experts_module
from predictionio_tpu.models.sequence.cca_moe import CcaMoEConfig
from predictionio_tpu.models.sequence.model import (
    fit_attrs, make_fit, score_next_items_batch, train_sasrec,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, T, ROWS, LAYERS, EXPERTS = 96, 40, 2, 3, 8
#: the configuration file's keys at the test's size, as ``seeded_cca`` reads them
FILE = dict(hidden_size=32, head_dim=8, router_hidden_size=16, num_attention_heads=4,
            num_key_value_heads=2, moe_intermediate_size=24, num_experts=EXPERTS,
            num_hidden_layers=LAYERS, cca_time0=2, cca_time1=2)


def _dims(held=(2, 6), **kw) -> dict:
    return dict(num_heads=4, num_kv_heads=2, head_dim=8, conv_time0=2, conv_time1=2,
                experts_held=held, rope_theta=1e3, rotary_fraction=0.5, bias_rate=1e-2,
                rms_eps=1e-5, query_block=8, head_block=16, **kw)


def _config(held=(2, 6), **kw) -> CcaMoEConfig:
    base = dict(num_items=VOCAB - 1, max_len=T, hidden_size=32, num_layers=LAYERS, num_heads=4,
                num_kv_heads=2, head_dim=8, router_dim=16, expert_dim=24, num_experts=EXPERTS,
                experts_held=held, bias_rate=1e-2, rope_theta=1e3, compute_dtype="float32",
                attention="plain", batch_size=ROWS, learning_rate=1e-3, head_chunk=32,
                moe_chunk=64)
    base.update(kw)
    return CcaMoEConfig(**base)


def _params(held=(2, 6), file=FILE, seed=5):
    """Seeded weights with a bias wide enough that it decides some choices and
    that the skip is taken, and a router sharp enough that none is near a tie."""
    drawn = seeded_cca.make_params(
        seeded_cca.param_shapes(file, VOCAB, held[1] - held[0]), seed, 2 * LAYERS, bias_std=0.05)
    drawn["layers"]["w_3"] = drawn["layers"]["w_3"] * 3
    drawn["layers"]["router_bias"][:, -1] += 0.1
    return drawn


@pytest.fixture(scope="module")
def params():
    drawn = _params()
    assert jax.tree_util.tree_map(np.shape, drawn) == cca_moe.param_shapes(_config())
    return drawn


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    seq = rng.integers(1, VOCAB, (ROWS, T)).astype(np.int32)
    seq[1, 29:] = 0                                  # a padded row
    targets = np.zeros_like(seq)
    targets[:, :-1] = seq[:, 1:]
    return {"seq": jnp.asarray(seq), "target": jnp.asarray(targets)}


def _program(config, params, batch):
    """``(loss, aux, grads)`` of the backbone's loss."""
    (loss, aux), grads = jax.jit(jax.value_and_grad(cca_moe.make_loss(config, None),
                                                    has_aux=True))(params, batch, None)
    return loss, aux, grads


def _reference(params, batch, dims, **control):
    how = {**ref.SOUND, **control}
    return jax.jit(lambda p, s, y: ref.loss_and_grads(p, s, y, dims, how))(
        params, batch["seq"], batch["target"])


def _worst(have, want) -> float:
    """The largest relative error, a leaf at a time, in the Frobenius norm."""
    errors = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30)),
        have, want)
    return max(jax.tree_util.tree_leaves(errors))


@pytest.fixture(scope="module")
def sound(params, batch):
    return _reference(params, batch, _dims())


# ---- against the reference -----------------------------------------------------

@pytest.mark.parametrize("held, taps", [((2, 6), (2, 2)), ((0, 8), (2, 2)), ((4, 8), (3, 1)),
                                        ((0, 4), (1, 3))])
def test_the_loss_and_every_gradient_are_the_references(batch, held, taps):
    file = {**FILE, "cca_time0": taps[0], "cca_time1": taps[1]}
    drawn = _params(held, file)
    config = _config(held, conv_time0=taps[0], conv_time1=taps[1])
    loss, aux, grads = _program(config, drawn, batch)
    dims = {**_dims(held), "conv_time0": taps[0], "conv_time1": taps[1]}
    want, seen, want_grads = _reference(drawn, batch, dims)
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    assert (np.asarray(aux["router_load"]) == np.asarray(seen["load"])).all()
    assert int(aux["moe_bias_decided"]) == int(seen["decided"].sum()) > 0
    assert int(aux["moe_skip_assignments"]) == int(seen["load"][:, -1].sum()) > 0
    assert float(aux["router_carry_rms"]) == pytest.approx(float(seen["carry_rms"].mean()), rel=1e-5)
    assert int(aux["moe_dropped"]) == 0
    lo, hi = held
    assert int(aux["moe_held_assignments"]) == int(seen["load"][:, lo:hi].sum())
    assert int(aux["moe_assignments"]) == int(seen["load"][:, :EXPERTS].sum())
    assert _worst(grads, want_grads) < 2e-5
    # no gradient reaches the bias: the optimizer leaves it and ``move`` moves it
    assert float(jnp.abs(grads["layers"]["router_bias"]).max()) == 0.0


CONTROLS = {"bfloat16": {"precision": "bfloat16"}, "no_conv0": {"conv0": False},
            "no_conv1": {"conv1": False}, "no_qk_mean": {"qk_mean": False},
            "no_value_shift": {"value_shift": False}, "no_qk_norm": {"qk_norm": False},
            "no_temperature": {"temperature": False}, "whole_rope": {"rope": "whole"},
            "no_carry": {"carry": False}, "linear_router": {"router": "linear"},
            "no_bias": {"bias": False}, "no_skip": {"skip": False},
            "no_residual_scale": {"residual_scale": False}, "untied_head": {"head": "untied"}}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_of_the_reference_reads_other_gradients(params, batch, sound, control):
    """A control that changed nothing could not fail the benchmark's ``correct``."""
    _, _, want = sound
    _, _, low = _reference(params, batch, _dims(), **CONTROLS[control])
    subset = lambda grads: ref.subset_of(grads, np.arange(1, 9), np.asarray(batch["seq"][0, :8]))  # noqa: E731
    errors = {name: float(jnp.linalg.norm(a - subset(want)[name])
                          / jnp.maximum(jnp.linalg.norm(subset(want)[name]), 1e-30))
              for name, a in subset(low).items()}
    assert max(errors.values()) > (2e-3 if control == "bfloat16" else 2e-2), errors


def test_the_drivers_controls_are_the_references_switches():
    from benchmarks.drivers import seq_cca_train

    assert seq_cca_train.CONTROLS == CONTROLS
    assert all(set(control) <= set(ref.SOUND) for control in CONTROLS.values())
    assert set(seq_cca_train.GRADIENTS) == set(ref.subset_of(
        _params(), np.arange(1, 3), np.arange(1, 3)))


@pytest.mark.parametrize("how", [dict(remat=False), dict(head_chunk=0), dict(head_chunk=16),
                                 dict(moe_chunk=32)])
def test_remat_and_chunks_change_nothing(params, batch, how):
    base = _program(_config(), params, batch)
    other = _program(_config(**how), params, batch)
    assert float(other[0]) == pytest.approx(float(base[0]), rel=1e-6)
    assert _worst(other[2], base[2]) < 1e-5


def test_the_packages_programs_change_nothing_but_roundings(params, batch):
    """``attention="flash"``: the operands' program, the attention programs and
    the run sum, interpreted, against the plain expressions."""
    base = _program(_config(), params, batch)
    other = _program(_config(attention="flash"), params, batch)
    assert float(other[0]) == pytest.approx(float(base[0]), rel=1e-5)
    assert int(other[1]["moe_dropped"]) == 0
    assert (np.asarray(other[1]["router_load"]) == np.asarray(base[1]["router_load"])).all()
    assert _worst(other[2], base[2]) < 1e-3


def test_a_step_is_adams_update_from_the_references_gradients(params, batch, sound):
    config = _config()
    _, place, step_fn, _ = make_fit(config, _mesh())
    placed, opt_state = place(jax.tree_util.tree_map(jnp.asarray, params))
    new, _, loss, aux = step_fn(placed, opt_state, batch, jax.random.PRNGKey(0))
    want_loss, seen, grads = sound
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-6)
    adam = optax.adam(config.learning_rate)
    updates, _ = adam.update(grads, adam.init(params), params)
    want = optax.apply_updates(params, updates)
    want["layers"]["router_bias"] = ref.bias_after(params, seen["load"], config.bias_rate)
    for name, leaf in jax.tree_util.tree_flatten_with_path(want)[0]:
        have = new
        for key in name:
            have = have[key.key]
        np.testing.assert_allclose(np.asarray(have), np.asarray(leaf), rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(name))
    assert "router_load" not in aux
    assert float(aux["router_bias_abs_max"]) == pytest.approx(
        float(jnp.abs(want["layers"]["router_bias"]).max()))


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def test_move_moves_every_bias_against_its_load():
    config = _config()
    load = jnp.asarray([[9.0] + [1.0] * EXPERTS, [1.0] * EXPERTS + [9.0], [2.0] * (EXPERTS + 1)])
    drawn = {"layers": {cca_moe.BIAS: jnp.zeros((LAYERS, EXPERTS + 1))}}
    moved, aux = cca_moe.move(config, drawn, {"router_load": load, "ce": jnp.float32(1.0)})
    bias = np.asarray(moved["layers"][cca_moe.BIAS])
    assert bias[0, 0] == np.float32(-1e-2) and (bias[0, 1:] == np.float32(1e-2)).all()
    assert bias[1, -1] == np.float32(-1e-2) and (bias[2] == 0).all()      # the skip is a choice
    assert set(aux) == {"ce", "router_bias_abs_max"}
    labels = cca_moe.trained_labels(_params())
    fixed = [jax.tree_util.keystr(path) for path, label in
             jax.tree_util.tree_flatten_with_path(labels)[0] if label == "fixed"]
    assert fixed == ["['layers']['router_bias']"]


# ---- the shares, the skip, the carry, the table --------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer_and_the_skip_adds_nothing(batch):
    """One layer: the shares of experts 0 to 4 and 4 to 8, what every chip
    computes alike (the merge of a layer whose experts gave nothing) counted
    once, add up to the uncut reference's whole layer."""
    file = {**FILE, "num_hidden_layers": 1}
    whole = seeded_cca.make_params(seeded_cca.param_shapes(file, VOCAB, EXPERTS), 9, 2,
                                   bias_std=0.05)
    whole["layers"]["router_bias"][:, -1] += 0.1
    seq = batch["seq"]

    def share(lo, hi):
        mine = jax.tree_util.tree_map(lambda a: a, whole)
        for name in ("w_gate", "w_up", "w_down"):
            mine["layers"][name] = whole["layers"][name][:, lo:hi]
        config = _config((lo, hi), num_layers=1)
        return jax.jit(lambda p: cca_moe.hidden_states(config, "cpu", p, seq))(mine)

    def reference(lo, hi):
        p = jax.tree_util.tree_map(lambda a: a[0], whole["layers"])
        p = {**p, **{name: p[name][lo:hi] for name in ("w_gate", "w_up", "w_down")}}
        with jax.default_matmul_precision("highest"):
            return jnp.stack([ref.layer_row(
                p, whole["embed"][seq[b]], jnp.zeros((T, 16)), seq[b] > 0,
                _dims((lo, hi)), ref.SOUND)[0] for b in range(ROWS)])

    (first, first_stats), (second, second_stats) = share(0, 4), share(4, 8)
    alike = reference(0, 0)                # no expert held: the merge of nothing
    np.testing.assert_allclose(np.asarray(first + second - alike),
                               np.asarray(reference(0, EXPERTS)), rtol=2e-5, atol=2e-6)
    skipped = int(first_stats["skip_assignments"].sum())
    assert skipped == int(second_stats["skip_assignments"].sum()) > 0
    real = int((seq > 0).sum())
    assert (int(first_stats["held_assignments"].sum()) + int(second_stats["held_assignments"].sum())
            + skipped) == real
    # a token of the other chip's, or the skip's, adds nothing here: the merge alone
    load = np.asarray(first_stats["load"][0])
    assert load.sum() == real and load[-1] == skipped


def test_a_token_that_takes_the_skip_moves_no_experts_gradient(params, batch):
    """With a bias that sends every token to the skip, no expert's weights have
    a gradient, and the layers' output is the merges' alone."""
    everyone = jax.tree_util.tree_map(jnp.asarray, params)
    everyone["layers"]["router_bias"] = everyone["layers"]["router_bias"].at[:, -1].set(10.0)
    _, aux, grads = _program(_config(), everyone, batch)
    real = int((batch["seq"] > 0).sum())
    assert int(aux["moe_skip_assignments"]) == LAYERS * real and int(aux["moe_assignments"]) == 0
    for name in ("w_gate", "w_up", "w_down"):
        assert float(jnp.abs(grads["layers"][name]).max()) == 0.0
    assert float(jnp.abs(grads["layers"]["wq"]).max()) > 0.0


def test_the_carry_reaches_the_next_layer_and_not_layer_0(params, batch):
    _, _, grads = _program(_config(), params, batch)
    gamma = np.abs(np.asarray(grads["layers"]["gamma"])).max(axis=-1)
    assert gamma[0] == 0.0 and (gamma[1:] > 0).all()        # layer 0 reads a state of zeros
    # layer 0's down-projection is read by layer 1's router through the carry:
    # with gamma zero from layer 1 on, its gradient is another
    cut = jax.tree_util.tree_map(jnp.asarray, params)
    cut["layers"]["gamma"] = cut["layers"]["gamma"].at[1:].set(0.0)
    _, _, alone = _program(_config(), cut, batch)
    assert _worst(alone["layers"]["w_d"][0], grads["layers"]["w_d"][0]) > 1e-3


def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(params, batch):
    config = _config()

    def loss(embed, head):
        x, _ = cca_moe.hidden_states(config, "cpu", {**params, "embed": embed}, batch["seq"])
        return blocks.masked_ce(config, x, params["final_norm"], head, batch["target"])

    table = jnp.asarray(params["embed"])
    as_embedding, as_head = jax.jit(jax.grad(loss, argnums=(0, 1)))(table, table)
    _, _, grads = _program(config, params, batch)
    assert float(jnp.abs(as_embedding).max()) > 0 and float(jnp.abs(as_head).max()) > 0
    np.testing.assert_allclose(np.asarray(grads["embed"]), np.asarray(as_embedding + as_head),
                               rtol=1e-5, atol=1e-7)
    assert "head" not in params and cca_moe.count_params(config) == sum(
        a.size for path, a in jax.tree_util.tree_flatten_with_path(params)[0]
        if path[-1].key != cca_moe.BIAS)


# ---- the mixing stage ----------------------------------------------------------

def _mixed(config, p, q0, k0, v1, v2):
    return jax.jit(lambda *a: cca_moe.mix(config, *a, p))(q0, k0, v1, v2)


@pytest.fixture(scope="module")
def mix_inputs(params):
    rng = np.random.default_rng(3)
    layer = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]), params["layers"])
    draw = lambda width: jnp.asarray(rng.standard_normal((2, T, width)), jnp.float32)  # noqa: E731
    return layer, (draw(32), draw(16), draw(8), draw(8))


@pytest.mark.parametrize("taps", [(2, 2), (3, 2)])
@pytest.mark.parametrize("changed", [0, 1, 2, 3])
def test_the_mixing_stage_is_causal(taps, changed):
    """A change at position ``t`` of any input moves nothing before ``t`` (the
    value's delayed half nothing before ``t + 1``)."""
    file = {**FILE, "cca_time0": taps[0], "cca_time1": taps[1]}
    layer = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]), _params(file=file)["layers"])
    config = _config(conv_time0=taps[0], conv_time1=taps[1])
    rng = np.random.default_rng(4)
    inputs = [jnp.asarray(rng.standard_normal((2, T, w)), jnp.float32) for w in (32, 16, 8, 8)]
    at = 17
    other = list(inputs)
    other[changed] = inputs[changed].at[:, at].add(1.0)
    before, after = _mixed(config, layer, *inputs), _mixed(config, layer, *other)
    first = at + (changed == 3)
    for a, b in zip(before, after):
        assert (np.asarray(a[:, :first]) == np.asarray(b[:, :first])).all()
    moved = [float(jnp.abs(a[:, first] - b[:, first]).max()) for a, b in zip(before, after)]
    assert max(moved) > 0
    # and no further than the taps reach: q and k after t + (K0 - 1) + (K1 - 1) are as they were
    reach = at + taps[0] + taps[1] - 1
    for a, b in zip(before[:2], after[:2]):
        assert (np.asarray(a[:, reach:]) == np.asarray(b[:, reach:])).all()


def test_a_rows_first_position_sees_zeros(mix_inputs):
    """Position 0 reads nothing before the row: its ``q`` and ``k`` are what one
    tap of each convolution gives, and the delayed half of its value is zero."""
    layer, (q0, k0, v1, v2) = mix_inputs
    config = _config()
    q, k, v = _mixed(config, layer, q0, k0, v1, v2)
    assert (np.asarray(v[:, 0, 1]) == 0).all() and (np.asarray(v[:, 1, 1]) == np.asarray(v2[:, 0])).all()
    assert (np.asarray(v[:, :, 0]) == np.asarray(v1)).all()
    z = jnp.concatenate([q0, k0], axis=-1)[:, 0]
    z1 = (layer["conv0_b"] + layer["conv0_w"][:, -1] * z).reshape(2, 6, 8)
    z2 = layer["conv1_b"] + jnp.einsum("bgd,gde->bge", z1, layer["conv1_w"][:, -1],
                                       precision="highest")
    m_q = 0.5 * (q0[:, 0].reshape(2, 2, 2, 8) + k0[:, 0].reshape(2, 2, 1, 8))
    q1 = z2[:, :4] + m_q.reshape(2, 4, 8)
    want = np.sqrt(8.0) * q1 / jnp.linalg.norm(q1, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(q[:, 0]), np.asarray(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(q, axis=-1)), np.sqrt(8.0), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(k, axis=-1)),
        np.broadcast_to(np.sqrt(8.0) * np.abs(np.asarray(layer["tau"])), (2, T, 2)), rtol=1e-5)


# ---- the experts' seam -----------------------------------------------------------

def test_a_choice_beyond_the_experts_is_counted_and_held_by_no_pass():
    config = _config((0, 4))
    chosen = jnp.asarray([[0], [3], [8], [8], [5], [8]], jnp.int32)      # 8: the skip
    real = jnp.asarray([True, True, True, False, True, True])
    load = experts_module.load_of(config, chosen, real, config.choices)
    assert load.tolist() == [1, 0, 0, 1, 0, 1, 0, 0, 2]
    stats = experts_module.load_stats(config, load)
    assert {k: int(v) for k, v in stats.items()} == {
        "assignments": 3, "held_assignments": 2, "held_load_max": 1, "skip_assignments": 2}
    assert "skip_assignments" not in experts_module.load_stats(config, load[:8])
    assert experts_module.pass_plan(config, 64) == (64, 1)     # one choice a token, half held
    assert experts_module.pass_plan(_config((0, 2)), 128) == (128, 1)


@pytest.mark.parametrize("bad", [dict(num_heads=3), dict(num_kv_heads=1, num_heads=4),
                                 dict(experts_per_token=2),
                                 dict(rotary_fraction=0.4), dict(conv_time0=0)])
def test_the_configuration_refuses_what_the_block_cannot_be(bad):
    with pytest.raises(ValueError):
        _config(**bad)


# ---- through the template --------------------------------------------------------

def test_a_toy_trains_and_scores_through_the_templates_entry_points():
    config = _config((0, EXPERTS), num_layers=2, epochs=12, learning_rate=1e-2, batch_size=8,
                     compute_dtype="bfloat16", attention="auto")
    sequences = np.tile((np.arange(T) % 12 + 1).astype(np.int32), (16, 1))
    for row in range(16):
        sequences[row] = np.roll(sequences[row], row)
    trained, losses = train_sasrec(config, sequences, _mesh(), log_every=1)
    assert losses[-1] < 0.7 * losses[0]
    scores = score_next_items_batch(trained, config, [sequences[0, :20]])
    assert scores.shape == (1, VOCAB - 1) and int(scores[0].argmax()) + 1 == sequences[0, 20]
    attrs = fit_attrs(config, 1, 1, 8, "cpu")
    assert (attrs["backbone"], attrs["latent_q_width"], attrs["latent_kv_width"]) == ("cca_moe", 32, 16)
    assert (attrs["conv_time0"], attrs["conv_time1"], attrs["router_width"]) == (2, 2, 16)
    assert (attrs["skip_choices"], attrs["experts_held_share"], attrs["head_tied"]) == (1, 1.0, 1)
    assert attrs["rematerialised"] == "mixer and experts" and attrs["experts_per_token"] == 1


def test_the_engine_takes_the_backbone_at_the_cells_sizes_and_engine_json_round_trips():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    with open(os.path.join(ROOT, "benchmarks", "configs", "zaya1-8b-ep2.json")) as f:
        file = json.load(f)
    engine_params = file["engine"]["algorithms"][0]["params"]
    config = SASRecAlgorithm(Params(engine_params))._config(
        file["data"]["items"], file["engine"]["preparator"]["params"]["maxLen"])
    assert type(config) is CcaMoEConfig and config.vocab == file["vocab_size"]
    assert cca_moe.count_params(config) == file["parameters"]["total"]
    assert (config.q_width, config.kv_width, config.choices, config.held) == (1024, 256, 17, 8)
    assert config.num_layers == file["num_hidden_layers"] == len(file["layer_types"])
    shapes = cca_moe.param_shapes(config)
    assert shapes == seeded_cca.param_shapes(file, file["vocab_size"], file["num_local_experts"])
    assert experts_module.pass_plan(config, 2 * config.max_len) == (32768, 1)
    # a configuration written out as engine parameters reads back as itself
    written = {name: getattr(config, field) for name, field in cca_moe.ENGINE_PARAMS.items()}
    written = json.loads(json.dumps({**written, "backbone": "cca_moe", "learningRate": 1e-05,
                                     "batchSize": 2, "epochs": 1}))
    again = SASRecAlgorithm(Params(written))._config(file["data"]["items"], config.max_len)
    assert again == dataclasses.replace(config)
