"""The leaf scopes under ``attention``, ``moe/experts`` and ``gram``: names
only. Every leaf is in the program under its stage, forward and (for the
sequence steps) in the backward pass, as ``benchmarks/scopes_leaf.py`` takes an
``op_name`` apart; ``again`` marks the experts' forward half of the backward
rule and nothing else; with the package's programs (interpreted here) a pass's
rows come back by runs, under the same leaves, ``take``'s backward pass all
``sum``; and the same builder with ``jax.named_scope`` patched out
in the test (the program has no switch) gives the same loss, gradients and
updated state bit for bit."""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmarks import scopes_leaf
from predictionio_tpu.models.sequence import experts as experts_module
from predictionio_tpu.models.sequence import looped, model as seq_model, sparse_moe
from predictionio_tpu.parallel import als
import test_als
from test_als import synthetic  # noqa: F401  (a fixture)

T, ROWS = 32, 2
CONFIGS = {
    "looped": looped.LoopedConfig(
        num_items=50, max_len=T, num_layers=2, ut_steps=2, batch_size=ROWS,
        attention="plain"),
    "sparse_moe": sparse_moe.SparseMoEConfig(
        num_items=50, max_len=T, num_layers=2, batch_size=ROWS, experts_held=(0, 4),
        index_topk=8, moe_chunk=32, attention="plain"),
}
#: the same step with the package's programs: the experts' rows back by runs
CONFIGS["sparse_moe-programs"] = dataclasses.replace(CONFIGS["sparse_moe"], attention="flash")
#: (stage, leaf) -> the phases that hold it. The indexer and the selection pass
#: no gradient and are not worked again (the rematerialised layer starts from the
#: kept bits); the sort is integers; a pass's sum back onto its tokens is
#: not needed again, its rows and grouped matmuls are
ALL = ("forward", "recomputed", "backward")
_ATTENTION = {("attention", leaf): ALL for leaf in ("norm", "qkv", "rope", "kernel", "out")}
LEAVES = {
    "looped": _ATTENTION | {("mlp", "norm"): ALL},
    "sparse_moe": _ATTENTION | {
        ("attention", "index"): ALL[:1], ("attention", "select"): ALL[:1],
        ("moe", "norm"): ALL, ("experts", "sort"): ALL[:2], ("experts", "take"): ALL,
        ("experts", "grouped"): ALL, ("experts", "give"): ALL[::2], ("experts", "sum"): ALL[::2]},
}
#: by runs the transpose of ``take`` is one sum that writes the compute dtype
LEAVES["sparse_moe-programs"] = LEAVES["sparse_moe"] | {("experts", "take"): ALL[:2]}


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def _step_and_args(backbone: str):
    config = CONFIGS[backbone]
    init, place, step_fn, _ = seq_model.make_fit(config, _mesh())
    params, opt_state = place(init(jax.random.PRNGKey(3), T))
    rows = np.random.default_rng(7).integers(1, 51, (ROWS, T + 1)).astype(np.int32)
    rows[0, :5] = 0                       # a padded head, as a short history has
    batch = {"seq": jnp.asarray(rows[:, :-1]),
             "target": jnp.asarray(rows[:, 1:] * (rows[:, :-1] > 0))}
    return step_fn, (params, opt_state, batch, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def op_names():
    """The ``op_name``s of each backbone's compiled step, traced once."""
    found = {}
    for backbone in CONFIGS:
        step_fn, args = _step_and_args(backbone)
        found[backbone] = set(re.findall(r'op_name="([^"]*)"',
                                         step_fn.lower(*args).compile().as_text()))
    return found


@pytest.mark.parametrize("backbone", list(CONFIGS))
def test_every_leaf_is_under_its_stage_forward_and_backward(op_names, backbone):
    places = {scopes_leaf.place_of(name) for name in op_names[backbone]} - {None}
    seen = {(p.stage, p.leaf, p.phase) for p in places}
    for (stage, leaf), phases in LEAVES[backbone].items():
        assert tuple(p for p in ALL if (stage, leaf, p) in seen) == phases, (stage, leaf)
    # a leaf is read under its own stage alone, and the exit's norm is the exit's
    assert {(p.stage, p.leaf) for p in places if p.leaf} == set(LEAVES[backbone])
    assert ("exit", None, "forward") in seen and ("exit", None, "backward") in seen
    assert all(p.top.startswith("pass") or p.stage is None for p in places)


def test_again_marks_the_forward_half_of_the_experts_backward_rule(op_names):
    # (the CPU compiler leaves a reduction's inner computation a name cut short)
    names = {n for n in op_names["sparse_moe"] if "seq." in n}
    again = [n for n in names if experts_module.SCOPE_AGAIN in n.split("/")]
    assert again and all(
        "transpose(jvp(seq.pass1))" in n and "/moe/experts/" in n
        and scopes_leaf.place_of(n).phase == "recomputed" for n in again)
    # the forward half: the rows taken and the grouped matmuls; a pass's sum is
    # not needed again
    assert {scopes_leaf.place_of(n).leaf for n in again} == {None, "take", "grouped"}
    # the pullback is outside it, whatever the transposition makes of the name
    pulled = [n for n in names if f"transpose({experts_module.SCOPE_AGAIN})" in n]
    assert pulled and all(scopes_leaf.place_of(n).phase == "backward" for n in pulled)
    assert {scopes_leaf.place_of(n).leaf for n in pulled} == {"take", "give", "sum"}
    by_runs = [scopes_leaf.place_of(n) for n in op_names["sparse_moe-programs"]
               if f"transpose({experts_module.SCOPE_AGAIN})" in n]
    assert {p.leaf for p in by_runs if p is not None} == {"give", "sum"}
    assert not [n for n in names if experts_module.SCOPE_AGAIN in n and "transpose(" not in n]
    assert not [n for n in op_names["looped"] if experts_module.SCOPE_AGAIN in n.split("/")]


@pytest.mark.parametrize("backbone", list(CONFIGS))
def test_the_step_is_the_unscoped_step_bit_for_bit(monkeypatch, backbone):
    """Loss, gradients, updated parameters and Adam's state of the builder as
    it is against the same builder traced with every ``named_scope`` out."""
    def run():
        step_fn, args = _step_and_args(backbone)
        _, loss_fn = seq_model.backbone_of(CONFIGS[backbone], _mesh())
        (loss, _), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            args[0], args[2], args[3])
        names = re.findall(r'op_name="([^"]*)"', step_fn.lower(*args).compile().as_text())
        return names, jax.device_get((loss, grads, step_fn(*args)[:3]))

    names, got = run()
    assert any("seq.pass1" in n and "/attention/qkv/" in n for n in names)
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare_names, want = run()
    assert not [n for n in bare_names if "seq." in n or "/attention/" in n]
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) > 20
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


# ---- the ALS iteration, both layouts --------------------------------------

@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
@pytest.mark.parametrize("sharding", ["replicated", "model"])
def test_every_bucket_names_its_gather_and_its_products(synthetic, worked, sharding,  # noqa: F811
                                                        implicit):
    program, args = test_als.TestDeviceScopes._program_and_args(synthetic, worked, sharding, implicit)
    stacks = set(test_als.TestDeviceScopes._name_stacks(program.trace(*args).jaxpr.jaxpr))
    for side, blocks in zip(als.SCOPE_HALF_STEP.values(), args):
        for bucket in range(len(blocks)):
            gram = f"{side}/{als.SCOPE_BUCKET.format(bucket)}/{als.SCOPE_GRAM}"
            under = {s[len(gram) + 1:].split("/")[0] for s in stacks
                     if s.startswith(gram + "/")}
            # the exchange stays the stage's own child, beside the gather
            want = {als.SCOPE_GATHER, als.SCOPE_PRODUCTS}
            assert under == (want | {als.SCOPE_EXCHANGE} if sharding == "model" else want)
    # as the benchmark's reader takes the names apart
    places = {scopes_leaf.place_of(s + "/mul") for s in stacks} - {None}
    assert {p.leaf for p in places if p.stage == als.SCOPE_GRAM} >= {"gather", "products"}
    assert not [p for p in places if p.leaf and p.stage != als.SCOPE_GRAM
                and p.leaf != als.SCOPE_EXCHANGE]


@pytest.mark.parametrize("sharding", ["replicated", "model"])
def test_implicit_factors_equal_the_unscoped_programs_bit_for_bit(
        synthetic, monkeypatch, worked, sharding):  # noqa: F811
    """PR 24's test holds the explicit iteration to this; the implicit tail
    (the primal blocks and, at rank 6, no dual one) is held here."""
    scoped, args = test_als.TestDeviceScopes._program_and_args(synthetic, worked, sharding, True)
    copy = lambda tree: jax.tree_util.tree_map(lambda a: a + 0, tree)  # noqa: E731  (donated)
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare, _ = test_als.TestDeviceScopes._program_and_args(synthetic, worked, sharding, True)
        stacks = set(test_als.TestDeviceScopes._name_stacks(bare.trace(*args).jaxpr.jaxpr))
        assert not any("als." in stack for stack in stacks)
        want = bare(*copy(args))
    for got, unscoped in zip(scoped(*copy(args)), want):
        assert np.array_equal(np.asarray(got), np.asarray(unscoped))
