"""The sequence template's trainer, over whichever backbone a configuration
names (``BACKBONES``: name -> module).

The long-context model family: per-user event histories (the reference
streams these unboundedly through ``PEvents``; SURVEY.md section 5.7) become
item sequences, and a causal decoder predicts the next item. TPU-first
design:

- batch shards over the mesh ``data`` axis (dp); the SEQUENCE dim shards
  over the ``seq`` axis (sp) -- attention across shards runs as ring
  attention (``parallel.ring_attention``), K/V blocks hopping the ICI ring,
  so histories longer than one chip's memory train without replication;
- everything position-local (embedding lookup, LayerNorm, the pointwise
  FFN) needs no communication under sp: XLA keeps it shard-local.

A backbone is one module that exports its configuration's dataclass
(``CONFIG``), ``ENGINE_PARAMS`` (engine parameter -> field, which
``engine.py`` reads), ``init_params(c, rng)``, ``make_loss(c, mesh)``,
``score_last(c, params, seqs, last)``, ``fit_attrs(c, rows, platform)`` (its
part of the fit's span, in the order the ``seq_fit:`` line prints it) and,
where the loss's gradient does not train every leaf, ``trained_labels(params)``
and ``move(c, params, aux)`` (the latent backbone's routers' biases, and the
compressed-convolution backbone's). Nothing here knows a backbone beyond that:
what a backbone's layers hand one another beside the residual stream (the
compressed-convolution backbone's router state, the carry of its layer scan)
stays inside its module, and the contract is as it was.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel.mesh import (
    check_steps_ran,
    fetch_global,
    one_step_in_flight,
    put_global,
)
from predictionio_tpu.models.sequence import (
    blocks, cca_moe, hybrid, latent_moe, looped, sasrec, sparse_moe, window_moe,
)
from predictionio_tpu.models.sequence.sasrec import SASRec, SASRecConfig  # noqa: F401

logger = logging.getLogger("pio.sequence")

#: the ``backbone`` engine parameter -> the module that is that backbone
BACKBONES = {"sasrec": sasrec, "looped": looped, "sparse_moe": sparse_moe,
             "hybrid_linear": hybrid, "latent_moe": latent_moe, "window_moe": window_moe,
             "cca_moe": cca_moe}


def backbone_named(config) -> tuple:
    """``(name, module)`` of the backbone whose configuration ``config`` is."""
    for name, module in BACKBONES.items():
        if type(config) is module.CONFIG:
            return name, module
    raise TypeError(f"{type(config).__name__} is no backbone's configuration")


def make_train_step(loss_fn, optimizer, move=None):
    """One optimizer step of ``loss_fn(params, batch, rng) -> (loss, aux)``.
    ``move(params, aux) -> (params, aux)`` is the rule of a backbone's leaves
    that the optimizer leaves alone (``untrained_of``): it moves them from
    what the step's own loss counted, inside the same program."""
    def train_step(params, opt_state, batch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng)
        with jax.named_scope(blocks.SCOPE_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if move is not None:
                params, aux = move(params, aux)
        return params, opt_state, loss, aux

    return train_step


def backbone_of(config, mesh):
    """``(init, loss_fn)`` of the backbone ``config`` names: ``init(rng, t)``
    gives the parameter tree, ``loss_fn(params, batch, rng)`` the objective
    and what the fit's span reports of it."""
    module = backbone_named(config)[1]
    return (lambda rng, t: module.init_params(config, rng)), module.make_loss(config, mesh)


def untrained_of(config):
    """``(labels, move)`` of a backbone with leaves that the loss's gradient
    does not train, else ``(None, None)``. ``labels(params)`` names every leaf
    ``"train"`` or ``"fixed"``: a fixed leaf gets no update from the optimizer
    and no moments. ``move(params, aux) -> (params, aux)``, where there is one,
    moves the fixed leaves after the step from what its loss counted."""
    module = backbone_named(config)[1]
    move = getattr(module, "move", None)
    return (getattr(module, "trained_labels", None),
            move and functools.partial(move, config))


def optimizer_of(config):
    """Adam at the configuration's learning rate, over the leaves the loss
    trains (``untrained_of`` names the others)."""
    adam = optax.adam(config.learning_rate)
    labels = untrained_of(config)[0]
    if labels is not None:
        return optax.multi_transform({"train": adam, "fixed": optax.set_to_zero()}, labels)
    return adam


def _tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))


def make_fit(config, mesh):
    """What the fit runs, for ``train_sasrec`` and for a caller that steps it
    itself: ``(init, place, step_fn, seq_shard)``. ``init(rng, t)`` draws the
    parameters; ``place(params)`` puts them on the mesh and makes Adam's
    state; ``step_fn(params, opt_state, batch, rng)`` is one jitted optimizer
    step (both donated) returning ``(params, opt_state, loss, aux)``;
    ``seq_shard`` is the sharding of a batch's ``seq`` and ``target``."""
    init, loss_fn = backbone_of(config, mesh)
    rep = NamedSharding(mesh, P())
    dp_axis = "data" if "data" in mesh.axis_names else None
    sp_axis = "seq" if "seq" in mesh.axis_names else None
    seq_shard = NamedSharding(mesh, P(dp_axis, sp_axis))
    optimizer = optimizer_of(config)

    def place(params):
        # put_global/jitted-init: on multi-process meshes every rank holds
        # identical params (same PRNGKey); placement and Adam-state creation
        # must not touch non-addressable shards eagerly
        params = jax.tree_util.tree_map(lambda a: put_global(a, rep), params)
        # Adam's state replicated on the mesh like the parameters, its step
        # count too: a count left off the mesh is another input type than the
        # one a step returns, and the second step of every fit compiled again
        return params, jax.jit(optimizer.init, out_shardings=rep)(params)

    step_fn = jax.jit(
        make_train_step(loss_fn, optimizer, untrained_of(config)[1]),
        in_shardings=(rep, rep, {"seq": seq_shard, "target": seq_shard}, None),
        out_shardings=(rep, rep, rep, rep),
        donate_argnums=(0, 1),
    )
    return init, place, step_fn, seq_shard


def train_sasrec(
    config,                  # a backbone's configuration (``BACKBONES``)
    sequences: np.ndarray,   # [N, T] int32 padded item ids (0 = pad)
    mesh,
    log_every: int = 0,
):
    """Train on next-item prediction; returns (params pytree on host, losses).

    Inputs/targets are the sequence and its left-shift: position t predicts
    the item at t+1. The [N, T] matrix shards over (data, seq).
    """
    from predictionio_tpu.obs.trace import global_tracer

    t = sequences.shape[1]
    if t != config.max_len:
        raise ValueError(f"sequences padded to {t}, config.max_len={config.max_len}")
    sp = mesh.shape.get("seq", 1)
    if t % sp:
        raise ValueError(f"max_len={t} must divide over seq axis size {sp}")

    init, place, step_fn, seq_shard = make_fit(config, mesh)
    rng = jax.random.PRNGKey(config.seed)
    params, opt_state = place(init(rng, t))

    inputs = sequences.astype(np.int32)
    targets = np.zeros_like(inputs)
    targets[:, :-1] = inputs[:, 1:]

    np_rng = np.random.default_rng(config.seed)
    n = inputs.shape[0]
    dp = mesh.shape.get("data", 1)
    losses = []
    step = 0
    aux = {}
    first_loss = loss = None
    span_attrs = fit_attrs(config, _tree_bytes(params), _tree_bytes(opt_state),
                           min(config.batch_size, n) // dp * dp,
                           mesh.devices.flat[0].platform)
    # now: the span adds its own attributes to the same dict
    on_the_line = "".join(f" {k}={v}" for k, v in span_attrs.items()
                          if k not in _NOT_ON_THE_LINE)
    with global_tracer().span("seq.fit", attrs=span_attrs) as span:
        for _ in range(config.epochs):
            order = np_rng.permutation(n)
            for start in range(0, n, config.batch_size):
                take = order[start : start + config.batch_size]
                usable = (take.size // dp) * dp
                if not usable:
                    continue
                take = take[:usable]
                # identical permutation on every rank (same seed): put_global
                # hands each process exactly its addressable (data, seq) shards
                batch = {
                    "seq": put_global(inputs[take], seq_shard),
                    "target": put_global(targets[take], seq_shard),
                }
                params, opt_state, loss, aux = step_fn(
                    params, opt_state, batch, jax.random.fold_in(rng, step)
                )
                one_step_in_flight(mesh, loss)
                if first_loss is None:
                    first_loss = loss
                step += 1
                if log_every and step % log_every == 0:
                    losses.append(float(loss))
        check_steps_ran(step, n, dp, "sequence")
        span.set_attr("steps", step)
        last = {name: np.asarray(fetch_global(value)).tolist()
                for name, value in aux.items() if value.ndim <= 1}  # of the last step
        logger.info(
            "seq_fit: platform=%s devices=%d backbone=%s steps=%d"
            " first_loss=%.5f last_loss=%.5f%s",
            mesh.devices.flat[0].platform, mesh.devices.size,
            span_attrs["backbone"], step, float(first_loss), float(loss),
            on_the_line + "".join(f" {k}={v:.6g}" for k, v in last.items() if np.ndim(v) == 0),
        )
        for name, value in last.items():
            span.set_attr(name, value)
    return jax.tree_util.tree_map(fetch_global, params), losses


#: the span's attributes the ``seq_fit:`` line names itself or leaves out; it
#: repeats the others as the backbone's ``fit_attrs`` orders them
_NOT_ON_THE_LINE = ("backbone", "param_bytes", "state_bytes", "layers", "passes",
                    "rematerialised", "head")


def fit_attrs(config, param_bytes: int, opt_state_bytes: int, rows: int,
              platform: str) -> dict:
    """What the fit's span says of the model it trains and of how a step on
    ``rows`` rows is worked on ``platform``."""
    name, module = backbone_named(config)
    return {
        "backbone": name,
        "param_bytes": param_bytes,
        # weights, their gradients and the optimizer's moments
        "state_bytes": 2 * param_bytes + opt_state_bytes,
        **module.fit_attrs(config, rows, platform),
    }


def _score_fn(config):
    """The backbone's ``score_last`` as one jitted program (the forward and
    the vocab projection: no dispatch each, per query), cached per config."""
    if config not in _SCORE_CACHE:
        module = backbone_named(config)[1]
        _SCORE_CACHE[config] = jax.jit(functools.partial(module.score_last, config))
    return _SCORE_CACHE[config]


_SCORE_CACHE: dict = {}


def score_next_items_batch(params, config, prefixes) -> np.ndarray:
    """Scores over the item vocab for the next item after each prefix.

    ``prefixes``: list of 1-D id arrays (no padding); each uses its last
    max_len entries. Returns [B, num_items] (column i scores item id i+1 --
    id 0 is the padding token and is dropped). The batch pads to the next
    power of two internally, so arbitrary caller batch sizes compile at
    most log2(max_B) distinct programs (<2x padded compute) instead of one
    per size.
    """
    t = config.max_len
    b = len(prefixes)
    padded_b = 1 << (b - 1).bit_length() if b > 1 else 1
    seqs = np.zeros((padded_b, t), np.int32)
    last = np.zeros((padded_b,), np.int32)
    for i, p in enumerate(prefixes):
        tail = np.asarray(p, np.int32)[-t:]
        seqs[i, : len(tail)] = tail
        last[i] = max(len(tail) - 1, 0)
    scores = np.asarray(
        _score_fn(config)(params, jnp.asarray(seqs), jnp.asarray(last))
    )
    return scores[:b, 1:]


def score_next_items(params, config, prefix: np.ndarray) -> np.ndarray:
    """Single-prefix convenience over :func:`score_next_items_batch`."""
    return score_next_items_batch(params, config, [prefix])[0]
