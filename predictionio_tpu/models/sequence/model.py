"""Self-attentive sequential recommender (SASRec-style) on the device mesh.

The long-context model family: per-user event histories (the reference
streams these unboundedly through ``PEvents``; SURVEY.md section 5.7) become
item sequences, and a causal transformer predicts the next item. TPU-first
design:

- batch shards over the mesh ``data`` axis (dp); the SEQUENCE dim shards
  over the ``seq`` axis (sp) -- attention across shards runs as ring
  attention (``parallel.ring_attention``), K/V blocks hopping the ICI ring,
  so histories longer than one chip's memory train without replication;
- everything position-local (embedding lookup, LayerNorm, the pointwise
  FFN) needs no communication under sp: XLA keeps it shard-local;
- next-item loss is full-softmax cross-entropy against the tied item
  embedding matrix -- one [B*T, D] x [D, V] matmul on the MXU.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass

import flax.linen as nn
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
from predictionio_tpu.models.sequence import hybrid, latent_moe, looped, sparse_moe
from predictionio_tpu.models.sequence.hybrid import HybridConfig
from predictionio_tpu.models.sequence.latent_moe import LatentMoEConfig
from predictionio_tpu.models.sequence.looped import LoopedConfig
from predictionio_tpu.models.sequence.sparse_moe import SparseMoEConfig
from predictionio_tpu.ops.flash_attention import flash_attention
from predictionio_tpu.parallel.ring_attention import plain_attention, ring_attention
from predictionio_tpu.parallel.ulysses import ulysses_attention

logger = logging.getLogger("pio.sequence")


@dataclass(frozen=True)
class SASRecConfig:
    num_items: int              # real item vocab; id 0 is reserved for padding
    max_len: int = 64
    embed_dim: int = 32
    num_heads: int = 2
    num_blocks: int = 2
    ffn_dim: int = 64
    dropout: float = 0.0
    learning_rate: float = 1e-3
    batch_size: int = 256
    epochs: int = 10
    seed: int = 0
    seq_parallel: str = "ring"  # "ring" | "ulysses" (all-to-all head scatter)
    #: intra-shard attention: "auto" = Pallas flash attention on TPU, the
    #: materialized-score reference elsewhere; "flash" / "plain" force it
    attention: str = "auto"

    def __post_init__(self):
        if self.embed_dim % self.num_heads:
            raise ValueError(
                f"embed_dim={self.embed_dim} must be divisible by "
                f"num_heads={self.num_heads}"
            )
        if self.attention not in ("auto", "flash", "plain"):
            raise ValueError(
                f"attention={self.attention!r} must be one of"
                " 'auto' | 'flash' | 'plain'"
            )
        if self.seq_parallel not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel={self.seq_parallel!r}: want 'ring' or 'ulysses'"
            )

    @property
    def vocab(self) -> int:
        return self.num_items + 1  # +1 for the padding id 0


def attend(q, k, v, pad_mask, mesh, attention: str, seq_parallel: str):
    """Causal attention with the padded keys masked, q, k, v [B, T, H, D],
    mesh-aware: ring or Ulysses attention when the mesh has a >1 ``seq``
    axis, else the Pallas flash kernel or the materialized-score reference
    (``attention``: "auto" | "flash" | "plain"). Both backbones call it."""
    # the platform the program is built for: the mesh's, when there is one
    backend = (
        mesh.devices.flat[0].platform if mesh is not None
        else jax.default_backend()
    )
    use_flash = attention == "flash" or (attention == "auto" and backend == "tpu")
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        if seq_parallel == "ulysses":
            # ulysses gathers full sequences per chip, so the flash
            # kernel slots in as its local attention
            return ulysses_attention(q, k, v, mesh, axis_name="seq",
                                     causal=True, mask=pad_mask,
                                     use_flash=use_flash)
        # ring attention IS the online softmax across shards; its
        # per-step scores are already [Tl, Tl] blocks, so "flash"
        # asks for nothing it does not already do
        return ring_attention(q, k, v, mesh, axis_name="seq",
                              causal=True, mask=pad_mask)
    if use_flash:
        # O(T*D) memory: scores never materialize (ops/flash_attention)
        return flash_attention(
            q, k, v, pad_mask, causal=True,
            interpret=backend != "tpu",
        )
    return plain_attention(q, k, v, causal=True, mask=pad_mask)


class _MultiHeadSelfAttention(nn.Module):
    """Causal MHA whose score computation is mesh-aware (:func:`attend`)."""

    config: SASRecConfig
    mesh: object = None

    @nn.compact
    def __call__(self, x, pad_mask):
        c = self.config
        b, t, d = x.shape
        h = c.num_heads
        head_dim = d // h
        qkv = nn.Dense(3 * d, use_bias=False, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        reshape = lambda a: a.reshape(b, t, h, head_dim)
        q, k, v = reshape(q), reshape(k), reshape(v)
        out = attend(q, k, v, pad_mask, self.mesh, c.attention, c.seq_parallel)
        return nn.Dense(d, use_bias=False, name="proj")(out.reshape(b, t, d))


class SASRec(nn.Module):
    config: SASRecConfig
    mesh: object = None

    @nn.compact
    def __call__(self, seq, deterministic: bool = True):
        """seq: [B, T] int32, 0 = padding. Returns hidden states [B, T, D]."""
        c = self.config
        pad_mask = seq > 0
        x = nn.Embed(c.vocab, c.embed_dim, name="item_embed")(seq)
        x = x * (c.embed_dim**0.5)
        pos = jnp.arange(seq.shape[1])[None, :]
        x = x + nn.Embed(c.max_len, c.embed_dim, name="pos_embed")(pos)
        x = nn.Dropout(c.dropout, deterministic=deterministic)(x)
        for i in range(c.num_blocks):
            a = nn.LayerNorm(name=f"ln_att_{i}")(x)
            a = _MultiHeadSelfAttention(c, self.mesh, name=f"att_{i}")(a, pad_mask)
            x = x + nn.Dropout(c.dropout, deterministic=deterministic)(a)
            f = nn.LayerNorm(name=f"ln_ffn_{i}")(x)
            f = nn.Dense(c.ffn_dim, name=f"ffn_in_{i}")(f)
            f = nn.Dense(c.embed_dim, name=f"ffn_out_{i}")(nn.relu(f))
            x = x + nn.Dropout(c.dropout, deterministic=deterministic)(f)
        x = nn.LayerNorm(name="ln_out")(x)
        return x * pad_mask[..., None]


def _logits(params, hidden):
    """Tied-embedding output head: [B,T,D] x [V,D]^T -> [B,T,V]."""
    table = params["item_embed"]["embedding"]
    return jnp.einsum("btd,vd->btv", hidden, table)


def _sasrec_loss(model: SASRec):
    def loss_fn(params, batch, rng):
        hidden = model.apply(
            {"params": params}, batch["seq"], deterministic=False,
            rngs={"dropout": rng},
        )
        logits = _logits(params, hidden)
        targets = batch["target"]                     # [B, T], 0 = no target
        mask = (targets > 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0), {}

    return loss_fn


def make_train_step(loss_fn, optimizer, move=None):
    """One optimizer step of ``loss_fn(params, batch, rng) -> (loss, aux)``.
    ``move(params, aux) -> (params, aux)`` is the rule of a backbone's leaves
    that the optimizer leaves alone (``untrained_of``): it moves them from
    what the step's own loss counted, inside the same program."""
    def train_step(params, opt_state, batch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng)
        with jax.named_scope(looped.SCOPE_OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            if move is not None:
                params, aux = move(params, aux)
        return params, opt_state, loss, aux

    return train_step


def _attention_of(config, mesh):
    """``attend`` with the configuration's choices and the mesh bound."""
    def attention(q, k, v, pad_mask):
        return attend(q, k, v, pad_mask, mesh, config.attention, config.seq_parallel)

    return attention


def backbone_of(config, mesh):
    """``(init, loss_fn)`` of the backbone ``config`` names: ``init(rng, t)``
    gives the parameter tree, ``loss_fn(params, batch, rng)`` the objective
    and what the fit's span reports of it."""
    if isinstance(config, LoopedConfig):
        return (lambda rng, t: looped.init_params(config, rng),
                looped.make_loss(config, _attention_of(config, mesh)))
    if isinstance(config, SparseMoEConfig):
        return (lambda rng, t: sparse_moe.init_params(config, rng),
                sparse_moe.make_loss(config, mesh))
    if isinstance(config, HybridConfig):
        return (lambda rng, t: hybrid.init_params(config, rng),
                hybrid.make_loss(config, mesh))
    if isinstance(config, LatentMoEConfig):
        return (lambda rng, t: latent_moe.init_params(config, rng),
                latent_moe.make_loss(config, mesh))
    model = SASRec(config, mesh)
    # dummy batch = one row per data-shard: shard_map needs divisibility
    dp0 = max(mesh.shape.get("data", 1), 1)
    return (lambda rng, t: model.init(rng, jnp.zeros((dp0, t), jnp.int32))["params"],
            _sasrec_loss(model))


def untrained_of(config):
    """``(labels, move)`` of a backbone with leaves that the loss's gradient
    does not train, else ``(None, None)``. ``labels(params)`` names every leaf
    ``"train"`` or ``"fixed"``: a fixed leaf gets no update from the optimizer
    and no moments. ``move(params, aux) -> (params, aux)``, where there is one,
    moves the fixed leaves after the step from what its loss counted. The
    sparse backbone's indexer stays as drawn (no rule); the latent backbone's
    router biases move against the step's load."""
    if isinstance(config, SparseMoEConfig):
        return sparse_moe.trained_labels, None
    if isinstance(config, LatentMoEConfig):
        return latent_moe.trained_labels, functools.partial(latent_moe.move_bias, config)
    return None, None


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
    config,                  # SASRecConfig or a backbone's (``_BACKBONES``)
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
            "".join(f" {k}={span_attrs[k]}" for k in _FIT_LINE_ATTRS if k in span_attrs)
            + "".join(f" {k}={v:.6g}" for k, v in last.items() if np.ndim(v) == 0),
        )
        for name, value in last.items():
            span.set_attr(name, value)
    return jax.tree_util.tree_map(fetch_global, params), losses


#: the span's attributes the ``seq_fit:`` line repeats (a backbone that has them)
_FIT_LINE_ATTRS = ("experts_total", "experts_held", "experts_per_token", "experts_shared",
                   "moe_sum", "attention_backward_programs", "attention_backward_heads_per_step",
                   "index_topk", "kv_heads", "selection_kept_bytes", "linear_layers",
                   "full_layers", "delta_chunk", "delta_heads_per_step", "delta_state_bytes",
                   "delta_kept_bytes", "dense_layers", "mtp_depth", "latent_q_rank",
                   "latent_kv_rank", "score_width", "value_width", "latent_bytes_per_token",
                   "router_bias_leaves")
_BACKBONES = {LoopedConfig: "looped", SparseMoEConfig: "sparse_moe",
              HybridConfig: "hybrid_linear", LatentMoEConfig: "latent_moe"}
#: the backbones with routed experts of which the program holds a share, whose
#: attention is ``ops/sparse_attention``'s streamed programs
_EXPERT_MODULES = {SparseMoEConfig: sparse_moe, HybridConfig: hybrid,
                   LatentMoEConfig: latent_moe}
_EXPERTS = tuple(_EXPERT_MODULES)


def fit_attrs(config, param_bytes: int, opt_state_bytes: int, rows: int,
              platform: str) -> dict:
    """What the fit's span says of the model it trains and of how a step on
    ``rows`` rows is worked on ``platform``."""
    attrs = {
        "backbone": _BACKBONES.get(type(config), "sasrec"),
        "param_bytes": param_bytes,
        # weights, their gradients and the optimizer's moments
        "state_bytes": 2 * param_bytes + opt_state_bytes,
        # what a rematerialised layer keeps beside its input (the sparse
        # backbone: its selection, one bit a pair)
        "selection_kept_bytes": 0,
    }
    if type(config) in _BACKBONES:
        chunk = looped.head_chunk_of(config)
        halves = isinstance(config, (HybridConfig, LatentMoEConfig))
        attrs.update(
            layers=config.num_layers, passes=getattr(config, "ut_steps", 1),
            rematerialised=("nothing" if not config.remat else
                            "mixer and experts" if halves else "layer"),
            head=(f"chunks of {chunk} positions, recomputed" if chunk else
                  "whole pass, recomputed"),
        )
        if isinstance(config, _EXPERTS):
            attrs.update(
                experts_total=config.num_experts, experts_held=config.held,
                experts_per_token=config.experts_per_token, kv_heads=config.num_kv_heads,
                moe_sum=sparse_moe.sum_path(config, platform),
                # the backward pass of a layer's attention: one program where
                # the package's programs run, the plain twin's transpose elsewhere
                attention_backward_programs=int(sparse_moe.uses_kernels(config, platform)),
                attention_backward_heads_per_step=_EXPERT_MODULES[
                    type(config)].attention_backward_heads_per_step(config))
        if isinstance(config, SparseMoEConfig):
            attrs.update(
                index_topk=config.index_topk,
                selection_kept_bytes=sparse_moe.selection_kept_bytes(config, rows))
        if isinstance(config, HybridConfig):
            attrs.update(
                experts_shared=1, linear_layers=config.linear_layers,
                full_layers=config.periods, delta_chunk=config.delta_chunk,
                delta_heads_per_step=hybrid.delta_heads_per_step(config, rows),
                delta_state_bytes=hybrid.delta_state_bytes(config),
                delta_kept_bytes=hybrid.delta_kept_bytes(config, rows))
        if isinstance(config, LatentMoEConfig):
            attrs.update(
                experts_shared=1, dense_layers=config.dense_layers, mtp_depth=config.mtp_depth,
                latent_q_rank=config.q_rank, latent_kv_rank=config.kv_rank,
                score_width=config.score_dim, value_width=config.value_dim,
                latent_bytes_per_token=latent_moe.latent_bytes_per_token(config),
                router_bias_leaves=config.routers)
    else:
        attrs.update(layers=config.num_blocks, passes=1,
                     rematerialised="nothing", head="whole")
    return attrs


def _score_fn(config):
    """Jitted forward + vocab projection in ONE program, cached per config.

    The old path dispatched the transformer forward and the [D] x [V, D]
    einsum as separate eager calls, paying a dispatch each, per query.
    """
    if config not in _SCORE_CACHE:
        if isinstance(config, LoopedConfig):
            attention = _attention_of(config, None)
            _SCORE_CACHE[config] = jax.jit(
                lambda params, seqs, last: looped.score_last(
                    config, attention, params, seqs, last))
            return _SCORE_CACHE[config]
        if isinstance(config, _EXPERTS):
            module = _EXPERT_MODULES[type(config)]
            _SCORE_CACHE[config] = jax.jit(functools.partial(module.score_last, config))
            return _SCORE_CACHE[config]
        model = SASRec(config, None)

        @jax.jit
        def score(params, seqs, last):
            hidden = model.apply({"params": params}, seqs)       # [B, T, D]
            h_last = jnp.take_along_axis(
                hidden, last[:, None, None].astype(jnp.int32), axis=1
            )[:, 0, :]                                           # [B, D]
            return h_last @ params["item_embed"]["embedding"].T  # [B, V]

        _SCORE_CACHE[config] = score
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
