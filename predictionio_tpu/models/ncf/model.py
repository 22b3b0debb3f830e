"""NeuMF model + sharded training step.

Pure functions over a params pytree (flax.linen for init/apply), so the
training step jits cleanly with explicit shardings:

- params: embeddings sharded over the ``model`` axis on the EMBEDDING dim,
  MLP kernels sharded on their hidden dim (tensor parallelism);
- batch: sharded over the ``data`` axis (data parallelism);
- optimizer: optax Adam; gradients reduce over data via jit's implicit psum.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

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
from predictionio_tpu.utils.jax_compat import broadcast_one_to_all


@dataclass
class NCFConfig:
    num_users: int
    num_items: int
    embed_dim: int = 32
    hidden: tuple = (64, 32)
    learning_rate: float = 0.01
    implicit: bool = False      # BCE over sampled negatives vs MSE on ratings
    negatives: int = 4
    batch_size: int = 4096
    epochs: int = 5
    seed: int = 0


class NeuMF(nn.Module):
    config: NCFConfig

    @nn.compact
    def __call__(self, user_ids, item_ids):
        c = self.config
        gmf_u = nn.Embed(c.num_users, c.embed_dim, name="gmf_user")(user_ids)
        gmf_i = nn.Embed(c.num_items, c.embed_dim, name="gmf_item")(item_ids)
        mlp_u = nn.Embed(c.num_users, c.embed_dim, name="mlp_user")(user_ids)
        mlp_i = nn.Embed(c.num_items, c.embed_dim, name="mlp_item")(item_ids)
        gmf = gmf_u * gmf_i
        h = jnp.concatenate([mlp_u, mlp_i], axis=-1)
        for i, width in enumerate(c.hidden):
            h = nn.relu(nn.Dense(width, name=f"mlp_{i}")(h))
        fused = jnp.concatenate([gmf, h], axis=-1)
        return nn.Dense(1, name="out")(fused)[..., 0]


def param_shardings(mesh, params) -> Any:
    """Embedding tables + MLP kernels shard over the 'model' axis.

    Tensors whose trailing dim doesn't divide the model-axis size (e.g. the
    [*, 1] output head) stay replicated."""
    model_size = mesh.shape.get("model", 1)

    def spec_for(path: tuple, leaf) -> P:
        names = [getattr(p, "key", str(p)) for p in path]
        shardable = (
            leaf.ndim == 2 and model_size > 1 and leaf.shape[-1] % model_size == 0
        )
        if shardable and ("embedding" in names or "kernel" in names):
            return P(None, "model")  # [vocab, embed/model] or [in, out/model]
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path, leaf)), params
    )


def make_train_step(model: NeuMF, optimizer, implicit: bool):
    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["user"], batch["item"])
        if implicit:
            return optax.sigmoid_binary_cross_entropy(logits, batch["label"]).mean()
        return ((logits - batch["label"]) ** 2).mean()

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return train_step


def train_ncf(
    config: NCFConfig,
    users: np.ndarray,
    items: np.ndarray,
    labels: np.ndarray,
    mesh,
    checkpoint=None,
    log_every: int = 0,
):
    """Full training loop; returns the trained params pytree (host)."""
    model = NeuMF(config)
    rng = jax.random.PRNGKey(config.seed)
    params = model.init(
        rng, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
    )["params"]
    p_shard = param_shardings(mesh, params)
    data_shard = NamedSharding(mesh, P("data"))
    # put_global (not device_put): every process initialized identical
    # params from the same PRNGKey; on a multi-process mesh each
    # contributes its addressable shards of the tp layout
    params = jax.tree_util.tree_map(put_global, params, p_shard)
    optimizer = optax.adam(config.learning_rate)
    # init AFTER placement, jitted: adam's mu/nu zeros_like the sharded
    # params and inherit the tp layout (eager zeros_like on non-addressable
    # multi-process arrays would fail)
    opt_state = jax.jit(optimizer.init)(params)

    step_fn = jax.jit(
        make_train_step(model, optimizer, config.implicit),
        in_shardings=(
            p_shard,
            None,
            {"user": data_shard, "item": data_shard, "label": data_shard},
        ),
        out_shardings=(p_shard, None, NamedSharding(mesh, P())),
        donate_argnums=(0, 1),
    )

    np_rng = np.random.default_rng(config.seed)
    n = users.size
    batch = config.batch_size
    n_devices = mesh.shape.get("data", 1)
    n_proc = jax.process_count()
    step = 0
    start_epoch = 0
    # resume must stay rank-SYMMETRIC on multi-process meshes: only rank 0
    # holds a checkpoint manager, but fetch/put of sharded state are
    # collectives every rank joins; the restored state broadcasts from
    # rank 0 so ranks never diverge
    latest = checkpoint.latest_step() if checkpoint is not None else None
    any_checkpoint = checkpoint is not None
    if n_proc > 1:
        flags = broadcast_one_to_all(
            np.int64([1 if any_checkpoint else 0, -1 if latest is None else latest])
        )
        any_checkpoint = bool(int(flags[0]))
        latest = None if int(flags[1]) < 0 else int(flags[1])
    if latest is not None:
        host_state = {
            "params": jax.tree_util.tree_map(fetch_global, params),
            "opt_state": jax.tree_util.tree_map(fetch_global, opt_state),
            "epoch": 0,
        }
        if checkpoint is not None:
            host_state = checkpoint.restore(host_state)
        if n_proc > 1:
            host_state = broadcast_one_to_all(host_state)
        params = jax.tree_util.tree_map(put_global, host_state["params"], p_shard)
        # restore Adam's moments too -- a zeroed mu/nu after resume would
        # spike the first post-resume updates
        opt_state = jax.tree_util.tree_map(
            lambda a, b: put_global(np.asarray(a), b.sharding)
            if hasattr(b, "sharding")
            else a,
            host_state["opt_state"],
            opt_state,
        )
        start_epoch = int(host_state["epoch"]) + 1

    losses = []
    for epoch in range(start_epoch, config.epochs):
        order = np_rng.permutation(n)
        for start in range(0, n, batch):
            take = order[start : start + batch]
            if take.size < max(n_devices, 1):
                continue
            usable = (take.size // n_devices) * n_devices
            take = take[:usable]
            # every process computes the same permutation (same seed), so
            # put_global can hand each exactly its addressable batch shards
            b = {
                "user": put_global(users[take], data_shard),
                "item": put_global(items[take], data_shard),
                "label": put_global(labels[take].astype(np.float32), data_shard),
            }
            params, opt_state, loss = step_fn(params, opt_state, b)
            one_step_in_flight(mesh, loss)
            step += 1
            if log_every and step % log_every == 0:
                losses.append(float(loss))
        if any_checkpoint:
            # the fetches are collectives: when ANY rank checkpoints, EVERY
            # rank joins them each epoch (only rank 0 writes); with no
            # checkpointing anywhere, nobody pays the per-epoch allgather
            epoch_state = {
                "params": jax.tree_util.tree_map(fetch_global, params),
                "opt_state": jax.tree_util.tree_map(fetch_global, opt_state),
                "epoch": epoch,
            }
            if checkpoint is not None:
                checkpoint.save(epoch, epoch_state)
    if start_epoch < config.epochs:
        check_steps_ran(step, n, n_devices, "example")
    return jax.tree_util.tree_map(fetch_global, params), losses


def make_implicit_batches(
    users: np.ndarray, items: np.ndarray, num_items: int, negatives: int, rng
):
    """Positive pairs + sampled negatives -> (users, items, labels)."""
    pos_set = set(zip(users.tolist(), items.tolist()))
    neg_u = np.repeat(users, negatives)
    neg_i = rng.integers(0, num_items, size=neg_u.size)
    keep = np.array([(u, i) not in pos_set for u, i in zip(neg_u, neg_i)])
    all_u = np.concatenate([users, neg_u[keep]])
    all_i = np.concatenate([items, neg_i[keep]])
    all_y = np.concatenate([np.ones(users.size), np.zeros(int(keep.sum()))])
    return all_u, all_i, all_y.astype(np.float32)
