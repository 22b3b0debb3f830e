"""The default backbone of the sequence template: a self-attentive
sequential recommender (SASRec-style). A causal transformer of ``num_blocks``
blocks (LayerNorm, multi-head self-attention, a pointwise ReLU FFN, learned
positions) predicts the next item; the loss is full-softmax cross-entropy
against the tied item embedding matrix -- one [B*T, D] x [D, V] matmul on the
MXU. Everything position-local needs no communication under sequence
parallelism; attention across ``seq`` shards is ``blocks.attend``'s."""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax.numpy as jnp
import optax

from predictionio_tpu.models.sequence import blocks


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


CONFIG = SASRecConfig
ENGINE_PARAMS = {"embedDim": "embed_dim", "numHeads": "num_heads", "numBlocks": "num_blocks",
                 "ffnDim": "ffn_dim", "dropout": "dropout"}


class _MultiHeadSelfAttention(nn.Module):
    """Causal MHA whose score computation is mesh-aware (``blocks.attend``)."""

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
        out = blocks.attend(c, self.mesh, q, k, v, pad_mask)
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


def logits(params, hidden):
    """Tied-embedding output head: [B,T,D] x [V,D]^T -> [B,T,V]."""
    table = params["item_embed"]["embedding"]
    return jnp.einsum("btd,vd->btv", hidden, table)


def init_params(c: SASRecConfig, rng) -> dict:
    """Flax's own draw. It depends on no mesh: one row of ``max_len`` on the
    default device shapes every parameter."""
    return SASRec(c, None).init(rng, jnp.zeros((1, c.max_len), jnp.int32))["params"]


def make_loss(c: SASRecConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step."""
    model = SASRec(c, mesh)

    def loss_fn(params, batch, rng):
        hidden = model.apply(
            {"params": params}, batch["seq"], deterministic=False,
            rngs={"dropout": rng},
        )
        targets = batch["target"]                     # [B, T], 0 = no target
        mask = (targets > 0).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits(params, hidden), targets)
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0), {}

    return loss_fn


def fit_attrs(c: SASRecConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span."""
    return {"layers": c.num_blocks, "passes": 1, "rematerialised": "nothing", "head": "whole",
            "selection_kept_bytes": 0}


def score_last(c: SASRecConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row: the forward
    and the vocab projection in one program."""
    hidden = SASRec(c, None).apply({"params": params}, seqs)         # [B, T, D]
    return blocks.take_last(hidden, last) @ params["item_embed"]["embedding"].T
