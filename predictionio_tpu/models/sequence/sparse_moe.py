"""The sparse backbone of the sequence template: a decoder whose attention
reads, for every query, the ``index_topk`` earlier positions a learned indexer
scores highest, and whose feed-forward is a routed mixture of experts of which
this program holds a share.

The block is that of ``Keye-VL-2.0-30B-A3B``'s language model (``model_type
KeyeVL2``: grouped key-value heads, a DeepSeek-Sparse-Attention indexer, 128
experts, 8 a token, no shared expert) with the item catalog as its
vocabulary. For one row ``x`` ``[T, D]`` (``n1``, ``n2`` RMSNorm):

- ``h = n1(x)``; ``q = h Wq`` ``[T, H, hd]``, ``k = h Wk``, ``v = h Wv``
  ``[T, KV, hd]``, rotary positions on ``q`` and ``k``;
- indexer: ``qI = h WqI`` ``[T, HI, dI]``, ``kI = rms(h WkI)`` ``[T, dI]`` (no
  learned scale), ``w = h Ww`` ``[T, HI]``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` is
  the ``index_topk`` positions ``s <= t`` with the largest ``I[t, s]`` (all of
  them while ``t < index_topk``; ties to the earlier position);
- ``a[t] = softmax over s in S_t of (q[t, g] . k[s, g // (H / KV)] / sqrt(hd))``,
  ``x = x + concat_g(a v) Wo``;
- ``u = n2(x)``; ``p = softmax(u Wr)`` over all ``num_experts`` in float32;
  ``E_t`` the ``experts_per_token`` largest, ``g[t, e] = p[t, e] / sum_{E_t} p``;
  ``x = x + sum_{e in E_t, e held here} g[t, e] W2_e (silu(W1_e u) * (W3_e u))``
  (``experts.py``: ``experts_held``, the router, the held experts' passes);
- after the last layer ``h = n_f(x)``, ``logits = W_head h`` (not tied), and
  the loss of a position with a target is its cross-entropy, the mean over
  such positions, plus ``aux_coef`` times the mean over the layers of
  ``num_experts sum_e f_e P_e`` (``f_e`` the assignments to expert ``e`` a
  real token, ``P_e`` the mean of ``p[., e]``; the load-balancing loss of the
  family).

The indexer decides by a hard top-k, which passes no gradient, so the
next-item loss cannot train it: its three matrices (``params["indexer"]``)
are inputs of the fit that stay as drawn, and the optimizer keeps no state
for them (``trained_labels``, which ``model.py:optimizer_of`` reads). DeepSeek
trains its indexer by a separate alignment loss; that recipe is not part of
this backbone.

How it is worked (``benchmarks/reference_keye.py`` is the same mathematics
with none of this):

- layer parameters are stacked ``[L, ...]`` and the stack is one ``lax.scan``,
  each layer rematerialised from its input (``remat``) and from its selection:
  the forward pass keeps the mask of selected pairs, one bit a pair
  (``pack_rows``; ``L B T T / 8`` bytes, 100.7 MB at 6 layers of 2 rows of
  8,192), and the backward pass unpacks it where the layer is worked again,
  so the indexer's projections, the index scores and the k-th largest are
  worked once a step. Everything else of a layer is recomputed;
- matmul inputs are ``compute_dtype`` (bfloat16) with float32 accumulation;
  the router, the residual stream, norms, rotary positions, attention softmax,
  loss, master weights and Adam's moments are float32; the index scores are
  bfloat16 products accumulated in float32;
- on a TPU (``attention`` "auto") index scores, selection and attention are
  the three programs of ``ops/sparse_attention.py``: scores in tiles over the
  causal triangle, the k-th largest by bisection with a block of queries'
  scores in VMEM, attention with K and V streamed a block at a time; the
  attention's operands are written by ``ops/rope_layout.py``'s one program a
  phase (``blocks.rope_operands`` under ``rope``: q and k turned, q scaled,
  all three cast and laid heads-first as the programs read them; its
  transpose turns the backward program's ``dq``, ``dk``, ``dv`` back);
  elsewhere their ``jax.numpy`` twins and ``blocks.rotate``;
- the head and loss are ``blocks.exit_ce``'s chunks of positions.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from predictionio_tpu.models.sequence import blocks, experts
from predictionio_tpu.ops import sparse_attention as sa

#: Device scopes of a training step beside ``blocks``'s and ``experts``'s:
#: under ``attention`` the indexer's projections and scores (``index``) and the
#: k-th largest (``select``) before the attention over the selection (``kernel``).
SCOPE_INDEX = "index"
SCOPE_SELECT = "select"
#: what a rematerialised layer keeps from the forward pass beside its input
#: (``jax.ad_checkpoint.checkpoint_name``): the selection, one bit a pair
KEPT_SELECTION = "selection"


@dataclass(frozen=True)
class SparseMoEConfig(experts.ExpertsConfig):
    hidden_size: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 2
    index_heads: int = 2
    index_dim: int = 16
    index_topk: int = 16
    rope_theta: float = 1e7
    aux_coef: float = 0.001

    def __post_init__(self):
        super().__post_init__()
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} must be a multiple of num_kv_heads="
                f"{self.num_kv_heads}")
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even (rotary pairs)")
        if self.index_topk < 1 or self.num_layers < 1:
            raise ValueError("index_topk and num_layers must be at least 1")


CONFIG = SparseMoEConfig
ENGINE_PARAMS = {
    **experts.ENGINE_PARAMS, "hiddenSize": "hidden_size", "numHeads": "num_heads",
    "numKvHeads": "num_kv_heads", "headDim": "head_dim", "numLayers": "num_layers",
    "indexHeads": "index_heads", "indexDim": "index_dim", "indexTopk": "index_topk",
    "ropeTheta": "rope_theta", "rmsNormEps": "rms_eps", "auxLossCoef": "aux_coef",
}
moe_chunk_of = experts.moe_chunk_of     # the benchmark's drivers ask here


def param_shapes(c: SparseMoEConfig) -> dict:
    """The parameter tree as shapes. The layers' arrays lead with ``[L]``;
    ``indexer`` is held fixed by the fit."""
    d, n, hd = c.hidden_size, c.num_layers, c.head_dim
    return {
        "embed": (c.vocab, d),
        "layers": {
            "n1": (n, d), "wq": (n, d, c.num_heads * hd), "wk": (n, d, c.num_kv_heads * hd),
            "wv": (n, d, c.num_kv_heads * hd), "wo": (n, c.num_heads * hd, d),
            "n2": (n, d), "router": (n, d, c.num_experts),
            "w_gate": (n, c.held, d, c.expert_dim), "w_up": (n, c.held, d, c.expert_dim),
            "w_down": (n, c.held, c.expert_dim, d),
        },
        "indexer": {
            "wq": (n, d, c.index_heads * c.index_dim), "wk": (n, d, c.index_dim),
            "ww": (n, d, c.index_heads),
        },
        "final_norm": (d,),
        "head": (c.vocab, d),
    }


def init_params(c: SparseMoEConfig, rng) -> dict:
    """Norm weights 1, the embedding N(0, 1), matrices N(0, 0.02), those that
    write into the residual stream scaled down (``blocks.writer_stds``)."""
    return blocks.draw_params(param_shapes(c), rng, ones=("n1", "n2", "final_norm"),
                              stds=blocks.writer_stds(("wo", "w_down"), c.num_layers))


def count_params(c: SparseMoEConfig) -> int:
    return blocks.count_params(param_shapes(c))


def trained_labels(params) -> dict:
    """``"train"`` or ``"fixed"`` for every leaf: the indexer is fixed (no
    rule moves it: it stays as drawn)."""
    return {name: jax.tree_util.tree_map(
        lambda _: "fixed" if name == "indexer" else "train", sub)
        for name, sub in params.items()}


# ---- attention over the indexer's selection ----------------------------------

def pack_rows(mask):
    """The 0/1 mask ``[B, T, T]`` as bits, eight query rows a byte with the
    keys left where they are: ``byte[b, r, s] = sum_i mask[b, 8 r + i, s] << i``,
    uint8 ``[B, ceil(T / 8), T]``. Both directions are elementwise over whole
    rows of keys; bits along the key axis would be gathered within a row."""
    b, t, keys = mask.shape
    rows = jnp.pad(mask.astype(jnp.uint8), ((0, 0), (0, -t % 8), (0, 0)))
    rows = rows.reshape(b, -1, 8, keys) << jnp.arange(8, dtype=jnp.uint8)[:, None]
    return rows.sum(axis=2, dtype=jnp.uint8)


def unpack_rows(packed, t: int):
    """``pack_rows`` undone: int8 ``[B, t, T]``."""
    b, _, keys = packed.shape
    bits = (packed[:, :, None, :] >> jnp.arange(8, dtype=jnp.uint8)[:, None]) & 1
    return bits.reshape(b, -1, keys)[:, :t].astype(jnp.int8)


def selection_kept_bytes(c: SparseMoEConfig, rows: int) -> int:
    """What a step on ``rows`` rows keeps of its selections from the forward
    pass to the backward pass: every layer's packed mask, where the layers are
    rematerialised (otherwise the backward pass has the mask itself)."""
    return c.num_layers * rows * -(-c.max_len // 8) * c.max_len if c.remat else 0


def attention_backward_heads_per_step(c: SparseMoEConfig) -> int:
    """The key-value heads a grid step of the attention's backward program works
    on a row of ``max_len`` (``ops/sparse_attention``, from the shapes alone),
    the selection's tile one of the step's blocks."""
    return sa.backward_heads_per_step(
        c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, c.head_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize, True)


def fit_attrs(c: SparseMoEConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span, for a step on ``rows`` rows."""
    return {
        **blocks.decoder_fit_attrs(c, c.num_layers),
        **experts.fit_attrs(c, platform, attention_backward_heads_per_step(c), shared=False),
        "index_topk": c.index_topk, "kv_heads": c.num_kv_heads,
        "rope_block": blocks.rope_block(c, platform, c.num_heads, c.num_kv_heads, c.head_dim),
        # what a rematerialised layer keeps beside its input: one bit a pair
        "selection_kept_bytes": selection_kept_bytes(c, rows),
    }


def _attention(c: SparseMoEConfig, backend: str, rope, h, p, ip, real, probe=None):
    """``(o, counts)``: the attention output before ``Wo`` ``[B, T, H x hd]``
    on the normed input ``h``, and what it selected. ``probe``: query
    positions whose index scores and selection are returned too."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    kernels, interpret = blocks.uses_kernels(c, backend), backend != "tpu"
    with jax.named_scope(blocks.SCOPE_QKV):
        q, k, v = (blocks.matmul(h, p[w], dtype).reshape(b, t, n, c.head_dim)
                   for w, n in (("wq", c.num_heads), ("wk", c.num_kv_heads),
                                ("wv", c.num_kv_heads)))
    with jax.named_scope(blocks.SCOPE_ROPE):
        q, k, v = blocks.rope_operands(c, backend, q, k, v, rope)
    with jax.named_scope(SCOPE_INDEX):
        # the selection is a hard top-k: no gradient, to the input or the indexer
        hs, ip = jax.lax.stop_gradient((h, ip))
        q_idx = blocks.matmul(hs, ip["wq"], dtype).reshape(b, t, c.index_heads, c.index_dim)
        k_idx = blocks.matmul(hs, ip["wk"], dtype)
        k_idx = k_idx * jax.lax.rsqrt(jnp.mean(k_idx * k_idx, axis=-1, keepdims=True)
                                      + c.rms_eps)
        w = blocks.matmul(hs, ip["ww"], dtype)
        q_idx, k_idx = q_idx.astype(dtype), k_idx.astype(dtype)
        scores = (sa.index_scores(q_idx, k_idx, w, interpret=interpret) if kernels
                  else sa.index_scores_plain(q_idx, k_idx, w))
    with jax.named_scope(SCOPE_SELECT):
        mask = (sa.select_topk(scores, c.index_topk, interpret=interpret) if kernels
                else sa.select_topk_plain(scores, c.index_topk))
        per_query = mask.astype(jnp.int32).sum(axis=-1)
        counts = {"selected_pairs": jnp.where(real, per_query, 0).sum(),
                  "causal_pairs": jnp.where(real, jnp.arange(1, t + 1)[None, :], 0).sum()}
        if probe is not None:
            counts["probe_scores"] = scores[:, probe, :]
            counts["probe_mask"] = mask[:, probe, :]
        # a rematerialised layer starts from these bits (``hidden_states``)
        kept = checkpoint_name(pack_rows(mask), KEPT_SELECTION)
    with jax.named_scope(blocks.SCOPE_KERNEL):
        out = blocks.attention_of(c, backend, q, k, v, unpack_rows(kept, t))
    return out.reshape(b, t, -1), counts


# ---- the stack ---------------------------------------------------------------

def _layer(c: SparseMoEConfig, backend: str, rope, real, x, p, ip, probe=None):
    """One decoder layer on ``x`` [B, T, D]: ``(x', stats)``."""
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(blocks.SCOPE_ATTENTION):
        with jax.named_scope(blocks.SCOPE_NORM):
            h = blocks.rms_norm(x, p["n1"], c.rms_eps)
        out, stats = _attention(c, backend, rope, h, p, ip, real, probe)
        with jax.named_scope(blocks.SCOPE_OUT):
            x = x + blocks.matmul(out, p["wo"], dtype)
    x, routed = experts.expert_half(c, backend, x, p, real)
    return x, {**stats, **routed}


def hidden_states(c: SparseMoEConfig, backend: str, params, seq, probe=None):
    """``(x, stats)``: the residual stream after the last layer ``[B, T, D]``
    and every layer's counts ``[L, ...]``, under the pass's scope."""
    with jax.named_scope(blocks.SCOPE_EMBED):
        real = seq > 0
        rope = blocks.rope_tables(seq.shape[1], c.head_dim, c.rope_theta)
        x = jnp.take(params["embed"], seq, axis=0)

    def body(carry, layer):
        return _layer(c, backend, rope, real, carry, *layer, probe)

    if c.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(KEPT_SELECTION))
    with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_LAYERS):
        return jax.lax.scan(body, x, (params["layers"], params["indexer"]))


def make_loss(c: SparseMoEConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step;
    ``aux`` is scalars: the two terms of the loss and the step's counts."""
    backend = blocks.backend_of(mesh, whole_rows=True)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        x, stats = hidden_states(c, backend, params, seq)
        with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_EXIT):
            ce = blocks.masked_ce(c, x, params["final_norm"], params["head"], targets)
            aux_loss = stats["aux"].mean()
            out = {"ce": ce, "aux_loss": aux_loss, **experts.counts(c, stats),
                   "selected_pairs": stats["selected_pairs"].sum(),
                   "causal_pairs": stats["causal_pairs"].sum()}
            return ce + c.aux_coef * aux_loss, out

    return loss_fn


def probe_selection(c: SparseMoEConfig, mesh, params, seq, queries):
    """What the step's own index and select programs give for the query
    positions ``queries`` in every layer: ``(scores, mask)``, each
    ``[L, B, len(queries), T]`` (a score above the diagonal is undefined)."""
    _, stats = hidden_states(c, blocks.backend_of(mesh, whole_rows=True), params, seq,
                             probe=queries)
    return stats["probe_scores"], stats["probe_mask"]


def score_last(c: SparseMoEConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row."""
    x, _ = hidden_states(c, blocks.backend_of(None), params, seqs)
    return blocks.score_last(c, x, params["final_norm"], params["head"], last)
