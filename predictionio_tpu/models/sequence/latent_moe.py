"""The latent backbone of the sequence template: a decoder whose attention
reads keys and values through a low-rank latent with a rotary key all heads
share, whose layers after ``dense_layers`` leading dense ones are routed
mixtures of experts chosen by sigmoid scores plus a bias that no gradient
trains, beside a shared expert every token takes, and which predicts a second
event ahead through one more layer (a multi-token-prediction module).

The block is that of ``JoyAI-LLM-Flash`` (``model_type joyai_llm_flash``: MLA
with 32 heads that score over 128 + 64 and carry 128, 256 experts, 8 a token,
``topk_method noaux_tc``, one shared expert, one leading dense layer,
``num_nextn_predict_layers 1``) with the item catalog as its vocabulary. For
one row ``x`` ``[T, D]``, ``n(.)`` RMSNorm with a plain weight:

- **latent attention**, ``h = n1(x)``: ``c_q = n_q(h W_qa)``; ``q = c_q W_qb``,
  ``H`` heads of ``[q_nope | q_rope]`` (``nope_dim | rope_dim``);
  ``[c | k_r] = h W_kva`` (``kv_rank | rope_dim``); ``n_kv(c) W_kvb``, ``H``
  heads of ``[k_nope | v]`` (``nope_dim | value_dim``). Rotary positions
  (pairs interleaved ``(2i, 2i + 1)``) on every head's ``q_rope`` and on
  ``k_r``, which is one vector a position for all the heads. Head ``a``:
  causal ``softmax((q_nope_a . k_nope_a + q_rope_a . k_r) / sqrt(nope_dim +
  rope_dim)) v_a``; ``x <- x + concat_a(o_a) W_o``;
- **a dense layer's MLP**: ``x <- x + W_down(silu(W_gate u) * (W_up u))``,
  ``u = n2(x)``;
- **an expert layer's**: ``s = sigmoid(u W_r)`` over all ``num_experts`` in
  float32; ``E_t`` the ``experts_per_token`` largest of ``s + b``;
  ``g = s[E_t]`` (without ``b``), ``g <- routed_scale g / (sum g + 1e-20)``;
  ``x <- x + sum_{e in E_t, e held here} g_e FFN_e(u) + FFN_shared(u)``; the
  shared expert has no gate. ``experts_held`` is ``experts.py``'s: the router
  is whole, this program adds the held experts' part, and of an
  expert-parallel deployment's shares each adds the shared expert's part;
- **the bias** ``b`` (``router_bias``, ``[E]`` a layer, float32) is in no
  gradient and has no moments: after a step it moves by ``bias_rate`` against
  that step's load, ``b_e += bias_rate sign(mean(load) - load_e)``
  (``move``, which ``model.make_train_step`` runs inside the step);
- **the balance loss** that goes with it, a row at a time:
  ``sum_e f_e P_e``, ``f_e = E / (K T_r)`` times the row's real positions that
  chose ``e``, ``P_e`` the row's mean of ``s_e / sum_j s_j``; the mean over
  rows and over every layer with a router, the module's among them;
- **the prediction module** (``mtp_depth`` 1): ``m_i = [n_e(Emb(target_i)) |
  n_h(z_i)] W_m``, ``z`` the stack's output before the final norm; one expert
  layer on ``m``; logits through its own norm and the stack's head; position
  ``i`` is scored on ``target_{i+1}`` where that is an event;
- loss: the stack's mean cross-entropy + ``mtp_coef`` x the module's +
  ``balance_coef`` x the balance loss. Serving runs the stack without the
  module, the whole history a query (no cache of latents).

How it is worked (``benchmarks/reference_joyai.py`` is the same mathematics
with none of this):

- the dense layers and the expert layers are each stacked ``[n, ...]`` and
  scanned; each half of a layer (attention, MLP) keeps its input alone and is
  worked again in the backward pass (``remat``), as ``hybrid.py``; the
  module's merge, its two halves and both heads do the same;
- attention is ``ops/sparse_attention.heads_first_attention`` with ``q`` and
  ``k`` of width ``nope_dim + rope_dim`` and ``v`` of ``value_dim``, eight
  heads a grid step, on operands that ``ops/rope_layout.latent_rope_layout``'s
  one program a phase writes from the projections' outputs (under ``rope``):
  ``[q_nope | q_rope]`` turned, scaled for the scores, cast and laid
  heads-first, ``k_r`` turned once and laid beside every head's ``k_nope`` in
  HBM, ``v`` taken out of ``W_kvb``'s heads; its transpose sums the key's
  cotangent over the heads. Off the TPU XLA works the rotation and the
  attention's plain twin;
- the router is this module's (``route``); the held experts' passes are
  ``experts.moe``'s, as they stand;
- matmul inputs are ``compute_dtype`` (bfloat16) with float32 accumulation;
  the two latent norms, the router's matmul, sigmoid, the bias, top-k and
  gates, the residual stream, norms, rotary positions, softmax, losses, master
  weights and Adam's moments are float32;
- the heads and losses are ``blocks.exit_ce``'s chunks of positions.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from predictionio_tpu.models.sequence import blocks, experts
from predictionio_tpu.ops import rope_layout, sparse_attention as sa

#: Device scopes beside ``blocks``'s and ``experts``'s: inside
#: ``attention/qkv`` the two latent paths; the prediction module under
#: ``seq.pass1/mtp`` with ``merge``, the layer's own ``layers/...`` and
#: ``exit``; the bias's move under ``seq.optimizer/bias``.
SCOPE_Q_LATENT = "q_latent"
SCOPE_KV_LATENT = "kv_latent"
SCOPE_MTP = "mtp"
SCOPE_MERGE = "merge"
SCOPE_BIAS = "bias"
BIAS = experts.BIAS


@dataclass(frozen=True)
class LatentMoEConfig(experts.ExpertsConfig):
    hidden_size: int = 64
    num_layers: int = 3         # the dense layers and the expert layers, not the module
    dense_layers: int = 1       # leading layers with a dense MLP
    num_heads: int = 4
    q_rank: int = 48            # the queries' latent
    kv_rank: int = 32           # the keys' and values' latent
    nope_dim: int = 16          # a head's dimensions without a position
    rope_dim: int = 8           # the rotary key's, shared by the heads
    value_dim: int = 16
    ffn_dim: int = 128          # a dense layer's MLP
    shared_expert_dim: int = 32
    routed_scale: float = 2.5
    mtp_depth: int = 1          # 0: no prediction module
    mtp_coef: float = 0.3
    balance_coef: float = 1e-4
    bias_rate: float = 1e-3
    rope_theta: float = 3.2e7

    def __post_init__(self):
        super().__post_init__()
        if not 0 <= self.dense_layers < self.num_layers:
            raise ValueError(
                f"dense_layers={self.dense_layers}: want 0 .. num_layers - 1 ="
                f" {self.num_layers - 1} (an expert layer at least)")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth={self.mtp_depth}: want 0 or 1")
        if self.rope_dim % 2:
            raise ValueError(f"rope_dim={self.rope_dim} must be even (rotary pairs)")

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @property
    def routers(self) -> int:
        """Layers with a router: the stack's and the module's."""
        return self.expert_layers + self.mtp_depth

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads  # every head has a key and a value of its own

    @property
    def score_dim(self) -> int:
        return self.nope_dim + self.rope_dim


CONFIG = LatentMoEConfig
ENGINE_PARAMS = {
    **experts.ENGINE_PARAMS, "hiddenSize": "hidden_size", "numLayers": "num_layers",
    "denseLayers": "dense_layers", "numHeads": "num_heads", "qLoraRank": "q_rank",
    "kvLoraRank": "kv_rank", "qkNopeHeadDim": "nope_dim", "qkRopeHeadDim": "rope_dim",
    "vHeadDim": "value_dim", "ffnDim": "ffn_dim", "sharedExpertDim": "shared_expert_dim",
    "routedScalingFactor": "routed_scale", "mtpDepth": "mtp_depth", "mtpLossCoef": "mtp_coef",
    "balanceLossCoef": "balance_coef", "biasUpdateRate": "bias_rate", "ropeTheta": "rope_theta",
    "rmsNormEps": "rms_eps",
}


def param_shapes(c: LatentMoEConfig) -> dict:
    """The parameter tree as shapes. ``dense`` leads with ``[dense_layers]``,
    ``layers`` with ``[expert_layers]``; ``mtp/layer`` is one expert layer."""
    d, h, shared = c.hidden_size, c.num_heads, c.shared_expert_dim

    def attention(lead):
        return {
            "n1": lead + (d,), "w_qa": lead + (d, c.q_rank), "q_norm": lead + (c.q_rank,),
            "w_qb": lead + (c.q_rank, h * c.score_dim),
            "w_kva": lead + (d, c.kv_rank + c.rope_dim), "kv_norm": lead + (c.kv_rank,),
            "w_kvb": lead + (c.kv_rank, h * (c.nope_dim + c.value_dim)),
            "wo": lead + (h * c.value_dim, d), "n2": lead + (d,),
        }

    def expert_layer(lead):
        return {
            **attention(lead), "router": lead + (d, c.num_experts),
            BIAS: lead + (c.num_experts,),
            "w_gate": lead + (c.held, d, c.expert_dim), "w_up": lead + (c.held, d, c.expert_dim),
            "w_down": lead + (c.held, c.expert_dim, d),
            "s_gate": lead + (d, shared), "s_up": lead + (d, shared), "s_down": lead + (shared, d),
        }

    k = (c.dense_layers,)
    shapes = {
        "embed": (c.vocab, d),
        "dense": {**attention(k), "w_gate": k + (d, c.ffn_dim), "w_up": k + (d, c.ffn_dim),
                  "w_down": k + (c.ffn_dim, d)},
        "layers": expert_layer((c.expert_layers,)),
        "final_norm": (d,),
        "head": (c.vocab, d),
    }
    if c.mtp_depth:
        shapes["mtp"] = {"embed_norm": (d,), "hidden_norm": (d,), "merge": (2 * d, d),
                         "layer": expert_layer(()), "final_norm": (d,)}
    return shapes


def init_params(c: LatentMoEConfig, rng) -> dict:
    """Norm weights 1, the embedding N(0, 1), matrices N(0, 0.02), those that
    write into the residual stream scaled down (``blocks.writer_stds``); the
    router's bias starts at 0."""
    return blocks.draw_params(
        param_shapes(c), rng, zeros=(BIAS,),
        ones=("n1", "n2", "q_norm", "kv_norm", "final_norm", "embed_norm", "hidden_norm"),
        stds=blocks.writer_stds(("wo", "w_down", "s_down"), c.num_layers))


def count_params(c: LatentMoEConfig) -> int:
    """The trained parameters: every leaf but the routers' biases."""
    return blocks.count_params(param_shapes(c), but=(BIAS,))


#: a router's bias is fixed as far as the optimizer goes (``move`` moves it)
trained_labels = experts.trained_labels


def latent_bytes_per_token(c: LatentMoEConfig) -> int:
    """What a cache of latents would hold a token a layer, in ``compute_dtype``:
    the normed latent and the rotary key."""
    return (c.kv_rank + c.rope_dim) * jnp.dtype(c.compute_dtype).itemsize


def attention_backward_heads_per_step(c: LatentMoEConfig) -> int:
    """The heads a grid step of the attention's backward program works on a row
    of ``max_len``: each head has a key and a value of its own (from the shapes
    alone)."""
    return sa.backward_heads_per_step(
        c.num_heads, 1, c.score_dim, c.value_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize)


def rope_block(c: LatentMoEConfig, platform: str) -> str:
    """``blocks.rope_block`` for this backbone's operands: the tile of
    ``ops/rope_layout.latent_rope_layout``'s programs on a row of ``max_len``,
    positions by lanes of q; ``plain`` where XLA works the rotation."""
    if not blocks.uses_kernels(c, platform):
        return "plain"
    bt, lanes, _ = rope_layout.latent_tile_of(c.num_heads, c.nope_dim, c.rope_dim, c.value_dim,
                                              c.max_len)
    return f"{bt}x{lanes}"


def fit_attrs(c: LatentMoEConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span."""
    return {
        **blocks.decoder_fit_attrs(c, c.num_layers, halves=True),
        **experts.fit_attrs(c, platform, attention_backward_heads_per_step(c), shared=True),
        "kv_heads": c.num_kv_heads, "selection_kept_bytes": 0,
        "dense_layers": c.dense_layers, "mtp_depth": c.mtp_depth,
        "latent_q_rank": c.q_rank, "latent_kv_rank": c.kv_rank,
        "score_width": c.score_dim, "value_width": c.value_dim,
        "latent_bytes_per_token": latent_bytes_per_token(c), "router_bias_leaves": c.routers,
        "rope_block": rope_block(c, platform),
    }


# ---- latent attention --------------------------------------------------------

def rope_tables(t: int, dim: int, theta: float):
    """``cos, sin`` of ``[T, dim]``, a frequency for each pair ``(2i, 2i + 1)``."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.repeat(jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :], 2, axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


#: rotary positions on [B, T, H, dim], pairs interleaved ``(2i, 2i + 1)``
rotate = rope_layout.rotate_pairs


def _operands(c: LatentMoEConfig, backend: str, q, kv, k_r, rope):
    """What the attention reads (``blocks.attention_of``), for the scope
    ``rope``, from the projections' float32 outputs: ``q`` [B, T, H x
    score_dim] a head's ``[q_nope | q_rope]``, ``kv`` [B, T, H x (nope_dim +
    value_dim)] a head's ``[k_nope | v]`` and the rotary key ``k_r`` [B, T,
    rope_dim]. Where the package's programs run,
    ``ops/rope_layout.latent_rope_layout``'s one program a phase writes them
    once: turned, q scaled for the scores, cast and laid heads-first, the key
    beside every head's ``k_nope``. Elsewhere the same operands [B, T, H, .],
    float32."""
    if blocks.uses_kernels(c, backend):
        return rope_layout.latent_rope_layout(q, kv, k_r, *rope, c.num_heads, c.compute_dtype,
                                              backend != "tpu")
    return rope_layout.latent_operands(q, kv, k_r, *rope, c.num_heads)


def _attention(c: LatentMoEConfig, backend: str, rope, h, p):
    """The attention output before ``W_o`` ``[B, T, H x value_dim]`` on the
    normed input ``h``."""
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(blocks.SCOPE_QKV):
        with jax.named_scope(SCOPE_Q_LATENT):
            c_q = blocks.rms_norm(blocks.matmul(h, p["w_qa"], dtype), p["q_norm"], c.rms_eps)
            q = blocks.matmul(c_q, p["w_qb"], dtype)
        with jax.named_scope(SCOPE_KV_LATENT):
            c_kv, k_r = jnp.split(blocks.matmul(h, p["w_kva"], dtype), [c.kv_rank], axis=-1)
            c_kv = blocks.rms_norm(c_kv, p["kv_norm"], c.rms_eps)
            kv = blocks.matmul(c_kv, p["w_kvb"], dtype)
    with jax.named_scope(blocks.SCOPE_ROPE):
        q, k, v = _operands(c, backend, q, kv, k_r, rope)
    with jax.named_scope(blocks.SCOPE_KERNEL):
        out = blocks.attention_of(c, backend, q, k, v)
    return out.astype(jnp.float32).reshape(*h.shape[:2], -1)


def _mixer(c: LatentMoEConfig, backend: str, rope, x, p):
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(blocks.SCOPE_ATTENTION):
        with jax.named_scope(blocks.SCOPE_NORM):
            h = blocks.rms_norm(x, p["n1"], c.rms_eps)
        out = _attention(c, backend, rope, h, p)
        with jax.named_scope(blocks.SCOPE_OUT):
            return x + blocks.matmul(out, p["wo"], dtype)


# ---- the MLPs ----------------------------------------------------------------

def _dense_mlp(c: LatentMoEConfig, x, p):
    with jax.named_scope(blocks.SCOPE_MLP):
        with jax.named_scope(blocks.SCOPE_NORM):
            u = blocks.rms_norm(x, p["n2"], c.rms_eps)
        return x + blocks.swiglu(u, p["w_gate"], p["w_up"], p["w_down"],
                                 jnp.dtype(c.compute_dtype))


def route(c: LatentMoEConfig, u, p, real, rows: int):
    """``experts.sigmoid_route`` with the layer's bias in the selection: the
    ``experts_per_token`` largest of the sigmoid scores plus the bias, their
    gates from the scores alone; ``aux`` is the balance loss, a row at a time."""
    return experts.sigmoid_route(c, u, p, real, rows, bias=p[BIAS])


# ---- the stack and the module -------------------------------------------------

def _halves(c: LatentMoEConfig, backend: str, rope):
    """``(mixer, dense_mlp, expert_half)``, each rematerialised from its input."""
    kept = jax.checkpoint if c.remat else (lambda half: half)
    return (kept(lambda x, p: _mixer(c, backend, rope, x, p)),
            kept(lambda x, p: _dense_mlp(c, x, p)),
            kept(lambda x, p, real: experts.expert_half(
                c, backend, x, p, real, route=functools.partial(route, rows=x.shape[0]))))


def hidden_states(c: LatentMoEConfig, backend: str, params, seq):
    """``(x, stats, rope)``: the residual stream after the last layer of the
    stack ``[B, T, D]``, every expert layer's counts ``[expert_layers, ...]``
    and the positions' tables, under the pass's scope."""
    with jax.named_scope(blocks.SCOPE_EMBED):
        real = seq > 0
        rope = rope_tables(seq.shape[1], c.rope_dim, c.rope_theta)
        x = jnp.take(params["embed"], seq, axis=0)
    mixer, dense_mlp, expert_half = _halves(c, backend, rope)
    with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_LAYERS):
        if c.dense_layers:
            x, _ = jax.lax.scan(lambda x, p: (dense_mlp(mixer(x, p), p), None), x,
                                params["dense"])
        x, stats = jax.lax.scan(lambda x, p: expert_half(mixer(x, p), p, real), x,
                                params["layers"])
    return x, stats, rope


def _predict_ahead(c: LatentMoEConfig, backend: str, rope, params, z, targets):
    """``(ce, stats)`` of the prediction module on the stack's output ``z``
    [B, T, D]: position ``i`` reads ``target_i`` and is scored on
    ``target_{i+1}``; a position without a ``target_i`` is a padded slot."""
    p, dtype = params["mtp"], jnp.dtype(c.compute_dtype)
    kept = jax.checkpoint if c.remat else (lambda part: part)
    mixer, _, expert_half = _halves(c, backend, rope)

    def merge(z, embed, p):
        event = blocks.rms_norm(jnp.take(embed, targets, axis=0), p["embed_norm"], c.rms_eps)
        state = blocks.rms_norm(z, p["hidden_norm"], c.rms_eps)
        return blocks.matmul(jnp.concatenate([event, state], axis=-1), p["merge"], dtype)

    with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(SCOPE_MTP):
        with jax.named_scope(SCOPE_MERGE):
            m = kept(merge)(z, params["embed"], p)
        with jax.named_scope(blocks.SCOPE_LAYERS):
            m, stats = expert_half(mixer(m, p["layer"]), p["layer"], targets > 0)
        with jax.named_scope(blocks.SCOPE_EXIT):
            ahead = jnp.pad(targets[:, 1:], ((0, 0), (0, 1)))
            return blocks.masked_ce(c, m, p["final_norm"], params["head"], ahead), stats


def make_loss(c: LatentMoEConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step;
    ``aux`` is the three terms of the loss, the step's counts under
    ``sparse_moe.make_loss``'s names and ``router_load`` [routers, E], which
    ``move`` takes out again."""
    backend = blocks.backend_of(mesh, whole_rows=True)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        x, stats, rope = hidden_states(c, backend, params, seq)
        with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_EXIT):
            ce = blocks.masked_ce(c, x, params["final_norm"], params["head"], targets)
        mtp_ce = jnp.float32(0.0)
        if c.mtp_depth:
            mtp_ce, ahead = _predict_ahead(c, backend, rope, params, x, targets)
            stats = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b[None]]),
                                           stats, ahead)
        with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_EXIT):
            balance = stats["aux"].mean()
            out = {"ce": ce, "mtp_ce": mtp_ce, "balance": balance,
                   **experts.counts(c, stats), "router_load": stats["load"]}
            return ce + c.mtp_coef * mtp_ce + c.balance_coef * balance, out

    return loss_fn


def move(c: LatentMoEConfig, params, aux):
    """``(params, aux)`` after a step: every router's bias moved by
    ``bias_rate`` against the load the step's ``aux["router_load"]`` counted
    (up for an expert under the mean, down for one over it), and ``aux``
    without the loads, with the largest bias there now is."""
    aux = dict(aux)
    load = aux.pop("router_load").astype(jnp.float32)                   # [routers, E]
    with jax.named_scope(SCOPE_BIAS):
        moved = experts.bias_step(c.bias_rate, load)
        layers = {**params["layers"], BIAS: params["layers"][BIAS] + moved[:c.expert_layers]}
        params = {**params, "layers": layers}
        largest = jnp.abs(layers[BIAS]).max()
        if c.mtp_depth:
            layer = params["mtp"]["layer"]
            layer = {**layer, BIAS: layer[BIAS] + moved[-1]}
            params["mtp"] = {**params["mtp"], "layer": layer}
            largest = jnp.maximum(largest, jnp.abs(layer[BIAS]).max())
    return params, {**aux, "router_bias_abs_max": largest}


def score_last(c: LatentMoEConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row: the stack
    without the prediction module, the whole history a query."""
    x, _, _ = hidden_states(c, blocks.backend_of(None), params, seqs)
    return blocks.score_last(c, x, params["final_norm"], params["head"], last)
