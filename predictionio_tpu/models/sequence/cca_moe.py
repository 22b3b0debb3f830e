"""The compressed-convolution backbone of the sequence template: a decoder
whose attention runs in a compressed latent that two causal convolutions mix
before the heads read it, whose router is a small MLP that carries a state
from layer to layer and may send a token past the layer's experts, whose
half-layers merge into the residual stream through learned scales, and whose
item table is its head.

The block is that of ``ZAYA1-8B`` (``model_type zaya``: Compressed
Convolutional Attention, arXiv 2510.04476, with ``cca_time0`` and ``cca_time1``
taps, 8 query heads on 2 key-value heads of 128 inside a hidden size of 2,048;
the router of arXiv 2511.17127 with ``router_hidden_size`` 256, one of 16
experts a token or none; ``tie_word_embeddings``) with the item catalog as its
vocabulary. For one row ``x`` ``[T, D]``, ``n(.; w)`` RMSNorm, ``H`` query
heads on ``KV`` key-value heads of ``d``, ``G = H / KV``, ``Lq = H d``,
``Lk = KV d``; a row starts at ``t = 0`` and anything read before it is zero:

- **attention half**, ``u = n(x; n1)``: ``q0 = u W_q`` ``[T, Lq]``,
  ``k0 = u W_k`` ``[T, Lk]``; the value is this position's and the one
  before's, ``v[t] = concat(u[t] W_v1, u[t-1] W_v2)``, split into ``KV`` heads;
  ``m_q[t, i] = (q0[t, i] + k0[t, i // G]) / 2`` a query head and ``m_k[t, j]``
  its mean over group ``j``; over ``z = concat(q0, k0)`` a depthwise causal
  convolution of ``conv_time0`` taps with a bias and then one of
  ``conv_time1`` taps that mixes the ``d`` channels of each of the ``H + KV``
  head blocks (``conv1_w`` ``[H + KV, taps, d, d]``), tap ``i`` on position
  ``t - (taps - 1) + i``; ``q1 = z2[:, :Lq] + m_q``, ``k1 = z2[:, Lq:] + m_k``;
  ``q2 = sqrt(d) q1 / |q1|`` a head, ``k2 = tau_j sqrt(d) k1 / |k1|`` a
  key-value head; rotary positions over the first ``d x rotary_fraction``
  dimensions of every head (rotate-half); causal attention, scores times
  ``d ** -0.5``; ``y = heads W_o``;
- **expert half**, ``u = n(x; n2)``: ``r = u W_d + b_d + gamma * r_prev``
  (``r_prev`` the same of the layer before, zero for layer 0; ``r`` goes on to
  the next layer); ``s = W_3 gelu(W_2 gelu(W_1 n(r; n_r) + c_1) + c_2)``,
  ``p = softmax(s)`` over ``num_experts + 1`` choices in float32; the choice is
  ``argmax(p + beta)`` with ``beta`` reached by no gradient, the gate ``p`` at
  the choice; an expert gives ``y = gate SwiGLU_e(u)`` where this program
  holds it, the last choice (the skip) gives 0;
- **either half merges** ``x <- (x + b_r) a_r + (y + b_y) a_y``, four ``[D]``
  vectors a half;
- loss: the mean cross-entropy at the positions with a target, logits
  ``n(x; final_norm) Emb'`` (the table is the head). After the step ``beta``
  moves by ``bias_rate`` against the sign of each choice's load less the even
  load (``move``, the latent backbone's rule).

How it is worked (``benchmarks/reference_zaya.py`` is the same mathematics
with none of this):

- the layers are stacked ``[L, ...]`` under ``lax.scan`` with the carry
  ``(x, r)``; each half of a layer keeps its input alone and is worked again
  in the backward pass (``remat``), as ``hybrid.py``;
- the stage between the projections and the attention's operands (the value's
  shift, the mean, both convolutions, the norms and the temperature) is the
  plain expression under the scope ``attention/mix``: passes over ``[B, T,
  Lq + Lk]`` floats, the second convolution's taps grouped matmuls on
  ``compute_dtype`` inputs. ``u[t-1] W_v2`` is worked as ``(u W_v2)[t-1]``;
- attention is ``ops/sparse_attention.py``'s causal programs on the operands
  ``ops/rope_layout.py``'s one program a phase writes (``blocks.rope_operands``
  under ``rope``, ``blocks.attention_of`` under ``kernel``); off the TPU
  ``blocks.rotate`` and the plain twin;
- the router under ``moe/route`` with the leaves ``down``, ``carry``, ``mlp``
  and ``choose``, all float32 (the down-projection at ``highest``); the held
  experts are ``experts.py``'s, one assignment a token;
- matmul inputs are ``compute_dtype`` (bfloat16) with float32 accumulation; the
  residual stream, norms, the mean, the depthwise convolution, the l2 norms,
  rotary positions, softmax, the loss, master weights and Adam's moments are
  float32; the head is ``blocks.exit_ce``'s chunks of positions on the table.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from predictionio_tpu.models.sequence import blocks, experts
from predictionio_tpu.ops import sparse_attention as sa

#: Device scopes beside ``blocks``'s and ``experts``'s: ``attention/mix`` (the
#: stage between the projections and the operands' program), ``merge`` at the
#: end of either half, the router's leaves under ``moe/route``, the bias's move
#: under ``seq.optimizer/bias``.
SCOPE_MIX = "mix"
SCOPE_MERGE = "merge"
SCOPE_DOWN = "down"
SCOPE_CARRY = "carry"
SCOPE_ROUTER_MLP = "mlp"
SCOPE_CHOOSE = "choose"
SCOPE_BIAS = "bias"
BIAS = experts.BIAS
#: the leaves of either half's merge, in the order ``_merge`` reads them
MERGE = ("a_r", "b_r", "a_y", "b_y")


@dataclass(frozen=True)
class CcaMoEConfig(experts.ExpertsConfig):
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4          # query heads of the compressed latent
    num_kv_heads: int = 2
    head_dim: int = 16
    conv_time0: int = 2         # taps of the depthwise convolution
    conv_time1: int = 2         # taps of the convolution grouped by head
    router_dim: int = 32        # the router's state and its MLP's width
    experts_per_token: int = 1
    bias_rate: float = 1e-3
    rope_theta: float = 5e6
    rotary_fraction: float = 0.5
    rms_eps: float = 1e-5

    def __post_init__(self):
        super().__post_init__()
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_kv_heads={self.num_kv_heads} must divide the"
                             f" {self.num_heads} heads it serves")
        if self.num_kv_heads % 2:
            raise ValueError(f"num_kv_heads={self.num_kv_heads}: the value's two halves"
                             " (this position's, the one before's) want an even count")
        if self.conv_time0 < 1 or self.conv_time1 < 1:
            raise ValueError("conv_time0 and conv_time1: want a tap at least (the position's own)")
        if self.experts_per_token != 1:
            raise ValueError(f"experts_per_token={self.experts_per_token}: this router takes"
                             " one choice a token (an expert or the skip)")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"rotary_fraction={self.rotary_fraction} of head_dim={self.head_dim}"
                " must be an even count of dimensions")

    @property
    def q_width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def choices(self) -> int:
        """What a router chooses among: the experts, then the skip."""
        return self.num_experts + 1

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_fraction)


CONFIG = CcaMoEConfig
ENGINE_PARAMS = {
    **experts.ENGINE_PARAMS, "hiddenSize": "hidden_size", "numLayers": "num_layers",
    "numHeads": "num_heads", "numKvHeads": "num_kv_heads", "headDim": "head_dim",
    "ccaTime0": "conv_time0", "ccaTime1": "conv_time1", "routerHiddenSize": "router_dim",
    "biasUpdateRate": "bias_rate", "ropeTheta": "rope_theta",
    "partialRotaryFactor": "rotary_fraction", "rmsNormEps": "rms_eps",
}


def param_shapes(c: CcaMoEConfig) -> dict:
    """The parameter tree as shapes; the layers' arrays are stacked
    ``[L, ...]``. There is no head: the table is it."""
    d, hd, r = c.hidden_size, c.head_dim, c.router_dim
    n, blocks_ = (c.num_layers,), c.num_heads + c.num_kv_heads
    width = c.q_width + c.kv_width
    merges = {f"{name}{half}": n + (d,) for half in (1, 2) for name in MERGE}
    return {
        "embed": (c.vocab, d),
        "layers": {
            "n1": n + (d,), "wq": n + (d, c.q_width), "wk": n + (d, c.kv_width),
            "wv1": n + (d, c.kv_width // 2), "wv2": n + (d, c.kv_width // 2),
            "conv0_w": n + (width, c.conv_time0), "conv0_b": n + (width,),
            "conv1_w": n + (blocks_, c.conv_time1, hd, hd), "conv1_b": n + (blocks_, hd),
            "tau": n + (c.num_kv_heads,), "wo": n + (c.q_width, d),
            "n2": n + (d,), "w_d": n + (d, r), "b_d": n + (r,), "gamma": n + (r,),
            "n_r": n + (r,), "w_1": n + (r, r), "c_1": n + (r,), "w_2": n + (r, r),
            "c_2": n + (r,), "w_3": n + (r, c.choices), BIAS: n + (c.choices,),
            "w_gate": n + (c.held, d, c.expert_dim), "w_up": n + (c.held, d, c.expert_dim),
            "w_down": n + (c.held, c.expert_dim, d), **merges,
        },
        "final_norm": (d,),
    }


def init_params(c: CcaMoEConfig, rng) -> dict:
    """Norm weights, the merges' scales and the temperature 1, the merges'
    shifts, every bias and the router's bias 0, ``gamma`` 0.5; the embedding
    N(0, 1) and the final norm's weight ``1 / sqrt(D)`` (the table is the head
    too: logits of unit scale); matrices N(0, 0.02), those that write into the
    residual stream scaled down (``blocks.writer_stds``); the convolutions and the router's MLP
    drawn to keep their input's scale (a tap N(0, 1 / taps), a matrix
    N(0, 1 / fan-in)), so that the mixed path weighs what the mean weighs and a
    router's logits tell tokens apart from the first step."""
    fan_in = {"conv0_w": c.conv_time0, "conv1_w": c.conv_time1 * c.head_dim,
              "w_1": c.router_dim, "w_2": c.router_dim, "w_3": c.router_dim}
    return blocks.draw_params(
        param_shapes(c), rng,
        ones=("n1", "n2", "n_r", "tau", "a_r1", "a_y1", "a_r2", "a_y2"),
        zeros=(BIAS, "conv0_b", "conv1_b", "b_d", "c_1", "c_2", "b_r1", "b_y1", "b_r2", "b_y2"),
        stds={**blocks.writer_stds(("wo", "w_down"), c.num_layers),
              **{name: n ** -0.5 for name, n in fan_in.items()}},
        draws={"gamma": lambda key, shape: jnp.full(shape, 0.5, jnp.float32),
               "final_norm": lambda key, shape: jnp.full(shape, shape[-1] ** -0.5, jnp.float32)})


def count_params(c: CcaMoEConfig) -> int:
    """The trained parameters: every leaf but the routers' biases."""
    return blocks.count_params(param_shapes(c), but=(BIAS,))


#: a router's bias is fixed as far as the optimizer goes (``move`` moves it)
trained_labels = experts.trained_labels


def attention_backward_heads_per_step(c: CcaMoEConfig) -> int:
    """The key-value heads a grid step of the attention's backward program
    works on a row of ``max_len`` (from the shapes alone)."""
    return sa.backward_heads_per_step(
        c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, c.head_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize)


def cache_bytes_per_token(c: CcaMoEConfig) -> int:
    """What a cache of compressed keys and values would hold a token a layer,
    in ``compute_dtype``."""
    return 2 * c.kv_width * jnp.dtype(c.compute_dtype).itemsize


def fit_attrs(c: CcaMoEConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span."""
    return {
        **blocks.decoder_fit_attrs(c, c.num_layers, halves=True),
        **experts.fit_attrs(c, platform, attention_backward_heads_per_step(c), shared=False),
        "kv_heads": c.num_kv_heads, "selection_kept_bytes": 0,
        "latent_q_width": c.q_width, "latent_kv_width": c.kv_width,
        "conv_time0": c.conv_time0, "conv_time1": c.conv_time1,
        "router_width": c.router_dim, "skip_choices": 1,
        "experts_held_share": round(c.held / c.num_experts, 4), "router_bias_leaves": c.num_layers,
        "cache_bytes_per_token": cache_bytes_per_token(c), "head_tied": 1,
        "rope_block": blocks.rope_block(c, platform, c.num_heads, c.num_kv_heads, c.head_dim),
    }


# ---- the attention half --------------------------------------------------------

def _before(a, n: int):
    """``a[:, t - n]`` for ``a`` [B, T, ...]: a row's first ``n`` positions read zeros."""
    if not n:
        return a
    return jnp.pad(a, ((0, 0), (n, 0)) + ((0, 0),) * (a.ndim - 2))[:, :a.shape[1]]


def _unit(x, scale):
    """``scale x / |x|`` over the last axis."""
    return x * (scale * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)))


def _by_block(x, w):
    """``x[b, t, g, :] @ w[g]``: ``x`` [B, T, G, d], ``w`` [G, d, e] ->
    [B, T, G, e] float32, the head blocks a batch of matmuls."""
    y = jax.lax.dot_general(x, w, (((3,), (1,)), ((2,), (0,))),
                            preferred_element_type=jnp.float32)
    return jnp.moveaxis(y, 0, 2)


def mix(c: CcaMoEConfig, q0, k0, v1, v2, p):
    """The stage between the projections and the attention's operands:
    ``(q2 [B, T, H, d], k2, v [B, T, KV, d])`` float32 from ``q0`` [B, T, Lq],
    ``k0`` [B, T, Lk] and the value's two halves ``v1``, ``v2`` [B, T, Lk / 2]
    (``v2`` not yet shifted)."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = q0.shape
    h, kv, hd = c.num_heads, c.num_kv_heads, c.head_dim
    v = jnp.concatenate([v1, _before(v2, 1)], axis=-1).reshape(b, t, kv, hd)
    m_q = 0.5 * (q0.reshape(b, t, kv, h // kv, hd) + k0.reshape(b, t, kv, 1, hd))
    m_k = m_q.mean(axis=3)
    z = jnp.concatenate([q0, k0], axis=-1)
    z1 = p["conv0_b"] + sum(p["conv0_w"][:, i] * _before(z, c.conv_time0 - 1 - i)
                            for i in range(c.conv_time0))
    z1 = z1.reshape(b, t, h + kv, hd).astype(dtype)
    z2 = p["conv1_b"] + sum(
        _by_block(_before(z1, c.conv_time1 - 1 - i), p["conv1_w"][:, i].astype(dtype))
        for i in range(c.conv_time1))
    q1 = z2[:, :, :h] + m_q.reshape(b, t, h, hd)
    k1 = z2[:, :, h:] + m_k
    root = jnp.sqrt(jnp.float32(hd))
    return _unit(q1, root), _unit(k1, root) * p["tau"][:, None], v


def _merge(half: int, p, x, y):
    """``(x + b_r) a_r + (y + b_y) a_y`` with the four vectors of ``half``."""
    a_r, b_r, a_y, b_y = (p[f"{name}{half}"] for name in MERGE)
    with jax.named_scope(SCOPE_MERGE):
        return (x + b_r) * a_r + (y + b_y) * a_y


def _mixer(c: CcaMoEConfig, backend: str, rope, x, p):
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = x.shape
    with jax.named_scope(blocks.SCOPE_ATTENTION):
        with jax.named_scope(blocks.SCOPE_NORM):
            u = blocks.rms_norm(x, p["n1"], c.rms_eps)
        with jax.named_scope(blocks.SCOPE_QKV):
            q0, k0, v1, v2 = (blocks.matmul(u, p[w], dtype) for w in ("wq", "wk", "wv1", "wv2"))
        with jax.named_scope(SCOPE_MIX):
            q, k, v = mix(c, q0, k0, v1, v2, p)
        with jax.named_scope(blocks.SCOPE_ROPE):
            q, k, v = blocks.rope_operands(c, backend, q, k, v, rope)
        with jax.named_scope(blocks.SCOPE_KERNEL):
            out = blocks.attention_of(c, backend, q, k, v).astype(jnp.float32)
        with jax.named_scope(blocks.SCOPE_OUT):
            y = blocks.matmul(out.reshape(b, t, -1), p["wo"], dtype)
        return _merge(1, p, x, y)


# ---- the expert half -----------------------------------------------------------

def route(c: CcaMoEConfig, u, p, real, carry):
    """``(experts, gates, stats, carry')`` for the normed tokens ``u`` [N, D]
    and the router's state of the layer before ``carry`` [N, R]: the state
    ``r = u W_d + b_d + gamma carry`` (handed on as it is), the MLP's softmax
    over the ``choices``, the largest of it plus the bias [N, 1] and its gate.
    ``stats`` holds ``experts.load_stats``'s counts, the whole ``load``
    [choices], the choices the bias changed and the state's rms."""
    high = jax.lax.Precision.HIGHEST
    with jax.named_scope(SCOPE_DOWN):
        r = jnp.matmul(u, p["w_d"], precision=high) + p["b_d"]
    with jax.named_scope(SCOPE_CARRY):
        r = r + p["gamma"] * carry
    with jax.named_scope(SCOPE_ROUTER_MLP):
        gelu = functools.partial(jax.nn.gelu, approximate=False)
        hidden = blocks.rms_norm(r, p["n_r"], c.rms_eps)
        for w, bias in (("w_1", "c_1"), ("w_2", "c_2")):
            hidden = gelu(jnp.matmul(hidden, p[w], precision=high) + p[bias])
        logits = jnp.matmul(hidden, p["w_3"], precision=high)
    with jax.named_scope(SCOPE_CHOOSE):
        probs = jax.nn.softmax(logits, axis=-1)
        chosen = jnp.argmax(probs + jax.lax.stop_gradient(p[BIAS]), axis=-1)[:, None]
        gates = jnp.take_along_axis(probs, chosen, axis=-1)
        load = experts.load_of(c, chosen, real, c.choices)
        count = jnp.maximum(real.sum(), 1).astype(jnp.float32)
        stats = {
            **experts.load_stats(c, load), "load": load,
            "bias_decided": (real & (chosen[:, 0] != jnp.argmax(probs, axis=-1))).sum(),
            "carry_rms": jnp.sqrt(jnp.where(real[:, None], r * r, 0.0).sum()
                                  / (count * r.shape[-1])),
        }
    return chosen.astype(jnp.int32), gates, stats, r


# ---- the stack -----------------------------------------------------------------

def rope_of(c: CcaMoEConfig, t: int):
    """``cos, sin`` ``[T, rotary_dim]``: the head's first dimensions turn."""
    return blocks.rope_tables(t, c.rotary_dim, c.rope_theta)


def hidden_states(c: CcaMoEConfig, backend: str, params, seq):
    """``(x, stats)``: the residual stream after the last layer ``[B, T, D]``
    and every layer's counts ``[L, ...]``, under the pass's scope."""
    with jax.named_scope(blocks.SCOPE_EMBED):
        real = seq > 0
        rope = rope_of(c, seq.shape[1])
        x = jnp.take(params["embed"], seq, axis=0)
    kept = jax.checkpoint if c.remat else (lambda half: half)
    mixer = kept(functools.partial(_mixer, c, backend, rope))
    expert_half = kept(lambda x, r, p: experts.expert_half(
        c, backend, x, p, real, route=route, carry=r, merge=functools.partial(_merge, 2, p)))

    def layer(carry, p):
        x, stats, r = expert_half(mixer(carry[0], p), carry[1], p)
        return (x, r), stats

    with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_LAYERS):
        state = jnp.zeros(seq.shape + (c.router_dim,), jnp.float32)   # layer 0 reads no state
        (x, _), stats = jax.lax.scan(layer, (x, state), params["layers"])
    return x, stats


def make_loss(c: CcaMoEConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step;
    ``aux`` is the loss's one term, the step's counts under
    ``sparse_moe.make_loss``'s names with the skip's, the bias's and the
    state's beside them, and ``router_load`` [L, choices], which ``move`` takes
    out again."""
    backend = blocks.backend_of(mesh, whole_rows=True)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        x, stats = hidden_states(c, backend, params, seq)
        with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_EXIT):
            ce = blocks.masked_ce(c, x, params["final_norm"], params["embed"], targets)
            out = {"ce": ce, **experts.counts(c, stats),
                   "moe_bias_decided": stats["bias_decided"].sum(),
                   "router_carry_rms": stats["carry_rms"].mean(),
                   "router_load": stats["load"]}
            return ce, out

    return loss_fn


def move(c: CcaMoEConfig, params, aux):
    """``(params, aux)`` after a step: every router's bias moved by
    ``bias_rate`` against the load the step's ``aux["router_load"]`` counted
    (up for a choice under the even load, down for one over it; the skip is a
    choice like the others), and ``aux`` without the loads, with the largest
    bias there now is."""
    aux = dict(aux)
    load = aux.pop("router_load").astype(jnp.float32)                   # [L, choices]
    with jax.named_scope(SCOPE_BIAS):
        layers = {**params["layers"],
                  BIAS: params["layers"][BIAS] + experts.bias_step(c.bias_rate, load)}
        return ({**params, "layers": layers},
                {**aux, "router_bias_abs_max": jnp.abs(layers[BIAS]).max()})


def score_last(c: CcaMoEConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row: the whole
    history a query (no cache of compressed keys and values, of the
    convolutions' trailing positions or of the router's state is carried
    between queries)."""
    x, _ = hidden_states(c, blocks.backend_of(None), params, seqs)
    return blocks.score_last(c, x, params["final_norm"], params["embed"], last)
