"""The window backbone of the sequence template: a decoder whose layers are
of two kinds with their own head counts and rotary tables, **full** attention
over every earlier position and **window** attention over the last ``window``
positions alone, both on the same few key-value heads and behind one sigmoid
gate a head; the first layer's MLP is dense and every later layer's a routed
mixture of experts chosen by sigmoid scores, beside a shared expert every
token takes.

The block is that of ``Laguna-XS.2`` (``model_type laguna``: ``layer_types`` in
periods of one ``full_attention`` and three ``sliding_attention`` layers,
``sliding_window`` 512, ``num_attention_heads_per_layer`` 48 on a full layer
and 64 on a window layer over 8 key-value heads of 128, ``gating``, two
``rope_parameters``, ``mlp_layer_types`` ``dense`` then ``sparse``: 256
experts, 8 a token, one shared) with the item catalog as its vocabulary. For
one row ``x`` ``[T, D]``, ``n(.)`` RMSNorm with a plain weight:

- **attention of a layer** of kind ``k`` with ``H_k`` heads: ``h = n1(x)``;
  ``q = h W_q`` ``[T, H_k, hd]``, ``k = h W_k``, ``v = h W_v`` ``[T, KV, hd]``,
  ``g = sigmoid(h W_g)`` ``[T, H_k]``; ``q`` and ``k`` rotated by the table of
  ``k``; ``a = softmax over the pairs of k (q k' / sqrt(hd)) v``, query head
  ``j`` on key-value head ``j // (H_k / KV)``; ``x <- x + (g * a) W_o``. The
  pairs: full ``s <= t``; window ``t - window < s <= t`` (a query's own
  position counts);
- **the tables** (rotate-half convention, ``blocks.rotate``). Window:
  ``blocks.rope_tables`` at ``window_rope_theta`` over the whole head. Full:
  over the first ``rotary_dim = hd x full_rotary_fraction`` dimensions, the rest
  passed through, with YaRN's blended frequencies and ``cos``, ``sin`` scaled by
  ``full_rope_attention_factor`` (``yarn_tables``);
- **a dense layer's MLP**: ``x <- x + W_down(silu(W_gate u) * (W_up u))``,
  ``u = n2(x)``; **an expert layer's**: ``s = sigmoid(u W_r)`` over all
  ``num_experts`` in float32, the ``experts_per_token`` largest, their gates
  ``routed_scale s_e / sum s``; ``x <- x + sum_{e chosen, held here} gate_e
  FFN_e(u) + FFN_shared(u)``, the shared expert ungated
  (``experts.sigmoid_route`` with no bias, ``experts.expert_half``);
- loss: the mean cross-entropy at the positions with a target +
  ``balance_coef`` x the routers' balance loss (``experts.sigmoid_route``'s).

How it is worked (``benchmarks/reference_laguna.py`` is the same mathematics
with none of this):

- the layers are grouped **by shape** (``grouping``): ``first`` (layer 0: full,
  dense), ``periods`` (stacked ``[P, ...]``: each ``window`` ``[P, W, ...]``
  scanned inside, then ``full``), ``tail`` (the window layers after the last
  whole period, ``[W', ...]``); a ``layer_types`` that does not group so is
  refused. Each half of a layer keeps its input alone and is worked again in
  the backward pass (``remat``), as ``hybrid.py``;
- attention is ``ops/sparse_attention.py``'s causal programs, a window layer's
  with ``window``: forward and the one backward, they walk the tiles of the
  band alone. Their operands are written by ``ops/rope_layout.py``'s one
  program a phase (``blocks.rope_operands``, under ``rope``): q and k turned by
  the layer's table, q scaled, all three cast and laid heads-first as the
  programs read them (``blocks.attention_of``, under ``kernel``), and the
  backward program's ``dq``, ``dk``, ``dv`` turned back by its transpose; off
  the TPU ``blocks.rotate`` and the plain twin. A full layer's lies under
  the scope ``attention``, a window layer's under ``window_attention`` (one
  component, so a reader that looks for ``attention`` does not take it), with
  ``blocks``'s leaves below both;
- matmul inputs are ``compute_dtype`` (bfloat16) with float32 accumulation; the
  router, the residual stream, norms, gates, rotary positions, softmax, loss,
  master weights and Adam's moments are float32.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from predictionio_tpu.models.sequence import blocks, experts
from predictionio_tpu.ops import sparse_attention as sa

#: a window layer's mixer under ``seq.pass1/layers/window_attention``
SCOPE_WINDOW = "window_attention"
FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclass(frozen=True)
class WindowMoEConfig(experts.ExpertsConfig):
    hidden_size: int = 64
    #: every layer's kind, its MLP's and its heads, as the published lists
    layer_types: tuple = (FULL, WINDOW, WINDOW, WINDOW, FULL)
    mlp_layer_types: tuple = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    heads_per_layer: tuple = (6, 8, 8, 8, 6)
    num_kv_heads: int = 2
    head_dim: int = 16
    window: int = 16
    ffn_dim: int = 128          # the dense layer's MLP
    shared_expert_dim: int = 32
    routed_scale: float = 2.5
    balance_coef: float = 1e-4
    full_rope_theta: float = 5e5
    full_rope_factor: float = 64.0
    full_rope_original_len: int = 4096
    full_rope_beta_fast: float = 64.0
    full_rope_beta_slow: float = 1.0
    full_rope_attention_factor: float = 1.4158883083359672
    full_rotary_fraction: float = 0.5
    window_rope_theta: float = 1e4

    def __post_init__(self):
        super().__post_init__()
        for name, kind in (("layer_types", str), ("mlp_layer_types", str),
                           ("heads_per_layer", int)):
            object.__setattr__(self, name, tuple(kind(v) for v in getattr(self, name)))
        grouping(self)   # refuses a pattern that does not group
        for heads in set(self.heads_per_layer):
            if heads % self.num_kv_heads:
                raise ValueError(f"num_kv_heads={self.num_kv_heads} must divide the {heads}"
                                 " heads it serves")
        if self.window < 1:
            raise ValueError(f"window={self.window}: want at least 1 (a query reads itself)")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim or self.head_dim % 2:
            raise ValueError(
                f"full_rotary_fraction={self.full_rotary_fraction} of head_dim={self.head_dim}"
                " must be an even count of dimensions")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.full_rotary_fraction)


CONFIG = WindowMoEConfig
ENGINE_PARAMS = {
    **experts.ENGINE_PARAMS, "hiddenSize": "hidden_size", "layerTypes": "layer_types",
    "mlpLayerTypes": "mlp_layer_types", "numAttentionHeadsPerLayer": "heads_per_layer",
    "numKvHeads": "num_kv_heads", "headDim": "head_dim", "slidingWindow": "window",
    "ffnDim": "ffn_dim", "sharedExpertDim": "shared_expert_dim",
    "routedScalingFactor": "routed_scale", "balanceLossCoef": "balance_coef",
    "fullRopeTheta": "full_rope_theta", "fullRopeFactor": "full_rope_factor",
    "fullRopeOriginalLen": "full_rope_original_len", "fullRopeBetaFast": "full_rope_beta_fast",
    "fullRopeBetaSlow": "full_rope_beta_slow",
    "fullRopeAttentionFactor": "full_rope_attention_factor",
    "fullPartialRotaryFactor": "full_rotary_fraction", "windowRopeTheta": "window_rope_theta",
    "rmsNormEps": "rms_eps",
}


@dataclass(frozen=True)
class Grouping:
    """The stack by shape: layer 0, ``periods`` whole periods of
    ``period_windows`` window layers and a full one, ``tail_windows`` window
    layers after them; ``heads_full``, ``heads_window`` the two head counts."""

    periods: int
    period_windows: int
    tail_windows: int
    heads_full: int
    heads_window: int

    @property
    def window_layers(self) -> int:
        return self.periods * self.period_windows + self.tail_windows

    @property
    def full_layers(self) -> int:
        return 1 + self.periods


def grouping(c: WindowMoEConfig) -> Grouping:
    """How the three lists group, or a ``ValueError`` that prints them: layer
    0 full and dense; every later layer sparse; after it whole periods of the
    same count of window layers and then a full layer, and at the end fewer
    window layers than a period and its full layer hold; one head count a
    kind."""
    kinds, mlps, heads = c.layer_types, c.mlp_layer_types, c.heads_per_layer

    def refuse(why: str):
        raise ValueError(
            f"the layers do not group into a first layer, whole periods and a tail: {why};"
            f" layer_types={list(kinds)} mlp_layer_types={list(mlps)}"
            f" heads_per_layer={list(heads)}")

    if not len(kinds) == len(mlps) == len(heads) or len(kinds) < 2:
        refuse("the three lists want one entry a layer, two layers at least")
    if any(k not in (FULL, WINDOW) for k in kinds) or any(m not in (DENSE, SPARSE) for m in mlps):
        refuse(f"a layer is {FULL!r} or {WINDOW!r} and its MLP {DENSE!r} or {SPARSE!r}")
    if kinds[0] != FULL or mlps[0] != DENSE or DENSE in mlps[1:]:
        refuse("layer 0 is the full, dense one and every later layer sparse")
    rest = kinds[1:]
    every = rest.index(FULL) if FULL in rest else len(rest)
    periods = 0
    while rest[periods * (every + 1):(periods + 1) * (every + 1)] == (WINDOW,) * every + (FULL,):
        periods += 1
    tail = rest[periods * (every + 1):]
    if periods and not every:
        refuse("a period holds a window layer at least")
    if any(k != WINDOW for k in tail) or (periods and len(tail) > every) or not (periods or tail):
        refuse("after the periods come window layers alone, no more than a period's")
    by_kind = {kind: {h for k, h in zip(kinds, heads) if k == kind} for kind in (FULL, WINDOW)}
    if len(by_kind[FULL]) != 1 or len(by_kind[WINDOW]) != 1:
        refuse("every layer of a kind has the same heads")
    return Grouping(periods, every if periods else 0, len(tail),
                    by_kind[FULL].pop(), by_kind[WINDOW].pop())


def param_shapes(c: WindowMoEConfig) -> dict:
    """The parameter tree as shapes: ``first`` one layer, ``periods/window``
    led by ``[P, W]`` and ``periods/full`` by ``[P]``, ``tail`` by ``[W']``
    (a group without layers is left out)."""
    d, hd, kv, shared = c.hidden_size, c.head_dim, c.num_kv_heads, c.shared_expert_dim
    g = grouping(c)

    def attention(lead, heads):
        return {"n1": lead + (d,), "wq": lead + (d, heads * hd), "wk": lead + (d, kv * hd),
                "wv": lead + (d, kv * hd), "wg": lead + (d, heads),
                "wo": lead + (heads * hd, d), "n2": lead + (d,)}

    def expert_layer(lead, heads):
        return {
            **attention(lead, heads), "router": lead + (d, c.num_experts),
            "w_gate": lead + (c.held, d, c.expert_dim), "w_up": lead + (c.held, d, c.expert_dim),
            "w_down": lead + (c.held, c.expert_dim, d),
            "s_gate": lead + (d, shared), "s_up": lead + (d, shared), "s_down": lead + (shared, d),
        }

    shapes = {
        "embed": (c.vocab, d),
        "first": {**attention((), g.heads_full), "w_gate": (d, c.ffn_dim),
                  "w_up": (d, c.ffn_dim), "w_down": (c.ffn_dim, d)},
        "final_norm": (d,),
        "head": (c.vocab, d),
    }
    if g.periods:
        shapes["periods"] = {
            "window": expert_layer((g.periods, g.period_windows), g.heads_window),
            "full": expert_layer((g.periods,), g.heads_full)}
    if g.tail_windows:
        shapes["tail"] = expert_layer((g.tail_windows,), g.heads_window)
    return shapes


def init_params(c: WindowMoEConfig, rng) -> dict:
    """Norm weights 1, the embedding N(0, 1), matrices N(0, 0.02), those that
    write into the residual stream scaled down (``blocks.writer_stds``)."""
    return blocks.draw_params(
        param_shapes(c), rng, ones=("n1", "n2", "final_norm"),
        stds=blocks.writer_stds(("wo", "w_down", "s_down"), c.num_layers))


def count_params(c: WindowMoEConfig) -> int:
    return blocks.count_params(param_shapes(c))


def attention_backward_heads_per_step(c: WindowMoEConfig, kind: str) -> int:
    """The key-value heads a grid step of the backward attention program of a
    layer of ``kind`` works on a row of ``max_len`` (from the shapes alone)."""
    g = grouping(c)
    heads = g.heads_full if kind == FULL else g.heads_window
    return sa.backward_heads_per_step(
        c.num_kv_heads, heads // c.num_kv_heads, c.head_dim, c.head_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize)


def fit_attrs(c: WindowMoEConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span. ``window_tiles_walked``: what a
    window layer's forward and backward programs walk on a row a head, in tiles
    of the forward program's size (``window_tile``; the backward program's tile
    of queries may be twice it); ``window_tiles_needed``: the band's pairs,
    once for each program, in the same tiles."""
    g = grouping(c)
    band = sa.band_of(c.window, c.max_len)
    (bq, bk), (wide, _) = sa.tiles_of(
        c.num_kv_heads, g.heads_window // c.num_kv_heads, c.head_dim, c.head_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize)
    pairs = sa.band_pairs(c.max_len, band)
    walked = (sa.band_tiles(c.max_len, bq, bk, band)
              + sa.band_tiles(c.max_len, wide, bk, band) * wide // bq)
    return {
        **blocks.decoder_fit_attrs(c, c.num_layers, halves=True),
        **experts.fit_attrs(c, platform, attention_backward_heads_per_step(c, FULL), shared=True),
        "window_attention_backward_heads_per_step": attention_backward_heads_per_step(c, WINDOW),
        "kv_heads": c.num_kv_heads, "selection_kept_bytes": 0,
        "window": c.window, "window_layers": g.window_layers, "full_layers": g.full_layers,
        "heads_window": g.heads_window, "heads_full": g.heads_full,
        "window_pairs": pairs, "causal_pairs": sa.band_pairs(c.max_len, None),
        "window_tile": f"{bq}x{bk}", "window_tiles_walked": walked,
        "window_tiles_needed": round(2 * pairs / (bq * bk), 3),
        "rope_tables": 2,
        "rope_block": blocks.rope_block(c, platform, g.heads_full, c.num_kv_heads, c.head_dim),
        "window_rope_block": blocks.rope_block(c, platform, g.heads_window, c.num_kv_heads,
                                               c.head_dim),
    }


# ---- the tables --------------------------------------------------------------

def yarn_frequencies(dim: int, theta: float, factor: float, original_len: int,
                     beta_fast: float, beta_slow: float):
    """YaRN's ``dim / 2`` inverse frequencies: ``f_i = theta^(-2i / dim)``
    kept where a dimension turns more than ``beta_fast`` times over
    ``original_len`` positions, divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, blended linearly by dimension between the two."""
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def turns_at(n: float) -> float:   # the dimension that turns n times
        return dim * math.log(original_len / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001   # a ramp of no width is a step
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_tables(t: int, dim: int, theta: float, factor: float, original_len: int,
                beta_fast: float, beta_slow: float, attention_factor: float):
    """``cos, sin`` of ``[T, dim]`` at :func:`yarn_frequencies`, both scaled by
    ``attention_factor``; the frequencies repeated over both halves
    (``blocks.rope_tables``'s layout, which it equals at ``factor`` 1 and
    ``attention_factor`` 1)."""
    inv = yarn_frequencies(dim, theta, factor, original_len, beta_fast, beta_slow)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle) * attention_factor, jnp.sin(angle) * attention_factor


def rope_of(c: WindowMoEConfig, t: int) -> dict:
    """Each kind's ``(cos, sin)``: the full layers' over ``rotary_dim``, the
    window layers' over the whole head."""
    return {
        FULL: yarn_tables(t, c.rotary_dim, c.full_rope_theta, c.full_rope_factor,
                          c.full_rope_original_len, c.full_rope_beta_fast,
                          c.full_rope_beta_slow, c.full_rope_attention_factor),
        WINDOW: blocks.rope_tables(t, c.head_dim, c.window_rope_theta),
    }


# ---- the layers --------------------------------------------------------------

def _attention(c: WindowMoEConfig, backend: str, kind: str, rope, h, p):
    """The gated attention output before ``W_o`` ``[B, T, H x hd]`` of a layer
    of ``kind`` on the normed input ``h``."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    hd = c.head_dim
    window = c.window if kind == WINDOW else None
    with jax.named_scope(blocks.SCOPE_QKV):
        q = blocks.matmul(h, p["wq"], dtype).reshape(b, t, -1, hd)
        k, v = (blocks.matmul(h, p[w], dtype).reshape(b, t, c.num_kv_heads, hd)
                for w in ("wk", "wv"))
        gate = jax.nn.sigmoid(blocks.matmul(h, p["wg"], dtype))          # [B, T, H]
    with jax.named_scope(blocks.SCOPE_ROPE):
        q, k, v = blocks.rope_operands(c, backend, q, k, v, rope)
    with jax.named_scope(blocks.SCOPE_KERNEL):
        out = blocks.attention_of(c, backend, q, k, v, window=window)
        out = out.astype(jnp.float32) * gate[..., None]
    return out.reshape(b, t, -1)


def _mixer(c: WindowMoEConfig, backend: str, kind: str, rope, x, p):
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(SCOPE_WINDOW if kind == WINDOW else blocks.SCOPE_ATTENTION):
        with jax.named_scope(blocks.SCOPE_NORM):
            h = blocks.rms_norm(x, p["n1"], c.rms_eps)
        out = _attention(c, backend, kind, rope, h, p)
        with jax.named_scope(blocks.SCOPE_OUT):
            return x + blocks.matmul(out, p["wo"], dtype)


def _dense_mlp(c: WindowMoEConfig, x, p):
    with jax.named_scope(blocks.SCOPE_MLP):
        with jax.named_scope(blocks.SCOPE_NORM):
            u = blocks.rms_norm(x, p["n2"], c.rms_eps)
        return x + blocks.swiglu(u, p["w_gate"], p["w_up"], p["w_down"],
                                 jnp.dtype(c.compute_dtype))


def hidden_states(c: WindowMoEConfig, backend: str, params, seq):
    """``(x, stats)``: the residual stream after the last layer ``[B, T, D]``
    and every expert layer's counts ``[layers - 1, ...]`` in the layers' order,
    under the pass's scope."""
    with jax.named_scope(blocks.SCOPE_EMBED):
        real = seq > 0
        rope = rope_of(c, seq.shape[1])
        x = jnp.take(params["embed"], seq, axis=0)

    kept = jax.checkpoint if c.remat else (lambda half: half)
    mixer = {kind: kept(functools.partial(_mixer, c, backend, kind, rope[kind]))
             for kind in (FULL, WINDOW)}
    dense_mlp = kept(functools.partial(_dense_mlp, c))
    expert_half = kept(lambda x, p: experts.expert_half(
        c, backend, x, p, real, route=functools.partial(experts.sigmoid_route, rows=x.shape[0])))

    def layer(kind):
        return lambda x, p: expert_half(mixer[kind](x, p), p)

    def period(x, p):
        x, stats = jax.lax.scan(layer(WINDOW), x, p["window"])
        x, last = layer(FULL)(x, p["full"])
        return x, jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b[None]]), stats, last)

    found = []
    with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_LAYERS):
        x = dense_mlp(mixer[FULL](x, params["first"]), params["first"])
        if "periods" in params:
            x, stats = jax.lax.scan(period, x, params["periods"])
            found.append(jax.tree_util.tree_map(lambda a: a.reshape(-1, *a.shape[2:]), stats))
        if "tail" in params:
            x, stats = jax.lax.scan(layer(WINDOW), x, params["tail"])
            found.append(stats)
    return x, jax.tree_util.tree_map(lambda *parts: jnp.concatenate(parts), *found)


def make_loss(c: WindowMoEConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step;
    ``aux`` is scalars: the two terms of the loss and the step's counts, under
    ``sparse_moe.make_loss``'s names."""
    backend = blocks.backend_of(mesh, whole_rows=True)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        x, stats = hidden_states(c, backend, params, seq)
        with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_EXIT):
            ce = blocks.masked_ce(c, x, params["final_norm"], params["head"], targets)
            balance = stats["aux"].mean()
            out = {"ce": ce, "balance": balance, **experts.counts(c, stats)}
            return ce + c.balance_coef * balance, out

    return loss_fn


def score_last(c: WindowMoEConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row: the whole
    history a query (no cache of keys and values is carried between queries)."""
    x, _ = hidden_states(c, blocks.backend_of(None), params, seqs)
    return blocks.score_last(c, x, params["final_norm"], params["head"], last)
