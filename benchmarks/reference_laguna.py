"""The plain reference of the window-and-full decoder (``model_type laguna``,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json: layers of
full attention and of attention over a window of 512, each kind with its own
head count and rotary table, 8 key-value heads, one sigmoid gate a head, a
dense first layer, then 256 experts of which a token takes the 8 its sigmoid
scores pick, beside one ungated shared expert) with an item catalog as its
vocabulary: forward, loss (two terms) and gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense scores head by head against
every key of the row with a mask made from positions, tables made from the
formula, every held expert over every token
(``reference_qwen3next.experts_part``), no kernel, no scan over layers, nothing
imported from the program. So that two rows of 8,192 positions fit a chip, a
layer keeps its input alone for the backward pass and a row of it, an expert
of it, a block of ``query_block`` queries their inputs alone
(``jax.checkpoint``): the same numbers, recomputed.

The parameters come grouped by shape, as ISSUE 44 groups them: ``first``
(layer 0: full attention, dense MLP), ``periods`` (``window`` ``[P, W, ...]``
and ``full`` ``[P, ...]``: ``W`` window layers, then a full one, ``P`` times),
``tail`` (``[W', ...]`` window layers after the last period); ``layers_of``
puts them back in the model's order. A layer's heads are its ``wq``'s columns
over ``head_dim``.

For one row ``x`` ``[T, D]``, ``n(.)`` RMSNorm with a plain weight, ``dims``
giving ``head_dim``, ``num_kv_heads``, ``window``, ``full_rope`` (``theta``,
``factor``, ``original_len``, ``beta_fast``, ``beta_slow``,
``attention_factor``, ``rotary_fraction``), ``window_rope_theta``,
``experts_per_token``, ``experts_held`` ``(lo, hi)``, ``routed_scale``,
``balance_coef``, ``rms_eps``:

- **attention** of a layer of kind full or window with ``H`` heads:
  ``h = n1(x)``; ``q = h W_q`` ``[T, H, hd]``, ``k = h W_k``, ``v = h W_v``
  ``[T, KV, hd]``, ``g = sigmoid(h W_g)`` ``[T, H]``; ``q``, ``k`` rotated by the
  kind's table (rotate-half: dimension ``i`` of the rotated part pairs with
  ``i + half``); query head ``j`` reads key-value head ``j // (H / KV)``;
  ``a = softmax(q k' / sqrt(hd)) v`` over ``s <= t`` (full) or
  ``t - window < s <= t`` (window); ``x <- x + (g * a) W_o``;
- **tables**. Window: angle ``t theta_w^(-2i / hd)`` over the whole head.
  Full, over the first ``rd = hd x rotary_fraction`` dimensions, the rest
  passed through: ``f_i = theta^(-2i / rd)``; ``c(n) = rd ln(original_len /
  (2 pi n)) / (2 ln theta)``; ``low = max(floor(c(beta_fast)), 0)``,
  ``high = min(ceil(c(beta_slow)), rd - 1)``; ``r_i = clip((i - low) / (high -
  low), 0, 1)``; ``inv_i = (f_i / factor) r_i + f_i (1 - r_i)``; ``cos`` and
  ``sin`` of ``t inv_i`` times ``attention_factor``;
- layer 0's MLP: ``x <- x + W_down(silu(W_gate u) * (W_up u))``, ``u = n2(x)``;
  a later layer's: ``s = sigmoid(u W_r)``; the ``experts_per_token`` largest;
  ``gate_e = routed_scale s_e / (sum s + 1e-20)``; ``x <- x + sum_{e chosen,
  held} gate_e FFN_e(u) + FFN_shared(u)``; a row's balance term is
  ``sum_e f_e P_e`` with ``f_e = E / (K T_r)`` times the row's real positions
  that chose ``e`` (no gradient) and ``P_e`` the row's mean of
  ``s_e / sum_j s_j``;
- ``loss = ce + balance_coef balance``: the mean cross-entropy at the
  positions with a target, and the mean of the rows' balance terms over rows
  and expert layers.

The controls of the benchmark's ``correct`` (``how``): ``precision``
"bfloat16" (every parameter rounded to bfloat16, logits and loss held in
bfloat16); ``window`` "none" (every causal pair on the window layers) or
"off_by_one" (``t - window <= s``); ``tables`` "one" (the full layers' table
on every layer); ``yarn`` False (plain frequencies at the full layers' theta);
``rope_scaled`` False (``attention_factor`` 1); ``gate`` False; ``router``
"softmax"; ``scaled`` False (``routed_scale`` 1).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference_joyai import head_ce, swiglu
from benchmarks.reference_keye import _rounded, rms_norm
from benchmarks.reference_qwen3next import experts_part

_NEG = -1e30
SOUND = {"precision": "float32", "window": "band", "tables": "two", "yarn": True,
         "rope_scaled": True, "gate": True, "router": "sigmoid", "scaled": True}
FULL, WINDOW = "full", "window"


def layers_of(params) -> list:
    """``[(kind, layer's parameters), ...]`` in the model's order, out of a
    tree grouped like the parameters."""
    at = lambda tree, *i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    out = [(FULL, params["first"])]
    if "periods" in params:
        window, full = params["periods"]["window"], params["periods"]["full"]
        periods, inside = window["n1"].shape[:2]
        for p in range(periods):
            out += [(WINDOW, at(window, p, w)) for w in range(inside)]
            out.append((FULL, at(full, p)))
    if "tail" in params:
        out += [(WINDOW, at(params["tail"], w)) for w in range(params["tail"]["n1"].shape[0])]
    return out


def full_frequencies(rope: dict, dim: int, how) -> jnp.ndarray:
    """The full layers' ``dim / 2`` inverse frequencies: YaRN's blend."""
    theta = float(rope["theta"])
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not how["yarn"]:
        return f

    def c(n):
        return dim * math.log(rope["original_len"] / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), dim - 1)
    r = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0, 1)
    return f / rope["factor"] * r + f * (1 - r)


def table_of(kind: str, t: int, dims: dict, how) -> tuple:
    """``(cos, sin)`` ``[T, rotated / 2]`` of a layer of ``kind``."""
    hd = dims["head_dim"]
    if kind == WINDOW and how["tables"] == "two":
        inv = dims["window_rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        scale = 1.0
    else:
        rope = dims["full_rope"]
        inv = full_frequencies(rope, int(hd * rope["rotary_fraction"]), how)
        scale = rope["attention_factor"] if how["rope_scaled"] else 1.0
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rotated(x, cos, sin):
    """Rotary positions on the first ``2 x cos.shape[-1]`` dimensions of ``x``
    [T, H, hd]: dimension ``i`` of them pairs with ``i + half``."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(kind: str, p, h, dims, how):
    """The attention's output ``[T, D]`` of one row's normed input ``h``."""
    t = h.shape[0]
    hd, kv, window = dims["head_dim"], dims["num_kv_heads"], dims["window"]
    heads = p["wq"].shape[-1] // hd
    cos, sin = table_of(kind, t, dims, how)
    q = rotated((h @ p["wq"]).reshape(t, heads, hd), cos, sin).reshape(t, kv, heads // kv, hd)
    k = rotated((h @ p["wk"]).reshape(t, kv, hd), cos, sin)
    v = (h @ p["wv"]).reshape(t, kv, hd)
    block = min(dims.get("query_block", 256), t)
    banded = kind == WINDOW and how["window"] != "none"
    reach = window + (how["window"] == "off_by_one")

    @jax.checkpoint
    def queries(q_block, positions):
        s = jnp.einsum("qkgd,skd->kgqs", q_block, k) / jnp.sqrt(jnp.float32(hd))
        keys = jnp.arange(t)[None, :]
        on = keys <= positions[:, None]
        if banded:
            on = on & (keys > positions[:, None] - reach)
        weights = jax.nn.softmax(jnp.where(on[None, None], s, _NEG), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", weights, v)

    out = jax.lax.map(lambda args: queries(*args), (
        q.reshape(-1, block, kv, heads // kv, hd), jnp.arange(t).reshape(-1, block)))
    out = out.reshape(t, heads, hd)
    if how["gate"]:
        out = out * jax.nn.sigmoid(h @ p["wg"])[:, :, None]
    return out.reshape(t, heads * hd) @ p["wo"]


def experts_mlp(p, x, real, dims, how):
    """``(x', seen)``: the routed experts held here and the shared expert on
    one row; ``seen`` holds the row's assignments to every expert ``load`` [E]
    and its balance term."""
    u = rms_norm(x, p["n2"], dims["rms_eps"])
    logits = u @ p["router"]
    scores = jax.nn.sigmoid(logits) if how["router"] == "sigmoid" else jax.nn.softmax(logits)
    total, slots = scores.shape[-1], dims["experts_per_token"]
    experts = jax.lax.top_k(scores, slots)[1]
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    gates = gates * (dims["routed_scale"] if how["scaled"] else 1.0)
    picked = (experts[..., None] == jnp.arange(total)).any(axis=1)
    load = jnp.where(real[:, None], picked, False).sum(axis=0)
    count = jnp.maximum(real.sum(), 1).astype(jnp.float32)
    often = jax.lax.stop_gradient(load.astype(jnp.float32)) * (total / slots) / count
    share = scores / scores.sum(axis=-1, keepdims=True)
    mean_share = jnp.where(real[:, None], share, 0.0).sum(axis=0) / count
    shared = swiglu(u, p["s_gate"], p["s_up"], p["s_down"])
    return (x + experts_part(p, u, experts, gates, real, dims) + shared,
            {"load": load, "balance": (often * mean_share).sum()})


def loss(params, seq, targets, dims, how=SOUND):
    """``(loss, aux)`` of the batch ``seq`` [B, T] with ``targets`` [B, T]
    (0 = none): ``aux`` holds the two terms (``ce``, ``balance``) and the
    assignments ``load`` [expert layers, E]. Layers and rows are Python loops."""
    with jax.default_matmul_precision("highest"):
        params = _rounded(params, how["precision"])
        out = jnp.dtype(how["precision"])
        eps, real = dims["rms_eps"], seq > 0
        x = params["embed"][seq]
        seen = []
        for n, (kind, p) in enumerate(layers_of(params)):
            @jax.checkpoint
            def row(p, x_b, real_b, kind=kind, dense=n == 0):
                x_b = x_b + attention(kind, p, rms_norm(x_b, p["n1"], eps), dims, how)
                if dense:
                    u = rms_norm(x_b, p["n2"], eps)
                    return x_b + swiglu(u, p["w_gate"], p["w_up"], p["w_down"]), {}
                return experts_mlp(p, x_b, real_b, dims, how)

            done = [row(p, x[b], real[b]) for b in range(seq.shape[0])]
            x = jnp.stack([x_b for x_b, _ in done])
            if n:
                seen.append({name: jnp.stack([s[name] for _, s in done]) for name in done[0][1]})
        ce = head_ce(rms_norm(x, params["final_norm"], eps), params["head"], targets, out)
        balance = jnp.stack([s["balance"] for s in seen]).mean()      # [layers, B] -> over both
        return ce + dims["balance_coef"] * balance, {
            "ce": ce, "balance": balance,
            "load": jnp.stack([s["load"].sum(axis=0) for s in seen])}


def loss_and_grads(params, seq, targets, dims, how=SOUND):
    """``(loss, aux, grads)``: the gradient with respect to every parameter."""
    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(params, seq, targets, dims, how)
    return value, aux, grads


def subset_of(params, head_rows) -> dict:
    """The tensors the benchmark's ``correct`` compares gradients of, out of a
    tree shaped like the parameters; between them they see every new piece:
    ``W_q``, ``W_k``, ``W_v`` and ``W_g`` of the first window layer (the band,
    its table, the wider heads and their gate; a key's value gathers the
    cotangents of the queries whose band holds it, so one query more or fewer
    shows there first); ``W_q`` and ``W_g`` of the last
    full layer (the YaRN table over half the head, six query heads a key-value
    head); ``W_o`` of layer 0 and its MLP's down-projection; the first and the
    last router; the held experts' down-projections in the first expert layer,
    all of them together; the shared expert's in the last; the final norm and
    the head's rows of the sampled items."""
    layers = layers_of(params)
    windows = [p for kind, p in layers if kind == WINDOW]
    fulls = [p for kind, p in layers if kind == FULL]
    first, routed = layers[0][1], [p for _, p in layers[1:]]
    return {
        "wq_window_first": windows[0]["wq"], "wk_window_first": windows[0]["wk"],
        "wv_window_first": windows[0]["wv"], "wg_window_first": windows[0]["wg"],
        "wq_full_last": fulls[-1]["wq"], "wg_full_last": fulls[-1]["wg"],
        "wo_first": first["wo"], "dense_down": first["w_down"],
        "router_first": routed[0]["router"], "router_last": routed[-1]["router"],
        "w_down_first": routed[0]["w_down"], "shared_down_last": routed[-1]["s_down"],
        "final_norm": params["final_norm"], "head_rows": params["head"][head_rows],
    }
