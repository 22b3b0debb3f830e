"""The plain reference of the sparse-expert decoder with learned sparse
attention (``model_type KeyeVL2``, the language model of
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json:
grouped key-value heads, a DeepSeek-Sparse-Attention indexer, 128 experts and
8 a token, no shared expert) with an item catalog as its vocabulary: forward,
index scores, selection, loss and gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense ``[queries, T]`` scores with
the selection as a mask, a loop over rows, layers, experts and blocks of
queries, every expert over every token, no kernel, no grouped matmul, nothing
imported from the program. It makes its own selection from its own index
scores, by rank (a stable sort), not by a threshold. So that two rows of 8,192
positions fit a chip and compile in seconds, the loops over layers, experts
and query blocks are ``lax.scan`` / ``lax.map`` over the stacked arrays (a
loop's body is the equations below), a block of ``query_block`` queries'
scores exists at a time, and a layer keeps its input alone for the backward
pass (``jax.checkpoint`` around a block and around a layer): the same
numbers, recomputed.

For one row ``x`` ``[T, D]`` of the residual stream, ``dims`` giving
``num_heads``, ``num_kv_heads``, ``head_dim``, ``index_heads``, ``index_dim``,
``index_topk``, ``experts_per_token``, ``experts_held`` ``(lo, hi)``,
``rope_theta``, ``rms_eps``:

- ``h = n1(x)``; ``q = h Wq`` ``[T, H, hd]``, ``k = h Wk``, ``v = h Wv``
  ``[T, KV, hd]``; rotary positions on ``q`` and ``k`` (rotate-half over the
  whole head, positions ``0..T-1``: ``mrope_section`` with one position id for
  its three sections, which is what a text token gets);
- indexer: ``qI = h WqI`` ``[T, HI, dI]``, ``kI = rms(h WkI)`` ``[T, dI]``,
  ``w = h Ww`` ``[T, HI]``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``;
  ``S_t`` = the ``index_topk`` positions ``s <= t`` of largest ``I[t, s]``, all
  of them while ``t < index_topk``, ties to the earlier position;
- ``a[t] = softmax_{s in S_t}(q[t, g] . k[s, g // (H / KV)] / sqrt(hd))``;
  ``x = x + concat_g(a v) Wo``;
- ``u = n2(x)``; ``p = softmax(u Wr)`` over all the experts; ``E_t`` the
  ``experts_per_token`` largest; ``g[t, e] = p[t, e] / sum_{E_t} p``;
  ``x = x + sum_{e in E_t, lo <= e < hi} g[t, e] W2_e (silu(W1_e u) * (W3_e u))``:
  the experts outside ``[lo, hi)`` are another chip's and add nothing here;
- ``logits = W_head n_f(x)``; the loss is the mean cross-entropy over the
  positions with a target plus ``aux_coef`` times the mean over the layers of
  ``E sum_e f_e P_e``, ``f_e`` the batch's assignments to expert ``e`` a real
  token (no gradient: a count) and ``P_e`` the batch's mean of ``p[., e]``.

Choices the published config leaves open (the configuration's ``assumed``):
the indexer reads the layer's normed input; ``kI`` is RMS-normed with no
learned scale; no per-head norm on ``q`` and ``k``; the auxiliary loss is the
family's (Qwen3-MoE's ``load_balancing_loss_func``), a layer at a time; the
indexer's three matrices take no gradient from this loss (a hard top-k
passes none). The vocabulary is an item catalog: id 0 is padding, rows are
left-aligned (padding follows the events, so no real query can see it), a
padded slot is routed nowhere and counted nowhere, and a position whose next
slot is padding has no target.

``f_e`` and ``P_e`` are the batch's, so ``loss`` takes the batch whole.

The controls of the benchmark's ``correct`` (``how``): ``precision`` "bfloat16"
(every parameter rounded to bfloat16, logits and loss held in bfloat16);
``selection`` "window" (the last ``index_topk`` positions in place of the
indexer's choice); ``renormalise`` False (``g[t, e] = p[t, e]``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30
SOUND = {"precision": "float32", "selection": "indexer", "renormalise": True}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """Rotary positions on ``x`` [T, H, hd], positions 0..T-1, rotate-half."""
    t, hd = x.shape[0], x.shape[2]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def index_scores(ip, h, positions, dims):
    """``I[t, s]`` for the queries ``positions`` of the row ``h`` [T, D]."""
    hi, di = dims["index_heads"], dims["index_dim"]
    k_idx = h @ ip["wk"]
    k_idx = k_idx * jax.lax.rsqrt(jnp.mean(k_idx * k_idx, axis=-1, keepdims=True)
                                  + dims["rms_eps"])
    hq = h[positions]
    q_idx = (hq @ ip["wq"]).reshape(-1, hi, di)
    dots = jnp.maximum(jnp.einsum("qjd,sd->qjs", q_idx, k_idx), 0.0)
    return jnp.einsum("qjs,qj->qs", dots, hq @ ip["ww"])


def selection(scores, positions, dims, how):
    """bool ``[queries, T]``: the keys each query reads."""
    t, topk = scores.shape[1], dims["index_topk"]
    keys = jnp.arange(t)[None, :]
    causal = keys <= positions[:, None]
    if how["selection"] == "window":
        return causal & (keys > positions[:, None] - topk)
    # rank 0 is the largest score; among equals the earlier position ranks first
    order = jnp.argsort(-jnp.where(causal, scores, -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return causal & (rank < topk)


def attention(p, ip, h, dims, how, probe=None):
    """``(o, probed)``: the attention output ``[T, D]`` of one row's normed
    input ``h``, and the index scores and selection of the query positions
    ``probe`` (None: nothing)."""
    t = h.shape[0]
    heads, kv, hd = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    q = rope((h @ p["wq"]).reshape(t, heads, hd), dims["rope_theta"])
    k = rope((h @ p["wk"]).reshape(t, kv, hd), dims["rope_theta"])
    v = (h @ p["wv"]).reshape(t, kv, hd)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))   # head g reads g // (H / KV)
    block = min(dims.get("query_block", 512), t)
    blocks = jnp.arange(t).reshape(-1, block)
    # the selection passes no gradient: made once, from the input as it stands
    frozen = jax.lax.stop_gradient(h)
    chosen = jax.lax.map(
        lambda positions: selection(index_scores(ip, frozen, positions, dims),
                                    positions, dims, how), blocks)

    @jax.checkpoint
    def queries(q_block, chosen_block):
        s = jnp.einsum("qhd,shd->hqs", q_block, k) / jnp.sqrt(jnp.float32(hd))
        weights = jax.nn.softmax(jnp.where(chosen_block[None], s, _NEG), axis=-1)
        return jnp.einsum("hqs,shd->qhd", weights, v)

    out = jax.lax.map(lambda args: queries(*args), (q.reshape(-1, block, heads, hd), chosen))
    probed = None
    if probe is not None:
        probed = (index_scores(ip, frozen, probe, dims), chosen.reshape(t, t)[probe])
    return out.reshape(t, heads * hd) @ p["wo"], probed


def routing(p, u, dims, how):
    """``(probs [T, E], experts [T, K], gates [T, K])``."""
    probs = jax.nn.softmax(u @ p["router"], axis=-1)
    top_p, experts = jax.lax.top_k(probs, dims["experts_per_token"])
    gates = top_p / top_p.sum(axis=-1, keepdims=True) if how["renormalise"] else top_p
    return probs, experts, gates


def experts_part(p, u, experts, gates, real, dims):
    """The held experts' part of the routed sum, an expert at a time over every
    token, weighted by the token's gate for it (0 where it did not choose it)."""
    lo, hi = dims["experts_held"]

    def one(args):
        e, w_gate, w_up, w_down = args
        gate = jnp.where((experts == e) & real[:, None], gates, 0.0).sum(axis=-1)
        return gate[:, None] * ((jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down)

    return jax.lax.map(one, (jnp.arange(lo, hi), p["w_gate"], p["w_up"], p["w_down"])).sum(axis=0)


def layer(p, ip, x, real, dims, how, probe=None):
    """One decoder layer on one row: ``(x', seen)``; ``seen`` holds the sum
    over the row's real tokens of the router's probabilities ``[E]``, the
    assignments to every expert ``[E]`` and what ``attention`` probed."""
    eps = dims["rms_eps"]
    o, probed = attention(p, ip, rms_norm(x, p["n1"], eps), dims, how, probe)
    x = x + o
    u = rms_norm(x, p["n2"], eps)
    probs, experts, gates = routing(p, u, dims, how)
    picked = (experts[..., None] == jnp.arange(probs.shape[-1])).any(axis=1)
    seen = {"probs": jnp.where(real[:, None], probs, 0.0).sum(axis=0),
            "load": jnp.where(real[:, None], picked, False).sum(axis=0)}
    if probed is not None:
        seen["scores"], seen["chosen"] = probed
    return x + experts_part(p, u, experts, gates, real, dims), seen


def _rounded(tree, precision):
    if precision == "float32":
        return tree
    low = jnp.dtype(precision)
    return jax.tree_util.tree_map(lambda a: a.astype(low).astype(jnp.float32), tree)


def loss(params, seq, targets, dims, aux_coef, how=SOUND, probe=None):
    """``(loss, aux)`` of the batch ``seq`` [B, T] with ``targets`` [B, T]
    (0 = none): ``aux`` holds the two terms (``ce``, ``aux_loss``), the
    assignments ``load`` [L, E] and, for the query positions ``probe``, every
    layer's index ``scores`` and ``chosen`` keys ``[L, B, len(probe), T]``.
    The layers are a ``lax.scan`` over the stacked parameters, each keeping
    its input alone for the backward pass; the rows are a Python loop."""
    with jax.default_matmul_precision("highest"):
        params = _rounded(params, how["precision"])
        out = jnp.dtype(how["precision"])
        real = seq > 0

        @jax.checkpoint
        def every_row(x, stacked):
            rows = [layer(*stacked, x[b], real[b], dims, how, probe)
                    for b in range(seq.shape[0])]
            seen = {name: jnp.stack([s[name] for _, s in rows]) for name in rows[0][1]}
            return jnp.stack([x_b for x_b, _ in rows]), seen

        x, seen = jax.lax.scan(every_row, params["embed"][seq],
                               (params["layers"], params["indexer"]))
        h = rms_norm(x, params["final_norm"], dims["rms_eps"])
        logits = (h @ params["head"].T).astype(out)
        ce = (jax.nn.logsumexp(logits, axis=-1)
              - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
        n_targets = jnp.maximum((targets > 0).sum(), 1).astype(out)
        ce = (jnp.where(targets > 0, ce, 0).sum() / n_targets).astype(jnp.float32)
        # the batch's routing fractions, a layer at a time; a count has no gradient
        n_tokens = jnp.maximum(real.sum(), 1).astype(jnp.float32)
        load = seen["load"].sum(axis=1)                                   # [L, E]
        mean_p = seen["probs"].sum(axis=1) / n_tokens
        aux = (load.shape[1] * (jax.lax.stop_gradient(load / n_tokens) * mean_p).sum(axis=1)
               ).mean()
        found = {"ce": ce, "aux_loss": aux, "load": load}
        if probe is not None:
            found.update(scores=seen["scores"], chosen=seen["chosen"])
        return ce + aux_coef * aux, found


def loss_and_grads(params, seq, targets, dims, aux_coef, how=SOUND, probe=None):
    """``(loss, aux, grads)``: the gradient with respect to every parameter
    (the indexer's is zero: it acts through the selection alone)."""
    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(
        params, seq, targets, dims, aux_coef, how, probe)
    return value, aux, grads


def subset_of(params, head_rows) -> dict:
    """The tensors the benchmark's ``correct`` compares gradients of, out of a
    tree shaped like the parameters: layer 0's ``W_q`` and ``W_k`` (the
    grouped heads), the first and the last layer's router, a held expert's
    down-projection in the first layer (the first held) and in the last (the
    last held), the final norm and the head's rows of the sampled items."""
    layers = params["layers"]
    return {
        "wq_first": layers["wq"][0], "wk_first": layers["wk"][0],
        "router_first": layers["router"][0], "router_last": layers["router"][-1],
        "w_down_first": layers["w_down"][0, 0], "w_down_last": layers["w_down"][-1, -1],
        "final_norm": params["final_norm"],
        "head_rows": params["head"][head_rows],
    }
