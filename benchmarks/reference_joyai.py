"""The plain reference of the latent-attention decoder (``model_type
joyai_llm_flash``, https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json:
multi-head latent attention with a rotary key all heads share, one leading
dense layer, 256 experts of which a token takes the 8 its sigmoid scores plus
a bias pick, one ungated shared expert, one multi-token-prediction module)
with an item catalog as its vocabulary: forward, loss (three terms),
gradients, and the bias's move after a step.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense causal scores head by head
with the shared rotary key broadcast to every head, every held expert over
every token (``reference_qwen3next.experts_part``), no kernel, no chunked
form, no cache of latents, nothing imported from the program. So that two rows
of 8,192 positions fit a chip, the layers are ``lax.scan``s, a layer keeps its
input alone for the backward pass and a row of it, an expert of it, a block of
``query_block`` queries their inputs alone (``jax.checkpoint``): the same
numbers, recomputed.

For one row ``x`` ``[T, D]`` of the residual stream, ``n(.)`` RMSNorm with a
plain weight and ``dims`` giving ``num_heads``, ``kv_rank``, ``nope_dim``,
``rope_dim``, ``value_dim``, ``experts_per_token``, ``experts_held``
``(lo, hi)``, ``routed_scale``, ``mtp_coef``, ``balance_coef``, ``bias_rate``,
``rope_theta``, ``rms_eps``:

- **latent attention**, ``h = n1(x)``: ``c_q = n_q(h W_qa)``; ``q = c_q W_qb``,
  ``H`` heads of ``[q_nope | q_rope]``; ``[c | k_r] = h W_kva``;
  ``n_kv(c) W_kvb``, ``H`` heads of ``[k_nope | v]``. Rotary positions on
  ``q_rope`` of every head and on ``k_r``: pair ``(2i, 2i + 1)`` of position
  ``t`` turns by ``t theta^(-2i / rope_dim)`` (``rope_interleave``; no
  scaling). Head ``a``: causal ``softmax((q_nope_a . k_nope_a + q_rope_a .
  k_r) / sqrt(nope_dim + rope_dim)) v_a``; ``x <- x + concat_a(o_a) W_o``;
- **a dense layer's MLP** (``params["dense"]``, stacked):
  ``x <- x + W_down(silu(W_gate u) * (W_up u))``, ``u = n2(x)``;
- **an expert layer's** (``params["layers"]``, stacked; ``params["mtp"]["layer"]``):
  ``s = sigmoid(u W_r)``; ``E_t`` the ``experts_per_token`` largest of
  ``s + b`` (``router_bias``; one group, so no group limit); ``g = s[E_t]``,
  ``g <- routed_scale g / (sum g + 1e-20)``; ``x <- x + sum_{e in E_t, e held}
  g_e FFN_e(u) + FFN_shared(u)``; a row's balance term is ``sum_e f_e P_e``
  with ``f_e = E / (K T_r)`` times the row's real positions that chose ``e``
  (no gradient) and ``P_e`` the row's mean of ``s_e / sum_j s_j``;
- **the prediction module**: ``m_i = [n_e(Emb(target_i)) | n_h(z_i)] W_m`` with
  ``z`` the stack's output before its final norm; one expert layer on ``m``
  (a position without a ``target_i`` is a padded slot); its own final norm,
  the stack's head; cross-entropy against ``target_{i+1}`` where that is an
  event;
- ``loss = ce + mtp_coef mtp_ce + balance_coef balance``, ``balance`` the mean
  of the rows' terms over rows and over every layer with a router (the
  module's is the last);
- **the bias after a step** (``bias_after``): ``b_e + bias_rate
  sign(mean(load) - load_e)`` from that step's assignments ``load`` of real
  tokens to all the experts, a layer at a time.

Departures from the source's own code, each also under the configuration
file's ``assumed``: which state the module reads (before the final norm) and
the order of the merge's halves; ``bias_rate``, ``balance_coef`` and
``mtp_coef`` (the family's recipe; the config gives none); the balance term's
mean over rows.

The controls of the benchmark's ``correct`` (``how``): ``precision``
"bfloat16" (every parameter rounded to bfloat16, logits and losses held in
bfloat16); ``rope_key`` False (the ``q_rope . k_r`` term dropped); ``router``
"softmax" (softmax scores in place of sigmoid ones); ``bias`` False (selection
by ``s`` alone); ``scaled`` False (``routed_scale`` 1); ``mtp`` False
(``mtp_coef`` 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference_keye import _rounded, rms_norm
from benchmarks.reference_qwen3next import experts_part

_NEG = -1e30
SOUND = {"precision": "float32", "rope_key": True, "router": "sigmoid", "bias": True,
         "scaled": True, "mtp": True}


def rope_interleaved(x, theta):
    """Rotary positions on ``x`` [T, H, dim], positions 0..T-1: the pair
    ``(x[2i], x[2i + 1])`` turns by ``t theta^(-2i / dim)``."""
    t, heads, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :])[:, None, :]
    pairs = x.reshape(t, heads, dim // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                        a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)
    return turned.reshape(t, heads, dim)


def swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def latent_attention(p, h, dims, how):
    """The attention's output ``[T, D]`` of one row's normed input ``h``."""
    t = h.shape[0]
    heads, rank = dims["num_heads"], dims["kv_rank"]
    dn, dr, dv = dims["nope_dim"], dims["rope_dim"], dims["value_dim"]
    eps, theta = dims["rms_eps"], dims["rope_theta"]
    q = (rms_norm(h @ p["w_qa"], p["q_norm"], eps) @ p["w_qb"]).reshape(t, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_interleaved(q[..., dn:], theta)
    latent = h @ p["w_kva"]
    k_rope = rope_interleaved(latent[:, None, rank:], theta)[:, 0]      # [T, dr]: every head's
    kv = (rms_norm(latent[:, :rank], p["kv_norm"], eps) @ p["w_kvb"]).reshape(t, heads, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    block = min(dims.get("query_block", 512), t)

    @jax.checkpoint
    def queries(nope_block, rope_block, positions):
        s = jnp.einsum("qhd,shd->hqs", nope_block, k_nope)
        if how["rope_key"]:
            s = s + jnp.einsum("qhd,sd->hqs", rope_block, k_rope)
        causal = jnp.arange(t)[None, :] <= positions[:, None]
        weights = jax.nn.softmax(
            jnp.where(causal[None], s / jnp.sqrt(jnp.float32(dn + dr)), _NEG), axis=-1)
        return jnp.einsum("hqs,shd->qhd", weights, v)

    out = jax.lax.map(lambda args: queries(*args), (
        q_nope.reshape(-1, block, heads, dn), q_rope.reshape(-1, block, heads, dr),
        jnp.arange(t).reshape(-1, block)))
    return out.reshape(t, heads * dv) @ p["wo"]


def routing(p, u, dims, how):
    """``(scores [T, E], experts [T, K], gates [T, K])``: the selection reads
    the scores plus the bias, the gates the scores alone."""
    logits = u @ p["router"]
    scores = jax.nn.sigmoid(logits) if how["router"] == "sigmoid" else jax.nn.softmax(logits)
    biased = scores + p["router_bias"] if how["bias"] else scores
    experts = jax.lax.top_k(biased, dims["experts_per_token"])[1]
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return scores, experts, gates * (dims["routed_scale"] if how["scaled"] else 1.0)


def experts_mlp(p, x, real, dims, how):
    """``(x', seen)``: the routed experts held here and the shared expert on
    one row; ``seen`` holds the row's assignments to every expert ``load``
    [E], its balance term, and how many of its assignments the bias decided
    (``decided``: those that selection by the scores alone would not make)."""
    u = rms_norm(x, p["n2"], dims["rms_eps"])
    scores, experts, gates = routing(p, u, dims, how)
    total, slots = scores.shape[-1], dims["experts_per_token"]
    picked = (experts[..., None] == jnp.arange(total)).any(axis=1)
    load = jnp.where(real[:, None], picked, False).sum(axis=0)
    count = jnp.maximum(real.sum(), 1).astype(jnp.float32)
    often = jax.lax.stop_gradient(load.astype(jnp.float32)) * (total / slots) / count
    share = scores / scores.sum(axis=-1, keepdims=True)
    mean_share = jnp.where(real[:, None], share, 0.0).sum(axis=0) / count
    unbiased = jax.lax.top_k(scores, slots)[1]
    same = (experts[:, :, None] == unbiased[:, None, :]).any(axis=-1)
    seen = {"load": load, "balance": (often * mean_share).sum(),
            "decided": jnp.where(real[:, None], ~same, False).sum()}
    shared = swiglu(u, p["s_gate"], p["s_up"], p["s_down"])
    return x + experts_part(p, u, experts, gates, real, dims) + shared, seen


def head_ce(h, head, targets, out):
    """The mean cross-entropy of ``h`` [B, T, D] over the positions with a
    target; logits and the mean held in ``out``."""
    logits = (h @ head.T).astype(out)
    ce = (jax.nn.logsumexp(logits, axis=-1)
          - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    count = jnp.maximum((targets > 0).sum(), 1).astype(out)
    return (jnp.where(targets > 0, ce, 0).sum() / count).astype(jnp.float32)


def loss(params, seq, targets, dims, how=SOUND):
    """``(loss, aux)`` of the batch ``seq`` [B, T] with ``targets`` [B, T]
    (0 = none): ``aux`` holds the three terms (``ce``, ``mtp_ce``,
    ``balance``), the assignments ``load`` [routers, E] (the module's last) and
    ``decided`` [routers]. The rows are a Python loop."""
    with jax.default_matmul_precision("highest"):
        params = _rounded(params, how["precision"])
        out = jnp.dtype(how["precision"])
        eps = dims["rms_eps"]
        rows = range(seq.shape[0])

        def layers_of(mlp, real):
            @jax.checkpoint
            def row(p, x_b, real_b):
                x_b = x_b + latent_attention(p, rms_norm(x_b, p["n1"], eps), dims, how)
                return mlp(p, x_b, real_b)

            @jax.checkpoint
            def layer(x, p):
                done = [row(p, x[b], real[b]) for b in rows]
                return (jnp.stack([x_b for x_b, _ in done]),
                        {name: jnp.stack([s[name] for _, s in done]) for name in done[0][1]})
            return layer

        def dense_mlp(p, x, real):
            return x + swiglu(rms_norm(x, p["n2"], eps), p["w_gate"], p["w_up"], p["w_down"]), {}

        def expert_mlp(p, x, real):
            return experts_mlp(p, x, real, dims, how)

        x = params["embed"][seq]
        x, _ = jax.lax.scan(layers_of(dense_mlp, seq > 0), x, params["dense"])
        x, seen = jax.lax.scan(layers_of(expert_mlp, seq > 0), x, params["layers"])
        ce = head_ce(rms_norm(x, params["final_norm"], eps), params["head"], targets, out)

        module = params["mtp"]
        merged = jnp.concatenate([rms_norm(params["embed"][targets], module["embed_norm"], eps),
                                  rms_norm(x, module["hidden_norm"], eps)], axis=-1)
        ahead_x, ahead = layers_of(expert_mlp, targets > 0)(merged @ module["merge"],
                                                           module["layer"])
        two_on = jnp.pad(targets[:, 1:], ((0, 0), (0, 1)))
        mtp_ce = head_ce(rms_norm(ahead_x, module["final_norm"], eps), params["head"], two_on, out)

        seen = {name: jnp.concatenate([a, ahead[name][None]]) for name, a in seen.items()}
        balance = seen["balance"].mean()                       # [routers, B] -> over both
        value = ce + (dims["mtp_coef"] if how["mtp"] else 0.0) * mtp_ce \
            + dims["balance_coef"] * balance
        return value, {"ce": ce, "mtp_ce": mtp_ce, "balance": balance,
                       "load": seen["load"].sum(axis=1), "decided": seen["decided"].sum(axis=1)}


def loss_and_grads(params, seq, targets, dims, how=SOUND):
    """``(loss, aux, grads)``: the gradient with respect to every parameter (a
    router's bias acts through the selection alone: its gradient is zero)."""
    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(params, seq, targets, dims, how)
    return value, aux, grads


def bias_after(params, load, rate):
    """Every router's bias after the step that counted ``load`` [routers, E]
    (the module's last): ``[routers, E]``."""
    before = jnp.concatenate([params["layers"]["router_bias"],
                              params["mtp"]["layer"]["router_bias"][None]])
    load = load.astype(jnp.float32)
    return before + rate * jnp.sign(load.mean(axis=-1, keepdims=True) - load)


def subset_of(params, head_rows) -> dict:
    """The tensors the benchmark's ``correct`` compares gradients of, out of a
    tree shaped like the parameters; between them they see every new piece:
    the dense layer's ``W_qa`` and ``W_kvb`` and its MLP's down-projection; the
    last expert layer's ``W_qb`` and ``W_kva``, and of the latter the rotary
    key's columns on their own (``w_kr_last``: the only way to ``k_r``; at the
    rehearsal's widths a fiftieth of ``W_kva``'s gradient by norm, which the
    whole matrix does not show);
    the first and the last expert layer's router; the held experts'
    down-projections in the first expert layer, all of them together; the
    shared expert's in the last; the module's merge and its router; the final
    norm and the head's rows of the sampled items."""
    dense, layers, module = params["dense"], params["layers"], params["mtp"]
    rank = layers["kv_norm"].shape[-1]
    return {
        "w_qa_first": dense["w_qa"][0], "w_kvb_first": dense["w_kvb"][0],
        "w_qb_last": layers["w_qb"][-1], "w_kva_last": layers["w_kva"][-1],
        "w_kr_last": layers["w_kva"][-1][:, rank:],
        "dense_down": dense["w_down"][0],
        "router_first": layers["router"][0], "router_last": layers["router"][-1],
        "w_down_first": layers["w_down"][0], "shared_down_last": layers["s_down"][-1],
        "mtp_merge": module["merge"], "mtp_router": module["layer"]["router"],
        "final_norm": params["final_norm"], "head_rows": params["head"][head_rows],
    }
