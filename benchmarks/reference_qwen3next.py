"""The plain reference of the hybrid decoder (``model_type qwen3_next``,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json:
three gated-delta-rule linear-attention layers to one gated full-attention
layer, 512 routed experts and 10 a token, one shared expert behind a sigmoid
gate) with an item catalog as its vocabulary: forward, loss and gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence **token by token**
(``lax.scan`` over the positions), dense causal scores, every held expert over
every token, no kernel, no chunked form, nothing imported from the program
(the norm, the rotation, the routing and the experts' sum are
``reference_keye.py``'s, which is as plain). So that two rows of 8,192
positions fit a chip and compile in seconds the loops are ``lax.scan`` /
``lax.map`` (a loop's body is the equations below), a layer keeps its input
alone for the backward pass and a row of it, an expert of it, a block of
``query_block`` positions of the recurrence (its first state) or of
attention's queries their inputs alone (``jax.checkpoint``): the same numbers,
recomputed, so that the reference reserves less of the chip than the step it
judges.

For one row ``x`` ``[T, D]`` of the residual stream, ``n(.)`` the zero-centred
RMSNorm ``x / rms(x) (1 + w)`` and ``dims`` giving ``linear_key_heads``,
``linear_value_heads``, ``linear_key_dim``, ``linear_value_dim``,
``conv_kernel``, ``num_heads``, ``num_kv_heads``, ``head_dim``, ``rotary_dim``,
``experts_per_token``, ``experts_held`` ``(lo, hi)``, ``rope_theta``,
``rms_eps``:

- **linear layer**: ``h = n1(x)``; ``[q, k, v, z] = h W_qkvz`` (widths ``HK dk``,
  ``HK dk``, ``HV dv``, ``HV dv``), ``[b, a] = h W_ba`` (``HV`` each);
  ``[q, k, v] <- silu(conv([q, k, v]))``, ``conv`` depthwise and causal over the
  last ``conv_kernel`` positions, no bias, a padded slot's input zero;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` a value head;
  ``q <- q / |q| / sqrt(dk)``, ``k <- k / |k|`` (eps 1e-6 under the root). A key
  head ``j`` serves the value heads ``j HV / HK ..``. ``S_0 = 0 [dk, dv]`` a
  value head; for ``t = 1..T``: ``S <- exp(g_t) S``; ``u = S^T k_t``;
  ``S <- S + k_t (beta_t (v_t - u))^T``; ``o_t = S^T q_t``. A padded slot has
  ``beta = 0``, ``g = 0``. ``y = o / rms(o) w_n silu(z)`` (over ``dv``, a plain
  weight); ``x <- x + concat_heads(y) W_out``;
- **full layer**: ``[q, gate] = h W_q`` (``H`` heads of ``hd + hd``), ``k = h W_k``,
  ``v = h W_v`` (``KV`` heads of ``hd``); ``q <- n_q(q)``, ``k <- n_k(k)`` over the
  head; rotary positions on the first ``rotary_dim`` of a head's dimensions,
  the rest pass; causal ``softmax(q k^T / sqrt(hd)) v``, head ``g`` reading
  key-value head ``g // (H / KV)``; ``x <- x + (attn sigmoid(gate)) W_o``;
- **experts, every layer**: ``u = n2(x)``; ``p = softmax(u W_r)`` over all the
  experts; the ``experts_per_token`` largest, gates renormalised over them;
  ``x <- x + sum_{e held} g_e W2_e(silu(W1_e u) W3_e u) + sigmoid(u . w_sg)
  W2_s(silu(W1_s u) W3_s u)``;
- ``logits = W_head n_f(x)``; the loss is the mean cross-entropy over the
  positions with a target plus ``aux_coef`` times the mean over the layers of
  ``E sum_e f_e P_e`` (``reference_keye.py`` has the terms).

The layers are ``params["periods"]``: ``linear`` ``[P, I - 1, ...]`` and ``full``
``[P, ...]`` for ``P`` periods of ``I`` layers, the last of a period the full
one; each holds its layers' mixer and experts.

The controls of the benchmark's ``correct`` (``how``): ``precision`` "bfloat16"
(every parameter rounded to bfloat16, the state, logits and loss held in
bfloat16); ``decay`` False (``g = 0``); ``delta`` False (``u = 0``: gated linear
attention without the correction); ``shared_gate`` False (the shared expert
ungated).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference_keye import _rounded, rms_norm, rope, routing

_NEG = -1e30
SOUND = {"precision": "float32", "decay": True, "delta": True, "shared_gate": True,
         "renormalise": True}


def norm0(x, weight, eps):
    return rms_norm(x, 1.0 + weight, eps)


def causal_conv(x, weight):
    """``y[t, c] = sum_i weight[c, i] x[t - (K - 1) + i, c]`` with ``x`` zero
    before the row: ``x`` [T, C], ``weight`` [C, K]."""
    t, width = x.shape[0], weight.shape[1]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(padded[i:i + t] * weight[:, i] for i in range(width))


def l2_normalised(x):
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, how, block: int):
    """``o`` [T, HV, dv] of the gated delta rule, token by token, for ``q``,
    ``k`` [T, HV, dk], ``v`` [T, HV, dv], ``g``, ``beta`` [T, HV]."""
    held = jnp.dtype(how["precision"])

    def token(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t) if how["delta"] else 0.0
        state = state + jnp.einsum("hk,hv->hkv", k_t, beta_t[:, None] * (v_t - seen))
        state = state.astype(held).astype(jnp.float32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    t = q.shape[0]
    pad = -t % block
    blocks = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        -1, block, *a.shape[1:]) for a in (q, k, v, g, beta))
    start = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    out = jax.lax.scan(jax.checkpoint(lambda s, b: jax.lax.scan(token, s, b)), start, blocks)[1]
    return out.reshape(-1, *out.shape[2:])[:t]


def linear_attention(p, h, real, dims, how):
    """The linear mixer's output ``[T, D]`` of one row's normed input ``h``."""
    t = h.shape[0]
    hk, hv = dims["linear_key_heads"], dims["linear_value_heads"]
    dk, dv = dims["linear_key_dim"], dims["linear_value_dim"]
    q, k, v, z = jnp.split(h @ p["w_qkvz"], [hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv], axis=-1)
    b, a = jnp.split(h @ p["w_ba"], 2, axis=-1)
    mixed = jnp.where(real[:, None], jnp.concatenate([q, k, v], axis=-1), 0.0)
    mixed = jax.nn.silu(causal_conv(mixed, p["conv"]))
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    beta = jnp.where(real[:, None], jax.nn.sigmoid(b), 0.0)
    g = jnp.where(real[:, None], -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"]), 0.0)
    if not how["decay"]:
        g = jnp.zeros_like(g)
    q = l2_normalised(q.reshape(t, hk, dk)) / jnp.sqrt(jnp.float32(dk))
    k = l2_normalised(k.reshape(t, hk, dk))
    q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
    o = recurrence(q, k, v.reshape(t, hv, dv), g, beta, how, min(dims.get("query_block", 512), t))
    y = rms_norm(o, p["norm"], dims["rms_eps"]) * jax.nn.silu(z.reshape(t, hv, dv))
    return y.reshape(t, hv * dv) @ p["w_out"]


def full_attention(p, h, dims):
    """The full mixer's output ``[T, D]`` of one row's normed input ``h``."""
    t = h.shape[0]
    heads, kv, hd = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    rd, eps = dims["rotary_dim"], dims["rms_eps"]
    q, gate = jnp.split((h @ p["wq"]).reshape(t, heads, 2 * hd), 2, axis=-1)
    q = norm0(q, p["q_norm"], eps)
    k = norm0((h @ p["wk"]).reshape(t, kv, hd), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(t, kv, hd)
    turn = lambda x: jnp.concatenate(  # noqa: E731
        [rope(x[..., :rd], dims["rope_theta"]), x[..., rd:]], axis=-1)
    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    block = min(dims.get("query_block", 512), t)

    @jax.checkpoint
    def queries(q_block, positions):
        s = jnp.einsum("qhd,shd->hqs", q_block, k) / jnp.sqrt(jnp.float32(hd))
        causal = jnp.arange(t)[None, :] <= positions[:, None]
        weights = jax.nn.softmax(jnp.where(causal[None], s, _NEG), axis=-1)
        return jnp.einsum("hqs,shd->qhd", weights, v)

    out = jax.lax.map(lambda args: queries(*args),
                      (q.reshape(-1, block, heads, hd), jnp.arange(t).reshape(-1, block)))
    out = out.reshape(t, heads, hd) * jax.nn.sigmoid(gate)
    return out.reshape(t, heads * hd) @ p["wo"]


def experts_part(p, u, experts, gates, real, dims):
    """The held experts' part of the routed sum, an expert at a time over every
    token, weighted by the token's gate for it (0 where it did not choose it):
    ``reference_keye.experts_part``, an expert keeping its inputs alone."""
    lo, hi = dims["experts_held"]

    @jax.checkpoint
    def one(args):
        e, w_gate, w_up, w_down = args
        gate = jnp.where((experts == e) & real[:, None], gates, 0.0).sum(axis=-1)
        return gate[:, None] * ((jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down)

    return jax.lax.map(one, (jnp.arange(lo, hi), p["w_gate"], p["w_up"], p["w_down"])).sum(axis=0)


def experts_block(p, x, real, dims, how):
    """``(x', seen)``: the routed experts held here and the shared expert on
    one row; ``seen`` holds the sums over the row's real tokens of the router's
    probabilities ``[E]`` and of the assignments to every expert ``[E]``."""
    u = norm0(x, p["n2"], dims["rms_eps"])
    probs, experts, gates = routing(p, u, dims, how)
    picked = (experts[..., None] == jnp.arange(probs.shape[-1])).any(axis=1)
    seen = {"probs": jnp.where(real[:, None], probs, 0.0).sum(axis=0),
            "load": jnp.where(real[:, None], picked, False).sum(axis=0)}
    shared = (jax.nn.silu(u @ p["s_gate"]) * (u @ p["s_up"])) @ p["s_down"]
    if how["shared_gate"]:
        shared = jax.nn.sigmoid(u @ p["s_g"])[:, None] * shared
    return x + experts_part(p, u, experts, gates, real, dims) + shared, seen


def loss(params, seq, targets, dims, aux_coef, how=SOUND):
    """``(loss, aux)`` of the batch ``seq`` [B, T] with ``targets`` [B, T]
    (0 = none): ``aux`` holds the two terms (``ce``, ``aux_loss``) and the
    assignments ``load`` [layers, E]. Periods and the linear layers inside one
    are ``lax.scan``s over the stacked parameters, each layer keeping its input
    alone for the backward pass; the rows are a Python loop."""
    with jax.default_matmul_precision("highest"):
        params = _rounded(params, how["precision"])
        out = jnp.dtype(how["precision"])
        real = seq > 0
        eps = dims["rms_eps"]
        rows = range(seq.shape[0])

        def both(mixer):
            @jax.checkpoint
            def row(p, x_b, real_b):
                return experts_block(p, x_b + mixer(p, norm0(x_b, p["n1"], eps), real_b),
                                     real_b, dims, how)

            @jax.checkpoint
            def layer(x, p):
                done = [row(p, x[b], real[b]) for b in rows]
                return (jnp.stack([x_b for x_b, _ in done]),
                        {name: jnp.stack([s[name] for _, s in done]) for name in done[0][1]})
            return layer

        linear = both(lambda p, h, r: linear_attention(p, h, r, dims, how))
        full = both(lambda p, h, r: full_attention(p, h, dims))

        def period(x, stacked):
            x, seen = jax.lax.scan(linear, x, stacked["linear"])
            x, seen_full = full(x, stacked["full"])
            return x, jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b[None]]), seen, seen_full)

        x, seen = jax.lax.scan(period, params["embed"][seq], params["periods"])
        seen = {name: a.reshape(-1, *a.shape[2:]) for name, a in seen.items()}   # [layers, B, E]
        h = norm0(x, params["final_norm"], eps)
        logits = (h @ params["head"].T).astype(out)
        ce = (jax.nn.logsumexp(logits, axis=-1)
              - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
        n_targets = jnp.maximum((targets > 0).sum(), 1).astype(out)
        ce = (jnp.where(targets > 0, ce, 0).sum() / n_targets).astype(jnp.float32)
        n_tokens = jnp.maximum(real.sum(), 1).astype(jnp.float32)
        load = seen["load"].sum(axis=1)
        mean_p = seen["probs"].sum(axis=1) / n_tokens
        aux = (load.shape[1] * (jax.lax.stop_gradient(load / n_tokens) * mean_p).sum(axis=1)
               ).mean()
        return ce + aux_coef * aux, {"ce": ce, "aux_loss": aux, "load": load}


def loss_and_grads(params, seq, targets, dims, aux_coef, how=SOUND):
    """``(loss, aux, grads)``: the gradient with respect to every parameter."""
    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(
        params, seq, targets, dims, aux_coef, how)
    return value, aux, grads


def subset_of(params, head_rows) -> dict:
    """The tensors the benchmark's ``correct`` compares gradients of, out of a
    tree shaped like the parameters: the first linear layer's ``W_qkvz``, conv
    weights, ``A_log`` and ``dt_bias``; the first full layer's ``W_q`` (queries
    and output gates); the first and the last layer's router; the held experts'
    down-projections in the first layer, all of them together (a token whose
    tenth and eleventh logits lie within the program's rounding of each other
    goes to another expert in the program than here: one such token is some 4%
    of one expert's gradient and under 1% of the layer's); the shared expert's
    gate in the last layer; the final norm and the head's rows of the sampled
    items."""
    linear, full = params["periods"]["linear"], params["periods"]["full"]
    return {
        "w_qkvz_first": linear["w_qkvz"][0, 0], "conv_first": linear["conv"][0, 0],
        "a_log_first": linear["a_log"][0, 0], "dt_bias_first": linear["dt_bias"][0, 0],
        "wq_full": full["wq"][0],
        "router_first": linear["router"][0, 0], "router_last": full["router"][-1],
        "w_down_first": linear["w_down"][0, 0], "shared_gate_last": full["s_g"][-1],
        "final_norm": params["final_norm"], "head_rows": params["head"][head_rows],
    }
