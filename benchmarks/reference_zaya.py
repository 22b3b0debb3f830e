"""The plain reference of the compressed-convolution decoder (``model_type
zaya``, https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json:
Compressed Convolutional Attention, arXiv 2510.04476, 8 query heads on 2
key-value heads of 128 inside a hidden size of 2,048, two causal convolutions
of 2 taps, the mean of queries and keys added back, the value of this position
and the one before, l2-normed queries and keys with a learned temperature;
the router of arXiv 2511.17127, an MLP of width 256 that carries its state from
layer to layer and takes one of 16 experts or none; half-layers merged through
learned scales; the table tied to the head) with an item catalog as its
vocabulary: forward, loss and gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense scores head by head against
every key of the row with a mask made from positions, the convolutions as
shifted sums, every held expert over every token
(``reference_qwen3next.experts_part``), no kernel, nothing imported from the
program. So that two rows of 16,384 positions fit a chip, a layer
keeps its input alone for the backward pass and, of it, a row, an expert, a
block of ``query_block`` queries and a block of ``head_block`` positions of the
head their inputs alone (``jax.checkpoint``): the same numbers, recomputed.
The layers are one ``lax.scan`` over the stacked parameters with the carry
``(x, r)``; the rows are a Python loop.

``dims`` gives ``num_heads`` ``H``, ``num_kv_heads`` ``KV``, ``head_dim``
``d``, ``conv_time0`` ``K0``, ``conv_time1`` ``K1``, ``experts_held``
``(lo, hi)``, ``rope_theta``, ``rotary_fraction``, ``bias_rate``, ``rms_eps``.
``G = H / KV``, ``Lq = H d``, ``Lk = KV d``, ``E`` experts; ``n(.; w)`` is
RMSNorm. A row starts at ``t = 0``; anything read at ``t < 0`` is zero.

**Attention half**, ``u = n(x; n1)``:

1. ``q0 = u W_q`` ``[T, Lq]``, ``k0 = u W_k`` ``[T, Lk]``; heads are
   consecutive blocks of ``d``.
2. ``v[t] = concat(u[t] W_v1, u[t-1] W_v2)`` ``[T, Lk]``, split into ``KV``
   heads of ``d``.
3. ``m_q[t, i] = (q0[t, i] + k0[t, i // G]) / 2`` a query head ``i``;
   ``m_k[t, j]`` the mean of ``m_q[t, i]`` over the heads of group ``j``.
4. ``z = concat(q0, k0)``; ``z1[t, c] = b0[c] + sum_i a[c, i] z[t - (K0 - 1) +
   i, c]``; ``z2[t, g, :] = b1[g] + sum_i z1[t - (K1 - 1) + i, g, :] B[g, i]``
   for each of the ``H + KV`` head blocks ``g``.
5. ``q1 = z2[:, :Lq] + m_q``, ``k1 = z2[:, Lq:] + m_k``.
6. ``q2 = sqrt(d) q1 / |q1|`` a head; ``k2 = tau_j sqrt(d) k1 / |k1|`` a
   key-value head.
7. Rotary positions on the first ``d x rotary_fraction`` dimensions of every
   head of ``q2`` and ``k2`` (rotate-half).
8. Causal attention, scores times ``d ** -0.5``, ``G`` query heads a key-value
   head; the heads' outputs times ``W_o`` give ``y``.
9. ``x <- (x + b_r) a_r + (y + b_y) a_y``.

**Expert half**, ``u = n(x; n2)``:

1. ``r = u W_d + b_d``; ``r <- r + gamma r_prev`` (layer 0's ``r_prev`` is
   zero); ``r`` is what the next layer receives.
2. ``s = W_3 gelu(W_2 gelu(W_1 n(r; n_r) + c_1) + c_2)`` (the exact GELU);
   ``p = softmax(s)`` over the ``E + 1`` choices.
3. The choice is ``argmax(p + beta)``, ``beta`` reached by no gradient; the
   gate is ``p`` at the choice.
4. A choice ``e < E`` gives ``gate SwiGLU_e(u)`` where ``lo <= e < hi``;
   choice ``E``, the skip, gives 0.
5. The merge of step 9, with its own four vectors.
6. After the step ``beta`` moves by ``bias_rate`` against the sign of each
   choice's load less the even load (``bias_after``).

**The model**: ``x = Emb[seq]``, the layers, logits ``n(x; final_norm) Emb'``,
the mean cross-entropy at the positions with a target.

The controls of the benchmark's ``correct`` (``how``): ``precision``
"bfloat16" (every parameter rounded to bfloat16, logits and loss held in
bfloat16); ``conv0`` False (``z1 = z``); ``conv1`` False (``z2 = z1``);
``qk_mean`` False (step 5 adds nothing); ``value_shift`` False (both halves of
the value read this position); ``qk_norm`` False (step 6 divides by nothing);
``temperature`` False (``tau`` 1); ``rope`` "whole" (the whole head turns);
``carry`` False (``r_prev`` never added); ``router`` "linear" (``s = W_3 n(r;
n_r)``); ``bias`` False (``argmax(p)``); ``skip`` False (the last choice is
never offered: the softmax is over the experts); ``residual_scale`` False
(``x + y``); ``head`` "untied" (the head a copy of the table that hands the
table no gradient).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference_keye import _rounded, rms_norm
from benchmarks.reference_qwen3next import experts_part

_NEG = -1e30
SOUND = {"precision": "float32", "conv0": True, "conv1": True, "qk_mean": True,
         "value_shift": True, "qk_norm": True, "temperature": True, "rope": "partial",
         "carry": True, "router": "mlp", "bias": True, "skip": True,
         "residual_scale": True, "head": "tied"}


def before(a, n: int):
    """``a[t - n]`` for ``a`` [T, ...]: the first ``n`` positions read zeros."""
    if n == 0:
        return a
    return jnp.concatenate([jnp.zeros_like(a[:n]), a[:-n]], axis=0)


def rotated(x, theta: float, rotary: int):
    """Rotary positions on the first ``rotary`` dimensions of ``x`` [T, H, d],
    positions 0..T-1: dimension ``i`` of them pairs with ``i + rotary / 2``."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def merged(p, half: int, x, y, how):
    """Step 9: ``(x + b_r) a_r + (y + b_y) a_y`` with the vectors of ``half``."""
    if not how["residual_scale"]:
        return x + y
    return ((x + p[f"b_r{half}"]) * p[f"a_r{half}"]
            + (y + p[f"b_y{half}"]) * p[f"a_y{half}"])


def attention(p, u, dims, how):
    """The attention's output ``y`` ``[T, D]`` of one row's normed input ``u``."""
    t = u.shape[0]
    h, kv, d = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    g, lq = h // kv, h * d
    k0_taps, k1_taps = dims["conv_time0"], dims["conv_time1"]
    q0, k0 = u @ p["wq"], u @ p["wk"]                                       # 1
    late = before(u, 1) if how["value_shift"] else u
    v = jnp.concatenate([u @ p["wv1"], late @ p["wv2"]], axis=-1).reshape(t, kv, d)   # 2
    m_q = (q0.reshape(t, kv, g, d) + k0.reshape(t, kv, 1, d)) / 2           # 3
    m_k = m_q.mean(axis=2)
    z = jnp.concatenate([q0, k0], axis=-1)                                  # 4
    z1 = z
    if how["conv0"]:
        z1 = p["conv0_b"] + sum(p["conv0_w"][:, i] * before(z, k0_taps - 1 - i)
                                for i in range(k0_taps))
    z2 = z1.reshape(t, h + kv, d)
    if how["conv1"]:
        z2 = p["conv1_b"] + sum(
            jnp.einsum("tgd,gde->tge", before(z2, k1_taps - 1 - i), p["conv1_w"][:, i])
            for i in range(k1_taps))
    z2 = z2.reshape(t, -1)
    q1, k1 = z2[:, :lq].reshape(t, h, d), z2[:, lq:].reshape(t, kv, d)      # 5
    if how["qk_mean"]:
        q1, k1 = q1 + m_q.reshape(t, h, d), k1 + m_k
    tau = p["tau"] if how["temperature"] else jnp.ones_like(p["tau"])       # 6
    if how["qk_norm"]:
        q1 = jnp.sqrt(jnp.float32(d)) * q1 / jnp.linalg.norm(q1, axis=-1, keepdims=True)
        k1 = jnp.sqrt(jnp.float32(d)) * k1 / jnp.linalg.norm(k1, axis=-1, keepdims=True)
    k1 = k1 * tau[:, None]
    rotary = d if how["rope"] == "whole" else int(d * dims["rotary_fraction"])     # 7
    q2 = rotated(q1, dims["rope_theta"], rotary).reshape(t, kv, g, d)
    k2 = rotated(k1, dims["rope_theta"], rotary)
    block = min(dims.get("query_block", 256), t)

    @jax.checkpoint
    def queries(q_block, positions):                                        # 8
        s = jnp.einsum("qkgd,skd->kgqs", q_block, k2) * d ** -0.5
        on = jnp.arange(t)[None, :] <= positions[:, None]
        weights = jax.nn.softmax(jnp.where(on[None, None], s, _NEG), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", weights, v)

    out = jax.lax.map(lambda args: queries(*args), (
        q2.reshape(-1, block, kv, g, d), jnp.arange(t).reshape(-1, block)))
    return out.reshape(t, lq) @ p["wo"]


def routing(p, u, r_prev, dims, how):
    """``(chosen [T], gate [T], p [T, E + 1], r [T, R])`` of one row's normed
    input ``u`` and the state of the layer before."""
    r = u @ p["w_d"] + p["b_d"]                                             # 1
    if how["carry"]:
        r = r + p["gamma"] * r_prev
    hidden = rms_norm(r, p["n_r"], dims["rms_eps"])                         # 2
    if how["router"] == "mlp":
        hidden = jax.nn.gelu(hidden @ p["w_1"] + p["c_1"], approximate=False)
        hidden = jax.nn.gelu(hidden @ p["w_2"] + p["c_2"], approximate=False)
    s = hidden @ p["w_3"]
    if not how["skip"]:
        s = s.at[:, -1].set(_NEG)
    probs = jax.nn.softmax(s, axis=-1)
    ranked = probs + jax.lax.stop_gradient(p["router_bias"]) if how["bias"] else probs    # 3
    if not how["skip"]:
        ranked = ranked.at[:, -1].set(_NEG)
    chosen = jnp.argmax(ranked, axis=-1)
    return chosen, jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0], probs, r


def layer_row(p, x, r_prev, real, dims, how):
    """One layer on one row: ``(x', r, seen)``; ``seen`` holds the row's
    assignments to every choice ``load`` [E + 1], the choices its bias changed
    and the sum of squares of its state over the real positions."""
    eps = dims["rms_eps"]
    x = merged(p, 1, x, attention(p, rms_norm(x, p["n1"], eps), dims, how), how)
    u = rms_norm(x, p["n2"], eps)
    chosen, gate, probs, r = routing(p, u, r_prev, dims, how)
    # step 4: the held experts' part, an expert at a time over every token; the skip
    # and the other chip's experts are no expert here and give nothing
    y = experts_part(p, u, chosen[:, None], gate[:, None], real, dims)
    picked = (chosen[:, None] == jnp.arange(probs.shape[-1])) & real[:, None]
    seen = {"load": picked.sum(axis=0),
            "decided": (real & (chosen != jnp.argmax(probs, axis=-1))).sum(),
            "state_squares": jnp.where(real[:, None], r * r, 0.0).sum()}
    return merged(p, 2, x, y, how), r, seen


def head_ce(h, head, targets, out, block: int):
    """The mean cross-entropy of ``h`` [N, D] over the positions with a
    target, a block of positions' logits at a time, held in ``out``."""
    n = h.shape[0]
    pad = -n % block

    @jax.checkpoint
    def piece(args):
        h_block, y_block = args
        logits = (h_block @ head.T).astype(out)
        ce = (jax.nn.logsumexp(logits, axis=-1)
              - jnp.take_along_axis(logits, y_block[:, None], axis=-1)[:, 0])
        return jnp.where(y_block > 0, ce, 0).sum()

    total = jax.lax.map(piece, (jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[-1]),
                                jnp.pad(targets, (0, pad)).reshape(-1, block))).sum()
    count = jnp.maximum((targets > 0).sum(), 1).astype(out)
    return (total.astype(out) / count).astype(jnp.float32)


def loss(params, seq, targets, dims, how=SOUND):
    """``(loss, aux)`` of the batch ``seq`` [B, T] with ``targets`` [B, T]
    (0 = none): ``aux`` holds ``ce`` (the loss), the assignments ``load``
    [L, E + 1], the choices the bias changed ``decided`` [L] and the routers'
    states' rms over the real positions ``carry_rms`` [L]."""
    with jax.default_matmul_precision("highest"):
        params = _rounded(params, how["precision"])
        out = jnp.dtype(how["precision"])
        real = seq > 0
        rows = range(seq.shape[0])
        x = params["embed"][seq]
        state = jnp.zeros(seq.shape + (params["layers"]["w_d"].shape[-1],), jnp.float32)

        @jax.checkpoint
        def row(p, x_b, r_b, real_b):
            return layer_row(p, x_b, r_b, real_b, dims, how)

        @jax.checkpoint
        def layer(carry, p):
            x, r = carry
            done = [row(p, x[b], r[b], real[b]) for b in rows]
            seen = {name: jnp.stack([s[name] for _, _, s in done]).sum(axis=0)
                    for name in done[0][2]}
            return (jnp.stack([d[0] for d in done]), jnp.stack([d[1] for d in done])), seen

        (x, state), seen = jax.lax.scan(layer, (x, state), params["layers"])
        head = params["embed"]
        if how["head"] == "untied":
            head = jax.lax.stop_gradient(head)
        h = rms_norm(x, params["final_norm"], dims["rms_eps"])
        ce = head_ce(h.reshape(-1, h.shape[-1]), head, targets.reshape(-1), out,
                     dims.get("head_block", 2048))
        count = jnp.maximum(real.sum(), 1).astype(jnp.float32)
        return ce, {"ce": ce, "load": seen["load"], "decided": seen["decided"],
                    "carry_rms": jnp.sqrt(seen["state_squares"] / (count * state.shape[-1]))}


def loss_and_grads(params, seq, targets, dims, how=SOUND):
    """``(loss, aux, grads)``: the gradient with respect to every parameter."""
    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(params, seq, targets, dims, how)
    return value, aux, grads


def bias_after(params, load, rate: float):
    """Every router's bias ``[L, E + 1]`` after the step that counted
    ``load``: moved by ``rate`` against the sign of each choice's load less
    the even load."""
    load = load.astype(jnp.float32)
    return params["layers"]["router_bias"] + rate * jnp.sign(
        load.mean(axis=-1, keepdims=True) - load)


def subset_of(params, head_rows, seen_rows) -> dict:
    """The tensors the benchmark's ``correct`` compares gradients of, out of a
    tree shaped like the parameters; between them they see every new piece.
    Layer 0's ``W_q``, ``W_k`` (the mean, both convolutions, the norms, the
    temperature and the rotary half) and ``W_v2`` (the value's delayed half);
    the depthwise taps of layer 0, whole; the head block's matrices of its
    first query head and of its first key head; ``tau`` of layer 0; ``W_o`` and
    the attention half's ``a_r`` of layer 0 (the merge); ``gamma`` of layer 1
    (the carry: layer 0's multiplies zero) and ``W_d`` of layer 0, whose state
    layer 1 reads too; ``W_1`` of layer 0 and ``W_3`` of the last layer (the
    MLP, the softmax over the skip's column too); ``W_down`` of the first held
    expert of layer 0 (one choice a token, or none); the final norm; the
    table's rows of ``seen_rows``, items the step's rows hold (the embedding's
    use and the head's), and of ``head_rows``, sampled items (mostly the
    head's)."""
    at = lambda name, n: params["layers"][name][n]  # noqa: E731
    heads = params["layers"]["wq"].shape[-1] // params["layers"]["conv1_w"].shape[-1]
    return {
        "wq_first": at("wq", 0), "wk_first": at("wk", 0), "wv2_first": at("wv2", 0),
        "conv0_w_first": at("conv0_w", 0), "conv1_w_query": at("conv1_w", 0)[0],
        "conv1_w_key": at("conv1_w", 0)[heads], "tau_first": at("tau", 0),
        "wo_first": at("wo", 0), "a_r1_first": at("a_r1", 0), "gamma_second": at("gamma", 1),
        "w_d_first": at("w_d", 0), "w_1_first": at("w_1", 0), "w_3_last": at("w_3", -1),
        "w_down_first": at("w_down", 0)[0], "final_norm": params["final_norm"],
        "table_rows_seen": params["embed"][seen_rows], "table_rows_head": params["embed"][head_rows],
    }
