"""The plain reference of the looped decoder (``model_type ouro``: the LoopLM
of "Scaling Latent Reasoning via Looped Language Models", arXiv 2510.25741,
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json) with an
item catalog as its vocabulary: forward, the exits' logits, the exit
distribution, the loss and its gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: materialised attention scores, a
Python loop over passes and layers, no scan, no rematerialisation, no kernel,
no cache, nothing imported from the program.

For one sequence ``x`` of item ids (0 = padding), ``dims`` giving
``num_heads``, ``head_dim``, ``rope_theta``, ``rms_eps``, ``ut_steps``:

- layer ``l``: ``a = h + N2_l(Attn_l(N1_l(h)))``,
  ``h' = a + N4_l(SwiGLU_l(N3_l(a)))``; ``N`` an RMSNorm with its own weight;
  ``Attn`` causal softmax attention, padded keys masked, rotary positions over
  the whole head on ``q`` and ``k`` (rotate-half); no biases;
  ``SwiGLU(z) = W_down (silu(W_gate z) * (W_up z))``;
- loop: ``h_0 = E[x]``; for ``t = 1..ut_steps``: ``u_t`` = all layers in order
  on ``h_{t-1}``, the same parameters for every ``t``; ``h_t = N_f(u_t)``;
- exits: ``logits_t = W_head h_t``; ``lam_t = sigmoid(w_g . h_t + b_g)``;
  ``S_0 = 1``, ``S_t = S_{t-1} (1 - lam_t)``; ``p_t = lam_t S_{t-1}`` for
  ``t < ut_steps``, ``p_last = S_{last-1}``;
- loss of a position with a target ``y``:
  ``sum_t p_t CE(logits_t, y) - beta H(p)``, ``H(p) = -sum_t p_t log p_t``;
  mean over the positions with a target.

Departures from the published description, and the choices it leaves open:

- the vocabulary is an item catalog: id 0 is padding, a padded key is masked,
  and a position whose next slot is padding has no target. The published model
  has token ids and no padding inside a packed sequence;
- the normed state ``N_f(u_t)``, not ``u_t``, is what the next pass takes in,
  and the exit gate reads that normed state: both as the published
  ``modeling_ouro.py`` does (its loop norms, appends the state for the head,
  reads the gate, and goes round again), which the config alone does not say;
- ``beta`` is the paper's first training stage's entropy weight (0.1 in the
  configuration here); with the uniform prior over exits its KL term is this
  entropy up to a constant. The second stage's gate-only objective is not
  implemented;
- parameters are stacked ``[L, ...]`` arrays (the layout they are handed over
  in); the loop reads layer ``l`` as ``array[l]``.

``precision="bfloat16"`` is the control of the benchmark's ``correct``, the
step below what the configuration states: every parameter rounded to bfloat16
(bfloat16 master weights), and each exit's logits, cross-entropy, the exit
mixture and the mean held in bfloat16. ``loss_and_subset_grads(...,
shared=False)`` is its second control, a loop put together wrongly: every
pass after the first reads a copy of the layers' weights, so the forward pass
is the right one and a layer tensor's gradient is the first pass's term alone
and not the sum over the passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG = -1e30


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """Rotary positions on ``x`` [B, T, H, hd], positions 0..T-1, the
    rotate-half convention over the whole head."""
    t, hd = x.shape[1], x.shape[3]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def attention(p, z, pad_mask, dims):
    b, t, _ = z.shape
    heads = lambda a: a.reshape(b, t, dims["num_heads"], dims["head_dim"])  # noqa: E731
    q = rope(heads(z @ p["wq"]), dims["rope_theta"])
    k = rope(heads(z @ p["wk"]), dims["rope_theta"])
    v = heads(z @ p["wv"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(dims["head_dim"]))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None] & pad_mask[:, None, None, :], scores, _NEG)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, -1) @ p["wo"]


def layer(p, h, pad_mask, dims):
    """One decoder layer with its own parameters ``p``."""
    eps = dims["rms_eps"]
    a = h + rms_norm(attention(p, rms_norm(h, p["n1"], eps), pad_mask, dims),
                     p["n2"], eps)
    z = rms_norm(a, p["n3"], eps)
    mlp = (jax.nn.silu(z @ p["w_gate"]) * (z @ p["w_up"])) @ p["w_down"]
    return a + rms_norm(mlp, p["n4"], eps)


def layer_params(params, l: int, t: int = 0) -> dict:
    """Layer ``l``'s parameters, the same for every pass ``t``."""
    return {name: array[l] for name, array in params["layers"].items()}


def states(params, seq, dims, layers_of=layer_params):
    """``[h_1, .., h_last]``: the normed state after every pass."""
    pad_mask = seq > 0
    n_layers = params["layers"]["wq"].shape[0]
    h = params["embed"][seq]
    out = []
    for t in range(dims["ut_steps"]):
        for l in range(n_layers):
            h = layer(layers_of(params, l, t), h, pad_mask, dims)
        h = rms_norm(h, params["final_norm"], dims["rms_eps"])
        out.append(h)
    return out


def exit_distribution(lams):
    """``[p_1, .., p_last]`` from ``[lam_1, .., lam_last]``."""
    p, survive = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        p.append(lam * survive)
        survive = survive * (1.0 - lam)
    return p + [survive]


def _rounded(tree, precision):
    if precision == "float32":
        return tree
    low = jnp.dtype(precision)
    return jax.tree_util.tree_map(lambda a: a.astype(low).astype(jnp.float32), tree)


def forward(params, seq, dims, precision: str = "float32", layers_of=layer_params):
    """``logits`` [steps, B, T, V] and the exit distribution ``p``
    [steps, B, T] of every position."""
    with jax.default_matmul_precision("highest"):
        params = _rounded(params, precision)
        out = jnp.dtype(precision)
        hs = states(params, seq, dims, layers_of)
        logits = [(h @ params["head"].T).astype(out) for h in hs]
        lams = [jax.nn.sigmoid(h @ params["gate_w"] + params["gate_b"]) for h in hs]
        return jnp.stack(logits), jnp.stack(exit_distribution(lams))


def loss(params, seq, targets, dims, beta, denominator=None,
         precision: str = "float32", layers_of=layer_params):
    """``(loss, {"exit_ce": [steps], "p": [steps, B, T]})``. ``targets`` [B, T],
    0 = no target. The sums over positions are divided by ``denominator``
    (default: this block's count of targets), so that blocks of rows of one
    batch, each given the batch's count, add up to the batch's loss and
    gradients."""
    logits, p = forward(params, seq, dims, precision, layers_of)
    with jax.default_matmul_precision("highest"):
        out = logits.dtype
        mask = targets > 0
        if denominator is None:
            denominator = jnp.maximum(mask.sum(), 1)
        ce = (jax.nn.logsumexp(logits, axis=-1)
              - jnp.take_along_axis(logits, targets[None, :, :, None], axis=-1)[..., 0])
        p_low = p.astype(out)
        entropy = -(p_low * jnp.log(jnp.maximum(p_low, 1e-30))).sum(axis=0)
        per_position = (p_low * ce).sum(axis=0) - jnp.asarray(beta, out) * entropy
        mean = lambda a: (jnp.where(mask, a, 0).sum(axis=(-2, -1))  # noqa: E731
                          / jnp.asarray(denominator, out)).astype(jnp.float32)
        return mean(per_position), {"exit_ce": mean(ce), "p": p}


# ---- gradients -----------------------------------------------------------

def loss_and_grads(params, seq, targets, dims, beta, denominator=None,
                   precision: str = "float32"):
    """``(loss, aux, grads)``: the gradient with respect to every parameter."""
    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(
        params, seq, targets, dims, beta, denominator, precision)
    return value, aux, grads


def subset_of(params, head_rows) -> dict:
    """The tensors the benchmark's ``correct`` takes gradients of: the gate,
    the final norm, layer 0's ``W_q``, the last layer's ``W_down`` and the
    head's rows of the sampled items."""
    return {
        "gate_w": params["gate_w"], "gate_b": params["gate_b"],
        "final_norm": params["final_norm"],
        "wq_first": params["layers"]["wq"][0],
        "w_down_last": params["layers"]["w_down"][-1],
        "head_rows": params["head"][head_rows],
    }


def loss_and_subset_grads(params, head_rows, seq, targets, dims, beta,
                          denominator=None, precision: str = "float32",
                          shared: bool = True):
    """``(loss, aux, grads)``, the gradient taken with respect to the tensors
    of ``subset_of`` only. The layer tensors' gradients are sums over the
    passes: the same tensor is read in every pass. ``shared=False`` is the
    wrong loop of the benchmark's second control (the module's docstring)."""
    last = params["layers"]["wq"].shape[0] - 1

    def of_subset(subset):
        subset = _rounded(subset, precision)
        whole = {**params, "gate_w": subset["gate_w"], "gate_b": subset["gate_b"],
                 "final_norm": subset["final_norm"],
                 "head": params["head"].at[head_rows].set(subset["head_rows"])}

        def layers_of(tree, l, t):
            p = layer_params(tree, l)
            read = (lambda a: a) if shared or t == 0 else jax.lax.stop_gradient
            if l == 0:
                p["wq"] = read(subset["wq_first"])
            if l == last:
                p["w_down"] = read(subset["w_down_last"])
            return p

        return loss(whole, seq, targets, dims, beta, denominator, precision,
                    layers_of)

    (value, aux), grads = jax.value_and_grad(of_subset, has_aux=True)(
        subset_of(params, head_rows))
    return value, aux, grads
