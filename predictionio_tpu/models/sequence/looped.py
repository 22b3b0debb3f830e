"""The looped backbone of the sequence template: one stack of decoder layers
applied ``ut_steps`` times with the same weights, an exit after every pass.

The block is that of a current open decoder (the LoopLM of "Scaling Latent
Reasoning via Looped Language Models", arXiv 2510.25741, ``model_type ouro``)
with the item catalog as its vocabulary. For one sequence ``x`` of item ids
(0 = padding):

- layer ``l``, "sandwich" norms: ``a = h + N2(Attn(N1(h)))``,
  ``h' = a + N4(SwiGLU(N3(a)))``, each ``N`` an RMSNorm with its own weight;
  ``Attn`` is causal softmax attention with the padded keys masked and rotary
  positions over the whole head on ``q`` and ``k``;
  ``SwiGLU(z) = W_down (silu(W_gate z) * (W_up z))``; no biases;
- loop: ``h_0 = E[x]``; for ``t = 1..ut_steps``: ``u_t = Stack(h_{t-1})`` with
  the same parameters for every ``t``, ``h_t = N_f(u_t)``: the normed state is
  what the next pass takes in;
- exits: ``logits_t = W_head h_t`` (the head is not tied to ``E``);
  ``lam_t = sigmoid(w_g . h_t + b_g)``; with ``S_0 = 1`` and
  ``S_t = S_{t-1} (1 - lam_t)`` the exit distribution of a position is
  ``p_t = lam_t S_{t-1}`` for ``t < ut_steps`` and ``p_last = S_{last-1}``;
- loss of a position with a target ``y``:
  ``sum_t p_t CE(logits_t, y) - beta H(p)``, ``H(p) = -sum_t p_t log p_t``;
  mean over the positions with a target;
- serving stops at the first pass whose cumulative exit probability reaches
  ``early_exit_threshold``; at 1.0, as published, every pass runs and the
  scores are the last pass's.

How it is worked (``benchmarks/reference_ouro.py`` is the same mathematics
with none of this):

- layer parameters are stacked ``[L, ...]`` and a pass is one ``lax.scan``
  over them; the passes are a Python loop over the same arrays, so a layer's
  gradient is the sum over the passes;
- matmul inputs are cast to ``compute_dtype`` (bfloat16) and accumulated in
  float32; the residual stream, norms, rotary positions, softmax, gate, loss,
  master weights and Adam's moments are float32;
- ``remat``: each layer application keeps its input alone and is recomputed in
  the backward pass (``ut_steps x L`` saved ``[B, T, D]`` states);
- the head and loss of an exit are ``blocks.exit_ce``'s chunks of positions;
- attention is the template's on its mesh (``blocks.attend``): ring or Ulysses
  attention over a ``seq`` axis, else the flash kernel (which, at heads of whole
  lane tiles, turns the rotary positions itself) or the plain reference.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from predictionio_tpu.models.sequence import blocks


@dataclass(frozen=True)
class LoopedConfig(blocks.DecoderConfig):
    hidden_size: int = 64
    num_heads: int = 4
    head_dim: int = 16
    ffn_dim: int = 176
    num_layers: int = 2
    ut_steps: int = 4
    rope_theta: float = 1e6
    exit_beta: float = 0.1
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if self.seq_parallel not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel={self.seq_parallel!r}: want 'ring' or 'ulysses'"
            )
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even (rotary pairs)")
        if self.ut_steps < 1 or self.num_layers < 1:
            raise ValueError("ut_steps and num_layers must be at least 1")
        if not 0.0 < self.early_exit_threshold <= 1.0:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold} must lie in (0, 1]"
            )


CONFIG = LoopedConfig
ENGINE_PARAMS = {
    "hiddenSize": "hidden_size", "numHeads": "num_heads", "headDim": "head_dim",
    "ffnDim": "ffn_dim", "numLayers": "num_layers", "utSteps": "ut_steps",
    "ropeTheta": "rope_theta", "rmsNormEps": "rms_eps", "exitBeta": "exit_beta",
    "earlyExitThreshold": "early_exit_threshold",
}
head_chunk_of = blocks.head_chunk_of     # the benchmark's drivers ask here


def param_shapes(c: LoopedConfig) -> dict:
    """The parameter tree as shapes. The layers' arrays lead with ``[L]``."""
    d, a, f, n = c.hidden_size, c.num_heads * c.head_dim, c.ffn_dim, c.num_layers
    return {
        "embed": (c.vocab, d),
        "layers": {
            "n1": (n, d), "wq": (n, d, a), "wk": (n, d, a), "wv": (n, d, a),
            "wo": (n, a, d), "n2": (n, d),
            "n3": (n, d), "w_gate": (n, d, f), "w_up": (n, d, f),
            "w_down": (n, f, d), "n4": (n, d),
        },
        "final_norm": (d,),
        "head": (c.vocab, d),
        "gate_w": (d,),
        "gate_b": (),
    }


def init_params(c: LoopedConfig, rng) -> dict:
    """Matrices N(0, 0.02), norm weights 1, the gate's bias 0."""
    return blocks.draw_params(param_shapes(c), rng, zeros=("gate_b",),
                              ones=("n1", "n2", "n3", "n4", "final_norm"))


def count_params(c: LoopedConfig) -> int:
    return blocks.count_params(param_shapes(c))


def fit_attrs(c: LoopedConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span."""
    return {**blocks.decoder_fit_attrs(c, c.num_layers, c.ut_steps), "selection_kept_bytes": 0,
            "attention_operands": blocks.attention_operands(c, platform, c.head_dim)}


# ---- the block -----------------------------------------------------------

def _layer(c: LoopedConfig, mesh, rope, pad_mask, h, p):
    """One decoder layer on ``h`` [B, T, D] with its parameters ``p``."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    with jax.named_scope(blocks.SCOPE_ATTENTION):
        with jax.named_scope(blocks.SCOPE_NORM):
            z = blocks.rms_norm(h, p["n1"], c.rms_eps)
        with jax.named_scope(blocks.SCOPE_QKV):
            q, k, v = (blocks.matmul(z, p[w], dtype).reshape(b, t, c.num_heads, c.head_dim)
                       for w in ("wq", "wk", "wv"))
        out = blocks.attend(c, mesh, q, k, v, pad_mask, rope).reshape(b, t, -1)
        with jax.named_scope(blocks.SCOPE_OUT):
            out = blocks.matmul(out, p["wo"], dtype)
        with jax.named_scope(blocks.SCOPE_NORM):
            out = blocks.rms_norm(out, p["n2"], c.rms_eps)
        with jax.named_scope(blocks.SCOPE_OUT):
            a = h + out
    with jax.named_scope(blocks.SCOPE_MLP):
        with jax.named_scope(blocks.SCOPE_NORM):
            z = blocks.rms_norm(a, p["n3"], c.rms_eps)
        down = blocks.swiglu(z, p["w_gate"], p["w_up"], p["w_down"], dtype)
        with jax.named_scope(blocks.SCOPE_NORM):
            down = blocks.rms_norm(down, p["n4"], c.rms_eps)
        return a + down


def _stack(c: LoopedConfig, mesh, rope, pad_mask, h, layers):
    """All layers in order: a scan over the stacked parameters."""
    def body(carry, p):
        return _layer(c, mesh, rope, pad_mask, carry, p), None

    if c.remat:
        body = jax.checkpoint(body)
    with jax.named_scope(blocks.SCOPE_LAYERS):
        return jax.lax.scan(body, h, layers)[0]


def _states(c: LoopedConfig, mesh, params, seq):
    """``h_1 .. h_last`` (a generator, each [B, T, D]): the normed state after
    every pass, under the pass's scope."""
    with jax.named_scope(blocks.SCOPE_EMBED):
        pad_mask = seq > 0
        rope = blocks.rope_tables(seq.shape[1], c.head_dim, c.rope_theta)
        h = jnp.take(params["embed"], seq, axis=0)
    for t in range(1, c.ut_steps + 1):
        with jax.named_scope(blocks.SCOPE_PASS.format(t)):
            u = _stack(c, mesh, rope, pad_mask, h, params["layers"])
            with jax.named_scope(blocks.SCOPE_EXIT):
                h = blocks.rms_norm(u, params["final_norm"], c.rms_eps)
        yield t, h


def _gate(params, h):
    """``lam`` [B, T]: float32 throughout (one output, no MXU work to save)."""
    return jax.nn.sigmoid(
        jnp.einsum("btd,d->bt", h, params["gate_w"],
                   precision=jax.lax.Precision.HIGHEST) + params["gate_b"])


def exit_distribution(lams):
    """``p`` [steps, ...] from ``lam`` [steps, ...]: the survival product; the
    last pass takes what is left (its own gate is not read)."""
    survive = jnp.cumprod(1.0 - lams[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lams[:1]), survive[:-1]], axis=0)
    return jnp.concatenate([lams[:-1] * before, survive[-1:]], axis=0)


def exits_loss(c: LoopedConfig, ces, lams, targets):
    """The objective from each exit's cross-entropy and gate: ``ces``, ``lams``
    [steps, B, T]. Returns ``(loss, aux)``; ``aux`` holds each exit's own mean
    cross-entropy and the mean exit distribution over the positions with a
    target, and the exit distribution ``p`` of every position."""
    mask = (targets > 0).astype(jnp.float32)
    count = jnp.maximum(mask.sum(), 1.0)
    p = exit_distribution(lams)
    entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(axis=0)
    per_position = (p * ces).sum(axis=0) - c.exit_beta * entropy
    mean = lambda a: (a * mask).sum(axis=(-2, -1)) / count  # noqa: E731
    return mean(per_position), {"exit_ce": mean(ces), "exit_p": mean(p), "p": p}


def make_loss(c: LoopedConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step on
    ``mesh``."""
    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        ces, lams = [], []
        for t, h in _states(c, mesh, params, seq):
            with (jax.named_scope(blocks.SCOPE_PASS.format(t)),
                  jax.named_scope(blocks.SCOPE_EXIT)):
                lams.append(_gate(params, h))
                ces.append(blocks.exit_ce(
                    c, h.reshape(-1, h.shape[-1]), params["head"],
                    targets.reshape(-1)).reshape(targets.shape))
        with (jax.named_scope(blocks.SCOPE_PASS.format(c.ut_steps)),
              jax.named_scope(blocks.SCOPE_EXIT)):
            return exits_loss(c, jnp.stack(ces), jnp.stack(lams), targets)

    return loss_fn


def forward_exits(c: LoopedConfig, mesh, params, seq):
    """Every exit in full, for tests and small sizes: ``logits``
    [steps, B, T, V] and the exit distribution ``p`` [steps, B, T]."""
    dtype = jnp.dtype(c.compute_dtype)
    logits, lams = [], []
    for _, h in _states(c, mesh, params, seq):
        lams.append(_gate(params, h))
        logits.append(blocks.matmul(h, params["head"].T, dtype))
    return jnp.stack(logits), exit_distribution(jnp.stack(lams))


def score_last(c: LoopedConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row. Every pass
    runs; a row is scored from the first pass at which its cumulative exit
    probability reaches ``early_exit_threshold``, which at 1.0 is the last."""
    dtype = jnp.dtype(c.compute_dtype)
    pick = lambda a: blocks.take_last(a, last)  # noqa: E731
    early = c.early_exit_threshold < 1.0
    states, lams = [], []
    for _, h in _states(c, None, params, seqs):
        states.append(pick(h))                                       # [B, D]
        if early:
            lams.append(pick(_gate(params, h)[..., None])[:, 0])     # [B]
    h_exit = states[-1]
    if early:
        reached = jnp.cumsum(exit_distribution(jnp.stack(lams)), axis=0)
        # the last pass qualifies whatever rounding made of its sum
        ok = (reached >= c.early_exit_threshold).at[-1].set(True)
        taken = jnp.argmax(ok, axis=0)                            # first True
        h_exit = jnp.take_along_axis(
            jnp.stack(states), taken[None, :, None], axis=0)[0]
    return blocks.matmul(h_exit, params["head"].T, dtype)
