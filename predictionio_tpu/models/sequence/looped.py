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
- the head and loss of an exit never hold more than ``HEAD_CHUNK_BYTES`` of
  float32 logits: positions are worked in chunks (2,048 of them at a
  vocabulary of 49,152), each recomputed in the backward pass; a count the
  chunk does not divide is padded up to whole chunks.

``compute_dtype``, ``remat`` and ``head_chunk`` are fields for the tests that
hold these workings to the same numbers; no engine parameter reaches them.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

#: Device scopes of one training step (``jax.named_scope``; metadata only).
#: Every device operation of the step falls under ``seq.embed``,
#: ``seq.pass<t>/layers/attention``, ``seq.pass<t>/layers/mlp``,
#: ``seq.pass<t>/exit`` (final norm, gate, head, loss) or ``seq.optimizer``;
#: the backward pass wraps the same names (``transpose(jvp(seq.pass1))/...``).
SCOPE_EMBED = "seq.embed"
SCOPE_PASS = "seq.pass{}"
SCOPE_LAYERS = "layers"
SCOPE_ATTENTION = "attention"
SCOPE_MLP = "mlp"
SCOPE_EXIT = "exit"
SCOPE_OPTIMIZER = "seq.optimizer"
#: Leaves under the stages, by class of operation (``sparse_moe`` uses the
#: same names): ``norm`` at a layer's RMSNorm call sites (the exit's final norm
#: stays the exit's), ``qkv`` the three projections and the reshape to heads,
#: ``rope`` the rotary positions of ``q`` and ``k``, ``kernel`` the call of
#: ``attend`` (the Pallas programs and the transposes and casts around them),
#: ``out`` the ``wo`` projection and the residual add.
SCOPE_NORM = "norm"
SCOPE_QKV = "qkv"
SCOPE_ROPE = "rope"
SCOPE_KERNEL = "kernel"
SCOPE_OUT = "out"

#: the most float32 logits one chunk of an exit's head holds at once
HEAD_CHUNK_BYTES = 384 << 20


@dataclass(frozen=True)
class LoopedConfig:
    num_items: int              # real item vocab; id 0 is reserved for padding
    max_len: int = 64
    hidden_size: int = 64
    num_heads: int = 4
    head_dim: int = 16
    ffn_dim: int = 176
    num_layers: int = 2
    ut_steps: int = 4
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    exit_beta: float = 0.1
    early_exit_threshold: float = 1.0
    learning_rate: float = 3e-4
    batch_size: int = 256
    epochs: int = 10
    seed: int = 0
    seq_parallel: str = "ring"
    attention: str = "auto"
    # how the step is worked: what the tests vary, and no engine parameter
    compute_dtype: str = "bfloat16"   # matmul inputs; accumulation is float32
    remat: bool = True
    head_chunk: int | None = None     # None: from HEAD_CHUNK_BYTES; 0: a pass whole

    def __post_init__(self):
        if self.attention not in ("auto", "flash", "plain"):
            raise ValueError(
                f"attention={self.attention!r} must be one of"
                " 'auto' | 'flash' | 'plain'"
            )
        if self.seq_parallel not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel={self.seq_parallel!r}: want 'ring' or 'ulysses'"
            )
        if self.compute_dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"compute_dtype={self.compute_dtype!r}: want 'bfloat16' or 'float32'"
            )
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even (rotary pairs)")
        if self.ut_steps < 1 or self.num_layers < 1:
            raise ValueError("ut_steps and num_layers must be at least 1")
        if not 0.0 < self.early_exit_threshold <= 1.0:
            raise ValueError(
                f"early_exit_threshold={self.early_exit_threshold} must lie in (0, 1]"
            )

    @property
    def vocab(self) -> int:
        return self.num_items + 1  # +1 for the padding id 0


def head_chunk_of(c: LoopedConfig) -> int:
    """Positions of an exit's logits held at once (0 = a pass whole): a
    multiple of 128 that keeps a chunk's float32 logits within
    ``HEAD_CHUNK_BYTES``, unless the configuration names a count."""
    if c.head_chunk is not None:
        return c.head_chunk
    return max(128, HEAD_CHUNK_BYTES // (4 * c.vocab) // 128 * 128)


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


_NORMS = ("n1", "n2", "n3", "n4", "final_norm")


def init_params(c: LoopedConfig, rng) -> dict:
    """Matrices N(0, 0.02), norm weights 1, the gate's bias 0."""
    shapes = param_shapes(c)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for n, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name in _NORMS:
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "gate_b":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(0.02 * jax.random.normal(
                jax.random.fold_in(rng, n), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def count_params(c: LoopedConfig) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(c), is_leaf=lambda x: isinstance(x, tuple)))


# ---- the block -----------------------------------------------------------

def _rms_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope_tables(t: int, head_dim: int, theta: float):
    """``cos, sin`` of ``[T, head_dim]``: the half-width frequencies repeated
    over both halves of the head (the rotate-half convention)."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def _rotate(x, cos, sin):
    """x [B, T, H, hd] float32."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def _matmul(x, w, dtype):
    """``x @ w``: inputs cast to ``dtype``, accumulated in float32."""
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32)


def _layer(c: LoopedConfig, attend, rope, pad_mask, h, p):
    """One decoder layer on ``h`` [B, T, D] with its parameters ``p``."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    with jax.named_scope(SCOPE_ATTENTION):
        with jax.named_scope(SCOPE_NORM):
            z = _rms_norm(h, p["n1"], c.rms_eps)
        with jax.named_scope(SCOPE_QKV):
            q, k, v = (_matmul(z, p[w], dtype).reshape(b, t, c.num_heads, c.head_dim)
                       for w in ("wq", "wk", "wv"))
        with jax.named_scope(SCOPE_ROPE):
            q, k = _rotate(q, *rope), _rotate(k, *rope)
        with jax.named_scope(SCOPE_KERNEL):
            out = attend(q, k, v, pad_mask).reshape(b, t, -1)
        with jax.named_scope(SCOPE_OUT):
            out = _matmul(out, p["wo"], dtype)
        with jax.named_scope(SCOPE_NORM):
            out = _rms_norm(out, p["n2"], c.rms_eps)
        with jax.named_scope(SCOPE_OUT):
            a = h + out
    with jax.named_scope(SCOPE_MLP):
        with jax.named_scope(SCOPE_NORM):
            z = _rms_norm(a, p["n3"], c.rms_eps)
        inner = jax.nn.silu(_matmul(z, p["w_gate"], dtype)) * _matmul(z, p["w_up"], dtype)
        down = _matmul(inner, p["w_down"], dtype)
        with jax.named_scope(SCOPE_NORM):
            down = _rms_norm(down, p["n4"], c.rms_eps)
        return a + down


def _stack(c: LoopedConfig, attend, rope, pad_mask, h, layers):
    """All layers in order: a scan over the stacked parameters."""
    def body(carry, p):
        return _layer(c, attend, rope, pad_mask, carry, p), None

    if c.remat:
        body = jax.checkpoint(body)
    with jax.named_scope(SCOPE_LAYERS):
        return jax.lax.scan(body, h, layers)[0]


def _states(c: LoopedConfig, attend, params, seq):
    """``h_1 .. h_last`` (a generator, each [B, T, D]): the normed state after
    every pass, under the pass's scope."""
    with jax.named_scope(SCOPE_EMBED):
        pad_mask = seq > 0
        rope = _rope_tables(seq.shape[1], c.head_dim, c.rope_theta)
        h = jnp.take(params["embed"], seq, axis=0)
    for t in range(1, c.ut_steps + 1):
        with jax.named_scope(SCOPE_PASS.format(t)):
            u = _stack(c, attend, rope, pad_mask, h, params["layers"])
            with jax.named_scope(SCOPE_EXIT):
                h = _rms_norm(u, params["final_norm"], c.rms_eps)
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


def _exit_ce(c: LoopedConfig, h, head, targets):
    """Cross-entropy of every position against ``targets``: ``h`` [N, D],
    ``head`` [V, D] -> [N]. Logits exist for ``head_chunk_of(c)`` positions
    at a time and are recomputed in the backward pass; the positions are
    padded up to whole chunks (the padding's values are dropped)."""
    dtype = jnp.dtype(c.compute_dtype)
    head = head.astype(dtype)  # once, not a chunk

    @jax.checkpoint
    def piece(hc, yc):
        logits = _matmul(hc, head.T, dtype)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    n = h.shape[0]
    chunk = head_chunk_of(c)
    if not chunk or chunk >= n:
        return piece(h, targets)
    pad = -n % chunk
    h = jnp.pad(h, ((0, pad), (0, 0)))
    targets = jnp.pad(targets, (0, pad))
    return jax.lax.map(
        lambda args: piece(*args),
        (h.reshape(-1, chunk, h.shape[-1]), targets.reshape(-1, chunk)),
    ).reshape(-1)[:n]


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


def make_loss(c: LoopedConfig, attend):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step.
    ``attend(q, k, v, pad_mask)`` is the template's attention on its mesh."""
    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        ces, lams = [], []
        for t, h in _states(c, attend, params, seq):
            with jax.named_scope(SCOPE_PASS.format(t)), jax.named_scope(SCOPE_EXIT):
                lams.append(_gate(params, h))
                ces.append(_exit_ce(
                    c, h.reshape(-1, h.shape[-1]), params["head"],
                    targets.reshape(-1)).reshape(targets.shape))
        with jax.named_scope(SCOPE_PASS.format(c.ut_steps)), jax.named_scope(SCOPE_EXIT):
            return exits_loss(c, jnp.stack(ces), jnp.stack(lams), targets)

    return loss_fn


def forward_exits(c: LoopedConfig, attend, params, seq):
    """Every exit in full, for tests and small sizes: ``logits``
    [steps, B, T, V] and the exit distribution ``p`` [steps, B, T]."""
    dtype = jnp.dtype(c.compute_dtype)
    logits, lams = [], []
    for _, h in _states(c, attend, params, seq):
        lams.append(_gate(params, h))
        logits.append(_matmul(h, params["head"].T, dtype))
    return jnp.stack(logits), exit_distribution(jnp.stack(lams))


def score_last(c: LoopedConfig, attend, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row. Every pass
    runs; a row is scored from the first pass at which its cumulative exit
    probability reaches ``early_exit_threshold``, which at 1.0 is the last."""
    dtype = jnp.dtype(c.compute_dtype)
    pick = lambda a: jnp.take_along_axis(  # noqa: E731
        a, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    early = c.early_exit_threshold < 1.0
    states, lams = [], []
    for _, h in _states(c, attend, params, seqs):
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
    return _matmul(h_exit, params["head"].T, dtype)
