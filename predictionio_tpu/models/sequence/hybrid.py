"""The hybrid backbone of the sequence template: a decoder whose layers come
in periods of ``full_attention_interval``, all but the last of a period
linear-attention layers (a gated delta rule: a state a row carries along, no
score square) and the last a gated full-attention layer, each followed by a
routed mixture of experts of which this program holds a share, beside a
shared expert every token takes.

The block is that of ``Qwen3-Next-80B-A3B`` (``model_type qwen3_next``: 3
Gated DeltaNet layers to 1 gated attention layer, 512 experts, 10 a token, one
shared expert behind a sigmoid gate) with the item catalog as its vocabulary.
For one row ``x`` ``[T, D]``, ``n(.)`` the zero-centred RMSNorm
``x / rms(x) (1 + w)``:

- **linear layer** (``HV`` value heads of ``dv``, ``HK`` key heads of ``dk``, a
  key head serving ``HV / HK`` value heads): ``h = n1(x)``;
  ``[q, k, v, z] = h W_qkvz``, ``[b, a] = h W_ba``;
  ``[q, k, v] <- silu(conv([q, k, v]))``, ``conv`` depthwise, causal, over the
  last ``conv_kernel`` positions, no bias; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) softplus(a + dt_bias)`` a value head;
  ``q <- q / |q| / sqrt(dk)``, ``k <- k / |k|``; the gated delta rule
  (``ops/delta_rule.py`` has the recurrence) gives ``o`` ``[T, HV, dv]``;
  ``y = o / rms(o) w_n silu(z)``; ``x <- x + concat_heads(y) W_out``. A padded
  slot (``seq == 0``) has ``beta = 0``, ``g = 0`` and a zero conv input: it
  neither moves the state nor is read;
- **full layer**: ``[q, gate] = h W_q`` (``H`` heads of ``hd + hd``),
  ``k = h W_k``, ``v = h W_v`` (``KV`` heads); ``q <- n_q(q)``, ``k <- n_k(k)``
  over the head; rotary positions on the first ``rotary_dim`` dimensions of a
  head, the rest pass; causal ``softmax(q k^T / sqrt(hd)) v``;
  ``x <- x + (attn sigmoid(gate)) W_o``;
- **experts, every layer**: ``u = n2(x)``; the router, its top
  ``experts_per_token``, the renormalised gates, the held experts' part of the
  sum and the load-balancing loss are ``experts.moe``'s, as they stand
  (``experts_held = (lo, hi)``: the router is whole, the other chips' parts are
  theirs to add, nothing stands in for them); beside it the shared expert,
  worked on every token: ``sigmoid(u . w_sg) W2_s(silu(W1_s u) W3_s u)``;
- final norm, an untied head, cross-entropy at the positions with a target
  plus ``aux_coef`` times the mean over the layers of the load-balancing loss.

How it is worked (``benchmarks/reference_qwen3next.py`` is the same
mathematics with none of this):

- the parameters of a period are stacked by kind of layer, a layer's mixer
  and experts together: ``periods/linear`` ``[P, I - 1, ...]``, ``periods/full``
  ``[P, ...]`` (one array of all layers' experts would be sliced, and so
  copied, for each kind); the stack is a ``lax.scan`` over the periods, inside
  it a ``lax.scan`` over the period's linear layers and then its full layer.
  Each half of a layer, the mixer and the experts, is rematerialised from its
  own input (``remat``): the forward pass keeps the residual stream before
  each, ``2 L`` states ``[B, T, D]``, and nothing else, and a backward pass
  holds one half's intermediates at a time (a linear mixer's and its experts'
  together did not leave the chip room);
- a linear mixer's conv, its silu, the mask of empty positions and the split
  into q, k, v are ``ops/causal_conv.causal_conv_silu``: on a TPU one Pallas
  program forward and one backward, each reading the projection's float32
  output once and writing q, k, v (or ``dx`` and a partial ``dw``) once, a
  tile of ``conv_block`` positions by channels a grid step (1,024 by 512 in
  the published widths, from the shapes alone) with the 8 rows beside it, the
  tile walked 32 rows at a time in registers. It keeps for the backward pass
  what the plain expression's ``jax.checkpoint`` keeps, the projection's
  output, and forms the pre-activation again in VMEM; off the TPU its plain
  twin, XLA's pad, shifted slices, silu and split;
- the delta rule is worked ``delta_chunk`` positions at a time
  (``ops/delta_rule.gated_delta_rule``): the triangular system and the
  products inside the chunks for all chunks at once, the state by a pass over
  the chunks, on a TPU two Pallas programs (the pass and its transpose), a
  grid step of which works one chunk of a block of row-heads, a row's value
  heads and the batch's rows alike (``delta_heads_per_step``: 8 where 8 divide
  ``B x HV``, from the shapes alone). While a layer's backward pass runs, the
  state every chunk starts from is held (``delta_kept_bytes``); between the
  passes nothing of the rule is;
- the full layer's attention is the sparse backbone's attention programs,
  forward and one backward (8 query heads a key-value head, K and V streamed),
  with no mask operand, on operands that ``ops/rope_layout.py``'s one program
  a phase writes from the normed q and k and from v: turned over the first
  ``rotary_dim`` of a head, q scaled, cast and laid heads-first
  (``blocks.rope_operands`` under ``rope``, ``blocks.attention_of`` under
  ``kernel``); off the TPU ``blocks.rotate`` and the plain twin;
- matmul inputs are ``compute_dtype`` (bfloat16) with float32 accumulation;
  the state, ``g``, ``beta``, the l2 norms, the triangular system, the router,
  norms, rotary positions, softmax, residual stream, loss, master weights and
  Adam's moments are float32;
- the head and loss are ``blocks.exit_ce``'s chunks of positions.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from predictionio_tpu.models.sequence import blocks, experts
from predictionio_tpu.ops import causal_conv, delta_rule, sparse_attention as sa

#: Device scopes of a training step beside ``blocks``'s and ``experts``'s: a
#: linear layer's mixer under ``seq.pass1/layers/linear_attention`` (one
#: component, so a reader that looks for ``attention`` does not take it), the
#: full layer's under ``attention`` with ``blocks``'s leaves.
SCOPE_LINEAR = "linear_attention"
#: Leaves under ``linear_attention`` beside ``norm``, ``qkv`` (the two input
#: projections) and ``out``: ``conv`` (the depthwise convolution and its silu),
#: ``gates`` (beta, g, the l2 norms), ``delta`` (the chunked rule and its
#: programs), ``gated_norm`` (the output's norm and gate).
SCOPE_CONV = "conv"
SCOPE_GATES = "gates"
SCOPE_DELTA = "delta"
SCOPE_GATED_NORM = "gated_norm"


@dataclass(frozen=True)
class HybridConfig(experts.ExpertsConfig):
    hidden_size: int = 64
    num_layers: int = 4
    full_attention_interval: int = 4   # the last layer of every so many is the full one
    linear_key_heads: int = 2
    linear_value_heads: int = 4
    linear_key_dim: int = 16
    linear_value_dim: int = 16
    conv_kernel: int = 4
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rotary_fraction: float = 0.25      # of a head's dimensions, the first, are rotated
    shared_expert_dim: int = 32
    rope_theta: float = 1e7
    aux_coef: float = 0.001
    delta_chunk: int = delta_rule.CHUNK   # as ``remat``: what the tests vary

    def __post_init__(self):
        super().__post_init__()
        if self.full_attention_interval < 2 or self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"num_layers={self.num_layers} must be whole periods of"
                f" full_attention_interval={self.full_attention_interval} (at least 2)")
        for many, few, what in ((self.num_heads, self.num_kv_heads, "num_kv_heads"),
                                (self.linear_value_heads, self.linear_key_heads,
                                 "linear_key_heads")):
            if many % few:
                raise ValueError(f"{what}={few} must divide the {many} heads it serves")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"rotary_fraction={self.rotary_fraction} of head_dim={self.head_dim} must"
                " be an even count of dimensions")

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def linear_layers(self) -> int:
        return self.num_layers - self.periods

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.rotary_fraction)


CONFIG = HybridConfig
ENGINE_PARAMS = {
    **experts.ENGINE_PARAMS, "hiddenSize": "hidden_size", "numLayers": "num_layers",
    "fullAttentionInterval": "full_attention_interval", "linearKeyHeads": "linear_key_heads",
    "linearValueHeads": "linear_value_heads", "linearKeyDim": "linear_key_dim",
    "linearValueDim": "linear_value_dim", "convKernel": "conv_kernel", "numHeads": "num_heads",
    "numKvHeads": "num_kv_heads", "headDim": "head_dim", "partialRotaryFactor": "rotary_fraction",
    "sharedExpertDim": "shared_expert_dim", "ropeTheta": "rope_theta", "rmsNormEps": "rms_eps",
    "auxLossCoef": "aux_coef",
}


def param_shapes(c: HybridConfig) -> dict:
    """The parameter tree as shapes. ``periods/linear`` leads with ``[P, I - 1]``,
    ``periods/full`` with ``[P]``; each holds its layers' mixer and experts."""
    d, p, i = c.hidden_size, c.periods, c.full_attention_interval
    keys, values = c.linear_key_heads * c.linear_key_dim, c.linear_value_heads * c.linear_value_dim
    hd, shared = c.head_dim, c.shared_expert_dim
    lin = (p, i - 1)

    def experts(lead):
        return {
            "n2": lead + (d,), "router": lead + (d, c.num_experts),
            "w_gate": lead + (c.held, d, c.expert_dim), "w_up": lead + (c.held, d, c.expert_dim),
            "w_down": lead + (c.held, c.expert_dim, d),
            "s_gate": lead + (d, shared), "s_up": lead + (d, shared),
            "s_down": lead + (shared, d), "s_g": lead + (d,),
        }

    return {
        "embed": (c.vocab, d),
        "periods": {
            "linear": {
                "n1": lin + (d,), "w_qkvz": lin + (d, 2 * keys + 2 * values),
                "w_ba": lin + (d, 2 * c.linear_value_heads),
                "conv": lin + (2 * keys + values, c.conv_kernel),
                "a_log": lin + (c.linear_value_heads,), "dt_bias": lin + (c.linear_value_heads,),
                "norm": lin + (c.linear_value_dim,), "w_out": lin + (values, d),
                **experts(lin),
            },
            "full": {
                "n1": (p, d), "wq": (p, d, 2 * c.num_heads * hd),
                "wk": (p, d, c.num_kv_heads * hd), "wv": (p, d, c.num_kv_heads * hd),
                "wo": (p, c.num_heads * hd, d), "q_norm": (p, hd), "k_norm": (p, hd),
                **experts((p,)),
            },
        },
        "final_norm": (d,),
        "head": (c.vocab, d),
    }


def init_params(c: HybridConfig, rng) -> dict:
    """The embedding N(0, 1), matrices N(0, 0.02), those that write into the
    residual stream scaled down (``blocks.writer_stds``), with the
    family's own: zero-centred norm weights 0 and the gated norm's plain weight
    1, ``A_log = log U(0, 16)``, ``dt_bias = 1``, the conv weights U(-1/2, 1/2)
    (``torch.nn.Conv1d``'s default at a fan-in of 4)."""
    return blocks.draw_params(
        param_shapes(c), rng, ones=("norm", "dt_bias"),
        zeros=("n1", "n2", "q_norm", "k_norm", "final_norm"),
        stds=blocks.writer_stds(("w_out", "wo", "w_down", "s_down"), c.num_layers),
        draws={"a_log": lambda key, shape: jnp.log(
                   jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)),
               "conv": lambda key, shape: jax.random.uniform(
                   key, shape, jnp.float32, -0.5, 0.5)})


def count_params(c: HybridConfig) -> int:
    return blocks.count_params(param_shapes(c))


def delta_state_bytes(c: HybridConfig) -> int:
    """A row's recurrent states, all linear layers: what serving one user
    would carry from one event to the next."""
    return c.linear_layers * c.linear_value_heads * c.linear_key_dim * c.linear_value_dim * 4


def delta_kept_bytes(c: HybridConfig, rows: int) -> int:
    """The states every chunk of ``rows`` rows starts from, float32: what a
    step holds of the rule for a backward pass. A rematerialised layer keeps
    nothing between the passes and holds its own chunks' states while its
    backward pass runs, one layer at a time; with ``remat`` off every linear
    layer's are kept from the forward pass."""
    chunks = -(-c.max_len // c.delta_chunk)
    layers = 1 if c.remat else c.linear_layers
    return rows * chunks * layers * delta_state_bytes(c) // c.linear_layers


def delta_heads_per_step(c: HybridConfig, rows: int) -> int:
    """The row-heads a grid step of the rule's state pass works on ``rows``
    rows (``ops/delta_rule.heads_per_step``, from the shapes alone)."""
    return delta_rule.heads_per_step(
        rows * c.linear_value_heads, c.delta_chunk, c.linear_key_dim, c.linear_value_dim,
        jnp.dtype(c.compute_dtype).itemsize)


def conv_block(c: HybridConfig, platform: str) -> str:
    """The tile of the conv's programs on a row of ``max_len``, positions by
    channels (``ops/causal_conv.block_of``, from the shapes alone); ``plain``
    where XLA works the conv."""
    if not blocks.uses_kernels(c, platform):
        return "plain"
    keys = c.linear_key_heads * c.linear_key_dim
    return "x".join(map(str, causal_conv.block_of(
        c.max_len, (keys, keys, c.linear_value_heads * c.linear_value_dim))))


def attention_backward_heads_per_step(c: HybridConfig) -> int:
    """The key-value heads a grid step of the full layer's backward attention
    program works on a row of ``max_len`` (``ops/sparse_attention``, from the
    shapes alone): grouped heads with no mask operand."""
    return sa.backward_heads_per_step(
        c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, c.head_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize, False)


def fit_attrs(c: HybridConfig, rows: int, platform: str) -> dict:
    """The backbone's part of the fit's span, for a step on ``rows`` rows."""
    return {
        **blocks.decoder_fit_attrs(c, c.num_layers, halves=True),
        **experts.fit_attrs(c, platform, attention_backward_heads_per_step(c), shared=True),
        "kv_heads": c.num_kv_heads, "selection_kept_bytes": 0,
        "linear_layers": c.linear_layers, "full_layers": c.periods,
        "delta_chunk": c.delta_chunk, "delta_heads_per_step": delta_heads_per_step(c, rows),
        "conv_block": conv_block(c, platform),
        "rope_block": blocks.rope_block(c, platform, c.num_heads, c.num_kv_heads, c.head_dim),
        "delta_state_bytes": delta_state_bytes(c), "delta_kept_bytes": delta_kept_bytes(c, rows),
    }


def norm0(x, weight, eps):
    return blocks.rms_norm(x, 1.0 + weight, eps)


# ---- the mixers --------------------------------------------------------------

def _l2_normalised(x):
    return x * jax.lax.rsqrt((x * x).sum(axis=-1, keepdims=True) + 1e-6)


def _linear_attention(c: HybridConfig, backend: str, h, p, real):
    """The linear mixer's output before ``W_out`` ``[B, T, HV x dv]`` on the
    normed input ``h``."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    hk, hv, dk, dv = (c.linear_key_heads, c.linear_value_heads, c.linear_key_dim,
                      c.linear_value_dim)
    on = real[..., None]
    kernels, interpret = blocks.uses_kernels(c, backend), backend != "tpu"
    with jax.named_scope(blocks.SCOPE_QKV):
        # two products, so that the gate z is not a view into one array that
        # holds q, k and v until the backward pass is done with z
        w_mixed, w_z = jnp.split(p["w_qkvz"], [2 * hk * dk + hv * dv], axis=-1)
        mixed, z = blocks.matmul(h, w_mixed, dtype), blocks.matmul(h, w_z, dtype)
        beta, a = jnp.split(blocks.matmul(h, p["w_ba"], dtype), 2, axis=-1)
    with jax.named_scope(SCOPE_CONV):
        # kept either way: the input alone; the products and the silu are worked
        # again. v enters the rule's products as the compute dtype: it is written so
        split = (hk * dk, hk * dk, hv * dv), ("float32", "float32", dtype.name)
        q, k, v = (causal_conv.causal_conv_silu(mixed, p["conv"], real, *split, interpret)
                   if kernels else
                   causal_conv.causal_conv_silu_plain(mixed, p["conv"], real, *split))
    with jax.named_scope(SCOPE_GATES):
        beta = jnp.where(on, jax.nn.sigmoid(beta), 0.0)
        g = jnp.where(on, -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"]), 0.0)
        q = _l2_normalised(q.reshape(b, t, hk, dk)) * dk ** -0.5
        k = _l2_normalised(k.reshape(b, t, hk, dk))
    with jax.named_scope(SCOPE_DELTA):
        o = delta_rule.gated_delta_rule(
            q, k, v.reshape(b, t, hv, dv), g, beta, chunk=c.delta_chunk, dtype=dtype,
            kernels=kernels, interpret=interpret)
    with jax.named_scope(SCOPE_GATED_NORM):
        y = blocks.rms_norm(o, p["norm"], c.rms_eps) * jax.nn.silu(z.reshape(b, t, hv, dv))
    return y.reshape(b, t, hv * dv)


def _full_attention(c: HybridConfig, backend: str, rope, h, p):
    """The full mixer's gated output before ``W_o`` ``[B, T, H x hd]``."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    hd = c.head_dim
    with jax.named_scope(blocks.SCOPE_QKV):
        q, gate = jnp.split(blocks.matmul(h, p["wq"], dtype).reshape(b, t, c.num_heads, 2 * hd),
                            2, axis=-1)
        k, v = (blocks.matmul(h, p[w], dtype).reshape(b, t, c.num_kv_heads, hd)
                for w in ("wk", "wv"))
    with jax.named_scope(blocks.SCOPE_NORM):
        q, k = norm0(q, p["q_norm"], c.rms_eps), norm0(k, p["k_norm"], c.rms_eps)
    with jax.named_scope(blocks.SCOPE_ROPE):
        q, k, v = blocks.rope_operands(c, backend, q, k, v, rope)
    with jax.named_scope(blocks.SCOPE_KERNEL):
        out = blocks.attention_of(c, backend, q, k, v)
        out = out.astype(jnp.float32) * jax.nn.sigmoid(gate)
    return out.reshape(b, t, -1)


def _linear_mixer(c: HybridConfig, backend: str, real, x, p):
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(SCOPE_LINEAR):
        with jax.named_scope(blocks.SCOPE_NORM):
            h = norm0(x, p["n1"], c.rms_eps)
        y = _linear_attention(c, backend, h, p, real)
        with jax.named_scope(blocks.SCOPE_OUT):
            return x + blocks.matmul(y, p["w_out"], dtype)


def _full_mixer(c: HybridConfig, backend: str, rope, x, p):
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(blocks.SCOPE_ATTENTION):
        with jax.named_scope(blocks.SCOPE_NORM):
            h = norm0(x, p["n1"], c.rms_eps)
        out = _full_attention(c, backend, rope, h, p)
        with jax.named_scope(blocks.SCOPE_OUT):
            return x + blocks.matmul(out, p["wo"], dtype)


# ---- the stack ---------------------------------------------------------------

def hidden_states(c: HybridConfig, backend: str, params, seq):
    """``(x, stats)``: the residual stream after the last layer ``[B, T, D]``
    and every layer's counts ``[layers, ...]``, under the pass's scope."""
    with jax.named_scope(blocks.SCOPE_EMBED):
        real = seq > 0
        rope = blocks.rope_tables(seq.shape[1], c.rotary_dim, c.rope_theta)
        x = jnp.take(params["embed"], seq, axis=0)

    kept = jax.checkpoint if c.remat else (lambda half: half)
    expert_half = kept(lambda x, p: experts.expert_half(c, backend, x, p, real, norm0))
    linear_mixer = kept(lambda x, p: _linear_mixer(c, backend, real, x, p))
    full_mixer = kept(lambda x, p: _full_mixer(c, backend, rope, x, p))

    def linear(carry, layer):
        return expert_half(linear_mixer(carry, layer), layer)

    def full(carry, layer):
        return expert_half(full_mixer(carry, layer), layer)

    def period(carry, p):
        carry, stats = jax.lax.scan(linear, carry, p["linear"])
        carry, last = full(carry, p["full"])
        return carry, jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b[None]]), stats, last)

    with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_LAYERS):
        x, stats = jax.lax.scan(period, x, params["periods"])
    return x, jax.tree_util.tree_map(lambda a: a.reshape(-1, *a.shape[2:]), stats)


def make_loss(c: HybridConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step;
    ``aux`` is scalars: the two terms of the loss and the step's counts, under
    ``sparse_moe.make_loss``'s names."""
    backend = blocks.backend_of(mesh, whole_rows=True)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        x, stats = hidden_states(c, backend, params, seq)
        with jax.named_scope(blocks.SCOPE_PASS.format(1)), jax.named_scope(blocks.SCOPE_EXIT):
            ce = blocks.masked_ce(c, x, params["final_norm"], params["head"], targets, norm0)
            aux_loss = stats["aux"].mean()
            out = {"ce": ce, "aux_loss": aux_loss, **experts.counts(c, stats)}
            return ce + c.aux_coef * aux_loss, out

    return loss_fn


def score_last(c: HybridConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row: the whole
    history a query (no state is carried from one query to the next)."""
    x, _ = hidden_states(c, blocks.backend_of(None), params, seqs)
    return blocks.score_last(c, x, params["final_norm"], params["head"], last, norm0)
