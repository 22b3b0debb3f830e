"""What every backbone of the sequence template is built from: the device
scopes of a training step, the fields every decoder's configuration holds, the
parameter draw, the block's small pieces (norm, rotary positions, matmul,
SwiGLU), the chunked head and its loss, the template's attention on its
mesh, and the streamed attention of the sparse, hybrid and window backbones
with the operands it reads (``rope_operands``, ``attention_of``). The backbones
import this module (the expert ones through
``experts.py``) and none imports another; nothing here imports a backbone.

How the pieces are worked, for all of them: matmul inputs are cast to
``compute_dtype`` (bfloat16) and accumulated in float32; norms, rotary
positions and the loss are float32; the head and loss of an exit never hold
more than ``HEAD_CHUNK_BYTES`` of float32 logits: positions are worked in
chunks (2,048 of them at a vocabulary of 49,152), each recomputed in the
backward pass; a count the chunk does not divide is padded up to whole chunks.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import rope_layout, sparse_attention as sa
from predictionio_tpu.ops.flash_attention import flash_attention, operands_in_place
from predictionio_tpu.parallel.ring_attention import plain_attention, ring_attention
from predictionio_tpu.parallel.ulysses import ulysses_attention

#: Device scopes of one training step (``jax.named_scope``; metadata only).
#: Every device operation of the step falls under ``seq.embed``,
#: ``seq.pass<t>/layers/attention``, ``seq.pass<t>/layers/mlp`` (the expert
#: backbones: ``layers/moe``, ``experts.py``), ``seq.pass<t>/exit`` (final norm,
#: gate, head, loss) or ``seq.optimizer``; the backward pass wraps the same
#: names (``transpose(jvp(seq.pass1))/...``).
SCOPE_EMBED = "seq.embed"
SCOPE_PASS = "seq.pass{}"
SCOPE_LAYERS = "layers"
SCOPE_ATTENTION = "attention"
SCOPE_MLP = "mlp"
SCOPE_EXIT = "exit"
SCOPE_OPTIMIZER = "seq.optimizer"
#: Leaves under the stages, by class of operation, the same names in every
#: backbone: ``norm`` at a layer's RMSNorm call sites (the exit's final norm
#: stays the exit's), ``qkv`` the input projections and the reshape to heads,
#: ``rope`` the rotary positions of ``q`` and ``k`` (in the streamed backbones,
#: where the package's programs run, the programs that write the attention's
#: operands: ``rope_operands``; nothing where the flash kernel's programs turn
#: them themselves: ``attention_operands``), ``kernel`` the attention itself
#: (the Pallas programs and the transposes and casts around them), ``out`` the
#: output projection and the residual add.
SCOPE_NORM = "norm"
SCOPE_QKV = "qkv"
SCOPE_ROPE = "rope"
SCOPE_KERNEL = "kernel"
SCOPE_OUT = "out"

#: the most float32 logits one chunk of an exit's head holds at once
HEAD_CHUNK_BYTES = 384 << 20


@dataclass(frozen=True)
class DecoderConfig:
    """What the configuration of every decoder backbone holds beside its own
    widths: what the fit reads, and how the step is worked. ``compute_dtype``,
    ``remat`` and ``head_chunk`` are fields for the tests that hold these
    workings to the same numbers; no engine parameter reaches them."""

    num_items: int              # real item vocab; id 0 is reserved for padding
    max_len: int = 64
    rms_eps: float = 1e-6
    learning_rate: float = 3e-4
    batch_size: int = 256
    epochs: int = 10
    seed: int = 0
    seq_parallel: str = "ring"
    attention: str = "auto"
    compute_dtype: str = "bfloat16"   # matmul inputs; accumulation is float32
    remat: bool = True
    head_chunk: int | None = None     # None: from HEAD_CHUNK_BYTES; 0: a pass whole

    def __post_init__(self):
        if self.attention not in ("auto", "flash", "plain"):
            raise ValueError(
                f"attention={self.attention!r} must be one of 'auto' | 'flash' | 'plain'")
        if self.compute_dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"compute_dtype={self.compute_dtype!r}: want 'bfloat16' or 'float32'")

    @property
    def vocab(self) -> int:
        return self.num_items + 1  # +1 for the padding id 0


def head_chunk_of(c) -> int:
    """Positions of an exit's logits held at once (0 = a pass whole): a
    multiple of 128 that keeps a chunk's float32 logits within
    ``HEAD_CHUNK_BYTES``, unless the configuration names a count."""
    if c.head_chunk is not None:
        return c.head_chunk
    return max(128, HEAD_CHUNK_BYTES // (4 * c.vocab) // 128 * 128)


def decoder_fit_attrs(c, layers: int, passes: int = 1, halves: bool = False) -> dict:
    """A decoder's part of the fit's span that every decoder has: its layers
    and passes, what a step keeps for the backward pass (``halves``: a layer's
    mixer and experts are rematerialised apart), how the head is worked."""
    chunk = head_chunk_of(c)
    return {
        "layers": layers, "passes": passes,
        "rematerialised": ("nothing" if not c.remat else
                           "mixer and experts" if halves else "layer"),
        "head": f"chunks of {chunk} positions, recomputed" if chunk else "whole pass, recomputed",
    }


# ---- parameters --------------------------------------------------------------

_is_shape = lambda x: isinstance(x, tuple)  # noqa: E731


def writer_stds(writers, num_layers: int) -> dict:
    """The standard deviations of a draw that are not 0.02: the embedding
    N(0, 1) and the projections that write into the residual stream
    (``writers``) N(0, 0.02 / sqrt(2 L)), GPT-2's scaling. With everything at
    0.02 the near-uniform attention of an untrained model adds the same mean of
    values to every position, and a router that sees one state in every
    position sends a layer's tokens to the same few experts."""
    return {"embed": 1.0, **{name: 0.02 / np.sqrt(2 * num_layers) for name in writers}}


def draw_params(shapes: dict, rng, ones=(), zeros=(), stds=None, draws=None) -> dict:
    """A float32 parameter tree from a tree of shapes. Leaf ``n`` of the
    flattened tree, by its own name: 1 (``ones``), 0 (``zeros``),
    ``draws[name](key, shape)``, else N(0, ``stds[name]`` or 0.02), the key
    ``fold_in(rng, n)``."""
    stds, draws = stds or {}, draws or {}
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    out = []
    for n, (path, shape) in enumerate(leaves):
        name, key = path[-1].key, jax.random.fold_in(rng, n)
        if name in ones:
            out.append(jnp.ones(shape, jnp.float32))
        elif name in zeros:
            out.append(jnp.zeros(shape, jnp.float32))
        elif name in draws:
            out.append(draws[name](key, shape))
        else:
            out.append(stds.get(name, 0.02) * jax.random.normal(key, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def count_params(shapes: dict, but=()) -> int:
    """The parameters of a tree of shapes, less the leaves named in ``but``."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]
    return sum(int(np.prod(shape)) for path, shape in leaves if path[-1].key not in but)


# ---- the block's pieces ------------------------------------------------------

def rms_norm(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope_tables(t: int, head_dim: int, theta: float):
    """``cos, sin`` of ``[T, head_dim]``: the half-width frequencies repeated
    over both halves of the head (the rotate-half convention)."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


#: x [B, T, H, hd] float32 turned by ``cos, sin`` [T, rd] over the first ``rd``
#: of a head, the rest passed through (the plain twin of the rotation's program)
rotate = rope_layout.rotate


def matmul(x, w, dtype):
    """``x @ w``: inputs cast to ``dtype``, accumulated in float32."""
    return jnp.matmul(x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32)


def swiglu(u, w_gate, w_up, w_down, dtype):
    """``W_down (silu(W_gate u) * (W_up u))``, no biases."""
    inner = jax.nn.silu(matmul(u, w_gate, dtype)) * matmul(u, w_up, dtype)
    return matmul(inner, w_down, dtype)


# ---- the head ----------------------------------------------------------------

def exit_ce(c, h, head, targets):
    """Cross-entropy of every position against ``targets``: ``h`` [N, D],
    ``head`` [V, D] -> [N]. Logits exist for ``head_chunk_of(c)`` positions
    at a time and are recomputed in the backward pass; the positions are
    padded up to whole chunks (the padding's values are dropped)."""
    dtype = jnp.dtype(c.compute_dtype)
    head = head.astype(dtype)  # once, not a chunk

    @jax.checkpoint
    def piece(hc, yc):
        logits = matmul(hc, head.T, dtype)
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


def masked_ce(c, x, weight, head, targets, norm=rms_norm):
    """The mean cross-entropy of a residual stream ``x`` [B, T, D] over the
    positions with a target: its final norm (``weight``), the chunked head."""
    h = norm(x, weight, c.rms_eps)
    ce = exit_ce(c, h.reshape(-1, h.shape[-1]), head, targets.reshape(-1))
    mask = (targets.reshape(-1) > 0).astype(jnp.float32)
    return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def take_last(a, last):
    """``a[b, last[b]]`` for ``a`` [B, T, ...]."""
    return jnp.take_along_axis(a, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def score_last(c, x, weight, head, last, norm=rms_norm):
    """Next-item scores [B, V] at position ``last`` of each row of a residual
    stream ``x`` [B, T, D]: its final norm (``weight``), the position, the
    head."""
    h = take_last(norm(x, weight, c.rms_eps), last)
    return matmul(h, head.T, jnp.dtype(c.compute_dtype))


# ---- the platform and the attention on its mesh --------------------------------

def backend_of(mesh, whole_rows: bool = False) -> str:
    """The platform a program is built for: the mesh's, when there is one.
    ``whole_rows``: the backbone works a row whole and refuses a ``seq`` axis."""
    if whole_rows and mesh is not None and mesh.shape.get("seq", 1) > 1:
        raise ValueError(
            "this backbone works a row whole (its selection, its recurrent state): it"
            " does not run on a mesh whose 'seq' axis is larger than 1")
    return mesh.devices.flat[0].platform if mesh is not None else jax.default_backend()


def uses_kernels(c, backend: str) -> bool:
    """Whether the package's Pallas programs work the step (``attention``:
    "auto" takes them on a TPU, "flash" everywhere, "plain" nowhere)."""
    return c.attention == "flash" or (c.attention == "auto" and backend == "tpu")


def rope_operands(c, backend: str, q, k, v, rope):
    """What a layer's streamed attention reads (:func:`attention_of`), from the
    projections' float32 outputs q [B, T, H, hd], k, v [B, T, KV, hd] and the
    layer's table, for the scope ``rope``. Where the package's programs run,
    ``ops/rope_layout.py``'s one program a phase writes them once: rotated,
    q scaled for the scores, cast to the compute dtype and laid heads-first as
    the attention programs read them. Elsewhere q and k rotated, float32."""
    if not uses_kernels(c, backend):
        return rotate(q, *rope), rotate(k, *rope), v
    b, t, h, _ = q.shape
    return rope_layout.rope_layout(
        *(x.reshape(b, t, -1) for x in (q, k, v)), *rope, (h, k.shape[2]), c.compute_dtype,
        backend != "tpu")


def attention_of(c, backend: str, q, k, v, mask=None, window=None):
    """Attention [B, T, H, hd] in the compute dtype on :func:`rope_operands`'
    q, k, v, for the scope ``kernel``: over the pairs ``mask`` [B, T, T]
    selects, or every causal pair, with a ``window`` those of the band. The
    programs of ``ops/sparse_attention.py`` on the operands as they lie, or
    their plain twins."""
    if uses_kernels(c, backend):
        return sa.heads_first_attention(q, k, v, mask, sa.BLOCK_Q, sa.BLOCK_K,
                                        backend != "tpu", window)
    q, k, v = (x.astype(jnp.dtype(c.compute_dtype)) for x in (q, k, v))
    if mask is not None:
        return sa.sparse_attention_plain(q, k, v, mask)
    return sa.causal_attention_plain(q, k, v, window)


def rope_block(c, platform: str, heads: int, kv_heads: int, head_dim: int) -> str:
    """The tile of the programs that write a layer's attention operands on a
    row of ``max_len``, positions by lanes of q (``ops/rope_layout.tile_of``,
    from the shapes alone); ``plain`` where XLA works the rotation."""
    if not uses_kernels(c, platform):
        return "plain"
    bt, lanes, _ = rope_layout.tile_of((heads, kv_heads), head_dim, head_dim, c.max_len)
    return f"{bt}x{lanes}"


def attention_operands(c, platform: str, head_dim: int) -> str:
    """How :func:`attend`'s q, k, v reach the flash kernel on ``platform`` and
    its results leave (``ops/flash_attention.operands_in_place``, from the
    head's width alone): as blocks of the projections' own arrays, the rotary
    positions turned inside the programs; through ``[B, H, T, D]`` transposes
    around them, XLA's rotation before; ``plain`` where the kernel does not
    run. (A mesh that shares a row over a ``seq`` axis rotates by XLA before
    its ring or Ulysses attention whatever this says.)"""
    if not uses_kernels(c, platform):
        return "plain"
    return "in place, rotated in the programs" if operands_in_place(head_dim) else "transposed"


def attend(c, mesh, q, k, v, pad_mask, rope=None):
    """Causal attention with the padded keys masked, q, k, v [B, T, H, D],
    mesh-aware, under the scope ``kernel``: ring or Ulysses attention
    (``c.seq_parallel``) when the mesh has a >1 ``seq`` axis, else the Pallas
    flash kernel or the materialized-score reference (``uses_kernels``).
    ``rope``: the layer's ``cos, sin`` [T, D] where q and k come unrotated. The
    flash kernel's programs turn them on heads of whole lane tiles
    (:func:`attention_operands`); on every other path ``rotate`` does first,
    under the scope ``rope``."""
    backend = backend_of(mesh)
    use_flash = uses_kernels(c, backend)
    seq_parallel = mesh is not None and mesh.shape.get("seq", 1) > 1
    in_programs = use_flash and not seq_parallel and operands_in_place(q.shape[-1])
    if rope is not None and not in_programs:
        with jax.named_scope(SCOPE_ROPE):
            q, k, rope = rotate(q, *rope), rotate(k, *rope), None
    with jax.named_scope(SCOPE_KERNEL):
        if seq_parallel:
            if c.seq_parallel == "ulysses":
                # ulysses gathers full sequences per chip, so the flash
                # kernel slots in as its local attention
                return ulysses_attention(q, k, v, mesh, axis_name="seq",
                                         causal=True, mask=pad_mask,
                                         use_flash=use_flash)
            # ring attention IS the online softmax across shards; its
            # per-step scores are already [Tl, Tl] blocks, so "flash"
            # asks for nothing it does not already do
            return ring_attention(q, k, v, mesh, axis_name="seq",
                                  causal=True, mask=pad_mask)
        if use_flash:
            # O(T*D) memory: scores never materialize (ops/flash_attention)
            return flash_attention(
                q, k, v, pad_mask, causal=True,
                interpret=backend != "tpu", rope=rope,
            )
        return plain_attention(q, k, v, causal=True, mask=pad_mask)
