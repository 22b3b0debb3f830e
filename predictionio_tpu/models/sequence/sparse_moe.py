"""The sparse backbone of the sequence template: a decoder whose attention
reads, for every query, the ``index_topk`` earlier positions a learned indexer
scores highest, and whose feed-forward is a routed mixture of experts of which
this program holds a share.

The block is that of ``Keye-VL-2.0-30B-A3B``'s language model (``model_type
KeyeVL2``: grouped key-value heads, a DeepSeek-Sparse-Attention indexer, 128
experts, 8 a token, no shared expert) with the item catalog as its
vocabulary. For one row ``x`` ``[T, D]`` (``n1``, ``n2`` RMSNorm):

- ``h = n1(x)``; ``q = h Wq`` ``[T, H, hd]``, ``k = h Wk``, ``v = h Wv``
  ``[T, KV, hd]``, rotary positions on ``q`` and ``k``;
- indexer: ``qI = h WqI`` ``[T, HI, dI]``, ``kI = rms(h WkI)`` ``[T, dI]`` (no
  learned scale), ``w = h Ww`` ``[T, HI]``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` is
  the ``index_topk`` positions ``s <= t`` with the largest ``I[t, s]`` (all of
  them while ``t < index_topk``; ties to the earlier position);
- ``a[t] = softmax over s in S_t of (q[t, g] . k[s, g // (H / KV)] / sqrt(hd))``,
  ``x = x + concat_g(a v) Wo``;
- ``u = n2(x)``; ``p = softmax(u Wr)`` over all ``num_experts`` in float32;
  ``E_t`` the ``experts_per_token`` largest, ``g[t, e] = p[t, e] / sum_{E_t} p``;
  ``x = x + sum_{e in E_t, e held here} g[t, e] W2_e (silu(W1_e u) * (W3_e u))``;
- after the last layer ``h = n_f(x)``, ``logits = W_head h`` (not tied), and
  the loss of a position with a target is its cross-entropy, the mean over
  such positions, plus ``aux_coef`` times the mean over the layers of
  ``num_experts sum_e f_e P_e`` (``f_e`` the assignments to expert ``e`` a
  real token, ``P_e`` the mean of ``p[., e]``; the load-balancing loss of the
  family).

``experts_held = (lo, hi)`` names the experts this program holds, as one chip
of an expert-parallel deployment does: the router is whole (every chip routes
its tokens over all the experts), the expert weights are ``hi - lo`` of them,
and a layer adds the held experts' part of the sum. The other chips' parts
are theirs to add: nothing here stands in for them, and with every expert
held the layer is the whole layer.

The indexer decides by a hard top-k, which passes no gradient, so the
next-item loss cannot train it: its three matrices (``params["indexer"]``)
are inputs of the fit that stay as drawn, and the optimizer keeps no state
for them (``model.py:optimizer_of``). DeepSeek trains its indexer by a
separate alignment loss; that recipe is not part of this backbone.

How it is worked (``benchmarks/reference_keye.py`` is the same mathematics
with none of this):

- layer parameters are stacked ``[L, ...]`` and the stack is one ``lax.scan``,
  each layer rematerialised from its input (``remat``) and from its selection:
  the forward pass keeps the mask of selected pairs, one bit a pair
  (``_pack_rows``; ``L B T T / 8`` bytes, 100.7 MB at 6 layers of 2 rows of
  8,192), and the backward pass unpacks it where the layer is worked again,
  so the indexer's projections, the index scores and the k-th largest are
  worked once a step. Everything else of a layer is recomputed;
- matmul inputs are ``compute_dtype`` (bfloat16) with float32 accumulation;
  the router's matmul, softmax and top-k, the residual stream, norms, rotary
  positions, attention softmax, loss, master weights and Adam's moments are
  float32; the index scores are bfloat16 products accumulated in float32;
- on a TPU (``attention`` "auto") index scores, selection and attention are
  the three programs of ``ops/sparse_attention.py``: scores in tiles over the
  causal triangle, the k-th largest by bisection with a block of queries'
  scores in VMEM, attention with K and V streamed a block at a time; elsewhere
  their ``jax.numpy`` twins;
- experts: a token's assignments to held experts are sorted by expert and
  worked as grouped matmuls (``jax.lax.ragged_dot``) over exactly those rows:
  no capacity, no token dropped. Every row array is as long as a static bound
  ``R`` (``pass_plan``: twice the held experts' even share of the tokens
  worked at once, within ``MOE_CHUNK_BYTES``), not as the worst case: pass
  ``p`` works the sorted rows ``[p R, (p + 1) R)``, and a pass past the last
  held row is skipped at run time (``lax.cond``), forward and backward. An
  even router takes one pass a layer, a skewed one as many as it needs, and
  with every expert held the bound is the worst case. Rows come from their
  tokens by a gather and go back by a gather too (a scatter of rows cost the
  chip more than the whole of this, PERF.md PR 33). Where the package's
  programs run (``sum_path``: the platform decides, as for attention) a pass's
  ``R`` rows are gathered once into token order and one program adds each
  token's run (``ops/run_sum.py``, PERF.md PR 41): the same float32 sum of
  float32 rows times float32 gates. Elsewhere a token sums, by the position
  the sort gave each of its ``K`` assignments, its rows of the pass's short
  array (``_sum_by_position``, which the tests hold the program to). The
  experts' part keeps its operands alone and is worked again in the backward
  pass;
- the head and loss are ``looped._exit_ce``'s chunks of positions.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from predictionio_tpu.models.sequence import looped
from predictionio_tpu.ops import run_sum
from predictionio_tpu.ops import sparse_attention as sa

#: Device scopes of a training step beside ``looped``'s (``seq.embed``,
#: ``seq.pass1/layers/attention``, ``seq.pass1/exit``, ``seq.optimizer``):
#: under ``attention`` the indexer's projections and scores, the k-th
#: largest, and the attention over the selection; under ``layers/moe`` the
#: router and the held experts.
SCOPE_INDEX = "index"
SCOPE_SELECT = "select"
SCOPE_KERNEL = looped.SCOPE_KERNEL
SCOPE_MOE = "moe"
SCOPE_ROUTE = "route"
SCOPE_EXPERTS = "experts"
#: Leaves under ``moe/experts``, by class of operation (a layer's ``norm``,
#: ``qkv``, ``rope``, ``out`` are ``looped``'s): ``sort`` the held test, the two
#: argsorts, the group sizes and the passes' plan; ``take`` the gather of a
#: pass's rows from their tokens and its transpose; ``grouped`` the three
#: grouped matmuls and the gated product between them; ``give`` the rows back
#: onto their tokens and its transpose; ``sum`` a token's sum of its rows (by
#: runs: the gather into token order and the program; else by position), inside
#: ``give`` forward and inside ``take`` backward; the runs' plan is ``sort``'s.
#: ``again`` marks the forward work a backward rule runs again: a
#: ``custom_vjp`` rule's recomputation carries no ``rematted_computation``, so
#: the program says it.
SCOPE_SORT = "sort"
SCOPE_TAKE = "take"
SCOPE_GROUPED = "grouped"
SCOPE_GIVE = "give"
SCOPE_SUM = "sum"
SCOPE_AGAIN = "again"
#: what a rematerialised layer keeps from the forward pass beside its input
#: (``jax.ad_checkpoint.checkpoint_name``): the selection, one bit a pair
KEPT_SELECTION = "selection"

#: the most float32 bytes the held experts' output rows of one pass may take
MOE_CHUNK_BYTES = 256 << 20
#: a pass's rows over the held experts' even share of the tokens worked at
#: once: PERF.md PR 33 read 0.99 to 1.02 of that share a step over 14 seeds
#: (single experts up to 1.58 of theirs, PR 32; the sum over those held is
#: steadier than any one); a router that sends more takes further passes
MOE_ROWS_OVER_EVEN = 2


@dataclass(frozen=True)
class SparseMoEConfig:
    num_items: int              # real item vocab; id 0 is reserved for padding
    max_len: int = 64
    hidden_size: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    expert_dim: int = 32
    num_experts: int = 8
    experts_per_token: int = 2
    experts_held: tuple = (0, 8)    # [lo, hi) of the experts: this program's share
    num_layers: int = 2
    index_heads: int = 2
    index_dim: int = 16
    index_topk: int = 16
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    aux_coef: float = 0.001
    learning_rate: float = 3e-4
    batch_size: int = 256
    epochs: int = 10
    seed: int = 0
    seq_parallel: str = "ring"
    attention: str = "auto"
    # how the step is worked: what the tests vary, and no engine parameter
    compute_dtype: str = "bfloat16"   # matmul inputs; accumulation is float32
    remat: bool = True
    head_chunk: int | None = None     # None: from looped.HEAD_CHUNK_BYTES; 0: whole
    moe_chunk: int | None = None      # None: from MOE_CHUNK_BYTES; tokens a chunk

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(int(e) for e in self.experts_held))
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"experts_held={self.experts_held}: want 0 <= lo < hi <= num_experts="
                f"{self.num_experts}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} must be a multiple of num_kv_heads="
                f"{self.num_kv_heads}")
        if not 1 <= self.experts_per_token <= self.num_experts:
            raise ValueError(
                f"experts_per_token={self.experts_per_token}: want 1 .. num_experts")
        if self.attention not in ("auto", "flash", "plain"):
            raise ValueError(
                f"attention={self.attention!r} must be one of 'auto' | 'flash' | 'plain'")
        if self.compute_dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"compute_dtype={self.compute_dtype!r}: want 'bfloat16' or 'float32'")
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even (rotary pairs)")
        if self.index_topk < 1 or self.num_layers < 1:
            raise ValueError("index_topk and num_layers must be at least 1")

    @property
    def vocab(self) -> int:
        return self.num_items + 1  # +1 for the padding id 0

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def param_shapes(c: SparseMoEConfig) -> dict:
    """The parameter tree as shapes. The layers' arrays lead with ``[L]``;
    ``indexer`` is held fixed by the fit."""
    d, n, hd = c.hidden_size, c.num_layers, c.head_dim
    return {
        "embed": (c.vocab, d),
        "layers": {
            "n1": (n, d), "wq": (n, d, c.num_heads * hd), "wk": (n, d, c.num_kv_heads * hd),
            "wv": (n, d, c.num_kv_heads * hd), "wo": (n, c.num_heads * hd, d),
            "n2": (n, d), "router": (n, d, c.num_experts),
            "w_gate": (n, c.held, d, c.expert_dim), "w_up": (n, c.held, d, c.expert_dim),
            "w_down": (n, c.held, c.expert_dim, d),
        },
        "indexer": {
            "wq": (n, d, c.index_heads * c.index_dim), "wk": (n, d, c.index_dim),
            "ww": (n, d, c.index_heads),
        },
        "final_norm": (d,),
        "head": (c.vocab, d),
    }


_NORMS = ("n1", "n2", "final_norm")
_is_shape = lambda x: isinstance(x, tuple)  # noqa: E731


def init_params(c: SparseMoEConfig, rng) -> dict:
    """Norm weights 1, the embedding N(0, 1), matrices N(0, 0.02) and the
    projections that write into the residual stream (``wo``, ``w_down``)
    N(0, 0.02 / sqrt(2 L)), GPT-2's scaling. With everything at 0.02 the
    near-uniform attention of an untrained model adds the same mean of values
    to every position, and a router that sees one state in every position
    sends a layer's tokens to the same few experts."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(c), is_leaf=_is_shape)
    stds = {"embed": 1.0, "wo": 0.02 / np.sqrt(2 * c.num_layers),
            "w_down": 0.02 / np.sqrt(2 * c.num_layers)}
    out = []
    for n, (path, shape) in enumerate(leaves):
        name = path[-1].key
        if name in _NORMS:
            out.append(jnp.ones(shape, jnp.float32))
            continue
        out.append(stds.get(name, 0.02) * jax.random.normal(
            jax.random.fold_in(rng, n), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def count_params(c: SparseMoEConfig) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(c), is_leaf=_is_shape))


def trained_labels(params) -> dict:
    """``"train"`` or ``"fixed"`` for every leaf: the indexer is fixed."""
    return {name: jax.tree_util.tree_map(
        lambda _: "fixed" if name == "indexer" else "train", sub)
        for name, sub in params.items()}


def uses_kernels(c: SparseMoEConfig, backend: str) -> bool:
    return c.attention == "flash" or (c.attention == "auto" and backend == "tpu")


def sum_path(c, backend: str) -> str:
    """How a pass's rows come back onto their tokens: ``"runs"`` where the
    package's programs run (``ops/run_sum.py``), else ``"positions"``."""
    return "runs" if uses_kernels(c, backend) else "positions"


# ---- attention over the indexer's selection ----------------------------------

def _pack_rows(mask):
    """The 0/1 mask ``[B, T, T]`` as bits, eight query rows a byte with the
    keys left where they are: ``byte[b, r, s] = sum_i mask[b, 8 r + i, s] << i``,
    uint8 ``[B, ceil(T / 8), T]``. Both directions are elementwise over whole
    rows of keys; bits along the key axis would be gathered within a row."""
    b, t, keys = mask.shape
    rows = jnp.pad(mask.astype(jnp.uint8), ((0, 0), (0, -t % 8), (0, 0)))
    rows = rows.reshape(b, -1, 8, keys) << jnp.arange(8, dtype=jnp.uint8)[:, None]
    return rows.sum(axis=2, dtype=jnp.uint8)


def _unpack_rows(packed, t: int):
    """``_pack_rows`` undone: int8 ``[B, t, T]``."""
    b, _, keys = packed.shape
    bits = (packed[:, :, None, :] >> jnp.arange(8, dtype=jnp.uint8)[:, None]) & 1
    return bits.reshape(b, -1, keys)[:, :t].astype(jnp.int8)


def selection_kept_bytes(c: SparseMoEConfig, rows: int) -> int:
    """What a step on ``rows`` rows keeps of its selections from the forward
    pass to the backward pass: every layer's packed mask, where the layers are
    rematerialised (otherwise the backward pass has the mask itself)."""
    return c.num_layers * rows * -(-c.max_len // 8) * c.max_len if c.remat else 0


def attention_backward_heads_per_step(c, masked: bool = True) -> int:
    """The key-value heads a grid step of the attention's backward program works
    on a row of ``max_len`` (``ops/sparse_attention``, from the shapes alone);
    ``masked``: the selection's tile is one of the step's blocks."""
    return sa.backward_heads_per_step(
        c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim, c.head_dim, c.max_len,
        jnp.dtype(c.compute_dtype).itemsize, masked)


def _attention(c: SparseMoEConfig, backend: str, rope, h, p, ip, real, probe=None):
    """``(o, counts)``: the attention output before ``Wo`` ``[B, T, H x hd]``
    on the normed input ``h``, and what it selected. ``probe``: query
    positions whose index scores and selection are returned too."""
    dtype = jnp.dtype(c.compute_dtype)
    b, t, _ = h.shape
    kernels, interpret = uses_kernels(c, backend), backend != "tpu"
    with jax.named_scope(looped.SCOPE_QKV):
        q, k, v = (looped._matmul(h, p[w], dtype).reshape(b, t, n, c.head_dim)
                   for w, n in (("wq", c.num_heads), ("wk", c.num_kv_heads),
                                ("wv", c.num_kv_heads)))
    with jax.named_scope(looped.SCOPE_ROPE):
        q, k = looped._rotate(q, *rope), looped._rotate(k, *rope)
    with jax.named_scope(SCOPE_INDEX):
        # the selection is a hard top-k: no gradient, to the input or the indexer
        hs, ip = jax.lax.stop_gradient((h, ip))
        q_idx = looped._matmul(hs, ip["wq"], dtype).reshape(b, t, c.index_heads, c.index_dim)
        k_idx = looped._matmul(hs, ip["wk"], dtype)
        k_idx = k_idx * jax.lax.rsqrt(jnp.mean(k_idx * k_idx, axis=-1, keepdims=True)
                                      + c.rms_eps)
        w = looped._matmul(hs, ip["ww"], dtype)
        q_idx, k_idx = q_idx.astype(dtype), k_idx.astype(dtype)
        scores = (sa.index_scores(q_idx, k_idx, w, interpret=interpret) if kernels
                  else sa.index_scores_plain(q_idx, k_idx, w))
    with jax.named_scope(SCOPE_SELECT):
        mask = (sa.select_topk(scores, c.index_topk, interpret=interpret) if kernels
                else sa.select_topk_plain(scores, c.index_topk))
        per_query = mask.astype(jnp.int32).sum(axis=-1)
        counts = {"selected_pairs": jnp.where(real, per_query, 0).sum(),
                  "causal_pairs": jnp.where(real, jnp.arange(1, t + 1)[None, :], 0).sum()}
        if probe is not None:
            counts["probe_scores"] = scores[:, probe, :]
            counts["probe_mask"] = mask[:, probe, :]
        # a rematerialised layer starts from these bits (``hidden_states``)
        kept = checkpoint_name(_pack_rows(mask), KEPT_SELECTION)
    with jax.named_scope(SCOPE_KERNEL):
        mask = _unpack_rows(kept, t)
        q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
        if kernels:
            out = sa.sparse_attention(q, k, v, mask, sa.BLOCK_Q, sa.BLOCK_K, interpret)
        else:
            out = sa.sparse_attention_plain(q, k, v, mask)
    return out.reshape(b, t, -1), counts


# ---- routed experts ---------------------------------------------------------

def pass_plan(c: SparseMoEConfig, n: int) -> tuple[int, int]:
    """``(R, passes)`` for ``n`` tokens worked at once, from static shapes: a
    pass works ``R`` sorted rows, ``MOE_ROWS_OVER_EVEN`` times the held experts'
    even share in whole 128s and no more than the worst case (every token's
    ``min(K, held)`` slots held); the passes cover that worst case."""
    worst = n * min(c.experts_per_token, c.held)
    share = -(-MOE_ROWS_OVER_EVEN * n * c.experts_per_token * c.held // c.num_experts)
    bound = min(-(-share // 128) * 128, worst)
    return bound, -(-worst // bound)


def moe_chunk_of(c: SparseMoEConfig) -> int:
    """Tokens of a layer's experts worked at once: the most, in whole 128s,
    whose pass of rows (``pass_plan``) keeps within ``MOE_CHUNK_BYTES``."""
    if c.moe_chunk is not None:
        return c.moe_chunk
    rows = MOE_CHUNK_BYTES // (4 * c.hidden_size)
    by_worst = rows // min(c.experts_per_token, c.held)
    by_share = (rows // 128 * 128 * c.num_experts
                // (MOE_ROWS_OVER_EVEN * c.experts_per_token * c.held))
    return max(128, max(by_worst, by_share) // 128 * 128)


def _cut(a, chunk: int):
    """``a`` as ``[chunks, chunk, ...]``, its leading axis padded with zeros."""
    pad = -a.shape[0] % chunk
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        -1, chunk, *a.shape[1:])


def _sum_by_position(rows, pos, weight):
    """``y[t] = sum_k weight[t, k] rows[pos[t, k]]`` over the ``k`` whose
    weight is not 0, in float32: a token's rows of a pass, found where the sort
    put them. Gathers from the short array ``rows`` [R, D], in chunks of tokens
    whose gathered ``[tokens, K, D]`` block keeps within ``MOE_CHUNK_BYTES``."""
    n, slots = pos.shape

    def block(at):
        pos, weight = at
        return jnp.where(weight[..., None] != 0, rows[pos] * weight[..., None], 0.0).sum(axis=1)

    chunk = max(1, MOE_CHUNK_BYTES // (4 * slots * rows.shape[-1]))
    with jax.named_scope(SCOPE_SUM):
        if chunk >= n:
            return block((pos, weight))
        y = jax.lax.map(block, (_cut(pos, chunk), _cut(weight, chunk)))
        return y.reshape(-1, y.shape[-1])[:n]


def _sum_by_runs(rows, weight, runs, shape, interpret, *, unit, dtype=jnp.float32):
    """``_sum_by_position``'s sum for ``shape = (n, K)`` with the weights by
    row, ``weight`` [R] (0 past the live rows), over the pass's rows in token
    order (``runs``, ``run_sum.plan``'s): one gather of ``R`` rows and one
    program, under the same scope. ``unit``: the weights are 0 and 1 alone."""
    n, slots = shape
    with jax.named_scope(SCOPE_SUM):
        return run_sum.sum_runs(rows, weight, runs, n, slots, unit=unit, out_dtype=dtype,
                                interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_rows(interpret, u, token, live, pos, mine, runs):
    """Row ``r`` of a pass takes its token, ``u[token[r]]``; rows past the
    pass's ``live`` ones are 0. The transpose sums a token's rows, by runs
    where the pass brings them (``runs``), else by position."""
    return jnp.where(live, u[token], 0)


def _take_rows_bwd(interpret, res, g):
    live, pos, mine, runs = res
    if runs is None:
        d_u = _sum_by_position(g, pos, mine.astype(jnp.float32)).astype(g.dtype)
    else:
        d_u = _sum_by_runs(g, live[:, 0], runs, pos.shape, interpret, unit=True, dtype=g.dtype)
    return d_u, None, None, None, None, None


_take_rows.defvjp(lambda interpret, *args: (_take_rows(interpret, *args), args[2:]),
                  _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _give_back(interpret, out, gates, row, live, pos, mine, runs):
    """A token's sum of its rows of the pass, ``out`` [R, D], each times the
    gate of its assignment: ``[n, D]`` float32. ``row`` [R] is a row's
    assignment, an index into ``gates`` [n, K]. The transpose gathers ``R``
    rows of ``dy`` and, for the gates, a row dot: no scatter either way."""
    if runs is None:
        return _sum_by_position(out, pos, jnp.where(mine, gates, 0.0))
    by_row = jnp.where(live[:, 0], gates.reshape(-1)[row], 0.0)
    return _sum_by_runs(out, by_row, runs, pos.shape, interpret, unit=False)


def _give_back_bwd(interpret, res, dy):
    out, gates, row, live, pos, mine, _ = res
    sent = dy[row // mine.shape[1]]                                # [R, D]
    d_gate = jnp.where(live[:, 0], (out * sent).sum(axis=-1), 0.0)
    return (jnp.where(live, sent * gates.reshape(-1)[row][:, None], 0.0),
            jnp.where(mine, d_gate[pos], 0.0), None, None, None, None, None)


_give_back.defvjp(lambda interpret, *args: (_give_back(interpret, *args), args),
                  _give_back_bwd)


def _one_pass(interpret, u, gates, w_gate, w_up, w_down, back, row, sizes, start):
    """What one pass adds to the tokens ``[n, D]``: the sorted rows ``[start,
    start + R)``, the assignments ``row`` [R], of which the first
    ``sizes.sum()`` are held (``sizes`` [held]: the pass's share of each
    expert's rows), through the three grouped matmuls and back. ``interpret``
    None: the rows come back by position; else by runs (``ops/run_sum.py``, its
    program interpreted or compiled), the pass's rows put in token order once
    for both sums."""
    n, slots = gates.shape
    worked = sizes.sum()
    # rows past the held ones belong to no group: whatever a grouped matmul
    # leaves there goes no further, forward or backward
    live = (jnp.arange(row.shape[0]) < worked)[:, None]
    pos = back - start
    mine = (pos >= 0) & (pos < worked)
    pos = jnp.where(mine, pos, 0)
    token = row // slots
    runs = None
    if interpret is not None:
        with jax.named_scope(SCOPE_SORT):
            runs = run_sum.plan(jnp.where(live[:, 0], token, run_sum.NO_TOKEN), n)
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=jnp.float32)
    with jax.named_scope(SCOPE_TAKE):
        x = _take_rows(interpret, u, token, live, pos, mine, runs)             # [R, D]
    with jax.named_scope(SCOPE_GROUPED):
        inner = jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)
        out = dot(inner.astype(u.dtype), w_down)
    with jax.named_scope(SCOPE_GIVE):
        return _give_back(interpret, out, gates, row, live, pos, mine, runs)


def _over_passes(plans, run, zeros):
    """``run(plan)`` summed over the passes that hold a row (``plans``: every
    pass's ``(rows, sizes, start)``, stacked). A pass without rows is not run:
    the sum is carried past it, and nothing is written for it. The first pass
    starts the sum (``zeros()`` stands for it where no row is held at all)."""
    holds_rows = lambda plan: plan[1].sum() > 0  # noqa: E731
    at = lambda i: jax.tree_util.tree_map(lambda a: a[i], plans)  # noqa: E731
    total = jax.lax.cond(holds_rows(at(0)), lambda: run(at(0)), zeros)
    if plans[0].shape[0] == 1:
        return total

    def one(total, plan):
        return jax.lax.cond(
            holds_rows(plan),
            lambda total: jax.tree_util.tree_map(jnp.add, total, run(plan)),
            lambda total: total, total), None

    return jax.lax.scan(one, total, at(slice(1, None)))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _passes(interpret, operands, back, plans):
    """``_one_pass`` over the passes, for ``operands = (u, gates, w_gate, w_up,
    w_down)``: ``[n, D]`` float32. Only the operands are kept: the backward
    pass works each pass it runs again, and skips the same passes."""
    return _over_passes(plans, lambda plan: _one_pass(interpret, *operands, back, *plan),
                        lambda: jnp.zeros(operands[0].shape, jnp.float32))


def _passes_bwd(interpret, res, dy):
    operands, back, plans = res

    def pulled(plan):
        with jax.named_scope(SCOPE_AGAIN):
            pull = jax.vjp(lambda *a: _one_pass(interpret, *a, back, *plan), *operands)[1]
        return pull(dy)

    return (_over_passes(plans, pulled, lambda: tuple(jnp.zeros_like(a) for a in operands)),
            None, None)


_passes.defvjp(lambda interpret, *args: (_passes(interpret, *args), args), _passes_bwd)


def _experts_chunk(c: SparseMoEConfig, interpret, w_gate, w_up, w_down, u, experts, gates,
                   real):
    """The held experts' part of the layer for a chunk of tokens: ``u`` [n, D]
    bfloat16, ``experts``, ``gates`` [n, K], ``real`` [n] -> ``(y [n, D]
    float32, rows worked, passes run)``. The chunk's ``n K`` assignments are
    sorted once, those to held experts first and by expert; a pass works ``R``
    of the sorted rows (``pass_plan``), and the passes past the last held row
    are skipped at run time."""
    lo, hi = c.experts_held
    n, slots = experts.shape
    bound, passes = pass_plan(c, n)
    with jax.named_scope(SCOPE_SORT):
        held = (experts >= lo) & (experts < hi) & real[:, None]
        local = jnp.where(held, experts - lo, c.held).reshape(-1)      # not held: last
        order = jnp.argsort(local, stable=True)
        back = jnp.argsort(order).reshape(n, slots)
        sizes = (local[:, None] == jnp.arange(c.held)[None, :]).sum(axis=0).astype(jnp.int32)
        # a pass's share of each expert's rows: its group sizes clipped to the range
        starts = bound * jnp.arange(passes, dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        clip = lambda edge: jnp.clip(edge[None, :], starts[:, None], starts[:, None] + bound)  # noqa: E731
        pass_sizes = clip(ends) - clip(ends - sizes)                   # [passes, held]
        rows = jnp.pad(order, (0, max(0, passes * bound - order.size)))[:passes * bound]
    y = _passes(interpret, (u, gates, w_gate, w_up, w_down), back,
                (rows.reshape(passes, bound), pass_sizes, starts))
    worked = pass_sizes.sum(axis=1)
    return y, worked.sum(), (worked > 0).sum()


def load_of(c, experts, real):
    """The assignments of real tokens to every expert ``[E]`` under a router's
    choice ``experts`` [N, K]."""
    chosen = (experts[..., None] == jnp.arange(c.num_experts)) & real[:, None, None]
    return chosen.sum(axis=(0, 1))


def load_stats(c, load) -> dict:
    """A layer's counts of its ``load`` [E]: every assignment, those to held
    experts, the most one held expert takes."""
    lo, hi = c.experts_held
    held_load = load[lo:hi]
    return {"assignments": load.sum(), "held_assignments": held_load.sum(),
            "held_load_max": held_load.max()}


def _route(c: SparseMoEConfig, u, p, real):
    """``(experts, gates, stats)``: the softmax router's ``experts_per_token``
    largest of all ``num_experts`` for the normed tokens ``u`` [N, D], their
    renormalised gates, and the layer's counts with its load-balancing loss
    (``aux``). ``real`` [N]: a padded slot counts nowhere."""
    probs = jax.nn.softmax(jnp.matmul(
        u, p["router"], precision=jax.lax.Precision.HIGHEST), axis=-1)
    top_p, experts = jax.lax.top_k(probs, c.experts_per_token)
    gates = top_p / top_p.sum(axis=-1, keepdims=True)
    count = jnp.maximum(real.sum(), 1).astype(jnp.float32)
    load = load_of(c, experts, real)
    mean_p = jnp.where(real[:, None], probs, 0.0).sum(axis=0) / count
    aux = c.num_experts * jnp.sum(load.astype(jnp.float32) / count * mean_p)
    return experts, gates, {"aux": aux, **load_stats(c, load)}


def _held_experts(c: SparseMoEConfig, backend: str, u, p, experts, gates, real, stats):
    """``(y, stats)``: the held experts' part of the routed sum for the normed
    tokens ``u`` [N, D] under a router's choice (``experts``, ``gates``
    [N, K]), ``stats`` gaining what the passes did and what their forward sums
    read. A padded slot (``real`` [N]) is routed nowhere."""
    dtype = jnp.dtype(c.compute_dtype)
    n = u.shape[0]
    chunk = min(moe_chunk_of(c), n)
    by_runs = sum_path(c, backend) == "runs"
    work = jax.checkpoint(functools.partial(
        _experts_chunk, c, (backend != "tpu") if by_runs else None,
        p["w_gate"].astype(dtype), p["w_up"].astype(dtype), p["w_down"].astype(dtype)))
    y, rows, ran = jax.lax.map(lambda args: work(*args), tuple(
        _cut(a, chunk) for a in (u.astype(dtype), experts, gates, real)))
    stats["dropped"] = stats["held_assignments"] - rows.sum()
    bound, passes = pass_plan(c, chunk)
    slots = chunk * c.experts_per_token
    stats["passes"] = jnp.int32(len(rows) * passes)                   # chunks x passes
    stats["passes_run"] = ran.sum()
    # a pass's forward sum reads its ``R`` rows once by runs, every token's
    # ``K`` positions otherwise
    stats["sum_rows"] = ran.sum() * (bound if by_runs else slots)
    stats["sum_slots"] = ran.sum() * slots
    return y.reshape(-1, y.shape[-1])[:n], stats


def _moe(c: SparseMoEConfig, backend: str, u, p, real, route=_route):
    """``(y, stats)``: the held experts' part of the routed sum for the normed
    tokens ``u`` [N, D], and the layer's counts. ``backend`` is the platform
    the layer runs on, which decides how a pass's rows come back
    (``sum_path``). ``route(c, u, p, real)`` is
    the layer's router, under ``moe/route`` (this backbone's and the hybrid's
    is the softmax ``_route``; the latent backbone brings its own); the held
    experts' work under ``moe/experts`` is the same for all. ``real`` [N]: a
    padded slot is routed nowhere and counts nowhere."""
    with jax.named_scope(SCOPE_ROUTE):
        experts, gates, stats = route(c, u, p, real)
    with jax.named_scope(SCOPE_EXPERTS):
        return _held_experts(c, backend, u, p, experts, gates, real, stats)


# ---- the stack ---------------------------------------------------------------

def _layer(c: SparseMoEConfig, backend: str, rope, real, x, p, ip, probe=None):
    """One decoder layer on ``x`` [B, T, D]: ``(x', stats)``."""
    dtype = jnp.dtype(c.compute_dtype)
    with jax.named_scope(looped.SCOPE_ATTENTION):
        with jax.named_scope(looped.SCOPE_NORM):
            h = looped._rms_norm(x, p["n1"], c.rms_eps)
        out, stats = _attention(c, backend, rope, h, p, ip, real, probe)
        with jax.named_scope(looped.SCOPE_OUT):
            x = x + looped._matmul(out, p["wo"], dtype)
    with jax.named_scope(SCOPE_MOE):
        with jax.named_scope(looped.SCOPE_NORM):
            u = looped._rms_norm(x, p["n2"], c.rms_eps)
        y, routed = _moe(c, backend, u.reshape(-1, u.shape[-1]), p, real.reshape(-1))
        return x + y.reshape(x.shape), {**stats, **routed}


def _backend_of(mesh) -> str:
    if mesh is not None and mesh.shape.get("seq", 1) > 1:
        raise ValueError(
            "this backbone works a row whole (its selection, its recurrent state): it"
            " does not run on a mesh whose 'seq' axis is larger than 1")
    return mesh.devices.flat[0].platform if mesh is not None else jax.default_backend()


def hidden_states(c: SparseMoEConfig, backend: str, params, seq, probe=None):
    """``(x, stats)``: the residual stream after the last layer ``[B, T, D]``
    and every layer's counts ``[L, ...]``, under the pass's scope."""
    with jax.named_scope(looped.SCOPE_EMBED):
        real = seq > 0
        rope = looped._rope_tables(seq.shape[1], c.head_dim, c.rope_theta)
        x = jnp.take(params["embed"], seq, axis=0)

    def body(carry, layer):
        return _layer(c, backend, rope, real, carry, *layer, probe)

    if c.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(KEPT_SELECTION))
    with jax.named_scope(looped.SCOPE_PASS.format(1)), jax.named_scope(looped.SCOPE_LAYERS):
        return jax.lax.scan(body, x, (params["layers"], params["indexer"]))


def make_loss(c: SparseMoEConfig, mesh):
    """``loss_fn(params, batch, rng) -> (loss, aux)`` for the trainer's step;
    ``aux`` is scalars: the two terms of the loss and the step's counts."""
    backend = _backend_of(mesh)

    def loss_fn(params, batch, rng):
        del rng  # no dropout in this block
        seq, targets = batch["seq"], batch["target"]
        x, stats = hidden_states(c, backend, params, seq)
        with jax.named_scope(looped.SCOPE_PASS.format(1)), jax.named_scope(looped.SCOPE_EXIT):
            h = looped._rms_norm(x, params["final_norm"], c.rms_eps)
            ce = looped._exit_ce(c, h.reshape(-1, h.shape[-1]), params["head"],
                                 targets.reshape(-1))
            mask = (targets.reshape(-1) > 0).astype(jnp.float32)
            ce = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
            aux_loss = stats["aux"].mean()
            held = stats["held_assignments"].sum()
            out = {
                "ce": ce, "aux_loss": aux_loss,
                "moe_assignments": stats["assignments"].sum(),
                "moe_held_assignments": held,
                "moe_held_load_max": stats["held_load_max"].max(),
                "moe_held_load_mean": held / (c.num_layers * c.held),
                "moe_dropped": stats["dropped"].sum(),
                "moe_passes": stats["passes"].sum(),
                "moe_passes_run": stats["passes_run"].sum(),
                "moe_sum_rows": stats["sum_rows"].sum(),
                "moe_sum_slots": stats["sum_slots"].sum(),
                "selected_pairs": stats["selected_pairs"].sum(),
                "causal_pairs": stats["causal_pairs"].sum(),
            }
            return ce + c.aux_coef * aux_loss, out

    return loss_fn


def probe_selection(c: SparseMoEConfig, mesh, params, seq, queries):
    """What the step's own index and select programs give for the query
    positions ``queries`` in every layer: ``(scores, mask)``, each
    ``[L, B, len(queries), T]`` (a score above the diagonal is undefined)."""
    _, stats = hidden_states(c, _backend_of(mesh), params, seq, probe=queries)
    return stats["probe_scores"], stats["probe_mask"]


def score_last(c: SparseMoEConfig, params, seqs, last):
    """Next-item scores [B, V] at position ``last`` of each row."""
    x, _ = hidden_states(c, _backend_of(None), params, seqs)
    h = looped._rms_norm(x, params["final_norm"], c.rms_eps)
    h = jnp.take_along_axis(h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return looped._matmul(h, params["head"].T, jnp.dtype(c.compute_dtype))
