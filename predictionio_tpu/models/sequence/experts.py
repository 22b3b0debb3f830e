"""The routed experts of the sequence template's expert backbones
(``sparse_moe``, ``hybrid``, ``latent_moe``, ``window_moe``, ``cca_moe``): a
router's choice worked as grouped matmuls over the experts this program holds,
beside a shared expert every token takes where the layer has one.

A router is ``route(c, u, p, real) -> (experts, gates, stats)``. Two things a
router may do beyond that (``cca_moe``'s does both):

- **a choice beyond** ``num_experts``. ``experts`` may hold indexes from
  ``num_experts`` up: choices that no program of any chip holds, whose output
  is zero (a token that skips the layer's experts). Such an assignment is in no
  pass, adds nothing and moves no expert's gradient; the router counts it
  (``load_of(..., choices=)``) and reports it beside the experts' own counts
  (``skip_assignments``: ``counts`` gives ``moe_skip_assignments``), so
  ``assignments`` stays the assignments to experts;
- **a carry**. With ``carry`` given, ``moe`` and ``expert_half`` call
  ``route(c, u, p, real, carry)``, the router's state of the layer before, and
  the router returns its own state fourth, which they hand back last: a second
  value that travels from layer to layer beside the residual stream.

``experts_held = (lo, hi)`` names the experts this program holds, as one chip
of an expert-parallel deployment does: the router is whole (every chip routes
its tokens over all the experts), the expert weights are ``hi - lo`` of them,
and a layer adds the held experts' part of the sum. The other chips' parts
are theirs to add: nothing here stands in for them, and with every expert
held the layer is the whole layer. Of an expert-parallel deployment's shares
each adds the shared expert's part; it is counted once when shares are added up.

How the held experts are worked: a token's assignments to held experts are
sorted by expert and worked as grouped matmuls (``jax.lax.ragged_dot``) over
exactly those rows: no capacity, no token dropped. Every row array is as long
as a static bound ``R`` (``pass_plan``: twice the held experts' even share of
the tokens worked at once, within ``MOE_CHUNK_BYTES``), not as the worst case:
pass ``p`` works the sorted rows ``[p R, (p + 1) R)``, and a pass past the last
held row is skipped at run time (``lax.cond``), forward and backward. An even
router takes one pass a layer, a skewed one as many as it needs, and with
every expert held the bound is the worst case. Rows come from their tokens by
a gather and go back by a gather too (a scatter of rows cost the chip more
than the whole of this, PERF.md PR 33). Where the package's programs run
(``sum_path``: the platform decides, as for attention) a pass's ``R`` rows are
gathered once into token order and one program adds each token's run
(``ops/run_sum.py``, PERF.md PR 41): the same float32 sum of float32 rows times
float32 gates. Elsewhere a token sums, by the position the sort gave each of
its ``K`` assignments, its rows of the pass's short array
(``sum_by_position``, which the tests hold the program to). The experts' part
keeps its operands alone and is worked again in the backward pass. The
router's matmul, its softmax or sigmoid, top-k and gates are float32.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from predictionio_tpu.models.sequence import blocks
from predictionio_tpu.ops import run_sum

#: Device scopes of an expert layer's second half, ``seq.pass1/layers/moe``:
#: the router under ``route``, the held experts under ``experts``, the shared
#: expert under ``shared``.
SCOPE_MOE = "moe"
SCOPE_ROUTE = "route"
SCOPE_EXPERTS = "experts"
SCOPE_SHARED = "shared"
#: Leaves under ``moe/experts``, by class of operation: ``sort`` the held test, the two
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

#: the most float32 bytes the held experts' output rows of one pass may take
MOE_CHUNK_BYTES = 256 << 20
#: a pass's rows over the held experts' even share of the tokens worked at
#: once: PERF.md PR 33 read 0.99 to 1.02 of that share a step over 14 seeds
#: (single experts up to 1.58 of theirs, PR 32; the sum over those held is
#: steadier than any one); a router that sends more takes further passes
MOE_ROWS_OVER_EVEN = 2

#: a router's bias that no gradient reaches: the leaf no optimizer touches
BIAS = "router_bias"

#: engine parameter -> field, for the backbones' own tables
ENGINE_PARAMS = {"expertDim": "expert_dim", "numExperts": "num_experts",
                 "expertsPerToken": "experts_per_token", "expertsHeld": "experts_held"}


@dataclass(frozen=True)
class ExpertsConfig(blocks.DecoderConfig):
    """A decoder's configuration with routed experts of which the program
    holds a share."""

    expert_dim: int = 32
    num_experts: int = 8
    experts_per_token: int = 2
    experts_held: tuple = (0, 8)      # [lo, hi) of the experts: this program's share
    moe_chunk: int | None = None      # None: from MOE_CHUNK_BYTES; tokens a chunk

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "experts_held", tuple(int(e) for e in self.experts_held))
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"experts_held={self.experts_held}: want 0 <= lo < hi <= num_experts="
                f"{self.num_experts}")
        if not 1 <= self.experts_per_token <= self.num_experts:
            raise ValueError(
                f"experts_per_token={self.experts_per_token}: want 1 .. num_experts")

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def sum_path(c, backend: str) -> str:
    """How a pass's rows come back onto their tokens: ``"runs"`` where the
    package's programs run (``ops/run_sum.py``), else ``"positions"``."""
    return "runs" if blocks.uses_kernels(c, backend) else "positions"


def fit_attrs(c, platform: str, backward_heads_per_step: int, shared: bool) -> dict:
    """The experts' part of the fit's span, and how the layer's attention goes
    backward where the package's programs run."""
    return {
        "experts_total": c.num_experts, "experts_held": c.held,
        "experts_per_token": c.experts_per_token, **({"experts_shared": 1} if shared else {}),
        "moe_sum": sum_path(c, platform),
        # the backward pass of a layer's attention: one program where the
        # package's programs run, the plain twin's transpose elsewhere
        "attention_backward_programs": int(blocks.uses_kernels(c, platform)),
        "attention_backward_heads_per_step": backward_heads_per_step,
    }


def pass_plan(c, n: int) -> tuple[int, int]:
    """``(R, passes)`` for ``n`` tokens worked at once, from static shapes: a
    pass works ``R`` sorted rows, ``MOE_ROWS_OVER_EVEN`` times the held experts'
    even share in whole 128s and no more than the worst case (every token's
    ``min(K, held)`` slots held); the passes cover that worst case."""
    worst = n * min(c.experts_per_token, c.held)
    share = -(-MOE_ROWS_OVER_EVEN * n * c.experts_per_token * c.held // c.num_experts)
    bound = min(-(-share // 128) * 128, worst)
    return bound, -(-worst // bound)


def moe_chunk_of(c) -> int:
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


def sum_by_position(rows, pos, weight):
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


def sum_by_runs(rows, weight, runs, shape, interpret, *, unit, dtype=jnp.float32):
    """``sum_by_position``'s sum for ``shape = (n, K)`` with the weights by
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
        d_u = sum_by_position(g, pos, mine.astype(jnp.float32)).astype(g.dtype)
    else:
        d_u = sum_by_runs(g, live[:, 0], runs, pos.shape, interpret, unit=True, dtype=g.dtype)
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
        return sum_by_position(out, pos, jnp.where(mine, gates, 0.0))
    by_row = jnp.where(live[:, 0], gates.reshape(-1)[row], 0.0)
    return sum_by_runs(out, by_row, runs, pos.shape, interpret, unit=False)


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


def experts_chunk(c, interpret, w_gate, w_up, w_down, u, experts, gates,
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


def load_of(c, experts, real, choices: int | None = None):
    """The assignments of real tokens to every expert ``[E]`` under a router's
    choice ``experts`` [N, K]; with ``choices``, to every one of a router's
    ``choices`` (the experts first, then those that no program holds)."""
    chosen = (experts[..., None] == jnp.arange(choices or c.num_experts)) & real[:, None, None]
    return chosen.sum(axis=(0, 1))


def load_stats(c, load) -> dict:
    """A layer's counts of its ``load`` [E]: every assignment, those to held
    experts, the most one held expert takes. A ``load`` longer than
    ``num_experts`` also says how many took a choice that is no expert."""
    lo, hi = c.experts_held
    held_load = load[lo:hi]
    beyond = {}
    if load.shape[0] > c.num_experts:
        load, past = load[:c.num_experts], load[c.num_experts:]
        beyond = {"skip_assignments": past.sum()}
    return {"assignments": load.sum(), "held_assignments": held_load.sum(),
            "held_load_max": held_load.max(), **beyond}


def route(c, u, p, real):
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


def sigmoid_route(c, u, p, real, rows: int, bias=None):
    """``(experts, gates, stats)`` of a router that scores by sigmoid, for the
    normed tokens ``u`` [N, D] of ``rows`` rows: the ``experts_per_token``
    largest of the scores (plus ``bias`` [E] where the layer has one, which no
    gradient reaches), their gates from the scores alone, renormalised and
    times ``c.routed_scale``, and the layer's counts under ``route``'s names
    with the whole ``load`` [E] beside them; ``aux`` is the balance loss, a
    row at a time: ``sum_e f_e P_e``, ``f_e = E / (K T_r)`` times the row's
    real positions that chose ``e``, ``P_e`` the row's mean of
    ``s_e / sum_j s_j``, the mean over the rows."""
    scores = jax.nn.sigmoid(jnp.matmul(u, p["router"], precision=jax.lax.Precision.HIGHEST))
    ranked = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    chosen = jax.lax.top_k(ranked, c.experts_per_token)[1]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = c.routed_scale * picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    by_row = lambda a: a.reshape(rows, -1, *a.shape[1:])  # noqa: E731
    on = by_row(real)
    takes = (by_row(chosen)[..., None] == jnp.arange(c.num_experts)) & on[..., None, None]
    row_load = takes.sum(axis=(1, 2))                                    # [rows, E]
    count = jnp.maximum(on.sum(axis=1), 1).astype(jnp.float32)[:, None]
    share = by_row(scores / scores.sum(axis=-1, keepdims=True))
    mean_share = jnp.where(on[..., None], share, 0.0).sum(axis=1) / count
    often = row_load.astype(jnp.float32) * (c.num_experts / c.experts_per_token) / count
    load = row_load.sum(axis=0)
    return chosen, gates, {"aux": (often * mean_share).sum(axis=-1).mean(),
                            **load_stats(c, load), "load": load}


def held_experts(c, backend: str, u, p, experts, gates, real, stats):
    """``(y, stats)``: the held experts' part of the routed sum for the normed
    tokens ``u`` [N, D] under a router's choice (``experts``, ``gates``
    [N, K]), ``stats`` gaining what the passes did and what their forward sums
    read. A padded slot (``real`` [N]) is routed nowhere."""
    dtype = jnp.dtype(c.compute_dtype)
    n = u.shape[0]
    chunk = min(moe_chunk_of(c), n)
    by_runs = sum_path(c, backend) == "runs"
    work = jax.checkpoint(functools.partial(
        experts_chunk, c, (backend != "tpu") if by_runs else None,
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


def moe(c, backend: str, u, p, real, route=route, carry=None):
    """``(y, stats)``: the held experts' part of the routed sum for the normed
    tokens ``u`` [N, D], and the layer's counts. ``backend`` is the platform
    the layer runs on, which decides how a pass's rows come back
    (``sum_path``). ``route(c, u, p, real)`` is
    the layer's router, under ``moe/route`` (this backbone's and the hybrid's
    is the softmax ``_route``; the latent backbone brings its own); the held
    experts' work under ``moe/experts`` is the same for all. ``real`` [N]: a
    padded slot is routed nowhere and counts nowhere. With a ``carry`` [N, R]
    the router takes it fifth and its own comes back third."""
    with jax.named_scope(SCOPE_ROUTE):
        experts, gates, stats, *carried = route(
            c, u, p, real, *(() if carry is None else (carry,)))
    with jax.named_scope(SCOPE_EXPERTS):
        return (*held_experts(c, backend, u, p, experts, gates, real, stats), *carried)

def shared_expert(u, p, dtype):
    """The shared expert every token takes, ``u`` [N, D]: a SwiGLU of the
    layer's ``s_gate``, ``s_up``, ``s_down``, behind a sigmoid gate where the
    layer's parameters hold ``s_g``."""
    inner = jax.nn.silu(blocks.matmul(u, p["s_gate"], dtype)) * blocks.matmul(u, p["s_up"], dtype)
    if "s_g" not in p:
        return blocks.matmul(inner, p["s_down"], dtype)
    gate = jax.nn.sigmoid(jnp.matmul(u, p["s_g"], precision=jax.lax.Precision.HIGHEST))
    return gate[:, None] * blocks.matmul(inner, p["s_down"], dtype)


def expert_half(c, backend: str, x, p, real, norm=blocks.rms_norm, route=route,
                carry=None, merge=jnp.add):
    """``(x', stats)``: a layer's second half on the residual stream ``x``
    [B, T, D] with the layer's parameters ``p``: ``norm`` by ``n2``, the held
    routed experts' part and, where ``p`` holds one, the shared expert added to
    ``x`` (``merge(x, y)`` where the layer merges otherwise). ``real`` [B, T].
    With a ``carry`` [B, T, R], the router's state of the layer before, the
    router's own comes back third."""
    with jax.named_scope(SCOPE_MOE):
        with jax.named_scope(blocks.SCOPE_NORM):
            u = norm(x, p["n2"], c.rms_eps)
        flat = u.reshape(-1, u.shape[-1])
        y, stats, *carried = moe(c, backend, flat, p, real.reshape(-1), route,
                                 None if carry is None else carry.reshape(-1, carry.shape[-1]))
        if "s_gate" in p:
            with jax.named_scope(SCOPE_SHARED):
                y = y + shared_expert(flat, p, jnp.dtype(c.compute_dtype))
        return (merge(x, y.reshape(x.shape)), stats,
                *(r.reshape(*x.shape[:-1], -1) for r in carried))


def trained_labels(params) -> dict:
    """``"train"`` or ``"fixed"`` for every leaf: a router's bias (``BIAS``) is
    fixed as far as the optimizer goes (a backbone's ``move`` moves it)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: "fixed" if path[-1].key == BIAS else "train", params)


def bias_step(rate: float, load):
    """What a step adds to a router's bias for the ``load`` [..., choices] it
    counted: ``rate`` up for a choice under the even load, down for one over."""
    return rate * jnp.sign(load.mean(axis=-1, keepdims=True) - load)


def counts(c, stats) -> dict:
    """The scalars a loss reports of its expert layers, from their stacked
    ``stats`` ``[layers with a router, ...]``."""
    held = stats["held_assignments"].sum()
    beyond = ({"moe_skip_assignments": stats["skip_assignments"].sum()}
              if "skip_assignments" in stats else {})
    return {
        "moe_assignments": stats["assignments"].sum(),
        "moe_held_assignments": held,
        "moe_held_load_max": stats["held_load_max"].max(),
        "moe_held_load_mean": held / (stats["assignments"].shape[0] * c.held),
        "moe_dropped": stats["dropped"].sum(),
        "moe_passes": stats["passes"].sum(),
        "moe_passes_run": stats["passes_run"].sum(),
        "moe_sum_rows": stats["sum_rows"].sum(),
        "moe_sum_slots": stats["sum_slots"].sum(),
        **beyond,
    }
