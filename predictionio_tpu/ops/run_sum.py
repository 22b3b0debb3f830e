"""Rows summed onto their tokens by runs.

``y[t] = sum over the rows r with token[r] == t of weight[r] rows[r]``, in
float32, for a short array of rows ``[R, D]`` of which every token holds at
most ``most``: what a pass of the routed experts hands back
(``models/sequence/experts.py``). No token looks for its rows: the rows are
put in token order once (``plan``: one sort of ``R`` token ids, the rows of no
token last), so a token's rows are a run and a block of tokens' rows a range,
and one program (``sum_runs``) writes ``y`` a block of tokens at a time from
the row blocks that hold its range.

The program's grid is ``(token blocks, row blocks a token block can span)``.
Scalar prefetch hands it, for each token block, its first row block and how
many it spans; a grid step past that count does nothing and asks for the block
it already has, so nothing is moved for it. A row block against a token block
is the 0/1 matrix ``token[r] == t0 + i`` ``[TB, RB]``, and the add is a product
on the MXU: a row of a neighbouring token block in a shared row block falls
out by the compare, a token without rows gets zeros.

**The same sum.** A float32 row goes in as three bfloat16 terms (``hi``,
``mid``, ``lo``: 24 bits are three times 8, so the three add up to the row
exactly), each against the 0/1 matrix with float32 accumulation: every product
is exact and only the order of a token's additions is the program's. The
weight is multiplied in float32 on the VPU first, as the plain sum does. A
bfloat16 row with unit weights is one term.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from predictionio_tpu.utils.jax_compat import pallas as pl, pallas_tpu as pltpu

#: tokens and rows a block (my chip runs, PERF.md PR 41: blocks of 128 to 512
#: move a sum of the three cells' shapes by under 0.4 ms)
TOKEN_BLOCK = 256
ROW_BLOCK = 256
VMEM_LIMIT_BYTES = 64 << 20
#: the token of a row that belongs to none: above every token, so sorted last
NO_TOKEN = 1 << 30


class Runs(NamedTuple):
    """A pass's rows in token order, ``plan``'s."""
    perm: jax.Array      # [Rp] int32: the rows by token, those of no token last
    token: jax.Array     # [1, Rp] int32: their tokens (``NO_TOKEN``: none)
    first: jax.Array     # [token blocks] int32: the first row block of each
    count: jax.Array     # [token blocks] int32: and the row blocks it spans


def blocks_of(n: int, rows: int) -> tuple[int, int]:
    """``(TB, RB)`` for ``n`` tokens and ``rows`` rows: the two constants, or
    the whole of a shorter axis in whole sublanes / lanes."""
    return min(TOKEN_BLOCK, -(-n // 8) * 8), min(ROW_BLOCK, -(-rows // 128) * 128)


def plan(token, n: int) -> Runs:
    """``token`` [R]: the token ``0 .. n - 1`` of every row, anything else for
    a row of no token. One stable sort of ``R`` integers and a count at every
    token block's edge: no scatter."""
    r = token.shape[0]
    tb, rb = blocks_of(n, r)
    token = jnp.where((token >= 0) & (token < n), token, NO_TOKEN).astype(jnp.int32)
    token, perm = jax.lax.sort((token, jnp.arange(r, dtype=jnp.int32)), num_keys=1)
    pad = -r % rb
    token = jnp.pad(token, (0, pad), constant_values=NO_TOKEN)
    perm = jnp.pad(perm, (0, pad))
    edges = tb * jnp.arange(-(-n // tb) + 1, dtype=jnp.int32)
    starts = jnp.searchsorted(token, edges, method="compare_all").astype(jnp.int32)
    lo, hi = starts[:-1], starts[1:]
    first = jnp.minimum(lo // rb, token.shape[0] // rb - 1)
    count = jnp.where(hi > lo, -(-hi // rb) - first, 0)
    return Runs(perm, token[None, :], first, count)


def _kernel(first_ref, count_ref, token_ref, weight_ref, rows_ref, out_ref, acc_ref, *,
            tb: int, unit: bool, terms: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < count_ref[i])
    def _():
        rb = rows_ref.shape[0]
        mine = token_ref[...] == i * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, rb), 0)
        mine = mine.astype(jnp.float32).astype(jnp.bfloat16)                  # [TB, RB]
        weight = weight_ref[...]                                              # [RB, 1]
        left = rows_ref[...].astype(jnp.float32)
        if not unit:
            left = left * weight
        # what a grouped matmul left in a row of no token goes no further
        left = jnp.where(weight != 0, left, 0.0)
        for _ in range(terms):
            term = left.astype(jnp.bfloat16)
            acc_ref[...] += jnp.dot(mine, term, preferred_element_type=jnp.float32)
            left = left - term.astype(jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def sum_runs(rows, weight, runs: Runs, n: int, most: int, *, unit: bool,
             out_dtype=jnp.float32, interpret: bool = False):
    """``y`` [n, D] ``out_dtype``: each token's sum of its ``rows`` [R, D]
    times their ``weight`` [R] (0 for a row of no token), added in float32.
    ``most``: the most rows a token holds. ``unit``: the weights are 0 and 1
    alone, so a bfloat16 row is one exact term."""
    (tb, rb), d = blocks_of(n, rows.shape[0]), rows.shape[-1]
    token_blocks, row_blocks = runs.first.shape[0], runs.perm.shape[0] // rb
    live = runs.token[0] != NO_TOKEN
    weight = jnp.where(live, weight.astype(jnp.float32)[runs.perm], 0.0)[:, None]
    terms = 1 if unit and rows.dtype == jnp.bfloat16 else 3

    def row_block(i, j, first, count):
        return first[i] + jnp.minimum(j, jnp.maximum(count[i] - 1, 0))

    y = pl.pallas_call(
        functools.partial(_kernel, tb=tb, unit=unit, terms=terms),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # a token block's rows are one range: it spans a block more than it fills
            grid=(token_blocks, min(-(-tb * most // rb) + 1, row_blocks)),
            in_specs=[
                pl.BlockSpec((1, rb), lambda i, j, *s: (0, row_block(i, j, *s))),
                pl.BlockSpec((rb, 1), lambda i, j, *s: (row_block(i, j, *s), 0)),
                pl.BlockSpec((rb, d), lambda i, j, *s: (row_block(i, j, *s), 0)),
            ],
            out_specs=pl.BlockSpec((tb, d), lambda i, j, *s: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tb, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((token_blocks * tb, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(runs.first, runs.count, runs.token, weight, rows[runs.perm])
    return y[:n]
