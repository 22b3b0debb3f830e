"""The gated delta rule for TPU, worked in chunks: linear attention whose
state a row carries from its first position to its last.

For one value head, with keys and queries of width ``dk`` and values of width
``dv``, the recurrence is (``S_0 = 0`` in ``R^{dk x dv}``, ``t = 1..T``)

    S <- exp(g_t) S;  u = S^T k_t;  S <- S + k_t (beta_t (v_t - u))^T;  o_t = S^T q_t

(Yang, Kautz and Hatamizadeh, "Gated Delta Networks", arXiv 2412.06464). A
position with ``beta = 0`` and ``g = 0`` leaves the state as it is.

:func:`gated_delta_rule` works it ``C`` positions at a time (the WY / UT form).
With ``G_i`` the running sum of ``g`` inside a chunk and ``S`` the state the
chunk starts from:

- ``L = tril(diag(beta) K K^T o exp(G_i - G_j), -1)``, ``T = (I + L)^{-1}``
  (:func:`unit_lower_inverse`: the ``C x C`` lower-triangular system, as a
  product of ``log2 C`` factors ``I + (-L)^(2^j)``, float32 at ``highest``);
- ``W = T (diag(beta exp(G)) K)``, ``U = T (diag(beta) V)``;
- ``V' = U - W S``; ``O = diag(exp(G)) Q S + tril(Q K^T o exp(G_i - G_j)) V'``;
- ``S <- exp(G_C) S + (diag(exp(G_C - G)) K)^T V'``.

Everything but the third and fifth line's ``S`` is worked for all chunks at
once, as batched matmuls. What walks a row is the **state pass**: ``V'`` and
the state each chunk starts from, chunk after chunk. On a TPU it is a Pallas
program (:func:`state_pass`) with a second program for its transpose, which
walks the chunks backwards from the kept starting states; elsewhere
:func:`state_pass_plain`, a ``lax.scan`` that JAX differentiates itself.

A grid step of either program works one chunk of a **block of row-heads**:
grid ``(row-heads / G, chunks)``, the ``G`` states (or carried cotangents) in
VMEM scratch across the block's chunks. A TensorCore runs its grid steps one
after another, and a row-head's chunk is a chain (``V'`` needs ``W S``, the
next state needs ``V'``; the transpose is four products in a chain of three),
so a step of one row-head leaves the matrix units waiting on a single chain
and pays a step's fixed cost for two small products. The ``G`` row-heads of a
block are independent: the body is a static loop over them, each head's dots,
dtypes and order of chunks what they are alone, for the scheduler to overlap.
``G`` is 8, 4, 2 or 1 by :func:`heads_per_step`, from the shapes alone: the
most that divide the row-heads and keep the transpose program's blocks,
double-buffered, within ``PASS_VMEM_BYTES``.

Matmul inputs are ``dtype`` (bfloat16) with float32 accumulation; ``g``, its
sums and exponentials, ``beta``, ``L``, ``T`` and the state are float32. Every
exponent is a difference ``G_i - G_j`` with ``i >= j`` or ``G_i`` itself, so
none is positive: nothing overflows whatever the decay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from predictionio_tpu.utils.jax_compat import pallas as pl, pallas_tpu as pltpu

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, dtype):
    """Batched ``a @ b``: inputs cast to ``dtype``, accumulated in float32."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32)


# ---- the triangular system ---------------------------------------------------

@jax.custom_vjp
def unit_lower_inverse(lower):
    """``(I + L)^{-1}`` for strictly lower-triangular ``L`` ``[..., C, C]``.
    ``L`` is nilpotent, so the inverse is ``sum_k (-L)^k = prod_j (I + (-L)^(2^j))``
    over ``ceil(log2 C)`` factors: matmuls, no substitution loop."""
    size = lower.shape[-1]
    power = -lower
    inverse = jnp.eye(size, dtype=lower.dtype) + power
    for _ in range(max(size - 1, 1).bit_length() - 1):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    return inverse


def _inverse_bwd(inverse, g):
    # d(I + L)^{-1} = -T dL T, so the cotangent of L is -T' g T'
    t = jnp.swapaxes(inverse, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, g, precision=_HIGHEST), t, precision=_HIGHEST),)


def _inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ---- the state pass ----------------------------------------------------------

def state_pass_plain(w, u, kd, decay):
    """``(V', S)``: for a row-head's chunks ``c`` in order, from ``S_0 = 0``,
    ``V'_c = U_c - W_c S_c`` and ``S_{c+1} = decay_c S_c + Kd_c^T V'_c``.
    ``w``, ``kd`` ``[R, N, C, dk]``, ``u`` ``[R, N, C, dv]``, ``decay``
    ``[R, N]`` -> ``V'`` ``[R, N, C, dv]`` and the states the chunks start from
    ``[R, N, dk, dv]``. The state is float32 along the row; the matmuls take
    ``w``'s dtype, and both results leave in it: matmul inputs are all they
    become."""
    dtype = w.dtype

    def chunk(state, at):
        w_c, u_c, kd_c, decay_c = at
        v_new = u_c - _mm(w_c, state, dtype)
        after = decay_c[:, None, None] * state + _mm(jnp.swapaxes(kd_c, -1, -2), v_new, dtype)
        return after, (v_new.astype(dtype), state.astype(dtype))

    rows, _, _, dk = w.shape
    start = jnp.zeros((rows, dk, u.shape[-1]), jnp.float32)
    by_chunk = tuple(jnp.moveaxis(a, 1, 0) for a in (w, u, kd, decay))
    v_new, states = jax.lax.scan(chunk, start, by_chunk)[1]
    return jnp.moveaxis(v_new, 0, 1), jnp.moveaxis(states, 0, 1)


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(a, b, (((contract_a,), (contract_b,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pass_kernel(w_ref, u_ref, kd_ref, decay_ref, v_ref, start_ref, state):
    """One chunk of a block of row-heads, each an independent chain."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    for h in range(w_ref.shape[0]):
        w, kd, s = w_ref[h, 0], kd_ref[h, 0], state[h]
        low = s.astype(w.dtype)
        start_ref[h, 0] = low
        v_new = (u_ref[h, 0] - _dot(w, low, 1, 0)).astype(w.dtype)
        v_ref[h, 0] = v_new
        state[h] = decay_ref[h, 0] * s + _dot(kd, v_new, 0, 0)


def _pass_bwd_kernel(w_ref, kd_ref, decay_ref, v_ref, start_ref, dv_ref, dstart_ref,
                     dw_ref, du_ref, dkd_ref, ddecay_ref, carried):
    """The transpose, chunks last to first; ``carried`` is the cotangent of
    the state a chunk leaves, a row-head of the block each."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        carried[...] = jnp.zeros(carried.shape, jnp.float32)

    for h in range(w_ref.shape[0]):
        w, kd, s, d_after = w_ref[h, 0], kd_ref[h, 0], start_ref[h, 0], carried[h]
        low = w.dtype
        d_v = dv_ref[h, 0].astype(jnp.float32) + _dot(kd, d_after.astype(low), 1, 0)  # of V'_c, in all
        du_ref[h, 0] = d_v
        dkd_ref[h, 0] = _dot(v_ref[h, 0], d_after.astype(low), 1, 1).astype(low)
        dw_ref[h, 0] = (-_dot(d_v.astype(low), s, 1, 1)).astype(low)
        ddecay_ref[h, 0] = jnp.full(ddecay_ref.shape[2:], jnp.sum(d_after * s), jnp.float32)
        carried[h] = (dstart_ref[h, 0].astype(jnp.float32) + decay_ref[h, 0] * d_after
                      - _dot(w, d_v.astype(low), 0, 0))


#: what the transpose program's blocks, double-buffered, may take of VMEM
PASS_VMEM_BYTES = 4 * 2 ** 20


def heads_per_step(rows: int, c: int, dk: int, dv: int, itemsize: int) -> int:
    """Row-heads a grid step works: 8, 4, 2 or 1, the most that divide the
    ``rows`` row-heads and keep the transpose program's blocks (the larger of
    the two programs': ``w``, ``kd``, ``dw``, ``dkd``; ``V'``, its cotangent
    and ``dU`` in float32; the starting state and its cotangent; two scalars'
    lanes), double-buffered, within ``PASS_VMEM_BYTES``."""
    a_head = (4 * c * dk + 2 * c * dv + 2 * dk * dv) * itemsize + (c * dv + 2 * dv) * 4
    for heads in (8, 4, 2):
        if rows % heads == 0 and 2 * heads * a_head <= PASS_VMEM_BYTES:
            return heads
    return 1


def _pass_specs(w, dv: int, backwards: bool):
    """``(specs, grid, scratch)`` of either program for ``w`` ``[R, N, C, dk]``:
    blocks of :func:`heads_per_step` row-heads and one chunk, the chunks in
    order or last to first, a float32 ``[dk, dv]`` of scratch a head."""
    rows, chunks, c, dk = w.shape
    heads = heads_per_step(rows, c, dk, dv, w.dtype.itemsize)
    at = (lambda r, n: (r, chunks - 1 - n, 0, 0)) if backwards else (lambda r, n: (r, n, 0, 0))
    specs = {"k": pl.BlockSpec((heads, 1, c, dk), at), "v": pl.BlockSpec((heads, 1, c, dv), at),
             "state": pl.BlockSpec((heads, 1, dk, dv), at),
             "scalar": pl.BlockSpec((heads, 1, 1, dv), at)}
    return specs, (rows // heads, chunks), [pltpu.VMEM((heads, dk, dv), jnp.float32)]


_PASS_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def state_pass(w, u, kd, decay, interpret=False):
    """The Pallas form of :func:`state_pass_plain`, with its transpose."""
    return _pass_fwd(w, u, kd, decay, interpret)[0]


def _lanes(decay, dv: int):
    """A chunk's scalar as a row of ``dv`` lanes: ``[R, N] -> [R, N, 1, dv]``."""
    return jnp.broadcast_to(decay[:, :, None, None], decay.shape + (1, dv))


def _pass_fwd(w, u, kd, decay, interpret):
    rows, chunks, _, dk = w.shape
    dv = u.shape[-1]
    sp, grid, scratch = _pass_specs(w, dv, backwards=False)
    v_new, starts = pl.pallas_call(
        _pass_kernel,
        grid=grid,
        in_specs=[sp["k"], sp["v"], sp["k"], sp["scalar"]],
        out_specs=[sp["v"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(u.shape, w.dtype),
                   jax.ShapeDtypeStruct((rows, chunks, dk, dv), w.dtype)],
        scratch_shapes=scratch,
        compiler_params=_PASS_PARAMS,
        interpret=interpret,
    )(w, u.astype(jnp.float32), kd, _lanes(decay.astype(jnp.float32), dv))
    return (v_new, starts), (w, kd, decay, v_new, starts)


def _pass_bwd(interpret, res, cotangents):
    w, kd, decay, v_new, starts = res
    d_v, d_starts = cotangents
    rows, chunks = w.shape[:2]
    dv = v_new.shape[-1]
    sp, grid, scratch = _pass_specs(w, dv, backwards=True)
    d_w, d_u, d_kd, d_decay = pl.pallas_call(
        _pass_bwd_kernel,
        grid=grid,
        in_specs=[sp["k"], sp["k"], sp["scalar"], sp["v"], sp["state"], sp["v"], sp["state"]],
        out_specs=[sp["k"], sp["v"], sp["k"], sp["scalar"]],
        out_shape=[jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(v_new.shape, jnp.float32),
                   jax.ShapeDtypeStruct(kd.shape, kd.dtype),
                   jax.ShapeDtypeStruct((rows, chunks, 1, dv), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=_PASS_PARAMS,
        interpret=interpret,
    )(w, kd, _lanes(decay.astype(jnp.float32), dv), v_new, starts, d_v, d_starts)
    return d_w, d_u, d_kd, d_decay[:, :, 0, 0].astype(decay.dtype)


state_pass.defvjp(_pass_fwd, _pass_bwd)


# ---- the rule ---------------------------------------------------------------

def _chunks(x, heads: int, chunk: int):
    """``[B, T, h, ...] -> [B x heads, N, C, ...]``: heads first (each of the
    ``h`` heads repeated for the ``heads // h`` it serves), time in chunks, the
    tail padded with zeros (a position that neither moves nor reads the state)."""
    b, t, h = x.shape[:3]
    x = jnp.pad(x, ((0, 0), (0, -t % chunk)) + ((0, 0),) * (x.ndim - 2))
    x = jnp.repeat(jnp.moveaxis(x, 2, 1), heads // h, axis=1)
    return x.reshape(b * heads, -1, chunk, *x.shape[3:])


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK, dtype=jnp.bfloat16,
                     kernels: bool = False, interpret: bool = False):
    """``o`` ``[B, T, HV, dv]`` float32 of the recurrence above for ``q``, ``k``
    ``[B, T, HK, dk]`` (key head ``j`` serves value heads ``j HV / HK ..``),
    ``v`` ``[B, T, HV, dv]``, ``g`` (log decay, ``<= 0``) and ``beta``
    ``[B, T, HV]``. ``kernels``: the state pass as the Pallas programs.

    ``q``, ``k`` and ``v`` enter every product as ``dtype``, so they are cast
    once, here; a row's or a column's factor (``beta``, ``exp(G)``) is applied
    to the ``C x C`` side of a product, not to a copy of the ``[C, d]`` side."""
    b, t, heads, dv = v.shape
    q, k, v = (_chunks(a.astype(dtype), heads, chunk) for a in (q, k, v))
    g, beta = (_chunks(a.astype(jnp.float32), heads, chunk) for a in (g, beta))
    total = jnp.cumsum(g, axis=-1)                                       # G_i, [R, N, C]
    gap = total[..., :, None] - total[..., None, :]                      # G_i - G_j
    at = jnp.arange(chunk)
    below, upto = at[:, None] > at[None, :], at[:, None] >= at[None, :]
    k_t = jnp.swapaxes(k, -1, -2)
    lower = jnp.where(below, _mm(k, k_t, dtype) * beta[..., None]
                      * jnp.exp(jnp.where(below, gap, 0.0)), 0.0)
    inverse = unit_lower_inverse(lower)
    w = _mm(inverse * (beta * jnp.exp(total))[..., None, :], k, dtype).astype(dtype)
    u = _mm(inverse * beta[..., None, :], v, dtype)
    last = total[..., -1]
    kd = (k * jnp.exp(last[..., None] - total)[..., None]).astype(dtype)
    v_new, starts = (state_pass(w, u, kd, jnp.exp(last), interpret) if kernels
                     else state_pass_plain(w, u, kd, jnp.exp(last)))
    pairs = jnp.where(upto, _mm(q, k_t, dtype) * jnp.exp(jnp.where(upto, gap, 0.0)), 0.0)
    out = jnp.exp(total)[..., None] * _mm(q, starts, dtype) + _mm(pairs, v_new, dtype)
    out = out.reshape(b, heads, -1, dv)[:, :, :t]
    return jnp.moveaxis(out, 1, 2)
