"""Pallas kernel: fused all-items NeuMF scoring for one user.

The serving hot path scores EVERY item for a user (then top-k). Done naively
that is four HBM-bound passes (gmf mult, concat, two dense layers). This
kernel fuses the whole NeuMF head over item tiles resident in VMEM:

    score[i] = w_out . [gmf_u * gmf_item[i] ; mlp(mlp_u ++ mlp_item[i])]

Item embedding tables stream through VMEM in (TILE_I, E) blocks; the user's
vectors and the MLP weights (small) are broadcast to every grid step. One
HBM read of the tables per query -> bandwidth-bound at the theoretical
minimum. On CPU test backends the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.utils.jax_compat import pallas as pl
from predictionio_tpu.utils.platform import note_kernel

logger = logging.getLogger("pio.ncf")

#: the kernel's name in ``device_report``, the compiled program and a
#: profiler trace
KERNEL_NAME = "ncf_score_all_items"

# 1024 = XLA's tile for 1-D f32 arrays (8 sublanes x 128 lanes): the
# kernel's output block must match it exactly -- real TPU lowering rejects
# a T(512) Mosaic layout against XLA's T(1024) (interpret mode cannot see
# the mismatch), and 2-D (1, TILE) output blocks fail the (8, 128)
# divisibility rule
TILE_I = 1024


def _ncf_score_kernel(
    gmf_item_ref,  # [TILE_I, E]
    mlp_item_ref,  # [TILE_I, E]
    gmf_user_ref,  # [1, E]
    mlp_user_ref,  # [1, E]
    w0u_ref,       # [E, H0]   (user half of the first MLP kernel)
    w0i_ref,       # [E, H0]   (item half)
    b0_ref,        # [1, H0]
    w1_ref,        # [H0, H1]
    b1_ref,        # [1, H1]
    wog_ref,       # [1, E]    (output weights, gmf part)
    woh_ref,       # [1, H1]   (output weights, mlp part)
    bo_ref,        # [1, 1]
    out_ref,       # [TILE_I]
):
    # f32 weights and activations at "highest": at the MXU's default the
    # operands are rounded to bf16 and the scores come out 5e-3 off the
    # NumPy head on the chip (chip_smoke.py, PR 21). The kernel is bound by
    # the one read of the item tables, not by these small matmuls.
    dot = functools.partial(
        jnp.dot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    gmf = gmf_item_ref[:] * gmf_user_ref[0][None, :]
    # first dense over the concat == split matmul (avoids concat in VMEM)
    h = (
        dot(mlp_user_ref[:], w0u_ref[:])
        + dot(mlp_item_ref[:], w0i_ref[:])
        + b0_ref[0][None, :]
    )
    h = jnp.maximum(h, 0.0)
    h = jnp.maximum(dot(h, w1_ref[:]) + b1_ref[0][None, :], 0.0)
    # final projections as multiply+reduce (VPU) -- a [., 1] matmul would
    # fight the 128-lane tiling for no gain
    score = (
        jnp.sum(gmf * wog_ref[0][None, :], axis=1)
        + jnp.sum(h * woh_ref[0][None, :], axis=1)
        + bo_ref[0, 0]
    )
    out_ref[:] = score


def _mlp_depth(params) -> int:
    return len([k for k in params if k.startswith("mlp_") and k[4:].isdigit()])


def score_call(padded: int, e: int, h0: int, h1: int, interpret: bool):
    """The ``pallas_call`` over ``padded`` items (a ``TILE_I`` multiple) for
    embedding width ``e`` and hidden widths ``(h0, h1)``."""
    tile_spec = lambda: pl.BlockSpec((TILE_I, e), lambda i: (i, 0))
    rep = lambda r, c: pl.BlockSpec((r, c), lambda i: (0, 0))
    return pl.pallas_call(
        _ncf_score_kernel,
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.float32),
        grid=(padded // TILE_I,),
        in_specs=[
            tile_spec(),
            tile_spec(),
            rep(1, e),
            rep(1, e),
            rep(e, h0),
            rep(e, h0),
            rep(1, h0),
            rep(h0, h1),
            rep(1, h1),
            rep(1, e),
            rep(1, h1),
            rep(1, 1),
        ],
        out_specs=pl.BlockSpec((TILE_I,), lambda i: (i,)),
        interpret=interpret,
        name=KERNEL_NAME,
    )


def make_all_items_scorer(params, num_items: int, interpret: bool):
    """Build a host-callable ``score(user_index) -> np.ndarray[num_items]``.

    The item tables and MLP weights upload to the device ONCE at build
    time, and each call is a single jitted dispatch (the user-row gather
    runs on device) plus one result fetch. The per-call construction this
    replaces re-uploaded ~13 operands and re-dispatched eagerly per query.

    The kernel is specialized to the default 2-hidden-layer tower; other
    depths serve through the (XLA-fused anyway) reference head, and say
    so once, here.
    """
    if _mlp_depth(params) != 2:
        logger.warning(
            "NCF all-items scorer: the Pallas kernel covers 2 hidden layers,"
            " this model has %d; serving through the XLA reference head",
            _mlp_depth(params),
        )
        return lambda user_index: reference_score_all_items(
            params, user_index, num_items
        )
    note_kernel(KERNEL_NAME, interpret)
    e = params["gmf_user"]["embedding"].shape[1]
    h0 = params["mlp_0"]["kernel"].shape[1]
    h1 = params["mlp_1"]["kernel"].shape[1]

    gmf_items = np.asarray(params["gmf_item"]["embedding"], np.float32)
    mlp_items = np.asarray(params["mlp_item"]["embedding"], np.float32)
    padded = ((num_items + TILE_I - 1) // TILE_I) * TILE_I
    if padded != gmf_items.shape[0]:
        pad = padded - gmf_items.shape[0]
        gmf_items = np.pad(gmf_items, ((0, pad), (0, 0)))
        mlp_items = np.pad(mlp_items, ((0, pad), (0, 0)))

    w0 = np.asarray(params["mlp_0"]["kernel"], np.float32)   # [2E, H0]
    out_w = np.asarray(params["out"]["kernel"], np.float32)  # [E+H1, 1]
    device = jax.devices()[0] if not interpret else None
    put = (lambda a: jax.device_put(jnp.asarray(a), device)) if device else jnp.asarray
    gmf_items_d = put(gmf_items)
    mlp_items_d = put(mlp_items)
    gmf_user_tab = put(np.asarray(params["gmf_user"]["embedding"], np.float32))
    mlp_user_tab = put(np.asarray(params["mlp_user"]["embedding"], np.float32))
    weights = (
        put(w0[:e]),
        put(w0[e:]),
        put(np.asarray(params["mlp_0"]["bias"], np.float32)[None, :]),
        put(np.asarray(params["mlp_1"]["kernel"], np.float32)),
        put(np.asarray(params["mlp_1"]["bias"], np.float32)[None, :]),
        put(np.asarray(out_w[:e, 0])[None, :]),
        put(np.asarray(out_w[e:, 0])[None, :]),
        put(np.asarray(params["out"]["bias"], np.float32).reshape(1, 1)),
    )

    call = score_call(padded, e, h0, h1, interpret)

    @jax.jit
    def score(user_idx):
        gmf_u = jax.lax.dynamic_slice_in_dim(gmf_user_tab, user_idx, 1)
        mlp_u = jax.lax.dynamic_slice_in_dim(mlp_user_tab, user_idx, 1)
        return call(gmf_items_d, mlp_items_d, gmf_u, mlp_u, *weights)

    return lambda user_index: np.asarray(score(np.int32(user_index)))[:num_items]


def ncf_score_all_items(params, user_index: int, num_items: int, interpret: bool):
    """One-shot convenience around :func:`make_all_items_scorer` (tests,
    oracles). Serving paths should build the scorer once and reuse it."""
    return make_all_items_scorer(params, num_items, interpret)(user_index)


def make_batch_scorer(params, num_items: int, pair_budget: int = 2_000_000):
    """Host-callable ``scores(user_indices [U]) -> np [U, num_items]``.

    The ``pio batchpredict`` engine of NCF: one jitted device call scores a
    whole chunk of users against the full catalog (the reference's
    P2LAlgorithm broadcast-batchPredict parallelism as a single XLA
    program), instead of one 2-round-trip dispatch per query. Works for
    ANY tower depth (plain jnp forward, not the depth-2 Pallas kernel).
    Chunks are sized so the [U, I, feature] intermediates stay bounded
    (~``pair_budget`` user-item pairs per call); the python-visible
    function accepts any U and slices internally.
    """
    depth = _mlp_depth(params)
    dev_params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a, np.float32)), dict(params)
    )

    @jax.jit
    # f32 matmuls at "highest", like the Pallas scorer: batched and single
    # answers stay the same numbers on a TPU too (its default rounds the
    # operands to bf16)
    @jax.default_matmul_precision("highest")
    def chunk_scores(user_idx):                              # [u] -> [u, I]
        gmf_u = dev_params["gmf_user"]["embedding"][user_idx]     # [u, E]
        mlp_u = dev_params["mlp_user"]["embedding"][user_idx]
        gmf_i = dev_params["gmf_item"]["embedding"][:num_items]   # [I, E]
        mlp_i = dev_params["mlp_item"]["embedding"][:num_items]
        u, e = gmf_u.shape
        gmf = gmf_u[:, None, :] * gmf_i[None, :, :]               # [u, I, E]
        h = jnp.concatenate(
            [
                jnp.broadcast_to(mlp_u[:, None, :], (u, num_items, e)),
                jnp.broadcast_to(mlp_i[None, :, :], (u, num_items, e)),
            ],
            axis=-1,
        )
        for layer in range(depth):
            h = jnp.maximum(
                h @ dev_params[f"mlp_{layer}"]["kernel"]
                + dev_params[f"mlp_{layer}"]["bias"],
                0.0,
            )
        fused = jnp.concatenate([gmf, h], axis=-1)
        return (
            fused @ dev_params["out"]["kernel"] + dev_params["out"]["bias"]
        )[..., 0]

    chunk = max(1, pair_budget // max(num_items, 1))

    def bucket(n: int) -> int:
        # pad ragged calls to the next power of two, not to the full
        # chunk: offline bulk runs still see the one big chunk shape, but
        # a serving micro-batch of 16 must not pay a 400-row program.
        # Compiled-shape count stays bounded at log2(chunk).
        b = 1
        while b < n:
            b <<= 1
        return min(b, chunk)

    def scores(user_indices) -> np.ndarray:
        user_indices = np.asarray(user_indices, np.int32)
        out = np.empty((user_indices.size, num_items), np.float32)
        for start in range(0, user_indices.size, chunk):
            part = user_indices[start : start + chunk]
            n = part.size
            pad = bucket(n)
            if n < pad:
                part = np.pad(part, (0, pad - n))
            out[start : start + n] = np.asarray(
                chunk_scores(jnp.asarray(part))
            )[:n]
        return out

    return scores


def reference_score_all_items(params, user_index: int, num_items: int) -> np.ndarray:
    """Plain-numpy NeuMF head for ANY tower depth (kernel oracle + CPU path)."""
    gmf_u = np.asarray(params["gmf_user"]["embedding"][user_index])
    mlp_u = np.asarray(params["mlp_user"]["embedding"][user_index])
    gmf_i = np.asarray(params["gmf_item"]["embedding"][:num_items])
    mlp_i = np.asarray(params["mlp_item"]["embedding"][:num_items])
    gmf = gmf_i * gmf_u
    h = np.concatenate([np.broadcast_to(mlp_u, mlp_i.shape), mlp_i], axis=1)
    for layer in range(_mlp_depth(params)):
        h = np.maximum(
            h @ np.asarray(params[f"mlp_{layer}"]["kernel"])
            + np.asarray(params[f"mlp_{layer}"]["bias"]),
            0.0,
        )
    fused = np.concatenate([gmf, h], axis=1)
    return (
        fused @ np.asarray(params["out"]["kernel"]) + np.asarray(params["out"]["bias"])
    )[:, 0]
