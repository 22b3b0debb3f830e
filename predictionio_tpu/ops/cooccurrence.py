"""Item-item cooccurrence + LLR scoring kernels.

TPU-native replacement for the similar-product template's cooccurrence logic
and the Universal Recommender's correlated cross-occurrence (CCO) with
log-likelihood-ratio scoring (community template, Mahout CCO -- SURVEY.md
section 2.5 #37, BASELINE.json configs #3/#4).

Design: cooccurrence is a matmul. With the user-history one-hot matrix
``A [users, items]``, the cooccurrence of primary events with event-type-t
events is ``A_primary^T @ A_t`` -- the MXU's favorite shape. Only the
compact padded-CSR ``(indices, mask)`` ever leaves the host; the dense
one-hot chunks are scattered ON DEVICE inside a ``lax.scan`` (an earlier
host-built-chunk version shipped the dense [chunk, items] f32 blocks to
the device -- ~4 GB for 2M events, ~40x the CSR's footprint). The ``[items, items]`` accumulator lives on device;
LLR is then elementwise.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops.ragged import PaddedCSR
from predictionio_tpu.parallel.mesh import cached_by_mesh
from predictionio_tpu.utils.jax_compat import pcast_varying, shard_map


def _dense_onehot(indices, mask, num_cols: int):
    """Binarized dense [rows, num_cols] from padded-CSR rows (jittable;
    scatter-add then clamp, sentinel column dropped) -- the ONE definition
    both the host-streamed and mesh paths build their matmuls from."""
    rows = indices.shape[0]
    row_ids = jnp.repeat(jnp.arange(rows), indices.shape[1])
    out = jnp.zeros((rows, num_cols + 1), dtype=jnp.float32)
    out = out.at[row_ids, indices.reshape(-1)].add(mask.reshape(-1))
    return jnp.minimum(out[:, :num_cols], 1.0)


def _normalize(primary: PaddedCSR, other: PaddedCSR | None, mesh):
    """Shared preamble of both entry points: resolve self-cooccurrence,
    validate the shared user universe, default to a 1-device local mesh
    (same on-device path, degenerate psum)."""
    other = other if other is not None else primary
    if primary.num_rows != other.num_rows:
        raise ValueError(
            f"CSRs must share the user universe: {primary.num_rows} vs {other.num_rows}"
        )
    if mesh is None or "data" not in mesh.axis_names:
        from predictionio_tpu.parallel.mesh import local_mesh

        mesh = local_mesh(1, 1)
    return other, mesh


def cooccurrence(
    primary: PaddedCSR,
    other: PaddedCSR | None = None,
    chunk: int = 4096,
    mesh=None,
) -> np.ndarray:
    """``A_primary^T @ A_other`` over shared user rows -> [items_p, items_o].

    ``other=None`` means self-cooccurrence. Both CSRs must be row-indexed by
    the same user universe (same num_rows). User rows shard over the mesh's
    ``data`` axis (a 1-device local mesh when none is given): each device
    scatters its local users' one-hot chunks on device and accumulates
    their contribution (fixed-size chunks keep the dense buffers bounded),
    and one final ``psum`` combines the per-device ``[items_p, items_o]``
    partials over ICI -- the Spark-shuffle aggregation of the reference's
    cooccurrence jobs as a single collective.
    """
    other, mesh = _normalize(primary, other, mesh)
    return _cooccurrence_mesh(primary, other, chunk, mesh)


def _pad_rows_sentinel(csr: PaddedCSR, rows: int) -> tuple[np.ndarray, np.ndarray]:
    """(indices, mask) grown to ``rows`` rows; padding rows carry the
    sentinel column with mask 0, so they contribute nothing."""
    pad = rows - csr.indices.shape[0]
    indices = np.pad(csr.indices, ((0, pad), (0, 0)), constant_values=csr.num_cols)
    mask = np.pad(csr.mask, ((0, pad), (0, 0)))
    return indices, mask


@cached_by_mesh(maxsize=64)
def _build_cooc_fn(
    mesh,
    chunk: int,
    num_p: int,
    num_o: int,
    len_p: int,
    len_o: int,
    top_k: int,
    llr: bool,
    drop_diagonal: bool,
    total: float,
):
    """The jitted sharded cooccurrence program, cached by every static it
    closes over (a fresh closure per call would retrace + recompile each
    of URAlgorithm's per-event-type calls and every re-train). ``top_k ==
    0`` returns the raw replicated accumulator; otherwise the (optionally
    LLR-weighted) per-row top-k indicators, computed ON DEVICE so the
    [items, items] matrix never crosses the host link. The LLR totals are
    runtime ARGUMENTS (replicated), not baked constants, so one compiled
    program serves every event type of the same shape.
    """
    from jax.sharding import PartitionSpec

    def local(idx_p, msk_p, idx_o, msk_o, row_t, col_t):
        n_chunks = idx_p.shape[0] // chunk

        def body(acc, args):
            i_p, m_p, i_o, m_o = args
            return (
                acc
                + _dense_onehot(i_p, m_p, num_p).T
                @ _dense_onehot(i_o, m_o, num_o),
                None,
            )

        def split(a):
            return a.reshape(n_chunks, chunk, a.shape[1])

        # fresh constants are "unvarying" under shard_map's vma tracking;
        # the scan carry must match the (varying) body output type
        acc0 = pcast_varying(
            jnp.zeros((num_p, num_o), dtype=jnp.float32), "data"
        )
        acc, _ = jax.lax.scan(
            body, acc0, (split(idx_p), split(msk_p), split(idx_o), split(msk_o))
        )
        acc = jax.lax.psum(acc, "data")
        if top_k == 0:
            return acc
        m = _llr_math(acc, row_t, col_t, total) if llr else acc
        if drop_diagonal:
            m = jnp.where(jnp.eye(num_p, dtype=bool), -jnp.inf, m)
        vals, idx = jax.lax.top_k(m, top_k)
        return idx.astype(jnp.int32), jnp.where(jnp.isfinite(vals), vals, 0.0)

    row = PartitionSpec("data")
    rep = PartitionSpec()
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(row, row, row, row, rep, rep),
            out_specs=rep if top_k == 0 else (rep, rep),
        )
    )


def _run_cooc(
    primary: PaddedCSR,
    other: PaddedCSR,
    chunk: int,
    mesh,
    *,
    top_k: int = 0,
    llr: bool = False,
    drop_diagonal: bool = False,
    total: float = 0.0,
    row_totals=None,
    col_totals=None,
):
    """Pad, upload (once per distinct CSR), run the cached program, fetch.

    Accepts ``ShardedPaddedCSR`` inputs (parallel.reader): each process
    then contributes only its local user-row slice via
    make_array_from_process_local_data instead of uploading a full host
    copy -- the retention-bounded multi-host path.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from predictionio_tpu.parallel.reader import ShardedPaddedCSR, cooc_global_rows

    data_size = int(mesh.shape["data"])
    sharded = isinstance(primary, ShardedPaddedCSR)
    if sharded != isinstance(other, ShardedPaddedCSR):
        raise ValueError(
            "mixing a sharded-reader CSR with a full host CSR is not "
            "supported: build both sides sharded (or neither)"
        )
    if sharded:
        rows = primary.global_rows
        expect = cooc_global_rows(primary.num_rows, mesh, chunk)
        if rows != expect or other.global_rows != rows:
            raise ValueError(
                f"sharded CSR was built for a different mesh/chunk layout "
                f"(rows {rows}/{other.global_rows}, this call expects "
                f"{expect}); rebuild with build_cooc_csr_sharded(mesh=..., "
                f"chunk={chunk})"
            )
        per_device = rows // data_size
        chunk = max(1, min(chunk, per_device))
    else:
        # base row math on the PHYSICAL (row_multiple-padded) CSR rows, not
        # num_rows: pack_padded_csr rounds rows up, and a target below the
        # physical count would make _pad_rows_sentinel's pad width negative
        phys_rows = max(primary.indices.shape[0], other.indices.shape[0])
        per_device = -(-phys_rows // data_size)
        chunk = max(1, min(chunk, per_device))
        # every device scans the same number of fixed-size chunks: pad the
        # user universe so rows = data * chunks_per_device * chunk
        chunks_per_device = -(-per_device // chunk)
        rows = data_size * chunks_per_device * chunk
    fn = _build_cooc_fn(
        mesh, chunk, primary.num_cols, other.num_cols,
        primary.max_len, other.max_len,
        top_k, llr, drop_diagonal, float(total),
    )
    from predictionio_tpu.parallel.mesh import fetch_global, put_global

    sharding = NamedSharding(mesh, PartitionSpec("data"))
    rep = NamedSharding(mesh, PartitionSpec())
    if sharded:
        put_local = lambda a, L: jax.make_array_from_process_local_data(
            sharding, a, (rows, L)
        )
        g_idx_p = put_local(primary.local.indices, primary.max_len)
        g_msk_p = put_local(primary.local.mask, primary.max_len)
        if other is primary:
            g_idx_o, g_msk_o = g_idx_p, g_msk_p
        else:
            g_idx_o = put_local(other.local.indices, other.max_len)
            g_msk_o = put_local(other.local.mask, other.max_len)
    else:
        put = lambda a: put_global(a, sharding)
        idx_p, msk_p = _pad_rows_sentinel(primary, rows)
        g_idx_p, g_msk_p = put(idx_p), put(msk_p)
        if other is primary:  # self-cooccurrence: one upload serves both
            g_idx_o, g_msk_o = g_idx_p, g_msk_p
        else:
            idx_o, msk_o = _pad_rows_sentinel(other, rows)
            g_idx_o, g_msk_o = put(idx_o), put(msk_o)
    dummy = np.zeros(1, np.float32)
    row_t = jax.device_put(
        np.asarray(row_totals if row_totals is not None else dummy, np.float32),
        rep,
    )
    col_t = jax.device_put(
        np.asarray(col_totals if col_totals is not None else dummy, np.float32),
        rep,
    )
    out = fn(g_idx_p, g_msk_p, g_idx_o, g_msk_o, row_t, col_t)
    return jax.tree_util.tree_map(fetch_global, out)


def _cooccurrence_mesh(primary: PaddedCSR, other: PaddedCSR, chunk: int, mesh):
    return _run_cooc(primary, other, chunk, mesh)


def distinct_user_counts(csr: PaddedCSR) -> np.ndarray:
    """Per-item distinct-user count in O(nnz) on the host -- the diagonal of
    the (binarized) self-cooccurrence, without the [items, items] matmul."""
    rows = np.repeat(np.arange(csr.indices.shape[0]), csr.max_len)
    cols = csr.indices.reshape(-1)
    valid = (csr.mask.reshape(-1) > 0) & (cols < csr.num_cols)
    pairs = np.unique(
        rows[valid].astype(np.int64) * csr.num_cols + cols[valid].astype(np.int64)
    )
    return np.bincount(
        (pairs % csr.num_cols).astype(np.int64), minlength=csr.num_cols
    ).astype(np.float32)


def _xlogx(x):
    return jnp.where(x > 0, x * jnp.log(x), 0.0)


def _llr_math(k11, row_totals, col_totals, total):
    """G^2 log-likelihood-ratio over the 2x2 contingency per (i, j) pair."""
    k12 = jnp.maximum(row_totals[:, None] - k11, 0.0)
    k21 = jnp.maximum(col_totals[None, :] - k11, 0.0)
    k22 = jnp.maximum(total - k11 - k12 - k21, 0.0)
    h_k = _xlogx(k11) + _xlogx(k12) + _xlogx(k21) + _xlogx(k22)
    h_rows = _xlogx(k11 + k12) + _xlogx(k21 + k22)
    h_cols = _xlogx(k11 + k21) + _xlogx(k12 + k22)
    h_total = _xlogx(k11 + k12 + k21 + k22)
    llr = 2.0 * (h_k + h_total - h_rows - h_cols)
    return jnp.where(k11 > 0, jnp.maximum(llr, 0.0), 0.0)


_llr_kernel = jax.jit(_llr_math)


def llr_scores(
    cooc: np.ndarray,
    row_totals: np.ndarray,
    col_totals: np.ndarray,
    total: float,
) -> np.ndarray:
    """LLR significance of each cooccurrence count (same shape as cooc)."""
    return np.asarray(
        _llr_kernel(
            jnp.asarray(cooc, dtype=jnp.float32),
            jnp.asarray(row_totals, dtype=jnp.float32),
            jnp.asarray(col_totals, dtype=jnp.float32),
            float(total),
        )
    )


def cooccurrence_indicators(
    primary: PaddedCSR,
    other: PaddedCSR | None = None,
    *,
    top_k: int,
    llr_row_totals: np.ndarray | None = None,
    llr_col_totals: np.ndarray | None = None,
    total: float | None = None,
    drop_diagonal: bool | None = None,
    chunk: int = 4096,
    mesh=None,
):
    """Fused cooc -> (optional LLR) -> per-row top-k, entirely on device.

    Returns ``(indices [items_p, k], values [items_p, k])`` like
    :func:`top_k_sparsify`. Providing ``llr_row_totals``/``llr_col_totals``
    (+ ``total``) applies the G^2 weighting before ranking. The unfused
    chain fetches the [items_p, items_o] matrix to the host TWICE (once
    after cooccurrence, once into top_k_sparsify) -- ~800 MB at 10k items
    -- where the fused form downloads only the [items_p, k] indicator
    arrays.

    Ties may rank in a different order than the host ``argpartition`` path;
    the selected VALUES are identical.
    """
    self_cooc = other is None or other is primary
    other, mesh = _normalize(primary, other, mesh)
    if (llr_row_totals is None) != (llr_col_totals is None):
        raise ValueError("provide both llr totals or neither")
    if llr_row_totals is not None and total is None:
        raise ValueError("LLR weighting needs the grand total")
    if drop_diagonal is None:
        drop_diagonal = self_cooc
    if drop_diagonal and primary.num_cols != other.num_cols:
        raise ValueError("drop_diagonal requires a square matrix")
    idx, vals = _run_cooc(
        primary,
        other,
        chunk,
        mesh,
        top_k=min(top_k, other.num_cols),
        llr=llr_row_totals is not None,
        drop_diagonal=drop_diagonal,
        total=float(total or 0.0),
        row_totals=llr_row_totals,
        col_totals=llr_col_totals,
    )
    return np.asarray(idx), np.asarray(vals)


def top_k_sparsify(matrix: np.ndarray, k: int, drop_diagonal: bool = True):
    """Keep the top-k entries per ROW -> (indices [n, k], values [n, k]).

    The serving-side 'indicator' form (reference UR keeps top-N correlators
    per item in Elasticsearch)."""
    m = matrix.copy()
    if drop_diagonal and m.shape[0] == m.shape[1]:
        np.fill_diagonal(m, -np.inf)
    k = min(k, m.shape[1])
    idx = np.argpartition(-m, k - 1, axis=1)[:, :k]
    vals = np.take_along_axis(m, idx, axis=1)
    order = np.argsort(-vals, axis=1)
    idx = np.take_along_axis(idx, order, axis=1)
    vals = np.take_along_axis(vals, order, axis=1)
    vals = np.where(np.isfinite(vals), vals, 0.0)
    return idx.astype(np.int32), vals.astype(np.float32)
