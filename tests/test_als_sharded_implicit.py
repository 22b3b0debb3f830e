"""The implicit half-step at ALX's width on a data=2 x model=2 mesh, the row
chunks a block is worked in, and the rule that decides them.

The reference here is the repo's plain statement of the implicit half-step
(Hu, Koren, Volinsky 2008) in ``jax.numpy`` float32 at ``highest`` matmul
precision: no packer, no buckets, no mesh. CPU, the 8 host devices that
``conftest.py`` forces.
"""

import ml_dtypes
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.parallel import als
from predictionio_tpu.parallel.als import (
    ALSConfig, als_fit, block_paths, block_plan, build_als_data, make_iteration)
from predictionio_tpu.parallel.mesh import local_mesh

RANK, ALPHA, REG = 128, 40.0, 0.1


def reference_half_step(own, other, plays, table, n_rows):
    """``(Y'Y + sum_obs alpha r y y' + reg I) x = sum_obs (1 + alpha r) y``
    for every row of one side, float32, ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(table, jnp.float32)
        y = table[other]                                   # [E, K]
        weight = ALPHA * jnp.asarray(plays, jnp.float32)   # [E]
        outer = jnp.einsum("ek,e,ej->ekj", y, weight, y)
        fix = jax.ops.segment_sum(outer, own, num_segments=n_rows)
        rhs = jax.ops.segment_sum(y * (1.0 + weight)[:, None], own, num_segments=n_rows)
        gram = table.T @ table + REG * jnp.eye(table.shape[1]) + fix
        return np.asarray(jnp.linalg.solve(gram, rhs[..., None])[..., 0])


def relative_error(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def plays():
    """300 users x 200 songs, every pair at most once, play counts >= 1 with
    a heavy tail, and seeded N(0, 1/sqrt(K)) tables for both sides. Eight
    users have 80 songs or more and six songs 90 listeners or more, the
    others at most 32: packed in two buckets a side, the long one is solved
    in the primal form and the short one (24 and 32 slots against rank 128)
    in the dual form, so every program here holds both."""
    rng = np.random.default_rng(26)
    n_users, n_items = 300, 200
    pairs = np.unique(np.concatenate(
        [rng.choice(n_users * n_items, size=4000, replace=False)]
        + [u * n_items + rng.choice(n_items, size=80, replace=False) for u in range(8)]
        + [rng.choice(n_users, size=90, replace=False) * n_items + i for i in range(6)]))
    rng.shuffle(pairs)
    users, items = pairs // n_items, pairs % n_items
    counts = np.minimum(rng.zipf(2.25, size=pairs.size), 500).astype(np.float32)
    tables = [rng.standard_normal((n, RANK)).astype(np.float32) / np.sqrt(RANK)
              for n in (n_users, n_items)]
    return n_users, n_items, users, items, counts, tables


def _one_iteration(plays, dtype, mesh_shape, sharding, budget=None):
    """One call of ``make_iteration``'s program on the fixture's state:
    ``(users out, items out)`` in original order, float32."""
    n_users, n_items, users, items, counts, (u0, v0) = plays
    d, m = mesh_shape
    mesh = local_mesh(d, m)
    config = ALSConfig(rank=RANK, implicit=True, alpha=ALPHA, reg=REG, dtype=dtype,
                       buckets=2, factor_sharding=sharding)
    data = build_als_data(users, items, counts, n_users, n_items, config,
                          num_shards=d, model_shards=m)
    if budget is not None:
        als.EINSUM_GATHER_BUDGET_BYTES = budget
    als._build_iteration.cache_clear()  # the rule is asked as a program is traced
    paths = block_paths(data, config, mesh)

    def slotted(side, table):
        out = np.zeros((side.total_slots, RANK), np.float32)
        out[side.slot_of] = table
        return jnp.asarray(out, jnp.dtype(dtype))

    put = lambda a: jnp.asarray(a)  # noqa: E731
    uf, vf = make_iteration(mesh, config)(
        als.device_put_blocks(data.by_row, put), als.device_put_blocks(data.by_col, put),
        slotted(data.by_row, u0), slotted(data.by_col, v0),
        jnp.float32(REG), jnp.float32(ALPHA))
    return (np.asarray(uf, np.float32)[data.by_row.slot_of],
            np.asarray(vf, np.float32)[data.by_col.slot_of], paths)


@pytest.fixture(autouse=True)
def _the_budget_is_put_back(monkeypatch):
    monkeypatch.setattr(als, "EINSUM_GATHER_BUDGET_BYTES", als.EINSUM_GATHER_BUDGET_BYTES)
    yield
    als._build_iteration.cache_clear()


#: float32 tables: program and reference both solve in float32, systems whose
#: condition number reaches 1e4 (confidences up to 40 x 500 on a ridge of
#: 0.1), with their float32 sums in different orders (the Gram over a row's
#: edges, Y'Y over the table's rows in two shards): 2e-5 to 2.1e-4 of the norm
#: was read. bfloat16 tables: the reference starts from the same
#: bfloat16-rounded table, so what is left is the rounding of each solved row
#: to 8 bits of mantissa, 2**-9 at most an element, 1.7e-3 of the norm read
#: here and on the chip (PERF.md section 2). float8_e4m3fn has 3 bits: 2**-4
#: an element, 3e-2 to 8e-2 of the norm, over five times either limit.
TOLERANCE = {"float32": 1e-3, "bfloat16": 4e-3}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sharded_implicit_iteration_against_the_reference(plays, dtype):
    n_users, n_items, users, items, counts, (u0, v0) = plays
    got_u, got_v, paths = _one_iteration(plays, dtype, (2, 2), "model")
    assert paths["blocks"] == 4 and paths["dual_solve"] == 2
    stored = lambda a: np.asarray(jnp.asarray(a, jnp.dtype(dtype)), np.float32)  # noqa: E731
    want_u = reference_half_step(users, items, counts, stored(v0), n_users)
    want_v = reference_half_step(items, users, counts, got_u, n_items)
    assert relative_error(got_u, want_u) < TOLERANCE[dtype]
    assert relative_error(got_v, want_v) < TOLERANCE[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fp8_storage_is_outside_the_tolerance(plays, dtype):
    """The same half-step with the table and the solved rows stored one
    precision lower than the program can be asked for: it has to fail."""
    n_users, _, users, items, counts, (_, v0) = plays
    fp8 = lambda a: np.asarray(a, np.float32).astype(  # noqa: E731
        ml_dtypes.float8_e4m3fn).astype(np.float32)
    want = reference_half_step(users, items, counts, v0, n_users)
    low = fp8(reference_half_step(users, items, counts, fp8(v0), n_users))
    assert relative_error(low, want) > 5 * TOLERANCE[dtype]


@pytest.mark.parametrize("mesh_shape,sharding", [
    ((1, 1), "replicated"), ((2, 1), "replicated"), ((2, 2), "replicated"),
    ((2, 2), "model"),
], ids=["one_device", "data2", "data2_model2_replicated", "data2_model2_sharded"])
def test_a_chunked_block_equals_the_block_worked_whole(plays, mesh_shape, sharding):
    """Rows are independent: under a budget so small that the blocks are
    worked in several row chunks, each row comes out bit for bit as from the
    block whole. float32 tables, every layout."""
    whole_u, whole_v, whole = _one_iteration(plays, "float32", mesh_shape, sharding)
    assert whole["chunked"] == 0 and whole["max_chunks"] == 1 and whole["dual_solve"] == 2
    cut_u, cut_v, cut = _one_iteration(plays, "float32", mesh_shape, sharding,
                                       budget=1 << 20)
    assert cut["chunked"] >= 3 and cut["max_chunks"] >= 3  # of 4 blocks
    assert np.array_equal(cut_u, whole_u) and np.array_equal(cut_v, whole_v)


def test_two_by_two_equals_one_device(plays):
    """Three iterations of ``als_fit``, implicit, rank 128: tables sharded
    over data=2 x model=2 against one device. Sums over a table's rows and
    over a row's edges are ordered differently, nothing else."""
    n_users, n_items, users, items, counts, _ = plays
    models = []
    for (d, m), sharding in (((1, 1), "replicated"), ((2, 2), "model")):
        config = ALSConfig(rank=RANK, iterations=3, implicit=True, alpha=ALPHA, reg=REG,
                           buckets=2, factor_sharding=sharding, seed=5)
        data = build_als_data(users, items, counts, n_users, n_items, config,
                              num_shards=d, model_shards=m)
        models.append(als_fit(data, config, local_mesh(d, m)))
    one, four = models
    assert relative_error(four.user_factors, one.user_factors) < 1e-3
    assert relative_error(four.item_factors, one.item_factors) < 1e-3


GIB = 1 << 30
#: (rows on one data shard, pad_len, rank, itemsize, model shards[, implicit])
#: -> row chunks
RULE = {
    # als-ml20m-r16.train-steady's eight blocks (PERF.md section 4): whole
    **{f"ml20m_r16_{rows}x{length}": ((rows, length, 16, 2, 1), 1)
       for rows, length in [(35_312, 256), (22_872, 152), (28_696, 88), (51_632, 48),
                            (7_648, 256), (2_224, 144), (3_840, 64), (13_048, 16)]},
    # the same blocks at rank 128: the rows take the blocked solve, at most
    # 4,096 of them in a chunk (64 KiB of Gram a row: the bytes alone ask 2)
    "ml20m_r128_35312x256": ((35_312, 256, 128, 2, 1), 9),
    "ml20m_r128_51632x48": ((51_632, 48, 128, 2, 1), 13),
    "r128_125000x24": ((125_000, 24, 128, 2, 1), 31),
    # als-msd-r128.train-sharded's eight blocks (PERF.md section 4), a data
    # shard's rows, two model shards: each device solves half of them. The
    # cell is implicit, so the four short blocks are dual (PR 29): on a TPU a
    # dual block goes 4,096 solved rows a chunk too, so the chunks are the same
    **{f"msd_{side}_{rows}x{length}": ((rows, length, 128, 2, 2, True), chunks)
       for side, rows, length, chunks in [
           ("users", 39_680, 256, 5), ("users", 85_152, 136, 11),
           ("users", 153_696, 56, 19), ("users", 231_168, 24, 29),
           ("songs", 34_656, 256, 5), ("songs", 14_512, 128, 2),
           ("songs", 38_496, 48, 5), ("songs", 104_640, 16, 13)]},
    # a dual block at rank 16 (8 slots: a fold-in's short histories)
    "r16_dual_100000x8": ((100_000, 8, 16, 4, 1, True), 25),
    # the unrolled solve (rank <= 32) holds a copy of the Gram with the rows
    # on the lanes, and the chip pads a 32-wide row of either to 128 lanes;
    # its rows are not capped
    "r32_unrolled": ((1_000_000, 8, 32, 4, 1), 7),
    # the template's default ML-1M item block, 45.3 GB of gathered rows:
    # 344 rows a chunk, 3.91 GiB of them
    "ml1m_template_default": ((3_712, 23_832, 16, 4, 1), 11),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule(case):
    shape, want = RULE[case]
    assert block_plan("tpu", *shape) == want


def test_the_rule_counts_gathered_rows_grams_and_factors():
    """... and, since PR 27, what each solve path holds beside the Gram
    (``ops.linalg.solve_gram_arrays``, read from a compile for a described
    v5e): the blocked solve as much again and a quarter, the unrolled one a
    lane-padded copy, LAPACK's the factor. Rows on the blocked solve are cut
    to ``ops.linalg.BLOCKED_SOLVE_ROWS`` a chunk as well."""
    from predictionio_tpu.ops.linalg import BLOCKED_SOLVE_ROWS

    rows, pad_len = 125_000, 24
    gathered = als.gathered_bytes(rows, pad_len, 128, 2)
    assert gathered == rows * pad_len * 256
    assert als.normal_equation_bytes(rows, 128, unroll=True) == int(rows * 2.25 * 65_536)
    assert als.normal_equation_bytes(rows, 16, unroll=True) == int(rows * 1_024 * 1.35 * 8)
    assert als.normal_equation_bytes(rows, 128, unroll=False) == rows * 2 * 65_536
    # off the TPU the bytes decide: LAPACK's batches are not capped
    total = gathered + rows * 2 * 65_536
    assert block_plan("cpu", rows, pad_len, 128, 2) == -(-total // (4 * GIB)) == 4
    # on it, above rank 32, the rows a device solves in a chunk are
    assert block_plan("tpu", rows, pad_len, 128, 2) == -(-rows // BLOCKED_SOLVE_ROWS)
    assert block_plan("tpu", rows, pad_len, 128, 2, model_shards=2) == 16
    # ... unless the bytes ask for more: 4,096 rows of 4,096 slots are 4 GiB gathered
    assert block_plan("tpu", 4_096, 4_096, 128, 2) == 2
    assert block_plan("cpu", 35_312, 256, 16, 2) == 1


@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
def test_the_fold_in_asks_the_same_rule(plays, implicit):
    """``online.foldin.fold_in_users`` solves its touched rows through
    ``block_plan`` too: in chunks under a small budget, the same rows."""
    from predictionio_tpu.online import foldin

    n_users, _, users, items, counts, (_, v0) = plays
    config = ALSConfig(rank=RANK, implicit=implicit, alpha=ALPHA, reg=REG)
    args = (v0, users, items, counts, n_users, config)
    whole = foldin.fold_in_users(*args)
    als.EINSUM_GATHER_BUDGET_BYTES = 1 << 20
    rows, pad_len = 512, 128  # the pow2 ladder over 300 users with at most 93 songs
    assert block_plan("cpu", rows, pad_len, RANK, 4, implicit=implicit) > 3
    assert np.array_equal(foldin.fold_in_users(*args), whole)
