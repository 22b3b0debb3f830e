"""Compile the main path's kernels for a described TPU v5e, without the chip.

The only test file that describes the chip. The TPU compiler is installed
here and compiles for a ``v5e:2x2`` that is described and not attached, so
what Mosaic or the Pallas lowering would refuse on the machine with the chip
(a slice not aligned to the tiling, a block shape the lowering does not
admit) is refused here, where it costs no chip time. Interpret-mode tests
cannot see any of it. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, every xdist worker
imports every test file, and only the worker that is given this file may
load it. Keep every described-chip test in THIS file.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_instructions(text: str, name: str) -> list[str]:
    """The custom calls of ``text`` that carry the kernel's name: what a
    profiler trace prints for them (``<name>.<n> tpu_custom_call``)."""
    import re

    return re.findall(
        rf"%({re.escape(name)}(?:\.\d+)?) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text,
    )


#: sha256 of ``_without_names`` of the four sequence cells' compiled steps, the
#: first two as PR 36's tree compiled them for the described v5e (jax 0.9.0,
#: libtpu 0.0.34). A change that shares code with them (PR 37: the experts'
#: passes, the attention programs that gained a mode without a mask) leaves
#: both programs as they were, instruction for instruction; one that means to
#: change a step re-measures its cell and writes the new digest here (PR 41:
#: the sparse cell's, whose experts' rows come back by runs; the looped one's
#: is PR 36's still; PR 42: the sparse cell's again, whose attention's backward
#: pass is one program where it was two; PR 43: the hybrid and the latent
#: cell's, written from PR 42's tree before PR 43 moved the backbones' shared
#: pieces, so that all four held that refactor to the same programs; PR 45: the
#: hybrid cell's, whose conv, silu, mask and split are one program a phase;
#: PR 46: the sparse, the hybrid and the window cell's, written from PR 46's
#: final tree: ``ops/rope_layout.py``'s one program a phase writes their
#: attention programs' operands, rotated, scaled, cast and heads-first, where
#: XLA's passes did, and the programs read them as they lie; PR 49: the latent
#: cell's, written from PR 49's final tree: ``latent_rope_layout``'s one
#: program a phase writes its operands, the one rotary key beside every head's
#: ``k_nope``; PR 51: the looped cell's, written from PR 51's final tree: the
#: flash programs read q, k, v and write the output and the gradients as blocks
#: of the projections' ``[B, T, H x D]`` arrays and turn the rotary positions in
#: VMEM, so the step holds no rotation and no ``[B, H, T, D]`` transpose)
ACCEPTED_STEPS = {
    "ouro-2.6b-d8.train-histories":
        "215e7495f85f1ff8bdc6147ad3ac7cb72a44b6441c716f4137f1447e108a46a1",
    "keye-vl2-30b-a3b-ep8.train-lifelong-histories":
        "c987761ca7197dec6087574977898361decb40ac515715c9035640b32a936bd6",
    "qwen3-next-80b-a3b-ep16.train-lifelong-histories":
        "7e2f6d327669f7fff9c34a134263d21b4841eed263f67bc6b45fdd055b24dcd9",
    "joyai-llm-flash-ep16.train-lifelong-histories":
        "bf2313e68cc85ad6a42e92268996e253034e3a22899ea550ce40feeb466f4eeb",
    "laguna-xs2-ep16.train-lifelong-histories":
        "50a02a6db3f754ae12925d2cb7a6a53cb29f41fec8728df6ce2e50f0abcce9e5",
}


def _digest(text: str) -> str:
    import hashlib

    return hashlib.sha256(_without_names(text).encode()).hexdigest()


def _without_names(text: str) -> str:
    """The optimised HLO ``text`` with everything that only names things taken
    out, so that two programs compare instruction for instruction: each
    instruction's ``metadata``, the tables of files, functions and stack frames
    it points into, the instructions' own names (the compiler names a Pallas
    call after the innermost scope around it: ``%attention.124`` becomes
    ``%kernel.124``), numbered here by first appearance, and the source
    locations inside a Pallas program's serialised body, which is printed
    without them."""
    import base64
    import json
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body_without_locations(found) -> str:
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True   # the serialised Mosaic dialect
        with context:
            try:
                module = ir.Module.parse(base64.b64decode(found.group(1)))
            except ir.MLIRError:      # the compiler's own kernel (a ragged dot): as it is
                return found.group(0)
            printed = module.operation.get_asm(enable_debug_info=False)
        return '"body":' + json.dumps(printed)

    text = re.sub(r",? ?metadata=\{[^{}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:[^\n]+\n)*",
                  "\n", text)
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', body_without_locations, text)
    seen: dict = {}
    return re.sub(r"%[\w.\-]+", lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"), text)


@pytest.mark.parametrize("rank", [16, 128])
def test_mips_block_topk_compiles(one_chip, no_persistent_cache, rank):
    """Stage 1 of device retrieval at 1M items, default tile and top-R."""
    from predictionio_tpu.ops.mips import BLOCK_QUERIES, mips_block_topk
    from predictionio_tpu.ops.quantize import BLOCK_ITEMS

    items = 1_000_000
    padded = -(-items // BLOCK_ITEMS) * BLOCK_ITEMS
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compiled_text(
        functools.partial(
            mips_block_topk, block_topk=16, num_items=items, interpret=False
        ),
        sds((BLOCK_QUERIES, rank), jnp.float32),
        sds((padded, rank), jnp.int8),
        sds((padded // BLOCK_ITEMS, 1), jnp.float32),
    )
    assert "tpu_custom_call" in text
    assert _kernel_instructions(text, "mips_block_topk")


def test_search_program_names_its_kernel_and_its_stages(one_chip, no_persistent_cache):
    """The whole device search (stage 1, merge, exact re-rank) over 100k
    items: the kernel under its name, every stage under its scope."""
    from predictionio_tpu.ops import mips
    from predictionio_tpu.ops.quantize import BLOCK_ITEMS

    items, rank = 100_000, 16
    padded = -(-items // BLOCK_ITEMS) * BLOCK_ITEMS
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compiled_text(
        functools.partial(
            mips._search_program, block_topk=16, shortlist=512, num_items=items,
            interpret=False,
        ),
        sds((mips.BLOCK_QUERIES, rank), jnp.float32),
        sds((padded, rank), jnp.int8),
        sds((padded // BLOCK_ITEMS, 1), jnp.float32),
        sds((items, rank), jnp.float32),
    )
    (kernel,) = _kernel_instructions(text, mips.KERNEL_NAME)
    assert f"/{mips.SCOPE_STAGE1}/" in next(
        line for line in text.splitlines() if f"%{kernel} = " in line
    )
    for scope in (mips.SCOPE_STAGE1, mips.SCOPE_MERGE, mips.SCOPE_RERANK):
        assert f"/{scope}/" in text


@pytest.mark.parametrize(
    "shape,masked,rope",
    [((8, 512, 2, 32), False, False), ((4, 2048, 4, 64), False, False),
     ((32, 256, 16, 128), True, False), ((32, 256, 16, 128), True, True)],
    ids=["b8_t512_h2_d32", "b4_t2048_h4_d64", "b32_t256_h16_d128_masked",
         "b32_t256_h16_d128_masked_rope"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, no_persistent_cache, direction,
                                  shape, masked, rope):
    """The last two cases are the sequence cell's call (``ouro-2.6b-d8``: 32
    rows of 256, 16 heads of 128) with a mask: the block bounds reach the three
    programs by scalar prefetch and bound their loops from SMEM. At heads of
    128 the operands are blocks of ``[B, T, H x D]`` and a head a lane slice;
    with ``rope`` the programs turn q and k by a lane roll against the table.
    Neither way is anything q-sized transposed or copied around the programs."""
    import re

    from predictionio_tpu.models.sequence.blocks import rope_tables
    from predictionio_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v, mask=None):
        tables = rope_tables(shape[1], shape[3], 1e6) if rope else None
        return flash_attention(q, k, v, mask, True, None, False, tables)

    fn = fwd
    if direction == "bwd":
        fn = jax.grad(lambda q, k, v, mask=None: fwd(q, k, v, mask).sum(),
                      argnums=(0, 1, 2))
    qkv = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    args = (qkv, qkv, qkv)
    if masked:
        args += (jax.ShapeDtypeStruct(shape[:2], jnp.bool_, sharding=one_chip),)
    text = _compiled_text(fn, *args)
    # three programs an attention and no fourth: forward; dq; dkv
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if direction == "fwd" else 3)
    b, t, h, d = shape
    if d % 128 == 0:
        assert not re.findall(rf"= f32\[{b},(?:{t},{h},{d}|{h},{t},{d}|{t},{h * d})\]\S* "
                              r"(?:transpose|copy|fusion)\(", text)


def test_ncf_scorer_compiles(one_chip, no_persistent_cache):
    """The all-items NeuMF scorer of examples/ncf (E=32, hidden (64, 32))
    over a 27k-item catalog."""
    from predictionio_tpu.models.ncf.kernel import TILE_I, score_call

    e, h0, h1 = 32, 64, 32
    padded = -(-27_000 // TILE_I) * TILE_I
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        score_call(padded, e, h0, h1, interpret=False),
        sds(padded, e), sds(padded, e), sds(1, e), sds(1, e),
        sds(e, h0), sds(e, h0), sds(1, h0), sds(h0, h1), sds(1, h1),
        sds(1, e), sds(1, h1), sds(1, 1),
    )
    assert "tpu_custom_call" in text
    assert _kernel_instructions(text, "ncf_score_all_items")


def test_model_sharded_als_compiles_on_four_chips(topo, no_persistent_cache, worked):
    """The ALX block body (``factor_sharding="model"``) on ``Mesh(topo.devices)``
    as data=2 x model=2: a block gathers its local hits and hands each chip of
    the model pair its rows' share through an all-to-all (a ``psum_scatter`` of
    the gathered rows the compiler turns into pad + all-reduce + slice), whole
    or, under a small budget, inside the loop over its row chunks."""
    from predictionio_tpu.parallel.als import ALSConfig, block_plan, make_iteration

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    config = ALSConfig(rank=8, factor_sharding="model")
    row, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    fsh = NamedSharding(mesh, P("model"))
    rows, length = 1024, 64
    assert (block_plan("tpu", rows // 2, length, 8, 4, 2) > 1) == (worked == "chunked")

    def block():
        return ((
            jax.ShapeDtypeStruct((rows, length), jnp.int32, sharding=row),
            jax.ShapeDtypeStruct((rows, length), jnp.float32, sharding=row),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=row),
        ),)

    factors = jax.ShapeDtypeStruct((rows, 8), jnp.float32, sharding=fsh)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    text = make_iteration(mesh, config).lower(
        block(), block(), factors, factors, scalar, scalar
    ).compile().as_text()
    assert "all-to-all" in text and "tpu_custom_call" not in text
    assert (" while(" in text) == (worked == "chunked")


def _one_chip_iteration(topo, config, user_blocks, item_blocks, mesh_shape=(1, 1)):
    """``make_iteration`` for one described chip (or ``mesh_shape`` = data x
    model of them, tables sharded as ``config.factor_sharding`` says),
    compiled at the given block shapes (users and items sized by their
    blocks' rows)."""
    from predictionio_tpu.parallel.als import make_iteration

    d, m = mesh_shape
    mesh = Mesh(np.array(topo.devices[:d * m]).reshape(d, m), ("data", "model"))
    row, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    table = NamedSharding(
        mesh, P("model" if config.factor_sharding == "model" else "data"))
    dtype = jnp.dtype(config.dtype)

    def blocks(shapes):
        return tuple((
            jax.ShapeDtypeStruct((rows, length), jnp.int32, sharding=row),
            jax.ShapeDtypeStruct((rows, length), jnp.float32, sharding=row),
            jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=row),
        ) for rows, length in shapes)

    def factors(shapes):
        return jax.ShapeDtypeStruct(
            (sum(rows for rows, _ in shapes), config.rank), dtype, sharding=table)

    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return make_iteration(mesh, config).lower(
        blocks(user_blocks), blocks(item_blocks), factors(user_blocks),
        factors(item_blocks), scalar, scalar,
    ).compile()


def test_the_rank_128_solve_is_blocked_and_holds_what_the_rule_counts(
    topo, no_persistent_cache
):
    """``als-msd-r128.train-sharded``'s largest user block (79,360 x 256,
    implicit, rank 128, bf16 tables over ``model``) on data=2 x model=2:
    neither of ``lax.linalg.cholesky`` + ``cho_solve``'s custom calls is left
    in the rows' solves (the one 128 x 128 factorisation under ``assemble/
    yty`` is the whitening matrix of the item half-step, whose 16-slot block
    is dual; the user half-step's block is primal and drops its own as dead
    code), and its temporaries stay within what ``block_plan``
    counted for one of the block's 5 chunks (3,968 rows a device to solve:
    its gathered rows, its Grams, what the blocked solve holds beside them),
    and a third more: the exchange over ``model`` holds the gathered rows
    twice (PERF.md section 7)."""
    from predictionio_tpu.parallel import als

    config = als.ALSConfig(rank=128, implicit=True, alpha=40.0, reg=0.1,
                           dtype="bfloat16", factor_sharding="model")
    rows, length = 79_360, 256
    chunks = als.block_plan("tpu", rows // 2, length, 128, 2, 2, implicit=True)
    counted = (als.gathered_bytes(rows // 2, length, 128, 2)
               + als.normal_equation_bytes(rows // 4, 128, unroll=True)) / chunks
    assert chunks == 5
    compiled = _one_chip_iteration(
        topo, config, [(rows, length)], [(1_024, 16)], mesh_shape=(2, 2))
    text = compiled.as_text()
    factorisations = [line for line in text.splitlines()
                      if 'custom_call_target="Cholesky"' in line
                      or "InvertDiagBlocksLowerTriangular" in line]
    assert all(f"/{als.SCOPE_ASSEMBLE}/{als.SCOPE_YTY}/" in line for line in factorisations)
    assert text.count('custom_call_target="Cholesky"') == 1
    assert "all-to-all" in text
    temp_size = compiled.memory_analysis().temp_size_in_bytes
    print(f"msd r128 largest user block: temp_size {temp_size} bytes, counted {counted:.0f}")
    assert 0.8 * counted <= temp_size < 1.35 * counted < als.EINSUM_GATHER_BUDGET_BYTES


def test_the_train_cell_compiles_whole_and_fits_the_chip_several_times_over(
    topo, no_persistent_cache
):
    """``als-ml20m-r16.train-steady``'s eight blocks (PERF.md section 4): no
    custom call in the program, and its temporaries
    (the lane-padded gathered rows of the largest block, 2.31 GB, and what
    the einsums and the solve add) fit the chip several times over."""
    from predictionio_tpu.parallel.als import ALSConfig

    config = ALSConfig(rank=16, dtype="bfloat16")
    compiled = _one_chip_iteration(
        topo, config,
        [(35_312, 256), (22_872, 152), (28_696, 88), (51_632, 48)],
        [(7_648, 256), (2_224, 144), (3_840, 64), (13_048, 16)],
    )
    assert "tpu_custom_call" not in compiled.as_text()
    temp_size = compiled.memory_analysis().temp_size_in_bytes
    print(f"train cell: temp_size {temp_size} bytes")
    assert 35_312 * 256 * 128 * 2 <= temp_size < 4 << 30


def test_the_template_default_block_compiles_in_11_chunks(
    topo, no_persistent_cache, monkeypatch
):
    """The recommendation template's default packing (one bucket, no cap,
    f32) at MovieLens-1M: users [6040, 216], items [3712, 23832]. The gathered
    rows of the item block are 45.3 GB: worked whole, the compiler refuses the
    program (the control: the chunk rule taken out). With the rule it compiles
    as a loop over 11 chunks of 344 rows with no custom call, and its
    temporaries stay within 1.3x of the 3.91 GiB the rule counted for one."""
    from predictionio_tpu.parallel import als
    from predictionio_tpu.parallel.als import ALSConfig

    users, items = [(6_040, 216)], [(3_712, 23_832)]
    als._build_iteration.cache_clear()
    with monkeypatch.context() as patch:
        patch.setattr(als, "block_plan", lambda *shape, **kw: 1)
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            _one_chip_iteration(topo, ALSConfig(rank=16), users, items)
    als._build_iteration.cache_clear()  # the program built without the rule
    assert als.block_plan("tpu", *items[0], 16, 4) == 11
    compiled = _one_chip_iteration(topo, ALSConfig(rank=16), users, items)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and text.count(" while(") == 1
    counted = (als.gathered_bytes(344, 23_832, 16, 4)
               + als.normal_equation_bytes(344, 16, unroll=True))
    temp_size = compiled.memory_analysis().temp_size_in_bytes
    print(f"ML-1M template defaults: temp_size {temp_size} bytes, counted {counted}")
    assert counted <= temp_size < 1.3 * counted


@pytest.mark.parametrize("rank,pad_len", [(16, 256), (128, 4_096)], ids=["rank16", "rank128"])
@pytest.mark.parametrize("implicit", [False, True], ids=["explicit", "implicit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_budget_sized_chunk_holds_what_the_rule_counted(
    topo, one_chip, no_persistent_cache, dtype, implicit, rank, pad_len
):
    """The most rows of ``pad_len`` slots that ``block_plan`` still takes in
    one piece (at rank 128 a length at which the bytes bind before the blocked
    solve's 4,096 rows do), through the half-step it picks: the compiled
    program's temporaries are what the rule counted for them (gathered rows,
    Grams, what the solve holds beside them) and no more than 1.3 times that
    (PERF.md section 7)."""
    from predictionio_tpu.parallel import als

    itemsize = jnp.dtype(dtype).itemsize
    per_row = (als.gathered_bytes(1, pad_len, rank, itemsize)
               + als.normal_equation_bytes(8, rank, unroll=True) // 8)
    rows = als.EINSUM_GATHER_BUDGET_BYTES // per_row // 8 * 8
    assert als.block_plan("tpu", rows, pad_len, rank, itemsize) == 1
    assert als.block_plan("tpu", rows + 64, pad_len, rank, itemsize) == 2
    counted = (als.gathered_bytes(rows, pad_len, rank, itemsize)
               + als.normal_equation_bytes(rows, rank, unroll=True))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    idx, table = sds((rows, pad_len), jnp.int32), sds((27_001, rank), dtype)
    step = als._half_steps(mesh, implicit, rank, "replicated")(idx, table)
    gram = sds((rank, rank), jnp.float32)
    compiled = jax.jit(step).lower(
        idx, sds((rows, pad_len), jnp.float32), sds((rows,), jnp.float32), table,
        (gram, gram) if implicit else gram, sds((), jnp.float32), sds((), jnp.float32),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    temp_size = compiled.memory_analysis().temp_size_in_bytes
    print(f"rank {rank} {rows} x {pad_len}: temp_size {temp_size} bytes, counted {counted}")
    assert 0.8 * counted <= temp_size <= 1.3 * counted


@pytest.mark.parametrize("pad_len", [24, 56])
def test_a_dual_chunk_holds_what_the_rule_counted(
    topo, one_chip, no_persistent_cache, monkeypatch, pad_len
):
    """One chunk of a dual block (implicit, rank 128, bf16 tables) against
    ``dual_block_bytes``. 56 slots (systems the blocked solve takes) at the
    4,096 rows ``block_plan`` cuts a dual block to on a TPU. 24 slots (systems
    the unrolled solve takes) with that cap taken out, so that the bytes bind
    as they do off the TPU: 24 KiB a row, 174,760 rows to the budget. The
    compiled program's temporaries are what was counted (the float32 whitened
    rows beside the lane-padded ``[L, L]`` systems, two of them or one),
    within the band the primal chunks were read in (PERF.md section 7): 0.92
    and 1.10 times it."""
    from predictionio_tpu.parallel import als

    rank = 128
    assert als.takes_dual(True, pad_len, rank)
    if pad_len == 24:
        monkeypatch.setattr(als, "DUAL_CHUNK_ROWS", 1 << 30)
        rows = als.EINSUM_GATHER_BUDGET_BYTES // (24 * 1_024) // 8 * 8
    else:
        rows = als.DUAL_CHUNK_ROWS
    assert als.block_plan("tpu", rows, pad_len, rank, 2, implicit=True) == 1
    assert als.block_plan("tpu", rows + 8, pad_len, rank, 2, implicit=True) == 2
    counted = als.dual_block_bytes(rows, rows, pad_len, rank, 2, unroll=True)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    idx, table = sds((rows, pad_len), jnp.int32), sds((192_289, rank), jnp.bfloat16)
    gram = sds((rank, rank), jnp.float32)
    step = als._half_steps(mesh, True, rank, "replicated")(idx, table)
    compiled = jax.jit(step).lower(
        idx, sds((rows, pad_len), jnp.float32), sds((rows,), jnp.float32), table,
        (gram, gram), sds((), jnp.float32), sds((), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "Cholesky" not in text
    assert f"f32[{rows},{rank},{rank}]" not in text  # no [K, K] Gram of a row
    temp_size = compiled.memory_analysis().temp_size_in_bytes
    print(f"dual {rows} x {pad_len}: temp_size {temp_size} bytes, counted {counted}")
    assert 0.9 * counted <= temp_size <= 1.2 * counted


#: instructions of an entry computation that move or name data and do no
#: arithmetic: the compiler's own, which carry no scope (PERF.md section 5
#: lists what they cost in a trace)
_NO_WORK = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy",
    "copy-start", "copy-done", "slice-start", "slice-done", "dynamic-update-slice",
}


def test_als_iteration_scopes_its_work(topo, no_persistent_cache, worked):
    """One chip, two buckets a side, bf16 factors, each bucket whole or in
    row chunks: no custom call, and every instruction of the entry
    computation that does work under an ``als.`` scope. The compiler leaves
    the ``op_name`` off some fusions it forms itself; those are held to their
    fused instructions."""
    import re
    from collections import Counter

    from predictionio_tpu.parallel import als

    config = als.ALSConfig(rank=8, dtype="bfloat16")
    shapes = [(256, 64), (512, 16), (128, 128), (256, 32)]
    for rows, length in shapes:
        assert (als.block_plan("tpu", rows, length, 8, 2) > 1) == (worked == "chunked")
    text = _one_chip_iteration(topo, config, shapes[:2], shapes[2:]).as_text()

    assert "tpu_custom_call" not in text
    assert "%iteration" not in text and "_unknown_" not in text

    def scope(line: str):
        """As the benchmark's reader takes an ``op_name`` apart."""
        from benchmarks.scopes import parse_scope

        found = re.search(r'op_name="([^"]*)"', line)
        return parse_scope(found.group(1)) if found else None

    computations = dict(re.findall(r"\n(?:ENTRY )?%([\w.\-]+) [^\n]*\{\n(.*?)\n\}", text, re.S))
    entry = re.search(r"\nENTRY %[^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    seen = Counter()
    for line in entry.splitlines():
        name, opcode = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(", line).groups()
        # (the compiler's own placing of a chunked bucket's rows is a fusion
        # named for the dynamic-update-slice it holds: no arithmetic either)
        if opcode in _NO_WORK or "dynamic-update-slice_fusion" in name:
            continue
        found = scope(line)
        if found is None and opcode == "fusion":
            inner = computations[re.search(r"calls=%([\w.\-]+)", line).group(1)]
            votes = Counter(filter(None, map(scope, inner.splitlines())))
            found = votes.most_common(1)[0][0] if votes else None
        # a chunked bucket's splitting and stitching (pad, reshape, the loop)
        # lie under its ``bucket<i>`` and under no stage
        assert found is not None and (found[1] is not None or worked == "chunked"), line[:200]
        seen[found] += 1
    for side in als.SCOPE_HALF_STEP.values():
        for stage in (als.SCOPE_GRAM, als.SCOPE_SOLVE):
            if worked == "whole":
                assert seen[(side, stage)] > 0, (side, stage, seen)
            else:  # the stages are in the chunk loops' bodies, not in the entry
                assert re.search(rf'op_name="[^"]*{re.escape(side)}/bucket\d/while/body/[^"]*{stage}/', text)
    # under ``gram`` every operation is the gather's or the products', and
    # under no other stage is there a leaf
    places = _places(text)
    for side in als.SCOPE_HALF_STEP.values():
        assert {p.leaf for p in places if p.top == side and p.stage == als.SCOPE_GRAM} == {
            als.SCOPE_GATHER, als.SCOPE_PRODUCTS}
    assert not [p for p in places if p.leaf and p.stage != als.SCOPE_GRAM]


def _lowered_looped_step(topo, layers: int, rows: int, **how):
    """One optimizer step of the looped backbone at Ouro-2.6B's widths on
    ``rows`` rows of 256, lowered for one described chip."""
    import optax

    from predictionio_tpu.models.sequence import looped, model as seq_model

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "seq"))
    config = looped.LoopedConfig(
        num_items=49_151, max_len=256, hidden_size=2048, num_heads=16, head_dim=128,
        ffn_dim=5632, num_layers=layers, ut_steps=4, batch_size=rows, **how)
    _, _, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda shape: sds(shape, jnp.float32, rep), looped.param_shapes(config),
        is_leaf=lambda x: isinstance(x, tuple))
    opt_state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, rep),
        jax.eval_shape(optax.adam(config.learning_rate).init, params))
    batch = {k: sds((rows, 256), jnp.int32, seq_shard) for k in ("seq", "target")}
    return config, step_fn.lower(params, opt_state, batch, sds((2,), jnp.uint32, rep))


@pytest.fixture(scope="module")
def small_looped_step(topo, no_persistent_cache):
    """The looped step at Ouro-2.6B's widths, two layers, eight rows of 256."""
    return _lowered_looped_step(topo, 2, 8, head_chunk=1024)[1].compile()


def _places(text: str) -> set:
    """Where the instructions of a compiled program lie, as
    ``benchmarks/scopes_leaf.py`` takes an ``op_name`` apart."""
    import re

    from benchmarks import scopes_leaf

    return {scopes_leaf.place_of(name) for name in re.findall(r'op_name="([^"]*)"', text)} - {None}


def _moved_under_attention(text: str, rows: int) -> list:
    """The transposes and copies in a compiled looped step (rows of 256, 16
    heads of 128) of a float32 array the size of q under a pass's ``attention``
    scope, in whichever of its shapes: ``(shape, opcode, scope below attention)``."""
    import re

    shapes = "|".join(f"{rows},{dims}" for dims in ("256,16,128", "16,256,128", "256,2048"))
    found = []
    for line in text.splitlines():
        hit = re.search(rf"= f32\[({shapes})\]\S* (transpose|copy)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if hit and name and "/attention/" in name.group(1):
            found.append((*hit.groups(), name.group(1).split("/attention/")[1]))
    return found


def test_the_looped_step_compiles_at_the_published_widths_and_scopes_its_work(
        small_looped_step):
    """One optimizer step of the sequence template's looped backbone at
    Ouro-2.6B's widths (two layers, eight rows of 256): the flash kernel at
    heads of 128 is there four times a pass (forward, the recomputed forward,
    ``dq`` and ``dkv``), each call under its pass's ``attention`` scope as the
    benchmark's reader takes an ``op_name`` apart, and the step fits the chip.
    Every leaf of a layer but ``rope`` is in the compiled program in every
    pass, forward, recomputed and backward, and each flash call lies under
    ``attention/kernel``. Nothing is rooted at ``rope``: the programs turn q
    and k themselves (PR 51), and between ``qkv`` and ``out`` no float32 array
    the size of q is transposed or copied in either direction (PR 50's step
    held 20 such instructions outside its fusions)."""
    import re

    from benchmarks import scopes_leaf, scopes_seq

    compiled = small_looped_step
    assert compiled.memory_analysis().peak_memory_in_bytes < 8e9
    text = compiled.as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert len(calls) == 16
    kinds = [(scopes_seq.parse_scope(c), scopes_seq.kernel_kind(c)) for c in calls]
    for t in range(1, 5):
        mine = sorted(kind for scope, kind in kinds if scope == (f"pass{t}", "attention"))
        assert mine == ["backward", "backward", "forward", "forward"]
    placed = [scopes_leaf.place_of(c) for c in calls]
    assert {(p.stage, p.leaf) for p in placed} == {("attention", "kernel")}
    assert sorted(p.phase for p in placed if p.top == "pass3") == [
        "backward", "backward", "forward", "recomputed"]
    seen = {(p.top, p.stage, p.leaf, p.phase) for p in _places(text)}
    for t in range(1, 5):
        for phase in ("forward", "recomputed", "backward"):
            for leaf in ("norm", "qkv", "kernel", "out"):
                assert (f"pass{t}", "attention", leaf, phase) in seen, (t, leaf, phase)
            assert (f"pass{t}", "mlp", "norm", phase) in seen, (t, phase)
    assert {leaf for _, stage, leaf, _ in seen if stage == "exit"} == {None}
    assert "rope" not in {leaf for _, _, leaf, _ in seen}
    assert _moved_under_attention(text, 8) == []


def test_the_leaf_scopes_leave_the_looped_step_instruction_for_instruction(
        topo, small_looped_step, monkeypatch):
    """The same step compiled with every ``jax.named_scope`` patched out here
    in the test (the program has no switch): with the names taken out of both,
    the two optimised programs for the described v5e are one text."""
    import contextlib

    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _lowered_looped_step(topo, 2, 8, head_chunk=1024)[1].compile().as_text()
    scoped = small_looped_step.as_text()
    assert "seq.pass" not in bare and "/attention/qkv/" in scoped
    assert _without_names(bare) == _without_names(scoped)


@pytest.mark.parametrize("layers,fits", [(6, True), (8, False)], ids=["6-layers", "8-layers"])
def test_the_sequence_cells_step_fits_the_chip_at_6_layers_and_not_at_8(
        topo, no_persistent_cache, layers, fits):
    """The step of ``ouro-2.6b-d8.train-histories`` (32 rows of 256 at the
    published widths): at 6 layers it compiles at a peak under the chip's
    15.75 GB, at the 8 the issue asked for the compiler refuses it for memory
    (16.35 GB), which is why ``benchmarks/configs/ouro-2.6b-d8.json`` holds 6."""
    from predictionio_tpu.models.sequence import looped

    config, lowered = _lowered_looped_step(topo, layers, 32)
    assert looped.head_chunk_of(config) == 2048
    if fits:
        compiled = lowered.compile()
        peak = compiled.memory_analysis().peak_memory_in_bytes
        assert 13.5e9 < peak < 15.0e9, peak    # 14.19 GB (14.23 before PR 51)
        assert _digest(compiled.as_text()) == ACCEPTED_STEPS["ouro-2.6b-d8.train-histories"]
    else:
        with pytest.raises(Exception, match=r"RESOURCE_EXHAUSTED(.|\n)*hbm"):
            lowered.compile()


def test_the_lifelong_histories_cells_step_fits_the_chip_at_6_layers_and_scopes_its_work(
        topo, no_persistent_cache):
    """The step of ``keye-vl2-30b-a3b-ep8.train-lifelong-histories`` (2 rows of
    8,192 at the published widths, 16 of 128 experts held, an eighth of the
    vocabulary) at the 6 layers ``benchmarks/configs/keye-vl2-30b-a3b-ep8.json``
    holds: Mosaic takes the programs of ``ops/sparse_attention.py`` at that
    size (index, select, the forward attention and its one backward program,
    which holds a key-value head's ``dk`` and ``dv`` of the whole row in VMEM) and ``ops/run_sum.py``'s for a pass of 32,768 rows onto 16,384
    tokens (float32 rows forward, bfloat16 ones backward), the grouped matmuls
    lower to the chip's own ragged dot, no row
    of the experts' path is scattered, the peak is under the chip's 15.75 GB,
    and every program sits under the scope the benchmark's reader looks for.
    The selection is worked once: the index and select programs stand in the
    forward pass alone, and the recomputed pass starts from the kept bits
    (``uint8[6, 2, 1024, 8192]`` under the scan) and runs the attention
    program again, so attention is there forward, recomputed and backward."""
    import re

    from benchmarks import scopes_leaf, scopes_sparse
    from predictionio_tpu.models.sequence import experts, model as seq_model, sparse_moe

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "seq"))
    config = sparse_moe.SparseMoEConfig(
        num_items=18_991, max_len=8192, hidden_size=2048, num_heads=32, num_kv_heads=4,
        head_dim=128, expert_dim=768, num_experts=128, experts_per_token=8,
        experts_held=(0, 16), num_layers=6, index_heads=16, index_dim=64, index_topk=2048,
        batch_size=2)
    assert sparse_moe.count_params(config) == 659_187_712
    # the run sum: 64 blocks of 256 tokens, each over at most the 9 row blocks
    # of 256 that 256 x 8 rows can span
    assert experts.pass_plan(config, 16384) == (32768, 4)
    assert _run_sum_grid(16384, 8, 32768) == (64, 9)
    _, _, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda shape: sds(shape, jnp.float32, rep), sparse_moe.param_shapes(config),
        is_leaf=lambda x: isinstance(x, tuple))
    opt_state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, rep),
        jax.eval_shape(seq_model.optimizer_of(config).init, params))
    batch = {k: sds((2, 8192), jnp.int32, seq_shard) for k in ("seq", "target")}
    compiled = step_fn.lower(params, opt_state, batch, sds((2,), jnp.uint32, rep)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    # 14.97 GB (15.04 with the sum by position); not above PR 45's 14,974,214,144
    assert 13.5e9 < peak <= 14_974_214_144, peak
    text = compiled.as_text()
    assert _digest(text) == ACCEPTED_STEPS["keye-vl2-30b-a3b-ep8.train-lifelong-histories"]
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    stages = [scopes_sparse.parse_stage(c) for c in calls]
    kinds = [scopes_sparse.kernel_kind(c) for c in calls]
    # forward: one index, one select and one attention program; recomputed:
    # the attention program alone, on the selection the forward pass kept;
    # backward: one program for dq, dk and dv (PR 42; two before). The experts'
    # grouped matmuls are custom calls too
    assert stages.count("index") == 1 and stages.count("select") == 1
    assert kinds.count("forward") == 2 and kinds.count("backward") == 1
    # and their operands by one program a phase under ``rope`` (PR 46), which
    # ``kernel_kind`` does not take for an attention call
    _the_operands_are_written_once(calls, {"attention": 1})
    assert sparse_moe.fit_attrs(config, 2, "tpu")["rope_block"] == "512x1024"
    assert sparse_moe.attention_backward_heads_per_step(config) == 1
    assert _backward_attention_grid((2, 8192, 32, 128), 4, 128, masked=True) == (2, 4, 16, 16)
    assert sparse_moe.selection_kept_bytes(config, 2) == 6 * 2 * 1024 * 8192
    assert re.search(r"u8\[6,2,1024,8192\]", text)
    # the held experts: XLA's own ragged dot, which keeps its own name and no
    # scope. A pass is three grouped matmuls forward and, in the backward pass,
    # three worked again and six transposed; the program holds that pass twice,
    # the first, which starts the sum, and the body of the scan over the three
    # further passes the worst case would take (a `cond` inside a scanned pass
    # holds one body, however many passes it stands for): 2 x 12
    grouped = re.findall(
        r"(%ragged-dot-none(?:\.\d+)?) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert len(grouped) == 24, grouped
    assert all(scopes_sparse.stage_of(g, "ragged-dot-none") == "experts" for g in grouped)
    # rows come back onto their tokens by a gather into token order and a
    # program that sums each token's run: a scatter of rows cost the chip more
    # than the whole of the experts (PERF.md PR 33). The step's two
    # scatters are the embedding's gradient and the transpose of the router's
    # top-k (scalars into [tokens x experts]); XLA leaves some without a name
    scatters = re.findall(r'= (\S+?)\{\S* scatter\(([^\n]*)', text)
    assert sorted(shape for shape, _ in scatters) == ["f32[18992,2048]", "f32[2097152]"]
    assert not [rest for _, rest in scatters if "moe/experts" in rest]
    # the leaves, as ``benchmarks/scopes_leaf.py`` takes an ``op_name`` apart: a
    # layer's in every phase; the indexer and the selection, which pass no
    # gradient, in the forward pass alone (the packing lies under ``select``,
    # the unpacking under ``kernel``, forward and recomputed);
    # the experts' rows and grouped matmuls forward and again, the sum back onto
    # the tokens not again (the backward of ``take`` is its ``sum``, which
    # writes the compute dtype itself); every program under its leaf, the sum's
    # in the first pass and in the scanned body, forward and backward
    seen = {(p.stage, p.leaf, p.phase) for p in _places(text)}
    every = ("forward", "recomputed", "backward")
    want = {("attention", leaf): every for leaf in ("norm", "qkv", "rope", "kernel", "out")}
    want |= {("attention", "index"): every[:1], ("attention", "select"): every[:1],
             ("moe", "norm"): every, ("experts", "sort"): every[:2],
             ("experts", "take"): every[:2], ("experts", "grouped"): every,
             ("experts", "give"): every[::2], ("experts", "sum"): every[::2]}
    for (stage, leaf), phases in want.items():
        assert tuple(p for p in every if (stage, leaf, p) in seen) == phases, (stage, leaf)
    assert {(stage, leaf) for stage, leaf, _ in seen if leaf} == set(want)
    again = re.findall(r'op_name="([^"]*seq\.[^"]*/again/[^"]*)"', text)
    assert again and all("transpose(jvp(seq.pass1))" in name and "/moe/experts/" in name
                         for name in again)
    programs = [scopes_leaf.place_of(c) for c in calls if "seq." in c]
    assert {p.leaf for p in programs} == {"index", "select", "rope", "kernel", "sum"}
    assert sorted(p.phase for p in programs if (p.stage, p.leaf) == ("experts", "sum")) == [
        "backward", "backward", "forward", "forward"]


def _the_operands_are_written_once(calls: list, kinds: dict) -> None:
    """Of a compiled step's device programs (``calls``: their ``op_name``s),
    under each stage of ``kinds`` (``attention``, ``window_attention``: how
    many kinds of layer the program holds under it, each once) the leaf
    ``rope`` holds ``ops/rope_layout.py``'s programs, two forward (the pass and
    the pass worked again) to one backward a kind of layer, and the leaf
    ``kernel`` the attention's, two forward to one backward still: the
    operands' programs never lie under ``kernel``, where the benchmark's
    readers count every program as an attention call."""
    import re

    parts = [re.split(r"[/():]", c) for c in calls]
    for stage, count in kinds.items():
        for leaf in ("rope", "kernel"):
            under = [c for c, p in zip(calls, parts)
                     if stage in p and leaf in p[p.index(stage):]]
            phases = ["recomputed" if "rematted_computation" in c else
                      "backward" if "transpose(" in c else "forward" for c in under]
            assert sorted(phases) == sorted(["forward", "recomputed", "backward"] * count), (
                stage, leaf, under)


def _backward_attention_grid(q_shape: tuple, kv: int, dv: int, masked: bool = False) -> tuple:
    """The grid of ``ops/sparse_attention.py``'s backward program as a step
    traces it for bfloat16 ``q`` of ``q_shape`` on ``kv`` key-value heads and
    values of ``dv``: (rows, key-value heads over those a step, tiles of
    queries, tiles of keys), heads and the tile of queries from the shapes."""
    from predictionio_tpu.ops import sparse_attention as sa

    b, t, _, d = q_shape
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16)
               for s in (q_shape, (b, t, kv, d), (b, t, kv, dv)))
    mask = jax.ShapeDtypeStruct((b, t, t), jnp.int8) if masked else None
    out, lse = jax.eval_shape(lambda q, k, v, mask: sa._forward(
        q, k, v, mask, sa.BLOCK_Q, sa.BLOCK_K, False), q, k, v, mask)
    traced = jax.make_jaxpr(lambda q, k, v, mask, out, lse: sa._bwd(
        sa.BLOCK_Q, sa.BLOCK_K, False, (q, k, v, mask, out, lse), out))(q, k, v, mask, out, lse)
    (call,) = [eqn for eqn in traced.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    return call.params["grid_mapping"].grid


def _run_sum_grid(n: int, slots: int, rows: int) -> tuple:
    """The grid of ``ops/run_sum.py``'s program as a step traces it for a pass
    of ``rows`` rows onto ``n`` tokens of ``slots`` slots: blocks from the
    shapes alone."""
    from predictionio_tpu.ops import run_sum

    traced = jax.make_jaxpr(lambda v, w, t: run_sum.sum_runs(
        v, w, run_sum.plan(t, n), n, slots, unit=False))(
            jax.ShapeDtypeStruct((rows, 2048), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32), jax.ShapeDtypeStruct((rows,), jnp.int32))
    (grid,) = [eqn.params["grid_mapping"].grid for eqn in traced.jaxpr.eqns
               if eqn.primitive.name == "pallas_call"]
    return grid


def _the_sums_are_programs(text: str, calls: list, each: int) -> None:
    """Of a compiled step's device programs (``calls``: their ``op_name``s),
    ``each`` lie under ``experts/.../sum`` forward and as many backward, every
    other program of the step lies under ``attention`` or the delta rule, and
    no scatter lies under ``moe/experts``."""
    import re

    from benchmarks import scopes_leaf

    places = [scopes_leaf.place_of(c) for c in calls if "seq." in c]
    sums = [p.phase for p in places if (p.stage, p.leaf) == ("experts", "sum")]
    assert sorted(sums) == ["backward"] * each + ["forward"] * each, sums
    assert {p.stage for p in places if p.leaf != "sum"} <= {"attention", "layers"}
    scatters = re.findall(r'= \S+?\{\S* scatter\(([^\n]*)', text)
    assert scatters and not [rest for rest in scatters if "moe/experts" in rest]


def test_the_hybrid_cells_step_fits_the_chip_and_scopes_its_work(topo, no_persistent_cache):
    """The step of ``qwen3-next-80b-a3b-ep16.train-lifelong-histories`` (2 rows
    of 8,192 at the published widths, one period of three linear layers and a
    full one, 32 of 512 experts held, an eighth of the vocabulary): Mosaic takes
    the delta rule's state pass and its transpose at 128 chunks of 64, blocks
    of 8 of the 64 row-heads a grid step (a grid of 8 x 128), the conv's two
    programs at tiles of 1,024 positions by 512 channels, q, k and v each an
    output of the forward one and a cotangent operand of the backward one, and the
    attention programs with no mask operand at head width 256, and the run
    sum's for a pass of 20,480 rows of 10 slots a token, the peak is
    under the chip's 15.75 GB, and every program and every leaf sits under the
    scope the benchmark's readers look for. A linear mixer's state pass and its
    conv stand forward, recomputed and (their transposes) backward, and nothing
    else of the conv's scope moves a ``[2, 8192, 8192]`` array; the full layer's
    attention forward, recomputed and as one backward program, a key-value
    head's ``dk`` and ``dv`` (16.8 MB) in VMEM for the whole row."""
    import re

    from benchmarks import scopes_hybrid, scopes_leaf, scopes_seq, scopes_sparse
    from predictionio_tpu.models.sequence import experts, hybrid, model as seq_model
    from predictionio_tpu.ops import delta_rule

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "seq"))
    config = hybrid.HybridConfig(
        num_items=18_991, max_len=8192, hidden_size=2048, num_layers=4,
        full_attention_interval=4, linear_key_heads=16, linear_value_heads=32,
        linear_key_dim=128, linear_value_dim=128, conv_kernel=4, num_heads=16,
        num_kv_heads=2, head_dim=256, rotary_fraction=0.25, expert_dim=512, num_experts=512,
        experts_per_token=10, experts_held=(0, 32), shared_expert_dim=512, batch_size=2)
    assert hybrid.count_params(config) == 625_667_136
    assert hybrid.delta_heads_per_step(config, 2) == 8
    assert hybrid.conv_block(config, "tpu") == "1024x512"
    assert experts.pass_plan(config, 16384) == (20480, 8)
    assert _run_sum_grid(16384, 10, 20480) == (64, 11)
    _, _, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    # the two programs as the step traces them at these shapes: 1,024 grid steps each
    low = jax.ShapeDtypeStruct((64, 128, 64, 128), jnp.bfloat16)
    state, scalar = (jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((64, 128, 128, 128), jnp.bfloat16), ((64, 128), jnp.float32)))
    for traced in (
            jax.make_jaxpr(lambda *a: delta_rule._pass_fwd(*a, False))(low, low, low, scalar),
            jax.make_jaxpr(lambda *a: delta_rule._pass_bwd(False, a[:5], a[5:]))(
                low, low, scalar, low, state, low, state)):
        grids = [eqn.params["grid_mapping"].grid for eqn in traced.jaxpr.eqns
                 if eqn.primitive.name == "pallas_call"]
        assert grids == [(8, 128)], grids
    params = jax.tree_util.tree_map(
        lambda shape: sds(shape, jnp.float32, rep), hybrid.param_shapes(config),
        is_leaf=lambda x: isinstance(x, tuple))
    opt_state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, rep),
        jax.eval_shape(seq_model.optimizer_of(config).init, params))
    batch = {k: sds((2, 8192), jnp.int32, seq_shard) for k in ("seq", "target")}
    compiled = step_fn.lower(params, opt_state, batch, sds((2,), jnp.uint32, rep)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    # 15.53 GB: 268 MB over PR 45's 15,262,026,240, and no buffer of PR 46's:
    # XLA's schedule now places the full layer's dW_o after the linear layers'
    # backward loop and holds its two 134 MB operands across it (PERF.md, PR 46)
    assert 14.0e9 < peak <= 15_530_461_696, peak
    text = compiled.as_text()
    assert _digest(text) == ACCEPTED_STEPS["qwen3-next-80b-a3b-ep16.train-lifelong-histories"]
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    rule = [c for c in calls if scopes_hybrid.place_of(c) == ("linear", "delta")]
    phases = lambda names: sorted(  # noqa: E731
        "recomputed" if "rematted_computation" in c else
        "backward" if "transpose(" in c else "forward" for c in names)
    assert phases(rule) == ["backward", "forward", "recomputed"]
    conv = [c for c in calls if scopes_hybrid.place_of(c) == ("linear", "conv")]
    assert phases(conv) == ["backward", "forward", "recomputed"]
    # the split and the cotangents' joining are the programs' own: beside them
    # the scope holds the mask's and the taps' small arrays alone
    beside = re.findall(r"= \(?(f32|bf16)\[2,8192,\d{4}\][^\n]*? (fusion|copy)\([^\n]*"
                        r'op_name="[^"]*linear_attention/conv/', text)
    assert not beside, beside
    # the full layer's attention programs under ``kernel`` and their operands'
    # under ``rope`` (PR 46); ``scopes_seq.kernel_kind`` takes every program
    # under ``attention`` for an attention call, these too
    _the_operands_are_written_once(calls, {"attention": 1})
    kinds = [scopes_seq.kernel_kind(c) for c in calls]
    assert kinds.count("forward") == 4 and kinds.count("backward") == 2
    assert hybrid.fit_attrs(config, 2, "tpu")["rope_block"] == "256x2048"
    assert hybrid.attention_backward_heads_per_step(config) == 1
    assert _backward_attention_grid((2, 8192, 16, 256), 2, 256) == (2, 2, 16, 16)
    assert not [c for c in calls if scopes_sparse.parse_stage(c) in ("index", "select")]
    # the held experts: the sparse backbone's passes, XLA's own ragged dot, and
    # the rows back by runs (the linear layers' scan and the full layer, each
    # the first pass and the scanned body): programs under ``sum``, no scatter
    assert re.search(r"%ragged-dot-none(?:\.\d+)? = [^\n]*tpu_custom_call", text)
    _the_sums_are_programs(text, calls, 4)
    places = {scopes_hybrid.place_of(name) for name in re.findall(r'op_name="([^"]*)"', text)}
    assert {leaf for kind, leaf in places - {None} if kind == "linear"} >= set(
        scopes_hybrid.LEAVES)
    assert ("shared", None) in places


def test_the_latent_cells_step_fits_the_chip_and_scopes_its_work(topo, no_persistent_cache):
    """The step of ``joyai-llm-flash-ep16.train-lifelong-histories`` (2 rows of
    8,192 at the published widths: a dense layer, four expert layers with 16 of
    256 experts held, the prediction module, an eighth of the vocabulary):
    Mosaic takes the attention programs with no mask operand at a score width
    of 192 (one and a half lane tiles) and a value width of 128, eight of the
    32 heads a grid step (a grid of 2 x 4 x 32 x 16) and four a step of the
    backward program, whose ``dk`` and ``dv`` of the whole row stay in VMEM (a
    grid of 2 x 8 x 32 x 16: its tile of queries stays 256, where the sparse and
    hybrid cells' takes 512), the peak is under the
    chip's 15.75 GB, and every program and every new scope sits where the
    benchmark's readers look for it: the attention forward, recomputed and as
    one backward program in the dense layer, in the scanned expert layers and
    under ``mtp``, each behind ``ops/rope_layout.latent_rope_layout``'s program
    under ``rope`` (PR 49: 256 positions of four heads a step); the run sum's programs for a pass of 16,384 rows, as many as
    tokens, in the expert layers and the module; the two latent paths inside
    ``qkv``; the bias's move under ``seq.optimizer``."""
    import re

    from benchmarks import scopes_latent, scopes_leaf, scopes_seq, scopes_sparse
    from predictionio_tpu.models.sequence import experts, latent_moe, model as seq_model
    from predictionio_tpu.ops import sparse_attention as sa

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "seq"))
    config = latent_moe.LatentMoEConfig(
        num_items=16_159, max_len=8192, hidden_size=2048, num_layers=5, dense_layers=1,
        num_heads=32, q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, value_dim=128,
        ffn_dim=7168, expert_dim=768, num_experts=256, experts_per_token=8,
        experts_held=(0, 16), shared_expert_dim=768, batch_size=2)
    assert latent_moe.count_params(config) == 680_439_808
    # the forward program as the step traces it at these shapes: eight heads a step
    q = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda q, k, v: sa._forward(
        q, k, v, None, sa.BLOCK_Q, sa.BLOCK_K, False))(q, q, v)
    grids = [eqn.params["grid_mapping"].grid for eqn in traced.jaxpr.eqns
             if eqn.primitive.name == "pallas_call"]
    assert grids == [(2, 4, 8192 // sa.BLOCK_Q, 8192 // sa.BLOCK_K)] == [(2, 4, 32, 16)], grids
    # the backward program: four heads a step, their dk and dv over the row
    assert latent_moe.attention_backward_heads_per_step(config) == 4
    assert _backward_attention_grid((2, 8192, 32, 192), 32, 128) == (2, 8, 32, 16)
    assert experts.pass_plan(config, 16384) == (16384, 8)
    assert _run_sum_grid(16384, 8, 16384) == (64, 9)
    _, _, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda shape: sds(shape, jnp.float32, rep), latent_moe.param_shapes(config),
        is_leaf=lambda x: isinstance(x, tuple))
    opt_state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, rep),
        jax.eval_shape(seq_model.optimizer_of(config).init, params))
    # Adam keeps two moments for every trained leaf and none for the biases
    moments = [a for a in jax.tree_util.tree_leaves(opt_state) if a.ndim]
    assert sum(int(np.prod(a.shape)) for a in moments) == 2 * 680_439_808
    batch = {k: sds((2, 8192), jnp.int32, seq_shard) for k in ("seq", "target")}
    compiled = step_fn.lower(params, opt_state, batch, sds((2,), jnp.uint32, rep)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 14.0e9 < peak < 15.6e9, peak       # 15.01 GB (15.18 before PR 49)
    text = compiled.as_text()
    assert _digest(text) == ACCEPTED_STEPS["joyai-llm-flash-ep16.train-lifelong-histories"]
    calls = [c for c in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text) if "seq." in c]
    # the dense layer, the scan's body and the module: each forward, again and
    # one backward program (PR 42; dq and dkv before: 12 programs and 6
    # backward), under ``kernel``, and as many of ``latent_rope_layout``'s under
    # ``rope`` (PR 49); the experts' rows back by runs in the scan's body and
    # the module
    attention = [c for c in calls if scopes_leaf.place_of(c).stage == "attention"]
    assert sorted(scopes_leaf.place_of(c).leaf for c in attention) == (
        ["kernel"] * 9 + ["rope"] * 9)
    _the_operands_are_written_once(calls, {"attention": 3})
    assert latent_moe.fit_attrs(config, 2, "tpu")["rope_block"] == "256x768"
    _the_sums_are_programs(text, calls, 4)
    # ``scopes_seq.kernel_kind`` takes every program under ``attention`` for an
    # attention call, the operands' too
    kinds = [scopes_seq.kernel_kind(c) for c in calls]
    assert kinds.count("forward") == 12 and kinds.count("backward") == 6
    under_module = [c for c in attention if "mtp" in scopes_latent.places_of(c)]
    assert sorted(scopes_leaf.place_of(c).leaf for c in under_module) == (
        ["kernel"] * 3 + ["rope"] * 3)
    assert sum("mtp" in scopes_latent.places_of(c) for c in calls) == 10     # and its four sums
    assert not [c for c in calls if scopes_sparse.parse_stage(c) in ("index", "select")]
    # the held experts: the sparse backbone's passes, XLA's own ragged dot
    assert re.search(r"%ragged-dot-none(?:\.\d+)? = [^\n]*tpu_custom_call", text)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    places = {place for name in names for place in scopes_latent.places_of(name)}
    assert places == {"q_latent", "kv_latent", "mtp"}
    under_module = {scopes_seq.parse_scope(n)[1] for n in names
                    if "mtp" in scopes_latent.places_of(n)}
    assert {"attention", "exit", "layers"} <= under_module
    assert any("/seq.optimizer/bias/" in n for n in names)
    seen = {(p.stage, p.leaf) for p in map(scopes_leaf.place_of, names) if p and p.leaf}
    assert {("attention", leaf) for leaf in ("norm", "qkv", "rope", "kernel", "out")} <= seen
    assert {("mlp", "norm"), ("moe", "norm"), ("experts", "grouped")} <= seen


def test_the_window_cells_step_fits_the_chip_and_scopes_its_work(topo, no_persistent_cache):
    """The step of ``laguna-xs2-ep16.train-lifelong-histories`` (2 rows of 8,192
    at the published widths: layer 0 full and dense, then one period of three
    window layers of 64 heads and a full layer of 48, 16 of 256 experts held,
    an eighth of the vocabulary): Mosaic takes the attention programs with no
    mask operand at 6 query heads a key-value head (the full layers: a grid of
    2 x 8 x 32 x 16, the row's sixteen key blocks) and with a window of 512 at
    8 (the window layers: 2 x 8 x 32 x 2, the two key blocks a query block's
    band touches; the backward program 2 x 8 x 16 x 2), the peak is under the
    chip's 15.75 GB, and every program sits where the benchmark's readers look:
    the full layers' under ``attention`` (layer 0 and the period's: forward,
    again and one backward program each), the window layers' under
    ``window_attention`` in the scan inside the period's, the run sum's in both
    kinds of expert layer."""
    import re

    from benchmarks import scopes_leaf, scopes_seq, scopes_sparse, scopes_window
    from predictionio_tpu.models.sequence import experts, model as seq_model, window_moe
    from predictionio_tpu.ops import sparse_attention as sa

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "seq"))
    full, window = window_moe.FULL, window_moe.WINDOW
    config = window_moe.WindowMoEConfig(
        num_items=12_543, max_len=8192, hidden_size=2048,
        layer_types=(full, window, window, window, full),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
        heads_per_layer=(48, 64, 64, 64, 48), num_kv_heads=8, head_dim=128, window=512,
        ffn_dim=8192, expert_dim=512, num_experts=256, experts_per_token=8,
        experts_held=(0, 16), shared_expert_dim=512, batch_size=2)
    assert window_moe.count_params(config) == 490_297_344

    def forward_grid(heads, band):
        q = jax.ShapeDtypeStruct((2, 8192, heads, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
        traced = jax.make_jaxpr(lambda q, k, v: sa._forward(
            q, k, v, None, sa.BLOCK_Q, sa.BLOCK_K, False, band))(q, kv, kv)
        (grid,) = [eqn.params["grid_mapping"].grid for eqn in traced.jaxpr.eqns
                   if eqn.primitive.name == "pallas_call"]
        return grid

    assert forward_grid(48, None) == (2, 8, 32, 16) and forward_grid(64, 512) == (2, 8, 32, 2)
    assert _backward_attention_grid((2, 8192, 48, 128), 8, 128) == (2, 8, 16, 16)
    assert sa.tiles_of(8, 8, 128, 128, 8192, 2) == ((256, 512), (512, 512))
    assert sa.band_key_blocks(8192, 512, 512, 512) == 2
    assert experts.pass_plan(config, 16384) == (16384, 8)
    _, _, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda shape: sds(shape, jnp.float32, rep), window_moe.param_shapes(config),
        is_leaf=lambda x: isinstance(x, tuple))
    opt_state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype, rep),
        jax.eval_shape(seq_model.optimizer_of(config).init, params))
    batch = {k: sds((2, 8192), jnp.int32, seq_shard) for k in ("seq", "target")}
    compiled = step_fn.lower(params, opt_state, batch, sds((2,), jnp.uint32, rep)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 11.0e9 < peak <= 11_490_394_112, peak       # 11.49 GB; not above PR 45's
    text = compiled.as_text()
    assert _digest(text) == ACCEPTED_STEPS["laguna-xs2-ep16.train-lifelong-histories"]
    calls = [c for c in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text) if "seq." in c]
    # layer 0 and the period's full layer: each forward, again and one backward,
    # the attention programs under ``kernel`` and their operands' under ``rope``
    # (PR 46); ``scopes_seq.kernel_kind`` takes both for attention calls
    attention = [c for c in calls if scopes_leaf.place_of(c).stage == "attention"]
    assert sorted(scopes_leaf.place_of(c).leaf for c in attention) == ["kernel"] * 6 + ["rope"] * 6
    kinds = [scopes_seq.kernel_kind(c) for c in calls]
    assert kinds.count("forward") == 8 and kinds.count("backward") == 4
    # the three window layers are one scan's body: forward, again, backward
    banded = [c for c in calls if scopes_window.place_of(c) is not None]
    assert sorted(scopes_window.place_of(c) for c in banded) == (
        [("window", "kernel")] * 3 + [("window", "rope")] * 3)
    assert sorted(scopes_leaf.phase_of(c) for c in banded) == sorted(
        ["backward", "forward", "recomputed"] * 2)
    _the_operands_are_written_once(calls, {"attention": 2, "window_attention": 1})
    attrs = window_moe.fit_attrs(config, 2, "tpu")
    assert (attrs["rope_block"], attrs["window_rope_block"]) == ("1024x768", "512x1024")
    assert all(scopes_leaf.place_of(c).stage == "layers" for c in banded)
    assert not [c for c in calls if scopes_sparse.parse_stage(c) in ("index", "select")]
    # no array of the row by the row: the band's mask is made in the tiles (the
    # one [2, 8192, 8192] is layer 0's SwiGLU of 8,192, positions by width)
    assert not re.search(r"(?:pred|s8|u8|s32)\[(?:\d+,)*8192,8192\]", text)
    assert not re.search(r"\[(?:\d+,){2,}8192,8192\]", text)
    assert re.search(r"%ragged-dot-none(?:\.\d+)? = [^\n]*tpu_custom_call", text)
    _the_sums_are_programs(text, [c for c in calls if c not in banded], 4)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    places = {scopes_window.place_of(n) for n in names} - {None}
    assert {leaf for _, leaf in places} >= set(scopes_window.LEAVES)
    seen = {(p.stage, p.leaf) for p in map(scopes_leaf.place_of, names) if p and p.leaf}
    assert {("attention", leaf) for leaf in ("norm", "qkv", "rope", "kernel", "out")} <= seen
    assert {("mlp", "norm"), ("moe", "norm"), ("experts", "grouped")} <= seen
