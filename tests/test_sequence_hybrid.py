"""The hybrid backbone of the sequence template (gated-delta-rule linear
attention, a gated full-attention layer every fourth, routed experts beside a
shared one) against its plain reference (``benchmarks/reference_qwen3next.py``),
at a small size with seeded weights: loss, auxiliary loss and every gradient
in float32 and with bfloat16 matmul inputs, with padded rows; the chunked rule
against the token-by-token recurrence at three chunk sizes and a length none
divides; the state pass's Pallas programs, interpreted, against the scan; the
shares of an expert-parallel deployment, the shared expert counted once, add
up to the uncut layer; the attention programs with no mask operand at head
width 256; the engine takes the backbone by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_qwen3next as ref
from benchmarks import seeded_hybrid
from predictionio_tpu.models.sequence import experts as experts_module, hybrid
from predictionio_tpu.models.sequence.hybrid import HybridConfig
from predictionio_tpu.models.sequence.model import (
    fit_attrs, make_fit, score_next_items_batch, train_sasrec,
)
from predictionio_tpu.ops import delta_rule, sparse_attention as sa
from predictionio_tpu.parallel.ring_attention import plain_attention

VOCAB, T, ROWS = 256, 80, 3
#: the configuration file's keys at the test's size, as ``seeded_hybrid`` reads them
FILE = dict(hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
            linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, linear_conv_kernel_dim=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=24, num_experts=8,
            shared_expert_intermediate_size=24)
DIMS = dict(linear_key_heads=2, linear_value_heads=4, linear_key_dim=8, linear_value_dim=16,
            conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
            experts_per_token=2, experts_held=(2, 6), rope_theta=1e7, rms_eps=1e-6,
            query_block=16)
AUX = 0.01


def _config(**kw) -> HybridConfig:
    base = dict(num_items=VOCAB - 1, max_len=T, hidden_size=32, num_layers=4,
                full_attention_interval=4, linear_key_heads=2, linear_value_heads=4,
                linear_key_dim=8, linear_value_dim=16, conv_kernel=4, num_heads=4,
                num_kv_heads=2, head_dim=16, rotary_fraction=0.25, expert_dim=24,
                num_experts=8, experts_per_token=2, experts_held=(2, 6),
                shared_expert_dim=24, aux_coef=AUX, compute_dtype="float32",
                attention="plain", head_chunk=64, moe_chunk=64, delta_chunk=32)
    base.update(kw)
    return HybridConfig(**base)


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


@pytest.fixture(scope="module")
def params():
    drawn = seeded_hybrid.make_params(seeded_hybrid.param_shapes(FILE, VOCAB, 4), 5, 8)
    assert jax.tree_util.tree_map(np.shape, drawn) == hybrid.param_shapes(_config())
    # a router wide enough that no choice is near a tie, and a decay mild
    # enough that the state carries over many positions
    for kind in drawn["periods"].values():
        kind["router"] = kind["router"] * 10
    drawn["periods"]["linear"]["a_log"] = drawn["periods"]["linear"]["a_log"] - 3.0
    return drawn


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(11)
    seq = rng.integers(1, VOCAB, (ROWS, T)).astype(np.int32)
    seq[1, 50:] = 0  # padded tails: they move no state, are routed nowhere
    seq[2, 7:] = 0
    targets = np.zeros_like(seq)
    targets[:, :-1] = seq[:, 1:]
    return seq, targets


def _reference(params, batch, dims=DIMS, how=ref.SOUND):
    seq, targets = (jnp.asarray(a) for a in batch)
    return jax.jit(lambda p: ref.loss_and_grads(p, seq, targets, dims, AUX, how))(params)


@pytest.fixture(scope="module")
def sound(params, batch):
    return _reference(params, batch)


def _flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {".".join(k.key for k in path): np.asarray(a) for path, a in leaves}


def _step(config, params, batch):
    loss_fn = hybrid.make_loss(config, _mesh())
    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}, None)


@pytest.fixture(scope="module")
def plain_step(params, batch):
    """The step as ``_config()`` has it (float32, the plain paths), worked once
    for the tests that compare against it."""
    return _step(_config(), params, batch)


@pytest.mark.parametrize("attention", ["plain", "flash"])
def test_loss_auxiliary_loss_and_every_gradient_match_the_reference(
        params, batch, sound, plain_step, attention):
    """float32 throughout; "flash" is the state pass's two Pallas programs and
    the three attention programs in their causal mode, interpreted. Two rows
    have padded tails, one shorter than a chunk."""
    (loss, aux), grads = (plain_step if attention == "plain" else
                          _step(_config(attention=attention), params, batch))
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 2e-5
    assert abs(float(aux["ce"]) - float(want_aux["ce"])) < 2e-5
    assert abs(float(aux["aux_loss"]) - float(want_aux["aux_loss"])) < 2e-5
    have, want_flat = _flat(grads), _flat(want_grads)
    assert sorted(have) == sorted(want_flat)
    for name, g in want_flat.items():
        scale = np.abs(g).max()
        assert scale > 0, name
        assert np.abs(have[name] - g).max() < 2e-3 * scale, name
    real = int((batch[0] > 0).sum())
    assert int(aux["moe_assignments"]) == 4 * 2 * real     # layers x K x real tokens
    assert 0 < int(aux["moe_held_assignments"]) < int(aux["moe_assignments"])
    assert int(aux["moe_dropped"]) == 0


@pytest.mark.parametrize("attention", ["plain", "flash"])
def test_bfloat16_matmul_inputs_stay_near_the_reference(params, batch, sound, attention):
    """As the cell runs it: bfloat16 into every product, float32 out of it;
    with the programs the conv writes v in bfloat16 and reads its cotangent so."""
    (loss, aux), grads = _step(_config(compute_dtype="bfloat16", attention=attention),
                               params, batch)
    want, want_aux, want_grads = sound
    assert abs(float(loss) - float(want)) < 5e-3
    assert abs(float(aux["aux_loss"]) - float(want_aux["aux_loss"])) < 1e-3
    have, want_flat = _flat(grads), _flat(want_grads)
    for name in ("periods.linear.w_qkvz", "periods.linear.conv", "periods.linear.w_out",
                 "periods.full.wq", "periods.linear.s_down", "periods.full.w_down", "head"):
        rel = np.linalg.norm(have[name] - want_flat[name]) / np.linalg.norm(want_flat[name])
        assert rel < 0.05, (name, rel)


@pytest.mark.parametrize("control,tensor,least", [
    ({"decay": False}, "periods.linear.w_qkvz", 1e-2),
    ({"delta": False}, "periods.linear.w_qkvz", 1e-2),
    ({"shared_gate": False}, "periods.linear.s_down", 1e-2),
    ({"precision": "bfloat16"}, "periods.linear.router", 1e-3)])
def test_each_control_of_the_reference_reads_other_gradients(
        params, batch, sound, control, tensor, least):
    """What the benchmark's ``--control 1`` plants, at this size: each moves a
    gradient of the path it touches by far more than the program differs
    (2e-6 of the gradient's norm in float32)."""
    sound = _flat(sound[2])[tensor]
    wrong = _flat(_reference(params, batch, how={**ref.SOUND, **control})[2])[tensor]
    assert np.linalg.norm(wrong - sound) > least * np.linalg.norm(sound)


REWORKED = {"no-remat": dict(remat=False), "chunk-16": dict(delta_chunk=16),
            "chunk-64": dict(delta_chunk=64), "head-whole": dict(head_chunk=0),
            "programs": dict(attention="flash")}


@pytest.mark.parametrize("case", list(REWORKED))
def test_remat_and_chunks_change_nothing(params, batch, plain_step, case):
    (loss, _), grads = plain_step
    (other, _), other_grads = _step(_config(**REWORKED[case]), params, batch)
    assert abs(float(loss) - float(other)) < 1e-5
    for name, g in _flat(grads).items():
        assert np.abs(_flat(other_grads)[name] - g).max() < 1e-4 * max(np.abs(g).max(), 1e-8), name


# ---- the delta rule ----------------------------------------------------------

def _rule_inputs(t: int, seed: int = 0, heads=(2, 2, 4)):
    """``heads``: rows, key heads and value heads; 2 x 4 row-heads unless said."""
    rng = np.random.default_rng(seed)
    (b, hk, hv), dk, dv = heads, 16, 32
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    q = ref.l2_normalised(draw(b, t, hk, dk)) / 4.0
    k = ref.l2_normalised(draw(b, t, hk, dk))
    g = -0.5 * jnp.asarray(rng.random((b, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.random((b, t, hv)), jnp.float32)
    return q, k, draw(b, t, hv, dv), g, beta


def _token_by_token(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    return jnp.stack([ref.recurrence(jnp.repeat(q[b], rep, 1), jnp.repeat(k[b], rep, 1),
                                     v[b], g[b], beta[b], ref.SOUND, 32)
                      for b in range(q.shape[0])])


#: rows, key heads and value heads whose row-heads pick each block of the state
#: pass's programs: 8, 4, 2 and 1 row-heads a grid step
BLOCKS = {8: (2, 2, 4), 4: (1, 2, 4), 2: (1, 2, 6), 1: (1, 1, 3)}
RULE_CASES = ([pytest.param(chunk, kernels, 8, id=f"{how}-{chunk}")
               for chunk in (16, 32, 64) for kernels, how in ((False, "scan"), (True, "programs"))]
              + [pytest.param(32, True, g, id=f"programs-32-block-of-{g}") for g in (4, 2, 1)])


@pytest.mark.parametrize("chunk,kernels,block", RULE_CASES)
def test_the_chunked_rule_matches_the_recurrence_forward_and_gradient(chunk, kernels, block):
    """A length of 100 is no multiple of any of the chunks: the tail is padded
    with positions that neither move nor read the state. The programs work 8
    row-heads a grid step at 2 rows of 4 value heads, and 4, 2 and 1 at the
    row-heads that 8, then 4, then 2 do not divide."""
    b, _, hv = BLOCKS[block]
    assert delta_rule.heads_per_step(b * hv, chunk, 16, 32, 4) == block
    inputs = _rule_inputs(100, heads=BLOCKS[block])
    with jax.default_matmul_precision("highest"):
        want = _token_by_token(*inputs)
        weight = jnp.asarray(np.random.default_rng(1).standard_normal(want.shape), jnp.float32)
        want_grads = jax.grad(lambda *a: (_token_by_token(*a) * weight).sum(),
                              argnums=(0, 1, 2, 3, 4))(*inputs)
        rule = lambda *a: delta_rule.gated_delta_rule(  # noqa: E731
            *a, chunk=chunk, dtype=jnp.float32, kernels=kernels, interpret=True)
        have = rule(*inputs)
        grads = jax.grad(lambda *a: (rule(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(*inputs)
    assert have.shape == want.shape == (b, 100, hv, 32)
    assert np.abs(np.asarray(have - want)).max() < 1e-5
    for name, a, b in zip("qkvgb", grads, want_grads):
        assert np.abs(np.asarray(a - b)).max() < 1e-4 * np.abs(np.asarray(b)).max(), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,block", [(8, 8), (4, 4), (6, 2), (3, 1)])
def test_the_state_pass_programs_match_the_scan_forward_and_every_gradient(rows, block, dtype):
    """The two Pallas programs, interpreted, against ``state_pass_plain`` and
    what JAX differentiates of it, at row-heads that pick each block. A head's
    arithmetic is the same in every block: with bfloat16 inputs, as the cell
    has them, the programs' results are the scan's to the last bit."""
    dtype = jnp.dtype(dtype)
    chunks, c, dk, dv = 5, 16, 16, 32
    assert delta_rule.heads_per_step(rows, c, dk, dv, dtype.itemsize) == block
    rng = np.random.default_rng(rows)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32)  # noqa: E731
    w, kd = draw(rows, chunks, c, dk).astype(dtype), draw(rows, chunks, c, dk).astype(dtype)
    u = draw(rows, chunks, c, dv)
    decay = jnp.asarray(0.5 + 0.5 * rng.random((rows, chunks)), jnp.float32)
    weights = draw(rows, chunks, c, dv), draw(rows, chunks, dk, dv)

    def loss(pass_fn):
        def of(w, u, kd, decay):
            v_new, starts = pass_fn(w, u, kd, decay)
            return ((v_new * weights[0]).sum() + (starts * weights[1]).sum(),
                    (v_new, starts))
        return jax.value_and_grad(of, argnums=(0, 1, 2, 3), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, have), grads = loss(lambda *a: delta_rule.state_pass(*a, True))(w, u, kd, decay)
        (_, want), want_grads = loss(delta_rule.state_pass_plain)(w, u, kd, decay)
    for name, a, b in zip(("v_new", "starts"), have, want):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype, name
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        if dtype == jnp.bfloat16:
            assert np.array_equal(a, b), name
        else:
            assert np.abs(a - b).max() < 1e-5, name
    for name, a, b in zip(("w", "u", "kd", "decay"), grads, want_grads):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert a.shape == b.shape and np.abs(b).max() > 0, name
        assert np.abs(a - b).max() < (3e-2 if dtype == jnp.bfloat16 else 1e-4) * np.abs(b).max(), name


def test_a_grid_step_takes_the_row_heads_that_divide_and_fit():
    """The chooser alone: the most of 8, 4, 2, 1 that divide the row-heads, and
    of those the most whose blocks of the transpose program, double-buffered,
    stay within the module's budget."""
    cell = (64, 128, 128, 2)                      # chunk, dk, dv, bfloat16
    a_head = (4 * 64 * 128 + 2 * 64 * 128 + 2 * 128 * 128) * 2 + (64 * 128 + 2 * 128) * 4
    assert a_head == 197_632 and 2 * 8 * a_head <= delta_rule.PASS_VMEM_BYTES < 2 * 16 * a_head
    assert delta_rule.heads_per_step(2 * 32, *cell) == 8     # the cell: 2 rows of 32 value heads
    assert [delta_rule.heads_per_step(rows, *cell) for rows in (128, 12, 6, 3, 1, 7)] == [
        8, 4, 2, 1, 1, 1]
    # float32 inputs nearly double a head's blocks, a key and value width of
    # 256 more than triples them, one of 512 takes eight times as much: the
    # budget refuses 8, then 4, then 2
    assert delta_rule.heads_per_step(64, 64, 128, 128, 4) == 4
    assert delta_rule.heads_per_step(64, 64, 256, 256, 2) == 2
    assert delta_rule.heads_per_step(64, 64, 512, 512, 2) == 1
    config = _config(max_len=8192, linear_value_heads=32, linear_key_dim=128,
                     linear_value_dim=128, compute_dtype="bfloat16", delta_chunk=64)
    assert hybrid.delta_heads_per_step(config, 2) == 8
    assert fit_attrs(config, 4, 8, 2, "cpu")["delta_heads_per_step"] == 8
    assert fit_attrs(_config(), 4, 8, 3, "cpu")["delta_heads_per_step"] == 4     # 3 rows x 4 heads
    # the conv's tile, where its programs run: 8,192 positions of 2 x 16 x 128 + 32 x 128 channels
    auto = _config(max_len=8192, linear_key_heads=16, linear_value_heads=32, linear_key_dim=128,
                   linear_value_dim=128, attention="auto")
    assert fit_attrs(auto, 4, 8, 2, "tpu")["conv_block"] == "1024x512"
    assert fit_attrs(auto, 4, 8, 2, "cpu")["conv_block"] == "plain"
    assert fit_attrs(_config(attention="flash"), 4, 8, 3, "cpu")["conv_block"] == "64x96"


def test_a_position_without_beta_or_decay_leaves_the_state():
    """What a padded slot is given: the positions after it read the state the
    positions before it left."""
    q, k, v, g, beta = _rule_inputs(48)
    hole = jnp.arange(48)[None, :, None] == 20
    g, beta = jnp.where(hole, 0.0, g), jnp.where(hole, 0.0, beta)
    keep = np.arange(48) != 20
    with jax.default_matmul_precision("highest"):
        whole = delta_rule.gated_delta_rule(q, k, v, g, beta, chunk=16, dtype=jnp.float32)
        cut = delta_rule.gated_delta_rule(*(a[:, keep] for a in (q, k, v, g, beta)),
                                          chunk=16, dtype=jnp.float32)
    assert np.abs(np.asarray(whole[:, keep] - cut)).max() < 1e-5


@pytest.mark.parametrize("size", [8, 48, 64])
def test_the_triangular_system_is_inverted_by_its_powers(size):
    rng = np.random.default_rng(size)
    lower = jnp.asarray(np.tril(rng.standard_normal((3, size, size)) * 0.3, -1), jnp.float32)
    inverse = delta_rule.unit_lower_inverse(lower)
    assert np.abs(np.asarray(inverse @ (jnp.eye(size) + lower)) - np.eye(size)).max() < 1e-4
    by_solve = jax.grad(lambda a: (jnp.linalg.inv(jnp.eye(size) + a) ** 2).sum())(lower)
    by_rule = jax.grad(lambda a: (delta_rule.unit_lower_inverse(a) ** 2).sum())(lower)
    scale = np.abs(np.asarray(by_solve)).max()
    assert np.abs(np.asarray(jnp.tril(by_rule - by_solve, -1))).max() < 1e-4 * scale


# ---- the full layer's kernel in its causal mode ----------------------------

def test_attention_programs_without_a_mask_match_plain_attention_at_width_256():
    """16 query heads on 2 key-value heads of width 256, the cell's layout,
    tiles smaller than the row so that the causal tile test is worked."""
    rng = np.random.default_rng(0)
    b, t, h, kv, d = 2, 64, 16, 2, 256
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((b, t, h, d), (b, t, kv, d), (b, t, kv, d)))
    weight = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    have = lambda q, k, v: sa.causal_attention(q, k, v, 16, 32, True)  # noqa: E731
    want = lambda q, k, v: plain_attention(  # noqa: E731
        q, jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2), causal=True)
    assert np.abs(np.asarray(have(q, k, v) - want(q, k, v))).max() < 1e-5
    assert np.abs(np.asarray(sa.causal_attention_plain(q, k, v) - want(q, k, v))).max() < 1e-5
    grads = jax.grad(lambda *a: (have(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    want_grads = jax.grad(lambda *a: (want(*a) * weight).sum(), (0, 1, 2))(q, k, v)
    for name, a, g in zip("qkv", grads, want_grads):
        assert np.abs(np.asarray(a - g)).max() < 1e-4 * np.abs(np.asarray(g)).max(), name


@pytest.mark.parametrize("t,bq,bk", [(128, 64, 32), (128, 16, 64), (64, sa.BLOCK_Q, sa.BLOCK_K)],
                         ids=["query-block-over-key-block", "key-block-over-query-block",
                              "one-block"])
def test_the_backward_program_without_a_mask_gives_the_twins_gradients(t, bq, bk):
    """The one backward program in its causal mode, 8 query heads on a
    key-value head of width 256 (the full layer's group): ``dq``, ``dk`` and
    ``dv`` against the plain twin's, at tiles whose edges differ either way
    (the key block clamped at the diagonal) and at one block."""
    rng = np.random.default_rng(t + bq)
    b, h, kv, d = 1, 8, 1, 256
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((b, t, h, d), (b, t, kv, d), (b, t, kv, d)))
    weight = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    program = lambda q, k, v: sa.causal_attention(q, k, v, bq, bk, True)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        have = jax.grad(lambda *a: (program(*a) * weight).sum(), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (sa.causal_attention_plain(*a) * weight).sum(),
                        (0, 1, 2))(q, k, v)
    for name, a, g in zip("qkv", have, want):
        assert a.shape == g.shape, name
        assert np.abs(np.asarray(a - g)).max() < 1e-4 * np.abs(np.asarray(g)).max(), name


def test_the_full_layers_backward_program_holds_one_key_value_head_a_step():
    """The cell's full layer (2 key-value heads of 8 query heads, 256 + 256, a
    row of 8,192, bfloat16): one key-value head a step, 16.8 MB of ``dk`` and
    ``dv`` in the 30 the step holds. A row too long for one head's pair is
    refused when the step is traced, with the numbers."""
    cell = _config(num_heads=16, num_kv_heads=2, head_dim=256, max_len=8192,
                   compute_dtype="bfloat16", attention="auto")
    assert hybrid.attention_backward_heads_per_step(cell) == 1
    held = sa.backward_step_bytes(1, 8, 256, 256, 8192, 2, False)
    assert 4 * 8192 * 512 < held < 32e6
    assert sa.backward_query_block(1, 8, 256, 256, 8192, 2, False, sa.BLOCK_Q, sa.BLOCK_K) == 512
    attrs = fit_attrs(cell, 4, 8, 2, "tpu")
    assert (attrs["attention_backward_programs"], attrs["attention_backward_heads_per_step"]) == (
        1, 1)
    assert fit_attrs(cell, 4, 8, 2, "cpu")["attention_backward_programs"] == 0
    assert sa.backward_heads_per_step(2, 8, 256, 256, 16384, 2) == 1
    with pytest.raises(ValueError, match=r"a row of 65536 positions is too long(.|\n)*"
                                         r"widths 256 and 256(.|\n)*67,108,864"):
        sa.backward_heads_per_step(2, 8, 256, 256, 65536, 2)
    q = jax.ShapeDtypeStruct((1, 65536, 8, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="too long for the attention's backward program"):
        jax.eval_shape(jax.grad(lambda q, k, v: sa.causal_attention(q, k, v).sum().astype(
            jnp.float32), (0, 1, 2)), q, q, q)


# ---- the experts -------------------------------------------------------------

def test_the_shares_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """Four programs, each holding 2 of 8 experts with the same router and the
    same shared expert: their routed parts, and the shared expert's counted
    once, add up to the reference's uncut expert layer."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 96, 32)), jnp.float32)
    real = jnp.asarray(np.arange(96) < 90)[None]
    shapes = seeded_hybrid.param_shapes(FILE, VOCAB, 8)["periods"]["full"]
    names = ("n2", "router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down", "s_g")
    drawn = seeded_hybrid.make_params({k: shapes[k][1:] for k in names}, 9, 8)
    drawn["router"] = drawn["router"] * 10
    dims = {**DIMS, "experts_held": (0, 8)}
    with jax.default_matmul_precision("highest"):
        want = ref.experts_block(drawn, x[0], real[0], dims, ref.SOUND)[0] - x[0]
        routed, shared, held = 0.0, None, 0
        for lo in range(0, 8, 2):
            config = _config(experts_held=(lo, lo + 2))
            share = {**drawn, **{k: drawn[k][lo:lo + 2] for k in ("w_gate", "w_up", "w_down")}}
            with_shared, stats = experts_module.expert_half(
                config, "cpu", x, share, real, hybrid.norm0)
            alone = {**share, "s_down": jnp.zeros_like(drawn["s_down"])}
            without, _ = experts_module.expert_half(
                config, "cpu", x, alone, real, hybrid.norm0)
            assert int(stats["dropped"]) == 0
            held += int(stats["held_assignments"])
            routed = routed + (without - x)
            mine = with_shared - without
            assert shared is None or np.abs(np.asarray(mine - shared)).max() < 1e-6
            shared = mine
    assert held == int(stats["assignments"]) == 2 * 90
    assert np.abs(np.asarray(routed[0] + shared[0] - want)).max() < 1e-5


# ---- the template ------------------------------------------------------------

def test_the_engine_takes_the_backbone_at_the_cells_sizes():
    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence.engine import SASRecAlgorithm

    config = SASRecAlgorithm(Params({
        "backbone": "hybrid_linear", "hiddenSize": 2048, "numLayers": 4,
        "fullAttentionInterval": 4, "linearKeyHeads": 16, "linearValueHeads": 32,
        "linearKeyDim": 128, "linearValueDim": 128, "convKernel": 4, "numHeads": 16,
        "numKvHeads": 2, "headDim": 256, "partialRotaryFactor": 0.25, "expertDim": 512,
        "numExperts": 512, "expertsPerToken": 10, "expertsHeld": [0, 32],
        "sharedExpertDim": 512, "ropeTheta": 10000000, "batchSize": 2}))._config(18991, 8192)
    assert isinstance(config, HybridConfig) and config.held == 32
    assert (config.periods, config.linear_layers, config.rotary_dim) == (1, 3, 64)
    assert hybrid.count_params(config) == 625_667_136
    # a whole layer's tokens at once: a pass of 20,480 rows is twice their even share
    assert experts_module.moe_chunk_of(config) >= 16384
    assert experts_module.pass_plan(config, 16384) == (20480, 8)
    # a row's states, 3 layers x 32 heads x 128 x 128 float32; a layer's chunks' for 2 rows
    assert hybrid.delta_state_bytes(config) == 3 * 32 * 128 * 128 * 4
    assert hybrid.delta_kept_bytes(config, 2) == 2 * 128 * 32 * 128 * 128 * 4
    attrs = fit_attrs(config, 4, 8, 2, "cpu")
    assert (attrs["backbone"], attrs["linear_layers"], attrs["full_layers"], attrs["delta_chunk"],
            attrs["delta_heads_per_step"], attrs["experts_shared"]) == (
                "hybrid_linear", 3, 1, 64, 8, 1)
    whole = SASRecAlgorithm(Params({"backbone": "hybrid_linear", "numExperts": 16}))._config(12, 64)
    assert whole.experts_held == (0, 16)
    with pytest.raises(ValueError, match="'sparse_moe', 'hybrid_linear'"):
        SASRecAlgorithm(Params({"backbone": "mamba"}))._config(12, 64)
    with pytest.raises(ValueError, match="whole periods"):
        SASRecAlgorithm(Params({"backbone": "hybrid_linear", "numLayers": 6}))._config(12, 64)


def test_a_step_moves_every_parameter_and_drops_nothing(params, batch):
    config = _config()
    _, place, step_fn, _ = make_fit(config, _mesh())
    placed, opt_state = place(params)
    moments = [a for a in jax.tree_util.tree_leaves(opt_state) if a.ndim]
    assert sum(a.size for a in moments) == 2 * hybrid.count_params(config)
    feed = {"seq": jnp.asarray(batch[0]), "target": jnp.asarray(batch[1])}
    after, _, loss, aux = step_fn(placed, opt_state, feed, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss)) and int(aux["moe_dropped"]) == 0
    for name, before in _flat(params).items():
        assert (_flat(after)[name] != before).any(), name


def _cyclic(n_items=12, t=8, rows=96, seed=0):
    starts = np.random.default_rng(seed).integers(0, n_items, rows)
    return ((starts[:, None] + np.arange(t)[None, :]) % n_items + 1).astype(np.int32)


def test_the_backbone_learns_a_cycle_and_reports_its_fit(caplog):
    import logging

    from predictionio_tpu.obs.trace import global_tracer

    config = HybridConfig(
        num_items=12, max_len=8, hidden_size=32, num_layers=2, full_attention_interval=2,
        linear_key_heads=2, linear_value_heads=2, linear_key_dim=8, linear_value_dim=8,
        num_heads=4, num_kv_heads=2, head_dim=8, rotary_fraction=0.5, expert_dim=32,
        num_experts=4, experts_per_token=2, experts_held=(0, 4), shared_expert_dim=16,
        learning_rate=0.01, batch_size=32, epochs=12, attention="plain", delta_chunk=4)
    with caplog.at_level(logging.INFO, logger="pio.sequence"):
        trained, losses = train_sasrec(config, _cyclic(), _mesh(), log_every=1)
    assert losses[-1] < 0.6 * losses[0]
    hits = 0
    for start in range(12):
        prefix = (start + np.arange(4)) % 12 + 1
        scores = score_next_items_batch(trained, config, [prefix])[0]
        hits += int(np.argmax(scores) == (start + 4) % 12)
    assert hits >= 10
    attrs = next(s for tr in global_tracer().snapshot(limit=50)["recent"]
                 for s in tr["spans"] if s["op"] == "seq.fit")["attrs"]
    assert attrs["backbone"] == "hybrid_linear" and attrs["passes"] == 1
    assert (attrs["linear_layers"], attrs["full_layers"], attrs["delta_chunk"],
            attrs["experts_shared"], attrs["experts_held"]) == (1, 1, 4, 1, 4)
    assert attrs["delta_heads_per_step"] == 8                      # 32 rows x 2 value heads
    assert attrs["conv_block"] == "plain"                          # ``attention="plain"``
    assert (attrs["attention_backward_programs"],
            attrs["attention_backward_heads_per_step"]) == (0, 2)       # ``attention="plain"``
    assert attrs["delta_state_bytes"] == 2 * 8 * 8 * 4
    assert attrs["delta_kept_bytes"] == 32 * 2 * 2 * 8 * 8 * 4     # rows x chunks x a state
    assert attrs["moe_dropped"] == 0 and attrs["moe_held_assignments"] == attrs["moe_assignments"]
    line = next(r.getMessage() for r in caplog.records if "seq_fit:" in r.getMessage())
    for word in ("backbone=hybrid_linear", "linear_layers=1", "full_layers=1", "delta_chunk=4",
                 "delta_heads_per_step=8", "conv_block=plain", "delta_state_bytes=512",
                 "delta_kept_bytes=",
                 "experts_shared=1", "moe_dropped=0"):
        assert word in line, (word, line)


def test_the_linear_mixers_scope_is_not_read_as_attention():
    """``linear_attention`` is one component: the accepted readers leave it to
    ``layers``, the new one takes it apart by leaf."""
    from benchmarks import scopes_hybrid, scopes_leaf, scopes_seq

    name = ("jit(train_step)/transpose(jvp(seq.pass1))/layers/while/body/closed_call/while/body/"
            "closed_call/checkpoint/rematted_computation/linear_attention/delta/pallas_call")
    assert scopes_seq.parse_scope(name) == ("pass1", "layers")
    assert scopes_seq.kernel_kind(name) is None
    assert scopes_leaf.place_of(name).stage == "layers"
    assert scopes_hybrid.place_of(name) == ("linear", "delta")
    assert scopes_hybrid.place_of(name.replace("/delta/", "/")) == ("linear", None)
    shared = "jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/moe/shared/dot_general"
    assert scopes_hybrid.place_of(shared) == ("shared", None)
    full = ("jit(train_step)/jvp(seq.pass1)/layers/while/body/closed_call/attention/kernel/"
            "pallas_call")
    assert scopes_hybrid.place_of(full) is None
    assert scopes_seq.kernel_kind(full) == "forward"


def test_train_deploy_query_with_the_hybrid_backbone(storage_env, tmp_path):
    """``examples/sequence/engine-hybrid-linear.json`` through ``run_train`` and
    the query server, as ``pio train`` and ``pio deploy`` take it, cut to a
    size a test can train: a session of three items is continued by the next
    of the cycle."""
    import datetime as dt
    import json
    import os
    import urllib.request

    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.create_server import create_query_server
    from predictionio_tpu.workflow.json_extractor import load_engine_variant

    app_id = storage_env.get_meta_data_apps().insert(App(name="HybridShop"))
    le = storage_env.get_l_events()
    le.init_channel(app_id)
    rng = np.random.default_rng(3)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    le.batch_insert([
        Event(event="view", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{(start + step) % 12}",
              properties=DataMap({}),
              event_time=t0 + dt.timedelta(seconds=u * 1000 + step))
        for u in range(48) for start in [int(rng.integers(0, 12))] for step in range(8)
    ], app_id=app_id)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "examples", "sequence", "engine-hybrid-linear.json")) as f:
        variant = json.load(f)
    variant["datasource"]["params"].update(appName="HybridShop", eventNames=["view"])
    variant["preparator"]["params"]["maxLen"] = 8
    algo = variant["algorithms"][0]["params"]
    assert algo["backbone"] == "hybrid_linear"
    algo.update(hiddenSize=32, numLayers=2, fullAttentionInterval=2, linearKeyDim=8,
                linearValueDim=8, numHeads=4, headDim=8, partialRotaryFactor=0.5,
                expertDim=32, numExperts=4, expertsPerToken=2, expertsHeld=[0, 4],
                sharedExpertDim=16, epochs=12, batchSize=32, learningRate=0.01,
                attention="plain")
    path = tmp_path / "engine.json"
    path.write_text(json.dumps(variant))
    loaded = load_engine_variant(str(path))
    run_train(loaded)
    thread, _ = create_query_server(loaded, host="127.0.0.1", port=0)
    thread.start()

    def post(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{thread.port}/queries.json", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read())

    try:
        session = post({"items": ["i3", "i4", "i5"], "num": 3})
        user = post({"user": "u0", "num": 3})
    finally:
        thread.stop()
    assert "i6" in [s["item"] for s in session["itemScores"]], session
    assert len(user["itemScores"]) == 3
