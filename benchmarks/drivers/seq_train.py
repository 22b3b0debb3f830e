"""Whole optimizer steps of the sequence template for the window: histories
drawn and packed once, one warm step, then steps back to back, a device sync
after each.

The template's own pieces in the template's own order: ``SequencePreparator``
packs the histories (a user a row, the last ``maxLen`` events),
``SASRecAlgorithm`` reads the engine parameters into the backbone's
configuration, and ``models/sequence/model.py:make_fit`` gives the jitted step
``train_sasrec``'s loop runs (same shardings, same donation, Adam). A fit runs
epochs, a window runs for seconds, so the loop is here.

``correct`` judges the window's own step twice, against ``reference_ouro.py``
on the parameters the step started from and its rows, in blocks of rows so
that it fits beside the resident state: the warm step, on the seed's draw
(``seeded_*``), and one more step on the state the window left. Each time:
every exit's loss and the total, the exit distribution of every position, the
gradients of a named subset (``reference_ouro.subset_of``; ``GRADIENTS``), and
the subset's change over the step against Adam worked in NumPy float64 from
the step's own gradient and the moments it started from. The step returns no
gradient; Adam's first moment does: ``g = (mu' - b1 mu) / (1 - b1)``. A
tensor with no limit at a state is printed (``unjudged``): layer 0's ``W_q``
on the state the window left (PERF.md section 2).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts_seq, reference_ouro, seeded_histories, trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.harness import REHEARSAL_CUT, check as _check, traced_window

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # optax.adam's defaults, the trainer's
#: the tensors whose gradients are compared, as relative error in the Frobenius
#: norm; the gate's weight and bias are one tensor (a saturated gate leaves the
#: bias alone a gradient too near zero to have a relative error). Layer 0's
#: ``W_q`` and the last layer's ``W_down`` are sums over the passes
GRADIENTS = {"gate": ("gate_w", "gate_b"), "final_norm": ("final_norm",),
             "wq_first": ("wq_first",), "w_down_last": ("w_down_last",),
             "head_rows": ("head_rows",)}
#: the judged steps: the prefix of their checks' names and their limits' key
STATES = {"seeded": "seeded_", "trained": ""}


def adam_change(g, mu, nu, count: int, lr: float):
    """The change ``optax.adam(lr)`` makes to a tensor whose gradient is ``g``,
    from the moments and the step count it starts with; NumPy float64."""
    t = count + 1
    mu = ADAM_B1 * mu + (1 - ADAM_B1) * g
    nu = ADAM_B2 * nu + (1 - ADAM_B2) * g * g
    return -lr * (mu / (1 - ADAM_B1 ** t)) / (np.sqrt(nu / (1 - ADAM_B2 ** t)) + ADAM_EPS)


def _flat(tree: dict, parts) -> np.ndarray:
    return np.concatenate([np.ravel(tree[part]) for part in parts])


def _rel(have: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(have - want) / max(np.linalg.norm(want), 1e-30))


def compared(have: dict, want: dict, real, limits: dict, lr: float, prefix: str = ""):
    """``(rows, unjudged)``: each number of one judged step as ``(name, value,
    limit)``, and the gradients the state has no limit for. ``have`` is the
    step (``judged`` below), ``want`` the reference on what it started from."""
    rows = [("loss_abs_err", abs(have["loss"] - want["loss"]), limits["loss_abs_err_limit"])]
    rows += [(f"exit{n + 1}_loss_abs_err", float(abs(a - b)), limits["loss_abs_err_limit"])
             for n, (a, b) in enumerate(zip(have["exit_ce"], want["exit_ce"]))]
    rows.append(("exit_p_abs_err", float(np.abs(have["p"] - want["p"])[:, real].max()),
                 limits["exit_p_abs_err_limit"]))
    old, new = have["old"], have["new"]
    grads = {k: (new["mu"][k] - ADAM_B1 * old["mu"][k]) / (1 - ADAM_B1) for k in new["mu"]}
    unjudged = {}
    for name, parts in GRADIENTS.items():
        err = _rel(_flat(grads, parts), _flat(want["grads"], parts))
        if name in limits["grad_rel_err_limits"]:
            rows.append((f"grad_{name}_rel_err", err, limits["grad_rel_err_limits"][name]))
        else:
            unjudged[f"{prefix}grad_{name}_rel_err"] = err
    every = sorted(grads)
    moved = {k: new["params"][k] - old["params"][k] for k in every}
    by_adam = {k: adam_change(grads[k], old["mu"][k], old["nu"][k], old["count"], lr)
               for k in every}
    rows.append(("adam_update_rel_err", _rel(_flat(moved, every), _flat(by_adam, every)),
                 limits["adam_update_rel_err_limit"]))
    return [(prefix + name, value, limit) for name, value, limit in rows], unjudged


def _algorithm_params(engine: dict, published: dict, traffic: dict, rehearse: bool) -> dict:
    """The engine parameters of the configuration's file, held to the
    published keys beside them; a rehearsal swaps in its cut widths."""
    params = dict(engine["algorithms"][0]["params"])
    same = {"hiddenSize": "hidden_size", "numHeads": "num_attention_heads",
            "headDim": "head_dim", "ffnDim": "intermediate_size",
            "numLayers": "num_hidden_layers", "utSteps": "total_ut_steps",
            "ropeTheta": "rope_theta", "rmsNormEps": "rms_norm_eps",
            "earlyExitThreshold": "early_exit_threshold"}
    for ours, theirs in same.items():
        if params[ours] != published[theirs]:
            raise ValueError(f"engine param {ours}={params[ours]} is not the"
                             f" configuration's {theirs}={published[theirs]}")
    if rehearse:
        cut = traffic["rehearsal"]
        params.update(hiddenSize=cut["hidden_size"], numHeads=cut["num_attention_heads"],
                      headDim=cut["head_dim"], ffnDim=cut["intermediate_size"],
                      numLayers=cut["num_hidden_layers"],
                      batchSize=cut["users_per_step"])
    return params


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import engine as seq_engine
    from predictionio_tpu.models.sequence import looped
    from predictionio_tpu.models.sequence import model as seq_model
    from predictionio_tpu.parallel.mesh import put_global
    from predictionio_tpu.workflow.context import RuntimeContext

    if not hasattr(seq_model, "make_fit"):
        raise SystemExit(
            f"{ctx.cell}: this program's sequence template has one backbone"
            " (models/sequence/model.py has no make_fit): it cannot train the"
            " looped decoder this cell times")
    traffic, config_file = ctx.traffic, ctx.config
    data, check = config_file["data"], traffic["correct"]
    cut = traffic["rehearsal"] if ctx.rehearse else {}
    n_users = data["users"] // REHEARSAL_CUT if ctx.rehearse else data["users"]
    n_events = data["events"] // REHEARSAL_CUT if ctx.rehearse else data["events"]
    vocab = cut.get("vocab_size", config_file["vocab_size"])
    max_len = cut.get("max_len", traffic["max_len"])
    per_step = cut.get("users_per_step", traffic["users_per_step"])
    compiles = CompileCounter()
    setup: dict = {}
    clock = time.perf_counter

    # ---- set-up: histories, packing, parameters, the step -----------------
    t = clock()
    histories = seeded_histories.make_histories(data, n_events, n_users, vocab - 1, ctx.seed)
    setup["histories_s"] = clock() - t

    rctx = RuntimeContext({"pio.mesh_shape": [ctx.chips, 1],
                           "pio.mesh_axes": ["data", "seq"]})
    mesh = rctx.mesh
    t = clock()
    packed = seq_engine.SequencePreparator(Params({"maxLen": max_len})).prepare(
        rctx, seq_engine.SequencesData(
            sequences=histories, user_ids=[], item_ids=[None] * (vocab - 1)))
    setup["seq_pack_s"] = clock() - t
    inputs = packed.matrix
    targets = np.zeros_like(inputs)
    targets[:, :-1] = inputs[:, 1:]

    algorithm = seq_engine.SASRecAlgorithm(Params(_algorithm_params(
        config_file["engine"], config_file, traffic, ctx.rehearse)))
    config = algorithm._config(vocab - 1, max_len)
    dims = {"num_heads": config.num_heads, "head_dim": config.head_dim,
            "rope_theta": config.rope_theta, "rms_eps": config.rms_eps,
            "ut_steps": config.ut_steps}
    shapes = seeded_histories.param_shapes(
        vocab, config.hidden_size, config.num_heads * config.head_dim,
        config.ffn_dim, config.num_layers)
    t = clock()
    host_params = seeded_histories.make_params(shapes, ctx.seed)
    setup["params_s"] = clock() - t

    _, place, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    t = clock()
    params, opt_state = place(host_params)  # the host's copy stays, for the reference
    jax.block_until_ready((params, opt_state))
    setup["h2d_s"] = clock() - t
    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))

    order = seeded_histories.batch_order(n_users, ctx.seed)
    rng = jax.random.PRNGKey(0)  # the looped block draws nothing from it
    taken = 0

    def step():
        """One optimizer step on the next ``per_step`` users; synced."""
        nonlocal params, opt_state, taken
        rows = order[taken * per_step:(taken + 1) * per_step]
        if rows.size < per_step:
            raise RuntimeError("the window outran the users: no batch repeats")
        taken += 1
        batch = {"seq": put_global(inputs[rows], seq_shard),
                 "target": put_global(targets[rows], seq_shard)}
        params, opt_state, loss, aux = step_fn(params, opt_state, batch, rng)
        return rows, float(loss), aux  # float(): the device has finished

    head_rows = seeded_histories.head_rows(
        vocab, cut.get("head_rows", check["head_rows"]), ctx.seed)

    def subset_state(*moments) -> dict:
        """The judged tensors, the named moments of Adam's and its step count,
        on the host."""
        adam = opt_state[0]
        trees = {"params": params, **{name: getattr(adam, name) for name in moments}}
        return {"count": int(adam.count), **{
            name: {k: np.asarray(v, np.float64)
                   for k, v in reference_ouro.subset_of(tree, head_rows).items()}
            for name, tree in trees.items()}}

    def judged() -> dict:
        """One step of the window's program with what ``correct`` reads of it."""
        old = subset_state("mu", "nu")
        rows, loss, aux = step()
        return {"rows": rows, "loss": loss, "old": old, "new": subset_state("mu"),
                "exit_ce": np.asarray(aux["exit_ce"], np.float64),
                "p": np.asarray(aux["p"]), "exit_p_mean": np.asarray(aux["exit_p"]).tolist()}

    t = clock()
    steps = {"seeded": judged()}  # the first warm step, on the seed's draw
    warm_loss = steps["seeded"]["loss"]
    for _ in range(traffic["warm_steps"] - 1):
        _, warm_loss, _ = step()
    setup["first_call_s"] = clock() - t
    setup["compile_requests"] = compiles.count
    setup["compile_s"] = compiles.seconds
    filled = int(np.count_nonzero(inputs))
    ctx.say(setup=setup, backbone=type(config).__name__, layers=config.num_layers,
            passes=config.ut_steps, parameters=param_bytes // 4, param_bytes=param_bytes,
            state_bytes=4 * param_bytes, users=n_users, max_len=max_len,
            users_per_step=per_step, slot_fill=filled / inputs.size,
            remat=config.remat, head_chunk=looped.head_chunk_of(config), warm_loss=warm_loss,
            memory_after_warm=[dev.memory_stats() for dev in ctx.devices])

    # ---- the window: whole steps only, a sync after each -------------------
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    spans: list = []
    window_rows: list = []
    losses: list = []
    compiles.reset()
    with traced_window(ctx.out_dir, ctx.trace) as trace_dir:
        setup_s = clock() - ctx.t0
        w0 = clock()
        while not losses or clock() - w0 < seconds:
            a = clock()
            rows, loss, _ = step()
            spans.append(("bench.step", a - w0, clock() - w0))
            window_rows.append(rows)
            losses.append(loss)
        window_s = clock() - w0
        in_window = compiles.count
    done = len(losses)
    memory_after_window = [dev.memory_stats() for dev in ctx.devices]

    flops = float(np.mean([counts_seq.step_model_flops(
        inputs[r], targets[r], config.hidden_size, config.num_heads * config.head_dim,
        config.ffn_dim, vocab, config.num_layers, config.ut_steps) for r in window_rows]))
    ctx.say(window_s=window_s, steps=done, losses=losses, model_flops_per_step=flops,
            step_s=[end - start for _, start, end in spans],
            memory_after_window=memory_after_window)

    # ---- correct: the warm step, and one more step of the window's program --
    block = cut.get("reference_rows", check["reference_rows"])
    started_from = {"seeded": host_params,   # and the state the window left
                    "trained": jax.tree_util.tree_map(np.asarray, params)}
    steps["trained"] = judged()
    # a loaded program keeps its temporaries reserved (8 GB of the chip here):
    # the trained state and the step's program go, and the reference has the
    # chip to itself with the parameters a judged step started from
    params = opt_state = None
    step_fn.clear_cache()
    jax.clear_caches()

    programs: dict = {}

    def referee(state: str, precision: str = "float32", shared: bool = True) -> dict:
        """The reference on the rows of a judged step, in blocks of rows, each
        block's sums over the batch's count of targets. One jitted program
        for every block, state, seed and run (the sampled head rows and the
        count are arguments), so the persistent cache holds it: unrolled over
        24 layer applications and their backward pass it takes two minutes to
        compile. Jitted, its temporaries are the program's and the reading of
        the chip's peak stays the timed path's."""
        fn = programs.setdefault((precision, shared), jax.jit(
            lambda p, picked, s, y, n: reference_ouro.loss_and_subset_grads(
                p, picked, s, y, dims, config.exit_beta, n, precision, shared)))
        on_chip = jax.device_put(started_from[state], ctx.devices[0])
        rows = steps[state]["rows"]
        count = jnp.float32((targets[rows] > 0).sum())
        total, exit_ce, grads, p_all = 0.0, 0.0, None, []
        for at in range(0, per_step, block):
            value, ref_aux, g = fn(
                on_chip, jnp.asarray(head_rows), jnp.asarray(inputs[rows[at:at + block]]),
                jnp.asarray(targets[rows[at:at + block]]), count)
            total += float(value)
            exit_ce = exit_ce + np.asarray(ref_aux["exit_ce"], np.float64)
            p_all.append(np.asarray(ref_aux["p"]))
            g = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), g)
            grads = g if grads is None else jax.tree_util.tree_map(np.add, grads, g)
        return {"loss": total, "exit_ce": exit_ce,
                "p": np.concatenate(p_all, axis=1), "grads": grads}

    def against(**how) -> tuple:
        """Both judged steps against the reference worked ``how``."""
        rows, unjudged = [], {}
        for state, prefix in STATES.items():
            r, u = compared(steps[state], referee(state, **how),
                            targets[steps[state]["rows"]] > 0, check[state],
                            config.learning_rate, prefix)
            rows += r
            unjudged.update(u)
        return [_check(*row) for row in rows], unjudged

    t = clock()
    checks, unjudged = against()
    reference_s = clock() - t
    finite = [np.isfinite(losses).all()]
    for have in steps.values():
        finite += [np.isfinite(have["loss"]), np.isfinite(have["exit_ce"]).all(),
                   np.isfinite(have["p"]).all()]
        finite += [np.isfinite(a).all() for a in have["new"]["mu"].values()]
    checks += [
        _check("nonfinite_values", int(sum(not ok for ok in finite)), 0),
        _check("compilations_in_window", in_window, 0),
    ]
    if ctx.control:
        for name, how in (("bfloat16", {"precision": "bfloat16"}),
                          ("unshared", {"shared": False})):
            low, _ = against(**how)
            ctx.say(control=name,
                    checks=[{k: c[k] for k in ("name", "value", "limit", "ok")} for c in low],
                    correct=all(c["ok"] for c in low))

    last = steps["trained"]
    ctx.say(unjudged=unjudged)
    ctx.say(reference_s=reference_s,
            targets_in_checked_steps=[int((targets[have["rows"]] > 0).sum())
                                      for have in steps.values()],
            loss=last["loss"], exit_losses=last["exit_ce"].tolist(),
            exit_p_mean={state: have["exit_p_mean"] for state, have in steps.items()},
            subset_change_norm={state: float(np.linalg.norm(_flat(
                {k: have["new"]["params"][k] - have["old"]["params"][k]
                 for k in have["new"]["params"]}, sorted(have["new"]["params"]))))
                for state, have in steps.items()},
            memory_after_reference=[dev.memory_stats() for dev in ctx.devices])
    out = {
        "end_to_end": {"train_iters_per_s": done / window_s, "setup_s": setup_s},
        "attempted": done, "failed": 0, "checks": checks, "setup": setup,
        "steps": done, "model_flops_per_step": flops,
        "flash_call": {"rows": per_step, "length": max_len, "heads": config.num_heads,
                       "head_dim": config.head_dim},
        "device_kind": ctx.devices[0].device_kind,
    }
    if ctx.trace:
        out["trace"] = trace_reduce.reduce_trace(trace_dir, spans)
    return out
