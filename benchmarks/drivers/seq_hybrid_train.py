"""Whole optimizer steps of the sequence template's hybrid backbone (linear
attention by a gated delta rule, a gated full-attention layer every fourth,
routed experts beside a shared one) for the window: lifelong histories drawn
and packed once (every row full), one warm step, then steps back to back, a
device sync after each.

The template's own pieces in the template's own order, as
``seq_sparse_train.py`` takes them for the sparse backbone:
``SequencePreparator`` packs the histories, ``SASRecAlgorithm`` reads the
engine parameters into the backbone's configuration,
``models/sequence/model.py:make_fit`` gives the jitted step ``train_sasrec``'s
loop runs.

``correct`` judges the window's own step twice against
``reference_qwen3next.py`` on the parameters the step started from and its
rows, a batch whole: the warm step, on the seed's draw (``seeded_*``), and one
more step on the state the window left. Each time: the loss and its two terms;
the gradients of a named subset that covers every new path
(``reference_qwen3next.subset_of``; the step returns no gradient, Adam's first
moment does: ``g = (mu' - b1 mu) / (1 - b1)``); and the subset's change over
the step against Adam worked in NumPy float64. ``moe_dropped`` of every step
the run made is 0, exact.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts_qwen3next, reference_qwen3next, seeded_histories
from benchmarks import seeded_hybrid, seeded_lifelong, trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.drivers.seq_train import ADAM_B1, _flat, _rel, adam_change
from benchmarks.harness import check as _check, traced_window

#: the tensors whose gradients are compared, as relative error in the
#: Frobenius norm: ``reference_qwen3next.subset_of``'s
GRADIENTS = ("w_qkvz_first", "conv_first", "a_log_first", "dt_bias_first", "wq_full",
             "router_first", "router_last", "w_down_first", "shared_gate_last",
             "final_norm", "head_rows")
STATES = {"seeded": "seeded_", "trained": ""}
CONTROLS = {"bfloat16": {"precision": "bfloat16"}, "no_decay": {"decay": False},
            "no_delta": {"delta": False}, "ungated_shared": {"shared_gate": False}}
#: the step's counts a window averages for the readers
COUNTS = ("moe_assignments", "moe_held_assignments", "moe_held_load_max",
          "moe_held_load_mean", "moe_passes", "moe_passes_run")

#: engine parameter -> the configuration file's key (a width is the source's)
PUBLISHED = {
    "hiddenSize": "hidden_size", "numLayers": "num_hidden_layers",
    "fullAttentionInterval": "full_attention_interval",
    "linearKeyHeads": "linear_num_key_heads", "linearValueHeads": "linear_num_value_heads",
    "linearKeyDim": "linear_key_head_dim", "linearValueDim": "linear_value_head_dim",
    "convKernel": "linear_conv_kernel_dim", "numHeads": "num_attention_heads",
    "numKvHeads": "num_key_value_heads", "headDim": "head_dim",
    "partialRotaryFactor": "partial_rotary_factor", "expertDim": "moe_intermediate_size",
    "numExperts": "num_experts", "expertsPerToken": "num_experts_per_tok",
    "sharedExpertDim": "shared_expert_intermediate_size", "ropeTheta": "rope_theta",
    "rmsNormEps": "rms_norm_eps"}


def _algorithm_params(config_file: dict, cut: dict) -> dict:
    """The engine parameters of the configuration's file, held to the
    published keys beside them; a rehearsal swaps in its cut widths."""
    params = dict(config_file["engine"]["algorithms"][0]["params"])
    for ours, theirs in PUBLISHED.items():
        if params[ours] != config_file[theirs]:
            raise ValueError(f"engine param {ours}={params[ours]} is not the"
                             f" configuration's {theirs}={config_file[theirs]}")
        params[ours] = cut.get(theirs, params[ours])
    lo, hi = params["expertsHeld"]
    if hi - lo != config_file["num_local_experts"]:
        raise ValueError(f"expertsHeld={params['expertsHeld']} is not the"
                         f" configuration's num_local_experts")
    params["expertsHeld"] = [lo, lo + cut.get("num_local_experts", hi - lo)]
    params["batchSize"] = cut.get("users_per_step", params["batchSize"])
    return params


def gradients_of(have: dict) -> dict:
    """A judged step's gradients, from Adam's first moment before and after."""
    old, new = have["old"]["mu"], have["new"]["mu"]
    return {k: (new[k] - ADAM_B1 * old[k]) / (1 - ADAM_B1) for k in new}


def compared(have: dict, want: dict, limits: dict, lr: float, prefix: str = ""):
    """Each number of one judged step as ``(name, value, limit)``; a number the
    workload gives no limit yet has the limit ``inf`` (a first reading)."""
    limit = lambda name: limits.get(name + "_limit", float("inf"))  # noqa: E731
    rows = [(name + "_abs_err", abs(have[name] - want[name]), limit(name + "_abs_err"))
            for name in ("loss", "ce", "aux_loss")]
    old, new = have["old"], have["new"]
    grads = gradients_of(have)
    by_tensor = limits.get("grad_rel_err_limits", {})
    rows += [(f"grad_{name}_rel_err", _rel(grads[name], want["grads"][name]),
              by_tensor.get(name, float("inf"))) for name in GRADIENTS]
    every = sorted(grads)
    moved = {k: new["params"][k] - old["params"][k] for k in every}
    by_adam = {k: adam_change(grads[k], old["mu"][k], old["nu"][k], old["count"], lr)
               for k in every}
    rows.append(("adam_update_rel_err", _rel(_flat(moved, every), _flat(by_adam, every)),
                 limit("adam_update_rel_err")))
    return [(prefix + name, float(value), lim) for name, value, lim in rows]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import engine as seq_engine
    from predictionio_tpu.models.sequence import looped, model as seq_model
    from predictionio_tpu.parallel.mesh import put_global
    from predictionio_tpu.workflow.context import RuntimeContext

    if "hybrid_linear" not in getattr(seq_engine.SASRecAlgorithm, "BACKBONES", ()):
        raise SystemExit(
            f"{ctx.cell}: this program's sequence template has no hybrid_linear backbone"
            " (models/sequence/engine.py): it cannot train the decoder this cell times")
    from predictionio_tpu.models.sequence import hybrid, sparse_moe

    traffic, config_file = ctx.traffic, ctx.config
    check = traffic["correct"]
    cut = traffic["rehearsal"] if ctx.rehearse else {}
    limits = cut.get("correct", check)   # a rehearsal's widths have their own readings
    data = {**config_file["data"], **{k: cut[k] for k in ("users", "min_events", "mean_events")
                                      if k in cut}}
    vocab = cut.get("vocab_size", config_file["vocab_size"])
    max_len = cut.get("max_len", traffic["max_len"])
    per_step = cut.get("users_per_step", traffic["users_per_step"])
    compiles = CompileCounter()
    setup: dict = {}
    clock = time.perf_counter

    # ---- set-up: histories, packing, parameters ----------------------------
    t = clock()
    histories = seeded_lifelong.make_histories(data, data["users"], vocab - 1, ctx.seed)
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

    algorithm = seq_engine.SASRecAlgorithm(Params(_algorithm_params(config_file, cut)))
    config = algorithm._config(vocab - 1, max_len)
    #: the configuration file's keys at the sizes that run (a rehearsal's are cut)
    counted = {**config_file, **{k: cut[k] for k in PUBLISHED.values() if k in cut},
               "num_local_experts": config.held}
    dims = {"linear_key_heads": config.linear_key_heads,
            "linear_value_heads": config.linear_value_heads,
            "linear_key_dim": config.linear_key_dim, "linear_value_dim": config.linear_value_dim,
            "conv_kernel": config.conv_kernel, "num_heads": config.num_heads,
            "num_kv_heads": config.num_kv_heads, "head_dim": config.head_dim,
            "rotary_dim": config.rotary_dim, "experts_per_token": config.experts_per_token,
            "experts_held": config.experts_held, "rope_theta": config.rope_theta,
            "rms_eps": config.rms_eps,
            "query_block": cut.get("query_block", check["query_block"])}
    t = clock()
    host_params = seeded_hybrid.make_params(
        seeded_hybrid.param_shapes(counted, vocab, config.held), ctx.seed,
        2 * config_file["published"]["num_hidden_layers"])
    # a rehearsal's few heads draw their decay as the cell's 32 do, which leaves
    # none with a state that outlasts a few positions: it makes theirs mild, so
    # that the controls of the rule have a state to be wrong about
    host_params["periods"]["linear"]["a_log"] += np.float32(cut.get("a_log_shift", 0.0))
    setup["params_s"] = clock() - t

    order = seeded_histories.batch_order(data["users"], ctx.seed)
    head_rows = seeded_histories.head_rows(
        vocab, cut.get("head_rows", check["head_rows"]), ctx.seed)

    _, place, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    t = clock()
    params, opt_state = place(host_params)  # the host's copy stays, for the reference
    jax.block_until_ready((params, opt_state))
    setup["h2d_s"] = clock() - t
    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    state_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(opt_state))
    rng = jax.random.PRNGKey(0)  # the block draws nothing from it
    taken = 0
    dropped: list = []
    host: list = []   # a step's seconds on the host's clock: dispatch, next batch, done, fetched

    def placed(n: int):
        """Step ``n``'s users and their rows on the device; None past the last."""
        rows = order[n * per_step:(n + 1) * per_step]
        if rows.size < per_step:
            return None
        return rows, {"seq": put_global(inputs[rows], seq_shard),
                      "target": put_global(targets[rows], seq_shard)}

    ahead = placed(0)

    def step():
        """One optimizer step on the next ``per_step`` users; synced. What the
        host does between two steps is kept to the dispatch and one transfer:
        the next step's rows go to the device while this one runs, and the loss
        and the step's counts come back together (a round trip a scalar, and
        the rows' transfer, held the chip idle for as many latencies, and a
        process's latency is one of two, 2.5 times apart: PERF.md section 6)."""
        nonlocal params, opt_state, taken, ahead
        if ahead is None:
            raise RuntimeError("the window outran the users: no batch repeats")
        rows, batch = ahead
        taken += 1
        t0 = clock()
        params, opt_state, loss, aux = step_fn(params, opt_state, batch, rng)
        t1 = clock()
        ahead = placed(taken)
        t2 = clock()
        jax.block_until_ready(loss)  # the device has finished
        t3 = clock()
        loss, aux = jax.device_get((loss, aux))
        host.append((t1 - t0, t2 - t1, t3 - t2, clock() - t3))
        loss, aux = float(loss), {k: float(v) for k, v in aux.items()}
        dropped.append(aux["moe_dropped"])
        return rows, loss, aux

    def subset_state(*moments) -> dict:
        """The judged tensors, the named moments of Adam's and its step count,
        on the host."""
        adam = opt_state[0]
        trees = {"params": params, **{name: getattr(adam, name) for name in moments}}
        return {"count": int(adam.count), **{
            name: {k: np.asarray(v, np.float64)
                   for k, v in reference_qwen3next.subset_of(tree, head_rows).items()}
            for name, tree in trees.items()}}

    def judged() -> dict:
        """One step of the window's program with what ``correct`` reads of it."""
        old = subset_state("mu", "nu")
        rows, loss, aux = step()
        return {"rows": rows, "loss": loss, "ce": aux["ce"], "aux_loss": aux["aux_loss"],
                "aux": aux, "old": old, "new": subset_state("mu")}

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
            linear_layers=config.linear_layers, experts_held=list(config.experts_held),
            parameters=param_bytes // 4, param_bytes=param_bytes,
            state_bytes=2 * param_bytes + state_bytes, users=data["users"], max_len=max_len,
            users_per_step=per_step, slot_fill=filled / inputs.size,
            head_chunk=looped.head_chunk_of(config), moe_chunk=sparse_moe.moe_chunk_of(config),
            delta_chunk=config.delta_chunk,
            delta_kept_bytes=hybrid.delta_kept_bytes(config, per_step), warm_loss=warm_loss,
            memory_after_warm=[dev.memory_stats() for dev in ctx.devices])

    # ---- the window: whole steps only, a sync after each -------------------
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    spans: list = []
    window: list = []
    compiles.reset()
    with traced_window(ctx.out_dir, ctx.trace) as trace_dir:
        setup_s = clock() - ctx.t0
        w0 = clock()
        while not window or clock() - w0 < seconds:
            a = clock()
            _, loss, aux = step()
            spans.append(("bench.step", a - w0, clock() - w0))
            window.append({"loss": loss, **aux})
        window_s = clock() - w0
        in_window = compiles.count
    done = len(window)
    tokens = float(per_step * max_len * filled / inputs.size)
    counts = {name: float(np.mean([w[name] for w in window])) for name in COUNTS}
    lengths = np.count_nonzero(inputs[order[:per_step]], axis=1).astype(np.float64)
    counts.update(tokens=tokens, linear_layers=config.linear_layers,
                  causal_pairs=float((lengths * (lengths + 1) / 2).sum()))
    flops = counts_qwen3next.step_model_flops(
        tokens, float((targets[order[:per_step]] > 0).sum()), counts["causal_pairs"],
        counts["moe_held_assignments"], counted, vocab)
    dispatch_s, next_rows_s, done_s, fetch_s = (list(part) for part in zip(*host[-done:]))
    ctx.say(window_s=window_s, steps=done, losses=[w["loss"] for w in window],
            step_s=[end - start for _, start, end in spans], dispatch_s=dispatch_s,
            next_rows_s=next_rows_s, done_s=done_s, fetch_s=fetch_s, model_flops_per_step=flops,
            held_by_step=[w["moe_held_assignments"] for w in window],
            load_max_by_step=[w["moe_held_load_max"] for w in window],
            step_counts=counts,
            moe_held_share=100.0 * counts["moe_held_assignments"] / counts["moe_assignments"],
            moe_load_max_over_mean=counts["moe_held_load_max"] / counts["moe_held_load_mean"],
            memory_after_window=[dev.memory_stats() for dev in ctx.devices])

    # ---- correct: the warm step, and one more step of the window's program --
    started_from = {"seeded": host_params,   # and the state the window left
                    "trained": jax.tree_util.tree_map(np.asarray, params)}
    steps["trained"] = judged()
    # a loaded program keeps its temporaries reserved: the trained state and the
    # step's program go, and the reference has the chip
    params = opt_state = None
    step_fn.clear_cache()
    jax.clear_caches()

    programs: dict = {}

    def referee(state: str, **control) -> dict:
        """The reference on the rows of a judged step, the batch whole: loss
        and every gradient (the judged subset comes to the host). One jitted
        program for every state, seed and run (the rows are arguments)."""
        how = {**reference_qwen3next.SOUND, **control}
        grade = programs.setdefault(tuple(sorted(how.items())), jax.jit(
            lambda p, picked, s, y: (lambda value, aux, grads: (
                value, aux, reference_qwen3next.subset_of(grads, picked)))(
                *reference_qwen3next.loss_and_grads(p, s, y, dims, config.aux_coef, how))))
        rows = steps[state]["rows"]
        value, ref_aux, grads = grade(
            jax.device_put(started_from[state], ctx.devices[0]), jnp.asarray(head_rows),
            jnp.asarray(inputs[rows]), jnp.asarray(targets[rows]))
        return {"loss": float(value), "ce": float(ref_aux["ce"]),
                "aux_loss": float(ref_aux["aux_loss"]),
                "grads": {k: np.asarray(v, np.float64) for k, v in grads.items()}}

    def against(**control) -> list:
        """Both judged steps against the reference worked ``control``'s way."""
        rows = []
        for state, prefix in STATES.items():
            want = referee(state, **control)
            rows += compared(steps[state], want, limits[state], config.learning_rate, prefix)
            if not control:
                # beside the layer's number, each held expert's own: a token the
                # program routes otherwise than the reference shows in one of them
                have = gradients_of(steps[state])["w_down_first"]
                ctx.say(state=state, grad_w_down_first_rel_err_by_expert=[
                    float(_rel(a, b)) for a, b in zip(have, want["grads"]["w_down_first"])])
        return [_check(*row) for row in rows]

    t = clock()
    checks = against()
    reference_s = clock() - t
    finite = [np.isfinite([w["loss"] for w in window]).all()]
    for have in steps.values():
        finite += [np.isfinite(have["loss"]), np.isfinite(list(have["aux"].values())).all()]
        finite += [np.isfinite(a).all() for a in have["new"]["mu"].values()]
    checks += [
        _check("moe_dropped", float(np.abs(dropped).sum()), 0),
        _check("nonfinite_values", int(sum(not ok for ok in finite)), 0),
        _check("compilations_in_window", in_window, 0),
    ]
    if ctx.control:
        for name, control in CONTROLS.items():
            low = against(**control)
            ctx.say(control=name,
                    checks=[{k: c[k] for k in ("name", "value", "limit", "ok")} for c in low],
                    correct=all(c["ok"] for c in low))

    ctx.say(reference_s=reference_s, steps_run=len(dropped),
            loss={state: have["loss"] for state, have in steps.items()},
            aux_loss={state: have["aux_loss"] for state, have in steps.items()},
            subset_change_norm={state: float(np.linalg.norm(_flat(
                {k: have["new"]["params"][k] - have["old"]["params"][k]
                 for k in have["new"]["params"]}, sorted(have["new"]["params"]))))
                for state, have in steps.items()},
            memory_after_reference=[dev.memory_stats() for dev in ctx.devices])
    out = {
        "end_to_end": {"train_iters_per_s": done / window_s, "setup_s": setup_s},
        "attempted": done, "failed": 0, "checks": checks, "setup": setup,
        "steps": done, "model_flops_per_step": flops, "step_counts": counts,
        "dims": counted, "device_kind": ctx.devices[0].device_kind,
    }
    if ctx.trace:
        out["trace"] = trace_reduce.reduce_trace(trace_dir, spans)
    return out
