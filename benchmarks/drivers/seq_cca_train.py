"""Whole optimizer steps of the sequence template's compressed-convolution
backbone (attention in a compressed latent mixed by two causal convolutions, a
router MLP that carries its state from layer to layer and may send a token
past the experts, scaled merges, a tied head) for the window: lifelong
histories drawn and packed once (every row full), one warm step, then steps
back to back, a device sync after each.

The template's own pieces in the template's own order, as
``seq_window_train.py`` takes them for the window backbone:
``SequencePreparator`` packs the histories, ``SASRecAlgorithm`` reads the
engine parameters into the backbone's configuration,
``models/sequence/model.py:make_fit`` gives the jitted step ``train_sasrec``'s
loop runs, and the steps run under the fit's span (``seq.fit``, with
``model.fit_attrs``'s attributes, as ``train_sasrec`` opens it).

What the host exchanges with the chip a step is kept to the step's dispatch,
the next rows' transfer, the wait and **one** fetch: the loss and every
counter the step returned leave the device as one array (``packed``, a second
small program dispatched behind the step), where a ``device_get`` of the
step's ``aux`` is a fetch a scalar.

``correct`` judges the window's own step twice against ``reference_zaya.py``
on the parameters the step started from and its rows, a batch whole: the warm
step, on the seed's draw (``seeded_*``), and one more step on the state the
window left. Each time: the loss; the gradients of a named subset that covers
every new path (``reference_zaya.subset_of``; the step returns no gradient,
Adam's first moment does: ``g = (mu' - b1 mu) / (1 - b1)``); the subset's
change over the step against Adam worked in NumPy float64; the step's counts
of its routers' choices (those that took the skip, those to held experts, those
the bias decided) against the reference's, the largest difference as a share
of all choices (``routing_counts_share``); and every router's bias after the
step against the reference's move from its own load
(``seq_latent_train.bias_rows``). ``moe_dropped`` of every step the run made is
0, exact.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts_zaya, reference_zaya, seeded_cca, seeded_histories
from benchmarks import seeded_lifelong, trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.drivers.seq_latent_train import bias_rows, gradients_of
from benchmarks.drivers.seq_window_train import _host_rss
from benchmarks.drivers.seq_train import _flat, _rel, adam_change
from benchmarks.harness import check as _check, traced_window

#: the tensors whose gradients are compared, as relative error in the
#: Frobenius norm: ``reference_zaya.subset_of``'s
GRADIENTS = ("wq_first", "wk_first", "wv2_first", "conv0_w_first", "conv1_w_query",
             "conv1_w_key", "tau_first", "wo_first", "a_r1_first", "gamma_second",
             "w_d_first", "w_1_first", "w_3_last", "w_down_first", "final_norm",
             "table_rows_seen", "table_rows_head")
STATES = {"seeded": "seeded_", "trained": ""}
CONTROLS = {"bfloat16": {"precision": "bfloat16"}, "no_conv0": {"conv0": False},
            "no_conv1": {"conv1": False}, "no_qk_mean": {"qk_mean": False},
            "no_value_shift": {"value_shift": False}, "no_qk_norm": {"qk_norm": False},
            "no_temperature": {"temperature": False}, "whole_rope": {"rope": "whole"},
            "no_carry": {"carry": False}, "linear_router": {"router": "linear"},
            "no_bias": {"bias": False}, "no_skip": {"skip": False},
            "no_residual_scale": {"residual_scale": False}, "untied_head": {"head": "untied"}}
#: the step's counts a window averages for the readers
COUNTS = ("moe_assignments", "moe_held_assignments", "moe_held_load_max",
          "moe_held_load_mean", "moe_passes", "moe_passes_run", "moe_skip_assignments",
          "moe_bias_decided", "router_carry_rms")

#: engine parameter -> the configuration file's key (a width is the source's)
PUBLISHED = {
    "hiddenSize": "hidden_size", "numLayers": "num_hidden_layers",
    "numHeads": "num_attention_heads", "numKvHeads": "num_key_value_heads",
    "headDim": "head_dim", "ccaTime0": "cca_time0", "ccaTime1": "cca_time1",
    "routerHiddenSize": "router_hidden_size", "expertDim": "moe_intermediate_size",
    "numExperts": "num_experts", "expertsPerToken": "num_experts_per_tok",
    "partialRotaryFactor": "partial_rotary_factor", "rmsNormEps": "rms_norm_eps"}


def _algorithm_params(config_file: dict, cut: dict) -> dict:
    """The engine parameters of the configuration's file, held to the
    published keys beside them; a rehearsal swaps in its cut widths."""
    params = dict(config_file["engine"]["algorithms"][0]["params"])
    for ours, theirs in PUBLISHED.items():
        if params[ours] != config_file[theirs]:
            raise ValueError(f"engine param {ours}={params[ours]} is not the"
                             f" configuration's {theirs}={config_file[theirs]}")
        params[ours] = cut.get(theirs, params[ours])
    rope = config_file["rope_parameters"]["hybrid"]
    if (params["ropeTheta"] != rope["rope_theta"]
            or params["partialRotaryFactor"] != rope["partial_rotary_factor"]):
        raise ValueError("ropeTheta and partialRotaryFactor are not the configuration's"
                         f" rope_parameters.hybrid={rope}")
    if (config_file["layer_types"] != ["hybrid"] * config_file["num_hidden_layers"]
            or config_file["sliding_window"] is not None
            or not config_file["tie_word_embeddings"] or config_file["hidden_act"] != "silu"):
        raise ValueError("this backbone runs hybrid layers with no window, silu experts and"
                         " a tied head: the configuration asks for something else")
    lo, hi = params["expertsHeld"]
    if hi - lo != config_file["num_local_experts"]:
        raise ValueError(f"expertsHeld={params['expertsHeld']} is not the"
                         f" configuration's num_local_experts")
    params["expertsHeld"] = [lo, lo + cut.get("num_local_experts", hi - lo)]
    params["batchSize"] = cut.get("users_per_step", params["batchSize"])
    return params


def compared(have: dict, want: dict, limits: dict, lr: float, rate: float, prefix: str = ""):
    """Each number of one judged step as ``(name, value, limit)``; a number the
    workload gives no limit yet has the limit ``inf`` (a first reading)."""
    limit = lambda name: limits.get(name + "_limit", float("inf"))  # noqa: E731
    rows = [("loss_abs_err", abs(have["loss"] - want["loss"]), limit("loss_abs_err"))]
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
    rows.append(("routing_counts_share", max(
        abs(have["aux"][name] - want["counts"][name]) for name in want["counts"])
        / max(want["load"].sum(), 1.0), limit("routing_counts_share")))
    rows += [(name, value, limit(name))
             for name, value in bias_rows(new["bias"], want["bias"], want["load"], rate)]
    return [(prefix + name, float(value), lim) for name, value, lim in rows]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import engine as seq_engine
    from predictionio_tpu.models.sequence import model as seq_model
    from predictionio_tpu.obs.trace import global_tracer
    from predictionio_tpu.parallel.mesh import put_global
    from predictionio_tpu.workflow.context import RuntimeContext

    if "cca_moe" not in getattr(seq_engine.SASRecAlgorithm, "BACKBONES", ()):
        raise SystemExit(
            f"{ctx.cell}: this program's sequence template has no cca_moe backbone"
            " (models/sequence/engine.py): it cannot train the decoder this cell times")
    from predictionio_tpu.models.sequence import blocks, experts

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
    packed_rows = seq_engine.SequencePreparator(Params({"maxLen": max_len})).prepare(
        rctx, seq_engine.SequencesData(
            sequences=histories, user_ids=[], item_ids=[None] * (vocab - 1)))
    setup["seq_pack_s"] = clock() - t
    inputs = packed_rows.matrix
    targets = np.zeros_like(inputs)
    targets[:, :-1] = inputs[:, 1:]

    algorithm = seq_engine.SASRecAlgorithm(Params(_algorithm_params(config_file, cut)))
    config = algorithm._config(vocab - 1, max_len)
    #: the configuration file's keys at the sizes that run (a rehearsal's are cut)
    counted = {**config_file, **{k: cut[k] for k in PUBLISHED.values() if k in cut},
               "num_local_experts": config.held}
    dims = {"num_heads": config.num_heads, "num_kv_heads": config.num_kv_heads,
            "head_dim": config.head_dim, "conv_time0": config.conv_time0,
            "conv_time1": config.conv_time1, "experts_held": config.experts_held,
            "rope_theta": config.rope_theta, "rotary_fraction": config.rotary_fraction,
            "bias_rate": config.bias_rate, "rms_eps": config.rms_eps,
            "query_block": cut.get("query_block", check["query_block"]),
            "head_block": cut.get("head_block", check["head_block"])}
    t = clock()
    host_params = seeded_cca.make_params(
        seeded_cca.param_shapes(counted, vocab, config.held), ctx.seed,
        2 * config_file["published"]["num_hidden_layers"],
        cut.get("bias_std", seeded_cca.BIAS_STD))
    setup["params_s"] = clock() - t

    order = seeded_histories.batch_order(data["users"], ctx.seed)
    sampled = cut.get("head_rows", check["head_rows"])
    head_rows = seeded_histories.head_rows(vocab, sampled, ctx.seed)

    def seen_rows_of(rows) -> np.ndarray:
        """``sampled`` of the item ids the step's rows hold, spread over them."""
        ids = np.unique(inputs[rows])
        ids = ids[ids > 0]
        return ids[np.linspace(0, ids.size - 1, min(sampled, ids.size)).astype(np.int64)]

    _, place, step_fn, seq_shard = seq_model.make_fit(config, mesh)
    t = clock()
    params, opt_state = place(host_params)  # the host's copy stays, for the reference
    jax.block_until_ready((params, opt_state))
    setup["h2d_s"] = clock() - t
    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    state_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(opt_state))
    fit_attrs = seq_model.fit_attrs(config, param_bytes, state_bytes, per_step,
                                    ctx.devices[0].platform)
    rng = jax.random.PRNGKey(0)  # the block draws nothing from it
    taken = 0
    dropped: list = []
    host: list = []   # a step's seconds on the host's clock: dispatch, next batch, done, fetched
    names: list = []  # the step's scalars in the order ``as_one`` stacks them

    @jax.jit
    def as_one(loss, aux):
        """The loss and the step's scalars as one float32 array (a count of at
        most a step's assignments is whole in float32)."""
        return jnp.stack([loss.astype(jnp.float32)]
                         + [aux[name].astype(jnp.float32) for name in sorted(aux)])

    def placed(n: int):
        """Step ``n``'s users and their rows on the device; None past the last."""
        rows = order[n * per_step:(n + 1) * per_step]
        if rows.size < per_step:
            return None
        return rows, {"seq": put_global(inputs[rows], seq_shard),
                      "target": put_global(targets[rows], seq_shard)}

    ahead = placed(0)

    def step():
        """One optimizer step on the next ``per_step`` users; synced. Between two
        steps the host dispatches, transfers the next rows, waits and fetches
        one array."""
        nonlocal params, opt_state, taken, ahead
        if ahead is None:
            raise RuntimeError("the window outran the users: no batch repeats")
        rows, batch = ahead
        taken += 1
        t0 = clock()
        params, opt_state, loss, aux = step_fn(params, opt_state, batch, rng)
        one = as_one(loss, aux)
        t1 = clock()
        ahead = placed(taken)
        t2 = clock()
        jax.block_until_ready(one)  # the device has finished
        t3 = clock()
        values = np.asarray(one)
        host.append((t1 - t0, t2 - t1, t3 - t2, clock() - t3))
        if not names:
            names.extend(sorted(aux))
        aux = dict(zip(names, (float(v) for v in values[1:])))
        dropped.append(aux["moe_dropped"])
        return rows, float(values[0]), aux

    def subset_state(seen, *moments) -> dict:
        """The judged tensors, the named moments of Adam's, its step count and
        the routers' biases, on the host."""
        adam = opt_state.inner_states["train"].inner_state[0]
        trees = {"params": params, **{name: getattr(adam, name) for name in moments}}
        return {"count": int(adam.count),
                "bias": np.asarray(params["layers"]["router_bias"], np.float64), **{
                    name: {k: np.asarray(v, np.float64) for k, v in
                           reference_zaya.subset_of(tree, head_rows, seen).items()}
                    for name, tree in trees.items()}}

    def judged() -> dict:
        """One step of the window's program with what ``correct`` reads of it."""
        seen = seen_rows_of(ahead[0])
        old = subset_state(seen, "mu", "nu")
        rows, loss, aux = step()
        return {"rows": rows, "seen": seen, "loss": loss, "aux": aux,
                "old": old, "new": subset_state(seen, "mu")}

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
            fit={k: v for k, v in fit_attrs.items() if k != "head"},
            experts_held=list(config.experts_held), parameters=param_bytes // 4,
            param_bytes=param_bytes, state_bytes=2 * param_bytes + state_bytes,
            users=data["users"], max_len=max_len, users_per_step=per_step,
            slot_fill=filled / inputs.size, head_chunk=blocks.head_chunk_of(config),
            moe_chunk=experts.moe_chunk_of(config), warm_loss=warm_loss,
            memory_after_warm=[dev.memory_stats() for dev in ctx.devices])

    # ---- the window: whole steps only, a sync after each -------------------
    seconds = min(ctx.seconds, traffic["trace_seconds"]) if ctx.trace else ctx.seconds
    spans: list = []
    window: list = []
    compiles.reset()
    with traced_window(ctx.out_dir, ctx.trace) as trace_dir, \
            global_tracer().span("seq.fit", attrs=fit_attrs) as fit_span:
        setup_s = clock() - ctx.t0
        w0 = clock()
        while not window or clock() - w0 < seconds:
            a = clock()
            _, loss, aux = step()
            spans.append(("bench.step", a - w0, clock() - w0))
            window.append({"loss": loss, **aux})
        window_s = clock() - w0
        in_window = compiles.count
        fit_span.set_attr("steps", len(window))
    done = len(window)
    tokens = float(per_step * max_len * filled / inputs.size)
    counts = {name: float(np.mean([w[name] for w in window])) for name in COUNTS}
    first = order[:per_step]
    counts.update(tokens=tokens, targets=float(np.count_nonzero(targets[first])),
                  causal_pairs=counts_zaya.pairs_of(np.count_nonzero(inputs[first], axis=1)))
    flops = counts_zaya.step_model_flops(counts, counted, vocab)
    dispatch_s, next_rows_s, done_s, fetch_s = (list(part) for part in zip(*host[-done:]))
    ctx.say(window_s=window_s, steps=done, losses=[w["loss"] for w in window],
            step_s=[end - start for _, start, end in spans], dispatch_s=dispatch_s,
            next_rows_s=next_rows_s, done_s=done_s, fetch_s=fetch_s, model_flops_per_step=flops,
            forward_flops_by_part=counts_zaya.forward_parts(counts, counted, vocab),
            attention_flops_per_step=counts_zaya.attention_flops(counts, counted),
            mix_bytes_per_step=counts_zaya.mix_bytes(counts, counted),
            held_by_step=[w["moe_held_assignments"] for w in window],
            skip_by_step=[w["moe_skip_assignments"] for w in window],
            load_max_by_step=[w["moe_held_load_max"] for w in window],
            bias_abs_max_by_step=[w["router_bias_abs_max"] for w in window],
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
        """The reference on the rows of a judged step, the batch whole: the
        loss, every gradient (the judged subset comes to the host), the loads
        and the biases it moves to. One jitted program for every state, seed
        and run (the rows are arguments)."""
        how = {**reference_zaya.SOUND, **control}

        def grade(p, picked, seen, s, y):
            value, aux, grads = reference_zaya.loss_and_grads(p, s, y, dims, how)
            return (value, aux, reference_zaya.subset_of(grads, picked, seen),
                    reference_zaya.bias_after(p, aux["load"], dims["bias_rate"]))

        program = programs.setdefault(tuple(sorted(how.items())), jax.jit(grade))
        have = steps[state]
        value, ref_aux, grads, bias = program(
            jax.device_put(started_from[state], ctx.devices[0]), jnp.asarray(head_rows),
            jnp.asarray(have["seen"]), jnp.asarray(inputs[have["rows"]]),
            jnp.asarray(targets[have["rows"]]))
        load = np.asarray(ref_aux["load"], np.float64)
        lo, hi = config.experts_held
        decided = float(np.asarray(ref_aux["decided"]).sum())
        return {"loss": float(value), "load": load, "bias": np.asarray(bias, np.float64),
                "counts": {"moe_skip_assignments": float(load[:, config.num_experts:].sum()),
                           "moe_held_assignments": float(load[:, lo:hi].sum()),
                           "moe_bias_decided": decided},
                "grads": {k: np.asarray(v, np.float64) for k, v in grads.items()},
                "decided_share": decided / load.sum(),
                "skip_share": float(load[:, -1].sum() / load.sum()),
                "carry_rms": float(np.asarray(ref_aux["carry_rms"]).mean())}

    def against(**control) -> list:
        """Both judged steps against the reference worked ``control``'s way."""
        rows = []
        for state, prefix in STATES.items():
            want = referee(state, **control)
            rows += compared(steps[state], want, limits[state], config.learning_rate,
                             config.bias_rate, prefix)
            if not control:
                lo, hi = config.experts_held
                ctx.say(state=state, bias_decided_share=want["decided_share"],
                        skip_share=want["skip_share"], reference_carry_rms=want["carry_rms"],
                        reference_held_share=float(want["load"][:, lo:hi].sum()
                                                   / want["load"].sum()),
                        reference_load_max_over_mean=float(
                            (want["load"].max(axis=-1) / want["load"].mean(axis=-1)).max()))
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
            # a control's program is used for its two states and goes: compiled
            # references held together take the host's memory (PERF.md, PR 44)
            programs.clear()
            jax.clear_caches()
            low = against(**control)
            ctx.say(control=name, host_rss_bytes=_host_rss(), live_arrays=len(jax.live_arrays()),
                    checks=[{k: c[k] for k in ("name", "value", "limit", "ok")} for c in low],
                    correct=all(c["ok"] for c in low))

    ctx.say(reference_s=reference_s, steps_run=len(dropped),
            loss={state: have["loss"] for state, have in steps.items()},
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
