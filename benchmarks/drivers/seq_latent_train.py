"""Whole optimizer steps of the sequence template's latent backbone (latent
attention with a rotary key all heads share, a leading dense layer, experts
chosen by sigmoid scores plus a bias the step moves, a shared expert, a module
that predicts a second event ahead) for the window: lifelong histories drawn
and packed once (every row full), one warm step, then steps back to back, a
device sync after each.

The template's own pieces in the template's own order, as
``seq_hybrid_train.py`` takes them for the hybrid backbone:
``SequencePreparator`` packs the histories, ``SASRecAlgorithm`` reads the
engine parameters into the backbone's configuration,
``models/sequence/model.py:make_fit`` gives the jitted step ``train_sasrec``'s
loop runs.

``correct`` judges the window's own step twice against ``reference_joyai.py``
on the parameters the step started from and its rows, a batch whole: the warm
step, on the seed's draw (``seeded_*``), and one more step on the state the
window left. Each time: the loss and its three terms; the gradients of a named
subset that covers every new path (``reference_joyai.subset_of``; the step
returns no gradient, Adam's first moment does: ``g = (mu' - b1 mu) / (1 -
b1)``); the subset's change over the step against Adam worked in NumPy
float64; and every router's bias after the step against the reference's move
from the reference's own load (``bias_*``, below). ``moe_dropped`` of every
step the run made is 0, exact.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts_joyai, reference_joyai, seeded_histories
from benchmarks import seeded_latent, seeded_lifelong, trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.drivers.seq_train import ADAM_B1, _flat, _rel, adam_change
from benchmarks.harness import check as _check, traced_window

#: the tensors whose gradients are compared, as relative error in the
#: Frobenius norm: ``reference_joyai.subset_of``'s
GRADIENTS = ("w_qa_first", "w_kvb_first", "w_qb_last", "w_kva_last", "w_kr_last",
             "dense_down",
             "router_first", "router_last", "w_down_first", "shared_down_last",
             "mtp_merge", "mtp_router", "final_norm", "head_rows")
TERMS = ("loss", "ce", "mtp_ce", "balance")
STATES = {"seeded": "seeded_", "trained": ""}
CONTROLS = {"bfloat16": {"precision": "bfloat16"}, "no_rope_key": {"rope_key": False},
            "softmax_router": {"router": "softmax"}, "no_bias": {"bias": False},
            "unscaled": {"scaled": False}, "no_mtp": {"mtp": False}}
#: the step's counts a window averages for the readers
COUNTS = ("moe_assignments", "moe_held_assignments", "moe_held_load_max",
          "moe_held_load_mean", "moe_passes", "moe_passes_run")

#: engine parameter -> the configuration file's key (a width is the source's)
PUBLISHED = {
    "hiddenSize": "hidden_size", "numLayers": "num_hidden_layers",
    "denseLayers": "first_k_dense_replace", "numHeads": "num_attention_heads",
    "qLoraRank": "q_lora_rank", "kvLoraRank": "kv_lora_rank",
    "qkNopeHeadDim": "qk_nope_head_dim", "qkRopeHeadDim": "qk_rope_head_dim",
    "vHeadDim": "v_head_dim", "ffnDim": "intermediate_size",
    "expertDim": "moe_intermediate_size", "numExperts": "n_routed_experts",
    "expertsPerToken": "num_experts_per_tok", "routedScalingFactor": "routed_scaling_factor",
    "mtpDepth": "num_nextn_predict_layers", "ropeTheta": "rope_theta",
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
    shared = config_file["n_shared_experts"] * config_file["moe_intermediate_size"]
    if (params["sharedExpertDim"] != shared or config_file["qk_head_dim"]
            != config_file["qk_nope_head_dim"] + config_file["qk_rope_head_dim"]):
        raise ValueError("sharedExpertDim or qk_head_dim is not the configuration's")
    params["sharedExpertDim"] = cut.get("moe_intermediate_size", params["sharedExpertDim"])
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


def bias_rows(have: np.ndarray, want: np.ndarray, load: np.ndarray, rate: float) -> list:
    """What is compared of every router's bias after a judged step, ``have``
    the program's and ``want`` the reference's move from its own ``load``
    (all ``[routers, E]``). The program routes a few tokens a layer otherwise
    than the float32 reference, so an expert whose load lies at the mean may
    move the other way. ``bias_unequal_beyond_one``: the entries that differ
    where the expert's load is not within one assignment of the mean;
    ``bias_flip_load_distance``: the farthest from the mean, in assignments,
    that an entry which differs lies; ``bias_step_abs_err``: the largest
    difference among the entries that moved the same way (a rate, a start or a
    dtype that is not the reference's)."""
    distance = np.abs(load - load.mean(axis=-1, keepdims=True))
    unequal = np.abs(have - want) > 0.5 * rate
    return [("bias_unequal_beyond_one", float((unequal & (distance > 1.0)).sum())),
            ("bias_flip_load_distance", float(np.where(unequal, distance, 0.0).max())),
            ("bias_step_abs_err", float(np.where(unequal, 0.0, np.abs(have - want)).max()))]


def compared(have: dict, want: dict, limits: dict, lr: float, rate: float, prefix: str = ""):
    """Each number of one judged step as ``(name, value, limit)``; a number the
    workload gives no limit yet has the limit ``inf`` (a first reading)."""
    limit = lambda name: limits.get(name + "_limit", float("inf"))  # noqa: E731
    rows = [(name + "_abs_err", abs(have[name] - want[name]), limit(name + "_abs_err"))
            for name in TERMS]
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
    rows += [(name, value, limit(name))
             for name, value in bias_rows(new["bias"], want["bias"], want["load"], rate)]
    return [(prefix + name, float(value), lim) for name, value, lim in rows]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import engine as seq_engine
    from predictionio_tpu.models.sequence import looped, model as seq_model
    from predictionio_tpu.parallel.mesh import put_global
    from predictionio_tpu.workflow.context import RuntimeContext

    if "latent_moe" not in getattr(seq_engine.SASRecAlgorithm, "BACKBONES", ()):
        raise SystemExit(
            f"{ctx.cell}: this program's sequence template has no latent_moe backbone"
            " (models/sequence/engine.py): it cannot train the decoder this cell times")
    from predictionio_tpu.models.sequence import sparse_moe

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
    counted["qk_head_dim"] = counted["qk_nope_head_dim"] + counted["qk_rope_head_dim"]
    dims = {"num_heads": config.num_heads, "kv_rank": config.kv_rank,
            "nope_dim": config.nope_dim, "rope_dim": config.rope_dim,
            "value_dim": config.value_dim, "experts_per_token": config.experts_per_token,
            "experts_held": config.experts_held, "routed_scale": config.routed_scale,
            "mtp_coef": config.mtp_coef, "balance_coef": config.balance_coef,
            "bias_rate": config.bias_rate, "rope_theta": config.rope_theta,
            "rms_eps": config.rms_eps,
            "query_block": cut.get("query_block", check["query_block"])}
    t = clock()
    host_params = seeded_latent.make_params(
        seeded_latent.param_shapes(counted, vocab, config.held), ctx.seed,
        2 * config_file["published"]["num_hidden_layers"],
        cut.get("bias_std", seeded_latent.BIAS_STD))
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
        host does between two steps is kept to the dispatch and one transfer,
        as ``seq_hybrid_train.py`` keeps it."""
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

    def biases() -> np.ndarray:
        """Every router's bias ``[routers, E]``, the module's last."""
        return np.concatenate([np.asarray(params["layers"]["router_bias"], np.float64),
                               np.asarray(params["mtp"]["layer"]["router_bias"], np.float64)[None]])

    def subset_state(*moments) -> dict:
        """The judged tensors, the named moments of Adam's and its step count,
        and the routers' biases, on the host."""
        adam = opt_state.inner_states["train"].inner_state[0]
        trees = {"params": params, **{name: getattr(adam, name) for name in moments}}
        return {"count": int(adam.count), "bias": biases(), **{
            name: {k: np.asarray(v, np.float64)
                   for k, v in reference_joyai.subset_of(tree, head_rows).items()}
            for name, tree in trees.items()}}

    def judged() -> dict:
        """One step of the window's program with what ``correct`` reads of it."""
        old = subset_state("mu", "nu")
        rows, loss, aux = step()
        return {"rows": rows, "loss": loss, **{name: aux[name] for name in TERMS[1:]},
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
            dense_layers=config.dense_layers, mtp_depth=config.mtp_depth,
            experts_held=list(config.experts_held), parameters=param_bytes // 4,
            param_bytes=param_bytes, state_bytes=2 * param_bytes + state_bytes,
            users=data["users"], max_len=max_len, users_per_step=per_step,
            slot_fill=filled / inputs.size, head_chunk=looped.head_chunk_of(config),
            moe_chunk=sparse_moe.moe_chunk_of(config), warm_loss=warm_loss,
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
    first = order[:per_step]
    lengths = np.count_nonzero(inputs[first], axis=1).astype(np.float64)
    ahead_lengths = np.count_nonzero(targets[first], axis=1).astype(np.float64)
    counts.update(
        tokens=tokens, targets=float(ahead_lengths.sum()),
        causal_pairs=float((lengths * (lengths + 1) / 2).sum()),
        mtp_tokens=float(ahead_lengths.sum()),
        mtp_targets=float(np.count_nonzero(targets[first][:, 1:])),
        mtp_causal_pairs=float((ahead_lengths * (ahead_lengths + 1) / 2).sum()))
    flops = counts_joyai.step_model_flops(counts, counted, vocab)
    dispatch_s, next_rows_s, done_s, fetch_s = (list(part) for part in zip(*host[-done:]))
    ctx.say(window_s=window_s, steps=done, losses=[w["loss"] for w in window],
            step_s=[end - start for _, start, end in spans], dispatch_s=dispatch_s,
            next_rows_s=next_rows_s, done_s=done_s, fetch_s=fetch_s, model_flops_per_step=flops,
            attention_flops_per_step=counts_joyai.latent_attention_flops(counts, counted),
            held_by_step=[w["moe_held_assignments"] for w in window],
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
        loss's terms, every gradient (the judged subset comes to the host), its
        load and the biases it moves to. One jitted program for every state,
        seed and run (the rows are arguments)."""
        how = {**reference_joyai.SOUND, **control}

        def grade(p, picked, s, y):
            value, aux, grads = reference_joyai.loss_and_grads(p, s, y, dims, how)
            return (value, aux, reference_joyai.subset_of(grads, picked),
                    reference_joyai.bias_after(p, aux["load"], dims["bias_rate"]))

        program = programs.setdefault(tuple(sorted(how.items())), jax.jit(grade))
        rows = steps[state]["rows"]
        value, ref_aux, grads, bias = program(
            jax.device_put(started_from[state], ctx.devices[0]), jnp.asarray(head_rows),
            jnp.asarray(inputs[rows]), jnp.asarray(targets[rows]))
        load = np.asarray(ref_aux["load"], np.float64)
        return {"loss": float(value), **{name: float(ref_aux[name]) for name in TERMS[1:]},
                "grads": {k: np.asarray(v, np.float64) for k, v in grads.items()},
                "load": load, "bias": np.asarray(bias, np.float64),
                "decided_share": float(np.asarray(ref_aux["decided"]).sum() / load.sum())}

    def against(**control) -> list:
        """Both judged steps against the reference worked ``control``'s way."""
        rows = []
        for state, prefix in STATES.items():
            want = referee(state, **control)
            rows += compared(steps[state], want, limits[state], config.learning_rate,
                             config.bias_rate, prefix)
            if not control:
                # beside the layer's number, each held expert's own: a token the
                # program routes otherwise than the reference shows in one of
                # them; and the share of the choices the bias decided
                have = gradients_of(steps[state])["w_down_first"]
                ctx.say(state=state, bias_decided_share=want["decided_share"],
                        reference_load_max_over_mean=float(
                            (want["load"].max(axis=-1) / want["load"].mean(axis=-1)).max()),
                        grad_w_down_first_rel_err_by_expert=[
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
            terms={state: {name: have[name] for name in TERMS[1:]}
                   for state, have in steps.items()},
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
