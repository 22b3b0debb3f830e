"""Whole optimizer steps of the sequence template's sparse backbone for the
window: lifelong histories drawn and packed once (every row full), one warm
step, then steps back to back, a device sync after each.

The template's own pieces in the template's own order, as ``seq_train.py``
takes them for the looped backbone: ``SequencePreparator`` packs the
histories, ``SASRecAlgorithm`` reads the engine parameters into the backbone's
configuration, ``models/sequence/model.py:make_fit`` gives the jitted step
``train_sasrec``'s loop runs.

``correct`` judges the window's own step twice against
``reference_keye.py`` on the parameters the step started from and its rows, a
batch whole: the warm step, on the seed's draw (``seeded_*``), and one more
step on the state the window left. Each time: the loss and its two terms; the
gradients of a named subset that covers every new path
(``reference_keye.subset_of``; the step returns no gradient, Adam's first
moment does: ``g = (mu' - b1 mu) / (1 - b1)``); the subset's change over the
step against Adam worked in NumPy float64; and, from the program's own index
and select programs run on the same parameters and rows
(``sparse_moe.probe_selection``, before the step, whose parameters are
donated), the index scores of sampled queries in every layer and the share of
the reference's selected pairs the program selected too (``select_overlap``;
the check is its shortfall from 1: a pair at the threshold that rounding
flipped is the only way the two can differ). ``moe_dropped`` of every step the
run made is 0, exact.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import counts_keye, reference_keye, seeded_histories, seeded_lifelong
from benchmarks import trace_reduce
from benchmarks.compiles import CompileCounter
from benchmarks.drivers.seq_train import ADAM_B1, _flat, _rel, adam_change
from benchmarks.harness import check as _check, traced_window

#: the tensors whose gradients are compared, as relative error in the
#: Frobenius norm: ``reference_keye.subset_of``'s
GRADIENTS = ("wq_first", "wk_first", "router_first", "router_last", "w_down_first",
             "w_down_last", "final_norm", "head_rows")
STATES = {"seeded": "seeded_", "trained": ""}
CONTROLS = {"bfloat16": {"precision": "bfloat16"}, "window": {"selection": "window"},
            "unrenormalised": {"renormalise": False}}
#: the step's counts a window averages for the readers
COUNTS = ("moe_assignments", "moe_held_assignments", "moe_held_load_max",
          "moe_held_load_mean", "selected_pairs", "causal_pairs")

#: engine parameter -> the configuration file's key (a width is the source's)
PUBLISHED = {
    "hiddenSize": "hidden_size", "numHeads": "num_attention_heads",
    "numKvHeads": "num_key_value_heads", "headDim": "head_dim",
    "expertDim": "moe_intermediate_size", "numExperts": "num_experts",
    "expertsPerToken": "num_experts_per_tok", "numLayers": "num_hidden_layers",
    "ropeTheta": "rope_theta", "rmsNormEps": "rms_norm_eps"}
INDEXER = {"indexHeads": "indexer_num_heads", "indexDim": "indexer_head_dim",
           "indexTopk": "topk"}


def _algorithm_params(config_file: dict, cut: dict) -> dict:
    """The engine parameters of the configuration's file, held to the
    published keys beside them; a rehearsal swaps in its cut widths."""
    params = dict(config_file["engine"]["algorithms"][0]["params"])
    for ours, theirs in {**PUBLISHED, **INDEXER}.items():
        want = (config_file["sa_config"] if ours in INDEXER else config_file)[theirs]
        if params[ours] != want:
            raise ValueError(f"engine param {ours}={params[ours]} is not the"
                             f" configuration's {theirs}={want}")
        params[ours] = cut.get(theirs, params[ours])
    lo, hi = params["expertsHeld"]
    if hi - lo != config_file["num_local_experts"]:
        raise ValueError(f"expertsHeld={params['expertsHeld']} is not the"
                         f" configuration's num_local_experts")
    params["expertsHeld"] = [lo, lo + cut.get("num_local_experts", hi - lo)]
    params["batchSize"] = cut.get("users_per_step", params["batchSize"])
    return params


def compared(have: dict, want: dict, limits: dict, lr: float, prefix: str = ""):
    """Each number of one judged step as ``(name, value, limit)``; a number the
    workload gives no limit yet has the limit ``inf`` (a first reading)."""
    limit = lambda name: limits.get(name + "_limit", float("inf"))  # noqa: E731
    rows = [(name + "_abs_err", abs(have[name] - want[name]), limit(name + "_abs_err"))
            for name in ("loss", "ce", "aux_loss")]
    old, new = have["old"], have["new"]
    grads = {k: (new["mu"][k] - ADAM_B1 * old["mu"][k]) / (1 - ADAM_B1) for k in new["mu"]}
    by_tensor = limits.get("grad_rel_err_limits", {})
    rows += [(f"grad_{name}_rel_err", _rel(grads[name], want["grads"][name]),
              by_tensor.get(name, float("inf"))) for name in GRADIENTS]
    every = sorted(grads)
    moved = {k: new["params"][k] - old["params"][k] for k in every}
    by_adam = {k: adam_change(grads[k], old["mu"][k], old["nu"][k], old["count"], lr)
               for k in every}
    rows.append(("adam_update_rel_err", _rel(_flat(moved, every), _flat(by_adam, every)),
                 limit("adam_update_rel_err")))
    causal = have["causal"]
    rows.append(("index_score_rel_err",
                 _rel(have["scores"][causal], want["scores"][causal]),
                 limit("index_score_rel_err")))
    both = (have["chosen"] & want["chosen"]).sum()
    rows.append(("select_overlap_shortfall", 1.0 - both / max(want["chosen"].sum(), 1),
                 limit("select_overlap_shortfall")))
    return [(prefix + name, float(value), lim) for name, value, lim in rows]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.controller.base import Params
    from predictionio_tpu.models.sequence import engine as seq_engine
    from predictionio_tpu.models.sequence import looped, model as seq_model
    from predictionio_tpu.parallel.mesh import put_global
    from predictionio_tpu.workflow.context import RuntimeContext

    if "sparse_moe" not in getattr(seq_engine.SASRecAlgorithm, "BACKBONES", ()):
        raise SystemExit(
            f"{ctx.cell}: this program's sequence template has no sparse_moe backbone"
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
    dims = {"num_heads": config.num_heads, "num_kv_heads": config.num_kv_heads,
            "head_dim": config.head_dim, "index_heads": config.index_heads,
            "index_dim": config.index_dim, "index_topk": config.index_topk,
            "experts_per_token": config.experts_per_token,
            "experts_held": config.experts_held, "rope_theta": config.rope_theta,
            "rms_eps": config.rms_eps,
            "query_block": cut.get("query_block", check["query_block"])}
    counted = {**config_file, "num_hidden_layers": config.num_layers,
               "hidden_size": config.hidden_size, "head_dim": config.head_dim,
               "num_attention_heads": config.num_heads,
               "num_key_value_heads": config.num_kv_heads,
               "num_experts": config.num_experts,
               "moe_intermediate_size": config.expert_dim,
               "sa_config": {"indexer_num_heads": config.index_heads,
                             "indexer_head_dim": config.index_dim}}
    shapes = seeded_lifelong.param_shapes(
        vocab, config.hidden_size, config.num_heads, config.num_kv_heads, config.head_dim,
        config.expert_dim, config.num_experts, config.held, config.num_layers,
        config.index_heads, config.index_dim)
    t = clock()
    host_params = seeded_lifelong.make_params(
        shapes, ctx.seed, 2 * config_file["published"]["num_hidden_layers"])
    setup["params_s"] = clock() - t

    order = seeded_histories.batch_order(data["users"], ctx.seed)
    head_rows = seeded_histories.head_rows(
        vocab, cut.get("head_rows", check["head_rows"]), ctx.seed)
    queries = seeded_lifelong.probe_queries(
        max_len, cut.get("probe_queries", check["probe_queries"]), ctx.seed)
    causal = np.arange(max_len)[None, :] <= queries[:, None]          # [Q, T]

    def probed(start: dict, rows) -> dict:
        """The program's own index scores and selection for the sampled
        queries of ``rows``, from the parameters ``start`` (host). Its program
        is loaded, run and dropped: the step's has the chip to itself."""
        fn = jax.jit(lambda p, s: sparse_moe.probe_selection(
            config, mesh, p, s, jnp.asarray(queries)))
        scores, chosen = fn(jax.device_put(start, ctx.devices[0]), jnp.asarray(inputs[rows]))
        out = {"scores": np.asarray(scores, np.float64),
               "chosen": np.asarray(chosen).astype(bool),
               "causal": np.broadcast_to(causal, scores.shape)}
        del fn, scores, chosen
        jax.clear_caches()
        return out

    t = clock()
    probes = {"seeded": probed(host_params, order[:per_step])}
    setup["probe_s"] = clock() - t

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
        loss = float(loss)  # the device has finished
        aux = {k: float(v) for k, v in aux.items()}
        dropped.append(aux["moe_dropped"])
        return rows, loss, aux

    def subset_state(*moments) -> dict:
        """The judged tensors, the named moments of Adam's and its step count,
        on the host."""
        adam = opt_state.inner_states["train"].inner_state[0]
        trees = {"params": params, **{name: getattr(adam, name) for name in moments}}
        return {"count": int(adam.count), **{
            name: {k: np.asarray(v, np.float64)
                   for k, v in reference_keye.subset_of(tree, head_rows).items()}
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
    counts["tokens"] = tokens
    flops = counts_keye.step_model_flops(
        tokens, float((targets[order[:per_step]] > 0).sum()), counts["selected_pairs"],
        counts["causal_pairs"], counts["moe_held_assignments"], counted, vocab)
    ctx.say(window_s=window_s, steps=done, losses=[w["loss"] for w in window],
            step_s=[end - start for _, start, end in spans], model_flops_per_step=flops,
            held_by_step=[w["moe_held_assignments"] for w in window],
            load_max_by_step=[w["moe_held_load_max"] for w in window],
            step_counts=counts,
            moe_held_share=100.0 * counts["moe_held_assignments"] / counts["moe_assignments"],
            moe_load_max_over_mean=counts["moe_held_load_max"] / counts["moe_held_load_mean"],
            sparse_selected_share=100.0 * counts["selected_pairs"] / counts["causal_pairs"],
            memory_after_window=[dev.memory_stats() for dev in ctx.devices])

    # ---- correct: the warm step, and one more step of the window's program --
    started_from = {"seeded": host_params,   # and the state the window left
                    "trained": jax.tree_util.tree_map(np.asarray, params)}
    steps["trained"] = judged()
    # a loaded program keeps its temporaries reserved: the trained state and the
    # step's program go, and the probe and the reference have the chip in turn
    params = opt_state = None
    step_fn.clear_cache()
    jax.clear_caches()
    probes["trained"] = probed(started_from["trained"], steps["trained"]["rows"])

    programs: dict = {}

    def referee(state: str, **control) -> dict:
        """The reference on the rows of a judged step, the batch whole: loss,
        every gradient (the judged subset comes to the host), and its own index
        scores and selection for the sampled queries. One jitted program for
        every state, seed and run (the rows and queries are arguments)."""
        how = {**reference_keye.SOUND, **control}
        grade = programs.setdefault(tuple(sorted(how.items())), jax.jit(
            lambda p, picked, s, y, q: (lambda value, aux, grads: (
                value, aux, reference_keye.subset_of(grads, picked)))(
                *reference_keye.loss_and_grads(p, s, y, dims, config.aux_coef, how, q))))
        rows = steps[state]["rows"]
        value, ref_aux, grads = grade(
            jax.device_put(started_from[state], ctx.devices[0]), jnp.asarray(head_rows),
            jnp.asarray(inputs[rows]), jnp.asarray(targets[rows]), jnp.asarray(queries))
        return {"loss": float(value), "ce": float(ref_aux["ce"]),
                "aux_loss": float(ref_aux["aux_loss"]),
                "grads": {k: np.asarray(v, np.float64) for k, v in grads.items()},
                "scores": np.asarray(ref_aux["scores"], np.float64),
                "chosen": np.asarray(ref_aux["chosen"])}

    def against(**control) -> list:
        """Both judged steps against the reference worked ``control``'s way."""
        rows = []
        for state, prefix in STATES.items():
            rows += compared({**steps[state], **probes[state]}, referee(state, **control),
                             limits[state], config.learning_rate, prefix)
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
            select_overlap={state: 1.0 - c["value"] for state, prefix in STATES.items()
                            for c in checks if c["name"] == prefix + "select_overlap_shortfall"},
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
