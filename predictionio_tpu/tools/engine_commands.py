"""``pio train / deploy / undeploy / eval / batchpredict`` verbs.

Behavioral model: reference ``tools/.../{RunWorkflow,RunServer}.scala`` +
``Console.scala`` dispatch (apache/predictionio layout, unverified --
SURVEY.md section 2.4 #27/#28). Where the reference shells out to
spark-submit, these verbs invoke the workflow runtime in-process; `--`
passthrough args become runtime conf overrides (e.g.
``-- --mesh-shape 2,4``).
"""

from __future__ import annotations

import argparse
import os

from predictionio_tpu.workflow.context import WorkflowParams
from predictionio_tpu.workflow.json_extractor import load_engine_variant


def register(sub: argparse._SubParsersAction) -> None:
    train = sub.add_parser("train", help="train an engine (reads engine.json)")
    _add_variant_args(train)
    train.add_argument("--batch", default="", help="batch label recorded on the instance")
    train.add_argument("--skip-sanity-check", action="store_true")
    train.add_argument(
        "--resume",
        action="store_true",
        help="continue the variant's latest crashed/preempted run from its"
        " step checkpoints instead of starting over",
    )
    train.add_argument(
        "--snapshot-mode",
        choices=("off", "use", "refresh"),
        default=None,
        help="training-snapshot cache: 'use' replays the on-disk columnar"
        " spill (building it on first run), 'refresh' first appends events"
        " ingested since; default off (always scan the event store)",
    )
    train.add_argument(
        "--snapshot-dir",
        default=None,
        help="snapshot root (default $PIO_FS_BASEDIR/snapshots)",
    )
    train.add_argument(
        "--als-feed",
        choices=("resident", "streamed"),
        default=None,
        help="how ALS reads training data: 'resident' materializes rating"
        " arrays in host memory, 'streamed' trains straight from the"
        " snapshot's on-disk columnar chunks (needs --snapshot-mode"
        " use/refresh; bounded host memory for catalogs bigger than RAM)."
        " Overrides the engine.json alsFeed param for this run",
    )
    train.add_argument(
        "--profile",
        nargs="?",
        const="__default__",
        default=None,
        metavar="DIR",
        help="capture a jax.profiler trace (tensorboard/xprof-loadable) AND"
        " a per-step telemetry journal (wall time, edges/sec, achieved HBM"
        " GB/s, recompile count) into DIR (default:"
        " <engine-dir>/pio-profile)",
    )
    from predictionio_tpu.obs.logs import add_logging_arguments

    add_logging_arguments(train)
    train.add_argument("passthrough", nargs="*", help="runtime conf after --")
    train.set_defaults(func=cmd_train)

    deploy = sub.add_parser("deploy", help="deploy the latest trained instance")
    _add_variant_args(deploy)
    deploy.add_argument("--ip", default="0.0.0.0")
    deploy.add_argument("--port", type=int, default=8000)
    deploy.add_argument("--engine-instance-id", default=None)
    deploy.add_argument(
        "--model-version", type=int, default=None, metavar="N",
        help="deploy an exact model-registry version (the continuous-"
        "learning registry `pio retrain` publishes into) instead of the"
        " latest trained instance -- the rollback lever; fails loudly on a"
        " missing or corrupt version",
    )
    deploy.add_argument("--feedback", action="store_true")
    deploy.add_argument("--event-server-ip", default="localhost")
    deploy.add_argument("--event-server-port", type=int, default=7070)
    deploy.add_argument("--event-server-scheme", default="http",
                        choices=("http", "https"),
                        help="https when the event server uses --ssl-cert")
    deploy.add_argument("--accesskey", default="")
    # python analogue of the reference's --key-store TLS option
    deploy.add_argument("--ssl-cert", default=None, help="PEM cert: serve HTTPS")
    deploy.add_argument("--ssl-key", default=None, help="PEM key (if not in cert)")
    deploy.add_argument(
        "--batch-window-ms", type=float, default=2.0,
        help="micro-batching latency deadline: how long a query may wait "
        "for batchmates (0 disables batching)",
    )
    deploy.add_argument(
        "--max-batch-size", type=int, default=64,
        help="micro-batching flush size (1 disables batching)",
    )
    deploy.add_argument(
        "--batch-buckets", default="1,4,16,64,128",
        help="comma-separated padded batch shapes; jitted scorers compile "
        "once per bucket",
    )
    deploy.add_argument(
        "--frontend-workers", type=int, default=0, metavar="N",
        help="multi-process serving tier: N SO_REUSEPORT frontend "
        "processes parse/validate HTTP and feed this process's scorer "
        "through shared-memory rings ('add a core' = 'add a worker'); "
        "0 (default) serves single-process",
    )
    deploy.add_argument(
        "--scorer-shards", type=int, default=0, metavar="N",
        help="sharded serving fabric: hash-partition the user factor"
        " table across N scorer processes (item-side state replicated),"
        " each hot-swapping per shard behind the SO_REUSEPORT frontend"
        " tier; 0/1 (default) serves unsharded. Sizing: see"
        " PIO_SHARD_BUDGET_BYTES in docs/operations.md",
    )
    deploy.add_argument(
        "--frontend-ring-slots", type=int, default=128, metavar="SLOTS",
        help="per-worker request/completion ring capacity; a full request "
        "ring answers 429 + Retry-After (scorer backpressure)",
    )
    deploy.add_argument(
        "--frontend-max-inflight", type=int, default=16, metavar="N",
        help="concurrent requests the scorer admits before letting the "
        "rings back up (the backpressure horizon and the micro-batcher's "
        "coalescing ceiling; with --dispatch sync, also the dispatcher "
        "thread count)",
    )
    deploy.add_argument(
        "--dispatch", choices=("async", "sync"), default="async",
        help="scorer dispatch model with --frontend-workers: 'async' "
        "(ring consumer submits straight into the micro-batcher; zero "
        "dispatcher threads and 2 wakeups on the query path) or 'sync' "
        "(dispatcher thread pool -- the pre-async model, kept for A/B; "
        "also used whenever batching is disabled)",
    )
    deploy.add_argument(
        "--pin-cpus", action=argparse.BooleanOptionalAction,
        default=os.environ.get("PIO_PIN_CPUS", "") not in ("", "0"),
        help="sched_setaffinity: pin each frontend worker to one core "
        "from the top of the affinity set, the scorer keeps the rest "
        "(default from PIO_PIN_CPUS=1; --no-pin-cpus overrides it); "
        "needs --frontend-workers and >=2 cores",
    )
    deploy.add_argument(
        "--no-tracing", action="store_true",
        help="disable the span tracer (/traces.json reports enabled=false;"
        " the off path allocates no spans)",
    )
    deploy.add_argument(
        "--trace-sample", type=float, default=None, metavar="RATE",
        help="head-sampling rate (0..1) for headerless root traces;"
        " requests with a traceparent header always trace (default:"
        " $PIO_TRACE_SAMPLE or 0.125)",
    )
    deploy.add_argument(
        "--slow-query-ms", type=float, default=None, metavar="MS",
        help="log one span-summary line for any query trace slower than"
        " this (off by default)",
    )
    add_logging_arguments(deploy)
    deploy.set_defaults(func=cmd_deploy)

    retrain = sub.add_parser(
        "retrain",
        help="continuous learning: tail the ingest WAL, fold new events"
        " into the model, hot-swap running query servers (--follow loops;"
        " without it one catch-up cycle runs)",
    )
    _add_variant_args(retrain)
    retrain.add_argument(
        "--follow", action="store_true",
        help="keep following the WAL until interrupted (the online loop);"
        " default is one catch-up cycle",
    )
    retrain.add_argument(
        "--interval", type=float, default=2.0, metavar="SEC",
        help="seconds between WAL polls in --follow mode",
    )
    retrain.add_argument(
        "--notify", action="append", default=[], metavar="URL",
        help="query server base URL to hot-swap after each publish"
        " (repeatable; default http://localhost:8000 -- pass --notify ''"
        " for batch mode, where publishing to the registry is the"
        " reflection boundary)",
    )
    retrain.add_argument(
        "--wal-dir", default=None,
        help="ingest WAL directory to tail (default $PIO_FS_BASEDIR/wal;"
        " must match the event server's --wal-dir)",
    )
    retrain.add_argument(
        "--registry-dir", default=None,
        help="model registry root (default $PIO_FS_BASEDIR/registry)",
    )
    retrain.add_argument(
        "--registry-keep", type=int, default=5, metavar="N",
        help="retained model versions (each is a rollback target)",
    )
    retrain.add_argument(
        "--max-touched-frac", type=float, default=0.2, metavar="F",
        help="staleness budget: touched-user fraction beyond which a full"
        " retrain replaces fold-in",
    )
    retrain.add_argument(
        "--max-item-growth-frac", type=float, default=0.05, metavar="F",
        help="staleness budget: new-item fraction beyond which a full"
        " retrain replaces fold-in (fold-in gives new items zero factors)",
    )
    retrain.add_argument(
        "--no-full-retrain", action="store_true",
        help="never escalate to a full retrain (log and keep serving"
        " stale instead; schedule retrains out of band)",
    )
    retrain.add_argument(
        "--max-cycles", type=int, default=0, metavar="N",
        help="stop after N cycles (0 = until interrupted; test/bench knob)",
    )
    retrain.add_argument(
        "--scorer-shards", type=int, default=0, metavar="N",
        help="publish per-shard model blobs alongside the full blob so a"
        " `pio deploy --scorer-shards N` fabric swaps without ever"
        " loading the full model in one shard; fold-in republishes only"
        " the shards whose users were touched (0 = full blob only)",
    )
    add_logging_arguments(retrain)
    retrain.set_defaults(func=cmd_retrain)

    undeploy = sub.add_parser("undeploy", help="stop a deployed engine server")
    undeploy.add_argument("--ip", default="localhost")
    undeploy.add_argument("--port", type=int, default=8000)
    undeploy.add_argument("--ssl", action="store_true",
                          help="server was deployed with --ssl-cert")
    undeploy.set_defaults(func=cmd_undeploy)

    ev = sub.add_parser(
        "eval",
        help="run an evaluation (dotted Evaluation, or --replay for the"
        " time-travel offline replay harness)",
    )
    ev.add_argument(
        "evaluation", nargs="?", default=None,
        help="dotted path to an Evaluation object/callable (omit with --replay)",
    )
    ev.add_argument("paramsgen", nargs="?", default=None,
                    help="dotted path to an EngineParamsGenerator")
    ev.add_argument("--engine-dir", default=".")
    ev.add_argument(
        "--variant", default=None,
        help="engine variant JSON for --replay (default engine.json)",
    )
    ev.add_argument("--output-path", default=None, help="also write results JSON here")
    ev.add_argument(
        "--replay", action="store_true",
        help="offline replay evaluation: cut the event timeline at a"
        " boundary, train on the prefix (or pin a registry version),"
        " score every held-out user in one batched pass, report ranking"
        " metrics + the scan-vs-mips retrieval guard as JSON",
    )
    ev.add_argument(
        "--split-time", default=None, metavar="ISO8601",
        help="replay boundary: train < t, holdout >= t (e.g."
        " 2024-03-01T00:00:00Z; naive times are UTC, same parse as event"
        " ingestion so the cut is microsecond-exact)",
    )
    ev.add_argument(
        "--split-frac", type=float, default=None, metavar="F",
        help="replay boundary as a fraction of the time-sorted event"
        " stream (0 < F < 1); resolves to a concrete event timestamp so"
        " the split is replayable (default 0.8 when --split-time absent)",
    )
    ev.add_argument("--k", type=int, default=10,
                    help="ranking cutoff for metrics and queries (default 10)")
    ev.add_argument(
        "--metrics", default=None,
        help="comma-separated metric names (default: all; see the metric"
        " catalog in the unknown-metric error or docs/evaluation.md)",
    )
    ev.add_argument(
        "--model-version", type=int, default=None, metavar="N",
        help="evaluate an exact model-registry version (what `pio deploy"
        " --model-version N` would serve) instead of training on the"
        " prefix; the report's model block carries its lineage",
    )
    ev.add_argument(
        "--registry-dir", default=None,
        help="model registry root for --model-version"
        " (default $PIO_FS_BASEDIR/registry)",
    )
    ev.add_argument(
        "--snapshot-mode", choices=("off", "use", "refresh"), default=None,
        help="training-snapshot cache for the replay read (same semantics"
        " as `pio train --snapshot-mode`)",
    )
    ev.add_argument("--snapshot-dir", default=None,
                    help="snapshot root (default $PIO_FS_BASEDIR/snapshots)")
    ev.add_argument(
        "--no-retrieval-guard", action="store_true",
        help="skip the scan-vs-mips shortlist-recall/identity guard"
        " (runs by default when the algorithm has a retrieval surface)",
    )
    ev.set_defaults(func=cmd_eval)

    from predictionio_tpu.analysis.engine import add_check_arguments

    check = sub.add_parser(
        "check",
        help="static analysis: jax drift-shim + interprocedural "
        "concurrency lint (thread roles, locksets, race detection; "
        "rule catalog: docs/static_analysis.md, or --explain RULE)",
    )
    add_check_arguments(check)
    check.set_defaults(func=cmd_check)

    bp = sub.add_parser("batchpredict", help="bulk offline predictions")
    _add_variant_args(bp)
    bp.add_argument("--input", required=True, help="JSON-lines query file")
    bp.add_argument("--output", required=True, help="JSON-lines prediction output")
    bp.add_argument("--engine-instance-id", default=None)
    bp.set_defaults(func=cmd_batchpredict)


def _add_variant_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--engine-dir", default=".", help="engine directory (holds engine.json)"
    )
    parser.add_argument(
        "--variant", default=None, help="engine variant JSON (default engine.json)"
    )


def _load_variant(args: argparse.Namespace):
    path = args.variant or os.path.join(args.engine_dir, "engine.json")
    return load_engine_variant(path)


def cmd_train(args: argparse.Namespace) -> int:
    from predictionio_tpu.obs.logs import configure_logging
    from predictionio_tpu.workflow.core_workflow import run_train

    configure_logging(args.log_format)
    variant = _load_variant(args)
    variant.runtime_conf.update(_parse_passthrough(args.passthrough))
    if args.profile:
        profile_dir = (
            os.path.join(args.engine_dir, "pio-profile")
            if args.profile == "__default__"
            else args.profile
        )
        variant.runtime_conf["pio.profile"] = profile_dir
    # runtime conf reaches components holding a ctx; the env mirrors it for
    # ctx-free layers (PEventStore.dataset) in this same process
    if args.snapshot_mode:
        variant.runtime_conf["pio.snapshot_mode"] = args.snapshot_mode
        os.environ["PIO_SNAPSHOT_MODE"] = args.snapshot_mode
    if args.snapshot_dir:
        variant.runtime_conf["pio.snapshot_dir"] = args.snapshot_dir
        os.environ["PIO_SNAPSHOT_DIR"] = args.snapshot_dir
    if args.als_feed:
        variant.runtime_conf["pio.als_feed"] = args.als_feed
    params = WorkflowParams(
        batch=args.batch,
        skip_sanity_check=args.skip_sanity_check,
        resume=args.resume,
    )
    instance = run_train(variant, params)
    import json

    from predictionio_tpu.utils.platform import device_report

    # what the train ran on, from JAX itself: a run on the wrong platform
    # cannot pass for a chip run
    print(f"Device: {json.dumps(device_report())}")
    print(f"Training completed. Engine instance ID: {instance.id}")
    return 0


def cmd_deploy(args: argparse.Namespace) -> int:
    from predictionio_tpu.obs.logs import configure_logging
    from predictionio_tpu.workflow.create_server import (
        FeedbackConfig,
        run_query_server,
    )
    from predictionio_tpu.workflow.microbatch import BatchConfig

    configure_logging(args.log_format)
    variant = _load_variant(args)
    feedback = None
    if args.feedback:
        feedback = FeedbackConfig(
            event_server_url=(
                f"{args.event_server_scheme}://"
                f"{args.event_server_ip}:{args.event_server_port}"
            ),
            access_key=args.accesskey,
        )
    try:
        buckets = tuple(
            int(b) for b in args.batch_buckets.split(",") if b.strip()
        )
    except ValueError:
        raise SystemExit(
            f"Error: --batch-buckets must be comma-separated integers, "
            f"got {args.batch_buckets!r}"
        )
    frontend = None
    if args.scorer_shards > 1 and (args.ssl_cert or args.ssl_key):
        raise SystemExit(
            "Error: --scorer-shards does not support TLS "
            "(--ssl-cert/--ssl-key); terminate TLS in front of the "
            "frontend tier or deploy single-process"
        )
    if args.frontend_workers > 0:
        if args.ssl_cert or args.ssl_key:
            raise SystemExit(
                "Error: --frontend-workers does not support TLS "
                "(--ssl-cert/--ssl-key); terminate TLS in front of the "
                "frontend tier or deploy single-process"
            )
        from predictionio_tpu.serving.procserver import FrontendConfig

        frontend = FrontendConfig(
            workers=args.frontend_workers,
            ring_slots=args.frontend_ring_slots,
            max_inflight=args.frontend_max_inflight,
            dispatch=args.dispatch,
            pin_cpus=args.pin_cpus,
        )
    from predictionio_tpu.online.registry import RegistryError

    try:
        run_query_server(
            variant,
            host=args.ip,
            port=args.port,
            instance_id=args.engine_instance_id,
            model_version=args.model_version,
            feedback=feedback,
            ssl_cert=args.ssl_cert,
            ssl_key=args.ssl_key,
            batching=BatchConfig(
                max_batch_size=args.max_batch_size,
                window_ms=args.batch_window_ms,
                buckets=buckets,
            ),
            tracing=False if args.no_tracing else None,
            trace_sample=args.trace_sample,
            slow_query_ms=args.slow_query_ms,
            frontend=frontend,
            scorer_shards=args.scorer_shards,
        )
    except RegistryError as exc:
        # --model-version names an exact artifact; a missing or corrupt one
        # must be an actionable error, never a silent fallback deploy
        raise SystemExit(f"Error: {exc}")
    return 0


def cmd_retrain(args: argparse.Namespace) -> int:
    from predictionio_tpu.obs.logs import configure_logging
    from predictionio_tpu.online.foldin import StalenessBudget
    from predictionio_tpu.online.loop import RetrainConfig, RetrainLoop

    configure_logging(args.log_format)
    variant = _load_variant(args)
    notify = [u for u in (args.notify or ["http://localhost:8000"]) if u]
    config = RetrainConfig(
        interval_s=args.interval,
        wal_dir=args.wal_dir,
        registry_dir=args.registry_dir,
        registry_keep=args.registry_keep,
        notify_urls=notify,
        budget=StalenessBudget(
            max_touched_frac=args.max_touched_frac,
            max_item_growth_frac=args.max_item_growth_frac,
        ),
        max_cycles=args.max_cycles if args.follow else 1,
        allow_full_retrain=not args.no_full_retrain,
        scorer_shards=args.scorer_shards,
    )
    try:
        loop = RetrainLoop(variant, config)
    except (LookupError, ValueError) as exc:
        raise SystemExit(f"Error: {exc}")
    import signal

    signal.signal(signal.SIGTERM, lambda *_: loop.stop())
    try:
        counts = loop.run_follow()
    except KeyboardInterrupt:
        counts = dict(loop.cycles)
    print(
        "Retrain loop finished: "
        + ", ".join(f"{k}={v}" for k, v in sorted(counts.items()) if v)
    )
    return 0


def cmd_undeploy(args: argparse.Namespace) -> int:
    import ssl
    import urllib.request

    import http.client

    # try the flagged scheme first; fall back to the other scheme ONLY on
    # errors that look like a scheme mismatch (TLS handshake noise / bad
    # status line), so a plainly-down server reports its real error once
    schemes = ("https", "http") if args.ssl else ("http", "https")
    insecure = ssl.create_default_context()
    insecure.check_hostname = False
    insecure.verify_mode = ssl.CERT_NONE
    first_exc = None
    for attempt, scheme in enumerate(schemes):
        url = f"{scheme}://{args.ip}:{args.port}/stop"
        try:
            urllib.request.urlopen(
                urllib.request.Request(url, method="POST", data=b""),
                timeout=5,
                context=insecure if scheme == "https" else None,
            )
            print("Engine server stopping.")
            return 0
        except Exception as exc:
            if attempt == 0:
                first_exc = exc
                root = getattr(exc, "reason", exc)
                mismatch = isinstance(
                    root, (ssl.SSLError, http.client.BadStatusLine)
                )
                if not mismatch:
                    break
    print(
        f"Error: cannot reach engine server at {args.ip}:{args.port}: {first_exc}"
    )
    return 1


def _resolve_dotted(dotted: str, engine_dir: str):
    """Resolve a dotted path to an Evaluation/EngineParamsGenerator, calling
    it if it is a class or factory function."""
    from predictionio_tpu.controller.metrics import EngineParamsGenerator, Evaluation
    from predictionio_tpu.workflow.json_extractor import (
        EngineConfigError,
        resolve_dotted,
    )

    try:
        obj = resolve_dotted(dotted, engine_dir)
    except EngineConfigError as exc:
        raise SystemExit(f"Error: {exc}")
    if isinstance(obj, (Evaluation, EngineParamsGenerator)):
        return obj
    return obj()


def _cmd_replay_eval(args: argparse.Namespace) -> int:
    import json

    from predictionio_tpu.eval.replay import run_replay_eval
    from predictionio_tpu.online.registry import RegistryError

    variant = _load_variant(args)
    # env mirror for ctx-free layers, same as cmd_train
    if args.snapshot_mode:
        variant.runtime_conf["pio.snapshot_mode"] = args.snapshot_mode
        os.environ["PIO_SNAPSHOT_MODE"] = args.snapshot_mode
    if args.snapshot_dir:
        variant.runtime_conf["pio.snapshot_dir"] = args.snapshot_dir
        os.environ["PIO_SNAPSHOT_DIR"] = args.snapshot_dir
    try:
        report = run_replay_eval(
            variant,
            split_time=args.split_time,
            split_frac=args.split_frac,
            k=args.k,
            metrics=args.metrics,
            model_version=args.model_version,
            registry_dir=args.registry_dir,
            retrieval_guard=not args.no_retrieval_guard,
        )
    except (ValueError, NotImplementedError, RegistryError) as exc:
        # exit-2 contract (mirrors `pio check --rules`): a bad metric name,
        # malformed boundary, unsupported engine, or GC'd pinned version is
        # an actionable one-liner, never a traceback
        print(f"Error: {exc}")
        return 2
    text = json.dumps(report, indent=2, sort_keys=True)
    print(text)
    if args.output_path:
        with open(args.output_path, "w") as f:
            f.write(text + "\n")
        print(f"Results written to {args.output_path}")
    return 0


def cmd_eval(args: argparse.Namespace) -> int:
    from predictionio_tpu.controller.metrics import (
        EngineParamsGenerator,
        Evaluation,
    )
    from predictionio_tpu.workflow.core_workflow import run_evaluation

    if args.replay:
        return _cmd_replay_eval(args)
    if not args.evaluation:
        print(
            "Error: pio eval needs a dotted Evaluation path, or --replay"
            " for the offline replay harness"
        )
        return 2
    evaluation = _resolve_dotted(args.evaluation, args.engine_dir)
    if not isinstance(evaluation, Evaluation):
        raise SystemExit(
            f"Error: {args.evaluation!r} did not yield an Evaluation"
        )
    if args.paramsgen:
        generator = _resolve_dotted(args.paramsgen, args.engine_dir)
    else:
        from predictionio_tpu.controller.engine import EngineParams

        generator = EngineParamsGenerator([EngineParams()])
    if not isinstance(generator, EngineParamsGenerator):
        raise SystemExit(f"Error: {args.paramsgen!r} did not yield an EngineParamsGenerator")
    instance = run_evaluation(
        evaluation,
        generator,
        evaluation_class=args.evaluation,
        generator_class=args.paramsgen or "",
    )
    print(instance.evaluator_results)
    if args.output_path:
        with open(args.output_path, "w") as f:
            f.write(instance.evaluator_results_json)
        print(f"Results written to {args.output_path}")
    print(f"Evaluation instance ID: {instance.id}")
    return 0


def cmd_check(args: argparse.Namespace) -> int:
    from predictionio_tpu.analysis.engine import run_with_args

    return run_with_args(args)


def cmd_batchpredict(args: argparse.Namespace) -> int:
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    variant = _load_variant(args)
    count = run_batch_predict(
        variant, args.input, args.output, instance_id=args.engine_instance_id
    )
    print(f"Batch predict completed: {count} queries -> {args.output}")
    return 0


def _parse_passthrough(tokens: list[str]) -> dict:
    """``-- --mesh-shape 2,4 --key value`` -> runtime conf entries."""
    conf = {}
    i = 0
    while i < len(tokens):
        tok = tokens[i]
        if tok.startswith("--"):
            key = tok[2:].replace("-", "_")
            if i + 1 < len(tokens) and not tokens[i + 1].startswith("--"):
                value = tokens[i + 1]
                i += 1
            else:
                value = "true"
            if key in ("mesh_shape", "dcn_mesh_shape"):
                conf[f"pio.{key}"] = [int(x) for x in value.split(",")]
            elif key == "mesh_axes":
                conf["pio.mesh_axes"] = value.split(",")
            else:
                conf[f"pio.{key}"] = value
        i += 1
    return conf
