"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``workloads/<cell>.json``; it names its configuration
(``configs/<config>.json``) and its driver (``drivers/<driver>.py``). Which
metrics a cell reports is read from ``BENCHMARK.json``; each per-layer metric
is ``layer_metrics/<name>.py``. This file names no cell, configuration or
metric: a later PR adds files and ``BENCHMARK.json`` entries and edits nothing.

The last line of standard output is the result, one JSON object. Without the
chips the cell asks for the command prints no result and exits non-zero.
``--rehearse 1`` walks the same path on the CPU at a cut size and prints
counts only: no metric, and a device that says ``cpu``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, ".out")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest: dict, cell_name: str, produced: dict) -> tuple[list, list]:
    """The end-to-end and per-layer entries this cell reports."""
    def listed(entry):
        return "workloads" not in entry or cell_name in entry["workloads"]

    end_to_end = [m for m in manifest["end_to_end"]
                  if listed(m) and m["name"] in produced]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if listed(m) and m["moves"] in reported]
    return end_to_end, per_layer


def say(**facts) -> None:
    """An earlier line of the output: one JSON object, never the last."""
    print(json.dumps(facts, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the next lower precision (not a benchmark run)")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest than BENCHMARK.json (a test of a cell it"
                         " does not list; not a benchmark run)")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    cell = load_json(HERE, "workloads", args.workload + ".json")
    manifest = load_json(args.manifest)
    entry = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT, config_entry["file"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("PIO_PLATFORM", None)
    from predictionio_tpu.utils.platform import ensure_backend

    platform = ensure_backend()  # raises where no accelerator comes up
    import jax

    devices = jax.devices()
    if not args.rehearse and (platform == "cpu" or len(devices) < entry["chips"]):
        raise SystemExit(
            f"{args.workload} needs {entry['chips']} accelerator chip(s); JAX"
            f" found {len(devices)} device(s) on {platform!r}"
        )

    out_dir = os.path.join(OUT, args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = SimpleNamespace(
        t0=T0, cell=args.workload, config=config, traffic=cell["traffic"],
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=bool(args.rehearse), control=bool(args.control),
        chips=entry["chips"], devices=devices[: entry["chips"]],
        out_dir=out_dir, say=say,
    )
    run = load_module("drivers", cell["driver"]).run(ctx)

    for check in run["checks"]:
        say(check=check["name"], value=check["value"], limit=check["limit"],
            ok=check["ok"])
    correct = all(check["ok"] for check in run["checks"])

    stats = [d.memory_stats() or {} for d in ctx.devices]
    # the runtime counts the two apart: what the process keeps on the device
    # (ratings, indexes, factors) and what the loaded programs reserve for
    # their temporaries. The peak a chip held is their sum (PERF.md section 6)
    say(memory=[{"live_peak_bytes": s.get("peak_bytes_in_use", 0),
                 "program_reserved_peak_bytes": s.get("peak_bytes_reserved", 0)}
                for s in stats])
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
            for s in stats
        ),
    }
    end_to_end, per_layer = metrics_of(manifest, args.workload, run["end_to_end"])
    metrics: dict = {}
    result = {"correct": correct, "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if args.rehearse:
        pass  # counts only: a CPU number is never a device metric
    elif args.trace:
        trace = run["trace"]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        for m in per_layer:
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        for m in end_to_end:
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
