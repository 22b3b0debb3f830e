"""A cell's set-up as the program's own timeline, for a reader of `setup_s`.

    python benchmarks/setup_timeline.py --workload <cell> --seed <n> --seconds <s> [--trace 1]

runs the cell as ``run.py`` does (its arguments, its output, its result line)
and then prints what ``layer_metrics/_setup.py`` reads, row by row: every
program of ``utils.platform.compile_report()`` and every span of the global
tracer in the order they began, seconds from ``run.py``'s ``T0``, with ``>``
before those that ended after the window opened (the probe after
``jax.clear_caches()``, the reference), and the rows' seconds split at the
opening. The same goes to ``.out/<cell>/setup_timeline.json``. Not a benchmark
run: the result line is not the last. The compile cache's key holds the frames
a program was traced under, this file's among them, so what ``run.py`` cached
is not found from here: run it twice to read a warm machine.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as run_mod  # noqa: E402  (sets T0)
from benchmarks.layer_metrics import _setup  # noqa: E402

COLUMNS = ("trace_s", "lower_s", "compile_s", "nested_s")


def main(argv=None) -> int:
    kept: dict = {}
    load_module = run_mod.load_module

    def keeping(kind: str, name: str):
        module = load_module(kind, name)
        if kind == "drivers":
            driver = module.run

            def run(ctx):
                kept["out_dir"] = ctx.out_dir
                kept["run"] = driver(ctx)
                return kept["run"]

            module.run = run
        return module

    run_mod.load_module = keeping
    try:
        code = run_mod.main(argv)
    finally:
        run_mod.load_module = load_module

    found = _setup.timeline(kept["run"])
    setup_s = found.opened - found.start
    lines = [{"what": row["program"], "start": row["start_s"] - found.start,
              "end": row["end_s"] - found.start, "cache": row["cache"],
              **{column: row[column] for column in COLUMNS}} for row in found.rows]
    lines += [{"what": op, "start": begin - found.start, "end": end - found.start}
              for op, begin, end in found.spans if not op.startswith("jit.")]
    lines.sort(key=lambda line: line["start"])
    late = [row["end_s"] > found.opened for row in found.rows]
    totals = {column: {"before": sum(r[column] for r, after in zip(found.rows, late) if not after),
                       "after": sum(r[column] for r, after in zip(found.rows, late) if after)}
              for column in COLUMNS}
    summary = {"setup_s": setup_s, "rows": len(found.rows), "totals": totals}
    with open(os.path.join(kept["out_dir"], "setup_timeline.json"), "w") as f:
        json.dump({**summary, "timeline": lines}, f)
    for line in lines:
        parts = "" if "cache" not in line else "  " + " ".join(
            f"{column[:-2]} {line[column]:.2f}" for column in COLUMNS) + f" cache {line['cache']}"
        print(f"{'>' if line['end'] > setup_s else ' '} {line['start']:8.2f}"
              f" {line['end']:8.2f}  {line['what']}{parts}")
    print(json.dumps(summary))
    return code


if __name__ == "__main__":
    sys.exit(main())
