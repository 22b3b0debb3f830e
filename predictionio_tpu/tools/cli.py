"""The ``pio`` command-line console.

Behavioral model: reference ``tools/.../console/{Console,Pio}.scala``
(apache/predictionio layout, unverified -- SURVEY.md section 2.4 #27). Verb
set and flag names kept; process orchestration targets the JAX runtime
instead of spark-submit.

This module grows with the framework; verbs are registered in
``predictionio_tpu.tools.commands``.
"""

from __future__ import annotations

import argparse
import os
import sys

from predictionio_tpu.version import __version__


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="pio",
        description="predictionio_tpu: TPU-native machine learning server",
    )
    sub = parser.add_subparsers(dest="command")

    sub.add_parser("version", help="print version")

    status = sub.add_parser("status", help="verify configuration and storage connectivity")
    status.set_defaults(func=cmd_status)

    from predictionio_tpu.tools import commands

    commands.register(sub)
    return parser


def cmd_status(args: argparse.Namespace) -> int:
    from predictionio_tpu.data import storage

    print(f"pio (predictionio_tpu) {__version__}")
    # the accelerator is this framework's execution substrate (the role
    # SPARK_HOME verification played in the reference's `pio status`).
    # Probe it in a child with a time limit: this process never imports
    # JAX, so a status check beside a running train or deploy cannot take
    # the chip from it, and the command always answers.
    import subprocess

    probe = (
        "from predictionio_tpu.utils.platform import ensure_backend\n"
        "import jax\n"
        "p = ensure_backend()\n"
        "ds = jax.devices()\n"
        "print('PIO_ACCEL|' + p + '|' + str(len(ds)) + '|' + ds[0].device_kind)\n"
    )
    device_ok = False
    try:
        proc = subprocess.run(
            [sys.executable, "-c", probe],
            capture_output=True,
            text=True,
            timeout=float(os.environ.get("PIO_STATUS_PROBE_TIMEOUT_S", "60")),
        )
        fields = next(
            (
                line.split("|")
                for line in proc.stdout.splitlines()
                if line.startswith("PIO_ACCEL|")
            ),
            None,
        )
        if fields is None:
            reason = (proc.stderr.strip().splitlines() or ["no output"])[-1]
            print(f"Device: NOT AVAILABLE -- {reason}")
        else:
            device_ok = True
            print(f"Device: {fields[1]} x{fields[2]} ({fields[3]})")
    except subprocess.TimeoutExpired:
        print("Device: NOT AVAILABLE -- the backend probe timed out")
    print("Storage configuration:")
    for repo, cfg in storage.config_summary().items():
        detail = ", ".join(f"{k}={v}" for k, v in cfg.items() if k not in ("source",))
        print(f"  {repo}: source={cfg['source']} ({detail})")
    failures = storage.verify_all_data_objects()
    if failures:
        print("Storage check FAILED:")
        for failure in failures:
            print(f"  - {failure}")
        return 1
    if not device_ok:
        print("Storage check OK, but the configured JAX platform did not"
              " come up: train and deploy will fail.")
        return 1
    print("Storage check OK. Your system is all ready to go.")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.command is None:
        parser.print_help()
        return 2
    if args.command == "version":
        print(__version__)
        return 0
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
