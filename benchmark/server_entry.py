#!/usr/bin/env python3
"""The benchmark's launcher for a TRACED run: the same `seaweedfs_tpu.cli`
entry with the same argv, beside a control thread that starts and stops
the jax profiler on a command from the harness.  Only the process that
holds the chip can trace it, and no file of the program changes.

    python benchmark/server_entry.py --control-dir DIR -- server -dir ...

Commands arrive as DIR/command.json {"command": "trace_start" | "trace_stop"};
the answer is DIR/ack.json.  The trace lands in DIR/trace.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _answer(control_dir: str, payload: dict) -> None:
    tmp = os.path.join(control_dir, "ack.json.tmp")
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, os.path.join(control_dir, "ack.json"))


def _handle(command: str, control_dir: str) -> dict:
    import jax

    if command == "trace_start":
        # no python tracer: it slows a Python server several times over
        # (PR 25: 761 GETs in a traced phase against 3,120 untraced) and
        # the layer metrics are read in this run
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        t = time.monotonic()
        jax.profiler.start_trace(os.path.join(control_dir, "trace"),
                                 profiler_options=options)
        return {"seconds": time.monotonic() - t}
    if command == "trace_stop":
        t = time.monotonic()
        jax.profiler.stop_trace()
        return {"seconds": time.monotonic() - t}
    raise ValueError(f"unknown command {command!r}")


def control_loop(control_dir: str) -> None:
    req = os.path.join(control_dir, "command.json")
    while True:
        if not os.path.exists(req):
            time.sleep(0.005)
            continue
        try:
            with open(req) as f:
                command = json.load(f)["command"]
            os.remove(req)
            _answer(control_dir, _handle(command, control_dir))
        except Exception as e:  # noqa: BLE001 — reported to the harness
            _answer(control_dir, {"error": f"{type(e).__name__}: {e}"})


def main(argv: list) -> None:
    if len(argv) < 4 or argv[0] != "--control-dir" or argv[2] != "--":
        sys.exit(__doc__)
    control_dir, server_argv = argv[1], argv[3:]
    os.makedirs(control_dir, exist_ok=True)
    threading.Thread(target=control_loop, args=(control_dir,),
                     daemon=True).start()
    from seaweedfs_tpu.cli import main as cli_main

    cli_main(server_argv)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main(sys.argv[1:])
