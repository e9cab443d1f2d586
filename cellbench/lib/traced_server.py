#!/usr/bin/env python3
"""The server child: ``pilosa_tpu.cli`` ``server`` with a control thread.

The main thread calls the entry ``python -m pilosa_tpu.cli server`` calls,
with the same arguments. Only the process that holds the chip can trace
it or read its memory, so a daemon thread polls a directory the parent
names and answers three requests, each a file the parent creates:

``start`` (holding a directory name)  ``jax.profiler.start_trace`` there,
                                      then ``started`` is written
``stop``                              ``stop_trace``, then ``done``
``mem``                               ``mem.json``: the devices as JAX
                                      reports them and the fullest
                                      one's ``peak_bytes_in_use``

Usage: traced_server.py <control dir> <repo root> -d <data> --bind <host>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

POLL_S = 0.05


def _write(path: str, payload: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)


def _take(ctl: str, name: str) -> str | None:
    """The request file's content, removing it; None when not there."""
    path = os.path.join(ctl, name)
    try:
        with open(path) as f:
            body = f.read()
    except FileNotFoundError:
        return None
    os.remove(path)
    return body


def _device_report() -> dict:
    import jax
    devs = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def control_loop(ctl: str) -> None:
    import jax
    started = None
    parent = os.getppid()
    while True:
        time.sleep(POLL_S)
        if os.getppid() != parent:      # the harness died: leave nothing
            os._exit(1)
        body = _take(ctl, "start")
        if body is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(body.strip(), profiler_options=opts)
            started = time.time()
            _write(os.path.join(ctl, "started"), {"wall": started})
        if _take(ctl, "stop") is not None:
            stopping = time.time()
            jax.profiler.stop_trace()
            _write(os.path.join(ctl, "done"),
                   {"traceStart": started, "traceStop": stopping})
        if _take(ctl, "mem") is not None:
            _write(os.path.join(ctl, "mem.json"), _device_report())


def main(argv: list[str]) -> int:
    ctl, root, server_args = argv[0], argv[1], argv[2:]
    sys.path.insert(0, root)
    from pilosa_tpu.cli.commands import main as cli_main
    threading.Thread(target=control_loop, args=(ctl,), daemon=True,
                     name="cellbench-control").start()
    return cli_main(["server"] + server_args)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
