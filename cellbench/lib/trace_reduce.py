"""From a profiler trace (``.xplane.pb``) to busy seconds, the device
operations that took most time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone. The caller imports this
only once the server child has exited, and with ``JAX_PLATFORMS=cpu``, so
this process never reaches for the chip.

Read off one trace by hand first (c4-count-hot, seed 101, PR 24; a
trimmed copy is ``tests/data/c4-count-hot.trimmed.xplane.pb``): a device
plane is named ``/device:TPU:<n>``; its lines are ``XLA Modules`` (one
event a program run, named ``jit_fn(<hash>)``), ``XLA Ops`` (one event
for each operation inside it, named by its HLO text), ``Async XLA Ops``
and ``TC Overlay`` (both empty there). The host is the plane
``/host:CPU``, one line a thread; the server's threads are all named
``python3`` and carry ``PjitFunction(fn)``, ``np.asarray(jax.Array)``
and the runtime's own events. Busy time is the union of the intervals
of the events on ``XLA Ops`` and ``Async XLA Ops``, averaged over the
device planes. The traced window is what the control thread clocked around
``start_trace``/``stop_trace`` where the caller gives it, else the span
from the first to the last event of any plane. Idle gaps are the
intervals between busy intervals on the first device; each is named by
the host event (from the ``/host:CPU`` plane) that covers most of it,
``unattributed`` where none does.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
HOST_PLANE = "/host:CPU"
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: list[tuple]) -> list[tuple]:
    merged: list[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def load_events(path: str) -> dict:
    """{plane name: {line name: [(event name, start ns, end ns)]}}."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                s = float(ev.start_ns)
                evs.append((ev.name, s, s + float(ev.duration_ns)))
    return out


def reduce_events(planes: dict, window_s: float | None = None) -> dict:
    """busy_s, window_s and the breakdown from ``load_events``' shape.
    ``busy_s`` is 0.0 where no device plane holds an operation."""
    device = {}
    for name, lines in planes.items():
        ops = [e for ln in OPS_LINES for e in lines.get(ln, ())]
        if name.startswith(DEVICE_PLANE_PREFIX) and ops:
            device[name] = ops
    every = [e for lines in planes.values() for evs in lines.values()
             for e in evs]
    if window_s is None and every:
        window_s = (max(e[2] for e in every)
                    - min(e[1] for e in every)) / 1e9
    out = {"busy_s": 0.0, "window_s": window_s or 0.0, "devices": 0,
           "device_ops": [], "idle_gaps": []}
    if not device:
        return out
    busy_ns = []
    by_name: dict[str, float] = {}
    for ops in device.values():
        busy_ns.append(sum(e - s for s, e in
                           _union([(s, e) for _, s, e in ops])))
        for name, s, e in ops:
            by_name[name] = by_name.get(name, 0) + (e - s)
    out["devices"] = len(device)
    out["busy_s"] = sum(busy_ns) / len(busy_ns) / 1e9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    out["device_ops"] = [[n, ns / 1e9 / len(device)] for n, ns in top]

    first = device[sorted(device)[0]]
    merged = _union([(s, e) for _, s, e in first])
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])),
                  reverse=True)[:TOP]
    host = [e for evs in planes.get(HOST_PLANE, {}).values() for e in evs]
    for length, g0, g1 in gaps:
        best, best_ns = "unattributed", 0.0
        for name, s, e in host:
            cover = min(e, g1) - max(s, g0)
            if cover > best_ns:
                best, best_ns = name, cover
        out["idle_gaps"].append([best, length / 1e9])
    return out


def reduce_file(path: str, window_s: float | None = None) -> dict:
    return reduce_events(load_events(path), window_s)
