"""host_blocked_pct: share of the window's reads' wall time in which no
thread of the request was on a CPU.

The wall is the connection thread's stages (they tile the request from
``recv`` returning to ``sendall`` returning); the CPU is that thread's
plus the map-reduce leg thread's, which runs while the connection
thread waits in ``legs_wait``. What is left is time the request sat
behind the GIL, a lock, the device or the socket."""

from . import _stages


def read(run):
    win = _stages.window(run)
    if win is None:
        return None
    wall = sum(d["wallUs"] for d in win["stages"].values())
    cpu = win["cpuUs"] + win["offThreadCpuUs"]
    if wall <= 0:
        return None
    return 100.0 * max(0.0, wall - cpu) / wall
