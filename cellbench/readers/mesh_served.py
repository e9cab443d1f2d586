"""Share of the window's answered reads that a device program served on
a mesh as wide as the configuration says (``mesh_devices``).

The server writes ``meshDevices`` into ``X-Pilosa-Stats``: the widest
mesh any device program of the request ran on, absent where none ran
(``pilosa_tpu/obs/accounting.py``, ``docs/OBSERVABILITY.md``). A read the
host answered, or one a narrower mesh answered (a server that meshed one
device of four), counts against the share: ``device_served_pct`` alone
cannot tell those from a four-chip answer.

None where there is nothing to read, never 0: the configuration names
no ``mesh_devices``, the window answered no read, or the program has no
such field — no record carries it AND ``/debug/vars`` has no ``mesh``
(the parent of PR 27). A program that has the field and served every
read from the host reads 0."""


def read(run):
    want = run.config.get("mesh_devices")
    reads = [r for r in run.records if r.ok and not r.op.write]
    if not want or not reads:
        return None
    known = (any("meshDevices" in r.stats for r in reads)
             or (run.after is not None and "mesh" in run.after["vars"]))
    if not known:
        return None
    served = sum(1 for r in reads if r.stats.get("meshDevices") == want)
    return 100.0 * served / len(reads)
