"""unattributed_ms: what no stage sees — mean client latency of the
window's answered reads minus the mean sum of the connection thread's
stage walls (TCP, the client, and the connection thread waiting for the
GIL before ``recv`` returns)."""

from . import _stages


def read(run):
    win = _stages.window(run)
    if win is None:
        return None
    lat = [r.latency_s for r in run.records if r.ok and not r.op.write]
    staged_ms = sum(d["wallUs"] for d in win["stages"].values()) \
        / win["requests"] / 1e3
    return 1e3 * sum(lat) / len(lat) - staged_ms
