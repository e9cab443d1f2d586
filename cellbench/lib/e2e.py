"""End-to-end metrics: what the client saw over the whole window.

Each function takes the finished ``Run`` and returns a number, or None
where the window holds nothing to read it from (the harness then leaves
the metric out and the run fails its own completeness check)."""

from __future__ import annotations

from .stats import percentile


def _latencies_ms(run, write: bool) -> list[float]:
    return sorted(r.latency_s * 1e3 for r in run.records
                  if r.ok and r.op.write == write)


def read_p50_ms(run):
    return percentile(_latencies_ms(run, write=False), 50)


def read_p95_ms(run):
    return percentile(_latencies_ms(run, write=False), 95)


def write_p95_ms(run):
    return percentile(_latencies_ms(run, write=True), 95)


def qps(run):
    """Requests answered, and not found wrong, over the whole window:
    from its first request sent to its last answer read."""
    good = sum(1 for r in run.records if r.ok) - run.wrong_answers
    return good / (run.t_end - run.t_start)


def setup_s(run):
    return run.setup_s


METRICS = {f.__name__: f for f in
           (read_p50_ms, read_p95_ms, write_p95_ms, qps, setup_s)}
