"""Distributed tracing: one trace per query, spans per pipeline stage.

A trace's id IS the query id (sched.context.QueryContext.id), which
already rides cluster fan-out as ``X-Pilosa-Query-Id`` — so every
node's spans for one query share an id for free. The wire contract:

- ``X-Pilosa-Trace: 1`` on a forwarded (remote) query asks the peer to
  trace its leg even when the peer's own tracing is off;
- the peer piggybacks its spans back as the compact JSON response
  header ``X-Pilosa-Trace-Spans``, and the coordinator's cluster
  client stitches them into the originating trace (child spans with
  the remote node's attribution).

Spans record wall-clock start + duration (microsecond precision is
plenty; coordinator and peers align on wall time), a name, optional
tags, the owning node, and the recording thread. ``GET /debug/traces``
lists the per-node bounded ring of recent traces;
``GET /debug/traces/{id}`` exports one as Chrome trace-event JSON
(open in https://ui.perfetto.dev — each node renders as a process,
each thread as a track).

One emitter: spans are recorded by the query's stage clock
(``QueryContext.stage`` / ``.span``, sched.context) and by nothing
else; this module keeps the span buffer, the ring and the export.

Overhead contract: the *keep-everything* mode is OFF by default, and a
QueryContext whose ``trace`` is None records no span (``ctx.span()``
returns a shared no-op context manager). Since the always-on PR the
serving layer attaches a
span buffer to EVERY query (tail sampling, obs.sampler): the buffer
itself is the measured-near-free part, and the keep decision at query
end picks which traces reach the ring and the on-disk segment ring
(``Tracer.keep(trace, reason)``; the keep-reason catalogue lives in
obs.sampler / docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import threading
import time
import weakref
from collections import deque
from typing import Optional

# Wire headers (see module docstring).
TRACE_HEADER = "X-Pilosa-Trace"
SPANS_HEADER = "X-Pilosa-Trace-Spans"

# Hard caps so a pathological query can't balloon a trace or the
# piggyback header.
MAX_SPANS = 512
MAX_TRACES = 64


class Span:
    __slots__ = ("name", "start", "dur", "tags", "node", "tid")

    def __init__(self, name: str, start: float, dur: float,
                 tags: Optional[dict] = None, node: str = "",
                 tid: int = 0):
        self.name = name
        self.start = start          # wall seconds
        self.dur = dur              # seconds
        self.tags = tags
        self.node = node
        self.tid = tid

    def to_json(self) -> list:
        # Compact array form: [name, start_us, dur_us, node, tid, tags]
        return [self.name, round(self.start * 1e6),
                round(self.dur * 1e6), self.node, self.tid,
                self.tags or None]

    @staticmethod
    def from_json(row: list) -> "Span":
        return Span(row[0], row[1] / 1e6, row[2] / 1e6,
                    tags=row[5], node=row[3], tid=int(row[4]))


class Trace:
    """All spans this node recorded (or stitched) for one query."""

    def __init__(self, id: str, node: str = "", pql: str = "",
                 max_spans: int = MAX_SPANS):
        self.id = id
        self.node = node
        self.pql = pql
        self.started = time.time()
        self.max_spans = max_spans
        self.dropped = 0
        # Why the tail sampler retained this trace ("" while in
        # flight / never kept) — obs.sampler's keep-reason catalogue.
        self.keep_reason = ""
        self._mu = threading.Lock()
        self._spans: list[Span] = []
        # The query whose stage clock records this trace's stage spans
        # (bound by Tracer.start): read from it while it lives, copied
        # in by ``seal`` when a kept trace is about to outlive it. A
        # weak reference: ctx.trace points here, and a cycle would
        # leave every request to the cyclic collector.
        self._ctx = None

    # -- recording -----------------------------------------------------------

    def claim_keep(self, reason: str) -> bool:
        """Atomically claim the keep of this trace (first claimant
        wins): the end-of-query decision and the watchdog's force-keep
        can race, and exactly ONE of them may enter the ring/disk."""
        with self._mu:
            if self.keep_reason:
                return False
            self.keep_reason = reason
            return True

    def add_span(self, name: str, start: float, dur: float,
                 tags: Optional[dict] = None, node: str = "",
                 tid: Optional[int] = None) -> None:
        s = Span(name, start, dur, tags, node or self.node,
                 threading.get_ident() if tid is None else tid)
        with self._mu:
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                return
            self._spans.append(s)

    def add_remote_json(self, payload: str) -> None:
        """Stitch a peer's piggybacked spans (SPANS_HEADER value)."""
        try:
            rows = json.loads(payload)
        except ValueError:
            return
        with self._mu:
            for row in rows:
                if len(self._spans) >= self.max_spans:
                    self.dropped += 1
                    break
                try:
                    self._spans.append(Span.from_json(row))
                except (IndexError, TypeError, ValueError):
                    continue

    # -- export --------------------------------------------------------------

    def _stage_spans(self) -> list[Span]:
        ctx = self._ctx() if self._ctx is not None else None
        if ctx is None:
            return []
        return [Span(name, start, dur, tags, self.node, tid)
                for name, start, dur, tags, tid in ctx.stage_spans()]

    def spans(self) -> list[Span]:
        with self._mu:
            out = list(self._spans)
        room = max(0, self.max_spans - len(out))
        out.extend(self._stage_spans()[:room])
        return out

    def seal(self) -> None:
        """Copy the query's stage spans in and let go of the query:
        called when the query ends, for a trace that was kept."""
        staged = self._stage_spans()
        with self._mu:
            self._ctx = None
            room = max(0, self.max_spans - len(self._spans))
            self.dropped += max(0, len(staged) - room)
            self._spans.extend(staged[:room])

    # Serialized-spans budget for the piggyback header: http.client
    # rejects header LINES over 65536 bytes (LineTooLong kills the
    # whole response), so the wire form must stay comfortably under.
    _WIRE_BYTES = 48 << 10

    def spans_json(self, max_bytes: int = _WIRE_BYTES) -> str:
        """Compact JSON of this trace's spans, capped at ``max_bytes``
        serialized — over budget, the newest spans drop (the early
        pipeline stages are the ones a stitched view can't infer)."""
        spans = self.spans()
        out = json.dumps([s.to_json() for s in spans],
                         separators=(",", ":"))
        while len(out) > max_bytes and len(spans) > 1:
            spans = spans[:max(1, len(spans) // 2)]
            out = json.dumps([s.to_json() for s in spans],
                             separators=(",", ":"))
        return out

    def summary(self) -> dict:
        spans = self.spans()
        end = max((s.start + s.dur for s in spans),
                  default=self.started)
        out = {
            "id": self.id,
            "node": self.node,
            "pql": self.pql[:200],
            "startedAt": self.started,
            "durationS": round(max(0.0, end - self.started), 6),
            "spanN": len(spans),
            "dropped": self.dropped,
            "nodes": sorted({s.node for s in spans if s.node}),
        }
        if self.keep_reason:
            out["reason"] = self.keep_reason
        return out

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (perfetto-loadable): one process
        per node, one track per recording thread, spans as complete
        ("X") events in microseconds."""
        events = []
        pids: dict[str, int] = {}
        tids: dict[tuple[int, int], int] = {}
        for s in self.spans():
            node = s.node or self.node or "?"
            pid = pids.setdefault(node, len(pids) + 1)
            tid = tids.setdefault((pid, s.tid), len(tids) + 1)
            ev = {"name": s.name, "ph": "X", "pid": pid, "tid": tid,
                  "ts": round(s.start * 1e6),
                  "dur": max(1, round(s.dur * 1e6))}
            if s.tags:
                ev["args"] = s.tags
            events.append(ev)
        for node, pid in pids.items():
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid, "args": {"name": node}})
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"traceId": self.id, "pql": self.pql[:200],
                          "coordinator": self.node,
                          "dropped": self.dropped},
        }


class Tracer:
    """Per-node tracer: the enabled flag plus the bounded ring of
    recent traces behind /debug/traces."""

    def __init__(self, enabled: bool = False,
                 max_traces: int = MAX_TRACES,
                 max_spans: int = MAX_SPANS):
        self.enabled = enabled
        self.max_spans = max_spans
        self._mu = threading.Lock()
        self._ring: deque[Trace] = deque(maxlen=max(1, max_traces))

    def start(self, ctx, node: str = "") -> Trace:
        """Open a trace for a query context and bind it (ctx.trace) so
        every layer below can record spans through the context."""
        trace = Trace(ctx.id, node=node or getattr(ctx, "node", ""),
                      pql=getattr(ctx, "pql", ""),
                      max_spans=self.max_spans)
        trace._ctx = weakref.ref(ctx)
        ctx.trace = trace
        return trace

    def keep(self, trace: Trace, reason: str = "requested") -> bool:
        """Retain ``trace`` in the ring under ``reason``; idempotent —
        False (and no second ring entry / counter tick) when another
        keeper already claimed it."""
        from . import metrics as obs_metrics
        if not trace.claim_keep(reason):
            return False
        with self._mu:
            self._ring.append(trace)
        obs_metrics.TRACES_KEPT.labels(reason).inc()
        return True

    def traces(self) -> list[dict]:
        with self._mu:
            ring = list(self._ring)
        return [t.summary() for t in reversed(ring)]

    def get(self, id: str) -> Optional[Trace]:
        with self._mu:
            for t in reversed(self._ring):
                if t.id == id:
                    return t
        return None


# Module default, for layers constructed without explicit wiring (bare
# test handlers); the server builds its own Tracer from [trace] config.
_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _tracer
