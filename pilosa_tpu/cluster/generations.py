"""Mutation-generation tokens: cluster-wide cache-validity facts.

The single-node fast paths (result residency, the fused device count
fold, single-pass TopN) key every cached artifact by the backing
fragments' ``(uid, generation)`` pairs (parallel.residency): writes
bump the generation, reopen mints a fresh uid, and stale entries
simply stop being referenced. That staleness contract was *local* —
a slice owned by another node had an invisible generation, so
ownership-gated paths fell back to the slow fan-out the moment a
query touched a remote slice.

This module makes generations a *cluster-wide* fact:

- **Tokens**: per-fragment ``(uid, generation)`` pairs, grouped per
  slice as ``{"<frame>/<view>": [uid, gen]}`` dicts. uids are
  process-local counters, so a token is only meaningful relative to
  the peer that minted it — every consumer keys by ``(peer, uid,
  gen)``, never by the bare pair.
- **Wire**: serving nodes piggyback their current tokens for the
  served slices on internal query responses and import acks as the
  ``X-Pilosa-Generations`` header (the X-Pilosa-Cost /
  X-Pilosa-Trace-Spans stitching pattern), and answer the cheap
  ``GET /generations`` probe — the validation round-trip the
  coordinator result cache rides.
- **GenerationMap**: the coordinator-side per-peer map. Entries carry
  a monotonic receive timestamp; reads specify a staleness bound and
  get ``None`` past it, so a consumer can choose between
  bounded-staleness keying (executor._bitmap_result_key: serve from
  cache while the map is fresh) and round-trip validation (the
  cluster result cache: probe /generations and compare before
  serving).

Invalidation is by mismatch, not callbacks: a write to any replica
bumps that replica's generations, the next exchange with it (query
response, import ack, or probe) carries the new tokens, and every
cached artifact keyed by the old tokens stops matching.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Optional

# Internal response header carrying the serving node's tokens for the
# slices it served (query legs and import acks).
GENERATIONS_HEADER = "X-Pilosa-Generations"

# Caps on one wire payload: fragment entries AND encoded bytes (the
# byte budget is the binding one — http.client rejects header LINES
# over 64 KiB, same rationale as the trace/cost 48 KiB budgets). Past
# either, whole slices are dropped (never a partial slice — a consumer
# either sees a slice's complete token dict or nothing) and the
# payload is marked truncated.
MAX_WIRE_FRAGMENTS = 4096
MAX_WIRE_BYTES = 48 << 10

# Default staleness bound (seconds) for map reads that do NOT pay a
# validation round-trip. Writes routed through this coordinator refresh
# the map on their own response, so the bound only governs writes that
# bypassed it (another coordinator, a direct client).
DEFAULT_STALENESS_S = 2.0


def frag_key(frame: str, view: str) -> str:
    return f"{frame}/{view}"


def slice_tokens(holder, index: str, slice: int) -> dict:
    """This node's current ``{frame/view: (uid, gen)}`` dict for one
    slice — every open fragment of every frame/view at that slice.
    Empty dict = no fragments there (a valid, comparable state)."""
    idx = holder.index(index)
    if idx is None:
        return {}
    out: dict[str, tuple[int, int]] = {}
    for fname in sorted(idx.frames):
        frame = idx.frames[fname]
        for vname in sorted(frame.views):
            frag = frame.views[vname].fragments.get(slice)
            if frag is not None:
                dev = frag.device
                out[frag_key(fname, vname)] = (dev.uid, dev.generation)
    return out


def local_tokens(holder, index: str, slices) -> dict:
    """``{slice: {frame/view: (uid, gen)}}`` for the given slices."""
    return {int(s): slice_tokens(holder, index, int(s)) for s in slices}


def encode_wire(index: str, tokens: dict,
                max_fragments: int = MAX_WIRE_FRAGMENTS,
                max_bytes: int = MAX_WIRE_BYTES) -> str:
    """Compact JSON for the header / probe body. ``tokens`` is the
    local_tokens shape. Slices are included whole, in ascending order,
    until the fragment cap OR the byte budget (whichever binds —
    header lines over 64 KiB would fail the very response carrying
    them); the rest are dropped and ``x`` marks the truncation
    (consumers treat absent slices as unknown, never as empty)."""
    t: dict = {}
    n = 0
    # Envelope + truncation marker overhead, counted up front so the
    # budget bounds the FINAL encoded size.
    size = len(json.dumps({"i": index, "t": {}, "x": 1},
                          separators=(",", ":")))
    truncated = False
    for s in sorted(tokens):
        m = tokens[s]
        chunk = json.dumps(
            {str(s): {k: [v[0], v[1]] for k, v in m.items()}},
            separators=(",", ":"))
        cost = len(chunk) - 1  # minus braces, plus the joining comma
        if size + cost > max_bytes:
            # Byte budget binds even for the FIRST slice: an
            # over-64KiB header line would fail the whole response.
            truncated = True
            break
        if t and n + len(m) > max_fragments:
            truncated = True
            break
        n += len(m)
        size += cost
        t[str(s)] = {k: [v[0], v[1]] for k, v in m.items()}
    out = {"i": index, "t": t}
    if truncated:
        out["x"] = 1
    return json.dumps(out, separators=(",", ":"))


def decode_wire(payload: str):
    """(index, {slice: {frag_key: (uid, gen)}}) or None on garbage —
    a malformed header must never fail the query that carried it."""
    try:
        data = json.loads(payload)
        index = data["i"]
        tokens = {}
        for s, m in (data.get("t") or {}).items():
            tokens[int(s)] = {str(k): (int(v[0]), int(v[1]))
                              for k, v in m.items()}
    except (ValueError, KeyError, TypeError, IndexError):
        return None
    if not isinstance(index, str):
        return None
    return index, tokens


def decode_tokens(raw: dict) -> dict:
    """The /generations probe's ``tokens`` object → the local_tokens
    shape (lenient: bad entries dropped, not raised)."""
    out: dict = {}
    for s, m in (raw or {}).items():
        try:
            out[int(s)] = {str(k): (int(v[0]), int(v[1]))
                           for k, v in m.items()}
        except (ValueError, TypeError, KeyError, IndexError):
            continue
    return out


class GenerationMap:
    """Coordinator-side per-peer generation knowledge.

    ``apply(peer, index, tokens)`` records a peer's tokens (from a
    response header or a probe) with a monotonic timestamp; readers
    pass a staleness bound and get None past it. Thread-safe; bounded
    per peer (oldest slices evicted beyond ``max_slices_per_peer``).
    """

    def __init__(self, staleness_s: float = DEFAULT_STALENESS_S,
                 max_slices_per_peer: int = 65536):
        self.staleness_s = staleness_s
        self.max_slices_per_peer = max_slices_per_peer
        self._mu = threading.Lock()
        # peer -> (index, slice) -> (tokens dict, monotonic ts)
        self._peers: dict[str, dict[tuple, tuple]] = {}

    def apply(self, peer: str, index: str, tokens: dict) -> int:
        """Record ``{slice: {frag_key: (uid, gen)}}`` for a peer;
        returns the number of slice entries applied."""
        if not peer or not tokens:
            return 0
        now = time.monotonic()
        with self._mu:
            m = self._peers.setdefault(peer, {})
            for s, toks in tokens.items():
                m[(index, int(s))] = (dict(toks), now)
            if len(m) > self.max_slices_per_peer:
                # Rare; evict oldest entries wholesale.
                drop = sorted(m.items(), key=lambda kv: kv[1][1])
                for k, _ in drop[:len(m) - self.max_slices_per_peer]:
                    del m[k]
        try:
            from ..obs import metrics as obs_metrics
            obs_metrics.GENERATION_UPDATES.labels(peer).inc(len(tokens))
        except Exception:  # noqa: BLE001 - accounting never fails a query
            pass
        return len(tokens)

    def apply_wire(self, peer: str, payload: str) -> int:
        """Record a piggybacked GENERATIONS_HEADER payload."""
        decoded = decode_wire(payload)
        if decoded is None:
            return 0
        index, tokens = decoded
        return self.apply(peer, index, tokens)

    def tokens(self, peer: str, index: str, slice: int,
               max_age_s: Optional[float] = None) -> Optional[dict]:
        """The freshest-known token dict for (peer, index, slice), or
        None when unknown or older than the staleness bound."""
        if max_age_s is None:
            max_age_s = self.staleness_s
        with self._mu:
            m = self._peers.get(peer)
            ent = m.get((index, slice)) if m else None
        if ent is None:
            return None
        toks, ts = ent
        if time.monotonic() - ts > max_age_s:
            return None
        return toks

    def token(self, peer: str, index: str, frame: str, view: str,
              slice: int,
              max_age_s: Optional[float] = None) -> Optional[tuple]:
        """One fragment's (uid, gen) at a peer, or None when the slice
        is unknown/stale. An absent fragment in a KNOWN slice reads as
        (0, 0) — same identity the local key path uses for absent
        fragments, and distinguishable from "unknown"."""
        toks = self.tokens(peer, index, slice, max_age_s=max_age_s)
        if toks is None:
            return None
        return toks.get(frag_key(frame, view), (0, 0))

    def newest(self, index: str, slice: int,
               min_ts: Optional[float] = None):
        """(peer, tokens, ts) with the newest knowledge of (index,
        slice) across peers, or None. ``min_ts`` filters to entries at
        least that fresh (the cluster result cache only snapshots
        tokens refreshed by the query being cached)."""
        best = None
        with self._mu:
            for peer, m in self._peers.items():
                ent = m.get((index, slice))
                if ent is None:
                    continue
                toks, ts = ent
                if min_ts is not None and ts < min_ts:
                    continue
                if best is None or ts > best[2]:
                    best = (peer, toks, ts)
        return best

    def snapshot(self) -> dict:
        with self._mu:
            return {"peers": len(self._peers),
                    "entries": sum(len(m)
                                   for m in self._peers.values()),
                    "stalenessS": self.staleness_s}
