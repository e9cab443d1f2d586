"""Per-query plan artifacts: the plan tree, its wire record, and the
stable fingerprint.

The planner (plan.planner) rewrites a read query before execution; this
module is the OBSERVABILITY half — every planned query carries a
``PlanRecord`` on its QueryContext (``ctx.plan``, next to ``ctx.cost``)
holding the chosen plan tree with per-node estimated-vs-actual
cardinality and cost. The record follows the PR-4 cost-ledger shape:

- remote legs serialize their plan into the ``X-Pilosa-Plan`` response
  header (48 KiB budget) and the coordinator's client stitches it back
  under the originating record (``add_remote_json``), so ``?profile=1``
  shows ONE plan tree spanning the whole cluster;
- ``?profile=1`` embeds ``to_tree()`` in the response (EXPLAIN ANALYZE);
  ``?plan=1`` returns the same shape without executing (EXPLAIN);
- the module enable switch mirrors obs.accounting: planning stays on by
  default and ``set_enabled(False)`` (or PILOSA_TPU_PLANNER=0) restores
  the unplanned dispatcher for A/B measurement.

Fingerprint stability contract (docs/OBSERVABILITY.md): the fingerprint
hashes the NORMALIZED canonical tree — numeric literals (row/column ids,
TopN n, BSI condition values) become ``?`` while frame/view/field names
are kept, and commutative operands (Intersect/Union children) are
sorted by their normalized form. Two queries with the same shape over
the same frames share a fingerprint regardless of literal ids or
operand order, so ``/debug/plans`` aggregates them into one row.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Optional

from ..pql.ast import Call, Condition

PLAN_HEADER = "X-Pilosa-Plan"

# Remote legs stitched under one coordinator record; past the cap extra
# legs are dropped (the accounting MAX_CHILDREN rule — a plan is a
# debugging artifact, not an unbounded ledger).
MAX_CHILDREN = 64

_enabled = os.environ.get("PILOSA_TPU_PLANNER", "1") != "0"


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


# -- fingerprint --------------------------------------------------------------


def _norm_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return "?"
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_norm_value(x) for x in v) + "]"
    return repr(v)


def normalize_call(call: Call) -> str:
    """The normalized canonical form one call hashes to — numeric
    literals ``?``'d out, commutative children sorted."""
    parts = [normalize_call(c) for c in call.children]
    if call.name in ("Intersect", "Union"):
        parts.sort()
    for k in sorted(call.args):
        v = call.args[k]
        if isinstance(v, Condition):
            parts.append(f"{k} {v.op} ?")
        else:
            parts.append(f"{k}={_norm_value(v)}")
    return f"{call.name}({','.join(parts)})"


def fingerprint_calls(calls) -> str:
    text = "\n".join(normalize_call(c) for c in calls)
    return hashlib.sha1(text.encode()).hexdigest()[:12]


# -- the plan tree ------------------------------------------------------------


class PlanNode:
    """One operator of the chosen plan. ``est_rows``/``est_cost_s``
    are the planner's predictions; ``actual_rows``/``actual_s`` are
    filled by the executor as the node runs (ANALYZE). ``decisions``
    records what the planner DID here (reordered / short_circuit /
    cse / placement:*) so a plan reads as a decision log, not just a
    shape."""

    __slots__ = ("op", "detail", "est_rows", "exact", "est_cost_s",
                 "placement", "decisions", "children", "actual_rows",
                 "actual_s", "frames", "key", "cache_lookup",
                 "cache_store", "short_circuit")

    def __init__(self, op: str, detail: str = ""):
        self.op = op
        self.detail = detail
        self.est_rows: Optional[int] = None
        self.exact = False
        self.est_cost_s: Optional[float] = None
        self.placement = "auto"
        self.decisions: list[str] = []
        self.children: list[PlanNode] = []
        self.actual_rows: Optional[int] = None
        self.actual_s: Optional[float] = None
        # Planner wiring (not serialized): frame/view keys under this
        # subtree, the canonical subtree string (the subresult-cache
        # key stem), and the cache/short-circuit marks.
        self.frames: frozenset = frozenset()
        self.key = ""
        self.cache_lookup = False
        self.cache_store = False
        self.short_circuit = False

    def to_json(self) -> dict:
        out: dict = {"op": self.op}
        if self.detail:
            out["detail"] = self.detail
        if self.est_rows is not None:
            out["estRows"] = int(self.est_rows)
            out["exact"] = self.exact
        if self.est_cost_s is not None:
            out["estCostS"] = round(self.est_cost_s, 6)
        if self.placement != "auto":
            out["placement"] = self.placement
        if self.decisions:
            out["decisions"] = list(self.decisions)
        if self.actual_rows is not None:
            out["actualRows"] = int(self.actual_rows)
        if self.actual_s is not None:
            out["actualS"] = round(self.actual_s, 6)
        if self.children:
            out["children"] = [c.to_json() for c in self.children]
        return out


class PlanRecord:
    """The per-query plan ledger riding ``ctx.plan`` (the ctx.cost
    pattern): root plan nodes (one per planned call), the query
    fingerprint, a decision roll-up, and remote-leg plans stitched in
    from X-Pilosa-Plan headers."""

    __slots__ = ("fingerprint", "node", "roots", "decisions",
                 "children", "analyze", "sample", "_mu")

    def __init__(self, fingerprint: str, node: str = ""):
        self.fingerprint = fingerprint
        self.node = node
        self.roots: list[PlanNode] = []
        self.decisions: dict[str, int] = {}
        self.children: list[dict] = []
        self.analyze = False
        # Observability sampling gate: a shape's first sighting, every
        # whole planning pass and a 1-in-16 slice of shape hits carry
        # full per-node actuals and feed the plan store /
        # misestimation stream; the rest skip that bookkeeping (the
        # ≤2% overhead budget). ?profile=1 (analyze) always records.
        self.sample = True
        self._mu = threading.Lock()

    def note(self, outcome: str, n: int = 1) -> None:
        with self._mu:
            self.decisions[outcome] = self.decisions.get(outcome, 0) + n

    def add_remote_json(self, payload: str) -> None:
        """Stitch one remote leg's plan (its wire_json) under this
        record — the trace/cost header-stitching contract."""
        try:
            child = json.loads(payload)
        except (ValueError, TypeError):
            return
        if not isinstance(child, dict):
            return
        with self._mu:
            if len(self.children) < MAX_CHILDREN:
                self.children.append(child)

    def decision_summary(self) -> dict:
        with self._mu:
            return dict(self.decisions)

    def to_tree(self) -> dict:
        out: dict = {
            "fingerprint": self.fingerprint,
            "node": self.node,
            "calls": [r.to_json() for r in self.roots],
        }
        summary = self.decision_summary()
        if summary:
            out["decisions"] = summary
        with self._mu:
            if self.children:
                out["legs"] = list(self.children)
        return out

    def wire_json(self, max_bytes: int = 48 << 10) -> str:
        """The X-Pilosa-Plan payload, kept under the header budget the
        way trace spans are: drop stitched legs first, then per-node
        detail, halving until it fits."""
        tree = self.to_tree()
        payload = json.dumps(tree, separators=(",", ":"))
        while len(payload) > max_bytes:
            legs = tree.get("legs")
            if legs:
                del legs[len(legs) // 2:]
                if not legs:
                    tree.pop("legs", None)
            elif tree.get("calls"):
                del tree["calls"][len(tree["calls"]) // 2:]
            else:
                break
            payload = json.dumps(tree, separators=(",", ":"))
        return payload


def current_plan() -> Optional[PlanRecord]:
    """The calling thread's bound plan record, if its query has one —
    the executor's per-slice hooks run in pool threads that carry the
    context via sched_context.use."""
    from ..sched.context import current
    ctx = current()
    return getattr(ctx, "plan", None) if ctx is not None else None
