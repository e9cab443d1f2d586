"""Cost-based planning for read queries (ROADMAP item 1).

The executor consults the planner once per read query, before the
cluster-cache key is computed and before any fan-out. Planning operates
on a CLONE of the parsed call tree and produces (rewritten calls, a
``PlanRecord``); the executor then runs the rewritten tree and fills in
per-node actuals. Four decision kinds, each observable in the plan tree
and in ``pilosa_planner_decisions_total{outcome}``:

- **reorder** — ``Intersect``/``Union`` operands sorted smallest-first
  by estimated cardinality (the galloping-intersection ordering,
  arXiv:1402.6407 §4): the host fold then carries the smallest running
  operand, and the pairwise ``intersection_count`` shortcut sees its
  cheap operand first. ``Difference`` is never reordered (left operand
  is semantic).
- **short_circuit** — branches PROVEN empty are not executed: an
  exactly-empty operand empties an ``Intersect``, is dropped from a
  ``Union``/``Difference`` subtrahend list, and empties a whole call
  (``Count`` answers 0 with no fan-out). Proofs are exact only — every
  slice enumerated against local fragments (absent fragment = 0 bits);
  sampled or non-local estimates never short-circuit.
- **cse** — duplicate pure bitmap subtrees are hoisted through a
  generation-token-keyed per-slice subresult cache (SubresultCache):
  the second occurrence WITHIN a batch and repeats ACROSS queries fold
  once per slice and then hit. Keys carry the slice's (uid, generation)
  tokens (cluster.generations), so any write to any involved fragment
  invalidates by key mismatch — the PR-9 whole-query cache rule,
  generalized to interior nodes.
- **placement** — per-subtree host/device choice priced from the
  measured ``costmodel`` constants (sync floor, host fold rate, upload
  rate) instead of the global slice/leaf gates alone: a ``host`` hint
  makes the executor skip the device attempt for that subtree; the
  device gates still apply when the hint is ``auto``/``device``.

Estimates come from ``Fragment`` rank caches (``cache.get(rid)``) with
a ``row_count`` fallback, summed across slices — exact up to
``EXACT_SLICES`` slices, sampled+extrapolated past that. Estimation
never faults cold tier fragments in (cache-only, inexact) and never
reaches across the cluster (non-local slices extrapolate from the
local fraction, inexact).

A statement's SHAPE — the calls with their integer argument values
lifted out (``pql.shape``: ``rowID=3``, list members and condition
operands are parameters; names, strings, floats, ``true/false/null``
and operators are the shape) — is planned once
(``plan_query_cached``): the entry holds what no row id changes (the
fingerprint, a ``_Skel`` a call with its frame, view and purity, the
facts those rest on) and a request only binds its row ids into it
(``_bind``): one estimate a leaf from a row memo keyed by the view's
mutation token, the emptiness proofs, the order, placement, the CSE
ladder. ``plan_query`` is the same skeleton built from scratch and
the same bind, so the two cannot plan a request differently; what
differs is only what is kept. ``/debug/vars.planShapes`` counts
``hits`` / ``misses`` / ``full`` (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Optional

from ..cluster import generations
from ..obs import metrics as obs_metrics
from ..ops.packed import WORDS_PER_SLICE
from ..pql import shape
from ..pql.ast import Call
from ..utils.hotlock import HotLock
from .record import PlanNode, PlanRecord, fingerprint_calls

# Slice-count ceiling for exact (every slice enumerated) estimation;
# past it the planner samples ESTIMATE_SAMPLES slices and extrapolates.
EXACT_SLICES = 64
ESTIMATE_SAMPLES = 8

# The ops the planner rewrites / caches. Placement + estimation also
# understand Count/TopN wrappers (their bitmap child is planned).
_BITMAP_OPS = ("Intersect", "Union", "Difference")

# Leaf estimates kept in the row memo (keyed by the view's mutation
# token: a write gives a new key, the old entries age out).
_ESTIMATE_CACHE_ENTRIES = 4096
# Canonical subtrees remembered for cross-query CSE detection.
_SEEN_ENTRIES = 1024
# Shape entries (and the executor's route records) kept per (index,
# literal-free calls, slices) — what a read must not redo per request.
# Validity is fact-checked per hit (plan_query_cached), never assumed.
_PLAN_MEMO_ENTRIES = 256


def _observe_misestimate(node: PlanNode, rows: int) -> None:
    node.actual_rows = rows
    if node.est_rows is None:
        return
    ratio = (rows + 1) / (node.est_rows + 1)
    obs_metrics.PLANNER_MISESTIMATE.observe(ratio)


class SubresultCache:
    """Bounded per-slice interior-node result cache.

    Key: (index, canonical subtree, slice, generation tokens of every
    frame/view the subtree reads at that slice). A mutation bumps the
    fragment generation, the token tuple changes, and the stale entry
    simply stops matching (it ages out by LRU) — no explicit
    invalidation channel, the PR-9 contract.
    """

    def __init__(self, max_entries: int = 512,
                 max_bits: int = 32 << 20):
        self.max_entries = max_entries
        self.max_bits = max_bits
        self._mu = HotLock()
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._bits = 0

    def get(self, key: tuple):
        with self._mu:
            ent = self._entries.get(key)
            if ent is None:
                obs_metrics.PLANNER_SUBRESULT_EVENTS.labels(
                    "miss").inc()
                return None
            self._entries.move_to_end(key)
        obs_metrics.PLANNER_SUBRESULT_EVENTS.labels("hit").inc()
        return ent[0]

    def put(self, key: tuple, bm, bits: int) -> None:
        with self._mu:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bits -= old[1]
            self._entries[key] = (bm, bits)
            self._bits += bits
            while (len(self._entries) > self.max_entries
                   or self._bits > self.max_bits):
                if len(self._entries) <= 1 and \
                        self._bits <= self.max_bits:
                    break
                _, (_, b) = self._entries.popitem(last=False)
                self._bits -= b
                obs_metrics.PLANNER_SUBRESULT_EVENTS.labels(
                    "evict").inc()
        obs_metrics.PLANNER_SUBRESULT_EVENTS.labels("store").inc()

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
            self._bits = 0

    def stats(self) -> dict:
        with self._mu:
            return {"entries": len(self._entries), "bits": self._bits}


class _Skel:
    """One call of a planned shape: what planning it needs that no
    parameter changes. ``kind`` is ``_WRAP`` (a pass-through call: its
    bitmap children are planned), ``_OP`` (Intersect / Union /
    Difference), ``_LEAF`` (a Bitmap whose row can be estimated:
    ``dep`` indexes the entry's (frame name, frame, view) facts,
    ``row_slot`` the parameter that names the row, ``canon`` its
    canonical string cut around the row id, or None where more than
    the row varies) or ``_OPEN`` (a Bitmap left unestimated: no index,
    no frame, a filter, an inverse leaf)."""

    __slots__ = ("name", "spec", "kids", "kind", "covered", "dep",
                 "row_slot", "row_key", "frame_name", "frames", "canon")

    def __init__(self, name: str, spec, kind: int):
        self.name = name
        self.spec = spec
        self.kids: list = []
        self.kind = kind
        self.covered = False
        self.dep = -1
        self.row_slot = -1
        self.row_key = ""
        self.frame_name = ""
        self.frames: frozenset = frozenset()
        self.canon = None


_WRAP, _OP, _LEAF, _OPEN = range(4)


class _Binding:
    """One request's half of a plan: its parameter values, the view
    tokens read before any estimate, its slices, and the decisions
    made while binding."""

    __slots__ = ("vals", "deps", "gens", "slices", "skey", "all_local",
                 "decisions")

    def __init__(self, vals, deps, gens, slices, skey, all_local):
        self.vals = vals
        self.deps = deps
        self.gens = gens
        self.slices = slices
        self.skey = skey
        self.all_local = all_local
        self.decisions: dict[str, int] = {}


class _RowMark:
    """Stands in for the row id while a leaf's canonical string is cut
    around it."""

    def __str__(self):
        return "\0"


class Planner:
    """One per executor. Thread-safe: planning itself runs on the
    query thread; the memo, the seen LRU and inserts into the row memo
    take the planner lock (a ``HotLock``: every request thread enters
    it), reads of warm estimates do not."""

    def __init__(self, holder, margin: float = 0.5,
                 subresult_entries: int = 512,
                 subresult_bits: int = 32 << 20):
        self.holder = holder
        self.margin = margin
        self.subresults = SubresultCache(subresult_entries,
                                         subresult_bits)
        # Measured cost constants (parallel.costmodel Calibration);
        # the executor installs its calibrated model's constants once
        # a mesh exists, warmup primes the persisted ones earlier.
        self.calibration = None
        self._mu = HotLock()
        self._seen: OrderedDict[str, int] = OrderedDict()
        # The row memo: (leaf canon, view uid, view generation, row,
        # slices key, all_local) -> the finished leaf PlanNode. The
        # view token stands for every fragment's epoch and for the
        # absent ones, so a write to the view gives a new key and the
        # old entries age out (oldest insertion first); nothing is ever
        # invalidated in place.
        self._rows: dict[tuple, PlanNode] = {}
        # Shape entries: (index, shape key, slices key, all_local) ->
        # {idx, skels, deps, fingerprint, hits} (plan_query_cached).
        # The executor's route records live here too, one an index
        # under ("route", index) (memo_get / memo_put / memo_drop):
        # what a read resolves once — the plan of a statement shape,
        # the route of a slice set — is one bounded LRU under one
        # validity rule, identity and view tokens compared.
        self._plans: OrderedDict[tuple, dict] = OrderedDict()
        # /debug/vars.planShapes (with pql.parser's own `full`): reads
        # planned from a shape entry, entries built, and whole planning
        # passes (plan_query: ?profile=1, ?plan=1, a shape with an
        # unhashable value). Plain bumps, like decision_totals.
        self.shapes = {"hits": 0, "misses": 0, "full": 0}
        # Decision roll-up for the blackbox / debug snapshot.
        self.decision_totals: dict[str, int] = {}
        self._decision_counters: dict[str, object] = {}

    # -- public entry points -------------------------------------------------

    def plan_query(self, index: str, calls: list[Call], slices,
                   all_local: bool = True, node: str = ""
                   ) -> tuple[list[Call], PlanRecord]:
        """Plan a batch of read calls in full: the shape's skeleton is
        built from scratch and bound to the calls' own values, nothing
        is looked up or kept. Returns (rewritten clones, the populated
        record). Caller is responsible for gating (write queries and
        disabled planning never reach here)."""
        t0 = time.perf_counter()
        all_local = bool(all_local)
        self.shapes["full"] += 1
        vals: list = []
        for call in calls:
            shape.ints_of(call, vals)
        ent = self._shape_entry(index, calls)
        out = self._bind_query(ent, vals, self._shape_gens(index, ent),
                               slices, None, all_local, node)
        obs_metrics.PLANNER_PLAN_SECONDS.observe(
            time.perf_counter() - t0)
        return out

    def plan_query_cached(self, index: str, calls: list[Call], slices,
                          all_local: bool = True, node: str = "",
                          slices_key: Optional[tuple] = None
                          ) -> tuple[list[Call], PlanRecord]:
        """``plan_query`` with the statement's shape planned once.

        The entry is keyed by (index, the calls with their integer
        argument values lifted out — ``pql.shape.lift`` —, the slice
        set's name, ``all_local``), NOT by the row ids: it holds what
        no parameter changes — the fingerprint, one ``_Skel`` a call
        (frame, view, purity, which slot names the row, the canonical
        string cut around it) and the facts those rest on. A request
        then does only what depends on its rows (``_bind``): one
        estimate a leaf from the row memo, the short-circuit proofs,
        the smallest-first order, placement from k numbers and the CSE
        ladder — the plan ``plan_query`` makes for the same request,
        because ``plan_query`` is the same skeleton and the same bind.
        ``slices_key`` is the caller's O(1) name for the slice set
        (the executor's route record has one); absent, the slices
        themselves key the entry.

        Safety: an entry is used only while the index, each frame and
        each standard view it resolved are still those OBJECTS (a drop
        and recreate, a view appearing, voids it), and every estimate
        is keyed by the view's mutation token read BEFORE binding (any
        write to, and any fragment appearing in or leaving, the view
        moves it: a fragment appearing breaks an emptiness proof as
        surely as a write), so a proof can never outlive the emptiness
        it proved. Leaf plan NODES are shared between requests that
        name the same row; the per-query PlanRecord (actuals, stitched
        legs) and every interior node are always fresh. A hit samples
        its observability 1 time in 16; a first sighting always. A
        warm shape holds the planner's lock once, for the CSE ladder
        of a Count's bitmap child (and once more when it samples)."""
        all_local = bool(all_local)
        if slices_key is None:
            slices_key = tuple(map(int, slices))
        vals: list = []
        try:
            key = (index, tuple([shape.lift(c, vals) for c in calls]),
                   slices_key, all_local)
            # Read without the lock (one dict look-up); a hit renews
            # its place in the LRU when it samples, 1 time in 16.
            ent = self._plans.get(key)
        except TypeError:
            # Unhashable literal somewhere in the tree: no shape.
            return self.plan_query(index, calls, slices, all_local,
                                   node=node)
        gens = None if ent is None else self._shape_gens(index, ent)
        built = gens is None
        if built:
            t0 = time.perf_counter()
            ent = self._shape_entry(index, calls)
            gens = self._shape_gens(index, ent)
            self.memo_put(key, ent)
            self.shapes["misses"] += 1
            hits = 0
        else:
            self.shapes["hits"] += 1
            ent["hits"] = hits = ent["hits"] + 1
            if hits % 16 == 0:
                self.memo_get(key)
        planned, rec = self._bind_query(ent, vals, gens, slices,
                                        slices_key, all_local, node)
        rec.sample = hits % 16 == 0
        if built:
            obs_metrics.PLANNER_PLAN_SECONDS.observe(
                time.perf_counter() - t0)
        return planned, rec

    # -- the memo's three verbs (plans here, routes from the executor) -------

    def memo_get(self, key: tuple) -> Optional[dict]:
        with self._mu:
            ent = self._plans.get(key)
            if ent is not None:
                self._plans.move_to_end(key)
        return ent

    def memo_put(self, key: tuple, ent: dict) -> None:
        with self._mu:
            self._plans[key] = ent
            while len(self._plans) > _PLAN_MEMO_ENTRIES:
                self._plans.popitem(last=False)

    def memo_drop(self, key: tuple, ent: dict) -> None:
        """Forget ``ent`` (and only it: a racing reader may already
        have stored its successor under the key)."""
        with self._mu:
            if self._plans.get(key) is ent:
                del self._plans[key]

    def explain(self, index: str, calls: list[Call], slices,
                all_local: bool = True) -> dict:
        """EXPLAIN-only (?plan=1): plan without executing."""
        _, record = self.plan_query(index, calls, slices,
                                    all_local=all_local)
        return record.to_tree()

    def snapshot(self) -> dict:
        """Planner state for the blackbox / debug surfaces."""
        with self._mu:
            totals = dict(self.decision_totals)
            seen = len(self._seen)
        out = {"decisions": totals, "seenSubtrees": seen,
               "shapes": dict(self.shapes),
               "subresultCache": self.subresults.stats()}
        if self.calibration is not None:
            out["calibration"] = self.calibration.to_dict()
        return out

    # -- decision bookkeeping ------------------------------------------------

    def _bump(self, outcome: str) -> None:
        counter = self._decision_counters.get(outcome)
        if counter is None:
            counter = self._decision_counters[outcome] = \
                obs_metrics.PLANNER_DECISIONS.labels(outcome)
        counter.inc()
        # A plain bump, no lock: a roll-up for the debug surfaces,
        # where a rare lost count is accepted.
        self.decision_totals[outcome] = \
            self.decision_totals.get(outcome, 0) + 1

    def _decide(self, b: _Binding, node: PlanNode,
                outcome: str) -> None:
        node.decisions.append(outcome)
        b.decisions[outcome] = b.decisions.get(outcome, 0) + 1
        self._bump(outcome)

    # -- the shape's half: built once ----------------------------------------

    def _shape_entry(self, index: str, calls: list[Call]) -> dict:
        """Everything planning ``calls`` needs that does not depend on
        their integer values."""
        idx = self.holder.index(index)
        deps: list[tuple] = []
        skels = []
        n = 0
        for call in calls:
            spec, n = shape.spec_of(call, n)
            skels.append(self._skeleton(idx, spec, deps, covered=True))
        self._bump("planned")
        return {"idx": idx, "skels": skels, "deps": deps,
                "fingerprint": fingerprint_calls(calls), "hits": 0}

    def _shape_gens(self, index: str, ent: dict) -> Optional[list]:
        """The view tokens a bind of ``ent`` keys its estimates by, one
        a dependency, or None when a fact the skeleton rests on no
        longer holds. Identity checks (``is``) catch drop-and-recreate
        and a view appearing; the token, read here BEFORE any estimate
        walks a fragment, stands for every fragment of the view."""
        try:
            idx = self.holder.index(index)
            if idx is not ent["idx"]:
                return None
            gens = []
            for name, frame, view in ent["deps"]:
                if idx.frames.get(name) is not frame:
                    return None
                now = None if frame is None \
                    else frame.views.get("standard")
                if now is not view:
                    return None
                gens.append(0 if view is None else view.generation)
        except Exception:  # noqa: BLE001 - any doubt means replan
            return None
        return gens

    def _skeleton(self, idx, spec, deps: list,
                  covered: bool = False) -> _Skel:
        """``spec``'s subtree as ``_Skel``s. ``covered`` marks
        subtrees the executor's whole-result caches already key (the
        root of a Union/Intersect/Difference call) — those skip
        subresult-cache marking to avoid double storage."""
        name = spec[0]
        if name == "Bitmap":
            return self._leaf_skeleton(idx, spec, deps)
        sk = _Skel(name, spec, _OP if name in _BITMAP_OPS else _WRAP)
        sk.covered = covered and sk.kind == _OP
        # Wrappers (Count/TopN/...) plan their bitmap children; the
        # call itself is a pass-through node.
        sk.kids = [self._skeleton(idx, kid, deps) for kid in spec[3]]
        return sk

    def _leaf_skeleton(self, idx, spec, deps: list) -> _Skel:
        sk = _Skel("Bitmap", spec, _OPEN)
        args = spec[1]
        frame_name = args.get("frame")
        if not isinstance(frame_name, str) or not frame_name:
            frame_name = "general"  # executor.DEFAULT_FRAME
        if idx is None or args.get("filter") is not None:
            return sk
        frame = idx.frames.get(frame_name)
        dep = len(deps)
        for i, d in enumerate(deps):
            if d[0] == frame_name:
                dep = i
                break
        else:
            # A frame or a view APPEARING breaks what was resolved
            # here ("no view" = exact 0), so both absences are facts.
            deps.append((frame_name, frame,
                         None if frame is None
                         else frame.views.get("standard")))
        if frame is None:
            # No frame: estimation stays open (the executor raises its
            # own FrameNotFound; planning must not pre-empt errors).
            return sk
        slot = None
        for key, fill in spec[2]:
            if key == frame.row_label and type(fill) is int:
                slot = fill
        if slot is None:
            # Inverse leaves (columnID) read the inverse view over a
            # different slice domain; leave them unestimated.
            return sk
        sk.kind = _LEAF
        sk.dep = dep
        sk.row_slot = slot
        sk.row_key = frame.row_label
        sk.frame_name = frame_name
        sk.frames = frozenset((f"{frame_name}/standard",))
        if len(spec[2]) == 1 and not spec[3]:
            marked = dict(args)
            marked[frame.row_label] = _RowMark()
            text = str(Call("Bitmap", marked))
            if text.count("\0") == 1:
                sk.canon = tuple(text.split("\0"))
        return sk

    # -- the request's half: bound every time --------------------------------

    def _bind_query(self, ent: dict, vals: list, gens: Optional[list],
                    slices, slices_key, all_local: bool, node: str
                    ) -> tuple[list[Call], PlanRecord]:
        if gens is None:
            # A fact moved between the build and this read of it: bind
            # what was resolved, keep no estimate.
            gens = [None] * len(ent["deps"])
        b = _Binding(vals, ent["deps"], gens, slices, slices_key,
                     all_local)
        planned: list[Call] = []
        rec = PlanRecord(ent["fingerprint"], node=node)
        for sk in ent["skels"]:
            call, pnode = self._bind(sk, b)
            planned.append(call)
            rec.roots.append(pnode)
        b.decisions["planned"] = 1
        rec.decisions = b.decisions
        return planned, rec

    def _bind(self, sk: _Skel, b: _Binding) -> tuple[Call, PlanNode]:
        """The planned call and plan node of ``sk`` for one request's
        values. Every call is a fresh object carrying its node as
        ``_plan_node``."""
        kind = sk.kind
        if kind == _LEAF:
            return self._bind_leaf(sk, b)
        if kind == _OP:
            return self._bind_bitmap_op(sk, b)
        if kind == _OPEN:
            call = shape.bind_call(sk.spec, b.vals)
            node = call._plan_node = PlanNode("Bitmap")
            return call, node
        node = PlanNode(sk.name)
        children = []
        for kid in sk.kids:
            child, child_node = self._bind(kid, b)
            children.append(child)
            node.children.append(child_node)
        call = Call(sk.name, shape.bind_args(sk.spec, b.vals), children)
        call._plan_node = node
        if node.children:
            first = node.children[0]
            node.est_rows = first.est_rows
            node.exact = first.exact
            if sk.name == "Count" and first.short_circuit:
                # Count of a proven-empty subtree answers 0 without
                # fan-out.
                node.short_circuit = True
                self._decide(b, node, "short_circuit")
        return call, node

    def _bind_bitmap_op(self, sk: _Skel,
                        b: _Binding) -> tuple[Call, PlanNode]:
        name = sk.name
        node = PlanNode(name)
        children = []
        child_nodes = []
        for kid in sk.kids:
            child, child_node = (self._bind_leaf(kid, b)
                                 if kid.kind == _LEAF
                                 else self._bind(kid, b))
            children.append(child)
            child_nodes.append(child_node)
        call = Call(name, shape.bind_args(sk.spec, b.vals), children)
        call._plan_node = node

        # Short-circuit rewrites (exact proofs only).
        empty = [c.exact and c.est_rows == 0 for c in child_nodes]
        if True in empty or (name == "Union" and not empty):
            if name == "Intersect" or (name == "Difference" and empty[0]) \
                    or False not in empty:
                node.short_circuit = True
                node.est_rows, node.exact = 0, True
                node.children = child_nodes
                self._decide(b, node, "short_circuit")
                return call, node
            # A Union's, or a Difference's subtrahends': dropped.
            keep = [i for i, e in enumerate(empty) if not e]
            call.children = children = [children[i] for i in keep]
            child_nodes = [child_nodes[i] for i in keep]
            self._decide(b, node, "short_circuit")

        # Reorder commutative operands smallest-first.
        n = len(child_nodes)
        if name != "Difference" and n > 1:
            ests = [float("inf") if c.est_rows is None else c.est_rows
                    for c in child_nodes]
            order = sorted(range(n), key=ests.__getitem__)
            if order != list(range(n)):
                call.children = [children[i] for i in order]
                child_nodes = [child_nodes[i] for i in order]
                self._decide(b, node, "reordered")

        node.children = child_nodes

        # Combined estimate.
        ests = [c.est_rows for c in child_nodes]
        known = [e for e in ests if e is not None]
        all_exact = bool(child_nodes) and all(c.exact
                                              for c in child_nodes)
        if name == "Intersect" and known:
            node.est_rows = min(known)
            node.exact = all_exact and node.est_rows == 0
        elif name == "Union" and len(known) == len(ests):
            node.est_rows = sum(known)
            node.exact = all_exact and node.est_rows == 0
        elif name == "Difference" and ests and ests[0] is not None:
            node.est_rows = ests[0]
            node.exact = child_nodes[0].exact and node.est_rows == 0

        # Purity: every descendant contributed a known frame/view set.
        frames: set = set()
        pure = bool(child_nodes)
        for c in child_nodes:
            if not c.frames:
                pure = False
                break
            frames.update(c.frames)
        if pure:
            node.frames = frozenset(frames)
            if not sk.covered:
                # The canonical subtree string keys the CSE ladder and
                # the subresult cache, and nothing else reads it: a
                # covered root never needs one. Every child of a pure
                # node carries its own.
                node.key = str(call) if call.args else "%s(%s)" % (
                    name, ", ".join([c.key for c in child_nodes]))
                self._mark_cse(node)
            self._placement(node, b.slices)
        return call, node

    def _bind_leaf(self, sk: _Skel,
                   b: _Binding) -> tuple[Call, PlanNode]:
        raw = b.vals[sk.row_slot]
        canon = sk.canon
        if canon is None:
            call = shape.bind_call(sk.spec, b.vals)
        else:  # the row is the leaf's one parameter
            args = dict(sk.spec[1])
            args[sk.row_key] = raw
            call = Call("Bitmap", args)
        view = b.deps[sk.dep][2]
        gen = b.gens[sk.dep]
        key = node = None
        if (canon is not None and view is not None and gen is not None
                and b.skey is not None):
            # Read without the lock (one dict look-up is atomic under
            # the interpreter's own lock): estimates once took the
            # planner's lock per leaf and slice, and eight connection
            # threads doing so formed a convoy on it (PERF.md, PR 27).
            key = (canon, view.uid, gen, raw, b.skey, b.all_local)
            node = self._rows.get(key)
        if node is None:
            row_id = raw & 0xFFFFFFFFFFFFFFFF  # Call.uint_arg's wrap
            node = PlanNode("Bitmap", f"{sk.frame_name}/{row_id}")
            node.frames = sk.frames
            node.key = (str(call) if canon is None
                        else f"{canon[0]}{raw}{canon[1]}")
            node.est_rows, node.exact = self._estimate_row(
                view, row_id, b.slices, b.all_local)
            if key is not None:
                with self._mu:
                    rows = self._rows
                    rows[key] = node
                    while len(rows) > _ESTIMATE_CACHE_ENTRIES:
                        del rows[next(iter(rows))]
        call._plan_node = node
        return call, node

    def _estimate_row(self, view, row_id: int, slices,
                      all_local: bool) -> tuple[int, bool]:
        """Estimated bits for one (frame, standard view, row) over
        ``slices``. Exact only when every slice was enumerated against
        a local fragment (or a provably absent one)."""
        if view is None:
            return (0, all_local)
        if len(slices) > EXACT_SLICES:
            step = max(1, len(slices) // ESTIMATE_SAMPLES)
            sample = slices[::step][:ESTIMATE_SAMPLES]
            total, _ = self._sum_slices(view, row_id, sample, False)
            scaled = int(total * len(slices) / max(len(sample), 1))
            return (scaled, False)
        return self._sum_slices(view, row_id, slices, all_local)

    def _sum_slices(self, view, row_id: int, slices,
                    all_local: bool) -> tuple[int, bool]:
        total = 0
        exact = all_local
        for s in slices:
            frag = view.fragments.get(int(s))
            if frag is None:
                # Locally absent fragment = 0 bits — exact only when
                # this node owns every slice of the query. (A fragment
                # appearing voids the proof: it moves the view's token,
                # which keys the row memo.)
                continue
            n = 0
            try:
                if frag.cache is not None:
                    n = int(frag.cache.get(row_id))
                if n <= 0:
                    if (frag.tier is not None
                            and frag.tier_state != "hot"):
                        # Never fault a cold fragment in to plan a
                        # query; the estimate stays open.
                        exact = False
                        continue
                    n = int(frag.row_count(row_id))
            except Exception:
                exact = False
                continue
            total += n
        return (total, exact)

    # -- CSE + placement -----------------------------------------------------

    def _mark_cse(self, node: PlanNode) -> None:
        """Interior pure subtrees consult the subresult cache; a
        subtree STORES once its canonical form has been seen twice
        (within one batch or across queries) — first sightings only
        register, so one-off shapes never occupy cache budget."""
        with self._mu:
            count = self._seen.get(node.key, 0) + 1
            self._seen[node.key] = count
            self._seen.move_to_end(node.key)
            while len(self._seen) > _SEEN_ENTRIES:
                self._seen.popitem(last=False)
        node.cache_lookup = True
        node.cache_store = count >= 2
        if count >= 2 and "cse" not in node.decisions:
            node.decisions.append("cse")

    def _placement(self, node: PlanNode, slices) -> None:
        """Price host vs device for this subtree from the measured
        constants. Only a clear host win becomes a hint (the costmodel
        margin rule); everything else stays ``auto`` and the usual
        device gates decide."""
        cal = self.calibration
        if cal is None or not slices:
            return
        ests: list = []
        _leaf_estimates(node, ests)
        leaves = len(ests)
        n_slices = len(slices)
        slab = n_slices * WORDS_PER_SLICE * 4
        device_bytes = leaves * slab
        host_bytes = 0
        for leaf_est in ests:
            if leaf_est is None:
                host_bytes += slab
            else:
                # Roaring walk cost: ~2 bytes/bit in array containers,
                # capped at the dense slab.
                host_bytes += min(leaf_est * 2, slab)
        # every leaf row of every slice is one visit of the host walk
        host = cal.host_cost(host_bytes, leaves * n_slices)
        device = cal.device_cost(device_bytes)
        node.est_cost_s = min(host, device)
        if host < self.margin * device:
            node.placement = "host"
            node.decisions.append("placement:host")
            self._bump("placement")
        else:
            node.placement = "device"

    # -- subresult cache wiring ----------------------------------------------

    def subresult_key(self, index: str, node: PlanNode,
                      slice: int) -> Optional[tuple]:
        """The generation-token cache key for one planned subtree at
        one slice, or None when any involved fragment is untracked."""
        toks = generations.slice_tokens(self.holder, index, slice)
        out = []
        for fv in sorted(node.frames):
            out.append((fv, toks.get(fv, (0, 0))))
        return (index, node.key, int(slice), tuple(out))


def _leaf_estimates(node: PlanNode, out: list) -> None:
    """Append the estimate of every leaf under ``node`` to ``out``."""
    if not node.children:
        out.append(node.est_rows)
        return
    for c in node.children:
        _leaf_estimates(c, out)
