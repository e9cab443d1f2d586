"""Row caches: TopN rank cache, LRU cache, and the Pair merge algebra.

Reference: cache.go. The rank cache keeps per-row bit counts above a dynamic
threshold so TopN can scan candidates in rank order without touching every
row; it is also the working-set signal for device residency (the top-ranked
rows are exactly the rows worth pinning in HBM — see
pilosa_tpu.parallel.residency).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Iterable

import numpy as np

# ThresholdFactor of maxEntries is how far the unsorted entry map may grow
# past maxEntries before a trim (reference cache.go:30-33, factor 1.1).
THRESHOLD_FACTOR = 1.1

# Default cache size per fragment (reference frame.go:39).
DEFAULT_CACHE_SIZE = 50000

CACHE_TYPE_LRU = "lru"
CACHE_TYPE_RANKED = "ranked"
DEFAULT_CACHE_TYPE = CACHE_TYPE_LRU


class Pair:
    """(id, count) result pair (reference cache.go:278-316)."""

    __slots__ = ("id", "count")

    def __init__(self, id: int, count: int):
        self.id = id
        self.count = count

    def __repr__(self):
        return f"Pair(id={self.id}, count={self.count})"

    def __eq__(self, other):
        return (isinstance(other, Pair) and self.id == other.id
                and self.count == other.count)


def pairs_add(a: list[Pair], b: list[Pair]) -> list[Pair]:
    """Merge two pair lists, summing counts by id (cache.go:343-361)."""
    m: dict[int, int] = {}
    for p in a:
        m[p.id] = m.get(p.id, 0) + p.count
    for p in b:
        m[p.id] = m.get(p.id, 0) + p.count
    return [Pair(k, v) for k, v in m.items()]


def pairs_sort(pairs: Iterable[Pair]) -> list[Pair]:
    """Descending by count, ascending id for ties (BitmapPairs sort order)."""
    return sorted(pairs, key=lambda p: (-p.count, p.id))


def _rank_arrays(keys, values, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(ids, counts) in rank order — count desc, id asc on ties — via
    one numpy lexsort instead of a 50 K-object Python sort. This is the
    TopN candidate phase's hot loop at BASELINE config-3 scale."""
    ids = np.fromiter(keys, dtype=np.uint64, count=n)
    counts = np.fromiter(values, dtype=np.int64, count=n)
    order = np.lexsort((ids, -counts))
    return ids[order], counts[order]


def _pairs_from_arrays(ids: np.ndarray, counts: np.ndarray) -> list[Pair]:
    return [Pair(i, c) for i, c in zip(ids.tolist(), counts.tolist())]


class RankCache:
    """Keeps ids with counts above a dynamic threshold, ranked.

    Semantics follow reference cache.go:126-275: adds below thresholdValue
    are ignored; rankings are recomputed at most every 10 s (except via
    recalculate()); when the entry map outgrows maxEntries*1.1 it is trimmed
    to the rows just ranked: those above the threshold and those tied at
    it that the ranking holds. cache.go keeps those above it only, so its
    next recalculation ranks fewer rows than the one that trimmed.
    """

    def __init__(self, max_entries: int = DEFAULT_CACHE_SIZE):
        self.max_entries = max_entries
        self.threshold_buffer = int(THRESHOLD_FACTOR * max_entries)
        self.threshold_value = 0
        self.entries: dict[int, int] = {}
        self.rankings: list[Pair] | None = []
        self._rank_ids = np.empty(0, dtype=np.uint64)
        self._rank_counts = np.empty(0, dtype=np.int64)
        self._update_time = 0.0

    def add(self, id: int, n: int) -> None:
        if n < self.threshold_value:
            return
        self.entries[id] = n
        self.invalidate()

    def bulk_add(self, id: int, n: int) -> None:
        """Unsorted add; call recalculate() when done."""
        if n < self.threshold_value:
            return
        self.entries[id] = n

    def get(self, id: int) -> int:
        return self.entries.get(id, 0)

    def __len__(self):
        return len(self.entries)

    def ids(self) -> list[int]:
        return sorted(self.entries)

    def invalidate(self) -> None:
        # Rate-limited recalculation (cache.go:219-226).
        if time.monotonic() - self._update_time < 10:
            return
        self.recalculate()

    def recalculate(self) -> None:
        ids, counts = _rank_arrays(self.entries.keys(),
                                   self.entries.values(),
                                   len(self.entries))
        if len(ids) > self.max_entries:
            self.threshold_value = int(counts[self.max_entries])
            ids = ids[:self.max_entries]
            counts = counts[:self.max_entries]
        else:
            self.threshold_value = 1
        self._rank_ids, self._rank_counts = ids, counts
        self.rankings = None  # Pair list built lazily by top()
        self._update_time = time.monotonic()
        if len(self.entries) > self.threshold_buffer:
            # The ranked rows tied at the threshold stay (cache.go drops
            # them, and its next recalculation over the same counts — at
            # most 10 s on, from any read — ranks fewer rows than this
            # one); the unranked ties go. So every recalculation of
            # unchanged counts gives this ranking again, and the map
            # keeps at most maxEntries rows however many tie.
            self.entries = dict(zip(ids.tolist(), counts.tolist()))

    def top_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(ids, counts) in rank order, no per-entry objects."""
        return self._rank_ids, self._rank_counts

    def top(self) -> list[Pair]:
        if self.rankings is None:
            self.rankings = _pairs_from_arrays(self._rank_ids,
                                               self._rank_counts)
        return self.rankings


class LRUCache:
    """LRU id→count cache (reference cache.go:55-123 over groupcache/lru)."""

    def __init__(self, max_entries: int = DEFAULT_CACHE_SIZE):
        self.max_entries = max_entries
        self._od: OrderedDict[int, int] = OrderedDict()
        self._ranked = None  # cached (ids, counts) arrays, rank order

    def add(self, id: int, n: int) -> None:
        self._od[id] = n
        self._od.move_to_end(id)
        while len(self._od) > self.max_entries:
            self._od.popitem(last=False)
        self._ranked = None

    bulk_add = add

    def get(self, id: int) -> int:
        n = self._od.get(id, 0)
        if id in self._od:
            self._od.move_to_end(id)  # recency changes, counts don't
        return n

    def __len__(self):
        return len(self._od)

    def ids(self) -> list[int]:
        return sorted(self._od)

    def invalidate(self) -> None:
        pass

    def recalculate(self) -> None:
        pass

    def top_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(ids, counts) in rank order; cached until the next mutation
        — the old per-call Python sort of 50 K entries dominated the
        TopN candidate phase."""
        if self._ranked is None:
            self._ranked = _rank_arrays(self._od.keys(),
                                        self._od.values(), len(self._od))
        return self._ranked

    def top(self) -> list[Pair]:
        ids, counts = self.top_arrays()
        return _pairs_from_arrays(ids, counts)


class SimpleCache:
    """Unbounded row-bitmap cache for write-heavy loads
    (reference cache.go:437-462)."""

    def __init__(self):
        self._m: dict[int, object] = {}

    def fetch(self, id: int):
        return self._m.get(id)

    def add(self, id: int, bm) -> None:
        self._m[id] = bm

    def invalidate(self, id: int) -> None:
        self._m.pop(id, None)

    def clear(self) -> None:
        self._m.clear()


def new_cache(cache_type: str, size: int):
    if cache_type == CACHE_TYPE_RANKED:
        return RankCache(size)
    if cache_type == CACHE_TYPE_LRU:
        return LRUCache(size)
    raise ValueError(f"unknown cache type: {cache_type!r}")


def top_n_heap_merge(pairs_lists: list[list[Pair]], n: int) -> list[Pair]:
    """Merge per-slice TopN pair lists: sum counts by id, keep top n
    (reference executor.go:319-334 reduce step)."""
    merged: list[Pair] = []
    for pl in pairs_lists:
        merged = pairs_add(merged, pl)
    merged = pairs_sort(merged)
    return merged[:n] if n else merged
