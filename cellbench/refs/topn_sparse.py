"""A ranked frame too wide for dense rows, and TopN over it, as plain numpy.

BASELINE config 3 has 100,000 rows over 10,000,000 columns: held as
``lib/data.py`` holds a row (2^20 bits a slice), it would take 122 GiB.
Here every slice is the sorted vector of its set positions
(``row << 20 | column``), made from the seed alone, with a column-major
index beside it for the source counts. The TopN is written from the
semantics, not from the program: nothing here imports ``pilosa_tpu``.

The data: row r is set in each column independently with probability
``min(1, d0 / (r + 1) ** zipf_s)`` over the configuration's ``n_cols``
columns (the last slice holds what is left of them); see
``_slice_bits`` for how it is drawn.

The TopN, ``TopN(Bitmap(frame, rowID=a), frame, n)`` — the two phases
of executor.go:271-396 on one node:

1. Each slice's ranked cache holds its rows with at least one bit,
   ordered by count descending then id ascending, the first
   ``cache_size`` of them (cache.go's rank cache after the import's
   recalculation).
2. Each slice walks its ranking (fragment.go Top, with a source): the
   first n rows whose intersection with the source is at least 1 are
   taken; t is the least intersection among them; the walk goes on
   until the first row whose cached count is below t, taking every row
   whose intersection is at least t. Fewer than n such rows: all of
   them.
3. The candidates are the union over the slices; each one's count is
   its intersection with the source summed over every slice; the answer
   is the candidates by count descending then id ascending, the first
   n, as ``[{"id", "count"}]``.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLICE_WIDTH = 1 << 20   # columns per slice: the index format's constant
MAKE_THREADS = 8
_COL_MASK = np.uint64(SLICE_WIDTH - 1)


def _slice_bits(rng, width: int, p: np.ndarray) -> np.ndarray:
    """Sorted positions ``row << 20 | column`` of one slice. A row of
    density at least 1/64 tests every column; a sparser one takes a
    binomial count of columns and then that many distinct columns
    uniformly (draws that repeat a column are drawn again): the same
    law either way, the second in bulk."""
    dense = int(np.count_nonzero(p >= 1.0 / 64))
    parts = [(np.uint64(r) << np.uint64(20))
             | np.flatnonzero(rng.random(width) < p[r]).astype(np.uint64)
             for r in range(dense)]
    want = np.zeros(len(p), dtype=np.int64)
    want[dense:] = rng.binomial(width, p[dense:])
    rows_all = np.arange(len(p), dtype=np.uint64)
    have = np.empty(0, dtype=np.uint64)
    need = want
    while need.any():
        rows = np.repeat(rows_all, need)
        cols = rng.integers(0, width, len(rows), dtype=np.uint64)
        have = np.unique(np.concatenate((have, (rows << np.uint64(20))
                                         | cols)))
        got = np.bincount((have >> np.uint64(20)).astype(np.int64),
                          minlength=len(p))
        need = want - got
    return np.concatenate(parts + [have])


class Reference:
    """``pos[s]``: the sorted set positions of slice s; ``row_of[s]`` /
    ``col_ptr[s]``: the same bits by column (the rows of column c are
    ``row_of[s][col_ptr[s][c]:col_ptr[s][c + 1]]``)."""

    def __init__(self, seed: int, config: dict):
        self.n_slices = int(config["n_slices"])
        self.n_rows = int(config["n_rows"])
        self.n_cols = int(config["n_cols"])
        self.cache_size = int(config["cache_size"])
        p = np.minimum(1.0, float(config["d0"])
                       / np.arange(1, self.n_rows + 1, dtype=np.float64)
                       ** float(config["zipf_s"]))
        self._mu = threading.Lock()
        self._rankings: dict[int, tuple] = {}

        def make(s: int):
            width = min(SLICE_WIDTH, self.n_cols - s * SLICE_WIDTH)
            pos = _slice_bits(np.random.default_rng([seed, 3, s]), width,
                              p)
            cols = (pos & _COL_MASK).astype(np.int64)
            order = np.argsort(cols, kind="stable")
            ptr = np.zeros(SLICE_WIDTH + 1, dtype=np.int64)
            np.cumsum(np.bincount(cols, minlength=SLICE_WIDTH),
                      out=ptr[1:])
            return pos, (pos[order] >> np.uint64(20)).astype(np.int64), ptr

        with ThreadPoolExecutor(MAKE_THREADS) as pool:
            made = list(pool.map(make, range(self.n_slices)))
        self.pos = [m[0] for m in made]
        self.row_of = [m[1] for m in made]
        self.col_ptr = [m[2] for m in made]

    def set_bits(self) -> int:
        return sum(len(p) for p in self.pos)

    def slice_positions(self, s0: int, s1: int):
        """(row ids, column ids) of every set bit in slices [s0, s1)."""
        rows, cols = [], []
        for s in range(s0, s1):
            pos = self.pos[s]
            rows.append(pos >> np.uint64(20))
            cols.append((pos & _COL_MASK) + np.uint64(s * SLICE_WIDTH))
        return np.concatenate(rows), np.concatenate(cols)

    def row_cols(self, s: int, row: int) -> np.ndarray:
        """Slice-local columns of one row in slice s."""
        pos = self.pos[s]
        lo, hi = np.searchsorted(pos, [np.uint64(row) << np.uint64(20),
                                       np.uint64(row + 1) << np.uint64(20)])
        return (pos[lo:hi] & _COL_MASK).astype(np.int64)

    def src_counts(self, s: int, row: int) -> np.ndarray:
        """Every row's intersection with ``row`` in slice s."""
        cols = self.row_cols(s, row)
        ptr = self.col_ptr[s]
        start, end = ptr[cols], ptr[cols + 1]
        lens = end - start
        idx = (np.repeat(start - np.cumsum(lens) + lens, lens)
               + np.arange(int(lens.sum())))
        return np.bincount(self.row_of[s][idx], minlength=self.n_rows)

    def ranking(self, s: int) -> tuple[np.ndarray, np.ndarray]:
        """Slice s's ranked cache: (ids, counts), count descending then
        id ascending, at most ``cache_size``."""
        with self._mu:
            hit = self._rankings.get(s)
        if hit is not None:
            return hit
        counts = np.bincount((self.pos[s] >> np.uint64(20))
                             .astype(np.int64), minlength=self.n_rows)
        ids = np.flatnonzero(counts)
        order = np.lexsort((ids, -counts[ids]))[:self.cache_size]
        out = (ids[order], counts[ids[order]])
        with self._mu:
            self._rankings[s] = out
        return out

    @staticmethod
    def walk(cached: np.ndarray, src: np.ndarray, n: int) -> np.ndarray:
        """Positions in a ranking that the slice's walk takes, given
        each ranked row's cached count and intersection."""
        hit = np.flatnonzero(src >= 1)
        if len(hit) <= n:
            return hit
        first = hit[:n]
        t = int(src[first].min())
        after = np.arange(first[-1] + 1, len(cached))
        low = after[cached[after] < t]
        stop = int(low[0]) if len(low) else len(cached)
        after = after[after < stop]
        return np.concatenate((first, after[src[after] >= t]))

    def topn_src(self, row: int, n: int) -> list[dict]:
        per_slice = [self.src_counts(s, row) for s in range(self.n_slices)]
        cand = set()
        for s, counts in enumerate(per_slice):
            ids, cached = self.ranking(s)
            cand.update(ids[self.walk(cached, counts[ids], n)].tolist())
        ids = np.array(sorted(cand), dtype=np.int64)
        totals = sum(c[ids] for c in per_slice) if len(ids) else ids
        order = np.lexsort((ids, -totals))[:n]
        return [{"id": int(i), "count": int(c)}
                for i, c in zip(ids[order], totals[order]) if c > 0]
