"""One general generator: a traffic file's parameters + a seed -> requests.

A traffic file (``cellbench/traffic/<mix>.json``) holds::

    arrival   {"loop": "closed", "clients": 8}
    block     how many requests make one block; each block holds every
              op class in its exact share (largest remainder), shuffled
              by the seed, so every seed sends the same mix in another
              order
    ops       op classes: name, weight, template, leaves (reads),
              bytes_fn (the name of its function in ``bytes_fns.py``)
    keys      the row law: {"law": "uniform", "hot_rows": n,
              "no_repeat": true} draws each class's row sets from a
              seed-shuffled list of all its combinations of the n
              densest rows, starting over when the list is spent;
              {"law": "zipf", "s": s} draws ranks 0..n_rows-1 with
              p(i) ~ 1/(i+1)^s, distinct within a request. Rank i is
              row i: the generator's densities fall with the row id.
    warm_requests   requests of this same traffic sent before the
                    window, uncounted, by ``warm_clients`` clients
                    (the arrival's own count where the key is absent)
    check_sample    how many of the window's reads the reference checks
                    (drawn from the seed; writes are all checked)

Templates: ``count_intersect`` (Count(Intersect(leaves rows))) and
``setbit`` (SetBit(row by the key law, column uniform over all columns)).
The generator is one object behind a lock: the clients draw from one
sequence, so the requests sent are the seed's, whichever client sends
which.
"""

from __future__ import annotations

import itertools
import threading

import numpy as np

from .data import SLICE_WIDTH


class Op:
    """One request: its class, its rows (and column for a write) and
    its PQL text."""

    __slots__ = ("cls", "write", "rows", "col", "pql")

    def __init__(self, cls: dict, rows: tuple, col: int | None, pql: str):
        self.cls = cls
        self.write = col is not None
        self.rows = rows
        self.col = col
        self.pql = pql


def block_counts(weights: list[float], block: int) -> list[int]:
    """Integer shares of a block, by the largest remainder."""
    total = float(sum(weights))
    exact = [w / total * block for w in weights]
    counts = [int(x) for x in exact]
    order = sorted(range(len(exact)),
                   key=lambda i: (counts[i] - exact[i], i))
    for i in order[:block - sum(counts)]:
        counts[i] += 1
    return counts


class Generator:
    def __init__(self, traffic: dict, config: dict, seed: int):
        self.ops = traffic["ops"]
        self.frame = config["frame"]
        self.n_rows = int(config["n_rows"])
        self.n_cols = int(config["n_slices"]) * SLICE_WIDTH
        self.rng = np.random.default_rng([seed, 0])
        self._mu = threading.Lock()
        counts = block_counts([o["weight"] for o in self.ops],
                              int(traffic["block"]))
        self._block = np.repeat(np.arange(len(self.ops)), counts)
        self._pending: list[int] = []
        keys = traffic["keys"]
        self.law = keys["law"]
        if self.law == "uniform":
            self.hot = int(keys["hot_rows"])
            self.no_repeat = bool(keys.get("no_repeat"))
            self._combos: dict = {}
        elif self.law == "zipf":
            p = 1.0 / np.arange(1, self.n_rows + 1) ** float(keys["s"])
            self._cdf = np.cumsum(p / p.sum())
        else:
            raise ValueError(f"unknown key law {self.law!r}")

    def _rows(self, k: int) -> tuple:
        if self.law == "zipf":
            out: list[int] = []
            while len(out) < k:
                r = int(np.searchsorted(self._cdf, self.rng.random()))
                r = min(r, self.n_rows - 1)
                if r not in out:
                    out.append(r)
            return tuple(out)
        if not self.no_repeat:
            return tuple(int(r) for r in
                         self.rng.choice(self.hot, k, replace=False))
        state = self._combos.get(k)
        if state is None or not state:
            state = list(itertools.combinations(range(self.hot), k))
            self.rng.shuffle(state)
            self._combos[k] = state
        return state.pop()

    def next(self) -> Op:
        with self._mu:
            if not self._pending:
                self._pending = self.rng.permutation(self._block).tolist()
            cls = self.ops[self._pending.pop()]
            if cls["template"] == "count_intersect":
                rows = self._rows(int(cls["leaves"]))
                leaves = ", ".join(
                    f'Bitmap(frame="{self.frame}", rowID={r})'
                    for r in rows)
                return Op(cls, rows, None, f"Count(Intersect({leaves}))")
            if cls["template"] == "setbit":
                (row,) = self._rows(1)
                col = int(self.rng.integers(0, self.n_cols))
                return Op(cls, (row,), col,
                          f'SetBit(frame="{self.frame}", rowID={row},'
                          f' columnID={col})')
            raise ValueError(f"unknown template {cls['template']!r}")
