"""A deployment's data as plain numpy, made from the seed alone.

Copied from ``chip_smoke.py`` (PR 21, proven on the chip) with the sizes
taken from the configuration file instead of module constants, and with
one random stream a row in place of one for all, so that the rows are
made side by side (33 s -> a few seconds of every run's set-up at 256
slices): the densities and layouts are ``chip_smoke.py``'s, the bits at
a given seed are not. Nothing here imports the program.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLICE_WIDTH = 1 << 20   # columns per slice: the index format's constant
MAKE_THREADS = 8
_BLOCK_WORDS = 1 << 15   # 256 KiB of uniform words at a time


def _bernoulli_words(rng, n_words: int, p: float, bits: int = 12):
    """u64 words whose bits are independently set with probability
    round(p * 2^bits) / 2^bits: fold uniform words LSB-first with OR
    for a 1 digit and AND for a 0 digit of that binary fraction. Made a
    block at a time, so the uniform words live in memory that is
    reused, not in fresh pages."""
    m = int(round(p * (1 << bits)))
    out = np.empty(n_words, dtype=np.uint64)
    for w0 in range(0, n_words, _BLOCK_WORDS):
        n = min(_BLOCK_WORDS, n_words - w0)
        acc = np.zeros(n, dtype=np.uint64)
        for i in range(bits):
            digit = (m >> i) & 1
            if not digit and not acc.any():
                continue
            r = rng.integers(0, 1 << 64, n, dtype=np.uint64)
            if digit:
                acc |= r
            else:
                acc &= r
        out[w0:w0 + n] = acc
    return out


def _positions_to_words(pos: np.ndarray, n_words: int) -> np.ndarray:
    words = np.zeros(n_words, dtype=np.uint64)
    if not len(pos):
        return words
    pos = np.unique(pos)
    w = pos >> np.uint64(6)
    bit = np.uint64(1) << (pos & np.uint64(63))
    starts = np.concatenate(([0], np.flatnonzero(np.diff(w)) + 1))
    words[w[starts]] = np.bitwise_or.reduceat(bit, starts)
    return words


def _run_words(rng, n_cols: int, p: float) -> np.ndarray:
    """One run of round(p * 65536) columns at a random offset in every
    65536-column container."""
    n_cont = n_cols // 65536
    length = max(1, int(round(p * 65536)))
    starts = (np.arange(n_cont, dtype=np.uint64) * np.uint64(65536)
              + rng.integers(0, 65536 - length, n_cont, dtype=np.uint64))
    pos = (np.repeat(starts, length)
           + np.tile(np.arange(length, dtype=np.uint64), n_cont))
    return _positions_to_words(pos, n_cols // 64)


class Reference:
    """``rows[r]`` is row r of the ranked frame as packed little-endian
    u64 words over all columns; ``bsi_cols``/``bsi_vals`` are the integer
    field, where the configuration has one. Answers come from popcounts
    on these arrays."""

    def __init__(self, seed: int, config: dict):
        self.n_slices = int(config["n_slices"])
        self.n_rows = int(config["n_rows"])
        self.n_cols = self.n_slices * SLICE_WIDTH
        d0, zipf_s = float(config["d0"]), float(config["zipf_s"])
        run_rows = set(config["run_rows"])
        n_words = self.n_cols // 64
        self.rows = np.zeros((self.n_rows, n_words), dtype=np.uint64)

        # Every row, and every slice of the integer field, has a random
        # stream of its own ([seed, kind, index]), so they are made side
        # by side: numpy's generators release the GIL.
        def make_row(r: int) -> None:
            rng = np.random.default_rng([seed, 0, r])
            p = d0 / (r + 1) ** zipf_s
            if r in run_rows:
                self.rows[r] = _run_words(rng, self.n_cols, p)
            elif p >= 1.0 / 256:
                self.rows[r] = _bernoulli_words(rng, n_words, p)
            else:
                k = int(round(p * self.n_cols))
                self.rows[r] = _positions_to_words(
                    rng.integers(0, self.n_cols, k, dtype=np.uint64),
                    n_words)

        bsi = config.get("bsi")

        def make_bsi(s: int):
            rng = np.random.default_rng([seed, 1, s])
            per = int(bsi["columns_per_slice"])
            cols = (np.sort(rng.choice(SLICE_WIDTH, per, replace=False))
                    .astype(np.uint64) + np.uint64(s * SLICE_WIDTH))
            return cols, rng.integers(int(bsi["min"]),
                                      int(bsi["max"]) + 1,
                                      per).astype(np.int64)

        with ThreadPoolExecutor(MAKE_THREADS) as pool:
            list(pool.map(make_row, range(self.n_rows)))
            self.bsi_cols = self.bsi_vals = None
            if bsi:
                parts = list(pool.map(make_bsi, range(self.n_slices)))
                self.bsi_cols = np.concatenate([c for c, _ in parts])
                self.bsi_vals = np.concatenate([v for _, v in parts])

    def set_bits(self) -> int:
        return int(np.bitwise_count(self.rows).sum())

    def count_intersect(self, ids) -> int:
        ids = list(ids)
        acc = self.rows[ids[0]] & self.rows[ids[1]] if len(ids) > 1 \
            else self.rows[ids[0]]
        for r in ids[2:]:
            acc &= self.rows[r]
        return int(np.bitwise_count(acc).sum())

    def bit(self, row: int, col: int) -> bool:
        return bool((int(self.rows[row, col >> 6]) >> (col & 63)) & 1)

    def slice_positions(self, s0: int, s1: int):
        """(row ids, column ids) of every set bit in slices [s0, s1)."""
        w0, w1 = s0 * SLICE_WIDTH // 64, s1 * SLICE_WIDTH // 64
        base = np.uint64(s0 * SLICE_WIDTH)
        rows_out, cols_out = [], []
        for r in range(self.n_rows):
            bits = np.unpackbits(self.rows[r, w0:w1].view(np.uint8),
                                 bitorder="little")
            cols = np.flatnonzero(bits).astype(np.uint64) + base
            rows_out.append(np.full(len(cols), r, dtype=np.uint64))
            cols_out.append(cols)
        return np.concatenate(rows_out), np.concatenate(cols_out)
