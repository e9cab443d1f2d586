"""The comparison that decides ``correct``: the window's own answers
against the plain reference, once the window has closed.

Every comparison is exact, so every limit is 0:

``wrong_reads``       checked reads whose count is not the reference's
``wrong_writes``      SetBit acks whose changed/unchanged flag is wrong
``lost_writes``       rows whose count, read back after the window, lacks
                      an acknowledged bit (or holds one never sent)
``failed_requests``   requests with no answer: a refusal, an HTTP error,
                      a dropped connection

The guarantee held to is the configuration's: exact answers, and an
acknowledged write is visible to the next read. A write is *due* in a
read when its ack was read before the read was sent; a write in flight
while the read was (sent before the read's answer came, acked after the
read was sent) may or may not show, so the read may say the count with
any subset of those applied. A write that got no ack stays in doubt for
good.
"""

from __future__ import annotations

import itertools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .data import Reference
from .loadgen import Record

CHECK_THREADS = 8
MAX_SUBSET_WRITES = 10   # 2^10 subsets; beyond it, a range is accepted


def _gain(ref: Reference, rows: tuple, writes: list[Record]) -> int:
    """Columns that join the intersection of ``rows`` once ``writes``
    are applied to the reference's bits."""
    by_col: dict[int, set] = {}
    for w in writes:
        by_col.setdefault(w.op.col, set()).add(w.op.rows[0])
    gain = 0
    for col, written in by_col.items():
        base = [ref.bit(r, col) for r in rows]
        if all(base):
            continue
        if all(b or r in written for b, r in zip(base, rows)):
            gain += 1
    return gain


def _read_ok(ref: Reference, rec: Record, base: int,
             writes: list[Record]) -> tuple[bool, str]:
    rows = set(rec.op.rows)
    due, maybe = [], []
    for w in writes:
        if w.op.rows[0] not in rows or w.sent > rec.done:
            continue
        (due if w.ok and w.done < rec.sent else maybe).append(w)
    got = rec.results[0] if rec.results else None
    lo = base + _gain(ref, rec.op.rows, due)
    if not maybe:
        return got == lo, f"{lo}"
    if len(maybe) > MAX_SUBSET_WRITES:
        hi = base + _gain(ref, rec.op.rows, due + maybe)
        return isinstance(got, int) and lo <= got <= hi, f"{lo}..{hi}"
    allowed = {base + _gain(ref, rec.op.rows, due + list(sub))
               for n in range(len(maybe) + 1)
               for sub in itertools.combinations(maybe, n)}
    return got in allowed, f"one of {sorted(allowed)}"


def _write_ok(ref: Reference, rec: Record,
              writes: list[Record]) -> tuple[bool, str]:
    row, col = rec.op.rows[0], rec.op.col
    if ref.bit(row, col):
        allowed = {False}
    else:
        same = [w for w in writes if w is not rec
                and w.op.rows[0] == row and w.op.col == col]
        before = [w for w in same if w.ok and w.done < rec.sent]
        racing = [w for w in same if w not in before
                  and w.sent <= rec.done]
        allowed = {False} if before else ({True, False} if racing
                                          else {True})
    got = rec.results[0] if rec.results else None
    return got in allowed, f"one of {sorted(allowed)}"


def sample_reads(reads: list[Record], n: int, seed: int) -> list[Record]:
    """At most ``n`` of the reads, drawn from the seed."""
    if len(reads) <= n:
        return reads
    rng = np.random.default_rng([seed, 1])
    picks = rng.choice(len(reads), n, replace=False)
    return [reads[i] for i in sorted(picks.tolist())]


def compare(ref: Reference, records: list[Record], earlier: list[Record],
            readback: dict, check_sample: int, seed: int, log=None) -> dict:
    """``records`` are the window's; ``earlier`` are the writes sent
    before it (the warm-up sends the cell's own traffic), which the
    window's reads have to show; ``readback`` maps each written row to
    its count as read after the window. Returns the numbers compared,
    each beside its limit, and how much was compared."""
    answered = [r for r in records if r.ok]
    writes = earlier + [r for r in records if r.op.write]
    reads = sample_reads([r for r in answered if not r.op.write],
                         check_sample, seed)
    wrong: list[str] = []

    keys = sorted({tuple(sorted(r.op.rows)) for r in reads})
    with ThreadPoolExecutor(CHECK_THREADS) as pool:
        base = dict(zip(keys, pool.map(ref.count_intersect, keys)))
    wrong_reads = 0
    for rec in reads:
        ok, want = _read_ok(ref, rec, base[tuple(sorted(rec.op.rows))],
                            writes)
        if not ok:
            wrong_reads += 1
            wrong.append(f"{rec.op.pql}: got {rec.results}, want {want}")

    wrong_writes = 0
    for rec in writes:
        if not rec.ok:
            continue
        ok, want = _write_ok(ref, rec, writes)
        if not ok:
            wrong_writes += 1
            wrong.append(f"{rec.op.pql}: got {rec.results}, want {want}")

    lost = 0
    for row in sorted({w.op.rows[0] for w in writes}):
        mine = [w for w in writes if w.op.rows[0] == row]
        new = {w.op.col for w in mine if w.ok
               and not ref.bit(row, w.op.col)}
        doubt = {w.op.col for w in mine if not w.ok
                 and not ref.bit(row, w.op.col)} - new
        lo = int(np.bitwise_count(ref.rows[row]).sum()) + len(new)
        got = readback.get(row)
        if not (isinstance(got, int) and lo <= got <= lo + len(doubt)):
            lost += 1
            wrong.append(f"row {row} read back {got}, want {lo}"
                         + (f"..{lo + len(doubt)}" if doubt else ""))

    if log:
        for line in wrong[:20]:
            log("MISMATCH " + line)
    return {
        "compared": {
            "wrong_reads": {"value": wrong_reads, "limit": 0},
            "wrong_writes": {"value": wrong_writes, "limit": 0},
            "lost_writes": {"value": lost, "limit": 0},
            "failed_requests": {"value": len(records) - len(answered),
                                "limit": 0}},
        "checked": {"reads": len(reads),
                    "writes": sum(1 for w in writes if w.ok),
                    "rows_read_back": len(readback)},
        "mismatches": wrong[:20]}
