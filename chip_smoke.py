#!/usr/bin/env python3
"""Chip smoke: one real server, BASELINE config 4's width, served from the TPU.

Starts ``python -m pilosa_tpu.cli server`` as a subprocess with no
routing, mesh, warmup or sparse-upload variable set, loads a
256-slice index over the HTTP import route, answers every device-eligible
query class over HTTP, and compares each answer with a plain numpy
reference built from the same seed. Then it reads the server's own
surfaces (``/status``, ``/debug/vars``, the ``X-Pilosa-Stats`` header) to
see WHICH LEG answered: correct answers from the host path are a failure
here.

This process never imports jax: the chip belongs to the server child.

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every phase ran, every answer matched, and every class was served by a
device program on a TPU backend. Anything else: reasons on stderr,
non-zero exit, no result line.
"""

from __future__ import annotations

import argparse
import http.client
import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.cluster.client import Client

INDEX = "smoke"
FRAME = "f"          # ranked frame, Zipf-like row densities
BSI_FRAME = "g"      # frame holding the integer field
BSI_FIELD = "v"
BSI_MIN, BSI_MAX = 0, 1000

# BASELINE.json configs[3]: Count(Intersect(...)) across 256 slices.
FULL_SLICES = 256
FULL_ROWS = 64
BSI_COLUMNS_PER_SLICE = 4096   # x256 slices = 2^20 columns

# Row r is set with probability D0 / (r+1)^ZIPF_S per column: two
# low-cardinality rows >= 10 % dense (bitmap/run containers), rows >= 9
# under 1 % (array containers), the last rows sparse enough (0.06 %) for
# the bucketed sparse upload. 64 rows x 2^28 columns is 1.9e8 set bits and
# 2 GiB of dense leaf slabs against the 1 GiB residency budget.
D0 = 0.3
ZIPF_S = 1.5
# These rows are laid out as one run per 65536-column container (sorted
# low-cardinality data), so run containers reach the pack path too.
RUN_ROWS = (1, 4)

# A server that came up without these set is the server a user gets.
_STEERING_PREFIXES = ("PILOSA_TPU_MESH", "PILOSA_TPU_COST_",
                      "PILOSA_TPU_WARMUP", "PILOSA_TPU_SPARSE_UPLOAD")

# A class is repeated until a device program answers it, at most this
# often, then WARM_REPEATS more times for the warm figure.
MAX_REPEATS = 48
WARM_REPEATS = 3


class SmokeFailure(Exception):
    """A phase could not run to its end (server died, HTTP error)."""


_T0 = time.monotonic()


def _log(msg: str) -> None:
    sys.stderr.write(f"chip_smoke: [{time.monotonic() - _T0:7.1f}s] {msg}\n")
    sys.stderr.flush()


# -- reference data -----------------------------------------------------------

def row_density(r: int) -> float:
    return D0 / (r + 1) ** ZIPF_S


def _bernoulli_words(rng, n_words: int, p: float, bits: int = 12):
    """u64 words whose bits are independently set with probability
    round(p * 2^bits) / 2^bits: fold uniform words LSB-first with OR
    for a 1 digit and AND for a 0 digit of that binary fraction."""
    m = int(round(p * (1 << bits)))
    acc = np.zeros(n_words, dtype=np.uint64)
    for i in range(bits):
        digit = (m >> i) & 1
        if not digit and not acc.any():
            continue
        r = rng.integers(0, 1 << 64, n_words, dtype=np.uint64)
        acc = (acc | r) if digit else (acc & r)
    return acc


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
    """The deployment's data as plain numpy, made from the seed alone:
    ``rows[r]`` is row r of the ranked frame as packed little-endian
    u64 words over all columns; ``bsi_cols``/``bsi_vals`` are the integer
    field. Answers come from popcounts and comparisons on these arrays;
    nothing here touches pilosa_tpu.storage."""

    def __init__(self, seed: int, n_slices: int):
        self.n_slices = n_slices
        self.n_rows = FULL_ROWS
        self.n_cols = n_slices * SLICE_WIDTH
        rng = np.random.default_rng(seed)
        n_words = self.n_cols // 64
        self.rows = np.zeros((self.n_rows, n_words), dtype=np.uint64)
        for r in range(self.n_rows):
            p = row_density(r)
            if r in RUN_ROWS:
                self.rows[r] = _run_words(rng, self.n_cols, p)
            elif p >= 1.0 / 256:
                self.rows[r] = _bernoulli_words(rng, n_words, p)
            else:
                k = int(round(p * self.n_cols))
                self.rows[r] = _positions_to_words(
                    rng.integers(0, self.n_cols, k, dtype=np.uint64),
                    n_words)
        # Integer field: the same number of columns in every slice.
        offs = np.stack([
            rng.choice(SLICE_WIDTH, BSI_COLUMNS_PER_SLICE, replace=False)
            for _ in range(n_slices)]).astype(np.uint64)
        self.bsi_cols = (offs + (np.arange(n_slices, dtype=np.uint64)
                                 * np.uint64(SLICE_WIDTH))[:, None]
                         ).ravel()
        self.bsi_vals = rng.integers(BSI_MIN, BSI_MAX + 1,
                                     len(self.bsi_cols)).astype(np.int64)

    def set_bits(self) -> int:
        return int(np.bitwise_count(self.rows).sum())

    def count(self, words: np.ndarray) -> int:
        return int(np.bitwise_count(words).sum())

    def union(self, ids) -> np.ndarray:
        return np.bitwise_or.reduce(self.rows[list(ids)], axis=0)

    def difference(self, ids) -> np.ndarray:
        ids = list(ids)
        return self.rows[ids[0]] & ~self.union(ids[1:])

    def columns(self, words: np.ndarray) -> list[int]:
        bits = np.unpackbits(words.view(np.uint8), bitorder="little")
        return np.flatnonzero(bits).tolist()

    def topn(self, n: int, src=None, ids=None) -> list[dict]:
        """Exact top-n: count descending, row id ascending on ties,
        zero counts dropped (the order pairs_sort gives)."""
        ids = list(range(self.n_rows)) if ids is None else list(ids)
        block = self.rows[ids] if src is None else self.rows[ids] & src
        counts = np.bitwise_count(block).sum(axis=1)
        order = sorted(range(len(ids)),
                       key=lambda i: (-int(counts[i]), ids[i]))
        pairs = [{"id": ids[i], "count": int(counts[i])}
                 for i in order if counts[i] > 0]
        return pairs[:n] if n else pairs

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


# -- server process -----------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_env() -> dict:
    """The child's environment: this process's, minus the CPU pin this
    sandbox exports and minus anything that steers routing."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    for k in list(env):
        if k.startswith(_STEERING_PREFIXES):
            del env[k]
    return env


def start_server(data_dir: str, log_path: str):
    port = _free_port()
    host = f"127.0.0.1:{port}"
    log = open(log_path, "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server",
         "-d", data_dir, "--bind", host],
        env=server_env(), stdout=log, stderr=subprocess.STDOUT,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    log.close()
    return proc, host


def stop_server(proc) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


class Http:
    """Plain HTTP to the server, one connection per request; every call
    checks the child is still alive so a dead server is named as such."""

    def __init__(self, host: str, proc, timeout: float = 900.0):
        self.host, self.proc, self.timeout = host, proc, timeout

    def request(self, method: str, path: str, body: bytes | None = None):
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"server exited early with code {self.proc.returncode}")
        conn = http.client.HTTPConnection(self.host, timeout=self.timeout)
        try:
            conn.request(method, path, body)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise SmokeFailure(f"{method} {path}: HTTP {resp.status}:"
                               f" {data[:300]!r}")
        return data, resp

    def get_json(self, path: str) -> dict:
        return json.loads(self.request("GET", path)[0])

    def query(self, pql: str):
        """(results, stats header dict, wall ms) for one PQL body."""
        t0 = time.perf_counter()
        data, resp = self.request("POST", f"/index/{INDEX}/query",
                                  pql.encode())
        ms = (time.perf_counter() - t0) * 1e3
        stats = json.loads(resp.getheader("X-Pilosa-Stats") or "{}")
        return json.loads(data)["results"], stats, ms


def wait_up(http: Http, timeout: float = 300.0) -> dict:
    deadline = time.monotonic() + timeout
    while True:
        try:
            return http.get_json("/status")
        except OSError:
            if time.monotonic() > deadline:
                raise SmokeFailure("server did not answer /status in"
                                   f" {timeout:.0f}s")
            time.sleep(0.5)


def wait_warmup(http: Http, timeout: float = 600.0) -> dict:
    deadline = time.monotonic() + timeout
    while True:
        warm = http.get_json("/status").get("warmup") or {}
        if warm.get("state") not in ("pending", "running"):
            return warm
        if time.monotonic() > deadline:
            raise SmokeFailure(f"warmup still {warm.get('state')} after"
                               f" {timeout:.0f}s")
        time.sleep(1.0)


# -- load ----------------------------------------------------------------------

def load(http: Http, ref: Reference) -> dict:
    """Schema + data through the HTTP routes a user's loader calls, the
    bits eight slices (~6e6 bits) per import call."""
    chunk_slices = 8
    t0 = time.perf_counter()
    client = Client(http.host, timeout=900.0)
    client.create_index(INDEX)
    client.create_frame(INDEX, FRAME, {"cacheType": "ranked"})
    client.create_frame(INDEX, BSI_FRAME)
    client.create_field(INDEX, BSI_FRAME, BSI_FIELD, BSI_MIN, BSI_MAX)
    n_bits = 0
    for s0 in range(0, ref.n_slices, chunk_slices):
        rows, cols = ref.slice_positions(
            s0, min(s0 + chunk_slices, ref.n_slices))
        client.import_arrays(INDEX, FRAME, rows, cols)
        n_bits += len(rows)
    t_bits = time.perf_counter()
    client.import_field_values(INDEX, BSI_FRAME, BSI_FIELD,
                               ref.bsi_cols, ref.bsi_vals)
    client.close()
    t1 = time.perf_counter()
    return {"bits": n_bits, "bitsSeconds": round(t_bits - t0, 3),
            "bsiColumns": len(ref.bsi_cols),
            "bsiSeconds": round(t1 - t_bits, 3),
            "seconds": round(t1 - t0, 3)}


# -- queries -------------------------------------------------------------------

def _bm(r: int) -> str:
    return f'Bitmap(frame="{FRAME}", rowID={r})'


def query_classes(ref: Reference) -> list[dict]:
    """Each class: a name, ``pql(i)`` for repeat i and ``want(i)`` for its
    exact answer. Operands rotate through a small hot set of rows from
    repeat to repeat: the leaf slabs are shared between repeats (what
    device residency serves) while no two repeats are the same query
    (what the host's result and subresult caches would serve). TopN, the
    BSI queries and Sum have no such cache and repeat as they are."""
    pairs = list(itertools.combinations(range(6), 2))
    wides = list(itertools.combinations(
        (3, 7, 12, 20, 25, 30, 35, 40, 50, 60), 8))
    diffs = list(itertools.permutations((0, 2, 5, 6), 3))
    tail = ref.n_rows - 24          # materialised rows: the sparse tail
    topn_src = 0
    fused_ids = [2, 3, 5, 7]
    k = (BSI_MIN + BSI_MAX) // 3

    def pick(seq, i):
        return seq[i % len(seq)]

    def window(i: int) -> list[int]:
        start = tail + i % 17
        return list(range(start, start + 8))

    def bitmap(words) -> dict:
        return {"attrs": {}, "bits": ref.columns(words)}

    def fused(i: int) -> str:
        a, b = pick(pairs, i)
        return (f"Count(Intersect({_bm(a)}, {_bm(b)}))"
                f" TopN({_bm(a)}, frame=\"{FRAME}\", ids={fused_ids})"
                f" Count(Union({_bm(a)}, {_bm(b)}))")

    def fused_want(i: int) -> list:
        a, b = pick(pairs, i)
        return [ref.count(ref.rows[a] & ref.rows[b]),
                ref.topn(0, src=ref.rows[a], ids=fused_ids),
                ref.count(ref.rows[a] | ref.rows[b])]

    return [
        {"name": "count_intersect",
         "pql": lambda i: "Count(Intersect(%s, %s))"
                          % tuple(map(_bm, pick(pairs, i))),
         "want": lambda i: [ref.count(ref.rows[pick(pairs, i)[0]]
                                      & ref.rows[pick(pairs, i)[1]])]},
        {"name": "count_union8",
         "pql": lambda i: "Count(Union(%s))"
                          % ", ".join(map(_bm, pick(wides, i))),
         "want": lambda i: [ref.count(ref.union(pick(wides, i)))]},
        {"name": "count_difference",
         "pql": lambda i: "Count(Difference(%s))"
                          % ", ".join(map(_bm, pick(diffs, i))),
         "want": lambda i: [ref.count(ref.difference(pick(diffs, i)))]},
        {"name": "union_materialize",
         "pql": lambda i: "Union(%s)" % ", ".join(map(_bm, window(i))),
         "want": lambda i: [bitmap(ref.union(window(i)))]},
        {"name": "topn",
         "pql": lambda i: f'TopN(frame="{FRAME}", n=10)',
         "want": lambda i: [ref.topn(10)]},
        {"name": "topn_src",
         "pql": lambda i: f'TopN({_bm(topn_src)}, frame="{FRAME}", n=10)',
         "want": lambda i: [ref.topn(10, src=ref.rows[topn_src])]},
        {"name": "count_range",
         "pql": lambda i: (f'Count(Range(frame="{BSI_FRAME}",'
                           f' {BSI_FIELD} < {k}))'),
         "want": lambda i: [int((ref.bsi_vals < k).sum())]},
        {"name": "sum",
         "pql": lambda i: (f'Sum(frame="{BSI_FRAME}",'
                           f' field="{BSI_FIELD}")'),
         "want": lambda i: [{"value": int(ref.bsi_vals.sum()),
                             "count": len(ref.bsi_vals)}]},
        {"name": "fused_tree", "pql": fused, "want": fused_want},
    ]


def run_class(http: Http, cls: dict) -> dict:
    """Repeat one class until a device program answers it (at most
    MAX_REPEATS times), then WARM_REPEATS more. Every answer is compared."""
    repeats = []
    mismatches = []
    wants: dict = {}    # by PQL: a class that repeats a query computes
    engaged_at = None   # its reference answer once
    i = 0
    while i < MAX_REPEATS + WARM_REPEATS:
        pql = cls["pql"](i)
        got, stats, ms = http.query(pql)
        if pql not in wants:
            wants[pql] = cls["want"](i)
        want = wants[pql]
        if got != want:
            mismatches.append({"repeat": i, "got": _clip(got),
                               "want": _clip(want)})
        repeats.append({"ms": round(ms, 3),
                        "devicePrograms": stats.get("devicePrograms", 0),
                        "deviceBytes": stats.get("deviceBytes", 0),
                        "compileMs": stats.get("compileMs", 0)})
        if engaged_at is None and repeats[-1]["devicePrograms"] >= 1:
            engaged_at = i
        i += 1
        if engaged_at is None and i >= MAX_REPEATS:
            break
        if engaged_at is not None and i > engaged_at + WARM_REPEATS:
            break
    last = repeats[-1]
    _log(f"{cls['name']}: {len(repeats)} repeats, device from repeat"
         f" {engaged_at}, first {repeats[0]['ms']:.0f} ms, last"
         f" {last['ms']:.0f} ms, {len(mismatches)} mismatches")
    return {"name": cls["name"], "coldMs": repeats[0]["ms"],
            "warmMs": last["ms"], "engagedAtRepeat": engaged_at,
            "devicePrograms": last["devicePrograms"],
            "deviceBytes": last["deviceBytes"],
            "repeats": repeats, "mismatches": mismatches}


def _clip(v, n: int = 400) -> str:
    s = json.dumps(v)
    return s if len(s) <= n else s[:n] + f"...({len(s)} chars)"


def run_write(http: Http, ref: Reference) -> dict:
    """An acknowledged SetBit must show in the very next reads, and the
    leaf slab it invalidated (generation bump) must come back on the
    device. The bit goes into row 0 at a column rows 1-3 all have, so
    every re-read (row 0 against each of them in turn) grows by one."""
    partners = (1, 2, 3)
    a = ref.rows[0]
    free = np.bitwise_and.reduce(ref.rows[list(partners)], axis=0) & ~a
    w = int(np.flatnonzero(free)[0])
    bit = int(free[w]) & -int(free[w])      # lowest free bit of the word
    col = w * 64 + bit.bit_length() - 1
    got, _, ms = http.query(
        f'SetBit(frame="{FRAME}", rowID=0, columnID={col})')
    a[w] |= np.uint64(bit)
    out = run_class(http, {
        "name": "write_then_read",
        "pql": lambda i: "Count(Intersect(%s, %s))" % (
            _bm(0), _bm(partners[i % 3])),
        "want": lambda i: [ref.count(a & ref.rows[partners[i % 3]])]})
    out.update({"column": col, "setBitResult": got,
                "setBitMs": round(ms, 3)})
    if got != [True]:
        out["mismatches"].append({"repeat": -1, "got": _clip(got),
                                  "want": "[true]"})
    return out


# -- surfaces + verdict --------------------------------------------------------

def read_surfaces(http: Http, after: float) -> dict:
    """/status and /debug/vars; /status' runtime block is a periodic
    sample, so wait for one taken after the last query."""
    deadline = time.monotonic() + 60.0
    while True:
        status = http.get_json("/status")
        sampled = (status.get("runtime") or {}).get("sampledAt", 0)
        if sampled >= after or time.monotonic() > deadline:
            break
        time.sleep(1.0)
    return {"status": status, "vars": http.get_json("/debug/vars")}


def verdict(report: dict) -> list[str]:
    """Every reason this run does not prove the served path ran on the
    chip. Empty list = pass."""
    bad: list[str] = []
    build = report.get("build") or {}
    if build.get("backend") != "tpu":
        bad.append(f"backend is {build.get('backend')!r}, not 'tpu'")
    if not build.get("deviceKind") or not build.get("deviceCount"):
        bad.append("server did not report deviceKind/deviceCount")
    for lib in ("native", "nativeExt"):
        if build.get(lib) is not True:
            bad.append(f"{lib} library not built: {build.get(lib)!r}")
    warm = report.get("warmup") or {}
    cov = warm.get("coverage") or {}
    if warm.get("state") != "done":
        bad.append(f"warmup.state is {warm.get('state')!r}"
                   f" ({warm.get('error')})")
    elif cov.get("warmed") != cov.get("programs") or cov.get("missing"):
        bad.append(f"warmup coverage {cov.get('warmed')}/"
                   f"{cov.get('programs')}, missing {cov.get('missing')}")
    for cls in report.get("classes") or []:
        for m in cls["mismatches"]:
            bad.append(f"{cls['name']} repeat {m['repeat']}: got"
                       f" {m['got']}, want {m['want']}")
        if cls["devicePrograms"] < 1:
            bad.append(f"{cls['name']}: served by the host"
                       f" (devicePrograms == 0 after"
                       f" {len(cls['repeats'])} repeats)")
    v = report.get("vars") or {}
    if v.get("deviceFallback") != 0:
        bad.append(f"deviceFallback is {v.get('deviceFallback')!r}")
    cache = v.get("deviceBlockCache") or {}
    if not cache.get("misses") or not cache.get("usedBytes"):
        bad.append(f"residency cache never filled: {cache}")
    else:
        # Sharding slices over chips is the point: each device of the
        # mesh holds an equal share of the resident slabs, the shares
        # add up to usedBytes (none whole on device 0, none replicated).
        per = cache.get("perDeviceBytes") or {}
        n_dev = build.get("deviceCount") or 0
        if (len(per) != n_dev or len(set(per.values())) != 1
                or sum(per.values()) != cache["usedBytes"]):
            bad.append(f"resident slabs are not spread evenly over"
                       f" {n_dev} device(s): perDeviceBytes {per},"
                       f" usedBytes {cache['usedBytes']}")
    if "costModel" not in v:
        bad.append("no costModel block: the router never calibrated")
    cc = report.get("compileCache") or {}
    if not cc.get("firstCalls"):
        bad.append(f"compileCache.firstCalls is {cc.get('firstCalls')!r}"
                   " (no compile was ever counted)")
    if not cc.get("persistentCacheDir"):
        bad.append("no persistent compile cache directory is armed")
    elif not (cc.get("persistentHits") or cc.get("persistentMisses")):
        bad.append("persistent compile cache saw neither hit nor miss")
    return bad


def _versions() -> dict:
    """Installed versions, read from package metadata (no jax import)."""
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def run(seed: int, n_slices: int,
        fail_fast: bool = True) -> tuple[dict, list[str]]:
    """Every phase in order; returns (report, reasons it failed).
    ``fail_fast`` stops right after start-up when the server's backend is
    not a TPU, before the minutes of load a machine with no chip would
    waste; the tier-1 test turns it off to drive the rest on the CPU, and
    is the only caller that cuts ``n_slices`` (the command line cannot)."""
    report: dict = {
        "seed": seed, "slices": n_slices, "rows": FULL_ROWS,
        "reduced": ({} if n_slices == FULL_SLICES
                    else {"slices": [FULL_SLICES, n_slices]}),
        "versions": _versions()}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        log_path = os.path.join(tmp, "server.log")
        proc, host = start_server(os.path.join(tmp, "data"), log_path)
        http = Http(host, proc)
        try:
            t0 = time.perf_counter()
            build = wait_up(http).get("build") or {}
            report["startSeconds"] = round(time.perf_counter() - t0, 3)
            _log(f"server up at {host} in {report['startSeconds']} s:"
                 f" {build}")
            if fail_fast and build.get("backend") != "tpu":
                raise SmokeFailure(
                    f"backend is {build.get('backend')!r}, not 'tpu':"
                    " JAX found no accelerator")
            t0 = time.perf_counter()
            ref = Reference(seed, n_slices)
            report["generateSeconds"] = round(time.perf_counter() - t0, 3)
            report["referenceBits"] = ref.set_bits()
            _log(f"reference: {report['referenceBits']} bits over"
                 f" {n_slices} slices in {report['generateSeconds']} s")
            report["load"] = load(http, ref)
            _log(f"loaded: {report['load']}")
            warm = wait_warmup(http)
            _log(f"warmup: {warm.get('state')} {warm.get('coverage')}")
            classes = [run_class(http, c) for c in query_classes(ref)]
            classes.append(run_write(http, ref))
            report["classes"] = classes
            surfaces = read_surfaces(http, time.time())
        finally:
            failed = sys.exc_info()[0] is not None
            stop_server(proc)
            with open(log_path, "rb") as f:
                log = f.read().decode("utf-8", "replace")
            if failed:
                sys.stderr.write("--- server log (tail) ---\n"
                                 + log[-8000:]
                                 + "\n--- end of server log ---\n")
    # Donation into outputs that cannot reuse the buffer is expected
    # noise from the streaming programs; a native build error is not.
    report["serverLog"] = {
        "donationWarnings": log.count("donated buffers were not usable"),
        "nativeBuildErrors": log.count("failed to build or load")}

    status, v = surfaces["status"], surfaces["vars"]
    runtime = status.get("runtime") or {}
    report["build"] = status.get("build")
    report["warmup"] = status.get("warmup")
    report["compileCache"] = runtime.get("compileCache")
    report["vars"] = {k: v.get(k) for k in (
        "deviceFallback", "costModelVetoes", "costModel",
        "deviceBlockCache")}
    return report, verdict(report)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=21,
                    help="seed of the generated data (default 21)")
    args = ap.parse_args(argv)
    report, bad = run(args.seed, FULL_SLICES)
    build = report.get("build") or {}
    if bad:
        sys.stderr.write(json.dumps(report) + "\n")
        for reason in bad:
            sys.stderr.write(f"chip_smoke: FAIL: {reason}\n")
        return 1
    print(json.dumps(report))
    print(json.dumps({"ok": True, "device": {
        "platform": build["backend"], "kind": build["deviceKind"],
        "count": build["deviceCount"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
