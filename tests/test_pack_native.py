"""The native pack of a residency fill: ``ops.packed.pack_slab`` in one
call into ``native/bitops.cpp`` (``pack_slab_u32``) wherever the library
loaded, and the numpy pass where it did not.

The numpy pass is the oracle: every slab here is packed both ways and the
two must agree array for array — the same path (sparse or dense), the
same bucket width, the same lanes, values and padding, the same dense
block, the same count of containers taken. Beside that: the numpy pass
still answers alone, a served fill says which pass packed it, and a
pass over mapped containers is safe while the fragment swaps its file.
"""

import gc
import http.client
import json
import threading

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.cluster.client import Client
from pilosa_tpu.ops import packed
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import residency
from pilosa_tpu.server.server import Server
from pilosa_tpu.storage import native
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.roaring import Bitmap, Container, values_to_runs

W = packed.WORDS_PER_SLICE
GROUPS = W // 128

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="the native library did not build")


@pytest.fixture
def numpy_only(monkeypatch):
    """The program as it runs where the native library did not load."""
    monkeypatch.setattr(native, "_load", lambda: None)
    assert not native.available()


def numpy_pack(rows, sparse=True):
    saved = native._load
    native._load = lambda: None
    try:
        return packed.pack_slab(rows, sparse)
    finally:
        native._load = saved


def assert_same(rows, sparse=True):
    """Pack ``rows`` both ways; they must agree array for array. Returns
    the path taken and G."""
    got = packed.pack_slab(rows, sparse)
    want = numpy_pack(rows, sparse)
    assert got[2] == want[2]                            # containers taken
    if want[0] is None:
        assert got[0] is None
        assert got[1].dtype == want[1].dtype == np.uint32
        assert got[1].shape == want[1].shape == (len(rows), W)
        assert np.array_equal(got[1], want[1])
        return "dense", None
    assert got[1] is None
    for a, b in zip(got[0], want[0]):
        assert a.dtype == b.dtype == np.uint32
        assert a.shape == b.shape
        assert np.array_equal(a, b)
    return "sparse", want[0][0].shape[-1]


# -- rows of chosen container kinds -------------------------------------------

def row_of(cols, kinds=None) -> Bitmap:
    """A slice-row's containers keyed 0..15, as ``Fragment.row_containers``
    hands them: ``kinds`` maps a key to ``bitmap`` or ``run`` (an array
    otherwise, whatever its cardinality)."""
    cols = np.unique(np.asarray(cols, dtype=np.int64))
    out = Bitmap()
    for key in np.unique(cols >> 16).tolist():
        low = (cols[cols >> 16 == key] & 0xFFFF).astype(np.uint32)
        kind = (kinds or {}).get(key, "array")
        if kind == "run":
            c = Container.from_runs(values_to_runs(low))
        else:
            c = Container.from_array(low)
            if kind == "bitmap":
                c._to_bitmap()
        assert c.kind() == kind
        out.keys.append(key)
        out.containers.append(c)
    return out


def spread(width: int, group: int = 3, first_bit: int = 0) -> np.ndarray:
    """Columns that set ``width`` words of one 128-word group."""
    return (group * 128 + np.arange(width)) * 32 + first_bit


RNG = np.random.default_rng(40)
THIN = RNG.choice(SLICE_WIDTH, 900, replace=False)
THICK = RNG.choice(1 << 16, 30000, replace=False)
SHORT_RUNS = np.concatenate([np.arange(s, s + 40)
                             for s in (100, 9000, 200000, 900100)])
LONG_RUN = np.arange(70000, 76000)
CLUSTERED = np.concatenate([
    (g * 128 + np.arange(10))[:, None] * 32 + np.arange(32)
    for g in range(16)]).ravel() + 5 * (1 << 16)
EDGES = np.array([0, 31, 32, 4095, 4096, 65535, 65536, SLICE_WIDTH - 1])

CASES = {
    # name: (columns a slice-row or None, kinds a slice-row, path, G)
    "arrays_only": ([THIN, THIN[:300] + 1, EDGES], None, "sparse", None),
    "arrays_only_dense": ([THICK, THIN], None, "dense", None),
    "bitmaps_only": ([THICK, THICK + (7 << 16)],
                     [{0: "bitmap"}, {7: "bitmap"}], "dense", None),
    "bitmap_that_passes_the_gate": ([CLUSTERED], [{5: "bitmap"}],
                                    "sparse", 16),
    "runs_short": ([SHORT_RUNS, None],
                   [{0: "run", 3: "run", 13: "run"}, None], "sparse", 2),
    "runs_long": ([LONG_RUN, LONG_RUN + 65536, [65535, 65536]],
                  [{1: "run"}, {2: "run"}, {0: "run", 1: "run"}],
                  "dense", None),
    "mixed_sparse": ([np.concatenate([THIN, CLUSTERED, SHORT_RUNS]), EDGES],
                     [{5: "bitmap", 13: "run"}, None], "sparse", None),
    "mixed_dense": ([np.concatenate([THIN, THICK, LONG_RUN + (1 << 16)])],
                    [{0: "bitmap", 2: "run"}], "dense", None),
    "all_absent": ([None, None, None], None, "sparse", 1),
    "all_empty": ([None, [], None], None, "sparse", 1),
    "g_exactly_32": ([spread(32, first_bit=31), spread(5)], None,
                     "sparse", 32),
    "g_33": ([spread(33), spread(5)], None, "dense", None),
    "g_33_in_a_bitmap": ([spread(33, group=17)], [{1: "bitmap"}],
                         "dense", None),
    "last_group_of_the_last_row": (
        [spread(3, group=GROUPS - 1), [SLICE_WIDTH - 1, 15 << 16]],
        [None, {15: "bitmap"}], "sparse", 4),
}


@pytest.mark.parametrize("sparse", [True, False], ids=["gate", "dense_only"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_native_equals_numpy(name, sparse):
    cols, kinds, path, width = CASES[name]
    kinds = kinds or [None] * len(cols)
    rows = [None if c is None else row_of(c, k)
            for c, k in zip(cols, kinds)]
    got_path, got_width = assert_same(rows, sparse)
    assert got_path == (path if sparse else "dense")
    if sparse and width is not None:
        assert got_width == width


@pytest.mark.parametrize("n_rows", [32, 256])
def test_slab_heights_of_the_cells(n_rows):
    """A one-chip cell's slab (32 slices) and a 256-slice one, holes and
    all: most slices absent or thin, one row per kind."""
    rng = np.random.default_rng(n_rows)
    rows = [None] * n_rows
    for t in rng.choice(n_rows, n_rows // 4, replace=False).tolist():
        rows[t] = row_of(rng.choice(SLICE_WIDTH, 60, replace=False))
    rows[1] = row_of(SHORT_RUNS, {0: "run", 3: "run", 13: "run"})
    rows[-1] = row_of(CLUSTERED, {5: "bitmap"})
    assert assert_same(rows) == ("sparse", 16)
    rows[7] = row_of(THICK, {0: "bitmap"})
    assert assert_same(rows) == ("dense", None)


def test_candidate_block_shape():
    """A TopN candidate block: slices x rows slice-rows, row-major, an
    absent fragment a run of Nones."""
    n_slices, row_ids = 8, (0, 3, 9)
    rng = np.random.default_rng(9)
    rows = []
    for si in range(n_slices):
        for rid in row_ids:
            rows.append(None if si in (2, 5) else
                        row_of(rng.choice(SLICE_WIDTH, 40 * (rid + 1),
                                          replace=False)))
    path, width = assert_same(rows)
    assert path == "sparse" and width >= 1
    lanes, vals = packed.pack_slab(rows)[0]
    assert lanes.shape == (n_slices * len(row_ids), GROUPS, width)


def share8_row(rng, r: int, n_slices: int) -> list:
    """Row ``r`` of ``baseline-c4-share8``'s density law over
    ``n_slices`` slices as a fragment holds it: d0 / (r + 1) ** 1.5 a
    column, rows 1 and 4 one run a container, the containers' kinds
    those ``optimize`` picks."""
    p = 0.3 / (r + 1) ** 1.5
    out = []
    for _ in range(n_slices):
        if r in (1, 4):
            length = max(1, int(round(p * 65536)))
            starts = (np.arange(16) << 16) + rng.integers(
                0, 65536 - length, 16)
            cols = (starts[:, None] + np.arange(length)).ravel()
        elif p >= 1.0 / 256:
            cols = np.flatnonzero(rng.random(SLICE_WIDTH) < p)
        else:
            cols = rng.integers(0, SLICE_WIDTH,
                                max(1, int(round(p * SLICE_WIDTH))))
        b = Bitmap.from_sorted(np.unique(cols).astype(np.uint64))
        b.optimize()
        out.append(b)
    return out


@pytest.mark.parametrize("r", [0, 1, 4, 6, 17, 18, 40, 150, 511])
def test_share8_density_law(r):
    """Slabs drawn from the deployment's own law: the head packs dense,
    the tail ships sparse, both ways alike."""
    rows = share8_row(np.random.default_rng([40, r]), r, 32)
    kinds = {c.kind() for b in rows for c in b.containers}
    if r in (1, 4):
        assert kinds == {"run"}
    path, _ = assert_same(rows)
    if r <= 6 or r >= 40:       # rows 17 and 18 sit at the gate's edge
        assert path == ("dense" if r <= 6 else "sparse")
    assert_same(rows, sparse=False)


@pytest.mark.parametrize("n", [40, packed._POOLED, packed._POOLED + 1, 3334])
def test_unusual_buffers_are_made_plain_first(n):
    """A strided or foreign-typed buffer is copied to its kind's plain
    form before its address is taken or it joins the pool of small
    arrays (the call reads raw memory)."""
    vals = np.arange(0, 6 * n, 3, dtype=np.uint64)[::2]
    assert len(vals) == n
    c = Container()
    c.array, c.n = vals, len(vals)
    row = Bitmap()
    row.keys.append(2)
    row.containers.append(c)
    assert_same([row, None])
    assert_same([row], sparse=False)


# -- the numpy pass alone -----------------------------------------------------

def dense_oracle(cols_by_row) -> np.ndarray:
    out = np.zeros((len(cols_by_row), W), dtype=np.uint32)
    for t, cols in enumerate(cols_by_row):
        for col in ([] if cols is None else np.asarray(cols).tolist()):
            out[t, col // 32] |= np.uint32(1 << (col % 32))
    return out


@pytest.mark.parametrize("name", ["mixed_sparse", "mixed_dense", "g_33"])
def test_without_the_library_the_numpy_pass_answers(numpy_only, name):
    cols, kinds, _, _ = CASES[name]
    rows = [None if c is None else row_of(c, k)
            for c, k in zip(cols, kinds or [None] * len(cols))]
    _, block, taken = packed.pack_slab(rows, sparse=False)
    assert np.array_equal(block, dense_oracle(cols))
    assert taken == sum(len(r.keys) for r in rows if r is not None)


# -- a served fill says which pass packed it ----------------------------------

@pytest.fixture
def server(tmp_path, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_MESH_MIN_SLICES", "1")
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    monkeypatch.setattr(residency, "_device_cache",
                        residency.DeviceBlockCache(1 << 30))
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    try:
        s.executor._mesh = mesh_mod.make_mesh(1)
        yield s
    finally:
        s.close()


def _call(s, method, path, body=b""):
    conn = http.client.HTTPConnection(s.host, timeout=30)
    try:
        conn.request(method, path, body)
        resp = conn.getresponse()
        data = resp.read()
        assert resp.status == 200, data
        return json.loads(data or b"{}"), resp
    finally:
        conn.close()


def _served_fills(s):
    """Two slices, row 0 thick (a dense fill), rows 1 and 2 thin (sparse
    fills); three cold Counts. Returns the cache's counters and the
    ``pack`` spans of the traced one."""
    rng = np.random.default_rng(7)
    bits = {0: rng.choice(2 * SLICE_WIDTH, 60000, replace=False),
            1: rng.choice(2 * SLICE_WIDTH, 300, replace=False),
            2: rng.choice(2 * SLICE_WIDTH, 200, replace=False)}
    client = Client(s.host, timeout=60.0)
    try:
        client.create_index("i")
        client.create_frame("i", "f", {})
        client.import_arrays(
            "i", "f",
            np.concatenate([np.full(len(c), r, dtype=np.uint64)
                            for r, c in bits.items()]),
            np.concatenate(list(bits.values())).astype(np.uint64))
    finally:
        client.close()
    want = len(np.intersect1d(bits[0], bits[1]))
    body, resp = _call(s, "POST", "/index/i/query?trace=1",
                       b'Count(Intersect(Bitmap(frame="f", rowID=0),'
                       b' Bitmap(frame="f", rowID=1)))')
    assert body["results"] == [want]
    qid = resp.getheader("X-Pilosa-Query-Id")
    body, _ = _call(s, "POST", "/index/i/query",
                    b'Count(Intersect(Bitmap(frame="f", rowID=1),'
                    b' Bitmap(frame="f", rowID=2)))')
    assert body["results"] == [len(np.intersect1d(bits[1], bits[2]))]
    events = _call(s, "GET", f"/debug/traces/{qid}")[0]["traceEvents"]
    packs = [e["args"] for e in events if e["name"] == "pack"]
    cache = _call(s, "GET", "/debug/vars")[0]["deviceBlockCache"]
    return cache, packs


def test_a_served_fill_counts_the_native_pack(server):
    cache, packs = _served_fills(server)
    assert cache["fills"] == 3 and cache["fillsDense"] == 1
    assert cache["packsNative"] == cache["fills"]
    assert sorted(p["path"] for p in packs) == ["dense", "sparse"]
    assert all(p["native"] is True for p in packs)


def test_without_the_library_no_fill_counts_as_native(server, numpy_only):
    cache, packs = _served_fills(server)
    assert cache["fills"] == 3 and cache["fillsDense"] == 1
    assert cache["packsNative"] == 0
    assert all(p["native"] is False for p in packs)


# -- mapped containers while the fragment swaps its file ----------------------

def test_a_pass_over_mapped_containers_while_the_fragment_snapshots(
        tmp_path):
    """The pass reads mapped buffers by address with the interpreter let
    go of. A writer thread keeps snapshotting the fragment (every 16th
    swap drops the old map and maps the new file) and collecting, while
    the packer collects rows by reference and packs them: every pack is
    the rows' bits, none reads an unmapped or freed buffer."""
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0)
    f.open()
    rng = np.random.default_rng(11)
    cols = {0: rng.choice(SLICE_WIDTH, 70000, replace=False),   # bitmaps
            1: rng.choice(SLICE_WIDTH, 900, replace=False),     # arrays
            2: SHORT_RUNS}                                      # runs
    for row, c in cols.items():
        f.import_bits(np.full(len(c), row, dtype=np.uint64),
                      c.astype(np.uint64))
    f.storage.optimize()
    f.snapshot()
    f.close()
    f.open()
    want = dense_oracle([cols[0], cols[1], cols[2]])
    assert {c.mapped for c in f.storage.containers} == {True}
    assert {c.kind() for c in f.storage.containers} == {
        "array", "bitmap", "run"}
    stop, errors = threading.Event(), []

    def writer():
        try:
            col = 0
            while not stop.is_set():
                f.set_bit(5, col)       # a row nobody packs
                col += 1
                f.snapshot()
                gc.collect()
        except BaseException as e:     # noqa: BLE001 - reported below
            errors.append(e)

    t = threading.Thread(target=writer)
    t.start()
    try:
        i = 0
        while i < 30 or f._snapshot_n < 20:     # past a remap, at least
            i += 1
            assert i < 5000 and not errors
            rows = [f.row_containers(r) for r in (0, 1, 2)]
            _, block, taken = packed.pack_slab(rows, sparse=False)
            del rows
            assert np.array_equal(block, want), i
            sparse, block, _ = packed.pack_slab(
                [f.row_containers(r) for r in (1, 2)])
            assert sparse is not None and np.array_equal(
                numpy_pack([f.row_containers(r) for r in (1, 2)])[0][1],
                sparse[1])
    finally:
        stop.set()
        t.join(60)
    assert not t.is_alive() and not errors
    f.close()
