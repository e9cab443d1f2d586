"""The one-pass pack of a residency fill (ISSUE 35): every container of
every fragment of a slab goes through ``ops.packed.pack_slab`` once.

The oracle shares no code with it: column positions become a dense
``uint32[T, 32768]`` by plain bit arithmetic, a sparse result is
densified here on the host slot by slot, and the gate's width G is
counted from the oracle's own words.
"""

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.ops import packed
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import residency
from pilosa_tpu.sched import QueryContext
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.storage.fragment import Fragment
from pilosa_tpu.storage.roaring import Bitmap, Container, values_to_runs

W = packed.WORDS_PER_SLICE
GROUPS = W // 128


# -- the oracle ---------------------------------------------------------------

def dense_oracle(rows: list) -> np.ndarray:
    """Column ids (or None) a slice-row → ``uint32[T, 32768]``."""
    out = np.zeros((len(rows), W), dtype=np.uint32)
    for t, cols in enumerate(rows):
        for col in ([] if cols is None else np.asarray(cols).tolist()):
            out[t, col // 32] |= np.uint32(1 << (col % 32))
    return out


def oracle_width(block: np.ndarray) -> int:
    """G: the fullest 128-word group's set words, as a power of two."""
    fullest = int((block.reshape(-1, 128) != 0).sum(axis=1).max())
    return 1 << (max(fullest, 1) - 1).bit_length()


def densify_on_host(lanes: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Bucketed lanes and values → dense words, a slot at a time; the
    layout the kernel counts on is asserted on the way: a group's set
    words first, in ascending lane order, zero padding behind them."""
    n, groups, width = lanes.shape
    assert lanes.dtype == vals.dtype == np.uint32 and vals.shape == lanes.shape
    used = vals != 0
    assert not (~used[..., :-1] & used[..., 1:]).any()
    assert not lanes[~used].any()
    ordered = lanes[..., :-1].astype(np.int64) < lanes[..., 1:]
    assert (ordered | ~used[..., 1:]).all()
    out = np.zeros((n, groups * 128), dtype=np.uint32)
    for g in range(width):
        t, s = np.nonzero(used[..., g])
        assert not out[t, s * 128 + lanes[t, s, g]].any()
        out[t, s * 128 + lanes[t, s, g]] = vals[t, s, g]
    return out


# -- rows of chosen container kinds -------------------------------------------

def row_of(cols, kinds=None) -> Bitmap:
    """A row's containers keyed 0..15, as ``Fragment.row_containers``
    hands them: ``kinds`` maps a container key to ``bitmap`` or ``run``
    (an array otherwise, whatever its cardinality)."""
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


RNG = np.random.default_rng(35)
THIN = RNG.choice(SLICE_WIDTH, 900, replace=False)          # G = 2..4
THICK = RNG.choice(1 << 16, 30000, replace=False)           # a full container
LONG_RUN = np.arange(70000, 76000)                          # 188 words
SHORT_RUNS = np.concatenate([np.arange(s, s + 40)
                             for s in (100, 9000, 200000, 900100)])
CLUSTERED = np.concatenate([                    # 5,120 bits in 160 words
    (g * 128 + np.arange(10))[:, None] * 32 + np.arange(32)
    for g in range(16)]).ravel() + 5 * (1 << 16)

CASES = {
    # name: (columns a slice-row or None, kinds a slice-row, path, G)
    "arrays_g1": ([spread(1), spread(1, group=200)], None, "sparse", 1),
    "arrays_g4": ([spread(4), spread(3), None], None, "sparse", 4),
    "arrays_g32": ([spread(32, first_bit=31), spread(5)], None,
                   "sparse", 32),
    "arrays_g33_is_dense": ([spread(33), spread(5)], None, "dense", 64),
    "arrays_g64_is_dense": ([spread(5), spread(64)], None, "dense", 64),
    "arrays_thin_random": ([THIN, THIN[:300] + 1], None, "sparse", None),
    "bitmaps_only": ([THICK, THICK + (7 << 16)], [{0: "bitmap"},
                                                  {7: "bitmap"}],
                     "dense", 128),
    "runs_alone": ([LONG_RUN, LONG_RUN + 65536], [{1: "run"}, {2: "run"}],
                   "dense", 128),
    "runs_that_pass_the_gate": (
        [SHORT_RUNS, None], [{0: "run", 3: "run", 13: "run"}, None],
        "sparse", 2),
    "bitmap_that_passes_the_gate": ([CLUSTERED], [{5: "bitmap"}],
                                    "sparse", 16),
    "mixture_in_one_row_dense": (
        [np.concatenate([THIN, THICK, LONG_RUN + (1 << 16)])],
        [{0: "bitmap", 2: "run"}], "dense", 128),
    "mixture_in_one_row_sparse": (
        [np.concatenate([THIN, CLUSTERED, SHORT_RUNS])],
        [{5: "bitmap", 13: "run"}], "sparse", None),
    "absent_fragments_and_padding": (
        [None, spread(2), None, spread(7, group=255), None, None], None,
        "sparse", 8),
    "all_empty": ([None, [], None], None, "sparse", 1),
    "last_container_last_group": (
        [spread(3, group=GROUPS - 1), [SLICE_WIDTH - 1, 15 << 16]],
        [None, {15: "bitmap"}], "sparse", 4),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_one_pass_equals_the_oracle(name):
    cols, kinds, path, width = CASES[name]
    kinds = kinds or [None] * len(cols)
    rows = [None if c is None else row_of(c, k)
            for c, k in zip(cols, kinds)]
    want = dense_oracle(cols)
    if width is not None:
        assert oracle_width(want) == width
    sparse, block, taken = packed.pack_slab(rows)
    assert taken == sum(len(r.keys) for r in rows if r is not None)
    if path == "dense":
        assert sparse is None and block.dtype == np.uint32
        assert np.array_equal(block, want)
    else:
        assert block is None
        lanes, vals = sparse
        assert lanes.shape == (len(rows), GROUPS, oracle_width(want))
        assert np.array_equal(densify_on_host(lanes, vals), want)
    # a server told to upload dense blocks only skips the gate
    _, forced, _ = packed.pack_slab(rows, sparse=False)
    assert np.array_equal(forced, want)


# -- through the residency builders, over real fragments ----------------------

@pytest.fixture
def cache(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    c = residency.DeviceBlockCache(1 << 30)
    monkeypatch.setattr(residency, "_device_cache", c)
    return c


@pytest.fixture
def fragments(tmp_path):
    """Five slices, the third absent: rows 0 (arrays), 1 (a bitmap
    container in slice 0), 2 (runs after ``optimize``) and 7 (empty)."""
    made, cols = [], {}
    for si in (0, 1, None, 3, 4):
        if si is None:
            made.append(None)
            continue
        f = Fragment(str(tmp_path / f"frag{si}"), "i", "f", "standard", si)
        f.open()
        rng = np.random.default_rng(si)
        cols[si, 0] = rng.choice(SLICE_WIDTH, 400, replace=False)
        cols[si, 1] = (rng.choice(1 << 16, 9000, replace=False) if si == 0
                       else rng.choice(SLICE_WIDTH, 50, replace=False))
        cols[si, 2] = np.arange(1000, 1000 + 100 * (si + 1)) + (si << 16)
        for row in (0, 1, 2):
            f.import_bits(np.full(len(cols[si, row]), row, dtype=np.uint64),
                          cols[si, row].astype(np.uint64)
                          + np.uint64(si * SLICE_WIDTH))
        f.storage.optimize()
        made.append(f)
    kinds = {c.kind() for f in made if f is not None
             for c in f.storage.containers}
    assert kinds == {"array", "bitmap", "run"}
    yield made, cols
    for f in made:
        if f is not None:
            f.close()


def _want(cols, slices, rows, padded):
    out = np.zeros((padded, len(rows), W), dtype=np.uint32)
    for si in slices:
        out[si] = dense_oracle([cols.get((si, r)) for r in rows])
    return out


@pytest.mark.parametrize("row, path", [(0, "sparse"), (1, "dense"),
                                       (2, "sparse"), (7, "sparse")])
def test_leaf_slab_over_absent_fragments_and_bucket_padding(
        cache, fragments, row, path):
    frags, cols = fragments
    mesh = mesh_mod.make_mesh(1)
    ctx = QueryContext(pql="fill")
    ctx.trace = []
    with sched_context.use(ctx):
        slab = residency.leaf_slab(mesh, ("leaf", row), frags, row)
    assert slab.shape == (8, W)                 # 5 slices, bucket 8
    assert np.array_equal(np.asarray(slab),
                          _want(cols, (0, 1, 3, 4), (row,), 8)[:, 0])
    assert cache.fills_dense == (path == "dense")
    spans = {name: tags for name, _, _, tags, _ in ctx.stage_spans()}
    assert spans["pack"]["path"] == spans["upload"]["path"] == path
    assert spans["pack"]["containers"] == sum(
        len(f.row_containers(row).keys) for f in frags if f is not None)
    assert "containers" not in spans["upload"]


def test_candidate_block_of_three_rows_by_five_slices(cache, fragments):
    frags, cols = fragments
    mesh = mesh_mod.make_mesh(1)
    block = residency.candidate_block(mesh, ("block",), frags, (2, 7, 0))
    assert block.shape == (8, 3, W)
    assert np.array_equal(np.asarray(block),
                          _want(cols, (0, 1, 3, 4), (2, 7, 0), 8))
    assert cache.fills_dense == 0
    dense = residency.candidate_block(mesh, ("block2",), frags, (0, 1, 2))
    assert np.array_equal(np.asarray(dense),
                          _want(cols, (0, 1, 3, 4), (0, 1, 2), 8))
    assert cache.fills_dense == 1


# -- the lock contract --------------------------------------------------------

@pytest.mark.parametrize("row, kind", [(0, "array"), (1, "bitmap"),
                                       (2, "run")])
def test_a_write_between_collection_and_pass_changes_neither(
        fragments, row, kind):
    """Only references leave the fragment lock. A ``SetBit`` (through
    the compiled mutate path where it is built) and a ``ClearBit`` into
    a collected container must copy, not write in place: the slab packed
    afterwards is the row as collected, and the fragment answers with
    the write."""
    frags, cols = fragments
    f = frags[0]
    before = np.sort(cols[0, row])
    collected = f.row_containers(row)
    assert kind in {c.kind() for c in collected.containers}
    hit = next(k for k, c in zip(collected.keys, collected.containers)
               if c.kind() == kind)
    inside = before[before >> 16 == hit]
    new = next(c for c in range(int(inside[0]), int(inside[0]) + 70000)
               if c not in set(inside.tolist()))
    assert new >> 16 == hit
    assert f.set_bit(row, new) and f.clear_bit(row, int(inside[0]))
    _, block, _ = packed.pack_slab([collected], sparse=False)
    assert np.array_equal(block, dense_oracle([before]))
    after = np.sort(np.append(before[before != inside[0]], new))
    assert np.array_equal(np.sort(f.row(row).bits()), after)
    _, again, _ = packed.pack_slab([f.row_containers(row)], sparse=False)
    assert np.array_equal(again, dense_oracle([after]))
