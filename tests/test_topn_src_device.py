"""The with-source TopN answered from one device program
(``Executor._topn_src_device``) against the plain reference of the
benchmark's TopN cell (``cellbench/refs/topn_sparse.py``, which shares
no code with the program) on seeded data, on the CPU: ids, counts and
order, the cache's trim and its ties, a write between two reads, the
fallback where the frame's form does not fit, the counters."""

from __future__ import annotations

import numpy as np
import pytest

from cellbench.refs import topn_sparse
from pilosa_tpu.executor import Executor
from pilosa_tpu.models.frame import FrameOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import residency

W = 1 << 20


def _config(**kw) -> dict:
    cfg = dict(n_slices=3, n_rows=2000, n_cols=3 * W - 12345,
               cache_size=50000, d0=0.02, zipf_s=1.0)
    cfg.update(kw)
    return cfg


def _load(tmp_path, cfg: dict, seed: int):
    ref = topn_sparse.Reference(seed, cfg)
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    idx = holder.create_index_if_not_exists("i")
    frame = idx.create_frame_if_not_exists(
        "f", FrameOptions(cache_type="ranked",
                          cache_size=cfg["cache_size"]))
    for s in range(cfg["n_slices"]):
        frame.import_slice_positions(s, ref.pos[s])
    return ref, holder


def _device(holder, n_dev: int = 1) -> Executor:
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex._mesh = mesh_mod.make_mesh(n_dev)
    return ex


def _topn(ex: Executor, row: int, n: int) -> list[dict]:
    q = f'TopN(Bitmap(frame="f", rowID={row}), frame="f", n={n})'
    return [{"id": p.id, "count": p.count}
            for p in ex.execute("i", q)[0]]


CASES = {
    # name: (config changes, sources, n values)
    "plain": ({}, (0, 1, 7, 60, 300), (1, 10, 100, 1000)),
    # every row of one density: counts tie everywhere
    "ties": ({"n_rows": 600, "d0": 0.0004, "zipf_s": 0.0},
             (0, 5, 599), (1, 25, 250)),
    # sources of a few bits a slice
    "sparse-source": ({}, (900, 1500, 1999), (10, 1000)),
    # a ranked cache of 150 rows a fragment: the trim engages
    "cache-trim": ({"cache_size": 150}, (0, 3, 40, 700), (5, 100, 1000)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("n_dev", [1, 4])
def test_served_topn_is_the_reference(tmp_path, case, n_dev):
    changes, sources, ns = CASES[case]
    cfg = _config(**changes)
    ref, holder = _load(tmp_path, cfg, seed=2**31 + 7)
    try:
        ex = _device(holder, n_dev)
        for row in sources:
            for n in ns:
                assert _topn(ex, row, n) == ref.topn_src(row, n), (row, n)
        asked = len(sources) * len(ns)
        assert ex.topn_src["srcDevice"] == asked
        assert ex.topn_src["srcHost"] == 0
        assert ex.topn_src["candidates"] > 0
        assert ex.device_fallbacks == 0
        if case == "cache-trim":
            # A second ranking over the same counts is the first: the
            # rows tied at the trimmed cache's threshold stay in it.
            for s in range(cfg["n_slices"]):
                holder.fragment("i", "f", "standard", s).recalculate_cache()
            for row in sources:
                assert _topn(ex, row, 100) == ref.topn_src(row, 100)
    finally:
        holder.close()


def test_a_write_between_two_reads_retires_the_frame_form(tmp_path):
    cfg = _config()
    ref, holder = _load(tmp_path, cfg, seed=11)
    try:
        ex = _device(holder)
        host = Executor(holder, host="local", use_mesh=False)
        cache = residency.device_cache()
        builds = cache.frame_builds
        assert _topn(ex, 3, 50) == ref.topn_src(3, 50)
        assert _topn(ex, 3, 50) == ref.topn_src(3, 50)
        assert cache.frame_builds == builds + 1     # built once, kept
        assert cache.frames()[0] >= 1
        # Set row 0 in every column of row 3 in slice 0 that it lacks:
        # their intersection there becomes row 3's count there.
        cols = ref.row_cols(0, 3)
        new = np.setdiff1d(cols, ref.row_cols(0, 0))
        retired = cache.frames_retired
        for c in new.tolist():
            ex.execute("i", f'SetBit(frame="f", rowID=0, columnID={c})')
        assert cache.frames_retired == retired + 1
        got = _topn(ex, 3, 50)
        assert got == _topn(host, 3, 50)
        assert got != ref.topn_src(3, 50)
        assert {"id": 0, "count": len(cols)
                + int(ref.src_counts(1, 3)[0]
                      + ref.src_counts(2, 3)[0])} in got
        assert cache.frame_builds == builds + 2
        assert ex.topn_src["srcDevice"] == 3
    finally:
        holder.close()


def test_the_frame_form_is_sharded_by_slice(tmp_path):
    """On four devices each holds the offsets and positions of its own
    slices only (6 slices padded to 8: two a device), and its positions
    are its rows' columns, the local slice in the high bits."""
    cfg = _config(n_slices=6, n_cols=6 * W - 999)
    ref, holder = _load(tmp_path, cfg, seed=14)
    try:
        ex = _device(holder, 4)
        assert _topn(ex, 4, 100) == ref.topn_src(4, 100)
        cache = residency.device_cache()
        offsets, positions = [e for e in cache._lru.values()
                              if isinstance(e, tuple)][-1]
        assert offsets.shape[0] == 8 and positions.shape[0] == 4
        for arr in (offsets, positions):
            shards = sorted(arr.addressable_shards,
                            key=lambda sh: sh.index[0].start)
            assert len({sh.device for sh in shards}) == 4
            assert [sh.data.shape[0] for sh in shards] \
                == [arr.shape[0] // 4] * 4
        off, pos = np.asarray(offsets), np.asarray(positions)
        held = [0] * 4
        for s in range(cfg["n_slices"]):
            dev, local = divmod(s, 2)
            for row in (0, 1, 17, cfg["n_rows"] - 1):
                got = pos[dev, off[s, row]:off[s, row + 1]]
                assert got.tolist() == (
                    (local << 20) | ref.row_cols(s, row)).tolist()
            held[dev] += len(ref.pos[s])
        assert off[6:].tolist() == [[held[3]] * off.shape[1]] * 2
        assert positions.shape[1] >= max(held)
        assert positions.shape[1] < 1.04 * max(held)
    finally:
        holder.close()


def test_a_frame_form_that_does_not_fit_leaves_the_read_to_the_host(
        tmp_path, monkeypatch):
    cfg = _config()
    ref, holder = _load(tmp_path, cfg, seed=12)
    try:
        ex = _device(holder)
        monkeypatch.setattr(Executor, "_topn_resident_bytes",
                            staticmethod(lambda: 1 << 10))
        builds = residency.device_cache().frame_builds
        for row in (0, 9, 400):
            assert _topn(ex, row, 100) == ref.topn_src(row, 100)
        assert ex.topn_src["srcDevice"] == 0
        assert ex.topn_src["srcHost"] == 3
        assert residency.device_cache().frame_builds == builds
    finally:
        holder.close()


def test_other_topn_forms_keep_their_paths(tmp_path):
    """Thresholds, Tanimoto, explicit ids and the sourceless form are
    not the device leg's; their answers stay the host path's."""
    cfg = _config()
    _, holder = _load(tmp_path, cfg, seed=13)
    try:
        ex = _device(holder)
        host = Executor(holder, host="local", use_mesh=False)
        for q in ('TopN(Bitmap(frame="f", rowID=2), frame="f", n=20,'
                  ' threshold=3)',
                  'TopN(Bitmap(frame="f", rowID=2), frame="f", n=20,'
                  ' tanimotoThreshold=10)',
                  'TopN(Bitmap(frame="f", rowID=2), frame="f",'
                  ' ids=[0, 1, 2, 3])',
                  'TopN(frame="f", n=20)'):
            want = host.execute("i", q)[0]
            assert [(p.id, p.count) for p in ex.execute("i", q)[0]] \
                == [(p.id, p.count) for p in want], q
        assert ex.topn_src["srcDevice"] == 0
    finally:
        holder.close()


def test_the_reference_walk_keeps_the_superset():
    """The reference's walk on a fixed ranking: the first n rows that
    meet the source, then every row up to the first whose cached count
    is below the least of those, that meets it as well."""
    cached = np.array([9, 8, 8, 7, 5, 4, 4, 2, 1])
    src = np.array([3, 0, 2, 2, 0, 3, 1, 2, 5])
    assert topn_sparse.Reference.walk(cached, src, 2).tolist() \
        == [0, 2, 3, 5, 7]
    assert topn_sparse.Reference.walk(cached, src, 9).tolist() \
        == [0, 2, 3, 5, 6, 7, 8]
