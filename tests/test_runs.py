"""Run containers (ISSUE 7): the third container type end-to-end.

Randomized differential legs hold the algebra to the pure-python set
model bit-for-bit across every operand-kind pair, the serialization
legs prove the 12347 runs cookie round-trips through snapshot + WAL
replay + mmap + the fragment lifecycle, the optimize() legs pin the
cardinality-adaptive selection thresholds from the Roaring papers, and
the device legs prove run-backed fragments decode to the same
bit-plane slabs as their array/bitmap-backed twins.
"""

import io
import os

import numpy as np
import pytest

from pilosa_tpu.storage import native, roaring
from pilosa_tpu.storage.roaring import (ARRAY_MAX_SIZE, RUN_MAX_SIZE,
                                        Bitmap, Container, Op,
                                        runs_to_values, runs_to_words,
                                        values_to_runs)

KINDS = ("array", "bitmap", "run")


def make_container(kind: str, vals) -> Container:
    """A container of the given kind holding exactly ``vals``."""
    vals = np.asarray(sorted(vals), dtype=np.uint32)
    if kind == "run":
        return Container.from_runs(values_to_runs(vals))
    if kind == "bitmap":
        return Container.from_bitmap(
            runs_to_words(values_to_runs(vals)).copy())
    return Container.from_array(vals)


def runny_set(rng, span=3000, n_points=400, n_runs=3, run_len=200):
    """A value set mixing isolated points and dense intervals."""
    out = set(rng.integers(0, span, size=int(rng.integers(0, n_points)))
              .tolist())
    for _ in range(int(rng.integers(0, n_runs + 1))):
        s = int(rng.integers(0, span))
        out |= set(range(s, min(s + run_len, 1 << 16)))
    return out


class TestRunHelpers:
    def test_values_runs_words_roundtrip_randomized(self):
        rng = np.random.default_rng(1)
        for _ in range(100):
            vals = np.asarray(sorted(runny_set(rng, span=1 << 16)),
                              np.uint32)
            runs = values_to_runs(vals)
            assert np.array_equal(runs_to_values(runs), vals)
            assert np.array_equal(
                roaring.bitmap_words_to_values(runs_to_words(runs)),
                vals)

    def test_run_count_words_matches_array_form(self):
        rng = np.random.default_rng(2)
        for _ in range(60):
            vals = np.asarray(sorted(runny_set(rng, span=1 << 16)),
                              np.uint32)
            if not len(vals):
                continue
            words = runs_to_words(values_to_runs(vals))
            assert (roaring.run_count_words(words)
                    == roaring.run_count_array(vals))

    def test_run_crossing_word_boundaries(self):
        vals = np.arange(60, 70, dtype=np.uint32)  # spans word 0→1
        words = runs_to_words(values_to_runs(vals))
        assert np.array_equal(roaring.bitmap_words_to_values(words),
                              vals)


class TestRunContainerPointOps:
    def test_randomized_add_remove_vs_set_model(self):
        rng = np.random.default_rng(3)
        c = make_container("run", range(100, 400))
        model = set(range(100, 400))
        for _ in range(800):
            v = int(rng.integers(0, 600))
            if rng.random() < 0.5:
                assert c.add(v) == (v not in model)
                model.add(v)
            else:
                assert c.remove(v) == (v in model)
                model.discard(v)
            assert c.n == len(model)
        c.check()
        assert set(c.values().tolist()) == model

    def test_add_merges_adjacent_runs(self):
        c = make_container("run", [1, 2, 4, 5])
        assert c.add(3)
        c.check()
        assert (len(c.runs) - 1) >> 1 == 1

    def test_remove_splits_run(self):
        c = make_container("run", range(10, 20))
        assert c.remove(15)
        c.check()
        assert (len(c.runs) - 1) >> 1 == 2

    def test_contains_rank_count_range(self):
        c = make_container("run", list(range(100, 200)) + [500])
        assert c.contains(150) and not c.contains(200)
        assert c.rank(150) == 51
        assert c.count_range(150, 520) == 51
        assert c.rank(500) == 101

    def test_degrading_run_converts_at_bound(self):
        # Alternating adds fragment the run container; past
        # RUN_MAX_SIZE runs it must convert to a legacy kind.
        c = make_container("run", [0])
        for v in range(2, 2 * (RUN_MAX_SIZE + 10), 2):
            c.add(v)
        c.check()
        assert not c.is_run()
        assert c.n == RUN_MAX_SIZE + 10


class TestAlgebraDifferential:
    """Every op × every operand-kind pair vs the set model."""

    OPS = {
        "intersect": (roaring._intersect, lambda a, b: a & b),
        "union": (roaring._union, lambda a, b: a | b),
        "difference": (roaring._difference, lambda a, b: a - b),
        "xor": (roaring._xor, lambda a, b: a ^ b),
    }

    @pytest.mark.parametrize("ka", KINDS)
    @pytest.mark.parametrize("kb", KINDS)
    def test_container_ops_bit_for_bit(self, ka, kb):
        rng = np.random.default_rng(hash((ka, kb)) % (1 << 32))
        for trial in range(40):
            A = runny_set(rng)
            B = runny_set(rng)
            for name, (fn, model_fn) in self.OPS.items():
                out = fn(make_container(ka, A), make_container(kb, B))
                out.check()
                assert set(out.values().tolist()) == model_fn(A, B), \
                    (name, trial)
            got = roaring._intersection_count(make_container(ka, A),
                                              make_container(kb, B))
            assert got == len(A & B), trial

    def test_empty_and_full_extremes(self):
        full = set(range(1 << 16))
        for ka in KINDS:
            for kb in KINDS:
                for A, B in ((set(), full), (full, set()), (full, full)):
                    a, b = make_container(ka, A), make_container(kb, B)
                    assert (set(roaring._intersect(a, b).values()
                                .tolist()) == (A & B))
                    assert (set(roaring._union(a, b).values()
                                .tolist()) == (A | B))

    def test_bitmap_level_ops_with_mixed_kinds(self):
        """Whole-bitmap algebra over containers of all three kinds in
        one keyspace, vs the set model."""
        rng = np.random.default_rng(9)
        for trial in range(15):
            A, B = set(), set()
            ba, bb = Bitmap(), Bitmap()
            for key in range(4):
                base = key << 16
                sa = runny_set(rng, span=1 << 16)
                sb = runny_set(rng, span=1 << 16)
                A |= {base + v for v in sa}
                B |= {base + v for v in sb}
            ba.add_many(np.array(sorted(A), dtype=np.uint64))
            bb.add_many(np.array(sorted(B), dtype=np.uint64))
            ba.optimize()
            if trial % 2:
                bb.optimize()
            assert set(ba.intersect(bb).values().tolist()) == A & B
            assert set(ba.union(bb).values().tolist()) == A | B
            assert set(ba.difference(bb).values().tolist()) == A - B
            assert set(ba.xor(bb).values().tolist()) == A ^ B
            assert ba.intersection_count(bb) == len(A & B)

    def test_run_op_kinds_feed_counters(self):
        before = roaring.op_counts()
        a = make_container("run", range(100))
        b = make_container("run", range(50, 150))
        roaring._intersect(a, b)
        roaring._union(a, make_container("array", [1, 7]))
        roaring._difference(a, make_container("bitmap", range(0, 60)))
        after = roaring.op_counts()
        assert (after[("intersect", "run_run")]
                == before[("intersect", "run_run")] + 1)
        assert (after[("union", "run_array")]
                == before[("union", "run_array")] + 1)
        assert (after[("difference", "run_bitmap")]
                == before[("difference", "run_bitmap")] + 1)

    def test_galloping_skewed_intersection(self):
        """Lopsided sorted-array operands take the searchsorted
        (galloping) strategy — results identical to the merge path."""
        rng = np.random.default_rng(12)
        big = np.unique(rng.integers(0, 1 << 16, size=20000)
                        ).astype(np.uint32)
        small = np.unique(rng.choice(big, size=8)).astype(np.uint32)
        a, b = Container.from_array(small), Container.from_array(big)
        assert roaring._skewed(small, big)
        out = roaring._intersect(a, b)
        assert np.array_equal(out.values(), small)
        assert roaring._intersection_count(a, b) == len(small)


class TestOptimizeSelection:
    """The cardinality-adaptive thresholds: smallest of 4n / 8192 /
    2+4R wins (arXiv:1603.06549 §3)."""

    def test_one_long_run_wins_over_bitmap(self):
        c = make_container("bitmap", range(10000))
        assert c.optimize() == "run"
        assert c.size_bytes() == 6

    def test_isolated_values_stay_array(self):
        c = make_container("array", range(0, 100, 2))
        assert c.optimize() == "array"

    def test_dense_random_stays_bitmap(self):
        rng = np.random.default_rng(5)
        vals = np.unique(rng.integers(0, 1 << 16, size=30000))
        c = make_container("bitmap", vals)
        assert c.optimize() == "bitmap"

    def test_exact_boundary_prefers_legacy(self):
        # 4 values in 2 runs: run block 2+8=10 > array 16? No: 10 < 16
        # → run. 3 isolated values: run 2+12=14 > array 12 → array.
        assert make_container("array", [1, 2, 10, 11]).optimize() == "run"
        assert make_container("array", [1, 10, 20]).optimize() == "array"

    def test_bitmap_boundary_against_runs(self):
        # n > ARRAY_MAX_SIZE: legacy = 8192 bytes; R = 2047 runs →
        # 2+4*2047 = 8190 < 8192 → run; R = 2048 → 8194 → bitmap.
        vals = []
        for i in range(2047):
            vals.extend((i * 8, i * 8 + 1, i * 8 + 2))
        c = make_container("bitmap", vals)
        assert c.n > ARRAY_MAX_SIZE
        assert c.optimize() == "run"
        vals2 = []
        for i in range(2048):
            vals2.extend((i * 8, i * 8 + 1, i * 8 + 2))
        c2 = make_container("bitmap", vals2)
        assert c2.optimize() == "bitmap"

    def test_bitmap_optimize_reports_kinds(self):
        b = Bitmap()
        b.add_many(np.arange(20000, dtype=np.uint64))          # run
        b.add_many((1 << 16) * 4 + np.arange(0, 20000, 2,
                                             dtype=np.uint64))  # bitmap
        b.add_many((1 << 16) * 8 + np.arange(0, 300, 3,
                                             dtype=np.uint64))  # array
        kinds = b.optimize()
        assert kinds == {"array": 1, "bitmap": 1, "run": 1}
        stats = b.container_stats()
        assert stats["counts"] == {"array": 1, "bitmap": 1, "run": 1}
        assert stats["bytes"]["run"] == 6
        assert stats["intervals"]["run"] == 1


class TestSerializationAndWal:
    def test_snapshot_roundtrip_randomized(self):
        rng = np.random.default_rng(6)
        for trial in range(10):
            b = Bitmap()
            model = set()
            for key in range(int(rng.integers(1, 5))):
                base = key << 16
                s = runny_set(rng, span=1 << 16)
                model |= {base + v for v in s}
            b.add_many(np.array(sorted(model), dtype=np.uint64))
            b.optimize()
            data = b.marshal()
            for mapped in (False, True):
                back = Bitmap.unmarshal(memoryview(data), mapped=mapped)
                back.check()
                assert set(back.values().tolist()) == model
                assert back.marshal() == data

    def test_wal_replay_over_runs_snapshot(self):
        b = Bitmap()
        b.add_many(np.arange(1000, 30000, dtype=np.uint64))
        b.optimize()
        assert b.containers[0].is_run()
        data = b.marshal()
        ops = (Op(roaring.OP_ADD, 30000).marshal()
               + Op(roaring.OP_REMOVE, 1500).marshal()
               + Op(roaring.OP_ADD, 99 << 16).marshal())
        back = Bitmap.unmarshal(memoryview(data + ops))
        model = (set(range(1000, 30001)) | {99 << 16}) - {1500}
        assert set(back.values().tolist()) == model
        assert back.op_n == 3

    def test_torn_tail_after_runs_snapshot(self):
        b = Bitmap()
        b.add_many(np.arange(0, 70000, dtype=np.uint64))
        b.optimize()
        data = b.marshal() + Op(roaring.OP_ADD, 5).marshal()[:7]
        back = Bitmap.unmarshal(memoryview(data),
                                tolerate_torn_tail=True)
        assert back.torn_bytes == 7
        assert back.count() == 70000

    def test_write_frozen_with_runs_falls_back_identically(self,
                                                           tmp_path):
        b = Bitmap()
        b.add_many(np.arange(500, 40000, dtype=np.uint64))
        b.add_many((1 << 20) + np.arange(0, 999, 3, dtype=np.uint64))
        b.optimize()
        frozen = b.freeze()
        assert frozen.has_runs
        buf = io.BytesIO()
        roaring.write_frozen(frozen, buf)
        assert buf.getvalue() == b.marshal()
        p = tmp_path / "snap"
        with open(p, "wb") as f:
            roaring.write_frozen(frozen, f)
        assert p.read_bytes() == b.marshal()

    def test_unmarshal_rejects_truncated_run_block(self):
        b = Bitmap()
        b.add_many(np.arange(100, 50000, dtype=np.uint64))
        b.optimize()
        data = b.marshal()
        with pytest.raises(ValueError, match="out of bounds"):
            Bitmap.unmarshal(memoryview(data[:-3]))


class TestBatchEngineOverRuns:
    """The native batch write engine (and its numpy fallback) must
    transparently upgrade run containers — identical results, WAL
    records only for genuinely changed bits."""

    @pytest.mark.parametrize("force_python", [False, True])
    def test_apply_batch_differential(self, force_python, monkeypatch):
        if force_python:
            monkeypatch.setattr(native, "available", lambda: False)
        rng = np.random.default_rng(8)
        b = Bitmap()
        b.add_many(np.arange(10, 30000, dtype=np.uint64))
        b.add_many((3 << 16) + np.arange(0, 220, 2, dtype=np.uint64))
        b.optimize()
        assert any(c.is_run() for c in b.containers)
        model = set(b.values().tolist())
        wal = io.BytesIO()
        b.op_writer = wal
        adds = np.unique(rng.integers(0, 5 << 16, size=4000)
                         ).astype(np.uint64)
        changed = b.apply_batch(adds, set=True)
        assert set(changed.tolist()) == set(adds.tolist()) - model
        model |= set(adds.tolist())
        rems = np.unique(rng.integers(0, 5 << 16, size=2500)
                         ).astype(np.uint64)
        changed = b.apply_batch(rems, set=False)
        assert set(changed.tolist()) == model & set(rems.tolist())
        model -= set(rems.tolist())
        assert set(b.values().tolist()) == model
        b.check()
        assert not any(c.is_run() for c in b.containers
                       if c.n)  # upgraded by the engine
        # WAL replays to the same state over the pre-batch snapshot.
        pre = Bitmap()
        pre.add_many(np.arange(10, 30000, dtype=np.uint64))
        pre.add_many((3 << 16) + np.arange(0, 220, 2, dtype=np.uint64))
        pre.optimize()
        back = Bitmap.unmarshal(memoryview(pre.marshal()
                                           + wal.getvalue()))
        assert set(back.values().tolist()) == model

    @pytest.mark.parametrize("force_python", [False, True])
    def test_batch_remove_oversized_run_keeps_invariant(
            self, force_python, monkeypatch):
        """A remove against a run container with n > ARRAY_MAX_SIZE
        must come back as a bitmap (or a <=4096 array), never an
        oversized array — the snapshot sizer maps n>4096 to a bitmap
        block, so that state serializes corrupt (review finding)."""
        if force_python:
            monkeypatch.setattr(native, "available", lambda: False)
        b = Bitmap()
        b.add_many(np.arange(0, 10000, dtype=np.uint64))
        b.optimize()
        assert b.containers[0].is_run() and b.containers[0].n == 10000
        changed = b.apply_batch(
            np.arange(0, 20, dtype=np.uint64), set=False)
        assert len(changed) == 20
        c = b.containers[0]
        assert c.n == 9980
        assert c.kind() == "bitmap"
        b.check()
        back = Bitmap.unmarshal(memoryview(b.marshal()))
        assert back.values().tolist() == list(range(20, 10000))
        # Removing below the boundary unpacks to array as usual.
        changed = b.apply_batch(
            np.arange(20, 6000, dtype=np.uint64), set=False)
        assert len(changed) == 5980
        assert b.containers[0].kind() == "array"
        b.check()
        back = Bitmap.unmarshal(memoryview(b.marshal()))
        assert back.values().tolist() == list(range(6000, 10000))

    def test_point_writes_through_bitmap_level(self):
        b = Bitmap()
        b.add_many(np.arange(0, 25000, dtype=np.uint64))
        b.optimize()
        assert b.containers[0].is_run()
        assert not b.add(5)           # already set, run membership
        assert b.remove(100)          # run split via Bitmap._remove
        assert b.add(100)
        assert b.contains(24999)
        assert b.count() == 25000
        assert b.max() == 24999
        assert b.rank(99) == 100


class TestFragmentEndToEnd:
    @pytest.fixture
    def holder(self, tmp_path):
        from pilosa_tpu.models.holder import Holder
        h = Holder(str(tmp_path))
        h.open()
        yield h
        h.close()

    def _run_heavy_frame(self, holder, name="f"):
        from pilosa_tpu import SLICE_WIDTH
        frame = holder.create_index_if_not_exists("r") \
            .create_frame_if_not_exists(name)
        rows, cols = [], []
        for row in range(3):
            # timestamp-view shape: long dense column ranges
            start = row * 10000
            span = np.arange(start, start + 30000, dtype=np.uint64)
            rows.append(np.full(len(span), row, dtype=np.uint64))
            cols.append(span % SLICE_WIDTH)
        frame.import_bits(np.concatenate(rows), np.concatenate(cols))
        return frame

    def test_import_produces_runs_and_snapshot_roundtrips(self, holder):
        frame = self._run_heavy_frame(holder)
        frag = holder.fragment("r", "f", "standard", 0)
        stats = frag.container_stats()
        assert stats["counts"]["run"] > 0, stats
        # WAL-first imports no longer force a synchronous snapshot;
        # take one so the on-disk cookie reflects the run containers.
        frag._join_snapshot()
        frag.snapshot()
        with open(frag.path, "rb") as f:
            assert int.from_bytes(f.read(4),
                                  "little") == roaring.COOKIE_RUNS
        row0 = set(frag.row(0).bits())
        # Point writes (WAL ops) on top of run containers, then reopen.
        frame.set_bit("standard", 0, 12)
        frame.clear_bit("standard", 0, 50)
        holder.close()
        holder.open()
        frag2 = holder.fragment("r", "f", "standard", 0)
        got = set(frag2.row(0).bits())
        assert got == (row0 | {12}) - {50}
        frag2.storage.check()

    def test_run_backed_rows_decode_to_same_device_words(self, holder):
        """pack_row / pack_slab over run containers equal the
        legacy-kind decode — the residency upload sees identical
        bit-plane slabs."""
        from pilosa_tpu import SLICE_WIDTH
        from pilosa_tpu.ops import packed
        self._run_heavy_frame(holder)
        frag = holder.fragment("r", "f", "standard", 0)
        assert frag.container_stats()["counts"]["run"] > 0
        legacy = Bitmap.unmarshal(memoryview(frag.storage.marshal()))
        for c in legacy.containers:  # force legacy kinds
            if c.runs is not None:
                c._run_to_legacy()
        for row in range(3):
            out_run = np.zeros(packed.WORDS_PER_SLICE, np.uint32)
            packed.pack_storage_row(frag.storage, row, out_run)
            out_legacy = np.zeros(packed.WORDS_PER_SLICE, np.uint32)
            packed.pack_storage_row(legacy, row, out_legacy)
            assert np.array_equal(out_run, out_legacy)
            for sparse in (True, False):
                (sr, br, nr), (sl, bl, nl) = (
                    packed.pack_slab([st.offset_range(
                        0, row * SLICE_WIDTH, (row + 1) * SLICE_WIDTH)],
                        sparse=sparse) for st in (frag.storage, legacy))
                assert nr == nl > 0
                if br is not None:
                    assert np.array_equal(br, bl)
                    assert np.array_equal(br[0], out_run)
                else:
                    assert np.array_equal(sr[0], sl[0])
                    assert np.array_equal(sr[1], sl[1])

    def test_resident_bytes_shrink_vs_legacy(self, holder):
        self._run_heavy_frame(holder)
        frag = holder.fragment("r", "f", "standard", 0)
        stats = frag.storage.container_stats()
        run_bytes = sum(stats["bytes"].values())
        legacy = Bitmap.unmarshal(memoryview(frag.storage.marshal()))
        for c in legacy.containers:
            if c.runs is not None:
                c._run_to_legacy()
        legacy_bytes = sum(legacy.container_stats()["bytes"].values())
        assert run_bytes < legacy_bytes / 4, (run_bytes, legacy_bytes)

    def test_queries_on_run_backed_fragment_match_legacy_mode(
            self, holder, monkeypatch, tmp_path):
        """The same import with the optimize pass disabled answers
        every query identically (host roaring algebra over runs)."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.storage import fragment as fragment_mod
        self._run_heavy_frame(holder)
        other_dir = tmp_path / "legacy"
        monkeypatch.setattr(fragment_mod, "_RUN_OPTIMIZE", False)
        h2 = Holder(str(other_dir))
        h2.open()
        try:
            self._run_heavy_frame(h2)
            assert (h2.fragment("r", "f", "standard", 0)
                    .container_stats()["counts"]["run"] == 0)
            ex1 = Executor(holder, host="local", use_mesh=False)
            ex2 = Executor(h2, host="local", use_mesh=False)
            queries = [
                'Count(Intersect(Bitmap(rowID=0, frame=f),'
                ' Bitmap(rowID=1, frame=f)))',
                'Count(Union(Bitmap(rowID=0, frame=f),'
                ' Bitmap(rowID=2, frame=f)))',
                'Count(Difference(Bitmap(rowID=1, frame=f),'
                ' Bitmap(rowID=2, frame=f)))',
                'TopN(frame=f, n=2)',
            ]
            for q in queries:
                r1, r2 = ex1.execute("r", q), ex2.execute("r", q)
                if hasattr(r1[0], "bits"):
                    assert list(r1[0].bits()) == list(r2[0].bits()), q
                else:
                    assert r1 == r2, q
        finally:
            h2.close()


class TestObsSurface:
    def test_runtime_collector_publishes_container_mix(self, tmp_path):
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.obs import metrics as obs_metrics
        from pilosa_tpu.obs.runtime import RuntimeCollector
        h = Holder(str(tmp_path))
        h.open()
        try:
            frame = h.create_index_if_not_exists("m") \
                .create_frame_if_not_exists("f")
            cols = np.arange(0, 40000, dtype=np.uint64)
            frame.import_bits(np.zeros(len(cols), np.uint64), cols)
            snap = RuntimeCollector(holder=h).collect()
            mix = snap["holder"]["containers"]
            assert mix["counts"]["run"] >= 1, mix
            assert mix["bytes"]["run"] > 0
            fams = obs_metrics.default_registry().families()
            assert "pilosa_roaring_containers_live" in fams
            assert "pilosa_roaring_container_bytes" in fams
            rendered = obs_metrics.default_registry().render()
            assert 'pilosa_roaring_containers_live{kind="run"}' \
                in rendered
        finally:
            h.close()


class TestCliRunSurface:
    def test_inspect_and_check_report_run_stats(self, tmp_path, capsys):
        from pilosa_tpu.cli.commands import main as cli_main
        b = Bitmap()
        b.add_many(np.arange(100, 30000, dtype=np.uint64))
        b.add_many((2 << 16) + np.arange(0, 100, 2, dtype=np.uint64))
        b.optimize()
        p = tmp_path / "frag"
        p.write_bytes(b.marshal())
        assert cli_main(["check", str(p)]) == 0
        assert ": ok" in capsys.readouterr().out
        assert cli_main(["inspect", str(p)]) == 0
        out = capsys.readouterr().out
        assert "run" in out and "Container Types" in out
        assert "INTERVALS" in out

    def test_check_flags_corrupt_run_invariants(self, tmp_path, capsys):
        from pilosa_tpu.cli.commands import main as cli_main
        b = Bitmap()
        b.add_many(np.arange(100, 30000, dtype=np.uint64))
        b.optimize()
        data = bytearray(b.marshal())
        # Corrupt the run block: overlap the (single) run with a bogus
        # second one by rewriting numRuns and appending garbage is
        # fiddly; instead break the cardinality header (n-1) so the
        # Σ lengths == n invariant trips.
        hdr_off = roaring.HEADER_SIZE + roaring._run_flags_len(1) + 8
        data[hdr_off:hdr_off + 4] = (5).to_bytes(4, "little")
        p = tmp_path / "bad"
        p.write_bytes(bytes(data))
        assert cli_main(["check", str(p)]) == 1
        assert "lengths sum" in capsys.readouterr().out
