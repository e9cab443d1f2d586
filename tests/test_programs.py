"""Shape-stable global-view program catalogue (parallel.programs):
slice buckets, fused multi-op trees, bucket-bound compile counts, and
the cross-process persistent XLA compile cache."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import programs


def _popcount(a: np.ndarray) -> int:
    return int(np.bitwise_count(a).sum())


class TestSliceBuckets:
    def test_bucket_ladder(self):
        # n_dev × 2^k ladder: every count in (bucket/2, bucket] shares
        # one compiled shape.
        assert programs.slice_bucket(0, 8) == 8
        assert programs.slice_bucket(1, 8) == 8
        assert programs.slice_bucket(8, 8) == 8
        assert programs.slice_bucket(9, 8) == 16
        assert programs.slice_bucket(16, 8) == 16
        assert programs.slice_bucket(17, 8) == 32
        assert programs.slice_bucket(32, 8) == 32
        assert programs.slice_bucket(33, 8) == 64

    def test_bucket_count_is_logarithmic(self):
        buckets = {programs.slice_bucket(n, 8) for n in range(1, 1025)}
        assert len(buckets) == 8  # 8, 16, ..., 1024

    def test_above_largest_bucket_falls_back_to_device_multiple(self):
        bound = mesh_mod.slice_chunk_bound(8)
        big = bound - 3  # above the largest 8×2^k under the bound
        got = programs.slice_bucket(big, 8)
        assert got >= big and got % 8 == 0 and got <= (1 << 15)

    def test_bucket_pad_is_count_identity(self):
        rng = np.random.default_rng(0)
        m = mesh_mod.make_mesh(8)
        leaves = rng.integers(0, 2**32, size=(2, 11, 128),
                              dtype=np.uint32)
        padded = programs.bucket_pad(leaves, 1, 8)
        assert padded.shape[1] == 16
        arrs = [mesh_mod.shard_slices(m, padded[i]) for i in range(2)]
        got = mesh_mod.count_expr_sharded(
            m, ("and", ("leaf", 0), ("leaf", 1)), arrs)
        assert got == _popcount(leaves[0] & leaves[1])


class TestFusedTree:
    def test_counts_and_topn_one_program_one_fetch(self):
        rng = np.random.default_rng(3)
        m = mesh_mod.make_mesh(8)
        S, W, R = 16, 256, 5
        leaves = rng.integers(0, 2**32, size=(3, S, W), dtype=np.uint32)
        rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
        arrs = [mesh_mod.shard_slices(m, leaves[i]) for i in range(3)]
        d_rows = mesh_mod.shard_slices(m, rows)
        exprs = (("and", ("leaf", 0), ("leaf", 1)),
                 ("andnot", ("leaf", 2), ("leaf", 0)))
        counts, topns = mesh_mod.fused_tree_sharded(
            m, exprs, [(("leaf", 1), R)], arrs, [d_rows])
        assert counts == [
            _popcount(leaves[0] & leaves[1]),
            _popcount(leaves[2] & ~leaves[0])]
        assert topns[0] == [_popcount(rows[:, r, :] & leaves[1])
                            for r in range(R)]

    def test_topn_only_tree(self):
        rng = np.random.default_rng(4)
        m = mesh_mod.make_mesh(8)
        S, W, R = 8, 128, 3
        leaves = rng.integers(0, 2**32, size=(1, S, W), dtype=np.uint32)
        rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
        arrs = [mesh_mod.shard_slices(m, leaves[0])]
        counts, topns = mesh_mod.fused_tree_sharded(
            m, (), [(("leaf", 0), R)], arrs,
            [mesh_mod.shard_slices(m, rows)])
        assert counts == []
        assert topns[0] == [_popcount(rows[:, r, :] & leaves[0])
                            for r in range(R)]


class TestTopKProgram:
    def test_lo_sum_carry_does_not_break_order(self):
        """The per-candidate lo-halves sum past 2^16 on dense rows, so
        the in-program lexicographic sort must carry lo's overflow into
        hi first: row A (per-slice counts 65535+65535 = 131070) must
        outrank row B (65536 = hi 1, lo 0) even though B's raw hi is
        larger (review finding)."""
        m = mesh_mod.make_mesh(8)
        S, W = 8, 2048  # 2048 u32 words = 65536 bits per slice
        rows = np.zeros((S, 2, W), dtype=np.uint32)
        rows[0, 0, :] = 0xFFFFFFFF
        rows[1, 0, :] = 0xFFFFFFFF
        rows[0, 0, 0] = 0xFFFFFFFE  # row 0: 65535 + 65535 = 131070
        rows[1, 0, 0] = 0xFFFFFFFE
        rows[0, 1, :] = 0xFFFFFFFF  # row 1: 65536
        counts, idx = mesh_mod.topn_topk_sharded(
            m, None, mesh_mod.shard_slices(m, rows), [], 2)
        assert idx == [0, 1]
        assert counts == [131070, 65536]


EXPR = ("or", ("and", ("leaf", 0), ("leaf", 1)),
        ("andnot", ("leaf", 2), ("leaf", 0)))


def _eval(expr, leaves):
    if expr[0] == "leaf":
        return leaves[expr[1]]
    f = {"and": np.bitwise_and, "or": np.bitwise_or,
         "xor": np.bitwise_xor,
         "andnot": lambda a, b: a & ~b}[expr[0]]
    return f(_eval(expr[1], leaves), _eval(expr[2], leaves))


def _block(seed=7, L=3, S=16, R=9, W=384):
    rng = np.random.default_rng(seed)
    leaves = rng.integers(0, 2**32, size=(L, S, W), dtype=np.uint32)
    rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
    return leaves, rows


def _filtered_want(expr, rows, leaves, threshold, tanimoto):
    """Per-slice threshold/Tanimoto pruning, then the slice sum — the
    host reference of mesh._filtered_counts."""
    src = _eval(expr, leaves)
    inter = np.bitwise_count(rows & src[:, None, :]).sum(axis=-1)
    rowc = np.bitwise_count(rows).sum(axis=-1)
    srcc = np.bitwise_count(src).sum(axis=-1)[:, None]
    if tanimoto:
        keep = ((100 * rowc > srcc * tanimoto)
                & (rowc * tanimoto < srcc * 100) & (inter > 0)
                & (100 * inter > tanimoto * (rowc + srcc - inter)))
    else:
        keep = (rowc >= threshold) & (inter >= threshold)
    return np.where(keep, inter, 0).sum(axis=0).tolist()


class TestDispatchEntryPoints:
    """The mesh entry points against numpy at program level, on the
    8-device mesh: each builds its one catalogue program."""

    @pytest.mark.parametrize("expr,shape", [
        (EXPR, (3, 16, 384)),
        (("leaf", 2), (3, 16, 384)),
        # Slices that do not divide the mesh and words that fill no
        # vector lane must pad losslessly.
        (("xor", ("leaf", 0), ("leaf", 1)), (2, 5, 130)),
        # A wide fold takes _eval_expr's lax.reduce form.
        (("or", ("or", ("or", ("leaf", 0), ("leaf", 1)), ("leaf", 2)),
          ("leaf", 3)), (4, 13, 257)),
    ], ids=["multi_leaf", "single_leaf", "unaligned", "wide_fold"])
    def test_count_expr(self, expr, shape):
        leaves, _ = _block(8, *shape[:2], R=1, W=shape[2])
        m = mesh_mod.make_mesh(8)
        assert mesh_mod.count_expr(m, expr, leaves) == _popcount(
            _eval(expr, leaves))

    @pytest.mark.parametrize("expr,threshold,tanimoto", [
        (EXPR, 1, 0), (None, 1, 0), (EXPR, 3, 0), (EXPR, 1, 50),
    ], ids=["expr", "plain_popcount", "threshold", "tanimoto"])
    def test_topn_exact(self, expr, threshold, tanimoto):
        leaves, rows = _block()
        m = mesh_mod.make_mesh(8)
        if expr is None:
            want = np.bitwise_count(rows).sum(axis=(0, 2)).tolist()
            leaves = None
        else:
            want = _filtered_want(expr, rows, leaves, threshold,
                                  tanimoto)
        assert mesh_mod.topn_exact(m, expr, rows, leaves,
                                   threshold, tanimoto) == want

    @pytest.mark.parametrize("threshold,tanimoto", [
        (1, 0), (3, 0), (10**6, 0), (1, 5), (1, 50), (1, 99)])
    def test_topn_filtered_sharded(self, threshold, tanimoto):
        leaves, rows = _block()
        m = mesh_mod.make_mesh(8)
        got = mesh_mod.topn_filtered_sharded(
            m, EXPR, mesh_mod.shard_slices(m, rows),
            [mesh_mod.shard_slices(m, leaf) for leaf in leaves],
            threshold=threshold, tanimoto=tanimoto)
        assert got == _filtered_want(EXPR, rows, leaves, threshold,
                                     tanimoto)

    def test_topn_topk_with_source_selects_in_program(self):
        leaves, rows = _block()
        rows[:, 4] = rows[:, 2]  # a tie: the lower index wins
        m = mesh_mod.make_mesh(8)
        counts, idx = mesh_mod.topn_topk_sharded(
            m, EXPR, mesh_mod.shard_slices(m, rows),
            [mesh_mod.shard_slices(m, leaf) for leaf in leaves], 4)
        want = _filtered_want(EXPR, rows, leaves, 1, 0)
        order = np.lexsort((np.arange(len(want)), -np.asarray(want)))[:4]
        assert idx == order.tolist()
        assert counts == [want[i] for i in order]

    def test_one_program_family_whatever_the_environment(
            self, monkeypatch):
        """There is one program per query kind and no variable picks
        another: the name that used to select the Pallas twins (spelt
        in two halves, so a search for its readers finds none) changes
        neither the answers nor the programs that are compiled."""
        monkeypatch.setenv("PILOSA_TPU_" + "PALLAS", "interpret")
        leaves, rows = _block(11, W=136)  # shapes no other test compiles
        m = mesh_mod.make_mesh(8)
        arrs = [mesh_mod.shard_slices(m, leaf) for leaf in leaves]
        exprs = (EXPR, ("xor", ("leaf", 1), ("leaf", 2)))
        assert mesh_mod.count_exprs_sharded(m, exprs, arrs) == [
            _popcount(_eval(e, leaves)) for e in exprs]
        assert mesh_mod.topn_exact_sharded(
            m, EXPR, mesh_mod.shard_slices(m, rows), arrs) \
            == _filtered_want(EXPR, rows, leaves, 1, 0)
        names = [e["program"] for e in mesh_mod.compile_log()]
        assert "count_exprs_n2_k3" in names and "topn_exact_k3" in names
        assert not [n for n in names if "pallas" in n]


class TestExecutorFusedTree:
    """Count+TopN multi-op queries lower into ONE fused device program
    through the executor, and agree with the host path exactly."""

    N_SLICES = 8

    def _fill(self, holder):
        rng = np.random.default_rng(9)
        f = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        for row in range(5):
            cols = (rng.integers(0, SLICE_WIDTH,
                                 size=60 * self.N_SLICES)
                    + np.repeat(np.arange(self.N_SLICES), 60)
                    * SLICE_WIDTH)
            f.import_bits(np.full(len(cols), row, dtype=np.uint64),
                          cols.astype(np.uint64))

    QUERY = ("Count(Intersect(Bitmap(rowID=0, frame=f),"
             " Bitmap(rowID=1, frame=f)))"
             " TopN(Bitmap(rowID=0, frame=f), frame=f, ids=[1, 2, 3])"
             " Count(Union(Bitmap(rowID=2, frame=f),"
             " Bitmap(rowID=3, frame=f)))")

    def test_fused_run_matches_host(self, tmp_path, monkeypatch):
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        holder = Holder(str(tmp_path))
        holder.open()
        try:
            self._fill(holder)
            fast = Executor(holder, host="local", use_mesh=True,
                            mesh_min_slices=1)
            slow = Executor(holder, host="local", use_mesh=False)
            calls = []
            orig = mesh_mod.fused_tree_sharded

            def spy(*a, **kw):
                calls.append(1)
                return orig(*a, **kw)

            monkeypatch.setattr(mesh_mod, "fused_tree_sharded", spy)
            got = fast.execute("i", self.QUERY)
            want = slow.execute("i", self.QUERY)

            def norm(r):
                return [[(p.id, p.count) for p in x]
                        if isinstance(x, list) else x for x in r]

            assert norm(got) == norm(want)
            assert calls == [1], "whole tree must be one dispatch"
            assert fast.device_fallbacks == 0
        finally:
            holder.close()

    def test_filtered_topn_breaks_the_run(self, tmp_path, monkeypatch):
        """threshold>1 keeps its per-kind pruning program — the run
        must fall back per call, still correct."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        holder = Holder(str(tmp_path))
        holder.open()
        try:
            self._fill(holder)
            fast = Executor(holder, host="local", use_mesh=True,
                            mesh_min_slices=1)
            slow = Executor(holder, host="local", use_mesh=False)
            q = ("Count(Bitmap(rowID=0, frame=f))"
                 " TopN(Bitmap(rowID=0, frame=f), frame=f,"
                 " ids=[1, 2], threshold=5)")
            monkeypatch.setattr(
                mesh_mod, "fused_tree_sharded",
                lambda *a, **kw: pytest.fail("filtered TopN fused"))
            got = fast.execute("i", q)
            want = slow.execute("i", q)

            def norm(r):
                return [[(p.id, p.count) for p in x]
                        if isinstance(x, list) else x for x in r]

            assert norm(got) == norm(want)
        finally:
            holder.close()


class TestCompileCountBucketBound:
    """The acceptance gate for ROADMAP item 1(a): growing the slice
    count 8→32 compiles a NEW program only when the count crosses into
    a new bucket — never per slice count. firstCalls counts true XLA
    compilations (shape-keyed, via the jitted cache size), so the
    assertion is on the real cold tax, not the builder-cache shape."""

    def test_count_and_topn_compiles_constant_within_bucket(
            self, tmp_path):
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        holder = Holder(str(tmp_path))
        holder.open()
        try:
            rng = np.random.default_rng(21)
            f = holder.create_index_if_not_exists("i") \
                .create_frame_if_not_exists("f")
            n_slices = 32
            for row in range(3):
                cols = (rng.integers(0, SLICE_WIDTH, size=4 * n_slices)
                        + np.repeat(np.arange(n_slices), 4)
                        * SLICE_WIDTH)
                f.import_bits(np.full(len(cols), row, dtype=np.uint64),
                              cols.astype(np.uint64))
            ex = Executor(holder, host="local", mesh_min_slices=1)
            # A distinctive expression so earlier tests can't have
            # pre-warmed this exact program.
            q = ("Count(Union(Intersect(Bitmap(rowID=0, frame=f),"
                 " Bitmap(rowID=1, frame=f)),"
                 " Difference(Bitmap(rowID=2, frame=f),"
                 " Bitmap(rowID=0, frame=f))))")
            qt = ("TopN(Difference(Bitmap(rowID=1, frame=f),"
                  " Bitmap(rowID=2, frame=f)), frame=f, ids=[0, 2])")
            host = Executor(holder, host="local", use_mesh=False)
            compiles = {}
            for n in (8, 10, 12, 16, 20, 24, 32):
                slices = list(range(n))
                before = mesh_mod.compile_stats()["firstCalls"]
                got = ex.execute("i", q, slices)
                got_t = ex.execute("i", qt, slices)
                compiles[n] = (mesh_mod.compile_stats()["firstCalls"]
                               - before)
                assert got == host.execute("i", q, slices), n
                wt = host.execute("i", qt, slices)
                assert [(p.id, p.count) for p in got_t[0]] == \
                    [(p.id, p.count) for p in wt[0]], n
            assert ex.device_fallbacks == 0
            # 8 → bucket 8 (first touch may compile); 10 → bucket 16
            # (first touch); 12, 16 → SAME bucket: zero new compiles.
            assert compiles[12] == 0, compiles
            assert compiles[16] == 0, compiles
            # 20 → bucket 32 (first touch); 24, 32 → zero again.
            assert compiles[24] == 0, compiles
            assert compiles[32] == 0, compiles
            # And the buckets that did compile each did real work once.
            assert compiles[8] > 0 and compiles[10] > 0
            assert compiles[20] > 0
        finally:
            holder.close()


class TestPersistentCompileCache:
    """The on-disk XLA cache must HIT across processes — a restarted
    server re-reads compiled programs instead of re-paying the
    trace+compile. The directory comes from JAX_COMPILATION_CACHE_DIR
    alone: jax reads it, arm_compile_cache sets no other."""

    CHILD = textwrap.dedent("""
        import os, sys, json
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, %(repo)r)
        import jax
        from pilosa_tpu.parallel import mesh as mesh_mod
        armed = mesh_mod.arm_compile_cache()
        assert armed == %(cache)r, armed
        assert jax.config.jax_compilation_cache_dir == %(cache)r
        # Tiny test programs compile fast; drop the persistence
        # threshold so they are cacheable (real serving programs
        # clear the default 0.1 s on their own).
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        import numpy as np
        m = mesh_mod.make_mesh(8)
        slab = mesh_mod.shard_slices(
            m, np.ones((8, 512), dtype=np.uint32))
        got = mesh_mod.count_expr_sharded(
            m, ("and", ("leaf", 0), ("leaf", 1)), [slab, slab])
        assert got == 8 * 512, got  # value 1 per word = 1 bit
        print("STATS " + json.dumps(mesh_mod.compile_stats()))
    """)

    def test_second_process_hits_on_disk_cache(self, tmp_path):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        cache = str(tmp_path / "xla")
        code = self.CHILD % {"repo": repo, "cache": cache}
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = cache

        def run():
            out = subprocess.run([sys.executable, "-c", code],
                                 capture_output=True, text=True,
                                 env=env, timeout=240)
            assert out.returncode == 0, out.stderr[-2000:]
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith("STATS ")][0]
            import json
            return json.loads(line[len("STATS "):])

        first = run()
        assert first["persistentMisses"] >= 1, first
        assert first["persistentHits"] == 0, first
        files = set(os.listdir(cache))
        assert files, "first process wrote no cache entries"
        second = run()
        # The counter the satellite asks for: the second process's
        # compile was served from disk — hit, not miss.
        assert second["persistentHits"] >= 1, second
        assert second["persistentMisses"] == 0, second
        assert set(os.listdir(cache)) == files  # nothing re-written

