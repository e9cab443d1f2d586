"""1 B-column scale smoke: 1024 slices (1024 × 2^20 = 2^30 columns)
through the mesh programs and the executor, asserting the chunk guards
actually execute and results stay exact (so the first real pod run is
not the first time the chunking runs at scale).

The real constants trigger for TopN at this size: a 1024-slice
candidate block is 128 MB per row, so TOPN_BLOCK_BYTES (256 MB) forces
row-chunking at 2 rows per call. The 2^15 slice bound needs 4 GB+ of
leaves to trigger naturally; the seam logic is exercised by shrinking
the bound (monkeypatch) over the same data and requiring identical
results.
"""

import numpy as np
import pytest

from pilosa_tpu.ops.packed import WORDS_PER_SLICE
from pilosa_tpu.parallel import mesh as mesh_mod

N_SLICES = 1024  # × 2^20 columns per slice = 2^30 columns


@pytest.fixture(scope="module")
def mesh():
    return mesh_mod.make_mesh(8)


@pytest.fixture(scope="module")
def leaves():
    rng = np.random.default_rng(30)
    # Sparse-ish leaves: dense random words in 1/8 of the slices, zero
    # elsewhere — 256 MB total, popcount reference stays cheap.
    out = np.zeros((2, N_SLICES, WORDS_PER_SLICE), dtype=np.uint32)
    idx = rng.choice(N_SLICES, size=N_SLICES // 8, replace=False)
    out[:, idx] = rng.integers(0, 2**32,
                               size=(2, len(idx), WORDS_PER_SLICE),
                               dtype=np.uint32)
    return out


def test_count_expr_1b_columns(mesh, leaves):
    expr = ("and", ("leaf", 0), ("leaf", 1))
    want = int(np.bitwise_count(leaves[0] & leaves[1]).sum())
    assert mesh_mod.count_expr(mesh, expr, leaves) == want


def test_count_expr_chunk_seams_exact(mesh, leaves, monkeypatch):
    """Force the slice-chunk loop to run many times (the 2^15 bound
    needs 4 GB to trigger naturally) — seams must not change the sum."""
    expr = ("or", ("leaf", 0), ("leaf", 1))
    want = int(np.bitwise_count(leaves[0] | leaves[1]).sum())
    monkeypatch.setattr(mesh_mod, "slice_chunk_bound", lambda n: 100)
    assert mesh_mod.count_expr(mesh, expr, leaves) == want


def test_topn_exact_1b_columns_row_chunk_triggers(mesh, leaves):
    """1024-slice candidate blocks exceed TOPN_BLOCK_BYTES per 2 rows —
    the REAL row-chunk guard must fire, and counts must stay exact."""
    rng = np.random.default_rng(31)
    n_rows = 5  # 5 × 128 MB per-row block → 3 chunks of ≤2 rows
    rows = np.zeros((N_SLICES, n_rows, WORDS_PER_SLICE), dtype=np.uint32)
    idx = rng.choice(N_SLICES, size=64, replace=False)
    rows[idx] = rng.integers(0, 2**32,
                             size=(len(idx), n_rows, WORDS_PER_SLICE),
                             dtype=np.uint32)

    row_chunk = max(1, mesh_mod.TOPN_BLOCK_BYTES
                    // (N_SLICES * WORDS_PER_SLICE * 4))
    assert row_chunk == 2  # the guard is live at this scale

    calls = []
    from pilosa_tpu.parallel import programs
    orig = programs.topn_block_program

    def spy(mesh_, expr_, filtered):
        fn = orig(mesh_, expr_, filtered=filtered)

        def wrapped(*a):
            calls.append(1)
            return fn(*a)
        return wrapped

    expr = ("leaf", 0)
    src = leaves[:1]
    want = np.bitwise_count(
        rows & leaves[0][:, None, :]).sum(axis=(0, 2)).tolist()
    import unittest.mock as mock
    with mock.patch.object(programs, "topn_block_program", spy):
        got = mesh_mod.topn_exact(mesh, expr, rows, src)
    assert got == want
    assert len(calls) == -(-n_rows // row_chunk)  # 3 chunked programs


def test_executor_1b_column_index(tmp_path):
    """A real 1024-slice index served through the executor: Count and
    the streamed TopN exact phase (resident path exceeds its block
    budget at this scale and must hand off to the chunked stream)."""
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    holder = Holder(str(tmp_path))
    holder.open()
    try:
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(32)
        # 3 bits per slice per row, deterministic counts.
        for row in (1, 2, 3):
            cols = (rng.integers(0, SLICE_WIDTH, size=N_SLICES)
                    + np.arange(N_SLICES, dtype=np.uint64) * SLICE_WIDTH)
            frame.import_bits([row] * N_SLICES, cols)
        ex = Executor(holder, host="local", mesh_min_slices=1)
        got = ex.execute("i", "Count(Bitmap(frame=f, rowID=1))")[0]
        assert got == N_SLICES
        # TopN exact phase across all 1024 slices: 3 candidates × 1024
        # slices = 384 MB block > the 256 MB resident budget, so the
        # executor must hand off to the chunked streaming path — and
        # counts must stay exact against the host path.
        q = "TopN(Bitmap(frame=f, rowID=1), frame=f, ids=[1, 2, 3])"
        res = ex.execute("i", q)
        assert ex.device_fallbacks == 0
        got = {p.id: p.count for p in res[0]}
        slow = Executor(holder, host="local", use_mesh=False)
        sres = slow.execute("i", q)
        assert got == {p.id: p.count for p in sres[0]}
        assert got[1] == N_SLICES  # row ∩ itself = every slice's bit
        # Plain TopN (both phases: 1024 rank-cache walks + the exact
        # re-query across every slice) — BASELINE config 5's shape at
        # the full 1 B-column axis.
        res = ex.execute("i", "TopN(frame=f, n=2)")[0]
        assert [(p.id, p.count) for p in res] == \
            [(1, N_SLICES), (2, N_SLICES)]
    finally:
        holder.close()
