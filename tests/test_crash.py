"""Process-level crash durability: SIGKILL a live server mid-write.

The in-process suites cover torn-WAL-tail trims and clean restarts
(test_fragment, test_server soaks); this one kills a REAL server
process with SIGKILL while a write storm is in flight, then proves the
data directory reopens cleanly: `check` passes on every fragment file,
and every acknowledged write is present after restart (the reference's
durability contract — an op acked over HTTP has hit the WAL).

The child runs CPU-forced with the device paths disabled: the kill is
about the storage engine, and a test child must never take a chip.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from podenv import cpu_env, free_port, wait_up

_HERE = os.path.dirname(os.path.abspath(__file__))


def _spawn_server(data_dir, port, log):
    env = cpu_env()
    env["PILOSA_TPU_MESH"] = "0"
    return subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server",
         "-d", str(data_dir), "-b", f"127.0.0.1:{port}"],
        env=env, stdout=log, stderr=log,
        cwd=os.path.dirname(_HERE))


def _query(port, pql, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/index/ci/query", data=pql.encode(),
        method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())["results"]


def test_sigkill_mid_write_storm_recovers(tmp_path):
    port = free_port()
    data_dir = tmp_path / "data"
    with open(tmp_path / "server.log", "w") as log:
        proc = _spawn_server(data_dir, port, log)
        try:
            wait_up(f"127.0.0.1:{port}")
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/index/ci", data=b"{}",
                method="POST"), timeout=30).read()
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/index/ci/frame/cf", data=b"{}",
                method="POST"), timeout=30).read()

            # Write storm: every acked SetBit is recorded; the kill
            # lands somewhere inside the stream.
            acked = []
            deadline = time.monotonic() + 6.0
            i = 0
            while time.monotonic() < deadline and i < 3000:
                col = (i * 131) % (1 << 20)
                row = i % 40
                _query(port, f'SetBit(frame="cf", rowID={row},'
                             f' columnID={col})')
                acked.append((row, col))
                i += 1
            assert len(acked) > 200, "storm too slow to be meaningful"
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    # Offline integrity: every fragment file must pass check().
    frag_dir = data_dir / "ci" / "cf" / "views" / "standard" / "fragments"
    frags = [str(p) for p in frag_dir.iterdir()
             if p.name.isdigit()] if frag_dir.exists() else []
    assert frags, "no fragment files written before the kill"
    from pilosa_tpu.cli.commands import main as cli_main
    import io
    out = io.StringIO()
    rc = cli_main(["check"] + frags, stdout=out, stderr=out)
    assert rc == 0, f"check failed after SIGKILL:\n{out.getvalue()}"

    # Restart on the same data dir: every acked bit answers.
    with open(tmp_path / "server2.log", "w") as log:
        proc = _spawn_server(data_dir, port, log)
        try:
            wait_up(f"127.0.0.1:{port}")
            want = {}
            for row, col in acked:
                want.setdefault(row, set()).add(col)
            for row, cols in sorted(want.items()):
                got = _query(port, f'Bitmap(frame="cf", rowID={row})')
                bits = set(got[0]["bits"])
                missing = cols - bits
                assert not missing, (row, sorted(missing)[:5])
        finally:
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


@pytest.mark.chaos
def test_wal_append_torn_at_every_offset_recovers(tmp_path):
    """Failpoint-driven DETERMINISTIC crash-mid-wal.append, group-commit
    form: the ``wal.append`` failpoint now fires at the LEADER's batch
    write (storage.wal), so ``torn(k)`` tears a GROUPED multi-record
    batch at every byte offset — exactly where a crash mid group
    commit would cut the log. The reopen must recover the acked prefix
    (records whose commit barrier returned) plus exactly the complete
    records of the torn batch (written but never acked — at-least-once
    is allowed, loss of acked ops is not), and the fragment must
    accept writes again."""
    from pilosa_tpu.fault import failpoints
    from pilosa_tpu.fault.failpoints import FailpointError
    from pilosa_tpu.storage.fragment import Fragment
    from pilosa_tpu.storage.roaring import OP_SIZE
    from pilosa_tpu.storage.wal import WalError

    batch_cols = [99, 100, 101]  # the torn batch: 3 records, 39 bytes
    try:
        for k in range(OP_SIZE * len(batch_cols)):
            path = str(tmp_path / f"frag{k}")
            f = Fragment(path, "i", "f", "standard", 0)
            f.open()
            acked = []
            for col in range(8):  # acked prefix: barriered below
                f.set_bit(1, col)
                acked.append(col)
            f.wal_barrier()  # the ack point (group-commit contract)
            with failpoints.injected("wal.append", f"torn({k})"):
                # ONE atomic 3-record append (the batched write path)
                # so the torn batch is the same 39 bytes regardless of
                # when a background flush races the barrier.
                import numpy as np
                f.set_bits(np.full(3, 1, dtype=np.uint64),
                           np.array(batch_cols, dtype=np.uint64))
                with pytest.raises((FailpointError, WalError)):
                    f.wal_barrier()  # leader write tears mid-batch
                # Simulate the crash HERE (still torn-armed, so the
                # background flusher cannot quietly retry the batch):
                # mark the dead process's WAL dead and free its flock.
                f._wal.close()
                import fcntl
                fcntl.flock(f._file.fileno(), fcntl.LOCK_UN)
            f2 = Fragment(path, "i", "f", "standard", 0)
            f2.open()
            try:
                # The failed leader truncated back to the durable
                # prefix, so recovery is EXACTLY the acked set — none
                # of the torn batch's records survive at any offset.
                got = sorted(f2.row(1).bits())
                assert got == acked, (
                    f"torn at {k}: {got} != acked {acked}")
                assert f2.set_bit(1, 999), \
                    f"torn at {k}: fragment must accept writes again"
                f2.wal_barrier()
            finally:
                f2.close()
    finally:
        failpoints.disarm_all()


@pytest.mark.chaos
def test_crash_mid_snapshot_write_recovers(tmp_path):
    """Failpoint-driven crash-mid-``snapshot.write``: the async
    MAX_OP_N-triggered snapshot dies mid-serialization, the old
    snapshot+WAL stays the file of record, writes keep flowing, the
    retry lands, and a reopen sees every acked bit."""
    import pilosa_tpu.storage.fragment as fragmod
    from pilosa_tpu.fault import failpoints
    from pilosa_tpu.storage.fragment import Fragment

    old_maxop = fragmod.MAX_OP_N
    fragmod.MAX_OP_N = 20  # force snapshot storms
    path = str(tmp_path / "frag")
    try:
        f = Fragment(path, "i", "f", "standard", 0)
        f.open()
        acked = []
        with failpoints.injected("snapshot.write", "error"):
            for col in range(100):  # many ops → several failed
                f.set_bit(2, col)   # background snapshot attempts
                acked.append(col)
            f._join_snapshot()
        # Disarmed: more writes re-trigger the snapshot, which now
        # lands cleanly.
        for col in range(100, 140):
            f.set_bit(2, col)
            acked.append(col)
        f._join_snapshot()
        assert sorted(f.row(2).bits()) == acked
        f.close()
        f2 = Fragment(path, "i", "f", "standard", 0)
        f2.open()
        try:
            assert sorted(f2.row(2).bits()) == acked, \
                "every acked bit must survive the failed snapshots"
        finally:
            f2.close()
    finally:
        fragmod.MAX_OP_N = old_maxop
        failpoints.disarm_all()


def test_single_fragment_storm_exact_model(tmp_path):
    """Mixed per-op set/clear + batched sets under forced snapshot-storm
    cadence, ops serialized so model order == apply order: the final
    storage must equal the model EXACTLY, live and after reopen. This
    is the single-node half of the 60-min soak's consistency argument —
    when a cluster soak diverges by a bit, this pins whether the
    storage engine (WAL, async snapshot splice, batch engine) can lose
    or invent ops at all (round 5: it could not; the soak event was an
    opposing-op linearization ambiguity across replica fan-outs)."""
    import random
    import threading
    import time

    import numpy as np

    import pilosa_tpu.storage.fragment as fragmod
    from pilosa_tpu.storage.fragment import Fragment

    old_maxop = fragmod.MAX_OP_N
    fragmod.MAX_OP_N = 200
    try:
        f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0)
        f.open()
        model: dict[int, set] = {}
        mu = threading.Lock()
        stop = threading.Event()
        errs: list = []

        def worker(seed):
            rng = random.Random(seed)
            try:
                while not stop.is_set():
                    r = rng.randrange(16)
                    c = rng.randrange(1 << 18)
                    if rng.random() < 0.85:
                        with mu:
                            f.set_bit(r, c)
                            model.setdefault(r, set()).add(c)
                    else:
                        with mu:
                            f.clear_bit(r, c)
                            model.setdefault(r, set()).discard(c)
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        def batch_worker(seed):
            rng = random.Random(seed)
            try:
                while not stop.is_set():
                    r = rng.randrange(16)
                    cols = np.array(
                        [rng.randrange(1 << 18) for _ in range(100)],
                        dtype=np.uint64)
                    with mu:
                        f.set_bits(np.full(100, r, dtype=np.uint64),
                                   cols)
                        model.setdefault(r, set()).update(cols.tolist())
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        threads += [threading.Thread(target=batch_worker, args=(9,))]
        for t in threads:
            t.start()
        time.sleep(8)
        stop.set()
        for t in threads:
            t.join()
        assert not errs, errs

        def rows_equal(frag):
            from pilosa_tpu import SLICE_WIDTH
            for r, want in model.items():
                # offset_range rebases to 0, so values ARE the cols
                pos = frag.storage.offset_range(
                    0, r * SLICE_WIDTH, (r + 1) * SLICE_WIDTH)
                got = set(pos.values().tolist())
                if got != want:
                    return False, r
            return True, None

        ok, bad = rows_equal(f)
        assert ok, f"live mismatch in row {bad}"
        f.close()
        f2 = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0)
        f2.open()
        ok, bad = rows_equal(f2)
        assert ok, f"reopen mismatch in row {bad}"
        f2.close()
    finally:
        fragmod.MAX_OP_N = old_maxop
