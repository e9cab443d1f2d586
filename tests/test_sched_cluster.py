"""Query lifecycle on a REAL 2-node gossip cluster (replicas=1, so
fan-out is mandatory): a peer that stalls mid-fan-out must not hang
the coordinator — the propagated deadline clamps the remote leg's
socket timeout and the coordinator answers 504 within the budget.
Cancellation must release the coordinator's slot and broadcast to the
peer, and neither node may leak registry entries."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

from podenv import cpu_env, free_port, wait_up  # noqa: E402

from pilosa_tpu import SLICE_WIDTH  # noqa: E402


def _post(host, path, body=b"", timeout=30):
    req = urllib.request.Request(f"http://{host}{path}", data=body,
                                 method="POST")
    return urllib.request.urlopen(req, timeout=timeout).read()


def _get_json(host, path, timeout=10):
    with urllib.request.urlopen(f"http://{host}{path}",
                                timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture
def cluster(tmp_path):
    """Two gossip-joined nodes with bits spanning 4 slices (replicas=1
    → both nodes own some slices, so reads MUST fan out)."""
    pa, pb = free_port(), free_port()
    ga, gb = free_port(), free_port()
    hosts = f"127.0.0.1:{pa},127.0.0.1:{pb}"
    procs, logs = [], []

    def spawn(name, port, internal, seed=""):
        d = tmp_path / name
        d.mkdir(exist_ok=True)
        env = cpu_env()
        env["PILOSA_TPU_MESH"] = "0"
        env["PILOSA_TPU_WARMUP"] = "0"
        log = open(tmp_path / f"{name}.log", "a")
        logs.append(log)
        argv = [sys.executable, "-m", "pilosa_tpu.cli", "server",
                "-d", str(d), "-b", f"127.0.0.1:{port}",
                "--cluster.type", "gossip",
                "--cluster.hosts", hosts,
                "--cluster.replicas", "1",
                "--cluster.internal-port", str(internal),
                "--anti-entropy.interval", "300s"]
        if seed:
            argv += ["--cluster.gossip-seed", seed]
        p = subprocess.Popen(argv, env=env, stdout=log, stderr=log,
                             cwd=os.path.dirname(_HERE))
        procs.append(p)
        wait_up(f"127.0.0.1:{port}")
        return f"127.0.0.1:{port}"

    host_a = spawn("a", pa, ga)
    host_b = spawn("b", pb, gb, seed=f"127.0.0.1:{ga}")
    _post(host_a, "/index/sc", b"{}")
    _post(host_a, "/index/sc/frame/f", b"{}")

    from pilosa_tpu.cluster.client import Client
    import numpy as np
    client = Client(host_a)
    cols = np.arange(0, 4 * SLICE_WIDTH,
                     SLICE_WIDTH // 8).astype(np.uint64)
    client.import_arrays("sc", "f", np.ones(len(cols), np.uint64), cols)

    # Wait until A can answer the full count (slice knowledge of B's
    # slices arrives via broadcast/gossip).
    deadline = time.time() + 30
    while time.time() < deadline:
        got = json.loads(_post(
            host_a, "/index/sc/query",
            b'Count(Bitmap(frame="f", rowID=1))'))["results"][0]
        if got == len(cols):
            break
        time.sleep(0.3)
    assert got == len(cols), got

    yield {"a": host_a, "b": host_b, "procs": procs,
           "n_bits": len(cols)}

    for p in procs:
        try:
            os.kill(p.pid, signal.SIGCONT)  # in case a test left it stopped
        except OSError:
            pass
        try:
            p.send_signal(signal.SIGINT)
        except OSError:
            pass
    for p in procs:
        try:
            p.wait(timeout=20)
        except subprocess.TimeoutExpired:
            p.kill()
    for log in logs:
        log.close()


def test_stalled_peer_returns_deadline_error_within_budget(cluster):
    """SIGSTOP one node mid-cluster: a deadline-carrying query from
    the other must answer 504 in ~the budget (the propagated deadline
    clamps the remote leg's socket timeout; the idempotent retry never
    starts past the budget) instead of hanging for the 30s client
    default × attempts."""
    host_a, procs = cluster["a"], cluster["procs"]
    os.kill(procs[1].pid, signal.SIGSTOP)
    try:
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(host_a, "/index/sc/query?timeout=2s",
                  b'Count(Bitmap(frame="f", rowID=1))', timeout=30)
        elapsed = time.monotonic() - t0
        assert ei.value.code == 504
        assert b"deadline" in ei.value.read().lower()
        # Within budget + scheduling slack, nowhere near a 30s hang.
        assert elapsed < 8, elapsed
        # The coordinator freed everything (bounded grace for the
        # abandoned leg, then the registry must be clean).
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if not _get_json(host_a, "/debug/queries")["queries"]:
                break
            time.sleep(0.2)
        assert _get_json(host_a, "/debug/queries")["queries"] == []
    finally:
        os.kill(procs[1].pid, signal.SIGCONT)
    # A recovered peer serves the same query fine again.
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            got = json.loads(_post(
                host_a, "/index/sc/query?timeout=10s",
                b'Count(Bitmap(frame="f", rowID=1))'))["results"][0]
            if got == cluster["n_bits"]:
                break
        except urllib.error.HTTPError:
            pass
        time.sleep(0.3)
    assert got == cluster["n_bits"]


def test_cancel_releases_coordinator_and_reaches_peer(cluster):
    """DELETE /debug/queries/{id} while the query's remote leg is
    stuck on a stalled peer: the coordinator returns 409 promptly
    (slot + registry freed without waiting out the stalled leg), the
    cancel broadcast reaches the peer, and after the peer resumes
    neither node leaks a registry entry."""
    host_a, host_b, procs = cluster["a"], cluster["b"], cluster["procs"]
    os.kill(procs[1].pid, signal.SIGSTOP)
    res = {}

    def bg():
        t0 = time.monotonic()
        try:
            _post(host_a, "/index/sc/query?timeout=60s",
                  b'Count(Bitmap(frame="f", rowID=1))', timeout=90)
            res["code"] = 200
        except urllib.error.HTTPError as e:
            res["code"] = e.code
        res["elapsed"] = time.monotonic() - t0

    t = threading.Thread(target=bg)
    t.start()
    try:
        deadline = time.monotonic() + 10
        qs = []
        while time.monotonic() < deadline and not qs:
            qs = _get_json(host_a, "/debug/queries")["queries"]
            time.sleep(0.05)
        assert qs, "query never became visible on the coordinator"
        q = qs[0]
        # Legs appear once the fan-out dispatches; the query may
        # first spend a bounded moment in the cluster result cache's
        # hit-validation probe (the fixture's convergence loop cached
        # this exact query, and the probe to the STOPPED peer must
        # fail within its ~1s budget before the real fan-out runs).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not q["legs"]:
            time.sleep(0.05)
            found = [x for x in _get_json(
                host_a, "/debug/queries")["queries"]
                if x["id"] == q["id"]]
            if not found:
                break
            q = found[0]
        assert q["legs"], "no fan-out legs recorded"
        req = urllib.request.Request(
            f"http://{host_a}/debug/queries/{q['id']}", method="DELETE")
        out = json.loads(urllib.request.urlopen(req, timeout=10).read())
        assert out["cancelled"] >= 1
        t.join(timeout=15)
        assert res["code"] == 409, res
        # 409 arrived promptly — not held hostage by the stalled leg.
        assert res["elapsed"] < 10, res
        assert _get_json(host_a, "/debug/queries")["queries"] == []
    finally:
        os.kill(procs[1].pid, signal.SIGCONT)
        t.join(timeout=15)
    # After the peer resumes, its leg (which it buffered while
    # stopped) must drain without leaking a registry entry. The
    # registry is also empty BEFORE the resumed peer reads that leg
    # off its socket, so one empty reading proves nothing: wait for
    # two in a row.
    deadline = time.monotonic() + 20
    empty = 0
    while time.monotonic() < deadline and empty < 2:
        empty = (0 if _get_json(host_b, "/debug/queries")["queries"]
                 else empty + 1)
        time.sleep(0.3)
    assert empty == 2, _get_json(host_b, "/debug/queries")["queries"]
