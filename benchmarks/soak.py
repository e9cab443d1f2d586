"""Longevity soak: a 2-node gossip cluster under continuous mixed load.

Not a pytest (it runs for minutes by design) — a reproducible harness
that prints its own PASS/FAIL verdict. It exercises, at once, the surfaces
that only misbehave over time: WAL growth + snapshotting under a write
storm (MAX_OP_N forced low -> snapshot storms), anti-entropy sweeps
against live writes, gossip probes across BOTH a mid-soak clean restart
AND a mid-soak SIGKILL of node B (WAL replay + torn-tail recovery under
load), the batched write path (one writer issues 100-call pipelined
bodies), and the Python heap (sampled via /debug/pprof/heap). Per-op
write latencies are collected for p50/p99/p999; the verdict also fails
on RSS growth (leak detection over the run).

Usage: python benchmarks/soak.py [minutes]   (default 10)

Prints one JSON line per minute (ops so far, error count, RSS of each
server, traced heap) and a final PASS/FAIL verdict with the consistency
check: every sampled row's Bitmap must equal the model on BOTH nodes
after a final anti-entropy pass.
"""

from __future__ import annotations

import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tests"))

from podenv import cpu_env, free_port, wait_up  # noqa: E402

SLICE_SPAN = 4 * (1 << 20)   # 4 slices of columns
ROWS = 64


def http(method, host, path, body=b"", timeout=60):
    req = urllib.request.Request(f"http://{host}{path}", data=body,
                                 method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def query(host, pql, timeout=60):
    raw = http("POST", host, "/index/si/query", pql.encode(),
               timeout=timeout)
    return json.loads(raw)["results"]


class Node:
    def __init__(self, name, data_dir, port, internal_port, seed=""):
        self.name = name
        self.data_dir = data_dir
        self.port = port
        self.host = f"127.0.0.1:{port}"
        self.internal_port = internal_port
        self.seed = seed
        self.log = open(os.path.join(data_dir, "..", f"{name}.log"), "a")
        self.proc = None

    def start(self):
        env = cpu_env()
        env["PILOSA_TPU_MESH"] = "0"  # device-free children: a kill or
        # crash here must never touch the shared accelerator state
        env["PILOSA_TPU_MAX_OP_N"] = "200"  # snapshot storm cadence
        argv = [sys.executable, "-m", "pilosa_tpu.cli", "server",
                "-d", self.data_dir, "-b", self.host,
                "--cluster.type", "gossip",
                "--cluster.hosts", CLUSTER_HOSTS,
                "--cluster.replicas", "2",
                "--cluster.internal-port", str(self.internal_port),
                "--anti-entropy.interval", "45s",
                "--log-path", os.path.join(self.data_dir, "..",
                                           f"{self.name}-server.log")]
        if self.seed:
            argv += ["--cluster.gossip-seed", self.seed]
        self.proc = subprocess.Popen(argv, env=env, stdout=self.log,
                                     stderr=self.log, cwd=_REPO)
        wait_up(self.host)

    def stop(self, sig=signal.SIGINT, timeout=30):
        if self.proc is None:
            return
        self.proc.send_signal(sig)
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.proc = None

    def rss_mb(self):
        if self.proc is None:
            return 0.0
        try:
            with open(f"/proc/{self.proc.pid}/statm") as f:
                return int(f.read().split()[1]) * 4096 / (1 << 20)
        except OSError:
            return 0.0


def main():
    minutes = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0
    base = f"/tmp/pilosa-soak-{os.getpid()}"
    os.makedirs(base, exist_ok=True)
    pa, pb = free_port(), free_port()
    ga, gb = free_port(), free_port()
    global CLUSTER_HOSTS
    CLUSTER_HOSTS = f"127.0.0.1:{pa},127.0.0.1:{pb}"

    for name, port in (("a", pa), ("b", pb)):
        os.makedirs(f"{base}/{name}", exist_ok=True)
    na = Node("a", f"{base}/a", pa, ga)
    nb = Node("b", f"{base}/b", pb, gb, seed=f"127.0.0.1:{ga}")
    na.start()
    nb.start()
    nodes = [na, nb]

    http("POST", na.host, "/index/si", b"{}")
    http("POST", na.host, "/index/si/frame/sf", b"{}")
    time.sleep(2)  # let the schema gossip

    model = {r: set() for r in range(ROWS)}
    # Every cell ever SET (never pruned): a final extra bit on a
    # set-then-cleared cell is an anti-entropy RESURRECTION — a clear
    # whose replica fan-out was mid-flight when the 45 s sweep read
    # its block gets undone by the 2-copy set-biased MergeBlock
    # majority ((2+1)//2 = 1; the reference has the same arithmetic).
    # Proven deterministically in tests/test_server.py::
    # test_anti_entropy_resurrects_clear_racing_the_sweep; observed
    # ~1-2 times per 60-min run. Tolerated up to a bound and REPORTED;
    # never-set extras and missing sets remain hard failures.
    set_ever = {r: set() for r in range(ROWS)}
    # Bits whose final state is unknowable: the write errored
    # client-side (restart window) but may have applied server-side —
    # at-least-once semantics, exactly like the reference's replicated
    # writes (no rollback of a partially-applied fan-out).
    uncertain = {r: set() for r in range(ROWS)}
    model_mu = threading.Lock()
    stop = threading.Event()
    stats = {"writes": 0, "reads": 0, "errors": 0, "restarts": 0}

    write_lat = []
    lat_mu = threading.Lock()

    # In-flight op registry per cell: (set_count, clear_count). A SET
    # overlapping an in-flight CLEAR on the same cell (or vice versa)
    # is order-ambiguous — the server linearizes by arrival, the model
    # by response order, and they can disagree. Any such overlap marks
    # the cell uncertain (monotone). A 60-min run once failed its
    # check by exactly ONE bit this way (~1-in-10^6 writes at this
    # cell-space, which is why shorter soaks never saw it); both nodes
    # agreed with each other, proving the storage converged and only
    # the harness model was ambiguous.
    inflight: dict = {}

    def _begin(r, c, is_set):
        with model_mu:
            s, cl = inflight.get((r, c), (0, 0))
            if (cl if is_set else s):
                uncertain[r].add(c)
            inflight[(r, c)] = (s + (1 if is_set else 0),
                                cl + (0 if is_set else 1))

    def _end(r, c, is_set, conflicted_ok):
        with model_mu:
            s, cl = inflight[(r, c)]
            if (cl if is_set else s):
                uncertain[r].add(c)
            s, cl = (s - 1, cl) if is_set else (s, cl - 1)
            if s or cl:
                inflight[(r, c)] = (s, cl)
            else:
                del inflight[(r, c)]
            if conflicted_ok:
                (model[r].add if is_set else model[r].discard)(c)
                if is_set:
                    set_ever[r].add(c)

    def writer(seed):
        rng = random.Random(seed)
        while not stop.is_set():
            r = rng.randrange(ROWS)
            c = rng.randrange(SLICE_SPAN)
            setbit = rng.random() < 0.9
            host = nodes[rng.randrange(2)].host
            verb = "SetBit" if setbit else "ClearBit"
            _begin(r, c, setbit)
            t0 = time.perf_counter()
            try:
                query(host, f'{verb}(frame="sf", rowID={r},'
                            f' columnID={c})', timeout=30)
            except Exception:
                stats["errors"] += 1  # restart window errors tolerated
                with model_mu:
                    uncertain[r].add(c)
                _end(r, c, setbit, conflicted_ok=False)
                time.sleep(0.5)
                continue
            el = time.perf_counter() - t0
            with lat_mu:
                write_lat.append(el)
                if len(write_lat) > 2_000_000:
                    del write_lat[:1_000_000]
            # NOTE: uncertain is MONOTONE — a cell touched by an
            # errored request stays unverifiable: the timed-out
            # request's bytes can still be sitting in a server
            # connection buffer and apply AFTER this success
            # (at-least-once, same as the reference's replicated
            # writes). Round-5's first 60-min run failed its
            # consistency check by exactly one such zombie bit.
            _end(r, c, setbit, conflicted_ok=True)
            stats["writes"] += 1

    def batch_writer(seed):
        """Round-5 batched write path: 100-call bodies through the
        executor mutate-batch run + the fragments' native batch
        engine."""
        rng = random.Random(seed)
        while not stop.is_set():
            r = rng.randrange(ROWS)
            cols = [rng.randrange(SLICE_SPAN) for _ in range(100)]
            host = nodes[rng.randrange(2)].host
            body = "\n".join(
                f'SetBit(frame="sf", rowID={r}, columnID={c})'
                for c in cols)
            for c in cols:
                _begin(r, c, True)
            try:
                query(host, body, timeout=60)
            except Exception:
                stats["errors"] += 1
                with model_mu:
                    uncertain[r].update(cols)
                for c in cols:
                    _end(r, c, True, conflicted_ok=False)
                time.sleep(0.5)
                continue
            for c in cols:
                _end(r, c, True, conflicted_ok=True)
            stats["writes"] += 100

    def reader(seed):
        rng = random.Random(seed)
        while not stop.is_set():
            host = nodes[rng.randrange(2)].host
            r = rng.randrange(ROWS)
            try:
                if rng.random() < 0.5:
                    query(host, f'Count(Bitmap(frame="sf", rowID={r}))',
                          timeout=30)
                else:
                    query(host, 'TopN(frame="sf", n=5)', timeout=30)
            except Exception:
                stats["errors"] += 1
                time.sleep(0.5)
                continue
            stats["reads"] += 1
            time.sleep(0.02)

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(2)]
    threads += [threading.Thread(target=batch_writer, args=(5,),
                                 daemon=True)]
    threads += [threading.Thread(target=reader, args=(10 + i,),
                                 daemon=True) for i in range(2)]
    for t in threads:
        t.start()

    t0 = time.monotonic()
    deadline = t0 + minutes * 60
    restarted = False
    killed = False
    minute = 0
    rss_curve = []
    http("GET", na.host, "/debug/pprof/heap")  # arm tracing on A
    while time.monotonic() < deadline:
        time.sleep(min(60, max(1, deadline - time.monotonic())))
        minute += 1
        heap = http("GET", na.host,
                    "/debug/pprof/heap?n=1").decode().splitlines()[0]
        rss_curve.append((round(na.rss_mb(), 1), round(nb.rss_mb(), 1)))
        print(json.dumps({
            "minute": minute, **stats,
            "rss_a_mb": rss_curve[-1][0],
            "rss_b_mb": rss_curve[-1][1],
            "heap_a": heap}), flush=True)
        if not restarted and time.monotonic() - t0 > minutes * 20:
            # Mid-soak (1/3): clean-restart node B under load.
            restarted = True
            stats["restarts"] += 1
            nb.stop()
            time.sleep(2)
            nb.start()
            print(json.dumps({"event": "restarted b"}), flush=True)
        elif killed is False and time.monotonic() - t0 > minutes * 40:
            # Mid-soak (2/3): SIGKILL node B — WAL replay + torn-tail
            # trim under load, the crash-durability path at soak scale.
            killed = True
            stats["restarts"] += 1
            nb.stop(sig=signal.SIGKILL, timeout=10)
            time.sleep(2)
            nb.start()
            print(json.dumps({"event": "sigkilled+revived b"}),
                  flush=True)

    stop.set()
    for t in threads:
        t.join(timeout=30)

    # Settle, then final consistency: both nodes answer the model for a
    # sample of rows (anti-entropy has had >1 sweep since the restart).
    time.sleep(3)
    rng = random.Random(0)
    failures = []
    resurrections = []
    for r in rng.sample(range(ROWS), 16):
        with model_mu:
            base = model[r] - uncertain[r]
            upper = model[r] | uncertain[r]
            ever = set_ever[r]
        for node in nodes:
            got = set(query(node.host,
                            f'Bitmap(frame="sf", rowID={r})')[0]["bits"])
            extra = got - upper
            rez = extra & ever       # set-then-cleared: resurrection
            hard_extra = extra - ever  # never set: invented bit
            if hard_extra or (base - got):
                failures.append((node.name, r, len(hard_extra),
                                 len(base - got),
                                 sorted(hard_extra)[:3],
                                 sorted(base - got)[:3]))
            for c in rez:
                resurrections.append((node.name, r, c))
    if len(resurrections) > 20:
        failures.append(("resurrection-storm", len(resurrections)))
    # Latency percentiles over the whole run (tail = snapshot storms,
    # restarts, anti-entropy interference).
    with lat_mu:
        lats = sorted(write_lat)
    pct = {}
    if lats:
        for name, q in (("p50", 0.5), ("p99", 0.99), ("p999", 0.999)):
            pct[name + "_ms"] = round(
                lats[min(len(lats) - 1, int(q * len(lats)))] * 1e3, 2)
    # RSS flatness: compare each node's median RSS over the first vs
    # last quarter of the run; a leak shows as unbounded growth.
    rss_verdict = "flat"
    if len(rss_curve) >= 8:
        qn = len(rss_curve) // 4
        for side, name in ((0, "a"), (1, "b")):
            first = sorted(c[side] for c in rss_curve[:qn])[qn // 2]
            last = sorted(c[side] for c in rss_curve[-qn:])[qn // 2]
            if last > 2.0 * first + 200:
                rss_verdict = f"LEAK:{name} {first}->{last}MB"
                failures.append(("rss", name, first, last))
    verdict = "PASS" if not failures else f"FAIL: {failures[:4]}"
    print(json.dumps({"verdict": verdict,
                      "resurrections": sorted(resurrections)[:8],
                      **stats, **pct,
                      "rss": rss_verdict,
                      "minutes": minutes}), flush=True)
    na.stop()
    nb.stop()
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
