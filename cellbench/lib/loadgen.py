"""Closed-loop load: a fixed number of clients, each with one keep-alive
connection, each sending its next request when the last one is read."""

from __future__ import annotations

import http.client
import json
import threading
import time

from .traffic import Generator, Op


class Record:
    """One request as the client saw it. ``sent``/``done`` are wall-clock
    seconds (the trace and the server's samples use the same clock),
    ``latency_s`` is from the monotonic clock."""

    __slots__ = ("op", "sent", "done", "latency_s", "status", "results",
                 "stats", "error")

    def __init__(self, op: Op):
        self.op = op
        self.sent = self.done = self.latency_s = 0.0
        self.status = 0
        self.results = None
        self.stats: dict = {}
        self.error = None

    @property
    def ok(self) -> bool:
        return self.status == 200 and self.results is not None


def _client(host: str, path: str, gen: Generator, take, out: list,
            timeout: float) -> None:
    conn = http.client.HTTPConnection(host, timeout=timeout)
    try:
        while take():
            rec = Record(gen.next())
            body = rec.op.pql.encode()
            rec.sent = time.time()
            t0 = time.perf_counter()
            try:
                conn.request("POST", path, body)
                resp = conn.getresponse()
                data = resp.read()
                rec.latency_s = time.perf_counter() - t0
                rec.status = resp.status
                if resp.status == 200:
                    rec.results = json.loads(data)["results"]
                    rec.stats = json.loads(
                        resp.getheader("X-Pilosa-Stats") or "{}")
                else:
                    rec.error = data[:200].decode("utf-8", "replace")
            except (OSError, http.client.HTTPException, ValueError) as e:
                rec.latency_s = time.perf_counter() - t0
                rec.error = f"{type(e).__name__}: {e}"
                conn.close()
                conn = http.client.HTTPConnection(host, timeout=timeout)
            rec.done = rec.sent + rec.latency_s
            out.append(rec)
    finally:
        conn.close()


def drive(host: str, index: str, gen: Generator, clients: int,
          seconds: float | None = None, requests: int | None = None,
          timeout: float = 120.0) -> tuple[list[Record], float, float]:
    """Run the closed loop until ``seconds`` have passed (no request is
    started after that; those in flight are read to their end and count)
    or until ``requests`` have been started. Returns the records in
    order of completion and the wall-clock start and end of the window,
    the end being when the last answer was read."""
    mu = threading.Lock()
    left = [requests]
    t_start = time.time()
    deadline = None if seconds is None else time.monotonic() + seconds

    def take() -> bool:
        if deadline is not None and time.monotonic() >= deadline:
            return False
        if left[0] is None:
            return True
        with mu:
            if left[0] <= 0:
                return False
            left[0] -= 1
            return True

    outs: list[list[Record]] = [[] for _ in range(clients)]
    threads = [threading.Thread(
        target=_client, name=f"cellbench-client-{i}",
        args=(host, f"/index/{index}/query", gen, take, outs[i], timeout))
        for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = time.time()
    records = sorted((r for out in outs for r in out),
                     key=lambda r: r.done)
    return records, t_start, t_end
