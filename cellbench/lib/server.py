"""The server child and the routes a loader and a client call.

``server_env``, ``start_server``, ``stop_server``, ``Http``, the waits,
``load`` and ``read_surfaces`` are copies from ``chip_smoke.py`` (PR 21),
with the configuration's names and sizes as parameters. The child is
``traced_server.py`` in every run: the contract wants the device's peak
memory, which only the process that holds the chip can read. This
process never imports jax while the child lives.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .data import Reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHILD = [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "traced_server.py")]

# A server that came up without these set is the server a user gets.
_STEERING_PREFIXES = ("PILOSA_TPU_MESH", "PILOSA_TPU_COST_",
                      "PILOSA_TPU_WARMUP", "PILOSA_TPU_PALLAS",
                      "PILOSA_TPU_SPARSE_UPLOAD")

# Slices per import call (~6e6 bits at baseline-c4's densities) and the
# import calls in flight: one chunk is cut from the reference while the
# server applies the one before it.
CHUNK_SLICES = 4
IMPORT_WORKERS = 4


class BenchFailure(Exception):
    """A phase could not run to its end (server died, HTTP error, no
    chip)."""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_env() -> dict:
    """The child's environment: this process's, minus the CPU pin this
    sandbox exports and minus anything that steers routing."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    for k in list(env):
        if k.startswith(_STEERING_PREFIXES):
            del env[k]
    return env


def start_server(data_dir: str, log_path: str, ctl_dir: str,
                 child: list[str] = CHILD):
    """``child`` is the script to start and any arguments that come
    before the control directory."""
    port = _free_port()
    host = f"127.0.0.1:{port}"
    os.makedirs(ctl_dir, exist_ok=True)
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, *child, ctl_dir, ROOT,
             "-d", data_dir, "--bind", host],
            env=server_env(),
            stdout=log, stderr=subprocess.STDOUT, cwd=ROOT)
    return proc, host


def stop_server(proc) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


class Http:
    """Plain HTTP to the server, one connection per request; every call
    checks the child is still alive so a dead server is named as such."""

    def __init__(self, host: str, proc, timeout: float = 900.0):
        self.host, self.proc, self.timeout = host, proc, timeout

    def request(self, method: str, path: str, body: bytes | None = None):
        if self.proc.poll() is not None:
            raise BenchFailure(
                f"server exited early with code {self.proc.returncode}")
        conn = http.client.HTTPConnection(self.host, timeout=self.timeout)
        try:
            conn.request(method, path, body)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise BenchFailure(f"{method} {path}: HTTP {resp.status}:"
                               f" {data[:300]!r}")
        return data, resp

    def get_json(self, path: str) -> dict:
        return json.loads(self.request("GET", path)[0])

    def query(self, index: str, pql: str) -> list:
        data, _ = self.request("POST", f"/index/{index}/query",
                               pql.encode())
        return json.loads(data)["results"]


def wait_up(http: Http, timeout: float = 300.0) -> dict:
    deadline = time.monotonic() + timeout
    while True:
        try:
            return http.get_json("/status")
        except OSError:
            if time.monotonic() > deadline:
                raise BenchFailure("server did not answer /status in"
                                   f" {timeout:.0f}s")
            time.sleep(0.25)


def wait_warmup(http: Http, timeout: float = 600.0) -> dict:
    deadline = time.monotonic() + timeout
    while True:
        warm = http.get_json("/status").get("warmup") or {}
        if warm.get("state") not in ("pending", "running"):
            return warm
        if time.monotonic() > deadline:
            raise BenchFailure(f"warmup still {warm.get('state')} after"
                               f" {timeout:.0f}s")
        time.sleep(0.25)


def load(http: Http, ref: Reference, config: dict) -> dict:
    """Schema + data through the HTTP routes a user's loader calls."""
    from pilosa_tpu.cluster.client import Client
    index, frame = config["index"], config["frame"]
    bsi = config.get("bsi")
    t0 = time.perf_counter()
    client = Client(http.host, timeout=900.0)
    client.create_index(index)
    client.create_frame(index, frame, {"cacheType": "ranked"})
    if bsi:
        client.create_frame(index, bsi["frame"])
        client.create_field(index, bsi["frame"], bsi["field"],
                            int(bsi["min"]), int(bsi["max"]))

    def one_chunk(s0: int) -> int:
        rows, cols = ref.slice_positions(
            s0, min(s0 + CHUNK_SLICES, ref.n_slices))
        worker = Client(http.host, timeout=900.0)
        try:
            worker.import_arrays(index, frame, rows, cols)
        finally:
            worker.close()
        return len(rows)

    t_schema = time.perf_counter()
    with ThreadPoolExecutor(IMPORT_WORKERS) as pool:
        n_bits = sum(pool.map(one_chunk,
                              range(0, ref.n_slices, CHUNK_SLICES)))
    t_bits = time.perf_counter()
    if bsi:
        client.import_field_values(index, bsi["frame"], bsi["field"],
                                   ref.bsi_cols, ref.bsi_vals)
    client.close()
    t1 = time.perf_counter()
    return {"bits": n_bits, "bitsSeconds": t_bits - t_schema,
            "bsiColumns": 0 if not bsi else len(ref.bsi_cols),
            "bsiSeconds": t1 - t_bits, "seconds": t1 - t0}


def read_surfaces(http: Http, after: float) -> dict:
    """/status and /debug/vars; /status' runtime block is a periodic
    sample, so wait for one taken after ``after`` (wall clock)."""
    deadline = time.monotonic() + 60.0
    while True:
        status = http.get_json("/status")
        sampled = (status.get("runtime") or {}).get("sampledAt", 0)
        if sampled >= after or time.monotonic() > deadline:
            break
        time.sleep(0.25)
    return {"status": status, "vars": http.get_json("/debug/vars")}


def control(ctl_dir: str, request: str, reply: str, body: str = "",
            timeout: float = 120.0) -> dict:
    """Ask the child's control thread for something: create the file
    ``request`` and wait for the file ``reply``."""
    reply_path = os.path.join(ctl_dir, reply)
    if os.path.exists(reply_path):
        os.remove(reply_path)
    tmp = os.path.join(ctl_dir, request + ".tmp")
    with open(tmp, "w") as f:
        f.write(body)
    os.replace(tmp, os.path.join(ctl_dir, request))
    deadline = time.monotonic() + timeout
    while not os.path.exists(reply_path):
        if time.monotonic() > deadline:
            raise BenchFailure(f"the server child did not answer"
                               f" {request!r} in {timeout:.0f}s")
        time.sleep(0.02)
    with open(reply_path) as f:
        return json.load(f)
