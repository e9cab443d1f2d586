"""Shared helpers for the multi-process pod tests (test_pod.py,
test_pod_cluster.py) and their child scripts.

One copy of the env contract: children get CPU JAX decided in the PARENT
environment (``JAX_PLATFORMS=cpu``), before their interpreter starts.
"""

from __future__ import annotations

import contextlib
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def cpu_env() -> dict:
    """A child env with CPU JAX selected."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["PILOSA_TPU_MESH_MIN_SLICES"] = "1"
    return env


def pod_env(proc_id: int, jax_port: int, peers: list[str],
            cpu_devices: int = 2) -> dict:
    """cpu_env plus the pod process contract (parallel.multihost/pod)."""
    env = cpu_env()
    env.update({
        "PILOSA_TPU_DIST_COORDINATOR": f"localhost:{jax_port}",
        "PILOSA_TPU_DIST_NUM_PROCS": str(len(peers)),
        "PILOSA_TPU_DIST_PROC_ID": str(proc_id),
        "PILOSA_TPU_DIST_CPU_DEVICES": str(cpu_devices),
        "PILOSA_TPU_POD_PEERS": ",".join(peers),
    })
    return env


class ChildSet:
    """Spawn child processes with log files, kill + close on exit."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.procs: dict[str, subprocess.Popen] = {}
        self._stack = contextlib.ExitStack()

    def spawn(self, name: str, argv: list[str], env: dict,
              pipe: bool = False):
        """pipe=True captures stdout/stderr (for the driver child);
        otherwise output goes to <name>.log — a PIPE nothing drains
        would wedge a long-lived worker on a full buffer."""
        if pipe:
            stdout = stderr = subprocess.PIPE
        else:
            stdout = stderr = self._stack.enter_context(
                open(self.log_path(name), "w"))
        p = subprocess.Popen(argv, env=env, stdout=stdout, stderr=stderr,
                             text=True)
        self.procs[name] = p
        return p

    def log_path(self, name: str):
        return self.tmp_path / f"{name}.log"

    def logs_tail(self, n: int = 2000) -> str:
        out = []
        for name in self.procs:
            path = self.log_path(name)
            if path.exists():
                out.append(f"{name}:\n{path.read_text()[-n:]}")
        return "\n".join(out)

    def cleanup(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
        self._stack.close()


# ---- helpers for the child scripts themselves --------------------------


def http(method: str, host: str, path: str, body: bytes = b"",
         content_type: str = "application/json") -> bytes:
    req = urllib.request.Request(
        f"http://{host}{path}", data=body, method=method,
        headers={"Content-Type": content_type, "Accept": content_type})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.read()
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"{method} {path}: {e.code}: "
            f"{e.read().decode(errors='replace')[:500]}") from e


def query(host: str, index: str, pql: str):
    raw = http("POST", host, f"/index/{index}/query", pql.encode())
    return json.loads(raw)["results"]


def wait_up(host: str, deadline: float = 120) -> None:
    t0 = time.time()
    while time.time() - t0 < deadline:
        try:
            http("GET", host, "/version")
            return
        except Exception:  # noqa: BLE001 - keep polling until deadline
            time.sleep(0.3)
    raise RuntimeError(f"{host} not up")


def child_main(fn) -> None:
    """Run a child's main() and hard-exit either way: jax.distributed's
    atexit shutdown can hang on dead peers, and the launcher only
    watches rc/stdout."""
    try:
        fn()
    except BaseException:
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    os._exit(0)
