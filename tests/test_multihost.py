"""Multi-host layer tests (single-process forms).

Real pod hardware isn't available; what IS testable: the local-shard →
global-array assembly and the pod-wide count/topn programs in their
1-process degenerate form (same code path, process_count()==1), plus
jax.distributed bootstrap in a subprocess so its global state can't
leak into this suite.
"""

import os
import subprocess
import sys

import numpy as np

from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import multihost


def _popcount(a):
    return int(np.bitwise_count(a).sum())


class TestSingleProcessForms:
    def test_initialize_without_env_is_noop(self, monkeypatch):
        monkeypatch.delenv("PILOSA_TPU_DIST_COORDINATOR", raising=False)
        assert multihost.initialize_from_env() is False

    def test_process_slice_range_whole_axis(self):
        # 1-process degenerate form: the whole axis belongs to us.
        lo, hi = multihost.process_slice_range(16)
        assert (lo, hi) == (0, 16)

    def test_count_matches_single_host_path(self):
        rng = np.random.default_rng(0)
        mesh = multihost.pod_mesh()
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        S, W = n_dev * 2, 256
        leaves = rng.integers(0, 2**32, size=(2, S, W), dtype=np.uint32)
        expr = ("and", ("leaf", 0), ("leaf", 1))
        got = multihost.count_expr(mesh, expr, leaves)
        assert got == mesh_mod.count_expr(mesh, expr, leaves)
        assert got == _popcount(leaves[0] & leaves[1])

    def test_topn_matches_single_host_path(self):
        rng = np.random.default_rng(1)
        mesh = multihost.pod_mesh()
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        S, R, W = n_dev * 2, 5, 128
        rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
        src = rng.integers(0, 2**32, size=(1, S, W), dtype=np.uint32)
        got = multihost.topn_exact(mesh, ("leaf", 0), rows, src)
        assert got == mesh_mod.topn_exact(mesh, ("leaf", 0), rows, src)
        want = [_popcount(rows[:, r, :] & src[0]) for r in range(R)]
        assert got == want

    def test_count_exprs_batch_matches_singles(self):
        rng = np.random.default_rng(3)
        mesh = multihost.pod_mesh()
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        S, W = n_dev * 2, 256
        leaves = rng.integers(0, 2**32, size=(3, S, W), dtype=np.uint32)
        exprs = (("leaf", 0),
                 ("and", ("leaf", 0), ("leaf", 1)),
                 ("or", ("leaf", 1), ("leaf", 2)))
        got = multihost.count_exprs(mesh, exprs, leaves)
        assert got == [multihost.count_expr(mesh, e, leaves)
                       for e in exprs]

    def test_topn_filtered_matches_single_host_path(self):
        rng = np.random.default_rng(2)
        mesh = multihost.pod_mesh()
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        S, R, W = n_dev * 2, 5, 128
        rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
        src = rng.integers(0, 2**32, size=(1, S, W), dtype=np.uint32)
        for threshold, tanimoto in ((3, 0), (W * 16, 0), (1, 40)):
            got = multihost.topn_exact(mesh, ("leaf", 0), rows, src,
                                       threshold=threshold,
                                       tanimoto=tanimoto)
            assert got == mesh_mod.topn_exact(
                mesh, ("leaf", 0), rows, src,
                threshold=threshold, tanimoto=tanimoto), \
                (threshold, tanimoto)


class TestDistributedBootstrap:
    def test_one_process_pod_in_subprocess(self):
        """jax.distributed.initialize + pod count, isolated subprocess."""
        code = """
import jax
import numpy as np
import sys
sys.path.insert(0, %r)
from pilosa_tpu.parallel import multihost
assert multihost.initialize_from_env() is True
assert jax.process_count() == 1
mesh = multihost.pod_mesh()
S = mesh.shape["slices"] * 2
leaves = np.ones((1, S, 64), dtype=np.uint32)
lo, hi = multihost.process_slice_range(S)
assert (lo, hi) == (0, S)
got = multihost.count_expr(mesh, ("leaf", 0), leaves[:, lo:hi])
assert got == S * 64, got
print("POD OK", got)
"""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        import socket
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            port = sk.getsockname()[1]
        env = dict(os.environ)
        env.update({
            "PILOSA_TPU_DIST_COORDINATOR": f"127.0.0.1:{port}",
            "PILOSA_TPU_DIST_NUM_PROCS": "1",
            "PILOSA_TPU_DIST_PROC_ID": "0",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        })
        out = None
        for _attempt in range(2):  # retry once on coordinator-port races
            out = subprocess.run([sys.executable, "-c", code % repo],
                                 capture_output=True, text=True, env=env,
                                 timeout=240)
            if out.returncode == 0:
                break
            with socket.socket() as sk:
                sk.bind(("127.0.0.1", 0))
                env["PILOSA_TPU_DIST_COORDINATOR"] = \
                    f"127.0.0.1:{sk.getsockname()[1]}"
        assert out.returncode == 0, out.stderr[-2000:]
        assert "POD OK" in out.stdout
