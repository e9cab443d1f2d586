"""Device-mesh slice executor tests on the 8-device virtual CPU mesh
(conftest.py sets xla_force_host_platform_device_count=8)."""

import numpy as np
import pytest

from pilosa_tpu.parallel import mesh as mesh_mod


def _popcount(arr: np.ndarray) -> int:
    return int(np.unpackbits(arr.view(np.uint8)).sum())


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


class TestMakeMesh:
    def test_shapes(self):
        m = mesh_mod.make_mesh(8)
        assert m.devices.shape == (1, 8)
        m2 = mesh_mod.make_mesh(8, rows=2)
        assert m2.devices.shape == (2, 4)

    def test_too_many_devices(self):
        with pytest.raises(ValueError):
            mesh_mod.make_mesh(512)


class TestCountOp:
    @pytest.mark.parametrize("op,npop", [
        ("and", np.bitwise_and),
        ("or", np.bitwise_or),
        ("xor", np.bitwise_xor),
        ("andnot", lambda a, b: np.bitwise_and(a, np.bitwise_not(b))),
    ])
    def test_matches_numpy(self, rng, op, npop):
        m = mesh_mod.make_mesh(8)
        a = rng.integers(0, 2**32, size=(16, 512), dtype=np.uint32)
        b = rng.integers(0, 2**32, size=(16, 512), dtype=np.uint32)
        got = mesh_mod.count_op(m, op, mesh_mod.shard_slices(m, a),
                                mesh_mod.shard_slices(m, b))
        assert got == _popcount(npop(a, b))

    def test_zero_padding_is_identity(self, rng):
        m = mesh_mod.make_mesh(8)
        a = rng.integers(0, 2**32, size=(5, 256), dtype=np.uint32)
        b = rng.integers(0, 2**32, size=(5, 256), dtype=np.uint32)
        ap = mesh_mod.pad_to_multiple(a, 8)
        bp = mesh_mod.pad_to_multiple(b, 8)
        assert ap.shape[0] == 8
        got = mesh_mod.count_op(m, "and", mesh_mod.shard_slices(m, ap),
                                mesh_mod.shard_slices(m, bp))
        assert got == _popcount(np.bitwise_and(a, b))


class TestTopN:
    def test_matches_numpy(self, rng):
        m = mesh_mod.make_mesh(8, rows=2)   # 2×4 grid: both axes real
        S, R, W = 8, 16, 128
        rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
        src = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
        vals, ids = mesh_mod.topn_counts(
            m, "and",
            mesh_mod.shard_slices(m, rows), mesh_mod.shard_slices(m, src),
            k=4)
        want = np.array([
            _popcount(np.bitwise_and(rows[:, r, :], src))
            for r in range(R)])
        order = np.argsort(-want, kind="stable")
        assert list(vals) == list(want[order][:4])
        # ids must be a valid argmax set (ties may reorder).
        assert sorted(want[ids]) == sorted(vals)


class TestQueryStep:
    def test_fused_step(self, rng):
        m = mesh_mod.make_mesh(8, rows=2)
        S, R, W = 8, 8, 128
        a = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
        b = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
        rows = rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint32)
        n_i, n_u, vals, ids = mesh_mod.query_step(
            m, mesh_mod.shard_slices(m, a), mesh_mod.shard_slices(m, b),
            mesh_mod.shard_slices(m, rows), k=3)
        inter = np.bitwise_and(a, b)
        assert n_i == _popcount(inter)
        assert n_u == _popcount(np.bitwise_or(a, b))
        want = np.array([
            _popcount(np.bitwise_and(rows[:, r, :], inter))
            for r in range(R)])
        assert list(vals) == sorted(want, reverse=True)[:3]


class TestSlabOrigins:
    @pytest.mark.parametrize("n_devices", [1, 8])
    def test_one_specialisation_whatever_the_upload(self, n_devices):
        """A slab uploaded sparse (the densify program's output) and one
        uploaded dense (device_put) name the same sharding, so a count
        program compiles once for a shape — not once a mix of origins
        among its leaves. On a one-device mesh (one chip) jit hands an
        unnamed output back as ``P()``: the 2^k ~2 ms re-specialisations
        that were most of ``compiles_in_window``."""
        from pilosa_tpu.ops import packed
        mesh = mesh_mod.make_mesh(n_devices)
        n = 8
        dense = np.zeros((n, packed.WORDS_PER_SLICE), dtype=np.uint32)
        dense[:, :4] = 7
        thin = np.zeros(packed.WORDS_PER_SLICE, dtype=np.uint32)
        thin[1:4] = 5, 6, 7
        sparse, _, _ = packed.pack_slab([packed.unpack_to_bitmap(thin)] * n)
        a = mesh_mod.shard_slices(mesh, dense)
        b = mesh_mod.densify_sharded(
            mesh, *sparse, interpret=True)
        assert a.sharding == b.sharding
        expr = ("and", ("leaf", 0), ("leaf", 1))
        want_ab = _popcount(np.asarray(a) & np.asarray(b))
        assert mesh_mod.count_expr_sharded(mesh, expr, [a, a]) \
            == _popcount(np.asarray(a))
        compiled = mesh_mod.compile_stats()["firstCalls"]
        for leaves in ([a, b], [b, a], [b, b]):
            got = mesh_mod.count_expr_sharded(mesh, expr, leaves)
            assert got == (want_ab if leaves[0] is not leaves[1]
                           else _popcount(np.asarray(b)))
        assert mesh_mod.compile_stats()["firstCalls"] == compiled


class TestCompileCache:
    def test_one_rule_for_the_directory(self, monkeypatch, tmp_path,
                                        caplog):
        """JAX_COMPILATION_CACHE_DIR set: jax already has the
        directory and arm_compile_cache leaves it alone. Unset: the one
        fixed directory inside the checkout. First call wins."""
        import os

        import jax

        from pilosa_tpu.parallel import mesh as mesh_mod
        from pilosa_tpu.utils import cache_dir
        prior_armed = mesh_mod._compile_cache_dir
        prior_dir = jax.config.jax_compilation_cache_dir
        prior_min = jax.config.jax_persistent_cache_min_compile_time_secs
        try:
            # env set: config untouched, the env's directory reported
            monkeypatch.setattr(mesh_mod, "_compile_cache_armed", False)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / "from-env"))
            jax.config.update("jax_compilation_cache_dir", "sentinel")
            assert mesh_mod.arm_compile_cache() == str(
                tmp_path / "from-env")
            assert jax.config.jax_compilation_cache_dir == "sentinel"
            # env unset: <checkout>/.cache/xla, created
            monkeypatch.setattr(mesh_mod, "_compile_cache_armed", False)
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            want = cache_dir("xla")
            repo = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            assert want == os.path.join(repo, ".cache", "xla")
            assert mesh_mod.arm_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isdir(want)
            # idempotent: a later call is a no-op even with env changed
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            assert mesh_mod.arm_compile_cache() == want
            # env unset and the default not creatable (a read-only
            # install): no cache, said on the log and in the stats —
            # not an exception out of Server.open
            monkeypatch.setattr(mesh_mod, "_compile_cache_armed", False)
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            (tmp_path / "file").write_text("")
            monkeypatch.setattr(
                "pilosa_tpu.utils.cache_dir",
                lambda *parts: str(tmp_path.joinpath("file", *parts)))
            jax.config.update("jax_compilation_cache_dir", "sentinel")
            with caplog.at_level("WARNING", logger="pilosa_tpu.mesh"):
                assert mesh_mod.arm_compile_cache() is None
            assert "JAX_COMPILATION_CACHE_DIR" in caplog.text
            assert jax.config.jax_compilation_cache_dir == "sentinel"
            assert mesh_mod.compile_stats()["persistentCacheDir"] is None
        finally:
            monkeypatch.undo()
            mesh_mod._compile_cache_dir = prior_armed
            # jax.config is process-global: restore so later tests are
            # order-independent.
            jax.config.update("jax_compilation_cache_dir", prior_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                prior_min)
