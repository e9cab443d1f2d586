"""Test configuration.

Tests run on CPU with a virtual 8-device mesh so multi-chip sharding
(`shard_map` over a `jax.sharding.Mesh`) compiles and executes without TPU
hardware: ``JAX_PLATFORMS=cpu`` plus the 8-virtual-device XLA flag, set
here before jax is imported and inherited by every subprocess.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses
# Deterministic routing in tests: the calibrated device/host cost model
# measures THIS machine and could veto device paths that device-path
# tests assert engage. Cost-model behavior is tested explicitly with
# injected calibrations (tests/test_costmodel.py).
os.environ.setdefault("PILOSA_TPU_COST_MODEL", "0")
# Cold-start warmup compiles XLA programs on every Server.open — fine
# for one real server, a tax on the dozens the suite spawns. Warmup
# behavior is tested explicitly (tests/test_sched.py enables it).
os.environ.setdefault("PILOSA_TPU_WARMUP", "0")


def pytest_configure(config):
    # Build the one-crossing mutate extension (storage/native_ext) once
    # at session start so the FIRST fragment test doesn't pay the
    # compile inside its own timing/timeout budget. Graceful: a missing
    # toolchain (or PILOSA_TPU_NATIVE_EXT=0) latches to the pure-Python
    # paths, and tests/test_write_path.py::test_extension_loaded is the
    # tier-1 assertion that the build actually happened where expected.
    from pilosa_tpu.storage import native_ext
    native_ext.load()
    # Marker registry (no pytest.ini in this repo): `slow` is what the
    # tier-1 gate excludes (`-m 'not slow'`); `chaos` tags the
    # failpoint/fault-injection tests — the fast ones run in tier-1,
    # the multi-process SIGKILL cluster legs are additionally `slow`.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (multi-process"
                   " cluster legs, soaks)")
    config.addinivalue_line(
        "markers", "chaos: failpoint-driven fault-injection tests;"
                   " schedules replay from PILOSA_FAULT_SEED")
    config.addinivalue_line(
        "markers", "resize: elastic cluster-resize tests (ISSUE 12) —"
                   " fast failpoint legs run tier-1, the multi-process"
                   " SIGKILL legs are additionally `slow`")
    config.addinivalue_line(
        "markers", "tenant: multi-tenant QoS tests (ISSUE 14) — "
                   "per-tenant lanes/quotas/kill-policy/cache-quota"
                   " units run tier-1, the real 2-node gossip legs"
                   " are additionally `slow`")
    config.addinivalue_line(
        "markers", "scrub: storage-integrity tests (ISSUE 15) — "
                   "footer/scrub/quarantine/repair units run tier-1,"
                   " the real 3-node bit-flip chaos legs are"
                   " additionally `slow`")
    config.addinivalue_line(
        "markers", "tier: tiered-storage tests (ISSUE 16) — "
                   "demotion/faulting/blob/eviction/prefetch units and"
                   " fast failpoint legs run tier-1, the SIGKILL crash"
                   " legs and soaks are additionally `slow`")
    config.addinivalue_line(
        "markers", "replay: workload capture/replay/shadow tests"
                   " (ISSUE 19) — digest/redaction/ring/export units"
                   " run tier-1, the real 2-node merged-export replay"
                   " leg is additionally `slow`")
    config.addinivalue_line(
        "markers", "backup: disaster-recovery tests (ISSUE 20) — "
                   "archive/journal/retention/walarchive units and"
                   " the in-process backup→destroy→restore legs run"
                   " tier-1, the SIGKILL coordinator-crash legs are"
                   " additionally `slow`")
