"""Benchmark suite for the BASELINE.md target configurations.

Prints one JSON line per config. `bench.py` at the repo root remains the
single-metric benchmark of record; this suite covers the remaining
BASELINE.json configs for documentation and regression tracking:

1. Single-fragment Intersect+Count on two 1M-column rows (config 1) —
   through the Fragment/query layer, host path vs device kernel.
2. Union/Difference over 1K rows in one slice, mixed container kinds
   (config 2) — device row-block fold vs the C++/numpy host kernel.
3. TopN(n) over a rows×columns frame with a source bitmap (config 3) —
   p50 latency of the executor's exact-count phase, host vs mesh.
4. Count(Intersect) across N slices on the device mesh (config 4) —
   mesh.count_expr, the mapReduce replacement.
5. Cluster-style TopN across N slices (config 5, single-host form) —
   mesh.topn_exact; the multi-host leg adds HTTP remote legs on top.

Timing: one host↔device sync costs far more than a small kernel, so
each throughput measurement chains dispatches and syncs once (see
bench.py's methodology note), except the latency configs (3) where the
sync IS part of the reported p50.

Env: PILOSA_BENCH_SCALE (default 1.0) scales row/slice counts down for
smoke runs; PILOSA_BENCH_DEVICE=0 skips device measurements.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

SCALE = float(os.environ.get("PILOSA_BENCH_SCALE", "1.0"))
USE_DEVICE = os.environ.get("PILOSA_BENCH_DEVICE", "1") != "0"

# Every emit of this pass, in order — main() folds them into
# benchmarks/MANIFEST.json so "which run wrote this artifact" is
# answerable.
_EMITTED: list[dict] = []


def emit(metric: str, value: float, unit: str, **extra) -> None:
    line = {"metric": metric, "value": round(value, 4),
            "unit": unit, **extra}
    _EMITTED.append(line)
    print(json.dumps(line), flush=True)


# Canonical artifact file per metric family: the one JSON a consumer
# should read for that number (everything else is a historical or
# intermediate record). bench.py owns ROOFLINE.json; this suite owns
# the rest.
_CANONICAL_ARTIFACTS = {
    "intersect_count": "ROOFLINE.json",
    "write_path": "WRITEPATH.json",
    "distributed_topn": "DISTRIBUTED.json",
    "resize": "RESIZE.json",
    "pallas_ab": "PALLAS_AB.json",
    "densify": "DENSIFY.json",
    "host_baselines": "HOST_BASELINE.json",
    "latency_under_load": "LATENCY.json",
    "tenant_isolation": "TENANTS.json",
    "tiered": "TIERED.json",
    "planner": "PLANNER.json",
    "replay": "REPLAY.json",
}


def write_manifest(partial: bool = False) -> None:
    """benchmarks/MANIFEST.json: THE index of benchmark truth — which
    artifact file is canonical per metric family, plus this pass's
    metrics with their same-pass canary (the measured device sync
    floor) and canary-normalized ratios. Cross-round comparisons
    should compare vs_canary, not absolute values: the shared VM slot
    swings absolutes ~±10%, and "whichever run last wrote
    WRITEPATH.json" is no longer the provenance story — the manifest
    records the writing pass and its canary alongside."""
    floor_ms = _SYNC_FLOOR_MS
    metrics = {}
    first_vs_warm = {}
    for line in _EMITTED:
        entry = dict(line)
        entry.pop("metric", None)
        if floor_ms > 0 and line.get("unit") == "ms":
            # Device latencies scale with the slot's sync floor; the
            # ratio transfers across passes (and to direct-attached
            # hardware) where the absolute ms does not.
            entry["vs_canary_sync_floor"] = round(
                line["value"] / floor_ms, 3)
        metrics[line["metric"]] = entry
        if "first_ms" in line and line.get("unit") == "ms":
            # Cold-vs-warm per config (a tracked regression metric):
            # first query pays compile + upload,
            # the warm p50 must not.
            first_vs_warm[line["metric"]] = {
                "first_ms": line["first_ms"],
                "warm_p50_ms": line["value"],
                "first_over_warm": round(
                    line["first_ms"] / max(line["value"], 1e-9), 2),
            }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MANIFEST.json")
    # The latency_* entries are owned by latency_under_load.py (its
    # _fold_into_manifest); a suite pass must carry them forward, not
    # clobber them. One read serves every carry-forward below.
    try:
        with open(path) as f:
            prior_doc = json.load(f)
    except (OSError, ValueError):
        prior_doc = {}
    prior = prior_doc.get("metrics", {})
    for k, v in prior.items():
        # A partial pass (argv-selected configs) re-measures only its
        # own families; everything else carries forward so the
        # manifest stays the full index. Full passes carry only the
        # latency_* entries (owned by latency_under_load.py).
        # Error rows never carry forward: a failed config's row is
        # keyed by FUNCTION name while its successful rerun emits
        # metric names, so a stale error would otherwise contradict
        # the fresh section forever.
        if (k not in metrics and (partial or k.startswith("latency_"))
                and (not isinstance(v, dict)
                     or v.get("unit") != "error")):
            metrics[k] = v
    out = {
        "written_by": "benchmarks/suite.py",
        "scale": SCALE,
        "device": USE_DEVICE,
        "canary": {"sync_floor_ms": round(floor_ms, 3) or None},
        "canonical_artifacts": _CANONICAL_ARTIFACTS,
        "metrics": metrics,
        "first_vs_warm": first_vs_warm,
        "compile_cache": _compile_cache_snapshot(),
    }
    if partial:
        # A subset pass that measured no sync floor / warm tables /
        # compile stats keeps the full pass's values on record — and
        # must not relabel the retained sections' environment: the
        # top-level device flag and the compile-cache block describe
        # the FULL pass the carried-forward numbers came from, so a
        # CPU-only partial rerun of one config keeps both (its own
        # device flag rides its section's entry).
        if floor_ms <= 0:
            out["canary"] = prior_doc.get("canary", out["canary"])
        if not first_vs_warm:
            out["first_vs_warm"] = prior_doc.get("first_vs_warm", {})
        if "device" in prior_doc:
            out["device"] = prior_doc["device"]
        if prior_doc.get("compile_cache"):
            out["compile_cache"] = prior_doc["compile_cache"]
    # Per-config cost ledgers (config_query_cost) and the measured
    # roofline constants (benchmarks/roofline.py) ride the manifest;
    # a pass that skipped either carries the prior values forward.
    out["query_cost"] = _QUERY_COST or prior_doc.get("query_cost", {})
    # Run-container mix on the run-heavy workload
    # (config_container_mix): run-op share, resident bytes vs the
    # two-kind baseline, p50 — ROADMAP item 4's acceptance artifact.
    out["container_mix"] = (_CONTAINER_MIX
                            or prior_doc.get("container_mix", {}))
    # Fresh-process first-vs-warm + compile counts per slice config
    # (config_compile_stability): the restart-latency acceptance table.
    out["compile_stability"] = (_COMPILE_STABILITY
                                or prior_doc.get("compile_stability",
                                                 {}))
    # Write-path A/B (config_write_path): per-op SetBit, executor
    # per-op, wire import, fsync amortization — ISSUE 8's acceptance
    # table, one-crossing+group-commit vs the pre-extension path.
    out["write_path"] = _WRITE_PATH or prior_doc.get("write_path", {})
    # Distributed fast paths (config_distributed_topn): 2-node TopN
    # pushdown vs fan-out A/B + the generation-validated resident
    # chain — ROADMAP item 3's acceptance table.
    out["distributed_topn"] = (_DISTRIBUTED_TOPN
                               or prior_doc.get("distributed_topn",
                                                {}))
    # Always-on observability overhead (config_obs_overhead): tail
    # sampling + blackbox cadence vs all-off, interleaved — ISSUE 11's
    # ≤2% acceptance artifact.
    out["obs_overhead"] = (_OBS_OVERHEAD
                           or prior_doc.get("obs_overhead", {}))
    # Metric-history sampler + regression sentinel overhead
    # (config_obs_history): whole-registry sampling, disk ticks, and
    # rule evaluation vs all-off, interleaved — ISSUE 13's ≤2%
    # acceptance artifact.
    out["obs_history"] = (_OBS_HISTORY
                          or prior_doc.get("obs_history", {}))
    # Background storage-scrub overhead (config_scrub_overhead): the
    # bench-leg p50 with the scrubber re-verifying checksums at an
    # elevated cadence vs off, interleaved — ISSUE 15's ≤2%
    # acceptance artifact.
    out["scrub_overhead"] = (_SCRUB_OVERHEAD
                             or prior_doc.get("scrub_overhead", {}))
    # Elastic resize under load (config_resize): duration, streamed
    # volume, and query p99 inflation during the migration — ROADMAP
    # item 5's acceptance table.
    out["resize"] = _RESIZE or prior_doc.get("resize", {})
    # Multi-tenant isolation (config_tenant_isolation): quiet-tenant
    # p99 under an aggressor at ≥3× its cap vs solo, per-tenant
    # shed/kill counts, and the quiet burn rate — ISSUE 14's
    # acceptance table.
    out["tenant_isolation"] = (_TENANT_ISOLATION
                               or prior_doc.get("tenant_isolation",
                                                {}))
    # Tiered storage (config_tiered): hot-working-set p99 with the
    # index 10× over the resident budget (bulk in the blob tier) vs
    # all-resident, zero wrong answers — ISSUE 16's acceptance table.
    out["tiered"] = _TIERED or prior_doc.get("tiered", {})
    # Cost-based planner A/B (config_planner): skewed multi-operand
    # speedup legs + the planner+plan-recording overhead guard +
    # the costmodel-constants fold-back — ISSUE 18's acceptance table.
    out["planner"] = _PLANNER or prior_doc.get("planner", {})
    # Recorded-traffic replay (config_replay -> benchmarks/replay.py):
    # the open-loop sustained-QPS artifact re-driven from a captured
    # stream, the self-shadow/seeded-fault proof, and the capture
    # on/off overhead guard — ISSUE 19's acceptance table.
    out["replay"] = _REPLAY or prior_doc.get("replay", {})
    out["capture_overhead"] = (_CAPTURE_OVERHEAD
                               or prior_doc.get("capture_overhead",
                                                {}))
    # Disaster recovery (config_backup): the backup-while-serving
    # overhead guard (continuous coordinator passes vs off,
    # interleaved; ≤5% target on the bench-leg p50) plus the restore
    # wall time into a fresh node — ISSUE 20's acceptance table.
    out["backup"] = _BACKUP or prior_doc.get("backup", {})
    measured = _roofline_measured() or prior_doc.get(
        "roofline_measured_constants")
    if measured:
        out["roofline_measured_constants"] = measured
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


# Per-config cost ledgers captured by config_query_cost() — folded
# into MANIFEST.json's query_cost section.
_QUERY_COST: dict = {}

# Run-container mix measurements captured by config_container_mix() —
# folded into MANIFEST.json's container_mix section (ROADMAP item 4's
# done-when artifact).
_CONTAINER_MIX: dict = {}

# Per-slice-config restart latency + compile counts captured by
# config_compile_stability() — folded into MANIFEST.json.
_COMPILE_STABILITY: dict = {}

# Write-path A/B acceptance table captured by config_write_path() —
# folded into MANIFEST.json's write_path section and merged into
# WRITEPATH.json for bench.py's line of record (ISSUE 8).
_WRITE_PATH: dict = {}

# Distributed-fast-path acceptance table captured by
# config_distributed_topn() — folded into MANIFEST.json's
# distributed_topn section and written to DISTRIBUTED.json
# (ROADMAP item 3 / ISSUE 9).
_DISTRIBUTED_TOPN: dict = {}

# Always-on observability overhead A/B captured by
# config_obs_overhead() — folded into MANIFEST.json's obs_overhead
# section (ISSUE 11's ≤2% acceptance bound on the bench-leg p50).
_OBS_OVERHEAD: dict = {}

# Metric-history + sentinel overhead A/B captured by
# config_obs_history() — folded into MANIFEST.json's obs_history
# section (ISSUE 13's ≤2% acceptance bound on the bench-leg p50).
_OBS_HISTORY: dict = {}

# Background-scrub overhead A/B captured by config_scrub_overhead()
# — folded into MANIFEST.json's scrub_overhead section (ISSUE 15's
# ≤2% acceptance bound on the bench-leg p50 with the scrubber at
# elevated cadence).
_SCRUB_OVERHEAD: dict = {}

# Elastic-resize acceptance table captured by config_resize() —
# folded into MANIFEST.json's resize section and written to
# RESIZE.json (ROADMAP item 5 / ISSUE 12): resize duration + query
# p99 inflation under live load during the migration.
_RESIZE: dict = {}

# Multi-tenant isolation A/B captured by config_tenant_isolation() —
# folded into MANIFEST.json's tenant_isolation section and written to
# TENANTS.json (ROADMAP item 5's multi-tenant half / ISSUE 14): the
# quiet tenant's p99 with an aggressor at ≥3× its admission cap vs its
# solo baseline, interleaved, with the aggressor's shed/kill counts.
_TENANT_ISOLATION: dict = {}

# Tiered-storage acceptance table captured by config_tiered() —
# folded into MANIFEST.json's tiered section and written to
# TIERED.json (ISSUE 16: hot-working-set p99 ≤ 1.2× all-resident
# while the index is ≥ 10× the resident budget, zero wrong answers).
_TIERED: dict = {}

# Cost-based planner A/B captured by config_planner() — folded into
# MANIFEST.json's planner section and written to PLANNER.json
# (ISSUE 18): planned-vs-unplanned p50 on the skewed multi-operand
# workload (short-circuit, reorder, cross-query CSE legs; ≥3× target)
# plus the planner+plan-recording overhead guard on the production
# default workload (≤1.02 target), and the costmodel-constants
# fold-back record.
_PLANNER: dict = {}

# Recorded-traffic replay summary captured by config_replay() (which
# shells out to benchmarks/replay.py) — folded into MANIFEST.json's
# replay + capture_overhead sections and written to REPLAY.json
# (ISSUE 19): offered/achieved QPS with per-lane p99s + shed rates,
# the self-shadow zero-mismatch proof, the seeded-fault detection,
# and the capture on/off p50 ratio (≤1.02 target).
_REPLAY: dict = {}
_CAPTURE_OVERHEAD: dict = {}

# Disaster-recovery acceptance table captured by config_backup() —
# folded into MANIFEST.json's backup section (ISSUE 20): the
# backup-while-serving p50 overhead (coordinator running continuous
# full passes vs off, interleaved; ≤1.05 target) and the wall time
# of a digest-verified restore into a fresh empty node.
_BACKUP: dict = {}


# Fresh-process measurement: each slice config restarts python, arms
# the SHARED persistent compile cache, and times the FIRST device
# query end-to-end (backend init + mesh + program acquisition +
# dispatch) then the warm p50 — the real "first device query after
# restart" number, not an in-process proxy.
_STABILITY_CHILD = r"""
import json, os, sys, tempfile, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PILOSA_TPU_COST_MODEL"] = "0"
sys.path.insert(0, %(repo)r)
import jax
import numpy as np
from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel import mesh as mesh_mod, programs

armed = mesh_mod.arm_compile_cache()  # one rule: env dir or .cache/xla
n_slices = %(n_slices)d
rng = np.random.default_rng(17)
with tempfile.TemporaryDirectory() as d:
    holder = Holder(d)
    holder.open()
    try:
        frame = holder.create_index_if_not_exists("cs") \
            .create_frame_if_not_exists("f")
        for row in (0, 1):
            cols = (rng.integers(0, SLICE_WIDTH, size=50 * n_slices)
                    + np.repeat(np.arange(n_slices), 50) * SLICE_WIDTH)
            frame.import_bits(np.full(len(cols), row, dtype=np.uint64),
                              cols.astype(np.uint64))
        ex = Executor(holder, host="local", mesh_min_slices=1)
        # The server's boot sequence: warmup compiles the catalogue at
        # the holder's actual bucket (reading the persistent cache),
        # THEN queries arrive. first_ms is the first real device query
        # a restarted server serves; warmup_s is the startup cost it
        # paid in the background to get there.
        q = ("Count(Intersect(Bitmap(frame=f, rowID=0),"
             " Bitmap(frame=f, rowID=1)))")
        from pilosa_tpu.sched.warmup import Warmup
        w = Warmup(ex)
        t0 = time.perf_counter()
        w._run()
        warmup_s = time.perf_counter() - t0
        assert w.state == "done", (w.state, w.error)
        t0 = time.perf_counter()
        first = ex.execute("cs", q)[0]
        first_s = time.perf_counter() - t0
        lat = []
        for _ in range(5):
            t0 = time.perf_counter()
            again = ex.execute("cs", q)[0]
            lat.append(time.perf_counter() - t0)
        assert again == first
        assert ex.device_fallbacks == 0, "fell back to host"
        stats = mesh_mod.compile_stats()
        print("RESULT " + json.dumps({
            "first_ms": round(first_s * 1e3, 1),
            "warm_p50_ms": round(sorted(lat)[2] * 1e3, 2),
            "warmup_s": round(warmup_s, 2),
            "compile_count": stats["firstCalls"],
            "persistent_hits": stats["persistentHits"],
            "persistent_misses": stats["persistentMisses"],
            "bucket": programs.slice_bucket(n_slices, 8),
            "cache_dir": armed}))
    finally:
        holder.close()
"""


def config_compile_stability() -> None:
    """First-vs-warm device query latency AND compile counts per
    slice-count config, each in a FRESH process sharing one on-disk
    XLA cache — records (a) whether the compile count stays constant
    (bucket-bound) as slice count grows 8→32, and (b) what the first
    device query after a restart actually costs once the persistent
    cache is warm. The tier-1 regression twin lives in
    tests/test_programs.py; this is the measured artifact."""
    import subprocess
    import tempfile

    from pilosa_tpu.utils import cache_dir

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    share = os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_dir(
        "xla")
    env = dict(os.environ)
    for n_slices in (8, 16, 24, 32):
        code = _STABILITY_CHILD % {"repo": repo, "n_slices": n_slices}
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("RESULT ")]
        if out.returncode != 0 or not line:
            emit(f"compile_stability_s{n_slices}", -1, "error",
                 error=(out.stderr or out.stdout)[-200:])
            continue
        rec = json.loads(line[0][len("RESULT "):])
        _COMPILE_STABILITY[f"s{n_slices}"] = rec
        emit(f"compile_stability_s{n_slices}", rec["warm_p50_ms"],
             "ms", first_ms=rec["first_ms"],
             compile_count=rec["compile_count"],
             persistent_hits=rec["persistent_hits"],
             bucket=rec["bucket"], slices=n_slices)


def _roofline_measured() -> dict | None:
    """The measured projection constants benchmarks/roofline.py
    records (dispatch/collective next to the 0.3 ms / 50 us
    assumptions) — carried into MANIFEST.json so both artifacts agree."""
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(
                __file__)), "ROOFLINE.json")) as f:
            return json.load(f).get("measured_constants")
    except (OSError, ValueError):
        return None


def config_query_cost() -> None:
    """Per-config query-cost ledgers (obs.accounting): the bench query
    shapes through the executor with a cost-attached QueryContext, so
    MANIFEST.json records WHAT each config's query costs (container-op
    mix by operand kinds, device programs/bytes, compile ms) next to
    how long it took — the attribution layer's numbers as committed
    artifacts."""
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import ExecOptions, Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import accounting
    from pilosa_tpu.sched import QueryContext

    rng = np.random.default_rng(21)
    n_slices = max(2, int(8 * SCALE))
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        try:
            frame = holder.create_index_if_not_exists("qc") \
                .create_frame_if_not_exists("f")
            for row in range(8):
                cols = (rng.integers(0, SLICE_WIDTH,
                                     size=400 * n_slices)
                        + np.repeat(np.arange(n_slices), 400)
                        * SLICE_WIDTH)
                frame.import_bits(
                    np.full(len(cols), row, dtype=np.uint64),
                    cols.astype(np.uint64))
            # Narrow materializing shapes run the roaring container
            # algebra (the wide-union shape routes to the vectorized
            # word fold, which by design does no container ops); the
            # Count shape exercises the fused count path, whose cost
            # shows up as device programs/bytes on the device leg.
            shapes = {
                "c1_intersect_materialize":
                    "Intersect(Bitmap(frame=f, rowID=0),"
                    " Bitmap(frame=f, rowID=1))",
                "c2_union_materialize":
                    "Union(Bitmap(rowID=0, frame=f),"
                    " Bitmap(rowID=1, frame=f),"
                    " Bitmap(rowID=2, frame=f))",
                "c4_count_intersect":
                    "Count(Intersect(Bitmap(frame=f, rowID=0),"
                    " Bitmap(frame=f, rowID=1)))",
            }
            legs = [("host", False)]
            if USE_DEVICE:
                legs.append(("device", True))
            for leg, use_mesh in legs:
                ex = Executor(holder, host="local", use_mesh=use_mesh,
                              mesh_min_slices=1)
                if use_mesh:
                    ex._cost_model_enabled = False
                for name, q in shapes.items():
                    ex.execute("qc", q)  # warm (compile outside ledger)
                    # The ledger run must do the real work: drop the
                    # materialized-result cache the warm run seeded.
                    ex._bitmap_results.clear()
                    ctx = QueryContext(pql=q)
                    accounting.attach(ctx)
                    # ctx travels via ExecOptions: the executor binds
                    # it into every worker leg, where the container
                    # algebra actually runs.
                    ex.execute("qc", q, opt=ExecOptions(ctx=ctx))
                    cost = ctx.cost.to_tree()
                    cost.pop("node", None)
                    _QUERY_COST[f"{name}_{leg}"] = cost
                    emit(f"query_cost_{name}_{leg}",
                         float(sum(cost["containerOps"].values())),
                         "container_ops",
                         device_bytes=cost["deviceBytes"],
                         device_programs=cost["devicePrograms"],
                         compile_ms=cost["compileMs"],
                         words_scanned=cost["wordsScanned"])
                ex.close()
        finally:
            holder.close()


def config_container_mix() -> None:
    """Run containers on a run-heavy (timestamp/BSI-shaped) workload:
    the same import + query mix with the cardinality-adaptive
    optimize() pass ON vs OFF (PILOSA_TPU_RUN_CONTAINERS semantics),
    recording (1) resident container bytes, (2) the container-op mix
    by operand kind from the PR 4 cost ledger — the "mix shifts to
    run ops" claim as numbers — and (3) host-path query p50. The
    MANIFEST container_mix section is ROADMAP item 4's done-when
    artifact: run-op share > 0 on the run leg, strictly reduced
    resident bytes, equal-or-better p50."""
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import ExecOptions, Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import accounting
    from pilosa_tpu.sched import QueryContext
    from pilosa_tpu.storage import fragment as fragment_mod

    n_slices = max(2, int(4 * SCALE))
    n_rows = 6
    span_len = int(120_000 * SCALE)
    queries = [
        "Count(Intersect(Bitmap(rowID=0, frame=f),"
        " Bitmap(rowID=1, frame=f)))",
        "Count(Union(Bitmap(rowID=1, frame=f),"
        " Bitmap(rowID=2, frame=f)))",
        "Count(Difference(Bitmap(rowID=2, frame=f),"
        " Bitmap(rowID=3, frame=f)))",
        "TopN(frame=f, n=3)",
    ]

    def build(d: str, optimize_on: bool):
        prior = fragment_mod._RUN_OPTIMIZE
        fragment_mod._RUN_OPTIMIZE = optimize_on
        try:
            holder = Holder(d)
            holder.open()
            frame = holder.create_index_if_not_exists("cm") \
                .create_frame_if_not_exists("f")
            # Timestamp-view shape: each row holds long dense column
            # spans (sequential ids), overlapping so intersections are
            # non-trivial.
            for row in range(n_rows):
                start = row * span_len // 2
                cols = np.arange(start, start + span_len,
                                 dtype=np.uint64) \
                    % (n_slices * SLICE_WIDTH)
                frame.import_bits(
                    np.full(len(cols), row, dtype=np.uint64),
                    np.sort(cols))
        finally:
            fragment_mod._RUN_OPTIMIZE = prior
        stats = {"array": 0, "bitmap": 0, "run": 0}
        bytes_ = dict(stats)
        for s in range(n_slices):
            frag = holder.fragment("cm", "f", "standard", s)
            if frag is None:
                continue
            cs = frag.container_stats()
            for k in stats:
                stats[k] += cs["counts"][k]
                bytes_[k] += cs["bytes"][k]
        ex = Executor(holder, host="local", use_mesh=False)
        for q in queries:
            ex.execute("cm", q)  # warm
        meas = {"containers": stats,
                "resident_bytes": sum(bytes_.values()),
                "bytes_by_kind": bytes_, "container_ops": {},
                "lat_ms": []}
        return holder, ex, meas

    def round_of(ex, meas) -> None:
        for q in queries:
            ex._bitmap_results.clear()
            ctx = QueryContext(pql=q)
            accounting.attach(ctx)
            t0 = time.perf_counter()
            ex.execute("cm", q, opt=ExecOptions(ctx=ctx))
            meas["lat_ms"].append((time.perf_counter() - t0) * 1e3)
            ops = meas["container_ops"]
            for key, cnt in ctx.cost.to_tree()[
                    "containerOps"].items():
                ops[key] = ops.get(key, 0) + cnt

    def finish(meas) -> dict:
        ops = meas.pop("container_ops")
        total_ops = sum(ops.values()) or 1
        run_ops = sum(cnt for key, cnt in ops.items()
                      if "run" in key.split(":")[-1])
        meas["container_ops"] = ops
        meas["run_op_share"] = round(run_ops / total_ops, 4)
        meas["p50_ms"] = round(float(np.median(meas.pop("lat_ms"))), 3)
        return meas

    # INTERLEAVED A/B rounds: the shared VM slot swings absolute
    # latencies ±10%+ between back-to-back passes, so the two legs
    # alternate round by round and the p50s compare like for like
    # (same pattern as the accounting overhead guard).
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        h1, ex1, m_runs = build(d1, True)
        h2, ex2, m_base = build(d2, False)
        try:
            for _ in range(int(max(8, 24 * SCALE))):
                round_of(ex1, m_runs)
                round_of(ex2, m_base)
        finally:
            ex1.close()
            ex2.close()
            h1.close()
            h2.close()
    runs_leg = finish(m_runs)
    baseline = finish(m_base)
    _CONTAINER_MIX.update({
        "workload": {"slices": n_slices, "rows": n_rows,
                     "span_len": span_len, "queries": len(queries)},
        "runs": runs_leg,
        "baseline_array_bitmap": baseline,
        "resident_bytes_ratio": round(
            runs_leg["resident_bytes"]
            / max(baseline["resident_bytes"], 1), 4),
        "p50_ratio": round(runs_leg["p50_ms"]
                           / max(baseline["p50_ms"], 1e-9), 3),
    })
    emit("container_mix_runs", runs_leg["p50_ms"], "ms",
         run_op_share=runs_leg["run_op_share"],
         resident_bytes=runs_leg["resident_bytes"],
         containers=runs_leg["containers"])
    emit("container_mix_baseline", baseline["p50_ms"], "ms",
         run_op_share=baseline["run_op_share"],
         resident_bytes=baseline["resident_bytes"],
         containers=baseline["containers"])


def config_obs_overhead() -> None:
    """Always-on observability overhead guard (ISSUE 11): the
    bench-leg query p50 with the production default (tail sampling on
    every query + the blackbox recorder at its default cadence) vs
    everything off, interleaved in small alternating groups so shared
    CI noise lands on both modes equally (the PR-3 accounting-guard
    pattern). Acceptance: on/off p50 ratio ≤ 1.02."""
    import io
    import tempfile

    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs import metrics as obs_metrics
    from pilosa_tpu.obs.blackbox import Blackbox
    from pilosa_tpu.obs.diskring import SegmentRing
    from pilosa_tpu.obs.sampler import TailSampler
    from pilosa_tpu.obs.trace import Tracer
    from pilosa_tpu.server.handler import Handler
    from pilosa_tpu.storage import wal as storage_wal

    def call(app, method, path, body=b""):
        environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
                   "QUERY_STRING": "",
                   "CONTENT_LENGTH": str(len(body)),
                   "wsgi.input": io.BytesIO(body)}
        out = {}

        def start_response(status, hs):
            out["status"] = int(status.split()[0])

        list(app(environ, start_response))
        return out["status"]

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(os.path.join(d, "data"))
        holder.open()
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(11)
        n_rows = max(8, int(24 * SCALE))
        for row in range(n_rows):
            cols = rng.choice(1 << 16, size=2000, replace=False)
            frame.import_bits(np.full(2000, row, np.uint64),
                              cols.astype(np.uint64))
        from pilosa_tpu.utils.profiling import thread_dump

        ex = Executor(holder, host="local")
        handler = Handler(holder, ex, host="local",
                          tracer=Tracer(enabled=False))
        sampler = TailSampler(
            disk=SegmentRing(os.path.join(d, "traces")))

        def state_fn():
            # Production-shaped snapshot weight (Server._blackbox_state
            # without the server wiring): WAL health, thread dump,
            # query-state reads.
            return {"wal": storage_wal.flusher_health(),
                    "threads": thread_dump()[:20000],
                    "queries": {"active": handler.registry.active(),
                                "slow": handler.registry
                                .slow_queries()[-8:]},
                    "metrics": {"queries": obs_metrics.QUERIES_TOTAL
                                .labels("Union", "read", "200").value}}

        # 0.25 s cadence (40× the 10 s production default) so real
        # snapshots actually land INSIDE the measured on-windows —
        # at the default cadence a ~0.4 s group would never see one
        # and the A/B would measure tail sampling alone. Conservative:
        # the recorded ratio over-counts snapshot load per query.
        blackbox = Blackbox(os.path.join(d, "bb"), state_fn=state_fn,
                            interval_s=0.25, node="bench")
        children = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                             for r in range(n_rows))
        q = f"Union({children})".encode()

        def run_group(samples, n=40):
            for _ in range(n):
                # The materialized-result cache would collapse repeats
                # to a dict hit and measure nothing; clear per query
                # (both modes identically).
                ex._bitmap_results.clear()
                t0 = time.perf_counter()
                status = call(handler, "POST", "/index/i/query", q)
                samples.append(time.perf_counter() - t0)
                assert status == 200, status

        warm: list = []
        run_group(warm, 40)
        on_samples: list = []
        off_samples: list = []
        # Alternating ~0.4 s groups: long enough for the 0.25 s
        # blackbox cadence to land snapshots inside on-windows, short
        # enough that shared-VM scheduler noise spreads over both
        # modes (the per-query sampling cost itself is microseconds
        # against a ~10 ms query, so the measurement is noise-bound).
        rounds = max(6, int(15 * SCALE))
        for _ in range(rounds):
            handler.sampler = None
            run_group(off_samples)
            handler.sampler = sampler
            blackbox.start()
            try:
                run_group(on_samples)
            finally:
                blackbox.stop()
        on_p50 = sorted(on_samples)[len(on_samples) // 2]
        off_p50 = sorted(off_samples)[len(off_samples) // 2]
        ratio = on_p50 / off_p50
        _OBS_OVERHEAD.update({
            "on_p50_ms": round(on_p50 * 1e3, 4),
            "off_p50_ms": round(off_p50 * 1e3, 4),
            "ratio": round(ratio, 4),
            "samples_per_mode": len(on_samples),
            "rounds": rounds,
            "query": f"Union over {n_rows} rows",
            "tail_default": {"head_n": sampler.head_n,
                             "slow_floor_s": sampler.slow_floor_s},
            "blackbox_interval_s": blackbox.interval_s,
            "blackbox_interval_note":
                "40x the 10s production cadence, so snapshots land"
                " inside the measured windows (conservative)",
            "blackbox_snapshots_during_on": blackbox.ring.written,
            "device": USE_DEVICE,
            "target_ratio": 1.02,
        })
        emit("obs_overhead_on_p50", on_p50 * 1e3, "ms")
        emit("obs_overhead_off_p50", off_p50 * 1e3, "ms")
        emit("obs_overhead_ratio", ratio, "x_on_vs_off",
             target=1.02)
        sampler.disk.close()
        ex.close()
        holder.close()


def config_replay() -> None:
    """Recorded-traffic replay artifact (ISSUE 19): shells out to
    benchmarks/replay.py in a fresh interpreter (its multi-process
    open-loop driver forks; a clean process keeps that away from this
    pass's jax state) and folds REPLAY.json into the manifest's
    line of record."""
    import subprocess

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "replay.py")
    proc = subprocess.run([sys.executable, script],
                          capture_output=True, text=True,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(
            f"replay.py failed rc={proc.returncode}:"
            f" {proc.stderr[-400:]}")
    with open(os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "REPLAY.json")) as f:
        doc = json.load(f)
    _REPLAY.update(doc["replay"])
    _REPLAY["shadow"] = doc["shadow"]
    _CAPTURE_OVERHEAD.update(doc["capture_overhead"])
    emit("replay_offered_qps", doc["replay"]["offered_qps"], "qps",
         target=20000)
    emit("replay_achieved_qps", doc["replay"]["achieved_qps"], "qps")
    emit("replay_shadow_mismatches",
         doc["shadow"]["self"]["mismatches"], "count", target=0)
    emit("capture_overhead_ratio", doc["capture_overhead"]["ratio"],
         "x_on_vs_off", target=1.02)


def config_planner() -> None:
    """Cost-based planner A/B (ISSUE 18), interleaved alternating
    groups on ONE holder (shared fragment caches keep the comparison
    fair — the PR-3 guard pattern):

    - the SKEWED MULTI-OPERAND workload the planner exists for —
      short-circuit (a 3-operand intersect containing an empty row:
      unplanned pays the huge∩huge intermediate, planned proves 0
      without touching a fragment), reorder (tiny operand folded
      first vs the written huge-first order), and cross-query CSE
      (a repeated interior union under a varying outer leaf, served
      from the generation-token-keyed subresult cache) —
      acceptance: unplanned/planned p50 ≥ 3×;
    - the production-default workload the planner can only lose on
      (single-row counts through the full handler path, plan
      recording + the fingerprint store live) —
      acceptance: on/off p50 ratio ≤ 1.02;
    - the routing constants this process measured on its backend.
    """
    import io
    import tempfile

    import numpy as np

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.parallel import costmodel
    from pilosa_tpu.server.handler import Handler

    def call(app, method, path, body=b""):
        environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
                   "QUERY_STRING": "",
                   "CONTENT_LENGTH": str(len(body)),
                   "wsgi.input": io.BytesIO(body)}
        out = {}

        def start_response(status, hs):
            out["status"] = int(status.split()[0])

        list(app(environ, start_response))
        return out["status"]

    def p50(samples):
        return sorted(samples)[len(samples) // 2]

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(os.path.join(d, "data"))
        holder.open()
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(18)
        n_cols = 4 * SLICE_WIDTH
        # The skew the planner exploits: two huge rows (0, 1), a band
        # of medium rows for the shared union, tiny rows, and row 40
        # empty — rank caches make all of this estimable.
        huge = max(60_000, int(150_000 * SCALE))
        for row in (0, 1):
            cols = rng.choice(n_cols, size=huge, replace=False)
            frame.import_bits(np.full(huge, row, np.uint64),
                              cols.astype(np.uint64))
        for row in range(2, 32):
            cols = rng.choice(n_cols, size=2_000, replace=False)
            frame.import_bits(np.full(2_000, row, np.uint64),
                              cols.astype(np.uint64))
        for row in range(32, 36):
            cols = rng.choice(n_cols, size=50, replace=False)
            frame.import_bits(np.full(50, row, np.uint64),
                              cols.astype(np.uint64))

        planned = Executor(holder, host="local")
        unplanned = Executor(holder, host="local")
        unplanned.planner_enabled = False

        union = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                          for r in range(2, 32))
        legs = {
            # Written worst-first: empty row LAST, huge rows first.
            "short_circuit":
                lambda i: ("Count(Intersect(Bitmap(rowID=0, frame=f),"
                           " Bitmap(rowID=1, frame=f),"
                           " Bitmap(rowID=40, frame=f)))"),
            "reorder":
                lambda i: (f"Count(Intersect(Bitmap(rowID=0, frame=f),"
                           f" Bitmap(rowID=1, frame=f),"
                           f" Bitmap(rowID={32 + i % 4}, frame=f)))"),
            "cse":
                lambda i: (f"Count(Intersect(Union({union}),"
                           f" Bitmap(rowID={2 + i % 30}, frame=f)))"),
        }

        def run_group(ex, leg_fn, samples, n, base):
            for i in range(n):
                # Both modes clear the whole-result cache identically:
                # it would collapse repeats for both sides and measure
                # nothing (the subresult cache under test is interior-
                # node, token-keyed — it survives this clear).
                ex._bitmap_results.clear()
                q = leg_fn(base + i)
                t0 = time.perf_counter()
                ex.execute("i", q)
                samples.append(time.perf_counter() - t0)

        rounds = max(4, int(8 * SCALE))
        group_n = 6
        leg_results: dict = {}
        workload_planned: list = []
        workload_unplanned: list = []
        for leg, leg_fn in legs.items():
            a: list = []
            b: list = []
            # Warm both paths once (fragment row caches, rank caches,
            # and the CSE second-sighting threshold) outside the
            # measured groups.
            run_group(planned, leg_fn, [], 3, 0)
            run_group(unplanned, leg_fn, [], 3, 0)
            for r in range(rounds):
                run_group(unplanned, leg_fn, b, group_n, r * group_n)
                run_group(planned, leg_fn, a, group_n, r * group_n)
            leg_results[leg] = {
                "planned_p50_ms": round(p50(a) * 1e3, 4),
                "unplanned_p50_ms": round(p50(b) * 1e3, 4),
                "speedup": round(p50(b) / max(p50(a), 1e-9), 2),
            }
            workload_planned.extend(a)
            workload_unplanned.extend(b)
            emit(f"planner_{leg}_speedup",
                 leg_results[leg]["speedup"], "x_unplanned_vs_planned",
                 planned_p50_ms=leg_results[leg]["planned_p50_ms"],
                 unplanned_p50_ms=leg_results[leg]["unplanned_p50_ms"])
        skew_speedup = (p50(workload_unplanned)
                        / max(p50(workload_planned), 1e-9))
        emit("planner_skewed_workload_speedup", skew_speedup,
             "x_unplanned_vs_planned", target=3.0)

        # Overhead guard: the handler path (plan recording, the
        # fingerprint store, ctx stitching all live) on single-row
        # counts the planner cannot improve.
        handler = Handler(holder, planned, host="local")
        simple = [f"Count(Bitmap(rowID={r}, frame=f))".encode()
                  for r in range(2, 32)]

        def run_simple(samples, n=40):
            for i in range(n):
                planned._bitmap_results.clear()
                t0 = time.perf_counter()
                status = call(handler, "POST", "/index/i/query",
                              simple[i % len(simple)])
                samples.append(time.perf_counter() - t0)
                assert status == 200, status

        run_simple([], 20)  # warm
        on_s: list = []
        off_s: list = []
        for _ in range(rounds):
            planned.planner_enabled = False
            run_simple(off_s)
            planned.planner_enabled = True
            run_simple(on_s)
        overhead = p50(on_s) / max(p50(off_s), 1e-9)
        emit("planner_overhead_ratio", overhead, "x_on_vs_off",
             target=1.02, on_p50_ms=round(p50(on_s) * 1e3, 4),
             off_p50_ms=round(p50(off_s) * 1e3, 4))

        snap = planned.planner.snapshot()
        from pilosa_tpu.parallel import mesh as mesh_mod
        cal = costmodel.get_model(mesh_mod.make_mesh()).cal
        table = {
            "legs": leg_results,
            "skewed_workload_speedup": round(skew_speedup, 2),
            "target_speedup": 3.0,
            "overhead": {
                "on_p50_ms": round(p50(on_s) * 1e3, 4),
                "off_p50_ms": round(p50(off_s) * 1e3, 4),
                "ratio": round(overhead, 4),
                "target_ratio": 1.02,
                "samples_per_mode": len(on_s),
            },
            "planner_snapshot": snap,
            # The routing constants this process measured on its own
            # backend (costmodel.get_model): there are no committed
            # defaults to compare them with.
            "constants": {"this_rig": cal.to_dict()},
            "rounds": rounds, "group_n": group_n,
            "device": USE_DEVICE,
        }
        _PLANNER.update(table)
        with open(os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "PLANNER.json"),
                "w") as f:
            json.dump(table, f, indent=1)
        planned.close()
        unplanned.close()
        holder.close()


def config_scrub_overhead() -> None:
    """Background storage-scrub overhead guard (ISSUE 15): the
    bench-leg query p50 with the scrubber re-reading + re-crc'ing
    every fragment file at an ELEVATED cadence (continuous
    back-to-back passes — production runs one pass per [scrub]
    interval, default 10 min) vs scrubber off, interleaved in small
    alternating groups (the config_obs_overhead pattern).
    Acceptance: on/off p50 ratio ≤ 1.02."""
    import io
    import tempfile

    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.server.handler import Handler
    from pilosa_tpu.storage.scrub import Scrubber
    from pilosa_tpu.obs.trace import Tracer

    def call(app, method, path, body=b""):
        environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
                   "QUERY_STRING": "",
                   "CONTENT_LENGTH": str(len(body)),
                   "wsgi.input": io.BytesIO(body)}
        out = {}

        def start_response(status, hs):
            out["status"] = int(status.split()[0])

        list(app(environ, start_response))
        return out["status"]

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(os.path.join(d, "data"))
        holder.open()
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(13)
        n_rows = max(8, int(24 * SCALE))
        for row in range(n_rows):
            cols = rng.choice(1 << 18, size=4000, replace=False)
            frame.import_bits(np.full(4000, row, np.uint64),
                              cols.astype(np.uint64))
        # Real footered on-disk snapshots: the scrub pass must be
        # re-crc'ing actual container blocks, not empty stubs.
        blocks_on_disk = 0
        for frag in holder.iter_fragments():
            frag.snapshot(sync=True)
            blocks_on_disk += frag.verify_on_disk()["blocks"]
        assert blocks_on_disk > 0

        ex = Executor(holder, host="local")
        handler = Handler(holder, ex, host="local",
                          tracer=Tracer(enabled=False))
        children = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                             for r in range(n_rows))
        q = f"Union({children})".encode()

        def run_group(samples, n=40):
            for _ in range(n):
                ex._bitmap_results.clear()
                t0 = time.perf_counter()
                status = call(handler, "POST", "/index/i/query", q)
                samples.append(time.perf_counter() - t0)
                assert status == 200, status

        warm: list = []
        run_group(warm, 40)
        on_samples: list = []
        off_samples: list = []
        passes = 0
        rounds = max(6, int(15 * SCALE))
        for _ in range(rounds):
            run_group(off_samples)
            # Elevated cadence: a fresh scrubber per on-window
            # starting a pass every 50 ms (vs one per 10 MINUTES in
            # production — >10000x elevated), with the default
            # inter-fragment pacing the shipped scrubber uses (pacing
            # IS the discipline that keeps scrub IO out of serving's
            # way; measuring an unpaced spin-loop would benchmark a
            # configuration that never runs).
            scrubber = Scrubber(holder, interval_s=0.05, pace_s=0.01)
            scrubber.start()
            try:
                run_group(on_samples)
            finally:
                scrubber.stop()
            passes += scrubber.state()["passes"]
        on_p50 = sorted(on_samples)[len(on_samples) // 2]
        off_p50 = sorted(off_samples)[len(off_samples) // 2]
        ratio = on_p50 / off_p50
        _SCRUB_OVERHEAD.update({
            "on_p50_ms": round(on_p50 * 1e3, 4),
            "off_p50_ms": round(off_p50 * 1e3, 4),
            "ratio": round(ratio, 4),
            "samples_per_mode": len(on_samples),
            "rounds": rounds,
            "scrub_passes_during_on": passes,
            "blocks_on_disk": blocks_on_disk,
            "query": f"Union over {n_rows} rows",
            "cadence_note":
                "a pass every 50ms with the default 10ms fragment"
                " pacing (production default is one pass per 10 min"
                " — >10000x elevated)",
            "device": USE_DEVICE,
            "target_ratio": 1.02,
        })
        emit("scrub_overhead_on_p50", on_p50 * 1e3, "ms")
        emit("scrub_overhead_off_p50", off_p50 * 1e3, "ms")
        emit("scrub_overhead_ratio", ratio, "x_on_vs_off",
             target=1.02)
        ex.close()
        holder.close()


def config_obs_history() -> None:
    """Metric-history + sentinel overhead guard (ISSUE 13): the
    bench-leg query p50 with the history sampler ticking AND the
    regression sentinel evaluating vs both off, interleaved in small
    alternating groups (the config_obs_overhead pattern). The sampler
    runs at 0.25 s — 40× the 10 s production cadence — so whole-
    registry sampling passes + disk tick records actually land inside
    the measured on-windows (conservative: the recorded ratio
    over-counts sampling load per query). Acceptance: on/off p50
    ratio ≤ 1.02."""
    import io
    import tempfile
    import threading

    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.obs.history import MetricHistory
    from pilosa_tpu.obs.sentinel import Sentinel
    from pilosa_tpu.obs.trace import Tracer
    from pilosa_tpu.server.handler import Handler

    def call(app, method, path, body=b""):
        environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
                   "QUERY_STRING": "",
                   "CONTENT_LENGTH": str(len(body)),
                   "wsgi.input": io.BytesIO(body)}
        out = {}

        def start_response(status, hs):
            out["status"] = int(status.split()[0])

        list(app(environ, start_response))
        return out["status"]

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(os.path.join(d, "data"))
        holder.open()
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(13)
        n_rows = max(8, int(24 * SCALE))
        for row in range(n_rows):
            cols = rng.choice(1 << 16, size=2000, replace=False)
            frame.import_bits(np.full(2000, row, np.uint64),
                              cols.astype(np.uint64))
        ex = Executor(holder, host="local")
        handler = Handler(holder, ex, host="local",
                          tracer=Tracer(enabled=False))
        history = MetricHistory(
            os.path.join(d, "hist"),
            resolutions=((0.25, 400), (1.0, 200), (5.0, 100)))
        sentinel = Sentinel(history, interval_s=3600, window_s=5,
                            baseline_s=60, min_points=3)

        # The ticker thread IS the production runtime-collector +
        # sentinel cadence, accelerated: one whole-registry sampling
        # pass (and a disk tick) every 0.25 s, a full rule evaluation
        # every other tick.
        stop = threading.Event()

        def ticker():
            while not stop.wait(0.25):
                try:
                    history.sample()
                    sentinel.check()
                except Exception:  # noqa: BLE001 - bench must finish
                    pass

        children = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                             for r in range(n_rows))
        q = f"Union({children})".encode()

        def run_group(samples, n=40):
            for _ in range(n):
                ex._bitmap_results.clear()
                t0 = time.perf_counter()
                status = call(handler, "POST", "/index/i/query", q)
                samples.append(time.perf_counter() - t0)
                assert status == 200, status

        warm: list = []
        run_group(warm, 40)
        on_samples: list = []
        off_samples: list = []
        rounds = max(6, int(15 * SCALE))
        for _ in range(rounds):
            run_group(off_samples)
            stop.clear()
            t = threading.Thread(target=ticker, daemon=True)
            t.start()
            try:
                run_group(on_samples)
            finally:
                stop.set()
                t.join(timeout=5)
        on_p50 = sorted(on_samples)[len(on_samples) // 2]
        off_p50 = sorted(off_samples)[len(off_samples) // 2]
        ratio = on_p50 / off_p50
        _OBS_HISTORY.update({
            "on_p50_ms": round(on_p50 * 1e3, 4),
            "off_p50_ms": round(off_p50 * 1e3, 4),
            "ratio": round(ratio, 4),
            "samples_per_mode": len(on_samples),
            "rounds": rounds,
            "query": f"Union over {n_rows} rows",
            "history": history.stats(),
            "sentinel_checks": sentinel.checks,
            "sample_interval_s": 0.25,
            "cadence_note":
                "0.25s sampling + sentinel evaluation per tick —"
                " 40-120x the 10s/30s production cadence, so passes"
                " land inside the measured windows (conservative)",
            "device": USE_DEVICE,
            "target_ratio": 1.02,
        })
        emit("obs_history_on_p50", on_p50 * 1e3, "ms")
        emit("obs_history_off_p50", off_p50 * 1e3, "ms")
        emit("obs_history_ratio", ratio, "x_on_vs_off", target=1.02)
        history.close()
        ex.close()
        holder.close()


def _compile_cache_snapshot() -> dict:
    """The XLA program-cache counters for THIS pass
    (parallel.mesh.compile_stats): hit/miss ratio + compile seconds —
    the cold-query question as numbers a regression check can hold
    onto."""
    try:
        from pilosa_tpu.parallel import mesh as mesh_mod
        return mesh_mod.compile_stats()
    except Exception as e:  # noqa: BLE001 - manifest must still write
        return {"error": str(e)[:120]}


def emit_compile_cache() -> None:
    """Emit the compile-cache counters as a suite metric so they ride
    the normal manifest metrics table too."""
    s = _compile_cache_snapshot()
    if "error" in s:
        emit("compile_cache", -1, "error", **s)
        return
    emit("compile_cache", float(s["misses"]), "programs", **s)


def _timed_chain(fn, iters: int) -> float:
    """Median-of-3 per-call seconds, chained dispatch + single sync."""
    np.asarray(fn())  # warmup/compile
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn()
        np.asarray(out)
        best.append((time.perf_counter() - t0) / iters)
    return sorted(best)[1]


def config1_fragment_intersect_count() -> None:
    from pilosa_tpu.ops import kernels
    from pilosa_tpu.storage import native
    import jax

    n_words = (1 << 20) // 32
    rng = np.random.default_rng(1)
    a = rng.integers(0, 2**32, size=n_words, dtype=np.uint32)
    b = rng.integers(0, 2**32, size=n_words, dtype=np.uint32)

    native.popcnt_and(a.view(np.uint64), b.view(np.uint64))
    t0 = time.perf_counter()
    iters = 50
    for _ in range(iters):
        native.popcnt_and(a.view(np.uint64), b.view(np.uint64))
    host_s = (time.perf_counter() - t0) / iters
    extra = {}
    if native.available():
        # Only a real C++ run may pin the *_native denominator — the
        # numpy fallback rate must never masquerade as it.
        extra["native_pinned_ops"] = round(
            pin_best("c1_intersect_1M_native", 1.0 / host_s), 1)
    emit("c1_intersect_count_1M_host", 1.0 / host_s, "ops/sec", **extra)

    if USE_DEVICE:
        da, db = jax.device_put(a), jax.device_put(b)
        dev_s = _timed_chain(
            lambda: kernels.op_count_rows("and", da, db), 64)
        emit("c1_intersect_count_1M_device", 1.0 / dev_s, "ops/sec",
             vs_host=round(host_s / dev_s, 3))


def config2_union_difference_1k_rows() -> None:
    from pilosa_tpu.ops import kernels
    import jax

    n_rows = max(8, int(1000 * SCALE))
    n_words = (1 << 20) // 32
    rng = np.random.default_rng(2)
    # mixed "containers": half dense rows, half sparse (array-like)
    rows = rng.integers(0, 2**32, size=(n_rows, n_words), dtype=np.uint32)
    rows[n_rows // 2:] &= rng.integers(0, 2, size=(n_rows - n_rows // 2,
                                                   n_words),
                                       dtype=np.uint32)  # sparsify
    other = rng.integers(0, 2**32, size=n_words, dtype=np.uint32)

    np.bitwise_count(np.bitwise_or(rows, other[None, :]))  # warmup
    lat = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.bitwise_count(np.bitwise_or(rows, other[None, :])).sum(axis=-1)
        lat.append(time.perf_counter() - t0)
    host_s = sorted(lat)[1]
    emit("c2_union_1k_rows_host", 1.0 / host_s, "ops/sec")

    # Host-NATIVE leg: the same per-row union counts through the C++
    # kernel (one popcnt_or per row) — the pinned reference-equivalent
    # denominator (round-3 verdict: c1-c3 compared device against
    # numpy, not native).
    from pilosa_tpu.storage import native as native_mod
    if native_mod.available():
        o64 = other.view(np.uint64)
        r64 = rows.view(np.uint64)
        native_mod.popcnt_or(r64[0], o64)  # warmup
        lat = []
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(n_rows):
                native_mod.popcnt_or(r64[i], o64)
            lat.append(time.perf_counter() - t0)
        nat_s = sorted(lat)[1]
        pinned = pin_best(f"c2_union_native,rows={n_rows}",
                          1.0 / nat_s)
        emit("c2_union_1k_rows_native", 1.0 / nat_s, "ops/sec",
             native_pinned_ops=round(pinned, 2))

    if USE_DEVICE:
        dr, do = jax.device_put(rows), jax.device_put(other)
        dev_s = _timed_chain(
            lambda: kernels.row_block_op_count("or", dr, do), 16)
        emit("c2_union_1k_rows_device", 1.0 / dev_s, "ops/sec",
             vs_host=round(host_s / dev_s, 3))
        dev_s = _timed_chain(
            lambda: kernels.row_block_op_count("andnot", dr, do), 16)
        emit("c2_difference_1k_rows_device", 1.0 / dev_s, "ops/sec")


def config3_topn_latency() -> None:
    """TopN exact-count phase p50 latency, host loop vs one mesh call."""
    from pilosa_tpu.parallel import mesh as mesh_mod
    import jax

    n_rows = max(64, int(1000 * SCALE))
    n_slices = max(2, int(10 * SCALE))
    n_words = (1 << 20) // 32
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 2**32, size=(n_slices, n_rows, n_words),
                        dtype=np.uint32)
    src = rng.integers(0, 2**32, size=(1, n_slices, n_words),
                       dtype=np.uint32)

    lat = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.bitwise_count(rows & src[0][:, None, :]).sum(axis=(0, 2))
        lat.append(time.perf_counter() - t0)
    emit("c3_topn_exact_host_p50", sorted(lat)[2] * 1e3, "ms",
         rows=n_rows, slices=n_slices)

    # Host-NATIVE leg: the same exact-count phase through the C++
    # kernel — one popcnt_and per (slice, candidate) pair, matching
    # the reference's per-row IntersectionCount loop shape
    # (fragment.go:560-614). Pinned as the c3 denominator.
    from pilosa_tpu.storage import native as native_mod
    if native_mod.available():
        r64 = rows.view(np.uint64)
        s64 = src[0].view(np.uint64)
        native_mod.popcnt_and(r64[0, 0], s64[0])  # warmup
        lat = []
        for _ in range(3):
            t0 = time.perf_counter()
            for si in range(n_slices):
                srow = s64[si]
                for ri in range(n_rows):
                    native_mod.popcnt_and(r64[si, ri], srow)
            lat.append(time.perf_counter() - t0)
        nat_ms = sorted(lat)[1] * 1e3
        pinned = pin_best(
            f"c3_exact_native,rows={n_rows},slices={n_slices}",
            1e3 / nat_ms)  # phases/sec so "best" = highest
        emit("c3_topn_exact_native_p50", nat_ms, "ms",
             rows=n_rows, slices=n_slices,
             native_pinned_ms=round(1e3 / pinned, 2))

    if USE_DEVICE:
        # Device-resident form — what the executor's residency cache
        # serves on repeat queries (first-query upload is measured by
        # config_residency_repeat_latency's first_ms).
        mesh = mesh_mod.make_mesh()
        expr = ("leaf", 0)
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        rows_p = mesh_mod.pad_to_multiple(rows, n_dev)
        d_rows = mesh_mod.shard_slices(mesh, rows_p)
        d_leaves = [mesh_mod.shard_slices(
            mesh, mesh_mod.pad_to_multiple(src[0], n_dev))]
        mesh_mod.topn_exact_sharded(mesh, expr, d_rows, d_leaves)
        lat = []
        for _ in range(5):
            t0 = time.perf_counter()
            mesh_mod.topn_exact_sharded(mesh, expr, d_rows, d_leaves)
            lat.append(time.perf_counter() - t0)
        emit_latency("c3_topn_exact_mesh_p50", sorted(lat)[2] * 1e3,
                     rows=n_rows, slices=n_slices)


def _kernel_ab_modes() -> list[tuple[str, str]]:
    """(label, PILOSA_TPU_PALLAS value) pairs to A/B on this backend.

    On TPU both serving-path kernel variants are measured — the Pallas
    fused kernels vs XLA fusion — so the winner is chosen from data,
    per the round-2 mandate. Off-TPU only XLA runs (interpret-mode
    Pallas is a correctness tool, not a performance candidate).
    """
    import jax
    if jax.devices()[0].platform == "tpu":
        return [("xla", "0"), ("pallas", "1")]
    return [("xla", "0")]


@contextlib.contextmanager
def _pallas_mode_env(mode: str):
    """Force PILOSA_TPU_PALLAS for one measurement, restoring the
    caller's value even when the measured leg throws (main() continues
    fail-soft past per-config errors)."""
    prior = os.environ.get("PILOSA_TPU_PALLAS")
    os.environ["PILOSA_TPU_PALLAS"] = mode
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop("PILOSA_TPU_PALLAS", None)
        else:
            os.environ["PILOSA_TPU_PALLAS"] = prior


def config4_mesh_count_over_slices() -> None:
    from pilosa_tpu.parallel import mesh as mesh_mod
    import jax

    n_slices = max(8, int(256 * SCALE))
    n_words = (1 << 20) // 32
    rng = np.random.default_rng(4)
    leaves = rng.integers(0, 2**32, size=(2, n_slices, n_words),
                          dtype=np.uint32)

    t0 = time.perf_counter()
    int(np.bitwise_count(leaves[0] & leaves[1]).sum())
    host_s = time.perf_counter() - t0
    emit("c4_count_intersect_host", 1.0 / host_s, "ops/sec",
         slices=n_slices)

    if USE_DEVICE:
        # Device-resident leaf slabs (the executor residency form).
        mesh = mesh_mod.make_mesh()
        expr = ("and", ("leaf", 0), ("leaf", 1))
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        arrs = [mesh_mod.shard_slices(
            mesh, mesh_mod.pad_to_multiple(leaves[i], n_dev))
            for i in range(2)]
        for label, mode in _kernel_ab_modes():
            with _pallas_mode_env(mode):
                mesh_mod.count_expr_sharded(mesh, expr, arrs)  # compile
                lat = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    mesh_mod.count_expr_sharded(mesh, expr, arrs)
                    lat.append(time.perf_counter() - t0)
            dev_s = sorted(lat)[2]
            emit(f"c4_count_intersect_mesh_{label}", 1.0 / dev_s,
                 "ops/sec", slices=n_slices, devices=len(jax.devices()),
                 vs_host=round(host_s / dev_s, 3))


def config5_cluster_topn() -> None:
    from pilosa_tpu.parallel import mesh as mesh_mod
    import jax

    n_slices = max(8, int(256 * SCALE))
    n_rows = max(16, int(100 * SCALE))
    n_words = (1 << 20) // 32
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 2**32, size=(n_slices, n_rows, n_words),
                        dtype=np.uint32)
    src = rng.integers(0, 2**32, size=(1, n_slices, n_words),
                       dtype=np.uint32)

    if USE_DEVICE:
        mesh = mesh_mod.make_mesh()
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        d_rows = mesh_mod.shard_slices(
            mesh, mesh_mod.pad_to_multiple(rows, n_dev))
        d_leaves = [mesh_mod.shard_slices(
            mesh, mesh_mod.pad_to_multiple(src[0], n_dev))]
        for label, mode in _kernel_ab_modes():
            with _pallas_mode_env(mode):
                mesh_mod.topn_exact_sharded(mesh, ("leaf", 0), d_rows,
                                            d_leaves)  # compile
                lat = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    mesh_mod.topn_exact_sharded(mesh, ("leaf", 0),
                                                d_rows, d_leaves)
                    lat.append(time.perf_counter() - t0)
            emit_latency(f"c5_cluster_topn_mesh_p50_{label}",
                         sorted(lat)[2] * 1e3, slices=n_slices,
                         rows=n_rows, devices=len(jax.devices()))


def config2_executor_wide_union() -> None:
    """Config 2 through the EXECUTOR: materializing Union/Difference
    over many rows — device fold + repack vs per-slice roaring merges."""
    import tempfile
    import numpy as np
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    n_rows = max(16, int(1000 * SCALE))
    rng = np.random.default_rng(8)
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        for row in range(n_rows):
            cols = rng.choice(SLICE_WIDTH, size=500, replace=False)
            frame.import_bits([row] * len(cols), cols.tolist())
        children = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                             for r in range(n_rows))
        for name in ("Union", "Difference"):
            q = f"{name}({children})"
            want = None
            for label, use_mesh in (("host", False),) + (
                    (("device", True),) if USE_DEVICE else ()):
                ex = Executor(holder, host="local", use_mesh=use_mesh,
                              mesh_min_slices=1)
                got = ex.execute("i", q)[0].count()  # warmup/compile
                if want is None:
                    want = got
                assert got == want, (name, label, got, want)
                # COLD leg: the fold + repack itself, result cache
                # cleared per iteration (the residency row below
                # measures the cache).
                lat = []
                for _ in range(3):
                    ex._bitmap_results.clear()
                    t0 = time.perf_counter()
                    ex.execute("i", q)
                    lat.append(time.perf_counter() - t0)
                if use_mesh:  # the device label must measure the device
                    assert ex.device_fallbacks == 0, "device path fell back"
                emit(f"c2_executor_{name.lower()}_{n_rows}rows_{label}",
                     sorted(lat)[1] * 1e3, "ms", bits=int(want))
                # RESIDENT repeat: the materialized-result cache serves
                # the identical chain with zero re-fold and zero repack
                # (executor._bitmap_results).
                lat = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    ex.execute("i", q)
                    lat.append(time.perf_counter() - t0)
                emit(f"c2_executor_{name.lower()}_{n_rows}rows_"
                     f"{label}_resident", sorted(lat)[1] * 1e3, "ms")
                ex.close()
        holder.close()


def config_residency_repeat_latency() -> None:
    """Configs 3-4 through the EXECUTOR with the budgeted HBM residency
    cache: first query packs + uploads leaf/candidate blocks, repeats
    hit device-resident slabs — repeat p50 must sit well below first."""
    if not USE_DEVICE:
        return
    import tempfile
    import numpy as np
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    # Sized so the TopN candidate block (slices × cand × 128 KB) stays
    # under mesh.TOPN_BLOCK_BYTES — above it the executor streams
    # instead of using the residency cache this config measures.
    n_slices = max(8, int(32 * SCALE))
    n_cand = max(8, int(50 * SCALE))
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        for row in range(n_cand):
            cols = (rng.integers(0, SLICE_WIDTH, size=n_slices)
                    + np.arange(n_slices) * SLICE_WIDTH)
            frame.import_bits([row] * n_slices, cols.tolist())
        ex = Executor(holder, host="local", mesh_min_slices=1)
        # This config MEASURES the device residency path; the routing
        # veto (which may rightly prefer host at this size —
        # config4_executor_routing measures that choice) would
        # make it measure the wrong leg.
        ex._cost_model_enabled = False

        def timed(q, label):
            t0 = time.perf_counter()
            first = ex.execute("i", q)
            first_s = time.perf_counter() - t0
            lat = []
            for _ in range(5):
                t0 = time.perf_counter()
                again = ex.execute("i", q)
                lat.append(time.perf_counter() - t0)
            assert again == first
            emit_latency(label, sorted(lat)[2] * 1e3,
                         first_ms=round(first_s * 1e3, 4),
                         slices=n_slices,
                         speedup_vs_first=round(first_s / sorted(lat)[2],
                                                2))

        timed("Count(Intersect(Bitmap(frame=f, rowID=0),"
              " Bitmap(frame=f, rowID=1)))", "c4_executor_count_repeat_p50")
        ids = ",".join(str(r) for r in range(n_cand))
        timed(f"TopN(Bitmap(frame=f, rowID=0), frame=f, ids=[{ids}])",
              "c3_executor_topn_repeat_p50")
        assert ex.device_fallbacks == 0, "device path fell back"
        holder.close()


def config_host_write_and_import() -> None:
    """Host write-side throughput (the device only serves reads): bulk
    CSV parse, server-side bulk apply, and per-op SetBit through the
    executor — the round-2 host-path optimizations, reproducible."""
    import io
    import random
    import tempfile

    from pilosa_tpu.cli.commands import _parse_csv_arrays
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    n = int(1_000_000 * SCALE)
    random.seed(0)
    buf = io.StringIO()
    for _ in range(n):
        buf.write(f"{random.randrange(100)},{random.randrange(1 << 22)}\n")
    buf.seek(0)
    t0 = time.perf_counter()
    chunks = list(_parse_csv_arrays(buf, sys.stderr, 10_000_000))
    emit("host_csv_parse", n / (time.perf_counter() - t0), "bits/sec")

    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        try:
            frame = holder.create_index("bench").create_frame("f")
            t0 = time.perf_counter()
            for rows, cols, ts in chunks:
                frame.import_bits(rows, cols, ts)
            emit("host_import_apply", n / (time.perf_counter() - t0),
                 "bits/sec")

            ex = Executor(holder, host="local", use_mesh=False)
            k = int(5000 * SCALE)
            ex.execute("bench", 'SetBit(frame="f", rowID=0, columnID=0)')
            t0 = time.perf_counter()
            for i in range(k):
                ex.execute("bench",
                           f'SetBit(frame="f", rowID={i % 50},'
                           f' columnID={i * 13 % (1 << 20)})')
            setbit_exec = k / (time.perf_counter() - t0)
            emit("host_setbit_inprocess", setbit_exec, "ops/sec")
            # Batched bodies (1000 SetBits per query): the executor's
            # mutate-batch run + fast-path parse (round 5).
            kb = max(1000, int(100_000 * SCALE))
            queries = ["\n".join(
                f'SetBit(frame="f", rowID={i % 50},'
                f' columnID={i * 13 % (1 << 20)})'
                for i in range(s, min(s + 1000, kb)))
                for s in range(0, kb, 1000)]
            t0 = time.perf_counter()
            for q in queries:
                ex.execute("bench", q)
            emit("host_setbit_inprocess_batched",
                 kb / (time.perf_counter() - t0), "ops/sec")
            ex.close()
        finally:
            holder.close()

    _write_denominator(setbit_exec)


def _write_denominator(setbit_exec: float) -> None:
    """The write path's measured host-native denominator (round-3
    verdict: writes were the one surface with no reference-equivalent
    number). Runs the same workload through (a) the C++ write
    micro-engine (native.bench_setbit: container mutate + 13-byte WAL
    append per op + snapshot/fsync/rename every MAX_OP_N — the faithful
    stand-in for fragment.go:369-459 with no Go toolchain here) and
    (b) Fragment.set_bit in-process; pins the native best in
    HOST_BASELINE.json and leaves both in benchmarks/WRITEPATH.json for
    bench.py to stamp into the round artifact."""
    import tempfile

    from pilosa_tpu.storage import native
    from pilosa_tpu.storage.fragment import MAX_OP_N, Fragment

    rng = np.random.default_rng(9)
    n = max(1, int(100_000 * SCALE))
    rows = rng.integers(0, 1000, n).astype(np.uint64)
    cols = rng.integers(0, 1 << 20, n).astype(np.uint64)
    pos = (rows << np.uint64(20)) + cols

    native_ops = None
    if native.available():
        with tempfile.TemporaryDirectory() as d:
            t0 = time.perf_counter()
            native.bench_setbit(os.path.join(d, "frag"), pos, MAX_OP_N)
            native_ops = n / (time.perf_counter() - t0)
        emit("host_setbit_native", native_ops, "ops/sec")

    # Same op count as the native leg: amortized snapshot cost grows
    # with bits set so far, so different run lengths would bias the
    # published ratio (review finding, round 4).
    with tempfile.TemporaryDirectory() as d:
        frag = Fragment(os.path.join(d, "frag"), "bench", "f",
                        "standard", 0)
        frag.open()
        try:
            lat = np.empty(n)
            t0 = time.perf_counter()
            for i, (r, c) in enumerate(zip(rows.tolist(),
                                           cols.tolist())):
                t1 = time.perf_counter()
                frag.set_bit(r, c)
                lat[i] = time.perf_counter() - t1
            frag._join_snapshot()
            frag_ops = n / (time.perf_counter() - t0)
            lat.sort()
            p999_ms = float(lat[int(n * 0.999)]) * 1e3
            max_ms = float(lat[-1]) * 1e3
        finally:
            frag.close()
    emit("host_setbit_fragment", frag_ops, "ops/sec",
         p999_ms=round(p999_ms, 2), max_ms=round(max_ms, 1))

    # The batched serving path (round-5: one native crossing + one WAL
    # group-commit per batch — how query fan-outs and pipelined bodies
    # actually hit the fragment). Same workload, same durability.
    batch_ops = {}
    for B in (1000, 4000):
        with tempfile.TemporaryDirectory() as d:
            frag = Fragment(os.path.join(d, "frag"), "bench", "f",
                            "standard", 0)
            frag.open()
            try:
                t0 = time.perf_counter()
                for s in range(0, n, B):
                    frag.set_bits(rows[s:s + B], cols[s:s + B])
                frag._join_snapshot()
                batch_ops[B] = n / (time.perf_counter() - t0)
            finally:
                frag.close()
        emit(f"host_setbit_fragment_batched_b{B}", batch_ops[B],
             "ops/sec")

    # Key carries the op count: snapshot amortization scales with run
    # length, so a short smoke run must not pin the canonical shape.
    pinned = (pin_best(f"setbit_native,n={n}", native_ops)
              if native_ops else None)
    art = {"setbit_native_ops": round(native_ops, 1) if native_ops else None,
           "setbit_native_pinned_ops": round(pinned, 1) if pinned else None,
           "setbit_fragment_ops": round(frag_ops, 1),
           "setbit_fragment_batched_b1000_ops": round(batch_ops[1000], 1),
           "setbit_fragment_batched_b4000_ops": round(batch_ops[4000], 1),
           "setbit_fragment_p999_ms": round(p999_ms, 2),
           "setbit_executor_ops": round(setbit_exec, 1),
           "fragment_vs_native_pinned": (
               round(pinned / frag_ops, 2) if pinned else None),
           "batched_vs_native_pinned": (
               round(pinned / batch_ops[4000], 2) if pinned else None)}
    emit("write_denominator", art["fragment_vs_native_pinned"] or 0.0,
         "x_native_over_fragment", **art)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "WRITEPATH.json"), "w") as f:
        json.dump(art, f, indent=1)


def pin_best(name: str, ops_s: float) -> float:
    """Persist the best-ever (highest ops/s) host-native measurement for
    ``name`` on this machine; returns the pinned best (monotone, like
    bench.py's read denominator — one shared writer, benchmarks.pinning)."""
    import platform

    from benchmarks.pinning import pin
    return pin(f"{name},host={platform.node()}", "best_ops_s", ops_s,
               lambda new, old: new > old)


def _build_topn_frame(holder, n_rows: int, n_slices: int):
    """BASELINE config 3's frame: ranked rows with a long tail, columns
    spread over n_slices × 2^20. Bulk-built in slice-grouped batches."""
    from pilosa_tpu import SLICE_WIDTH

    rng = np.random.default_rng(33)
    frame = holder.create_index_if_not_exists("t3") \
        .create_frame_if_not_exists("f")
    # Head: 2000 rows with counts 1000→21 (descending, distinct ranks);
    # tail: the rest at 4 bits each. Totals ~1.4 M bits at full scale.
    head = min(2000, n_rows)
    counts = np.concatenate([
        np.maximum(21, 1000 - np.arange(head)).astype(np.int64),
        np.full(n_rows - head, 4, dtype=np.int64)])
    rows = np.repeat(np.arange(n_rows, dtype=np.uint64), counts)
    cols = rng.integers(0, n_slices * SLICE_WIDTH, size=len(rows),
                        dtype=np.uint64)
    order = np.argsort(cols // np.uint64(SLICE_WIDTH), kind="stable")
    rows, cols = rows[order], cols[order]
    step = max(1, len(rows) // 20)
    for i in range(0, len(rows), step):
        frame.import_bits(rows[i:i + step], cols[i:i + step])
    return frame, int(counts.sum())


def config3_topn1000_end_to_end() -> None:
    """The second clause of the metric of record: TopN(n=1000) p50 on a
    100 K-row × 10 M-column frame (BASELINE config 3, Fragment.Top
    fragment.go:490-625 + rank cache cache.go:126-275), END TO END
    through the executor — candidate phase over the rank caches plus
    the exact merge — first query and residency-warm, device vs host."""
    import tempfile

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    n_rows = max(1000, int(100_000 * SCALE))
    n_slices = max(2, int(10 * SCALE))
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        t0 = time.perf_counter()
        _build_topn_frame(holder, n_rows, n_slices)
        build_s = time.perf_counter() - t0

        q = "TopN(frame=f, n=1000)"
        want = None
        legs = (("host", False),)
        if USE_DEVICE:
            # routed before the forced-device leg: the forced leg's
            # drain contaminates whatever follows on this shared core.
            legs += (("routed", True), ("device", True))
        for label, use_mesh in legs:
            ex = Executor(holder, host="local", use_mesh=use_mesh,
                          mesh_min_slices=1)
            if label == "device":
                ex._cost_model_enabled = False
            t0 = time.perf_counter()
            got = ex.execute("t3", q)[0]
            first_s = time.perf_counter() - t0
            if want is None:
                want = got
            assert got == want, (label, len(got), len(want))
            lat = []
            for _ in range(5):
                t0 = time.perf_counter()
                ex.execute("t3", q)
                lat.append(time.perf_counter() - t0)
            lat.sort()
            emit_latency(f"c3_topn1000_e2e_{label}_p50", lat[2] * 1e3,
                         device=(label != "host"),
                         rows=n_rows, slices=n_slices, n=len(want),
                         first_ms=round(first_s * 1e3, 1),
                         p95_ms=round(lat[-1] * 1e3, 1),
                         build_s=round(build_s, 1))
            ex.close()
        holder.close()


def config4_executor_routing() -> None:
    """Task: the chosen path must never be slower than the better of
    the two. Config-4 shape through the EXECUTOR three ways: host
    (use_mesh=0), forced device (cost model off), and the default
    calibrated routing — emitting all three so the routing quality is
    a measured fact, not an assumption."""
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    n_slices = max(8, int(128 * SCALE))
    rng = np.random.default_rng(44)
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        frame = holder.create_index_if_not_exists("r4") \
            .create_frame_if_not_exists("f")
        for row in (0, 1):
            cols = (rng.integers(0, SLICE_WIDTH, size=200 * n_slices)
                    + np.repeat(np.arange(n_slices), 200) * SLICE_WIDTH)
            frame.import_bits(np.full(len(cols), row, dtype=np.uint64),
                              cols.astype(np.uint64))
        q = ("Count(Intersect(Bitmap(frame=f, rowID=0),"
             " Bitmap(frame=f, rowID=1)))")

        def measure(label, **kw):
            ex = Executor(holder, host="local", mesh_min_slices=1, **kw)
            if label == "device_forced":
                ex._cost_model_enabled = False
            want = ex.execute("r4", q)  # warm (compile/residency/pools)
            lat = []
            for _ in range(7):
                t0 = time.perf_counter()
                got = ex.execute("r4", q)
                lat.append(time.perf_counter() - t0)
            assert got == want
            p50 = sorted(lat)[len(lat) // 2]
            # 8 routed executions (1 warm + 7 timed): all vetoed = the
            # host path, none = the device path, anything in between =
            # mixed per-query decisions (report it, don't guess).
            if label == "routed":
                chose = {0: "device", 8: "host"}.get(ex.cost_vetoes,
                                                     "mixed")
            else:
                chose = "device" if label == "device_forced" else "host"
            emit_latency(f"c4_executor_{label}_p50", p50 * 1e3,
                         device=(chose != "host"),
                         slices=n_slices, vetoes=ex.cost_vetoes)
            ex.close()
            return p50, chose

        # routed before device_forced: the forced leg leaves queued
        # device work draining, which contaminates whatever follows on
        # this shared-core rig.
        host, _ = measure("host", use_mesh=False)
        if USE_DEVICE:
            routed, chose = measure("routed")
            forced, _ = measure("device_forced")
            best = min(host, forced)
            emit("c4_routing_overhead", routed / best, "x_vs_best",
                 host_ms=round(host * 1e3, 2),
                 device_ms=round(forced * 1e3, 2),
                 routed_ms=round(routed * 1e3, 2),
                 chose=chose)
        holder.close()


def config5_executor_cluster_topn() -> None:
    """BASELINE config 5's single-host form through the EXECUTOR: TopN
    over a 256-slice (268 M-column) ranked frame, end to end — the
    candidate phase walks 256 rank caches, the exact phase merges
    cluster-wide, and the calibrated router picks the serving path.
    (The multi-host form of the same program is exercised by the pod
    tests and the driver's dryrun_multichip.)"""
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    n_slices = max(8, int(256 * SCALE))
    n_rows = max(100, int(1000 * SCALE))
    rng = np.random.default_rng(55)
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        frame = holder.create_index_if_not_exists("t5") \
            .create_frame_if_not_exists("f")
        head = min(500, n_rows)
        counts = np.concatenate([
            np.maximum(40, 2000 - 4 * np.arange(head)).astype(np.int64),
            np.full(n_rows - head, 8, dtype=np.int64)])
        rows = np.repeat(np.arange(n_rows, dtype=np.uint64), counts)
        cols = rng.integers(0, n_slices * SLICE_WIDTH, size=len(rows),
                            dtype=np.uint64)
        order = np.argsort(cols // np.uint64(SLICE_WIDTH), kind="stable")
        rows, cols = rows[order], cols[order]
        t0 = time.perf_counter()
        step = max(1, len(rows) // 16)
        for i in range(0, len(rows), step):
            frame.import_bits(rows[i:i + step], cols[i:i + step])
        build_s = time.perf_counter() - t0

        legs = (("host", False),)
        if USE_DEVICE:
            legs += (("routed", True),)
        want: dict = {}
        for label, use_mesh in legs:
            ex = Executor(holder, host="local", use_mesh=use_mesh,
                          mesh_min_slices=1)
            for q, tag in (("TopN(frame=f, n=10)", "plain"),
                           ("TopN(Bitmap(frame=f, rowID=0), frame=f,"
                            " n=10)", "src")):
                t0 = time.perf_counter()
                got = ex.execute("t5", q)[0]
                first_s = time.perf_counter() - t0
                assert want.setdefault(tag, got) == got, (label, tag)
                lat = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    again = ex.execute("t5", q)[0]
                    lat.append(time.perf_counter() - t0)
                assert again == got
                lat.sort()
                # The routed leg only crossed the device when nothing
                # was vetoed (mirrors config4_executor_routing).
                crossed = (label != "host" and ex.cost_vetoes == 0)
                emit_latency(f"c5_executor_topn_{tag}_{label}_p50",
                             lat[2] * 1e3, device=crossed,
                             slices=n_slices, rows=n_rows,
                             first_ms=round(first_s * 1e3, 1),
                             vetoes=ex.cost_vetoes,
                             build_s=round(build_s, 1))
            ex.close()
        holder.close()


_SYNC_FLOOR_MS: float = 0.0


def emit_latency(metric: str, ms: float, device: bool = True,
                 **extra) -> None:
    """Latency emit with the sync-floor-subtracted column on DEVICE
    legs, so device-vs-host conclusions transfer between rigs whose
    host↔device sync floors differ. Host legs never pay the sync, so
    the column would be meaningless there."""
    if device and _SYNC_FLOOR_MS > 0:
        extra["minus_floor_ms"] = round(max(0.0, ms - _SYNC_FLOOR_MS), 3)
    emit(metric, ms, "ms", **extra)


def _measure_sync_floor() -> None:
    global _SYNC_FLOOR_MS
    if not USE_DEVICE:
        return
    from pilosa_tpu.parallel import costmodel, mesh as mesh_mod
    model = costmodel.get_model(mesh_mod.make_mesh())
    _SYNC_FLOOR_MS = model.cal.sync_s * 1e3
    emit("sync_floor", _SYNC_FLOOR_MS, "ms",
         host_gbps=round(model.cal.host_bps / 1e9, 2))


def config_topn1000_1024slices() -> None:
    """Plain TopN(1000) p50 at 1024 slices (the 1 B-column shape) —
    round-3 verdict item 7: the candidate/refetch curve past 256
    slices was uncharacterized; the vectorized rank-array host leg
    (executor._topn_local_host_fn + fragment.present_rows) replaced a
    ~2.4 s per-Pair walk with a ~0.3 s merge. Host path (the rank
    caches ARE the candidate source; no device leg exists for the
    sourceless form)."""
    import tempfile

    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder

    n_slices = max(16, int(1024 * SCALE))
    n_rows = max(100, int(2000 * SCALE))
    rng = np.random.default_rng(13)
    with tempfile.TemporaryDirectory() as d:
        holder = Holder(d)
        holder.open()
        try:
            frame = holder.create_index_if_not_exists("t1024") \
                .create_frame_if_not_exists("f")
            counts = np.maximum(
                20, 3000 - 2 * np.arange(n_rows)).astype(np.int64)
            rows = np.repeat(np.arange(n_rows, dtype=np.uint64), counts)
            cols = rng.integers(0, n_slices * SLICE_WIDTH,
                                size=len(rows), dtype=np.uint64)
            order = np.argsort(cols // np.uint64(SLICE_WIDTH),
                               kind="stable")
            rows, cols = rows[order], cols[order]
            step = max(1, len(rows) // 32)
            for i in range(0, len(rows), step):
                frame.import_bits(rows[i:i + step], cols[i:i + step])
            ex = Executor(holder, host="local", use_mesh=False)
            q = "TopN(frame=f, n=1000)"
            t0 = time.perf_counter()
            ex.execute("t1024", q)
            first_ms = (time.perf_counter() - t0) * 1e3
            lat = []
            for _ in range(5):
                t0 = time.perf_counter()
                ex.execute("t1024", q)
                lat.append(time.perf_counter() - t0)
            emit("topn1000_1024slices_p50", sorted(lat)[2] * 1e3, "ms",
                 slices=n_slices, rows=n_rows,
                 first_ms=round(first_ms, 1))
            ex.close()
        finally:
            holder.close()


def config_http_pipelined_setbit() -> None:
    """Over-the-wire SetBit through the real HTTP front door: one
    pipelined keep-alive connection driven by a SUBPROCESS client (the
    in-process GIL would contaminate the measurement). The round-4
    wsgiref server measured ~970 op/s here; the round-5 server's
    pipelining + batch lane is the fix."""
    import subprocess
    import tempfile

    from pilosa_tpu.server.server import Server

    n = max(2000, int(30000 * SCALE))
    with tempfile.TemporaryDirectory() as d:
        srv = Server(d, host="127.0.0.1:0", anti_entropy_interval=0,
                     polling_interval=0)
        srv.open()
        try:
            hostname, port = srv.host.split(":")
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "http_pipeline_client.py"),
                 hostname, port, str(n)],
                capture_output=True, text=True, timeout=240)
            for line in out.stdout.splitlines():
                if line.startswith("RESULT"):
                    emit("http_pipelined_setbit",
                         float(line.split()[1]), "ops/sec", n=n)
                    break
            else:
                emit("http_pipelined_setbit", -1, "error",
                     error=out.stderr[-200:])
        finally:
            srv.close()


def config_wire_import() -> None:
    """Bulk import over the real wire: client-side protobuf encode +
    concurrent per-slice POSTs + server-side decode and apply (the
    round-5 packed-sort lanes). Complements host_import_apply, which
    measures only the in-process apply."""
    import tempfile

    from pilosa_tpu.cluster.client import Client
    from pilosa_tpu.server.server import Server

    n = int(1_000_000 * SCALE)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 300, n).astype(np.uint64)
    cols = rng.integers(0, 1 << 22, n).astype(np.uint64)
    with tempfile.TemporaryDirectory() as d:
        srv = Server(d, host="127.0.0.1:0", anti_entropy_interval=0,
                     polling_interval=0)
        srv.open()
        try:
            client = Client(srv.host)
            client.create_index("wi")
            client.create_frame("wi", "f")
            t0 = time.perf_counter()
            client.import_arrays("wi", "f", rows, cols)
            emit("wire_import", n / (time.perf_counter() - t0),
                 "bits/sec", n=n)
        finally:
            srv.close()


@contextlib.contextmanager
def _write_path_leg(ext: bool, group: bool, fsync: str = "none"):
    """Select one write-path configuration for the A/B legs below:
    the one-crossing extension on/off (roaring reads native_ext.EXT
    per op, so toggling the module attribute is the real switch) and
    the WAL mode env vars, which fragments read at open()."""
    from pilosa_tpu.storage import native_ext

    # Load BEFORE snapshotting: the extension loads lazily at the
    # first Fragment.open() — snapshotting the pre-load None and
    # restoring it on exit would clobber the loaded module for every
    # later leg (load() latches, so it never comes back): round-1 A
    # measures the extension, every round after silently measures
    # pure Python.
    native_ext.load()
    saved_ext = native_ext.EXT
    saved_env = {k: os.environ.get(k)
                 for k in ("PILOSA_TPU_WAL_GROUP", "PILOSA_TPU_WAL_FSYNC")}
    if not ext:
        native_ext.EXT = None
    os.environ["PILOSA_TPU_WAL_GROUP"] = "1" if group else "0"
    os.environ["PILOSA_TPU_WAL_FSYNC"] = fsync
    try:
        yield
    finally:
        native_ext.EXT = saved_ext
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def config_write_path() -> None:
    """ISSUE 8 acceptance table: the write path A/B, interleaved.

    Leg A is the production write path — one compiled crossing per op
    (native/fastmutate.c: container mutate + marshaled WAL record in
    one call) feeding the group-committed WAL. Leg B is the
    pre-ISSUE-8 path: pure-Python mutate through the per-call layers,
    write-through op-log. Rounds interleave A and B so shared-slot
    drift cancels; best-of-rounds is reported (steady state — the
    slot's scheduling stalls are not the write path's cost). Four
    measurements: per-op Fragment.set_bit, per-op through the
    executor (parse + route + mutate), bulk import over the real
    wire, and fsyncs-per-1k-ops from 8 concurrent durable writers
    (group commit coalescing barriers vs one fsync per op). Folds
    into MANIFEST.json `write_path` and merges into WRITEPATH.json
    for bench.py's line of record."""
    import tempfile
    import threading

    from pilosa_tpu.storage.fragment import Fragment

    rounds = 3

    def setbit_leg(n: int) -> float:
        # Steady-state serving shape: 50 rows over the slice (the
        # executor-leg workload) keeps ops landing in EXISTING
        # containers — the production per-op shape. A warmup fifth
        # populates the container set so the measured span isn't
        # dominated by one-time container creation (which bails to
        # the Python path by design).
        with tempfile.TemporaryDirectory() as d:
            frag = Fragment(os.path.join(d, "frag"), "wp", "f",
                            "standard", 0)
            frag.open()
            try:
                rng = np.random.default_rng(7)
                warm = n // 5
                rows = rng.integers(0, 50, n + warm).tolist()
                cols = rng.integers(0, 1 << 20, n + warm).tolist()
                for r, c in zip(rows[:warm], cols[:warm]):
                    frag.set_bit(r, c)
                t0 = time.perf_counter()
                for r, c in zip(rows[warm:], cols[warm:]):
                    frag.set_bit(r, c)
                frag.wal_barrier()  # the ack point is part of the cost
                el = time.perf_counter() - t0
                frag._join_snapshot()
            finally:
                frag.close()
        return n / el

    # Interleaved A/B rounds: per-op Fragment.set_bit.
    n_a, n_b = max(1000, int(40_000 * SCALE)), max(500, int(8_000 * SCALE))
    a_ops = b_ops = 0.0
    for _ in range(rounds):
        with _write_path_leg(ext=True, group=True):
            a_ops = max(a_ops, setbit_leg(n_a))
        with _write_path_leg(ext=False, group=False):
            b_ops = max(b_ops, setbit_leg(n_b))
    emit("writepath_setbit_per_op", a_ops, "ops/sec",
         baseline_ops=round(b_ops, 1), speedup=round(a_ops / b_ops, 2))

    # Executor per-op: the full serving stack minus HTTP — parse
    # (point-mutation regex lane), route (write fast lane), mutate —
    # with the commit barrier at the httpd batch-lane cadence (one
    # barrier acks a 64-query pipelined group, server.py's
    # _query_batcher contract). A per-op barrier would measure the
    # bare write(2) syscall (~80 us on this host), which is exactly
    # the cost group commit exists to amortize — the concurrent-
    # writer fsync leg below covers per-op durability.
    def executor_leg(n: int) -> float:
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.storage import wal as wal_mod

        with tempfile.TemporaryDirectory() as d:
            holder = Holder(d)
            holder.open()
            try:
                holder.create_index("wp").create_frame("f")
                ex = Executor(holder, host="local", use_mesh=False)
                warm = n // 5
                queries = [f'SetBit(frame="f", rowID={i % 50},'
                           f' columnID={i * 13 % (1 << 20)})'
                           for i in range(n + warm)]
                for q in queries[:warm]:  # containers + caches warm
                    ex.execute("wp", q)
                t0 = time.perf_counter()
                for i, q in enumerate(queries[warm:]):
                    ex.execute("wp", q)
                    if i % 64 == 63:
                        wal_mod.barrier_all()
                wal_mod.barrier_all()
                el = time.perf_counter() - t0
                ex.close()
            finally:
                holder.close()
        return n / el

    ea_ops = eb_ops = 0.0
    for _ in range(rounds):
        with _write_path_leg(ext=True, group=True):
            ea_ops = max(ea_ops, executor_leg(
                max(1000, int(25_000 * SCALE))))
        with _write_path_leg(ext=False, group=False):
            eb_ops = max(eb_ops, executor_leg(
                max(500, int(6_000 * SCALE))))
    emit("writepath_executor_per_op", ea_ops, "ops/sec",
         baseline_ops=round(eb_ops, 1),
         speedup=round(ea_ops / eb_ops, 2))

    # Wire import (real HTTP: encode + concurrent per-slice POSTs +
    # decode + WAL-first apply + commit barrier before the 200) vs the
    # same block applied in-process — the ≥70%-of-in-process target.
    def wire_leg() -> tuple:
        from pilosa_tpu.cluster.client import Client
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.server.server import Server

        n = int(1_000_000 * SCALE)
        rng = np.random.default_rng(0)
        # 50 rows x 4 slices: the steady-ingest shape (containers see
        # ~250 bits each) — matches the per-op legs' row space and the
        # host_import_apply density family.
        rows = rng.integers(0, 50, n).astype(np.uint64)
        cols = rng.integers(0, 1 << 22, n).astype(np.uint64)
        with tempfile.TemporaryDirectory() as d:
            srv = Server(d, host="127.0.0.1:0", anti_entropy_interval=0,
                         polling_interval=0)
            srv.open()
            try:
                client = Client(srv.host)
                client.create_index("wi")
                client.create_frame("wi", "f")
                t0 = time.perf_counter()
                client.import_arrays("wi", "f", rows, cols)
                wire = n / (time.perf_counter() - t0)
            finally:
                srv.close()
        with tempfile.TemporaryDirectory() as d:
            holder = Holder(d)
            holder.open()
            try:
                frame = holder.create_index("wi").create_frame("f")
                t0 = time.perf_counter()
                frame.import_bits(rows, cols)
                inproc = n / (time.perf_counter() - t0)
            finally:
                holder.close()
        return wire, inproc

    wire_bps = inproc_bps = 0.0
    for _ in range(rounds):
        with _write_path_leg(ext=True, group=True):
            w, p = wire_leg()
            wire_bps, inproc_bps = max(wire_bps, w), max(inproc_bps, p)
    emit("writepath_wire_import", wire_bps, "bits/sec",
         inprocess_bps=round(inproc_bps, 1),
         wire_over_inprocess=round(wire_bps / inproc_bps, 3))

    # fsync amortization: 32 concurrent writers (a production ingest
    # fan-in), each op durably acked. A: FSYNC=group — concurrent
    # barriers coalesce into one leader fsync per batch (the
    # reduction factor approaches the writer count). B: the
    # un-amortized discipline — write-through WAL, one fsync per op
    # per writer.
    def fsync_leg(group: bool, per: int) -> tuple:
        n_threads = 32
        with tempfile.TemporaryDirectory() as d:
            frag = Fragment(os.path.join(d, "frag"), "wp", "f",
                            "standard", 0)
            frag.open()
            try:
                errs: list = []
                start = threading.Barrier(n_threads)

                def writer(t: int) -> None:
                    rng = np.random.default_rng(t)
                    # 32 disjoint 32 Ki-column stripes tile the 2^20
                    # slice exactly; << 16 would push t >= 16 past it.
                    base = t << 15
                    try:
                        start.wait()
                        for _ in range(per):
                            frag.set_bit(int(rng.integers(0, 50)),
                                         base + int(rng.integers(0, 3000)))
                            if group:
                                frag.wal_barrier()  # durable ack
                            else:
                                os.fsync(frag._file.fileno())
                    except Exception as e:  # noqa: BLE001
                        errs.append(e)

                threads = [threading.Thread(target=writer, args=(t,))
                           for t in range(n_threads)]
                t0 = time.perf_counter()
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                el = time.perf_counter() - t0
                if errs:
                    raise errs[0]
                n = n_threads * per
                fsyncs = frag._wal.fsyncs if group else n
                frag._join_snapshot()
            finally:
                frag.close()
        return n / el, fsyncs * 1000.0 / n

    ga_ops = gb_ops = 0.0
    ga_per1k = gb_per1k = float("inf")
    for _ in range(rounds):
        with _write_path_leg(ext=True, group=True, fsync="group"):
            ops, per1k = fsync_leg(True, max(50, int(400 * SCALE)))
            ga_ops, ga_per1k = max(ga_ops, ops), min(ga_per1k, per1k)
        with _write_path_leg(ext=True, group=False, fsync="none"):
            ops, per1k = fsync_leg(False, max(25, int(125 * SCALE)))
            gb_ops, gb_per1k = max(gb_ops, ops), min(gb_per1k, per1k)
    emit("writepath_fsync_group", ga_ops, "ops/sec",
         fsyncs_per_1k=round(ga_per1k, 1),
         baseline_fsyncs_per_1k=round(gb_per1k, 1),
         reduction_x=round(gb_per1k / max(ga_per1k, 1e-9), 1))

    art = {
        "setbit_per_op_ops": round(a_ops, 1),
        "setbit_per_op_baseline_ops": round(b_ops, 1),
        "setbit_per_op_speedup": round(a_ops / b_ops, 2),
        "executor_per_op_ops": round(ea_ops, 1),
        "executor_per_op_baseline_ops": round(eb_ops, 1),
        "wire_import_bits_s": round(wire_bps, 1),
        "wire_import_mbits_s": round(wire_bps / 1e6, 2),
        "inprocess_import_bits_s": round(inproc_bps, 1),
        "wire_over_inprocess": round(wire_bps / inproc_bps, 3),
        "concurrent_durable_ops_s": round(ga_ops, 1),
        "fsyncs_per_1k_group": round(ga_per1k, 1),
        "fsyncs_per_1k_per_op": round(gb_per1k, 1),
        "fsync_reduction_x": round(gb_per1k / max(ga_per1k, 1e-9), 1),
        "rounds": rounds,
        "scale": SCALE,
        "date": time.strftime("%Y-%m-%d"),
    }
    _WRITE_PATH.update(art)
    # Merge into WRITEPATH.json (the canonical write_path artifact
    # bench.py stamps into its line) alongside _write_denominator's
    # native-denominator keys — merge, not clobber: either config may
    # run without the other.
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "WRITEPATH.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    doc.update(art)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def config_distributed_topn() -> None:
    """ROADMAP item 3 acceptance artifact: distributed TopN pushdown
    vs the fan-out path, interleaved A/B on a 2-node IN-PROCESS
    cluster (cross-wired static membership, replicas=1 so slices
    genuinely split), plus a single-node reference server over the
    same data, plus the repeated resident Count(Intersect) chain on
    the coordinator — first call pays the fan-out + fold, repeats
    serve from the generation-validated hot-query cache at the
    /generations round-trip floor. Host path only (mesh off): the
    coordination tax is the thing under test, not device compute.
    Folds into MANIFEST.json `distributed_topn` and writes
    DISTRIBUTED.json for bench.py's line of record."""
    import statistics
    import tempfile
    import urllib.request

    saved_env = {k: os.environ.get(k)
                 for k in ("PILOSA_TPU_MESH", "PILOSA_TPU_WARMUP")}
    os.environ["PILOSA_TPU_MESH"] = "0"
    os.environ["PILOSA_TPU_WARMUP"] = "0"
    from pilosa_tpu import SLICE_WIDTH as W
    from pilosa_tpu.cluster.client import Client as PClient
    from pilosa_tpu.cluster.topology import Node
    from pilosa_tpu.server.server import Server

    def post(host, path, body=b"{}"):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body, method="POST")
        return urllib.request.urlopen(req, timeout=30).read()

    def query(host, index, body):
        return json.loads(post(host, f"/index/{index}/query",
                               body.encode()))["results"]

    def p50_ms(host, index, body, reps):
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            query(host, index, body)
            lat.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(lat)

    n_slices = 8
    n_rows = 16
    n_bits = max(2000, int(12_000 * SCALE))
    reps = max(5, int(15 * SCALE))
    rounds = 3
    servers = []
    td = tempfile.TemporaryDirectory()
    try:
        def make(name):
            s = Server(os.path.join(td.name, name), host="127.0.0.1:0",
                       anti_entropy_interval=0, polling_interval=0)
            s.open()
            servers.append(s)
            return s

        s1, s2, solo = make("n1"), make("n2"), make("solo")
        nodes = [Node(s1.host), Node(s2.host)]
        for s in (s1, s2):
            s.cluster.nodes = [Node(n.host) for n in nodes]
        # Static membership has no broadcast channel: create the
        # schema on every node explicitly (server_test.go pattern).
        for h in (s1.host, s2.host, solo.host):
            post(h, "/index/dt")
            post(h, "/index/dt/frame/f")
        rng = np.random.default_rng(11)
        rows = rng.integers(0, n_rows, n_bits).astype(np.uint64)
        cols = rng.choice(n_slices * W, size=n_bits,
                          replace=False).astype(np.uint64)
        PClient(s1.host).import_arrays("dt", "f", rows, cols)
        PClient(solo.host).import_arrays("dt", "f", rows, cols)

        topn_q = 'TopN(frame="f", n=5)'
        # The hot-query cache would serve repeats and hide the merge
        # being measured — off for the TopN legs, back on for the
        # chain leg below.
        s1.executor._cluster_cache_entries = 0
        want = query(solo.host, "dt", topn_q)
        assert query(s1.host, "dt", topn_q) == want, \
            "pushdown merge diverged from single-node"

        # Per-round ADJACENT triples (pushdown, fan-out, single-node)
        # so shared-slot drift cancels in the ratios; best-of-rounds
        # is the steady state. A warmup query per mode arms the
        # speculative hint memo (the cold first pushdown pays an
        # extra round by design).
        query(s1.host, "dt", topn_q)
        push = fan = single = float("inf")
        r_single = r_fanout = float("inf")
        for _ in range(rounds):
            s1.executor._topn_pushdown = True
            p = p50_ms(s1.host, "dt", topn_q, reps)
            s1.executor._topn_pushdown = False
            assert query(s1.host, "dt", topn_q) == want
            fo = p50_ms(s1.host, "dt", topn_q, reps)
            sg = p50_ms(solo.host, "dt", topn_q, reps)
            push, fan, single = (min(push, p), min(fan, fo),
                                 min(single, sg))
            r_single = min(r_single, p / max(sg, 1e-9))
            r_fanout = min(r_fanout, p / max(fo, 1e-9))
        s1.executor._topn_pushdown = True
        emit("distributed_topn_p50", push, "ms",
             fanout_p50_ms=round(fan, 3),
             single_node_p50_ms=round(single, 3),
             vs_single=round(r_single, 3),
             vs_fanout=round(r_fanout, 3))

        # Resident chain: repeated Count(Intersect) over the split
        # slice set — repeats validate generation tokens (~one
        # /generations RTT per peer) instead of re-running the
        # fan-out + fold.
        s1.executor._cluster_cache_entries = 64
        chain_q = ('Count(Intersect(Bitmap(frame="f", rowID=0),'
                   ' Bitmap(frame="f", rowID=1)))')
        t0 = time.perf_counter()
        query(s1.host, "dt", chain_q)
        miss_ms = (time.perf_counter() - t0) * 1e3
        hit_ms = p50_ms(s1.host, "dt", chain_q, reps)
        # The floor the hit is bounded by: one bare /generations
        # probe round-trip to the peer.
        probe = []
        for _ in range(reps):
            t0 = time.perf_counter()
            urllib.request.urlopen(
                f"http://{s2.host}/generations?index=dt&slices=0",
                timeout=10).read()
            probe.append((time.perf_counter() - t0) * 1e3)
        rtt_ms = statistics.median(probe)
        from pilosa_tpu.obs import metrics as obs_metrics
        hits = obs_metrics.CLUSTER_CACHE_REQUESTS.labels("hit").value
        emit("distributed_chain_hit_p50", hit_ms, "ms",
             miss_ms=round(miss_ms, 3),
             generations_rtt_ms=round(rtt_ms, 3),
             vs_rtt_floor=round(hit_ms / max(rtt_ms, 1e-9), 3))
        assert hits >= reps, "chain repeats were not cache hits"

        table = {
            "topn_pushdown_p50_ms": round(push, 3),
            "topn_fanout_p50_ms": round(fan, 3),
            "topn_single_node_p50_ms": round(single, 3),
            "topn_vs_single": round(r_single, 3),
            "topn_vs_fanout": round(r_fanout, 3),
            "chain_miss_ms": round(miss_ms, 3),
            "chain_hit_p50_ms": round(hit_ms, 3),
            "generations_rtt_ms": round(rtt_ms, 3),
            "chain_hit_vs_rtt": round(hit_ms / max(rtt_ms, 1e-9), 3),
            "n_slices": n_slices, "n_rows": n_rows, "bits": n_bits,
            "differential_equal": True,
        }
        _DISTRIBUTED_TOPN.update(table)
        with open(os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "DISTRIBUTED.json"),
                "w") as f:
            json.dump(table, f, indent=1)
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        td.cleanup()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def config_resize() -> None:
    """ROADMAP item 5 acceptance artifact: an online 2→3 node resize
    on an in-process cluster under OPEN query load — records the
    resize duration, the streamed volume, and what the migration did
    to query latency (p50/p99 during vs a baseline window measured
    immediately before, same query mix, same slot). Host path only
    (mesh off): the migration machinery is the thing under test.
    Folds into MANIFEST.json `resize` and writes RESIZE.json for
    bench.py's line of record."""
    import statistics
    import tempfile
    import threading
    import urllib.request

    saved_env = {k: os.environ.get(k)
                 for k in ("PILOSA_TPU_MESH", "PILOSA_TPU_WARMUP")}
    os.environ["PILOSA_TPU_MESH"] = "0"
    os.environ["PILOSA_TPU_WARMUP"] = "0"
    from pilosa_tpu import SLICE_WIDTH as W
    from pilosa_tpu.cluster.client import Client as PClient
    from pilosa_tpu.cluster.topology import Node
    from pilosa_tpu.server.server import Server

    def post(host, path, body=b"{}"):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body, method="POST")
        return urllib.request.urlopen(req, timeout=30).read()

    def query(host, index, body):
        return json.loads(post(host, f"/index/{index}/query",
                               body.encode()))["results"]

    n_slices = 8
    n_bits = max(4000, int(20_000 * SCALE))
    baseline_s = max(1.0, 2.0 * SCALE)
    servers = []
    td = tempfile.TemporaryDirectory()
    try:
        def make(name):
            s = Server(os.path.join(td.name, name),
                       host="127.0.0.1:0", anti_entropy_interval=0,
                       polling_interval=0)
            s.open()
            servers.append(s)
            return s

        s1, s2, s3 = make("n1"), make("n2"), make("n3")
        for s in servers:
            s.cluster.nodes = [Node(s1.host), Node(s2.host)]
        for h in (s1.host, s2.host, s3.host):
            post(h, "/index/rs")
            post(h, "/index/rs/frame/f")
        rng = np.random.default_rng(29)
        rows = rng.integers(0, 300, n_bits).astype(np.uint64)
        cols = rng.choice(n_slices * W, size=n_bits,
                          replace=False).astype(np.uint64)
        PClient(s1.host).import_arrays("rs", "f", rows, cols)
        for s in servers:
            s.holder.index("rs").set_remote_max_slice(n_slices - 1)
        model0 = int((rows == 0).sum())
        q = 'Count(Bitmap(frame="f", rowID=0))'
        assert query(s1.host, "rs", q)[0] == model0

        # Wrong answers are collected, not asserted inline: an
        # AssertionError inside the loader THREAD would die silently
        # and the artifact would still claim zero_wrong_answers
        # (review finding) — the join below re-raises.
        wrong: list = []

        def sample_window(stop_fn):
            lat = []
            while not stop_fn():
                t0 = time.perf_counter()
                got = query(s1.host, "rs", q)[0]
                lat.append((time.perf_counter() - t0) * 1e3)
                if got != model0:
                    wrong.append(got)
            return lat

        # Baseline window (steady 2-node cluster, same query).
        t_end = time.perf_counter() + baseline_s
        base = sample_window(lambda: time.perf_counter() >= t_end)

        # Resize under the same open load.
        during: list = []
        done_evt = threading.Event()

        def loader():
            try:
                during.extend(sample_window(done_evt.is_set))
            except Exception as e:  # noqa: BLE001 - surfaced below
                wrong.append(f"loader died: {e!r}")

        t = threading.Thread(target=loader)
        t.start()
        post(s1.host, "/cluster/resize", json.dumps(
            {"hosts": [s1.host, s2.host, s3.host]}).encode())
        op = None
        deadline = time.time() + 120
        while time.time() < deadline:
            op = json.loads(urllib.request.urlopen(
                f"http://{s1.host}/cluster/resize",
                timeout=10).read())["op"]
            if op["phase"] in ("done", "aborted"):
                break
            time.sleep(0.05)
        done_evt.set()
        t.join()
        assert op and op["phase"] == "done", op
        assert not wrong, f"WRONG ANSWERS under migration: {wrong[:5]}"
        assert query(s1.host, "rs", q)[0] == model0
        assert query(s3.host, "rs", q)[0] == model0

        def pct(xs, p):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(p * len(xs)))]

        duration_s = (op["finishedAt"] or 0) - op["startedAt"]
        base_p50, base_p99 = (statistics.median(base),
                              pct(base, 0.99))
        dur_p50, dur_p99 = (statistics.median(during),
                            pct(during, 0.99))
        table = {
            "resize_duration_s": round(duration_s, 3),
            "slices_moved": op["slicesMoved"],
            "bytes_streamed": op["bytesStreamed"],
            "stream_passes": op["streamPasses"],
            "baseline_p50_ms": round(base_p50, 3),
            "baseline_p99_ms": round(base_p99, 3),
            "during_p50_ms": round(dur_p50, 3),
            "during_p99_ms": round(dur_p99, 3),
            "p99_inflation": round(dur_p99 / max(base_p99, 1e-9), 3),
            "queries_during": len(during),
            "zero_wrong_answers": True,
            "n_slices": n_slices, "bits": n_bits,
            # All three nodes + the streamer share ONE interpreter
            # (GIL) here, so the inflation is an upper bound on what
            # cross-process deployments see; [cluster] resize-pace
            # trades migration duration for serving headroom.
            "note": "in-process cluster: shared-GIL upper bound",
        }
        emit("resize_duration", duration_s, "s",
             p99_inflation=table["p99_inflation"],
             bytes_streamed=op["bytesStreamed"])
        emit("resize_during_p99", dur_p99, "ms",
             baseline_p99_ms=table["baseline_p99_ms"])
        _RESIZE.update(table)
        with open(os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "RESIZE.json"), "w") as f:
            json.dump(table, f, indent=1)
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        td.cleanup()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def config_tenant_isolation() -> None:
    """ISSUE 14 acceptance artifact: interleaved multi-tenant A/B
    against a REAL server subprocess (the load generator must not
    share the server's interpreter, or the measurement itself
    perturbs the quiet tenant).

    Leg A: the quiet tenant alone, closed-loop — its solo p50/p99.
    Leg B: the same quiet loop while an AGGRESSOR tenant (admission
    cap 2, queue quota 2, 2 s wall ceiling) is driven by 8 concurrent
    Retry-After-honoring workers — 4x its cap — running a dense
    multi-row Union/Count (~0.8 s of work per request). Overflow
    sheds as tenant-scoped 429s; requests whose queue wait pushes
    them past the wall ceiling are cost-policy KILLED (402). Leg C
    (the counterfactual): the identical aggressor against the same
    data with NO tenant policy — it eats the global slot pool and the
    quiet tenant queues behind ~0.8 s queries. Rounds interleave A
    and B; C runs once at the end on a fresh default-policy server
    over the same data dir. Both tenants' successful results are
    differential-checked every probe. Folds into MANIFEST.json
    `tenant_isolation` and writes TENANTS.json."""
    import statistics
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tests"))
    from podenv import cpu_env, free_port, wait_up

    from pilosa_tpu import SLICE_WIDTH as W
    from pilosa_tpu.cluster.client import Client as PClient

    rounds = 3
    window_s = max(1.5, 3.0 * SCALE)
    # 8 workers against a concurrency cap of 2 (+2 queue quota): 4x
    # the cap offered, 2x what the whole admission envelope accepts.
    aggr_workers, aggr_cap, aggr_quota = 8, 2, 2
    wall_ms = 2000
    n_rows, col_stride = 12, 3

    def post(host, path, body=b"", timeout=120):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body, method="POST")
        return urllib.request.urlopen(req, timeout=timeout).read()

    td = tempfile.TemporaryDirectory()
    data_dir = os.path.join(td.name, "data")
    logf = open(os.path.join(td.name, "server.log"), "w")
    env = cpu_env()
    env["PILOSA_TPU_WARMUP"] = "0"
    env["PILOSA_TPU_COST_MODEL"] = "0"
    env["PILOSA_TPU_MESH"] = "0"  # the admission machinery is the
    # thing under test (the config_resize precedent); host path keeps
    # the 0.4 CPU backend's serialized device dispatch out of the A/B

    def spawn(tenants_spec):
        port = free_port()
        p = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "-d", data_dir, "-b", f"127.0.0.1:{port}",
             "--tenants", tenants_spec,
             "--anti-entropy.interval", "300s"],
            env=env, stdout=logf, stderr=logf, cwd=repo)
        host = f"127.0.0.1:{port}"
        wait_up(host)
        return p, host

    proc, host = spawn(
        f"default:weight=1;aggr:weight=1,concurrency={aggr_cap},"
        f"queue-depth={aggr_quota},max-wall={wall_ms}ms")
    proc_c = None
    try:
        # Dense rows (every {col_stride}rd column over 4 slices):
        # bitmap containers, so the aggressor's Union folds are big
        # contiguous numpy — the workload shape where per-tenant QoS
        # (not the interpreter) decides who waits.
        for index in ("quiet", "aggr"):
            post(host, f"/index/{index}")
            post(host, f"/index/{index}/frame/f")
            for r in range(n_rows):
                cols_d = np.arange(r % col_stride, 4 * W, col_stride,
                                   dtype=np.uint64)
                PClient(host).import_arrays(
                    index, "f", np.full(len(cols_d), r, np.uint64),
                    cols_d)
        model = len(np.arange(0, 4 * W, col_stride))
        # The 12 rows cycle through every column residue, so their
        # union covers the whole 4-slice column space.
        heavy_model = 4 * W
        heavy = ("Count(Union(" + ",".join(
            f'Bitmap(frame="f", rowID={r})'
            for r in range(n_rows)) + "))").encode()
        quiet_body = b'Count(Bitmap(frame="f", rowID=0))'

        wrong: list = []

        def quiet_probe(h):
            t0 = time.perf_counter()
            got = json.loads(post(h, "/index/quiet/query",
                                  quiet_body))["results"][0]
            if got != model:
                wrong.append(("quiet", got))
            return (time.perf_counter() - t0) * 1e3

        def quiet_window(h, seconds):
            lat = []
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                lat.append(quiet_probe(h))
            return lat

        def drive_aggr(h, seconds, counts):
            stop = threading.Event()
            mu = threading.Lock()

            def worker():
                while not stop.is_set():
                    try:
                        got = json.loads(post(
                            h, "/index/aggr/query",
                            heavy))["results"][0]
                        if got != heavy_model:
                            wrong.append(("aggr", got))
                        c, ra = 200, 0.0
                    except urllib.error.HTTPError as e:
                        e.read()
                        c = e.code
                        ra = float(e.headers.get("Retry-After")
                                   or 0.2)
                    with mu:
                        counts[c] = counts.get(c, 0) + 1
                    if c != 200:
                        # Compliant clients honor Retry-After; a
                        # client that ignores it is a DoS, and even
                        # then the quiet tenant's ADMISSION position
                        # is protected (its slots/queue are its own).
                        stop.wait(min(ra, 1.0))

            threads = [threading.Thread(target=worker)
                       for _ in range(aggr_workers)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            out = quiet_window(h, seconds)
            stop.set()
            for t in threads:
                t.join(timeout=60)
            return out

        def pct(xs, p):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(p * len(xs)))]

        # Warm both paths once.
        quiet_probe(host)
        try:
            post(host, "/index/aggr/query", heavy)
        except urllib.error.HTTPError as e:
            e.read()

        solo, contended = [], []
        aggr_counts: dict = {}
        for _ in range(rounds):
            solo.extend(quiet_window(host, window_s))      # leg A
            contended.extend(drive_aggr(host, window_s,
                                        aggr_counts))      # leg B
        shed = aggr_counts.get(429, 0)
        killed = aggr_counts.get(402, 0)
        assert not wrong, f"WRONG ANSWERS: {wrong[:5]}"
        assert shed + killed > 0, (
            f"aggressor at {aggr_workers} workers vs cap {aggr_cap}"
            f" was never shed/killed: {aggr_counts}")
        dbg = json.loads(urllib.request.urlopen(
            f"http://{host}/debug/tenants", timeout=10).read())
        burn = (dbg["tenants"].get("quiet", {}).get("slo", {})
                .get("burnRates", {}).get("5m", 0.0))
        aggr_row = dbg["tenants"].get("aggr", {})
        proc.send_signal(2)
        proc.wait(timeout=30)

        # Leg C: the same aggressor, NO tenant policy, same data.
        # Compared against the SAME solo baseline as leg B (one
        # denominator for both ratios).
        proc_c, host_c = spawn("default:weight=1")
        unpol_counts: dict = {}
        quiet_probe(host_c)  # warm the fresh server's caches
        unpoliced = drive_aggr(host_c, window_s, unpol_counts)
        assert not wrong, f"WRONG ANSWERS (unpoliced): {wrong[:5]}"

        solo_p50, solo_p99 = statistics.median(solo), pct(solo, 0.99)
        cont_p50, cont_p99 = (statistics.median(contended),
                              pct(contended, 0.99))
        unpol_p99 = pct(unpoliced, 0.99)
        ratio = cont_p99 / max(solo_p99, 1e-9)
        unpol_ratio = unpol_p99 / max(solo_p99, 1e-9)
        # The artifact ENFORCES its isolation invariants, not just
        # records them: the quiet tenant's burn must sit under the
        # fast-burn threshold under attack, and the policed quiet
        # p99 must beat the unpoliced counterfactual by a wide
        # margin (the machinery's effect). The 1.5x solo target is
        # recorded with a pass flag — on this CPU-only container the
        # residual is interpreter timesharing (environment_note).
        assert burn < 10.0, f"quiet burn {burn} past threshold"
        assert unpol_p99 > 5 * cont_p99, (
            f"no isolation effect: policed p99 {cont_p99:.1f}ms vs"
            f" unpoliced {unpol_p99:.1f}ms")
        table = {
            "quiet_solo_p50_ms": round(solo_p50, 3),
            "quiet_solo_p99_ms": round(solo_p99, 3),
            "quiet_contended_p50_ms": round(cont_p50, 3),
            "quiet_contended_p99_ms": round(cont_p99, 3),
            "quiet_p99_ratio": round(ratio, 3),
            "quiet_p99_ratio_target": 1.5,
            "quiet_p99_ratio_pass": ratio <= 1.5,
            "quiet_p99_unpoliced_ms": round(unpol_p99, 3),
            "quiet_p99_ratio_unpoliced": round(unpol_ratio, 3),
            "isolation_factor": round(unpol_p99 / max(cont_p99,
                                                      1e-9), 2),
            "quiet_burn_5m": burn,
            "burn_threshold": 10.0,
            "aggr_workers": aggr_workers,
            "aggr_admission_cap": aggr_cap,
            "aggr_offered_over_cap": round(aggr_workers / aggr_cap,
                                           2),
            "aggr_wall_ceiling_ms": wall_ms,
            "aggr_ok": aggr_counts.get(200, 0),
            "aggr_shed_429": shed,
            "aggr_killed_402": killed,
            "aggr_penalty_score": aggr_row.get("penaltyScore", 0.0),
            "aggr_unpoliced_ok": unpol_counts.get(200, 0),
            "zero_wrong_answers": True,
            "rounds": rounds,
            "window_s": window_s,
            "samples_solo": len(solo),
            "samples_contended": len(contended),
            "environment_note": (
                "CPU-only container, single interpreter: the"
                " residual contended-vs-solo inflation is"
                " GIL/core timesharing below the scheduler —"
                " admission wait stays ~0.1 ms under full attack"
                " (per-stage profile); on parallel hardware the"
                " admission numbers are the binding ones"),
        }
        _TENANT_ISOLATION.update(table)
        emit("tenant_isolation_quiet_p99", cont_p99, "ms",
             **{k: v for k, v in table.items()
                if k not in ("quiet_contended_p99_ms",
                             "environment_note")})
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "TENANTS.json")
        with open(path, "w") as f:
            json.dump({"written_by": "benchmarks/suite.py"
                                     " config_tenant_isolation",
                       "scale": SCALE, **table}, f, indent=1)
    finally:
        for pp in (proc, proc_c):
            if pp is not None and pp.poll() is None:
                pp.send_signal(2)
                try:
                    pp.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pp.kill()
        logf.close()
        td.cleanup()


def config_tiered() -> None:
    """ISSUE 16 acceptance artifact: the tiered-storage working-set
    manager serving an index ≥ 10× the resident budget.

    Build a bulk of fragments plus a small working set, snapshot
    everything, and measure the working-set Count p50/p99 through the
    executor twice: leg A all-resident (the baseline), leg B after
    demoting EVERYTHING cold and pushing the bulk into the blob tier
    — so local residency starts at zero, the first probe pays the
    blob fetch + block faults (reported as first_ms), and the warm
    window runs with the manager's eviction/retry pass interleaved
    under a budget of total/10. Every probe differential-checks its
    count against the build-time model: zero wrong answers is an
    assertion, not a hope. Folds into MANIFEST.json `tiered` and
    writes TIERED.json."""
    import statistics
    import tempfile

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.tier.manager import TierManager

    n_bulk = max(10, int(30 * SCALE))
    n_ws = 2
    n_rows, per_row = 4, 20000
    probes_resident = max(60, int(200 * SCALE))
    probes_tiered = max(90, int(300 * SCALE))

    td = tempfile.TemporaryDirectory()
    holder = Holder(os.path.join(td.name, "data"))
    holder.open()
    ex = Executor(holder, host="local", use_mesh=False)
    try:
        rng = np.random.default_rng(16)
        model: dict = {}
        frags: dict = {}
        names = [f"bulk{i}" for i in range(n_bulk)] + \
                [f"ws{i}" for i in range(n_ws)]
        for name in names:
            idx = holder.create_index(name)
            view = idx.create_frame("f").create_view_if_not_exists(
                "standard")
            frag = view.create_fragment_if_not_exists(0)
            rows_np, cols_np, counts = [], [], {}
            for r in range(n_rows):
                cols = np.unique(rng.integers(
                    0, 1 << 20, size=per_row)).astype(np.uint64)
                rows_np.append(np.full(len(cols), r, np.uint64))
                cols_np.append(cols)
                counts[r] = len(cols)
            frag.import_bits(np.concatenate(rows_np),
                             np.concatenate(cols_np))
            model[name] = counts
            frags[name] = frag
        total_bytes = sum(os.path.getsize(f.path)
                          for f in frags.values())
        budget = total_bytes // 10
        ws_bytes = sum(os.path.getsize(frags[f"ws{i}"].path)
                       for i in range(n_ws))

        wrong: list = []

        def probe(i: int) -> float:
            name = f"ws{i % n_ws}"
            r = (i // n_ws) % n_rows
            t0 = time.perf_counter()
            got = ex.execute(
                name, f'Count(Bitmap(frame="f", rowID={r}))')[0]
            dt = (time.perf_counter() - t0) * 1e3
            if got != model[name][r]:
                wrong.append((name, r, got))
            return dt

        def pct(xs, p):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(p * len(xs)))]

        probe(0)  # warm the executor path once
        resident = [probe(i) for i in range(probes_resident)]

        mgr = TierManager(
            holder, resident_budget=budget, high_watermark=0.9,
            low_watermark=0.7, idle_s=30.0, blob_idle_s=60.0,
            cold_dir=os.path.join(td.name, "_tier"), blob="dir",
            pace_s=0.0)
        holder.tier = mgr
        mgr.sync()
        for frag in frags.values():
            frag.demote_cold()
        for i in range(n_bulk):
            mgr.push_blob(frags[f"bulk{i}"])
        local_bytes = sum(
            os.path.getsize(f.path) for f in frags.values()
            if os.path.exists(f.path))

        first_ms = probe(0)  # pays the blob fetch + block faults
        for i in range(n_ws):
            # The prefetcher's move for a known-hot working set:
            # promote fully so the warm window measures the resident
            # fast path, not a long cold-fault ramp.
            frags[f"ws{i}"].promote(trigger="prefetch")
        tiered = []
        for i in range(probes_tiered):
            if i % 50 == 25:
                mgr.pass_once()  # eviction pressure stays live
            tiered.append(probe(i))

        assert not wrong, f"WRONG ANSWERS: {wrong[:5]}"
        res_p50, res_p99 = statistics.median(resident), pct(resident,
                                                            0.99)
        t_p50, t_p99 = statistics.median(tiered), pct(tiered, 0.99)
        ratio = t_p99 / max(res_p99, 1e-9)
        oversub = total_bytes / max(budget, 1)
        assert oversub >= 10.0, f"index only {oversub:.1f}× budget"
        assert ratio <= 1.2, (
            f"hot working-set p99 {t_p99:.3f}ms is {ratio:.2f}× the"
            f" all-resident {res_p99:.3f}ms (target ≤ 1.2×)")
        st = mgr.state()
        table = {
            "total_bytes": total_bytes,
            "resident_budget_bytes": budget,
            "oversubscription": round(oversub, 2),
            "working_set_bytes": ws_bytes,
            "local_bytes_after_blob_push": local_bytes,
            "fragments_bulk": n_bulk,
            "fragments_ws": n_ws,
            "resident_p50_ms": round(res_p50, 4),
            "resident_p99_ms": round(res_p99, 4),
            "tiered_p50_ms": round(t_p50, 4),
            "tiered_p99_ms": round(t_p99, 4),
            "tiered_first_probe_ms": round(first_ms, 3),
            "p99_ratio": round(ratio, 3),
            "p99_ratio_target": 1.2,
            "p99_ratio_pass": ratio <= 1.2,
            "zero_wrong_answers": True,
            "samples_resident": len(resident),
            "samples_tiered": len(tiered),
            "blob_pushes": st["blobPushes"],
            "blob_fetches": st["blobFetches"],
            "promotions": st["promotions"],
            "demotions": st["demotions"],
        }
        _TIERED.update(table)
        emit("tiered_hot_ws_p99", t_p99, "ms", first_ms=round(
            first_ms, 3), **{k: v for k, v in table.items()
                             if k != "tiered_p99_ms"})
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "TIERED.json")
        with open(path, "w") as f:
            json.dump({"written_by": "benchmarks/suite.py"
                                     " config_tiered",
                       "scale": SCALE, **table}, f, indent=1)
    finally:
        ex.close()
        holder.close()
        td.cleanup()


def config_backup() -> None:
    """Disaster-recovery acceptance artifact (ISSUE 20), two legs:
    (a) backup-while-serving overhead — the bench-leg query p50 with
    a full cluster-backup coordinator pass IN FLIGHT for every
    on-sample (steady-state warm pool, the coordinator's default
    inter-fragment pacing) vs no backup, interleaved in alternating
    rounds (the config_obs_overhead pattern at a 100% backup duty
    cycle); acceptance: on/off p50 ratio ≤ 1.05.
    (b) restore wall time — the same archive restored into a FRESH
    empty node (schema recreate + digest-verified admission + WAL
    replay), with a correctness probe against the source's answers.
    Host path only (mesh off): the snapshot/push/verify machinery is
    the thing under test. Folds into MANIFEST.json ``backup`` for
    bench.py's line of record."""
    import statistics
    import tempfile
    import urllib.request

    saved_env = {k: os.environ.get(k)
                 for k in ("PILOSA_TPU_MESH", "PILOSA_TPU_WARMUP")}
    os.environ["PILOSA_TPU_MESH"] = "0"
    os.environ["PILOSA_TPU_WARMUP"] = "0"
    from pilosa_tpu import SLICE_WIDTH as W
    from pilosa_tpu.backup import archive as backup_archive
    from pilosa_tpu.backup import coordinator as backup_coord
    from pilosa_tpu.backup import restore as backup_restore
    from pilosa_tpu.cluster.client import Client as PClient
    from pilosa_tpu.server.server import Server
    from pilosa_tpu.utils.config import BackupConfig

    def post(host, path, body=b"{}"):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body, method="POST")
        return urllib.request.urlopen(req, timeout=30).read()

    def query(host, body):
        return json.loads(post(host, "/index/b/query",
                               body.encode()))["results"]

    n_slices = 8
    n_rows = 12
    n_bits = max(4000, int(20_000 * SCALE))
    servers = []
    td = tempfile.TemporaryDirectory()
    try:
        arch = os.path.join(td.name, "archive")
        bc = BackupConfig(archive=f"dir:{arch}", wal_interval=60.0)
        srv = Server(os.path.join(td.name, "src"),
                     host="127.0.0.1:0", anti_entropy_interval=0,
                     polling_interval=0, backup_config=bc)
        srv.open()
        servers.append(srv)
        post(srv.host, "/index/b")
        post(srv.host, "/index/b/frame/f")
        rng = np.random.default_rng(20)
        rows = rng.integers(0, n_rows, n_bits).astype(np.uint64)
        cols = rng.choice(n_slices * W, size=n_bits,
                          replace=False).astype(np.uint64)
        PClient(srv.host).import_arrays("b", "f", rows, cols)
        # Drain the import backlog out of the WAL archiver so every
        # backup pass pays the same (steady-state) archiving cost
        # instead of the first on-window eating the whole backlog.
        srv.wal_archiver.flush()
        want = [query(srv.host, f"Count(Bitmap(rowID={r},"
                                f' frame="f"))')[0]
                for r in range(n_rows)]

        children = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                             for r in range(n_rows))
        q = f"Union({children})"

        def run_group(samples, n=40):
            for _ in range(n):
                srv.executor._bitmap_results.clear()
                t0 = time.perf_counter()
                query(srv.host, q)
                samples.append(time.perf_counter() - t0)

        warm: list = []
        run_group(warm, 40)

        def backup_done(coord):
            return (coord.finished_at
                    or coord.phase in (backup_coord.PHASE_DONE,
                                       backup_coord.PHASE_FAILED))

        def wait_backup(coord):
            while not backup_done(coord):
                time.sleep(0.002)
            assert coord.phase == backup_coord.PHASE_DONE, coord.error

        # Warm the pool with one full pass so every measured pass is
        # steady state (snapshot + verify + exists-skip — the
        # economics every backup after the first actually has).
        wait_backup(srv.start_backup("full"))

        # The on-window is the production scenario itself: ONE backup
        # in flight (per-fragment WAL-barriered snapshot over HTTP,
        # footer verify, body digest, pool exists-checks, journal +
        # manifest fsyncs, with the coordinator's default
        # inter-fragment pacing — pacing IS the discipline that keeps
        # backup work out of serving's way) while the bench leg
        # queries. Every on-sample STARTS with the coordinator
        # active, so the on-window duty cycle is 100%, still far
        # above production (one backup per day, not back-to-back
        # rounds).
        def on_round(samples):
            coord = srv.start_backup("full")
            n = 0
            while not backup_done(coord):
                srv.executor._bitmap_results.clear()
                t0 = time.perf_counter()
                query(srv.host, q)
                samples.append(time.perf_counter() - t0)
                n += 1
            assert coord.phase == backup_coord.PHASE_DONE, coord.error
            return n

        on_samples: list = []
        off_samples: list = []
        passes = 0
        rounds = max(6, int(12 * SCALE))
        for _ in range(rounds):
            run_group(off_samples)
            on_round(on_samples)
            passes += 1
        assert len(on_samples) >= rounds, \
            "backup passes too short to sample under"
        on_p50 = statistics.median(on_samples)
        off_p50 = statistics.median(off_samples)
        ratio = on_p50 / max(off_p50, 1e-9)

        # Restore leg: a FRESH empty node, the real admission path
        # (re-crc every object, re-digest every body, WAL replay),
        # then the answers must match the source's.
        rest = Server(os.path.join(td.name, "restored"),
                      host="127.0.0.1:0", anti_entropy_interval=0,
                      polling_interval=0)
        rest.open()
        servers.append(rest)
        store = backup_archive.open_archive(f"dir:{arch}",
                                            rest.holder.path)
        t0 = time.perf_counter()
        summary = backup_restore.run_restore(rest.host, store)
        restore_wall = time.perf_counter() - t0
        got = [query(rest.host, f"Count(Bitmap(rowID={r},"
                                f' frame="f"))')[0]
               for r in range(n_rows)]
        assert got == want, "restored answers diverged from source"

        _BACKUP.update({
            "on_p50_ms": round(on_p50 * 1e3, 4),
            "off_p50_ms": round(off_p50 * 1e3, 4),
            "ratio": round(ratio, 4),
            "samples_on": len(on_samples),
            "samples_off": len(off_samples),
            "rounds": rounds,
            "backup_passes_during_on": passes,
            "restore_wall_s": round(restore_wall, 4),
            "restore_fragments": summary["fragments"],
            "restore_wal_only_fragments": summary["walOnlyFragments"],
            "restore_wal_ops_bytes": summary["walOpsBytes"],
            "restore_answers_match": True,
            "n_slices": n_slices, "n_rows": n_rows, "bits": n_bits,
            "query": f"Union over {n_rows} rows",
            "cadence_note":
                "every on-sample starts with a full coordinator pass"
                " in flight (steady-state warm pool, default"
                " inter-fragment pacing) — a 100% backup duty cycle,"
                " far above production's one pass per operator"
                " request",
            "device": USE_DEVICE,
            "target_ratio": 1.05,
        })
        emit("backup_overhead_on_p50", on_p50 * 1e3, "ms")
        emit("backup_overhead_off_p50", off_p50 * 1e3, "ms")
        emit("backup_overhead_ratio", ratio, "x_on_vs_off",
             target=1.05)
        emit("backup_restore_wall", restore_wall, "s",
             fragments=summary["fragments"])
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        td.cleanup()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main(argv: Optional[list] = None) -> None:
    """Full pass by default; ``suite.py <config_name>...`` runs just
    the named configs (e.g. ``suite.py config_write_path``) and folds
    their families into MANIFEST.json, carrying every other family
    forward from the prior full pass."""
    configs = (_measure_sync_floor,
               config1_fragment_intersect_count,
               config2_union_difference_1k_rows,
               config2_executor_wide_union,
               config3_topn_latency,
               config3_topn1000_end_to_end,
               config4_mesh_count_over_slices,
               config4_executor_routing,
               config5_cluster_topn,
               config5_executor_cluster_topn,
               config_topn1000_1024slices,
               config_residency_repeat_latency,
               config_host_write_and_import,
               config_http_pipelined_setbit,
               config_wire_import,
               config_write_path,
               config_distributed_topn,
               config_resize,
               config_tenant_isolation,
               config_tiered,
               config_obs_overhead,
               config_obs_history,
               config_scrub_overhead,
               config_planner,
               config_replay,
               config_backup,
               config_query_cost,
               config_container_mix,
               config_compile_stability,
               emit_compile_cache)
    names = [a for a in (sys.argv[1:] if argv is None else argv)
             if not a.startswith("-")]
    if names:
        table = {fn.__name__: fn for fn in configs}
        unknown = [n for n in names if n not in table]
        if unknown:
            raise SystemExit(
                f"unknown config(s) {unknown}; "
                f"choose from {sorted(table)}")
        fns = [table[n] for n in names]
    else:
        fns = list(configs)
    for fn in fns:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - report and continue
            emit(fn.__name__, -1, "error", error=str(e)[:200])
    try:
        write_manifest(partial=bool(names))
    except Exception as e:  # noqa: BLE001 - manifest must not kill runs
        print(f"manifest write failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()

