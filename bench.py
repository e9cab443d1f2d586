"""Benchmark of record: Intersect+Count throughput on 1 Gbit rows.

Metric (BASELINE.md): Intersect+Count row-ops/sec on 2^30-bit packed rows.
The device op is the fused count kernel ``sum(popcount(a & b), axis=-1)``
(pilosa_tpu.ops.kernels.op_count, which A/Bs the Pallas kernel against
XLA fusion on TPU) — the TPU replacement for the reference's amd64 POPCNT
assembly loop (roaring/assembly_amd64.s:60-77, `popcntAndSliceAsm`). The
baseline denominator is measured on this machine: the same algorithm
through our C++ host kernel (pilosa_tpu/native/bitops.cpp, `popcnt_and`),
which is the faithful stand-in for the reference's native path (no Go
toolchain in this image — BASELINE.md records that denominators must be
measured, not quoted).

No fallback: the number of record is a device number. The device
measurement runs in a subprocess with a bounded timeout and retries (a
chip belongs to one process at a time, and a hung backend init must not
hang the benchmark); if no attempt yields a result measured on a TPU,
the reason goes to stderr, nothing is printed on stdout, and the exit
code is 1.

Methodology: one host↔device sync costs far more than one kernel pass
over a row, so per-call timing would measure the sync, not the chip. We
instead batch K row pairs per call, chain N asynchronous dispatches, and
sync ONCE on the last output; the measured window then amortizes one
sync over K*N row-ops of real HBM traffic. Counts are verified against
the host kernel before timing.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Env knobs: PILOSA_BENCH_BITS (row width, default 2^30, must be < 2^31 —
per-row counts are int32), PILOSA_BENCH_ROWS (K, default 16 — 4 GB of
operands in HBM), PILOSA_BENCH_ITERS (chained dispatches, default 256;
measured asymptote — 512 gains <2%), PILOSA_BENCH_TRIALS (default 3,
median reported), PILOSA_BENCH_DEVICE_TIMEOUT (seconds per device
attempt, default 300 — covers the operand upload),
PILOSA_BENCH_DEVICE_TRIES (default 2).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_MARK = "DEVICE_RESULT:"
# jax device_kind strings of the chip whose HBM peak benchmarks/roofline.py
# divides by.
_V5E_KINDS = ("TPU v5 lite", "TPU v5e")


def _params():
    bits = int(os.environ.get("PILOSA_BENCH_BITS", str(1 << 30)))
    if bits >= 1 << 31:
        raise SystemExit("PILOSA_BENCH_BITS must be < 2^31 "
                         "(per-row device counts are int32)")
    if bits % 64:
        raise SystemExit("PILOSA_BENCH_BITS must be a multiple of 64")
    return (bits,
            int(os.environ.get("PILOSA_BENCH_ROWS", "16")),
            int(os.environ.get("PILOSA_BENCH_ITERS", "256")),
            int(os.environ.get("PILOSA_BENCH_TRIALS", "3")))


def _rows(bits, k_rows):
    rng = np.random.default_rng(42)
    n_words = bits // 32
    a = rng.integers(0, 2**32, size=(k_rows, n_words), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(k_rows, n_words), dtype=np.uint32)
    return a, b


def device_worker() -> None:
    """Measure the device kernel; prints one DEVICE_RESULT line.

    Runs in its own process so a hung/broken TPU backend init cannot take
    down the benchmark of record — the parent enforces the timeout.
    """
    t_begin = time.perf_counter()  # budget anchor: the parent's kill
    # deadline started when this process did

    import jax

    from pilosa_tpu.ops.kernels import op_count
    from pilosa_tpu.storage import native

    bits, k_rows, iters, trials = _params()
    a, b = _rows(bits, k_rows)

    da, db = jax.device_put(a), jax.device_put(b)
    got = np.asarray(op_count("and", da, db))  # warmup + verify
    want = [native.popcnt_and(a[i].view(np.uint64), b[i].view(np.uint64))
            for i in range(k_rows)]
    assert got.tolist() == want, (got.tolist(), want)
    del a, b, got, want  # parent holds nothing; don't double RSS here

    # Self-budget against the parent's kill deadline: probe one synced
    # dispatch (an upper bound per chained iter — it includes the sync)
    # and scale the chain down on platforms too slow for the full
    # default workload, so a DEVICE_RESULT always lands in time.
    t0 = time.perf_counter()
    np.asarray(op_count("and", da, db))
    probe_s = time.perf_counter() - t0
    # Budget = what's left of the parent's deadline (minus headroom for
    # the final sync + result print), not a fixed slice — setup (4 GB
    # generation, upload, warmup/verify) already consumed part of it.
    deadline = float(os.environ.get("PILOSA_BENCH_DEVICE_TIMEOUT", "300"))
    budget = max(5.0, 0.8 * deadline - (time.perf_counter() - t_begin))
    iters = max(1, min(iters, int(budget / max(probe_s, 1e-9) / trials)))

    best = []
    t_start = time.perf_counter()
    for _ in range(trials):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = op_count("and", da, db)
        np.asarray(out)  # single sync: flushes the whole chained queue
        best.append((time.perf_counter() - t0) / (k_rows * iters))
        if time.perf_counter() - t_start > budget:
            break  # report what we have instead of being killed
    device_s = sorted(best)[len(best) // 2]
    dev = jax.devices()[0]
    print(_MARK + json.dumps({"device_s": device_s,
                              "platform": dev.platform,
                              "device_kind": dev.device_kind}),
          flush=True)


def main() -> None:
    from pilosa_tpu.storage import native

    bits, k_rows, _, _ = _params()
    a, b = _rows(bits, k_rows)

    # --- host-native baseline (C++ popcount kernel, same rows).
    # Rows are viewed as u64 (bit-identical reinterpret, the kernel's
    # native word) so the timed region is the kernel alone. Median of
    # per-row times over two passes, mirroring the device side's
    # median-of-trials.
    a64, b64 = a.view(np.uint64), b.view(np.uint64)
    native.popcnt_and(a64[0], b64[0])  # warmup: page in + lib load
    host_times = []
    for _ in range(2):
        for i in range(k_rows):
            t0 = time.perf_counter()
            native.popcnt_and(a64[i], b64[i])
            host_times.append(time.perf_counter() - t0)
    host_s = sorted(host_times)[len(host_times) // 2]
    # Pin the denominator: this shared 1-core VM is noisy, and a freshly
    # measured host leg swung vs_baseline 2× between otherwise identical
    # runs. Persist the best (fastest) host measurement across rounds
    # and divide by that; both raw legs are reported alongside.
    host_pinned_s = _pin_host_baseline(bits, k_rows, host_s)
    # The device subprocess regenerates its own operands — drop ours
    # (4 GB at default ROWS) so peak host RSS doesn't double.
    del a, b, a64, b64

    # --- device path, in a bounded subprocess (see module docstring).
    timeout = int(os.environ.get("PILOSA_BENCH_DEVICE_TIMEOUT", "300"))
    tries = int(os.environ.get("PILOSA_BENCH_DEVICE_TRIES", "2"))
    device_s, platform, device_kind, err = None, None, None, None
    for attempt in range(tries):
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--device-worker"],
                timeout=timeout, capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            err = f"device attempt {attempt + 1} timed out after {timeout}s"
            print(err, file=sys.stderr)
            continue
        for line in proc.stdout.splitlines():
            if line.startswith(_MARK):
                res = json.loads(line[len(_MARK):])
                device_s, platform = res["device_s"], res["platform"]
                device_kind = res["device_kind"]
                break
        if device_s is not None:
            break
        err = (f"device attempt {attempt + 1} rc={proc.returncode}: "
               + proc.stderr.strip()[-800:])
        print(err, file=sys.stderr)
        if attempt + 1 < tries:
            time.sleep(5)

    if device_s is not None and platform != "tpu":
        device_s = None
        err = f"device worker ran on platform {platform!r}, not a TPU"
    if device_s is None:
        # The device worker's failure is this script's failure: a host
        # number under the metric's name would be a different metric.
        print("bench.py: no TPU result: "
              + (err or "device measurement unavailable"),
              file=sys.stderr)
        sys.exit(1)

    metric = f"intersect_count_{bits // (1 << 20)}Mbit_rows"
    line = {
        "metric": metric,
        "bits": bits,
        "value": round(1.0 / device_s, 3),
        "unit": "ops/sec",
        # vs_baseline uses the PINNED (best-ever, i.e. fastest) host
        # denominator — conservative on this noisy VM, where a slow
        # host run would otherwise inflate the same-run ratio. Both
        # ratios are published explicitly so the semantics are
        # unambiguous to downstream consumers.
        "vs_baseline": round(host_pinned_s / device_s, 3),
        "vs_baseline_pinned": round(host_pinned_s / device_s, 3),
        "vs_baseline_same_run": round(host_s / device_s, 3),
        "platform": platform,
        "device_kind": device_kind,
        "device_ops": round(1.0 / device_s, 3),
        "host_ops_this_run": round(1.0 / host_s, 3),
        "host_ops_pinned": round(1.0 / host_pinned_s, 3),
    }
    # Kernel-level Pallas-vs-XLA A/B record (benchmarks/pallas_ab.py)
    # and the write-path legs (suite._write_denominator) — the two
    # round-4 perf-proof artifacts, carried in the line of record.
    try:
        with open(os.path.join(os.path.dirname(_BASELINE_PATH),
                               "PALLAS_AB.json")) as f:
            ab = json.load(f)
            line["pallas_ab"] = {
                "pallas_wins": ab["pallas_wins"],
                "total": ab["total"],
                "serving_default": "xla"}
    except (OSError, ValueError, KeyError):
        pass
    try:
        with open(os.path.join(os.path.dirname(_BASELINE_PATH),
                               "WRITEPATH.json")) as f:
            line["write_path"] = json.load(f)
    except (OSError, ValueError, KeyError):
        pass
    # Compile-cache counters from the last suite pass
    # (benchmarks/MANIFEST.json, obs subsystem): hit/miss +
    # compile seconds, so the cold-compile tax rides the line of
    # record as a tracked number.
    try:
        with open(os.path.join(os.path.dirname(_BASELINE_PATH),
                               "MANIFEST.json")) as f:
            manifest = json.load(f)
        cc = manifest.get("compile_cache") or {}
        if "misses" in cc:
            line["compile_cache"] = {
                "hits": cc["hits"], "misses": cc["misses"],
                "compile_seconds": cc.get("compileSeconds")}
        # Restart-latency acceptance table (suite.
        # config_compile_stability): first-vs-warm device query
        # per slice config in FRESH processes sharing the
        # persistent XLA cache, plus the (bucket-bound) compile
        # count — the cold-query cost as a tracked number on the
        # line of record.
        cs = manifest.get("compile_stability") or {}
        if cs:
            line["compile_stability"] = {
                name: {"first_ms": rec.get("first_ms"),
                       "warm_p50_ms": rec.get("warm_p50_ms"),
                       "compile_count": rec.get("compile_count"),
                       "bucket": rec.get("bucket")}
                for name, rec in cs.items()}
        # Per-config cost ledgers (obs.accounting via
        # suite.config_query_cost): container-op mix, device
        # bytes, compile ms — the attribution numbers ride the
        # line of record next to the throughput they explain.
        qc = manifest.get("query_cost") or {}
        if qc:
            line["query_cost"] = {
                name: {"containerOps": sum(
                           (c.get("containerOps") or {}).values()),
                       "deviceBytes": c.get("deviceBytes", 0),
                       "compileMs": c.get("compileMs", 0.0)}
                for name, c in qc.items()}
        # Run-container mix on the run-heavy workload
        # (suite.config_container_mix): run-op share, resident
        # bytes vs the two-kind baseline, p50 ratio — ROADMAP
        # item 4's acceptance numbers on the line of record.
        cm = manifest.get("container_mix") or {}
        if cm.get("runs"):
            line["container_mix"] = {
                "run_op_share": cm["runs"].get("run_op_share"),
                "resident_bytes_ratio": cm.get(
                    "resident_bytes_ratio"),
                "p50_ratio": cm.get("p50_ratio"),
                "runs_p50_ms": cm["runs"].get("p50_ms"),
                "containers": cm["runs"].get("containers")}
        # Distributed fast paths (suite.config_distributed_topn →
        # DISTRIBUTED.json): 2-node TopN pushdown vs fan-out vs
        # single-node, and the generation-validated resident
        # chain — ROADMAP item 3's acceptance numbers on the line
        # of record.
        # Always-on observability overhead (suite.
        # config_obs_overhead): tail sampling + blackbox cadence
        # vs all-off, interleaved A/B — ISSUE 11's ≤2% acceptance
        # bound on the bench-leg p50, on the line of record.
        oo = manifest.get("obs_overhead") or {}
        if oo.get("ratio") is not None:
            line["obs_overhead"] = {
                "ratio": oo["ratio"],
                "on_p50_ms": oo.get("on_p50_ms"),
                "off_p50_ms": oo.get("off_p50_ms"),
                "target_ratio": oo.get("target_ratio")}
        # Metric-history + sentinel overhead (suite.
        # config_obs_history): whole-registry sampling + rule
        # evaluation vs all-off, interleaved A/B — ISSUE 13's
        # ≤2% acceptance bound, on the line of record.
        oh = manifest.get("obs_history") or {}
        if oh.get("ratio") is not None:
            line["obs_history"] = {
                "ratio": oh["ratio"],
                "on_p50_ms": oh.get("on_p50_ms"),
                "off_p50_ms": oh.get("off_p50_ms"),
                "target_ratio": oh.get("target_ratio")}
        # Background storage-scrub overhead (suite.
        # config_scrub_overhead): continuous re-verification
        # passes vs off, interleaved A/B — ISSUE 15's ≤2%
        # acceptance bound, on the line of record.
        so = manifest.get("scrub_overhead") or {}
        if so.get("ratio") is not None:
            line["scrub_overhead"] = {
                "ratio": so["ratio"],
                "on_p50_ms": so.get("on_p50_ms"),
                "off_p50_ms": so.get("off_p50_ms"),
                "target_ratio": so.get("target_ratio")}
        dt = manifest.get("distributed_topn") or {}
        if dt.get("topn_pushdown_p50_ms") is not None:
            line["distributed_topn"] = {
                "pushdown_p50_ms": dt["topn_pushdown_p50_ms"],
                "vs_single": dt.get("topn_vs_single"),
                "vs_fanout": dt.get("topn_vs_fanout"),
                "chain_hit_p50_ms": dt.get("chain_hit_p50_ms"),
                "chain_miss_ms": dt.get("chain_miss_ms"),
                "generations_rtt_ms": dt.get(
                    "generations_rtt_ms")}
        # Elastic resize under load (suite.config_resize →
        # RESIZE.json): resize duration + query p99 inflation
        # during the migration — ROADMAP item 5's acceptance
        # numbers on the line of record.
        rz = manifest.get("resize") or {}
        if rz.get("resize_duration_s") is not None:
            line["resize"] = {
                "duration_s": rz["resize_duration_s"],
                "p99_inflation": rz.get("p99_inflation"),
                "during_p99_ms": rz.get("during_p99_ms"),
                "baseline_p99_ms": rz.get("baseline_p99_ms"),
                "bytes_streamed": rz.get("bytes_streamed"),
                "slices_moved": rz.get("slices_moved"),
                "zero_wrong_answers": rz.get(
                    "zero_wrong_answers")}
        # Recorded-traffic replay (suite.config_replay →
        # REPLAY.json): offered-vs-achieved open-loop QPS of the
        # scaled captured workload, the self-shadow digest
        # verdict, and the capture-plane overhead guard — ISSUE
        # 19's acceptance numbers on the line of record.
        rp = manifest.get("replay") or {}
        if rp.get("offered_qps") is not None:
            shadow = rp.get("shadow") or {}
            line["replay"] = {
                "offered_qps": rp["offered_qps"],
                "achieved_qps": rp.get("achieved_qps"),
                "shed": rp.get("shed"),
                "shadow_self_mismatches": (shadow.get("self")
                                           or {}).get("mismatches"),
                "seeded_fault_detected": (
                    shadow.get("seeded_fault") or {}).get(
                        "detected")}
        co = manifest.get("capture_overhead") or {}
        if co.get("ratio") is not None:
            line["capture_overhead"] = {
                "ratio": co["ratio"],
                "on_p50_ms": co.get("on_p50_ms"),
                "off_p50_ms": co.get("off_p50_ms"),
                "target_ratio": co.get("target_ratio")}
        # Disaster recovery (suite.config_backup): the
        # backup-while-serving p50 overhead (continuous
        # coordinator passes vs off, interleaved; ISSUE 20's
        # ≤5% bound) and the digest-verified restore wall time
        # into a fresh node, on the line of record.
        bk = manifest.get("backup") or {}
        if bk.get("ratio") is not None:
            line["backup"] = {
                "ratio": bk["ratio"],
                "on_p50_ms": bk.get("on_p50_ms"),
                "off_p50_ms": bk.get("off_p50_ms"),
                "restore_wall_s": bk.get("restore_wall_s"),
                "restore_fragments": bk.get("restore_fragments"),
                "target_ratio": bk.get("target_ratio")}
    except (OSError, ValueError, KeyError):
        pass
    # Serving-quality artifact (sched subsystem): open-loop
    # latency under load vs the admission cap
    # (benchmarks/latency_under_load.py → LATENCY.json).
    try:
        with open(os.path.join(os.path.dirname(_BASELINE_PATH),
                               "LATENCY.json")) as f:
            lat = json.load(f)
            line["latency_under_load"] = {
                "below_cap_p99_ms": lat["below_cap"]["p99_ms"],
                "above_cap_p99_ms": lat["above_cap"]["p99_ms"],
                "above_cap_rejected": lat["above_cap"]["rejected"]}
    except (OSError, ValueError, KeyError):
        pass
    # Roofline accounting: effective HBM GB/s of THIS run's number
    # (arithmetic, a measurement) + the v5e-8 projections for
    # configs 4-5 (labeled projections, from recorded kernel times
    # — benchmarks/roofline.py). Only at the canonical 2^30-bit
    # shape: roofline.compute's bytes/op assumes it, and smaller
    # smoke shapes under-amortize the dispatch so their GB/s is not
    # the metric of record. Only on a v5e: the peak the fraction is
    # taken of is that chip's.
    if bits == (1 << 30) and device_kind in _V5E_KINDS:
        try:
            from benchmarks import roofline
            roof = roofline.compute(metric_ops_s=line["value"])
            line["effective_hbm_gbps"] = \
                roof["metric_of_record"]["effective_hbm_gbps"]
            line["hbm_fraction_of_v5e_peak"] = \
                roof["metric_of_record"]["fraction_of_v5e_peak"]
            roof_path = os.path.join(
                os.path.dirname(_BASELINE_PATH), "ROOFLINE.json")
            # Headline = the RECENT-RUN MEDIAN, not a historical
            # pin: the old best-run pin only expired after three
            # consecutive runs below 80% of it, so a sustained
            # ≤20% regression reported the stale peak forever
            # (ADVICE r5 #1). The median of the last 5 runs tracks
            # the current level while still shrugging off one
            # congested-slot outlier; the all-time max survives as
            # the separate best_observed field, and this run's raw
            # number always lands in latest_run_ops_per_s.
            try:
                with open(roof_path) as f:
                    prior = json.load(f)
            except (OSError, ValueError):
                prior = {}
            prior_best = max(
                prior.get("metric_of_record", {})
                .get("ops_per_s", 0),
                prior.get("best_observed", {}).get("ops_per_s", 0))
            # Every run that gets here ran on a v5e (no TPU result ->
            # exit 1 above), so every run folds into the history.
            recent = list(prior.get("recent_runs") or [])[-4:] + [
                line["value"]]
            # True median (even windows average the middle pair):
            # the upper median would bias the headline high right
            # after a regression, which is what this change exists
            # to stop.
            import statistics
            headline = float(statistics.median(recent))
            if headline != line["value"]:
                roof = roofline.compute(metric_ops_s=headline)
            roof["metric_of_record"]["kind"] = \
                "measurement (median of recent runs)"
            roof["metric_of_record"]["latest_run_ops_per_s"] = \
                line["value"]
            roof["metric_of_record"]["latest_run_platform"] = \
                line.get("platform")
            roof["best_observed"] = {
                "ops_per_s": round(max(prior_best, line["value"]), 3),
                "note": "historical max across rounds; not the"
                        " headline metric"}
            roof["recent_runs"] = recent
            # roofline.compute() builds the projections fresh with
            # the ASSUMED constants; roofline.py's own main()
            # stamps the measured values next to them — carry the
            # prior file's measured annotations forward instead of
            # erasing them on every bench pass (review finding:
            # this writer reverted the PR-4 'projections carry
            # measured constants' guarantee).
            if prior.get("measured_constants"):
                roof["measured_constants"] = \
                    prior["measured_constants"]
            for cfg, block in prior.items():
                if not (isinstance(block, dict)
                        and cfg in roof
                        and isinstance(block.get("assumptions"),
                                       dict)):
                    continue
                target = roof[cfg].setdefault("assumptions", {})
                for k, v in block["assumptions"].items():
                    if k.endswith("_measured") \
                            or k == "measured_platform":
                        target[k] = v
            with open(roof_path, "w") as f:
                json.dump(roof, f, indent=1)
        except Exception:  # noqa: BLE001 - must not kill the line
            pass
    print(json.dumps(line))


_BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "benchmarks", "HOST_BASELINE.json")


def _pin_host_baseline(bits: int, k_rows: int, host_s: float) -> float:
    """Best-of-all-rounds host seconds for this workload shape ON THIS
    MACHINE (the key carries the hostname — a faster rig's measurement
    must not poison vs_baseline for every other rig); updates the
    persisted record when this run's measurement is faster. One shared
    writer for HOST_BASELINE.json lives in benchmarks.pinning."""
    import platform

    from benchmarks.pinning import pin
    return pin(f"bits={bits},rows={k_rows},host={platform.node()}",
               "best_host_s", host_s, lambda new, old: new < old)


if __name__ == "__main__":
    if "--device-worker" in sys.argv[1:]:
        device_worker()
    elif "--latency-under-load" in sys.argv[1:]:
        # Open-loop latency-under-load benchmark (sched subsystem):
        # fixed arrival rates below/above the admission cap, p50/p99 +
        # rejected count into benchmarks/LATENCY.json + MANIFEST.json.
        from benchmarks import latency_under_load
        latency_under_load.main()
    else:
        main()
