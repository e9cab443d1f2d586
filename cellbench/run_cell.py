#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print the contract's result line.

    python3 cellbench/run_cell.py --workload <name> --seed <n>
                                  --seconds <s> --trace <0|1>

One server child is started, the configuration's data is made from the
seed and loaded over HTTP, the cell's own traffic warms the server, the
window is driven for ``--seconds``, the server is stopped, and the
window's answers are compared with the plain reference. Everything that
belongs to one configuration, one traffic mix or one per-layer metric is
a file found by the name ``BENCHMARK.json`` gives; this file names none.

Without a TPU backend, or with fewer chips than the cell asks for, the
exit code is not 0 and no result line is printed. The command line has
no size option; only ``run()``'s Python callers (the rehearsal among the
tests) may cut the slices and allow the CPU.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()    # set-up is counted from here

import argparse        # noqa: E402
import importlib       # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import sys             # noqa: E402
import tempfile        # noqa: E402
import threading       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cellbench.lib import check, e2e, loadgen, server      # noqa: E402
from cellbench.lib.data import Reference                   # noqa: E402
from cellbench.lib.traffic import Generator                # noqa: E402

HERE = os.path.join(ROOT, "cellbench")
TRACE_AT = 0.4          # the traced slice starts this far into the window
TRACE_SLICE_S = 3.0     # and lasts this long, or 0.3 of a shorter window


def _log(msg: str) -> None:
    sys.stderr.write(f"cellbench: [{time.perf_counter() - _T0:7.1f}s]"
                     f" {msg}\n")
    sys.stderr.flush()


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Run:
    """What one run gathered; the metric functions and readers read it."""

    def __init__(self):
        self.config = self.traffic = None
        self.records: list = []
        self.t_start = self.t_end = 0.0
        self.setup_s = 0.0
        self.load = None
        self.before = self.after = None     # surfaces, traced runs only
        self.trace = None                   # the reduction + t0, t1
        self.device: dict = {}
        self.peak = None
        self.wrong_answers = 0


def resolve(workload: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, its workload entry, the configuration, the
    traffic mix), each found by name."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise server.BenchFailure(f"no workload {workload!r} in"
                                  " BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = _json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _json(os.path.join(HERE, "traffic",
                                 cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def _listed(metrics: list[dict], workload: str) -> list[dict]:
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def _reader(metric: str):
    """The per-layer metric's reader, found through its file."""
    spec = _json(os.path.join(HERE, "metrics", metric + ".json"))
    return importlib.import_module(
        "cellbench.readers." + spec["reader"]).read


def _tenths(run_) -> str:
    """How steady the window was: its answers in ten equal parts."""
    span = (run_.t_end - run_.t_start) / 10 or 1.0
    parts: list[list] = [[] for _ in range(10)]
    for r in run_.records:
        parts[min(9, int((r.done - run_.t_start) / span))].append(r)
    out = []
    for recs in parts:
        lat = sorted(r.latency_s * 1e3 for r in recs)
        dev = sum(1 for r in recs if r.stats.get("devicePrograms"))
        out.append(f"{len(recs)}/{100 * dev // max(1, len(recs))}/"
                   f"{lat[len(lat) // 2] if lat else 0:.0f}")
    return " ".join(out)


def _trace_slice(ctl: str, trace_dir: str, at: float, seconds: float,
                 out: dict) -> None:
    time.sleep(max(0.0, at - time.time()))
    try:
        server.control(ctl, "start", "started", body=trace_dir)
        time.sleep(seconds)
        out.update(server.control(ctl, "stop", "done", timeout=300.0))
    except server.BenchFailure as e:
        out["error"] = str(e)


def run(workload: str, seed: int, seconds: float, trace: bool,
        n_slices: int | None = None, allow_cpu: bool = False,
        child: list[str] = server.CHILD) -> dict:
    """One run of a cell of BENCHMARK.json; see ``run_resolved``."""
    return run_resolved(*resolve(workload), seed, seconds, trace,
                        n_slices, allow_cpu, child)


def run_resolved(bench: dict, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool,
                 n_slices: int | None = None, allow_cpu: bool = False,
                 child: list[str] = server.CHILD) -> dict:
    """One run, every phase in order; returns the result line's object.
    Raises ``BenchFailure`` where no result may be printed."""
    workload = cell["name"]
    if n_slices is not None:
        config = dict(config, n_slices=n_slices)
    run_ = Run()
    run_.config, run_.traffic = config, traffic
    clients = int(traffic["arrival"]["clients"])
    if traffic["arrival"]["loop"] != "closed":
        raise server.BenchFailure("only the closed loop is written")

    with tempfile.TemporaryDirectory(prefix="cellbench_") as tmp:
        ctl = os.path.join(tmp, "ctl")
        log_path = os.path.join(tmp, "server.log")
        proc, host = server.start_server(os.path.join(tmp, "data"),
                                         log_path, ctl, child=child)
        http = server.Http(host, proc)
        tracer = None
        try:
            ref = Reference(seed, config)     # while the server starts
            _log(f"reference: {ref.n_rows} rows x {ref.n_slices} slices")
            build = server.wait_up(http).get("build") or {}
            _log(f"server up at {host}: {build}")
            if not allow_cpu:
                if build.get("backend") != "tpu":
                    raise server.BenchFailure(
                        f"backend is {build.get('backend')!r}, not 'tpu':"
                        " JAX found no accelerator")
                if (build.get("deviceCount") or 0) < cell["chips"]:
                    raise server.BenchFailure(
                        f"{build.get('deviceCount')} chip(s), the cell"
                        f" asks for {cell['chips']}")
            peaks = _json(os.path.join(HERE, "lib", "peaks.json"))
            run_.peak = peaks["devices"].get(build.get("deviceKind"))
            if run_.peak is None and not allow_cpu:
                raise server.BenchFailure(
                    f"device kind {build.get('deviceKind')!r} is not in"
                    " cellbench/lib/peaks.json")
            run_.load = server.load(http, ref, config)
            _log(f"loaded: {run_.load}")
            warm = server.wait_warmup(http)
            _log(f"warmup: {warm.get('state')} {warm.get('coverage')}")
            gen = Generator(traffic, config, seed)
            warm_recs, _, _ = loadgen.drive(
                host, config["index"], gen,
                int(traffic.get("warm_clients", clients)),
                requests=int(traffic["warm_requests"]))
            _log(f"warmed with {len(warm_recs)} requests,"
                 f" {sum(1 for r in warm_recs if not r.ok)} failed;"
                 " seconds to each 50th answer: " + " ".join(
                     f"{r.done - warm_recs[0].sent:.1f}"
                     for r in warm_recs[49::50]))
            if trace:
                run_.before = server.read_surfaces(http, time.time())
            run_.setup_s = time.perf_counter() - _T0

            slice_out: dict = {}
            if trace:
                tracer = threading.Thread(
                    target=_trace_slice, name="cellbench-trace",
                    args=(ctl, os.path.join(tmp, "trace"),
                          time.time() + TRACE_AT * seconds,
                          min(TRACE_SLICE_S, 0.3 * seconds), slice_out))
                tracer.start()
            run_.records, run_.t_start, run_.t_end = loadgen.drive(
                host, config["index"], gen, clients, seconds=seconds)
            _log(f"window: {len(run_.records)} requests in"
                 f" {run_.t_end - run_.t_start:.3f} s; compiled inside: "
                 + str([(r.op.pql, r.stats["compileMs"])
                        for r in run_.records
                        if r.stats.get("compileMs")][:5]))
            _log("window by tenths (answers, % by a device program,"
                 " median ms): " + _tenths(run_))
            if tracer is not None:
                tracer.join()
                run_.after = server.read_surfaces(http, run_.t_end)
                for name, s in (("before", run_.before),
                                ("after", run_.after)):
                    _log(f"{name}: " + json.dumps(
                        {k: s["vars"].get(k) for k in (
                            "deviceBlockCache", "costModelVetoes",
                            "deviceFallback", "costModel")}
                        | {"compileCache": (s["status"].get("runtime")
                                            or {}).get("compileCache")}))
            run_.device = server.control(ctl, "mem", "mem.json")
            earlier = [r for r in warm_recs if r.op.write]
            written = sorted({r.op.rows[0] for r in
                              earlier + run_.records if r.op.write})
            readback = {
                row: http.query(config["index"], "Count(Bitmap(frame="
                                f'"{config["frame"]}", rowID={row}))')[0]
                for row in written}
        finally:
            failed = sys.exc_info()[0] is not None
            if tracer is not None and tracer.is_alive():
                tracer.join()
            server.stop_server(proc)
            if failed:
                with open(log_path, "rb") as f:
                    tail = f.read()[-8000:].decode("utf-8", "replace")
                sys.stderr.write("--- server log (tail) ---\n" + tail
                                 + "\n--- end of server log ---\n")

        _log("data directory: %d bytes on disk" % sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, fs in os.walk(os.path.join(tmp, "data")) for f in fs))
        verdict = check.compare(ref, run_.records, earlier, readback,
                                int(traffic["check_sample"]), seed, _log)
        if trace:
            from cellbench.lib import trace_reduce
            if "error" in slice_out:
                raise server.BenchFailure("trace: " + slice_out["error"])
            path = trace_reduce.find_xplane(os.path.join(tmp, "trace"))
            if path is None:
                raise server.BenchFailure("the profiler wrote no trace")
            window = slice_out["traceStop"] - slice_out["traceStart"]
            run_.trace = trace_reduce.reduce_file(path, window)
            run_.trace.update(t0=slice_out["traceStart"],
                              t1=slice_out["traceStop"])
            _log(f"trace: {os.path.getsize(path)} bytes, busy"
                 f" {run_.trace['busy_s']:.4f} s of {window:.4f} s")

    compared = verdict["compared"]
    run_.wrong_answers = (compared["wrong_reads"]["value"]
                          + compared["wrong_writes"]["value"])
    metrics: dict = {}
    for m in _listed(bench["per_layer" if trace else "end_to_end"],
                     workload):
        value = (_reader(m["name"]) if trace
                 else e2e.METRICS[m["name"]])(run_)
        if value is not None:       # nothing to read: left out of the line
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(run_.device)
    result = {
        "correct": all(c["value"] <= c["limit"]
                       for c in compared.values()),
        "attempted": len(run_.records),
        "failed": sum(1 for r in run_.records if not r.ok),
        "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run_.trace["busy_s"]
        device["window_s"] = run_.trace["window_s"]
        result["breakdown"] = {"device_ops": run_.trace["device_ops"],
                               "idle_gaps": run_.trace["idle_gaps"]}
    result["workload"] = workload
    result["seed"] = seed
    result["checked"] = verdict["checked"]
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except server.BenchFailure as e:
        sys.stderr.write(f"cellbench: FAIL: {e}\n")
        return 1
    sys.stderr.write("cellbench: compared (value / limit): " + ", ".join(
        f"{k} {v['value']} / {v['limit']}"
        for k, v in result["compared"].items())
        + f"; checked {result['checked']}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
